"""Native host-IO core tests.

Parity is tolerance-based vs PIL (the reference tolerated cross-backend
resize differences between java.awt and TF bilinear the same way); failure
handling must preserve the drop-to-null contract; the PIL fallback path must
produce identical-shape results when the native core is unavailable.
"""

import io as _io
import os
import shutil

import numpy as np
import pytest

import sparkdl_tpu.native as native
from sparkdl_tpu.image.io import decodeResizeBatch, filesToModelBatch


def _jpeg(arr, quality=92):
    from PIL import Image

    buf = _io.BytesIO()
    Image.fromarray(arr).save(buf, "JPEG", quality=quality)
    return buf.getvalue()


def _png(arr):
    from PIL import Image

    buf = _io.BytesIO()
    Image.fromarray(arr).save(buf, "PNG")
    return buf.getvalue()


@pytest.fixture(scope="module")
def blobs(rng=None):
    rng = np.random.default_rng(9)
    imgs = [(rng.random((h, w, 3)) * 255).astype(np.uint8)
            for h, w in [(80, 100), (64, 64), (120, 90)]]
    return imgs, [_jpeg(imgs[0]), _jpeg(imgs[1]), _png(imgs[2]), b"garbage"]


needs_native = pytest.mark.skipif(not native.native_available(),
                                  reason="native core unavailable")


@needs_native
def test_native_decode_resize_parity(blobs):
    from PIL import Image

    imgs, encoded = blobs
    out, ok = native.decode_resize_batch(encoded, 48, 56)
    assert out.shape == (4, 48, 56, 3) and out.dtype == np.uint8
    assert ok.tolist() == [True, True, True, False]
    assert not out[3].any()  # failed row zeroed
    for i, blob in enumerate(encoded[:3]):
        ref = np.asarray(Image.open(_io.BytesIO(blob)).convert("RGB")
                         .resize((56, 48), Image.BILINEAR))
        diff = np.abs(out[i].astype(int) - ref.astype(int))
        assert diff.mean() < 8.0, f"img {i} mean diff {diff.mean()}"


@needs_native
def test_native_resize_batch(blobs):
    imgs, _ = blobs
    out = native.resize_batch_rgb(imgs, 32, 32)
    assert out.shape == (3, 32, 32, 3)
    # identity resize is exact
    same = native.resize_batch_rgb([imgs[1]], 64, 64)
    np.testing.assert_array_equal(same[0], imgs[1])
    with pytest.raises(ValueError, match="uint8"):
        native.resize_batch_rgb([np.zeros((4, 4), np.uint8)], 8, 8)


def test_decode_resize_batch_api(blobs):
    """Public fused API works regardless of which backend serves it."""
    _, encoded = blobs
    out, ok = decodeResizeBatch(encoded, 40, 40)
    assert out.shape == (4, 40, 40, 3)
    assert ok.tolist() == [True, True, True, False]


def test_decode_resize_batch_pil_fallback(blobs, monkeypatch):
    """Force the PIL path and compare against the default path's shape and
    mask behavior."""
    _, encoded = blobs
    monkeypatch.setattr(
        "sparkdl_tpu.image.io._native_io_preferred", lambda: False)
    out, ok = decodeResizeBatch(encoded, 40, 40)
    assert out.shape == (4, 40, 40, 3)
    assert ok.tolist() == [True, True, True, False]


def test_files_to_model_batch(fixture_images):
    paths = fixture_images["paths"] + [fixture_images["bad"], "/nope.jpg"]
    out, ok = filesToModelBatch(paths, 32, 32)
    assert out.shape == (len(paths), 32, 32, 3)
    assert ok.tolist() == [True] * 3 + [False, False]


# -- the loader: which library a process gets ------------------------------

@pytest.fixture
def fresh_loader(monkeypatch, tmp_path):
    """The loader over its own copy of the source and an empty build
    dir, with no memory of an earlier load; returns the source path and
    the list of library paths ``_build`` was asked for."""
    src = tmp_path / "sparkdl_native.cpp"
    shutil.copy(native._SRC, src)
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_LIB_DIR", str(tmp_path / "_build"))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_lib_info", None)
    monkeypatch.delenv("SPARKDL_TPU_DISABLE_NATIVE", raising=False)
    built = []
    real_build = native._build
    monkeypatch.setattr(native, "_build",
                        lambda path: (built.append(path),
                                      real_build(path))[1])

    def load():
        monkeypatch.setattr(native, "_load_attempted", False)
        return native.library_info()

    return src, built, load


@needs_native
def test_loader_rebuilds_on_source_content_not_mtime(fresh_loader):
    """A copied tree keeps no timestamps, so the rebuild is keyed on what
    the source SAYS: the library's name carries the source's sha256.  An
    unchanged source never rebuilds, however old the library looks; an
    edited source never loads the library built from the old text,
    however new that one looks."""
    src, built, load = fresh_loader
    first = load()
    assert first["decoder"] == "native" and built == [first["path"]]
    assert first["source_sha256"][:16] in os.path.basename(first["path"])
    # the library looks older than its source: still the right one
    os.utime(first["path"], (1, 1))
    assert load() == first and len(built) == 1
    # the source changes, and the stale library looks NEWER than it
    with open(src, "a") as fh:
        fh.write("\n// a later revision\n")
    os.utime(src, (2, 2))
    os.utime(first["path"], None)
    second = load()
    assert second["decoder"] == "native"
    assert second["path"] != first["path"] and built[-1] == second["path"]
    assert second["source_sha256"] != first["source_sha256"]
    # no half-written build product is left beside the libraries
    assert sorted(os.listdir(os.path.dirname(second["path"]))) == sorted(
        os.path.basename(p) for p in (first["path"], second["path"]))


def test_loader_reports_pil_when_the_core_cannot_build(fresh_loader,
                                                       monkeypatch):
    """The fallback stays, but it is visible: a toolchain failure is a
    ``{"decoder": "pil"}`` answer, which chip_smoke.py treats as a
    failed phase."""
    _, built, load = fresh_loader
    monkeypatch.setattr(native, "_build",
                        lambda path: (built.append(path), False)[1])
    assert load() == {"decoder": "pil"}
    assert len(built) == 1
