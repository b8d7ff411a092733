"""Streaming data-path tests (VERDICT round 1, Missing #2).

The production transform path must be partition-at-a-time like the
reference's executor hot loop: at no point may the whole dataset's decoded
pixels coexist in host memory, and ``transformStream`` must be lazy
end-to-end (batch k yields before batch k+1 is read from disk).
"""

import os

import numpy as np
import pyarrow as pa
import pytest
from PIL import Image

from sparkdl_tpu.frame import DataFrame
from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.image.io import (iterFileBatches, iterImageBatches,
                                  readImages)
from sparkdl_tpu.models import get_model_spec
from sparkdl_tpu.transformers import (DeepImageFeaturizer, PipelineModel,
                                      TFImageTransformer)
from sparkdl_tpu.transformers import named_image as ni


@pytest.fixture()
def many_images(tmp_path):
    """40 tiny JPEGs — 10x the device batch used below — plus 2 bad files."""
    rng = np.random.default_rng(7)
    d = tmp_path / "imgs"
    d.mkdir()
    for i in range(40):
        arr = (rng.random((24, 24, 3)) * 255).astype("uint8")
        Image.fromarray(arr).save(d / f"img_{i:03d}.jpg", quality=92)
    (d / "bad_a.jpg").write_bytes(b"nope")
    (d / "bad_b.jpg").write_bytes(b"also nope")
    return str(d)


@pytest.fixture()
def fake_resnet(monkeypatch):
    class _Tiny:
        feature_size = 2048

        def apply(self, variables, x, train=False, features=False):
            import jax.numpy as jnp

            m = jnp.mean(x, axis=(1, 2, 3))
            dim = self.feature_size if features else 1000
            return m[:, None] * 0.01 + jnp.arange(
                dim, dtype=jnp.float32)[None, :] * 1e-4

    spec = get_model_spec("ResNet50")
    monkeypatch.setitem(ni._MODEL_CACHE, "ResNet50", (_Tiny(), {}))
    ni._ENGINE_CACHE.clear()
    yield spec
    ni._ENGINE_CACHE.clear()


def test_featurizer_never_materializes_full_decoded_batch(
        fake_resnet, many_images, monkeypatch):
    """Decode calls must each cover at most one device batch of rows even
    when the frame is 10x larger (the round-1 path decoded ALL rows into
    one [N,H,W,3] array)."""
    df = readImages(many_images)
    assert len(df) == 42

    sizes = []
    orig = ni.arrowStructsToBatch

    def spy(column, h, w, **kw):
        sizes.append(len(column))
        return orig(column, h, w, **kw)

    monkeypatch.setattr(ni, "arrowStructsToBatch", spy)
    ft = DeepImageFeaturizer(inputCol="image", outputCol="features",
                             modelName="ResNet50", batchSize=4)
    rows = ft.transform(df).collect()
    assert len(rows) == 42
    assert sum(1 for r in rows if r["features"] is None) == 2
    # 8-device mesh rounds batchSize=4 up to 8; decode granularity follows.
    assert sizes, "streaming decode was never exercised"
    assert max(sizes) <= 8, sizes
    # the arrow packer sees every row of each chunk (nulls masked inside)
    assert sum(sizes) == 42


def test_streaming_matches_materialized_path(fake_resnet, many_images):
    """Chunked streaming must produce exactly the numbers a single
    whole-table pass produces (row order and null alignment included)."""
    df = readImages(many_images)
    ft = DeepImageFeaturizer(inputCol="image", outputCol="features",
                             modelName="ResNet50", batchSize=16)
    out1 = [r["features"] for r in ft.transform(df).collect()]
    out2 = [r["features"] for r in
            ft.transform(df.repartition(7)).collect()]
    assert len(out1) == len(out2) == 42
    for a, b in zip(out1, out2):
        if a is None:
            assert b is None
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5)


def test_iter_file_batches_is_lazy(many_images, monkeypatch):
    """Bytes must be read per batch, not all up front."""
    import builtins

    opened = []
    orig_open = builtins.open

    def spy_open(path, *a, **kw):
        if str(path).endswith(".jpg"):
            opened.append(str(path))
        return orig_open(path, *a, **kw)

    monkeypatch.setattr(builtins, "open", spy_open)
    it = iterFileBatches(many_images, batch_size=10)
    first = next(it)
    assert first.num_rows == 10
    assert len(opened) == 10  # only the first batch touched disk
    rest = list(it)
    assert sum(rb.num_rows for rb in rest) == 32
    assert len(opened) == 42


def test_transform_stream_is_lazy_end_to_end(fake_resnet, many_images):
    """Batch k's output must be yielded before batch k+1 is decoded."""
    events = []

    def source():
        for i, rb in enumerate(iterImageBatches(many_images, batch_size=8)):
            events.append(f"read:{i}")
            yield rb

    ft = DeepImageFeaturizer(inputCol="image", outputCol="features",
                             modelName="ResNet50", batchSize=8)
    stream = ft.transformStream(source())
    first = next(stream)
    events.append("first-output")
    assert first.num_rows == 8
    assert events.index("first-output") <= 2, events  # not all 6 reads first
    total = first.num_rows + sum(rb.num_rows for rb in stream)
    assert total == 42


def test_pipeline_transform_stream_chains_lazily(fake_resnet, many_images):
    mf = ModelFunction(fn=lambda v, x: x.astype("float32").mean(
        axis=(1, 2)), variables={})
    t1 = TFImageTransformer(inputCol="image", outputCol="mean_bgr",
                            modelFunction=mf, inputSize=[16, 16],
                            outputMode="vector", batchSize=8)
    ft = DeepImageFeaturizer(inputCol="image", outputCol="features",
                             modelName="ResNet50", batchSize=8)
    pm = PipelineModel([t1, ft])
    out_batches = list(pm.transformStream(
        iterImageBatches(many_images, batch_size=8)))
    table = pa.Table.from_batches(out_batches)
    assert table.num_rows == 42
    assert set(table.column_names) >= {"image", "mean_bgr", "features"}


def test_image_file_transformer_streams(many_images, monkeypatch):
    """URI-column path: files are loaded per chunk, not all at once."""
    from sparkdl_tpu.transformers.image_file import ImageFileTransformer

    paths = sorted(
        os.path.join(many_images, f) for f in os.listdir(many_images))
    df = DataFrame({"uri": paths})

    chunk_sizes = []

    def loader(uri):
        img = Image.open(uri).convert("RGB").resize((16, 16))
        return np.asarray(img, dtype=np.float32)

    mf = ModelFunction(fn=lambda v, x: x.mean(axis=(1, 2)), variables={})
    t = ImageFileTransformer(inputCol="uri", outputCol="out",
                             modelFunction=mf, imageLoader=loader,
                             batchSize=8)
    orig = t._loaded_chunks

    def spy(dataset, chunk_rows, valid_idx):
        for chunk in orig(dataset, chunk_rows, valid_idx):
            chunk_sizes.append(chunk.shape[0])
            yield chunk

    monkeypatch.setattr(t, "_loaded_chunks", spy)
    rows = t.transform(df).collect()
    assert len(rows) == 42
    assert sum(1 for r in rows if r["out"] is None) == 2  # bad files
    assert max(chunk_sizes) <= 8


def _featurizer_job(many_images):
    df = readImages(many_images)
    ft = DeepImageFeaturizer(inputCol="image", outputCol="features",
                             modelName="ResNet50", batchSize=8)
    return ft, "_decoded_chunks", df, "features"


def _file_transformer_job(many_images):
    from sparkdl_tpu.transformers.image_file import ImageFileTransformer

    def loader(uri):
        img = Image.open(uri).convert("RGB").resize((16, 16))
        return np.asarray(img, dtype=np.float32)

    paths = sorted(
        os.path.join(many_images, f) for f in os.listdir(many_images))
    t = ImageFileTransformer(
        inputCol="uri", outputCol="out", imageLoader=loader, batchSize=8,
        modelFunction=ModelFunction(fn=lambda v, x: x.mean(axis=(1, 2)),
                                    variables={}))
    return t, "_loaded_chunks", DataFrame({"uri": paths}), "out"


@pytest.mark.parametrize("make_job", [_featurizer_job,
                                      _file_transformer_job],
                         ids=["DeepImageFeaturizer", "ImageFileTransformer"])
def test_decode_runs_ahead_on_the_runners_prepare_thread(
        fake_resnet, many_images, monkeypatch, make_job):
    """The decode generator is handed to ``map_batches`` as it is: after
    the chunk that proves there is work (pulled by the caller, before an
    engine exists) the runner's prepare thread pulls it, and chunk k+1 is
    decoded while chunk k is still on its way out — the gather of chunk k
    WAITS here for that, so a decode that ran only when the consumer
    asked for more would time out."""
    import threading

    from sparkdl_tpu.parallel.engine import InferenceEngine

    stage, chunks_attr, df, out_col = make_job(many_images)
    pulled_on, done = [], []
    ahead = threading.Condition()
    decode = getattr(stage, chunks_attr)

    def spy(*args, **kwargs):
        for chunk in decode(*args, **kwargs):
            with ahead:
                pulled_on.append(threading.current_thread().name)
                ahead.notify_all()
            yield chunk
        with ahead:
            done.append(True)
            ahead.notify_all()

    monkeypatch.setattr(stage, chunks_attr, spy)
    gathered, late = [], []
    force = InferenceEngine._force_part

    def gather_after_the_next_decode(self, n, out, block=None):
        k = len(gathered)
        with ahead:
            if not ahead.wait_for(
                    lambda: len(pulled_on) > k + 1 or done, timeout=10.0):
                late.append(k)
        gathered.append(k)
        return force(self, n, out, block)

    monkeypatch.setattr(InferenceEngine, "_force_part",
                        gather_after_the_next_decode)
    rows = stage.transform(df).collect()
    assert sum(1 for r in rows if r[out_col] is None) == 2  # bad files
    assert len(gathered) == len(pulled_on) == 6     # 42 rows in chunks of 8
    assert late == [], f"decode never ran ahead of the gather of {late}"
    assert pulled_on[0] == threading.current_thread().name
    assert set(pulled_on[1:]) == {"sparkdl-pipeline-prepare"}
