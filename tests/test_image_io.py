"""Image schema & I/O tests — round-trip array<->struct, decode of real
fixture images, malformed input handling (reference C2 test strategy)."""

import threading

import numpy as np
import pyarrow as pa
import pytest

from sparkdl_tpu import obs
from sparkdl_tpu.image import io as image_io
from sparkdl_tpu.image import (
    PIL_decode,
    createResizeImageUDF,
    filesToDF,
    imageArrayToStruct,
    imageSchema,
    imageStructToArray,
    imageTypeByMode,
    imageTypeByName,
    ocvTypes,
    readImages,
    readImagesWithCustomFn,
    resizeImage,
)


def test_ocv_mode_table():
    assert ocvTypes["CV_8UC3"] == 16
    assert imageTypeByName("CV_8UC3").dtype == "uint8"
    assert imageTypeByMode(21).name == "CV_32FC3"
    with pytest.raises(ValueError):
        imageTypeByMode(99)


@pytest.mark.parametrize("dtype,channels", [("uint8", 1), ("uint8", 3),
                                            ("uint8", 4), ("float32", 3)])
def test_array_struct_roundtrip(rng, dtype, channels):
    if dtype == "uint8":
        arr = (rng.random((7, 5, channels)) * 255).astype(np.uint8)
    else:
        arr = rng.random((7, 5, channels)).astype(np.float32)
    s = imageArrayToStruct(arr, origin="mem://x")
    assert s["height"] == 7 and s["width"] == 5 and s["nChannels"] == channels
    back = imageStructToArray(s)
    np.testing.assert_array_equal(arr, back)


def test_struct_validation():
    arr = np.zeros((4, 4, 3), dtype=np.uint8)
    s = imageArrayToStruct(arr)
    s["nChannels"] = 4
    with pytest.raises(ValueError):
        imageStructToArray(s)


def test_decode_real_jpeg_is_bgr(fixture_images):
    with open(fixture_images["paths"][0], "rb") as f:
        raw = f.read()
    bgr = PIL_decode(raw)
    assert bgr is not None and bgr.ndim == 3 and bgr.shape[2] == 3
    from PIL import Image
    rgb = np.asarray(Image.open(fixture_images["paths"][0]).convert("RGB"))
    np.testing.assert_array_equal(bgr[:, :, ::-1], rgb)


def test_decode_failure_returns_none(fixture_images):
    with open(fixture_images["bad"], "rb") as f:
        assert PIL_decode(f.read()) is None


def test_read_images_dataframe(fixture_images):
    df = readImages(fixture_images["dir"])
    assert df.count() == 4  # 3 good + 1 bad (null row kept)
    rows = df.collect()
    nulls = [r for r in rows if r["image"] is None]
    assert len(nulls) == 1
    good = [r for r in rows if r["image"] is not None]
    for r in good:
        arr = imageStructToArray(r["image"])
        assert arr.dtype == np.uint8 and arr.shape[2] == 3


def _jpeg_dir(path, rng, files, corrupt=None):
    """``files`` tiny JPEGs of mixed sizes under ``path``, the one at index
    ``corrupt`` (in file-name order) not an image -> the sorted paths."""
    from PIL import Image

    paths = []
    for i in range(files):
        p = path / f"img_{i:04d}.jpg"
        if i == corrupt:
            p.write_bytes(b"not a jpeg, row %d" % i)
        else:
            arr = (rng.random((9 + i % 5, 11 + i % 3, 3)) * 255
                   ).astype("uint8")
            Image.fromarray(arr).save(p, quality=90)
        paths.append(str(p))
    return paths


def _read(path):
    with open(path, "rb") as fh:
        return fh.read()


def _table_of(paths, arrays):
    """What ``readImages`` builds from decoded ``arrays`` (None: a file
    that did not decode), in record batches of 256 rows as it does."""
    batches = []
    for off in range(0, len(paths), 256):
        structs = [
            None if arr is None else imageArrayToStruct(arr, origin=f)
            for f, arr in zip(paths[off:off + 256], arrays[off:off + 256])]
        batches.append(pa.record_batch(
            {"image": pa.array(structs, type=imageSchema)}))
    return pa.Table.from_batches(
        batches, schema=pa.schema([pa.field("image", imageSchema)]))


@pytest.fixture()
def tracer():
    yield obs.configure(enabled=True)
    obs.configure_from_env()


def _spans(tracer, name):
    return [s for s in tracer.snapshot() if s["name"] == name]


def _decode_span(tracer):
    (span,) = _spans(tracer, "io.decode")
    return span


def _ipc_bytes(table):
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, table.schema) as writer:
        writer.write_table(table)
    return sink.getvalue().to_pybytes()


def _partitions_of(table, n):
    """``table`` copied into ``n`` chunks as equal as whole rows allow,
    the larger first (``None``: left in its record batches of 256)."""
    if n is None:
        return table
    rows = table.num_rows
    n = max(1, min(n, rows))
    sizes = [rows // n + (i < rows % n) for i in range(n)]
    whole = table.combine_chunks()
    return pa.concat_tables([whole.slice(sum(sizes[:i]), size)
                             for i, size in enumerate(sizes)])


def _chunk_sizes(table):
    return [len(chunk) for chunk in table.column("image").chunks]


def _data_bytes(table):
    return [None if row is None else row["data"]
            for row in table.column("image").to_pylist()]


def _assert_same_frame(got, want):
    """``got`` is ``want`` by value, null for null and chunk for chunk."""
    assert got.schema == want.schema
    assert got.equals(want)
    assert _data_bytes(got) == _data_bytes(want)
    assert got.column("image").null_count == want.column("image").null_count
    assert _chunk_sizes(got) == _chunk_sizes(want)


@pytest.mark.parametrize("partitions", [None, 1, 3, 7])
@pytest.mark.parametrize("files,corrupt", [
    (1, None), (3, None), (12, None), (12, 6), (260, 257)],
    ids=["1_serial", "3_serial", "12_pooled", "12_one_corrupt",
         "260_two_record_batches"])
def test_read_images_table_is_the_serial_decodes_table(tmp_path, rng,
                                                       files, corrupt,
                                                       partitions):
    """Pooled or not, built partition by partition or in record batches
    of 256, ``readImages`` gives byte for byte the table that
    ``imageArrayToStruct`` + ``pa.array`` give from ``[PIL_decode(b) for
    b in blobs]`` in file-name order, the null struct at the corrupt
    file's row — down to the Arrow IPC stream, so to which rows and
    which chunks carry a validity bitmap."""
    paths = _jpeg_dir(tmp_path, rng, files, corrupt)
    serial = [PIL_decode(_read(p)) for p in paths]
    assert [i for i, arr in enumerate(serial) if arr is None] == (
        [] if corrupt is None else [corrupt])
    want = _partitions_of(_table_of(paths, serial), partitions)
    got = readImages(str(tmp_path), numPartitions=partitions).table
    _assert_same_frame(got, want)
    assert got.column("image").null_count == (corrupt is not None)
    if partitions in (None, 1):     # a slice's IPC stream is its own
        assert _ipc_bytes(got) == _ipc_bytes(want)


def _png(path, arr, mode):
    from PIL import Image

    Image.fromarray(arr, mode).save(path)


GREY = np.arange(6 * 7, dtype=np.uint8).reshape(6, 7)


@pytest.mark.parametrize("decoded", [
    lambda i: GREY + i,
    lambda i: (GREY + i)[:, :, None],
    lambda i: np.stack([GREY + i] * 4, axis=2),
    lambda i: np.stack([GREY + i] * 3, axis=2).astype(np.float32) / 7,
    lambda i: np.stack([GREY + i] * 3, axis=2)[:, ::-1],
    lambda i: (np.full((3 + i, 2, 1), i, np.uint8) if i % 2
               else np.full((2, 3 + i, 3), i / 3, np.float32)),
], ids=["grey_2d", "grey_1_channel", "bgra", "float32", "not_contiguous",
        "sizes_and_dtypes_mixed_in_one_batch"])
@pytest.mark.parametrize("partitions", [None, 2])
def test_a_callers_arrays_are_copied_once_into_the_buffer(
        tmp_path, rng, tracer, decoded, partitions):
    """Whatever supported dtype and channel count a caller's ``decode_f``
    returns, the frame is the table of ``imageArrayToStruct`` +
    ``pa.array`` — built from buffers (``direct_rows``), a ``None`` a
    null struct in its place."""
    paths = _jpeg_dir(tmp_path, rng, 6)
    arrays = [None if i == 4 else decoded(i) for i in range(6)]
    by_blob = {_read(p): arr for p, arr in zip(paths, arrays)}
    got = readImagesWithCustomFn(str(tmp_path), by_blob.__getitem__,
                                 numPartitions=partitions).table
    _assert_same_frame(
        got, _partitions_of(_table_of(paths, arrays), partitions))
    (to_arrow,) = _spans(tracer, "io.to_arrow")
    assert to_arrow["attrs"]["direct_rows"] == to_arrow["attrs"]["rows"] == 6
    # one record batch of 6 rows is sliced in two where it lies
    (repartition,) = _spans(tracer, "io.repartition")
    assert repartition["attrs"]["copied_bytes"] == 0


@pytest.mark.parametrize("partitions,copied", [(None, False), (1, True),
                                                (2, True)])
def test_a_callers_decoder_leaves_the_copy_to_repartition(
        tmp_path, rng, tracer, partitions, copied):
    """The sizes a caller's ``decode_f`` will give are not known before
    it ran: its frame is built in record batches of 256 files, and where
    those are not the partitions asked for, ``repartition`` joins them —
    every byte of the frame copied once more, and counted."""
    paths = _jpeg_dir(tmp_path, rng, 260, corrupt=3)

    def decode_f(blob):
        return PIL_decode(blob)

    df = readImagesWithCustomFn(str(tmp_path), decode_f,
                                numPartitions=partitions)
    want = _table_of(paths, [PIL_decode(_read(p)) for p in paths])
    _assert_same_frame(df.table, _partitions_of(want, partitions))
    (repartition,) = _spans(tracer, "io.repartition")
    assert repartition["attrs"]["copied_bytes"] == (
        want.nbytes if copied else 0)
    assert [s["attrs"]["direct_rows"]
            for s in _spans(tracer, "io.to_arrow")] == [256, 4]


def test_the_packages_decoder_turns_grey_and_rgba_files_to_bgr(tmp_path,
                                                               rng):
    """Files that are not RGB (a grey JPEG, an RGBA and a palette PNG) go
    through ``convert('RGB')`` on the pool as in ``PIL_decode``."""
    from PIL import Image

    paths = _jpeg_dir(tmp_path, rng, 4)
    Image.fromarray(GREY * 5).save(tmp_path / "img_0004.jpg")
    _png(tmp_path / "img_0005.png", np.stack([GREY] * 4, axis=2), "RGBA")
    Image.fromarray(GREY % 4).convert("P").save(tmp_path / "img_0006.png")
    paths += [str(tmp_path / n) for n in
              ("img_0004.jpg", "img_0005.png", "img_0006.png")]
    serial = [PIL_decode(_read(p)) for p in paths]
    assert all(arr.shape[2] == 3 for arr in serial)
    got = readImages(str(tmp_path), numPartitions=1).table
    _assert_same_frame(got, _table_of(paths, serial))
    assert _ipc_bytes(got) == _ipc_bytes(_table_of(paths, serial))


@pytest.mark.parametrize("some_arrays", [False, True],
                         ids=["dicts", "dicts_and_arrays"])
def test_a_record_batch_of_struct_dicts_goes_the_old_road(
        tmp_path, rng, tracer, some_arrays):
    paths = _jpeg_dir(tmp_path, rng, 6, corrupt=2)
    serial = [PIL_decode(_read(p)) for p in paths]

    def decode_f(blob):
        i = [_read(p) for p in paths].index(blob)
        if serial[i] is None or (some_arrays and i % 2):
            return serial[i]
        return imageArrayToStruct(serial[i], origin=paths[i])

    got = readImagesWithCustomFn(str(tmp_path), decode_f).table
    _assert_same_frame(got, _table_of(paths, serial))
    (to_arrow,) = _spans(tracer, "io.to_arrow")
    assert (to_arrow["attrs"]["rows"],
            to_arrow["attrs"]["direct_rows"]) == (6, 0)


def test_a_row_that_fails_after_its_header_is_a_null_in_its_place(
        tmp_path, rng):
    """A truncated JPEG: the header gives its size, the decoder fails.
    Its slot stays in the buffer under a null struct; every other row
    reads as before, through ``arrowStructsToBatch`` too."""
    from sparkdl_tpu.image import arrowStructsToBatch

    from PIL import Image

    paths = _jpeg_dir(tmp_path, rng, 8)
    Image.fromarray((rng.random((64, 64, 3)) * 255).astype("uint8")).save(
        paths[3], quality=90)
    whole = _read(paths[3])
    with open(paths[3], "wb") as fh:
        fh.write(whole[:len(whole) * 2 // 3])
    assert image_io._open_image(_read(paths[3])) is not None
    serial = [PIL_decode(_read(p)) for p in paths]
    assert [i for i, arr in enumerate(serial) if arr is None] == [3]
    for partitions in (None, 1, 2):
        got = readImages(str(tmp_path), numPartitions=partitions).table
        _assert_same_frame(
            got, _partitions_of(_table_of(paths, serial), partitions))
    batch, ok = arrowStructsToBatch(got.column("image"), 9, 11)
    want, _ = arrowStructsToBatch(
        _table_of(paths, serial).column("image"), 9, 11)
    assert list(ok) == [i != 3 for i in range(8)]
    np.testing.assert_array_equal(batch, want)


def test_a_header_that_gives_another_size_than_the_decoder(tmp_path, rng,
                                                          monkeypatch):
    """Where an image decodes to another size than its header said (ICO
    can), the record batch is laid out again from the decoded sizes."""
    paths = _jpeg_dir(tmp_path, rng, 8, corrupt=1)
    serial = [PIL_decode(_read(p)) for p in paths]
    rgb_pixels = image_io._rgb_pixels

    def cropped(img):
        rgb = rgb_pixels(img)
        return rgb[1:] if rgb.shape[0] == serial[5].shape[0] else rgb

    monkeypatch.setattr(image_io, "_rgb_pixels", cropped)
    want = [arr if arr is None or arr.shape[0] != serial[5].shape[0]
            else arr[1:] for arr in serial]
    assert sum(a is not b for a, b in zip(want, serial)) >= 1
    got = readImages(str(tmp_path), numPartitions=1).table
    _assert_same_frame(got, _table_of(paths, want))


@pytest.mark.parametrize("partitions", [None, 1, 3])
def test_pixels_past_the_int32_limit_are_cut_into_chunks_under_it(
        tmp_path, rng, monkeypatch, tracer, partitions):
    """One Arrow binary array holds 2 GiB: a partition (or a lazy record
    batch) whose pixels pass that comes as several chunks, each under the
    limit and as long as the limit allows, the rows in order."""
    from sparkdl_tpu.image import schema

    paths = _jpeg_dir(tmp_path, rng, 12, corrupt=4)
    serial = [PIL_decode(_read(p)) for p in paths]
    monkeypatch.setattr(schema, "MAX_BINARY_BYTES", 1500)
    got = readImages(str(tmp_path), numPartitions=partitions).table
    want = _table_of(paths, serial)
    assert got.equals(want) and _data_bytes(got) == _data_bytes(want)
    sizes = _chunk_sizes(got)
    assert len(sizes) > (partitions or 1) and sum(sizes) == 12
    nbytes = [0 if arr is None else arr.nbytes for arr in serial]
    bounds = np.cumsum([0] + sizes)
    held = [sum(nbytes[lo:hi]) for lo, hi in zip(bounds, bounds[1:])]
    assert max(held) <= 1500
    # no chunk could have taken the next one's first row as well, unless
    # an asked partition ends there
    asked = set(np.cumsum(_chunk_sizes(
        _partitions_of(want, partitions or 1))))
    assert all(h + nbytes[hi] > 1500 or hi in asked
               for h, hi in zip(held[:-1], bounds[1:-1]))
    assert all(s["attrs"]["direct_rows"] == s["attrs"]["rows"]
               for s in _spans(tracer, "io.to_arrow"))
    (repartition,) = _spans(tracer, "io.repartition")
    assert repartition["attrs"]["copied_bytes"] == 0
    lazy = list(image_io.iterImageBatches(str(tmp_path), batch_size=12))
    assert [rb.num_rows for rb in lazy] == _chunk_sizes(
        readImages(str(tmp_path), numPartitions=1).table)
    assert pa.Table.from_batches(lazy).equals(want)
    # one image over the limit fits no array at all
    monkeypatch.setattr(schema, "MAX_BINARY_BYTES", 100)
    with pytest.raises(ValueError, match="bytes of pixels"):
        readImages(str(tmp_path))
    with pytest.raises(ValueError, match="bytes of pixels"):
        schema.imageStructArray([serial[0]], ["o"])


def _buffer_addresses(table):
    return [buf.address for chunk in table.column("image").chunks
            for buf in chunk.buffers() if buf is not None]


@pytest.mark.parametrize("partitions", [1, 3, 12, 40])
def test_repartition_of_chunks_that_fit_copies_nothing(tmp_path, rng,
                                                       tracer, partitions):
    """``readImages`` builds the partitions asked for, so its
    ``repartition`` has nothing to do (``copied_bytes``), and a second
    ``repartition`` to the same count hands back the same frame: the same
    buffers.  To another count it gives an equal frame, copied."""
    paths = _jpeg_dir(tmp_path, rng, 12, corrupt=7)
    df = readImages(str(tmp_path), numPartitions=partitions)
    (span,) = _spans(tracer, "io.repartition")
    assert span["attrs"] == {"rows": 12, "partitions": min(partitions, 12),
                             "copied_bytes": 0}
    again = df.repartition(partitions)
    assert again is df
    other = df.repartition(5)
    assert _chunk_sizes(other.table) == [3, 3, 2, 2, 2]
    assert other.table.equals(df.table)
    # one chunk is sliced where it lies; several are joined first
    shared = set(_buffer_addresses(other.table)) \
        & set(_buffer_addresses(df.table))
    assert bool(shared) == (partitions == 1)
    back = other.repartition(partitions)
    _assert_same_frame(back.table, df.table)
    serial = [PIL_decode(_read(p)) for p in paths]
    assert df.table.equals(_table_of(paths, serial))


def test_repartition_looks_at_every_column(rng):
    """A frame is left as it is only if EVERY column's chunks fit."""
    from sparkdl_tpu.frame import DataFrame

    a = pa.chunked_array([[1, 2], [3, 4]])
    b = pa.chunked_array([[1, 2, 3], [4]])
    even = DataFrame(pa.table({"a": a, "b": a}))
    assert even.repartition(2) is even
    uneven = DataFrame(pa.Table.from_arrays([a, b], names=["a", "b"]))
    out = uneven.repartition(2)
    assert out is not uneven and out.table.equals(uneven.table)
    assert [len(c) for c in out.table.column("b").chunks] == [2, 2]
    empty = DataFrame(pa.table({"a": pa.array([], type=pa.int64())}))
    assert len(empty.repartition(3)) == 0


def test_image_struct_array_is_pa_array_of_the_structs(rng):
    """``imageStructArray`` against the row-level helper it stands in
    for: equal by value and in the IPC stream, nulls and all, and no
    validity bitmap where no row is null."""
    from sparkdl_tpu.image.schema import imageStructArray

    arrays = [(rng.random((5, 4, 3)) * 255).astype(np.uint8), None,
              rng.random((3, 3, 1)).astype(np.float32),
              (rng.random((2, 6)) * 255).astype(np.uint8)]
    origins = [f"mem://{i}" for i in range(4)]
    for rows in (slice(0, 4), slice(2, 4), slice(0, 0), slice(1, 2)):
        got = imageStructArray(arrays[rows], origins[rows])
        want = pa.array(
            [None if a is None else imageArrayToStruct(a, origin=o)
             for a, o in zip(arrays[rows], origins[rows])], type=imageSchema)
        assert got.type == imageSchema and got.equals(want)
        assert got.to_pylist() == want.to_pylist()
        assert _ipc_bytes(pa.table({"image": got})) \
            == _ipc_bytes(pa.table({"image": want}))
        got.validate(full=True)
    assert imageStructArray(arrays[2:], origins[2:]).buffers()[0] is None
    with pytest.raises(ValueError, match="dtype"):
        imageStructArray([np.zeros((2, 2, 3), np.int16)], ["o"])
    with pytest.raises(ValueError, match="channel count"):
        imageStructArray([np.zeros((2, 2, 2), np.uint8)], ["o"])


def test_read_images_has_two_rows_in_flight_at_once(tmp_path, rng,
                                                    monkeypatch, tracer):
    """On the pooled path two rows decode side by side: every call of
    the decoder waits for a second one to arrive.  A serial loop would
    break the barrier at its time limit and fail the read."""
    if image_io._io_executor()._max_workers < 2:
        pytest.skip("the io pool of a one-core host has one thread")
    paths = _jpeg_dir(tmp_path, rng, 8)
    barrier = threading.Barrier(2)
    idents = []

    decode_into = image_io._decode_into

    def met_by_another(*args):
        barrier.wait(timeout=30)
        idents.append(threading.get_ident())
        return decode_into(*args)

    monkeypatch.setattr(image_io, "_decode_into", met_by_another)
    got = readImages(str(tmp_path)).table
    decode = _decode_span(tracer)
    assert not barrier.broken
    assert len(idents) == 8
    assert threading.get_ident() not in idents
    assert decode["attrs"] == {"rows": 8, "failed": 0,
                               "workers": len(set(idents))}
    assert decode["attrs"]["workers"] >= 2
    assert decode["thread"] == threading.current_thread().name
    assert got.equals(_table_of(paths, [PIL_decode(_read(p))
                                        for p in paths]))


def test_custom_decode_f_runs_on_the_callers_thread_in_file_order(
        tmp_path, rng, tracer):
    """A caller's ``decode_f`` never goes to the pool: once a file, in
    file order, on the thread that called ``readImagesWithCustomFn``."""
    paths = _jpeg_dir(tmp_path, rng, 12, corrupt=5)
    calls = []

    def decode_f(blob):
        calls.append((threading.get_ident(), blob))
        return PIL_decode(blob)

    got = readImagesWithCustomFn(str(tmp_path), decode_f).table
    decode = _decode_span(tracer)
    assert [ident for ident, _ in calls] == [threading.get_ident()] * 12
    assert [blob for _, blob in calls] == [_read(p) for p in paths]
    assert decode["attrs"] == {"rows": 12, "failed": 1, "workers": 1}
    assert got.column("image").to_pylist()[5] is None
    assert got.equals(readImages(str(tmp_path)).table)


def test_files_to_df_and_partitions(fixture_images):
    df = filesToDF(fixture_images["dir"], numPartitions=2)
    assert df.count() == 4
    assert set(df.columns) == {"filePath", "fileData"}
    assert df.num_partitions == 2


def test_resize_bilinear_parity_with_pil(rng):
    arr = (rng.random((20, 30, 3)) * 255).astype(np.uint8)
    out = resizeImage(arr, 10, 15)
    assert out.shape == (10, 15, 3)
    from PIL import Image
    ref = np.asarray(Image.fromarray(arr).resize((15, 10), Image.BILINEAR))
    np.testing.assert_array_equal(out, ref)
    # float path stays close to the uint8 path (tolerance-based, like the
    # reference's cross-backend resize tests)
    outf = resizeImage(arr.astype(np.float32), 10, 15)
    assert outf.dtype == np.float32
    assert np.abs(outf - ref.astype(np.float32)).max() <= 1.0


def test_resize_udf_on_struct(rng):
    arr = (rng.random((8, 8, 3)) * 255).astype(np.uint8)
    udf = createResizeImageUDF([4, 6])
    out = udf(imageArrayToStruct(arr, origin="o"))
    assert out["height"] == 4 and out["width"] == 6
    assert udf(None) is None
    with pytest.raises(ValueError):
        createResizeImageUDF([1, 2, 3])


# ---------------------------------------------------------------------------
# arrowStructsToBatch: the zero-copy UDF hot path (VERDICT r3 #5)

def _struct_column(arrays, origins=None, nulls=()):
    """Build an image-struct arrow column from [H,W,C] BGR arrays, with
    ``None`` at the positions listed in ``nulls``."""
    import pyarrow as pa
    from sparkdl_tpu.image import imageSchema
    structs = []
    j = 0
    n = len(arrays) + len(nulls)
    for i in range(n):
        if i in nulls:
            structs.append(None)
        else:
            structs.append(imageArrayToStruct(
                arrays[j], origin="" if origins is None else origins[j]))
            j += 1
    return pa.array(structs, type=imageSchema)


def test_arrow_structs_uniform_parity(rng):
    """Fast path (all rows target-size uint8 BGR) matches structsToBatch."""
    from sparkdl_tpu.image import arrowStructsToBatch, structsToBatch
    arrays = [(rng.random((16, 16, 3)) * 255).astype(np.uint8)
              for _ in range(6)]
    col = _struct_column(arrays)
    batch, ok = arrowStructsToBatch(col, 16, 16)
    assert ok.all() and batch.shape == (6, 16, 16, 3)
    ref = structsToBatch(col.to_pylist(), 16, 16)
    np.testing.assert_array_equal(batch, ref)


def test_arrow_structs_nulls_and_slice(rng):
    """Null rows -> ok=False + zeros; sliced columns read correct buffers."""
    from sparkdl_tpu.image import arrowStructsToBatch
    arrays = [np.full((8, 8, 3), 10 * (i + 1), np.uint8) for i in range(4)]
    col = _struct_column(arrays, nulls=(2,))  # [10, 20, None, 30, 40]
    batch, ok = arrowStructsToBatch(col, 8, 8)
    assert list(ok) == [True, True, False, True, True]
    assert (batch[2] == 0).all()
    assert (batch[3] == 30).all()  # array index shifts past the null
    # slice: drop the first two rows — offsets must follow the slice
    sliced = col.slice(2, 3)
    b2, ok2 = arrowStructsToBatch(sliced, 8, 8)
    assert list(ok2) == [False, True, True]
    assert (b2[1] == 30).all() and (b2[2] == 40).all()


def test_arrow_structs_resize_and_modes(rng):
    """Mixed sizes / grayscale / float32 rows take the general path and
    match the per-dict converter bit-for-bit."""
    import pyarrow as pa
    from sparkdl_tpu.image import arrowStructsToBatch, imageSchema
    from sparkdl_tpu.image.io import structToModelInput
    arrays = [
        (rng.random((20, 30, 3)) * 255).astype(np.uint8),   # resize needed
        (rng.random((12, 12, 1)) * 255).astype(np.uint8),   # grayscale
        (rng.random((12, 12, 3)) * 255).astype(np.float32),  # CV_32FC3
        (rng.random((12, 12, 4)) * 255).astype(np.uint8),   # BGRA
    ]
    structs = [imageArrayToStruct(a) for a in arrays]
    col = pa.array(structs, type=imageSchema)
    batch, ok = arrowStructsToBatch(col, 12, 12)
    assert ok.all()
    for i, s in enumerate(structs):
        np.testing.assert_array_equal(batch[i], structToModelInput(s, 12, 12))


def test_arrow_structs_chunked_and_empty(rng):
    import pyarrow as pa
    from sparkdl_tpu.image import arrowStructsToBatch, imageSchema
    arrays = [np.full((4, 4, 3), i + 1, np.uint8) for i in range(4)]
    c1 = _struct_column(arrays[:2])
    c2 = _struct_column(arrays[2:])
    chunked = pa.chunked_array([c1, c2])
    batch, ok = arrowStructsToBatch(chunked, 4, 4)
    assert ok.all() and (batch[3] == 4).all()
    empty = pa.array([], type=imageSchema)
    b0, ok0 = arrowStructsToBatch(empty, 4, 4)
    assert b0.shape == (0, 4, 4, 3) and ok0.shape == (0,)
    allnull = pa.array([None, None], type=imageSchema)
    bn, okn = arrowStructsToBatch(allnull, 4, 4)
    assert not okn.any() and (bn == 0).all()


def test_arrow_structs_channel_order(rng):
    """channel_order='bgr' returns struct bytes untouched (the UDF hot-path
    feed; the device program does the swap); 'rgb' is its flip."""
    from sparkdl_tpu.image import arrowStructsToBatch
    arrays = [(rng.random((10, 10, 3)) * 255).astype(np.uint8)
              for _ in range(3)]
    col = _struct_column(arrays)
    bgr, ok = arrowStructsToBatch(col, 10, 10, channel_order="bgr")
    rgb, _ = arrowStructsToBatch(col, 10, 10)
    assert ok.all()
    np.testing.assert_array_equal(bgr, np.stack(arrays))
    np.testing.assert_array_equal(rgb, bgr[..., ::-1])
    # general (resize) path honors the order too
    big = [(rng.random((20, 20, 3)) * 255).astype(np.uint8)]
    colb = _struct_column(big)
    b, _ = arrowStructsToBatch(colb, 10, 10, channel_order="bgr")
    r, _ = arrowStructsToBatch(colb, 10, 10)
    np.testing.assert_array_equal(r, b[..., ::-1])
    with pytest.raises(ValueError):
        arrowStructsToBatch(col, 10, 10, channel_order="hsv")


def test_arrow_structs_packing_cost(rng):
    """Host packing cost per 299x299 image stays under 0.5 ms (VERDICT r3
    #5 target) on the UDF hot path (BGR passthrough: pure memcpy — the
    channel swap rides the fused device program)."""
    import time
    from sparkdl_tpu.image import arrowStructsToBatch
    n = 32
    arrays = [(rng.random((299, 299, 3)) * 255).astype(np.uint8)
              for _ in range(n)]
    col = _struct_column(arrays)
    arrowStructsToBatch(col, 299, 299, channel_order="bgr")  # warm
    best = float("inf")
    best_ref = float("inf")
    stacked = np.stack(arrays)
    for _ in range(5):  # best-of-5: 1-vCPU CI hosts are noisy
        t0 = time.perf_counter()
        batch, ok = arrowStructsToBatch(col, 299, 299, channel_order="bgr")
        best = min(best, (time.perf_counter() - t0) * 1000 / n)
        t0 = time.perf_counter()
        stacked.copy()  # same bytes, pure memcpy: the contention baseline
        best_ref = min(best_ref, (time.perf_counter() - t0) * 1000 / n)
    assert ok.all()
    # absolute target (VERDICT r3 #5) on a quiet host, OR within 25x of a
    # raw memcpy of the same bytes when the host is contended — both sides
    # inflate together under noisy-neighbor load, so the relative bound
    # keeps the assertion meaningful without flaking
    assert best < max(0.5, 25 * best_ref), \
        f"packing {best:.3f} ms/img vs memcpy {best_ref:.3f} ms/img"


def test_arrow_structs_compact(rng):
    """compact=True emits only ok rows, in row order, on every path —
    uniform, resize, chunked — and never zero-fills null slots."""
    import pyarrow as pa
    from sparkdl_tpu.image import arrowStructsToBatch, imageSchema
    arrays = [np.full((8, 8, 3), 10 * (i + 1), np.uint8) for i in range(4)]
    col = _struct_column(arrays, nulls=(1, 3))  # [10, None, 20, None, 30, 40]
    b, ok = arrowStructsToBatch(col, 8, 8, compact=True)
    assert b.shape[0] == 4 and list(ok) == [True, False, True, False,
                                            True, True]
    assert [int(b[k, 0, 0, 2]) for k in range(4)] == [10, 20, 30, 40]
    # resize (general) path
    big = [np.full((16, 16, 3), 7, np.uint8), np.full((16, 16, 3), 9,
                                                      np.uint8)]
    colb = _struct_column(big, nulls=(1,))
    bb, okb = arrowStructsToBatch(colb, 8, 8, compact=True)
    assert bb.shape[0] == 2 and list(okb) == [True, False, True]
    assert (bb[0] == 7).all() and (bb[1] == 9).all()
    # multi-chunk: packed per chunk (no combine_chunks), concatenated
    chunked = pa.chunked_array([_struct_column(arrays[:2], nulls=(1,)),
                                _struct_column(arrays[2:])])
    bc, okc = arrowStructsToBatch(chunked, 8, 8, compact=True)
    assert bc.shape[0] == 4 and okc.sum() == 4
    assert [int(bc[k, 0, 0, 2]) for k in range(4)] == [10, 20, 30, 40]


def _oracle_row(struct, height, width, channel_order):
    """The recipe a row before the raw route, written out: reshape,
    ``Image.fromarray``, ``resize(BILINEAR)``, flip.  ``None`` for a row
    whose ``data`` length lies."""
    from PIL import Image

    t = imageTypeByMode(struct["mode"])
    h, w, c = struct["height"], struct["width"], struct["nChannels"]
    arr = np.frombuffer(struct["data"], dtype=t.dtype)
    if arr.size != h * w * c:
        return None
    arr = arr.reshape(h, w, c)
    if arr.dtype != np.uint8:
        arr = np.clip(arr, 0, 255).astype(np.uint8)
    if c == 1:
        arr = np.repeat(arr, 3, axis=2)
    elif c == 4:
        arr = arr[:, :, :3]
    if (h, w) != (height, width):
        arr = np.asarray(Image.fromarray(np.ascontiguousarray(arr)).resize(
            (width, height), Image.BILINEAR))
    return arr if channel_order == "bgr" else arr[:, :, ::-1]


def _u8(rng, h, w, c=3):
    return rng.integers(0, 256, size=(h, w, c), dtype=np.uint8)


def _pack_case(name, rng):
    """``(column, structs a row or None, (height, width))`` of a case."""
    size = (9, 11)
    cut = None
    if name == "down_375x500":
        size = (299, 299)
        rows = [_u8(rng, 375, 500) for _ in range(5)]
    elif name == "up_odd_7x5":
        rows = [_u8(rng, 7, 5) for _ in range(6)]
    elif name == "mixed_sizes":     # above, below, odd, and the target's own
        rows = [_u8(rng, h, w) for h, w in
                [(20, 30), (9, 11), (7, 5), (9, 30), (31, 11), (9, 11)]]
    elif name == "mixed_kinds":     # the route is chosen row by row
        rows = [_u8(rng, 12, 14), _u8(rng, 12, 14, 1), _u8(rng, 9, 11, 4),
                (rng.random((12, 14, 3)) * 300 - 20).astype(np.float32),
                (rng.random((9, 11, 1)) * 255).astype(np.float32),
                _u8(rng, 9, 11), _u8(rng, 13, 8, 4)]
    elif name == "sliced":          # a non-zero offset in every child
        rows = [_u8(rng, 10 + i, 17 - i) for i in range(9)]
        cut = (2, 6)
    elif name == "chunked":
        rows = [_u8(rng, 12, 14), None, _u8(rng, 5, 7), _u8(rng, 9, 11, 1),
                _u8(rng, 20, 30), _u8(rng, 9, 11), _u8(rng, 8, 8)]
    elif name == "nulls_and_a_lying_length":
        rows = [_u8(rng, 12, 14), None, _u8(rng, 10, 10), _u8(rng, 5, 7),
                None, _u8(rng, 6, 6, 1), _u8(rng, 20, 30)]
    elif name == "serial_under_4_rows":     # two rows share a source image
        rows = [_u8(rng, 12, 14), _u8(rng, 12, 14), None, _u8(rng, 7, 5)]
    elif name == "tasks_of_several_rows":   # sizes in runs of three
        n = 8 * image_io._io_executor()._max_workers + 3
        rows = [_u8(rng, 6 + (k // 3) % 2, 5) for k in range(n)]
    structs = [None if a is None else imageArrayToStruct(a) for a in rows]
    if name == "nulls_and_a_lying_length":
        structs[2]["height"] += 1   # an 8-bit BGR row: the raw route's check
        structs[5]["width"] -= 1    # a grayscale row: the other route's
    column = pa.array(structs, type=imageSchema)
    if cut is not None:
        column = column.slice(*cut)
        structs = structs[cut[0]:cut[0] + cut[1]]
    if name == "chunked":
        column = pa.chunked_array([column.slice(0, 3), column.slice(3, 0),
                                   column.slice(3)])
    return column, structs, size


@pytest.mark.parametrize("channel_order", ["rgb", "bgr"])
@pytest.mark.parametrize("compact", [False, True],
                         ids=["row_aligned", "compact"])
@pytest.mark.parametrize("case", [
    "down_375x500", "up_odd_7x5", "mixed_sizes", "mixed_kinds", "sliced",
    "chunked", "nulls_and_a_lying_length", "serial_under_4_rows",
    "tasks_of_several_rows"])
def test_arrow_structs_general_path_equals_the_recipe_a_row(
        rng, case, compact, channel_order):
    """The general path's pixels are those of the recipe a row, whatever
    route a row takes, in tasks of several rows or on the caller's
    thread; null rows and rows whose length lies are dropped when
    ``compact`` and zeroed when not."""
    from sparkdl_tpu.image import arrowStructsToBatch
    column, structs, (height, width) = _pack_case(case, rng)
    want = [None if s is None else _oracle_row(s, height, width,
                                               channel_order)
            for s in structs]
    want_ok = np.array([w is not None for w in want])
    if compact:
        want = [w for w in want if w is not None]
    else:
        want = [np.zeros((height, width, 3), np.uint8) if w is None else w
                for w in want]
    batch, ok = arrowStructsToBatch(column, height, width,
                                    channel_order=channel_order,
                                    compact=compact)
    assert batch.dtype == np.uint8
    assert batch.shape == (len(want), height, width, 3)
    np.testing.assert_array_equal(ok, want_ok)
    np.testing.assert_array_equal(batch, np.stack(want))


@pytest.mark.parametrize("born,raw", [("jpeg", True), ("grayscale", False)])
def test_pack_in_span_says_how_many_rows_went_raw(rng, tmp_path, tiny_resnet,
                                                  born, raw):
    """``transform.pack_in`` of a toy ``DeepImageFeaturizer.transform``
    carries ``raw_rows`` and ``tasks``: every JPEG-born struct is 8-bit
    BGR and goes raw; no row of a grayscale column does."""
    from PIL import Image

    from sparkdl_tpu.frame import DataFrame
    from sparkdl_tpu.transformers import DeepImageFeaturizer
    rows, batch_size = 11, 8    # 8: the test mesh's data axis
    if born == "jpeg":
        for i in range(rows):
            Image.fromarray(_u8(rng, 20, 24)).save(tmp_path / f"{i}.jpg")
        df = readImages(str(tmp_path), numPartitions=1)
    else:
        df = DataFrame(pa.table({"image": pa.array(
            [imageArrayToStruct(_u8(rng, 20, 24, 1)) for _ in range(rows)],
            type=imageSchema)}))
    tracer = obs.configure(enabled=True)
    try:
        out = DeepImageFeaturizer(
            inputCol="image", outputCol="features", modelName="ResNet50",
            batchSize=batch_size).transform(df)
        packs = sorted((s for s in tracer.snapshot()
                        if s["name"] == "transform.pack_in"),
                       key=lambda s: s["ts_us"])
    finally:
        obs.configure_from_env()
    assert out.count() == rows
    # one chunk of 8 rows on the pool, one of 3 on the caller's thread
    assert [p["attrs"]["rows"] for p in packs] == [batch_size,
                                                   rows - batch_size]
    assert [p["attrs"]["raw_rows"] for p in packs] == [
        p["attrs"]["rows"] if raw else 0 for p in packs]
    assert 1 <= packs[0]["attrs"]["tasks"] <= batch_size
    assert packs[1]["attrs"]["tasks"] == 0


def test_arrow_structs_multi_chunk_never_combines():
    """Chunked columns must be packed chunk by chunk: combine_chunks on a
    binary child overflows int32 offsets past 2 GB of image bytes
    (ArrowInvalid on pyarrow 25).  pa.ChunkedArray is an immutable C type
    (cannot be spied on), so pin the invariant at the source level for the
    two functions on the image hot path."""
    import inspect

    import sparkdl_tpu.udf.registry as registry_mod
    from sparkdl_tpu.image.io import arrowStructsToBatch
    assert ".combine_chunks(" not in inspect.getsource(arrowStructsToBatch)
    assert ".combine_chunks(" not in inspect.getsource(registry_mod)
