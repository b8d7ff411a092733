"""Host-side image decode / resize / file ingestion.

Replaces ``imageIO._decodeImage`` / ``readImagesWithCustomFn`` / ``filesToDF``
/ ``createResizeImageUDF`` and the Scala ``ImageUtils.resizeImage``.  Decode
runs on the host (PIL) because the TPU has no decode engine; the output of
this layer is either image-struct rows (for the DataFrame API) or dense
numpy batches (for the device pipeline).
"""

from __future__ import annotations

import glob as _glob
import os
import threading
from typing import Callable, Iterable, List, Optional, Sequence

import numpy as np
import pyarrow as pa

from sparkdl_tpu.image import schema as _schema
from sparkdl_tpu.image.schema import (
    ImageRowBuffers,
    imageArrayToStruct,
    imageSchema,
    imageStructArray,
    imageStructToArray,
    imageTypeByMode,
    imageTypeByName,
)
from sparkdl_tpu.obs.trace import get_tracer


def _open_image(raw_bytes: bytes):
    """The PIL image of compressed bytes with its header parsed and no
    pixel decoded yet (``size`` is known), or ``None``."""
    import io as _io

    from PIL import Image

    try:
        return Image.open(_io.BytesIO(raw_bytes))
    # graftlint: allow=SDL003 reason=PIL raises a zoo of types for bad bytes; None rides the ok-mask drop-to-null contract
    except Exception:
        return None


def _rgb_pixels(img) -> Optional[np.ndarray]:
    """Decode an opened PIL image -> a [H,W,3] or [H,W,4] uint8 array whose
    first three channels are RGB, or ``None``.  Where PIL hands out its
    own memory (Arrow's C data interface, Pillow 11.2; an image in one
    block of PIL's allocator, 16 MB) the array is a view of it — four
    bytes a pixel — and nothing is copied, nor the GIL held for a copy;
    else it is ``np.asarray``'s copy."""
    try:
        if img.mode != "RGB":
            img = img.convert("RGB")
        else:
            img.load()      # convert() of an RGB image would copy it
        if hasattr(img, "__arrow_c_array__"):
            try:
                flat = pa.array(img).values.to_numpy(zero_copy_only=True)
                return flat.reshape(img.size[1], img.size[0], 4)
            except ValueError:      # the image lies in several blocks
                pass
        return np.asarray(img, dtype=np.uint8)
    # graftlint: allow=SDL003 reason=PIL raises a zoo of types for bad bytes; None rides the ok-mask drop-to-null contract
    except Exception:
        return None


def _flip_into(out: np.ndarray, rgb: np.ndarray) -> None:
    """``out[:] = rgb[:, :, 2::-1]``: RGB -> BGR, OpenCV order.  One call:
    on the io pool a task pays for every NumPy call with a wait for the
    GIL, so three strided assigns, 4x faster alone, are slower there."""
    np.copyto(out, rgb[..., 2::-1])


def PIL_decode(raw_bytes: bytes) -> Optional[np.ndarray]:
    """Decode compressed image bytes to a [H,W,3] uint8 **BGR** array.

    Counterpart of ``imageIO.PIL_decode``/``_decodeImage``: undecodable input
    yields ``None`` (the reference drops/nulls such rows rather than failing
    the job).
    """
    img = _open_image(raw_bytes)
    rgb = None if img is None else _rgb_pixels(img)
    if rgb is None:
        return None
    out = np.empty(rgb.shape[:2] + (3,), dtype=np.uint8)
    _flip_into(out, rgb)
    return out


def decodeImage(raw_bytes: bytes, origin: str = "") -> Optional[dict]:
    """Decode bytes into an image struct dict, or None on failure."""
    arr = PIL_decode(raw_bytes)
    if arr is None:
        return None
    return imageArrayToStruct(arr, origin=origin)


def resizeImage(array: np.ndarray, height: int, width: int) -> np.ndarray:
    """Bilinear resize of a [H,W,C] uint8/float32 array on the host.

    Counterpart of the Scala ``ImageUtils.resizeImage`` (java.awt bilinear) and
    the TF resize the Python path used — parity is tolerance-based, matching
    the reference's own tests (they assert closeness, not bit-equality, across
    their two resize backends).
    """
    from PIL import Image

    if array.shape[0] == height and array.shape[1] == width:
        return array
    dtype = array.dtype
    if dtype == np.uint8:
        img = Image.fromarray(array if array.shape[2] != 1 else array[:, :, 0])
        out = np.asarray(img.resize((width, height), Image.BILINEAR), dtype=np.uint8)
        if out.ndim == 2:
            out = out[:, :, None]
        return out
    # float path: resize channel-planes via PIL 'F' mode
    planes = [
        np.asarray(
            Image.fromarray(array[:, :, c].astype(np.float32), mode="F")
            .resize((width, height), Image.BILINEAR))
        for c in range(array.shape[2])
    ]
    return np.stack(planes, axis=2).astype(dtype)


def createResizeImageUDF(size: Sequence[int]) -> Callable[[dict], dict]:
    """Return a row-level function image-struct -> resized image-struct.

    Counterpart of ``imageIO.createResizeImageUDF``; with our DataFrame layer
    it is applied via ``DataFrame.withColumn(map_struct=...)`` and, when a real
    pyspark is present, can be wrapped with ``pyspark.sql.functions.udf``.
    """
    if len(size) != 2:
        raise ValueError(f"New image size should have format [height, width], got {size}")
    height, width = int(size[0]), int(size[1])

    def _resize(row: Optional[dict]) -> Optional[dict]:
        if row is None:
            return None
        arr = imageStructToArray(row)
        out = resizeImage(arr, height, width)
        return imageArrayToStruct(out, origin=row.get("origin", ""))

    return _resize


def structToModelInput(struct: dict, height: int, width: int) -> np.ndarray:
    """Image struct -> [h,w,3] uint8 **RGB** array resized for a model.

    Handles channel normalization the way the reference's converter subgraph
    did (``graph/pieces.py — buildSpImageConverter`` BGR->RGB swap):
    grayscale replicates to 3 channels, BGRA drops alpha, BGR flips to RGB.
    """
    arr = imageStructToArray(struct)
    if arr.dtype != np.uint8:
        arr = np.clip(arr, 0, 255).astype(np.uint8)
    c = arr.shape[2]
    if c == 1:
        arr = np.repeat(arr, 3, axis=2)
    elif c == 4:
        arr = arr[:, :, :3]          # BGRA -> BGR
    arr = resizeImage(arr, height, width)
    return arr[:, :, ::-1]           # BGR -> RGB


def _native_io_preferred() -> bool:
    """Use the native core whenever it built: measured on a 1-vCPU host
    (tools/native_thread_scaling.py, PERF.md) it beats serial PIL even
    single-threaded (232 vs 192 img/s at 500x375 JPEG -> 299x299), and it
    scales with real threads (no GIL) on multi-core hosts."""
    import sparkdl_tpu.native as native

    return native.native_available()


def decodeResizeBatch(blobs: Sequence[bytes], height: int, width: int
                      ) -> "tuple[np.ndarray, np.ndarray]":
    """Fused decode+resize of encoded images into a [N,h,w,3] uint8 **RGB**
    batch + ok-mask — the fast path from raw files straight to model input
    (skips the full-size intermediate the struct path materializes).

    Uses the native threaded core (libjpeg DCT prescale + libpng) when
    available and useful; PIL otherwise.  Undecodable rows: ok=False,
    zeroed pixels (drop-to-null upstream).

    Fault site ``io.decode`` (per row, :mod:`sparkdl_tpu.faults`): an
    injected decode error mid-stream must ride the SAME drop-to-null
    contract as a genuinely corrupt blob — the row's ok flag goes False
    and the stream continues.  A plan with ``io.decode`` rules routes
    around the native core AND the decode thread pool, so the per-row
    site is reached in deterministic row order (``at=``/``every=``
    schedules count calls; pool scheduling would make the dropped row
    arbitrary).
    """
    from sparkdl_tpu import faults as _faults

    io_faults = _faults.has_rules("io.decode")
    if not io_faults and _native_io_preferred():
        import sparkdl_tpu.native as native

        result = native.decode_resize_batch(blobs, height, width)
        if result is not None:
            return result
    out = np.zeros((len(blobs), height, width, 3), dtype=np.uint8)
    ok = np.zeros(len(blobs), dtype=bool)

    def one(i_blob):
        i, blob = i_blob
        try:
            _faults.inject("io.decode", row=i)
        except _faults.InjectedFault:
            return  # simulated corrupt row: ok stays False (drop-to-null)
        arr = PIL_decode(blob)  # BGR or None
        if arr is None:
            return
        if arr.shape[2] == 1:
            arr = np.repeat(arr, 3, axis=2)
        out[i] = resizeImage(arr, height, width)[:, :, ::-1]
        ok[i] = True

    if len(blobs) >= 4 and not io_faults:
        list(_io_executor().map(one, enumerate(blobs)))
    else:
        for pair in enumerate(blobs):
            one(pair)
    return out, ok


def filesToModelBatch(paths: Sequence[str], height: int, width: int
                      ) -> "tuple[np.ndarray, np.ndarray]":
    """Read+decode+resize files into a model-ready uint8 RGB batch."""
    blobs = []
    for p in paths:
        try:
            with open(p, "rb") as fh:
                blobs.append(fh.read())
        except OSError:
            blobs.append(b"")
    return decodeResizeBatch(blobs, height, width)


_IO_EXECUTOR = None


def _io_executor():
    """Shared host-prep thread pool — reused across batches (spawning a pool
    per device batch would put thread startup on the feed-the-chip path)."""
    global _IO_EXECUTOR
    if _IO_EXECUTOR is None:
        from concurrent.futures import ThreadPoolExecutor

        _IO_EXECUTOR = ThreadPoolExecutor(
            min(16, (os.cpu_count() or 4)), thread_name_prefix="sparkdl-io")
    return _IO_EXECUTOR


def structsToBatch(structs: Sequence[dict], height: int, width: int,
                   num_threads: Optional[int] = None) -> np.ndarray:
    """Decode+resize a sequence of image structs into one [N,h,w,3] uint8
    RGB batch.  Threaded: PIL releases the GIL during resize, and host-side
    prep is the throughput-critical path feeding the chip (SURVEY.md §7
    hard part #2)."""
    if len(structs) == 0:
        return np.zeros((0, height, width, 3), dtype=np.uint8)
    if _native_io_preferred() and len(structs) >= 4:
        import sparkdl_tpu.native as native

        def to_rgb(s):
            arr = imageStructToArray(s)
            if arr.dtype != np.uint8:
                arr = np.clip(arr, 0, 255).astype(np.uint8)
            c = arr.shape[2]
            if c == 1:
                arr = np.repeat(arr, 3, axis=2)
            elif c == 4:
                arr = arr[:, :, :3]
            return np.ascontiguousarray(arr[:, :, ::-1])  # BGR -> RGB

        batch = native.resize_batch_rgb(
            [to_rgb(s) for s in structs], height, width)
        if batch is not None:
            return batch
    if (num_threads is not None and num_threads <= 1) or len(structs) < 4:
        arrs = [structToModelInput(s, height, width) for s in structs]
    else:
        arrs = list(_io_executor().map(
            lambda s: structToModelInput(s, height, width), structs))
    return np.stack(arrs, axis=0)


def arrowStructsToBatch(column, height: int, width: int,
                        channel_order: str = "rgb", compact: bool = False
                        ) -> "tuple[np.ndarray, np.ndarray]":
    """Image-struct Arrow column -> ([N,h,w,3] uint8 batch, valid mask)
    WITHOUT materializing per-row Python dicts.

    This is the zero-copy replacement for ``to_pylist()`` +
    :func:`structsToBatch` on the UDF/scoring hot path: child arrays are
    read as numpy views over Arrow buffers, and each row's pixel block is
    sliced straight out of the binary child's value buffer.  Chunked
    columns are packed chunk by chunk (never ``combine_chunks``, whose
    int32 binary offsets overflow past 2 GB of image bytes).  Two paths
    (what each costs on the chip's host: ``PERF.md`` §5, §6):

    * **uniform** — every valid row is already ``height x width`` uint8
      BGR (a resized column): one memcpy a row on the caller's thread,
      then for "rgb" one channel shuffle over the whole batch.
    * **general** — any other column, on the shared io pool in tasks of
      several rows (the task's size follows from the rows and the pool's
      workers).  Each row takes the route its struct names: an 8-bit
      three-channel row (``CV_8UC3``) is handed to PIL as the raw Arrow
      slice — the unpacker swaps the channels while it reads, so every
      source pixel is read once, into one image that a task's rows of
      one size share — resized by :func:`resizeImage`'s filter, and
      copied into its slot once; a row of any other kind
      (one or four channels, 16-bit or float modes) goes through
      :func:`resizeImage` as an array, as :func:`structToModelInput`
      does.  The pixels are the same either way.  The span open around
      the call (``transform.pack_in``) is annotated with ``raw_rows``,
      the rows that took the raw route, and ``tasks``, the pool tasks
      submitted (0 under 4 rows, which are packed on the caller's
      thread).

    ``channel_order``: "rgb" (default) swaps BGR struct bytes to RGB on the
    host; "bgr" returns the struct's native byte order untouched — the
    feed for pipelines that fold the channel swap into the device program
    (as the reference's converter subgraph did: ``graph/pieces.py``
    buildSpImageConverter swapped BGR->RGB *inside* the graph).

    ``compact``: when True the batch holds ONLY the ok rows (in row order) —
    row ``k`` of the batch is the ``k``-th True of the mask — so callers
    feeding an engine skip both the null-row zero fill and a second
    valid-rows copy.  When False (default) the batch is row-aligned with
    the column and failed rows are zeroed, matching the reference's
    scoring-path null contract.
    """
    if channel_order not in ("rgb", "bgr"):
        raise ValueError(f"channel_order must be 'rgb' or 'bgr', "
                         f"got {channel_order!r}")
    if isinstance(column, pa.ChunkedArray):
        chunks = column.chunks
        if len(chunks) == 1:
            column = chunks[0]
        else:
            parts = [arrowStructsToBatch(c, height, width,
                                         channel_order=channel_order,
                                         compact=compact)
                     for c in chunks if len(c)]
            if not parts:
                return (np.zeros((0, height, width, 3), dtype=np.uint8),
                        np.zeros(0, dtype=bool))
            return (np.concatenate([p[0] for p in parts], axis=0),
                    np.concatenate([p[1] for p in parts], axis=0))
    n = len(column)
    ok = np.zeros(n, dtype=bool)
    if n == 0:
        return np.zeros((0, height, width, 3), dtype=np.uint8), ok
    valid = np.asarray(column.is_valid())
    idx = np.nonzero(valid)[0]
    nrows = len(idx) if compact else n
    if len(idx) == 0:
        return np.zeros((nrows, height, width, 3), dtype=np.uint8), ok
    # Child arrays: pyarrow's .field() applies the parent struct's
    # offset/length, so sliced columns are handled.
    heights = np.asarray(column.field("height"))
    widths = np.asarray(column.field("width"))
    channels = np.asarray(column.field("nChannels"))
    modes = np.asarray(column.field("mode"))
    data = column.field("data")
    # Binary child buffers: [validity, int32 offsets, values].  The child
    # carries its own offset when the parent was sliced.
    bufs = data.buffers()
    offsets = np.frombuffer(bufs[1], dtype=np.int32)[
        data.offset:data.offset + n + 1]
    values = np.frombuffer(bufs[2], dtype=np.uint8) if bufs[2] is not None \
        else np.zeros(0, dtype=np.uint8)

    # slot[k]: output row for source row idx[k]
    slots = np.arange(len(idx)) if compact else idx
    uniform = (
        np.all(heights[idx] == height) and np.all(widths[idx] == width)
        and np.all(channels[idx] == 3) and np.all(modes[idx] == 16)  # CV_8UC3
        and np.all((offsets[idx + 1] - offsets[idx]) == height * width * 3))
    if uniform:
        hw3 = height * width * 3
        # compact output is fully written -> skip the zero fill
        alloc = np.empty if compact else np.zeros
        if channel_order == "bgr":
            out = alloc((nrows, height, width, 3), dtype=np.uint8)
            for s, i in zip(slots, idx):  # pure memcpy per row
                out[s] = values[offsets[i]:offsets[i] + hw3].reshape(
                    height, width, 3)
        else:
            # memcpy rows, then one batch-level channel shuffle (3 strided
            # assigns beat a negative-stride copy ~3x on this host)
            # non-compact alloc is zeros, so null rows stay zeroed through
            # the shuffle; compact output has no null slots to zero
            tmp = alloc((nrows, height, width, 3), dtype=np.uint8)
            for s, i in zip(slots, idx):
                tmp[s] = values[offsets[i]:offsets[i] + hw3].reshape(
                    height, width, 3)
            out = np.empty_like(tmp)
            out[..., 0] = tmp[..., 2]
            out[..., 1] = tmp[..., 1]
            out[..., 2] = tmp[..., 0]
        ok[idx] = True
        return out, ok

    # General path: the io pool packs tasks of several rows, each row by
    # the route its struct names.  Every slot is written or (compact)
    # dropped below, so nothing is zeroed up front.
    from PIL import Image

    out = np.empty((nrows, height, width, 3), dtype=np.uint8)
    # the unpacker swaps the channels, or not, while it reads the slice
    raw_mode = "BGR" if channel_order == "rgb" else "RGB"

    def pack(lo, hi):
        """Rows ``idx[lo:hi]`` into their slots -> how many went raw."""
        raw = 0
        src = None
        for s, i in zip(slots[lo:hi], idx[lo:hi]):
            h, w, c = int(heights[i]), int(widths[i]), int(channels[i])
            row = values[offsets[i]:offsets[i + 1]]
            if modes[i] == 16 and c == 3 and row.size == h * w * 3:
                # CV_8UC3, the raw route: PIL reads the Arrow slice
                # once, the resized row is copied once.  Rows of one size
                # unpack into one image a task: a new one would be
                # allocated and filled with the GIL held
                if src is None or src.size != (w, h):
                    src = Image.new("RGB", (w, h), None)
                src.frombytes(row, "raw", (raw_mode, 0, 1))
                img = src
                if h != height or w != width:
                    img = src.resize((width, height), Image.BILINEAR)
                out[s] = np.asarray(img)
                raw += 1
            else:
                t = imageTypeByMode(int(modes[i]))
                arr = row.view(t.dtype) if t.dtype != "uint8" else row
                if arr.size != h * w * c:
                    continue
                arr = arr.reshape(h, w, c)
                if arr.dtype != np.uint8:
                    arr = np.clip(arr, 0, 255).astype(np.uint8)
                if c == 1:
                    arr = np.repeat(arr, 3, axis=2)
                elif c == 4:
                    arr = arr[:, :, :3]
                resized = resizeImage(np.ascontiguousarray(arr), height,
                                      width)
                out[s] = (resized if channel_order == "bgr"
                          else resized[:, :, ::-1])
            ok[i] = True
        return raw

    rows = len(idx)
    if rows >= 4:
        pool = _io_executor()
        # a task's submit, wake-up and result are paid once for its rows;
        # four tasks a worker still even out rows of unequal cost
        per = min(32, max(1, rows // (4 * pool._max_workers)))
        los = range(0, rows, per)
        tasks = len(los)
        raw_rows = sum(pool.map(lambda lo: pack(lo, lo + per), los))
    else:
        tasks, raw_rows = 0, pack(0, rows)
    span = get_tracer().current()
    if span is not None:
        # summed: a chunked column packs chunk by chunk under one span
        span.annotate(
            raw_rows=span.attrs.get("raw_rows", 0) + raw_rows,
            tasks=span.attrs.get("tasks", 0) + tasks)
    if not compact:
        out[~ok] = 0    # null rows, and valid ones that failed
    elif not ok[idx].all():
        # a valid struct failed decode (size mismatch): drop its slot so
        # batch rows stay aligned with the True positions of the mask
        out = out[ok[idx]]
    return out, ok


def _list_files(path: str, recursive: bool = False) -> List[str]:
    """Expand a path/glob/directory into a sorted file list (deterministic
    ordering replaces Spark's nondeterministic partition enumeration)."""
    if os.path.isdir(path):
        pattern = os.path.join(path, "**" if recursive else "*")
        files = [f for f in _glob.glob(pattern, recursive=recursive)
                 if os.path.isfile(f)]
    else:
        files = [f for f in _glob.glob(path, recursive=recursive)
                 if os.path.isfile(f)]
    return sorted(files)


def _read_files(files: Sequence[str]) -> List[bytes]:
    with get_tracer().span("io.read", files=len(files)) as sp:
        data = []
        for f in files:
            with open(f, "rb") as fh:
                data.append(fh.read())
        sp.annotate(bytes=sum(map(len, data)))
    return data


def iterFileBatches(path: str, batch_size: int = 64,
                    recursive: bool = False) -> Iterable[pa.RecordBatch]:
    """LAZILY read files under ``path`` into ``{filePath, fileData}`` record
    batches of ``batch_size`` rows — bytes for one batch at a time, never
    the whole directory (the streaming analog of the reference's
    ``sc.binaryFiles`` partition iterator).  Compose with any transformer's
    ``transformStream``."""
    files = _list_files(path, recursive=recursive)
    batch_size = max(1, int(batch_size))
    for off in range(0, len(files), batch_size):
        chunk = files[off:off + batch_size]
        # every span here and in iterImageBatches closes before the
        # yield: a generator must not leave one open on the puller's stack
        yield pa.record_batch({
            "filePath": pa.array(chunk, type=pa.string()),
            "fileData": pa.array(_read_files(chunk), type=pa.binary()),
        })


def _decode_into(images: list, i: int, slot: np.ndarray
                 ) -> "tuple[Optional[np.ndarray], int]":
    """One task of the io pool: decode the opened image ``images[i]`` and
    flip it to BGR straight into ``slot``, its row of the record batch's
    values buffer -> (the pixels, the thread).  The pixels are ``slot``;
    ``None`` where the decoder failed; an array of their own where the
    image decoded to another size than its header gave (ICO does that).
    The task takes the image out of the list: decoded, it holds its
    pixels, and only as many may be alive as the pool has threads."""
    img, images[i] = images[i], None
    rgb = _rgb_pixels(img)
    if rgb is None:
        return None, threading.get_ident()
    out = (slot if rgb.shape[:2] == slot.shape[:2]
           else np.empty(rgb.shape[:2] + (3,), np.uint8))
    _flip_into(out, rgb)
    return out, threading.get_ident()


def _cut_under(nbytes: Sequence[int], limit: int) -> "List[tuple[int, int]]":
    """``(lo, hi)`` runs of rows in order, each run as long as ``limit``
    bytes allow and of one row at least."""
    cuts, lo, held = [], 0, 0
    for i, size in enumerate(nbytes):
        if i > lo and held + size > limit:
            cuts.append((lo, i))
            lo, held = i, 0
        held += size
    return cuts + [(lo, len(nbytes))] if nbytes else []


def _pil_decode_pooled(blobs: Sequence[bytes], origins: Sequence[str]
                       ) -> "tuple[List[ImageRowBuffers], int]":
    """:func:`PIL_decode` over ``blobs`` with each row's flip to BGR — its
    decoder's last write — landing in its record batch's own values
    buffer -> (the buffers, how many threads decoded a row).  The
    headers, parsed here, give the sizes the buffer is laid out from; the
    pixels are decoded on the shared io pool, a decoded image alive only
    until its task has written it.  One set of buffers, unless the pixels
    pass what int32 offsets hold."""
    from PIL import Image

    # the lazy plugin registry, filled here once: a cold process must not
    # race Image.preinit from every thread of the pool
    Image.init()
    bgr8 = imageTypeByName("CV_8UC3")
    # header parsing is Python under the GIL: the pool would not speed it
    opened = [_open_image(blob) for blob in blobs]
    nbytes = [0 if im is None else 3 * im.size[0] * im.size[1]
              for im in opened]
    built, idents = [], set()
    for lo, hi in _cut_under(nbytes, _schema.MAX_BINARY_BYTES):
        rows = ImageRowBuffers(origins[lo:hi], [
            None if im is None else (bgr8, im.size[1], im.size[0])
            for im in opened[lo:hi]])
        live = [i for i in range(hi - lo) if opened[lo + i] is not None]
        slots = [rows.view(i) for i in live]
        done = list(_io_executor().map(
            lambda i, slot: _decode_into(opened, lo + i, slot), live, slots))
        idents.update(ident for _, ident in done)
        pixels = [out for out, _ in done]
        if all(out is slot or out is None
               for out, slot in zip(pixels, slots)):
            for i, out in zip(live, pixels):
                if out is None:
                    rows.drop(i)
        else:
            # a header gave another size than its decoder: copy the rows
            # out of the buffer laid out from the headers into one that fits
            decoded = [None] * (hi - lo)
            for i, out in zip(live, pixels):
                decoded[i] = out
            rows = ImageRowBuffers.of_arrays(decoded, origins[lo:hi])
        built.append(rows)
    return built, len(idents)


def _struct_chunks(files: Sequence[str], sizes: Iterable[int],
                   decode: Callable) -> Iterable[pa.StructArray]:
    """Read and decode ``files`` in runs of ``sizes`` -> one image-struct
    array a run (more where a run's pixels pass 2 GiB), null structs for
    undecodable files, rows in the order of ``files``."""
    tracer = get_tracer()
    off = 0
    for size in sizes:
        run = files[off:off + size]
        off += size
        blobs = _read_files(run)
        built = decoded = None
        # every span closes before the yield, as in iterFileBatches
        with tracer.span("io.decode", rows=len(run)) as sp:
            if decode is PIL_decode and len(run) >= 4:
                built, workers = _pil_decode_pooled(blobs, run)
                failed = sum(int((~rows.valid).sum()) for rows in built)
            else:
                decoded, workers = [decode(blob) for blob in blobs], 1
                failed = sum(arr is None for arr in decoded)
            sp.annotate(failed=failed, workers=workers)
        del blobs
        with tracer.span("io.to_arrow", rows=len(run)) as sp:
            direct = len(run)
            if built is not None:
                arrays = [rows.to_arrow() for rows in built]
            elif any(isinstance(arr, dict) for arr in decoded):
                direct = 0
                arrays = [pa.array(
                    [arr if arr is None or isinstance(arr, dict)
                     else imageArrayToStruct(np.asarray(arr), origin=f)
                     for f, arr in zip(run, decoded)], type=imageSchema)]
            else:
                arrays = [imageStructArray(decoded, run)]
            # not kept alive while the puller holds the arrays
            built = decoded = None
            sp.annotate(bytes=sum(a.nbytes for a in arrays),
                        direct_rows=direct)
        yield from arrays


def iterImageBatches(path: str, batch_size: int = 64, recursive: bool = False,
                     decode_f: Callable[[bytes], Optional[np.ndarray]] = None
                     ) -> Iterable[pa.RecordBatch]:
    """LAZILY decode images under ``path`` into image-struct record batches
    (null structs for undecodable files).  Peak host memory is one batch of
    decoded images, not the dataset: a record batch is decoded whole before
    the next is read, never ahead.

    The package's own decoder (``decode_f`` left out, or :func:`PIL_decode`
    itself) runs on the shared io pool for a batch of 4 files or more — PIL
    releases the GIL inside the JPEG decoder — with the rows kept in file
    order.  A caller's ``decode_f`` is called on the caller's thread, one
    file after the other: the package cannot know that it is safe on
    threads.  ``io.decode``'s ``workers`` says how many threads decoded.

    What is copied: on the pool a row's pixels are written once after the
    decoder, by the BGR flip, into the record batch's Arrow ``data``
    buffer (laid out from the files' headers), and never again.  Arrays
    that a ``decode_f`` returns are copied once into that buffer
    (:func:`~sparkdl_tpu.image.schema.imageStructArray`).  Only a record
    batch in which a ``decode_f`` returned struct dicts goes the old road:
    ``tobytes`` a row, then ``pa.array``.  ``io.to_arrow``'s ``direct_rows``
    counts the rows of the first two kinds.  A batch whose pixels pass
    2 GiB (int32 offsets) comes as several record batches."""
    decode = decode_f if decode_f is not None else PIL_decode
    files = _list_files(path, recursive=recursive)
    batch_size = max(1, int(batch_size))
    sizes = [batch_size] * -(-len(files) // batch_size)
    for array in _struct_chunks(files, sizes, decode):
        yield pa.RecordBatch.from_arrays([array], names=["image"])


def filesToDF(path: str, numPartitions: Optional[int] = None,
              recursive: bool = False):
    """Read raw files into a DataFrame ``{filePath: str, fileData: binary}``.

    Counterpart of ``imageIO.filesToDF`` (which wraps ``sc.binaryFiles``).
    ``numPartitions`` controls batch chunking of the resulting frame.  For
    datasets that don't fit in host RAM, use :func:`iterFileBatches` +
    ``transformStream`` instead of materializing a frame.
    """
    from sparkdl_tpu.frame import DataFrame

    table = pa.Table.from_batches(
        list(iterFileBatches(path, batch_size=1 << 30, recursive=recursive)),
        schema=pa.schema([pa.field("filePath", pa.string()),
                          pa.field("fileData", pa.binary())]))
    df = DataFrame(table)
    if numPartitions:
        df = df.repartition(numPartitions)
    return df


def readImagesWithCustomFn(path: str, decode_f: Callable[[bytes], Optional[np.ndarray]],
                           numPartitions: Optional[int] = None,
                           recursive: bool = False):
    """Read images under ``path`` using a custom decoder into an image-struct
    DataFrame.  Counterpart of ``imageIO.readImagesWithCustomFn``; rows whose
    decode fails become null image structs (kept, so origins stay auditable).
    For datasets that don't fit in host RAM, use :func:`iterImageBatches` +
    ``transformStream`` instead of materializing a frame.

    ``decode_f`` is called on the caller's thread, once a file, in file
    order — the reference ran it in separate worker processes, and a
    function with a shared buffer, a session, or work of its own on this
    package's io pool is not safe on threads.  Only the package's own
    :func:`PIL_decode` (what :func:`readImages` passes) is fanned out over
    the io pool.

    What is copied (see :func:`iterImageBatches`): with the package's
    decoder each of the ``numPartitions`` partitions (record batches of 256
    files where it is left out) is built as asked, its pixels written once
    into its one Arrow buffer, and nothing is copied after — a partition
    whose pixels pass 2 GiB comes as several chunks under that.  With a
    caller's ``decode_f`` the sizes are not known before the decode: the
    frame is built in record batches of 256 files and ``repartition`` copies
    it once more where those are not the partitions asked for
    (``io.repartition``'s ``copied_bytes``)."""
    from sparkdl_tpu.frame import DataFrame
    from sparkdl_tpu.frame.dataframe import partition_sizes

    tracer = get_tracer()
    with tracer.span("io.read_images") as root:
        files = _list_files(path, recursive=recursive)
        as_asked = bool(numPartitions) and decode_f is PIL_decode
        sizes = ([n for n in partition_sizes(len(files), numPartitions) if n]
                 if as_asked else [256] * -(-len(files) // 256))
        arrays = list(_struct_chunks(files, sizes, decode_f))
        with tracer.span("io.repartition") as sp:
            built = DataFrame(pa.table(
                {"image": pa.chunked_array(arrays, type=imageSchema)}))
            df = (built.repartition(numPartitions)
                  if numPartitions and not as_asked else built)
            # combine_chunks leaves a column of one chunk where it lies
            sp.annotate(rows=len(df), partitions=df.num_partitions,
                        copied_bytes=0 if df is built else sum(
                            col.nbytes for col in built.table.columns
                            if col.num_chunks > 1))
        root.annotate(files=len(df), rows=len(df),
                      null_rows=df.table.column("image").null_count,
                      partitions=df.num_partitions)
    return df


def readImages(path: str, numPartitions: Optional[int] = None,
               recursive: bool = False):
    """Read images with the default PIL decoder (BGR uint8)."""
    return readImagesWithCustomFn(path, PIL_decode, numPartitions, recursive)
