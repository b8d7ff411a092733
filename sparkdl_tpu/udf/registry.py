"""UDF registry + builders.

``registerKerasImageUDF(name, model, preprocessor)`` keeps the reference's
composition contract (``udf/keras_image_model.py``): [image-struct
converter] ∘ [optional preprocessor] ∘ [model] fused into ONE program — here
one XLA program instead of one merged GraphDef.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

from sparkdl_tpu.utils.logging import get_logger

logger = get_logger(__name__)


# Declared return types -> arrow types for apply()/pandas_udf emission.
_RETURN_TYPES = {
    "array<float>": pa.list_(pa.float32()),
    "array<double>": pa.list_(pa.float64()),
    "float": pa.float32(),
    "double": pa.float64(),
    "int": pa.int64(),
    "bigint": pa.int64(),
    "string": pa.string(),
    "boolean": pa.bool_(),
}


class RegisteredUDF:
    """A vectorized function column -> column with engine caching."""

    def __init__(self, name: str, fn: Callable[[Sequence], List],
                 returns: str = "array<float>"):
        if returns not in _RETURN_TYPES:
            raise ValueError(f"Unsupported UDF return type {returns!r}; "
                             f"supported: {sorted(_RETURN_TYPES)}")
        self.name = name
        self.fn = fn
        self.returns = returns

    @property
    def arrow_type(self) -> pa.DataType:
        return _RETURN_TYPES[self.returns]

    def __call__(self, column) -> List:
        """column: sequence / pyarrow Array / pandas Series of row values.

        Arrow-aware UDFs (``fn.accepts_arrow``) receive the Arrow column
        as-is — the image hot path reads struct buffers zero-copy instead
        of round-tripping every row through a Python dict (``to_pylist``).
        """
        if isinstance(column, (pa.Array, pa.ChunkedArray)):
            if getattr(self.fn, "accepts_arrow", False):
                return self.fn(column)
            column = column.to_pylist()
        elif hasattr(column, "tolist") and not isinstance(column, list):
            column = column.tolist()
        return self.fn(list(column))


class UDFRegistry:
    """Process-wide name -> UDF map (the stand-in for Spark's SQL function
    registry; ``spark.sql`` is replaced by ``apply`` over our frames)."""

    def __init__(self):
        self._udfs: Dict[str, RegisteredUDF] = {}

    def register(self, name: str, fn: Callable, returns: str = "array<float>"
                 ) -> RegisteredUDF:
        udf = fn if isinstance(fn, RegisteredUDF) else RegisteredUDF(
            name, fn, returns)
        self._udfs[name] = udf
        logger.info("registered UDF %r", name)
        return udf

    def get(self, name: str) -> RegisteredUDF:
        if name not in self._udfs:
            raise KeyError(f"No UDF named {name!r}; registered: "
                           f"{sorted(self._udfs)}")
        return self._udfs[name]

    def names(self) -> List[str]:
        return sorted(self._udfs)

    def apply(self, name: str, dataset, inputCol: str, outputCol: str):
        """SELECT name(inputCol) AS outputCol equivalent over a DataFrame."""
        udf = self.get(name)
        values = udf(dataset.table.column(inputCol))
        return dataset.withColumn(outputCol, pa.array(
            values, type=udf.arrow_type))

    def to_pandas_udf(self, name: str):
        """Bind to pyspark's pandas_udf when pyspark is installed (the
        reference's [D->J] registration step; optional here)."""
        try:
            import pandas as pd
            from pyspark.sql.functions import pandas_udf
        except ImportError as e:
            raise ImportError(
                "pyspark is not installed; to_pandas_udf requires it "
                f"({e})") from e
        udf = self.get(name)

        @pandas_udf(udf.returns)
        def _udf(col: "pd.Series") -> "pd.Series":
            return pd.Series(udf(col))

        return _udf


udf_registry = UDFRegistry()
register_udf = udf_registry.register


def _first_valid_hw(column) -> Optional[Tuple[int, int]]:
    """(height, width) of the first non-null struct row, scanning chunk by
    chunk (no combine_chunks — its int32 offsets overflow past 2 GB)."""
    chunks = (column.chunks if isinstance(column, pa.ChunkedArray)
              else [column])
    for ch in chunks:
        valid = np.asarray(ch.is_valid()) if len(ch) else np.zeros(0, bool)
        if valid.any():
            i0 = int(np.nonzero(valid)[0][0])
            return (int(ch.field("height")[i0].as_py()),
                    int(ch.field("width")[i0].as_py()))
    return None


def _model_input_hw(keras_model) -> Optional[Tuple[int, int]]:
    shape = getattr(keras_model, "input_shape", None)
    if shape and len(shape) == 4 and shape[1] and shape[2]:
        return int(shape[1]), int(shape[2])
    return None


def register_image_udf(name: str, model_function, *,
                       input_size: Optional[Sequence[int]] = None,
                       preprocessor: Optional[Callable] = None,
                       batch_size: int = 32,
                       registry: Optional[UDFRegistry] = None) -> RegisteredUDF:
    """Register a ModelFunction as an image-column UDF.

    Pipeline per call: decode/resize image structs on the host (null rows
    stay null) -> [optional jax ``preprocessor``] ∘ model in one jit program
    on the mesh.  Scoring rides the engine's pipelined path: a multi-batch
    column overlaps H2D, compute, and gather across chunks, and the output
    matrix is preallocated and streamed into, not accumulated per chunk.
    """
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.image.io import arrowStructsToBatch, structsToBatch
    from sparkdl_tpu.parallel.engine import get_cached_engine

    # Host batches are uint8 **BGR** (the struct's native byte order — host
    # packing stays a pure memcpy); the struct-converter stage swaps to RGB
    # and casts to float ([0,255]) INSIDE the fused program, exactly where
    # the reference's buildSpImageConverter subgraph did both.  The user
    # preprocessor / model sees RGB floats.
    converter = ModelFunction.from_callable(
        lambda x: x[..., ::-1].astype("float32"))
    if preprocessor is not None:
        converter = converter.compose(
            ModelFunction.from_callable(preprocessor))
    model_function = converter.compose(model_function)
    holder = _EngineHolder()  # one engine cache per registration

    def _score(batch: np.ndarray, valid_idx, n: int) -> List[Optional[list]]:
        out: List[Optional[list]] = [None] * n
        if batch.shape[0] == 0:
            return out
        eng = get_cached_engine(holder, model_function,
                                device_batch_size=batch_size)
        # pipelined __call__: pad of chunk k+1 overlaps compute of k and
        # gather of k-1, streaming into one preallocated [n_valid, ...]
        res = np.asarray(eng(batch))
        flat = res.reshape(res.shape[0], -1).astype(np.float32)
        for row_list, i in zip(flat.tolist(), valid_idx):
            out[i] = row_list
        return out

    def fn(rows) -> List[Optional[list]]:
        if isinstance(rows, (pa.Array, pa.ChunkedArray)):
            # Zero-copy hot path: struct buffers -> batch, no dict per row.
            if input_size is not None:
                h, w = int(input_size[0]), int(input_size[1])
            else:
                hw = _first_valid_hw(rows)
                if hw is None:
                    return [None] * len(rows)
                h, w = hw
            batch, ok = arrowStructsToBatch(rows, h, w,
                                            channel_order="bgr",
                                            compact=True)
            return _score(batch, np.nonzero(ok)[0], len(rows))
        valid_idx = [i for i, r in enumerate(rows) if r is not None]
        if not valid_idx:
            return [None] * len(rows)
        if input_size is not None:
            h, w = int(input_size[0]), int(input_size[1])
        else:
            first = rows[valid_idx[0]]
            h, w = int(first["height"]), int(first["width"])
        # legacy list-of-dicts path: structsToBatch emits RGB; the fused
        # converter expects BGR, so flip back (off the Arrow hot path)
        batch = structsToBatch([rows[i] for i in valid_idx], h, w)
        return _score(np.ascontiguousarray(batch[..., ::-1]),
                      valid_idx, len(rows))

    fn.accepts_arrow = True

    registry = registry if registry is not None else udf_registry
    return registry.register(name, fn)


class _EngineHolder:
    """Plain object whose __dict__ hosts get_cached_engine's cache."""


def register_serving_udf(name: str, server, *, returns: str = "array<float>",
                         max_admission_retries: int = 100,
                         timeout_ms: float = float("inf"),
                         registry: Optional[UDFRegistry] = None
                         ) -> RegisteredUDF:
    """Register a running ``serving.Server`` as a column UDF.

    Each row becomes ONE request on the server's admission queue, so
    offline column scoring and any concurrent online traffic share the
    same dynamic micro-batches, deadlines, and metrics — the offline API
    riding the online path.  All rows are submitted asynchronously before
    any result is awaited, letting the batcher fill micro-batches instead
    of ping-ponging one row at a time.

    Backpressure is honored, not bypassed: a ``QueueFullError`` sleeps the
    server's ``retry_after_s`` hint and resubmits, up to
    ``max_admission_retries`` per row.  Null rows stay null.

    Offline rows carry NO deadline by default (``timeout_ms=inf``
    overrides the server's ``default_timeout_ms``): a bulk column submit
    parks most rows deep in the queue, where an online-sized deadline
    would shed the tail and fail the whole apply — offline flow control
    is the backpressure loop above, not deadlines.  Pass a finite
    ``timeout_ms`` to opt back in to shedding.
    """
    import time as _time

    from sparkdl_tpu.serving.errors import QueueFullError

    def _submit_with_backoff(value):
        for _ in range(max(1, int(max_admission_retries))):
            try:
                return server.submit(value, timeout_ms=timeout_ms)
            except QueueFullError as e:
                _time.sleep(max(1e-3, e.retry_after_s))
        # final attempt: let rejection raise
        return server.submit(value, timeout_ms=timeout_ms)

    def fn(rows) -> List[Optional[list]]:
        if isinstance(rows, (pa.Array, pa.ChunkedArray)):
            rows = rows.to_pylist()
        out: List[Optional[list]] = [None] * len(rows)
        futures = []
        for i, r in enumerate(rows):
            if r is None:
                continue
            if isinstance(r, (list, tuple)):
                # arrow list rows arrive as Python lists; submit() treats
                # a list as a PYTREE of scalars, so densify here (struct
                # rows stay dicts — the server's host_preprocess owns those)
                r = np.asarray(r, dtype=np.float32)
            futures.append((i, _submit_with_backoff(r)))
        for i, fut in futures:
            res = np.asarray(fut.result())
            out[i] = [float(v) for v in res.reshape(-1)]
        return out

    registry = registry if registry is not None else udf_registry
    return registry.register(name, fn, returns=returns)


def registerKerasImageUDF(name: str, model_or_file, preprocessor=None,
                          registry: Optional[UDFRegistry] = None
                          ) -> RegisteredUDF:
    """Reference-parity entry (``udf/keras_image_model.py``): register a
    Keras model (object or saved file) as an image UDF, composing the
    optional ``preprocessor`` (jax-traceable ``batch -> batch``) in front.
    """
    import keras

    from sparkdl_tpu.graph.function import ModelFunction

    if isinstance(model_or_file, (str, bytes)):
        model = keras.models.load_model(model_or_file, compile=False)
    else:
        model = model_or_file
    mf = ModelFunction.from_keras(model)
    return register_image_udf(
        name, mf, input_size=_model_input_hw(model),
        preprocessor=preprocessor, registry=registry)
