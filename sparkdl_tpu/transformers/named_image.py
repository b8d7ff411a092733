"""Named pretrained-model transformers.

Replaces ``python/sparkdl/transformers/named_image.py`` (C3:
``DeepImagePredictor``, ``DeepImageFeaturizer``, ``_NamedImageTransformer``)
and the Scala fast path (C13 ``DeepImageFeaturizer.scala``): zoo-model
inference over an image-struct column.  The reference's two execution paths
(Python tf.Session vs. Scala TensorFrames) collapse into one: a
jit-compiled, mesh-sharded XLA program (parallel.engine).

Also hosts :class:`TFImageTransformer` — the arbitrary-model-over-images
stage (C4 ``tf_image.py``), which here takes a :class:`ModelFunction`
instead of a TF graph.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

from sparkdl_tpu.frame import list_column, list_values_nbytes
from sparkdl_tpu.image.io import arrowStructsToBatch
from sparkdl_tpu.image.schema import imageArrayToStruct, imageSchema
from sparkdl_tpu.models import get_model_spec, load_model
from sparkdl_tpu.models.imagenet import decode_predictions
from sparkdl_tpu.obs.trace import get_tracer
from sparkdl_tpu.param.converters import SparkDLTypeConverters
from sparkdl_tpu.param.params import Param, TypeConverters, keyword_only
from sparkdl_tpu.param.shared import (HasBatchSize, HasInputCol, HasModelName,
                                      HasOutputCol, HasOutputMode, HasTopK)
from sparkdl_tpu.parallel.engine import InferenceEngine, get_cached_engine
from sparkdl_tpu.persistence import PersistableModelFunctionMixin
from sparkdl_tpu.transformers.base import Transformer
from sparkdl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# Process-wide caches: zoo weights load once, engines compile once per
# (model, purpose, batch).  The analog of the reference broadcasting one
# GraphDef per stage rather than per partition.
_MODEL_CACHE: Dict[str, tuple] = {}
_ENGINE_CACHE: Dict[tuple, InferenceEngine] = {}


def clear_model_caches():
    _MODEL_CACHE.clear()
    _ENGINE_CACHE.clear()


def _cached_model(name: str):
    if name not in _MODEL_CACHE:
        _MODEL_CACHE[name] = load_model(name)
    return _MODEL_CACHE[name]


def set_zoo_model(name: str, module, variables) -> None:
    """Serve ``(module, variables)`` as zoo model ``name`` in this
    process, in place of the weights import (which needs a weights file
    or the network): every zoo stage and server resolves its weights
    through the one process cache this fills.  Engines built on the
    model's earlier weights are dropped."""
    name = get_model_spec(name).name
    _MODEL_CACHE[name] = (module, variables)
    for key in [k for k in _ENGINE_CACHE if k[0] == name]:
        del _ENGINE_CACHE[key]


def zoo_compute_dtype_name() -> str:
    """Canonicalized ``SPARKDL_ZOO_COMPUTE_DTYPE`` ("float32" or
    "bfloat16"); raises on unsupported values.  One parser for the engine
    cache key, the serving resolver, and the program auditor — the
    declared compute dtype graftcheck GC002 enforces must be read the
    same way everywhere."""
    import os

    cdt_name = os.environ.get("SPARKDL_ZOO_COMPUTE_DTYPE", "").lower()
    if cdt_name not in ("", "float32", "f32", "bfloat16", "bf16"):
        raise ValueError(
            f"SPARKDL_ZOO_COMPUTE_DTYPE={cdt_name!r} not supported; use "
            f"'bfloat16' or 'float32'")
    return {"bf16": "bfloat16", "f32": "float32", "": "float32"}.get(
        cdt_name, cdt_name)


def zoo_model_fn(name: str, featurize: bool, compute_dtype=None,
                 module=None):
    """THE ``fn(variables, x)`` the zoo engine jit-compiles: fused
    preprocess, optional cast to the compute dtype, inference-mode apply
    at the featurizer or predictor cut.  ``module`` defaults to a fresh
    ``spec.build()`` — the program auditor (``analysis.program``) builds
    the fn this way with abstract variables (no weights, no device), so
    the audited program is the served program by construction."""
    spec = get_model_spec(name)
    if module is None:
        module = spec.build()
    pre = spec.preprocess
    cdt = compute_dtype

    def fn(v, x):  # x: uint8 RGB [B,H,W,3]
        xf = pre(x)
        if cdt is not None:
            xf = xf.astype(cdt)
        return module.apply(v, xf, train=False, features=featurize)

    return fn


def zoo_serving_bundle(name: str, featurize: bool,
                       feature_cut: bool = False):
    """``(fn, variables, engine_overrides)`` for serving zoo model
    ``name`` — THE zoo resolution the online stack shares: weights via
    the process cache, the fn through :func:`zoo_model_fn` (so served ==
    transformed == audited stays true by construction), and the
    ``SPARKDL_ZOO_COMPUTE_DTYPE`` contract as engine overrides (bf16
    compute + f32 host cast under the bench configuration).  Used by
    ``serving.server._resolve_model`` and the fleet model registry
    (``serving.fleet.registry``); the registry resolves ONCE per entry
    and reuses the fn across versions, which is what lets a hot-swapped
    version reuse the compiled executable instead of re-jitting.

    ``feature_cut=True`` (head fan-out tier, ISSUE 17) instead returns
    the SPLIT bundle ``(backbone_fn, variables, engine_overrides,
    head_fn)``: ``backbone_fn`` is the featurizer-cut fn — the exact
    object the featurize programs in ``PROGRAMS.lock.json`` pin, built
    through the same :func:`zoo_model_fn` path, so backbone identity
    (jit object + StableHLO fingerprint) can NEVER change when tenant
    heads churn — and ``head_fn`` is the canonical per-row head
    (``parallel.engine.dense_head_row``) the vmapped
    ``build_head_fanout_jit`` program serves over a
    :class:`~sparkdl_tpu.parallel.engine.HeadBank`."""
    module, zoo_vars = _cached_model(name)
    cdt = None
    # GC001's recorded zoo exemption, enforced where the engines are
    # built: the uint8 image batch can never alias the float feature
    # output, so declaring the donation would only make XLA drop it —
    # the serving auto-donation probe must not even try
    # (analysis.program.inventory.ZOO_DONATE_REASON).
    # Weight sharding (ISSUE 14): the zoo family's default partition
    # rules ride the overrides — flax kernels split their output dim
    # across the mesh's model axis when it is >1 (per-chip HBM =
    # bytes/model_axis), and resolve all-replicated (byte-identical
    # programs) on the model-axis-1 meshes every current zoo config
    # uses.  An explicit Server partition_rules/param_shardings wins.
    from sparkdl_tpu.parallel import mesh as _mesh_lib

    overrides: Dict[str, object] = {
        "donate_batch": False,
        "partition_rules": _mesh_lib.default_partition_rules,
    }
    if zoo_compute_dtype_name() == "bfloat16":
        import jax.numpy as jnp

        cdt = jnp.bfloat16
        overrides.update({"compute_dtype": jnp.bfloat16,
                          "output_host_dtype": np.float32})
    if feature_cut and not featurize:
        raise ValueError(
            "feature_cut=True requires featurize=True: the split's "
            "backbone program IS the featurizer cut (the head fan-out "
            "tier has no predictor-cut backbone)")
    fn = zoo_model_fn(name, featurize=featurize, compute_dtype=cdt,
                      module=module)
    if feature_cut:
        from sparkdl_tpu.parallel.engine import dense_head_row

        return fn, zoo_vars, overrides, dense_head_row
    return fn, zoo_vars, overrides


def _zoo_engine(name: str, featurize: bool, batch_size: int) -> InferenceEngine:
    """One cached engine per (model, cut, batch).

    ``SPARKDL_ZOO_COMPUTE_DTYPE=bfloat16`` runs the zoo model in bf16 (the
    bench's configuration: ~MXU-native, and outputs are fetched in bf16
    then cast to f32 on the HOST — bit-identical features, half the D2H
    bytes).  Default stays float32: the reference's scoring contract is
    f32 end-to-end and the parity oracles are f32.
    """
    cdt_name = zoo_compute_dtype_name()
    key = (name, featurize, batch_size, cdt_name)
    eng = _ENGINE_CACHE.get(key)
    if eng is None:
        import jax.numpy as jnp

        module, variables = _cached_model(name)
        cdt = jnp.bfloat16 if cdt_name == "bfloat16" else None
        fn = zoo_model_fn(name, featurize, compute_dtype=cdt, module=module)
        eng = InferenceEngine(
            fn, variables, device_batch_size=batch_size,
            compute_dtype=cdt,
            output_host_dtype=np.float32 if cdt is not None else None)
        _ENGINE_CACHE[key] = eng
    return eng


class _ImageInputStage(Transformer, HasInputCol, HasOutputCol, HasBatchSize):
    """Shared plumbing: pull the image-struct column, decode/resize valid
    rows into dense batches, keep nulls aligned (undecodable rows stay null
    — the reference's imageIO drops-to-null contract).

    The decode is STREAMING: the column is consumed one record batch at a
    time (the analog of the reference's per-partition hot loop, SURVEY.md
    §3.1) — at no point does a whole-dataset ``[N,H,W,3]`` array exist.
    Host decode of chunk k+1 runs on the runner's prepare thread while the
    device computes chunk k, and the engine bounds in-flight device buffers."""

    def _first_valid_struct(self, dataset) -> Optional[dict]:
        """First non-null image struct, without materializing the column."""
        col_idx = dataset.table.column_names.index(self.getInputCol())
        for rb in dataset.iter_batches(64):
            for s in rb.column(col_idx).to_pylist():
                if s is not None:
                    return s
        return None

    def _decoded_chunks(self, dataset, height: int, width: int,
                        chunk_rows: int, valid_idx: List[int],
                        origins: Optional[List[str]] = None):
        """Generator of decoded [b,h,w,3] uint8 RGB chunks over valid rows.

        Side effects as it advances: appends the global row index of each
        valid row to ``valid_idx`` (and its origin to ``origins`` if given)
        so the caller can re-align outputs with null rows after the stream
        is drained."""
        name = self.getInputCol()
        col_idx = dataset.table.column_names.index(name)
        offset = 0
        tracer = get_tracer()
        for rb in dataset.iter_batches(chunk_rows):
            col = rb.column(col_idx)
            # closed before the yield: the span nests under whoever pulls
            # this generator and is never left open on that thread
            with tracer.span("transform.pack_in", rows=len(col)) as sp:
                # zero-copy struct packing (no per-row dict
                # materialization); compact=True: the batch holds only
                # the decodable rows
                batch, ok = arrowStructsToBatch(col, height, width,
                                                compact=True)
                vi_local = np.nonzero(ok)[0]
                sp.annotate(valid=len(vi_local))
                if len(vi_local):
                    valid_idx.extend(int(offset + i) for i in vi_local)
                    if origins is not None:
                        ocol = col.field("origin")
                        origins.extend(
                            (ocol[int(i)].as_py() or "") for i in vi_local)
            if len(vi_local):
                yield batch
            offset += len(col)

    def _chunk_rows(self) -> int:
        """Decode granularity: batchSize rounded up to the data-axis size,
        computed WITHOUT building an engine (mesh construction is cheap;
        engine construction loads weights and compiles)."""
        from sparkdl_tpu.parallel import mesh as mesh_lib

        dp = mesh_lib.get_mesh().shape[mesh_lib.DATA_AXIS]
        b = max(1, int(self.getBatchSize()))
        return b + (-b % dp)

    def _stream_model_outputs(self, dataset, engine_factory, height: int,
                              width: int, valid_idx: List[int],
                              origins: Optional[List[str]] = None):
        """Lazily yield per-chunk model outputs for the image column.

        Fills ``valid_idx`` (and ``origins``) as a side effect; yields
        nothing when no row decodes.  The engine (weights + compile) is
        only built once the first decoded chunk proves there is work to
        do.  Consumers that pack outputs incrementally (image mode) keep
        peak host residency at O(chunk), not O(dataset)."""
        from itertools import chain

        import time

        it = iter(self._decoded_chunks(
            dataset, height, width, self._chunk_rows(), valid_idx, origins))
        first = next(it, None)
        if first is None:
            return
        engine = engine_factory()
        t0 = time.perf_counter()
        yield from engine.map_batches(chain([first], it))
        elapsed = time.perf_counter() - t0
        n, ndev = len(valid_idx), engine.num_devices
        ips = n / elapsed if elapsed > 0 else float("inf")
        logger.info("%s: %d images in %.3fs — %.1f img/s "
                    "(%.1f img/s/chip over %d devices)",
                    type(self).__name__, n, elapsed, ips, ips / ndev, ndev)

    def _run_streaming(self, dataset, engine_factory, height: int,
                       width: int, origins: Optional[List[str]] = None):
        """Stream the image column through the engine; returns (outputs
        [n_valid, ...] or None when nothing decoded, valid_idx).  For
        small-row outputs (vectors/probabilities) concatenating is cheap;
        image-sized outputs should consume :meth:`_stream_model_outputs`
        directly instead."""
        import jax

        valid_idx: List[int] = []
        outs = list(self._stream_model_outputs(
            dataset, engine_factory, height, width, valid_idx, origins))
        if not outs:
            return None, valid_idx
        out = jax.tree_util.tree_map(
            lambda *parts: np.concatenate(parts, axis=0), *outs)
        return out, valid_idx


class _NamedImageTransformer(_ImageInputStage, HasModelName):
    """Base of the zoo stages — resolves modelName against the registry
    (same role as the reference's ``SUPPORTED_MODELS`` lookup)."""

    featurize: bool = False

    def __init__(self):
        super().__init__()
        from sparkdl_tpu.models import SUPPORTED_MODELS

        self.modelName.typeConverter = SparkDLTypeConverters.supportedNameConverter(
            SUPPORTED_MODELS)
        self._setDefault(batchSize=64)

    def engine(self) -> InferenceEngine:
        """The engine ``transform`` runs this stage on under the current
        environment — process-wide, one per (model, cut, batch, compute
        dtype), built on first use."""
        return _zoo_engine(self.getModelName(), self.featurize,
                           self.getBatchSize())

    def _run_model(self, dataset) -> Tuple[np.ndarray, list, int]:
        name = self.getModelName()
        spec = get_model_spec(name)
        h, w = spec.input_size
        out, valid_idx = self._run_streaming(dataset, self.engine, h, w)
        if out is None:
            dim = spec.feature_size if self.featurize else 1000
            return np.zeros((0, dim), np.float32), valid_idx, len(dataset)
        return np.asarray(out), valid_idx, len(dataset)

    def _output_column(self, out: np.ndarray, valid_idx: List[int],
                       num_rows: int) -> Tuple[pa.Array, int]:
        """The output column from the model's rows ``out`` at positions
        ``valid_idx``, nulls elsewhere; and how many of its values were
        Python objects on the way."""
        return list_column(out, valid_idx, num_rows), 0

    def _transform(self, dataset):
        tracer = get_tracer()
        with tracer.span("transform.run", model=self.getModelName(),
                         batch_size=self.getBatchSize()) as root:
            out, valid_idx, n = self._run_model(dataset)
            root.annotate(rows=n, valid_rows=len(valid_idx))
            with tracer.span("transform.pack_out", rows=len(valid_idx),
                             values=int(out.size)) as sp:
                col, py_values = self._output_column(out, valid_idx, n)
                sp.annotate(bytes=list_values_nbytes(col),
                            null_rows=col.null_count, py_values=py_values)
                return dataset.withColumn(self.getOutputCol(), col)


class DeepImageFeaturizer(_NamedImageTransformer):
    """Zoo-model featurization for transfer learning.

    Counterpart of the reference's ``DeepImageFeaturizer`` (Python wrapper +
    Scala implementation): output column holds the penultimate-layer vector
    (e.g. 2048-d for InceptionV3), ready for any downstream classifier.
    """

    featurize = True

    @keyword_only
    def __init__(self, inputCol: Optional[str] = None,
                 outputCol: Optional[str] = None,
                 modelName: Optional[str] = None,
                 batchSize: Optional[int] = None):
        super().__init__()
        self._set(**self._input_kwargs)

    @keyword_only
    def setParams(self, inputCol: Optional[str] = None,
                  outputCol: Optional[str] = None,
                  modelName: Optional[str] = None,
                  batchSize: Optional[int] = None):
        return self._set(**self._input_kwargs)


class DeepImagePredictor(_NamedImageTransformer):
    """Zoo-model prediction.

    Counterpart of the reference's ``DeepImagePredictor``: class
    probabilities, optionally decoded to top-K ``(class, description,
    probability)`` structs (``_decodeOutputAsPredictions``).
    """

    featurize = False

    decodePredictions = Param(
        "undefined", "decodePredictions",
        "decode the output probabilities into top-K (class, description, "
        "probability) rows", typeConverter=TypeConverters.toBoolean)

    topK = HasTopK.topK

    @keyword_only
    def __init__(self, inputCol: Optional[str] = None,
                 outputCol: Optional[str] = None,
                 modelName: Optional[str] = None,
                 decodePredictions: bool = False,
                 topK: int = 5,
                 batchSize: Optional[int] = None):
        super().__init__()
        self._setDefault(decodePredictions=False, topK=5)
        self._set(**self._input_kwargs)

    @keyword_only
    def setParams(self, inputCol: Optional[str] = None,
                  outputCol: Optional[str] = None,
                  modelName: Optional[str] = None,
                  decodePredictions: Optional[bool] = None,
                  topK: Optional[int] = None,
                  batchSize: Optional[int] = None):
        return self._set(**self._input_kwargs)

    def getDecodePredictions(self):
        return self.getOrDefault(self.decodePredictions)

    def getTopK(self):
        return self.getOrDefault(self.topK)

    def _output_column(self, probs, valid_idx, n):
        if not self.getDecodePredictions():
            return super()._output_column(probs, valid_idx, n)
        decoded = decode_predictions(probs, top=self.getTopK())
        pred_type = pa.list_(pa.struct([
            pa.field("class", pa.string()),
            pa.field("description", pa.string()),
            pa.field("probability", pa.float32()),
        ]))
        values: List[Optional[list]] = [None] * n
        for row, i in zip(decoded, valid_idx):
            values[i] = [
                {"class": c, "description": d, "probability": p}
                for c, d, p in row]
        # each decoded probability is a Python float in a Python dict
        return (pa.array(values, type=pred_type),
                sum(len(row) for row in decoded))


class TFImageTransformer(PersistableModelFunctionMixin, _ImageInputStage,
                         HasOutputMode):
    """Arbitrary model over the image column.

    Counterpart of the reference's ``TFImageTransformer`` (C4): where that
    shipped a merged GraphDef (image-converter subgraph ∘ user graph) to
    TensorFrames, this applies a user :class:`ModelFunction` to the decoded
    uint8 RGB batch inside one jit program.  ``outputMode="vector"`` emits a
    flat float vector per row; ``"image"`` re-packs a [H,W,3] float output
    as an image struct.
    """

    modelFunction = Param(
        "undefined", "modelFunction",
        "ModelFunction applied to the decoded [B,H,W,3] uint8 RGB batch",
        typeConverter=SparkDLTypeConverters.toModelFunction)

    inputSize = Param(
        "undefined", "inputSize",
        "[height, width] the images are resized to before the model; "
        "defaults to the first row's stored size",
        typeConverter=TypeConverters.toList)

    @keyword_only
    def __init__(self, inputCol: Optional[str] = None,
                 outputCol: Optional[str] = None,
                 modelFunction=None,
                 inputSize: Optional[Sequence[int]] = None,
                 outputMode: str = "vector",
                 batchSize: Optional[int] = None):
        super().__init__()
        self._setDefault(outputMode="vector", batchSize=64)
        self._set(**self._input_kwargs)

    @keyword_only
    def setParams(self, inputCol: Optional[str] = None,
                  outputCol: Optional[str] = None,
                  modelFunction=None,
                  inputSize: Optional[Sequence[int]] = None,
                  outputMode: Optional[str] = None,
                  batchSize: Optional[int] = None):
        return self._set(**self._input_kwargs)

    def getModelFunction(self):
        return self.getOrDefault(self.modelFunction)

    def transformStream(self, batches, params=None):
        """Stream with a CONSISTENT inferred input size: when ``inputSize``
        is unset, it is resolved once from the first valid struct and pinned
        for the whole stream — per-batch re-inference would let batches with
        different first-image sizes emit different feature dims into one
        column."""
        if params:
            yield from self.copy(params).transformStream(batches)
            return
        if self.isDefined(self.inputSize):
            yield from super().transformStream(batches)
            return
        from itertools import chain

        from sparkdl_tpu.frame import DataFrame

        it = iter(batches)
        buffered, size = [], None
        for rb in it:
            buffered.append(rb)
            s = self._first_valid_struct(DataFrame(rb))
            if s is not None:
                size = [int(s["height"]), int(s["width"])]
                break
        if size is None:
            raise ValueError(
                f"No decodable images in column {self.getInputCol()!r}")
        pinned = self.copy({"inputSize": size})
        yield from pinned.transformStream(chain(buffered, it))

    def _transform(self, dataset):
        if self.isDefined(self.inputSize):
            h, w = (int(v) for v in self.getOrDefault(self.inputSize))
        else:
            first = self._first_valid_struct(dataset)
            if first is None:
                raise ValueError(
                    f"No decodable images in column {self.getInputCol()!r}")
            h, w = int(first["height"]), int(first["width"])
        n = len(dataset)
        mode = self.getOutputMode()
        factory = lambda: get_cached_engine(  # noqa: E731
            self, self.getModelFunction(),
            device_batch_size=self.getBatchSize())
        if mode == "image":
            return self._transform_image_mode(dataset, factory, h, w, n)
        origins: List[str] = []
        out, valid_idx = self._run_streaming(dataset, factory, h, w,
                                             origins=origins)
        if out is None:
            # Nothing decodable but the size was known (explicit or pinned
            # by transformStream): keep the drop-to-null contract — an
            # all-null record batch mid-stream must not kill the job.
            out = np.zeros((0, 0), np.float32)
        return dataset.withColumn(
            self.getOutputCol(), list_column(out, valid_idx, n))

    def _transform_image_mode(self, dataset, engine_factory, h, w, n):
        """Image-sized outputs are packed to structs PER CHUNK as the
        engine yields them (VERDICT r2 weak #5): at no point does a
        whole-dataset float output array exist — peak residency is the
        arrow column under construction plus O(engine window) chunks."""
        origins: List[str] = []
        valid_idx: List[int] = []
        packed: List[dict] = []
        consumed = 0
        for out in self._stream_model_outputs(
                dataset, engine_factory, h, w, valid_idx, origins):
            out = np.asarray(out)
            if out.ndim != 4:
                raise ValueError(
                    f'outputMode="image" needs [B,H,W,C] model output, got '
                    f"shape {out.shape}")
            for row, origin in zip(out, origins[consumed:consumed + len(out)]):
                if row.shape[-1] == 3:
                    row = row[:, :, ::-1]  # model RGB -> struct BGR
                elif row.shape[-1] == 4:
                    # RGBA -> BGRA: flip color channels, keep alpha last
                    # (the CV_8UC4/CV_32FC4 struct convention).
                    row = row[:, :, [2, 1, 0, 3]]
                packed.append(imageArrayToStruct(
                    np.ascontiguousarray(row, dtype=np.float32),
                    origin=origin))
            consumed += len(out)
        values: List[Optional[dict]] = [None] * n
        for struct, i in zip(packed, valid_idx):
            values[i] = struct
        return dataset.withColumn(
            self.getOutputCol(), pa.array(values, type=imageSchema))
