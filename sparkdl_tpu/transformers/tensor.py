"""Tensor-column transformers.

Replaces ``python/sparkdl/transformers/tf_tensor.py`` (C5 ``TFTransformer``)
and ``keras_tensor.py`` (C6 ``KerasTransformer``): applying a model to
numeric/array columns.  The reference froze a TF graph and ran it blockwise
through TensorFrames; here the model is a :class:`ModelFunction` jitted over
the mesh.

  * :class:`ModelTransformer` — the native stage: ModelFunction over one
    array column.
  * :class:`KerasTransformer` — loads a user Keras model (file or object),
    converts it to a ModelFunction (graph.keras_convert), then behaves like
    ModelTransformer.  Input rows are 1-D float arrays (reference contract).
  * :class:`TFTransformer` — multi-input/multi-output mapping form: a
    TFInputGraph/ModelFunction plus {column->input} / {output->column}
    maps (reference's feed/fetch wiring).  Integer columns stay
    integers on the way in (token ids) and on the way out (an int32
    list column: ``output_column``), as ``ModelTransformer``'s do.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from sparkdl_tpu.frame import list_column, list_values_nbytes
from sparkdl_tpu.obs.trace import get_tracer
from sparkdl_tpu.param.converters import SparkDLTypeConverters
from sparkdl_tpu.param.params import Param, keyword_only
from sparkdl_tpu.param.shared import HasBatchSize, HasInputCol, HasOutputCol
from sparkdl_tpu.parallel.engine import get_cached_engine
from sparkdl_tpu.persistence import PersistableModelFunctionMixin
from sparkdl_tpu.transformers.base import Transformer


def count_outputs(model_function, out, metrics, keep, tokens=None):
    """What a stage does with a program's outputs that are no columns.
    ``out`` is what the engine gathered (real rows only); ``keep`` the
    outputs that become columns; ``tokens`` the ids of the INPUT rows,
    where rows are sequences (a program that makes positions of its own,
    as a generator does, carries its own count in its counter).  A
    declared counter
    (``ModelFunction.counter_names``) is added to ``metrics`` and taken
    out; an output that is neither is taken out too, and NAMED in what
    is returned beside the kept outputs — nothing is dropped without a
    word.  ``({kept name: array}, {span attribute: value})``."""
    if not isinstance(out, dict):
        return {model_function.output_names[0]: out}, {}
    attrs = {}
    for name in model_function.counter_names:
        if name in out:
            attrs.update(COUNTERS[name](np.asarray(out[name]), metrics,
                                        tokens))
    unmapped = sorted(set(out) - set(keep) - set(model_function.counter_names))
    if unmapped:
        attrs["unmapped_outputs"] = ",".join(unmapped)
    return {name: out[name] for name in keep}, attrs


def _count_expert_load(load, metrics, tokens):
    """``expert_load`` ``[rows, expert layers, held experts]``: tokens of
    a row that each held expert of each layer took.  ``moe.pairs``:
    token-expert pairs computed here; ``moe.tokens``: tokens x expert
    layers that were routed; ``moe.busiest_expert_pairs``: the fullest
    expert's pairs, summed over the layers, of this call's rows."""
    pairs = int(load.sum())
    metrics.incr("moe.pairs", pairs)
    metrics.incr("moe.busiest_expert_pairs",
                 int(load.sum(axis=0).max(axis=-1).sum()))
    if tokens is not None:
        metrics.incr("moe.tokens", tokens * load.shape[1])
    return {"expert_pairs": pairs}


def _count_diffusion(counts, metrics, tokens):
    """``diffusion_counts`` ``[rows, 8]`` (``block_diffusion.COUNTS``): a
    generator's own count of its passes, the ids it revealed, the
    positions it routed (it makes positions of its own, so the input's
    ids say nothing of them) and their pairs, summed over the rows, and
    the experts its loop touched, the slots the loop laid its pairs out
    in (``diffusion.expert_slots``: the loop's pairs over them is the
    slots' fill) and the key positions a row's attention fetched from
    the cache (``diffusion.cache_positions``: the filled lengths' sum
    over them is the share that was needed).  ``tokens`` is the prompts'
    ids."""
    (denoise, commit, revealed, routed, pairs, touched, slots,
     fetched) = (int(v) for v in counts.sum(axis=0))
    for name, value in (("diffusion.denoise_passes", denoise),
                        ("diffusion.commit_passes", commit),
                        ("diffusion.revealed_ids", revealed),
                        ("diffusion.touched_experts", touched),
                        ("diffusion.expert_slots", slots),
                        ("diffusion.cache_positions", fetched),
                        ("moe.tokens", routed), ("moe.pairs", pairs)):
        metrics.incr(name, value)
    attrs = {"generated_ids": revealed, "denoise_passes": denoise,
             "commit_passes": commit, "expert_pairs": pairs,
             "expert_slots": slots}
    if tokens is not None:
        attrs["prompt_tokens"] = tokens
    return attrs


#: how each counter a program may declare reaches the engine's metrics
COUNTERS = {"expert_load": _count_expert_load,
            "diffusion_counts": _count_diffusion}


def output_column(out):
    """A program's output as a list column (``frame.list_column``): an
    integer output stays int32, any other is float32."""
    out = np.asarray(out)
    if np.issubdtype(out.dtype, np.integer):
        return list_column(out, dtype=np.int32)
    return list_column(out)


def _model_input(dataset, col):
    """A column as the array a program is given: token ids stay
    integers; everything else is a float column and reaches the function
    as float32."""
    x = dataset.column_to_numpy(col)
    return x if np.issubdtype(x.dtype, np.integer) else x.astype(np.float32)


class ModelTransformer(PersistableModelFunctionMixin, Transformer,
                       HasInputCol, HasOutputCol, HasBatchSize):
    """Apply a ModelFunction to an array column (one row = one example)."""

    modelFunction = Param(
        "undefined", "modelFunction",
        "ModelFunction applied to the stacked input column",
        typeConverter=SparkDLTypeConverters.toModelFunction)

    @keyword_only
    def __init__(self, inputCol: Optional[str] = None,
                 outputCol: Optional[str] = None,
                 modelFunction=None,
                 batchSize: Optional[int] = None):
        super().__init__()
        self._setDefault(batchSize=64)
        self._set(**self._input_kwargs)

    @keyword_only
    def setParams(self, inputCol: Optional[str] = None,
                  outputCol: Optional[str] = None,
                  modelFunction=None,
                  batchSize: Optional[int] = None):
        return self._set(**self._input_kwargs)

    def getModelFunction(self):
        return self.getOrDefault(self.modelFunction)

    def engine(self):
        """The engine ``transform`` runs this stage on: the one
        ``get_cached_engine`` keeps on the stage for its function and
        batch size, built on first use."""
        return get_cached_engine(self, self.getModelFunction(),
                                 device_batch_size=self.getBatchSize())

    def _transform(self, dataset):
        tracer = get_tracer()
        with tracer.span("transform.run",
                         batch_size=self.getBatchSize()) as root:
            with tracer.span("transform.pack_in", rows=len(dataset)) as sp:
                x = _model_input(dataset, self.getInputCol())
                sp.annotate(bytes=int(x.nbytes))
            root.annotate(rows=len(x))
            if x.ndim == 2:
                root.annotate(tokens=int(x.size))
            engine, mf = self.engine(), self.getModelFunction()
            kept, counted = count_outputs(
                mf, engine(x), engine.metrics, mf.output_names[:1],
                tokens=int(x.size) if x.ndim == 2 else None)
            root.annotate(**counted)
            out = kept[mf.output_names[0]]
            with tracer.span("transform.pack_out", rows=len(out),
                             values=int(np.size(out))) as sp:
                col = output_column(out)
                sp.annotate(bytes=list_values_nbytes(col),
                            null_rows=col.null_count, py_values=0)
                return dataset.withColumn(self.getOutputCol(), col)


class KerasTransformer(ModelTransformer):
    """Apply a user Keras model to a column of 1-D float arrays.

    Counterpart of the reference's ``KerasTransformer``
    (``keras_tensor.py``): ``modelFile`` points at a saved Keras model
    (HDF5/.keras); it is converted once to a jax ModelFunction at first
    transform.
    """

    modelFile = Param(
        "undefined", "modelFile",
        "path to a saved Keras model (.h5/.keras) applied row-wise")

    @keyword_only
    def __init__(self, inputCol: Optional[str] = None,
                 outputCol: Optional[str] = None,
                 modelFile: Optional[str] = None,
                 batchSize: Optional[int] = None):
        # Note: bypasses ModelTransformer.__init__ (keyword_only stashing
        # composes badly across two levels); Params init + own defaults.
        Transformer.__init__(self)
        self._setDefault(batchSize=64)
        self._set(**self._input_kwargs)

    @keyword_only
    def setParams(self, inputCol: Optional[str] = None,
                  outputCol: Optional[str] = None,
                  modelFile: Optional[str] = None,
                  batchSize: Optional[int] = None):
        return self._set(**self._input_kwargs)

    def getModelFile(self):
        return self.getOrDefault(self.modelFile)

    def getModelFunction(self):
        if not self.isSet(self.modelFunction):
            from sparkdl_tpu.graph.function import ModelFunction

            mf = ModelFunction.from_keras(self.getModelFile())
            self._set(modelFunction=mf)
        return self.getOrDefault(self.modelFunction)


class TFTransformer(Transformer, HasBatchSize):
    """Mapping form: model with named inputs/outputs over several columns.

    Counterpart of the reference's ``TFTransformer`` (C5): ``inputMapping``
    = {column name -> model input name}, ``outputMapping`` = {model output
    name -> new column name}.  The model is a :class:`ModelFunction` whose
    ``fn(variables, x)`` takes a dict of arrays keyed by input name and
    returns a dict keyed by output name (exactly what
    ``TFInputGraph``-imported graphs produce).
    """

    modelFunction = Param(
        "undefined", "modelFunction",
        "ModelFunction taking/returning dicts keyed by input/output names",
        typeConverter=SparkDLTypeConverters.toModelFunction)

    inputMapping = Param(
        "undefined", "inputMapping", "{column -> model input name}",
        typeConverter=SparkDLTypeConverters.toColumnToTensorMap)

    outputMapping = Param(
        "undefined", "outputMapping", "{model output name -> column}",
        typeConverter=SparkDLTypeConverters.toColumnToTensorMap)

    @keyword_only
    def __init__(self, modelFunction=None,
                 inputMapping: Optional[Dict[str, str]] = None,
                 outputMapping: Optional[Dict[str, str]] = None,
                 batchSize: Optional[int] = None):
        super().__init__()
        self._setDefault(batchSize=64)
        self._set(**self._input_kwargs)

    @keyword_only
    def setParams(self, modelFunction=None,
                  inputMapping: Optional[Dict[str, str]] = None,
                  outputMapping: Optional[Dict[str, str]] = None,
                  batchSize: Optional[int] = None):
        return self._set(**self._input_kwargs)

    def getModelFunction(self):
        return self.getOrDefault(self.modelFunction)

    def getInputMapping(self) -> Dict[str, str]:
        return self.getOrDefault(self.inputMapping)

    def getOutputMapping(self) -> Dict[str, str]:
        return self.getOrDefault(self.outputMapping)

    def engine(self):
        """The engine ``transform`` runs this stage on (as
        ``ModelTransformer.engine``)."""
        return get_cached_engine(self, self.getModelFunction(),
                                 device_batch_size=self.getBatchSize())

    def _transform(self, dataset):
        mf = self.getModelFunction()
        in_map = self.getInputMapping()
        out_map = self.getOutputMapping()
        missing = set(in_map.values()) - set(mf.input_names)
        if missing:
            raise ValueError(
                f"inputMapping refers to unknown model inputs {sorted(missing)}; "
                f"model has {list(mf.input_names)}")
        missing = set(out_map) - set(mf.output_names)
        if missing:
            raise ValueError(
                f"outputMapping refers to unknown model outputs "
                f"{sorted(missing)}; model has {list(mf.output_names)}")
        tracer = get_tracer()
        with tracer.span("transform.run",
                         batch_size=self.getBatchSize()) as root:
            with tracer.span("transform.pack_in", rows=len(dataset)) as sp:
                x = {input_name: _model_input(dataset, col)
                     for col, input_name in in_map.items()}
                sp.annotate(bytes=sum(int(a.nbytes) for a in x.values()))
            root.annotate(rows=len(dataset))
            ids = [a for a in x.values() if a.ndim == 2
                   and np.issubdtype(a.dtype, np.integer)]
            eng = self.engine()
            out, counted = count_outputs(
                mf, eng(x), eng.metrics, out_map,
                tokens=sum(int(a.size) for a in ids) if ids else None)
            root.annotate(**counted)
            for output_name, name in out_map.items():
                values = out[output_name]
                with tracer.span("transform.pack_out", rows=len(values),
                                 values=int(np.size(values)), column=name,
                                 dtype=str(values.dtype)) as sp:
                    col = output_column(values)
                    sp.annotate(bytes=list_values_nbytes(col),
                                null_rows=col.null_count, py_values=0)
                    dataset = dataset.withColumn(name, col)
        return dataset
