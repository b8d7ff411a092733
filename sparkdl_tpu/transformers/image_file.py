"""URI-column transformers with a user image loader.

Replaces ``python/sparkdl/transformers/keras_image.py`` (C6
``KerasImageFileTransformer`` + ``CanLoadImage`` mixin): the stage reads a
column of file URIs, runs the user's ``imageLoader`` (decode + model-specific
preprocessing, ``uri -> [H,W,C] float array``) on the host, and feeds the
stacked batch to the model on the mesh.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from sparkdl_tpu.frame import list_column
from sparkdl_tpu.image.io import _io_executor
from sparkdl_tpu.param.converters import SparkDLTypeConverters
from sparkdl_tpu.param.params import Param, keyword_only
from sparkdl_tpu.param.shared import (CanLoadImage, HasBatchSize, HasInputCol,
                                      HasOutputCol)
from sparkdl_tpu.parallel.engine import get_cached_engine
from sparkdl_tpu.persistence import PersistableModelFunctionMixin
from sparkdl_tpu.transformers.base import Transformer
from sparkdl_tpu.utils.logging import get_logger

logger = get_logger(__name__)


class ImageFileTransformer(PersistableModelFunctionMixin, Transformer,
                           HasInputCol, HasOutputCol,
                           HasBatchSize, CanLoadImage):
    """Apply a ModelFunction to images loaded from a URI column via the
    user's ``imageLoader``.  Rows whose loader raises or returns None become
    null outputs (the imageIO drop-to-null contract)."""

    modelFunction = Param(
        "undefined", "modelFunction",
        "ModelFunction applied to the stacked loaded-image batch",
        typeConverter=SparkDLTypeConverters.toModelFunction)

    @keyword_only
    def __init__(self, inputCol: Optional[str] = None,
                 outputCol: Optional[str] = None,
                 modelFunction=None,
                 imageLoader=None,
                 batchSize: Optional[int] = None):
        super().__init__()
        self._setDefault(batchSize=64)
        self._set(**self._input_kwargs)

    @keyword_only
    def setParams(self, inputCol: Optional[str] = None,
                  outputCol: Optional[str] = None,
                  modelFunction=None,
                  imageLoader=None,
                  batchSize: Optional[int] = None):
        return self._set(**self._input_kwargs)

    def getModelFunction(self):
        return self.getOrDefault(self.modelFunction)

    def _safe_loader(self):
        loader = self.getImageLoader()

        def safe_load(uri):
            if uri is None:
                return None
            try:
                arr = loader(uri)
                return None if arr is None else np.asarray(arr)
            except Exception as e:
                logger.warning("imageLoader failed for %r: %s", uri, e)
                return None

        return safe_load

    def _loaded_chunks(self, dataset, chunk_rows: int, valid_idx: List[int]):
        """Generator of stacked float32 chunks over URIs whose load
        succeeded.  Reads + decodes one record batch of files at a time (on
        the shared host-IO pool) — the whole dataset's pixels never coexist
        in memory; appends global indices of loadable rows to ``valid_idx``
        as a side effect."""
        safe_load = self._safe_loader()
        col_idx = dataset.table.column_names.index(self.getInputCol())
        offset = 0
        for rb in dataset.iter_batches(chunk_rows):
            uris = rb.column(col_idx).to_pylist()
            arrays = list(_io_executor().map(safe_load, uris))
            vi_local = [i for i, a in enumerate(arrays) if a is not None]
            if vi_local:
                valid_idx.extend(offset + i for i in vi_local)
                yield np.stack(
                    [arrays[i] for i in vi_local]).astype(np.float32)
            offset += len(uris)

    def _transform(self, dataset):
        from itertools import chain

        valid_idx: List[int] = []
        # the engine's runner pulls this on its prepare thread
        it = iter(self._loaded_chunks(dataset, max(1, self.getBatchSize()),
                                      valid_idx))
        first = next(it, None)
        outs = []
        if first is not None:
            import time

            # Engine (weight load + compile) only once a chunk proves
            # there's work to do.
            eng = get_cached_engine(self, self.getModelFunction(),
                                    device_batch_size=self.getBatchSize())
            t0 = time.perf_counter()
            outs = list(eng.map_batches(chain([first], it)))
            elapsed = time.perf_counter() - t0
            k, ndev = len(valid_idx), eng.num_devices
            ips = k / elapsed if elapsed > 0 else float("inf")
            logger.info("%s: %d images in %.3fs — %.1f img/s "
                        "(%.1f img/s/chip over %d devices)",
                        type(self).__name__, k, elapsed, ips, ips / ndev,
                        ndev)
        n = len(dataset)
        if outs:
            out = np.concatenate([np.asarray(o) for o in outs], axis=0)
        else:
            logger.warning("imageLoader produced no usable images out of %d "
                           "URIs; output column is all null", n)
            out = np.zeros((0, 0), np.float32)
        return dataset.withColumn(self.getOutputCol(),
                                  list_column(out, valid_idx, n))


class KerasImageFileTransformer(ImageFileTransformer):
    """The Keras-model flavor: ``modelFile`` (.h5/.keras) is converted to a
    ModelFunction on first use — reference's ``KerasImageFileTransformer``."""

    modelFile = Param(
        "undefined", "modelFile",
        "path to a saved Keras model applied to the loaded images")

    @keyword_only
    def __init__(self, inputCol: Optional[str] = None,
                 outputCol: Optional[str] = None,
                 modelFile: Optional[str] = None,
                 imageLoader=None,
                 batchSize: Optional[int] = None):
        Transformer.__init__(self)
        self._setDefault(batchSize=64)
        self._set(**self._input_kwargs)

    @keyword_only
    def setParams(self, inputCol: Optional[str] = None,
                  outputCol: Optional[str] = None,
                  modelFile: Optional[str] = None,
                  imageLoader=None,
                  batchSize: Optional[int] = None):
        return self._set(**self._input_kwargs)

    def getModelFile(self):
        return self.getOrDefault(self.modelFile)

    def getModelFunction(self):
        if not self.isSet(self.modelFunction):
            from sparkdl_tpu.graph.function import ModelFunction

            self._set(modelFunction=ModelFunction.from_keras(self.getModelFile()))
        return self.getOrDefault(self.modelFunction)
