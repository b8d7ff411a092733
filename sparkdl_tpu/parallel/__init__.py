"""Device-mesh parallel execution layer.

Replaces the reference's L0 execution engines (Spark task dispatch +
TensorFrames JNI + per-partition ``tf.Session`` — SURVEY.md §1 L0, §3 hot
loops) with XLA:TPU: a ``jax.sharding.Mesh`` over chips, jit-compiled
programs with batch-axis ``NamedSharding``, and XLA collectives over ICI
instead of Spark shuffle/broadcast.
"""

from sparkdl_tpu.parallel.mesh import (batch_sharding, get_mesh,
                                       replicated_sharding)
from sparkdl_tpu.parallel.engine import (CircuitOpenError,
                                         DispatchCircuitBreaker,
                                         InferenceEngine)
from sparkdl_tpu.parallel.pipeline import (PipelinedRunner,
                                           PipelineStageError,
                                           PipelineStageFatalError)
from sparkdl_tpu.parallel import distributed

__all__ = [
    "CircuitOpenError",
    "DispatchCircuitBreaker",
    "InferenceEngine",
    "PipelinedRunner",
    "PipelineStageError",
    "PipelineStageFatalError",
    "batch_sharding",
    "distributed",
    "get_mesh",
    "replicated_sharding",
]
