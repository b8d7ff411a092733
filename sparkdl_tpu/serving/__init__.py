"""sparkdl_tpu.serving — online inference over the TPU engine.

The L7 layer the offline stack was missing: where transformers and UDFs
score whole DataFrames, this package serves SINGLE requests under load —
an async dynamic-batching front-end (clipper-style adaptive batching)
over the same :class:`~sparkdl_tpu.parallel.engine.InferenceEngine`,
with deadlines, backpressure, fault isolation, graceful drain, and
latency/throughput metrics.

Public surface:

* :class:`Server` — ``Server(model_fn_or_named_model, ...)``; accepts a
  zoo model name, a ``ModelFunction``, or a raw ``fn(variables, batch)``.
* :func:`from_transformer` — lift a zoo/image/tensor transformer stage
  into a running server.
* ``register_serving_udf`` (``sparkdl_tpu.udf``) — expose a running
  server as a column UDF, so offline scoring shares the online queue.
* The error classes: :class:`QueueFullError` (backpressure, carries
  ``retry_after_s``), :class:`DeadlineExceededError` (shed before
  dispatch), :class:`DispatchTimeoutError` (stalled model),
  :class:`ServiceUnavailableError` (shed at submit while the dispatch
  circuit breaker is open; carries ``retry_after_s``),
  :class:`ServerClosedError`.
* ``Server.health()`` — live/ready/degraded with last error, per-bucket
  circuit-breaker state, and a bounded transition history (also under
  ``varz()["health"]``); README "Failure model" documents the states.
* :class:`Fleet` (``sparkdl_tpu.serving.fleet``) — the multi-model,
  multi-tenant front door: named versioned registry entries,
  zero-downtime canary rollout with no-recompile hot-swap, per-tenant
  token-bucket quotas + priority classes (:class:`TenantQuota`,
  :class:`QuotaExceededError`), aggregated ``Fleet.varz()``/``health()``.
* :class:`InferenceCache` (``sparkdl_tpu.serving.cache``, ISSUE 11) —
  the content-addressed result cache + single-flight coalescing both
  front doors (and ``StreamScorer``) probe before any queue charge:
  bounded entries+bytes LRU keyed on ``utils.digest`` content digests,
  N concurrent identical requests -> one dispatch, hot-swap survival
  pinned against ``PROGRAMS.lock.json``, ``SPARKDL_CACHE`` env gate.
* :class:`HeadFanoutServer` (ISSUE 17) — the shared-backbone head
  fan-out tier: featurize each distinct input ONCE at the zoo's feature
  cut (cached under the backbone's lockfile fingerprint + weight
  digest), then serve per-tenant classifier heads from a stacked
  :class:`~sparkdl_tpu.parallel.engine.HeadBank` via one vmapped
  gather-by-tenant program; ``add_head``/``swap_head`` hot-swap can
  never recompile the backbone (witnessed per swap).
"""

from sparkdl_tpu.serving.adapters import from_transformer
from sparkdl_tpu.serving.batcher import DynamicBatcher, Request
from sparkdl_tpu.serving.cache import InferenceCache
from sparkdl_tpu.serving.errors import (DeadlineExceededError,
                                        DispatchTimeoutError, QueueFullError,
                                        QuotaExceededError, ServerClosedError,
                                        ServiceUnavailableError, ServingError)
from sparkdl_tpu.serving.server import (HeadFanoutServer, Server,
                                        bucket_plan)
# the fleet package imports serving.server/serving.errors, so it must
# come last here
from sparkdl_tpu.serving.fleet import (Fleet, ModelRegistry, ModelVersion,
                                       Rollout, TenantQuota)

__all__ = [
    "Server",
    "HeadFanoutServer",
    "bucket_plan",
    "InferenceCache",
    "from_transformer",
    "DynamicBatcher",
    "Request",
    "Fleet",
    "ModelRegistry",
    "ModelVersion",
    "Rollout",
    "TenantQuota",
    "ServingError",
    "QueueFullError",
    "QuotaExceededError",
    "DeadlineExceededError",
    "DispatchTimeoutError",
    "ServiceUnavailableError",
    "ServerClosedError",
]
