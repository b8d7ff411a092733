"""sparkdl_tpu.obs — span tracing, metrics export, and slow-request
exemplars for the scoring and serving stack.

The observability layer SURVEY.md §5 found missing from the reference
(Spark UI only): every request/batch carries a trace, every stage emits
spans, and every run can export a machine-readable record.

* :mod:`~sparkdl_tpu.obs.trace` — :class:`Tracer` / spans / the
  ``SPARKDL_TRACE=0|1|dir`` gate (disabled path near-zero cost).
* :mod:`~sparkdl_tpu.obs.export` — Chrome trace-event JSON (Perfetto /
  chrome://tracing), Prometheus text exposition, and JSONL snapshots of
  the :class:`~sparkdl_tpu.utils.metrics.Metrics` registry.
* :mod:`~sparkdl_tpu.obs.exemplar` — top-K slowest request span trees,
  surfaced by ``Server.varz()``.
* :mod:`~sparkdl_tpu.obs.flight` — the :class:`FlightRecorder` incident
  black box: a bounded ring of structured state-change events
  (``SPARKDL_BLACKBOX=0|1|dir`` gate, near-zero disabled path) durably
  dumped on atexit/SIGTERM/ready->degraded; ``tools/blackbox.py`` folds
  a dump + span JSONL + stream journal + bench artifact into one
  trace-id-correlated incident timeline.
* :mod:`~sparkdl_tpu.obs.slo` — declarative SLOs (availability, p99
  latency, streaming watermark lag) evaluated with multi-window
  burn-rate math over the existing ``Metrics`` series, feeding
  ``HealthTracker`` degradation and surfacing in
  ``Server.varz()``/``Fleet.varz()``/``StreamScorer.health()``.
* :mod:`~sparkdl_tpu.obs.cost` — the :class:`CostLedger` hardware
  showback layer (``SPARKDL_COST`` gate): every settled request
  attributed to a bounded (tenant, model, program, bucket) ledger —
  metered device seconds split by real rows with the pad tax on a
  shared ``__pad__`` line, batcher queue wait, lockfile-analytic
  FLOPs, HBM byte-seconds, near-zero cache/coalesced/feature-hit
  charges — plus the per-program perf-regression sentinel
  (``cost.regression``/``cost.recovered`` flight events, SLO-style
  ``health()`` degradation) and ``tools/costreport.py`` showback.

Instrumented surfaces: ``serving.Server``/``DynamicBatcher`` (request +
micro-batch spans; shed/drain flight events; ``batch.topoff`` events +
``serving.topoff_rows``/``serving.batch_fill_ratio`` metrics for the
ragged top-off path), ``image.io`` (``io.read_images`` per
``readImages`` call over ``io.read``/``io.decode``/``io.to_arrow`` per
record batch and ``io.repartition``; attrs ``files``/``rows``/
``null_rows``/``partitions``/``bytes``/``failed``),
``transformers.named_image`` (``transform.run`` per zoo-stage
``transform`` — ``rows``/``valid_rows``/``model``/``batch_size`` — over
``transform.pack_in`` per chunk — ``rows``/``valid`` — and
``transform.pack_out`` — ``rows``/``values``, and of the column it
appended ``bytes`` (its values, offsets and validity left out),
``null_rows`` and ``py_values``: values that were a Python object on
the way, 0 wherever ``frame.list_column`` built the column from the
model's matrix, ``topK`` a row under ``decodePredictions``;
``transformers.tensor.ModelTransformer`` opens the same three spans),
``parallel.engine.
InferenceEngine`` (call/dispatch spans; ``engine.build`` once an
engine — ``param_bytes``/``device_batch_size``/``jit_cached`` — around
the cast, the weights' placement and the jit lookup, none on a
``get_cached_engine`` hit; ``engine.pad`` —
``rows``/``pad_rows`` — where a piece is padded and ``engine.h2d`` —
``bytes`` — around the dispatch's ``device_put``, host side only;
breaker open/half-open/close flight events; the
``engine.rows``/``engine.pad_rows`` pad ledger; ``engine.call_wall_s``,
host wall seconds of ``__call__``, the cost ledger's conservation
reference),
``parallel.compile_cache`` (``compile.persist``/``compile.invalidate``
flight events + hit/miss counters for the persistent executable
store; and every compile of the process, cache on or off: closed spans
``compile.trace``/``compile.lower``/``compile.backend`` —
``program``, and on the last ``cache`` hit/miss/off with ``load_s``/
``saved_s`` on a hit — under the span whose call compiled
(``Tracer.record``), their seconds in ``compile_cache.stats()`` as
``trace_s``/``lower_s``/``backend_s``/``load_s``/``saved_s`` whether
the tracer is on or not; ``tools/trace_summary.py`` folds them by
program), ``parallel.pipeline.PipelinedRunner`` (per-stage spans
with ``block_until_ready``-bracketed device time; ``pipeline.gather``
carries ``rows``/``bytes`` beside ``device_us``),
``serving.fleet.Fleet`` (rollout start/promote/rollback + tenant-shed
flight events), ``serving.cache.InferenceCache`` (hit/miss/coalesced/
evict/invalidate flight events + ``cache.*`` metrics),
``streaming.StreamScorer`` (``stream.run``/
``stream.chunk`` spans + stall/redelivery/commit flight events),
``utils.health.HealthTracker`` (ready<->degraded transition events),
``faults`` (``fault.fired`` per injected rule firing), ``utils.retry``
(``retry.attempt`` per re-execution), ``obs.cost.CostLedger``
(per-tenant/per-program attribution in ``varz()["cost"]``; its own
labeled ``prometheus_text``; ``cost.regression``/``cost.recovered``
flight events from the sentinel; the ``cost.attr`` degrade-not-fail
fault site), and ``bench.py`` (one trace artifact + metrics snapshot +
``slo`` + ``cost`` snapshot per config line).
"""

from sparkdl_tpu.obs.exemplar import ExemplarReservoir
from sparkdl_tpu.obs.export import (load_spans, metrics_snapshot,
                                    prometheus_text, to_chrome_trace,
                                    write_chrome_trace,
                                    write_metrics_jsonl, write_spans_jsonl)
from sparkdl_tpu.obs.trace import (NULL_SPAN, Span, Tracer, configure,
                                   configure_from_env, current_trace_id,
                                   get_tracer, tracing_from_env)
from sparkdl_tpu.obs import flight
from sparkdl_tpu.obs import slo as slo_module  # noqa: F401 — re-export
from sparkdl_tpu.obs import cost as cost_module  # noqa: F401 — re-export
from sparkdl_tpu.obs.cost import (CostLedger, CostRegression, cost_rider,
                                  resolve_cost)
from sparkdl_tpu.obs.flight import FlightRecorder, blackbox_from_env
from sparkdl_tpu.obs.slo import SLO, SLOEngine, SLOViolation, slo_snapshot

__all__ = [
    "Tracer",
    "Span",
    "NULL_SPAN",
    "get_tracer",
    "configure",
    "configure_from_env",
    "current_trace_id",
    "tracing_from_env",
    "to_chrome_trace",
    "write_chrome_trace",
    "write_spans_jsonl",
    "load_spans",
    "metrics_snapshot",
    "write_metrics_jsonl",
    "prometheus_text",
    "ExemplarReservoir",
    "flight",
    "FlightRecorder",
    "blackbox_from_env",
    "SLO",
    "SLOEngine",
    "SLOViolation",
    "slo_snapshot",
    "CostLedger",
    "CostRegression",
    "cost_rider",
    "resolve_cost",
]
