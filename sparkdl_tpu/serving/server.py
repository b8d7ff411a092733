"""In-process async inference server over the TPU engine.

The online counterpart of the offline paths (transformers / UDFs /
``InferenceEngine.map_batches``): single-example requests are admitted
into a bounded queue, assembled into dynamic micro-batches
(:mod:`sparkdl_tpu.serving.batcher`), padded to a small set of BUCKET
sizes so the engine's jit executable cache stays warm (a handful of
compiled shapes, never one per request count), dispatched through the
existing :class:`~sparkdl_tpu.parallel.engine.InferenceEngine` (same
dispatch program and per-controller mesh policy), and
demultiplexed back to per-request futures.

Production envelope:
  * per-request deadlines — expired requests are shed BEFORE dispatch;
  * bounded admission queue — reject-with-``retry_after_s`` when full;
  * per-batch fault isolation — a model fn that raises (after the
    configured ``utils.retry`` budget) or stalls past
    ``dispatch_timeout_ms`` fails only its OWN batch's futures;
  * graceful drain on ``close()`` / context-manager exit;
  * ``utils.metrics``-integrated counters/gauges/latency histograms
    (queue depth, batch fill ratio, time-in-queue, p50/p99 latency).
"""

from __future__ import annotations

import inspect
import threading
import time
from concurrent.futures import Future, InvalidStateError
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from sparkdl_tpu.analysis.lockcheck import named_condition, named_lock
from sparkdl_tpu.faults import inject
from sparkdl_tpu.obs.exemplar import ExemplarReservoir
from sparkdl_tpu.obs.flight import emit as flight_emit
from sparkdl_tpu.parallel.engine import CircuitOpenError
from sparkdl_tpu.obs.trace import get_tracer
from sparkdl_tpu.serving.batcher import (DynamicBatcher, Request,
                                         ragged_enabled_from_env)
from sparkdl_tpu.serving.errors import (DeadlineExceededError,
                                        DispatchTimeoutError,
                                        ServerClosedError,
                                        ServiceUnavailableError)
from sparkdl_tpu.utils.digest import content_digest
from sparkdl_tpu.utils.health import HealthTracker
from sparkdl_tpu.utils.logging import get_logger
from sparkdl_tpu.utils.metrics import Metrics
from sparkdl_tpu.utils.retry import NON_RETRYABLE, with_retries

logger = get_logger(__name__)


def _resolve_model(model, variables, featurize: bool):
    """(fn, host_variables, engine_overrides) from the three accepted
    model forms:

    * a zoo model NAME (str) — weights via the shared process cache, the
      model's ImageNet preprocess fused in front (``featurize`` picks the
      feature cut vs. probabilities), uint8 RGB ``[B, H, W, 3]`` input.
      Honors ``SPARKDL_ZOO_COMPUTE_DTYPE`` exactly like the zoo
      transformers (``named_image._zoo_engine``) — bf16 compute with f32
      host cast under the bench configuration — so served rows match
      ``transform()`` rows; the dtype choice rides ``engine_overrides``
      (applied unless the caller set the knobs explicitly);
    * a :class:`~sparkdl_tpu.graph.function.ModelFunction`;
    * a plain jit-traceable ``fn(variables, batch)`` plus ``variables``.
    """
    from sparkdl_tpu.graph.function import ModelFunction

    if isinstance(model, str):
        if variables is not None:
            raise ValueError("variables must be None when serving a named "
                             "zoo model")
        # the ONE zoo fn constructor — shared with _zoo_engine, the fleet
        # registry, and the program auditor, so served == transformed ==
        # audited
        from sparkdl_tpu.transformers.named_image import zoo_serving_bundle

        return zoo_serving_bundle(model, featurize)
    if isinstance(model, ModelFunction):
        if variables is not None:
            raise ValueError("variables must be None when serving a "
                             "ModelFunction (it carries its own)")
        return model.fn, model.variables, {}
    if callable(model):
        return model, ({} if variables is None else variables), {}
    raise TypeError(f"Cannot serve a {type(model).__name__}; expected a "
                    f"zoo model name, ModelFunction, or callable "
                    f"fn(variables, batch)")


def _default_buckets(max_batch_size: int) -> List[int]:
    """Quarter / half / full batch — three compiled shapes cover light,
    medium, and saturated traffic without per-count recompiles."""
    b = max(1, int(max_batch_size))
    return sorted({max(1, b // 4), max(1, b // 2), b})


def bucket_plan(max_batch_size: int,
                bucket_sizes: Optional[Sequence[int]] = None,
                mesh=None) -> List[int]:
    """The COMPILED bucket set a :class:`Server` would build: requested
    buckets (default quarter/half/full), validated, rounded up to the
    mesh's data-axis multiple (the engine does this per bucket anyway),
    and de-duplicated — two raw buckets that round to the same device
    batch were two engine objects compiling ONE shape.  This is the
    enumeration hook ``analysis.program`` walks to audit every serving
    program chip-free; the server itself builds its engines from the
    same plan so the audited set cannot drift from the served set."""
    from sparkdl_tpu.parallel.engine import (effective_device_batch,
                                             resolve_engine_mesh)

    max_batch_size = max(1, int(max_batch_size))
    buckets = (list(bucket_sizes) if bucket_sizes is not None
               else _default_buckets(max_batch_size))
    if not buckets or any(int(b) < 1 for b in buckets):
        raise ValueError(f"bucket_sizes must be positive, got {buckets}")
    buckets = sorted(int(b) for b in buckets)
    if buckets[-1] < max_batch_size:
        raise ValueError(
            f"largest bucket ({buckets[-1]}) must cover "
            f"max_batch_size ({max_batch_size})")
    mesh = resolve_engine_mesh(mesh)
    return sorted({effective_device_batch(b, mesh) for b in buckets})


class _Once:
    """Run a callback exactly once across racing threads (worker finish
    vs. stall watchdog)."""

    def __init__(self, fn: Callable[[], None]):
        self._fn = fn
        self._lock = named_lock("serving.once")
        self._done = False

    def __call__(self) -> None:
        with self._lock:
            if self._done:
                return
            self._done = True
        self._fn()


def _deadline_guard(inner: Future, timeout_s: float) -> Future:
    """Caller-facing view of ``inner`` that fails with
    ``DeadlineExceededError`` after ``timeout_s`` — how a coalesced
    follower keeps its own deadline while parked on a leader whose
    request may have none.

    One ``threading.Timer`` per deadline-carrying follower, cancelled
    the moment the leader settles — the same per-waiter budget as the
    dispatch watchdog's per-attempt timer, and it exists only for the
    flight's (typically milliseconds-long) lifetime.  A deadline wheel
    would amortize this if stampedes of deadline-carrying identical
    requests ever become a measured hot spot."""
    out: Future = Future()

    def _forward(f: Future) -> None:
        timer.cancel()
        try:
            if f.cancelled():
                out.cancel()
                return
            exc = f.exception()
            if exc is not None:
                out.set_exception(exc)
            else:
                out.set_result(f.result())
        except InvalidStateError:  # the deadline timer fired first
            pass

    def _expire() -> None:
        try:
            out.set_exception(DeadlineExceededError(
                f"coalesced request exceeded its "
                f"{timeout_s * 1e3:.0f}ms deadline while waiting on the "
                f"single-flight leader"))
        except InvalidStateError:  # the leader settled first
            pass

    timer = threading.Timer(timeout_s, _expire)
    timer.daemon = True
    timer.start()
    inner.add_done_callback(_forward)
    return out


def _settle_error(requests: Sequence[Request], exc: BaseException) -> None:
    for r in requests:
        if not r.future.done():
            try:
                r.future.set_exception(exc)
            except InvalidStateError:  # lost a race with the watchdog
                pass
        r.finish_span("error")
    if requests:
        bs = requests[0].batch_span
        if bs is not None:
            requests[0].batch_span = None
            bs.finish("error")


class Server:
    """Async dynamic-batching inference service over one model.

    ::

        with serving.Server(fn, variables, max_batch_size=64,
                            max_wait_ms=5) as srv:
            fut = srv.submit(example)           # concurrent.futures.Future
            y = fut.result()
            y = srv.predict(example)            # blocking sugar
            y = await srv.predict_async(example)  # asyncio integration

    Requests are single examples WITHOUT the batch axis (arrays or
    pytrees); results are the matching single-example output rows —
    bit-identical to batching the same inputs through
    ``InferenceEngine.map_batches`` at the same padded shape, regardless
    of arrival order or which micro-batch a request lands in (across
    DIFFERENT bucket shapes results agree to XLA-refusion tolerance).

    Parameters beyond the batcher knobs:
      * ``bucket_sizes`` — padded dispatch sizes (default quarter/half/
        full ``max_batch_size``); each bucket is one compiled shape.
      * ``default_timeout_ms`` — deadline applied to requests that pass
        no ``timeout_ms`` of their own (None = no deadline).
      * ``dispatch_timeout_ms`` — stall watchdog: a model-call ATTEMPT
        exceeding this fails its batch with ``DispatchTimeoutError`` and
        later batches proceed (None = wait forever).  The window is
        re-armed per retry attempt and excludes both jit compile (each
        bucket's first batch triggers an untimed warm call) and the
        host-side demux.
      * ``max_retries`` — per-batch ``utils.retry.with_retries`` budget
        for transient model failures (default 0: fail fast; deterministic
        errors in ``retry.NON_RETRYABLE`` never retry).
      * ``max_inflight_batches`` — dispatch concurrency bound (device
        residency stays O(inflight x bucket), mirroring the engine's
        in-flight window).
      * ``host_preprocess`` — optional per-request host-side fn applied
        in ``submit`` on the CALLER's thread (e.g. image resize), so the
        dispatcher never blocks on host prep.
      * ``dispatch_retries`` / ``breaker_threshold`` /
        ``breaker_cooldown_s`` — the engines' failure-domain knobs
        (ISSUE 4): engine-level transient-dispatch retry budget
        (jittered, capped backoff) and the consecutive-device-error
        circuit breaker.  While a breaker is OPEN, :meth:`submit` sheds
        with ``ServiceUnavailableError`` + ``retry_after_s`` instead of
        letting every request queue, dispatch into a dead device, and
        time out; :meth:`health` reports live/ready/degraded with the
        per-bucket breaker state and last error.
      * ``slos`` — declarative :class:`~sparkdl_tpu.obs.slo.SLO`
        objectives (ISSUE 9) evaluated over this server's metrics on
        every :meth:`health`/:meth:`varz` poll; a burn-rate breach
        degrades health (naming the objective in ``last_error``) and
        the evaluation rides ``health()["slo"]``.
      * ``ragged`` — continuous ragged batching (ISSUE 13; default:
        the ``SPARKDL_RAGGED`` env knob, ON): flushes cut the queue at
        compiled-bucket boundaries (zero pad rows for the cut) and
        sub-bucket residuals top off with stack-compatible late
        arrivals right before dispatch, so the engine's pad path is
        only paid for the true residual.  ``False`` restores the
        flush-on-full baseline (everything waiting pads into the
        nearest covering bucket).
      * ``donate_batch`` — donate the per-dispatch device batch buffer
        to XLA (None = auto: donate iff an eval-shape probe proves the
        donation is CONSUMED — some output leaf aliases the batch;
        zoo models resolve to False by recorded GC001 exemption, their
        uint8 batch can never alias the float features).
      * ``partition_rules`` / ``param_shardings`` — tensor-parallel
        WEIGHT sharding (ISSUE 14): a ``(regex, PartitionSpec)`` rule
        list (or ``mesh -> rules`` factory) / an explicit per-leaf spec
        pytree splitting chosen params across the mesh's ``model``
        axis, so every bucket engine holds ``bytes / model_axis`` of a
        sharded leaf instead of one full weight copy per chip.  Zoo
        models default to ``mesh.default_partition_rules`` (resolves
        all-replicated — byte-identical programs — unless the mesh has
        a model axis > 1); ``varz()["sharding"]`` reports the resolved
        layout and per-chip HBM bytes.
    """

    def __init__(self, model, variables: Any = None, *,
                 featurize: bool = False,
                 max_batch_size: int = 64,
                 max_wait_ms: float = 5.0,
                 max_queue: int = 1024,
                 default_timeout_ms: Optional[float] = None,
                 dispatch_timeout_ms: Optional[float] = None,
                 bucket_sizes: Optional[Sequence[int]] = None,
                 max_inflight_batches: int = 2,
                 max_retries: int = 0,
                 retry_backoff_s: float = 0.0,
                 mesh=None,
                 compute_dtype: Optional[Any] = None,
                 output_host_dtype: Optional[Any] = None,
                 host_preprocess: Optional[Callable[[Any], Any]] = None,
                 dispatch_retries: int = 0,
                 breaker_threshold: int = 8,
                 breaker_cooldown_s: float = 30.0,
                 slos: Optional[Sequence[Any]] = None,
                 cache: Any = None,
                 cache_namespace: Optional[Sequence[Any]] = None,
                 ragged: Optional[bool] = None,
                 donate_batch: Optional[bool] = None,
                 partition_rules: Any = None,
                 param_shardings: Any = None,
                 metrics: Optional[Metrics] = None,
                 clock: Optional[Callable[[], float]] = None,
                 cost: Any = None,
                 model_desc: Optional[str] = None):
        self._fn, self._host_variables, _overrides = _resolve_model(
            model, variables, featurize)
        # what the cost ledger's lockfile lookup and showback report as
        # "model": zoo names match PROGRAMS.lock.json dispatch records;
        # anything else gets the fn's name (rows-only attribution)
        self.model_desc = (model_desc if model_desc is not None
                           else (model if isinstance(model, str)
                                 else getattr(model, "__name__",
                                              type(model).__name__)))
        if compute_dtype is None and output_host_dtype is None:
            compute_dtype = _overrides.get("compute_dtype")
            output_host_dtype = _overrides.get("output_host_dtype")
        if donate_batch is None:
            # zoo models override to False (uint8 batch can never alias
            # the float features — GC001's recorded exemption); anything
            # else stays None = probe per bucket at first dispatch
            donate_batch = _overrides.get("donate_batch")
        self._donate_batch = donate_batch
        # Tensor-parallel weight sharding (ISSUE 14): the policy every
        # bucket engine compiles/places weights under.  Zoo models
        # default to the per-family rules (mesh.default_partition_rules
        # via zoo_serving_bundle overrides) — a no-op replicate on
        # model-axis-1 meshes, weight splitting when the mesh has a
        # usable model axis; explicit partition_rules/param_shardings
        # always win.
        if partition_rules is None and param_shardings is None:
            partition_rules = _overrides.get("partition_rules")
        self._partition_rules = partition_rules
        self._param_shardings = param_shardings
        self.metrics = metrics if metrics is not None else Metrics()
        # Injected monotonic clock (ISSUE 16): deadlines, queue ages and
        # latency accounting all read THIS source, so a virtual-time
        # harness (the traffic twin) drives the whole request path
        # deterministically.  Real-time mechanics stay real: close()'s
        # drain wait, the dispatch watchdog and the follower deadline
        # guard are wall-clock liveness devices, not request semantics.
        self._clock = clock if clock is not None else time.monotonic
        self.max_batch_size = max(1, int(max_batch_size))
        from sparkdl_tpu.parallel import mesh as mesh_lib
        from sparkdl_tpu.parallel.engine import resolve_engine_mesh

        resolved_mesh = resolve_engine_mesh(mesh)
        self._data_parallel = int(resolved_mesh.shape[mesh_lib.DATA_AXIS])
        # mesh-rounded, de-duplicated compiled shapes; also what the
        # program auditor enumerates (bucket_plan docstring)
        self._buckets = bucket_plan(self.max_batch_size,
                                    bucket_sizes=bucket_sizes,
                                    mesh=resolved_mesh)
        self._default_timeout_s = (None if default_timeout_ms is None
                                   else max(0.0, default_timeout_ms) / 1e3)
        self._dispatch_timeout_s = (None if dispatch_timeout_ms is None
                                    else max(1e-3, dispatch_timeout_ms) / 1e3)
        self._max_retries = max(0, int(max_retries))
        self._retry_backoff_s = max(0.0, float(retry_backoff_s))
        self._mesh = mesh
        self._compute_dtype = compute_dtype
        self._output_host_dtype = output_host_dtype
        self._host_preprocess = host_preprocess
        self._dispatch_retries = max(0, int(dispatch_retries))
        self._breaker_threshold = int(breaker_threshold)
        self._breaker_cooldown_s = float(breaker_cooldown_s)
        # Health state machine (ISSUE 4): "ready" <-> "degraded" driven
        # by dispatch/batch outcomes (every failed ATTEMPT notes
        # degraded — even one an engine retry later absorbs — and the
        # next success notes ready), with a bounded transition history
        # so tests/operators can see degraded->ready recoveries that a
        # point-in-time poll would race past.  Shared with the streaming
        # runner since ISSUE 8 (utils.health mirrors this contract).
        self._health = HealthTracker("serving.health")
        # Hardware cost attribution (ISSUE 18): ``cost=None`` resolves
        # the SPARKDL_COST process default (unset env = unmetered),
        # ``cost=False`` forces unmetered, a CostLedger is shared (the
        # fleet passes one across its servers).  First health binder
        # wins: a fleet binds its fleet-wide tracker before handing the
        # ledger here, so this bind is a no-op in that deployment.
        from sparkdl_tpu.obs.cost import resolve_cost

        self._cost = resolve_cost(cost)
        if self._cost is not None:
            self._cost.bind_health(self._health)
        self._cost_hbm: Dict[int, float] = {}
        # Declarative objectives (ISSUE 9): evaluated over THIS server's
        # metrics on every health()/varz() poll; a burn-rate breach
        # degrades the same tracker dispatch failures do, so "degraded"
        # finally answers "against what objective?".
        self._slo_engine = None
        if slos:
            from sparkdl_tpu.obs.slo import SLOEngine

            self._slo_engine = SLOEngine(self.metrics, slos,
                                         health=self._health,
                                         clock=self._clock)
        # Content-addressed result cache + single-flight coalescing
        # (ISSUE 11): probe BEFORE the admission-queue charge — a hit
        # costs zero queue slots and zero dispatches, a coalesced
        # follower parks on the identical in-flight leader.  ``cache=
        # None`` (the default) resolves the SPARKDL_CACHE process
        # default (unset env = uncached, the pre-ISSUE-11 behavior);
        # pass an InferenceCache to share one across servers (the
        # fleet does, with per-version namespaces) or ``cache=False``
        # to force uncached.
        from sparkdl_tpu.serving.cache import resolve_cache

        # owned (= auto-generated anon) namespaces are reclaimed from
        # the possibly-shared store by close() — nobody else can ever
        # reach those keys, so leaving them would charge the byte
        # budget until LRU pressure
        self._cache, self._cache_ns, self._cache_ns_owned = resolve_cache(
            cache, cache_namespace, "server")
        self._engines: Dict[int, Any] = {}
        self._warm: set = set()  # buckets whose program is compiled
        self._engine_lock = named_lock("serving.engines")
        # Continuous ragged batching (ISSUE 13): the batcher cuts
        # flushes at this server's compiled bucket boundaries, and
        # _execute tops a sub-bucket residual off with late arrivals
        # right before stacking.  ``SPARKDL_RAGGED=0`` (or
        # ``ragged=False``) restores the flush-on-full baseline.
        self._ragged = (ragged_enabled_from_env() if ragged is None
                        else bool(ragged))
        self._batcher = DynamicBatcher(
            max_batch_size=self.max_batch_size, max_wait_ms=max_wait_ms,
            max_queue=max_queue,
            bucket_plan=self._buckets if self._ragged else None,
            align=self._data_parallel,
            metrics=self.metrics, clock=self._clock)
        # Slow-request exemplars: top-K span trees, surfaced by varz();
        # inert (offer() returns False) unless SPARKDL_TRACE is on.
        self.exemplars = ExemplarReservoir(k=4)
        self._closed = False
        self._abandon = threading.Event()
        self._inflight = 0
        self._inflight_cond = named_condition("serving.inflight")
        self._inflight_sem = threading.Semaphore(
            max(1, int(max_inflight_batches)))
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, daemon=True,
            name="sparkdl-serving-dispatch")
        self._dispatcher.start()

    # -- engines (one per bucket, shared weights + shared jit program) ----
    def _bucket_for(self, n: int) -> int:
        for b in self._buckets:
            if b >= n:
                return b
        return self._buckets[-1]

    def _probe_donate(self, bucket: int, batch_example: Any) -> bool:
        """True iff XLA can actually CONSUME a donated batch buffer for
        this server's fn at ``bucket`` rows: every batch leaf must find
        a distinct output leaf with identical (shape, dtype) to alias
        (GC001's consumption criterion, probed abstractly — one
        ``eval_shape``, no compile).  Donating an unconsumable buffer
        is harmless but noisy (XLA drops it with a warning), so the
        auto path only declares what the audit would verify consumed.
        Zoo models never reach here (their uint8 batch can never alias
        the float features — the recorded GC001 exemption rides the
        ``zoo_serving_bundle`` engine overrides as
        ``donate_batch=False``)."""
        import jax

        from collections import Counter

        try:
            cdt = self._compute_dtype

            def var_aval(leaf):
                arr = leaf if hasattr(leaf, "dtype") else np.asarray(leaf)
                dt = arr.dtype
                if cdt is not None and np.issubdtype(dt, np.floating):
                    dt = cdt  # mirror the engine's _cast_floating
                return jax.ShapeDtypeStruct(tuple(arr.shape), dt)

            variables = jax.tree_util.tree_map(var_aval,
                                               self._host_variables)
            avals = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    (bucket,) + tuple(a.shape[1:]), a.dtype),
                batch_example)
            out = jax.eval_shape(self._fn, variables, avals)
            need = Counter((tuple(l.shape), np.dtype(l.dtype))
                           for l in jax.tree_util.tree_leaves(avals))
            have = Counter((tuple(l.shape), np.dtype(l.dtype))
                           for l in jax.tree_util.tree_leaves(out))
            return all(have[k] >= c for k, c in need.items())
        except Exception as e:  # noqa: BLE001 — probe must never break serving
            logger.info("donation probe failed (%s: %s); building bucket "
                        "%d without batch donation", type(e).__name__, e,
                        bucket)
            return False

    def _engine_for(self, bucket: int, batch_example: Any = None):
        with self._engine_lock:
            eng = self._engines.get(bucket)
            if eng is None:
                from sparkdl_tpu.parallel.engine import InferenceEngine

                first = next(iter(self._engines.values()), None)
                donate = self._donate_batch
                if donate is None:
                    # auto: donate the per-dispatch device batch iff the
                    # probe proves XLA will alias it into an output
                    # (ISSUE 13 satellite — the engine device_puts a
                    # fresh buffer per dispatch and never touches it
                    # again, so donation is always SAFE; the probe only
                    # decides whether it is CONSUMED)
                    donate = (self._probe_donate(bucket, batch_example)
                              if batch_example is not None else False)
                # Buckets share ONE device copy of the weights (device_put
                # of an already-replicated pytree is a no-op) and ONE jit
                # program (module-level engine cache keyed on fn/mesh) —
                # each bucket only adds one executable for its shape.
                eng = InferenceEngine(
                    self._fn,
                    first.variables if first is not None
                    else self._host_variables,
                    mesh=first.mesh if first is not None else self._mesh,
                    device_batch_size=bucket,
                    compute_dtype=(None if first is not None
                                   else self._compute_dtype),
                    output_host_dtype=self._output_host_dtype,
                    donate_batch=bool(donate),
                    # later buckets resolve the same policy against the
                    # first bucket's already-sharded device arrays —
                    # same specs, so device_put is a per-leaf no-op and
                    # every bucket shares one device copy of the weights
                    partition_rules=self._partition_rules,
                    param_shardings=self._param_shardings,
                    dispatch_retries=self._dispatch_retries,
                    breaker_threshold=self._breaker_threshold,
                    breaker_cooldown_s=self._breaker_cooldown_s,
                    on_dispatch_error=self._note_failure,
                    metrics=self.metrics)
                self._engines[bucket] = eng
            return eng

    def warmup(self, example: Any) -> None:
        """Compile every bucket's program ahead of traffic (one dummy
        dispatch per bucket shaped like ``example``, a single request
        payload) so first requests never pay compile time."""
        import jax

        if self._host_preprocess is not None:
            example = self._host_preprocess(example)
        example = jax.tree_util.tree_map(np.asarray, example)
        for b in self._buckets:
            # buckets are mesh-rounded already (bucket_plan), so the
            # bucket IS the engine's device batch; stacking first lets
            # _engine_for's donation probe see the real batch aval
            stacked = jax.tree_util.tree_map(
                lambda a: np.stack([a] * b), example)
            eng = self._engine_for(b, stacked)
            eng(stacked)
            self._warm.add(b)

    # -- health / failure domain -------------------------------------------
    def _note_failure(self, exc: BaseException) -> None:
        """Record a failed dispatch attempt / batch: state -> degraded.
        Wired as every engine's ``on_dispatch_error`` hook, so faults an
        engine-level retry absorbs still leave a health trace."""
        self._health.note_failure(exc)

    def _note_success(self) -> None:
        self._health.note_success()

    def _breaker_states(self) -> Dict[int, Dict[str, Any]]:
        with self._engine_lock:
            engines = dict(self._engines)
        return {b: eng.breaker_state() for b, eng in sorted(engines.items())}

    def _breaker_retry_after(self) -> Optional[float]:
        """Max remaining cool-down over OPEN bucket breakers, or None
        when none is open (the per-submit fast path: one cheap query per
        engine, no state snapshots).  Half-open breakers admit traffic —
        the trial dispatch that can close them has to come from
        somewhere."""
        with self._engine_lock:
            engines = list(self._engines.values())
        worst = None
        for eng in engines:
            remaining = eng.breaker.open_remaining_s()
            if remaining is not None:
                worst = max(worst or 0.0, remaining)
        return worst

    def breaker_retry_after(self) -> Optional[float]:
        """Public form of the per-submit breaker query: remaining
        cool-down of the worst OPEN bucket breaker, or None when
        admission is open.  The fleet front door consults this to shed
        lowest-priority traffic first while a model's device is
        failing."""
        return self._breaker_retry_after()

    def health(self) -> Dict[str, Any]:
        """Liveness/readiness snapshot (JSON-serializable; also embedded
        in :meth:`varz`), built through the ONE
        :meth:`~sparkdl_tpu.utils.health.HealthTracker.payload` schema
        every ``health()`` in the stack shares (ISSUE 9):

        * ``live`` — the serving loop exists (False once closed);
        * ``state`` — ``ready`` (serving normally), ``degraded``
          (breaker open/half-open, SLO breach, or a dispatch/batch
          failure with no success since), or ``closed``;
        * ``last_error`` — most recent failure (type/message/monotonic
          ts), surviving recovery for post-mortems;
        * ``transitions`` — bounded ready/degraded history, so a
          degraded->ready recovery is observable after the fact;
        * ``breaker`` — per-bucket engine circuit-breaker state (this
          surface's extra);
        * ``slo`` — the objective evaluation, when ``slos=`` were
          configured (each ``health()`` poll takes one burn-rate
          sample).
        """
        extra: Dict[str, Any] = {}
        if self._slo_engine is not None:
            # evaluate BEFORE the snapshot: a breach crossing on this
            # very poll must already show as degraded
            extra["slo"] = self._slo_engine.evaluate()
        breakers = self._breaker_states()
        state_override = None
        if any(st["state"] in ("open", "half_open")
               for st in breakers.values()):
            state_override = "degraded"
        if self._closed:
            state_override = "closed"
        return self._health.payload(live=not self._closed,
                                    state_override=state_override,
                                    breaker=breakers, **extra)

    # -- request path ------------------------------------------------------
    def submit(self, example: Any,
               timeout_ms: Optional[float] = None,
               tenant: Optional[str] = None) -> Future:
        """Admit one example; returns its ``concurrent.futures.Future``.

        ``tenant`` is the cost-attribution identity (ISSUE 18) — it
        changes nothing about scheduling or admission here (quota lives
        in the Fleet); it only decides which ledger line the request's
        device/queue time lands on.  None charges ``"default"``.

        Raises ``ServerClosedError`` after close, ``QueueFullError``
        (with ``retry_after_s``) under backpressure, and
        ``ServiceUnavailableError`` (with ``retry_after_s``) while the
        dispatch circuit breaker is open — the device is failing every
        dispatch, so admitting more work would only convert each request
        into a slow timeout.  ``timeout_ms`` overrides the server's
        ``default_timeout_ms`` deadline.

        With a result cache configured (ISSUE 11) the probe runs FIRST
        — before the breaker shed and the admission-queue charge — so a
        hit serves even while the device is failing (the cached row
        needs no device), and N concurrent identical requests cost one
        dispatch: the first becomes the single-flight leader, the rest
        park on its future.  A leader failure settles its followers
        with the same error and caches nothing.
        """
        if self._closed:
            raise ServerClosedError("server is closed")
        if self._cache is not None:
            return self._submit_cached(example, timeout_ms, tenant)
        return self._submit_dispatch(example, timeout_ms, tenant=tenant)

    def _charge_hit(self, tenant: Optional[str], kind: str) -> None:
        """Near-zero ledger charge for a cache-absorbed request.
        Attribution is observability: any failure (the ``cost.attr``
        fault site included) degrades to an error counter — it must
        never fail the request it was accounting for."""
        if self._cost is None:
            return
        try:
            self._cost.record_hit(tenant=tenant or "default",
                                  model=self.model_desc, kind=kind)
        # graftlint: allow=SDL003 reason=cost.attr degrade contract: attribution failure is counted and logged, the request it accounted for already served
        except Exception as e:  # noqa: BLE001
            self.metrics.incr("serving.cost_attr_errors")
            self._cost.record_error()
            logger.warning("cost attribution (%s) failed: %s: %s", kind,
                           type(e).__name__, e)

    def _submit_cached(self, example: Any,
                       timeout_ms: Optional[float],
                       tenant: Optional[str] = None) -> Future:
        """The cache-fronted request path; see :meth:`submit`."""
        import jax

        t0 = self._clock()
        if self._host_preprocess is not None:
            example = self._host_preprocess(example)
        example = jax.tree_util.tree_map(np.asarray, example)
        key = self._cache_ns + (content_digest(example),)
        kind, res = self._cache.lookup(key)
        if kind == "hit":
            self.metrics.incr("serving.requests")
            self.metrics.incr("serving.completed")
            self.metrics.incr("serving.cache_hits")
            self.metrics.record_time("serving.request_latency",
                                     self._clock() - t0)
            self._charge_hit(tenant, "hit")
            fut: Future = Future()
            fut.set_result(res)
            return fut
        if kind == "follower":
            self.metrics.incr("serving.requests")
            self.metrics.incr("serving.cache_coalesced")
            self._charge_hit(tenant, "coalesced")

            def _follower_done(f: Future) -> None:
                if not f.cancelled() and f.exception() is None:
                    self.metrics.incr("serving.completed")
                    self.metrics.record_time("serving.request_latency",
                                             self._clock() - t0)

            # a coalesced follower keeps its OWN deadline: the leader
            # may have none, and "timeout_ms overrides the server
            # default" must hold whether or not the request coalesced
            timeout_s = (self._default_timeout_s if timeout_ms is None
                         else max(0.0, timeout_ms) / 1e3)
            caller_fut = (res if timeout_s is None
                          else _deadline_guard(res, timeout_s))
            # metrics ride the future the CALLER holds: a follower
            # whose deadline guard already failed it must not count as
            # completed (with the leader's latency) when the leader
            # eventually settles
            caller_fut.add_done_callback(_follower_done)
            return caller_fut
        flight = res
        try:
            # the leader's payload must be OURS: the digest above
            # described the ORIGINAL bytes, and a caller that refills
            # its input buffer after submit() returns would otherwise
            # have the dispatch compute the NEW bytes' output and
            # settle it under the OLD digest — a self-validating
            # poisoned entry the output re-check cannot catch.
            # O(input) copy, paid by leaders (misses) only; inside the
            # try so even a failed copy (MemoryError) fails the flight
            # instead of leaking it (which would park every later
            # identical request on a future nobody resolves).
            example = jax.tree_util.tree_map(
                lambda a: np.array(a, copy=True), example)
            # chaos hook: a sleep rule here holds the leader open so
            # follower pile-up is observable; an error rule is a leader
            # failure every follower must see (and caches nothing)
            inject("cache.stampede")
            fut = self._submit_dispatch(example, timeout_ms,
                                        preprocessed=True, tenant=tenant)
        except BaseException as e:  # noqa: BLE001 — settled to followers, re-raised
            self._cache.fail(flight, e)
            raise
        # the caller gets a SEPARATE future resolved only AFTER settle
        # has copied the row: returning the dispatch future directly
        # would let the caller mutate its row in place concurrently
        # with settle's copy — a torn copy would digest-validate
        # against itself and poison every later hit
        out: Future = Future()

        def _leader_done(f: Future) -> None:
            # settle/fail OFF the dispatch worker's completion: insert
            # + resolve followers on success, fail them (cache
            # untouched) on error — a poisoned result can never be
            # stored because only a SUCCESSFUL dispatch settles
            try:
                value = f.result()
            # graftlint: allow=SDL003 reason=the leader error is forwarded to every follower via cache.fail and the caller future; re-raising in a done-callback would only hit the executor's swallow
            except BaseException as e:  # noqa: BLE001
                self._cache.fail(flight, e)
                if not out.done():
                    out.set_exception(e)
            else:
                # store=False once closed: close() already reclaimed an
                # owned namespace, and a late-settling leader (the
                # abandoned-wait close path) must not re-insert under
                # it — followers still get their copies either way
                self._cache.settle(
                    flight, value,
                    store=not (self._closed and self._cache_ns_owned))
                if not out.done():
                    out.set_result(value)

        fut.add_done_callback(_leader_done)
        return out

    def _submit_dispatch(self, example: Any,
                         timeout_ms: Optional[float],
                         preprocessed: bool = False,
                         tenant: Optional[str] = None) -> Future:
        """The direct dispatch path (the whole request path when no
        cache is configured; the single-flight leader's path when one
        is)."""
        retry_after = self._breaker_retry_after()
        if retry_after is not None:
            # count the request too: shed-rate consumers compute
            # rejected_*/requests, and queue-full rejects (raised after
            # the serving.requests incr below) are in the denominator —
            # breaker sheds must be as well or the ratio breaks 1.0
            self.metrics.incr("serving.requests")
            self.metrics.incr("serving.rejected_breaker_open")
            flight_emit("serving.shed", reason="breaker_open",
                        retry_after_s=round(retry_after, 4))
            raise ServiceUnavailableError(
                f"dispatch circuit breaker open (device failing); "
                f"retry in {retry_after:.2f}s", retry_after_s=retry_after)
        if not preprocessed:
            if self._host_preprocess is not None:
                example = self._host_preprocess(example)
            import jax

            example = jax.tree_util.tree_map(np.asarray, example)
        timeout_s = (self._default_timeout_s if timeout_ms is None
                     else max(0.0, timeout_ms) / 1e3)
        now_m = self._clock()
        deadline = None if timeout_s is None else now_m + timeout_s
        req = Request(example, deadline, now=now_m,
                      tenant=tenant or "default")
        tracer = get_tracer()
        if tracer.enabled:
            # root span of this request's trace: submit -> future settle
            req.span = tracer.start_span(
                "serving.request",
                timeout_ms=None if timeout_s is None else timeout_s * 1e3)
        self.metrics.incr("serving.requests")
        try:
            self._batcher.submit(req)
        except BaseException:
            req.finish_span("rejected")
            raise
        return req.future

    def predict(self, example: Any,
                timeout_ms: Optional[float] = None) -> Any:
        """Blocking single-request convenience: submit + wait."""
        return self.submit(example, timeout_ms=timeout_ms).result()

    async def predict_async(self, example: Any,
                            timeout_ms: Optional[float] = None) -> Any:
        """Awaitable form for asyncio handlers (wraps the submit future)."""
        import asyncio

        return await asyncio.wrap_future(
            self.submit(example, timeout_ms=timeout_ms))

    # -- dispatch ----------------------------------------------------------
    def _dispatch_loop(self) -> None:
        while True:
            batch = self._batcher.next_batch()
            if batch is None:
                return  # closed and drained
            if not batch:
                continue  # every request shed at flush
            # interruptible slot wait: if close() abandons a wedged server
            # (no watchdog configured), the batches the dispatcher holds
            # must still SETTLE — clients block in result() forever
            # otherwise
            acquired = False
            while not acquired and not self._abandon.is_set():
                acquired = self._inflight_sem.acquire(timeout=0.1)
            if not acquired:
                _settle_error(batch, ServerClosedError(
                    "server close abandoned a wedged dispatch; request "
                    "was never dispatched"))
                continue
            with self._inflight_cond:
                self._inflight += 1
            worker = threading.Thread(
                target=self._run_batch, args=(batch,), daemon=True,
                name="sparkdl-serving-batch")
            worker.start()

    def _finish_batch(self) -> None:
        self._inflight_sem.release()
        with self._inflight_cond:
            self._inflight -= 1
            self._inflight_cond.notify_all()

    def _run_batch(self, requests: List[Request]) -> None:
        finish = _Once(self._finish_batch)
        try:
            self._execute(requests, finish)
        except BaseException as e:  # noqa: BLE001 — isolate to this batch
            self.metrics.incr("serving.batch_failures")
            self._note_failure(e)
            _settle_error(requests, e)
            logger.warning("serving batch of %d failed: %s: %s",
                           len(requests), type(e).__name__, e)
        finally:
            finish()

    @staticmethod
    def _metered_kwargs(eng, on_metered) -> Dict[str, Any]:
        """``{"on_metered": ...}`` only when ``eng`` can take it.  Tests
        (and embedders) substitute plain ``fn(batch)`` callables for the
        engine; those still serve — they just don't feed the cost
        ledger's device-time meter (they don't tick the engine's
        ``engine.call_wall_s`` counter either, so conservation holds).
        The signature probe is cached on the callable."""
        if on_metered is None:
            return {}
        cached = getattr(eng, "_sdl_accepts_on_metered", None)
        if cached is None:
            try:
                params = inspect.signature(eng).parameters
                cached = ("on_metered" in params or any(
                    p.kind is inspect.Parameter.VAR_KEYWORD
                    for p in params.values()))
            except (TypeError, ValueError):
                cached = False
            try:
                eng._sdl_accepts_on_metered = cached
            except AttributeError:
                pass
        return {"on_metered": on_metered} if cached else {}

    def _guarded_call(self, eng, stacked, requests: List[Request],
                      finish: _Once, on_metered=None):
        """One model-call ATTEMPT under the stall watchdog.  The timer is
        armed per attempt (retry backoff and later attempts get their own
        window, so configuring retries never silently nullifies them) and
        covers ONLY the engine call — compile time is excluded by the
        untimed warm call in ``_execute``, and the host-side demux runs
        after the timer is disarmed.  The ``serving.model`` fault site
        sits INSIDE the watchdog window (a ``sleep`` rule is a wedged
        model the watchdog must catch; an ``error`` rule is a per-batch
        model failure)."""
        meter_kw = self._metered_kwargs(eng, on_metered)
        if self._dispatch_timeout_s is None:
            inject("serving.model")
            return eng(stacked, **meter_kw)
        attempt_done = threading.Event()

        def on_stall():
            if attempt_done.is_set():
                return
            self.metrics.incr("serving.dispatch_timeouts")
            self.metrics.incr("serving.batch_failures")
            _settle_error(requests, DispatchTimeoutError(
                f"model call exceeded "
                f"{self._dispatch_timeout_s * 1e3:.0f}ms; batch of "
                f"{len(requests)} abandoned"))
            # free the concurrency slot the wedged worker holds so later
            # batches keep flowing
            finish()

        timer = threading.Timer(self._dispatch_timeout_s, on_stall)
        timer.daemon = True
        timer.start()
        try:
            inject("serving.model")
            return eng(stacked, **meter_kw)
        finally:
            attempt_done.set()
            timer.cancel()

    def _top_off(self, gap: int, bucket: int, base: int,
                 like: Any) -> List[Request]:
        """The continuous half of ragged batching (ISSUE 13): right
        before a sub-bucket batch stacks, pull up to ``gap`` requests
        that arrived since the flush decision — they ride pad rows the
        dispatch was about to waste.  The ``batch.topoff`` fault site
        covers the pull: top-off is an OPTIMIZATION, so an injected
        failure degrades to the baseline padding (nobody is lost, the
        base batch still dispatches) instead of failing the batch."""
        try:
            inject("batch.topoff")
        # graftlint: allow=SDL003 reason=chaos contract: a failed top-off pull degrades to baseline padding (logged); the base batch must still dispatch
        except Exception as e:  # noqa: BLE001
            logger.warning("batch.topoff aborted: %s: %s; dispatching at "
                           "base fill %d/%d", type(e).__name__, e, base,
                           bucket)
            self.metrics.incr("serving.topoff_aborted")
            return []
        extras = self._batcher.top_off(gap, like=like)
        if extras:
            self.metrics.incr("serving.topoffs")
            self.metrics.incr("serving.topoff_rows", len(extras))
            flight_emit("batch.topoff", rows=len(extras), base=base,
                        bucket=bucket)
        return extras

    def _execute(self, requests: List[Request], finish: _Once) -> None:
        import jax

        n = len(requests)
        bucket = self._bucket_for(n)
        if self._ragged and n < bucket and len(
                {DynamicBatcher._payload_signature(r.payload)
                 for r in requests}) == 1:
            # top off only when the WHOLE base batch stacks: a flush can
            # legitimately pop mixed shapes (that batch is doomed to
            # fail its own stack — baseline behavior), and pulling a
            # healthy late arrival into it would widen the failure's
            # blast radius beyond what the flush policy dealt
            extras = self._top_off(bucket - n, bucket, n,
                                   requests[0].payload)
            if extras:
                # extend IN PLACE: _run_batch's error handler and the
                # stall watchdog hold this same list — a topped-off
                # request must be settled by every failure path too
                requests.extend(extras)
                n = len(requests)
        now = self._clock()
        queue_by: Dict[str, float] = {}
        for r in requests:
            waited = now - r.enqueued_at
            self.metrics.record_time("serving.time_in_queue", waited)
            queue_by[r.tenant] = queue_by.get(r.tenant, 0.0) + waited
        # Dispatch rides the same engine entrypoint as the offline stack
        # (parallel.pipeline): a micro-batch is a single device batch, so
        # the engine's single-piece fast path applies (no thread hop on
        # the latency path) and the online H2D/compute/gather overlap
        # comes from running up to max_inflight_batches of these worker
        # threads concurrently over jax's async dispatch.
        stacked = jax.tree_util.tree_map(
            lambda *rows: np.stack(rows, axis=0),
            *[r.payload for r in requests])
        eng = self._engine_for(bucket, stacked)
        if self._dispatch_timeout_s is not None and bucket not in self._warm:
            # compile OUTSIDE the watchdog window: the first call to a
            # bucket jits the program (seconds for real models), which
            # would otherwise eat any production-sized dispatch timeout
            eng(jax.tree_util.tree_map(np.zeros_like, stacked))
            self._warm.add(bucket)
        tracer = get_tracer()
        batch_span = requests[0].batch_span
        if batch_span is not None:
            batch_span.annotate(bucket=bucket)
        t0 = time.monotonic()  # real: batch_seconds_hint sizes real waits
        # re-root this worker thread onto the micro-batch span so the
        # engine's own spans (engine.call -> engine.dispatch) nest under
        # serving.request -> serving.microbatch
        # per-attempt metered engine seconds (the cost ledger's device-
        # time feed; retries append — the batch is charged what it
        # actually burned, not just the winning attempt)
        metered: List[float] = []
        with tracer.use(batch_span):
            # CircuitOpenError is exempt from the batch retry budget for
            # the same reason the engine's own _run_dispatch exempts it:
            # an open breaker fails fast BY DESIGN, and re-attempting it
            # max_retries times with backoff would turn every shed batch
            # into seconds of dead sleep against a device known to be
            # failing
            out = with_retries(
                lambda: self._guarded_call(eng, stacked, requests, finish,
                                           on_metered=metered.append),
                max_retries=self._max_retries,
                non_retryable=NON_RETRYABLE + (CircuitOpenError,),
                backoff_seconds=self._retry_backoff_s)
        batch_s = time.monotonic() - t0
        self._note_success()  # a served batch flips health back to ready
        self._batcher.batch_seconds_hint = batch_s
        self.metrics.incr("serving.batches")
        self.metrics.record_time("serving.batch_latency", batch_s)
        self.metrics.observe("serving.batch_fill_ratio",
                             n / eng.device_batch_size)
        # Attribute the settled batch BEFORE futures resolve, so any
        # completion-ordered observer (the fleet's settle barrier, the
        # twin's tick) sees the ledger already charged.  Degrade-not-
        # fail: the batch SERVED — an attribution failure (cost.attr
        # chaos included) is an error counter, never a failed request.
        if self._cost is not None:
            try:
                tenant_rows: Dict[str, int] = {}
                for r in requests:
                    tenant_rows[r.tenant] = tenant_rows.get(r.tenant,
                                                            0) + 1
                hbm = self._cost_hbm.get(bucket)
                if hbm is None:
                    sh = eng.sharding_info()
                    hbm = float(sh.get("param_bytes_per_chip") or 0.0)
                    self._cost_hbm[bucket] = hbm
                self._cost.record_batch(
                    model=self.model_desc, bucket=bucket,
                    tenant_rows=tenant_rows,
                    device_s=sum(metered),
                    queue_s_by_tenant=queue_by,
                    pad_rows=bucket - n,
                    hbm_bytes=hbm)
            # graftlint: allow=SDL003 reason=cost.attr degrade contract: attribution failure is counted and logged, the served batch still settles below
            except Exception as e:  # noqa: BLE001
                self.metrics.incr("serving.cost_attr_errors")
                self._cost.record_error()
                logger.warning("cost attribution failed for batch of %d "
                               "(bucket %d): %s: %s", n, bucket,
                               type(e).__name__, e)
        done = self._clock()
        slowest: Optional[Request] = None
        slowest_s = 0.0
        for i, r in enumerate(requests):
            if r.future.done():
                continue  # watchdog raced us; result discarded
            # copy, don't view: a retained row must pin O(row), not the
            # whole [bucket, ...] batch output it was sliced from
            row = jax.tree_util.tree_map(
                lambda a: np.array(a[i], copy=True), out)
            try:
                r.future.set_result(row)
                self.metrics.incr("serving.completed")
                latency_s = done - r.enqueued_at
                self.metrics.record_time("serving.request_latency",
                                         latency_s)
                if latency_s >= slowest_s:
                    slowest, slowest_s = r, latency_s
            except InvalidStateError:
                pass
        # close the micro-batch span BEFORE the request roots so every
        # child window sits inside its parent's, then capture exemplars
        # (offer is a float compare unless this batch holds a new top-K
        # outlier; a no-op with tracing off)
        if batch_span is not None:
            requests[0].batch_span = None
            batch_span.finish()
        slow_trace = (slowest.span.trace_id
                      if slowest is not None and slowest.span is not None
                      else None)
        for r in requests:
            r.finish_span()
        if slow_trace is not None:
            self.exemplars.offer(slowest_s, slow_trace, tracer)

    # -- lifecycle ---------------------------------------------------------
    @property
    def closed(self) -> bool:
        return self._closed

    def queue_depth(self) -> int:
        return self._batcher.depth()

    @property
    def bucket_sizes(self) -> List[int]:
        """The compiled bucket plan (mesh-rounded, de-duplicated)."""
        return list(self._buckets)

    @property
    def max_queue(self) -> int:
        return self._batcher.max_queue

    def queue_pressure(self) -> float:
        """Queue occupancy in [0, 1] — the admission-pressure signal the
        fleet layer sheds lowest-priority traffic against."""
        return self._batcher.depth() / max(1, self._batcher.max_queue)

    def wake(self) -> None:
        """Re-evaluate the batcher's flush conditions — how a
        virtual-time driver tells the dispatcher the injected clock
        moved (see :meth:`DynamicBatcher.wake`)."""
        self._batcher.wake()

    @property
    def cache(self):
        """The result cache this server probes (None when uncached)."""
        return self._cache

    @property
    def cache_namespace(self) -> tuple:
        """The key prefix this server's entries live under."""
        return self._cache_ns

    def sharding_info(self) -> Optional[Dict[str, Any]]:
        """The bucket engines' weight-sharding layout (ISSUE 14):
        mesh shape, total vs per-chip param bytes, sharded leaf count,
        policy digest.  All buckets share one device weight copy and
        one policy, so the first engine's snapshot speaks for the
        server; ``None`` until a bucket engine exists (pre-warmup, no
        traffic yet)."""
        with self._engine_lock:
            first = next(iter(self._engines.values()), None)
        return None if first is None else first.sharding_info()

    def executable_state(self) -> Dict[int, Dict[str, Any]]:
        """Per-bucket compiled-program identity: the ``id()`` of the
        bucket engine's shared ``jax.jit`` object and that object's
        executable-cache size.  Two servers over the SAME fn (a fleet
        entry's v1 and v2) report equal ``jit_id`` per bucket, and a
        hot-swap that truly reuses the compiled executable leaves
        ``executables`` unchanged — the no-recompile proof
        ``serving.fleet.rollout`` asserts at promote time."""
        with self._engine_lock:
            engines = dict(self._engines)
        out: Dict[int, Dict[str, Any]] = {}
        for b, eng in sorted(engines.items()):
            compiled = eng._compiled
            try:
                n_exec = int(compiled._cache_size())
            except (AttributeError, TypeError):  # older jax: identity only
                n_exec = None
            out[b] = {"jit_id": id(compiled), "executables": n_exec}
        return out

    def stats(self) -> Dict[str, float]:
        """Snapshot of the serving metrics (counters, gauges, latency
        p50/p99 — see ``utils.metrics.Metrics.summary``), plus any
        ``pipeline.*`` stage metrics the shared engines recorded."""
        summary = self.metrics.summary()  # ONE aggregation pass
        return {k: v for k, v in summary.items()
                if k.startswith(("serving.", "engine_", "pipeline."))}

    def varz(self) -> Dict[str, Any]:
        """The ``/varz``-shaped structured form of :meth:`stats`: nested
        sections instead of flat dotted keys, plus server config/state,
        the full metrics snapshot (stable schema —
        ``obs.export.metrics_snapshot``), and the slow-request exemplars
        (full span trees of the slowest requests; populated only while
        ``SPARKDL_TRACE`` tracing is on).  JSON-serializable throughout:
        ``json.dumps(srv.varz())`` IS the monitoring endpoint body."""
        from sparkdl_tpu.obs.export import metrics_snapshot

        m = self.metrics

        def dist_ms(name: str) -> Dict[str, float]:
            out: Dict[str, float] = {}
            for q, key in ((50, "p50_ms"), (99, "p99_ms")):
                v = m.percentile(name, q, kind="timing")
                if v is not None:
                    out[key] = round(v * 1e3, 3)
            return out

        snap = metrics_snapshot(m)
        return {
            "server": {
                "closed": self._closed,
                "max_batch_size": self.max_batch_size,
                "bucket_sizes": list(self._buckets),
                "ragged": self._ragged,
                "queue_depth": self.queue_depth(),
                "inflight_batches": self._inflight,
            },
            "health": self.health(),
            "counters": {k: v for k, v in snap["counters"].items()
                         if k.startswith("serving.")},
            "latency_ms": {
                "request": dist_ms("serving.request_latency"),
                "batch": dist_ms("serving.batch_latency"),
                "queue": dist_ms("serving.time_in_queue"),
            },
            "metrics": snap,
            "cache": (self._cache.info() if self._cache is not None
                      else None),
            "cost": (self._cost.snapshot() if self._cost is not None
                     else None),
            "sharding": self.sharding_info(),
            "exemplars": self.exemplars.snapshot(),
        }

    def close(self, drain: bool = True,
              timeout_s: Optional[float] = 30.0) -> None:
        """Stop the server.  ``drain=True`` (graceful): stop admission,
        flush and serve everything already queued, wait for in-flight
        batches.  ``drain=False``: queued requests fail with
        ``ServerClosedError``; in-flight batches are still awaited.
        Idempotent.

        If the drain cannot complete within ``timeout_s`` (a wedged model
        call with no ``dispatch_timeout_ms`` configured), the wait is
        abandoned and every request NOT in the wedged batch itself is
        settled with ``ServerClosedError`` — only futures inside a batch
        whose model call never returns stay pending (configure
        ``dispatch_timeout_ms`` to bound that case too)."""
        if self._closed:
            self._batcher.close(drain=drain)
            return
        self._closed = True
        flight_emit("serving.drain", drain=drain,
                    queued=self._batcher.depth())
        try:
            self._batcher.close(drain=drain)
            self._dispatcher.join(timeout=timeout_s)
            if self._dispatcher.is_alive():
                logger.warning(
                    "close(): dispatcher still busy after %ss; abandoning "
                    "— undispatched requests fail with ServerClosedError",
                    timeout_s)
                self._abandon.set()
                self._dispatcher.join(timeout=5.0)
                self._batcher.close(drain=False)  # settle anything queued
            deadline = (None if timeout_s is None
                        else time.monotonic() + timeout_s)
            with self._inflight_cond:
                while self._inflight > 0:
                    remaining = (None if deadline is None
                                 else deadline - time.monotonic())
                    if remaining is not None and remaining <= 0:
                        logger.warning(
                            "close(): %d batch(es) still in flight "
                            "after %.1fs; abandoning wait",
                            self._inflight, timeout_s)
                        return
                    self._inflight_cond.wait(remaining)
        finally:
            if self._cache is not None and self._cache_ns_owned:
                # this server's anon namespace is unreachable once it
                # is closed — reclaim the bytes from the shared store
                self._cache.invalidate(self._cache_ns)

    def __enter__(self) -> "Server":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close(drain=exc_type is None)


class HeadFanoutServer:
    """Featurize ONCE, serve thousands of per-tenant heads (ISSUE 17).

    The production shape of the paper's core trick (a shared
    ``DeepImageFeaturizer`` backbone + a cheap per-use-case head): one
    backbone :class:`Server` at the FEATURE cut, fronted by the
    feature-cut cache namespace (``serving.cache.feature_namespace`` —
    keyed on the backbone's lockfile fingerprint + weight digest, so a
    hot content digest pays the backbone once EVER, and head churn
    keeps entries warm), fanned out through a
    :class:`~sparkdl_tpu.parallel.engine.HeadBank` whose single vmapped
    program serves every tenant's head by gather-by-tenant-index.

    Per-request cost once the feature cache is warm: zero backbone
    FLOPs, zero backbone queue slots (the probe short-circuits BEFORE
    the backbone server), head-milliseconds only.  Per-fleet HBM cost:
    one backbone copy + one stacked head bank (budgeted via
    ``hbm_budget_bytes`` against ``mesh.param_sharding_stats``) instead
    of a full model copy per tenant.

    The no-backbone-recompile contract: :meth:`add_head` /
    :meth:`swap_head` / :meth:`remove_head` return a
    ``serving.fleet.rollout.head_swap_report`` proving — via backbone
    jit-object identity, executable-cache non-growth, and the committed
    ``PROGRAMS.lock.json`` fingerprint — that head mutation never
    touched the backbone program.  In-flight requests are safe across a
    swap: the bank mutates atomically under its lock, so every future
    settles (with the old head's output or the new one, never a torn
    bank)."""

    def __init__(self, model, variables: Any = None, *,
                 head_fn: Optional[Callable] = None,
                 mesh=None,
                 hbm_budget_bytes: Optional[int] = None,
                 cache: Any = None,
                 cost: Any = None,
                 metrics: Optional[Metrics] = None,
                 model_desc: Optional[str] = None,
                 **server_kwargs):
        from sparkdl_tpu.parallel.engine import HeadBank
        from sparkdl_tpu.serving.cache import (feature_namespace,
                                               lockfile_model_fingerprint,
                                               resolve_cache)

        if isinstance(model, str):
            from sparkdl_tpu.transformers.named_image import \
                zoo_serving_bundle

            fn, host_vars, overrides, zoo_head = zoo_serving_bundle(
                model, featurize=True, feature_cut=True)
            if head_fn is None:
                head_fn = zoo_head
            desc = model
        else:
            fn, host_vars, overrides = _resolve_model(
                model, variables, featurize=True)
            desc = getattr(model, "__name__", type(model).__name__)
        self.model_desc = model_desc if model_desc is not None else desc
        self.metrics = metrics if metrics is not None else Metrics()
        self._backbone_fn = fn
        self._backbone_vars = host_vars
        # Backbone identity, pinned ONCE at construction: the committed
        # StableHLO fingerprint (None for unaudited fns) and the weight
        # digest together key the feature-cut namespace — head churn
        # can touch neither.
        self._fingerprint = lockfile_model_fingerprint(self.model_desc)
        self._weights_digest = content_digest(host_vars)
        self._feature_ns = feature_namespace(
            self.model_desc, self._fingerprint, self._weights_digest)
        # The backbone Server is built from the RESOLVED fn (one
        # resolution, like the fleet registry) so its jit identity is
        # this object's identity for the whole lifetime; zoo engine
        # overrides ride along fleet-style (caller kwargs win, and the
        # dtype pair travels together).
        dtype_keys = ("compute_dtype", "output_host_dtype")
        caller_set_dtype = any(k in server_kwargs for k in dtype_keys)
        for k, v in overrides.items():
            if k in dtype_keys and caller_set_dtype:
                continue
            server_kwargs.setdefault(k, v)
        resolved_cache, _, _ = resolve_cache(cache, self._feature_ns,
                                             "headfanout")
        # One ledger for the tier: feature-hit charges here and the
        # backbone's device-time attribution land on the SAME instance,
        # so the per-tenant showback covers both halves of a request
        from sparkdl_tpu.obs.cost import resolve_cost

        self._cost = resolve_cost(cost)
        self._backbone = Server(fn, host_vars, mesh=mesh,
                                cache=(resolved_cache if resolved_cache
                                       is not None else False),
                                cache_namespace=self._feature_ns,
                                metrics=self.metrics,
                                cost=(self._cost if self._cost is not None
                                      else False),
                                model_desc=self.model_desc,
                                **server_kwargs)
        self._bank = HeadBank(head_fn=head_fn, mesh=mesh,
                              hbm_budget_bytes=hbm_budget_bytes,
                              metrics=self.metrics)
        self.last_head_swap_report: Optional[Dict[str, Any]] = None
        self._swap_lock = named_lock("serving.headfanout.swap")

    # -- head management (the no-backbone-recompile surface) --------------

    @property
    def bank(self):
        """The :class:`HeadBank` serving this tier's head pass."""
        return self._bank

    @property
    def backbone(self) -> Server:
        """The feature-cut backbone server."""
        return self._backbone

    @property
    def feature_namespace(self) -> tuple:
        """The feature-cut cache namespace (backbone identity only)."""
        return self._feature_ns

    def tenants(self) -> List[str]:
        return self._bank.tenants()

    def _head_mutation(self, op: str, tenant: str, weights) -> Dict[str, Any]:
        from sparkdl_tpu.serving.fleet.rollout import head_swap_report

        with self._swap_lock:
            exec_before = self._backbone.executable_state()
            bank_before = self._bank.jit_info()
            fp_before = self._fingerprint
            if op == "add":
                self._bank.add_head(tenant, weights)
            elif op == "swap":
                self._bank.swap_head(tenant, weights)
            else:
                self._bank.remove_head(tenant)
            from sparkdl_tpu.serving.cache import \
                lockfile_model_fingerprint

            report = head_swap_report(
                self.model_desc, tenant, op,
                exec_before, self._backbone.executable_state(),
                bank_before, self._bank.jit_info(),
                fp_before, lockfile_model_fingerprint(self.model_desc))
            self.last_head_swap_report = report
            return report

    def add_head(self, tenant: str, weights) -> Dict[str, Any]:
        """Register a new tenant's head; returns the no-backbone-
        recompile report (``head_swap_report``)."""
        return self._head_mutation("add", tenant, weights)

    def swap_head(self, tenant: str, weights) -> Dict[str, Any]:
        """Hot-swap an existing tenant's head under load; returns the
        no-backbone-recompile report."""
        return self._head_mutation("swap", tenant, weights)

    def remove_head(self, tenant: str) -> Dict[str, Any]:
        """Evict a departed tenant's head; returns the report."""
        return self._head_mutation("remove", tenant, None)

    # -- request path ------------------------------------------------------

    def _feature_probe(self, example: Any):
        """(digest-keyed feature row or None) from the feature-cut
        cache — side-effect-free on a miss (``InferenceCache.get``), so
        miss accounting stays with the backbone's single-flight
        lookup."""
        cache = self._backbone.cache
        if cache is None:
            return None
        import jax

        probe = example
        if self._backbone._host_preprocess is not None:
            probe = self._backbone._host_preprocess(probe)
        probe = jax.tree_util.tree_map(np.asarray, probe)
        key = self._feature_ns + (content_digest(probe),)
        return cache.get(key)

    def submit(self, example: Any, tenant: str,
               timeout_ms: Optional[float] = None) -> Future:
        """Admit one (example, tenant) request; returns a Future of the
        tenant's head output row.

        A warm content digest short-circuits BEFORE the backbone server
        (``cache.feature_hit``): no backbone queue slot, no dispatch —
        the request pays the head pass only.  A cold digest rides the
        backbone's cached submit path (single-flight leaders, so N
        concurrent identical payloads cost ONE backbone dispatch), and
        the head pass runs when the features settle."""
        tenant = str(tenant)
        self.metrics.incr("headfanout.requests")
        feats_value = self._feature_probe(example)
        if feats_value is not None:
            self.metrics.incr("headfanout.feature_hits")
            flight_emit("cache.feature_hit", tenant=tenant)
            self._charge_feature_hit(tenant)
            out: Future = Future()
            try:
                row = self._bank.dispatch(
                    np.asarray(feats_value)[None], [tenant])[0]
            # graftlint: allow=SDL003 reason=the error is the future's result; the caller decides
            except BaseException as e:  # noqa: BLE001
                out.set_exception(e)
            else:
                out.set_result(row)
            return out
        feats_fut = self._backbone.submit(example, timeout_ms=timeout_ms,
                                          tenant=tenant)
        out = Future()

        def _features_done(f: Future) -> None:
            try:
                feats = f.result()
                row = self._bank.dispatch(
                    np.asarray(feats)[None], [tenant])[0]
            # graftlint: allow=SDL003 reason=forwarded to the caller's future; raising in a done-callback would only hit the executor's swallow
            except BaseException as e:  # noqa: BLE001
                if not out.done():
                    out.set_exception(e)
            else:
                if not out.done():
                    out.set_result(row)

        feats_fut.add_done_callback(_features_done)
        return out

    def predict(self, example: Any, tenant: str,
                timeout_ms: Optional[float] = None):
        """Blocking single-request form of :meth:`submit`."""
        return self.submit(example, tenant, timeout_ms=timeout_ms).result()

    def predict_batch(self, examples: Sequence[Any],
                      tenants: Sequence[str],
                      timeout_ms: Optional[float] = None) -> List[Any]:
        """K tenants' rows, ONE head pass: resolve every row's features
        (warm digests from the cache, cold ones through the backbone —
        which batches/coalesces them), stack, and dispatch the whole
        mixed-tenant batch through the bank's single vmapped program."""
        tenants = [str(t) for t in tenants]
        if len(tenants) != len(examples):
            raise ValueError(f"{len(examples)} examples but "
                             f"{len(tenants)} tenants")
        self.metrics.incr("headfanout.requests", len(tenants))
        rows: List[Any] = [None] * len(tenants)
        pending: List[tuple] = []
        for i, ex in enumerate(examples):
            feats = self._feature_probe(ex)
            if feats is not None:
                self.metrics.incr("headfanout.feature_hits")
                flight_emit("cache.feature_hit", tenant=tenants[i])
                self._charge_feature_hit(tenants[i])
                rows[i] = np.asarray(feats)
            else:
                pending.append(
                    (i, self._backbone.submit(ex, timeout_ms=timeout_ms,
                                              tenant=tenants[i])))
        for i, fut in pending:
            rows[i] = np.asarray(fut.result())
        out = self._bank.dispatch(np.stack(rows), tenants)
        self.metrics.incr("headfanout.head_passes")
        return [out[i] for i in range(len(tenants))]

    # -- proof / observability surfaces -----------------------------------

    def executable_state(self) -> Dict[int, Dict[str, Any]]:
        """The BACKBONE's per-bucket compiled-program identity (the
        half the no-recompile proof pins; the head side is
        :meth:`head_state`)."""
        return self._backbone.executable_state()

    def head_state(self) -> Dict[str, Any]:
        """The head bank's jit identity + executable-cache size."""
        return self._bank.jit_info()

    def head_stats(self) -> Dict[str, Any]:
        """Stacked-bank HBM accounting (``param_sharding_stats``)."""
        return self._bank.stats()

    def warmup(self, example: Any) -> None:
        """Compile the backbone's bucket programs (no cache writes)."""
        self._backbone.warmup(example)

    def warm_head(self, features_row) -> None:
        """Compile the head program for the current bank capacity by
        dispatching one zeroed feature row — so latency measurements
        over a sleep-wrapped backbone never charge a head compile."""
        ts = self._bank.tenants()
        if not ts:
            return
        row = np.zeros_like(np.asarray(features_row))
        self._bank.dispatch(row[None], [ts[0]])

    def health(self) -> Dict[str, Any]:
        return self._backbone.health()

    def queue_depth(self) -> int:
        return self._backbone.queue_depth()

    def queue_pressure(self) -> float:
        return self._backbone.queue_pressure()

    def breaker_retry_after(self) -> Optional[float]:
        return self._backbone.breaker_retry_after()

    def wake(self) -> None:
        self._backbone.wake()

    @property
    def cache(self):
        return self._backbone.cache

    @property
    def bucket_sizes(self) -> List[int]:
        return self._backbone.bucket_sizes

    def stats(self) -> Dict[str, float]:
        summary = self.metrics.summary()
        return {k: v for k, v in summary.items()
                if k.startswith(("serving.", "engine_", "pipeline.",
                                 "headfanout.", "headbank."))}

    def _charge_feature_hit(self, tenant: str) -> None:
        """Near-zero ledger charge for a feature-cut short-circuit
        (same degrade-not-fail contract as ``Server._charge_hit``)."""
        if self._cost is None:
            return
        try:
            self._cost.record_hit(tenant=tenant, model=self.model_desc,
                                  kind="feature_hit")
        # graftlint: allow=SDL003 reason=cost.attr degrade contract: attribution failure is counted and logged, the hit already served
        except Exception as e:  # noqa: BLE001
            self.metrics.incr("serving.cost_attr_errors")
            self._cost.record_error()
            logger.warning("cost attribution (feature_hit) failed: "
                           "%s: %s", type(e).__name__, e)

    def varz(self) -> Dict[str, Any]:
        """The backbone's ``/varz`` body plus the fan-out tier's own
        section (bank mode/size/HBM, feature-hit counters, swap
        report).

        The ``cache`` section follows the SAME schema as
        ``Server.varz()`` — the fan-out tier's feature-cut hit and
        request counters are merged into ``cache["counters"]`` under
        ``cache.*`` keys, so one dashboard query shape covers both
        server types (ISSUE 18 satellite)."""
        doc = self._backbone.varz()
        snap = doc.get("metrics", {}).get("counters", {})
        doc["headfanout"] = {
            "tenants": len(self._bank),
            "bank": self._bank.stats(),
            "head_state": self._bank.jit_info(),
            "feature_namespace": list(self._feature_ns),
            "requests": snap.get("headfanout.requests", 0),
            "feature_hits": snap.get("headfanout.feature_hits", 0),
            "head_passes": snap.get("headfanout.head_passes", 0),
            "last_head_swap_report": self.last_head_swap_report,
        }
        if doc.get("cache") is not None:
            counters = doc["cache"].setdefault("counters", {})
            counters["cache.feature_hits"] = snap.get(
                "headfanout.feature_hits", 0)
            counters["cache.feature_requests"] = snap.get(
                "headfanout.requests", 0)
        if self._cost is not None:
            doc["cost"] = self._cost.snapshot()
        return doc

    def close(self, drain: bool = True,
              timeout_s: Optional[float] = 30.0) -> None:
        """Close the backbone server.  Feature entries are NOT
        reclaimed: the namespace is backbone identity, not this
        object's — a later server over the same backbone (same
        fingerprint + weights) legitimately serves them warm."""
        self._backbone.close(drain=drain, timeout_s=timeout_s)

    def __enter__(self) -> "HeadFanoutServer":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close(drain=exc_type is None)
