"""Dynamic micro-batching: bounded admission queue + flush policy.

The clipper-style adaptive-batching core of the serving layer: single
requests accumulate in a bounded FIFO and flush as one micro-batch when
the batch is full (``max_batch_size``) or the OLDEST waiting request has
waited ``max_wait_ms`` — so light traffic pays at most one wait window of
latency and heavy traffic amortizes dispatch over full batches.

Continuous RAGGED batching (ISSUE 13): when the batcher knows the
server's compiled ``bucket_plan``, an age/deadline-triggered flush no
longer grabs *everything waiting* and pads it into the nearest bucket —
it cuts the queue at the largest bucket boundary the depth covers, so
that cut dispatches with ZERO pad rows and only the true sub-bucket
residual ever pays the engine's ``_pad`` path.  The residual itself can
still be topped off by late arrivals right up to dispatch
(:meth:`DynamicBatcher.top_off`, pulled by ``Server._execute`` after it
picks the bucket).  ``SPARKDL_RAGGED=0`` restores the flush-on-full
baseline everywhere (:func:`ragged_enabled_from_env`).

Responsibilities split: the batcher owns admission (backpressure via
``QueueFullError``), the flush policy, and deadline shedding at flush
time; the :class:`~sparkdl_tpu.serving.server.Server` owns bucketing,
dispatch, and demultiplexing.
"""

from __future__ import annotations

import os
import time
from collections import deque
from concurrent.futures import Future, InvalidStateError
from typing import Any, Callable, List, Optional, Sequence

from sparkdl_tpu.analysis.lockcheck import named_condition
from sparkdl_tpu.faults import inject
from sparkdl_tpu.obs.flight import emit as flight_emit
from sparkdl_tpu.obs.trace import get_tracer
from sparkdl_tpu.serving.errors import (DeadlineExceededError, QueueFullError,
                                        ServerClosedError)
from sparkdl_tpu.utils.logging import get_logger
from sparkdl_tpu.utils.metrics import Metrics

logger = get_logger(__name__)


def ragged_enabled_from_env() -> bool:
    """``SPARKDL_RAGGED`` (default ON) — the one parser every
    ragged-aware call site shares.
    ``0``/``false``/``off``/``no`` restore the flush-on-full baseline:
    an age-triggered flush takes everything waiting and pads it into
    the nearest bucket."""
    raw = os.environ.get("SPARKDL_RAGGED", "").strip().lower()
    return raw not in ("0", "false", "off", "no")


class Request:
    """One admitted example: payload + completion future + queue timing.

    ``deadline`` is absolute ``time.monotonic()`` seconds (None = no
    deadline).  The future settles exactly once — with the model output
    row, or with a serving error (shed / rejected / batch failure).

    Tracing (``SPARKDL_TRACE``): ``span`` is the request's root span
    (opened at submit, closed at settle); ``batch_span`` rides the
    FIRST live request of a flushed micro-batch and carries the
    batcher→engine segment (see :meth:`DynamicBatcher.next_batch`).
    Both stay None with tracing off.
    """

    __slots__ = ("payload", "future", "enqueued_at", "deadline", "span",
                 "batch_span", "tenant")

    def __init__(self, payload: Any, deadline: Optional[float] = None,
                 now: Optional[float] = None, tenant: str = "default"):
        self.payload = payload
        # cost-attribution identity only (admission/quota live in the
        # Fleet): every request charges SOME tenant, anonymous = "default"
        self.tenant = tenant
        self.future: Future = Future()
        # ``now`` lets a clock-injected caller stamp queue entry on the
        # same (possibly virtual) timeline its deadlines live on
        self.enqueued_at = time.monotonic() if now is None else now
        self.deadline = deadline
        self.span = None
        self.batch_span = None

    def expired(self, now: Optional[float] = None) -> bool:
        if self.deadline is None:
            return False
        return (time.monotonic() if now is None else now) >= self.deadline

    def finish_span(self, status: str = "ok") -> None:
        """Close this request's root span exactly once (settle paths
        race: worker demux vs. watchdog vs. close — ``Span.finish`` is
        idempotent, so the losers are no-ops)."""
        sp = self.span
        if sp is not None:
            self.span = None
            sp.finish(status)


class DynamicBatcher:
    """Bounded request queue with size-or-age flush.

    Thread model: any number of submitter threads call :meth:`submit`;
    ONE dispatcher thread blocks in :meth:`next_batch`.  ``close`` may be
    called from any thread.
    """

    def __init__(self, *, max_batch_size: int = 64,
                 max_wait_ms: float = 5.0,
                 max_queue: int = 1024,
                 bucket_plan: Optional[Sequence[int]] = None,
                 align: int = 1,
                 metrics: Optional[Metrics] = None,
                 clock: Optional[Callable[[], float]] = None):
        if max_batch_size < 1:
            raise ValueError(f"max_batch_size must be >= 1, got "
                             f"{max_batch_size}")
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.max_batch_size = int(max_batch_size)
        # Mesh alignment (ISSUE 14): ``align`` is the serving mesh's
        # data-axis size.  Every ragged CUT must land on a compiled
        # bucket boundary that is a multiple of it (the engine rounds
        # its device batch the same way — effective_device_batch), so a
        # raw bucket plan is rounded up here exactly as the engine
        # would round it; the Server already passes mesh-rounded
        # buckets, making this a no-op there, but a batcher constructed
        # directly with raw buckets must not cut at sizes the mesh
        # cannot split evenly.  A bucket rounded ABOVE max_batch_size
        # is reachable only via top-off, exactly like a Server whose
        # mesh-rounded bucket exceeds its configured batch (_ragged_take
        # keeps the baseline's max_batch_size cut contract).
        self.align = max(1, int(align))
        # Ragged mode (ISSUE 13): with the server's compiled bucket plan
        # in hand, flushes cut the queue at bucket boundaries (module
        # docstring).  None = the flush-on-full baseline.
        if bucket_plan is not None:
            bucket_plan = sorted(int(b) for b in bucket_plan)
            if not bucket_plan or bucket_plan[0] < 1:
                raise ValueError(f"bucket_plan must be positive, got "
                                 f"{bucket_plan}")
            if self.align > 1:
                bucket_plan = sorted(
                    {b + (self.align - b % self.align) % self.align
                     for b in bucket_plan})
        self.bucket_plan = bucket_plan
        self.max_wait_s = max(0.0, float(max_wait_ms)) / 1e3
        self.max_queue = int(max_queue)
        # Flush-early guard: a queued request whose deadline lands INSIDE
        # the wait window flushes this long before expiry, so a timeout
        # shorter than max_wait_ms still dispatches under light load
        # instead of being shed with 100% loss.  Sized above typical
        # thread-wakeup jitter; expiry is then judged at the FLUSH
        # DECISION (see next_batch), so scheduler overshoot between the
        # decision and the pop can't shed a request that made the flush.
        self.deadline_guard_s = 10e-3
        self.metrics = metrics if metrics is not None else Metrics()
        # Server-maintained estimate of one batch's service time; seeds the
        # retry_after hint before the first batch completes.
        self.batch_seconds_hint = max(self.max_wait_s, 1e-3)
        # Injected monotonic clock: every flush/age/deadline judgement
        # reads THIS source, so a virtual clock (the traffic twin's)
        # drives the whole wait-window state machine deterministically.
        # Condition WAITS still time out on the real clock — a frozen
        # virtual clock re-checks flush conditions on submit/:meth:`wake`.
        self._clock = clock if clock is not None else time.monotonic
        self._q: deque = deque()
        self._cond = named_condition("serving.batcher")
        self._closed = False
        self._drain = True

    # -- admission (submitter threads) ------------------------------------
    def submit(self, request: Request) -> None:
        """Admit one request or raise: ``ServerClosedError`` after close,
        ``QueueFullError`` (with a ``retry_after_s`` hint) when the queue
        is at capacity — admission never blocks the caller.  A queue-full
        shed is a ``serving.shed`` flight event (emitted AFTER the
        batcher lock is released — the recorder never runs under the
        locks it observes)."""
        full = None
        with self._cond:
            if self._closed:
                raise ServerClosedError("server is closed")
            # fault site: a queue-full storm (exc=queue_full) or an
            # admission stall (a sleep here holds the batcher lock —
            # deliberately: that IS a stalled admission path) — AFTER
            # the closed check, so injected faults never mask
            # ServerClosedError for clients of a closed server
            inject("serving.admit")
            if len(self._q) >= self.max_queue:
                self.metrics.incr("serving.rejected_queue_full")
                # Capacity frees one batch at a time: full-queue drain time
                # is (depth / batch) service periods.
                periods = len(self._q) / self.max_batch_size
                hint = max(1e-3, periods * self.batch_seconds_hint)
                full = (len(self._q), hint)
            else:
                self._q.append(request)
                self.metrics.gauge("serving.queue_depth",
                                   float(len(self._q)))
                self._cond.notify_all()
        if full is not None:
            depth, hint = full
            flight_emit("serving.shed", reason="queue_full", depth=depth,
                        retry_after_s=round(hint, 4))
            raise QueueFullError(
                f"admission queue full ({depth}/{self.max_queue})",
                retry_after_s=hint)

    def depth(self) -> int:
        with self._cond:
            return len(self._q)

    def wake(self) -> None:
        """Nudge the dispatcher to re-evaluate its flush conditions.

        With an injected clock the age/deadline triggers only move when
        that clock does — and nothing else notifies the condition when
        it moves.  A virtual-time driver advances its clock, then calls
        this, so wait-window flushes fire at the virtual instant they
        would have fired at on the real clock."""
        with self._cond:
            self._cond.notify_all()

    # -- flush (dispatcher thread) ----------------------------------------
    def next_batch(self) -> Optional[List[Request]]:
        """Block until a micro-batch is due; return its LIVE requests.

        Flush triggers: queue holds ``max_batch_size`` requests, the
        oldest waiting request is ``max_wait_s`` old, a queued request's
        deadline is about to expire (within ``deadline_guard_s`` — a
        timeout tighter than the wait window flushes early rather than
        being shed), or the batcher is closing (drain: remaining requests
        flush immediately).  Expired deadlines are shed HERE — after the
        flush decision, before any device work — so a shed request costs
        nothing downstream.  May return an empty list (whole batch shed);
        returns None only when closed and fully drained.
        """
        with self._cond:
            now = self._clock()
            while True:
                if self._q:
                    if self._closed:
                        break  # draining: flush whatever is left
                    now = self._clock()
                    oldest_wait = now - self._q[0].enqueued_at
                    earliest = min(
                        (r.deadline for r in self._q
                         if r.deadline is not None), default=None)
                    if (len(self._q) >= self.max_batch_size
                            or oldest_wait >= self.max_wait_s
                            or (earliest is not None
                                and earliest - now <= self.deadline_guard_s)):
                        break
                    timeout = self.max_wait_s - oldest_wait
                    if earliest is not None:
                        timeout = min(timeout, earliest - now
                                      - self.deadline_guard_s)
                    self._cond.wait(max(timeout, 1e-4))
                elif self._closed:
                    return None
                else:
                    self._cond.wait()
                    now = self._clock()
            take = min(len(self._q), self.max_batch_size)
            if self.bucket_plan is not None:
                take = self._ragged_take(len(self._q), now)
            batch = [self._q.popleft() for _ in range(take)]
            self.metrics.gauge("serving.queue_depth", float(len(self._q)))
        # expiry is judged at the flush DECISION: a request the guard
        # selected while still live dispatches even if the pop itself was
        # delayed past its deadline by scheduling jitter
        live = self._shed_expired(batch, now)
        tracer = get_tracer()
        if tracer.enabled and live:
            # the micro-batch span adopts the FIRST live request's trace
            # (the convention that keeps one strict serving → batcher →
            # engine nesting chain; sibling requests keep their own root
            # spans and are recorded on the batch as an attribute)
            live[0].batch_span = tracer.start_span(
                "serving.microbatch", parent=live[0].span,
                batch_size=len(live), shed=len(batch) - len(live),
                member_traces=[r.span.trace_id for r in live
                               if r.span is not None])
        return live

    def _ragged_take(self, depth: int, now: float) -> int:
        """How many requests THIS flush should pop (called under the
        condition lock): the largest compiled bucket the queue depth
        covers — that cut dispatches with zero pad rows — or the whole
        sub-bucket residual when the depth is below the smallest
        bucket.  A deadline about to expire PAST the cut grows it to
        the smallest bucket covering that request (capped at the
        largest bucket; the loop re-flushes immediately for anything
        still beyond it), so ragged cuts never starve an urgent
        request the baseline would have carried."""
        buckets = self.bucket_plan
        # the flush cut never exceeds max_batch_size — a mesh-rounded
        # bucket can be LARGER than the configured batch, and popping
        # past the baseline's cut would merge requests the flush policy
        # promised separate batches (top-off may still fill the pad gap
        # up to the bucket, but only with stack-compatible arrivals)
        depth = min(depth, self.max_batch_size)
        take = depth
        for b in reversed(buckets):
            if depth >= b:
                take = b
                break
        else:
            return depth  # sub-bucket residual: pad is the true floor
        if take >= depth:
            return take
        # urgent-deadline coverage beyond the cut (bounded scan: at most
        # max_batch_size entries — deque indexing stays cheap)
        last_urgent = -1
        for i in range(take, depth):
            r = self._q[i]
            if (r.deadline is not None
                    and r.deadline - now <= self.deadline_guard_s):
                last_urgent = i
        if last_urgent >= take:
            for b in buckets:
                if b > last_urgent:
                    return min(depth, b)
        return take

    @staticmethod
    def _payload_signature(payload: Any):
        """(shape, dtype) per leaf — what has to match for two requests
        to stack into one device batch."""
        import jax

        return tuple((tuple(getattr(l, "shape", ())),
                      str(getattr(l, "dtype", type(l).__name__)))
                     for l in jax.tree_util.tree_leaves(payload))

    def top_off(self, k: int, like: Any = None) -> List[Request]:
        """Pop up to ``k`` late-arriving requests to TOP OFF a forming
        batch right before dispatch (the continuous half of ragged
        batching): a sub-bucket residual the flush popped can absorb
        arrivals that landed between the flush decision and the stack,
        up to its bucket boundary, instead of dispatching pad rows.

        ``like`` (a payload of the forming batch) bounds the pull to
        STACK-COMPATIBLE requests only, stopping at the first mismatch
        (FIFO preserved, never reordered): a poison-shaped request must
        keep failing only the batch the flush policy would have put it
        in — top-off can shrink pad, never widen a failure's blast
        radius.  Expired deadlines among the pulled requests are shed
        exactly like a flush would (they cost nothing downstream).
        Returns the LIVE pulled requests; safe from any dispatch worker
        thread."""
        if k <= 0:
            return []
        sig = (None if like is None
               else self._payload_signature(like))
        with self._cond:
            take = min(int(k), len(self._q))
            if take <= 0:
                return []
            batch: List[Request] = []
            for _ in range(take):
                if sig is not None and self._payload_signature(
                        self._q[0].payload) != sig:
                    break
                batch.append(self._q.popleft())
            if not batch:
                return []
            self.metrics.gauge("serving.queue_depth", float(len(self._q)))
            now = self._clock()
        return self._shed_expired(batch, now)

    def _shed_expired(self, batch: List[Request],
                      now: float) -> List[Request]:
        live: List[Request] = []
        for r in batch:
            if r.expired(now):
                self.metrics.incr("serving.shed_deadline")
                flight_emit("serving.shed", reason="deadline",
                            waited_s=round(now - r.enqueued_at, 4))
                try:
                    r.future.set_exception(DeadlineExceededError(
                        f"deadline expired after "
                        f"{now - r.enqueued_at:.3f}s in queue"))
                except InvalidStateError:
                    pass  # client cancel() raced us; never kill the
                    # dispatcher over an already-settled future
                r.finish_span("shed")
            else:
                live.append(r)
        if len(live) < len(batch):
            logger.info("shed %d expired request(s) before dispatch",
                        len(batch) - len(live))
        return live

    # -- shutdown ----------------------------------------------------------
    def close(self, drain: bool = True) -> None:
        """Stop admission.  ``drain=True`` lets the dispatcher flush the
        remaining queue; ``drain=False`` fails every queued future with
        ``ServerClosedError`` immediately."""
        with self._cond:
            self._closed = True
            if not drain:
                while self._q:
                    r = self._q.popleft()
                    try:
                        r.future.set_exception(
                            ServerClosedError("server closed before "
                                              "dispatch"))
                    except InvalidStateError:
                        pass  # client cancel() raced the close
                    r.finish_span("closed")
                self.metrics.gauge("serving.queue_depth", 0.0)
            self._cond.notify_all()


def ragged_arrival_benchmark(n_bursts: int = 10,
                             max_batch_size: int = 32,
                             bucket_sizes=(8, 16, 32),
                             dispatch_ms: float = 8.0,
                             max_wait_ms: float = 25.0,
                             gap_ms: float = 70.0,
                             seed: int = 0,
                             feature_dim: int = 8):
    """Deterministic chip-free proof of the ragged-batching lever
    (ISSUE 13 — the ``synthetic_overlap_benchmark`` /
    ``zipfian_cache_benchmark`` pattern: a sleep stands in for the
    device, so the result is stable on any host and needs no chip).

    A seeded MIXED-SIZE arrival process — ``n_bursts`` bursts of
    1..``max_batch_size`` requests, each burst isolated by ``gap_ms`` >
    ``max_wait_ms`` so every burst forms its own flush window — is
    replayed twice through a real sleep-wrapped
    :class:`~sparkdl_tpu.serving.server.Server`: once with
    ``ragged=False`` (the flush-on-full baseline: each burst pops whole
    and pads into the nearest covering bucket) and once with
    ``ragged=True`` (bucket-boundary cuts + top-off: only the true
    sub-bucket residual pads).  The model fn is row-local elementwise
    math, so per-request outputs are BIT-IDENTICAL regardless of which
    micro-batch or bucket a request lands in — the ragged path must be
    a pure pad-row optimization, never an approximation.  Pad
    accounting comes from the machinery that already exists: the
    engine's ``engine.rows``/``engine.pad_rows`` ledger and the
    ``serving.batch_fill_ratio`` histogram.
    """
    import time as _time

    import numpy as np

    from sparkdl_tpu.serving.server import Server
    from sparkdl_tpu.utils.metrics import Metrics as _Metrics

    rng = np.random.default_rng(seed)
    sizes = [int(s) for s in rng.integers(1, max_batch_size + 1,
                                          size=n_bursts)]
    n_requests = sum(sizes)
    variables = {"scale": np.float32(2.0)}

    def fn(v, x):
        import jax.numpy as jnp

        # row-local elementwise math: a request's output row depends
        # only on its own input row, never on batch size or pad
        # content — what makes the cross-mode bit-identity assertable
        return jnp.tanh(x * v["scale"] + 0.5)

    payloads = [rng.normal(size=(feature_dim,)).astype(np.float32)
                for _ in range(n_requests)]

    def run(ragged: bool):
        metrics = _Metrics()
        srv = Server(fn, variables, max_batch_size=max_batch_size,
                     max_wait_ms=max_wait_ms,
                     max_queue=n_requests + 16,
                     bucket_sizes=list(bucket_sizes),
                     max_inflight_batches=4,
                     ragged=ragged, cache=False, metrics=metrics)
        try:
            srv.warmup(payloads[0])  # compile BEFORE the sleep wrap
            dispatches = [0]
            for b in srv.bucket_sizes:
                eng = srv._engine_for(b)
                real = eng.run_padded

                def slow(batch, _real=real):  # the synthetic device
                    dispatches[0] += 1
                    _time.sleep(dispatch_ms / 1e3)
                    return _real(batch)

                eng.run_padded = slow
            # warmup dispatched one exact-fill batch per bucket; snapshot
            # its ledger so the returned accounting covers the replay only
            warm = dict(metrics.snapshot_raw()["counters"])
            warm_fills = len(metrics.histograms.get(
                "serving.batch_fill_ratio", []))
            futs = []
            t0 = _time.perf_counter()
            i = 0
            for s in sizes:
                for _ in range(s):
                    futs.append(srv.submit(payloads[i]))
                    i += 1
                _time.sleep(gap_ms / 1e3)
            outs = [np.asarray(f.result(timeout=60)) for f in futs]
            wall_s = _time.perf_counter() - t0
        finally:
            srv.close()
        snap = metrics.snapshot_raw()
        counters = {k: v - warm.get(k, 0.0)
                    for k, v in snap["counters"].items()}
        fills = list(metrics.histograms.get(
            "serving.batch_fill_ratio", []))[warm_fills:]
        return {
            "wall_s": round(wall_s, 4),
            "dispatches": dispatches[0],
            "rows": int(counters.get("engine.rows", 0)),
            "pad_rows": int(counters.get("engine.pad_rows", 0)),
            "topoff_rows": int(counters.get("serving.topoff_rows", 0)),
            "batches": int(counters.get("serving.batches", 0)),
            "fill_mean": (round(float(np.mean(fills)), 4)
                          if len(fills) else None),
        }, outs

    flush, flush_out = run(ragged=False)
    ragged, ragged_out = run(ragged=True)
    bit_identical = all(np.array_equal(a, b)
                        for a, b in zip(flush_out, ragged_out))
    total = max(1, flush["rows"] + flush["pad_rows"])
    rtotal = max(1, ragged["rows"] + ragged["pad_rows"])
    return {
        "n_requests": n_requests,
        "n_bursts": n_bursts,
        "burst_sizes": sizes,
        "bucket_sizes": list(bucket_sizes),
        "dispatch_ms": dispatch_ms,
        "flush": flush,
        "ragged": ragged,
        "flush_pad_frac": round(flush["pad_rows"] / total, 4),
        "ragged_pad_frac": round(ragged["pad_rows"] / rtotal, 4),
        "pad_rows_saved": flush["pad_rows"] - ragged["pad_rows"],
        "bit_identical": bit_identical,
    }
