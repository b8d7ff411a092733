"""Pipelined host/device execution for the scoring stack.

An end-to-end scoring run is a host loop around a device: run serially,
the device idles while the host decodes/packs the next batch, and the
host idles while a dispatch+fetch round trip completes.  This module is
the tf.data/prefetch analog for the engine: a bounded-depth stage graph

    host prepare (decode/pack/pad)  ->  H2D + device dispatch
                                    ->  D2H gather + host cast

run on overlapping worker threads with backpressure queues, so batch k+1
decodes while batch k computes and batch k-1 gathers.  ``jax``'s async
dispatch provides the device-side overlap; this layer provides the
host-side one.

Contracts:
  * BIT-IDENTICAL outputs to the serial path (``pipeline=False``) — the
    stages call the same engine methods (``_pad``/``run_padded``/
    ``_trim``) in the same per-piece order; the FIFO queues only move
    them onto threads.
  * bounded residency — host prep runs at most ``depth`` items ahead and
    at most ``window`` dispatched batches are device-resident, the
    serial path's in-flight window.
  * per-stage queue-depth / stall metrics land in the engine's
    ``utils.metrics.Metrics`` registry under ``pipeline.*`` (surfaced by
    ``bench.py`` per-config JSON lines and ``Server.stats``).

Failure domain (ISSUE 4): each stage loop carries a fault-injection
site (``pipeline.prepare`` / ``pipeline.dispatch`` / ``pipeline.gather``
— :mod:`sparkdl_tpu.faults`), and a stage crash — injected or real —
cancels the graph, joins every worker with a bounded timeout, and
re-raises consumer-side as :class:`PipelineStageError` naming the stage
and piece index, with the original exception chained.  No queue is left
with a blocked producer/consumer and no thread outlives the run.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Dict, Iterable, Iterator, Optional

import numpy as np

from sparkdl_tpu.faults import inject
from sparkdl_tpu.obs.trace import get_tracer
from sparkdl_tpu.utils.logging import get_logger
from sparkdl_tpu.utils.metrics import Metrics

logger = get_logger(__name__)

_DONE = object()    # end-of-stream marker flowing through every queue
_ABORT = object()   # returned by queue helpers when the run was cancelled


class PipelineStageError(RuntimeError):
    """A pipeline worker stage crashed.  Carries the failure DOMAIN —
    ``stage`` (``prepare``/``dispatch``/``gather``) and ``piece`` (the
    0-based piece index the stage was working when it died; -1 when it
    crashed before touching one) — so a production incident names the
    failing layer instead of surfacing a bare exception from an anonymous
    daemon thread.  The original exception is chained as ``__cause__``
    (and echoed in the message, so existing ``pytest.raises(...,
    match=...)`` callers keep matching); the run is guaranteed to have
    drained: all three stage threads observed the stop flag and exited
    before this raises."""

    def __init__(self, stage: str, piece: int, cause: BaseException):
        super().__init__(
            f"pipeline {stage} stage failed at piece {piece}: "
            f"{type(cause).__name__}: {cause}")
        self.stage = stage
        self.piece = piece


class PipelineStageFatalError(PipelineStageError, ValueError):
    """The DETERMINISTIC variant: raised when the stage's underlying
    cause sits in ``utils.retry.NON_RETRYABLE`` (shape/param validation,
    NaN fail-fast).  Subclassing ``ValueError`` keeps it non-retryable
    through every ``utils.retry`` wrapper — wrapping a deterministic
    model bug in a plain RuntimeError would silently re-classify it as
    transient and burn whole retry budgets reproducing it."""


def wrap_stage_error(stage: str, piece: int,
                     cause: BaseException) -> BaseException:
    """The consumer-side re-raise policy for a crashed stage: wrap into
    the structured :class:`PipelineStageError` family — EXCEPT the
    engine's typed fail-fast signal.  ``CircuitOpenError`` must reach
    callers unwrapped (its ``retry_after_s``/``last_error`` drive
    serving shed decisions, and wrapping it in a RuntimeError would turn
    the breaker's fail-fast back into retryable noise)."""
    # runtime-only import: engine imports this module at load time
    from sparkdl_tpu.parallel.engine import CircuitOpenError
    from sparkdl_tpu.utils.retry import NON_RETRYABLE

    if isinstance(cause, CircuitOpenError):
        return cause
    cls = (PipelineStageFatalError if isinstance(cause, NON_RETRYABLE)
           else PipelineStageError)
    return cls(stage, piece, cause)


class PipelinedRunner:
    """Runs an :class:`~sparkdl_tpu.parallel.engine.InferenceEngine` over
    an iterator of host batches with host prepare, H2D+dispatch, and
    D2H gather on three overlapping threads.

    ``window`` bounds dispatched-but-ungathered device batches;
    ``depth`` bounds how far host prepare runs ahead of dispatch and how
    many gathered host outputs wait for the consumer.  Peak residency is
    therefore O(depth) prepared + O(window) device + O(depth) gathered
    batches regardless of input size.
    """

    def __init__(self, engine, window: int = 2, depth: int = 2,
                 metrics: Optional[Metrics] = None):
        self.engine = engine
        self.window = max(1, int(window))
        self.depth = max(1, int(depth))
        self.metrics = metrics if metrics is not None else engine.metrics

    # -- internals ---------------------------------------------------------
    def _put(self, q: "queue.Queue", item, stop: threading.Event,
             stage: str, qname: str) -> bool:
        """Bounded put with backpressure accounting.  Gives up (False)
        when the run was cancelled — a consumer that abandoned the output
        iterator must not leak a producer blocked on a full queue."""
        t0 = time.perf_counter()
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
            except queue.Full:
                continue
            stall = time.perf_counter() - t0
            if stall > 1e-4:
                self.metrics.incr(f"pipeline.{stage}_out_stall_s", stall)
            self.metrics.observe(f"pipeline.{qname}_depth", q.qsize())
            return True
        return False

    def _get(self, q: "queue.Queue", stop: threading.Event, stage: str):
        """Bounded get with starvation accounting; ``_ABORT`` on cancel."""
        t0 = time.perf_counter()
        while not stop.is_set():
            try:
                item = q.get(timeout=0.05)
            except queue.Empty:
                continue
            stall = time.perf_counter() - t0
            if stall > 1e-4:
                self.metrics.incr(f"pipeline.{stage}_in_stall_s", stall)
            return item
        return _ABORT

    # -- the stage graph ---------------------------------------------------
    def run(self, batches: Iterable[Any]) -> Iterator[Any]:
        """Yield per-piece host outputs, bit-identical to (and in the same
        order as) the serial path."""
        import jax

        eng = self.engine
        m = self.metrics
        stop = threading.Event()
        errors: list = []

        # Observability: one "pipeline.run" span brackets the whole
        # stage graph (parented to the consumer thread's current span,
        # e.g. engine.call); each stage emits one child span per piece.
        # Disabled tracing costs one enabled-check per piece — the
        # stage code paths are otherwise byte-identical.
        tracer = get_tracer()
        run_span = (tracer.start_span("pipeline.run",
                                      parent=tracer.current())
                    if tracer.enabled else None)

        prep_q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        disp_q: "queue.Queue" = queue.Queue(maxsize=self.window)
        out_q: "queue.Queue" = queue.Queue(maxsize=self.depth)

        def fail(stage: str, piece: int, e: BaseException) -> None:
            # first failure wins (later stage crashes are usually the
            # stop-flag cascade of the first); the consumer re-raises it
            # as a structured PipelineStageError naming stage + piece
            errors.append((stage, piece, e))
            stop.set()

        def prepare() -> None:
            # the engine's OWN piece iterator (the serial path consumes
            # the same one), so dispatch order is shared by construction
            idx = 0
            try:
                src = eng._iter_pieces(batches)
                while True:
                    inject("pipeline.prepare", piece=idx)
                    with tracer.span("pipeline.prepare", parent=run_span,
                                     piece=idx) as sp:
                        item = next(src, _DONE)
                        if item is _DONE:
                            sp.annotate(eos=True)
                    if item is _DONE:
                        self._put(prep_q, _DONE, stop, "prepare",
                                  "prep_q")
                        return
                    idx += 1
                    if not self._put(prep_q, item, stop, "prepare",
                                     "prep_q"):
                        return
            # graftlint: allow=SDL003 reason=recorded via fail() and re-raised consumer-side as PipelineStageError
            except BaseException as e:
                fail("prepare", idx, e)

        def dispatch() -> None:
            idx = -1
            try:
                while True:
                    item = self._get(prep_q, stop, "dispatch")
                    if item is _ABORT:
                        return
                    if item is _DONE:
                        break
                    idx += 1
                    n, host = item
                    inject("pipeline.dispatch", piece=idx)
                    # H2D + async launch: returns as soon as the transfer
                    # is enqueued; the device computes while we loop
                    with tracer.span("pipeline.dispatch", parent=run_span):
                        dev = eng.run_padded(host)
                    m.incr("pipeline.dispatches")
                    if not self._put(disp_q, (n, dev), stop,
                                     "dispatch", "inflight_q"):
                        return
                self._put(disp_q, _DONE, stop, "dispatch", "inflight_q")
            # graftlint: allow=SDL003 reason=recorded via fail() and re-raised consumer-side as PipelineStageError
            except BaseException as e:
                fail("dispatch", idx, e)

        def gather() -> None:
            idx = -1
            try:
                while True:
                    item = self._get(disp_q, stop, "gather")
                    if item is _ABORT:
                        return
                    if item is _DONE:
                        break
                    idx += 1
                    n, dev = item
                    inject("pipeline.gather", piece=idx)
                    # span covers device wait + D2H + trim, NOT the
                    # downstream put (pipeline.gather_out_stall_s tells
                    # backpressure); when tracing is on,
                    # block_until_ready splits device wait (device_us)
                    # from the host-side copy/cast.  The force is the
                    # serial drain's own _force_part, where device
                    # errors charge the breaker/health accounting.
                    with tracer.span("pipeline.gather", parent=run_span) as sp:
                        part = eng._force_part(
                            n, dev, block=sp.block_until_ready)
                        if tracer.enabled:
                            sp.annotate(
                                rows=n,
                                bytes=sum(
                                    a.nbytes for a in
                                    jax.tree_util.tree_leaves(part)))
                    if not self._put(out_q, part, stop, "gather", "out_q"):
                        return
                    m.incr("pipeline.gathers")
                self._put(out_q, _DONE, stop, "gather", "out_q")
            # graftlint: allow=SDL003 reason=recorded via fail() and re-raised consumer-side as PipelineStageError
            except BaseException as e:
                fail("gather", idx, e)

        threads = [
            threading.Thread(target=prepare, daemon=True,
                             name="sparkdl-pipeline-prepare"),
            threading.Thread(target=dispatch, daemon=True,
                             name="sparkdl-pipeline-dispatch"),
            threading.Thread(target=gather, daemon=True,
                             name="sparkdl-pipeline-gather"),
        ]
        for t in threads:
            t.start()
        try:
            while True:
                try:
                    item = out_q.get(timeout=0.05)
                except queue.Empty:
                    if stop.is_set():
                        break
                    continue
                if item is _DONE:
                    break
                yield item
        finally:
            # cancels every stage whether we finished, raised, or the
            # consumer closed the iterator early, then ALWAYS joins with
            # a bounded timeout: a crashed run must hand back a drained
            # stage graph (no thread blocked on a queue, nothing left to
            # wedge a later run), not just a stop flag — and when tracing
            # is on the join also closes stage spans BEFORE their parent
            # (the child-within-parent invariant tests rely on).  Threads
            # exit within one 50 ms queue-poll of stop; a thread still
            # alive after the timeout is a bug worth a loud log line.
            stop.set()
            for t in threads:
                t.join(timeout=2.0)
                if t.is_alive():
                    logger.warning("pipeline stage thread %s did not exit "
                                   "within 2s of cancellation", t.name)
            if run_span is not None:
                run_span.finish()
        if errors:
            stage, piece, cause = errors[0]
            self.metrics.incr(f"pipeline.{stage}_crashes")
            err = wrap_stage_error(stage, piece, cause)
            if err is cause:
                raise err  # typed pass-through (CircuitOpenError)
            raise err from cause


def pipeline_stage_summary(metrics: Metrics) -> Dict[str, float]:
    """Compact per-stage stall/occupancy snapshot for bench JSON lines:
    stall-second counters, dispatch/gather counts, and mean queue depths
    (a stage's ``_in_stall_s`` is time starved for input; ``_out_stall_s``
    is time blocked on downstream backpressure)."""
    out: Dict[str, float] = {}
    for k, v in metrics.subset("pipeline.").items():
        if k.endswith(("_in_stall_s", "_out_stall_s")) or k.endswith(
                ("dispatches", "gathers")) or k.endswith("_depth.mean"):
            out[k] = round(float(v), 4)
    return out


def synthetic_overlap_benchmark(n_batches: int = 6,
                                dispatch_ms: float = 100.0,
                                prepare_ms: float = 100.0,
                                rows: int = 8,
                                feature_dim: int = 4,
                                metrics: Optional[Metrics] = None
                                ) -> Dict[str, Any]:
    """Deterministic proof of host/device overlap on the CPU backend.

    Simulates a device whose BLOCKING ~100 ms dispatch+fetch round trip
    rivals the host-side decode cost, without a device: the engine's
    ``run_padded`` is wrapped with a ``dispatch_ms`` sleep (the synthetic
    device) and producing each input batch sleeps ``prepare_ms`` (the
    synthetic JPEG decode).  The serial path pays ``n * (prepare + dispatch)``; the
    pipelined path overlaps them to ~``n * max(prepare, dispatch)`` — a
    2x ideal speedup at the default 100 ms/100 ms point, asserted at
    >= 1.5x by the tier-1 contract test.  Sleep-dominated, so the result
    is deterministic on any host; outputs are verified equal between the
    two paths before timings are reported.
    """
    from sparkdl_tpu.parallel.engine import InferenceEngine

    rng = np.random.default_rng(0)
    variables = {
        "w": rng.normal(size=(feature_dim, feature_dim)).astype(np.float32)}

    def fn(v, x):
        import jax.numpy as jnp

        return jnp.tanh(x @ v["w"])

    m = metrics if metrics is not None else Metrics()
    eng = InferenceEngine(fn, variables, device_batch_size=rows, metrics=m)
    real_run = eng.run_padded

    def slow_run(batch):  # the synthetic device: a blocking round trip
        time.sleep(dispatch_ms / 1e3)
        return real_run(batch)

    eng.run_padded = slow_run
    x = rng.normal(size=(eng.device_batch_size, feature_dim)
                   ).astype(np.float32)

    def batches():
        for _ in range(n_batches):
            time.sleep(prepare_ms / 1e3)  # the synthetic host decode
            yield x

    # warm the compile outside the timed region
    list(eng.map_batches([x], pipeline=False))

    t0 = time.perf_counter()
    serial = list(eng.map_batches(batches(), pipeline=False))
    serial_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    piped = list(eng.map_batches(batches(), pipeline=True))
    pipelined_s = time.perf_counter() - t0
    if len(serial) != len(piped) or not all(
            np.array_equal(a, b) for a, b in zip(serial, piped)):
        raise AssertionError(
            "pipelined outputs diverged from the serial path")
    return {
        "n_batches": n_batches,
        "dispatch_ms": dispatch_ms,
        "prepare_ms": prepare_ms,
        "serial_s": round(serial_s, 4),
        "pipelined_s": round(pipelined_s, 4),
        "speedup": round(serial_s / pipelined_s, 4),
        "stages": pipeline_stage_summary(m),
    }
