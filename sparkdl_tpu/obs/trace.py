"""Low-overhead span tracing for the scoring stack.

SURVEY.md §5: the reference had no metrics beyond the Spark UI, and
VERDICT r5 found every perf claim living in builder-side artifacts —
gap stories stayed qualitative because nothing in the pipeline could
say WHERE a request's time went.  This module makes every run
self-describing: a :class:`Tracer` issues trace/span IDs that propagate
serving request → batcher micro-batch → engine dispatch → pipeline
stage, recording parent/child spans (wall clock on a shared
``perf_counter`` timeline, plus ``block_until_ready``-bracketed device
time where a stage must force the device anyway) into a bounded,
lock-cheap ring buffer (a ``deque(maxlen)`` whose lock guards only the
O(1) append/copy, never span construction).

Gate: ``SPARKDL_TRACE``
  * ``""``/``0``/``false``/``off``/``no`` — DISABLED (default).  The
    disabled path is near-zero cost: every instrumentation site does
    one enabled-check and receives the shared no-op :data:`NULL_SPAN`;
    no IDs, no timestamps, no ring writes, and
    ``NULL_SPAN.block_until_ready`` never blocks, so async dispatch
    behavior is byte-identical to the un-instrumented code.
  * ``1``/``true``/``on``/``yes`` — enabled, in-memory ring only
    (read it with :meth:`Tracer.snapshot` / ``obs.export``).
  * anything else — treated as a DIRECTORY: enabled, and an ``atexit``
    hook flushes ``trace_<pid>.json`` (Chrome trace-event JSON,
    viewable in Perfetto / chrome://tracing) plus ``spans_<pid>.jsonl``
    there on interpreter exit (or call :meth:`Tracer.flush` yourself).

Thread model: spans cross threads by design (a serving request is
admitted on the caller's thread, batched on the dispatcher thread,
dispatched on a worker).  Parenting therefore composes two mechanisms:
an explicit ``parent=`` handle for cross-thread edges, and a per-thread
current-span stack (``tracer.span(...)`` as a context manager pushes;
:meth:`Tracer.use` re-roots a thread onto a span started elsewhere) so
same-thread nesting is automatic.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from sparkdl_tpu.analysis.lockcheck import named_lock

__all__ = [
    "Span",
    "NULL_SPAN",
    "Tracer",
    "get_tracer",
    "configure",
    "configure_from_env",
    "current_trace_id",
    "tracing_from_env",
]

_OFF = ("", "0", "false", "off", "no")
_ON = ("1", "true", "on", "yes")


def tracing_from_env():
    """``(enabled, out_dir)`` from ``SPARKDL_TRACE`` — the one parser
    every gate shares (``0|1|dir``, see module docstring)."""
    raw = os.environ.get("SPARKDL_TRACE", "").strip()
    low = raw.lower()
    if low in _OFF:
        return False, None
    if low in _ON:
        return True, None
    return True, raw


class _NullSpan:
    """The disabled-path span: a shared, stateless no-op.  Supports the
    full Span surface so instrumentation sites never branch on enabled
    beyond the one check inside ``tracer.span()``."""

    __slots__ = ()
    name = None
    trace_id = None
    span_id = None
    parent_id = None
    device_s = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def annotate(self, **attrs):
        return self

    def block_until_ready(self, x):
        # Disabled tracing must not alter async-dispatch behavior: the
        # value passes through UNBLOCKED.
        return x

    def finish(self, status: str = "ok"):
        return self


NULL_SPAN = _NullSpan()


class Span:
    """One timed operation.  ``t0``/``t1`` are ``time.perf_counter``
    seconds (a single process-wide monotonic timeline, so spans from
    different threads order correctly); ``device_s`` accumulates
    ``block_until_ready``-bracketed device wait inside the span."""

    __slots__ = ("tracer", "name", "trace_id", "span_id", "parent_id",
                 "attrs", "thread", "tid", "t0", "t1", "device_s",
                 "status")

    def __init__(self, tracer: "Tracer", name: str, trace_id: str,
                 span_id: str, parent_id: Optional[str],
                 attrs: Dict[str, Any]):
        self.tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.attrs = attrs
        t = threading.current_thread()
        self.thread = t.name
        self.tid = t.ident or 0
        self.t0 = time.perf_counter()
        self.t1: Optional[float] = None
        self.device_s = 0.0
        self.status = "ok"

    def annotate(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def block_until_ready(self, x):
        """Force device completion of ``x`` inside this span, crediting
        the wait to ``device_s`` (the wall-vs-device split the exporter
        surfaces).  Use only where the stage must block anyway (gather)
        — never on the async dispatch path."""
        import jax

        t0 = time.perf_counter()
        jax.block_until_ready(x)
        self.device_s += time.perf_counter() - t0
        return x

    def finish(self, status: str = "ok") -> "Span":
        """Close the span and record it.  Idempotent UNDER RACES: the
        claim (t1 check-and-set) and the ring append happen in one ring-
        lock hold, so concurrent finishers (worker demux vs. the stall
        watchdog settling the same batch) record the span exactly once —
        the first caller's timestamp/status win."""
        return self._finish_at(time.perf_counter(), status)

    def _finish_at(self, t1: float, status: str = "ok") -> "Span":
        tracer = self.tracer
        with tracer._ring_lock:
            if self.t1 is not None:
                return self
            self.t1 = t1
            if status != "ok":
                self.status = status
            if len(tracer._ring) == tracer.capacity:
                tracer.dropped += 1
            tracer._ring.append(self)
        return self

    # -- context-manager form: push/pop the thread-current stack -------
    def __enter__(self) -> "Span":
        self.tracer._push(self)
        return self

    def __exit__(self, exc_type, exc, tb):
        self.tracer._pop(self)
        self.finish("error" if exc_type is not None else "ok")
        return False

    def as_dict(self) -> Dict[str, Any]:
        d = {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "ts_us": round(self.t0 * 1e6, 1),
            "dur_us": round(((self.t1 if self.t1 is not None
                              else time.perf_counter()) - self.t0) * 1e6,
                            1),
            "thread": self.thread,
            "tid": self.tid,
            "status": self.status,
        }
        if self.device_s > 0.0:
            d["device_us"] = round(self.device_s * 1e6, 1)
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        return d


class _Use:
    """Context manager re-rooting THIS thread's current-span stack onto
    a span started elsewhere (cross-thread continuation) without
    finishing it on exit."""

    __slots__ = ("tracer", "span")

    def __init__(self, tracer: "Tracer", span: Span):
        self.tracer = tracer
        self.span = span

    def __enter__(self) -> Span:
        self.tracer._push(self.span)
        return self.span

    def __exit__(self, *exc):
        self.tracer._pop(self.span)
        return False


class Tracer:
    """Issues IDs, tracks per-thread current spans, and keeps finished
    spans in a bounded ring (oldest evicted first)."""

    def __init__(self, enabled: bool = False,
                 out_dir: Optional[str] = None,
                 capacity: int = 8192):
        self.enabled = bool(enabled)
        self.out_dir = out_dir
        self.capacity = int(capacity)
        # Lock-cheap ring: the bounded deque evicts oldest-first, and the
        # lock guards only the O(1) append (record hot path) and the
        # snapshot copy — never span construction or ID issue.  A bare
        # maxlen-deque append is GIL-atomic, but readers (snapshot /
        # exemplar capture under live traffic) would then race iteration
        # against appends and hit "deque mutated during iteration".
        self._ring: deque = deque(maxlen=self.capacity)
        self._ring_lock = named_lock("obs.trace.ring")
        #: spans evicted from the full ring since ``clear()``: a reader
        #: that sums spans must refuse a ring that overflowed
        self.dropped = 0
        self._ids = itertools.count(1)  # next() is atomic in CPython
        self._local = threading.local()

    # -- ids / context -------------------------------------------------
    def _next(self) -> int:
        return next(self._ids)

    def _stack(self) -> list:
        s = getattr(self._local, "stack", None)
        if s is None:
            s = self._local.stack = []
        return s

    def _push(self, span: Span) -> None:
        self._stack().append(span)

    def _pop(self, span: Span) -> None:
        stack = self._stack()
        if stack and stack[-1] is span:
            stack.pop()
        elif span in stack:  # tolerate out-of-order exits
            stack.remove(span)

    def current(self) -> Optional[Span]:
        """This thread's innermost open span (None outside any span)."""
        stack = getattr(self._local, "stack", None)
        return stack[-1] if stack else None

    # -- span creation -------------------------------------------------
    def span(self, name: str, parent: Optional[Span] = None, **attrs):
        """A span as a context manager: nests under ``parent`` (or this
        thread's current span; a new trace root when neither exists) and
        records itself on exit.  Returns :data:`NULL_SPAN` when
        disabled — the caller's ``with`` block costs two no-op calls."""
        if not self.enabled:
            return NULL_SPAN
        return self._make(name, parent, attrs)

    def start_span(self, name: str, parent: Optional[Span] = None,
                   **attrs):
        """A manually-finished span for operations that cross threads
        (e.g. a serving request: started at submit on the caller's
        thread, finished at future-settle on a worker).  NOT pushed on
        any thread stack — pair with :meth:`use` to parent same-thread
        children under it.  Call :meth:`Span.finish` exactly once."""
        if not self.enabled:
            return NULL_SPAN
        return self._make(name, parent, attrs)

    def record(self, name: str, seconds: float,
               parent: Optional[Span] = None, **attrs):
        """A span that is closed at birth: it ended now and began
        ``seconds`` ago, for an operation whose duration is only
        reported when it is over (``jax.monitoring``'s compile phases).
        Parented like :meth:`span` and appended to the ring under the
        same lock; pushed on no thread stack.  :data:`NULL_SPAN` when
        disabled."""
        if not self.enabled:
            return NULL_SPAN
        span = self._make(name, parent, attrs)
        t1 = span.t0                     # stamped on construction: now
        span.t0 = t1 - seconds
        return span._finish_at(t1)

    def _make(self, name, parent, attrs) -> Span:
        if parent is None:
            parent = self.current()
        if parent is None or parent is NULL_SPAN:
            trace_id = f"t{self._next():06x}"
            parent_id = None
        else:
            trace_id = parent.trace_id
            parent_id = parent.span_id
        return Span(self, name, trace_id, f"s{self._next():06x}",
                    parent_id, attrs)

    def use(self, span):
        """Make ``span`` this thread's current parent for the duration
        of the ``with`` block (no-op for None / the null span)."""
        if not self.enabled or span is None or span is NULL_SPAN:
            return NULL_SPAN
        return _Use(self, span)

    # -- ring ----------------------------------------------------------
    def __len__(self) -> int:
        return len(self._ring)

    def snapshot(self) -> List[Dict[str, Any]]:
        """Finished spans, oldest first, as plain dicts (the JSONL span
        schema ``tools/trace_summary.py`` and ``obs.export`` consume)."""
        with self._ring_lock:
            spans = list(self._ring)
        return [s.as_dict() for s in spans]

    def clear(self) -> None:
        with self._ring_lock:
            self._ring.clear()
            self.dropped = 0

    # -- flush ---------------------------------------------------------
    def flush(self, out_dir: Optional[str] = None) -> List[str]:
        """Write the ring to ``out_dir`` (default: the directory from
        ``SPARKDL_TRACE=<dir>``): Chrome trace-event JSON + span JSONL.
        Returns the written paths ([] when there is nothing to write or
        no directory is configured)."""
        out_dir = out_dir or self.out_dir
        spans = self.snapshot()
        if not out_dir or not spans:
            return []
        from sparkdl_tpu.obs.export import (write_chrome_trace,
                                            write_spans_jsonl)

        os.makedirs(out_dir, exist_ok=True)
        pid = os.getpid()
        chrome = os.path.join(out_dir, f"trace_{pid}.json")
        jsonl = os.path.join(out_dir, f"spans_{pid}.jsonl")
        write_chrome_trace(chrome, spans)
        write_spans_jsonl(jsonl, spans)
        return [chrome, jsonl]


# -- module singleton ------------------------------------------------------
_tracer: Optional[Tracer] = None
_tracer_lock = named_lock("obs.trace.configure")
_atexit_registered = False


def _register_atexit() -> None:
    global _atexit_registered
    if _atexit_registered:
        return
    import atexit

    # Flush whatever tracer is CURRENT at exit (configure() may have
    # replaced the one that registered the hook).
    atexit.register(lambda: _tracer is not None and _tracer.flush())
    _atexit_registered = True


def get_tracer() -> Tracer:
    """The process tracer, lazily configured from ``SPARKDL_TRACE`` on
    first use.  Cheap enough for hot paths: one global read + None
    check after initialization."""
    t = _tracer
    if t is not None:
        return t
    return configure_from_env()


def configure(enabled: bool = True, out_dir: Optional[str] = None,
              capacity: int = 8192) -> Tracer:
    """Replace the process tracer programmatically (tests, bench.py).
    A fresh tracer starts with an empty ring."""
    global _tracer
    with _tracer_lock:
        _tracer = Tracer(enabled=enabled, out_dir=out_dir,
                         capacity=capacity)
        if out_dir:
            _register_atexit()
        return _tracer


def configure_from_env() -> Tracer:
    """(Re-)configure the process tracer from ``SPARKDL_TRACE``."""
    enabled, out_dir = tracing_from_env()
    return configure(enabled=enabled, out_dir=out_dir)


def current_trace_id() -> Optional[str]:
    """The calling thread's current trace id, or None — the hook the
    trace-id-aware log format uses; must stay near-free when tracing is
    off (one global read, no tracer construction)."""
    t = _tracer
    if t is None or not t.enabled:
        return None
    s = t.current()
    return s.trace_id if s is not None else None
