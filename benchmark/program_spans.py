"""The program's own spans (``sparkdl_tpu.obs.trace``) in the traced run.

Importing this module turns the program's tracer on.  The harness
imports a cell's per-layer readers before set-up and only in a
``--trace 1`` run (``harness.run_cell``), and the readers of the
program's spans import this module: so a traced run records the spans,
an untraced run never imports it and runs with the tracer off, and the
difference between the two runs is what the tracing costs.  That import
is the one hook a new file has; an explicit step in the harness is a
``benchmark`` issue's (PERF.md section 7).

The spans are stamped with ``time.perf_counter``, the benchmark's own
clock, so a job's spans are those between its start and its end.  The
helpers here cut the tracer's ring to the window's jobs and add spans
up; a reader under ``layer_metrics/`` is a few lines over them.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from sparkdl_tpu.obs import trace

#: a job of the largest cell leaves about 35 spans; the ring must hold a
#: window's worth and set-up's warm job with room to spare
CAPACITY = 1 << 16

Span = Dict[str, Any]


def enable() -> None:
    """Turn the program's tracer on, once: a second call keeps the ring."""
    tracer = trace.get_tracer()
    if not tracer.enabled or tracer.capacity < CAPACITY:
        trace.configure(enabled=True, capacity=CAPACITY)


def in_window(obs) -> Optional[List[Span]]:
    """The finished spans that lie inside the window's jobs: from the
    first job's start to the last job's end (set-up's warm job lies
    before).  ``None`` where there is nothing to read: no job, a tracer
    that is off, or a ring that overflowed — what is left of it must not
    be summed."""
    tracer = trace.get_tracer()
    if not obs.jobs or not tracer.enabled:
        return None
    # a program from before ``Tracer.dropped``: a full ring may have lost
    dropped = getattr(tracer, "dropped", len(tracer) >= tracer.capacity)
    if dropped:
        return None
    start, end = obs.jobs[0].start * 1e6, obs.jobs[-1].end * 1e6
    return [s for s in tracer.snapshot()
            if s["ts_us"] >= start and s["ts_us"] + s["dur_us"] <= end]


def named(spans: List[Span], name: str) -> List[Span]:
    return [s for s in spans if s["name"] == name]


def total_s(spans: List[Span], name: str) -> float:
    """Summed duration of the spans called ``name``, in seconds."""
    return sum(s["dur_us"] for s in named(spans, name)) / 1e6


def attr_sum(spans: List[Span], name: str, key: str) -> float:
    """Sum of attribute ``key`` over the spans called ``name``."""
    return sum(s.get("attrs", {}).get(key, 0) for s in named(spans, name))


def ms_per_row(obs, names, rows_of: str) -> Optional[float]:
    """Milliseconds in the spans called ``names`` per row that the spans
    called ``rows_of`` count under ``rows``, over the window's jobs;
    ``None`` where there is nothing to read."""
    spans = in_window(obs)
    rows = spans and attr_sum(spans, rows_of, "rows")
    if not rows:
        return None
    return 1e3 * sum(total_s(spans, name) for name in names) / rows


def self_s(spans: List[Span], name: str) -> float:
    """Summed self time of the spans called ``name``, in seconds: a
    span's duration minus the part of it that its descendants cover, on
    whatever thread they ran (the union of their intervals)."""
    children: Dict[str, List[Span]] = {}
    for s in spans:
        children.setdefault(s["parent_id"], []).append(s)
    total = 0.0
    for span in named(spans, name):
        lo, hi = span["ts_us"], span["ts_us"] + span["dur_us"]
        below, stack = [], list(children.get(span["span_id"], ()))
        while stack:
            s = stack.pop()
            below.append((max(lo, s["ts_us"]),
                          min(hi, s["ts_us"] + s["dur_us"])))
            stack.extend(children.get(s["span_id"], ()))
        covered, at = 0.0, lo
        for a, b in sorted(below):
            if b > at:
                covered += b - max(a, at)
                at = b
        total += (hi - lo) - covered
    return total / 1e6


enable()
