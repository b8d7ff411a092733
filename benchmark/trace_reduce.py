"""From a profiler trace to numbers.

The JAX profiler writes an ``.xplane.pb``; ``load_xplane`` reads it
with ``jax.profiler.ProfileData`` into plain tuples, and everything
else here is arithmetic on lists of ``(name, start_ns, duration_ns)``
that ``tests/test_trace_reduce.py`` checks on a synthetic trace with a
known answer:

* ``busy_union``: the time in which at least one operation ran — the
  union of the operations' intervals, so nested and overlapping events
  count once;
* ``totals_by_name``: summed duration per operation name;
* ``idle_gaps``: the intervals of the window in which nothing ran, each
  labelled with the host annotation that covers most of it.

On a TPU each chip is one plane ``/device:TPU:<n>``.  Its line
``XLA Ops`` holds one event per executed operation, and its line
``XLA Modules`` one event per execution of a compiled program (a
dispatch).  Only the device is traced: with the host tracer on, the
runtime's own host threads write millions of events a second around
every transfer and the traced jobs take three times as long (PERF.md,
findings).  The host's side of the story is the benchmark's own spans
on its own clock, brought onto the trace's clock by one marker program
(``CLOCK_MARKER``) that the harness runs between starting the trace
and opening the window: the host knows when it ran, the trace knows
when the device ran it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)
Interval = Tuple[float, float]            # (start_ns, end_ns)

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the name the harness jits its clock marker under
CLOCK_MARKER = "bench_clock_marker"


class Trace(NamedTuple):
    """What the reduction reads of one profile."""
    device_ops: Dict[str, List[Event]]       # per device plane
    device_modules: Dict[str, List[Event]]   # per device plane


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
    return Trace(ops, modules)


def _events(line) -> List[Event]:
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


# -- arithmetic on intervals -------------------------------------------------

def clip(events: Iterable[Event], window: Interval) -> List[Event]:
    """The part of each event that lies inside ``window``."""
    lo, hi = window
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def merge(events: Iterable[Event]) -> List[Interval]:
    """Disjoint, sorted intervals covering exactly what the events cover."""
    merged: List[List[float]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if dur <= 0:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return [(s, e) for s, e in merged]


def busy_union(events: Iterable[Event]) -> float:
    """Nanoseconds in which at least one of ``events`` ran."""
    return sum(e - s for s, e in merge(events))


def totals_by_name(events: Iterable[Event]) -> Dict[str, float]:
    totals: Dict[str, float] = {}
    for name, _, dur in events:
        totals[name] = totals.get(name, 0.0) + dur
    return totals


_HLO_NAME = re.compile(r"^(%[\w.\-]+) = ")
_HLO_SHAPE = re.compile(r"\w+\[[^\]]*\]")
_HLO_KIND = re.compile(r"\s([\w\-]+)\(")


def short_op_name(name: str) -> str:
    """The trace names an operation by its whole HLO line; keep its name,
    the shape of its (first) result and its kind:
    ``%fusion.685 f32[512,147,147,64] fusion``."""
    head = _HLO_NAME.match(name)
    if not head:
        return name[:100]
    rest = name[head.end():]
    shape, kind = _HLO_SHAPE.search(rest), _HLO_KIND.search(rest)
    return " ".join([head.group(1)] + [m.group(0).strip("( ") if m is kind
                                       else m.group(0)
                                       for m in (shape, kind) if m])


def top(totals: Dict[str, float], n: int = 10) -> List[Tuple[str, float]]:
    return sorted(totals.items(), key=lambda kv: (-kv[1], kv[0]))[:n]


def idle_gaps(busy: Sequence[Interval], window: Interval,
              annotations: Sequence[Event],
              unlabelled: str = "bench.unannotated"
              ) -> List[Tuple[str, float, float]]:
    """``(label, start_ns, duration_ns)`` of every interval of ``window``
    not covered by ``busy`` (disjoint and sorted, as ``merge`` gives).
    The label is the annotation that overlaps the gap longest; among
    annotations that nest, the innermost (shortest) wins a tie."""
    lo, hi = window
    gaps: List[Interval] = []
    cursor = lo
    for s, e in busy:
        if e <= lo or s >= hi:
            continue
        if s > cursor:
            gaps.append((cursor, min(s, hi)))
        cursor = max(cursor, e)
    if cursor < hi:
        gaps.append((cursor, hi))
    out = []
    for gs, ge in gaps:
        best, best_key = unlabelled, (0.0, 0.0)
        for name, start, dur in annotations:
            overlap = min(ge, start + dur) - max(gs, start)
            key = (overlap, -dur)
            if overlap > 0 and key > best_key:
                best, best_key = name, key
        out.append((best, gs, ge - gs))
    return out


# -- the reduction the benchmark uses ---------------------------------------

class Reduced(NamedTuple):
    window_s: float
    busy_s: float                            # averaged over the chips used
    module_s: float                          # program executions, per chip
    module_executions: int
    device_ops: List[Tuple[str, float]]      # top operations, seconds
    idle_gaps: List[Tuple[str, float]]       # longest gaps by label, seconds


def clock_offset_ns(trace: Trace, marker_host_ns: float) -> float:
    """What to add to a time on the host's clock (ns) to get the
    trace's: the device ran the clock marker at the one, the host at
    the other."""
    starts = [start + dur / 2 for events in trace.device_modules.values()
              for name, start, dur in events if CLOCK_MARKER in name]
    if not starts:
        raise ValueError(f"the trace holds no {CLOCK_MARKER!r} execution")
    return min(starts) - marker_host_ns


def reduce_trace(trace: Trace, chips: int, window: Interval,
                 annotations: Sequence[Event]) -> Reduced:
    """Busy time, program-execution time, top operations and idle gaps
    of ``window``, over the ``chips`` device planes that did the most
    work.  ``window`` and ``annotations`` (the benchmark's own spans)
    are on the trace's clock."""
    if not trace.device_ops:
        raise ValueError("the trace holds no device plane with operations")
    per_plane = {p: clip(ev, window) for p, ev in trace.device_ops.items()}
    planes = sorted(per_plane, key=lambda p: -busy_union(per_plane[p]))[:chips]
    busy_ns = sum(busy_union(per_plane[p]) for p in planes) / len(planes)
    module_events = [e for p in planes
                     for e in clip(trace.device_modules.get(p, []), window)]
    totals: Dict[str, float] = {}
    for p in planes:
        for name, dur in totals_by_name(per_plane[p]).items():
            totals[name] = totals.get(name, 0.0) + dur / len(planes)
    gaps = idle_gaps(merge(per_plane[planes[0]]), window, annotations)
    gap_totals: Dict[str, float] = {}
    for label, _, dur in gaps:
        gap_totals[label] = gap_totals.get(label, 0.0) + dur
    return Reduced(
        window_s=(window[1] - window[0]) / 1e9,
        busy_s=busy_ns / 1e9,
        module_s=sum(d for _, _, d in module_events) / len(planes) / 1e9,
        module_executions=len(module_events),
        device_ops=[(short_op_name(n), d / 1e9) for n, d in top(totals)],
        idle_gaps=[(n, d / 1e9) for n, d in top(gap_totals)])
