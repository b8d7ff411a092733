"""Set-up, seen from inside the program: the tracer's spans that ended
BEFORE the window.

``harness.run_cell`` turns the program's tracer on before set-up in a
``--trace 1`` run, so set-up's spans are in the ring beside the
window's; ``program_spans.in_window`` cuts them away, this module keeps
them and nothing else.  What set-up leaves there (PR 39): a closed span
a compile phase, ``compile.trace`` / ``compile.lower`` /
``compile.backend`` with ``program`` (and on the last ``cache``: hit,
miss or off), parented under the program's span that caused it
(``engine.dispatch`` of the warm job, ``engine.build``); and
``engine.build`` once an engine.  A compile span WITHOUT a parent is a
jit the benchmark made itself (the weights' draw, the clock's marker
program): set-up too, but not the program's, and in no metric here.

The three readers ``layer_metrics/setup_*.py`` move ``setup_s``.
"""

from __future__ import annotations

from typing import List, Optional

from benchmark import program_spans as ps
from sparkdl_tpu.obs import trace


def before_window(obs) -> Optional[List[ps.Span]]:
    """The finished spans that ended before the window's first job
    began.  ``None`` where there is nothing to read: no job, a tracer
    that is off, a ring that overflowed, or a program from before its
    compiles left spans (it has no ``Tracer.record``: the sums below
    would read a 0.0 that says nothing)."""
    tracer = trace.get_tracer()
    if not obs.jobs or not tracer.enabled or not hasattr(tracer, "record"):
        return None
    if tracer.dropped:
        return None
    start = obs.jobs[0].start * 1e6
    return [s for s in tracer.snapshot()
            if s["ts_us"] + s["dur_us"] <= start]


def parented_s(obs, names) -> Optional[float]:
    """Summed seconds of set-up's spans called one of ``names`` that the
    program's own spans caused (they have a parent); 0.0, not ``None``,
    where the tracer was on and set-up left none."""
    spans = before_window(obs)
    if spans is None:
        return None
    return sum(s["dur_us"] for s in spans
               if s["name"] in names and s["parent_id"] is not None) / 1e6


def self_s(obs, name: str) -> Optional[float]:
    """Summed self time of set-up's spans called ``name``
    (``program_spans.self_s``'s rule: what their descendants cover, a
    compile under them, is not counted twice)."""
    spans = before_window(obs)
    if spans is None:
        return None
    return ps.self_s(spans, name)
