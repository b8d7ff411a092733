"""Per-layer metrics: one reader per file, ``layer_metrics/<name>.py``,
found by the metric's name in ``BENCHMARK.json``.  (The end-to-end
metrics' readers, ``end_to_end/<name>.py``, read the same ``obs``.)

A reader is one function ``read(obs) -> float | None``.  ``obs`` is the
run's ``Observations``; a reader that finds nothing to read (no such
span in this cell, no trace in this run) returns ``None`` and the
harness leaves the metric out of the line.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional


class JobSpan(NamedTuple):
    start: float               # seconds on the benchmark's clock
    end: float
    images: int
    spans: Dict[str, float]    # seconds by layer inside the job


class Observations(NamedTuple):
    window_s: float                      # window start to last job's end
    jobs: List[JobSpan]                  # finished jobs, in order
    counters: Dict[str, float]           # program counters, window deltas
    config: Dict[str, Any]               # the configuration's file
    peak: Dict[str, Any]                 # this device kind's row of peaks.json
    chips: int
    trace: Optional[Any]                 # trace_reduce.Reduced, traced runs
    setup_s: float = 0.0                 # process start to window start
