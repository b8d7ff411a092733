"""Step timing + throughput metrics.

SURVEY.md §5: the reference had no metrics at all (Spark UI only); the TPU
build makes images/sec/chip a first-class counter since it is the baseline
metric.  Timers bracket device work with ``jax.block_until_ready`` so async
dispatch doesn't fake speedups.

The serving layer (sparkdl_tpu.serving) adds concurrent writers (admission
thread + dispatch workers), so every mutation takes a process-local lock,
and adds latency-distribution consumers, so timing/observation series
expose percentiles (``percentile``) and ``summary`` carries p50/p99.

Series are BOUNDED: each timing/histogram list keeps at most
``max_samples`` recent samples (the oldest half is dropped on overflow),
so a long-running server records per-request latency forever without
growing without limit — percentiles/means then describe the recent
window, while counters stay cumulative.
"""

from __future__ import annotations

import contextlib
import math
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from sparkdl_tpu.analysis.lockcheck import named_lock


@dataclass
class Metrics:
    """A tiny metrics registry: named counters + gauges + timing lists +
    unitless observation histograms (e.g. batch fill ratios, queue depths).
    """

    counters: Dict[str, float] = field(default_factory=dict)
    gauges: Dict[str, float] = field(default_factory=dict)
    timings_s: Dict[str, List[float]] = field(default_factory=dict)
    histograms: Dict[str, List[float]] = field(default_factory=dict)
    # Per-series sample bound: on overflow the OLDEST half is dropped, so
    # a server recording per-request latency indefinitely holds O(cap)
    # floats per series, and percentiles describe the recent window.
    max_samples: int = 16384
    # named_lock: a plain threading.Lock unless SPARKDL_LOCKCHECK=1, in
    # which case acquisitions feed the analysis.lockcheck order graph
    _lock: threading.Lock = field(
        default_factory=lambda: named_lock("utils.metrics"),
        init=False, repr=False, compare=False)

    def incr(self, name: str, value: float = 1.0):
        # float() on every recorder: numpy scalars (an np.float32 batch
        # statistic, an np.int64 row count) must never enter the
        # registry — json.dumps(Server.varz()) IS the monitoring
        # endpoint body, and a leaked numpy scalar breaks it
        value = float(value)
        with self._lock:
            self.counters[name] = self.counters.get(name, 0.0) + value

    def gauge(self, name: str, value: float):
        value = float(value)
        with self._lock:
            self.gauges[name] = value

    def _append_bounded(self, series: List[float], value: float):
        series.append(value)
        if self.max_samples and len(series) > self.max_samples:
            del series[:len(series) // 2]

    def record_time(self, name: str, seconds: float):
        seconds = float(seconds)
        with self._lock:
            self._append_bounded(self.timings_s.setdefault(name, []),
                                 seconds)

    def observe(self, name: str, value: float):
        """Append one sample to the unitless histogram ``name`` (for
        non-time distributions: batch fill ratio, queue depth, ...)."""
        with self._lock:
            self._append_bounded(self.histograms.setdefault(name, []),
                                 float(value))

    @staticmethod
    def _percentile(values: List[float], q: float) -> float:
        """Nearest-rank percentile (q in [0, 100]) of a non-empty list."""
        vs = sorted(values)
        k = max(0, min(len(vs) - 1, math.ceil(q / 100.0 * len(vs)) - 1))
        return vs[k]

    def percentile(self, name: str, q: float,
                   kind: Optional[str] = None) -> Optional[float]:
        """Percentile of a timing or histogram series; None when the
        series is absent/empty.

        Name-collision contract (a name living in BOTH families):
        lookup is EXPLICIT and deterministic — ``kind="timing"`` /
        ``kind="histogram"`` selects a family outright; with
        ``kind=None`` (default) a name PRESENT in ``timings_s`` always
        resolves to the timing series, even when that series is
        currently empty (historically an empty timing list fell through
        to a same-named histogram via ``or``-short-circuit, so the
        answer flipped family with buffer occupancy)."""
        with self._lock:
            if kind == "timing":
                series = self.timings_s.get(name)
            elif kind == "histogram":
                series = self.histograms.get(name)
            elif kind is not None:
                raise ValueError(f"kind must be 'timing', 'histogram', "
                                 f"or None, got {kind!r}")
            elif name in self.timings_s:  # timings win, even when empty
                series = self.timings_s[name]
            else:
                series = self.histograms.get(name)
            series = list(series) if series else None
        if not series:
            return None
        return self._percentile(series, q)

    def snapshot_raw(self) -> Dict[str, Dict]:
        """Consistent copies of every family under one lock hold —
        the raw shape the exporters (``obs.export``) aggregate from:
        ``{"counters", "gauges", "timings_s", "histograms"}`` with
        series copied so the caller can iterate without racing
        writers."""
        with self._lock:
            return {
                "counters": dict(self.counters),
                "gauges": dict(self.gauges),
                "timings_s": {k: list(v) for k, v in self.timings_s.items()},
                "histograms": {k: list(v)
                               for k, v in self.histograms.items()},
            }

    def subset(self, prefix: str) -> Dict[str, float]:
        """``summary()`` filtered to keys starting with ``prefix`` — the
        shape consumers embed elsewhere (``bench.py`` per-config JSON
        lines carry ``pipeline.*`` stage stalls; ``Server.stats`` carries
        ``serving.*``)."""
        return {k: v for k, v in self.summary().items()
                if k.startswith(prefix)}

    def summary(self) -> Dict[str, float]:
        with self._lock:
            out = dict(self.counters)
            out.update(self.gauges)
            timings = {k: list(v) for k, v in self.timings_s.items()}
            hists = {k: list(v) for k, v in self.histograms.items()}
        for k, v in timings.items():
            if v:
                out[f"{k}.mean_s"] = sum(v) / len(v)
                out[f"{k}.total_s"] = sum(v)
                out[f"{k}.count"] = len(v)
                out[f"{k}.p50_s"] = self._percentile(v, 50)
                out[f"{k}.p99_s"] = self._percentile(v, 99)
        for k, v in hists.items():
            if v:
                out[f"{k}.mean"] = sum(v) / len(v)
                out[f"{k}.count"] = len(v)
                out[f"{k}.p50"] = self._percentile(v, 50)
                out[f"{k}.p99"] = self._percentile(v, 99)
        return out


class StepTimer:
    """Wall-clock timer that forces device completion before stopping."""

    def __init__(self, metrics: Optional[Metrics] = None, name: str = "step"):
        self.metrics = metrics
        self.name = name
        self.elapsed_s = 0.0

    @contextlib.contextmanager
    def time(self, block_on=None):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            if block_on is not None:
                import jax
                jax.block_until_ready(block_on)
            self.elapsed_s = time.perf_counter() - t0
            if self.metrics is not None:
                self.metrics.record_time(self.name, self.elapsed_s)


def throughput_counter(num_items: int, seconds: float, num_devices: int = 1) -> Dict[str, float]:
    """items/sec and items/sec/chip — the baseline metric shape."""
    ips = num_items / seconds if seconds > 0 else float("inf")
    return {
        "items_per_sec": ips,
        "items_per_sec_per_chip": ips / max(1, num_devices),
        "seconds": seconds,
        "num_items": float(num_items),
    }
