"""Crash-safe driver dryrun: ``__graft_entry__`` with incremental JSONL.

A driver whose only record of the dryrun is its stdout capture loses
every stage that HAD completed when the run hangs or is killed.  This
CLI runs the same entry points (``dryrun_multichip(n)`` — the sharded
train/score step on n VIRTUAL CPU devices, in a CPU-pinned child, run
FIRST so that no child starts once this process holds a backend — then
the ``entry()`` compile check on this process's default device) and
appends one fsync'd JSONL record per stage to an on-disk artifact as it goes —
``started`` / ``ok`` / ``error`` with wall seconds — so a SIGKILL at any
instant leaves a valid, stage-resolved partial record (atexit cannot
survive SIGKILL; incremental flush can).

Every record is stamped ``faults: none|<spec>`` (``SPARKDL_FAULTS``), so
a chaos dryrun can never be mistaken for a clean one.

Usage::

    python tools/dryrun.py [--devices N] [--artifact PATH] [--skip-entry]

Exit code 0 iff every requested stage passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


class StageLog:
    """Stage records through the shared crash-safe JSONL writer
    (``utils.jsonl.CrashSafeJsonlWriter``): one fsync'd write per
    record, and — same policy as bench.py's artifact rider — a
    read-only checkout disables the on-disk copy instead of failing the
    dryrun (stdout still carries every record)."""

    def __init__(self, path: str):
        from sparkdl_tpu.utils.jsonl import CrashSafeJsonlWriter

        self.writer = CrashSafeJsonlWriter(path)
        self.writer.reset()

    def write(self, **rec) -> None:
        from sparkdl_tpu.faults import current_spec

        rec.setdefault("ts", round(time.time(), 3))
        rec.setdefault("faults", current_spec() or "none")
        line = json.dumps(rec)
        print(line, flush=True)
        self.writer.write_line(line)


def _run_stage(log: StageLog, stage: str, fn) -> bool:
    log.write(stage=stage, status="started")
    t0 = time.perf_counter()
    try:
        detail = fn()
    # graftlint: allow=SDL003 reason=the written stage record IS the report; driver greps it for pass/fail
    except BaseException as e:
        log.write(stage=stage, status="error",
                  seconds=round(time.perf_counter() - t0, 3),
                  error=f"{type(e).__name__}: {str(e)[:300]}")
        return False
    log.write(stage=stage, status="ok",
              seconds=round(time.perf_counter() - t0, 3),
              **(detail or {}))
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, default=8,
                    help="mesh size for dryrun_multichip (default 8)")
    ap.add_argument("--artifact", default=os.path.join(
        _REPO, "artifacts", "dryrun_lines.jsonl"),
        help="incremental JSONL artifact path")
    ap.add_argument("--skip-entry", action="store_true",
                    help="skip the single-chip entry() compile check")
    args = ap.parse_args(argv)

    log = StageLog(args.artifact)
    import __graft_entry__

    ok = _run_stage(
        log, f"dryrun_multichip[{args.devices}]",
        lambda: __graft_entry__.dryrun_multichip(args.devices))
    if not args.skip_entry:
        def run_entry():
            import jax
            import numpy as np

            fn, (variables, batch) = __graft_entry__.entry()
            # no donation: one-shot smoke dispatch of caller-owned arrays
            out = jax.jit(fn, donate_argnums=())(variables, batch)
            from sparkdl_tpu.parallel.mesh import device_stamp

            return {"output_shape": list(np.asarray(out).shape),
                    "device": device_stamp()}

        ok = _run_stage(log, "entry", run_entry) and ok
    log.write(stage="summary", status="ok" if ok else "error")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
