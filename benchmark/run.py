"""python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1> [--control 1]

One run of one cell of ``BENCHMARK.json`` in one process on the machine
it is started on: set-up (weights and traffic from the seed, the cell's
one shape warmed by one job), the measured window, then the check of
what the window produced against the plain reference.  The LAST line of
standard output is the result, one JSON object; the numbers compared,
each beside its limit, are its last key and the last lines of standard
error.  Without a TPU, with fewer or more chips than the cell asks
for, or outside a checkout of the repository it exits non-zero and
prints no result.  ``--control 1`` is the control of ``correct`` at the
cell's own size (never a measured run, never run by the driver): the
configuration's lower precision in the program's place, which has to
come out ``"correct": false``.  ``README.md`` here says how cells,
configurations,
traffic mixes and metrics are added as files.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmark import harness

    try:
        import sparkdl_tpu
    except ImportError as e:
        print(f"no sparkdl_tpu in {ROOT}: {e}", file=sys.stderr)
        return 3
    if not os.path.abspath(sparkdl_tpu.__file__).startswith(ROOT + os.sep):
        print(f"sparkdl_tpu was imported from {sparkdl_tpu.__file__}, not "
              f"from this checkout ({ROOT})", file=sys.stderr)
        return 3
    try:
        cell = harness.load_cell(ROOT, args.workload)
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace),
                                  process_start=_PROCESS_START,
                                  control=bool(args.control))
    except harness.BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    for name, check in result["checks"].items():
        print(f"check {name}: {check['value']!r} (limit {check['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
