"""Faults planted in the trunk that ``correct`` has to fail, each through
the kind and the whole of a run: the scan's carry between chunks
dropped, attention that sees the positions after it, one multiplier
left at 1.  ``plant(fault)`` patches the program for the length of a
``with`` block; nothing of the program knows of it.

``python3 -m benchmark.tests.planted_trunk --fault <name> --seed <n>`` drives
the cell ``falcon_h1_34b.rows4k`` once with the fault planted on the
machine it is started on (through ``chiprun`` that is the chip, where
the scan and attention are the kernels) and prints the result line,
which has to say ``"correct": false``; ``--blocks 1`` cuts the depth to
one block for a shorter run.  Its line is no measurement.
"""

import contextlib
import json
import sys
import time

FAULTS = ("carry_dropped", "attention_not_causal", "key_multiplier_at_1",
          "mlp_down_multiplier_at_1")
CELL = "falcon_h1_34b.rows4k"


@contextlib.contextmanager
def plant(fault: str):
    from sparkdl_tpu.models import hybrid_trunk
    from sparkdl_tpu.ops import attention

    real = {name: getattr(hybrid_trunk, name)
            for name in ("ssd_scan", "causal_attention", "model_function")}

    def scan_without_carry(x, dt, a, b, c, d, *, chunk, **kw):
        # every chunk a row of its own: it is entered with an empty state
        r, t = x.shape[:2]

        def cut(v):
            return v.reshape((r * (t // chunk), chunk) + v.shape[2:])

        y = real["ssd_scan"](cut(x), cut(dt), a, cut(b), cut(c), d,
                             chunk=chunk, **kw)
        return y.reshape(x.shape)

    def attention_over_all(q, k, v, *, heads, kv_heads, precision=None):
        form = (attention.attention_kernel if attention._on_tpu()
                else attention.attention_blocked)
        return form(q, k, v, heads=heads, kv_heads=kv_heads, causal=False,
                    precision=precision)

    def with_config(change):
        def model_function(config, *args, **kwargs):
            return real["model_function"]({**config, **change(config)},
                                          *args, **kwargs)
        return model_function

    patch = {
        "carry_dropped": ("ssd_scan", scan_without_carry),
        "attention_not_causal": ("causal_attention", attention_over_all),
        "key_multiplier_at_1": ("model_function", with_config(
            lambda config: {"key_multiplier": 1.0})),
        "mlp_down_multiplier_at_1": ("model_function", with_config(
            lambda config: {"mlp_multipliers": [config["mlp_multipliers"][0],
                                                1.0]})),
    }[fault]
    setattr(hybrid_trunk, *patch)
    try:
        yield
    finally:
        setattr(hybrid_trunk, patch[0], real[patch[0]])


def main(argv=None) -> int:
    import argparse

    from benchmark import harness
    from benchmark.flops import reference_module

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[1])
    parser.add_argument("--fault", choices=FAULTS, required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    parser.add_argument("--blocks", type=int, default=None)
    args = parser.parse_args(argv)
    cell = harness.load_cell(harness.ROOT, CELL)
    if args.blocks:
        cell.config["num_hidden_layers"] = args.blocks
        cell.config["flops_per_image"] = reference_module(
            harness.reference_of(cell.config)).flops_per_row(cell.config)
    try:
        with plant(args.fault):
            line = harness.run_cell(cell, args.seed, args.seconds, False,
                                    process_start=time.perf_counter())
    except harness.BenchmarkError as e:
        sys.exit(f"benchmark: {e}")
    line["planted"] = args.fault
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
