"""Faults planted in the expert trunk that ``correct`` has to fail, each
through the kind and the whole of a run.  ``plant(fault)`` patches the
program for the length of a ``with`` block; nothing of the program knows
of it.  A fault is a change of the configuration the program is given
(the reference keeps the file's), of the weights it is given, or of one
function:

* ``window_ignored``: every layer sees the whole row;
* ``rotary_in_full_layers``: the layers that carry no position turn
  their queries and keys too;
* ``attention_gate_off``: ``gate_proj`` zeroed, so the gate is one half
  everywhere (a constant, which the norm after the branch takes out);
* ``route_scale_at_1``, ``route_norm_off``: the key changed;
* ``expert_bias_ignored``: the bias zeroed, so the scores alone choose;
* ``shared_expert_dropped``: the shared expert's ``down_proj`` zeroed;
* ``held_experts_shifted``: the share computed for the experts of the
  next chip (``expert_share[0] + 1``) with this chip's weights.

``python3 -m benchmark.tests.planted_experts --fault <name> [<name> ...]
--seed <n>`` drives the cell ``trinity_large_preview.rows16k`` once a
fault on the machine it is started on (through ``chiprun`` that is the
chip, where attention and the experts are the kernels) and prints each
result line, which has to say ``"correct": false``.  Its lines are no
measurements.
"""

import contextlib
import json
import sys
import time

CELL = "trinity_large_preview.rows16k"


def _zeroed(kind, name):
    def change(variables):
        import jax.numpy as jnp

        leaves = dict(variables[kind])
        leaves[name] = jnp.zeros_like(leaves[name])
        return {**variables, kind: leaves}
    return change


#: fault -> (change of the configuration, change of the variables)
GIVEN = {
    "attention_gate_off": ({}, _zeroed("layers", "self_attn.gate_proj")),
    "route_scale_at_1": ({"route_scale": 1.0}, None),
    "expert_bias_ignored": ({}, _zeroed("experts", "mlp.expert_bias")),
    "route_norm_off": ({"route_norm": False}, None),
    "shared_expert_dropped": ({}, _zeroed(
        "experts", "mlp.shared_experts.down_proj")),
    "held_experts_shifted": (lambda config: {"expert_share": [
        config["expert_share"][0] + 1, config["expert_share"][1]]}, None),
}
FAULTS = ("window_ignored", "rotary_in_full_layers") + tuple(GIVEN)


@contextlib.contextmanager
def plant(fault: str):
    from sparkdl_tpu.models import expert_trunk

    real = {name: getattr(expert_trunk, name) for name in
            ("causal_attention", "_normed_rotary", "model_function")}

    def attention_over_the_row(q, k, v, *, window, **kw):
        return real["causal_attention"](q, k, v, window=None, **kw)

    def always_turned(x, heads, scale, eps, theta, turn):
        return real["_normed_rotary"](x, heads, scale, eps, theta, 1.0)

    def given(config, variables, **kw):
        change, vary = GIVEN[fault]
        change = change(config) if callable(change) else change
        return real["model_function"](
            {**config, **change}, vary(variables) if vary else variables,
            **kw)

    patch = {"window_ignored": ("causal_attention", attention_over_the_row),
             "rotary_in_full_layers": ("_normed_rotary", always_turned),
             }.get(fault, ("model_function", given))
    setattr(expert_trunk, *patch)
    try:
        yield
    finally:
        setattr(expert_trunk, patch[0], real[patch[0]])


def main(argv=None) -> int:
    import argparse

    from benchmark import harness

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[2])
    parser.add_argument("--fault", choices=FAULTS, nargs="+", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    args = parser.parse_args(argv)
    for fault in args.fault:
        cell = harness.load_cell(harness.ROOT, CELL)
        try:
            with plant(fault):
                line = harness.run_cell(cell, args.seed, args.seconds, False,
                                        process_start=time.perf_counter())
        except harness.BenchmarkError as e:
            sys.exit(f"benchmark: {e}")
        line["planted"] = fault
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
