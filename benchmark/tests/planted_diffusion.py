"""Faults planted in the block-diffusion generator that ``correct`` has
to fail, each through the kind and the whole of a run.  ``plant(fault)``
patches the program for the length of a ``with`` block; nothing of the
program knows of it.  A fault is a change of the configuration the
program is given (the reference keeps the file's), of the weights it is
given, or of one function:

* ``cache_keeps_last_denoise``: the cache takes the block's keys and
  values at its LAST DENOISE pass (half of them computed from mask ids)
  and the commit pass writes nothing;
* ``prompt_causal``: the prompt runs under the causal mask, not the mask
  by blocks;
* ``own_block_causal``: a block's queries do not see the later positions
  of their own block;
* ``rotary_one_block_late``: a generated position is turned by the
  rotary angle of the position one block after it;
* ``norm_topk_prob_off``: the key changed;
* ``reveal_by_position``: each pass reveals the first still masked
  positions of the block, whatever their confidence;
* ``mask_id_not_excluded``: the choice is over the whole vocabulary;
* ``q_norm_at_1``: ``q_norm``'s scale set to one.

``python3 -m benchmark.tests.planted_diffusion --fault <name> [<name>
...] --seed <n>`` drives the cell ``sdar_30b_a3b_chat.gen256`` once a
fault on the machine it is started on (through ``chiprun`` that is the
chip, where the prefill's attention and the experts are the kernels) and
prints each result line, which has to say ``"correct": false``.  Its
lines are no measurements.
"""

import contextlib
import json
import sys
import time

CELL = "sdar_30b_a3b_chat.gen256"


def _q_norm_at_1(variables):
    import jax.numpy as jnp

    layers = dict(variables["layers"])
    layers["self_attn.q_norm"] = jnp.ones_like(layers["self_attn.q_norm"])
    return {**variables, "layers": layers}


#: fault -> (change of the configuration, change of the variables)
GIVEN = {"norm_topk_prob_off": ({"norm_topk_prob": False}, None),
         "q_norm_at_1": ({}, _q_norm_at_1)}
FAULTS = ("cache_keeps_last_denoise", "prompt_causal", "own_block_causal",
          "rotary_one_block_late", "reveal_by_position",
          "mask_id_not_excluded") + tuple(GIVEN)


@contextlib.contextmanager
def plant(fault: str):
    import jax.numpy as jnp

    from sparkdl_tpu.models import block_diffusion, expert_trunk

    real = {"causal_attention": block_diffusion.causal_attention,
            "_attend_cache": block_diffusion._attend_cache,
            "_choose": block_diffusion._choose,
            "_turn": block_diffusion._turn,
            "model_function": block_diffusion.model_function,
            "_normed_rotary": expert_trunk._normed_rotary}

    def last_denoise_writes(step, denoise_steps):
        return step < denoise_steps, step == denoise_steps - 1

    def causal_prompt(q, k, v, *, block_length, **kw):
        return real["causal_attention"](q, k, v, **kw)

    def blind_to_later(q, k, v, *cache, **kw):
        # query i against the block's keys up to its own
        return jnp.concatenate([
            real["_attend_cache"](q[:, i:i + 1], k[:, :i + 1], v[:, :i + 1],
                                  *cache, **kw)
            for i in range(q.shape[1])], axis=1)

    def one_block_late(x, heads, scale, eps, theta, turn, first=None):
        if first is not None:           # a pass of the loop, not the prompt
            first = first + x.shape[1]
        return real["_normed_rotary"](x, heads, scale, eps, theta, turn,
                                      first)

    def by_position(logits, still_masked, mask_id, reveal):
        _, ids, top, lse = real["_choose"](logits, still_masked, mask_id,
                                           reveal)
        first = jnp.cumsum(still_masked, axis=-1) <= reveal
        return jnp.logical_and(first, still_masked), ids, top, lse

    def whole_vocabulary(logits, still_masked, mask_id, reveal):
        return real["_choose"](logits, still_masked, -1, reveal)

    def given(config, variables, **kw):
        change, vary = GIVEN[fault]
        return real["model_function"](
            {**config, **change}, vary(variables) if vary else variables,
            **kw)

    module, name, planted = {
        "cache_keeps_last_denoise": (block_diffusion, "_turn",
                                     last_denoise_writes),
        "prompt_causal": (block_diffusion, "causal_attention", causal_prompt),
        "own_block_causal": (block_diffusion, "_attend_cache",
                             blind_to_later),
        "rotary_one_block_late": (expert_trunk, "_normed_rotary",
                                  one_block_late),
        "reveal_by_position": (block_diffusion, "_choose", by_position),
        "mask_id_not_excluded": (block_diffusion, "_choose",
                                 whole_vocabulary),
    }.get(fault, (block_diffusion, "model_function", given))
    setattr(module, name, planted)
    try:
        yield
    finally:
        setattr(module, name, real[name])


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
