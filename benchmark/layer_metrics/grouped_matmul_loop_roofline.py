"""The expert kernel's share of its roofline as the generation loop
calls it: a few rows an expert, every pass, every layer.  The least time
the chip could take for the loop's token-expert pairs
(``expert_trunk_flops.pair_flops`` a pair at the bf16 peak; the touched
experts' matrices — the program's own count, a pass a layer — and a
pair's input and output rows at the memory's rate, whichever is larger)
over the device seconds of the loop's ``%grouped_matmul`` instruction in
the traced window.  The program holds two such instructions, the
prefill's and the loop's; the loop's is the one whose result has the
fewer rows (a chunk of a pass's slots against a chunk of thousands of
prompt positions').  ``None`` where no ``%grouped_matmul`` line is among
the ten operations kept, or the program counts no pass."""

import re

from benchmark import diffusion_flops as df
from benchmark import expert_trunk_flops as ef
from benchmark import sequence_flops as sf

_LINE = re.compile(r"^%grouped_matmul(?:\.\d+)* \w+\[(\d+),\d+\] custom-call$")


def read(obs):
    passes = df.passes_per_dispatch(obs)
    if passes is None:
        return None
    lines = [(int(m.group(1)), s) for op, s in obs.trace.device_ops
             for m in [_LINE.match(op)] if m]
    if not lines:
        return None
    _, seconds = min(lines)
    c = obs.config
    every = (passes["denoise"] + passes["commit"]) \
        * obs.trace.module_executions
    pairs = (every * df.dispatched_rows(obs) * c["block_length"]
             * c["num_hidden_layers"] * c["num_experts_per_tok"])
    nbytes = 2 * (passes["touched_experts"] * obs.trace.module_executions
                  * df.expert_parameters(c) + pairs * 2 * c["hidden_size"])
    return 100.0 * sf.roofline_seconds(
        pairs * ef.pair_flops(c), nbytes, obs.peak) / obs.chips / seconds
