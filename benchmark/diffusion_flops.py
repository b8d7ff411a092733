"""Operations and bytes of generation by diffusion over blocks, from
shapes: what ``expert_trunk_flops.py`` is for the one-pass expert trunk,
by the same rule (2 x the multiply-accumulates of every matrix product at
every position it is applied to, plus attention at its query-key pairs;
norms, the rotary position, softmaxes, the sort and the gathers of the
routing and the sampler's comparisons left out).

A row is a prompt of ``prompt_length`` ids and yields
``generated_length`` ids in blocks of ``block_length``:

* the prefill runs every prompt position through every layer once, its
  attention under the mask by blocks (a position sees every earlier
  block and its own: ``block_attention_pairs``);
* every block then takes ``denoise_steps`` passes with the output head
  and one commit pass without it, each over the block's
  ``block_length`` positions, whose queries see the cache's filled part
  and their own block.

Every routed expert is held here, so a token's ``num_experts_per_tok``
pairs are all computed: nothing is an expectation.

The BYTES are those a pass of the loop cannot avoid
(``block_pass_bytes``): the weights of the experts that were touched
(counted by the program), attention's and the router's weights in every
layer, the cache's filled part, and on a denoise pass the head's.  They
are a LOWER bound on what the pass moves: the gathered rows, the
scattered sums and the logits are not among them.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional

from benchmark import sequence_flops as sf
from benchmark.expert_trunk_flops import attention_flops, pair_flops


def layer_matrices(config: Dict[str, Any]):
    """``[in, out]`` of every matrix a layer applies at EVERY position:
    ``q``, ``k``, ``v``, ``o`` and the router."""
    d, hd = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    return [(d, q), (d, kv), (d, kv), (q, d), (d, config["num_experts"])]


def position_flops(config: Dict[str, Any]) -> int:
    """One position through one layer, attention's pairs left out: the
    matrices and its ``num_experts_per_tok`` experts."""
    return (sf.matmul_flops(layer_matrices(config), 1)
            + config["num_experts_per_tok"] * pair_flops(config))


def expert_parameters(config: Dict[str, Any]) -> int:
    """One routed expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def head_flops(config: Dict[str, Any]) -> int:
    """The output head at one position."""
    return 2 * config["hidden_size"] * config["vocab_size"]


def block_attention_pairs(positions: int, block: int) -> int:
    """Pairs of a query and a key with ``key // block <= query // block``
    over ``positions`` positions (a multiple of ``block``): each of the
    ``n``-th block's queries sees ``n * block`` keys."""
    blocks = positions // block
    return block * block * blocks * (blocks + 1) // 2


def pass_attention_pairs(config: Dict[str, Any], block_index: int) -> int:
    """Pairs of one pass over block ``block_index`` of the generated ids:
    its queries against the filled cache and their own block."""
    b = config["block_length"]
    return b * (config["prompt_length"] + block_index * b + b)


def passes_per_row(config: Dict[str, Any]):
    """``(denoise passes, commit passes)`` a row."""
    blocks = config["generated_length"] // config["block_length"]
    return blocks * config["denoise_steps"], blocks


def prefill_flops(config: Dict[str, Any]) -> int:
    c = config
    p = c["prompt_length"]
    return c["num_hidden_layers"] * (
        p * position_flops(c) + attention_flops(
            c["num_attention_heads"], c["head_dim"],
            block_attention_pairs(p, c["block_length"])))


def loop_flops(config: Dict[str, Any]) -> int:
    """All passes of all blocks of one row."""
    c = config
    denoise, commit = passes_per_row(c)
    blocks, b = commit, c["block_length"]
    pairs = (c["denoise_steps"] + 1) * sum(
        pass_attention_pairs(c, i) for i in range(blocks))
    return (c["num_hidden_layers"] * (
        (denoise + commit) * b * position_flops(c)
        + attention_flops(c["num_attention_heads"], c["head_dim"], pairs))
        + denoise * b * head_flops(c))


def flops_per_row(config: Dict[str, Any]) -> int:
    """Operations a row: the prefill and the whole loop."""
    return prefill_flops(config) + loop_flops(config)


def block_pass_flops(config: Dict[str, Any], rows: float,
                     head_share: float) -> float:
    """Operations of ONE pass of the loop over ``rows`` rows, at the mean
    block's filled cache; ``head_share`` of the passes run the head."""
    c = config
    blocks, b = c["generated_length"] // c["block_length"], c["block_length"]
    pairs = sum(pass_attention_pairs(c, i) for i in range(blocks)) / blocks
    return rows * (c["num_hidden_layers"] * (
        b * position_flops(c) + attention_flops(
            c["num_attention_heads"], c["head_dim"], pairs))
        + head_share * b * head_flops(c))


def block_pass_bytes(config: Dict[str, Any], rows: float,
                     touched_experts: float, head_share: float,
                     itemsize: int = 2) -> float:
    """Bytes ONE pass of the loop cannot avoid, at the mean block:
    ``touched_experts`` experts' three matrices (a pass's sum over the
    layers), every layer's attention and router matrices, the filled
    part of the cache of ``rows`` rows (keys and values), and the head's
    matrix on the ``head_share`` of passes that run it."""
    c = config
    blocks, b = c["generated_length"] // c["block_length"], c["block_length"]
    filled = c["prompt_length"] + b * (blocks - 1) / 2
    every_layer = sum(i * o for i, o in layer_matrices(c))
    cache = (2 * c["num_key_value_heads"] * c["head_dim"] * filled * rows
             * c["num_hidden_layers"])
    head = c["hidden_size"] * c["vocab_size"]
    return itemsize * (touched_experts * expert_parameters(c)
                       + c["num_hidden_layers"] * every_layer + cache
                       + head_share * head)


# -- what the readers of the loop's metrics share -----------------------------

#: the generation loop's line among the trace's operations: the program's
#: ``lax.while_loop`` leads its carry with the generated ids, so its HLO
#: line reads ``%while.<n> s32[rows, generated_length] while``
_LOOP_LINE = re.compile(r"^%while[\w.\-]* s32\[\d+,(\d+)\] while$")


def loop_seconds(obs) -> Optional[float]:
    """Device seconds of the generation loop in the traced window, or
    ``None`` where its line is not among the operations the reduction
    kept (or the program has no such loop)."""
    if obs.trace is None or "generated_length" not in obs.config:
        return None
    seconds = sum(
        s for op, s in obs.trace.device_ops
        for line in [_LOOP_LINE.match(op)]
        if line and int(line.group(1)) == obs.config["generated_length"])
    return seconds if seconds > 0 else None


def passes_per_dispatch(obs) -> Optional[Dict[str, float]]:
    """``{"denoise", "commit", "touched_experts"}`` of one dispatch, from
    the program's own counters (every row of a dispatch counts the same
    passes; the touched experts are counted a dispatch), or ``None``
    where the program counts none."""
    rows = obs.counters.get("engine.rows", 0.0)
    denoise = obs.counters.get("diffusion.denoise_passes", 0.0)
    if rows <= 0 or denoise <= 0 or obs.trace is None \
            or obs.trace.module_executions <= 0:
        return None
    return {"denoise": denoise / rows,
            "commit": obs.counters.get("diffusion.commit_passes", 0.0) / rows,
            "touched_experts": obs.counters.get(
                "diffusion.touched_experts", 0.0)
            / obs.trace.module_executions}


def dispatched_rows(obs) -> float:
    """Rows of one dispatch, padding included."""
    return ((obs.counters.get("engine.rows", 0.0)
             + obs.counters.get("engine.pad_rows", 0.0))
            / obs.trace.module_executions)
