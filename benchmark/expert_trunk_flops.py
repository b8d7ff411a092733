"""Operations and bytes of a sparse-expert trunk, from shapes: what
``sequence_flops.py`` is for the hybrid trunk, by the same rule (2 x the
multiply-accumulates of every matrix product at every position, plus
attention at its query-key pairs; norms, gates, the rotary position, the
sort and the gathers of the routing left out).

Two things are new.  Attention under a window counts the pairs of the
BAND (``banded_attention_pairs``).  And the routed experts' work depends
on the data: ``flops_per_row`` counts them at their EXPECTED pairs
(``experts a token x held / routed`` a token a layer: uniform routing),
while a kernel's roofline is counted from the pairs that were really
computed (``grouped_matmul_flops`` and ``_bytes`` take ``pairs``; the
program counts them, ``moe.pairs``).

All keys are the configuration file's: the head counts and
``num_experts`` are what is HELD here, ``expert_share[1]`` the number of
chips that share a layer, so the router is ``num_experts *
expert_share[1]`` wide.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import sequence_flops as sf


def banded_attention_pairs(positions: int, window=None) -> int:
    """Pairs of a query and a key with ``key <= query`` and, under a
    window, ``query - key < window``: the first ``window`` queries see
    all keys before them, every later one ``window``."""
    w = positions if window is None else min(window, positions)
    return w * (w + 1) // 2 + (positions - w) * w


def attention_flops(heads: int, head_dim: int, pairs: int) -> int:
    """Scores and weighted values, a multiply-accumulate each over
    ``head_dim``, for every query head at every pair."""
    return 4 * head_dim * heads * pairs


def routed_experts(config: Dict[str, Any]) -> int:
    """The experts the router chooses among (all chips' together)."""
    return config["num_experts"] * config["expert_share"][1]


def expected_pairs_per_token(config: Dict[str, Any]) -> float:
    """Token-expert pairs a token a layer that fall to the experts held
    here, if every expert is as likely as any other."""
    return (config["num_experts_per_tok"] * config["num_experts"]
            / routed_experts(config))


def layer_matrices(config: Dict[str, Any], dense: bool):
    """``[in, out]`` of every matrix a layer applies at EVERY position
    (a routed expert's are not among them)."""
    d, hd = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    shapes = [(d, q), (d, q), (d, kv), (d, kv), (q, d)]    # q, gate, k, v, o
    if dense:
        return shapes + [(d, config["intermediate_size"])] * 2 + [
            (config["intermediate_size"], d)]
    shared = config["moe_intermediate_size"] * config["num_shared_experts"]
    return shapes + [(d, routed_experts(config)), (d, shared), (d, shared),
                     (shared, d)]


def pair_flops(config: Dict[str, Any]) -> int:
    """One token through one routed expert: gate, up, down."""
    return 2 * 3 * config["hidden_size"] * config["moe_intermediate_size"]


def grouped_matmul_flops(config: Dict[str, Any], pairs: float) -> float:
    return pairs * pair_flops(config)


def grouped_matmul_bytes(config: Dict[str, Any], pairs: float,
                         layer_calls: float, itemsize: int = 2) -> float:
    """The held experts' matrices once a layer a dispatch
    (``layer_calls`` of them), a pair's input and output rows once."""
    weights = (3 * config["num_experts"] * config["hidden_size"]
               * config["moe_intermediate_size"])
    return itemsize * (layer_calls * weights
                       + pairs * 2 * config["hidden_size"])


def layer_windows(config: Dict[str, Any]):
    """The window of each layer's attention, ``None`` for a full layer."""
    return [config["sliding_window"] if kind == "sliding_attention" else None
            for kind in config["layer_types"]]


def attention_flops_per_row(config: Dict[str, Any]) -> int:
    t = config["sequence_length"]
    return sum(attention_flops(config["num_attention_heads"],
                               config["head_dim"],
                               banded_attention_pairs(t, window))
               for window in layer_windows(config))


def attention_bytes_per_row(config: Dict[str, Any], itemsize: int = 2) -> int:
    return len(config["layer_types"]) * sf.causal_attention_bytes(
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"], config["sequence_length"], itemsize)


def flops_per_row(config: Dict[str, Any]) -> int:
    """Operations a row of ``sequence_length`` positions, the routed
    experts at their expected pairs."""
    t, layers = config["sequence_length"], config["num_hidden_layers"]
    dense = config["num_dense_layers"]
    products = (dense * sf.matmul_flops(layer_matrices(config, True), t)
                + (layers - dense) * sf.matmul_flops(
                    layer_matrices(config, False), t))
    routed = (layers - dense) * t * expected_pairs_per_token(config) \
        * pair_flops(config)
    if routed != int(routed):
        raise ValueError("the expected pairs of a row are no whole number")
    return products + int(routed) + attention_flops_per_row(config)
