"""Operations and bytes of a vision-language trunk (``kind: vl_trunk``),
from shapes, by ``sequence_flops``'s rule: 2 x the multiply-accumulates
of every matrix applied at every position or patch, plus the parts whose
work goes by pairs:

* the vision tower, a FRAME at a time: the patch embedding and every
  block's matrices at each of its patches, attention both ways over the
  frame's patches (``4 P^2 D`` a block: ``q k^T`` and the weighted values),
  and the merger at each merged token (``frame_flops``);
* the language layers, a row at a time: the projections, the indexer's
  three matrices, the router and the ``num_experts_per_tok`` experts a
  token chooses at every position (``token_layer_flops``); the indexer at
  every causal pair (``latent_trunk_flops.indexer_pair_flops``'s rule at
  ``sa_config``'s heads: ``indexer_num_heads`` products of
  ``indexer_head_dim`` and the weighted sum); the grouped-query attention
  at every SELECTED pair (``sum min(t + 1, topk)``), every query head's
  score and value of ``head_dim`` (``attention_pair_flops``).

The norms, the rotary position, the splice, the selection, the routing's
sort and the gathers are left out.  A kernel's roofline count does not
depend on what implements it: the bytes are a LOWER bound, what the work
must read and write once.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark import sequence_flops as sf
from benchmark.latent_trunk_flops import causal_pairs, selected_pairs


def patches_per_frame(config: Dict[str, Any]) -> int:
    v = config["vision_config"]
    return (v["image_size"] // v["patch_size"]) ** 2


def tokens_per_frame(config: Dict[str, Any]) -> int:
    return patches_per_frame(config) // config["vision_config"][
        "spatial_merge_size"] ** 2


def block_matrices(config: Dict[str, Any]):
    """``[in, out]`` of a vision block's matrices, applied at every patch."""
    v = config["vision_config"]
    d, f = v["hidden_size"], v["intermediate_size"]
    return [(d, d)] * 4 + [(d, f), (f, d)]


def frame_attention_flops(config: Dict[str, Any], frames: float,
                          patches: Optional[float] = None) -> float:
    """Attention both ways over each frame's ``patches`` (the
    configuration's where none are given), every block: ``q k^T`` and
    the weighted values, ``4 P^2 D``."""
    v = config["vision_config"]
    p = patches_per_frame(config) if patches is None else patches
    return frames * v["num_hidden_layers"] * 4 * p * p * v["hidden_size"]


def frame_attention_bytes(config: Dict[str, Any], frames: float,
                          patches: Optional[float] = None,
                          itemsize: int = 2) -> float:
    """``q``, ``k``, ``v`` and the output once, every block."""
    v = config["vision_config"]
    p = patches_per_frame(config) if patches is None else patches
    return (frames * v["num_hidden_layers"] * 4 * p * v["hidden_size"]
            * itemsize)


def frame_flops(config: Dict[str, Any]) -> int:
    """Operations of the tower over one frame, the merger included."""
    v = config["vision_config"]
    p, d = patches_per_frame(config), v["hidden_size"]
    joined = d * v["spatial_merge_size"] ** 2
    embed = sf.matmul_flops([(v["patch_size"] ** 2 * 3, d)], p)
    blocks = v["num_hidden_layers"] * sf.matmul_flops(block_matrices(config), p)
    merger = sf.matmul_flops([(joined, joined),
                              (joined, config["hidden_size"])],
                             tokens_per_frame(config))
    return embed + blocks + int(frame_attention_flops(config, 1)) + merger


def layer_matrices(config: Dict[str, Any]):
    """``[in, out]`` of every matrix a language layer applies at EVERY
    position (a routed expert's are not among them)."""
    c, sc = config, config["sa_config"]
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return [(d, q), (d, kv), (d, kv), (q, d),
            (d, sc["indexer_num_heads"] * sc["indexer_head_dim"]),
            (d, sc["indexer_head_dim"]), (d, sc["indexer_num_heads"]),
            (d, c["num_experts"])]


def pair_flops(config: Dict[str, Any]) -> int:
    """One token through one routed expert: gate, up, down."""
    return 2 * 3 * config["hidden_size"] * config["moe_intermediate_size"]


def token_layer_flops(config: Dict[str, Any]) -> int:
    """A position through a language layer's matrices and its experts."""
    return (sf.matmul_flops(layer_matrices(config), 1)
            + config["num_experts_per_tok"] * pair_flops(config))


def indexer_pair_flops(config: Dict[str, Any]) -> int:
    sc = config["sa_config"]
    return 2 * sc["indexer_num_heads"] * (sc["indexer_head_dim"] + 1)


def indexer_flops(config: Dict[str, Any], pairs: float) -> float:
    return pairs * indexer_pair_flops(config)


def indexer_bytes(config: Dict[str, Any], positions: float,
                  itemsize: int = 2) -> float:
    """The indexer's queries and its one key head (``itemsize``) and its
    float32 head weights, once, for ``positions`` positions (each a
    query and a key)."""
    sc = config["sa_config"]
    heads, dim = sc["indexer_num_heads"], sc["indexer_head_dim"]
    return positions * ((heads + 1) * dim * itemsize + heads * 4)


def attention_pair_flops(config: Dict[str, Any]) -> int:
    """A selected pair: every query head's score and value."""
    return 4 * config["num_attention_heads"] * config["head_dim"]


def attention_flops(config: Dict[str, Any], pairs: float) -> float:
    return pairs * attention_pair_flops(config)


def attention_bytes(config: Dict[str, Any], positions: float,
                    itemsize: int = 2) -> float:
    """Once each, for ``positions`` positions: the queries and outputs of
    every query head, the keys and values of every key/value head."""
    c = config
    return positions * itemsize * c["head_dim"] * 2 * (
        c["num_attention_heads"] + c["num_key_value_heads"])


def language_flops(config: Dict[str, Any]) -> int:
    t, layers = config["sequence_length"], config["num_hidden_layers"]
    pairs = (indexer_flops(config, causal_pairs(t)) + attention_flops(
        config, selected_pairs(t, config["sa_config"]["topk"])))
    return layers * (t * token_layer_flops(config) + int(pairs))


def flops_per_row(config: Dict[str, Any]) -> int:
    """Operations a row: its ``frames`` through the tower, its
    ``sequence_length`` positions through the language layers."""
    return config["frames"] * frame_flops(config) + language_flops(config)
