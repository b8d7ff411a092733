"""A sparse-expert sequence trunk as a featurizer: rows of token ids in,
one vector a row out.

The layer is Trinity-Large's (``model_type: afmoe``; ``config`` holds the
keys of its published ``config.json``): gated attention with RMSNorm of
the queries and keys by head, in layers that see a window of keys (and
carry the rotary position) or the whole row (and carry none), then a
gated MLP that is dense in the leading layers and, in the others, a
shared expert beside routed experts — and a norm before AND after each
branch (every ``N`` an RMSNorm with a learned scale)::

    x   = E[ids] * sqrt(hidden_size)                          (mup_enabled)
    h   = N_in(x)
    q,k = N_q(W_q h), N_k(W_k h)  by head;   v = W_v h;   g = W_g h
    q,k = rotary(q), rotary(k)    in sliding_attention layers only
    a   = softmax(q k^T / sqrt(head_dim)) v  over key <= query and, in a
          sliding layer, query - key < sliding_window
    x   = x + N_post_attn(W_o (a * sigmoid(g)))
    h2  = N_pre_mlp(x)
    m   = W_down(SiLU(W_gate h2) * W_up h2)                     dense layer
    m   = shared(h2) + sum over e in top-k, e held here, of w_e expert_e(h2)
          s = sigmoid(W_r h2), float32;  top-k of s + expert_bias (the bias
          chooses only);  w = s[top-k] / sum (route_norm) * route_scale
    x   = x + N_post_mlp(m)

After the last layer a final RMSNorm; the row's feature is the mean of
it over the row's positions, float32.  The output head is left off.

**The layer is told which experts it holds.**  ``num_experts`` routed
experts are held here, experts ``expert_share[0] * num_experts`` and on
of the ``num_experts * expert_share[1]`` that the router scores (with
``expert_share = [0, 1]``, all of them).  The router scores them all;
the token-expert pairs that fall to the held experts are sorted by
expert and computed by ``ops/grouped_matmul``, every one of them: no
capacity is set and no token is dropped, whatever the imbalance (the
pairs are worked off a chunk at a time, as many chunks as it takes).  What the other chips' experts
would add is left out, and so is the exchange that would bring it: this
is one chip's part of the layer.  Likewise ``num_attention_heads`` and
``num_key_value_heads`` are the heads whose weights are here.  Beside
the features the program returns ``expert_load`` ``[rows, expert layers,
num_experts]`` int32, how many of a row's tokens each held expert took:
a COUNTER of the program, not a column (``ModelFunction.counter_names``).

All layers run under ONE ``lax.scan``: a layer's attention weights and
norms are scanned, its kind is scanned data (the window's width is an
operand of the attention kernel, the rotary's angles are multiplied by 0
in a layer that carries no position), and the MLP's kind picks by
``lax.cond`` between the dense weights and the experts', which lie
stacked by kind and are read in place by the layer's number.  So every
kernel is one instruction of the program whatever the depth.  Weights
and matrix-product operands are in the compute dtype (bfloat16 unless
told otherwise) and accumulate in float32; the residual stream, the
norms, the rotary position, softmax, the gates, the router's product
(at ``highest`` precision) with its sigmoid, the routing weights and the
experts' weighted sum are float32.  Names of weights are the published
checkpoint's and matrices are ``[in, out]``; ``gate_proj`` and
``up_proj`` lie side by side (``gate_up_proj``), a layer's routed
experts stacked on a leading axis.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from sparkdl_tpu.ops import grouped_matmul as gm
from sparkdl_tpu.ops.attention import causal_attention

#: the program's outputs that are counters, not columns
COUNTERS = ("expert_load",)

def routed_experts(config: Dict[str, Any]) -> int:
    """The experts the router chooses among (all chips' together)."""
    return config["num_experts"] * config["expert_share"][1]


def layer_shapes(config: Dict[str, Any]) -> Dict[str, Dict[str, tuple]]:
    """Shape of every weight of one layer, by kind (``layers``: what
    every layer has; ``dense``; ``experts``) and published name."""
    c = config
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    ff, f = c["intermediate_size"], c["moe_intermediate_size"]
    fs, held = f * c["num_shared_experts"], c["num_experts"]
    return {
        "layers": {
            "input_layernorm": (d,), "post_attention_layernorm": (d,),
            "pre_mlp_layernorm": (d,), "post_mlp_layernorm": (d,),
            "self_attn.q_proj": (d, q), "self_attn.k_proj": (d, kv),
            "self_attn.v_proj": (d, kv), "self_attn.gate_proj": (d, q),
            "self_attn.o_proj": (q, d),
            "self_attn.q_norm": (hd,), "self_attn.k_norm": (hd,)},
        "dense": {"mlp.gate_up_proj": (d, 2 * ff), "mlp.down_proj": (ff, d)},
        "experts": {
            "mlp.router.gate": (d, routed_experts(c)),
            "mlp.expert_bias": (routed_experts(c),),
            "mlp.shared_experts.gate_up_proj": (d, 2 * fs),
            "mlp.shared_experts.down_proj": (fs, d),
            "mlp.experts.gate_up_proj": (held, d, 2 * f),
            "mlp.experts.down_proj": (held, f, d)}}


def init(config: Dict[str, Any], key, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Random variables in the trunk's tree: ``embedding`` ``[V, D]``,
    ``layers`` / ``dense`` / ``experts`` (the weights of
    ``layer_shapes``, stacked by kind on a leading axis; the routed
    experts of all expert layers on ONE axis, layer after layer) and
    ``final_layernorm``.  Matrices N(0, 1/fan-in), norm scales 1, the
    bias 0."""
    c = config
    depth, dense = c["num_hidden_layers"], c["num_dense_layers"]
    count = {"layers": depth, "dense": dense, "experts": depth - dense}
    keys = iter(jax.random.split(key, 32))

    def leaf(kind, name, shape):
        full = (count[kind],) + shape
        if name.startswith("mlp.experts."):
            full = (count[kind] * shape[0],) + shape[1:]
        if name.endswith("norm"):
            return jnp.ones(full, dtype)
        if name == "mlp.expert_bias":
            return jnp.zeros(full, jnp.float32)
        return (jax.random.normal(next(keys), full, jnp.float32)
                / shape[-2] ** 0.5).astype(dtype)

    variables = {kind: {n: leaf(kind, n, s) for n, s in shapes.items()}
                 for kind, shapes in layer_shapes(c).items()}
    variables["embedding"] = (jax.random.normal(
        next(keys), (c["vocab_size"], c["hidden_size"]), jnp.float32)
        / c["hidden_size"] ** 0.5).astype(dtype)
    variables["final_layernorm"] = jnp.ones((c["hidden_size"],), dtype)
    return variables


def stack_layers(leaf: Callable[[int, str], Any], config: Dict[str, Any]
                 ) -> Dict[str, Dict[str, Any]]:
    """The trunk's ``layers``, ``dense`` and ``experts`` from ``leaf(layer,
    published name)``, built one weight at a time (never two copies of
    more than the weight in hand): stacked by kind, ``gate_proj`` and
    ``up_proj`` side by side."""
    depth, dense = config["num_hidden_layers"], config["num_dense_layers"]

    @functools.partial(jax.jit, donate_argnums=(),
                       static_argnames=("flat",))   # pieces cannot alias
    def joined(pieces, flat=False):
        # one program: eager ``jnp.stack`` would copy every piece once more
        rows = [jnp.concatenate(p, axis=-1) for p in pieces]
        return jnp.concatenate(rows) if flat else jnp.stack(rows)

    def stacked(layers, *names, flat=False):
        # waited for, so that the pieces of one weight are gone before
        # the next is drawn (dispatch runs ahead of the device otherwise)
        return joined([[leaf(i, n) for n in names] for i in layers],
                      flat=flat).block_until_ready()

    def gate_up(layers, prefix, flat=False):
        return stacked(layers, prefix + "gate_proj", prefix + "up_proj",
                       flat=flat)

    every, first, rest = range(depth), range(dense), range(dense, depth)
    out = {"layers": {name: stacked(every, name)
                      for name in layer_shapes(config)["layers"]},
           "dense": {}, "experts": {}}
    if dense:
        out["dense"] = {"mlp.gate_up_proj": gate_up(first, "mlp."),
                        "mlp.down_proj": stacked(first, "mlp.down_proj")}
    if dense < depth:
        out["experts"] = {
            "mlp.experts.gate_up_proj": gate_up(rest, "mlp.experts.", True),
            "mlp.experts.down_proj": stacked(
                rest, "mlp.experts.down_proj", flat=True),
            "mlp.shared_experts.gate_up_proj": gate_up(
                rest, "mlp.shared_experts."),
            "mlp.shared_experts.down_proj": stacked(
                rest, "mlp.shared_experts.down_proj"),
            "mlp.router.gate": stacked(rest, "mlp.router.gate"),
            "mlp.expert_bias": stacked(rest, "mlp.expert_bias")}
    return out


def _rms_norm(x, scale, eps: float):
    """RMSNorm over the last axis in float32 with a learned scale."""
    x = x.astype(jnp.float32)
    return (x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def _normed_rotary(x, heads: int, scale, eps: float, theta: float, turn,
                   first=None):
    """RMSNorm by head, then the rotary position over the whole head,
    halves paired, angles in float32 and multiplied by ``turn`` (1 in a
    layer that carries the position, 0 in one that does not: the turn by
    no angle is exact).  ``x`` ``[R, T, heads*hd]`` float32, its
    positions ``first`` and on (0 and on where none is given)."""
    r, t, width = x.shape
    hd = width // heads
    xh = _rms_norm(x.reshape(r, t, heads, hd), scale, eps)
    inv = float(theta) ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    at = jnp.arange(t, dtype=jnp.float32)
    if first is not None:
        at = at + jnp.asarray(first, jnp.float32)
    angle = turn * at[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    lo, hi = xh[..., :hd // 2], xh[..., hd // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           axis=-1).reshape(r, t, width)


def _attention_branch(config: Dict[str, Any], x, w, sliding, dtype,
                      precision):
    """``W_o (a * sigmoid(g))`` of one layer over ``x`` ``[R, T, D]``,
    before the norm that follows it: with the held heads, their part of
    ``W_o``'s sum.  ``sliding`` is a boolean scalar of the program."""
    c, f32 = config, jnp.float32
    eps, t = c["rms_norm_eps"], x.shape[1]
    heads, kv_heads = c["num_attention_heads"], c["num_key_value_heads"]

    def mm(a, name, out=f32):
        return jnp.dot(a.astype(dtype), w[name], precision=precision,
                       preferred_element_type=f32).astype(out)

    h = _rms_norm(x, w["input_layernorm"], eps)
    turn = jnp.asarray(sliding, f32)
    q = _normed_rotary(mm(h, "self_attn.q_proj"), heads,
                       w["self_attn.q_norm"], eps, c["rope_theta"],
                       turn) * c["head_dim"] ** -0.5
    k = _normed_rotary(mm(h, "self_attn.k_proj"), kv_heads,
                       w["self_attn.k_norm"], eps, c["rope_theta"], turn)
    # a window as long as the row is no window
    window = jnp.where(sliding, min(c["sliding_window"], t), t)
    a = causal_attention(q.astype(dtype), k.astype(dtype),
                         mm(h, "self_attn.v_proj", dtype), heads=heads,
                         kv_heads=kv_heads, window=window.astype(jnp.int32),
                         precision=precision)
    gated = a.astype(f32) * jax.nn.sigmoid(mm(h, "self_attn.gate_proj"))
    return mm(gated, "self_attn.o_proj")


def _gated_mlp(h, gate_up, down, dtype, precision):
    """``W_down(SiLU(W_gate h) * W_up h)``: the widest array leaves its
    product in the compute dtype; the gate's arithmetic is float32."""
    f32 = jnp.float32
    dot = functools.partial(jnp.dot, precision=precision,
                            preferred_element_type=f32)
    gate, up = jnp.split(dot(h.astype(dtype), gate_up).astype(dtype), 2,
                         axis=-1)
    act = (jax.nn.silu(gate.astype(f32)) * up.astype(f32)).astype(dtype)
    return dot(act, down)


#: an expert's score from its logit among all the router's
SCORES = {"sigmoid": jax.nn.sigmoid,
          "softmax": functools.partial(jax.nn.softmax, axis=-1)}


def _limit_groups(choice, groups: int, kept: int):
    """``choice`` ``[N, E]`` with every expert outside a token's ``kept``
    best groups at ``-inf``: the experts lie in ``groups`` groups of
    ``E // groups`` in order, and a group scores the sum of its two
    largest."""
    n, e = choice.shape
    by_group = choice.reshape(n, groups, e // groups)
    score = jnp.sum(lax.top_k(by_group, 2)[0], axis=-1)          # [N, groups]
    _, best = lax.top_k(score, kept)
    keep = jnp.any(best[:, :, None] == jnp.arange(groups), axis=1)
    return jnp.where(keep[:, :, None], by_group, -jnp.inf).reshape(n, e)


def _route(config: Dict[str, Any], tokens, router, bias=None,
           scores: str = "sigmoid"):
    """``(chosen experts [N, k] by their number among all, weights [N, k]
    float32)`` of ``tokens`` ``[N, D]`` float32.  ``scores`` names how a
    logit becomes a score (``SCORES``); a ``bias`` chooses only.  Where
    ``n_group`` is over 1 the choice is limited to the ``topk_group``
    best groups of experts first (``_limit_groups``)."""
    scores = SCORES[scores](jnp.dot(
        tokens, router.astype(jnp.float32), precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32))
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    if config.get("n_group", 1) > 1:
        choice = _limit_groups(choice, config["n_group"], config["topk_group"])
    _, chosen = lax.top_k(choice, config["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["route_norm"]:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return chosen, config["route_scale"] * weight


def _held_experts(config: Dict[str, Any], h2, chosen, weight, gate_up, down,
                  first_group, dtype, precision, tile: Optional[int] = None,
                  chunk_tiles: Optional[int] = None):
    """``(sum over a token's chosen experts that are held here of w_e
    expert_e(h2) [R, T, D] float32, tokens by row and held expert [R,
    held] int32, the slots worked through, an int32 scalar)``.

    The pairs of a token and a held expert are sorted by expert and laid
    out in slots, every expert on tiles of its own (``ops/grouped_matmul``).
    The tile follows the rows a held expert expects under even routing,
    read off the static shapes (``grouped_matmul.tile_for``): the gather,
    the weighting and the scatter-add below cost by the slot, filled or
    not.  The slots are worked off ``chunk_tiles`` tiles at a time under
    a ``lax.while_loop``: a chunk's rows are gathered, run through the
    experts, weighted and added to their tokens.  Where an expert
    expects a tile or more, the chunk is a quarter over what uniform
    routing needs plus half a tile an expert, so the loop runs once
    then; where it expects less, every touched expert costs a whole tile
    however few its rows, so the chunk is that quarter over plus a WHOLE
    tile an expert — with every expert held here the worst fall's few
    thousand slots: one turn whatever the routing.  Under any imbalance
    the loop runs as often as there are pairs to compute.  No pair
    is dropped and memory is the chunk's, not the worst case's.  The
    slots worked through are the loop's turns times the chunk."""
    f32, i32 = jnp.float32, jnp.int32
    r, t, d = h2.shape
    held, k = config["num_experts"], chosen.shape[-1]
    pairs = r * t * k
    expected = pairs * held / routed_experts(config)
    if tile is None:
        tile = gm.tile_for(expected / held)
    if chunk_tiles is None:
        spare = held // 2 if expected >= held * tile else held
        chunk_tiles = -(-int(1.25 * expected) // tile) + spare
    slots = gm.slots_for(pairs, held, tile)
    chunk_tiles = min(chunk_tiles, slots // tile)
    chunk = chunk_tiles * tile
    with jax.named_scope("expert_dispatch"):
        local = chosen.astype(i32) - config["expert_share"][0] * held
        mine = jnp.logical_and(local >= 0, local < held)       # [N, k]
        pair_group = jnp.where(mine, local, held).reshape(-1)  # held: nobody's
        load = jnp.sum(pair_group.reshape(r, t * k, 1)
                       == jnp.arange(held, dtype=i32), axis=1, dtype=i32)
        sizes = jnp.sum(load, axis=0)
        start = jnp.cumsum(sizes) - sizes
        by_group = jnp.argsort(pair_group, stable=True).astype(i32)
        # the slots of the worst fall, cut into whole chunks: small arrays
        layout = gm.aligned_layout(sizes, -(-slots // chunk) * chunk, tile)
        pair_of_slot = by_group[jnp.minimum(
            start[layout.slot_group] + layout.slot_rank, pairs - 1)]
        tokens = h2.reshape(r * t, d).astype(dtype)
        share = weight.reshape(-1)

    def one_chunk(state):
        at, total = state
        cut = functools.partial(lax.dynamic_slice_in_dim, start_index=at * chunk,
                                slice_size=chunk)
        pair = cut(pair_of_slot)
        with jax.named_scope("expert_dispatch"):
            rows = jnp.take(tokens, pair // k, axis=0)
        with jax.named_scope("experts"):
            out = gm.grouped_matmul(
                rows, gate_up, down,
                lax.dynamic_slice_in_dim(layout.tile_group, at * chunk_tiles,
                                         chunk_tiles),
                jnp.clip(layout.tiles_in_use - at * chunk_tiles, 0,
                         chunk_tiles),
                first_group, tile=tile, out_dtype=f32, precision=precision)
        with jax.named_scope("expert_combine"):
            # a slot that holds no pair computed on some token's row: it
            # is finite, weighs nothing and goes nowhere
            filled = cut(layout.slot_filled)
            out = jnp.where(filled[:, None], out * share[pair][:, None], 0.0)
            total = total.at[jnp.where(filled, pair // k, r * t)].add(
                out, mode="drop")
        return at + 1, total

    turns, total = lax.while_loop(
        lambda state: state[0] * chunk_tiles < layout.tiles_in_use,
        one_chunk, (jnp.int32(0), jnp.zeros((r * t, d), f32)))
    return total.reshape(r, t, d), load, turns * chunk


def apply(variables: Dict[str, Any], ids, config: Dict[str, Any], *,
          precision=None):
    """``ids`` ``[R, T]`` integers -> ``{"features": [R, D] float32,
    "expert_load": [R, expert layers, held] int32}``.  The compute dtype
    is the weights' own.  An id outside the vocabulary raises nothing
    under ``jit`` (the gather clamps it)."""
    c = config
    f32, i32 = jnp.float32, jnp.int32
    dtype = variables["embedding"].dtype
    eps = c["rms_norm_eps"]
    depth, dense = c["num_hidden_layers"], c["num_dense_layers"]
    held = c["num_experts"]
    r, t = ids.shape
    with jax.named_scope("embed"):
        x = jnp.take(variables["embedding"], ids.astype(i32),
                     axis=0).astype(f32)
        if c["mup_enabled"]:
            x = x * c["hidden_size"] ** 0.5

    def dense_mlp(h2, index):
        w = {name: lax.dynamic_index_in_dim(leaf, index, keepdims=False)
             for name, leaf in variables["dense"].items()}
        with jax.named_scope("dense_mlp"):
            m = _gated_mlp(h2, w["mlp.gate_up_proj"], w["mlp.down_proj"],
                           dtype, precision)
        return m, jnp.zeros((r, held), i32)

    def expert_mlp(h2, index):
        layer = index - dense
        stacks = variables["experts"]
        w = {name: lax.dynamic_index_in_dim(leaf, layer, keepdims=False)
             for name, leaf in stacks.items()
             if not name.startswith("mlp.experts.")}
        with jax.named_scope("router"):
            chosen, weight = _route(c, h2.reshape(r * t, -1),
                                    w["mlp.router.gate"],
                                    w["mlp.expert_bias"])
        m, load, _ = _held_experts(
            c, h2, chosen, weight, stacks["mlp.experts.gate_up_proj"],
            stacks["mlp.experts.down_proj"], layer * held, dtype, precision)
        with jax.named_scope("shared_expert"):
            m = m + _gated_mlp(h2, w["mlp.shared_experts.gate_up_proj"],
                               w["mlp.shared_experts.down_proj"], dtype,
                               precision)
        return m, load

    def layer(x, scanned):
        w, index, sliding = scanned
        with jax.named_scope("attention"):
            x = x + _rms_norm(
                _attention_branch(c, x, w, sliding, dtype, precision),
                w["post_attention_layernorm"], eps)
        h2 = _rms_norm(x, w["pre_mlp_layernorm"], eps)
        if dense == 0:
            m, load = expert_mlp(h2, index)
        elif dense == depth:
            m, load = dense_mlp(h2, index)
        else:
            m, load = lax.cond(index < dense, dense_mlp, expert_mlp, h2, index)
        return x + _rms_norm(m, w["post_mlp_layernorm"], eps), load

    sliding = jnp.asarray([kind == "sliding_attention"
                           for kind in c["layer_types"]])
    x, load = lax.scan(layer, x, (variables["layers"],
                                  jnp.arange(depth, dtype=i32), sliding))
    with jax.named_scope("pool"):
        features = jnp.mean(_rms_norm(x, variables["final_layernorm"], eps),
                            axis=1)
    # [layers, R, held] -> the expert layers' [R, layers, held]
    return {"features": features,
            "expert_load": jnp.moveaxis(load[dense:], 0, 1)}


def model_function(config: Dict[str, Any], variables: Dict[str, Any], *,
                   compute_dtype: Optional[str] = None,
                   matmul_precision: Optional[str] = None):
    """The trunk as a ``ModelFunction`` for ``ModelTransformer`` over an
    integer list column: ``features`` is the column, ``expert_load`` a
    counter.  The compute dtype is the weights' own: a leaf that is
    already of ``compute_dtype`` (or every leaf, where none is named) is
    used as it is given, so weights placed on the device stay the one
    copy; ``expert_bias``, which only chooses, stays float32.
    ``matmul_precision`` ``"highest"`` is for float32 parity runs,
    ``None``/``"default"`` the chip's default."""
    from sparkdl_tpu.graph.function import ModelFunction

    config = dict(config)
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types names not one kind a layer")
    if compute_dtype is not None:
        target = jnp.dtype(compute_dtype)
        variables = jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf if leaf.dtype == target or getattr(
                path[-1], "key", None) == "mlp.expert_bias"
            else leaf.astype(target), variables)
    precision = (None if matmul_precision in (None, "default")
                 else lax.Precision(matmul_precision))

    def fn(v, ids):
        return apply(v, ids, config, precision=precision)

    return ModelFunction(fn=fn, variables=variables, input_names=("ids",),
                         output_names=("features",) + COUNTERS,
                         counter_names=COUNTERS, token_input="ids")
