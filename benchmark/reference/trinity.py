"""Trinity-Large's layer (``model_type: afmoe``), plainly: the reference
of ``trinity_large_preview``.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``
and nothing of the program: no kernel, no sort, no batching.  Attention
is a block of queries at a time against ALL keys with the mask written
out; a routed expert runs over the tokens that a boolean mask picks.
Written from the published ``config.json``
(https://huggingface.co/arcee-ai/Trinity-Large-Preview) and the
catalog's description ("SWA(4096) gated; global every 4th", "256 experts,
top-4, 1 shared; sigmoid routing, SMEBU bias", "sandwich norm"); what is
from memory of the family's code, not from a copy of it (there is no
network here), is listed under ``assumed`` in the configuration's file.

For the positions of one row, ``x = E[ids] * sqrt(hidden_size)``
(``mup_enabled``), then a layer; every ``N`` an RMSNorm with a learned
scale (eps ``rms_norm_eps``)::

    h   = N_in(x)
    q,k = N_q(W_q h), N_k(W_k h)  by head, over head_dim;  v = W_v h;  g = W_g h
    q,k = rotary(q), rotary(k)    in sliding_attention layers ONLY
                                  (rope_theta, halves paired); a
                                  full_attention layer carries no position
    a   = softmax(q k^T / sqrt(head_dim)) v  over keys with key <= query
          and, in a sliding layer, query - key < sliding_window
    x   = x + N_post_attn(W_o (a * sigmoid(g)))
    h2  = N_pre_mlp(x)
    m   = W_down(SiLU(W_gate h2) * W_up h2)                        a dense layer
    m   = shared(h2) + sum over e in top4, e held here, of w_e expert_e(h2)
                                                                 an expert layer
          s    = sigmoid(W_r h2)            every routed expert's score
          top4 = the four largest of s + expert_bias   (the bias chooses only)
          w    = s[top4];  w = w / sum(w) (route_norm, over all four, held
                 here or not);  w = route_scale * w
    x   = x + N_post_mlp(m)

and after the last layer ``f = N_final(x)``; a row's feature is the mean
of ``f`` over its positions.

**The share.**  The configuration states one chip's part of a layer that
``expert_share[1]`` chips divide: ``num_attention_heads`` and
``num_key_value_heads`` are the heads HELD here (with the matching rows
of ``W_o``), ``num_experts`` the routed experts held, which are experts
``expert_share[0] * num_experts`` and on of the ``num_experts *
expert_share[1]`` that the router scores.  What the other chips would
add to ``W_o``'s sum and to the experts' sum is left out, here as in the
program, and the partial result goes on to the next layer.  With
``expert_share = [0, 1]`` and every head this is the whole layer.

Departures from the published description, every one: the output head
is left off and a row's feature is the MEAN of ``f`` (a featurizer);
depth, the leading dense layers, the kinds of the layers, the heads and
experts held are what the configuration's file says; weights are drawn
from the seed (``DRAW``), in float32 and rounded to bfloat16 VALUES,
which is what the program holds.

One layer's weights at a time are on the chip as float32, drawn anew
from the seed when the reference reaches the layer (``Weights``).

The CONTROL (``operands="int8"``): the same, with both operands of every
matrix product (projections, ``q k^T``, the weighted values, the router)
held in int8, one scale a tensor.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark import expert_trunk_flops as ef

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 512
#: a mask's tokens are padded to a multiple of this many (weight 0), so
#: that an expert's function compiles for one length at the cell's size
#: (4096 tokens an expert over 8 rows, give or take a hundred), not for each
TOKEN_BUCKET = 8192

DRAW = (
    "every matrix N(0, 1/fan-in): a unit-variance input gives a "
    "unit-variance output, and the norm after each branch brings the "
    "branch to the residual's scale whatever its own; norm scales 1 + "
    "N(0, 0.01) but q_norm's, 3 + N(0, 0.09) (logits of standard "
    "deviation 3: a peaked softmax over 4096 keys, so that the window, "
    "the rotary position and the gate each move a row's feature); the "
    "router's matrix N(0, 1/fan-in) (logits of deviation 1: the four "
    "chosen scores 0.88-0.95, their sum 3.6-3.7, so route_norm changes "
    "the weights 3.7-fold and route_scale 2.448-fold); expert_bias: "
    "every share of num_experts experts gets the SAME values, 0.03 x "
    "the standard normal's quantiles at (i + 1/2) / num_experts, in an "
    "order of its own from the seed (deviation 0.03, three times the "
    "distance of the fourth score to the fifth: it changes the choice "
    "of nine tokens in ten and makes an expert's load uneven, the "
    "fullest about three times the mean, while every chip's share of "
    "the pairs stays the same, which is what such a bias is trained "
    "for); embedding N(0, 1/hidden_size), so that "
    "x enters at unit variance under mup's sqrt(hidden_size); all "
    "rounded to bfloat16 values")


def _int8(x):
    """``x`` held in int8 with one scale for the whole tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / scale) * scale


#: what a control may hold the matrix products' operands in
OPERANDS = {"int8": _int8}


def held_experts(config: Dict[str, Any]) -> range:
    """The routed experts this share holds, by their number among all."""
    first = config["expert_share"][0] * config["num_experts"]
    return range(first, first + config["num_experts"])


def layer_shapes(config: Dict[str, Any], index: int) -> Dict[str, tuple]:
    """Every weight of layer ``index``, by its published name, matrices
    ``[in, out]``, a layer's routed experts stacked on a leading axis."""
    c = config
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    shapes = {"input_layernorm": (d,), "post_attention_layernorm": (d,),
              "pre_mlp_layernorm": (d,), "post_mlp_layernorm": (d,),
              "self_attn.q_proj": (d, q), "self_attn.k_proj": (d, kv),
              "self_attn.v_proj": (d, kv), "self_attn.gate_proj": (d, q),
              "self_attn.o_proj": (q, d),
              "self_attn.q_norm": (hd,), "self_attn.k_norm": (hd,)}
    if index < c["num_dense_layers"]:
        ff = c["intermediate_size"]
        shapes.update({"mlp.gate_proj": (d, ff), "mlp.up_proj": (d, ff),
                       "mlp.down_proj": (ff, d)})
        return shapes
    f, held = c["moe_intermediate_size"], c["num_experts"]
    fs = f * c["num_shared_experts"]
    shapes.update({
        "mlp.router.gate": (d, ef.routed_experts(c)),
        "mlp.expert_bias": (ef.routed_experts(c),),
        "mlp.shared_experts.gate_proj": (d, fs),
        "mlp.shared_experts.up_proj": (d, fs),
        "mlp.shared_experts.down_proj": (fs, d),
        "mlp.experts.gate_proj": (held, d, f),
        "mlp.experts.up_proj": (held, d, f),
        "mlp.experts.down_proj": (held, f, d)})
    return shapes


def _key(seed: int, *path: int):
    # a seed a little over 2**31: its high and low halves, folded in apart
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    for p in path:
        key = jax.random.fold_in(key, p)
    return key


@functools.lru_cache(maxsize=128)
def _leaf_drawer(name: str, shape: tuple, share: int = 0):
    """``key -> the weight called name`` (bfloat16 but the bias, on the
    device; ``DRAW``), jitted once a name and shape; ``share`` is the
    number of experts a chip holds, which the bias's draw goes by."""
    normal = functools.partial(jax.random.normal, shape=shape,
                               dtype=jnp.float32)

    @jax.jit
    def draw(key):
        if name == "mlp.expert_bias":
            # the bias only chooses: float32 in program and reference
            values = 0.03 * jax.scipy.special.ndtri(
                (jnp.arange(share, dtype=jnp.float32) + 0.5) / share)
            v = jnp.concatenate([
                jax.random.permutation(k, values)
                for k in jax.random.split(key, shape[0] // share)])
            return v.astype(jnp.bfloat16).astype(jnp.float32)
        if name.endswith("q_norm"):
            v = 3.0 + 0.3 * normal(key)
        elif name.endswith("norm"):
            v = 1.0 + 0.1 * normal(key)
        else:
            v = normal(key) / shape[-2] ** 0.5
        return v.astype(jnp.bfloat16)

    return draw


@functools.lru_cache(maxsize=4)
def _embedding_drawer(vocab: int, width: int):
    parts = 8 if vocab % 8 == 0 and vocab >= 8192 else 1

    def part(key):
        return (jax.random.normal(key, (vocab // parts, width), jnp.float32)
                / width ** 0.5).astype(jnp.bfloat16)

    return jax.jit(lambda key: lax.map(
        part, jax.random.split(key, parts)).reshape(vocab, width))


class Weights(NamedTuple):
    """The configuration's weights as a rule, not as arrays: every call
    draws the named weight anew on the device from the seed (``DRAW``),
    the same numbers every time.  So the program is given one copy and
    the reference draws a layer when it reaches it."""
    config: Dict[str, Any]
    seed: int

    def names(self, layer: int) -> List[str]:
        return sorted(layer_shapes(self.config, layer))

    def leaf(self, layer: int, name: str) -> jnp.ndarray:
        shapes = layer_shapes(self.config, layer)
        # a name keeps its number whatever the layer's kind
        number = sorted({*layer_shapes(self.config, 0), *layer_shapes(
            self.config, self.config["num_hidden_layers"] - 1)}).index(name)
        share = (self.config["num_experts"] if name == "mlp.expert_bias"
                 else 0)
        return _leaf_drawer(name, shapes[name], share)(
            _key(self.seed, 1, layer, number))

    def layer(self, index: int) -> Dict[str, jnp.ndarray]:
        return {name: self.leaf(index, name) for name in self.names(index)}

    def embedding(self) -> jnp.ndarray:
        return _embedding_drawer(self.config["vocab_size"],
                                 self.config["hidden_size"])(
            _key(self.seed, 2))

    def final_layernorm(self) -> jnp.ndarray:
        return _leaf_drawer("norm", (self.config["hidden_size"],))(
            _key(self.seed, 3))


def draw_weights(config: Dict[str, Any], seed: int) -> Weights:
    return Weights(dict(config), seed)


# -- the forward pass ---------------------------------------------------------

def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary(x, theta: float):
    """``x`` ``[T, heads, hd]``: position ``t`` turns the pair
    ``(x[i], x[i + hd/2])`` by the angle ``t * theta**(-2i/hd)``."""
    t, _, hd = x.shape
    half = hd // 2
    freq = jnp.exp(-np.log(float(theta)) * jnp.arange(half) * 2.0 / hd)
    angle = jnp.arange(t)[:, None, None] * freq[None, None, :]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * jnp.cos(angle) - hi * jnp.sin(angle),
                            hi * jnp.cos(angle) + lo * jnp.sin(angle)], -1)


def _product(operands: Optional[str]):
    hold = OPERANDS[operands] if operands else (lambda v: v)

    def product(subscripts, a, b):
        return jnp.einsum(subscripts, hold(a), hold(b), precision=HIGHEST)

    return product


def attention_branch(config: Dict[str, Any], w, x, kind: str,
                     operands: Optional[str] = None):
    """The attention branch of one layer over one row ``x`` ``[T, D]``,
    before the norm that follows it: ``W_o (a * sigmoid(g))`` — with the
    held heads only, their part of ``W_o``'s sum."""
    c, product = config, _product(operands)
    t = x.shape[0]
    eps, hd = c["rms_norm_eps"], c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    sliding = kind == "sliding_attention"
    h = rms_norm(x, w["input_layernorm"], eps)
    q = product("td,de->te", h, w["self_attn.q_proj"]).reshape(t, heads, hd)
    k = product("td,de->te", h, w["self_attn.k_proj"]).reshape(t, kv, hd)
    v = product("td,de->te", h, w["self_attn.v_proj"]).reshape(t, kv, hd)
    gate = product("td,de->te", h, w["self_attn.gate_proj"])
    q = rms_norm(q, w["self_attn.q_norm"], eps)
    k = rms_norm(k, w["self_attn.k_norm"], eps)
    if sliding:
        q, k = rotary(q, c["rope_theta"]), rotary(k, c["rope_theta"])
    k, v = (jnp.repeat(u, heads // kv, axis=1) for u in (k, v))
    block = min(QUERY_BLOCK, t)
    key_at = jnp.arange(t)

    def queries(at):
        q_i, first = at                             # [block, heads, hd]
        query_at = first + jnp.arange(block)
        seen = key_at[None, :] <= query_at[:, None]
        if sliding:
            seen &= query_at[:, None] - key_at[None, :] < c["sliding_window"]
        scores = product("qhd,khd->hqk", q_i, k) / np.sqrt(hd)
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return product("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    mixed = lax.map(queries, (q.reshape(t // block, block, heads, hd),
                              jnp.arange(0, t, block)))
    mixed = mixed.reshape(t, heads * hd) * jax.nn.sigmoid(gate)
    return product("te,ed->td", mixed, w["self_attn.o_proj"])


def gated_mlp(product, h, gate, up, down):
    return product("tf,fd->td", jax.nn.silu(product("td,df->tf", h, gate))
                   * product("td,df->tf", h, up), down)


def route(config: Dict[str, Any], w, h2, operands: Optional[str] = None):
    """``(the chosen experts [T, k], their weights [T, k])``."""
    c = config
    scores = jax.nn.sigmoid(_product(operands)(
        "td,de->te", h2, w["mlp.router.gate"]))
    _, chosen = lax.top_k(scores + w["mlp.expert_bias"],
                          c["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    if c["route_norm"]:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return chosen, c["route_scale"] * weight


@functools.lru_cache(maxsize=32)
def _steps(config_json: str, kind: str, operands: Optional[str]):
    """The jitted pieces of one layer of this kind, rows one at a time:
    ``attention (w, x) -> (x, h2)``, ``dense (w, h2) -> m``, ``shared (w,
    h2) -> (m, chosen, weight)``, ``expert (w, m, h2, chosen, weight,
    local, tokens, picked) -> m`` and ``close (w, x, m) -> x``."""
    c = json.loads(config_json)
    product, eps = _product(operands), c["rms_norm_eps"]

    def attention(w, x):
        def row(x):
            x = x + rms_norm(attention_branch(c, w, x, kind, operands),
                             w["post_attention_layernorm"], eps)
            return x, rms_norm(x, w["pre_mlp_layernorm"], eps)
        return lax.map(row, x)

    def shared(w, h2):
        return lax.map(lambda h: (gated_mlp(
            product, h, w["mlp.shared_experts.gate_proj"],
            w["mlp.shared_experts.up_proj"],
            w["mlp.shared_experts.down_proj"]),) + route(c, w, h, operands),
            h2)

    def expert(w, m, h2, chosen, weight, local, tokens, picked):
        """``m`` with ``w_e expert_e(h2)`` added at the first ``picked``
        of ``tokens`` (the rest pad the bucket and weigh nothing), ``e``
        the held expert number ``local``."""
        f32 = jnp.float32
        expert = held_experts(c)[0] + local
        share = jnp.sum(jnp.where(chosen[tokens] == expert, weight[tokens],
                                  0.0), axis=-1)
        share = share * (jnp.arange(tokens.shape[0]) < picked)
        gate, up, down = (
            lax.dynamic_index_in_dim(w[f"mlp.experts.{name}_proj"], local,
                                     keepdims=False).astype(f32)
            for name in ("gate", "up", "down"))
        out = gated_mlp(product, h2[tokens], gate, up, down)
        return m.at[tokens].add(share[:, None] * out)

    return {
        "attention": jax.jit(attention),
        "dense": jax.jit(lambda w, h2: lax.map(lambda h: gated_mlp(
            product, h, w["mlp.gate_proj"], w["mlp.up_proj"],
            w["mlp.down_proj"]), h2)),
        "shared": jax.jit(shared),
        "expert": jax.jit(expert, donate_argnums=1),
        "close": jax.jit(lambda w, x, m: x + rms_norm(
            m, w["post_mlp_layernorm"], eps))}


def expert_branch(config: Dict[str, Any], w, h2, steps):
    """The MLP branch of an expert layer over ``h2`` ``[rows, T, D]``,
    before the norm that follows it: the shared expert plus the held
    experts' weighted part; and how many tokens of each row every held
    expert took ``[rows, held]``."""
    c = config
    rows, t, d = h2.shape
    m, chosen, weight = (v.reshape((rows * t,) + v.shape[2:])
                         for v in steps["shared"](w, h2))
    h2 = h2.reshape(rows * t, d)
    # the tokens a boolean mask picks, expert by expert: the masks on the
    # host, their tokens' numbers padded to a bucket so that the expert's
    # function compiles for a few lengths
    took = np.asarray(chosen)[:, :, None] == np.asarray(held_experts(c))
    took = took.any(axis=1)                               # [tokens, held]
    for local in range(c["num_experts"]):
        tokens = np.flatnonzero(took[:, local])
        if not len(tokens):
            continue
        pad = -len(tokens) % TOKEN_BUCKET
        m = steps["expert"](
            w, m, h2, chosen, weight, local,
            np.concatenate([tokens, np.zeros(pad, tokens.dtype)]),
            len(tokens))
    load = took.reshape(rows, t, -1).sum(axis=1)
    return m.reshape(rows, t, d), load


def layer_tokens(config: Dict[str, Any], w, x, kind: str, dense: bool,
                 operands: Optional[str] = None):
    """One layer over rows ``x`` ``[rows, T, D]``; ``w`` float32 (a
    layer's routed experts as they are drawn: their values are
    bfloat16's).  Returns the new ``x`` and, for an expert layer, the
    tokens by row and held expert."""
    steps = _steps(json.dumps(config, sort_keys=True), kind, operands)
    x, h2 = steps["attention"](w, x)
    if dense:
        m, load = steps["dense"](w, h2), None
    else:
        m, load = expert_branch(config, w, h2, steps)
    return steps["close"](w, x, m), load


def forward(config: Dict[str, Any], weights: Weights, ids: np.ndarray,
            operands: Optional[str] = None, loads: Optional[List] = None
            ) -> np.ndarray:
    """``ids`` ``[rows, T]`` -> features ``[rows, D]`` float32 (numpy).
    One layer's weights at a time on the device, as float32.  ``loads``,
    a list, is filled with each expert layer's ``[rows, held]`` counts of
    tokens by held expert."""
    f32 = jnp.float32
    c = config
    with jax.default_matmul_precision("highest"):
        x = (jnp.take(weights.embedding(), jnp.asarray(ids), axis=0)
             .astype(f32) * (c["hidden_size"] ** 0.5 if c["mup_enabled"]
                             else 1.0))
        for index in range(c["num_hidden_layers"]):
            w = {name: (leaf if name.startswith("mlp.experts.")
                        else leaf.astype(f32))
                 for name in weights.names(index)
                 for leaf in [weights.leaf(index, name)]}
            x, load = layer_tokens(c, w, x, c["layer_types"][index],
                                   index < c["num_dense_layers"], operands)
            if loads is not None and load is not None:
                loads.append(load)
            del w
        f = rms_norm(x, weights.final_layernorm().astype(f32),
                     c["rms_norm_eps"])
        return np.asarray(jnp.mean(f, axis=1))


def flops_per_row(config: Dict[str, Any]) -> int:
    """Operations a row (``expert_trunk_flops``): every matrix applied at
    every position, the routed experts at their expected pairs,
    attention at the pairs of each layer's band."""
    return ef.flops_per_row(config)
