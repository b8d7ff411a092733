"""Keye-VL-2.0's first stage (``model_type: KeyeVL2``), plainly: the
reference of ``keye_vl_2_30b_a3b``.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``
and nothing of the program: no kernel, no tile of keys, no chunk of the
row that the mathematics does not have.  Written from the published
``config.json`` (https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B),
the catalog's description ("GQA 32Q/4KV with DeepSeek-Sparse-Attention
indexer (sa_config topk 2048)", "128 experts, top-8, 0 shared",
"SigLIP-class ViT 27L") and SigLIP's published widths; what is from
memory of the family's code (there is no network here) is listed under
``assumed`` in the configuration's file.

The vision tower, a frame at a time (every ``LN`` a LayerNorm with scale
and bias, eps ``layer_norm_eps``)::

    x  = patches(pixels / 127.5 - 1) W_pe + b_pe                 [1024, 1152]
    27 x:  h = LN1(x);  q, k, v = h W + b (16 heads of 72);  q, k turned
           by the 2-D rotary position (half the pairs by the patch's row,
           half by its column);  x += softmax(q k^T / sqrt(72)) v W_o + b_o
           over the frame's own patches, both ways;
           x += GELU_tanh(LN2(x) W_1 + b_1) W_2 + b_2
    z  = GELU(join2x2(LN_pre(LN_post(x))) W_m1 + b) W_m2 + b       [256, 2048]

the splice (the n-th placeholder id's embedding row replaced by the n-th
``z`` of the row's frames, in order), the positions ``(t, h, w)`` of the
Qwen2-VL rule (worked out here a position at a time in numpy), and then a
layer (every ``N`` an RMSNorm, eps ``rms_norm_eps``)::

    h   = N_attn(x);  q = N_q(h W_q), k = N_k(h W_k) by head, v = h W_v
    q, k turned by mRoPE: pairs (i, i + 64), the first 16 by t, the next
          24 by h, the last 24 by w, theta rope_theta
    q_I = h W_Iq (16 x 64);  k_I = LayerNorm(h W_Ik);  both turned by
          mRoPE over 64 with the sections halved;  w = h W_Iw / sqrt(16 x 64)
    I   = sum_j w_j ReLU(q_I,j . k_I)   every query against every key,
          the keys after the query at -inf
    S   = lax.top_k(I, topk), the keys after the query taken out
    a_g = softmax over S of q_g . k_{g // 8} / sqrt(128), of v_{g // 8}
    x  += [a_1 ... a_32] W_o
    u   = N_ffn(x);  p = softmax(u W_r);  the top 8, renormalised
    x  += sum over the top 8 of p_e (SiLU(u W_gate,e) * u W_up,e) W_down,e

and after the last layer ``f = N_final(x)``; a row's feature is the mean
of ``f`` over its positions.  A block of ``QUERY_BLOCK`` queries at a
time meets ALL keys: the indexer's full score matrix and ``lax.top_k``
over it; each query's softmax then runs over ITS selected keys, the
others at -inf (with 4 key/value heads the scores of every key are one
product a head group, cheaper on the chip than gathering each query's
2,048 key rows).

Departures from the published description, every one: the output head
is left off and a row's feature is the MEAN of ``f`` (a featurizer);
the indexer in float32 where the published one may run in lower
precision; depth is what the file says (the first stage's 6 of 48
layers); weights are drawn from the seed (``DRAW``), in float32 and
rounded to bfloat16 VALUES, which is what the program holds.

A row at a time goes through all layers, one layer's weights at a time
on the chip as float32 (the routed experts as drawn, bfloat16 values),
drawn anew from the seed when the reference reaches the layer
(``Weights``); the tower's weights are drawn once for all rows.  The
draw takes the seed as an ARGUMENT of its programs, so one compiled draw
serves every seed.

The CONTROL (``operands="int8"``): the same, with both operands of every
matrix product held in int8, one scale a tensor.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark import vl_trunk_flops as vf

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 128
#: frames the tower takes together
FRAME_BLOCK = 8
#: a mask's tokens are padded to a multiple of this many (weight 0), so
#: that an expert's function compiles for a few lengths
TOKEN_BUCKET = 2048

DRAW = (
    "vision: the patch embedding N(0, 1/fan-in); a block's q_proj "
    "N(0, 4/fan-in) (attention logits of deviation about 2 over a "
    "frame's 1024 patches: a few tens of patches carry a query's "
    "softmax, so the frame mask and the 2-D rotary position show), its "
    "k_proj, v_proj, out_proj, fc1, fc2 N(0, 1/fan-in), biases N(0, "
    "0.01), LayerNorm scales 1 + N(0, 0.01) and biases N(0, 0.01); the "
    "merger's linear_1, linear_2 N(0, 1/fan-in). Language: embedding "
    "N(0, 1) (no norm follows a branch, so each branch adds a variance "
    "of the residual's order); matrices N(0, 1/fan-in) but o_proj's "
    "N(0, 16/fan-in) (the heads average a few tens of keys and come "
    "out small) and the router's N(0, 9/fan-in) (logits of deviation 3: "
    "the eighth choice is no coin toss at every token); q_norm 2 + "
    "N(0, 0.01) (attention logits of deviation about 2), other RMSNorm "
    "scales 1 + N(0, 0.01); the indexer's k_norm 1 + N(0, 0.01), bias "
    "N(0, 0.01), weights_proj N(0, 1/fan-in): index scores of "
    "deviation about 0.7 against a rounding of about 0.002, so that "
    "bfloat16 moves a query's selection at its boundary only; all "
    "rounded to bfloat16 values")

#: the standard deviation of a matrix times sqrt(fan-in), where not 1
GAIN = {"vision.self_attn.q_proj.weight": 2.0, "self_attn.o_proj": 4.0,
        "mlp.gate": 3.0}
#: a norm's scale about this value, where not 1
NORM_AT = {"self_attn.q_norm": 2.0}


def _int8(x):
    """``x`` held in int8 with one scale for the whole tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / scale) * scale


#: what a control may hold the matrix products' operands in
OPERANDS = {"int8": _int8}


# -- shapes, by published name --------------------------------------------------

def vision_block_shapes(config: Dict[str, Any]) -> Dict[str, tuple]:
    v = config["vision_config"]
    d, f = v["hidden_size"], v["intermediate_size"]
    shapes = {"layer_norm1.weight": (d,), "layer_norm1.bias": (d,),
              "layer_norm2.weight": (d,), "layer_norm2.bias": (d,),
              "mlp.fc1.weight": (d, f), "mlp.fc1.bias": (f,),
              "mlp.fc2.weight": (f, d), "mlp.fc2.bias": (d,)}
    for name in ("q_proj", "k_proj", "v_proj", "out_proj"):
        shapes[f"self_attn.{name}.weight"] = (d, d)
        shapes[f"self_attn.{name}.bias"] = (d,)
    return shapes


def vision_shapes(config: Dict[str, Any]) -> Dict[str, tuple]:
    """The tower's weights outside its blocks."""
    v = config["vision_config"]
    d, p = v["hidden_size"], v["patch_size"]
    joined = d * v["spatial_merge_size"] ** 2
    return {"patch_embedding.weight": (p * p * 3, d),
            "patch_embedding.bias": (d,),
            "post_layernorm.weight": (d,), "post_layernorm.bias": (d,),
            "projector.pre_norm.weight": (d,), "projector.pre_norm.bias": (d,),
            "projector.linear_1.weight": (joined, joined),
            "projector.linear_1.bias": (joined,),
            "projector.linear_2.weight": (joined, config["hidden_size"]),
            "projector.linear_2.bias": (config["hidden_size"],)}


def layer_shapes(config: Dict[str, Any]) -> Dict[str, tuple]:
    """Every weight of a language layer by its published name, matrices
    ``[in, out]``, the routed experts stacked on a leading axis."""
    c, sc = config, config["sa_config"]
    d, hd, f = c["hidden_size"], c["head_dim"], c["moe_intermediate_size"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    ih, idim, e = (sc["indexer_num_heads"], sc["indexer_head_dim"],
                   c["num_experts"])
    return {"input_layernorm": (d,), "post_attention_layernorm": (d,),
            "self_attn.q_proj": (d, q), "self_attn.k_proj": (d, kv),
            "self_attn.v_proj": (d, kv), "self_attn.o_proj": (q, d),
            "self_attn.q_norm": (hd,), "self_attn.k_norm": (hd,),
            "self_attn.indexer.wq": (d, ih * idim),
            "self_attn.indexer.wk": (d, idim),
            "self_attn.indexer.k_norm.weight": (idim,),
            "self_attn.indexer.k_norm.bias": (idim,),
            "self_attn.indexer.weights_proj": (d, ih),
            "mlp.gate": (d, e),
            "mlp.experts.gate_proj": (e, d, f),
            "mlp.experts.up_proj": (e, d, f),
            "mlp.experts.down_proj": (e, f, d)}


# -- the draw -------------------------------------------------------------------

def _key(seed, *path: int):
    """The key of ``path`` under ``seed``: a whole number a little over
    2**31 (its low 31 bits and the rest folded in apart), or the int32
    pair ``seed_parts`` makes of one, traced."""
    if isinstance(seed, (int, np.integer)):
        seed = seed_parts(int(seed))
    key = jax.random.fold_in(jax.random.PRNGKey(seed[0]), seed[1])
    for p in path:
        key = jax.random.fold_in(key, p)
    return key


def seed_parts(seed: int) -> np.ndarray:
    return np.asarray([seed & 0x7FFFFFFF, seed >> 31], np.int32)


def _draw(name: str, shape: tuple, key):
    """The weight called ``name`` (``vision.`` before a tower's name) from
    ``key``: bfloat16 values (``DRAW``)."""
    normal = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("bias"):
        v = 0.1 * normal
    elif len(shape) == 1:                                   # a norm's scale
        v = NORM_AT.get(name, 1.0) + 0.1 * normal
    else:
        v = GAIN.get(name, 1.0) * normal / shape[-2] ** 0.5
    return v.astype(jnp.bfloat16)


@functools.lru_cache(maxsize=256)
def _drawer(name: str, shape: tuple):
    return jax.jit(functools.partial(_draw, name, shape))


def _embedding(vocab: int, width: int, key):
    parts = 8 if vocab % 8 == 0 and vocab >= 8192 else 1

    def part(k):
        return jax.random.normal(k, (vocab // parts, width),
                                 jnp.float32).astype(jnp.bfloat16)

    return lax.map(part, jax.random.split(key, parts)).reshape(vocab, width)


class Weights(NamedTuple):
    """The configuration's weights as a rule, not as arrays: every call
    draws the named weight anew on the device from the seed (``DRAW``),
    the same numbers every time.  ``seed`` is a whole number, or the
    traced int32 pair of ``seed_parts`` inside a program that draws
    (then every call is traced into it)."""
    config: Dict[str, Any]
    seed: Any

    def _get(self, name, shape, *path):
        key = _key(self.seed, *path)
        if isinstance(self.seed, (int, np.integer)):
            return _drawer(name, shape)(key)
        return _draw(name, shape, key)

    def leaf(self, layer: int, name: str) -> jnp.ndarray:
        """A language layer's weight by its published name."""
        shapes = layer_shapes(self.config)
        return self._get(name, shapes[name], 1, layer,
                         sorted(shapes).index(name))

    def vision_leaf(self, block, name: str) -> jnp.ndarray:
        """A vision block's weight (``block`` 0 and on, a whole number or
        an int32 of a program), or one outside the blocks (``block`` -1)."""
        outside = isinstance(block, (int, np.integer)) and block < 0
        shapes = (vision_shapes(self.config) if outside
                  else vision_block_shapes(self.config))
        return self._get("vision." + name, shapes[name], 4, block + 1,
                         sorted(shapes).index(name))

    def embedding(self) -> jnp.ndarray:
        args = (self.config["vocab_size"], self.config["hidden_size"])
        key = _key(self.seed, 2)
        if isinstance(self.seed, (int, np.integer)):
            return _embedding_drawer(*args)(key)
        return _embedding(*args, key)

    def final_norm(self) -> jnp.ndarray:
        return self._get("norm", (self.config["hidden_size"],), 3)


@functools.lru_cache(maxsize=4)
def _embedding_drawer(vocab: int, width: int):
    return jax.jit(functools.partial(_embedding, vocab, width))


def draw_weights(config: Dict[str, Any], seed: int) -> Weights:
    return Weights(dict(config), seed)


# -- the pieces -----------------------------------------------------------------

def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def rotate(x, angle):
    """Pairs ``(x[i], x[i + d/2])`` of ``x`` ``[T, ..., d]`` turned by
    ``angle`` ``[T, d/2]``."""
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    half = x.shape[-1] // 2
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * jnp.cos(angle) - hi * jnp.sin(angle),
                            hi * jnp.cos(angle) + lo * jnp.sin(angle)], -1)


def positions(config: Dict[str, Any], ids: np.ndarray) -> np.ndarray:
    """``[T, 3]`` int32 ``(t, h, w)`` of one row, a position at a time: the
    Qwen2-VL rule over runs of placeholder ids, each a clip of whole
    frames of the merged grid."""
    v = config["vision_config"]
    side = v["image_size"] // v["patch_size"] // v["spatial_merge_size"]
    out = np.zeros((len(ids), 3), np.int64)
    ph = np.asarray(ids) == config["image_token_id"]
    at, i = 0, 0
    while i < len(ids):
        if not ph[i]:
            out[i] = at
            at, i = at + 1, i + 1
            continue
        end = i
        while end < len(ids) and ph[end]:
            end += 1
        for k in range(end - i):
            frame, cell = divmod(k, side * side)
            out[i + k] = (at + frame, at + cell // side, at + cell % side)
        at = int(out[i:end].max()) + 1
        i = end
    return out.astype(np.int32)


def mrope_angles(pos, dim: int, sections, theta: float):
    """``[T, dim / 2]``: pair ``i`` turns by its section's stream."""
    stream = np.repeat(np.arange(3), sections)
    inv = theta ** (-np.arange(0, dim, 2) / dim)
    return pos[:, stream].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)


def _product(operands: Optional[str]):
    hold = OPERANDS[operands] if operands else (lambda v: v)

    def product(subscripts, a, b):
        return jnp.einsum(subscripts, hold(a), hold(b), precision=HIGHEST)

    return product


# -- the vision tower -----------------------------------------------------------

def vision_frames(config: Dict[str, Any], w, frames, operands=None):
    """uint8 ``[F, S, S, 3]`` -> the merged tokens ``[F, 256, D]``;
    ``w``: ``blocks`` (a list of each block's weights) and the rest."""
    v, product = config["vision_config"], _product(operands)
    eps, heads, d = v["layer_norm_eps"], v["num_attention_heads"], v["hidden_size"]
    p, m = v["patch_size"], v["spatial_merge_size"]
    side = v["image_size"] // p
    hd = d // heads
    f = frames.shape[0]
    x = frames.astype(jnp.float32) / 127.5 - 1.0
    x = x.reshape(f, side, p, side, p, 3).transpose(0, 1, 3, 2, 4, 5).reshape(
        f, side * side, p * p * 3)
    x = product("fpi,id->fpd", x, w["patch_embedding.weight"]) \
        + w["patch_embedding.bias"]
    inv = float(v["rope_theta"]) ** (-np.arange(0, hd // 2, 2) / (hd // 2))
    row, col = np.divmod(np.arange(side * side), side)
    angle = jnp.asarray(np.concatenate([row[:, None] * inv, col[:, None] * inv],
                                       -1), jnp.float32)          # [P, hd/2]

    def turn(t):                                   # [F, P, H, hd]
        return jnp.swapaxes(rotate(jnp.swapaxes(t, 0, 1), angle), 0, 1)

    for b in w["blocks"]:
        h = layer_norm(x, b["layer_norm1.weight"], b["layer_norm1.bias"], eps)

        def proj(name):
            return (product("fpd,de->fpe", h, b[f"self_attn.{name}.weight"])
                    + b[f"self_attn.{name}.bias"]).reshape(f, -1, heads, hd)

        q, k, val = turn(proj("q_proj")), turn(proj("k_proj")), proj("v_proj")
        s = product("fqhd,fkhd->fhqk", q, k) / math.sqrt(hd)
        a = product("fhqk,fkhd->fqhd", jax.nn.softmax(s, -1), val)
        x = x + product("fpe,ed->fpd", a.reshape(f, -1, d),
                        b["self_attn.out_proj.weight"]) \
            + b["self_attn.out_proj.bias"]
        h = layer_norm(x, b["layer_norm2.weight"], b["layer_norm2.bias"], eps)
        u = jax.nn.gelu(product("fpd,de->fpe", h, b["mlp.fc1.weight"])
                        + b["mlp.fc1.bias"], approximate=True)
        x = x + product("fpe,ed->fpd", u, b["mlp.fc2.weight"]) \
            + b["mlp.fc2.bias"]
    x = layer_norm(x, w["post_layernorm.weight"], w["post_layernorm.bias"], eps)
    x = layer_norm(x, w["projector.pre_norm.weight"],
                   w["projector.pre_norm.bias"], eps)
    x = x.reshape(f, side // m, m, side // m, m, d).transpose(
        0, 1, 3, 2, 4, 5).reshape(f, (side // m) ** 2, m * m * d)
    u = jax.nn.gelu(product("ftj,jk->ftk", x, w["projector.linear_1.weight"])
                    + w["projector.linear_1.bias"], approximate=False)
    return (product("ftk,kd->ftd", u, w["projector.linear_2.weight"])
            + w["projector.linear_2.bias"])


@functools.lru_cache(maxsize=8)
def _vision_program(config_json: str, operands: Optional[str]):
    c = json.loads(config_json)
    return jax.jit(lambda w, frames: vision_frames(c, w, frames, operands))


def vision_weights(config: Dict[str, Any], weights: Weights):
    """The tower's weights as float32 on the device."""
    depth = config["vision_config"]["num_hidden_layers"]
    f32 = jnp.float32
    return {"blocks": [{n: weights.vision_leaf(i, n).astype(f32)
                        for n in vision_block_shapes(config)}
                       for i in range(depth)],
            **{n: weights.vision_leaf(-1, n).astype(f32)
               for n in vision_shapes(config)}}


def vision_tokens(config: Dict[str, Any], weights: Weights,
                  pixels: np.ndarray, operands: Optional[str] = None
                  ) -> List[np.ndarray]:
    """A row's merged tokens ``[frames x 256, D]`` float32 (numpy), for
    each row of ``pixels`` ``[rows, ...]`` uint8, ``FRAME_BLOCK`` frames
    at a time."""
    size = config["vision_config"]["image_size"]
    program = _vision_program(json.dumps(config, sort_keys=True), operands)
    out = []
    with jax.default_matmul_precision("highest"):
        w = vision_weights(config, weights)
        for row in np.asarray(pixels):
            frames = row.reshape(-1, size, size, 3)
            out.append(np.concatenate([
                np.asarray(program(w, jnp.asarray(
                    frames[i:i + FRAME_BLOCK]))).reshape(
                        -1, config["hidden_size"])
                for i in range(0, len(frames), FRAME_BLOCK)]))
        del w
    return out


# -- a language layer -----------------------------------------------------------

def attention_branch(config: Dict[str, Any], w, x, angle, index_angle,
                     operands=None, chosen_out=None):
    """``[a_1 ... a_H] W_o`` of one layer over one row ``x`` ``[T, D]``."""
    c, sc, product = config, config["sa_config"], _product(operands)
    t, eps = x.shape[0], c["rms_norm_eps"]
    heads, kv_heads, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                           c["head_dim"])
    ih, idim, top = sc["indexer_num_heads"], sc["indexer_head_dim"], sc["topk"]
    rep = heads // kv_heads
    h = rms_norm(x, w["input_layernorm"], eps)
    q = rotate(rms_norm(product("td,de->te", h, w["self_attn.q_proj"]).reshape(
        t, heads, hd), w["self_attn.q_norm"], eps), angle) / math.sqrt(hd)
    k = rotate(rms_norm(product("td,de->te", h, w["self_attn.k_proj"]).reshape(
        t, kv_heads, hd), w["self_attn.k_norm"], eps), angle)
    v = product("td,de->te", h, w["self_attn.v_proj"]).reshape(t, kv_heads, hd)
    q_i = rotate(product("td,de->te", h, w["self_attn.indexer.wq"]).reshape(
        t, ih, idim), index_angle)
    k_i = rotate(layer_norm(product("td,de->te", h, w["self_attn.indexer.wk"]),
                            w["self_attn.indexer.k_norm.weight"],
                            w["self_attn.indexer.k_norm.bias"], eps),
                 index_angle)
    weight = product("td,dj->tj", h, w["self_attn.indexer.weights_proj"]) \
        / math.sqrt(ih * idim)
    block = min(QUERY_BLOCK, t)
    key_at = jnp.arange(t)

    def queries(first):
        query_at = first + jnp.arange(block)
        index = jnp.einsum(
            "qj,qjk->qk", lax.dynamic_slice_in_dim(weight, first, block),
            jax.nn.relu(product("qjd,kd->qjk",
                                lax.dynamic_slice_in_dim(q_i, first, block),
                                k_i)), precision=HIGHEST)
        causal = key_at[None, :] <= query_at[:, None]
        _, chosen = lax.top_k(jnp.where(causal, index, -jnp.inf), top)
        # fewer keys than top before position top - 1: the rest of the
        # answer is keys after the query, at -inf, and taken out here
        seen = chosen <= query_at[:, None]                        # [q, top]
        selected = jnp.zeros((block, t), bool).at[
            jnp.arange(block)[:, None], chosen].set(seen)         # S(t)
        q_b = lax.dynamic_slice_in_dim(q, first, block).reshape(
            block, kv_heads, rep, hd)
        scores = product("qgrd,kgd->qgrk", q_b, k)
        p = jax.nn.softmax(jnp.where(selected[:, None, None], scores,
                                     -jnp.inf), -1)
        a = product("qgrk,kgd->qgrd", p, v).reshape(block, heads * hd)
        return product("te,ed->td", a, w["self_attn.o_proj"]), jnp.where(
            seen, chosen, -1)

    out, chosen = lax.map(queries, jnp.arange(0, t, block))
    return out.reshape(t, -1), chosen.reshape(t, top)


def route(config: Dict[str, Any], w, u, operands=None):
    """``(the chosen experts [T, k], their weights [T, k])``."""
    p = jax.nn.softmax(_product(operands)("td,de->te", u, w["mlp.gate"]), -1)
    weight, chosen = lax.top_k(p, config["num_experts_per_tok"])
    if config["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return chosen, weight


@functools.lru_cache(maxsize=16)
def _steps(config_json: str, operands: Optional[str]):
    """The jitted pieces of a layer over one row: ``attention (w, x,
    angle, index_angle) -> (x, u, chosen, weight, selected)`` and
    ``expert (w, m, u, chosen, weight, e, tokens, picked) -> m``."""
    c = json.loads(config_json)
    product, eps = _product(operands), c["rms_norm_eps"]

    def attention(w, x, angle, index_angle):
        a, selected = attention_branch(c, w, x, angle, index_angle, operands)
        x = x + a
        u = rms_norm(x, w["post_attention_layernorm"], eps)
        return (x, u) + route(c, w, u, operands) + (selected,)

    def expert(w, m, u, chosen, weight, e, tokens, picked):
        """``m`` with ``p_e expert_e(u)`` added at the first ``picked`` of
        ``tokens`` (the rest pad the bucket and weigh nothing)."""
        f32 = jnp.float32
        share = jnp.sum(jnp.where(chosen[tokens] == e, weight[tokens], 0.0),
                        axis=-1) * (jnp.arange(tokens.shape[0]) < picked)
        gate, up, down = (
            lax.dynamic_index_in_dim(w[f"mlp.experts.{name}_proj"], e,
                                     keepdims=False).astype(f32)
            for name in ("gate", "up", "down"))
        h = u[tokens]
        out = product("tf,fd->td", jax.nn.silu(product("td,df->tf", h, gate))
                      * product("td,df->tf", h, up), down)
        return m.at[tokens].add(share[:, None] * out)

    return {"attention": jax.jit(attention),
            "expert": jax.jit(expert, donate_argnums=1)}


def layer_row(config: Dict[str, Any], w, x, angle, index_angle,
              operands: Optional[str] = None):
    """One layer over one row ``x`` ``[T, D]``: the new ``x`` and each
    query's selected keys ``[T, topk]`` (-1 where it had fewer)."""
    steps = _steps(json.dumps(config, sort_keys=True), operands)
    x, u, chosen, weight, selected = steps["attention"](w, x, angle,
                                                        index_angle)
    took = np.asarray(chosen)
    m = jnp.zeros_like(x)
    for e in range(config["num_experts"]):
        tokens = np.flatnonzero((took == e).any(axis=1))
        if not len(tokens):
            continue
        pad = -len(tokens) % TOKEN_BUCKET
        m = steps["expert"](w, m, u, chosen, weight, e, np.concatenate(
            [tokens, np.zeros(pad, tokens.dtype)]), len(tokens))
    return x + m, selected


def embed_row(config: Dict[str, Any], weights: Weights, ids, tokens):
    """The first layer's input of one row: ``E[ids]`` with the n-th
    placeholder id's row the n-th merged token."""
    x = np.array(jnp.take(weights.embedding(), jnp.asarray(ids), axis=0)
                 .astype(jnp.float32))
    at = np.flatnonzero(np.asarray(ids) == config["image_token_id"])
    used = min(len(at), len(tokens))
    x[at[:used]] = tokens[:used]
    x[at[used:]] = tokens[-1]
    return jnp.asarray(x)


def layer_weights(config: Dict[str, Any], weights: Weights, index: int):
    return {name: (leaf if name.startswith("mlp.experts.")
                   else leaf.astype(jnp.float32))
            for name in layer_shapes(config)
            for leaf in [weights.leaf(index, name)]}


def forward(config: Dict[str, Any], weights: Weights, pixels: np.ndarray,
            ids: np.ndarray, operands: Optional[str] = None,
            selections: Optional[List] = None) -> np.ndarray:
    """``pixels`` ``[rows, ...]`` uint8 and ``ids`` ``[rows, T]`` ->
    features ``[rows, D]`` float32 (numpy).  The tower over every row
    first, then a row at a time through all layers, one layer's weights
    at a time on the device, drawn again for every row.  ``selections``,
    a list, is given each row's ``[layers, T, topk]`` selected keys."""
    c = config
    sections = c["rope_scaling"]["mrope_section"]
    theta = float(c["rope_theta"])
    tokens = vision_tokens(c, weights, pixels, operands)
    features = []
    with jax.default_matmul_precision("highest"):
        for row, z in zip(np.asarray(ids), tokens):
            pos = positions(c, row)
            angle = mrope_angles(pos, c["head_dim"], sections, theta)
            index_angle = mrope_angles(pos, c["sa_config"]["indexer_head_dim"],
                                       [s // 2 for s in sections], theta)
            x = embed_row(c, weights, row, z)
            chosen = []
            for index in range(c["num_hidden_layers"]):
                w = layer_weights(c, weights, index)
                x, selected = layer_row(c, w, x, angle, index_angle, operands)
                if selections is not None:
                    chosen.append(np.asarray(selected))
                del w
            if selections is not None:
                selections.append(np.stack(chosen))
            features.append(np.asarray(jnp.mean(rms_norm(
                x, weights.final_norm().astype(jnp.float32),
                c["rms_norm_eps"]), axis=0)))
            del x
    return np.stack(features)


def flops_per_row(config: Dict[str, Any]) -> int:
    """Operations a row (``vl_trunk_flops``)."""
    return vf.flops_per_row(config)
