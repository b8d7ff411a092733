"""SDAR's layer and its block-diffusion sampler's passes, plainly: the
reference of ``sdar_30b_a3b_chat``.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``
and nothing of the program: no kernel, no cache, no loop over blocks, no
sort, no batching.  Written from the published ``config.json``
(https://huggingface.co/JetLM/SDAR-30B-A3B-Chat, ``model_type:
sdar_moe``: a Qwen3-MoE decoder block) and the catalog's description
("128 experts, top-8, 0 shared", "block diffusion"); what is from memory
of the family's code, not from a copy of it (there is no network here),
is listed under ``assumed`` in the configuration's file.

For a residual stream ``x`` ``[T, D]`` (``x = E[ids]``), a position's
index ``pos`` and block length ``B``, every ``N`` an RMSNorm with a
learned scale (eps ``rms_norm_eps``)::

    u   = N_in(x)
    q,k = N_q(W_q u), N_k(W_k u)  by head, over head_dim;   v = W_v u
    q,k = rotary(q), rotary(k)    rope_theta, halves paired, at ``pos``
    h   = x + W_o softmax(q k^T / sqrt(head_dim) + M) v
    u2  = N_post(h)
    p   = softmax(W_r u2) over all experts; the num_experts_per_tok largest;
    w   = p[top] / sum(p[top])                               (norm_topk_prob)
    y   = h + sum over e in top of w_e W_down,e (SiLU(W_gate,e u2) * W_up,e u2)

and after the last layer ``N_final`` and ``lm_head``.  No bias anywhere,
no shared expert.

**The two streams.**  Block diffusion is trained on a clean sequence
followed by a noisy copy of it, and that is the form here.  Given a
row's prompt (``P`` ids) and a trajectory of the sampler — the ids it
generated (``L``) and the pass ``revealed_at`` that revealed each — pass
``s`` is ONE forward pass over ``P + L`` clean positions (the prompt and
the generated ids) followed by ``L`` noisy ones: the generated positions
as they stood at the start of pass ``s`` (the id where ``revealed_at <
s``, the mask id elsewhere), each carrying the rotary index of its clean
twin.  The mask ``M`` is written out (``two_stream_mask``): a clean
position sees the clean positions of every earlier block and its own; a
noisy position sees the clean positions of every EARLIER block and the
noisy ones of its own block; a clean position never sees a noisy one.
The logits at the noisy positions are then, block by block, what the
sampler's pass ``s`` saw at that block.

``replay`` reads from them, for each position at the pass that revealed
it: the LARGEST logit over the ids but the mask id, the ``logsumexp``
over the vocabulary, and ``max(0, c* - c_i)``: ``c_i`` this position's
log-confidence (the first minus the second) and ``c*`` the largest among
the positions of its block that were still masked at that pass and NOT
revealed in it — 0 where the sampler revealed the most confident.

Departures from the published model, every one: 6 of the 48 layers (the
configuration's file says which); weights drawn from the seed (``DRAW``),
in float32 and rounded to bfloat16 VALUES, which is what the program
holds.  One layer's weights at a time are on the chip, drawn anew from
the seed when the reference reaches the layer (``Weights``); a routed
expert runs over the tokens that a boolean mask picks.

The CONTROL (``operands="int8"``): the same, with both operands of every
matrix product (projections, ``q k^T``, the weighted values, the router,
the experts, the head) held in int8, one scale a tensor.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark import diffusion_flops

HIGHEST = lax.Precision.HIGHEST
#: a mask's tokens are padded to a multiple of this many (weight 0), so
#: that an expert's function compiles for a few lengths, not for each
TOKEN_BUCKET = 1024

DRAW = (
    "embed_tokens N(0, 1): x enters at unit variance (the model has no "
    "multiplier on its embedding); q_proj, k_proj, v_proj, o_proj and an "
    "expert's gate_proj and up_proj N(0, 1/fan-in): a unit-variance input "
    "gives a unit-variance output; norm scales 1 + N(0, 0.01) but "
    "q_norm's, 2 + N(0, 0.04) (attention logits of standard deviation 2: "
    "a softmax over a thousand keys that some twenty keys carry, so that "
    "the mask by blocks, the rotary position and the cache's keys each "
    "move a logit; the branch adds about 0.05 of variance a layer); the "
    "router mlp.gate N(0, 9/fan-in) (logits of deviation 3: the eight "
    "chosen weights fall from about 0.5 to 0.02 and their softmax scores "
    "sum to about 0.4, so norm_topk_prob changes the weights 2.3-fold, "
    "and a choice that flips on rounding swaps the lightest expert, not a "
    "heavy one); an expert's down_proj N(0, 0.25/fan-in) (the experts' "
    "sum adds about 0.03 of variance a layer); lm_head N(0, 16/fan-in) "
    "(logits of deviation 4 over 151,936 ids: the largest about 18, the "
    "logsumexp about 20, so the chosen id's confidence is 0.05-0.3 and "
    "differs between the positions of a block by more than rounding moves "
    "it), its column of the mask id 2.5 times that (deviation 10: the "
    "mask id is the largest logit at a few positions in a hundred, so "
    "that leaving it out of the choice does work in every row); final "
    "norm 1 + N(0, 0.01); all rounded to bfloat16 values.  The branches "
    "are this much smaller than the embedding because a random layer is "
    "chaotic: a rounding error in the stream moves the attention scores "
    "by about their deviation times it and flips the lightest chosen "
    "expert of many tokens, so each layer hands on MORE than its input's "
    "error, the more the larger its branches; what a fault in a branch "
    "moves shrinks only in proportion.  Three draws were read on the "
    "chip (PERF.md section 2): q_norm 3 and down_proj N(0, 4/fan-in) "
    "grew bfloat16's rounding to 0.18 of a logit in the median and a "
    "wrong mask moved it no further; this one reads 0.014")


def _int8(x):
    """``x`` held in int8 with one scale for the whole tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / scale) * scale


#: what a control may hold the matrix products' operands in
OPERANDS = {"int8": _int8}


def layer_shapes(config: Dict[str, Any]) -> Dict[str, tuple]:
    """Every weight of a layer, by its published name, matrices ``[in,
    out]``, a layer's routed experts stacked on a leading axis."""
    c = config
    d, hd, f = c["hidden_size"], c["head_dim"], c["moe_intermediate_size"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    e = c["num_experts"]
    return {"input_layernorm": (d,), "post_attention_layernorm": (d,),
            "self_attn.q_proj": (d, q), "self_attn.k_proj": (d, kv),
            "self_attn.v_proj": (d, kv), "self_attn.o_proj": (q, d),
            "self_attn.q_norm": (hd,), "self_attn.k_norm": (hd,),
            "mlp.gate": (d, e),
            "mlp.experts.gate_proj": (e, d, f),
            "mlp.experts.up_proj": (e, d, f),
            "mlp.experts.down_proj": (e, f, d)}


#: the variance of a matrix's draw over 1/fan-in (``DRAW``)
GAIN = {"mlp.gate": 9.0, "mlp.experts.down_proj": 0.25, "lm_head": 16.0}
#: the mean of q_norm's scale (the others' is 1)
Q_NORM = 2.0
MASK_COLUMN_GAIN = 2.5


def _key(seed: int, *path: int):
    # a seed a little over 2**31: its high and low halves, folded in apart
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    for p in path:
        key = jax.random.fold_in(key, p)
    return key


@functools.lru_cache(maxsize=64)
def _leaf_drawer(name: str, shape: tuple):
    """``key -> the weight called name`` (bfloat16, on the device;
    ``DRAW``), jitted once a name and shape."""

    @jax.jit
    def draw(key):
        normal = jax.random.normal(key, shape, jnp.float32)
        if name.endswith("q_norm"):
            v = Q_NORM * (1.0 + 0.1 * normal)
        elif name.endswith("norm"):
            v = 1.0 + 0.1 * normal
        else:
            v = normal * (GAIN.get(name, 1.0) / shape[-2]) ** 0.5
        return v.astype(jnp.bfloat16)

    return draw


@functools.lru_cache(maxsize=4)
def _vocabulary_drawer(vocab: int, width: int, variance: float):
    """``key -> [vocab, width]`` N(0, variance), drawn in parts."""
    parts = 8 if vocab % 8 == 0 and vocab >= 8192 else 1

    def part(key):
        return (jax.random.normal(key, (vocab // parts, width), jnp.float32)
                * variance ** 0.5).astype(jnp.bfloat16)

    return jax.jit(lambda key: lax.map(
        part, jax.random.split(key, parts)).reshape(vocab, width))


class Weights(NamedTuple):
    """The configuration's weights as a rule, not as arrays: every call
    draws the named weight anew on the device from the seed (``DRAW``),
    the same numbers every time.  So the program is given one copy and
    the reference draws a layer when it reaches it."""
    config: Dict[str, Any]
    seed: int

    def names(self) -> List[str]:
        return sorted(layer_shapes(self.config))

    def leaf(self, layer: int, name: str) -> jnp.ndarray:
        return _leaf_drawer(name, layer_shapes(self.config)[name])(
            _key(self.seed, 1, layer, self.names().index(name)))

    def layer(self, index: int) -> Dict[str, jnp.ndarray]:
        return {name: self.leaf(index, name) for name in self.names()}

    def embedding(self) -> jnp.ndarray:
        return _vocabulary_drawer(self.config["vocab_size"],
                                  self.config["hidden_size"], 1.0)(
            _key(self.seed, 2))

    def final_norm(self) -> jnp.ndarray:
        return _leaf_drawer("norm", (self.config["hidden_size"],))(
            _key(self.seed, 3))

    def lm_head(self) -> jnp.ndarray:
        """``[D, V]``, the mask id's column ``MASK_COLUMN_GAIN`` times as
        wide a draw as the others'."""
        c = self.config
        rows = _vocabulary_drawer(c["vocab_size"], c["hidden_size"],
                                  GAIN["lm_head"] / c["hidden_size"])(
            _key(self.seed, 4))
        rows = rows.at[c["mask_token_id"]].multiply(MASK_COLUMN_GAIN)
        return rows.T


def draw_weights(config: Dict[str, Any], seed: int) -> Weights:
    return Weights(dict(config), seed)


# -- the forward pass ---------------------------------------------------------

def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary(x, at, theta: float):
    """``x`` ``[T, heads, hd]``: the position ``at[t]`` turns the pair
    ``(x[i], x[i + hd/2])`` by the angle ``at[t] * theta**(-2i/hd)``."""
    hd = x.shape[-1]
    half = hd // 2
    freq = jnp.exp(-np.log(float(theta)) * jnp.arange(half) * 2.0 / hd)
    angle = at[:, None, None] * freq[None, None, :]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * jnp.cos(angle) - hi * jnp.sin(angle),
                            hi * jnp.cos(angle) + lo * jnp.sin(angle)], -1)


def _product(operands: Optional[str]):
    hold = OPERANDS[operands] if operands else (lambda v: v)

    def product(subscripts, a, b):
        return jnp.einsum(subscripts, hold(a), hold(b), precision=HIGHEST)

    return product


def two_stream_mask(clean: int, noisy: int, block: int):
    """``(seen [clean + noisy, clean + noisy] bool, the rotary index of
    every position)``: ``clean`` clean positions, then the noisy copies
    of the LAST ``noisy`` of them."""
    at = np.concatenate([np.arange(clean), np.arange(clean - noisy, clean)])
    is_noisy = np.arange(clean + noisy) >= clean
    query_block, key_block = at[:, None] // block, at[None, :] // block
    query_noisy, key_noisy = is_noisy[:, None], is_noisy[None, :]
    seen = np.where(
        query_noisy,
        np.where(key_noisy, key_block == query_block,
                 key_block < query_block),
        np.logical_and(~key_noisy, key_block <= query_block))
    return seen, at


def attention_branch(config: Dict[str, Any], w, x, seen, at,
                     operands: Optional[str] = None):
    """``W_o softmax(q k^T / sqrt(hd) + M) v`` of one layer over one row
    ``x`` ``[T, D]`` whose positions carry the rotary indices ``at``
    and see each other as ``seen`` says."""
    c, product = config, _product(operands)
    t = x.shape[0]
    eps, hd = c["rms_norm_eps"], c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    u = rms_norm(x, w["input_layernorm"], eps)
    q = product("td,de->te", u, w["self_attn.q_proj"]).reshape(t, heads, hd)
    k = product("td,de->te", u, w["self_attn.k_proj"]).reshape(t, kv, hd)
    v = product("td,de->te", u, w["self_attn.v_proj"]).reshape(t, kv, hd)
    q = rotary(rms_norm(q, w["self_attn.q_norm"], eps), at, c["rope_theta"])
    k = rotary(rms_norm(k, w["self_attn.k_norm"], eps), at, c["rope_theta"])
    k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
    scores = product("qhd,khd->hqk", q, k) / np.sqrt(hd)
    scores = jnp.where(seen[None], scores, -jnp.inf)
    mixed = product("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return product("te,ed->td", mixed.reshape(t, heads * hd),
                   w["self_attn.o_proj"])


def route(config: Dict[str, Any], w, u2, operands: Optional[str] = None):
    """``(the chosen experts [T, k], their weights [T, k])``."""
    p = jax.nn.softmax(_product(operands)("td,de->te", u2, w["mlp.gate"]),
                       axis=-1)
    weight, chosen = lax.top_k(p, config["num_experts_per_tok"])
    if config["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return chosen, weight


@functools.lru_cache(maxsize=16)
def _steps(config_json: str, clean: int, noisy: int,
           operands: Optional[str]):
    """The jitted pieces of one layer, rows one at a time: ``attention
    (w, x) -> (h, u2, chosen, weight)``, ``expert (w, m, u2, chosen,
    weight, e, tokens, picked) -> m`` and ``head (norm, lm_head, y) ->
    (largest logit but the mask id's, logsumexp, the id of that logit)``
    at the noisy positions."""
    c = json.loads(config_json)
    product, eps = _product(operands), c["rms_norm_eps"]
    seen, at = two_stream_mask(clean, noisy, c["block_length"])
    seen, at = jnp.asarray(seen), jnp.asarray(at, jnp.float32)

    def attention(w, x):
        def row(x):
            h = x + attention_branch(c, w, x, seen, at, operands)
            u2 = rms_norm(h, w["post_attention_layernorm"], eps)
            return (h, u2) + route(c, w, u2, operands)
        return lax.map(row, x)

    def expert(w, m, u2, chosen, weight, e, tokens, picked):
        """``m`` with ``w_e expert_e(u2)`` added at the first ``picked``
        of ``tokens`` (the rest pad the bucket and weigh nothing)."""
        f32 = jnp.float32
        share = jnp.sum(jnp.where(chosen[tokens] == e, weight[tokens], 0.0),
                        axis=-1)
        share = share * (jnp.arange(tokens.shape[0]) < picked)
        gate, up, down = (
            lax.dynamic_index_in_dim(w[f"mlp.experts.{name}_proj"], e,
                                     keepdims=False).astype(f32)
            for name in ("gate", "up", "down"))
        u = u2[tokens]
        out = product("tf,fd->td", jax.nn.silu(product("td,df->tf", u, gate))
                      * product("td,df->tf", u, up), down)
        return m.at[tokens].add(share[:, None] * out)

    def head(norm, lm_head, y):
        def row(y):
            logits = product("td,dv->tv", rms_norm(y[clean:], norm, eps),
                             lm_head)
            allowed = jnp.where(
                jnp.arange(logits.shape[-1]) == c["mask_token_id"],
                -jnp.inf, logits)
            return (jnp.max(allowed, axis=-1),
                    jax.nn.logsumexp(logits, axis=-1),
                    jnp.argmax(allowed, axis=-1).astype(jnp.int32))
        return lax.map(row, y)

    return {"attention": jax.jit(attention),
            "expert": jax.jit(expert, donate_argnums=1),
            "head": jax.jit(head)}


def expert_branch(config: Dict[str, Any], w, u2, chosen, weight, steps):
    """The experts' weighted sum over ``u2`` ``[tokens, D]``: expert by
    expert over the tokens a boolean mask picks (the masks on the host,
    their tokens' numbers padded to a bucket)."""
    m = jnp.zeros_like(u2)
    took = (np.asarray(chosen)[:, :, None]
            == np.arange(config["num_experts"])).any(axis=1)
    bucket = min(TOKEN_BUCKET, u2.shape[0])
    for e in range(config["num_experts"]):
        tokens = np.flatnonzero(took[:, e])
        if not len(tokens):
            continue
        pad = -len(tokens) % bucket
        m = steps["expert"](
            w, m, u2, chosen, weight, e,
            np.concatenate([tokens, np.zeros(pad, tokens.dtype)]),
            len(tokens))
    return m


def forward(config: Dict[str, Any], weights: Weights, clean: np.ndarray,
            noisy: np.ndarray, operands: Optional[str] = None):
    """One two-stream pass over rows: ``clean`` ``[rows, P + L]`` ids,
    ``noisy`` ``[rows, L]`` ids (the last ``L`` positions as the sampler
    saw them).  ``(the largest logit but the mask id's, the logsumexp,
    the id of that logit)`` at the noisy positions, each ``[rows, L]``
    (numpy).  One layer's weights at a time on the device."""
    f32 = jnp.float32
    c = config
    rows, n_clean = clean.shape
    n_noisy = noisy.shape[1]
    steps = _steps(json.dumps(c, sort_keys=True), n_clean, n_noisy, operands)
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(np.concatenate([clean, noisy], axis=1))
        x = jnp.take(weights.embedding(), ids, axis=0).astype(f32)
        for index in range(c["num_hidden_layers"]):
            w = {name: (leaf if name.startswith("mlp.experts.")
                        else leaf.astype(f32))
                 for name, leaf in weights.layer(index).items()}
            h, u2, chosen, weight = steps["attention"](w, x)
            flat = (rows * (n_clean + n_noisy),)
            m = expert_branch(c, w, u2.reshape(flat + u2.shape[2:]),
                              chosen.reshape(flat + chosen.shape[2:]),
                              weight.reshape(flat + weight.shape[2:]), steps)
            x = h + m.reshape(h.shape)
            del w, h, u2, m
        return tuple(np.asarray(a) for a in steps["head"](
            weights.final_norm().astype(f32), weights.lm_head().astype(f32),
            x))


def noisy_at(config: Dict[str, Any], generated: np.ndarray,
             revealed_at: np.ndarray, step: int) -> np.ndarray:
    """The generated positions as they stood at the start of pass
    ``step``: the id where an earlier pass revealed it, else the mask."""
    return np.where(np.logical_and(revealed_at > 0, revealed_at < step),
                    generated, config["mask_token_id"]).astype(np.int32)


def replay(config: Dict[str, Any], weights: Weights, prompts: np.ndarray,
           generated: np.ndarray, revealed_at: np.ndarray,
           operands: Optional[str] = None) -> np.ndarray:
    """What the sampler's passes read along the given trajectories:
    ``[rows, 3 L]`` float32, a position's three numbers side by side
    (the module's docstring)."""
    c = config
    rows, length = generated.shape
    b = c["block_length"]
    clean = np.concatenate([prompts, generated], axis=1).astype(np.int32)
    out = np.zeros((rows, length, 3), np.float32)
    for step in range(1, c["denoise_steps"] + 1):
        top, lse, _ = forward(c, weights, clean,
                              noisy_at(c, generated, revealed_at, step),
                              operands)
        confidence = (top - lse).reshape(rows, length // b, b)
        later = (revealed_at > step).reshape(confidence.shape)
        best_left = np.where(later, confidence, -np.inf).max(
            axis=-1, keepdims=True)
        short = np.maximum(0.0, best_left - confidence).reshape(rows, length)
        out = np.where((revealed_at == step)[..., None],
                       np.stack([top, lse, short], axis=-1), out)
    return out.reshape(rows, 3 * length)


def flops_per_row(config: Dict[str, Any]) -> int:
    """Operations a row (``diffusion_flops``): the prefill, and every
    pass of every block with or without the head."""
    return diffusion_flops.flops_per_row(config)
