"""Falcon-H1's block, plainly: the reference of ``falcon_h1_34b``.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``
and nothing of the program: full softmax attention, a row at a time,
over the row's whole ``[heads, T, T]`` score matrix; the state-space
recurrence token by token (``lax.scan`` over the positions, no chunks);
no kernel, no batching, no cache.  Written from the published
``config.json`` (https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct)
and the catalog's description ("parallel Mamba-2 + attention heads per
block"); where a multiplier is applied is from memory of the family's
reference code, not from a copy of it (there is no network here).

For positions ``t`` of one row, ``x = embedding_multiplier * E[ids]``,
then a block, with ``h = RMSNorm(x)`` (eps ``rms_norm_eps``, learned
scale)::

    attention   q = W_q(attention_in_multiplier * h)        20 heads of 128
                k = key_multiplier * W_k(...), v = W_v(...)  4 heads of 128,
                    each serving 5 query heads
                rotary position on q and k over the whole head
                    (halves paired, rope_theta, no scaling)
                a = attention_out_multiplier * W_o(causal
                    softmax(q k^T / sqrt(128)) v);  no biases
    mixer       z|x|B|C|dt = ssm_multipliers[0..4] (by section)
                    * in_proj(ssm_in_multiplier * h)
                x|B|C = SiLU(causal depthwise conv, width 4, with bias)
                dt = softplus(dt + dt_bias),  A = -exp(A_log)
                by head:  S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t
                          y_t = S_t C_t + D x_t
                    (B and C shared by the heads of a group)
                m = ssm_out_multiplier * out_proj(RMSNorm_by_group(
                    y * SiLU(z)))       (mamba_norm_before_gate false)
    x = x + a + m
    x = x + mlp_multipliers[1] * W_down(SiLU(mlp_multipliers[0] * W_gate h2)
                                        * W_up h2),    h2 = RMSNorm(x)

and after the last block ``f = RMSNorm(x)``.

Departures from the published description, every one:

* the output head (``lm_head``, ``lm_head_multiplier``) is left off and
  a row's feature is the MEAN of ``f`` over its positions: a featurizer,
  as ``DeepImageFeaturizer`` leaves the classifier off.  Neither is in
  ``config.json``;
* ``num_hidden_layers`` is what the configuration's file says (6 of 72);
* weights are drawn from the seed (``Weights``), not trained: see
  ``DRAW``.  They are drawn in float32 and rounded to bfloat16 VALUES,
  which is what the program holds, so both sides compute from the same
  numbers;
* token ids come from the traffic, uniform over the vocabulary.

At the cell's size six blocks in float32 would be 10.3 GB beside a 5.3
GB embedding: ONE block at a time is on the chip as float32, drawn anew
from the seed when the reference reaches it (``Weights``: the draw is a
function of the seed alone, so these are the numbers the program was
given), and ``E`` as bfloat16 for the gather of the rows the ids name.

The CONTROL (``operands="int8"``): the same, with both operands of every
matrix product (the projections, ``q k^T`` and the weighted values) held
in int8, one scale a tensor — one precision below the bfloat16 the
configuration states.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark import sequence_flops as sf

HIGHEST = lax.Precision.HIGHEST

DRAW = (
    "every matrix N(0, 1/fan-in) divided by the published multipliers "
    "that lie between the normalised input and the matrix's output, so "
    "that each projection, multipliers applied, has unit variance for a "
    "unit-variance input (plain N(0, 1/fan-in) leaves the branches at "
    "1e-2..1e-3 of the residual and the attention logits at 0.011: no "
    "fault in a branch would show); q_proj x3 on top (logits of "
    "standard deviation 3: a peaked softmax over 4096 keys); the dt "
    "columns of in_proj x1/4; embedding N(0, 1)/embedding_multiplier; "
    "conv1d_weight N(0, 1/4), conv1d_bias N(0, 0.01); norm scales and "
    "D 1 + N(0, 0.01); exp(A_log) uniform in [1, 16]; dt_bias the "
    "inverse softplus of dt log-uniform in [0.001, 0.1] (Mamba-2's own "
    "initialisation); all rounded to bfloat16 values")


def _int8(x):
    """``x`` held in int8 with one scale for the whole tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / scale) * scale


#: what a control may hold the matrix products' operands in
OPERANDS = {"int8": _int8}


def widths(config: Dict[str, Any]) -> Dict[str, int]:
    d_ssm = config["mamba_d_ssm"]
    bc = config["mamba_n_groups"] * config["mamba_d_state"]
    return {"q": config["num_attention_heads"] * config["head_dim"],
            "kv": config["num_key_value_heads"] * config["head_dim"],
            "bc": bc, "conv": d_ssm + 2 * bc,
            "in_proj": 2 * d_ssm + 2 * bc + config["mamba_n_heads"]}


def matrices(config: Dict[str, Any]) -> Dict[str, tuple]:
    """The matrices of one block, ``[in, out]``, by published name."""
    d, ff, w = config["hidden_size"], config["intermediate_size"], widths(config)
    return {"q_proj": (d, w["q"]), "k_proj": (d, w["kv"]),
            "v_proj": (d, w["kv"]), "o_proj": (w["q"], d),
            "in_proj": (d, w["in_proj"]),
            "out_proj": (config["mamba_d_ssm"], d),
            "gate_proj": (d, ff), "up_proj": (d, ff), "down_proj": (ff, d)}


def _section_multipliers(config: Dict[str, Any]) -> np.ndarray:
    """``ssm_multipliers`` spread over in_proj's columns: gate, x, B, C,
    dt, in that order."""
    w = widths(config)
    d_ssm = config["mamba_d_ssm"]
    sections = (d_ssm, d_ssm, w["bc"], w["bc"], config["mamba_n_heads"])
    return np.concatenate([np.full(n, m, np.float32) for n, m in
                           zip(sections, config["ssm_multipliers"])])


def _draw_scales(config: Dict[str, Any]) -> Dict[str, Any]:
    """Standard deviation of each matrix's draw (``DRAW``); a vector
    where it differs by column."""
    c = config
    per_column = (c["ssm_in_multiplier"] * _section_multipliers(c))
    per_column[-c["mamba_n_heads"]:] *= 4.0              # dt: a quarter
    gain = {"q_proj": c["attention_in_multiplier"] / 3.0,
            "k_proj": c["attention_in_multiplier"] * c["key_multiplier"],
            "v_proj": c["attention_in_multiplier"],
            "o_proj": c["attention_out_multiplier"],
            "in_proj": per_column,
            "out_proj": c["ssm_out_multiplier"],
            "gate_proj": c["mlp_multipliers"][0], "up_proj": 1.0,
            "down_proj": c["mlp_multipliers"][1]}
    return {name: 1.0 / (shape[0] ** 0.5 * gain[name])
            for name, shape in matrices(c).items()}


def _key(seed: int, *path: int):
    # a seed a little over 2**31: its high and low halves, folded in apart
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    for p in path:
        key = jax.random.fold_in(key, p)
    return key


def vectors(config: Dict[str, Any]) -> Dict[str, tuple]:
    """What a block holds beside its matrices, by published name."""
    d, heads, w = config["hidden_size"], config["mamba_n_heads"], widths(config)
    return {"input_layernorm": (d,), "pre_ff_layernorm": (d,),
            "mamba_norm": (config["mamba_d_ssm"],), "D": (heads,),
            "conv1d_weight": (config["mamba_d_conv"], w["conv"]),
            "conv1d_bias": (w["conv"],), "A_log": (heads,),
            "dt_bias": (heads,)}


@functools.lru_cache(maxsize=64)
def _leaf_drawer(config_json: str, name: str):
    """``key -> the weight called name`` of one block (bfloat16, on the
    device; ``DRAW``), jitted once a configuration and name."""
    config = json.loads(config_json)
    shapes = matrices(config)
    shape = {**shapes, **vectors(config)}[name]
    normal = functools.partial(jax.random.normal, shape=shape,
                               dtype=jnp.float32)

    @jax.jit
    def draw(key):
        if name in shapes:
            v = normal(key) * jnp.asarray(_draw_scales(config)[name],
                                          jnp.float32)
        elif name == "A_log":
            v = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1., 16.))
        elif name == "dt_bias":
            v = jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))))
        elif name == "conv1d_weight":
            v = normal(key) / shape[0] ** 0.5
        elif name == "conv1d_bias":
            v = 0.1 * normal(key)
        else:                                       # norm scales, D
            v = 1.0 + 0.1 * normal(key)
        return v.astype(jnp.bfloat16)

    return draw


@functools.lru_cache(maxsize=4)
def _embedding_drawer(config_json: str):
    config = json.loads(config_json)
    v, d = config["vocab_size"], config["hidden_size"]
    parts = 8 if v % 8 == 0 and v >= 8192 else 1     # 0.7 GB of float32 a part

    def part(key):
        return (jax.random.normal(key, (v // parts, d), jnp.float32)
                / config["embedding_multiplier"]).astype(jnp.bfloat16)

    return jax.jit(lambda key: lax.map(
        part, jax.random.split(key, parts)).reshape(v, d))


class Weights(NamedTuple):
    """The configuration's weights as a rule, not as arrays: every call
    draws the named weight anew on the device from the seed (bfloat16
    values, ``DRAW``), the same numbers every time.  So the program is
    given one copy, and the reference draws a block when it reaches it:
    nothing of 7.8 GB crosses to the host and back."""
    config: Dict[str, Any]
    seed: int

    def _json(self) -> str:
        return json.dumps(self.config, sort_keys=True)

    def names(self) -> List[str]:
        return sorted({**matrices(self.config), **vectors(self.config)})

    def leaf(self, block: int, name: str) -> jnp.ndarray:
        return _leaf_drawer(self._json(), name)(
            _key(self.seed, 1, block, self.names().index(name)))

    def block(self, index: int) -> Dict[str, jnp.ndarray]:
        return {name: self.leaf(index, name) for name in self.names()}

    def embedding(self) -> jnp.ndarray:
        return _embedding_drawer(self._json())(_key(self.seed, 2))

    def final_layernorm(self) -> jnp.ndarray:
        return (1.0 + 0.1 * jax.random.normal(
            _key(self.seed, 3), (self.config["hidden_size"],),
            jnp.float32)).astype(jnp.bfloat16)


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


def block_row(config: Dict[str, Any], w: Dict[str, jnp.ndarray], x,
              operands: Optional[str] = None):
    """One block over one row ``x`` ``[T, D]`` float32; ``w`` float32.
    Returns the new ``x`` and the root mean squares of the three
    branches (attention, mixer, MLP) for the check of the draw."""
    c = config
    hold = OPERANDS[operands] if operands else (lambda v: v)

    def product(subscripts, a, b):
        return jnp.einsum(subscripts, hold(a), hold(b), precision=HIGHEST)

    t = x.shape[0]
    eps = c["rms_norm_eps"]
    heads, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                     c["head_dim"])
    h = rms_norm(x, w["input_layernorm"], eps)

    # attention: every query head against the key/value head it shares
    ha = c["attention_in_multiplier"] * h
    q = product("td,de->te", ha, w["q_proj"]).reshape(t, heads, hd)
    k = (c["key_multiplier"]
         * product("td,de->te", ha, w["k_proj"])).reshape(t, kv, hd)
    v = product("td,de->te", ha, w["v_proj"]).reshape(t, kv, hd)
    q, k = rotary(q, c["rope_theta"]), rotary(k, c["rope_theta"])
    k, v = (jnp.repeat(u, heads // kv, axis=1) for u in (k, v))
    scores = product("qhd,khd->hqk", q, k) / np.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    mixed = product("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    a = c["attention_out_multiplier"] * product(
        "te,ed->td", mixed.reshape(t, heads * hd), w["o_proj"])

    # mixer
    d_ssm, groups, state = c["mamba_d_ssm"], c["mamba_n_groups"], c["mamba_d_state"]
    mh, mp = c["mamba_n_heads"], c["mamba_d_head"]
    zxbcdt = _section_multipliers(c) * product(
        "td,de->te", c["ssm_in_multiplier"] * h, w["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [d_ssm, zxbcdt.shape[1] - mh], axis=1)
    width = c["mamba_d_conv"]
    padded = jnp.concatenate([jnp.zeros((width - 1, xbc.shape[1])), xbc])
    conv = w["conv1d_bias"] + sum(w["conv1d_weight"][j] * padded[j:j + t]
                                  for j in range(width))
    xs, b, cc = jnp.split(jax.nn.silu(conv), [d_ssm, d_ssm + groups * state],
                          axis=1)
    xs = xs.reshape(t, mh, mp)
    b = jnp.repeat(b.reshape(t, groups, state), mh // groups, axis=1)
    cc = jnp.repeat(cc.reshape(t, groups, state), mh // groups, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])                   # [T, heads]
    decay = jnp.exp(dt * -jnp.exp(w["A_log"]))

    def token(s, at):                     # s [heads, P, N]: ONE position
        x_t, b_t, c_t, dt_t, decay_t = at
        s = (decay_t[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    _, y = lax.scan(token, jnp.zeros((mh, mp, state)), (xs, b, cc, dt, decay))
    y = (y + w["D"][:, None] * xs).reshape(t, d_ssm)
    gated = (y * jax.nn.silu(z)).reshape(t, groups, d_ssm // groups)
    normed = gated * lax.rsqrt(jnp.mean(gated * gated, -1, keepdims=True) + eps)
    m = c["ssm_out_multiplier"] * product(
        "te,ed->td", normed.reshape(t, d_ssm) * w["mamba_norm"], w["out_proj"])

    x = x + a + m
    h2 = rms_norm(x, w["pre_ff_layernorm"], eps)
    gate = c["mlp_multipliers"][0] * product("td,df->tf", h2, w["gate_proj"])
    up = product("td,df->tf", h2, w["up_proj"])
    mlp = c["mlp_multipliers"][1] * product(
        "tf,fd->td", jax.nn.silu(gate) * up, w["down_proj"])
    rms = lambda u: jnp.sqrt(jnp.mean(u * u))                 # noqa: E731
    return x + mlp, jnp.stack([rms(x - a - m), rms(a), rms(m), rms(mlp)])


def forward(config: Dict[str, Any], weights: Weights, ids: np.ndarray,
            operands: Optional[str] = None, scales: Optional[List] = None
            ) -> np.ndarray:
    """``ids`` ``[rows, T]`` -> features ``[rows, D]`` float32 (numpy).
    One block's weights at a time on the device, as float32, one row at
    a time through it.  ``scales``, a list, is filled with each block's
    root mean squares ``[rows, (x, attention, mixer, mlp)]``."""
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        step = jax.jit(lambda w, x: lax.map(
            functools.partial(block_row, config, w, operands=operands), x))
        x = (jnp.take(weights.embedding(), jnp.asarray(ids), axis=0)
             .astype(f32) * config["embedding_multiplier"])
        for index in range(config["num_hidden_layers"]):
            w = {name: leaf.astype(f32)
                 for name, leaf in weights.block(index).items()}
            x, rms = step(w, x)
            if scales is not None:
                scales.append(np.asarray(rms))
            del w
        f = rms_norm(x, weights.final_layernorm().astype(f32),
                     config["rms_norm_eps"])
        return np.asarray(jnp.mean(f, axis=1))


def flops_per_row(config: Dict[str, Any]) -> int:
    """Operations a row of ``sequence_length`` positions
    (``sequence_flops``): every matrix of every block at every position,
    causal attention, the recurrence."""
    t = config["sequence_length"]
    block = (sf.matmul_flops(matrices(config).values(), t)
             + sf.causal_attention_flops(config["num_attention_heads"],
                                         config["head_dim"], t)
             + sf.scan_flops(config["mamba_n_heads"], config["mamba_d_head"],
                             config["mamba_d_state"], t))
    return config["num_hidden_layers"] * block
