"""A hybrid sequence trunk as a featurizer: rows of token ids in, one
vector a row out.

The block is Falcon-H1's (``config`` holds the keys of its published
``config.json``): under ONE RMSNorm a grouped-query attention and a
Mamba-2 mixer read the same normalised input side by side, their
outputs are added to the residual together, and a gated MLP follows
under a norm of its own::

    h  = RMSNorm(x)
    a  = attention_out_multiplier * W_o attention(rotary(W_q h'),
             rotary(key_multiplier * W_k h'), W_v h'),
             h' = attention_in_multiplier * h
    z|x|B|C|dt = ssm_multipliers (by section) * in_proj(ssm_in_multiplier * h)
    x|B|C = SiLU(causal depthwise conv(x|B|C) + bias)
    y  = scan(x, softplus(dt + dt_bias), -exp(A_log), B, C, D)
    m  = ssm_out_multiplier * out_proj(RMSNorm_by_group(y * SiLU(z)))
    x  = x + a + m
    x  = x + mlp_multipliers[1] * W_down(SiLU(mlp_multipliers[0] * W_gate h2)
                                         * W_up h2),   h2 = RMSNorm(x)

After the last block a final RMSNorm; the row's feature is the mean of
it over the row's positions, float32.  The output head is left off, as
``DeepImageFeaturizer`` leaves the classifier off.

Block weights are stacked on a leading axis and the blocks run under
``lax.scan``: one block is compiled once whatever the depth.  Weights
and matrix-product operands are in the compute dtype (bfloat16 unless
told otherwise) and accumulate in float32; the residual stream, the
norms' statistics, the rotary angles, the convolution's sum,
``softplus``, the scan's decays and its carried state are float32.
Names of weights are the published checkpoint's and matrices are
``[in, out]``; ``gate_proj`` and ``up_proj`` lie side by side in one
matrix, ``gate_up_proj``, so that the MLP's first step is one product.

``ops/ssd`` and ``ops/attention`` are the two parts that are kernels on
the TPU; ``model_function`` gives the trunk to ``ModelTransformer``.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from sparkdl_tpu.ops.attention import causal_attention
from sparkdl_tpu.ops.ssd import ssd_scan

#: every multiplier of the published config that the featurizer applies
#: (``lm_head_multiplier`` scales the head, which is left off)
MULTIPLIERS = ("embedding_multiplier", "attention_in_multiplier",
               "key_multiplier", "attention_out_multiplier",
               "ssm_in_multiplier", "ssm_multipliers", "ssm_out_multiplier",
               "mlp_multipliers")


def sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """The widths the published keys imply."""
    d_ssm, groups = config["mamba_d_ssm"], config["mamba_n_groups"]
    state = config["mamba_d_state"]
    return {
        "q": config["num_attention_heads"] * config["head_dim"],
        "kv": config["num_key_value_heads"] * config["head_dim"],
        "conv": d_ssm + 2 * groups * state,
        "in_proj": 2 * d_ssm + 2 * groups * state + config["mamba_n_heads"],
    }


def block_shapes(config: Dict[str, Any]) -> Dict[str, tuple]:
    """Shape of every weight of one block, by its published name."""
    d, ff, sz = config["hidden_size"], config["intermediate_size"], sizes(config)
    d_ssm, heads = config["mamba_d_ssm"], config["mamba_n_heads"]
    return {
        "input_layernorm": (d,),
        "q_proj": (d, sz["q"]), "k_proj": (d, sz["kv"]),
        "v_proj": (d, sz["kv"]), "o_proj": (sz["q"], d),
        "in_proj": (d, sz["in_proj"]),
        "conv1d_weight": (config["mamba_d_conv"], sz["conv"]),
        "conv1d_bias": (sz["conv"],),
        "dt_bias": (heads,), "A_log": (heads,), "D": (heads,),
        "mamba_norm": (d_ssm,), "out_proj": (d_ssm, d),
        "pre_ff_layernorm": (d,),
        "gate_up_proj": (d, 2 * ff), "down_proj": (ff, d),
    }


def init(config: Dict[str, Any], key, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Random variables in the trunk's tree: ``embedding`` ``[V, D]``,
    ``blocks`` (every weight of ``block_shapes`` with the blocks stacked
    on a leading axis), ``final_layernorm``.  Matrices N(0, 1/fan-in),
    norm scales 1, ``dt_bias`` and ``A_log`` as Mamba-2 draws them."""
    depth, d = config["num_hidden_layers"], config["hidden_size"]
    keys = iter(jax.random.split(key, 32))

    def leaf(name, shape):
        full = (depth,) + shape
        if name.endswith("norm") or name == "D":
            return jnp.ones(full, dtype)
        if name == "conv1d_bias":
            return jnp.zeros(full, dtype)
        if name == "A_log":
            return jnp.log(jax.random.uniform(next(keys), full, jnp.float32,
                                              1.0, 16.0)).astype(dtype)
        if name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(next(keys), full, jnp.float32,
                                            jnp.log(1e-3), jnp.log(1e-1)))
            return jnp.log(jnp.expm1(dt)).astype(dtype)
        return (jax.random.normal(next(keys), full, jnp.float32)
                / shape[0] ** 0.5).astype(dtype)

    return {
        "embedding": jax.random.normal(
            next(keys), (config["vocab_size"], d), jnp.float32).astype(dtype),
        "blocks": {n: leaf(n, s) for n, s in block_shapes(config).items()},
        "final_layernorm": jnp.ones((d,), dtype),
    }


def stack_blocks(leaf: Callable[[int, str], Any], depth: int
                 ) -> Dict[str, Any]:
    """The trunk's ``blocks`` from ``leaf(block, published name)``, built
    one weight at a time (never two copies of more than the weight in
    hand): the blocks stacked on a leading axis, ``gate_proj`` and
    ``up_proj`` side by side as ``gate_up_proj``."""
    @functools.partial(jax.jit, donate_argnums=())   # pieces cannot alias
    def joined(pieces):
        # one program: eager ``jnp.stack`` would copy every piece once
        # more (its ``expand_dims``) before the copy into the result
        return jnp.stack([jnp.concatenate(p, axis=-1) for p in pieces])

    def stacked(*names):
        # waited for, so that the pieces of one weight are gone before
        # the next is drawn (dispatch runs ahead of the device otherwise)
        return joined([[leaf(i, n) for n in names]
                       for i in range(depth)]).block_until_ready()

    return {name: (stacked("gate_proj", "up_proj") if name == "gate_up_proj"
                   else stacked(name))
            for name in ("gate_up_proj", "down_proj", "in_proj", "out_proj",
                         "q_proj", "o_proj", "k_proj", "v_proj",
                         "conv1d_weight", "conv1d_bias", "input_layernorm",
                         "pre_ff_layernorm", "mamba_norm", "dt_bias", "A_log",
                         "D")}


def _rms_norm(x, scale, eps: float, groups: int = 1):
    """RMSNorm in float32 with a learned scale; with ``groups`` the
    statistics are taken over each group of the last axis apart (by
    slices of the axis: a ``[..., groups, width]`` view would be a
    relayout of the whole array on the chip)."""
    x = x.astype(jnp.float32)
    parts = [part * lax.rsqrt(jnp.mean(part * part, axis=-1, keepdims=True)
                              + eps)
             for part in jnp.split(x, groups, axis=-1)]
    return jnp.concatenate(parts, axis=-1) * scale.astype(jnp.float32)


def _rotary(x, heads: int, theta: float):
    """Rotary position over the whole head, halves paired (the
    ``rotate_half`` form), angles in float32.  ``x`` ``[R, T, heads*hd]``
    float32."""
    r, t, width = x.shape
    hd = width // heads
    inv = float(theta) ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    xh = x.reshape(r, t, heads, hd)
    lo, hi = xh[..., :hd // 2], xh[..., hd // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           axis=-1).reshape(r, t, width)


def _causal_conv(x, scale, weight, bias):
    """Depthwise causal convolution along the positions of ``scale * x``,
    summed in float32: ``y_t = bias + scale * sum_k weight[k] *
    x_{t - (K-1) + k}`` (a multiplier a channel commutes with a
    convolution a channel, so ``x`` is read as it is stored)."""
    k, t = weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    weight = weight.astype(jnp.float32)
    return bias.astype(jnp.float32) + scale * sum(
        weight[i] * padded[:, i:i + t].astype(jnp.float32) for i in range(k))


def _block(config: Dict[str, Any], x, w, dtype, precision):
    """One block over the residual stream ``x`` ``[R, T, D]`` float32."""
    f32 = jnp.float32
    eps = config["rms_norm_eps"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    d_ssm, groups, state = (config["mamba_d_ssm"], config["mamba_n_groups"],
                            config["mamba_d_state"])
    m_heads, m_head = config["mamba_n_heads"], config["mamba_d_head"]
    r, t, _ = x.shape

    def mm(a, name, out=f32):
        return jnp.dot(a.astype(dtype), w[name], precision=precision,
                       preferred_element_type=f32).astype(out)

    h = _rms_norm(x, w["input_layernorm"], eps)

    with jax.named_scope("attention"):
        ha = h * config["attention_in_multiplier"]
        q = _rotary(mm(ha, "q_proj"), heads, config["rope_theta"])
        k = _rotary(mm(ha, "k_proj") * config["key_multiplier"], kv_heads,
                    config["rope_theta"])
        q = q * config["head_dim"] ** -0.5
        a = causal_attention(q.astype(dtype), k.astype(dtype),
                             mm(ha, "v_proj", dtype), heads=heads,
                             kv_heads=kv_heads, precision=precision)
        a = mm(a, "o_proj") * config["attention_out_multiplier"]

    with jax.named_scope("mixer_proj"):
        # in_proj's 9248 columns leave the product in the compute dtype;
        # the multipliers of its sections are applied in float32 where
        # each section is read
        sections = (d_ssm, d_ssm, groups * state, groups * state, m_heads)
        by_section = jnp.concatenate([
            jnp.full((n,), m, f32)
            for n, m in zip(sections, config["ssm_multipliers"])])
        zxbcdt = mm(h * config["ssm_in_multiplier"], "in_proj", dtype)
        z = zxbcdt[..., :d_ssm].astype(f32) * by_section[:d_ssm]
        dt_raw = zxbcdt[..., -m_heads:].astype(f32) * by_section[-m_heads:]
    with jax.named_scope("mixer_conv"):
        xbc = jax.nn.silu(_causal_conv(
            zxbcdt[..., d_ssm:-m_heads], by_section[d_ssm:-m_heads],
            w["conv1d_weight"], w["conv1d_bias"]))
        xs = xbc[..., :d_ssm].astype(dtype).reshape(r, t, m_heads, m_head)
        b, c = (v.astype(dtype).reshape(r, t, groups, state)
                for v in jnp.split(xbc[..., d_ssm:], 2, axis=-1))
        dt = jax.nn.softplus(dt_raw + w["dt_bias"].astype(f32))
    with jax.named_scope("mixer_scan"):
        y = ssd_scan(xs, dt, -jnp.exp(w["A_log"].astype(f32)), b, c,
                     w["D"].astype(f32), chunk=min(config["mamba_chunk_size"], t),
                     precision=precision)
    with jax.named_scope("mixer_norm"):
        # mamba_norm_before_gate is false: the gate first, then the norm
        gated = y.reshape(r, t, d_ssm).astype(f32) * jax.nn.silu(z)
        m = mm(_rms_norm(gated, w["mamba_norm"], eps, groups), "out_proj")
        m = m * config["ssm_out_multiplier"]

    x = x + a + m
    with jax.named_scope("mlp"):
        h2 = _rms_norm(x, w["pre_ff_layernorm"], eps)
        # the widest array of the block leaves its product in the compute
        # dtype; the gate's arithmetic is float32 all the same
        gate, up = jnp.split(mm(h2, "gate_up_proj", dtype), 2, axis=-1)
        act = (jax.nn.silu(gate.astype(f32) * config["mlp_multipliers"][0])
               * up.astype(f32)).astype(dtype)
        x = x + mm(act, "down_proj") * config["mlp_multipliers"][1]
    return x


def apply(variables: Dict[str, Any], ids, config: Dict[str, Any], *,
          precision=None):
    """``ids`` ``[R, T]`` integers -> features ``[R, D]`` float32.  The
    compute dtype is the weights' own.  An id outside the vocabulary
    raises nothing under ``jit`` (the gather clamps it): a caller whose
    ids come from outside checks them on the host."""
    dtype = variables["embedding"].dtype
    with jax.named_scope("embed"):
        x = (jnp.take(variables["embedding"], ids.astype(jnp.int32), axis=0)
             .astype(jnp.float32) * config["embedding_multiplier"])

    def step(x, w):
        return _block(config, x, w, dtype, precision), None

    x, _ = lax.scan(step, x, variables["blocks"])
    with jax.named_scope("pool"):
        return jnp.mean(_rms_norm(x, variables["final_layernorm"],
                                  config["rms_norm_eps"]), axis=1)


def model_function(config: Dict[str, Any], variables: Dict[str, Any], *,
                   compute_dtype: Optional[str] = None,
                   matmul_precision: Optional[str] = None):
    """The trunk as a ``ModelFunction`` for ``ModelTransformer`` over an
    integer list column.  The compute dtype is the weights' own: a leaf
    that is already of ``compute_dtype`` (or every leaf, where none is
    named) is used as it is given, so weights placed on the device stay
    the one copy.  ``matmul_precision`` ``"highest"`` is for float32
    parity runs, ``None``/``"default"`` the chip's default."""
    from sparkdl_tpu.graph.function import ModelFunction

    config = dict(config)
    if compute_dtype is not None:
        variables = jax.tree_util.tree_map(
            lambda leaf: leaf if leaf.dtype == jnp.dtype(compute_dtype)
            else leaf.astype(compute_dtype), variables)
    precision = (None if matmul_precision in (None, "default")
                 else lax.Precision(matmul_precision))

    def fn(v, ids):
        return apply(v, ids, config, precision=precision)

    return ModelFunction(fn=fn, variables=variables, input_names=("ids",),
                         output_names=("features",))
