"""A latent-attention trunk with learned sparse attention and sparse
experts as a featurizer: rows of token ids in, one vector a row out.

The layer is DeepSeek-V3.2-Exp's (``model_type: deepseek_v32``;
``config`` holds the keys of its published ``config.json``): latent
attention (MLA) whose keys a learned indexer selects (DSA: the top
``index_topk`` of each query), then a gated MLP that is dense in the
first ``first_k_dense_replace`` layers and, in the others, a shared
expert beside routed experts chosen by group-limited sigmoid routing
(every ``N`` an RMSNorm with a learned scale, eps ``rms_norm_eps``)::

    x    = E[ids]
    h    = N_attn(x)
    c_q  = N_q(W_qa h)                                       [q_lora_rank]
    q    = W_qb c_q -> per head [q_nope | q_pe];  q_pe = rope(q_pe)
    [c_kv | k_pe] = W_kva h;  c_kv = N_kv(c_kv);  k_pe = rope(k_pe)
                                                  (one k_pe for all heads)
    k_nope, v = W_kvb c_kv -> per head
    -- the lightning indexer (index_n_heads x index_head_dim) --
    q_I  = W_Iq c_q, rope on its first qk_rope_head_dim
    k_I  = LayerNorm(W_Ik h), rope on its first qk_rope_head_dim
    w    = W_Iw h * index_n_heads^-1/2 * index_head_dim^-1/2
    I(t, s) = sum_j w[t, j] ReLU(q_I[t, j] . k_I[s])          for s <= t
    S(t) = the index_topk keys s <= t of largest I(t, s), ties to the
           lower position (all s <= t while t < index_topk)
    -- latent attention over S(t) --
    a_h(t) = softmax over s in S(t) of (q_nope.k_nope + q_pe.k_pe)
             * qk_head_dim^-1/2 * m^2, of v    (m: YaRN's, 0.1 ln(factor) + 1)
    x    = x + W_o [a_1 ... a_H]
    h2   = N_ffn(x)
    m    = W_down(SiLU(W_gate h2) * W_up h2)                   dense layers
    m    = shared(h2) + sum over e in top-k, e held here, of w_e expert_e(h2)
           s = sigmoid(W_r h2) float32;  b = e_score_correction_bias
           (chooses only); the n_group groups score the sum of their two
           largest s + b, the topk_group best are kept, and the top-k of
           s + b among them chosen;  w = s[top-k] / sum (norm_topk_prob)
           * routed_scaling_factor
    x    = x + m

After the last layer a final RMSNorm; the row's feature is the mean of
it over the row's positions, float32.  The output head and the
multi-token-prediction module are left off.  The rotary position is
YaRN's (``rope_scaling``), adjacent pairs turned in the attention, the
halves of the first ``qk_rope_head_dim`` paired in the indexer.

**Where the work goes** (``ops/sparse_attention``): the indexer's scores,
the exact selection and the attention over the selected keys are three
Pallas kernels on the TPU.  A row's queries are worked a chunk of
``QUERY_CHUNK`` at a time (all of its heads' absorbed queries would not
fit the chip at 32,768 positions), every chunk against ALL the row's
keys, which a layer makes once: ``k_nope`` and ``v`` heads first, the
indexer's keys, ``k_pe``.  The dense MLP is worked ``TOKEN_CHUNK``
tokens at a time.  The routed experts are ``expert_trunk``'s
(``_route``, ``_held_experts``): the layer holds ``n_routed_experts``
of ``n_routed_experts * expert_share[1]``, the router scores them all,
and what the other chips' experts would add is left out.

All layers run under ONE ``lax.scan`` (the MLP's kind picks by
``lax.cond``), so every kernel is one instruction of the program
whatever the depth.  Weights and matrix-product operands are in the
compute dtype (bfloat16 unless told otherwise) and accumulate in
float32; the residual stream, the norms, the rotary position, the
indexer's weights and scores, softmax, the router's product (at
``highest``) with its sigmoid and the routing weights are float32.
Beside the features the program returns two COUNTERS
(``ModelFunction.counter_names``): ``expert_load`` ``[rows, expert
layers, held]`` as ``expert_trunk``'s, and ``sparse_counts`` ``[rows,
layers, 4]`` int32: the causal pairs the indexer scored, the pairs
selected, the key rows the attention read, and the queries whose
selection needed the tie passes (``SPARSE_COUNTS``).
Names of weights are the published checkpoint's and matrices ``[in,
out]``; ``gate_proj`` and ``up_proj`` lie side by side
(``gate_up_proj``), a layer's routed experts stacked on a leading axis.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from sparkdl_tpu.models import expert_trunk
from sparkdl_tpu.ops import sparse_attention as sa

#: the program's outputs that are counters, not columns
COUNTERS = ("expert_load", "sparse_counts")
#: what ``sparse_counts`` holds a row and a layer, in order
SPARSE_COUNTS = ("indexed_pairs", "selected_pairs", "fetched_keys",
                 "tied_queries")
#: a row's queries worked together (scores [chunk, T] float32: 0.5 GB at
#: 4096 x 32,768)
QUERY_CHUNK = 4096
#: tokens the dense MLP works together ([chunk, 2 x 18,432] bfloat16: 0.6 GB)
TOKEN_CHUNK = 8192


def routing(config: Dict[str, Any]) -> Dict[str, Any]:
    """The keys ``expert_trunk``'s routing reads, from the published ones."""
    return {"num_experts": config["n_routed_experts"],
            "expert_share": list(config["expert_share"]),
            "num_experts_per_tok": config["num_experts_per_tok"],
            "n_group": config["n_group"], "topk_group": config["topk_group"],
            "route_norm": config["norm_topk_prob"],
            "route_scale": config["routed_scaling_factor"]}


def layer_shapes(config: Dict[str, Any]) -> Dict[str, Dict[str, tuple]]:
    """Shape of every weight of one layer, by kind (``layers``: what
    every layer has; ``dense``; ``experts``) and published name."""
    c = config
    d, heads = c["hidden_size"], c["num_attention_heads"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    ql, kl = c["q_lora_rank"], c["kv_lora_rank"]
    ih, idim = c["index_n_heads"], c["index_head_dim"]
    ff, f = c["intermediate_size"], c["moe_intermediate_size"]
    fs, held = f * c["n_shared_experts"], c["n_routed_experts"]
    routed = held * c["expert_share"][1]
    return {
        "layers": {
            "input_layernorm": (d,), "post_attention_layernorm": (d,),
            "self_attn.q_a_proj": (d, ql), "self_attn.q_a_layernorm": (ql,),
            "self_attn.q_b_proj": (ql, heads * (nope + rope)),
            "self_attn.kv_a_proj_with_mqa": (d, kl + rope),
            "self_attn.kv_a_layernorm": (kl,),
            "self_attn.kv_b_proj": (kl, heads * (nope + vd)),
            "self_attn.o_proj": (heads * vd, d),
            "self_attn.indexer.wq_b": (ql, ih * idim),
            "self_attn.indexer.wk": (d, idim),
            "self_attn.indexer.k_norm.weight": (idim,),
            "self_attn.indexer.k_norm.bias": (idim,),
            "self_attn.indexer.weights_proj": (d, ih)},
        "dense": {"mlp.gate_up_proj": (d, 2 * ff), "mlp.down_proj": (ff, d)},
        "experts": {
            "mlp.gate.weight": (d, routed),
            "mlp.gate.e_score_correction_bias": (routed,),
            "mlp.shared_experts.gate_up_proj": (d, 2 * fs),
            "mlp.shared_experts.down_proj": (fs, d),
            "mlp.experts.gate_up_proj": (held, d, 2 * f),
            "mlp.experts.down_proj": (held, f, d)}}


def _counts(config: Dict[str, Any]) -> Dict[str, int]:
    depth, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    return {"layers": depth, "dense": dense, "experts": depth - dense}


def init(config: Dict[str, Any], key, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Random variables in the trunk's tree (``layers`` / ``dense`` /
    ``experts`` stacked by kind, the routed experts of all expert layers
    on ONE axis), ``embedding`` and ``final_layernorm``: matrices
    N(0, 1/fan-in), norm scales 1, biases 0."""
    count = _counts(config)
    keys = iter(jax.random.split(key, 64))

    def leaf(kind, name, shape):
        full = (count[kind],) + shape
        if name.startswith("mlp.experts."):
            full = (count[kind] * shape[0],) + shape[1:]
        if name.endswith("norm") or name.endswith("norm.weight"):
            return jnp.ones(full, dtype)
        if name.endswith("bias"):
            return jnp.zeros(full, jnp.float32 if "gate" in name else dtype)
        return (jax.random.normal(next(keys), full, jnp.float32)
                / shape[-2] ** 0.5).astype(dtype)

    variables = {kind: {n: leaf(kind, n, s) for n, s in shapes.items()}
                 for kind, shapes in layer_shapes(config).items()}
    variables["embedding"] = (jax.random.normal(
        next(keys), (config["vocab_size"], config["hidden_size"]),
        jnp.float32) / config["hidden_size"] ** 0.5).astype(dtype)
    variables["final_layernorm"] = jnp.ones((config["hidden_size"],), dtype)
    return variables


def stack_layers(leaf: Callable[[int, str], Any], config: Dict[str, Any]
                 ) -> Dict[str, Dict[str, Any]]:
    """The trunk's ``layers``, ``dense`` and ``experts`` from ``leaf(layer,
    published name)`` (``gate_proj`` and ``up_proj`` apart, a layer's
    routed experts ``mlp.experts.gate_proj`` ``[held, D, F]`` and so on),
    ``leaf`` traced: ONE program makes every stack, so set-up compiles
    (or loads) one program, not one a weight and a layer.  Every stack is
    written a layer at a time into its place in the one array that holds
    it, which the compiler updates in place: set-up holds no second
    copy (``tests/test_tpu_compile.py`` reads it at published widths)."""
    count = _counts(config)
    first = {"layers": 0, "dense": 0, "experts": count["dense"]}

    def filled(kind, name, *pieces):
        flat = name.startswith("mlp.experts.")       # [held, ...] a layer
        stack = None
        for i in range(count[kind]):
            piece = jnp.concatenate([leaf(first[kind] + i, p)
                                     for p in pieces], axis=-1)
            if not flat:
                piece = piece[None]
            if stack is None:
                stack = jnp.zeros((count[kind] * piece.shape[0],)
                                  + piece.shape[1:], piece.dtype)
            stack = lax.dynamic_update_slice_in_dim(
                stack, piece, i * piece.shape[0], axis=0)
        return stack

    def pieces(name):
        if name.endswith("gate_up_proj"):
            prefix = name[:-len("gate_up_proj")]
            return (prefix + "gate_proj", prefix + "up_proj")
        return (name,)

    return jax.jit(lambda: {
        kind: {name: filled(kind, name, *pieces(name)) for name in shapes}
        for kind, shapes in layer_shapes(config).items() if count[kind]},
        donate_argnums=())()


# -- the pieces of a layer -----------------------------------------------------

def yarn_inverse_frequencies(config: Dict[str, Any]) -> np.ndarray:
    """The rotary's ``qk_rope_head_dim / 2`` inverse frequencies under
    YaRN (``rope_scaling``; applied since ``max_position_embeddings``
    exceeds ``original_max_position_embeddings``): the high frequencies
    as they are, the low ones divided by ``factor``, a linear ramp
    between the dimensions that turn ``beta_fast`` and ``beta_slow``
    times over the original length."""
    dim, base = config["qk_rope_head_dim"], float(config["rope_theta"])
    freq = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    y = config.get("rope_scaling")
    if not y or config["max_position_embeddings"] <= \
            y["original_max_position_embeddings"]:
        return freq.astype(np.float32)

    def turns_at(rotations):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(turns_at(y["beta_fast"])), 0)
    high = min(math.ceil(turns_at(y["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001), 0, 1)
    smooth = 1.0 - ramp
    return (freq / y["factor"] * (1 - smooth) + freq * smooth).astype(np.float32)


def softmax_scale(config: Dict[str, Any]) -> float:
    """``qk_head_dim^-1/2``, times YaRN's ``m^2`` where it applies."""
    scale = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5
    y = config.get("rope_scaling")
    if y and config["max_position_embeddings"] > \
            y["original_max_position_embeddings"]:
        m = 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0
        scale *= m * m
    return scale


def _angles(config, positions):
    """``[len(positions), dr/2]`` float32 angles."""
    return (positions.astype(jnp.float32)[:, None]
            * jnp.asarray(yarn_inverse_frequencies(config))[None, :])


def _turn(x, angle, interleaved: bool):
    """The rotary turn of ``x`` ``[T, ..., dr]`` by ``angle`` ``[T, dr/2]``:
    adjacent pairs (``interleaved``) or the two halves paired."""
    shape = (angle.shape[0],) + (1,) * (x.ndim - 2) + (angle.shape[1],)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    if interleaved:
        pairs = x.reshape(x.shape[:-1] + (-1, 2))
        a, b = pairs[..., 0], pairs[..., 1]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _layer_norm(x, scale, bias, eps: float):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            + bias.astype(jnp.float32))


def index_keys(config: Dict[str, Any], h, w, dtype, precision):
    """The indexer's keys ``[T, index_head_dim]`` of ``h`` ``[T, D]``:
    LayerNorm, then the rotary turn of the first ``qk_rope_head_dim``."""
    rope = config["qk_rope_head_dim"]
    k_i = _layer_norm(jnp.dot(h.astype(dtype), w["self_attn.indexer.wk"],
                              precision=precision,
                              preferred_element_type=jnp.float32),
                      w["self_attn.indexer.k_norm.weight"],
                      w["self_attn.indexer.k_norm.bias"],
                      config["rms_norm_eps"])
    at = jnp.arange(h.shape[0], dtype=jnp.int32)
    return jnp.concatenate([_turn(k_i[:, :rope], _angles(config, at), False),
                            k_i[:, rope:]], axis=-1).astype(dtype)


def index_queries(config: Dict[str, Any], h, c_q, at, w, dtype, precision):
    """The indexer's queries ``[Q, heads, dim]`` (their first
    ``qk_rope_head_dim`` turned) and head weights ``[Q, heads]`` float32
    of positions ``at``."""
    c = config
    ih, idim = c["index_n_heads"], c["index_head_dim"]
    rope = c["qk_rope_head_dim"]

    def mm(a, name):
        return jnp.dot(a.astype(dtype), w[name], precision=precision,
                       preferred_element_type=jnp.float32)

    q_i = mm(c_q, "self_attn.indexer.wq_b").reshape(-1, ih, idim)
    q_i = jnp.concatenate([_turn(q_i[..., :rope], _angles(c, at), False),
                           q_i[..., rope:]], axis=-1).astype(dtype)
    return q_i, mm(h, "self_attn.indexer.weights_proj") * (
        ih ** -0.5 * idim ** -0.5)


def attention_row(config: Dict[str, Any], x, w, dtype, precision,
                  query_chunk: int = QUERY_CHUNK):
    """``x`` ``[T, D]`` float32 (one row) plus the layer's attention
    branch, and the row's ``[4]`` int32 counts (``SPARSE_COUNTS``)."""
    c, f32, i32 = config, jnp.float32, jnp.int32
    eps, t = c["rms_norm_eps"], x.shape[0]
    heads, nope, rope = (c["num_attention_heads"], c["qk_nope_head_dim"],
                         c["qk_rope_head_dim"])
    vd, kl, top = c["v_head_dim"], c["kv_lora_rank"], c["index_topk"]
    chunk = min(query_chunk, t)
    if t % chunk:
        raise ValueError(f"{t} positions are no multiple of {chunk}")
    rms = expert_trunk._rms_norm

    def mm(a, name):
        return jnp.dot(a.astype(dtype), w[name], precision=precision,
                       preferred_element_type=f32)

    h = rms(x, w["input_layernorm"], eps).astype(dtype)
    every = jnp.arange(t, dtype=i32)
    with jax.named_scope("latent_keys"):
        kv = mm(h, "self_attn.kv_a_proj_with_mqa")
        c_kv = rms(kv[:, :kl], w["self_attn.kv_a_layernorm"], eps).astype(dtype)
        k_pe = _turn(kv[:, kl:], _angles(c, every), True).astype(dtype)
        kv_b = w["self_attn.kv_b_proj"].reshape(kl, heads, nope + vd)
        k_nope = jnp.einsum("tc,chd->htd", c_kv, kv_b[..., :nope],
                            precision=precision,
                            preferred_element_type=f32).astype(dtype)
        v = jnp.einsum("tc,chd->htd", c_kv, kv_b[..., nope:],
                       precision=precision,
                       preferred_element_type=f32).astype(dtype)
    with jax.named_scope("indexer"):
        k_i = index_keys(c, h, w, dtype, precision)
    scale = softmax_scale(c)
    o_proj = w["self_attn.o_proj"].reshape(heads, vd, -1)

    def one_chunk(n, state):
        x, counts = state
        first = n * chunk
        at = first + jnp.arange(chunk, dtype=i32)
        h_c = lax.dynamic_slice_in_dim(h, first, chunk)
        angle = _angles(c, at)
        with jax.named_scope("queries"):
            c_q = rms(mm(h_c, "self_attn.q_a_proj"),
                      w["self_attn.q_a_layernorm"], eps).astype(dtype)
            q = mm(c_q, "self_attn.q_b_proj").reshape(chunk, heads, nope + rope)
            q_nope = jnp.swapaxes(q[..., :nope] * scale, 0, 1).astype(dtype)
            q_pe = jnp.swapaxes(_turn(q[..., nope:], angle, True) * scale,
                                0, 1).astype(dtype)
        with jax.named_scope("indexer"):
            q_i, weight = index_queries(c, h_c, c_q, at, w, dtype, precision)
            scores = sa.indexer_scores(q_i, k_i, weight, first,
                                       precision=precision)
        with jax.named_scope("topk_select"):
            sel = sa.topk_select(scores, first, top)
        with jax.named_scope("sparse_attention"):
            a = sa.sparse_attention(q_nope, q_pe, k_nope, v, k_pe, scores,
                                    sel, first, precision=precision)
            out = jnp.einsum("hqd,hde->qe", a, o_proj, precision=precision,
                             preferred_element_type=f32)
        x = lax.dynamic_update_slice_in_dim(
            x, lax.dynamic_slice_in_dim(x, first, chunk) + out, first, 0)
        counts = counts + jnp.stack([
            sa.indexed_pairs(first, chunk), sa.selected_pairs(first, chunk, top),
            sa.fetched_keys(first, chunk, t),
            sa.tied_queries(sel, first)]).astype(i32)
        return x, counts

    return lax.fori_loop(0, t // chunk, one_chunk,
                         (x, jnp.zeros((len(SPARSE_COUNTS),), i32)))


def apply(variables: Dict[str, Any], ids, config: Dict[str, Any], *,
          precision=None, query_chunk: int = QUERY_CHUNK,
          token_chunk: int = TOKEN_CHUNK):
    """``ids`` ``[R, T]`` integers -> ``{"features": [R, D] float32,
    "expert_load": [R, expert layers, held] int32, "sparse_counts": [R,
    layers, 4] int32}``.  The compute dtype is the weights' own."""
    c = config
    f32, i32 = jnp.float32, jnp.int32
    dtype = variables["embedding"].dtype
    eps = c["rms_norm_eps"]
    count = _counts(c)
    depth, dense = count["layers"], count["dense"]
    held = c["n_routed_experts"]
    route = routing(c)
    r, t = ids.shape
    rms = expert_trunk._rms_norm
    with jax.named_scope("embed"):
        x = jnp.take(variables["embedding"], ids.astype(i32),
                     axis=0).astype(f32)

    def dense_mlp(x, w, index):
        stacks = {name: lax.dynamic_index_in_dim(leaf, index, keepdims=False)
                  for name, leaf in variables["dense"].items()}
        flat = x.reshape(r * t, -1)
        size = min(token_chunk, r * t)

        def one_chunk(n, flat):
            part = lax.dynamic_slice_in_dim(flat, n * size, size)
            with jax.named_scope("dense_mlp"):
                m = expert_trunk._gated_mlp(
                    rms(part, w["post_attention_layernorm"], eps),
                    stacks["mlp.gate_up_proj"], stacks["mlp.down_proj"],
                    dtype, precision)
            return lax.dynamic_update_slice_in_dim(flat, part + m, n * size, 0)

        flat = lax.fori_loop(0, r * t // size, one_chunk, flat)
        return flat.reshape(r, t, -1), jnp.zeros((r, held), i32)

    def expert_mlp(x, w, index):
        layer = index - dense
        stacks = variables["experts"]
        own = {name: lax.dynamic_index_in_dim(leaf, layer, keepdims=False)
               for name, leaf in stacks.items()
               if not name.startswith("mlp.experts.")}
        h2 = rms(x, w["post_attention_layernorm"], eps)
        with jax.named_scope("router"):
            chosen, weight = expert_trunk._route(
                route, h2.reshape(r * t, -1), own["mlp.gate.weight"],
                own["mlp.gate.e_score_correction_bias"])
        m, load, _ = expert_trunk._held_experts(
            route, h2, chosen, weight, stacks["mlp.experts.gate_up_proj"],
            stacks["mlp.experts.down_proj"], layer * held, dtype, precision)
        with jax.named_scope("shared_expert"):
            m = m + expert_trunk._gated_mlp(
                h2, own["mlp.shared_experts.gate_up_proj"],
                own["mlp.shared_experts.down_proj"], dtype, precision)
        return x + m, load

    def layer(x, scanned):
        w, index = scanned

        def row(n, state):
            x, counts = state
            x_n, counts_n = attention_row(c, x[n], w, dtype, precision,
                                          query_chunk)
            return x.at[n].set(x_n), counts.at[n].set(counts_n)

        with jax.named_scope("attention"):
            x, counts = lax.fori_loop(
                0, r, row, (x, jnp.zeros((r, len(SPARSE_COUNTS)), i32)))
        if dense == 0:
            x, load = expert_mlp(x, w, index)
        elif dense == depth:
            x, load = dense_mlp(x, w, index)
        else:
            x, load = lax.cond(index < dense, dense_mlp, expert_mlp, x, w,
                               index)
        return x, (load, counts)

    x, (load, counts) = lax.scan(
        layer, x, (variables["layers"], jnp.arange(depth, dtype=i32)))
    with jax.named_scope("pool"):
        features = jnp.mean(rms(x, variables["final_layernorm"], eps), axis=1)
    return {"features": features,
            "expert_load": jnp.moveaxis(load[dense:], 0, 1),
            "sparse_counts": jnp.moveaxis(counts, 0, 1)}


def model_function(config: Dict[str, Any], variables: Dict[str, Any], *,
                   compute_dtype: Optional[str] = None,
                   matmul_precision: Optional[str] = None):
    """The trunk as a ``ModelFunction`` for ``ModelTransformer`` over an
    integer list column: ``features`` is the column, ``expert_load`` and
    ``sparse_counts`` counters.  The compute dtype is the weights' own:
    a leaf already of ``compute_dtype`` (or every leaf, where none is
    named) is used as it is given; the routing bias, which only chooses,
    stays float32.  ``matmul_precision`` ``"highest"`` is for float32
    parity runs, ``None``/``"default"`` the chip's default."""
    from sparkdl_tpu.graph.function import ModelFunction

    config = dict(config)
    if compute_dtype is not None:
        target = jnp.dtype(compute_dtype)
        variables = jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf if leaf.dtype == target or getattr(
                path[-1], "key", None) == "mlp.gate.e_score_correction_bias"
            else leaf.astype(target), variables)
    precision = (None if matmul_precision in (None, "default")
                 else lax.Precision(matmul_precision))

    def fn(v, ids):
        return apply(v, ids, config, precision=precision)

    return ModelFunction(fn=fn, variables=variables, input_names=("ids",),
                         output_names=("features",) + COUNTERS,
                         counter_names=COUNTERS, token_input="ids")
