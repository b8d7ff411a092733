"""A vision-language trunk as a featurizer: a clip's frames and a row of
token ids in, one vector a row out.

The model is Keye-VL-2.0's first stage (``model_type: KeyeVL2``;
``config`` holds the keys of its published ``config.json``, the vision
tower's widths under ``vision_config``): the frames go through
``vision_tower`` and become tokens of the language model's width, which
take the places of the row's placeholder ids; then Qwen3-MoE layers whose
grouped-query attention reads only the keys a learned indexer selects
(``sa_config``: DeepSeek Sparse Attention's lightning indexer and exact
top-k).  Every ``N`` is an RMSNorm with a learned scale, eps
``rms_norm_eps``::

    e    = E[ids];  the n-th placeholder id's row <- the n-th merged token
    h    = N_attn(x);  q = h W_q (H x hd), k = h W_k, v = h W_v (KV x hd)
    q, k = N_q(q), N_k(k) by head, then turned by mRoPE: of the hd / 2
           pairs (i, i + hd/2) the first sections[0] by t, the next
           sections[1] by h, the last sections[2] by w (``mrope_section``)
    q_I  = h W_Iq (Hi x di);  k_I = LayerNorm(h W_Ik) (di, one head);
           both turned by mRoPE over di with the sections halved
    w    = h W_Iw / sqrt(Hi di)
    I(t, s) = sum_j w[t, j] ReLU(q_I[t, j] . k_I[s])          s <= t
    S(t) = the min(t + 1, topk) keys s <= t of largest I, ties to the
           lower position
    a_g  = softmax over s in S(t) of q_g . k_{g // (H/KV)} / sqrt(hd), of v
    x    = x + [a_1 ... a_H] W_o
    u    = N_ffn(x);  p = softmax(u W_r) over the experts; the top k,
           renormalised (``norm_topk_prob``)
    x    = x + sum over the top k of p_e W_down,e (SiLU(W_gate,e u) * W_up,e u)

After the last layer ``f = N_final(x)``; the row's feature is the mean
of ``f`` over its positions, float32.  The output head is left off.

**Positions** (three streams ``(t, h, w)``, computed in the program from
the ids, the rule of Qwen2-VL): a text id has all three at its running
position; a run of placeholder ids is a clip of whole frames of a
``G x G`` merged grid, its token of frame ``f``, row ``i``, column ``j``
at ``(s + f, s + i, s + j)`` where ``s`` is the running position; after
the run the text goes on from the largest position so far + 1.

**Where the work goes.**  The tower's blocks are ``vision_tower``'s
(frames in chunks, attention ``ops/attention.frame_attention``).  The
indexer's scores, the selection and the attention over the selected keys
are ``ops/sparse_attention``'s three kernels, a row's queries
``QUERY_CHUNK`` at a time against ALL its keys, which a layer makes once
(``k`` and ``v`` heads first, ``KV`` of them, read in place by their
query heads); the rotary part of that kernel is not used, the keys are
turned already.  The experts are ``expert_trunk``'s (``_route`` with a
softmax, ``_held_experts`` over ``ops/grouped_matmul``, every expert
held), their slots worked ``EXPERT_CHUNK_TILES`` tiles at a time.  The
layers run under ONE ``lax.scan``, so every kernel is one instruction of
the program whatever the depth.  Weights and matrix-product operands are
in the compute dtype and accumulate in float32; the residual stream, the
norms, the rotary position, the indexer's weights and scores, softmax
and the router are float32.

Beside the features the program returns two COUNTERS
(``ModelFunction.counter_names``): ``sparse_counts`` ``[rows, layers,
4]`` as ``latent_trunk``'s, and ``vision_counts`` ``[rows, 3]`` int32:
the frames and patches through the tower and the tokens spliced into
the row (``VISION_COUNTS``).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from sparkdl_tpu.models import expert_trunk, vision_tower
from sparkdl_tpu.models.latent_trunk import SPARSE_COUNTS, _layer_norm, _turn
from sparkdl_tpu.ops import sparse_attention as sa

#: the program's outputs that are counters, not columns
COUNTERS = ("sparse_counts", "vision_counts")
#: what ``vision_counts`` holds a row, in order
VISION_COUNTS = ("frames", "patches", "spliced_tokens")
#: the program's two inputs
INPUTS = ("pixels", "ids")
#: a row's queries worked together (scores [chunk, T] float32: 0.5 GB at
#: 4096 x 32,768)
QUERY_CHUNK = 4096
#: the experts' slots a turn of ``_held_experts``' loop works: 384 tiles
#: of 256 rows ([98,304, 2048] rows in, float32 out: 1.2 GB), about a
#: third of 32,768 tokens' pairs
EXPERT_CHUNK_TILES = 384


def routing(config: Dict[str, Any]) -> Dict[str, Any]:
    """The keys ``expert_trunk``'s routing reads, from the published ones."""
    return {"num_experts": config["num_experts"],
            "expert_share": list(config["expert_share"]),
            "num_experts_per_tok": config["num_experts_per_tok"],
            "route_norm": config["norm_topk_prob"], "route_scale": 1.0}


def layer_shapes(config: Dict[str, Any]) -> Dict[str, Dict[str, tuple]]:
    """Shape of every weight of one language layer, by kind (``layers``:
    stacked a layer; ``experts``: stacked an expert) and published name."""
    c, sc = config, config["sa_config"]
    d, hd, f = c["hidden_size"], c["head_dim"], c["moe_intermediate_size"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    ih, idim = sc["indexer_num_heads"], sc["indexer_head_dim"]
    held = c["num_experts"]
    return {
        "layers": {
            "input_layernorm": (d,), "post_attention_layernorm": (d,),
            "self_attn.q_proj": (d, q), "self_attn.k_proj": (d, kv),
            "self_attn.v_proj": (d, kv), "self_attn.o_proj": (q, d),
            "self_attn.q_norm": (hd,), "self_attn.k_norm": (hd,),
            "self_attn.indexer.wq": (d, ih * idim),
            "self_attn.indexer.wk": (d, idim),
            "self_attn.indexer.k_norm.weight": (idim,),
            "self_attn.indexer.k_norm.bias": (idim,),
            "self_attn.indexer.weights_proj": (d, ih),
            "mlp.gate": (d, expert_trunk.routed_experts(routing(c)))},
        "experts": {"mlp.experts.gate_up_proj": (held, d, 2 * f),
                    "mlp.experts.down_proj": (held, f, d)}}


def init(config: Dict[str, Any], key, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Random variables in the program's tree: ``vision`` (the tower's),
    ``embed_tokens``, ``layers`` (stacked a layer), ``experts`` (all
    layers' experts on one leading axis) and ``norm``.  Matrices
    N(0, 1/fan-in), norm scales 1, biases 0."""
    c = config
    depth, d = c["num_hidden_layers"], c["hidden_size"]
    tower_key, key = jax.random.split(key)
    keys = iter(jax.random.split(key, 32))

    def leaf(name, shape):
        if name.endswith("bias"):
            return jnp.zeros(shape, dtype)
        if name.endswith("norm") or name.endswith("norm.weight"):
            return jnp.ones(shape, dtype)
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / shape[-2] ** 0.5).astype(dtype)

    shapes = layer_shapes(c)
    return {
        "vision": vision_tower.init(c["vision_config"], d, tower_key, dtype),
        "embed_tokens": (jax.random.normal(
            next(keys), (c["vocab_size"], d), jnp.float32)
            / d ** 0.5).astype(dtype),
        "layers": {n: leaf(n, (depth,) + s)
                   for n, s in shapes["layers"].items()},
        "experts": {n: leaf(n, (depth * s[0],) + s[1:])
                    for n, s in shapes["experts"].items()},
        "norm": jnp.ones((d,), dtype)}


def stack_layers(leaf: Callable[[int, str], Any], config: Dict[str, Any]
                 ) -> Dict[str, Dict[str, Any]]:
    """The program's ``layers`` and ``experts`` from ``leaf(layer,
    published name)`` (a layer's experts stacked: ``mlp.experts.gate_proj``
    ``[held, D, F]`` and so on), traced: every stack is a ``lax.map`` over
    the layer's index (an int32 of the program), each layer written into
    its place in the one array that holds it."""
    depth = config["num_hidden_layers"]
    shapes = layer_shapes(config)

    def filled(name, *pieces, flat=False):
        stack = lax.map(lambda i: jnp.concatenate([leaf(i, p) for p in pieces],
                                                  axis=-1),
                        jnp.arange(depth, dtype=jnp.int32))
        return stack.reshape((-1,) + stack.shape[2:]) if flat else stack

    return {
        "layers": {name: filled(name, name) for name in shapes["layers"]},
        "experts": {
            "mlp.experts.gate_up_proj": filled(
                "mlp.experts.gate_up_proj", "mlp.experts.gate_proj",
                "mlp.experts.up_proj", flat=True),
            "mlp.experts.down_proj": filled(
                "mlp.experts.down_proj", "mlp.experts.down_proj",
                flat=True)}}


# -- positions and the splice -------------------------------------------------

def mrope_positions(ids, placeholder: int, side: int):
    """``ids`` ``[T]`` -> int32 ``[T, 3]`` positions ``(t, h, w)``: text
    at its running position; a run of placeholder ids as whole frames of
    a ``side x side`` grid at ``(s + frame, s + row, s + column)``, ``s``
    the running position; after a run the largest so far + 1."""
    i32 = jnp.int32
    t = ids.shape[0]
    at = jnp.arange(t, dtype=i32)
    ph = ids == placeholder
    before = jnp.concatenate([jnp.zeros((1,), bool), ph[:-1]])
    after = jnp.concatenate([ph[1:], jnp.zeros((1,), bool)])
    run_start = lax.cummax(jnp.where(ph & ~before, at, 0))
    k = at - run_start                          # index within the run
    per_frame = side * side
    frame, cell = k // per_frame, k % per_frame
    # a text id moves the running position on by 1, a run by its extent
    extent = jnp.maximum((k + per_frame) // per_frame, side)
    step = jnp.where(ph, jnp.where(ph & ~after, extent, 0), 1)
    s = jnp.cumsum(step) - step
    zero = jnp.zeros_like(k)
    return s[:, None] + jnp.where(ph[:, None], jnp.stack(
        [frame, cell // side, cell % side], axis=1), zero[:, None])


def splice(embedded, ids, tokens, placeholder: int):
    """``embedded`` ``[T, D]`` with the n-th placeholder id's row the n-th
    of ``tokens`` ``[N, D]`` (a surplus placeholder takes the last), and
    the count of tokens spliced."""
    ph = ids == placeholder
    n = jnp.cumsum(ph.astype(jnp.int32)) - 1
    spliced = jnp.take(tokens, jnp.clip(n, 0, tokens.shape[0] - 1), axis=0)
    count = jnp.minimum(jnp.sum(ph, dtype=jnp.int32), tokens.shape[0])
    return jnp.where(ph[:, None], spliced.astype(embedded.dtype),
                     embedded), count


def mrope_angles(positions, dim: int, sections, theta: float):
    """``[T, dim / 2]`` float32: pair ``i`` of a ``dim``-wide head turns by
    its stream's position (``sections`` pairs a stream) x
    ``theta^(-2i / dim)``."""
    inv = float(theta) ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    stream = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                        total_repeat_length=dim // 2)
    return positions[:, stream].astype(jnp.float32) * inv


def index_sections(config: Dict[str, Any]):
    """The indexer's mRoPE sections: the attention's halved."""
    return [s // 2 for s in config["rope_scaling"]["mrope_section"]]


# -- the attention of one row -------------------------------------------------

def attention_row(config: Dict[str, Any], x, positions, w, dtype, precision,
                  query_chunk: int = QUERY_CHUNK):
    """``x`` ``[T, D]`` float32 (one row, its positions ``[T, 3]``) plus
    the layer's attention branch, and the row's ``[4]`` int32 counts
    (``SPARSE_COUNTS``)."""
    c, sc, f32, i32 = config, config["sa_config"], jnp.float32, jnp.int32
    eps, theta, t = c["rms_norm_eps"], c["rope_theta"], x.shape[0]
    heads, kv_heads, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                           c["head_dim"])
    ih, idim, top = (sc["indexer_num_heads"], sc["indexer_head_dim"],
                     sc["topk"])
    sections = c["rope_scaling"]["mrope_section"]
    chunk = min(query_chunk, t)
    if t % chunk:
        raise ValueError(f"{t} positions are no multiple of {chunk}")
    rms = expert_trunk._rms_norm

    def mm(a, name):
        return jnp.dot(a.astype(dtype), w[name], precision=precision,
                       preferred_element_type=f32)

    h = rms(x, w["input_layernorm"], eps).astype(dtype)
    with jax.named_scope("keys"):
        angle = mrope_angles(positions, hd, sections, theta)
        k = _turn(rms(mm(h, "self_attn.k_proj").reshape(t, kv_heads, hd),
                      w["self_attn.k_norm"], eps), angle, False)
        k = jnp.swapaxes(k, 0, 1).astype(dtype)                 # [KV, T, hd]
        v = jnp.swapaxes(mm(h, "self_attn.v_proj").reshape(t, kv_heads, hd),
                         0, 1).astype(dtype)
    with jax.named_scope("indexer"):
        index_angle = mrope_angles(positions, idim, index_sections(c), theta)
        k_i = _turn(_layer_norm(
            mm(h, "self_attn.indexer.wk"),
            w["self_attn.indexer.k_norm.weight"],
            w["self_attn.indexer.k_norm.bias"], eps), index_angle,
            False).astype(dtype)
    o_proj = w["self_attn.o_proj"].reshape(heads, hd, -1)

    def one_chunk(n, state):
        x, counts = state
        first = n * chunk
        h_c = lax.dynamic_slice_in_dim(h, first, chunk)
        with jax.named_scope("queries"):
            q = _turn(rms(mm(h_c, "self_attn.q_proj").reshape(chunk, heads, hd),
                          w["self_attn.q_norm"], eps),
                      lax.dynamic_slice_in_dim(angle, first, chunk), False)
            q = jnp.swapaxes(q * hd ** -0.5, 0, 1).astype(dtype)  # [H, Q, hd]
        with jax.named_scope("indexer"):
            q_i = _turn(mm(h_c, "self_attn.indexer.wq").reshape(
                chunk, ih, idim), lax.dynamic_slice_in_dim(
                    index_angle, first, chunk), False)
            weight = mm(h_c, "self_attn.indexer.weights_proj") * (
                ih * idim) ** -0.5
            scores = sa.indexer_scores(q_i.astype(dtype), k_i, weight, first,
                                       precision=precision)
        with jax.named_scope("topk_select"):
            sel = sa.topk_select(scores, first, top)
        with jax.named_scope("sparse_attention"):
            a = sa.sparse_attention(q, None, k, v, None, scores, sel, first,
                                    precision=precision)
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


def embed(variables: Dict[str, Any], pixels, ids, config: Dict[str, Any], *,
          precision=None):
    """The rows' residual stream at the first layer ``[R, T, D]``
    float32 (the tower's tokens spliced in) and the ``[R, 3]`` counts
    (``VISION_COUNTS``).  ``pixels`` ``[R, frames x S x S x 3]`` uint8
    (or already ``[R, frames, S, S, 3]``)."""
    c, vision, i32 = config, config["vision_config"], jnp.int32
    size = vision["image_size"]
    r = ids.shape[0]
    frames = pixels.reshape(r, -1, size, size, 3)
    f = frames.shape[1]
    with jax.named_scope("vision"):
        tokens = vision_tower.apply(
            variables["vision"], frames.reshape((r * f,) + frames.shape[2:]),
            vision, precision=precision)
        tokens = tokens.reshape(r, -1, tokens.shape[-1])
    with jax.named_scope("embed"):
        e = jnp.take(variables["embed_tokens"], ids, axis=0).astype(jnp.float32)
        x, spliced = jax.vmap(
            lambda e_r, ids_r, z_r: splice(e_r, ids_r, z_r,
                                           c["image_token_id"]))(e, ids, tokens)
    counts = jnp.stack([
        jnp.full((r,), f, i32),
        jnp.full((r,), f * vision_tower.grid(vision) ** 2, i32), spliced],
        axis=1)
    return x, counts


def apply(variables: Dict[str, Any], pixels, ids, config: Dict[str, Any], *,
          precision=None, query_chunk: int = QUERY_CHUNK):
    """``pixels`` uint8 and ``ids`` ``[R, T]`` integers -> ``{"features":
    [R, D] float32, "sparse_counts": [R, layers, 4] int32,
    "vision_counts": [R, 3] int32}``.  The compute dtype is the weights'
    own."""
    c, f32, i32 = config, jnp.float32, jnp.int32
    dtype = variables["embed_tokens"].dtype
    eps, depth = c["rms_norm_eps"], c["num_hidden_layers"]
    held = c["num_experts"]
    route = routing(c)
    ids = ids.astype(i32)
    r, t = ids.shape
    x, vision_counts = embed(variables, pixels, ids, c, precision=precision)
    side = (vision_tower.grid(c["vision_config"])
            // c["vision_config"]["spatial_merge_size"])
    positions = jax.vmap(lambda row: mrope_positions(
        row, c["image_token_id"], side))(ids)
    experts = variables["experts"]
    rms = expert_trunk._rms_norm

    def layer(x, scanned):
        w, index = scanned

        def row(n, state):
            x, counts = state
            x_n, counts_n = attention_row(c, x[n], positions[n], w, dtype,
                                          precision, query_chunk)
            return x.at[n].set(x_n), counts.at[n].set(counts_n)

        with jax.named_scope("attention"):
            x, counts = lax.fori_loop(
                0, r, row, (x, jnp.zeros((r, len(SPARSE_COUNTS)), i32)))
        u = rms(x, w["post_attention_layernorm"], eps)
        with jax.named_scope("router"):
            chosen, weight = expert_trunk._route(
                route, u.reshape(r * t, -1), w["mlp.gate"], scores="softmax")
        m, _, _ = expert_trunk._held_experts(
            route, u, chosen, weight, experts["mlp.experts.gate_up_proj"],
            experts["mlp.experts.down_proj"], index * held, dtype, precision,
            chunk_tiles=EXPERT_CHUNK_TILES)
        return x + m, counts

    x, counts = lax.scan(layer, x, (variables["layers"],
                                    jnp.arange(depth, dtype=i32)))
    with jax.named_scope("pool"):
        features = jnp.mean(rms(x, variables["norm"], eps), axis=1)
    return {"features": features,
            "sparse_counts": jnp.moveaxis(counts, 0, 1).astype(i32),
            "vision_counts": vision_counts}


def model_function(config: Dict[str, Any], variables: Dict[str, Any], *,
                   compute_dtype: Optional[str] = None,
                   matmul_precision: Optional[str] = None):
    """The trunk as a ``ModelFunction`` of two named inputs for
    ``TFTransformer``: ``pixels`` (a uint8 list column, a row's frames
    flat) and ``ids`` (an int32 list column); ``features`` is the
    column, ``sparse_counts`` and ``vision_counts`` counters.  A leaf
    already of ``compute_dtype`` (or every leaf, where none is named) is
    used as it is given.  ``matmul_precision`` ``"highest"`` is for
    float32 parity runs, ``None``/``"default"`` the chip's default."""
    from sparkdl_tpu.graph.function import ModelFunction

    config = dict(config)
    if compute_dtype is not None:
        target = jnp.dtype(compute_dtype)
        variables = jax.tree_util.tree_map(
            lambda leaf: leaf if leaf.dtype == target else leaf.astype(target),
            variables)
    precision = (None if matmul_precision in (None, "default")
                 else lax.Precision(matmul_precision))

    def fn(v, x):
        return apply(v, x["pixels"], x["ids"], config, precision=precision)

    return ModelFunction(fn=fn, variables=variables, input_names=INPUTS,
                         output_names=("features",) + COUNTERS,
                         counter_names=COUNTERS, token_input="ids")
