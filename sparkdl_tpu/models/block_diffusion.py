"""Generation by diffusion over blocks: rows of prompt ids in, rows of
generated ids out, the whole of it ONE dispatched program.

The layer is SDAR's (``model_type: sdar_moe``; ``config`` holds the keys
of its published ``config.json``): a Qwen3-MoE decoder block — a norm
before each branch, RMSNorm of the queries and keys by head, the rotary
position, grouped-query attention, then routed experts alone (a softmax
router, the ``num_experts_per_tok`` largest, renormalised under
``norm_topk_prob``) — under a mask by BLOCKS of ``block_length``
positions: a position sees every earlier block and its own block both
ways::

    h   = x + W_o softmax(q k^T / sqrt(head_dim) + M) v
          q,k = rotary(N_q(W_q N(x))), rotary(N_k(W_k N(x)));  v = W_v N(x)
          M(i, j) = 0 where j // B <= i // B, else -inf
    y   = h + sum over e in top-k of w_e W_down,e (SiLU(W_gate,e u) * W_up,e u)
          u = N(h);  p = softmax(W_r u), float32;  w = p[top-k] / sum

and after the last layer a final RMSNorm and the output head (untied).

**The generation loop is the program's.**  A row is a prompt of ``P``
ids (a multiple of ``B``) and yields ``generated_length`` ids.  The
prompt runs through the layers once under ``M`` (``prefill``; the
attention kernel of ``ops/attention`` with its static ``block_length``)
and leaves its keys and values in the cache of all layers, which the
loop carries.  Then block after block: the block starts as ``B`` copies
of ``mask_token_id``; ``denoise_steps`` passes run its ``B`` positions
through the layers (the queries see the cache's filled part and each
other: ``ops/cache_attention``, on the TPU ONE kernel a layer-pass that
is handed the carried cache of all layers as it stands, with the layer
and the filled length as data, and reads the layer's filled tiles in
place; elsewhere ``jax.numpy`` over the layer's slice, masked) and the
head, and each reveals the ``B / denoise_steps`` still
masked positions whose choice (the largest logit, the mask id left out)
has the largest confidence ``exp(logit - logsumexp)``, ties to the lower
position; a last pass runs the clean block without the head and writes
ITS keys and values into the cache, so the cache never holds a key
computed from a mask id.  All passes are the turns of one
``lax.while_loop`` whose carry leads with the generated ids (so that the
device trace's line of the loop shows their shape): a turn runs the
layers over the block as it stands, then EITHER the head and the choice
(a denoise pass) or the write into the cache (the commit pass), so the
layers' scan and the expert kernel are one instruction each in the loop
and one in the prefill, and the loop's attention one in the loop.

The experts are ``expert_trunk``'s dispatch and combine
(``_held_experts`` over ``ops/grouped_matmul``), told which experts are
held (``expert_share``, ``[0, 1]``: all of them) and that the scores are
a softmax.  The program returns, a row, ``generated`` int32 ``[L]``,
``revealed_at`` int32 ``[L]`` (the pass, 1 and on, that revealed the
position), ``features`` float32 ``[3 L]`` (a position's chosen logit,
the ``logsumexp`` over the vocabulary and a zero, at the pass that
revealed it) and the counter ``diffusion_counts`` int32 ``[8]``
(``COUNTS``; ``ModelFunction.counter_names``).

Weights, the cache and matrix-product operands are in the compute dtype
(bfloat16 unless told otherwise) and accumulate in float32; the residual
stream, the norms, the rotary position, the softmaxes, the router's
product (at ``highest``), the routing weights, the experts' weighted sum
and the logits are float32.  Names of weights are the published
checkpoint's, matrices ``[in, out]``; an expert's ``gate_proj`` and
``up_proj`` lie side by side, all layers' experts stacked on ONE axis.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from sparkdl_tpu.models import expert_trunk
from sparkdl_tpu.ops import cache_attention
from sparkdl_tpu.ops.attention import causal_attention

#: the program's outputs that are counters, not columns
COUNTERS = ("diffusion_counts",)
#: what ``diffusion_counts`` ``[rows, 8]`` counts, a row: the passes with
#: and without the head, the ids revealed, the tokens that were routed
#: (positions x layers, the prompt's among them), the token-expert pairs
#: computed, and — on a dispatch's FIRST row, 0 on the others, summed
#: over the loop's passes and the layers (the prefill's are not among
#: them) — the experts with at least one pair, the slots that
#: ``_held_experts``' chunk turns worked through (turns x chunk: the
#: loop's pairs over them is the slots' fill) and the key positions a
#: row's attention fetched from the cache (``cache_attention.
#: fetched_positions``: the kernel's tiles x tile, the whole cache on the
#: ``jax.numpy`` path; the filled lengths' sum over them is the share
#: that was needed)
COUNTS = ("denoise_passes", "commit_passes", "revealed_ids", "tokens",
          "pairs", "touched_experts", "expert_slots", "cache_positions")
#: rows of prompts that go through the layers together in the prefill
PREFILL_ROWS = 8


def _routing(config: Dict[str, Any]) -> Dict[str, Any]:
    """The keys ``expert_trunk``'s routing reads, from the published ones."""
    return {"num_experts": config["num_experts"],
            "expert_share": list(config.get("expert_share", (0, 1))),
            "num_experts_per_tok": config["num_experts_per_tok"],
            "route_norm": config["norm_topk_prob"], "route_scale": 1.0}


def layer_shapes(config: Dict[str, Any]) -> Dict[str, Dict[str, tuple]]:
    """Shape of every weight of one layer, by kind (``layers``: what is
    stacked a layer; ``experts``: stacked an expert) and published name."""
    c = config
    d, hd, f = c["hidden_size"], c["head_dim"], c["moe_intermediate_size"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    held = c["num_experts"]
    return {
        "layers": {
            "input_layernorm": (d,), "post_attention_layernorm": (d,),
            "self_attn.q_proj": (d, q), "self_attn.k_proj": (d, kv),
            "self_attn.v_proj": (d, kv), "self_attn.o_proj": (q, d),
            "self_attn.q_norm": (hd,), "self_attn.k_norm": (hd,),
            "mlp.gate": (d, expert_trunk.routed_experts(_routing(c)))},
        "experts": {"mlp.experts.gate_up_proj": (held, d, 2 * f),
                    "mlp.experts.down_proj": (held, f, d)}}


def init(config: Dict[str, Any], key, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Random variables in the program's tree: ``embed_tokens`` ``[V,
    D]``, ``layers`` (stacked a layer), ``experts`` (all layers' experts
    on one leading axis, layer after layer), ``norm`` and ``lm_head``
    ``[D, V]``.  Matrices N(0, 1/fan-in), norm scales 1."""
    c = config
    depth, d, v = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    keys = iter(jax.random.split(key, 16))

    def leaf(name, shape):
        if name.endswith("norm"):
            return jnp.ones(shape, dtype)
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / shape[-2] ** 0.5).astype(dtype)

    shapes = layer_shapes(c)
    return {
        "embed_tokens": (jax.random.normal(next(keys), (v, d), jnp.float32)
                         / d ** 0.5).astype(dtype),
        "layers": {n: leaf(n, (depth,) + s)
                   for n, s in shapes["layers"].items()},
        "experts": {n: leaf(n, (depth * s[0],) + s[1:])
                    for n, s in shapes["experts"].items()},
        "norm": jnp.ones((d,), dtype),
        "lm_head": leaf("lm_head", (d, v))}


def stack_layers(leaf: Callable[[int, str], Any], config: Dict[str, Any]
                 ) -> Dict[str, Dict[str, Any]]:
    """The program's ``layers`` and ``experts`` from ``leaf(layer,
    published name)`` (a layer's experts stacked: ``mlp.experts.gate_proj``
    ``[held, D, F]`` and so on).  The experts are written a layer at a
    time into their place in the one array that holds all layers', so
    never more than one layer's pieces stand beside it."""
    depth = config["num_hidden_layers"]
    shapes = layer_shapes(config)

    # the array being filled is given up to its successor: one copy
    @functools.partial(jax.jit, donate_argnums=0)
    def put(stack, at, *pieces):
        return lax.dynamic_update_slice_in_dim(
            stack, jnp.concatenate(pieces, axis=-1), at, axis=0)

    def filled(name, *pieces):
        held = shapes["experts"][name][0]
        stack = None
        for i in range(depth):
            parts = [leaf(i, p) for p in pieces]
            if stack is None:
                stack = jnp.zeros((depth * held,) + shapes["experts"][name][1:],
                                  parts[0].dtype)
            # waited for, so that a layer's pieces are gone before the
            # next are drawn (dispatch runs ahead of the device otherwise)
            stack = put(stack, i * held, *parts).block_until_ready()
        return stack

    return {
        "layers": {name: jnp.stack([leaf(i, name) for i in range(depth)])
                   for name in shapes["layers"]},
        "experts": {
            "mlp.experts.gate_up_proj": filled(
                "mlp.experts.gate_up_proj", "mlp.experts.gate_proj",
                "mlp.experts.up_proj"),
            "mlp.experts.down_proj": filled(
                "mlp.experts.down_proj", "mlp.experts.down_proj")}}


def _layer(config: Dict[str, Any], x, w, index, experts, first, attend,
           dtype, precision):
    """One layer over ``x`` ``[R, T, D]`` float32 whose positions are
    ``first`` and on.  ``attend(q, k, v)`` is the attention over whatever
    the caller lets these queries see.  Returns the new ``x``, the keys
    and values of these positions ``[R, T, KV*hd]`` in the compute dtype,
    the tokens by row and held expert, and the expert slots worked
    through."""
    c, f32 = config, jnp.float32
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    heads, kv_heads = c["num_attention_heads"], c["num_key_value_heads"]
    routing = _routing(c)

    def mm(a, name, out=f32):
        return jnp.dot(a.astype(dtype), w[name], precision=precision,
                       preferred_element_type=f32).astype(out)

    with jax.named_scope("attention"):
        h = expert_trunk._rms_norm(x, w["input_layernorm"], eps)
        q = expert_trunk._normed_rotary(
            mm(h, "self_attn.q_proj"), heads, w["self_attn.q_norm"], eps,
            theta, 1.0, first) * c["head_dim"] ** -0.5
        k = expert_trunk._normed_rotary(
            mm(h, "self_attn.k_proj"), kv_heads, w["self_attn.k_norm"], eps,
            theta, 1.0, first).astype(dtype)
        v = mm(h, "self_attn.v_proj", dtype)
        x = x + mm(attend(q.astype(dtype), k, v), "self_attn.o_proj")
    u = expert_trunk._rms_norm(x, w["post_attention_layernorm"], eps)
    with jax.named_scope("router"):
        chosen, weight = expert_trunk._route(
            routing, u.reshape(-1, u.shape[-1]), w["mlp.gate"],
            scores="softmax")
    m, load, slots = expert_trunk._held_experts(
        routing, u, chosen, weight, experts["mlp.experts.gate_up_proj"],
        experts["mlp.experts.down_proj"], index * routing["num_experts"],
        dtype, precision)
    return x + m, k, v, load, slots


def _attend_cache(q, k, v, cache_k, cache_v, layer, filled, *, heads: int,
                  kv_heads: int, precision):
    """The block's queries ``[R, B, H*hd]`` (scaled) against the first
    ``filled`` positions of layer ``layer`` of the carried cache
    ``[depth, R, T, KV*hd]`` and the block's own keys and values, both
    ways; scores and softmax float32.  ``ops/cache_attention``: on the
    TPU, with as many own keys as queries, its kernel reads the layer's
    filled tiles in place; anywhere else (and at any other shapes)
    plain ``jax.numpy`` over the layer's slice."""
    return cache_attention.cache_attention(
        q, k, v, cache_k, cache_v, layer, filled, heads=heads,
        kv_heads=kv_heads, precision=precision)


def _choose(logits, still_masked, mask_id: int, reveal: int):
    """The sampler's step over a block: ``logits`` ``[R, B, V]`` float32,
    ``still_masked`` ``[R, B]``.  ``(which positions to reveal [R, B]
    bool, their ids [R, B] int32, the chosen id's logit, the logsumexp
    over the vocabulary)``: greedy with the mask id left out, the
    ``reveal`` still masked positions of largest confidence, ties to the
    lower position."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    allowed = jnp.where(jnp.arange(logits.shape[-1]) == mask_id, -jnp.inf,
                        logits)
    ids = jnp.argmax(allowed, axis=-1).astype(jnp.int32)
    top = jnp.max(allowed, axis=-1)
    # positions by falling confidence, the revealed ones last
    order = jnp.argsort(jnp.where(still_masked, lse - top, jnp.inf),
                        axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    return jnp.logical_and(rank < reveal, still_masked), ids, top, lse


def _turn(step, denoise_steps: int):
    """What a turn of the loop does once the block is through the
    layers, by its number among its block's turns: ``(the head and the
    choice run, the block's keys and values go into the cache)``."""
    return step < denoise_steps, step == denoise_steps


def apply(variables: Dict[str, Any], ids, config: Dict[str, Any], *,
          generated_length: int, denoise_steps: int, precision=None):
    """``ids`` ``[R, P]`` integers -> ``{"generated": [R, L] int32,
    "revealed_at": [R, L] int32, "features": [R, 3 L] float32,
    "diffusion_counts": [R, 8] int32}``.  The compute dtype is the
    weights' own."""
    c, f32, i32 = config, jnp.float32, jnp.int32
    dtype = variables["embed_tokens"].dtype
    eps, depth = c["rms_norm_eps"], c["num_hidden_layers"]
    heads, kv_heads = c["num_attention_heads"], c["num_key_value_heads"]
    b, mask_id, length = c["block_length"], c["mask_token_id"], generated_length
    r, p = ids.shape
    if p % b or length % b or b % denoise_steps or denoise_steps < 1:
        raise ValueError(
            f"a prompt of {p} and {length} generated ids in blocks of {b}, "
            f"{denoise_steps} passes a block: each has to divide the next")
    kv_width = kv_heads * c["head_dim"]
    ids = ids.astype(i32)
    layers, experts = variables["layers"], variables["experts"]
    layer_index = jnp.arange(depth, dtype=i32)

    def embed(tokens):
        with jax.named_scope("embed"):
            return jnp.take(variables["embed_tokens"], tokens,
                            axis=0).astype(f32)

    def through_layers(x, first, attend_layer):
        def layer(x, scanned):
            w, index = scanned
            x, k, v, load, slots = _layer(
                c, x, w, index, experts, first,
                functools.partial(attend_layer, index), dtype, precision)
            return x, (k, v, load, slots)
        return lax.scan(layer, x, (layers, layer_index))

    # -- the prompt, a few rows at a time, its keys and values into the cache
    group = math.gcd(r, PREFILL_ROWS)

    def prefill(i, carry):
        cache_k, cache_v, pairs = carry
        rows = lax.dynamic_slice_in_dim(ids, i * group, group)
        _, (k, v, load, _) = through_layers(
            embed(rows), None,
            lambda index, q, k, v: causal_attention(
                q, k, v, heads=heads, kv_heads=kv_heads, block_length=b,
                precision=precision))
        put = functools.partial(lax.dynamic_update_slice,
                                start_indices=(0, i * group, 0, 0))
        return (put(cache_k, k), put(cache_v, v),
                lax.dynamic_update_slice_in_dim(
                    pairs, jnp.sum(load, axis=(0, 2)), i * group, axis=0))

    with jax.named_scope("prefill"):
        empty = jnp.zeros((depth, r, p + length, kv_width), dtype)
        cache_k, cache_v, pairs = lax.fori_loop(
            0, r // group, prefill, (empty, empty, jnp.zeros((r,), i32)))

    # -- the loop: every turn one pass of one block through the layers
    turns = denoise_steps + 1               # a block's passes, the commit last

    def one_pass(state):
        (generated, revealed_at, features, cache_k, cache_v, pairs,
         loop_counts, passes, n) = state
        at, step = n // turns * b, n % turns
        first = p + at
        denoise_pass, commit = _turn(step, denoise_steps)
        cut = functools.partial(lax.dynamic_slice_in_dim, start_index=at,
                                slice_size=b, axis=1)
        block_ids, block_at = cut(generated), cut(revealed_at)
        tokens = jnp.where(block_at > 0, block_ids, mask_id)

        def attend(index, q, k, v):
            return _attend_cache(q, k, v, cache_k, cache_v, index, first,
                                 heads=heads, kv_heads=kv_heads,
                                 precision=precision)

        with jax.named_scope("block_pass"):
            x, (k, v, load, slots) = through_layers(embed(tokens), first,
                                                    attend)
        pairs = pairs + jnp.sum(load, axis=(0, 2))
        # the experts touched, the slots worked and the cache's positions
        # fetched, all layers of this pass
        fetched = cache_attention.fetched_positions(
            first, p + length, cache_attention.key_tile(b, b, p + length))
        loop_counts = loop_counts + jnp.stack([
            jnp.sum(jnp.sum(load, axis=1) > 0, dtype=i32), jnp.sum(slots),
            depth * fetched])
        passes = passes + jnp.stack([denoise_pass, ~denoise_pass]).astype(i32)

        with jax.named_scope("commit_pass"):
            # the block's place in the cache keeps what it held unless
            # this is the clean block's pass
            place = (0, 0, first, 0)
            size = (depth, r, b, kv_width)
            cache_k = lax.dynamic_update_slice(cache_k, jnp.where(
                commit, k, lax.dynamic_slice(cache_k, place, size)), place)
            cache_v = lax.dynamic_update_slice(cache_v, jnp.where(
                commit, v, lax.dynamic_slice(cache_v, place, size)), place)

        def denoise(x, block_ids, block_at, block_features):
            with jax.named_scope("denoise_pass"):
                with jax.named_scope("head"):
                    f = expert_trunk._rms_norm(x, variables["norm"], eps)
                    logits = jnp.dot(f.astype(dtype), variables["lm_head"],
                                     precision=precision,
                                     preferred_element_type=f32)
                with jax.named_scope("sample"):
                    reveal, chosen, top, lse = _choose(
                        logits, block_at == 0, mask_id, b // denoise_steps)
                    read = jnp.stack([top, lse, jnp.zeros_like(top)], axis=-1)
                    return (jnp.where(reveal, chosen, block_ids),
                            jnp.where(reveal, step + 1, block_at),
                            jnp.where(reveal[..., None], read,
                                      block_features))

        block_ids, block_at, block_features = lax.cond(
            denoise_pass, denoise, lambda x, *block: block, x, block_ids,
            block_at, cut(features))
        put = functools.partial(lax.dynamic_update_slice_in_dim,
                                start_index=at, axis=1)
        return (put(generated, block_ids), put(revealed_at, block_at),
                put(features, block_features), cache_k, cache_v, pairs,
                loop_counts, passes, n + 1)

    with jax.named_scope("generation"):
        blank = jnp.zeros((r, length), i32)
        state = lax.while_loop(
            lambda state: state[-1] < length // b * turns, one_pass,
            (blank, blank, jnp.zeros((r, length, 3), f32), cache_k, cache_v,
             pairs, jnp.zeros((3,), i32), jnp.zeros((2,), i32), jnp.int32(0)))
    (generated, revealed_at, features, _, _, pairs, loop_counts, passes,
     _) = state
    positions = p + (passes[0] + passes[1]) * b
    counts = jnp.stack([
        jnp.broadcast_to(passes[0], (r,)), jnp.broadcast_to(passes[1], (r,)),
        jnp.sum(revealed_at > 0, axis=1, dtype=i32),
        jnp.broadcast_to(positions * depth, (r,)), pairs,
        *jnp.zeros((3, r), i32).at[:, 0].set(loop_counts)], axis=1)
    return {"generated": generated, "revealed_at": revealed_at,
            "features": features.reshape(r, 3 * length),
            "diffusion_counts": counts}


def model_function(config: Dict[str, Any], variables: Dict[str, Any], *,
                   generated_length: int, denoise_steps: int,
                   compute_dtype: Optional[str] = None,
                   matmul_precision: Optional[str] = None):
    """The generator as a ``ModelFunction`` over an integer list column
    of prompts (``TFTransformer`` maps its outputs to columns):
    ``generated``, ``revealed_at`` and ``features`` are columns,
    ``diffusion_counts`` a counter.  A leaf that is already of
    ``compute_dtype`` (or every leaf, where none is named) is used as it
    is given, so weights placed on the device stay the one copy.
    ``matmul_precision`` ``"highest"`` is for float32 parity runs,
    ``None``/``"default"`` the chip's default."""
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
        ids = x["ids"] if isinstance(x, dict) else x
        return apply(v, ids, config, generated_length=generated_length,
                     denoise_steps=denoise_steps, precision=precision)

    return ModelFunction(
        fn=fn, variables=variables, input_names=("ids",),
        output_names=("generated", "revealed_at", "features") + COUNTERS,
        counter_names=COUNTERS, token_input="ids")
