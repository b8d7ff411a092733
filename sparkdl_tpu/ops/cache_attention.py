"""A block's queries against a key/value cache that a loop carries, the
cache read in place.

``q`` ``[R, B, H*hd]`` (scaled, turned by the rotary position) are the
``B`` queries of a row's current block; ``k`` and ``v`` ``[R, B, KV*hd]``
the block's OWN keys and values, which every query of the block sees
(both ways); ``cache_k`` and ``cache_v`` ``[depth, R, T, KV*hd]`` the
cache of ALL layers as the loop carries it, heads side by side on the
last axis, of which layer ``layer``'s first ``filled`` positions are
seen (both int32 scalars of the program: data, not shapes).  Each
key/value head serves ``H // KV`` query heads.  Returns ``[R, B, H*hd]``
in ``q``'s dtype.

On the TPU a Pallas kernel (``name="cache_attention"``): grid rows (a
few a step) x key tiles, the key axis sequential and only as long as
``filled`` needs (a grid dimension that is data), the running maximum,
the running sum and the float32 accumulator of the online softmax in
VMEM scratch.  A step fetches one tile of its rows' keys and values, all
key/value heads wide, straight out of the array of all layers — its
block is named by ``(layer, rows, tile)`` from the scalars, so no
layer's cache is ever sliced or copied — and a key/value head's
``B * H // KV`` query rows ride on it together: a key is fetched once
for all of its query heads (the queries are the operand the MXU holds,
the keys stream past).  The last tile is masked by position
``< filled``; the block's own keys and values are the last step of the
same online softmax.  Tiles at or past ``filled`` are neither computed
nor fetched.

Elsewhere the same sum in ``jax.numpy`` over the layer's whole cache,
masked.  Scores, softmax and the accumulator are float32; the two matrix
products take their operands in ``q``'s dtype.  The platform and the
shapes pick (``key_tile``), and nothing else does: the kernel takes as
many own keys as queries and a cache whose length a tile divides.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "cache_attention"
#: key positions of a row that a step fetches (of every key/value head,
#: keys and values): 256 x 512 bfloat16 twice are 512 KB a row
TILE = 256
#: rows a step takes together where that many divide the batch (2 MB a
#: step): the steps' fixed cost is paid a quarter as often
ROWS = 4
_MIN_TILE = 16        # a bfloat16 tile's rows
_NEG = -1e30          # a score no softmax notices; finite, so no NaN


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def key_tile(queries: int, own_keys: int, positions: int,
             force: Optional[object] = None) -> Optional[int]:
    """The kernel's key tile where the kernel runs, ``None`` where the
    ``jax.numpy`` form does: the platform (``force`` is the tests') and
    the shapes pick.  The tile is the largest of 256, 128 ... 16 that
    divides the cache's ``positions``."""
    if not (_on_tpu() if force is None else force) or queries != own_keys:
        return None
    tile = TILE
    while tile >= _MIN_TILE:
        if positions % tile == 0:
            return tile
        tile //= 2
    return None


def _key_tiles(filled, tile: int):
    """Tiles of the cache that hold a position under ``filled``; one at
    least, so that the kernel's first step always runs."""
    return jnp.maximum((filled + tile - 1) // tile, 1)


def fetched_positions(filled, positions: int, tile: Optional[int]):
    """Key positions a row's attention fetches from the cache at
    ``filled``: the kernel's key axis (tiles x tile), or the whole cache
    on the ``jax.numpy`` path (``tile`` ``None``)."""
    if tile is None:
        return jnp.int32(positions)
    return (_key_tiles(filled, tile) * tile).astype(jnp.int32)


def cache_attention_plain(q, k, v, cache_k, cache_v, layer, filled, *,
                          heads: int, kv_heads: int, precision=None):
    """``jax.numpy``: the layer's whole cache sliced out, scored and
    masked at or past ``filled``; any number of own keys."""
    f32 = jnp.float32
    cache_k = lax.dynamic_index_in_dim(cache_k, layer, keepdims=False)
    cache_v = lax.dynamic_index_in_dim(cache_v, layer, keepdims=False)
    r, b, _ = q.shape
    t = cache_k.shape[1]
    hd = q.shape[-1] // heads
    rep = heads // kv_heads
    qh = q.reshape(r, b, kv_heads, rep, hd)
    score = functools.partial(jnp.einsum, "rbgjd,rtgd->rgjbt",
                              precision=precision, preferred_element_type=f32)
    mix = functools.partial(jnp.einsum, "rgjbt,rtgd->rbgjd",
                            precision=precision, preferred_element_type=f32)
    before = jnp.where(jnp.arange(t) < filled,
                       score(qh, cache_k.reshape(r, t, kv_heads, hd)), _NEG)
    own = score(qh, k.reshape(r, -1, kv_heads, hd))
    p = jax.nn.softmax(jnp.concatenate([before, own], axis=-1), axis=-1)
    p = p.astype(q.dtype)
    out = (mix(p[..., :t], cache_v.reshape(r, t, kv_heads, hd))
           + mix(p[..., t:], v.reshape(r, -1, kv_heads, hd)))
    return out.reshape(r, b, heads * hd).astype(q.dtype)


def _cache_attention_kernel(at_ref, q_ref, k_ref, v_ref, ck_ref, cv_ref,
                            o_ref, m_ref, l_ref, acc_ref, *, precision):
    """A step's rows, each row's queries ``[KV, hd, B * rep]`` against
    one tile of its cache ``[tile, KV * hd]`` and, at the last tile,
    against the block's own keys ``[B, KV * hd]``.  ``at_ref`` holds
    ``(layer, filled)``.  The few queries are the operand the MXU holds
    and the keys stream past it (scores ``[keys, queries]``, the
    softmax's statistics a row of lanes): with the KEYS held, each
    serving 32 query rows, a layer-pass of ``sdar_30b_a3b_chat.gen256``
    took 0.42 ms where this takes 0.31 (``PERF.md``, PR 40)."""
    f32 = jnp.float32
    filled = at_ref[1]
    kj, nk = pl.program_id(1), pl.num_programs(1)
    rows, kv_heads, hd, queries = q_ref.shape
    tile = ck_ref.shape[1]

    @pl.when(kj == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(g, keys, values, seen=None):
        """Queries ``g`` (a row, a head) over ``keys``, ``values``, both
        ``[n, hd]``: one step of the online softmax."""
        s = jnp.dot(keys, q_ref[g], precision=precision,
                    preferred_element_type=f32)              # [n, queries]
        if seen is not None:
            # a tile never holds only unseen keys unless nothing is
            # filled; what that sums at weight exp(0) the own keys scale
            # to nothing (exp(_NEG - m) is 0)
            s = jnp.where(seen, s, _NEG)
        m_old = m_ref[g]                                     # [1, queries]
        m_new = jnp.maximum(m_old, s.max(axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        scale = jnp.exp(m_old - m_new)
        l_ref[g] = scale * l_ref[g] + p.sum(axis=0, keepdims=True)
        acc_ref[g] = scale * acc_ref[g] + lax.dot_general(
            values, p.astype(values.dtype), (((0,), (0,)), ((), ())),
            precision=precision, preferred_element_type=f32)  # [hd, queries]
        m_ref[g] = m_new

    def each_row(body):
        # a loop, not an unrolled body: the kernel's text is traced and
        # lowered at every start of the program, by its length (unrolled,
        # the rows' chains interleave, 0.24 ms a layer-pass, and the
        # program starts 2 s later: PERF.md, PR 40)
        lax.fori_loop(0, rows, lambda i, _: body(i), None)

    seen = kj * tile + lax.broadcasted_iota(
        jnp.int32, (tile, queries), 0) < filled

    @each_row
    def _(i):
        for g in range(kv_heads):
            head = slice(g * hd, (g + 1) * hd)
            attend((i, g), ck_ref[i, :, head], cv_ref[i, :, head], seen)

    @pl.when(kj == nk - 1)
    def _():
        @each_row
        def _(i):
            for g in range(kv_heads):
                head = slice(g * hd, (g + 1) * hd)
                attend((i, g), k_ref[i, :, head], v_ref[i, :, head])
                o_ref[i, g] = (acc_ref[i, g] / l_ref[i, g]).astype(o_ref.dtype)


@functools.partial(jax.jit, donate_argnums=(), static_argnames=(
    "heads", "kv_heads", "tile", "rows", "interpret", "precision"))
def cache_attention_kernel(q, k, v, cache_k, cache_v, layer, filled, *,
                           heads: int, kv_heads: int, tile: int,
                           rows: int = 1, interpret: bool = False,
                           precision=None):
    """The Pallas kernel; on the chip ``hd`` is a multiple of 128 and
    ``tile`` of 16; ``tile`` divides the cache's positions and ``rows``
    (the rows a step takes together) the batch."""
    r, b, _ = q.shape
    _, _, t, kv_width = cache_k.shape
    hd = q.shape[-1] // heads
    rep = heads // kv_heads
    if k.shape[1] != b or t % tile or r % rows:
        raise ValueError(
            f"{b} queries against {k.shape[1]} own keys, a cache of {t} "
            f"positions in tiles of {tile}, {r} rows {rows} a step: the "
            "kernel takes as many own keys as queries and whole tiles and "
            "steps")
    filled = jnp.asarray(filled, jnp.int32)
    at = jnp.stack([jnp.asarray(layer, jnp.int32), filled])
    # a key/value head's query rows side by side on the lanes:
    # [R, KV, hd, B * rep]
    by_head = jnp.transpose(q.reshape(r, b, kv_heads, rep, hd),
                            (0, 2, 4, 1, 3)).reshape(r, kv_heads, hd, b * rep)
    query = pl.BlockSpec((rows, kv_heads, hd, b * rep),
                         lambda i, kj, at: (i, 0, 0, 0))
    own = pl.BlockSpec((rows, b, kv_width), lambda i, kj, at: (i, 0, 0))
    cache = pl.BlockSpec((None, rows, tile, kv_width),
                         lambda i, kj, at: (at[0], i, kj, 0))
    out = pl.pallas_call(
        functools.partial(_cache_attention_kernel, precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r // rows, _key_tiles(filled, tile)),
            in_specs=[query, own, own, cache, cache],
            out_specs=query,
            scratch_shapes=[
                pltpu.VMEM((rows, kv_heads, 1, b * rep), jnp.float32),
                pltpu.VMEM((rows, kv_heads, 1, b * rep), jnp.float32),
                pltpu.VMEM((rows, kv_heads, hd, b * rep), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(by_head.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=NAME,
    )(at, by_head, k, v, cache_k, cache_v)
    return jnp.transpose(out.reshape(r, kv_heads, hd, b, rep),
                         (0, 3, 1, 4, 2)).reshape(r, b, heads * hd)


def cache_attention(q, k, v, cache_k, cache_v, layer, filled, *, heads: int,
                    kv_heads: int, precision=None,
                    force: Optional[object] = None):
    """The kernel where ``key_tile`` names a tile (on the TPU, as many
    own keys as queries, a cache a tile divides), the ``jax.numpy`` form
    anywhere else; ``force`` is the tests' (``True``, ``"interpret"``,
    ``False``)."""
    tile = key_tile(q.shape[1], k.shape[1], cache_k.shape[2], force)
    if tile is None:
        return cache_attention_plain(q, k, v, cache_k, cache_v, layer, filled,
                                     heads=heads, kv_heads=kv_heads,
                                     precision=precision)
    return cache_attention_kernel(q, k, v, cache_k, cache_v, layer, filled,
                                  heads=heads, kv_heads=kv_heads, tile=tile,
                                  rows=math.gcd(q.shape[0], ROWS),
                                  interpret=(force == "interpret"),
                                  precision=precision)
