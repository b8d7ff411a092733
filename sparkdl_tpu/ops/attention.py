"""Causal grouped-query attention that never holds a row's score matrix.

``q`` ``[R, T, H*hd]``, ``k`` and ``v`` ``[R, T, KV*hd]``, heads side by
side on the last axis as the projections leave them, each key/value
head serving ``H // KV`` query heads; ``q`` comes already scaled (and
turned by the rotary position, as ``k``).  Returns ``[R, T, H*hd]`` in
``q``'s dtype.  With ``window`` a query sees the ``window`` keys that
end at its own position (``query - key < window``) and none before.
With a ``block_length`` ``B`` over 1 the mask is by blocks of ``B``
positions (``key // B <= query // B``): a query sees every earlier block
and its OWN block both ways, which is how block diffusion reads a
prompt; ``B`` is static and 1 is the causal mask, the same instruction
as without it.

On the TPU a Pallas kernel (``name="causal_attention"``): grid rows x
query heads x query blocks x key blocks, the key axis sequential, the
running maximum, the running sum and the float32 accumulator of the
online softmax in VMEM scratch.  A key/value head is read in place for
each of its query heads (no repeated copy in memory, no transpose to a
heads-first layout); key blocks above the diagonal are neither computed
nor fetched, and with a window the key axis of the grid is only as
long as the band is wide (a grid dimension that is data, as the window
is): it starts at the first block a query block can see, so blocks
below the band are never visited at all.  Elsewhere the same sum in ``jax.numpy``, one block of
queries after the other (``lax.map``), so that the largest score array
is ``[R, H, block, T]``.  Scores, softmax and the accumulator are
float32; the two matrix products take their operands in ``q``'s dtype.
The platform picks, as in ``ops/sepconv``, and nothing else does.

``frame_attention`` is the same sum both ways over each row alone
(``causal=False``), for rows as short as a frame of image patches and
heads narrower than a lane (a vision tower's 72): one frame is one row,
so a query sees every key of its own frame and none of another's.  On
the TPU its own Pallas kernel (``name="frame_attention"``), not
``causal_attention``'s, whose blocks slice a head's columns out of the
heads side by side and want ``hd`` a multiple of 128: the heads go
first (``[R, H, T, hd]``, ``hd`` the whole last axis, so no head is
padded in memory), a step takes a block of queries of a group of heads
against ALL of its frame's keys, which fit VMEM whole, and the softmax
is taken at once, with no running maximum.  It turns ``q`` and ``k``
by the rotary ``angle`` itself, on the whole head (``x cos + (x P) sin``,
``P`` the halves swapped on the MXU): an array of half a head, 36 lanes,
would be padded to 128 in memory.  Elsewhere ``turn_halves`` and
``attention_blocked(..., causal=False)``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "causal_attention"
BLOCK = 512
FRAME_NAME = "frame_attention"
#: frame attention's queries and heads a step (scores [512, 1024] float32
#: a head at a time: 2 MB)
FRAME_QUERIES = 512
FRAME_HEADS = 4
FRAME_VMEM_LIMIT = 32 * 2**20
_NEG = -1e30          # a score no softmax notices; finite, so no NaN


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _first_key_block(qi, block: int, window):
    """The first key block that holds a key a query of block ``qi`` sees."""
    return jnp.maximum(qi * block - (window - 1), 0) // block


def _check_block_length(block_length: int, block: int, causal: bool,
                        window) -> None:
    if block_length == 1:
        return
    if not causal or window is not None:
        raise ValueError("a block length goes with the causal mask alone")
    if block_length < 1 or block % block_length:
        raise ValueError(f"blocks of {block_length} positions do not fill "
                         f"a query block of {block}")


def attention_blocked(q, k, v, *, heads: int, kv_heads: int,
                      block: int = BLOCK, causal: bool = True,
                      window=None, block_length: int = 1, precision=None):
    """``jax.numpy``: queries in blocks of ``block`` against all keys.
    ``causal=False`` lets every position see every other."""
    if window is not None and not causal:
        raise ValueError("a window needs causal attention")
    f32 = jnp.float32
    r, t, _ = q.shape
    hd = q.shape[-1] // heads
    rep = heads // kv_heads
    block = min(block, t)
    if t % block:
        raise ValueError(f"{t} positions are no multiple of the block {block}")
    _check_block_length(block_length, block, causal, window)
    kh = k.reshape(r, t, kv_heads, hd)
    vh = v.reshape(r, t, kv_heads, hd)
    qb = jnp.moveaxis(q.reshape(r, t // block, block, kv_heads, rep, hd), 1, 0)
    key_at = jnp.arange(t)

    def one(at):
        q_i, i = at
        s = jnp.einsum("rqgjd,rkgd->rgjqk", q_i, kh, precision=precision,
                       preferred_element_type=f32)
        if causal:
            query_at = i * block + jnp.arange(block)
            seen = key_at[None, :] <= _last_seen(query_at, block_length)[:, None]
            if window is not None:
                seen &= query_at[:, None] - key_at[None, :] < window
            s = jnp.where(seen, s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("rgjqk,rkgd->rqgjd", p.astype(q.dtype), vh,
                          precision=precision, preferred_element_type=f32)

    out = lax.map(one, (qb, jnp.arange(t // block)))
    return jnp.moveaxis(out, 0, 1).reshape(r, t, heads * hd).astype(q.dtype)


def _last_seen(query_at, block_length: int):
    """The last key a query sees: itself, or with blocks the last
    position of its own block."""
    if block_length == 1:
        return query_at
    return query_at // block_length * block_length + (block_length - 1)


def _attention_kernel(*refs, causal: bool, windowed: bool,
                      block_length: int, precision):
    """One query block of one head against one key block; blocks are
    ``[1, block, hd]``.  With a window its width comes first, in scalar
    memory, and the grid's key axis counts ``kj`` from the first block
    the query block sees (block 0 without a window)."""
    f32 = jnp.float32
    window = refs[0][0] if windowed else None
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs[windowed:]
    qi, kj, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    block = q_ref.shape[1]
    ki = kj + _first_key_block(qi, block, window) if windowed else kj

    @pl.when(kj == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_or(ki <= qi, not causal))
    def _():
        s = lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            precision=precision, preferred_element_type=f32)
        if causal:
            rows = qi * block + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = ki * block + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            seen = cols <= _last_seen(rows, block_length)
            if windowed:
                # a row whose keys of this block are all out of the band
                # sums garbage at weight exp(0); the first key it does
                # see scales that to nothing (exp(_NEG - m) is 0)
                seen = jnp.logical_and(seen, rows - cols < window)
            s = jnp.where(seen, s, _NEG)
        m_old = m_ref[...]                                   # [block, 1]
        m_new = jnp.maximum(m_old, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        scale = jnp.exp(m_old - m_new)
        l_ref[...] = scale * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = scale * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0], precision=precision,
            preferred_element_type=f32)
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, donate_argnums=(), static_argnames=(
    "heads", "kv_heads", "block", "causal", "block_length", "interpret",
    "precision"))
def attention_kernel(q, k, v, window=None, *, heads: int, kv_heads: int,
                     block: int = BLOCK, causal: bool = True,
                     block_length: int = 1, interpret: bool = False,
                     precision=None):
    """The Pallas kernel; on the chip ``hd`` is a multiple of 128 and
    ``block`` of 8.  ``window`` is data (an int32 scalar, so that layers
    of both kinds run one compiled kernel under one ``lax.scan``) or
    ``None``: the grid's key axis is then the whole row."""
    r, t, _ = q.shape
    hd = q.shape[-1] // heads
    rep = heads // kv_heads
    block = min(block, t)
    if t % block:
        raise ValueError(f"{t} positions are no multiple of the block {block}")
    nb = t // block
    windowed = window is not None
    if windowed and not causal:
        raise ValueError("a window needs causal attention")
    _check_block_length(block_length, block, causal, window)

    def key_block(i, h, qi, kj, *width):
        # above the diagonal nothing is computed: name the block that is
        # already there, so that nothing is fetched either
        ki = kj + _first_key_block(qi, block, width[0][0]) if windowed else kj
        return (i, jnp.minimum(ki, qi) if causal else ki, h // rep)

    query = pl.BlockSpec((1, block, hd), lambda i, h, qi, kj, *_: (i, qi, h))
    keys = pl.BlockSpec((1, block, hd), key_block)
    scalars = ()
    nk = nb
    if windowed:
        width = jnp.maximum(jnp.asarray(window, jnp.int32), 1)
        scalars = (width.reshape(1),)
        # the band of a query block spans window - 1 + block keys
        nk = jnp.minimum(nb, (width - 1 + block - 1) // block + 1)
    return pl.pallas_call(
        functools.partial(_attention_kernel, causal=causal, windowed=windowed,
                          block_length=block_length, precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(r, heads, nb, nk),
            in_specs=[query, keys, keys],
            out_specs=query,
            scratch_shapes=[pltpu.VMEM((block, 1), jnp.float32),
                            pltpu.VMEM((block, 1), jnp.float32),
                            pltpu.VMEM((block, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=NAME,
    )(*scalars, q, k, v)


def causal_attention(q, k, v, *, heads: int, kv_heads: int, window=None,
                     block: int = BLOCK, block_length: int = 1,
                     precision=None, force: Optional[object] = None):
    """The kernel on the TPU, the blocked ``jax.numpy`` form on any
    other platform; ``window=None`` is causal over the whole row, else
    an integer or an int32 scalar of the program (a window no shorter
    than the row is the same sum); ``block_length`` over 1 is the mask
    by blocks; ``force`` is the tests' (``True``, ``"interpret"``,
    ``False``)."""
    if _on_tpu() if force is None else force:
        return attention_kernel(q, k, v, window, heads=heads,
                                kv_heads=kv_heads, block=block,
                                block_length=block_length,
                                interpret=(force == "interpret"),
                                precision=precision)
    return attention_blocked(q, k, v, heads=heads, kv_heads=kv_heads,
                             block=block, window=window,
                             block_length=block_length, precision=precision)



def turn_halves(x, angle):
    """``x`` ``[R, T, H, hd]`` turned by the rotary ``angle`` ``[T, hd/2]``:
    pairs ``(i, i + hd/2)``, float32."""
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    half = x.shape[-1] // 2
    lo, hi = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


def _half_swap(hd: int):
    """``[hd, hd]`` with ``x @ P = (-x[hd/2:], x[:hd/2])``: entries 0 and
    +-1, exact in any float type."""
    half = hd // 2
    p = jnp.zeros((hd, hd), jnp.float32)
    p = p.at[jnp.arange(half) + half, jnp.arange(half)].set(-1.0)
    return p.at[jnp.arange(half), jnp.arange(half) + half].set(1.0)


def _frame_kernel(q_ref, k_ref, v_ref, cq_ref, sq_ref, ck_ref, sk_ref, p_ref,
                  o_ref, *, precision):
    """A block of queries of a group of heads against all keys of their
    frame; blocks are ``[1, group, block, hd]`` and ``[1, group, T,
    hd]``.  The heads one after the other, unrolled.  The queries and
    keys are turned here: ``x cos + (x P) sin`` over the whole head
    (``P`` swaps and negates the halves on the MXU, exactly), so no
    array in memory holds a half of a head."""
    f32 = jnp.float32

    def turned(x, cos, sin):
        swapped = jnp.dot(x, p_ref[...], preferred_element_type=f32)
        return (x.astype(f32) * cos + swapped * sin).astype(x.dtype)

    for h in range(q_ref.shape[1]):
        q = turned(q_ref[0, h], cq_ref[...], sq_ref[...])
        k = turned(k_ref[0, h], ck_ref[...], sk_ref[...])
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=precision, preferred_element_type=f32)
        p = jnp.exp(s - s.max(axis=1, keepdims=True))
        out = jnp.dot(p.astype(v_ref.dtype), v_ref[0, h], precision=precision,
                      preferred_element_type=f32)
        o_ref[0, h] = (out / p.sum(axis=1, keepdims=True)).astype(o_ref.dtype)


@functools.partial(jax.jit, donate_argnums=(), static_argnames=(
    "block", "group", "interpret", "precision"))
def frame_attention_kernel(q, k, v, angle, *, block: int = FRAME_QUERIES,
                           group: int = FRAME_HEADS, interpret: bool = False,
                           precision=None):
    """The Pallas kernel over heads first: ``q``, ``k``, ``v`` ``[R, H,
    T, hd]`` (``q`` scaled), each row attended both ways within itself,
    ``q`` and ``k`` turned first by the rotary ``angle`` ``[T, hd/2]``;
    ``[R, H, T, hd]`` in ``q``'s dtype."""
    r, heads, t, hd = q.shape
    block, group = min(block, t), min(group, heads)
    if t % block or heads % group:
        raise ValueError(f"{t} positions in blocks of {block}, {heads} heads "
                         f"in groups of {group}: no whole number of either")
    query = pl.BlockSpec((1, group, block, hd),
                         lambda i, g, qi: (i, g, qi, 0))
    keys = pl.BlockSpec((1, group, t, hd), lambda i, g, qi: (i, g, 0, 0))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    cos, sin = (jnp.concatenate([cos, cos], axis=-1),
                jnp.concatenate([sin, sin], axis=-1))
    by_query = pl.BlockSpec((block, hd), lambda i, g, qi: (qi, 0))
    whole = pl.BlockSpec((t, hd), lambda i, g, qi: (0, 0))
    return pl.pallas_call(
        functools.partial(_frame_kernel, precision=precision),
        grid=(r, heads // group, t // block),
        in_specs=[query, keys, keys, by_query, by_query, whole, whole,
                  pl.BlockSpec((hd, hd), lambda i, g, qi: (0, 0))],
        out_specs=query,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=FRAME_VMEM_LIMIT),
        interpret=interpret,
        name=FRAME_NAME,
    )(q, k, v, cos, sin, cos, sin, _half_swap(hd).astype(q.dtype))


def frame_attention(q, k, v, angle, *, heads: int, precision=None,
                    force: Optional[object] = None):
    """``q``, ``k``, ``v`` ``[R, T, H*hd]`` (a row a frame, ``q`` scaled)
    -> ``[R, T, H*hd]``: every query over every key of its own row, ``q``
    and ``k`` turned first by the rotary ``angle`` ``[T, hd/2]`` (pairs
    ``(i, i + hd/2)``).  The kernel on the TPU (heads put first around
    it, the turn inside it), the blocked ``jax.numpy`` form elsewhere;
    ``force`` as ``causal_attention``'s."""
    r, t, width = q.shape
    hd = width // heads
    if _on_tpu() if force is None else force:

        def heads_first(x):
            return jnp.swapaxes(x.reshape(r, t, heads, hd), 1, 2)

        out = frame_attention_kernel(
            heads_first(q), heads_first(k), heads_first(v), angle,
            interpret=(force == "interpret"), precision=precision)
        return jnp.swapaxes(out, 1, 2).reshape(r, t, width)
    q, k = (turn_halves(x.reshape(r, t, heads, hd), angle).reshape(
        r, t, width).astype(x.dtype) for x in (q, k))
    return attention_blocked(q, k, v, heads=heads, kv_heads=heads,
                             causal=False, precision=precision)
