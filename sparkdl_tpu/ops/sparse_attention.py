"""Learned sparse attention over a row: a lightning indexer scores every
causal key for every query, the top ``k`` keys of each query are chosen
exactly, and the query's heads attend to those keys and no others.

Three calls, each a Pallas kernel on the TPU and a ``jax.numpy`` form
elsewhere (the platform picks, as in ``ops/attention``; ``force`` is the
tests'), over ``Q`` queries at positions ``first`` .. ``first + Q - 1``
of a row of ``T`` positions (``first`` is data, an int32 scalar):

* ``indexer_scores(q, k, w, first)``: ``I(t, s) = sum_j w[t, j] *
  ReLU(q[t, j] . k[s])`` for every key ``s <= t``, ``q`` ``[Q, Hi, d]``,
  ``k`` ``[T, d]``, ``w`` ``[Q, Hi]`` float32; ``[Q, T]`` float32, the
  entries of keys after the query unspecified.  The kernel
  (``name="indexer_scores"``) takes a block of queries' heads as the
  rows of one product against a tile of keys, the ReLU, the weights
  and the sum over the heads in VMEM: ``[Q, T, Hi]`` never exists.
  Tiles above the diagonal are neither computed nor fetched.
* ``topk_select(scores, first, k)``: ``[Q, 2]`` int32, ``(tau, last)``:
  a query's keys are the ``min(t + 1, k)`` keys ``s <= t`` of largest
  score, ties to the lower position — those of ``key(score) > tau``
  and, of ``key(score) == tau``, those at positions ``<= last``
  (``key`` orders float32 as int32 do: ``order_key``).  Exact: the
  kernel (``name="topk_select"``) finds ``tau`` by counting over its
  bits, 32 passes over a query block's causal keys in VMEM (no sort),
  and ``last`` by counting over the positions of the ties — passes that
  run only for a block where ties at ``tau`` lie on both sides of the
  boundary; elsewhere ``last`` is the query's own position.
* ``sparse_attention(q_nope, q_pe, k_nope, v, k_pe, scores, sel,
  first)``: for each head ``softmax((q_nope . k_nope + q_pe . k_pe)
  over the selected keys) v`` — heads first: ``q_*`` ``[H, Q, d]``
  already scaled (and turned by the rotary position), ``k_nope``, ``v``
  ``[KV, T, d]``, ``k_pe`` ``[T, dr]`` one for all heads; ``[H, Q, dv]``
  in ``q``'s dtype.  ``KV`` divides ``H``: a key/value head serves the
  ``H // KV`` query heads beside it (grouped-query attention; ``KV = H``
  is latent attention's per-head keys), read in place for each of them,
  never repeated in memory.  The rotary part is optional: with
  ``q_pe`` and ``k_pe`` ``None`` the score is ``q_nope . k_nope`` alone
  (keys already turned, as grouped-query attention's are).  The kernel (``name="sparse_attention"``) is an
  online softmax over the causal key tiles of a block of queries, a group of heads a
  step, the selection read from ``scores`` and ``sel`` tile by tile and
  applied as a mask: exact, but a tile is fetched and computed whole
  whatever share of it was selected (``fetched_keys`` counts what it
  reads; a gather of the selected rows alone is not written yet).  A
  step's heads and sub-tiles of keys are one software pipeline, so that the
  MXU's products of one overlap the softmax of another.

Scores, softmax and the accumulator are float32; matrix products take
their operands in the inputs' dtype.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INDEXER_NAME = "indexer_scores"
SELECT_NAME = "topk_select"
ATTENTION_NAME = "sparse_attention"
#: the indexer's queries a step (x its heads: the product's rows) and keys
INDEX_QUERIES = 16
INDEX_KEYS = 1024
#: queries whose scores a selection step holds (8 x 32,768 float32: 1 MB),
#: the keys a counting pass takes at a time, and the lanes its partial
#: counts are kept in ([8, 512] int32: 4 vector registers)
SELECT_QUERIES = 8
SELECT_LANES = 4096
SELECT_FOLD = 512
#: the attention's queries, keys and heads a step, and the keys a head
#: scores at a time (256 x 256 float32: the 64 vector registers)
QUERIES = 256
KEYS = 512
HEADS = 16
SUB_KEYS = 256
VMEM_LIMIT = 64 * 2**20
_NEG = -1e30          # a score no softmax notices; finite, so no NaN
_INT_MIN = -2**31


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_kernel(force) -> bool:
    return _on_tpu() if force is None else bool(force)


def order_key(scores):
    """float32 -> int32 in the same total order as ``lax.top_k``'s
    (``-0.0`` below ``0.0``)."""
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def selected(scores, sel, query_at, key_at):
    """The selection as a mask: ``scores`` ``[Q, K]`` of keys ``key_at``
    ``[1, K]`` for queries ``query_at`` ``[Q, 1]``, ``sel`` ``[Q, 2]``."""
    key = order_key(scores)
    tau, last = sel[:, 0:1], sel[:, 1:2]
    chosen = (key > tau) | ((key == tau) & (key_at <= last))
    return chosen & (key_at <= query_at)


def _tiles(first, i, block: int, tile: int):
    """Key tiles that query block ``i`` sees (through its last query)."""
    return (first + (i + 1) * block - 1) // tile + 1


# -- counts ------------------------------------------------------------------

def indexed_pairs(first, queries: int):
    """Causal pairs of the ``queries`` queries from ``first``: what the
    indexer scores (int32)."""
    first = jnp.asarray(first, jnp.int32)
    return queries * first + queries * (queries + 1) // 2


def selected_pairs(first, queries: int, top: int):
    """``sum min(t + 1, top)`` over the queries (int32)."""
    at = jnp.asarray(first, jnp.int32) + jnp.arange(queries, dtype=jnp.int32)
    return jnp.sum(jnp.minimum(at + 1, top))


def tied_queries(sel, first):
    """Queries from ``first`` whose ``last`` the tie passes placed: those
    with ties at ``tau`` on both sides of the boundary (int32)."""
    query_at = (jnp.asarray(first, jnp.int32)
                + jnp.arange(sel.shape[0], dtype=jnp.int32))
    return jnp.sum((sel[:, 1] < query_at).astype(jnp.int32))


def fetched_keys(first, queries: int, positions: int,
                 force: Optional[object] = None):
    """Key rows the attention reads for the ``queries`` queries from
    ``first``, a query at a time: every causal tile of its query block
    on the kernel's path, the whole row on the ``jax.numpy`` form's."""
    if not _use_kernel(force):
        return jnp.int32(queries * positions)
    block, tile = min(QUERIES, queries), min(KEYS, positions)
    blocks = jnp.arange(queries // block, dtype=jnp.int32)
    seen = jnp.minimum(_tiles(first, blocks, block, tile), positions // tile)
    return jnp.sum(seen * tile * block)


# -- the indexer ---------------------------------------------------------------

def indexer_scores_plain(q, k, w, first=0, *, precision=None):
    del first
    dots = jnp.einsum("qhd,kd->qhk", q, k, precision=precision,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("qhk,qh->qk", jnp.maximum(dots, 0.0),
                      w.astype(jnp.float32), precision=lax.Precision.HIGHEST)


def _indexer_kernel(at_ref, q_ref, k_ref, w_ref, o_ref, *, heads: int,
                    precision):
    i, j = pl.program_id(0), pl.program_id(1)
    block, tile = o_ref.shape

    @pl.when(j < _tiles(at_ref[0], i, block, tile))
    def _():
        s = lax.dot_general(q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
                            precision=precision,
                            preferred_element_type=jnp.float32)
        r = jnp.maximum(s, 0.0) * w_ref[...]                  # [block*Hi, tile]
        o_ref[...] = r.reshape(block, heads, tile).sum(axis=1)

    @pl.when(j >= _tiles(at_ref[0], i, block, tile))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, donate_argnums=(), static_argnames=(
    "block", "tile", "interpret", "precision"))
def indexer_scores_kernel(q, k, w, first=0, *, block: int = INDEX_QUERIES,
                          tile: int = INDEX_KEYS, interpret: bool = False,
                          precision=None):
    queries, heads, d = q.shape
    t = k.shape[0]
    block, tile = min(block, queries), min(tile, t)
    if queries % block or t % tile:
        raise ValueError(f"{queries} queries in blocks of {block}, {t} keys "
                         f"in tiles of {tile}: no whole number of either")
    at = jnp.asarray(first, jnp.int32).reshape(1)

    def key_tile(i, j, at):
        return (jnp.minimum(j, _tiles(at[0], i, block, tile) - 1), 0)

    return pl.pallas_call(
        functools.partial(_indexer_kernel, heads=heads, precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(queries // block, t // tile),
            in_specs=[pl.BlockSpec((block * heads, d), lambda i, j, at: (i, 0)),
                      pl.BlockSpec((tile, d), key_tile),
                      pl.BlockSpec((block * heads, 1),
                                   lambda i, j, at: (i, 0))],
            out_specs=pl.BlockSpec((block, tile), lambda i, j, at: (i, j))),
        out_shape=jax.ShapeDtypeStruct((queries, t), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=INDEXER_NAME,
    )(at, q.reshape(queries * heads, d), k,
      w.astype(jnp.float32).reshape(queries * heads, 1))


def indexer_scores(q, k, w, first=0, *, precision=None,
                   force: Optional[object] = None):
    if _use_kernel(force):
        return indexer_scores_kernel(q, k, w, first,
                                     interpret=(force == "interpret"),
                                     precision=precision)
    return indexer_scores_plain(q, k, w, first, precision=precision)


# -- the selection -------------------------------------------------------------

def _select(count, query_at, top: int, width: int):
    """``(tau, last)`` ``[Q, 1]`` of a query block's keys.  ``count(pred)``
    is ``[Q, 1]``, the number of a query's keys ``s`` with ``pred(key,
    key_at)``, keys after the query being ``_INT_MIN`` (which no score's
    key is) and positions below ``2**width``.

    ``tau`` is the ``need``-th largest key, found bit by bit from the top;
    its pass also says how many keys reach it.  Where that is ``need``
    every tie at ``tau`` is taken, and ``last`` is the query's own
    position.  Only where ties lie on both sides of the boundary does
    ``last`` take ``width`` more passes, over the positions of the ties,
    and those run only for a block that holds such a query."""
    i32 = jnp.int32
    need = jnp.minimum(query_at + 1, top)

    def bit(b, state):
        # the largest tau (bits set from the top, in the order of
        # unsigned numbers: the sign bit flipped) with need keys >= it
        ans, reach = state
        cand = ans | lax.shift_left(jnp.int32(1), 31 - b)
        n = count(lambda key, key_at: key >= (cand ^ _INT_MIN))
        return jnp.where(n >= need, cand, ans), jnp.where(n >= need, n, reach)

    zeros = jnp.zeros_like(need)
    ans, reach = lax.fori_loop(0, 32, bit, (zeros, zeros))
    tau = ans ^ _INT_MIN
    split = reach > need                    # ties left out at the boundary

    def placed():
        wanted = need - count(lambda key, key_at: key > tau)  # >= 1 of ties

        def place(b, ans):
            # the largest position with fewer than ``wanted`` ties before it
            cand = ans + lax.shift_left(jnp.int32(1), width - 1 - b)
            n = count(lambda key, key_at: (key == tau) & (key_at < cand))
            return jnp.where(n < wanted, cand, ans)

        return jnp.where(split, lax.fori_loop(0, width, place, zeros),
                         query_at)

    last = lax.cond(jnp.max(split.astype(i32)) > 0, placed,
                    lambda: query_at)
    return tau, last


def _width(positions: int) -> int:
    return max(positions - 1, 1).bit_length()


def topk_select_plain(scores, first, top: int):
    q, t = scores.shape
    query_at = (jnp.asarray(first, jnp.int32)
                + jnp.arange(q, dtype=jnp.int32)[:, None])
    key_at = jnp.arange(t, dtype=jnp.int32)[None, :]
    key = jnp.where(key_at <= query_at, order_key(scores), _INT_MIN)

    def count(pred):
        return jnp.sum(pred(key, key_at).astype(jnp.int32), axis=1,
                       keepdims=True)

    tau, last = _select(count, query_at, top, _width(t))
    return jnp.concatenate([tau, last], axis=1)


def _select_kernel(at_ref, s_ref, o_ref, key_ref, *, top: int, lanes: int,
                   fold: int):
    """A block of queries: their order keys are built once, over the
    ``lanes``-wide chunks of keys up to the block's last query (the causal
    prefix), into ``key_ref``; a pass counts over those chunks alone into
    ``[block, fold]`` partial counts, summed across lanes once a pass.

    On a v5e chip at ``deepseek_v3_2_exp.rows32k``'s shape a layer-row's
    eight chunks take 27.0 ms, against 90.2 counting every key 47 times;
    2,048-wide chunks added into ``[8, 2048]`` counts took 32.6
    (``PERF.md`` §6)."""
    i32 = jnp.int32
    block, t = s_ref.shape
    i = pl.program_id(0)
    query_at = (at_ref[0] + i * block
                + lax.broadcasted_iota(i32, (block, 1), 0))
    chunks = jnp.minimum((at_ref[0] + (i + 1) * block - 1) // lanes + 1,
                         t // lanes)

    def at(c):
        return pl.ds(pl.multiple_of(c * lanes, lanes), lanes)

    def key_at(c):
        return c * lanes + lax.broadcasted_iota(i32, (1, lanes), 1)

    def build(c, carry):
        key_ref[:, at(c)] = jnp.where(key_at(c) <= query_at,
                                      order_key(s_ref[:, at(c)]), _INT_MIN)
        return carry

    lax.fori_loop(0, chunks, build, 0)

    def count(pred):
        def add(c, acc):
            hit = pred(key_ref[:, at(c)], key_at(c))
            for k in range(0, lanes, fold):
                acc = jnp.where(hit[:, k:k + fold], acc + 1, acc)
            return acc

        acc = lax.fori_loop(0, chunks, add, jnp.zeros((block, fold), i32))
        # a count is below 2**24: exact in float32, whose sum across
        # lanes is one reduction where int32's takes two
        return jnp.sum(acc.astype(jnp.float32), axis=1,
                       keepdims=True).astype(i32)

    tau, last = _select(count, query_at, top, _width(t))
    o_ref[...] = jnp.concatenate([tau, last], axis=1)


@functools.partial(jax.jit, donate_argnums=(), static_argnames=(
    "top", "block", "lanes", "fold", "interpret"))
def topk_select_kernel(scores, first, *, top: int,
                       block: int = SELECT_QUERIES,
                       lanes: int = SELECT_LANES, fold: int = SELECT_FOLD,
                       interpret: bool = False):
    q, t = scores.shape
    block, lanes = min(block, q), min(lanes, t)
    fold = min(fold, lanes)
    if q % block or t % lanes or lanes % fold:
        raise ValueError(f"{q} queries in blocks of {block}, {t} keys in "
                         f"chunks of {lanes} counted {fold} lanes wide: no "
                         "whole number of one of them")
    at = jnp.asarray(first, jnp.int32).reshape(1)
    return pl.pallas_call(
        functools.partial(_select_kernel, top=top, lanes=lanes,
                          fold=fold),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(q // block,),
            in_specs=[pl.BlockSpec((block, t), lambda i, at: (i, 0))],
            out_specs=pl.BlockSpec((block, 2), lambda i, at: (i, 0)),
            scratch_shapes=[pltpu.VMEM((block, t), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((q, 2), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=SELECT_NAME,
    )(at, scores)


def topk_select(scores, first, top: int, *, force: Optional[object] = None):
    if _use_kernel(force):
        return topk_select_kernel(scores, first, top=top,
                                  interpret=(force == "interpret"))
    return topk_select_plain(scores, first, top)


# -- the attention -------------------------------------------------------------

def sparse_attention_plain(q_nope, q_pe, k_nope, v, k_pe, scores, sel,
                           first=0, *, precision=None):
    f32 = jnp.float32
    q, t = scores.shape
    heads, kv_heads = q_nope.shape[0], k_nope.shape[0]
    query_at = (jnp.asarray(first, jnp.int32)
                + jnp.arange(q, dtype=jnp.int32)[:, None])
    mask = selected(scores, sel, query_at,
                    jnp.arange(t, dtype=jnp.int32)[None, :])
    grouped = q_nope.reshape(kv_heads, heads // kv_heads, q, -1)
    s = jnp.einsum("grqd,gkd->grqk", grouped, k_nope, precision=precision,
                   preferred_element_type=f32).reshape(heads, q, t)
    if q_pe is not None:
        s = s + jnp.einsum("hqd,kd->hqk", q_pe, k_pe, precision=precision,
                           preferred_element_type=f32)
    p = jax.nn.softmax(jnp.where(mask[None], s, _NEG), axis=-1)
    out = jnp.einsum("grqk,gkd->grqd",
                     p.astype(v.dtype).reshape(kv_heads, -1, q, t), v,
                     precision=precision, preferred_element_type=f32)
    return out.reshape(heads, q, -1).astype(q_nope.dtype)


def _attention_kernel(*refs, sub: int, rep: int, rotary: bool, precision):
    """A step: a block of queries, a group of heads, a tile of keys; query
    head ``h`` of the group reads key/value head ``h // rep`` of the
    step's (``rep`` query heads a key/value head), and with ``rotary``
    the shared ``k_pe`` beside it.

    Scores are taken transposed, ``[keys, queries]``: the queries are the
    lanes, so a head's running maximum and sum are one row of lanes
    (``m_ref``, ``l_ref`` ``[group, 1, block]``) and its accumulator is
    ``[dv, block]``.  The selection is applied as a bias of 0 or ``_NEG``
    (``bias_ref`` ``[tile, block]``), made once a step for every head.

    A head takes the tile's keys ``sub`` at a time, each an online
    softmax step, and the step's (head, sub-tile) pieces run as ONE
    pipeline: a piece's scores go to the MXU before the softmax and the
    weighted sum of the piece before it, so that the MXU works on one
    piece while the vector units work on the other.  The MXU takes its
    work in program order, so under a loop of heads each head's products
    waited behind the previous head's softmax: in
    ``deepseek_v3_2_exp.rows32k`` 18.3 µs a step, against 10.0 µs so
    (``PERF.md`` §6)."""
    if rotary:
        (at_ref, qn_ref, qp_ref, kn_ref, v_ref, kp_ref, s_ref, sel_ref, o_ref,
         m_ref, l_ref, acc_ref, bias_ref) = refs
    else:
        (at_ref, qn_ref, kn_ref, v_ref, s_ref, sel_ref, o_ref, m_ref, l_ref,
         acc_ref, bias_ref) = refs
    f32 = jnp.float32
    i, j, nk = pl.program_id(0), pl.program_id(2), pl.num_programs(2)
    heads, block, _ = qn_ref.shape
    tile = kn_ref.shape[1]
    by_row = (((1,), (1,)), ((), ()))       # a . b^T
    by_key = (((0,), (0,)), ((), ()))       # a^T . b

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < _tiles(at_ref[0], i, block, tile))
    def _():
        query_at = (at_ref[0] + i * block
                    + lax.broadcasted_iota(jnp.int32, (block, 1), 0))
        key_at = j * tile + lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        # a query none of whose keys in a piece was chosen sums garbage
        # at weight exp(0); the first key it does see scales that to
        # nothing.  s + _NEG is _NEG for any score under 1e22
        seen = selected(s_ref[...], sel_ref[...], query_at, key_at)
        bias_ref[...] = jnp.where(seen, 0.0, _NEG).T

        def scores(h, c):
            keys = pl.ds(c * sub, sub)
            s = lax.dot_general(kn_ref[h // rep, keys], qn_ref[h], by_row,
                                precision=precision,
                                preferred_element_type=f32)
            if rotary:
                s = s + lax.dot_general(kp_ref[keys], qp_ref[h], by_row,
                                        precision=precision,
                                        preferred_element_type=f32)
            return s + bias_ref[keys]                       # [sub, block]

        # unrolled, not a loop of heads: a loop body is a scheduling
        # boundary.  The step's 32 pieces cost 0.26 s more of tracing and
        # lowering at every start of deepseek_v3_2_exp.rows32k's program
        # (PERF.md §6); pipelined under a loop of two heads a turn, a
        # chunk took 43.0 ms where this takes 39.1 and the parent 68.5
        subs = tile // sub
        pieces = [(h, c) for h in range(heads) for c in range(subs)]
        s = scores(*pieces[0])
        for n, (h, c) in enumerate(pieces):
            if c == 0:
                m, l, acc = m_ref[h], l_ref[h], acc_ref[h]
            later = scores(*pieces[n + 1]) if n + 1 < len(pieces) else None
            m_new = jnp.maximum(m, s.max(axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            scale = jnp.exp(m - m_new)
            l = scale * l + p.sum(axis=0, keepdims=True)
            acc = scale * acc + lax.dot_general(
                v_ref[h // rep, pl.ds(c * sub, sub)], p.astype(v_ref.dtype),
                by_key,
                precision=precision, preferred_element_type=f32)  # [dv, block]
            m, s = m_new, later
            if c == subs - 1:
                m_ref[h], l_ref[h], acc_ref[h] = m, l, acc

    @pl.when(j == nk - 1)
    def _():
        def out(h, _):
            o_ref[h] = (acc_ref[h] / l_ref[h]).T.astype(o_ref.dtype)
            return _

        lax.fori_loop(0, heads, out, None)


@functools.partial(jax.jit, donate_argnums=(), static_argnames=(
    "block", "tile", "group", "sub", "interpret", "precision"))
def sparse_attention_kernel(q_nope, q_pe, k_nope, v, k_pe, scores, sel,
                            first=0, *, block: int = QUERIES,
                            tile: int = KEYS, group: int = HEADS,
                            sub: int = SUB_KEYS, interpret: bool = False,
                            precision=None):
    """The Pallas kernel; ``k_pe`` and the selection are shared by the
    heads, a group of which a step takes with the key/value heads they
    read (a group is a whole number of key/value heads' query heads).
    The grid's key axis is as long as the last query block's causal
    tiles (a grid dimension that is data): the steps past a block's
    diagonal fetch and compute nothing."""
    heads, q, dn = q_nope.shape
    kv_heads, t, dv = k_nope.shape[0], k_nope.shape[1], v.shape[-1]
    rotary = q_pe is not None
    block, tile, group = min(block, q), min(tile, t), min(group, heads)
    sub = min(sub, tile)
    rep = heads // kv_heads
    if (q % block or t % tile or heads % group or tile % sub
            or heads % kv_heads or group % rep):
        raise ValueError(f"{q} queries in blocks of {block}, {t} keys in "
                         f"tiles of {tile} in sub-tiles of {sub}, {heads} "
                         f"heads in groups of {group} over {kv_heads} "
                         "key/value heads: no whole number of one of them")
    at = jnp.asarray(first, jnp.int32).reshape(1)
    tiles = jnp.minimum(_tiles(at[0], q // block - 1, block, tile), t // tile)

    def key_tile(i, g, j, at):
        # above the diagonal nothing is computed: name the tile that is
        # already there, so that nothing is fetched either
        return jnp.minimum(j, _tiles(at[0], i, block, tile) - 1)

    def by_query(width):
        return pl.BlockSpec((group, block, width), lambda i, g, j, at: (g, i, 0))

    def by_key(width):
        return pl.BlockSpec((group // rep, tile, width),
                            lambda i, g, j, at: (g, key_tile(i, g, j, at), 0))

    rotary_specs, rotary_args = [], []
    if rotary:
        dr = q_pe.shape[-1]
        rotary_specs = [by_query(dr), pl.BlockSpec(
            (tile, dr), lambda i, g, j, at: (key_tile(i, g, j, at), 0))]
        rotary_args = [q_pe, k_pe]
    key_specs = [by_key(dn), by_key(dv)]
    return pl.pallas_call(
        functools.partial(_attention_kernel, sub=sub, rep=rep, rotary=rotary,
                          precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(q // block, heads // group, tiles),
            in_specs=[by_query(dn), *rotary_specs[:1], *key_specs,
                      *rotary_specs[1:],
                      pl.BlockSpec((block, tile), lambda i, g, j, at: (
                          i, key_tile(i, g, j, at))),
                      pl.BlockSpec((block, 2), lambda i, g, j, at: (i, 0))],
            out_specs=by_query(dv),
            scratch_shapes=[pltpu.VMEM((group, 1, block), jnp.float32),
                            pltpu.VMEM((group, 1, block), jnp.float32),
                            pltpu.VMEM((group, dv, block), jnp.float32),
                            pltpu.VMEM((tile, block), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((heads, q, dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=ATTENTION_NAME,
    )(at, q_nope, *rotary_args[:1], k_nope, v, *rotary_args[1:], scores, sel)


def sparse_attention(q_nope, q_pe, k_nope, v, k_pe, scores, sel, first=0, *,
                     precision=None, force: Optional[object] = None):
    if _use_kernel(force):
        return sparse_attention_kernel(q_nope, q_pe, k_nope, v, k_pe, scores,
                                       sel, first,
                                       interpret=(force == "interpret"),
                                       precision=precision)
    return sparse_attention_plain(q_nope, q_pe, k_nope, v, k_pe, scores, sel,
                                  first, precision=precision)
