"""Learned sparse attention (``sparkdl_tpu.ops.sparse_attention``): the
indexer's scores and the exact selection against ``lax.top_k``, ties
included; the three Pallas kernels in interpret mode against the
``jax.numpy`` forms; nothing attended outside the selection."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from sparkdl_tpu.ops import sparse_attention as sa

#: 32 queries from position 16 of a row of 64, 4 indexer heads of 32,
#: the top 8 kept: the first queries see fewer than 8 keys, the rest
#: choose; blocks of 8 queries and tiles of 16 keys in the kernels
T, Q, FIRST, HI, D, TOP = 64, 32, 16, 4, 32, 8
H, DN, DR, DV = 4, 16, 8, 16


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _index_operands(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (Q, HI, D)),
            jax.random.normal(ks[1], (T, D)), jax.random.normal(ks[2], (Q, HI)))


def _positions(first=FIRST, queries=Q):
    return (first + np.arange(queries)[:, None], np.arange(T)[None, :])


def _top_k_mask(scores, first=FIRST, top=TOP):
    """The selection ``lax.top_k`` makes: the keys after a query at
    -inf, the ``min(t + 1, top)`` first of its answer."""
    query_at, key_at = _positions(first, scores.shape[0])
    causal = key_at <= query_at
    _, chosen = lax.top_k(jnp.where(causal, scores, -jnp.inf), top)
    mask = np.zeros(scores.shape, bool)
    for row in range(scores.shape[0]):
        n = min(int(query_at[row, 0]) + 1, top)
        mask[row, np.asarray(chosen[row, :n])] = True
    return mask


def _mask(scores, sel, first=FIRST):
    query_at, key_at = _positions(first, scores.shape[0])
    return np.asarray(sa.selected(scores, sel, jnp.asarray(query_at),
                                  jnp.asarray(key_at)))


def test_indexer_scores_are_the_weighted_relu_sum():
    q, k, w = _index_operands()
    got = sa.indexer_scores(q, k, w, FIRST, force=False)
    want = np.einsum("qh,qhk->qk", np.asarray(w),
                     np.maximum(np.einsum("qhd,kd->qhk", q, k), 0))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("first", [0, FIRST])
def test_the_indexer_kernel_is_the_jax_numpy_form(first):
    """Above the diagonal the kernel writes 0 and fetches nothing; below
    it the sums are the plain form's."""
    q, k, w = _index_operands(1)
    got = sa.indexer_scores_kernel(q, k, w, first, block=8, tile=16,
                                   interpret=True)
    want = sa.indexer_scores(q, k, w, first, force=False)
    query_at, key_at = _positions(first)
    causal = key_at <= query_at
    np.testing.assert_allclose(np.where(causal, got, 0),
                               np.where(causal, want, 0), atol=1e-4)
    # whole tiles past a block's last query are written as 0
    assert np.all(np.asarray(got)[:8, 32:] == 0) if first == 0 else True


def _tied_by_hand(scores, first=FIRST, top=TOP):
    """``[Q]`` bool: the queries that leave out a key of the same score
    (the same float bits) as the last of ``lax.top_k``'s ``min(t + 1,
    top)`` — ties on both sides of the boundary."""
    query_at, key_at = _positions(first, scores.shape[0])
    causal = key_at <= query_at
    _, chosen = lax.top_k(jnp.where(causal, scores, -jnp.inf), top)
    bits = np.asarray(scores, np.float32).view(np.int32)
    tied = np.zeros(scores.shape[0], bool)
    for row in range(scores.shape[0]):
        n = min(int(query_at[row, 0]) + 1, top)
        taken = np.zeros(scores.shape[1], bool)
        taken[np.asarray(chosen[row, :n])] = True
        boundary = bits[row, int(chosen[row, n - 1])]
        tied[row] = np.any(causal[row] & ~taken & (bits[row] == boundary))
    return tied


@pytest.mark.parametrize("first, top, lanes, fold, quantum", [
    (0, TOP, 16, 16, None),     # the first chunk: prefixes end inside one
    (FIRST, TOP, 16, 8, None),  # a middle chunk of queries
    (32, TOP, 16, 16, None),    # the last: the prefix reaches the row's end
    (12, TOP, 16, 16, None),    # a block's queries on both sides of a chunk
    (FIRST, TOP, 32, 8, None),  # prefixes of 24 and 40 keys, chunks of 32
    (FIRST, TOP, T, 16, None),  # the whole row one chunk
    (FIRST, T, 16, 16, None),   # every block below top: all its keys
    (0, TOP, 16, 16, 0.5),      # halves: two blocks need the tie passes
    (FIRST, TOP, 32, 8, 0.5),
    (FIRST, TOP, 16, 16, 4.0),  # fours: ties in every block
])
def test_the_selection_is_top_k_exactly_ties_included(first, top, lanes,
                                                      fold, quantum):
    """Scores as drawn (zeros among them: every head's ReLU at 0), and
    rounded to halves and to fours so that many ties straddle the
    boundary (zeros of both signs among them): the same keys as
    ``lax.top_k``, the lower position first among equals, never a key
    after the query, ``min(t + 1, top)`` of them; the kernel, counting
    over each block's causal prefix ``lanes`` keys at a time, is the
    plain form bit for bit (its partial counts ``fold`` lanes wide)."""
    q, k, w = _index_operands(2)
    scores = sa.indexer_scores(q, k, w, first, force=False)
    if quantum is not None:
        scores = jnp.round(scores / quantum) * quantum
    sel = sa.topk_select(scores, first, top, force=False)
    mask = _mask(scores, sel, first)
    np.testing.assert_array_equal(mask, _top_k_mask(scores, first, top))
    query_at, _ = _positions(first)
    np.testing.assert_array_equal(mask.sum(1),
                                  np.minimum(query_at[:, 0] + 1, top))
    kernel = sa.topk_select_kernel(scores, first, top=top, block=8,
                                   lanes=lanes, fold=fold, interpret=True)
    np.testing.assert_array_equal(np.asarray(kernel), np.asarray(sel))
    # ``last`` is the query's own position wherever no tie straddles
    tied = _tied_by_hand(scores, first, top)
    np.testing.assert_array_equal(np.asarray(sel[:, 1]) < query_at[:, 0],
                                  tied)
    if (first, quantum) == (0, 0.5):
        by_block = tied.reshape(-1, 8).any(1)
        assert by_block.any() and not by_block.all(), by_block


@pytest.mark.parametrize("quantum", [None, 0.5, 4.0])
@pytest.mark.parametrize("force", [False, "interpret"])
def test_the_count_of_tied_queries_is_the_hand_count(force, quantum):
    """``tied_queries`` — what ``sparse.tied_queries`` sums — against the
    queries with ties on both sides of ``lax.top_k``'s last key, on the
    plain path and the kernel's."""
    q, k, w = _index_operands(2)
    scores = sa.indexer_scores(q, k, w, FIRST, force=False)
    if quantum is not None:
        scores = jnp.round(scores / quantum) * quantum
    sel = sa.topk_select(scores, FIRST, TOP, force=force)
    want = int(_tied_by_hand(scores).sum())
    assert int(sa.tied_queries(sel, FIRST)) == want
    if quantum is not None:
        assert want > 0


@pytest.mark.parametrize("lanes, fold", [(24, 8), (32, 12)])
def test_chunks_must_divide_the_row_and_folds_the_chunk(lanes, fold):
    scores = jnp.zeros((Q, T))
    with pytest.raises(ValueError, match=f"chunks of {lanes} counted {fold}"):
        sa.topk_select_kernel(scores, 0, top=TOP, lanes=lanes, fold=fold,
                              interpret=True)


def test_the_selection_with_every_score_equal_takes_the_first_keys():
    scores = jnp.zeros((Q, T))
    mask = _mask(scores, sa.topk_select(scores, FIRST, TOP, force=False))
    np.testing.assert_array_equal(mask, _top_k_mask(scores))
    assert mask[-1].nonzero()[0].tolist() == list(range(TOP))


def _attention_operands(seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (0.3 * jax.random.normal(ks[0], (H, Q, DN)),
            0.3 * jax.random.normal(ks[1], (H, Q, DR)),
            jax.random.normal(ks[2], (H, T, DN)),
            jax.random.normal(ks[3], (H, T, DV)),
            jax.random.normal(ks[4], (T, DR)))


@pytest.mark.parametrize("block, tile, group, sub", [
    (8, 16, 2, 16),     # a tile a piece, two heads a step
    (8, 32, 4, 16),     # two sub-tiles a tile, every head in one step
    (16, 16, 1, 8),     # two sub-tiles, one head a step
    (32, 64, 2, 16),    # four sub-tiles, one block of all the queries
])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("first", [0, FIRST])
def test_the_attention_kernel_is_the_jax_numpy_form(first, dtype, block, tile,
                                                    group, sub):
    """The step's (head, sub-tile) pieces, pipelined, against the plain
    form: from position 0 (the first block's partial diagonal) and from
    ``FIRST``, every causal tile visited and none after the last block's
    diagonal."""
    q, k, w = _index_operands(4)
    scores = sa.indexer_scores(q, k, w, first, force=False)
    sel = sa.topk_select(scores, first, TOP, force=False)
    ops = [a.astype(dtype) for a in _attention_operands()]
    got = sa.sparse_attention_kernel(*ops, scores, sel, first, block=block,
                                     tile=tile, group=group, sub=sub,
                                     interpret=True)
    want = sa.sparse_attention(*ops, scores, sel, first, force=False)
    assert got.shape == want.shape == (H, Q, DV) and got.dtype == want.dtype
    atol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_nothing_outside_the_selection_is_attended():
    """The plain form against the softmax over the selected keys alone,
    written out; and keys that were not chosen may be anything."""
    q, k, w = _index_operands(5)
    scores = sa.indexer_scores(q, k, w, FIRST, force=False)
    sel = sa.topk_select(scores, FIRST, TOP, force=False)
    mask = _mask(scores, sel)
    q_nope, q_pe, k_nope, v, k_pe = _attention_operands()
    got = sa.sparse_attention(q_nope, q_pe, k_nope, v, k_pe, scores, sel,
                              FIRST, force=False)
    s = (np.einsum("hqd,hkd->hqk", q_nope, k_nope)
         + np.einsum("hqd,kd->hqk", q_pe, k_pe))
    s = np.where(mask[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("hqk,hkd->hqd", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    garbage = jnp.where(jnp.asarray(mask).any(0)[None, :, None], v, 1e4)
    other = sa.sparse_attention_kernel(q_nope, q_pe, k_nope, garbage, k_pe,
                                       scores, sel, FIRST, block=8, tile=16,
                                       group=2, interpret=True)
    np.testing.assert_allclose(np.asarray(other), want, atol=1e-4)


def test_keys_past_the_last_diagonal_are_never_read():
    """Keys and values after the last query's tile are NaN: the kernel's
    key axis ends at that tile, and before it the index maps name causal
    tiles only."""
    q, k, w = _index_operands(6)
    scores = sa.indexer_scores(q, k, w, 0, force=False)
    sel = sa.topk_select(scores, 0, TOP, force=False)
    q_nope, q_pe, k_nope, v, k_pe = _attention_operands()
    want = sa.sparse_attention(q_nope, q_pe, k_nope, v, k_pe, scores, sel, 0,
                               force=False)
    after = jnp.arange(T)[None, :, None] >= Q      # past position Q - 1
    k_nope, v = (jnp.where(after, jnp.nan, a) for a in (k_nope, v))
    k_pe = jnp.where(after[0], jnp.nan, k_pe)
    got = sa.sparse_attention_kernel(q_nope, q_pe, k_nope, v, k_pe, scores,
                                     sel, 0, block=8, tile=16, group=2,
                                     sub=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_a_sub_tile_must_divide_the_tile():
    ops = _attention_operands()
    scores = jnp.zeros((Q, T))
    sel = sa.topk_select(scores, 0, TOP, force=False)
    with pytest.raises(ValueError, match="tiles of 16 in sub-tiles of 12"):
        sa.sparse_attention_kernel(*ops, scores, sel, 0, block=8, tile=16,
                                   sub=12, interpret=True)


def test_the_counts_of_pairs_and_fetched_keys():
    """Of 32 queries from 16 in blocks of 8, tiles of 16 keys: the
    indexer's causal pairs, the selected pairs, and the kernel's key rows
    (every causal tile of a query's block) against the plain form's
    (every key)."""
    assert int(sa.indexed_pairs(FIRST, Q)) == sum(
        t + 1 for t in range(FIRST, FIRST + Q))
    assert int(sa.selected_pairs(FIRST, Q, TOP)) == sum(
        min(t + 1, TOP) for t in range(FIRST, FIRST + Q))
    assert int(sa.selected_pairs(0, 4, TOP)) == 1 + 2 + 3 + 4
    assert int(sa.fetched_keys(FIRST, Q, T, force=False)) == Q * T
    # the cell's blocks: 256 queries, tiles of 512 keys
    t, q = 32768, 4096
    got = int(sa.fetched_keys(q, q, t, force=True))
    blocks = q // sa.QUERIES
    want = sum(sa.QUERIES * sa.KEYS * ((q + (i + 1) * sa.QUERIES - 1)
                                       // sa.KEYS + 1) for i in range(blocks))
    assert got == want
    # a whole row fetches about the causal half, never all of it
    whole = sum(int(sa.fetched_keys(f, q, t, force=True))
                for f in range(0, t, q))
    assert t * (t + 1) // 2 < whole < t * t


# -- grouped-query heads, no rotary part ---------------------------------------

KV = 2


def _grouped_operands(seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (0.3 * jax.random.normal(ks[0], (H, Q, DN)),
            jax.random.normal(ks[1], (KV, T, DN)),
            jax.random.normal(ks[2], (KV, T, DV)))


@pytest.mark.parametrize("block, tile, group, sub", [
    (8, 16, 2, 16),     # one key/value head a step
    (8, 32, 4, 16),     # both key/value heads in one step
    (32, 64, 2, 16),    # one block of all the queries
])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_grouped_heads_read_their_key_value_head_in_place(dtype, block, tile,
                                                          group, sub):
    """``kv_heads`` < heads and no rotary part: the kernel against the
    plain form, and the plain form against every key/value head repeated
    for its query heads (the per-head form the module had)."""
    q, k, w = _index_operands(8)
    scores = sa.indexer_scores(q, k, w, FIRST, force=False)
    sel = sa.topk_select(scores, FIRST, TOP, force=False)
    q_g, k_g, v_g = (a.astype(dtype) for a in _grouped_operands())
    want = sa.sparse_attention(q_g, None, k_g, v_g, None, scores, sel, FIRST,
                               force=False)
    got = sa.sparse_attention_kernel(q_g, None, k_g, v_g, None, scores, sel,
                                     FIRST, block=block, tile=tile,
                                     group=group, sub=sub, interpret=True)
    assert got.shape == want.shape == (H, Q, DV)
    atol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)
    repeated = [jnp.repeat(a, H // KV, axis=0) for a in (k_g, v_g)]
    zeros_q, zeros_k = jnp.zeros((H, Q, DR), dtype), jnp.zeros((T, DR), dtype)
    per_head = sa.sparse_attention(q_g, zeros_q, repeated[0], repeated[1],
                                   zeros_k, scores, sel, FIRST, force=False)
    np.testing.assert_allclose(np.asarray(want, np.float32),
                               np.asarray(per_head, np.float32), atol=atol)


def test_a_group_must_hold_whole_key_value_heads():
    q_g, k_g, v_g = _grouped_operands()
    scores = jnp.zeros((Q, T))
    sel = sa.topk_select(scores, 0, TOP, force=False)
    with pytest.raises(ValueError, match="over 2 key/value heads"):
        sa.sparse_attention_kernel(q_g, None, k_g, v_g, None, scores, sel, 0,
                                   block=8, tile=16, group=1, interpret=True)
