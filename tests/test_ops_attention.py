"""Causal grouped-query attention (``sparkdl_tpu.ops.attention``): the
blocked ``jax.numpy`` form and the Pallas kernel in interpret mode
against a full softmax over the whole score matrix."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.ops import attention

ROWS, T, HEADS, KV, HD = 2, 32, 4, 2, 16


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _qkv(seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (2 * jax.random.normal(k[0], (ROWS, T, HEADS * HD)),
            jax.random.normal(k[1], (ROWS, T, KV * HD)),
            jax.random.normal(k[2], (ROWS, T, KV * HD)))


def _full(q, k, v, causal=True, window=None):
    qh = q.reshape(ROWS, T, HEADS, HD)
    kh, vh = (jnp.repeat(u.reshape(ROWS, T, KV, HD), HEADS // KV, axis=2)
              for u in (k, v))
    s = jnp.einsum("rqhd,rkhd->rhqk", qh, kh)
    if causal:
        seen = jnp.tril(jnp.ones((T, T), bool))
        if window is not None:
            at = jnp.arange(T)
            seen &= at[:, None] - at[None, :] < window
        s = jnp.where(seen, s, -jnp.inf)
    return jnp.einsum("rhqk,rkhd->rqhd", jax.nn.softmax(s, -1),
                      vh).reshape(ROWS, T, HEADS * HD)


FORMS = {
    "jax.numpy": lambda q, k, v, **kw: attention.attention_blocked(
        q, k, v, heads=HEADS, kv_heads=KV, **kw),
    "kernel, interpreted": lambda q, k, v, window=None, **kw:
        attention.attention_kernel(q, k, v, window, heads=HEADS, kv_heads=KV,
                                   interpret=True, **kw),
}


@pytest.mark.parametrize("block", [8, 16, T])
@pytest.mark.parametrize("form", list(FORMS))
def test_blocked_attention_is_the_full_softmax(form, block):
    q, k, v = _qkv()
    got = FORMS[form](q, k, v, block=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_full(q, k, v)),
                               atol=1e-5)


@pytest.mark.parametrize("form", list(FORMS))
def test_causal_false_sees_every_position(form):
    q, k, v = _qkv(1)
    got = FORMS[form](q, k, v, block=8, causal=False)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_full(q, k, v, causal=False)), atol=1e-5)
    assert np.abs(np.asarray(got - _full(q, k, v))).max() > 1e-2


@pytest.mark.parametrize("window", [1, 5, 8, 12, 16, T, T + 9])
@pytest.mark.parametrize("form", list(FORMS))
def test_a_window_is_the_masked_full_softmax(form, window):
    """Blocks of 8: a window under the block, the block, between two
    blocks, two blocks, the whole row and past it."""
    q, k, v = _qkv(3)
    got = FORMS[form](q, k, v, block=8, window=window)
    want = _full(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    if window < T:
        assert np.abs(np.asarray(want - _full(q, k, v))).max() > 1e-2


@pytest.mark.parametrize("form", list(FORMS))
def test_no_window_is_bit_equal_to_a_window_past_the_row(form):
    """``window=None`` is today's program; a window no query can reach
    the end of computes the same sums in the same order."""
    q, k, v = _qkv(4)
    none = np.asarray(FORMS[form](q, k, v, block=8))
    np.testing.assert_array_equal(
        none, np.asarray(FORMS[form](q, k, v, block=8, window=None)))
    np.testing.assert_array_equal(
        none, np.asarray(FORMS[form](q, k, v, block=8, window=T)))


@pytest.mark.parametrize("form", list(FORMS))
def test_the_window_is_data(form):
    """One compiled program, windows of both kinds of layer: the width
    is an operand (and, in the kernel, the grid's key axis with it)."""
    q, k, v = _qkv(5)
    run = jax.jit(lambda w: FORMS[form](q, k, v, block=8, window=w))
    for window in (5, 12, T):
        np.testing.assert_allclose(
            np.asarray(run(jnp.int32(window))),
            np.asarray(_full(q, k, v, window=window)), atol=1e-5)
    assert run._cache_size() == 1


def test_a_window_needs_causal_attention():
    q, k, v = _qkv()
    for form in FORMS.values():
        with pytest.raises(ValueError, match="window"):
            form(q, k, v, block=8, window=4, causal=False)


@pytest.mark.parametrize("form", list(FORMS))
def test_a_query_head_reads_its_own_key_value_head(form):
    """Changing key/value head 1 moves query heads 2 and 3 and leaves
    heads 0 and 1 as they were."""
    q, k, v = _qkv(2)
    before = np.asarray(FORMS[form](q, k, v, block=8))
    after = np.asarray(FORMS[form](q, k, v.at[..., HD:].add(1.0), block=8))
    moved = np.abs(after - before).reshape(ROWS, T, HEADS, HD).max((0, 1, 3))
    assert (moved[:2] == 0).all() and (moved[2:] > 0.5).all()


def test_the_platform_picks_the_form(monkeypatch):
    q, k, v = _qkv()
    calls = []
    monkeypatch.setattr(attention, "attention_kernel",
                        lambda *a, **kw: calls.append(kw) or "kernel")
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert attention.causal_attention(q, k, v, heads=HEADS,
                                      kv_heads=KV) == "kernel"
    assert calls[0]["interpret"] is False
    monkeypatch.setattr(attention, "_on_tpu", lambda: False)
    assert attention.causal_attention(q, k, v, heads=HEADS,
                                      kv_heads=KV).shape == q.shape


# -- the mask by blocks (a static block length) ------------------------------

def _full_by_blocks(q, k, v, block_length):
    qh = q.reshape(ROWS, T, HEADS, HD)
    kh, vh = (jnp.repeat(u.reshape(ROWS, T, KV, HD), HEADS // KV, axis=2)
              for u in (k, v))
    at = jnp.arange(T)
    seen = at[None, :] // block_length <= at[:, None] // block_length
    s = jnp.where(seen, jnp.einsum("rqhd,rkhd->rhqk", qh, kh), -jnp.inf)
    return jnp.einsum("rhqk,rkhd->rqhd", jax.nn.softmax(s, -1),
                      vh).reshape(ROWS, T, HEADS * HD)


@pytest.mark.parametrize("block_length", [2, 4, 8])
@pytest.mark.parametrize("block", [8, 16, T])
@pytest.mark.parametrize("form", list(FORMS))
def test_a_block_length_is_the_mask_by_blocks(form, block, block_length):
    """A position sees every earlier block and its own block both ways."""
    q, k, v = _qkv(5)
    got = FORMS[form](q, k, v, block=block, block_length=block_length)
    want = _full_by_blocks(q, k, v, block_length)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert np.abs(np.asarray(got - _full(q, k, v))).max() > 1e-2


@pytest.mark.parametrize("form", list(FORMS))
def test_block_length_1_is_the_causal_form_bit_for_bit(form):
    q, k, v = _qkv(6)
    np.testing.assert_array_equal(
        np.asarray(FORMS[form](q, k, v, block=8, block_length=1)),
        np.asarray(FORMS[form](q, k, v, block=8)))


def test_block_length_1_is_the_same_kernel_program():
    """Static and 1 by default: the kernel's program is the one the two
    sequence cells ran before there was a block length."""
    q, k, v = _qkv()
    lowered = [attention.attention_kernel.lower(
        q, k, v, heads=HEADS, kv_heads=KV, block=8, interpret=True,
        **kw).as_text() for kw in ({}, {"block_length": 1})]
    assert lowered[0] == lowered[1]


def test_a_block_length_fills_the_query_block_and_takes_no_window():
    q, k, v = _qkv()
    for form in FORMS.values():
        with pytest.raises(ValueError, match="do not fill"):
            form(q, k, v, block=8, block_length=3)
        with pytest.raises(ValueError, match="causal mask alone"):
            form(q, k, v, block=8, block_length=4, window=4)
        with pytest.raises(ValueError, match="causal mask alone"):
            form(q, k, v, block=8, block_length=4, causal=False)
