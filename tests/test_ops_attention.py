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


def _full(q, k, v, causal=True):
    qh = q.reshape(ROWS, T, HEADS, HD)
    kh, vh = (jnp.repeat(u.reshape(ROWS, T, KV, HD), HEADS // KV, axis=2)
              for u in (k, v))
    s = jnp.einsum("rqhd,rkhd->rhqk", qh, kh)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    return jnp.einsum("rhqk,rkhd->rqhd", jax.nn.softmax(s, -1),
                      vh).reshape(ROWS, T, HEADS * HD)


FORMS = {
    "jax.numpy": lambda q, k, v, **kw: attention.attention_blocked(
        q, k, v, heads=HEADS, kv_heads=KV, **kw),
    "kernel, interpreted": lambda q, k, v, **kw: attention.attention_kernel(
        q, k, v, heads=HEADS, kv_heads=KV, interpret=True, **kw),
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
