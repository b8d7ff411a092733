"""The chunked state-space scan (``sparkdl_tpu.ops.ssd``) against the
recurrence written token by token: the ``jax.numpy`` form and the Pallas
kernel in interpret mode, at several chunk sizes, with a state that has
to cross the chunks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.ops import ssd

ROWS, T, HEADS, P, GROUPS, N = 2, 32, 4, 16, 2, 16


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(seed=0, t=T):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (ROWS, t, HEADS, P))
    # dt log-uniform in [1e-3, 0.3] and A in [-16, -1]: states that last
    # hundreds of positions beside states that fade within one
    dt = jnp.exp(jax.random.uniform(k[1], (ROWS, t, HEADS),
                                    minval=np.log(1e-3), maxval=np.log(0.3)))
    a = -jnp.exp(jax.random.uniform(k[2], (HEADS,), maxval=np.log(16.0)))
    b = jax.random.normal(k[3], (ROWS, t, GROUPS, N))
    c = jax.random.normal(k[4], (ROWS, t, GROUPS, N))
    d = jax.random.normal(k[5], (HEADS,))
    return x, dt, a, b, c, d


def _forms(chunk):
    return {
        "jax.numpy": lambda *a: ssd.ssd_chunked(*a, chunk),
        "kernel, interpreted": lambda *a: ssd.ssd_scan_kernel(
            *a, chunk=chunk, interpret=True),
    }


@pytest.mark.parametrize("chunk", [4, 8, T])
@pytest.mark.parametrize("form", ["jax.numpy", "kernel, interpreted"])
def test_chunked_form_is_the_recurrence(form, chunk):
    args = _inputs()
    want = np.asarray(ssd.ssd_recurrence(*args))
    got = np.asarray(_forms(chunk)[form](*args))
    # reduction order alone differs: float32 sums of a few hundred terms
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


def test_kernel_is_the_jax_numpy_form():
    args = _inputs(seed=3)
    np.testing.assert_allclose(
        np.asarray(ssd.ssd_scan_kernel(*args, chunk=8, interpret=True)),
        np.asarray(ssd.ssd_chunked(*args, 8)), atol=1e-5)


@pytest.mark.parametrize("form", ["jax.numpy", "kernel, interpreted"])
def test_the_state_is_carried_across_chunks(form):
    """Only the first chunk of ``x`` is non-zero, and the last chunk's
    output still moves with it: what it reads came through every carry
    between."""
    x, dt, a, b, c, d = _inputs(seed=1)
    chunk = 8
    dt = jnp.full_like(dt, 0.01)        # decay 0.85..0.99 a position
    x = x.at[:, chunk:].set(0.0)
    run = _forms(chunk)[form]
    y = np.asarray(run(x, dt, a, b, c, d))
    want = np.asarray(ssd.ssd_recurrence(x, dt, a, b, c, d))
    last = np.abs(want[:, -chunk:]).max()
    assert last > 1e-3                  # the oracle itself sees the carry
    np.testing.assert_allclose(y[:, -chunk:], want[:, -chunk:],
                               atol=1e-4 * last)
    silent = np.asarray(run(jnp.zeros_like(x), dt, a, b, c, d))
    assert np.abs(silent[:, -chunk:]).max() == 0.0


def test_bfloat16_inputs_stay_near_the_float32_recurrence():
    args = _inputs(seed=2)
    want = np.asarray(ssd.ssd_recurrence(*args))
    low = tuple(v.astype(jnp.bfloat16) if v.ndim == 4 else v for v in args)
    for run in _forms(8).values():
        got = np.asarray(run(*low).astype(jnp.float32))
        assert got.dtype == np.float32 and run(*low).dtype == jnp.bfloat16
        # bfloat16 operands: 2**-8 of the scale a term, a few terms deep
        assert np.abs(got - want).max() < 0.03 * np.abs(want).max()


def test_positions_that_are_no_multiple_of_the_chunk_are_refused():
    with pytest.raises(ValueError, match="no multiple"):
        ssd.ssd_chunked(*_inputs(t=12), 8)


def test_the_platform_picks_the_form(monkeypatch):
    args = _inputs()
    calls = []
    monkeypatch.setattr(ssd, "ssd_scan_kernel",
                        lambda *a, **k: calls.append(k) or "kernel")
    monkeypatch.setattr(ssd, "_on_tpu", lambda: True)
    assert ssd.ssd_scan(*args, chunk=8) == "kernel"
    assert calls[0]["interpret"] is False
    monkeypatch.setattr(ssd, "_on_tpu", lambda: False)
    assert ssd.ssd_scan(*args, chunk=8).shape == args[0].shape
