"""A block's queries against a carried key/value cache
(``sparkdl_tpu.ops.cache_attention``): the Pallas kernel in interpret
mode against the ``jax.numpy`` form, on a cache of several layers whose
OTHER layers and whose positions at or past ``filled`` hold large finite
garbage, so that a read out of place shows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.ops import cache_attention as ca

#: four rows (one step of the kernel takes them together), blocks of 4
#: queries, three layers of 48 positions: the largest tile that divides
#: 48 is 16, so a row's cache is three tiles
ROWS, B, DEPTH, T, HD, TILE = 4, 4, 3, 48, 16, 16
GARBAGE = 1e4


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _operands(heads, kv, dtype, layer, filled, queries=B, own_keys=B):
    ks = jax.random.split(jax.random.PRNGKey(filled + 7 * layer), 5)
    q = 2 * HD ** -0.5 * jax.random.normal(ks[0], (ROWS, queries, heads * HD))
    k = jax.random.normal(ks[1], (ROWS, own_keys, kv * HD))
    v = jax.random.normal(ks[2], (ROWS, own_keys, kv * HD))
    out_of_place = ((jnp.arange(T)[None, None, :, None] >= filled)
                    | (jnp.arange(DEPTH)[:, None, None, None] != layer))
    cache_k = jnp.where(out_of_place, GARBAGE, jax.random.normal(
        ks[3], (DEPTH, ROWS, T, kv * HD)))
    cache_v = jnp.where(out_of_place, -GARBAGE, jax.random.normal(
        ks[4], (DEPTH, ROWS, T, kv * HD)))
    return tuple(a.astype(dtype) for a in (q, k, v, cache_k, cache_v))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rep", [1, 8])
@pytest.mark.parametrize("layer", [0, DEPTH - 1])
@pytest.mark.parametrize("filled", [B, TILE - 1, TILE, TILE + B, T - B])
def test_the_kernel_is_the_jax_numpy_form(filled, layer, rep, dtype):
    """``filled`` at a block, one short of a tile, a tile, a tile and a
    block, and the whole cache but its last block; the first and the
    last layer; one and eight query heads a key/value head."""
    kv = 2
    heads = kv * rep
    q, k, v, cache_k, cache_v = _operands(heads, kv, dtype, layer, filled)
    assert ca.key_tile(B, B, T, "interpret") == TILE
    kw = dict(heads=heads, kv_heads=kv)
    got = ca.cache_attention(q, k, v, cache_k, cache_v, layer, filled,
                             force="interpret", **kw)
    want = ca.cache_attention_plain(q, k, v, cache_k, cache_v, layer, filled,
                                    **kw)
    assert got.shape == want.shape == q.shape and got.dtype == q.dtype
    # rounding alone: an unseen position carries 1e4
    atol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)
    assert np.abs(np.asarray(want, np.float32)).max() < 10
    # a row alone (a step of its own) is the row in the batch (four a step)
    assert ROWS == ca.ROWS
    alone = ca.cache_attention(
        q[1:2], k[1:2], v[1:2], cache_k[:, 1:2], cache_v[:, 1:2], layer,
        filled, force="interpret", **kw)
    np.testing.assert_array_equal(np.asarray(alone, np.float32),
                                  np.asarray(got[1:2], np.float32))


def test_other_shapes_fall_to_the_jax_numpy_form():
    """One query against two own keys (what ``benchmark/tests/
    planted_diffusion`` asks of ``block_diffusion._attend_cache``) and a
    cache no tile divides: no kernel, decided from the shapes."""
    assert ca.key_tile(1, 2, T, True) is None
    assert ca.key_tile(B, B, T + 4, True) is None
    assert ca.key_tile(B, B, T, False) is None
    assert ca.key_tile(B, B, 1280, True) == ca.TILE
    q, k, v, cache_k, cache_v = _operands(4, 2, "float32", 1, 20, queries=1,
                                          own_keys=2)
    kw = dict(heads=4, kv_heads=2)
    jaxpr = jax.make_jaxpr(lambda *a: ca.cache_attention(
        *a, 1, 20, force="interpret", **kw))(q, k, v, cache_k, cache_v)
    assert "pallas_call" not in str(jaxpr)
    got = ca.cache_attention(q, k, v, cache_k, cache_v, 1, 20,
                             force="interpret", **kw)
    want = ca.cache_attention_plain(q, k, v, cache_k, cache_v, 1, 20, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError, match="as many own keys"):
        ca.cache_attention_kernel(q, k, v, cache_k, cache_v, 1, 20, tile=TILE,
                                  interpret=True, **kw)


@pytest.mark.parametrize("filled", [0, 1, TILE - 1, TILE, TILE + 1, T])
def test_the_fetched_positions_are_the_kernels_key_axis(filled):
    """Tiles x tile where the kernel runs (one tile at least), the whole
    cache where the ``jax.numpy`` form does."""
    tiles = max(-(-filled // TILE), 1)
    assert int(ca.fetched_positions(jnp.int32(filled), T, TILE)) == tiles * TILE
    assert int(ca._key_tiles(jnp.int32(filled), TILE)) == tiles
    assert int(ca.fetched_positions(jnp.int32(filled), T, None)) == T
