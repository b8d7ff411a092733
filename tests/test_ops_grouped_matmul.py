"""The expert layer's grouped product (``sparkdl_tpu.ops.grouped_matmul``):
the ``jax.lax.ragged_dot`` form and the Pallas kernel in interpret mode
against a loop over the experts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.ops import grouped_matmul as gm

K, F, N, GROUPS, TILE = 16, 256, 24, 5, 8


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _weights(groups=GROUPS, seed=0, dtype=jnp.float32):
    a, b = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(a, (groups, K, 2 * F), dtype) / K ** 0.5,
            jax.random.normal(b, (groups, F, N), dtype) / F ** 0.5)


def _expert(rows, gate_up, down):
    rows, gate_up, down = (np.asarray(v, np.float32)
                           for v in (rows, gate_up, down))
    gate, up = np.split(rows @ gate_up, 2, axis=-1)
    return (gate / (1 + np.exp(-gate)) * up) @ down


def _laid_out(sizes, seed=0, dtype=jnp.float32):
    """Rows of every group, in order, in the slots the layout gives them."""
    sizes = np.asarray(sizes)
    slots = gm.slots_for(int(sizes.sum()), len(sizes), TILE)
    layout = gm.aligned_layout(jnp.asarray(sizes, jnp.int32), slots, TILE)
    rows = jax.random.normal(jax.random.PRNGKey(seed + 7),
                             (int(sizes.sum()) + 1, K), dtype)
    start = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    source = np.where(np.asarray(layout.slot_filled),
                      start[np.asarray(layout.slot_group)]
                      + np.asarray(layout.slot_rank), len(rows) - 1)
    return layout, rows[source], rows, start


FORMS = {
    "jax.numpy": lambda *a, **kw: gm.grouped_matmul(
        *a, tile=TILE, force=False, **kw),
    "kernel, interpreted": lambda *a, **kw: gm.grouped_matmul_kernel(
        *a, tile=TILE, block_f=128, interpret=True, **kw),
}

#: sizes by group: even; an empty expert in the middle and at both
#: ends; one expert taking every token; lengths that are no multiple of
#: the tile; nothing routed at all
CASES = {
    "even": [8, 8, 8, 8, 8],
    "empty experts": [0, 17, 0, 23, 0],
    "one takes all": [0, 0, 40, 0, 0],
    "no multiples of the tile": [3, 9, 1, 20, 7],
    "nothing routed": [0, 0, 0, 0, 0],
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("form", list(FORMS))
def test_the_grouped_product_is_a_loop_over_the_experts(form, case):
    sizes = CASES[case]
    layout, x, rows, start = _laid_out(sizes)
    gate_up, down = _weights()
    got = np.asarray(FORMS[form](x, gate_up, down, layout.tile_group,
                                 layout.tiles_in_use))
    assert got.shape == (x.shape[0], N)
    filled = np.asarray(layout.slot_filled)
    assert filled.sum() == sum(sizes)
    for g, size in enumerate(sizes):
        at = int(layout.first_slot[g])
        assert filled[at:at + size].all() or size == 0
        np.testing.assert_allclose(
            got[at:at + size],
            _expert(rows[start[g]:start[g] + size], gate_up[g], down[g]),
            atol=1e-5)


def test_every_group_starts_on_a_tile_of_its_own():
    """Sizes 3, 9, 0, 20 in tiles of 8: tile 0 is group 0's, tiles 1-2
    group 1's, tiles 3-5 group 3's; the empty group has none, and the
    tiles past the six in use name the last group again."""
    layout = gm.aligned_layout(jnp.asarray([3, 9, 0, 20]),
                               gm.slots_for(32, 4, 8), 8)
    assert int(layout.tiles_in_use) == 6
    assert list(map(int, layout.tile_group)) == [0, 1, 1, 3, 3, 3, 3, 3]
    assert list(map(int, layout.first_slot)) == [0, 8, 24, 24]
    filled = np.asarray(layout.slot_filled)
    assert filled.sum() == 32 and filled[:3].all() and not filled[3:8].any()
    assert list(map(int, layout.slot_rank[24:28])) == [0, 1, 2, 3]
    # the worst fall: every group one row over a tile
    assert gm.slots_for(4 * 9, 4, 8) >= 4 * 16


@pytest.mark.parametrize("form", list(FORMS))
def test_the_groups_matrices_start_where_first_group_says(form):
    """Three layers' experts stacked in one array: the second layer's are
    used in place, ``first_group`` a scalar of the program."""
    sizes = CASES["no multiples of the tile"]
    layout, x, rows, start = _laid_out(sizes, seed=1)
    gate_up, down = _weights(groups=3 * GROUPS, seed=1)
    run = jax.jit(lambda first: FORMS[form](
        x, gate_up, down, layout.tile_group, layout.tiles_in_use, first))
    got = np.asarray(run(GROUPS))
    for g, size in enumerate(sizes):
        at = int(layout.first_slot[g])
        np.testing.assert_allclose(
            got[at:at + size],
            _expert(rows[start[g]:start[g] + size], gate_up[GROUPS + g],
                    down[GROUPS + g]), atol=1e-5)


@pytest.mark.parametrize("form", list(FORMS))
def test_bfloat16_operands_accumulate_in_float32(form):
    """Both forms round where the program says: the products' operands
    and the gated activations to bfloat16, everything between float32."""
    sizes = CASES["empty experts"]
    bf16 = jnp.bfloat16
    layout, x, _, _ = _laid_out(sizes, seed=2, dtype=bf16)
    gate_up, down = _weights(seed=2, dtype=bf16)
    got = FORMS[form](x, gate_up, down, layout.tile_group,
                      layout.tiles_in_use, out_dtype=jnp.float32)
    assert got.dtype == jnp.float32
    g, at = 1, int(layout.first_slot[1])
    rows = np.asarray(x[at:at + sizes[g]], np.float32)
    gate, up = np.split(rows @ np.asarray(gate_up[g], np.float32), 2, -1)
    act = np.asarray(jnp.asarray(gate / (1 + np.exp(-gate)) * up, bf16),
                     np.float32)
    np.testing.assert_allclose(np.asarray(got[at:at + sizes[g]]),
                               act @ np.asarray(down[g], np.float32),
                               atol=2e-3)


def test_the_platform_picks_the_form(monkeypatch):
    layout, x, _, _ = _laid_out(CASES["even"])
    gate_up, down = _weights()
    calls = []
    monkeypatch.setattr(gm, "grouped_matmul_kernel",
                        lambda *a, **kw: calls.append(kw) or "kernel")
    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    args = (x, gate_up, down, layout.tile_group, layout.tiles_in_use)
    assert gm.grouped_matmul(*args, tile=TILE) == "kernel"
    assert calls[0]["interpret"] is False
    monkeypatch.setattr(gm, "_on_tpu", lambda: False)
    assert gm.grouped_matmul(*args, tile=TILE).shape == (x.shape[0], N)
