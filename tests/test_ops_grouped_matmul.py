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


# -- the tile follows the rows a group expects --------------------------------

@pytest.mark.parametrize("rows_a_group, tile", [
    (512, gm.TILE),                # trinity_large_preview.rows16k; the prefill
    (256, gm.TILE), (300, gm.TILE), (4096, gm.TILE),
    (16, 16),                      # a pass of sdar_30b_a3b_chat.gen256's loop
    (17, 32), (100, 128), (128, 128),        # rounded UP to a power of two
    (1, 16), (0.25, 16), (0, 16),            # the floor
], ids=lambda v: str(v))
def test_the_tile_follows_the_rows_a_group_expects(rows_a_group, tile):
    """Between bfloat16's sublane packing and the chip's ridge, a power
    of two: the kernel's blocks stay whole sublane groups."""
    assert gm.tile_for(rows_a_group) == tile
    assert (gm.MIN_TILE, gm.TILE) == (16, 256)


@pytest.mark.parametrize("k, f, n, itemsize, block", [
    (3072, 3072, 3072, 2, 512),    # trinity_large_preview: as before the rule
    (2048, 768, 2048, 2, 768),     # sdar_30b_a3b_chat: 9.4 MB an expert, whole
    (5120, 1536, 5120, 2, 512),    # too wide to hold whole
    # float32 operands are twice the bytes: the same rule, a narrower fit
    (2048, 768, 2048, 4, 512), (2048, 384, 2048, 4, 384),
], ids=lambda v: str(v))
def test_a_block_is_f_whole_where_an_experts_matrices_fit_twice(
        k, f, n, itemsize, block):
    assert gm.block_f_for(k, f, n, itemsize) == block


@pytest.mark.parametrize("block_f", [None, 128], ids=["F whole", "blocks"])
def test_the_smallest_tile_in_bfloat16_with_an_expert_on_two_tiles(block_f):
    """The kernel, interpreted, at ``MIN_TILE`` rows a tile against the
    ``ragged_dot`` form on the same layout: group 1 lies on two
    consecutive tiles and one row over them on a third (the steps whose
    weights stand still), group 3 is empty, and tiles past the ones in
    use are left alone."""
    bf16, tile = jnp.bfloat16, gm.MIN_TILE
    sizes = [5, 2 * tile + 1, tile, 0, 3]
    slots = gm.slots_for(sum(sizes), len(sizes), tile)
    layout = gm.aligned_layout(jnp.asarray(sizes, jnp.int32), slots, tile)
    assert list(map(int, layout.tile_group[:6])) == [0, 1, 1, 1, 2, 4]
    x = jax.random.normal(jax.random.PRNGKey(3), (slots, K), bf16)
    gate_up, down = _weights(seed=3, dtype=bf16)
    args = (x, gate_up, down, layout.tile_group, layout.tiles_in_use)
    got = gm.grouped_matmul_kernel(*args, tile=tile, block_f=block_f,
                                   out_dtype=jnp.float32, interpret=True)
    want = gm.grouped_matmul_reference(*args, tile=tile,
                                       out_dtype=jnp.float32)
    used = int(layout.tiles_in_use) * tile
    assert used == 6 * tile and got.dtype == jnp.float32
    # the same roundings; the order of the float32 sum over F alone differs
    np.testing.assert_allclose(np.asarray(got[:used]), np.asarray(want[:used]),
                               atol=2e-3)
    assert np.abs(np.asarray(want[:used])).max() > 0.1
