"""The matrix products of an expert layer, by group: every row through
the gated pair of matrices of the group (the expert) it belongs to::

    y[row] = (SiLU(x[row] W_gate[g]) * (x[row] W_up[g])) W_down[g],   g = group of row

A group's length is data, not shape: nothing is padded to a capacity and
nothing is dropped.  The rows lie in ``slots`` that are cut into tiles
of ``tile`` rows, and every group starts on a tile of its own
(``aligned_layout`` says where, from the groups' lengths), so a tile
belongs to ONE group and the kernel's grid step is one plain tile of a
matrix product with the group's matrices.  What that costs is the empty
end of each group's last tile (half a tile a group on average where a
group fills several, nearly the whole tile where it fills less than
one); what it saves is every mask inside the kernel.  So the tile
follows the rows a group is EXPECTED to get (``tile_for``, from the
caller's static shapes): ``TILE`` where a group expects that many or
more, down to ``MIN_TILE`` where it expects a handful — everything a
caller lays out by the slot (its gather, its weighting, its scatter-add)
costs by the slot, filled or not.  ``gate_up`` ``[E, K, 2F]`` holds
``W_gate | W_up`` side by side, ``down`` ``[E, F, N]``; both hold the
groups' matrices from ``first_group`` on (a scalar of the program: the
matrices of several layers lie stacked in one array and none is copied
out of it).  Slots that ``aligned_layout`` does not fill compute on
whatever they hold, and tiles past ``tiles_in_use`` are never written:
a caller reads only the slots it filled.

On the TPU one Pallas kernel (``name="grouped_matmul"``): grid tiles x
blocks of ``F``.  A step multiplies the tile by a block of ``W_gate``
and of ``W_up`` (``K`` whole in the block, float32 accumulation), gates
in float32, rounds to the operands' dtype and adds the product with the
block of ``W_down`` to the tile's float32 accumulator in VMEM: the
``[rows, F]`` activations never reach memory.  A block is ``F`` whole
where a group's three matrices fit VMEM twice over (``block_f_for``):
then consecutive tiles of one group stand still in EVERY index of the
weights and the group's matrices are fetched once, however many tiles
it fills; cut into blocks, every tile fetches them again.  Tiles past
``tiles_in_use`` name the last tile in use again, which fetches nothing,
and compute nothing.  Elsewhere two ``jax.lax.ragged_dot`` with the same
roundings.  The platform picks, as in ``ops/attention``.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "grouped_matmul"
#: rows of the largest tile: 256 operations a byte of weights, the
#: chip's ridge — what a group that expects 256 rows or more is given,
#: and ``tile_for``'s ceiling, not THE tile
TILE = 256
#: rows of the smallest: bfloat16 packs 16 rows to a sublane group (the
#: float32 accumulator and output want a multiple of 8)
MIN_TILE = 16
BLOCK_F = 512
#: two copies of each block at the sizes above and K, N of a few
#: thousand (the default scoped limit is 16 MiB of the chip's 128)
VMEM_LIMIT = 64 * 2**20
#: what both copies of a step's three weight blocks may take of it
WEIGHT_BLOCKS = 24 * 2**20


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


class Layout(NamedTuple):
    """Where the groups' rows lie (``aligned_layout``)."""
    tile_group: jax.Array      # [tiles] the group a tile belongs to
    tiles_in_use: jax.Array    # scalar: the tiles before it hold rows
    first_slot: jax.Array      # [groups] where a group's rows start
    slot_group: jax.Array      # [slots] the group a slot belongs to
    slot_rank: jax.Array       # [slots] which of its group's rows it holds
    slot_filled: jax.Array     # [slots] bool: it holds a row


def tile_for(rows_a_group: float) -> int:
    """The tile for groups that expect ``rows_a_group`` rows each under
    even routing: that, rounded up to a power of two, held between
    ``MIN_TILE`` and ``TILE``.  No room over the mean is worth its slots:
    at 16 rows a group a tile of 16 ran the generation loop in 3.55 s a
    dispatch, 32 in 3.70, 64 in 4.43, 128 in 6.05 (PERF.md, PR 38) — a
    group over the mean takes a second tile of its own matrices, which
    stand still in the kernel, and every slot costs its gather and its
    scatter-add, filled or not."""
    rows = max(1, math.ceil(rows_a_group))
    return min(TILE, max(MIN_TILE, 1 << (rows - 1).bit_length()))


def block_f_for(k: int, f: int, n: int, itemsize: int) -> int:
    """The width a step's blocks of ``F`` should have: ``F`` whole where
    both copies of the three blocks fit ``WEIGHT_BLOCKS``, else
    ``BLOCK_F``."""
    return f if 2 * (2 * k + n) * f * itemsize <= WEIGHT_BLOCKS else BLOCK_F


def slots_for(rows: int, groups: int, tile: int = TILE) -> int:
    """Slots that hold ``rows`` rows however they fall into ``groups``:
    every group but an empty one leaves its last tile partly empty."""
    return -(-rows // tile) * tile + groups * tile


def aligned_layout(group_sizes, slots: int, tile: int = TILE) -> Layout:
    """Every group on tiles of its own, in order; an empty group has no
    tile.  Tiles past the last in use are given its group, so that the
    kernel fetches nothing for them."""
    i32 = jnp.int32
    sizes = group_sizes.astype(i32)
    tiles_of = -(-sizes // tile)
    tile_ends = jnp.cumsum(tiles_of)
    in_use = tile_ends[-1]
    at = jnp.minimum(jnp.arange(slots // tile, dtype=i32),
                     jnp.maximum(in_use - 1, 0))
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_ends, at, side="right").astype(i32),
        sizes.shape[0] - 1)
    first_slot = (tile_ends - tiles_of) * tile
    slot = jnp.arange(slots, dtype=i32)
    slot_group = tile_group[slot // tile]
    slot_rank = slot - first_slot[slot_group]
    filled = jnp.logical_and(slot // tile < in_use,
                             slot_rank < sizes[slot_group])
    return Layout(tile_group, in_use, first_slot, slot_group, slot_rank,
                  filled)


def _gate(gate, up, dtype):
    """The gate's arithmetic in float32, rounded to the next product's
    operand dtype."""
    return (jax.nn.silu(gate) * up).astype(dtype)


def grouped_matmul_reference(x, gate_up, down, tile_group, tiles_in_use,
                             first_group=0, *, tile: int = TILE,
                             out_dtype=None, precision=None):
    """Two ``jax.lax.ragged_dot`` over the groups' tiles; slots past the
    tiles in use come out as zeros."""
    f32 = jnp.float32
    used = jnp.arange(tile_group.shape[0]) < tiles_in_use
    sizes = tile * jnp.zeros((gate_up.shape[0],), jnp.int32).at[
        tile_group + first_group].add(used.astype(jnp.int32))
    dot = functools.partial(lax.ragged_dot, group_sizes=sizes,
                            precision=precision, preferred_element_type=f32)
    gate, up = jnp.split(dot(x, gate_up), 2, axis=-1)
    return dot(_gate(gate, up, x.dtype), down).astype(out_dtype or x.dtype)


def _kernel(group_ref, meta_ref, x_ref, gate_ref, up_ref, down_ref, out_ref,
            acc_ref, *, precision):
    """One tile of rows against one block of ``F``.  ``meta`` holds the
    tiles in use (and the first group, which the index maps read)."""
    f32 = jnp.float32
    j, nj = pl.program_id(1), pl.num_programs(1)

    @pl.when(pl.program_id(0) < meta_ref[0])
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, gate_ref[0], precision=precision,
                       preferred_element_type=f32)
        up = jnp.dot(x, up_ref[0], precision=precision,
                     preferred_element_type=f32)
        part = jnp.dot(_gate(gate, up, x.dtype), down_ref[0],
                       precision=precision, preferred_element_type=f32)

        @pl.when(j == 0)
        def _():
            acc_ref[...] = part

        @pl.when(j > 0)
        def _():
            acc_ref[...] += part

        @pl.when(j == nj - 1)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, donate_argnums=(), static_argnames=(
    "tile", "block_f", "out_dtype", "interpret", "precision"))
def grouped_matmul_kernel(x, gate_up, down, tile_group, tiles_in_use,
                          first_group=0, *, tile: int = TILE,
                          block_f: Optional[int] = None, out_dtype=None,
                          interpret: bool = False, precision=None):
    """The Pallas kernel; the slots are whole tiles.  ``block_f`` is the
    tests'; ``block_f_for`` where none is given, and either way the next
    narrower width that divides ``F``."""
    slots, k = x.shape
    f, n = down.shape[1:]
    if slots % tile or tile_group.shape[0] != slots // tile:
        raise ValueError(f"{slots} slots are not {tile_group.shape[0]} "
                         f"tiles of {tile}")
    block_f = next(b for b in (
        block_f or block_f_for(k, f, n, gate_up.dtype.itemsize), 256, 128, f)
        if f % b == 0)
    meta = jnp.stack([jnp.asarray(tiles_in_use, jnp.int32),
                      jnp.asarray(first_group, jnp.int32)])

    def rows(i, j, group, meta):
        # past the tiles in use: the last one again, nothing is fetched
        return (jnp.minimum(i, jnp.maximum(meta[0] - 1, 0)), 0)

    def weights(block):
        def index(i, j, group, meta):
            # past the tiles in use the last block of F again, so that
            # the steps that compute nothing fetch nothing either
            return block(meta[1] + group[i],
                         jnp.where(i < meta[0], j, f // block_f - 1))
        return index

    return pl.pallas_call(
        functools.partial(_kernel, precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots // tile, f // block_f),
            in_specs=[
                pl.BlockSpec((tile, k), rows),
                pl.BlockSpec((1, k, block_f),
                             weights(lambda g, j: (g, 0, j))),
                pl.BlockSpec((1, k, block_f),
                             weights(lambda g, j: (g, 0, f // block_f + j))),
                pl.BlockSpec((1, block_f, n),
                             weights(lambda g, j: (g, j, 0))),
            ],
            out_specs=pl.BlockSpec((tile, n), rows),
            scratch_shapes=[pltpu.VMEM((tile, n), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((slots, n),
                                       jnp.dtype(out_dtype or x.dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=NAME,
    )(tile_group, meta, x, gate_up, gate_up, down)


def grouped_matmul(x, gate_up, down, tile_group, tiles_in_use, first_group=0,
                   *, tile: int = TILE, out_dtype=None, precision=None,
                   force: Optional[object] = None):
    """The kernel on the TPU, ``jax.lax.ragged_dot`` on any other
    platform; ``force`` is the tests' (``True``, ``"interpret"``,
    ``False``)."""
    if _on_tpu() if force is None else force:
        return grouped_matmul_kernel(
            x, gate_up, down, tile_group, tiles_in_use, first_group,
            tile=tile, out_dtype=out_dtype,
            interpret=(force == "interpret"), precision=precision)
    return grouped_matmul_reference(
        x, gate_up, down, tile_group, tiles_in_use, first_group, tile=tile,
        out_dtype=out_dtype, precision=precision)
