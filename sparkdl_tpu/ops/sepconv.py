"""Fused separable-conv inference kernel (pallas/TPU).

Motivation (measured, PERF.md round 4): Xception — the reference zoo's
depthwise model (``python/sparkdl/transformers/named_image.py``
SUPPORTED_MODELS) — spends its device time in XLA fusions that
materialize the depthwise intermediate in HBM: per separable conv the
default lowering reads the input for the depthwise, writes the depthwise
result, re-reads it for the pointwise matmul, writes the output, and
runs the pre-activation ReLU and inference BatchNorm as extra
elementwise traffic.  On a trace the pure-matmul halves run at MXU peak
(~0.26 ms at 19x19x728, batch 128) while the depthwise-carrying halves
cost 3-5x that.

This kernel computes ``BN(pointwise(depthwise(relu?(x))))`` in ONE HBM
round trip per layer.  The trick that makes it fit Mosaic's alignment
rules is the PADDED-FLAT layout: activations live as ``[N, (H+2)*Wp, C]``
where ``Wp = round_up(W+2, 8)`` — each spatial row padded with the conv
halo and rounded to a full sublane tile.  In that layout a (dy, dx)
kernel-tap shift is a SINGLE sublane rotation of the whole 2-D block
(``pltpu.roll`` by ``dy*Wp+dx``), so the 3x3 depthwise is 9 roll+FMA
passes on the VPU with f32 accumulation, the pointwise is one aligned
MXU ``dot`` over all spatial positions, and the BatchNorm affine
(+ optional post-ReLU) lands on the f32 accumulator.  The epilogue
re-zeros the halo so THE OUTPUT IS ALREADY IN THE NEXT LAYER'S INPUT
LAYOUT: a chain of stride-1 separable convs (Xception's entire middle
flow) runs with no repacking passes between layers at all.

Scope: 3x3, stride 1, SAME, depth_multiplier 1 — every separable conv
in Xception.  Inference only: train mode needs batch statistics, so
callers keep the unfused path there (``models/layers.py``).

The pure-jax twin :func:`sepconv_reference` is the parity oracle and the
non-TPU fallback; ``tests/test_ops_sepconv.py`` pins kernel==reference
on every shape class Xception uses.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def flat_width(w: int) -> int:
    """Padded row length: W + 2 halo columns, rounded to a sublane tile."""
    return round_up(w + 2, 8)


def flat_rows(h: int, row_tile: Optional[int] = None) -> int:
    """Row count of the padded-flat layout: H + 2 halo rows, rounded up to
    a whole number of row tiles when the tiled kernel will consume it."""
    return round_up(h + 2, row_tile) if row_tile else h + 2


def pad_to_flat(x, h: int, w: int, row_tile: Optional[int] = None):
    """[N, H, W, C] -> padded-flat [N, rows*Wp, C] (halo rows/cols = 0).

    ``rows`` is H+2, rounded up to a multiple of ``row_tile`` for the
    row-tiled kernel (extra bottom rows stay zero and are masked)."""
    n, c = x.shape[0], x.shape[-1]
    wp = flat_width(w)
    rows = flat_rows(h, row_tile)
    xp = jnp.pad(x, ((0, 0), (1, rows - h - 1), (1, wp - w - 1), (0, 0)))
    return xp.reshape(n, rows * wp, c)


def unflatten(xf, h: int, w: int):
    """Padded-flat [N, rows*Wp, C] -> [N, H, W, C] (drops halo/pad rows)."""
    n, c = xf.shape[0], xf.shape[-1]
    wp = flat_width(w)
    rows = xf.shape[1] // wp
    return xf.reshape(n, rows, wp, c)[:, 1:h + 1, 1:w + 1, :]


def halo_mask(h: int, w: int):
    """[(H+2)*Wp, 1] f32: 1 on the interior, 0 on the halo — restores the
    kernels' zero-halo contract after a position-wise op touches halo
    positions outside a kernel (e.g. MobileNet's expand matmul on the
    flat layout)."""
    wp = flat_width(w)
    return _interior_mask((h + 2) * wp, wp, h, w).astype(jnp.float32)


def _dw_taps(xt, dwk_ref, wp: int):
    """The 3x3 depthwise as 9 roll+FMA VPU passes over a padded-flat f32
    block: ``out[q] = sum_{dy,dx} in[q + dy*wp + dx] * k[dy,dx]`` — one
    ``pltpu.roll`` (sublane rotation) per tap.  THE layout trick of this
    module, in one place: every kernel variant (plain, tiled, mbconv)
    shares this loop so the delta arithmetic cannot drift."""
    from jax.experimental.pallas import tpu as pltpu

    lo = xt.shape[0]
    acc = jnp.zeros(xt.shape, jnp.float32)
    for dy in (-1, 0, 1):
        for dx in (-1, 0, 1):
            # out[q] = in[q + dy*wp + dx]  <=>  roll by the negation
            delta = (-(dy * wp + dx)) % lo
            tap = pltpu.roll(xt, delta, 0) if delta else xt
            acc += tap * dwk_ref[dy + 1, dx + 1, :].astype(jnp.float32)
    return acc


def _interior_mask(n_pos: int, wp: int, h: int, w: int, row0: int = 0):
    """[n_pos, 1] bool: True on interior (non-halo, non-pad) positions of
    a padded-flat block whose first position sits at global row ``row0``
    — the zero-halo output contract, single-sourced for every kernel."""
    pos = jax.lax.broadcasted_iota(jnp.int32, (n_pos, 1), 0)
    r = row0 + pos // wp
    col = pos % wp
    return ((r >= 1) & (r <= h) & (col >= 1) & (col <= w))


def _sepconv_kernel(x_ref, dwk_ref, pw_ref, scale_ref, shift_ref, out_ref,
                    *, h, w, wp, pre_relu, post_relu):
    """One batch element, whole image in padded-flat layout."""
    lo = (h + 2) * wp
    xt = x_ref[0].astype(jnp.float32)  # Mosaic rotate needs 32-bit data
    if pre_relu:
        xt = jnp.maximum(xt, jnp.float32(0))
    acc = _dw_taps(xt, dwk_ref, wp)
    y = jax.lax.dot_general(
        acc.astype(jnp.bfloat16), pw_ref[:],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y = y * scale_ref[0, :] + shift_ref[0, :]
    if post_relu:
        y = jnp.maximum(y, 0.0)
    valid = _interior_mask(lo, wp, h, w)
    out_ref[0] = jnp.where(valid, y, 0.0).astype(out_ref.dtype)


# graftlint: allow=SDL007 reason=xf is a chained flat activation the caller may reuse (Xception residual adds); donation would corrupt the residual source
@functools.partial(
    jax.jit,
    static_argnames=("h", "w", "pre_relu", "post_relu", "interpret"))
def _fused_sepconv_tpu(xf, dwk, pw, scale, shift, h, w, pre_relu,
                       post_relu, interpret=False):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, lo, c = xf.shape
    f = pw.shape[-1]
    wp = flat_width(w)
    assert lo == (h + 2) * wp, (lo, h, w, wp)
    kernel = functools.partial(_sepconv_kernel, h=h, w=w, wp=wp,
                               pre_relu=pre_relu, post_relu=post_relu)
    return pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, lo, c), lambda b: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, 3, c), lambda b: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((c, f), lambda b: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, f), lambda b: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, f), lambda b: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, lo, f), lambda b: (b, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, lo, f), jnp.bfloat16),
        interpret=interpret,
    )(xf.astype(jnp.bfloat16), dwk.astype(jnp.bfloat16),
      pw.astype(jnp.bfloat16),
      scale.reshape(1, f).astype(jnp.float32),
      shift.reshape(1, f).astype(jnp.float32))


def _sepconv_tiled_kernel(above_ref, cur_ref, below_ref, dwk_ref, pw_ref,
                          scale_ref, shift_ref, out_ref,
                          *, h, w, wp, th, pre_relu, post_relu):
    """One (batch, row-tile) cell: TH output rows + 1 halo row each side.

    The working buffer is [(TH+2)*Wp, C] — the previous tile's last row,
    this tile's TH rows, the next tile's first row (fetched as separate
    Wp-row blocks, so halo re-fetch traffic is 2/TH of the tile, not 2x).
    Taps roll the whole buffer like the full-image kernel; outputs are
    computed for the middle TH*Wp positions only, so the roll's wraparound
    touches only the halo slices and every tap a VALID output reads stays
    in-bounds.  Edge tiles fetch clamped (garbage) halo blocks whose
    contributions land exclusively on masked halo/pad rows."""
    import jax.experimental.pallas as pl

    t = pl.program_id(1)
    xt = jnp.concatenate(
        [above_ref[0], cur_ref[0], below_ref[0]], axis=0).astype(jnp.float32)
    if pre_relu:
        xt = jnp.maximum(xt, jnp.float32(0))
    acc = _dw_taps(xt, dwk_ref, wp)
    y = jax.lax.dot_general(
        acc[wp:wp + th * wp].astype(jnp.bfloat16), pw_ref[:],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y = y * scale_ref[0, :] + shift_ref[0, :]
    if post_relu:
        y = jnp.maximum(y, 0.0)
    valid = _interior_mask(th * wp, wp, h, w, row0=t * th)
    out_ref[0] = jnp.where(valid, y, 0.0).astype(out_ref.dtype)


# graftlint: allow=SDL007 reason=xf is a chained flat activation the caller may reuse (residual adds), and it feeds all three halo views; donation would corrupt them
@functools.partial(
    jax.jit,
    static_argnames=("h", "w", "th", "pre_relu", "post_relu", "interpret"))
def _fused_sepconv_tpu_tiled(xf, dwk, pw, scale, shift, h, w, th, pre_relu,
                             post_relu, interpret=False):
    """Row-tiled variant for shapes whose full image exceeds VMEM (the
    147^2/74^2 entry-flow sepconvs).  Grid (batch, row-tile); the input
    must be padded-flat with rows = round_up(H+2, th)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, lo, c = xf.shape
    f = pw.shape[-1]
    wp = flat_width(w)
    rows = lo // wp
    assert lo == rows * wp and rows % th == 0, (lo, wp, rows, th)
    assert rows >= h + 2, (rows, h)
    nt = rows // th
    kernel = functools.partial(_sepconv_tiled_kernel, h=h, w=w, wp=wp,
                               th=th, pre_relu=pre_relu, post_relu=post_relu)
    return pl.pallas_call(
        kernel,
        grid=(n, nt),
        in_specs=[
            # prev tile's last row (clamped at the top edge: tile 0 reads
            # row-block 0, whose contribution is masked)
            pl.BlockSpec((1, wp, c),
                         lambda b, t: (b, jnp.maximum(t * th - 1, 0), 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, th * wp, c), lambda b, t: (b, t, 0),
                         memory_space=pltpu.VMEM),
            # next tile's first row (clamped at the bottom edge)
            pl.BlockSpec(
                (1, wp, c),
                lambda b, t: (b, jnp.minimum(t * th + th, rows - 1), 0),
                memory_space=pltpu.VMEM),
            pl.BlockSpec((3, 3, c), lambda b, t: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((c, f), lambda b, t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, f), lambda b, t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, f), lambda b, t: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, th * wp, f), lambda b, t: (b, t, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, lo, f), jnp.bfloat16),
        interpret=interpret,
    )(xf.astype(jnp.bfloat16), xf.astype(jnp.bfloat16),
      xf.astype(jnp.bfloat16), dwk.astype(jnp.bfloat16),
      pw.astype(jnp.bfloat16),
      scale.reshape(1, f).astype(jnp.float32),
      shift.reshape(1, f).astype(jnp.float32))


def _mbconv_kernel(x_ref, dwk_ref, pw_ref, mid_shift_ref, shift_ref,
                   out_ref, *, h, w, wp):
    """One batch element of the MobileNet inverted-residual tail:
    ``BN(project(relu6(BN(depthwise(x)))))`` with both BN scales already
    FOLDED into ``dwk``/``pw`` by the caller (depthwise and 1x1 convs are
    per-output-channel linear), leaving one mid shift + relu6 clamp
    between the stages and one output shift after the dot."""
    lo = (h + 2) * wp
    xt = x_ref[0].astype(jnp.float32)
    acc = _dw_taps(xt, dwk_ref, wp)
    acc = jnp.clip(acc + mid_shift_ref[0, :], 0.0, 6.0)  # BN shift + relu6
    y = jax.lax.dot_general(
        acc.astype(jnp.bfloat16), pw_ref[:],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y = y + shift_ref[0, :]  # project BN (scale folded into pw)
    valid = _interior_mask(lo, wp, h, w)
    out_ref[0] = jnp.where(valid, y, 0.0).astype(out_ref.dtype)


# graftlint: allow=SDL007 reason=xf is a chained flat activation the caller may reuse (MobileNet inverted-residual add); donation would corrupt the residual source
@functools.partial(jax.jit, static_argnames=("h", "w", "interpret"))
def _fused_mbconv_tpu(xf, dwk, pw, mid_shift, shift, h, w,
                      interpret=False):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, lo, c = xf.shape
    f = pw.shape[-1]
    wp = flat_width(w)
    assert lo == (h + 2) * wp, (lo, h, w, wp)
    kernel = functools.partial(_mbconv_kernel, h=h, w=w, wp=wp)
    return pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, lo, c), lambda b: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, 3, c), lambda b: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((c, f), lambda b: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda b: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, f), lambda b: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, lo, f), lambda b: (b, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, lo, f), jnp.bfloat16),
        interpret=interpret,
    )(xf.astype(jnp.bfloat16), dwk.astype(jnp.bfloat16),
      pw.astype(jnp.bfloat16),
      mid_shift.reshape(1, c).astype(jnp.float32),
      shift.reshape(1, f).astype(jnp.float32))


def mbconv_reference(x, dwk, pw, mid_shift, shift):
    """Pure-jax twin of the mbconv kernel in NHWC (parity oracle /
    non-TPU fallback), on the same FOLDED weights: depthwise 3x3 SAME ->
    +mid_shift -> relu6 -> 1x1 conv -> +shift."""
    cdt = jnp.bfloat16
    c = x.shape[-1]
    y = jax.lax.conv_general_dilated(
        x.astype(cdt), dwk.reshape(3, 3, 1, c).astype(cdt),
        window_strides=(1, 1), padding="SAME", feature_group_count=c,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)
    y = jnp.clip(y + mid_shift, 0.0, 6.0)
    y = jax.lax.conv_general_dilated(
        y.astype(cdt), pw.reshape(1, 1, c, -1).astype(cdt),
        window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)
    return (y + shift).astype(cdt)


def fused_mbconv_flat(xf, dwk, pw, mid_shift, shift, h: int, w: int,
                      force: Optional[bool] = None):
    """Fused MobileNet inverted-residual tail on PADDED-FLAT input/output
    (zero-halo contract as :func:`fused_sepconv_flat`).  ``dwk``
    [3,3,C]/[3,3,C,1] and ``pw`` [C,F]/[1,1,C,F] must already carry their
    BN scales (``models.layers.fold_bn_into_conv``); ``mid_shift`` [C] is
    the depthwise BN shift (applied before the relu6 clamp), ``shift``
    [F] the project BN shift (linear bottleneck: no output activation).
    """
    if dwk.ndim == 4:
        dwk = dwk.reshape(3, 3, -1)
    if pw.ndim == 4:
        pw = pw.reshape(pw.shape[-2], pw.shape[-1])
    use_pallas = _on_tpu() if force is None else force
    if use_pallas:
        return _fused_mbconv_tpu(xf, dwk, pw, mid_shift, shift, h, w,
                                 interpret=(force == "interpret"))
    x = unflatten(xf, h, w)
    y = mbconv_reference(x, dwk, pw, mid_shift, shift)
    return pad_to_flat(y, h, w)


def sepconv_reference(x, dwk, pw, scale, shift, pre_relu: bool,
                      post_relu: bool = False):
    """Pure-jax twin of the kernel (parity oracle / non-TPU fallback) in
    NHWC: relu? -> depthwise 3x3 SAME (grouped conv) -> 1x1 conv ->
    y*scale+shift -> relu?.

    ``dwk`` [3,3,C] (keras depthwise kernel, mult 1, squeezed), ``pw``
    [C,F], ``scale``/``shift`` [F] — the inference-mode BatchNorm affine:
    scale = gamma / sqrt(var + eps), shift = beta - mean * scale.
    """
    cdt = jnp.bfloat16
    xt = x.astype(cdt)
    if pre_relu:
        xt = jax.nn.relu(xt)
    c = x.shape[-1]
    y = jax.lax.conv_general_dilated(
        xt, dwk.reshape(3, 3, 1, c).astype(cdt),
        window_strides=(1, 1), padding="SAME", feature_group_count=c,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    y = jax.lax.conv_general_dilated(
        y, pw.reshape(1, 1, c, -1).astype(cdt),
        window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)
    y = y * scale + shift
    if post_relu:
        y = jax.nn.relu(y)
    return y.astype(cdt)


def _on_tpu() -> bool:
    """True iff the process's default backend is the TPU.  A backend
    that cannot initialise RAISES: answering False there would swap the
    kernels for the reference path without a trace."""
    return jax.default_backend() == "tpu"


def fused_sepconv_flat(xf, dwk, pw, scale, shift, h: int, w: int,
                       pre_relu: bool = False, post_relu: bool = False,
                       force: Optional[bool] = None,
                       row_tile: Optional[int] = None):
    """Fused sepconv+BN on PADDED-FLAT input/output (see module doc).

    ``xf`` [N, rows*Wp, C] with zeroed halo; returns [N, rows*Wp, F]
    with zeroed halo — directly consumable by the next stride-1 sepconv.
    ``dwk`` [3,3,C] or [3,3,C,1]; ``pw`` [C,F] or [1,1,C,F].  Dispatches
    to the pallas kernel on TPU backends, to the NHWC reference (with
    pack/unpack) elsewhere; ``force`` overrides, and
    ``force="interpret"`` runs the REAL kernel through the pallas
    interpreter (CI parity on CPU).

    ``row_tile``: process TH rows per grid cell instead of the whole
    image — required when (H+2)*Wp*C exceeds VMEM (the 147^2/74^2
    entry-flow shapes).  The input must have rows = round_up(H+2, TH)
    (``pad_to_flat(..., row_tile=TH)``); chains of equal-shape sepconvs
    still need no repacking.
    """
    if dwk.ndim == 4:
        dwk = dwk.reshape(3, 3, -1)
    if pw.ndim == 4:
        pw = pw.reshape(pw.shape[-2], pw.shape[-1])
    use_pallas = _on_tpu() if force is None else force
    if use_pallas:
        interpret = (force == "interpret")
        if row_tile:
            return _fused_sepconv_tpu_tiled(xf, dwk, pw, scale, shift, h,
                                            w, row_tile, pre_relu,
                                            post_relu, interpret=interpret)
        return _fused_sepconv_tpu(xf, dwk, pw, scale, shift, h, w,
                                  pre_relu, post_relu, interpret=interpret)
    rows = xf.shape[1] // flat_width(w)
    x = unflatten(xf, h, w)
    y = sepconv_reference(x, dwk, pw, scale, shift, pre_relu, post_relu)
    yf = pad_to_flat(y, h, w)
    wp = flat_width(w)
    if rows > h + 2:  # preserve the caller's row padding
        yf = jnp.pad(yf, ((0, 0), (0, (rows - h - 2) * wp), (0, 0)))
    return yf
