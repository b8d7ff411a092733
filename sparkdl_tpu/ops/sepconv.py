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
in Xception — with one batch element's whole image as one VMEM block,
so a shape whose working set exceeds VMEM stays on XLA
(``models/xception.py``).  Inference only: train mode needs batch
statistics, so callers keep the unfused path there
(``models/layers.py``).

The pure-jax twin :func:`sepconv_reference` is the parity oracle and the
non-TPU fallback; ``tests/test_ops_sepconv.py`` pins kernel==reference
on every shape class Xception uses.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def flat_width(w: int) -> int:
    """Padded row length: W + 2 halo columns, rounded to a sublane tile."""
    return round_up(w + 2, 8)


def pad_to_flat(x, h: int, w: int):
    """[N, H, W, C] -> padded-flat [N, (H+2)*Wp, C] (halo rows/cols = 0)."""
    n, c = x.shape[0], x.shape[-1]
    wp = flat_width(w)
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, wp - w - 1), (0, 0)))
    return xp.reshape(n, (h + 2) * wp, c)


def unflatten(xf, h: int, w: int):
    """Padded-flat [N, (H+2)*Wp, C] -> [N, H, W, C] (drops the halo)."""
    n, c = xf.shape[0], xf.shape[-1]
    wp = flat_width(w)
    return xf.reshape(n, h + 2, wp, c)[:, 1:h + 1, 1:w + 1, :]


def _dw_taps(xt, dwk_ref, wp: int):
    """The 3x3 depthwise as 9 roll+FMA VPU passes over a padded-flat f32
    block: ``out[q] = sum_{dy,dx} in[q + dy*wp + dx] * k[dy,dx]`` — one
    ``pltpu.roll`` (sublane rotation) per tap — THE layout trick of this
    module."""
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
    """[n_pos, 1] bool: True on interior (non-halo) positions of a
    padded-flat block whose first position sits at row ``row0`` — the
    zero-halo output contract.  The kernel takes the whole image
    (``row0`` 0); the ``row0 +`` is kept because it is part of the
    kernel's lowered program, which ``PROGRAMS.lock.json`` fingerprints."""
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
                       force: Optional[bool] = None):
    """Fused sepconv+BN on PADDED-FLAT input/output (see module doc).

    ``xf`` [N, (H+2)*Wp, C] with zeroed halo; returns [N, (H+2)*Wp, F]
    with zeroed halo — directly consumable by the next stride-1 sepconv.
    ``dwk`` [3,3,C] or [3,3,C,1]; ``pw`` [C,F] or [1,1,C,F].  Dispatches
    to the pallas kernel on TPU backends, to the NHWC reference (with
    pack/unpack) elsewhere; ``force`` overrides, and
    ``force="interpret"`` runs the REAL kernel through the pallas
    interpreter (CI parity on CPU).  The whole image is one VMEM block:
    callers keep shapes whose working set exceeds VMEM on XLA
    (``models/xception.py``).
    """
    if dwk.ndim == 4:
        dwk = dwk.reshape(3, 3, -1)
    if pw.ndim == 4:
        pw = pw.reshape(pw.shape[-2], pw.shape[-1])
    use_pallas = _on_tpu() if force is None else force
    if use_pallas:
        return _fused_sepconv_tpu(xf, dwk, pw, scale, shift, h, w,
                                  pre_relu, post_relu,
                                  interpret=(force == "interpret"))
    x = unflatten(xf, h, w)
    y = sepconv_reference(x, dwk, pw, scale, shift, pre_relu, post_relu)
    return pad_to_flat(y, h, w)
