"""Xception as a flax module.

Zoo entry from the reference's ``SUPPORTED_MODELS`` registry
(``python/sparkdl/transformers/named_image.py``).  Featurizer cut = global
average pool (2048-d).

Layer names mirror keras.applications.xception ("block1_conv1",
"block2_sepconv1", ..., "predictions"); the four residual-shortcut convs/BNs
are auto-named upstream, so they import by creation order — see
``xception_auto_order`` and ``models/keras_import.py``.  Separable convs are
bias-free depthwise+pointwise pairs lowered as grouped convs (MXU-friendly);
BN epsilon is the Keras default 1e-3.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from flax import linen as nn

from sparkdl_tpu.models.layers import (BNAffine, SeparableConv2D,
                                       global_avg_pool)
from sparkdl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# (block index, filters) of the three entry-flow residual blocks.
_ENTRY_BLOCKS = ((2, 128), (3, 256), (4, 728))


def xception_auto_order():
    """Creation-order import targets for the auto-named shortcut layers."""
    order = []
    for i, _ in _ENTRY_BLOCKS:
        order.append(("conv", (f"shortcut{i}_conv",)))
        order.append(("bn", (f"shortcut{i}_bn",)))
    order.append(("conv", ("shortcut13_conv",)))
    order.append(("bn", ("shortcut13_bn",)))
    return order


def _pick_row_tile(h: int, w: int, channels: int):
    """Row tile when the whole-image padded-flat working set would exceed
    VMEM; None = whole-image kernel.  Budget calibrated on hardware: 37^2
    x 728ch (1.14M position-channels, block4 at the native 299^2 input)
    compiles and runs; 74^2 x 256ch (1.56M) does not fit — so the
    threshold sits just above the known-good point and the decision
    scales with the actual block shape, not a block index (works for
    non-299 input sizes too)."""
    from sparkdl_tpu.ops.sepconv import flat_width

    if (h + 2) * flat_width(w) * channels <= 1_200_000:
        return None
    return 16


class Xception(nn.Module):
    """``fused_inference`` routes every separable conv through the pallas
    fused kernel (``ops/sepconv.py``) when not training: None = auto (on
    for a TPU backend with ONE device, off — and logged — on a
    multi-chip host), True = always (CPU falls back to the jax reference
    path — used by parity tests), False = never.  Both
    paths declare identical variables, so weights import/persist the same
    way regardless."""

    num_classes: int = 1000
    fused_inference: Optional[bool] = None
    # entry blocks 2-3 (147^2/74^2) through the ROW-TILED kernel
    # (ops/sepconv.py).  Measured round 5 and retired: whole-model -24%
    # (2341 vs 3086 img/s) — the pad/unflatten repacking around 2-layer
    # blocks dominates, and XLA's own sepconv lowering at 147^2 is within
    # 3% of the kernel per-layer (PERF.md "Row-tiled sepconv").  Kept
    # off-by-default behind SPARKDL_XC_TILED=1 with parity tests.
    tiled_entry: bool = False

    def _use_fused(self, train: bool) -> bool:
        if train:
            return False
        if self.fused_inference is not None:
            return self.fused_inference
        import jax

        from sparkdl_tpu.ops.sepconv import _on_tpu

        if not _on_tpu():
            return False
        if jax.device_count() != 1:
            # the engine's mesh spans every device and Mosaic refuses to
            # partition a kernel automatically ("wrap the call in a
            # shard_map"), so a multi-chip host serves the XLA lowering
            logger.info("Xception: %d devices, separable convs take the "
                        "XLA path (the Pallas kernel is single-device)",
                        jax.device_count())
            return False
        return True

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False,
                 features: bool = False, logits: bool = False) -> jnp.ndarray:
        fused = self._use_fused(train)

        def bn(name):
            return nn.BatchNorm(use_running_average=not train, momentum=0.99,
                                epsilon=1e-3, name=name)

        def bn_act(x, name, relu=False):
            """Inference BN in fused mode folds to a precomputed affine
            (scale/shift derived in f32 from the running stats — BNAffine)
            applied in x's dtype.  vs nn.BatchNorm this keeps the folded
            constants at full precision even when the engine has cast all
            variables (incl. running var) to bf16, and keeps the epilogue
            a two-op elementwise chain in the activation dtype.  Identical
            variable tree either way."""
            if fused:
                s, t = BNAffine(epsilon=1e-3, name=name)(x.shape[-1])
                y = x * s.astype(x.dtype) + t.astype(x.dtype)
                if relu:
                    y = nn.relu(y)
                return y
            y = bn(name)(x)
            return nn.relu(y) if relu else y

        def sep(x, filters, name, pre_relu=False, post_relu=False,
                flat_hw=None, row_tile=None):
            """sepconv + BN (+ neighboring ReLUs).  When ``fused`` and a
            ``flat_hw`` is given, x is PADDED-FLAT [N,rows*Wp,C] and the
            whole stack runs as one pallas kernel (``row_tile`` selects
            the row-tiled variant for VMEM-oversized spatial shapes);
            otherwise the plain NHWC conv/BN modules run (XLA path)."""
            if fused and flat_hw is not None:
                s, t = BNAffine(epsilon=1e-3, name=f"{name}_bn")(filters)
                h, w = flat_hw
                return SeparableConv2D(filters, (3, 3), use_bias=False,
                                       name=name)(
                    x, fused_flat=dict(scale=s, shift=t, h=h, w=w,
                                       pre_relu=pre_relu,
                                       post_relu=post_relu,
                                       row_tile=row_tile))
            if pre_relu:
                x = nn.relu(x)
            x = SeparableConv2D(filters, (3, 3), use_bias=False, name=name)(x)
            x = bn_act(x, f"{name}_bn", relu=post_relu)
            return x

        if fused:
            from sparkdl_tpu.ops.sepconv import pad_to_flat, unflatten

        # Entry flow: two plain convs (VALID, stride-2 first)
        x = nn.Conv(32, (3, 3), strides=(2, 2), padding="VALID",
                    use_bias=False, name="block1_conv1")(x)
        x = bn_act(x, "block1_conv1_bn", relu=True)
        x = nn.Conv(64, (3, 3), padding="VALID", use_bias=False,
                    name="block1_conv2")(x)
        x = bn_act(x, "block1_conv2_bn", relu=True)

        # Entry-flow residual blocks (block2 has no leading relu — upstream
        # quirk preserved).  Fused mode routes ALL entry blocks through
        # the kernel: block4 (37x37) fits VMEM whole; blocks 2-3 (147/74
        # spatial — whose padded-flat working set exceeds VMEM) use the
        # ROW-TILED kernel generation (ops/sepconv.py — VERDICT r4 #1).
        for i, f in _ENTRY_BLOCKS:
            residual = nn.Conv(f, (1, 1), strides=(2, 2), padding="SAME",
                               use_bias=False, name=f"shortcut{i}_conv")(x)
            residual = bn_act(residual, f"shortcut{i}_bn")
            h, w = x.shape[1], x.shape[2]
            needs_tile = _pick_row_tile(h, w, max(x.shape[-1], f))
            use_flat = fused and (needs_tile is None or self.tiled_entry)
            if use_flat:
                xf = pad_to_flat(x, h, w, row_tile=needs_tile)
                xf = sep(xf, f, f"block{i}_sepconv1", pre_relu=i > 2,
                         flat_hw=(h, w), row_tile=needs_tile)
                xf = sep(xf, f, f"block{i}_sepconv2", pre_relu=True,
                         flat_hw=(h, w), row_tile=needs_tile)
                x = unflatten(xf, h, w)
            else:
                x = sep(x, f, f"block{i}_sepconv1", pre_relu=i > 2)
                x = sep(x, f, f"block{i}_sepconv2", pre_relu=True)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
            x = x + residual

        # Middle flow: 8 identity blocks of three sepconvs.  In fused mode
        # the whole flow CHAINS in padded-flat layout — the kernel's output
        # halo contract means zero repacking passes between the 24 layers.
        mid_fits = _pick_row_tile(x.shape[1], x.shape[2], 728) is None
        if fused and mid_fits:
            h, w = x.shape[1], x.shape[2]
            xf = pad_to_flat(x, h, w)
            for i in range(5, 13):
                res_f = xf
                for j in (1, 2, 3):
                    xf = sep(xf, 728, f"block{i}_sepconv{j}", pre_relu=True,
                             flat_hw=(h, w))
                xf = xf + res_f
            x19 = unflatten(xf, h, w)
        else:
            for i in range(5, 13):
                residual = x
                for j in (1, 2, 3):
                    x = sep(x, 728, f"block{i}_sepconv{j}", pre_relu=True)
                x = x + residual
            x19 = x

        # Exit flow
        residual = nn.Conv(1024, (1, 1), strides=(2, 2), padding="SAME",
                           use_bias=False, name="shortcut13_conv")(x19)
        residual = bn_act(residual, "shortcut13_bn")
        h, w = x19.shape[1], x19.shape[2]
        if fused and mid_fits and _pick_row_tile(h, w, 1024) is None:
            xf = sep(xf, 728, "block13_sepconv1", pre_relu=True,
                     flat_hw=(h, w))
            xf = sep(xf, 1024, "block13_sepconv2", pre_relu=True,
                     flat_hw=(h, w))
            x = unflatten(xf, h, w)
        else:
            x = sep(x19, 728, "block13_sepconv1", pre_relu=True)
            x = sep(x, 1024, "block13_sepconv2", pre_relu=True)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        x = x + residual

        if fused and _pick_row_tile(x.shape[1], x.shape[2], 2048) is None:
            h = x.shape[1]
            xf = pad_to_flat(x, h, x.shape[2])
            xf = sep(xf, 1536, "block14_sepconv1", post_relu=True,
                     flat_hw=(h, x.shape[2]))
            xf = sep(xf, 2048, "block14_sepconv2", post_relu=True,
                     flat_hw=(h, x.shape[2]))
            x = unflatten(xf, h, x.shape[2])
        else:
            x = sep(x, 1536, "block14_sepconv1", post_relu=True)
            x = sep(x, 2048, "block14_sepconv2", post_relu=True)
        x = global_avg_pool(x)  # 2048-d featurizer cut
        if features:
            return x
        x = nn.Dense(self.num_classes, name="predictions")(x)
        if logits:
            return x
        return nn.softmax(x)
