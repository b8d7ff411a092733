"""Shared flax.linen building blocks for the pretrained-CNN zoo.

The zoo replaces the reference's model registry (``python/sparkdl/transformers/
named_image.py — SUPPORTED_MODELS`` and the Scala ``Models.scala`` packaged
GraphDefs) with hand-written flax modules.  Design rules:

  * NHWC layout, ``padding="SAME"`` via lax's TF-compatible asymmetric padding
    — both match what the MXU/XLA:TPU pipeline expects and what the Keras
    weights were trained under, so weight import is layout-transpose-free.
  * Submodule names equal the corresponding Keras layer names wherever
    keras.applications assigns explicit names (VGG/ResNet/Xception), so the
    weight importer can match by name; InceptionV3 (auto-named layers
    upstream) is matched by deterministic build order instead.
  * BatchNorm carries real ``batch_stats`` so the same module trains (for
    fine-tuning in the estimator) and infers (featurizer/predictor).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax.numpy as jnp
from flax import linen as nn

# Keras BatchNormalization defaults; individual models override epsilon.
BN_EPS_DEFAULT = 1e-3
BN_MOMENTUM_DEFAULT = 0.99


def _depthwise_conv(x: jnp.ndarray, dw: jnp.ndarray, strides, padding,
                    dtype) -> jnp.ndarray:
    """Apply a Keras-layout depthwise kernel [H,W,Cin,mult] via lax.

    The Keras depthwise output channel (c, m) -> c*mult + m equals a
    C-major reshape to [H,W,1,Cin*mult], which is exactly lax's
    grouped-conv kernel layout (feature_group_count=Cin) — the one subtle
    layout fact both depthwise modules depend on, kept in one place."""
    import jax.lax as lax

    kh, kw, cin, mult = dw.shape
    dw_lax = dw.reshape(kh, kw, 1, cin * mult)
    return lax.conv_general_dilated(
        jnp.asarray(x, dtype), jnp.asarray(dw_lax, dtype),
        window_strides=strides,
        padding=padding,
        feature_group_count=cin,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


class SeparableConv2D(nn.Module):
    """Depthwise-separable conv matching ``keras.layers.SeparableConv2D``.

    Param layout mirrors Keras: ``depthwise_kernel`` [H,W,Cin,mult] and
    ``pointwise_kernel`` [1,1,Cin*mult,Cout] (plus optional bias), so the
    importer can copy Keras weights verbatim.  Lowered as a grouped conv
    (feature_group_count=Cin) followed by a 1x1 conv — XLA fuses both onto
    the MXU.

    ``fused_flat`` switches to the pallas fused inference path
    (``ops/sepconv.py``): the input/output are PADDED-FLAT
    [N, (H+2)*Wp, C] and the BatchNorm affine + activations fuse into the
    kernel.  Param creation is identical either way, so a module's
    variables are interchangeable between paths (and with the keras
    importer).
    """

    features: int
    kernel_size: Tuple[int, int] = (3, 3)
    strides: Tuple[int, int] = (1, 1)
    padding: str = "SAME"
    depth_multiplier: int = 1
    use_bias: bool = True
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray,
                 fused_flat: Optional[dict] = None) -> jnp.ndarray:
        cin = x.shape[-1]
        kh, kw = self.kernel_size
        dw = self.param(
            "depthwise_kernel",
            nn.initializers.lecun_normal(),
            (kh, kw, cin, self.depth_multiplier))
        pw = self.param(
            "pointwise_kernel",
            nn.initializers.lecun_normal(),
            (1, 1, cin * self.depth_multiplier, self.features))
        if fused_flat is not None:
            assert (self.kernel_size == (3, 3)
                    and self.strides == (1, 1)
                    and self.padding == "SAME"
                    and self.depth_multiplier == 1
                    and not self.use_bias), \
                "fused path: 3x3/s1/SAME/mult1/nobias"
            from sparkdl_tpu.ops.sepconv import fused_sepconv_flat

            return fused_sepconv_flat(
                x, dw, pw, fused_flat["scale"], fused_flat["shift"],
                h=fused_flat["h"], w=fused_flat["w"],
                pre_relu=fused_flat.get("pre_relu", False),
                post_relu=fused_flat.get("post_relu", False),
                force=fused_flat.get("force"),
                row_tile=fused_flat.get("row_tile"))
        dtype = self.dtype or x.dtype
        import jax.lax as lax

        y = _depthwise_conv(x, dw, self.strides, self.padding, dtype)
        y = lax.conv_general_dilated(
            y, jnp.asarray(pw, dtype),
            window_strides=(1, 1),
            padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if self.use_bias:
            b = self.param("bias", nn.initializers.zeros, (self.features,))
            y = y + jnp.asarray(b, dtype)
        return y


class BNAffine(nn.Module):
    """Inference-mode twin of ``nn.BatchNorm``: declares the IDENTICAL
    variable tree (params scale/bias, batch_stats mean/var — same names,
    shapes, inits) but returns the folded affine ``(scale', shift')`` with
    scale' = gamma / sqrt(var + eps), shift' = beta - mean * scale',
    for fusion into a preceding conv's epilogue (ops/sepconv.py).  A model
    can therefore apply the same variables through either module."""

    epsilon: float = BN_EPS_DEFAULT
    use_scale: bool = True

    @nn.compact
    def __call__(self, features: int):
        mean = self.variable("batch_stats", "mean",
                             lambda: jnp.zeros((features,), jnp.float32))
        var = self.variable("batch_stats", "var",
                            lambda: jnp.ones((features,), jnp.float32))
        beta = self.param("bias", nn.initializers.zeros, (features,))
        if self.use_scale:
            gamma = self.param("scale", nn.initializers.ones, (features,))
        else:
            gamma = jnp.float32(1.0)
        s = (jnp.asarray(gamma, jnp.float32)
             / jnp.sqrt(jnp.asarray(var.value, jnp.float32) + self.epsilon))
        t = jnp.asarray(beta, jnp.float32) - \
            jnp.asarray(mean.value, jnp.float32) * s
        return s, t


class KernelParam(nn.Module):
    """Variable-tree twin of ``nn.Conv``: declares the identical
    ``kernel`` (and, with ``use_bias``, ``bias``) params — same names,
    shapes, inits — and returns them instead of convolving.  Lets a
    parent fuse several branch convs into one wider conv
    (models/inception.py fused heads, models/resnet.py fused shortcut)
    while keeping the per-branch variable tree interchangeable with the
    plain path."""

    shape: Tuple[int, ...]
    use_bias: bool = False
    # "depthwise_kernel" twins DepthwiseConv2D instead of nn.Conv
    param_name: str = "kernel"

    @nn.compact
    def __call__(self):
        kernel = self.param(self.param_name, nn.initializers.lecun_normal(),
                            self.shape)
        if not self.use_bias:
            return kernel
        bias = self.param("bias", nn.initializers.zeros,
                          (self.shape[-1],))
        return kernel, bias


def fold_bn_into_conv(kernel, scale, shift, bias=None):
    """Fold an inference-mode BN affine into conv constants:
    ``(conv(x, k) + b) * s + t == conv(x, k*s) + (b*s + t)`` (conv is
    linear).  Returns ``(K, B)`` — K cast back to the kernel's dtype so a
    bf16 program stays bf16 (fold math in f32), B in f32 for the caller
    to cast at the add.  Shared by every fused-conv path
    (models/inception.py fused heads, models/resnet.py fused shortcut)
    so precision/dtype fixes cannot diverge between them."""
    K = (kernel.astype(jnp.float32) * scale).astype(kernel.dtype)
    b = bias.astype(jnp.float32) if bias is not None else jnp.float32(0)
    return K, b * scale + shift


class DepthwiseConv2D(nn.Module):
    """Depthwise conv matching ``keras.layers.DepthwiseConv2D``.

    Param layout mirrors Keras (``depthwise_kernel`` [H,W,Cin,mult], the
    importer's ``depthconv`` kind); lowered as a grouped conv
    (feature_group_count=Cin)."""

    kernel_size: Tuple[int, int] = (3, 3)
    strides: Tuple[int, int] = (1, 1)
    padding: Any = "SAME"
    depth_multiplier: int = 1
    use_bias: bool = True
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cin = x.shape[-1]
        kh, kw = self.kernel_size
        dw = self.param(
            "depthwise_kernel",
            nn.initializers.lecun_normal(),
            (kh, kw, cin, self.depth_multiplier))
        dtype = self.dtype or x.dtype
        y = _depthwise_conv(x, dw, self.strides, self.padding, dtype)
        if self.use_bias:
            b = self.param("bias", nn.initializers.zeros,
                           (cin * self.depth_multiplier,))
            y = y + jnp.asarray(b, dtype)
        return y


class ConvBN(nn.Module):
    """``conv2d_bn`` from keras.applications.inception_v3: Conv(no bias) +
    BatchNorm(scale=False) + ReLU."""

    features: int
    kernel_size: Tuple[int, int]
    strides: Tuple[int, int] = (1, 1)
    padding: str = "SAME"
    bn_eps: float = BN_EPS_DEFAULT
    bn_scale: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False,
                 fold: bool = False):
        if fold:
            # declare the identical variable tree but return the folded
            # (kernel, bn_scale, bn_shift) for a parent-level fused conv
            # (inference only — models/inception.py fused heads)
            kh, kw = self.kernel_size
            kernel = KernelParam((kh, kw, x.shape[-1], self.features),
                                 name="conv")()
            s, t = BNAffine(epsilon=self.bn_eps, use_scale=self.bn_scale,
                            name="bn")(self.features)
            return kernel, s, t
        x = nn.Conv(self.features, self.kernel_size,
                    strides=self.strides, padding=self.padding,
                    use_bias=False, name="conv")(x)
        x = nn.BatchNorm(use_running_average=not train,
                         momentum=BN_MOMENTUM_DEFAULT, epsilon=self.bn_eps,
                         use_scale=self.bn_scale, name="bn")(x)
        return nn.relu(x)


def max_pool_valid(x: jnp.ndarray, window: int, stride: int) -> jnp.ndarray:
    return nn.max_pool(x, (window, window), strides=(stride, stride),
                       padding="VALID")


def global_avg_pool(x: jnp.ndarray) -> jnp.ndarray:
    """GlobalAveragePooling2D — the featurizer cut of every non-VGG zoo
    model (DeepImageFeaturizer's penultimate-layer semantics)."""
    return jnp.mean(x, axis=(1, 2))
