"""InceptionV3, featurizer cut (global average pool, 2048), written from
Szegedy et al. 2015 (arXiv:1512.00567) as
``keras.applications.inception_v3`` lays it out: 94 units of
convolution (no bias) + batch norm (no gamma, epsilon 1e-3) + ReLU; the
average-pool branches leave the padding out of the mean (TensorFlow's
SAME AvgPool).  A unit named ``b7x7_2`` with a 1x7 kernel has one row
and seven columns, as keras's ``conv2d_bn(x, f, 1, 7)``.

The units are named by block and branch; the program's flax tree nests
``conv`` and ``bn`` under the same unit names (``reference_name``)."""

from __future__ import annotations

import jax.numpy as jnp

from . import net as ops

INPUT_HW = (299, 299)
FEATURES = 2048
BN_EPS = 1e-3
UNUSED_BY_FEATURIZER = ("predictions",)


def preprocess(rgb_uint8):
    """uint8 RGB -> float32 in [-1, 1]."""
    return rgb_uint8.astype(jnp.float32) / 127.5 - 1.0


def _unit(net, name, x, filters, rows, cols, stride=1, padding="SAME"):
    x = net.conv(name, x, filters, rows, cols, stride=stride, padding=padding)
    return ops.relu(net.bn(name, x, eps=BN_EPS, scale=False))


def _cat(*branches):
    return jnp.concatenate(branches, axis=-1)


def _mixed_35(net, x, n, pool_filters):
    b1 = _unit(net, f"{n}_b1x1", x, 64, 1, 1)
    b5 = _unit(net, f"{n}_b5x5_1", x, 48, 1, 1)
    b5 = _unit(net, f"{n}_b5x5_2", b5, 64, 5, 5)
    b3 = _unit(net, f"{n}_b3x3dbl_1", x, 64, 1, 1)
    b3 = _unit(net, f"{n}_b3x3dbl_2", b3, 96, 3, 3)
    b3 = _unit(net, f"{n}_b3x3dbl_3", b3, 96, 3, 3)
    bp = ops.avg_pool_same_excluding_pad(x, 3)
    bp = _unit(net, f"{n}_bpool", bp, pool_filters, 1, 1)
    return _cat(b1, b5, b3, bp)


def _mixed_17(net, x, n, f):
    b1 = _unit(net, f"{n}_b1x1", x, 192, 1, 1)
    b7 = _unit(net, f"{n}_b7x7_1", x, f, 1, 1)
    b7 = _unit(net, f"{n}_b7x7_2", b7, f, 1, 7)
    b7 = _unit(net, f"{n}_b7x7_3", b7, 192, 7, 1)
    bd = _unit(net, f"{n}_b7x7dbl_1", x, f, 1, 1)
    bd = _unit(net, f"{n}_b7x7dbl_2", bd, f, 7, 1)
    bd = _unit(net, f"{n}_b7x7dbl_3", bd, f, 1, 7)
    bd = _unit(net, f"{n}_b7x7dbl_4", bd, f, 7, 1)
    bd = _unit(net, f"{n}_b7x7dbl_5", bd, 192, 1, 7)
    bp = ops.avg_pool_same_excluding_pad(x, 3)
    bp = _unit(net, f"{n}_bpool", bp, 192, 1, 1)
    return _cat(b1, b7, bd, bp)


def _mixed_8(net, x, n):
    b1 = _unit(net, f"{n}_b1x1", x, 320, 1, 1)
    b3 = _unit(net, f"{n}_b3x3", x, 384, 1, 1)
    b3 = _cat(_unit(net, f"{n}_b3x3_1", b3, 384, 1, 3),
              _unit(net, f"{n}_b3x3_2", b3, 384, 3, 1))
    bd = _unit(net, f"{n}_b3x3dbl_1", x, 448, 1, 1)
    bd = _unit(net, f"{n}_b3x3dbl_2", bd, 384, 3, 3)
    bd = _cat(_unit(net, f"{n}_b3x3dbl_3", bd, 384, 1, 3),
              _unit(net, f"{n}_b3x3dbl_4", bd, 384, 3, 1))
    bp = ops.avg_pool_same_excluding_pad(x, 3)
    bp = _unit(net, f"{n}_bpool", bp, 192, 1, 1)
    return _cat(b1, b3, bd, bp)


def forward(net, x):
    """Preprocessed float32 NHWC images -> [N, 2048] features."""
    x = _unit(net, "stem_conv1", x, 32, 3, 3, stride=2, padding="VALID")
    x = _unit(net, "stem_conv2", x, 32, 3, 3, padding="VALID")
    x = _unit(net, "stem_conv3", x, 64, 3, 3)
    x = ops.max_pool(x, 3, 2)
    x = _unit(net, "stem_conv4", x, 80, 1, 1, padding="VALID")
    x = _unit(net, "stem_conv5", x, 192, 3, 3, padding="VALID")
    x = ops.max_pool(x, 3, 2)

    x = _mixed_35(net, x, "mixed0", 32)                     # 35 x 35 x 256
    x = _mixed_35(net, x, "mixed1", 64)                     # 35 x 35 x 288
    x = _mixed_35(net, x, "mixed2", 64)

    b3 = _unit(net, "mixed3_b3x3", x, 384, 3, 3, stride=2, padding="VALID")
    bd = _unit(net, "mixed3_b3x3dbl_1", x, 64, 1, 1)
    bd = _unit(net, "mixed3_b3x3dbl_2", bd, 96, 3, 3)
    bd = _unit(net, "mixed3_b3x3dbl_3", bd, 96, 3, 3, stride=2,
               padding="VALID")
    x = _cat(b3, bd, ops.max_pool(x, 3, 2))                 # 17 x 17 x 768

    x = _mixed_17(net, x, "mixed4", 128)
    x = _mixed_17(net, x, "mixed5", 160)
    x = _mixed_17(net, x, "mixed6", 160)
    x = _mixed_17(net, x, "mixed7", 192)

    b3 = _unit(net, "mixed8_b3x3_1", x, 192, 1, 1)
    b3 = _unit(net, "mixed8_b3x3_2", b3, 320, 3, 3, stride=2,
               padding="VALID")
    b7 = _unit(net, "mixed8_b7x7x3_1", x, 192, 1, 1)
    b7 = _unit(net, "mixed8_b7x7x3_2", b7, 192, 1, 7)
    b7 = _unit(net, "mixed8_b7x7x3_3", b7, 192, 7, 1)
    b7 = _unit(net, "mixed8_b7x7x3_4", b7, 192, 3, 3, stride=2,
               padding="VALID")
    x = _cat(b3, b7, ops.max_pool(x, 3, 2))                 # 8 x 8 x 1280

    x = _mixed_8(net, x, "mixed9")                          # 8 x 8 x 2048
    x = _mixed_8(net, x, "mixed10")
    return ops.global_avg_pool(x)


_FIELDS = {("conv", "kernel"): "kernel", ("bn", "bias"): "beta",
           ("bn", "mean"): "mean", ("bn", "var"): "var"}


def reference_name(path):
    """The reference's name for the program's variable at ``path``: the
    program nests ``conv``/``bn`` under the unit's name."""
    unit, child, field = path[-3], path[-2], path[-1]
    return f"{unit}/{_FIELDS[(child, field)]}"
