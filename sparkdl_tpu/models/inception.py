"""InceptionV3 as a flax module — the north-star featurizer model
(BASELINE.json config #1; reference zoo entry in
``python/sparkdl/transformers/named_image.py — SUPPORTED_MODELS`` and
``src/main/scala/com/databricks/sparkdl/Models.scala``).

The architecture (94 conv+BN units, mixed0..mixed10) is declared ONCE as a
spec table; both the forward pass and the Keras weight-import order are
generated from it, so they cannot drift.  Import is order-matched because
upstream keras.applications leaves InceptionV3's conv/BN layers auto-named
(``conv2d_41``) — see ``models/keras_import.py``.

Keras semantics preserved: conv(no bias) + BN(scale=False, eps=1e-3) + relu;
avg-pool branches exclude padding from the denominator (TF AvgPool SAME
behavior); featurizer cut = global average pool (2048-d).
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
from flax import linen as nn

from sparkdl_tpu.models.layers import ConvBN, global_avg_pool


class C(NamedTuple):
    """One conv2d_bn unit."""
    name: str
    filters: int
    kh: int
    kw: int
    strides: Tuple[int, int] = (1, 1)
    padding: str = "SAME"


class P(NamedTuple):
    """One pooling op."""
    kind: str  # "max" | "avg"
    window: int
    stride: int
    padding: str


Split = Tuple[str, list, list]               # ("split", ops_a, ops_b)
Op = Union[C, P, Split]
Block = Tuple[str, List[List[Op]]]           # ("mixed0", [branch_ops, ...])


def _c(name, f, kh, kw, s=1, p="SAME"):
    return C(name, f, kh, kw, (s, s), p)


def _mixed35(i: int, pool_filters: int) -> Block:
    n = f"mixed{i}"
    return (n, [
        [_c(f"{n}_b1x1", 64, 1, 1)],
        [_c(f"{n}_b5x5_1", 48, 1, 1), _c(f"{n}_b5x5_2", 64, 5, 5)],
        [_c(f"{n}_b3x3dbl_1", 64, 1, 1), _c(f"{n}_b3x3dbl_2", 96, 3, 3),
         _c(f"{n}_b3x3dbl_3", 96, 3, 3)],
        [P("avg", 3, 1, "SAME"), _c(f"{n}_bpool", pool_filters, 1, 1)],
    ])


def _mixed17(i: int, f: int) -> Block:
    n = f"mixed{i}"
    return (n, [
        [_c(f"{n}_b1x1", 192, 1, 1)],
        [_c(f"{n}_b7x7_1", f, 1, 1), _c(f"{n}_b7x7_2", f, 1, 7),
         _c(f"{n}_b7x7_3", 192, 7, 1)],
        [_c(f"{n}_b7x7dbl_1", f, 1, 1), _c(f"{n}_b7x7dbl_2", f, 7, 1),
         _c(f"{n}_b7x7dbl_3", f, 1, 7), _c(f"{n}_b7x7dbl_4", f, 7, 1),
         _c(f"{n}_b7x7dbl_5", 192, 1, 7)],
        [P("avg", 3, 1, "SAME"), _c(f"{n}_bpool", 192, 1, 1)],
    ])


def _mixed8x8(i: int) -> Block:
    n = f"mixed{i}"
    return (n, [
        [_c(f"{n}_b1x1", 320, 1, 1)],
        [_c(f"{n}_b3x3", 384, 1, 1),
         ("split",
          [_c(f"{n}_b3x3_1", 384, 1, 3)],
          [_c(f"{n}_b3x3_2", 384, 3, 1)])],
        [_c(f"{n}_b3x3dbl_1", 448, 1, 1), _c(f"{n}_b3x3dbl_2", 384, 3, 3),
         ("split",
          [_c(f"{n}_b3x3dbl_3", 384, 1, 3)],
          [_c(f"{n}_b3x3dbl_4", 384, 3, 1)])],
        [P("avg", 3, 1, "SAME"), _c(f"{n}_bpool", 192, 1, 1)],
    ])


# Full network in upstream source build order (keras inception_v3.py).
STEM: List[Op] = [
    _c("stem_conv1", 32, 3, 3, s=2, p="VALID"),
    _c("stem_conv2", 32, 3, 3, p="VALID"),
    _c("stem_conv3", 64, 3, 3),
    P("max", 3, 2, "VALID"),
    _c("stem_conv4", 80, 1, 1, p="VALID"),
    _c("stem_conv5", 192, 3, 3, p="VALID"),
    P("max", 3, 2, "VALID"),
]

BLOCKS: List[Block] = [
    _mixed35(0, 32),
    _mixed35(1, 64),
    _mixed35(2, 64),
    ("mixed3", [
        [_c("mixed3_b3x3", 384, 3, 3, s=2, p="VALID")],
        [_c("mixed3_b3x3dbl_1", 64, 1, 1), _c("mixed3_b3x3dbl_2", 96, 3, 3),
         _c("mixed3_b3x3dbl_3", 96, 3, 3, s=2, p="VALID")],
        [P("max", 3, 2, "VALID")],
    ]),
    _mixed17(4, 128),
    _mixed17(5, 160),
    _mixed17(6, 160),
    _mixed17(7, 192),
    ("mixed8", [
        [_c("mixed8_b3x3_1", 192, 1, 1),
         _c("mixed8_b3x3_2", 320, 3, 3, s=2, p="VALID")],
        [_c("mixed8_b7x7x3_1", 192, 1, 1), _c("mixed8_b7x7x3_2", 192, 1, 7),
         _c("mixed8_b7x7x3_3", 192, 7, 1),
         _c("mixed8_b7x7x3_4", 192, 3, 3, s=2, p="VALID")],
        [P("max", 3, 2, "VALID")],
    ]),
    _mixed8x8(9),
    _mixed8x8(10),
]


def _iter_convs(ops: Sequence[Op]):
    for op in ops:
        if isinstance(op, C):
            yield op
        elif isinstance(op, tuple) and op and op[0] == "split":
            yield from _iter_convs(op[1])
            yield from _iter_convs(op[2])


def inception_import_order():
    """(kind, flax_path) sequence in upstream creation order for the
    auto-named conv/BN layers.  Each conv2d_bn creates its Conv2D then its
    BatchNormalization, so per-kind creation order both equal spec order.
    (The final "predictions" Dense is explicitly named upstream and matches
    by name instead.)"""
    order = []
    convs = list(_iter_convs(STEM))
    for _, branches in BLOCKS:
        for branch in branches:
            convs.extend(_iter_convs(branch))
    for c in convs:
        order.append(("conv", (c.name, "conv")))
        order.append(("bn", (c.name, "bn")))
    return order


class InceptionV3(nn.Module):
    """``fused_heads``: at inference, the 2-3 LEADING 1x1 convs of each mixed
    block's branches (which all read the same block input) run as ONE
    wider conv — kernels concatenated along output channels, BN folded
    into the kernel/shift, one ReLU, then split.  Identical math and
    variables (``ConvBN(fold=True)`` declares the same tree); attacks the
    "many small matmuls" MFU story the round-4 profile documented (no
    single fusion >4% of device time).  None = on at inference; False
    keeps the per-branch (training) program: the parity test's reference."""

    num_classes: int = 1000
    fused_heads: Optional[bool] = None

    def _use_fused_heads(self, train: bool) -> bool:
        if train:
            return False
        return True if self.fused_heads is None else self.fused_heads

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False,
                 features: bool = False, logits: bool = False) -> jnp.ndarray:
        fuse_heads = self._use_fused_heads(train)

        def pool(x, p: P):
            if p.kind == "max":
                return nn.max_pool(x, (p.window, p.window),
                                   strides=(p.stride, p.stride),
                                   padding=p.padding)
            # flax divides by f32 window counts under count_include_pad=
            # False, which would upcast a bf16 program — and every conv
            # downstream of the branch concat — to f32 (graftcheck GC002);
            # the cast is a no-op in the default f32 path
            return nn.avg_pool(x, (p.window, p.window),
                               strides=(p.stride, p.stride),
                               padding=p.padding,
                               count_include_pad=False).astype(x.dtype)

        def run(x, ops: Sequence[Op]):
            for op in ops:
                if isinstance(op, C):
                    x = ConvBN(op.filters, (op.kh, op.kw), strides=op.strides,
                               padding=op.padding, bn_eps=1e-3,
                               bn_scale=False,
                               name=op.name)(x, train=train)
                elif isinstance(op, P):
                    x = pool(x, op)
                else:  # split: apply both arms to x, concat results
                    a = run(x, op[1])
                    b = run(x, op[2])
                    x = jnp.concatenate([a, b], axis=-1)
            return x

        def run_block(x, branches):
            """One mixed block.  With fused heads, every branch whose
            first op is a stride-1 1x1 ConvBN is started by one combined
            conv over the shared block input; remaining ops run per
            branch from their split slice."""
            head_idx = [bi for bi, br in enumerate(branches)
                        if (isinstance(br[0], C) and br[0].kh == 1
                            and br[0].kw == 1 and br[0].strides == (1, 1))]
            starts = {}
            if fuse_heads and len(head_idx) >= 2:
                import jax.lax as lax

                parts = []
                for bi in head_idx:
                    c0 = branches[bi][0]
                    k, s, t = ConvBN(c0.filters, (1, 1), bn_eps=1e-3,
                                     bn_scale=False, name=c0.name)(
                        x, fold=True)
                    parts.append((c0.filters, k, s, t))
                # fold the BN scale into the kernel (conv is linear), keep
                # the conv in the variables' dtype (bf16 under the engine)
                from sparkdl_tpu.models.layers import fold_bn_into_conv

                folded = [fold_bn_into_conv(k, s, t)
                          for _, k, s, t in parts]
                kdt = folded[0][0].dtype
                K = jnp.concatenate([f[0] for f in folded], axis=-1)
                T = jnp.concatenate([f[1] for f in folded])
                y = lax.conv_general_dilated(
                    x.astype(kdt), K, (1, 1), "SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"))
                y = nn.relu(y + T.astype(y.dtype)).astype(x.dtype)
                off = 0
                for bi, (f, _, _, _) in zip(head_idx, parts):
                    starts[bi] = y[..., off:off + f]
                    off += f
            outs = []
            for bi, br in enumerate(branches):
                if bi in starts:
                    outs.append(run(starts[bi], br[1:]))
                else:
                    outs.append(run(x, br))
            return jnp.concatenate(outs, axis=-1)

        x = run(x, STEM)
        for _, branches in BLOCKS:
            x = run_block(x, branches)
        x = global_avg_pool(x)  # 2048-d featurizer cut
        if features:
            return x
        x = nn.Dense(self.num_classes, name="predictions")(x)
        if logits:
            return x
        return nn.softmax(x)
