"""MobileNetV2 as a flax module — a zoo extension BEYOND the reference.

The reference's ``SUPPORTED_MODELS`` stops at five architectures
(``python/sparkdl/transformers/named_image.py``); MobileNetV2 (alpha=1.0,
224x224) is added because edge-class backbones are the common "cheap
featurizer" ask the reference never served.  Featurizer cut = global
average pool (1280-d).

Layer names mirror ``keras.applications.MobileNetV2`` exactly ("Conv1",
"bn_Conv1", "expanded_conv_depthwise", "block_1_expand", ..., "Conv_1",
"predictions"), so weight import matches entirely BY NAME (no
creation-order table needed).  Keras's stride-2 stages zero-pad
((0,1),(0,1)) then convolve VALID; reproduced verbatim so spatial parity
is exact.  BN epsilon 1e-3 (the keras app overrides the layer default).
"""

from __future__ import annotations

import jax.numpy as jnp
from flax import linen as nn

from sparkdl_tpu.models.layers import DepthwiseConv2D, global_avg_pool

# (expansion t, out channels c, repeats n, first stride s) — table 2 of the
# MobileNetV2 paper, alpha=1.0.
_BLOCKS = ((1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
           (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1))


def _relu6(x):
    return jnp.minimum(nn.relu(x), 6.0)


def _pad_correct(x):
    """Keras ``ZeroPadding2D(((0,1),(0,1)))`` before stride-2 VALID convs."""
    return jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0)))


class MobileNetV2(nn.Module):
    num_classes: int = 1000

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False,
                 features: bool = False, logits: bool = False) -> jnp.ndarray:
        def bn(name):
            return nn.BatchNorm(use_running_average=not train,
                                momentum=0.999, epsilon=1e-3, name=name)

        # Stem: pad-correct + 3x3 s2 VALID
        x = _pad_correct(x)
        x = nn.Conv(32, (3, 3), strides=(2, 2), padding="VALID",
                    use_bias=False, name="Conv1")(x)
        x = _relu6(bn("bn_Conv1")(x))

        block_id = 0
        for t, c, n, s in _BLOCKS:
            for i in range(n):
                stride = s if i == 0 else 1
                prefix = ("expanded_conv" if block_id == 0
                          else f"block_{block_id}")
                cin = x.shape[-1]
                inp = x
                if t != 1:
                    x = nn.Conv(cin * t, (1, 1), use_bias=False,
                                name=f"{prefix}_expand")(x)
                    x = _relu6(bn(f"{prefix}_expand_BN")(x))
                if stride == 2:
                    x = _pad_correct(x)
                x = DepthwiseConv2D(
                    (3, 3), strides=(stride, stride),
                    padding="SAME" if stride == 1 else "VALID",
                    use_bias=False, name=f"{prefix}_depthwise")(x)
                x = _relu6(bn(f"{prefix}_depthwise_BN")(x))
                x = nn.Conv(c, (1, 1), use_bias=False,
                            name=f"{prefix}_project")(x)
                x = bn(f"{prefix}_project_BN")(x)  # linear bottleneck
                if stride == 1 and cin == c:
                    x = x + inp
                block_id += 1

        x = nn.Conv(1280, (1, 1), use_bias=False, name="Conv_1")(x)
        x = _relu6(bn("Conv_1_bn")(x))
        x = global_avg_pool(x)  # 1280-d featurizer cut
        if features:
            return x
        x = nn.Dense(self.num_classes, name="predictions")(x)
        if logits:
            return x
        return nn.softmax(x)
