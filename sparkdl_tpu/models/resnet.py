"""ResNet50 as a flax module.

Zoo entry from the reference's ``SUPPORTED_MODELS`` registry
(``python/sparkdl/transformers/named_image.py``).  Featurizer cut = global
average pool (2048-d), matching ``DeepImageFeaturizer``'s penultimate-layer
semantics.

Architecture and layer names mirror keras.applications ResNet50 (v1
bottleneck blocks, stride on the first 1x1 conv, BN epsilon 1.001e-5,
explicit 3-pad before the 7x7 stem conv) so the weight importer matches by
name: "conv1_conv", "conv2_block1_1_conv", ..., "predictions".
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
from flax import linen as nn

from sparkdl_tpu.models.layers import global_avg_pool

BN_EPS = 1.001e-5
BN_MOMENTUM = 0.99


def _bn(name: str, train: bool) -> nn.BatchNorm:
    return nn.BatchNorm(use_running_average=not train, momentum=BN_MOMENTUM,
                        epsilon=BN_EPS, name=name)


class BottleneckBlock(nn.Module):
    """Keras ``residual_block_v1``: 1x1 -> 3x3 -> 1x1 with a (possibly
    projected) shortcut; stride lives on the first 1x1 conv (classic v1)."""

    filters: int
    stride: int = 1
    conv_shortcut: bool = True
    prefix: str = ""

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        p = self.prefix
        if self.conv_shortcut:
            shortcut = nn.Conv(4 * self.filters, (1, 1),
                               strides=(self.stride, self.stride),
                               name=f"{p}_0_conv")(x)
            shortcut = _bn(f"{p}_0_bn", train)(shortcut)
        else:
            shortcut = x
        y = nn.Conv(self.filters, (1, 1),
                    strides=(self.stride, self.stride),
                    name=f"{p}_1_conv")(x)
        y = nn.relu(_bn(f"{p}_1_bn", train)(y))
        y = nn.Conv(self.filters, (3, 3), padding="SAME",
                    name=f"{p}_2_conv")(y)
        y = nn.relu(_bn(f"{p}_2_bn", train)(y))
        y = nn.Conv(4 * self.filters, (1, 1), name=f"{p}_3_conv")(y)
        y = _bn(f"{p}_3_bn", train)(y)
        return nn.relu(shortcut + y)


RESNET_STAGES = {
    # (filters, num_blocks, first_stride) per stage, keras stack order
    50: ((64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2)),
    101: ((64, 3, 1), (128, 4, 2), (256, 23, 2), (512, 3, 2)),
    152: ((64, 3, 1), (128, 8, 2), (256, 36, 2), (512, 3, 2)),
}


class ResNet50(nn.Module):
    """Also parameterizes ResNet101/152 via ``stages`` (keras layer names
    are depth-independent — ``conv{stage}_block{b}_*`` — so the by-name
    weight importer covers the whole family)."""

    num_classes: int = 1000
    stages: Tuple[Tuple[int, int, int], ...] = RESNET_STAGES[50]

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False,
                 features: bool = False, logits: bool = False) -> jnp.ndarray:
        # Stem: explicit 3-pad + 7x7/2 VALID conv (keras "conv1_pad"+"conv1_conv")
        x = nn.Conv(64, (7, 7), strides=(2, 2), padding=((3, 3), (3, 3)),
                    name="conv1_conv")(x)
        x = nn.relu(_bn("conv1_bn", train)(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for stage_idx, (filters, blocks, stride) in enumerate(self.stages, 2):
            for b in range(1, blocks + 1):
                x = BottleneckBlock(
                    filters=filters,
                    stride=stride if b == 1 else 1,
                    conv_shortcut=(b == 1),
                    prefix=f"conv{stage_idx}_block{b}",
                    name=f"conv{stage_idx}_block{b}")(x, train=train)
        x = global_avg_pool(x)  # 2048-d featurizer cut
        if features:
            return x
        x = nn.Dense(self.num_classes, name="predictions")(x)
        if logits:
            return x
        return nn.softmax(x)


def ResNet101(**kwargs) -> ResNet50:
    return ResNet50(stages=RESNET_STAGES[101], **kwargs)


def ResNet152(**kwargs) -> ResNet50:
    return ResNet50(stages=RESNET_STAGES[152], **kwargs)
