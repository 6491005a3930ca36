"""VGG16-backbone flow model.

Parity with reference `VGG16` (`flyingChairsWrapFlow.py:635-749`): 13-conv
VGG16 trunk with 2x2 max-pools, 5 pyramid heads on pool5..pool1 with flow
scales 10/5/2.5/1.25/0.625 finest-first, decoder deconv widths
256/128/64/32. The reference pads its losses/flows lists to 6 entries by
repeating the coarsest — we return the true 5 scales (divergence documented;
the padding carried no information).

`VGG16Trunk` is reusable by the UCF-101 two-stream models, which tap pool5
(`ucf101wrapFlow.py:82-119`).
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp
from flax import linen as nn

from .common import ConvELU, FlowDecoder

FLOW_SCALES = (10.0, 5.0, 2.5, 1.25, 0.625)  # finest (pr1) first

_VGG_CFG = ((64, 2), (128, 2), (256, 3), (512, 3), (512, 3))


class VGG16Trunk(nn.Module):
    """conv1_1..conv5_3 + pools; returns [pool1..pool5]."""

    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> list[jnp.ndarray]:
        pools = []
        for block, (feat, n) in enumerate(_VGG_CFG, start=1):
            for i in range(1, n + 1):
                x = ConvELU(feat, dtype=self.dtype, name=f"conv{block}_{i}")(x)
            x = nn.max_pool(x, (2, 2), strides=(2, 2), padding="SAME")
            pools.append(x)
        return pools


class VGG16Flow(nn.Module):
    flow_channels: int = 2
    dtype: Any = jnp.float32

    flow_scales: tuple[float, ...] = FLOW_SCALES
    max_downsample = 32  # five maxpools; spatial-CP gradient-safety bound
    vgg16_trunk_path = ("encoder",)  # where `train.vgg16_npz` lands

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> list[jnp.ndarray]:
        pools = VGG16Trunk(dtype=self.dtype, name="encoder")(x)
        flows = FlowDecoder(
            upconv_features=(256, 128, 64, 32),
            flow_channels=self.flow_channels,
            dtype=self.dtype,
            name="decoder",
        )(pools[::-1])
        return flows[::-1]
