"""UCF-101 action models: spatial classifier, STsingle, STbaseline.

Parity with `ucf101wrapFlow.py`:
  - `UCF101Spatial` (`:7-60`): plain VGG16 (ReLU) on a single frame +
    fc6(4096)/fc7(4096)/fc8(101) with dropout keep-prob 0.9; supervised
    cross-entropy only.
  - `STSingle` (`:62-194`): ONE shared VGG16 trunk (ELU) over the
    concatenated frame pair; spatial branch = fc head on pool5; temporal
    branch = 5 flow heads pr5..pr1 on pool5..pool1 (flow scales
    10/5/2.5/1.25/0.625 finest-first). Joint loss = weighted flow losses +
    weight[0] * action cross-entropy (`:186-188`) — assembled by the
    trainer, the model returns (flows, action_logits).
  - `STBaseline` (`:197-363`): independent FlowNet-S temporal trunk (6 flow
    heads) + VGG16 spatial trunk (ReLU, single frame); classifier consumes
    concat(pool5, Tconv5_2) -> 2x2 maxpool -> concat(., Tconv6_2) -> 1x1
    conv 512 -> fc head (`:330-337`).

Cross-entropy itself lives in `losses` land (optax), not in the model.
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp
from flax import linen as nn

from .common import FlowDecoder, conv_init, flownet_trunk
from .flownet_s import FLOW_SCALES as FLOWNET_SCALES
from .vgg16_flow import FLOW_SCALES as VGG_SCALES
from .vgg16_flow import VGG16Trunk

_fc_init = nn.initializers.truncated_normal(0.01)


class _VGGReLUTrunk(nn.Module):
    """VGG16 conv trunk with ReLU + truncated-normal init (the classifier
    flavor, `ucf101wrapFlow.py:13-49`); returns [pool1..pool5]."""

    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        pools = []
        for block, (feat, n) in enumerate(
            ((64, 2), (128, 2), (256, 3), (512, 3), (512, 3)), start=1
        ):
            for i in range(1, n + 1):
                x = nn.Conv(feat, (3, 3), padding="SAME", kernel_init=_fc_init,
                            dtype=self.dtype, name=f"conv{block}_{i}")(x)
                x = nn.relu(x)
            x = nn.max_pool(x, (2, 2), strides=(2, 2), padding="SAME")
            pools.append(x)
        return pools


class _FCHead(nn.Module):
    """flatten -> fc6 -> drop -> fc7 -> drop -> fc8(num_classes) logits."""

    num_classes: int = 101
    act: str = "relu"  # STsingle uses elu (arg_scope), classifier uses relu
    dropout_rate: float = 0.1  # slim keep_prob 0.9
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        act = nn.elu if self.act == "elu" else nn.relu
        init = _fc_init if self.act == "relu" else conv_init
        x = x.reshape(x.shape[0], -1)
        x = act(nn.Dense(4096, kernel_init=init, dtype=self.dtype, name="fc6")(x))
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = act(nn.Dense(4096, kernel_init=init, dtype=self.dtype, name="fc7")(x))
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        return nn.Dense(self.num_classes, kernel_init=init, dtype=self.dtype,
                        name="fc8")(x)


class UCF101Spatial(nn.Module):
    num_classes: int = 101
    dtype: Any = jnp.float32

    classifier_only = True  # step dispatch: logits, no flow pyramid
    max_downsample = 32
    task = "classify"  # eval: accuracy (models/registry.py)
    input_frames = 1  # one frame in, whatever the dataset's time_step
    vgg16_trunk_path = ("encoder",)  # where `train.vgg16_npz` lands

    @nn.compact
    def __call__(self, frame: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        pools = _VGGReLUTrunk(dtype=self.dtype, name="encoder")(frame)
        return _FCHead(self.num_classes, dtype=self.dtype, name="head")(pools[-1], train)


class STSingle(nn.Module):
    """Shared-encoder two-stream model. Input: (B, H, W, 6) frame pair."""

    num_classes: int = 101
    flow_channels: int = 2
    dtype: Any = jnp.float32

    flow_scales: tuple[float, ...] = VGG_SCALES
    max_downsample = 32
    has_action_head = True  # step dispatch: returns (flows, logits)
    task = "action"
    smooth_border_mask = True
    vgg16_trunk_path = ("encoder",)

    @nn.compact
    def __call__(self, pair: jnp.ndarray, train: bool = False):
        pools = VGG16Trunk(dtype=self.dtype, name="encoder")(pair)
        logits = _FCHead(self.num_classes, act="elu", dtype=self.dtype,
                         name="head")(pools[-1], train)
        flows = FlowDecoder(
            upconv_features=(256, 128, 64, 32),
            flow_channels=self.flow_channels,
            dtype=self.dtype,
            name="decoder",
        )(pools[::-1])
        return flows[::-1], logits


class STBaseline(nn.Module):
    """Two independent streams + temporal->classifier feature fusion.

    Input: (B, H, W, 6) frame pair; the spatial stream sees frame 1 only
    (`ucf101wrapFlow.py:281`).
    """

    num_classes: int = 101
    flow_channels: int = 2
    dtype: Any = jnp.float32

    flow_scales: tuple[float, ...] = FLOWNET_SCALES
    max_downsample = 64
    has_action_head = True  # step dispatch: returns (flows, logits)
    task = "action"
    smooth_border_mask = True
    vgg16_trunk_path = ("spatial",)

    @nn.compact
    def __call__(self, pair: jnp.ndarray, train: bool = False):
        dt = self.dtype
        # temporal FlowNet-S trunk
        taps = flownet_trunk(pair, dt, prefix="Tconv")
        t5_2, t6_2 = taps[4], taps[5]

        flows = FlowDecoder(
            upconv_features=(512, 256, 128, 64, 32),
            flow_channels=self.flow_channels,
            dtype=dt,
            name="decoder",
        )(taps[::-1])

        # spatial VGG16 on frame 1
        pools = _VGGReLUTrunk(dtype=dt, name="spatial")(pair[..., :3])

        # fusion: concat(pool5, Tconv5_2) -> pool -> concat(., Tconv6_2) -> 1x1
        st = jnp.concatenate([pools[-1], t5_2], axis=-1)
        st = nn.max_pool(st, (2, 2), strides=(2, 2), padding="SAME")
        st = jnp.concatenate([st, t6_2], axis=-1)
        st = nn.relu(nn.Conv(512, (1, 1), kernel_init=_fc_init, dtype=dt,
                             name="fuse_1x1")(st))
        logits = _FCHead(self.num_classes, dtype=dt, name="head")(st, train)
        return flows[::-1], logits
