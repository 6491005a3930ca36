"""Plain reference of the Inception-v3 flow model (arXiv:1702.02295, the
`flyingchairs` preset's model).

Inception-v3 (arXiv:1512.00567) with SAME padding everywhere so that every
stage halves cleanly, conv + bias + ReLU and no normalisation, tapped at six
resolutions; a FlowNet-style decoder (ELU transposed convolutions of widths
512/256/128/64/32, one of stride 1 between the two taps that share a size)
predicts the flow at each. Layer paths are the published layer names.
"""

from __future__ import annotations

import jax.numpy as jnp

from ._common import (Params, avg_pool3, conv, flow_decoder, max_pool3, relu)

FLOW_SCALES = (10.0, 5.0, 2.5, 2.5, 1.25, 0.625)  # finest first


def _c(p, path, x, cout, kernel=(1, 1), stride=1):
    return conv(p, path, x, cout, kernel, stride, act=relu)


def _inception_a(p, path, x, pool_features):
    b0 = _c(p, f"{path}/b0_1x1", x, 64)
    b1 = _c(p, f"{path}/b1_1x1", x, 48)
    b1 = _c(p, f"{path}/b1_5x5", b1, 64, (5, 5))
    b2 = _c(p, f"{path}/b2_1x1", x, 64)
    b2 = _c(p, f"{path}/b2_3x3a", b2, 96, (3, 3))
    b2 = _c(p, f"{path}/b2_3x3b", b2, 96, (3, 3))
    b3 = _c(p, f"{path}/b3_proj", avg_pool3(x), pool_features)
    return jnp.concatenate([b0, b1, b2, b3], -1)


def _reduction_a(p, path, x):
    b0 = _c(p, f"{path}/b0_3x3", x, 384, (3, 3), 2)
    b1 = _c(p, f"{path}/b1_1x1", x, 64)
    b1 = _c(p, f"{path}/b1_3x3a", b1, 96, (3, 3))
    b1 = _c(p, f"{path}/b1_3x3b", b1, 96, (3, 3), 2)
    return jnp.concatenate([b0, b1, max_pool3(x)], -1)


def _inception_b(p, path, x, m):
    b0 = _c(p, f"{path}/b0_1x1", x, 192)
    b1 = _c(p, f"{path}/b1_1x1", x, m)
    b1 = _c(p, f"{path}/b1_1x7", b1, m, (1, 7))
    b1 = _c(p, f"{path}/b1_7x1", b1, 192, (7, 1))
    b2 = _c(p, f"{path}/b2_1x1", x, m)
    b2 = _c(p, f"{path}/b2_7x1a", b2, m, (7, 1))
    b2 = _c(p, f"{path}/b2_1x7a", b2, m, (1, 7))
    b2 = _c(p, f"{path}/b2_7x1b", b2, m, (7, 1))
    b2 = _c(p, f"{path}/b2_1x7b", b2, 192, (1, 7))
    b3 = _c(p, f"{path}/b3_proj", avg_pool3(x), 192)
    return jnp.concatenate([b0, b1, b2, b3], -1)


def _reduction_b(p, path, x):
    b0 = _c(p, f"{path}/b0_1x1", x, 192)
    b0 = _c(p, f"{path}/b0_3x3", b0, 320, (3, 3), 2)
    b1 = _c(p, f"{path}/b1_1x1", x, 192)
    b1 = _c(p, f"{path}/b1_1x7", b1, 192, (1, 7))
    b1 = _c(p, f"{path}/b1_7x1", b1, 192, (7, 1))
    b1 = _c(p, f"{path}/b1_3x3", b1, 192, (3, 3), 2)
    return jnp.concatenate([b0, b1, max_pool3(x)], -1)


def _inception_c(p, path, x):
    b0 = _c(p, f"{path}/b0_1x1", x, 320)
    b1 = _c(p, f"{path}/b1_1x1", x, 384)
    b1 = jnp.concatenate([_c(p, f"{path}/b1_1x3", b1, 384, (1, 3)),
                          _c(p, f"{path}/b1_3x1", b1, 384, (3, 1))], -1)
    b2 = _c(p, f"{path}/b2_1x1", x, 448)
    b2 = _c(p, f"{path}/b2_3x3", b2, 384, (3, 3))
    b2 = jnp.concatenate([_c(p, f"{path}/b2_1x3", b2, 384, (1, 3)),
                          _c(p, f"{path}/b2_3x1", b2, 384, (3, 1))], -1)
    b3 = _c(p, f"{path}/b3_proj", avg_pool3(x), 192)
    return jnp.concatenate([b0, b1, b2, b3], -1)


def forward(p: Params, pair):
    """pair: (B, H, W, 6) preprocessed frames. Returns six flows, finest
    (H/2) first."""
    e = "encoder"
    t1 = _c(p, f"{e}/Conv2d_1a_3x3", pair, 32, (3, 3), 2)
    net = _c(p, f"{e}/Conv2d_2a_3x3", t1, 32, (3, 3))
    net = _c(p, f"{e}/Conv2d_2b_3x3", net, 64, (3, 3))
    t2 = max_pool3(net)
    net = _c(p, f"{e}/Conv2d_3b_1x1", t2, 80)
    net = _c(p, f"{e}/Conv2d_4a_3x3", net, 192, (3, 3))
    t3 = max_pool3(net)
    net = _inception_a(p, f"{e}/Mixed_5b", t3, 32)
    net = _inception_a(p, f"{e}/Mixed_5c", net, 64)
    t4 = _inception_a(p, f"{e}/Mixed_5d", net, 64)
    net = _reduction_a(p, f"{e}/Mixed_6a", t4)
    net = _inception_b(p, f"{e}/Mixed_6b", net, 128)
    net = _inception_b(p, f"{e}/Mixed_6c", net, 160)
    net = _inception_b(p, f"{e}/Mixed_6d", net, 160)
    t5 = _inception_b(p, f"{e}/Mixed_6e", net, 192)
    net = _reduction_b(p, f"{e}/Mixed_7a", t5)
    net = _inception_c(p, f"{e}/Mixed_7b", net)
    t6 = _inception_c(p, f"{e}/Mixed_7c", net)
    return flow_decoder(p, [t6, t5, t4, t3, t2, t1],
                        widths=(512, 256, 128, 64, 32), scales=(2, 2, 1, 2, 2))
