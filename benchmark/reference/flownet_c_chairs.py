"""Plain reference of FlowNet-C (arXiv:1504.06852 section 3).

Siamese conv1-3 on each frame, a multiplicative correlation of the two
feature maps over displacements up to 20 in steps of 2 (441 maps, the mean
over channels, zero outside the image), `conv_redir` 32 beside it, the
FlowNet-S contracting tail and the shared decoder. ELU activations.
"""

from __future__ import annotations

import jax.numpy as jnp

from ._common import Params, conv, elu, flow_decoder

FLOW_SCALES = (20.0, 10.0, 5.0, 2.5, 1.25, 0.625)  # finest first
MAX_DISP, STRIDE = 20, 2


def correlation(f1, f2, max_disp: int = MAX_DISP, stride: int = STRIDE):
    """corr[b,y,x,i] = mean_c f1[b,y,x,c] * f2[b,y+dy_i,x+dx_i,c]."""
    b, h, w, c = f1.shape
    k = max_disp // stride
    pad = k * stride
    f2p = jnp.pad(f2, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    maps = []
    for iy in range(2 * k + 1):
        for ix in range(2 * k + 1):
            dy, dx = iy * stride, ix * stride
            maps.append(jnp.mean(f1 * f2p[:, dy:dy + h, dx:dx + w], axis=-1))
    return jnp.stack(maps, axis=-1)


def forward(p: Params, pair):
    """pair: (B, H, W, 6) preprocessed frames. Returns six flows, finest
    (H/2) first."""
    img1, img2 = pair[..., :3], pair[..., 3:]

    def tower(x):
        c1 = conv(p, "conv1", x, 64, (7, 7), 2, act=elu)
        c2 = conv(p, "conv2", c1, 128, (5, 5), 2, act=elu)
        return c1, c2, conv(p, "conv3", c2, 256, (5, 5), 2, act=elu)

    c1, c2, f1 = tower(img1)
    _, _, f2 = tower(img2)
    # the correlation's operands go through the hooks too: it is the one
    # product of activations in the model, and `corr` the part whose
    # backward the comparison can cut
    corr = elu(correlation(*p.backward_scaled("corr", p.quant(f1), p.quant(f2))))
    redir = conv(p, "conv_redir", f1, 32, (1, 1), act=elu)
    net = jnp.concatenate([corr, redir], -1)
    c3_1 = conv(p, "conv3_1", net, 256, act=elu)
    c4_1 = conv(p, "conv4_1", c3_1, 512, stride=2, act=elu)
    c4_2 = conv(p, "conv4_2", c4_1, 512, act=elu)
    c5_1 = conv(p, "conv5_1", c4_2, 512, stride=2, act=elu)
    c5_2 = conv(p, "conv5_2", c5_1, 512, act=elu)
    c6_1 = conv(p, "conv6_1", c5_2, 1024, stride=2, act=elu)
    c6_2 = conv(p, "conv6_2", c6_1, 1024, act=elu)
    return flow_decoder(p, [c6_2, c5_2, c4_2, c3_1, c2, c1],
                        widths=(512, 256, 128, 64, 32), scales=(2, 2, 2, 2, 2))
