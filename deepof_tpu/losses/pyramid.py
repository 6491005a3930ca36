"""Multi-scale pyramid loss orchestration.

The reference entangles preprocessing, per-scale resizing, and loss calls
inside each model graph (`flyingChairsWrapFlow.py:16-124`). Here the model
only predicts a flow pyramid; this module owns:

  - preprocessing: BGR dataset-mean subtraction, /255 scaling, and the LRN
    copy used exclusively inside the photometric loss
    (`flyingChairsWrapFlow.py:16-26`);
  - resizing the LRN images to every pyramid resolution (bilinear; the
    reference uses TF1's legacy asymmetric resize_bilinear — we use
    half-pixel-centered bilinear, which matches cv2/`check_loss.py` and is
    the modern convention; divergence documented);
  - per-scale `loss_interp` and the weighted total
    (`flyingChairsWrapFlow.py:122-124`), weights ordered finest (pr1) first.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.config import LossConfig
from ..ops.lrn import local_response_normalization
from .photometric import (
    LossDict,
    loss_interp,
    loss_interp_multi,
    occlusion_mask,
)


def preprocess(images: jnp.ndarray, mean) -> jnp.ndarray:
    """(images - BGR mean) / 255 — the network input scaling."""
    return (images - jnp.asarray(mean)) / 255.0


def lrn_normalize(scaled: jnp.ndarray) -> jnp.ndarray:
    """LRN copy of preprocessed images for the photometric loss."""
    return local_response_normalization(scaled, depth_radius=4, beta=0.7)


def _resize(img: jnp.ndarray, h: int, w: int) -> jnp.ndarray:
    if img.shape[1] == h and img.shape[2] == w:
        return img
    return jax.image.resize(img, (img.shape[0], h, w, img.shape[3]), "bilinear")


def pyramid_loss(
    flow_pyramid: list[tuple[jnp.ndarray, float]],
    inputs_norm: jnp.ndarray,
    outputs_norm: jnp.ndarray,
    cfg: LossConfig,
    smooth_border_mask: bool = False,
    flow_pyramid_bw: list[jnp.ndarray] | None = None,
) -> tuple[jnp.ndarray, list[LossDict], jnp.ndarray]:
    """flow_pyramid: [(flow_k, flow_scale_k)] finest first.

    flow_pyramid_bw: optional matching backward-flow pyramid (raw head
    outputs, same scales) enabling per-scale fw/bw occlusion masking of
    the photometric term (`LossConfig.occlusion`).

    Returns (weighted_total, per-scale loss dicts finest first, finest
    reconstruction).
    """
    losses: list[LossDict] = []
    recon_finest = None
    total = jnp.zeros(())
    for k, (flow, scale) in enumerate(flow_pyramid):
        h, w = flow.shape[1:3]
        # one scope a level (0 = finest): a profile's operations carry it
        # in their op_name, the backward pass as transpose(jvp(...))
        with jax.named_scope(f"loss_level_{k}"):
            li = _resize(inputs_norm, h, w)
            lo = _resize(outputs_norm, h, w)
            occ = None
            if flow_pyramid_bw is not None:
                occ = occlusion_mask(flow * scale, flow_pyramid_bw[k] * scale,
                                     cfg)
            ld, recon = loss_interp(flow, li, lo, scale, cfg,
                                    smooth_border_mask, occ_mask=occ)
        losses.append(ld)
        if k == 0:
            recon_finest = recon
        weight = cfg.weights[k] if k < len(cfg.weights) else cfg.weights[-1]
        total = total + weight * ld["total"]
    return total, losses, recon_finest


def pyramid_loss_multi(
    flow_pyramid: list[tuple[jnp.ndarray, float]],
    volume_norm: jnp.ndarray,
    cfg: LossConfig,
) -> tuple[jnp.ndarray, list[LossDict], jnp.ndarray]:
    """Multi-frame (Sintel T-volume) pyramid loss; flows have 2*(T-1) ch."""
    losses = []
    recon_finest = None
    total = jnp.zeros(())
    for k, (flow, scale) in enumerate(flow_pyramid):
        h, w = flow.shape[1:3]
        with jax.named_scope(f"loss_level_{k}"):
            vol = _resize(volume_norm, h, w)
            ld, recon = loss_interp_multi(flow, vol, scale, cfg)
        losses.append(ld)
        if k == 0:
            recon_finest = recon
        weight = cfg.weights[k] if k < len(cfg.weights) else cfg.weights[-1]
        total = total + weight * ld["total"]
    return total, losses, recon_finest
