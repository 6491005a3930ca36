"""Bilinear backward warping — the core "kernel" op.

Semantics match the reference's TF graph construction at
`flyingChairsWrapFlow.py:785-838` exactly, but fully vectorized (one fused
XLA gather instead of the reference's O(batch * channels) python-loop graph
nodes):

  - flow channel 0 = u = horizontal displacement (added to the x/width
    coordinate), channel 1 = v = vertical (y/height);
  - the *already scaled* flow is split into integer floor + fractional
    weights;
  - each of the four neighbor coordinates is clipped to the image border
    independently (clip-at-border, NOT zero-fill outside);
  - the four neighbors are blended bilinearly.

`backward_warp(next_frame, flow)` returns the next frame warped backward to
the previous frame's coordinates ("reconstructs" in the reference).

TPU note: XLA lowers `jnp.take_along_axis` over the flattened H*W axis to a
single dynamic-gather, which is the right tool for fine pyramid levels
(Mosaic cannot express arbitrary-displacement gathers — see
`ops/pallas/warp.py`). For coarse levels (W <= 128) the Pallas row-sweep
kernel computes the same warp in one VMEM pass; select it with
`impl="pallas"` or `impl="auto"`.

Gather-cost note: a TPU gather's cost scales with the index count times
the gathered-row width, and narrow rows waste the 128-lane datapath. The
naive formulation issues FOUR gathers of C(=3)-wide rows (one per
bilinear neighbor, 3/128 lane utilization). The XLA path here instead
packs the 2x2 neighborhood into channels with two edge-clamped shifts
(patch = [img, img_x+1, img_y+1, img_xy], a (B,H,W,4C) tensor built by
cheap rolls) and issues ONE gather of 4C-wide rows at the (y0, x0) base
address: 4x fewer indices, 4x wider rows. Border exactness: the shifted
channels give neighbor min(x0+1, w-1) instead of the reference's
x1 = clip(x+fx+1), which differ only when x+fx < 0 (both collapse to
column 0 there); zeroing the fractional weight on that saturated side
reproduces the reference's value AND its (zero) flow gradient exactly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: impl="auto" routes to the Pallas kernel when W <= 128 (the kernel's
#: hard limit: one 128-lane register) AND H <= 128. Measured on v5e
#: (perf_probe warp section, r03): the kernel beats the XLA gather at
#: every real pyramid level it admits (40x56 and 80x112, fwd and grad —
#: no admissible level is taller than 80). The H cap is a safety fence,
#: not a tuning knob: the kernel holds whole (Hp, 128) planes in VMEM
#: and its row sweep is a serial 2H-1 loop, so a tall-narrow input
#: (e.g. 4096x64) would compile slowly or not at all — such shapes fall
#: back to the XLA patch-gather instead.
PALLAS_AUTO_MAX_W = 128
PALLAS_AUTO_MAX_H = 128


def backward_warp(image: jnp.ndarray, flow: jnp.ndarray,
                  impl: str = "xla",
                  batch_axes: tuple[str, ...] = ("data",)) -> jnp.ndarray:
    """Warp `image` (B, H, W, C) backward by `flow` (B, H, W, 2).

    `flow` must already include any flow_scale factor (the caller applies it,
    as the reference does at `flyingChairsWrapFlow.py:785`).

    impl: "xla" (one fused patch-gather, any size; the function default —
    golden tests and the Pallas image-cotangent fallback reference it),
    "pallas" (VMEM row-sweep kernel, requires W <= 128), or "auto"
    (pallas where admissible, xla for fine levels — the measured-fastest
    choice and the `LossConfig.warp_impl` default).

    batch_axes: the mesh axes the leading axis is sharded over — the
    Pallas kernel launches once per shard of them under a `mesh_context`
    (the XLA formulation is partitioned by GSPMD and ignores it).
    """
    b, h, w, c = image.shape
    # "auto" = the measured-fastest choice, and the measurement is a TPU
    # measurement: off-TPU the kernel only exists in interpret mode
    # (python-level emulation, ~10-100x slower than the XLA gather — it
    # silently dominated the CPU-mesh test suite's runtime before this
    # gate). Explicit impl="pallas" still honors the request anywhere,
    # which is what the kernel's correctness tests use.
    if impl == "pallas" or (impl == "auto" and w <= PALLAS_AUTO_MAX_W
                            and h <= PALLAS_AUTO_MAX_H
                            and jax.default_backend() == "tpu"):
        from .pallas.warp import backward_warp_pallas

        return backward_warp_pallas(image, flow, batch_axes=batch_axes)
    elif impl not in ("xla", "auto"):
        raise ValueError(f"unknown warp impl {impl!r}")
    flow_flat = flow.reshape(b, h * w, 2)

    floor_flow = jnp.floor(flow_flat)
    frac = flow_flat - floor_flow
    fx = floor_flow[..., 0].astype(jnp.int32)  # u -> x offset
    fy = floor_flow[..., 1].astype(jnp.int32)  # v -> y offset

    # Flat pixel grid: x = column index, y = row index.
    ys, xs = jnp.meshgrid(jnp.arange(h, dtype=jnp.int32),
                          jnp.arange(w, dtype=jnp.int32), indexing="ij")
    pos_x = xs.reshape(-1)[None, :]  # (1, H*W)
    pos_y = ys.reshape(-1)[None, :]

    x0 = jnp.clip(pos_x + fx, 0, w - 1)
    y0 = jnp.clip(pos_y + fy, 0, h - 1)
    # Left/top saturation: the reference's independently clipped +1
    # neighbor collapses onto x0/y0 there; the patch channels instead hold
    # column/row 1 — zeroing the fractional weight on the saturated side
    # restores exact value and (zero) flow-gradient. Right/bottom
    # saturation needs nothing: min(x0+1, w-1) == clip(x+fx+1) there.
    wx = jnp.where(pos_x + fx < 0, 0.0, frac[..., 0])[..., None]
    wy = jnp.where(pos_y + fy < 0, 0.0, frac[..., 1])[..., None]

    # 2x2 neighborhood packed into channels by edge-clamped shifts, then
    # ONE gather of (B, H*W) indices over 4C-wide rows (see module note).
    img_x = jnp.concatenate([image[:, :, 1:], image[:, :, -1:]], axis=2)
    img_y = jnp.concatenate([image[:, 1:], image[:, -1:]], axis=1)
    img_xy = jnp.concatenate([img_x[:, 1:], img_x[:, -1:]], axis=1)
    patch = jnp.concatenate([image, img_x, img_y, img_xy], axis=-1)
    g = jnp.take_along_axis(patch.reshape(b, h * w, 4 * c),
                            (y0 * w + x0)[..., None], axis=1)
    ia, ic, ib, id_ = (g[..., :c], g[..., c:2 * c],
                       g[..., 2 * c:3 * c], g[..., 3 * c:])

    out = (ia * (1 - wx) * (1 - wy) + ib * (1 - wx) * wy
           + ic * wx * (1 - wy) + id_ * wx * wy)
    return out.reshape(b, h, w, c)


def backward_warp_volume(volume: jnp.ndarray, flows: jnp.ndarray,
                         impl: str = "xla") -> jnp.ndarray:
    """Multi-frame warp (reference `sintelWrapFlow.py:539-577` semantics).

    volume: (B, H, W, 3*T) channel-stacked frames; flows: (B, H, W, 2*(T-1)).
    Reconstructs frame t from frame t+1 using flow pair t, for t in [0, T-1):
    returns (B, H, W, 3*(T-1)) — channel c is gathered from volume channel
    c+3 using flow channels (2*(c//3), 2*(c//3)+1).
    """
    from ..parallel.spatial import (current_mesh, pair_axes,
                                    pair_axis_constraint)

    b, h, w, c3t = volume.shape
    t = c3t // 3
    frames = volume.reshape(b, h, w, t, 3)
    pairs = flows.reshape(b, h, w, t - 1, 2)
    # Fold the pair axis into batch: warp all (T-1) next-frames at once, and
    # shard the folded axis over ("data", "time") so the independent pair
    # warps run pair-parallel across the mesh (SURVEY.md §5.7a).
    nxt = pair_axis_constraint(
        jnp.moveaxis(frames[..., 1:, :], 3, 1).reshape(b * (t - 1), h, w, 3))
    flw = pair_axis_constraint(
        jnp.moveaxis(pairs, 3, 1).reshape(b * (t - 1), h, w, 2))
    rec = backward_warp(
        nxt, flw, impl=impl,
        batch_axes=pair_axes(current_mesh(), b * (t - 1)),
    ).reshape(b, t - 1, h, w, 3)
    return jnp.moveaxis(rec, 1, 3).reshape(b, h, w, 3 * (t - 1))
