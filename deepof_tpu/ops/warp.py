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

TPU note: Mosaic's gather reaches one 128-lane register, so an
arbitrary 2D gather cannot be written in Pallas; what can is a sweep over
the ROW offsets a flow field holds, each a roll plus per-lane gathers
(`ops/pallas/warp.py`). Its cost is linear in the number of offsets
present, a dozen for a trained flow at 160x224 against the XLA gather's
25 ns an index, so `impl="auto"` takes it for every level of up to two
lane tiles (W <= 256) and keeps XLA's single dynamic-gather over the
flattened H*W axis for wider or taller images, and for a two-tile launch
whose flow spans more rows than `PALLAS_AUTO_MAX_SWEEP`.

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

#: impl="auto" routes to the Pallas kernel on a TPU when W <= 256 (the
#: kernel's limit: two 128-lane tiles a plane) and H <= 256 (it holds
#: whole (Hp, 128*T) planes in VMEM: 160x224 is under 2 MB of blocks a grid
#: step; a tall-narrow input such as 4096x64 would not fit). Wider or
#: taller goes to the XLA patch-gather.
PALLAS_AUTO_MAX_W = 256
PALLAS_AUTO_MAX_H = 256
#: The kernels' time is linear in the row offsets the launch's flow holds
#: (its largest per-image sweep); the gather's does not depend on them.
#: Measured on a v5e (`tools/perf_probe.py --only warpsweep`, PR 29; warp
#: forward + flow gradient, batch 64, 160x224): kernels 1.26 / 3.66 / 23.9
#: / 47.8 / 95.3 ms at sweeps of 3 / 11 / 79 / 159 / 319 rows, i.e. 0.35 +
#: 0.30 ms a row; the gather 52.9-61.2 ms by the flow field. They meet at
#: 176 rows (the gather's fastest reading) to 203 (its slowest); 160 keeps
#: the kernels 9% or more under the gather. So `auto` hands a two-tile
#: launch whose sweep is longer than this to the gather, per launch
#: (`lax.cond`; the gather's branch then costs the gather + 2.8%).
#: One lane tile (W <= 128) has no limit: a full-frame flow there costs
#: what the kernels cost before they were bounded (7.4 ms at 80x112x64; the
#: gather reads 3.6 ms), anything a trained flow holds (3-4 rows) 0.34 ms.
PALLAS_AUTO_MAX_SWEEP = 160


def row_sweep_lengths(v: jnp.ndarray) -> jnp.ndarray:
    """Per image, how many row offsets `y - i` the warp's two vertical
    neighbours take under the vertical flow `v` (B, H, W[, K]), as int32
    (B,): what the Pallas kernel's row sweep visits (`ops/pallas/warp.py::
    _sweep_bounds`, the same floor/clip arithmetic). 1 for a zero flow,
    at most 2H-1."""
    h = v.shape[1]
    i = jnp.arange(h, dtype=jnp.int32).reshape((1, h) + (1,) * (v.ndim - 2))
    fy = jnp.floor(v).astype(jnp.int32)
    d0 = jnp.clip(i + fy, 0, h - 1) - i
    d1 = jnp.clip(i + fy + 1, 0, h - 1) - i
    axes = tuple(range(1, v.ndim))
    return jnp.max(d1, axis=axes) - jnp.min(d0, axis=axes) + 1


def _kernel_route(impl: str, h: int, w: int) -> tuple[bool, int | None]:
    """(whether this launch goes to the Pallas kernels, the sweep limit it
    goes under): the one rule `backward_warp` and `warp_sweep_stats` share.
    An explicit "pallas" is honored anywhere and always runs the kernels,
    which is what their correctness tests use. "auto" is the
    measured-fastest choice, and the measurement is a TPU one: off-TPU the
    kernels only exist in interpret mode (python-level emulation, ~10-100x
    slower than the XLA gather — it silently dominated the CPU-mesh test
    suite's runtime before this gate)."""
    if impl == "pallas":
        return True, None
    if (impl == "auto" and w <= PALLAS_AUTO_MAX_W and h <= PALLAS_AUTO_MAX_H
            and jax.default_backend() == "tpu"):
        return True, PALLAS_AUTO_MAX_SWEEP if w > 128 else None
    return False, None


def warp_sweep_stats(flow: jnp.ndarray, impl: str
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """What `backward_warp(_, flow, impl)` does with this launch, as two
    float32 scalars for the step's metrics: the largest per-image row
    sweep of the batch (0.0 where the shape or `impl` sends the level to
    XLA), and 1.0 where `auto`'s two-tile launch exceeded
    `PALLAS_AUTO_MAX_SWEEP` and took the gather (under a mesh each shard
    decides on its own rows; this is "any shard did"). `flow` is the
    scaled flow, (B, H, W, 2K) with the vertical components odd."""
    zero = jnp.zeros((), jnp.float32)
    kernels, limit = _kernel_route(impl, *flow.shape[1:3])
    if not kernels:
        return zero, zero
    rows = jnp.max(row_sweep_lengths(flow[..., 1::2]))
    fallback = zero if limit is None else (rows > limit).astype(jnp.float32)
    return rows.astype(jnp.float32), fallback


def backward_warp(image: jnp.ndarray, flow: jnp.ndarray,
                  impl: str = "xla",
                  batch_axes: tuple[str, ...] = ("data",)) -> jnp.ndarray:
    """Warp `image` (B, H, W, C) backward by `flow` (B, H, W, 2).

    `flow` must already include any flow_scale factor (the caller applies it,
    as the reference does at `flyingChairsWrapFlow.py:785`).

    impl: "xla" (one fused patch-gather, any size; the function default —
    golden tests and the Pallas image-cotangent fallback reference it),
    "pallas" (VMEM row-sweep kernel, requires W <= 256), or "auto" (on a
    TPU the kernel for W, H <= 256, with the gather taking over a two-tile
    launch whose flow spans more than `PALLAS_AUTO_MAX_SWEEP` rows; xla
    otherwise — the measured-fastest choice and the `LossConfig.warp_impl`
    default).

    batch_axes: the mesh axes the leading axis is sharded over — the
    Pallas kernel launches once per shard of them under a `mesh_context`
    (the XLA formulation is partitioned by GSPMD and ignores it).
    """
    b, h, w, c = image.shape
    kernels, sweep_limit = _kernel_route(impl, h, w)
    if kernels:
        from .pallas.warp import backward_warp_pallas

        return backward_warp_pallas(image, flow, batch_axes=batch_axes,
                                    sweep_limit=sweep_limit)
    elif impl not in ("xla", "auto"):
        raise ValueError(f"unknown warp impl {impl!r}")
    return _blend_patches(_gather_patches(image, flow), flow).reshape(
        b, h, w, c)


def _flat_split(flow: jnp.ndarray):
    """Scaled flow (B, H, W, 2) -> integer base offsets, fractional parts
    and the pixel grid, all over the flattened H*W axis."""
    b, h, w, _ = flow.shape
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
    return fx, fy, frac, pos_x, pos_y


def _gather_patches(image: jnp.ndarray, flow: jnp.ndarray) -> jnp.ndarray:
    """The 2x2 neighbourhood at every pixel's clipped base address (y0,
    x0), (B, H*W, 4C): the ONE gather of the XLA path. The flow enters
    through integer indices only, so nothing is differentiated here."""
    b, h, w, c = image.shape
    fx, fy, _, pos_x, pos_y = _flat_split(flow)
    x0 = jnp.clip(pos_x + fx, 0, w - 1)
    y0 = jnp.clip(pos_y + fy, 0, h - 1)
    # 2x2 neighborhood packed into channels by edge-clamped shifts, then
    # ONE gather of (B, H*W) indices over 4C-wide rows (see module note).
    img_x = jnp.concatenate([image[:, :, 1:], image[:, :, -1:]], axis=2)
    img_y = jnp.concatenate([image[:, 1:], image[:, -1:]], axis=1)
    img_xy = jnp.concatenate([img_x[:, 1:], img_x[:, -1:]], axis=1)
    patch = jnp.concatenate([image, img_x, img_y, img_xy], axis=-1)
    return jnp.take_along_axis(patch.reshape(b, h * w, 4 * c),
                               (y0 * w + x0)[..., None], axis=1)


def _blend_patches(g: jnp.ndarray, flow: jnp.ndarray) -> jnp.ndarray:
    """Bilinear blend of gathered patches (B, H*W, 4C) -> (B, H*W, C); the
    flow's whole gradient route (through the fractional weights)."""
    c = g.shape[-1] // 4
    fx, fy, frac, pos_x, pos_y = _flat_split(flow)
    # Left/top saturation: the reference's independently clipped +1
    # neighbor collapses onto x0/y0 there; the patch channels instead hold
    # column/row 1 — zeroing the fractional weight on the saturated side
    # restores exact value and (zero) flow-gradient. Right/bottom
    # saturation needs nothing: min(x0+1, w-1) == clip(x+fx+1) there.
    wx = jnp.where(pos_x + fx < 0, 0.0, frac[..., 0])[..., None]
    wy = jnp.where(pos_y + fy < 0, 0.0, frac[..., 1])[..., None]
    ia, ic, ib, id_ = (g[..., :c], g[..., c:2 * c],
                       g[..., 2 * c:3 * c], g[..., 3 * c:])
    return (ia * (1 - wx) * (1 - wy) + ib * (1 - wx) * wy
            + ic * wx * (1 - wy) + id_ * wx * wy)


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
