"""Fused Pallas bilinear backward-warp kernels (levels of W <= 256).

Replaces the reference's O(batch * channels) python-loop gather graph
(`flyingChairsWrapFlow.py:799-838`) with a single-VMEM-pass TPU kernel.

Why a *row sweep* instead of a plain gather: Mosaic's dynamic-gather
primitive on TPU only lowers for gathers along the lane dimension within
a single 128-lane register (measured on v5e: a `take_along_axis(axis=-1)`
lowers iff the last dim is exactly 128; wider rows, sublane-dim gathers,
and flattened-image gathers all fail to compile). An arbitrary 2D gather
therefore cannot be written in Pallas on this hardware. What can: for
each ROW offset dy a pixel's two vertical neighbours may take, a sublane
`roll` of the image by dy, a per-lane gather of the two horizontal
neighbours, and a select of the pixels whose neighbour row is i + dy.

Which offsets: the reference's clip-at-border indexing
(`flyingChairsWrapFlow.py:815-818`) bounds them by H-1 whatever the flow
holds, so a sweep of all 2H-1 is exact with no displacement cap — and is
what the kernels ran until PR 29, 159 iterations at 80x112 for a flow
that holds 4. The sweep now runs from the smallest to the largest offset
PRESENT in the image's flow (`_sweep_bounds`, a vector-to-scalar min/max
per grid step): the skipped iterations are exactly those whose masks are
all zero, so the result is the full sweep's to the bit, and the cost is
linear in what the data asks for. Everything runs from VMEM: image and
flow are read from HBM exactly once per batch element.

Width: a plane is one or two 128-lane tiles, (Hp, 128*T). Source column
x lives in tile x >> 7 at lane x & 127: every source tile is gathered by
lane and the result selected by tile (`_lane_gather`), per output tile.
T = 2 is the finest level of a 320x448 input (160x224); wider images stay
on the XLA gather (`ops.warp.backward_warp`), as does a two-tile launch
whose flow spans more rows than the gather costs (`sweep_limit`).

Layout: channel-planar (B, C, Hp, 128*T) so each plane is a well-tiled
f32 VMEM operand (8x128 tiles); the public wrapper pads W -> 128*T and
H -> multiple of 8 and transposes from/to NHWC. Padded lanes/rows gather
only clipped (valid) addresses and are sliced off.

Backward: the FLOW cotangent — the only one the training loss ever uses
(the warped operand is the target image, i.e. data: its cotangent is
dead code under the loss) — is a second row-sweep kernel with the same
single-VMEM-pass structure and no scatter: gu/gv are elementwise in the
output position once the four bilinear neighbors are gathered, the same
a.e.-derivative XLA autodiff produces (through the blend weights, zero
through floor and clipped indices). The IMAGE cotangent (a bilinear
scatter) is delegated to XLA autodiff of the jnp formulation and is
dead-code-eliminated whenever the image is not differentiated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas import tpu as pltpu

from . import pallas_call, pl
from ...parallel.spatial import current_mesh, shard_over_batch

LANES = 128
MAX_TILES = 2  # lane tiles a plane may hold: W <= 256


def _lane_tiles(w: int) -> int:
    return -(-w // LANES)


def _bilinear_setup(flow_ref, h: int, w: int, hp: int, t: int = 0):
    """Shared index/weight setup for the forward and flow-grad kernels, for
    the output pixels of lane tile `t` — the two kernels MUST agree exactly
    (clip bounds, +1 neighbor offset) for the gradient to match the
    primal. Returns (wx, wy, x0, x1, d0, d1), each (Hp, 128)."""
    lanes = slice(t * LANES, (t + 1) * LANES)
    u = flow_ref[0, 0, :, lanes]
    v = flow_ref[0, 1, :, lanes]
    fu = jnp.floor(u)
    fv = jnp.floor(v)
    wx = u - fu
    wy = v - fv
    i = lax.broadcasted_iota(jnp.int32, (hp, LANES), 0)
    j = lax.broadcasted_iota(jnp.int32, (hp, LANES), 1) + t * LANES
    x0 = jnp.clip(j + fu.astype(jnp.int32), 0, w - 1)
    x1 = jnp.clip(j + fu.astype(jnp.int32) + 1, 0, w - 1)
    y0 = jnp.clip(i + fv.astype(jnp.int32), 0, h - 1)
    y1 = jnp.clip(i + fv.astype(jnp.int32) + 1, 0, h - 1)
    # d0/d1 in [-(h-1), h-1] by construction (clip shrinks offsets)
    return wx, wy, x0, x1, y0 - i, y1 - i


def _sweep_bounds(setups, h: int, w: int, hp: int):
    """The row offsets this image's flow holds, as the loop's (start,
    stop): every offset outside has all-zero masks in every tile, so the
    bounded sweep is the full one's sum to the bit. Taken from the
    kernel's OWN d0/d1 (d1 >= d0 everywhere), so they are legal, and the
    result the full sweep's, whatever the flow holds. Padded rows and
    lanes are sliced off by the wrapper and do not widen them."""
    i = lax.broadcasted_iota(jnp.int32, (hp, LANES), 0)
    j = lax.broadcasted_iota(jnp.int32, (hp, LANES), 1)
    los, his = [], []
    for t, (_, _, _, _, d0, d1) in enumerate(setups):
        real = (i < h) & (j + t * LANES < w)
        los.append(jnp.min(jnp.where(real, d0, h)))
        his.append(jnp.max(jnp.where(real, d1, -h)))
    return (functools.reduce(jnp.minimum, los),
            functools.reduce(jnp.maximum, his) + 1)


def _lane_gather(plane, x, tiles: int):
    """plane[i, x[i, j]] for a (Hp, 128*tiles) plane and (Hp, 128) columns.
    Mosaic's gather reaches one 128-lane register: column x lives in
    source tile x >> 7 at lane x & 127, so gather every source tile by
    lane and select by tile."""
    if tiles == 1:
        return jnp.take_along_axis(plane, x, axis=1)
    lane = x & (LANES - 1)
    src = x >> 7
    g = jnp.take_along_axis(plane[:, :LANES], lane, axis=1)
    for s in range(1, tiles):
        gs = jnp.take_along_axis(plane[:, s * LANES:(s + 1) * LANES], lane,
                                 axis=1)
        g = jnp.where(src == s, gs, g)
    return g


def _to_planar(x, h: int, w: int, hp: int):
    """NHWC -> channel-planar (B, C, Hp, 128*T), zero-padded to the
    kernels' block shape."""
    xp = jnp.pad(x.astype(jnp.float32),
                 ((0, 0), (0, hp - h), (0, _lane_tiles(w) * LANES - w),
                  (0, 0)))
    return jnp.transpose(xp, (0, 3, 1, 2))


def _warp_kernel(img_ref, flow_ref, out_ref, *, h: int, w: int, c: int,
                 hp: int, tiles: int):
    """One batch element: img (1,C,Hp,128T), flow (1,2,Hp,128T) -> out."""
    setups = [_bilinear_setup(flow_ref, h, w, hp, t) for t in range(tiles)]
    start, stop = _sweep_bounds(setups, h, w, hp)

    def body(dy, accs):
        shift = (hp - dy) % hp  # roll so row i holds img[(i + dy) % hp]
        planes = [pltpu.roll(img_ref[0, ch], shift, 0) for ch in range(c)]
        out = []
        for t, (wx, wy, x0, x1, d0, d1) in enumerate(setups):
            m0 = (d0 == dy).astype(jnp.float32)
            m1 = (d1 == dy).astype(jnp.float32)
            wsel = (1.0 - wy) * m0 + wy * m1
            for ch in range(c):
                g0 = _lane_gather(planes[ch], x0, tiles)
                g1 = _lane_gather(planes[ch], x1, tiles)
                out.append(accs[t * c + ch]
                           + wsel * ((1.0 - wx) * g0 + wx * g1))
        return tuple(out)

    accs = lax.fori_loop(
        start, stop, body,
        tuple(jnp.zeros((hp, LANES), jnp.float32) for _ in range(tiles * c)))
    for t in range(tiles):
        for ch in range(c):
            out_ref[0, ch, :, t * LANES:(t + 1) * LANES] = accs[t * c + ch]


def _warp_flow_grad_kernel(img_ref, flow_ref, ct_ref, out_ref, *, h: int,
                           w: int, c: int, hp: int, tiles: int):
    """One batch element: img (1,C,Hp,128T), flow (1,2,Hp,128T), cotangent
    (1,C,Hp,128T) -> (1,2,Hp,128T) = (dL/du, dL/dv).

    Same data-bounded row sweep as the forward. With the bilinear blend
    recon = (1-wy)[(1-wx)Ia + wx Ib] + wy[(1-wx)Ic + wx Id]:
      d/du = (1-wy)(Ib-Ia) + wy(Id-Ic)
      d/dv = (1-wx)(Ic-Ia) + wx(Id-Ib)
    where Ia/Ib live on the y0 row (mask m0) and Ic/Id on y1 (m1), so per
    row-offset dy both terms reduce to masked combinations of the two
    lane gathers g0=img[.,x0], g1=img[.,x1] — no scatter anywhere.
    """
    setups = [_bilinear_setup(flow_ref, h, w, hp, t) for t in range(tiles)]
    start, stop = _sweep_bounds(setups, h, w, hp)

    def body(dy, accs):
        shift = (hp - dy) % hp
        planes = [pltpu.roll(img_ref[0, ch], shift, 0) for ch in range(c)]
        out = []
        for t, (wx, wy, x0, x1, d0, d1) in enumerate(setups):
            au, av = accs[2 * t], accs[2 * t + 1]
            m0 = (d0 == dy).astype(jnp.float32)
            m1 = (d1 == dy).astype(jnp.float32)
            wu = (1.0 - wy) * m0 + wy * m1
            wv = m1 - m0
            for ch in range(c):
                g0 = _lane_gather(planes[ch], x0, tiles)
                g1 = _lane_gather(planes[ch], x1, tiles)
                gc = ct_ref[0, ch, :, t * LANES:(t + 1) * LANES]
                au = au + gc * wu * (g1 - g0)
                av = av + gc * wv * ((1.0 - wx) * g0 + wx * g1)
            out += [au, av]
        return tuple(out)

    accs = lax.fori_loop(
        start, stop, body,
        tuple(jnp.zeros((hp, LANES), jnp.float32) for _ in range(2 * tiles)))
    for t in range(tiles):
        out_ref[0, 0, :, t * LANES:(t + 1) * LANES] = accs[2 * t]
        out_ref[0, 1, :, t * LANES:(t + 1) * LANES] = accs[2 * t + 1]


def _plane_spec(channels: int, hp: int, tiles: int) -> pl.BlockSpec:
    return pl.BlockSpec((1, channels, hp, tiles * LANES),
                        lambda bi: (bi, 0, 0, 0), memory_space=pltpu.VMEM)


def _pallas_warp_flow_grad(image: jnp.ndarray, flow: jnp.ndarray,
                           ct: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    b, h, w, c = image.shape
    hp = -(-h // 8) * 8
    tiles = _lane_tiles(w)
    kernel = functools.partial(_warp_flow_grad_kernel, h=h, w=w, c=c, hp=hp,
                               tiles=tiles)
    out = pallas_call(
        kernel, name="warp_flow_grad",
        grid=(b,),
        in_specs=[_plane_spec(c, hp, tiles), _plane_spec(2, hp, tiles),
                  _plane_spec(c, hp, tiles)],
        out_specs=_plane_spec(2, hp, tiles),
        out_shape=jax.ShapeDtypeStruct((b, 2, hp, tiles * LANES),
                                       jnp.float32),
        interpret=interpret,
    )(_to_planar(image, h, w, hp), _to_planar(flow, h, w, hp),
      _to_planar(ct, h, w, hp))
    return jnp.transpose(out, (0, 2, 3, 1))[:, :h, :w]


def _pallas_warp_fwd(image: jnp.ndarray, flow: jnp.ndarray,
                     interpret: bool) -> jnp.ndarray:
    b, h, w, c = image.shape
    tiles = _lane_tiles(w)
    if tiles > MAX_TILES:
        raise ValueError(
            f"pallas warp requires W <= {MAX_TILES * LANES} (got {w}); use "
            "the XLA path for wider images")
    hp = -(-h // 8) * 8
    kernel = functools.partial(_warp_kernel, h=h, w=w, c=c, hp=hp,
                               tiles=tiles)
    out = pallas_call(
        kernel, name="warp_fwd",
        grid=(b,),
        in_specs=[_plane_spec(c, hp, tiles), _plane_spec(2, hp, tiles)],
        out_specs=_plane_spec(c, hp, tiles),
        out_shape=jax.ShapeDtypeStruct((b, c, hp, tiles * LANES),
                                       jnp.float32),
        interpret=interpret,
    )(_to_planar(image, h, w, hp), _to_planar(flow, h, w, hp))
    return jnp.transpose(out, (0, 2, 3, 1))[:, :h, :w].astype(image.dtype)


def backward_warp_pallas(image: jnp.ndarray, flow: jnp.ndarray,
                         interpret: bool | None = None,
                         batch_axes: tuple[str, ...] = ("data",),
                         sweep_limit: int | None = None) -> jnp.ndarray:
    """Pallas warp: image (B,H,W,C), *scaled* flow (B,H,W,2) -> (B,H,W,C).

    Exact `ops.warp.backward_warp` semantics for W <= 256 (any flow
    magnitude — border clipping bounds the sweep), including gradients
    with respect to both arguments. interpret=None auto-selects
    interpreter mode off-TPU (CPU test meshes). Under a `mesh_context`
    both kernels run per shard of the leading axis over `batch_axes`, the
    mesh axes the caller shards it over
    (`parallel.spatial.shard_over_batch`); the mesh is resolved HERE and
    carried as a static argument because the backward rule is traced
    after the context has exited.

    sweep_limit: `impl="auto"`'s never-worse-than-the-gather rule for two
    lane tiles. The kernels' time is linear in the rows their sweep
    visits; a launch (one shard's batch rows) whose largest per-image
    sweep is longer takes the XLA gather instead, forward and backward,
    by a `lax.cond` INSIDE the shard so that the decision needs no
    collective. None: always the kernels.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _warp(image, flow, interpret, current_mesh(), tuple(batch_axes),
                 sweep_limit)


def _sweep_fits(flow, sweep_limit: int):
    from ..warp import row_sweep_lengths

    return jnp.max(row_sweep_lengths(flow[..., 1])) <= sweep_limit


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _warp(image, flow, interpret, mesh, axes, sweep_limit):
    return _fwd(image, flow, interpret, mesh, axes, sweep_limit)[0]


def _fwd(image, flow, interpret, mesh, axes, sweep_limit):
    from ..warp import _blend_patches, _gather_patches

    def kernel(im, fl):
        return _pallas_warp_fwd(im, fl, interpret)

    if sweep_limit is None:
        out = shard_over_batch(kernel, mesh, image.shape[0], axes)(image, flow)
        return out, (image, flow, None)

    # The gather's branch hands its gathered patches to the backward pass,
    # as the XLA path's own linearisation does (the flow gradient is then
    # elementwise: no second gather); the kernels' branch fills the same
    # buffer with zeros, 0.27 ms at 160x224x64 (v5e, PR 29).
    def launch(im, fl):
        def gather(im, fl):
            g = _gather_patches(im, fl)
            return _blend_patches(g, fl).reshape(im.shape).astype(im.dtype), g

        b, h, w, c = im.shape
        return lax.cond(
            _sweep_fits(fl, sweep_limit),
            lambda im, fl: (kernel(im, fl),
                            jnp.zeros((b, h * w, 4 * c), im.dtype)),
            gather, im, fl)

    out, patches = shard_over_batch(launch, mesh, image.shape[0], axes)(
        image, flow)
    return out, (image, flow, patches)


def _bwd(interpret, mesh, axes, sweep_limit, res, g):
    from ..warp import _blend_patches, backward_warp  # jnp formulation

    image, flow, patches = res
    g32 = g.astype(jnp.float32)

    # flow cotangent: the training hot path (the model's only gradient
    # route through the warp) — fused Pallas sweep, no scatter
    def kernel(im, fl, ct):
        return _pallas_warp_flow_grad(im, fl, ct, interpret)

    if patches is None:
        gf = shard_over_batch(kernel, mesh, image.shape[0], axes)(
            image, flow, g32)
    else:
        def launch(im, fl, ct, pt):
            def blend_vjp():
                ct_flat = ct.reshape(pt.shape[0], -1, ct.shape[-1])
                return jax.vjp(lambda f: _blend_patches(pt, f), fl)[1](
                    ct_flat)[0]

            return lax.cond(_sweep_fits(fl, sweep_limit),
                            lambda: kernel(im, fl, ct), blend_vjp)

        gf = shard_over_batch(launch, mesh, image.shape[0], axes)(
            image, flow, g32, patches)
    # image cotangent: XLA bilinear scatter; under jit it is dead-code-
    # eliminated when the image operand is data (the default loss). Eager
    # op-by-op grads do pay it — debug-only territory
    gi = jax.vjp(lambda im: backward_warp(im, flow, impl="xla"),
                 image)[1](g32)[0]
    return gi.astype(image.dtype), gf.astype(flow.dtype)


_warp.defvjp(_fwd, _bwd)
