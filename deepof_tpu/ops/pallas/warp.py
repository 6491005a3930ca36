"""Fused Pallas bilinear backward-warp kernel (coarse pyramid levels).

Replaces the reference's O(batch * channels) python-loop gather graph
(`flyingChairsWrapFlow.py:799-838`) with a single-VMEM-pass TPU kernel.

Why a *bounded-row-sweep* design instead of a plain gather: Mosaic's
dynamic-gather primitive on TPU only lowers for gathers along the lane
dimension within a single 128-lane register (measured on v5e: a
`take_along_axis(axis=-1)` lowers iff the last dim is exactly 128; wider
rows, sublane-dim gathers, and flattened-image gathers all fail to
compile). An arbitrary-displacement 2D gather therefore cannot be
expressed efficiently in Pallas on this hardware — XLA's native gather
HLO is the right tool for fine levels, and `ops.warp.backward_warp`
(one fused XLA gather) remains the default path.

What *can* be fused exactly: levels whose width fits one lane register
(W <= 128). There the reference's clip-at-border indexing
(`flyingChairsWrapFlow.py:815-818`) bounds the row displacement by H-1
regardless of flow magnitude, so a sweep over the 2H-1 possible row
offsets — each a cheap sublane `roll` + per-lane gather + select — is
*exact* for any flow, needs no semantic displacement cap, and runs
entirely from VMEM: image and flow are read from HBM exactly once per
batch element (the XLA formulation reads the image four times, once per
bilinear neighbor).

Layout: channel-planar (B, C, Hp, 128) so each (Hp, 128) plane is a
well-tiled f32 VMEM operand (8x128 tiles); the public wrapper pads
W -> 128 and H -> multiple of 8 and transposes from/to NHWC. Padded
lanes/rows gather only clipped (valid) addresses and are sliced off.

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...parallel.spatial import current_mesh, shard_over_batch

LANES = 128


def _bilinear_setup(flow_ref, h: int, w: int, hp: int):
    """Shared index/weight setup for the forward and flow-grad kernels —
    they MUST agree exactly (clip bounds, +1 neighbor offset) for the
    gradient to match the primal. Returns (wx, wy, x0, x1, d0, d1)."""
    u = flow_ref[0, 0]
    v = flow_ref[0, 1]
    fu = jnp.floor(u)
    fv = jnp.floor(v)
    wx = u - fu
    wy = v - fv
    i = lax.broadcasted_iota(jnp.int32, (hp, LANES), 0)
    j = lax.broadcasted_iota(jnp.int32, (hp, LANES), 1)
    x0 = jnp.clip(j + fu.astype(jnp.int32), 0, w - 1)
    x1 = jnp.clip(j + fu.astype(jnp.int32) + 1, 0, w - 1)
    y0 = jnp.clip(i + fv.astype(jnp.int32), 0, h - 1)
    y1 = jnp.clip(i + fv.astype(jnp.int32) + 1, 0, h - 1)
    # d0/d1 in [-(h-1), h-1] by construction (clip shrinks offsets)
    return wx, wy, x0, x1, y0 - i, y1 - i


def _to_planar(x, h: int, w: int, hp: int):
    """NHWC -> channel-planar (B, C, Hp, 128), zero-padded to the kernels'
    block shape."""
    xp = jnp.pad(x.astype(jnp.float32),
                 ((0, 0), (0, hp - h), (0, LANES - w), (0, 0)))
    return jnp.transpose(xp, (0, 3, 1, 2))


def _warp_kernel(img_ref, flow_ref, out_ref, *, h: int, w: int, c: int,
                 hp: int):
    """One batch element: img (1,C,Hp,128), flow (1,2,Hp,128) -> out."""
    wx, wy, x0, x1, d0, d1 = _bilinear_setup(flow_ref, h, w, hp)

    def body(k, accs):
        dy = k - (h - 1)
        shift = (hp - dy) % hp  # roll so row i holds img[(i + dy) % hp]
        m0 = (d0 == dy).astype(jnp.float32)
        m1 = (d1 == dy).astype(jnp.float32)
        wsel = (1.0 - wy) * m0 + wy * m1
        out = []
        for ch in range(c):
            plane = pltpu.roll(img_ref[0, ch], shift, 0)
            g0 = jnp.take_along_axis(plane, x0, axis=1)
            g1 = jnp.take_along_axis(plane, x1, axis=1)
            out.append(accs[ch] + wsel * ((1.0 - wx) * g0 + wx * g1))
        return tuple(out)

    accs = lax.fori_loop(
        0, 2 * h - 1, body,
        tuple(jnp.zeros((hp, LANES), jnp.float32) for _ in range(c)))
    for ch in range(c):
        out_ref[0, ch] = accs[ch]


def _warp_flow_grad_kernel(img_ref, flow_ref, ct_ref, out_ref, *, h: int,
                           w: int, c: int, hp: int):
    """One batch element: img (1,C,Hp,128), flow (1,2,Hp,128), cotangent
    (1,C,Hp,128) -> (1,2,Hp,128) = (dL/du, dL/dv).

    Same bounded row sweep as the forward. With the bilinear blend
    recon = (1-wy)[(1-wx)Ia + wx Ib] + wy[(1-wx)Ic + wx Id]:
      d/du = (1-wy)(Ib-Ia) + wy(Id-Ic)
      d/dv = (1-wx)(Ic-Ia) + wx(Id-Ib)
    where Ia/Ib live on the y0 row (mask m0) and Ic/Id on y1 (m1), so per
    row-offset dy both terms reduce to masked combinations of the two
    lane gathers g0=img[.,x0], g1=img[.,x1] — no scatter anywhere.
    """
    wx, wy, x0, x1, d0, d1 = _bilinear_setup(flow_ref, h, w, hp)

    def body(k, accs):
        au, av = accs
        dy = k - (h - 1)
        shift = (hp - dy) % hp
        m0 = (d0 == dy).astype(jnp.float32)
        m1 = (d1 == dy).astype(jnp.float32)
        wu = (1.0 - wy) * m0 + wy * m1
        wv = m1 - m0
        for ch in range(c):
            plane = pltpu.roll(img_ref[0, ch], shift, 0)
            g0 = jnp.take_along_axis(plane, x0, axis=1)
            g1 = jnp.take_along_axis(plane, x1, axis=1)
            gc = ct_ref[0, ch]
            au = au + gc * wu * (g1 - g0)
            av = av + gc * wv * ((1.0 - wx) * g0 + wx * g1)
        return au, av

    zero = jnp.zeros((hp, LANES), jnp.float32)
    au, av = lax.fori_loop(0, 2 * h - 1, body, (zero, zero))
    out_ref[0, 0] = au
    out_ref[0, 1] = av


def _pallas_warp_flow_grad(image: jnp.ndarray, flow: jnp.ndarray,
                           ct: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    b, h, w, c = image.shape
    hp = -(-h // 8) * 8
    kernel = functools.partial(_warp_flow_grad_kernel, h=h, w=w, c=c, hp=hp)
    out = pl.pallas_call(  # name=: the HLO instruction's, so a trace event's
        kernel, name="warp_flow_grad",
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, c, hp, LANES), lambda bi: (bi, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 2, hp, LANES), lambda bi: (bi, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c, hp, LANES), lambda bi: (bi, 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 2, hp, LANES), lambda bi: (bi, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, 2, hp, LANES), jnp.float32),
        interpret=interpret,
    )(_to_planar(image, h, w, hp), _to_planar(flow, h, w, hp),
      _to_planar(ct, h, w, hp))
    return jnp.transpose(out, (0, 2, 3, 1))[:, :h, :w]


def _pallas_warp_fwd(image: jnp.ndarray, flow: jnp.ndarray,
                     interpret: bool) -> jnp.ndarray:
    b, h, w, c = image.shape
    if w > LANES:
        raise ValueError(
            f"pallas warp requires W <= {LANES} (got {w}); use the XLA path "
            "for fine pyramid levels")
    hp = -(-h // 8) * 8
    imgp = _to_planar(image, h, w, hp)   # (B, C, Hp, 128)
    flowp = _to_planar(flow, h, w, hp)   # (B, 2, Hp, 128)

    kernel = functools.partial(_warp_kernel, h=h, w=w, c=c, hp=hp)
    out = pl.pallas_call(
        kernel, name="warp_fwd",
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, c, hp, LANES), lambda bi: (bi, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 2, hp, LANES), lambda bi: (bi, 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, c, hp, LANES), lambda bi: (bi, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, c, hp, LANES), jnp.float32),
        interpret=interpret,
    )(imgp, flowp)
    return jnp.transpose(out, (0, 2, 3, 1))[:, :h, :w].astype(image.dtype)


def _fwd_launch(image, flow, interpret, mesh, axes):
    return shard_over_batch(
        lambda im, fl: _pallas_warp_fwd(im, fl, interpret),
        mesh, image.shape[0], axes)(image, flow)


def backward_warp_pallas(image: jnp.ndarray, flow: jnp.ndarray,
                         interpret: bool | None = None,
                         batch_axes: tuple[str, ...] = ("data",)
                         ) -> jnp.ndarray:
    """Pallas warp: image (B,H,W,C), *scaled* flow (B,H,W,2) -> (B,H,W,C).

    Exact `ops.warp.backward_warp` semantics for W <= 128 (any flow
    magnitude — border clipping bounds the sweep), including gradients
    with respect to both arguments. interpret=None auto-selects
    interpreter mode off-TPU (CPU test meshes). Under a `mesh_context`
    both kernels run per shard of the leading axis over `batch_axes`, the
    mesh axes the caller shards it over
    (`parallel.spatial.shard_over_batch`); the mesh is resolved HERE and
    carried as a static argument because the backward rule is traced
    after the context has exited.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _warp(image, flow, interpret, current_mesh(), tuple(batch_axes))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _warp(image, flow, interpret, mesh, axes):
    return _fwd_launch(image, flow, interpret, mesh, axes)


def _fwd(image, flow, interpret, mesh, axes):
    return _fwd_launch(image, flow, interpret, mesh, axes), (image, flow)


def _bwd(interpret, mesh, axes, res, g):
    from ..warp import backward_warp  # jnp formulation; same a.e. gradient

    image, flow = res
    g32 = g.astype(jnp.float32)
    # flow cotangent: the training hot path (the model's only gradient
    # route through the warp) — fused Pallas sweep, no scatter
    gf = shard_over_batch(
        lambda im, fl, ct: _pallas_warp_flow_grad(im, fl, ct, interpret),
        mesh, image.shape[0], axes)(image, flow, g32)
    # image cotangent: XLA bilinear scatter; under jit it is dead-code-
    # eliminated when the image operand is data (the default loss). Eager
    # op-by-op grads do pay it — debug-only territory
    gi = jax.vjp(lambda im: backward_warp(im, flow, impl="xla"),
                 image)[1](g32)[0]
    return gi.astype(image.dtype), gf.astype(flow.dtype)


_warp.defvjp(_fwd, _bwd)
