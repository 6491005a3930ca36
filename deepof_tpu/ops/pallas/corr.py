"""Fused Pallas correlation (cost-volume) kernels for FlowNet-C: forward
and backward.

Semantics identical to `ops.corr.correlation` (FlowNet paper §3,
arXiv:1504.06852): for a (2K+1)x(2K+1) displacement grid with stride s,

    corr[b, y, x, i*n+j] = mean_c f1[b,y,x,c] * f2[b, y+dy_i, x+dx_j, c]

with zero contribution outside f2's bounds.

Forward (`corr_fwd`, TPU-first):
  - grid = (B, H/TILE_H). Per step, the f1 row-tile lives in VMEM via
    BlockSpec; the zero-padded f2 stays in HBM/ANY and ONE haloed row
    window (TILE_H + 2*pad rows) is DMA'd into VMEM scratch.
  - the (2K+1)^2 displacement sweep then runs entirely from VMEM: each
    displacement is a static-size dynamic slice of the window, an
    elementwise product with the f1 tile, and a channel reduction on the
    VPU. The XLA formulation pays an HBM round-trip per displacement
    ((2K+1)^2 = 441 reads of f2); here f2 is read from HBM exactly once.
  - output layout is (B, n*n, H, W): the displacement index is the
    *leading* (untiled) axis of the block so the per-displacement store is
    a plain row write, not a lane-dimension scatter. The public wrapper
    transposes to the model's (B, H, W, n*n) layout.

Backward (`corr_bwd`, the custom VJP's one implementation on every
backend; residuals f1, f2). In padded coordinates (f2p = f2 with `pad =
K*s` zeros on every side), offsets (oy_i, ox_j) = (s*i, s*j), i, j in
0..n-1, and g the cotangent of corr:

    df1[b,y,x,:]                  = (1/C) sum_ij g[b,y,x,i*n+j] f2p[b, y+oy_i, x+ox_j, :]
    df2p[b, y+oy_i, x+ox_j, :]   += (1/C) g[b,y,x,i*n+j] f1[b,y,x,:]
    df2                           = df2p[:, pad:pad+H, pad:pad+W]

  - grid = (B, H/TILE_H), the tile axis "arbitrary": the image's padded
    f2 is one block (read from HBM once an image) and its padded float32
    df2p accumulator a VMEM scratch that stays resident across the row
    tiles and is written out once, at the image's last tile (scatter
    form). f1, the cotangent and df1 move by row tile.
  - for one row y and one row offset i, the n column offsets are a band:
    mt[X, x] = g[y, x, i*n+j] where X = x + s*j, else 0 ((Wp, W), built on
    the VPU by n selects against X - x). Both sums over j are then
    products on the MXU, with no sublane shift of f1 or f2 (Mosaic takes
    only offsets it can prove tile-aligned there):
        df2p[y+oy_i] += mt . f1[y]        df1[y] += mt^T . f2p[y+oy_i]
    The band holds the cotangent's own values, so with bfloat16 operands
    the products are exact in float32 and the sums float32; the outputs
    are cast to the input dtype, as the forward's.
  - the cotangent enters as (B, H, n_dy, n_dx, W): the row offset a
    leading (loop) index, the column offset a sublane, x on the lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...parallel.spatial import current_mesh, shard_over_batch


def _corr_kernel(f1_ref, f2p_ref, out_ref, win_ref, sem, *,
                 n: int, stride: int, tile_h: int, w: int, c: int):
    b = pl.program_id(0)
    t = pl.program_id(1)

    # One haloed window of padded f2: rows [t*TILE_H, t*TILE_H + TILE_H+2p).
    dma = pltpu.make_async_copy(
        f2p_ref.at[b, pl.ds(t * tile_h, win_ref.shape[0])], win_ref, sem)
    dma.start()
    dma.wait()

    f1 = f1_ref[0].astype(jnp.float32)  # (TILE_H, W, C)
    inv_c = 1.0 / c

    # dy indexes the window's LEADING (untiled) axis, so it may be a loop
    # variable; dx lands on the sublane axis of the (W, C) tiles, where
    # Mosaic only takes offsets it can prove tile-aligned — a traced
    # `pl.ds(dx, w)` is refused on the chip ("cannot statically prove
    # that index in dimension 1 is a multiple of 8"). So the dx sweep is
    # unrolled in Python with static slices.
    def body(i, _):
        for j in range(n):
            sl = win_ref[pl.ds(i * stride, tile_h),
                         j * stride:j * stride + w, :].astype(jnp.float32)
            out_ref[0, i * n + j] = jnp.sum(f1 * sl, axis=-1) * inv_c
        return 0

    lax.fori_loop(0, n, body, 0)


def _pallas_corr_fwd(f1: jnp.ndarray, f2: jnp.ndarray, max_disp: int,
                     stride: int, tile_h: int, interpret: bool) -> jnp.ndarray:
    b, h, w, c = f1.shape
    k = max_disp // stride
    n = 2 * k + 1
    pad = k * stride

    h_pad = (-h) % tile_h
    if h_pad:
        f1 = jnp.pad(f1, ((0, 0), (0, h_pad), (0, 0), (0, 0)))
        f2 = jnp.pad(f2, ((0, 0), (0, h_pad), (0, 0), (0, 0)))
    hp = h + h_pad
    f2p = jnp.pad(f2, ((0, 0), (pad, pad), (pad, pad), (0, 0)))

    grid = (b, hp // tile_h)
    kernel = functools.partial(_corr_kernel, n=n, stride=stride,
                               tile_h=tile_h, w=w, c=c)
    out = pl.pallas_call(
        kernel, name="corr_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_h, w, c), lambda bi, ti: (bi, ti, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),  # padded f2, windowed DMA
        ],
        out_specs=pl.BlockSpec((1, n * n, tile_h, w),
                               lambda bi, ti: (bi, 0, ti, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, n * n, hp, w), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((tile_h + 2 * pad, w + 2 * pad, c), f2.dtype),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(f1, f2p)
    # accumulate in f32, return the input dtype (matches the XLA sweep, so
    # the cost volume's dtype is not backend-dependent under bf16 compute)
    return jnp.moveaxis(out[:, :, :h], 1, -1).astype(f1.dtype)


# The image's blocks (padded f2 and df2, double-buffered) and its float32
# accumulator stay in VMEM beside the row tiles: 27 MB at 48 x 64 x 256,
# over Mosaic's default scoped limit (a v5e core holds 128 MiB).
_BWD_VMEM_BYTES = 96 * 2**20


def _corr_bwd_kernel(f1_ref, f2p_ref, g_ref, df1_ref, df2_ref, acc_ref, *,
                     n: int, stride: int, tile_h: int, h: int, w: int,
                     pad: int, c: int):
    t = pl.program_id(1)
    inv_c = 1.0 / c
    wp = acc_ref.shape[1]
    dt = f1_ref.dtype
    prec = lax.Precision.HIGHEST if dt == jnp.float32 else None

    @pl.when(t == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # band[X, x] = X - x: the column offset of padded f2's X from x.
    band = (lax.broadcasted_iota(jnp.int32, (wp, w), 0)
            - lax.broadcasted_iota(jnp.int32, (wp, w), 1))

    def row(r, _):
        f1r = f1_ref[0, r]  # (W, C)

        def disp(i, df1r):
            # mt[X, x] = g[y, x, i*n + j] where X = x + j*stride: the one
            # dy row of the sweep as a (Wp, W) band, so both sums over dx
            # are products on the MXU (no sublane shift of f1 or f2).
            gt = g_ref[0, r, i].astype(jnp.float32)  # (n, W): dx, x
            mt = jnp.zeros((wp, w), jnp.float32)
            for j in range(n):
                mt = jnp.where(band == j * stride, gt[j:j + 1, :], mt)
            mt = mt.astype(dt)
            yy = t * tile_h + r + i * stride  # padded row of f2 / df2
            acc_ref[yy] += lax.dot_general(
                mt, f1r, (((1,), (0,)), ((), ())), precision=prec,
                preferred_element_type=jnp.float32)
            return df1r + lax.dot_general(
                mt, f2p_ref[0, yy], (((0,), (0,)), ((), ())), precision=prec,
                preferred_element_type=jnp.float32)

        df1r = lax.fori_loop(0, n, disp, jnp.zeros((w, c), jnp.float32))
        df1_ref[0, r] = (df1r * inv_c).astype(df1_ref.dtype)
        return 0

    lax.fori_loop(0, tile_h, row, 0)

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        def out_row(y, _):
            df2_ref[0, y] = (acc_ref[pad + y, pad:pad + w, :]
                             * inv_c).astype(df2_ref.dtype)
            return 0

        lax.fori_loop(0, h, out_row, 0)


def _pallas_corr_bwd(f1, f2, g, max_disp: int, stride: int, tile_h: int,
                     interpret: bool):
    """(df1, df2) of the correlation for its cotangent g (B, H, W, n*n)."""
    b, h, w, c = f1.shape
    k = max_disp // stride
    n = 2 * k + 1
    pad = k * stride
    h_pad = (-h) % tile_h
    hp = h + h_pad
    wp = -(-(w + 2 * pad) // 16) * 16  # bf16's sublane tile
    rows = ((0, 0), (0, h_pad), (0, 0), (0, 0))
    f1 = jnp.pad(f1, rows)
    f2p = jnp.pad(f2, ((0, 0), (pad, pad + h_pad), (pad, wp - w - pad),
                       (0, 0)))
    # (B, H, W, n*n) -> (B, H, n_dy, n_dx, W): dy a leading (loop) index,
    # dx a sublane, x on the lanes.
    gt = jnp.pad(jnp.moveaxis(g.reshape(b, h, w, n, n), 2, -1),
                 ((0, 0), (0, h_pad), (0, 0), (0, 0), (0, 0)))

    kernel = functools.partial(_corr_bwd_kernel, n=n, stride=stride,
                               tile_h=tile_h, h=h, w=w, pad=pad, c=c)
    tile = pl.BlockSpec((1, tile_h, w, c), lambda bi, ti: (bi, ti, 0, 0))
    image = lambda shape: pl.BlockSpec(shape, lambda bi, ti: (bi, 0, 0, 0))
    df1, df2 = pl.pallas_call(
        kernel, name="corr_bwd",
        grid=(b, hp // tile_h),
        in_specs=[
            tile,
            image((1, hp + 2 * pad, wp, c)),
            pl.BlockSpec((1, tile_h, n, n, w),
                         lambda bi, ti: (bi, ti, 0, 0, 0)),
        ],
        out_specs=[tile, image((1, h, w, c))],
        out_shape=[jax.ShapeDtypeStruct((b, hp, w, c), f1.dtype),
                   jax.ShapeDtypeStruct((b, h, w, c), f2.dtype)],
        scratch_shapes=[pltpu.VMEM((hp + 2 * pad, wp, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_BYTES),
        interpret=interpret,
    )(f1, f2p, gt)
    return df1[:, :h], df2


def _launch(f1, f2, max_disp, stride, tile_h, interpret, mesh):
    return shard_over_batch(
        lambda a, b: _pallas_corr_fwd(a, b, max_disp, stride, tile_h,
                                      interpret),
        mesh, f1.shape[0])(f1, f2)


def correlation_pallas(f1, f2, max_disp: int = 20, stride: int = 2,
                       tile_h: int = 8, interpret: bool | None = None):
    """Pallas cost volume: (B,H,W,C) x2 -> (B,H,W,(2K+1)^2), K=max_disp//stride.

    interpret=None auto-selects interpreter mode off-TPU (CPU test
    meshes), exactly as `backward_warp_pallas` does. Under a
    `mesh_context` the kernel runs per batch shard
    (`parallel.spatial.shard_over_batch`); the mesh is resolved HERE and
    carried as a static argument because the VJP's backward rule is
    traced after the context has exited.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _correlation(f1, f2, max_disp, stride, tile_h, interpret,
                        current_mesh())


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _correlation(f1, f2, max_disp, stride, tile_h, interpret, mesh):
    return _launch(f1, f2, max_disp, stride, tile_h, interpret, mesh)


def _fwd(f1, f2, max_disp, stride, tile_h, interpret, mesh):
    return (_launch(f1, f2, max_disp, stride, tile_h, interpret, mesh),
            (f1, f2))


def _bwd(max_disp, stride, tile_h, interpret, mesh, res, g):
    f1, f2 = res
    return shard_over_batch(
        lambda a, b, ct: _pallas_corr_bwd(a, b, ct, max_disp, stride, tile_h,
                                          interpret),
        mesh, f1.shape[0])(f1, f2, g)


_correlation.defvjp(_fwd, _bwd)
