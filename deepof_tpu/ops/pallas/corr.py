"""Fused Pallas correlation (cost-volume) kernel for FlowNet-C.

Semantics identical to `ops.corr.correlation` (FlowNet paper §3,
arXiv:1504.06852): for a (2K+1)x(2K+1) displacement grid with stride s,

    corr[b, y, x, i*n+j] = mean_c f1[b,y,x,c] * f2[b, y+dy_i, x+dx_j, c]

with zero contribution outside f2's bounds.

Kernel design (TPU-first):
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
  - backward: `correlation_pallas` carries a custom VJP whose adjoints are
    expressed with the same displacement-sweep structure in XLA (gradients
    flow through both feature maps); the forward hot path is the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...parallel.spatial import current_mesh, shard_over_batch


def _sweep_offsets(n: int, stride: int) -> jnp.ndarray:
    offs = jnp.arange(n) * stride
    return jnp.stack(jnp.meshgrid(offs, offs, indexing="ij"), -1).reshape(-1, 2)


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
    b, h, w, c = f1.shape
    k = max_disp // stride
    pad = k * stride
    inv_c = 1.0 / c
    offsets = _sweep_offsets(2 * k + 1, stride)
    f2p = jnp.pad(f2, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    gm = jnp.moveaxis(g, -1, 0)  # (n*n, B, H, W)

    hp, wp = h + 2 * pad, w + 2 * pad

    # Accumulate over displacements with a scan: a vmap here would
    # materialize all (2K+1)^2 full-size (B,H,W,C) products at once.
    def step(carry, off_gi):
        df1_acc, df2p_acc = carry
        off, gi = off_gi
        sl = lax.dynamic_slice(f2p, (0, off[0], off[1], 0), (b, h, w, c))
        df1_acc = df1_acc + gi[..., None] * sl * inv_c
        # df2p[y+dy, x+dx] += g[..., i] * f1[y, x] / C
        prod = gi[..., None] * f1 * inv_c
        cur = lax.dynamic_slice(df2p_acc, (0, off[0], off[1], 0), (b, h, w, c))
        df2p_acc = lax.dynamic_update_slice(df2p_acc, cur + prod,
                                            (0, off[0], off[1], 0))
        return (df1_acc, df2p_acc), None

    init = (jnp.zeros((b, h, w, c), jnp.float32),
            jnp.zeros((b, hp, wp, c), jnp.float32))
    (df1, df2p), _ = lax.scan(step, init, (offsets, gm))
    df2 = df2p[:, pad : pad + h, pad : pad + w]
    return df1.astype(f1.dtype), df2.astype(f2.dtype)


_correlation.defvjp(_fwd, _bwd)
