"""Fused Pallas correlation (cost-volume) kernels for FlowNet-C: forward
and backward.

Semantics identical to `ops.corr.correlation` (FlowNet paper §3,
arXiv:1504.06852): for a (2K+1)x(2K+1) displacement grid with stride s,

    corr[b, y, x, i*n+j] = mean_c f1[b,y,x,c] * f2[b, y+dy_i, x+dx_j, c]

with zero contribution outside f2's bounds.

Forward (`corr_fwd`, TPU-first). In padded coordinates (f2p = f2 with
`pad = K*s` zeros on every side) one image row y meets padded f2 row
y + s*i in the product P = f1[y] . f2p[y+s*i]^T ((W, C) . (C, Wp) on the
MXU, float32 sums), and corr[y, x, i*n+j] = P[x, x + s*j] / C: the n
column offsets are n diagonals of P, `corr_bwd`'s band read instead of
written.
  - x and f2p's columns are split by their phase modulo s (x = s*u + p,
    X = s*V + p): a phase's wanted entries are the unit-stride diagonals
    V = u + j. The kernel lays both out itself, by 0/1 products on the MXU
    (exact): f1's tile as (phase, reversed u) rows, the image's padded f2
    transposed, (C, (row phase, row, phase, V)), one VMEM scratch built at
    the image's first tile; f1 and f2 come from HBM once and as they are.
  - grid = (B, H/TILE_H), TILE_H `_FWD_TILE_H` rows (fewer where H is
    less). A padded f2 row of row phase q meets the tile's rows of phase q
    (row r at row offset i = (Y - r)/s): ONE product (NN, f2 stationary)
    takes those rows stacked against `_FWD_GROUP` consecutive f2 rows of
    the phase, both column phases at once.
  - read-out: each (row, offset, phase) block of P (u on the sublanes,
    (phase, V) on the lanes) is turned by one strided lane roll
    (`pltpu.roll(..., stride=1, stride_axis=0)`: row u by u more than the
    last, which the reversed order makes a turn to the left), so its n
    diagonals land on the lanes i*n .. i*n+n-1 of their output; a select
    puts them into a float32 accumulator through the aligned 128-lane
    tiles that hold them. The blocks of one product are unrolled, the
    offsets that do not exist masked out of the select: in a `fori_loop`
    under `pl.when` Mosaic ran them one after another, 6 of 14 ms at the
    cell's shapes (PERF.md section 6, PR 41). At the tile's end the
    transposed permutation restores x order, and each row is written once,
    (W, n*n) in f1's dtype: the model's (B, H, W, n*n) layout.
  - why not the VPU sweep it replaced (PR 41): that did the 2.2e10
    multiply-adds of a step as float32 products and lane reductions on
    the VPU and XLU, a sublane relayout of f2 per displacement, and a
    synchronous window DMA a tile: 1.8% of the kernel's roofline. PR 41
    timed the masked select and sublane sum read-out (the products
    transposed) against the roll: PERF.md section 6.

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
from jax.experimental.pallas import tpu as pltpu

from . import pallas_call, pl
from ...parallel.spatial import current_mesh, shard_over_batch


# Both kernels hold the image's padded f2 in VMEM beside their row tiles
# (about 6 MB at the cell's 48 x 64 x 256 in bfloat16, the forward's with
# each row's columns split by phase; the backward's double-buffered, with
# its float32 df2 accumulator 10 MB more: 27 MB at that size), over
# Mosaic's default scoped limit (a v5e core holds 128 MiB).
_VMEM_BYTES = 96 * 2**20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# The forward's row tile and the padded f2 rows one product takes: PR 41's
# chip runs at the cell's shapes (PERF.md section 6).
_FWD_TILE_H = 8
_FWD_GROUP = 2


def _fwd_geometry(w: int, k: int, stride: int) -> tuple[int, int, int]:
    """(wh, vp, lanes) of the forward at feature width w: wh output columns
    a phase (a bfloat16 sublane tile's multiple), vp padded f2 columns a
    phase (the wh + 2k a phase's diagonals reach), and the lanes one padded
    f2 row takes (both phases' columns)."""
    wh = _round_up(-(-w // stride), 16)
    vp = _round_up(wh + 2 * k, 16)
    return wh, vp, _round_up(stride * vp, 128)


def _corr_kernel(f1_ref, f2_ref, out_ref, f2q_ref, f1s_ref, p_ref, acc_ref,
                 *, n: int, stride: int, tile_h: int, wh: int, vp: int,
                 c: int, group: int):
    s = stride
    k = n // 2
    t = pl.program_id(1)
    dt = f1_ref.dtype
    prec = lax.Precision.HIGHEST if dt == jnp.float32 else None
    h, w = f2_ref.shape[1:3]
    kt = tile_h // s  # rows of one row phase in the tile
    lanes = p_ref.shape[1] // group
    inv_c = 1.0 / c

    def onehot(rows, cols, hit):
        r = lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
        x = lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
        return hit(r, x).astype(dt)

    def mm(a, b, dims):
        # with a 0/1 operand (one term a sum) the products are exact
        return lax.dot_general(a, b, (dims, ((), ())), precision=prec,
                               preferred_element_type=jnp.float32)

    # f1's columns by phase, each phase reversed: row (p, rho) holds
    # x = s*(wh-1-rho) + p, so that ONE strided roll (row rho turned by rho
    # more than row 0) brings every row's diagonals to the same lanes
    def f1_col(pr):
        return s * (wh - 1 - pr % wh) + pr // wh

    perm = onehot(s * wh, w, lambda pr, x: x == f1_col(pr))
    perm_t = onehot(w, s * wh, lambda x, pr: x == f1_col(pr))

    @pl.when(t == 0)
    def _():
        # the image's padded f2, transposed, its rows by row phase and each
        # row's columns by phase: f2q[Y % s, :, (Y // s, p, V)] holds
        # f2[Y - pad, s*(V-k) + p], zero outside the image
        perm2 = onehot(w, s * vp,
                       lambda x, pv: x == s * (pv % vp - k) + pv // vp)
        f2q_ref[...] = jnp.zeros_like(f2q_ref)

        def put(y, _):
            yy = y + k * s
            col = pl.multiple_of((yy // s) * lanes, 128)
            f2q_ref[yy % s, :, pl.ds(col, s * vp)] = mm(
                f2_ref[0, y], perm2, ((0,), (0,))).astype(dt)
            return 0

        lax.fori_loop(0, h, put, 0)

    def permute(r, _):
        f1s_ref[r % s, r // s] = mm(perm, f1_ref[0, r], ((1,), (0,))).astype(dt)
        return 0

    lax.fori_loop(0, tile_h, permute, 0)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    lane = lax.broadcasted_iota(jnp.int32, (1, lanes + 128), 1)

    def phase(q, _):
        # the tile's rows of row phase q, stacked (kk, p, rho), meet the
        # padded f2 rows Y = t*TILE_H + q + s*m of the same phase (m below
        # kt + n - 1):
        # row r = q + s*kk at row offset i = m - kk. One product takes
        # `group` consecutive such rows (their columns on the lanes).
        lhs = f1s_ref[q].reshape(kt * s * wh, c)

        def rows(gi, _):
            m0 = gi * group
            col = pl.multiple_of((t * kt + m0) * lanes, 128)
            p_ref[...] = mm(lhs, f2q_ref[q, :, pl.ds(col, group * lanes)],
                            ((1,), (0,)))
            for kk in range(kt):
                for g in range(group):
                    i = m0 + g - kk
                    # row rho, phase p wants lanes p*vp + u + j (u =
                    # wh-1-rho): turned by lo - p*vp - (wh-1) + rho, its n
                    # diagonals sit at lanes lo..lo+n-1 of the 128-lane
                    # tile that holds i*n (none where i is no row offset)
                    ii = jnp.clip(i, 0, n - 1)
                    lo = (ii * n) % 128
                    win = pl.ds(pl.multiple_of(ii * n - lo, 128), lanes + 128)
                    lo_sel = jnp.where((i >= 0) & (i < n), lo, 2 * lanes)
                    sel = (lane >= lo_sel) & (lane < lo_sel + n)
                    for p in range(s):
                        blk = p_ref[(kk * s + p) * wh:(kk * s + p + 1) * wh,
                                    g * lanes:(g + 1) * lanes]
                        rot = pltpu.roll(blk, (lo - p * vp - (wh - 1)) % lanes,
                                         1, stride=1, stride_axis=0)
                        rot = jnp.concatenate([rot, rot[:, :128]], axis=1)
                        acc = acc_ref.at[q + s * kk, p * wh:(p + 1) * wh]
                        acc[:, win] = jnp.where(sel, rot, acc[:, win])
            return 0

        return lax.fori_loop(0, -(-(kt + n - 1) // group), rows, 0)

    lax.fori_loop(0, s, phase, 0)

    def emit(r, _):
        # back to x order by the transposed permutation, scaled and rounded
        # once to the output dtype
        nn = out_ref.shape[-1]
        a = (acc_ref[r, :, :_round_up(nn, 128)] * inv_c).astype(dt)
        out_ref[0, r] = mm(perm_t, a, ((1,), (0,)))[:, :nn].astype(
            out_ref.dtype)
        return 0

    lax.fori_loop(0, tile_h, emit, 0)


def _pallas_corr_fwd(f1: jnp.ndarray, f2: jnp.ndarray, max_disp: int,
                     stride: int, interpret: bool) -> jnp.ndarray:
    b, h, w, c = f1.shape
    s = stride
    k = max_disp // s
    n = 2 * k + 1
    wh, vp, lanes = _fwd_geometry(w, k, s)
    tile_h = _round_up(min(_FWD_TILE_H, h), s)
    group = _FWD_GROUP
    h_pad = (-h) % tile_h
    hp = h + h_pad
    kt = tile_h // s
    if h_pad:
        f1 = jnp.pad(f1, ((0, 0), (0, h_pad), (0, 0), (0, 0)))
    # f2 rows of one row phase, padded, and the last group's overrun
    f2_rows = (hp + 2 * k * s) // s + group

    kernel = functools.partial(_corr_kernel, n=n, stride=s, tile_h=tile_h,
                               wh=wh, vp=vp, c=c, group=group)
    out = pallas_call(
        kernel, name="corr_fwd",
        grid=(b, hp // tile_h),
        in_specs=[
            pl.BlockSpec((1, tile_h, w, c), lambda bi, ti: (bi, ti, 0, 0)),
            pl.BlockSpec((1, h, w, c), lambda bi, ti: (bi, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_h, w, n * n),
                               lambda bi, ti: (bi, ti, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hp, w, n * n), f1.dtype),
        scratch_shapes=[
            pltpu.VMEM((s, c, f2_rows * lanes), f1.dtype),
            pltpu.VMEM((s, kt, s * wh, c), f1.dtype),
            pltpu.VMEM((kt * s * wh, group * lanes), jnp.float32),
            pltpu.VMEM((tile_h, s * wh, _round_up(n * n, 128) + lanes),
                       jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(f1, f2)
    return out[:, :h] if h_pad else out


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
    df1, df2 = pallas_call(
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
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(f1, f2p, gt)
    return df1[:, :h], df2


def _launch(f1, f2, max_disp, stride, interpret, mesh):
    return shard_over_batch(
        lambda a, b: _pallas_corr_fwd(a, b, max_disp, stride, interpret),
        mesh, f1.shape[0])(f1, f2)


def correlation_pallas(f1, f2, max_disp: int = 20, stride: int = 2,
                       tile_h: int = 8, interpret: bool | None = None):
    """Pallas cost volume: (B,H,W,C) x2 -> (B,H,W,(2K+1)^2), K=max_disp//stride.

    tile_h is the backward's row tile; the forward takes its own
    (`_FWD_TILE_H`, or H where that is less). interpret=None
    auto-selects interpreter mode off-TPU (CPU test
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
    return _launch(f1, f2, max_disp, stride, interpret, mesh)


def _fwd(f1, f2, max_disp, stride, tile_h, interpret, mesh):
    return _launch(f1, f2, max_disp, stride, interpret, mesh), (f1, f2)


def _bwd(max_disp, stride, tile_h, interpret, mesh, res, g):
    f1, f2 = res
    return shard_over_batch(
        lambda a, b, ct: _pallas_corr_bwd(a, b, ct, max_disp, stride, tile_h,
                                          interpret),
        mesh, f1.shape[0])(f1, f2, g)


_correlation.defvjp(_fwd, _bwd)
