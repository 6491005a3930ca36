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
column offsets are n diagonals of P, which `corr_bwd` writes where this
kernel reads them.
  - x and f2p's columns are split by their phase modulo s (x = s*u + p,
    X = s*V + p): a phase's wanted entries are the unit-stride diagonals
    V = u + j. The kernel lays both out itself, by 0/1 products on the MXU
    (exact): f1's tile as (phase, reversed u) rows, the image's padded f2
    transposed, its rows by row phase, the rows of a phase in groups of
    `_GROUP` and phase-major inside a group, (C, (row phase, group, p,
    row, V)) with vp columns a phase padded so that a group's columns of
    one phase are whole 128-lane tiles; one VMEM scratch built at the
    image's first tile. f1 and f2 come from HBM once and as they are.
  - grid = (B, H/TILE_H), TILE_H `_TILE_H` rows (fewer where H is less,
    a multiple of s * `_GROUP`). A padded f2 row of row phase q meets the
    tile's rows of phase q (row r at row offset i = (Y - r)/s): one
    product a column phase (NN, f2 stationary) takes those rows' columns
    of the phase stacked against a group's f2 columns of the same phase,
    so no product multiplies the other phase's zeros.
  - read-out: each (row, offset) block of a phase's product (u on the
    sublanes, the group's (row, V) on the lanes) is turned by one strided
    lane roll (`pltpu.roll(..., stride=1, stride_axis=0)`: row u by u more
    than the last, which the reversed order makes a turn to the left), so
    its n diagonals land on the lanes i*n .. i*n+n-1 of their output (the
    group's other rows land outside them: vp >= wh + 2K); a select puts
    them into a float32 accumulator through the aligned 128-lane tiles
    that hold them. The blocks of one product are unrolled, the offsets
    that do not exist masked out of the select: in a `fori_loop` under
    `pl.when` Mosaic ran them one after another, 6 of 14 ms at the cell's
    shapes (PERF.md section 6, PR 41). At the tile's end the transposed
    permutation restores x order, and each row is written once, (W, n*n)
    in f1's dtype: the model's (B, H, W, n*n) layout.
  - why not the VPU sweep it replaced (PR 41): that did the 2.2e10
    multiply-adds of a step as float32 products and lane reductions on
    the VPU and XLU, a sublane relayout of f2 per displacement, and a
    synchronous window DMA a tile: 1.8% of the kernel's roofline. PR 41
    timed the masked select and sublane sum read-out (the products
    transposed) against the roll: PERF.md section 6.

Backward (`corr_bwd`, the custom VJP's one implementation on every
backend; residuals f1, f2): the forward's transpose. With g the cotangent
of corr and P-bar the cotangent of a phase's product P,

    P-bar[(row, u), (f2 row, V)] = g[y, s*u + p, i*n + (V - u)]
                                   for 0 <= V - u < n, else 0
    df1 rows  += P-bar . f2q^T        df2q += f1s^T . P-bar

for each column phase p, on the forward's grid, tile, product groups and
f2 layout; df2q is a float32 scratch in f2q's layout that stays resident
across the image's row tiles.
  - inputs as they are: f1 and f2 (B, H, W, C), g (B, H, W, n*n), the
    forward's output layout. In the kernel f2 is laid out once an image
    into the forward's f2q, and each tile's f1 rows and g rows are
    permuted on the MXU into (phase, u) order, u increasing (exact: each
    output is one term), f1's then transposed once a row and column
    phase, so that both products are plain NN / NT ones.
  - placing a block: the n diagonals of a (row, offset, phase) block are
    g's lanes i*n .. i*n+n-1 of that row. An aligned window of the permuted
    row holding them is masked to those lanes (the offsets that do not
    exist to none), folded onto the group's lanes and turned by one
    strided lane roll, row u by u more than row 0 (the forward's roll
    inverted; u increasing makes it a turn to the right, since Mosaic's
    strides are not negative): each entry lands on lane gg*vp + u + j of
    its f2 row gg, everything else is zero, and the group's blocks of one
    tile row add up to that row's part of P-bar. The blocks of one
    product are unrolled as in the forward; the products stay a
    `fori_loop`.
  - outputs: at the tile's end df1 is scaled, rounded once to the input
    dtype and returned to x order by the transposed permutation, a row
    written once; at the image's last tile each row of df2q is mapped
    back to (W, C) by the transpose of the permutation that built f2q, and
    written once. P-bar holds the cotangent's own values, so with
    bfloat16 operands the products are exact in float32.
  - why not the band loop it replaced (PR 43): for each row and row
    offset, one at a time in a `fori_loop`, that built a (Wp, W) band of
    both column phases by n selects and multiplied it twice, 64,512
    serial pairs a step at 277 ns, half of each product zeros, with XLA's
    transpose of the cotangent and pad of f2 around it: 17.85 ms a step,
    3.9% of the kernel's roofline (PERF.md section 6, PR 43).
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
# (about 6 MB at the cell's 48 x 64 x 256 in bfloat16, each row's columns
# split by phase; the backward's float32 df2 accumulator in the same
# layout 12 MB more), over Mosaic's default scoped limit (a v5e core holds
# 128 MiB).
_VMEM_BYTES = 96 * 2**20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# The row tile and the padded f2 rows one product takes, both directions:
# PR 41's and PR 43's chip runs at the cell's shapes (PERF.md section 6;
# larger tiles or groups unroll twice the blocks for 0.05-0.6 ms).
_TILE_H = 8
_GROUP = 2


def _tiling(h: int, w: int, max_disp: int, stride: int):
    """Both kernels' geometry at (h, w): (k, n, wh, vp, tile_h, h_pad,
    groups). wh output columns a phase (a bfloat16 sublane tile's
    multiple); vp padded f2 columns a phase (the wh + 2k a phase's
    diagonals reach, so that a group's `_GROUP` rows of one phase fill
    whole 128-lane tiles); a row tile of whole groups of each row phase;
    `groups` the groups of one row phase, with the last product's
    overrun."""
    k = max_disp // stride
    wh = _round_up(-(-w // stride), 16)
    vp = _round_up(wh + 2 * k, max(16, 128 // _GROUP))
    tile_h = _round_up(min(_TILE_H, h), stride * _GROUP)
    h_pad = (-h) % tile_h
    groups = -(-((h + h_pad) // stride + 2 * k) // _GROUP) + 1
    return k, 2 * k + 1, wh, vp, tile_h, h_pad, groups


def _onehot(rows, cols, hit, dt):
    r = lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    x = lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    return hit(r, x).astype(dt)


def _mm(a, b, dims):
    # with a 0/1 operand (one term a sum) the products are exact
    prec = lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return lax.dot_general(a, b, (dims, ((), ())), precision=prec,
                           preferred_element_type=jnp.float32)


def _group_columns(w, k, s, vp, gg, dt):
    """(W, s*lw) 0/1, lw = _GROUP*vp: column (p, gg', V) of a group's block
    of f2q holds f2[., s*(V-k) + p] of the group's row gg (gg' == gg; none
    outside the image)."""
    lw = _GROUP * vp
    return _onehot(w, s * lw, lambda x, col: ((col % lw) // vp == gg)
                   & (x == s * (col % vp - k) + col // lw), dt)


def _lay_out_f2(f2_ref, f2q_ref, k, vp):
    """The image's padded f2, transposed, its rows by row phase and the
    rows of one phase in groups of `_GROUP`, phase-major inside a group:
    f2q[q, :, (G, p, gg, V)] holds f2[Y - pad, s*(V-k) + p] for the padded
    row Y = s*(G*_GROUP + gg) + q, zero outside the image. A group's block
    of one column phase is whole 128-lane tiles: one product's operand."""
    s = f2q_ref.shape[0]
    h, w = f2_ref.shape[1:3]
    dt = f2q_ref.dtype
    lw = _GROUP * vp
    cols = [_group_columns(w, k, s, vp, gg, dt) for gg in range(_GROUP)]

    def put(qg, _):
        q, gi = qg % s, qg // s
        blk = 0.0
        for gg in range(_GROUP):
            y = s * (gi * _GROUP + gg) + q - k * s
            inside = ((y >= 0) & (y < h)).astype(jnp.float32)
            blk = blk + inside * _mm(f2_ref[0, jnp.clip(y, 0, h - 1)],
                                     cols[gg], ((0,), (0,)))
        f2q_ref[q, :, pl.ds(pl.multiple_of(gi * s * lw, 128), s * lw)] = (
            blk.astype(dt))
        return 0

    lax.fori_loop(0, f2q_ref.shape[2] // lw, put, 0)


def _corr_kernel(f1_ref, f2_ref, out_ref, f2q_ref, f1s_ref, p_ref, acc_ref,
                 *, n: int, stride: int, tile_h: int, wh: int, vp: int,
                 c: int):
    s = stride
    k = n // 2
    t = pl.program_id(1)
    dt = f1_ref.dtype
    w = f2_ref.shape[2]
    kt = tile_h // s  # rows of one row phase in the tile
    lw = _GROUP * vp  # the lanes of one column phase of a group
    inv_c = 1.0 / c

    # f1's columns by phase, each phase reversed: row (p, rho) holds
    # x = s*(wh-1-rho) + p, so that ONE strided roll (row rho turned by rho
    # more than row 0) brings every row's diagonals to the same lanes
    def f1_col(pr):
        return s * (wh - 1 - pr % wh) + pr // wh

    perm = _onehot(s * wh, w, lambda pr, x: x == f1_col(pr), dt)
    perm_t = _onehot(w, s * wh, lambda x, pr: x == f1_col(pr), dt)

    @pl.when(t == 0)
    def _():
        _lay_out_f2(f2_ref, f2q_ref, k, vp)

    def permute(r, _):
        rows = _mm(perm, f1_ref[0, r], ((1,), (0,))).astype(dt)
        for p in range(s):
            f1s_ref[r % s, p, r // s] = rows[p * wh:(p + 1) * wh]
        return 0

    lax.fori_loop(0, tile_h, permute, 0)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    lane = lax.broadcasted_iota(jnp.int32, (1, lw + 128), 1)

    def phase(q, _):
        # the tile's rows of row phase q meet the padded f2 rows
        # Y = t*TILE_H + q + s*m of the same phase (m below kt + n - 1):
        # row r = q + s*kk at row offset i = m - kk. One product a column
        # phase p takes the phase's rows stacked (kk, rho) against a group
        # of `_GROUP` consecutive such f2 rows (their columns on the lanes).
        def rows(gi, _):
            m0 = gi * _GROUP
            col = (t * (kt // _GROUP) + gi) * s * lw
            for p in range(s):
                p_ref[p] = _mm(f1s_ref[q, p].reshape(kt * wh, c),
                               f2q_ref[q, :, pl.ds(pl.multiple_of(
                                   col + p * lw, 128), lw)], ((1,), (0,)))
            for kk in range(kt):
                for g in range(_GROUP):
                    i = m0 + g - kk
                    # row rho, phase p wants lanes g*vp + u + j (u =
                    # wh-1-rho): turned by lo - g*vp - (wh-1) + rho, its n
                    # diagonals sit at lanes lo..lo+n-1 of the 128-lane
                    # tile that holds i*n (none where i is no row offset;
                    # the group's other rows land elsewhere: vp >= wh + 2k)
                    ii = jnp.clip(i, 0, n - 1)
                    lo = (ii * n) % 128
                    win = pl.ds(pl.multiple_of(ii * n - lo, 128), lw + 128)
                    lo_sel = jnp.where((i >= 0) & (i < n), lo, 2 * lw)
                    sel = (lane >= lo_sel) & (lane < lo_sel + n)
                    for p in range(s):
                        rot = pltpu.roll(p_ref[p, kk * wh:(kk + 1) * wh],
                                         (lo - g * vp - (wh - 1)) % lw,
                                         1, stride=1, stride_axis=0)
                        rot = jnp.concatenate([rot, rot[:, :128]], axis=1)
                        acc = acc_ref.at[q + s * kk, p * wh:(p + 1) * wh]
                        acc[:, win] = jnp.where(sel, rot, acc[:, win])
            return 0

        return lax.fori_loop(0, -(-(kt + n - 1) // _GROUP), rows, 0)

    lax.fori_loop(0, s, phase, 0)

    def emit(r, _):
        # back to x order by the transposed permutation, scaled and rounded
        # once to the output dtype
        nn = out_ref.shape[-1]
        a = (acc_ref[r, :, :_round_up(nn, 128)] * inv_c).astype(dt)
        out_ref[0, r] = _mm(perm_t, a, ((1,), (0,)))[:, :nn].astype(
            out_ref.dtype)
        return 0

    lax.fori_loop(0, tile_h, emit, 0)


def _pallas_corr_fwd(f1: jnp.ndarray, f2: jnp.ndarray, max_disp: int,
                     stride: int, interpret: bool) -> jnp.ndarray:
    b, h, w, c = f1.shape
    s = stride
    k, n, wh, vp, tile_h, h_pad, groups = _tiling(h, w, max_disp, s)
    hp = h + h_pad
    kt = tile_h // s
    lw = _GROUP * vp
    if h_pad:
        f1 = jnp.pad(f1, ((0, 0), (0, h_pad), (0, 0), (0, 0)))

    kernel = functools.partial(_corr_kernel, n=n, stride=s, tile_h=tile_h,
                               wh=wh, vp=vp, c=c)
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
            pltpu.VMEM((s, c, groups * s * lw), f1.dtype),
            pltpu.VMEM((s, s, kt, wh, c), f1.dtype),
            pltpu.VMEM((s, kt * wh, lw), jnp.float32),
            pltpu.VMEM((tile_h, s * wh, _round_up(n * n, 128) + lw),
                       jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(f1, f2)
    return out[:, :h] if h_pad else out


def _fold(win, sel):
    """The lanes `sel` keeps of a (rows, lanes + 128) window, folded onto
    its first `lanes` lanes (lane L + lanes onto lane L)."""
    m = jnp.where(sel, win, 0.0)
    lanes = m.shape[1] - 128
    tail = m[:, lanes:]
    if lanes > 128:
        tail = jnp.concatenate(
            [tail, jnp.zeros((m.shape[0], lanes - 128), m.dtype)], axis=1)
    return m[:, :lanes] + tail


def _place_block(win, sel, lo, base):
    """One (row, offset, phase) block of P-bar, (wh, lw) float32, from an
    aligned window (wh, lw + 128) of the permuted cotangent rows (p, u):
    the block's n diagonals, the lanes lo .. lo+n-1 that `sel` keeps, turned
    by base - lo + u on row u, so diagonal j of row u lands on lane
    base + u + j (base = gg*vp: the columns of the group's row gg)."""
    f = _fold(win, sel)
    return pltpu.roll(f, (base - lo) % f.shape[1], 1, stride=1, stride_axis=0)


def _corr_bwd_kernel(f1_ref, f2_ref, g_ref, df1_ref, df2_ref, f2q_ref,
                     df2q_ref, f1t_ref, gs_ref, pb_ref, df1s_ref, *,
                     n: int, stride: int, tile_h: int, wh: int, vp: int,
                     c: int):
    s = stride
    k = n // 2
    t = pl.program_id(1)
    dt = f1_ref.dtype
    h, w = f2_ref.shape[1:3]
    kt = tile_h // s  # rows of one row phase in the tile
    lw = _GROUP * vp  # the lanes of one column phase of a group
    nn = n * n
    inv_c = 1.0 / c

    # rows (p, u): x = s*u + p, u increasing, so that the strided roll
    # that places a block turns row u by u more than row 0
    def col_of(pu):
        return s * (pu % wh) + pu // wh

    perm = _onehot(s * wh, w, lambda pu, x: x == col_of(pu), dt)
    perm_t = _onehot(w, s * wh, lambda x, pu: x == col_of(pu), dt)

    @pl.when(t == 0)
    def _():
        _lay_out_f2(f2_ref, f2q_ref, k, vp)
        df2q_ref[...] = jnp.zeros_like(df2q_ref)
        gs_ref[...] = jnp.zeros_like(gs_ref)  # lanes past n*n stay zero

    for q in range(s):
        # f1's rows of row phase q permuted, and for each column phase p
        # stacked (kk, u) and transposed once: the contraction of the df2
        # product on the lanes
        rows = [_mm(perm, f1_ref[0, q + s * kk], ((1,), (0,)))
                for kk in range(kt)]
        for p in range(s):
            f1t_ref[q, p] = jnp.concatenate(
                [r[p * wh:(p + 1) * wh] for r in rows], axis=0).T.astype(dt)

    def permute(r, _):
        gs_ref[r, :, :nn] = _mm(perm, g_ref[0, r], ((1,), (0,)))
        return 0

    lax.fori_loop(0, tile_h, permute, 0)
    lane = lax.broadcasted_iota(jnp.int32, (1, lw + 128), 1)

    def phase(q, _):
        # the forward's blocks: the tile's rows of row phase q meet the
        # padded f2 rows Y = t*TILE_H + q + s*m, row r = q + s*kk at row
        # offset i = m - kk, a group of `_GROUP` such f2 rows a product
        df1s_ref[...] = jnp.zeros_like(df1s_ref)

        def rows(gi, _):
            m0 = gi * _GROUP
            col = (t * (kt // _GROUP) + gi) * s * lw
            # row offset i's diagonals are lanes lo..lo+n-1 of the 128-lane
            # tile that holds i*n (none where i is no row offset)
            sel = {}
            for kk in range(kt):
                for g in range(_GROUP):
                    i = m0 + g - kk
                    ii = jnp.clip(i, 0, n - 1)
                    lo = (ii * n) % 128
                    lo_sel = jnp.where((i >= 0) & (i < n), lo, 2 * lw)
                    sel[kk, g] = (
                        lo, pl.ds(pl.multiple_of(ii * n - lo, 128), lw + 128),
                        (lane >= lo_sel) & (lane < lo_sel + n))
            for p in range(s):
                for kk in range(kt):
                    blk = 0.0
                    for g in range(_GROUP):
                        lo, win, keep = sel[kk, g]
                        blk = blk + _place_block(
                            gs_ref[q + s * kk, p * wh:(p + 1) * wh, win],
                            keep, lo, g * vp)
                    pb_ref[p, kk * wh:(kk + 1) * wh] = blk.astype(dt)
                cols = pl.ds(pl.multiple_of(col + p * lw, 128), lw)
                pb = pb_ref[p]
                df1s_ref[p] += _mm(pb, f2q_ref[q, :, cols], ((1,), (1,)))
                df2q_ref[q, :, cols] += _mm(f1t_ref[q, p], pb, ((1,), (0,)))
            return 0

        lax.fori_loop(0, -(-(kt + n - 1) // _GROUP), rows, 0)
        for kk in range(kt):
            # back to x order by the transposed permutation, scaled and
            # rounded once to the output dtype
            a = jnp.concatenate([df1s_ref[p, kk * wh:(kk + 1) * wh]
                                 for p in range(s)], axis=0)
            df1_ref[0, q + s * kk] = _mm(perm_t, (a * inv_c).astype(dt),
                                         ((1,), (0,))).astype(df1_ref.dtype)
        return 0

    lax.fori_loop(0, s, phase, 0)

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        def out_row(y, _):
            yy = y + k * s
            rr = yy // s
            col = pl.multiple_of((rr // _GROUP) * s * lw, 128)
            a = (df2q_ref[yy % s, :, pl.ds(col, s * lw)] * inv_c).astype(dt)
            df2_ref[0, y] = _mm(_group_columns(w, k, s, vp, rr % _GROUP, dt),
                                a, ((1,), (1,))).astype(df2_ref.dtype)
            return 0

        lax.fori_loop(0, h, out_row, 0)


def _pallas_corr_bwd(f1, f2, g, max_disp: int, stride: int,
                     interpret: bool):
    """(df1, df2) of the correlation for its cotangent g (B, H, W, n*n)."""
    b, h, w, c = f1.shape
    s = stride
    k, n, wh, vp, tile_h, h_pad, groups = _tiling(h, w, max_disp, s)
    hp = h + h_pad
    kt = tile_h // s
    lw = _GROUP * vp
    if h_pad:  # zero rows: their cotangent adds nothing
        rows = ((0, 0), (0, h_pad), (0, 0), (0, 0))
        f1, g = jnp.pad(f1, rows), jnp.pad(g, rows)

    kernel = functools.partial(_corr_bwd_kernel, n=n, stride=s, tile_h=tile_h,
                               wh=wh, vp=vp, c=c)
    tile = lambda last: pl.BlockSpec((1, tile_h, w, last),  # noqa: E731
                                     lambda bi, ti: (bi, ti, 0, 0))
    image = pl.BlockSpec((1, h, w, c), lambda bi, ti: (bi, 0, 0, 0))
    df1, df2 = pallas_call(
        kernel, name="corr_bwd",
        grid=(b, hp // tile_h),
        in_specs=[tile(c), image, tile(n * n)],
        out_specs=[tile(c), image],
        out_shape=[jax.ShapeDtypeStruct((b, hp, w, c), f1.dtype),
                   jax.ShapeDtypeStruct((b, h, w, c), f2.dtype)],
        scratch_shapes=[
            pltpu.VMEM((s, c, groups * s * lw), f1.dtype),
            pltpu.VMEM((s, c, groups * s * lw), jnp.float32),
            pltpu.VMEM((s, s, c, kt * wh), f1.dtype),
            pltpu.VMEM((tile_h, s * wh, _round_up(n * n, 128) + lw),
                       jnp.float32),
            pltpu.VMEM((s, kt * wh, lw), f1.dtype),
            pltpu.VMEM((s, kt * wh, c), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(f1, f2, g)
    return (df1[:, :h] if h_pad else df1), df2


def _launch(f1, f2, max_disp, stride, interpret, mesh):
    return shard_over_batch(
        lambda a, b: _pallas_corr_fwd(a, b, max_disp, stride, interpret),
        mesh, f1.shape[0])(f1, f2)


def correlation_pallas(f1, f2, max_disp: int = 20, stride: int = 2,
                       interpret: bool | None = None):
    """Pallas cost volume: (B,H,W,C) x2 -> (B,H,W,(2K+1)^2), K=max_disp//stride.

    Forward and backward take one row tile (`_TILE_H`, or H where that is
    less). interpret=None auto-selects interpreter mode off-TPU (CPU test
    meshes), exactly as `backward_warp_pallas` does. Under a
    `mesh_context` the kernel runs per batch shard
    (`parallel.spatial.shard_over_batch`); the mesh is resolved HERE and
    carried as a static argument because the VJP's backward rule is
    traced after the context has exited.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _correlation(f1, f2, max_disp, stride, interpret, current_mesh())


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _correlation(f1, f2, max_disp, stride, interpret, mesh):
    return _launch(f1, f2, max_disp, stride, interpret, mesh)


def _fwd(f1, f2, max_disp, stride, interpret, mesh):
    return _launch(f1, f2, max_disp, stride, interpret, mesh), (f1, f2)


def _bwd(max_disp, stride, interpret, mesh, res, g):
    f1, f2 = res
    return shard_over_batch(
        lambda a, b, ct: _pallas_corr_bwd(a, b, ct, max_disp, stride,
                                          interpret),
        mesh, f1.shape[0])(f1, f2, g)


_correlation.defvjp(_fwd, _bwd)
