"""The `kernel` path of `ops/ssm.py::doubled_scan`: the state-space scan of
a doubled row as one pair of Mosaic kernels, `ssd_fwd` / `ssd_bwd`, behind
a custom VJP. The arithmetic is `ops/ssm.py`'s docstring's, term for term;
what changes is where it lives: a chunk's decay and score matrices exist
only in VMEM, and the clean state is carried from chunk to chunk in VMEM
scratch in place of the chunked form's matrix of decays between chunk ends.

Grid (row, B/C group, chunk), the chunks of a copy in order ("arbitrary")
and the rows and groups parallel. One step takes chunk z of BOTH copies at
one group's heads: x [Q, R P] (the group's R heads of P, as the layer lays
them out: no head-major copy), B and C [Q, N], and the per-head vectors of
the chunk, [5 R, Q] float32 rows (`DT_N`, `DT_C`, `RUN_N`, `RUN_C`, `PATH`:
dt, the running sums of dt A inside the chunk and the noised path sums P_t,
made by XLA over [2 L, H] only).

Forward (`ssd_fwd`), with h the clean state at the chunk's start [R P, N]
(zero at chunk 0):

  - clean:  yc = (C_c B_c^T o exp(L_t - L_s))[s <= t] (x dt)_c + (C_c h^T) exp(L_t)
  - noised: yn = own block (C_n B_n^T o exp(Ln_t - Ln_s)) (x dt)_n
                 + earlier blocks (C_n B_c^T o exp(P_t - L_s)) (x dt)_c
                 + (C_n h^T) exp(P_t)
  - h <- exp(L_end) h + ((x dt)_c o exp(L_end - L_s))^T B_c

and writes yn, yc [Q, R P] float32 and h at the chunk's start, the only
residual (`ops/ssm.py::STATES`). Backward (`ssd_bwd`), the chunks in
reverse: each chunk's matrices are built again from its inputs and its
saved start state; the cotangent of the clean state at the chunk's end is
carried in VMEM scratch; out come dx, dB, dC of both copies and the
cotangents of the per-head rows, which XLA takes back through the running
sums to dt and A.

Heads go through the chunk in slabs of whole heads up to 128 lanes (two
heads of 64): a head's product takes the slab with the other heads' lanes
zeroed, which costs the MXU what a 64-wide product costs, and the
group's state products (C h^T, the state's update and their transposes)
take all heads of the group at once.

Precision, as `ops/ssm.py` states it: every product takes operands in the
compute dtype and accumulates in float32; dt, the decays, the running sums,
the state and its carry stay float32.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.pallas import tpu as pltpu

from . import pallas_call, pl
from ...parallel.spatial import current_mesh, shard_over_batch
from .. import ssm

F32 = jnp.float32
LANES = 128
_NT = (((1,), (1,)), ((), ()))  # a[m, k] . b[n, k] -> [m, n]
_TN = (((0,), (0,)), ((), ()))  # a[k, m] . b[k, n] -> [m, n]
#: the per-head rows of a chunk's vector block, R rows each, in this order
DT_N, DT_C, RUN_N, RUN_C, PATH = range(5)
KINDS = 5


class Geometry(NamedTuple):
    heads: int  # R, the heads of a group
    head_dim: int  # P
    slab: int  # heads a slab
    chunk: int  # Q, positions a grid step takes of each copy
    block: int  # positions of a noised block, a power of two
    dtype: jnp.dtype  # of the products' operands


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """A product accumulated in float32; float32 operands at full
    precision (Mosaic's default rounds them to bfloat16)."""
    return lax.dot_general(a, b, dims, preferred_element_type=F32, precision=(
        lax.Precision.HIGHEST if a.dtype == F32 else None))


class _Chunk:
    """The masks and per-head vectors of one chunk, built in VMEM."""

    def __init__(self, v, geo: Geometry, n: int):
        q = v.shape[1]
        self.v, self.vt, self.geo = v, v.T, geo  # [5R, Q], [Q, 5R]
        t = lax.broadcasted_iota(jnp.int32, (q, q), 0)
        s = lax.broadcasted_iota(jnp.int32, (q, q), 1)
        shift = geo.block.bit_length() - 1
        self.causal = t >= s
        self.own = (t >> shift == s >> shift) & self.causal
        self.earlier = t >> shift > s >> shift
        width = geo.slab * geo.head_dim
        lane = lax.broadcasted_iota(jnp.int32, (q, width), 1)
        row = lax.broadcasted_iota(jnp.int32, (width, n), 0)
        # which head of the slab a lane (a state row) belongs to
        self.lane_head, self.row_head = (
            sum(((a >= j * geo.head_dim).astype(jnp.int32)
                 for j in range(1, geo.slab)), jnp.zeros(a.shape, jnp.int32))
            for a in (lane, row))
        self.q, self.n = q, n

    def index(self, kind, r):
        return kind * self.geo.heads + r

    def col(self, kind, r):
        """[Q, 1]: the vector over t, down the sublanes."""
        i = self.index(kind, r)
        return self.vt[:, i:i + 1]

    def row(self, kind, r):
        """[1, Q]: the vector over s, along the lanes."""
        i = self.index(kind, r)
        return self.v[i:i + 1, :]

    def spread(self, kind, heads):
        """[Q, slab width]: each head's vector over t on its own lanes."""
        out = None
        for j, r in enumerate(heads):
            c = jnp.broadcast_to(self.col(kind, r), self.lane_head.shape)
            out = c if out is None else jnp.where(self.lane_head == j, c, out)
        return out

    def only(self, j, a):
        """a[Q, slab width] with every lane but head j's zeroed."""
        return jnp.where(self.lane_head == j, a, 0.0)

    def head_sum(self, j, a):
        """[Q, 1]: a[Q, slab width] summed over head j's lanes."""
        return jnp.sum(self.only(j, a), axis=1, keepdims=True)

    def end_rows(self, heads):
        """[slab width, N]: each state row's head's exp(L_end), the clean
        copy's decay over the whole chunk."""
        out = None
        for j, r in enumerate(heads):
            e = jnp.broadcast_to(self.col(RUN_C, r), (self.q, self.n))[-1:, :]
            e = jnp.broadcast_to(jnp.exp(e), self.row_head.shape)
            out = e if out is None else jnp.where(self.row_head == j, e, out)
        return out

    def weights(self, r, cb_c, cb_n, cb_x):
        """Head r's three [Q, Q] float32 matrices (clean, noised own
        block, noised against the clean copy's earlier blocks) and the
        decays they were scaled by."""
        rc_t, rc_s = self.col(RUN_C, r), self.row(RUN_C, r)
        rn_t, rn_s = self.col(RUN_N, r), self.row(RUN_N, r)
        e_c = jnp.exp(jnp.where(self.causal, rc_t - rc_s, -jnp.inf))
        e_n = jnp.exp(jnp.where(self.own, rn_t - rn_s, jnp.where(
            self.earlier, self.col(PATH, r) - rc_s, -jnp.inf)))
        return (cb_c * e_c, jnp.where(self.own, cb_n, 0.0) * e_n,
                jnp.where(self.earlier, cb_x, 0.0) * e_n, e_c, e_n)


def _slabs(geo: Geometry):
    width = geo.slab * geo.head_dim
    for k in range(geo.heads // geo.slab):
        yield (slice(k * width, (k + 1) * width),
               range(k * geo.slab, (k + 1) * geo.slab))


def _fwd_kernel(xn_ref, xc_ref, bn_ref, bc_ref, cn_ref, cc_ref, v_ref,
                yn_ref, yc_ref, h0_ref, h_ref, *, geo: Geometry):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    cdt = geo.dtype  # the products' operands
    ch = _Chunk(v_ref[...], geo, h_ref.shape[-1])
    bn, bc, cn, cc = (r[...].astype(cdt) for r in (bn_ref, bc_ref, cn_ref, cc_ref))
    cb_c, cb_n, cb_x = _dot(cc, bc, _NT), _dot(cn, bn, _NT), _dot(cn, bc, _NT)
    h = h_ref[...]
    h0_ref[...] = h
    hb = h.astype(cdt)
    sc_c, sc_n = _dot(cc, hb, _NT), _dot(cn, hb, _NT)  # [Q, R P]
    for lanes, heads in _slabs(geo):
        run_c = ch.spread(RUN_C, heads)
        xdt_c = xc_ref[:, lanes].astype(F32) * ch.spread(DT_C, heads)
        xdt_n = xn_ref[:, lanes].astype(F32) * ch.spread(DT_N, heads)
        yc = sc_c[:, lanes] * jnp.exp(run_c)
        yn = sc_n[:, lanes] * jnp.exp(ch.spread(PATH, heads))
        for j, r in enumerate(heads):
            w_c, w_n, w_x, _, _ = ch.weights(r, cb_c, cb_n, cb_x)
            x_c = ch.only(j, xdt_c).astype(cdt)
            yc = yc + _dot(w_c.astype(cdt), x_c)
            yn = yn + _dot(w_n.astype(cdt), ch.only(j, xdt_n).astype(cdt)) \
                + _dot(w_x.astype(cdt), x_c)
        yc_ref[:, lanes] = yc
        yn_ref[:, lanes] = yn
        own = _dot((xdt_c * jnp.exp(run_c[-1:, :] - run_c)).astype(cdt), bc, _TN)
        h_ref[lanes, :] = ch.end_rows(heads) * h[lanes, :] + own


def _bwd_kernel(xn_ref, xc_ref, bn_ref, bc_ref, cn_ref, cc_ref, v_ref, h0_ref,
                dyn_ref, dyc_ref, dxn_ref, dxc_ref, dbn_ref, dbc_ref, dcn_ref,
                dcc_ref, dv_ref, dh_ref, *, geo: Geometry):
    @pl.when(pl.program_id(2) == 0)  # the last chunk: nothing after it
    def _():
        dh_ref[...] = jnp.zeros_like(dh_ref)

    cdt = geo.dtype  # the products' operands
    ch = _Chunk(v_ref[...], geo, dh_ref.shape[-1])
    q = ch.q
    bn, bc, cn, cc = (r[...].astype(cdt) for r in (bn_ref, bc_ref, cn_ref, cc_ref))
    cb_c, cb_n, cb_x = _dot(cc, bc, _NT), _dot(cn, bn, _NT), _dot(cn, bc, _NT)
    h = h0_ref[...]
    hb = h.astype(cdt)
    sc_c, sc_n = _dot(cc, hb, _NT), _dot(cn, hb, _NT)
    dh_end = dh_ref[...]  # the cotangent of the state at the chunk's end
    dcb_c = dcb_n = dcb_x = jnp.zeros((q, q), F32)
    dc_c = dc_n = db_c = jnp.zeros(cc.shape, F32)
    cols, rows = {}, {}  # (kind, r) -> [Q, 1] over t / [1, Q] over s
    last = lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    for lanes, heads in _slabs(geo):
        xc, xn = xc_ref[:, lanes].astype(F32), xn_ref[:, lanes].astype(F32)
        dtc, dtn = ch.spread(DT_C, heads), ch.spread(DT_N, heads)
        xdt_c, xdt_n = xc * dtc, xn * dtn
        run_c = ch.spread(RUN_C, heads)
        dyc, dyn = dyc_ref[:, lanes], dyn_ref[:, lanes]
        # the start state's terms
        dg_c = dyc * jnp.exp(run_c)
        dg_n = dyn * jnp.exp(ch.spread(PATH, heads))
        dc_c = dc_c + _dot(dg_c.astype(cdt), hb[lanes, :])
        dc_n = dc_n + _dot(dg_n.astype(cdt), hb[lanes, :])
        dh = _dot(dg_c.astype(cdt), cc, _TN) + _dot(dg_n.astype(cdt), cn, _TN)
        t_run_c = dg_c * sc_c[:, lanes]
        t_path = dg_n * sc_n[:, lanes]
        # the state carried out of the chunk: h' = exp(L_end) h + own
        dec = jnp.exp(run_c[-1:, :] - run_c)
        xs = xdt_c * dec
        dhe = dh_end[lanes, :]
        dxs = _dot(bc, dhe.astype(cdt), _NT)  # [Q, slab width]
        db_c = db_c + _dot(xs.astype(cdt), dhe.astype(cdt))
        dxdt_c = dxs * dec
        d_dec = dxs * xs
        ends = ch.end_rows(heads)
        carried = ends * h[lanes, :] * dhe
        dh_ref[lanes, :] = dh + ends * dhe
        dxdt_n = jnp.zeros_like(xdt_n)
        xb_c, xb_n = xdt_c.astype(cdt), xdt_n.astype(cdt)
        for j, r in enumerate(heads):
            w_c, w_n, w_x, e_c, e_n = ch.weights(r, cb_c, cb_n, cb_x)
            dy_c = ch.only(j, dyc).astype(cdt)  # head j's lanes alone
            dy_n = ch.only(j, dyn).astype(cdt)
            dw_c = _dot(dy_c, xb_c, _NT)
            dw_n = _dot(dy_n, xb_n, _NT)
            dw_x = _dot(dy_n, xb_c, _NT)
            dxdt_c = dxdt_c + _dot(w_c.astype(cdt), dy_c, _TN) \
                + _dot(w_x.astype(cdt), dy_n, _TN)
            dxdt_n = dxdt_n + _dot(w_n.astype(cdt), dy_n, _TN)
            dcb_c = dcb_c + dw_c * e_c
            dcb_n = dcb_n + jnp.where(ch.own, dw_n * e_n, 0.0)
            dcb_x = dcb_x + jnp.where(ch.earlier, dw_x * e_n, 0.0)
            ds_c, ds_n, ds_x = dw_c * w_c, dw_n * w_n, dw_x * w_x
            d_end = jnp.sum(ch.head_sum(j, d_dec), axis=0, keepdims=True) + \
                jnp.sum(jnp.sum(jnp.where(ch.row_head == j,
                                          carried, 0.0), axis=1, keepdims=True),
                        axis=0, keepdims=True)
            cols[RUN_C, r] = (jnp.sum(ds_c, axis=1, keepdims=True)
                              + ch.head_sum(j, t_run_c - d_dec)
                              + jnp.where(last, d_end, 0.0))
            cols[RUN_N, r] = jnp.sum(ds_n, axis=1, keepdims=True)
            cols[PATH, r] = jnp.sum(ds_x, axis=1, keepdims=True) \
                + ch.head_sum(j, t_path)
            rows[RUN_C, r] = -jnp.sum(ds_c + ds_x, axis=0, keepdims=True)
            rows[RUN_N, r] = -jnp.sum(ds_n, axis=0, keepdims=True)
        for j, r in enumerate(heads):
            cols[DT_C, r] = ch.head_sum(j, dxdt_c * xc)
            cols[DT_N, r] = ch.head_sum(j, dxdt_n * xn)
        dxc_ref[:, lanes] = (dxdt_c * dtc).astype(dxc_ref.dtype)
        dxn_ref[:, lanes] = (dxdt_n * dtn).astype(dxn_ref.dtype)
    dcb_c, dcb_n, dcb_x = (a.astype(cdt) for a in (dcb_c, dcb_n, dcb_x))
    dcc_ref[...] = (dc_c + _dot(dcb_c, bc)).astype(dcc_ref.dtype)
    dbc_ref[...] = (db_c + _dot(dcb_c, cc, _TN) + _dot(dcb_x, cn, _TN)
                    ).astype(dbc_ref.dtype)
    dcn_ref[...] = (dc_n + _dot(dcb_n, bn) + _dot(dcb_x, bc)).astype(dcn_ref.dtype)
    dbn_ref[...] = _dot(dcb_n, cn, _TN).astype(dbn_ref.dtype)
    k = KINDS * geo.heads
    lane = lax.broadcasted_iota(jnp.int32, (q, k), 1)
    sub = lax.broadcasted_iota(jnp.int32, (k, q), 0)
    by_t = jnp.zeros((q, k), F32)
    for (kind, r), c in cols.items():
        by_t = jnp.where(lane == ch.index(kind, r), c, by_t)
    by_s = jnp.zeros((k, q), F32)
    for (kind, r), c in rows.items():
        by_s = jnp.where(sub == ch.index(kind, r), c, by_s)
    dv_ref[...] = by_t.T + by_s


def _specs(geo: Geometry, q: int, n: int, chunks: int, reverse: bool):
    """BlockSpecs of x, B or C, the vector rows and a chunk's state, for
    the grid (row, group, z); z runs the chunks backwards if `reverse`."""
    z_of = (lambda z: chunks - 1 - z) if reverse else (lambda z: z)
    width = geo.heads * geo.head_dim
    x = pl.BlockSpec((None, q, width), lambda b, g, z: (b, z_of(z), g))
    bc = pl.BlockSpec((None, q, n), lambda b, g, z: (b, z_of(z), g))
    v = pl.BlockSpec((None, None, KINDS * geo.heads, q),
                     lambda b, g, z: (b, g, 0, z_of(z)))
    h = pl.BlockSpec((None, None, None, width, n),
                     lambda b, g, z: (b, g, z_of(z), 0, 0))
    return x, bc, v, h


_PARAMS = pltpu.CompilerParams(dimension_semantics=(
    "parallel", "parallel", "arbitrary"))


def _sizes(xn, bn, v, geo):
    """(rows, groups, chunk, chunks, state)."""
    groups, q = v.shape[1], geo.chunk
    return xn.shape[0], groups, q, xn.shape[1] // q, bn.shape[-1] // groups


def _forward(xn, xc, bn, bc, cn, cc, v, geo, interpret):
    b, groups, q, chunks, n = _sizes(xn, bn, v, geo)
    width = geo.heads * geo.head_dim
    x, bcs, vs, hs = _specs(geo, q, n, chunks, False)
    y = jax.ShapeDtypeStruct(xn.shape, F32)
    return pallas_call(
        functools.partial(_fwd_kernel, geo=geo),
        grid=(b, groups, chunks),
        in_specs=[x, x, bcs, bcs, bcs, bcs, vs],
        out_specs=[x, x, hs],
        out_shape=[y, y, jax.ShapeDtypeStruct((b, groups, chunks, width, n), F32)],
        scratch_shapes=[pltpu.VMEM((width, n), F32)],
        compiler_params=_PARAMS, name="ssd_fwd", interpret=interpret,
    )(xn, xc, bn, bc, cn, cc, v)


def _backward(xn, xc, bn, bc, cn, cc, v, h0, dyn, dyc, geo, interpret):
    b, groups, q, chunks, n = _sizes(xn, bn, v, geo)
    width = geo.heads * geo.head_dim
    x, bcs, vs, hs = _specs(geo, q, n, chunks, True)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    return pallas_call(
        functools.partial(_bwd_kernel, geo=geo),
        grid=(b, groups, chunks),
        in_specs=[x, x, bcs, bcs, bcs, bcs, vs, hs, x, x],
        out_specs=[x, x, bcs, bcs, bcs, bcs, vs],
        out_shape=[like(xn), like(xc), like(bn), like(bc), like(cn), like(cc),
                   jax.ShapeDtypeStruct(v.shape, F32)],
        scratch_shapes=[pltpu.VMEM((width, n), F32)],
        compiler_params=_PARAMS, name="ssd_bwd", interpret=interpret,
    )(xn, xc, bn, bc, cn, cc, v, h0, dyn.astype(F32), dyc.astype(F32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _scan(xn, xc, bn, bc, cn, cc, v, geo, interpret):
    return tuple(_forward(xn, xc, bn, bc, cn, cc, v, geo, interpret)[:2])


def _scan_fwd(xn, xc, bn, bc, cn, cc, v, geo, interpret):
    yn, yc, h0 = _forward(xn, xc, bn, bc, cn, cc, v, geo, interpret)
    return (yn, yc), (xn, xc, bn, bc, cn, cc, v, checkpoint_name(h0, ssm.STATES))


def _scan_bwd(geo, interpret, res, dy):
    return tuple(_backward(*res, *dy, geo, interpret))


_scan.defvjp(_scan_fwd, _scan_bwd)


def slab(heads: int, head_dim: int) -> int:
    """Heads a slab: the most of a group's heads, a divisor of them, that
    fit 128 lanes (one, where a head is 128 or wider)."""
    return max(d for d in range(1, heads + 1)
               if heads % d == 0 and (d == 1 or d * head_dim <= LANES))


def doubled_scan(xn, dtn, bn, cn, xc, dtc, bc, cc, A, chunk: int, block: int,
                 dtype=F32, interpret: bool = False):
    """`ops/ssm.py::doubled_scan`'s contract, by the kernels: x[b,L,H,P],
    dt[b,L,H] (after softplus), b, c [b,L,G,N] of each copy, A[H] ->
    (yn, yc) [b,L,H,P] float32. `chunk` holds whole blocks of a power of
    two of positions; rows are padded to whole chunks with dt = 0. Under a
    `mesh_context` the kernels run once per batch shard."""
    if chunk % block or block & (block - 1):
        raise ValueError(f"ssd: chunks of {chunk} do not hold whole blocks "
                         f"of {block}, a power of two")

    def rows(xn, dtn, bn, cn, xc, dtc, bc, cc, A):
        nb, L, H, P = xn.shape
        G, N = bn.shape[2:]
        geo = Geometry(H // G, P, slab(H // G, P), chunk, block,
                       jnp.dtype(dtype))
        z = -(-L // chunk)

        def flat(a):  # [b, L, ...] -> [b, z chunk, prod(...)], zeros after L
            a = jnp.pad(a, [(0, 0), (0, z * chunk - L)] + [(0, 0)] * (a.ndim - 2))
            return a.reshape(nb, z * chunk, -1)

        def by_chunk(a):  # [b, z chunk, H] -> [b, z, chunk, H]
            return a.reshape(nb, z, chunk, H)

        dts = [flat(d).astype(F32) for d in (dtn, dtc)]
        run_n, run_c = (jnp.cumsum(by_chunk(d * A), axis=2) for d in dts)
        path = ssm.path_sums(run_c, run_n, block)
        vec = jnp.stack([by_chunk(d) for d in dts]
                        + [run_n, run_c, path], axis=-1)  # [b, z, q, H, 5]
        vec = vec.reshape(nb, z * chunk, G, H // G, KINDS)
        vec = jnp.transpose(vec, (0, 2, 4, 3, 1)).reshape(nb, G, -1, z * chunk)
        yn, yc = _scan(flat(xn), flat(xc), flat(bn), flat(bc), flat(cn),
                       flat(cc), vec, geo, interpret)
        return tuple(y.reshape(nb, -1, H, P)[:, :L] for y in (yn, yc))

    return shard_over_batch(rows, current_mesh(), xn.shape[0], whole=1)(
        xn, dtn, bn, cn, xc, dtc, bc, cc, A)
