"""The `fused` path of `ops/attention.py`: two pairs of Mosaic kernels of
the repo's own on shared tile code (`_dot`, `_lanes`, the chunks of
`COMPUTE_KV` keys, the online softmax's scratch layout). `mla_attn_fwd` /
`mla_attn_bwd`: the latent-attention layer's causal attention, described
first; `bd_attn_fwd` / `bd_attn_bwd`: grouped-query attention under a
mask RULE (`ops/attention.py::Mask`: causal, or the block-diffusion mask
of a doubled row), described at `_rule_fwd_kernel`, and the same bodies
as `swa_attn_fwd` / `swa_attn_bwd` under the sliding window, on grids
that walk only the tiles a window reaches. They stand beside each
other, not one generalised into the other: the latent kernels' score is
the sum of two products with a rotary key shared by all heads and their
mask and skipping are the diagonal's, so a shared body would carry both
sets of operands and both skipping rules through every line.

  o = softmax_k(scale * (qn . kn + qr . kr))[k <= q] . v

Forward (`mla_attn_fwd`), grid (row, head, query block, key block), the
key blocks innermost: per query row a running max and sum (online
softmax) and a float32 accumulator of the output live in VMEM scratch
across the key blocks; a tile's scores and probabilities exist only in
VMEM; key tiles wholly above the diagonal are neither fetched (their
block index is clamped to the last visible one, which Pallas does not
fetch twice) nor computed, tiles wholly below it skip the mask. Out: o in
the compute dtype and the row's logsumexp in float32.

Backward (`mla_attn_bwd`, one kernel, the custom VJP's), grid (row, head,
key block, query block), the query blocks innermost: each tile's
probabilities are recomputed from q, k and the logsumexp, transposed
(keys in sublanes, queries in lanes, so the per-query logsumexp and
`di = sum(o * do)` broadcast as rows); dk and dv accumulate in VMEM over
the query blocks; dq leaves as one float32 part a key block, summed
outside the kernel. Nothing of size S x S is kept or re-materialised.

The rotary key is ONE head's, shared by all: the kernels take it as its
own [row, position, dr] operand (k = [k_nope | k_rope] is never built),
the score is the sum of two products, and its cotangent leaves per head
in float32 and is summed over the heads outside.

Precision, as `ops/attention.py` states it: every product takes operands
in the compute dtype and accumulates in float32; the scale multiplies
float32 scores; mask, max, exp, sum, logsumexp in float32; the
probabilities (and, backward, their cotangents) are cast to the compute
dtype only as operands of the next product.

Why not the kernels jax ships (`pallas.ops.tpu.splash_attention`; read on
a v5e at the cell's layer, `tools/perf_probe.py --only attn`, PR 32: 7.2
ms forward, 17.8 forward + backward, against the XLA blocks' 18.6 and
56.9): their custom calls carry a `kernel_metadata` attribute that the
compiled text prints over three lines, so the benchmark's scope join
(`benchmark/harness/scope_share.py`, one instruction a line) does not
find their `op_name` and `mla_device_pct.lm_train` loses them; their
forward multiplies float32 probabilities by v cast up to float32, not
the stated product; and they need the rotary key broadcast over the
heads (100 MB a layer).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.pallas import tpu as pltpu

from . import pallas_call, pl
from ...parallel.spatial import current_mesh, shard_over_batch
from ..attention import _NEG, RESIDUALS, Mask

F32 = jnp.float32
LANES = 128
SUBLANES = 8
#: Keys of a key block that one pair of products takes at a time: what
#: bounds a tile's float32 scores in VMEM (512 x 512 x 4 B = 1 MB each of
#: scores, probabilities and, backward, their two cotangents).
COMPUTE_KV = 512
_NT = (((1,), (1,)), ((), ()))  # a[m, k] . b[n, k] -> [m, n]


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, preferred_element_type=F32)


def _lanes(x, width: int):
    """x[r, 128], every lane alike -> [r, width]."""
    return jnp.tile(x, (1, width // LANES))


def _tiles(q0, k0, bq: int, bkv: int, tile):
    """Run `tile(keys, first_key, masked)` for every chunk of `COMPUTE_KV`
    keys (a slice of the key block at k0, and the chunk's first position)
    that the query block at q0 sees: not at all where the chunk lies above
    the diagonal, without the mask where wholly below it."""
    compute = min(bkv, COMPUTE_KV)
    for c in range(bkv // compute):
        c0 = k0 + c * compute
        keys = pl.ds(c * compute, compute)
        visible = c0 <= q0 + (bq - 1)
        diagonal = c0 + (compute - 1) > q0
        pl.when(visible & diagonal)(functools.partial(tile, keys, c0, True))
        pl.when(visible & jnp.logical_not(diagonal))(
            functools.partial(tile, keys, c0, False))


def _fwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, scale: float, bq: int, bkv: int):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(keys, first_key, masked):
        s = (_dot(qn_ref[...], kn_ref[keys, :], _NT)
             + _dot(qr_ref[...], kr_ref[keys, :], _NT)) * scale
        if masked:
            qpos = i * bq + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = first_key + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, _NEG)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, s.shape[1]))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_next
        acc_ref[...] = (acc_ref[...] * _lanes(alpha, acc_ref.shape[1])
                        + _dot(p.astype(v_ref.dtype), v_ref[keys, :]))

    _tiles(i * bq, j * bkv, bq, bkv, tile)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] * _lanes(1.0 / l, acc_ref.shape[1])
                      ).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def _bwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                di_ref, dqn_ref, dqr_ref, dkn_ref, dkr_ref, dv_ref,
                dkn_acc, dkr_acc, dv_acc, *, scale: float, bq: int, bkv: int):
    j, i = pl.program_id(2), pl.program_id(3)
    dt = qn_ref.dtype

    @pl.when(i == 0)
    def _():
        dkn_acc[...] = jnp.zeros_like(dkn_acc)
        dkr_acc[...] = jnp.zeros_like(dkr_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    dqn_ref[...] = jnp.zeros_like(dqn_ref)  # this key block's part of dq
    dqr_ref[...] = jnp.zeros_like(dqr_ref)

    def tile(keys, first_key, masked):
        st = (_dot(kn_ref[keys, :], qn_ref[...], _NT)
              + _dot(kr_ref[keys, :], qr_ref[...], _NT)) * scale
        if masked:
            kpos = first_key + lax.broadcasted_iota(jnp.int32, st.shape, 0)
            qpos = i * bq + lax.broadcasted_iota(jnp.int32, st.shape, 1)
            st = jnp.where(qpos >= kpos, st, _NEG)
        pt = jnp.exp(st - lse_ref[:1, :])
        do = do_ref[...]
        dv_acc[keys, :] += _dot(pt.astype(dt), do)
        dst = (_dot(v_ref[keys, :], do, _NT) - di_ref[:1, :]) * pt * scale
        ds_k = dst.astype(dt)
        dkn_acc[keys, :] += _dot(ds_k, qn_ref[...])
        dkr_acc[keys, :] += _dot(ds_k, qr_ref[...])
        ds_q = dst.T.astype(dt)
        dqn_ref[...] += _dot(ds_q, kn_ref[keys, :])
        dqr_ref[...] += _dot(ds_q, kr_ref[keys, :])

    _tiles(i * bq, j * bkv, bq, bkv, tile)

    @pl.when(i == pl.num_programs(3) - 1)
    def _():
        dkn_ref[...] = dkn_acc[...].astype(dkn_ref.dtype)
        dkr_ref[...] = dkr_acc[...]
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _specs(bq: int, bkv: int, dims, q_of, k_of):
    """BlockSpecs of (qn, qr, kn, kr, v) [row, head, position, d] (kr
    without heads) for a grid whose ids `q_of` / `k_of` turn into the
    query / key block to fetch."""
    dn, dr, dv = dims

    def q(d):
        return pl.BlockSpec((None, None, bq, d),
                            lambda b, h, x, y: (b, h, q_of(x, y), 0))

    def k(d):
        return pl.BlockSpec((None, None, bkv, d),
                            lambda b, h, x, y: (b, h, k_of(x, y), 0))

    kr = pl.BlockSpec((None, bkv, dr), lambda b, h, x, y: (b, k_of(x, y), 0))
    return q, k, [q(dn), q(dr), k(dn), kr, k(dv)]


def _forward(qn, qr, kn, kr, v, scale, bq, bkv, interpret):
    b, h, s, dn = qn.shape
    dr, dv = qr.shape[-1], v.shape[-1]
    # the last key block a query block sees: later grid steps fetch nothing
    q, _, ins = _specs(bq, bkv, (dn, dr, dv), lambda i, j: i,
                       lambda i, j: jnp.minimum(j, (i * bq + bq - 1) // bkv))
    o, lse = pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=bq, bkv=bkv),
        grid=(b, h, s // bq, s // bkv),
        in_specs=ins,
        out_specs=[q(dv), q(LANES)],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, dv), qn.dtype),
                   jax.ShapeDtypeStruct((b, h, s, LANES), F32)],
        scratch_shapes=[pltpu.VMEM((bq, LANES), F32),
                        pltpu.VMEM((bq, LANES), F32),
                        pltpu.VMEM((bq, dv), F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        name="mla_attn_fwd", interpret=interpret,
    )(qn, qr, kn, kr, v)
    return o, lse[..., 0]


def _backward(qn, qr, kn, kr, v, o, lse, do, scale, bq, bkv, interpret):
    b, h, s, dn = qn.shape
    dr, dv = qr.shape[-1], v.shape[-1]
    nkv = s // bkv
    do = do.astype(qn.dtype)
    di = jnp.sum(o.astype(F32) * do.astype(F32), axis=-1)
    # per-query rows for the transposed tiles, sublane-expanded as Mosaic
    # wants a block's second-minor dimension
    rows = [jnp.broadcast_to(x[:, :, None, :], (b, h, SUBLANES, s))
            for x in (lse, di)]
    # the first query block that sees a key block: earlier steps fetch it
    q, k, ins = _specs(bq, bkv, (dn, dr, dv),
                       lambda j, i: jnp.maximum(i, (j * bkv) // bq),
                       lambda j, i: j)
    row = pl.BlockSpec(
        (None, None, SUBLANES, bq),
        lambda b, h, j, i: (b, h, 0, jnp.maximum(i, (j * bkv) // bq)))

    def part(d):  # dq's part of key block j, every query block written
        return pl.BlockSpec((None, None, None, bq, d),
                            lambda b, h, j, i: (b, h, j, i, 0))

    dqn, dqr, dkn, dkr, dvv = pallas_call(
        functools.partial(_bwd_kernel, scale=scale, bq=bq, bkv=bkv),
        grid=(b, h, nkv, s // bq),
        in_specs=ins + [q(dv), row, row],
        out_specs=[part(dn), part(dr), k(dn), k(dr), k(dv)],
        out_shape=[jax.ShapeDtypeStruct((b, h, nkv, s, dn), F32),
                   jax.ShapeDtypeStruct((b, h, nkv, s, dr), F32),
                   jax.ShapeDtypeStruct(kn.shape, kn.dtype),
                   jax.ShapeDtypeStruct((b, h, s, dr), F32),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bkv, dn), F32), pltpu.VMEM((bkv, dr), F32),
                        pltpu.VMEM((bkv, dv), F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary")),
        name="mla_attn_bwd", interpret=interpret,
    )(qn, qr, kn, kr, v, do, *rows)
    return (jnp.sum(dqn, axis=2).astype(qn.dtype),
            jnp.sum(dqr, axis=2).astype(qr.dtype), dkn,
            jnp.sum(dkr, axis=1).astype(kr.dtype), dvv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _attend(qn, qr, kn, kr, v, scale, bq, bkv, interpret):
    return _forward(qn, qr, kn, kr, v, scale, bq, bkv, interpret)[0]


def _attend_fwd(qn, qr, kn, kr, v, scale, bq, bkv, interpret):
    o, lse = _forward(qn, qr, kn, kr, v, scale, bq, bkv, interpret)
    o, lse = checkpoint_name(o, RESIDUALS), checkpoint_name(lse, RESIDUALS)
    return o, (qn, qr, kn, kr, v, o, lse)


def _attend_bwd(scale, bq, bkv, interpret, res, do):
    return _backward(*res, do, scale, bq, bkv, interpret)


_attend.defvjp(_attend_fwd, _attend_bwd)


def fused_causal_attention(qn, qr, kn, kr, v, scale: float, block_q: int,
                           block_kv: int, interpret: bool = False,
                           head_major: bool = False):
    """qn[b,s,h,dn] qr[b,s,h,dr] kn[b,s,h,dn] kr[b,s,dr] v[b,s,h,dv], one
    dtype -> [b,s,h,dv] in it. `head_major`: the four operands with heads
    come as the kernels take them, [b,h,s,d] (from `qk_prep` and the
    products that write that layout), and no copy of them is made here;
    their cotangents go back head-major too. `block_q` and `block_kv` are
    multiples of 128 that divide s. Under a `mesh_context` the kernels run
    once per batch shard."""
    def rows(qn, qr, kn, kr, v):
        if not head_major:
            qn, qr, kn, v = (jnp.swapaxes(a, 1, 2) for a in (qn, qr, kn, v))
        o = _attend(qn, qr, kn, kr, v, scale, block_q, block_kv, interpret)
        return jnp.swapaxes(o, 1, 2)

    return shard_over_batch(rows, current_mesh(), qn.shape[0])(
        qn, qr, kn, kr, v)


# ---- grouped-query attention under a mask rule ----------------------------
#
# Forward (`bd_attn_fwd`), grid (row, query head, query tile, key tile), the
# key tiles innermost, online softmax as above. Query head n reads
# key/value head n // r through the BlockSpec's index. Which key tiles a
# query tile visits is the RULE's (`Mask.key_tile_ranges`: at most two
# ranges; under `block_diffusion` a noised query tile visits the ONE noised
# key-tile range that covers its own blocks and the clean key tiles that
# start before its end, a clean query tile the clean key tiles up to its
# own, no query tile a noised key tile of other blocks): a hidden tile's
# block index is clamped to a visited one, which Pallas does not fetch
# twice, and its chunks are not computed (`Mask.tile_visible`); a chunk the
# rule shows whole skips the mask (`Mask.tile_wholly_visible`); elsewhere
# the mask is position arithmetic inside the tile (`Mask.visible`).
#
# Backward (`bd_attn_bwd`), grid (row, key/value head, key tile, r x query
# tiles), innermost the r query heads that read this key/value head times
# the query tiles: a key/value tile is fetched ONCE for all of them, and
# dk and dv accumulate in VMEM over heads and query tiles alike; which
# query tiles see a key tile is `Mask.query_tile_ranges`; dq leaves as one
# float32 part a key tile, summed outside.
#
# Under `window` the same bodies run as `swa_attn_fwd` / `swa_attn_bwd` on
# grids whose innermost axis covers only what the window reaches: the
# forward the key tiles from a query tile's first (`_window_reach`: 2 of
# 2048 for a query tile of 512 under W = 2048), the backward the query
# tiles from a key tile's first (8 of 512); steps past the last do
# nothing. dq's part of a key tile holds only those query tiles, so the
# parts are as many rows as the reach, not the row, and are added into
# place outside (`_sum_window_parts`).


def _clamp2(x, lo1, hi1, lo2, hi2):
    """x held inside [lo1, hi1] or [lo2, hi2] (lo > hi: empty; the first
    range lies before the second): inside a range x itself, before one
    its start, after the last its end. Consecutive grid steps that map to
    one block fetch it once."""
    first = (hi1 >= lo1) & ((x <= hi1) | (hi2 < lo2))
    return jnp.where(first, jnp.clip(x, lo1, hi1), jnp.clip(x, lo2, hi2))


def _rule_chunks(mask: Mask, q0, k0, bq: int, bkv: int, tile):
    """Run `tile(keys, first_key, masked)` for every chunk of `COMPUTE_KV`
    keys of the key tile at k0 that the query tile at q0 sees under `mask`:
    not at all where the rule hides the chunk, without the mask where it
    shows all of it."""
    compute = min(bkv, COMPUTE_KV)
    for c in range(bkv // compute):
        c0 = k0 + c * compute
        keys = pl.ds(c * compute, compute)
        seen = mask.tile_visible(q0, q0 + bq, c0, c0 + compute)
        whole = mask.tile_wholly_visible(q0, q0 + bq, c0, c0 + compute)
        pl.when(seen & jnp.logical_not(whole))(
            functools.partial(tile, keys, c0, True))
        pl.when(seen & whole)(functools.partial(tile, keys, c0, False))


def _rule_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                     acc_ref, *, scale: float, bq: int, bkv: int, mask: Mask,
                     key_tile=None):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(keys, first_key, masked):
        s = _dot(q_ref[...], k_ref[keys, :], _NT) * scale
        if masked:
            qpos = i * bq + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = first_key + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(mask.visible(qpos, kpos), s, _NEG)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, s.shape[1]))
        if masked:
            # a query may see no key of this chunk at all (its block ends
            # before the chunk's first key): exp(_NEG - _NEG) is not nought
            p = jnp.where(s > 0.5 * _NEG, p, 0.0)
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_next
        acc_ref[...] = (acc_ref[...] * _lanes(alpha, acc_ref.shape[1])
                        + _dot(p.astype(v_ref.dtype), v_ref[keys, :]))

    _rule_chunks(mask, i * bq, (j if key_tile is None else key_tile(i, j)) * bkv,
                 bq, bkv, tile)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] * _lanes(1.0 / l, acc_ref.shape[1])
                      ).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def _rule_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
                     dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float, bq: int,
                     bkv: int, mask: Mask, positions: int, query_tiles=None,
                     reach: int = 0):
    j, x = pl.program_id(2), pl.program_id(3)
    nq = positions // bq
    # the query tile; under `window` the `reach` tiles from the key tile's
    # first (`query_tiles(j)`: its first and last), those past the last idle
    i = x % nq if query_tiles is None else query_tiles(j)[0] + x % reach
    dt = q_ref.dtype

    @pl.when(x == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    dq_ref[...] = jnp.zeros_like(dq_ref)  # this key tile's part of dq

    def tile(keys, first_key, masked):
        st = _dot(k_ref[keys, :], q_ref[...], _NT) * scale
        pt = jnp.exp(st - lse_ref[:1, :])
        if masked:
            kpos = first_key + lax.broadcasted_iota(jnp.int32, st.shape, 0)
            qpos = i * bq + lax.broadcasted_iota(jnp.int32, st.shape, 1)
            pt = jnp.where(mask.visible(qpos, kpos), pt, 0.0)
        do = do_ref[...]
        dv_acc[keys, :] += _dot(pt.astype(dt), do)
        dst = (_dot(v_ref[keys, :], do, _NT) - di_ref[:1, :]) * pt * scale
        dk_acc[keys, :] += _dot(dst.astype(dt), q_ref[...])
        dq_ref[...] += _dot(dst.T.astype(dt), k_ref[keys, :])

    if query_tiles is None:
        _rule_chunks(mask, i * bq, j * bkv, bq, bkv, tile)
    else:
        pl.when(i <= query_tiles(j)[1])(functools.partial(
            _rule_chunks, mask, i * bq, j * bkv, bq, bkv, tile))

    @pl.when(x == pl.num_programs(3) - 1)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _window_reach(mask: Mask, bq: int, bkv: int, s: int):
    """(key tiles a query tile visits, query tiles a key tile is visited
    by), the most over the row, under `window`: the extents of the grids'
    innermost axes, where the other rules walk the whole row."""
    keys = max((q1 - 1) // bkv - k0 // bkv + 1 for k0, q1 in (
        mask.key_ranges(i, i + bq)[0] for i in range(0, s, bq)))
    # a key tile at j is seen by the queries j .. j + bkv - 1 + W - 1
    queries = max(min(s - 1, j + bkv + mask.window - 2) // bq - j // bq + 1
                  for j in range(0, s, bkv))
    return keys, queries


def _rule_forward(q, k, v, scale, bq, bkv, mask, interpret):
    b, h, s, d = q.shape
    r = h // k.shape[1]
    ranges = lambda i: mask.key_tile_ranges(i * bq, bq, bkv)  # noqa: E731
    if mask.rule == "window":  # the key tiles from the query tile's first
        nk, name = _window_reach(mask, bq, bkv, s)[0], "swa_attn_fwd"
        key_tile = lambda i, j: ranges(i)[0] + j  # noqa: E731
        k_of = lambda i, j: jnp.minimum(key_tile(i, j), ranges(i)[1])  # noqa: E731
    else:
        nk, name, key_tile = s // bkv, "bd_attn_fwd", None
        k_of = lambda i, j: _clamp2(j, *ranges(i))  # noqa: E731
    qs = lambda w: pl.BlockSpec((None, None, bq, w),  # noqa: E731
                                lambda b, n, i, j: (b, n, i, 0))
    ks = pl.BlockSpec((None, None, bkv, d),
                      lambda b, n, i, j: (b, n // r, k_of(i, j), 0))
    o, lse = pallas_call(
        functools.partial(_rule_fwd_kernel, scale=scale, bq=bq, bkv=bkv,
                          mask=mask, key_tile=key_tile),
        grid=(b, h, s // bq, nk),
        in_specs=[qs(d), ks, ks],
        out_specs=[qs(d), qs(LANES)],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, s, LANES), F32)],
        scratch_shapes=[pltpu.VMEM((bq, LANES), F32),
                        pltpu.VMEM((bq, LANES), F32),
                        pltpu.VMEM((bq, d), F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        name=name, interpret=interpret,
    )(q, k, v)
    return o, lse[..., 0]


def _rule_backward(q, k, v, o, lse, do, scale, bq, bkv, mask, interpret):
    b, h, s, d = q.shape
    g = k.shape[1]
    r, nq, nkv = h // g, s // bq, s // bkv
    do = do.astype(q.dtype)
    di = jnp.sum(o.astype(F32) * do.astype(F32), axis=-1)
    rows = [jnp.broadcast_to(a[:, :, None, :], (b, h, SUBLANES, s))
            for a in (lse, di)]
    ranges = lambda j: mask.query_tile_ranges(j * bkv, bkv, bq, s)  # noqa: E731
    if mask.rule == "window":
        # the query tiles from the key tile's first; dq's part of key tile
        # j holds those `nx` tiles alone, from position j * bkv
        nx, name = _window_reach(mask, bq, bkv, s)[1], "swa_attn_bwd"
        query_tiles = lambda j: ranges(j)[:2]  # noqa: E731
        q_of = lambda j, x: jnp.minimum(  # noqa: E731
            ranges(j)[0] + x % nx, ranges(j)[1])
    else:
        nx, name, query_tiles = nq, "bd_attn_bwd", None
        q_of = lambda j, x: _clamp2(x % nq, *ranges(j))  # noqa: E731
    # innermost grid id x = (query head of the group, query tile)
    head = lambda n, x: n * r + x // nx  # noqa: E731
    qs = pl.BlockSpec((None, None, bq, d),
                      lambda b, n, j, x: (b, head(n, x), q_of(j, x), 0))
    ks = pl.BlockSpec((None, None, bkv, d), lambda b, n, j, x: (b, n, j, 0))
    row = pl.BlockSpec((None, None, SUBLANES, bq),
                       lambda b, n, j, x: (b, head(n, x), 0, q_of(j, x)))
    part = pl.BlockSpec((None, None, None, bq, d),
                        lambda b, n, j, x: (b, head(n, x), j, x % nx, 0))
    dq, dk, dv = pallas_call(
        functools.partial(_rule_bwd_kernel, scale=scale, bq=bq, bkv=bkv,
                          mask=mask, positions=s, query_tiles=query_tiles,
                          reach=nx),
        grid=(b, g, nkv, r * nx),
        in_specs=[qs, ks, ks, qs, row, row],
        out_specs=[part, ks, ks],
        out_shape=[jax.ShapeDtypeStruct((b, h, nkv, nx * bq, d), F32),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bkv, d), F32), pltpu.VMEM((bkv, d), F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary")),
        name=name, interpret=interpret,
    )(q, k, v, do, *rows)
    if query_tiles is None:
        return jnp.sum(dq, axis=2).astype(q.dtype), dk, dv
    return _sum_window_parts(dq, bkv).astype(q.dtype), dk, dv


def _sum_window_parts(parts, bkv: int):
    """dq[b, h, s, d] from parts[b, h, nkv, n, d]: part j's n rows hold the
    queries from j * bkv on (past the row's end: nought). Each part cut
    into key tiles, the t-th of part j lands on key tile j + t."""
    b, h, nkv, n, d = parts.shape
    m = -(-n // bkv)
    parts = jnp.pad(parts, ((0, 0),) * 3 + ((0, m * bkv - n), (0, 0)))
    parts = parts.reshape(b, h, nkv, m, bkv, d)
    dq = sum(jnp.pad(parts[:, :, :nkv - t, t], ((0, 0), (0, 0), (t, 0),
                                                 (0, 0), (0, 0)))
             for t in range(min(m, nkv)))
    return dq.reshape(b, h, nkv * bkv, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _rule_attend(q, k, v, scale, bq, bkv, mask, interpret):
    return _rule_forward(q, k, v, scale, bq, bkv, mask, interpret)[0]


def _rule_attend_fwd(q, k, v, scale, bq, bkv, mask, interpret):
    o, lse = _rule_forward(q, k, v, scale, bq, bkv, mask, interpret)
    o, lse = checkpoint_name(o, RESIDUALS), checkpoint_name(lse, RESIDUALS)
    return o, (q, k, v, o, lse)


def _rule_attend_bwd(scale, bq, bkv, mask, interpret, res, do):
    return _rule_backward(*res, do, scale, bq, bkv, mask, interpret)


_rule_attend.defvjp(_rule_attend_fwd, _rule_attend_bwd)


def fused_grouped_attention(q, k, v, scale: float, block_q: int,
                            block_kv: int, mask: Mask,
                            interpret: bool = False, head_major: bool = False):
    """q[b,s,h,d] k, v[b,s,g,d] (g divides h), one dtype -> [b,s,h,d] in
    it, under `mask`. `head_major`: q[b,h,s,d] k, v[b,g,s,d], as the
    kernels take them, no copy made here (`fused_causal_attention`).
    `block_q` and `block_kv` are multiples of 128 that divide s (and a copy
    of a doubled row); under `block_diffusion` the rule's blocks are a
    power of two of positions that tile `block_q`. Under a `mesh_context`
    the kernels run once per batch shard."""
    def rows(q, k, v):
        if not head_major:
            q, k, v = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
        o = _rule_attend(q, k, v, scale, block_q, block_kv, mask, interpret)
        return jnp.swapaxes(o, 1, 2)

    return shard_over_batch(rows, current_mesh(), q.shape[0])(q, k, v)
