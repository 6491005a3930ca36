"""The state-space scan of the Mamba-2 layer (`models/lm/layers.py::Mamba2`),
in its chunked form, on a row and on the doubled row of training by
diffusion over blocks; and the layer's causal depthwise convolution under
the same two rules. Two paths of `doubled_scan`, by `route`: on a TPU,
where the chunk, the state and a B/C group's heads are whole 128-lane
tiles, the Mosaic kernels of `ops/pallas/ssd.py` (`kernel`: the same
terms, a chunk's matrices only in VMEM, the clean state carried from
chunk to chunk); everywhere else, the CPU and every toy shape, the XLA
chunked form below (`chunked`, the kernels' oracle in the tests).

The recurrence, per head n (state P x N, head n reading group n // (H/G)):

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,      y_t = h_t C_t

from a zero state, computed in chunks of Q positions
(arXiv:2405.21060, "SSD"): inside a chunk, products of C against B scaled
by the decay between the two positions (L_t - L_s, L the running sum of
dt A inside the chunk) and masked to s <= t; across chunks, each chunk's
own contribution to the state at its end, carried to the start of every
later chunk by one matrix of decays between chunk ends (no scan over
positions, and none over chunks); the state at a chunk's start then
reaches each of its positions through C, decayed by exp(L_t).

The doubled row `[xt ; x0]` (`ops/attention.py`'s `block_diffusion` rule
for the state): the clean half x0 runs the recurrence above. A noised
position t of block b (first position bB, blocks of `block` positions)
sees the path `x0[0 .. bB-1] ++ xt[bB .. t]`: its state starts from the
CLEAN state h^clean_{bB-1} and runs over the block's own noised
positions; noised blocks never see each other. `doubled_scan` takes a
chunk of noised positions (chunks hold whole blocks) as three terms:

  - intra-block: noised C against noised B, x of its own block, s <= t,
    decayed by the noised running sum from s to t;
  - cross: noised C against the CLEAN B, x of the chunk's positions before
    its block, decayed along the path: clean from s to bB, noised from bB
    to t (P_t - L^clean_s, P_t = L^clean_{bB-1} + L^noised_t - L^noised_{bB-1});
  - the clean chunk-start state, decayed by exp(P_t).

The clean state at each block's start is never held (1024 blocks of a
4096-position copy at 64 heads x 64 x 128: 2.1 GB a layer); only the clean
chunk-start states are, one a chunk.

Precision: dt, the decays, the running sums and the chunk states in
float32; every product takes operands in `dtype` and accumulates in
float32, but the one that carries chunk states between chunks (float32
and `highest`). Rows are padded to a whole number of chunks with dt = 0
(decay 1, no input), which changes no real position.

`jax.checkpoint` around each scan keeps only its chunk states (`STATES`)
for the backward: the [chunks, heads, Q, Q] decay and score matrices are
computed again there, not kept (a layer's recomputation under
`train.remat` would otherwise hold several of 134 MB each). The kernels'
custom VJP keeps the same states, under the same name.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

F32 = jnp.float32
#: `checkpoint_name` of what a scan keeps for its backward
STATES = "ssm_chunk_states"
_KEEP = jax.checkpoint_policies.save_only_these_names(STATES)
#: lanes the kernel path's chunk, state and group of heads come in whole of
KERNEL_TILE = 128


def route(positions: int, chunk: int, block: int, state: int,
          group_width: int) -> dict:
    """What the layer's scan does with a doubled row of `positions` (2L)
    in chunks of `chunk` holding blocks of `block`, a state of `state` and
    `group_width` = heads of a B/C group x head size: the step-0 `routes`
    record's `ssm` entry, and the one rule `doubled_scan` follows.
    `kernel` (`ops/pallas/ssd.py`) on a TPU where the chunk holds whole
    blocks of a power of two and the chunk, the state and a group's heads
    are whole 128-lane tiles; `chunked` (this module) elsewhere."""
    kernel = (jax.default_backend() == "tpu" and chunk % block == 0
              and block & (block - 1) == 0
              and all(n % KERNEL_TILE == 0 for n in (chunk, state, group_width)))
    return {"path": "kernel" if kernel else "chunked", "chunk": chunk,
            "rule": "block_diffusion", "chunks": 2 * -(-(positions // 2) // chunk)}


def _padded(a, q: int):
    """a[b, s, ...] -> [b, s / q, q, ...], zeros appended to a whole chunk."""
    s = a.shape[1]
    n = -(-s // q)
    a = jnp.pad(a, [(0, 0), (0, n * q - s)] + [(0, 0)] * (a.ndim - 2))
    return a.reshape(a.shape[0], n, q, *a.shape[2:])


def _heads(a, groups: int):
    """[..., H] -> [..., G, H / G]: head n is (n // (H/G), n % (H/G))."""
    return a.reshape(*a.shape[:-1], groups, a.shape[-1] // groups)


def _mixed(c, b, xdt, seg, see, dtype):
    """sum_s see[t, s] exp(seg[t, s]) (c_t . b_s) xdt_s within each chunk.
    c[b,c,t,g,n] b[b,c,s,g,n] xdt[b,c,s,g,r,p] seg[b,c,g,r,t,s] see[t,s]
    -> [b,c,t,g,r,p] float32."""
    cb = jnp.einsum("bctgn,bcsgn->bcgts", c.astype(dtype), b.astype(dtype),
                    preferred_element_type=F32)
    w = cb[:, :, :, None] * jnp.exp(jnp.where(see, seg, -jnp.inf))
    return jnp.einsum("bcgrts,bcsgrp->bctgrp", w.astype(dtype),
                      xdt.astype(dtype), preferred_element_type=F32)


def _chunk_states(x, dt, A, b, q: int, dtype):
    """The chunked inputs and the state at each chunk's START: (xdt
    [b,c,q,g,r,p], running sum L [b,c,q,g,r], B [b,c,q,g,n], h0
    [b,c,g,r,p,n] float32)."""
    g = b.shape[2]
    xdt = _padded(x.reshape(*x.shape[:2], g, -1, x.shape[-1])
                  * _heads(dt, g)[..., None], q)
    run = jnp.cumsum(_padded(_heads(dt * A, g), q), axis=2)  # [b,c,q,g,r]
    bb = _padded(b, q)
    end = run[:, :, -1]  # [b,c,g,r]: the chunk's whole decay
    own = jnp.einsum("bcsgrp,bcsgn->bcgrpn",
                     (xdt * jnp.exp(end[:, :, None] - run)[..., None]).astype(dtype),
                     bb.astype(dtype), preferred_element_type=F32)
    # state at chunk z's start: sum over chunks c < z of own[c] decayed by
    # the chunks strictly between them
    tot = jnp.cumsum(end, axis=1)  # [b,c,g,r], inclusive
    gap = (tot - end)[:, :, None] - tot[:, None]  # [b,z,c,g,r]
    n = end.shape[1]
    earlier = jnp.arange(n)[:, None] > jnp.arange(n)[None, :]
    carry = jnp.exp(jnp.where(earlier[None, :, :, None, None], gap, -jnp.inf))
    h0 = jnp.einsum("bzcgr,bcgrpn->bzgrpn", carry, own,
                    precision=lax.Precision.HIGHEST)
    return xdt, run, bb, checkpoint_name(h0, STATES)


def _causal(x, dt, A, b, c, q: int, dtype):
    """(y [b, s, H, P] float32, the chunked pieces the noised half reads)."""
    s, (nb, _, nh, p) = x.shape[1], x.shape
    xdt, run, bb, h0 = _chunk_states(x, dt, A, b, q, dtype)
    cc = _padded(c, q)
    rt = jnp.moveaxis(run, 2, -1)  # [b,c,g,r,q]
    causal = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    y = _mixed(cc, bb, xdt, rt[..., :, None] - rt[..., None, :], causal, dtype)
    y = y + jnp.einsum("bctgn,bcgrpn->bctgrp", cc.astype(dtype), h0.astype(dtype),
                       preferred_element_type=F32) * jnp.exp(run)[..., None]
    return y.reshape(nb, -1, nh, p)[:, :s], (xdt, run, bb, h0)


def path_sums(run_c, run_n, block: int):
    """P_t = L^clean_{bB-1} + L^noised_t - L^noised_{bB-1}: the running sums
    of the path a noised position t of block b sees, from the running sums
    of both copies inside each chunk, run[b, c, q, ...] (the position
    before a chunk's first reads 0)."""
    start = (jnp.arange(run_c.shape[2]) // block) * block
    pad = [(0, 0), (0, 0), (1, 0)] + [(0, 0)] * (run_c.ndim - 3)
    before = lambda r: jnp.pad(r, pad)[:, :, start]  # noqa: E731
    return before(run_c) + run_n - before(run_n)


def _doubled(xn, dtn, bn, cn, xc, dtc, bc, cc, A, q: int, block: int, dtype):
    L, (nb, _, nh, p) = xn.shape[1], xn.shape
    yc, (xdt_c, run_c, bb_c, h0_c) = _causal(xc, dtc, A, bc, cc, q, dtype)
    g = bn.shape[2]
    xdt_n = _padded(xn.reshape(nb, L, g, -1, p) * _heads(dtn, g)[..., None], q)
    run_n = jnp.cumsum(_padded(_heads(dtn * A, g), q), axis=2)
    bb_n, cc_n = _padded(bn, q), _padded(cn, q)
    path = path_sums(run_c, run_n, block)  # P_t [b,c,q,g,r]
    pt, rn, rc = (jnp.moveaxis(a, 2, -1) for a in (path, run_n, run_c))
    blk = jnp.arange(q) // block
    own_block = (blk[:, None] == blk[None, :]) & (
        jnp.arange(q)[:, None] >= jnp.arange(q)[None, :])
    earlier_block = blk[:, None] > blk[None, :]
    y = _mixed(cc_n, bb_n, xdt_n, rn[..., :, None] - rn[..., None, :],
               own_block, dtype)
    y = y + _mixed(cc_n, bb_c, xdt_c, pt[..., :, None] - rc[..., None, :],
                   earlier_block, dtype)
    y = y + jnp.einsum("bctgn,bcgrpn->bctgrp", cc_n.astype(dtype),
                       h0_c.astype(dtype), preferred_element_type=F32) \
        * jnp.exp(path)[..., None]
    return y.reshape(nb, -1, nh, p)[:, :L], yc


def doubled_scan(xn, dtn, bn, cn, xc, dtc, bc, cc, A, chunk: int, block: int,
                 dtype=F32):
    """The noised half (xn, dtn, bn, cn) and the clean half (xc, ...) of a
    doubled row, each x[b,L,H,P], dt[b,L,H] (after softplus), b, c
    [b,L,G,N], with A[H] (negative) -> (yn, yc) [b,L,H,P] float32, by the
    module's docstring's rule: yc is the recurrence over the clean half
    from a zero state. `chunk` holds whole blocks."""
    if chunk % block:
        raise ValueError(f"ssm: chunks of {chunk} do not hold whole blocks "
                         f"of {block}")
    heads, groups = xn.shape[2], bn.shape[2]
    if route(2 * xn.shape[1], chunk, block, bn.shape[3],
             heads // groups * xn.shape[3])["path"] == "kernel":
        from .pallas import ssd  # only a scan on the chip reaches it
        return ssd.doubled_scan(xn, dtn, bn, cn, xc, dtc, bc, cc, A, chunk,
                                block, dtype)
    return jax.checkpoint(
        lambda *a: _doubled(*a, chunk, block, dtype), policy=_KEEP)(
            xn, dtn, bn, cn, xc, dtc, bc, cc, A)


def _shifted(x, k: int):
    """x[b, s, ...] moved k positions later, zeros in front."""
    return jnp.pad(x, [(0, 0), (k, 0)] + [(0, 0)] * (x.ndim - 2))[:, :x.shape[1]]


def causal_conv(x, w, bias):
    """x[b, s, c], w[K, c], bias[c] -> y[b, s, c] float32: y_p = bias +
    sum_j w_j x_{p - K + 1 + j}, zeros before the row."""
    K = w.shape[0]
    return bias + sum(w[K - 1 - k] * _shifted(x, k) for k in range(K))


def doubled_conv(xn, xc, w, bias, block: int):
    """The noised half's convolution on a doubled row: position p's window
    reads the noised copy from its block's first position on and the
    CLEAN copy before it (tap k reads p - k, noised iff p mod `block` >= k).
    The clean half's is `causal_conv(xc, ...)`."""
    K = w.shape[0]
    mine = (jnp.arange(xn.shape[1]) % block)[None, :, None]
    return bias + sum(
        w[K - 1 - k] * jnp.where(mine >= k, _shifted(xn, k), _shifted(xc, k))
        for k in range(K))
