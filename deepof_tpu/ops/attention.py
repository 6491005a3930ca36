"""Attention of the language-model layers (`models/lm/layers.py`): two
layers (`MLA`, `GQA`), ONE route, two paths of the same mathematics, and
the mask given as a rule over positions (`Mask`).

  latent (`causal_attention`):   softmax_k(scale * (qn . kn + qr . kr))[visible] . v
      with one rotary key shared by all heads, always under the causal rule;
  grouped (`grouped_attention`): softmax_k(scale * q_n . k_[n / r])[visible] . v_[n / r]
      query head n reading key/value head n // r (r = heads / groups),
      under either rule;

with operands in the compute dtype, float32 accumulation in both products,
mask / max / exp / sum in float32, and the probabilities cast to the
compute dtype only as the operand of the second product. Every visible key
is attended; no soft cap, no approximate exponential.

The rules (`Mask.visible`). `causal`: key k <= query q. `block_diffusion`
(training by diffusion over blocks, arXiv:2503.09573): the row is doubled,
a noised copy [0, L) beside the clean one [L, 2L), in blocks of B
positions, b(p) = p // B inside a copy; a noised query sees the noised
keys of its own block and the clean keys of EARLIER blocks; a clean query
sees the clean keys of its own and earlier blocks; no query sees a noised
key of another block. `window` (sliding-window layers, W = `window`): key
k <= query q and q - k < W, the W latest keys. Every query sees itself,
so no row is empty.

  - `fused` (`ops/pallas/attention.py`): on a TPU, where the kernel's
    blocks divide the row. Queries and keys both blocked, a running max
    and sum per query row, the scores of a (query block, key block) tile
    only ever in VMEM, key tiles the rule hides skipped, and a custom VJP
    that recomputes tiles from q, k, v, the output and the row's
    logsumexp.
  - `xla_blocks`: everywhere else (the CPU, rows the kernel's blocks do
    not divide), and the fused path's oracle in the tests. Blocks of
    queries, each against only the key ranges its rule can see
    (`Mask.key_ranges`); the scores of one block exist at a time in HBM
    and the backward recomputes them.

`attention_route` decides from what the code can observe (backend, shape,
rule) and is what the trainer writes into its step-0 info record, with the
mask's name and, as static counts, the (query block, key block) tiles the
rule lets the path visit over all of them; there is no option that picks
a path.

The route's SECOND decision, `prep`: how the layers get from a
projection's float32 output to the operands above (per-head norm where the
family has one, rotary positions, the cast).

  - `fused` (`ops/pallas/qk_prep.py`, kernels `qk_prep_fwd` /
    `qk_prep_bwd`): wherever the scores are fused and the rotated width is
    one the pass's slabs hold. One pass reads the product's output and
    writes the operand head-major, [b, h, s, d], which the fused attention
    takes as it is (`head_major`); operands with no arithmetic before the
    cast leave their products in that layout and dtype.
  - `xla`: everywhere else; the layers' own elementwise code
    (`models/lm/layers.py::rope`, `rope_halves`, `RMSNorm`) on [b, s, h, d],
    and the fused path's oracle in the tests.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

F32 = jnp.float32
_NEG = -1e30  # masked score: finite, so a fully masked row cannot give NaN
#: The fused kernels' blocks of queries and keys are multiples of the 128
#: lanes of a register.
FUSED_BLOCK_MULTIPLE = 128
#: Keys a grid step of the fused kernels holds, where that divides the row
#: (else the query block, which does). Read on a v5e at the language-model
#: cell's layer, queries in blocks of 512 (`tools/perf_probe.py --only
#: attn`, PR 32; forward, forward + backward): 512 -> 6.69, 19.47 ms; 1024
#: -> 6.37, 16.76; 2048 -> 5.90, 14.87 (the backward's dq leaves as one
#: float32 part a key block: fewer blocks, fewer parts). In the cell's
#: step 2048 read 2.7% more tokens a second than 1024.
FUSED_BLOCK_KV = 2048
#: Positions a grid step of the fused preparation pass holds: the largest
#: of these that divides the row (a fused row is a multiple of 128).
PREP_BLOCKS_S = (512, 256, 128)
#: `jax.ad_checkpoint.checkpoint_name` of the fused forward's output and
#: logsumexp (64 MB + 1 MB a layer at the cell's size): a layer recomputed
#: in the backward (`train.remat`) whose policy keeps them does not run the
#: forward kernel a second time. The XLA blocks name nothing.
RESIDUALS = "mla_attention_residuals"


@dataclasses.dataclass(frozen=True)
class Mask:
    """Which keys a query sees, by position arithmetic (the module's
    docstring has the rules). Positions are indices into the row the
    layers run over: for `block_diffusion` the doubled row of 2 * `half`.
    Hashable: a static argument of the layers and of the kernels."""

    rule: str = "causal"  # | "block_diffusion" | "window"
    block: int = 0  # B: positions a block
    half: int = 0  # L: positions a copy of the row
    window: int = 0  # W: keys a query of a window layer sees

    def __post_init__(self):
        if self.rule not in ("causal", "block_diffusion", "window"):
            raise ValueError(f"attention: no mask rule {self.rule!r}")
        if self.rule == "block_diffusion" and (self.block < 1 or self.half < 1):
            raise ValueError("attention: block_diffusion needs block >= 1 "
                             f"and half >= 1, got {self.block}, {self.half}")
        if self.rule == "window" and self.window < 1:
            raise ValueError(f"attention: window needs window >= 1, got {self.window}")

    def _block_of(self, p):
        """Block index of in-copy positions `p` (a shift where B is a power
        of two: the fused kernels' case, whose tiles have no vector divide)."""
        B = self.block
        return p >> (B.bit_length() - 1) if B & (B - 1) == 0 else p // B

    def visible(self, q, k):
        """bool, broadcast over integer position arrays q and k."""
        if self.rule == "causal":
            return q >= k
        if self.rule == "window":
            return (q >= k) & (q - k < self.window)
        qc, kc = q >= self.half, k >= self.half  # in the clean copy?
        qb = self._block_of(jnp.where(qc, q - self.half, q))
        kb = self._block_of(jnp.where(kc, k - self.half, k))
        # logical operations only: Mosaic has no select between masks
        qn = jnp.logical_not(qc)
        return (kc & ((qc & (kb <= qb)) | (qn & (kb < qb)))) \
            | (jnp.logical_not(kc) & qn & (kb == qb))

    # What the fused kernels ask about a (query tile, key tile) pair, on
    # traced scalars; a tile lies in one copy of the row, and under
    # `block_diffusion` a block never straddles a query tile's edge.

    def tile_visible(self, q0, q1, k0, k1):
        """Does any query of [q0, q1) see any key of [k0, k1)?"""
        if self.rule == "causal":
            return k0 <= q1 - 1
        if self.rule == "window":
            return (k0 <= q1 - 1) & (k1 - 1 > q0 - self.window)
        L = self.half
        blocks_meet = (self._block_of(k0) <= self._block_of(q1 - 1)) \
            & (self._block_of(k1 - 1) >= self._block_of(q0))
        return ((k0 >= L) & self.visible(q1 - 1, k0)) \
            | ((k0 < L) & (q0 < L) & blocks_meet)

    def tile_wholly_visible(self, q0, q1, k0, k1):
        """Does every query of [q0, q1) see every key of [k0, k1)? (Sound,
        not complete: where false the kernel applies the mask.)"""
        if self.rule == "causal":
            return q0 >= k1 - 1
        if self.rule == "window":
            return (q0 >= k1 - 1) & (q1 - 1 - k0 < self.window)
        return (k0 >= self.half) & self.visible(q0, k1 - 1)

    def key_tile_ranges(self, q0, bq: int, bkv: int):
        """(lo1, hi1, lo2, hi2): the key tiles of `bkv` a query tile at q0
        can see lie in [lo1, hi1] or [lo2, hi2]; lo > hi: empty."""
        q1 = q0 + bq
        if self.rule == "causal":
            return 0, (q1 - 1) // bkv, 1, 0
        if self.rule == "window":
            return jnp.maximum(q0 - self.window + 1, 0) // bkv, (q1 - 1) // bkv, 1, 0
        L, B = self.half, self.block
        clean = q0 >= L
        lo1 = jnp.where(clean, L, q0) // bkv
        hi1 = (q1 - 1) // bkv
        before = q1 - B  # a noised tile's clean keys: the blocks before its last
        lo2 = jnp.where(clean | (before <= 0), 1, L // bkv)
        hi2 = jnp.where(clean | (before <= 0), 0, (L + before - 1) // bkv)
        return lo1, hi1, lo2, hi2

    def query_tile_ranges(self, k0, bkv: int, bq: int, positions: int):
        """(lo1, hi1, lo2, hi2): the query tiles of `bq` that can see a key
        tile at k0, as `key_tile_ranges`."""
        nq = positions // bq
        if self.rule == "causal":
            return k0 // bq, nq - 1, 1, 0
        if self.rule == "window":  # the last key's W - 1 later queries
            return k0 // bq, jnp.minimum((k0 + bkv + self.window - 2) // bq,
                                         nq - 1), 1, 0
        L, B = self.half, self.block
        clean = k0 >= L
        kc = k0 - L
        # a noised key tile: the noised queries of its own blocks; a clean
        # one: the noised queries of later blocks, and the clean from its own
        lo1 = jnp.where(clean, (kc + B) // bq, k0 // bq)
        hi1 = jnp.where(clean, L // bq - 1, (k0 + bkv - 1) // bq)
        lo2 = jnp.where(clean, (L + kc) // bq, 1)
        hi2 = jnp.where(clean, nq - 1, 0)
        return lo1, hi1, lo2, hi2

    def key_ranges(self, q0: int, q1: int) -> tuple:
        """The key ranges [(k0, k1), ...] outside which no query of
        [q0, q1) sees a key; [q0, q1) lies in one copy of the row."""
        if self.rule == "causal":
            return ((0, q1),)
        if self.rule == "window":
            return ((max(0, q0 - self.window + 1), q1),)
        L, B = self.half, self.block
        if q0 >= L:  # clean queries: the clean blocks up to their own
            return ((L, L + min(L, -(-(q1 - L) // B) * B)),)
        if q1 > L:
            raise ValueError(f"attention: queries {q0}..{q1} straddle the "
                             f"two copies of a row of {L}")
        own = (q0 // B * B, min(L, -(-q1 // B) * B))
        before = (q1 - 1) // B * B  # clean keys of the blocks before the last query's
        return (own, (L, L + before)) if before else (own,)

    def rope_positions(self, positions: int):
        """The rotary position of each index of the row: its index INSIDE
        its copy (0..L-1 in both halves of a doubled row)."""
        p = jnp.arange(positions)
        return p % self.half if self.rule == "block_diffusion" else p

    def tiles(self, positions: int, block_q: int, block_kv: int) -> dict:
        """{"visited", "all"}: (query block, key block) tiles that hold a
        visible (query, key) pair, over all tiles of the row."""
        nq, nk = positions // block_q, positions // block_kv
        seen = 0
        for i in range(nq):
            for k0, k1 in self.key_ranges(i * block_q, (i + 1) * block_q):
                seen += (k1 - 1) // block_kv - k0 // block_kv + 1 if k1 > k0 else 0
        return {"visited": seen, "all": nq * nk}


CAUSAL = Mask()


def attention_route(positions: int, block_q: int, head_dims,
                    mask: Mask = CAUSAL) -> dict:
    """What `causal_attention` / `grouped_attention` do with rows of
    `positions` under `lm.attn_block_q = block_q` at `head_dims` =
    (qk_nope, qk_rope, v) (a grouped layer: (head_dim, 0, head_dim)) and
    `mask`: {"path": "fused", "block_q", "block_kv"} or {"path":
    "xla_blocks", "block_q"}, with "mask" (the rule's name, and its blocks
    where it has them), "tiles" (`Mask.tiles` at the path's blocks; the
    XLA blocks count keys in blocks of `block_q`) and "prep" ({"path":
    "fused", "block_s"} or {"path": "xla"}: the module's docstring). The
    one rule the layers and the trainer's step-0 info record share. Fused:
    on a TPU, blocks of whole 128-lane registers, head sizes the kernels'
    tiles hold (128s; the rotary part 64s); under `block_diffusion` also
    blocks of a power of two of positions that tile a query block, and
    query and key blocks that tile a copy of the row; under `window` key
    blocks of whole query blocks. The pass is fused
    where the scores are and the rotated width (the rotary part, or the
    whole head where there is none) is 64 or a power of two of 128s."""
    bq = min(block_q, mask.half or positions)
    if positions % bq or (mask.half and (mask.half % bq
                                         or positions != 2 * mask.half)):
        raise ValueError(f"lm.attn_block_q={block_q} does not divide "
                         f"the {mask.half or positions} positions of a row")
    dn, dr, dv = head_dims
    named = {"mask": mask.rule if mask.rule == "causal" else
             {"rule": mask.rule, "window": mask.window} if mask.rule == "window"
             else {"rule": mask.rule, "block": mask.block, "half": mask.half}}
    fused = (jax.default_backend() == "tpu"
             and all(n % FUSED_BLOCK_MULTIPLE == 0 for n in (bq, dn, dv))
             and dr % (FUSED_BLOCK_MULTIPLE // 2) == 0)
    if fused and mask.rule == "block_diffusion":
        fused = (mask.block & (mask.block - 1)) == 0 and bq % mask.block == 0
    row = mask.half or positions
    bkv = bq if row % FUSED_BLOCK_KV else FUSED_BLOCK_KV
    if fused and mask.rule == "window":
        fused = bkv % bq == 0  # a key tile's first query starts a query tile
    if fused:
        rotated = dr or dn
        prep = {"path": "xla"} if rotated & (rotated - 1) else {
            "path": "fused",
            "block_s": next(n for n in PREP_BLOCKS_S if positions % n == 0)}
        return {"path": "fused", "block_q": bq, "block_kv": bkv, **named,
                "tiles": mask.tiles(positions, bq, bkv), "prep": prep}
    return {"path": "xla_blocks", "block_q": bq, **named,
            "tiles": mask.tiles(positions, bq, bq), "prep": {"path": "xla"}}


def _attend_block(qn, qr, kn, kr, v, q0: int, scale: float, dtype):
    """Queries q0.. of one block against the keys 0..L that a causal mask
    lets them see. qn[b,q,h,dn] qr[b,q,h,dr] kn[b,L,h,dn] kr[b,L,dr]
    v[b,L,h,dv] -> [b,q,h,dv] float32. The rotary key is one head's,
    shared by all: k = [k_nope | k_rope] is never built."""
    s = (jnp.einsum("bqhd,bkhd->bhqk", qn, kn, preferred_element_type=F32)
         + jnp.einsum("bqhd,bkd->bhqk", qr, kr, preferred_element_type=F32))
    qpos = q0 + jnp.arange(qn.shape[1])[:, None]
    s = jnp.where(qpos >= jnp.arange(kn.shape[1])[None, :], s * scale, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(dtype), v,
                      preferred_element_type=F32)


def xla_blocks_attention(qn, qr, kn, kr, v, scale: float, block_q: int, dtype):
    """The `xla_blocks` path: the masked half above the diagonal blocks is
    never computed, and the backward recomputes a block's scores
    (`jax.checkpoint`) instead of keeping them."""
    s = qn.shape[1]
    block = jax.checkpoint(_attend_block, static_argnums=(5, 6, 7))
    outs = []
    for q0 in range(0, s, block_q):
        hi = q0 + block_q
        outs.append(block(qn[:, q0:hi], qr[:, q0:hi], kn[:, :hi], kr[:, :hi],
                          v[:, :hi], q0, scale, dtype))
    return jnp.concatenate(outs, axis=1)


def causal_attention(qn, qr, kn, kr, v, scale: float, block_q: int, dtype,
                     head_major: bool = False):
    """qn[b,s,h,dn] qr[b,s,h,dr] kn[b,s,h,dn] kr[b,s,dr] v[b,s,h,dv], all in
    `dtype` -> [b,s,h,dv], by the path `attention_route` names: float32
    from the XLA blocks, `dtype` from the fused kernels (the output
    projection casts to it anyway). `head_major`: qn, qr, kn and v come
    [b,h,s,d], from a layer whose route's `prep` is fused (so the scores
    are: the XLA blocks take no such operands)."""
    route = attention_route(kr.shape[1], block_q,
                            (qn.shape[-1], qr.shape[-1], v.shape[-1]))
    if route["path"] == "fused":
        from .pallas.attention import fused_causal_attention

        return fused_causal_attention(qn, qr, kn, kr, v, scale,
                                      route["block_q"], route["block_kv"],
                                      head_major=head_major)
    if head_major:
        raise ValueError("attention: head-major operands on the XLA blocks")
    return xla_blocks_attention(qn, qr, kn, kr, v, scale, route["block_q"],
                                dtype)


def _attend_ranges(q, k, v, qpos, kpos, scale: float, dtype, mask: Mask):
    """Queries at positions qpos[q] against the keys at kpos[k] that `mask`
    lets each see. q[b,q,g,r,d] k, v[b,k,g,d] -> [b,q,g,r,d] float32: query
    head (g, r) reads key/value head g."""
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k, preferred_element_type=F32)
    s = jnp.where(mask.visible(qpos[:, None], kpos[None, :]), s * scale, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(dtype), v,
                      preferred_element_type=F32)


def xla_blocks_grouped_attention(q, k, v, scale: float, block_q: int, dtype,
                                 mask: Mask = CAUSAL):
    """The `xla_blocks` path of `grouped_attention`: each block of queries
    against the key ranges its rule can see, the rest never computed; the
    backward recomputes a block's scores (`jax.checkpoint`)."""
    b, s, h, d = q.shape
    g = k.shape[2]
    q = q.reshape(b, s, g, h // g, d)
    block = jax.checkpoint(_attend_ranges, static_argnums=(5, 6, 7))
    outs = []
    for q0 in range(0, s, block_q):
        ranges = mask.key_ranges(q0, q0 + block_q)
        keys = [jnp.concatenate([a[:, k0:k1] for k0, k1 in ranges], axis=1)
                for a in (k, v)]
        kpos = jnp.concatenate([jnp.arange(k0, k1) for k0, k1 in ranges])
        outs.append(block(q[:, q0:q0 + block_q], *keys,
                          q0 + jnp.arange(block_q), kpos, scale, dtype, mask))
    return jnp.concatenate(outs, axis=1).reshape(b, s, h, d)


def grouped_attention(q, k, v, scale: float, block_q: int, dtype,
                      mask: Mask = CAUSAL, head_major: bool = False):
    """q[b,s,h,d] k, v[b,s,g,d] (g divides h), all in `dtype` -> [b,s,h,d],
    by the path `attention_route` names: float32 from the XLA blocks,
    `dtype` from the fused kernels. `head_major`: q[b,h,s,d] k, v[b,g,s,d],
    as `causal_attention`."""
    d = q.shape[-1]
    route = attention_route(q.shape[2 if head_major else 1], block_q,
                            (d, 0, d), mask)
    if route["path"] == "fused":
        from .pallas.attention import fused_grouped_attention

        return fused_grouped_attention(q, k, v, scale, route["block_q"],
                                       route["block_kv"], mask,
                                       head_major=head_major)
    if head_major:
        raise ValueError("attention: head-major operands on the XLA blocks")
    return xla_blocks_grouped_attention(q, k, v, scale, route["block_q"],
                                        dtype, mask)
