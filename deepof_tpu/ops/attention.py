"""Causal attention of the latent-attention layer (`models/lm/layers.py::
MLA`): one function, one route, two paths of the same mathematics.

  softmax_k(scale * (qn . kn + qr . kr))[k <= q] . v

with operands in the compute dtype, float32 accumulation in both products,
mask / max / exp / sum in float32, and the probabilities cast to the
compute dtype only as the operand of the second product. Every visible key
is attended; no soft cap, no approximate exponential.

  - `fused` (`ops/pallas/attention.py`): on a TPU, where the kernel's
    blocks divide the row. Queries and keys both blocked, a running max
    and sum per query row, the scores of a (query block, key block) tile
    only ever in VMEM, key blocks above the diagonal skipped, and a custom
    VJP that recomputes tiles from q, k, v, the output and the row's
    logsumexp.
  - `xla_blocks`: everywhere else (the CPU, rows the kernel's blocks do
    not divide), and the fused path's oracle in the tests. Blocks of
    queries, each against its own prefix of keys; the scores of one block
    exist at a time in HBM and the backward recomputes them.

`attention_route` decides from what the code can observe (backend, shape)
and is what the trainer writes into its step-0 info record; there is no
option that picks a path.
"""

from __future__ import annotations

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
#: `jax.ad_checkpoint.checkpoint_name` of the fused forward's output and
#: logsumexp (64 MB + 1 MB a layer at the cell's size): a layer recomputed
#: in the backward (`train.remat`) whose policy keeps them does not run the
#: forward kernel a second time. The XLA blocks name nothing.
RESIDUALS = "mla_attention_residuals"


def attention_route(positions: int, block_q: int, head_dims) -> dict:
    """What `causal_attention` does with rows of `positions` under
    `lm.attn_block_q = block_q` at `head_dims` = (qk_nope, qk_rope, v):
    {"path": "fused", "block_q", "block_kv"} or {"path": "xla_blocks",
    "block_q"}. The one rule the layer and the trainer's step-0 info
    record share. Fused: on a TPU, blocks of whole 128-lane registers,
    head sizes the kernels' tiles hold (128s; the rotary part 64s)."""
    bq = min(block_q, positions)
    if positions % bq:
        raise ValueError(f"lm.attn_block_q={block_q} does not divide "
                         f"the {positions} positions of a row")
    dn, dr, dv = head_dims
    if (jax.default_backend() == "tpu"
            and all(n % FUSED_BLOCK_MULTIPLE == 0 for n in (bq, dn, dv))
            and dr % (FUSED_BLOCK_MULTIPLE // 2) == 0):
        bkv = bq if positions % FUSED_BLOCK_KV else FUSED_BLOCK_KV
        return {"path": "fused", "block_q": bq, "block_kv": bkv}
    return {"path": "xla_blocks", "block_q": bq}


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


def causal_attention(qn, qr, kn, kr, v, scale: float, block_q: int, dtype):
    """qn[b,s,h,dn] qr[b,s,h,dr] kn[b,s,h,dn] kr[b,s,dr] v[b,s,h,dv], all in
    `dtype` -> [b,s,h,dv], by the path `attention_route` names: float32
    from the XLA blocks, `dtype` from the fused kernels (the output
    projection casts to it anyway)."""
    route = attention_route(qn.shape[1], block_q,
                            (qn.shape[-1], qr.shape[-1], v.shape[-1]))
    if route["path"] == "fused":
        from .pallas.attention import fused_causal_attention

        return fused_causal_attention(qn, qr, kn, kr, v, scale,
                                      route["block_q"], route["block_kv"])
    return xla_blocks_attention(qn, qr, kn, kr, v, scale, route["block_q"],
                                dtype)
