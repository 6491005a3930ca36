"""`LatentMoELM`: embedding, `num_hidden_layers` blocks (latent attention
+ a dense SwiGLU in the first `first_k_dense_replace`, the expert layer in
the rest), a final RMSNorm and an untied head; next-token cross-entropy
taken in blocks of positions so that the logits of a whole batch never
exist at once."""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ...core.config import LMConfig
from ...ops.attention import RESIDUALS, attention_route
from .layers import (F32, MLA, MoE, RMSNorm, SwiGLU, _init, dot,
                     expert_row_cap)

COUNTERS = ("moe_slots_held_share", "moe_load_max_over_mean",
            "moe_tokens_none_held_share", "moe_full_width")


def is_expert_layer(cfg: LMConfig, i: int) -> bool:
    return i >= cfg.first_k_dense_replace and i % cfg.moe_layer_freq == 0


class Block(nn.Module):
    cfg: LMConfig
    expert: bool
    dtype: Any = F32

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        # flax scopes a module's call by its name: `layer_<i>`, `mla`, `moe`
        x = x + MLA(c, self.dtype, name="mla")(
            RMSNorm(c.rms_norm_eps, name="attn_norm")(x))
        h = RMSNorm(c.rms_norm_eps, name="ffn_norm")(x)
        if not self.expert:
            with jax.named_scope("dense_ffn"):
                return x + SwiGLU(c, c.intermediate_size, self.dtype,
                                  name="ffn")(h), {}
        y, counters = MoE(c, self.dtype, name="moe")(h)
        return x + y, counters


def cross_entropy_rows(h, kernel, targets, block: int, dtype):
    """Mean over a row's positions of -log softmax(h W)[target], float32,
    [b]. Blocks of `block` positions; the backward recomputes a block's
    logits."""
    b, s, d = h.shape
    blk = min(block, s)
    if s % blk:
        raise ValueError(f"lm.loss_block={block} does not divide {s} positions")

    @jax.checkpoint
    def one(hb, tb):
        with jax.named_scope("lm_head"):
            logits = dot(hb, kernel, dtype)
        with jax.named_scope("loss_ce"):
            lse = jax.nn.logsumexp(logits, axis=-1)
            hit = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
            return jnp.sum(lse - hit, axis=-1)

    total = jnp.zeros((b,), F32)
    for p0 in range(0, s, blk):
        total = total + one(h[:, p0:p0 + blk], targets[:, p0:p0 + blk])
    return total / s


class LatentMoELM(nn.Module):
    cfg: LMConfig = LMConfig()
    dtype: Any = F32
    remat: bool = False  # recompute each block in the backward

    task = "lm"  # what `models/registry.py` and the trainer dispatch on

    def routes(self) -> dict:
        """The paths this model's layers take for rows of `lm.seq_len`, by
        the layers' own rule: what the trainer writes at step 0."""
        c = self.cfg
        return {"attention_route": attention_route(
            c.seq_len, c.attn_block_q,
            (c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim)),
            # a row's share of the expert layer's sorted list: `cap` rows
            # are gathered and scattered unless a step's held experts take
            # more (`moe_full_width` 1.0); cap == slots: one width, by shape
            "expert_rows": {"cap": expert_row_cap(c, c.seq_len),
                            "slots": c.seq_len * c.num_experts_per_tok}}

    @nn.compact
    def __call__(self, ids, targets=None):
        """ids[b, s] int32 -> logits[b, s, v] float32, or with
        targets[b, s]: {"loss_rows": [b], <counter>: [expert layers]}."""
        c = self.cfg
        if c.tie_word_embeddings or c.attention_bias or c.hidden_act != "silu" \
                or c.rope_scaling is not None:
            raise NotImplementedError(
                "models/lm: written for untied head, no bias, silu, no "
                "rope scaling")
        init = _init(c)
        emb = self.param("embedding", nn.initializers.normal(c.embed_std),
                         (c.vocab_size, c.hidden_size), F32)
        with jax.named_scope("embed"):
            x = emb[ids]
        # a recomputed layer keeps what the fused attention names (its
        # output and logsumexp), so its forward kernel runs once
        block_cls = nn.remat(
            Block, policy=jax.checkpoint_policies.save_only_these_names(
                RESIDUALS)) if self.remat else Block
        per_layer = []
        for i in range(c.num_hidden_layers):
            expert = is_expert_layer(c, i)
            x, counters = block_cls(c, expert, self.dtype,
                                    name=f"layer_{i}")(x)
            if expert:
                per_layer.append(counters)
        h = RMSNorm(c.rms_norm_eps, name="final_norm")(x)
        head = self.param("lm_head", init, (c.hidden_size, c.vocab_size), F32)
        if targets is None:
            with jax.named_scope("lm_head"):
                return dot(h, head, self.dtype)
        out = {"loss_rows": cross_entropy_rows(h, head, targets, c.loss_block,
                                               self.dtype)}
        for key in COUNTERS:
            out[key] = jnp.stack([cn[key] for cn in per_layer]) if per_layer \
                else jnp.zeros((0,), F32)
        return out
