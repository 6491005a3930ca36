"""The language-model families, one trunk (`MoELM`): embedding,
`num_hidden_layers` blocks (the family's `block`: here the attention layer
+ a dense SwiGLU or the expert layer; `hybrid.py`'s one mixer a layer),
a final RMSNorm and an untied head; the loss taken
in blocks of positions so that the logits of a whole batch never exist at
once. What differs is declared on the family's class, and nothing outside
`models/registry.py` asks for a family by name:

  - `LatentMoELM` (`model_type: deepseek_v3`): latent attention under the
    causal mask; objective `next_token`: position t's logits against
    position t + 1's id, the mean over a row's positions.
  - `BlockDiffusionMoELM` (`model_type: sdar_moe`): grouped-query
    attention; objective `block_diffusion` (arXiv:2503.09573, the form an
    autoregressive model is adapted with). A row `x0` of L ids (the first
    `seq_len` of the batch's `seq_len + 1`), blocks of B = `block_length`
    positions, b(p) = p // B. For each block one `t_b ~ U(noise_t_lo,
    noise_t_hi)`, for each position `m_p ~ Bernoulli(t_b(p))`; the noised
    copy `xt_p = mask_token_id if m_p else x0_p`. The layers run over the
    2L positions `[xt ; x0]` under the `block_diffusion` mask of
    `ops/attention.py`, rotary positions counted inside each copy. Logits
    are taken from the noised half only, each position's own logits
    against its own id (no shift), and the row's loss is
    `(1/L) sum_p m_p (1/t_b(p)) (-log softmax(z_p)[x0_p])`. A masked
    position is one with `m_p = 1`, not one whose id is the mask's.
  - `WindowedMoELM` (`model_type: afmoe`): objective `next_token`; each
    layer's attention and mask its own (`layer_fields`): three window
    layers to one full one, sandwich norms (`Block`'s `post_norms`).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ...core.config import LMConfig
from ...ops.attention import CAUSAL, RESIDUALS, Mask, attention_route
from .layers import (F32, GQA, MLA, MoE, RMSNorm, SwiGLU, _init, dot,
                     expert_row_cap)

COUNTERS = ("moe_slots_held_share", "moe_load_max_over_mean",
            "moe_tokens_none_held_share", "moe_full_width")


def is_expert_layer(cfg: LMConfig, i: int) -> bool:
    """Both families' published rules at once (each leaves the other's
    keys at the values that say "every layer")."""
    return (i >= cfg.first_k_dense_replace and i % cfg.moe_layer_freq == 0
            and i not in cfg.mlp_only_layers
            and (i + 1) % cfg.decoder_sparse_step == 0)


class Block(nn.Module):
    """`x <- x + A(RMSNorm(x))`, `x <- x + F(RMSNorm(x))`, F the dense
    SwiGLU or the expert layer; with `post_norms` (sandwich norms) each
    branch's output is normed again before it is added. A is
    `attention(cfg, dtype, mask, **attention_kw)`, named after its class
    where `attention_kw` gives no `name`."""

    cfg: LMConfig
    expert: bool
    dtype: Any = F32
    attention: Any = MLA  # the layer's attention class
    mask: Mask = CAUSAL
    attention_kw: tuple = ()  # (key, value) pairs: the attention's settings
    post_norms: bool = False

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        # flax scopes a module's call by its name: `layer_<i>`, `mla`,
        # `gqa` or `swa`, `moe`
        kw = {"name": self.attention.__name__.lower(), **dict(self.attention_kw)}
        y = self.attention(c, self.dtype, self.mask, **kw)(
            RMSNorm(c.rms_norm_eps, name="attn_norm")(x))
        if self.post_norms:
            y = RMSNorm(c.rms_norm_eps, name="attn_post_norm")(y)
        x = x + y
        h = RMSNorm(c.rms_norm_eps, name="ffn_norm")(x)
        if not self.expert:
            with jax.named_scope("dense_ffn"):
                return x + self.post_ffn(SwiGLU(c, c.intermediate_size,
                                                self.dtype, name="ffn")(h)), {}
        y, counters = MoE(c, self.dtype, name="moe")(h)
        return x + self.post_ffn(y), counters

    def post_ffn(self, y):
        return RMSNorm(self.cfg.rms_norm_eps, name="ffn_post_norm")(y) \
            if self.post_norms else y


def cross_entropy_rows(h, kernel, targets, block: int, dtype, weights=None):
    """Mean over a row's positions of weight * -log softmax(h W)[target]
    (`weights` None: every weight 1), float32, [b]. Blocks of `block`
    positions; the backward recomputes a block's logits."""
    b, s, d = h.shape
    blk = min(block, s)
    if s % blk:
        raise ValueError(f"lm.loss_block={block} does not divide {s} positions")

    @jax.checkpoint
    def one(hb, tb, wb):
        with jax.named_scope("lm_head"):
            logits = dot(hb, kernel, dtype)
        with jax.named_scope("loss_ce"):
            lse = jax.nn.logsumexp(logits, axis=-1)
            hit = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
            nll = lse - hit
            return jnp.sum(nll if wb is None else wb * nll, axis=-1)

    total = jnp.zeros((b,), F32)
    for p0 in range(0, s, blk):
        total = total + one(h[:, p0:p0 + blk], targets[:, p0:p0 + blk],
                            None if weights is None else weights[:, p0:p0 + blk])
    return total / s


class MoELM(nn.Module):
    """The trunk the families share. A family's class declares
    `model_type`, `objective`, `attention` and, as methods, `mask(s)` (the
    rule for a row of `s` positions as the layers see it), `loss_positions`
    (how many of them, from the first, bear logits) and `loss(tokens)`;
    where it differs from these two, its `block` (called as `Block` is,
    with `layer_kind(i)` in the place of `expert`) and the `counters` its
    layers report."""

    cfg: LMConfig = LMConfig()
    dtype: Any = F32
    remat: bool = False  # recompute each block in the backward

    task = "lm"  # what `models/registry.py` and the trainer dispatch on
    block = Block
    counters = COUNTERS

    def layer_kind(self, i: int):
        return is_expert_layer(self.cfg, i)

    def layer_fields(self, i: int, mask: Mask) -> dict:
        """Layer i's fields of `block` past cfg, kind and dtype, `mask` the
        family's rule for the row: the family's one attention under it on
        every layer."""
        return {"attention": self.attention, "mask": mask}

    def layer_positions(self) -> int:
        """Positions the layers run over for a row of `lm.seq_len`."""
        return self.cfg.seq_len

    def routes(self) -> dict:
        """The paths this model's layers take for rows of `lm.seq_len`, by
        the layers' own rule: what the trainer writes at step 0."""
        c, s = self.cfg, self.layer_positions()
        return {"objective": self.objective,
                "attention_route": attention_route(
                    s, c.attn_block_q, self.attention.route_dims(c), self.mask(s)),
                # the expert layer's sorted list for the positions of a row:
                # `cap` rows are gathered and scattered unless a step's held
                # experts take more (`moe_full_width` 1.0); cap == slots: one
                # width, by shape
                "expert_rows": {"cap": expert_row_cap(c, s),
                                "slots": s * c.num_experts_per_tok}}

    @nn.compact
    def __call__(self, ids, targets=None, weights=None):
        """ids[b, s] int32, the row as the layers see it -> logits[b, n, v]
        float32 of its first n = `loss_positions(s)` positions, or with
        targets[b, n] (and weights[b, n]): {"loss_rows": [b], <counter>:
        [expert layers]}."""
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
            if c.mup_enabled:  # the row of the embedding times sqrt(d)
                x = x * jnp.float32(c.hidden_size ** 0.5)
        # a recomputed layer keeps what the fused attention names (its
        # output and logsumexp), so its forward kernel runs once
        block_cls = nn.remat(
            self.block, policy=jax.checkpoint_policies.save_only_these_names(
                RESIDUALS)) if self.remat else self.block
        mask = self.mask(ids.shape[1])
        per_layer = []
        for i in range(c.num_hidden_layers):
            x, counters = block_cls(c, self.layer_kind(i), self.dtype,
                                    name=f"layer_{i}",
                                    **self.layer_fields(i, mask))(x)
            per_layer.append(counters)
        n = self.loss_positions(ids.shape[1])
        h = RMSNorm(c.rms_norm_eps, name="final_norm")(
            x if n == ids.shape[1] else x[:, :n])
        head = self.param("lm_head", init, (c.hidden_size, c.vocab_size), F32)
        if targets is None:
            with jax.named_scope("lm_head"):
                return dot(h, head, self.dtype)
        out = {"loss_rows": cross_entropy_rows(h, head, targets, c.loss_block,
                                               self.dtype, weights)}
        for key in self.counters:
            got = [cn[key] for cn in per_layer if key in cn]
            out[key] = jnp.stack(got) if got else jnp.zeros((0,), F32)
        return out


class LatentMoELM(MoELM):
    model_type = "deepseek_v3"
    objective = "next_token"
    attention = MLA

    def mask(self, positions: int) -> Mask:
        return CAUSAL

    def loss_positions(self, positions: int) -> int:
        return positions

    def loss(self, tokens, rng=None):
        """tokens[b, seq_len + 1]: position t's logits against position
        t + 1's id. The objective draws nothing: `rng` is unused."""
        return self(tokens[:, :-1], tokens[:, 1:])


def block_noise(key, rows: int, positions: int, block: int, t_lo: float,
                t_hi: float):
    """(m[rows, positions] bool, t[rows, positions] float32): one
    `t_b ~ U(t_lo, t_hi)` a block of `block` positions, `m_p ~
    Bernoulli(t_b(p))`. The one draw of the objective: the plain reference
    is handed the same key and calls the same two `jax.random` functions in
    this order."""
    kt, km = jax.random.split(key)
    n_blocks = -(-positions // block)
    t = jax.random.uniform(kt, (rows, n_blocks), F32, t_lo, t_hi)
    t = jnp.repeat(t, block, axis=1)[:, :positions]
    return jax.random.uniform(km, (rows, positions), F32) < t, t


class BlockDiffusionMoELM(MoELM):
    model_type = "sdar_moe"
    objective = "block_diffusion"
    attention = GQA

    def layer_positions(self) -> int:
        return 2 * self.cfg.seq_len

    def mask(self, positions: int) -> Mask:
        return Mask("block_diffusion", self.cfg.block_length, positions // 2)

    def loss_positions(self, positions: int) -> int:
        return positions // 2  # the noised half

    def loss(self, tokens, rng=None, noise=None):
        """tokens[b, seq_len + 1] (the last id is unused) -> {"loss_rows",
        "bd_masked_share", counters}. The noise is `block_noise(rng, ...)`,
        `rng` the key the trainer's step hands over (the key itself, not a
        stream derived from it: the plain reference draws from the same),
        or `noise` = (m, t) given outright."""
        c = self.cfg
        if c.mask_token_id is None or not 0 <= c.mask_token_id < c.vocab_size:
            raise ValueError("lm.mask_token_id must name one of the "
                             f"{c.vocab_size} ids held, got {c.mask_token_id}")
        x0 = tokens[:, :c.seq_len]
        with jax.named_scope("bd_noise"):
            m, t = block_noise(rng, *x0.shape, c.block_length, c.noise_t_lo,
                               c.noise_t_hi) if noise is None else noise
            doubled = jnp.concatenate(
                [jnp.where(m, jnp.int32(c.mask_token_id), x0), x0], axis=1)
            weights = m.astype(F32) / t
        out = self(doubled, x0, weights)
        # loss-bearing positions over L, one value a row's batch: rides the
        # loss fetch beside the expert layers' counters
        out["bd_masked_share"] = jnp.mean(m.astype(F32))[None]
        return out


#: `layer_types`' names of a window layer and of a full one
LAYER_TYPES = {"sliding_attention": True, "full_attention": False}


class WindowedMoELM(LatentMoELM):
    """`model_type: afmoe`, objective `next_token`: grouped-query attention
    with per-head norms and an output gate (`GQA` with `gated`), on each
    layer as `layer_types` says either under the sliding window of
    `sliding_window` keys with rotary positions (flax name `swa`) or over
    all earlier keys with no positional encoding (`rotary` off, `gqa`);
    sandwich norms (`post_norms`); the embedding times sqrt(hidden)
    (`mup_enabled`); a dense SwiGLU on the first `num_dense_layers` layers,
    then sigmoid-routed experts with a shared one. A cut configuration says
    by `published_layers` which published layer each of its layers is; its
    type and whether it is dense are that layer's."""

    model_type = "afmoe"
    attention = GQA

    def published(self, i: int) -> int:
        return self.cfg.published_layers[i] if self.cfg.published_layers else i

    def layer_kind(self, i: int) -> bool:
        return is_expert_layer(self.cfg, self.published(i))

    def layer_fields(self, i: int, mask: Mask) -> dict:
        c = self.cfg
        kind = c.layer_types[self.published(i)] \
            if self.published(i) < len(c.layer_types) else None
        if kind not in LAYER_TYPES or not c.sliding_window:
            raise ValueError(f"lm: layer {i} (published {self.published(i)}) "
                             f"has no type of {sorted(LAYER_TYPES)} in "
                             "layer_types, or sliding_window is not set")
        window = LAYER_TYPES[kind]
        return {"attention": GQA,
                "mask": Mask("window", window=c.sliding_window) if window else mask,
                "attention_kw": (("name", "swa" if window else "gqa"),
                                 ("gated", True), ("rotary", window)),
                "post_norms": True}

    def routes(self) -> dict:
        """`MoELM.routes` with the attention's route a layer, and whether
        the layer rotates its q and k."""
        c, s = self.cfg, self.layer_positions()
        out = super().routes()
        out["attention_route"] = [
            {**attention_route(s, c.attn_block_q, f["attention"].route_dims(c),
                               f["mask"]),
             "rotary": dict(f["attention_kw"])["rotary"]}
            for f in (self.layer_fields(i, self.mask(s))
                      for i in range(c.num_hidden_layers))]
        return out
