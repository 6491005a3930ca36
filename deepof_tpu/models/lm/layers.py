"""The layers of the FOUR language-model families `models/lm/` writes, by
their equations. Each family's are in one place: the attention layer's
class (`MLA`: `model_type: deepseek_v3`; `GQA`: `model_type: sdar_moe`,
with `plain` the `nemotron_h` family's, q, k and v only cast, and with
`gated` the `afmoe` family's, rotated on its window layers only), the
state-space mixer `Mamba2` (`nemotron_h`), `route` (both routers) and
`MoE` (ONE expert layer, told by the config which scoring it uses, which
expert it runs (SwiGLU, or `mlp_hidden_act: relu2`'s W_down relu(W_up
h)^2) and whether a shared expert exists).

Precision: norm statistics, rotary angles, router scores, softmax and loss
in float32; every other matrix product takes operands in the compute dtype
(float32 masters are cast on use) and accumulates in float32; the residual
stream stays float32. No bias anywhere. The attention's scores, mask and
softmax are `ops/attention.py`'s, which states the same for both of its
paths (fused kernels on a TPU, XLA blocks elsewhere).

Between a projection and the scores `ops/attention.py::attention_route`
decides a second time (`prep`), and each attention layer writes both
ways of the same arithmetic. `xla`: the elementwise code below (`rope`,
`rope_halves`, `RMSNorm`) on [b, s, h, d]. `fused` (on a TPU, wherever the
scores are fused): what is normed or rotated goes from its product's
float32 output through `ops/pallas/qk_prep.py` (norm statistic and angles
float32, ONE cast, written head-major [b, h, s, d]); what is only cast
(`qn`, `kn`, `v`) leaves its product head-major in the compute dtype
(`heads_dot`: the same float32 accumulation rounded once), the nope / rope
/ value split made on the WEIGHT's columns; the fused attention takes all
of it as it comes.

Scopes (what the per-layer readers find in a profile; flax names a
module's scope after the module, the rest are `jax.named_scope`s):
`layer_<i>` > `mla` > `mla_proj`, `mla_scores`, `mla_out` or `gqa` >
`gqa_proj`, `gqa_scores`, (`gqa_gate`,) `gqa_out` or `swa` > `swa_proj`,
`swa_scores`, `swa_gate`, `swa_out` or `mamba` > `mamba_proj` (the input
projection, the step sizes' softplus), `mamba_conv` (the depthwise
convolution and its silu), `mamba_scan` (`ops/ssm.py`'s scan and the skip
D x), `mamba_out` (gate, grouped norm, output projection);
`dense_ffn`; `moe` > `moe_route`, `moe_dispatch`, `moe_experts`,
`moe_shared`, `moe_combine`; beside them `embed`, `bd_noise`, `lm_head`,
`loss_ce`, `optimizer`. Counters, one value a layer riding the loss
fetch: the expert layer's `moe_*`, and the state-space layer's
`ssm_decay_mean`, the mean over positions and heads of exp(dt A): how
far the state carries from one position to the next. On the chip
`mla_scores` holds the Mosaic calls `mla_attn_fwd` and, under
`transpose(jvp(...))`, `mla_attn_bwd`; `gqa_scores` the calls
`bd_attn_fwd` and `bd_attn_bwd`, `swa_scores` `swa_attn_fwd` and
`swa_attn_bwd`; `mla_proj`, `gqa_proj` and `swa_proj` the calls
`qk_prep_fwd` and `qk_prep_bwd` (two a layer and direction) beside the
projections' products; `mamba_scan` the calls `ssd_fwd` (forward and
recomputed) and `ssd_bwd` (transposed) of `ops/pallas/ssd.py`.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from ...core.config import LMConfig
from ...ops.attention import (CAUSAL, Mask, attention_route, causal_attention,
                              grouped_attention)

F32 = jnp.float32
ragged_dot = lax.ragged_dot  # a name of this module's: a test stands in for the chip's


def _init(cfg: LMConfig):
    return nn.initializers.normal(cfg.init_std)


def dot(x, w, dtype):
    """x[..., k] @ w[k, n]: operands in `dtype`, float32 accumulation."""
    return lax.dot_general(x.astype(dtype), w.astype(dtype),
                           (((x.ndim - 1,), (0,)), ((), ())),
                           preferred_element_type=F32)


def heads_dot(x, w, dtype):
    """x[b, s, k] @ w[k, h, n] -> [b, h, s, n] in `dtype`: `dot`'s product
    (operands in `dtype`, float32 accumulation) rounded once and laid out
    head-major as it leaves, for an operand of the fused attention that
    has no arithmetic between its product and its cast."""
    return jnp.transpose(lax.dot_general(
        x.astype(dtype), w.astype(dtype), (((2,), (0,)), ((), ())),
        preferred_element_type=F32).astype(dtype), (0, 2, 1, 3))


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), F32)
        x = x.astype(F32)
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + self.eps) * scale


class HeadScale(nn.Module):
    """The learned scale of a per-head `RMSNorm`, alone and under the same
    name, for the fused pass that norms inside its kernel."""

    width: int

    @nn.compact
    def __call__(self):
        return self.param("scale", nn.initializers.ones, (self.width,), F32)


def rope(x, theta: float):
    """Rotary positions on x[b, s, h, d], float32; channel pairs
    (2i, 2i+1) are the rotated pairs (`rope_interleave` true)."""
    s, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x = x.astype(F32)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def rope_halves(x, theta: float, positions):
    """Rotary positions on x[b, s, h, d], float32, at `positions`[s];
    channel j is rotated against channel j + d / 2 (`rope_interleave`
    false: the `sdar_moe` family's own)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x = x.astype(F32)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


class MLA(nn.Module):
    """Latent attention without query compression (`q_lora_rank` null),
    always under the causal rule:
    `q = h Wq` -> [heads, nope + rope]; `ckv = h Wkva` -> [latent + rope];
    `k_rope = rope(ckv[latent:])`, one head's, shared by all; `kv =
    RMSNorm(ckv[:latent]) Wkvb` -> [heads, nope + v]; scores `scale *
    (q_nope . k_nope + rope(q_rope) . k_rope)`, scale 1/sqrt(nope + rope),
    rotary pairs interleaved; softmax over the visible keys, times v,
    then `Wo`."""

    cfg: LMConfig
    dtype: Any = F32
    mask: Mask = CAUSAL

    @staticmethod
    def route_dims(c: LMConfig) -> tuple:
        """The head sizes `ops/attention.py::attention_route` decides on."""
        return c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim

    @nn.compact
    def __call__(self, h):
        c, dt = self.cfg, self.dtype
        if self.mask != CAUSAL:
            raise NotImplementedError(
                "models/lm: latent attention is written under the causal "
                f"mask only, not {self.mask.rule!r}")
        if c.q_lora_rank is not None or not c.rope_interleave:
            raise NotImplementedError(
                "models/lm: only uncompressed queries (q_lora_rank null) "
                "and interleaved rotary pairs (rope_interleave true) are "
                "written here")
        b, s, d = h.shape
        nh, dn, dr, dv = (c.num_attention_heads, c.qk_nope_head_dim,
                          c.qk_rope_head_dim, c.v_head_dim)
        init = _init(c)
        wq = self.param("wq", init, (d, nh * (dn + dr)), F32)
        wkva = self.param("wkva", init, (d, c.kv_lora_rank + dr), F32)
        wkvb = self.param("wkvb", init, (c.kv_lora_rank, nh * (dn + dv)), F32)
        wo = self.param("wo", init, (nh * dv, d), F32)
        prep = attention_route(s, c.attn_block_q, self.route_dims(c),
                               self.mask)["prep"]
        fused = prep["path"] == "fused"
        with jax.named_scope("mla_proj"):
            if fused:
                from ...ops.pallas.qk_prep import qk_prep

                rot = functools.partial(
                    qk_prep, positions=self.mask.rope_positions(s),
                    theta=c.rope_theta, interleave=True, dtype=dt,
                    block_s=prep["block_s"])
                lr = c.kv_lora_rank
                wq = wq.reshape(d, nh, dn + dr)
                qn = heads_dot(h, wq[..., :dn], dt)
                qr = rot(dot(h, wq[..., dn:].reshape(d, nh * dr), dt), heads=nh)
                kr = rot(dot(h, wkva[:, lr:], dt), heads=1)[:, 0]
                lat = RMSNorm(c.rms_norm_eps, name="kv_norm")(
                    dot(h, wkva[:, :lr], dt))
                wkvb = wkvb.reshape(lr, nh, dn + dv)
                kn = heads_dot(lat, wkvb[..., :dn], dt)
                v = heads_dot(lat, wkvb[..., dn:], dt)
            else:
                q = dot(h, wq, dt).reshape(b, s, nh, dn + dr)
                qn = q[..., :dn].astype(dt)
                qr = rope(q[..., dn:], c.rope_theta).astype(dt)
                ckv = dot(h, wkva, dt)
                kr = rope(ckv[:, :, None, c.kv_lora_rank:],
                          c.rope_theta)[:, :, 0].astype(dt)
                lat = RMSNorm(c.rms_norm_eps, name="kv_norm")(
                    ckv[..., :c.kv_lora_rank])
                kv = dot(lat, wkvb, dt).reshape(b, s, nh, dn + dv)
                kn, v = kv[..., :dn].astype(dt), kv[..., dn:].astype(dt)
        with jax.named_scope("mla_scores"):
            o = causal_attention(qn, qr, kn, kr, v, 1.0 / math.sqrt(dn + dr),
                                 c.attn_block_q, dt, head_major=fused)
        with jax.named_scope("mla_out"):
            return dot(o.reshape(b, s, nh * dv), wo, dt)


class GQA(nn.Module):
    """Grouped-query attention with per-head norms (`model_type:
    sdar_moe`): `q = h Wq` -> [heads, head_dim]; `k = h Wk`, `v = h Wv` ->
    [kv heads, head_dim]; `q <- RMSNorm(q)`, `k <- RMSNorm(k)` over the
    head's channels with one learned scale each a layer; rotary positions
    over the whole head (channel j against j + head_dim / 2) at the
    position's index inside its copy of the row (`Mask.rope_positions`);
    query head n reads key/value head n // (heads / kv heads); scores
    scaled by 1/sqrt(head_dim), masked by `mask`'s rule, softmax over the
    visible keys, times v, then `Wo`. With `plain` (`model_type:
    nemotron_h`) q, k and v are only cast: no norm, no rotary positions.
    With `gated` (`model_type: afmoe`) the output is gated before `Wo`, `o
    <- o * sigmoid(h Wg)` per head and channel; without `rotary` (that
    family's full layers) q and k are normed and not rotated. The scopes
    take the module's name: `<name>_proj`, `_scores`, `_gate`, `_out`."""

    cfg: LMConfig
    dtype: Any = F32
    mask: Mask = CAUSAL
    plain: bool = False
    gated: bool = False
    rotary: bool = True

    @staticmethod
    def route_dims(c: LMConfig) -> tuple:
        """As `MLA.route_dims`: one head size, no separate rotary part."""
        return c.head_dim, 0, c.head_dim

    @nn.compact
    def __call__(self, h):
        c, dt = self.cfg, self.dtype
        if c.use_sliding_window or (c.rope_interleave and not self.plain):
            raise NotImplementedError(
                "models/lm: grouped-query attention is written with rotary "
                "halves (rope_interleave false) and no sliding window "
                "(use_sliding_window false)")
        b, s, d = h.shape
        nh, g, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        if nh % g:
            raise ValueError(f"lm: {g} key/value heads do not divide {nh} heads")
        init = _init(c)
        wq = self.param("wq", init, (d, nh * hd), F32)
        wk = self.param("wk", init, (d, g * hd), F32)
        wv = self.param("wv", init, (d, g * hd), F32)
        wo = self.param("wo", init, (nh * hd, d), F32)
        route = attention_route(s, c.attn_block_q, self.route_dims(c),
                                self.mask)
        prep = route["prep"]
        # the plain layer has no prep pass: its operands go head-major
        # wherever the attention takes the fused kernels
        fused = (route if self.plain else prep)["path"] == "fused"
        with jax.named_scope(f"{self.name}_proj"):
            if self.plain:
                q, k, v = (heads_dot(h, w.reshape(d, -1, hd), dt) if fused
                           else dot(h, w, dt).reshape(b, s, -1, hd).astype(dt)
                           for w in (wq, wk, wv))
            elif fused:
                from ...ops.pallas.qk_prep import qk_prep

                # unrotated: the pass's angles at position 0, cos 1 and
                # sin 0, exact
                pos = self.mask.rope_positions(s) if self.rotary else \
                    jnp.zeros((s,), jnp.int32)
                normed = functools.partial(
                    qk_prep, positions=pos, theta=c.rope_theta,
                    interleave=False, dtype=dt, block_s=prep["block_s"],
                    eps=c.rms_norm_eps)
                q = normed(dot(h, wq, dt), heads=nh,
                           scale=HeadScale(hd, name="q_norm")())
                k = normed(dot(h, wk, dt), heads=g,
                           scale=HeadScale(hd, name="k_norm")())
                v = heads_dot(h, wv.reshape(d, g, hd), dt)
            else:
                pos = self.mask.rope_positions(s)
                q = RMSNorm(c.rms_norm_eps, name="q_norm")(
                    dot(h, wq, dt).reshape(b, s, nh, hd))
                k = RMSNorm(c.rms_norm_eps, name="k_norm")(
                    dot(h, wk, dt).reshape(b, s, g, hd))
                q = (rope_halves(q, c.rope_theta, pos) if self.rotary
                     else q).astype(dt)
                k = (rope_halves(k, c.rope_theta, pos) if self.rotary
                     else k).astype(dt)
                v = dot(h, wv, dt).reshape(b, s, g, hd).astype(dt)
        with jax.named_scope(f"{self.name}_scores"):
            o = grouped_attention(q, k, v, 1.0 / math.sqrt(hd), c.attn_block_q,
                                  dt, self.mask, head_major=fused)
        if self.gated:
            wg = self.param("wg", init, (d, nh * hd), F32)
            with jax.named_scope(f"{self.name}_gate"):
                o = o.reshape(b, s, nh * hd) * jax.nn.sigmoid(dot(h, wg, dt))
        with jax.named_scope(f"{self.name}_out"):
            return dot(o.reshape(b, s, nh * hd), wo, dt)


class Mamba2(nn.Module):
    """The state-space mixer (`model_type: nemotron_h`), on the row h[b, s,
    d]: `in_proj` gives z[H P], xBC[H P + 2 G N] and dt[H]; `xBC <-
    silu(causal depthwise conv(xBC))` (kernel `conv_kernel`, a bias); x[H,
    P], B[G, N], C[G, N]; `dt <- softplus(dt + dt_bias)`; `A = -exp(A_log)`;
    the recurrence of `ops/ssm.py` per head, `y = h C + D x`; `y <-
    RMSNorm_grouped(y silu(z))` over `n_groups` groups of channels with a
    learned scale; `out_proj`. No projection bias. The row is the doubled
    one of the `block_diffusion` mask, the one objective of the family: the
    convolution and the scan follow `ops/ssm.py`'s rule for its noised half.

    Returns (y[b, s, d] float32, {"ssm_decay_mean": scalar})."""

    cfg: LMConfig
    dtype: Any = F32
    mask: Mask = CAUSAL

    @nn.compact
    def __call__(self, h):
        from ...ops import ssm  # only a state-space family reaches it

        c, dt = self.cfg, self.dtype
        if c.mamba_proj_bias or not c.use_conv_bias \
                or c.mamba_hidden_act != "silu" \
                or tuple(c.time_step_limit) not in ((0, None), (0.0, None)) \
                or self.mask.rule != "block_diffusion":
            raise NotImplementedError(
                "models/lm: the state-space layer is written with no "
                "projection bias, a convolution bias, silu and no step "
                "limit, on the doubled row of diffusion over blocks")
        b, s, d = h.shape
        H, P, N, G, K = (c.mamba_num_heads, c.mamba_head_dim, c.ssm_state_size,
                         c.n_groups, c.conv_kernel)
        inner, width = H * P, H * P + 2 * G * N
        init = _init(c)
        w_in = self.param("in_proj", init, (d, inner + width + H), F32)
        conv_w = self.param("conv_w", _conv_init(K), (K, width), F32)
        conv_b = self.param("conv_b", _conv_init(K), (width,), F32)
        dt_bias = self.param("dt_bias", _dt_bias_init(c), (H,), F32)
        a_log = self.param("A_log", _a_log_init, (H,), F32)
        skip = self.param("D", nn.initializers.ones, (H,), F32)
        w_out = self.param("out_proj", init, (inner, d), F32)
        with jax.named_scope("mamba_proj"):
            zxd = dot(h, w_in, dt)
            z, xbc = zxd[..., :inner], zxd[..., inner:inner + width]
            step = jax.nn.softplus(zxd[..., inner + width:] + dt_bias)
        L = self.mask.half
        with jax.named_scope("mamba_conv"):
            xbc = jax.nn.silu(jnp.concatenate([
                ssm.doubled_conv(xbc[:, :L], xbc[:, L:], conv_w, conv_b,
                                 self.mask.block),
                ssm.causal_conv(xbc[:, L:], conv_w, conv_b)], axis=1))
        x = xbc[..., :inner]  # [b, s, H P]
        bm = xbc[..., inner:inner + G * N].reshape(b, s, G, N)
        cm = xbc[..., inner + G * N:].reshape(b, s, G, N)
        A = -jnp.exp(a_log)
        with jax.named_scope("mamba_scan"):
            # x takes its heads of P only at the scan's call, a copy at a
            # time: the chip lays a whole [.., H, 64] array out positions
            # minor, and the kernels' operands were copied out of it and back
            noised, clean = ((x[:, h].reshape(b, L, H, P), step[:, h],
                              bm[:, h], cm[:, h])
                             for h in (slice(0, L), slice(L, s)))
            y = jnp.concatenate([y.reshape(b, L, inner) for y in ssm.doubled_scan(
                *noised, *clean, A, c.chunk_size, self.mask.block, dt)], axis=1)
            y = y + jnp.repeat(skip, P) * x
            decay = jnp.mean(jnp.exp(step * A))
        with jax.named_scope("mamba_out"):
            y = y * jax.nn.silu(z)
            y = y.reshape(b, s, G, inner // G)
            y = y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + c.rms_norm_eps)
            scale = self.param("norm_scale", nn.initializers.ones, (inner,), F32)
            return dot(y.reshape(b, s, inner) * scale, w_out, dt), \
                {"ssm_decay_mean": decay}


def _conv_init(kernel: int):
    """The framework's default for a depthwise convolution's weight and
    bias: uniform within 1 / sqrt(fan in), fan in = the kernel's width."""
    bound = 1.0 / math.sqrt(kernel)
    return lambda key, shape, dtype=F32: jax.random.uniform(
        key, shape, dtype, -bound, bound)


def _a_log_init(key, shape, dtype=F32):
    """A = exp(A_log) uniform on [1, 16]."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(c: LMConfig):
    """softplus^-1 of dt, dt log-uniform on [time_step_min, time_step_max],
    floored at time_step_floor."""
    def init(key, shape, dtype=F32):
        lo, hi = math.log(c.time_step_min), math.log(c.time_step_max)
        step = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, dtype, lo, hi)),
                           c.time_step_floor)
        return step + jnp.log(-jnp.expm1(-step))
    return init


def swiglu(h, w_gate, w_up, w_down, dtype):
    a = jax.nn.silu(dot(h, w_gate, dtype)) * dot(h, w_up, dtype)
    return dot(a, w_down, dtype)


class SwiGLU(nn.Module):
    cfg: LMConfig
    width: int
    dtype: Any = F32

    @nn.compact
    def __call__(self, h):
        d, init = h.shape[-1], _init(self.cfg)
        return swiglu(h, self.param("w_gate", init, (d, self.width), F32),
                      self.param("w_up", init, (d, self.width), F32),
                      self.param("w_down", init, (self.width, d), F32),
                      self.dtype)


class Relu2MLP(nn.Module):
    """W_down relu(W_up h)^2: the expert of `mlp_hidden_act: relu2`."""

    cfg: LMConfig
    width: int
    dtype: Any = F32

    @nn.compact
    def __call__(self, h):
        d, init = h.shape[-1], _init(self.cfg)
        a = jnp.square(jax.nn.relu(dot(
            h, self.param("w_up", init, (d, self.width), F32), self.dtype)))
        return dot(a, self.param("w_down", init, (self.width, d), F32),
                   self.dtype)


def route(h, router, bias, cfg: LMConfig):
    """(chosen expert ids [t, k], their weights [t, k]) over ALL the
    router's experts, float32, by the family's own router:

      - `scoring_func=sigmoid`, `topk_method=noaux_tc`: sigmoid scores; the
        k largest of score + bias are chosen, the bias is a buffer and the
        gradient does not reach it; the weights are the scores without it;
      - `scoring_func=softmax`, `topk_method=greedy`: `p = softmax(h Wr)`
        over all experts; the k largest are chosen; no buffer (`bias` None).

    Ties: the lower id. With `norm_topk_prob` the weights are divided by
    their sum over all k chosen; then times `routed_scaling_factor`."""
    kind = (cfg.scoring_func, cfg.topk_method)
    if kind not in (("sigmoid", "noaux_tc"), ("softmax", "greedy")) \
            or cfg.n_group != 1 or cfg.topk_group != 1:
        raise NotImplementedError(
            "models/lm: the routers written here are scoring_func=sigmoid "
            "with topk_method=noaux_tc and scoring_func=softmax with "
            f"topk_method=greedy, n_group = topk_group = 1; not {kind}")
    logits = jnp.dot(h.astype(F32), router, precision=lax.Precision.HIGHEST)
    if cfg.scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, idx = lax.top_k(scores + lax.stop_gradient(bias),
                           cfg.num_experts_per_tok)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        _, idx = lax.top_k(scores, cfg.num_experts_per_tok)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * cfg.routed_scaling_factor


#: The expert layer's sorted row list is this many times the held experts'
#: EVEN share of the token-slots (`t*k*held/width`) wide, not `t*k`: a row
#: gather or scatter-add on the chip is bound by its row count, not its
#: bytes. A step whose held experts take more runs at the full width
#: (`lax.cond`), so the factor trades rows moved every step against how
#: often that happens, never a result. Measured on a v5e (PR 34, one layer
#: of the 4k cell, 16 of 128 experts held, forward + recomputation +
#: backward, ms at held shares of 0.12 / 0.24 / 0.33 / 0.46 of the slots):
#: full width 31.5 / 33.9 / 35.9 / 38.7; factor 2 (12,288 of 49,152 rows)
#: 18.2 / 20.4 / 35.5 / 38.3; 3: 20.6 / 22.9 / 24.7 / 38.4; 4: 23.0 / 25.5 /
#: 27.3 / 30.1: 2.4 ms a layer for each 6,144 rows, and the fallback costs
#: what the full width costs. Over the 240 layer-records of four seeds'
#: windows (share over 0.25 in 12%, over 0.375 in 6%: the last layer's
#: drifts up as the cut model trains) that is 20.5 ms a layer at 2, 22.0
#: at 3, 23.9 at 4, 32.3 at the full width: PERF.md section 6, PR 34. A
#: layer that holds half or more of its router's experts has no second
#: width.
EXPERT_ROWS_OVER_EVEN = 2
#: and rounded up to the rows the chip's grouped product takes at a time
#: (XLA's fusion for `lax.ragged_dot` on a v5e walks 96 row tiles for
#: 49,152 rows and 24 for 12,288, in all three products of the 4k cell: its
#: metadata holds tiles + groups - 1 entries, 111 and 39; sandbox compile
#: for a described v5e, PR 34): rows up to the next tile's end cost nothing
EXPERT_ROWS_MULTIPLE = 512


def expert_row_cap(cfg: LMConfig, tokens: int) -> int:
    """Rows of the expert layer's compact sorted list for `tokens` tokens;
    `tokens * k`, all the slots, where that is no wider."""
    held = cfg.n_routed_experts
    slots = tokens * cfg.num_experts_per_tok
    even = -(-slots * held // (cfg.n_routed_experts_published or held))
    cap = EXPERT_ROWS_OVER_EVEN * even
    return min(slots, -(-cap // EXPERT_ROWS_MULTIPLE) * EXPERT_ROWS_MULTIPLE)


def add_routed(shared, x, w, order, sizes, experts, rows: int, dtype):
    """shared[t, d] + the held experts' products of their token-slots,
    weighted, over the first `rows` of the sorted slot list `order` (held
    experts' slots first, in expert order). Exact whenever `sum(sizes) <=
    rows`: the rows left out are no held expert's and would be selected to
    zero before they were added. x[t, d], w[t, k], sizes[held],
    experts = (w_gate, w_up, w_down) of SwiGLU experts or (w_up, w_down)
    of relu^2 ones."""
    k = w.shape[-1]
    with jax.named_scope("moe_dispatch"):
        order = order[:rows]
        tok = order // k
        # Rows past the held experts' are no expert's. The chip's
        # grouped product neither reads nor WRITES them, forward or
        # transposed: what it leaves there (in the output, and in the
        # cotangent of its input) is whatever the memory held. So every
        # value that enters or leaves a grouped product is selected by
        # `held_row`, which also zeroes the cotangent on the way back.
        held_row = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
        xs = jnp.where(held_row, x.astype(dtype)[tok], 0)
        ws = w.reshape(-1)[order]
    with jax.named_scope("moe_experts"):
        grouped = lambda a, m: jnp.where(held_row, ragged_dot(  # noqa: E731
            a, m.astype(dtype), sizes, preferred_element_type=F32), 0.0)
        if len(experts) == 3:
            a = jax.nn.silu(grouped(xs, experts[0])) * grouped(xs, experts[1])
        else:
            a = jnp.square(jax.nn.relu(grouped(xs, experts[0])))
        o = grouped(a.astype(dtype), experts[-1])
    with jax.named_scope("moe_combine"):
        return shared.at[tok].add(o * ws[:, None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def add_routed_within(cap: int, dtype, fits, shared, x, w, order, sizes,
                      experts):
    """`add_routed` over `cap` rows in a step whose held experts' slots fit
    there (`fits`: the step's own `sum(sizes) <= cap`), over all of `order`
    in one where they do not (`lax.cond`): the same sum either way. The
    backward is a `cond` of its own over each width's own backward, from
    the operands. A `cond` differentiated as it stands hands its backward
    the residuals of BOTH widths, the one not taken as zeros (2.4 GB a layer
    in the 4k cell: 10.4 GB of temporaries, a step that no longer fits the
    chip); with `jax.checkpoint` around each branch the residuals are
    copies of the operands (18.8 ms a layer where this reads 18.2: PR 34)."""
    return lax.cond(fits, lambda *a: add_routed(*a, cap, dtype),
                    lambda *a: add_routed(*a, order.shape[0], dtype),
                    shared, x, w, order, sizes, experts)


def _add_routed_within_fwd(cap, dtype, fits, *args):
    return add_routed_within(cap, dtype, fits, *args), (fits, args)


def _add_routed_within_bwd(cap, dtype, res, ct):
    fits, (shared, x, w, order, sizes, experts) = res

    def back(rows):
        return lambda: jax.vjp(
            lambda sh, xx, ww, ex: add_routed(sh, xx, ww, order, sizes, ex,
                                              rows, dtype),
            shared, x, w, experts)[1](ct)

    # The barrier keeps the optimizer's first use of the experts' gradients
    # (the norm's sums, Adam's moments) out of the branches: XLA sinks it
    # into both, where it writes two float32 copies a weight, and the step's
    # temporaries read 7.14 GB against 4.99 with the barrier (3.98 with no
    # `cond`; sandbox compile for a described v5e, PR 34).
    d_shared, dx, dw, d_experts = lax.optimization_barrier(
        lax.cond(fits, back(cap), back(order.shape[0])))
    return None, d_shared, dx, dw, None, None, d_experts


add_routed_within.defvjp(_add_routed_within_fwd, _add_routed_within_bwd)


class MoE(nn.Module):
    """The expert layer of ONE share of an expert-parallel deployment: it
    holds experts `first_expert .. first_expert + n_routed_experts` of the
    router's `n_routed_experts_published`. It scores, chooses and
    normalises over all of them and adds only the products of the chosen
    experts it holds, plus the whole shared expert where the family has
    one (`n_shared_experts`; none: nothing is built); what absent experts
    would add is left out (their chips add it, after the exchange that one
    chip does not have). No capacity in the model's sense: the token-slots
    are sorted by expert and each projection is one grouped product over
    the held experts' rows (`lax.ragged_dot`), so no token is dropped or
    padded. The sorted list that is gathered, multiplied and scatter-added
    is `expert_row_cap` rows wide, the part of it the held experts' slots
    usually fill (in the deployment: the exchange's receive buffer); a step
    in which they take more runs the same function over all `t*k` slots,
    so `cap` bounds what is moved, never what is computed.

    Returns (y[b, s, d] float32, counters of this layer)."""

    cfg: LMConfig
    dtype: Any = F32

    @nn.compact
    def __call__(self, h):
        c, dt = self.cfg, self.dtype
        b, s, d = h.shape
        held = c.n_routed_experts
        width = c.n_routed_experts_published or held
        k, we = c.num_experts_per_tok, c.moe_intermediate_size
        if not 0 <= c.first_expert <= width - held:
            raise ValueError(f"lm: experts {c.first_expert}.."
                             f"{c.first_expert + held} are not among {width}")
        init = _init(c)
        router = self.param("router", init, (d, width), F32)
        # the sigmoid router's buffer; the softmax router has none
        bias = self.param("bias", nn.initializers.normal(c.bias_std),
                          (width,), F32) if c.topk_method == "noaux_tc" else None
        relu2 = c.mlp_hidden_act == "relu2"
        if c.mlp_hidden_act not in ("", "relu2"):
            raise NotImplementedError("models/lm: experts are SwiGLU or "
                                      f"relu2, not {c.mlp_hidden_act!r}")
        experts = tuple(
            self.param("experts_" + n, init,
                       (held, we, d) if n == "w_down" else (held, d, we), F32)
            for n in (("w_up", "w_down") if relu2 else
                      ("w_gate", "w_up", "w_down")))
        x = h.reshape(b * s, d)
        t = b * s
        with jax.named_scope("moe_route"):
            idx, w = route(x, router, bias, c)
            self.sow("intermediates", "chosen", idx)
            local = idx - c.first_expert
            mine = (local >= 0) & (local < held)
            gid = jnp.where(mine, local, held).reshape(-1)  # absent: sorts last
            sizes = jnp.sum(jax.nn.one_hot(gid, held + 1, dtype=jnp.int32),
                            axis=0)[:held]
        with jax.named_scope("moe_dispatch"):
            order = jnp.argsort(gid, stable=True)
        with jax.named_scope("moe_shared"):
            shared = (Relu2MLP if relu2 else SwiGLU)(
                c, c.moe_shared_expert_intermediate_size
                or c.n_shared_experts * we, dt, name="shared")(x) \
                if c.n_shared_experts else jnp.zeros((t, d), F32)
        args = (shared, x, w, order, sizes, experts)
        cap = expert_row_cap(c, t)
        if cap < t * k:
            fits = jnp.sum(sizes) <= cap
            y = add_routed_within(cap, dt, fits, *args)
            full_width = 1.0 - fits.astype(F32)
        else:  # the one width: the program holds no `cond`
            y, full_width = add_routed(*args, t * k, dt), jnp.ones((), F32)
        load = sizes.astype(F32)
        counters = {
            "moe_slots_held_share": jnp.sum(load) / (t * k),
            "moe_load_max_over_mean": jnp.max(load) / jnp.maximum(
                jnp.mean(load), 1e-9),
            "moe_tokens_none_held_share": 1.0 - jnp.mean(
                jnp.any(mine, axis=-1).astype(F32)),
            "moe_full_width": full_width,
        }
        return y.reshape(b, s, d), counters
