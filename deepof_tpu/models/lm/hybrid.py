"""The third language-model family, `model_type: nemotron_h`: on `MoELM`'s
trunk, layers that are ONE mixer each behind a pre-norm and a residual,
`x <- x + mixer(RMSNorm(x))`, the mixer picked by the layer's character of
`hybrid_override_pattern`:

  - `M`: the Mamba-2 state-space layer (`layers.py::Mamba2`, its scan
    `ops/ssm.py`);
  - `*`: grouped-query attention with q, k and v only cast: no rotary
    positions, no per-head norm (`layers.py::GQA` with `plain`);
  - `E`: the expert layer (`layers.py::MoE`): sigmoid scores, top-k of
    score + the fixed buffer, renormalised over all k and scaled, relu^2
    experts and a relu^2 shared expert (`mlp_hidden_act: relu2`).

Trained by diffusion over blocks as `BlockDiffusionMoELM` is (the doubled
row, its noise, its loss): the attention under the `block_diffusion` mask
and the state-space layer under its form of the same rule (`ops/ssm.py`:
a noised block starts from the CLEAN copy's state at its first position).
Imported only where a configuration names the family
(`models/registry.py`).
"""

from __future__ import annotations

from typing import Any

from flax import linen as nn

from ...core.config import LMConfig
from ...ops import ssm
from ...ops.attention import CAUSAL, Mask
from .layers import F32, GQA, Mamba2, MoE, RMSNorm
from .model import COUNTERS, BlockDiffusionMoELM

#: the pattern's characters this family writes
MIXERS = {"M": "mamba", "*": "gqa", "E": "moe"}


class MixerBlock(nn.Module):
    cfg: LMConfig
    kind: str  # the layer's character of the pattern
    dtype: Any = F32
    attention: Any = GQA
    mask: Mask = CAUSAL

    @nn.compact
    def __call__(self, x):
        c, dt = self.cfg, self.dtype
        if self.kind not in MIXERS:
            raise NotImplementedError(f"models/lm: no mixer {self.kind!r} in "
                                      f"nemotron_h's pattern; written: {sorted(MIXERS)}")
        h = RMSNorm(c.rms_norm_eps, name="norm")(x)
        # flax scopes a module's call by its name: `mamba`, `gqa`, `moe`
        if self.kind == "M":
            y, counters = Mamba2(c, dt, self.mask, name="mamba")(h)
        elif self.kind == "*":
            y, counters = self.attention(c, dt, self.mask, plain=True,
                                         name="gqa")(h), {}
        else:
            y, counters = MoE(c, dt, name="moe")(h)
        return x + y, counters


class HybridBlockDiffusionLM(BlockDiffusionMoELM):
    model_type = "nemotron_h"
    block = MixerBlock
    counters = COUNTERS + ("ssm_decay_mean",)

    def layer_kind(self, i: int) -> str:
        pattern = self.cfg.hybrid_override_pattern
        if len(pattern) < self.cfg.num_hidden_layers:
            raise ValueError(f"lm: hybrid_override_pattern {pattern!r} names "
                             f"fewer than {self.cfg.num_hidden_layers} layers")
        return pattern[i]

    def routes(self) -> dict:
        """`MoELM.routes`, the attention's `prep` as this family takes it (q,
        k and v only cast), and the state-space scan's path."""
        out = super().routes()
        out["attention_route"]["prep"] = {"path": "cast"}
        c = self.cfg
        out["ssm"] = ssm.route(self.layer_positions(), c.chunk_size,
                               c.block_length, c.ssm_state_size,
                               c.mamba_num_heads // c.n_groups * c.mamba_head_dim)
        return out
