"""Decoder-only language models of four families on one trunk: latent,
grouped-query or gated window/full attention (or state-space mixers),
SwiGLU, and a sparse expert layer that is told which experts it holds;
next-token loss or diffusion over blocks."""

from .model import BlockDiffusionMoELM, LatentMoELM, WindowedMoELM  # noqa: F401
