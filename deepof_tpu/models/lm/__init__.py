"""Decoder-only language models of two families on one trunk: latent
attention or grouped-query attention, SwiGLU, and a sparse expert layer
that is told which experts it holds; next-token loss or diffusion over
blocks."""

from .model import BlockDiffusionMoELM, LatentMoELM  # noqa: F401
