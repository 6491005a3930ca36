"""Decoder-only language models: latent attention (MLA), SwiGLU, and a
sparse expert layer that is told which experts it holds."""

from .model import LatentMoELM  # noqa: F401
