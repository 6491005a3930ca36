"""The sliding-window attention's forward kernel (`name="swa_attn_fwd"`)
against its roofline: the window's visible pairs' work reckoned from shapes
(`benchmark/kernels/window_attention.py`), whatever implements it. None
where the step holds no such kernel (the XLA-blocks path) or the
configuration lists none."""

from benchmark.kernels.named_share import read as named_share


def read(obs):
    return named_share(obs, "window_attention.forward", "%swa_attn_fwd")
