"""The sliding-window attention's backward kernel (`name="swa_attn_bwd"`)
against its roofline: the window's visible pairs' work reckoned from shapes
(`benchmark/kernels/window_attention.py`), whatever implements it. None
where the step holds no such kernel (the XLA-blocks path) or the
configuration lists none."""

from benchmark.kernels.named_share import read as named_share


def read(obs):
    return named_share(obs, "window_attention.backward", "%swa_attn_bwd")
