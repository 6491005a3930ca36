"""The block-mask attention's forward kernel (`name="bd_attn_fwd"`)
against its roofline: the visible tiles' work reckoned from shapes
(`benchmark/kernels/attention.py`), whatever implements it. None where the
step holds no such kernel (the XLA-blocks path) or the configuration lists
none."""

from benchmark.kernels.named_share import read as named_share


def read(obs):
    return named_share(obs, "attention.forward", "%bd_attn_fwd")
