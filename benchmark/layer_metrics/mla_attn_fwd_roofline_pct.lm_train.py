"""The latent attention's forward kernel (`name="mla_attn_fwd"`) against
its roofline: the causal half's work at the published widths (queries and
keys 192, values 128) reckoned from shapes (`benchmark/kernels/attention.py`),
whatever implements it. None where the step holds no such kernel (the
XLA-blocks path) or the configuration lists none."""

from benchmark.kernels.named_share import read as named_share


def read(obs):
    return named_share(obs, "attention.forward", "%mla_attn_fwd")
