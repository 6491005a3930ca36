"""The latent attention's backward kernel (`name="mla_attn_bwd"`) against
its roofline, as `mla_attn_fwd_roofline_pct.lm_train`."""

from benchmark.kernels.named_share import read as named_share


def read(obs):
    return named_share(obs, "attention.backward", "%mla_attn_bwd")
