"""The block-mask attention's backward kernel (`name="bd_attn_bwd"`)
against its roofline, as `bd_attn_fwd_roofline_pct.lm_train`."""

from benchmark.kernels.named_share import read as named_share


def read(obs):
    return named_share(obs, "attention.backward", "%bd_attn_bwd")
