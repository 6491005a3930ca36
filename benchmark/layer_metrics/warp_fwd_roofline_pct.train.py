"""The warp's forward kernel (`name="warp_fwd"`) against its roofline."""

from benchmark.kernels.named_share import read as named_share


def read(obs):
    return named_share(obs, "warp.forward", "%warp_fwd")
