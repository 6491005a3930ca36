"""The warp's flow-gradient kernel (`name="warp_flow_grad"`) against its
roofline."""

from benchmark.kernels.named_share import read as named_share


def read(obs):
    return named_share(obs, "warp.flow_grad", "%warp_flow_grad")
