"""The correlation's forward kernel (`name="corr_fwd"`) against its
roofline: `benchmark/kernels/corr.py`'s count at the shapes the
configuration's `step_kernels` gives (memory-bound: both feature maps read
once, the cost volume written once). None where the step holds no such
kernel."""

from benchmark.kernels.named_share import read as named_share


def read(obs):
    return named_share(obs, "corr.forward", "%corr_fwd")
