"""The correlation's backward kernel (`name="corr_bwd"`) against its
roofline: `benchmark/kernels/corr_bwd.py`'s count at the shapes of the
configuration's `corr.forward` entries of `step_kernels` (one backward a
forward; memory-bound: both feature maps and the cotangent read once, both
gradients written once). None where the step holds no such kernel (before
PR 40 the backward was an XLA scan)."""

from benchmark.kernels.named_share import read_pairs


def read(obs):
    config = obs["config"]
    calls = [dict(k, kernel="corr_bwd.backward")
             for k in config.get("step_kernels", [])
             if k["kernel"] == "corr.forward"]
    return read_pairs(dict(obs, config=dict(config, step_kernels=calls)),
                      [("corr_bwd.backward", "%corr_bwd")])
