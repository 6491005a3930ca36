"""Share of the traced window's device time spent under the state-space
layer's scope `mamba`, forward and transposed (its input projection, the
depthwise convolution, the chunked scan on the doubled row, the gate and
grouped norm, the output projection, each with its recomputation). None
where the program has no such scope."""

from benchmark.harness.scope_share import share_pct


def read(obs):
    return share_pct(obs, "mamba") or None
