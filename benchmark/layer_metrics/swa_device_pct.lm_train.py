"""Share of the traced window's device time spent under the window layers'
attention scope `swa`, forward and transposed (projections, per-head norms
and rotary, the scores under the sliding window with their
recomputation, the output gate, the output product). None where the
program has no such scope."""

from benchmark.harness.scope_share import share_pct


def read(obs):
    return share_pct(obs, "swa") or None
