"""Share of the traced window's device time spent under the grouped-query
attention's scope `gqa`, forward and transposed (projections, per-head
norms and rotary; the scores under the block mask of the doubled row with
their recomputation; the output product). None where the program has no
such scope."""

from benchmark.harness.scope_share import share_pct


def read(obs):
    return share_pct(obs, "gqa") or None
