"""Share of the traced window's device time spent under the latent
attention's scope `mla`, forward and transposed (projections and rotary,
the blocked scores with their recomputation, the output product)."""

from benchmark.harness.scope_share import share_pct


def read(obs):
    return share_pct(obs, "mla")
