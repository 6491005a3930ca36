"""Share of the traced window's device time spent under the expert layer's
scope `moe`, forward and transposed (router, sort and gather, the grouped
products, the shared expert, the scatter back). The parts bound by memory
and latency (`moe_route` + `moe_dispatch` + `moe_combine`) stand beside it
in the line's `breakdown.scopes`."""

from benchmark.harness.scope_share import share_pct


def read(obs):
    return share_pct(obs, "moe")
