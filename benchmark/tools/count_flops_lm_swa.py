"""Counts the operations the plain reference of a next-token language-model
configuration with sliding-window layers (`model_type: afmoe`) needs per
row and writes them into the configuration's file. Run once, by hand, on
the CPU (`count_flops_lm.py` is the same for the latent-attention file):

    JAX_PLATFORMS=cpu python benchmark/tools/count_flops_lm_swa.py <config> <traffic>

`train_flops_per_pair`: the operations one row of the traffic's `seq_len`
requires, forward and backward, in three parts. (1) XLA's operation count
(`cost_analysis()["flops"]` of the lowered, unoptimised module) of the
reference's loss and its gradient with NO routed expert held: the
attention as the reference writes it (each block of queries against every
key from its first query's earliest visible key to its last query), the
projections and the gate, the dense layer, the shared expert, the router,
head and loss; nothing is recomputed in `row_loss`, so recomputed
operations do not count. (2) LESS the two attention products' pairs that
the reference writes and the layer's rule hides, by arithmetic: a window
layer's visible pairs are `benchmark/kernels/window_attention.py`'s, a
full layer's the causal s (s + 1) / 2; each hidden pair is 2 x (2 x
head_dim) operations a query head, times 3 for forward and backward. (3)
The routed experts by arithmetic, because the reference's dense loop runs
every held expert on every token: positions x experts per token x (held
/ router width) token-slots when the routing is even, each 3 products of
2 x hidden x expert width, times 3 for forward and backward. A property
of the reference's arithmetic and of the configuration's sizes, never
read off the program. Nothing is allocated.
"""

from __future__ import annotations

import importlib
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def written_pairs(s: int, block: int, window: int | None) -> int:
    """(query, key) pairs the reference's blocks compute: each block of
    queries against the keys from its first query's earliest visible key
    (0 on a full layer) to its last query."""
    total = 0
    for q0 in range(0, s, block):
        q1 = min(s, q0 + block)
        total += (q1 - q0) * (q1 - (0 if window is None else max(0, q0 - window + 1)))
    return total


def main(argv) -> None:
    import jax
    import jax.numpy as jnp

    from benchmark.kernels.attention import visible_pairs as causal_pairs
    from benchmark.kernels.window_attention import visible_pairs

    name, traffic = argv[0], argv[1]
    path = os.path.join(ROOT, "benchmark", "configs", name + ".json")
    with open(path) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic", traffic + ".json")) as f:
        seq = json.load(f)["seq_len"]
    ref = importlib.import_module("benchmark.reference." + cfg["reference"])
    none_held = {**cfg, "num_experts": 0,
                 "n_routed_experts_published": ref.router_width(cfg)}
    shapes = lambda c: {p: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
                        for p, s, _ in ref.param_spec(c)}
    row = jax.ShapeDtypeStruct((seq + 1,), jnp.int32)
    lowered = jax.jit(jax.value_and_grad(
        lambda v, t: ref.row_loss(v, t, none_held))).lower(shapes(none_held), row)
    unrouted = lowered.cost_analysis()["flops"]
    layers = range(cfg["num_hidden_layers"])
    expert_layers = sum(ref.is_expert_layer(cfg, i) for i in layers)
    slots = seq * cfg["num_experts_per_tok"] * ref.held_experts(cfg) \
        / ref.router_width(cfg)
    routed = expert_layers * slots * 3 * (
        3 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"])
    window = cfg["sliding_window"]
    seen = {i: visible_pairs(seq, window) if ref.is_window_layer(cfg, i)
            else causal_pairs("causal", seq) for i in layers}
    written = {i: written_pairs(seq, ref.QUERY_BLOCK, window if
                                ref.is_window_layer(cfg, i) else None)
               for i in layers}
    hidden = 3 * cfg["num_attention_heads"] * 2 * (2 * cfg["head_dim"]) * sum(
        written[i] - seen[i] for i in layers)
    cfg["train_flops_per_pair"] = unrouted - hidden + routed
    cfg["flops_counted_by"] = "benchmark/tools/count_flops_lm_swa.py"
    cfg["train_flops_counted_at_seq_len"] = seq
    cfg["train_flops_parts"] = {
        "all_but_routed_experts_as_written": unrouted,
        "written_but_hidden_pairs_of_the_scores": hidden,
        "routed_experts_even_routing": routed,
        "visible_pairs_by_layer": [seen[i] for i in layers],
        "written_pairs_by_layer": [written[i] for i in layers],
        "seq_len": seq}
    values = shapes(cfg)
    cfg["parameters"] = int(sum(math.prod(v.shape) for v in values.values()))
    with open(path, "w") as f:
        json.dump(cfg, f, indent=1)
    print(name, cfg["train_flops_per_pair"], cfg["parameters"])


if __name__ == "__main__":
    main(sys.argv[1:])
