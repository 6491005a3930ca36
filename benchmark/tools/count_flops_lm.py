"""Counts the operations the plain reference of a language-model
configuration needs per row (a "pair": inputs with their shifted targets)
and writes them into the configuration's file. Run once, by hand, on the
CPU (`count_flops.py` is the same for the flow configurations):

    JAX_PLATFORMS=cpu python benchmark/tools/count_flops_lm.py <config> <traffic>

`train_flops_per_pair`: the operations one row of the traffic's `seq_len`
requires, forward and backward, in three parts. (1) XLA's operation count
(`cost_analysis()["flops"]` of the lowered, unoptimised module) of the
reference's loss and its gradient with NO routed expert held: attention as
the reference writes it (all S x S scores, the masked half too), the
projections, the dense layer, the shared expert, the router, head and
loss; nothing is recomputed in `row_loss`, so recomputed operations do not
count. (2) LESS the two attention products' masked half, by arithmetic: a
causal layer requires the S (S + 1) / 2 scores at or below the diagonal,
the reference writes all S x S, so S (S - 1) / 2 scores a head, each 2 x
(query width + value width) operations, times 3 for forward and backward,
are work no program has to do. (3) The routed experts by arithmetic, because the
reference's dense loop runs every held expert on every token and a count
of it would be 16 times what the layer requires: positions x experts per
token x (held / router width) token-slots fall here when the routing is
even, each 3 products of 2 x hidden x expert width, times 3 for forward
and backward. A property of the reference's arithmetic and of the
configuration's sizes, never read off the program. Nothing is allocated.
"""

from __future__ import annotations

import importlib
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv) -> None:
    import jax
    import jax.numpy as jnp

    name, traffic = argv[0], argv[1]
    path = os.path.join(ROOT, "benchmark", "configs", name + ".json")
    with open(path) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic", traffic + ".json")) as f:
        seq = json.load(f)["seq_len"]
    ref = importlib.import_module("benchmark.reference." + cfg["reference"])
    none_held = {**cfg, "n_routed_experts": 0,
                 "n_routed_experts_published": ref.router_width(cfg)}
    shapes = lambda c: {p: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
                        for p, s, _ in ref.param_spec(c)}
    row = jax.ShapeDtypeStruct((seq + 1,), jnp.int32)
    lowered = jax.jit(jax.value_and_grad(
        lambda v, t: ref.row_loss(v, t, none_held))).lower(shapes(none_held), row)
    unrouted = lowered.cost_analysis()["flops"]
    expert_layers = sum(ref.is_expert_layer(cfg, i)
                        for i in range(cfg["num_hidden_layers"]))
    slots = seq * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / ref.router_width(cfg)
    routed = expert_layers * slots * 3 * (
        3 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"])
    masked = cfg["num_hidden_layers"] * 3 * cfg["num_attention_heads"] * 2 * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    ) * (seq * (seq - 1) // 2)
    cfg["train_flops_per_pair"] = unrouted - masked + routed
    cfg["train_flops_parts"] = {"all_but_routed_experts_as_written": unrouted,
                                "masked_half_of_the_scores": masked,
                                "routed_experts_even_routing": routed,
                                "seq_len": seq}
    values = shapes(cfg)
    cfg["parameters"] = int(sum(math.prod(v.shape) for v in values.values()))
    with open(path, "w") as f:
        json.dump(cfg, f, indent=1)
    print(name, cfg["train_flops_per_pair"], cfg["parameters"])


if __name__ == "__main__":
    main(sys.argv[1:])
