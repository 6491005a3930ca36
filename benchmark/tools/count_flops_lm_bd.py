"""Counts the operations the plain reference of a language-model
configuration trained by diffusion over blocks needs per row and writes
them into the configuration's file. Run once, by hand, on the CPU
(`count_flops_lm.py` is the same for the next-token configurations):

    JAX_PLATFORMS=cpu python benchmark/tools/count_flops_lm_bd.py <config> <traffic>

`train_flops_per_pair`: the operations one row of the traffic's `seq_len`
(doubled to 2 x seq_len positions through every layer) requires, forward
and backward, in three parts. (1) XLA's operation count
(`cost_analysis()["flops"]` of the lowered, unoptimised module) of the
reference's loss and its gradient with NO expert held: attention as the
reference writes it (all 2L x 2L scores, the hidden three quarters too),
the projections, the router, head and loss over the noised half; nothing
is recomputed in `row_loss`, so recomputed operations do not count. (2)
LESS the two attention products' HIDDEN pairs, by arithmetic: the rule
makes `benchmark/kernels/attention.py::visible_pairs` of the (2L)^2 pairs
visible, each hidden pair is 2 x (2 x head_dim) operations a query head,
times 3 for forward and backward: work no program has to do. (3) The held
experts by arithmetic, because the reference's dense loop runs every held
expert on every position: positions x experts per token x (held / router
width) token-slots fall here when the routing is even, each 3 products of
2 x hidden x expert width, times 3 for forward and backward. A property
of the reference's arithmetic and of the configuration's sizes, never read
off the program. Nothing is allocated.
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

    from benchmark.kernels.attention import visible_pairs

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
    noise = (jax.ShapeDtypeStruct((seq,), jnp.bool_),
             jax.ShapeDtypeStruct((seq,), jnp.float32))
    lowered = jax.jit(jax.value_and_grad(
        lambda v, t, n: ref.row_loss(v, t, none_held, n))).lower(
            shapes(none_held), row, noise)
    unrouted = lowered.cost_analysis()["flops"]
    layers, positions = cfg["num_hidden_layers"], 2 * seq
    slots = positions * cfg["num_experts_per_tok"] * ref.held_experts(cfg) \
        / ref.router_width(cfg)
    routed = layers * slots * 3 * (
        3 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"])
    seen = visible_pairs("block_diffusion", positions, cfg["block_length"])
    hidden = layers * 3 * cfg["num_attention_heads"] * 2 * (
        2 * cfg["head_dim"]) * (positions * positions - seen)
    cfg["train_flops_per_pair"] = unrouted - hidden + routed
    cfg["flops_counted_by"] = "benchmark/tools/count_flops_lm_bd.py"
    cfg["train_flops_counted_at_seq_len"] = seq
    cfg["train_flops_parts"] = {"all_but_experts_as_written": unrouted,
                                "hidden_pairs_of_the_scores": hidden,
                                "held_experts_even_routing": routed,
                                "visible_pairs": seen,
                                "all_pairs": positions * positions,
                                "seq_len": seq}
    values = shapes(cfg)
    cfg["parameters"] = int(sum(math.prod(v.shape) for v in values.values()))
    with open(path, "w") as f:
        json.dump(cfg, f, indent=1)
    print(name, cfg["train_flops_per_pair"], cfg["parameters"])


if __name__ == "__main__":
    main(sys.argv[1:])
