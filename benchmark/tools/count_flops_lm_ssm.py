"""Counts the operations the plain reference of a language-model
configuration with state-space layers, trained by diffusion over blocks,
needs per row and writes them into the configuration's file.
`count_flops_lm_bd.py`'s count, with the recurrence counted by its
arithmetic. Run once, by hand, on the CPU:

    JAX_PLATFORMS=cpu python benchmark/tools/count_flops_lm_ssm.py <config> <traffic>

`train_flops_per_pair`: the operations one row of the traffic's `seq_len`
(doubled to 2 x seq_len positions through every layer) requires, forward
and backward, in four parts. (1) XLA's operation count
(`cost_analysis()["flops"]` of the lowered, unoptimised module) of the
reference's loss and its gradient with NO expert held. (2) The
recurrence: XLA counts a loop's body once, not once an iteration, so
what it counted for each state-space layer's scan (the same count of
`scan_doubled` alone, forward and backward, at the layer's shapes) is
taken out and `benchmark/kernels/ssd.py`'s forward and backward put in.
The layer's groups are a `lax.map` too, whose body XLA also counts once:
the convolution, gate and norm of seven groups in eight, about 1e10 of
1.6e13 a row, stay uncounted.
(3) LESS the attention products' HIDDEN pairs, as `count_flops_lm_bd.py`
takes them. (4) The held experts by arithmetic at even routing: positions
x experts per token x (held / router width) token-slots, each 2 products
of 2 x hidden x expert width (relu^2 has no gate), times 3 for forward
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

    from benchmark.kernels import ssd
    from benchmark.kernels.attention import visible_pairs

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
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    row = jax.ShapeDtypeStruct((seq + 1,), jnp.int32)
    noise = (jax.ShapeDtypeStruct((seq,), jnp.bool_), f32(seq))
    unrouted = jax.jit(jax.value_and_grad(
        lambda v, t, n: ref.row_loss(v, t, none_held, n))).lower(
            shapes(none_held), row, noise).cost_analysis()["flops"]
    positions = 2 * seq
    H, P, N = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"]
    scan_counted = jax.jit(jax.value_and_grad(
        lambda x, dt, b, c, A: jnp.sum(ref.scan_doubled(
            x, dt, b, c, A, seq, cfg["block_length"])),
        argnums=(0, 1, 2, 3, 4))).lower(
            f32(positions, H, P), f32(positions, H), f32(positions, H, N),
            f32(positions, H, N), f32(H)).cost_analysis()["flops"]
    shape = dict(s=positions, heads=H, head_dim=P, groups=cfg["n_groups"], state=N)
    scan = ssd.forward(1, **shape)["ops"] + ssd.backward(1, **shape)["ops"]
    kinds = ref.kinds(cfg)
    mambas, experts, attentions = (kinds.count(k) for k in "ME*")
    slots = positions * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / ref.router_width(cfg)
    routed = experts * slots * 3 * (
        2 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"])
    seen = visible_pairs("block_diffusion", positions, cfg["block_length"])
    hidden = attentions * 3 * cfg["num_attention_heads"] * 2 * (
        2 * cfg["head_dim"]) * (positions * positions - seen)
    cfg["train_flops_per_pair"] = unrouted + mambas * (scan - scan_counted) \
        - hidden + routed
    cfg["flops_counted_by"] = "benchmark/tools/count_flops_lm_ssm.py"
    cfg["train_flops_counted_at_seq_len"] = seq
    cfg["train_flops_parts"] = {"all_but_experts_as_written": unrouted,
                                "scan_as_xla_counts_it_a_layer": scan_counted,
                                "scan_by_arithmetic_a_layer": scan,
                                "state_space_layers": mambas,
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
