"""Counts the operations the plain reference needs per image pair and
writes them into the configuration's file. Run once, by hand, on the CPU:

    JAX_PLATFORMS=cpu python benchmark/tools/count_flops.py <config> [--serve H W]...

`train_flops_per_pair`: XLA's operation count (`cost_analysis()["flops"]`
of the lowered, unoptimised module: multiply and add counted apart, every
elementwise operation one) of the reference's loss and its gradient at the
configuration's image size, over the rows of a small batch. It is a
property of the reference's arithmetic and never read off the program.
`serve_flops_per_pair[HxW]`: the same for the forward alone at a bucket.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv) -> None:
    import importlib

    import jax
    import jax.numpy as jnp

    from benchmark.reference import _common as rc
    from benchmark.runners.train import reference_hp

    name = argv[0]
    buckets = [(int(argv[i + 1]), int(argv[i + 2]))
               for i, a in enumerate(argv) if a == "--serve"]
    path = os.path.join(ROOT, "benchmark", "configs", name + ".json")
    with open(path) as f:
        cfg = json.load(f)
    ref = importlib.import_module("benchmark.reference." + cfg["reference"])
    h, w = cfg["image_size"]
    hp, scales = reference_hp(cfg), tuple(cfg["flow_scales"])
    rows = 2

    def shapes(hh, ww):
        key = jax.random.PRNGKey(0)
        return jax.eval_shape(lambda: rc.make_params(
            ref.forward, jnp.zeros((1, hh, ww, 6)), key, key, 0.0))

    values = shapes(h, w)
    img = jax.ShapeDtypeStruct((rows, h, w, 3), jnp.float32)
    lowered = jax.jit(jax.value_and_grad(
        lambda v, s, t: rc.model_loss(ref.forward, scales, v, s, t, hp)[0])
    ).lower(values, img, img)
    cfg["train_flops_per_pair"] = lowered.cost_analysis()["flops"] / rows
    cfg["parameters"] = int(sum(int(jnp.prod(jnp.array(v.shape))) for v in values.values()))
    for bh, bw in buckets:
        x = jax.ShapeDtypeStruct((rows, bh, bw, 6), jnp.float32)
        low = jax.jit(lambda v, x: ref.forward(rc.Params(values=v), x)).lower(
            shapes(bh, bw), x)
        cfg["serve_flops_per_pair"][f"{bh}x{bw}"] = low.cost_analysis()["flops"] / rows
    with open(path, "w") as f:
        json.dump(cfg, f, indent=1)
    print(name, cfg["train_flops_per_pair"], cfg["serve_flops_per_pair"], cfg["parameters"])


if __name__ == "__main__":
    main(sys.argv[1:])
