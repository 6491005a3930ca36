"""Runner `lm_train_bd`: `runners/lm_train.py` as it is (its pool, tap,
window, reference call, comparison and trace), for a language model whose
objective draws noise (training by diffusion over blocks). It reuses that
runner the way that runner reuses `runners/train.py`, through the hook it
already has (`step_fault(tap, trainer)`, which runs once the trainer is
built and before anything is wrapped around it). What it adds:

  - each checked step's NOISE KEY: the key the program's step splits off
    its state's rng (`train/step.py`), read off the state before the call
    and handed to the plain reference in the configuration it is handed
    (`c["checked_noise_keys"]`), so that both draw the same masks;
  - the program's `bd_*` counters (`bd_masked_share`) beside the `moe_*`
    ones in the window's records, the result line and `observed`;
  - this family's scopes in the traced line's `breakdown.scopes`;
  - `router_choices_agree` on a doubled row (that runner's own calls the
    model as the other family's is called).
"""

from __future__ import annotations

import time

import numpy as np

from ..harness import scope_share
from ..harness.counters import window_means
from . import lm_train as base

N_CHECK_STEPS = base.N_CHECK_STEPS
run_reference = base.run_reference  # the calibration asks the runner for it
BREAKDOWN_SCOPES = ("embed", "bd_noise", "gqa", "gqa_proj", "gqa_scores",
                    "gqa_out", "moe", "moe_route", "moe_dispatch",
                    "moe_experts", "moe_shared", "moe_combine", "lm_head",
                    "loss_ce", "optimizer")
COUNTER_PREFIXES = ("moe_", "bd_")


class NoiseKeys:
    """Stands where the tap's inner step stands; keeps, for the first
    N_CHECK_STEPS calls, the key the step will draw its noise from."""

    def __init__(self, inner, keys: list):
        self.inner, self.keys = inner, keys

    def lower(self, *a, **k):
        return self.inner.lower(*a, **k)

    def __call__(self, state, batch):
        if len(self.keys) < N_CHECK_STEPS:
            import jax

            # `rng, noise = split(state.rng)`: the step's own first line
            self.keys.append(np.asarray(jax.random.split(state.rng)[1]))
        return self.inner(state, batch)


def choices_agree(ctx, ref, tokens) -> float | None:
    """Share of the (position, slot) choices of the first batch's first
    row, doubled under its first step's own mask, at the initial weights,
    on which the program's router (its compute dtype) and the reference's
    agree. Reported, not held."""
    import jax
    import jax.numpy as jnp
    from flax.traverse_util import unflatten_dict

    from deepof_tpu.models.registry import model_for

    c = ctx.config
    values = ref.make_params(c, base.weights_key(ctx))
    row = jnp.asarray(tokens[0])
    m = ref.draw_noise(jnp.asarray(c["checked_noise_keys"][0], jnp.uint32),
                       len(tokens), c, row.shape[0] - 1)[0][0]
    want = jax.jit(lambda v, r, mm: ref.chosen_experts(v, r, c, mm))(values, row, m)
    model = model_for(base.program_config(ctx, "unused"))
    params = unflatten_dict({tuple(k.split("/")): v for k, v in values.items()})
    ids = ref.doubled(row, m, c)[0]
    _, got = jax.jit(lambda p, r: model.apply(
        {"params": p}, r[None], mutable=["intermediates"]))(params, ids)
    got = [v["moe"]["chosen"][0] for _, v in sorted(
        got["intermediates"].items(), key=lambda kv: int(kv[0].split("_")[1]))]
    if not want or len(got) != len(want):
        return None
    same = [jnp.mean((jnp.sort(a, -1) == jnp.sort(b, -1)).astype(jnp.float32))
            for a, b in zip(got, want)]
    return float(sum(same) / len(same))


def run(ctx, step_fault=None, also=None, agree: bool | None = None,
        reference: bool = True) -> dict:
    """`runners/lm_train.py::run`'s arguments and result."""
    agree = ctx.trace if agree is None else agree
    # a program without this family fails here, at once, not after it has
    # built some other model from the keys it knows
    family = getattr(base.program_config(ctx, "unused").lm, "model_type", None)
    if family != ctx.config["model_type"]:
        raise SystemExit(f"benchmark: the program's `lm` section takes no "
                         f"model_type {ctx.config['model_type']!r}")
    keys: list = []
    # the reference reads the configuration it is handed: a copy of the
    # cell's with the checked steps' noise keys beside the sizes
    ctx.config = {**ctx.config, "checked_noise_keys": keys}
    counters: dict = {}

    def hook(tap, trainer):
        if step_fault is not None:
            step_fault(tap, trainer)
        tap.inner = NoiseKeys(tap.inner, keys)
        inner_log = trainer.logger.log

        def log(kind, step, **kw):
            if kind == "train":
                counters[int(step)] = {k: v for k, v in kw.items()
                                       if k.startswith(COUNTER_PREFIXES)}
            inner_log(kind, step, **kw)

        trainer.logger.log = log

    def also_(ctx_, ref, batches, refr, prog):
        out = {}
        if agree:
            t = time.perf_counter()
            out["router_choices_agree"] = choices_agree(ctx_, ref, batches[0])
            out["router_choices_agree_s"] = time.perf_counter() - t
        if refr.get("masked_share"):
            out["reference_masked_share"] = refr["masked_share"]
        if also is not None:
            out.update(also(ctx_, ref, batches, refr, prog))
        return out

    out = base.run(ctx, step_fault=hook, also=also_, agree=False,
                   reference=reference)
    # every counter of every record, the window's means among the extras
    for r in out["records"]:
        r.update(counters.get(r["step"], {}))
    in_window = [{k: v for k, v in r.items() if k.startswith(COUNTER_PREFIXES)}
                 for r in out["records"] if r["in_window"]]
    out["extra"].update(window_means(in_window))
    if "observed" in out:
        obs = out["observed"]
        obs["records"] = in_window
        by_scope = scope_share.breakdown(obs, BREAKDOWN_SCOPES)
        if by_scope is not None:
            out["breakdown"]["scopes"] = by_scope
    return out
