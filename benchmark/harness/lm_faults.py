"""Faults a language-model train cell must be able to see, planted under
the runner's tap (`step_fault(tap, trainer)`): by the CPU tests at a toy
size and by `tools/calibrate_lm.py` on the chip at the cell's own size.
Never used by a benchmark run."""

from __future__ import annotations

import dataclasses


def half_batch(tap, trainer) -> None:
    """Half of the batch left out: the first half stands in for the second."""
    import jax.numpy as jnp

    inner = tap.inner

    def step(state, batch):
        n = batch["tokens"].shape[0] // 2
        return inner(state, {"tokens": jnp.concatenate(
            [batch["tokens"][:n], batch["tokens"][:n]])})

    tap.inner = step


def program_with(**lm_keys):
    """The program's own step, built with a changed `lm` section."""
    def fault(tap, trainer) -> None:
        from deepof_tpu.models.registry import model_for
        from deepof_tpu.train.step import make_train_step

        cfg = trainer.cfg.replace(lm=dataclasses.replace(trainer.cfg.lm, **lm_keys))
        tap.inner = make_train_step(model_for(cfg), cfg, trainer.dataset.mean,
                                    trainer.mesh)
    return fault


def one_expert_fewer(tap, trainer) -> None:
    """top-(k-1) in the place of top-k (top-5 for top-6)."""
    program_with(num_experts_per_tok=trainer.cfg.lm.num_experts_per_tok - 1)(
        tap, trainer)


FAULTS = {"half_batch": half_batch, "one_expert_fewer": one_expert_fewer,
          "not_renormalised": program_with(norm_topk_prob=False)}
