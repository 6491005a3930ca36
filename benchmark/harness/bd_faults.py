"""Faults a language-model train cell of the block-diffusion objective
must be able to see, planted under the runner's tap as `lm_faults.py`'s
are (two of them ARE its own); the first is the mechanism's own. Never
used by a benchmark run."""

from __future__ import annotations

from . import lm_faults


def own_clean_block_visible(tap, trainer) -> None:
    """The noised->clean rule taken as `<=`: a noised block reads its own
    clean tokens, i.e. the answer. The program's own step, built from a
    model whose mask rule is changed in that one place (both of the
    attention's paths ask the rule for what is visible and for the key
    ranges to visit)."""
    import dataclasses

    import jax.numpy as jnp

    from deepof_tpu.models.registry import model_for
    from deepof_tpu.ops.attention import Mask
    from deepof_tpu.train.step import make_train_step

    @dataclasses.dataclass(frozen=True)
    class Leaky(Mask):
        def visible(self, q, k):
            qc, kc = q >= self.half, k >= self.half
            same = (q - qc * self.half) // self.block == \
                (k - kc * self.half) // self.block
            return super().visible(q, k) | (jnp.logical_not(qc) & kc & same)

        def key_ranges(self, q0, q1):
            if q0 >= self.half:
                return super().key_ranges(q0, q1)
            own = super().key_ranges(q0, q1)[0]
            return (own, (self.half, self.half + own[1]))

    model = model_for(trainer.cfg)
    sound = model.mask
    leaky = type(model).__name__ + "Leaky"
    cls = type(leaky, (type(model),), {"mask": lambda self, positions: Leaky(
        **dataclasses.asdict(sound(positions)))})
    tap.inner = make_train_step(cls(cfg=model.cfg, dtype=model.dtype,
                                    remat=model.remat),
                                trainer.cfg, trainer.dataset.mean, trainer.mesh)


FAULTS = {"own_clean_block_visible": own_clean_block_visible,
          "one_expert_fewer": lm_faults.one_expert_fewer,
          "not_renormalised": lm_faults.FAULTS["not_renormalised"]}
