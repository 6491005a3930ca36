"""Faults a language-model train cell with sliding-window layers
(`model_type: afmoe`) must be able to see, planted under the runner's tap
as `lm_faults.py`'s are: each breaks the window/full layout in one place.
Never used by a benchmark run.

  - `window_layers_causal`: the window layers attend under the causal rule
    (every earlier key), rotary positions kept;
  - `half_window`: a window of W/2 keys in the place of W;
  - `full_layer_rotated`: rotary positions on the full layers too.

Each builds the program's own step from a model whose family is changed
in that one place.
"""

from __future__ import annotations

from . import lm_faults


def _step_of(trainer, **overrides):
    """The program's step from a subclass of the trainer's model with
    `overrides` as its methods."""
    from deepof_tpu.models.registry import model_for
    from deepof_tpu.train.step import make_train_step

    model = model_for(trainer.cfg)
    cls = type(type(model).__name__ + "Fault", (type(model),), overrides)
    return make_train_step(cls(cfg=model.cfg, dtype=model.dtype,
                               remat=model.remat),
                           trainer.cfg, trainer.dataset.mean, trainer.mesh)


def window_layers_causal(tap, trainer) -> None:
    from deepof_tpu.models.lm.model import WindowedMoELM
    from deepof_tpu.ops.attention import CAUSAL

    def layer_fields(self, i, mask):
        return {**WindowedMoELM.layer_fields(self, i, mask), "mask": CAUSAL}

    tap.inner = _step_of(trainer, layer_fields=layer_fields)


def full_layer_rotated(tap, trainer) -> None:
    from deepof_tpu.models.lm.model import WindowedMoELM

    def layer_fields(self, i, mask):
        f = WindowedMoELM.layer_fields(self, i, mask)
        return {**f, "attention_kw": (*f["attention_kw"], ("rotary", True))}

    tap.inner = _step_of(trainer, layer_fields=layer_fields)


def half_window(tap, trainer) -> None:
    lm_faults.program_with(sliding_window=trainer.cfg.lm.sliding_window // 2)(
        tap, trainer)


FAULTS = {"window_layers_causal": window_layers_causal,
          "half_window": half_window,
          "full_layer_rotated": full_layer_rotated}
