"""Model registry — replaces the reference's string-dispatch in
`version1/trainOF.py:76-90` and the per-dataset trainer imports.

What a model IS it declares on its class, and nothing outside asks for it
by name: `task` ("flow" where absent; "action", "classify", "lm") picks the
objective, the example input and the evaluation; a language model also
declares the `model_type` it writes (the published key that `model_for`
finds the family by) and its `objective`; `input_frames` the frames
it takes (absent: the dataset's `time_step`); `smooth_border_mask` and
`vgg16_trunk_path` what the trainer does around it. `model_for` and
`example_input` are the one way from an ExperimentConfig to a model.
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp

from .flownet_s import FlowNetS
from .vgg16_flow import VGG16Flow
from .inception_v3_flow import InceptionV3Flow
from .flownet_c import FlowNetC
from .flownet2 import FlowNetCS
from .lm import BlockDiffusionMoELM, LatentMoELM, WindowedMoELM
from .two_stream import STBaseline, STSingle, UCF101Spatial

MODELS = {
    "flownet_s": FlowNetS,
    "vgg16": VGG16Flow,
    "inception_v3": InceptionV3Flow,
    "flownet_c": FlowNetC,
    "flownet_cs": FlowNetCS,
    "st_single": STSingle,
    "st_baseline": STBaseline,
    "ucf101_spatial": UCF101Spatial,
    "latent_moe_lm": LatentMoELM,
    "block_diffusion_moe_lm": BlockDiffusionMoELM,
    "windowed_moe_lm": WindowedMoELM,
}


#: (config-surface name, model-field name, model-family default): knobs
#: honored only by models that declare the field. Non-default values for
#: a model without the field raise a NAMED error instead of silently
#: dropping (the "displacements invisible to the correlation" class of
#: silent failure, DESIGN.md r04) or a dataclass TypeError.
_OPTIONAL_KNOBS = (
    ("width_mult", "width_mult", 1.0),
    ("corr_max_disp", "max_disp", 20),
    ("corr_stride", "corr_stride", 2),
)


#: language-model families imported only where a configuration names one
#: (`lm.model_type`): their modules cost every other job nothing
LAZY_LM_FAMILIES = {"nemotron_h": ("deepof_tpu.models.lm.hybrid",
                                   "HybridBlockDiffusionLM")}


def build_model(name: str, flow_channels: int = 2, dtype: Any = jnp.float32,
                width_mult: float = 1.0, corr_max_disp: int = 20,
                corr_stride: int = 2, **kw):
    if name not in MODELS:
        raise KeyError(f"unknown model {name!r}; available: {sorted(MODELS)}")
    cls = MODELS[name]
    import dataclasses

    fields = {f.name for f in dataclasses.fields(cls)}
    passed = {"width_mult": width_mult, "corr_max_disp": corr_max_disp,
              "corr_stride": corr_stride}
    for knob, field, default in _OPTIONAL_KNOBS:
        value = passed[knob]
        if field in fields and field not in kw:
            kw[field] = value
        elif value != default and field not in fields:
            supported = sorted(
                n for n, c in MODELS.items()
                if field in {f.name for f in dataclasses.fields(c)})
            raise ValueError(
                f"model {name!r} does not support {knob} (={value}); "
                f"models honoring it: {supported}")
    if "flow_channels" in fields:
        kw["flow_channels"] = flow_channels
    return cls(dtype=dtype, **kw)


def task_of(model) -> str:
    """The task a model (class or instance) declares; "flow" where it
    declares none."""
    return getattr(model, "task", "flow")


def compute_dtype(cfg):
    return jnp.bfloat16 if cfg.train.compute_dtype == "bfloat16" else jnp.float32


def model_for(cfg, dtype: Any = None):
    """The model an ExperimentConfig names, built the one way the trainer,
    the warmup and the recipe engine all build it."""
    if cfg.model not in MODELS:
        raise KeyError(f"unknown model {cfg.model!r}; available: {sorted(MODELS)}")
    dtype = compute_dtype(cfg) if dtype is None else dtype
    if task_of(MODELS[cfg.model]) == "lm":
        # the `lm` section's published `model_type` says which family it is
        # (the `lm` preset names one; a config.json of the other wins)
        families = {m.model_type: m for m in MODELS.values()
                    if task_of(m) == "lm"}
        if cfg.lm.model_type in LAZY_LM_FAMILIES:
            import importlib

            module, name = LAZY_LM_FAMILIES[cfg.lm.model_type]
            families[cfg.lm.model_type] = getattr(
                importlib.import_module(module), name)
        if cfg.lm.model_type not in families:
            raise KeyError(f"lm.model_type={cfg.lm.model_type!r} is no family "
                           "models/lm writes; available: "
                           f"{sorted([*families, *LAZY_LM_FAMILIES])}")
        return families[cfg.lm.model_type](cfg=cfg.lm, dtype=dtype,
                                           remat=cfg.train.remat)
    return build_model(cfg.model, flow_channels=2 * (cfg.data.time_step - 1),
                       dtype=dtype, width_mult=cfg.width_mult,
                       corr_max_disp=cfg.corr_max_disp,
                       corr_stride=cfg.corr_stride)


def example_input(model, cfg) -> jnp.ndarray:
    """Zeros of the shape and type of what the model takes for a train
    batch of `cfg`: a row of ids for a language model, else frames."""
    if task_of(model) == "lm":
        return jnp.zeros((cfg.data.batch_size, cfg.lm.seq_len), jnp.int32)
    h, w = cfg.data.crop_size or cfg.data.image_size
    frames = getattr(model, "input_frames", None) or cfg.data.time_step
    return jnp.zeros((cfg.data.batch_size, h, w, 3 * frames), jnp.float32)


class NoServingPath(NotImplementedError):
    """`predict` / `serve` asked for a model family that only trains."""


def require_flow_serving(cfg) -> None:
    if cfg.model in MODELS and task_of(MODELS[cfg.model]) == "lm":
        raise NoServingPath(
            f"model {cfg.model!r} is a language model: it trains "
            "(`train --preset lm`) and has no predict/serve path yet")
