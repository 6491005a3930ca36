"""Inference: run a trained flow model on image pairs, write `.flo` + visuals.

The reference has no standalone inference path — flow predictions only exist
inside the training/eval session loops (`flyingChairsTrain.py:216-296`,
`version1/testOF.py`). Decoupling the model from the loss graph
(SURVEY.md §7.1) makes this a plain forward pass: preprocess, apply, take
the finest pyramid flow, run the eval amplifier/clip/resize protocol, and
serialize with the (fixed) Middlebury writer — the reference's `writeFlow`
was dead code (`utils.py:44`, undefined TAG_CHAR).

Since the serving subsystem (DESIGN.md "Serving"), this module is a thin
offline frontend over `serve.engine.InferenceEngine`: pairs are submitted
to the dynamic micro-batcher and execute in device batches of up to
`serve.max_batch` instead of one dispatch per pair, and params restore
through the verified-checkpoint path (resilience layer) instead of a raw
orbax read.
"""

from __future__ import annotations

import os

import cv2
import numpy as np
import jax.numpy as jnp

from .core.config import ExperimentConfig
from .io.flo import write_flo
from .utils.flowviz import flow_to_color


def _restore_verified(cfg: ExperimentConfig, model, channels: int,
                      ckpt_dir: str | None = None):
    """Params of `model` from the newest VERIFIED checkpoint under
    `ckpt_dir` (default: cfg.train.log_dir's Trainer layout).

    Restore goes through the resilience layer's manifest verification
    (`train/checkpoint.py` + `resilience/verify.py`): a candidate whose
    manifest fails checksum/structure validation is skipped with a
    warning and the next-newest valid step restores instead — serving
    never loads a torn or bit-flipped checkpoint. Disable with
    resilience.verify_checkpoints=false.
    """
    from .train.checkpoint import CheckpointManager
    from .train.schedule import step_decay_schedule
    from .train.state import create_train_state, make_optimizer

    h, w = cfg.data.image_size  # eval-protocol resolution (val is uncropped)
    tx = make_optimizer(cfg.optim, step_decay_schedule(cfg.optim, 1))
    template = create_train_state(
        model, jnp.zeros((1, h, w, channels)), tx, seed=0)
    ckpt_dir = ckpt_dir or cfg.train.log_dir + "/ckpt"
    mgr = CheckpointManager(ckpt_dir, async_save=False, create=False,
                            verify=cfg.resilience.verify_checkpoints)
    state = mgr.restore(template)
    if state is None:
        candidates = mgr.all_steps()
        if candidates:
            raise RuntimeError(
                f"checkpoints exist under {ckpt_dir} (steps {candidates}) "
                "but none restored — all candidates failed verification or "
                f"the read itself; run `python -m deepof_tpu verify-ckpt "
                f"{cfg.train.log_dir}` to see per-step corruption detail")
        raise FileNotFoundError(
            f"no checkpoint under {ckpt_dir} (run `python -m deepof_tpu "
            f"verify-ckpt {cfg.train.log_dir}` to inspect the directory)")
    return model, state.params


def restore_params(cfg: ExperimentConfig):
    """(model, params) for the flow predict/serve path — see
    `_restore_verified` for the verification contract."""
    from .serve.engine import build_serve_model

    return _restore_verified(cfg, build_serve_model(cfg),
                             3 * cfg.data.time_step)


def restore_action_params(cfg: ExperimentConfig, ckpt_dir: str | None = None):
    """(model, params) for the action predict path: the full training
    model (the checkpoint's exact param tree — the serve path's
    `build_serve_model` strips the action head, which is precisely the
    part this path needs).

    ckpt_dir: explicit checkpoint directory override — a recipe run's
    final stage lives under <log_dir>/ckpt-stage<i> (train/recipe.py),
    not the plain Trainer's <log_dir>/ckpt.
    """
    from .models.registry import model_for, require_flow_serving

    require_flow_serving(cfg)
    t = cfg.data.time_step
    model = model_for(cfg)
    if not (getattr(model, "has_action_head", False)
            or getattr(model, "classifier_only", False)):
        raise ValueError(
            f"model {cfg.model!r} has no action head — the action predict "
            "path needs st_single, st_baseline, or ucf101_spatial")
    channels = 3 if getattr(model, "classifier_only", False) else 3 * t
    return _restore_verified(cfg, model, channels, ckpt_dir=ckpt_dir)


def write_outputs(out_dir: str, stem: str, flow: np.ndarray,
                  write_png: bool = True) -> list[str]:
    """Serialize one native-resolution flow: `.flo` (+ flow-color png).
    Shared by predict_pairs and the offline serve mode."""
    written = []
    flo_path = os.path.join(out_dir, f"{stem}_flow.flo")
    write_flo(flo_path, flow)
    written.append(flo_path)
    if write_png:
        png_path = os.path.join(out_dir, f"{stem}_flow.png")
        cv2.imwrite(png_path, flow_to_color(flow))
        written.append(png_path)
    return written


def output_stem(src_path: str, idx: int, many: bool) -> str:
    stem = os.path.splitext(os.path.basename(src_path))[0]
    # basenames may collide across dirs once there is more than one pair
    return f"{idx:04d}_{stem}" if many else stem


def predict_pairs(cfg: ExperimentConfig, pairs: list[tuple[str, str]],
                  out_dir: str, mean=None, write_png: bool = True,
                  model_params=None, precision: str | None = None
                  ) -> list[str]:
    """Predict flow for (prev, next) image-path pairs; returns written paths.

    The net runs at the request's shape bucket (default ladder: one
    bucket at cfg.data.image_size — the eval resolution); the output is
    amplified/clipped per the eval protocol (`flyingChairsTrain.py:
    264-296`), resized to the source image resolution, and — unlike the
    reference's AEE protocol, which resizes the flow *map* only — the
    u/v vectors are rescaled by (W_native/W_net, H_native/H_net) so the
    standalone `.flo` is in native pixel units.

    Execution goes through the serving engine: all pairs are enqueued up
    front and the micro-batcher coalesces them into device batches of up
    to `serve.max_batch` (one dispatch per flush instead of one per
    pair). Responses are bit-identical to the serial per-pair path at
    the same bucket (padded fixed-occupancy dispatch; pinned in tests).

    model_params: optional (model, params) overriding the checkpoint
    restore (tests; callers that already restored).
    precision: serving tier for every pair ("f32" | "bf16" | "int8";
    must be in cfg.serve.precisions — the engine owns one quantized
    params tree and one AOT executable per (bucket, tier)). None = the
    config's first tier.
    """
    from collections import deque

    from .serve.engine import InferenceEngine

    os.makedirs(out_dir, exist_ok=True)
    written: list[str] = []
    many = len(pairs) > 1
    with InferenceEngine(cfg, model_params=model_params, mean=mean) as eng:
        # bounded outstanding-futures window: a resolved future holds a
        # full native-resolution flow, so consuming-as-we-submit (not
        # after submitting everything) keeps host memory O(window) on
        # arbitrarily long pair lists — and overlaps writes with
        # in-flight inference
        window = max(4 * eng.max_batch, 16)
        buf: deque = deque()

        def drain_one() -> None:
            idx, src_path, fut = buf.popleft()
            flow = fut.result()["flow"]
            written.extend(write_outputs(
                out_dir, output_stem(src_path, idx, many), flow,
                write_png=write_png))

        for idx, (src, tgt) in enumerate(pairs):
            buf.append((idx, src, eng.submit(src, tgt,
                                             precision=precision)))
            if len(buf) >= window:
                drain_one()
        while buf:
            drain_one()
    return written


def predict_action(cfg: ExperimentConfig, pairs: list[tuple[str, str]],
                   out_dir: str, model_params=None,
                   labels: list[str] | None = None, top_k: int = 5,
                   ckpt_dir: str | None = None) -> list[dict]:
    """Classify (prev, next) frame pairs with a trained action model
    (the UCF-101 workload: st_single / st_baseline two-stream heads, or
    the ucf101_spatial single-frame classifier — which ignores the
    `next` frame by construction).

    Each pair becomes one network input at cfg.data.image_size through
    the SAME preprocess the trainer applies (resize, BGR mean subtract,
    /255 — serve/buckets.py); the head's softmax yields the top_k
    classes. Returns the per-pair prediction rows and writes them to
    <out_dir>/actions.json.

    labels: optional class-name list (index order) to attach names.
    model_params: optional (model, params) override (tests; callers
    that already restored). ckpt_dir: see `restore_action_params`.
    """
    import json

    import jax

    from .data.datasets import DATASET_MEANS
    from .serve.buckets import prepare_frame, prepare_pair

    if model_params is not None:
        model, params = model_params
    else:
        model, params = restore_action_params(cfg, ckpt_dir=ckpt_dir)
    mean = DATASET_MEANS.get(cfg.data.dataset, DATASET_MEANS["flyingchairs"])
    h, w = cfg.data.image_size
    spatial_only = getattr(model, "classifier_only", False)

    @jax.jit
    def fwd(p, x):
        out = model.apply({"params": p}, x, train=False)
        logits = out if spatial_only else out[1]
        return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    rows: list[dict] = []
    for src_path, tgt_path in pairs:
        src, tgt = cv2.imread(src_path), cv2.imread(tgt_path)
        if src is None or tgt is None:
            missing = src_path if src is None else tgt_path
            raise FileNotFoundError(f"cannot read image {missing!r}")
        x = (prepare_frame(src, (h, w), mean) if spatial_only
             else prepare_pair(src, tgt, (h, w), mean))[None]
        probs = np.asarray(fwd(params, x))[0]
        order = np.argsort(probs)[::-1][: max(top_k, 1)]
        top = [{"class": int(i),
                **({"label": labels[i]} if labels and i < len(labels)
                   else {}),
                "prob": round(float(probs[i]), 6)} for i in order]
        rows.append({"source": src_path, "target": tgt_path,
                     **{k: top[0][k] for k in ("class", "label", "prob")
                        if k in top[0]},
                     "top": top})
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "actions.json"), "w") as f:
        json.dump(rows, f, indent=2)
    return rows
