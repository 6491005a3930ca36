"""Evaluation protocols.

Reproduces the reference's AEE measurement conventions exactly
(SURVEY.md §6): the finest prediction (already multiplied by its
flow_scale) is multiplied by the dataset `eval_amplifier`, clipped to
`eval_clip`, bilinearly resized to the native ground-truth resolution, and
compared against GT flow with mean endpoint error:

  - FlyingChairs: x2, clip [-300, 250], resize to 384x512
    (`flyingChairsTrain.py:264-296`);
  - Sintel: x3, clip [-420.621, 426.311], resize to 436x1024, averaged over
    all T-1 flow pairs (`sintelTrain.py:264-328`);
  - UCF-101: action accuracy over per-class batches (`ucf101train.py:210-287`);
  - language models: mean next-token cross-entropy over the held-out rows.

`EVALUATORS` maps the task a model declares (`models/registry.py`) to its
protocol; the trainer asks for none by a model's name.

Visual artifacts (flow color images, warped frames) mirror the reference's
cv2.imwrite dumps (`flyingChairsTrain.py:272-291`).
"""

from __future__ import annotations

import os

import numpy as np

try:
    import cv2
except Exception:  # noqa: BLE001
    cv2 = None

from ..core.config import ExperimentConfig
from ..utils.flowviz import flow_to_color
from ..utils.metrics import flow_aae, flow_epe


def postprocess_flow(flow: np.ndarray, cfg: ExperimentConfig,
                     gt_hw: tuple[int, int]) -> np.ndarray:
    """(B, h, w, 2k) net output -> amplified/clipped/native-res flow."""
    lo, hi = cfg.train.eval_clip
    flow = np.clip(flow * cfg.train.eval_amplifier, lo, hi)
    b, h, w, c = flow.shape
    gh, gw = gt_hw
    if (h, w) == (gh, gw):
        return flow
    out = np.empty((b, gh, gw, c), np.float32)
    for i in range(b):
        for p in range(0, c, 2):  # cv2.resize handles <=4 channels; per pair
            out[i, :, :, p : p + 2] = cv2.resize(
                flow[i, :, :, p : p + 2], (gw, gh), interpolation=cv2.INTER_LINEAR)
    return out


def dump_visuals(out_dir: str, tag: str, flow: np.ndarray,
                 recon: np.ndarray | None = None,
                 gt: np.ndarray | None = None,
                 max_samples: int = 8) -> None:
    """Write flow-color / reconstruction / GT images per sample (the
    reference dumps one set per val clip, `sintelTrain.py:283-307`)."""
    os.makedirs(out_dir, exist_ok=True)
    for i in range(min(flow.shape[0], max_samples)):
        cv2.imwrite(os.path.join(out_dir, f"{tag}_s{i}_flow.png"),
                    flow_to_color(flow[i, :, :, :2]))
        if gt is not None:
            cv2.imwrite(os.path.join(out_dir, f"{tag}_s{i}_gt.png"),
                        flow_to_color(gt[i, :, :, :2]))
        if recon is not None:
            img = np.clip(recon[i, :, :, :3] * 255.0, 0, 255).astype(np.uint8)
            cv2.imwrite(os.path.join(out_dir, f"{tag}_s{i}_recon.png"), img)


def _wmean(pairs: list[tuple[float, int]]) -> float:
    """Row-weighted mean of per-batch (value, valid_rows) pairs — the
    full-split eval convention shared by evaluate_aee/evaluate_ucf101."""
    vals, ws = zip(*pairs)
    return float(np.average(vals, weights=ws))


def evaluate_aee(eval_fn, params, dataset, cfg: ExperimentConfig,
                 dump_dir: str | None = None) -> dict[str, float]:
    """Run the AEE protocol over the full validation split.

    Every val sample is counted exactly once for any eval_batch_size
    (matching the reference's full-split iteration,
    `flyingChairsTrain.py:227-236`): batches are ceil-divided and the
    final, short one is evaluated by tiling its `v` unseen rows
    cyclically across L = v/gcd(v, bs) full-shape calls — every row
    appears exactly bs/gcd times, so the mean of the L jitted
    batch-mean totals IS the uniform mean over the v rows, making
    `val_loss` exact for any eval_batch_size (VERDICT r04 item 7)
    whenever the loss is row-separable (all variants except
    `loss.occlusion`, whose visibility normalizer couples rows — there
    a split-wide val_loss from batch means is composition-dependent by
    definition). The eval_fn only ever sees the full batch shape: no
    extra jit compile, and the sharded path never receives a batch dim
    the mesh can't divide."""
    import math as _math

    bs = cfg.train.eval_batch_size
    n_val = max(dataset.num_val, 1)
    n_batches = -(-n_val // bs)  # ceil: cover the remainder batch too
    epes, aaes, totals = [], [], []
    # running aggregates (O(1) memory — the val split at native res is GBs)
    p_sum = g_sum = 0.0
    p_n = g_n = 0
    p_max = g_max = 0.0
    for bid in range(n_batches):
        batch = dataset.sample_val(bs, bid)
        valid = min(bs, n_val - bid * bs)
        if valid < bs:
            # remainder: replace sample_val's wrap-to-head padding (rows
            # from OTHER batches, which polluted val_loss) with the
            # cyclic self-tiling described in the docstring
            vrows = {k: np.asarray(v)[:valid] for k, v in batch.items()}
            n_tiles = valid // _math.gcd(valid, bs)
            tile_totals = []
            out = None
            for j in range(n_tiles):
                idx = np.arange(j * bs, (j + 1) * bs) % valid
                o = eval_fn(params, {k: v[idx] for k, v in vrows.items()})
                o = {k: np.asarray(x) for k, x in o.items()}
                if j == 0:
                    out = o  # rows 0..valid-1 are the unseen rows in order
                tile_totals.append(float(o["total"]))
            batch_total = float(np.mean(tile_totals))
        else:
            out = {k: np.asarray(v) for k, v in eval_fn(params, batch).items()}
            batch_total = float(out["total"])
        gt = batch["flow"][:valid]
        pred = postprocess_flow(out["flow"][:valid], cfg, gt.shape[1:3])
        # AEE per flow pair, averaged (multi-frame: all T-1 pairs, like
        # `sintelTrain.py:309-328`), row-weighted so a short final batch
        # contributes per-sample, not per-batch
        for p in range(0, gt.shape[-1], 2):
            epes.append((float(flow_epe(pred[..., p : p + 2], gt[..., p : p + 2])), valid))
            aaes.append((float(flow_aae(pred[..., p : p + 2], gt[..., p : p + 2])), valid))
        totals.append((batch_total, valid))
        pa, ga = np.abs(pred), np.abs(gt)
        p_sum += float(pa.sum()); p_n += pa.size; p_max = max(p_max, float(pa.max()))
        g_sum += float(ga.sum()); g_n += ga.size; g_max = max(g_max, float(ga.max()))
        if dump_dir and bid == 0:
            dump_visuals(dump_dir, f"val{bid}", pred,
                         out.get("recon"), gt)

    # flow-statistics report (reference `flyingChairsTrain.py:298-312`)
    return {
        "aee": _wmean(epes),
        "aae": _wmean(aaes),
        "val_loss": _wmean(totals),
        "pred_abs_mean": p_sum / max(p_n, 1),
        "pred_abs_max": p_max,
        "gt_abs_mean": g_sum / max(g_n, 1),
        "gt_abs_max": g_max,
    }


def evaluate_ucf101(eval_fn, params, dataset, cfg: ExperimentConfig,
                    n_classes: int = 101) -> dict[str, float]:
    """Action accuracy over one batch per class (`ucf101train.py:210-223`)."""
    bs = cfg.train.eval_batch_size
    correct, seen, totals = 0, 0, []
    per_class = hasattr(dataset, "val_clips")
    if per_class:
        n = min(n_classes, max(len(dataset.val_clips), 1))
    else:  # non-class datasets (synthetic): cover the val split exactly once
        n = -(-max(dataset.num_val, 1) // bs)
    for bid in range(n):
        batch = dataset.sample_val(bs, bid)
        valid = bs if per_class else min(bs, dataset.num_val - bid * bs)
        out = eval_fn(params, batch)
        logits = np.asarray(out["logits"])[:valid]
        correct += int(np.sum(np.argmax(logits, -1) == batch["label"][:valid]))
        seen += logits.shape[0]
        # weight the (jitted, whole-batch-mean) total by unseen rows so a
        # padded remainder batch doesn't over-weight its wrapped head
        # duplicates (same convention as evaluate_aee's wmean)
        totals.append((float(out["total"]), valid))
    return {
        "accuracy": correct / max(seen, 1),
        "val_loss": _wmean(totals),
    }


def evaluate_lm(eval_fn, params, dataset, cfg: ExperimentConfig,
                dump_dir: str | None = None) -> dict[str, float]:
    """Mean cross-entropy per position over the held-out rows, each once."""
    bs = cfg.train.eval_batch_size
    totals = []
    for bid in range(-(-max(dataset.num_val, 1) // bs)):
        valid = min(bs, dataset.num_val - bid * bs)
        rows = np.asarray(eval_fn(params, dataset.sample_val(bs, bid))["loss_rows"])
        totals.append((float(rows[:valid].mean()), valid))
    loss = _wmean(totals)
    return {"val_loss": loss, "val_perplexity": float(np.exp(min(loss, 50.0)))}


def _evaluate_action(eval_fn, params, dataset, cfg: ExperimentConfig,
                     dump_dir: str | None = None) -> dict[str, float]:
    return evaluate_ucf101(eval_fn, params, dataset, cfg)


#: task a model declares -> (eval_fn, params, dataset, cfg, dump_dir) -> metrics
EVALUATORS = {"flow": evaluate_aee, "action": _evaluate_action,
              "classify": _evaluate_action, "lm": evaluate_lm}
