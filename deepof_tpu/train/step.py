"""pjit train/eval step functions.

One step builder serves every model family (the reference re-implements the
session loop per dataset, SURVEY.md §2.2):

  - 2-frame flow models (FlowNet-S/C, VGG16, Inception-v3): unsupervised
    pyramid loss over (source, target);
  - multi-frame volume models (Sintel T-volume): `pyramid_loss_multi`;
  - two-stream action models (STsingle/STbaseline): pyramid loss + action
    cross-entropy weighted by the finest flow weight, matching
    `ucf101wrapFlow.py:186-188`;
  - spatial-only classifier: cross-entropy;
  - language models (`models/lm/`, task "lm"): the family's own objective
    (next-token cross-entropy, or diffusion over blocks, whose noise is the
    step's own rng stream) over the vocabulary held, float32; the model
    takes its loss itself (`model.loss`), in blocks of positions, and
    hands back its layers' counters.

Data parallelism: the step is `jax.jit`-ed with the batch sharded over the
mesh "data" axis and the state replicated; XLA inserts the gradient
all-reduce over ICI from the sharding annotations (no hand-written psum
needed — SURVEY.md §2.7).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import optax

from ..core.config import ExperimentConfig, LossConfig
from ..losses.pyramid import (
    lrn_normalize,
    preprocess,
    pyramid_loss,
    pyramid_loss_multi,
)
from ..models.registry import compute_dtype as compute_dtype_of, task_of
from ..parallel.mesh import batch_sharding, replicated_sharding
from ..parallel.spatial import constrain_batch, mesh_context
from .state import TrainState

#: Step metrics that hold one value a LAYER of the model (a model's own
#: counters, whatever their names) carry this prefix; the loop writes each
#: into the periodic record under its name without it.
LAYER_METRIC_PREFIX = "layer_"

Mean = tuple[float, float, float]


def _tiled_mean(mean: Mean, channels: int) -> jnp.ndarray:
    reps = channels // len(mean)
    return jnp.tile(jnp.asarray(mean), reps)


def model_losses(
    model,
    params,
    batch: dict[str, jnp.ndarray],
    mean: Mean,
    loss_cfg: LossConfig,
    train: bool = False,
    dropout_rng: jax.Array | None = None,
    smooth_border_mask: bool = False,
    compute_dtype: Any = jnp.float32,
    remat: bool = False,
) -> tuple[jnp.ndarray, dict[str, Any]]:
    """Forward + objective. Returns (total_loss, aux dict with per-scale
    loss dicts, finest flow, reconstruction, and optional action logits)."""
    rngs = {"dropout": dropout_rng} if (train and dropout_rng is not None) else None
    if task_of(model) == "lm":
        # batch["tokens"][b, s + 1] int32, by the model's own objective
        # (`models/lm/model.py`). The model casts its float32 masters to its
        # own compute dtype and recomputes per block (`model.remat`). An
        # objective that draws noise draws it from the key the step splits
        # off `state.rng` each step: a resumed run continues the stream and
        # two steps never share a mask; an evaluation draws from a fixed key.
        noise = dropout_rng if dropout_rng is not None else jax.random.PRNGKey(0)
        out = model.apply({"params": params}, batch["tokens"], noise,
                          method="loss")
        rows = out.pop("loss_rows")
        return jnp.mean(rows), {"loss_rows": rows, "counters": out}
    # Spatial context parallelism: shard H over the "spatial" mesh axis (if
    # populated) so GSPMD partitions the convs with compiler-inserted halo
    # exchanges (SURVEY.md §5.7). Reads the mesh from the enclosing
    # `mesh_context` set by the step builders. The model's downsample
    # factor derives the gradient-safety fence (parallel/spatial.py).
    batch = constrain_batch(
        batch, max_downsample=getattr(model, "max_downsample", 64))

    def fwd(x, **kw):
        def inner(xx):
            with jax.named_scope("forward"):
                out = model.apply({"params": params},
                                  xx.astype(compute_dtype), rngs=rngs, **kw)
                return jax.tree_util.tree_map(
                    lambda a: a.astype(jnp.float32), out)

        # rematerialize the encoder-decoder in backward instead of storing
        # its activations (TrainConfig.remat; params are closure-captured,
        # which jax.checkpoint differentiates through)
        return jax.checkpoint(inner)(x) if remat else inner(x)

    aux: dict[str, Any] = {}

    if "volume" in batch:  # multi-frame Sintel volume
        vol = batch["volume"]
        with jax.named_scope("preprocess"):
            scaled = preprocess(vol, _tiled_mean(mean, vol.shape[-1]))
        flows = fwd(scaled)
        pyramid = list(zip(flows, model.flow_scales))
        with jax.named_scope("preprocess"):
            vol_norm = lrn_normalize(scaled)
        total, losses, recon = pyramid_loss_multi(pyramid, vol_norm, loss_cfg)
        aux.update(losses=losses, flow=flows[0] * model.flow_scales[0], recon=recon)
        return total, aux

    # Dual-stream augmentation (reference `flyingChairsTrain_vgg.py:186-195`):
    # the photo-augmented pair (net_*) feeds the network; the geo-only pair
    # (source/target) feeds the photometric loss.
    with jax.named_scope("preprocess"):
        src = preprocess(batch["source"], mean)
        tgt = preprocess(batch["target"], mean)
        net_src = (preprocess(batch["net_source"], mean)
                   if "net_source" in batch else src)
        net_tgt = (preprocess(batch["net_target"], mean)
                   if "net_target" in batch else tgt)
        pair = jnp.concatenate([net_src, net_tgt], axis=-1)

    if getattr(model, "classifier_only", False):
        logits = fwd(src, train=train)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, batch["label"])
        total = jnp.mean(ce)
        aux.update(logits=logits, action_loss=total)
        return total, aux

    is_two_stream = getattr(model, "has_action_head", False)
    if is_two_stream:
        flows, logits = fwd(pair, train=train)
    else:
        flows = fwd(pair)

    flows_bw = None
    if loss_cfg.occlusion and not is_two_stream:
        # fw/bw occlusion masking: second forward on the swapped pair
        # (LossConfig.occlusion; costs one extra model evaluation)
        flows_bw = fwd(jnp.concatenate([net_tgt, net_src], axis=-1))

    pyramid = list(zip(flows, model.flow_scales))
    with jax.named_scope("preprocess"):
        src_norm, tgt_norm = lrn_normalize(src), lrn_normalize(tgt)
    total, losses, recon = pyramid_loss(
        pyramid, src_norm, tgt_norm, loss_cfg,
        smooth_border_mask=smooth_border_mask, flow_pyramid_bw=flows_bw)
    aux.update(losses=losses, flow=flows[0] * model.flow_scales[0], recon=recon)

    if is_two_stream:
        ce = jnp.mean(
            optax.softmax_cross_entropy_with_integer_labels(logits, batch["label"]))
        # action loss enters with the finest flow weight (`ucf101wrapFlow.py:186-188`)
        total = total + loss_cfg.weights[0] * ce
        acc = jnp.mean((jnp.argmax(logits, -1) == batch["label"]).astype(jnp.float32))
        aux.update(logits=logits, action_loss=ce, accuracy=acc)
    return total, aux


def make_train_step(model, cfg: ExperimentConfig, mean: Mean, mesh,
                    smooth_border_mask: bool = False):
    """Build the jitted, sharded train step: (state, batch) -> (state, metrics).

    One optimizer step a call. The state is replicated over the mesh and
    donated, the batch split over "data"; the metrics come back replicated:
    `total`, `grad_norm`, `update_skipped` (and a two-stream model's
    `action_loss`, `accuracy`) scalars, every `scale_*` and `layer_*` metric
    one value a loss level or layer: what `Trainer._on_metrics` reads.
    """
    compute_dtype = compute_dtype_of(cfg)

    if cfg.loss.occlusion and (
            getattr(model, "has_action_head", False)
            or getattr(model, "classifier_only", False)
            or cfg.data.time_step > 2):
        raise ValueError(
            "loss.occlusion=true supports only flow-only 2-frame models; "
            f"model={cfg.model!r} time_step={cfg.data.time_step} would "
            "silently skip the masking")

    def apply_update(state: TrainState, grads, total, rng):
        """(new state, gradient norm, 1.0 where the update was skipped)."""
        grad_norm = optax.global_norm(grads)
        if not cfg.resilience.skip_nonfinite:
            return (state.apply_gradients(grads).replace(rng=rng), grad_norm,
                    jnp.float32(0.0))
        # Divergence-ladder rung 1 (DESIGN.md "Resilience"): detect
        # non-finite loss/grads BEFORE the update and skip it in place —
        # params, opt_state, and step stay exactly the previous state's
        # (rng still advances so a retried batch doesn't replay the same
        # dropout draw), and the host sees `update_skipped` per
        # step. One bad batch then costs one skipped update, not a
        # checkpoint rollback. The select is a no-op bitwise when finite:
        # jnp.where(True, new, old) returns `new` exactly.
        finite = jnp.isfinite(total) & jnp.isfinite(grad_norm)
        applied = state.apply_gradients(grads).replace(rng=rng)
        kept = state.replace(rng=rng)
        new_state = jax.tree_util.tree_map(
            lambda n, o: jnp.where(finite, n, o), applied, kept)
        return new_state, grad_norm, 1.0 - finite.astype(jnp.float32)

    def step(state: TrainState, batch):
        rng, dropout_rng = jax.random.split(state.rng)

        def loss_fn(params):
            with mesh_context(mesh):
                total, aux = model_losses(
                    model, params, batch, mean, cfg.loss, train=True,
                    dropout_rng=dropout_rng,
                    smooth_border_mask=smooth_border_mask,
                    compute_dtype=compute_dtype, remat=cfg.train.remat)
            return total, aux

        (total, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        with jax.named_scope("optimizer"):
            new_state, grad_norm, skipped = apply_update(state, grads, total,
                                                         rng)
        metrics = {"total": total, "grad_norm": grad_norm,
                   "update_skipped": skipped}
        if "losses" in aux:
            # per-pyramid-scale decomposition (finest first): photometric
            # ("Charbonnier_reconstruct") and smoothness ("smooth" = U+V)
            # components ride every metrics fetch — the loop folds them
            # into each periodic train record as loss_*_by_scale lists,
            # and beside them what each level's warp launch did: the rows
            # its sweep visited and whether the gather took it over
            for key in ("total", "Charbonnier_reconstruct", "U_loss",
                        "V_loss", "smooth", "warp_sweep_rows",
                        "warp_gather_fallback"):
                metrics[f"scale_{key}"] = jnp.stack([d[key] for d in aux["losses"]])
        for key in ("action_loss", "accuracy", "loss_rows"):
            if key in aux:
                metrics[key] = aux[key]
        # a model's own counters, one value a layer (a language model's
        # routing): they ride the loss fetch as the warp's counters do, and
        # the loop records every `layer_*` metric without knowing its name
        metrics.update({LAYER_METRIC_PREFIX + key: v
                        for key, v in aux.get("counters", {}).items()})
        return new_state, metrics

    repl, data = replicated_sharding(mesh), batch_sharding(mesh)
    return jax.jit(
        step,
        in_shardings=(repl, data),
        out_shardings=(repl, repl),
        donate_argnums=(0,),
    )


def make_eval_fn(model, cfg: ExperimentConfig, mean: Mean, mesh=None,
                 smooth_border_mask: bool = False):
    """Jitted eval forward: (params, batch) -> metrics + finest flow (already
    multiplied by flow_scale, before the eval amplifier/clip protocol which
    is host-side in `evaluate.py`). Reuses the training graph — the gen-1
    `testOF.py` design, not gen-2's graph-rebuilding evaluateNet
    (SURVEY.md §3.2)."""

    def fwd(params, batch):
        with mesh_context(mesh):
            total, aux = model_losses(
                model, params, batch, mean, cfg.loss, train=False,
                smooth_border_mask=smooth_border_mask)
        out = {"total": total}
        for key in ("flow", "recon", "logits", "loss_rows"):
            if key in aux:
                out[key] = aux[key]
        return out

    if mesh is None:
        return jax.jit(fwd)
    return jax.jit(fwd, in_shardings=(replicated_sharding(mesh), batch_sharding(mesh)))
