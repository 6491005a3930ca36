"""Training-stack tests on the virtual 8-device CPU mesh: mesh construction,
LR schedule, sharded train step (flow / volume / two-stream), checkpoint
save-restore, and an end-to-end Trainer.fit on the synthetic dataset."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepof_tpu.core.config import (
    DataConfig,
    ExperimentConfig,
    LossConfig,
    MeshConfig,
    OptimConfig,
    TrainConfig,
)
from deepof_tpu.data import SyntheticData, build_dataset
from deepof_tpu.models.registry import build_model
from deepof_tpu.parallel.mesh import batch_sharding, build_mesh
from deepof_tpu.train import (
    CheckpointManager,
    Trainer,
    create_train_state,
    evaluate_aee,
    make_eval_fn,
    make_train_step,
    step_decay_schedule,
)
from deepof_tpu.train.state import make_optimizer
pytestmark = pytest.mark.slow  # full-model/train-step compiles; see pytest.ini

H, W = 64, 64


def _cfg(tmp_path, **data_kw) -> ExperimentConfig:
    data = dict(dataset="synthetic", image_size=(H, W), gt_size=(H, W),
                batch_size=8)
    data.update(data_kw)
    return ExperimentConfig(
        name="test",
        model="flownet_s",
        # thin trunk: these tests assert wiring/equivalence semantics that
        # are width-independent; full-width flownet_s costs ~30s/step of
        # pure compute on the single-core CPU mesh (VERDICT r03 item 8)
        width_mult=0.25,
        loss=LossConfig(weights=(16, 8, 4, 2, 1, 1)),
        optim=OptimConfig(learning_rate=1e-4, epochs_per_decay=2),
        data=DataConfig(**data),
        train=TrainConfig(num_epochs=1, log_every=1, eval_every=0,
                          ckpt_every_epochs=1, log_dir=str(tmp_path),
                          eval_amplifier=1.0, eval_clip=(-1e4, 1e4),
                          eval_batch_size=8, seed=0),
    )


def test_build_mesh_axes():
    mesh = build_mesh(MeshConfig())
    assert mesh.axis_names == ("data", "spatial", "time")
    assert mesh.devices.size == jax.device_count()
    mesh2 = build_mesh(MeshConfig(spatial=2))
    assert mesh2.shape["spatial"] == 2
    assert mesh2.shape["data"] == jax.device_count() // 2
    with pytest.raises(ValueError):
        build_mesh(MeshConfig(spatial=3))  # 8 % 3 != 0


def test_step_decay_schedule():
    sched = step_decay_schedule(
        OptimConfig(learning_rate=1.0, decay_factor=0.5, epochs_per_decay=2),
        steps_per_epoch=10)
    assert sched(0) == 1.0
    assert sched(19) == 1.0  # epoch 1
    assert sched(20) == 0.5  # epoch 2
    assert sched(40) == 0.25


@pytest.fixture(scope="module")
def flow_setup(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("flow")
    cfg = _cfg(tmp)
    mesh = build_mesh(cfg.mesh)
    trainer = Trainer(cfg, profile=False)
    return cfg, mesh, trainer


def test_train_step_decreases_loss(flow_setup):
    cfg, mesh, trainer = flow_setup
    ds = trainer.dataset
    batch = jax.device_put(ds.sample_train(8, iteration=0), batch_sharding(mesh))
    state = trainer.state
    first = None
    for _ in range(5):
        state, metrics = trainer.train_step(state, batch)
        total = float(metrics["total"])
        assert np.isfinite(total)
        if first is None:
            first = total
    assert total < first  # same batch, loss must go down
    assert metrics["scale_total"].shape == (6,)
    trainer.state = state


def test_eval_protocol_and_fit(flow_setup, tmp_path):
    cfg, mesh, trainer = flow_setup
    res = trainer.evaluate()
    assert {"aee", "aae", "val_loss"} <= set(res)
    assert np.isfinite(res["aee"])
    out = trainer.fit(num_epochs=1, max_steps=2)
    assert "steps_per_sec" in out
    # checkpoint written and resumable
    assert trainer.ckpt.latest_step() is not None
    restored = trainer.ckpt.restore(trainer.state)
    assert int(restored.step) == int(trainer.state.step)


def test_checkpoint_roundtrip(tmp_path):
    model = build_model("flownet_s", width_mult=0.25)
    tx = make_optimizer(OptimConfig(), lambda s: 1e-4)
    state = create_train_state(model, jnp.zeros((1, H, W, 6)), tx, seed=1)
    state = state.replace(step=state.step + 7)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
    mgr.save(state)
    template = create_train_state(model, jnp.zeros((1, H, W, 6)), tx, seed=2)
    restored = mgr.restore(template)
    assert int(restored.step) == 7
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b),
        state.params, restored.params)
    # keep=2 pruning
    for d in (8, 9, 10):
        mgr.save(state.replace(step=jnp.asarray(d, jnp.int32)))
    assert mgr.all_steps() == [9, 10]


def test_remat_train_step_matches(tmp_path):
    """jax.checkpoint'ed forward must give the same loss/grads (it only
    changes what is stored vs recomputed)."""
    import dataclasses

    cfg = _cfg(tmp_path)
    mesh = build_mesh(cfg.mesh)
    ds = SyntheticData(cfg.data)
    model = build_model("flownet_s", width_mult=0.25)
    tx = make_optimizer(cfg.optim, lambda s: 1e-4)
    batch = jax.device_put(ds.sample_train(8, iteration=0), batch_sharding(mesh))
    results = {}
    for remat in (False, True):
        c = cfg.replace(train=dataclasses.replace(cfg.train, remat=remat))
        state = create_train_state(model, jnp.zeros((8, H, W, 6)), tx, seed=0)
        step = make_train_step(model, c, ds.mean, mesh)
        _, metrics = step(state, batch)
        results[remat] = (float(metrics["total"]), float(metrics["grad_norm"]))
    assert np.isclose(results[False][0], results[True][0], rtol=1e-6)
    assert np.isclose(results[False][1], results[True][1], rtol=1e-5)


def test_occlusion_rejected_for_unsupported_models(tmp_path):
    """loss.occlusion only masks flow-only 2-frame models; anything else
    must fail at step-build time, not silently skip."""
    import dataclasses

    cfg = _cfg(tmp_path).replace(model="st_single")
    cfg = cfg.replace(loss=dataclasses.replace(cfg.loss, occlusion=True))
    mesh = build_mesh(cfg.mesh)
    model = build_model("st_single")
    with pytest.raises(ValueError, match="occlusion"):
        make_train_step(model, cfg, (0.0, 0.0, 0.0), mesh)


def test_grad_accum_matches_large_batch(tmp_path):
    """Two accumulated micro-batches == one optimizer step on the
    concatenated batch (losses are batch means, so gradients average)."""
    import dataclasses

    cfg = _cfg(tmp_path)
    mesh = build_mesh(cfg.mesh)
    ds = SyntheticData(cfg.data)
    model = build_model("flownet_s", width_mult=0.25)
    b0 = ds.sample_train(8, iteration=0)
    b1 = ds.sample_train(8, iteration=1)

    # accumulation: 2 micro-steps of 8
    acfg = cfg.replace(optim=dataclasses.replace(cfg.optim, grad_accum=2))
    tx_a = make_optimizer(acfg.optim, lambda s: 1e-4)
    state_a = create_train_state(model, jnp.zeros((8, H, W, 6)), tx_a, seed=0)
    init_params = jax.device_get(state_a.params)
    step_a = make_train_step(model, acfg, ds.mean, mesh)
    state_a, _ = step_a(state_a, jax.device_put(b0, batch_sharding(mesh)))
    mid = jax.device_get(state_a.params)
    jax.tree_util.tree_map(np.testing.assert_array_equal, init_params, mid)
    state_a, _ = step_a(state_a, jax.device_put(b1, batch_sharding(mesh)))

    # ... and the deferred update did land after the 2nd micro-step
    moved = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a)
                                  - np.asarray(jax.device_get(b))).max()),
        init_params, state_a.params))
    assert max(moved) > 0

    # Exact averaging equivalence needs a gradient-linear optimizer (Adam
    # normalizes, so any two runs differ by <= 2*lr and the comparison
    # proves nothing): SGD accum of 2x8 == SGD on the concatenated 16.
    import optax

    sgd_a = optax.MultiSteps(optax.sgd(1e-2), every_k_schedule=2)
    state_sa = create_train_state(model, jnp.zeros((8, H, W, 6)), sgd_a, seed=0)
    step_sa = make_train_step(model, acfg, ds.mean, mesh)
    for b in (b0, b1):
        state_sa, _ = step_sa(state_sa, jax.device_put(b, batch_sharding(mesh)))

    big = {k: np.concatenate([b0[k], b1[k]]) for k in b0}
    bcfg = cfg.replace(data=dataclasses.replace(cfg.data, batch_size=16))
    state_sb = create_train_state(model, jnp.zeros((16, H, W, 6)),
                                  optax.sgd(1e-2), seed=0)
    step_sb = make_train_step(model, bcfg, ds.mean, mesh)
    state_sb, _ = step_sb(state_sb, jax.device_put(big, batch_sharding(mesh)))

    # Tolerance note: the b=8-accum and b=16 runs are DIFFERENT XLA
    # programs whose f32 forward rounding differs, and the warp's
    # floor/clip indexing turns a rounding flip at an integer flow
    # boundary into a DISCRETE gradient jump at that pixel — observed as
    # isolated ~1e-2-relative param diffs (one SGD lr=1e-2 step). So the
    # MAX bound absorbs the few discontinuity-amplified elements, while
    # the 99.9th-percentile bound keeps the BULK of parameters tight
    # (ADVICE r04: a blanket 5e-2 rtol would also pass a sub-5%
    # systematic error like an off-by-one in the 1/K averaging; a
    # systematic bug shifts every element and trips the percentile).
    diffs, refs = [], []

    def _collect(a, b):
        diffs.append(np.abs(np.asarray(jax.device_get(a), np.float64)
                            - np.asarray(jax.device_get(b), np.float64)).ravel())
        refs.append(np.abs(np.asarray(jax.device_get(b), np.float64)).ravel())

    jax.tree_util.tree_map(_collect, state_sa.params, state_sb.params)
    d, r = np.concatenate(diffs), np.concatenate(refs)
    # loose envelope (the old allclose bound): holds EVERYWHERE
    loose = d > 5e-4 + 5e-2 * r
    assert not loose.any(), \
        f"{loose.sum()} elements beyond the warp-discontinuity envelope"
    # tight envelope: only the isolated warp-discontinuity pixels may
    # exceed it — a systematic error shifts every element and trips this
    tight_frac = float(np.mean(d > 5e-4 + 1e-3 * r))
    assert tight_frac < 1e-3, f"tight-envelope violations: {tight_frac:.2e}"


def test_ckpt_every_steps(tmp_path):
    """Step-granularity checkpoints: saves land mid-epoch, not just at
    epoch/ckpt_every_epochs boundaries (SURVEY.md §5.3)."""
    import dataclasses

    cfg = _cfg(tmp_path)
    cfg = cfg.replace(train=dataclasses.replace(
        cfg.train, ckpt_every_steps=2, ckpt_every_epochs=10**6,
        nan_guard=False))
    trainer = Trainer(cfg, profile=False)
    trainer.fit(num_epochs=1, max_steps=4)
    assert trainer.ckpt.latest_step() >= 4  # saved at step cadence (+final)


def test_nan_guard_rollback_aborts_after_retries(tmp_path):
    """Persistent divergence must abort (bounded rollbacks), not loop
    forever re-training the same region from the restored checkpoint."""
    cfg = _cfg(tmp_path)
    trainer = Trainer(cfg, profile=False)
    real_step = trainer.train_step

    def nan_step(state, batch):
        state, metrics = real_step(state, batch)
        metrics = dict(metrics)
        metrics["total"] = jnp.float32(np.nan)
        return state, metrics

    trainer.train_step = nan_step
    with pytest.raises(FloatingPointError, match="consecutive"):
        trainer.fit(num_epochs=1, max_steps=50)


def test_final_save_skipped_on_unchecked_nan(tmp_path):
    """Divergence in the trailing (never host-checked) steps must not be
    saved as the newest checkpoint — a poisoned final save would become
    the auto-resume AND rollback target, defeating both."""
    import dataclasses

    cfg = _cfg(tmp_path)
    # log_every larger than the run so no in-loop NaN check ever fires
    cfg = cfg.replace(train=dataclasses.replace(
        cfg.train, log_every=10**6, ckpt_every_epochs=10**6))
    trainer = Trainer(cfg, profile=False)
    real_step = trainer.train_step

    def nan_step(state, batch):
        state, metrics = real_step(state, batch)
        metrics = dict(metrics)
        metrics["total"] = jnp.float32(np.nan)
        return state, metrics

    trainer.train_step = nan_step
    trainer.fit(num_epochs=1, max_steps=3)
    # only the pre-step-1 rollback target exists; the poisoned final state
    # was refused and the in-memory state rolled back to match it
    assert trainer.ckpt.latest_step() == 0
    assert int(trainer.state.step) == 0


def test_flownet_c_learns_matching_below_zero_flow(tmp_path):
    """The r04 learning-evidence property, pinned: FlowNet-C with the
    task displacement scale matched to its correlation bins (max_shift
    8 px at 64 px = ~1 feature px at the 1/8-res corr grid, stride 1)
    descends WELL below the zero-flow AEE under the default unsupervised
    recipe within a few hundred steps — where FlowNet-S (which must
    discover correspondence from scratch) provably parks at the
    zero-flow level for any in-round budget (DESIGN.md r04; full run:
    artifacts/synthetic_fit_cpu_corr8.jsonl, 0.99 px at step 6500)."""
    import dataclasses

    cfg = _cfg(tmp_path)
    cfg = cfg.replace(
        model="flownet_c",
        train=dataclasses.replace(cfg.train, eval_amplifier=2.0,
                                  eval_clip=(-300.0, 250.0)))
    mesh = build_mesh(cfg.mesh)
    ds = SyntheticData(cfg.data, num_train=512, max_shift=8.0,
                       style="blobs", n_blobs=40)
    model = build_model("flownet_c", width_mult=0.25, max_disp=3,
                        corr_stride=1)
    tx = make_optimizer(cfg.optim, lambda s: 3e-4)
    state = create_train_state(model, jnp.zeros((8, H, W, 6)), tx, seed=0)
    step = make_train_step(model, cfg, ds.mean, mesh)
    eval_fn = make_eval_fn(model, cfg, ds.mean, mesh=mesh)

    vflows = np.concatenate([ds.sample_val(8, i)["flow"] for i in range(2)])
    zero_epe = float(np.sqrt((vflows ** 2).sum(-1)).mean())
    rng = np.random.RandomState(0)
    for _ in range(600):
        b = jax.device_put(ds.sample_train(8, rng=rng), batch_sharding(mesh))
        state, _ = step(state, b)
    res = evaluate_aee(eval_fn, state.params, ds, cfg)
    # the full-run curve's knee is between steps 250 and 500 (at batch
    # 16): baseline-level until ~250, 0.55x by 500. 600 steps at batch 8
    # sits past the knee; 0.85x still asserts genuine matching (a
    # zero-flow collapse sits at 1.0x) with slack for the smaller batch
    assert res["aee"] < 0.85 * zero_epe, (res["aee"], zero_epe)


def test_inception_learns_flow_below_zero_flow(tmp_path):
    """The r05 flagship learning-evidence property, pinned: Inception-v3
    flow (the model the reference actually trains,
    `flyingChairsTrain.py:103`) descends WELL below the zero-flow AEE
    under the default unsupervised recipe on the spatially varying
    affine field with a sub-pixel curriculum start — where the
    FlowNet-S trunk provably parks (corr(pred, gt) ~ 0, DESIGN.md
    "Learning evidence, r05"). Thin variant (width 0.25) for CI cost;
    both probe configs locked on by step ~2000 (full runs:
    artifacts/synthetic_fit_cpu_inc_{affine: 0.883 px full width,
    thin: 1.08 px, pin: 1.15 px}.jsonl). Early-exits at the bound, so
    the typical cost is ~the lock-on point, not the cap."""
    import dataclasses

    cfg = _cfg(tmp_path)
    cfg = cfg.replace(
        model="inception_v3", width_mult=1.0,  # model built thin below
        train=dataclasses.replace(cfg.train, eval_amplifier=2.0,
                                  eval_clip=(-300.0, 250.0)))
    mesh = build_mesh(cfg.mesh)
    ds = SyntheticData(cfg.data, num_train=8192, max_shift=4.0,
                       style="affine", n_blobs=40, feature_scale=16)
    model = build_model("inception_v3", width_mult=0.25)
    tx = make_optimizer(cfg.optim, lambda s: 5e-4)
    state = create_train_state(model, jnp.zeros((8, H, W, 6)), tx, seed=0)
    step = make_train_step(model, cfg, ds.mean, mesh)
    eval_fn = make_eval_fn(model, cfg, ds.mean, mesh=mesh)

    vflows = np.concatenate([ds.sample_val(8, i)["flow"] for i in range(2)])
    zero_epe = float(np.sqrt((vflows ** 2).sum(-1)).mean())
    bound = 0.9 * zero_epe
    rng = np.random.RandomState(0)
    best = float("inf")
    for s in range(2600):
        shift = min(0.25 + (4.0 - 0.25) * s / 1200.0, 4.0)
        b = jax.device_put(ds.sample_train(8, rng=rng, max_shift=shift),
                           batch_sharding(mesh))
        state, _ = step(state, b)
        # evals only once lock-on is possible; early-exit at the bound
        if s >= 1399 and (s + 1) % 200 == 0:
            best = min(best,
                       evaluate_aee(eval_fn, state.params, ds, cfg)["aee"])
            if best < bound:
                break
    assert best < bound, (best, zero_epe)


def test_volume_train_step(tmp_path):
    cfg = _cfg(tmp_path, time_step=3)
    mesh = build_mesh(cfg.mesh)
    ds = SyntheticData(cfg.data)
    model = build_model("flownet_s", flow_channels=4, width_mult=0.25)
    tx = make_optimizer(cfg.optim, lambda s: 1e-4)
    state = create_train_state(model, jnp.zeros((8, H, W, 9)), tx)
    step = make_train_step(model, cfg, ds.mean, mesh)
    batch = jax.device_put(ds.sample_train(8, iteration=0), batch_sharding(mesh))
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["total"]))


def test_two_stream_train_step(tmp_path):
    cfg = _cfg(tmp_path).replace(model="st_single")
    mesh = build_mesh(cfg.mesh)
    ds = SyntheticData(cfg.data)
    model = build_model("st_single")
    tx = make_optimizer(cfg.optim, lambda s: 1e-4)
    state = create_train_state(model, jnp.zeros((8, H, W, 6)), tx)
    step = make_train_step(model, cfg, ds.mean, mesh, smooth_border_mask=True)
    batch = jax.device_put(ds.sample_train(8, iteration=0), batch_sharding(mesh))
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["total"]))
    assert "accuracy" in metrics and "action_loss" in metrics


@pytest.mark.parametrize("model_name,weights,smoothness", [
    ("vgg16", (16, 8, 4, 2, 1), "depthwise"),
    ("inception_v3", (16, 8, 4, 2, 1, 1), "canonical"),
    ("st_baseline", (16, 8, 4, 2, 1, 1), "canonical"),
    ("ucf101_spatial", (16,), "canonical"),
])
def test_every_model_family_trains(tmp_path, model_name, weights, smoothness):
    """One sharded train step per remaining model family (flownet_s/c and
    st_single are covered elsewhere): finite loss, grads flow."""
    cfg = _cfg(tmp_path).replace(
        model=model_name,
        loss=LossConfig(weights=weights, smoothness=smoothness))
    mesh = build_mesh(cfg.mesh)
    ds = SyntheticData(cfg.data)
    model = build_model(model_name)
    tx = make_optimizer(cfg.optim, lambda s: 1e-4)
    channels = 3 if model_name == "ucf101_spatial" else 6
    state = create_train_state(model, jnp.zeros((8, H, W, channels)), tx)
    smooth_border = model_name in ("st_single", "st_baseline")
    step = make_train_step(model, cfg, ds.mean, mesh, smooth_border)
    batch = jax.device_put(ds.sample_train(8, iteration=0),
                           batch_sharding(mesh))
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["total"]))
    assert float(metrics["grad_norm"]) > 0


def test_transfer_init_chairs_to_sintel_shapes(tmp_path):
    """Cross-config transfer: 2-frame FlowNet-S pretrain -> T=4 volume
    model. Trunk convs graft; first conv (3T in-ch) and pyramid heads
    (2(T-1) out-ch) re-initialize."""
    import dataclasses

    from deepof_tpu.core.config import get_config
    from deepof_tpu.train.loop import Trainer

    src_dir = str(tmp_path / "chairs")
    cfg = get_config("flyingchairs").replace(model="flownet_s")
    cfg = cfg.replace(
        data=dataclasses.replace(cfg.data, dataset="synthetic",
                                 image_size=(32, 64), gt_size=(32, 64),
                                 batch_size=4, crop_size=None),
        train=dataclasses.replace(cfg.train, log_dir=src_dir,
                                  eval_batch_size=4, eval_amplifier=1.0))
    src_tr = Trainer(cfg)
    src_tr.ckpt.save(src_tr.state)
    src_params = src_tr.state.params

    tgt_dir = str(tmp_path / "sintel")
    tcfg = cfg.replace(
        data=dataclasses.replace(cfg.data, time_step=4),
        train=dataclasses.replace(cfg.train, log_dir=tgt_dir,
                                  eval_batch_size=4, eval_amplifier=1.0,
                                  init_from=src_dir))
    tgt_tr = Trainer(tcfg)
    tp = tgt_tr.state.params

    # trunk conv2 transferred exactly
    np.testing.assert_array_equal(
        np.asarray(tp["conv2"]["Conv_0"]["kernel"]),
        np.asarray(src_params["conv2"]["Conv_0"]["kernel"]))
    # first conv re-initialized (in-ch 12 vs 6: shapes differ)
    assert tp["conv1"]["Conv_0"]["kernel"].shape[2] == 12
    # pyramid head re-initialized (6 flow channels vs 2)
    assert tp["decoder"]["pr1"]["Conv_0"]["kernel"].shape[-1] == 6


def test_early_sigterm_latch_stops_before_first_step(tmp_path):
    """ADVICE r03: a SIGTERM during the unprotected window (model build /
    first compile, before fit() installs its handler) must still end in a
    clean checkpoint. The CLI installs `install_preemption_latch()` at
    entry; a latched signal makes fit() exit before its first step and
    run the normal finalize path."""
    import os as _os
    import signal as _signal

    from deepof_tpu.train import loop as loop_mod

    prev = _signal.getsignal(_signal.SIGTERM)
    loop_mod.install_preemption_latch()
    try:
        _os.kill(_os.getpid(), _signal.SIGTERM)  # latched, not fatal
        assert loop_mod._EARLY_SIGTERM["sig"] == _signal.SIGTERM
        trainer = Trainer(_cfg(tmp_path), profile=False)
        trainer.fit(num_epochs=1, max_steps=10)
        # no step ran (the latch converted to an immediate stop) and the
        # finalize path still wrote a resumable checkpoint
        assert int(trainer.state.step) == 0
        assert trainer.ckpt.latest_step() is not None
        assert loop_mod._EARLY_SIGTERM["sig"] is None  # consumed
        # post-fit the latch must NOT be re-armed: a SIGTERM after the
        # final checkpoint is committed should kill, not be swallowed
        assert _signal.getsignal(_signal.SIGTERM) == _signal.SIG_DFL
    finally:
        _signal.signal(_signal.SIGTERM, prev)
        loop_mod._EARLY_SIGTERM["sig"] = None


@pytest.mark.slow
def test_sigterm_graceful_checkpoint(tmp_path):
    """Preemption handling (SURVEY.md §5.3): SIGTERM mid-training ends the
    step loop cleanly — final NaN-checked checkpoint saved, exit 0, and
    the run is auto-resumable. Driven end-to-end through the CLI in a
    subprocess (signal handlers only work in a main thread)."""
    import os
    import signal as _signal
    import subprocess
    import sys
    import time as _time

    logdir = tmp_path / "run"
    env = dict(os.environ, PYTHONPATH="", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.Popen(
        [sys.executable, "-m", "deepof_tpu.cli", "train",
         "--preset", "flyingchairs", "--synthetic", "--steps", "5000",
         "--model", "flownet_s", "--set", "train.log_every=2",
         "--set", "width_mult=0.25",
         "--log-dir", str(logdir)],
        cwd=repo, env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    try:
        # wait for the IN-LOOP "first step" record: it is logged after
        # fit() installs the SIGTERM handler (the construction-time
        # "model parameters" info line is too early — a signal sent then
        # still hits the default handler and kills the process)
        mlog = logdir / "metrics.jsonl"
        deadline = _time.time() + 300
        while _time.time() < deadline:
            if mlog.exists() and "first step" in mlog.read_text():
                break
            _time.sleep(2)
        else:
            raise AssertionError("training never reached its first step")
        p.send_signal(_signal.SIGTERM)
        rc = p.wait(timeout=240)
    finally:
        if p.poll() is None:
            p.kill()
    assert rc == 0, rc
    text = mlog.read_text()
    assert "signal 15 received" in text
    # a checkpoint was committed and the run is resumable
    from deepof_tpu.train.checkpoint import CheckpointManager as _CM
    assert _CM(str(logdir / "ckpt")).latest_step() is not None


def test_data_stream_rng_resume_no_replay():
    """Resume must NOT replay the data stream from the beginning (the
    numpy data rng is not checkpointed): distinct start steps give
    distinct streams; equal inputs are deterministic; the replica
    contract (same mesh/seed/step => identical stream) holds."""
    from deepof_tpu.train.loop import data_stream_rng

    mesh = build_mesh(MeshConfig())
    a = data_stream_rng(mesh, 7, 0).randint(0, 2**31, 8)
    a2 = data_stream_rng(mesh, 7, 0).randint(0, 2**31, 8)
    b = data_stream_rng(mesh, 7, 1000).randint(0, 2**31, 8)
    c = data_stream_rng(mesh, 8, 0).randint(0, 2**31, 8)
    np.testing.assert_array_equal(a, a2)
    assert not np.array_equal(a, b)
    assert not np.array_equal(a, c)
