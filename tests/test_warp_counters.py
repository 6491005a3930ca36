"""The counters that say what the data-dependent warp did, from the op to
the train record: `ops.warp.warp_sweep_stats` -> the loss dict of each
pyramid level -> the step's metrics -> `metrics.jsonl`'s train records
(`warp_sweep_rows_by_scale`, `warp_gather_fallback_by_scale`), and the
per-launch choice they report: a two-lane-tile launch whose flow spans
more rows than `PALLAS_AUTO_MAX_SWEEP` takes the XLA gather, with the same
loss and gradients. `impl="auto"` asks `jax.default_backend()`; the tests
steer it here (kernels in interpret mode), never through an option."""

import json

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import deepof_tpu.ops.warp as warp_mod
from deepof_tpu.core.config import (DataConfig, ExperimentConfig, LossConfig,
                                    OptimConfig, TrainConfig)
from deepof_tpu.ops.warp import backward_warp, warp_sweep_stats


def _flow(rng, b, h, w, reach_y):
    return jnp.asarray(np.stack(
        [rng.uniform(-2, 2, (b, h, w)),
         rng.uniform(-reach_y, reach_y, (b, h, w))], -1), jnp.float32)


@pytest.mark.parametrize("impl,hw,reach,limit,want", [
    ("xla", (8, 16), 3.0, 160, (0, 0.0)),        # XLA sweeps nothing
    ("pallas", (8, 16), 0.0, 160, (2, 0.0)),     # zero flow: offsets 0 and 1
    ("pallas", (8, 200), 100.0, 4, (15, 0.0)),   # asked for: never the gather
    ("auto", (8, 16), 100.0, 4, (15, 0.0)),      # one tile: no limit
    ("auto", (8, 200), 0.0, 4, (2, 0.0)),        # two tiles, under the limit
    ("auto", (8, 200), 100.0, 4, (15, 1.0)),     # over it: the gather
    ("auto", (8, 200), 100.0, 15, (15, 0.0)),    # at it: still the kernels
    ("auto", (8, 300), 100.0, 4, (0, 0.0)),      # W > 256: XLA by shape
])
def test_sweep_stats_say_what_the_launch_does(rng, on_a_tpu, monkeypatch,
                                              impl, hw, reach, limit, want):
    monkeypatch.setattr(warp_mod, "PALLAS_AUTO_MAX_SWEEP", limit)
    flow = _flow(rng, 2, *hw, reach)
    if reach > hw[0]:  # pin both ends of the frame
        flow = flow.at[0, 0, 0, 1].set(reach).at[0, -1, 0, 1].set(-reach)
    rows, fallback = warp_sweep_stats(flow, impl)
    assert (float(rows), float(fallback)) == want


def test_auto_off_a_tpu_reports_xla(rng):
    assert [float(x) for x in warp_sweep_stats(
        _flow(rng, 2, 8, 16, 3.0), "auto")] == [0.0, 0.0]


def test_gather_branch_equals_the_kernels(rng, on_a_tpu, monkeypatch):
    """One image of the batch over the limit sends the launch to the
    gather: value and both gradients are the kernels' (and XLA's)."""
    img = jnp.asarray(rng.rand(3, 8, 136, 3), jnp.float32)
    ct = jnp.asarray(rng.randn(3, 8, 136, 3), jnp.float32)
    flow = _flow(rng, 3, 8, 136, 0.9).at[1].set(_flow(rng, 1, 8, 136, 7.0)[0])

    def value_and_grads(impl):
        val, vjp = jax.vjp(lambda i, f: backward_warp(i, f, impl=impl),
                           img, flow)
        return (val, *vjp(ct))

    monkeypatch.setattr(warp_mod, "PALLAS_AUTO_MAX_SWEEP", 4)
    assert float(warp_sweep_stats(flow, "auto")[1]) == 1.0
    gather = value_and_grads("auto")
    monkeypatch.setattr(warp_mod, "PALLAS_AUTO_MAX_SWEEP", 15)
    assert float(warp_sweep_stats(flow, "auto")[1]) == 0.0
    for ref in (value_and_grads("auto"), value_and_grads("pallas"),
                value_and_grads("xla")):
        for got, r in zip(gather, ref):
            np.testing.assert_allclose(np.asarray(got), np.asarray(r),
                                       rtol=1e-5, atol=1e-5)


class _ToyFlow(nn.Module):
    """Two flow heads, finest first: (B, H/2, W/2, 2) and (B, H/4, W/4, 2)."""
    flow_scales: tuple = (1.0, 1.0)
    max_downsample = 4

    @nn.compact
    def __call__(self, x):
        a = nn.Conv(4, (3, 3), strides=2)(x)
        b = nn.Conv(4, (3, 3), strides=2)(nn.relu(a))
        return [nn.Conv(2, (3, 3))(a), nn.Conv(2, (3, 3))(b)]


def _toy_step(rng, flow_scale, impl="auto"):
    from deepof_tpu.parallel.mesh import batch_sharding, build_mesh
    from deepof_tpu.train import create_train_state, make_train_step

    cfg = ExperimentConfig(loss=LossConfig(weights=(2, 1), warp_impl=impl))
    model = _ToyFlow(flow_scales=(flow_scale, flow_scale))
    mesh = build_mesh(cfg.mesh)
    pair = {k: jnp.asarray(rng.rand(8, 16, 272, 3) * 255, jnp.float32)
            for k in ("source", "target")}
    state = create_train_state(model, jnp.zeros((8, 16, 272, 6)),
                               optax.sgd(1e-3))
    step = make_train_step(model, cfg, (0.0, 0.0, 0.0), mesh)
    state, metrics = step(state, jax.device_put(pair, batch_sharding(mesh)))
    return jax.device_get(metrics), jax.device_get(state.params)


def test_toy_step_metrics_carry_the_counters(on_a_tpu, monkeypatch):
    """Level 0 is 8x136 (two lane tiles), level 1 4x68 (one). A flow over
    the limit flips level 0's fallback to 1.0; loss, gradient norm and the
    updated parameters equal the kernel path's."""
    monkeypatch.setattr(warp_mod, "PALLAS_AUTO_MAX_SWEEP", 4)
    calm, _ = _toy_step(np.random.RandomState(0), 0.01)
    assert all(2 <= n <= 3 for n in calm["scale_warp_sweep_rows"])
    assert calm["scale_warp_gather_fallback"].tolist() == [0.0, 0.0]

    wild, params = _toy_step(np.random.RandomState(0), 100.0)
    assert wild["scale_warp_sweep_rows"][0] > 4
    assert wild["scale_warp_gather_fallback"].tolist() == [1.0, 0.0]
    kern, kparams = _toy_step(np.random.RandomState(0), 100.0, impl="pallas")
    assert kern["scale_warp_gather_fallback"].tolist() == [0.0, 0.0]
    np.testing.assert_array_equal(kern["scale_warp_sweep_rows"],
                                  wild["scale_warp_sweep_rows"])
    # (the op itself agrees to 1e-5 above; a whole step's sums of 255-
    # scaled differences reorder, hence 2e-4)
    for key in ("total", "grad_norm", "scale_total"):
        np.testing.assert_allclose(wild[key], kern[key], rtol=2e-4)
    for got, ref in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(kparams)):
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=1e-6)


def test_train_record_holds_both_lists(tmp_path):
    """`Trainer.fit` folds the step's per-level counters into every train
    record, finest level first, beside `loss_*_by_scale`."""
    from deepof_tpu.train import Trainer

    cfg = ExperimentConfig(
        name="counters", model="flownet_s", width_mult=0.125,
        loss=LossConfig(weights=(16, 8, 4, 2, 1, 1), warp_impl="pallas"),
        optim=OptimConfig(learning_rate=1e-4),
        data=DataConfig(dataset="synthetic", image_size=(64, 64),
                        gt_size=(64, 64), batch_size=8),
        train=TrainConfig(num_epochs=1, log_every=1, eval_every=0,
                          log_dir=str(tmp_path), seed=0))
    Trainer(cfg, profile=False).fit(num_epochs=1, max_steps=2)
    records = [json.loads(line) for line in open(tmp_path / "metrics.jsonl")]
    train = [r for r in records if r.get("kind") == "train"]
    assert len(train) == 2
    for r in train:
        rows = r["warp_sweep_rows_by_scale"]
        assert len(rows) == len(r["loss_total_by_scale"]) == 6
        heights = [32, 16, 8, 4, 2, 1]
        assert all(1 <= n <= 2 * h - 1 for n, h in zip(rows, heights))
        assert r["warp_gather_fallback_by_scale"] == [0.0] * 6
