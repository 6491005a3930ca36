"""Label-free flow-quality observability (ISSUE 13, DESIGN.md "Quality
observability"): the census op's first direct unit tests, numpy-vs-jnp
scorer parity, deterministic sampling, the drop-not-block contract under
a wedged scorer, the drift verdict (fires on an injected shift, quiet on
the control), exact fleet merging of the quality histograms, `tail` exit
code 7, the per-scale training-loss telemetry, the eval-EPE trend block,
and the bench_trend / serve_bench --quality report schemas.

Fast tier throughout except the 2-replica chaos drill (chaos marker,
jax-free fake-executor replicas — the test_fleet cost profile).
"""

import dataclasses
import json
import os
import threading
import time

import numpy as np
import pytest

from deepof_tpu.core.config import get_config
from deepof_tpu.obs.export import (QUALITY_BUCKETS, ValueHistogram,
                                   merge_hists, parse_prometheus,
                                   percentile_ms, render_prometheus)
from deepof_tpu.obs.quality import (QualitySampler, QualityScorer,
                                    census_descriptors_np,
                                    census_distance_np, score_pair_np,
                                    warp_bilinear_np)
from deepof_tpu.obs.registry import merge_stats_blocks
from deepof_tpu.serve.engine import InferenceEngine, make_fake_forward

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _quality_cfg(rate=1.0, max_batch=4, timeout_ms=2.0, ref_samples=4,
                 queue_depth=128, budget=0.1, image_size=(32, 64), **obs_kw):
    cfg = get_config("flyingchairs")
    return cfg.replace(
        model="flownet_s", width_mult=0.25,
        data=dataclasses.replace(cfg.data, dataset="synthetic",
                                 image_size=image_size, gt_size=image_size),
        serve=dataclasses.replace(cfg.serve, max_batch=max_batch,
                                  batch_timeout_ms=timeout_ms,
                                  host="127.0.0.1", port=0),
        obs=dataclasses.replace(cfg.obs, quality_sample_rate=rate,
                                quality_ref_samples=ref_samples,
                                quality_queue_depth=queue_depth,
                                quality_budget=budget, **obs_kw),
        train=dataclasses.replace(cfg.train, eval_amplifier=1.0,
                                  eval_clip=(-1e6, 1e6)))


def _pairs(rng, n, hw=(30, 60)):
    return [(rng.randint(0, 255, (*hw, 3), dtype=np.uint8),
             rng.randint(0, 255, (*hw, 3), dtype=np.uint8))
            for _ in range(n)]


# ------------------------------------------------- census op (ops/census)


def test_census_transform_shape_and_descriptor_semantics(rng):
    """First direct unit tests for ops/census.py (no consumer had any):
    descriptor shape, bounded soft-sign values, and zero self-distance."""
    from deepof_tpu.ops.census import census_distance, census_transform

    img = rng.rand(2, 12, 16, 3).astype(np.float32)
    desc = np.asarray(census_transform(img, window=5))
    assert desc.shape == (2, 12, 16, 25)
    # soft-sign components live strictly inside (-1, 1)
    assert np.all(desc > -1.0) and np.all(desc < 1.0)
    # self-distance is exactly zero; distance is symmetric and positive
    # for distinct images
    d_self = np.asarray(census_distance(desc, desc))
    assert d_self.shape == (2, 12, 16, 1)
    assert np.all(d_self == 0.0)
    other = np.asarray(census_transform(
        rng.rand(2, 12, 16, 3).astype(np.float32), window=5))
    d_ab = np.asarray(census_distance(desc, other))
    d_ba = np.asarray(census_distance(other, desc))
    assert np.allclose(d_ab, d_ba)
    assert float(d_ab.mean()) > 0.1
    # saturating per-neighbor penalty: bounded by the window size
    assert float(d_ab.max()) < 25.0


def test_census_illumination_robustness_vs_charbonnier(rng):
    """The property census exists for: a global brightness shift moves
    the raw photometric distance a lot and the census distance barely —
    the pair distinguishes 'flows degraded' from 'the scene got darker'.
    """
    from deepof_tpu.ops.census import census_distance, census_transform

    img = rng.rand(1, 16, 20, 3).astype(np.float32) * 0.5 + 0.2
    brighter = img + 0.2  # global additive illumination change
    d_census = float(np.asarray(census_distance(
        census_transform(img), census_transform(brighter)))[
            :, 4:-4, 4:-4].mean())
    d_raw = float(np.mean(np.abs(img - brighter))) * 255.0
    # raw photometric sees a 51-gray-level shift; census sees almost
    # nothing (edge-replicated border components excluded)
    assert d_raw > 50.0
    assert d_census < 2.0


def test_census_numpy_reference_matches_ops(rng):
    """The scorer's numpy census (obs/quality.py) is the same transform
    as ops/census.py — pinned so the jax-free replica path and the
    jitted path can never drift apart."""
    from deepof_tpu.ops.census import census_distance, census_transform
    from deepof_tpu.ops.smoothness import to_grayscale

    img = rng.rand(1, 10, 14, 3).astype(np.float32)
    ref = np.asarray(census_transform(img, window=5))
    gray = np.asarray(to_grayscale(img * 255.0))[0]
    got = census_descriptors_np(gray, window=5)
    np.testing.assert_allclose(got, ref[0], rtol=1e-5, atol=1e-6)
    other = rng.rand(*got.shape).astype(np.float32)
    np.testing.assert_allclose(
        census_distance_np(got, other),
        np.asarray(census_distance(got[None], other[None]))[0],
        rtol=1e-5, atol=1e-6)


# ----------------------------------------------------- scorer math parity


def test_score_fn_numpy_jnp_parity(rng):
    """The jitted scorer (real-model engines) and the numpy reference
    (jax-free fake-executor replicas) agree to float precision at every
    grid relationship — equal, and downsampled flow grids."""
    import jax

    from deepof_tpu.obs.quality import make_score_fn

    jfn = jax.jit(make_score_fn())
    for shape, fshape in (((16, 16), (16, 16)), ((32, 48), (8, 12)),
                          ((30, 60), (8, 16))):
        x = rng.rand(*shape, 6).astype(np.float32) - 0.4
        flow = (rng.rand(*fshape, 2).astype(np.float32) - 0.5) * 3.0
        jv = np.asarray(jfn(x[None], flow[None]))
        nv = np.array(score_pair_np(x, flow))
        np.testing.assert_allclose(jv, nv, rtol=1e-4, atol=1e-5)


def test_numpy_warp_matches_ops_warp(rng):
    """warp_bilinear_np == ops/warp.backward_warp for in-bounds flows
    (the proxy's operating regime; the left/top saturation corner where
    the XLA path zeroes the fractional weight is excluded by keeping
    displacements inside the frame)."""
    from deepof_tpu.ops.warp import backward_warp

    img = rng.rand(1, 12, 14, 3).astype(np.float32)
    flow = (rng.rand(1, 12, 14, 2).astype(np.float32) - 0.5) * 2.0
    ref = np.asarray(backward_warp(img, flow, impl="xla"))[0]
    got = warp_bilinear_np(img[0], flow[0])
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_perfect_flow_scores_better_than_wrong_flow(rng):
    """The proxy is a quality signal, not noise: for a pure-translation
    pair, the true flow scores a (much) lower photo/census error than a
    wrong flow."""
    base = rng.randint(0, 255, (40, 52, 3)).astype(np.float32)
    shift = 3
    f1 = base[:, shift:, :] / 255.0   # f1[y, x] = base[y, x + 3]
    f2 = base[:, :-shift, :] / 255.0  # f2[y, x] = base[y, x]
    x = np.concatenate([f1, f2], axis=-1).astype(np.float32) - 0.4
    h, w = f1.shape[:2]
    true_flow = np.full((h, w, 2), 0.0, np.float32)
    true_flow[..., 0] = shift  # recon[y, x] = f2[y, x + 3] == f1[y, x]
    wrong_flow = -true_flow
    p_true, _, c_true = score_pair_np(x, true_flow)
    p_wrong, _, c_wrong = score_pair_np(x, wrong_flow)
    assert p_true < 0.5 * p_wrong
    assert c_true < 0.5 * c_wrong


# ------------------------------------------------------------- sampling


def test_sampler_deterministic_and_rate_faithful():
    s1 = QualitySampler(0.3, seed=7)
    s2 = QualitySampler(0.3, seed=7)
    picks1 = [s1.sample(i) for i in range(2000)]
    picks2 = [s2.sample(i) for i in range(2000)]
    assert picks1 == picks2  # pure in (seed, index)
    frac = sum(picks1) / len(picks1)
    assert 0.25 < frac < 0.35
    # a different seed samples a different set at the same rate
    assert [QualitySampler(0.3, seed=8).sample(i)
            for i in range(2000)] != picks1
    assert not any(QualitySampler(0.0, seed=7).sample(i) for i in range(50))
    assert all(QualitySampler(1.0, seed=7).sample(i) for i in range(50))


def test_engine_sampled_set_independent_of_batching(rng):
    """The sampled SET is a pure function of submission order: engines
    differing in max_batch (and so in batching/flush interleaving)
    sample exactly the same count from the same sequential workload."""
    pairs = _pairs(rng, 24)
    counts = []
    for max_batch in (1, 4):
        cfg = _quality_cfg(rate=0.5, max_batch=max_batch)
        with InferenceEngine(cfg,
                             forward_fn=make_fake_forward(0.5)) as eng:
            for prev, nxt in pairs:
                eng.submit(prev, nxt).result(30)
            assert eng._quality.drain(30)
            counts.append(eng.stats()["serve_quality_sampled"])
    assert counts[0] == counts[1]
    assert 0 < counts[0] < 24  # genuinely a sample, not all-or-nothing


# -------------------------------------------- off-path + parity contracts


def test_rate_zero_is_schema_and_response_invariant(rng):
    """obs.quality_sample_rate=0 (the default): no scorer exists, no
    serve_quality_* key appears anywhere in stats, and the flows are
    bitwise identical to a sampling engine's — scoring observes
    responses, never participates in them."""
    pairs = _pairs(rng, 8)

    def flows_at(rate):
        with InferenceEngine(_quality_cfg(rate=rate),
                             forward_fn=make_fake_forward(0.5)) as eng:
            out = [eng.submit(p, n).result(30)["flow"] for p, n in pairs]
            stats = eng.stats()
            quality = eng._quality
        return out, stats, quality

    off_flows, off_stats, off_quality = flows_at(0.0)
    on_flows, on_stats, on_quality = flows_at(1.0)
    assert off_quality is None
    assert on_quality is not None
    assert not any(k.startswith("serve_quality") for k in off_stats)
    assert any(k.startswith("serve_quality") for k in on_stats)
    for a, b in zip(off_flows, on_flows):
        assert np.array_equal(a, b)


def test_wedged_scorer_drops_never_blocks(rng):
    """The hot-path contract: a scorer wedged mid-score (queue_depth 1)
    costs SAMPLES (dropped-and-counted), never latency — every response
    resolves promptly while the scorer thread is stuck."""
    wedge = threading.Event()
    release = threading.Event()

    cfg = _quality_cfg(rate=1.0, queue_depth=1)
    with InferenceEngine(cfg, forward_fn=make_fake_forward(0.5)) as eng:

        def stuck_score(bucket, x, flow):
            wedge.set()
            release.wait(30)  # wedged until the test releases it
            return (1.0, 0.0, 0.0)

        eng._quality._score_fn = stuck_score
        pairs = _pairs(rng, 12)
        t0 = time.monotonic()
        futs = [eng.submit(p, n) for p, n in pairs]
        for f in futs:
            f.result(30)
        wall = time.monotonic() - t0
        assert wedge.wait(10)
        stats = eng.stats()
        release.set()  # let close() drain
        time.sleep(0.2)  # scorer empties its 1-slot queue before close
    assert wall < 10.0  # responses never waited on the wedged scorer
    assert stats["serve_quality_dropped"] >= 1
    assert (stats["serve_quality_sampled"]
            + stats["serve_quality_dropped"]) == 12


# ---------------------------------------------------------- drift verdict


def _controlled_scorer(**kw):
    """A QualityScorer whose photo value is the flow's [0,0,0] entry —
    the drift machinery driven with exact, chosen values."""
    return QualityScorer(
        lambda bucket, x, flow: (float(flow[0, 0, 0, 0]), 0.1, 0.2),
        sample_rate=1.0, ref_samples=4, drift_factor=2.0, budget=0.25,
        **kw)


def _feed(scorer, photo_values):
    x = np.zeros((2, 2, 6), np.float32)
    for v in photo_values:
        flow = np.full((1, 1, 2), v, np.float32)
        assert scorer.submit(x, flow, (2, 2), "f32", "cold")
    assert scorer.drain(30)


def test_drift_verdict_fires_on_shift_quiet_on_control():
    # control: stable distribution around the reference -> no breaches
    control = _controlled_scorer()
    try:
        _feed(control, [1.0, 1.1, 0.9, 1.0] + [1.0, 1.2, 0.8] * 6)
        v = control.stats()["serve_quality"]
        assert v["ref_p50"] == pytest.approx(1.0, abs=0.1)
        assert v["breaches"] == 0
        assert v["burn"] == 0.0
        assert v["exhausted"] is False
    finally:
        control.close()
    # shifted: post-reference photo error jumps past ref_p50 * factor
    shifted = _controlled_scorer()
    try:
        _feed(shifted, [1.0, 1.1, 0.9, 1.0] + [5.0] * 12)
        v = shifted.stats()["serve_quality"]
        assert v["breaches"] == 12
        assert v["bad_fraction"] == 1.0
        assert v["burn"] == pytest.approx(4.0)
        assert v["exhausted"] is True
        assert v["drift_ratio"] > 2.0
    finally:
        shifted.close()


def test_drift_reference_freezes_before_shift():
    """The reference forms from the FIRST ref_samples scored requests
    and never moves: a later shift cannot drag the baseline with it."""
    s = _controlled_scorer()
    try:
        _feed(s, [1.0] * 4)
        assert s.stats()["serve_quality"]["ref_p50"] == pytest.approx(1.0)
        _feed(s, [5.0] * 8)
        v = s.stats()["serve_quality"]
        assert v["ref_p50"] == pytest.approx(1.0)  # frozen
        assert v["current_p50"] == pytest.approx(5.0)
    finally:
        s.close()


# ----------------------------------------------------- merge / prometheus


def test_quality_stats_merge_exactly_by_registry_kind(rng):
    """Two engines' quality blocks merge by the registry's declared
    kinds: counters sum, per-key maps sum key-wise, the fixed-bucket
    histograms merge EXACTLY, derived verdict blocks drop."""
    blocks, hists = [], []
    for _ in range(2):
        with InferenceEngine(_quality_cfg(rate=1.0),
                             forward_fn=make_fake_forward(0.5)) as eng:
            for prev, nxt in _pairs(rng, 6):
                eng.submit(prev, nxt).result(30)
            assert eng._quality.drain(30)
            s = eng.stats()
        blocks.append({k: v for k, v in s.items()
                       if k.startswith("serve_")})
        hists.append(s["serve_quality_photo_hist"])
    merged = merge_stats_blocks(blocks)
    assert merged["serve_quality_scored"] == 12
    assert merged["serve_quality_scored_by_key"]["f32/cold"] == 12
    expect = merge_hists(hists)
    assert merged["serve_quality_photo_hist"] == expect
    for i in range(len(QUALITY_BUCKETS) + 1):
        assert expect["counts"][i] == sum(h["counts"][i] for h in hists)
    assert "serve_quality" not in merged  # derived: re-derive, never sum
    assert "serve_quality_photo_p50" not in merged


def test_quality_histogram_prometheus_render_is_unitless():
    """Quality histograms render without the latency "_ms" unit suffix
    (their bounds are raw proxy units) and round-trip the parser."""
    h = ValueHistogram(QUALITY_BUCKETS)
    for v in (0.01, 1.5, 900.0, 1e5):
        h.observe(v)
    text = render_prometheus({"serve_quality_photo_hist": h.snapshot()})
    assert "deepof_serve_quality_photo_ms" not in text
    parsed = parse_prometheus(text)
    assert parsed['deepof_serve_quality_photo_bucket{le="+Inf"}'] == 4
    assert parsed["deepof_serve_quality_photo_count"] == 4
    # the percentile reads off the shared fixed bounds
    assert percentile_ms(h.snapshot(), 0.5) in QUALITY_BUCKETS


# ------------------------------------------------------------ tail rc 7


def test_tail_exits_7_on_quality_drift(tmp_path, capsys):
    from deepof_tpu.cli import main as cli_main

    def run_dir(name, exhausted):
        d = tmp_path / name
        d.mkdir()
        (d / "metrics.jsonl").write_text("")
        (d / "heartbeat.json").write_text(json.dumps({
            "time": time.time(), "pid": os.getpid(), "step": 0,
            "serve_requests": 50, "serve_responses": 50,
            "serve_quality": {"scored": 50, "breaches": 20,
                              "bad_fraction": 0.4, "budget": 0.1,
                              "burn": 4.0, "exhausted": exhausted}}))
        return d

    rc = cli_main(["tail", "--log-dir", str(run_dir("drift", True))])
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["serve"]["quality"]["exhausted"] is True
    assert rc == 7
    assert cli_main(["tail", "--log-dir",
                     str(run_dir("control", False))]) == 0


def test_tail_fleet_exits_7_on_a_child_replicas_drift(tmp_path, capsys):
    """The degraded replica's verdict lives in ITS process dir; `tail
    --fleet` on the fleet root must surface it as rc 7."""
    from deepof_tpu.cli import main as cli_main

    (tmp_path / "metrics.jsonl").write_text("")
    child = tmp_path / "replica-1"
    child.mkdir()
    rec = {"kind": "serve", "step": 0, "time": time.time(),
           "serve_requests": 40, "serve_responses": 40,
           "serve_quality": {"scored": 40, "breaches": 30,
                             "bad_fraction": 0.75, "budget": 0.1,
                             "burn": 7.5, "exhausted": True}}
    (child / "metrics.jsonl").write_text(json.dumps(rec) + "\n")
    assert cli_main(["tail", "--log-dir", str(tmp_path)]) == 0  # no --fleet
    capsys.readouterr()
    rc = cli_main(["tail", "--log-dir", str(tmp_path), "--fleet"])
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["processes"]["replica-1"]["serve"]["quality"][
        "exhausted"] is True
    assert rc == 7


# --------------------------------------- per-scale training-loss records


def test_loss_dict_carries_smooth_alias(rng):
    """losses/photometric.py: every loss dict now names its smoothness
    component; smooth == U_loss + V_loss exactly."""
    from deepof_tpu.core.config import LossConfig
    from deepof_tpu.losses.photometric import loss_interp

    img = rng.rand(1, 16, 20, 3).astype(np.float32)
    flow = (rng.rand(1, 16, 20, 2).astype(np.float32) - 0.5) * 2.0
    ld, _ = loss_interp(flow, img, img, 1.0, LossConfig())
    assert float(ld["smooth"]) == pytest.approx(
        float(ld["U_loss"]) + float(ld["V_loss"]), rel=1e-6)


def test_per_scale_record_fields_shape():
    """train/loop.py per_scale_last + SCALE_RECORD_FIELDS: per-scale
    vectors fold into JSON lists."""
    from deepof_tpu.train.loop import SCALE_RECORD_FIELDS, per_scale_last

    assert [f for f, _ in SCALE_RECORD_FIELDS] == [
        "loss_total_by_scale", "loss_photo_by_scale",
        "loss_smooth_by_scale", "warp_sweep_rows_by_scale",
        "warp_gather_fallback_by_scale"]
    v = np.array([1.0, 0.5, 0.25])
    assert per_scale_last(v) == [1.0, 0.5, 0.25]
    assert per_scale_last(np.float32(0.123456789)) == [0.123457]
    assert json.dumps(per_scale_last(v))  # JSON-ready


def _abstract_step(family):
    """(jitted step, state specs, batch specs) of a small model of
    `family` on one device: traced by the test, never compiled."""
    import jax

    from deepof_tpu.data import build_dataset
    from deepof_tpu.parallel.mesh import build_mesh
    from deepof_tpu.train.warmup import abstract_train_step

    if family == "lm":
        cfg = get_config("lm")
        cfg = cfg.replace(lm=dataclasses.replace(
            cfg.lm, vocab_size=1000, seq_len=64, attn_block_q=16,
            loss_block=16))
    else:
        cfg = get_config("flyingchairs")
        cfg = cfg.replace(
            model={"flow": "flownet_s", "two_stream": "st_single"}[family],
            width_mult=0.25 if family == "flow" else 1.0,
            data=dataclasses.replace(cfg.data, dataset="synthetic",
                                     image_size=(64, 64), gt_size=(64, 64),
                                     batch_size=2))
    a = abstract_train_step(cfg, build_mesh(devices=jax.devices()[:1]),
                            build_dataset(cfg.data, lm=cfg.lm))
    return a.step, a.state, a.batch


@pytest.mark.parametrize("family", ["flow", "two_stream", "lm"])
def test_train_step_metric_ranks(family):
    """The contract `Trainer._on_metrics` reads a fetched record by: one
    step a dispatch, so `total`, `grad_norm`, `update_skipped` (and the
    two-stream scalars) are rank 0 and every per-scale / per-layer
    metric is a rank-1 vector — no leading axis of steps anywhere. (The
    language model's `loss_rows`, one value a row, is no record field.)"""
    import jax

    from deepof_tpu.train.step import LAYER_METRIC_PREFIX

    step, state, batch = _abstract_step(family)
    _, metrics = jax.eval_shape(step, state, batch)
    for key in ("total", "grad_norm", "update_skipped"):
        assert metrics[key].shape == (), key
    vectors = [k for k in metrics
               if k.startswith(("scale_", LAYER_METRIC_PREFIX))]
    assert vectors, sorted(metrics)
    for key in vectors:
        assert len(metrics[key].shape) == 1, (key, metrics[key].shape)
    for key in set(metrics) - set(vectors) - {"loss_rows"}:
        assert metrics[key].shape == (), key
    assert ("accuracy" in metrics) == (family == "two_stream")
    assert any(k.startswith(LAYER_METRIC_PREFIX) for k in vectors) == (
        family == "lm")


def test_train_step_metrics_carry_scale_smooth(rng):
    """train/step.py stacks the smooth component per scale alongside the
    reference-named keys — the record decomposition's device half."""
    import jax.numpy as jnp

    from deepof_tpu.core.config import LossConfig
    from deepof_tpu.losses.pyramid import pyramid_loss

    img = jnp.asarray(rng.rand(1, 16, 16, 3).astype(np.float32))
    pyramid = [(jnp.zeros((1, 8, 8, 2)), 1.0),
               (jnp.zeros((1, 4, 4, 2)), 2.0)]
    _, losses, _ = pyramid_loss(pyramid, img, img, LossConfig())
    for d in losses:
        assert "smooth" in d and "Charbonnier_reconstruct" in d


def test_analyze_surfaces_scale_fields_and_eval_trend():
    from deepof_tpu.analyze import eval_trend, summarize

    records = [
        {"kind": "train", "step": 100, "time": 1.0, "loss": 2.0,
         "loss_photo_by_scale": [1.5, 0.4], "loss_smooth_by_scale":
         [0.1, 0.02], "loss_total_by_scale": [1.6, 0.42]},
    ] + [{"kind": "eval", "step": s, "aee": a}
         for s, a in ((100, 5.0), (200, 4.0), (300, 3.5), (400, 3.4))]
    out = summarize(records)
    assert out["train"]["loss_photo_by_scale"] == [1.5, 0.4]
    assert out["eval_trend"]["regressing"] is False
    assert out["eval_trend"]["slope_aee_per_kstep"] < 0
    # a sustained climb past best flags as regressing with a + slope
    climbing = [{"kind": "eval", "step": s, "aee": a}
                for s, a in ((100, 3.0), (200, 3.3), (300, 3.8),
                             (400, 4.5))]
    trend = eval_trend(climbing)
    assert trend["regressing"] is True
    assert trend["slope_aee_per_kstep"] > 0
    assert trend["best_aee"] == 3.0
    # too few evals: no trend (never a crash)
    assert eval_trend(climbing[:2]) is None


# ------------------------------------------------------- report schemas


def test_bench_trend_schema_and_regression_flag(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_trend", os.path.join(REPO, "tools", "bench_trend.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    # two synthetic rounds: serve proxy collapses 50% in the newer one
    (tmp_path / "BENCH_r01.json").write_text(json.dumps({
        "serve_bench": {"value": 400.0, "speedup_vs_serial": 4.0},
        "data_bench": {"workers0": {"value": 100.0}}}))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps({
        "serve_bench": {"value": 200.0, "speedup_vs_serial": 4.1},
        "data_bench": {"workers0": {"value": 101.0}}}))
    report = mod.bench_trend(str(tmp_path), tolerance=0.3)
    for key in mod.REQUIRED_KEYS:
        assert key in report, key
    assert report["rounds"] == [1, 2] and report["latest_round"] == 2
    serve = report["series"]["bench_serve_requests_per_s"]
    assert [p["value"] for p in serve] == [400.0, 200.0]
    assert "bench_serve_requests_per_s" in report["regressions"]
    assert report["regressions"]["bench_serve_requests_per_s"][
        "worse_frac"] == pytest.approx(0.5)
    # the improved proxies did not flag
    assert "bench_data_w0_batches_per_s" not in report["regressions"]
    # a directory with no BENCH_rNN.json (the repo itself, since the proxy
    # records were deleted) is an empty trend, not an error
    empty = mod.bench_trend(REPO)
    assert empty["rounds"] == [] and empty["latest_round"] is None


def test_serve_bench_quality_schema(tmp_path):
    """serve_bench --quality (real flownet_s, one tier to stay fast):
    pinned top-level + per-tier keys, proxies finite and positive,
    overhead pair present."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_bench", os.path.join(REPO, "tools", "serve_bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    res = mod.quality_bench(requests=4, gap_ms=0.0, max_batch=2,
                            timeout_ms=2.0, bucket=(32, 64),
                            native_hw=(30, 60), tiers=("f32",),
                            sample_rate=0.5)
    for key in mod.QUALITY_REQUIRED_KEYS:
        assert key in res, key
    tier = res["tiers"]["f32"]
    for key in mod.QUALITY_TIER_REQUIRED_KEYS:
        assert key in tier, key
    assert tier["scored"] == 4
    for proxy in ("photo", "smooth", "census"):
        assert tier[proxy] is not None and np.isfinite(tier[proxy])
        assert tier[proxy] >= 0
    assert res["quality"]["scored"] == 4
    assert res["rps_quality_off"] and res["rps_quality_on"]


# --------------------------------------------- fleet chaos acceptance


@pytest.mark.chaos
def test_fleet_quality_merge_exact_and_degraded_replica_drift(rng,
                                                              tmp_path):
    """ISSUE 13 chaos acceptance, live 2-replica fleet with sampling on:
    (1) the router's /metrics quality-histogram bucket counts EXACTLY
    equal the sum of the replicas' /healthz counts; (2) an injected
    degraded-weights replica (replica_degrade: every dispatch past the
    arm point returns corrupted flow — latency/SLO stay perfect) trips
    the drift verdict and `tail --fleet` exits 7, while the control
    fleet stays rc 0."""
    cv2 = pytest.importorskip("cv2")
    import base64

    from test_fleet import _fleet_cfg, _get_json, _post, _start_router
    from deepof_tpu.cli import main as cli_main
    from deepof_tpu.serve.fleet import Fleet

    def still_body(hw):
        """prev == next (a textured STILL frame): the fake executor's
        flow (channel difference) is exactly zero, so the healthy proxy
        is near its floor and a degraded replica's corrupted flow (+25
        px on a textured image) shifts it unmistakably — the structured
        workload that makes drift visible on the fake executor."""
        img = rng.randint(1, 255, (*hw, 3), dtype=np.uint8)
        ok, buf = cv2.imencode(".png", img)
        assert ok
        b64 = base64.b64encode(buf.tobytes()).decode()
        return json.dumps({"prev": b64, "next": b64}).encode()

    def quality_fleet_cfg(log_dir, degrade=False):
        cfg = _fleet_cfg(log_dir, max_batch=4, timeout_ms=5.0, exec_ms=2.0,
                         buckets=((32, 64), (64, 64)))
        cfg = cfg.replace(obs=dataclasses.replace(
            cfg.obs, quality_sample_rate=1.0, quality_ref_samples=4,
            quality_budget=0.1))
        if degrade:
            cfg = cfg.replace(resilience=dataclasses.replace(
                cfg.resilience, faults=dataclasses.replace(
                    cfg.resilience.faults, enabled=True,
                    replica_degrade_at=(0,), replica_fault_after=6)))
        return cfg

    def drive(cfg, n_each):
        """n_each requests per bucket through the router; returns the
        router port + fleet handle context results."""
        with Fleet(cfg, 2) as fleet:
            fleet.start()
            fleet.wait_ready(min_ready=2, timeout_s=120)
            router, httpd, port = _start_router(cfg, fleet)
            try:
                for _ in range(n_each):
                    s1, _ = _post(port, still_body((30, 60)))
                    s2, _ = _post(port, still_body((60, 60)))
                    assert s1 == 200 and s2 == 200
                # quiesce: every replica scored everything it sampled
                deadline = time.monotonic() + 30
                while time.monotonic() < deadline:
                    healths = [_get_json(r.port, "/healthz")[1]
                               for r in fleet.ready_replicas()]
                    if all(h["serve_quality_scored"]
                           + h.get("serve_quality_errors", 0)
                           >= h["serve_quality_sampled"]
                           for h in healths):
                        break
                    time.sleep(0.1)
                from test_obs_plane import _get_json_text

                _, metrics_text = _get_json_text(port, "/metrics")
                parsed = parse_prometheus(metrics_text)
                hists = [h["serve_quality_photo_hist"] for h in healths]
                verdicts = [h["serve_quality"] for h in healths]
            finally:
                router.draining = True
                httpd.shutdown()
                httpd.server_close()
        return parsed, hists, verdicts

    # --- control fleet: exact merge + no drift anywhere --------------
    control_dir = tmp_path / "control"
    parsed, hists, verdicts = drive(quality_fleet_cfg(control_dir), 8)
    expect = merge_hists(hists)
    assert expect["count"] == 16  # every request sampled and scored
    cum = 0
    for bound, count in zip(expect["buckets_ms"], expect["counts"]):
        cum += count
        key = f'deepof_serve_quality_photo_bucket{{le="{_fmt(bound)}"}}'
        assert parsed[key] == cum, key
    assert parsed['deepof_serve_quality_photo_bucket{le="+Inf"}'] == 16
    assert parsed["deepof_serve_quality_scored"] == 16
    assert not any(v["exhausted"] for v in verdicts)
    # the router/Fleet were driven in-process: give the root dir the
    # (empty) metrics log run_fleet would have owned, so tail reads it
    (control_dir / "metrics.jsonl").touch()
    rc = cli_main(["tail", "--log-dir", str(control_dir), "--fleet"])
    assert rc == 0

    # --- degraded fleet: replica 0's weights corrupt mid-run ---------
    degraded_dir = tmp_path / "degraded"
    parsed, hists, verdicts = drive(
        quality_fleet_cfg(degraded_dir, degrade=True), 10)
    assert any(v["exhausted"] for v in verdicts), verdicts
    assert parsed["deepof_serve_quality_breaches"] >= 1
    (degraded_dir / "metrics.jsonl").touch()
    rc = cli_main(["tail", "--log-dir", str(degraded_dir), "--fleet"])
    assert rc == 7


def _fmt(bound: float) -> str:
    f = float(bound)
    return repr(int(f)) if f == int(f) else repr(f)
