"""Recipe-engine tests (ISSUE r20): deterministic multi-dataset mixing,
staged curricula, and the UCF-101 action workload.

Fast, jax-free pins first: the mixed stream's bit-identity across
worker counts and elastic generation bumps (the `derive_batch_rng`
contract extended to the member CHOICE), the strict `recipe_from_dict`
round-trip with indexed unknown-key rejection, the loud build-time
member-structure validation, the pure `plateau_reached` trigger, and
the jax-free stage-resume scan over fabricated manifests.

Slow tests (full XLA compiles, `pytest.ini` slow marker) then drive
`run_recipe` end to end: a two-stage Chairs-shaped curriculum whose
stage switch provably compiles nothing (the run ledger holds only
warmup 'aot' rows), stage-correct resume from a mid-stage checkpoint,
an injected-AEE plateau advance, and the st_single action head trained
through a recipe and queried via `predict_action`.
"""

import dataclasses
import hashlib
import json
import os

import numpy as np
import pytest

from deepof_tpu.core.config import (
    DataConfig,
    ExperimentConfig,
    LossConfig,
    MixtureMemberConfig,
    OptimConfig,
    RecipeConfig,
    StageConfig,
    TrainConfig,
    config_from_dict,
    recipe_from_dict,
)
from deepof_tpu.data.mixture import MixtureDataset, build_mixture
from deepof_tpu.data.pipeline import InputPipeline, derive_batch_rng
from deepof_tpu.parallel.mesh import elastic_stream_seed
from deepof_tpu.resilience import verify as ckpt_verify
from deepof_tpu.train import recipe as recipe_mod


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _mix_data_cfg(**kw) -> DataConfig:
    base = dict(dataset="synthetic", image_size=(32, 32), gt_size=(32, 32),
                batch_size=4, time_step=2)
    base.update(kw)
    return DataConfig(**base)


def _mix_stage(weights=(0.8, 0.2), **member_kw) -> StageConfig:
    members = tuple(
        MixtureMemberConfig(dataset="synthetic", weight=w, **member_kw)
        for w in weights)
    return StageConfig(name="mixstage", mixture=members)


def _batch_digest(batch: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(batch):
        v = np.asarray(batch[k])
        h.update(k.encode())
        h.update(str(v.dtype).encode())
        h.update(str(v.shape).encode())
        h.update(np.ascontiguousarray(v).tobytes())
    return h.hexdigest()


def _stream_digest(seed, num_workers: int, n_batches: int = 12) -> str:
    """sha256 over `n_batches` mixed batches delivered through the real
    worker pipeline — the exact path the Trainer consumes."""
    ds = build_mixture(_mix_data_cfg(), _mix_stage())
    pipe = InputPipeline(
        lambda i: ds.sample_train(4, rng=derive_batch_rng(seed, i)),
        num_workers=num_workers)
    try:
        h = hashlib.sha256()
        for _ in range(n_batches):
            h.update(_batch_digest(pipe.get()).encode())
        return h.hexdigest()
    finally:
        pipe.close()


# --------------------------------------------------------------------------
# mixed-stream determinism (tentpole contract)
# --------------------------------------------------------------------------

def test_mixed_stream_identical_across_worker_counts():
    """The mixed stream is bit-identical for num_workers in {0, 1, 4}:
    the member choice folds out of the per-batch rng, so assembly order
    and pool size are invisible in the delivered bytes."""
    digests = {w: _stream_digest(1234, num_workers=w) for w in (0, 1, 4)}
    assert digests[0] == digests[1] == digests[4]


def test_mixed_stream_identical_across_elastic_generation_bump():
    """Elastic seeding composes with the mixture unchanged: the same
    `elastic_stream_seed` word array replays the identical mixed stream
    at any worker count, and a bumped generation yields a decorrelated
    (but itself reproducible) stream."""
    g0 = elastic_stream_seed(7, host_index=0, num_hosts=2, generation=0,
                             start_step=0)
    g1 = elastic_stream_seed(7, host_index=0, num_hosts=2, generation=1,
                             start_step=0)
    assert _stream_digest(g0, 0) == _stream_digest(g0, 4)
    assert _stream_digest(g1, 0) == _stream_digest(g1, 4)
    # survivors must not replay draws the old generation trained on
    assert _stream_digest(g0, 0) != _stream_digest(g1, 0)


def test_mixture_draw_counters_split_by_weight():
    """Both members of an 0.8/0.2 mixture are actually drawn, roughly
    weight-proportionally, and the registry-declared counter block
    reports the split."""
    members = (MixtureMemberConfig(dataset="synthetic", weight=0.75),
               MixtureMemberConfig(dataset="synthetic", weight=0.25,
                                   time_step=0))
    ds = build_mixture(_mix_data_cfg(),
                       StageConfig(name="counts", mixture=members))
    picks = [ds._pick(derive_batch_rng(0, i)) for i in range(400)]
    frac = sum(1 for p in picks if p == 0) / len(picks)
    assert 0.6 < frac < 0.9  # weight-proportional, not degenerate
    for i in range(10):
        ds.sample_train(2, rng=derive_batch_rng(0, i))
    stats = ds.mixture_stats()["recipe_draws_by_dataset"]
    assert sum(stats.values()) == 10


def test_mixture_normalizes_t2_volume_to_pair_form():
    """A T=2 volume batch mixes structurally with Chairs-style pairs:
    normalize_batch splits (B, H, W, 6) into {source, target}."""
    from deepof_tpu.data.mixture import normalize_batch

    vol = np.arange(2 * 4 * 4 * 6, dtype=np.float32).reshape(2, 4, 4, 6)
    out = normalize_batch({"volume": vol,
                           "flow": np.zeros((2, 4, 4, 2), np.float32)})
    assert set(out) == {"source", "target", "flow"}
    np.testing.assert_array_equal(out["source"], vol[..., :3])
    np.testing.assert_array_equal(out["target"], vol[..., 3:])


def test_mixture_member_structure_mismatch_is_loud():
    """Members that disagree on implied time_step (T=2 pairs vs a T=3
    volume) must fail at BUILD time with the stage name in the message
    — never mid-run with a shape error from inside the compiled step."""
    members = (MixtureMemberConfig(dataset="synthetic", weight=0.5),
               MixtureMemberConfig(dataset="synthetic", weight=0.5,
                                   time_step=3))
    stage = StageConfig(name="badstage", mixture=members)
    with pytest.raises(ValueError) as ei:
        build_mixture(_mix_data_cfg(), stage)
    msg = str(ei.value)
    assert "badstage" in msg and "disagree" in msg


def test_mixture_rejects_empty_and_nonpositive_weights():
    with pytest.raises(ValueError, match="empty mixture"):
        build_mixture(_mix_data_cfg(), StageConfig(name="empty"))
    with pytest.raises(ValueError, match="positive"):
        MixtureDataset([object()], [0.0], ["x"], stage="zeroweight")


# --------------------------------------------------------------------------
# config round-trip (satellite 1)
# --------------------------------------------------------------------------

def _sample_recipe() -> RecipeConfig:
    return RecipeConfig(
        enabled=True,
        stages=(
            StageConfig(
                name="chairs",
                mixture=(MixtureMemberConfig("flyingchairs", 0.8),
                         MixtureMemberConfig("sintel", 0.2,
                                             sintel_pass="clean")),
                image_size=(64, 64), steps=4),
            StageConfig(name="sintel", advance="plateau",
                        plateau_window=4, plateau_slope=0.05,
                        learning_rate=1e-5),
        ))


def test_recipe_config_json_round_trip():
    """RecipeConfig survives asdict -> JSON -> recipe_from_dict exactly,
    tuples (stages, mixture, image_size) re-tupled at every level."""
    rc = _sample_recipe()
    back = recipe_from_dict(json.loads(json.dumps(dataclasses.asdict(rc))))
    assert back == rc


def test_experiment_config_round_trip_carries_recipe():
    """The full config tree round-trips through config_from_dict with the
    recipe block intact — the parent->replica config handoff contract."""
    cfg = ExperimentConfig(recipe=_sample_recipe())
    back = config_from_dict(json.loads(json.dumps(dataclasses.asdict(cfg))))
    assert back == cfg
    assert back.recipe.stages[0].mixture[1].sintel_pass == "clean"


@pytest.mark.parametrize("field,value", [("steps_per_call", 4),
                                         ("pipeline_depth", 0)])
def test_removed_train_fields_are_refused(field, value):
    """One step a dispatch and a fetch depth of two are not options: the
    command line and a saved config that still hold either field fail
    loudly, never run with it ignored."""
    from deepof_tpu.cli import _apply_override

    with pytest.raises(SystemExit, match="unknown config field"):
        _apply_override(ExperimentConfig(), f"train.{field}", str(value))
    saved = json.loads(json.dumps(dataclasses.asdict(ExperimentConfig())))
    assert field not in saved["train"]
    saved["train"][field] = value
    with pytest.raises(ValueError, match=field):
        config_from_dict(saved)


def test_recipe_from_dict_rejects_unknown_keys_with_indexed_path():
    """A typo at ANY nesting level fails loudly with the exact indexed
    path — never a silently-defaulted field."""
    with pytest.raises(ValueError, match=r"recipe"):
        recipe_from_dict({"enabledd": True})
    with pytest.raises(ValueError, match=r"recipe\.stages\[1\]"):
        recipe_from_dict({"stages": [{"name": "ok"}, {"stepss": 4}]})
    with pytest.raises(ValueError,
                       match=r"recipe\.stages\[0\]\.mixture\[1\]"):
        recipe_from_dict({"stages": [
            {"mixture": [{"dataset": "sintel"},
                         {"dataset": "sintel", "wieght": 0.5}]}]})


# --------------------------------------------------------------------------
# stage resolution + advance trigger + resume scan (jax-free)
# --------------------------------------------------------------------------

def test_stage_config_overrides_apply_and_sentinels_inherit():
    base = ExperimentConfig(data=_mix_data_cfg(time_step=2))
    stage = StageConfig(name="s", image_size=(48, 48), time_step=3,
                        model="st_single", learning_rate=5e-5,
                        loss_weights=(1.0, 2.0),
                        mixture=(MixtureMemberConfig("sintel", 1.0),))
    scfg = recipe_mod.stage_config(base, stage)
    assert scfg.data.image_size == (48, 48)
    assert scfg.data.time_step == 3
    assert scfg.data.dataset == "sintel"  # first member is the face
    assert scfg.model == "st_single"
    assert scfg.optim.learning_rate == 5e-5
    assert scfg.loss.weights == (1.0, 2.0)
    # sentinels inherit the base untouched
    assert scfg.data.gt_size == base.data.gt_size
    assert scfg.data.batch_size == base.data.batch_size


def test_plateau_reached_drill():
    """The pure plateau trigger on injected AEE series: a steep descent
    is not a plateau; a flat tail is; too few evals never trigger."""
    stage = StageConfig(name="p", advance="plateau", plateau_window=4,
                        plateau_slope=0.01, min_evals=3)
    improving = [{"step": 1000 * i, "aee": 10.0 - 2.0 * i}
                 for i in range(5)]
    assert not recipe_mod.plateau_reached(stage, improving)
    flat = [{"step": 1000 * i, "aee": 2.0} for i in range(5)]
    assert recipe_mod.plateau_reached(stage, flat)
    assert not recipe_mod.plateau_reached(stage, flat[:2])  # < min_evals
    # slight regression also counts as plateaued (no longer improving)
    regress = [{"step": 1000 * i, "aee": 2.0 + 0.001 * i}
               for i in range(5)]
    assert recipe_mod.plateau_reached(stage, regress)


def _recipe_base_cfg(tmp_path, stages) -> ExperimentConfig:
    return ExperimentConfig(
        data=_mix_data_cfg(),
        train=TrainConfig(log_dir=str(tmp_path / "run"), seed=0),
        recipe=RecipeConfig(enabled=True, stages=tuple(stages)))


def _fabricate_stage_ckpt(cfg, stage_idx: int, step: int,
                          extra: dict | None) -> None:
    step_dir = os.path.join(recipe_mod.stage_ckpt_dir(cfg, stage_idx),
                            f"step_{step}")
    os.makedirs(step_dir, exist_ok=True)
    with open(os.path.join(step_dir, "payload.bin"), "wb") as f:
        f.write(b"x" * 8)
    manifest = ckpt_verify.build_manifest(step_dir, step, extra=extra)
    ckpt_verify.write_manifest(step_dir, manifest)


def test_find_resume_stage_scans_newest_stage_first(tmp_path):
    stages = [StageConfig(name="a", steps=4), StageConfig(name="b"),
              StageConfig(name="c")]
    cfg = _recipe_base_cfg(tmp_path, stages)
    assert recipe_mod.find_resume_stage(cfg) == (0, {})  # fresh run
    _fabricate_stage_ckpt(cfg, 0, 4,
                          {"recipe_stage": 0, "recipe_stage_name": "a",
                           "stage_start_step": 0})
    _fabricate_stage_ckpt(cfg, 1, 7,
                          {"recipe_stage": 1, "recipe_stage_name": "b",
                           "stage_start_step": 4})
    idx, extra = recipe_mod.find_resume_stage(cfg)
    assert idx == 1  # highest stage with a committed step wins
    assert extra["stage_start_step"] == 4
    assert extra["recipe_stage_name"] == "b"


def test_find_resume_stage_falls_back_to_directory_index(tmp_path):
    """A manifest without the recipe extra (or no manifest at all) still
    resumes into the stage its DIRECTORY names — the scan is usable on
    checkpoints written before the recipe plane existed."""
    stages = [StageConfig(name="a"), StageConfig(name="b")]
    cfg = _recipe_base_cfg(tmp_path, stages)
    _fabricate_stage_ckpt(cfg, 1, 9, extra=None)
    idx, extra = recipe_mod.find_resume_stage(cfg)
    assert idx == 1
    assert "recipe_stage" not in extra


# --------------------------------------------------------------------------
# run_recipe advance logic with an injected AEE series (fast: FakeTrainer)
# --------------------------------------------------------------------------

class _FakeState:
    def __init__(self, step=0, params=None):
        self.step = step
        self.params = params if params is not None else {}

    def replace(self, **kw):
        out = _FakeState(self.step, self.params)
        for k, v in kw.items():
            setattr(out, k, v)
        return out


class _FakeLogger:
    def __init__(self, sink):
        self._sink = sink

    def log(self, kind, step, **fields):
        self._sink.append({"kind": kind, "step": step, **fields})


class _FakeTrainer:
    """Trainer facade driving run_recipe's advance logic without XLA:
    fit() 'trains' one step at a time and feeds the on_eval hook an
    injected AEE series — steeply improving for the first 4 steps, flat
    after — so the plateau trigger has a real trend to flatten on."""

    logs: list = []

    def __init__(self, scfg, dataset=None, mesh=None, ckpt_dir=None,
                 train_step=None, eval_fn=None, tx=None,
                 manifest_extra=None, extra_stats=None, on_eval=None,
                 **_kw):
        self.cfg = scfg
        self.state = _FakeState()
        self.steps_per_epoch = 1000
        self.logger = _FakeLogger(_FakeTrainer.logs)
        self._on_eval = on_eval
        self._extra_stats = extra_stats

    @staticmethod
    def _aee(step: int) -> float:
        return max(6.0 - step, 1.0)  # improves to step 5, then flat

    def fit(self, num_epochs=1, max_steps=None):
        n = (max_steps if max_steps is not None
             else num_epochs * self.steps_per_epoch)
        aee = float("nan")
        for _ in range(int(n)):
            step = int(self.state.step) + 1
            self.state = self.state.replace(step=step)
            if self._extra_stats is not None:
                self._extra_stats()  # the loop merges this every record
            aee = self._aee(step)
            if self._on_eval is not None and self._on_eval(step,
                                                           {"aee": aee}):
                break
        return {"aee": aee}


def test_run_recipe_plateau_advance_with_injected_aee(tmp_path,
                                                      monkeypatch):
    """The eval_trend-driven advance drill: the injected AEE series
    improves steeply (no trigger at min_evals) and then flattens — the
    stage must advance on 'plateau' exactly when the windowed slope
    flattens, not on its step budget, and the tail stage then runs its
    own fixed-step budget from the handoff step."""
    monkeypatch.setattr("deepof_tpu.train.loop.Trainer", _FakeTrainer)
    _FakeTrainer.logs = []
    from deepof_tpu.train.recipe import run_recipe

    stages = (
        StageConfig(name="plat",
                    mixture=(MixtureMemberConfig("synthetic", 1.0),),
                    advance="plateau", plateau_window=3,
                    plateau_slope=0.01, min_evals=3, steps=0),
        StageConfig(name="tail",
                    mixture=(MixtureMemberConfig("synthetic", 1.0),),
                    steps=2),
    )
    cfg = ExperimentConfig(
        data=_mix_data_cfg(),
        train=TrainConfig(log_dir=str(tmp_path / "run"), seed=0),
        # warmup=False: no XLA — the FakeTrainer never compiles
        recipe=RecipeConfig(enabled=True, stages=stages, warmup=False))
    out = run_recipe(cfg)
    # AEE series: 5,4,3,2,1,1,1 — window-3 slope first flattens at the
    # 7th eval (steps 5..7 all 1.0), so stage 0 ends exactly there
    assert out["per_stage"][0]["advance"] == "plateau"
    assert out["per_stage"][0]["end_step"] == 7
    assert out["advances"] == 1
    assert out["last_trigger"] == "plateau"
    assert out["final_stage"] == 1
    assert out["global_step"] == 9  # tail's 2-step budget from step 7
    assert out["per_stage"][1]["start_step"] == 7
    advance_logs = [r for r in _FakeTrainer.logs
                    if "recipe advance" in str(r.get("message", ""))]
    assert advance_logs and "'plateau'" in advance_logs[0]["message"]


def test_run_recipe_budget_cap_intersects_stage_budget(tmp_path,
                                                       monkeypatch):
    """--max-steps bounds TOTAL steps across stages: a cap inside stage
    0's own budget ends the run with cause 'budget' and no advance."""
    monkeypatch.setattr("deepof_tpu.train.loop.Trainer", _FakeTrainer)
    _FakeTrainer.logs = []
    from deepof_tpu.train.recipe import run_recipe

    stages = (StageConfig(name="a",
                          mixture=(MixtureMemberConfig("synthetic", 1.0),),
                          steps=8),
              StageConfig(name="b",
                          mixture=(MixtureMemberConfig("synthetic", 1.0),),
                          steps=4))
    cfg = ExperimentConfig(
        data=_mix_data_cfg(),
        train=TrainConfig(log_dir=str(tmp_path / "run"), seed=0),
        recipe=RecipeConfig(enabled=True, stages=stages, warmup=False))
    out = run_recipe(cfg, max_steps=5)
    assert out["global_step"] == 5
    assert out["final_stage"] == 0
    assert out["advances"] == 0
    assert out["per_stage"] == [{"stage": 0, "name": "a", "start_step": 0,
                                 "end_step": 5, "advance": "budget"}]


# --------------------------------------------------------------------------
# end-to-end recipe runs (slow; CLI subprocess)
#
# Deliberately subprocess-shaped: the tests exercise the real
# `train --recipe` / `predict --action` entry paths, each run in a
# process of its own (own signal handlers, threads and — off on cpu by
# the CLI's auto gate — compile cache).
# --------------------------------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_TWO_STAGE_RECIPE = {
    "stages": [
        {"name": "warm",
         "mixture": [{"dataset": "synthetic", "weight": 0.8},
                     {"dataset": "synthetic", "weight": 0.2}],
         "steps": 4},
        {"name": "main",
         "mixture": [{"dataset": "synthetic", "weight": 1.0}],
         "steps": 4},
    ]
}


def _cli_train(tmp_path, recipe: dict, *extra, model="flownet_s",
               width="0.25"):
    """One `train --recipe` CLI run; returns the printed summary dict."""
    import subprocess
    import sys

    recipe_path = tmp_path / "recipe.json"
    recipe_path.write_text(json.dumps(recipe))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    res = subprocess.run(
        [sys.executable, "-m", "deepof_tpu", "train", "--preset",
         "flyingchairs", "--synthetic", "--recipe", str(recipe_path),
         "--log-dir", str(tmp_path / "run"),
         "--set", f"model={model}", "--set", f"width_mult={width}",
         "--set", "train.log_every=1", "--set", "train.eval_every=0",
         *extra],
        capture_output=True, text=True, timeout=560, env=env, cwd=REPO)
    assert res.returncode == 0, (res.stdout[-1000:], res.stderr[-2000:])
    return json.loads(res.stdout.strip().splitlines()[-1])


def _run_records(tmp_path) -> list[dict]:
    with open(tmp_path / "run" / "metrics.jsonl") as f:
        return [json.loads(ln) for ln in f if ln.strip()]


@pytest.mark.slow
def test_cli_recipe_two_stage_end_to_end(tmp_path):
    """The acceptance drill: a two-stage curriculum advances on 'steps',
    grafts params across the boundary, rides recipe counters in the
    train records, and — with warmup — its ledger holds ONLY 'aot' rows:
    the stage switch provably compiled nothing. A second invocation over
    the finished run resumes stage-correct and trains zero steps."""
    out = _cli_train(tmp_path, _TWO_STAGE_RECIPE)
    assert out["final_stage"] == 1
    assert out["global_step"] == 8
    assert out["advances"] == 1
    assert out["last_trigger"] == "steps"
    assert [s["advance"] for s in out["per_stage"]] == ["steps", "steps"]
    assert out["per_stage"][1]["start_step"] == 4

    # zero-recompile proof: every ledger row is a warmup AOT compile of
    # a stage executable — nothing compiled at the stage boundary
    with open(tmp_path / "run" / "ledger.jsonl") as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    assert rows and all(r["compile_kind"] == "aot" for r in rows)
    names = {r["name"] for r in rows}
    assert {"train_step_stage0", "eval_step_stage0",
            "train_step_stage1", "eval_step_stage1"} <= names

    # recipe counters ride the train records (obs/registry.py keys)
    records = _run_records(tmp_path)
    trains = [r for r in records if r.get("kind") == "train"]
    assert any(r.get("recipe_stage") == 1 for r in trains)
    draws = [r["recipe_draws_by_dataset"] for r in trains
             if isinstance(r.get("recipe_draws_by_dataset"), dict)]
    assert draws and sum(draws[-1].values()) > 0
    # params grafted at the boundary, not re-initialized
    assert any(r.get("kind") == "info"
               and "grafted" in str(r.get("message", "")) for r in records)

    out2 = _cli_train(tmp_path, _TWO_STAGE_RECIPE)
    assert out2["final_stage"] == 1
    assert out2["global_step"] == 8
    assert out2["advances"] == 0


@pytest.mark.slow
def test_cli_recipe_resumes_mid_stage(tmp_path):
    """A budget-truncated run stops inside stage 1; the next invocation
    lands in stage 1 (manifest extra), restores the mid-stage step, and
    completes the stage — never restarts it."""
    out1 = _cli_train(tmp_path, _TWO_STAGE_RECIPE, "--max-steps", "6")
    assert out1["global_step"] == 6
    assert out1["per_stage"][-1]["stage"] == 1
    assert out1["per_stage"][-1]["advance"] == "budget"

    out2 = _cli_train(tmp_path, _TWO_STAGE_RECIPE)
    assert out2["final_stage"] == 1
    assert out2["global_step"] == 8
    assert out2["per_stage"][-1]["advance"] == "steps"
    assert out2["per_stage"][-1]["start_step"] == 4  # stage 1's own base


@pytest.mark.slow
def test_cli_recipe_action_workload_trains_and_predicts(tmp_path):
    """The UCF-101 action workload end to end on the synthetic path: an
    st_single recipe stage trains the two-stream head, and
    `predict --action` classifies a frame pair from the stage
    checkpoint, attaching labels from the labels file."""
    import subprocess
    import sys

    import cv2

    recipe = {"stages": [
        {"name": "action",
         "mixture": [{"dataset": "synthetic", "weight": 1.0}],
         "steps": 2}]}
    out = _cli_train(tmp_path, recipe, model="st_single", width="1.0")
    assert out["global_step"] == 2

    rng = np.random.RandomState(0)
    a, b = str(tmp_path / "a.png"), str(tmp_path / "b.png")
    cv2.imwrite(a, rng.randint(0, 255, (80, 96, 3), np.uint8))
    cv2.imwrite(b, rng.randint(0, 255, (80, 96, 3), np.uint8))
    labels = tmp_path / "labels.txt"
    labels.write_text("".join(f"class{i}\n" for i in range(101)))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    res = subprocess.run(
        [sys.executable, "-m", "deepof_tpu", "predict", "--preset",
         "flyingchairs", "--synthetic", "--set", "model=st_single",
         "--action", "--labels", str(labels),
         "--ckpt-dir", str(tmp_path / "run" / "ckpt-stage0"),
         "--pairs", f"{a}:{b}", "--out", str(tmp_path / "out")],
        capture_output=True, text=True, timeout=560, env=env, cwd=REPO)
    assert res.returncode == 0, (res.stdout[-1000:], res.stderr[-2000:])

    rows = json.load(open(tmp_path / "out" / "actions.json"))
    assert len(rows) == 1 and len(rows[0]["top"]) >= 1
    probs = [t["prob"] for t in rows[0]["top"]]
    assert all(0.0 <= p <= 1.0 for p in probs)
    assert probs == sorted(probs, reverse=True)  # ranked descending
    assert rows[0]["class"] == rows[0]["top"][0]["class"]
    assert rows[0]["label"].startswith("class")
