"""Execution-layer tests: persistent compile cache + AOT warmup.

Pins the layer's core contract (ISSUE r06 acceptance): after `warmup`
populates the on-disk cache for a config, a cold process reaches
first-step execution with ZERO recompilations — the train-step
executable loads from `artifacts/xla_cache` instead of paying minutes
of XLA. "Cold process" is simulated in-process
with `jax.clear_caches()` (drops jax's in-memory jit/pjit caches, so
the next call re-lowers and consults the persistent cache exactly as a
fresh interpreter would).
"""

import json
import os

import jax
import numpy as np
import pytest

from deepof_tpu.core.config import (
    DataConfig,
    ExperimentConfig,
    LossConfig,
    OptimConfig,
    TrainConfig,
)
from deepof_tpu.train import warmup

pytestmark = pytest.mark.slow  # full train-step XLA compiles; see pytest.ini


def _cfg(tmp_path, **train_kw) -> ExperimentConfig:
    """The headline PIPELINE (inception flagship pipeline shape is pinned
    on TPU by bench.py; here the suite's thin-trunk convention keeps the
    CPU mesh affordable)."""
    train = dict(num_epochs=1, log_every=1, eval_every=0,
                 ckpt_every_epochs=10**6, log_dir=str(tmp_path / "run"),
                 eval_amplifier=1.0, eval_clip=(-1e4, 1e4),
                 eval_batch_size=8, seed=0,
                 # explicit True: the auto default disables the cache on
                 # cpu (cross-process read corruption, TrainConfig
                 # comment); these tests exercise it in-process, which
                 # has been stable on this host
                 compile_cache=True,
                 compile_cache_dir=str(tmp_path / "xla_cache"))
    train.update(train_kw)
    return ExperimentConfig(
        name="warmup_test", model="flownet_s", width_mult=0.25,
        loss=LossConfig(weights=(16, 8, 4, 2, 1, 1)),
        optim=OptimConfig(learning_rate=1e-4, epochs_per_decay=2),
        data=DataConfig(dataset="synthetic", image_size=(64, 64),
                        gt_size=(64, 64), batch_size=8),
        train=TrainConfig(**train),
    )


@pytest.fixture
def restore_cache_dir():
    """Tests point the persistent cache at a tmp dir; restore the
    suite-wide dir (conftest's force_cpu_devices) afterwards so later
    tests keep their warm cache."""
    prev = jax.config.jax_compilation_cache_dir
    yield
    warmup.enable_compile_cache(prev)


def test_warmup_cold_then_warm_cache_hit(tmp_path, restore_cache_dir):
    """Second compile of the warmed executables is all hits, no misses —
    the 'second process compiles nothing' counter pin."""
    cfg = _cfg(tmp_path)
    r1 = warmup.warmup_compile(cfg)
    assert r1["cache_dir"] == str(tmp_path / "xla_cache")
    assert r1["cache"]["misses"] >= 2  # train + eval compiled cold
    assert r1["cache"]["hits"] == 0
    assert os.listdir(tmp_path / "xla_cache")  # entries actually on disk

    jax.clear_caches()  # simulate a cold process
    r2 = warmup.warmup_compile(cfg)
    assert r2["cache"]["misses"] == 0
    assert r2["cache"]["hits"] == r1["cache"]["misses"]
    # loading is the point: far cheaper than compiling
    assert r2["train_compile_s"] < r1["train_compile_s"]


def test_warmup_then_trainer_compiles_nothing(tmp_path, restore_cache_dir):
    """The end-to-end acceptance pin: warmup a config, then a cold
    Trainer's FIRST STEP executes with zero train-step recompilations —
    pinned by the compile_cache_misses counter the loop logs. This also
    guards warmup's batch/state spec against drifting from the real
    producer (any aval mismatch = different cache key = a miss here)."""
    from deepof_tpu.train.loop import Trainer

    cfg = _cfg(tmp_path)
    warmup.warmup_compile(cfg, include_eval=False)
    jax.clear_caches()  # cold process: in-memory jit caches gone

    trainer = Trainer(cfg, profile=False)
    trainer.fit(num_epochs=1, max_steps=4)

    records = [json.loads(ln) for ln in
               open(os.path.join(cfg.train.log_dir, "metrics.jsonl"))]
    first = [r for r in records if r.get("kind") == "info"
             and "first step" in str(r.get("message", ""))]
    assert first, "first-step info record missing"
    assert first[-1]["compile_cache_misses"] == 0, \
        "warmed train step recompiled — warmup spec drifted from the loop"
    assert first[-1]["compile_cache_hits"] >= 1


def test_trainer_first_step_counters_present_cold(tmp_path,
                                                  restore_cache_dir):
    """Without warmup the same counters surface a nonzero miss count —
    the observable that distinguishes a cold window from a warm one."""
    from deepof_tpu.train.loop import Trainer

    cfg = _cfg(tmp_path)
    trainer = Trainer(cfg, profile=False)
    trainer.fit(num_epochs=1, max_steps=2)
    records = [json.loads(ln) for ln in
               open(os.path.join(cfg.train.log_dir, "metrics.jsonl"))]
    first = [r for r in records if r.get("kind") == "info"
             and "first step" in str(r.get("message", ""))]
    assert first and first[-1]["compile_cache_misses"] >= 1


def test_enable_after_early_compile_still_initializes(tmp_path,
                                                      restore_cache_dir):
    """jax initializes its cache singleton at most once per process; a
    jit that runs before any cache dir is configured trips that latch
    and every later write silently no-ops (found end-to-end: the CLI's
    import-time jits disabled caching for the whole train process).
    enable_compile_cache must recover by resetting the singleton."""
    import jax.numpy as jnp
    from jax._src import compilation_cache as _cc

    # simulate a process whose first compile predates any cache config
    jax.config.update("jax_compilation_cache_dir", None)
    _cc.reset_cache()
    jax.clear_caches()
    jax.jit(lambda x: x + 1)(jnp.ones(4))  # trips the init-once latch
    assert _cc._cache is None

    warmup.enable_compile_cache(str(tmp_path / "late_cache"))
    jax.clear_caches()
    jax.jit(lambda x: x * 2)(jnp.ones(4))
    # the singleton must now be live against the late-configured dir
    assert _cc._cache is not None
    assert str(tmp_path / "late_cache") in str(_cc._cache._path)


def test_compile_cache_false_disables_even_when_already_enabled(
        tmp_path, restore_cache_dir):
    """train.compile_cache=False must actually turn caching off, even in
    a process where an earlier caller (the CPU test mesh) already
    enabled it."""
    from jax._src import compilation_cache as _cc

    warmup.enable_compile_cache(str(tmp_path / "on_cache"))
    cfg = _cfg(tmp_path, compile_cache=False)
    assert warmup.enable_for_config(cfg) is None
    assert jax.config.jax_compilation_cache_dir is None
    assert _cc._cache is None  # singleton dropped: no reads or writes


def test_compile_cache_auto_disables_on_cpu(tmp_path, restore_cache_dir):
    """The auto default (compile_cache=None) must not ENABLE the cache on
    the cpu backend (compiles of seconds; tests and rehearsals manage
    their own cache). Ambient state is left alone either way (the suite's process-wide cache must survive a
    default-config Trainer construction)."""
    ambient = str(tmp_path / "ambient_cache")
    warmup.enable_compile_cache(ambient)
    cfg = _cfg(tmp_path, compile_cache=None)
    assert jax.default_backend() == "cpu"  # suite invariant
    assert warmup.enable_for_config(cfg) is None
    # not redirected to cfg's dir, not torn down: ambient untouched
    assert jax.config.jax_compilation_cache_dir == ambient


def test_example_train_batch_matches_producer_stacking(tmp_path):
    """The example batch has the producer's keys, shapes and dtypes —
    the aval contract the cache key depends on."""
    from deepof_tpu.data import build_dataset

    cfg = _cfg(tmp_path)
    ds = build_dataset(cfg.data)
    b = warmup.example_train_batch(cfg, ds)
    produced = ds.sample_train(cfg.data.batch_size,
                               rng=np.random.RandomState(1))
    # the FULL producer key set, label included — extra keys are part of
    # the jitted signature and therefore of the cache key
    assert set(b) == set(produced) == {"source", "target", "flow", "label"}
    for key, v in produced.items():
        assert b[key].shape == np.shape(v), key
        assert b[key].dtype == np.asarray(v).dtype, key
    assert b["source"].shape == (8, 64, 64, 3)
    assert b["source"].dtype == np.float32


def test_warmup_cli_verb_refuses_without_active_cache(tmp_path,
                                                      restore_cache_dir,
                                                      capsys):
    """On cpu the auto default disables the cache; the warmup verb must
    refuse (rc=2, no compile) instead of paying minutes of XLA and
    persisting nothing."""
    from deepof_tpu.cli import main

    rc = main(["warmup", "--preset", "flyingchairs", "--synthetic",
               "--set", "width_mult=0.25", "--set", "model=flownet_s",
               "--no-eval"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "compile_cache=true" in err  # tells the user the opt-in


def test_warmup_cli_verb(tmp_path, restore_cache_dir, capsys):
    """`deepof_tpu warmup` prints one JSON object with compile timings
    and the cache delta, rc=0."""
    from deepof_tpu.cli import main

    rc = main(["warmup", "--preset", "flyingchairs", "--synthetic",
               "--set", "train.compile_cache=true",  # cpu: auto = off
               "--set", f"train.compile_cache_dir={tmp_path / 'cli_cache'}",
               "--set", "width_mult=0.25", "--set", "model=flownet_s",
               "--no-eval"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["train_compile_s"] > 0
    assert out["cache"]["requests"] >= 1
    assert os.listdir(tmp_path / "cli_cache")
