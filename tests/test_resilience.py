"""Resilience-layer tests (DESIGN.md "Resilience").

The fast-tier chaos suite (`-m "chaos and not slow"`) exercises every
injection site once — decode, assemble, fetch, dispatch, ckpt_save,
ckpt_restore, and the two post-commit tamper sites — against the exact
recovery path that guards it. The slow-tier acceptance drives a full
fit() through all four operational sites in a subprocess (the real CLI
entry path, isolated from the suite's process state) and pins the
determinism contract:
recoverable data faults leave the final params bit-identical to a
fault-free run.
"""

import glob
import json
import os
import signal
import subprocess
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from deepof_tpu.data.pipeline import InputPipeline, derive_batch_rng
from deepof_tpu.resilience import verify as ckpt_verify
from deepof_tpu.resilience.faults import (
    FaultConfig,
    FaultInjector,
    InjectedFault,
    build_injector,
)
from deepof_tpu.resilience.healing import HealingSampler, QuarantineError
from deepof_tpu.train.checkpoint import CheckpointManager
from deepof_tpu.train.metrics_log import AsyncFetcher
from deepof_tpu.train.state import TrainState

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ injector

def test_build_injector_disabled_is_none():
    """Zero-overhead contract: a disabled config never constructs an
    injector (sites guard on `is not None`)."""
    assert build_injector(None) is None
    assert build_injector(FaultConfig()) is None
    assert build_injector(FaultConfig(enabled=True)) is not None


def test_injector_probability_deterministic():
    """Probability scheduling is a pure function of (seed, site, index):
    identical across injector instances, different across seeds."""
    mk = lambda s: FaultInjector(FaultConfig(enabled=True, decode_p=0.3,
                                             seed=s))  # noqa: E731
    a = [mk(7).scheduled("decode", i) for i in range(200)]
    b = [mk(7).scheduled("decode", i) for i in range(200)]
    c = [mk(8).scheduled("decode", i) for i in range(200)]
    assert a == b
    assert a != c
    assert 20 <= sum(a) <= 100  # ~30% of 200, loose band


def test_injector_tolerates_scalar_at_override():
    """--set resilience.faults.dispatch_at=9 (unquoted scalar) must
    behave like (9,), not TypeError in the hot loop."""
    inj = FaultInjector(FaultConfig(enabled=True, dispatch_at=9))
    assert inj.hit("dispatch", 9)
    assert not inj.hit("dispatch", 8)


def test_injector_attempt_counting():
    """fail_attempts bounds persistence: a (site, index) faults that many
    checks, then recovers — transient (1) heals on first retry,
    retries+1 exhausts the retry budget and forces substitution."""
    inj = FaultInjector(FaultConfig(enabled=True, decode_at=(3,),
                                    fail_attempts=2))
    for _ in range(2):
        with pytest.raises(InjectedFault):
            inj.check("decode", 3)
    inj.check("decode", 3)  # third attempt recovers
    inj.check("decode", 4)  # unscheduled index never faults
    assert inj.stats()["decode"] == 2


# ----------------------------------------------------- derive_batch_rng

def test_derive_batch_rng_salt_streams():
    """salt=0 must be bit-identical to the pre-salt stream (the
    determinism contract of every existing run); salted streams are
    distinct, deterministic siblings (the substitute draws)."""
    base = np.array([11, 22], np.uint32)
    words = np.array([11, 0, 22, 0, 5, 0], np.uint32)  # pre-salt layout
    legacy = np.random.RandomState(words).randint(0, 2**31, 8)
    np.testing.assert_array_equal(
        derive_batch_rng(base, 5).randint(0, 2**31, 8), legacy)
    np.testing.assert_array_equal(
        derive_batch_rng(base, 5, salt=0).randint(0, 2**31, 8), legacy)
    s1 = derive_batch_rng(base, 5, salt=1).randint(0, 2**31, 8)
    s1b = derive_batch_rng(base, 5, salt=1).randint(0, 2**31, 8)
    np.testing.assert_array_equal(s1, s1b)
    assert not np.array_equal(s1, legacy)


# -------------------------------------------------- self-healing data path

def _sample(i, rng):
    return {"x": rng.randint(0, 1000, 4)}


def _healer(injector=None, **kw):
    kw.setdefault("retries", 2)
    kw.setdefault("backoff_s", 0.0)
    return HealingSampler(lambda i, r: derive_batch_rng(9, i, salt=r),
                          _sample, injector=injector, **kw)


@pytest.mark.chaos
def test_healing_transient_retry_is_bit_identical():
    """decode site, transient: the retry re-derives the rng, so the
    healed stream equals the fault-free stream exactly — the substrate
    of the acceptance determinism pin."""
    inj = FaultInjector(FaultConfig(enabled=True, decode_at=(2,),
                                    fail_attempts=1))
    healed, clean = _healer(inj), _healer()
    for i in range(6):
        np.testing.assert_array_equal(healed(i)["x"], clean(i)["x"])
    assert healed.stats() == {"sample_retries": 1, "quarantined": 0,
                              "substituted": 0}


@pytest.mark.chaos
def test_healing_quarantine_and_deterministic_substitute():
    """decode site, persistent: the retry budget exhausts, the draw is
    quarantined (counted + listed) and replaced by the salt=1 sibling
    draw — a pure function of (stream, index, round), so identical for
    any worker count."""
    events = []
    inj = FaultInjector(FaultConfig(enabled=True, decode_at=(1,),
                                    fail_attempts=3))  # = retries + 1
    h = _healer(inj, log=events.append)
    out = h(1)
    np.testing.assert_array_equal(
        out["x"], _sample(1, derive_batch_rng(9, 1, salt=1))["x"])
    assert h.stats() == {"sample_retries": 2, "quarantined": 1,
                         "substituted": 1}
    assert h.quarantine_log[0]["index"] == 1
    assert events and "quarantined" in events[0]
    # other indices untouched
    np.testing.assert_array_equal(h(2)["x"], _healer()(2)["x"])


def test_healing_heals_corrupt_payload_valueerror():
    """A truncated .flo surfaces as ValueError (io/flo.py) — the
    quarantine path must treat it like any persistent per-sample decode
    fault, not let it kill the run."""
    # "sample X's .flo is truncated": the fault follows the DRAWN sample
    # (round 0's draw), so the substitute redraw — different samples for
    # the same batch index — heals it
    bad = _sample(4, derive_batch_rng(9, 4, salt=0))["x"].tolist()

    def sample(i, rng):
        out = _sample(i, rng)
        if out["x"].tolist() == bad:
            raise ValueError("truncated flow data")
        return out

    h = HealingSampler(lambda i, r: derive_batch_rng(9, i, salt=r), sample,
                       retries=1, backoff_s=0.0, substitutes=2)
    out = h(4)  # substituted from the salt=1 redraw (different draw, same shape)
    assert out["x"].shape == (4,)
    assert h.stats()["quarantined"] == 1 and h.stats()["substituted"] == 1


@pytest.mark.chaos
def test_healing_gives_up_when_data_path_is_down():
    inj = FaultInjector(FaultConfig(enabled=True, decode_at=(0,),
                                    fail_attempts=10**6))
    h = _healer(inj, retries=1, substitutes=1)
    with pytest.raises(QuarantineError, match="data path is down"):
        h(0)


@pytest.mark.chaos
@pytest.mark.parametrize("workers", [0, 2])
def test_pipeline_worker_retry_transient(workers):
    """assemble site: a transient worker error is retried (make_batch is
    index-pure, so the retry is bit-identical) instead of dooming
    delivery from that index on."""
    calls = {}

    def flaky(i):
        calls[i] = calls.get(i, 0) + 1
        if i == 1 and calls[i] == 1:
            raise OSError("transient")
        return {"i": np.array([i])}

    p = InputPipeline(flaky, num_workers=workers, retries=1, backoff_s=0.0)
    try:
        assert [int(p.get()["i"][0]) for _ in range(4)] == [0, 1, 2, 3]
        assert p.stats()["retries"] == 1
    finally:
        p.close()


@pytest.mark.chaos
def test_pipeline_does_not_retry_quarantine_error():
    """QuarantineError is the healing ladder's TERMINAL verdict: the
    pipeline's own retry rung must surface it immediately, not re-run
    the whole exhausted ladder (which would double-count quarantines)."""
    calls = {"n": 0}

    def down(i):
        calls["n"] += 1
        raise QuarantineError("data path down")

    p = InputPipeline(down, num_workers=0, retries=3, backoff_s=0.0)
    try:
        with pytest.raises(QuarantineError):
            p.get()
        assert calls["n"] == 1  # no retries of the terminal error
    finally:
        p.close()


@pytest.mark.chaos
def test_pipeline_retry_exhaustion_still_surfaces():
    def always_bad(i):
        if i == 0:
            raise OSError("persistent")
        return {"i": np.array([i])}

    p = InputPipeline(always_bad, num_workers=1, retries=2, backoff_s=0.0)
    try:
        with pytest.raises(OSError, match="persistent"):
            p.get()
    finally:
        p.close()


# ------------------------------------------------------------ fetch site

@pytest.mark.chaos
def test_fetcher_retries_transient_fetch_faults():
    inj = FaultInjector(FaultConfig(enabled=True, fetch_at=(0,),
                                    fail_attempts=1))
    got = []
    f = AsyncFetcher(depth=2, fetch_fn=lambda t: t, retries=2, backoff_s=0.0,
                     injector=inj)
    try:
        f.submit(("t", 0, True), {"total": 1.0}, lambda tag, m: got.append(m))
        assert f.drain(timeout=10.0)
        assert got == [{"total": 1.0}]
        assert f.stats()["fetch_retries"] == 1
    finally:
        f.close()


@pytest.mark.chaos
def test_fetcher_exhausted_retries_surface():
    inj = FaultInjector(FaultConfig(enabled=True, fetch_at=(0,),
                                    fail_attempts=10))
    got = []
    f = AsyncFetcher(depth=2, fetch_fn=lambda t: t, retries=1, backoff_s=0.0,
                     injector=inj)
    try:
        f.submit(("t", 0, True), {"total": 1.0}, lambda tag, m: got.append(m))
        # the consumer thread's error surfaces on the loop's next barrier
        with pytest.raises(InjectedFault):
            f.drain(timeout=10.0)
        assert got == [] and f.stats()["fetch_retries"] == 1
        # and is raised once: the fetcher stays usable (fetch 1 is clean)
        f.submit(("t", 1, True), {"total": 2.0}, lambda tag, m: got.append(m))
        assert f.drain(timeout=10.0) and got == [{"total": 2.0}]
    finally:
        f.close()


# ---------------------------------------------------------- dispatch site

@pytest.mark.chaos
def test_poison_batch_and_dispatch_hit():
    from deepof_tpu.train.loop import _poison_batch

    inj = FaultInjector(FaultConfig(enabled=True, dispatch_at=(6,)))
    assert not inj.hit("dispatch", 5)
    assert inj.hit("dispatch", 6)
    assert not inj.hit("dispatch", 6)  # consume-once
    batch = {"source": np.zeros((2, 3, 3, 3), np.float32),
             "label": np.zeros((2,), np.int32)}
    out = _poison_batch(batch)
    assert np.isnan(np.asarray(out["source"])).sum() == 1
    np.testing.assert_array_equal(out["label"], batch["label"])
    assert not np.isnan(batch["source"]).any()  # input not mutated


# ------------------------------------------------------ verified ckpts

def _mk_state(step: int, val: float) -> TrainState:
    tx = optax.sgd(0.1)
    params = {"w": jnp.full((4,), float(val))}
    return TrainState(step=jnp.asarray(step, jnp.int32), params=params,
                      opt_state=tx.init(params), rng=jax.random.PRNGKey(0),
                      tx=tx)


def _largest_file(d):
    return max(((os.path.getsize(p), p)
                for p in glob.glob(os.path.join(d, "**"), recursive=True)
                if os.path.isfile(p)))[1]


def test_manifest_written_and_verifies(tmp_path):
    msgs = []
    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=5,
                            log=lambda s, m: msgs.append(m),
                            config_digest="cafe0001")
    mgr.save(_mk_state(1, 1.0))
    mgr.finalize()
    mans = glob.glob(str(tmp_path / "ckpt" / "*.manifest.json"))
    assert len(mans) == 1
    m = ckpt_verify.load_manifest(mans[0])
    assert m["step"] == 1 and m["files"] and m["config_digest"] == "cafe0001"
    assert m["structure"]["num_leaves"] >= 3  # step, w, opt leaves, rng
    rep = ckpt_verify.verify_run(str(tmp_path))
    assert rep["ok"] and rep["valid_steps"] == [1]
    # restore of an intact checkpoint: no fallback, no warnings
    assert int(mgr.restore(_mk_state(0, 0.0)).step) == 1
    assert mgr.stats()["restore_fallbacks"] == 0


@pytest.mark.chaos
def test_restore_falls_back_past_corrupt_and_truncated(tmp_path):
    """The verified-restore ladder: newest (byte-flipped) and middle
    (truncated) checkpoints are skipped with logged warnings; the newest
    VALID step restores."""
    msgs = []
    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=5,
                            log=lambda s, m: msgs.append(m))
    for s in (1, 2, 3):
        mgr.save(_mk_state(s, float(s)))
    mgr.finalize()
    p3 = _largest_file(str(tmp_path / "ckpt" / "step_0000000003"))
    with open(p3, "r+b") as f:
        b = f.read(1)
        f.seek(0)
        f.write(bytes([b[0] ^ 0xFF]))
    os.remove(_largest_file(str(tmp_path / "ckpt" / "step_0000000002")))

    restored = mgr.restore(_mk_state(0, 0.0))
    assert int(restored.step) == 1
    assert float(np.asarray(restored.params["w"])[0]) == 1.0
    st = mgr.stats()
    assert st["verify_failures"] == 2 and st["restore_fallbacks"] == 1
    assert any("failed verification" in m for m in msgs)
    rep = ckpt_verify.verify_run(str(tmp_path))
    assert rep["corrupt_steps"] == [2, 3] and rep["valid_steps"] == [1]


def test_restore_rejects_structure_mismatch(tmp_path):
    """Files-intact-but-wrong-tree: the manifest's pytree digest must
    block the restore (counted as a verification failure) instead of
    handing orbax a mismatched template."""
    msgs = []
    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=5,
                            log=lambda s, m: msgs.append(m))
    mgr.save(_mk_state(1, 1.0))
    mgr.finalize()
    tx = optax.sgd(0.1)
    params = {"w": jnp.zeros((4,)), "extra": jnp.zeros((2,))}
    other = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=tx.init(params), rng=jax.random.PRNGKey(0),
                       tx=tx)
    assert mgr.restore(other) is None
    assert mgr.stats()["verify_failures"] == 1
    assert any("structure mismatch" in m for m in msgs)
    # the matching template still restores
    assert int(mgr.restore(_mk_state(0, 0.0)).step) == 1


@pytest.mark.chaos
def test_ckpt_save_failure_degrades_to_warning(tmp_path):
    inj = FaultInjector(FaultConfig(enabled=True, ckpt_save_at=(2,),
                                    fail_attempts=1))
    msgs = []
    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=5,
                            log=lambda s, m: msgs.append(m), injector=inj)
    assert mgr.save(_mk_state(1, 1.0)) is not None
    assert mgr.save(_mk_state(2, 2.0)) is None  # injected: degrade, no raise
    assert mgr.save(_mk_state(3, 3.0)) is not None
    mgr.finalize()
    assert mgr.stats()["save_failures"] == 1
    assert any("previous checkpoint retained" in m for m in msgs)
    # step-1 checkpoint survived the failed step-2 save
    assert mgr.all_steps() == [1, 3]


@pytest.mark.chaos
def test_ckpt_save_prewrite_failure_keeps_committed_checkpoint(tmp_path):
    """A save failure BEFORE the write starts (injected pre-write fault
    on a re-save of an existing step) must not delete the previously
    COMMITTED checkpoint at that step — 'previous checkpoint retained'
    has to be literally true."""
    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=5,
                            log=lambda s, m: None)
    assert mgr.save(_mk_state(5, 5.0)) is not None
    mgr.finalize()
    # second manager (fresh process analog) re-saves step 5 with an
    # injected pre-write fault
    inj = FaultInjector(FaultConfig(enabled=True, ckpt_save_at=(5,),
                                    fail_attempts=1))
    msgs = []
    mgr2 = CheckpointManager(str(tmp_path / "ckpt"), keep=5,
                             log=lambda s, m: msgs.append(m), injector=inj)
    assert mgr2.save(_mk_state(5, 6.0)) is None
    # the run-1 checkpoint (and its manifest) survived and restores
    restored = mgr2.restore(_mk_state(0, 0.0))
    assert restored is not None and int(restored.step) == 5
    assert float(np.asarray(restored.params["w"])[0]) == 5.0
    rep = ckpt_verify.verify_run(str(tmp_path))
    assert rep["valid_steps"] == [5], rep


@pytest.mark.chaos
def test_ckpt_tamper_and_restore_injection(tmp_path):
    """ckpt_truncate / ckpt_corrupt tamper the committed dir after the
    manifest (detectable, like real corruption); an injected
    ckpt_restore error falls back like a real read failure."""
    inj = FaultInjector(FaultConfig(enabled=True, ckpt_truncate_at=(2,),
                                    ckpt_corrupt_at=(3,),
                                    ckpt_restore_at=(1,), fail_attempts=1))
    msgs = []
    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=5,
                            log=lambda s, m: msgs.append(m), injector=inj)
    for s in (1, 2, 3):
        mgr.save(_mk_state(s, float(s)))
    mgr.finalize()
    assert inj.stats()["ckpt_truncate"] == 1
    assert inj.stats()["ckpt_corrupt"] == 1
    rep = ckpt_verify.verify_run(str(tmp_path))
    assert rep["corrupt_steps"] == [2, 3] and rep["valid_steps"] == [1]
    # steps 3 and 2 fail verification; step 1's restore hits the injected
    # ckpt_restore fault once -> counted, retried as a fallback candidate
    # exhausts -> None (fail_attempts=1 means the SECOND attempt would
    # succeed, but each candidate is tried once per restore call)
    assert mgr.restore(_mk_state(0, 0.0)) is None
    st = mgr.stats()
    assert st["verify_failures"] == 2 and st["restore_failures"] == 1
    # a second restore call: step 1's injected fault is spent -> succeeds
    restored = mgr.restore(_mk_state(0, 0.0))
    assert restored is not None and int(restored.step) == 1


def test_rollback_error_names_ckpt_dir(tmp_path):
    """Satellite: _rollback with no restorable checkpoint must fail with
    an actionable error naming the checkpoint directory."""
    from deepof_tpu.train.loop import Trainer

    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
    fake = SimpleNamespace(ckpt=mgr, state=None, logger=None)
    with pytest.raises(FloatingPointError) as ei:
        Trainer._rollback(fake, step=7)
    assert str(tmp_path / "ckpt") in str(ei.value)
    assert "verify-ckpt" in str(ei.value)


# ------------------------------------------------------------- CLI verbs

def test_verify_ckpt_cli_jax_free(tmp_path):
    """verify-ckpt validates manifests without importing jax and exits
    nonzero on corruption (2 when there is nothing to verify)."""
    run = tmp_path / "run"
    ck = run / "ckpt" / "step_0000000001"
    os.makedirs(ck)
    (ck / "a.bin").write_bytes(b"payload" * 64)
    ckpt_verify.write_manifest(str(ck), ckpt_verify.build_manifest(str(ck), 1))
    env = dict(os.environ, PYTHONPATH=REPO)

    def run_cli(path):
        return subprocess.run(
            [sys.executable, "-c",
             "import sys; from deepof_tpu.cli import main; "
             "sys.exit(main(['verify-ckpt', sys.argv[1]]))", path],
            capture_output=True, text=True, timeout=120, env=env, cwd=REPO)

    res = run_cli(str(run))
    assert res.returncode == 0, res.stderr[-800:]
    assert json.loads(res.stdout)["ok"] is True
    (ck / "a.bin").write_bytes(b"tampered")
    res = run_cli(str(run))
    assert res.returncode == 1
    assert json.loads(res.stdout)["corrupt_steps"] == [1]
    empty = tmp_path / "empty"
    os.makedirs(empty)
    assert run_cli(str(empty)).returncode == 2


def test_tail_exits_nonzero_when_wedged(tmp_path, capsys):
    from deepof_tpu.cli import main

    (tmp_path / "metrics.jsonl").write_text(json.dumps(
        {"kind": "train", "step": 4, "time": time.time(), "loss": 1.0,
         "skipped_updates": 2, "data_quarantined": 1}) + "\n")
    (tmp_path / "heartbeat.json").write_text(json.dumps(
        {"time": time.time(), "step": 4, "wedged": False}))
    assert main(["tail", "--log-dir", str(tmp_path)]) == 0
    out = json.loads(capsys.readouterr().out)
    # satellite: resilience counters surface in tail
    assert out["resilience"] == {"skipped_updates": 2, "data_quarantined": 1}
    (tmp_path / "heartbeat.json").write_text(json.dumps(
        {"time": time.time(), "step": 4, "wedged": True}))
    assert main(["tail", "--log-dir", str(tmp_path)]) == 3


def test_deep_set_override():
    from deepof_tpu.cli import _apply_override
    from deepof_tpu.core.config import get_config

    cfg = get_config("flyingchairs")
    cfg = _apply_override(cfg, "resilience.faults.decode_p", "0.25")
    cfg = _apply_override(cfg, "resilience.faults.decode_at", "(3, 7)")
    cfg = _apply_override(cfg, "resilience.max_consecutive_skips", "2")
    assert cfg.resilience.faults.decode_p == 0.25
    assert cfg.resilience.faults.decode_at == (3, 7)
    assert cfg.resilience.max_consecutive_skips == 2
    with pytest.raises(SystemExit):
        _apply_override(cfg, "resilience.faults.nope", "1")


def test_counter_summary_surfaces_resilience():
    from deepof_tpu.analyze import _counter_summary

    rec = {"step": 100, "starved": 3, "skipped_updates": 2, "rollbacks": 1,
           "data_quarantined": 4, "ckpt_restore_fallbacks": 1,
           "fault_decode": 5, "data_batches": 10}
    out = _counter_summary(rec)
    assert out["resilience"]["skipped_updates"] == 2
    assert out["resilience"]["rollbacks"] == 1
    assert out["resilience"]["data_quarantined"] == 4
    assert out["resilience"]["ckpt_restore_fallbacks"] == 1
    assert out["resilience"]["fault_decode"] == 5


# ------------------------------------------------- train CLI helpers

def _run_train_cli(log_dir, steps, extra, timeout=420, **env_over):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""), **env_over)
    return subprocess.run(
        [sys.executable, "-m", "deepof_tpu", "train", "--preset",
         "flyingchairs", "--synthetic", "--max-steps", str(steps),
         "--log-dir", str(log_dir),
         "--set", "model=flownet_s", "--set", "width_mult=0.25",
         "--set", "train.log_every=1", "--set", "train.eval_every=0",
         "--set", "train.ckpt_every_epochs=1000000",
         "--set", "resilience.data_backoff_s=0.001",
         *extra],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)


def _train_cli(log_dir, steps, extra, timeout=420, **env_over):
    res = _run_train_cli(log_dir, steps, extra, timeout, **env_over)
    assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-3000:])
    return json.loads(res.stdout.strip().splitlines()[-1])


# ------------------------------------------- the final checkpoint check

# the smallest size that still walks fit() end to end: ~15 s on ONE cpu
# device (XLA_FLAGS="" drops the suite's 8 virtual ones: 80 s there)
_TINY = ["--set", "data.image_size=32,32", "--set", "data.gt_size=32,32",
         "--set", "data.batch_size=2",
         "--set", "resilience.faults.enabled=true"]


def test_fit_with_one_skipped_update_returns_and_final_ckpt_verifies(
        tmp_path):
    """First rung of the divergence ladder under the default
    max_consecutive_skips: one poisoned batch costs one skipped update.
    The final checkpoint is named after state.step (5), which trails the
    loop's step count (6) — the fit must still return normally."""
    d = tmp_path / "skip"
    out = _train_cli(d, 6, _TINY + [
        "--set", "resilience.faults.dispatch_at=(3,)"], XLA_FLAGS="")
    assert out["fault_dispatch"] == 1 and out["skipped_updates"] == 1
    assert not out.get("rollbacks")
    rep = ckpt_verify.verify_run(str(d))
    assert rep["ok"], rep
    assert max(rep["valid_steps"]) == 5


def test_fit_whose_final_save_fails_does_not_return_zero(tmp_path):
    """Periodic saves may degrade to a warning; the final one is the run's
    product, so a fit that could not commit it exits non-zero."""
    res = _run_train_cli(tmp_path / "nosave", 3, _TINY + [
        "--set", "resilience.faults.ckpt_save_at=(3,)"], XLA_FLAGS="")
    assert res.returncode != 0
    assert "final checkpoint (step 3) did not commit" in res.stderr


# --------------------------------------------------- acceptance (slow)

@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_acceptance_fit_recovers_through_all_sites(tmp_path):
    """ISSUE 4 acceptance: a fit() with injected faults at all four
    sites — persistent 5%+scheduled decode IO errors (quarantine +
    substitute), one dispatch-adjacent non-finite grad (skip in place,
    escalating to rollback at max_consecutive_skips=1), and one
    truncated + one checksum-corrupted checkpoint (rollback falls back
    past both to the step-0 target) — completes to the target steps
    without aborting and reports every event in the run summary."""
    d = tmp_path / "chaos"
    out = _train_cli(
        d, 12,
        ["--set", "train.ckpt_every_steps=4",
         "--set", "train.keep_ckpts=10",
         "--set", "data.num_workers=2",
         "--set", "resilience.max_consecutive_skips=1",
         "--set", "resilience.faults.enabled=true",
         "--set", "resilience.faults.decode_p=0.05",
         "--set", "resilience.faults.decode_at=(2,5)",
         "--set", "resilience.faults.fail_attempts=3",  # data_retries+1
         "--set", "resilience.faults.dispatch_at=(9,)",
         "--set", "resilience.faults.ckpt_truncate_at=(4,)",
         "--set", "resilience.faults.ckpt_corrupt_at=(8,)"])
    # every event class reported in the run summary
    assert out["fault_dispatch"] == 1
    assert out["fault_ckpt_truncate"] == 1 and out["fault_ckpt_corrupt"] == 1
    assert out["fault_decode"] >= 2 and out["data_quarantined"] >= 2
    assert out["data_substituted"] == out["data_quarantined"]
    assert out["skipped_updates"] >= 1
    assert out["rollbacks"] >= 1
    assert out["ckpt_restore_fallbacks"] >= 1
    assert out["ckpt_verify_failures"] >= 2

    text = (d / "metrics.jsonl").read_text()
    assert "skipped in place" in text
    assert "failed verification" in text
    assert "rolled back to step 0" in text
    assert "quarantined sample draw" in text
    assert "poisoned with NaN" in text

    # completed to target steps and the surviving checkpoints verify
    train = [json.loads(ln) for ln in text.splitlines()
             if '"kind": "train"' in ln]
    assert max(r["step"] for r in train) == 12
    rep = ckpt_verify.verify_run(str(d))
    assert rep["ok"], rep
    assert 12 in rep["valid_steps"]


@pytest.mark.slow
@pytest.mark.chaos
def test_recoverable_faults_keep_params_bit_identical(tmp_path):
    """ISSUE 4 acceptance, determinism half: with recoverable data
    faults only (transient decode errors healed by retry), the final
    params are bit-identical to a fault-free run at the same seed and
    num_workers."""
    common = ["--set", "data.num_workers=2"]
    _train_cli(tmp_path / "faulty", 6, common + [
        "--set", "resilience.faults.enabled=true",
        "--set", "resilience.faults.decode_at=(1,3)",
        "--set", "resilience.faults.fail_attempts=1"])
    _train_cli(tmp_path / "clean", 6, common)

    params = {}
    for name in ("faulty", "clean"):
        mgr = CheckpointManager(str(tmp_path / name / "ckpt"), create=False,
                                async_save=False)
        assert mgr.latest_step() == 6
        params[name] = mgr.restore_raw(subtree="params")
    leaves_f = jax.tree_util.tree_leaves(params["faulty"])
    leaves_c = jax.tree_util.tree_leaves(params["clean"])
    assert len(leaves_f) == len(leaves_c) and leaves_f
    for a, b in zip(leaves_f, leaves_c):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_second_sigterm_falls_through_to_default(tmp_path):
    """Satellite: fit()'s graceful handler absorbs the FIRST SIGTERM
    (stop flag); a SECOND must fall through to the default action and
    kill even a run wedged where the stop flag is never polled — no
    operator SIGKILL needed. Subprocess, consistent with the
    warm-cache-read caveat in hostmesh.py."""
    env = dict(os.environ, PYTHONPATH="", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tests", "_sigterm_worker.py"),
         str(tmp_path / "run")],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True)
    try:
        deadline = time.time() + 300
        wedged = False
        while time.time() < deadline:
            line = p.stdout.readline()
            if "WEDGED" in line:
                wedged = True
                break
            if line == "" and p.poll() is not None:
                break
        assert wedged, "worker never reached its wedged step"
        p.send_signal(signal.SIGTERM)  # absorbed: graceful stop flag
        # generous margin: on a loaded host, slow signal delivery must not
        # let the second SIGTERM land before the first was handled
        time.sleep(2.0)
        assert p.poll() is None, "first SIGTERM must not kill a wedged run"
        p.send_signal(signal.SIGTERM)  # escalates to the default action
        rc = p.wait(timeout=120)
    finally:
        if p.poll() is None:
            p.kill()
    assert rc == -signal.SIGTERM, rc
