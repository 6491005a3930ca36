"""Executable-ledger + perf-regression-sentinel tests (obs/ledger.py,
tools/ledger_diff.py, DESIGN.md "Executable ledger").

Pins the ISSUE 15 contract: every lowering becomes a provenance row
(stable StableHLO fingerprint, compile seconds, persistent-cache
hit/miss, XLA cost analysis, memory footprint, donation map) with the
frozen ROW_KEYS schema; diff_ledgers classifies drift into exactly four
failure classes whose verdicts over the recorded fixture
(tests/fixtures/ledger, make_ledger_fixture.py) are byte-pinned against
goldens; `tools/ledger_diff.py` and `deepof_tpu tail` map a failed
verdict to exit code 8 while a same-config warm rerun diffs clean; the
real engine path writes rows + the registry-declared exec_* stats block
(and with obs.ledger=false keeps the stats schema byte-identical to the
pre-ledger stack); obs/telemetry.py's step_flops/device_memory_summary
get their first direct unit coverage; and the bench_trend /
serve_bench --ledger report schemas hold.

Fast tier throughout: the jax-touching tests lower tiny elementwise
functions (milliseconds, no conv-net compile).
"""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from deepof_tpu.obs.ledger import (DEFAULT_COMPILE_FACTOR, ROW_KEYS,
                                   ExecutableLedger, diff_ledgers,
                                   exec_name, fingerprint_text,
                                   latest_by_name, ledger_verdict,
                                   load_ledger, lowering_row,
                                   normalize_hlo, quality_exec_name,
                                   summarize_ledger)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
FIXTURE = os.path.join(HERE, "fixtures", "ledger")
GOLDENS = os.path.join(HERE, "fixtures", "goldens")


def _golden(name: str):
    with open(os.path.join(GOLDENS, name)) as f:
        return json.load(f)


# ------------------------------------------------- fingerprint contract


def test_normalize_hlo_strips_location_metadata_only():
    """The fingerprint input drops `loc(...)` attributes and `#loc`
    lines — the one nondeterministic part of the printed module — and
    trailing whitespace, but keeps every computation-bearing token
    (shapes, dtypes, donation aliasing)."""
    body = ('module @jit_f {\n'
            '  func.func public @main(%arg0: tensor<8x8xf32> '
            '{tf.aliasing_output = 0 : i32}) -> tensor<8x8xf32> {\n'
            '    %0 = stablehlo.add %arg0, %arg0 : tensor<8x8xf32>\n'
            '    return %0 : tensor<8x8xf32>\n'
            '  }\n'
            '}')
    with_locs = (body.replace(
        ': tensor<8x8xf32>\n    return',
        ': tensor<8x8xf32> loc("add" "f.py":3:0)\n    return')
        + '\n#loc0 = loc("f.py":1:0)\n') .replace(
        '  }', '  }   ')  # trailing whitespace noise
    assert normalize_hlo(with_locs) == normalize_hlo(body)
    assert fingerprint_text(with_locs) == fingerprint_text(body)
    # the full debug-info grammar must strip too: loc(unknown), nested
    # callsite/fused forms, and quoted names that contain parens —
    # a debug-enabled run and its baseline must hash identically
    anchor = ": tensor<8x8xf32>\n    return"
    for loc in ("loc(unknown)",
                'loc(callsite("add"("f.py":3:0) at "g.py":9:1))',
                'loc(fused["a", "weird(name.py":7:0])',
                'loc("paren(in)name.py":1:2)'):
        deco = body.replace(anchor,
                            f": tensor<8x8xf32> {loc}\n    return")
        assert normalize_hlo(deco) == normalize_hlo(body), loc
        assert fingerprint_text(deco) == fingerprint_text(body), loc
    # ...while an identifier merely ending in "loc" is computation text
    assert "myloc(" in normalize_hlo("  %0 = myloc(%arg0)")
    # any computation change changes the fingerprint
    assert (fingerprint_text(body.replace("8x8", "16x16"))
            != fingerprint_text(body))


def test_fingerprint_stable_across_lowerings_and_sensitive_to_shape():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x) * 2.0)
    a = jax.ShapeDtypeStruct((4, 4), jnp.float32)
    b = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    fp1 = fingerprint_text(f.lower(a).as_text())
    fp2 = fingerprint_text(f.lower(a).as_text())
    assert fp1 == fp2  # re-lowering the same avals is a pure function
    assert fingerprint_text(f.lower(b).as_text()) != fp1


# ------------------------------------------------------ row schema pins


def test_lowering_row_schema_cost_memory_and_donation():
    """One real (tiny) AOT lowering fills the frozen ROW_KEYS schema:
    fingerprint + cost analysis from the Lowered, memory_analysis from
    the Compiled, and the donation map from args_info."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x, y: (x @ y, y), donate_argnums=(0,))
    a = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    lowered = f.lower(a, a)
    row = lowering_row("demo", lowered=lowered, compiled=lowered.compile(),
                       compile_s=0.25, compile_kind="aot",
                       cache={"requests": 1, "hits": 0, "misses": 1},
                       backend="cpu")
    assert tuple(row.keys()) == ROW_KEYS  # the schema the fixture pins
    assert row["kind"] == "exec" and row["name"] == "demo"
    assert isinstance(row["fingerprint"], str) and len(row["fingerprint"]) == 16
    assert row["compile_s"] == 0.25
    assert row["compile_kind"] == "aot"
    assert row["cache_misses"] == 1 and row["cache_hits"] == 0
    assert row["flops"] and row["flops"] > 0  # 8x8 matmul ~ 2*8^3
    assert row["bytes_accessed"] and row["arith_intensity"] > 0
    # no roofline against a chip that is not there: the cpu has no entry
    # in obs/telemetry.py's peak table
    assert row["roofline_s"] is None
    assert row["donated_args"] == 1 and row["num_args"] == 2
    # cpu PJRT reports memory_analysis: argument/output/temp are ints
    assert isinstance(row["argument_bytes"], int)
    assert isinstance(row["output_bytes"], int)
    # a site with no Lowered/Compiled leaves every field None, never raises
    bare = lowering_row("bare")
    assert tuple(bare.keys()) == ROW_KEYS
    assert bare["fingerprint"] is None and bare["argument_bytes"] is None


def test_exec_names_are_the_shared_warmup_engine_contract():
    assert exec_name((32, 64), "f32", "cold") == "serve:32x64:f32:cold"
    assert quality_exec_name((32, 64)) == "quality:32x64"


# ------------------------------------------------- ledger record/stats


def test_ledger_records_counts_recompiles_and_flushes_timings(tmp_path):
    led = ExecutableLedger(str(tmp_path), backend="cpu")
    r1 = {"fingerprint": "aaaa", "compile_s": 1.0,
          "cache": {"requests": 1, "hits": 1, "misses": 0}}

    class _L:
        """Duck-typed Lowered: as_text only (cost analysis absent)."""

        def __init__(self, text):
            self._text = text

        def as_text(self):
            return self._text

        def cost_analysis(self):
            raise NotImplementedError

    led.record("train_step", lowered=_L("module A"), compile_s=1.0,
               cache=r1["cache"])
    # the SAME name lowering to a DIFFERENT module within one run is the
    # live recompile signal
    led.record("train_step", lowered=_L("module B"), compile_s=0.5,
               cache={"requests": 1, "hits": 0, "misses": 1})
    led.note_exec("train_step", 0.01)
    led.note_exec("train_step", 0.03)
    stats = led.stats()
    assert stats["exec_lowerings"] == 2
    assert stats["exec_recompiles"] == 1
    assert stats["exec_compile_s"] == 1.5
    assert stats["exec_cache_hits"] == 1
    assert stats["exec_cache_misses"] == 1
    assert stats["exec_executables"] == 1
    assert stats["exec_dispatches"] == 2
    assert stats["exec_dispatch_s"] == pytest.approx(0.04)
    assert stats["exec_fingerprints"]["train_step"] == fingerprint_text(
        "module B")
    led.flush()
    rows = load_ledger(str(tmp_path))
    assert [r["kind"] for r in rows] == ["exec", "exec", "exec_timing"]
    # newest row per name wins in the diff view
    assert latest_by_name(rows)["train_step"]["compile_s"] == 0.5
    s = summarize_ledger(rows)
    assert s["lowerings"] == 2 and s["recompiles"] == 1
    assert s["executables"] == 1 and s["compile_s_total"] == 1.5
    assert s["compile_s_by_kind"] == {"unknown": 1.5}
    # slowest is newest-row-per-name: the superseded first lowering of
    # train_step is not a second entry
    assert [e["name"] for e in s["slowest"]] == ["train_step"]
    assert s["slowest"][0]["compile_s"] == 0.5


def test_summarize_ledger_splits_compile_kinds():
    """A dir holding both a warmup baseline ("aot") and a live run
    ("first_step") reports the two compile-second units apart — the
    summary must not melt incompatible units into one figure the way
    diff_ledgers refuses to compare them."""
    rows = [
        {"kind": "exec", "name": "train_step", "compile_s": 32.4,
         "compile_kind": "aot", "fingerprint": "ff"},
        {"kind": "exec", "name": "train_step", "compile_s": 70.6,
         "compile_kind": "first_step", "fingerprint": "ff"},
    ]
    s = summarize_ledger(rows)
    assert s["compile_s_by_kind"] == {"aot": 32.4, "first_step": 70.6}
    assert s["recompiles"] == 0  # same fingerprint, different recorder
    assert len(s["slowest"]) == 1  # one executable, newest row wins
    assert s["slowest"][0]["compile_kind"] == "first_step"


def test_load_ledger_tolerates_torn_trailing_write(tmp_path):
    p = tmp_path / "ledger.jsonl"
    p.write_text(json.dumps({"kind": "exec", "name": "a",
                             "fingerprint": "ff"}) + "\n"
                 + '{"kind": "exec", "name": "b", "finge')
    rows = load_ledger(str(tmp_path))
    assert len(rows) == 1 and rows[0]["name"] == "a"


def test_disabled_ledger_writes_nothing_but_still_counts(tmp_path):
    led = ExecutableLedger(str(tmp_path), enabled=False, backend="cpu")
    led.record("x", compile_s=0.1)
    led.flush()
    assert not (tmp_path / "ledger.jsonl").exists()
    assert led.stats()["exec_lowerings"] == 1


# ------------------------------------------------------- diff verdicts


def test_diff_ledgers_failure_classes_and_reported_only_names():
    base = [{"kind": "exec", "name": "a", "fingerprint": "f1",
             "cache_hits": 1, "cache_misses": 0, "compile_s": 0.5,
             "argument_bytes": 100, "output_bytes": 50, "temp_bytes": 50}]
    same = [dict(base[0])]
    assert diff_ledgers(base, same)["failed"] is False
    # memory growth under the bound does not fail
    near = [dict(base[0], argument_bytes=110)]
    assert diff_ledgers(base, near)["failed"] is False
    # a new or missing name is reported, never fails
    v = diff_ledgers(base, same + [dict(base[0], name="b")])
    assert v["new"] == ["b"] and v["failed"] is False
    v = diff_ledgers(base + [dict(base[0], name="b")], same)
    assert v["missing"] == ["b"] and v["failed"] is False
    # each class alone fails
    assert diff_ledgers(base, [dict(base[0], fingerprint="f2")])[
        "fingerprint_drift"]
    assert diff_ledgers(base, [dict(base[0], cache_hits=0,
                                    cache_misses=1)])[
        "unexpected_recompiles"]
    assert diff_ledgers(base, [dict(base[0], compile_s=1.5)])[
        "compile_blowups"]  # > max(floor 1.0, 0.5 * 2.0)
    # ... but only between rows of the SAME compile_kind: a warmup
    # baseline's pure lower+compile ("aot") never bounds the train
    # loop's first-step wall ("first_step" = compile + one executed
    # step) — mixed units must not fire a false rc 8
    assert diff_ledgers(
        [dict(base[0], compile_kind="aot")],
        [dict(base[0], compile_s=1.5, compile_kind="first_step")])[
        "compile_blowups"] == []
    assert diff_ledgers(
        [dict(base[0], compile_kind="aot")],
        [dict(base[0], compile_s=1.5, compile_kind="aot")])[
        "compile_blowups"]
    assert diff_ledgers(base, [dict(base[0], temp_bytes=200)])[
        "memory_growth"]  # 350 > 200 * 1.2
    # bounds are parameters: a looser memory factor passes the same rows
    assert diff_ledgers(base, [dict(base[0], temp_bytes=200)],
                        memory_factor=2.0)["failed"] is False
    # the compile floor swallows sub-floor blowups (cpu-noise guard)
    tiny = [dict(base[0], compile_s=0.01)]
    assert diff_ledgers(tiny, [dict(base[0], compile_s=0.9)],
                        compile_factor=DEFAULT_COMPILE_FACTOR)[
        "failed"] is False


def test_fixture_verdicts_byte_pinned():
    """The recorded fixture's diff verdicts are byte-for-byte the
    committed goldens — drift classification can never move silently."""
    base = load_ledger(os.path.join(FIXTURE, "baseline.jsonl"))
    for name, want_failed in (("clean", False), ("drift", True)):
        run = load_ledger(os.path.join(FIXTURE, f"run_{name}"))
        got = diff_ledgers(base, run)
        assert got["failed"] is want_failed
        assert json.dumps(got) == json.dumps(
            _golden(f"ledger_diff_{name}.json"))


# ---------------------------------------------------- rc 8 CLI contract


def test_ledger_diff_cli_exit_codes(tmp_path):
    tool = os.path.join(REPO, "tools", "ledger_diff.py")
    base = os.path.join(FIXTURE, "baseline.jsonl")

    def run(*args):
        return subprocess.run([sys.executable, tool, *args], cwd=REPO,
                              capture_output=True, text=True)

    drift = run("--baseline", base, "--run",
                os.path.join(FIXTURE, "run_drift"))
    assert drift.returncode == 8
    verdict = json.loads(drift.stdout)
    assert verdict["failed"] and verdict["fingerprint_drift"]
    clean = run("--baseline", base, "--run",
                os.path.join(FIXTURE, "run_clean"))
    assert clean.returncode == 0
    assert json.loads(clean.stdout)["failed"] is False
    # loosened bounds flip the blowup/growth classes off (drift remains)
    loose = run("--baseline", base, "--run",
                os.path.join(FIXTURE, "run_drift"),
                "--compile-factor", "10", "--memory-factor", "10")
    v = json.loads(loose.stdout)
    assert loose.returncode == 8  # fingerprint drift still fails
    assert not v["compile_blowups"] and not v["memory_growth"]
    missing = run("--baseline", base, "--run", str(tmp_path / "nope"))
    assert missing.returncode == 1


def _run_copy(tmp_path, which: str, with_baseline: bool,
              dest: str | None = None) -> str:
    d = str(tmp_path / (dest or which))
    shutil.copytree(os.path.join(FIXTURE, which), d)
    if with_baseline:
        shutil.copy(os.path.join(FIXTURE, "baseline.jsonl"),
                    os.path.join(d, "ledger_baseline.jsonl"))
    return d


def test_tail_exits_8_on_ledger_drift_and_0_on_clean(tmp_path, capsys):
    from deepof_tpu.cli import main

    drift_dir = _run_copy(tmp_path, "run_drift", with_baseline=True)
    assert main(["tail", "--log-dir", drift_dir]) == 8
    summary = json.loads(capsys.readouterr().out)
    assert summary["ledger_diff"]["failed"] is True
    assert summary["ledger"]["lowerings"] == 5
    clean_dir = _run_copy(tmp_path, "run_clean", with_baseline=True)
    assert main(["tail", "--log-dir", clean_dir]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["ledger_diff"]["failed"] is False
    assert summary["ledger_diff"]["fingerprint_drift"] == []
    assert summary["ledger_diff"]["unexpected_recompiles"] == []
    # no baseline => no verdict, never a failure
    bare_dir = _run_copy(tmp_path, "run_clean", with_baseline=False,
                         dest="run_bare")
    assert main(["tail", "--log-dir", bare_dir]) == 0
    assert "ledger_diff" not in json.loads(capsys.readouterr().out)
    # an explicit --ledger-baseline needs no copied convention file
    assert main(["tail", "--log-dir", bare_dir, "--ledger-baseline",
                 os.path.join(FIXTURE, "baseline.jsonl")]) == 0
    capsys.readouterr()
    # ... and a run DIR holding a ledger.jsonl is a valid baseline too,
    # exactly as the standalone ledger_diff accepts it (the two gates
    # must agree on valid inputs, not just on bad ones)
    assert main(["tail", "--log-dir", bare_dir, "--ledger-baseline",
                 os.path.join(FIXTURE, "run_clean")]) == 0
    capsys.readouterr()
    # loosened tail bounds mirror ledger_diff's flags
    assert main(["tail", "--log-dir", drift_dir,
                 "--ledger-compile-factor", "10",
                 "--ledger-memory-factor", "10"]) == 8  # drift remains
    capsys.readouterr()
    # an empty/truncated baseline is STATIC — it can never become
    # valid, so the pre-check fails it loudly even before any summary
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    with pytest.raises(SystemExit, match="no ledger rows"):
        main(["tail", "--log-dir", drift_dir,
              "--ledger-baseline", str(empty)])
    with pytest.raises(SystemExit, match="no ledger rows"):
        main(["tail", "--log-dir", drift_dir, "--follow",
              "--ledger-baseline", str(empty)])
    # ...and the committed-by-convention file gets the same treatment:
    # an EXISTING but rowless <log_dir>/ledger_baseline.jsonl is a
    # broken gate, not the legitimate no-baseline case
    conv_dir = _run_copy(tmp_path, "run_clean", with_baseline=False,
                         dest="run_conv")
    open(os.path.join(conv_dir, "ledger_baseline.jsonl"), "w").close()
    with pytest.raises(SystemExit, match="no ledger rows"):
        main(["tail", "--log-dir", conv_dir])


def test_tail_follow_waits_for_first_ledger_row(tmp_path):
    """`tail --follow --ledger-baseline B` on a run that has not yet
    written its first ledger row (first compile pending — can be
    minutes cold) keeps following instead of dying rc 1 on iteration
    one; once rows appear the gate fires like every other rc 3-8
    condition. A one-shot (no --follow) tail on the same inputs stays
    a loud rc-1 error."""
    import time as _time

    run = tmp_path / "run"
    run.mkdir()
    (run / "metrics.jsonl").write_text(json.dumps(
        {"kind": "train", "step": 1, "time": 0.0, "total": 0.5}) + "\n")
    base = os.path.join(FIXTURE, "baseline.jsonl")
    from deepof_tpu.cli import main

    with pytest.raises(SystemExit, match="no verdict"):
        main(["tail", "--log-dir", str(run), "--ledger-baseline", base])
    proc = subprocess.Popen(
        [sys.executable, "-m", "deepof_tpu", "tail", "--log-dir",
         str(run), "--follow", "--interval", "0.2",
         "--ledger-baseline", base],
        cwd=REPO, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        text=True)
    try:
        _time.sleep(2.0)
        assert proc.poll() is None, proc.stderr.read()
        # the run's first rows land — drifted vs the baseline => rc 8
        shutil.copy(os.path.join(FIXTURE, "run_drift", "ledger.jsonl"),
                    run / "ledger.jsonl")
        assert proc.wait(timeout=30) == 8
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)


def test_fleet_ledger_drift_keeps_full_schema_without_root_ledger(
        tmp_path):
    """tail --fleet's drift verdict carries the full documented
    diff_ledgers schema even when only CHILDREN recorded ledgers (a
    router that lowered nothing above replica processes): the verdict's
    shape must not depend on whether the root happened to have one."""
    from deepof_tpu.analyze import ledger_drift
    from deepof_tpu.obs.ledger import diff_ledgers

    shutil.copy(os.path.join(FIXTURE, "baseline.jsonl"),
                tmp_path / "ledger_baseline.jsonl")
    child = tmp_path / "replica-0"
    child.mkdir()
    (child / "metrics.jsonl").write_text(json.dumps(
        {"kind": "train", "step": 1, "time": 0.0}) + "\n")
    shutil.copy(os.path.join(FIXTURE, "run_drift", "ledger.jsonl"),
                child / "ledger.jsonl")
    v = ledger_drift(str(tmp_path), fleet=True)
    reference = diff_ledgers([], [])
    assert set(reference) | {"children"} == set(v)
    assert v["failed"] is True  # the drifted child fails the fleet
    assert v["children"]["replica-0"]["failed"] is True
    assert v["fingerprint_drift"] == []  # root compared nothing


def test_ledger_verdict_none_when_either_side_absent(tmp_path):
    assert ledger_verdict(str(tmp_path)) is None  # no baseline
    shutil.copy(os.path.join(FIXTURE, "baseline.jsonl"),
                os.path.join(tmp_path, "ledger_baseline.jsonl"))
    assert ledger_verdict(str(tmp_path)) is None  # no run ledger


# ------------------------------------------------ engine path (ledger)


def test_engine_records_serve_executable_and_exec_stats(tmp_path):
    """The real engine path (jit -> AOT compile over the tiny
    elementwise model, test_serve lineage): one ledger row per lattice
    compile, measured-dispatch timings flushed at close, and the
    registry-declared exec_* block in stats() — while obs.ledger=false
    keeps the stats schema byte-identical to the pre-ledger stack and
    writes nothing."""
    from test_serve import _cfg, _img, _tiny_model_params

    rng = np.random.RandomState(0)
    cfg = _cfg(max_batch=2, timeout_ms=5.0, log_dir=str(tmp_path))
    from deepof_tpu.serve.engine import InferenceEngine

    with InferenceEngine(cfg, model_params=_tiny_model_params()) as eng:
        futs = [eng.submit(_img(rng), _img(rng)) for _ in range(4)]
        for f in futs:
            f.result(timeout=60)
        stats = eng.stats()
    assert stats["exec_lowerings"] >= 1
    assert stats["exec_recompiles"] == 0
    name = exec_name((32, 64), "f32", "cold")
    assert name in stats["exec_fingerprints"]
    assert stats["exec_dispatches"] >= 1
    rows = load_ledger(str(tmp_path))
    execs = [r for r in rows if r["kind"] == "exec"]
    timings = [r for r in rows if r["kind"] == "exec_timing"]
    assert [r["name"] for r in execs] == [name]
    assert execs[0]["fingerprint"] == stats["exec_fingerprints"][name]
    assert execs[0]["compile_s"] > 0
    assert execs[0]["compile_kind"] == "aot"  # record_aot stamps it
    assert timings and timings[0]["name"] == name
    assert timings[0]["count"] == stats["exec_dispatches"]

    # ledger off: schema byte-identical to the pre-ledger stack
    off_dir = tmp_path / "off"
    off_cfg = _cfg(max_batch=2, timeout_ms=5.0, log_dir=str(off_dir))
    off_cfg = off_cfg.replace(obs=dataclasses.replace(off_cfg.obs,
                                                      ledger=False))
    with InferenceEngine(off_cfg,
                         model_params=_tiny_model_params()) as eng:
        eng.submit(_img(rng), _img(rng)).result(timeout=60)
        off_stats = eng.stats()
    assert not any(k.startswith("exec_") for k in off_stats)
    assert not os.path.exists(os.path.join(str(off_dir), "ledger.jsonl"))
    assert (sorted(k for k in stats if not k.startswith("exec_"))
            == sorted(off_stats))


def test_ledger_preresolve_compile_failure_contained(tmp_path):
    """A compile error inside the ledger's pre-resolution (the
    executable is resolved BEFORE the timed window so the first
    measured dispatch is an execution, not compile+execution) fails
    that flush's futures as structured dispatch_failed errors — it must
    never kill the batcher thread and strand the futures forever."""
    from test_serve import _cfg, _img, _tiny_model_params

    from deepof_tpu.serve.engine import InferenceEngine, ServeError

    rng = np.random.RandomState(0)
    cfg = _cfg(max_batch=2, timeout_ms=5.0, log_dir=str(tmp_path))
    with InferenceEngine(cfg, model_params=_tiny_model_params()) as eng:
        assert eng._ledger is not None  # the path under test is active

        def boom(key):
            raise RuntimeError("injected compile failure")

        eng._executable = boom
        futs = [eng.submit(_img(rng), _img(rng)) for _ in range(3)]
        for f in futs:
            with pytest.raises(ServeError) as exc:
                f.result(timeout=30)
            assert exc.value.code == "dispatch_failed"
        stats = eng.stats()  # the batcher survived to serve stats
    assert stats["serve_errors"] == 3
    # the pre-resolve failure counts as a dispatch failure exactly like
    # the _forward path — serve_dispatch_failures must not undercount
    # compile failures just because the ledger pre-resolve caught them
    assert stats["serve_dispatch_failures"] >= 1


# -------------------------------------------- telemetry direct coverage


def test_step_flops_and_lowered_flops_agree_on_a_matmul():
    import jax
    import jax.numpy as jnp

    from deepof_tpu.obs.telemetry import lowered_flops, step_flops

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((16, 16), jnp.float32)
    direct = step_flops(f, x)
    assert direct is not None and direct > 0
    assert direct == lowered_flops(f.lower(x))
    # a 16x16 matmul is ~2*16^3 flops; the estimate must be that order
    assert 16 ** 3 <= direct <= 4 * 16 ** 3
    # best-effort contract: garbage in => None, never a raise
    assert lowered_flops(object()) is None
    assert step_flops(object()) is None


def test_device_memory_summary_schema_stable_on_any_backend():
    from deepof_tpu.obs.telemetry import (device_memory_stats,
                                          device_memory_summary)

    stats = device_memory_stats()
    assert stats and all(set(s) == {"device", "bytes_in_use",
                                    "peak_bytes_in_use"} for s in stats)
    summary = device_memory_summary()
    # keys always present; None where the backend (cpu PJRT) is silent
    assert set(summary) == {"dev_mem_bytes_in_use", "dev_mem_peak_bytes"}
    for v in summary.values():
        assert v is None or (isinstance(v, int) and v >= 0)


def test_process_rss_bytes_reports_linux_rss():
    from deepof_tpu.obs.telemetry import process_rss_bytes

    rss = process_rss_bytes()
    assert rss is None or rss > 1024 * 1024  # a live python is > 1 MB


# -------------------------------------------------------- trend schema


def test_bench_trend_ledger_series_and_trend_flag(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_trend", os.path.join(REPO, "tools", "bench_trend.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    # four rounds: overhead creeping up (a sustained slide past the
    # tolerance => the trend block flags), per-executable compile
    # seconds stable
    for rnd, pct, q_scorer, q_p99 in ((1, 1.0, -0.5, 1.0),
                                      (2, 1.4, 0.2, 2.0),
                                      (3, 2.0, 0.4, 4.0),
                                      (4, 3.0, 0.6, 6.0)):
        (tmp_path / f"BENCH_r{rnd:02d}.json").write_text(json.dumps({
            "ledger": {
                "p99_overhead_pct": pct,
                "compile_s_total": 0.9,
                "mfu_nominal": 2e-05,
                "executables": {
                    "serve:32x64:f32:cold": {"compile_s": 0.9,
                                             "mfu_nominal": 2e-05}}},
            "serve_bench_quality": {"scorer_overhead_pct": q_scorer,
                                    "p99_overhead_pct": q_p99}}))
    report = mod.bench_trend(str(tmp_path), tolerance=0.3)
    assert "trend" in report  # REQUIRED_KEYS gained the block
    over = report["series"]["bench_ledger_overhead_pct"]
    assert [p["value"] for p in over] == [1.0, 1.4, 2.0, 3.0]
    t = report["trend"]["bench_ledger_overhead_pct"]
    assert t["slope_per_round"] > 0 and t["regressing"] is True
    # dynamic per-executable series materialized with per-point sense
    key = "ledger_compile_s:serve:32x64:f32:cold"
    assert [p["value"] for p in report["series"][key]] == [0.9] * 4
    assert report["trend"][key]["regressing"] is False
    # stable series never flag
    assert not report["trend"]["bench_ledger_compile_s"]["regressing"]
    # the quality P99 overhead carries ISSUE 13's 5% acceptance bound:
    # 6.0 > 5.0 in the newest round flags it...
    assert "bench_quality_p99_overhead_pct" in report["regressions"]
    assert report["trend"]["bench_quality_p99_overhead_pct"][
        "regressing"] is True
    # ...while the rps-based scorer companion is noise-centered with NO
    # absolute acceptance: a -0.5 best vs +0.6 latest (relative-to-best
    # meaningless) must never auto-flag
    assert "bench_quality_scorer_overhead_pct" not in report["regressions"]
    assert report["trend"]["bench_quality_scorer_overhead_pct"][
        "regressing"] is False

    # compile-seconds series are cache-BIMODAL: a cache-hit round's
    # 0.05 s best must not turn a healthy cold round (0.86 s) into a
    # 17x phantom blowup — the ledger's own max(floor 1s, best*2) rule
    # applies; a genuine blowup past the floor still flags
    bimodal = tmp_path / "bimodal"
    bimodal.mkdir()
    for rnd, cs, mfu in ((1, 0.05, 3.8e-05), (2, 0.9, 3.0e-05),
                         (3, 0.06, 2.4e-05), (4, 0.86, 1.9e-05)):
        (bimodal / f"BENCH_r{rnd:02d}.json").write_text(json.dumps({
            "ledger": {"compile_s_total": cs, "mfu_nominal": mfu,
                       "executables": {
                           "serve:32x64:f32:cold": {
                               "compile_s": cs, "mfu_nominal": mfu}}}}))
    rep = mod.bench_trend(str(bimodal), tolerance=0.3)
    assert "bench_ledger_compile_s" not in rep["regressions"]
    assert rep["trend"]["bench_ledger_compile_s"]["regressing"] is False
    assert f"ledger_compile_s:serve:32x64:f32:cold" not in rep[
        "regressions"]
    # measured MFU halves on a contended host (wall-derived noise):
    # recorded and sloped, never auto-flagged
    assert "bench_ledger_mfu" not in rep["regressions"]
    assert rep["trend"]["bench_ledger_mfu"]["regressing"] is False
    assert "ledger_mfu_nominal:serve:32x64:f32:cold" not in rep[
        "regressions"]
    # the compile bound compares against the WORST prior round, so a
    # repeated healthy cold compile ABOVE the 1 s floor (32 s, 31 s)
    # never phantom-flags against a cache-hit best of 0.05 s
    big = tmp_path / "bigcold"
    big.mkdir()
    for rnd, cs in ((1, 0.05), (2, 32.0), (3, 0.06), (4, 31.0)):
        (big / f"BENCH_r{rnd:02d}.json").write_text(json.dumps({
            "ledger": {"compile_s_total": cs, "executables": {
                "serve:32x64:f32:cold": {"compile_s": cs}}}}))
    rep = mod.bench_trend(str(big), tolerance=0.3)
    assert "bench_ledger_compile_s" not in rep["regressions"]
    assert "ledger_compile_s:serve:32x64:f32:cold" not in rep[
        "regressions"]
    blow = tmp_path / "blow"
    blow.mkdir()
    for rnd, cs in ((1, 0.05), (2, 0.06), (3, 2.5)):
        (blow / f"BENCH_r{rnd:02d}.json").write_text(json.dumps({
            "ledger": {"compile_s_total": cs, "executables": {
                "serve:32x64:f32:cold": {"compile_s": cs}}}}))
    rep = mod.bench_trend(str(blow), tolerance=0.3)
    assert "bench_ledger_compile_s" in rep["regressions"]
    assert rep["regressions"]["bench_ledger_compile_s"][
        "compile_floor_s"] == 1.0
    assert rep["trend"]["bench_ledger_compile_s"]["regressing"] is True
    assert "ledger_compile_s:serve:32x64:f32:cold" in rep["regressions"]


def test_serve_bench_ledger_required_keys_schema():
    """serve_bench --ledger over the real (tiny-width) model: the
    LEDGER_REQUIRED_KEYS schema holds and the provenance block is
    self-consistent. The overhead FIGURE is recorded by BENCH runs, not
    asserted here — a loaded CI host makes p99 deltas meaningless."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_bench", os.path.join(REPO, "tools", "serve_bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    res = mod.ledger_bench(requests=6, gap_ms=0.0, max_batch=2,
                           timeout_ms=5.0, bucket=(32, 64),
                           native_hw=(30, 60))
    for key in mod.LEDGER_REQUIRED_KEYS:
        assert key in res, key
    assert res["lowerings"] >= 1 and res["recompiles"] == 0
    name = exec_name((32, 64), "f32", "cold")
    assert name in res["executables"]
    assert res["executables"][name]["fingerprint"]
    assert res["compile_s_total"] > 0
    assert res["p99_ledger_on_ms"] > 0 and res["p99_ledger_off_ms"] > 0
