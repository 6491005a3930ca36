"""Observability layer (deepof_tpu/obs/): span tracer ring/schema/
thread-safety, heartbeat file + wedge watchdog, profiler step window,
non-finite-safe JSONL, and the slow-tier fit() acceptance pin (trace
timeline with >= 3 named threads, fresh heartbeat, telemetry fields).

Fast-tier discipline: pure host-side, no model compiles, no sleep
longer than ~100 ms (watchdog tests use sub-100 ms periods and
event-waits with generous timeouts that return early).
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from deepof_tpu.obs import trace as obs_trace
from deepof_tpu.obs.heartbeat import Heartbeat, dump_all_stacks
from deepof_tpu.obs.trace import NullTracer, Tracer
from deepof_tpu.train.metrics_log import MetricsLogger, ProfilerSession

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _strict_loads(text: str):
    """json.loads that REJECTS bare NaN/Infinity tokens (the strictness
    real parsers — jq, browsers, other languages — apply)."""

    def _no_const(name):
        raise ValueError(f"non-JSON constant {name!r}")

    return json.loads(text, parse_constant=_no_const)


# --------------------------------------------------------------- tracer

def test_tracer_span_schema(tmp_path):
    path = str(tmp_path / "trace.json")
    tr = Tracer(path=path, ring_size=128)
    with tr.span("dispatch", step=4):
        time.sleep(0.001)
    tr.instant("watchdog_wedge", age_s=1.5)
    assert tr.flush() == path

    payload = _strict_loads(open(path).read())
    events = payload["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in meta)
    thread_names = [e["args"]["name"] for e in meta
                    if e["name"] == "thread_name"]
    assert "MainThread" in thread_names
    spans = [e for e in events if e["ph"] == "X"]
    assert len(spans) == 1
    s = spans[0]
    assert s["name"] == "dispatch" and s["args"] == {"step": 4}
    assert isinstance(s["ts"], (int, float)) and isinstance(s["dur"],
                                                            (int, float))
    assert s["dur"] >= 1e3  # the 1 ms sleep, in microseconds
    assert any(e["ph"] == "i" and e["name"] == "watchdog_wedge"
               for e in events)


def test_tracer_ring_bound_and_thread_safety(tmp_path):
    """200 spans from 4 concurrent threads against a 64-event ring: no
    exception, <= 64 retained, every retained event well-formed, all
    writer threads named in the metadata."""
    tr = Tracer(path=str(tmp_path / "trace.json"), ring_size=64)
    n_per_thread = 50
    gate = threading.Barrier(4, timeout=10)

    def writer(k: int):
        gate.wait()  # all four alive at once => four distinct idents
        for i in range(n_per_thread):
            with tr.span(f"work-{k}", i=i):
                pass

    threads = [threading.Thread(target=writer, args=(k,),
                                name=f"writer-{k}") for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    payload = _strict_loads(open(tr.flush()).read())
    spans = [e for e in payload["traceEvents"] if e["ph"] == "X"]
    assert 0 < len(spans) <= 64  # ring bound held
    assert payload["otherData"]["dropped_spans"] == 4 * n_per_thread - len(
        spans)
    named = {e["args"]["name"] for e in payload["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {f"writer-{k}" for k in range(4)} <= named
    for s in spans:
        assert s["name"].startswith("work-") and s["dur"] >= 0


def test_module_level_tracer_install_uninstall(tmp_path):
    """span()/instant() are no-ops with nothing installed, record after
    install, and stop recording after uninstall."""
    assert isinstance(obs_trace.current(), NullTracer)
    with obs_trace.span("ignored"):
        pass  # must not raise and must not record anywhere
    tr = obs_trace.install(Tracer(path=str(tmp_path / "t.json")))
    try:
        assert obs_trace.current() is tr
        with obs_trace.span("seen"):
            pass
    finally:
        obs_trace.uninstall()
    with obs_trace.span("after"):
        pass
    names = [e["name"] for e in tr.events() if e["ph"] == "X"]
    assert names == ["seen"]
    assert obs_trace.flush_current() is None  # null tracer again


# ------------------------------------------------------------ heartbeat

def test_heartbeat_file_schema_and_atomicity(tmp_path):
    path = str(tmp_path / "heartbeat.json")
    hb = Heartbeat(path, period_s=0.05, watchdog_min_s=60.0,
                   sample=lambda: {"queue_depth": 3})
    try:
        deadline = time.monotonic() + 5.0
        seen = 0
        rec = None
        while time.monotonic() < deadline and seen < 20:
            hb.beat(seen + 1)
            if os.path.exists(path):
                # atomic rewrite: EVERY read parses — no torn files
                rec = _strict_loads(open(path).read())
                seen += 1
            time.sleep(0.01)
        assert rec is not None, "heartbeat never wrote its file"
        for key in ("time", "pid", "step", "beats", "last_step_age_s",
                    "step_time_median_s", "wedged", "wedges", "rss_bytes",
                    "dev_mem_bytes_in_use", "dev_mem_peak_bytes",
                    "queue_depth"):
            assert key in rec, key
        assert rec["wedged"] is False and rec["wedges"] == 0
        assert rec["queue_depth"] == 3  # sample callback merged in
        assert rec["rss_bytes"] is None or rec["rss_bytes"] > 0
    finally:
        hb.close()
    # close() writes a final fresh record
    final = _strict_loads(open(path).read())
    assert time.time() - final["time"] < 5.0
    assert final["step"] == rec["step"] or final["step"] >= 1


def test_watchdog_fires_on_wedge_and_dumps_stacks(tmp_path):
    """The acceptance pin: steps stop completing -> within the
    configured factor the watchdog logs every thread's stack (naming the
    wedged thread) and flushes the trace ring."""
    release = threading.Event()

    def stuck():
        release.wait(timeout=30)

    wedged_thread = threading.Thread(target=stuck, name="wedged-fetcher",
                                     daemon=True)
    wedged_thread.start()

    tracer = Tracer(path=str(tmp_path / "trace.json"), ring_size=64)
    with tracer.span("pre-wedge"):
        pass
    logs: list = []
    fired = threading.Event()
    hb = Heartbeat(str(tmp_path / "heartbeat.json"), period_s=0.05,
                   watchdog_factor=3.0, watchdog_min_s=0.05,
                   log=lambda step, msg: logs.append((step, msg)),
                   tracer=tracer, on_wedge=lambda dump: fired.set())
    try:
        for i in range(4):  # arm with ~instant steps (median ~ms)
            hb.beat(i + 1)
        # ... then no step completes: threshold = max(3 x median, 50 ms)
        assert fired.wait(timeout=10.0), "watchdog never fired"
        step, msg = logs[0]
        assert step == 4
        assert "WATCHDOG" in msg
        assert "wedged-fetcher" in msg  # the stack dump names the thread
        assert "MainThread" in msg
        assert "release.wait" in msg  # ... and where it is stuck
        # trace ring flushed on the trigger, with the wedge marker
        payload = _strict_loads(open(tracer.path).read())
        names = [e["name"] for e in payload["traceEvents"]]
        assert "watchdog_wedge" in names and "pre-wedge" in names
        # one firing per stall (no log spam while still wedged)
        time.sleep(0.12)  # >= 2 poll periods
        assert sum(1 for _, m in logs if "WATCHDOG" in m) == 1
        hb_rec = _strict_loads(
            open(str(tmp_path / "heartbeat.json")).read())
        assert hb_rec["wedged"] is True and hb_rec["wedges"] == 1
        # a resumed step re-arms
        hb.beat(5)
        assert _strict_loads(
            open(tracer.path).read()) is not None  # file still valid
    finally:
        release.set()
        hb.close()


def test_dump_all_stacks_names_threads():
    dump = dump_all_stacks()
    assert "MainThread" in dump
    assert "test_dump_all_stacks_names_threads" in dump  # caller frame


# ---------------------------------------------------- non-finite JSONL

def test_metrics_logger_serializes_nonfinite_as_null(tmp_path):
    log = MetricsLogger(str(tmp_path), echo=False)
    log.log("train", 1, loss=float("nan"), grad_norm=float("inf"),
            scales=[1.0, float("-inf"), 2.0], ok=3.5, note=None)
    log.close()
    lines = open(os.path.join(str(tmp_path), "metrics.jsonl")).readlines()
    assert len(lines) == 1
    rec = _strict_loads(lines[0])  # bare NaN/Infinity would fail here
    assert rec["loss"] is None and rec["grad_norm"] is None
    assert rec["scales"] == [1.0, None, 2.0]
    assert rec["ok"] == 3.5 and rec["note"] is None


# ------------------------------------------------- profiler step window

def test_profiler_session_step_window(tmp_path, monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))

    p = ProfilerSession(str(tmp_path), steps=(2, 4))
    assert p.enabled  # a window implies enabled
    p.maybe_start()  # loop entry: window mode must NOT start here
    assert calls == []
    p.observe(0)
    p.observe(2)  # window opens
    assert [c[0] for c in calls] == ["start"]
    p.observe(3)
    p.observe(4)  # window closes
    assert [c[0] for c in calls] == ["start", "stop"]
    p.observe(6)  # never restarts
    p.maybe_stop()  # teardown: already stopped, must not double-stop
    assert [c[0] for c in calls] == ["start", "stop"]

    # stride-proof: steps_per_call=8 jumps the observed gsteps right
    # over a narrow window — the dispatch CONTAINING it must be captured
    calls.clear()
    s = ProfilerSession(str(tmp_path), steps=(100, 104))
    s.observe(96, steps_per_call=8)  # next dispatch covers 97..104
    assert [c[0] for c in calls] == ["start"]
    s.observe(104, steps_per_call=8)
    assert [c[0] for c in calls] == ["start", "stop"]

    # whole-run mode unchanged
    calls.clear()
    q = ProfilerSession(str(tmp_path), enabled=True)
    q.maybe_start()
    q.observe(100)  # no-op without a window
    q.maybe_stop()
    assert [c[0] for c in calls] == ["start", "stop"]

    with pytest.raises(ValueError):
        ProfilerSession(str(tmp_path), steps=(4, 2))
    with pytest.raises(ValueError):
        ProfilerSession(str(tmp_path), steps=(-1, 2))


# ------------------------------------------------------ trace_summary

def test_trace_summary_tool(tmp_path):
    tr = Tracer(path=str(tmp_path / "trace.json"))
    for i in range(3):
        with tr.span("dispatch", step=i):
            pass
    with tr.span("fetch"):
        time.sleep(0.002)
    tr.flush()
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_summary.py"),
         str(tmp_path / "trace.json"), "--top", "2"],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stderr[-500:]
    assert "dispatch" in res.stdout and "fetch" in res.stdout
    assert "longest spans" in res.stdout


# ---------------------------------------------- fit() acceptance (slow)

@pytest.mark.slow
def test_fit_writes_trace_heartbeat_and_telemetry(tmp_path):
    """The ISSUE acceptance: a cpu fit() with tracing on produces a
    strict-JSON Chrome trace with >= 3 distinct named threads and
    overlapping spans, a fresh heartbeat.json at exit, and model_tflops
    + device-memory fields in periodic train records.

    Runs the CLI in a SUBPROCESS, deliberately: the test exercises the
    real `--trace` entry path, in a process whose signal handlers,
    threads and compile cache (off on cpu by the CLI's auto gate) are
    its own and not the suite's."""
    period = 0.2
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    res = subprocess.run(
        [sys.executable, "-m", "deepof_tpu", "train", "--preset",
         "flyingchairs", "--synthetic", "--max-steps", "6",
         "--log-dir", str(tmp_path), "--trace",
         "--set", "model=flownet_s", "--set", "width_mult=0.25",
         "--set", "train.log_every=1", "--set", "train.eval_every=0",
         "--set", f"obs.heartbeat_period_s={period}"],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert res.returncode == 0, (res.stdout[-1000:], res.stderr[-2000:])

    payload = _strict_loads(open(str(tmp_path / "trace.json")).read())
    events = payload["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    named = {e["tid"]: e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    span_tids = {s["tid"] for s in spans}
    used_names = {named[tid] for tid in span_tids if tid in named}
    assert "MainThread" in used_names
    assert "prefetch" in used_names
    assert "metrics-fetcher" in used_names
    assert len(used_names) >= 3
    # the overlap PRs 1-2 claim, visible as a timeline: some span on one
    # thread runs concurrently with a span on another
    def overlaps(a, b):
        return (a["tid"] != b["tid"]
                and a["ts"] < b["ts"] + b["dur"]
                and b["ts"] < a["ts"] + a["dur"])

    assert any(overlaps(a, b) for i, a in enumerate(spans)
               for b in spans[i + 1:]), "no cross-thread span overlap"
    assert {"dispatch", "input_wait", "put", "assemble", "fetch"} <= {
        s["name"] for s in spans}

    train = [r for r in map(_strict_loads,
                            open(str(tmp_path / "metrics.jsonl")))
             if r.get("kind") == "train"]
    assert train, "no periodic train records"

    hb = _strict_loads(open(str(tmp_path / "heartbeat.json")).read())
    # heartbeat.close() writes a final record AFTER the last train
    # record, so at process exit the file was younger than 2x the period
    assert hb["time"] >= train[-1]["time"] - 2 * period
    assert hb["step"] == 6 and hb["wedged"] is False
    last = train[-1]
    for key in ("dev_mem_bytes_in_use", "dev_mem_peak_bytes", "rss_bytes"):
        assert key in last, key
    assert any(isinstance(r.get("model_tflops"), (int, float))
               for r in train), "model_tflops never logged"
