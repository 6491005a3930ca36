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


# ------------------------------------- one timeline with the profiler

class _Annotations:
    """Stands where jax.profiler's two annotation classes stand and
    records what was entered and exited, in order."""

    def __init__(self, monkeypatch):
        import jax

        self.log = log = []

        class Recorded:
            def __init__(self, name, **kw):
                self.what = (name, kw)

            def __enter__(self):
                log.append(("enter", *self.what))

            def __exit__(self, *exc):
                log.append(("exit", *self.what))

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorded)
        monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Recorded)


def test_span_under_a_tracer_is_a_trace_annotation_too(monkeypatch):
    ann = _Annotations(monkeypatch)
    tr = Tracer()
    with tr.span("input_wait", depth=2):
        assert ann.log == [("enter", "input_wait", {})]
    assert ann.log[-1] == ("exit", "input_wait", {})
    assert [e["name"] for e in tr.events() if e["ph"] == "X"] == [
        "input_wait"]


def test_step_span_is_wrapped_in_a_step_annotation(monkeypatch):
    ann = _Annotations(monkeypatch)
    tr = obs_trace.install(Tracer())
    try:
        with obs_trace.span("dispatch", step=8, step_trace=("train", 7)):
            pass
    finally:
        obs_trace.uninstall()
    assert ann.log == [("enter", "train", {"step_num": 7}),
                       ("enter", "dispatch", {}),
                       ("exit", "dispatch", {}),
                       ("exit", "train", {"step_num": 7})]
    (span,) = [e for e in tr.events() if e["ph"] == "X"]
    assert span["args"] == {"step": 8}  # step_trace is no argument of the span


def test_null_tracer_span_makes_no_annotation(monkeypatch):
    ann = _Annotations(monkeypatch)
    with NullTracer().span("dispatch", step=1, step_trace=("train", 0)):
        pass
    with obs_trace.span("dispatch", step=1, step_trace=("train", 0)):
        pass  # nothing installed
    obs_trace.record_span("xla_compile", 0.0, 1.0, fun_name="f")
    assert ann.log == []


def test_importing_obs_imports_no_jax():
    """Subprocess: this suite has jax loaded already. A process without
    jax (the fleet router) records spans and mirrors nothing."""
    code = ("import sys\n"
            "import deepof_tpu.obs\n"
            "from deepof_tpu.obs.trace import Tracer\n"
            "tr = Tracer()\n"
            "with tr.span('route', step_trace=('serve', 1)):\n"
            "    pass\n"
            "assert len(tr.events()) == 3\n"
            "bad = [m for m in sys.modules if m == 'jax'"
            " or m.startswith('jax.') or m == 'jaxlib']\n"
            "assert not bad, bad\n")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=REPO,
                   timeout=120)


def test_flush_exports_the_perf_counter_epoch(tmp_path):
    before = time.perf_counter()
    tr = Tracer(path=str(tmp_path / "trace.json"))
    after = time.perf_counter()
    t0 = time.perf_counter()
    with tr.span("dispatch"):
        pass
    payload = _strict_loads(open(tr.flush()).read())
    epoch = payload["otherData"]["trace_epoch_perf_counter"]
    if tr.process_start is None:  # the epoch is the tracer's construction
        assert before <= epoch <= after
    else:  # the process's start, before anything it did
        assert epoch == tr.process_start <= before
    # the wall clock's epoch is the same instant
    assert payload["otherData"]["trace_epoch_unix"] == pytest.approx(
        time.time() - (time.perf_counter() - epoch), abs=0.05)
    (span,) = [e for e in payload["traceEvents"] if e["ph"] == "X"]
    # ts is microseconds after the epoch, on perf_counter
    assert epoch + span["ts"] * 1e-6 == pytest.approx(t0, abs=1e-3)


# -------------------------------------------- compiles become spans

BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _spans(tr):
    return [(e["name"], e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6,
             e.get("args")) for e in tr.events() if e["ph"] == "X"]


@pytest.mark.parametrize("hit,name", [(False, "xla_compile"),
                                      (True, "xla_cache_load")])
def test_backend_compile_event_becomes_a_span(hit, name):
    """A synthetic backend-compile event of 2 s, reported when it is
    over: a span that ENDS at the report and began 2 s earlier; after a
    cache-hit event it is a load, and the next one a compile again."""
    from deepof_tpu.train import warmup

    tr = obs_trace.install(Tracer())
    try:
        t_before = time.perf_counter() - tr._epoch
        if hit:
            warmup._on_event(CACHE_HIT)
        warmup._on_duration(BACKEND, 2.0, fun_name="step")
        t_after = time.perf_counter() - tr._epoch
        warmup._on_duration(BACKEND, 0.5, fun_name="eval")
        warmup._on_duration("/jax/some/other_duration", 1.0)
    finally:
        obs_trace.uninstall()
    first, second = _spans(tr)
    assert first[0] == name and first[3] == {"fun_name": "step"}
    assert t_before <= first[2] <= t_after  # ends "now", never after it
    assert first[2] - first[1] == pytest.approx(2.0, abs=1e-5)
    assert second[0] == "xla_compile" and second[3] == {"fun_name": "eval"}


def test_compile_events_with_no_tracer_record_nothing():
    from deepof_tpu.train import warmup

    warmup._on_event(CACHE_HIT)
    warmup._on_duration(BACKEND, 1.0, fun_name="step")  # consumes the hit
    tr = obs_trace.install(Tracer())
    try:
        warmup._on_duration(BACKEND, 1.0, fun_name="step")
    finally:
        obs_trace.uninstall()
    assert [s[0] for s in _spans(tr)] == ["xla_compile"]


def test_a_real_jit_compile_under_a_tracer_is_traced_and_compiled():
    import jax
    import jax.numpy as jnp

    from deepof_tpu.train.warmup import install_cache_counters

    install_cache_counters()

    def toy_fn_for_the_span_test(x):  # a fresh function: nothing cached
        return jnp.tanh(x) * 3.0 + 1.0

    tr = obs_trace.install(Tracer())
    try:
        jax.jit(toy_fn_for_the_span_test)(jnp.ones((7, 3))).block_until_ready()
    finally:
        obs_trace.uninstall()
    mine = {s[0] for s in _spans(tr)
            if "toy_fn_for_the_span_test" in s[3]["fun_name"]}
    assert {"jax_trace", "jax_lower", "xla_compile"} <= mine
    assert all(s[2] >= s[1] >= 0 for s in _spans(tr))


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

    calls, options = [], []

    def start(d, profiler_options=None):
        calls.append(("start", d))
        options.append(profiler_options)

    monkeypatch.setattr(jax.profiler, "start_trace", start)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))

    p = ProfilerSession(str(tmp_path), steps=(2, 4))
    assert p.enabled  # a window implies enabled
    p.maybe_start()  # loop entry: window mode must NOT start here
    assert calls == []
    p.observe(0)
    p.observe(2)  # window opens
    assert [c[0] for c in calls] == ["start"]
    # the options the benchmark's own short trace takes: the program's
    # spans are annotations, so the Python tracer stays off
    assert options[0].python_tracer_level == 0
    assert options[0].host_tracer_level == 2
    p.observe(3)
    p.observe(4)  # window closes
    assert [c[0] for c in calls] == ["start", "stop"]
    p.observe(6)  # never restarts
    p.maybe_stop()  # teardown: already stopped, must not double-stop
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


# ------------------------------------- set-up and the loop as spans

def test_toy_fit_spans_setup_first_step_and_nests(tmp_path):
    """A 4-step fit at the smallest size that walks fit() end to end
    (one cpu device; tests/test_resilience.py's recipe), tracing on:
    the process's `boot` and `import` lead the timeline, `Trainer.__init__`
    and the first step are spans with their children, compiles are spans,
    the ledger's second lowering is `ledger_lower`, no record carries the
    FLOPs telemetry, and the main thread's spans nest: no two of them
    partially overlap, which is what lets a reader take their union."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    res = subprocess.run(
        [sys.executable, "-m", "deepof_tpu", "train", "--preset",
         "flyingchairs", "--synthetic", "--max-steps", "4",
         "--log-dir", str(tmp_path), "--trace",
         "--set", "model=flownet_s", "--set", "width_mult=0.25",
         "--set", "data.image_size=32,32", "--set", "data.gt_size=32,32",
         "--set", "data.batch_size=2", "--set", "train.log_every=1",
         "--set", "train.eval_every=0",
         "--set", "train.ckpt_every_epochs=1000000"],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert res.returncode == 0, (res.stdout[-1000:], res.stderr[-2000:])
    payload = _strict_loads(open(str(tmp_path / "trace.json")).read())
    assert payload["otherData"]["trace_epoch_perf_counter"] > 0
    events = payload["traceEvents"]
    named = {e["tid"]: e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    main = [(e["name"], e["ts"], e["ts"] + e["dur"], e.get("args", {}))
            for e in events
            if e["ph"] == "X" and named.get(e["tid"]) == "MainThread"]

    def one(name):
        (found,) = [s for s in main if s[0] == name]
        return found

    def inside(child, parent, slack=0.2):  # microseconds: ts is rounded
        return parent[1] - slack <= child[1] and child[2] <= parent[2] + slack

    init, first = one("trainer_init"), one("first_step")
    boot, imports = one("boot"), one("import")
    assert boot[1] >= 0 and boot[1] < 0.2  # ts 0 is the process's start
    assert abs(boot[2] - imports[1]) <= 0.2  # adjacent: the package's mark
    assert imports[2] <= init[1]
    for child in ("model_init", "ckpt_restore", "state_place", "step_build"):
        assert inside(one(child), init), child
    assert init[2] <= first[1]
    compiling = [s for s in main if s[0] == "dispatch"
                 and s[3].get("compile")]
    assert len(compiling) == 1 and inside(compiling[0], first)
    assert inside(one("ledger_lower"), first)
    assert not any(s[0] == "relower" for s in main)
    assert any(s[0] == "input_wait" and inside(s, first) for s in main)
    assert sum(s[0] == "dispatch" for s in main) == 4
    # the state's jitted init compiled inside model_init, the step inside
    # the first dispatch; the retrace for the ledger traced again but
    # did not compile again
    assert any(s[0] == "xla_compile" and inside(s, one("model_init"))
               for s in main)
    assert any(s[0] == "xla_compile" and inside(s, compiling[0])
               and "step" in s[3]["fun_name"] for s in main)
    in_ledger = {s[0] for s in main if inside(s, one("ledger_lower"))}
    assert "jax_trace" in in_ledger and "xla_compile" not in in_ledger
    assert any(s[0] == "drain" for s in main)  # the bounded one at the end
    for i, a in enumerate(main):
        for b in main[i + 1:]:
            apart = a[2] <= b[1] + 0.2 or b[2] <= a[1] + 0.2
            assert apart or inside(a, b) or inside(b, a), (a, b)
    records = [_strict_loads(line)
               for line in open(str(tmp_path / "metrics.jsonl"))]
    for key in ("flops_per_step", "model_tflops", "mfu_nominal"):
        assert not any(key in r for r in records), key


# ------------------------------------- names inside the device program

@pytest.mark.parametrize("time_step,loss_fn", [(2, "pyramid_loss"),
                                               (3, "pyramid_loss_multi")])
def test_lowered_train_step_carries_the_scopes(time_step, loss_fn):
    """Lower (never compile) a toy train step: the debug-info text holds
    `forward`, `optimizer`, `preprocess` and one `loss_level_<k>` per flow
    scale, forward (`jvp(...)`) and backward (`transpose(jvp(...))`),
    with `warp`, `photometric` and `smooth` inside each level."""
    import dataclasses
    import re

    import jax

    from deepof_tpu.core.config import get_config
    from deepof_tpu.models.registry import build_model
    from deepof_tpu.parallel.mesh import build_mesh
    from deepof_tpu.train.warmup import lower_train_step

    cfg = get_config("flyingchairs")
    cfg = cfg.replace(
        model="flownet_s", width_mult=0.25,
        data=dataclasses.replace(cfg.data, dataset="synthetic",
                                 image_size=(32, 32), gt_size=(32, 32),
                                 batch_size=2, time_step=time_step))
    lowered = lower_train_step(cfg, build_mesh(devices=jax.devices()[:1]))
    names = set(re.findall(r'loc\("(jit\(step\)/[^"]+)"',
                           lowered.as_text(debug_info=True)))
    parts = {tuple(n.split("/")[1:3]) for n in names if n.count("/") >= 2}
    outer = {p[0] for p in parts}
    n_scales = len(build_model("flownet_s", flow_channels=2).flow_scales)
    assert n_scales == 6
    for k in range(n_scales):
        assert f"jvp(loss_level_{k})" in outer, (loss_fn, k)
        assert f"transpose(jvp(loss_level_{k}))" in outer, (loss_fn, k)
        for inner in ("warp", "photometric", "smooth"):
            assert (f"jvp(loss_level_{k})", inner) in parts, (k, inner)
    assert f"jvp(loss_level_{n_scales})" not in outer
    assert {"jvp(forward)", "transpose(jvp(forward))", "optimizer",
            "jvp(preprocess)"} <= outer


# ---------------------------------------------- fit() acceptance (slow)

@pytest.mark.slow
def test_fit_writes_trace_heartbeat_and_telemetry(tmp_path):
    """The ISSUE acceptance: a cpu fit() with tracing on produces a
    strict-JSON Chrome trace with >= 3 distinct named threads and
    overlapping spans, a fresh heartbeat.json at exit, and device-memory
    fields in periodic train records.

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
