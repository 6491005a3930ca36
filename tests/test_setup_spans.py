"""Set-up under the program's own spans (`obs/trace.py`): the process's
start as the trace's epoch, `boot` and `import` once a process, a
`kernel_trace` span for every Pallas kernel's trace (in interpret mode on
the CPU) that changes nothing in the lowered program, and the benchmark's
eight readers of these spans and the ledger's `ledger_lower`."""

import collections
import time

import jax
import jax.numpy as jnp
import pytest

import deepof_tpu
from deepof_tpu.obs import trace as obs_trace
from deepof_tpu.obs.trace import Tracer
from deepof_tpu.train import loop


@pytest.fixture
def fresh_process(monkeypatch):
    """The process as if no tracer had recorded its set-up yet; whatever
    a test installs is uninstalled after it."""
    monkeypatch.setattr(obs_trace, "_setup_recorded", False)
    yield
    obs_trace.uninstall()


def _spans(tracer, name=None):
    return [e for e in tracer.events() if e["ph"] == "X"
            and (name is None or e["name"] == name)]


def test_process_start_is_before_the_package_mark():
    start = obs_trace.process_start()
    assert start is not None  # Linux: /proc/self/stat is there
    assert start <= deepof_tpu.T_PACKAGE_START < start + 60.0
    assert deepof_tpu.T_PACKAGE_START <= loop.T_IMPORTS_DONE


def test_boot_and_import_are_recorded_from_the_process_start(fresh_process):
    tracer = obs_trace.install(Tracer())
    obs_trace.record_setup(deepof_tpu.T_PACKAGE_START, loop.T_IMPORTS_DONE)
    (boot,), (imports,) = _spans(tracer, "boot"), _spans(tracer, "import")
    assert tracer._epoch == tracer.process_start
    assert boot["ts"] == 0.0  # the epoch is the process's start
    assert boot["ts"] + boot["dur"] == pytest.approx(imports["ts"], abs=0.2)
    assert (imports["ts"] + imports["dur"]) * 1e-6 == pytest.approx(
        loop.T_IMPORTS_DONE - tracer._epoch, abs=1e-6)
    named = {e["tid"]: e["args"]["name"] for e in tracer.events()
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert named[boot["tid"]] == named[imports["tid"]] == "MainThread"


def test_no_boot_span_where_the_process_start_is_unknown(fresh_process,
                                                         monkeypatch,
                                                         tmp_path):
    monkeypatch.setattr(obs_trace, "_PROC_STAT", str(tmp_path / "absent"))
    assert obs_trace.process_start() is None
    before = time.perf_counter()
    tracer = obs_trace.install(Tracer())
    after = time.perf_counter()
    obs_trace.record_setup(deepof_tpu.T_PACKAGE_START, loop.T_IMPORTS_DONE)
    assert before <= tracer._epoch <= after  # the tracer's construction
    assert _spans(tracer) == []  # both would lie before ts 0


def test_a_second_tracer_records_no_second_boot(fresh_process):
    first = obs_trace.install(Tracer())
    obs_trace.record_setup(deepof_tpu.T_PACKAGE_START, loop.T_IMPORTS_DONE)
    obs_trace.uninstall()
    second = obs_trace.install(Tracer())
    obs_trace.record_setup(deepof_tpu.T_PACKAGE_START, loop.T_IMPORTS_DONE)
    assert [e["name"] for e in _spans(first)] == ["boot", "import"]
    assert _spans(second) == []


def test_record_setup_with_no_tracer_waits_for_one(fresh_process):
    obs_trace.record_setup(deepof_tpu.T_PACKAGE_START, loop.T_IMPORTS_DONE)
    tracer = obs_trace.install(Tracer())
    obs_trace.record_setup(deepof_tpu.T_PACKAGE_START, loop.T_IMPORTS_DONE)
    assert [e["name"] for e in _spans(tracer)] == ["boot", "import"]


# ------------------------------------------------- each kernel's trace

def _warp(grad):
    from deepof_tpu.ops.pallas.warp import backward_warp_pallas

    img = jnp.ones((1, 8, 16, 3))
    flow = jnp.zeros((1, 8, 16, 2))
    f = lambda fl: jnp.sum(backward_warp_pallas(img, fl, interpret=True))  # noqa: E731
    return (jax.grad(f) if grad else f), (flow,)


def _corr(grad):
    from deepof_tpu.ops.pallas.corr import correlation_pallas

    f1 = jnp.ones((1, 8, 8, 4))
    f = lambda a, b: jnp.sum(correlation_pallas(a, b, 2, 1, True))  # noqa: E731
    return (jax.grad(f, argnums=(0, 1)) if grad else f), (f1, f1)


def _mla(grad):
    from deepof_tpu.ops.pallas import attention as K

    s = 256
    ops = (jnp.ones((1, s, 1, 128)), jnp.ones((1, s, 1, 64)),
           jnp.ones((1, s, 1, 128)), jnp.ones((1, s, 64)),
           jnp.ones((1, s, 1, 128)))
    f = lambda *o: jnp.sum(K.fused_causal_attention(  # noqa: E731
        *o, 0.1, 128, 128, interpret=True))
    return (jax.grad(f, argnums=range(5)) if grad else f), ops


def _bd(grad):
    from deepof_tpu.ops import attention as A
    from deepof_tpu.ops.pallas import attention as K

    s = 256
    q, kv = jnp.ones((1, s, 2, 128)), jnp.ones((1, s, 1, 128))
    f = lambda *o: jnp.sum(K.fused_grouped_attention(  # noqa: E731
        *o, 0.1, 128, 128, A.CAUSAL, interpret=True))
    return (jax.grad(f, argnums=range(3)) if grad else f), (q, kv, kv)


def _qk_prep(grad):
    from deepof_tpu.ops.pallas import qk_prep as P

    s, d = 128, 128
    x, scale = jnp.ones((1, s, d)), jnp.ones((d,))
    f = lambda x, g: jnp.sum(P.qk_prep(  # noqa: E731
        x, jnp.arange(s), 1, 1e4, False, jnp.float32, 128, g, 1e-6,
        interpret=True))
    return (jax.grad(f, argnums=(0, 1)) if grad else f), (x, scale)


#: kernel name -> (a function whose trace calls it, differentiated or not)
KERNELS = {
    "warp_fwd": (_warp, False), "warp_flow_grad": (_warp, True),
    "corr_fwd": (_corr, False), "corr_bwd": (_corr, True),
    "mla_attn_fwd": (_mla, False), "mla_attn_bwd": (_mla, True),
    "bd_attn_fwd": (_bd, False), "bd_attn_bwd": (_bd, True),
    "qk_prep_fwd": (_qk_prep, False), "qk_prep_bwd": (_qk_prep, True),
}


def _pallas_calls(jaxpr) -> collections.Counter:
    """Kernel name of every `pallas_call` in a jaxpr, sub-jaxprs included."""
    found = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            found[getattr(name, "name", name)] += 1
        for sub in _params_jaxprs(eqn.params):
            found.update(_pallas_calls(sub))
    return found


def _params_jaxprs(params):
    for v in params.values():
        for item in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(item, "eqns"):
                yield item
            elif hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_a_kernel_trace_is_one_span_naming_its_kernel(kernel):
    make, grad = KERNELS[kernel]
    fn, args = make(grad)
    jax.clear_caches()  # every kernel below is traced, none found cached
    tracer = obs_trace.install(Tracer())
    try:
        jaxpr = jax.make_jaxpr(fn)(*args)
    finally:
        obs_trace.uninstall()
    spans = collections.Counter(e["args"]["kernel"]
                                for e in _spans(tracer, "kernel_trace"))
    assert spans[kernel] == 1
    # one span per call the program holds, of every kernel traced
    assert spans == _pallas_calls(jaxpr.jaxpr)


@pytest.mark.parametrize("kernel", ["corr_bwd", "qk_prep_bwd"])
def test_a_traced_kernel_lowers_to_the_same_text(kernel):
    """The span stands around the call: with a tracer installed and
    without one the program, its locations included, is the same."""
    make, grad = KERNELS[kernel]
    fn, args = make(grad)
    texts = []
    for tracer in (None, Tracer()):
        jax.clear_caches()
        with obs_trace.installed(tracer):
            texts.append(jax.jit(fn).lower(*args).as_text(debug_info=True))
    assert texts[0] == texts[1]


# ------------------------------------------------- the benchmark's readers

READERS = {f"{m}.{fam}": span for m, span in (
    ("boot_s", "boot"), ("import_s", "import"),
    ("kernel_trace_s", "kernel_trace"), ("ledger_lower_s", "ledger_lower"))
    for fam in ("train", "lm_train")}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_sums_its_main_thread_span(metric):
    from benchmark.run import load_reader

    span = READERS[metric]
    reader = load_reader(metric)
    spans = [(span, "MainThread", 1.0, 1.5), (span, "MainThread", 2.0, 2.25),
             (span, "prefetch", 0.0, 9.0), ("first_step", "MainThread", 0, 5)]
    assert reader.read({"spans": spans}) == pytest.approx(0.75)
    # the parent's program records no such span: nothing is reported
    assert reader.read({"spans": spans[3:]}) is None
