"""The readers of the program's spans and of the named kernels, each on an
`observed` made by hand."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402

M, P, F = "MainThread", "prefetch", "metrics-fetcher"


def read(metric, **obs):
    return bench_run.load_reader(metric).read(obs)


def test_loop_self_clips_to_the_window_and_counts_nested_spans_once():
    spans = [
        ("first_step", M, 0.0, 9.0),        # before the window
        ("input_wait", M, 9.5, 11.0),       # straddles its start: 1.0 inside
        ("dispatch", M, 11.5, 14.0),        # 2.5
        ("jax_trace", M, 12.0, 13.0),       # nested in dispatch: nothing more
        ("submit_wait", M, 14.0, 17.0),     # 3.0, touching the one before
        ("drain", M, 19.0, 25.0),           # straddles its end: 1.0 inside
        ("put", P, 10.0, 20.0),             # another thread: no cover
        ("fetch", F, 17.0, 19.0),
    ]
    # covered 1.0 + 2.5 + 3.0 + 1.0 = 7.5 of 10
    assert read("loop_self_pct.train", spans=spans, window=(10.0, 20.0)) \
        == pytest.approx(25.0)


FIRST = ("first_step", M, 0.0, 5.0)


@pytest.mark.parametrize("spans,window", [
    ([FIRST, ("put", P, 10.0, 20.0)], (10.0, 20.0)),      # no MainThread span
    ([FIRST, ("dispatch", M, 6.0, 7.0)], (10.0, 20.0)),   # none in the window
    ([FIRST, ("dispatch", M, 11.0, 12.0)], (None, None)),  # no window
    # a program whose waits are not spans yet: what is uncovered is waiting
    ([("input_wait", M, 10.0, 11.0), ("dispatch", M, 11.0, 12.0)], (10.0, 20.0)),
])
def test_loop_self_reads_nothing_without_the_spans_it_needs(spans, window):
    assert read("loop_self_pct.train", spans=spans, window=window) is None


@pytest.mark.parametrize("metric,span", [("trainer_init_s.train", "trainer_init"),
                                         ("first_step_s.train", "first_step")])
def test_duration_readers(metric, span):
    spans = [(span, M, 3.0, 17.5), (span, P, 0.0, 100.0),
             ("dispatch", M, 4.0, 5.0)]
    assert read(metric, spans=spans) == pytest.approx(14.5)
    assert read(metric, spans=[("dispatch", M, 4.0, 5.0)]) is None
    assert read(metric, spans=[]) is None


def test_compile_miss_sums_compiles_on_every_thread():
    spans = [("xla_compile", M, 1.0, 91.0), ("xla_compile", P, 2.0, 2.5),
             ("xla_cache_load", M, 100.0, 115.0), ("jax_trace", M, 0.0, 1.0)]
    assert read("compile_miss_s.train", spans=spans) == pytest.approx(90.5)


def test_compile_miss_is_zero_when_every_compile_was_a_load():
    spans = [("xla_cache_load", M, 100.0, 115.0), ("dispatch", M, 99.0, 116.0)]
    value = read("compile_miss_s.train", spans=spans)
    assert value == 0.0 and isinstance(value, float)


def test_compile_miss_reads_nothing_without_compile_spans():
    assert read("compile_miss_s.train", spans=[("dispatch", M, 0.0, 1.0)]) is None
    assert read("compile_miss_s.train", spans=[]) is None


def kernel_obs(ops):
    from benchmark.harness.peaks import peaks_for

    return dict(
        config={"step_kernels": [
            {"kernel": "warp.forward", "args": {"h": 80, "w": 112, "c": 3}},
            {"kernel": "warp.flow_grad", "args": {"h": 80, "w": 112, "c": 3}},
            {"kernel": "warp.forward", "args": {"h": 40, "w": 56, "c": 3}}]},
        traffic={}, batch=64, chips=1, peaks=peaks_for("TPU v5 lite"),
        device={"chips": 1, "modules": {"jit_step(7)": 2.0}, "ops": ops})


CALL = ' = f32[64,3,80,128] custom-call(%a, %b), custom_call_target="tpu_custom_call"'


def test_named_kernel_share_takes_its_own_entries_and_events():
    from benchmark.kernels import warp
    from benchmark.kernels.roofline import least_seconds

    ops = {"%warp_fwd.5" + CALL: (0.008, 2), "%warp_fwd.7" + CALL: (0.002, 2),
           "%warp_flow_grad.5" + CALL: (0.010, 2),
           "%warp_fwd_fusion = f32[8] fusion(%x)": (5.0, 2),  # no Mosaic call
           "%fusion.1 = f32[8] fusion(%x)": (1.0, 2)}
    obs = kernel_obs(ops)
    peaks = obs["peaks"]
    least_fwd = sum(least_seconds(warp.forward(64, h, w, 3), peaks)[0]
                    for h, w in ((80, 112), (40, 56)))
    least_fg = least_seconds(warp.flow_grad(64, 80, 112, 3), peaks)[0]
    fwd = read("warp_fwd_roofline_pct.train", **obs)
    fg = read("warp_flow_grad_roofline_pct.train", **obs)
    assert fwd == pytest.approx(100.0 * least_fwd * 2.0 / 0.010)
    assert fg == pytest.approx(100.0 * least_fg * 2.0 / 0.010)
    # the cell's one number for all Mosaic calls lies between the two
    both = read("pallas_roofline_pct.train", **obs)
    assert min(fwd, fg) <= both <= max(fwd, fg)


def test_named_kernel_share_reads_nothing_from_unnamed_kernels():
    obs = kernel_obs({"%name.10" + CALL: (0.008, 2), "%name.15" + CALL: (0.01, 2)})
    assert read("warp_fwd_roofline_pct.train", **obs) is None
    assert read("warp_flow_grad_roofline_pct.train", **obs) is None
    assert read("pallas_roofline_pct.train", **obs) is not None


def test_every_per_layer_metric_of_the_benchmark_has_a_reader():
    import json

    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        assert callable(bench_run.load_reader(m["name"]).read), m["name"]
