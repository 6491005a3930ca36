"""The reduction from a trace to busy, idle and named gaps: on intervals
worked by hand, and on a small trace recorded on the chip."""

import json
import os

import pytest

from benchmark.harness import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def planes_by_hand():
    # window 0..10 s; ops busy on [1,3] u [2,4] u [6,7] = 4 s; idle 6 s
    return {
        "/host:CPU": {"main": [(tr.SYNC_START, 0.0, 0.0), (tr.SYNC_END, 10.0, 0.0)]},
        "/device:TPU:0": {
            "XLA Ops": [("fusion.1", 1.0, 2.0), ("custom-call.7", 2.0, 2.0),
                        ("fusion.1", 6.0, 1.0), ("outside", 11.0, 1.0)],
            "XLA Modules": [("jit_step(1)", 1.0, 3.0), ("jit_step(1)", 6.0, 1.0),
                            ("jit_other(2)", 9.0, 4.0)],  # one start inside
        },
    }


def test_union_and_gaps():
    assert tr.union([(2, 4), (1, 3), (6, 7)]) == [(1, 4), (6, 7)]
    assert tr.total([(1, 4), (6, 7)]) == 4
    assert tr.gaps([(1, 4), (6, 7)], 0, 10) == [(0, 1), (4, 6), (7, 10)]


def test_busy_idle_and_gap_named_by_host_span():
    host = [("input_wait", 4.2, 5.9), ("dispatch", 7.0, 7.5), ("fetch", 7.4, 10.0)]
    r = tr.reduce_device(planes_by_hand(), host_spans=host)
    assert r["busy_s"] == pytest.approx(4.0)
    assert r["window_s"] == pytest.approx(10.0)
    assert r["ops"]["fusion.1"] == (pytest.approx(3.0), 2)
    assert "outside" not in r["ops"]
    # starts at 1 and 6: period 5 in a window of 10
    assert r["modules"] == {"jit_step(1)": pytest.approx(2.0),
                            "jit_other(2)": pytest.approx(1.0)}
    gaps = dict(r["idle_gaps"])
    assert gaps["input_wait"] == pytest.approx(2.0)   # the gap 4..6
    assert gaps["fetch"] == pytest.approx(3.0)        # the gap 7..10
    assert gaps["no_span"] == pytest.approx(1.0)      # the gap 0..1
    assert tr.executions(r, "jit_step") == pytest.approx(2.0)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(3.0)]


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_device({"/host:CPU": {}})


RECORDED = os.path.join(HERE, "data", "trace_small.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_trace_busy_matches_a_sampled_grid():
    with open(RECORDED) as f:
        planes = {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
                  for p, lines in json.load(f).items()}
    # the recording is cut short: take its own span as the window
    for lines in planes.values():
        for evs in lines.values():
            evs[:] = [e for e in evs if e[0] not in (tr.SYNC_START, tr.SYNC_END)]
    r = tr.reduce_device(planes)
    lo, hi = r["window"]
    ops = planes[tr.device_planes(planes)[0]]["XLA Ops"]
    n = 20000
    hit = 0
    for i in range(n):
        t = lo + (hi - lo) * (i + 0.5) / n
        hit += any(a <= t < a + d for _, a, d in ops)
    assert r["busy_s"] / r["window_s"] == pytest.approx(hit / n, abs=2e-3)
    assert 0 < r["busy_s"] <= r["window_s"]
