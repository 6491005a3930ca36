"""The comparison's arithmetic on numbers worked by hand."""

import pytest

from benchmark.harness import compare


def readings(losses, g, d, levels=None):
    return {"losses": losses, "grad_norms": g, "dparam_norms": d,
            "level_losses": levels or [[x, 2 * x] for x in losses]}


def test_worst_leaf_is_measured_against_the_larger_of_leaf_and_median():
    ref = {"a": 10.0, "b": 1.0, "c": 0.001}
    prog = {"a": 10.5, "b": 1.2, "c": 0.101}
    gap, leaf = compare.worst_leaf_gap(prog, ref)
    # a: .5/10, b: .2/1 (median 1), c: .1/max(.001, 1)
    assert leaf.startswith("b ") and gap == pytest.approx(0.2)


def test_dead_leaves_are_left_out_of_the_change_only():
    ref = readings([2.0], {"a": 1.0, "b": 1.0, "dead": 1e-6}, {"a": 1.0, "b": 1.0, "dead": 1.0})
    prog = readings([2.1], {"a": 1.0, "b": 1.1, "dead": 1e-6}, {"a": 1.0, "b": 1.0, "dead": 3.0})
    n = compare.train_numbers(prog, ref)
    assert n["loss_gap"] == pytest.approx(0.05)
    assert n["level_loss_gap"] == pytest.approx(0.05)
    assert n["grad_norm_gap"] == pytest.approx(0.1)
    assert n["dparam_norm_gap"] == pytest.approx(0.0)


def test_judge_fails_missing_nan_and_over():
    ok, c = compare.judge({"x": 0.1, "y": float("nan")}, {"x": 0.2, "y": 1.0, "z": 1.0})
    assert not ok and c["x"]["value"] == 0.1 and c["z"]["value"] is None
    assert compare.judge({"x": 0.1}, {"x": 0.2})[0]
    assert not compare.judge({"x": 0.3}, {"x": 0.2})[0]
    assert compare.judge({"x": 0.0}, {"x": 0.0})[0]
