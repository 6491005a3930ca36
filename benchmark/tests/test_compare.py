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


def test_smoothness_gap_is_the_first_steps_over_the_live_levels():
    g, d = {"a": 1.0}, {"a": 1.0}
    ref = dict(readings([2.0, 3.0], g, d),
               level_smooth_losses=[[0.4, 0.1, 0.0], [9.0, 9.0, 9.0]])
    prog = dict(readings([2.0, 3.0], g, d),
                level_smooth_losses=[[0.412, 0.104, 0.0], [1.0, 1.0, 1.0]])
    n = compare.train_numbers(prog, ref)
    # step 1 only: gaps 0.03 and 0.04; the level whose reference is nought left out
    assert n["level_smooth_rms_gap_step1"] == pytest.approx((0.0025 / 2) ** 0.5)
    # a program or a reference that keeps no smoothness parts: not a number
    bare = compare.train_numbers(readings([2.0], g, d), ref)
    assert bare["level_smooth_rms_gap_step1"] != bare["level_smooth_rms_gap_step1"]
    assert not compare.judge(bare, {"level_smooth_rms_gap_step1": 1.0})[0]


def test_grad_share_gap_reads_the_part_that_flows_through_a_cut():
    import numpy as np

    from benchmark.reference import _common as rc

    # one leaf the part reaches, one it does not: g = rest + through, with
    # `through` at a right angle to `rest` but for its first element
    rest = {"a": np.array([3.0, 0.0, 0.0, 4.0]), "b": np.array([1.0, 1.0])}
    through = {"a": np.array([0.3, 0.5, -0.5, 0.0]), "b": np.zeros(2)}
    g = {k: rest[k] + through[k] for k in rest}
    cut = rc.flow_through(g, rest)
    assert list(cut["d"]) == ["a"]
    assert np.vdot(cut["d"]["a"], rest["a"]) == pytest.approx(0.0, abs=1e-12)
    assert cut["ref_dot"]["a"] == pytest.approx(cut["dd"]["a"])
    assert cut["w"]["a"] == pytest.approx(1 / np.vdot(g["a"], g["a"]))
    base = readings([2.0], {"a": 1.0}, {"a": 1.0})
    ref = dict(base, grad_cuts={"corr": cut})
    gap = lambda first: compare.train_numbers(  # noqa: E731
        dict(base, first_grads=first), ref)["grad_share_gap_corr"]
    assert gap(g) == pytest.approx(0.0, abs=1e-12)
    assert gap(rest) == pytest.approx(1.0)                 # nothing let through
    assert gap({k: 1.01 * v for k, v in g.items()}) == pytest.approx(0.01)
    half = {"a": rest["a"] + 0.5 * through["a"], "b": g["b"]}
    assert gap(half) == pytest.approx(0.5)
    # two leaves reached, one with a gradient a hundred times the other's:
    # the same miss counts a ten-thousandth there
    rest2 = {"a": rest["a"], "c": 100 * rest["a"]}
    g2 = {"a": g["a"], "c": rest2["c"] + through["a"]}
    cut2 = rc.flow_through(g2, rest2)
    assert sorted(cut2["d"]) == ["a", "c"]
    ref2 = dict(base, grad_cuts={"corr": cut2})
    gap2 = lambda first: compare.train_numbers(  # noqa: E731
        dict(base, first_grads=first), ref2)["grad_share_gap_corr"]
    assert gap2({"a": rest2["a"], "c": g2["c"]}) > 0.99        # the small leaf lacks it
    assert gap2({"a": g2["a"], "c": rest2["c"]}) < 0.01        # the big leaf lacks it
    assert gap2(rest2) == pytest.approx(1.0)
    # a program that kept no gradient, or a leaf short: not a number
    assert gap(None) != gap(None) and gap({"b": g["b"]}) != gap({"b": g["b"]})
    assert "grad_share_gap_corr" not in compare.train_numbers(base, base)


def test_judge_fails_missing_nan_and_over():
    ok, c = compare.judge({"x": 0.1, "y": float("nan")}, {"x": 0.2, "y": 1.0, "z": 1.0})
    assert not ok and c["x"]["value"] == 0.1 and c["z"]["value"] is None
    assert compare.judge({"x": 0.1}, {"x": 0.2})[0]
    assert not compare.judge({"x": 0.3}, {"x": 0.2})[0]
    assert compare.judge({"x": 0.0}, {"x": 0.0})[0]
