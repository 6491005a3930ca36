"""The comparison that decides `correct`: each number beside its limit."""

from __future__ import annotations

import statistics

import numpy as np


def worst_leaf_gap(prog: dict, ref: dict, keep=None,
                   gaps_out: list | None = None) -> tuple[float, str]:
    """Largest |prog - ref| of per-leaf norms, each measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some leaves are all but zero)."""
    keys = [k for k in ref if keep is None or keep(k)]
    med = statistics.median(ref[k] for k in keys)
    worst, where = 0.0, ""
    for k in keys:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if gaps_out is not None:
            gaps_out.append(gap)
        if not gap <= worst:  # a NaN takes the place too
            worst, where = gap, f"{k} program={prog[k]:.6g} reference={ref[k]:.6g} median={med:.6g}"
    return worst, where


def train_numbers(prog: dict, ref: dict) -> dict:
    """prog/ref: {"losses": [..], "grad_norms": {leaf: norm},
    "dparam_norms": {leaf: norm}} of the first steps. Returns the numbers
    compared, by name."""
    loss_gap = max(abs(a - b) / max(abs(b), 1e-30)
                   for a, b in zip(prog["losses"], ref["losses"]))
    if len(prog["losses"]) != len(ref["losses"]):
        loss_gap = float("nan")
    g_all: list = []
    d_all: list = []
    grad_gap, grad_leaf = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"],
                                         gaps_out=g_all)
    # leaves whose reference gradient is nought to rounding move under Adam
    # by round-off alone: left out of the change by a rule on the gradient
    med = statistics.median(ref["grad_norms"].values())
    live = lambda k: ref["grad_norms"][k] >= 1e-3 * med  # noqa: E731
    dp_gap, dp_leaf = worst_leaf_gap(prog["dparam_norms"], ref["dparam_norms"],
                                     live, gaps_out=d_all)
    glob = lambda d: sum(v * v for v in d.values()) ** 0.5  # noqa: E731
    rel = lambda a, b: abs(a - b) / max(b, 1e-30)  # noqa: E731
    first = abs(prog["losses"][0] - ref["losses"][0]) / max(abs(ref["losses"][0]), 1e-30)
    # every level's own loss at every step: the widest of 18 gaps swings
    # less from seed to seed than the one gap of their weighted sum
    level_gaps = [rel(a, abs(b)) for pl, rl in zip(prog["level_losses"],
                                                   ref["level_losses"])
                  for a, b in zip(pl, rl) if abs(b) > 1e-6]
    level_gap = max(level_gaps, default=float("nan"))
    # their root mean square: no one level's chance cancellation hides a
    # lower precision, and no one level's tail sets the reading
    rms = lambda gaps: (sum(g * g for g in gaps) / len(gaps)) ** 0.5 \
        if gaps else float("nan")  # noqa: E731
    level_rms = rms(level_gaps)
    if [len(x) for x in prog["level_losses"]] != [len(x) for x in ref["level_losses"]]:
        level_gap = level_rms = float("nan")
    # the levels' smoothness parts alone, a function of the flow field's
    # neighbouring differences and of nothing else: where the photometric
    # mean averages a pixel's rounding away, this keeps it. The first
    # step's only: the later steps' follow Adam's first sign-like updates
    smooth_rms = rms([rel(a, abs(b)) for a, b in zip(
        prog.get("level_smooth_losses", [[]])[0],
        ref.get("level_smooth_losses", [[]])[0]) if abs(b) > 1e-6])
    share_gaps, share_leaves = grad_share_gaps(prog, ref)
    return {**share_gaps,
            "loss_gap": loss_gap, "loss_gap_step1": first,
            "level_smooth_rms_gap_step1": smooth_rms,
            "level_loss_gap": level_gap, "level_loss_rms_gap": level_rms,
            "grad_norm_gap": grad_gap, "dparam_norm_gap": dp_gap,
            # the norms over all leaves together: what the big leaves do
            "grad_global_gap": rel(glob(prog["grad_norms"]), glob(ref["grad_norms"])),
            "dparam_global_gap": rel(glob(prog["dparam_norms"]), glob(ref["dparam_norms"])),
            # steadier companions of the two worst-leaf numbers
            "grad_norm_gap_median": statistics.median(g_all),
            "dparam_norm_gap_median": statistics.median(d_all),
            "_where": {"grad_norm_gap": grad_leaf, "dparam_norm_gap": dp_leaf,
                       **share_leaves}}


def grad_share_gaps(prog: dict, ref: dict) -> tuple[dict, dict]:
    """For each part whose backward the reference cut at the first step
    (`grad_cuts`, by leaf: `reference/_common.py::flow_through`): d is the
    part of the reference's first gradient that flows back through it,
    taken at a right angle to the rest of the leaf's gradient, and the
    number |sum_k w_k <g_program - g_reference, d>_k| / sum_k w_k <d, d>_k
    over the leaves k that d reaches, each weighted by 1 / |g_reference|_k^2:
    0 where the program's gradient holds that part whole, 1 where it holds
    none of it, a half where it holds half. A norm's gap cannot see a part
    that is a few hundredths of a leaf's gradient; of the rounding of the
    rest of the gradient only what falls on the one direction d is read.
    Returns (numbers, for each a line with every leaf's own reading)."""
    out, where = {}, {}
    for name, cut in ref.get("grad_cuts", {}).items():
        key = "grad_share_gap_" + name
        have = prog.get("first_grads") or {}
        if not cut["d"] or any(k not in have for k in cut["d"]):
            out[key] = float("nan")
            continue
        off = {k: float(np.vdot(np.asarray(have[k], np.float64), d)) - cut["ref_dot"][k]
               for k, d in cut["d"].items()}
        out[key] = abs(sum(cut["w"][k] * off[k] for k in off)) / sum(
            cut["w"][k] * cut["dd"][k] for k in off)
        where[key] = " ".join(f"{k} off={off[k]:.6g} dd={cut['dd'][k]:.6g} "
                              f"w={cut['w'][k]:.6g}" for k in off)
    return out, where


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every limit names a number; a number over its limit, missing or not
    finite fails. Returns (correct, {name: {"value", "limit"}})."""
    out, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and value == value and value <= limit
        ok = ok and good
        out[name] = {"value": value, "limit": limit}
    return ok, out
