"""The train runner at a toy size on the CPU: it counts steps right, the
program in float32 agrees with the plain reference, and each fault the cell
can have, planted under the tap, turns `correct` false."""

import pytest

from toy import toy_train_context

from benchmark.harness.flow_faults import FAULTS, ONLY_IN
from benchmark.runners import train as runner

TOY_LIMITS = {"loss_gap": 2e-4, "level_smooth_rms_gap_step1": 1e-4,
              "grad_norm_gap": 5e-3, "dparam_norm_gap": 5e-2}
#: the cost volume's cell also holds the share of the first gradient that
#: flows back through the correlation (the cell's `grad_cuts`)
TOY_SHARE_LIMIT = {"grad_share_gap_corr": 0.1}
CELLS = ["inception_v3_chairs.train", "flownet_c_chairs.train"]


def run_cell(cell, fault=None, **kw):
    ctx = toy_train_context(cell, **kw)
    ctx.cell["limits"] = dict(TOY_LIMITS)
    if ctx.cell.get("grad_cuts"):
        ctx.cell["limits"].update(TOY_SHARE_LIMIT)
    return runner.run(ctx, step_fault=fault)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_counts_steps(cell):
    out = run_cell(cell)
    assert out["correct"], out["compared"]
    assert out["attempted"] == out["extra"]["steps"] > 0
    rate = out["end_to_end"]["train_pairs_per_s"]
    assert rate == pytest.approx(out["extra"]["steps"] * 4 / out["extra"]["window_s"])
    assert out["end_to_end"]["setup_s"] > 0
    assert out["extra"]["window_s"] >= 0.5


FAULT_CASES = [(cell, fault) for cell in CELLS for fault in sorted(FAULTS)
               if cell.split(".")[0] in ONLY_IN.get(fault, (cell.split(".")[0],))]


@pytest.mark.parametrize("cell, fault", FAULT_CASES)
def test_planted_fault_is_not_correct(cell, fault):
    out = run_cell(cell, fault=FAULTS[fault])
    assert not out["correct"], out["compared"]
    over = [k for k, c in out["compared"].items() if not c["value"] <= c["limit"]]
    assert over, out["compared"]
    if fault.startswith("corr_bwd"):
        # a fault in the backward alone: the first step's loss and levels
        # are the sound run's, and no leaf's norm moves by a hundredth; the
        # share of the gradient that flows through the correlation sees it
        assert "grad_share_gap_corr" in over, out["compared"]
        assert not {"grad_norm_gap", "level_smooth_rms_gap_step1"} & set(over)
        assert out["compared"]["grad_share_gap_corr"]["value"] > \
            2 * TOY_SHARE_LIMIT["grad_share_gap_corr"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_put_in_the_programs_place_is_not_correct(cell):
    """The reference in the nearest precision below the toy program's
    float32, bfloat16 operands, read against the reference itself."""
    import importlib

    import numpy as np

    from benchmark.harness import compare, traffic as gen
    from benchmark.reference import _common as rc

    ctx = toy_train_context(cell)
    ref = importlib.import_module("benchmark.reference." + ctx.config["reference"])
    h, w = ctx.config["image_size"]
    src, tgt = (np.asarray(x) for x in gen.textured_frames(
        gen.jax_key(ctx.seed, 1), 12, h, w))
    batches = [(src[i:i + 4], tgt[i:i + 4]) for i in (0, 4, 8)]
    sound = runner.run_reference(ctx, ref, batches)
    control = runner.run_reference(ctx, ref, batches, q=rc.bf16_quantiser)
    same, _ = compare.judge(compare.train_numbers(sound, sound), TOY_LIMITS)
    low, compared = compare.judge(compare.train_numbers(control, sound), TOY_LIMITS)
    assert same and not low, compared
