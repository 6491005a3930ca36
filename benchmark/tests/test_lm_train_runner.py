"""The language-model train runner at a toy size on the CPU: it counts
steps and tokens right, the program in float32 agrees with the plain
reference, each planted fault turns `correct` false, a lower-precision
control in the program's place does too, and two equal rows fail
`rows_distinct`."""

import numpy as np
import pytest

from toy_lm import toy_lm_context

from benchmark.harness.lm_faults import FAULTS
from benchmark.runners import lm_train as runner

CELL = "kanana2_30b_a3b_ep8.train_4k"
# the toy program in float32 reads 4e-8, 4e-8, 7e-7, 2e-7 against the
# reference; the reference with bfloat16 operands 3e-6, 4e-6, 1.5e-3, 1e-3
TOY_LIMITS = {"loss_gap": 5e-7, "row_loss_rms_gap": 5e-7,
              "routed_grad_norm_gap": 5e-5, "dparam_norm_gap": 5e-2}


def run_cell(fault=None, **kw):
    ctx = toy_lm_context(CELL, **kw)
    ctx.cell["limits"] = dict(TOY_LIMITS)
    return runner.run(ctx, step_fault=fault, agree=True)


def test_sound_run_is_correct_and_counts_steps():
    out = run_cell()
    assert out["correct"], out["compared"]
    steps, window = out["extra"]["steps"], out["extra"]["window_s"]
    assert out["attempted"] == steps > 0 and window >= 0.5
    assert out["end_to_end"]["train_pairs_per_s"] == pytest.approx(steps * 2 / window)
    assert out["extra"]["tokens_per_s"] == pytest.approx(steps * 2 * 32 / window)
    assert out["end_to_end"]["setup_s"] > 0
    assert out["extra"]["router_choices_agree"] == 1.0
    # the window's mean of every `moe_*` counter of the program's records,
    # one value an expert layer, none of them named by the runner
    from deepof_tpu.models.lm.model import COUNTERS
    assert "moe_full_width" in COUNTERS
    for name in COUNTERS:
        assert len(out["extra"][name]) == 2, name  # two expert layers
    assert all(0.0 <= x <= 1.0 for x in out["extra"]["moe_slots_held_share"])


def test_without_the_reference_a_run_is_never_correct():
    ctx = toy_lm_context(CELL)
    ctx.cell["limits"] = dict(TOY_LIMITS)
    out = runner.run(ctx, reference=False)
    assert not out["correct"] and out["attempted"] > 0
    assert out["compared"]["loss_gap"]["value"] is None
    assert out["compared"]["window_closed"]["value"] == 0.0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault):
    out = run_cell(fault=FAULTS[fault])
    assert not out["correct"], out["compared"]
    over = [k for k, c in out["compared"].items() if not c["value"] <= c["limit"]]
    assert over and "window_closed" not in over, out["compared"]


def test_equal_rows_fail_rows_distinct():
    def equal_rows(tap, trainer):
        rows = trainer.dataset.first_rows.copy()  # what the checked steps take
        rows[1::2] = rows[0::2]
        trainer.dataset.first_rows = rows

    out = run_cell(fault=equal_rows)
    assert not out["correct"]
    assert out["compared"]["rows_distinct"]["value"] == 1.0


def test_window_data_is_one_on_every_seed_and_the_checked_rows_are_the_seeds():
    """`window_data` in the traffic's file: the first batches are the
    seed's rows, whole and in turn; every later one comes from the one pool
    by the rng's draw, which is drawn for the first batches too."""
    pool = np.arange(8 * 3).reshape(8, 3)
    seen = {}
    for seed in (1, 2):
        ds = runner.PoolTokens(pool, 100, first_rows=np.full((6, 3), -seed))
        rng = np.random.RandomState(7)  # the traffic fixes `train.seed`
        seen[seed] = [ds.sample_train(2, rng=rng)["tokens"] for _ in range(6)]
        assert all((b == -seed).all() for b in seen[seed][:3])
    for a, b in zip(seen[1][3:], seen[2][3:]):
        np.testing.assert_array_equal(a, b)
    # the pool alone, the same stream: its fourth batch on is the same
    plain = runner.PoolTokens(pool, 100)
    rng = np.random.RandomState(7)
    alone = [plain.sample_train(2, rng=rng)["tokens"] for _ in range(6)]
    for got, want in zip(alone[3:], seen[1][3:]):
        np.testing.assert_array_equal(got, want)


def test_the_cells_traffic_fixes_the_windows_data():
    ctx = toy_lm_context(CELL, seed=11)
    assert ctx.traffic["window_data"]["pool_seed"] > 0
    assert "train.seed" in ctx.traffic["set"]  # the order of the batches
    cfg_a = runner.program_config(ctx, "unused")
    cfg_b = runner.program_config(toy_lm_context(CELL, seed=12), "unused")
    assert cfg_a.train.seed == cfg_b.train.seed == ctx.traffic["set"]["train.seed"]


def test_control_put_in_the_programs_place_is_not_correct():
    """The reference with bfloat16 forward operands, the nearest precision
    below the toy program's float32, read against the reference itself."""
    import importlib

    from benchmark.harness import compare, lm_compare, traffic as gen
    from benchmark.reference import _common as rc

    ctx = toy_lm_context(CELL)
    ref = importlib.import_module("benchmark.reference." + ctx.config["reference"])
    pool = np.asarray(runner.token_pool(gen.jax_key(ctx.seed, 1), 6, 32, 256, 1.1))
    batches = [pool[i:i + 2] for i in (0, 2, 4)]
    sound = runner.run_reference(ctx, ref, batches)
    control = runner.run_reference(ctx, ref, batches, q=rc.bf16_quantiser)
    same, _ = compare.judge(lm_compare.train_numbers(sound, sound), TOY_LIMITS)
    low, compared = compare.judge(lm_compare.train_numbers(control, sound), TOY_LIMITS)
    assert same and not low, compared


def test_what_is_compiled_after_the_window_is_kept_in_no_cache():
    import jax

    key = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, key)
    with pytest.raises(RuntimeError), runner.compiles_not_kept():
        assert getattr(jax.config, key) == 1e9  # nothing compiles that long
        raise RuntimeError("the reference died")
    assert getattr(jax.config, key) == before
