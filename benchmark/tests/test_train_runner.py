"""The train runner at a toy size on the CPU: it counts steps right, the
program in float32 agrees with the plain reference, and each fault the cell
can have, planted under the tap, turns `correct` false."""

import pytest

from toy import toy_train_context

from benchmark.runners import train as runner

TOY_LIMITS = {"loss_gap": 2e-4, "grad_norm_gap": 5e-3, "dparam_norm_gap": 5e-2}
CELL = "inception_v3_chairs.train"
CONFIGS = ["inception_v3_chairs", "flownet_c_chairs"]


def run_cell(config=None, fault=None, **kw):
    ctx = toy_train_context(CELL, config=config, **kw)
    ctx.cell["limits"] = dict(TOY_LIMITS)
    return runner.run(ctx, step_fault=fault)


@pytest.mark.parametrize("config", CONFIGS)
def test_sound_run_is_correct_and_counts_steps(config):
    out = run_cell(config)
    assert out["correct"], out["compared"]
    assert out["attempted"] == out["extra"]["steps"] > 0
    rate = out["end_to_end"]["train_pairs_per_s"]
    assert rate == pytest.approx(out["extra"]["steps"] * 4 / out["extra"]["window_s"])
    assert out["end_to_end"]["setup_s"] > 0
    assert out["extra"]["window_s"] >= 0.5


def unchanged_state(tap):
    inner = tap.inner
    tap.inner = lambda state, batch: (state, inner(_copy(state), batch)[1])


def _copy(state):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(jnp.copy, state)


def half_batch(tap):
    import jax.numpy as jnp

    inner = tap.inner

    def step(state, batch):
        n = batch["source"].shape[0] // 2
        halved = {k: jnp.concatenate([v[:n], v[:n]]) for k, v in batch.items()}
        return inner(state, halved)

    tap.inner = step


@pytest.mark.parametrize("fault", [unchanged_state, half_batch])
def test_planted_fault_is_not_correct(fault):
    out = run_cell(fault=fault)
    assert not out["correct"], out["compared"]
    over = [k for k, c in out["compared"].items() if not c["value"] <= c["limit"]]
    assert over, out["compared"]


def test_control_put_in_the_programs_place_is_not_correct():
    """The reference in the nearest precision below the toy program's
    float32, bfloat16 operands, read against the reference itself."""
    import importlib

    import numpy as np

    from benchmark.harness import compare, traffic as gen
    from benchmark.reference import _common as rc

    ctx = toy_train_context(CELL)
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
