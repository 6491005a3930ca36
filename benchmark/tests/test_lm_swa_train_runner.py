"""Runner `lm_train` on the CPU at a toy size for the cell with
sliding-window layers (the cell's own files with the sizes cut: hidden 64,
4 query heads over 2 key/value heads of 16, layers: published 0 (dense,
window), 2 (expert, window) and 3 (expert, full), a window of 5, 8 experts
top-2 of which 2 are held, vocabulary 256, rows of 32): a sound run is
correct, and each of the window's planted faults is not."""

import copy
import os
import time

import pytest
from toy import bench_run

from benchmark.harness import swa_faults
from benchmark.runners import lm_train as runner

CELL = "trinity_mini_ep16.train_16k"
#: the reference's keys (the published names) and the program's (its
#: `lm` section's fields) for the same cut
SHARED = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
              moe_intermediate_size=32, num_hidden_layers=3,
              num_attention_heads=4, num_key_value_heads=2, head_dim=16,
              n_routed_experts_published=8, first_expert=2,
              num_experts_per_tok=2, sliding_window=5,
              published_layers=[0, 2, 3],
              layer_types=["sliding_attention"] * 3 + ["full_attention"])
REFERENCE = dict(SHARED, num_experts=2, num_dense_layers=1)
PROGRAM = dict(SHARED, n_routed_experts=2, first_k_dense_replace=1)
# the toy program in float32 reads 0 against the reference to a few
# ulps; a window off its rule moves every number by far more
TOY_LIMITS = {"loss_gap": 5e-7, "row_loss_rms_gap": 5e-7,
              "grad_norm_gap_median": 5e-6, "dparam_norm_gap_median": 2e-5}


def toy_context(seed: int = 5, seconds: float = 0.5, dtype: str = "float32"):
    bench_run.prepare_environment()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        bench_run.ROOT, ".bench_cache", "xla_cpu_tests")
    ctx = bench_run.build_context(CELL, seed, seconds, False)
    ctx.config = copy.deepcopy(ctx.config)
    ctx.traffic = copy.deepcopy(ctx.traffic)
    ctx.cell = copy.deepcopy(ctx.cell)
    ctx.config.update(REFERENCE)
    s = ctx.config["program"]["set"]
    s.update({f"lm.{k}": v for k, v in PROGRAM.items()})
    s.update({"train.compute_dtype": dtype, "lm.attn_block_q": 16,
              "lm.loss_block": 16})
    ctx.traffic.update(seq_len=32, pool_rows=8, warm_steps=4, log_every=1,
                       trace_delay_s=0.0, trace_seconds=0.2, batch_per_chip=2)
    ctx.traffic["set"]["lm.seq_len"] = 32
    ctx.cell["limits"] = dict(TOY_LIMITS)
    ctx.require_tpu = False
    ctx.t_process_start = time.perf_counter()
    bench_run.check_device(ctx)
    return ctx


def test_sound_run_is_correct():
    out = runner.run(toy_context(), agree=True)
    assert out["correct"], out["compared"]
    extra = out["extra"]
    assert len(extra["moe_slots_held_share"]) == 2  # the two expert layers
    assert extra["router_choices_agree"] > 0.95


@pytest.mark.parametrize("fault", sorted(swa_faults.FAULTS))
def test_planted_fault_is_not_correct(fault):
    out = runner.run(toy_context(), step_fault=swa_faults.FAULTS[fault])
    assert not out["correct"], out["compared"]
