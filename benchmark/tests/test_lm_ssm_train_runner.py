"""Runner `lm_train_bd` on the CPU at a toy size for the cell whose layers
hold a state-space mixer (the cell's own files with the sizes cut: hidden
64, layers `MEM*E`, 4 state-space heads of 8 with a state of 16 in 2
groups, chunks of 12, 4 query heads over 2 key/value heads of 16, 8
experts top-2 of which 2 are held, vocabulary 256, rows of 32): a sound
run is correct, and each of the state-space layer's planted faults is not."""

import copy
import os
import time

import pytest
from toy import bench_run

from benchmark.harness import ssm_faults
from benchmark.runners import lm_train_bd as runner

CELL = "nemotron_twotower_30b_a3b_ep16.train_bd_4k"
TOY = dict(vocab_size=256, hidden_size=64, moe_intermediate_size=32,
           moe_shared_expert_intermediate_size=48, num_hidden_layers=5,
           hybrid_override_pattern="MEM*E", num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, n_routed_experts=2,
           n_routed_experts_published=8, first_expert=2, num_experts_per_tok=2,
           mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
           chunk_size=12, mask_token_id=255)
# the cell's own limits are set for its size, where a noised block's state
# carries over 4096 positions; at rows of 32 the faults move the numbers
# far less. The toy program in float32 reads 0, 0, 0 and 0 against the
# reference; `noised_continues_noised` 1.9e-6, 1.6e-6, 3.2e-5 and 1.4e-4,
# `noised_from_zero` 7.1e-6, 5.9e-6, 9.9e-5 and 2.3e-4
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
    ctx.config.update(TOY)
    s = ctx.config["program"]["set"]
    s.update({f"lm.{k}": v for k, v in TOY.items()})
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


def test_sound_run_is_correct_and_reports_the_state_space_counter():
    out = runner.run(toy_context(), agree=True)
    assert out["correct"], out["compared"]
    extra = out["extra"]
    assert len(extra["moe_slots_held_share"]) == 2  # the two expert layers
    assert extra["router_choices_agree"] > 0.95


@pytest.mark.parametrize("fault", sorted(ssm_faults.FAULTS))
def test_planted_fault_is_not_correct(fault):
    out = runner.run(toy_context(), step_fault=ssm_faults.FAULTS[fault])
    assert not out["correct"], out["compared"]
