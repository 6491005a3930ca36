"""Runner `lm_train_bd` on the CPU at a toy size (the cell's own files
with the sizes cut: hidden 64, 4 query heads over 2 key/value heads of 16,
8 experts top-2 of which 2 are held, vocabulary 256, rows of 32): the
checked steps' noise keys reach the reference, a sound run is correct, the
mechanism's own fault and the float8 control are not."""

import copy
import os
import time

import numpy as np
import pytest
from toy import bench_run

from benchmark.harness import bd_faults, compare, lm_compare
from benchmark.reference import _common as rc
from benchmark.runners import lm_train_bd as runner

CELL = "sdar_30b_a3b_ep8.train_bd_4k"
TOY = dict(vocab_size=256, hidden_size=64, moe_intermediate_size=32,
           num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
           head_dim=16, n_routed_experts_published=8, first_expert=2,
           num_experts_per_tok=2, rope_theta=10000.0, mask_token_id=255)


def toy_context(seed: int = 5, seconds: float = 0.5, trace: bool = False,
                dtype: str = "float32"):
    bench_run.prepare_environment()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        bench_run.ROOT, ".bench_cache", "xla_cpu_tests")
    ctx = bench_run.build_context(CELL, seed, seconds, trace)
    ctx.config = copy.deepcopy(ctx.config)
    ctx.traffic = copy.deepcopy(ctx.traffic)
    ctx.cell = copy.deepcopy(ctx.cell)
    ctx.config.update(TOY, num_experts=2)
    s = ctx.config["program"]["set"]
    s.update({f"lm.{k}": v for k, v in TOY.items()})
    s.update({"lm.n_routed_experts": 2, "train.compute_dtype": dtype,
              "lm.attn_block_q": 16, "lm.loss_block": 16})
    ctx.traffic.update(seq_len=32, pool_rows=8, warm_steps=4, log_every=1,
                       trace_delay_s=0.0, trace_seconds=0.2, batch_per_chip=2)
    ctx.traffic["set"]["lm.seq_len"] = 32
    ctx.require_tpu = False
    ctx.t_process_start = time.perf_counter()
    bench_run.check_device(ctx)
    return ctx


def test_sound_run_is_correct_and_the_reference_drew_the_programs_masks():
    ctx = toy_context()
    out = runner.run(ctx, agree=True)
    assert out["correct"], out["compared"]
    keys = ctx.config["checked_noise_keys"]
    assert len(keys) == runner.N_CHECK_STEPS
    assert len({k.tobytes() for k in keys}) == len(keys)  # a mask a step
    extra = out["extra"]
    # the share of loss-bearing positions: the program's counter beside
    # the reference's own count of the masks it drew for the checked steps
    by_step = {r["step"]: r for r in out["records"]}
    for i, want in enumerate(extra["reference_masked_share"]):
        assert by_step[i + 1]["bd_masked_share"][0] == pytest.approx(want)
    assert 0.5 < extra["bd_masked_share"][0] < 0.9
    assert len(extra["moe_slots_held_share"]) == 2
    assert extra["router_choices_agree"] > 0.95
    assert out["end_to_end"]["train_pairs_per_s"] > 0


@pytest.mark.parametrize("fault", sorted(bd_faults.FAULTS))
def test_planted_fault_is_not_correct(fault):
    out = runner.run(toy_context(), step_fault=bd_faults.FAULTS[fault])
    assert not out["correct"], (fault, out["compared"])


def test_control_put_in_the_programs_place_is_not_correct():
    seen = {}

    def also(ctx, ref, batches, refr, prog):
        c = runner.run_reference(ctx, ref, batches, q=rc.fp8_quantiser)
        numbers = lm_compare.train_numbers(c, refr)
        numbers.pop("_where")
        seen["ok"], _ = compare.judge(numbers, ctx.cell["limits"])
        return {}

    out = runner.run(toy_context(), also=also)
    assert out["correct"] and seen["ok"] is False


def test_traced_run_reads_the_cells_new_metrics():
    ctx = toy_context(trace=True)
    out = runner.run(ctx)
    obs = out["observed"]
    assert all("bd_masked_share" in r for r in obs["records"])
    masked = bench_run.load_reader("bd_masked_pct.lm_train").read(obs)
    assert 50.0 < masked < 90.0
    # no device trace on the CPU: the scope and kernel readers find nothing
    # to read and say so, they do not raise
    for name in ("gqa_device_pct.lm_train", "bd_attn_fwd_roofline_pct.lm_train",
                 "bd_attn_bwd_roofline_pct.lm_train"):
        bench_run.load_reader(name).read(obs)
