"""Toy-size context of a language-model train cell for the CPU tests: the
cell's own files with the sizes cut (hidden 64, 4 heads, 8 experts top-2
of which 2 are held, vocabulary 256, 32 positions). The program's `lm`
section and the reference read the same cut keys. Never used by a run."""

import copy
import os
import time

from toy import bench_run

TOY_KEYS = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=4,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=2, n_routed_experts_published=8, first_expert=2,
    num_experts_per_tok=2, rope_theta=10000.0)


def toy_lm_context(cell: str, seed: int = 5, seconds: float = 0.5,
                   trace: bool = False, dtype: str = "float32", **keys):
    bench_run.prepare_environment()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        bench_run.ROOT, ".bench_cache", "xla_cpu_tests")
    ctx = bench_run.build_context(cell, seed, seconds, trace)
    ctx.config = copy.deepcopy(ctx.config)
    ctx.traffic = copy.deepcopy(ctx.traffic)
    ctx.cell = copy.deepcopy(ctx.cell)
    cut = {**TOY_KEYS, **keys}
    ctx.config.update(cut)
    s = ctx.config["program"]["set"]
    s.update({f"lm.{k}": v for k, v in cut.items()})
    s.update({"train.compute_dtype": dtype, "lm.attn_block_q": 16,
              "lm.loss_block": 16})
    ctx.traffic.update(seq_len=32, pool_rows=8, warm_steps=4, log_every=1,
                       trace_delay_s=0.0, trace_seconds=0.2)
    ctx.traffic["set"]["lm.seq_len"] = 32
    ctx.require_tpu = False
    ctx.t_process_start = time.perf_counter()
    bench_run.check_device(ctx)
    return ctx
