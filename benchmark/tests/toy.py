"""Toy-size contexts for the CPU tests: the cell's own files with the sizes
cut so that a test run can hold them. Never used by a benchmark run."""

import copy
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402


def toy_train_context(cell: str, seed: int = 5, seconds: float = 0.5,
                      trace: bool = False, size=(64, 96), batch: int = 4):
    bench_run.prepare_environment()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        bench_run.ROOT, ".bench_cache", "xla_cpu_tests")
    ctx = bench_run.build_context(cell, seed, seconds, trace)
    ctx.config = copy.deepcopy(ctx.config)
    ctx.traffic = copy.deepcopy(ctx.traffic)
    ctx.cell = copy.deepcopy(ctx.cell)
    ctx.config["image_size"] = list(size)
    s = ctx.config["program"]["set"]
    s["data.image_size"] = s["data.gt_size"] = f"({size[0]},{size[1]})"
    s["train.compute_dtype"] = "float32"
    ctx.traffic.update(batch_per_chip=batch, pool_pairs=2 * batch, warm_steps=4,
                       log_every=1, reference_block=batch // 2,
                       trace_delay_s=0.0, trace_seconds=0.2)
    ctx.require_tpu = False
    ctx.t_process_start = time.perf_counter()
    bench_run.check_device(ctx)
    return ctx
