"""Benchmark harness — prints ONE JSON line with the headline metric.

Headline (BASELINE.json): FlyingChairs image-pairs/sec/chip on the full
training step (forward + unsupervised pyramid loss + backward + Adam) of
the flagship Inception-v3 flow model at the reference's 320x448 input
(`deepOF.py:22`), bfloat16 compute.

`python bench.py` measures in THIS process on the attached TPU and names
the device it ran on (`platform`, `device_kind`, `n_chips`) next to the
number. A backend that is not `tpu` is an error: the script exits
non-zero and prints no value — a timing from the CPU backend or the
Pallas interpreter is not a speed. One process per chip: nothing here
starts a child that needs the device.

`python bench.py --data` times the host input pipeline alone (cpu only).
"""

from __future__ import annotations

import json
import os
import sys
import time


METRIC = "flyingchairs_train_pairs_per_sec_per_chip"
UNIT = "image-pairs/sec/chip"

# --data mode: host input-pipeline throughput in isolation (no TPU).
DATA_METRIC = "host_pipeline_batches_per_sec"
DATA_UNIT = "batches/s"


# Third-party imports are deferred: `--data` pins JAX_PLATFORMS=cpu, and
# jax reads that variable when it is imported.
jax = jnp = np = None


def _import_compute() -> None:
    global jax, jnp, np
    if jax is None:
        import jax as _jax
        import jax.numpy as _jnp
        import numpy as _np
        jax, jnp, np = _jax, _jnp, _np


def _require_tpu() -> list:
    """The attached TPU devices, with the persistent compile cache on;
    SystemExit (non-zero, nothing on stdout) on any other backend."""
    _import_compute()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"bench: needs a TPU, jax found platform={devs[0].platform!r} "
            f"({devs[0].device_kind}); no value is reported off the chip")
    from deepof_tpu.train.warmup import enable_compile_cache

    enable_compile_cache()
    return devs


def calibrate(n: int = 4096, reps: int = 10) -> dict:
    """Raw bf16 matmul rate measured beside the headline number: the
    denominator of `mfu_vs_matmul`."""
    _import_compute()
    a = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def mm(x):
        return (x @ x).sum()

    out = mm(a)  # compile mm AND the chaining ops used in the timed loop
    out = mm(out * 0 + a)
    float(jax.device_get(out))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = mm(out * 0 + a)  # chain to prevent overlap-free reordering
    float(jax.device_get(out))
    dt = max(time.perf_counter() - t0, 1e-9) / reps
    return {"matmul_tflops": round(2 * n**3 / dt / 1e12, 1)}


def headline_setup(model_name: str = "inception_v3", batch: int = 16,
                   image_size=(320, 448), warp_impl: str | None = None,
                   time_step: int = 2,
                   weights: tuple = (16, 8, 4, 2, 1, 1)):
    """The headline workload, shared with tools/perf_probe.py so the
    decomposition there always measures the same config as the headline.

    warp_impl overrides `LossConfig.warp_impl` (None = the config
    default). time_step > 2
    builds the multi-frame T-volume variant (2(T-1) flow channels, 3T
    input channels — the probe's Sintel-shaped section) on the same
    pipeline, so multiframe timings share every other headline setting.

    Returns (cfg, mesh, ds, model, state, step, sharded_batch)."""
    _import_compute()
    from deepof_tpu.core.config import (
        DataConfig, ExperimentConfig, LossConfig, OptimConfig, TrainConfig)
    from deepof_tpu.data.datasets import SyntheticData
    from deepof_tpu.models.registry import build_model
    from deepof_tpu.parallel.mesh import (
        batch_sharding, build_mesh, replicated_sharding)
    from deepof_tpu.train.state import create_train_state, make_optimizer
    from deepof_tpu.train.step import make_train_step

    h, w = image_size
    loss_kw = {"warp_impl": warp_impl} if warp_impl else {}
    cfg = ExperimentConfig(
        name="bench",
        model=model_name,
        loss=LossConfig(weights=tuple(weights), **loss_kw),
        optim=OptimConfig(learning_rate=1.6e-5),
        data=DataConfig(dataset="synthetic", image_size=(h, w), gt_size=(h, w),
                        batch_size=batch, time_step=time_step),
        train=TrainConfig(seed=0, compute_dtype="bfloat16"),
    )
    mesh = build_mesh(cfg.mesh)
    model = build_model(cfg.model, flow_channels=2 * (time_step - 1),
                        dtype=jnp.bfloat16,
                        corr_max_disp=cfg.corr_max_disp,
                        corr_stride=cfg.corr_stride)
    tx = make_optimizer(cfg.optim, lambda s: cfg.optim.learning_rate)
    # on the mesh from the start: a state whose type lacks the mesh would
    # make the second call retrace and recompile (see Trainer.__init__)
    state = jax.device_put(
        create_train_state(model, jnp.zeros((batch, h, w, 3 * time_step)),
                           tx, seed=0),
        replicated_sharding(mesh))
    ds = SyntheticData(cfg.data)
    step = make_train_step(model, cfg, ds.mean, mesh)
    b = jax.device_put(ds.sample_train(batch, iteration=0),
                       batch_sharding(mesh))
    return cfg, mesh, ds, model, state, step, b


def time_train_step(step, state, b, steps: int = 10, windows: int = 3,
                    warmup: int = 1, metrics_key: str = "total"):
    """Honest best-of-windows timing of a (state, batch) train step.

    Ends every window by FETCHING the loss value — it transitively
    depends on every dispatched step, so it cannot materialize early
    (unlike `block_until_ready`; DESIGN.md "Benchmark honesty"). The
    donated state threads the dependency chain across calls. Returns
    (seconds per CALL, final state, fetched metrics value). The single
    timing idiom shared by bench() and tools/perf_probe.py."""
    _import_compute()
    for _ in range(max(warmup, 1)):  # >=1: m must exist for the fetch
        state, m = step(state, b)
    val = jax.device_get(m[metrics_key])
    # fail fast BEFORE spending the timing windows: a NaN step should
    # cost warmup steps, not the whole measurement
    assert np.isfinite(val).all(), f"non-finite {metrics_key} after warmup: {val}"
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, b)
        val = jax.device_get(m[metrics_key])
        best = min(best, time.perf_counter() - t0)
    return best / steps, state, val


def step_flops(step, state, b) -> float | None:
    """XLA's own FLOPs estimate for one train step, from the LOWERED
    module — no second backend compile; None if the backend does not
    report it. Implementation shared with the train loop's per-record
    telemetry (deepof_tpu/obs/telemetry.py)."""
    from deepof_tpu.obs.telemetry import step_flops as _step_flops

    return _step_flops(step, state, b)


def bench(model_name: str = "inception_v3", batch: int = 16,
          image_size=(320, 448), steps: int = 20, warmup: int = 3,
          windows: int = 4, warp_impl: str | None = None) -> dict:
    """Time the headline train step on the attached TPU (SystemExit on
    any other backend). warp_impl None = the config default."""
    devs = _require_tpu()
    n_chips = len(devs)
    # cache accounting around everything that can compile (setup + the
    # timed fn's first call): a warmed run shows misses == 0
    from deepof_tpu.train.warmup import cache_delta

    cache_watch = cache_delta()
    cfg, mesh, ds, model, state, step, b = headline_setup(
        model_name, batch, image_size, warp_impl=warp_impl)

    per_step, state, total = time_train_step(
        step, state, b, steps=steps, windows=windows, warmup=warmup)
    cache_d = cache_watch.stats()
    pairs_per_sec = batch / per_step
    per_chip = pairs_per_sec / n_chips
    assert np.isfinite(total).all(), total
    res = {"pairs_per_sec_per_chip": per_chip, "pairs_per_sec": pairs_per_sec,
           "platform": devs[0].platform, "device_kind": devs[0].device_kind,
           "n_chips": n_chips, "batch": batch, "steps_per_sec": 1.0 / per_step,
           "warp_impl": cfg.loss.warp_impl, **calibrate(),
           # requests disambiguates: misses == 0 with requests == 0 means
           # the counters never saw a compile, NOT that the run was warm
           "compile_cache_requests": cache_d["requests"],
           "compile_cache_hits": cache_d["hits"],
           "compile_cache_misses": cache_d["misses"]}
    # Decoded-image cache counters (alongside the compile-cache ones):
    # zeros for the synthetic headline workload, live for CLI benches of
    # disk datasets — the host-decode half of the observability story.
    dcache = getattr(ds, "cache_stats", None)
    if dcache is not None:
        dstats = dcache()
        res["decode_cache_hits"] = int(dstats["hits"])
        res["decode_cache_misses"] = int(dstats["misses"])
        res["decode_cache_evictions"] = int(dstats["evictions"])
    # Device-memory telemetry (obs/telemetry.py): the same
    # bytes-in-use/peak fields the train loop logs per record, so a
    # bench line also answers "how close to HBM is this config".
    from deepof_tpu.obs.telemetry import (
        device_memory_summary, peak_bf16_tflops)

    res.update({k: v for k, v in device_memory_summary().items()
                if v is not None})
    # MFU: XLA-counted FLOPs/step x measured steps/sec, vs both the
    # device's published peak and the matmul rate measured beside it.
    flops = step_flops(step, state, b)
    if flops:
        # LOWERED cost_analysis reports GLOBAL (pre-partition) FLOPs —
        # verified: an 8-way-sharded einsum reports the full count from
        # .lower().cost_analysis() and 1/8 of it from
        # .compile().cost_analysis(). Per-chip rate therefore divides by
        # n_chips.
        model_tflops = flops * res["steps_per_sec"] / n_chips / 1e12
        res.update(
            flops_per_step=flops,
            model_tflops=round(model_tflops, 2),
            mfu_vs_matmul=round(model_tflops / max(res["matmul_tflops"], 1e-9),
                                4),
        )
        peak = peak_bf16_tflops(res["device_kind"])
        if peak:  # a kind outside the table gets no MFU, not a guess
            res["mfu_nominal"] = round(model_tflops / peak, 4)
    return res


def data_bench(num_workers: int = 0, batch: int = 8, image_size=(64, 64),
               batches: int = 32, dataset: str = "synthetic",
               data_path: str = "", seed: int = 0,
               recipe_path: str = "") -> dict:
    """Host input-pipeline throughput in ISOLATION (batches/s, MB/s):
    dataset decode/assembly through `data/pipeline.py`'s worker pool,
    no model, no train step — so host vs. device bottlenecks are
    attributable without a TPU. Forces the cpu backend (JAX_PLATFORMS)
    before any compute import: a data measurement must never take, or
    wait on, the chip.

    Returns one flat JSON-ready dict: the throughput numbers plus the
    pipeline's observability counters (assemble time, queue depth,
    waits, worker utilization) and the decoded-image cache's
    hit/miss/eviction counters — the schema the tier-1 smoke test pins.

    The cpu pin is unconditional (an inherited JAX_PLATFORMS=tpu must
    not defeat it) but scoped: the prior value is restored on return.
    In-process caveat: if jax was already
    imported with another platform before this call, the env var is too
    late — the `bench.py --data` CLI path imports compute only after
    this line.
    """
    prev_platforms = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        return _data_bench(num_workers, batch, image_size, batches,
                           dataset, data_path, seed, recipe_path)
    finally:
        if prev_platforms is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = prev_platforms


def _data_bench(num_workers, batch, image_size, batches, dataset,
                data_path, seed, recipe_path="") -> dict:
    import numpy as np  # noqa: F811 - the compute-import convention here

    from deepof_tpu.core.config import DataConfig
    from deepof_tpu.data.datasets import build_dataset
    from deepof_tpu.data.pipeline import InputPipeline, derive_batch_rng

    h, w = image_size
    if recipe_path:
        # mixed-stream proxy: the recipe's FIRST stage weighted mixture
        # assembled through the same pipeline — measures the mixture
        # layer's sampling/normalization overhead vs. a single dataset
        from deepof_tpu.core.config import recipe_from_dict
        from deepof_tpu.data.mixture import build_mixture

        with open(recipe_path) as f:
            recipe = recipe_from_dict(json.load(f))
        if not recipe.stages:
            raise SystemExit(f"--recipe {recipe_path!r}: no stages")
        stage = recipe.stages[0]
        sh, sw = stage.image_size or (h, w)
        h, w = sh, sw
        cfg = DataConfig(dataset=dataset, data_path=data_path,
                         image_size=(sh, sw),
                         gt_size=stage.gt_size or (sh, sw),
                         crop_size=stage.crop_size, batch_size=batch,
                         time_step=stage.time_step or 2,
                         num_workers=num_workers)
        ds = build_mixture(cfg, stage)
        dataset = "+".join(m.dataset for m in stage.mixture)
    else:
        cfg = DataConfig(dataset=dataset, data_path=data_path,
                         image_size=(h, w), gt_size=(h, w),
                         batch_size=batch, num_workers=num_workers)
        ds = build_dataset(cfg)

    def assemble(i: int) -> dict:
        return ds.sample_train(batch, rng=derive_batch_rng(seed, i))

    pipe = InputPipeline(assemble, num_workers=num_workers,
                         reorder_depth=cfg.reorder_depth)
    try:
        first = pipe.get()  # warm: worker spin-up, first-touch caches
        bytes_per_batch = sum(
            v.nbytes for v in first.values() if hasattr(v, "nbytes"))
        t0 = time.perf_counter()
        n_bytes = 0
        for _ in range(batches):
            b = pipe.get()
            n_bytes += sum(v.nbytes for v in b.values()
                           if hasattr(v, "nbytes"))
        dt = max(time.perf_counter() - t0, 1e-9)
        stats = pipe.stats()
    finally:
        pipe.close()
    cache = (ds.cache_stats() if hasattr(ds, "cache_stats")
             else {"hits": 0, "misses": 0, "evictions": 0})
    bps = batches / dt
    res = {
        "metric": DATA_METRIC,
        "value": round(bps, 2),
        "unit": DATA_UNIT,
        "mb_per_sec": round(n_bytes / dt / 2**20, 2),
        "bytes_per_batch": int(bytes_per_batch),
        "batches": batches,
        "batch": batch,
        "image_size": [int(h), int(w)],
        "dataset": dataset,
        "num_workers": stats["num_workers"],
        "assemble_s_mean": stats["assemble_s_mean"],
        "queue_depth": stats["queue_depth"],
        "max_queue_depth": stats["max_queue_depth"],
        "waits": stats["waits"],
        "wait_s": stats["wait_s"],
        "worker_util": stats["worker_util"],
        "decode_cache_hits": int(cache["hits"]),
        "decode_cache_misses": int(cache["misses"]),
        "decode_cache_evictions": int(cache["evictions"]),
    }
    if recipe_path and hasattr(ds, "mixture_stats"):
        # which member each timed batch actually drew — the weighted
        # split is part of the measurement's identity
        res["draws_by_dataset"] = dict(
            ds.mixture_stats()["recipe_draws_by_dataset"])
    assert np.isfinite(bps)
    return res


def parse_image_size(spec: str) -> tuple[int, int]:
    """'HxW' -> (H, W); the one parser shared by `bench.py --data` and
    the package CLI's `bench --data-only` so the two advertised forms of
    the measurement can't drift."""
    try:
        h, w = (int(x) for x in spec.lower().split("x"))
    except ValueError:
        raise SystemExit(f"bad --image-size {spec!r}: use HxW")
    return h, w


def data_main(argv: list[str]) -> int:
    """`bench.py --data [--workers N] [--batch B] [--batches N]
    [--image-size HxW] [--dataset NAME] [--data-path P]`: print the
    data-only measurement as one JSON line."""
    import argparse

    p = argparse.ArgumentParser(prog="bench.py --data")
    p.add_argument("--workers", type=int, default=0)
    # batch default matches the headline config AND the package CLI's
    # `deepof_tpu bench --data-only`, so the two advertised forms of
    # this measurement are comparable out of the box
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--batches", type=int, default=32)
    p.add_argument("--image-size", default="64x64",
                   metavar="HxW")
    p.add_argument("--dataset", default="synthetic")
    p.add_argument("--data-path", default="")
    p.add_argument("--recipe", default="", metavar="FILE",
                   help="time the recipe's first-stage weighted mixture "
                        "stream (data/mixture.py) instead of --dataset")
    args = p.parse_args([a for a in argv if a != "--data"])
    h, w = parse_image_size(args.image_size)
    res = data_bench(num_workers=args.workers, batch=args.batch,
                     image_size=(h, w), batches=args.batches,
                     dataset=args.dataset, data_path=args.data_path,
                     recipe_path=args.recipe)
    print(json.dumps(res), flush=True)
    return 0


_EXTRA_KEYS = ("platform", "device_kind", "n_chips", "matmul_tflops",
               "batch", "warp_impl", "model_tflops",
               "mfu_nominal", "mfu_vs_matmul", "compile_cache_requests",
               "compile_cache_hits", "compile_cache_misses",
               "decode_cache_hits", "decode_cache_misses",
               "decode_cache_evictions", "dev_mem_bytes_in_use",
               "dev_mem_peak_bytes")


def main() -> int:
    """Measure in this process and print the one JSON line. Any failure
    (no TPU, a compile error, a non-finite loss) propagates: non-zero
    exit, no value."""
    res = bench()
    line = {"metric": METRIC, "value": round(res["pairs_per_sec_per_chip"], 2),
            "unit": UNIT}
    line.update({k: res[k] for k in _EXTRA_KEYS if k in res})
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    if "--data" in sys.argv:
        sys.exit(data_main(sys.argv[1:]))
    sys.exit(main())
