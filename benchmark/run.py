"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is found by name in BENCHMARK.json; its configuration, traffic mix,
runner and per-layer metrics are files found by the names written there:

    benchmark/configs/<config>.json      sizes, constants, the program's settings
    benchmark/traffic/<traffic>.json     parameters of the traffic, and the runner's name
    benchmark/workloads/<cell>.json      the cell's limits for `correct`, sizes reckoned and found
    benchmark/runners/<runner>.py        drives the program through set-up, window and check
    benchmark/layer_metrics/<metric>.py  one reader per per-layer metric
    benchmark/reference/<config>.py      the plain reference

Nothing about a particular cell, model or metric is written in this file.
It exits non-zero and prints no result where JAX finds no TPU, fewer chips
than the cell asks for, or a device kind that `harness/peaks.py` lacks.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_reader(name: str):
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build_context(workload: str, seed: int, seconds: float, trace: bool,
                  bench: dict | None = None):
    """Everything a runner needs, read from the data files by name."""
    if bench is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"benchmark: no workload {workload!r} in BENCHMARK.json")
    config = load_json("configs", entry["config"] + ".json")
    traffic = load_json("traffic", entry["traffic"] + ".json")
    cell = load_json("workloads", workload + ".json")
    return types.SimpleNamespace(
        bench=bench, entry=entry, name=workload, chips=int(entry["chips"]),
        config=config, config_name=entry["config"], traffic=traffic, cell=cell,
        seed=int(seed), seconds=float(seconds), trace=bool(trace),
        t_process_start=T_PROCESS_START, root=ROOT, require_tpu=True)


def prepare_environment() -> None:
    # one fixed place for the compile cache inside the checkout, unless the
    # machine names one; the program takes what this variable says
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".bench_cache", "xla"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)


def check_device(ctx) -> dict:
    import jax

    devs = jax.devices()
    if ctx.require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"benchmark: no TPU (JAX found {devs[0].platform!r})")
    if len(devs) < ctx.chips:
        raise SystemExit(f"benchmark: cell needs {ctx.chips} chips, JAX found {len(devs)}")
    from benchmark.harness.peaks import peaks_for

    ctx.devices = devs[:ctx.chips]
    ctx.peaks = peaks_for(devs[0].device_kind) if ctx.require_tpu else {
        "flops_per_s": float("nan"), "bytes_per_s": float("nan")}
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": ctx.chips}


def collect_metrics(ctx, out: dict) -> dict:
    """`--trace 0`: the cell's end-to-end metrics as the runner measured
    them. `--trace 1`: its per-layer metrics, each from its own reader; a
    reader that finds nothing returns None and the metric is left out."""
    metrics = {}
    if not ctx.trace:
        for m in ctx.bench["end_to_end"]:
            if applies(m, ctx.name) and m["name"] in out["end_to_end"]:
                metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
        return metrics
    for m in ctx.bench["per_layer"]:
        if not applies(m, ctx.name):
            continue
        value = load_reader(m["name"]).read(out["observed"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    prepare_environment()
    ctx = build_context(args.workload, args.seed, args.seconds, bool(args.trace))
    device = check_device(ctx)
    runner = importlib.import_module("benchmark.runners." + ctx.traffic["runner"])
    out = runner.run(ctx)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    if ctx.trace:
        device["busy_s"] = out["observed"]["device"]["busy_s"]
        device["window_s"] = out["observed"]["device"]["window_s"]
    from benchmark.harness.result import emit

    emit(out["correct"], out["attempted"], out["failed"],
         collect_metrics(ctx, out), device, out["compared"],
         breakdown=out.get("breakdown"), extra=out.get("extra"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
