"""Readings that the limits of `correct` are set from, on the chip, at the
cell's own size, many seeds in one process (run by a builder, never by the
benchmark's own runs):

    python benchmark/tools/calibrate.py <cell> --seeds 1,2,3 [--control] [--faults]

For each seed: the program's first steps through the runner against the
plain reference (the lower readings); with --control the reference in the
nearest precision below the configuration's, put in the program's place
(the upper readings); with --faults the reference with half of the batch
left out, put in the program's place. One JSON line per seed.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    import run as bench_run

    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench_run.prepare_environment()
    from benchmark.harness import compare
    from benchmark.reference import _common as rc

    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = bench_run.build_context(args.cell, seed, args.seconds, False)
        ctx.t_process_start = time.perf_counter()
        bench_run.check_device(ctx)
        runner = importlib.import_module("benchmark.runners." + ctx.traffic["runner"])
        quant = {"bfloat16": rc.fp8_quantiser, "float32": rc.bf16_quantiser}[
            ctx.config["compute_dtype"]]

        def also(ctx, ref, batches, ref_readings, prog_readings):
            out = {"raw": {"program": prog_readings, "reference": ref_readings}}
            if args.control:
                t = time.perf_counter()
                c = runner.run_reference(ctx, ref, batches, q=quant)
                out["control"] = compare.train_numbers(c, ref_readings)
                out["control_s"] = time.perf_counter() - t
                out["raw"]["control"] = c
            if args.faults:
                half = runner.run_reference(
                    ctx, ref, batches, rows=batches[0][0].shape[0] // 2)
                out["half_batch"] = compare.train_numbers(half, ref_readings)
                out["raw"]["half_batch"] = half
            return out

        out = runner.run(ctx, also=also)
        line = {"cell": args.cell, "seed": seed, "correct": out["correct"],
                "program": {k: v["value"] for k, v in out["compared"].items()},
                "end_to_end": out["end_to_end"],
                "memory_peak_bytes": out["memory_peak_bytes"], **out["extra"]}
        print(json.dumps(line), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
