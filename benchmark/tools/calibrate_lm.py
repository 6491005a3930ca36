"""Readings that the limits of `correct` of a language-model train cell
are set from, on the chip, at the cell's own size, many seeds in one
process (run by a builder, never by the benchmark's own runs):

    python benchmark/tools/calibrate_lm.py <cell> --seeds 1,2,3 [--control]
        [--faults half_batch,one_expert_fewer,not_renormalised]

For each seed: the program's first steps through the runner against the
plain reference (the lower readings); with --control the reference with
float8 forward operands, the nearest precision below the configuration's
bfloat16, put in the program's place (the upper readings). With --faults,
for each named fault of `harness/lm_faults.py` one more run of EVERY seed
with the fault planted under the tap (the seed's reference is followed
once: the fault runs are fed the same batches). One JSON line per run.

Every run is judged by `compare.judge` under the limits the cell's file
holds now, the control's numbers too: `correct` must read true for the
program and false for the control and for every fault, and `over` names
the numbers that were over their limits. The last line, `verdicts`, says
whether all of them read as they must; the exit code is 1 where not.
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
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench_run.prepare_environment()
    from benchmark.harness import compare, lm_compare
    from benchmark.harness.lm_faults import FAULTS
    from benchmark.reference import _common as rc

    seeds = [int(s) for s in args.seeds.split(",")]
    faults = [f for f in args.faults.split(",") if f]
    runs = [(s, f) for s in seeds for f in [None, *faults]]
    over = lambda compared: sorted(  # noqa: E731
        k for k, v in compared.items()
        if not (v["value"] is not None and v["value"] <= v["limit"]))
    as_expected = []

    def emit(line):
        print(json.dumps(line), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")

    for n, (seed, fault) in enumerate(runs):
        ctx = bench_run.build_context(args.cell, seed, args.seconds, False)
        ctx.t_process_start = time.perf_counter()
        bench_run.check_device(ctx)
        runner = importlib.import_module("benchmark.runners." + ctx.traffic["runner"])
        limits = {**ctx.cell["limits"], "rows_distinct": 0.0, "window_closed": 0.0}

        def also(ctx, ref, batches, ref_readings, prog_readings):
            if not args.control or fault is not None:
                return {}
            t = time.perf_counter()
            c = runner.run_reference(ctx, ref, batches, q=rc.fp8_quantiser)
            numbers = lm_compare.train_numbers(c, ref_readings)
            numbers.pop("_where")
            ok, compared = compare.judge(
                {**numbers, "rows_distinct": 0.0, "window_closed": 0.0}, limits)
            return {"control": numbers, "control_correct": ok,
                    "control_over": over(compared),
                    "control_s": time.perf_counter() - t}

        out = runner.run(ctx, step_fault=FAULTS[fault] if fault else None,
                         also=also, agree=n == 0)
        extra = out["extra"]
        as_expected.append(out["correct"] == (fault is None))
        if "control_correct" in extra:
            as_expected.append(not extra["control_correct"])
        emit({"cell": args.cell, "seed": seed, "fault": fault,
              "correct": out["correct"], "over": over(out["compared"]),
              "limits": ctx.cell["limits"], "end_to_end": out["end_to_end"],
              "memory_peak_bytes": out["memory_peak_bytes"], **extra})
    emit({"verdicts": "as they must be" if all(as_expected) else "NOT as they must be",
          "runs": len(runs), "judged": len(as_expected)})
    return 0 if all(as_expected) else 1


if __name__ == "__main__":
    sys.exit(main())
