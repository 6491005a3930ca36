"""What the expert layers of a language-model train cell do under a base
draw, on the chip, at the cell's own size, many draws in one process (run
by a builder to choose `weights.base_key`, never by the benchmark's runs):

    python benchmark/tools/routing_by_base_key.py <cell> --keys 36,37,38
        --seeds 1 [--jitter 0.003] [--seconds 20] [--out FILE]

For each key and seed one window of the cell with the configuration's
`weights.base_key` (and `seed_jitter`, where given) replaced, the
reference left out. One JSON line a run: every train record's step with
its `moe_*` counters an expert layer, the window's means, and the two
things the choice rests on, NEVER the rate:
  (a) `full_width_records`: records, inside the window and in all, on which
      some layer ran at the full width (its held slots passed the row cap);
  (b) `share_in_range`: every layer's `moe_slots_held_share`, as the
      window's mean, within SHARE_RANGE (around the even 0.125).
The first key in the order given that reads 0 in all and true is taken.
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

SHARE_RANGE = (0.06, 0.20)


def main(argv=None) -> int:
    import run as bench_run

    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--keys", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--jitter", type=float, default=None)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench_run.prepare_environment()
    lo, hi = SHARE_RANGE

    def emit(line):
        print(json.dumps(line), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")

    for key in (int(k) for k in args.keys.split(",")):
        for seed in (int(s) for s in args.seeds.split(",")):
            ctx = bench_run.build_context(args.cell, seed, args.seconds, False)
            ctx.t_process_start = time.perf_counter()
            bench_run.check_device(ctx)
            ctx.config["weights"] = {**ctx.config["weights"], "base_key": key}
            if args.jitter is not None:
                ctx.config["weights"]["seed_jitter"] = args.jitter
            runner = importlib.import_module(
                "benchmark.runners." + ctx.traffic["runner"])
            out = runner.run(ctx, agree=False, reference=False)
            extra, records = out["extra"], out["records"]
            full = [r["step"] for r in records if any(r.get("moe_full_width", []))]
            inside = [r["step"] for r in records if r["in_window"]]
            share = extra.get("moe_slots_held_share")
            emit({"cell": args.cell, "base_key": key, "seed": seed,
                  "seed_jitter": ctx.config["weights"]["seed_jitter"],
                  "full_width_records": {
                      "in_window": len(set(full) & set(inside)),
                      "all": len(full), "steps": full},
                  "share_in_range": bool(share) and all(lo <= x <= hi for x in share),
                  "window_steps": [min(inside, default=0) - ctx.traffic["log_every"],
                                   max(inside, default=0)],
                  "window_means": {k: v for k, v in extra.items()
                                   if k.startswith("moe_")},
                  "end_to_end": out["end_to_end"],
                  "tokens_per_s": extra["tokens_per_s"],
                  "memory_peak_bytes": out["memory_peak_bytes"],
                  "fit_error": extra["fit_error"], "records": records})
    return 0


if __name__ == "__main__":
    sys.exit(main())
