"""Readings that the limits of `correct` are set from, on the chip, at the
cell's own size, many seeds in one process (run by a builder, never by the
benchmark's own runs):

    python benchmark/tools/calibrate.py <cell> --seeds 1,2,3 [--control] [--faults]
        [--reference-faults corr_bwd_zero,corr_bwd_no_df2,corr_bwd_flipped]
        [--program-faults corr_bwd_zero] [--watch decoder/pr5/Conv_0/kernel]

For each seed: the program's first steps through the runner against the
plain reference (the lower readings); with --control the reference in the
nearest precision below the configuration's, put in the program's place
(the upper readings); with --faults the reference with half of the batch
left out, put in the program's place; with --reference-faults the reference
with the named fault of `harness/flow_faults.py` in its correlation's
backward, put in the program's place; with --program-faults one more run
of the seed with the named fault planted under the runner's tap. --watch:
the named leaves' values after each of the first steps, in the program and
in the reference: each step's norm, how many elements keep their sign from
one step to the next, and the norm of the three steps together. One JSON
line per run.
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


def slim(readings: dict) -> dict:
    """A side's readings without its arrays."""
    out = {k: v for k, v in readings.items()
           if k not in ("first_grads", "grad_cuts", "watched")}
    for name, cut in readings.get("grad_cuts", {}).items():
        out.setdefault("grad_cuts", {})[name] = {
            k: v for k, v in cut.items() if k != "d"}
    return out


def watcher(names, store: list):
    """Under the tap, changing nothing: the named leaves before the first
    step and after each of the first three."""
    import numpy as np
    from flax.traverse_util import flatten_dict

    def leaves(params):
        flat = {"/".join(k): v for k, v in flatten_dict(params).items()}
        return {n: np.asarray(flat[n]) for n in names}

    def plant(tap):
        inner = tap.inner

        def step(state, batch):
            if not store:
                store.append(leaves(state.params))
            state, metrics = inner(state, batch)
            if len(store) <= 3:
                store.append(leaves(state.params))
            return state, metrics

        tap.inner = step
    return plant


def steps_of(values: list) -> dict:
    """values: a leaf before the first step and after each: every step's
    norm, the share of elements whose step keeps its sign from one step to
    the next, and the norm of all the steps together."""
    import numpy as np

    u = [np.asarray(b, np.float64) - np.asarray(a, np.float64)
         for a, b in zip(values, values[1:])]
    return {"step_norms": [float(np.linalg.norm(x)) for x in u],
            "same_sign_next": [float(np.mean(np.sign(a) == np.sign(b)))
                               for a, b in zip(u, u[1:])],
            "cos_next": [float(np.vdot(a, b) / (np.linalg.norm(a) * np.linalg.norm(b)))
                         for a, b in zip(u, u[1:])],
            "all_steps_norm": float(np.linalg.norm(sum(u))), "steps": u}


def watched_readings(prog_values: list, ref_values: dict) -> dict:
    import numpy as np

    out = {}
    for name, rv in ref_values.items():
        p, r = steps_of([v[name] for v in prog_values]), steps_of(rv)
        out[name] = {
            "program": {k: v for k, v in p.items() if k != "steps"},
            "reference": {k: v for k, v in r.items() if k != "steps"},
            "same_sign_program_reference": [
                float(np.mean(np.sign(a) == np.sign(b)))
                for a, b in zip(p["steps"], r["steps"])]}
    return out


def main(argv=None) -> int:
    import run as bench_run

    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--reference-faults", default="")
    ap.add_argument("--program-faults", default="")
    ap.add_argument("--watch", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench_run.prepare_environment()
    from benchmark.harness import compare, flow_faults
    from benchmark.reference import _common as rc

    names = lambda text: [n for n in text.split(",") if n]  # noqa: E731
    watch = names(args.watch)
    for seed in (int(s) for s in args.seeds.split(",")):
        for planted in [None, *names(args.program_faults)]:
            ctx = bench_run.build_context(args.cell, seed, args.seconds, False)
            ctx.t_process_start = time.perf_counter()
            bench_run.check_device(ctx)
            runner = importlib.import_module("benchmark.runners." + ctx.traffic["runner"])
            quant = {"bfloat16": rc.fp8_quantiser, "float32": rc.bf16_quantiser}[
                ctx.config["compute_dtype"]]
            seen: list = []

            def in_its_place(ref, batches, ref_readings, **how):
                """The reference changed by `how`, read as the program is."""
                side = runner.run_reference(ctx, ref, batches, cuts=(),
                                            keep_grads=True, **how)
                return compare.train_numbers(side, ref_readings), slim(side)

            def also(ctx, ref, batches, ref_readings, prog_readings):
                out = {"raw": {"program": slim(prog_readings),
                               "reference": slim(ref_readings)}}
                if planted is not None:
                    return out
                if watch:
                    again = runner.run_reference(ctx, ref, batches, cuts=(),
                                                 watch=tuple(watch))
                    out["watched"] = watched_readings(seen, again["watched"])
                if args.control:
                    t = time.perf_counter()
                    out["control"], out["raw"]["control"] = in_its_place(
                        ref, batches, ref_readings, q=quant)
                    out["control_s"] = time.perf_counter() - t
                if args.faults:
                    out["half_batch"], out["raw"]["half_batch"] = in_its_place(
                        ref, batches, ref_readings,
                        rows=batches[0][0].shape[0] // 2)
                for fault in names(args.reference_faults):
                    sound = ref.correlation
                    ref.correlation = flow_faults.with_faulty_backward(
                        sound, fault.removeprefix("corr_bwd_"))
                    try:
                        out[fault], out["raw"][fault] = in_its_place(
                            ref, batches, ref_readings)
                    finally:
                        ref.correlation = sound
                return out

            def under_tap(tap):
                if planted is not None:
                    flow_faults.FAULTS[planted](tap)
                elif watch:
                    watcher(watch, seen)(tap)

            out = runner.run(ctx, step_fault=under_tap, also=also)
            line = {"cell": args.cell, "seed": seed, "planted": planted,
                    "correct": out["correct"],
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
