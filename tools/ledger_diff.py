"""Perf-regression sentinel CLI: diff a run's executable ledger against
a committed baseline ledger (DESIGN.md "Executable ledger").

Every lowering the framework performs writes a provenance row (StableHLO
fingerprint, compile seconds, persistent-cache hit/miss, XLA cost
analysis, memory footprint, donation map) to ``<log_dir>/ledger.jsonl``
(deepof_tpu/obs/ledger.py). This tool compares a live run's rows to a
baseline's, per executable name, and fails — exit code **8**, the same
code ``deepof_tpu tail`` uses — on:

  - **HLO fingerprint drift**: the computation changed (a config edit,
    a jax upgrade, a silently different lowering);
  - **unexpected recompiles**: the baseline's compile was a persistent-
    cache hit but this run's missed (cache-key drift / evicted cache);
  - **compile-time blowups**: compile_s past
    max(--compile-floor-s, baseline * --compile-factor);
  - **memory growth**: argument+output+temp bytes past
    baseline * --memory-factor.

New/missing executable names are reported but never fail (a config may
legitimately grow or shrink its lattice; the `warmup --serve` report
owns per-entry coverage).

CI shape: rc 0 clean, rc 8 on drift, rc 1 usage error. Typical flow —
commit a known-good run's ledger.jsonl as the baseline, then gate every
run (or a chip call's measurement run) with::

    python tools/ledger_diff.py --baseline ledgers/BASELINE.jsonl \
        --run /tmp/deepof_tpu

jax-free by design: the diff must run from any machine, against a live
run, without touching an accelerator backend.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from deepof_tpu.obs.ledger import (  # noqa: E402 - path bootstrap above
    DEFAULT_COMPILE_FACTOR, DEFAULT_COMPILE_FLOOR_S, DEFAULT_MEMORY_FACTOR,
    diff_ledgers, load_ledger)

#: exit code on drift — deliberately the SAME code `deepof_tpu tail`
#: returns for a failed ledger verdict, so scripted gates treat the
#: standalone diff and the tail ladder interchangeably
RC_DRIFT = 8


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="ledger_diff",
        description="diff a run's executable ledger against a baseline "
                    "(rc 0 clean, 8 on drift, 1 usage error)")
    ap.add_argument("--baseline", required=True,
                    help="baseline ledger.jsonl (or a run dir holding "
                         "one)")
    ap.add_argument("--run", required=True,
                    help="the run's ledger.jsonl (or its --log-dir)")
    ap.add_argument("--compile-factor", type=float,
                    default=DEFAULT_COMPILE_FACTOR,
                    help="compile-time blowup bound: fail when "
                         "compile_s > max(floor, baseline * FACTOR) "
                         "(default %(default)s)")
    ap.add_argument("--compile-floor-s", type=float,
                    default=DEFAULT_COMPILE_FLOOR_S,
                    help="compile-blowup floor in seconds — below it no "
                         "compile time fails (default %(default)s)")
    ap.add_argument("--memory-factor", type=float,
                    default=DEFAULT_MEMORY_FACTOR,
                    help="memory-growth bound: fail when arg+out+temp "
                         "bytes > baseline * FACTOR "
                         "(default %(default)s)")
    ap.add_argument("--json-indent", type=int, default=None)
    args = ap.parse_args(argv)

    try:
        baseline = load_ledger(args.baseline)
        run = load_ledger(args.run)
    except OSError as e:
        print(f"ledger_diff: {e}", file=sys.stderr)
        return 1
    if not baseline:
        print(f"ledger_diff: no lowering rows in {args.baseline!r}",
              file=sys.stderr)
        return 1
    if not run:
        print(f"ledger_diff: no lowering rows in {args.run!r}",
              file=sys.stderr)
        return 1

    verdict = diff_ledgers(baseline, run,
                           compile_factor=args.compile_factor,
                           compile_floor_s=args.compile_floor_s,
                           memory_factor=args.memory_factor)
    print(json.dumps(verdict, indent=args.json_indent))
    return RC_DRIFT if verdict["failed"] else 0


if __name__ == "__main__":
    raise SystemExit(main())
