"""Single CLI entry — replaces the reference's per-dataset entry scripts
(`deepOF.py`, `deepOF_fc.py`, `version1/deepOF.py`, SURVEY.md §2.1) and the
edit-a-boolean dataset dispatch (`deepOF.py:8-10`).

Usage:
    python -m deepof_tpu train --preset flyingchairs --data-path /data/fc
    python -m deepof_tpu eval  --preset sintel --data-path /data/sintel \
        --log-dir /tmp/run1          # restores latest checkpoint
    python -m deepof_tpu bench --model inception_v3
    python -m deepof_tpu warmup --preset flyingchairs --synthetic
        # AOT-compile into the on-disk cache

`warmup` populates the persistent compilation cache (artifacts/xla_cache)
for a config ahead of time — lower + compile only, no data movement, no
step execution — so the next `train`/`bench` process for the same config
starts hot (zero recompilation; see DESIGN.md "Execution layer").

Any config field can be overridden with --set section.field=value, e.g.
    --set optim.learning_rate=1e-4 --set train.num_epochs=10
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import json
import os
import sys

from .core.config import (PRESETS, ExperimentConfig, config_from_dict,
                          get_config)


def _parse_value(raw: str):
    if raw.lower() in ("true", "false"):  # accept lowercase bools
        return raw.lower() == "true"
    if raw.lower() in ("none", "null"):
        return None
    try:
        return ast.literal_eval(raw)
    except (ValueError, SyntaxError):
        return raw


def _apply_override(cfg: ExperimentConfig, dotted: str, raw: str) -> ExperimentConfig:
    """Set a dotted config path (`field`, `section.field`, or deeper —
    e.g. `resilience.faults.decode_p`) on the frozen config tree,
    returning a new config. Every intermediate node must be a dataclass
    field of its parent."""
    value = _parse_value(raw)

    def rec(node, parts: list[str]):
        name, rest = parts[0], parts[1:]
        if not (dataclasses.is_dataclass(node) and hasattr(node, name)):
            raise SystemExit(f"unknown config field {dotted!r}")
        new = rec(getattr(node, name), rest) if rest else value
        return dataclasses.replace(node, **{name: new})

    return rec(cfg, dotted.split("."))


def _recipe_from_file(cfg: ExperimentConfig, path: str) -> ExperimentConfig:
    """Load a `--recipe FILE` JSON (a RecipeConfig dict, train/recipe.py)
    into the config. The file implies recipe.enabled; unknown keys are
    rejected at every nesting level (stages[i], stages[i].mixture[j])."""
    from .core.config import recipe_from_dict

    try:
        with open(path) as f:
            d = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise SystemExit(f"--recipe {path!r}: {e}")
    if not isinstance(d, dict):
        raise SystemExit(f"--recipe {path!r}: expected a JSON object "
                         '(a RecipeConfig dict with a "stages" list)')
    d.setdefault("enabled", True)
    try:
        return cfg.replace(recipe=recipe_from_dict(d))
    except (TypeError, ValueError) as e:
        raise SystemExit(f"--recipe {path!r}: {e}")


def _build_cfg(args) -> ExperimentConfig:
    if getattr(args, "config_json", None):
        # the fleet's parent->replica handoff: the exact serialized
        # config tree, not a preset re-derivation (--set still wins)
        with open(args.config_json) as f:
            cfg = config_from_dict(json.load(f))
    else:
        cfg = get_config(args.preset)
    if args.model:
        cfg = cfg.replace(model=args.model)
    if args.data_path:
        cfg = cfg.replace(data=dataclasses.replace(cfg.data, data_path=args.data_path))
    if args.log_dir:
        cfg = cfg.replace(train=dataclasses.replace(cfg.train, log_dir=args.log_dir))
    if getattr(args, "synthetic", False):
        # before --set so explicit overrides win over smoke-test defaults
        cfg = cfg.replace(data=dataclasses.replace(
            cfg.data, dataset="synthetic", image_size=(64, 64),
            gt_size=(64, 64), batch_size=8, crop_size=None, time_step=2),
            train=dataclasses.replace(cfg.train, eval_batch_size=8,
                                      eval_amplifier=1.0))
    if getattr(args, "recipe", None):
        # before --set so explicit --set recipe.* overrides win over
        # the file (same convention as every sugar flag above)
        cfg = _recipe_from_file(cfg, args.recipe)
    # serve session/autoscale sugar: the flags ride the same
    # nested-override path as --set (and before it, so an explicit
    # --set still wins)
    for flag, dotted in (("session_ttl", "serve.session.ttl_s"),
                        ("session_max", "serve.session.max_sessions"),
                        ("min_replicas", "serve.fleet.min_replicas"),
                        ("max_replicas", "serve.fleet.max_replicas"),
                        ("artifacts", "serve.artifacts_dir")):
        value = getattr(args, flag, None)
        if value is not None:
            cfg = _apply_override(cfg, dotted, repr(value))
    if getattr(args, "autoscale", False):
        cfg = _apply_override(cfg, "serve.fleet.autoscale", "true")
    overrides = []
    for item in args.set or []:
        if "=" not in item:
            raise SystemExit(f"bad --set {item!r}: use section.field=value")
        overrides.append(item.split("=", 1))
    # lm.config_file first: the file fills the section, every other
    # override (lm.* among them) then wins over what the file says
    for dotted, raw in overrides:
        if dotted == "lm.config_file":
            from .core.config import fill_lm_from_file

            try:
                cfg = cfg.replace(lm=fill_lm_from_file(cfg.lm, raw))
            except (OSError, ValueError, TypeError) as e:
                raise SystemExit(f"--set lm.config_file={raw!r}: {e}")
    for dotted, raw in overrides:
        if dotted != "lm.config_file":
            cfg = _apply_override(cfg, dotted, raw)
    return cfg


def _add_common(p: argparse.ArgumentParser) -> None:
    p.add_argument("--preset", default="flyingchairs", choices=sorted(PRESETS))
    p.add_argument("--model", default=None)
    p.add_argument("--data-path", default=None)
    p.add_argument("--log-dir", default=None)
    p.add_argument("--set", action="append", metavar="SECTION.FIELD=VALUE")
    p.add_argument("--multihost", action="store_true",
                   help="call jax.distributed.initialize() so the mesh spans "
                        "hosts (data axis over DCN). batch_size is GLOBAL; "
                        "each host loads only its shard's rows for training "
                        "(decorrelated rng streams), val batches load "
                        "host-identically and eval outputs allgather "
                        "(single-writer ckpt/logs/visuals)")
    p.add_argument("--synthetic", action="store_true",
                   help="swap in the synthetic dataset at small shapes "
                        "(smoke tests; no data on disk needed)")


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(prog="deepof_tpu")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p_train = sub.add_parser("train", help="train a model")
    _add_common(p_train)
    p_train.add_argument("--epochs", type=int, default=None)
    p_train.add_argument("--max-steps", "--steps", dest="max_steps",
                         type=int, default=None)
    p_train.add_argument("--recipe", default=None, metavar="FILE",
                         help="staged training-recipe JSON (DESIGN.md "
                              "\"Recipe engine\"): an ordered stage list, "
                              "each with a weighted dataset mixture "
                              "(deterministic for any data.num_workers), "
                              "per-stage shape/time_step/loss/lr "
                              "overrides, and an advance trigger — fixed "
                              "steps or the eval_trend sustained-AEE-"
                              "plateau signal. Implies recipe.enabled; "
                              "--set recipe.* still wins")
    p_train.add_argument("--profile", action="store_true",
                         help="whole-run jax.profiler trace (includes "
                              "compile; grows with run length)")
    p_train.add_argument("--profile-steps", default=None, metavar="K:N",
                         help="jax.profiler trace of steps K..N only "
                              "(excludes compile, stays small enough to "
                              "bring back from the chip)")
    p_train.add_argument("--trace", action="store_true",
                         help="cross-thread span timeline to "
                              "<log-dir>/trace.json (Perfetto/"
                              "chrome://tracing loadable) — shorthand "
                              "for --set obs.trace=true")
    p_train.add_argument("--elastic", type=int, default=None, metavar="N",
                         help="elastic multi-host training (DESIGN.md "
                              "\"Elastic training\"): supervise N "
                              "single-host trainer subprocesses that "
                              "survive host loss/preemption — a lost or "
                              "wedged host triggers a generation bump: "
                              "clean barrier stop, re-form on the "
                              "survivors (re-sharded data streams), "
                              "resume from the newest verified "
                              "checkpoint. Requires --max-steps (the "
                              "absolute target step). Overrides "
                              "elastic.hosts; <= 1 keeps plain training")
    p_train.add_argument("--host-index", type=int, default=None,
                         help=argparse.SUPPRESS)  # elastic-internal:
    #                      trainer children carry their host identity
    p_train.add_argument("--config-json", default=None,
                         help=argparse.SUPPRESS)  # elastic-internal:
    #                      children load the coordinator's exact config

    p_eval = sub.add_parser("eval", help="evaluate latest checkpoint")
    _add_common(p_eval)
    p_eval.add_argument("--dump-visuals", action="store_true")

    p_pred = sub.add_parser(
        "predict", help="run a trained model on image pairs; write .flo + png")
    _add_common(p_pred)
    p_pred.add_argument("--pairs", nargs="+", required=True,
                        metavar="PREV:NEXT",
                        help="image-path pairs, colon-separated")
    p_pred.add_argument("--out", required=True, help="output directory")
    p_pred.add_argument("--no-png", action="store_true")
    p_pred.add_argument("--action", action="store_true",
                        help="classify each pair with a trained action "
                             "head (st_single/st_baseline/ucf101_spatial "
                             "— the UCF-101 workload) instead of "
                             "predicting flow: writes <out>/actions.json "
                             "with top-k classes + softmax probs per "
                             "pair")
    p_pred.add_argument("--labels", default=None, metavar="FILE",
                        help="--action: class-name file (one name per "
                             "line, index order) to attach names to "
                             "predictions")
    p_pred.add_argument("--ckpt-dir", default=None, metavar="DIR",
                        help="--action: explicit checkpoint directory "
                             "(a recipe run's final stage lives under "
                             "<log-dir>/ckpt-stage<i>, not <log-dir>/"
                             "ckpt)")
    p_pred.add_argument("--precision", default=None,
                        choices=("f32", "bf16", "int8"),
                        help="serving precision tier (must be in "
                             "serve.precisions; default: the config's "
                             "first tier). bf16 halves and int8 quarters "
                             "the weight bytes each dispatch moves "
                             "(weight-only, per-output-channel scales; "
                             "DESIGN.md \"Precision tiers\")")

    p_cfg = sub.add_parser("config", help="print the resolved config")
    _add_common(p_cfg)

    p_warm = sub.add_parser(
        "warmup", help="AOT-compile a config's train+eval executables into "
                       "the persistent compile cache (no execution)")
    _add_common(p_warm)
    p_warm.add_argument("--no-eval", action="store_true",
                        help="skip the eval executable")
    p_warm.add_argument("--recipe", default=None, metavar="FILE",
                        help="AOT-compile EVERY stage of this training-"
                             "recipe JSON — one (train, eval) executable "
                             "pair per stage — so a later `train "
                             "--recipe` run switches stages with zero "
                             "recompiles (provable from the ledger)")
    p_warm.add_argument("--serve", action="store_true",
                        help="also AOT-compile the serve ladder "
                             "(serve.buckets x serve.precisions "
                             "inference executables at serve.max_batch) "
                             "so a cold engine's first requests load "
                             "instead of compiling")
    p_warm.add_argument("--serve-only", action="store_true",
                        help="compile only the serve ladder (skip "
                             "train/eval)")
    p_warm.add_argument("--artifacts", default=None, metavar="DIR",
                        help="publish each serve executable into this "
                             "artifact store (serialized, fingerprint-"
                             "keyed — DESIGN.md \"Artifact plane\"); "
                             "engines/replicas started with the same "
                             "store boot by fetching instead of "
                             "compiling. Shorthand for "
                             "--set serve.artifacts_dir=DIR; works "
                             "cache-free with --serve-only (single-"
                             "writer publish is cpu-safe)")

    p_srv = sub.add_parser(
        "serve", help="inference serving (DESIGN.md \"Serving\"): dynamic "
                      "micro-batching engine over the latest verified "
                      "checkpoint. Default: stdlib HTTP server (POST "
                      "/v1/flow; streaming video sessions on POST "
                      "/v1/flow/stream — one decode per frame; GET "
                      "/healthz) with a serve heartbeat in "
                      "--log-dir; with --input: offline high-throughput "
                      "directory/video inference to --out")
    _add_common(p_srv)
    p_srv.add_argument("--input", default=None,
                       help="offline mode: a directory of frames "
                            "(consecutive sorted pairs) or a video file")
    p_srv.add_argument("--out", default=None,
                       help="offline mode: output directory for "
                            ".flo/.png results")
    p_srv.add_argument("--no-png", action="store_true")
    p_srv.add_argument("--session-ttl", type=float, default=None,
                       metavar="SECONDS",
                       help="streaming sessions (POST /v1/flow/stream, "
                            "DESIGN.md \"Streaming sessions\"): idle TTL "
                            "before a session expires — shorthand for "
                            "--set serve.session.ttl_s=X; <= 0 disables "
                            "the TTL")
    p_srv.add_argument("--session-max", type=int, default=None,
                       metavar="N",
                       help="streaming sessions: LRU bound on "
                            "concurrently kept sessions per engine — "
                            "shorthand for "
                            "--set serve.session.max_sessions=N")
    p_srv.add_argument("--replicas", type=int, default=None,
                       help="self-healing serving fleet (DESIGN.md "
                            "\"Fleet\"): supervise N engine-replica "
                            "subprocesses behind a health-gated router "
                            "with bucket-affinity routing, failover "
                            "retries, load shedding, and automatic "
                            "evict/respawn of wedged or crashed "
                            "replicas. Overrides serve.fleet.replicas; "
                            "<= 1 keeps single-process serving")
    p_srv.add_argument("--autoscale", action="store_true",
                       help="SLO-driven fleet autoscaling (DESIGN.md "
                            "\"Supervision plane\"): scale the replica "
                            "pool between serve.fleet.min_replicas and "
                            "max_replicas from live signals — sustained "
                            "shed/overload and SLO budget burn scale up, "
                            "sustained idle scales down via graceful "
                            "drain. Shorthand for "
                            "--set serve.fleet.autoscale=true; implies "
                            "fleet mode even without --replicas")
    p_srv.add_argument("--min-replicas", type=int, default=None,
                       metavar="N",
                       help="autoscaler pool floor — shorthand for "
                            "--set serve.fleet.min_replicas=N")
    p_srv.add_argument("--max-replicas", type=int, default=None,
                       metavar="N",
                       help="autoscaler pool ceiling — shorthand for "
                            "--set serve.fleet.max_replicas=N")
    p_srv.add_argument("--artifacts", default=None, metavar="DIR",
                       help="boot executables from this artifact store "
                            "(`warmup --serve --artifacts DIR` publishes "
                            "it): fetch + deserialize instead of "
                            "compiling, fingerprint-gated — a cold "
                            "replica's first request pays zero XLA. "
                            "Shorthand for --set serve.artifacts_dir=DIR")
    p_srv.add_argument("--config-json", default=None,
                       help=argparse.SUPPRESS)  # fleet-internal: replica
    #                      processes load the supervisor's exact config

    p_bench = sub.add_parser("bench", help="throughput benchmark")
    p_bench.add_argument("--model", default="inception_v3")
    p_bench.add_argument("--batch", type=int, default=16)
    p_bench.add_argument("--steps", type=int, default=20)
    p_bench.add_argument("--data-only", action="store_true",
                         help="measure host input-pipeline throughput in "
                              "isolation (batches/s, MB/s; cpu-only, no "
                              "accelerator touched) instead of the train "
                              "step — attributes host vs. device "
                              "bottlenecks without a TPU")
    p_bench.add_argument("--workers", type=int, default=0,
                         help="data-only mode: pipeline worker threads")
    p_bench.add_argument("--batches", type=int, default=32,
                         help="data-only mode: batches to time")
    p_bench.add_argument("--image-size", default="64x64", metavar="HxW",
                         help="data-only mode: decoded image size")
    p_bench.add_argument("--dataset", default="synthetic",
                         help="data-only mode: dataset to assemble "
                              "(flyingchairs/sintel/ucf101/synthetic)")
    p_bench.add_argument("--data-path", default="",
                         help="data-only mode: dataset root on disk")
    p_bench.add_argument("--recipe", default=None, metavar="FILE",
                         help="data-only mode: time the recipe's first-"
                              "stage weighted MIXTURE stream "
                              "(data/mixture.py) through the pipeline "
                              "instead of a single --dataset")

    p_an = sub.add_parser("analyze", help="summarize a run's metrics log")
    p_an.add_argument("--log-dir", required=True)
    p_an.add_argument("--no-plot", action="store_true")

    p_vck = sub.add_parser(
        "verify-ckpt",
        help="offline manifest/checksum validation of every checkpoint "
             "in a run directory (jax-free; nonzero exit on corruption)")
    p_vck.add_argument("dir",
                       help="a run's --log-dir or its ckpt/ subdirectory")

    p_art = sub.add_parser(
        "artifacts",
        help="executable artifact store (DESIGN.md \"Artifact plane\"): "
             "list / verify / gc the fingerprint-keyed serialized AOT "
             "executables `warmup --serve` publishes and replicas boot "
             "from (jax-free; verify-ckpt's rc contract: 1 = corrupt "
             "entries, 2 = empty store)")
    p_art.add_argument("action", choices=("list", "verify", "gc"),
                       help="list: one identity line per entry; verify: "
                            "full structural verdicts (manifest + "
                            "fingerprint + payload size/crc32); gc: "
                            "remove corrupt entries and orphaned tmp "
                            "staging dirs")
    p_art.add_argument("--deep", action="store_true",
                       help="verify only: re-lower every indexed serve "
                            "executable under the given config and "
                            "compare StableHLO fingerprints against the "
                            "store's index (the offline twin of the "
                            "engine's background deep-verify plane). "
                            "Needs jax + the config the index was "
                            "published under (--preset/--model/--set); "
                            "rc 1 on drift, rc 2 on an empty/unindexed "
                            "store")
    p_art.add_argument("--preset", default="flyingchairs",
                       choices=sorted(PRESETS),
                       help="--deep only: config preset the index was "
                            "published under")
    p_art.add_argument("--model", default=None,
                       help="--deep only: model override")
    p_art.add_argument("--set", action="append",
                       metavar="SECTION.FIELD=VALUE",
                       help="--deep only: config overrides (must match "
                            "the publishing warmup's)")
    p_art.add_argument("--dir", default=None,
                       help="store root (default: <repo>/artifacts/exec, "
                            "serve.artifacts_dir's conventional home)")
    p_art.add_argument("--older-than-days", type=float, default=None,
                       metavar="DAYS",
                       help="gc: also remove structurally VALID entries "
                            "whose manifest is older than this many days "
                            "(code churn strands fingerprints forever)")
    p_art.add_argument("--json-indent", type=int, default=2)

    p_lint = sub.add_parser(
        "lint", help="graftlint: project-invariant static analysis "
                     "(DESIGN.md \"Static analysis\"): counters "
                     "registered in obs/registry.py, config attribute "
                     "typos, determinism (unseeded randomness in the "
                     "data/model path), jit-purity (side effects in "
                     "traced code), and cross-thread lock discipline. "
                     "jax-free; exit 0 clean, 2 on findings, 1 on "
                     "usage error")
    p_lint.add_argument("paths", nargs="*",
                        help="files/directories to lint (default: the "
                             "deepof_tpu package + tools/)")
    p_lint.add_argument("--json", action="store_true", dest="as_json",
                        help="machine-readable findings (CI mode)")
    p_lint.add_argument("--rule", action="append", default=None,
                        metavar="NAME",
                        help="run only this rule (repeatable); default: "
                             "all rules")

    p_tail = sub.add_parser(
        "tail", help="one-glance health of a live or finished run: step, "
                     "loss, recent vs overall throughput, phase shares, "
                     "starvation, resilience counters, heartbeat age; "
                     "exits 3 when the heartbeat reports wedged, 4 when "
                     "a serving fleet evicted or broke a replica, 5 "
                     "when an elastic run lost a host and re-formed, 6 "
                     "when the SLO error budget is exhausted "
                     "(obs.slo_latency_ms / obs.slo_error_budget), 7 "
                     "when the label-free flow-quality drift verdict "
                     "fired (obs.quality_sample_rate / obs.quality_budget"
                     " — with --fleet, any replica's verdict counts), 8 "
                     "when the executable ledger drifted against its "
                     "baseline (HLO fingerprint drift, unexpected "
                     "recompiles, compile blowups, memory growth — "
                     "obs/ledger.py; with --fleet, any replica's ledger "
                     "counts), and 9 — checked ahead of 3-7 (8 stays 8: "
                     "the live ledger verdict records its own bundle) — "
                     "when the incident plane holds unacknowledged "
                     "CRITICAL flight-recorder bundles (obs.incidents; "
                     "`incidents ack` clears it), and 10 when the "
                     "brownout controller held L3 (shedding low-priority"
                     " work) past serve.degrade.l3_sustained_s — "
                     "degradation was meant as a bridge to autoscaled "
                     "capacity that never arrived (serve/degrade.py)")
    p_tail.add_argument("--log-dir", required=True)
    p_tail.add_argument("--recent", type=int, default=10,
                        help="train records in the throughput-trend window")
    p_tail.add_argument("--fleet", action="store_true",
                        help="also aggregate the run dir's supervised "
                             "children (fleet replicas / elastic hosts) "
                             "into per-process blocks + an exact merged "
                             "latency histogram — the whole drill in one "
                             "read")
    p_tail.add_argument("--follow", action="store_true",
                        help="re-print every --interval seconds until ^C")
    p_tail.add_argument("--interval", type=float, default=10.0)
    p_tail.add_argument("--ledger-baseline", default=None, metavar="PATH",
                        help="baseline ledger.jsonl for the executable-"
                             "ledger drift verdict (exit 8). Default: "
                             "<log-dir>/ledger_baseline.jsonl when "
                             "present; no baseline = no verdict")
    p_tail.add_argument("--ledger-compile-factor", type=float,
                        default=None, metavar="X",
                        help="compile-time blowup bound: fail when an "
                             "executable's compile_s exceeds "
                             "max(floor, baseline * X) (default 2.0)")
    p_tail.add_argument("--ledger-compile-floor-s", type=float,
                        default=None, metavar="S",
                        help="compile-blowup floor in seconds — below "
                             "it no compile time fails (default 1.0)")
    p_tail.add_argument("--ledger-memory-factor", type=float,
                        default=None, metavar="X",
                        help="memory-growth bound: fail when arg+out+"
                             "temp bytes exceed baseline * X "
                             "(default 1.2)")

    p_inc = sub.add_parser(
        "incidents",
        help="incident flight-recorder triage (DESIGN.md \"Incident "
             "plane\"): list / show / ack / gc the bounded diagnostic "
             "bundles anomaly triggers committed under "
             "<log-dir>/incidents/ (jax-free; rc 1 = unacknowledged "
             "CRITICAL incidents need attention, rc 2 = none recorded)")
    p_inc.add_argument("action", choices=("list", "show", "ack", "gc"),
                       help="list: one line per committed bundle + the "
                            "summary block tail/analyze embed; show: one "
                            "bundle's full manifest + on-disk file "
                            "inventory; ack: acknowledge bundle(s) — "
                            "clears tail's rc 9; gc: remove old/acked "
                            "bundles and orphaned staging dirs")
    p_inc.add_argument("--log-dir", required=True)
    p_inc.add_argument("--id", default=None, metavar="ID",
                       help="show: required; ack: one bundle "
                            "(default: all)")
    p_inc.add_argument("--older-than-days", type=float, default=None,
                       metavar="DAYS",
                       help="gc: remove bundles whose manifest is older "
                            "than this many days")
    p_inc.add_argument("--acked", action="store_true",
                       help="gc: also remove acknowledged bundles of any "
                            "age")
    p_inc.add_argument("--keep", type=int, default=None, metavar="N",
                       help="gc: keep at most the newest N bundles")
    p_inc.add_argument("--json-indent", type=int, default=2)

    return parser


def config_for(argv) -> ExperimentConfig:
    """The ExperimentConfig a command line resolves to (preset, sugar
    flags, then --set), without running the verb."""
    return _build_cfg(build_parser().parse_args(argv))


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    if args.cmd == "lint":
        # jax-free by design (lint/ imports stdlib + core.config +
        # obs.registry only): the CI gate must run on hosts with no
        # accelerator stack at all
        import time as _time

        from .lint import RULES, lint_paths

        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        paths = args.paths or [
            p for p in (os.path.join(repo_root, "deepof_tpu"),
                        os.path.join(repo_root, "tools"))
            if os.path.isdir(p)]
        selected = sorted(set(args.rule)) if args.rule else sorted(RULES)
        t0 = _time.perf_counter()
        try:
            findings = lint_paths(paths, rules=selected)
        except (ValueError, FileNotFoundError) as e:
            print(f"lint: {e}", file=sys.stderr)
            return 1  # usage error: distinct from "findings" (2)
        elapsed = round(_time.perf_counter() - t0, 3)
        live = [f for f in findings if not f.waived]
        waived = [f for f in findings if f.waived]
        if args.as_json:
            print(json.dumps({
                "findings": [f.as_dict() for f in live],
                "waived": [f.as_dict() for f in waived],
                "rules": selected,
                "elapsed_s": elapsed}))
        else:
            for f in findings:
                print(f.format())
            print(f"lint: {len(live)} finding(s), {len(waived)} waived, "
                  f"{len(selected)} rule(s) in {elapsed}s")
        return 2 if live else 0

    if args.cmd == "verify-ckpt":
        # jax-free by design (resilience/verify.py is stdlib-only): the
        # manifests inventory files + crc32s, so validation runs from
        # any machine, against a live run, without touching a backend
        from .resilience.verify import verify_run

        report = verify_run(args.dir)
        print(json.dumps(report, indent=2))
        if report["corrupt_steps"]:
            return 1  # corruption is the nonzero-exit contract
        if not report["checkpoints"]:
            print(f"verify-ckpt: no checkpoints under {args.dir!r}",
                  file=sys.stderr)
            return 2
        return 0

    if args.cmd == "artifacts":
        # jax-free by design (serve/artifacts.py's store half is
        # stdlib): the store is listed/verified/gc'd from any machine —
        # same contract as verify-ckpt (rc 1 corrupt, rc 2 empty)
        from .serve.artifacts import (DEFAULT_STORE_DIR, gc_store,
                                      verify_store)

        root = args.dir or DEFAULT_STORE_DIR
        if args.action == "verify" and args.deep:
            # the one artifacts action that DOES need jax: re-lower the
            # serve lattice under the given config and compare StableHLO
            # fingerprints against the index — catches code drift the
            # structural (crc/manifest) verify cannot see
            from .train.warmup import deep_verify_serve

            args.data_path = None  # _build_cfg expects the common args
            args.log_dir = None
            cfg = _build_cfg(args)
            cfg = _apply_override(cfg, "serve.artifacts_dir", repr(root))
            try:
                report = deep_verify_serve(cfg)
            except ValueError as e:
                print(f"artifacts verify --deep: {e}", file=sys.stderr)
                return 2
            print(json.dumps(report, indent=args.json_indent))
            if report["drift"]:
                return 1
            if not report["entries"] or report["ok"] == 0:
                print(f"artifacts: nothing indexed to deep-verify at "
                      f"{root!r}", file=sys.stderr)
                return 2
            return 0
        if args.action == "gc":
            report = gc_store(root, older_than_days=args.older_than_days)
            print(json.dumps(report, indent=args.json_indent))
            return 0
        report = verify_store(root)
        if args.action == "list":
            print(json.dumps(
                {"dir": report["dir"], "total": report["total"],
                 "ok": report["ok"], "corrupt": report["corrupt"],
                 "entries": [{"fingerprint": e["fingerprint"],
                              "name": e["name"], "ok": e["ok"],
                              "size": e["size"], "created": e["created"]}
                             for e in report["entries"]]},
                indent=args.json_indent))
        else:
            print(json.dumps(report, indent=args.json_indent))
        if report["corrupt"]:
            return 1
        if not report["entries"]:
            print(f"artifacts: empty store at {root!r}", file=sys.stderr)
            return 2
        return 0

    if args.cmd == "incidents":
        # jax-free by design (obs/incident.py is stdlib-only): triage
        # runs from any machine, against a live run — same contract
        # family as verify-ckpt/artifacts (rc 1 = attention required,
        # rc 2 = empty plane)
        from .obs import incident as _incident

        if args.action == "show":
            if not args.id:
                print("incidents show: --id required", file=sys.stderr)
                return 1
            try:
                detail = _incident.show_incident(args.log_dir, args.id)
            except FileNotFoundError:
                print(f"incidents: no committed bundle {args.id!r} under "
                      f"{args.log_dir!r}", file=sys.stderr)
                return 1
            print(json.dumps(detail, indent=args.json_indent))
            return 0
        if args.action == "ack":
            acked = _incident.ack_incidents(args.log_dir,
                                            incident_id=args.id)
            print(json.dumps({"acked": acked},
                             indent=args.json_indent))
            if args.id is not None and not acked:
                print(f"incidents: no unacknowledged bundle {args.id!r} "
                      f"under {args.log_dir!r}", file=sys.stderr)
                return 1
            return 0
        if args.action == "gc":
            report = _incident.gc_incidents(
                args.log_dir, older_than_days=args.older_than_days,
                acked=args.acked, keep=args.keep)
            print(json.dumps(report, indent=args.json_indent))
            return 0
        rows = _incident.list_incidents(args.log_dir)
        summary = _incident.incident_summary(args.log_dir)
        print(json.dumps(
            {"dir": _incident.incidents_dir(args.log_dir),
             "summary": summary,
             "incidents": [
                 {"id": r.get("id"), "kind": r.get("kind"),
                  "severity": r.get("severity"), "role": r.get("role"),
                  "time": r.get("iso_time"), "acked": r.get("acked"),
                  "origin": r.get("origin")} for r in rows]},
            indent=args.json_indent))
        if summary is None:
            print(f"incidents: none recorded under {args.log_dir!r}",
                  file=sys.stderr)
            return 2
        return 1 if summary["unacked_critical"] else 0

    if args.cmd == "tail":
        # jax-free like analyze: tailing a run must never touch the
        # accelerator the trainer holds
        from .analyze import tail_summary

        ledger_bounds = {
            k: v for k, v in (
                ("compile_factor", args.ledger_compile_factor),
                ("compile_floor_s", args.ledger_compile_floor_s),
                ("memory_factor", args.ledger_memory_factor))
            if v is not None}
        # a requested ledger gate must never silently pass: a typo'd
        # baseline path or a run that recorded no ledger would
        # otherwise yield "no verdict" => rc 0 forever (the standalone
        # ledger_diff errors rc 1 on the same inputs — the two gates
        # must agree). This covers the committed-by-convention
        # <log_dir>/ledger_baseline.jsonl too: a convention file that
        # EXISTS but holds no parseable rows is a broken gate, not the
        # legitimate no-baseline case.
        from .obs.ledger import (find_baseline, load_ledger,
                                 resolve_ledger_path)

        _base = args.ledger_baseline
        if _base is not None:
            # a run dir holding a ledger.jsonl is a valid baseline —
            # the SAME resolution rule load_ledger/ledger_diff apply,
            # shared so the two gates can never diverge on it
            _p = resolve_ledger_path(_base)
            if not os.path.isfile(_p):
                raise SystemExit(f"tail: --ledger-baseline "
                                 f"{_base!r} does not exist "
                                 f"(expected a ledger.jsonl or a run dir "
                                 f"holding one)")
        else:
            _p = find_baseline(args.log_dir)  # convention file or None
        if _p is not None:
            # the baseline side is STATIC — an empty/truncated file can
            # never become valid, so even --follow must fail it loudly
            # up front (ledger_verdict would return None and the gate
            # would sit silently inert forever)
            try:
                _base_rows = load_ledger(_p)
            except OSError as e:
                raise SystemExit(f"tail: ledger baseline {_p!r} "
                                 f"unreadable: {e}")
            if not _base_rows:
                raise SystemExit(f"tail: ledger baseline {_p!r} "
                                 f"contains no ledger rows")
        while True:
            try:
                summary = tail_summary(args.log_dir, recent=args.recent,
                                       fleet=args.fleet,
                                       ledger_baseline=args.ledger_baseline,
                                       ledger_bounds=ledger_bounds)
            except FileNotFoundError:
                raise SystemExit(f"no metrics.jsonl under {args.log_dir!r} "
                                 "— is this a run's --log-dir?")
            print(json.dumps(summary), flush=True)
            if (args.ledger_baseline is not None
                    and "ledger_diff" not in summary
                    and not args.follow):
                # the explicit gate could not run: baseline unreadable
                # or the run recorded no ledger — loud, never rc 0. In
                # --follow mode keep following instead: a live run's
                # ledger.jsonl only appears after its first compile
                # (minutes, cold), and rc 3-7 likewise keep following
                # until their condition actually fires.
                raise SystemExit(f"tail: --ledger-baseline given but no "
                                 f"verdict could be computed — is "
                                 f"{args.ledger_baseline!r} a ledger and "
                                 f"does {args.log_dir!r} hold a "
                                 f"ledger.jsonl (obs.ledger on)?")
            # rc 8 when the executable ledger drifted against its
            # baseline (obs/ledger.py diff_ledgers): fingerprint
            # drift, unexpected recompiles, compile-time blowups, or
            # memory growth — the executables serving/training are NOT
            # the ones the baseline measured (with --fleet, any
            # replica's verdict counts). Checked before rc 9: the
            # verdict is LIVE — it
            # re-derives from the baseline on every invocation and
            # records its own ledger_drift bundle below — so the
            # invocation that derives the failure must keep the
            # documented rc 8 (otherwise the bundle it just committed
            # would flip every later tail to rc 9 while the drift
            # persists, hiding the specific verdict). The bundle
            # surfaces as rc 9 only once the drift itself is gone but
            # the incident is still un-triaged.
            verdict = summary.get("ledger_diff") or {}
            if verdict.get("failed"):
                # persist the verdict as an incident bundle before
                # exiting: a `tail --follow` gate is often the ONLY
                # process watching, and the regression evidence should
                # outlive its stdout. Structural dedup (the condensed
                # failure set keys the bundle) means re-running tail on
                # the same regression records it once.
                from .obs import incident as _incident

                condensed = {
                    cls: sorted(e.get("name", "?")
                                for e in (verdict.get(cls) or []))
                    for cls in ("fingerprint_drift",
                                "unexpected_recompiles",
                                "compile_blowups", "memory_growth")}
                _incident.record_offline(
                    args.log_dir, "ledger_drift", "critical",
                    trigger=condensed,
                    dedup_key=json.dumps(condensed, sort_keys=True))
                return 8
            # rc 9 ahead of the cumulative rc 3-7 counters:
            # unacknowledged CRITICAL incident bundles outrank them —
            # the same anomaly usually trips both (a SIGKILL eviction
            # bumps the rc-4 counters AND commits a
            # fleet_replica_crash bundle), and the bundle is the
            # richer artifact: it carries the underlying verdict plus
            # the trace/heartbeat/stack context to triage it.
            # `incidents ack` then moves past it, where the cumulative
            # counters would re-fire forever.
            if (summary.get("incidents") or {}).get("unacked_critical"):
                return 9
            # a wedged run must fail scripted health checks loudly: rc 3
            # when the heartbeat's watchdog has declared a wedge — in
            # --follow mode the loop ends at the first wedged heartbeat
            # (the run is no longer making the progress being followed)
            hb = summary.get("heartbeat") or {}
            if hb.get("wedged"):
                return 3
            # rc 4 when a serving fleet self-healed (evictions) or gave
            # up on a replica (circuit breaker): the fleet may be
            # serving again, but an operator must see that replicas
            # were sick — the counters are cumulative by design.
            # Autoscale scale-downs deliberately do NOT trip this:
            # retirement (fleet_retired / autoscale_down) is the pool
            # doing its job, not sickness
            fleet = summary.get("fleet") or {}
            if fleet.get("broken") or fleet.get("evictions"):
                return 4
            # rc 5 when an elastic run lost a host and re-formed (or
            # aborted hosts without re-forming): the run may have
            # completed to target, but an operator must see that the
            # world shrank — distinct from wedged (3) and fleet (4)
            elastic = summary.get("elastic") or {}
            if elastic.get("reforms") or elastic.get("lost_hosts"):
                return 5
            # rc 6 when the SLO error budget is exhausted (the serve
            # engine's serve_slo or the fleet router's fleet_slo block,
            # obs/export.py): latency breaches + server-side failures
            # overran obs.slo_error_budget — the run may still be
            # serving, but it is OUTSIDE its contract
            slo = ((summary.get("serve") or {}).get("slo")
                   or (summary.get("fleet") or {}).get("slo") or {})
            if slo.get("exhausted"):
                return 6
            # rc 7 when the label-free flow-quality drift verdict fired
            # (obs/quality.py): post-reference photometric-proxy
            # breaches overran obs.quality_budget — latency and errors
            # may look perfect while the FLOWS are degrading (quantized
            # tier drift, damaged weights). With --fleet, any child
            # replica's verdict counts: the degraded replica's quality
            # block lives in its own process dir, not the router's.
            quality_blocks = [(summary.get("serve") or {}).get("quality")]
            quality_blocks += [
                (child.get("serve") or {}).get("quality")
                for child in (summary.get("processes") or {}).values()]
            if any((q or {}).get("exhausted") for q in quality_blocks):
                return 7
            # rc 10 when the brownout controller (serve/degrade.py) has
            # held L3 — shedding low-priority work — past its
            # serve.degrade.l3_sustained_s budget: quality degradation
            # was supposed to be a TRANSIENT bridge to autoscaled
            # capacity, and a fleet parked at L3 means the capacity
            # never arrived. Distinct from rc 6 (SLO budget) because a
            # browned-out fleet can sit INSIDE its latency SLO exactly
            # by refusing work.
            if (summary.get("degrade") or {}).get("l3_sustained"):
                return 10
            if not args.follow:
                return 0
            import time as _time

            _time.sleep(max(args.interval, 0.1))

    if args.cmd == "analyze":
        # deliberately light import: must not pull in jax / the train stack
        from .analyze import analyze

        try:
            summary = analyze(args.log_dir, plot=not args.no_plot)
        except FileNotFoundError:
            raise SystemExit(f"no metrics.jsonl under {args.log_dir!r} — "
                             "is this a run's --log-dir?")
        print(json.dumps(summary, indent=2))
        return 0

    if args.cmd == "bench":
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        if repo_root not in sys.path:
            sys.path.insert(0, repo_root)
        import bench as bench_mod

        if args.data_only:
            h, w = bench_mod.parse_image_size(args.image_size)
            res = bench_mod.data_bench(num_workers=args.workers,
                                       batch=args.batch,
                                       batches=args.batches,
                                       image_size=(h, w),
                                       dataset=args.dataset,
                                       data_path=args.data_path,
                                       recipe_path=args.recipe or "")
        else:
            res = bench_mod.bench(model_name=args.model, batch=args.batch,
                                  steps=args.steps)
        print(json.dumps(res))
        return 0

    cfg = _build_cfg(args)
    if args.cmd == "config":
        print(json.dumps(dataclasses.asdict(cfg), indent=2, default=str))
        return 0

    if args.cmd == "train":
        if getattr(args, "host_index", None) is not None:
            cfg = cfg.replace(elastic=dataclasses.replace(
                cfg.elastic, host_index=args.host_index))
        hosts = (args.elastic if args.elastic is not None
                 else cfg.elastic.hosts)
        if hosts and hosts > 1 and cfg.elastic.host_index < 0:
            # coordinator mode (train/elastic.py): supervise the pool —
            # dispatched BEFORE jax.distributed/backend init so the
            # supervisor process stays jax-free
            if getattr(args, "multihost", False):
                raise SystemExit(
                    "train: --elastic and --multihost are exclusive — "
                    "elastic mode supervises one single-host trainer "
                    "process per host itself")
            if args.epochs is not None:
                raise SystemExit("train: elastic mode needs an absolute "
                                 "target step (--max-steps), not --epochs")
            if cfg.recipe.enabled and cfg.recipe.stages:
                raise SystemExit(
                    "train: --elastic and --recipe are exclusive — the "
                    "recipe engine drives staged single-pool runs "
                    "(train/recipe.py); run each stage elastically via "
                    "per-stage configs instead")
            # the train-package import chain below initializes a jax
            # backend (orbax does, at import): the coordinator must
            # move onto the cpu FIRST, in EVERY mode — it computes
            # nothing, and an accelerator-holding supervisor would
            # starve the trainer child it spawns on the same host
            # (a chip belongs to one process at a time;
            # children acquire the real backend themselves when
            # elastic.virtual_devices=0)
            from .core.hostmesh import force_cpu_devices

            force_cpu_devices(1)  # supervisor computes nothing
            from .train.elastic import run_elastic

            try:
                return run_elastic(cfg, hosts=hosts,
                                   max_steps=args.max_steps)
            except ValueError as e:
                raise SystemExit(f"train --elastic: {e}")

    if (args.cmd in ("train", "eval")
            and cfg.elastic.host_index >= 0
            and cfg.elastic.virtual_devices > 0):
        # elastic trainer child in virtual-host mode: force its private
        # CPU device slice BEFORE any backend init (core/hostmesh.py)
        from .core.hostmesh import force_cpu_devices

        force_cpu_devices(cfg.elastic.virtual_devices)
        if cfg.train.compile_cache is not True:
            # force_cpu_devices points the persistent compile cache at
            # the shared directory; keep the cpu auto-off default real
            # for the pool's children (compiles of seconds, N writers):
            # compile_cache=true opts in.
            from .train.warmup import disable_compile_cache

            disable_compile_cache()

    if getattr(args, "multihost", False):
        import jax

        jax.distributed.initialize()  # coordinator/process env-configured

    if args.cmd == "warmup":
        from .train.warmup import enable_for_config, warmup_compile, warmup_serve

        # the verb's sole purpose is persisting executables: refuse to
        # silently pay minutes of XLA and persist nothing. On cpu the
        # auto default does not enable the cache
        # (TrainConfig.compile_cache), so the user must opt in
        # explicitly. EXCEPTION: `--serve-only` with serve.artifacts_dir
        # set persists through the artifact plane (serve/artifacts.py).
        if enable_for_config(cfg) is None:
            if not (args.serve_only and cfg.serve.artifacts_dir):
                print("warmup: persistent compile cache is not active "
                      "for this config/backend (cpu auto-disables it; "
                      "add --set train.compile_cache=true to opt in, or "
                      "publish serve executables cache-free with "
                      "--serve-only --set serve.artifacts_dir=PATH) — "
                      "nothing would be persisted, refusing to compile",
                      file=sys.stderr)
                return 2
        if args.serve_only:
            res = warmup_serve(cfg)
        elif cfg.recipe.enabled and cfg.recipe.stages:
            # recipe mode (via --recipe FILE or --set recipe.*): one
            # (train, eval) executable pair PER STAGE — the stage-switch
            # zero-recompile contract's warm half (train/recipe.py)
            from .train.warmup import warmup_recipe

            res = warmup_recipe(cfg)
            if args.serve:
                res["serve"] = warmup_serve(cfg)
        else:
            res = warmup_compile(cfg, include_eval=not args.no_eval)
            if args.serve:
                res["serve"] = warmup_serve(cfg)
        print(json.dumps(res))
        # nonzero when the cache was already warm is WRONG here — a warm
        # cache is the goal; rc reflects only "did warmup complete"
        return 0

    if args.cmd == "serve":
        if (args.input is None) != (args.out is None):
            raise SystemExit("serve: offline mode needs BOTH --input and "
                             "--out (neither = HTTP server mode)")
        replicas = (args.replicas if args.replicas is not None
                    else cfg.serve.fleet.replicas)
        if args.input is not None:
            if (replicas and replicas > 1) or cfg.serve.fleet.autoscale:
                raise SystemExit("serve: --replicas/--autoscale are "
                                 "HTTP-fleet only (offline mode already "
                                 "parallelizes via serve.workers)")
            from .serve.server import run_offline

            res = run_offline(cfg, args.input, args.out,
                              write_png=not args.no_png)
            print(json.dumps(res))
            return 0
        if (replicas and replicas > 1) or cfg.serve.fleet.autoscale:
            # autoscale implies fleet mode even at --replicas 1: the
            # pool needs the supervisor/router to grow from its floor
            from .serve.fleet import run_fleet

            return run_fleet(cfg, replicas)
        from .serve.server import run_server

        return run_server(cfg)

    if args.cmd == "predict":
        pairs = []
        for item in args.pairs:
            if ":" not in item:
                raise SystemExit(f"bad --pairs {item!r}: use prev.png:next.png")
            prev, nxt = item.split(":", 1)
            pairs.append((prev, nxt))
        if args.action:
            from .predict import predict_action

            labels = None
            if args.labels:
                with open(args.labels) as f:
                    labels = [ln.strip() for ln in f if ln.strip()]
            rows = predict_action(cfg, pairs, args.out, labels=labels,
                                  ckpt_dir=args.ckpt_dir)
            print(json.dumps(
                {"written": [os.path.join(args.out, "actions.json")],
                 "actions": rows}))
            return 0
        from .predict import predict_pairs

        written = predict_pairs(cfg, pairs, args.out,
                                write_png=not args.no_png,
                                precision=args.precision)
        print(json.dumps({"written": written}))
        return 0

    from .train.loop import Trainer, install_preemption_latch

    profile_steps = None
    if getattr(args, "profile_steps", None):
        try:
            k, n = (int(x) for x in args.profile_steps.split(":"))
        except ValueError:
            raise SystemExit(
                f"bad --profile-steps {args.profile_steps!r}: use K:N "
                "(start:stop global steps)")
        if not 0 <= k < n:  # same clean exit as the syntax error above
            raise SystemExit(
                f"bad --profile-steps {args.profile_steps!r}: need "
                "0 <= K < N")
        profile_steps = (k, n)
    if getattr(args, "trace", False):
        import dataclasses as _dc

        cfg = cfg.replace(obs=_dc.replace(cfg.obs, trace=True))
    if args.cmd == "train":
        # before Trainer(): model build + first compile can take minutes,
        # and a preemption SIGTERM in that window must still checkpoint
        install_preemption_latch()
        if cfg.recipe.enabled and cfg.recipe.stages:
            # staged recipe run (train/recipe.py): one Trainer per
            # stage over the curriculum's mixtures, stage index riding
            # the checkpoint manifests, pre-compiled stage executables
            from .train.recipe import run_recipe

            out = run_recipe(cfg, max_steps=args.max_steps,
                             num_epochs=args.epochs)
            print(json.dumps(out))
            return 0
    trainer = Trainer(cfg, profile=getattr(args, "profile", False),
                      profile_steps=profile_steps)
    if args.cmd == "train":
        out = trainer.fit(num_epochs=args.epochs, max_steps=args.max_steps)
        print(json.dumps({k: float(v) for k, v in out.items()}))
    else:  # eval
        res = trainer.evaluate(dump=args.dump_visuals)
        print(json.dumps({k: float(v) for k, v in res.items()}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
