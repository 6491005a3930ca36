"""Persistent compile cache + AOT warmup: start every process hot.

A cold compile of the headline train step takes minutes; XLA
compilation should be a once-per-config cost, not a once-per-process
cost:

- `enable_compile_cache()` points jax's on-disk compilation cache at
  `hostmesh.compile_cache_dir()` — `JAX_COMPILATION_CACHE_DIR` where it
  is set, else the fixed `artifacts/xla_cache/` — and installs hit/miss
  counters, so "this process compiled nothing" is a checkable fact, not
  a hope.
- `warmup_compile(cfg)` AOT-lowers and compiles the train + eval
  executables for a config from shape specs alone — no training data
  movement, no step execution — populating the cache ahead of a run
  (`python -m deepof_tpu warmup ...`).

What the cache does and doesn't persist: entries are keyed by the
lowered HLO, compile options (shardings, donation), backend, and the
jax/XLA version — a config/jax upgrade misses cleanly (recompiles,
never loads stale executables), and CPU entries never serve TPU
processes.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Any, NamedTuple

import jax
import numpy as np

from ..core.config import ExperimentConfig
from ..core.hostmesh import compile_cache_dir
from ..obs import trace as obs_trace

# jax.monitoring event names emitted by jax/_src/compiler.py for every
# compile request that consults the persistent cache, and for each hit.
# misses = requests - hits (jax emits no dedicated miss event).
_EVENT_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_EVENT_HITS = "/jax/compilation_cache/cache_hits"

_counts = {"requests": 0, "hits": 0}
_listener_installed = False

# jax.monitoring duration events (jax/_src/dispatch.py) -> the span each
# becomes in the program's trace: "which step recompiled" is an
# `xla_compile` span inside the window. The backend event covers both a
# compile and a load from the persistent cache; it is an `xla_cache_load`
# when a cache-hit event arrived since the previous backend event. Tracing
# one step fires the trace event for every jitted function inside it, and
# lowering it for every function a lowering rule traces (10,000 together):
# only a trace or lowering that is not inside another becomes a span. jax
# announces the start of each as a scalar event of the same name.
_EVENT_BACKEND = "/jax/core/compile/backend_compile_duration"
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax_lower",
    _EVENT_BACKEND: "xla_compile",
}
# per thread: .cache_hit (a hit event awaiting its backend event) and
# .depth (traces and lowerings begun and not finished)
_compiling = threading.local()


def _on_event(event: str, **kw) -> None:
    if event == _EVENT_REQUESTS:
        _counts["requests"] += 1
    elif event == _EVENT_HITS:
        _counts["hits"] += 1
        _compiling.cache_hit = True


def _on_scalar(event: str, value: float, **kw) -> None:
    if event in _COMPILE_SPANS and event != _EVENT_BACKEND:
        _compiling.depth = getattr(_compiling, "depth", 0) + 1


def _on_duration(event: str, duration: float, **kw) -> None:
    """One finished trace/lower/compile -> a span on the thread that did
    it, recorded after the fact as [now - duration, now]. With no tracer
    installed nothing is recorded."""
    now = time.perf_counter()
    name = _COMPILE_SPANS.get(event)
    if name is None:
        return
    if event != _EVENT_BACKEND:
        _compiling.depth = depth = max(getattr(_compiling, "depth", 1) - 1, 0)
        if depth:
            return  # traced or lowered inside another trace or lowering
    elif _compiling.__dict__.pop("cache_hit", False):
        name = "xla_cache_load"
    obs_trace.record_span(name, now - float(duration), now,
                          fun_name=str(kw.get("fun_name")))


def install_cache_counters() -> None:
    """Idempotently register the hit/miss counting listener and the
    listener that turns jax's compile phases into spans."""
    global _listener_installed
    if not _listener_installed:
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_scalar_listener(_on_scalar)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listener_installed = True


def cache_stats() -> dict[str, int]:
    """Cumulative process-wide counters since install_cache_counters()."""
    return {"requests": _counts["requests"], "hits": _counts["hits"],
            "misses": _counts["requests"] - _counts["hits"]}


class cache_delta:
    """Measures cache activity of a code region: requests/hits/misses
    attributable to the region (counters are process-cumulative).
    Usable as a context manager (`with cache_delta() as d: ...;
    d.stats()`) or bare (`d = cache_delta(); ...; d.stats()`) — the
    snapshot is taken at construction and refreshed by __enter__."""

    def __init__(self) -> None:
        install_cache_counters()
        self._start = cache_stats()

    def __enter__(self) -> "cache_delta":
        self._start = cache_stats()
        return self

    def __exit__(self, *exc) -> None:
        self._end = cache_stats()

    def stats(self) -> dict[str, int]:
        end = getattr(self, "_end", None) or cache_stats()
        return {k: end[k] - self._start[k] for k in end}


def enable_compile_cache(cache_dir: str | None = None,
                         min_compile_time_secs: float = 1.0) -> str:
    """Enable jax's on-disk compilation cache and the hit/miss counters.

    The directory is `hostmesh.compile_cache_dir(cache_dir)`: where
    `JAX_COMPILATION_CACHE_DIR` is set it wins and `cache_dir` is
    ignored. min_compile_time_secs stays at jax's 1 s default (the
    model/step compiles that dominate cold starts clear it on every
    backend). Safe to call repeatedly; changing the directory resets
    jax's cache singleton so the new location takes effect.
    """
    d = compile_cache_dir(cache_dir)
    os.makedirs(d, exist_ok=True)
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_time_secs)
    # jax initializes its cache singleton AT MOST ONCE per process, bound
    # to whatever dir was configured at the first compile. Any jit that
    # ran before this call (CLI/import-time helpers) trips that latch
    # with no dir and silently disables caching for the rest of the
    # process — every "writing cache entry" after that is a no-op. Drop
    # the singleton whenever it isn't already live against `d` so the
    # next compile re-initializes with the configured dir.
    from jax._src import compilation_cache as _cc

    if prev != d or _cc._cache is None:
        _cc.reset_cache()
    one_frame_locations()
    install_cache_counters()
    return d


def one_frame_locations() -> None:
    """Make a compile's cache key a function of the program, not of who
    traced it. A Pallas kernel's Mosaic payload carries the source
    location of every op, and by default a location is ten frames of the
    Python call stack of the trace: the same step traced from `cli train`,
    bench.py or an AOT lowering got a different key each (measured on the
    chip, PR 23: an AOT lowering of the step the trainer had just compiled
    missed and paid the 73 s again). ONE frame per location, the innermost,
    cures that. Through the frame limit, not by turning
    `jax_include_full_tracebacks_in_locations` off: with that off jax 0.9
    hands XLA the bare primitive as an operation's `op_name` (the
    `jax.named_scope`s are lost to a profile) and the Mosaic custom call is
    called `%tpu_custom_call.N`, not by its kernel's `name=` (read on the
    chip, PR 28)."""
    jax.config.update("jax_include_full_tracebacks_in_locations", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)


def disable_compile_cache() -> None:
    """Turn the persistent cache off, including when a previous caller in
    this process enabled it (the CPU test mesh enables unconditionally):
    unset the dir and drop jax's cache singleton so no further entries
    are read or written."""
    from jax._src import compilation_cache as _cc

    if jax.config.jax_compilation_cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", None)
        _cc.reset_cache()


def enable_for_config(cfg: ExperimentConfig) -> str | None:
    """Apply cfg.train.compile_cache / compile_cache_dir (Trainer entry).

    None = auto: on for accelerator backends, where a cold step compile
    is minutes; on cpu (tests, rehearsals: compiles of seconds) the
    ambient state is left as it is — whatever `JAX_COMPILATION_CACHE_DIR`
    or the test harness configured stays in force.
    """
    if cfg.train.compile_cache is False:  # explicit off: tear down
        disable_compile_cache()
        return None
    if cfg.train.compile_cache is None and jax.default_backend() == "cpu":
        return None
    return enable_compile_cache(cfg.train.compile_cache_dir or None)


def _sds(tree: Any, sharding=None) -> Any:
    """Pytree of arrays (host arrays or specs) -> ShapeDtypeStructs, with
    `sharding` on every leaf when given."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding,
                                       weak_type=getattr(a, "weak_type",
                                                         False)), tree)


def example_train_batch(cfg: ExperimentConfig, dataset) -> dict:
    """One host batch assembled exactly as Trainer.fit()'s producer does
    (sample -> optional augment), so the lowered avals — and therefore
    the compile-cache key — match the real first step.
    `test_warmup_then_trainer_compiles_nothing` pins this equivalence.
    """
    b = dataset.sample_train(cfg.data.batch_size,
                             rng=np.random.RandomState(0))
    if cfg.data.augment_geo or cfg.data.augment_photo:
        from ..data.augmentation import make_augment_fn

        b = make_augment_fn(cfg.data)(b, np.int64(0))
    return {key: np.asarray(v) for key, v in b.items()}


class AbstractTrainStep(NamedTuple):
    """`abstract_train_step`'s result: what `step.lower(state, batch)`
    needs, plus the model and the optimizer the state was shaped around
    (a Compiled step pins `tx` by identity: a Trainer it is injected into
    must build its state around this one)."""
    model: Any
    tx: Any
    step: Any
    state: Any
    batch: Any


def abstract_train_step(cfg: ExperimentConfig, mesh,
                        dataset) -> AbstractTrainStep:
    """The Trainer's jitted train step for `cfg` with the specs to lower
    it from.

    Nothing is allocated and the backend is not touched: the state comes
    from `eval_shape` over `create_train_state`, the batch from
    `example_train_batch`. `mesh` may be built of described devices (a
    topology with no chip attached). The specs carry the shardings the
    Trainer's arguments have — state replicated on the mesh, batch over
    "data": jax 0.9 keeps the mesh in an argument's type, and a spec
    without it lowers to a different program (different cache key) than
    the committed arrays the loop passes. This is the ONE recipe for "the
    step the Trainer would compile" — the warmup, the recipe engine,
    `chip_smoke.py` and the compile tests all lower through it, and
    `test_warmup_then_trainer_compiles_nothing` pins it to the Trainer's
    cache key.
    """
    from ..models.registry import example_input, model_for
    from ..parallel.mesh import batch_sharding, replicated_sharding
    from .schedule import step_decay_schedule
    from .state import create_train_state, make_optimizer
    from .step import make_train_step

    model = model_for(cfg)
    steps_per_epoch = max(dataset.num_train // cfg.data.batch_size, 1)
    tx = make_optimizer(cfg.optim, step_decay_schedule(cfg.optim,
                                                       steps_per_epoch))
    x = example_input(model, cfg)
    example = jax.ShapeDtypeStruct(x.shape, x.dtype)
    # abstract state: eval_shape traces create_train_state without
    # allocating params or touching the backend
    state = _sds(jax.eval_shape(
        lambda x: create_train_state(model, x, tx, seed=cfg.train.seed),
        example), replicated_sharding(mesh))
    smooth_border = getattr(model, "smooth_border_mask", False)
    step = make_train_step(model, cfg, dataset.mean, mesh, smooth_border)
    batch = _sds(example_train_batch(cfg, dataset), batch_sharding(mesh))
    return AbstractTrainStep(model, tx, step, state, batch)


def lower_train_step(cfg: ExperimentConfig, mesh=None):
    """`abstract_train_step`'s step on `mesh` (default: the config's, over
    every device), lowered but not compiled."""
    from ..data import build_dataset
    from ..parallel.mesh import build_mesh

    mesh = mesh if mesh is not None else build_mesh(cfg.mesh)
    a = abstract_train_step(cfg, mesh, build_dataset(cfg.data, lm=cfg.lm))
    return a.step.lower(a.state, a.batch)


def warmup_compile(cfg: ExperimentConfig, mesh=None, dataset=None,
                   include_eval: bool = True) -> dict:
    """AOT-compile the train (and optionally eval) executables for `cfg`.

    Pure ahead-of-time: state and batch enter as ShapeDtypeStructs
    (`jit(...).lower(specs).compile()`), so nothing executes on the
    device and no batch bytes move — only XLA runs, and its output lands
    in the persistent cache for every later process to load. Returns
    compile timings plus the cache hit/miss delta of this call: a warm
    cache shows misses == 0.
    """
    from ..data import build_dataset
    from ..parallel.mesh import build_mesh
    from .step import make_eval_fn

    enable_for_config(cfg)
    mesh = mesh if mesh is not None else build_mesh(cfg.mesh)
    dataset = dataset if dataset is not None else build_dataset(cfg.data, lm=cfg.lm)
    model, _, step, state_sds, batch_sds = abstract_train_step(cfg, mesh,
                                                               dataset)

    out: dict[str, Any] = {"model": cfg.model,
                           "backend": jax.default_backend(),
                           "cache_dir": jax.config.jax_compilation_cache_dir}
    # executable ledger (obs/ledger.py): every AOT compile below appends
    # a provenance row (StableHLO fingerprint, compile seconds, cache
    # hit/miss, cost analysis, memory footprint, donation map) to
    # <log_dir>/ledger.jsonl — the baseline a later run's `tail`/
    # ledger_diff drift verdict compares against
    from ..obs.ledger import ExecutableLedger

    ledger = ExecutableLedger(cfg.train.log_dir, enabled=cfg.obs.ledger,
                              backend=jax.default_backend())
    out["executables"] = []
    with cache_delta() as d:
        _, row = ledger.record_aot(
            "train_step", lambda: step.lower(state_sds, batch_sds))
        out["train_compile_s"] = row["compile_s"]
        out["executables"].append(_ledger_report_entry(row))

        if include_eval:
            # mirror Trainer.__init__'s eval_batch_size shard rounding so
            # the eval executable's avals match the real eval sweep
            shards = mesh.shape["data"]
            eval_bs = max(cfg.train.eval_batch_size // shards, 1) * shards
            eval_fn = make_eval_fn(
                model, cfg, dataset.mean, mesh=mesh,
                smooth_border_mask=getattr(model, "smooth_border_mask",
                                           False))
            eval_sds = _sds({key: np.asarray(v)
                             for key, v in dataset.sample_val(eval_bs, 0).items()})
            _, row = ledger.record_aot(
                "eval_step",
                lambda: eval_fn.lower(state_sds.params, eval_sds))
            out["eval_compile_s"] = row["compile_s"]
            out["executables"].append(_ledger_report_entry(row))
    out["cache"] = d.stats()
    return out


def _ledger_report_entry(row: dict) -> dict:
    """The per-executable line the warmup CLI report carries: name,
    compile seconds, fingerprint, and the compile's own cache verdict —
    a warm rerun that silently re-lowered one entry shows `misses: 1`
    (and, next run, a drifted fingerprint) right at the CLI."""
    return {"name": row["name"], "compile_s": row["compile_s"],
            "fingerprint": row["fingerprint"],
            "cache_hits": row["cache_hits"],
            "cache_misses": row["cache_misses"]}


def warmup_recipe(cfg: ExperimentConfig) -> dict:
    """AOT-compile every recipe stage's (train, eval) executable pair
    (`warmup` with recipe stages configured). One lower+compile pass
    per stage through `recipe.precompile_stages` populates the
    persistent cache and writes one `train_step_stage<i>` /
    `eval_step_stage<i>` ledger row per executable — the baseline
    ledger_diff later holds a recipe run against to prove its stage
    switches compiled nothing."""
    from .recipe import precompile_stages

    enable_for_config(cfg)
    _, report = precompile_stages(cfg)
    return report


def warmup_serve(cfg: ExperimentConfig) -> dict:
    """AOT-compile the serve ladder into the persistent cache
    (`warmup --serve`): one inference executable per configured
    (shape bucket, precision tier, dispatch mode) entry, lowered
    exactly as `serve/engine.py:_executable` lowers at runtime (shared
    `make_raw_forward`/`make_refine_forward` + `serve_avals`/
    `refine_serve_avals`, tier params avals derived through the same
    `quantize_params` transform — abstractly, via eval_shape), so a
    later engine's first request per (bucket, tier, mode) LOADS instead
    of compiling — zero first-request XLA across the whole lattice
    (pinned in tests/test_serve.py, tests/test_quant.py and
    tests/test_warm.py). The mode axis ({cold} or {cold, warm}) follows
    `serve.session.warm_start`: a warm-enabled config's FIRST warm step
    — the temporal warm-start refinement executable — is pre-lowered
    next to its cold siblings. When `obs.quality_sample_rate` > 0 the
    per-bucket label-free quality-scorer executables (obs/quality.py)
    are pre-lowered too, so sampled scoring on a cold endpoint loads
    instead of compiling.

    No checkpoint needed: params enter as ShapeDtypeStructs from an
    eval_shape of model.init — warmup compiles executables for a
    *config*, ahead of any trained weights existing.

    Each bucket entry reports ``persisted``: whether this bucket's
    executable is actually IN the on-disk cache after the call — either
    its compile wrote a new cache file, or the compile was already a
    cache hit. A compile that persists nothing (``persisted: false``,
    status ``skipped``) is the jax 1 s persistence floor at work:
    sub-second forwards (e.g. flownet_s fwd-only on this host) sit AT
    the floor and intermittently don't persist, and the floor must stay
    at jax's default (hostmesh segfault note). The zero-recompile test
    asserts against this report, not raw cache deltas — a skipped bucket
    legitimately recompiles in the next process.
    """
    import jax.numpy as jnp

    from ..obs.quality import make_score_fn, quality_avals
    from ..serve.buckets import resolve_buckets
    from ..serve.engine import (PAIR_CHANNELS, _lowered_out_hw,
                                build_refine_model, build_serve_model,
                                cold_output_hw, make_raw_forward,
                                make_refine_forward, refine_serve_avals,
                                serve_avals)
    from ..serve.quant import quantize_params, resolve_precisions

    enable_for_config(cfg)
    model = build_serve_model(cfg)
    buckets = resolve_buckets(cfg)
    tiers = resolve_precisions(cfg)
    modes = (("cold", "warm") if cfg.serve.session.warm_start
             else ("cold",))
    max_batch = max(cfg.serve.max_batch, 1)
    fwd = jax.jit(make_raw_forward(model))
    refine_model = refine_fwd = None
    if "warm" in modes:
        refine_model = build_refine_model(cfg)
        refine_fwd = jax.jit(make_refine_forward(refine_model))
    # quality-scorer executables (obs/quality.py) ride the same warmup:
    # one per bucket (tiers/modes share it — f32 in, f32 flow in), same
    # make_score_fn + quality_avals lowering the engine uses at runtime,
    # so a sampled request on a cold endpoint LOADS its scorer
    score_jit = (jax.jit(make_score_fn())
                 if float(cfg.obs.quality_sample_rate) > 0 else None)

    # executable ledger (obs/ledger.py): one provenance row per lattice
    # entry, same naming scheme the engine uses at runtime — the
    # committed-baseline side of the ledger_diff drift gate
    from ..obs.ledger import (ExecutableLedger, exec_name,
                              quality_exec_name)
    from ..serve.artifacts import (params_aval_sig, resolution_key,
                                   serve_config_digest, store_for_config,
                                   write_index)

    ledger = ExecutableLedger(cfg.train.log_dir, enabled=cfg.obs.ledger,
                              backend=jax.default_backend())
    # artifact plane (serve/artifacts.py): `warmup --serve` is the
    # SINGLE WRITER — every freshly compiled lattice entry is
    # serialized + atomically published under its StableHLO
    # fingerprint, and a re-run against a warm store fetches instead of
    # compiling (compile_kind "artifact"), which is also the publish
    # idempotence proof. Next to the per-fingerprint entries it
    # publishes the executable INDEX (atomic-rename index.json): each
    # entry's jax-free resolution key -> the fingerprint this run
    # lowered, so a later engine/replica boots the whole lattice with
    # zero trace/lower calls (serve/engine.py `_resolve_index`).
    store = store_for_config(cfg)
    cfg_digest = serve_config_digest(cfg) if store is not None else None
    index_entries: dict[str, dict] = {}

    def _index(name, row, art, params_sds, bucket, extra_meta=None):
        """Stage one index entry: only executables that are actually IN
        the store (fresh publish, prior entry, or fingerprint hit) get
        indexed — an index entry whose target is absent would be a
        stale-target reject at every boot."""
        if store is None or not row["fingerprint"]:
            return
        if art not in ("hit", "published", "exists"):
            return
        x_aval = ("__x__",
                  (max_batch, bucket[0], bucket[1], PAIR_CHANNELS),
                  "float32")
        sig = params_aval_sig(params_sds, extra=(x_aval,))
        key = resolution_key(name, cfg_digest, sig,
                             store.backend or jax.default_backend(),
                             jax.__version__)
        ent = {"name": name, "fingerprint": row["fingerprint"],
               "config_digest": cfg_digest, "aval_sig": sig,
               "backend": store.backend or jax.default_backend(),
               "jax": jax.__version__, "created": time.time()}
        if extra_meta:
            ent.update(extra_meta)
        index_entries[key] = ent

    def _aot(name, lower_fn):
        compiled, row = ledger.record_aot(name, lower_fn, artifacts=store)
        art = None
        if store is not None:
            if row["compile_kind"] == "artifact":
                art = "hit"
            elif row["fingerprint"]:
                art = store.publish(
                    row["fingerprint"], compiled, name=name,
                    compile_s=row["compile_s"],
                    meta={"donated_args": row["donated_args"],
                          "num_args": row["num_args"]})
            else:
                art = "error:no_fingerprint"
        return row, art
    out: dict[str, Any] = {"model": cfg.model, "max_batch": max_batch,
                           "backend": jax.default_backend(),
                           "cache_dir": jax.config.jax_compilation_cache_dir,
                           "tiers": list(tiers),
                           "modes": list(modes),
                           "buckets": []}
    # everything inside the delta must be the bucket executables and
    # nothing else: abstract init (eval_shape over ShapeDtypeStructs
    # executes nothing) keeps helper compiles (zeros fills, PRNG setup)
    # from polluting the hit/miss pin
    key_sds = jax.ShapeDtypeStruct((2,), np.uint32)
    cache_dir = jax.config.jax_compilation_cache_dir

    def _entries() -> set[str]:
        try:
            return set(os.listdir(cache_dir)) if cache_dir else set()
        except OSError:
            return set()

    with cache_delta() as d:
        for bucket in buckets:
            h, w = bucket
            variables_sds = jax.eval_shape(
                model.init, key_sds,
                jax.ShapeDtypeStruct((1, h, w, PAIR_CHANNELS), jnp.float32))
            refine_vars_sds = None
            if refine_model is not None:
                # the refinement stage's params AVALS, abstractly: for
                # flownet_cs this equals the checkpoint's `refine`
                # subtree by construction (same module, same scope);
                # for other models it matches the engine's seeded init
                refine_vars_sds = jax.eval_shape(
                    refine_model.init, key_sds,
                    jax.ShapeDtypeStruct((1, h, w, PAIR_CHANNELS),
                                         jnp.float32),
                    jax.ShapeDtypeStruct((1, h, w, 2), jnp.float32))
            # the cold head grid is dtype-independent: derive it ONCE
            # per bucket (one eval_shape) and share it across every
            # tier's warm entry and the bucket's quality scorer — each
            # formerly paid its own trace of the full cold network
            bucket_hw: tuple[int, int] | None = None
            for tier in tiers:
                # the tier's params AVALS through the same transform the
                # engine applies to real weights — abstract, so no
                # weight bytes materialize and no helper compiles leak
                # into the delta
                cold_tier_sds = jax.eval_shape(
                    lambda p, _t=tier: quantize_params(p, _t),
                    variables_sds["params"])
                for mode in modes:
                    before_files = _entries()
                    name = exec_name(bucket, tier, mode)
                    idx_meta = None
                    if mode == "cold":
                        params_sds, x_sds = serve_avals(
                            cold_tier_sds, bucket, max_batch)
                        row, art = _aot(
                            name,
                            lambda: fwd.lower(params_sds, x_sds))
                        index_params = cold_tier_sds
                    else:
                        refine_tier_sds = jax.eval_shape(
                            lambda p, _t=tier: quantize_params(p, _t),
                            refine_vars_sds["params"])
                        if bucket_hw is None:
                            bucket_hw = tuple(cold_output_hw(
                                fwd, cold_tier_sds, bucket, max_batch))
                        prior_hw = bucket_hw
                        params_sds, x_sds, prior_sds = refine_serve_avals(
                            refine_tier_sds, bucket, max_batch, prior_hw)

                        def lower_checked(_p=params_sds, _x=x_sds,
                                          _pr=prior_sds, _hw=prior_hw):
                            lowered = refine_fwd.lower(_p, _x, _pr)
                            # mirror the engine's prior-chain shape
                            # check off the lowering's OWN out_info —
                            # one shared `lowered` per entry across the
                            # grid check, fingerprint, ledger row, and
                            # compile (no second trace); a config the
                            # engine would reject must fail warmup
                            # identically, not silently pre-compile
                            out_hw = _lowered_out_hw(lowered)
                            if out_hw != tuple(_hw):
                                raise ValueError(
                                    f"warm_start unsupported for model "
                                    f"{cfg.model!r} at bucket {bucket}: "
                                    f"refinement head grid {out_hw} != "
                                    f"cold head grid {tuple(_hw)}")
                            return lowered

                        row, art = _aot(name, lower_checked)
                        index_params = refine_tier_sds
                        idx_meta = {"prior_hw": list(prior_hw)}
                    _index(name, row, art, index_params, bucket,
                           extra_meta=idx_meta)
                    hits = row["cache_hits"] or 0
                    # persisted = a new on-disk entry appeared
                    # (filesystem truth, not the counter's hope) OR the
                    # compile was already a hit (the entry predates this
                    # call). Neither => the 1 s floor swallowed it:
                    # compiled fine, persisted nothing.
                    wrote = bool(_entries() - before_files)
                    persisted = wrote or hits >= 1
                    entry = {"bucket": [h, w], "tier": tier, "mode": mode,
                             "compile_s": row["compile_s"],
                             "fingerprint": row["fingerprint"],
                             "persisted": persisted,
                             "status": ("hit" if hits >= 1
                                        else "persisted" if wrote
                                        else "skipped")}
                    if art is not None:
                        entry["artifact"] = art
                    out["buckets"].append(entry)
            if score_jit is not None:
                # the bucket's quality scorer: flow grid derived from
                # the DEFAULT tier's cold executable, exactly as
                # engine._score_executable derives it at runtime
                tier0_sds = jax.eval_shape(
                    lambda p: quantize_params(p, tiers[0]),
                    variables_sds["params"])
                before_files = _entries()
                if bucket_hw is None:
                    bucket_hw = tuple(cold_output_hw(
                        fwd, tier0_sds, bucket, max_batch))
                flow_hw = bucket_hw
                x_sds, flow_sds = quality_avals(bucket, flow_hw)
                row, art = _aot(
                    quality_exec_name(bucket),
                    lambda: score_jit.lower(x_sds, flow_sds))
                _index(quality_exec_name(bucket), row, art, tier0_sds,
                       bucket, extra_meta={"flow_hw": list(flow_hw)})
                hits = row["cache_hits"] or 0
                wrote = bool(_entries() - before_files)
                persisted = wrote or hits >= 1
                entry = {"bucket": [h, w], "tier": "-", "mode": "quality",
                         "compile_s": row["compile_s"],
                         "fingerprint": row["fingerprint"],
                         "persisted": persisted,
                         "status": ("hit" if hits >= 1
                                    else "persisted" if wrote
                                    else "skipped")}
                if art is not None:
                    entry["artifact"] = art
                out["buckets"].append(entry)
    out["cache"] = d.stats()
    out["persisted_buckets"] = sum(b["persisted"] for b in out["buckets"])
    out["skipped_buckets"] = sum(not b["persisted"] for b in out["buckets"])
    if store is not None:
        arts = [b.get("artifact") for b in out["buckets"]]
        out["artifacts"] = {
            "dir": store.root,
            "published": sum(1 for a in arts if a == "published"),
            "exists": sum(1 for a in arts if a == "exists"),
            "hits": sum(1 for a in arts if a == "hit"),
            "errors": sum(1 for a in arts
                          if isinstance(a, str) and a.startswith("error")),
        }
        # the executable index: ONE atomic rename after the whole
        # lattice published (readers see the old complete index until
        # the new complete one lands — never a partial lattice)
        try:
            write_index(store.root, index_entries)
            out["artifacts"]["index_entries"] = len(index_entries)
            out["artifacts"]["config_digest"] = cfg_digest
        except OSError as e:
            print(f"warmup: index publish failed: {e}", file=sys.stderr)
            out["artifacts"]["index_entries"] = 0
    return out


def deep_verify_serve(cfg: ExperimentConfig) -> dict:
    """Offline deep audit of the executable index (`deepof_tpu
    artifacts verify --deep`): re-lower every lattice entry THIS config
    would serve — the full bucket x tier x mode ladder plus quality
    scorers, exactly the `warmup_serve` lowerings — and compare each
    local StableHLO fingerprint against what the index maps that
    entry's resolution key to. This is the same check the engine's
    background deep-verify plane performs behind live serving, run
    ahead of deployment instead: ``drift`` entries are executables an
    index boot would serve stale (until demoted), ``unindexed`` ones
    would miss to the compile path. Nothing is published or repaired —
    re-run `warmup --serve` for that."""
    import jax.numpy as jnp

    from ..obs.ledger import (exec_name, fingerprint_text,
                              quality_exec_name)
    from ..obs.quality import make_score_fn, quality_avals
    from ..serve.artifacts import (params_aval_sig, resolution_key,
                                   serve_config_digest, store_for_config)
    from ..serve.buckets import resolve_buckets
    from ..serve.engine import (PAIR_CHANNELS, build_refine_model,
                                build_serve_model, cold_output_hw,
                                make_raw_forward, make_refine_forward,
                                refine_serve_avals, serve_avals)
    from ..serve.quant import quantize_params, resolve_precisions

    store = store_for_config(cfg)
    if store is None:
        raise ValueError("artifacts verify --deep needs "
                         "serve.artifacts_dir (or --dir) set")
    cfg_digest = serve_config_digest(cfg)
    model = build_serve_model(cfg)
    buckets = resolve_buckets(cfg)
    tiers = resolve_precisions(cfg)
    modes = (("cold", "warm") if cfg.serve.session.warm_start
             else ("cold",))
    max_batch = max(cfg.serve.max_batch, 1)
    fwd = jax.jit(make_raw_forward(model))
    refine_model = refine_fwd = None
    if "warm" in modes:
        refine_model = build_refine_model(cfg)
        refine_fwd = jax.jit(make_refine_forward(refine_model))
    score_jit = (jax.jit(make_score_fn())
                 if float(cfg.obs.quality_sample_rate) > 0 else None)
    backend = store.backend or jax.default_backend()
    key_sds = jax.ShapeDtypeStruct((2,), np.uint32)

    entries: list[dict] = []

    def _check(name, params_sds, bucket, lowered):
        x_aval = ("__x__",
                  (max_batch, bucket[0], bucket[1], PAIR_CHANNELS),
                  "float32")
        sig = params_aval_sig(params_sds, extra=(x_aval,))
        key = resolution_key(name, cfg_digest, sig, backend,
                             jax.__version__)
        local_fp = fingerprint_text(lowered.as_text())
        ent = store.index_entry(key) or {}
        indexed_fp = ent.get("fingerprint")
        status = ("unindexed" if indexed_fp is None
                  else "ok" if indexed_fp == local_fp else "drift")
        entries.append({"name": name, "key": key,
                        "indexed": indexed_fp, "local": local_fp,
                        "status": status})

    for bucket in buckets:
        h, w = bucket
        variables_sds = jax.eval_shape(
            model.init, key_sds,
            jax.ShapeDtypeStruct((1, h, w, PAIR_CHANNELS), jnp.float32))
        refine_vars_sds = None
        if refine_fwd is not None:
            refine_vars_sds = jax.eval_shape(
                refine_model.init, key_sds,
                jax.ShapeDtypeStruct((1, h, w, PAIR_CHANNELS),
                                     jnp.float32),
                jax.ShapeDtypeStruct((1, h, w, 2), jnp.float32))
        bucket_hw = None
        for tier in tiers:
            cold_tier_sds = jax.eval_shape(
                lambda p, _t=tier: quantize_params(p, _t),
                variables_sds["params"])
            for mode in modes:
                name = exec_name(bucket, tier, mode)
                if mode == "cold":
                    params_sds, x_sds = serve_avals(
                        cold_tier_sds, bucket, max_batch)
                    lowered = fwd.lower(params_sds, x_sds)
                    _check(name, cold_tier_sds, bucket, lowered)
                else:
                    refine_tier_sds = jax.eval_shape(
                        lambda p, _t=tier: quantize_params(p, _t),
                        refine_vars_sds["params"])
                    if bucket_hw is None:
                        bucket_hw = tuple(cold_output_hw(
                            fwd, cold_tier_sds, bucket, max_batch))
                    params_sds, x_sds, prior_sds = refine_serve_avals(
                        refine_tier_sds, bucket, max_batch, bucket_hw)
                    lowered = refine_fwd.lower(params_sds, x_sds,
                                               prior_sds)
                    _check(name, refine_tier_sds, bucket, lowered)
        if score_jit is not None:
            tier0_sds = jax.eval_shape(
                lambda p: quantize_params(p, tiers[0]),
                variables_sds["params"])
            if bucket_hw is None:
                bucket_hw = tuple(cold_output_hw(
                    fwd, tier0_sds, bucket, max_batch))
            x_sds, flow_sds = quality_avals(bucket, bucket_hw)
            lowered = score_jit.lower(x_sds, flow_sds)
            _check(quality_exec_name(bucket), tier0_sds, bucket, lowered)

    return {
        "dir": store.root,
        "backend": backend,
        "config_digest": cfg_digest,
        "entries": entries,
        "total": len(entries),
        "ok": sum(1 for e in entries if e["status"] == "ok"),
        "drift": [e["name"] for e in entries if e["status"] == "drift"],
        "unindexed": [e["name"] for e in entries
                      if e["status"] == "unindexed"],
    }
