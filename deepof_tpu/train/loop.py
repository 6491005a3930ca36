"""The epoch-loop trainer — one engine for every dataset/model.

Wires together mesh, dataset, prefetcher, pjit step, LR schedule,
checkpointing, NaN guard, and evaluation; dataset-agnostic where the
reference duplicates a session loop per dataset (`flyingChairsTrain.py`,
`sintelTrain.py`, `ucf101train.py` — SURVEY.md §2.2).

NaN handling upgrades the reference's crash-on-NaN assert
(`flyingChairsTrain.py:203`) to restore-from-last-checkpoint
(SURVEY.md §5.3).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import signal
import time

import numpy as np
import jax
import jax.numpy as jnp

from .. import T_PACKAGE_START
from ..core.config import ExperimentConfig
from ..data import InputPipeline, Prefetcher, build_dataset, derive_batch_rng
from ..models.registry import example_input, model_for, task_of
from ..obs import incident as obs_incident
from ..obs import trace as obs_trace
from ..obs.heartbeat import Heartbeat
from ..obs.ledger import ExecutableLedger
from ..obs.telemetry import device_memory_summary, process_rss_bytes
from ..parallel.mesh import batch_sharding, build_mesh, replicated_sharding
from ..resilience.faults import build_injector
from ..resilience.healing import HealingSampler
from ..resilience.verify import config_digest
from .checkpoint import CheckpointManager
from .evaluate import EVALUATORS
from .metrics_log import (
    FETCH_DEPTH,
    AsyncFetcher,
    MetricsLogger,
    ProfilerSession,
    StepTimer,
)
from .elastic import maybe_host_fault, pace_to_world
from .schedule import step_decay_schedule
from .state import create_train_state, make_optimizer
from .step import LAYER_METRIC_PREFIX, make_eval_fn, make_train_step
from .warmup import cache_delta, enable_for_config, install_cache_counters


# Early-preemption latch (ADVICE r03): model build + the first TPU
# compile can take minutes, and a SIGTERM landing before fit() installs
# its graceful handler would hit the default action and kill the process
# with no checkpoint. The CLI installs this minimal latch at entry; fit()
# takes over and converts a latched signal into an immediate
# save-and-stop (the loop exits before its first step, and the normal
# finalize path writes the checkpoint). Same escalation contract as the
# fit() handler: a SECOND signal restores the default action and
# re-raises, so a run wedged in compile stays killable.
_EARLY_SIGTERM: dict = {"sig": None, "handler": None}

# A prefetch.get() wait above this is counted as a `starved` step (the
# device had no staged batch to eat); below it is queue-handoff noise.
STARVED_WAIT_S = 1e-3

#: Per-pyramid-scale loss decomposition: record field -> the step
#: metrics key it reads (train/step.py stacks these per scale, finest
#: first). "Models Matter, So Does Training" (PAPERS.md): the per-scale
#: photometric-vs-smoothness trajectories are what predicts EPE — and
#: the signal ROADMAP item 3's EPE-driven curriculum switch points will
#: consume. Written into every periodic train record by _on_metrics.
#: The warp_* pair says what the data-dependent warp did at each level
#: (`ops.warp.warp_sweep_stats`): the largest row sweep of the batch (0 =
#: the level ran on XLA by shape) and 1.0 where a two-tile launch's sweep
#: was over `PALLAS_AUTO_MAX_SWEEP` and the gather took it.
SCALE_RECORD_FIELDS: tuple[tuple[str, str], ...] = (
    ("loss_total_by_scale", "scale_total"),
    ("loss_photo_by_scale", "scale_Charbonnier_reconstruct"),
    ("loss_smooth_by_scale", "scale_smooth"),
    ("warp_sweep_rows_by_scale", "scale_warp_sweep_rows"),
    ("warp_gather_fallback_by_scale", "scale_warp_gather_fallback"),
)

def per_scale_last(v) -> list[float]:
    """The step's per-scale vector (finest first) as a JSON-ready list —
    the loss_*_by_scale record fields. 6 significant figures keep the
    record compact without rounding a 1e-5-scale term to zero."""
    return [float(f"{float(x):.6g}") for x in np.atleast_1d(v)]


def _poison_batch(batch: dict) -> dict:
    """Dispatch-site fault action: one NaN in the first float input
    tensor. The batch may already be device-resident and sharded (the
    prefetcher staged it); the functional `.at[].set` keeps it there."""
    out = dict(batch)
    for key in ("volume", "source", *batch):
        if key in out and jnp.issubdtype(
                jnp.asarray(out[key]).dtype, jnp.floating):
            arr = jnp.asarray(out[key])
            out[key] = arr.at[(0,) * arr.ndim].set(jnp.nan)
            return out
    return out


def install_preemption_latch() -> None:
    def _latch(signum, frame):
        if _EARLY_SIGTERM["sig"] is not None:
            signal.signal(signal.SIGTERM, signal.SIG_DFL)
            os.kill(os.getpid(), signal.SIGTERM)
            return
        _EARLY_SIGTERM["sig"] = signum

    # remembered so fit()'s handler restore can recognize the latch and
    # NOT re-install it after training: post-fit (checkpoint already
    # committed) a SIGTERM must kill the process, not be swallowed into
    # a flag nobody reads anymore
    _EARLY_SIGTERM["handler"] = _latch
    try:
        signal.signal(signal.SIGTERM, _latch)
    except ValueError:  # non-main thread: host runtime owns signals
        pass


def data_stream_seed(mesh, seed: int, start_step: int) -> np.ndarray:
    """Base seed of the host data-sampling stream for a fit() beginning
    at start_step.

    (process_seed, start_step): process_seed decorrelates data shards
    while keeping replica peers identical (parallel/mesh.py); start_step
    gives each RESUME a fresh stream — a fixed seed would replay the
    draws already trained on, since the numpy data rng is not part of
    the checkpoint. The loop derives one rng PER BATCH INDEX from this
    base (`data/pipeline.py::derive_batch_rng`), so the sample/augment
    stream is bit-identical for any `data.num_workers`.
    """
    from ..parallel.mesh import process_seed

    return np.array([process_seed(mesh, seed), start_step], dtype=np.uint32)


def data_stream_rng(mesh, seed: int, start_step: int) -> np.random.RandomState:
    """Sequential-stream view of `data_stream_seed` (tools that sample
    without the batch-indexed pipeline, e.g. tools/synthetic_fit.py).
    Array seeding is exact and order-sensitive."""
    return np.random.RandomState(data_stream_seed(mesh, seed, start_step))


class Trainer:
    def __init__(self, cfg: ExperimentConfig, dataset=None, mesh=None,
                 profile: bool = False,
                 profile_steps: tuple[int, int] | None = None,
                 ckpt_dir: str | None = None,
                 train_step=None, eval_fn=None, tx=None,
                 manifest_extra: dict | None = None,
                 extra_stats=None, on_eval=None):
        # Span tracer from the first line of set-up (DESIGN.md
        # "Observability"): model build, the state's init, restore and
        # every compile on the way are spans of the same trace.json the
        # loop writes. `fit` takes this tracer over and flushes and
        # uninstalls it when it ends; a later `fit` starts a fresh one.
        self._tracer = self._start_tracer(cfg)
        try:
            with obs_trace.span("trainer_init"):
                self._build(cfg, dataset, mesh, profile, profile_steps,
                            ckpt_dir, train_step, eval_fn, tx,
                            manifest_extra, extra_stats, on_eval)
        except BaseException:
            # the process-global tracer must not outlive a failed build
            self._stop_tracer(self._tracer)
            raise

    @staticmethod
    def _start_tracer(cfg: ExperimentConfig):
        """Install the run's span tracer where `cfg.obs.trace` asks for
        one. Single-writer (primary process only), same rationale as
        MetricsLogger. (role, index) stamp the trace so obs/aggregate.py
        can merge an elastic pool's per-host timelines; host_index < 0
        (plain single-process training) stamps trainer-0. The process's
        set-up before it, `boot` and `import`, enters the first such
        tracer of the process."""
        if not cfg.obs.trace or jax.process_index() != 0:
            return None
        install_cache_counters()  # compiles become spans from here on
        tracer = obs_trace.install(obs_trace.Tracer(
            path=os.path.join(cfg.train.log_dir, "trace.json"),
            ring_size=cfg.obs.trace_ring, role="trainer",
            index=max(cfg.elastic.host_index, 0)))
        obs_trace.record_setup(T_PACKAGE_START, T_IMPORTS_DONE)
        return tracer

    @staticmethod
    def _stop_tracer(tracer) -> None:
        """Uninstall first (this run's tracer must not keep collecting
        from a later fit()/eval), then a best-effort flush (a read-only
        tree must not mask a body exception)."""
        if tracer is not None:
            obs_trace.uninstall()
            try:
                tracer.flush()
            except OSError:
                pass

    def _build(self, cfg, dataset, mesh, profile, profile_steps, ckpt_dir,
               train_step, eval_fn, tx, manifest_extra, extra_stats,
               on_eval) -> None:
        # The recipe engine (train/recipe.py) drives one Trainer per
        # stage through these hooks: ckpt_dir isolates each stage's
        # checkpoint lineage, train_step/eval_fn inject the stage's
        # PRE-COMPILED executables (so a stage switch is a
        # zero-recompile event provable from the ledger) — with tx the
        # EXACT optimizer object those executables were lowered against
        # (a Compiled's input pytree pins the TrainState's static tx
        # metadata by identity, so a freshly built twin would not
        # match), manifest_extra rides the active stage index on every
        # checkpoint manifest, extra_stats() merges recipe counters
        # into heartbeat/train records/fit summary, and on_eval(step,
        # metrics) -> bool ends fit() early when the stage's advance
        # trigger fires (eval_trend plateau).
        self.cfg = cfg
        self._extra_stats = extra_stats
        self._on_eval = on_eval
        # Persistent compile cache BEFORE any compile (init, train, eval):
        # a process whose config was warmed (`deepof_tpu warmup`) or simply
        # run before loads executables instead of recompiling — the
        # execution layer's "start hot" half (train/warmup.py).
        enable_for_config(cfg)
        # An elastic trainer child (train/elastic.py) in virtual-host
        # mode owns exactly elastic.virtual_devices of the forced CPU
        # platform — each member of the pool gets its own private mesh.
        el = cfg.elastic
        self._elastic_child = el.host_index >= 0 and el.num_hosts > 0
        if mesh is not None:
            self.mesh = mesh
        elif self._elastic_child and el.virtual_devices > 0:
            from ..parallel.mesh import local_mesh

            self.mesh = local_mesh(el.virtual_devices)
        else:
            self.mesh = build_mesh(cfg.mesh)
        self.dataset = dataset if dataset is not None else build_dataset(cfg.data, lm=cfg.lm)
        self.logger = MetricsLogger(cfg.train.log_dir)
        self.profiler = ProfilerSession(cfg.train.log_dir, enabled=profile,
                                        steps=profile_steps)
        self.steps_per_epoch = max(self.dataset.num_train // cfg.data.batch_size, 1)
        schedule = step_decay_schedule(cfg.optim, self.steps_per_epoch)
        self.schedule = schedule
        tx = tx if tx is not None else make_optimizer(cfg.optim, schedule)
        with obs_trace.span("model_init"):
            self.model = model_for(cfg)
            self.state = create_train_state(
                self.model, example_input(self.model, cfg), tx,
                seed=cfg.train.seed,
                log=lambda m: self.logger.log("info", 0, message=m))
        # static routes a model's layers decide by backend and shape (the
        # language model's attention: fused kernels or XLA blocks)
        routes = getattr(self.model, "routes", None)
        if routes is not None:
            self.logger.log("info", 0, message="routes", **routes())

        # Deterministic fault injector (resilience/faults.py): None when
        # disabled — every site below guards on one `is not None`, the
        # zero-overhead contract. One injector is shared by the data
        # path, the fetchers, and the checkpoint manager so per-site
        # attempt counting is globally consistent.
        self._inj = build_injector(cfg.resilience.faults)
        if self._inj is not None:
            self.logger.log("warn", 0,
                            message="fault injection ENABLED "
                                    f"({cfg.resilience.faults})")
        # Elastic children share one verified-checkpoint directory: the
        # generation's PRIMARY host writes it, every host restores from
        # it on (re)spawn — so a re-formed world resumes from one
        # consistent state and a lost primary's torn last write falls
        # back to the previous valid step (train/elastic.py).
        ckpt_dir = (ckpt_dir if ckpt_dir
                    else el.ckpt_dir if self._elastic_child and el.ckpt_dir
                    else cfg.train.log_dir + "/ckpt")
        ckpt_writer = (not self._elastic_child
                       or el.host_index == el.primary_host)
        self._ckpt_writer = ckpt_writer
        # the advisory config digest must be identical across hosts and
        # generations of ONE elastic run (only per-host identity and the
        # host-local log_dir differ), or every re-form would warn about
        # a cross-config restore
        digest_src = cfg if not self._elastic_child else cfg.replace(
            train=dataclasses.replace(cfg.train, log_dir=""),
            elastic=type(el)())
        self.ckpt = CheckpointManager(
            ckpt_dir, keep=cfg.train.keep_ckpts,
            verify=cfg.resilience.verify_checkpoints,
            log=lambda s, m: self.logger.log("warn", s, message=m),
            info_log=lambda s, m: self.logger.log("info", s, message=m),
            injector=self._inj,
            config_digest=config_digest(dataclasses.asdict(digest_src)),
            writer=ckpt_writer, manifest_extra=manifest_extra)
        # VGG16 pretrained conv-trunk init (`flyingChairsTrain.py:60-76`);
        # fresh starts only — a checkpoint to resume from takes precedence.
        trunk_path = getattr(self.model, "vgg16_trunk_path", None)
        if (cfg.train.vgg16_npz and trunk_path
                and self.ckpt.latest_step() is None):
            from ..models.common import load_vgg16_npz

            self.state = self.state.replace(params=load_vgg16_npz(
                self.state.params, cfg.train.vgg16_npz,
                trunk_path=trunk_path))
            self.logger.log("info", 0,
                            message=f"VGG16 trunk init from {cfg.train.vgg16_npz}")

        # Cross-config transfer init (Chairs -> Sintel fine-tune recipe):
        # graft matching-shape params from another run; fresh starts only.
        if cfg.train.init_from and self.ckpt.latest_step() is None:
            from .checkpoint import transfer_params

            src_params = CheckpointManager(
                cfg.train.init_from + "/ckpt", create=False,
                async_save=False).restore_raw(subtree="params")
            if src_params is None:
                raise FileNotFoundError(
                    f"train.init_from: no checkpoint under "
                    f"{cfg.train.init_from}/ckpt")
            params, n_copied, n_skipped = transfer_params(
                self.state.params, src_params)
            self.state = self.state.replace(params=params)
            self.logger.log(
                "info", 0,
                message=f"transfer init from {cfg.train.init_from}: "
                        f"{n_copied} tensors copied, {n_skipped} re-init")

        with obs_trace.span("ckpt_restore"):
            restored = self.ckpt.restore(self.state)
        if restored is not None:
            self.state = restored
            self.logger.log("info", int(self.state.step),
                            message=f"resumed from step {int(self.state.step)}")
        elif self.ckpt.latest_step() is not None:
            # checkpoints EXIST but none is restorable (every candidate
            # failed verification/restore): silently starting from step 0
            # would clobber/prune a damaged run's directory and hide the
            # corruption — refuse, with the diagnosis command
            raise RuntimeError(
                f"auto-resume: checkpoints exist under {self.ckpt.directory} "
                "but none is restorable (all candidates failed "
                "verification/restore); refusing to silently restart from "
                "scratch — run `deepof_tpu verify-ckpt "
                f"{cfg.train.log_dir}` for per-checkpoint status, then move "
                "the ckpt directory aside to intentionally start fresh")

        if jax.process_count() == 1:
            # Commit the state to the mesh BEFORE the first step. jax 0.9
            # carries the mesh in an array's type: a state that is not on
            # the mesh yet and the (replicated, on-mesh) state the step
            # returns are different input types, so the second call would
            # retrace and compile the whole step a second time.
            with obs_trace.span("state_place"):
                self.state = jax.device_put(self.state,
                                            replicated_sharding(self.mesh))

        # Sharded eval requires eval_batch_size % data-axis size == 0; adjust
        # to the nearest multiple (minimum one sample per shard) rather than
        # erroring mid-training.
        data_shards = self.mesh.shape["data"]
        eval_bs = max(cfg.train.eval_batch_size // data_shards, 1) * data_shards
        if eval_bs != cfg.train.eval_batch_size:
            self.logger.log(
                "warn", 0,
                message=f"eval_batch_size {cfg.train.eval_batch_size} not "
                        f"divisible by data axis ({data_shards}); adjusted "
                        f"to {eval_bs}")
            import dataclasses as _dc

            cfg = cfg.replace(train=_dc.replace(cfg.train,
                                                eval_batch_size=eval_bs))
            self.cfg = cfg

        spatial = self.mesh.shape.get("spatial", 1)
        if spatial > 1:
            from ..parallel.spatial import min_spatial_height, spatial_cp_active

            h = (cfg.data.crop_size or cfg.data.image_size)[0]
            down = getattr(self.model, "max_downsample", 64)
            if not spatial_cp_active(h, down, spatial):
                self.logger.log(
                    "warn", 0,
                    message=f"spatial CP inactive: H={h} fails the "
                            f"gradient-safety gate for {cfg.model} at "
                            f"spatial={spatial} (need H >= "
                            f"{min_spatial_height(down, spatial)}, H % "
                            f"{spatial} == 0, and no empty deepest-level "
                            "shard — parallel/spatial.py); those devices "
                            "only replicate work")

        smooth_border = getattr(self.model, "smooth_border_mask", False)
        self._injected_step = train_step is not None
        with obs_trace.span("step_build"):
            self.train_step = (train_step if train_step is not None else
                               make_train_step(self.model, cfg,
                                               self.dataset.mean,
                                               self.mesh, smooth_border))
            self.eval_fn = (eval_fn if eval_fn is not None else
                            make_eval_fn(self.model, cfg, self.dataset.mean,
                                         mesh=self.mesh,
                                         smooth_border_mask=smooth_border))
        if jax.process_count() > 1:
            # Multi-host eval: every host loads the same full val batch
            # (deterministic), contributes its rows to the global array,
            # and allgathers outputs so host-side AEE sees the full batch.
            from jax.experimental import multihost_utils

            from ..parallel.mesh import put_global_from_full

            raw_eval, mesh_ = self.eval_fn, self.mesh

            def eval_fn_mh(params, batch):
                batch = put_global_from_full(batch, mesh_,
                                             batch_sharding(mesh_))
                return {k: multihost_utils.process_allgather(v, tiled=True)
                        for k, v in raw_eval(params, batch).items()}

            self.eval_fn = eval_fn_mh
        self._augment = None  # set by enable_augmentation()

    def enable_augmentation(self) -> None:
        # geometric and photometric augmentation are stages for frames
        if task_of(self.model) != "lm" and (
                self.cfg.data.augment_geo or self.cfg.data.augment_photo):
            from ..data.augmentation import make_augment_fn

            self._augment = make_augment_fn(self.cfg.data)

    def _local_train_batch_size(self) -> int:
        """Rows this host loads per step. Single-process: the full batch.
        Multi-host: only the rows of this process's data-axis shards — each
        host loads 1/num_hosts of the data (SURVEY.md §5.8); hosts draw
        from decorrelated rng streams (see fit())."""
        if jax.process_count() == 1:
            return self.cfg.data.batch_size
        from ..parallel.mesh import local_batch_rows

        n, _ = local_batch_rows(self.mesh, self.cfg.data.batch_size)
        return n

    def _next_train_batch(self, it: int, rng: np.random.RandomState) -> dict:
        batch = self.dataset.sample_train(self._local_train_batch_size(), rng=rng)
        if self._augment is not None:
            batch = self._augment(batch, np.int64(rng.randint(0, 2**31)))
        return batch

    def evaluate(self, dump: bool = False) -> dict[str, float]:
        # visuals are identical on every host (replicated state): one writer
        dump = dump and jax.process_index() == 0
        dump_dir = (self.cfg.train.log_dir + "/visuals") if dump else None
        return EVALUATORS[task_of(self.model)](
            self.eval_fn, self.state.params, self.dataset, self.cfg, dump_dir)

    def fit(self, num_epochs: int | None = None,
            max_steps: int | None = None) -> dict[str, float]:
        cfg = self.cfg
        self.enable_augmentation()
        start_step = int(self.state.step)
        el = cfg.elastic
        if self._elastic_child:
            # Elastic determinism contract (train/elastic.py): the host
            # index, the CURRENT world size, and the generation are all
            # folded into the base seed — each re-form re-shards every
            # survivor onto a stream decorrelated from everything any
            # previous generation drew, and the whole run reproduces
            # from (seed, fault schedule) alone.
            from ..parallel.mesh import elastic_stream_seed

            seed_arr = elastic_stream_seed(cfg.train.seed, el.host_index,
                                           el.num_hosts, el.generation,
                                           start_step)
        else:
            seed_arr = data_stream_seed(self.mesh, cfg.train.seed,
                                        start_step)
        inj = self._inj
        # Self-healing data path (resilience/healing.py): per batch
        # index, bounded retries with backoff — the rng is RE-DERIVED per
        # attempt, so a recovered transient fault yields the bit-identical
        # batch — then quarantine + a deterministic substitute drawn from
        # the same derive_batch_rng stream (salt = redraw round). Runs
        # inside the pipeline workers, so healing parallelizes with
        # assembly for any `num_workers`.
        # warn records from the healer (worker threads, possibly a few
        # batches ahead of the loop) stamp the loop's CURRENT step — an
        # approximate but live timeline, not the fit's start step
        cur_step = {"s": start_step}
        healer = HealingSampler(
            make_rng=lambda i, rnd: derive_batch_rng(seed_arr, i, salt=rnd),
            sample=self._next_train_batch,
            retries=cfg.resilience.data_retries,
            backoff_s=cfg.resilience.data_backoff_s,
            substitutes=cfg.resilience.data_substitutes,
            injector=inj,
            log=lambda m: self.logger.log("warn", cur_step["s"], message=m))
        sharding = batch_sharding(self.mesh)

        def assemble(call_idx: int) -> dict:
            """One dispatch's input, a pure function of its index:
            batch i draws from derive_batch_rng(seed_arr, i), so the
            stream is identical for any num_workers. Runs on pipeline
            workers (or inline on the prefetch thread at num_workers=0)
            — decode and augmentation happen off the main thread. A NaN
            rollback resumes dispatching from the next
            unconsumed index (the stream continues forward, exactly like
            the pre-pipeline sequential rng did). Sample draws go
            through the HealingSampler (retry/quarantine/substitute);
            the `assemble` injection site sits above it so an injected
            assembly fault exercises the pipeline-worker retry path."""
            if inj is not None:
                inj.check("assemble", call_idx)
            return healer(call_idx)

        # --- Observability (DESIGN.md "Observability") ---
        # The span tracer `__init__` installed (a later fit of the same
        # Trainer starts a fresh one), in place BEFORE the pipeline: its
        # workers start assembling eagerly at construction, and those
        # spans belong on the timeline. Uninstalled + flushed in finally.
        primary = jax.process_index() == 0
        tracer, self._tracer = self._tracer, None
        if tracer is None:
            tracer = self._start_tracer(cfg)
        elif obs_trace.current() is not tracer:
            obs_trace.install(tracer)  # another Trainer was built since

        def _obs_teardown() -> None:
            # construction-failure path: the process-global tracer must
            # not outlive this fit (a later fit/eval would silently
            # record into the dead run's ring); flush what was collected
            self._stop_tracer(tracer)
        timer = StepTimer(cfg.data.batch_size, len(self.mesh.devices.flat))
        # Multi-worker host assembly (data/pipeline.py): N threads
        # decode/augment out-of-order, delivery stays in index
        # order through the bounded reorder buffer.
        pipeline = InputPipeline(assemble, num_workers=cfg.data.num_workers,
                                 reorder_depth=cfg.data.reorder_depth,
                                 retries=cfg.resilience.pipeline_retries,
                                 backoff_s=cfg.resilience.data_backoff_s)
        # stage=True: the next batch is transferred AND resident on
        # device while the current step executes, its wait spent
        # on the prefetch thread and accounted as the `put` phase. The
        # pipeline's workers start assembling eagerly at construction, so
        # a failure before the main try/finally takes ownership must not
        # leak the live pool.
        try:
            prefetch = Prefetcher(pipeline.get, depth=cfg.data.prefetch,
                                  sharding=sharding, stage=True,
                                  phase_cb=timer.phase)
        except BaseException:
            pipeline.close()
            _obs_teardown()
            raise
        # In-flight metrics pipelining (DESIGN.md "Execution layer"):
        # value fetches drain on a background consumer so the next
        # dispatch never waits on the previous fetch's round trip; the
        # bounded queue blocks dispatch at FETCH_DEPTH in-flight calls.
        try:
            fetcher = AsyncFetcher(
                depth=FETCH_DEPTH, timer=timer,
                retries=cfg.resilience.fetch_retries,
                backoff_s=cfg.resilience.data_backoff_s, injector=inj)
        except BaseException:  # same leak guard as the Prefetcher above
            pipeline.close()
            prefetch.close()
            _obs_teardown()
            raise

        def resilience_stats() -> dict:
            """ONE source for the prefixed data-path/fetcher/ckpt/fault
            counter merge — the heartbeat sample, every periodic train
            record, and the fit summary all call this, so the three
            surfaces can never drift apart."""
            return {**{f"data_{sk}": sv
                       for sk, sv in pipeline.stats().items()},
                    **{f"data_{sk}": sv
                       for sk, sv in prefetch.stats().items()},
                    **{f"data_{sk}": sv
                       for sk, sv in healer.stats().items()},
                    **{f"pipeline_{sk}": sv
                       for sk, sv in fetcher.stats().items()},
                    **{f"ckpt_{sk}": sv
                       for sk, sv in self.ckpt.stats().items()},
                    **({f"fault_{sk}": sv
                        for sk, sv in inj.stats().items()}
                       if inj is not None else {}),
                    **(self._extra_stats()
                       if self._extra_stats is not None else {})}
        # Liveness heartbeat + wedge watchdog (obs/heartbeat.py): a
        # background thread atomically rewrites heartbeat.json with
        # step/rates/depths/device-memory/RSS, and dumps every thread's
        # stack to the log (+ flushes the trace ring) when no step
        # completes within watchdog_factor x the median recent step time
        # — a fetch hung on a dead device becomes a diagnosable
        # artifact instead of a silent stall.
        # Executable ledger (obs/ledger.py): the live run's train-step
        # provenance row — StableHLO fingerprint, first-step compile
        # wall, persistent-cache hit/miss, cost analysis, donation map —
        # appended to <log_dir>/ledger.jsonl at the first step, from a
        # lower-only retrace (`ledger_lower`) that nothing else needs.
        # Memory-analysis fields stay None here (the jit-dispatch path
        # has no AOT Compiled object; `warmup` rows carry them).
        ledger = (ExecutableLedger(cfg.train.log_dir,
                                   backend=jax.default_backend())
                  if cfg.obs.ledger and primary else None)
        # Incident flight recorder (obs/incident.py): NaN rollbacks,
        # quarantine exhaustion, and watchdog wedges snapshot a bounded
        # diagnostic bundle; off (and structurally absent) by default.
        incidents = (obs_incident.install(cfg, cfg.train.log_dir,
                                          "trainer")
                     if primary else None)
        heartbeat = None
        if cfg.obs.heartbeat and primary:

            def _hb_sample() -> dict:
                # resilience counters ride along (skipped_updates /
                # rollbacks via timer.counters(), quarantine/retry/
                # fallback via resilience_stats) so `deepof_tpu tail`
                # sees recovery activity even between train records;
                # the exec_* ledger block does too once the first step
                # has recorded the lowering
                return {**timer.rates(), **timer.counters(),
                        **resilience_stats(),
                        **(ledger.stats() if ledger is not None else {})}

            sample_fn = (_hb_sample if incidents is None
                         else incidents.wrap_sample(_hb_sample))
            try:
                heartbeat = Heartbeat(
                    os.path.join(cfg.train.log_dir, "heartbeat.json"),
                    period_s=cfg.obs.heartbeat_period_s,
                    watchdog_factor=cfg.obs.watchdog_factor,
                    watchdog_min_s=cfg.obs.watchdog_min_s,
                    sample=sample_fn,
                    log=lambda s, m: self.logger.log("warn", s, message=m),
                    tracer=tracer,
                    on_wedge=(None if incidents is None else
                              lambda dump: incidents.record(
                                  "watchdog_wedge", "critical",
                                  text_files={"stacks.txt": dump})))
            except BaseException:  # same leak guard as above
                fetcher.close()
                pipeline.close()
                prefetch.close()
                _obs_teardown()
                raise
        # Set by the fetch callback when a fetched step is non-finite;
        # the main loop converts it into a rollback at the next boundary
        # (at most `depth` extra dispatched calls late — all discarded by
        # the checkpoint restore, so divergence handling is unchanged).
        nan_event: dict = {"m": None}
        streak = {"ok": False}  # a fetched finite step resets the NaN streak
        # Divergence-ladder rung-1 state (DESIGN.md "Resilience"): the
        # step function skips non-finite updates in place; the observed
        # skip streak escalates to a rollback only at
        # resilience.max_consecutive_skips. Counted at fetch granularity
        # (metrics are only host-visible at log/eval/ckpt boundaries).
        skip_state = {"streak": 0}
        last_eval: dict[str, float] = {}
        # Preemption-graceful stop (SURVEY.md §5.3): TPU pods get SIGTERM
        # before eviction; the reference dies losing everything since its
        # last Saver call. Here the FIRST signal just ends the step loop,
        # so the normal end-of-fit path runs: NaN-guard-checked final
        # checkpoint + async-save commit — auto-resume then continues the
        # schedule exactly. A SECOND signal escalates to the default
        # action (a run hung in prefetch.get()/compile must stay killable
        # by SIGTERM, not force an operator SIGKILL that would skip
        # finalize()). Registered only in the main thread (signal.signal
        # raises ValueError elsewhere — e.g. a trainer driven from a
        # worker thread — where the host runtime owns signal handling).
        stop_sig: dict[str, int | None] = {"sig": None}

        def _on_sigterm(signum, frame):
            if stop_sig["sig"] is not None:
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
                os.kill(os.getpid(), signal.SIGTERM)
                return
            stop_sig["sig"] = signum

        # explicit installed flag: signal.signal() returns None for a
        # previous NON-Python (C-level) handler, so None cannot double as
        # the "not installed" sentinel
        handler_installed = False
        prev_handler = None
        try:
            prev_handler = signal.signal(signal.SIGTERM, _on_sigterm)
            handler_installed = True
        except ValueError:
            pass
        # A SIGTERM latched by install_preemption_latch() before this
        # point (during model build / first compile) becomes an immediate
        # save-and-stop: the loop below exits before its first step and
        # the finalize path writes the checkpoint.
        if _EARLY_SIGTERM["sig"] is not None:
            stop_sig["sig"] = _EARLY_SIGTERM["sig"]
            _EARLY_SIGTERM["sig"] = None
        # `first_step` span: the first iteration up to its info record
        # (input wait, compile or cache load + one run, the lower-only
        # retrace); closed there, or by the finally
        first_span = contextlib.ExitStack()
        try:
            total_steps = (num_epochs or cfg.train.num_epochs) * self.steps_per_epoch
            if max_steps is not None:
                total_steps = min(total_steps, start_step + max_steps)
            if self._elastic_child and el.target_step > 0:
                # elastic runs train to an ABSOLUTE step: a respawned
                # trainer resumes from the re-form checkpoint and stops
                # where the run ends, not target-more-steps later
                total_steps = int(el.target_step)
            if cfg.train.nan_guard and self.ckpt.latest_step() is None:
                self.ckpt.save(self.state)  # rollback target before step 1
            ckpt_mark = timer.mark()
            self.profiler.maybe_start()
            first_step = True

            def _crossed(prev: int, new: int, every: int) -> bool:
                return every > 0 and prev // every != new // every

            def _on_metrics(tag, m_host):
                """Fetch-completion consumer: divergence triage + the
                train log record. Runs on the fetcher thread once the
                device values for `tag`'s step have
                ARRIVED — the honest value-fetch clock (DESIGN.md).

                The graduated ladder: updates the step fn already
                skipped in place (`update_skipped`) cost nothing beyond
                a counter until the skip streak hits
                resilience.max_consecutive_skips — then escalate to the
                checkpoint rollback. A non-finite loss whose update was
                NOT skipped means divergence reached the state: roll
                back immediately (the pre-ladder behavior)."""
                gs, ep, log_due_ = tag
                skipped = 0
                if "update_skipped" in m_host:
                    skipped = int(round(float(m_host["update_skipped"])))
                if skipped:
                    timer.count("skipped_updates", skipped)
                    skip_state["streak"] += skipped
                    self.logger.log(
                        "warn", gs,
                        message=f"non-finite grads at step {gs}: "
                                f"{skipped} update(s) skipped in place "
                                f"(state unchanged; streak "
                                f"{skip_state['streak']}/"
                                f"{cfg.resilience.max_consecutive_skips})")
                nonfinite = cfg.train.nan_guard and not np.isfinite(
                    m_host["total"])
                if nonfinite and not skipped:
                    nan_event["m"] = (gs, m_host)
                    return  # never log a diverged record
                if (skipped and cfg.train.nan_guard
                        and skip_state["streak"] >= max(
                            cfg.resilience.max_consecutive_skips, 1)):
                    # escalate skip->rollback — rollback is nan_guard
                    # machinery, so nan_guard=false keeps its pre-ladder
                    # meaning: count skips, never roll back or abort
                    nan_event["m"] = (gs, m_host)
                    return
                if not skipped:
                    skip_state["streak"] = 0
                if nonfinite:
                    return  # skipped in place: state clean, record isn't
                streak["ok"] = True
                if log_due_:
                    # input-side observability travels with every train
                    # record: pipeline queue/assemble/utilization stats,
                    # the loop's starved counter, and the decoded-image
                    # cache's hit/miss/eviction counters (alongside the
                    # compile-cache counters in the first-step record)
                    cache_s = getattr(self.dataset, "cache_stats", None)
                    cache_kw = ({f"decode_cache_{ck}": cv
                                 for ck, cv in cache_s().items()
                                 if ck in ("hits", "misses", "evictions")}
                                if cache_s is not None else {})
                    self.logger.log(
                        "train", gs, epoch=ep,
                        loss=float(m_host["total"]),
                        lr=float(self.schedule(gs - 1)),
                        grad_norm=float(m_host["grad_norm"]),
                        **{key: float(v) for key, v in m_host.items()
                           if key in ("action_loss", "accuracy")},
                        # per-pyramid-scale loss decomposition (finest
                        # first): photometric vs smoothness trajectories
                        # in every periodic record, not just the total
                        **{field: per_scale_last(m_host[src])
                           for field, src in SCALE_RECORD_FIELDS
                           if src in m_host},
                        **{key[len(LAYER_METRIC_PREFIX):]: per_scale_last(v)
                           for key, v in m_host.items()
                           if key.startswith(LAYER_METRIC_PREFIX)},
                        **timer.rates(), **timer.phases(),
                        **timer.counters(), **resilience_stats(),
                        **cache_kw, **self._telemetry())

            gstep = start_step
            final_ckpt_step = None
            consecutive_nans = 0
            metrics = None
            # Pacing floor cache: the floor only advances within a
            # generation, so while gstep stays within sync_ahead of the
            # last observed floor no file read is needed at all.
            world_floor = start_step
            while gstep < total_steps and stop_sig["sig"] is None:
                if (self._elastic_child and el.sync_ahead > 0
                        and el.world_file
                        and gstep - world_floor > el.sync_ahead):
                    # step-skew limiter (train/elastic.py): wait while
                    # this host is more than sync_ahead steps past the
                    # slowest live host — a re-form can then discard at
                    # most ckpt-cadence + sync_ahead steps. The wait
                    # touches the heartbeat: a pacing leader is healthy.
                    floor = pace_to_world(
                        el.world_file, el.generation, gstep,
                        el.sync_ahead,
                        should_stop=lambda: stop_sig["sig"] is not None,
                        touch=(heartbeat.touch if heartbeat is not None
                               else None),
                        # a coordinator dead long enough to look stale
                        # by its own verdict horizon has stopped
                        # publishing: finish as an orphan, don't block
                        stale_s=max(3 * el.poll_s, el.stale_after_s))
                    # inapplicable pacing (no file / stale generation)
                    # re-checks only after another sync_ahead steps
                    world_floor = floor if floor is not None else gstep
                    if stop_sig["sig"] is not None:
                        break
                if first_step:
                    first_span.enter_context(obs_trace.span("first_step"))
                self.profiler.observe(gstep)  # --profile-steps window
                t0 = time.perf_counter()
                with obs_trace.span("input_wait"):
                    batch = prefetch.get()
                wait = time.perf_counter() - t0
                timer.phase("assemble", wait)
                if wait > STARVED_WAIT_S:
                    # the device-facing starvation signal: the main
                    # thread (and so the next dispatch) measurably
                    # waited on the host input side
                    timer.count("starved")
                if inj is not None:
                    # dispatch-site fault: poison the staged batch with
                    # one NaN — the deterministic stand-in for "the
                    # device produced non-finite grads at this step",
                    # exercising the skip-in-place rung end to end.
                    if inj.hit("dispatch", gstep):
                        batch = _poison_batch(batch)
                        self.logger.log(
                            "warn", gstep,
                            message=f"fault injection: dispatch batch at "
                                    f"step {gstep} poisoned with NaN")
                t0 = time.perf_counter()
                if first_step:  # XLA compile-time report (SURVEY.md §5.1)
                    cache_watch = cache_delta()
                    with obs_trace.span("dispatch", step=gstep + 1,
                                        compile=True,
                                        step_trace=("train", gstep)):
                        self.state, metrics = self.train_step(self.state,
                                                              batch)
                        jax.block_until_ready(metrics["total"])
                    dc = cache_watch.stats()
                    first_wall = time.perf_counter() - t0
                    if ledger is not None and not self._injected_step:
                        self._ledger_lower(ledger, batch, first_wall, dc)
                    # hit/miss counters surfaced in metrics: a warmed
                    # process shows compile_cache_misses == 0 here
                    self.logger.log(
                        "info", gstep + 1,
                        message=f"first step (compile + run): "
                                f"{time.perf_counter() - t0:.1f}s",
                        compile_cache_requests=dc["requests"],
                        compile_cache_hits=dc["hits"],
                        compile_cache_misses=dc["misses"])
                    first_step = False
                    first_span.close()
                else:
                    with obs_trace.span("dispatch", step=gstep + 1,
                                        step_trace=("train", gstep)):
                        self.state, metrics = self.train_step(self.state,
                                                              batch)
                timer.phase("dispatch", time.perf_counter() - t0)
                timer.tick()
                prev, gstep = gstep, gstep + 1
                cur_step["s"] = gstep  # live step for healer warn records
                if heartbeat is not None:
                    heartbeat.beat(gstep)
                if inj is not None and self._elastic_child:
                    # host-level chaos (train/elastic.py): SIGKILL /
                    # wedge / preemption-SIGTERM of THIS host once its
                    # step reaches faults.host_fault_step — after the
                    # beat, so the coordinator's last observation of a
                    # killed host is the step it actually completed
                    maybe_host_fault(
                        inj, el.host_index, gstep,
                        cfg.resilience.faults.host_fault_step,
                        log=lambda m: self.logger.log(
                            "warn", gstep, message=m))
                epoch = gstep // self.steps_per_epoch
                end_of_epoch = _crossed(prev, gstep, self.steps_per_epoch)
                log_due = _crossed(prev, gstep, cfg.train.log_every) or end_of_epoch
                eval_due = end_of_epoch or _crossed(prev, gstep,
                                                    cfg.train.eval_every)

                ckpt_due = (end_of_epoch
                            and epoch % cfg.train.ckpt_every_epochs == 0)
                ckpt_due = ckpt_due or _crossed(prev, gstep,
                                                cfg.train.ckpt_every_steps)

                # One host fetch serves the NaN guard, logging, and the
                # pre-checkpoint health check (per-metric fetches would
                # each pay a transport round trip — DESIGN.md). The fetch
                # drains in the background: the next iteration's dispatch
                # proceeds while these values are still in transit.
                if log_due or eval_due or ckpt_due:
                    fetcher.submit((gstep, epoch, log_due), metrics,
                                   _on_metrics)

                # Sync points: eval and checkpoint decisions must see every
                # host-visible metric first, so divergence never reaches an
                # eval record and a NaN state is never saved as a rollback
                # target; at most log_every-1 + FETCH_DEPTH steps of NaN
                # training are lost (all rewound by the restore).
                if eval_due or ckpt_due or nan_event["m"] is not None:
                    with obs_trace.span("drain"):
                        fetcher.drain()

                if nan_event["m"] is not None:
                    # a NaN callback may land between the drain trigger
                    # above and this read; drain again (no-op when already
                    # drained) so every in-flight fetch — possibly from a
                    # step dispatched off the diverged state — lands
                    # before the rewind, never after it
                    with obs_trace.span("drain"):
                        fetcher.drain()
                    nan_step, _ = nan_event["m"]
                    nan_event["m"] = None
                    streak["ok"] = False
                    skip_state["streak"] = 0  # the rollback rewinds the run
                    timer.count("rollbacks")
                    if incidents is not None:
                        incidents.record(
                            "nan_rollback",
                            trigger={"nan_step": nan_step,
                                     "consecutive": consecutive_nans + 1})
                    self._rollback(nan_step)
                    gstep = int(self.state.step)
                    # discarded steps must not count toward throughput
                    # (rewind to the restored checkpoint's snapshot);
                    # log/eval/ckpt boundaries between the rollback
                    # target and the NaN step will re-fire as gstep
                    # re-crosses them (duplicate step records downstream)
                    timer.rewind(ckpt_mark)
                    if heartbeat is not None:
                        heartbeat.touch()  # restore device_puts took time
                    consecutive_nans += 1
                    if consecutive_nans >= 3:
                        if incidents is not None:
                            incidents.record(
                                "nan_quarantine_exhausted", "critical",
                                trigger={"step": gstep,
                                         "consecutive": consecutive_nans})
                        raise FloatingPointError(
                            f"loss diverged to NaN {consecutive_nans} "
                            f"consecutive times around step {gstep}; "
                            "rollback is not recovering — aborting")
                    continue
                if streak["ok"]:
                    streak["ok"] = False
                    consecutive_nans = 0

                if eval_due:
                    if heartbeat is not None:
                        # flush BEFORE the sweep: the first eval's XLA
                        # trace/lowering is GIL-bound Python — on a
                        # contended host it starves the heartbeat writer
                        # thread for its whole duration, and the elastic
                        # coordinator would judge the (fresh-but-frozen)
                        # file stale and evict a healthy host mid-eval.
                        # A synchronous write re-bases the supervisor's
                        # staleness clock to the eval's start (CHANGES
                        # PR 9 known-benign, fixed here; pinned in
                        # tests/test_elastic.py host_verdict timing)
                        heartbeat.touch(flush=True)
                    with obs_trace.span("eval", step=gstep):
                        last_eval = self.evaluate(dump=cfg.train.dump_visuals)
                    self.logger.log("eval", gstep, epoch=epoch, **last_eval)
                    timer.pause()  # eval time is not training throughput
                    if heartbeat is not None:
                        heartbeat.touch()  # a long sweep is not a wedge
                    if (self._on_eval is not None
                            and self._on_eval(gstep, dict(last_eval))):
                        # recipe advance trigger (train/recipe.py): end
                        # this stage's fit at the eval boundary; the
                        # normal finalize path below writes the clean
                        # final checkpoint the next stage resumes from
                        self.logger.log(
                            "info", gstep,
                            message="on_eval hook requested stop at step "
                                    f"{gstep} (stage advance trigger)")
                        break
                if ckpt_due:
                    with obs_trace.span("ckpt", step=gstep):
                        saved = self.ckpt.save(self.state)
                    if saved is not None:
                        # a DEGRADED save (disk full, injected) keeps the
                        # previous mark: a later rollback restores the
                        # last checkpoint actually written, and rewind
                        # must discard exactly the steps that restore
                        # discards — not just those since the failed save
                        ckpt_mark = timer.mark()
                    timer.pause()
                    if heartbeat is not None:
                        heartbeat.touch()
            self.profiler.maybe_stop()
            if healer.quarantine_log:
                # the run summary's quarantine listing: one info record
                # naming every quarantined draw (index, round, error) —
                # the per-event warn records carry the live timeline,
                # this is the roll-up an operator greps for
                self.logger.log(
                    "info", gstep,
                    message=f"{len(healer.quarantine_log)} sample draw(s) "
                            "quarantined and substituted this run: "
                            + "; ".join(
                                f"batch {ev['index']} round {ev['round']} "
                                f"({ev['error']})"
                                for ev in healer.quarantine_log[:20]))
            # all in-flight NaN checks land before finalize — but bounded:
            # a consumer wedged in a hung device_get must not hang
            # this path away from the finally's close()/ckpt.finalize()
            with obs_trace.span("drain"):
                drained = fetcher.drain(timeout=120.0)
            if not drained:
                self.logger.log(
                    "warn", gstep,
                    message="metrics fetch still in flight after 120s at "
                            "finalize (hung device?); final state cannot "
                            "be NaN-checked — skipping the final save")
            if stop_sig["sig"] is not None:
                self.logger.log(
                    "warn", gstep,
                    message=f"signal {stop_sig['sig']} received; stopping "
                            "after a clean final checkpoint (auto-resume "
                            "continues from here)")
            # The final state may include up to log_every-1 steps that no
            # host-visible NaN check has seen; saving it unchecked would
            # make a diverged state the newest checkpoint and defeat both
            # auto-resume and _rollback.
            final_ok = drained and nan_event["m"] is None
            if final_ok and cfg.train.nan_guard and metrics is not None:
                total = np.asarray(jax.device_get(metrics["total"]))
                final_ok = bool(np.isfinite(total).all())
                if not final_ok and "update_skipped" in metrics:
                    # a non-finite final loss whose update(s) the step fn
                    # skipped IN PLACE never reached the state — the
                    # state is clean and saving it is correct (rolling
                    # back would discard good steps for nothing)
                    sk = np.atleast_1d(np.asarray(
                        jax.device_get(metrics["update_skipped"])))
                    bad = ~np.isfinite(np.atleast_1d(total))
                    final_ok = bool(np.all(sk[bad] >= 0.5))
            if final_ok:
                # the save is named after state.step, which trails gstep
                # by every update the step fn skipped in place
                final_ckpt_step = int(jax.device_get(self.state.step))
                self.ckpt.save(self.state)  # checked once it has committed
            elif not drained:
                # hung device: the rollback below would also touch the
                # device (restore device_puts params); leave state as-is —
                # the newest committed checkpoint stays the resume point
                pass
            else:
                # don't just suppress the save: leave self.state consistent
                # with the newest (healthy) checkpoint so callers that keep
                # using the trainer don't run on diverged params
                self._rollback(gstep)
                timer.rewind(ckpt_mark)
                self.logger.log(
                    "warn", gstep,
                    message="non-finite loss at final step; state rolled "
                            "back to the last good checkpoint instead of "
                            "saving the diverged state")
        finally:
            if heartbeat is not None:
                heartbeat.close()  # writes the final heartbeat.json state
            fetcher.close()
            # pipeline BEFORE prefetch: the prefetch thread may be
            # blocked inside pipeline.get() waiting on workers, which
            # the Prefetcher's own stop event cannot interrupt —
            # closing the pipeline first releases it (its get() raise
            # is swallowed into the dying prefetch thread), so
            # prefetch.close()'s join returns promptly
            pipeline.close()
            prefetch.close()
            self.ckpt.finalize()  # commit any in-flight async save
            first_span.close()  # a fit that ended inside its first step
            self._stop_tracer(tracer)
            # restore only AFTER finalize(): the final async-save commit
            # must stay protected by the graceful handler. A C-level
            # previous handler cannot be re-installed from Python
            # (signal.signal returned None for it) — fall back to SIG_DFL
            # so the process at least stays killable. The early preemption
            # latch is likewise NOT restored: its only job was protecting
            # the pre-fit window, and re-arming it would silently swallow
            # the first SIGTERM after training completes.
            if handler_installed:
                restore = prev_handler
                if restore is None or restore is _EARLY_SIGTERM.get("handler"):
                    restore = signal.SIG_DFL
                signal.signal(signal.SIGTERM, restore)
        if (final_ckpt_step is not None and self._ckpt_writer
                and final_ckpt_step not in self.ckpt.all_steps()):
            # periodic saves may degrade (the previous checkpoint stays
            # the rollback target and the run goes on); the FINAL one is
            # the run's product — a fit whose save, or its asynchronous
            # commit in finalize(), failed must not return as if it had
            raise RuntimeError(
                f"final checkpoint (step {final_ckpt_step}) did not commit "
                f"under {self.ckpt.directory} (see the warn record above)")
        # phases + fetcher + input-pipeline stats travel with the rates:
        # bench logs show where host time went (assemble/put/dispatch/
        # fetch), how much overlap the pipelined drain achieved
        # (max_in_flight), and whether the device ever starved on host
        # batch assembly (starved / data_* worker stats).
        return {**last_eval, **timer.rates(), **timer.phases(),
                **timer.counters(),
                # resilience roll-up rides along (quarantine/retry/
                # substitute, checkpoint recovery events, fault_* when
                # injection is on) — every recovery event is visible in
                # the one-line run summary, from the same merge the
                # heartbeat and train records use
                **resilience_stats(),
                # telemetry (dev mem/rss); None-valued fields dropped —
                # the summary stays float()-able for CLI printing
                **{k: v for k, v in self._telemetry().items()
                   if v is not None}}

    def _ledger_lower(self, ledger, batch, first_wall: float,
                      cache: dict) -> None:
        """After the first step: the ledger's provenance row, from a
        lower-only retrace of the step (no second backend compile). A
        span of its own, `ledger_lower`: every process traces and lowers
        the step a second time for it before the second step.

        compile_kind="first_step": first_wall includes one EXECUTED step,
        a different unit from warmup's pure lower+compile "aot" rows —
        diff_ledgers only bounds like against like. An INJECTED
        pre-compiled step (recipe engine) is never lowered here: its
        compile already owns an "aot" row (train_step_stage<i>) and its
        first dispatch is execution, not compile — keeping the ledger a
        pure compile record is what makes "a stage switch added zero
        rows" provable from it."""
        with obs_trace.span("ledger_lower"):
            try:
                lowered = self.train_step.lower(self.state, batch)
            except Exception:  # noqa: BLE001 - the row records None
                lowered = None
            ledger.record("train_step", lowered=lowered,
                          compile_s=first_wall,
                          compile_kind="first_step", cache=cache)

    @staticmethod
    def _telemetry() -> dict:
        """Device-memory / RSS fields for a train record
        (obs/telemetry.py). Keys are schema-stable across backends:
        values the backend cannot report serialize as null in
        metrics.jsonl."""
        out = dict(device_memory_summary())
        out["rss_bytes"] = process_rss_bytes()
        return out

    def _rollback(self, step: int) -> None:
        with obs_trace.span("rollback", step=step):
            restored = self.ckpt.restore(self.state)
            if restored is None:
                # no RESTORABLE checkpoint: either none was ever written
                # or every candidate failed verification/restore.
                # Proceeding would keep training on the diverged state —
                # fail with the one fact the operator needs (where the
                # checkpoints should be / what's in that dir).
                raise FloatingPointError(
                    f"divergence at step {step} and no restorable "
                    f"checkpoint under {self.ckpt.directory} to roll back "
                    "to (none written yet, or every candidate failed "
                    "verification — run `deepof_tpu verify-ckpt "
                    f"{os.path.dirname(self.ckpt.directory)}` to see "
                    "per-checkpoint status)")
            self.state = restored
        self.logger.log("warn", step,
                        message=f"divergence at step {step}; rolled back "
                                f"to step {int(restored.step)}")


#: `time.perf_counter()` once the trainer's imports are done (this
#: module's last line): where set-up's `import` span ends
T_IMPORTS_DONE = time.perf_counter()
