"""Experiment configuration.

One frozen dataclass tree per experiment replaces the reference's scattered
`tf.app.flags` + hard-coded constants + placeholder-fed hyper-parameter lists
(reference `flyingChairsTrain.py:14-53`, `sintelTrain.py:13-56`,
`version1/deepOF.py:12-35`, `version1/trainOF.py:45-53`).

Presets encode the reference's published hyper-parameter baselines
(see BASELINE.md table): FlyingChairs, FlyingChairs-VGG, Sintel, UCF-101.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any

from ..resilience.faults import FaultConfig


@dataclass(frozen=True)
class LossConfig:
    """Unsupervised pyramid-loss hyper-parameters.

    Mirrors the reference's (epsilon, alpha_c, alpha_s, lambda_smooth)
    quadruple (`flyingChairsWrapFlow.py:43-46`, `sintelTrain.py:50-53`,
    `version1/trainOF.py:45-53`) plus structural switches for the smoothness
    variant and edge-aware weighting.
    """

    epsilon: float = 1e-4
    alpha_c: float = 0.25
    alpha_s: float = 0.37
    lambda_smooth: float = 1.0
    # Per-scale loss weights, finest (pr1) first — reference weight_L
    # schedules e.g. [16,8,4,2,1,1] (`flyingChairsTrain.py:165`).
    weights: tuple[float, ...] = (16.0, 8.0, 4.0, 2.0, 1.0, 1.0)
    # "canonical": fused forward-difference filter (x-grad of U, y-grad of V;
    #   `flyingChairsWrapFlow.py:854`); "depthwise": both-direction gradients
    #   per component (`version1/model/warpflow.py:133-136`).
    smoothness: str = "canonical"
    # 1 = first differences (the reference's prior); 2 = second
    # differences (opt-in): penalizes flow curvature instead of slope, so
    # affine motion fields (dominant-plane scenes) are free — a standard
    # quality knob in modern unsupervised flow.
    smoothness_order: int = 1
    # Edge-aware Sobel image-gradient weighting of the smoothness term
    # (`loss_interp_bk`, `version1/model/warpflow.py:93-157`).
    edge_aware: bool = False
    # needImageGradients (`flyingChairsWrapFlow_vgg.py:226-301`): the
    # per-sample min-max-normalized Sobel gradient MAGNITUDE of the target
    # image multiplies the Charbonnier photometric elementwise loss
    # (gradient-rich pixels emphasized) and its complement (1 - |grad|)
    # multiplies both smoothness terms (edges may move freely). Charbonnier
    # photometric, two-frame loss only (multi-frame volume configs are
    # rejected — the reference feature exists only in the vgg 2-frame
    # variant). NOTE: the reference only ever pairs this with
    # smoothness='depthwise' (the vgg variant's shape); combining it with
    # smoothness='canonical' is accepted as an EXTENSION beyond the
    # reference — strict-parity configs should set both together.
    edge_aware_photo: bool = False
    # Smooth the *scaled* flow (canonical `flyingChairsWrapFlow.py:785,854`)
    # vs the raw head output (gen-1 `version1/model/warpflow.py:37,133`).
    smooth_scaled_flow: bool = True
    border_ratio: float = 0.1
    # Warp implementation: "xla" (one fused patch-gather, any level
    # size), "pallas" (VMEM row-sweep kernel, W <= 128 only), "auto"
    # (pallas wherever admissible, xla for fine levels). Default "auto":
    # measured fastest on v5e at every admissible level shape, fwd and
    # grad (perf_probe warp section, r03; see ops/pallas/warp.py).
    warp_impl: str = "auto"
    # Warp OPERAND dtype for the photometric reconstruction gather:
    # "float32" (exact reference numerics, default) or "bfloat16" (half
    # the gathered bytes on the fine-level XLA path; ~0.4% relative
    # quantization of the warped image and its flow-gradient factors —
    # an opt-in throughput lever, see DESIGN.md).
    gather_dtype: str = "float32"
    # Photometric penalty: "charbonnier" = the reference's raw-RGB
    # Charbonnier (`flyingChairsWrapFlow.py:841-851`); "census" = soft
    # census-transform distance (ops/census.py) — illumination-robust,
    # the standard quality upgrade in modern unsupervised flow (opt-in;
    # changes the loss scale, so retune lambda_smooth/weights).
    photometric: str = "charbonnier"
    census_window: int = 7
    # Forward-backward occlusion masking (opt-in; UnFlow/UFlow lineage):
    # the model also runs on the swapped pair, and pixels failing the
    # fw/bw consistency check |f_fw + warp(f_bw)|^2 <
    # occ_alpha*(|f_fw|^2+|warp(f_bw)|^2) + occ_beta are excluded from
    # the photometric term (their appearance is unobservable in the other
    # frame). Costs a second forward pass. Flow-only 2-frame models.
    occlusion: bool = False
    occ_alpha: float = 0.01
    occ_beta: float = 0.5
    # Per-occluded-pixel penalty (added as occ_penalty * occluded interior
    # fraction). Must be > 0: with a free mask the degenerate optimum is
    # to declare hard regions occluded (UnFlow's lambda_p guard).
    occ_penalty: float = 1.0


@dataclass(frozen=True)
class OptimConfig:
    """Adam + stepwise LR decay (reference `flyingChairsTrain.py:27-33,124`)."""

    learning_rate: float = 1.6e-5
    decay_factor: float = 0.5
    epochs_per_decay: int = 18
    beta1: float = 0.9
    beta2: float = 0.999
    adam_eps: float = 1e-8
    grad_clip_norm: float | None = None
    # Accumulate gradients over N micro-batches before each optimizer
    # update (optax.MultiSteps) — effective batch = N * batch_size when
    # the global batch exceeds HBM even with remat. LR-decay boundaries
    # stay aligned to data epochs (the schedule is stretched to count
    # micro-steps).
    grad_accum: int = 1


@dataclass(frozen=True)
class DataConfig:
    dataset: str = "flyingchairs"  # flyingchairs | sintel | ucf101 | synthetic | tokens
    data_path: str = ""
    image_size: tuple[int, int] = (384, 512)  # (H, W) network input
    gt_size: tuple[int, int] = (384, 512)  # native ground-truth resolution
    batch_size: int = 4
    time_step: int = 2  # frames per sample; Sintel volumes use 10
    sintel_pass: str = "final"  # clean | final
    # Gen-1 Sintel pair-mode split (`version1/loader/sintelLoader.py:
    # 38-70`): path to Sintel_train_val.txt — one line per consecutive
    # frame pair over sorted clips x sorted frames ("1" = train,
    # "2" = val). Requires time_step=2 (the gen-1 loader is pair-only);
    # None keeps the gen-2 window-membership split.
    sintel_pair_split_file: str | None = None
    # Host-side augmentation streams (reference `flyingChairsTrain_vgg.py:186-195`):
    # photometric-augmented pair feeds the network, geometric-only feeds the loss.
    augment_geo: bool = False
    augment_photo: bool = False
    crop_size: tuple[int, int] | None = None
    prefetch: int = 2
    # Host input-pipeline worker threads (data/pipeline.py): N workers
    # decode/resize/augment batches out-of-order and
    # deliver them in order through a bounded reorder buffer, with
    # deterministic per-batch seeding — the delivered stream is
    # bit-identical for any worker count. 0 = assemble inline on the
    # prefetch thread (the legacy single-thread path, zero overhead).
    # -1 = auto (data/pipeline.py resolve_num_workers): 0 on hosts with
    # <= 2 cores — BENCH_r06 measured workers=4 at 49.5 vs workers=0 at
    # 85.3 batches/s on a small host (thread contention, nothing to
    # overlap) — else min(4, cores - 2). cv2 and the native C++ IO
    # release the GIL, so decode parallelism is real; size to the host
    # cores left over after the runtime.
    num_workers: int = 0
    # Reorder-buffer bound: how many batches workers may run ahead of
    # delivery (caps buffered-batch memory when one slow batch holds
    # back the cursor). 0 = auto (2 x num_workers). NOTE: with
    # on-device augmentation (augment_geo/augment_photo) the buffered
    # batches are DEVICE arrays, so this bound spends HBM, not host
    # RAM — at large batch, size it (and num_workers)
    # against the chip's memory headroom.
    reorder_depth: int = 0
    cache_decoded: bool = True
    # byte budget of the decoded-image LRU (host RAM). The cache stores
    # NATIVE-resolution decoded images (resize happens per batch), so the
    # full 22,872-pair FlyingChairs set (~25 GiB at 384x512) does NOT fit
    # the default — use streaming mode (cache_decoded=False) there; 4 GiB
    # pins Sintel (~1k frames/pass) and the val splits comfortably.
    cache_bytes: int = 4 << 30


@dataclass(frozen=True)
class MeshConfig:
    """Device-mesh axes for pjit sharding (no reference equivalent; the
    reference is single-GPU, `flyingChairsTrain.py:99`)."""

    data: int = -1  # -1: all available devices on the data axis
    spatial: int = 1  # spatial context-parallel shards of H
    time: int = 1  # temporal pair-parallel shards (Sintel T-1 pairs)


@dataclass(frozen=True)
class TrainConfig:
    num_epochs: int = 110
    log_every: int = 500
    eval_every: int = 5000  # steps; 0 = only at epoch end
    ckpt_every_epochs: int = 18
    # Step-granularity checkpointing (0 = epoch cadence only). The
    # reference saves only every N epochs and restarts its LR schedule on
    # resume (SURVEY.md §5.3-5.4); step cadence bounds work lost to
    # preemption to ckpt_every_steps steps.
    ckpt_every_steps: int = 0
    keep_ckpts: int = 3
    seed: int = 0
    log_dir: str = "/tmp/deepof_tpu"
    # eval protocol: finest flow is multiplied by `amplifier`, clipped, and
    # resized to gt_size before AEE (`flyingChairsTrain.py:264-296`).
    eval_amplifier: float = 2.0
    eval_clip: tuple[float, float] = (-300.0, 250.0)
    eval_batch_size: int = 8
    nan_guard: bool = True
    dump_visuals: bool = False
    # Another run's log_dir to transfer-initialize from on fresh starts:
    # params with matching path+shape are grafted (trunk transfers; pr
    # heads / first conv re-init when T differs). The Chairs->Sintel
    # fine-tune path (reference paper recipe; BASELINE.json north star).
    init_from: str = ""
    # Path to the public `vgg16_weights.npz`; when set, VGG-trunk models
    # start from these conv weights with first-layer in-channel duplication
    # (reference `flyingChairsTrain.py:60-76,142-145`, `ucf101train.py:68-88`
    # with VGG16Init=True). No auto-download (zero-egress). A restored
    # checkpoint takes precedence.
    vgg16_npz: str = ""
    compute_dtype: str = "float32"  # float32 | bfloat16
    # jax.checkpoint the model forward: recompute activations in backward
    # instead of storing them — trades FLOPs for HBM (for high-res /
    # long-T configs that would not otherwise fit).
    remat: bool = False
    # --- Latency-hiding execution layer (DESIGN.md "Execution layer") ---
    # Persistent on-disk XLA compilation cache: a process whose graphs
    # were compiled before (same config, jax/XLA version, backend) loads
    # executables instead of recompiling — minutes saved per cold start
    # of the headline step. The `warmup` CLI verb populates it ahead of
    # time (train/warmup.py). None = auto: enabled on accelerator
    # backends, left as the environment has it on cpu (compiles of
    # seconds; the test harness manages its own cache). True forces it
    # on; False forces it off.
    compile_cache: bool | None = None
    # Cache location when JAX_COMPILATION_CACHE_DIR is unset (the
    # variable always wins); "" = <repo>/artifacts/xla_cache
    # (hostmesh.compile_cache_dir).
    compile_cache_dir: str = ""


@dataclass(frozen=True)
class ObsConfig:
    """Unified observability layer (deepof_tpu/obs/): cross-thread span
    tracing, liveness heartbeat + wedge watchdog, and train-record
    telemetry. DESIGN.md "Observability" explains what each instrument
    answers."""

    # Ring-buffered span tracer: fit() writes a Perfetto/chrome://tracing
    # loadable Chrome trace-event timeline to <log_dir>/trace.json
    # (main-thread dispatch/eval/ckpt, prefetch put, fetcher fetch,
    # pipeline-worker assemble — the thread overlap made visible), from
    # the process's start: boot, import, the Trainer's set-up, every
    # compile and Pallas kernel trace, the ledger's lowering.
    trace: bool = False
    # Max retained span events (bounded memory; newest win — the window
    # leading into a stall is the one that matters).
    trace_ring: int = 16384
    # Background liveness file: <log_dir>/heartbeat.json atomically
    # rewritten every heartbeat_period_s with step, rates, queue/staged
    # depths, device memory, and process RSS — progress is one `cat`
    # (or `deepof_tpu tail`) away, even from outside the process.
    heartbeat: bool = True
    heartbeat_period_s: float = 5.0
    # Wedge watchdog: declare a stall when no step completes within
    # watchdog_factor x a robust (median) recent-step-time estimate,
    # floored by watchdog_min_s (so eval pauses / scheduler jitter never
    # fire). On a wedge: all thread stacks dumped to the metrics log,
    # trace ring flushed. Observe-and-report only — never kills the run.
    watchdog_factor: float = 20.0
    watchdog_min_s: float = 60.0
    # Executable ledger (obs/ledger.py, DESIGN.md "Executable ledger"):
    # every lowering (train step, eval, the serve bucket x tier x mode
    # lattice, quality scorers) appends a provenance row — StableHLO
    # fingerprint, compile seconds, persistent-cache hit/miss, XLA cost
    # analysis, memory footprint, donation map — to <log_dir>/
    # ledger.jsonl, and the exec_* counter block rides heartbeat +
    # /metrics. Costs nothing on the request hot path (rows are written
    # at compile time); tools/ledger_diff.py + `tail` rc 8 turn the
    # rows into a perf-regression gate against a committed baseline.
    # The trainer's row costs a second trace and lowering of the step
    # after its first run (the `ledger_lower` span); off, nothing runs.
    ledger: bool = True
    # --- Fleet observability plane (obs/export.py + obs/aggregate.py,
    # DESIGN.md "Fleet observability") ---
    # SLO latency target in ms: requests slower than this (rounded UP to
    # the nearest fixed histogram bucket bound — the bucket contract
    # that makes burn identical at every aggregation level) breach the
    # SLO, and breaches + server-side failures burn the error budget.
    # The serve engine reports `serve_slo`, the fleet router
    # `fleet_slo` (on /healthz, /metrics, heartbeat, and `tail`, which
    # exits 6 when the budget is exhausted). 0 disables the SLO layer.
    slo_latency_ms: float = 0.0
    # Allowed bad fraction (latency breaches + failures over admitted
    # requests); burn = bad_fraction / budget, exhausted at burn >= 1.
    slo_error_budget: float = 0.01
    # Standalone GET /metrics + /healthz endpoint for processes without
    # an HTTP frontend of their own (the elastic coordinator binds one
    # when set; the serve server and fleet router mount /metrics on
    # their existing ports instead). None = off; 0 = ephemeral port
    # (announced on stdout); > 0 = that port.
    metrics_port: int | None = None
    # --- Label-free flow-quality observability (obs/quality.py,
    # DESIGN.md "Quality observability") ---
    # Fraction of served requests scored with the label-free quality
    # proxy (Charbonnier photometric error on warp(frame2, flow) vs
    # frame1, census distance, flow-smoothness magnitude) OFF the hot
    # path: sampled rows go to a bounded queue + one scorer thread; a
    # full queue drops the sample (counted), never blocks a response.
    # 0 = off — the serve path is then bitwise- and schema-unchanged.
    # Sampling is deterministic in (quality_seed, request index).
    quality_sample_rate: float = 0.0
    quality_seed: int = 0
    # Scorer-queue bound: samples waiting to be scored (each holds one
    # preprocessed input row + one flow row). Full = drop-and-count.
    quality_queue_depth: int = 128
    # Drift detection: the first quality_ref_samples scored requests
    # freeze a reference median photometric proxy; afterwards a sample
    # whose photo proxy exceeds ref_p50 * quality_drift_factor is a
    # BREACH, and breaches / post-reference samples burn quality_budget
    # (the SLO error-budget pattern). Exhaustion => `tail` exit code 7.
    # quality_window bounds the rolling current-p50 window the verdict
    # reports alongside the reference.
    quality_ref_samples: int = 64
    quality_window: int = 256
    quality_drift_factor: float = 2.0
    quality_budget: float = 0.1
    # --- Incident plane (obs/incident.py, DESIGN.md "Incident plane") ---
    # Anomaly-triggered flight recorder: every verdict site (watchdog
    # wedge, fleet eviction/broken, elastic re-form/abort, SLO/quality
    # budget exhaustion, ledger drift, deep-verify demote, NaN
    # rollback) snapshots a bounded evidence bundle into
    # <log_dir>/incidents/ — trace ring, last-K heartbeats, metrics
    # tail, thread stacks, ledger rows, manifest. False (the default)
    # is a structural no-op: no recorder object exists, no incident_*
    # key enters any stats block, zero hot-path cost.
    incidents: bool = False
    # Token-bucket rate limit across ALL incident kinds: burst capacity
    # refilled at rate_per_min — a trigger storm cannot fill the disk.
    incident_rate_per_min: float = 6.0
    incident_burst: int = 3
    # Per-kind dedup: a kind that already captured within this window
    # is counted (incident_deduped), not re-captured. Also the re-fire
    # cadence of a continuously-true alert rule.
    incident_dedup_window_s: float = 300.0
    # Bundle bounds: newest metrics/ledger lines per bundle, heartbeat
    # samples ring-buffered into heartbeats.jsonl, and the committed-
    # bundle count beyond which the oldest are pruned at capture time.
    incident_metrics_tail: int = 200
    incident_heartbeats: int = 8
    incident_keep: int = 32
    # Declarative alert rules evaluated on the heartbeat cadence over
    # registry-declared counters — "[name:] [rate(]counter[)] OP value
    # [warn|critical]", e.g. "err_burst: rate(serve_errors) > 5
    # critical". A firing rule records an incident of kind
    # alert_<name>. Malformed rules and unregistered counters fail
    # loudly at process start.
    alerts: tuple[str, ...] = ()


@dataclass(frozen=True)
class FleetConfig:
    """Self-healing serving fleet (serve/fleet.py + serve/router.py,
    DESIGN.md "Fleet"): N supervised engine-replica subprocesses behind
    a health-gated router. The supervisor evicts stale/wedged replicas
    (SIGTERM then SIGKILL), respawns with exponential backoff, and stops
    respawning a crash-looping replica (circuit breaker); the router
    keeps bucket-affinity executables hot, replays failed requests on
    healthy siblings, and sheds load with structured 503s when every
    replica is saturated."""

    # replica count behind the router; 0/1 = single-process serve (the
    # `serve --replicas N` CLI flag overrides this)
    replicas: int = 0
    # supervisor health-poll cadence
    poll_s: float = 1.0
    # a READY replica whose heartbeat.json is older than this is evicted
    # (the serve heartbeat rewrites every obs.heartbeat_period_s, so
    # size this to several periods)
    stale_after_s: float = 15.0
    # supervisor-side stall detector, independent of the replica's OWN
    # wedge watchdog (which arms only after 3 completed flushes — a
    # dispatch that hangs on flush 1 or 2 would otherwise keep a fresh,
    # never-wedged heartbeat forever): evict a replica whose heartbeat
    # shows requests in flight but no completion for this long. Safe
    # against cold-start false positives because engine.warm()
    # compiles the whole bucket ladder BEFORE the replica announces, so
    # a dispatch slower than this is a hang, not a compile. Must exceed
    # the worst-case honest dispatch time; 0 disables.
    stall_after_s: float = 60.0
    # how long an announced replica may take to start listening before
    # the spawn is declared failed (covers model restore + warm compile)
    spawn_timeout_s: float = 180.0
    # eviction: SIGTERM first (graceful drain), SIGKILL after this grace
    term_grace_s: float = 5.0
    # respawn backoff: backoff_s * 2^(consecutive fast failures), capped
    backoff_s: float = 0.5
    backoff_max_s: float = 30.0
    # circuit breaker: this many CONSECUTIVE fast failures (died within
    # healthy_after_s of becoming ready, or never became ready) stops
    # respawning the replica — a crash loop burns backoff forever and
    # masks the real defect; surviving replicas keep serving
    crash_loop_threshold: int = 3
    # alive this long after ready resets the fast-failure counter
    healthy_after_s: float = 5.0
    # failover: how many times ONE request may be replayed on a
    # different replica after a transport error / replica 5xx (requests
    # are pure, so replay is idempotent by construction)
    failover_retries: int = 2
    # router-side per-replica in-flight cap: when EVERY healthy replica
    # is at this bound the request is shed with a structured 503
    # instead of queuing unboundedly at the router
    max_in_flight: int = 32
    # per-replica in-flight level above which the router spills a
    # request past its affinity replica to the next healthy one.
    # 0 = auto (serve.max_batch): below one full batch the affinity
    # replica keeps its executables hot; above it, spreading wins.
    spill_in_flight: int = 0
    # per-attempt proxy timeout (a wedged replica's request times out
    # here and replays on a sibling; the watchdog/evictor handles the
    # replica itself)
    proxy_timeout_s: float = 30.0
    # graceful shutdown: stop admission, wait this long for in-flight
    # requests to flush before reaping replicas
    drain_timeout_s: float = 10.0
    # Artifact-store GC on the retirement path (ROADMAP item 5b): every
    # graceful replica retirement and fleet close sweeps the store —
    # corrupt entries and orphaned tmp staging always go; entries older
    # than this many days also go UNLESS pinned (the index's targets
    # and every fingerprint a live replica's ledger recorded are always
    # roots, so a sweep can never collect an executable the lattice
    # boots from). <= 0 keeps the sweep corrupt/tmp-only (no age-out).
    artifacts_gc_days: float = 0.0
    # --- SLO-driven autoscaler (serve/autoscale.py, DESIGN.md
    # "Supervision plane"): the fixed `--replicas N` pool becomes a
    # load-follower between min_replicas and max_replicas, scaling up on
    # sustained shed/overload, SLO breach burn, or near-saturation
    # occupancy, and down on sustained idle — always via graceful drain
    # (retire, never evict: `tail`'s rc-4 contract stays about
    # sickness). Hysteresis lives in the threshold gap (up_occupancy >>
    # down_occupancy) + the sustain windows; the cooldowns keep the
    # respawn-compile cost of a fresh replica from flapping the pool.
    autoscale: bool = False
    # pool bounds: the autoscaler owns the size between these
    min_replicas: int = 1
    max_replicas: int = 4
    # control-loop evaluation cadence
    autoscale_period_s: float = 1.0
    # scale up only after pressure (shed/overload delta, SLO breach
    # burn, occupancy >= up threshold) persists this long
    autoscale_up_after_s: float = 2.0
    # scale down only after idleness (occupancy <= down threshold AND
    # zero shed) persists this long — much longer than the up window:
    # adding capacity late sheds traffic, removing it late wastes a
    # replica
    autoscale_down_after_s: float = 20.0
    # pool occupancy (router in-flight / (ready * max_in_flight)) at or
    # above which a tick counts as pressure
    autoscale_up_occupancy: float = 0.75
    # occupancy at or below which a tick counts as idle; the wide gap
    # to up_occupancy is the hysteresis band where the pool holds steady
    autoscale_down_occupancy: float = 0.15
    # SLO budget-burn fraction (obs.slo_latency_ms must be set) at or
    # above which NEW latency breaches count as pressure — capacity is
    # added while the budget still has headroom, not after exhaustion
    autoscale_up_slo_burn: float = 0.5
    # Predictive pressure (ISSUE 16): requests/s GROWTH (req/s per
    # second, least-squares slope over the router's per-second
    # completion buckets) at or above this counts a tick as pressure —
    # the pool scales on the load *trend*, before occupancy saturates
    # or the first shed lands. The same up_after_s sustain window and
    # cooldowns apply, so one noisy second never spawns a replica.
    # <= 0 disables the slope signal (reactive-only, the r14 behavior).
    autoscale_up_slope: float = 0.0
    # no second scale-up within this window of the previous one: a
    # burst must not spawn the whole ladder before the first new
    # replica has even compiled
    autoscale_up_cooldown_s: float = 5.0
    # no scale-down within this window of ANY scale event: a fresh
    # replica's warm-up idle must not immediately retire its sibling
    autoscale_down_cooldown_s: float = 30.0


@dataclass(frozen=True)
class DegradeConfig:
    """Brownout control plane (serve/degrade.py, DESIGN.md "Brownout"):
    under overload the fleet walks declared quality-degradation levels
    instead of shedding default-priority work —

      L0 normal -> L1 downgrade the DEFAULT precision tier (requests
      that name no `precision` serve at the cheapest configured tier)
      -> L2 additionally route to the next-smaller shape bucket (flow
      rescales to native pixels either way; only accuracy drops) ->
      L3 additionally shed low-priority requests at router admission —

    with a symmetric recovery ladder. Every (bucket, tier) pair is
    already AOT-resolved through the artifact index, so walking levels
    NEVER compiles anything (provable from the executable ledger).
    The controller is the autoscaler's fast twin: it watches the same
    live shed/occupancy/SLO-burn signals, but degrades within ~a
    second where the autoscaler takes tens of seconds to add capacity
    — degrade instantly, scale up slowly, recover when the new
    capacity actually lands (occupancy falls back under the recovery
    threshold)."""

    # master switch: off keeps the serve/fleet path byte-identical to
    # the pre-brownout stack (no controller thread, level pinned 0)
    enabled: bool = False
    # control-loop cadence — deliberately faster than
    # fleet.autoscale_period_s: degradation is the instant response,
    # capacity the slow one
    period_s: float = 0.25
    # escalate one level only after pressure (new shed/unavailable
    # rejections, occupancy >= up_occupancy, or SLO burn >=
    # up_slo_burn) persists this long
    escalate_after_s: float = 0.5
    # recover one level only after calm (zero new rejections AND
    # occupancy <= down_occupancy AND burn < up_slo_burn) persists
    # this long — much longer than the escalate window: degrading too
    # late sheds work, recovering too early flaps quality
    recover_after_s: float = 3.0
    # no second escalation within this window of the previous one (a
    # burst must not slam L0 -> L3 before L1's relief is even visible)
    escalate_cooldown_s: float = 0.5
    # no recovery within this window of ANY level transition
    recover_cooldown_s: float = 2.0
    # pool occupancy (router in-flight / (ready * fleet.max_in_flight))
    # at or above which a tick counts as pressure — the queue-depth
    # face of the verdict (router in-flight IS the fleet-wide queue)
    up_occupancy: float = 0.85
    # occupancy at or below which a tick can count as calm; the gap to
    # up_occupancy is the hysteresis band where the level holds
    down_occupancy: float = 0.5
    # SLO error-budget burn fraction (obs.slo_latency_ms must be set
    # for the signal to exist) at or above which a tick is pressure
    up_slo_burn: float = 0.7
    # highest level the controller may reach (3 = full ladder; 2 keeps
    # low-priority traffic admitted however hot the fleet runs)
    max_level: int = 3
    # `tail` exits 10 (distinct from rc 3-9) when the fleet has sat at
    # L3 continuously for at least this long — brownout as a steady
    # state means capacity never arrived
    l3_sustained_s: float = 30.0


@dataclass(frozen=True)
class SessionConfig:
    """Streaming video sessions (serve/session.py, DESIGN.md "Streaming
    sessions"): a bounded per-session cache of the last frame's decoded +
    bucket-preprocessed tensor, so `POST /v1/flow/stream` with ONE new
    frame forms the (prev, next) pair server-side — one decode and one
    preprocess per frame instead of two for a client walking a video.
    Sessions end explicitly (DELETE), by idle TTL (the sweeper), or by
    LRU pressure; every eviction is a structured `session_expired` error
    on the session's next use, never a silent drop."""

    # LRU bound on concurrently kept sessions per engine (each holds one
    # bucket-resolution float32 frame: ~H*W*12 bytes). The oldest-used
    # session past the bound is evicted with a tombstone.
    max_sessions: int = 256
    # idle TTL: a session untouched this long is expired by the sweeper
    # (and exactly on access, whichever comes first). <= 0 disables TTL
    # (sessions live until DELETE or LRU pressure).
    ttl_s: float = 120.0
    # sweeper-thread cadence; <= 0 disables the background sweep (TTL is
    # then enforced only lazily on access)
    sweep_s: float = 5.0
    # Temporal warm-start (DESIGN.md "Temporal warm-start"): keep frame
    # t's predicted flow at bucket resolution in the session and dispatch
    # step (t, t+1) through a refinement-only executable (FlowNetCS-style
    # S stage on [img1, img2, warp(img2, prior), prior, brightness_err])
    # instead of the full cold network. Adds a third executable axis —
    # (bucket, tier, cold|warm) — to the engine and `warmup --serve`.
    # Default OFF until the serve_bench --stream `epe_vs_cold` quality
    # gate passes for the deployed weights; a session's first step (and
    # any step after a re-prime/rebucket, which DROP the cached flow)
    # falls back to the cold path.
    warm_start: bool = False
    # Width multiplier of the standalone warm refinement stage relative
    # to the serving model's width (models without a trained refinement
    # stage get a deterministic seeded FlowNetRefine at width_mult *
    # warm_width; flownet_cs reuses its checkpoint's full-width refine
    # stage and ignores this). < 1 is what makes the warm path cheaper
    # than the cold network.
    warm_width: float = 0.5


@dataclass(frozen=True)
class ServeConfig:
    """Inference serving subsystem (deepof_tpu/serve/, DESIGN.md
    "Serving"): the dynamic micro-batching engine, the shape-bucket
    ladder, and the zero-dependency HTTP/offline frontends."""

    # Dynamic micro-batcher: pending requests coalesce into one batched
    # forward of up to max_batch pairs; a partial batch flushes when the
    # OLDEST pending request has waited batch_timeout_ms (latency bound).
    # Every dispatch is padded to exactly max_batch rows, so each bucket
    # owns ONE executable (no per-occupancy recompiles) and a response is
    # bit-identical whatever batch it rode in.
    max_batch: int = 8
    batch_timeout_ms: float = 10.0
    # Shape-bucket resolution ladder, (H, W) network-input sizes (model
    # stride constraints apply — multiples of 64, like data.image_size).
    # Arbitrary native inputs map to the smallest covering bucket (else
    # the largest) and flow vectors rescale back to native pixel units,
    # so the set of compiled executables is fixed and warmable
    # (`warmup --serve`). () = one bucket at data.image_size.
    buckets: tuple[tuple[int, int], ...] = ()
    # Mixed-precision serving tiers (serve/quant.py): which weight
    # precisions this endpoint offers. Each (bucket, tier) pair owns one
    # AOT executable — "f32" (checkpoint-native), "bf16" (weights cast,
    # half the weight bytes per dispatch), "int8" (weight-only
    # per-output-channel quantized conv kernels, dequantized inside the
    # forward; biases/norm params stay f32). A request's `precision`
    # field (HTTP body / predict_pairs arg) picks its tier; the FIRST
    # entry here is the default when a request names none. `warmup
    # --serve` pre-compiles the full bucket x tier ladder.
    precisions: tuple[str, ...] = ("f32",)
    # Request-queue bound: submit() blocks when this many requests are
    # pending (backpressure instead of unbounded host memory). 0 = unbounded.
    queue_depth: int = 256
    # HTTP frontend (`deepof_tpu serve`): stdlib http.server, JSON/PNG/.flo
    # responses, /healthz for the serve counters.
    host: str = "127.0.0.1"
    port: int = 8191
    # Per-request wall-clock bound the HTTP handler waits on a future
    # before answering 504 (the engine keeps working; the slot is freed).
    request_timeout_s: float = 30.0
    # Offline mode (`deepof_tpu serve --input ... --out ...`): decode
    # workers for the data/pipeline.py pool that feeds the engine.
    # 0 = decode inline on the submit thread.
    workers: int = 0
    # Testing/bench executor: when set, the engine replaces the model
    # with the deterministic fake timed executor (sleeps this many ms
    # per dispatch, flow = channel difference) — no checkpoint, no jax.
    # This is how fleet tests and `serve_bench --fleet` run replica
    # subprocesses cheaply; None = the real restored model.
    fake_exec_ms: float | None = None
    # Executable artifact store (serve/artifacts.py, DESIGN.md
    # "Artifact plane"): directory of fingerprint-keyed serialized AOT
    # executables. `warmup --serve` publishes into it (single writer);
    # engine/replica startup fetches+deserializes instead of compiling,
    # keyed by the StableHLO fingerprint of the LOCAL lowering so
    # drifted code can never load a stale artifact. "" = disabled
    # (every process compiles, the pre-r16 behavior). The path rides
    # the parent->replica config.json handoff, so fleet children and
    # autoscale spawns boot from the same store.
    artifacts_dir: str = ""
    # Trace-free boot through the store's executable index (index.json):
    # the engine resolves each lattice executable by its jax-free
    # resolution key — (exec name, config digest, aval signature,
    # backend, jax version) — with ZERO trace/lower calls; any index
    # miss/reject falls back to the fingerprint-then-compile path.
    # False = ignore the index (the r16 fingerprint-keyed boot, which
    # still pays one trace+lower per executable; serve_bench's A/B leg
    # uses this to measure the index's win). No effect when
    # artifacts_dir is empty.
    artifacts_index: bool = True
    # Deferred deep-verify plane: after an index-resolved executable
    # starts serving, a background verifier re-lowers it and compares
    # StableHLO fingerprints; on mismatch the executable is loudly
    # demoted (exec_deep_verify_demoted counter + warn record) and a
    # freshly compiled one is swapped in. False = trust the index +
    # crc gates alone (offline audits remain available via
    # `deepof_tpu artifacts verify --deep`).
    artifacts_deep_verify: bool = True
    # Deep-verify pacing: the background verifier re-lowers ONE queued
    # entry per tick of this interval instead of burning through the
    # whole lattice in a tight loop — a hundred-entry lattice must not
    # monopolize a core right after boot. 0 = no stagger (drain as
    # fast as the re-lowers run).
    deep_verify_interval_s: float = 0.05
    # Streaming video sessions (serve/session.py): POST /v1/flow/stream
    # keeps the last frame per session so consecutive pairs cost one
    # decode, not two; the router pins each session to one replica.
    session: SessionConfig = field(default_factory=SessionConfig)
    # Self-healing replica fleet (serve/fleet.py); replicas=0 keeps the
    # single-process serve path.
    fleet: FleetConfig = field(default_factory=FleetConfig)
    # Brownout control plane (serve/degrade.py): deadline-aware
    # admission + priority shedding + recompile-free quality
    # degradation under overload.
    degrade: DegradeConfig = field(default_factory=DegradeConfig)


@dataclass(frozen=True)
class ElasticConfig:
    """Elastic multi-host training (train/elastic.py, DESIGN.md "Elastic
    training"): a stdlib coordinator supervises N single-host trainer
    subprocesses and survives host loss/preemption without operator
    action. On a lost or wedged host the coordinator bumps the
    **generation**: survivors are stopped at a clean barrier (SIGTERM ->
    verified checkpoint + exit 0), the world re-forms on the survivors
    (new host count, per-host data streams re-sharded with the
    generation folded in as a salt), and every survivor respawns from
    the newest VALID checkpoint in the shared directory. Lost work is
    bounded by the checkpoint cadence.

    Two roles share this config: the COORDINATOR (`train --elastic N`;
    ``hosts`` > 1 and ``host_index`` < 0) and the per-host TRAINER
    children it spawns (``host_index`` >= 0; the coordinator serializes
    each child's exact config — world size, generation, shared ckpt
    dir — to <log_dir>/host-<i>/config.json)."""

    # coordinator world size; 0/1 = plain single-process training (the
    # `train --elastic N` CLI flag overrides this)
    hosts: int = 0
    # abort instead of re-forming below this many surviving hosts
    min_hosts: int = 1
    # --- per-child identity (written by the coordinator; -1/-0 defaults
    # mean "not an elastic child") ---
    host_index: int = -1
    num_hosts: int = 0  # current generation's world size
    generation: int = 0
    # the host that owns checkpoint WRITES this generation (the lowest
    # surviving host index); every host restores from the shared dir
    primary_host: int = 0
    # absolute global step the run trains to (elastic runs need an
    # absolute target so a respawned trainer stops where the run ends,
    # not `max_steps` further); `train --elastic N --max-steps T` sets it
    target_step: int = 0
    # shared verified-checkpoint directory ("" = <log_dir>/ckpt); the
    # primary writes it, every trainer restores from it on (re)spawn
    ckpt_dir: str = ""
    # step-skew limiter: a host pauses (heartbeat-touched, so it never
    # reads as a stall) while it is more than this many steps ahead of
    # the slowest live host (the coordinator publishes the world floor
    # to `world_file` each poll). Real synchronous data-parallel is
    # lockstepped by its collectives; virtual hosts are independent
    # processes, and unbounded skew would void the elastic guarantee
    # that lost work <= the checkpoint cadence (the furthest host's
    # uncommitted tail is what a re-form discards). The floor advances
    # at heartbeat/poll granularity, so size this to AT LEAST the steps
    # one obs.heartbeat_period_s covers or the limiter throttles
    # healthy leaders; 0 disables.
    sync_ahead: int = 4
    # path of the coordinator's world-floor file (written by the
    # coordinator into each child's config; "" = pacing off)
    world_file: str = ""
    # force this many virtual CPU devices per trainer child
    # (core/hostmesh.py) — the whole pool is testable on one host; 0 =
    # use the real backend's devices (an actual per-host accelerator)
    virtual_devices: int = 1
    # --- coordinator supervision knobs (fleet.py lineage) ---
    poll_s: float = 0.5
    # a trainer heartbeat.json older than this is a lost host (heartbeat
    # rewrites every obs.heartbeat_period_s; size to several periods)
    stale_after_s: float = 15.0
    # content-stall verdict: a host whose heartbeat shows >= 1 completed
    # step but no step/touch activity for this long is wedged (its OWN
    # watchdog needs obs.watchdog_min_s — default 60 s — and 3 beats to
    # arm; the coordinator judges earlier). Gated on beats >= 1 so the
    # first-dispatch XLA compile is never judged. 0 disables.
    wedge_after_s: float = 45.0
    # how long a spawned trainer may take to write its first heartbeat
    # (model build + restore + first allocations) before the spawn is
    # declared failed and the world re-forms without it
    spawn_timeout_s: float = 300.0
    # barrier: how long survivors get to save + exit 0 after SIGTERM
    # before SIGKILL escalation (must cover one checkpoint write)
    barrier_timeout_s: float = 120.0
    term_grace_s: float = 10.0
    # give up re-forming after this many generations (a fault that
    # keeps killing hosts is a defect to surface, not to retry forever)
    max_reforms: int = 16


@dataclass(frozen=True)
class ResilienceConfig:
    """Fault-tolerance layer (deepof_tpu/resilience/, DESIGN.md
    "Resilience"): the self-healing data path, verified checkpoints, the
    graduated divergence-recovery ladder, and the deterministic fault
    injector that chaos-tests all of them."""

    # --- self-healing data path (resilience/healing.py) ---
    # bounded retries (exponential backoff) per sample draw before the
    # draw is quarantined and replaced by a deterministic substitute
    # from the same derive_batch_rng stream (salted by the round)
    data_retries: int = 2
    data_backoff_s: float = 0.05
    # quarantine-and-redraw rounds before giving up (every redraw
    # failing means the data path is down, not one bad sample)
    data_substitutes: int = 3
    # re-attempts of a failed batch assembly on a pipeline worker
    # (make_batch is index-pure, so a retry is bit-identical)
    pipeline_retries: int = 1
    # re-attempts of a failed device->host metric value fetch
    fetch_retries: int = 2
    # --- graduated divergence recovery (train/step.py + loop.py) ---
    # rung 1: non-finite grads detected INSIDE the jitted step, before
    # the update — the update is skipped in place (state unchanged,
    # `skipped_updates` counter) instead of poisoning the params
    skip_nonfinite: bool = True
    # rung 2: escalate to the checkpoint rollback only after this many
    # consecutively observed skipped updates (rung 3 — abort — stays the
    # existing 3-failed-rollbacks ladder)
    max_consecutive_skips: int = 5
    # --- verified checkpoints (train/checkpoint.py) ---
    # validate manifests (file inventory + checksums) on restore and
    # fall back to the newest checkpoint that verifies
    verify_checkpoints: bool = True
    # --- deterministic fault injection (resilience/faults.py) ---
    # disabled by default (and then never constructed: zero overhead);
    # e.g. --set resilience.faults.enabled=true
    #      --set resilience.faults.decode_p=0.05
    faults: FaultConfig = field(default_factory=FaultConfig)


@dataclass(frozen=True)
class MixtureMemberConfig:
    """One weighted member of a stage's dataset mixture (data/mixture.py).

    Empty/zero fields inherit the stage-resolved DataConfig, so a member
    usually names only its dataset and weight. All members of a stage
    must agree on per-sample structure (shape, dtype, implied
    time_step) — validated loudly at build time, naming the stage."""

    dataset: str = "synthetic"  # flyingchairs | sintel | ucf101 | synthetic
    weight: float = 1.0
    data_path: str = ""  # "" = the stage's data.data_path
    sintel_pass: str = ""  # "" = the stage's data.sintel_pass
    time_step: int = 0  # 0 = the stage's data.time_step


@dataclass(frozen=True)
class StageConfig:
    """One stage of a training recipe (train/recipe.py): a weighted
    dataset mixture plus per-stage overrides of the base config and an
    advance condition. Sentinel values (None / 0 / empty) inherit the
    base ExperimentConfig, so a stage names only what it changes."""

    name: str = "stage"
    # weighted dataset mixture; () = the base config's single dataset
    mixture: tuple[MixtureMemberConfig, ...] = ()
    # --- per-stage config overrides (sentinels inherit the base) ---
    image_size: tuple[int, int] | None = None
    gt_size: tuple[int, int] | None = None
    crop_size: tuple[int, int] | None = None
    time_step: int = 0
    batch_size: int = 0
    model: str = ""  # e.g. the UCF-101 action stage swaps in st_single
    loss_weights: tuple[float, ...] = ()
    learning_rate: float = 0.0  # this stage's lr-schedule segment base
    # --- advance condition ---
    # "steps": advance after exactly `steps` optimizer steps.
    # "plateau": advance when the stage's eval-AEE trend (analyze.py
    #   eval_trend over this stage's evals) has flattened — slope >=
    #   -plateau_slope AEE per 1000 steps over plateau_window evals —
    #   with `steps` (when > 0) as a hard step budget backstop.
    advance: str = "steps"
    steps: int = 0  # 0 = unbounded (terminal stage / plateau-only)
    plateau_window: int = 8
    plateau_slope: float = 0.01  # flat when slope >= -this (AEE/kstep)
    min_evals: int = 3  # plateau needs at least this many stage evals


@dataclass(frozen=True)
class RecipeConfig:
    """Staged training recipe (train/recipe.py, DESIGN.md "Recipe
    engine"): an ordered list of stages, each with a deterministic
    weighted dataset mixture, per-stage shape/time_step/loss/lr
    overrides, and a fixed-step or EPE-plateau advance condition. The
    active stage index rides the checkpoint manifest so resume — plain
    or post-reform — lands in the correct stage; `warmup` pre-compiles
    every stage's executable set so a stage switch is a zero-recompile
    event provable from the executable ledger."""

    enabled: bool = False
    stages: tuple[StageConfig, ...] = ()
    # AOT pre-compile every stage's (train, eval) executables at recipe
    # start (train/recipe.py precompile_stages) so stage boundaries
    # compile nothing mid-run. False = compile lazily per stage.
    warmup: bool = True
    # eval cadence driving the plateau trigger rides the per-stage
    # train.eval_every; this caps how many stage evals the trigger
    # retains (bounded memory on very long stages)
    max_trigger_evals: int = 512


@dataclass(frozen=True)
class LMConfig:
    """A decoder-only language model of one of the FOUR families
    `models/lm/` writes, under the keys of the model's own public
    `config.json` and with their meaning. `model_type` names the family:

      - `deepseek_v3`: latent attention (`kv_lora_rank`, `qk_nope_head_dim`,
        `qk_rope_head_dim`, `v_head_dim`), sigmoid-routed experts with
        shared ones after `first_k_dense_replace` dense layers, next-token
        loss;
      - `sdar_moe`: grouped-query attention (`num_key_value_heads`,
        `head_dim`, per-head norms), softmax-routed experts with no shared
        one on every `decoder_sparse_step`-th layer not in
        `mlp_only_layers`, trained by diffusion over blocks of
        `block_length` positions;
      - `nemotron_h`: one mixer a layer, picked by the first
        `num_hidden_layers` characters of `hybrid_override_pattern` (`M` a
        Mamba-2 state-space layer, `*` grouped-query attention with no
        rotary positions and no per-head norm, `E` sigmoid-routed relu^2
        experts with a shared one), trained by diffusion over blocks.
        `n_groups` is the state-space layer's B/C groups; `n_group` the
        router's group count.
      - `afmoe`: gated grouped-query attention with per-head norms, each
        layer a window of `sliding_window` keys with rotary positions or
        a full one with none, as `layer_types` says; sandwich norms;
        after `num_dense_layers` dense layers sigmoid-routed experts with
        `num_shared_experts` shared ones; next-token loss. Its router keys
        (`score_func`, `route_norm`, `route_scale`) are read as the
        others' (`scoring_func`, `norm_topk_prob`, `routed_scaling_factor`).

    `config_file` names a JSON file of that shape: `fill_lm_from_file`
    copies every key of the file that is a field here (the file may hold
    more: a benchmark configuration keeps its notes beside the sizes), and
    gives the keys a family's config.json does not write the values its
    modeling code fixes (`LM_FAMILY_FIXED`).

    The chip's share of an expert-parallel deployment: `n_routed_experts`
    (`num_experts` in an `sdar_moe` file: ONE field) counts the experts
    HELD here, `n_routed_experts_published` is the router's width (0: all
    are held) and `first_expert` the index of the first one held. The
    layer scores and chooses over all of them, normalises over every
    chosen one and adds only what its own experts give
    (`models/lm/layers.py::MoE`). `vocab_size` is the rows of the
    embedding and of the head held here; ids, logits and loss are over them.
    """

    config_file: str = ""
    # --- published keys (defaults: a toy of the same shape, for tests) ---
    model_type: str = "deepseek_v3"
    vocab_size: int = 256
    hidden_size: int = 64
    intermediate_size: int = 128  # the leading dense layers' SwiGLU width
    moe_intermediate_size: int = 32  # one routed expert's width
    num_hidden_layers: int = 3
    num_attention_heads: int = 4
    q_lora_rank: int | None = None  # None: queries are not compressed
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    n_routed_experts: int = 8
    n_shared_experts: int = 2
    num_experts_per_tok: int = 2
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_interleave: bool = True
    rope_scaling: Any = None
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 32768
    # published by `sdar_moe` (a `deepseek_v3` file's are read by nothing)
    num_key_value_heads: int = 2
    head_dim: int = 16
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    use_sliding_window: bool = False
    # published by `afmoe` (read by no other family): each layer's
    # "sliding_attention" or "full_attention", the window's keys, the
    # embedding times sqrt(hidden_size)
    layer_types: tuple = ()
    sliding_window: int | None = None
    mup_enabled: bool = False
    # published by `nemotron_h` (the other families' files write none)
    hybrid_override_pattern: str = ""
    mamba_num_heads: int = 4
    mamba_head_dim: int = 8
    ssm_state_size: int = 16
    n_groups: int = 2
    conv_kernel: int = 4
    chunk_size: int = 8
    mamba_hidden_act: str = "silu"
    mamba_proj_bias: bool = False
    use_conv_bias: bool = True
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    time_step_limit: tuple = (0.0, None)  # (0, inf): nothing clamped
    # "": the experts are SwiGLU of `hidden_act`; "relu2": W_down relu(W_up h)^2
    mlp_hidden_act: str = ""
    # the shared expert's width (0: n_shared_experts x moe_intermediate_size)
    moe_shared_expert_intermediate_size: int = 0
    # --- the share held here ---
    n_routed_experts_published: int = 0
    first_expert: int = 0
    # the published layer each layer is, where a cut keeps some and not
    # the first ones (empty: layer i is published layer i)
    published_layers: tuple = ()
    # --- not in config.json: the job's own sizes ---
    seq_len: int = 32  # positions a row trains on (a row holds seq_len + 1 ids)
    init_std: float = 0.02  # normal initialisation of every matrix
    # ... but the embedding: at init_std the causal mean that attention
    # adds to every position alike is several times a token's own vector,
    # every token then routes alike and one expert takes every slot
    embed_std: float = 1.0
    bias_std: float = 0.01  # e_score_correction_bias: drawn once, then fixed
    attn_block_q: int = 512  # queries a block of the attention (the fused
    # kernels' query block on a TPU, `ops/attention.py`); scores never exist
    # for more than one block, and the backward recomputes them
    loss_block: int = 2048  # positions a block of the head and the loss
    # ... of training by diffusion over blocks (arXiv:2503.09573; neither
    # is in an `sdar_moe` config.json): positions a block; the id a noised
    # position shows (None: the family has none; the token dataset never
    # draws it); a block's share of noised positions is U(t_lo, t_hi)
    block_length: int = 4
    mask_token_id: int | None = None
    noise_t_lo: float = 0.45
    noise_t_hi: float = 0.95


#: What a family's config.json does not write because its modeling code
#: fixes it, under the names the other family publishes it by: the router
#: (`sdar_moe`: softmax over all experts, the k largest, no bias buffer, no
#: scaling), no shared expert, no leading dense layer, rotary channel j
#: against j + head_dim / 2.
LM_FAMILY_FIXED: dict[str, dict] = {
    "deepseek_v3": {},
    "sdar_moe": dict(scoring_func="softmax", topk_method="greedy",
                     n_shared_experts=0, first_k_dense_replace=0,
                     moe_layer_freq=1, routed_scaling_factor=1.0,
                     rope_interleave=False),
    # the router: sigmoid scores, the k largest of score + its buffer
    "nemotron_h": dict(scoring_func="sigmoid", topk_method="noaux_tc",
                       first_k_dense_replace=0, moe_layer_freq=1,
                       rope_interleave=False),
    # the router: sigmoid scores (`score_func`), the k largest of score +
    # its buffer; rotary channel j against j + head_dim / 2
    "afmoe": dict(topk_method="noaux_tc", moe_layer_freq=1,
                  rope_interleave=False),
}
#: one field, two published names
_LM_KEY_ALIASES = {"num_experts": "n_routed_experts",
                   "layer_norm_epsilon": "rms_norm_eps",
                   "num_dense_layers": "first_k_dense_replace",
                   "num_shared_experts": "n_shared_experts",
                   "score_func": "scoring_func",
                   "route_norm": "norm_topk_prob",
                   "route_scale": "routed_scaling_factor"}


def lm_family_config(model_type: str, lm: LMConfig | None = None,
                     **keys) -> LMConfig:
    """`lm` (a toy where None) as a model of family `model_type`: what the
    family fixes, then `keys`. An unknown family is refused by name."""
    if model_type not in LM_FAMILY_FIXED:
        raise ValueError(f"lm.model_type={model_type!r} is not a family "
                         f"models/lm writes: {sorted(LM_FAMILY_FIXED)}")
    return dataclasses.replace(lm or LMConfig(), model_type=model_type,
                               **{**LM_FAMILY_FIXED[model_type], **keys})


def fill_lm_from_file(lm: LMConfig, path: str) -> LMConfig:
    """`lm` with every key of the JSON file at `path` that names a field,
    as a model of the file's `model_type`."""
    import json

    with open(path) as f:
        d = json.load(f)
    d = {_LM_KEY_ALIASES.get(k, k): v for k, v in d.items()}
    names = {f.name for f in dataclasses.fields(LMConfig)} - {"config_file"}
    keys = {k: tuple(v) if isinstance(v, list) else v
            for k, v in d.items() if k in names}
    return lm_family_config(keys.pop("model_type", lm.model_type), lm,
                            config_file=path, **keys)


@dataclass(frozen=True)
class ExperimentConfig:
    name: str = "flyingchairs_flownet_s"
    # any models/registry.py name: flownet_s | vgg16 | inception_v3 |
    # flownet_c | flownet_cs | st_single | st_baseline | ucf101_spatial |
    # latent_moe_lm (sized by the `lm` section)
    model: str = "flownet_s"
    # Thin-variant channel multiplier — honored by models declaring a
    # width_mult field (flownet_s, flownet_c; the parity backbones keep
    # their exact reference widths and build_model rejects non-default
    # values for them by name). 1.0 = reference widths; the test suite
    # uses 0.25 so full-train-step wiring checks don't pay 38M-param
    # compute on the CPU mesh.
    width_mult: float = 1.0
    # FlowNet-C/CS correlation cost-volume geometry. The displacement
    # bins live on the 1/8-resolution conv3 grid: bin granularity =
    # 8 * corr_stride image pixels, search radius ~ 8 * max_disp image
    # pixels. Size them to the expected flow at that grid — a task whose
    # displacements fit inside ONE bin is architecturally invisible to
    # the correlation (DESIGN.md r04: for 8 px flows at 64 px images the
    # working setting was corr_stride=1, corr_max_disp=3; the defaults
    # match the FlowNet paper's 320x448 large-displacement regime).
    corr_max_disp: int = 20
    corr_stride: int = 2
    loss: LossConfig = field(default_factory=LossConfig)
    optim: OptimConfig = field(default_factory=OptimConfig)
    data: DataConfig = field(default_factory=DataConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)
    elastic: ElasticConfig = field(default_factory=ElasticConfig)
    recipe: RecipeConfig = field(default_factory=RecipeConfig)
    lm: LMConfig = field(default_factory=LMConfig)

    def replace(self, **kw: Any) -> "ExperimentConfig":
        return dataclasses.replace(self, **kw)


# --- Presets: reference hyper-parameter baselines (BASELINE.md) ---

FLYINGCHAIRS = ExperimentConfig(
    name="flyingchairs_inception",
    model="inception_v3",
    loss=LossConfig(epsilon=1e-4, alpha_c=0.25, alpha_s=0.37, lambda_smooth=1.0,
                    weights=(16, 8, 4, 2, 1, 1)),
    optim=OptimConfig(learning_rate=1.6e-5, epochs_per_decay=18),
    # network input 320x448 (`deepOF.py:22`), GT kept native 384x512
    # (`flyingChairsLoader.py:74-81`); eval resizes pr1*2 back to gt_size.
    data=DataConfig(dataset="flyingchairs", image_size=(320, 448),
                    gt_size=(384, 512), batch_size=4),
    train=TrainConfig(num_epochs=110, ckpt_every_epochs=18,
                      eval_amplifier=2.0, eval_clip=(-300.0, 250.0)),
)

FLYINGCHAIRS_VGG = ExperimentConfig(
    name="flyingchairs_vgg",
    model="vgg16",
    loss=LossConfig(epsilon=1e-4, alpha_c=0.25, alpha_s=0.37, lambda_smooth=1.0,
                    weights=(16, 8, 4, 2, 1), smoothness="depthwise"),
    optim=OptimConfig(learning_rate=1.6e-5, epochs_per_decay=18),
    data=DataConfig(dataset="flyingchairs", image_size=(320, 448),
                    gt_size=(384, 512), batch_size=8,
                    augment_geo=True, augment_photo=True),
    # pr1 is half the final flow: x2 before clip (`flyingChairsTrain_vgg.py:291-292`)
    train=TrainConfig(num_epochs=110, eval_amplifier=2.0,
                      eval_clip=(-204.4790, 201.3478)),
)

SINTEL = ExperimentConfig(
    name="sintel_inception_multiframe",
    model="inception_v3",
    loss=LossConfig(epsilon=1e-4, alpha_c=0.3, alpha_s=0.3, lambda_smooth=0.0,
                    weights=(16, 8, 4, 4, 2, 1)),
    optim=OptimConfig(learning_rate=1.6e-5, epochs_per_decay=60),
    data=DataConfig(dataset="sintel", image_size=(256, 512), gt_size=(436, 1024),
                    crop_size=(224, 480), batch_size=4, time_step=10,
                    sintel_pass="final"),
    train=TrainConfig(num_epochs=400, ckpt_every_epochs=30, eval_amplifier=3.0,
                      eval_clip=(-420.621, 426.311)),
)

UCF101 = ExperimentConfig(
    name="ucf101_st_single",
    model="st_single",
    loss=LossConfig(epsilon=1e-4, alpha_c=0.25, alpha_s=0.37, lambda_smooth=0.8,
                    weights=(16, 8, 4, 2, 1)),
    optim=OptimConfig(learning_rate=1.6e-4, epochs_per_decay=50),
    # gen-2 entry trains 320x384 (`deepOF.py:19`), 1000 epochs (`ucf101train.py:50`)
    data=DataConfig(dataset="ucf101", image_size=(320, 384),
                    gt_size=(320, 384), batch_size=8),
    train=TrainConfig(num_epochs=1000, eval_amplifier=1.0, eval_clip=(-1e9, 1e9)),
)


# A language model (`models/lm/`, four families: `lm.model_type`) on rows of
# `lm.seq_len` + 1 ids, by the family's own objective (next-token
# cross-entropy, or diffusion over blocks). The sizes come from `--set lm.config_file=FILE`
# (a JSON file of the public config.json's shape) or `--set lm.<key>=...`;
# the defaults are a toy. Constant learning rate, no clipping: what a
# config.json does not give is the job's to set.
LM = ExperimentConfig(
    name="lm_latent_moe",
    model="latent_moe_lm",
    optim=OptimConfig(learning_rate=1e-4, decay_factor=1.0),
    data=DataConfig(dataset="tokens", batch_size=2),
    train=TrainConfig(num_epochs=1, log_every=50, eval_every=0,
                      eval_batch_size=2, remat=True),
)

# gen-1 per-model loss-weight alternates (`version1/trainOF.py:76-87`),
# selectable via LossConfig.weights overrides.
GEN1_LOSS_WEIGHTS = {
    "vgg16": (7.0, 5.0, 3.0, 3.0, 1.0),
    "flownet_s": (9.0, 7.0, 5.0, 3.0, 3.0, 1.0),
    "inception_v3": (9.0, 7.0, 5.0, 3.0, 3.0, 1.0),
}

PRESETS: dict[str, ExperimentConfig] = {
    "flyingchairs": FLYINGCHAIRS,
    "flyingchairs_vgg": FLYINGCHAIRS_VGG,
    "sintel": SINTEL,
    "ucf101": UCF101,
    "lm": LM,
}


def get_config(name: str, **overrides: Any) -> ExperimentConfig:
    cfg = PRESETS[name]
    return cfg.replace(**overrides) if overrides else cfg


# --- JSON round-trip: the fleet's parent->replica config handoff ---


def _tupleize(value: Any) -> Any:
    """JSON arrays -> the tuples the frozen config tree uses (nested:
    serve.buckets round-trips as a tuple of tuples)."""
    if isinstance(value, list):
        return tuple(_tupleize(v) for v in value)
    return value


def _from_dict(cls: type, d: dict, path: str = "") -> Any:
    import typing

    unknown = set(d) - {f.name for f in dataclasses.fields(cls)}
    if unknown:
        where = path or cls.__name__
        raise ValueError(
            f"config_from_dict: unknown field(s) {sorted(unknown)} in "
            f"{where}")
    hints = typing.get_type_hints(cls)
    kwargs: dict[str, Any] = {}
    for f in dataclasses.fields(cls):
        if f.name not in d:
            continue  # absent fields keep their defaults (older dumps)
        value = d[f.name]
        hint = hints.get(f.name)
        where = f"{path}.{f.name}" if path else f.name
        if dataclasses.is_dataclass(hint) and isinstance(value, dict):
            value = _from_dict(hint, value, where)
        elif (typing.get_origin(hint) is tuple
              and typing.get_args(hint)
              and dataclasses.is_dataclass(typing.get_args(hint)[0])
              and isinstance(value, (list, tuple))):
            # tuple-of-dataclass fields (recipe.stages, stage.mixture):
            # each element recurses with an indexed path so unknown-key
            # rejection names the exact offending entry
            elem = typing.get_args(hint)[0]
            value = tuple(
                _from_dict(elem, v, f"{where}[{i}]")
                if isinstance(v, dict) else _tupleize(v)
                for i, v in enumerate(value))
        else:
            value = _tupleize(value)
        kwargs[f.name] = value
    return cls(**kwargs)


def config_from_dict(d: dict) -> ExperimentConfig:
    """Inverse of `dataclasses.asdict` + JSON for the config tree:
    rebuilds the nested frozen dataclasses and re-tuples JSON arrays.
    `serve/fleet.py` serializes the parent's exact config to each
    replica's `config.json` and the replica loads it via the CLI's
    `serve --config-json` — replicas must serve the same ladder and the
    same fault schedule as the supervisor intended, not a preset
    re-derivation. Unknown keys are rejected AT EVERY LEVEL (a typo'd
    field must not silently become its default); missing keys keep
    their defaults so older dumps load."""
    return _from_dict(ExperimentConfig, d)


def recipe_from_dict(d: dict) -> RecipeConfig:
    """Strict dict -> RecipeConfig for the `train --recipe FILE` payload
    (train/recipe.py): the same unknown-key rejection as
    `config_from_dict`, at every nesting level — a typo in
    `stages[i].mixture[j]` fails with the exact indexed path, never a
    silently-defaulted field."""
    return _from_dict(RecipeConfig, d, "recipe")
