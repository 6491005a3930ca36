"""InferenceEngine: a dynamic micro-batching flow-inference engine.

The serial predict path pays one device round-trip per image pair and
jits ad hoc; under concurrent load that is the whole throughput story.
This engine owns the restored (verified) params and amortizes dispatch:

  submit() threads enqueue preprocessed requests -> a single batcher
  thread coalesces the queue into one batched forward per flush (up to
  `serve.max_batch` pairs, or whatever arrived within
  `serve.batch_timeout_ms` of the oldest pending request) -> per-request
  futures resolve with postprocessed native-resolution flow.

Design decisions that matter:

  - Every dispatch is padded to EXACTLY max_batch rows (zeros beyond the
    live occupancy, outputs sliced). One bucket therefore owns one
    executable — occupancy 1..max_batch never triggers a recompile — and
    a response is bitwise independent of which batch it rode in, so the
    batched path is bit-identical to the serial path at the same bucket
    (pinned in tests/test_serve.py).
  - Executables are AOT-compiled (`jit(...).lower(avals).compile()`)
    through the PR 1 persistent compile cache; `warmup --serve` runs the
    identical lowering per (bucket, tier) ahead of time, so a cold
    engine's first requests LOAD executables instead of compiling
    (compile-cache counters pinned in tests).
  - Precision is a request axis (serve/quant.py): each configured tier
    (f32 / bf16 weight-cast / int8 weight-only per-channel quantized)
    owns its own params tree and its own executable per bucket, and the
    batcher groups by (bucket, tier) — a request's `precision` field
    picks its operating point on the speed/accuracy frontier without
    touching its batchmates.
  - Decode/preprocess runs on the SUBMITTING thread (cv2 releases the
    GIL): a corrupt or undecodable input fails that one future with a
    structured ServeError before it ever reaches the batcher — a
    poisoned request cannot wedge the engine or fail its batchmates.
  - A failure inside the batched forward fails that flush's requests
    (structured `dispatch_failed`) and the batcher keeps serving; a
    per-request postprocess failure fails only that request.

  - Streaming video sessions (serve/session.py): `submit_next(session,
    frame)` keeps the last frame's preprocessed half-row per session and
    forms the (prev, next) pair server-side — one decode + one
    preprocess per frame instead of two for the video walk, with
    bitwise-identical flow to the pairwise path (prepare_pair is the
    concat of two per-frame preprocesses). The store is LRU + TTL
    bounded; a dead session's next frame is a structured
    `session_expired` the client re-primes from.
  - Temporal warm-start (`serve.session.warm_start`, DESIGN.md
    "Temporal warm-start"): the session additionally keeps the last
    step's RESOLVED flow (raw finest-head output), and a step that has one
    dispatches a refinement-only executable — FlowNetRefine
    (models/flownet2.py) on [img1, img2, warp(img2, prior), prior,
    brightness_err] — instead of the full cold network. The executable
    lattice gains a third axis: `_compiled` is keyed (bucket, tier,
    cold|warm), the batcher groups warm steps exactly like a tier
    switch, and `warmup --serve` pre-lowers the whole bucket x tier x
    mode lattice. A step with no prior (first step, or any step after a
    re-prime/rebucket dropped it) falls back cold — counted as
    `serve_sessions_cold_fallbacks` next to `serve_sessions_warm_steps`.
    Custom/fake executors are warm-blind (one forward fn, no refinement
    weights): warm steps still group/count/trace as warm, but execute
    the same function — grouping and bookkeeping testable without jax.

Observability: trace spans (serve_enqueue / serve_batch /
serve_dispatch / serve_postprocess, session_prime / session_step) on
the shared obs tracer, and a `serve_*` counter block (queue depth,
batch occupancy, p50/p99 latency, requests/s, the serve_sessions_*
streaming axis) exposed via stats()/heartbeat_sample() for the serve
heartbeat and `deepof_tpu tail`.
"""

from __future__ import annotations

import itertools
import queue
import sys
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Callable

import numpy as np

from ..core.config import ExperimentConfig
from ..obs import trace as obs_trace
from ..obs.ledger import ExecutableLedger, exec_name, quality_exec_name
from ..obs.export import (LatencyHistogram, percentile_ms, slo_state,
                          validate_slo)
from ..obs.quality import (QualityScorer, make_score_fn, quality_avals,
                           score_pair_np)
from .buckets import (flow_to_native, next_smaller_bucket, pick_bucket,
                      prepare_frame,
                      prepare_pair, resolve_buckets)
from .quant import dequantize_params, quantize_params, resolve_precisions
from .session import SessionExpired, SessionStore

_STOP = object()

#: Latency samples retained for the p50/p99 estimate (newest window).
_LATENCY_WINDOW = 2048
#: Seconds of completion history behind the requests/s figure.
_RATE_WINDOW_S = 10.0


class ServeError(RuntimeError):
    """Structured per-request failure: machine-readable `code` +
    human-readable message, JSON-ready via payload(). Codes:
    bad_input (decode/preprocess), dispatch_failed (the batched forward
    raised — the whole flush fails), postprocess_failed (one request's
    resize/rescale raised), engine_closed, bad_request (server-side),
    session_expired (a streaming session was TTL-expired or LRU-evicted
    — the client re-primes; serve/session.py), deadline_exceeded (the
    caller's propagated X-Deadline-Ms budget expired before dispatch —
    fail-fast instead of occupying a padded batch slot; HTTP 504)."""

    def __init__(self, code: str, message: str,
                 request_id: int | str | None = None):
        super().__init__(message)
        self.code = code
        self.request_id = request_id

    def payload(self) -> dict:
        out = {"error": self.code, "message": str(self)}
        if self.request_id is not None:
            out["request_id"] = self.request_id
        return out


class _Request:
    __slots__ = ("x", "bucket", "tier", "native_hw", "future", "t_enq",
                 "rid", "session", "frame_index", "mode", "prior",
                 "session_epoch", "score", "deadline")

    def __init__(self, x, bucket, tier, native_hw, future, t_enq, rid,
                 session=None, frame_index=None, mode="cold", prior=None,
                 session_epoch=None, deadline=None):
        self.x = x
        self.bucket = bucket
        self.tier = tier
        self.native_hw = native_hw
        self.future = future
        self.t_enq = t_enq
        self.rid = rid
        # streaming-session step provenance (serve/session.py): the
        # session id + 0-based frame index, echoed in the response and
        # observed into the per-session-frame latency histogram
        self.session = session
        self.frame_index = frame_index
        # temporal warm-start: mode "warm" dispatches the refinement
        # executable with `prior` = the session's cached flow (a prior
        # dispatch's raw finest-head output; always None for mode "cold")
        self.mode = mode
        self.prior = prior
        # the session's prime-generation at advance() time: the
        # writeback token set_flow guards on (None off-session)
        self.session_epoch = session_epoch
        # label-free quality sampling (obs/quality.py): set at enqueue
        # by the deterministic sampler; a sampled request's (input,
        # raw flow) pair is handed to the off-path scorer at resolve
        self.score = False
        # absolute time.monotonic() the caller's budget expires (None =
        # no deadline): checked at enqueue backpressure and again at
        # flush, so a doomed request fails fast with deadline_exceeded
        # instead of occupying a padded batch slot
        self.deadline = deadline

    @property
    def key(self) -> tuple[tuple[int, int], str, str]:
        """The dispatch-group identity: requests batch together iff they
        share (bucket, tier, mode) — one executable per key."""
        return (self.bucket, self.tier, self.mode)


def build_serve_model(cfg: ExperimentConfig):
    """The inference model for a config — the same build the serial
    predict path and `warmup --serve` use, so executables compiled by
    either are interchangeable cache entries."""
    from ..models.registry import build_model, require_flow_serving

    require_flow_serving(cfg)
    t = cfg.data.time_step
    return build_model(cfg.model, flow_channels=2 * (t - 1),
                      width_mult=cfg.width_mult,
                      corr_max_disp=cfg.corr_max_disp,
                      corr_stride=cfg.corr_stride)


def make_raw_forward(model) -> Callable:
    """(params, pairs[B,H,W,6]) -> finest scaled flow [B,h,w,2]. Defined
    once so the engine's runtime lowering and warmup's AOT lowering
    produce the same HLO (same persistent-cache key). `params` may be a
    quantized tier tree (serve/quant.py): int8 kernels dequantize HERE,
    inside the trace, so the executable's params input stays int8 while
    activations run f32 — on an f32/bf16 tree the dequantize pass
    inserts nothing and the HLO is unchanged."""

    def fwd(params, x):
        flows = model.apply({"params": dequantize_params(params)}, x)
        return flows[0] * model.flow_scales[0]

    return fwd


def build_refine_model(cfg: ExperimentConfig):
    """The warm-path refinement stage for a config (models/flownet2.py
    FlowNetRefine) — ONE definition shared by the engine and
    `warmup --serve` so their lowerings share a cache key.

    flownet_cs configs reuse their own full-width refinement stage
    (direct-prediction semantics; the checkpoint's `refine` subtree IS
    this module's params). Every other 2-frame model gets a standalone
    gated-residual stage at `width_mult * serve.session.warm_width` with
    a deterministic seeded init (`refine_init_params`) — identity on its
    prior until a trained refinement checkpoint exists."""
    from ..models.flownet2 import FlowNetRefine

    if cfg.model == "flownet_cs":
        return FlowNetRefine(width_mult=1.0, residual=False)
    return FlowNetRefine(
        width_mult=cfg.width_mult * float(cfg.serve.session.warm_width),
        residual=True)


def refine_init_params(cfg: ExperimentConfig, refine_model):
    """Deterministic (cfg.train.seed) init of the standalone refinement
    stage. Conv params are spatial-shape-independent, so one init at any
    /64-friendly size serves every bucket; the fixed seed is what makes
    the warm path bit-stable across engines and replicas."""
    import jax
    import jax.numpy as jnp

    variables = refine_model.init(
        jax.random.PRNGKey(cfg.train.seed),
        jnp.zeros((1, 64, 64, PAIR_CHANNELS), jnp.float32),
        jnp.zeros((1, 32, 32, 2), jnp.float32))
    return variables["params"]


def make_refine_forward(refine_model) -> Callable:
    """(refine_params, pairs[B,H,W,6], prior[B,H,W,2]) -> finest scaled
    flow [B,h,w,2] — the warm twin of make_raw_forward, defined once so
    the engine's runtime lowering and warmup's AOT lowering share a
    persistent-cache key. Same dequantize-inside-the-trace contract as
    the cold forward (int8 refine tiers stay int8 at the boundary)."""

    def fwd(params, x, prior):
        flows = refine_model.apply({"params": dequantize_params(params)},
                                   x, prior)
        return flows[0] * refine_model.flow_scales[0]

    return fwd


def cold_output_hw(cold_fwd, cold_params, bucket: tuple[int, int],
                   max_batch: int) -> tuple[int, int]:
    """The (h, w) grid of the COLD executable's output for one bucket —
    derived abstractly (eval_shape; nothing runs). This is the grid the
    session's warm-start prior lives on: the prior is a previous
    dispatch's output stored verbatim, so the warm executable's prior
    aval must match the cold executable's output aval by construction.
    The refinement stage's OWN output must land on the same grid (the
    prior chain is shape-stable only then) — `_executable`/warmup check
    that abstractly and reject the config loudly otherwise."""
    import jax

    params_sds, x_sds = serve_avals(cold_params, bucket, max_batch)
    out = jax.eval_shape(cold_fwd, params_sds, x_sds)
    return (int(out.shape[1]), int(out.shape[2]))


def _lowered_out_hw(lowered) -> tuple[int, int]:
    """The (h, w) grid of a lowering's (single-array) output, read off
    ``Lowered.out_info`` — the shape the trace ALREADY derived, so the
    prior-grid check costs zero additional traces (it formerly paid a
    full eval_shape of the refine forward per warm lattice entry)."""
    import jax

    leaf = jax.tree_util.tree_leaves(lowered.out_info)[0]
    return (int(leaf.shape[1]), int(leaf.shape[2]))


def refine_serve_avals(refine_params, bucket: tuple[int, int],
                       max_batch: int, prior_hw: tuple[int, int]):
    """(params_sds, x_sds, prior_sds) for one warm bucket executable —
    shared by engine._executable and warmup_serve so their cache keys
    match (the serve_avals twin, plus the prior input on the cold
    output's grid — `cold_output_hw`)."""
    import jax

    params_sds, x_sds = serve_avals(refine_params, bucket, max_batch)
    prior_sds = jax.ShapeDtypeStruct(
        (max_batch, prior_hw[0], prior_hw[1], 2), np.float32)
    return params_sds, x_sds, prior_sds


def make_fake_forward(exec_ms: float) -> Callable:
    """Deterministic timed executor standing in for the model: sleeps
    `exec_ms` per DISPATCH (batch-size independent, like a device whose
    forward is latency-bound) and computes flow as the scaled channel
    difference of the input pair — content-dependent, so output equality
    across runs/replicas is a real check. The batcher tests,
    `tools/serve_bench.py`, and fleet replica subprocesses
    (`serve.fake_exec_ms`) all share this one definition: no checkpoint,
    no jax import."""

    def forward(bucket, x):
        time.sleep(max(exec_ms, 0.0) / 1e3)
        return np.stack([x[..., 0] - x[..., 3], x[..., 1] - x[..., 4]],
                        axis=-1).astype(np.float32)

    return forward


#: Serving is pair-based: prepare_pair always concatenates exactly two
#: preprocessed BGR frames, so every executable takes 6 input channels
#: (multi-frame T-volume configs are a training shape, not a serving one).
PAIR_CHANNELS = 6


def serve_avals(params, bucket: tuple[int, int], max_batch: int):
    """(params_sds, x_sds) for one bucket executable — shared by
    engine._executable and warmup_serve so their cache keys match.
    `params` may be real arrays or ShapeDtypeStructs."""
    import jax

    params_sds = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(getattr(a, "shape", ()), a.dtype),
        params)
    x_sds = jax.ShapeDtypeStruct(
        (max_batch, bucket[0], bucket[1], PAIR_CHANNELS), np.float32)
    return params_sds, x_sds


class InferenceEngine:
    """See module docstring.

    cfg: full experiment config (serve.* drives the batcher; data/eval
        fields drive the preprocess/postprocess protocol).
    model_params: optional (model, params) — skips checkpoint restore
        (tests, and predict_pairs after it already restored).
    mean: optional BGR dataset mean override (DATASET_MEANS default).
    forward_fn: optional (bucket, x[max_batch,H,W,6]) -> [max_batch,h,w,2]
        executor replacing the jitted model entirely — the deterministic
        fake timed executor the batcher tests and serve_bench use. A
        custom executor is precision-blind (it has no weights to
        quantize): every tier routes/batches separately but executes the
        same function.
    """

    def __init__(self, cfg: ExperimentConfig, model_params=None, mean=None,
                 forward_fn: Callable | None = None):
        self.cfg = cfg
        self.max_batch = max(int(cfg.serve.max_batch), 1)
        self.timeout_s = max(float(cfg.serve.batch_timeout_ms), 0.0) / 1e3
        self.buckets = resolve_buckets(cfg)
        if float(cfg.obs.slo_latency_ms) > 0:
            validate_slo(cfg.obs)  # an unmeasurable SLO target fails HERE
        # precision tiers: one executable per (bucket, tier); the
        # config's first entry is the default a request gets when it
        # names none (serve/quant.py owns the transforms)
        self.tiers = resolve_precisions(cfg)
        self.default_tier = self.tiers[0]
        if mean is None:
            from ..data.datasets import DATASET_MEANS

            mean = DATASET_MEANS.get(cfg.data.dataset,
                                     DATASET_MEANS["flyingchairs"])
        self.mean = mean

        # temporal warm-start: the refinement-only executable axis
        # (serve/session.py prior + models/flownet2.py FlowNetRefine)
        self.warm_start = bool(cfg.serve.session.warm_start)

        if (forward_fn is None and model_params is None
                and cfg.serve.fake_exec_ms is not None):
            # config-driven fake executor: how a fleet replica subprocess
            # (which only gets a config.json) runs without a checkpoint
            forward_fn = make_fake_forward(float(cfg.serve.fake_exec_ms))
        self._forward_custom = forward_fn is not None
        if self._forward_custom:
            # internal convention: _forward(key, x, prior=None) with key
            # = (bucket, tier, mode); custom executors keep their
            # documented (bucket, x) signature — they are precision- AND
            # warm-blind (no weights to quantize, no refinement stage):
            # warm steps group/count separately but execute the same fn
            self._forward = (lambda key, x, prior=None, _fn=forward_fn:
                             _fn(key[0], x))
            self._model = self._params = None
        else:
            if model_params is not None:
                self._model, self._params = model_params
            else:
                from ..predict import restore_params

                self._model, self._params = restore_params(cfg)
            import jax

            from ..train.warmup import enable_for_config

            # persistent compile cache per config policy (auto: on for
            # accelerator backends): a cold serving process after
            # `warmup --serve` loads its bucket executables instead of
            # compiling them
            enable_for_config(cfg)
            # AOT executables are lowered from bare avals — the same
            # single-device lowering `warmup --serve` persists (cache-key
            # parity). Params restored onto a replicated mesh sharding
            # would mismatch that compiled input spec, so serving
            # canonicalizes them onto one device; scale-out is N engine
            # processes, not in-engine batch sharding.
            dev = self._device = jax.devices()[0]
            self._params = jax.device_put(self._params, dev)
            # one quantized params tree per tier, staged once (int8 is a
            # quarter, bf16 half the f32 bytes); the tier trees' avals
            # differ, so each (bucket, tier) lowers to its own cache key
            self._params_by_tier = {
                tier: jax.device_put(quantize_params(self._params, tier),
                                     dev)
                for tier in self.tiers}
            if self.warm_start:
                # the warm refinement stage: flownet_cs reuses its own
                # (restored) refine subtree; other models get the
                # deterministic seeded gated-residual stage — either
                # way, one quantized tree per tier, like the cold params
                self._refine_model = build_refine_model(cfg)
                if cfg.model == "flownet_cs":
                    refine_params = {"refine": self._params["refine"]}
                else:
                    refine_params = refine_init_params(
                        cfg, self._refine_model)
                self._refine_by_tier = {
                    tier: jax.device_put(
                        quantize_params(refine_params, tier), dev)
                    for tier in self.tiers}
                self._warm_jit = jax.jit(
                    make_refine_forward(self._refine_model))
            if "f32" not in self.tiers:
                # nothing reads the f32 tree once the tier trees exist;
                # keeping it would hold 1-2x the configured ladder's
                # weight bytes on the device for the engine's lifetime
                self._params = None
            self._jit = jax.jit(make_raw_forward(self._model))
            self._forward = self._model_forward
        self._compiled: dict[tuple[tuple[int, int], str], object] = {}
        self._compile_lock = threading.Lock()
        # executable ledger (obs/ledger.py): real-model engines append
        # one provenance row per AOT lowering to <log_dir>/ledger.jsonl
        # and export the exec_* block through stats() -> heartbeat +
        # /metrics. Custom/fake executors have no XLA executables to
        # ledger, and obs.ledger=false keeps the stats schema
        # byte-identical to the pre-ledger stack. Hot-path cost is one
        # timed dict update per flush (bounded <= 2% of serve p99 in
        # serve_bench --ledger-overhead).
        self._ledger: ExecutableLedger | None = None
        if not self._forward_custom and bool(cfg.obs.ledger):
            import jax

            self._ledger = ExecutableLedger(
                cfg.train.log_dir, backend=jax.default_backend())
        # artifact plane (serve/artifacts.py): when serve.artifacts_dir
        # names a store, every lattice entry is FETCHED (deserialized)
        # from it instead of compiled, keyed by the local lowering's
        # StableHLO fingerprint — the zero-cold-start replica boot. A
        # miss/reject falls back to the compile path loudly.
        self._artifacts = None
        if not self._forward_custom:
            from .artifacts import store_for_config

            # fetched executables load onto the device the params live on
            self._artifacts = store_for_config(cfg, device=self._device)
        # executable index (trace-free boot): resolve each lattice entry
        # by its jax-free resolution key BEFORE building avals or
        # lowering anything — an index hit is fetch + gates +
        # deserialize, zero trace/lower calls. Integrity beyond the
        # crc/manifest/name gates is deferred to the deep-verify plane
        # below; any index miss/reject falls through to the
        # fingerprint-then-compile path.
        self._index_enabled = (self._artifacts is not None
                               and bool(cfg.serve.artifacts_index))
        self._deep_verify_enabled = (self._index_enabled
                                     and bool(
                                         cfg.serve.artifacts_deep_verify))
        self._cfg_digest: str | None = None
        # deferred deep-verify plane: every index-resolved entry is
        # queued for a background re-lowering AFTER it starts serving;
        # a fingerprint mismatch loudly demotes it (counter + warn +
        # freshly compiled swap-in under _compile_lock). Lazily started
        # daemon thread; close() stops it.
        self._deep_verify_q: queue.Queue = queue.Queue()
        self._deep_verify_thread: threading.Thread | None = None
        # pacing: one re-lower per serve.deep_verify_interval_s tick —
        # a hundred-entry lattice must not monopolize a core after
        # boot. The event doubles as the close() wake-up so a long
        # interval never stalls shutdown.
        self._deep_verify_stop = threading.Event()
        # incident plane (obs/incident.py): the process-level recorder,
        # installed by server.py when obs.incidents is on. None keeps
        # every trigger site a structural no-op (one attribute check).
        self.incidents = None
        # cold-head output grid per bucket (one eval_shape each, shared
        # by every tier's warm entry and the bucket's quality scorer —
        # the grid is dtype-independent, so re-deriving it per tier was
        # pure duplicated tracing)
        self._cold_hw: dict[tuple[int, int], tuple[int, int]] = {}

        depth = max(int(cfg.serve.queue_depth), 0)
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._closed = False
        self._rid = itertools.count(1)
        # after each flush: (total_responses) -> None — the serve
        # heartbeat's beat() hook (server.py wires it)
        self.flush_hook: Callable[[int], None] | None = None

        # --- counters (guarded by _stats_lock; GIL-atomic reads are not
        # enough for the multi-field snapshots stats() returns) ---
        self._stats_lock = threading.Lock()
        self._requests = 0
        self._responses = 0
        self._errors = 0
        self._batches = 0
        self._dispatch_failures = 0
        self._bucket_splits = 0
        self._tier_splits = 0
        self._warm_splits = 0   # same (bucket, tier), cold|warm boundary
        # temporal warm-start ledger: steps dispatched through the
        # refinement executable vs warm-eligible steps that fell back
        # cold (no prior yet — first step, or dropped by re-prime/
        # rebucket). Both stay 0 with warm_start off.
        self._warm_steps = 0
        self._cold_fallbacks = 0
        # per-tier request/response counts (analyze/tail surface these
        # so a tier nobody asks for is visible as such)
        self._requests_by_tier = {t: 0 for t in self.tiers}
        self._responses_by_tier = {t: 0 for t in self.tiers}
        self._timeout_flushes = 0
        self._occupancy_sum = 0
        self._last_occupancy = 0
        self._max_queue_depth = 0
        self._submitting = 0  # submit() threads currently inside put()
        # server-side failures only (dispatch/postprocess/engine_closed):
        # the SLO error budget must not burn on a CALLER's bad input
        self._server_errors = 0
        # deadline plane: requests arriving WITH a budget, and where
        # expired ones died (enqueue backpressure / pre-dispatch flush /
        # the server's response wait). Expiry is the CALLER's budget
        # running out, not a server fault — like session_expired it
        # counts serve_errors but never serve_server_errors.
        self._deadline_requests = 0
        self._deadline_enqueue_expired = 0
        self._deadline_flush_expired = 0
        self._deadline_wait_expired = 0
        # brownout folding (serve/degrade.py): requests actually served
        # on a cheaper operating point than they would have gotten at L0
        self._degrade_tier_downgrades = 0
        self._degrade_bucket_downgrades = 0
        self._latency_s: deque = deque(maxlen=_LATENCY_WINDOW)
        # fixed-bucket latency histogram (obs/export.py): the scrapeable
        # /metrics face of the latency story — fixed log-spaced buckets,
        # so replica histograms merge EXACTLY at the router
        self._hist = LatencyHistogram()
        # streaming sessions (serve/session.py): last-frame cache +
        # a second fixed-bucket histogram for per-session-frame latency
        # (merges exactly at the router, separately from serve_latency)
        sc = cfg.serve.session
        self.sessions = SessionStore(max_sessions=sc.max_sessions,
                                     ttl_s=sc.ttl_s, sweep_s=sc.sweep_s)
        self._session_hist = LatencyHistogram()
        # per-second completion buckets for requests/s — unlike reusing
        # the latency deque, this can't clamp the rate at high load
        self._done_per_s: dict[int, int] = {}

        # label-free flow-quality scoring (obs/quality.py): OFF by
        # default (sample_rate 0 constructs nothing — the serve path
        # stays bitwise- and schema-unchanged). Real-model engines score
        # through one jitted executable per bucket (pre-lowered by
        # `warmup --serve`); custom/fake executors score through the
        # numpy reference — jax-free fleet replicas keep quality eyes.
        self._quality: QualityScorer | None = None
        self._quality_index = 0  # deterministic sampler's request index
        self._score_compiled: dict[tuple[int, int], object] = {}
        obs = cfg.obs
        if float(obs.quality_sample_rate) > 0:
            if self._forward_custom:
                score_fn = (lambda bucket, x, flow:
                            score_pair_np(x[0], flow[0]))
            else:
                import jax

                self._score_jit = jax.jit(make_score_fn())
                score_fn = (lambda bucket, x, flow:
                            tuple(float(v) for v in np.asarray(
                                self._score_executable(bucket)(x, flow))))
            self._quality = QualityScorer(
                score_fn, obs.quality_sample_rate,
                seed=obs.quality_seed,
                queue_depth=obs.quality_queue_depth,
                ref_samples=obs.quality_ref_samples,
                window=obs.quality_window,
                drift_factor=obs.quality_drift_factor,
                budget=obs.quality_budget)

        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="serve-batcher")
        self._thread.start()

    # ------------------------------------------------------------ submit
    def _decode(self, img) -> np.ndarray:
        """Path -> decoded BGR array (arrays pass through validated)."""
        if isinstance(img, np.ndarray):
            if img.ndim != 3 or img.shape[-1] != 3:
                raise ServeError("bad_input",
                                 f"image array must be (H, W, 3) BGR, "
                                 f"got {img.shape}")
            return img
        from ..data.datasets import _imread_bgr

        return _imread_bgr(str(img))

    def _resolve_tier(self, precision, rid, degrade_level: int = 0) -> str:
        """A request's tier: its explicit `precision` or the config's
        default; a tier this endpoint does not serve is a structured
        per-request error (no executable exists for it — admitting it
        would compile on the hot path).

        At brownout L1+ (serve/degrade.py) a request that named NO
        precision serves at the cheapest configured tier instead of the
        default — an explicit `precision` is always honored. Every tier
        is a pre-warmed lattice entry, so the downgrade never compiles.
        """
        if precision is None:
            if degrade_level >= 1 and len(self.tiers) > 1:
                tier = self.tiers[-1]  # config order: last = cheapest
                if tier != self.default_tier:
                    with self._stats_lock:
                        self._degrade_tier_downgrades += 1
                return tier
            return self.default_tier
        tier = str(precision)
        if tier not in self.tiers:
            raise ServeError(
                "bad_request",
                f"precision {tier!r} not served; this endpoint offers "
                f"{list(self.tiers)}", rid)
        return tier

    def _deadline_abs(self, deadline_s) -> float | None:
        """Caller budget (seconds remaining) -> absolute monotonic
        expiry; also ticks the deadline_requests ledger."""
        if deadline_s is None:
            return None
        with self._stats_lock:
            self._deadline_requests += 1
        return time.monotonic() + max(float(deadline_s), 0.0)

    def submit(self, prev, nxt, precision: str | None = None,
               request_id: int | str | None = None,
               deadline_s: float | None = None,
               degrade_level: int = 0) -> Future:
        """Enqueue one (prev, next) pair — paths or decoded BGR arrays.

        precision: serving tier ("f32" | "bf16" | "int8"); must be in
        cfg.serve.precisions; None = the config's first (default) tier.
        request_id: external correlation id (the router's X-Request-Id)
        stamped on this request's spans and echoed in the response, so
        obs/aggregate.py can chain the request's timeline across the
        router and this replica; None = a process-local sequence id.
        deadline_s: the caller's remaining budget (X-Deadline-Ms / 1e3);
        None = no deadline. An expired request fails fast with
        `deadline_exceeded` at enqueue or flush instead of dispatching.
        degrade_level: the live brownout level the router folded in
        (X-Degrade-Level; serve/degrade.py) — L1+ downgrades the default
        tier, L2+ routes one bucket down the ladder; both targets are
        pre-warmed lattice entries, so degradation never compiles.

        Returns a Future resolving to {"flow": (H_native, W_native, 2)
        float32 in native pixel units, "bucket", "precision",
        "native_hw", "latency_s", "request_id"}; failures raise
        ServeError from .result(). Decode/preprocess errors fail HERE
        (this request only) — they never enter the batcher.
        """
        rid = request_id if request_id is not None else next(self._rid)
        fut: Future = Future()
        with self._stats_lock:
            self._requests += 1
        try:
            tier = self._resolve_tier(precision, rid, degrade_level)
            deadline = self._deadline_abs(deadline_s)
            with obs_trace.span("serve_enqueue", request_id=rid):
                src = self._decode(prev)
                tgt = self._decode(nxt)
                native_hw = (int(src.shape[0]), int(src.shape[1]))
                bucket = pick_bucket(native_hw, self.buckets)
                if degrade_level >= 2:
                    down = next_smaller_bucket(bucket, self.buckets)
                    if down != bucket:
                        bucket = down
                        with self._stats_lock:
                            self._degrade_bucket_downgrades += 1
                x = prepare_pair(src, tgt, bucket, self.mean)
            with self._stats_lock:
                self._requests_by_tier[tier] += 1
            self._enqueue(_Request(x, bucket, tier, native_hw, fut,
                                   time.monotonic(), rid,
                                   deadline=deadline))
        except ServeError as e:
            e.request_id = e.request_id or rid
            self._fail(fut, e)
        except Exception as e:  # noqa: BLE001 - decode errors are per-request
            self._fail(fut, ServeError(
                "bad_input", f"{type(e).__name__}: {e}", rid))
        return fut

    def submit_prepared(self, x: np.ndarray, bucket: tuple[int, int],
                        native_hw: tuple[int, int],
                        precision: str | None = None,
                        request_id: int | str | None = None,
                        deadline_s: float | None = None) -> Future:
        """Enqueue an already-preprocessed row (offline mode: the
        data/pipeline.py worker pool runs prepare_pair concurrently and
        feeds rows here in order). No brownout folding: the row is
        already prepared at its bucket, and offline throughput work is
        not latency-degradable."""
        rid = request_id if request_id is not None else next(self._rid)
        fut: Future = Future()
        with self._stats_lock:
            self._requests += 1
        try:
            tier = self._resolve_tier(precision, rid)
            deadline = self._deadline_abs(deadline_s)
            with self._stats_lock:
                self._requests_by_tier[tier] += 1
            self._enqueue(_Request(np.asarray(x, np.float32), tuple(bucket),
                                   tier, tuple(native_hw), fut,
                                   time.monotonic(), rid,
                                   deadline=deadline))
        except ServeError as e:
            e.request_id = e.request_id or rid
            self._fail(fut, e)
        return fut

    def submit_next(self, session: str, frame,
                    precision: str | None = None,
                    request_id: int | str | None = None,
                    deadline_s: float | None = None,
                    degrade_level: int = 0) -> Future:
        """Advance a streaming session by ONE frame (serve/session.py).

        The first frame of a session primes it: the future resolves
        immediately with {"primed": True, "session", "bucket",
        "native_hw", "frames", "request_id"} — nothing dispatches.
        Every later frame forms the (prev, next) pair from the cached
        previous frame — one decode + one preprocess instead of two —
        and resolves like submit(), plus {"session", "frame_index"}.

        Failure contract: a frame for a TTL-expired or LRU-evicted
        session fails with a structured `session_expired` ServeError
        (the client re-primes by resending — that retry is counted as
        `resumed`); a mid-session resolution change re-primes in place
        (a fresh `primed` reply, counted as `rebucketed`). A decode
        failure fails this frame only and does NOT advance the session.

        Brownout folding is tier-only here: L1+ downgrades the default
        precision, but L2's bucket downgrade is deliberately NOT applied
        to streaming steps — a bucket change re-primes the session
        (advance()'s rebucket path), dropping the cached frame and warm
        prior, which would cost more than the smaller bucket saves.
        """
        rid = request_id if request_id is not None else next(self._rid)
        fut: Future = Future()
        counted = False  # one _requests tick per frame, on ANY path
        # span name is a fast pre-probe; advance() is the authority (a
        # race with the sweeper at most mislabels one span's name)
        kind_hint = "session_step" if self.sessions.contains(session) \
            else "session_prime"
        try:
            tier = self._resolve_tier(precision, rid, degrade_level)
            deadline = self._deadline_abs(deadline_s)
            with obs_trace.span(kind_hint, session=str(session),
                                request_id=rid) as span:
                img = self._decode(frame)
                native_hw = (int(img.shape[0]), int(img.shape[1]))
                bucket = pick_bucket(native_hw, self.buckets)
                row = prepare_frame(img, bucket, self.mean)
                try:
                    out = self.sessions.advance(str(session), row, bucket,
                                                native_hw, tier)
                except SessionExpired as e:
                    raise ServeError(
                        "session_expired",
                        f"session {e.sid!r} {e.reason} — resend the frame "
                        f"to re-prime", rid)
                if out[0] == "primed":
                    _, s = out
                    span.set(kind="session_prime")
                    fut.set_result({"primed": True, "session": s.sid,
                                    "bucket": bucket,
                                    "native_hw": native_hw,
                                    "frames": s.frames,
                                    "request_id": rid})
                    return fut
                _, prev_row, prior, epoch, s = out
                # temporal warm-start: a step with a cached prior flow
                # dispatches the refinement-only executable; without one
                # (first step, or the prior was dropped by a re-prime/
                # rebucket) it falls back to the full cold network
                mode = "cold"
                if self.warm_start and prior is not None:
                    mode = "warm"
                    span.set(kind="session_warm",
                             frame_index=s.frames - 1)
                else:
                    if self.warm_start:
                        with self._stats_lock:
                            self._cold_fallbacks += 1
                    span.set(kind="session_step",
                             frame_index=s.frames - 1)
                x = np.concatenate([prev_row, row], axis=-1)
            with self._stats_lock:
                self._requests += 1
                self._requests_by_tier[tier] += 1
                if mode == "warm":
                    self._warm_steps += 1
            counted = True
            self._enqueue(_Request(x, bucket, tier, native_hw, fut,
                                   time.monotonic(), rid,
                                   session=s.sid,
                                   frame_index=s.frames - 1,
                                   mode=mode,
                                   prior=prior if mode == "warm" else None,
                                   session_epoch=epoch,
                                   deadline=deadline))
        except ServeError as e:
            e.request_id = e.request_id or rid
            if not counted:  # failed frames stay ledgered, exactly once
                with self._stats_lock:
                    self._requests += 1
            self._fail(fut, e)
        except Exception as e:  # noqa: BLE001 - decode errors are per-request
            if not counted:
                with self._stats_lock:
                    self._requests += 1
            self._fail(fut, ServeError(
                "bad_input", f"{type(e).__name__}: {e}", rid))
        return fut

    def _enqueue(self, req: _Request) -> None:
        with self._stats_lock:
            if self._closed:
                raise ServeError("engine_closed", "engine is shut down",
                                 req.rid)
            self._submitting += 1
            if self._quality is not None:
                # the sampling decision is a pure function of the
                # accepted-request index (obs/quality.py): the sampled
                # SET depends only on submission order — never on
                # batching, scorer backlog, or decode-worker count
                req.score = self._quality.should_sample(self._quality_index)
                self._quality_index += 1
        try:
            # bounded put = backpressure, but polled: a submitter blocked
            # on a full queue must observe close() — and its own
            # deadline — instead of completing its put into a dead queue
            # (its future would never resolve — close() drains only
            # after _submitting hits 0). A doomed request releasing its
            # backpressure slot here is load the queue never carries.
            while True:
                if self._closed:
                    raise ServeError("engine_closed", "engine is shut down",
                                     req.rid)
                if req.deadline is not None:
                    rem = req.deadline - time.monotonic()
                    if rem <= 0:
                        with self._stats_lock:
                            self._deadline_enqueue_expired += 1
                        raise ServeError(
                            "deadline_exceeded",
                            "deadline expired while queueing", req.rid)
                else:
                    rem = 0.1
                try:
                    self._q.put(req, timeout=min(0.1, max(rem, 0.001)))
                    break
                except queue.Full:
                    continue
        finally:
            with self._stats_lock:
                self._submitting -= 1
        with self._stats_lock:
            self._max_queue_depth = max(self._max_queue_depth,
                                        self._q.qsize())

    def _fail(self, fut: Future, err: ServeError) -> None:
        with self._stats_lock:
            self._errors += 1
            # session_expired is protocol, not failure: the client let
            # its session idle past the TTL (or lost an LRU race) and
            # re-primes — it must not burn the operator's SLO budget.
            # deadline_exceeded likewise: the CALLER's budget ran out,
            # not the server — overload shows up in the deadline_* and
            # degrade_* ledgers instead.
            if err.code not in ("bad_input", "bad_request",
                                "session_expired", "deadline_exceeded"):
                self._server_errors += 1  # burns the SLO error budget
        fut.set_exception(err)

    def note_wait_expired(self) -> None:
        """The SERVER's deadline ledger hook: its response wait hit the
        caller's budget (min(request_timeout_s, deadline)) before this
        engine resolved the future. Counted here so every stage of the
        deadline story rides one stats surface."""
        with self._stats_lock:
            self._deadline_wait_expired += 1

    # ----------------------------------------------------------- batcher
    def _run(self) -> None:
        pending: _Request | None = None  # carried over a bucket split
        stop = False
        while not stop:
            if pending is not None:
                req, pending = pending, None
            else:
                req = self._q.get()
            if req is _STOP:
                break
            batch = [req]
            timed_out = False
            with obs_trace.span("serve_batch") as batch_span:
                while len(batch) < self.max_batch:
                    rem = (batch[0].t_enq + self.timeout_s) - time.monotonic()
                    try:
                        nxt = (self._q.get(timeout=rem) if rem > 0
                               else self._q.get_nowait())
                    except queue.Empty:
                        timed_out = True  # the oldest waited out the deadline
                        break
                    if nxt is _STOP:
                        stop = True
                        break
                    if nxt.key != batch[0].key:
                        pending = nxt  # flush now; it opens the next batch
                        with self._stats_lock:
                            if nxt.bucket != batch[0].bucket:
                                self._bucket_splits += 1
                            elif nxt.tier != batch[0].tier:
                                self._tier_splits += 1
                            else:  # same shape+precision, cold|warm edge
                                self._warm_splits += 1
                        break
                    batch.append(nxt)
                # ids are only known once the batch closed: stamp them
                # late so aggregate.py can chain the request's timeline
                batch_span.set(request_ids=[r.rid for r in batch],
                               occupancy=len(batch))
            if timed_out and len(batch) < self.max_batch:
                with self._stats_lock:
                    self._timeout_flushes += 1
            self._flush(batch)
        # anything still queued after _STOP was submitted post-close
        # bookkeeping started — fail it loudly rather than hang a caller
        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                break
            if req is not _STOP:
                self._fail(req.future, ServeError(
                    "engine_closed", "engine shut down before dispatch",
                    req.rid))

    def _flush(self, batch: list[_Request]) -> None:
        # last pre-dispatch deadline gate: a request whose budget
        # expired while batching fails fast HERE — its padded batch slot
        # (and the postprocess work) would be wasted on a reply the
        # caller already abandoned
        expired = [r for r in batch if r.deadline is not None
                   and r.deadline <= time.monotonic()]
        if expired:
            with self._stats_lock:
                self._deadline_flush_expired += len(expired)
            for r in expired:
                self._fail(r.future, ServeError(
                    "deadline_exceeded", "deadline expired before dispatch",
                    r.rid))
            batch = [r for r in batch if r not in expired]
            if not batch:
                return
        bucket, tier, mode = batch[0].key
        n = len(batch)
        tag = f"{bucket[0]}x{bucket[1]}/{tier}/{mode}"
        rids = [r.rid for r in batch]
        with obs_trace.span("serve_dispatch", occupancy=n, bucket=tag,
                            request_ids=rids):
            x = np.zeros((self.max_batch, bucket[0], bucket[1],
                          batch[0].x.shape[-1]), np.float32)
            for i, r in enumerate(batch):
                x[i] = r.x
            prior = None
            if mode == "warm":
                # the refinement executable's second input: per-request
                # priors (finest-head grid — stored dispatch outputs),
                # zero-padded past the live occupancy like x
                ph, pw = batch[0].prior.shape[:2]
                prior = np.zeros((self.max_batch, ph, pw, 2), np.float32)
                for i, r in enumerate(batch):
                    prior[i] = r.prior
            if self._ledger is not None:
                # resolve (compile/load) the executable BEFORE the timed
                # window: the first flush's measured dispatch must be an
                # execution, not compile+execution — the MFU denominator
                # would otherwise be off by orders of magnitude. Same
                # containment as the dispatch below: a compile failure
                # (warm-grid ValueError, XLA error) fails this flush's
                # futures, never the batcher thread.
                try:
                    self._executable(batch[0].key)
                except Exception as e:  # noqa: BLE001 - contained per flush
                    with self._stats_lock:
                        self._dispatch_failures += 1
                    for r in batch:
                        self._fail(r.future, ServeError(
                            "dispatch_failed", f"{type(e).__name__}: {e}",
                            r.rid))
                    return
            t_fwd = time.perf_counter()
            try:
                out = np.asarray(self._forward(batch[0].key, x,
                                               prior=prior))
            except Exception as e:  # noqa: BLE001 - the flush fails, not the engine
                with self._stats_lock:
                    self._dispatch_failures += 1
                for r in batch:
                    self._fail(r.future, ServeError(
                        "dispatch_failed", f"{type(e).__name__}: {e}", r.rid))
                return
            if self._ledger is not None:
                # per-executable measured dispatch time (host-synced):
                # the denominator of the ledger's nominal-roofline MFU.
                # One dict update per FLUSH, not per request — the whole
                # ledger's hot-path cost.
                self._ledger.note_exec(exec_name(bucket, tier, mode),
                                       time.perf_counter() - t_fwd)
        with obs_trace.span("serve_postprocess", occupancy=n, bucket=tag,
                            request_ids=rids):
            for i, r in enumerate(batch):
                try:
                    flow = flow_to_native(out[i], self.cfg, bucket,
                                          r.native_hw)
                except Exception as e:  # noqa: BLE001 - one request's failure
                    self._fail(r.future, ServeError(
                        "postprocess_failed",
                        f"{type(e).__name__}: {e}", r.rid))
                    continue
                if r.session is not None and self.warm_start:
                    # warm-start writeback: this step's raw output
                    # (finest-head grid, stored VERBATIM — no resample,
                    # so the untrained residual identity is exact along
                    # a walk) becomes the session's prior. BEFORE
                    # set_result — a closed-loop client's next frame
                    # must observe it — and guarded inside the store
                    # against re-prime/rebucket/eviction/RESUME races
                    # (the prime-generation epoch captured at advance).
                    # The copy detaches the slice from the batch buffer.
                    self.sessions.set_flow(
                        r.session,
                        np.ascontiguousarray(out[i], np.float32), bucket,
                        r.session_epoch)
                if r.score and self._quality is not None:
                    # sampled label-free quality scoring (obs/quality.py):
                    # hand (input row, RAW dispatch output) to the
                    # off-path scorer. Row copies detach from the flush's
                    # output buffer; a full scorer queue drops-and-counts
                    # inside submit() — this response is never delayed.
                    self._quality.submit(
                        r.x, np.array(out[i], np.float32, copy=True),
                        bucket, r.tier, r.mode)
                done = time.monotonic()
                self._hist.observe(done - r.t_enq)
                if r.session is not None:
                    # per-session-frame latency: the streaming axis's own
                    # histogram (submit -> flow for ONE new frame)
                    self._session_hist.observe(done - r.t_enq)
                with self._stats_lock:
                    self._responses += 1
                    self._responses_by_tier[r.tier] += 1
                    self._latency_s.append(done - r.t_enq)
                    sec = int(done)
                    self._done_per_s[sec] = self._done_per_s.get(sec, 0) + 1
                    if len(self._done_per_s) > _RATE_WINDOW_S + 5:
                        for old in [s for s in self._done_per_s
                                    if s < sec - _RATE_WINDOW_S - 1]:
                            del self._done_per_s[old]
                result = {"flow": flow, "bucket": bucket,
                          "precision": tier, "native_hw": r.native_hw,
                          "latency_s": done - r.t_enq,
                          "request_id": r.rid}
                if r.session is not None:
                    result["session"] = r.session
                    result["frame_index"] = r.frame_index
                    if self.warm_start:
                        # only under the toggle: warm_start=false keeps
                        # the PR 10 response schema byte-identical
                        result["warm"] = r.mode == "warm"
                r.future.set_result(result)
        with self._stats_lock:
            self._batches += 1
            self._occupancy_sum += n
            self._last_occupancy = n
            total = self._responses
        hook = self.flush_hook
        if hook is not None:
            try:
                hook(total)
            except Exception:  # noqa: BLE001 - observability must not kill serving
                pass

    # ---------------------------------------------------------- forward
    def _model_forward(self, key: tuple[tuple[int, int], str, str],
                       x: np.ndarray, prior: np.ndarray | None = None):
        bucket, tier, mode = key
        if mode == "warm":
            return self._executable(key)(self._refine_by_tier[tier], x,
                                         prior)
        return self._executable(key)(self._params_by_tier[tier], x)

    def _executable(self, key: tuple[tuple[int, int], str, str]):
        """The (bucket, tier, mode) triple's AOT-compiled forward —
        cold: the full network, warm: the refinement-only stage —
        compiled (or loaded from the persistent cache — the
        `warmup --serve` contract) on first use. Steady state is a
        lock-free dict read (atomic in CPython; values are fully built
        before insertion under the lock): with the ledger on, every
        flush resolves the executable twice — the pre-resolve that
        keeps compile time out of the measured-dispatch window, then
        _model_forward — and taking the global compile lock both times
        per flush is the per-request lock-churn class PR 14's review
        removed from Fleet.size on this exact path."""
        c = self._compiled.get(key)
        if c is not None:
            return c
        with self._compile_lock:
            c = self._compiled.get(key)
            if c is None:
                bucket, tier, mode = key
                name = exec_name(bucket, tier, mode)
                # trace-free resolution first: an index hit skips the
                # aval construction AND the cold_output_hw eval_shape
                # below — the entire lattice can resolve with zero
                # trace/lower calls (the acceptance contract ISSUE 17
                # proves from the ledger's index_hit rows)
                c = self._resolve_index(name, serve_key=key)
                if c is None:
                    c = self._lower_and_compile(key)
                self._compiled[key] = c
        return c

    def _lower_and_compile(self, key: tuple[tuple[int, int], str, str]):
        """The lowering path (index off / miss / reject / demote):
        build avals, lower ONCE, and resolve via fingerprint fetch or
        compile. One `lowered` object per lattice entry is shared
        across the prior-grid check, the fingerprint, the ledger row,
        and the compile — the warm path's former eval_shape of the
        refine forward (a second full trace per entry) is replaced by
        reading the grid off the lowering's own out_info."""
        bucket, tier, mode = key
        if mode == "warm":
            prior_hw = self._cold_head_hw(bucket)
            params_sds, x_sds, prior_sds = refine_serve_avals(
                self._refine_by_tier[tier], bucket,
                self.max_batch, prior_hw)

            def lower_checked():
                lowered = self._warm_jit.lower(params_sds, x_sds,
                                               prior_sds)
                # the prior chain must be shape-stable: after the
                # first warm step the stored prior is the REFINE
                # stage's output, so its grid must equal the cold
                # head grid the executable was lowered for — check
                # abstractly HERE (warm()/first use), not as a
                # poisoned dispatch three steps in
                out_hw = _lowered_out_hw(lowered)
                if out_hw != tuple(prior_hw):
                    raise ValueError(
                        f"warm_start unsupported for model "
                        f"{self.cfg.model!r} at bucket {bucket}: the "
                        f"refinement head grid {out_hw} differs from "
                        f"the cold head grid {tuple(prior_hw)} — the "
                        f"session's prior would change shape after "
                        f"the first warm step")
                return lowered

            return self._compile_recorded(exec_name(bucket, tier, mode),
                                          lower_checked)
        params_sds, x_sds = serve_avals(
            self._params_by_tier[tier], bucket, self.max_batch)
        return self._compile_recorded(
            exec_name(bucket, tier, mode),
            lambda: self._jit.lower(params_sds, x_sds))

    def _cold_head_hw(self, bucket: tuple[int, int]) -> tuple[int, int]:
        """The cold network's output grid at `bucket` — ONE eval_shape
        per bucket, cached: every tier's warm entry and the bucket's
        quality scorer share it (the grid does not depend on the weight
        dtype), where each formerly paid its own trace."""
        hw = self._cold_hw.get(bucket)
        if hw is None:
            hw = tuple(cold_output_hw(
                self._jit, self._params_by_tier[self.default_tier],
                bucket, self.max_batch))
            self._cold_hw[bucket] = hw
        return hw

    def _compile_recorded(self, name: str, lower_fn):
        """Resolve one lattice executable: through the executable ledger
        when one is active (provenance row: fingerprint, compile
        seconds, cache hit/miss, artifact verdict, cost/memory
        analysis, donation) — which fetches from the artifact store
        before compiling — else bare (same fetch-first order, no
        row)."""
        if self._ledger is not None:
            compiled, _ = self._ledger.record_aot(
                name, lower_fn, artifacts=self._artifacts)
            return compiled
        lowered = lower_fn()
        if self._artifacts is not None:
            from ..obs.ledger import fingerprint_text

            compiled, _verdict = self._artifacts.fetch(
                fingerprint_text(lowered.as_text()))
            if compiled is not None:
                return compiled
        return lowered.compile()

    # ------------------------------------------- trace-free index boot
    def _config_digest(self) -> str:
        if self._cfg_digest is None:
            from .artifacts import serve_config_digest

            self._cfg_digest = serve_config_digest(self.cfg)
        return self._cfg_digest

    def _index_key(self, name: str,
                   serve_key: tuple | None = None,
                   quality_bucket: tuple | None = None) -> str:
        """The entry's jax-free resolution key. The aval signature
        reads shapes/dtypes off the CONCRETE in-memory param trees (no
        trace); `warmup --serve` computes the identical signature from
        its eval_shape trees, so both sides agree without either
        re-lowering. Warm entries sign the refine tree — the prior
        aval is derived state the index entry carries (`prior_hw`),
        validated at publish time and re-checked by deep verify."""
        import jax

        from .artifacts import params_aval_sig, resolution_key

        if serve_key is not None:
            bucket, tier, mode = serve_key
            params = (self._refine_by_tier[tier] if mode == "warm"
                      else self._params_by_tier[tier])
        else:
            bucket = quality_bucket
            params = self._params_by_tier[self.default_tier]
        x_aval = ("__x__",
                  (self.max_batch, bucket[0], bucket[1], PAIR_CHANNELS),
                  "float32")
        sig = params_aval_sig(params, extra=(x_aval,))
        return resolution_key(name, self._config_digest(), sig,
                              jax.default_backend(), jax.__version__)

    def _resolve_index(self, name: str, serve_key: tuple | None = None,
                       quality_bucket: tuple | None = None):
        """Trace-free resolution of one lattice entry through the
        store's executable index: key lookup + trust gates + fetch +
        deserialize, zero trace/lower calls. A hit is recorded as a
        ``cache_verdict="index_hit"`` ledger row and queued for the
        deferred deep-verify plane; every miss/reject returns None and
        the caller falls back to the lowering path (whose own row —
        `aot`/`artifact` — is the loud evidence on `tail`)."""
        if not self._index_enabled:
            return None
        key = self._index_key(name, serve_key=serve_key,
                              quality_bucket=quality_bucket)
        if self._ledger is not None:
            compiled, _row, verdict = self._ledger.record_index(
                name, self._artifacts, key)
        else:
            try:
                compiled, _fp, verdict = self._artifacts.resolve(key)
            except Exception:  # noqa: BLE001 - index is best-effort
                compiled, verdict = None, "index_reject:resolve_failed"
        if compiled is None:
            return None
        ent = self._artifacts.index_entry(key) or {}
        if self._deep_verify_enabled:
            self._schedule_deep_verify(
                name, serve_key, quality_bucket,
                ent.get("fingerprint"))
        return compiled

    # ------------------------------------------- deferred deep verify
    def _schedule_deep_verify(self, name, serve_key, quality_bucket,
                              expected_fp) -> None:
        """Queue one index-resolved entry for background re-lowering.
        Caller holds _compile_lock; the worker itself never takes it
        except for the swap-in, so verification cannot stall a boot."""
        self._deep_verify_q.put((name, serve_key, quality_bucket,
                                 expected_fp))
        if self._deep_verify_thread is None:
            t = threading.Thread(target=self._deep_verify_loop,
                                 name="deep-verify", daemon=True)
            self._deep_verify_thread = t
            t.start()

    def _deep_verify_loop(self) -> None:
        interval = max(float(self.cfg.serve.deep_verify_interval_s), 0.0)
        while True:
            item = self._deep_verify_q.get()
            if item is None:
                self._deep_verify_q.task_done()
                return
            try:
                self._deep_verify_one(*item)
            except Exception as e:  # noqa: BLE001 - verify best-effort
                print(f"serve: deep-verify {item[0]} failed: {e}",
                      file=sys.stderr)
                if self._ledger is not None:
                    self._ledger.note_deep_verify(True)
            finally:
                self._deep_verify_q.task_done()
            if interval > 0:
                # stagger AFTER task_done: deep_verify_join() sees the
                # entry complete immediately, and close() skips the
                # wait via the stop event
                self._deep_verify_stop.wait(interval)

    def _deep_verify_one(self, name, serve_key, quality_bucket,
                         expected_fp) -> None:
        """Re-lower one index-resolved entry and compare StableHLO
        fingerprints. Match -> exec_deep_verify_ok. Mismatch (the
        index's claimed lowering is NOT what local code produces —
        code drift against a stale index) -> loud demote: warn on
        stderr, exec_deep_verify_demoted counter, a
        compile_kind="deep_verify" ledger row carrying the TRUE
        fingerprint, and a freshly compiled executable swapped in
        under _compile_lock. Serving never pauses; at worst a few
        dispatches ride the stale-but-crc-intact executable before the
        swap lands."""
        import time as _time

        from ..obs.ledger import fingerprint_text

        t0 = _time.perf_counter()
        if serve_key is not None:
            bucket, tier, mode = serve_key
            if mode == "warm":
                prior_hw = self._cold_head_hw(bucket)
                params_sds, x_sds, prior_sds = refine_serve_avals(
                    self._refine_by_tier[tier], bucket,
                    self.max_batch, prior_hw)
                lowered = self._warm_jit.lower(params_sds, x_sds,
                                               prior_sds)
            else:
                params_sds, x_sds = serve_avals(
                    self._params_by_tier[tier], bucket, self.max_batch)
                lowered = self._jit.lower(params_sds, x_sds)
        else:
            flow_hw = self._cold_head_hw(quality_bucket)
            x_sds, flow_sds = quality_avals(quality_bucket, flow_hw)
            lowered = self._score_jit.lower(x_sds, flow_sds)
        fp = fingerprint_text(lowered.as_text())
        ok = fp == expected_fp
        if ok:
            if self._ledger is not None:
                self._ledger.note_deep_verify(True)
                self._ledger.record(
                    name, lowered=lowered,
                    compile_s=_time.perf_counter() - t0,
                    compile_kind="deep_verify",
                    cache_verdict="deep_verify_ok")
            return
        print(f"serve: DEEP-VERIFY DEMOTE {name}: index claimed "
              f"{expected_fp}, local code lowers to {fp} — swapping in "
              f"a fresh compile", file=sys.stderr)
        if self.incidents is not None:
            # the drifted executable SERVED requests before this verdict
            # — the evidence bundle (ledger tail, trace) is the story
            self.incidents.record(
                "deep_verify_demote", "critical",
                trigger={"exec": name, "expected_fp": expected_fp,
                         "actual_fp": fp})
        compiled = lowered.compile()
        with self._compile_lock:
            if serve_key is not None:
                self._compiled[serve_key] = compiled
            else:
                self._score_compiled[quality_bucket] = compiled
        if self._ledger is not None:
            self._ledger.note_deep_verify(False)
            self._ledger.record(
                name, lowered=lowered, compiled=compiled,
                compile_s=_time.perf_counter() - t0,
                compile_kind="deep_verify",
                cache_verdict="deep_verify_demoted")

    def deep_verify_join(self, timeout_s: float = 60.0) -> bool:
        """Wait until every queued deep verification has completed
        (tests and offline drills; serving never calls this). True when
        the queue drained within the timeout."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self._deep_verify_q.unfinished_tasks == 0:
                return True
            time.sleep(0.01)
        return self._deep_verify_q.unfinished_tasks == 0

    def _score_executable(self, bucket: tuple[int, int]):
        """The bucket's AOT-compiled quality scorer (obs/quality.py) —
        ONE executable per bucket (tiers and modes share it: the scorer
        consumes f32 inputs and f32 flow regardless of the tier that
        produced them), resolved index-first like the serve lattice,
        compiled on first use otherwise. Lock-free fast path on hit,
        same double-checked pattern as _executable."""
        c = self._score_compiled.get(bucket)
        if c is not None:
            return c
        with self._compile_lock:
            c = self._score_compiled.get(bucket)
            if c is None:
                name = quality_exec_name(bucket)
                c = self._resolve_index(name, quality_bucket=bucket)
                if c is None:
                    flow_hw = self._cold_head_hw(bucket)
                    x_sds, flow_sds = quality_avals(bucket, flow_hw)
                    c = self._compile_recorded(
                        name,
                        lambda: self._score_jit.lower(x_sds, flow_sds))
                self._score_compiled[bucket] = c
        return c

    def warm(self) -> dict:
        """AOT-compile every configured (bucket, tier, mode) triple now
        (server startup / offline-mode entry), through the persistent
        compile cache when active — after `warmup --serve` these are
        loads, not compiles. The mode axis exists only under
        serve.session.warm_start; quality-scorer executables
        (obs.quality_sample_rate > 0) ride along, one per bucket, so
        sampling never compiles on the hot path. Returns per-entry
        timings + the cache hit/miss delta."""
        # the postprocess import chain (train/evaluate and friends) is
        # first-request latency too — ~seconds in a fresh process, paid
        # inside the batcher thread if not paid here (measured via
        # tools/serve_bench.py)
        flow_to_native(np.zeros((2, 2, 2), np.float32), self.cfg,
                       (2, 2), (2, 2))
        if self._forward_custom:
            return {"buckets": [], "cache": None}  # nothing to compile
        from ..train.warmup import cache_delta

        modes = ("cold", "warm") if self.warm_start else ("cold",)
        out: dict = {"buckets": [], "modes": list(modes)}
        with cache_delta() as d:
            for b in self.buckets:
                for tier in self.tiers:
                    for mode in modes:
                        t0 = time.perf_counter()
                        self._executable((b, tier, mode))
                        out["buckets"].append(
                            {"bucket": list(b), "tier": tier, "mode": mode,
                             "compile_s": round(
                                 time.perf_counter() - t0, 3)})
                if self._quality is not None:
                    t0 = time.perf_counter()
                    self._score_executable(b)
                    out["buckets"].append(
                        {"bucket": list(b), "tier": "-", "mode": "quality",
                         "compile_s": round(time.perf_counter() - t0, 3)})
        out["cache"] = d.stats()
        return out

    # ------------------------------------------------------------- stats
    def stats(self) -> dict:
        """The serve_* counter block (heartbeat / tail / serve_bench)."""
        now = time.monotonic()
        with self._stats_lock:
            lat = sorted(self._latency_s)
            recent = sum(c for s, c in self._done_per_s.items()
                         if now - s <= _RATE_WINDOW_S)
            out = {
                "serve_requests": self._requests,
                "serve_responses": self._responses,
                "serve_errors": self._errors,
                # server-side subset of serve_errors (dispatch/
                # postprocess/engine_closed — NOT client bad input): the
                # count that distinguishes a failing executor from noisy
                # clients, and the one the fleet scrape can sum
                "serve_server_errors": self._server_errors,
                "serve_batches": self._batches,
                "serve_dispatch_failures": self._dispatch_failures,
                "serve_bucket_splits": self._bucket_splits,
                "serve_tier_splits": self._tier_splits,
                "serve_warm_splits": self._warm_splits,
                "serve_requests_by_tier": dict(self._requests_by_tier),
                "serve_responses_by_tier": dict(self._responses_by_tier),
                "serve_timeout_flushes": self._timeout_flushes,
                "serve_queue_depth": self._q.qsize(),
                "serve_max_queue_depth": self._max_queue_depth,
                "serve_last_occupancy": self._last_occupancy,
                "serve_occupancy_mean": (
                    round(self._occupancy_sum / self._batches, 3)
                    if self._batches else None),
                "serve_max_batch": self.max_batch,
                "serve_buckets": len(self.buckets),
                "serve_tiers": len(self.tiers),
                # deadline plane: budgeted arrivals + where expired ones
                # died (enqueue / flush / the server's response wait)
                "deadline_requests": self._deadline_requests,
                "deadline_enqueue_expired": self._deadline_enqueue_expired,
                "deadline_flush_expired": self._deadline_flush_expired,
                "deadline_wait_expired": self._deadline_wait_expired,
                # brownout folding: requests actually served cheaper
                # than their L0 operating point (serve/degrade.py)
                "degrade_tier_downgrades": self._degrade_tier_downgrades,
                "degrade_bucket_downgrades": self._degrade_bucket_downgrades,
            }
        if lat:
            out["serve_latency_p50_ms"] = round(
                1e3 * lat[int(0.50 * (len(lat) - 1))], 3)
            out["serve_latency_p99_ms"] = round(
                1e3 * lat[int(0.99 * (len(lat) - 1))], 3)
        else:
            out["serve_latency_p50_ms"] = None
            out["serve_latency_p99_ms"] = None
        out["serve_requests_per_s"] = round(recent / _RATE_WINDOW_S, 3)
        # streaming sessions: the serve_sessions_* block + the per-
        # session-frame latency histogram (p50/p99 read off the fixed
        # buckets — obs/export.py percentile_ms — so the figure an
        # operator sees here matches what a fleet-level merge would say)
        out.update(self.sessions.stats())
        # temporal warm-start ledger (engine-owned: the warm/cold
        # decision happens at submit, not in the store); rides the
        # serve_sessions_* block through heartbeat/metrics/analyze/tail
        with self._stats_lock:
            out["serve_sessions_warm_steps"] = self._warm_steps
            out["serve_sessions_cold_fallbacks"] = self._cold_fallbacks
        out["serve_sessions_warm_start"] = self.warm_start
        shist = self._session_hist.snapshot()
        out["serve_session_latency_hist"] = shist
        out["serve_session_latency_p50_ms"] = percentile_ms(shist, 0.50)
        out["serve_session_latency_p99_ms"] = percentile_ms(shist, 0.99)
        # label-free quality block (obs/quality.py): present ONLY when
        # sampling is configured on — sample_rate 0 keeps the serve
        # schema byte-identical to the pre-quality stack
        if self._quality is not None:
            out.update(self._quality.stats())
        # executable-ledger block (obs/ledger.py): lowering/compile/
        # cache counters + per-executable fingerprints + roofline MFU —
        # present only for real-model engines with obs.ledger on, so
        # fake-replica and ledger-off schemas stay byte-identical
        if self._ledger is not None:
            out.update(self._ledger.stats())
        # fixed-bucket histogram + SLO state (obs/export.py): the
        # scrapeable /metrics face; replica histograms merge exactly at
        # the router because the buckets are fixed by contract
        hist = self._hist.snapshot()
        out["serve_latency_hist"] = hist
        if float(self.cfg.obs.slo_latency_ms) > 0:
            with self._stats_lock:
                requests, failures = self._requests, self._server_errors
            out["serve_slo"] = slo_state(
                hist, requests, failures,
                self.cfg.obs.slo_latency_ms,
                self.cfg.obs.slo_error_budget)
        # incident plane: the verdicts this stats pass just computed
        # become flight-recorder triggers (dedup windows make the
        # heartbeat-cadence re-evaluation safe), and the incident_*/
        # alert_* block rides the same stats surface to /metrics
        rec = self.incidents
        if rec is not None:
            slo = out.get("serve_slo")
            if slo and slo.get("exhausted"):
                rec.record("slo_exhausted", "critical",
                           trigger={"slo": slo})
            q = out.get("serve_quality")
            if q and q.get("exhausted"):
                rec.record("quality_drift", "critical",
                           trigger={"quality": q})
            out.update(rec.stats())
        return out

    def heartbeat_sample(self) -> dict:
        """Heartbeat `sample` callback — same keys as stats()."""
        return self.stats()

    # ------------------------------------------------------------- close
    def close(self) -> None:
        """Flush everything already queued, then stop the batcher.
        Idempotent; submissions after close fail with engine_closed."""
        with self._stats_lock:
            if self._closed:
                return
            self._closed = True
        self.sessions.close()  # stop the TTL sweeper thread
        # drains in order: queued work still serves. The put can block on
        # a full queue only until the batcher frees a slot (it is still
        # consuming at this point).
        self._q.put(_STOP)
        self._thread.join(timeout=60.0)
        if self._deep_verify_thread is not None:
            # stop the verifier before the ledger flush: an in-progress
            # verification finishes (its row lands), queued-but-unstarted
            # ones stay pending (visible as exec_deep_verify_pending)
            self._deep_verify_stop.set()  # skip any pacing wait
            self._deep_verify_q.put(None)
            self._deep_verify_thread.join(timeout=30.0)
        if self._ledger is not None:
            # after the batcher join: every flush's note_exec has landed,
            # so the exec_timing rows carry the full run's measurements
            self._ledger.flush()
        if self._quality is not None:
            # AFTER the batcher join: drained flushes still submit
            # samples, and the scorer's exit sentinel must queue behind
            # them — the shutdown stats record (server.py's final serve
            # record) sees every tail-of-run sample scored, not
            # abandoned mid-queue.
            self._quality.close()  # stop the quality scorer thread
        # submitters that passed the closed check before we flipped it
        # may still complete a put; wait them out, then fail any request
        # the (now dead) batcher will never see
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with self._stats_lock:
                if self._submitting == 0:
                    break
            time.sleep(0.01)
        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                break
            if req is not _STOP:
                self._fail(req.future, ServeError(
                    "engine_closed", "engine shut down before dispatch",
                    req.rid))

    def __enter__(self) -> "InferenceEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
