"""Runner `train`: one `Trainer.fit` of the program, driven from outside.

Set-up builds ONE `Trainer` (the loop, the prefetcher, the metrics fetcher
and the compiled step, as `deepof_tpu train` has them), gives it weights
and a pool of pairs made from the seed, and lets `fit` run. The first
steps go through that same `fit`: a tap around the trainer's step callable
keeps the first three batches, their losses, the per-leaf norms of Adam's
first moment after step one (the first gradient as the optimizer got it)
and of the parameters' change after step three; where the cell's file
names `grad_cuts`, the first moment itself, on the host. After `warm_steps` the
window opens on a step record (a loss fetched to the host) and closes on
the first record `--seconds` later; the tap then ends `fit` from inside
its next dispatch, so no final checkpoint is written. Only then is the
peak memory read, the program freed and the plain reference run over the
same three batches. A traced run joins the profile's events to the
program's scopes through the step executable's own text
(`harness/scope_share.py`), for the readers that go by scope and for the
line's `breakdown.scopes`.
"""

from __future__ import annotations

import gc
import importlib
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

from ..harness import compare, scope_share, spans as span_tools
from ..harness import trace_reduce, traffic as gen

N_CHECK_STEPS = 3
#: the flow step's scopes in a traced line's `breakdown.scopes`; `corr` is
#: the cost volume's where the program names it (it does not yet)
BREAKDOWN_SCOPES = ("preprocess", "forward", "corr") + tuple(
    f"loss_level_{k}" for k in range(6)) + ("optimizer",)


class WindowClosed(BaseException):
    """Raised inside the step tap to end `fit` once the window has closed."""


class PoolDataset:
    """A pool of pairs made from the seed; every batch is `batch_size`
    neighbouring (so distinct) rows of it, the first drawn with the rng the
    program's pipeline hands in. A batch is a view of the pool: the
    generator hands over memory and copies nothing, so what the host then
    spends on a batch is the program's (staging, the copy to the chip)."""

    def __init__(self, src: np.ndarray, tgt: np.ndarray, mean, epoch_pairs: int):
        self.src, self.tgt = src, tgt
        self.mean = tuple(mean)
        self.num_train, self.num_val = int(epoch_pairs), 0

    def sample_train(self, batch_size, iteration=None, rng=None, **_):
        rng = rng or np.random
        first = int(rng.randint(0, len(self.src) - batch_size + 1))
        rows = slice(first, first + batch_size)
        return {"source": self.src[rows], "target": self.tgt[rows]}

    def sample_val(self, batch_size, batch_id):
        raise RuntimeError("the benchmark's window never reaches an eval")

    def cache_stats(self) -> dict:
        return {"hits": 0, "misses": 0, "evictions": 0, "bytes": 0, "entries": 0}


class StepTap:
    """Stands where the trainer's step callable stands. Passes every call
    through; observes the first N_CHECK_STEPS; ends `fit` when told to."""

    def __init__(self, inner, params0_copy, beta1: float,
                 keep_specs: bool = False, keep_grads: bool = False):
        import jax
        import jax.numpy as jnp

        self.inner, self.p0, self.beta1 = inner, params0_copy, beta1
        self.keep_specs, self.specs = keep_specs, None
        self.keep_grads, self.mu_host = keep_grads, None
        self.calls = 0
        self.stop = threading.Event()
        self.batches, self.losses, self.level_losses = [], [], []
        self.level_smooth = []
        self.mu_norms = self.dparam_norms = None
        norm = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t)
        self._norms = jax.jit(norm)
        self._to_host = jax.device_get
        self._diff_norms = jax.jit(lambda a, b: norm(
            jax.tree_util.tree_map(lambda x, y: x - y, a, b)))

    def lower(self, *a, **k):
        return self.inner.lower(*a, **k)

    def __call__(self, state, batch):
        from deepof_tpu.obs import trace as obs_trace

        if self.stop.is_set():
            raise WindowClosed()
        i = self.calls
        self.calls += 1
        if i == 0:
            obs_trace.instant(span_tools.CLOCK_MARK,
                              perf_counter=time.perf_counter())
            if self.keep_specs:
                self.specs = scope_share.arg_specs((state, batch))
        if i < N_CHECK_STEPS:
            self.batches.append((np.asarray(batch["source"]),
                                 np.asarray(batch["target"])))
        state, metrics = self.inner(state, batch)
        if i < N_CHECK_STEPS:
            self.losses.append(metrics["total"])
            self.level_losses.append(metrics["scale_total"])
            self.level_smooth.append(metrics["scale_smooth"])
        if i == 0:
            self.mu_norms = self._norms(_adam_mu(state.opt_state))
            if self.keep_grads:
                self.mu_host = self._to_host(_adam_mu(state.opt_state))
        if i == N_CHECK_STEPS - 1:
            self.dparam_norms = self._diff_norms(state.params, self.p0)
            self.p0 = None
        return state, metrics

    def readings(self) -> dict:
        from flax.traverse_util import flatten_dict

        flat = lambda t: {"/".join(k): float(v)  # noqa: E731
                          for k, v in flatten_dict(t).items()}
        rows = lambda xs: [[float(v) for v in np.asarray(x).reshape(-1)]  # noqa: E731
                           for x in xs]
        out = {"losses": [float(x) for x in self.losses],
               "level_losses": rows(self.level_losses),
               "level_smooth_losses": rows(self.level_smooth),
               "grad_norms": {k: v / (1.0 - self.beta1)
                              for k, v in flat(self.mu_norms).items()},
               "dparam_norms": flat(self.dparam_norms)}
        if self.mu_host is not None:
            out["first_grads"] = {
                "/".join(k): np.asarray(v) / np.float32(1.0 - self.beta1)
                for k, v in flatten_dict(self.mu_host).items()}
        return out


def _adam_mu(opt_state):
    """Adam's first moment, wherever the optimizer's state keeps it."""
    import jax

    found = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0].mu


class Controller:
    """Opens and closes the window on step records."""

    def __init__(self, warm_steps: int, seconds: float, tap: StepTap,
                 on_open=None):
        self.warm_steps, self.seconds, self.tap = warm_steps, seconds, tap
        self.on_open = on_open
        self.records: list[tuple[int, float]] = []
        self.t0 = self.s0 = self.t1 = self.s1 = None
        self.hold = threading.Event()  # set while a trace is being taken

    def on_record(self, step: int, t: float) -> None:
        self.records.append((step, t))
        if self.t0 is None:
            if step >= self.warm_steps:
                self.t0, self.s0 = t, step
                if self.on_open:
                    self.on_open()
        elif self.t1 is None and t - self.t0 >= self.seconds \
                and not self.hold.is_set():
            self.t1, self.s1 = t, step
            self.tap.stop.set()


def reference_hp(config: dict) -> dict:
    hp = dict(config["loss"])
    hp.update(config["optim"])
    hp["mean"] = config["mean"]
    return hp


def program_config(ctx, log_dir: str):
    from deepof_tpu import cli

    prog, tr = ctx.config["program"], ctx.traffic
    sets = dict(prog["set"])
    sets.update({
        "data.batch_size": tr["batch_per_chip"] * ctx.chips,
        "mesh.data": ctx.chips,
        "train.log_every": tr["log_every"],
        "train.seed": int(gen.seed_words(ctx.seed, 1)[0] >> 1),
        "obs.trace": "true" if ctx.trace else "false",
    })
    sets.update(tr.get("set", {}))
    argv = ["train", "--preset", prog["preset"], "--log-dir", log_dir]
    if prog.get("model"):
        argv += ["--model", prog["model"]]
    for k, v in sets.items():
        argv += ["--set", f"{k}={v}"]
    return cli.config_for(argv)


def build_trainer(ctx, log_dir: str):
    """One Trainer with the benchmark's weights and data. Returns
    (trainer, the tap around its step, the reference module)."""
    import jax
    import jax.numpy as jnp
    from flax.traverse_util import flatten_dict, unflatten_dict

    from deepof_tpu.train.loop import Trainer

    cfgj, tr = ctx.config, ctx.traffic
    h, w = cfgj["image_size"]
    phases = ctx.setup_phases = {
        "imports_s": time.perf_counter() - ctx.t_process_start}
    t = time.perf_counter()
    pcfg = program_config(ctx, log_dir)
    src, tgt = gen.textured_frames(gen.jax_key(ctx.seed, 1), tr["pool_pairs"],
                                   h, w, tr.get("feature_px", 8),
                                   tr.get("max_shift", 4))
    ds = PoolDataset(np.asarray(src), np.asarray(tgt), cfgj["mean"],
                     tr["epoch_pairs"])
    del src, tgt
    phases["pool_s"] = time.perf_counter() - t
    t = time.perf_counter()
    trainer = Trainer(pcfg, dataset=ds,
                      mesh=None if ctx.chips == len(jax.devices()) else
                      _mesh_of(ctx))
    phases["trainer_init_s"] = time.perf_counter() - t
    t = time.perf_counter()
    ref = importlib.import_module("benchmark.reference." + cfgj["reference"])
    values = make_weights(ctx, ref)
    have = flatten_dict(trainer.state.params)
    want = {tuple(k.split("/")): v for k, v in values.items()}
    if set(have) != set(want) or any(have[k].shape != want[k].shape for k in have):
        raise SystemExit("benchmark: the program's parameters and the "
                         "reference's differ in name or shape")
    p0_copy = unflatten_dict({k: jnp.copy(v) for k, v in want.items()})
    placed = unflatten_dict({k: jax.device_put(want[k].astype(have[k].dtype),
                                               have[k].sharding) for k in have})
    trainer.state = trainer.state.replace(params=placed)
    jax.block_until_ready(placed)
    phases["weights_s"] = time.perf_counter() - t
    tap = StepTap(trainer.train_step, p0_copy, cfgj["optim"]["beta1"],
                  keep_specs=ctx.trace,
                  keep_grads=bool(ctx.cell.get("grad_cuts")))
    trainer.train_step = tap
    return trainer, tap, ref


def _mesh_of(ctx):
    from deepof_tpu.parallel.mesh import build_mesh

    return build_mesh(devices=ctx.devices)


def make_weights(ctx, ref) -> dict:
    """The configuration's weights, one jitted call, float32: one draw that
    the configuration fixes, moved by a draw from the seed."""
    import jax
    import jax.numpy as jnp

    from ..reference import _common as rc

    h, w = ctx.config["image_size"]
    wcfg = ctx.config["weights"]
    return rc.make_params(ref.forward, jnp.zeros((1, h, w, 6), jnp.float32),
                          jax.random.PRNGKey(int(wcfg["base_key"])),
                          gen.jax_key(ctx.seed, 2), float(wcfg["seed_jitter"]))


def run_reference(ctx, ref, batches, cuts=None, **how) -> dict:
    """The plain reference over the checked batches. `cuts`: the parts whose
    share of the first gradient the cell's file has compared (`grad_cuts`);
    `how`: what the calibration puts in the program's place (`q`, `rows`,
    `keep_grads`) and its `watch`, as `reference/_common.py::make_trainer`
    takes them."""
    from ..reference import _common as rc

    t0 = time.perf_counter()
    if cuts is None:
        cuts = tuple(ctx.cell.get("grad_cuts", ()))
    steps = rc.make_trainer(ref.forward, tuple(ctx.config["flow_scales"]),
                            reference_hp(ctx.config),
                            block=ctx.traffic["reference_block"], cuts=cuts,
                            **how)
    values = make_weights(ctx, ref)
    t1 = time.perf_counter()
    out = steps(values, batches)
    out["timing"] = {"weights_s": t1 - t0, "steps_s": time.perf_counter() - t1,
                     **out.get("timing", {})}
    return out


def take_trace(ctx, trace_dir: str, marks: dict, hold: threading.Event):
    """Runs beside the window: a short profiler trace with the benchmark's
    two annotations around it."""
    import jax

    tr = ctx.traffic
    time.sleep(tr.get("trace_delay_s", 2.0))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.SYNC_START):
            marks["start"] = time.perf_counter()
        time.sleep(tr.get("trace_seconds", 3.0))
        with jax.profiler.TraceAnnotation(trace_reduce.SYNC_END):
            marks["end"] = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
        hold.clear()


def memory_peak(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    print("memory_stats", stats[0], file=sys.stderr)
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


def run(ctx, step_fault=None, also=None) -> dict:
    """`step_fault(tap)`: tests plant a fault under the tap; None in a run.
    `also(ctx, ref, batches, reference_readings, program_readings) -> dict`: the calibration
    tool's further readings on the same batches; None in a run."""
    import jax

    work = tempfile.mkdtemp(prefix="bench_train_")
    try:
        trainer, tap, ref = build_trainer(ctx, os.path.join(work, "run"))
        if step_fault is not None:
            step_fault(tap)
        tr = ctx.traffic
        marks: dict = {}
        trace_dir = os.path.join(work, "profile")
        ctl = Controller(tr["warm_steps"], ctx.seconds, tap)
        if ctx.trace:
            def on_open():
                ctl.hold.set()
                threading.Thread(target=take_trace, name="bench-trace",
                                 args=(ctx, trace_dir, marks, ctl.hold),
                                 daemon=True).start()
            ctl.on_open = on_open
        inner_log = trainer.logger.log

        def log(kind, step, **kw):
            inner_log(kind, step, **kw)
            if kind == "train":
                ctl.on_record(int(step), time.perf_counter())

        trainer.logger.log = log
        fit_error = None
        t_fit = time.perf_counter()
        try:
            trainer.fit(max_steps=10**9)
        except WindowClosed:
            pass
        except Exception as e:  # noqa: BLE001 - reported, then not correct
            fit_error = repr(e)
        t_fit_end = time.perf_counter()
        peak = memory_peak(ctx.devices)
        prog = tap.readings() if tap.dparam_norms is not None else None
        batches = tap.batches
        batch = tr["batch_per_chip"] * ctx.chips
        # on the rows' bytes: the float32 sum of a row moves in steps of 4 at
        # 5e7, and two distinct rows of 64 shared a sum in one run of 24
        distinct = all(len({row.tobytes() for row in s}) == len(s)
                       for s, _ in batches)
        span_file = os.path.join(work, "run", "trace.json")
        host_spans = span_tools.load_spans(span_file) if ctx.trace and \
            os.path.exists(span_file) else []
        scopes = {}
        if ctx.trace and tap.specs is not None:
            # the step's executable again (a load from the compile cache):
            # its text names every instruction's scope
            scopes = scope_share.executable_scopes(tap.inner, tap.specs)
        # free the program before the reference touches the chip
        trainer.train_step = None
        del trainer
        tap.inner = None
        gc.collect()
        jax.clear_caches()

        windowed = ctl.t1 is not None
        steps = (ctl.s1 - ctl.s0) if windowed else 0
        window_s = (ctl.t1 - ctl.t0) if windowed else float("nan")
        if ctl.records:
            ctx.setup_phases["fit_to_first_record_s"] = ctl.records[0][1] - t_fit
        if windowed:
            ctx.setup_phases["first_record_to_window_s"] = ctl.t0 - ctl.records[0][1]
        end_to_end = {}
        if windowed:
            end_to_end = {
                "train_pairs_per_s": steps * batch / window_s / ctx.chips,
                "setup_s": ctl.t0 - ctx.t_process_start,
            }
        t_ref = time.perf_counter()
        numbers, extra_readings, reference_s = {}, {}, 0.0
        if prog is not None and fit_error is None:
            refr = run_reference(ctx, ref, batches)
            numbers = compare.train_numbers(prog, refr)
            extra_readings["reference_timing"] = refr.get("timing")
            reference_s = time.perf_counter() - t_ref
            if also is not None:
                extra_readings.update(also(ctx, ref, batches, refr, prog))
        numbers["rows_distinct"] = 0.0 if distinct and batches else 1.0
        numbers["window_closed"] = 0.0 if windowed and fit_error is None else 1.0
        where = numbers.pop("_where", {})
        limits = dict(ctx.cell["limits"])
        limits.update({"rows_distinct": 0.0, "window_closed": 0.0})
        correct, compared = compare.judge(numbers, limits)
        out = {
            "correct": correct, "attempted": steps, "failed": 0,
            "end_to_end": end_to_end, "compared": compared,
            "memory_peak_bytes": peak,
            "extra": {"window_s": window_s, "steps": steps,
                      "reference_s": reference_s,
                      "teardown_s": t_fit_end - (ctl.t1 or t_fit_end),
                      "worst_leaf": where, "fit_error": fit_error,
                      "setup_phases": ctx.setup_phases,
                      "numbers": numbers,
                      **extra_readings},
        }
        if ctx.trace:
            out.update(observe(ctx, ctl, marks, trace_dir, host_spans, batch,
                               scopes, BREAKDOWN_SCOPES))
        return out
    finally:
        shutil.rmtree(work, ignore_errors=True)


def observe(ctx, ctl, marks, trace_dir, host_spans, batch, scopes,
            breakdown_scopes) -> dict:
    """What the per-layer readers read: the device trace reduced, the host
    spans, the window and its counts, and `scopes`, the step executable's
    {instruction: op_name} (`harness/scope_share.py`), by which the trace's
    events find the program's scopes."""
    planes = trace_reduce.load_xplane(trace_dir)
    if os.environ.get("BENCH_TRACE_DUMP"):
        from ..harness.trace_dump import dump, record_small

        dump(planes, os.environ["BENCH_TRACE_DUMP"])
        record_small(planes, os.environ["BENCH_TRACE_DUMP"] + ".small.json")
    lo, _ = trace_reduce.find_marks(planes)
    offset = (lo - marks["start"]) if lo is not None and "start" in marks else 0.0
    on_profile_clock = [(n, a + offset, b + offset) for n, _, a, b in host_spans]
    dev = trace_reduce.reduce_device(
        planes, host_spans=on_profile_clock,
        prefix="/device:TPU:" if ctx.require_tpu else "/host:CPU")
    windowed = ctl.t1 is not None
    observed = {
        "cell": ctx.name, "config": ctx.config, "traffic": ctx.traffic,
        "chips": ctx.chips, "peaks": ctx.peaks, "device": dev,
        "spans": host_spans, "window": (ctl.t0, ctl.t1),
        "trace_window_host": (marks.get("start"), marks.get("end")),
        "steps": (ctl.s1 - ctl.s0) if windowed else 0,
        "pairs": ((ctl.s1 - ctl.s0) * batch) if windowed else 0,
        "batch": batch, "op_scopes": scopes,
    }
    breakdown = {"device_ops": dev["device_ops"], "idle_gaps": dev["idle_gaps"]}
    by_scope = scope_share.breakdown(observed, breakdown_scopes)
    if by_scope is not None:
        breakdown["scopes"] = by_scope
    return {"observed": observed, "breakdown": breakdown}
