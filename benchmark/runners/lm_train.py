"""Runner `lm_train`: one `Trainer.fit` of a language model, driven from
outside as `runners/train.py` drives the flow models (its window, its
controller, its trace and its peak reading are used as they are).

What differs: the data are rows of token ids (a pool made on the device,
Zipf over the vocabulary held; a batch is neighbouring rows of it, a view;
from the seed, or, where the traffic's file has `window_data`, the checked
steps' rows from the seed and the window's pool the same on every seed); the weights and the plain reference come from
`benchmark/reference/<config>.py`'s own functions; the tap keeps each
row's loss beside the total and measures the parameters' change against
leaves made again from the seed, so that no second copy of the parameters
is held on a chip that 16 bytes a parameter already fill; `rows_distinct`
is decided on the rows' bytes; a traced run joins the profile's events to
the program's scopes through the step executable's own text.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import os
import shutil
import tempfile
import threading
import time
import types

import numpy as np

from ..harness import compare, lm_compare, scope_share, spans as span_tools
from ..harness import trace_reduce, traffic as gen
from ..harness.counters import window_means
from . import train as base

N_CHECK_STEPS = base.N_CHECK_STEPS
BREAKDOWN_SCOPES = ("embed", "mla", "mla_proj", "mla_scores", "mla_out",
                    "dense_ffn", "moe", "moe_route", "moe_dispatch",
                    "moe_experts", "moe_shared", "moe_combine", "lm_head",
                    "loss_ce", "optimizer")


def token_pool(key, rows: int, seq_len: int, vocab: int, exponent: float):
    """int32[rows, seq_len + 1] made on the device in one call: ids drawn
    from a Zipf law over the `vocab` ids held."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(k):
        p = 1.0 / jnp.arange(1, vocab + 1, dtype=jnp.float32) ** exponent
        cdf = jnp.cumsum(p / jnp.sum(p))
        u = jax.random.uniform(k, (rows, seq_len + 1))
        return jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1).astype(jnp.int32)

    return make(key)


class PoolTokens:
    """A pool of rows; every batch is `batch_size` neighbouring rows of it,
    the first drawn with the rng the program's pipeline hands in, handed
    over as a view. `first_rows`: rows that the first batches take in
    turn, in the pool's place (the traffic's `window_data`: the steps that
    `correct` follows see the seed's own rows, the window the same rows on
    every seed; the rng is drawn from all the same, so that its stream
    does not follow the seed either)."""

    mean = (0.0, 0.0, 0.0)

    def __init__(self, rows: np.ndarray, epoch_pairs: int,
                 first_rows: np.ndarray | None = None):
        self.rows, self.first_rows, self.given = rows, first_rows, 0
        self.num_train, self.num_val = int(epoch_pairs), 0

    def sample_train(self, batch_size, iteration=None, rng=None, **_):
        rng = rng or np.random
        first = int(rng.randint(0, len(self.rows) - batch_size + 1))
        if self.first_rows is not None and \
                self.given + batch_size <= len(self.first_rows):
            self.given += batch_size
            return {"tokens": self.first_rows[self.given - batch_size:self.given]}
        return {"tokens": self.rows[first:first + batch_size]}

    def sample_val(self, batch_size, batch_id):
        raise RuntimeError("the benchmark's window never reaches an eval")

    def cache_stats(self) -> dict:
        return {"hits": 0, "misses": 0, "evictions": 0, "bytes": 0, "entries": 0}


def weights_key(ctx):
    """The seed's key of the weights: the reference's `make_leaf` moves the
    configuration's base draw by it (`weights` in the configuration's file)."""
    return gen.jax_key(ctx.seed, 2)


def flat_params(tree) -> dict:
    from flax.traverse_util import flatten_dict

    return {"/".join(k): v for k, v in flatten_dict(tree).items()}


class StepTap:
    """Stands where the trainer's step callable stands. Passes every call
    through; observes the first N_CHECK_STEPS; ends `fit` when told to."""

    def __init__(self, inner, change_norms, beta1: float, keep_specs: bool):
        import jax
        import jax.numpy as jnp

        self.inner, self.beta1 = inner, beta1
        self._change_norms, self.keep_specs = change_norms, keep_specs
        self.calls = 0
        self.stop = threading.Event()
        self.batches, self.losses, self.row_losses = [], [], []
        self.mu_norms = self.dparam_norms = self.specs = None
        self._norms = jax.jit(lambda t: jax.tree_util.tree_map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))

    def lower(self, *a, **k):
        return self.inner.lower(*a, **k)

    def __call__(self, state, batch):
        from deepof_tpu.obs import trace as obs_trace

        if self.stop.is_set():
            raise base.WindowClosed()
        i = self.calls
        self.calls += 1
        if i == 0:
            obs_trace.instant(span_tools.CLOCK_MARK,
                              perf_counter=time.perf_counter())
            if self.keep_specs:
                self.specs = scope_share.arg_specs((state, batch))
        if i < N_CHECK_STEPS:
            self.batches.append(np.asarray(batch["tokens"]))
        state, metrics = self.inner(state, batch)
        if i < N_CHECK_STEPS:
            self.losses.append(metrics["total"])
            self.row_losses.append(metrics["loss_rows"])
        if i == 0:
            self.mu_norms = self._norms(base._adam_mu(state.opt_state))
        if i == N_CHECK_STEPS - 1:
            self.dparam_norms = self._change_norms(flat_params(state.params))
        return state, metrics

    def readings(self) -> dict:
        return {"losses": [float(x) for x in self.losses],
                "row_losses": [[float(v) for v in np.asarray(x).reshape(-1)]
                               for x in self.row_losses],
                "grad_norms": {k: float(v) / (1.0 - self.beta1)
                               for k, v in flat_params(self.mu_norms).items()},
                "dparam_norms": {k: float(v)
                                 for k, v in self.dparam_norms.items()}}


def program_config(ctx, log_dir: str):
    """`runners/train.py`'s, with the configuration's own file handed to
    the program's `lm` section (the other settings then win over it)."""
    prog = dict(ctx.config["program"])
    prog["set"] = {"lm.config_file": os.path.join(
        ctx.root, "benchmark", "configs", ctx.config_name + ".json"), **prog["set"]}
    shim = types.SimpleNamespace(**{**vars(ctx),
                                    "config": {**ctx.config, "program": prog}})
    return base.program_config(shim, log_dir)


def build_trainer(ctx, log_dir: str):
    """One Trainer with the benchmark's weights and data. Returns
    (trainer, the tap around its step, the reference module)."""
    import jax
    from flax.traverse_util import unflatten_dict

    from deepof_tpu.train.loop import Trainer

    tr = ctx.traffic
    c = ctx.config  # the reference reads the file the program's `lm` section is filled from
    phases = ctx.setup_phases = {
        "imports_s": time.perf_counter() - ctx.t_process_start}
    t = time.perf_counter()
    pcfg = program_config(ctx, log_dir)
    rows_of = lambda seed, n: np.asarray(token_pool(  # noqa: E731
        gen.jax_key(seed, 1), n, tr["seq_len"], c["vocab_size"],
        tr["zipf_exponent"]))
    fixed = tr.get("window_data")
    if fixed:
        # the window trains on one pool in one order on every seed (the
        # order: `train.seed` in the traffic's `set`); the checked steps'
        # rows are the seed's
        ds = PoolTokens(rows_of(fixed["pool_seed"], tr["pool_rows"]),
                        tr["epoch_pairs"], first_rows=rows_of(
                            ctx.seed, N_CHECK_STEPS * tr["batch_per_chip"] * ctx.chips))
    else:
        ds = PoolTokens(rows_of(ctx.seed, tr["pool_rows"]), tr["epoch_pairs"])
    phases["pool_s"] = time.perf_counter() - t
    t = time.perf_counter()
    trainer = Trainer(pcfg, dataset=ds,
                      mesh=None if ctx.chips == len(jax.devices()) else
                      base._mesh_of(ctx))
    phases["trainer_init_s"] = time.perf_counter() - t
    t = time.perf_counter()
    ref = importlib.import_module("benchmark.reference." + c["reference"])
    key = weights_key(ctx)
    have = flat_params(trainer.state.params)
    spec = {path: shape for path, shape, _ in ref.param_spec(c)}
    if set(have) != set(spec) or any(tuple(have[k].shape) != spec[k] for k in have):
        raise SystemExit("benchmark: the program's parameters and the "
                         "reference's differ in name or shape")
    values = ref.make_params(c, key)
    placed = unflatten_dict({tuple(k.split("/")): jax.device_put(
        values[k].astype(have[k].dtype), have[k].sharding) for k in have})
    del values, have
    trainer.state = trainer.state.replace(params=placed)
    jax.block_until_ready(placed)
    phases["weights_s"] = time.perf_counter() - t
    tap = StepTap(trainer.train_step, lambda v: ref.change_norms(c, v, key),
                  c["optim"]["beta1"], keep_specs=ctx.trace)
    trainer.train_step = tap
    return trainer, tap, ref


@contextlib.contextmanager
def compiles_not_kept():
    """What the benchmark compiles after the window (the reference's
    layers, the control's, the routers' choices) is written to no
    persistent compile cache: the machine's holds 192 MiB in all, the
    program's step is 81 of them and the reference's executables 62 more,
    which would push another cell's step out (PERF.md, PR 31). Lookups go
    on; nothing is stored because nothing compiles for 1e9 seconds."""
    import jax

    key = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, key)
    jax.config.update(key, 1e9)
    try:
        yield
    finally:
        jax.config.update(key, before)


_REFERENCE_READINGS: dict = {}


def run_reference(ctx, ref, batches, q=None) -> dict:
    """The reference's readings on `batches`. Kept by seed, hook and the
    batches' bytes: a benchmark run asks once; the calibration's fault runs
    of a seed are fed the sound run's batches and ask again."""
    import jax

    c = ctx.config  # the reference reads the file the program's `lm` section is filled from
    memo = (ctx.name, ctx.seed, q, b"".join(b.tobytes() for b in batches))
    if memo in _REFERENCE_READINGS:
        return _REFERENCE_READINGS[memo]
    t0 = time.perf_counter()
    key = weights_key(ctx)
    steps = ref.make_trainer(c, c["optim"], q=q)
    values = ref.make_params(c, key)
    t1 = time.perf_counter()
    out = steps(values, key, batches)
    out["timing"] = {"weights_s": t1 - t0, "steps_s": time.perf_counter() - t1}
    # the reference's executables go before the next thing is compiled (the
    # calibration's control is a second set of them, and the allocator
    # keeps every loaded executable's temporaries)
    del steps, values
    gc.collect()
    jax.clear_caches()
    _REFERENCE_READINGS[memo] = out
    return out


def choices_agree(ctx, ref, tokens) -> float | None:
    """Share of the (token, slot) choices of the first batch's first row,
    at the initial weights, on which the program's router (its compute
    dtype) and the reference's agree. Reported, not held: near-ties flip
    under bfloat16."""
    import jax
    import jax.numpy as jnp
    from flax.traverse_util import unflatten_dict

    from deepof_tpu.models.registry import model_for

    c = ctx.config  # the reference reads the file the program's `lm` section is filled from
    values = ref.make_params(c, weights_key(ctx))
    row = jnp.asarray(tokens[0])
    want = jax.jit(lambda v, r: ref.chosen_experts(v, r, c))(values, row)
    model = model_for(program_config(ctx, "unused"))
    params = unflatten_dict({tuple(k.split("/")): v for k, v in values.items()})
    _, got = jax.jit(lambda p, r: model.apply(
        {"params": p}, r[None, :-1], r[None, 1:],
        mutable=["intermediates"]))(params, row)
    got = [v["moe"]["chosen"][0] for _, v in sorted(
        got["intermediates"].items(), key=lambda kv: int(kv[0].split("_")[1]))]
    if not want or len(got) != len(want):
        return None
    same = [jnp.mean((jnp.sort(a, -1) == jnp.sort(b, -1)).astype(jnp.float32))
            for a, b in zip(got, want)]
    return float(sum(same) / len(same))


def run(ctx, step_fault=None, also=None, agree: bool | None = None,
        reference: bool = True) -> dict:
    """`step_fault(tap, trainer)`: tests and the calibration plant a fault
    under the tap; None in a run. `reference=False`: the window alone, no
    number compared and so never `correct` (`tools/routing_by_base_key.py`
    reads the routing of many draws in one process). `also(ctx, ref, batches,
    reference_readings, program_readings) -> dict`: the calibration's
    further readings on the same batches; None in a run. `agree`: also
    report `router_choices_agree` (two more whole-row compiles, half a
    minute on the chip: a traced run does, an untraced one does not; the
    calibration reads it on its first seed)."""
    import jax

    agree = ctx.trace if agree is None else agree

    work = tempfile.mkdtemp(prefix="bench_lm_train_")
    try:
        trainer, tap, ref = build_trainer(ctx, os.path.join(work, "run"))
        if step_fault is not None:
            step_fault(tap, trainer)
        tr = ctx.traffic
        marks: dict = {}
        trace_dir = os.path.join(work, "profile")
        ctl = base.Controller(tr["warm_steps"], ctx.seconds, tap)
        if ctx.trace:
            def on_open():
                ctl.hold.set()
                threading.Thread(target=base.take_trace, name="bench-trace",
                                 args=(ctx, trace_dir, marks, ctl.hold),
                                 daemon=True).start()
            ctl.on_open = on_open
        inner_log = trainer.logger.log
        records: list = []

        def log(kind, step, **kw):
            inner_log(kind, step, **kw)
            if kind == "train":
                now = time.perf_counter()
                records.append((now, int(step), {k: v for k, v in kw.items()
                                                 if k.startswith("moe_")}))
                ctl.on_record(int(step), now)

        trainer.logger.log = log
        fit_error = None
        t_fit = time.perf_counter()
        try:
            trainer.fit(max_steps=10**9)
        except base.WindowClosed:
            pass
        except Exception as e:  # noqa: BLE001 - reported, then not correct
            fit_error = repr(e)
        t_fit_end = time.perf_counter()
        peak = base.memory_peak(ctx.devices)
        prog = tap.readings() if tap.dparam_norms is not None else None
        batches = tap.batches
        batch = tr["batch_per_chip"] * ctx.chips
        distinct = all(len({r.tobytes() for r in b}) == len(b) for b in batches)
        span_file = os.path.join(work, "run", "trace.json")
        host_spans = span_tools.load_spans(span_file) if ctx.trace and \
            os.path.exists(span_file) else []
        scopes = {}
        if ctx.trace and tap.specs is not None:
            # the step's executable again (a load from the compile cache):
            # its text names every instruction's scope
            scopes = scope_share.executable_scopes(tap.inner, tap.specs)
        # free the program before the reference touches the chip
        # (deleted, not only dropped: on the chip `del` + `gc.collect()` left
        # the state's 6.9 GB in use although nothing but this frame held the
        # trainer, and the reference then died for memory: PERF.md, PR 31)
        for leaf in jax.tree_util.tree_leaves(trainer.state):
            leaf.delete()
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
        inside = lambda t: windowed and ctl.t0 < t <= ctl.t1  # noqa: E731
        in_window = [r for t, _, r in records if inside(t)]
        t_ref = time.perf_counter()
        numbers, extra_readings, reference_s = {}, {}, 0.0
        if reference and prog is not None and fit_error is None:
            with compiles_not_kept():
                refr = run_reference(ctx, ref, batches)
                numbers = lm_compare.train_numbers(prog, refr)
                extra_readings["reference_timing"] = refr.get("timing")
                reference_s = time.perf_counter() - t_ref
                if agree:
                    extra_readings["router_choices_agree"] = choices_agree(
                        ctx, ref, batches[0])
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
                      "tokens_per_s": (steps * batch * tr["seq_len"] / window_s
                                       / ctx.chips) if windowed else None,
                      # every `moe_*` counter of the window's records
                      **window_means(in_window),
                      "reference_s": reference_s,
                      "teardown_s": t_fit_end - (ctl.t1 or t_fit_end),
                      "worst_leaf": where, "fit_error": fit_error,
                      "setup_phases": ctx.setup_phases,
                      "numbers": numbers,
                      **extra_readings},
        }
        # every train record of the run, the window's marked: for the tools
        out["records"] = [{"step": s, "in_window": inside(t), **r}
                          for t, s, r in records]
        if ctx.trace:
            out.update(observe(ctx, ctl, marks, trace_dir, host_spans, batch,
                               scopes, in_window))
        return out
    finally:
        shutil.rmtree(work, ignore_errors=True)


def observe(ctx, ctl, marks, trace_dir, host_spans, batch, scopes,
            records) -> dict:
    """`runners/train.py::observe`, and beside it what the scope and the
    counter readers read: the instruction-to-scope map and the window's
    records."""
    out = base.observe(ctx, ctl, marks, trace_dir, host_spans, batch, scopes,
                       BREAKDOWN_SCOPES)
    out["observed"]["records"] = records
    return out
