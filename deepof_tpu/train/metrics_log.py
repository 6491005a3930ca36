"""Structured metrics logging + step timing + the async metrics drain.

Replaces the reference's print-only observability (SURVEY.md §5.5): every
record is one JSON line (machine-parseable, the `analyze_test_loss.py`
replacement reads it back), mirrored to stdout. StepTimer reports
steps/sec and image-pairs/sec/chip — the BASELINE.json north-star metric —
plus per-phase host time (assemble / put / dispatch / fetch) so dispatch/
fetch overlap is verifiable in CI and readable in bench logs.

AsyncFetcher is the loop's latency-hiding half (DESIGN.md "Execution
layer"): a synchronous `device_get` between dispatches serializes
dispatch->fetch->dispatch, and the slower the fetch the more of the step
it costs; draining metric values on
a bounded background consumer lets the next batch dispatch while the
previous call's fetch is still in flight.
"""

from __future__ import annotations

import json
import math
import os
import queue
import threading
import time

import jax
import numpy as np

from ..obs import trace as obs_trace
from ..resilience.healing import retry_bounded


def _scalarize(v):
    if v is None or isinstance(v, (str, bool, int)):
        return v
    if isinstance(v, dict):
        # map/state-kind counters (obs/registry.py) ride train records
        # as nested objects — e.g. the recipe engine's
        # recipe_draws_by_dataset — scalarized value-wise
        return {k: _scalarize(x) for k, x in v.items()}
    a = np.asarray(v)
    return a.tolist() if a.ndim else float(a)


def _json_safe(v):
    """Non-finite floats -> None: `json.dumps` would otherwise write bare
    `NaN`/`Infinity` tokens — not JSON — into metrics.jsonl, breaking
    strict parsers (analyze.py round-trips, jq, browsers). null keeps the
    key visible (a NaN loss is information) while the file stays JSON."""
    if isinstance(v, float) and not math.isfinite(v):
        return None
    if isinstance(v, list):
        return [_json_safe(x) for x in v]
    if isinstance(v, dict):
        return {k: _json_safe(x) for k, x in v.items()}
    return v


class MetricsLogger:
    def __init__(self, log_dir: str, filename: str = "metrics.jsonl",
                 echo: bool = True):
        # Multi-host: one writer — every process computes identical metrics
        # (state is replicated), so non-primary hosts would only interleave
        # duplicate lines into a shared log_dir.
        self._primary = jax.process_index() == 0
        self.path = os.path.join(log_dir, filename)
        if self._primary:
            os.makedirs(log_dir, exist_ok=True)
            self._f = open(self.path, "a", buffering=1)
        self.echo = echo and self._primary
        # train records arrive from the AsyncFetcher consumer thread while
        # info/eval/warn records come from the main loop — serialize writes
        # so jsonl lines never interleave mid-record
        self._lock = threading.Lock()

    def log(self, kind: str, step: int, **metrics) -> None:
        if not self._primary:
            return
        rec = {"kind": kind, "step": int(step), "time": time.time()}
        rec.update({k: _json_safe(_scalarize(v)) for k, v in metrics.items()})
        with self._lock:
            # allow_nan=False backstops _json_safe: an unsanitized
            # non-finite must fail loudly here, not corrupt the log
            self._f.write(json.dumps(rec, allow_nan=False) + "\n")
            if self.echo:
                brief = {k: (round(v, 6) if isinstance(v, float) else v)
                         for k, v in rec.items() if k != "time"}
                print(brief, flush=True)

    def close(self) -> None:
        if self._primary:
            self._f.close()


class StepTimer:
    """Cumulative steps/sec + items/sec/chip over *training* time only.

    The first tick after construction or `pause()` only arms the timer, so
    the compile step and any paused-over work (eval sweeps, checkpoint
    saves) are excluded from the rates.

    `phase(name, dt)` additionally accumulates per-phase host time — the
    dispatch-timeline instrument: `assemble` (waiting on the prefetcher),
    `put` (host->device staging, recorded by the prefetch thread),
    `dispatch` (the async step call), `fetch` (device->host value reads,
    recorded by the AsyncFetcher consumer). Under full overlap,
    fetch time stops appearing on the main thread's critical path while
    still being accounted here.

    `count(name)` accumulates named event counters — the loop's
    starvation instrument: `starved` counts steps where the main thread
    measurably waited on the input side (the device had nothing to eat).
    Counters travel with `counters()` into train logs and bench output.
    """

    def __init__(self, items_per_step: int, n_chips: int = 1):
        self.items_per_step = items_per_step
        self.n_chips = max(n_chips, 1)
        self._last: float | None = None
        self._elapsed = 0.0
        self._steps = 0
        self._phases: dict[str, float] = {}
        self._phase_counts: dict[str, int] = {}
        self._counters: dict[str, int] = {}

    def phase(self, name: str, seconds: float) -> None:
        """Accumulate host seconds spent in a named loop phase. Called
        from the main loop AND the prefetch/fetch threads — distinct
        names per thread, so the GIL-atomic dict ops suffice."""
        self._phases[name] = self._phases.get(name, 0.0) + seconds
        self._phase_counts[name] = self._phase_counts.get(name, 0) + 1

    def count(self, name: str, n: int = 1) -> None:
        """Accumulate a named event counter (e.g. `starved`)."""
        self._counters[name] = self._counters.get(name, 0) + n

    def counters(self) -> dict[str, int]:
        """Event-counter totals (snapshot-first, same rationale as
        `phases()`)."""
        return dict(self._counters)

    def phases(self) -> dict[str, float]:
        """Per-phase totals, `phase_<name>_s` keyed (log/bench-ready).
        Snapshot first: called from the fetcher thread while the main
        loop may be inserting a new phase key (C-level dict copy is
        atomic under the GIL; iterating the live dict is not)."""
        return {f"phase_{k}_s": round(v, 4)
                for k, v in sorted(dict(self._phases).items())}

    def tick(self) -> None:
        """Record one completed step."""
        now = time.perf_counter()
        if self._last is not None:
            self._elapsed += now - self._last
            self._steps += 1
        self._last = now

    def pause(self) -> None:
        """Exclude wall time until the next tick (eval / checkpoint)."""
        self._last = None

    def rates(self) -> dict[str, float]:
        if not self._steps or self._elapsed <= 0.0:
            return {"steps_per_sec": 0.0, "items_per_sec_per_chip": 0.0}
        sps = self._steps / self._elapsed
        return {
            "steps_per_sec": sps,
            "items_per_sec_per_chip": sps * self.items_per_step / self.n_chips,
        }

    def reset(self) -> None:
        self._last, self._elapsed, self._steps = None, 0.0, 0
        self._phases, self._phase_counts = {}, {}
        self._counters = {}

    def mark(self) -> tuple[float, int]:
        """Snapshot for `rewind` — taken when a checkpoint is saved."""
        return (self._elapsed, self._steps)

    def rewind(self, mark: tuple[float, int]) -> None:
        """Drop the time AND step count accumulated since `mark` (a NaN
        rollback discards those steps; keeping them would skew rates)."""
        self._elapsed, self._steps = mark
        self._last = None


def _fetch_with_retry(fetch, tree, seq: int, retries: int, backoff_s: float,
                      injector, count_retry) -> dict:
    """Device->host value fetch on the shared bounded retry ladder
    (resilience/healing.py): a transient device->host transfer error —
    or an injected `fetch` fault — is retried with exponential backoff instead
    of dooming the run at the next submit/drain. `seq` keys injection
    deterministically (fetch consumption order == submit order)."""

    def once():
        if injector is not None:
            injector.check("fetch", seq)
        return fetch(tree)

    return retry_bounded(once, retries=retries, backoff_s=backoff_s,
                         on_retry=count_retry)


#: In-flight fetches the train loop allows. A full queue blocks the next
#: dispatch (`submit`'s `submit_wait` span, which the benchmark's
#: `loop_self_pct.*` reads), so the host never runs more than two calls
#: ahead of the device.
FETCH_DEPTH = 2


class AsyncFetcher:
    """Bounded-depth background drain of device metric values.

    The main loop `submit()`s a (tag, device pytree, callback) and keeps
    dispatching; a consumer thread fetches the values (`jax.device_get`
    blocks until the step that produced them completes) and runs the
    callback with the host pytree. The in-flight bound is the honesty
    mechanism (DESIGN.md "Benchmark honesty"): `submit()` blocks while
    `depth` submitted-but-unfetched calls are outstanding (counted under
    a condition variable, so admission and the `max_in_flight` witness
    are race-free), and every recorded fetch duration is a *completed*
    value fetch — the only clock this repo trusts. The queue itself is
    unbounded so `close()` can always enqueue its stop sentinel — even
    when the consumer is wedged in a hung `device_get` (dead device),
    teardown proceeds to checkpoint finalization instead of hanging.

    Callback/fetch exceptions are re-raised on the next submit()/drain()
    (the Prefetcher's surface-on-get idiom). `stats()` reports completed
    fetch count, total fetch seconds, and the max observed in-flight
    depth — the overlap witness the CPU pipelining test pins.
    """

    _STOP = object()

    def __init__(self, depth: int = 2, fetch_fn=None,
                 timer: StepTimer | None = None, retries: int = 0,
                 backoff_s: float = 0.05, injector=None):
        self._fetch = fetch_fn if fetch_fn is not None else jax.device_get
        self._timer = timer
        self._retries = max(int(retries), 0)
        self._backoff = max(float(backoff_s), 0.0)
        self._inj = injector
        self._retry_count = 0
        self._seq = 0  # fetches consumed, = submit order (FIFO queue)
        self._depth = max(depth, 1)
        self._q: queue.Queue = queue.Queue()  # unbounded; _cv is the bound
        self._exc: BaseException | None = None
        self._cv = threading.Condition()
        self._in_flight = 0
        self._max_in_flight = 0
        self._fetches = 0
        self._fetch_s = 0.0
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="metrics-fetcher")
        self._thread.start()

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is self._STOP:
                self._q.task_done()
                return
            tag, tree, callback = item
            try:
                seq, self._seq = self._seq, self._seq + 1
                t0 = time.perf_counter()
                with obs_trace.span("fetch"):
                    host = _fetch_with_retry(self._fetch, tree, seq,
                                             self._retries, self._backoff,
                                             self._inj, self._count_retry)
                dt = time.perf_counter() - t0
                with self._cv:
                    self._fetches += 1
                    self._fetch_s += dt
                if self._timer is not None:
                    self._timer.phase("fetch", dt)
                callback(tag, host)
            except BaseException as e:  # noqa: BLE001 - surfaced on submit/drain
                self._exc = e
            finally:
                with self._cv:
                    self._in_flight -= 1
                    self._cv.notify()
                self._q.task_done()

    def _raise_pending(self) -> None:
        if self._exc is not None:
            exc, self._exc = self._exc, None
            raise exc

    def submit(self, tag, tree, callback) -> None:
        """Enqueue a fetch; blocks while `depth` fetches are in flight."""
        self._raise_pending()
        # admission and accounting are one atomic section: the counter
        # can never go negative or miss a peak, and a submit blocked in
        # wait() is by definition NOT in flight (that block is the bound)
        with self._cv:
            if self._in_flight >= self._depth:
                # the bound at work: where a loop that runs ahead of the
                # device spends its time
                with obs_trace.span("submit_wait"):
                    while self._in_flight >= self._depth:
                        self._cv.wait()
            self._in_flight += 1
            self._max_in_flight = max(self._max_in_flight, self._in_flight)
        self._q.put((tag, tree, callback))

    def drain(self, timeout: float | None = None) -> bool:
        """Block until every submitted fetch has completed and its
        callback has run (called before eval / checkpoint / rollback so
        those decisions see all host-visible metrics). With a timeout
        (the finalize path, where a consumer wedged in a hung
        device_get must not hang teardown away from ckpt.finalize()),
        gives up after `timeout` seconds and returns False; mid-loop
        barriers pass None — there a hung fetch means a hung device and
        the loop could not proceed anyway."""
        if timeout is None:
            self._q.join()
        else:
            deadline = time.monotonic() + timeout
            with self._q.all_tasks_done:
                while self._q.unfinished_tasks:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        return False
                    self._q.all_tasks_done.wait(remaining)
        self._raise_pending()
        return True

    def _count_retry(self) -> None:
        self._retry_count += 1  # GIL-atomic; read by stats()

    def stats(self) -> dict[str, float]:
        with self._cv:
            return {"fetches": self._fetches,
                    "fetch_s": round(self._fetch_s, 4),
                    "fetch_retries": self._retry_count,
                    "max_in_flight": self._max_in_flight}

    def close(self) -> None:
        # never blocks: the queue is unbounded, so a wedged consumer
        # (hung device_get on a dead device) can't stall teardown — the
        # daemon thread is abandoned after the join timeout and fit()'s
        # finally still reaches prefetch.close() / ckpt.finalize()
        self._q.put(self._STOP)
        self._thread.join(timeout=5.0)


class ProfilerSession:
    """Optional `jax.profiler` trace capture (SURVEY.md §5.1).

    Two modes:
      - whole-run (`enabled=True`, `steps=None`): the legacy behavior —
        start at loop entry, stop at teardown. Includes the first-step
        compile and grows with run length.
      - step window (`steps=(K, N)`, e.g. `--profile-steps 5:10`): the
        loop reports progress via `observe(gstep)`; the trace starts at
        the first iteration with gstep >= K and stops once gstep >= N.
        K >= 1 excludes the compile step, and the bounded
        window keeps the profile small enough to bring back from the chip
        (a whole-run trace of a long fit can run to GBs).
    """

    def __init__(self, log_dir: str, enabled: bool = False,
                 steps: tuple[int, int] | None = None):
        self.log_dir = os.path.join(log_dir, "profile")
        if steps is not None:
            start, stop = int(steps[0]), int(steps[1])
            if not 0 <= start < stop:
                raise ValueError(
                    f"profile step window must be 0 <= start < stop, "
                    f"got {steps}")
            steps = (start, stop)
        self.steps = steps
        self.enabled = enabled or steps is not None
        self._active = False
        self._done = False

    def maybe_start(self) -> None:
        """Loop entry: whole-run mode starts here; a step window waits
        for observe() so the profile excludes compile + early steps."""
        if self.enabled and self.steps is None and not self._active:
            self._start()

    def observe(self, gstep: int) -> None:
        """Step-window driver, called once per loop iteration with the
        completed-step count. Starts when the NEXT dispatch falls in
        [start, stop). Idempotent; one window per session."""
        if not self.enabled or self.steps is None or self._done:
            return
        start, stop = self.steps
        if self._active:
            if gstep >= stop:
                self._stop()
                self._done = True  # one window; never restart
        elif start <= gstep < stop:
            self._start()

    def maybe_stop(self) -> None:
        if self._active:
            self._stop()

    def _start(self) -> None:
        # the Python tracer off, the host tracer at 2: the program's
        # spans are annotations of their own (obs/trace.py), and a profile
        # then costs and shows what the benchmark's short trace does
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._active = True

    def _stop(self) -> None:
        jax.profiler.stop_trace()
        self._active = False
