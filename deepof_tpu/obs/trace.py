"""Lock-cheap, ring-buffered span tracer -> Chrome trace-event JSON.

Every instrumented site (`train/loop.py` dispatch/eval/ckpt/rollback,
`train/metrics_log.py` fetch, `data/prefetch.py` put, `data/pipeline.py`
worker assemble) calls the module-level `span(name, **args)`; with no
tracer installed that is one global read + a shared no-op context
manager, so instrumentation costs nothing when tracing is off and the
instrumented modules never need a tracer threaded through their
constructors.

Design constraints, in order:

  - The hot path takes NO lock: completed spans are appended to a
    `collections.deque(maxlen=ring_size)` — append and the implicit
    oldest-eviction are single C-level ops, atomic under the GIL, so
    pipeline workers / prefetch / fetcher / main all record concurrently
    without contending. Memory is bounded by construction: the ring
    keeps the newest `ring_size` spans (the window that matters when a
    watchdog fires).
  - Timestamps come from `time.perf_counter()` (CLOCK_MONOTONIC —
    comparable across threads of one process), rebased to the process's
    start where the OS tells it (`process_start`), else to the tracer's
    construction, so `ts` >= 0 for everything the process did since.
  - `flush()` writes the Chrome trace-event format (JSON object with a
    `traceEvents` list of "X" complete events + "M" thread-name
    metadata) atomically (tmp + rename), so a viewer — or the watchdog,
    which flushes mid-run — never reads a torn file. Perfetto and
    chrome://tracing both load it directly.

One timeline: while a real `Tracer` is installed every span also enters a
`jax.profiler.TraceAnnotation` of the same name, so a profile taken by
anyone meanwhile (`--profile-steps`, the benchmark's short trace) holds the
program's spans on the host plane's thread lines, on the profiler's own
clock, beside `PjitFunction(step)` and the device's operations. A span
given `step_trace=(name, n)` (the loop's `dispatch`) is wrapped in a
`StepTraceAnnotation` besides, which numbers the step in the profile. The
mirror costs about a microsecond a span and nothing with no tracer
installed; `flush()` exports the tracer's `perf_counter` epoch so
`trace.json` can be laid over any other `perf_counter` record.

Set-up before the first tracer: `record_setup` turns two marks into
spans after the fact, once a process: `boot` (process start -> the
package's first line) and `import` (-> the end of `train/loop.py`'s
import).

Stdlib-only at import (see obs/__init__ docstring): the mirror finds jax
in `sys.modules` and never imports it, so a process that has not loaded
jax (the fleet router) cannot be running its profiler and mirrors nothing.
"""

from __future__ import annotations

import json
import os
import re
import sys
import threading
import time
from collections import deque

#: CPython's auto-generated thread names ("Thread-12 (handler_func)"):
#: ThreadingHTTPServer spawns one uniquely-auto-named thread per HTTP
#: request, and keying tracks by (tid, emit-time name) would otherwise
#: mint one single-span track per REQUEST once idents recycle. The
#: serial number carries no identity — collapse it so every
#: auto-named thread running the same function shares one track name,
#: while explicitly-named threads (prefetch, serve-batcher,
#: pipeline-worker-N, ...) keep the full recycle-split fix.
_AUTO_THREAD_NAME = re.compile(r"^Thread-\d+( \(.*\))?$")

_PROC_STAT = "/proc/self/stat"


def process_start() -> float | None:
    """This process's start on the `time.perf_counter` clock, to the
    kernel's clock tick (10 ms): field 22 of `/proc/self/stat` (clock
    ticks after boot) against CLOCK_BOOTTIME now. None where either is
    missing (not Linux)."""
    try:
        with open(_PROC_STAT) as f:
            # field 2, the command, may hold spaces: count after its ")"
            after_comm = f.read().rpartition(")")[2].split()
        started = int(after_comm[22 - 3]) / os.sysconf("SC_CLK_TCK")
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.perf_counter() - age


class _NullSpan:
    """Shared, stateless no-op context manager (safe to re-enter from
    any number of threads at once)."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **args) -> None:
        """No-op counterpart of _Span.set."""


_NULL_SPAN = _NullSpan()


class NullTracer:
    """The uninstalled state: every operation is a no-op."""

    path: str | None = None

    def span(self, name: str, **args) -> _NullSpan:
        return _NULL_SPAN

    def instant(self, name: str, **args) -> None:
        pass

    def flush(self, path: str | None = None) -> str | None:
        return None


class _Span:
    """One live span: created by Tracer.span, records on __exit__, and
    for its lifetime is an annotation in the profiler's trace too."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_step", "_mirror")

    def __init__(self, tracer: "Tracer", name: str, args: dict | None,
                 step_trace: tuple[str, int] | None = None):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._step = step_trace

    def __enter__(self) -> "_Span":
        self._mirror = _profiler_annotations(self._name, self._step)
        for ann in self._mirror:
            ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set(self, **args) -> None:
        """Attach args discovered DURING the span (e.g. the batcher
        learns its request ids only while accumulating the batch)."""
        if self._args is None:
            self._args = {}
        self._args.update(args)

    def __exit__(self, *exc) -> bool:
        self._tracer._record(self._name, self._t0, time.perf_counter(),
                             self._args)
        for ann in reversed(self._mirror):
            ann.__exit__(*exc)
        return False


def _profiler_annotations(name: str, step_trace) -> tuple:
    """The span's twins in the profiler's trace, outermost first: a
    `StepTraceAnnotation(step name, step_num=n)` where the span is a step
    of the loop, then a `TraceAnnotation(name)`. Empty where this process
    never loaded jax."""
    jax = sys.modules.get("jax")
    if jax is None:
        return ()
    ann = jax.profiler.TraceAnnotation(name)
    if step_trace is None:
        return (ann,)
    return (jax.profiler.StepTraceAnnotation(
        step_trace[0], step_num=int(step_trace[1])), ann)


class Tracer:
    """Ring-buffered span recorder; see module docstring.

    path: default flush destination (conventionally
        `<log_dir>/trace.json`).
    ring_size: max retained events — spans beyond it evict the oldest
        (bounded memory; a full training run keeps its newest window).
    role / index: process identity stamped into the trace (process_name
        metadata + otherData) so obs/aggregate.py can merge many
        processes' traces into one fleet timeline — "trainer-1",
        "replica-0", "router", "coordinator".
    """

    def __init__(self, path: str | None = None, ring_size: int = 16384,
                 role: str | None = None, index: int | None = None):
        self.path = path
        self.ring_size = max(int(ring_size), 16)
        self.role = role
        self.index = index
        self._events: deque = deque(maxlen=self.ring_size)
        # ts 0: the process's start, so set-up's `boot` span fits on the
        # timeline; where it is unknown, this construction
        self.process_start = process_start()
        now = time.perf_counter()
        self._epoch = now if self.process_start is None else self.process_start
        self._epoch_unix = time.time() - (now - self._epoch)
        # tid -> thread name registry (historical record: a thread whose
        # every event was evicted from the ring is still named in the
        # metadata). NOT the source of truth for event->name binding —
        # each event records its thread's name at EMIT time, so a tid
        # the OS recycled onto a later, differently-named thread cannot
        # retroactively rename earlier spans (the PR 3 last-writer-wins
        # hazard); events() splits such a tid into per-name tracks.
        self._threads: dict[int, str] = {}
        self._dropped = 0  # informational; deque eviction is implicit

    # ------------------------------------------------------------ record
    def span(self, name: str, step_trace: tuple[str, int] | None = None,
             **args) -> _Span:
        return _Span(self, name, args or None, step_trace)

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker (ph='i') — e.g. the watchdog's wedge."""
        now = time.perf_counter()
        tname = self._note_thread()
        self._events.append(("i", name, threading.get_ident(), tname,
                             (now - self._epoch) * 1e6, 0.0, args or None))

    def _note_thread(self) -> str:
        # the registry write is one GIL-atomic dict op; the RETURNED
        # name is what binds the event (emit-time capture — see __init__)
        name = threading.current_thread().name
        m = _AUTO_THREAD_NAME.match(name)
        if m:  # auto-named ephemeral: drop the per-thread serial
            name = "Thread" + (m.group(1) or "")
        self._threads[threading.get_ident()] = name
        return name

    def _record(self, name: str, t0: float, t1: float,
                args: dict | None) -> None:
        tname = self._note_thread()
        if len(self._events) == self.ring_size:
            self._dropped += 1  # append below evicts the oldest
        self._events.append(("X", name, threading.get_ident(), tname,
                             (t0 - self._epoch) * 1e6, (t1 - t0) * 1e6,
                             args))

    # ------------------------------------------------------------- flush
    def process_name(self) -> str:
        """The track label for this process in a merged fleet trace."""
        if self.role is None:
            return "deepof_tpu"
        return (self.role if self.index is None
                else f"{self.role}-{self.index}")

    def events(self) -> list[dict]:
        """Chrome trace-event dicts for the current ring contents.

        Thread tracks are keyed by (tid, emit-time name): a tid the OS
        recycled across differently-named threads splits into one track
        per name (the first name keeps the real tid; later names get
        synthetic tids), so every span renders under the thread that
        actually emitted it."""
        pid = os.getpid()
        out: list[dict] = [{
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": self.process_name()},
        }]
        # snapshot first (C-level copies are GIL-atomic; iterating the
        # live deque while writers append is not)
        threads = dict(self._threads)
        events = list(self._events)
        track: dict[tuple[int, str], int] = {}
        used: set[int] = set()
        next_synthetic = max([e[2] for e in events] + list(threads)
                             + [0]) + 1

        def tid_for(tid: int, tname: str) -> int:
            nonlocal next_synthetic
            key = (tid, tname)
            mapped = track.get(key)
            if mapped is None:
                if tid not in used:
                    mapped = tid
                else:  # recycled ident: a fresh synthetic track
                    mapped = next_synthetic
                    next_synthetic += 1
                used.add(mapped)
                track[key] = mapped
            return mapped

        body: list[dict] = []
        for ph, name, tid, tname, ts, dur, args in events:
            ev: dict = {"ph": ph, "name": name, "cat": "obs", "pid": pid,
                        "tid": tid_for(tid, tname), "ts": round(ts, 1)}
            if ph == "X":
                ev["dur"] = round(dur, 1)
            else:
                ev["s"] = "g"  # instants render process-wide
            if args:
                ev["args"] = args
            body.append(ev)
        # registry-only threads (all their events evicted) still get a
        # track name; an entry contradicting an emit-time binding maps
        # to its own synthetic track instead of renaming the real one
        for tid in sorted(threads):
            tid_for(tid, threads[tid])
        for (tid, tname), mapped in sorted(track.items(),
                                           key=lambda kv: kv[1]):
            out.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": mapped, "args": {"name": tname}})
        out.extend(body)
        return out

    def flush(self, path: str | None = None) -> str | None:
        """Atomically write the trace file; safe to call repeatedly and
        from any thread (the watchdog flushes mid-run, fit() at close —
        later flushes simply rewrite with more events)."""
        path = path or self.path
        if path is None:
            return None
        payload = {
            "traceEvents": self.events(),
            "displayTimeUnit": "ms",
            "otherData": {
                "trace_epoch_unix": self._epoch_unix,
                # ts 0 on time.perf_counter() (the process's start where
                # known): lays this file over any other perf_counter
                # record without a marker instant
                "trace_epoch_perf_counter": self._epoch,
                "ring_size": self.ring_size,
                "dropped_spans": self._dropped,
                # process identity for obs/aggregate.py's fleet merge
                "role": self.role,
                "index": self.index,
                "pid": os.getpid(),
            },
        }
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = os.path.join(d, f".{os.path.basename(path)}.tmp.{os.getpid()}")
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
        return path


# --------------------------------------------------------------- current
# Module-level current tracer: instrumented code calls obs.trace.span()
# unconditionally; fit() installs a real Tracer for its lifetime when
# ObsConfig.trace is on and uninstalls (back to the no-op) in its finally.
_NULL = NullTracer()
_current: Tracer | NullTracer = _NULL
_install_lock = threading.Lock()


def install(tracer: Tracer) -> Tracer:
    """Make `tracer` the process-current tracer (returns it)."""
    global _current
    with _install_lock:
        _current = tracer
    return tracer


class _Installed:
    """Scope guard returned by installed(); see its docstring."""

    def __init__(self, tracer: Tracer | None):
        self.tracer = tracer

    def __enter__(self) -> Tracer | None:
        return self.tracer

    def __exit__(self, *exc) -> bool:
        if self.tracer is not None:
            uninstall()
            try:
                self.tracer.flush()
            except OSError:
                pass
        return False


def installed(tracer: Tracer | None) -> _Installed:
    """Install `tracer` for the duration of a with-block and make the
    teardown STRUCTURAL: uninstall + best-effort flush on ANY exit —
    clean return, SIGTERM-driven drain, or a failure anywhere in the
    body (a bind error, a failed restore/compile). The spans leading
    into a startup failure are exactly what an early-installed tracer
    exists to capture, and the process-global current tracer must never
    outlive its run (a later run would silently record into the dead
    ring). `tracer=None` (tracing off) makes the whole block a no-op,
    so call sites need no conditional."""
    if tracer is not None:
        install(tracer)
    return _Installed(tracer)


def uninstall() -> None:
    """Back to the no-op tracer."""
    global _current
    with _install_lock:
        _current = _NULL


def current() -> Tracer | NullTracer:
    return _current


def span(name: str, **args):
    """Record a span on the current tracer (no-op when none installed)."""
    return _current.span(name, **args)


def record_span(name: str, t0: float, t1: float, **args) -> None:
    """A span that already happened, [t0, t1] on `time.perf_counter`, on
    the calling thread (jax reports a compile's seconds when it is over).
    No-op when no tracer is installed; not mirrored into the profiler."""
    if _current is not _NULL:
        _current._record(name, t0, t1, args or None)


_setup_recorded = False


def record_setup(package_start: float, imports_done: float) -> None:
    """Set-up from before any tracer existed, as two spans on the calling
    thread, once a process: `boot`, the process's start to
    `package_start` (the package's first line: the interpreter and what
    the entry point did before it imported the package; in the benchmark
    `import jax` and the chip's start-up), and `import`, from there to
    `imports_done` (the end of the trainer's imports). Only into an
    installed tracer whose epoch is the process's start: where the start
    is unknown, both would lie before ts 0 and neither is recorded. Later
    calls (a recipe's later Trainers) record nothing."""
    global _setup_recorded
    if _setup_recorded or _current is _NULL:
        return
    _setup_recorded = True
    if _current.process_start is not None:
        record_span("boot", _current.process_start, package_start)
        record_span("import", package_start, imports_done)


def instant(name: str, **args) -> None:
    _current.instant(name, **args)


def flush_current(path: str | None = None) -> str | None:
    """Flush the installed tracer (the watchdog's entry point)."""
    return _current.flush(path)
