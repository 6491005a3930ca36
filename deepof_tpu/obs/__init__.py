"""Unified observability layer (L0 — stdlib-only at import).

The stack is genuinely concurrent (prefetch thread, N pipeline workers,
AsyncFetcher consumer, staged device puts), and scalar counters cannot
show *when* those threads overlapped, stalled, or wedged. This package
holds the instruments that can:

  trace.py      lock-cheap ring-buffered span tracer emitting Chrome
                trace-event JSON (load artifacts' trace.json in Perfetto
                / chrome://tracing) — the cross-thread timeline that
                makes dispatch/put/fetch/assemble overlap visible
                instead of inferred from phase totals. Set-up from the
                process's start, the trace's epoch (boot, import,
                trainer_init, first_step, ledger_lower), compiles
                (jax_trace, jax_lower, xla_compile, xla_cache_load),
                each Pallas kernel's trace (kernel_trace) and the main
                thread's waits (submit_wait, drain) are spans of the
                same file, and while a tracer is installed every span
                is mirrored as a jax.profiler.TraceAnnotation (dispatch
                as a numbered step), so a profiler trace holds them
                beside the device's operations.
  heartbeat.py  background thread atomically rewriting heartbeat.json
                (step, rates, queue depths, device memory, RSS) plus a
                wedge watchdog: no step within k x a robust recent
                step-time estimate => all thread stacks dumped to the
                log and the trace ring flushed.
  telemetry.py  process/device sampling shared by training and bench:
                per-device memory_stats and process RSS (every train
                record); XLA cost-analysis FLOPs and the peak table
                (bench.py, chip_smoke.py, the ledger; the train loop
                logs no FLOPs, which the TPU's lowering never reports).
  export.py     the scrapeable face (DESIGN.md "Fleet observability"):
                fixed log-spaced latency histograms that merge EXACTLY
                across processes, Prometheus text rendering/parsing for
                every stats block (GET /metrics on the serve server,
                the fleet router, the elastic coordinator), and the
                latency/error-budget SLO layer (`tail` rc 6).
  aggregate.py  multi-process trace merge: every per-process
                trace.json/heartbeat.json/metrics.jsonl under a run dir
                becomes ONE Perfetto timeline with per-process tracks
                and request-id flow arrows chaining each request across
                router and replica (`tools/trace_summary.py --merge`).

Import discipline: this __init__, trace.py, export.py, and aggregate.py
import only the stdlib (`analyze.py` and the jax-free CLI verbs may
import them without initializing an accelerator backend); telemetry.py defers its jax imports into the sampling
functions for the same reason. trace.py's profiler mirror finds jax
in `sys.modules` and never imports it.
"""

from . import trace

__all__ = ["trace"]
