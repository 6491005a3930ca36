"""Process + device telemetry sampling, shared by training and bench.

Per-device `memory_stats()` (HBM bytes-in-use / peak) and process RSS,
which every train record and the heartbeat carry; XLA's own
cost-analysis FLOPs and the peak table, which bench.py, chip_smoke.py
and the executable ledger read (the train loop logs no FLOPs: on the
TPU a lowering's cost analysis reports none).

Import discipline: jax is imported lazily inside the functions —
importing this module must stay side-effect free (the heartbeat thread
and the jax-free CLI verbs import it without wanting a backend
initialized; see obs/__init__).
"""

from __future__ import annotations

import os

#: Published dense bf16 peak per chip, TFLOP/s, keyed by
#: `jax.devices()[0].device_kind`. Source: Google Cloud documentation,
#: "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s). The single table
#: behind every `mfu_nominal` / `roofline_s` (bench.py, obs/ledger.py).
#: A kind that is not listed — the CPU included — has no
#: peak: callers then report no MFU rather than one against a chip that
#: is not there.
PEAK_BF16_TFLOPS = {"TPU v5 lite": 197.0}


def peak_bf16_tflops(device_kind: str | None = None) -> float | None:
    """Peak of `device_kind` (default: this process's first device), or
    None for a kind outside the table."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    return PEAK_BF16_TFLOPS.get(device_kind)


def lowered_flops(lowered) -> float | None:
    """FLOPs from an already-lowered module's cost analysis — the
    shared extraction behind step_flops, split out so a caller that
    holds a `jax.stages.Lowered` or `Compiled` (chip_smoke.py reads
    both) never pays a second trace. None when the backend does not
    report it."""
    try:
        ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        flops = float(ca.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception:  # noqa: BLE001 - cost model is best-effort
        return None


def step_flops(step, *example_args) -> float | None:
    """XLA's FLOPs estimate for one call of a jitted `step`, from the
    LOWERED module (`jit(...).lower(...).cost_analysis()`) — traces but
    never compiles on the backend. Lowered cost analysis reports GLOBAL
    (pre-partition) FLOPs (bench.py has the verification notes). None
    when the backend does not report it."""
    try:
        return lowered_flops(step.lower(*example_args))
    except Exception:  # noqa: BLE001 - cost model is best-effort
        return None


def process_rss_bytes() -> int | None:
    """Resident set size of this process (host RAM actually mapped) —
    the input pipeline's decoded-image cache, reorder buffers, and any
    leak all show up here. Linux /proc; None elsewhere."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return None


def device_memory_stats() -> list[dict]:
    """Per-device `memory_stats()` snapshot. Fields are None where the
    backend does not report (the cpu PJRT client returns no stats);
    callers decide whether to surface or drop the nulls."""
    import jax

    out = []
    for d in jax.devices():
        try:
            ms = d.memory_stats()
        except Exception:  # noqa: BLE001 - never let sampling kill a run
            ms = None
        out.append({
            "device": str(d),
            "bytes_in_use": ms.get("bytes_in_use") if ms else None,
            "peak_bytes_in_use": ms.get("peak_bytes_in_use") if ms else None,
        })
    return out


def device_memory_summary() -> dict:
    """Max bytes-in-use / peak across devices, log-record keyed.

    Max (not sum): with replicated params + sharded batches the hottest
    chip is the one that OOMs, so the headroom question is per-device.
    Keys are always present (None on backends without stats) so a
    record's schema does not depend on the backend — `MetricsLogger`
    serializes None as null.
    """
    stats = device_memory_stats()
    in_use = [s["bytes_in_use"] for s in stats
              if s["bytes_in_use"] is not None]
    peak = [s["peak_bytes_in_use"] for s in stats
            if s["peak_bytes_in_use"] is not None]
    return {
        "dev_mem_bytes_in_use": max(in_use) if in_use else None,
        "dev_mem_peak_bytes": max(peak) if peak else None,
    }
