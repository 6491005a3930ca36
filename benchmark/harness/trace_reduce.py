"""From a profiler trace (`.xplane.pb`) and the program's host spans to
numbers: device busy and idle time, per-operation durations, idle gaps
named by what the host was doing.

Everything is plain intervals `(start_s, end_s)` on the profiler's clock.
`load_xplane` is the only function that touches the file format.
"""

from __future__ import annotations

import glob
import os

SYNC_START, SYNC_END = "bench_window_start", "bench_window_end"


def load_xplane(trace_dir: str) -> dict:
    """{plane name: {line name: [(event name, start_s, dur_s), ...]}}"""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    planes: dict = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for e in line.events:
                evs.append((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
    return planes


def device_planes(planes: dict, prefix: str = "/device:TPU:") -> list[str]:
    return sorted(p for p in planes if p.startswith(prefix))


def find_marks(planes: dict) -> tuple[float | None, float | None]:
    """The benchmark's own two annotations on any host line: the traced
    window's start and end on the profiler's clock."""
    start = end = None
    for pname, lines in planes.items():
        if pname.startswith("/device:"):
            continue
        for evs in lines.values():
            for name, t, d in evs:
                if name == SYNC_START:
                    start = t
                elif name == SYNC_END:
                    end = t + d
    return start, end


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy_union, lo: float, hi: float) -> list[tuple[float, float]]:
    out, t = [], lo
    for a, b in busy_union:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def name_gap(gap, host_spans) -> str:
    """The host span that covers most of the gap. host_spans:
    [(name, start_s, end_s)] on the same clock."""
    best, best_cov = "no_span", 0.0
    cover: dict[str, float] = {}
    for name, a, b in host_spans:
        c = min(b, gap[1]) - max(a, gap[0])
        if c > 0:
            cover[name] = cover.get(name, 0.0) + c
    for name, c in cover.items():
        if c > best_cov:
            best, best_cov = name, c
    return best


def executions(dev: dict, pattern: str) -> float:
    """Executions, inside the traced window, of the programs whose name
    holds `pattern` (`dev` is what `reduce_device` returns)."""
    return sum(n for name, n in dev["modules"].items() if pattern in name)


def reduce_device(planes: dict, ops_line: str = "XLA Ops",
                  modules_line: str = "XLA Modules",
                  host_spans=(), top: int = 10,
                  prefix: str = "/device:TPU:") -> dict:
    """Busy/idle of the traced window, averaged over the device planes.

    Returns busy_s, window_s, ops {name: (seconds, count)} summed over the
    chips, modules {name: executions inside the window, taken as the window
    over the period of their starts}, device_ops and idle_gaps (top lists) and
    op_events [(name, start, dur)] of the first chip, clipped to the window.
    """
    devs = device_planes(planes, prefix)
    if not devs:
        raise ValueError("trace holds no device plane")
    lo, hi = find_marks(planes)
    busy_total, ops, modules, all_gaps = 0.0, {}, {}, []
    first_events = []
    for i, d in enumerate(devs):
        evs = planes[d].get(ops_line, [])
        if lo is None or hi is None:  # no marks: the span of what ran
            lo = min((t for _, t, _ in evs), default=0.0)
            hi = max((t + dur for _, t, dur in evs), default=0.0)
        inside = [(n, max(t, lo), min(t + dur, hi)) for n, t, dur in evs
                  if min(t + dur, hi) > max(t, lo)]
        u = union([(a, b) for _, a, b in inside])
        busy_total += total(u)
        for n, a, b in inside:
            s, c = ops.get(n, (0.0, 0))
            ops[n] = (s + (b - a), c + 1)
        starts: dict = {}
        for n, t, dur in planes[d].get(modules_line, []):
            if lo <= t <= hi:
                starts.setdefault(n, []).append(t)
            elif t < lo < t + dur:
                starts.setdefault(n, [])
        for n, ts in starts.items():
            # executions inside the window = window / period of the starts
            # (a program's events may overlap their neighbours at the ends,
            # so neither a count of whole events nor a sum of shares holds)
            if len(ts) >= 2:
                period = (max(ts) - min(ts)) / (len(ts) - 1)
                modules[n] = modules.get(n, 0.0) + (hi - lo) / period
            else:
                modules[n] = modules.get(n, 0.0) + max(len(ts), 1)
        if i == 0:
            first_events = [(n, a, b - a) for n, a, b in inside]
            all_gaps = gaps(u, lo, hi)
    window = max(hi - lo, 0.0)
    dev_ops = sorted(((n, s) for n, (s, _) in ops.items()),
                     key=lambda kv: -kv[1])[:top]
    named: dict[str, float] = {}
    for g in sorted(all_gaps, key=lambda g: g[0] - g[1])[:200]:
        nm = name_gap(g, host_spans)
        named[nm] = named.get(nm, 0.0) + (g[1] - g[0])
    idle = sorted(named.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_total / len(devs), "window_s": window,
            "window": (lo, hi), "ops": ops, "modules": modules,
            "chips": len(devs), "op_events": first_events,
            "device_ops": [[n, s] for n, s in dev_ops],
            "idle_gaps": [[n, s] for n, s in idle]}
