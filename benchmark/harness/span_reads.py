"""Readings of the program's host spans that more than one per-layer
reader takes: how long a set-up span of the loop's main thread lasted, and
what that thread's spans leave uncovered of a window. `spans` is what `spans.load_spans`
returns: [(name, thread name, start_s, end_s)] on `time.perf_counter`."""

from __future__ import annotations

from .trace_reduce import total, union

MAIN = "MainThread"


def span_seconds(spans, name: str) -> float | None:
    """Seconds inside the main thread's spans called `name` over the whole
    run. None where the program recorded no such span."""
    found = [b - a for n, th, a, b in spans if n == name and th == MAIN]
    return sum(found) if found else None


def uncovered_share(spans, lo: float, hi: float) -> float | None:
    """Share of [lo, hi] that NO span of the main thread covers (nested
    and overlapping spans count once). None where none touches the window."""
    if lo is None or hi is None or hi <= lo:
        return None
    inside = [(max(a, lo), min(b, hi)) for _, th, a, b in spans
              if th == MAIN and min(b, hi) > max(a, lo)]
    if not inside:
        return None
    return 1.0 - total(union(inside)) / (hi - lo)
