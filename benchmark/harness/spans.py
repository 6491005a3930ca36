"""The program's host spans (Chrome trace-event JSON written by its
tracer) as plain tuples on the `time.perf_counter` clock."""

from __future__ import annotations

import json

CLOCK_MARK = "bench_clock"  # an instant the benchmark emits, carrying perf_counter


def load_spans(path: str) -> list[tuple[str, str, float, float]]:
    """[(name, thread name, start_s, end_s)] on the perf_counter clock."""
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    threads = {e["tid"]: e["args"]["name"] for e in events
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    epoch = None
    for e in events:
        if e.get("name") == CLOCK_MARK and "args" in e:
            epoch = e["args"]["perf_counter"] - e["ts"] * 1e-6
    if epoch is None:
        raise ValueError(f"{path}: no {CLOCK_MARK} instant")
    return [(e["name"], threads.get(e["tid"], str(e["tid"])),
             epoch + e["ts"] * 1e-6, epoch + (e["ts"] + e["dur"]) * 1e-6)
            for e in events if e.get("ph") == "X"]


def share_of_window(spans, name: str, lo: float, hi: float,
                    thread: str | None = None) -> float | None:
    """Share of [lo, hi] that spans called `name` (on `thread`, if given)
    cover. None where no such span was recorded at all."""
    found, covered = False, 0.0
    for n, th, a, b in spans:
        if n != name or (thread is not None and th != thread):
            continue
        found = True
        covered += max(0.0, min(b, hi) - max(a, lo))
    if not found or hi <= lo:
        return None
    return covered / (hi - lo)
