"""A look at a trace by hand: planes, lines, and the names that took most
time on each line. Written where BENCH_TRACE_DUMP says, for a builder."""

from __future__ import annotations

import json
import os


def dump(planes: dict, path: str, top: int = 60) -> None:
    out = {}
    for pname, lines in planes.items():
        out[pname] = {}
        for lname, evs in lines.items():
            agg: dict = {}
            for name, _, d in evs:
                s, c = agg.get(name, (0.0, 0))
                agg[name] = (s + d, c + 1)
            best = sorted(agg.items(), key=lambda kv: -kv[1][0])[:top]
            out[pname][lname] = {"events": len(evs), "names": len(agg),
                                 "top": [[n, s, c] for n, (s, c) in best]}
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)


def record_small(planes: dict, path: str, seconds: float = 0.03,
                 lines=("XLA Ops", "XLA Modules", "Steps")) -> None:
    """A small recorded trace for the tests: the device lines' events of
    the first `seconds` after the window's start mark, and the marks."""
    from .trace_reduce import SYNC_END, SYNC_START, find_marks

    lo, _ = find_marks(planes)
    lo = lo or 0.0
    out: dict = {}
    for pname, pl in planes.items():
        for lname, evs in pl.items():
            if pname.startswith("/device:"):
                keep = [[n, t, d] for n, t, d in evs
                        if lname in lines and lo - 0.002 <= t <= lo + seconds]
            else:
                keep = [[n, t, d] for n, t, d in evs if n in (SYNC_START, SYNC_END)]
            if keep:
                out.setdefault(pname, {})[lname] = keep
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f)
