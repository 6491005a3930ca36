"""The program's per-layer counters, as the window's records carry them."""

from __future__ import annotations


def window_means(records: list) -> dict:
    """{counter: [its mean over the records that carry it, one value an
    expert layer]} for every per-layer counter of `records`, by whatever
    name the program writes it; {} where there is none."""
    by_name: dict = {}
    for r in records:
        for k, v in r.items():
            if isinstance(v, list):
                by_name.setdefault(k, []).append(v)
    return {k: [sum(col) / len(rows) for col in zip(*rows)]
            for k, rows in by_name.items()}


def largest_layer_mean(obs: dict, counter: str) -> float | None:
    """The largest over the expert layers of `counter`'s window mean; None
    without records or the counter."""
    by_layer = window_means(obs.get("records", [])).get(counter)
    return max(by_layer) if by_layer else None
