"""Operations and bytes of softmax attention with grouped keys under a
sliding window of W keys (key k visible to query q iff k <= q and
q - k < W), from its shapes: what ANY implementation has to do.

Only the visible (query, key) pairs count, written here from the rule's
statement, not imported: the first W queries see W (W + 1) / 2 pairs, each
later query W, so s positions hold W (W + 1) / 2 + (s - W) W (s (s + 1)
/ 2 where s <= W). Per visible pair and query head the work and the bytes
are `attention.py`'s (which this reuses): forward 2 d + 2 d_v, backward
4 d + 4 d_v; every operand read once and every result written once.
"""

from benchmark.kernels import attention


def visible_pairs(s: int, window: int) -> int:
    if s <= window:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def _scaled(count: dict, s: int, window: int) -> dict:
    """`attention.py`'s count under the causal rule, its operations taken
    over the window's pairs instead."""
    causal = attention.visible_pairs("causal", s)
    return {"ops": count["ops"] // causal * visible_pairs(s, window),
            "bytes": count["bytes"]}


def forward(b: int, h: int, g: int, s: int, d: int, window: int,
            itemsize: int = 2, d_v: int | None = None) -> dict:
    """b rows, h query heads over g key/value heads, s positions."""
    return _scaled(attention.forward(b, h, g, s, d, "causal", itemsize=itemsize,
                                     d_v=d_v), s, window)


def backward(b: int, h: int, g: int, s: int, d: int, window: int,
             itemsize: int = 2, d_v: int | None = None) -> dict:
    return _scaled(attention.backward(b, h, g, s, d, "causal", itemsize=itemsize,
                                      d_v=d_v), s, window)
