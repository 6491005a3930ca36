"""Operations and bytes of masked softmax attention with grouped keys,
from its shapes and its mask's rule: what ANY implementation has to do.

Only the (query, key) pairs the rule makes visible count (`visible_pairs`).
Queries and keys are d channels wide, values and outputs d_v (d where not
given; the latent attention's 192 and 128). Forward, per visible pair and
query head: the score (2 d operations) and its share of the output
(2 d_v). Backward: the score again is recomputation and does not count;
what the gradient requires is dp = do . v and dv = p . do (2 d_v each),
dq = ds . k and dk = ds . q (2 d each). The exponentials and the
softmax's sums are a few operations a pair beside 512 and are left out.
Bytes: every operand read once and every result written once in
`itemsize` bytes at its own width (q, k, v, o forward; q, k, v, o, do
read and dq, dk, dv written backward), the logsumexp in float32.

`visible_pairs` for the rules of `deepof_tpu/ops/attention.py`, written
here from their statement, not imported: `causal` over s positions:
s (s + 1) / 2. `block_diffusion` over a doubled row of 2 L positions in
blocks of B (L a multiple of B, n = L / B blocks): noised to noised L B;
noised to clean B B n (n - 1) / 2; clean to clean B B n (n + 1) / 2.
"""


def visible_pairs(rule: str, s: int, block: int = 0) -> int:
    if rule == "causal":
        return s * (s + 1) // 2
    if rule == "block_diffusion":
        L = s // 2
        if s % 2 or L % block:
            raise ValueError(f"a doubled row of {s} in blocks of {block}")
        n = L // block
        return L * block + block * block * n * n
    raise ValueError(f"no mask rule {rule!r}")


def _sizes(b, h, g, s, d, d_v, itemsize):
    """Bytes of (q, k, v, o, logsumexp)."""
    per = b * s * itemsize
    return per * h * d, per * g * d, per * g * d_v, per * h * d_v, b * h * s * 4


def forward(b: int, h: int, g: int, s: int, d: int, rule: str, block: int = 0,
            itemsize: int = 2, d_v: int | None = None) -> dict:
    """b rows, h query heads over g key/value heads, s positions as the
    layers see them (the doubled row's 2 L)."""
    d_v = d if d_v is None else d_v
    q, k, v, o, lse = _sizes(b, h, g, s, d, d_v, itemsize)
    return {"ops": b * h * visible_pairs(rule, s, block) * (2 * d + 2 * d_v),
            "bytes": q + k + v + o + lse}


def backward(b: int, h: int, g: int, s: int, d: int, rule: str, block: int = 0,
             itemsize: int = 2, d_v: int | None = None) -> dict:
    d_v = d if d_v is None else d_v
    q, k, v, o, lse = _sizes(b, h, g, s, d, d_v, itemsize)
    return {"ops": b * h * visible_pairs(rule, s, block) * (4 * d + 4 * d_v),
            "bytes": 2 * (q + k + v + o) + lse}
