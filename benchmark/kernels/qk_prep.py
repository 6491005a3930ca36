"""Operations and bytes of the pass between an attention layer's projection
and its attention kernels, from its shapes: per head an optional RMS norm
over the head's d channels, rotary positions, one cast, head-major layout.
Memory-bound: the bytes are what counts.

Forward: the projection's output read once (float32), the attention's
operand written once (the compute type). Per element: the rotation
x cos + partner(x) sin (3) and, with the norm, the square, its share of the
sum, the statistic's and the scale's products (4). The rotary tables are a
[s, d] array beside [b, s, heads d] and are left out.

Backward: the operand's cotangent read once and the projection's written
once, both in the compute type (the transposed products round a float32
cotangent to it anyway), and with the norm its float32 input read again
(the pass keeps no residual). Per element: the rotation transposed (3) and,
with the norm, the statistic again (3), the two sums and the combination
(6) and the scale's gradient (2).
"""


#: bytes an element: the projection's float32 and the attention's bfloat16,
#: the one pair of types a configuration of the benchmark computes in
IN_ITEMSIZE, OUT_ITEMSIZE = 4, 2


def forward(b: int, s: int, heads: int, d: int, norm: bool = False) -> dict:
    n = b * s * heads * d
    return {"ops": n * (7 if norm else 3),
            "bytes": n * (IN_ITEMSIZE + OUT_ITEMSIZE)}


def backward(b: int, s: int, heads: int, d: int, norm: bool = False) -> dict:
    n = b * s * heads * d
    return {"ops": n * (14 if norm else 3),
            "bytes": n * (2 * OUT_ITEMSIZE + (IN_ITEMSIZE if norm else 0))}
