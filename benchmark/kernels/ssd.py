"""Operations and bytes of the state-space recurrence of a Mamba-2 layer
on a row or on the doubled row of training by diffusion over blocks, from
its shapes: what ANY implementation has to do, chunked, per position or in
a kernel.

Per head (state P x N, the head's B and C from its group) and position:
h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t, y_t = h_t C_t. Forward: the
decay times the state, the input's outer product and their sum (3 P N
operations), the output's product with C (2 P N): 5 P N. Backward, what
the gradient requires from the cotangent of y: the state's cotangent
carried back (its decay and C's outer product with dy, 3 P N), dC, dB, dx
and the decay's (2 P N each): 11 P N; the state itself again is
recomputation and does not count. On the doubled row every position of
both copies runs the recurrence once (a noised block starts from the
clean state its copy's scan holds at the block's start, which costs no
further state), so the positions are 2 L. Bytes: x, B and C read once and
y written once in `itemsize` bytes, dt in float32 (forward); x, B, C, dt
and dy read, dx, dB, dC and ddt written (backward). The skip D x, the
exponentials and the softplus are a few operations a position beside
5 P N H and are left out.
"""


def _sizes(b, s, heads, head_dim, groups, state, itemsize):
    """Bytes of (x, B and C, dt, y)."""
    return (b * s * heads * head_dim * itemsize,
            2 * b * s * groups * state * itemsize, b * s * heads * 4,
            b * s * heads * head_dim * itemsize)


def forward(b: int, s: int, heads: int, head_dim: int, groups: int, state: int,
            itemsize: int = 2) -> dict:
    """b rows of s positions as the layer sees them (the doubled row's 2 L),
    `heads` of `head_dim` channels, `groups` of B and C of `state`."""
    x, bc, dt, y = _sizes(b, s, heads, head_dim, groups, state, itemsize)
    return {"ops": 5 * b * s * heads * head_dim * state,
            "bytes": x + bc + dt + y}


def backward(b: int, s: int, heads: int, head_dim: int, groups: int,
             state: int, itemsize: int = 2) -> dict:
    x, bc, dt, y = _sizes(b, s, heads, head_dim, groups, state, itemsize)
    return {"ops": 11 * b * s * heads * head_dim * state,
            "bytes": 2 * (x + bc + dt) + y}
