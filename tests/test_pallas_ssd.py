"""The state-space scan's kernels (`ops/pallas/ssd.py`, interpret mode on the
CPU) against the chunked form they replace on the chip (`ops/ssm.py`'s XLA
`doubled_scan`, the oracle): both copies' outputs and the gradient of every
input, at small shapes that cover what the chip's layer does and more:
several chunks a copy, a chunk of several blocks, blocks of 4 and of 2, a
copy padded to a whole chunk, two heads to a slab and one.

Tolerances: in float32 both sides do the same arithmetic in another order
(the state carried chunk by chunk against the matrix of decays between
chunk ends): 2e-5 on an output, 1e-4 on a gradient. In bfloat16 both round
the same operands; the gradients' casts fall elsewhere: 1e-4 on an output,
1e-2 on a gradient.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from deepof_tpu.ops import ssm
from deepof_tpu.ops.pallas import ssd

TOL = {"float32": (2e-5, 1e-4), "bfloat16": (1e-4, 1e-2)}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
#: rows, copy length, heads, head size, groups, state, chunk, block
SHAPES = {
    "two_chunks_of_four_blocks": (2, 32, 4, 8, 2, 5, 16, 4),
    "blocks_of_2": (1, 32, 4, 8, 2, 6, 8, 2),
    "padded_to_a_whole_chunk": (2, 20, 4, 8, 2, 5, 8, 4),
    "one_head_a_slab": (1, 24, 2, 128, 1, 8, 8, 4),
}


def rel(a, b):
    return float(jnp.linalg.norm((a - b).astype(jnp.float32))
                 / (jnp.linalg.norm(b.astype(jnp.float32)) + 1e-30))


def inputs(rows, copy, heads, head_dim, groups, state, key=0):
    k = jax.random.split(jax.random.PRNGKey(key), 9)
    draw = lambda i, *s: jax.random.normal(k[i], (rows, copy, *s))  # noqa: E731
    halves = [(draw(i, heads, head_dim), jax.nn.softplus(draw(i + 1, heads) - 1.0),
               draw(i + 2, groups, state), draw(i + 3, groups, state))
              for i in (0, 4)]
    return (*halves[0], *halves[1], -jnp.exp(jax.random.normal(k[8], (heads,))))


def kernel(*a, **kw):
    return ssd.doubled_scan(*a, **kw, interpret=True)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_kernels_are_the_chunked_form(shape, dtype):
    rows, copy, heads, head_dim, groups, state, chunk, block = SHAPES[shape]
    a = inputs(rows, copy, heads, head_dim, groups, state)
    probe = jax.random.normal(jax.random.PRNGKey(9),
                              (2, rows, copy, heads, head_dim))
    tol_y, tol_g = TOL[dtype]

    def loss(fn):
        return lambda *x: sum(jnp.sum(p * y) for p, y in zip(
            probe, fn(*x, chunk, block, DTYPES[dtype])))

    (got, g_got), (want, g_want) = (jax.jit(lambda *x: (
        fn(*x, chunk, block, DTYPES[dtype]),
        jax.grad(loss(fn), argnums=tuple(range(9)))(*x)))(*a)
        for fn in (kernel, ssm.doubled_scan))
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == jnp.float32
        assert rel(g, w) < tol_y
    names = ("xn", "dtn", "bn", "cn", "xc", "dtc", "bc", "cc", "A")
    worst = max((rel(g, w), n) for g, w, n in zip(g_got, g_want, names))
    assert worst[0] < tol_g, worst


def test_cotangents_keep_the_inputs_dtypes():
    """bfloat16 activations in, their cotangents in bfloat16; outputs and
    the step sizes' and A's cotangents in float32."""
    a = list(inputs(1, 16, 4, 8, 2, 5))
    for i in (0, 2, 3, 4, 6, 7):
        a[i] = a[i].astype(jnp.bfloat16)
    ys, g = jax.jit(lambda *x: (kernel(*x, 8, 4, jnp.bfloat16), jax.grad(
        lambda *x: sum(jnp.sum(y) for y in kernel(*x, 8, 4, jnp.bfloat16)),
        argnums=tuple(range(9)))(*x)))(*a)
    assert all(y.dtype == jnp.float32 for y in ys)
    assert [x.dtype for x in g] == [x.dtype for x in a]


def test_chunk_start_states_are_the_one_residual():
    """What the backward keeps besides the inputs: the clean state at each
    chunk's start, named `ssm.STATES` so that a remat policy can see it."""
    a = inputs(1, 32, 4, 8, 2, 5)
    jaxpr = str(jax.make_jaxpr(functools.partial(
        jax.vjp, lambda *x: kernel(*x, 8, 4)))(*a))
    assert f"name={ssm.STATES}" in jaxpr
    assert jaxpr.count("ssd_fwd") == 1


def test_refuses_blocks_a_chunk_does_not_hold():
    a = inputs(1, 16, 4, 8, 2, 5)
    with pytest.raises(ValueError, match="whole blocks"):
        kernel(*a, 8, 3)
