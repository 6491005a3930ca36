"""Pallas correlation kernel vs the XLA/numpy oracles (interpret mode on the
CPU mesh; the same kernel lowers to Mosaic on TPU). Golden-test pattern per
SURVEY.md §4.2: accelerated kernel vs reference implementation."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepof_tpu.ops.corr import correlation, correlation_oracle
from deepof_tpu.ops.pallas.corr import correlation_pallas


@pytest.fixture
def feats(rng):
    f1 = rng.randn(2, 12, 16, 8).astype(np.float32)
    f2 = rng.randn(2, 12, 16, 8).astype(np.float32)
    return f1, f2


# (shape, max_disp, stride): the geometries both kernels must take, at
# their row tile of 8 (H where that is less, rounded up to whole product
# groups of each row phase)
GEOMETRY = [
    ((2, 12, 16, 8), 2, 1),    # two row tiles, the second padded
    ((2, 11, 16, 8), 4, 2),    # H=11: two row tiles, the second padded
    ((1, 18, 12, 8), 8, 2),    # three row tiles, the third padded
    ((1, 6, 12, 8), 8, 2),     # W 12: one sublane tile a phase
    ((1, 6, 12, 8), 8, 1),     # stride 1: one phase
    ((1, 5, 56, 4), 20, 2),    # conv3 of 320x448's width; H ragged
    ((1, 4, 60, 4), 20, 2),    # the Sintel crop's: W not a multiple of 16
    ((1, 4, 60, 4), 8, 1),
    ((2, 7, 12, 8), 20, 1),    # 41 x 41 offsets, most of them off the image
]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape, max_disp, stride", GEOMETRY)
def test_pallas_corr_forward_matches_oracle_and_xla(rng, shape, max_disp,
                                                    stride, dtype):
    """The forward kernel (products on the MXU, diagonals turned to their
    lanes by a strided roll) against the numpy oracle and the XLA sweep in
    float32 on the same values. float32: to 1e-5 of the largest element;
    bfloat16: the kernel's sums are float32 and only its output is
    rounded, so each element is within one bfloat16 step of the reference."""
    f1, f2 = (jnp.asarray(rng.randn(*shape), dtype) for _ in range(2))
    got = correlation_pallas(f1, f2, max_disp, stride, True)
    assert got.dtype == jnp.dtype(dtype)
    a, b = (np.asarray(x, np.float32) for x in (f1, f2))
    want = np.asarray(correlation(jnp.asarray(a), jnp.asarray(b), max_disp,
                                  stride, impl="xla"))
    np.testing.assert_allclose(
        correlation_oracle(a, b, max_disp, stride), want,
        atol=1e-5 * np.abs(want).max())
    if dtype == "float32":
        np.testing.assert_allclose(np.asarray(got), want,
                                   atol=1e-5 * np.abs(want).max())
    else:
        _close(got, want, 2.0 ** -8)


def _vjp(corr, f1, f2, g):
    return jax.vjp(corr, f1, f2)[1](g)


def _xla_vjp(f1, f2, g, max_disp, stride):
    """Autodiff of the XLA sweep in float32 on the same values."""
    f1, f2, g = (jnp.asarray(x, jnp.float32) for x in (f1, f2, g))
    return _vjp(lambda a, b: correlation(a, b, max_disp, stride, impl="xla"),
                f1, f2, g)


def _close(got, want, rtol):
    """Each element within `rtol` of itself, plus a float32 summation's
    share of the largest (an element that cancels to near zero)."""
    got, want = np.asarray(got, np.float32), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=rtol,
                               atol=1e-6 * np.abs(want).max())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape, max_disp, stride", GEOMETRY + [
    ((1, 48, 64, 4), 20, 2),  # the cell's sweep: 441 maps, pad 20, 6 tiles
])
def test_pallas_corr_grad_matches_xla(rng, shape, max_disp, stride, dtype):
    """The backward kernel's df1 and df2 against autodiff of the XLA
    sweep over the forward's geometries: the same row tiles, product
    groups and f2 layout, the cotangent's blocks placed where the forward
    reads its diagonals (a ragged H's padded rows carry a zero cotangent
    and must add nothing). bfloat16: the kernel's products and sums are
    float32 and only its output is rounded, so each element is within one
    bfloat16 step (2^-8) of the float32 reference on the same values."""
    n = 2 * (max_disp // stride) + 1
    f1, f2 = (jnp.asarray(rng.randn(*shape), dtype) for _ in range(2))
    g = jnp.asarray(rng.randn(*shape[:3], n * n), dtype)
    got = _vjp(lambda a, b: correlation_pallas(a, b, max_disp, stride, True),
               f1, f2, g)
    want = _xla_vjp(f1, f2, g, max_disp, stride)
    for x, y in zip(got, want):
        assert x.dtype == jnp.dtype(dtype)
        _close(x, y, 1e-5 if dtype == "float32" else 2.0 ** -8)


def test_pallas_corr_grad_books_each_displacement_to_its_own_offset(rng):
    """Features that are NOT near-constant over a small image, with the
    displacements reaching most of it (max_disp 8 on 10 x 12): a
    cotangent booked to the mirrored displacement (map i read as map
    n*n-1-i, the fault `benchmark/harness/flow_faults.py` calls
    `flipped`) moves df2 by far more than the tolerance here. The cell's
    `correct` cannot see that fault (its features are all but constant
    over the image: PERF.md section 7); this test is what guards it."""
    shape, max_disp, stride = (1, 10, 12, 4), 8, 2
    f1, f2 = (jnp.asarray(rng.randn(*shape), jnp.float32) for _ in range(2))
    g = jnp.asarray(rng.randn(*shape[:3], 81), jnp.float32)
    got = _vjp(lambda a, b: correlation_pallas(a, b, max_disp, stride, True),
               f1, f2, g)
    want = _xla_vjp(f1, f2, g, max_disp, stride)
    mirrored = _xla_vjp(f1, f2, g[..., ::-1], max_disp, stride)
    for x, y, m in zip(got, want, mirrored):
        _close(x, y, 1e-5)
        scale = np.abs(np.asarray(y)).max()
        assert np.abs(np.asarray(m) - np.asarray(y)).max() > 0.3 * scale


def test_pallas_corr_sharded_over_batch_mesh(feats):
    """shard_map form (parallel.spatial.shard_over_batch): under jit with
    the batch sharded over the 8-device mesh and the mesh published by
    `mesh_context`, the kernel runs per shard (GSPMD must not all-gather
    or choke on the opaque pallas_call) and matches the oracle."""
    from deepof_tpu.parallel.mesh import batch_sharding, local_mesh
    from deepof_tpu.parallel.spatial import mesh_context

    f1, f2 = feats
    f1 = np.concatenate([f1] * 4)  # batch 8 over 8 devices
    f2 = np.concatenate([f2] * 4)
    mesh = local_mesh()
    sharding = batch_sharding(mesh)

    fn = jax.jit(lambda a, b: correlation_pallas(a, b, 2, 1, True),
                 in_shardings=(sharding, sharding))
    with mesh_context(mesh):  # read at trace time, as the step builders do
        got = fn(jax.device_put(jnp.asarray(f1), sharding),
                 jax.device_put(jnp.asarray(f2), sharding))
    assert got.sharding.spec[0] == "data"
    want = correlation_oracle(f1, f2, max_disp=2, stride=1)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def test_pallas_corr_grad_sharded_over_batch_mesh(feats, rng):
    """The backward kernel under the 8-device mesh: each shard launches
    `corr_bwd` on its own rows (`shard_over_batch`, mesh carried as a
    static argument of the VJP), so both gradients stay sharded over
    "data" and match the XLA sweep's."""
    from deepof_tpu.parallel.mesh import batch_sharding, local_mesh
    from deepof_tpu.parallel.spatial import mesh_context

    f1, f2 = (np.concatenate([x] * 4) for x in feats)  # batch 8 over 8
    g = rng.randn(*f1.shape[:3], 25).astype(np.float32)
    mesh = local_mesh()
    sharding = batch_sharding(mesh)
    fn = jax.jit(lambda a, b, ct: _vjp(
        lambda x, y: correlation_pallas(x, y, 2, 1, True), a, b, ct),
        in_shardings=(sharding,) * 3)
    with mesh_context(mesh):
        got = fn(*(jax.device_put(jnp.asarray(x), sharding)
                   for x in (f1, f2, g)))
    want = _xla_vjp(f1, f2, g, 2, 1)
    for x, y in zip(got, want):
        assert x.sharding.spec[0] == "data"
        _close(x, y, 1e-5)


def test_pallas_corr_bf16_inputs(feats):
    f1, f2 = feats
    got = correlation_pallas(
        jnp.asarray(f1, jnp.bfloat16), jnp.asarray(f2, jnp.bfloat16),
        2, 1, True)
    # f32 accumulation inside, but input dtype out (same as the XLA sweep,
    # so `auto` dispatch is not backend-dependent under bf16 compute)
    assert got.dtype == jnp.bfloat16
    want = correlation_oracle(f1, f2, max_disp=2, stride=1)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=0.05, rtol=0.05)
