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


def test_pallas_corr_matches_oracle(feats):
    f1, f2 = feats
    got = np.asarray(correlation_pallas(
        jnp.asarray(f1), jnp.asarray(f2), 2, 1, 4, True))
    want = correlation_oracle(f1, f2, max_disp=2, stride=1)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_pallas_corr_stride_and_ragged_height(feats):
    f1, f2 = feats
    f1, f2 = f1[:, :11], f2[:, :11]  # H=11 not divisible by tile_h=4
    got = np.asarray(correlation_pallas(
        jnp.asarray(f1), jnp.asarray(f2), 4, 2, 4, True))
    want = correlation_oracle(f1, f2, max_disp=4, stride=2)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_pallas_corr_grad_matches_xla(feats):
    f1, f2 = feats
    f1, f2 = jnp.asarray(f1[:1, :8, :8]), jnp.asarray(f2[:1, :8, :8])

    def loss_pallas(a, b):
        return jnp.sum(correlation_pallas(a, b, 2, 1, 4, True) ** 2)

    def loss_xla(a, b):
        return jnp.sum(correlation(a, b, max_disp=2, stride=1) ** 2)

    g1p, g2p = jax.grad(loss_pallas, argnums=(0, 1))(f1, f2)
    g1x, g2x = jax.grad(loss_xla, argnums=(0, 1))(f1, f2)
    np.testing.assert_allclose(np.asarray(g1p), np.asarray(g1x), atol=1e-4)
    np.testing.assert_allclose(np.asarray(g2p), np.asarray(g2x), atol=1e-4)


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

    fn = jax.jit(lambda a, b: correlation_pallas(a, b, 2, 1, 4, True),
                 in_shardings=(sharding, sharding))
    with mesh_context(mesh):  # read at trace time, as the step builders do
        got = fn(jax.device_put(jnp.asarray(f1), sharding),
                 jax.device_put(jnp.asarray(f2), sharding))
    assert got.sharding.spec[0] == "data"
    want = correlation_oracle(f1, f2, max_disp=2, stride=1)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def test_pallas_corr_bf16_inputs(feats):
    f1, f2 = feats
    got = correlation_pallas(
        jnp.asarray(f1, jnp.bfloat16), jnp.asarray(f2, jnp.bfloat16),
        2, 1, 4, True)
    # f32 accumulation inside, but input dtype out (same as the XLA sweep,
    # so `auto` dispatch is not backend-dependent under bf16 compute)
    assert got.dtype == jnp.bfloat16
    want = correlation_oracle(f1, f2, max_disp=2, stride=1)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=0.05, rtol=0.05)
