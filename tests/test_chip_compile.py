"""The TPU v5e compiler's verdict on the Pallas kernels at their real widths,
from a sandbox with no chip: each case lowers a kernel for a DESCRIBED
v5e device (`topologies.get_topology_desc`) and compiles it with the
installed libtpu — Mosaic raises here what it would raise on the chip
(interpret mode, which every other kernel test uses, cannot: the
correlation kernel passed all of those while the chip refused it).

Rules this file keeps (on-chip-measurement guide, section 2): the topology
is described inside a module-scoped fixture that skips when it cannot be —
never at import, in a `skipif`/`parametrize` argument or in conftest, and
not autouse; shardings and shapes are built in fixtures/tests; compiles run
in the test's own process; all such tests live in this ONE file (only one
xdist worker may hold the TPU library); the persistent compile cache is off
around them (an entry compiled for an unattached chip cannot be read back).
A compile that passes is not a run: numbers on the chip come from
`chip_smoke.py`.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BATCH = 16  # the headline batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _mesh(topo, n, time=1):
    from deepof_tpu.core.config import MeshConfig
    from deepof_tpu.parallel.mesh import build_mesh

    return build_mesh(MeshConfig(time=time), devices=list(topo.devices[:n]))


def _compiled_text(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled text"
    return text


@pytest.mark.parametrize("hw", [(80, 112), (40, 56)])
@pytest.mark.parametrize("which", ["fwd", "flow_grad"])
def test_warp_kernels_compile_for_v5e(one_chip, which, hw):
    """Both warp kernels at the two pyramid levels `warp_impl=auto` admits
    for a 320x448 input (W <= 128): B16, C3, f32."""
    from deepof_tpu.ops.pallas.warp import (_pallas_warp_flow_grad,
                                            _pallas_warp_fwd)

    h, w = hw
    img = jax.ShapeDtypeStruct((BATCH, h, w, 3), jnp.float32, sharding=one_chip)
    flow = jax.ShapeDtypeStruct((BATCH, h, w, 2), jnp.float32,
                                sharding=one_chip)
    if which == "fwd":
        _compiled_text(lambda im, fl: _pallas_warp_fwd(im, fl, False),
                       img, flow)
    else:
        _compiled_text(lambda im, fl, ct: _pallas_warp_flow_grad(
            im, fl, ct, False), img, flow, img)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("hw", [(40, 56), (48, 64)])
def test_corr_kernel_compiles_for_v5e(one_chip, hw, dtype):
    """FlowNet-C's cost volume at its real shapes: conv3 features of
    320x448 and 384x512 inputs (1/8 resolution), C=256, max_disp=20,
    stride=2. Before the dx sweep was unrolled Mosaic refused every one of
    these: 'cannot statically prove that index in dimension 1 is a
    multiple of 8'."""
    from deepof_tpu.ops.pallas.corr import _pallas_corr_fwd

    h, w = hw
    f = jax.ShapeDtypeStruct((BATCH, h, w, 256), dtype, sharding=one_chip)
    _compiled_text(lambda a, b: _pallas_corr_fwd(a, b, 20, 2, 8, False), f, f)


@pytest.mark.parametrize("n_dev,time", [(1, 1), (4, 1), (4, 2)])
def test_warp_vjp_compiles_through_shard_map(topo, n_dev, time):
    """The public warp (custom_vjp: forward kernel + flow-grad kernel) in
    its one multi-device form — `shard_over_batch` over the mesh the step
    builders publish — on a one-device mesh, on all four described chips
    with the batch sharded over "data", and on a data 2 x time 2 mesh,
    where a data-sharded batch must stay on its "data" shards."""
    from deepof_tpu.ops.pallas.warp import backward_warp_pallas
    from deepof_tpu.parallel.mesh import batch_sharding
    from deepof_tpu.parallel.spatial import mesh_context

    mesh = _mesh(topo, n_dev, time)
    data = batch_sharding(mesh)
    img = jax.ShapeDtypeStruct((BATCH, 40, 56, 3), jnp.float32, sharding=data)
    flow = jax.ShapeDtypeStruct((BATCH, 40, 56, 2), jnp.float32, sharding=data)

    def flow_grad(im, fl):
        return jax.grad(lambda x: jnp.sum(
            backward_warp_pallas(im, x, False) ** 2))(fl)

    with mesh_context(mesh):  # read at trace time, as in train/step.py
        text = _compiled_text(flow_grad, img, flow)
    assert text.count("tpu_custom_call") >= 2  # forward + flow-grad kernels
    assert "all-gather" not in text  # each shard warps its own batch rows


def test_corr_compiles_through_shard_map_on_four_chips(topo):
    from deepof_tpu.ops.pallas.corr import correlation_pallas
    from deepof_tpu.parallel.mesh import batch_sharding
    from deepof_tpu.parallel.spatial import mesh_context

    mesh = _mesh(topo, 4)
    f = jax.ShapeDtypeStruct((BATCH, 40, 56, 256), jnp.bfloat16,
                             sharding=batch_sharding(mesh))
    with mesh_context(mesh):
        text = _compiled_text(
            lambda a, b: correlation_pallas(a, b, 20, 2, 8, False), f, f)
    assert "all-gather" not in text


@pytest.mark.slow
@pytest.mark.parametrize("model,n_dev", [("inception_v3", 1), ("flownet_c", 4)])
def test_whole_train_step_compiles_for_v5e(topo, monkeypatch, model, n_dev):
    """The headline train step (batch 16, 320x448, bf16, warp_impl=auto) as
    `chip_smoke.py` runs it, for one described chip (Inception-v3) and for
    the four-chip `data` mesh (FlowNet-C: both warp kernels and the
    correlation in one program). Minutes, hence slow; the `auto` gates ask
    `jax.default_backend()` and are steered here, never through an option."""
    import dataclasses

    from deepof_tpu.core.config import get_config
    from deepof_tpu.train.warmup import lower_train_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("flyingchairs")
    cfg = cfg.replace(
        model=model,
        data=dataclasses.replace(cfg.data, dataset="synthetic",
                                 image_size=(320, 448), gt_size=(320, 448),
                                 batch_size=BATCH),
        train=dataclasses.replace(cfg.train, compute_dtype="bfloat16"))
    compiled = lower_train_step(cfg, _mesh(topo, n_dev)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 10
    assert ("all-reduce" in text) == (n_dev > 1)
    assert "all-gather" not in text
    ma = compiled.memory_analysis()
    assert (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.generated_code_size_in_bytes) < 16e9  # fits one v5e's HBM
