"""Pallas warp kernel vs the jnp/XLA oracle (interpret mode on CPU).

Mirrors the reference's golden-test pattern (`check_loss.py`: numpy
re-implementation vs the accelerated graph — SURVEY.md §4.2): the
vectorized jnp `backward_warp` is itself tested against numpy in
test_warp.py, and serves here as the oracle for the Pallas kernel.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepof_tpu.core.config import LossConfig
from deepof_tpu.losses.photometric import loss_interp, loss_interp_multi
from deepof_tpu.ops.warp import backward_warp
from deepof_tpu.ops.pallas.warp import backward_warp_pallas

LOSS_TERMS = ("total", "Charbonnier_reconstruct", "U_loss", "V_loss",
              "smooth")


@pytest.mark.parametrize(
    "shape,mag",
    [((2, 5, 7, 3), 3.0),      # level-6 size: flow >> image size (all clip)
     ((2, 10, 14, 3), 30.0),   # level-5
     ((1, 40, 56, 3), 80.0),   # level-3
     ((1, 80, 112, 3), 20.0),  # level-2: the widest auto-admitted level
     ((2, 16, 128, 2), 200.0)],  # full-lane width, huge flow
)
def test_pallas_warp_matches_xla(rng, shape, mag):
    b, h, w, c = shape
    img = jnp.asarray(rng.rand(b, h, w, c), jnp.float32)
    flow = jnp.asarray(rng.randn(b, h, w, 2) * mag, jnp.float32)
    ref = backward_warp(img, flow)
    out = backward_warp_pallas(img, flow)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_pallas_warp_rejects_wide_levels(rng):
    """Two lane tiles are the kernel's limit; 256 itself is admitted."""
    img = jnp.zeros((1, 8, 257, 3))
    flow = jnp.zeros((1, 8, 257, 2))
    with pytest.raises(ValueError, match="W <= 256"):
        backward_warp_pallas(img, flow)
    full = jnp.asarray(rng.rand(1, 8, 256, 3), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(backward_warp_pallas(full, jnp.zeros((1, 8, 256, 2)))),
        np.asarray(full))


# ------------------------ the data-bounded sweep, one and two lane tiles

SWEEP_SHAPES = [(16, 112), (24, 160), (16, 224), (40, 56)]
SWEEP_FLOWS = ["zero", "subpixel", "six_px", "one_image_spans_the_frame",
               "every_image_spans_the_frame", "far_outside"]


def _sweep_flow(rng, kind, b, h, w):
    def uniform(reach_x, reach_y, n=b):
        return np.stack([rng.uniform(-reach_x, reach_x, (n, h, w)),
                         rng.uniform(-reach_y, reach_y, (n, h, w))], -1)

    if kind == "zero":
        flow = np.zeros((b, h, w, 2))
    elif kind == "subpixel":
        flow = uniform(0.99, 0.99)
    elif kind == "six_px":
        flow = uniform(6.0, 6.0)
    elif kind == "one_image_spans_the_frame":  # per-image bounds
        flow = uniform(0.99, 0.99)
        flow[1] = uniform(w, h, n=1)[0]
        flow[1, 0, 0, 1], flow[1, h - 1, 0, 1] = 2.0 * h, -2.0 * h
    elif kind == "every_image_spans_the_frame":  # the full sweep
        flow = uniform(w, h)
        flow[:, 0, 0, 1], flow[:, h - 1, 0, 1] = 2.0 * h, -2.0 * h
    else:  # every neighbour clipped to the border
        flow = np.stack([np.full((b, h, w), -3.0 * w),
                         np.full((b, h, w), 5.0 * h)], -1)
        flow[1] = -flow[1]
    return jnp.asarray(flow, jnp.float32)


def _value_and_grads(warp, img, flow, ct):
    val, vjp = jax.vjp(warp, img, flow)
    return (val, *vjp(ct))


@pytest.mark.parametrize("kind", SWEEP_FLOWS)
@pytest.mark.parametrize("hw", SWEEP_SHAPES)
def test_bounded_sweep_is_exact(rng, monkeypatch, hw, kind):
    """The sweep visits only the row offsets the image's flow holds.
    Value and both gradients equal the XLA warp's, and equal the FULL
    sweep of all 2H-1 offsets (what the kernel ran before) to the bit:
    the skipped offsets are exactly those whose masks are all zero."""
    import deepof_tpu.ops.pallas.warp as kernel_mod
    from deepof_tpu.ops.warp import row_sweep_lengths

    (h, w), b = hw, 3
    img = jnp.asarray(rng.rand(b, h, w, 3), jnp.float32)
    ct = jnp.asarray(rng.randn(b, h, w, 3), jnp.float32)
    flow = _sweep_flow(rng, kind, b, h, w)

    rows = np.asarray(row_sweep_lengths(flow[..., 1]))
    want = {"zero": [2] * b, "far_outside": [h] * b,
            "every_image_spans_the_frame": [2 * h - 1] * b}.get(kind)
    if want is not None:
        assert rows.tolist() == want
    elif kind == "one_image_spans_the_frame":
        assert rows[1] == 2 * h - 1 and rows[0] <= 3 and rows[2] <= 3
    else:
        assert rows.max() <= (3 if kind == "subpixel" else 14)

    bounded = _value_and_grads(backward_warp_pallas, img, flow, ct)
    xla = _value_and_grads(backward_warp, img, flow, ct)
    for got, ref in zip(bounded, xla):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
    monkeypatch.setattr(kernel_mod, "_sweep_bounds",
                        lambda setups, h, w, hp: (-(h - 1), h))
    full = _value_and_grads(backward_warp_pallas, img, flow, ct)
    for got, ref in zip(bounded, full):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_pallas_warp_gradients_match(rng):
    img = jnp.asarray(rng.rand(2, 10, 14, 3), jnp.float32)
    flow = jnp.asarray(rng.randn(2, 10, 14, 2) * 2.0, jnp.float32)

    def loss_p(i, f):
        return jnp.sum(backward_warp_pallas(i, f) ** 2)

    def loss_x(i, f):
        return jnp.sum(backward_warp(i, f) ** 2)

    gip, gfp = jax.grad(loss_p, argnums=(0, 1))(img, flow)
    gix, gfx = jax.grad(loss_x, argnums=(0, 1))(img, flow)
    np.testing.assert_allclose(np.asarray(gfp), np.asarray(gfx),
                               rtol=1e-5, atol=1e-5)
    # image cotangent (bilinear scatter) must match too — impl switching
    # may not change gradient semantics
    np.testing.assert_allclose(np.asarray(gip), np.asarray(gix),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(gip).max()) > 0.0


def test_loss_interp_pallas_impl_matches(rng):
    cfg_x = LossConfig()
    cfg_p = LossConfig(warp_impl="pallas")
    flow = jnp.asarray(rng.randn(2, 20, 28, 2), jnp.float32)
    prev = jnp.asarray(rng.rand(2, 20, 28, 3), jnp.float32)
    nxt = jnp.asarray(rng.rand(2, 20, 28, 3), jnp.float32)
    lx, rx = loss_interp(flow, prev, nxt, 2.5, cfg_x)
    lp, rp = loss_interp(flow, prev, nxt, 2.5, cfg_p)
    np.testing.assert_allclose(np.asarray(rp), np.asarray(rx),
                               rtol=1e-5, atol=1e-5)
    for k in LOSS_TERMS:
        np.testing.assert_allclose(float(lp[k]), float(lx[k]),
                                   rtol=1e-5, atol=1e-6)
    # what the warp launch did rides beside the terms: XLA sweeps nothing
    assert float(lx["warp_sweep_rows"]) == 0.0
    assert 2.0 <= float(lp["warp_sweep_rows"]) <= 2 * 20 - 1
    assert float(lp["warp_gather_fallback"]) == 0.0


def test_loss_interp_multi_pallas_impl_matches(rng):
    t = 4
    cfg_x = LossConfig()
    cfg_p = LossConfig(warp_impl="pallas")
    flows = jnp.asarray(rng.randn(2, 10, 14, 2 * (t - 1)), jnp.float32)
    vol = jnp.asarray(rng.rand(2, 10, 14, 3 * t), jnp.float32)
    lx, _ = loss_interp_multi(flows, vol, 1.25, cfg_x)
    lp, _ = loss_interp_multi(flows, vol, 1.25, cfg_p)
    for k in LOSS_TERMS:
        np.testing.assert_allclose(float(lp[k]), float(lx[k]),
                                   rtol=1e-5, atol=1e-6)
    # the volume's counter spans every pair's vertical flow (odd channels)
    from deepof_tpu.ops.warp import row_sweep_lengths
    assert float(lp["warp_sweep_rows"]) == float(jnp.max(row_sweep_lengths(
        flows[..., 1::2] * 1.25)))


@pytest.mark.parametrize("hw,takes", [
    ((12, 16), "kernel"),         # one lane tile: the kernel, no limit
    ((8, 128), "kernel"),
    ((8, 200), "kernel_or_gather"),   # two tiles: the kernel under the cond
    ((256, 130), "kernel_or_gather"),
    ((8, 300), "xla"),            # W > 256
    ((260, 16), "xla"),           # H > 256
])
def test_auto_impl_dispatch(rng, request, hw, takes):
    from deepof_tpu.ops.warp import PALLAS_AUTO_MAX_SWEEP

    h, w = hw
    img = jnp.asarray(rng.rand(1, h, w, 3), jnp.float32)
    flow = jnp.asarray(rng.randn(1, h, w, 2), jnp.float32)
    ref = np.asarray(backward_warp(img, flow))
    # off a TPU `auto` is the XLA gather at every size, to the bit
    np.testing.assert_array_equal(
        np.asarray(backward_warp(img, flow, impl="auto")), ref)
    calls = request.getfixturevalue("on_a_tpu")  # only from here on
    out = backward_warp(img, flow, impl="auto")
    assert calls == {"kernel": [None], "xla": [],
                     "kernel_or_gather": [PALLAS_AUTO_MAX_SWEEP]}[takes]
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_pallas_flow_grad_clipped_and_flow_only(rng):
    """The Pallas flow-cotangent kernel on heavily clipped flows (all four
    bilinear neighbors at the border), differentiated wrt flow ONLY — the
    training hot path, where the image cotangent is dead code."""
    img = jnp.asarray(rng.rand(2, 8, 10, 3), jnp.float32)
    flow = jnp.asarray(rng.randn(2, 8, 10, 2) * 50.0, jnp.float32)

    gp = jax.grad(lambda f: jnp.sum(backward_warp_pallas(img, f) ** 2))(flow)
    gx = jax.grad(lambda f: jnp.sum(backward_warp(img, f) ** 2))(flow)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gx),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------- the multi-device form on a mesh


def _data_time_mesh():
    from deepof_tpu.core.config import MeshConfig
    from deepof_tpu.parallel.mesh import build_mesh

    return build_mesh(MeshConfig(time=2))  # 8 virtual devices: data 4 x time 2


def test_pallas_warp_keeps_a_data_sharded_batch_on_a_data_time_mesh(rng):
    """A batch is sharded over "data" only. On a mesh that also has a
    "time" axis the kernel must launch per DATA shard and leave the
    operands where they are — not split them over ("data","time") because
    8 happens to divide, and gather them back."""
    from deepof_tpu.parallel.mesh import batch_sharding
    from deepof_tpu.parallel.spatial import mesh_context

    mesh = _data_time_mesh()
    data = batch_sharding(mesh)
    img = jax.device_put(jnp.asarray(rng.rand(8, 10, 14, 3), jnp.float32), data)
    flow = jax.device_put(
        jnp.asarray(rng.randn(8, 10, 14, 2) * 2.0, jnp.float32), data)
    fn = jax.jit(backward_warp_pallas, in_shardings=(data, data))
    with mesh_context(mesh):  # read at trace time, as the step builders do
        text = fn.lower(img, flow).compile().as_text()
        out = fn(img, flow)
    assert out.sharding.spec[0] in ("data", ("data",))
    assert "all-gather" not in text
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(backward_warp(img, flow)),
                               rtol=1e-5, atol=1e-5)


def test_pallas_volume_warp_shards_the_folded_pair_axis_over_data_time(rng):
    """`backward_warp_volume` folds the T-1 pairs into the batch and shards
    that axis over ("data","time"): the kernel follows, and matches XLA."""
    from deepof_tpu.ops.warp import backward_warp_volume
    from deepof_tpu.parallel.spatial import mesh_context, pair_axes

    mesh = _data_time_mesh()
    assert pair_axes(mesh, 8) == ("data", "time")
    assert pair_axes(mesh, 4) == ("data",)  # data*time does not divide
    assert pair_axes(None, 8) == ("data",)
    vol = jnp.asarray(rng.rand(4, 10, 14, 9), jnp.float32)        # T = 3
    flows = jnp.asarray(rng.randn(4, 10, 14, 4) * 2.0, jnp.float32)
    fn = jax.jit(lambda v, f: backward_warp_volume(v, f, impl="pallas"))
    with mesh_context(mesh):
        text = fn.lower(vol, flows).as_text()
        out = fn(vol, flows)
    launch = next(ln for ln in text.splitlines() if "manual_computation" in ln)
    assert 'in_shardings=[<@mesh, [{"data", "time"}, {}, {}, {}]>' in launch
    assert "tensor<1x10x14x3xf32>" in launch  # one pair per device
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(backward_warp_volume(vol, flows)),
        rtol=1e-5, atol=1e-5)


def test_pallas_warp_says_so_when_the_batch_cannot_be_split(rng):
    """Three images over data=4: every device runs the whole batch. That
    is correct and slow, so it is announced when the step is traced."""
    from deepof_tpu.parallel.spatial import mesh_context

    img = jnp.asarray(rng.rand(3, 10, 14, 3), jnp.float32)
    flow = jnp.asarray(rng.randn(3, 10, 14, 2), jnp.float32)
    with mesh_context(_data_time_mesh()):
        with pytest.warns(UserWarning, match="every device runs the whole"):
            out = jax.jit(backward_warp_pallas)(img, flow)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(backward_warp(img, flow)),
                               rtol=1e-5, atol=1e-5)
