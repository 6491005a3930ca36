"""The fused preparation pass (`ops/pallas/qk_prep.py`) in interpret mode
against the XLA code it replaces (`models/lm/layers.py`: `RMSNorm`, `rope`,
`rope_halves`, the cast, the layout): the kernels alone, and both attention
layers whole with the route steered to a TPU's, forward and every
gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepof_tpu.core.config import LMConfig
from deepof_tpu.models.lm import layers as L
from deepof_tpu.ops import attention as A
from deepof_tpu.ops.pallas import attention as K
from deepof_tpu.ops.pallas import qk_prep as P

F32 = jnp.float32
BD = A.Mask("block_diffusion", 4, 128)  # a doubled row of 256 positions
#: largest |difference| over values of size 1-4: float32 to rounding,
#: bfloat16 to two of its 2^-8 steps
TOLERANCE = {"float32": 2e-5, "bfloat16": 4e-2}


def xla_prep(x, positions, heads, theta, interleave, dtype, scale=None,
             eps=None):
    """What the layers' XLA path does between a product and the scores,
    then laid out head-major."""
    b, s, w = x.shape
    x = x.reshape(b, s, heads, w // heads)
    if scale is not None:
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale
    if interleave:
        assert positions is None
        y = L.rope(x, theta)
    else:
        y = L.rope_halves(x, theta, positions)
    return jnp.swapaxes(y.astype(dtype), 1, 2)


CASES = {
    # heads, d, positions, block_s, interleave, norm, rows
    "latent_q_2x64": (2, 64, 256, 128, True, False, 2),
    "latent_q_16x64_two_groups": (16, 64, 128, 128, True, False, 1),
    "latent_k_1x64": (1, 64, 256, 256, True, False, 2),
    "latent_q_6x64_group_of_6": (6, 64, 128, 128, True, False, 1),
    "grouped_q_8x128_two_groups": (8, 128, 256, 128, False, True, 1),
    "grouped_k_1x128": (1, 128, 256, 256, False, True, 2),
    "halves_2x64_no_norm": (2, 64, 256, 128, False, False, 1),
    "interleaved_2x128": (2, 128, 128, 128, True, False, 1),
    "halves_1x256_normed": (1, 256, 128, 128, False, True, 1),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("dtype", sorted(TOLERANCE))
def test_pass_matches_the_xla_code(dtype, case):
    """Output, dx and the scale's gradient: both pairings, with and
    without the norm, heads of 64 (two a register), 128 and 256, one and
    several head groups and position blocks a row, the halves at the
    positions of a doubled row (0..L-1 twice)."""
    heads, d, s, bs, interleave, norm, rows = CASES[case]
    dt = jnp.dtype(dtype)
    kx, kg, kw = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (rows, s, heads * d), F32) * 1.5
    weight = jax.random.normal(kw, (rows, heads, s, d), F32)
    scale = 1.0 + 0.2 * jax.random.normal(kg, (d,), F32) if norm else None
    eps = 1e-6 if norm else None
    theta = 1e4
    pos = jnp.arange(s) if interleave else BD.rope_positions(s)

    def fused(x, scale):
        return P.qk_prep(x, pos, heads, theta, interleave, dt, bs, scale, eps,
                         interpret=True)

    def xla(x, scale):
        return xla_prep(x, None if interleave else pos, heads, theta,
                        interleave, dt, scale, eps)

    def loss(f):
        return lambda x, scale: jnp.sum(f(x, scale).astype(F32) * weight)

    got, want = fused(x, scale), xla(x, scale)
    assert got.dtype == dt and got.shape == (rows, heads, s, d)
    pairs = {"y": (want, got)}
    args = (0, 1) if norm else (0,)
    for name, w_, g_ in zip(("dx", "dscale"),
                            jax.grad(loss(xla), argnums=args)(x, scale),
                            jax.grad(loss(fused), argnums=args)(x, scale)):
        pairs[name] = (w_, g_)
    for name, (w_, g_) in pairs.items():
        assert w_.shape == g_.shape and w_.dtype == g_.dtype, name
        size = max(1.0, float(jnp.max(jnp.abs(w_.astype(F32)))) / 4)
        gap = float(jnp.max(jnp.abs(w_.astype(F32) - g_.astype(F32))))
        # dx leaves the pass in the compute dtype (as the transposed
        # products take it); the XLA chain hands them float32
        assert gap < TOLERANCE[dtype] * size, (name, gap, size)


def test_shapes_the_pass_has_no_kernel_for_are_refused():
    x = jnp.zeros((1, 128, 192), F32)
    for heads, bs in ((2, 128), (3, 96), (1, 128)):  # d 96; blocks; d 192
        with pytest.raises(ValueError, match="qk_prep"):
            P.qk_prep(x, jnp.arange(128), heads, 1e4, True, F32, bs)
    with pytest.raises(ValueError, match="qk_prep"):  # the norm on 64s
        P.qk_prep(jnp.zeros((1, 128, 64), F32), jnp.arange(128), 1, 1e4, False,
                  F32, 128, jnp.ones((64,)), 1e-6)


def test_tables_are_the_layers_angles():
    """cos and the signed sine, a head of 8 twice side by side, against
    `rope` / `rope_halves` applied to unit vectors."""
    pos = jnp.array([0, 3, 7])
    for interleave in (True, False):
        cos, sin = P.rotary_tables(pos, 8, 1e4, interleave, 16)
        assert cos.shape == sin.shape == (3, 16)
        np.testing.assert_array_equal(cos[:, :8], cos[:, 8:])
        ones = jnp.ones((1, 3, 1, 8), F32)
        want = (L.rope(jnp.ones((1, 8, 1, 8), F32), 1e4)[:, pos] if interleave
                else L.rope_halves(ones, 1e4, pos))
        # all-ones input: y_j = cos_j + sin_j (the partner is 1 too)
        np.testing.assert_allclose(np.asarray(cos + sin)[:, :8],
                                   np.asarray(want)[0, :, 0], atol=1e-6)


# ---- the two layers whole, the route steered to a TPU's -------------------

def steer_to_tpu(monkeypatch, seen):
    """`jax.default_backend()` says "tpu"; the three kernels' wrappers run
    in interpret mode and record what they were handed."""
    def interpreted(module, name):
        real = getattr(module, name)

        def call(*a, **kw):
            seen.append((name, kw.get("head_major", kw.get("block_s"))))
            return real(*a, interpret=True, **kw)

        monkeypatch.setattr(module, name, call)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    interpreted(P, "qk_prep")
    interpreted(K, "fused_causal_attention")
    interpreted(K, "fused_grouped_attention")


LATENT = LMConfig(hidden_size=64, num_attention_heads=2, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=32,
                  attn_block_q=128, rope_interleave=True)
GROUPED = LMConfig(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                   head_dim=128, attn_block_q=128, rope_interleave=False)
LAYERS = {
    # layer, config, mask, positions
    "latent_64+128": (L.MLA, LATENT, A.CAUSAL, 256),
    "grouped_128_doubled_row": (L.GQA, GROUPED, BD, 256),
}


@pytest.mark.parametrize("case", sorted(LAYERS))
@pytest.mark.parametrize("dtype", sorted(TOLERANCE))
def test_layer_on_the_fused_route_matches_the_xla_route(monkeypatch, dtype,
                                                        case):
    """The layer's output and its gradient with respect to `h` and every
    parameter (each weight, the norms' scales), at lane-true head sizes
    (128, and 64 + 128), the grouped layer at the positions of a doubled
    row: the same parameter tree on both routes, the fused one through
    `qk_prep` and head-major operands."""
    layer, cfg, mask, s = LAYERS[case]
    dt = jnp.dtype(dtype)
    module = layer(cfg, dt, mask)
    kh, kp, kw = jax.random.split(jax.random.PRNGKey(1), 3)
    h = jax.random.normal(kh, (2, s, cfg.hidden_size), F32)
    weight = jax.random.normal(kw, h.shape, F32)
    params = module.init(kp, h)["params"]
    # weights of size 1/sqrt(fan-in), scales off 1: every gradient is live
    params = jax.tree_util.tree_map(
        lambda a: a * (4.0 if a.ndim == 2 else 1.0) + (
            0.1 * jnp.cos(jnp.arange(a.size, dtype=F32)) if a.ndim == 1 else 0),
        params)

    def loss(p, h):
        return jnp.sum(module.apply({"params": p}, h) * weight)

    want = (module.apply({"params": params}, h),
            *jax.grad(loss, argnums=(0, 1))(params, h))
    seen = []
    steer_to_tpu(monkeypatch, seen)
    assert jax.tree_util.tree_structure(module.init(kp, h)["params"]) \
        == jax.tree_util.tree_structure(params)
    got = (module.apply({"params": params}, h),
           *jax.grad(loss, argnums=(0, 1))(params, h))
    assert ("qk_prep", 256) in seen  # the largest block that divides 256
    assert any(name.startswith("fused_") and flag is True
               for name, flag in seen), seen
    flat = lambda t: {jax.tree_util.keystr(k): v for k, v in  # noqa: E731
                      jax.tree_util.tree_leaves_with_path(t)}
    for (name, w_), g_ in zip(flat(want).items(), flat(got).values()):
        assert w_.shape == g_.shape and g_.dtype == F32, name
        size = max(1.0, float(jnp.max(jnp.abs(w_))) / 4)
        gap = float(jnp.max(jnp.abs(w_ - g_)))
        assert gap < 2 * TOLERANCE[dtype] * size, (name, gap, size)


ROUTES = [
    # backend, positions, head sizes, mask -> prep
    ("tpu", 4096, (128, 64, 128), A.CAUSAL, {"path": "fused", "block_s": 512}),
    ("tpu", 8192, (128, 0, 128), A.Mask("block_diffusion", 4, 4096),
     {"path": "fused", "block_s": 512}),
    ("tpu", 768, (128, 64, 128), A.CAUSAL, {"path": "fused", "block_s": 256}),
    ("tpu", 384, (128, 64, 128), A.CAUSAL, {"path": "fused", "block_s": 128}),
    ("tpu", 4096, (128, 192, 128), A.CAUSAL, {"path": "xla"}),  # 192: no slab
    ("tpu", 4096, (16, 8, 16), A.CAUSAL, {"path": "xla"}),  # scores on XLA
    ("cpu", 4096, (128, 64, 128), A.CAUSAL, {"path": "xla"}),
]


@pytest.mark.parametrize("backend,positions,dims,mask,prep", ROUTES)
def test_route_names_the_pass(monkeypatch, backend, positions, dims, mask, prep):
    """The route's second decision: fused wherever the scores are and the
    rotated width is one the slabs hold, in the largest block of positions
    that divides the row; `xla` everywhere else."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    route = A.attention_route(positions, 128, dims, mask)
    assert route["prep"] == prep
    assert (route["path"] == "fused") or prep == {"path": "xla"}


def test_head_major_operands_need_the_fused_scores():
    q = jnp.zeros((1, 2, 128, 128), F32)
    with pytest.raises(ValueError, match="head-major"):
        A.grouped_attention(q, q, q, 1.0, 128, F32, head_major=True)
    with pytest.raises(ValueError, match="head-major"):
        A.causal_attention(q, q[..., :64], q, q[:, 0, :, :64], q, 1.0, 128,
                           F32, head_major=True)


@pytest.mark.parametrize("whole", [0, 1])
def test_shard_over_batch_keeps_whole_operands_whole(whole):
    """Under a mesh the pass's tables and scale reach every batch shard
    unsplit; batched operands are split over "data" as before."""
    from deepof_tpu.parallel.mesh import build_mesh
    from deepof_tpu.parallel.spatial import shard_over_batch

    mesh = build_mesh(devices=jax.devices()[:2])
    x = jnp.arange(8.0).reshape(4, 2)
    t = jnp.array([[10.0, 20.0]] * (1 if whole else 4))
    seen = []

    def fn(x, t):
        seen.append((x.shape, t.shape))
        return x + t

    out = shard_over_batch(fn, mesh, 4, whole=whole)(x, t)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x + t))
    assert seen[0] == ((2, 2), (1, 2) if whole else (2, 2))
