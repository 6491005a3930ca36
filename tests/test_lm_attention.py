"""The latent attention's two paths (`ops/attention.py`): the fused
kernels in interpret mode against the XLA blocks at the language-model
cell's head sizes, the one route between them, and its step-0 record."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepof_tpu.ops import attention as A

B, S, H, DN, DR, DV = 2, 256, 2, 128, 64, 128  # the cell's head sizes
SCALE = (DN + DR) ** -0.5
#: largest |difference| to the XLA blocks over values of size 1-4:
#: float32 to rounding, bfloat16 to two of its 2^-8 steps
TOLERANCE = {"float32": 2e-5, "bfloat16": 4e-2}


def operands(dtype):
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    shapes = ((B, S, H, DN), (B, S, H, DR), (B, S, H, DN), (B, S, DR),
              (B, S, H, DV), (B, S, H, DV))
    *ops, weight = (jax.random.normal(k, sh) for k, sh in zip(keys, shapes))
    return tuple(o.astype(dtype) for o in ops), weight


@pytest.mark.parametrize("blocks", [(128, 128, 512), (128, 256, 128),
                                    (256, 128, 512)],
                         ids=lambda b: "q%d_kv%d_compute%d" % b)
@pytest.mark.parametrize("dtype", sorted(TOLERANCE))
def test_fused_kernels_match_the_xla_blocks(monkeypatch, dtype, blocks):
    """Output and the gradient with respect to all five operands, with
    the key block equal to, twice and half the query block (the second in
    two chunks of keys: `COMPUTE_KV`), so tiles above, on and below the
    diagonal all occur."""
    from deepof_tpu.ops.pallas import attention as K

    bq, bkv, compute = blocks
    monkeypatch.setattr(K, "COMPUTE_KV", compute)
    dt = jnp.dtype(dtype)
    ops, weight = operands(dt)

    def loss(attend):
        return lambda *o: jnp.sum(attend(*o).astype(jnp.float32) * weight)

    def blocks_(*o):
        return A.xla_blocks_attention(*o, SCALE, 64, dt)

    def fused(*o):
        return K.fused_causal_attention(*o, SCALE, bq, bkv, interpret=True)

    assert fused(*ops).dtype == dt
    pairs = [(blocks_(*ops), fused(*ops))] + list(zip(
        jax.grad(loss(blocks_), argnums=range(5))(*ops),
        jax.grad(loss(fused), argnums=range(5))(*ops)))
    for name, (want, got) in zip(("o", "qn", "qr", "kn", "kr", "v"), pairs):
        assert want.shape == got.shape, name
        gap = float(jnp.max(jnp.abs(want.astype(jnp.float32)
                                    - got.astype(jnp.float32))))
        assert gap < TOLERANCE[dtype], (name, gap)


CELL = (128, 64, 128)
ROUTES = [
    # backend, positions, attn_block_q, head sizes -> route
    ("tpu", 4096, 512, CELL, {"path": "fused", "block_q": 512, "block_kv": 2048}),
    ("tpu", 8192, 512, CELL, {"path": "fused", "block_q": 512, "block_kv": 2048}),
    ("tpu", 1536, 512, CELL, {"path": "fused", "block_q": 512, "block_kv": 512}),
    ("tpu", 256, 512, CELL, {"path": "fused", "block_q": 256, "block_kv": 256}),
    ("tpu", 32, 16, CELL, {"path": "xla_blocks", "block_q": 16}),
    ("tpu", 4096, 64, CELL, {"path": "xla_blocks", "block_q": 64}),
    ("tpu", 4096, 512, (16, 8, 16), {"path": "xla_blocks", "block_q": 512}),
    ("cpu", 4096, 512, CELL, {"path": "xla_blocks", "block_q": 512}),
    ("cpu", 32, 512, CELL, {"path": "xla_blocks", "block_q": 32}),
]


@pytest.mark.parametrize("backend,positions,block_q,dims,route", ROUTES)
def test_route_is_decided_by_backend_and_shape(monkeypatch, backend, positions,
                                               block_q, dims, route):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    got = A.attention_route(positions, block_q, dims)
    assert {k: got[k] for k in route} == route
    # every route names its mask and, as static counts, the tiles its
    # blocks visit under it: the lower triangle of the tiles, blocks wide
    bq, bkv = got["block_q"], got.get("block_kv", got["block_q"])
    nq, nk = positions // bq, positions // bkv
    seen = sum(((i + 1) * bq - 1) // bkv + 1 for i in range(nq))
    assert got["mask"] == "causal"
    assert got["tiles"] == {"visited": seen, "all": nq * nk}


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_a_row_the_blocks_do_not_divide_is_refused_on_both_paths(monkeypatch,
                                                                 backend):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with pytest.raises(ValueError, match="attn_block_q=384"):
        A.attention_route(4096, 384, CELL)
    ops, _ = operands(jnp.float32)
    with pytest.raises(ValueError, match="attn_block_q=96"):
        A.causal_attention(*ops, SCALE, 96, jnp.float32)


def test_the_layer_takes_the_fused_path_where_the_route_says(monkeypatch):
    """`causal_attention` as a TPU would route it (steered here, kernels
    in interpret mode) against itself on the CPU's route."""
    from deepof_tpu.ops.pallas import attention as K

    calls, real = [], K.fused_causal_attention

    def recording(*a, **kw):
        calls.append(a[6:])
        return real(*a, interpret=True, **kw)

    ops, _ = operands(jnp.float32)
    blocks = A.causal_attention(*ops, SCALE, 128, jnp.float32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(K, "fused_causal_attention", recording)
    fused = A.causal_attention(*ops, SCALE, 128, jnp.float32)
    assert calls == [(128, 128)]  # 256 positions: the key block is the query's
    np.testing.assert_allclose(np.asarray(fused), np.asarray(blocks),
                               atol=2e-5)


def test_trainer_writes_the_route_into_its_step_0_info_record(tmp_path):
    from deepof_tpu.core.config import get_config
    from deepof_tpu.parallel.mesh import build_mesh
    from deepof_tpu.train.loop import Trainer

    cfg = get_config("lm")
    cfg = cfg.replace(
        lm=dataclasses.replace(cfg.lm, attn_block_q=16, loss_block=16,
                               n_routed_experts=2, n_routed_experts_published=8),
        train=dataclasses.replace(cfg.train, log_dir=str(tmp_path),
                                  nan_guard=False))
    trainer = Trainer(cfg, mesh=build_mesh(devices=jax.devices()[:1]))
    trainer.logger.close()
    with open(tmp_path / "metrics.jsonl") as f:
        records = [json.loads(line) for line in f]
    routes = [r for r in records if r.get("message") == "routes"]
    assert len(routes) == 1 and routes[0]["kind"] == "info"
    assert routes[0]["step"] == 0
    assert routes[0]["objective"] == "next_token"
    assert routes[0]["attention_route"] == {
        "path": "xla_blocks", "block_q": 16, "mask": "causal",
        "tiles": {"visited": 3, "all": 4}, "prep": {"path": "xla"}}


# ---- grouped-query attention under a mask rule (`bd_attn_fwd` / `_bwd`) ----

GROUPED = {
    # rule, positions a copy, block, block_q, block_kv, chunk of keys
    "bd_b4_q128_kv128": ("block_diffusion", 256, 4, 128, 128, 128),
    "bd_b4_q128_kv256": ("block_diffusion", 256, 4, 128, 256, 128),
    "bd_b32_q256_kv128": ("block_diffusion", 256, 32, 256, 128, 128),
    "bd_b1_q128_kv256": ("block_diffusion", 256, 1, 128, 256, 256),
    "causal_q256_kv128": ("causal", 512, 0, 256, 128, 128),
}


@pytest.mark.parametrize("case", sorted(GROUPED))
@pytest.mark.parametrize("dtype", sorted(TOLERANCE))
def test_fused_grouped_kernels_match_the_xla_blocks(monkeypatch, dtype, case):
    """Output and the gradient with respect to q, k and v, 4 query heads
    reading 2 key/value heads of 128, under the block-diffusion mask of a
    doubled row (blocks of 1, 4 and 32; key tiles equal to, twice and half
    the query tiles; tiles hidden, shown whole and cut by the rule all
    occur, and with chunks of 128 keys a query that sees no key of a chunk
    it is computed with) and under the causal rule."""
    from deepof_tpu.ops.pallas import attention as K

    rule, half, block, bq, bkv, compute = GROUPED[case]
    monkeypatch.setattr(K, "COMPUTE_KV", compute)
    dt = jnp.dtype(dtype)
    mask = A.CAUSAL if rule == "causal" else A.Mask(rule, block, half)
    s = half if rule == "causal" else 2 * half
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, weight = (jax.random.normal(kk, (2, s, h, 128))
                       for kk, h in zip(keys, (4, 2, 2, 4)))
    ops = tuple(o.astype(dt) for o in (q, k, v))

    def loss(attend):
        return lambda *o: jnp.sum(attend(*o).astype(jnp.float32) * weight)

    def blocks_(*o):
        return A.xla_blocks_grouped_attention(*o, 128 ** -0.5, 64, dt, mask)

    def fused(*o):
        return K.fused_grouped_attention(*o, 128 ** -0.5, bq, bkv, mask,
                                         interpret=True)

    assert fused(*ops).dtype == dt
    pairs = [(blocks_(*ops), fused(*ops))] + list(zip(
        jax.grad(loss(blocks_), argnums=range(3))(*ops),
        jax.grad(loss(fused), argnums=range(3))(*ops)))
    for name, (want, got) in zip(("o", "q", "k", "v"), pairs):
        assert want.shape == got.shape, name
        # k's and v's gradients sum over two query heads and up to 512
        # queries: values of size 10-30, bfloat16 steps of 2^-4 there
        scale = max(1.0, float(jnp.max(jnp.abs(want.astype(jnp.float32)))) / 4)
        gap = float(jnp.max(jnp.abs(want.astype(jnp.float32)
                                    - got.astype(jnp.float32))))
        assert gap < TOLERANCE[dtype] * scale, (name, gap, scale)


BD = A.Mask("block_diffusion", 4, 4096)
GQA_DIMS = (128, 0, 128)
BD_NAMED = {"rule": "block_diffusion", "block": 4, "half": 4096}


def test_route_under_the_block_mask_names_it_and_counts_its_tiles(monkeypatch):
    """The cell's layer: a doubled row of 8192 in query tiles of 512. On a
    TPU key tiles of 2048: a noised query tile visits its own noised key
    tile and the clean ones that start before its end, a clean one the
    clean tiles up to its own: 8 x 1 + (1+1+1+1+2+2+2+2) + the same again
    = 32 of 64. The XLA blocks count keys in blocks of 512: 8 + 36 + 36."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert A.attention_route(8192, 512, GQA_DIMS, BD) == {
        "path": "fused", "block_q": 512, "block_kv": 2048, "mask": BD_NAMED,
        "tiles": {"visited": 32, "all": 64},
        "prep": {"path": "fused", "block_s": 512}}
    # blocks that are no power of two, or do not tile a query tile: XLA
    for block in (3, 1024):
        odd = A.Mask("block_diffusion", block, 4096)
        assert A.attention_route(8192, 512, GQA_DIMS, odd)["path"] == "xla_blocks"
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert A.attention_route(8192, 512, GQA_DIMS, BD) == {
        "path": "xla_blocks", "block_q": 512, "mask": BD_NAMED,
        "tiles": {"visited": 80, "all": 256}, "prep": {"path": "xla"}}


def test_the_grouped_layer_takes_the_fused_path_where_the_route_says(monkeypatch):
    from deepof_tpu.ops.pallas import attention as K

    calls, real = [], K.fused_grouped_attention

    def recording(*a, **kw):
        calls.append(a[4:])
        return real(*a, interpret=True, **kw)

    mask = A.Mask("block_diffusion", 4, 128)
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (1, 256, h, 128))
               for kk, h in zip(keys, (2, 1, 1)))
    blocks = A.grouped_attention(q, k, v, 0.09, 128, jnp.float32, mask)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(K, "fused_grouped_attention", recording)
    fused = A.grouped_attention(q, k, v, 0.09, 128, jnp.float32, mask)
    assert calls == [(128, 128, mask)]  # a copy of 128: the key tile is the query's
    np.testing.assert_allclose(np.asarray(fused), np.asarray(blocks), atol=2e-5)
