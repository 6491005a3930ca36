"""The fourth language-model family (`model_type: afmoe`: gated grouped-query
attention with per-head norms, window layers with rotary positions beside
full layers with none, sandwich norms, sigmoid-routed experts with a
shared one), and the sliding window's rule (`ops/attention.py::Mask`
`window`) with its kernels, against brute force and the family's plain
reference (`benchmark/reference/trinity_mini_ep16.py`) at a small size on
the CPU: hidden 64, published layers 0 (dense, window), 2 (expert, window)
and 3 (expert, full), 4 query heads over 2 key/value heads of 16, a window
of 5, 8 experts top-2 of which 2 are held, vocabulary 256, rows of 32;
seeded random weights.

Tolerances, as `tests/test_lm_hybrid.py` states them: in float32 both
sides do the same arithmetic in another order (blocked attention and
loss, sorted grouped products): 2e-5 relative on a row's loss, 2e-4 on a
leaf's gradient. The kernels against the XLA blocks in float32: 1e-5 of
the largest value.
"""

import dataclasses
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict, unflatten_dict

from deepof_tpu.core.config import LMConfig, fill_lm_from_file
from deepof_tpu.models.lm import layers as L
from deepof_tpu.models.lm.model import WindowedMoELM
from deepof_tpu.ops import attention as A

ref = importlib.import_module("benchmark.reference.trinity_mini_ep16")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRINITY = os.path.join(ROOT, "benchmark", "configs", "trinity_mini_ep16.json")
with open(TRINITY) as _f:
    CELL = json.load(_f)
#: the toy's keys under the published names (the reference reads them; the
#: program's `lm` section is filled from a file of them)
TOY = dict(num_hidden_layers=3, hidden_size=64, vocab_size=256,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           num_experts=2, n_routed_experts_published=8, first_expert=2,
           moe_intermediate_size=32, intermediate_size=96,
           num_experts_per_tok=2, sliding_window=5, published_layers=[0, 2, 3],
           num_dense_layers=1,
           layer_types=["sliding_attention"] * 3 + ["full_attention"])
TOL = {"float32": dict(loss=2e-5, grad=2e-4)}
TOKENS = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (2, 33), 0, 255))


def toy_config(tmp_path_factory, **keys) -> tuple[dict, LMConfig]:
    """(the reference's dict, the program's `lm` section) of the toy: the
    cell's file with the sizes cut, through the program's own file reader."""
    c = {k: v for k, v in CELL.items() if k != "weights"}
    c.update(TOY, **keys)
    path = tmp_path_factory.mktemp("afmoe") / "toy.json"
    path.write_text(json.dumps(c))
    return c, dataclasses.replace(fill_lm_from_file(LMConfig(), str(path)),
                                  seq_len=32, attn_block_q=8, loss_block=16)


def rel(a, b):
    return float(jnp.linalg.norm((a - b).astype(jnp.float32))
                 / (jnp.linalg.norm(b.astype(jnp.float32)) + 1e-30))


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    c, lm = toy_config(tmp_path_factory)
    vals = ref.make_params(c, jax.random.PRNGKey(3))
    return c, lm, vals, unflatten_dict({tuple(k.split("/")): v
                                        for k, v in vals.items()})


@pytest.fixture
def small_query_blocks(monkeypatch):
    """The reference's blocks of queries at the toy's size: rows of 32 in
    blocks of 8, so that a window layer's blocks start past their first
    visible key."""
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)


# ------------------------------------------------------------- the rule


def brute_visible(s: int, window: int):
    q, k = np.arange(s)[:, None], np.arange(s)[None, :]
    return (k <= q) & (q - k < window)


RULE_CASES = [(w, blk, s) for w in (1, 128, 200, 256, 384)
              for blk, s in ((128, 256), (128, 1024), (256, 512), (256, 1024))]


@pytest.mark.parametrize("window,block,s", RULE_CASES)
def test_window_rules_tile_arithmetic_is_brute_force(window, block, s):
    """Every query tile of `block` against every key tile of 2 x `block`
    and of `block`: which tiles hold a visible pair (exactly), which are
    wholly visible (never where one pair is hidden), the key tiles a query
    tile visits and the query tiles a key tile is visited by (exactly the
    tiles holding a pair), the key range of a block of queries (from its
    earliest visible key to its last query), and the tiles counted for the
    step-0 record."""
    mask = A.Mask("window", window=window)
    see = brute_visible(s, window)
    assert (np.asarray(mask.visible(np.arange(s)[:, None],
                                    np.arange(s)[None, :])) == see).all()
    for bkv in (block, 2 * block):
        nq, nk = s // block, s // bkv
        held = np.array([[see[i * block:(i + 1) * block,
                              j * bkv:(j + 1) * bkv].any() for j in range(nk)]
                         for i in range(nq)])
        whole = np.array([[see[i * block:(i + 1) * block,
                               j * bkv:(j + 1) * bkv].all() for j in range(nk)]
                          for i in range(nq)])
        for i in range(nq):
            q0, q1 = i * block, (i + 1) * block
            for j in range(nk):
                k0, k1 = j * bkv, (j + 1) * bkv
                assert bool(mask.tile_visible(q0, q1, k0, k1)) == held[i, j]
                if bool(mask.tile_wholly_visible(q0, q1, k0, k1)):
                    assert whole[i, j]
            lo1, hi1, lo2, hi2 = (int(x) for x in mask.key_tile_ranges(q0, block, bkv))
            assert lo2 > hi2
            assert list(range(lo1, hi1 + 1)) == list(np.flatnonzero(held[i]))
            (k0, k1), = mask.key_ranges(q0, q1)
            cols = np.flatnonzero(see[q0:q1].any(axis=0))
            assert (k0, k1) == (cols[0], q1) and cols[-1] == q1 - 1
        for j in range(nk):
            lo1, hi1, lo2, hi2 = (int(x) for x in
                                  mask.query_tile_ranges(j * bkv, bkv, block, s))
            assert lo2 > hi2
            assert list(range(lo1, hi1 + 1)) == list(np.flatnonzero(held[:, j]))
        assert mask.tiles(s, block, bkv) == {"visited": int(held.sum()),
                                             "all": nq * nk}


def test_the_cells_tiles_are_the_window_s():
    """At the cell's row of 16384 under W = 2048: 150 of 1024 tiles of
    512 x 512 (the causal rule 528), 60 of 256 at the kernels' 512 x 2048
    (144); the kernels' grids walk 2 key tiles of 2048 a query tile and 8
    query tiles of 512 a key tile."""
    from deepof_tpu.ops.pallas.attention import _window_reach

    win = A.Mask("window", window=2048)
    assert win.tiles(16384, 512, 512) == {"visited": 150, "all": 1024}
    assert A.CAUSAL.tiles(16384, 512, 512) == {"visited": 528, "all": 1024}
    assert win.tiles(16384, 512, 2048) == {"visited": 60, "all": 256}
    assert A.CAUSAL.tiles(16384, 512, 2048) == {"visited": 144, "all": 256}
    assert _window_reach(win, 512, 2048, 16384) == (2, 8)


# ---------------------------------------------------------- the kernels


def grouped_operands(s: int, key=0):
    k = jax.random.split(jax.random.PRNGKey(key), 4)
    return (jax.random.normal(k[0], (1, s, 4, 128)),
            jax.random.normal(k[1], (1, s, 2, 128)),
            jax.random.normal(k[2], (1, s, 2, 128)),
            jax.random.normal(k[3], (1, s, 4, 128)))


def kernel_and_blocks(window: int, bkv: int, blocks_window: int):
    """(the fused kernels' loss under `window`, the XLA blocks' under
    `blocks_window`) of rows of 512, query tiles of 128, key tiles of
    `bkv`: the sum of the output against a fixed cotangent."""
    from deepof_tpu.ops.pallas.attention import fused_grouped_attention

    do = grouped_operands(512)[3]
    mine, theirs = (A.Mask("window", window=w) for w in (window, blocks_window))
    fused = lambda q, k, v: jnp.sum(fused_grouped_attention(  # noqa: E731
        q, k, v, 0.09, 128, bkv, mine, interpret=True) * do)
    blocks = lambda q, k, v: jnp.sum(A.xla_blocks_grouped_attention(  # noqa: E731
        q, k, v, 0.09, 128, jnp.float32, theirs) * do)
    return fused, blocks


def both_with_gradients(window, bkv, blocks_window):
    fused, blocks = kernel_and_blocks(window, bkv, blocks_window)
    q, k, v, _ = grouped_operands(512)
    return [jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
            for f in (fused, blocks)]


@pytest.mark.parametrize("window,bkv", [(200, 256), (300, 128), (131, 256)])
def test_window_kernels_are_the_xla_blocks(window, bkv):
    """`swa_attn_fwd` / `swa_attn_bwd` in interpret mode against the XLA
    blocks under the same window, one not a multiple of a tile: the output
    and the gradients of q, k and v."""
    (got, dgot), (want, dwant) = both_with_gradients(window, bkv, window)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    for a, b in zip(dgot, dwant):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5 * float(jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("off", [-1, 1])
def test_a_window_one_key_off_fails_the_comparison(off):
    """The comparison above sees a window of W +- 1: the kernels under W
    against the blocks under W + off differ by more than its tolerance, in
    the gradients of q, k and v each."""
    (_, dgot), (_, dwant) = both_with_gradients(200, 256, 200 + off)
    for a, b in zip(dgot, dwant):
        assert float(jnp.max(jnp.abs(a - b))) > 1e-3 * float(jnp.max(jnp.abs(b)))


# ------------------------------------------------------------ the model


def steer_to_tpu(monkeypatch):
    """`jax.default_backend()` says "tpu"; the prep pass and the grouped
    kernels run in interpret mode."""
    from deepof_tpu.ops.pallas import attention as K
    from deepof_tpu.ops.pallas import qk_prep as P

    for module, name in ((P, "qk_prep"), (K, "fused_grouped_attention")):
        real = getattr(module, name)
        monkeypatch.setattr(module, name, lambda *a, _r=real, **kw: _r(
            *a, interpret=True, **kw))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_loss_and_every_gradient_match_reference(toy, small_query_blocks):
    """Two window layers and one full layer, each recomputed in the
    backward as the cell runs them: each row's loss and every leaf's
    gradient against the reference's."""
    c, lm, vals, params = toy
    dtype = "float32"
    model = WindowedMoELM(lm, remat=True)

    def loss(p):
        return model.apply({"params": p}, jnp.asarray(TOKENS), method="loss")

    out = loss(params)
    assert out["moe_slots_held_share"].shape == (2,)  # two expert layers
    with jax.default_matmul_precision("highest"):
        rows = jnp.stack([ref.row_loss(vals, jnp.asarray(TOKENS[i]), c)
                          for i in range(2)])
        want = jax.grad(lambda v: sum(ref.row_loss(v, jnp.asarray(TOKENS[i]), c)
                                      for i in range(2)) / 2)(vals)
    got_rows = out["loss_rows"]
    assert float(jnp.max(jnp.abs(got_rows - rows) / rows)) < TOL[dtype]["loss"]
    got = {"/".join(k): v for k, v in flatten_dict(
        jax.grad(lambda p: loss(p)["loss_rows"].mean())(params)).items()}
    assert set(got) == set(want)
    worst = max((rel(got[k], want[k]), k) for k in want)
    assert worst[0] < TOL[dtype]["grad"], worst


def test_the_chips_route_matches_reference(tmp_path_factory, monkeypatch):
    """The same on the route a TPU takes (steered; kernels in interpret
    mode): heads of 128, rows of 256 in query tiles of 128, a window of
    100; the full layer's q and k through the prep pass at position 0."""
    c, lm = toy_config(tmp_path_factory, num_attention_heads=2,
                       num_key_value_heads=1, head_dim=128, sliding_window=100)
    lm = dataclasses.replace(lm, seq_len=256, attn_block_q=128, loss_block=128)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 128)
    vals = ref.make_params(c, jax.random.PRNGKey(4))
    params = unflatten_dict({tuple(k.split("/")): v for k, v in vals.items()})
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (1, 257), 0, 255))
    steer_to_tpu(monkeypatch)
    model = WindowedMoELM(lm)
    routes = model.routes()["attention_route"]
    assert [r["path"] for r in routes] == ["fused"] * 3
    assert [r["prep"]["path"] for r in routes] == ["fused"] * 3
    got, grads = jax.value_and_grad(lambda p: model.apply(
        {"params": p}, jnp.asarray(tokens), method="loss")["loss_rows"].mean())(params)
    with jax.default_matmul_precision("highest"):
        want, wgrads = jax.value_and_grad(
            lambda v: ref.row_loss(v, jnp.asarray(tokens[0]), c))(vals)
    assert abs(float(got) - float(want)) < TOL["float32"]["loss"] * float(want)
    grads = {"/".join(k): v for k, v in flatten_dict(grads).items()}
    worst = max((rel(grads[k], wgrads[k]), k) for k in wgrads)
    assert worst[0] < TOL["float32"]["grad"], worst


@pytest.mark.parametrize("route", ["xla", "fused"])
@pytest.mark.parametrize("layer", ["full", "full_rotated"])
def test_no_positions_reach_the_full_layer(monkeypatch, route, layer):
    """The full layer's attention sees its earlier keys as a set: its
    output at the last position does not change when the positions before
    it are permuted. Rotary positions reaching the layer (`rotary` set, the
    benchmark's planted fault) change it."""
    lm = dataclasses.replace(LMConfig(), hidden_size=64, num_attention_heads=2,
                             num_key_value_heads=1, head_dim=128,
                             attn_block_q=128, rope_interleave=False)
    if route == "fused":
        steer_to_tpu(monkeypatch)
    attn = L.GQA(lm, gated=True, rotary=layer == "full_rotated", name="gqa")
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 256, 64))
    params = attn.init(jax.random.PRNGKey(5), h)
    perm = np.concatenate([np.random.RandomState(0).permutation(255), [255]])
    last = [attn.apply(params, x)[0, -1] for x in (h, h[:, perm])]
    gap = rel(last[1], last[0])
    if layer == "full":
        assert gap < 1e-5, gap
    else:
        assert gap > 1e-3, gap


def test_expert_shares_add_up_to_the_uncut_layer(toy):
    """Two chips' shares of the expert layer (experts 0..3 and 4..7 of 8),
    the shared expert counted once, are the reference's layer with all 8."""
    c, lm, _, _ = toy
    c = {**c, "num_experts": 8}
    uncut = ref.make_params(c, jax.random.PRNGKey(11))
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 64, 64))
    h = h / jnp.sqrt(jnp.mean(h * h, -1, keepdims=True))
    p = {k.split("/", 2)[2]: v for k, v in uncut.items()
         if k.startswith("layer_1/moe/")}
    parts = []
    for first in (0, 4):
        share_lm = dataclasses.replace(lm, n_routed_experts=4, first_expert=first)
        share = {**p, **{k: v[first:first + 4] for k, v in p.items()
                         if k.startswith("experts_")}}
        parts.append(L.MoE(share_lm).apply(
            {"params": unflatten_dict({tuple(k.split("/")): v
                                       for k, v in share.items()})}, h)[0])
    with jax.default_matmul_precision("highest"):
        shared = jnp.stack([ref.swiglu(h[i], p["shared/w_gate"], p["shared/w_up"],
                                       p["shared/w_down"]) for i in range(2)])
        want = jnp.stack([ref.moe(uncut, "layer_1", h[i], c, first=0, held=8)
                          for i in range(2)])
    assert rel(parts[0] + parts[1] - shared, want) < 2e-5


def test_references_layer_by_layer_gradient_is_its_whole_row(toy, small_query_blocks):
    _, _, vals, _ = toy
    c, row = toy[0], jnp.asarray(TOKENS[0])
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(lambda v: ref.row_loss(v, row, c))(vals)
        loss, got = ref.make_row_grad(c)(
            vals, row, {k: jnp.zeros_like(v) for k, v in vals.items()}, 0.5)
    assert abs(float(loss) - float(want_loss)) < 1e-6 * float(want_loss)
    worst = max((rel(got[k], 0.5 * want[k]), k) for k in want)
    assert worst[0] < 1e-5, worst


# ------------------------------------------------------ the normal path


@pytest.mark.parametrize("backend,path", [("tpu", "fused"), ("cpu", "xla_blocks")])
def test_the_cells_routes_record(monkeypatch, backend, path):
    """The step-0 `routes` record of the cell's configuration (a row of
    16384, `lm.attn_block_q` 512 as the cell sets it): the window rule on
    layers 0-3 and the causal one on layer 4, rotary positions on 0-3
    only, the prep pass fused on all five on a TPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    lm = dataclasses.replace(fill_lm_from_file(LMConfig(), TRINITY),
                             seq_len=16384, attn_block_q=512)
    routes = WindowedMoELM(lm).routes()["attention_route"]
    assert [r["mask"] for r in routes] == \
        [{"rule": "window", "window": 2048}] * 4 + ["causal"]
    assert [r["rotary"] for r in routes] == [True] * 4 + [False]
    assert {r["path"] for r in routes} == {path}
    if backend == "tpu":
        assert [r["prep"] for r in routes] == [{"path": "fused", "block_s": 512}] * 5
        assert [r["tiles"]["visited"] for r in routes] == [60] * 4 + [144]


def test_family_trains_through_trainer_fit_from_its_config_file(tmp_path):
    """`train --preset lm` with a config.json of `model_type: afmoe` (the
    cell's own keys, the sizes cut): the registry finds the family by its
    model_type alone and the same Trainer fits it."""
    from deepof_tpu import cli
    from deepof_tpu.parallel.mesh import build_mesh
    from deepof_tpu.train.loop import Trainer

    c = {k: v for k, v in CELL.items() if k != "weights"}
    c.update(TOY)
    toy_file = tmp_path / "toy_trinity.json"
    toy_file.write_text(json.dumps(c))
    argv = ["train", "--preset", "lm", "--log-dir", str(tmp_path / "run"),
            "--set", f"lm.config_file={toy_file}", "--set", "lm.seq_len=32",
            "--set", "lm.attn_block_q=16", "--set", "lm.loss_block=16",
            "--set", "train.log_every=1", "--set", "train.nan_guard=false"]
    cfg = cli.config_for(argv)
    assert (cfg.lm.model_type, cfg.lm.scoring_func, cfg.lm.norm_topk_prob,
            cfg.lm.routed_scaling_factor, cfg.lm.n_shared_experts,
            cfg.lm.first_k_dense_replace, cfg.lm.mup_enabled) == \
        ("afmoe", "sigmoid", True, 2.826, 1, 1, True)
    trainer = Trainer(cfg, mesh=build_mesh(devices=jax.devices()[:1]))
    assert isinstance(trainer.model, WindowedMoELM)
    trainer.fit(max_steps=2)
    with open(tmp_path / "run" / "metrics.jsonl") as f:
        records = [json.loads(line) for line in f]
    train = [r for r in records if r["kind"] == "train"]
    assert [r["step"] for r in train] == [1, 2]
    assert all(np.isfinite(r["loss"]) and len(r["moe_slots_held_share"]) == 2
               for r in train)
    routes, = [r for r in records if r.get("message") == "routes"]
    assert [r["mask"] for r in routes["attention_route"]] == \
        [{"rule": "window", "window": 5}] * 2 + ["causal"]
    assert [r["rotary"] for r in routes["attention_route"]] == [True, True, False]


def test_configuration_file_keeps_every_published_width():
    """Every number of the catalog row's `config` under its own key but the
    three `reduced` (within the floors: a whole period of `layer_types`
    after a leading dense layer, 8 experts, an eighth of the vocabulary);
    the parameter table is the built tree's leaves."""
    from deepof_tpu.models.registry import model_for
    from deepof_tpu import cli

    c = CELL
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 32, "num_experts": 128,
                              "vocab_size": 200192}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == \
        (5, 8, 25024)
    assert (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["intermediate_size"], c["moe_intermediate_size"],
            c["num_experts_per_tok"], c["sliding_window"], c["num_dense_layers"],
            c["route_scale"], len(c["layer_types"])) == \
        (2048, 32, 4, 128, 6144, 1024, 8, 2048, 2, 2.826, 32)
    assert [c["layer_types"][p] for p in c["published_layers"]] == \
        ["sliding_attention"] * 4 + ["full_attention"]
    cfg = cli.config_for(["train", "--preset", "lm",
                          "--set", f"lm.config_file={TRINITY}",
                          "--set", "lm.seq_len=16"])
    model = model_for(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))["params"]
    built = {"/".join(k): v.shape for k, v in flatten_dict(shapes).items()}
    assert built == {p: s for p, s, _ in ref.param_spec(c)}
    total = sum(int(np.prod(s)) for s in built.values())
    assert total == c["parameter_table"]["all"] == 504147712
    assert c["parameter_table"]["bytes_at_16_per_parameter"] == 16 * total
