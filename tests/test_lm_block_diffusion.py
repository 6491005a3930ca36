"""The second language-model family (`model_type: sdar_moe`: grouped-query
attention under the block-diffusion mask of a doubled row, softmax-routed
experts with no shared one, trained by diffusion over blocks) against its
plain reference (`benchmark/reference/sdar_30b_a3b_ep8.py`) at a small
size on the CPU: hidden 64, 4 query heads over 2 key/value heads of 16, 8
experts top-2 of which 2 are held, vocabulary 256, rows of 32 (64
positions doubled), blocks of 4; seeded random weights.

Tolerances, as `tests/test_lm_model.py` states them for the other family:
in float32 both sides do the same arithmetic in another order (blocked
attention over the visible key ranges, sorted grouped products, blocked
loss): 2e-5 relative, 2e-4 on a leaf's gradient. In bfloat16 the program
rounds every matrix operand to 8 bits of mantissa and accumulates in
float32: 2e-2 on a layer's output, 3e-4 on the loss, 5e-2 on a gradient.
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict, unflatten_dict

from deepof_tpu.core.config import LMConfig, lm_family_config
from deepof_tpu.models.lm import BlockDiffusionMoELM
from deepof_tpu.models.lm import layers as L
from deepof_tpu.models.lm.model import block_noise
from deepof_tpu.ops import attention as A

ref = importlib.import_module("benchmark.reference.sdar_30b_a3b_ep8")

LM = lm_family_config(
    "sdar_moe", num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    n_routed_experts=2, n_routed_experts_published=8, first_expert=2,
    attn_block_q=8, loss_block=16, seq_len=32, block_length=4,
    mask_token_id=255, num_hidden_layers=2)
TOL = {"float32": dict(layer=2e-5, loss=2e-5, grad=2e-4),
       "bfloat16": dict(layer=2e-2, loss=3e-4, grad=5e-2)}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
TOKENS = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (2, 33), 0, 255))


def as_dict(lm: LMConfig) -> dict:
    return dataclasses.asdict(lm)


def rel(a, b):
    return float(jnp.linalg.norm((a - b).astype(jnp.float32))
                 / (jnp.linalg.norm(b.astype(jnp.float32)) + 1e-30))


@pytest.fixture(scope="module")
def weights():
    vals = ref.make_params(as_dict(LM), jax.random.PRNGKey(3))
    return vals, unflatten_dict({tuple(k.split("/")): v for k, v in vals.items()})


@pytest.fixture(scope="module")
def hidden():
    """Two doubled rows: 64 positions."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64), jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))


# ------------------------------------------------------------ the mask rule


def entry_by_entry(L_: int, B: int) -> np.ndarray:
    """The block-diffusion mask of a doubled row written out one entry at
    a time from the four rules, with no array arithmetic to share a slip
    with the program's or the reference's."""
    see = np.zeros((2 * L_, 2 * L_), bool)
    for q in range(2 * L_):
        for k in range(2 * L_):
            q_clean, k_clean = q >= L_, k >= L_
            qb, kb = (q % L_) // B, (k % L_) // B
            if not q_clean and not k_clean:
                see[q, k] = kb == qb
            elif not q_clean and k_clean:
                see[q, k] = kb < qb
            elif q_clean and k_clean:
                see[q, k] = kb <= qb
    return see


#: a copy of 96 positions in query blocks of 48: blocks of 32 straddle the
#: query blocks' edges, blocks of 4 and 1 tile them
@pytest.mark.parametrize("block", [1, 4, 32])
def test_mask_rule_is_the_mask_written_out_entry_by_entry(block):
    L_, bq = 96, 48
    want = entry_by_entry(L_, block)
    mask = A.Mask("block_diffusion", block, L_)
    pos = jnp.arange(2 * L_)
    assert np.array_equal(np.asarray(mask.visible(pos[:, None], pos[None, :])), want)
    assert np.array_equal(np.asarray(ref.visible(L_, block)), want)
    assert want.diagonal().all()  # every query sees itself: no empty row
    # the key ranges of a query block hold every key one of its queries sees
    for q0 in range(0, 2 * L_, bq):
        inside = np.zeros(2 * L_, bool)
        for k0, k1 in mask.key_ranges(q0, q0 + bq):
            inside[k0:k1] = True
        assert not (want[q0:q0 + bq].any(0) & ~inside).any(), (block, q0)
    tiles = mask.tiles(2 * L_, bq, bq)
    seen = sum(want[i:i + bq, j:j + bq].any() for i in range(0, 2 * L_, bq)
               for j in range(0, 2 * L_, bq))
    assert tiles == {"visited": seen, "all": 16}
    with pytest.raises(ValueError, match="straddle"):
        mask.key_ranges(L_ - 8, L_ + 8)


@pytest.mark.parametrize("block", [1, 4, 32])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_blocked_attention_under_the_rule_is_attention_under_the_written_mask(
        block, dtype):
    """`xla_blocks_grouped_attention` (each query block against the key
    ranges its rule can see) against every score under the mask written
    out; 4 query heads read 2 key/value heads."""
    L_, bq, dt = 96, 48, DTYPES[dtype]
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(k[0], (2, 2 * L_, 4, 16)).astype(dt)
    kk = jax.random.normal(k[1], (2, 2 * L_, 2, 16)).astype(dt)
    v = jax.random.normal(k[2], (2, 2 * L_, 2, 16)).astype(dt)
    mask = A.Mask("block_diffusion", block, L_)
    got = A.grouped_attention(q, kk, v, 0.25, bq, dt, mask)
    see = jnp.asarray(entry_by_entry(L_, block))
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q.reshape(2, 2 * L_, 2, 2, 16)
                   .astype(jnp.float32), kk.astype(jnp.float32)) * 0.25
    p = jax.nn.softmax(jnp.where(see, s, -jnp.inf), -1)
    want = jnp.einsum("bgrqk,bkgd->bqgrd", p, v.astype(jnp.float32)
                      ).reshape(2, 2 * L_, 4, 16)
    assert rel(got, want) < {"float32": 1e-6, "bfloat16": 1e-2}[dtype]
    # and under the causal rule the same function is causal attention
    causal = A.grouped_attention(q, kk, v, 0.25, bq, dt)
    tri = jnp.tril(jnp.ones((2 * L_, 2 * L_), bool))
    want = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(
        jnp.where(tri, s, -jnp.inf), -1), v.astype(jnp.float32)
    ).reshape(2, 2 * L_, 4, 16)
    assert rel(causal, want) < {"float32": 1e-6, "bfloat16": 1e-2}[dtype]


def test_a_query_block_that_straddles_the_copies_is_refused_by_name():
    with pytest.raises(ValueError, match="attn_block_q=64"):
        A.attention_route(192, 64, (16, 0, 16), A.Mask("block_diffusion", 4, 96))


# ---------------------------------------------------------------- the layers


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_gqa_matches_reference(weights, hidden, dtype):
    vals, params = weights
    mask = A.Mask("block_diffusion", 4, 32)
    got = L.GQA(LM, DTYPES[dtype], mask).apply(
        {"params": params["layer_1"]["gqa"]}, hidden)
    see, pos = ref.layout(32, as_dict(LM))
    want = jnp.stack([ref.gqa(vals, "layer_1", hidden[i], as_dict(LM), see, pos)
                      for i in range(2)])
    assert rel(got, want) < TOL[dtype]["layer"]
    # the reference's heads in blocks (what its training steps take)
    blocked = ref.gqa(vals, "layer_1", hidden[0], as_dict(LM), see, pos,
                      head_block=1)
    assert rel(blocked, want[0]) < 1e-6


def test_rotary_positions_count_inside_each_copy(weights, hidden):
    """The same hidden row in both halves: a clean query and the noised
    query of the same place get the same rotary angle, so with every block
    visible to neither but its own (B = L) the two halves' outputs agree."""
    _, params = weights
    both = jnp.concatenate([hidden[:, :32], hidden[:, :32]], axis=1)
    mask = A.Mask("block_diffusion", 32, 32)  # one block: each half sees itself
    out = L.GQA(LM, jnp.float32, mask).apply(
        {"params": params["layer_0"]["gqa"]}, both)
    assert rel(out[:, :32], out[:, 32:]) < 1e-6


def test_softmax_router_matches_reference(weights, hidden):
    vals, _ = weights
    h = hidden[0]
    idx, w = L.route(h, vals["layer_1/moe/router"], None, LM)
    ridx, rw = ref.route(h, vals["layer_1/moe/router"], None, as_dict(LM))
    assert np.array_equal(np.asarray(idx), np.asarray(ridx))
    assert rel(w, rw) < 1e-6
    assert np.allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-5)
    # not renormalised: the chosen probabilities themselves
    raw = dataclasses.replace(LM, norm_topk_prob=False)
    _, w2 = L.route(h, vals["layer_1/moe/router"], None, raw)
    p = jax.nn.softmax(h @ vals["layer_1/moe/router"], -1)
    assert rel(w2, jnp.take_along_axis(p, idx, -1)) < 1e-6


@pytest.mark.parametrize("which", ["program", "reference"])
def test_softmax_router_breaks_ties_toward_the_lower_id(which):
    h = jnp.ones((3, 64), jnp.float32)
    router = jnp.zeros((64, 8), jnp.float32)  # every probability is 1/8
    route = (lambda r: L.route(h, r, None, LM)) if which == "program" else \
        (lambda r: ref.route(h, r, None, as_dict(LM)))
    idx, w = route(router)
    assert np.asarray(idx).tolist() == [[0, 1]] * 3
    assert np.allclose(np.asarray(w), 0.5)
    idx, w = route(router.at[:, 6].set(0.05))
    assert np.asarray(idx).tolist() == [[6, 0]] * 3
    assert float(w[0, 0]) > 0.5 > float(w[0, 1])


@pytest.fixture(scope="module")
def long_hidden():
    """1024 tokens, 2048 token-slots: the shortest the expert layer's sorted
    list has a second width at (`tests/test_lm_model.py` says why)."""
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 512, 64), jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))


#: the router's own choice (compact list) and every token on the held
#: experts 2 and 3 (all 2048 slots held: the full width under the `cond`),
#: by a column of the router made large
WIDTHS = {"compact": (None, 0.0), "full": ((2, 3), 1.0)}


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_expert_layer_with_no_shared_expert_matches_reference(
        weights, long_hidden, dtype, width):
    vals, params = weights
    favoured, full_width = WIDTHS[width]
    p = dict(params["layer_1"]["moe"])
    assert set(p) == {"router", "experts_w_gate", "experts_w_up",
                      "experts_w_down"}  # no bias buffer, no shared expert
    if favoured:
        # rows are unit-RMS: a column along a row's own sign pattern cannot
        # be built for all, so shift the logits by a bias through one more
        # feature instead: hidden's first channel made constant
        long_hidden = long_hidden.at[..., 0].set(1.0)
        p["router"] = p["router"].at[0, jnp.array(favoured)].set(50.0)
        vals = {**vals, "layer_1/moe/router": p["router"]}
    got, counters = L.MoE(LM, DTYPES[dtype]).apply({"params": p}, long_hidden)
    want = jnp.stack([ref.moe(vals, "layer_1", long_hidden[i], as_dict(LM))
                      for i in range(2)])
    assert rel(got, want) < TOL[dtype]["layer"]
    assert float(counters["moe_full_width"]) == full_width
    if favoured:
        assert float(counters["moe_slots_held_share"]) == 1.0


# ------------------------------------------------------- model and objective


def noise_for(key=7):
    return block_noise(jax.random.PRNGKey(key), 2, 32, 4, 0.45, 0.95)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_loss_and_every_gradient_match_reference_under_the_same_masks(
        weights, dtype):
    vals, params = weights
    c = as_dict(LM)
    # recomputed layers in float32 (what the cell runs), kept ones in bfloat16
    model = BlockDiffusionMoELM(LM, dtype=DTYPES[dtype], remat=dtype == "float32")
    m, t = noise_for()
    # the reference draws the same masks from the same key
    rm, rt = ref.draw_noise(jax.random.PRNGKey(7), 2, c, 32)
    assert np.array_equal(np.asarray(m), np.asarray(rm))
    assert np.array_equal(np.asarray(t), np.asarray(rt))
    assert 0.45 <= float(t.min()) and float(t.max()) < 0.95
    assert np.array_equal(np.asarray(t[:, ::4]), np.asarray(t[:, 3::4]))  # one t a block
    ids = jnp.concatenate([jnp.where(m, 255, TOKENS[:, :32]), TOKENS[:, :32]], 1)
    logits = model.apply({"params": params}, ids)
    want = jnp.stack([ref.logits_row(vals, ids[i], c) for i in range(2)])
    assert logits.shape == (2, 32, 256)  # the noised half's only
    assert rel(logits, want) < TOL[dtype]["layer"]

    def loss(p):
        return model.apply({"params": p}, jnp.asarray(TOKENS), None, (m, t),
                           method="loss")["loss_rows"]

    rows = jnp.stack([ref.row_loss(vals, jnp.asarray(TOKENS[i]), c, (m[i], t[i]))
                      for i in range(2)])
    assert float(jnp.max(jnp.abs(loss(params) - rows) / rows)) < TOL[dtype]["loss"]
    got = {"/".join(k): v for k, v in flatten_dict(
        jax.grad(lambda p: loss(p).mean())(params)).items()}
    wantg = jax.grad(lambda v: sum(
        ref.row_loss(v, jnp.asarray(TOKENS[i]), c, (m[i], t[i]))
        for i in range(2)) / 2)(vals)
    assert set(got) == set(wantg)
    worst = max((rel(got[k], wantg[k]), k) for k in wantg)
    assert worst[0] < TOL[dtype]["grad"], worst


def test_references_layer_by_layer_gradient_is_its_whole_rows(weights):
    vals, _ = weights
    c, row = as_dict(LM), jnp.asarray(TOKENS[0])
    m, t = noise_for()
    noise = (m[0], t[0])
    want_loss, want = jax.value_and_grad(
        lambda v: ref.row_loss(v, row, c, noise))(vals)
    row_grad = ref.make_row_grad(c)
    loss, got = row_grad(vals, row, noise,
                         {k: jnp.zeros_like(v) for k, v in vals.items()}, 0.5)
    assert abs(float(loss) - float(want_loss)) < 1e-6 * float(want_loss)
    assert set(got) == set(want)
    worst = max((rel(got[k], 0.5 * want[k]), k) for k in want)
    assert worst[0] < 1e-5, worst


def test_a_row_with_no_position_masked_has_zero_loss_and_zero_gradient(weights):
    _, params = weights
    model = BlockDiffusionMoELM(LM)
    nothing = (jnp.zeros((2, 32), bool), jnp.full((2, 32), 0.5))

    def loss(p):
        return model.apply({"params": p}, jnp.asarray(TOKENS), None, nothing,
                           method="loss")["loss_rows"]

    assert np.array_equal(np.asarray(loss(params)), np.zeros(2, np.float32))
    grads = jax.grad(lambda p: loss(p).sum())(params)
    assert all(float(jnp.max(jnp.abs(g))) == 0.0
               for g in jax.tree_util.tree_leaves(grads))


def test_the_clean_half_never_sees_the_noised_half(weights):
    """Other ids in the noised half (another mask, and ids that are not
    the mask's): the clean half's hidden states do not move at all."""
    _, params = weights
    model = BlockDiffusionMoELM(LM)
    x0 = jnp.asarray(TOKENS[:, :32])

    def clean_half(noised):
        _, state = model.apply({"params": params},
                               jnp.concatenate([noised, x0], 1),
                               capture_intermediates=lambda mdl, _: mdl.name == "layer_1")
        out = state["intermediates"]["layer_1"]["__call__"][0][0]
        return out[:, 32:], out[:, :32]

    a_clean, a_noised = clean_half(jnp.where(noise_for(7)[0], 255, x0))
    b_clean, b_noised = clean_half((x0 + 1) % 255)
    assert np.array_equal(np.asarray(a_clean), np.asarray(b_clean))
    assert not np.array_equal(np.asarray(a_noised), np.asarray(b_noised))


def test_a_masked_position_is_one_the_noise_set_not_one_whose_id_is_the_masks(
        weights):
    """A row whose own ids hold the mask id where the noise did NOT fall
    bears no loss there."""
    _, params = weights
    model = BlockDiffusionMoELM(LM)
    toks = jnp.asarray(TOKENS).at[:, 5].set(255)
    m = jnp.zeros((2, 32), bool).at[:, 9].set(True)
    t = jnp.full((2, 32), 0.5)
    out = model.apply({"params": params}, toks, None, (m, t), method="loss")
    assert float(out["bd_masked_share"][0]) == pytest.approx(1 / 32)
    ids = jnp.concatenate([jnp.where(m, 255, toks[:, :32]), toks[:, :32]], 1)
    logits = model.apply({"params": params}, ids)
    nll = -jax.nn.log_softmax(logits)[jnp.arange(2), 9, toks[:, 9]]
    assert rel(out["loss_rows"], nll / 0.5 / 32) < 1e-6


def test_what_the_family_does_not_write_is_refused_by_name(hidden):
    key = jax.random.PRNGKey(0)
    with pytest.raises(NotImplementedError, match="rope_interleave"):
        L.GQA(dataclasses.replace(LM, rope_interleave=True)).init(key, hidden)
    with pytest.raises(NotImplementedError, match="use_sliding_window"):
        L.GQA(dataclasses.replace(LM, use_sliding_window=True)).init(key, hidden)
    with pytest.raises(ValueError, match="key/value heads"):
        L.GQA(dataclasses.replace(LM, num_key_value_heads=3)).init(key, hidden)
    with pytest.raises(NotImplementedError, match="causal"):
        L.MLA(LMConfig(), mask=A.Mask("block_diffusion", 4, 32)).init(key, hidden)
    with pytest.raises(NotImplementedError, match="greedy"):
        L.route(hidden[0], jnp.zeros((64, 8)), None,
                dataclasses.replace(LM, topk_method="noaux_tc"))
    with pytest.raises(ValueError, match="mask_token_id"):
        BlockDiffusionMoELM(dataclasses.replace(LM, mask_token_id=None)).apply(
            {"params": {}}, jnp.asarray(TOKENS), method="loss")
    with pytest.raises(ValueError, match="model_type"):
        lm_family_config("qwen9")
    with pytest.raises(ValueError, match="no mask rule"):
        A.Mask("sliding")
