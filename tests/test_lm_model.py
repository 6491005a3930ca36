"""The language-model family (`deepof_tpu/models/lm/`) against the plain
reference (`benchmark/reference/kanana2_30b_a3b_ep8.py`) at a small size
on the CPU: hidden 64, 4 heads, 8 experts top-2 of which 2 are held,
vocabulary 256, 32 positions; seeded random weights.

Tolerances: in float32 both sides do the same arithmetic in another order
(blocked attention, sorted grouped products, blocked loss): 2e-5 relative.
In bfloat16 the program rounds every matrix operand to 8 bits of mantissa
and accumulates in float32: 2e-2 on a layer's output, 3e-4 on the loss
(an average over positions), 5e-2 on a leaf's gradient.
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import unflatten_dict

from deepof_tpu.core.config import LMConfig, lm_family_config
from deepof_tpu.models.lm import LatentMoELM
from deepof_tpu.models.lm import layers as L

ref = importlib.import_module("benchmark.reference.kanana2_30b_a3b_ep8")

LM = LMConfig(n_routed_experts=2, n_routed_experts_published=8, first_expert=2,
              routed_scaling_factor=2.448, attn_block_q=8, loss_block=16)
UNCUT = dataclasses.replace(LM, n_routed_experts=8, first_expert=0)
TOL = {"float32": dict(layer=2e-5, loss=2e-5, grad=2e-4),
       "bfloat16": dict(layer=2e-2, loss=3e-4, grad=5e-2)}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def as_dict(lm: LMConfig) -> dict:
    return dataclasses.asdict(lm)


@pytest.fixture(scope="module")
def weights():
    vals = ref.make_params(as_dict(LM), jax.random.PRNGKey(3))
    return vals, unflatten_dict({tuple(k.split("/")): v for k, v in vals.items()})


@pytest.fixture(scope="module")
def hidden():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64), jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))


@pytest.fixture(scope="module")
def long_hidden():
    """1024 tokens, 2048 token-slots: the shortest the expert layer's sorted
    list has a second width at (`expert_row_cap`: 2 held of 8, twice their
    even share, is 1024 rows, two of the grouped product's row tiles)."""
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 512, 64), jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))


TOKENS = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (2, 33), 0, 256))


def rel(a, b):
    return float(jnp.linalg.norm((a - b).astype(jnp.float32))
                 / (jnp.linalg.norm(b.astype(jnp.float32)) + 1e-30))


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_mla_matches_reference(weights, hidden, dtype):
    vals, params = weights
    got = L.MLA(LM, DTYPES[dtype]).apply({"params": params["layer_1"]["mla"]}, hidden)
    want = jnp.stack([ref.mla(vals, "layer_1", hidden[i], as_dict(LM))
                      for i in range(2)])
    assert rel(got, want) < TOL[dtype]["layer"]


def test_attention_blocks_do_not_change_the_result(hidden):
    """8 blocks of 4 queries, 1 block of 32: the same attention."""
    k = jax.random.split(jax.random.PRNGKey(2), 5)
    qn, kn, v = (jax.random.normal(k[i], (2, 32, 4, 16)) for i in range(3))
    qr = jax.random.normal(k[3], (2, 32, 4, 8))
    kr = jax.random.normal(k[4], (2, 32, 8))
    one = L.causal_attention(qn, qr, kn, kr, v, 0.2, 32, jnp.float32)
    many = L.causal_attention(qn, qr, kn, kr, v, 0.2, 4, jnp.float32)
    assert rel(many, one) < 1e-6
    with pytest.raises(ValueError, match="attn_block_q"):
        L.causal_attention(qn, qr, kn, kr, v, 0.2, 5, jnp.float32)


def test_router_matches_reference(weights, hidden):
    vals, _ = weights
    h = hidden[0]
    idx, w = L.route(h, vals["layer_1/moe/router"], vals["layer_1/moe/bias"], LM)
    ridx, rw = ref.route(h, vals["layer_1/moe/router"], vals["layer_1/moe/bias"],
                         as_dict(LM))
    assert np.array_equal(np.asarray(idx), np.asarray(ridx))
    assert rel(w, rw) < 1e-6
    assert np.allclose(np.asarray(w).sum(-1), 2.448, rtol=1e-5)


@pytest.mark.parametrize("which", ["program", "reference"])
def test_ties_and_bias_change_the_chosen_set_as_the_equations_say(which):
    """Equal scores: the lower ids are chosen. A bias moves the choice and
    not the weights, which are the scores without it, renormalised."""
    h = jnp.ones((3, 64), jnp.float32)
    router = jnp.zeros((64, 8), jnp.float32)  # every score is sigmoid(0)
    route = (lambda b: L.route(h, router, b, LM)) if which == "program" else \
        (lambda b: ref.route(h, router, b, as_dict(LM)))
    idx, w = route(jnp.zeros((8,)))
    assert np.asarray(idx).tolist() == [[0, 1]] * 3
    idx, w = route(jnp.zeros((8,)).at[5].set(0.3))
    assert np.asarray(idx).tolist() == [[5, 0]] * 3
    assert np.allclose(np.asarray(w), 2.448 / 2)
    # a bias too small to pass a real gap in the scores changes nothing
    router = router.at[:, 6].set(0.05)
    idx, _ = route(jnp.zeros((8,)).at[5].set(0.3))
    assert np.asarray(idx).tolist() == [[6, 5]] * 3


# What the expert layer's `cond` sees, by a bias on the small model's
# router (`long_hidden`: t*k = 2048 slots, held experts 2 and 3, cap = 2 *
# 2048 * 2/8 = 1024 rows): the ordinary routing and every token on experts
# (2, 6), 1024 slots held == cap exactly, run the compact list; every token
# on (2, 3), all 2048 held, runs the full width under the same `cond`.
PATHS = {"compact": ({}, 0.0), "boundary": ({2: 10.0, 6: 5.0}, 0.0),
         "full": ({2: 10.0, 3: 10.0}, 1.0)}


def on_path(weights, path):
    """(reference's values, the layer's parameters, `moe_full_width`)
    with `path`'s bias on layer_1's router."""
    vals, params = weights
    bias, full_width = PATHS[path]
    if not bias:
        return vals, params["layer_1"]["moe"], full_width
    b = jnp.array([bias.get(i, 0.0) for i in range(8)])
    return ({**vals, "layer_1/moe/bias": b},
            {**params["layer_1"]["moe"], "bias": b}, full_width)


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_expert_layer_matches_reference(weights, long_hidden, dtype, path):
    vals, p, full_width = on_path(weights, path)
    hidden = long_hidden
    got, counters = L.MoE(LM, DTYPES[dtype]).apply({"params": p}, hidden)
    want = jnp.stack([ref.moe(vals, "layer_1", hidden[i], as_dict(LM))
                      for i in range(2)])
    assert rel(got, want) < TOL[dtype]["layer"]
    chosen = jnp.concatenate([ref.route(hidden[i], vals["layer_1/moe/router"],
                                        vals["layer_1/moe/bias"], as_dict(LM))[0]
                              for i in range(2)])
    mine = (chosen >= 2) & (chosen < 4)
    assert float(counters["moe_slots_held_share"]) == pytest.approx(
        float(mine.mean()))
    assert float(counters["moe_tokens_none_held_share"]) == pytest.approx(
        float(1 - mine.any(-1).mean()))
    assert float(counters["moe_full_width"]) == full_width
    if path == "boundary":
        assert int(mine.sum()) == L.expert_row_cap(LM, 1024) == 1024


@pytest.mark.parametrize("path", sorted(PATHS))
def test_rows_the_chips_grouped_product_leaves_unwritten_reach_nothing(
        weights, long_hidden, monkeypatch, path):
    """On the chip `lax.ragged_dot` leaves the rows past its groups, and
    the matching rows of its input's cotangent, unwritten (PR 31's first
    chip run: the loss agreed to 1e-5 and gradient norms read 400 times the
    reference's). Stand-in: NaN there, forward and backward. The layer's
    output and every gradient must not change, at either width of the
    sorted list, and every gradient matches the reference's."""
    vals, p, _ = on_path(weights, path)
    hidden = long_hidden
    real = jax.lax.ragged_dot

    @jax.custom_vjp
    def dirty(a, m, sizes):
        out = real(a, m, sizes, preferred_element_type=jnp.float32)
        rows = (jnp.arange(a.shape[0]) < jnp.sum(sizes))[:, None]
        return jnp.where(rows, out, jnp.nan)

    def fwd(a, m, sizes):
        return dirty(a, m, sizes), (a, m, sizes)

    def bwd(res, ct):
        a, m, sizes = res
        rows = (jnp.arange(a.shape[0]) < jnp.sum(sizes))[:, None]
        _, vjp = jax.vjp(lambda aa, mm: real(
            aa, mm, sizes, preferred_element_type=jnp.float32), a, m)
        da, dm = vjp(jnp.where(rows, ct, 0.0))  # the kernel reads held rows only
        return jnp.where(rows, da, jnp.nan), dm, None

    dirty.defvjp(fwd, bwd)
    loss = lambda pp, h: jnp.sum(L.MoE(LM).apply({"params": pp}, h)[0] ** 2)  # noqa: E731
    clean = jax.grad(loss, argnums=(0, 1))(p, hidden)
    monkeypatch.setattr(L, "ragged_dot", lambda a, m, sizes, **kw: dirty(a, m, sizes))
    got = jax.grad(loss, argnums=(0, 1))(p, hidden)
    ref_loss = lambda v, h: jnp.sum(jnp.stack(  # noqa: E731
        [ref.moe(v, "layer_1", h[i], as_dict(LM)) for i in range(2)]) ** 2)
    assert float(loss(p, hidden)) == pytest.approx(float(ref_loss(vals, hidden)),
                                                   rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(clean)):
        assert bool(jnp.all(jnp.isfinite(a))) and rel(a, b) < 1e-6
    want_v, want_h = jax.grad(ref_loss, argnums=(0, 1))(vals, hidden)
    assert rel(got[1], want_h) < TOL["float32"]["grad"]
    for name in ("router", "experts_w_gate", "experts_w_up", "experts_w_down"):
        assert rel(got[0][name], want_v[f"layer_1/moe/{name}"]) \
            < TOL["float32"]["grad"], name
    for name in ("w_gate", "w_up", "w_down"):
        assert rel(got[0]["shared"][name], want_v[f"layer_1/moe/shared/{name}"]) \
            < TOL["float32"]["grad"], name


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_compact_row_list_computes_what_the_full_width_computes(
        weights, long_hidden, dtype):
    """`add_routed`, the one function of a width, at the layer's two widths
    and one between on the same sorted list (the ordinary routing holds
    fewer slots than `cap`): output and every gradient equal to float32
    rounding. In bfloat16 a sum that differs in its last float32 bit flips
    the rounding of a few elements of `a` (4e-3 each): 1e-3 on a leaf."""
    _, params = weights
    p, x = params["layer_1"]["moe"], long_hidden.reshape(-1, 64)
    idx, w = L.route(x, p["router"], p["bias"], LM)
    gid = jnp.where((idx >= 2) & (idx < 4), idx - 2, 2).reshape(-1)
    sizes = jnp.bincount(gid, length=3)[:2].astype(jnp.int32)
    order = jnp.argsort(gid, stable=True)
    cap = L.expert_row_cap(LM, x.shape[0])
    assert int(sizes.sum()) <= cap < gid.size
    experts = tuple(p[f"experts_w_{n}"] for n in ("gate", "up", "down"))
    shared = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def at(rows):
        f = lambda sh, xx, ww, ex: L.add_routed(  # noqa: E731
            sh, xx, ww, order, sizes, ex, rows, DTYPES[dtype])
        y, vjp = jax.vjp(f, shared, x, w, experts)
        return [y, *jax.tree_util.tree_leaves(vjp(2.0 * y))]

    full, tol = at(gid.size), {"float32": 1e-6, "bfloat16": 1e-3}[dtype]
    for rows in (cap, cap + 512):
        for a, b in zip(at(rows), full):
            assert a.shape == b.shape and rel(a, b) < tol


#: the two families' small models, each beside its own plain reference:
#: the second has a softmax router, no bias buffer and no shared expert
BD = lm_family_config("sdar_moe", LM)
FAMILIES = {"deepseek_v3": (LM, ref),
            "sdar_moe": (BD, importlib.import_module(
                "benchmark.reference.sdar_30b_a3b_ep8"))}


def uncut_weights(family: str = "deepseek_v3"):
    lm, r = FAMILIES[family]
    uncut = dataclasses.replace(lm, n_routed_experts=8, first_expert=0)
    vals = r.make_params(as_dict(uncut), jax.random.PRNGKey(4))
    return {k[len("layer_1/moe/"):]: v for k, v in vals.items()
            if k.startswith("layer_1/moe/")}


def share_params(full: dict, first: int, held: int) -> dict:
    p = {k: (v[first:first + held] if k.startswith("experts_w_") else v)
         for k, v in full.items() if not k.startswith("shared/")}
    shared = {k[len("shared/"):]: v for k, v in full.items()
              if k.startswith("shared/")}
    return {**p, "shared": shared} if shared else p


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_all_shares_add_up_to_the_uncut_layer(long_hidden, held, family):
    """Over all shares of the small model, the routed parts summed with
    what every chip computes alike (the shared expert, where the family has
    one) counted once equal the uncut reference layer: ONE expert layer,
    both families."""
    lm0, r = FAMILIES[family]
    full, hidden = uncut_weights(family), long_hidden
    flat = {f"layer_1/moe/{k}": v for k, v in full.items()}
    c = as_dict(dataclasses.replace(lm0, n_routed_experts=8, first_expert=0))
    shared = jnp.stack([r.swiglu(hidden[i], full["shared/w_gate"],
                                 full["shared/w_up"], full["shared/w_down"])
                        for i in range(2)]) if lm0.n_shared_experts \
        else jnp.zeros_like(hidden)
    total, slots = shared, 0.0
    for first in range(0, 8, held):
        lm = dataclasses.replace(lm0, n_routed_experts=held, first_expert=first)
        apply = lambda h: L.MoE(lm).apply(  # noqa: E731
            {"params": share_params(full, first, held)}, h)
        y, counters = apply(hidden)
        total = total + (y - shared)
        slots += float(counters["moe_slots_held_share"])
        # half or more of the router's experts held: the list is all the
        # slots wide by shape, and the program holds no `cond`
        one_width = L.expert_row_cap(lm, 1024) == 2048
        assert one_width == (held >= 4)
        assert ("cond[" in str(jax.make_jaxpr(apply)(hidden))) != one_width
        if one_width:
            assert float(counters["moe_full_width"]) == 1.0
    want = jnp.stack([r.moe(flat, "layer_1", hidden[i], c) for i in range(2)])
    assert rel(total, want) < 2e-5
    assert slots == pytest.approx(1.0)  # every slot fell on exactly one share
    # and the reference's own share, summed the same way, says the same
    sliced = lambda f: {k: (v[f:f + held] if "experts_w_" in k else v)  # noqa: E731
                        for k, v in flat.items()}
    parts = sum(r.moe(sliced(f), "layer_1", hidden[0], c, first=f, held=held,
                      shared=False) for f in range(0, 8, held))
    assert rel(parts + shared[0], want[0]) < 2e-6


def test_a_token_with_no_held_expert_gets_the_shared_expert_alone(hidden):
    full = uncut_weights()
    full["bias"] = jnp.zeros((8,)).at[jnp.array([6, 7])].set(10.0)  # all choose 6, 7
    y, counters = L.MoE(LM).apply({"params": share_params(full, 2, 2)}, hidden)
    shared = jnp.stack([ref.swiglu(hidden[i], full["shared/w_gate"],
                                   full["shared/w_up"], full["shared/w_down"])
                        for i in range(2)])
    assert float(counters["moe_tokens_none_held_share"]) == 1.0
    assert float(counters["moe_slots_held_share"]) == 0.0
    assert rel(y, shared) < 2e-6


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_forward_loss_and_gradients_match_reference(weights, dtype):
    vals, params = weights
    c = as_dict(LM)
    m = LatentMoELM(LM, dtype=DTYPES[dtype], remat=True)
    ids, tgt = TOKENS[:, :-1], TOKENS[:, 1:]
    logits = m.apply({"params": params}, ids)
    want = jnp.stack([ref.logits_row(vals, TOKENS[i, :-1], c) for i in range(2)])
    assert rel(logits, want) < TOL[dtype]["layer"]
    loss = lambda p: m.apply({"params": p}, ids, tgt)["loss_rows"]  # noqa: E731
    rows = jnp.stack([ref.row_loss(vals, jnp.asarray(TOKENS[i]), c)
                      for i in range(2)])
    assert float(jnp.max(jnp.abs(loss(params) - rows) / rows)) < TOL[dtype]["loss"]
    from flax.traverse_util import flatten_dict

    got = {"/".join(k): v for k, v in flatten_dict(
        jax.grad(lambda p: loss(p).mean())(params)).items()}
    wantg = jax.grad(lambda v: sum(ref.row_loss(v, jnp.asarray(TOKENS[i]), c)
                                   for i in range(2)) / 2)(vals)
    assert float(jnp.max(jnp.abs(got["layer_1/moe/bias"]))) == 0.0  # a buffer
    worst = max((rel(got[k], wantg[k]), k) for k in wantg if "/bias" not in k)
    assert worst[0] < TOL[dtype]["grad"], worst


def test_references_layer_by_layer_gradient_is_its_whole_rows(weights):
    """The reference's training steps take a row's gradient one layer at a
    time, heads in blocks (`make_row_grad`): the same loss and gradient as
    `row_loss` differentiated whole."""
    vals, _ = weights
    c, row = as_dict(LM), jnp.asarray(TOKENS[0])
    want_loss, want = jax.value_and_grad(lambda v: ref.row_loss(v, row, c))(vals)
    row_grad = ref.make_row_grad(c)
    loss, got = row_grad(vals, row, {k: jnp.zeros_like(v) for k, v in vals.items()}, 0.5)
    assert abs(float(loss) - float(want_loss)) < 1e-6 * float(want_loss)
    assert set(got) == set(want)
    worst = max((rel(got[k], 0.5 * want[k]), k) for k in want if "/bias" not in k)
    assert worst[0] < 1e-5, worst
    assert float(jnp.max(jnp.abs(got["layer_1/moe/bias"]))) == 0.0
    # and it adds to what it is handed: the mean over a batch's rows
    _, twice = row_grad(vals, row, dict(got), 0.5)
    worst = max((rel(twice[k], want[k]), k) for k in want if "/bias" not in k)
    assert worst[0] < 1e-5, worst


def test_remat_and_loss_blocks_do_not_change_the_loss(weights):
    _, params = weights
    ids, tgt = TOKENS[:, :-1], TOKENS[:, 1:]
    a = LatentMoELM(LM, remat=True).apply({"params": params}, ids, tgt)
    whole = dataclasses.replace(LM, attn_block_q=32, loss_block=32)
    b = LatentMoELM(whole, remat=False).apply({"params": params}, ids, tgt)
    assert rel(a["loss_rows"], b["loss_rows"]) < 1e-6
    assert a["moe_slots_held_share"].shape == (2,)


def test_what_the_family_does_not_write_is_refused_by_name(hidden):
    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        L.MLA(dataclasses.replace(LM, q_lora_rank=16)).init(
            jax.random.PRNGKey(0), hidden)
    with pytest.raises(NotImplementedError, match="rope_interleave"):
        L.MLA(dataclasses.replace(LM, rope_interleave=False)).init(
            jax.random.PRNGKey(0), hidden)
    with pytest.raises(NotImplementedError, match="sigmoid"):
        L.route(hidden[0], jnp.zeros((64, 8)), jnp.zeros((8,)),
                dataclasses.replace(LM, scoring_func="softmax"))
    with pytest.raises(ValueError, match="are not among"):
        L.MoE(dataclasses.replace(LM, first_expert=7)).init(
            jax.random.PRNGKey(0), hidden)
