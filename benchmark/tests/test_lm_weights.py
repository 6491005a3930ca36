"""The language-model reference's weights under `weights` (one base draw
moved by the seed) at the toy size of `toy_lm.py` on the CPU: the law a
leaf is drawn from stays what `*_std` says, two seeds give weights that
differ and all but coincide, two base keys give unrelated ones, a leaf
made alone is the leaf inside `make_params`, a dict with no `weights`
draws what it drew before, and the routing at the initial weights, which
the cell's steadiness rests on, follows the base key and not the seed."""

import importlib

import jax
import numpy as np
import pytest

from toy_lm import TOY_KEYS

from benchmark.harness import traffic as gen
from benchmark.runners import lm_train as runner

ref = importlib.import_module("benchmark.reference.kanana2_30b_a3b_ep8")

CUT = {**TOY_KEYS, "first_k_dense_replace": 1, "moe_layer_freq": 1,
       "n_shared_experts": 2, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
       "routed_scaling_factor": 2.448,
       "init_std": 0.02, "embed_std": 1.0, "bias_std": 0.01}
STD = {"normal": "init_std", "embed": "embed_std", "bias": "bias_std"}


def config(base_key=36, jitter=0.01) -> dict:
    return {**CUT, "weights": {"base_key": base_key, "seed_jitter": jitter}}


def params(c: dict, seed: int) -> dict:
    return {k: np.asarray(v) for k, v in
            ref.make_params(c, gen.jax_key(seed, 2)).items()}


def drawn(c: dict):
    return [(p, kind) for p, _, kind in ref.param_spec(c) if kind != "ones"]


def correlation(a, b) -> float:
    return float(np.corrcoef(a.reshape(-1), b.reshape(-1))[0, 1])


def test_law_of_every_drawn_leaf_is_its_std():
    c = config()
    vals = params(c, 5)
    for path, kind in drawn(c):
        if vals[path].size >= 2048:  # 5% of a std needs some thousands of draws
            assert vals[path].std() == pytest.approx(c[STD[kind]], rel=0.05), path
    # the few small leaves (the routers' biases) together
    small = np.concatenate([vals[p].reshape(-1) / c[STD[k]] for p, k in drawn(c)
                            if vals[p].size < 2048])
    assert small.size < 2048 or small.std() == pytest.approx(1.0, rel=0.05)
    # a seed_jitter as large as the base draw leaves the law where it was
    wide = params(config(jitter=1.0), 5)
    assert wide["lm_head"].std() == pytest.approx(c["init_std"], rel=0.05)


def test_seeds_move_every_weight_a_little_and_base_keys_redraw_it():
    a, b = params(config(), 5), params(config(), 3100031999)
    other = params(config(base_key=37), 5)
    for path, _ in drawn(config()):
        if a[path].size < 2048:  # 512 unrelated pairs correlate by 0.044 a sigma
            continue
        assert not np.array_equal(a[path], b[path]), path
        assert correlation(a[path], b[path]) > 0.999, path
        assert abs(correlation(a[path], other[path])) < 0.1, path


def test_leaf_made_alone_is_the_leaf_of_make_params_and_has_not_moved():
    c = config()
    key = gen.jax_key(5, 2)
    vals = ref.make_params(c, key)
    for i, (path, shape, kind) in enumerate(ref.param_spec(c)):
        alone = jax.jit(lambda k: ref.make_leaf(c, k, i, shape, kind))(key)  # noqa: B023
        np.testing.assert_array_equal(np.asarray(alone), np.asarray(vals[path]), path)
    # nought to rounding: two programs may contract b + j*s differently (an
    # ulp of some elements), the same on the program's side and the reference's
    for path, norm in ref.change_norms(c, vals, key).items():
        assert float(norm) <= 1e-6 * float(np.linalg.norm(vals[path])), path
    moved = {**vals, "lm_head": vals["lm_head"] + 1.0}
    norms = ref.change_norms(c, moved, key)
    assert float(norms["lm_head"]) == pytest.approx(vals["lm_head"].size ** 0.5)
    assert float(norms["embedding"]) <= 1e-6 * float(np.linalg.norm(vals["embedding"]))


def test_without_weights_the_seed_alone_draws_as_before():
    """Pinned from the parent of the PR that brought `weights`: the
    program's own tests (`tests/test_lm_model.py`) hand in such a dict."""
    key = jax.random.PRNGKey(3)
    leaf = ref.make_leaf(CUT, key, 7, (3,), "normal")
    assert [float(x) for x in leaf] == [
        -0.003174508223310113, -0.006802103482186794, 0.01734529249370098]
    leaf = ref.make_leaf(CUT, key, 0, (2,), "embed")
    assert [float(x) for x in leaf] == [-2.3783397674560547, -1.7061667442321777]
    np.testing.assert_array_equal(
        np.asarray(ref.make_leaf({**CUT, "weights": None}, key, 7, (3,), "normal")),
        np.asarray(ref.make_leaf(CUT, key, 7, (3,), "normal")))


def choices(c: dict, seed: int, row) -> list:
    vals = ref.make_params(c, gen.jax_key(seed, 2))
    return [np.sort(np.asarray(x), -1) for x in
            jax.jit(lambda v, r: ref.chosen_experts(v, r, c))(vals, row)]


def test_routing_follows_the_base_key_and_not_the_seed():
    """One row of ids at the initial weights: two seeds agree on nearly
    every (token, slot) choice of every expert layer, two base keys do not."""
    row = np.asarray(runner.token_pool(gen.jax_key(5, 1), 1, 32, 256, 1.1))[0]
    a = choices(config(), 5, row)
    b = choices(config(), 3100031999, row)
    other = choices(config(base_key=37), 5, row)
    assert len(a) == 2  # the toy's two expert layers
    for la, lb, lo in zip(a, b, other):
        assert np.mean(la == lb) >= 0.9
        assert np.mean(la == lo) < 0.6
