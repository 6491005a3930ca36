"""The benchmark's language-model weights (`weights` in a configuration's
file: one base draw moved by the seed, PERF.md section 4), for every
language-model family's plain reference at a toy size on the CPU (the
state-space layer's uniform leaves among them): the law a leaf is drawn from stays
N(0, sigma^2), the seed moves every weight a little and a base key redraws
it, and any leaf can be made again alone (what the runner's tap measures
the parameters' change against). `benchmark/tests/test_lm_weights.py`
holds the same for the first reference beside the routing's tests; these
run in tier-1."""

import importlib

import jax
import numpy as np
import pytest

from benchmark.harness import traffic as gen

COMMON = dict(vocab_size=256, hidden_size=64, moe_intermediate_size=32,
              num_hidden_layers=3, num_attention_heads=4,
              num_experts_per_tok=2, n_routed_experts_published=8,
              first_expert=2, norm_topk_prob=True, rms_norm_eps=1e-6,
              rope_theta=10000.0, init_std=0.02, embed_std=1.0)
FAMILIES = {
    "kanana2_30b_a3b_ep8": dict(
        COMMON, intermediate_size=128, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=2,
        n_shared_experts=2, first_k_dense_replace=1, moe_layer_freq=1,
        routed_scaling_factor=2.448, bias_std=0.01),
    "sdar_30b_a3b_ep8": dict(
        COMMON, num_key_value_heads=2, head_dim=16, num_experts=2,
        block_length=4, mask_token_id=255, noise_t_lo=0.45, noise_t_hi=0.95),
    "nemotron_twotower_30b_a3b_ep16": dict(
        COMMON, hybrid_override_pattern="ME*", num_key_value_heads=2,
        head_dim=16, n_routed_experts=2, n_shared_experts=1,
        moe_shared_expert_intermediate_size=48, mamba_num_heads=4,
        mamba_head_dim=8, ssm_state_size=16, n_groups=2, conv_kernel=4,
        time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
        bias_std=0.01),
}
STD = {"normal": "init_std", "embed": "embed_std", "bias": "bias_std"}


@pytest.fixture(params=sorted(FAMILIES))
def family(request):
    ref = importlib.import_module("benchmark.reference." + request.param)
    return ref, lambda base_key=36, jitter=0.01: {
        **FAMILIES[request.param],
        "weights": {"base_key": base_key, "seed_jitter": jitter}}


def params(ref, c: dict, seed: int) -> dict:
    return {k: np.asarray(v) for k, v in
            ref.make_params(c, gen.jax_key(seed, 2)).items()}


def drawn(ref, c: dict):
    return [(p, kind) for p, _, kind in ref.param_spec(c) if kind != "ones"]


def correlation(a, b) -> float:
    return float(np.corrcoef(a.reshape(-1), b.reshape(-1))[0, 1])


def test_law_of_every_drawn_leaf_stays_its_std(family):
    ref, config = family
    c = config()
    vals = params(ref, c, 5)
    big = [(p, k) for p, k in drawn(ref, c) if vals[p].size >= 2048]
    assert len(big) >= 10
    for path, kind in big:  # 5% of a std needs some thousands of draws
        assert vals[path].std() == pytest.approx(c[STD[kind]], rel=0.05), path
        assert abs(vals[path].mean()) < 0.05 * c[STD[kind]], path
    # a seed_jitter as large as the base draw leaves the law where it was
    wide = params(ref, config(jitter=1.0), 5)
    assert wide["lm_head"].std() == pytest.approx(c["init_std"], rel=0.05)


def test_seed_moves_every_weight_a_little_and_a_base_key_redraws_it(family):
    ref, config = family
    a, b = params(ref, config(), 5), params(ref, config(), 3100031999)
    other = params(ref, config(base_key=37), 5)
    for path, _ in drawn(ref, config()):
        if a[path].size < 2048:  # 512 unrelated pairs correlate by 0.044 a sigma
            continue
        assert not np.array_equal(a[path], b[path]), path
        assert correlation(a[path], b[path]) > 0.999, path
        assert abs(correlation(a[path], other[path])) < 0.1, path


def test_any_leaf_can_be_made_again_alone(family):
    ref, config = family
    c, key = config(), gen.jax_key(5, 2)
    vals = ref.make_params(c, key)
    assert set(vals) == {p for p, _, _ in ref.param_spec(c)}
    for i, (path, shape, kind) in enumerate(ref.param_spec(c)):
        alone = jax.jit(lambda k: ref.make_leaf(c, k, i, shape, kind))(key)  # noqa: B023
        np.testing.assert_array_equal(np.asarray(alone), np.asarray(vals[path]), path)
    # nought to rounding: two programs may contract b + j*s differently
    for path, norm in ref.change_norms(c, vals, key).items():
        assert float(norm) <= 1e-6 * float(np.linalg.norm(vals[path])), path
    moved = {**vals, "lm_head": vals["lm_head"] + 1.0}
    norms = ref.change_norms(c, moved, key)
    assert float(norms["lm_head"]) == pytest.approx(vals["lm_head"].size ** 0.5)
    assert float(norms["embedding"]) <= 1e-6 * float(np.linalg.norm(vals["embedding"]))
