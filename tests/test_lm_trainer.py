"""The language model on the Trainer's normal path (`--preset lm`): fit,
metrics.jsonl with the routing counters, checkpoint and restore, eval; the
`lm` section filled from a JSON file of config.json's shape; the token
dataset; and the one place for what a model is (`models/registry.py`):
every model's task, example input and evaluation by what it declares."""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from deepof_tpu import cli
from deepof_tpu.core.config import (DataConfig, LMConfig, fill_lm_from_file,
                                    get_config)
from deepof_tpu.data import TokenData, build_dataset
from deepof_tpu.models import registry
from deepof_tpu.train.evaluate import EVALUATORS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KANANA = os.path.join(ROOT, "benchmark", "configs", "kanana2_30b_a3b_ep8.json")


def lm_cfg(tmp_path, seq_len=32, **train):
    cfg = get_config("lm")
    return cfg.replace(
        lm=dataclasses.replace(cfg.lm, attn_block_q=16, loss_block=16, seq_len=seq_len,
                               n_routed_experts=2, n_routed_experts_published=8),
        train=dataclasses.replace(cfg.train, log_dir=str(tmp_path), log_every=1,
                                  nan_guard=False, **train))


def one_device_mesh():
    """The suite's eight CPU devices would split two rows eight ways."""
    from deepof_tpu.parallel.mesh import build_mesh

    return build_mesh(devices=jax.devices()[:1])


def test_trainer_fits_logs_counters_checkpoints_and_restores(tmp_path):
    from deepof_tpu.train.loop import Trainer

    # 2 rows of 512: 2048 token-slots, the shortest step whose expert
    # layers hold both widths of the sorted list under a `cond`
    cfg = lm_cfg(tmp_path, seq_len=512)
    trainer = Trainer(cfg, mesh=one_device_mesh())
    out = trainer.fit(max_steps=3)
    assert out["steps_per_sec"] >= 0
    with open(tmp_path / "metrics.jsonl") as f:
        records = [json.loads(line) for line in f]
    train = [r for r in records if r["kind"] == "train"]
    assert [r["step"] for r in train] == [1, 2, 3]
    for r in train:
        assert np.isfinite(r["loss"]) and 4.0 < r["loss"] < 7.0  # ~ ln 256
        assert len(r["moe_slots_held_share"]) == 2  # one value an expert layer
        assert all(0.0 <= v <= 1.0 for v in r["moe_slots_held_share"])
        assert all(v >= 1.0 for v in r["moe_load_max_over_mean"])
        assert all(0.0 <= v <= 1.0 for v in r["moe_tokens_none_held_share"])
        assert r["moe_full_width"] == [0.0, 0.0]  # both expert layers ran the compact list
        assert "warp_sweep_rows_by_scale" not in r
    routes, = [r for r in records if r.get("message") == "routes"]
    assert routes["step"] == 0 and routes["attention_route"]["path"] == "xla_blocks"
    # a row's share: 2 of 8 held, twice even, up to the grouped product's tile
    assert routes["expert_rows"] == {"cap": 512, "slots": 1024}
    params = jax.device_get(trainer.state.params)
    again = Trainer(cfg, mesh=one_device_mesh())  # auto-resume from the final checkpoint
    assert int(again.state.step) == 3
    same = jax.tree_util.tree_map(lambda a, b: bool(np.array_equal(a, b)),
                                  params, jax.device_get(again.state.params))
    assert all(jax.tree_util.tree_leaves(same))
    ev = again.evaluate()
    assert set(ev) == {"val_loss", "val_perplexity"} and 4.0 < ev["val_loss"] < 7.0


def test_the_bias_is_a_buffer_the_optimizer_leaves_alone(tmp_path):
    from deepof_tpu.train.loop import Trainer

    trainer = Trainer(lm_cfg(tmp_path), mesh=one_device_mesh())
    before = np.asarray(trainer.state.params["layer_1"]["moe"]["bias"])
    router = np.asarray(trainer.state.params["layer_1"]["moe"]["router"])
    trainer.fit(max_steps=2)
    assert np.array_equal(before, np.asarray(
        trainer.state.params["layer_1"]["moe"]["bias"]))
    assert not np.array_equal(router, np.asarray(
        trainer.state.params["layer_1"]["moe"]["router"]))


def test_lm_section_is_filled_from_a_config_json_and_overrides_win():
    cfg = cli.config_for(["train", "--preset", "lm", "--set",
                          "lm.num_hidden_layers=2", "--set",
                          f"lm.config_file={KANANA}", "--set", "lm.seq_len=128"])
    lm = cfg.lm
    assert (lm.hidden_size, lm.num_attention_heads, lm.kv_lora_rank) == (2048, 32, 512)
    assert (lm.qk_nope_head_dim, lm.qk_rope_head_dim, lm.v_head_dim) == (128, 64, 128)
    assert (lm.n_routed_experts, lm.n_routed_experts_published) == (16, 128)
    assert (lm.num_experts_per_tok, lm.routed_scaling_factor) == (6, 2.448)
    assert lm.vocab_size == 16032 and lm.rope_theta == 1000000
    assert lm.num_hidden_layers == 2 and lm.seq_len == 128  # --set wins
    assert cfg.model == "latent_moe_lm" and cfg.data.dataset == "tokens"
    with pytest.raises(SystemExit, match="lm.config_file"):
        cli.config_for(["train", "--preset", "lm", "--set",
                        "lm.config_file=/nonexistent.json"])


def test_configuration_file_keeps_every_published_width():
    """No width of the catalog row differs; `reduced` names what does."""
    with open(KANANA) as f:
        c = json.load(f)
    published = dict(
        hidden_size=2048, intermediate_size=6144, moe_intermediate_size=768,
        num_attention_heads=32, kv_lora_rank=512, q_lora_rank=None,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        n_shared_experts=2, num_experts_per_tok=6, routed_scaling_factor=2.448,
        first_k_dense_replace=1, moe_layer_freq=1, rope_theta=1000000,
        rms_norm_eps=1e-06, scoring_func="sigmoid", topk_method="noaux_tc")
    assert {k: c[k] for k in published} == published
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"]) == (5, 16, 16032)
    assert c["published"] == {"num_hidden_layers": 48, "n_routed_experts": 128,
                              "vocab_size": 128256}
    assert c["vocab_size"] * 8 == 128256 and c["n_routed_experts_published"] == 128
    lm = fill_lm_from_file(LMConfig(), KANANA)
    assert c["parameter_table"]["all"] == c["parameters"] == 575955968
    assert lm.init_std == 0.02


def test_token_dataset_is_zipf_seeded_and_in_range():
    lm = LMConfig(vocab_size=1000, seq_len=64)
    ds = build_dataset(DataConfig(dataset="tokens"), lm=lm)
    assert isinstance(ds, TokenData) and ds.num_train == TokenData.POOL_ROWS
    b = ds.sample_train(4, rng=np.random.RandomState(0))
    assert b["tokens"].shape == (4, 65) and b["tokens"].dtype == np.int32
    assert 0 <= ds.rows.min() and ds.rows.max() < 1000
    counts = np.bincount(ds.rows.reshape(-1), minlength=1000)
    assert counts[0] > counts[9] > counts[99]  # frequent ids repeat
    again = build_dataset(DataConfig(dataset="tokens"), lm=lm)
    assert np.array_equal(ds.rows, again.rows)
    assert ds.sample_val(3, 0)["tokens"].shape == (3, 65)
    with pytest.raises(ValueError, match="lm"):
        build_dataset(DataConfig(dataset="tokens"))


DECLARED = {
    "flownet_s": ("flow", 6, False, None),
    "vgg16": ("flow", 6, False, ("encoder",)),
    "inception_v3": ("flow", 6, False, None),
    "flownet_c": ("flow", 6, False, None),
    "flownet_cs": ("flow", 6, False, None),
    "st_single": ("action", 6, True, ("encoder",)),
    "st_baseline": ("action", 6, True, ("spatial",)),
    "ucf101_spatial": ("classify", 3, False, ("encoder",)),
}


@pytest.mark.parametrize("name", sorted(DECLARED))
def test_each_model_declares_what_the_trainer_asked_its_name_for(name):
    task, channels, border, trunk = DECLARED[name]
    cfg = get_config("flyingchairs").replace(model=name)
    model = registry.model_for(cfg)
    assert registry.task_of(model) == task and task in EVALUATORS
    x = registry.example_input(model, cfg)
    assert x.shape == (cfg.data.batch_size, 320, 448, channels)
    assert x.dtype == np.float32
    assert getattr(model, "smooth_border_mask", False) is border
    assert getattr(model, "vgg16_trunk_path", None) == trunk
    assert hasattr(model, "flow_channels") == (task != "classify")


def test_the_language_model_declares_its_task_and_input():
    cfg = get_config("lm")
    model = registry.model_for(cfg)
    assert registry.task_of(model) == "lm" and model.remat is True
    x = registry.example_input(model, cfg)
    assert x.shape == (2, cfg.lm.seq_len) and x.dtype == np.int32
    with pytest.raises(KeyError, match="unknown model"):
        registry.model_for(cfg.replace(model="nope"))


@pytest.mark.parametrize("path", ["serve", "predict", "predict_action"])
def test_predict_and_serve_refuse_the_family_by_name(path):
    from deepof_tpu import predict
    from deepof_tpu.serve.engine import build_serve_model

    cfg = get_config("lm")
    call = {"serve": lambda: build_serve_model(cfg),
            "predict": lambda: predict.restore_params(cfg),
            "predict_action": lambda: predict.restore_action_params(cfg)}[path]
    with pytest.raises(registry.NoServingPath, match="language model"):
        call()
