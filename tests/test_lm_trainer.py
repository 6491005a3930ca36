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
    assert routes["attention_route"]["prep"] == {"path": "xla"}  # the CPU's
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


# ---- the second family (`model_type: sdar_moe`) on the same path ----------

SDAR = os.path.join(ROOT, "benchmark", "configs", "sdar_30b_a3b_ep8.json")
#: a toy of the published file's shape: what `--set lm.config_file=` reads
TOY_SDAR = dict(
    model_type="sdar_moe", vocab_size=256, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_experts=2, num_experts_per_tok=2,
    n_routed_experts_published=8, first_expert=2, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[], rope_theta=10000,
    use_sliding_window=False, block_length=4, mask_token_id=255,
    noise_t_lo=0.45, noise_t_hi=0.95, notes="a file may hold more than fields")


def sdar_cfg(tmp_path, log_dir, **train):
    toy = tmp_path / "toy_sdar.json"
    toy.write_text(json.dumps(TOY_SDAR))
    argv = ["train", "--preset", "lm", "--log-dir", str(log_dir),
            "--set", f"lm.config_file={toy}", "--set", "lm.seq_len=32",
            "--set", "lm.attn_block_q=16", "--set", "lm.loss_block=16",
            "--set", "train.log_every=1", "--set", "train.nan_guard=false"]
    for k, v in train.items():
        argv += ["--set", f"train.{k}={v}"]
    return cli.config_for(argv)


def train_records(log_dir):
    with open(os.path.join(log_dir, "metrics.jsonl")) as f:
        records = [json.loads(line) for line in f]
    return records, [r for r in records if r["kind"] == "train"]


def test_second_family_trains_from_its_config_file_and_resumes_its_noise(tmp_path):
    """`train --preset lm` with a config.json of the other `model_type`:
    the registry finds the family, the same Trainer fits it, checkpoints,
    restores and evaluates it. Every step draws another mask, from the
    trainer state's own rng: a run resumed from its checkpoint draws step
    3's mask as the run that was never stopped did."""
    from deepof_tpu.models.lm import BlockDiffusionMoELM
    from deepof_tpu.train.loop import Trainer

    cfg = sdar_cfg(tmp_path, tmp_path / "whole")
    assert cfg.model == "latent_moe_lm"  # the preset's; the file's model_type wins
    assert cfg.lm.scoring_func == "softmax" and cfg.lm.n_shared_experts == 0
    assert cfg.lm.n_routed_experts == 2 and cfg.lm.mlp_only_layers == ()
    whole = Trainer(cfg, mesh=one_device_mesh())
    assert isinstance(whole.model, BlockDiffusionMoELM)
    whole.fit(max_steps=3)
    records, train = train_records(tmp_path / "whole")
    assert [r["step"] for r in train] == [1, 2, 3]
    for r in train:
        assert np.isfinite(r["loss"]) and 2.0 < r["loss"] < 9.0  # ~ ln 256 by 1/t
        assert len(r["bd_masked_share"]) == 1 and 0.4 < r["bd_masked_share"][0] < 1.0
        assert len(r["moe_slots_held_share"]) == 2
    shares = [r["bd_masked_share"][0] for r in train]
    assert len(set(shares)) == 3  # no two steps share a mask
    routes, = [r for r in records if r.get("message") == "routes"]
    assert routes["objective"] == "block_diffusion"
    assert routes["attention_route"] == {
        "path": "xla_blocks", "block_q": 16,
        "mask": {"rule": "block_diffusion", "block": 4, "half": 32},
        "tiles": {"visited": 8, "all": 16}, "prep": {"path": "xla"}}
    assert routes["expert_rows"] == {"cap": 128, "slots": 128}  # of 64 positions
    ev = whole.evaluate()
    assert set(ev) == {"val_loss", "val_perplexity"} and np.isfinite(ev["val_loss"])
    # two steps, a checkpoint, and a new Trainer that goes on from it
    cfg2 = sdar_cfg(tmp_path, tmp_path / "stopped")
    Trainer(cfg2, mesh=one_device_mesh()).fit(max_steps=2)
    again = Trainer(cfg2, mesh=one_device_mesh())
    assert int(again.state.step) == 2
    again.fit(max_steps=1)
    _, resumed = train_records(tmp_path / "stopped")
    last = [r for r in resumed if r["step"] == 3][-1]
    assert last["bd_masked_share"][0] == shares[2]
    # (the loss is another row's: the data's order is the pipeline's own)
    assert np.array_equal(np.asarray(again.state.rng), np.asarray(whole.state.rng))


def test_registry_finds_the_family_by_its_published_model_type():
    from deepof_tpu.core.config import lm_family_config
    from deepof_tpu.models.lm import BlockDiffusionMoELM, LatentMoELM

    cfg = get_config("lm")
    assert isinstance(registry.model_for(cfg), LatentMoELM)
    bd = cfg.replace(lm=lm_family_config("sdar_moe", cfg.lm, mask_token_id=255))
    for name in ("latent_moe_lm", "block_diffusion_moe_lm"):
        model = registry.model_for(bd.replace(model=name))
        assert isinstance(model, BlockDiffusionMoELM)
        assert (model.task, model.objective) == ("lm", "block_diffusion")
        assert registry.example_input(model, bd).shape == (2, bd.lm.seq_len)
    with pytest.raises(KeyError, match="model_type='qwen9'"):
        registry.model_for(cfg.replace(
            lm=dataclasses.replace(cfg.lm, model_type="qwen9")))
    declared = {m.model_type: m.objective for m in registry.MODELS.values()
                if registry.task_of(m) == "lm"}
    assert declared == {"deepseek_v3": "next_token", "sdar_moe": "block_diffusion",
                        "afmoe": "next_token"}


def test_second_configuration_file_keeps_every_published_width():
    """Every number of the catalog row's `config` under its own key;
    `reduced` names what differs, within the floors (at least 4 layers, 8
    experts, exactly an eighth of the vocabulary); the parameter table sums
    to what the program holds."""
    with open(SDAR) as f:
        c = json.load(f)
    catalog = dict(
        attention_bias=False, decoder_sparse_step=1, head_dim=128,
        hidden_act="silu", hidden_size=2048, intermediate_size=6144,
        max_position_embeddings=32768, max_window_layers=48, mlp_only_layers=[],
        model_type="sdar_moe", moe_intermediate_size=768, norm_topk_prob=True,
        num_attention_heads=32, num_experts_per_tok=8, num_key_value_heads=4,
        rms_norm_eps=1e-06, rope_scaling=None, rope_theta=1000000,
        sliding_window=None, tie_word_embeddings=False, use_sliding_window=False)
    assert {k: c[k] for k in catalog} == catalog
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                              "vocab_size": 151936}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (5, 16, 18992)
    assert c["num_hidden_layers"] >= 4 and c["num_experts"] >= 8
    assert c["vocab_size"] * 8 == 151936 and c["n_routed_experts_published"] == 128
    assert c["mask_token_id"] == c["vocab_size"] - 1
    assert len(c["assumed"]) >= 9 and c["deployment"].startswith("8 chips share")
    lm = fill_lm_from_file(LMConfig(), SDAR)
    assert (lm.model_type, lm.n_routed_experts, lm.num_key_value_heads) == ("sdar_moe", 16, 4)
    assert (lm.scoring_func, lm.topk_method, lm.n_shared_experts) == ("softmax", "greedy", 0)
    assert (lm.block_length, lm.noise_t_lo, lm.noise_t_hi) == (4, 0.45, 0.95)
    cfg = get_config("lm").replace(lm=dataclasses.replace(lm, seq_len=64))
    model = registry.model_for(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            registry.example_input(model, cfg))["params"]
    held = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    t = c["parameter_table"]
    assert held == t["all"] == c["parameters"] == 550984960
    assert t["layer"] == (t["gqa_per_layer"] + t["norms_per_layer"]
                          + t["router_per_layer"] + t["experts_held_per_layer"])
    assert t["all"] == 5 * t["layer"] + t["embedding_and_head"] + t["final_norm"]
    assert c["train_flops_per_pair"] == pytest.approx(1.11e13, rel=0.01)


def test_token_dataset_never_draws_the_mask_id():
    lm = LMConfig(vocab_size=100, seq_len=256, mask_token_id=3)
    rows = build_dataset(DataConfig(dataset="tokens"), lm=lm).rows
    counts = np.bincount(rows.reshape(-1), minlength=100)
    assert counts[3] == 0 and rows.max() == 99 and counts[2] > counts[4] > 0
    plain = build_dataset(DataConfig(dataset="tokens"),
                          lm=LMConfig(vocab_size=100, seq_len=256)).rows
    assert np.bincount(plain.reshape(-1), minlength=100)[3] > 0
