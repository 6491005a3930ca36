"""The third language-model family (`model_type: nemotron_h`: one mixer a
layer by a pattern, Mamba-2 state-space layers, relu^2 experts with a
shared one, grouped-query attention with q, k and v only cast), trained by
diffusion over blocks, against its plain reference
(`benchmark/reference/nemotron_twotower_30b_a3b_ep16.py`) at a small size
on the CPU: hidden 64, layers `MEM*E`, 4 state-space heads of 8 with a
state of 16 in 2 groups, 4 query heads over 2 key/value heads of 16, 8
experts top-2 of which 2 are held, vocabulary 256, rows of 32 (64
positions doubled), blocks of 4, chunks of 12 (a copy of 32 is not a whole
number of them); seeded random weights.

Tolerances, as `tests/test_lm_block_diffusion.py` states them: in float32
both sides do the same arithmetic in another order (the chunked scan
against the per-position recurrence, sorted grouped products, blocked
attention and loss): 2e-5 relative, 2e-4 on a leaf's gradient. In
bfloat16 the program rounds every matrix operand to 8 bits of mantissa
and accumulates in float32: 3e-4 on the loss, 5e-2 on a gradient.
"""

import dataclasses
import functools
import importlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict, unflatten_dict

from deepof_tpu.core.config import lm_family_config
from deepof_tpu.models.lm import layers as L
from deepof_tpu.models.lm.model import block_noise
from deepof_tpu.ops import ssm

ref = importlib.import_module("benchmark.reference.nemotron_twotower_30b_a3b_ep16")
faults = importlib.import_module("benchmark.harness.ssm_faults")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEMOTRON = os.path.join(ROOT, "benchmark", "configs",
                        "nemotron_twotower_30b_a3b_ep16.json")
TOY = dict(hybrid_override_pattern="MEM*E", num_hidden_layers=5,
           hidden_size=64, vocab_size=256, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, n_routed_experts=2,
           n_routed_experts_published=8, first_expert=2, n_shared_experts=1,
           moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
           mlp_hidden_act="relu2", num_experts_per_tok=2,
           routed_scaling_factor=2.5, mamba_num_heads=4, mamba_head_dim=8,
           ssm_state_size=16, n_groups=2, chunk_size=12, block_length=4,
           mask_token_id=255, rms_norm_eps=1e-5)
LM = lm_family_config("nemotron_h", attn_block_q=8, loss_block=16, seq_len=32,
                      **TOY)
TOL = {"float32": dict(loss=2e-5, grad=2e-4),
       "bfloat16": dict(loss=3e-4, grad=5e-2)}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
TOKENS = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (2, 33), 0, 255))


def as_dict(lm) -> dict:
    return dataclasses.asdict(lm)


def rel(a, b):
    return float(jnp.linalg.norm((a - b).astype(jnp.float32))
                 / (jnp.linalg.norm(b.astype(jnp.float32)) + 1e-30))


@pytest.fixture(scope="module")
def weights():
    vals = ref.make_params(as_dict(LM), jax.random.PRNGKey(3))
    return vals, unflatten_dict({tuple(k.split("/")): v for k, v in vals.items()})


# ------------------------------------------------------------------ the scan

#: a copy of 20 positions: chunks of 8 hold blocks of 4, 20 is not a whole
#: number of chunks; 4 heads in 2 groups, head 8 wide, a state of 5
H, P, G, N, COPY, CHUNK, BLOCK = 4, 8, 2, 5, 20, 8, 4


def scan_inputs(key=0):
    k = jax.random.split(jax.random.PRNGKey(key), 9)
    rows = lambda i, *s: jax.random.normal(k[i], (2, COPY, *s))  # noqa: E731
    halves = [(rows(i, H, P), jax.nn.softplus(rows(i + 1, H) - 1.0),
               rows(i + 2, G, N), rows(i + 3, G, N)) for i in (0, 4)]
    return halves[0], halves[1], -jnp.exp(jax.random.normal(k[8], (H,)))


def by_head(a):
    """[b, s, G, N] -> [b, s, H, N]: each head's group."""
    return a[:, :, jnp.arange(H) // (H // G)]


def reference_scan(rule, noised, clean, A):
    """The reference's per-position recurrence on each row."""
    if rule == "causal":
        x, dt, b, c = clean
        return jax.vmap(lambda *a: ref.recur(jnp.zeros((H, P, N)), *a, A)[1])(
            x, dt, by_head(b), by_head(c))
    both = [jnp.concatenate([n, c], axis=1) for n, c in zip(noised, clean)]
    both[2], both[3] = by_head(both[2]), by_head(both[3])
    return jax.vmap(lambda *a: ref.scan_doubled(*a, A, COPY, BLOCK))(*both)


def on_both_routes(cases):
    """`parametrize` arguments: each case on the scan's two routes, the
    chunked form under the case's own id, the chip's kernels
    (`ops/pallas/ssd.py`, interpret mode, steered in where
    `ssm.doubled_scan` stands) under `<id>-kernel`."""
    routes = ("chunked", "kernel")
    return {"argvalues": [(c, r) for r in routes for c in cases],
            "ids": [f"{c}{'' if r == 'chunked' else '-kernel'}"
                    for r in routes for c in cases],
            "indirect": ["route"]}


@pytest.fixture
def route(request, monkeypatch):
    if request.param == "kernel":
        from deepof_tpu.ops.pallas import ssd

        monkeypatch.setattr(ssm, "doubled_scan", functools.partial(
            ssd.doubled_scan, interpret=True))
    return request.param


def program_scan(rule, noised, clean, A):
    if rule == "causal":  # the clean half: its own recurrence from zero
        return ssm.doubled_scan(*noised, *clean, A, CHUNK, BLOCK)[1]
    return jnp.concatenate(
        ssm.doubled_scan(*noised, *clean, A, CHUNK, BLOCK), axis=1)


@pytest.mark.parametrize("rule,route", **on_both_routes(["causal", "doubled"]))
def test_chunked_scan_is_the_per_position_recurrence(rule, route):
    """Values and the gradients of every input: the chunked form (chunks
    of 8, rows not a whole number of them) and the kernels against the
    reference's recurrence, on a row and on the doubled row."""
    noised, clean, A = scan_inputs()
    probe = jax.random.normal(jax.random.PRNGKey(9),
                              (2, COPY * (1 if rule == "causal" else 2), H, P))
    with jax.default_matmul_precision("highest"):
        got = program_scan(rule, noised, clean, A)
        want = reference_scan(rule, noised, clean, A)
        assert rel(got, want) < 1e-5
        grads = [jax.grad(lambda *a: jnp.sum(probe * f(rule, a[:4], a[4:8], a[8])),
                          argnums=tuple(range(9)))(*noised, *clean, A)
                 for f in (program_scan, reference_scan)]
    for g, w in zip(*grads):
        assert rel(g, w) < 1e-4


@pytest.mark.parametrize("fault,route",
                         **on_both_routes([None, *sorted(faults.FAULTS)]))
def test_one_noised_block_starts_from_the_clean_state_at_its_start(fault, route):
    """Block 2 (positions 8..11) by hand: the recurrence from the clean
    copy's state at position 7 over the block's noised positions is the
    noised half's output there; under each planted fault (the scan's, or
    the convolution's that feeds it) the output there is another, and the
    clean half's is its own. On either route of the scan."""
    noised, clean, A = scan_inputs()
    first = 2 * BLOCK
    block = slice(first, first + BLOCK)
    x, dt, b, c = clean
    with jax.default_matmul_precision("highest"):
        if fault is None:
            h, _ = jax.vmap(lambda *a: ref.recur(jnp.zeros((H, P, N)), *a, A))(
                x[:, :first], dt[:, :first], by_head(b)[:, :first],
                by_head(c)[:, :first])
            xn, dtn, bn, cn = (a[:, block] for a in noised)
            _, by_hand = jax.vmap(lambda h0, *a: ref.recur(h0, *a, A))(
                h, xn, dtn, by_head(bn), by_head(cn))
            got = ssm.doubled_scan(*noised, *clean, A, CHUNK, BLOCK)[0]
            assert rel(got[:, block], by_hand) < 1e-5
            return
        broken_fns = getattr(faults, fault)(ssm)
        if "doubled_scan" in broken_fns:
            sound = ssm.doubled_scan(*noised, *clean, A, CHUNK, BLOCK)
            broken = broken_fns["doubled_scan"](*noised, *clean, A, CHUNK,
                                                BLOCK, jnp.float32)
        else:
            w, bias = jax.random.normal(jax.random.PRNGKey(4), (2, 4, P))
            sound = (ssm.doubled_conv(noised[0][..., 0, :], x[..., 0, :], w,
                                      bias[0], BLOCK),)
            broken = (broken_fns["doubled_conv"](
                noised[0][..., 0, :], x[..., 0, :], w, bias[0], BLOCK),)
    assert rel(broken[0][:, block], sound[0][:, block]) > 1e-2
    if len(sound) == 2:
        assert rel(broken[1], sound[1]) == 0.0  # the clean half's own


# ------------------------------------------------------ the layers and model


def test_relu2_expert_shares_add_up_to_the_uncut_layer(weights):
    """Two chips' shares of the layer (experts 0..3 and 4..7 of 8), the
    shared expert counted once, are the reference's layer with all 8."""
    vals, _ = weights
    c = {**as_dict(LM), "n_routed_experts": 8}
    uncut = ref.make_params(c, jax.random.PRNGKey(11))
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 64, 64))
    h = h / jnp.sqrt(jnp.mean(h * h, -1, keepdims=True))
    p = {k.split("/", 2)[2]: v for k, v in uncut.items()
         if k.startswith("layer_1/moe/")}
    parts = []
    for first in (0, 4):
        lm = dataclasses.replace(LM, n_routed_experts=4, first_expert=first)
        share = {**p, **{k: v[first:first + 4] for k, v in p.items()
                         if k.startswith("experts_")}}
        parts.append(L.MoE(lm).apply(
            {"params": unflatten_dict({tuple(k.split("/")): v
                                       for k, v in share.items()})}, h)[0])
    shared = jnp.stack([ref.relu2(h[i], p["shared/w_up"], p["shared/w_down"])
                        for i in range(2)])
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.moe(uncut, "layer_1", h[i], c, first=0, held=8)
                          for i in range(2)])
    assert rel(parts[0] + parts[1] - shared, want) < 2e-5


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_loss_and_every_gradient_match_reference_under_the_same_masks(
        weights, dtype):
    from deepof_tpu.models.lm.hybrid import HybridBlockDiffusionLM

    vals, params = weights
    c = as_dict(LM)
    model = HybridBlockDiffusionLM(LM, dtype=DTYPES[dtype],
                                   remat=dtype == "float32")
    m, t = block_noise(jax.random.PRNGKey(7), 2, 32, 4, 0.45, 0.95)

    def loss(p):
        return model.apply({"params": p}, jnp.asarray(TOKENS), None, (m, t),
                           method="loss")

    out = loss(params)
    assert out["ssm_decay_mean"].shape == (2,)  # two state-space layers
    assert out["moe_slots_held_share"].shape == (2,)  # two expert layers
    with jax.default_matmul_precision("highest"):
        rows = jnp.stack([ref.row_loss(vals, jnp.asarray(TOKENS[i]), c,
                                       (m[i], t[i])) for i in range(2)])
        want = jax.grad(lambda v: sum(
            ref.row_loss(v, jnp.asarray(TOKENS[i]), c, (m[i], t[i]))
            for i in range(2)) / 2)(vals)
    got_rows = out["loss_rows"]
    assert float(jnp.max(jnp.abs(got_rows - rows) / rows)) < TOL[dtype]["loss"]
    got = {"/".join(k): v for k, v in flatten_dict(
        jax.grad(lambda p: loss(p)["loss_rows"].mean())(params)).items()}
    assert set(got) == set(want)
    worst = max((rel(got[k], want[k]), k) for k in want)
    assert worst[0] < TOL[dtype]["grad"], worst


def test_references_layer_by_layer_gradient_is_its_whole_row(weights):
    vals, _ = weights
    c, row = as_dict(LM), jnp.asarray(TOKENS[0])
    m, t = block_noise(jax.random.PRNGKey(7), 2, 32, 4, 0.45, 0.95)
    noise = (m[0], t[0])
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(
            lambda v: ref.row_loss(v, row, c, noise))(vals)
        loss, got = ref.make_row_grad(c)(
            vals, row, noise, {k: jnp.zeros_like(v) for k, v in vals.items()}, 0.5)
    assert abs(float(loss) - float(want_loss)) < 1e-6 * float(want_loss)
    worst = max((rel(got[k], 0.5 * want[k]), k) for k in want)
    assert worst[0] < 1e-5, worst


def test_the_steps_scan_is_the_chunked_form_with_no_scan_over_positions(weights):
    from deepof_tpu.models.lm.hybrid import HybridBlockDiffusionLM

    _, params = weights
    model = HybridBlockDiffusionLM(LM, remat=True)
    m, t = block_noise(jax.random.PRNGKey(7), 2, 32, 4, 0.45, 0.95)
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda p: model.apply(
        {"params": p}, jnp.asarray(TOKENS), None, (m, t),
        method="loss")["loss_rows"].mean()))(params))
    assert " scan[" not in jaxpr and "while[" not in jaxpr
    assert model.routes()["ssm"] == {"path": "chunked", "chunk": 12,
                                     "rule": "block_diffusion", "chunks": 6}


#: (doubled row, chunk, block, state, a B/C group's heads x head size)
ROUTE_SHAPES = {"cell": (8192, 128, 4, 128, 512), "toy": (64, 12, 4, 16, 16),
                "blocks_of_3": (8192, 384, 3, 128, 512),
                "state_of_64": (8192, 128, 4, 64, 512),
                "group_of_192": (8192, 128, 4, 128, 192),
                "chunk_of_64": (8192, 64, 4, 128, 512)}


@pytest.mark.parametrize("backend,shape,path", [
    ("tpu", "cell", "kernel"), ("cpu", "cell", "chunked"),
    ("tpu", "toy", "chunked"), ("tpu", "blocks_of_3", "chunked"),
    ("tpu", "state_of_64", "chunked"), ("tpu", "group_of_192", "chunked"),
    ("tpu", "chunk_of_64", "chunked")])
def test_route_takes_the_kernels_on_a_tpu_at_whole_tiles(monkeypatch, backend,
                                                         shape, path):
    """The kernels where the backend is a TPU, the chunk holds whole
    blocks of a power of two, and the chunk, the state and a group's heads
    are whole 128-lane tiles; the chunked form anywhere else. The backend
    is steered, never read from the host."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    got = ssm.route(*ROUTE_SHAPES[shape])
    assert got["path"] == path
    assert got["rule"] == "block_diffusion"


@pytest.mark.parametrize("backend,path", [("tpu", "kernel"), ("cpu", "chunked")])
def test_the_cells_routes_record_names_the_kernels_on_a_tpu(monkeypatch,
                                                            backend, path):
    """The step-0 `routes` record of the benchmark cell's configuration
    (rows of 4096 doubled, `lm.attn_block_q` 512 as the cell sets it)."""
    from deepof_tpu.core.config import LMConfig, fill_lm_from_file
    from deepof_tpu.models.lm.hybrid import HybridBlockDiffusionLM

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    lm = dataclasses.replace(fill_lm_from_file(LMConfig(), NEMOTRON),
                             seq_len=4096, attn_block_q=512)
    assert HybridBlockDiffusionLM(lm).routes()["ssm"] == {
        "path": path, "chunk": 128, "rule": "block_diffusion", "chunks": 64}


# ------------------------------------------------------ the normal path


def test_family_trains_through_trainer_fit_from_its_config_file(tmp_path):
    """`train --preset lm` with a config.json of `model_type: nemotron_h`
    (the cell's own keys, the sizes cut): the registry finds the family by
    its model_type alone and the same Trainer fits it."""
    from deepof_tpu import cli
    from deepof_tpu.models.lm.hybrid import HybridBlockDiffusionLM
    from deepof_tpu.parallel.mesh import build_mesh
    from deepof_tpu.train.loop import Trainer

    with open(NEMOTRON) as f:
        c = json.load(f)
    c.update({k: v for k, v in TOY.items() if k != "rms_norm_eps"})
    toy = tmp_path / "toy_nemotron.json"
    toy.write_text(json.dumps(c))
    argv = ["train", "--preset", "lm", "--log-dir", str(tmp_path / "run"),
            "--set", f"lm.config_file={toy}", "--set", "lm.seq_len=32",
            "--set", "lm.attn_block_q=16", "--set", "lm.loss_block=16",
            "--set", "train.log_every=1", "--set", "train.nan_guard=false"]
    cfg = cli.config_for(argv)
    assert cfg.model == "latent_moe_lm"  # the preset's; the file's model_type wins
    assert (cfg.lm.scoring_func, cfg.lm.n_group, cfg.lm.n_groups) == ("sigmoid", 1, 2)
    trainer = Trainer(cfg, mesh=build_mesh(devices=jax.devices()[:1]))
    assert isinstance(trainer.model, HybridBlockDiffusionLM)
    trainer.fit(max_steps=2)
    with open(tmp_path / "run" / "metrics.jsonl") as f:
        records = [json.loads(line) for line in f]
    train = [r for r in records if r["kind"] == "train"]
    assert [r["step"] for r in train] == [1, 2]
    for r in train:
        assert np.isfinite(r["loss"])
        assert len(r["ssm_decay_mean"]) == 2
        assert all(0.0 < d < 1.0 for d in r["ssm_decay_mean"])
        assert len(r["moe_slots_held_share"]) == 2
    routes, = [r for r in records if r.get("message") == "routes"]
    assert routes["ssm"] == {"path": "chunked", "chunk": 12,
                             "rule": "block_diffusion", "chunks": 6}
    assert routes["attention_route"]["prep"] == {"path": "cast"}


def test_the_family_is_imported_only_where_a_configuration_names_it():
    """What the accepted cells import does not grow: the trainer's modules
    and the other families' models import neither the family, nor its
    scan, nor the scan's kernels."""
    code = ("import sys, deepof_tpu.train.loop, deepof_tpu.models.registry, "
            "deepof_tpu.models.lm.model; "
            "print([m for m in ('deepof_tpu.models.lm.hybrid', "
            "'deepof_tpu.ops.ssm', 'deepof_tpu.ops.pallas.ssd') "
            "if m in sys.modules])")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=ROOT, env={**os.environ,
                                                   "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"


def test_configuration_file_keeps_every_published_width():
    """Every number of the catalog row's `config` under its own key but the
    three `reduced` (within the floors: a whole period of the pattern, 8
    experts, an eighth of the vocabulary); the parameter table sums to
    what the program holds."""
    from deepof_tpu.core.config import LMConfig, fill_lm_from_file

    with open(NEMOTRON) as f:
        c = json.load(f)
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 52, "n_routed_experts": 128,
                              "vocab_size": 131072}
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"]) == \
        (9, 8, 16384)
    assert c["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert (c["hidden_size"], c["mamba_num_heads"], c["mamba_head_dim"],
            c["ssm_state_size"], c["n_groups"], c["moe_intermediate_size"],
            c["moe_shared_expert_intermediate_size"]) == \
        (2688, 64, 64, 128, 8, 1856, 3712)
    total = sum(int(np.prod(s)) for _, s, _ in ref.param_spec(c))
    assert total == c["parameter_table"]["all"] == 666963456
    lm = fill_lm_from_file(LMConfig(), NEMOTRON)
    assert (lm.model_type, lm.rms_norm_eps, lm.mlp_hidden_act) == \
        ("nemotron_h", 1e-5, "relu2")


# ------------------------------------------- the other families' steps

TOY_SDAR = dict(
    model_type="sdar_moe", vocab_size=256, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_experts=2, num_experts_per_tok=2,
    n_routed_experts_published=8, first_expert=2, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[], rope_theta=10000,
    use_sliding_window=False, block_length=4, mask_token_id=255,
    noise_t_lo=0.45, noise_t_hi=0.95)


@pytest.mark.parametrize("family", ["deepseek_v3", "sdar_moe"])
def test_the_other_families_toy_steps_lower_as_before(family, tmp_path):
    """The SwiGLU families' toy train steps lower to the same text with
    `lm.moe_shared_expert_intermediate_size` left at 0 and set to what
    they had before the option existed (`n_shared_experts` times the
    expert width; a family with no shared expert has no use for it)."""
    from deepof_tpu import cli
    from deepof_tpu.obs.ledger import fingerprint_text
    from deepof_tpu.parallel.mesh import build_mesh
    from deepof_tpu.train.warmup import lower_train_step

    argv = ["train", "--preset", "lm", "--log-dir", str(tmp_path),
            "--set", "lm.seq_len=32", "--set", "lm.attn_block_q=16",
            "--set", "lm.loss_block=16", "--set", "lm.n_routed_experts=2",
            "--set", "lm.n_routed_experts_published=8"]
    if family == "sdar_moe":
        toy = tmp_path / "toy_sdar.json"
        toy.write_text(json.dumps(TOY_SDAR))
        argv += ["--set", f"lm.config_file={toy}"]
    cfg = cli.config_for(argv)
    assert cfg.lm.model_type == family
    assert cfg.lm.moe_shared_expert_intermediate_size == 0
    width = cfg.lm.n_shared_experts * cfg.lm.moe_intermediate_size or 64
    named = cli.config_for(
        argv + ["--set", f"lm.moe_shared_expert_intermediate_size={width}"])
    mesh = build_mesh(devices=jax.devices()[:1])
    prints = [fingerprint_text(lower_train_step(c, mesh).as_text())
              for c in (cfg, named)]
    assert prints[0] == prints[1]
