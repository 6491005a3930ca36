"""What PR 39 added, on the CPU: every file `BENCHMARK.json` names exists;
the new counts against numbers worked by hand and the old attention counts
unchanged; each new reader on the trace recorded on the chip, with its
events and without them."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402

from benchmark.harness import scope_share, trace_reduce as tr  # noqa: E402
from benchmark.kernels import attention, corr, qk_prep, roofline  # noqa: E402

PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def read(metric, **obs):
    return bench_run.load_reader(metric).read(obs)


def test_every_file_and_name_that_the_benchmark_names_exists():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in configs.values():
        assert os.path.isfile(os.path.join(bench_run.ROOT, c["file"])), c["file"]
        ref = bench_run.load_json("configs", c["name"] + ".json")["reference"]
        assert os.path.isfile(os.path.join(bench_run.HERE, "reference", ref + ".py"))
    for w in BENCH["workloads"]:
        assert w["config"] in configs
        assert "limits" in bench_run.load_json("workloads", w["name"] + ".json")
        runner = bench_run.load_json("traffic", w["traffic"] + ".json")["runner"]
        assert os.path.isfile(os.path.join(bench_run.HERE, "runners", runner + ".py"))
    end_to_end = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert callable(bench_run.load_reader(m["name"]).read), m["name"]
        assert m["moves"] in end_to_end
        assert set(m.get("workloads", CELLS)) <= set(CELLS), m["name"]
    for cell in CELLS:  # every cell reports a per-layer metric
        assert any(bench_run.applies(m, cell) for m in BENCH["per_layer"])


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_every_listed_kernel_call_has_its_count(config):
    """`step_kernels` names count functions with the arguments they take."""
    import importlib

    for k in bench_run.load_json("configs", config + ".json").get("step_kernels", []):
        mod, fn = k["kernel"].split(".")
        counts = getattr(importlib.import_module("benchmark.kernels." + mod),
                         fn)(b=2, **k["args"])
        assert counts["ops"] > 0 and counts["bytes"] > 0


def test_corr_forward_at_the_cells_shapes():
    # conv3's features at 1/8 of 384x512, batch 64: 64*48*64 = 196,608
    # pixels, 441 maps, 256 channels
    c = corr.forward(64, 48, 64, 256, max_disp=20, stride=2)
    assert c["ops"] == 196608 * 441 * 2 * 256 == 44392513536        # 4.44e10
    assert c["bytes"] == 2 * 196608 * 256 * 2 + 196608 * 441 * 2 == 374734848
    least, bound = roofline.least_seconds(c, PEAKS)
    assert bound == "memory" and least == pytest.approx(0.4575e-3, rel=1e-3)


def test_attention_counts_at_the_latent_widths():
    # 2 rows, 32 heads, causal over 4096: 4096 * 4097 / 2 = 8,390,656 pairs
    # a head; queries and keys 192 wide, values 128
    pairs = 2 * 32 * 8390656
    f = attention.forward(2, 32, 32, 4096, 192, "causal", d_v=128)
    b = attention.backward(2, 32, 32, 4096, 192, "causal", d_v=128)
    assert f["ops"] == pairs * (2 * 192 + 2 * 128) == 343681269760
    assert b["ops"] == pairs * (4 * 192 + 4 * 128) == 2 * f["ops"]
    per = 2 * 32 * 4096 * 2                      # one channel of all heads, bytes
    lse = 2 * 32 * 4096 * 4
    assert f["bytes"] == per * (192 + 192 + 128 + 128) + lse
    assert b["bytes"] == 2 * per * (192 + 192 + 128 + 128) + lse
    assert roofline.least_seconds(f, PEAKS)[1] == "compute"
    assert roofline.least_seconds(f, PEAKS)[0] == pytest.approx(1.7446e-3, rel=1e-4)


def test_attention_counts_with_one_width_are_what_they_were():
    """The block-diffusion cell's calls and a small causal one: the numbers
    the count gave before it took a value width (PR 37's, to the digit)."""
    assert attention.forward(1, 32, 4, 8192, 128, "block_diffusion", 4) == {
        "ops": 275146342400, "bytes": 152043520}
    assert attention.backward(1, 32, 4, 8192, 128, "block_diffusion", 4) == {
        "ops": 550292684800, "bytes": 303038464}
    assert attention.forward(2, 8, 2, 64, 16, "causal") == {
        "ops": 2129920, "bytes": 86016}
    assert attention.backward(2, 8, 2, 64, 16, "causal") == {
        "ops": 4259840, "bytes": 167936}
    assert attention.forward(2, 8, 2, 64, 16, "causal", d_v=16) == \
        attention.forward(2, 8, 2, 64, 16, "causal")


def test_qk_prep_counts():
    # the block-diffusion cell's query: 8192 positions x 32 heads x 128
    n = 8192 * 32 * 128
    f = qk_prep.forward(1, 8192, 32, 128, norm=True)
    b = qk_prep.backward(1, 8192, 32, 128, norm=True)
    assert f == {"ops": n * 7, "bytes": n * 6} and n * 6 == 201326592
    assert b == {"ops": n * 14, "bytes": n * 8} and n * 8 == 268435456
    assert roofline.least_seconds(f, PEAKS) == (pytest.approx(0.2458e-3, rel=1e-3), "memory")
    # the latent family's rotary part has no norm: nothing is read again
    m = 2 * 4096 * 32 * 64
    assert qk_prep.forward(2, 4096, 32, 64) == {"ops": m * 3, "bytes": m * 6}
    assert qk_prep.backward(2, 4096, 32, 64) == {"ops": m * 3, "bytes": m * 4}


# ---- the new readers on events made by hand -------------------------------

HLO = """
ENTRY %main {
  %corr_fwd.1 = f32[64,441,48,64]{3,2,1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(forward)/FlowNetC/corr_fwd/pallas_call"}
  %fusion.7 = f32[8]{0} fusion(%c), kind=kLoop, calls=%f7, metadata={op_name="jit(step)/transpose(jvp(forward))/FlowNetC/while/body/closed_call/add"}
  %dynamic-update-slice_fusion.2 = f32[8]{0} fusion(%c), kind=kLoop, calls=%f8, metadata={op_name="jit(step)/transpose(jvp(forward))/FlowNetC/while/body/closed_call/dynamic_update_slice"}
  %while.3 = (f32[8]) while(%t), metadata={op_name="jit(step)/transpose(jvp(forward))/FlowNetC/while"}
  %fusion.9 = f32[8]{0} fusion(%c), kind=kLoop, calls=%f9, metadata={op_name="jit(step)/transpose(jvp(forward))/FlowNetC/conv3/conv_general_dilated"}
  %fusion.10 = f32[8]{0} fusion(%c), kind=kLoop, calls=%f10, metadata={op_name="jit(step)/transpose(jvp(forward))/FlowNetC/corr/mul"}
  %corr_bwd.4 = f32[8]{0} custom-call(%c), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(forward))/FlowNetC/corr_bwd/pallas_call"}
  %fusion.11 = f32[8]{0} fusion(%c), kind=kLoop, calls=%f11, metadata={op_name="jit(step)/jvp(forward)/FlowNetC/corr/mul"}
  %fusion.12 = f32[8]{0} fusion(%c), kind=kLoop, calls=%f12, metadata={op_name="jit(step)/transpose(jvp(forward))/FlowNetC/correlate_like/mul"}
  %fusion.13 = f32[8]{0} fusion(%c), kind=kLoop, calls=%f13, metadata={op_name="jit(step)/transpose(jvp(forward))/FlowNetC/decoder/while/body/add"}
  %fusion.14 = f32[8]{0} fusion(%c), kind=kLoop, calls=%f14, metadata={op_name="jit(step)/transpose(jvp(loss))/while/body/mul"}
  %fusion.15 = f32[8]{0} fusion(%c), kind=kLoop, calls=%f15, metadata={op_name="jit(step)/jvp(forward)/FlowNetC/while/body/mul"}
}
"""


def scoped(ops):
    return {"device": {"ops": ops}, "op_scopes": scope_share.op_scopes(HLO)}


def test_corr_backward_share_goes_by_scope_not_by_operation_kind():
    today = {"%fusion.7 = f32[8] fusion(": (6.0, 441),
             "%dynamic-update-slice_fusion.2 = f32[8] fusion(": (2.0, 441),
             "%while.3 = (f32[8]) while(": (8.5, 1),        # container: left out
             "%fusion.9 = f32[8] fusion(": (1.5, 1),
             "%corr_fwd.1 = f32[64,441,48,64] custom-call(": (0.5, 1)}
    assert read("corr_bwd_device_pct.train", **scoped(today)) == pytest.approx(80.0)
    # what may implement it later: a named scope, a kernel of its own; the
    # forward under the same scope and a scope that only begins alike do not count
    later = {"%fusion.10 = f32[8] fusion(": (1.0, 1),
             "%corr_bwd.4 = f32[8] custom-call(": (2.0, 1),
             "%fusion.11 = f32[8] fusion(": (3.0, 1),
             "%fusion.12 = f32[8] fusion(": (1.5, 1),
             "%fusion.9 = f32[8] fusion(": (2.5, 1)}
    assert read("corr_bwd_device_pct.train", **scoped(later)) == pytest.approx(30.0)


def test_corr_backward_share_counts_no_other_loop():
    """Today's mark is the one `while` directly under the transposed
    forward's `FlowNetC`: a loop in the decoder's backward, one in the
    loss's and one in the forward pass are not the correlation's."""
    ops = {"%fusion.7 = f32[8] fusion(": (5.0, 441),
           "%fusion.13 = f32[8] fusion(": (2.0, 6),
           "%fusion.14 = f32[8] fusion(": (2.0, 6),
           "%fusion.15 = f32[8] fusion(": (1.0, 6)}
    assert read("corr_bwd_device_pct.train", **scoped(ops)) == pytest.approx(50.0)
    del ops["%fusion.7 = f32[8] fusion("]
    assert read("corr_bwd_device_pct.train", **scoped(ops)) is None


def test_corr_backward_share_reads_nothing_without_the_map_or_a_match():
    ops = {"%fusion.9 = f32[8] fusion(": (1.0, 1)}
    assert read("corr_bwd_device_pct.train", **scoped(ops)) is None
    assert read("corr_bwd_device_pct.train", device={"ops": ops}) is None
    assert read("corr_bwd_device_pct.train", **scoped({})) is None


def kernel_obs(ops, step_kernels, batch=2):
    return {"device": {"ops": ops, "chips": 1, "modules": {"jit_step(1)": 2.0}},
            "config": {"step_kernels": step_kernels}, "traffic": {},
            "batch": batch, "chips": 1, "peaks": PEAKS}


LATENT = {"h": 32, "g": 32, "s": 4096, "d": 192, "d_v": 128, "rule": "causal"}
PREP = {"s": 8192, "heads": 32, "d": 128, "norm": True}
CORR = {"h": 48, "w": 64, "c": 256, "max_disp": 20, "stride": 2}


@pytest.mark.parametrize("metric, kernels, events, want", [
    ("corr_roofline_pct.train", [("corr.forward", CORR)],
     {"%corr_fwd.1 = f32[64,441,48,64] custom-call(..tpu_custom_call": 0.0366},
     # 64 rows: 374,734,848 bytes a call, two steps in the window
     100 * 2 * (374734848 / 819e9) / 0.0366),
    ("mla_attn_fwd_roofline_pct.lm_train", [("attention.forward", LATENT)] * 5,
     {f"%mla_attn_fwd.{i} = bf16[2,32,4096,128] custom-call(..tpu_custom_call": 0.0074
      for i in range(5)}, 100 * 2 * 1.74457e-3 / 0.0074),
    ("mla_attn_bwd_roofline_pct.lm_train", [("attention.backward", LATENT)] * 5,
     {f"%mla_attn_bwd.{i} = bf16[2,32,4096,192] custom-call(..tpu_custom_call": 0.0150
      for i in range(5)}, 100 * 2 * 3.48915e-3 / 0.0150),
    ("qk_prep_roofline_pct.lm_train",
     [("qk_prep.forward", PREP)] * 2 + [("qk_prep.backward", PREP)],
     # one row a step: forward 0.24582 ms twice, backward 0.32776 once
     {"%qk_prep_fwd.1 = bf16[1,32,8192,128] custom-call(..tpu_custom_call": 0.0006,
      "%qk_prep_fwd.2 = bf16[1,32,8192,128] custom-call(..tpu_custom_call": 0.0006,
      "%qk_prep_bwd.1 = bf16[1,8192,4096] custom-call(..tpu_custom_call": 0.0008},
     100 * 2 * (2 * 0.24582e-3 + 0.32776e-3) / 0.0020),
])
def test_new_roofline_readers(metric, kernels, events, want):
    batch = 64 if metric.endswith(".train") else (
        1 if metric.startswith("qk_prep") else 2)
    ops = {n: (s, 2) for n, s in events.items()}
    ops["%fusion.1 = f32[8] fusion("] = (1.0, 2)         # no kernel: not counted
    step_kernels = [{"kernel": k, "args": a} for k, a in kernels]
    got = read(metric, **kernel_obs(ops, step_kernels, batch))
    assert got == pytest.approx(want, rel=1e-4) and 0 < got <= 100
    # the step holds no such kernel, or the configuration lists none
    other = {"%fusion.1 = f32[8] fusion(": (1.0, 2),
             "%warp_fwd.3 = f32[8] custom-call(..tpu_custom_call": (1.0, 2)}
    assert read(metric, **kernel_obs(other, step_kernels, batch)) is None
    assert read(metric, **kernel_obs(ops, [], batch)) is None


# ---- and on the trace recorded on the chip (PR 39, the new cell) ----------

RECORDED = os.path.join(HERE, "data", "trace_corr_small.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_new_flow_readers_on_the_recorded_trace():
    """One step of `flownet_c_chairs.train` as the chip's profile named it
    (my chip run, PR 39): the Mosaic events, the scan's `while` and the
    first and last of its bodies' events, the step's longest other events,
    with the instructions' own `op_name`s beside them (`op_scopes`)."""
    with open(RECORDED) as f:
        rec = json.load(f)
    planes = {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
              for p, lines in rec["planes"].items()}
    dev = tr.reduce_device(planes)
    obs = {"device": dev, "op_scopes": rec["op_scopes"], "traffic": {},
           "config": bench_run.load_json("configs", "flownet_c_chairs.json"),
           "batch": 64, "chips": 1, "peaks": PEAKS}
    for metric in ("corr_roofline_pct.train", "warp_fwd_roofline_pct.train",
                   "warp_flow_grad_roofline_pct.train", "pallas_roofline_pct.train"):
        assert 0 < read(metric, **obs) <= 100, metric
    assert 0 < read("corr_bwd_device_pct.train", **obs) < 100
    by, total = scope_share.seconds_by_scope(obs, ("forward", "loss_level_0"))
    assert 0 < by["loss_level_0"] < by["forward"] < total
    # the same trace with the correlation's events taken out
    instr = lambda n: scope_share._EVENT.match(n).group(1)  # noqa: E731
    bare = dict(obs, device=dict(dev, ops={
        n: v for n, v in dev["ops"].items()
        if not n.startswith("%corr_fwd")
        and "/while" not in rec["op_scopes"].get(instr(n), "")}))
    assert read("corr_roofline_pct.train", **bare) is None
    assert read("corr_bwd_device_pct.train", **bare) is None
    assert read("warp_fwd_roofline_pct.train", **bare) == pytest.approx(
        read("warp_fwd_roofline_pct.train", **obs))
    # the language-model readers find nothing of theirs in a flow step
    for metric in ("mla_attn_fwd_roofline_pct.lm_train",
                   "mla_attn_bwd_roofline_pct.lm_train",
                   "qk_prep_roofline_pct.lm_train"):
        assert read(metric, **obs) is None
