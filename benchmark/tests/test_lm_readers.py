"""The language-model cell's per-layer readers, each on an `observed` made
by hand and on the small trace recorded on the chip; and the join of a
profile's events with the program's scopes (`harness/scope_share.py`)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402

from benchmark.harness import scope_share, trace_reduce as tr  # noqa: E402

M, P = "MainThread", "prefetch"

HLO = """
HloModule jit_step
%fused_computation.1 (p: f32[8]) -> f32[8] {
  %inner.9 = f32[8]{0} add(%p, %p), metadata={op_name="jit(step)/jvp(LatentMoELM)/layer_1/moe/moe_route/add"}
}
ENTRY %main {
  %fusion.1 = bf16[8,64]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(LatentMoELM)/layer_1/moe/moe_experts/mul" source_file="x.py"}
  %ragged-dot-none.3 = f32[8,64]{1,0} custom-call(%b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(LatentMoELM))/layer_1/jvp(LatentMoELM)/layer_1/checkpoint/layer_1/moe/moe_experts/ragged_dot"}
  %fusion.2 = f32[8]{0} fusion(%c), kind=kInput, calls=%f2, metadata={op_name="jit(step)/jvp(LatentMoELM)/layer_1/mla/mla_scores/checkpoint/exp"}
  %copy.4 = f32[8]{0} copy(%d)
  ROOT %fusion.5 = f32[8]{0} fusion(%e), kind=kLoop, calls=%f5, metadata={op_name="jit(step)/optimizer/add"}
  %fusion.6 = f32[8]{0} fusion(%e), kind=kLoop, calls=%f6, metadata={op_name="jit(step)/jvp(LatentMoELM)/layer_1/moe_like/add"}
}
"""


def read(metric, **obs):
    return bench_run.load_reader(metric).read(obs)


def observed(ops, scopes=True):
    return {"device": {"ops": ops},
            **({"op_scopes": scope_share.op_scopes(HLO)} if scopes else {})}


def test_op_scopes_reads_every_instruction_that_has_an_op_name():
    table = scope_share.op_scopes(HLO)
    assert set(table) == {"inner.9", "fusion.1", "ragged-dot-none.3", "fusion.2",
                          "fusion.5", "fusion.6"}
    assert table["fusion.5"] == "jit(step)/optimizer/add"
    assert scope_share.under(table["ragged-dot-none.3"], "moe")
    assert scope_share.under(table["ragged-dot-none.3"], "moe_experts")
    assert not scope_share.under(table["fusion.6"], "moe")  # moe_like is another
    assert not scope_share.under(table["fusion.2"], "moe")


OPS = {
    "%fusion.1 = bf16[8,64]{1,0:T(8,128)(2,1)} fusion(": (1.0, 3),
    "%ragged-dot-none.3 = f32[8,64] custom-call(": (2.0, 3),
    "%fusion.2 = f32[8] fusion(": (4.0, 3),
    "%copy.4 = f32[8] copy(": (1.0, 3),       # no op_name: in the total only
    "%fusion.5 = f32[8] fusion(": (1.5, 3),
    "%fusion.6 = f32[8] fusion(": (0.5, 3),
    "%while.7 = (f32[8]) while(": (100.0, 1),  # a container: left out
}


def test_scope_shares_of_device_time():
    obs = observed(OPS)
    assert read("moe_device_pct.lm_train", **obs) == pytest.approx(30.0)
    assert read("mla_device_pct.lm_train", **obs) == pytest.approx(40.0)
    by, total = scope_share.seconds_by_scope(obs, ("moe_experts", "optimizer"))
    assert total == pytest.approx(10.0)
    assert by == {"moe_experts": pytest.approx(3.0), "optimizer": pytest.approx(1.5)}


@pytest.mark.parametrize("metric", ["moe_device_pct.lm_train",
                                    "mla_device_pct.lm_train"])
def test_scope_readers_read_nothing_without_the_map_or_a_match(metric):
    assert read(metric, **observed(OPS, scopes=False)) is None
    assert read(metric, **observed({"%other.1 = f32[8] add(": (1.0, 1)})) is None
    assert read(metric, **observed({})) is None


def test_moe_load_counter_is_the_mean_over_records_and_layers():
    records = [{"moe_load_max_over_mean": [1.0, 2.0]},
               {"moe_load_max_over_mean": [3.0, 6.0]}, {}]
    assert read("moe_load_max_over_mean.lm_train", records=records) \
        == pytest.approx(3.0)
    assert read("moe_load_max_over_mean.lm_train", records=[{}]) is None
    assert read("moe_load_max_over_mean.lm_train") is None  # the parent's line


ROUTING = [{"moe_full_width": [0.0, 0.0, 1.0], "moe_slots_held_share": [0.10, 0.30, 0.20]},
           {"moe_full_width": [0.0, 1.0, 1.0], "moe_slots_held_share": [0.12, 0.10, 0.20]}]


@pytest.mark.parametrize("metric, want", [
    ("moe_full_width_share.lm_train", 100.0),   # the third layer, both records
    ("moe_slots_held_pct.lm_train", 20.0)])     # a layer's MEAN: not the 0.30
def test_routing_readers_take_the_largest_window_mean_over_the_layers(metric, want):
    assert read(metric, records=ROUTING) == pytest.approx(want)
    assert read(metric, records=[{}]) is None   # a program without the counter
    assert read(metric, records=[]) is None
    assert read(metric) is None                 # a run that kept no records


def test_full_width_share_reads_nought_where_no_layer_left_its_cap():
    records = [{"moe_full_width": [0.0, 0.0]}, {"moe_full_width": [0.0, 0.0]}]
    assert read("moe_full_width_share.lm_train", records=records) == 0.0


def test_input_wait_first_step_and_mfu_readers():
    spans = [("first_step", M, 1.0, 8.5), ("input_wait", M, 9.0, 12.0),
             ("input_wait", P, 0.0, 100.0), ("dispatch", M, 12.0, 20.0)]
    assert read("first_step_s.lm_train", spans=spans) == pytest.approx(7.5)
    assert read("first_step_s.lm_train", spans=[]) is None
    assert read("input_wait_pct.lm_train", spans=spans, window=(10.0, 20.0)) \
        == pytest.approx(20.0)
    assert read("input_wait_pct.lm_train", spans=spans, window=(None, None)) is None
    obs = dict(window=(10.0, 20.0), pairs=50, chips=1,
               config={"train_flops_per_pair": 1e13},
               peaks={"flops_per_s": 2e14})
    assert read("step_mfu_pct.lm_train", **obs) == pytest.approx(25.0)
    assert read("step_mfu_pct.lm_train", **{**obs, "config": {}}) is None


def test_set_up_and_loop_readers_of_the_spans():
    spans = [("trainer_init", M, 0.0, 22.5), ("trainer_init", P, 0.0, 99.0),
             ("first_step", M, 30.0, 40.0), ("xla_compile", M, 31.0, 36.0),
             ("xla_compile", "bench-trace", 50.0, 52.5),
             ("xla_cache_load", M, 37.0, 38.0),
             ("input_wait", M, 100.0, 101.0), ("dispatch", M, 101.0, 109.5)]
    assert read("trainer_init_s.lm_train", spans=spans) == pytest.approx(22.5)
    assert read("trainer_init_s.lm_train", spans=[]) is None
    assert read("compile_miss_s.lm_train", spans=spans) == pytest.approx(7.5)
    loads = [s for s in spans if s[0] != "xla_compile"]
    assert read("compile_miss_s.lm_train", spans=loads) == 0.0
    assert read("compile_miss_s.lm_train", spans=spans[:3]) is None
    assert read("loop_self_pct.lm_train", spans=spans, window=(100.0, 110.0)) \
        == pytest.approx(5.0)
    # a program from before its waits were spans: nothing is read
    old = [s for s in spans if s[0] != "first_step"]
    assert read("loop_self_pct.lm_train", spans=old, window=(100.0, 110.0)) is None


RECORDED = os.path.join(HERE, "data", "trace_small.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_readers_on_the_recorded_trace():
    """The small trace recorded on the chip (another program's step): the
    step's device time reads as the accepted reader reads it, and every
    event's name yields an instruction name for the join."""
    with open(RECORDED) as f:
        planes = {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
                  for p, lines in json.load(f).items()}
    dev = tr.reduce_device(planes)
    obs = {"device": dev, "traffic": {}}
    assert read("step_device_ms.lm_train", **obs) == pytest.approx(
        read("step_device_ms.train", **obs))
    names = [scope_share._EVENT.match(n).group(1) for n in dev["ops"]]
    assert all(names) and any(n.startswith("fusion") for n in names)
    # mapped by hand: every fusion under one scope, the rest under none
    obs["op_scopes"] = {n: "jit(step)/jvp(M)/layer_0/mla/mla_proj/dot"
                        for n in names if n.startswith("fusion")}
    share = read("mla_device_pct.lm_train", **obs)
    assert 0.0 < share <= 100.0
    assert read("moe_device_pct.lm_train", **obs) == 0.0
