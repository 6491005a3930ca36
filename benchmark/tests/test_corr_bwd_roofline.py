"""What PR 40 added to the benchmark, on the CPU: the correlation's
backward count against numbers worked by hand, and its reader with the
kernel's events, without them (the parent's scan), and with no
`corr.forward` entry in the configuration."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402

from benchmark.kernels import corr_bwd, roofline  # noqa: E402

PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
CORR = {"h": 48, "w": 64, "c": 256, "max_disp": 20, "stride": 2}
FORWARD = [{"kernel": "corr.forward", "args": CORR}]


def test_backward_count_at_the_cell():
    got = corr_bwd.backward(b=64, **CORR)
    # 64*48*64 positions x 441 maps x 256 channels x 4 (two multiply-adds)
    assert got["ops"] == 4 * 64 * 48 * 64 * 441 * 256 == 88785027072
    # f1, f2, df1, df2: 100,663,296 B each; the cotangent 173,408,256 B
    assert got["bytes"] == 4 * 100663296 + 173408256
    least, bound = roofline.least_seconds(got, PEAKS)
    assert bound == "memory" and least == pytest.approx(7.0337e-4, rel=1e-4)


def obs(ops, step_kernels):
    return {"device": {"ops": ops, "chips": 1, "modules": {"jit_step(1)": 2.0}},
            "config": {"step_kernels": step_kernels}, "traffic": {},
            "batch": 64, "chips": 1, "peaks": PEAKS}


def read(**o):
    return bench_run.load_reader("corr_bwd_roofline_pct.train").read(o)


def test_reader_takes_the_forward_entries_shapes():
    kernel = "%corr_bwd.1 = (bf16[64,48,64,256], bf16[64,48,64,256]) custom-call(..tpu_custom_call"
    ops = {kernel: (0.08, 2),
           "%corr_fwd.1 = f32[64,441,48,64] custom-call(..tpu_custom_call": (0.05, 2),
           "%fusion.1 = f32[8] fusion(": (1.0, 2)}
    # two steps in the window, 40 ms a call
    assert read(**obs(ops, FORWARD)) == pytest.approx(100 * 2 * 7.0337e-4 / 0.08,
                                                      rel=1e-4)
    scan = {n: v for n, v in ops.items() if not n.startswith("%corr_bwd")}
    assert read(**obs(scan, FORWARD)) is None
    assert read(**obs(ops, [])) is None
