"""What replaced bench.py's probe/re-exec/stale harness: a measurement path
that fails off the chip instead of falling back, `chip_smoke.py`'s refusal
and its CPU rehearsal, the one rule for the compile cache's place, and the
peak table keyed by device kind.

Subprocess tests run the scripts exactly as a user would (`python bench.py`,
`python chip_smoke.py`), on the CPU backend; nothing here needs a chip.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import bench
from deepof_tpu.core import hostmesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout, **env):
    """One-device CPU subprocess from the repo root; `env` overrides (None
    removes a variable)."""
    e = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    for k, v in env.items():
        e.pop(k, None) if v is None else e.update({k: v})
    return subprocess.run([sys.executable, *args], cwd=REPO, env=e,
                          timeout=timeout, capture_output=True, text=True)


# ------------------------------------------------------------ bench.py


def _fake_tpu(kind):
    return [types.SimpleNamespace(platform="tpu", device_kind=kind)]


@pytest.mark.parametrize("kind,has_mfu", [("TPU v5 lite", True),
                                          ("TPU v9 imaginary", False)])
def test_bench_math_and_peak_by_device_kind(monkeypatch, kind, has_mfu):
    """bench(): throughput is batch over the timed step, the line names
    its device, and `mfu_nominal` exists only for a device kind in the
    peak table."""
    bench._import_compute()  # conftest forced the cpu backend already
    monkeypatch.setattr(bench, "_require_tpu", lambda: _fake_tpu(kind))
    monkeypatch.setattr(bench, "calibrate", lambda: {"matmul_tflops": 100.0})
    fake_cfg = types.SimpleNamespace(loss=types.SimpleNamespace(
        warp_impl="auto"))
    seen = {}

    def setup(model, batch, size, warp_impl):
        seen.update(warp_impl=warp_impl)
        return fake_cfg, None, None, None, "state", "step", "b"

    monkeypatch.setattr(bench, "headline_setup", setup)
    monkeypatch.setattr(
        bench, "time_train_step",
        lambda step, state, b, steps, windows, warmup: (0.1, state,
                                                        np.array([1.0])))
    monkeypatch.setattr(bench, "step_flops", lambda *a: 8e12)
    res = bench.bench()
    assert seen == {"warp_impl": None}
    assert abs(res["steps_per_sec"] - 10.0) < 1e-9   # 0.1 s a step
    assert abs(res["pairs_per_sec"] - 160.0) < 1e-9  # batch 16 x 10
    assert res["flops_per_step"] == 8e12
    assert res["model_tflops"] == 80.0               # 8e12 x 10 / 1 chip
    assert (res["platform"], res["device_kind"], res["n_chips"]) == (
        "tpu", kind, 1)
    if has_mfu:
        assert res["mfu_nominal"] == round(80.0 / 197.0, 4)
    else:  # no number against a chip that is not in the table
        assert "mfu_nominal" not in res


def test_bench_refuses_a_cpu_backend(capsys):
    """No TPU: bench() raises SystemExit with a message (exit code 1) before
    anything is built or timed, and prints no value."""
    with pytest.raises(SystemExit) as e:
        bench.bench()
    assert e.value.code not in (0, None) and "TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""


def test_bench_script_exits_nonzero_without_a_value_on_cpu():
    r = _run(["bench.py"], timeout=120)
    assert r.returncode != 0
    assert "{" not in r.stdout and "value" not in r.stdout, r.stdout
    assert "needs a TPU" in r.stderr


# -------------------------------------------------------- chip_smoke.py


def test_chip_smoke_without_accelerator_exits_nonzero_and_prints_no_ok():
    r = _run(["chip_smoke.py"], timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout, r.stdout
    assert "no accelerator" in r.stdout


def _rehearsal(*flags):
    r = _run(["chip_smoke.py", "--rehearse", *flags], timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert '"ok": true' not in r.stdout
    assert "REHEARSAL" in r.stdout
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    return r.stdout, last


def test_chip_smoke_rehearsal_walks_train_checkpoint_predict_kernels():
    """The smallest registry model and input that still walk every phase of
    the one-chip run: `cli train` -> checkpoint -> lowered step text ->
    `cli predict` at two native sizes -> Pallas-vs-XLA kernel checks."""
    out, last = _rehearsal()
    assert last["device"]["count"] == 1
    for marker in ("train: losses", "checkpoint written", "step text:",
                   "predict: 0000_pair0_a_flow.flo shape (64, 64, 2)",
                   "predict: 0001_pair1_a_flow.flo shape (48, 80, 2)",
                   "kernel: warp", "kernel: correlation"):
        assert marker in out, marker


def test_chip_smoke_rehearsal_of_the_four_chip_path():
    """`--chips 4` on four virtual CPU devices: kernels alone on the mesh,
    then the data-parallel trainer against the one-device trainer."""
    out, last = _rehearsal("--chips", "4")
    assert last["device"]["count"] == 4
    for marker in ("kernels alone on the 4-device mesh",
                   "batch sharded over 4 distinct devices [0, 1, 2, 3]",
                   "all-reduce", "dp4 vs one-device loss max rel diff"):
        assert marker in out, marker
    assert "train: losses" not in out  # no one-chip phase with the option


# ------------------------------------------------------ compile cache


def test_compile_cache_dir_rule(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins over everything; unset, the config
    field, else the fixed artifacts/xla_cache under the checkout."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(REPO, "artifacts", "xla_cache")
    assert hostmesh.COMPILE_CACHE_DIR == fixed
    assert hostmesh.compile_cache_dir() == fixed
    assert hostmesh.compile_cache_dir("/elsewhere") == "/elsewhere"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/from/env")
    assert hostmesh.compile_cache_dir() == "/from/env"
    assert hostmesh.compile_cache_dir("/elsewhere") == "/from/env"


_CACHE_PROBE = """
import dataclasses, os, sys
from deepof_tpu.core.hostmesh import force_cpu_devices
force_cpu_devices(2)
import jax, jax.numpy as jnp
from deepof_tpu.core.config import get_config
from deepof_tpu.train import warmup
want = os.environ.get("JAX_COMPILATION_CACHE_DIR") or sys.argv[1]
assert jax.config.jax_compilation_cache_dir == want, "force_cpu_devices"
cfg = get_config("flyingchairs")
cfg = cfg.replace(train=dataclasses.replace(
    cfg.train, compile_cache=True, compile_cache_dir=sys.argv[2]))
got = warmup.enable_for_config(cfg)
want = os.environ.get("JAX_COMPILATION_CACHE_DIR") or sys.argv[2]
assert got == want == jax.config.jax_compilation_cache_dir, (got, want)
warmup.enable_compile_cache(sys.argv[2], min_compile_time_secs=0.0)
jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64))).block_until_ready()
print(warmup.cache_stats())
"""


def test_cache_dir_env_is_used_and_left_alone(tmp_path):
    """With the variable set, force_cpu_devices, enable_for_config (config
    field pointing elsewhere) and enable_compile_cache (argument pointing
    elsewhere) all leave it alone, and entries appear there and nowhere
    else. In a subprocess: the suite's own cache is untouched."""
    env_dir, other = tmp_path / "from_env", tmp_path / "from_config"
    r = _run(["-c", _CACHE_PROBE, hostmesh.COMPILE_CACHE_DIR, str(other)],
             timeout=120, JAX_COMPILATION_CACHE_DIR=str(env_dir))
    assert r.returncode == 0, r.stderr[-2000:]
    assert os.listdir(env_dir), "no cache entry under the env directory"
    assert not other.exists()


def test_cache_dir_unset_uses_config_then_fixed_path(tmp_path):
    other = tmp_path / "from_config"
    r = _run(["-c", _CACHE_PROBE, hostmesh.COMPILE_CACHE_DIR, str(other)],
             timeout=120, JAX_COMPILATION_CACHE_DIR=None)
    assert r.returncode == 0, r.stderr[-2000:]
    assert os.listdir(other), "no cache entry under the configured directory"
