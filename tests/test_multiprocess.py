"""True multi-process DCN-path test (SURVEY.md §5.8).

Spawns 2 subprocess JAX CPU processes (2 virtual devices each) joined via
`jax.distributed.initialize`, runs the multi-host data plumbing
(`local_batch_rows` / `put_global` / allgathered eval) inside them, and asserts loss equality with a
single-process run of the identical batches on this process's own
8-device mesh. The experiment setup is shared with the worker
(`_mp_worker.make_setup`) so both sides are guaranteed identical.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _mp_worker  # noqa: E402

from deepof_tpu.parallel.mesh import batch_sharding, build_mesh  # noqa: E402
from deepof_tpu.train.step import make_eval_fn, make_train_step  # noqa: E402

pytestmark = pytest.mark.slow  # 2 extra processes, each compiling 3 steps

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _single_process_reference():
    """The same batches/model/optimizer on this process's 8-device mesh."""
    cfg, ds, model, new_state = _mp_worker.make_setup()
    batch = _mp_worker.BATCH
    mesh = build_mesh(cfg.mesh)
    state = new_state()
    step = make_train_step(model, cfg, ds.mean, mesh)
    totals = []
    for k in range(2):
        b = jax.device_put(ds.sample_train(batch, iteration=k),
                           batch_sharding(mesh))
        state, m = step(state, b)
        totals.append(float(jax.device_get(m["total"])))
    eval_fn = make_eval_fn(model, cfg, ds.mean, mesh=mesh)
    vb = jax.device_put(ds.sample_val(batch, 0), batch_sharding(mesh))
    eval_init = float(jax.device_get(eval_fn(new_state().params, vb)["total"]))
    out = eval_fn(state.params, vb)
    return totals, float(jax.device_get(out["total"])), eval_init


def _run_two_process(tmp_path):
    """One 2-process run; returns (returncodes, outputs). A worker that
    outlives the deadline is killed and reported rc=-9/"TIMEOUT" rather
    than raising — the caller's transient-failure retry must see it
    (r04: a TimeoutExpired here errored the test with no retry)."""
    # stale results from a prior attempt must not satisfy the parent's
    # results-complete acceptance for THIS attempt
    for pid in range(2):
        try:
            os.remove(tmp_path / f"proc{pid}.json")
        except OSError:
            pass
    port = _free_port()
    addr = f"127.0.0.1:{port}"
    env = dict(os.environ)
    # a clean interpreter: no inherited PYTHONPATH, no inherited
    # XLA flags from this pytest process (its 8-device count would
    # override the workers' own 2-device setting)
    env.pop("PYTHONPATH", None)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.join(REPO, "tests", "_mp_worker.py"),
             addr, "2", str(pid), str(tmp_path)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for pid in range(2)
    ]
    outs, rcs = [], []
    try:
        for p in procs:
            # generous: 3 cold compile legs per worker on a
            # potentially contended single-core host
            try:
                out, _ = p.communicate(timeout=1200)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
                out = (out or "") + "\nTIMEOUT: worker exceeded deadline"
            outs.append(out)
            rcs.append(p.returncode)
    finally:
        for p in procs:
            p.kill()
    return rcs, outs


#: Failure signatures of the distributed runtime's hard-deadlined
#: rendezvous/shutdown phases — transient under scheduler contention on
#: this single-core host, deterministic failures look different (worker
#: asserts / JSON mismatches fail every attempt).
_TRANSIENT = ("Gloo context initialization failed", "DEADLINE_EXCEEDED",
              "BarrierError", "CoordinationService", "UNAVAILABLE",
              "TIMEOUT: worker exceeded deadline", "Connection refused",
              "Shutdown barrier", "coordination_service",
              "distributed service detected fatal errors")


def _results_complete(tmp_path) -> bool:
    """Both workers atomically published complete result files — every
    data-path claim is verified; only teardown remained."""
    try:
        for pid in range(2):
            with open(tmp_path / f"proc{pid}.json") as f:
                json.load(f)
        return True
    except (OSError, ValueError):
        return False


def test_two_process_dcn_path(tmp_path):
    # gloo's rendezvous has a hard 30s deadline and the coordination
    # service's shutdown barrier a similar one; a contended scheduler
    # (full suite + background jobs) can blow either transiently. Up to
    # 3 attempts, each logged — a deterministic failure fails them all.
    # (A longer rendezvous timeout would be preferable, but jaxlib's
    # make_gloo_tcp_collectives exposes only hostname/interface — the
    # 30s kv-store deadline is baked into the C++ wrapper, checked
    # jax 0.9: no Python-reachable knob.) A SHUTDOWN-phase crash after
    # both workers published complete results is a pass: the DCN
    # data-path claims are all in the files; only teardown failed
    # (r05 full-suite observation: "Shutdown barrier has failed" FATAL
    # after every metric had been written and fsync'd).
    for attempt in range(3):
        rcs, outs = _run_two_process(tmp_path)
        if not any(rcs):
            break
        transient = any(sig in o for o in outs for sig in _TRANSIENT)
        accepted = transient and _results_complete(tmp_path)
        print(f"[mp-retry] attempt {attempt + 1} rcs={rcs} "
              f"transient={transient} results_complete={accepted}",
              flush=True)
        if accepted or not transient:
            break
    ok = (not any(rcs)
          or (_results_complete(tmp_path)
              and any(sig in o for o in outs for sig in _TRANSIENT)))
    if not ok:
        for rc, out in zip(rcs, outs):
            assert rc == 0, f"worker failed:\n{out[-3000:]}"

    res = []
    for pid in range(2):
        with open(tmp_path / f"proc{pid}.json") as f:
            res.append(json.load(f))

    # each process owns a disjoint contiguous half of the global batch
    assert res[0]["n_local"] == res[1]["n_local"] == 4
    assert sorted(res[0]["rows"] + res[1]["rows"]) == list(range(8))
    assert not set(res[0]["rows"]) & set(res[1]["rows"])
    # distinct data coords -> decorrelated host sampling streams
    assert res[0]["process_seed"] != res[1]["process_seed"]

    # metrics are replicated: both processes observe identical values
    for key in ("step0_total", "step1_total", "step0_gradnorm",
                "step1_gradnorm", "step0_param_checksum",
                "step1_param_checksum", "eval_total",
                "eval_flow_sum", "eval_flow_shape"):
        assert res[0][key] == res[1][key], key

    # and they equal the single-process run of the same batches.
    # step0 evaluates at IDENTICAL params (pure reassociation bound);
    # step1 already includes one step of curvature-amplified drift
    ref_totals, ref_eval, ref_eval_init = _single_process_reference()
    np.testing.assert_allclose(res[0]["step0_total"], ref_totals[0], rtol=1e-5)
    np.testing.assert_allclose(res[0]["step1_total"], ref_totals[1], rtol=1e-4)
    # the assembled global val batch is byte-identical to the full copy
    assert res[0]["val_src_assembled_ok"]
    np.testing.assert_allclose(res[0]["eval_init_total"], ref_eval_init,
                               rtol=1e-5)
    # the 2-step-trained eval compares across DIFFERENT collective
    # topologies (hierarchical 2-process all-reduce vs single-runtime):
    # the reduction-reassociation noise is amplified by the loss curvature
    # each SGD step (measured ~100x/step at lr=1e-3), so exact equality is
    # unattainable by construction; 1e-3 bounds the chaos at lr=1e-4 with
    # an order of margin. The exact-equality claims are the init-params
    # eval and per-step losses above.
    np.testing.assert_allclose(res[0]["eval_total"], ref_eval, rtol=1e-3)
    # allgathered eval output covers the FULL global val batch on each host
    assert res[0]["eval_flow_shape"][0] == 8
