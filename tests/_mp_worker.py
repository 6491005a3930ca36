"""Worker process for test_multiprocess.py: one of N JAX CPU processes.

Launched with a clean environment (no inherited XLA flags or platform);
forces 2 virtual CPU devices, joins the distributed runtime, and runs the
multi-host data-path plumbing (SURVEY.md §5.8): `local_batch_rows` row
slicing -> `put_global` assembly -> sharded train step, and the
allgathered eval. Writes its
metrics as JSON for the parent test to compare against a single-process
run of the identical batches.

`make_setup()` is imported by test_multiprocess.py for its single-process
reference run — the equality asserts are only meaningful if both sides
build the identical config/model/optimizer/initial state.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

H, W, BATCH = 16, 32, 8


def make_setup():
    """(cfg, ds, model, new_state_fn) shared by worker and reference."""
    import jax.numpy as jnp
    import optax

    from deepof_tpu.core.config import (
        DataConfig,
        ExperimentConfig,
        LossConfig,
        MeshConfig,
        OptimConfig,
        TrainConfig,
    )
    from deepof_tpu.data.datasets import SyntheticData
    from deepof_tpu.models.registry import build_model
    from deepof_tpu.train.state import create_train_state

    cfg = ExperimentConfig(
        name="mp",
        model="flownet_s",
        width_mult=0.25,  # thin trunk: DCN-equality semantics are width-free
        loss=LossConfig(weights=(16, 8, 4, 2, 1, 1)),
        optim=OptimConfig(learning_rate=1e-4),
        data=DataConfig(dataset="synthetic", image_size=(H, W),
                        gt_size=(H, W), batch_size=BATCH),
        mesh=MeshConfig(),  # pure data-parallel: data axis spans all hosts
        train=TrainConfig(seed=0),
    )
    ds = SyntheticData(cfg.data)
    model = build_model("flownet_s", width_mult=0.25)
    # SGD, not Adam: the test asserts cross-runtime loss EQUALITY, and
    # Adam's eps-scaled normalization amplifies the tiny collective
    # reassociation differences between the distributed and single-
    # process runtimes into O(lr) param drift; SGD is linear in grad
    tx = optax.sgd(cfg.optim.learning_rate)

    def new_state():
        return create_train_state(model, jnp.zeros((BATCH, H, W, 6)), tx,
                                  seed=0)

    return cfg, ds, model, new_state


def main() -> None:
    addr, nproc, pid, outdir = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4])

    from deepof_tpu.core.hostmesh import force_cpu_devices

    # 2 virtual devices per worker (4 global): the DCN-path claims (row
    # slicing, put_global, cross-process collectives, allgathered eval)
    # are device-count-free, and halving the SPMD partitions on this
    # single-core host roughly halves compile+execute wall-clock — the
    # r04 suite-load flake margin (VERDICT r04 weak #6)
    force_cpu_devices(2)
    import jax

    jax.distributed.initialize(
        coordinator_address=addr, num_processes=nproc, process_id=pid,
        initialization_timeout=600)
    assert jax.process_count() == nproc, jax.process_count()
    assert len(jax.local_devices()) == 2
    assert len(jax.devices()) == 2 * nproc

    import numpy as np
    import jax.numpy as jnp
    from jax import flatten_util

    from deepof_tpu.parallel.mesh import (
        batch_sharding,
        build_mesh,
        local_batch_rows,
        process_seed,
        put_global,
        put_global_from_full,
    )
    from deepof_tpu.train.step import make_eval_fn, make_train_step

    cfg, ds, model, new_state = make_setup()
    mesh = build_mesh(cfg.mesh)
    state = new_state()
    step = make_train_step(model, cfg, ds.mean, mesh)

    n_local, rows = local_batch_rows(mesh, BATCH)
    results = {
        "rows": rows,
        "n_local": n_local,
        "process_seed": process_seed(mesh, 123),
    }

    def local_global(iteration):
        gb = ds.sample_train(BATCH, iteration=iteration)
        lb = {key: np.asarray(v)[rows] for key, v in gb.items()}
        return put_global(lb, batch_sharding(mesh))

    # --- AOT-compile EVERY collective program, then rendezvous, then
    # execute. gloo's context init has a hard 30s kv-store deadline that
    # fires at the FIRST collective *execution*; per-worker compile-time
    # skew (AOT-cache hit vs miss, scheduler contention) routinely
    # exceeds it (the r05 full-suite flake). Compiling both legs
    # first and crossing a coordination-service barrier (10 min budget,
    # no gloo involved) brings both workers to the gloo key exchange
    # within milliseconds of each other.
    b = local_global(0)
    step_exec = step.lower(state, b).compile()

    from jax.experimental import multihost_utils

    eval_fn = make_eval_fn(model, cfg, ds.mean, mesh=mesh)
    vb = ds.sample_val(BATCH, 0)
    gvb = put_global_from_full(vb, mesh, batch_sharding(mesh))
    eval_exec = eval_fn.lower(state.params, gvb).compile()

    from jax._src import distributed

    distributed.global_state.client.wait_at_barrier(
        "mp_precollective", timeout_in_ms=600_000)

    # 2 train steps: each process loads ONLY its own rows of the
    # (deterministic) global batch; put_global assembles without any host
    # holding the full batch.
    for k in range(2):
        if k > 0:
            b = local_global(k)
        state, m = step_exec(state, b)
        results[f"step{k}_total"] = float(jax.device_get(m["total"]))
        results[f"step{k}_gradnorm"] = float(jax.device_get(m["grad_norm"]))
        flat, _ = flatten_util.ravel_pytree(state.params)
        results[f"step{k}_param_checksum"] = float(
            jax.device_get(jnp.abs(flat).sum()))

    # assembly diagnostics: the global array each host sees must be the
    # full val batch, byte-identical to the host-local copy
    gsrc = np.asarray(multihost_utils.process_allgather(gvb["source"],
                                                        tiled=True))
    results["val_src_assembled_ok"] = bool(
        np.array_equal(gsrc, np.asarray(vb["source"])))
    # eval with the UNTRAINED params isolates batch assembly from any
    # cross-runtime optimizer drift
    out0 = eval_exec(new_state().params, gvb)
    results["eval_init_total"] = float(np.asarray(
        multihost_utils.process_allgather(out0["total"], tiled=True)).ravel()[0])
    out = eval_exec(state.params, gvb)
    gathered = {k2: np.asarray(multihost_utils.process_allgather(v, tiled=True))
                for k2, v in out.items()}
    results["eval_total"] = float(gathered["total"].ravel()[0])
    results["eval_flow_shape"] = list(gathered["flow"].shape)
    results["eval_flow_sum"] = float(np.abs(gathered["flow"]).sum())

    # Atomic publish BEFORE the distributed shutdown: the coordination
    # service's shutdown barrier can fail under scheduler contention
    # (observed r05: "Shutdown barrier has failed" -> FATAL after all
    # work completed). A complete results file is the worker's success
    # criterion; the parent treats a teardown-phase crash after both
    # files exist as a pass.
    tmp = os.path.join(outdir, f"proc{pid}.json.tmp")
    with open(tmp, "w") as f:
        json.dump(results, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, os.path.join(outdir, f"proc{pid}.json"))
    jax.distributed.shutdown()


if __name__ == "__main__":
    main()
