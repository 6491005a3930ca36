"""Smoke test on the chip: train, checkpoint, predict and the Pallas kernels
of deepof-tpu in ONE process, through the entry points a user calls.

    python chip_smoke.py              one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4    only the data-parallel path on four
                                      chips and the one-chip run it is
                                      compared with
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [MODEL]
                                      the same phases at tiny sizes on the
                                      CPU; says so, and never prints
                                      `"ok": true`

Full width, cut depth of nothing: Inception-v3 (44.5M parameters) at the
reference's 320x448, global batch 16, bfloat16 — the headline shape, so the
one big executable compiled here is the one the benchmark measures and the
compile cache carries over. Weights are random, from the config's seed.

Any phase that raises ends the run with a non-zero exit code; nothing is
caught and carried on from. Without an accelerator (and without
`--rehearse`) the script exits non-zero before any phase and prints no
result. The last line of stdout is one JSON object,
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.

The compile cache lives where `JAX_COMPILATION_CACHE_DIR` says, else in
`artifacts/xla_cache` (deepof_tpu.core.hostmesh); the script sets neither.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")

# Stated tolerances (max abs error, Pallas kernel vs the XLA formulation of
# the same op on the same device). f32 in, f32 accumulate on both sides:
# only the summation order differs. bf16 correlation: both sides round the
# result to bf16 (8 bits of mantissa on values of order 0.1) and the XLA
# sweep also rounds each product.
TOL_WARP = 1e-3
TOL_WARP_GRAD = 1e-3
TOL_CORR_F32 = 1e-4
TOL_CORR_BF16 = 2e-2
# Data-parallel vs one-device loss, relative: bf16 compute, the batch mean
# taken in a different order (per-shard partial sums + all-reduce).
TOL_DP_LOSS = 2e-2


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok, what) -> None:
    """A failed check ends the run (a plain `assert` would vanish under
    `python -O`)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def train_argv(log_dir: str, steps: int, size, batch: int, model: str | None,
               extra=()) -> list[str]:
    """`deepof_tpu train` arguments of the smoke run: the flyingchairs
    preset's model and loss on synthetic pairs, every step logged."""
    h, w = size
    argv = ["train", "--preset", "flyingchairs", "--synthetic",
            "--max-steps", str(steps), "--log-dir", log_dir,
            "--set", f"data.image_size=({h},{w})",
            "--set", f"data.gt_size=({h},{w})",
            "--set", f"data.batch_size={batch}",
            "--set", "train.compute_dtype=bfloat16",
            "--set", "train.log_every=1"]
    if model:
        argv += ["--model", model]
    return argv + list(extra)


def read_records(log_dir: str) -> list[dict]:
    with open(os.path.join(log_dir, "metrics.jsonl")) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def check_losses(records: list[dict], steps: int) -> list[float]:
    """Every logged loss finite, one record per step, counter at `steps`."""
    import numpy as np

    train = [r for r in records if r["kind"] == "train"]
    losses = [r["loss"] for r in train]
    check(len(losses) >= steps, f"{len(losses)} train records < {steps}")
    check(all(v is not None and np.isfinite(v) for v in losses), losses)
    check(train[-1]["step"] == steps, (train[-1]["step"], steps))
    return losses


def make_pairs(out_dir: str, sizes, seed: int) -> list[tuple[str, str, tuple]]:
    """Seeded PNG pairs: smooth random texture, second frame shifted."""
    import cv2
    import numpy as np

    os.makedirs(out_dir, exist_ok=True)
    rng = np.random.RandomState(seed)
    pairs = []
    for i, (h, w) in enumerate(sizes):
        base = rng.rand(h // 8 + 2, w // 8 + 2, 3).astype(np.float32)
        img = cv2.resize(base, (w + 16, h + 16), interpolation=cv2.INTER_CUBIC)
        img = np.clip(img * 255.0, 0, 255).astype(np.uint8)
        dy, dx = rng.randint(1, 6, size=2)
        a, b = img[8:8 + h, 8:8 + w], img[8 - dy:8 - dy + h, 8 - dx:8 - dx + w]
        pa = os.path.join(out_dir, f"pair{i}_a.png")
        pb = os.path.join(out_dir, f"pair{i}_b.png")
        check(cv2.imwrite(pa, a) and cv2.imwrite(pb, b), f"writing {pa}")
        pairs.append((pa, pb, (h, w)))
    return pairs


# ------------------------------------------------------------- phases


def phase_train(cli, argv: list[str], run_dir: str, steps: int,
                on_chip: bool) -> None:
    import jax

    cfg = cli.config_for(argv)
    h, w = cfg.data.image_size
    say(f"train: {cfg.model} {h}x{w} batch {cfg.data.batch_size} "
        f"bf16, {steps} steps via `deepof_tpu train` — the first step "
        "compiles the whole train step (minutes when the cache is cold)")
    t0 = time.perf_counter()
    rc = cli.main(argv)
    check(rc == 0, f"train returned {rc}")
    say(f"train: done in {time.perf_counter() - t0:.1f}s")
    recs = read_records(run_dir)
    losses = check_losses(recs, steps)
    say(f"train: losses {losses} (all finite), step counter = {steps}")
    first = next(r for r in recs if r["kind"] == "info"
                 and "first step" in r.get("message", ""))
    first_s = float(re.search(r"([0-9.]+)s", first["message"]).group(1))
    # a step's record is written when its metrics have been fetched from
    # the device, so the gaps between records are whole-step wall times
    # (the loop's own steps_per_sec clocks the asynchronous dispatch)
    stamps = [r["time"] for r in recs if r["kind"] == "train"]
    gaps = sorted(b - a for a, b in zip(stamps, stamps[1:]))
    dev = jax.devices()[0]
    ms = dev.memory_stats() or {}
    say(f"train: device_kind={dev.device_kind!r} first step (compile+run) "
        f"{first_s:.1f}s, steady step {gaps[len(gaps) // 2]:.4f}s (median gap "
        f"between the records of steps 1..{steps}), "
        f"peak_bytes_in_use={ms.get('peak_bytes_in_use')}")
    say("train: compile cache at first step: requests="
        f"{first['compile_cache_requests']} hits={first['compile_cache_hits']} "
        f"misses={first['compile_cache_misses']}")
    ckpt = os.path.join(run_dir, "ckpt", f"step_{steps:010d}")
    check(os.path.isdir(ckpt), f"no checkpoint at {ckpt}")
    say(f"train: checkpoint written: {os.path.relpath(ckpt, ROOT)}")
    if on_chip:
        check(ms.get("peak_bytes_in_use"),
              "the TPU reported no memory stats")


def phase_step_text(cli, argv: list[str], on_chip: bool) -> None:
    """The step that just ran, lowered again from shapes alone (the
    warmup's recipe for the Trainer's step): on the chip its text must hold
    the Mosaic kernels (`tpu_custom_call`) — the `auto` gates took neither
    the XLA branch nor interpret mode — and compiling it must hit the
    cache entry the trainer just wrote."""
    from deepof_tpu.obs.telemetry import lowered_flops
    from deepof_tpu.train.warmup import cache_delta, lower_train_step

    lowered = lower_train_step(cli.config_for(argv))
    n_low = lowered.as_text().count("tpu_custom_call")
    say(f"step text: lowered train step holds {n_low} tpu_custom_call(s)")
    if not on_chip:
        return
    check(n_low > 0, "no Pallas kernel in the lowered train step on tpu")
    say("step text: compiling the lowered step (the trainer just compiled "
        "the same program: a cache hit)")
    with cache_delta() as d:
        compiled = lowered.compile()
    n_comp = compiled.as_text().count("tpu_custom_call")
    say(f"step text: compiled train step holds {n_comp} tpu_custom_call(s); "
        f"cache {d.stats()}; memory {compiled.memory_analysis()}")
    say("step text: flops per step by XLA's cost analysis: of the lowering "
        f"{lowered_flops(lowered)}, of the "
        f"compiled executable {lowered_flops(compiled)}")
    check(n_comp > 0, "no tpu_custom_call in the compiled train step")
    check(d.stats()["hits"] >= 1 and d.stats()["misses"] == 0,
          "the re-lowered train step missed the trainer's cache entry: the "
          "warmup's recipe has drifted from the Trainer's step")


def phase_predict(cli, run_dir: str, size, sizes, model: str | None,
                  extra) -> None:
    import numpy as np

    from deepof_tpu.io.flo import read_flo

    pairs = make_pairs(os.path.join(OUT, "pairs"), sizes, seed=0)
    out_dir = os.path.join(OUT, "flows")
    shutil.rmtree(out_dir, ignore_errors=True)
    say(f"predict: {len(pairs)} pairs at native sizes "
        f"{[p[2] for p in pairs]} via `deepof_tpu predict` from the "
        "checkpoint — compiles the serving forward once")
    argv = ["predict", "--preset", "flyingchairs", "--synthetic",
            "--log-dir", run_dir, "--out", out_dir,
            "--set", f"data.image_size=({size[0]},{size[1]})",
            "--set", f"data.gt_size=({size[0]},{size[1]})",
            *extra, "--pairs"] + [f"{a}:{b}" for a, b, _ in pairs]
    if model:
        argv += ["--model", model]
    t0 = time.perf_counter()
    rc = cli.main(argv)
    check(rc == 0, f"predict returned {rc}")
    flos = sorted(f for f in os.listdir(out_dir) if f.endswith(".flo"))
    check(len(flos) == len(pairs), (flos, len(pairs)))
    for name, (_, _, (h, w)) in zip(flos, pairs):
        flow = read_flo(os.path.join(out_dir, name))
        check(flow.shape == (h, w, 2), (name, flow.shape, (h, w)))
        check(np.isfinite(flow).all(), f"{name}: non-finite flow")
        say(f"predict: {name} shape {flow.shape} finite, "
            f"|flow| max {float(np.abs(flow).max()):.3f}")
    say(f"predict: done in {time.perf_counter() - t0:.1f}s")


def kernel_checks(warp_sizes, corr_size, batch: int, on_chip: bool,
                  mesh=None) -> None:
    """Pallas vs XLA on this device (compiled, not interpreted, on the
    chip): warp values and flow gradient, correlation values. With `mesh`,
    the batch is sharded over its "data" axis and the kernels run through
    `shard_over_batch`; outputs must then live on every device of it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepof_tpu.ops.corr import correlation
    from deepof_tpu.ops.warp import backward_warp
    from deepof_tpu.parallel.mesh import batch_sharding
    from deepof_tpu.parallel.spatial import mesh_context

    rng = np.random.RandomState(0)
    shard = batch_sharding(mesh) if mesh is not None else None

    def put(x):
        return jax.device_put(x, shard) if shard is not None else jnp.asarray(x)

    def run(fn, *args):
        f = jax.jit(fn)
        with mesh_context(mesh):  # read at trace time by the kernel wrappers
            text = f.lower(*args).as_text()
            out = f(*args)
        return out, "tpu_custom_call" in text

    def spread(x):
        if mesh is not None:
            devs = {s.device for s in x.addressable_shards}
            check(len(devs) == mesh.size, (len(devs), mesh.size))

    def err(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    def top(x):  # the reference's own magnitude: an error of 0 between
        m = float(jnp.max(jnp.abs(x.astype(jnp.float32))))  # two zero
        check(np.isfinite(m) and m > 0, "reference output is all zero")
        return m  # arrays would prove nothing

    for h, w in warp_sizes:
        img = put(rng.rand(batch, h, w, 3).astype(np.float32))
        flow = put((rng.randn(batch, h, w, 2) * 3.0).astype(np.float32))
        ct = put(rng.randn(batch, h, w, 3).astype(np.float32))

        def both(impl):
            def f(im, fl, c):
                val, vjp = jax.vjp(lambda x: backward_warp(im, x, impl=impl), fl)
                return val, vjp(c)[0]
            return f

        (vp, gp), kern = run(both("pallas"), img, flow, ct)
        (vx, gx), _ = run(both("xla"), img, flow, ct)
        spread(vp), spread(gp)
        ev, eg = err(vp, vx), err(gp, gx)
        say(f"kernel: warp {h}x{w} B{batch} pallas vs xla max abs err: "
            f"value {ev:.3e} (tol {TOL_WARP}, |xla| max {top(vx):.3f}), "
            f"flow-grad {eg:.3e} (tol {TOL_WARP_GRAD}, |xla| max "
            f"{top(gx):.3f}); tpu_custom_call in text: {kern}")
        check(np.isfinite([ev, eg]).all() and ev <= TOL_WARP
              and eg <= TOL_WARP_GRAD, f"warp {h}x{w} over tolerance")
        check(kern or not on_chip, "warp took no Mosaic kernel on tpu")

    h, w, c, md, st = corr_size
    for dtype, tol in ((jnp.float32, TOL_CORR_F32), (jnp.bfloat16, TOL_CORR_BF16)):
        f1 = put(rng.randn(batch, h, w, c).astype(np.float32)).astype(dtype)
        f2 = put(rng.randn(batch, h, w, c).astype(np.float32)).astype(dtype)
        cp, kern = run(lambda a, b: correlation(a, b, md, st, impl="pallas"),
                       f1, f2)
        cx, _ = run(lambda a, b: correlation(a, b, md, st, impl="xla"), f1, f2)
        spread(cp)
        e = err(cp, cx)
        say(f"kernel: correlation {h}x{w}x{c} B{batch} {jnp.dtype(dtype).name} "
            f"max_disp {md} stride {st} pallas vs xla max abs err {e:.3e} "
            f"(tol {tol}, |xla| max {top(cx):.3f}); tpu_custom_call in "
            f"text: {kern}")
        check(np.isfinite(e) and e <= tol, "correlation over tolerance")
        check(kern or not on_chip,
              "correlation took no Mosaic kernel on tpu")


def phase_four_chips(cli, size, batch: int, steps: int, model: str | None,
                     extra, warp_sizes, corr_size, on_chip: bool) -> None:
    """The trainer's default layout — data-parallel over every device —
    against the same run on one device, in this process."""
    import jax
    import numpy as np

    from deepof_tpu.parallel.mesh import batch_sharding, build_mesh
    from deepof_tpu.train.loop import Trainer

    devs = jax.devices()
    check(len(devs) == 4,
          f"--chips 4 needs four devices, jax found {len(devs)}")
    mesh4 = build_mesh(devices=devs)
    mesh1 = build_mesh(devices=devs[:1])
    say("four chips: kernels alone on the 4-device mesh first (seconds)")
    kernel_checks(warp_sizes[:1], corr_size, batch, on_chip, mesh=mesh4)

    losses = {}
    for name, mesh in (("dp4", mesh4), ("one", mesh1)):
        run_dir = os.path.join(OUT, f"run_{name}")
        shutil.rmtree(run_dir, ignore_errors=True)
        cfg = cli.config_for(
            train_argv(run_dir, steps, size, batch, model, extra))
        say(f"four chips: {name}: Trainer on mesh {dict(mesh.shape)}, global "
            f"batch {batch}, {steps} steps — compiles its own train step")
        t0 = time.perf_counter()
        trainer = Trainer(cfg, mesh=mesh)
        trainer.fit(max_steps=steps)
        say(f"four chips: {name}: done in {time.perf_counter() - t0:.1f}s")
        losses[name] = check_losses(read_records(run_dir), steps)
        say(f"four chips: {name}: losses {losses[name]}")
        if name == "dp4":
            leaf = jax.tree_util.tree_leaves(trainer.state.params)[0]
            check(leaf.sharding.is_fully_replicated
                  and len(leaf.devices()) == 4, "params not replicated on 4")
            b = jax.device_put(
                trainer.dataset.sample_train(batch, iteration=0),
                batch_sharding(mesh))
            shards = {s.device for s in b["source"].addressable_shards}
            check(len(shards) == 4, shards)
            say("four chips: params replicated on 4 devices; batch sharded "
                f"over 4 distinct devices {sorted(d.id for d in shards)}")
            text = trainer.train_step.lower(trainer.state, b).compile().as_text()
            n_ar, n_k = text.count("all-reduce"), text.count("tpu_custom_call")
            say(f"four chips: compiled dp4 step: {n_ar} all-reduce mention(s), "
                f"{n_k} tpu_custom_call(s)")
            check(n_ar > 0, "no all-reduce in the data-parallel step")
            check(n_k > 0 or not on_chip, "no Mosaic kernel in the dp4 step")
    rel = max(abs(a - b) / max(abs(b), 1e-9)
              for a, b in zip(losses["dp4"], losses["one"]))
    say(f"four chips: dp4 vs one-device loss max rel diff {rel:.3e} "
        f"(tol {TOL_DP_LOSS})")
    check(np.isfinite(rel) and rel <= TOL_DP_LOSS,
          "data-parallel and one-device losses disagree")


# --------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the data-parallel path on four chips and "
                         "its one-chip comparison")
    ap.add_argument("--rehearse", nargs="?", const="flownet_s", default=None,
                    metavar="MODEL",
                    help="CPU rehearsal at tiny sizes (default model "
                         "flownet_s at quarter width); never prints ok: true")
    args = ap.parse_args(argv)

    if args.rehearse and args.chips == 4:
        # virtual devices for the mesh path; must precede jax's backend init
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
    import jax

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    say(f"jax {jax.__version__}; devices: {devs}")
    if args.rehearse:
        if platform != "cpu":
            say(f"--rehearse is the CPU rehearsal; jax found {platform!r}")
            return 2
        say("REHEARSAL on the CPU at tiny sizes: control flow only, "
            "Pallas kernels interpreted, no device number means anything")
    elif platform != "tpu":
        say(f"no accelerator: jax found platform={platform!r} ({kind}); "
            "this script needs a TPU (or --rehearse). No result.")
        return 1

    from deepof_tpu import cli
    from deepof_tpu.train.warmup import cache_stats, install_cache_counters

    install_cache_counters()
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: say(f"  compiled an executable in {secs:.1f}s")
        if event.endswith("backend_compile_duration") and secs >= 1.0 else None)

    on_chip = platform == "tpu"
    if args.rehearse:
        model = args.rehearse
        extra = (["--set", "width_mult=0.25"] if model == "flownet_s" else [])
        size, batch, steps = (64, 64), 8, 3
        native = [(64, 64), (48, 80)]
        warp_sizes, corr_size = [(10, 14)], (10, 14, 16, 4, 2)
        kbatch = 8 if args.chips == 1 else 4
    else:
        model, extra = None, []  # the preset's own: inception_v3, full width
        size, batch, steps = (320, 448), 16, 3
        native = [(320, 448), (384, 512), (240, 320)]
        warp_sizes, corr_size = ([(40, 56), (80, 112), (160, 224)],
                                 (40, 56, 256, 20, 2))
        kbatch = 16
    os.makedirs(OUT, exist_ok=True)

    try:
        if args.chips == 4:
            phase_four_chips(cli, size, batch, steps, model, extra,
                             warp_sizes, corr_size, on_chip)
        else:
            run_dir = os.path.join(OUT, "run")
            shutil.rmtree(run_dir, ignore_errors=True)  # stale dirs resume
            argv = train_argv(run_dir, steps, size, batch, model, extra)
            phase_train(cli, argv, run_dir, steps, on_chip)
            phase_step_text(cli, argv, on_chip)
            phase_predict(cli, run_dir, size, native, model, extra)
            kernel_checks(warp_sizes, corr_size, kbatch, on_chip)
    finally:
        # params + Adam moments of 44.5M parameters, twice: more than the
        # chip tool brings back. The logs and flows stay.
        for d in os.listdir(OUT):
            shutil.rmtree(os.path.join(OUT, d, "ckpt"), ignore_errors=True)
    say(f"compile cache, whole process: {cache_stats()} "
        f"dir={jax.config.jax_compilation_cache_dir}")
    # the driver reads the device keys of this last line and ignores the rest
    print(json.dumps({"ok": on_chip, **({"rehearsal": True} if args.rehearse
                                         else {}),
                      "device": {"platform": platform, "kind": kind,
                                 "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
