"""TPU perf probe — the step decomposition and kernel timings, runnable.

Runs in this process on the attached TPU (one process per chip; exits
non-zero on any other backend — bench._require_tpu). Timings end in a
value fetch that depends on every dispatch of the window; see DESIGN.md
"Benchmark honesty". Through the chip tool: one command per call, e.g.

    python tools/perf_probe.py                      # all sections
    python tools/perf_probe.py --only warp,decomp   # named sections

Sections:
  headline bench.py headline (value + MFU fields)
  calib    raw matmul TFLOP/s measured beside it
  decomp   Inception-v3 train-step decomposition (fwd / fwd+loss /
           +bwd / full step, and the pyramid-loss/warp share)
  warpscan warp timing with 20 warps chained inside one jit (per-call
           dispatch amortized away), incl. the finest 160x224 level —
           supersedes `warp` for decisions
  warpsweep the warp kernels' time against the rows their sweep visits,
           beside the XLA gather, at 160x224 (two lane tiles) and 80x112,
           batch 64: the measurement behind `PALLAS_AUTO_MAX_SWEEP`
  corr     the correlation's kernels at `flownet_c_chairs.train`'s shapes:
           forward, backward, both, each kernel's TOP/s and roofline share,
           the backward's block builders (strided roll / selects) by row
           tile and product group, and the error of each direction
           against the XLA sweep
  batch    batch-size throughput curve (16/96)
  multiframe Sintel-shaped T=10 volume train step
  warp     per-call XLA vs Pallas warp table (includes dispatch)
  attn     the latent attention's causal scores at the language-model
           cell's layer shape: XLA blocks vs the fused kernels by key
           block, forward and forward + backward, and their difference
  proj     the projection block of either language-model family alone
           (`mla_proj` / `gqa_proj`: the products, norm, rotary positions,
           cast and layout, up to the attention's operands) at its cell's
           layer shape: the XLA route vs the fused pass (`qk_prep_*`),
           forward and forward + backward, and their difference
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench as bench_mod  # noqa: E402


def timeit(name, fn, *args, steps=10, windows=3, items=None):
    """Honest window timing. Each call's input is perturbed by 0 * the
    previous call's output, so the final value fetch transitively depends
    on EVERY dispatch in the window — per DESIGN.md "Benchmark honesty",
    a fetch depending only on the last dispatch undermeasures when
    earlier dispatches are still in flight."""
    import jax
    import jax.numpy as jnp

    def chain(tree, prev_out):
        z = jnp.asarray(prev_out).ravel()[0] * 0
        return jax.tree_util.tree_map(lambda x: x + z.astype(x.dtype), tree)

    out = fn(*args)
    val = float(jax.device_get(jnp.asarray(out).ravel()[0]))
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*args[:-1], chain(args[-1], out))
        float(jax.device_get(jnp.asarray(out).ravel()[0]))
        best = min(best, time.perf_counter() - t0)
    per = best / steps
    rate = f"  {items / per:9.1f} items/s" if items else ""
    print(f"{name:44s} {per*1e3:8.2f} ms{rate}  ({val:.4f})", flush=True)
    return per


def _time_full_step(step, state, b, steps=10, windows=3):
    """Per-call train-step timing via the ONE shared honesty-critical
    idiom (bench.time_train_step)."""
    per, state, _ = bench_mod.time_train_step(step, state, b, steps=steps,
                                              windows=windows)
    return per, state


def sec_calib() -> None:
    print("calib:", bench_mod.calibrate(), flush=True)


def sec_warp() -> None:
    import jax

    from deepof_tpu.ops.warp import backward_warp

    key = jax.random.PRNGKey(0)
    for (h, w) in [(40, 56), (80, 112)]:
        img = jax.random.uniform(key, (16, h, w, 3))
        flow = jax.random.uniform(key, (16, h, w, 2)) * 8 - 4
        for impl in ("xla", "pallas"):
            f = jax.jit(lambda i, fl, impl=impl:
                        backward_warp(i, fl, impl=impl).sum())
            timeit(f"warp fwd {impl} {h}x{w}", f, img, flow)
            g = jax.jit(lambda i, fl, impl=impl: jax.grad(
                lambda q: backward_warp(i, q, impl=impl).sum())(fl).sum())
            timeit(f"warp grad {impl} {h}x{w}", g, img, flow)


def sec_warp_scan() -> None:
    """Device-honest warp timing: 20 warps chained inside ONE jit via
    lax.scan, so the per-call dispatch floor amortizes to noise. Includes the finest pyramid level (160x224,
    XLA-only: W > 128) to decide whether a two-lane-tile W<=256 Pallas
    variant is worth building."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from deepof_tpu.ops.warp import backward_warp

    key = jax.random.PRNGKey(0)
    n_inner = 20
    for (h, w) in [(40, 56), (80, 112), (160, 224)]:
        img = jax.random.uniform(key, (16, h, w, 3))
        flow = jax.random.uniform(key, (16, h, w, 2)) * 8 - 4
        impls = ("xla",) if w > 128 else ("xla", "pallas")
        if w > 128:
            # byte-bound or index-bound? the loss.gather_dtype decision
            def fwd16(i, fl):
                def body(f, _):
                    out = backward_warp(i.astype(jnp.bfloat16), f,
                                        impl="xla")
                    return f + 1e-30 * out.astype(jnp.float32).mean(), None
                return lax.scan(body, fl, None, length=n_inner)[0].sum()

            per = timeit(f"warp scan fwd xla-bf16 {h}x{w}", jax.jit(fwd16),
                         img, flow)
            print(f"{'  -> per-warp':44s} {per/n_inner*1e3:8.3f} ms",
                  flush=True)
        for impl in impls:
            def scan_fwd(i, fl, impl=impl):
                def body(f, _):
                    out = backward_warp(i, f, impl=impl)
                    # chain: next flow depends on this warp's output
                    # (1e-30 scale, not *0: XLA may fold mul-by-zero
                    # and DCE the warp — the sec_decomp lesson)
                    return f + 1e-30 * out.mean(), None
                return lax.scan(body, fl, None, length=n_inner)[0].sum()

            f = jax.jit(scan_fwd)
            per = timeit(f"warp scan fwd {impl} {h}x{w}", f, img, flow)
            print(f"{'  -> per-warp':44s} {per/n_inner*1e3:8.3f} ms",
                  flush=True)

            def scan_grad(i, fl, impl=impl):
                def body(f, _):
                    g = jax.grad(lambda q: backward_warp(
                        i, q, impl=impl).sum())(f)
                    return f + 1e-30 * g, None
                return lax.scan(body, fl, None, length=n_inner)[0].sum()

            g = jax.jit(scan_grad)
            per = timeit(f"warp scan grad {impl} {h}x{w}", g, img, flow)
            print(f"{'  -> per-grad':44s} {per/n_inner*1e3:8.3f} ms",
                  flush=True)


def sec_warp_sweep(cases=(((160, 224), (0.4, 5.0, 39.0, 79.0, 160.0)),
                          ((80, 112), (0.4, 1.0, 80.0))),
                   batch: int = 64) -> None:
    """Kernel time is linear in the row offsets the flow holds; the
    gather's does not depend on them. Times warp forward+flow-gradient
    (both kernels, as a loss level runs them) chained 8 deep inside one
    jit, for flows whose vertical part is uniform in [-a, a): a sweep of
    about 2a+2 rows, 2H-1 once a >= H. `auto` is the kernel under its
    per-launch `cond`. Also prints the largest error against XLA."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from deepof_tpu.ops.warp import backward_warp, row_sweep_lengths

    n_inner = 8
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)

    def both(impl):
        def f(i, fl):
            def body(f_, _):
                val, g = jax.value_and_grad(lambda q: jnp.sum(
                    backward_warp(i, q, impl=impl) ** 2))(f_)
                return f_ + 1e-30 * (g + val), None
            return lax.scan(body, fl, None, length=n_inner)[0].sum()
        return jax.jit(f)

    def one(impl):
        return jax.jit(lambda i, fl: jax.value_and_grad(lambda q: jnp.sum(
            backward_warp(i, q, impl=impl) ** 2))(fl))

    for (h, w), reaches in cases:
        img = jax.random.uniform(k1, (batch, h, w, 3))
        for a in reaches:
            flow = jnp.stack(
                [jax.random.uniform(k2, (batch, h, w), minval=-4., maxval=4.),
                 jax.random.uniform(k3, (batch, h, w), minval=-a, maxval=a)],
                axis=-1)
            rows = int(jnp.max(row_sweep_lengths(flow[..., 1])))
            (vx, gx) = one("xla")(img, flow)
            (vp, gp) = one("pallas")(img, flow)
            print(f"{h}x{w} sweep {rows}: pallas vs xla value rel "
                  f"{abs(float(vp - vx)) / abs(float(vx)):.2e}, flow-grad "
                  f"max abs {float(jnp.max(jnp.abs(gp - gx))):.2e} of "
                  f"{float(jnp.max(jnp.abs(gx))):.2e}", flush=True)
            for impl in ("pallas", "auto", "xla"):
                per = timeit(f"warp fwd+grad {impl} {h}x{w} sweep {rows}",
                             both(impl), img, flow, steps=3, windows=2)
                print(f"{'  -> per fwd+grad':44s} {per/n_inner*1e3:8.3f} ms",
                      flush=True)


def sec_attn(block_kvs=(512, 1024, 2048), s=4096, h=32, bq=512,
             interpret=False) -> None:
    """The latent attention's causal scores at one layer of
    `kanana2_30b_a3b_ep8.train_4k` (2 rows x 4096 positions, 32 heads,
    128+64 / 128, bfloat16, queries in blocks of 512): the XLA blocks
    against the fused kernels at each key block, forward and forward +
    backward with respect to all five operands, and the largest
    difference between the two paths' outputs and gradients. The keyword
    sizes are for a rehearsal off the chip (`interpret=True`)."""
    import jax
    import jax.numpy as jnp

    from deepof_tpu.ops.attention import xla_blocks_attention
    from deepof_tpu.ops.pallas.attention import fused_causal_attention

    b, dn, dr, dv, dt = 2, 128, 64, 128, jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    shapes = ((b, s, h, dn), (b, s, h, dr), (b, s, h, dn), (b, s, dr),
              (b, s, h, dv))
    ops = tuple(jax.random.normal(k, sh).astype(dt) for k, sh in zip(ks, shapes))
    scale = (dn + dr) ** -0.5

    def both(attend):
        fwd = jax.jit(lambda o: attend(*o))
        # the loss's weight is an operand too: a closed-over array would
        # be a 134 MB constant of the executable (and of its cache entry)
        grad = jax.jit(jax.grad(lambda o: jnp.sum(
            attend(*o).astype(jnp.float32) * o[4].astype(jnp.float32))))
        # timed: a scalar that every gradient feeds (cheap beside them)
        timed = jax.jit(lambda o: sum(jnp.sum(g.astype(jnp.float32))
                                      for g in grad(o)))
        return fwd, grad, timed

    paths = {"xla_blocks": both(lambda *o: xla_blocks_attention(
        *o, scale, bq, dt))}
    for bkv in block_kvs:
        paths[f"fused kv{bkv}"] = both(lambda *o, bkv=bkv: (
            fused_causal_attention(*o, scale, bq, bkv, interpret=interpret)))
    ref_out, ref_grad = (f(ops) for f in paths["xla_blocks"][:2])
    for name, (fwd, grad, timed) in paths.items():
        t_f = timeit(f"attn {name} fwd", fwd, ops, steps=5)
        t_g = timeit(f"attn {name} fwd+bwd", timed, ops, steps=5)
        err = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - r.astype(jnp.float32))))
               for a, r in zip((fwd(ops), *grad(ops)), (ref_out, *ref_grad))]
        print(f"attn {name}: step share 5 x (2 fwd + bwd) = "
              f"{5e3 * (t_f + t_g):.1f} ms; max |diff| to xla_blocks: out "
              f"{err[0]:.2e}, d(qn, qr, kn, kr, v) "
              + " ".join(f"{e:.2e}" for e in err[1:]), flush=True)


def sec_proj(families=("mla", "gqa"), configs=None, rows=None,
             interpret=False, inner=10) -> None:
    """The projection block of one attention layer of each language-model
    cell (`kanana2_30b_a3b_ep8.train_4k`: 2 rows x 4096 positions into qn,
    qr, kn, kr, v; `sdar_30b_a3b_ep8.train_bd_4k`: one doubled row of 8192
    into q, k, v; bfloat16, float32 masters), exactly as the layer writes
    it: the layer runs with its attention call replaced by a tap that
    keeps the operands, so the products, the elementwise chain and the
    layouts are the layer's own and nothing after them is. Both routes of
    `ops/attention.py::attention_route`'s `prep`, the XLA one steered by
    the backend's name: forward, forward + backward (cotangents handed in
    as the attention's backward hands them), ms a layer, and the largest
    difference between the routes' operands and gradients. The keywords
    are for a rehearsal off the chip (`interpret=True`, toy configs)."""
    import jax
    import jax.numpy as jnp

    from deepof_tpu.core.config import LMConfig, fill_lm_from_file
    from deepof_tpu.models.lm import layers
    from deepof_tpu.ops.attention import CAUSAL, Mask
    from deepof_tpu.ops.pallas import qk_prep as prep_mod

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs")
    cells = {  # layer, configuration, the call it taps, (rows, positions)
        "mla": (layers.MLA, "kanana2_30b_a3b_ep8.json", "causal_attention",
                (2, 4096)),
        "gqa": (layers.GQA, "sdar_30b_a3b_ep8.json", "grouped_attention",
                (1, 8192)),
    }
    dt = jnp.bfloat16
    backend = jax.default_backend
    real_prep = prep_mod.qk_prep
    for family in families:
        layer, file, attend, shape = cells[family]
        cfg = (configs or {}).get(family) or fill_lm_from_file(
            LMConfig(), os.path.join(root, file))
        b, s = (rows or {}).get(family, shape)
        mask = CAUSAL if family == "mla" else Mask(
            "block_diffusion", cfg.block_length, s // 2)
        module = layer(cfg, dt, mask)
        kh, kp, kc = jax.random.split(jax.random.PRNGKey(0), 3)
        h = jax.random.normal(kh, (b, s, cfg.hidden_size), jnp.float32)
        params = jax.jit(lambda k: module.init(k, h)["params"])(kp)

        def block(route):
            """(params, h) -> the attention's operands on `route`, as the
            layer hands them over ([b, s, h, d]; head-major on the fused
            route)."""
            def operands(p, h):
                taken = []

                def tap(*a, head_major=False, **kw):
                    ops = a[:5 if family == "mla" else 3]
                    taken.append(ops)
                    rows_, heads = (0, 1) if head_major else (0, 2)
                    return jnp.zeros((ops[0].shape[rows_], s,
                                      ops[0].shape[heads], ops[-1].shape[-1]), dt)

                held = getattr(layers, attend)
                setattr(layers, attend, tap)
                jax.default_backend = (lambda: "cpu") if route == "xla" else backend
                prep_mod.qk_prep = (lambda *a, **kw: real_prep(
                    *a, interpret=True, **kw)) if interpret else real_prep
                try:
                    module.apply({"params": p}, h)
                finally:
                    setattr(layers, attend, held)
                    jax.default_backend = backend
                    prep_mod.qk_prep = real_prep
                return taken[0]

            return operands

        def looped(one):
            """`inner` runs of `one(h, params, cts) -> scalar` chained through
            `h` inside ONE program: a layer's block is 2-9 ms, a dispatch
            with its perturbed leaves 2 ms of host time."""
            def run(a):
                def body(_, c):
                    return one(a[1] + (c * 0).astype(a[1].dtype), a[0], a[2])
                return jax.lax.fori_loop(0, inner, body, jnp.zeros(()))
            return jax.jit(run)

        # every result whole behind a barrier, then one element of each: a
        # sum or a slice of a product's output is rewritten by XLA into a
        # product of sums or of slices, and the probe would time that
        whole = lambda t: sum(  # noqa: E731
            o.ravel()[0].astype(jnp.float32) for o in
            jax.tree_util.tree_leaves(jax.lax.optimization_barrier(t)))
        outs, grads = {}, {}
        for route in ("xla", "fused"):
            ops_of = block(route)
            outs[route] = jax.jit(ops_of)(params, h)
            cts = tuple(jax.random.normal(k, o.shape).astype(dt) for k, o in
                        zip(jax.random.split(kc, len(outs[route])), outs[route]))
            grads[route] = jax.jit(lambda p, hh, ct, f=ops_of: jax.vjp(
                f, p, hh)[1](ct))

            def both(hh, p, ct, f=ops_of):
                # the forward's results too: alone, the gradients of
                # products need no forward, and this would time a backward
                res, back = jax.vjp(f, p, hh)
                return whole((res, back(ct)))

            t_f = timeit(f"proj {family} {route} fwd x{inner}",
                         looped(lambda hh, p, _, f=ops_of: whole(f(p, hh))),
                         (params, h, cts), steps=2, windows=2) / inner
            t_g = timeit(f"proj {family} {route} fwd+bwd x{inner}", looped(both),
                         (params, h, cts), steps=2, windows=2) / inner
            print(f"proj {family} {route}: a layer fwd {1e3 * t_f:.2f} ms, "
                  f"fwd+bwd {1e3 * t_g:.2f} ms; a step's 5 x (2 fwd + bwd) = "
                  f"{5e3 * (t_f + t_g):.1f} ms", flush=True)
        # the routes compared on the SAME cotangents: the xla route's, laid
        # out head-major for the fused one
        major = lambda t: tuple(  # noqa: E731
            a if a.ndim == 3 else jnp.swapaxes(a, 1, 2) for a in t)
        cts = tuple(jax.random.normal(k, o.shape).astype(dt) for k, o in
                    zip(jax.random.split(kc, len(outs["xla"])), outs["xla"]))
        gap = lambda a, r: float(jnp.max(jnp.abs(  # noqa: E731
            a.astype(jnp.float32) - r.astype(jnp.float32))))
        print(f"proj {family}: max |fused - xla| operands " + " ".join(
            f"{gap(a, r):.2e}" for a, r in zip(outs["fused"], major(outs["xla"]))),
            flush=True)
        gx = grads["xla"](params, h, cts)
        gf = grads["fused"](params, h, major(cts))
        print(f"proj {family}: max |fused - xla| / max |xla| gradients " + " ".join(
            f"{jax.tree_util.keystr(k)}={gap(a, r) / max(float(jnp.max(jnp.abs(r))), 1e-30):.2e}"
            for (k, r), a in zip(jax.tree_util.tree_leaves_with_path(gx),
                                 jax.tree_util.tree_leaves(gf))), flush=True)


def sec_decomp() -> None:
    import jax
    import jax.numpy as jnp

    from deepof_tpu.losses.pyramid import lrn_normalize, preprocess, pyramid_loss
    from deepof_tpu.train.step import model_losses

    cfg, mesh, ds, model, state, step, b = bench_mod.headline_setup()
    B = cfg.data.batch_size

    src = preprocess(b["source"], ds.mean)
    tgt = preprocess(b["target"], ds.mean)
    pair = jnp.concatenate([src, tgt], -1).astype(jnp.bfloat16)

    fwd_sum = jax.jit(lambda p, x: sum(
        f.astype(jnp.float32).sum() for f in model.apply({"params": p}, x)))
    timeit("inception fwd only", fwd_sum, state.params, pair, items=B)

    fwd_loss = jax.jit(lambda p, bb: model_losses(
        model, p, bb, ds.mean, cfg.loss, compute_dtype=jnp.bfloat16)[0])
    timeit("inception fwd+loss", fwd_loss, state.params, b, items=B)

    def _fwd_loss_grad(p, bb):
        val, grads = jax.value_and_grad(
            lambda q: model_losses(model, q, bb, ds.mean, cfg.loss,
                                   compute_dtype=jnp.bfloat16)[0])(p)
        # keep every grad leaf alive: returning only `val` lets XLA DCE
        # the entire backward (caught in r03 — this line then measured
        # identical to fwd+loss)
        # 1e-30 scale (not *0: XLA may fold mul-by-zero and DCE again)
        return val + 1e-30 * sum(jnp.sum(g)
                                 for g in jax.tree_util.tree_leaves(grads))

    fwd_loss_grad = jax.jit(_fwd_loss_grad)
    timeit("inception fwd+loss+bwd", fwd_loss_grad, state.params, b, items=B)

    per, state = _time_full_step(step, state, b)
    print(f"{'full train step':44s} {per*1e3:8.2f} ms  "
          f"{B/per:9.1f} items/s", flush=True)

    flows = jax.jit(lambda p, x: model.apply({"params": p}, x))(state.params, pair)
    flows = [f.astype(jnp.float32) for f in flows]
    li, lo = lrn_normalize(src), lrn_normalize(tgt)
    loss_alone = jax.jit(lambda fl, a, o: pyramid_loss(
        list(zip(fl, model.flow_scales)), a, o, cfg.loss)[0])
    timeit("pyramid loss fwd alone", loss_alone, flows, li, lo, items=B)

    loss_grad_alone = jax.jit(lambda fl, a, o: sum(
        x.sum() for x in jax.grad(lambda q: pyramid_loss(
            list(zip(q, model.flow_scales)), a, o, cfg.loss)[0])(fl)))
    timeit("pyramid loss grad (wrt flows)", loss_grad_alone, flows, li, lo,
           items=B)


def sec_batch() -> None:
    # throughput curve: same model, growing batch; is the chip compute-
    # bound (flat items/s => yes) or dispatch/HBM-bound (rising)?
    # Two points only: each batch size is a distinct minutes-long
    # compile, and the decision (does 96 beat 16?) needs just the ends.
    for batch in (16, 96):
        cfg, mesh, ds, model, state, step, b = bench_mod.headline_setup(
            batch=batch)
        per, _ = _time_full_step(step, state, b, windows=2)
        print(f"{'batch sweep b=%d' % batch:44s} {per*1e3:8.2f} ms  "
              f"{batch/per:9.1f} items/s", flush=True)


def sec_headline() -> None:
    res = bench_mod.bench()
    print("bench:", {k: round(v, 2) if isinstance(v, float) else v
                     for k, v in res.items()}, flush=True)


def _corr_place_by_select(n):
    """The select-built alternative to `ops/pallas/corr.py::_place_block`
    (the same block of P-bar, the same arguments): the window's n diagonals
    turned to lanes 0..n-1 by one plain roll, then each put on its own
    diagonal of the block's columns (from `base` on) by a select of its
    lane broadcast over the row, n selects a block where the wired builder
    has one strided roll."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental.pallas import tpu as pltpu

    from deepof_tpu.ops.pallas.corr import _fold

    def place(win, sel, lo, base):
        f = _fold(win, sel)
        rows, lanes = f.shape
        f = pltpu.roll(f, (lanes - lo) % lanes, 1)  # diagonal j on lane j
        d = (lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
             - lax.broadcasted_iota(jnp.int32, (rows, lanes), 0) - base)
        out = jnp.zeros_like(f)
        for j in range(n):
            out = jnp.where(d == j, f[:, j:j + 1], out)
        return out

    return place


def sec_corr(tiles=(8, 16), groups=(1, 2, 4)) -> None:
    """The correlation at `flownet_c_chairs.train`'s shapes (384x512 input
    -> conv3 features 48x64x256, 441 maps, batch 64, bfloat16): the
    forward kernel alone and as the public route, the backward kernel
    alone, and forward + backward as the step takes them; each whole
    result behind `lax.optimization_barrier`, so XLA computes all of it,
    each kernel's TOP/s and share of its roofline by the benchmark's own
    counts (`benchmark/kernels/corr.py`, `corr_bwd.py`; peaks of
    `benchmark/harness/peaks.py`). Then the backward's block builders: the
    wired strided roll against n selects (`_corr_place_by_select`), each
    at the row tiles and product groups given (the module's `_TILE_H` /
    `_GROUP`, which the forward shares, set for the table only). Last,
    both directions' error on one image against the XLA sweep (its
    autodiff for the backward) in float32 on the same bfloat16 values, as
    a share of the reference's largest element."""
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from benchmark.harness.peaks import peaks_for
    from benchmark.kernels import corr as count_fwd, corr_bwd as count_bwd
    from benchmark.kernels.roofline import least_seconds
    from deepof_tpu.ops.corr import correlation
    from deepof_tpu.ops.pallas import corr as K

    shape = b, h, w, c = (64, 48, 64, 256)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    f1, f2 = (jax.random.normal(k, shape, jnp.bfloat16) for k in keys[:2])
    g = jax.random.normal(keys[2], shape[:3] + (441,), jnp.bfloat16)
    peaks = peaks_for(jax.devices()[0].device_kind)
    sizes = dict(b=b, h=h, w=w, c=c, max_disp=20, stride=2)

    def first(*outs):
        return sum(x.ravel()[0].astype(jnp.float32)
                   for x in lax.optimization_barrier(outs))

    def rate(per, count):
        least, bound = least_seconds(count, peaks)
        print(f"  {count['ops'] / per / 1e12:.2f} TOP/s, "
              f"{100 * least / per:.2f}% of its roofline ({bound}-bound, "
              f"{least * 1e3:.4f} ms least)", flush=True)

    def bwd_kernel():
        return jax.jit(lambda a, b, ct: first(
            *K._pallas_corr_bwd(a, b, ct, 20, 2, False)))

    per = timeit(f"corr fwd kernel {b}x{h}x{w}x{c}", jax.jit(
        lambda a, b: first(K._pallas_corr_fwd(a, b, 20, 2, False))), f1, f2)
    rate(per, count_fwd.forward(**sizes))
    timeit("corr fwd pallas (public route)", jax.jit(
        lambda a, b: first(correlation(a, b, impl="pallas"))), f1, f2)
    per = timeit("corr bwd kernel", bwd_kernel(), f1, f2, g)
    rate(per, count_bwd.backward(**sizes))

    def both(a, b, ct):
        out, vjp = jax.vjp(lambda p, q: correlation(p, q, impl="pallas"), a, b)
        return first(out, *vjp(ct))

    timeit("corr fwd + bwd pallas", jax.jit(both), f1, f2, g)

    builders = {"roll": K._place_block, "select": _corr_place_by_select(21)}
    for name, place in builders.items():
        for tile in tiles:
            for group in groups:
                with mock.patch.multiple(K, _TILE_H=tile, _GROUP=group,
                                         _place_block=place):
                    timeit(f"corr bwd {name:6s} tile {tile:2d} group {group}",
                           bwd_kernel(), f1, f2, g)
    # what the kernel costs with each window loaded and nothing placed
    # (P-bar all zeros; no select, fold or roll): its products,
    # accumulators, permutations and f2's layout
    with mock.patch.object(K, "_place_block",
                           lambda win, sel, lo, base: 0.0 * win[:, :-128]):
        timeit(f"corr bwd none   tile {K._TILE_H:2d} group {K._GROUP}",
               bwd_kernel(), f1, f2, g)

    a, b, ct = f1[:1], f2[:1], g[:1]
    ref = [x.astype(jnp.float32) for x in (a, b, ct)]
    got = jax.jit(lambda p, q, c: (correlation(p, q, impl="pallas"),)
                  + jax.vjp(lambda x, y: correlation(x, y, impl="pallas"),
                            p, q)[1](c))(a, b, ct)
    want = jax.jit(lambda p, q, c: (correlation(p, q, impl="xla"),)
                   + jax.vjp(lambda x, y: correlation(x, y, impl="xla"),
                             p, q)[1](c))(*ref)
    for name, x, y in zip(("corr", "df1", "df2"), got, want):
        x, y = np.asarray(x, np.float32), np.asarray(y)
        print(f"  {name} max |err| / max |ref| "
              f"{np.abs(x - y).max() / np.abs(y).max():.3e}", flush=True)


def sec_multiframe() -> None:
    """Sintel-shaped multi-frame step: Inception-v3, T=10 volume
    (B,224,480,30), 18 flow channels, batch 4 — the reference Sintel
    recipe (`deepOF.py:13-16`, crop 224x480, SURVEY §2.2). Built through
    bench.headline_setup so it shares every other headline setting."""
    t, batch = 10, 4
    cfg, mesh, ds, model, state, step, b = bench_mod.headline_setup(
        batch=batch, image_size=(224, 480), time_step=t,
        weights=(16, 8, 4, 4, 2, 1))
    per, _ = _time_full_step(step, state, b, steps=6, windows=2)
    pairs = batch * (t - 1)  # T-1 consecutive warped pairs per item
    print(f"{'sintel T=10 full step b=4 224x480':44s} {per*1e3:8.2f} ms  "
          f"{batch/per:9.1f} items/s  {pairs/per:9.1f} pairs/s", flush=True)


# Execution order: the headline + its MFU fields first, then
# calibration context, then the decision sections (decomp/warpscan/warpsweep/
# corr), then sweeps; the per-call warp table is superseded by warpscan
# and runs last.
SECTIONS = {
    "headline": sec_headline,
    "calib": sec_calib,
    "decomp": sec_decomp,
    "warpscan": sec_warp_scan,
    "warpsweep": sec_warp_sweep,
    "corr": sec_corr,
    "batch": sec_batch,
    "multiframe": sec_multiframe,
    "warp": sec_warp,
    "attn": sec_attn,
    "proj": sec_proj,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated section names (default: all, in "
                         f"order {','.join(SECTIONS)})")
    args = ap.parse_args()
    names = list(SECTIONS) if not args.only else args.only.split(",")
    unknown = [n for n in names if n not in SECTIONS]
    if unknown:
        raise SystemExit(f"unknown sections {unknown}; have {list(SECTIONS)}")
    print("devices:", bench_mod._require_tpu(), flush=True)
    for n in names:  # a section that raises ends the run non-zero
        print(f"--- section {n}", flush=True)
        t0 = time.perf_counter()
        SECTIONS[n]()
        print(f"--- section {n} done in {time.perf_counter() - t0:.1f}s",
              flush=True)


if __name__ == "__main__":
    main()
