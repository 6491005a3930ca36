"""Pallas TPU kernels for the framework's hot ops.

Kernel inventory (and why each op is/isn't a kernel):

  - `corr.py` — FlowNet-C correlation / cost volume. The (2K+1)^2
    displacement sweep re-reads the second feature map hundreds of times;
    the XLA `dynamic_slice` formulation pays HBM traffic per displacement,
    while the kernel holds one haloed row-window of f2 in VMEM and sweeps
    all displacements from on-chip memory. Its backward (`corr_bwd`) is
    the forward's transpose (its blocked MXU products, the cotangent put
    where the forward reads its diagonals, df2 accumulated in VMEM); it
    replaced an XLA scan that was nine tenths of the FlowNet-C step (PR
    40), then a serial band loop (PR 43).

  - `warp.py` — the bilinear backward warp and its flow gradient at
    every pyramid level of up to two lane tiles (W <= 256), as a sweep
    over the row offsets the flow field holds (Mosaic has no arbitrary 2D
    gather; border clipping bounds the offsets, so the sweep is exact for
    any flow). Wider images stay an XLA gather (`ops/warp.py`).

  - `attention.py` — the latent-attention layer's causal attention
    (`models/lm`), forward and backward: queries and keys both blocked, an
    online softmax, a tile's scores only ever in VMEM, tiles above the
    diagonal skipped. XLA's blocked formulation (`ops/attention.py`, the
    path off the chip) writes float32 score blocks to HBM four times a
    step; the kernels took the language-model cell's `mla_scores` from
    523 to 63 ms a step (PR 32).

  - `qk_prep.py` — the pass between an attention layer's projections and
    those kernels (`models/lm`), forward and backward: per-head norm,
    rotary positions, the cast and the head-major layout in one read and
    one write a tensor. XLA's elementwise chain (float32 norm and
    rotation, slices, the rotation's gathers, a pad, the cast, layout
    copies) took a query through HBM five times (PR 38).

  - `ssd.py` — the Mamba-2 layer's state-space scan on the doubled row
    (`models/lm`, `ops/ssm.py`'s `kernel` path), forward and backward:
    a chunk of both copies a grid step, its decay and score matrices
    only in VMEM, the clean state carried from chunk to chunk in VMEM
    scratch. XLA's chunked form (`ops/ssm.py`, off the chip) writes each
    chunk's [heads, 128, 128] float32 matrices to HBM and reads them
    back, three of them for a noised chunk, in the forward, the
    recomputation and the transpose: on a v5e 76 ms of the nemotron
    cell's 413 ms step, where the kernels take 10.

  The expert layer's grouped products are XLA's own Mosaic fusion for
  `lax.ragged_dot`, not a kernel of this package.

Under a mesh every kernel runs per batch shard through
`parallel.spatial.shard_over_batch`.

Every kernel is called through `pallas_call` below (the kernel files
take it and `pl` from here): its `name=` is the HLO instruction's (so a
profile's events and the benchmark's rooflines find it), and the call,
where the body is traced into a jaxpr, is a span `kernel_trace` of the
program's trace. Mosaic's lowering of that jaxpr to MLIR happens later,
inside the step's own `jax_lower`.
"""

from jax.experimental import pallas as pl

from ...obs import trace as obs_trace


def pallas_call(kernel, *, name: str, **kwargs):
    """`pl.pallas_call(kernel, name=name, **kwargs)`, whose call runs
    inside a span `kernel_trace` with `kernel=name`. The span stands
    around the call, not inside the body: the body's own lines stay the
    innermost frame of every location in the Mosaic payload, so no
    payload and no compile-cache key changes. With no tracer installed a
    call costs one global read; a step's compiled program calls nothing
    here."""
    call = pl.pallas_call(kernel, name=name, **kwargs)

    def traced(*args):
        with obs_trace.span("kernel_trace", kernel=name):
            return call(*args)

    return traced


from .corr import correlation_pallas  # noqa: E402 - needs pallas_call

__all__ = ["correlation_pallas", "pallas_call"]
