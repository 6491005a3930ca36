"""Correlation / cost-volume op for FlowNet-C.

New capability (no reference implementation; spec from the FlowNet paper,
arXiv:1504.06852 §3: multiplicative patch comparison): for displacements
(dy, dx) on a (2K+1)x(2K+1) grid with stride `stride` where K = max_disp //
stride,

    corr[b, y, x, i] = mean_c f1[b, y, x, c] * f2[b, y+dy_i, x+dx_i, c]

out-of-range f2 positions contribute zero. Implemented as a `vmap` over the
displacement grid with `dynamic_slice` into a zero-padded f2 — static
shapes, data-parallel across displacements so XLA can fuse/parallelize (a
`lax.scan` here would serialize the 441 steps). The output is (n*n, B, H, W)
either way, so peak memory is unchanged. This is the route off the chip and
the tests' reference; on a TPU the forward and its backward are the Mosaic
kernels of `ops/pallas/corr.py`. Both routes run under `jax.named_scope("corr")`,
so a profile finds the correlation, forward and transposed, by that scope.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def correlation(
    f1: jnp.ndarray,
    f2: jnp.ndarray,
    max_disp: int = 20,
    stride: int = 2,
    impl: str = "auto",
) -> jnp.ndarray:
    """f1, f2: (B, H, W, C) -> (B, H, W, (2K+1)**2), K = max_disp // stride.

    impl: "auto" picks the fused Pallas kernel on TPU (one HBM read of f2
    instead of one per displacement) and the XLA sweep elsewhere.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    with jax.named_scope("corr"):
        if impl == "pallas":
            from .pallas.corr import correlation_pallas

            return correlation_pallas(f1, f2, max_disp, stride)
        b, h, w, c = f1.shape
        k = max_disp // stride
        n = 2 * k + 1
        pad = k * stride
        f2p = jnp.pad(f2, ((0, 0), (pad, pad), (pad, pad), (0, 0)))

        offsets = jnp.arange(n) * stride  # dy/dx offsets into the padded array
        dydx = jnp.stack(jnp.meshgrid(offsets, offsets, indexing="ij"), -1).reshape(-1, 2)

        def one(off):
            sl = lax.dynamic_slice(f2p, (0, off[0], off[1], 0), (b, h, w, c))
            return jnp.mean(f1 * sl, axis=-1)

        maps = jax.vmap(one)(dydx)  # (n*n, B, H, W)
        return jnp.moveaxis(maps, 0, -1)


def correlation_oracle(f1, f2, max_disp=20, stride=2):
    """Slow numpy oracle for tests."""
    import numpy as np

    b, h, w, c = f1.shape
    k = max_disp // stride
    n = 2 * k + 1
    out = np.zeros((b, h, w, n * n), f1.dtype)
    for i, dy in enumerate(range(-k * stride, k * stride + 1, stride)):
        for j, dx in enumerate(range(-k * stride, k * stride + 1, stride)):
            for y in range(h):
                for x in range(w):
                    yy, xx = y + dy, x + dx
                    if 0 <= yy < h and 0 <= xx < w:
                        out[:, y, x, i * n + j] = (f1[:, y, x] * f2[:, yy, xx]).mean(-1)
    return out
