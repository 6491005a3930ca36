"""Pallas TPU kernels for the framework's hot ops.

Kernel inventory (and why each op is/isn't a kernel):

  - `corr.py` — FlowNet-C correlation / cost volume. The (2K+1)^2
    displacement sweep re-reads the second feature map hundreds of times;
    the XLA `dynamic_slice` formulation pays HBM traffic per displacement,
    while the kernel holds one haloed row-window of f2 in VMEM and sweeps
    all displacements from on-chip memory.

  - `warp.py` — the bilinear backward warp and its flow gradient at the
    coarse pyramid levels (W <= 128: one lane register), as a bounded row
    sweep. Fine levels stay an XLA gather (`ops/warp.py`): flow magnitude
    is unbounded, so windowed VMEM loads cannot be sized statically
    without changing semantics, and Mosaic has no arbitrary 2D gather.

Under a mesh every kernel runs per batch shard through
`parallel.spatial.shard_over_batch`.
"""

from .corr import correlation_pallas

__all__ = ["correlation_pallas"]
