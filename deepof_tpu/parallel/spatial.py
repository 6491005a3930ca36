"""Spatial context parallelism + temporal pair parallelism.

The reference is single-GPU (`tf.device('/gpu:0')`, `flyingChairsTrain.py:99`)
with no parallelism of any kind; these are the TPU-native long-context
equivalents (SURVEY.md §5.7):

  - **Spatial CP** ("spatial" mesh axis): image batches are sharded over H
    with `P(("data",), "spatial")`. Convolutions under `jit` are then
    spatially partitioned by GSPMD, which inserts the boundary halo
    exchanges itself — the idiomatic formulation of the ring/halo pattern
    (annotate shardings, let XLA place collectives on ICI). This is what
    makes high-resolution flow (e.g. Sintel 436x1024 and beyond) scale
    past one chip's HBM.

  - **Explicit halo exchange** (`halo_exchange`): the `lax.ppermute`
    neighbor ring, for custom ops inside `shard_map` where GSPMD cannot
    infer the halo (e.g. windowed ops with data-dependent reach).

  - **Temporal pair parallelism** ("time" mesh axis): the Sintel T-frame
    volume loss warps T-1 consecutive pairs independently
    (`sintelWrapFlow.py:539-577` semantics); folding the pair axis into
    batch and sharding it over ("data", "time") spreads the warp/
    Charbonnier work across the mesh.
"""

from __future__ import annotations

import contextlib
import math
import warnings

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Trace-time mesh stack: `jax.sharding.get_abstract_mesh()` is EMPTY inside
# plain `jax.jit` tracing (even with in_shardings), so sharding constraints
# need the concrete mesh threaded to them explicitly. The step builders wrap
# the loss computation in `mesh_context(mesh)`; ops deep in the call tree
# (e.g. the folded pair axis inside `backward_warp_volume`) read it via
# `current_mesh()` at trace time.
_MESH_STACK: list[Mesh] = []


@contextlib.contextmanager
def mesh_context(mesh: Mesh | None):
    if mesh is None:
        yield
        return
    _MESH_STACK.append(mesh)
    try:
        yield
    finally:
        _MESH_STACK.pop()


def current_mesh() -> Mesh | None:
    return _MESH_STACK[-1] if _MESH_STACK else None


def image_sharding(mesh: Mesh) -> NamedSharding:
    """(B, H, W, C) batches: batch over "data", height over "spatial"."""
    return NamedSharding(mesh, P("data", "spatial"))


def batch_spec(mesh: Mesh) -> P:
    """PartitionSpec for image batches on this mesh (H sharded only when
    the spatial axis is populated)."""
    if mesh.shape.get("spatial", 1) > 1:
        return P("data", "spatial")
    return P("data")


# Spatial CP gradient-safety contract: every pyramid level must keep
# >= MIN_ROWS_PER_SHARD rows per spatial shard. Root cause (minimal repro:
# tools/halo_grad_repro.py): when a stride-2 SAME conv chain reaches a
# level with FEWER than 2 rows per shard, XLA's SPMD partitioner emits a
# degenerate backward halo exchange that mis-scales the input cotangent —
# every upstream conv's gradient comes back multiplied by a constant (x4
# at spatial=2 with a 1-row/shard level; x2 in some sub-row collapse
# regimes; exact factor depends on GSPMD's level-by-level partitioning
# choices) while downstream layers stay correct. At >= 2 rows per shard
# the backward is exact in every configuration tested (spatial 2 and 4,
# depths 2-5). The guard is therefore derived per model from its real
# downsample factor, not a blanket constant.
MIN_ROWS_PER_SHARD = 2


def min_spatial_height(max_downsample: int, spatial: int) -> int:
    """Smallest input H for which spatial CP is gradient-safe for a model
    whose deepest level is H / max_downsample: that level must keep
    MIN_ROWS_PER_SHARD rows on each of `spatial` shards."""
    return MIN_ROWS_PER_SHARD * max_downsample * spatial


def spatial_cp_active(h: int, max_downsample: int, spatial: int) -> bool:
    """True iff sharding H over `spatial` is gradient-safe for a model
    downsampling by `max_downsample` (stride-2 SAME chain: each level is
    ceil(previous/2)).

    Probed-exact configurations (tools/halo_grad_repro.py) all satisfy,
    probed-broken all violate: (a) the deepest level keeps >= 2 average
    rows per shard, and (b) GSPMD's ceil-partition of the deepest level
    leaves no shard with zero real rows (e.g. H=520 at downsample 64,
    spatial=4: deepest ceil-chain gives 9 rows -> shards 3,3,3,0 — the
    padded-empty shard re-enters the degenerate-halo regime and is
    refused even though 9 >= 2*4 holds on average).
    """
    if h % spatial:
        return False
    d = h
    for _ in range(max(max_downsample.bit_length() - 1, 0)):
        d = -(-d // 2)
    if d < MIN_ROWS_PER_SHARD * spatial:
        return False
    return d - (spatial - 1) * (-(-d // spatial)) > 0


def constrain_batch(batch: dict, mesh: Mesh | None = None,
                    max_downsample: int = 64) -> dict:
    """Apply the spatial-CP sharding constraint to every image-like leaf
    (rank >= 4: (B, H, W, C) images, volumes, GT flows) of a batch dict.

    With a mesh whose "spatial" axis is populated, GSPMD reshards H over it
    and spatially partitions all downstream convolutions (halo exchanges
    inserted by the compiler). No-op otherwise, when H does not divide, or
    when H is below `min_spatial_height` for the model's downsample factor
    (the gradient-safety fence above — and at low res spatial CP would
    only lose to pure DP anyway).
    """
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None or mesh.shape.get("spatial", 1) <= 1:
        return batch
    spatial = mesh.shape["spatial"]
    sharding = NamedSharding(mesh, P(("data",), "spatial"))

    def put(v):
        # Uneven deep levels are fine (probed: 5 rows over 2 shards, 10
        # over 4 with a 1-real-row last shard — all exact); the precise
        # gradient-safety gate lives in `spatial_cp_active`.
        if (getattr(v, "ndim", 0) >= 4
                and spatial_cp_active(v.shape[1], max_downsample, spatial)):
            return lax.with_sharding_constraint(v, sharding)
        return v

    return {k: put(v) for k, v in batch.items()}


def pair_axes(mesh: Mesh | None, folded: int) -> tuple[str, ...]:
    """Mesh axes a folded (B*(T-1), ...) pair axis is sharded over:
    ("data", "time") when the time axis is populated and data*time divides
    it, else the batch's own ("data",)."""
    if mesh is None or mesh.shape.get("time", 1) <= 1:
        return ("data",)
    if folded % (mesh.shape["time"] * mesh.shape.get("data", 1)):
        return ("data",)
    return ("data", "time")


def pair_axis_constraint(x: jnp.ndarray) -> jnp.ndarray:
    """Constrain a (B*(T-1), H, W, C) folded pair-axis array to shard over
    ("data", "time") so the T-1 per-pair warps run pair-parallel.

    No-op outside a `mesh_context` or when the time axis is unpopulated or
    does not divide the folded axis.
    """
    mesh = current_mesh()
    if pair_axes(mesh, x.shape[0]) != ("data", "time"):
        return x
    return lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(("data", "time"),)))


def shard_over_batch(fn, mesh: Mesh | None, batch: int,
                     axes: tuple[str, ...] = ("data",), whole: int = 0):
    """Run `fn` ((B, ...) arrays in, one (B, ...) array or a tuple of them
    out) once per batch shard; its last `whole` operands have no batch axis
    (a table, a learned scale) and every shard sees all of each.

    The one multi-device form of the Pallas kernels: GSPMD cannot see
    inside a `pallas_call` and Mosaic refuses to be partitioned
    automatically, so the kernel wrappers hand the launcher here with the
    mesh of the enclosing `mesh_context`. The kernels are independent per
    batch element but need full H/W/C per shard (row sweep, displacement
    window), so only the leading axis is split, over `axes` — the axes the
    CALLER shards that axis over: "data" for a batch, `pair_axes` for the
    folded pair axis of `backward_warp_volume`. Naming them keeps the
    operands where they are; a split guessed from divisibility would
    reshard a data-sharded batch over "time" and gather it back. Every
    other mesh axis sees a replica. A leading axis `axes` do not divide
    (a lone image under a mesh) runs whole on every device, and says so.
    No mesh: `fn` itself (single-device jit, eager tests).
    """
    if mesh is None:
        return fn
    shards = math.prod(mesh.shape.get(a, 1) for a in axes)
    spec = P(axes)
    if batch % shards:
        spec = P()
        warnings.warn(
            f"Pallas kernel: leading axis {batch} is not divisible by mesh "
            f"axes {axes} = {shards}; every device runs the whole batch",
            stacklevel=2)
    if not whole:
        return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                             check_vma=False)
    return lambda *a: jax.shard_map(
        fn, mesh=mesh, in_specs=(spec,) * (len(a) - whole) + (P(),) * whole,
        out_specs=spec, check_vma=False)(*a)


def halo_exchange(x: jnp.ndarray, halo: int, axis_name: str = "spatial",
                  axis: int = 0) -> jnp.ndarray:
    """Pad a per-shard block with `halo` rows from each ring neighbor.

    Inside `shard_map` over `axis_name`: sends this shard's boundary rows
    to both neighbors via two `lax.ppermute` rings (the ICI-neighbor
    pattern) and concatenates the received halos. Edge shards receive
    zeros (clip-at-border ops should clamp indices instead of reading the
    zero halo).

    x: (..., H_shard, ...) -> (..., H_shard + 2*halo, ...) along `axis`.
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)

    def take(arr, sl):
        ix = [slice(None)] * arr.ndim
        ix[axis] = sl
        return arr[tuple(ix)]

    top = take(x, slice(0, halo))  # first rows -> previous neighbor
    bot = take(x, slice(x.shape[axis] - halo, x.shape[axis]))

    fwd = [(i, (i + 1) % n) for i in range(n)]  # bottom rows travel down
    bwd = [(i, (i - 1) % n) for i in range(n)]
    from_prev = lax.ppermute(bot, axis_name, fwd)  # neighbor above's bottom
    from_next = lax.ppermute(top, axis_name, bwd)  # neighbor below's top

    zero = jnp.zeros_like(top)
    from_prev = jnp.where(idx == 0, zero, from_prev)  # ring wrap -> zeros
    from_next = jnp.where(idx == n - 1, zero, from_next)
    return jnp.concatenate([from_prev, x, from_next], axis=axis)
