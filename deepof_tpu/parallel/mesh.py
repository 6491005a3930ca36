"""Named device meshes for pjit sharding.

Axes (SURVEY.md §5.8 build plan):
  - "data":    batch/data parallelism — gradients all-reduce over ICI;
  - "spatial": context parallelism over image height (halo exchange);
  - "time":    Sintel temporal pair parallelism (T-1 independent pair
               losses).

Multi-host: call `jax.distributed.initialize` before `build_mesh`; the mesh
uses the global device list, so the "data" axis spans hosts over DCN while
"spatial"/"time" should stay intra-slice (ICI).
"""

from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.config import MeshConfig

AXES = ("data", "spatial", "time")


def build_mesh(cfg: MeshConfig | None = None, devices=None) -> Mesh:
    """Build a (data, spatial, time) mesh over `devices` (default: all).

    cfg.data == -1 means "all remaining devices" after spatial/time are
    allocated.
    """
    cfg = cfg or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    spatial, time = max(cfg.spatial, 1), max(cfg.time, 1)
    if n % (spatial * time):
        raise ValueError(
            f"{n} devices not divisible by spatial*time={spatial * time}")
    data = n // (spatial * time) if cfg.data == -1 else cfg.data
    if data * spatial * time != n:
        raise ValueError(
            f"mesh {data}x{spatial}x{time} != {n} devices")
    arr = np.asarray(devices).reshape(data, spatial, time)
    return Mesh(arr, AXES)


def local_mesh(n: int | None = None) -> Mesh:
    """Pure-data-parallel mesh over the first n devices (test helper)."""
    devices = jax.devices()[: n or len(jax.devices())]
    return build_mesh(MeshConfig(), devices)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (batch) axis over "data"; replicate the rest."""
    return NamedSharding(mesh, P("data"))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def process_data_coords(mesh: Mesh) -> list[int]:
    """Sorted "data"-axis coordinates with devices addressable from this
    process (single-host: all of them)."""
    local = set(jax.local_devices())
    arr = mesh.devices
    return sorted(d for d in range(arr.shape[0])
                  if any(dev in local for dev in arr[d].flat))


def local_batch_rows(mesh: Mesh, global_batch: int) -> tuple[int, list[int]]:
    """(local_batch_size, owned global row indices) for this process under
    `batch_sharding`: P("data") places contiguous row blocks in data-axis
    coordinate order, so process-local rows are the blocks of its coords.

    When a data coordinate's devices span several processes those processes
    are *replicas* of that batch shard and must supply identical data
    (jax's make_array contract) — `process_seed` makes their host rng
    streams identical. The one unsupported layout is a process owning
    several coords of which only some span processes (rows would differ
    between the replica peers): rejected explicitly.
    """
    data = mesh.shape["data"]
    if global_batch % data:
        raise ValueError(f"global batch {global_batch} not divisible by "
                         f"data axis {data}")
    per = global_batch // data
    coords = process_data_coords(mesh)
    local = set(jax.local_devices())
    spans = [d for d in coords
             if any(dev not in local for dev in mesh.devices[d].flat)]
    if spans and len(coords) > 1:
        raise ValueError(
            f"data coords {spans} span processes while this process owns "
            f"{coords}: replica peers would load different rows. Pick a "
            "mesh where spatial*time divides the per-host device count")
    rows = [r for d in coords for r in range(d * per, (d + 1) * per)]
    return len(rows), rows


def elastic_stream_seed(seed: int, host_index: int, num_hosts: int,
                        generation: int, start_step: int) -> np.ndarray:
    """Base seed of one elastic trainer host's data-sampling stream
    (train/elastic.py; the elastic counterpart of `data_stream_seed`).

    The world re-forms when a host is lost: the survivors respawn with a
    new ``num_hosts`` and a bumped ``generation``, and every host's
    stream must (a) stay a pure function of the config — the whole run
    reproduces from (seed, fault schedule) alone — and (b) decorrelate
    from every other (host, world-size, generation) stream, so no
    survivor replays draws the old world already trained on and the
    post-reform shards are disjoint by construction. All five components
    are folded in losslessly as uint32 words (MT19937 ``init_by_array``
    via `data/pipeline.py::derive_batch_rng`, which derives one sibling
    rng per batch index from this base): the seed as a 64-bit word pair,
    then host, world size, generation, and the resume step — any
    differing component yields an unrelated stream. The layout is also
    longer than `data_stream_seed`'s two words, so an elastic host never
    collides with a plain single-host run at the same seed.

    ``host_index`` may EXCEED ``num_hosts``: survivors keep their
    original identity across re-forms (host 2 of original 3 stays
    "host 2" in the shrunken 2-host world — renumbering would let a
    host-indexed fault schedule re-fire on an innocent neighbor), so
    the index is an identity, not a coordinate.
    """
    if int(host_index) < 0 or int(num_hosts) < 1:
        raise ValueError(f"invalid elastic identity: host_index "
                         f"{host_index}, num_hosts {num_hosts}")
    s = int(seed)
    return np.array([s & 0xFFFFFFFF, (s >> 32) & 0xFFFFFFFF,
                     int(host_index), int(num_hosts), int(generation),
                     int(start_step)], dtype=np.uint32)


def process_seed(mesh: Mesh, seed: int) -> int:
    """Host-sampling seed: decorrelated across data shards, *identical*
    for processes that are replicas of the same data coordinate (their
    devices share coords, so they must feed identical batches)."""
    coords = process_data_coords(mesh)
    return seed + (min(coords) if coords else 0)


def put_global(batch: dict, sharding: NamedSharding) -> dict:
    """Place a host-local numpy batch under `sharding`.

    Single-process: plain device_put. Multi-process (hosts spanning the
    mesh over DCN): each process contributes only its local rows
    (`local_batch_rows`) and the global array is assembled without any
    cross-host copy of the full batch — this is what lets each host load
    1/num_hosts of the data (SURVEY.md §5.8). Leaves that are already
    device-resident jax.Arrays (on-device augmentation output) are
    split into per-device shards and moved device-to-device — no
    host readback on the hot input path.
    """
    if jax.process_count() == 1:
        return jax.device_put(batch, sharding)

    def place(x):
        if isinstance(x, jax.Array):
            return _assemble_from_local_array(x, sharding)
        return jax.make_array_from_process_local_data(sharding, np.asarray(x))

    return jax.tree_util.tree_map(place, batch)


def _assemble_from_local_array(x: jax.Array, sharding: NamedSharding):
    """Build the global batch array from this process's already-on-device
    local-rows array without a device->host roundtrip."""
    mesh = sharding.mesh
    gshape = (_global_rows(mesh, x.shape[0]),) + x.shape[1:]
    _, rows = local_batch_rows(mesh, gshape[0])
    row_pos = {r: i for i, r in enumerate(rows)}
    shards = []
    for dev, idx in sharding.addressable_devices_indices_map(gshape).items():
        rsl = idx[0] if idx else slice(None)
        start, stop = rsl.start or 0, rsl.stop if rsl.stop is not None else gshape[0]
        lsl = slice(row_pos[start], row_pos[stop - 1] + 1)
        shards.append(jax.device_put(x[lsl], dev))
    return jax.make_array_from_single_device_arrays(gshape, sharding, shards)


def _global_rows(mesh: Mesh, local_rows: int) -> int:
    """Global batch size implied by this process's local row count."""
    n_coords = len(process_data_coords(mesh))
    if local_rows % max(n_coords, 1):
        raise ValueError(f"local batch {local_rows} not divisible by "
                         f"owned data coords {n_coords}")
    return (local_rows // max(n_coords, 1)) * mesh.shape["data"]


def put_global_from_full(batch: dict, mesh: Mesh,
                         sharding: NamedSharding) -> dict:
    """Like `put_global`, but every process holds the SAME full batch
    (deterministic val loading): each contributes only its own rows."""
    if jax.process_count() == 1:
        return jax.device_put(batch, sharding)

    def place(x):
        x = np.asarray(x)
        _, rows = local_batch_rows(mesh, x.shape[0])
        return jax.make_array_from_process_local_data(sharding, x[rows])

    return jax.tree_util.tree_map(place, batch)
