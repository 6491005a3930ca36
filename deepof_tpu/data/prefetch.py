"""Async double-buffered host->device prefetcher.

Replaces the reference's synchronous per-step disk->numpy->feed_dict path
(`sintelTrain.py:189-195`, SURVEY.md §3.1 hot loop): a background thread
decodes/assembles the next batches while the device runs the current step,
and batches are placed on device (optionally with a NamedSharding) ahead of
use so the train step never waits on host IO.

With `stage=True` the producer thread additionally *blocks on transfer
completion* (`jax.block_until_ready`): the next batch is fully
resident in device memory while the current step executes, so dispatching
the next call never overlaps its own input transfer with its compute
warm-up. The wait happens off the critical path (background thread), and
its wall time is reported to the StepTimer as the `put` phase — one of
the four dispatch-timeline phases (DESIGN.md "Execution layer").
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterator

import jax

from ..obs import trace as obs_trace


class Prefetcher:
    """Wraps a batch-producing callable into a prefetching iterator.

    next_batch: () -> dict[str, np.ndarray] (host numpy)
    sharding: optional jax.sharding.Sharding applied via device_put.
    stage: block the producer thread until the device transfer completes
        (guarantees residency; only meaningful off the main thread).
    phase_cb: optional (name, seconds) sink for the `put` phase time
        (StepTimer.phase).
    """

    def __init__(
        self,
        next_batch: Callable[[], dict],
        depth: int = 2,
        sharding: jax.sharding.Sharding | None = None,
        stage: bool = False,
        phase_cb: Callable[[str, float], None] | None = None,
    ):
        self._next = next_batch
        self._sharding = sharding
        self._stage = stage
        self._phase_cb = phase_cb
        self._q: queue.Queue = queue.Queue(maxsize=max(depth, 1))
        self._stop = threading.Event()
        self._exc: BaseException | None = None
        self._max_depth = 0  # peak staged-batch count (GIL-atomic update)
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="prefetch")
        self._thread.start()

    def _place(self, batch: dict) -> dict:
        t0 = time.perf_counter()
        with obs_trace.span("put"):
            if self._sharding is not None:
                # multi-process: producer yields this host's local rows
                # and the global array is assembled shard-wise
                from ..parallel.mesh import put_global

                batch = put_global(batch, self._sharding)
            elif self._stage:
                batch = jax.device_put(batch)
            if self._stage:
                jax.block_until_ready(batch)
        if self._phase_cb is not None:
            self._phase_cb("put", time.perf_counter() - t0)
        return batch

    def _run(self) -> None:
        try:
            while not self._stop.is_set():
                batch = self._place(self._next())
                while not self._stop.is_set():
                    try:
                        self._q.put(batch, timeout=0.1)
                        self._max_depth = max(self._max_depth,
                                              self._q.qsize())
                        break
                    except queue.Full:
                        continue
        except BaseException as e:  # noqa: BLE001 - surfaced on get()
            self._exc = e

    def get(self) -> dict:
        while True:
            if self._exc is not None:
                raise self._exc
            try:
                return self._q.get(timeout=0.1)
            except queue.Empty:
                if not self._thread.is_alive() and self._exc is None:
                    raise RuntimeError("prefetch thread died without error")

    def __iter__(self) -> Iterator[dict]:
        while True:
            yield self.get()

    def stats(self) -> dict:
        """Staging-queue observability: current and peak staged depth.
        A persistently empty staging queue while the device consumes
        points the bottleneck at the producer side (the InputPipeline's
        own stats say whether assembly or staging is the cause)."""
        return {"staged_depth": self._q.qsize(),
                "max_staged_depth": self._max_depth}

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)
