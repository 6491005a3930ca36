"""Multi-worker host batch assembly with deterministic ordered delivery.

PR 1's execution layer hid device-side latency (compile cache, AOT
warmup, pipelined dispatch/fetch), which moves the wall-clock ceiling to
the host: a single thread decoding/resizing/augmenting every batch is
exactly the per-step host-decode starvation SURVEY.md §7.3.4 flags.

`InputPipeline` is the host-side fan-out: a pool of N worker threads
(cv2 imdecode/resize and the native C++ batch IO both release the GIL,
so decode parallelism is real even under CPython) assembles batches
out-of-order and delivers them **in order** through a bounded reorder
buffer. Determinism is by construction, not by luck:

  - every batch index maps to its own rng via `derive_batch_rng(base,
    i)` (MT19937 init_by_array over `[base..., i_lo, i_hi]`), so the
    sample/augment stream for index i never depends on which worker ran
    it, in what order, or how many workers exist;
  - delivery order is the index order, enforced by the reorder buffer.

Together: the delivered batch stream is bit-identical for ANY
`num_workers`, including 0 — where `get()` assembles inline on the
caller's thread (the Prefetcher's producer thread in the train loop,
i.e. today's single-thread topology) with zero pool overhead.

The layer is observable end-to-end (`stats()`): batches assembled,
per-batch assemble seconds, reorder-queue depth (current + max),
consumer waits (`get()` found the next batch not ready — the host side
of device starvation), and worker utilization. The train loop folds
these into the periodic metrics line and `bench.py --data` measures the
pipeline in isolation (batches/s, MB/s) so host vs. device bottlenecks
are attributable without a TPU.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable

import numpy as np

from ..obs import trace as obs_trace
from ..resilience.healing import retry_bounded


def resolve_num_workers(num_workers: int,
                        cpu_count: int | None = None) -> int:
    """`data.num_workers` -> an actual pool size.

    >= 0 passes through. -1 (auto) sizes to the host: 0 (inline
    assembly, zero pool overhead) when `os.cpu_count() <= 2` — BENCH_r06
    measured workers=4 LOSING to workers=0 on a small host (49.5 vs
    85.3 batches/s: pure thread contention, nothing to overlap when the
    runtime already owns the cores) — else `min(4, cpu_count - 2)`:
    leave two cores for the jax runtime + prefetch/fetcher threads, cap
    at 4 (decode parallelism saturates well before that on the measured
    workloads; beyond it the reorder buffer just buys memory).

    cpu_count: test override for the host probe.
    """
    n = int(num_workers)
    if n >= 0:
        return n
    if n != -1:  # a typo'd worker count must not silently become auto
        raise ValueError(f"num_workers must be >= 0 or -1 (auto), got {n}")
    cpus = cpu_count if cpu_count is not None else (os.cpu_count() or 1)
    if cpus <= 2:
        return 0
    return min(4, cpus - 2)


def derive_batch_rng(base_seed, batch_index: int,
                     salt: int = 0) -> np.random.RandomState:
    """Deterministic per-batch rng: (stream seed, batch index) -> rng.

    `base_seed` is an int or a uint32 array (the train loop passes
    `data_stream_seed(...)` — process-decorrelated, resume-fresh). The
    derived stream depends only on (base, index): identical for any
    worker count and any assembly order, the pipeline's determinism
    contract. Base words and the index are both carried as uint32
    PAIRS, so 64-bit seeds and indices are folded in losslessly.

    `salt` selects a SIBLING stream for the same (base, index) — the
    self-healing data path's substitute draws (resilience/healing.py:
    round r redraws a quarantined batch index from salt=r). salt=0
    appends nothing, so existing streams are bit-identical to the
    pre-salt implementation.
    """
    base = np.atleast_1d(np.asarray(base_seed, dtype=np.uint64))
    words = np.empty(2 * base.size + 2, np.uint32)
    words[0:-2:2] = (base & 0xFFFFFFFF).astype(np.uint32)
    words[1:-2:2] = (base >> 32).astype(np.uint32)
    idx = int(batch_index)
    words[-2] = idx & 0xFFFFFFFF
    words[-1] = (idx >> 32) & 0xFFFFFFFF
    if salt:
        s = int(salt)
        words = np.concatenate([
            words,
            np.asarray([s & 0xFFFFFFFF, (s >> 32) & 0xFFFFFFFF], np.uint32),
        ])
    return np.random.RandomState(words)


class InputPipeline:
    """Ordered delivery of `make_batch(i)` results over a worker pool.

    make_batch: batch index -> batch dict. Must be a pure function of
        the index (derive any randomness from the index — see
        `derive_batch_rng`); with `num_workers > 0` it runs concurrently
        on pool threads, so shared state it touches (decoded caches,
        ...) must be thread-safe.
    num_workers: pool size. 0 = no threads; `get()` assembles inline on
        the caller's thread (the legacy single-thread path, bit-identical
        stream, zero overhead). -1 = auto (`resolve_num_workers`): 0 on
        hosts with <= 2 cores, min(4, cores - 2) otherwise — the stream
        stays bit-identical either way (the determinism contract is
        worker-count independent).
    reorder_depth: how many indices past the delivery cursor workers may
        claim — bounds both in-flight assembly and the completed-but-
        undelivered reorder buffer, so buffered-batch memory stays
        bounded when one slow batch holds back delivery. The bounded
        memory lives WHERE the batches live: host RAM for numpy
        assembly, device HBM when make_batch returns device-resident
        arrays (e.g. on-device augmentation output) — size reorder_depth
        x batch bytes against the right budget. 0 = auto
        (2 x num_workers). Values below num_workers just idle the excess
        workers (never deadlock: the cursor's own batch is always
        claimable).
    retries: re-attempts of a failed `make_batch(i)` before the error
        dooms delivery (resilience layer: a transient IO/runtime error
        on a pipeline worker no longer kills the run). Safe because
        make_batch is a pure function of the index — a retry reproduces
        the exact same batch. Only OSError/RuntimeError retry;
        programming errors surface immediately.
    backoff_s: initial sleep before a retry; doubles per attempt.
    """

    def __init__(self, make_batch: Callable[[int], dict],
                 num_workers: int = 0, reorder_depth: int = 0,
                 retries: int = 0, backoff_s: float = 0.05):
        self._make = make_batch
        self._n = resolve_num_workers(num_workers)
        self._depth = (int(reorder_depth) if reorder_depth > 0
                       else max(2 * self._n, 1))
        self._retries = max(int(retries), 0)
        self._backoff = max(float(backoff_s), 0.0)
        self._cv = threading.Condition()
        self._next_claim = 0  # next index a worker will take
        self._next_out = 0  # next index get() delivers
        self._ready: dict[int, dict] = {}
        self._exc: BaseException | None = None
        self._fail_idx: int | None = None  # lowest index that errored
        self._stop = False
        # --- counters (all guarded by _cv; snapshot via stats()) ---
        self._batches = 0
        self._assemble_s = 0.0
        self._busy_s = 0.0
        self._waits = 0
        self._wait_s = 0.0
        self._retry_count = 0
        self._max_depth = 0
        self._t0 = time.perf_counter()
        self._threads = [
            threading.Thread(target=self._worker, daemon=True,
                             name=f"pipeline-worker-{i}")
            for i in range(self._n)
        ]
        for t in self._threads:
            t.start()

    # ------------------------------------------------------------- pool
    def _attempt(self, i: int) -> dict:
        """`make_batch(i)` with the shared bounded retry ladder
        (resilience/healing.py). Purity of make_batch makes a retry
        deliver the identical batch, so determinism survives transient
        faults."""

        def make():
            with obs_trace.span("assemble", index=i):
                return self._make(i)

        return retry_bounded(make, retries=self._retries,
                             backoff_s=self._backoff,
                             on_retry=self._count_retry)

    def _count_retry(self) -> None:
        with self._cv:
            self._retry_count += 1

    def _worker(self) -> None:
        while True:
            with self._cv:
                while (not self._stop and self._exc is None
                       and self._next_claim >= self._next_out + self._depth):
                    self._cv.wait()
                if self._stop or self._exc is not None:
                    return
                i = self._next_claim
                self._next_claim += 1
            t0 = time.perf_counter()
            try:
                batch = self._attempt(i)
            except BaseException as e:  # noqa: BLE001 - surfaced on get()
                with self._cv:
                    if self._exc is None:
                        self._exc = e
                    if self._fail_idx is None or i < self._fail_idx:
                        self._fail_idx = i
                    self._cv.notify_all()
                return
            dt = time.perf_counter() - t0
            with self._cv:
                self._ready[i] = batch
                self._batches += 1
                self._assemble_s += dt
                self._busy_s += dt
                self._max_depth = max(self._max_depth, len(self._ready))
                self._cv.notify_all()

    # ---------------------------------------------------------- consume
    def get(self) -> dict:
        """Deliver the next batch, in index order."""
        if self._n == 0:
            with self._cv:
                if self._exc is not None:
                    raise self._exc
                i = self._next_out
                self._next_out += 1
            t0 = time.perf_counter()
            try:
                batch = self._attempt(i)
            except BaseException as e:  # noqa: BLE001 - one idiom for both paths
                with self._cv:
                    if self._exc is None:
                        self._exc = e
                    if self._fail_idx is None or i < self._fail_idx:
                        self._fail_idx = i
                raise
            dt = time.perf_counter() - t0
            with self._cv:
                self._batches += 1
                self._assemble_s += dt
                self._busy_s += dt
            return batch
        with self._cv:
            i = self._next_out
            if i not in self._ready:
                # the consumer outran the pool: the host side of device
                # starvation (the train loop's `starved` counter is the
                # device-facing mirror of this)
                self._waits += 1
                t0 = time.perf_counter()
                while i not in self._ready:
                    # a pool error only dooms delivery from the FAILED
                    # index on: lower indices were claimed earlier by
                    # healthy workers and still arrive — deliver them
                    # (deterministically) before surfacing the error
                    if (self._exc is not None
                            and (self._fail_idx is None
                                 or i >= self._fail_idx)):
                        raise self._exc
                    if self._stop:
                        raise RuntimeError("InputPipeline closed during get()")
                    if not self._cv.wait(timeout=5.0):
                        if not any(t.is_alive() for t in self._threads):
                            if self._exc is not None:
                                raise self._exc
                            raise RuntimeError(
                                "all pipeline workers died without error")
                self._wait_s += time.perf_counter() - t0
            batch = self._ready.pop(i)
            self._next_out += 1
            self._cv.notify_all()  # a claim slot opened
            return batch

    def __iter__(self):
        while True:
            yield self.get()

    # ------------------------------------------------------ observability
    def stats(self) -> dict:
        """Counter snapshot, log/bench-ready (plain ints/floats)."""
        with self._cv:
            wall = max(time.perf_counter() - self._t0, 1e-9)
            denom = max(self._n, 1) * wall
            return {
                "num_workers": self._n,
                "batches": self._batches,
                "assemble_s": round(self._assemble_s, 4),
                "assemble_s_mean": round(
                    self._assemble_s / self._batches, 4) if self._batches
                    else 0.0,
                "queue_depth": len(self._ready),
                "max_queue_depth": self._max_depth,
                "waits": self._waits,
                "wait_s": round(self._wait_s, 4),
                "retries": self._retry_count,
                "worker_util": round(self._busy_s / denom, 4),
            }

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=2.0)
