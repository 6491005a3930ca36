"""Latency-hiding loop mechanics: AsyncFetcher overlap, StepTimer phases,
Prefetcher device staging. Pure host-side — no model compiles — so these
run in the fast tier and pin the ISSUE r06 acceptance on CPU: under an
injected 50 ms fetch delay the pipelined dispatch/fetch loop sustains
>= 2 calls in flight and beats the serial loop's wall-clock.
"""

import threading
import time

import numpy as np
import pytest

from deepof_tpu.train.metrics_log import AsyncFetcher, StepTimer

FETCH_DELAY = 0.05  # the ISSUE-specified injected 50 ms value-fetch RTT
N_STEPS = 10


def _delayed_fetch(tree):
    time.sleep(FETCH_DELAY)
    return tree


def _serial_submit(tag, tree, callback):
    """The loop without a fetcher: fetch, then the callback, inline."""
    callback(tag, _delayed_fetch(tree))


def _run_loop(submit, drain=lambda: None, dispatch_s=0.04):
    """A train loop skeleton: dispatch-side host work then the metrics
    fetch submit; every step is host-visible (log_every=1). Dispatch and
    fetch delays are comparable (40 vs 50 ms), so overlap should cut wall-clock to ~max(sum_dispatch,
    sum_fetch) while the serial loop pays their sum."""
    done = []
    t0 = time.perf_counter()
    for i in range(N_STEPS):
        time.sleep(dispatch_s)  # stand-in for the async dispatch call
        submit(i, {"total": np.float32(i)},
               lambda tag, host: done.append(tag))
    drain()
    return time.perf_counter() - t0, done


def test_pipelined_loop_beats_serial_under_fetch_delay():
    """The acceptance pin: >= 2 calls in flight, and wall-clock clearly
    under the serial dispatch+fetch sum (which is ~N*(dispatch+fetch))."""
    serial_wall, serial_done = _run_loop(_serial_submit)
    pipe = AsyncFetcher(depth=2, fetch_fn=_delayed_fetch)
    pipe_wall, pipe_done = _run_loop(pipe.submit, pipe.drain)
    pipe.close()

    assert serial_done == list(range(N_STEPS))
    assert pipe_done == list(range(N_STEPS))  # FIFO: records stay ordered
    assert pipe.stats()["max_in_flight"] >= 2
    assert pipe.stats()["fetches"] == N_STEPS
    # serial pays ~N*55ms; pipelined hides the fetch behind dispatch and
    # is bounded by ~N*50ms fetch drain alone. Demand a real margin, not
    # a scheduler wiggle.
    assert pipe_wall < serial_wall * 0.85, (pipe_wall, serial_wall)


def test_async_fetcher_bounded_depth_blocks_dispatch():
    """The honesty mechanism: with depth=1, submit() cannot run ahead —
    the dispatch clock stays within one unfetched call of completion."""
    f = AsyncFetcher(depth=1, fetch_fn=_delayed_fetch)
    t0 = time.perf_counter()
    for i in range(4):
        f.submit(i, i, lambda tag, host: None)
    submit_wall = time.perf_counter() - t0
    f.drain()
    f.close()
    # 4 submits against depth 1: at least 2 fetch delays serialized into
    # the submit path (would be ~0 if the bound leaked)
    assert submit_wall > 2 * FETCH_DELAY
    # the bound is exact: never more than `depth` submitted-but-unfetched
    assert f.stats()["max_in_flight"] == 1


def test_async_fetcher_close_never_blocks_on_wedged_consumer():
    """Teardown robustness: a consumer stuck in a hung device_get (dead
    device) must not block close() — fit()'s finally has to reach
    prefetch.close()/ckpt.finalize(). The stop sentinel goes onto an
    unbounded queue, and the daemon thread is abandoned after the join
    timeout."""
    wedged = threading.Event()

    def hang_fetch(tree):
        wedged.set()
        time.sleep(60)  # daemon thread: abandoned at interpreter exit
        return tree

    f = AsyncFetcher(depth=1, fetch_fn=hang_fetch)
    f.submit(0, 0, lambda tag, host: None)
    assert wedged.wait(5.0)  # consumer is now stuck inside the fetch
    t0 = time.perf_counter()
    f.close()  # must return via the join timeout, not hang on a put
    assert time.perf_counter() - t0 < 10.0


def test_async_fetcher_callback_error_surfaces():
    f = AsyncFetcher(depth=2, fetch_fn=lambda t: t)

    def boom(tag, host):
        raise ValueError("callback exploded")

    f.submit(0, 0, boom)
    with pytest.raises(ValueError, match="callback exploded"):
        f.drain()  # join guarantees the callback ran before the re-raise
    f.close()


def test_step_timer_phases_accumulate_and_reset():
    t = StepTimer(items_per_step=4)
    t.phase("dispatch", 0.1)
    t.phase("dispatch", 0.2)
    t.phase("fetch", 0.05)
    p = t.phases()
    assert abs(p["phase_dispatch_s"] - 0.3) < 1e-9
    assert abs(p["phase_fetch_s"] - 0.05) < 1e-9
    t.reset()
    assert t.phases() == {}


def test_async_fetcher_records_fetch_phase():
    timer = StepTimer(items_per_step=1)
    f = AsyncFetcher(depth=2, fetch_fn=_delayed_fetch, timer=timer)
    for i in range(3):
        f.submit(i, i, lambda tag, host: None)
    f.drain()
    f.close()
    assert timer.phases()["phase_fetch_s"] >= 3 * FETCH_DELAY * 0.9


def test_prefetcher_stages_on_device_and_reports_put_phase():
    """stage=True: get() returns committed jax arrays (transfer already
    complete) and the put phase lands in the timer from the producer
    thread."""
    import jax

    from deepof_tpu.data.prefetch import Prefetcher

    timer = StepTimer(items_per_step=1)
    produced = {"n": 0}

    def produce():
        produced["n"] += 1
        return {"x": np.ones((4, 4), np.float32) * produced["n"]}

    pf = Prefetcher(produce, depth=2, stage=True, phase_cb=timer.phase)
    try:
        b = pf.get()
        assert isinstance(b["x"], jax.Array)
        assert b["x"].is_fully_addressable
        assert "phase_put_s" in timer.phases()
    finally:
        pf.close()


def test_prefetcher_default_stays_host_side():
    """Without stage/sharding the old contract holds: host numpy out."""
    from deepof_tpu.data.prefetch import Prefetcher

    pf = Prefetcher(lambda: {"x": np.zeros(3)}, depth=1)
    try:
        assert isinstance(pf.get()["x"], np.ndarray)
    finally:
        pf.close()
