"""Share of the window the train loop's main thread spent inside the
program's `input_wait` span (waiting for the prefetcher), language-model
train cells."""

from benchmark.harness.spans import share_of_window


def read(obs):
    lo, hi = obs["window"]
    if lo is None or hi is None:
        return None
    share = share_of_window(obs["spans"], "input_wait", lo, hi, thread="MainThread")
    return None if share is None else 100.0 * share
