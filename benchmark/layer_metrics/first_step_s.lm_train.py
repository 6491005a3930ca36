"""Seconds in the program's `first_step` span (the wait for the first
batch, the step's compile or load from the cache and one run, the
lower-only retrace), language-model train cells."""

from benchmark.harness.span_reads import span_seconds


def read(obs):
    return span_seconds(obs["spans"], "first_step")
