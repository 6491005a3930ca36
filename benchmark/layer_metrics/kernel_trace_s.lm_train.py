"""Seconds in the program's `kernel_trace` spans on the main thread,
summed: each Pallas kernel's call, where its body is traced into a jaxpr
(`ops/pallas/__init__.py::pallas_call`), in the step's first trace and in
the ledger's second. Mosaic's lowering to MLIR is not in it (the step's
`jax_lower`); language-model train cells."""

from benchmark.harness.span_reads import span_seconds


def read(obs):
    return span_seconds(obs["spans"], "kernel_trace")
