"""Seconds in the program's `boot` span: the process's start to the
package's first line. In the benchmark's layout the interpreter, the
harness, `import jax`, `jax.devices()` (the chip's start-up) and the
runner's imports; recorded after the fact by the first tracer the
Trainer installs (`obs/trace.py::record_setup`); flow train cells."""

from benchmark.harness.span_reads import span_seconds


def read(obs):
    return span_seconds(obs["spans"], "boot")
