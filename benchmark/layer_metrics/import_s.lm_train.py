"""Seconds in the program's `import` span: the package's first line to
the end of `train/loop.py`'s import (flax, optax, orbax, the models),
recorded after the fact by the first tracer the Trainer installs; language-model train cells."""

from benchmark.harness.span_reads import span_seconds


def read(obs):
    return span_seconds(obs["spans"], "import")
