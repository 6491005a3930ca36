"""Seconds in the program's `trainer_init` span (`Trainer.__init__`: model
build, the state's jitted init over 576 M parameters, placing the state,
building the step), language-model train cells."""

from benchmark.harness.span_reads import span_seconds


def read(obs):
    return span_seconds(obs["spans"], "trainer_init")
