"""Seconds in the program's `trainer_init` span: `Trainer.__init__` from
its first line (model build, the state's jitted init, restore, placing the
state, building the step)."""

from benchmark.harness.span_reads import span_seconds


def read(obs):
    return span_seconds(obs["spans"], "trainer_init")
