"""Seconds in the program's `ledger_lower` span: the second trace and
lowering of the step after its first run, for the executable ledger's
row (`Trainer._ledger_lower`, inside `first_step`); flow train cells."""

from benchmark.harness.span_reads import span_seconds


def read(obs):
    return span_seconds(obs["spans"], "ledger_lower")
