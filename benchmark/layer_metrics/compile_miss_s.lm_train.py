"""Seconds the run spent in XLA compiles that the persistent cache did not
serve (the sum of the program's `xla_compile` spans on every thread),
language-model train cells: the step is the large one, and the benchmark's
own compiles of the window (weights, pool) are in it too. 0.0 where every
compile was a load, None where the program recorded neither kind of span."""


def read(obs):
    compiled = [b - a for n, _, a, b in obs["spans"] if n == "xla_compile"]
    loaded = any(n == "xla_cache_load" for n, _, _, _ in obs["spans"])
    if not compiled and not loaded:
        return None
    return float(sum(compiled))
