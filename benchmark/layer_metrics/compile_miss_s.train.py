"""Seconds the run spent in XLA compiles that the persistent cache did not
serve: the sum of the program's `xla_compile` spans, on every thread, from
`Trainer.__init__` on. A run whose compiles were all loads
(`xla_cache_load`) reads 0.0, not nothing; None only where the program
recorded neither kind of span (tracing off, or a program without them)."""


def read(obs):
    compiled = [b - a for n, _, a, b in obs["spans"] if n == "xla_compile"]
    loaded = any(n == "xla_cache_load" for n, _, _, _ in obs["spans"])
    if not compiled and not loaded:
        return None
    return float(sum(compiled))
