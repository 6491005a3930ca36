"""The share of the window in which the train loop's main thread was
inside NO span of the program (its own Python between `input_wait`,
`dispatch`, `submit_wait`, ...), language-model train cells."""

from benchmark.harness.span_reads import span_seconds, uncovered_share


def read(obs):
    if span_seconds(obs["spans"], "first_step") is None:
        return None
    lo, hi = obs["window"]
    share = uncovered_share(obs["spans"], lo, hi)
    return None if share is None else 100.0 * share
