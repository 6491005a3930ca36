"""What the train loop's own Python costs: the share of the window in which
the loop's main thread was inside NO span of the program. Every place the
loop can block is a span (`input_wait`, `dispatch`, `submit_wait`, `drain`,
`eval`, `ckpt`, `rollback`), so what is left is the loop's bookkeeping
between them: timers, the heartbeat's beat, the due-checks, logging.

A program from before its blocking sites were spans has no `first_step`
span either (they came together); what its spans leave uncovered is mostly
waiting, so nothing is read from it."""

from benchmark.harness.span_reads import span_seconds, uncovered_share


def read(obs):
    if span_seconds(obs["spans"], "first_step") is None:
        return None
    lo, hi = obs["window"]
    share = uncovered_share(obs["spans"], lo, hi)
    return None if share is None else 100.0 * share
