"""Device busy time per optimizer step of a language-model train cell:
the busy seconds of the traced window over the executions of the step's
program that lie inside it."""

from benchmark.harness.trace_reduce import executions


def read(obs):
    dev = obs["device"]
    steps = executions(dev, obs["traffic"].get("step_module", "jit_step"))
    if not steps or not dev["busy_s"]:
        return None
    return 1e3 * dev["busy_s"] / steps
