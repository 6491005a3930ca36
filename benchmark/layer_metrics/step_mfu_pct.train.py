"""The whole step's share of the chip's peak: the configuration file's
`train_flops_per_pair` (counted on the plain reference, never read off the
program) times the pairs per second of this run's window, over chips times
the published peak."""


def read(obs):
    lo, hi = obs["window"]
    flops = obs["config"].get("train_flops_per_pair")
    if not flops or lo is None or hi is None or not obs["pairs"]:
        return None
    rate = obs["pairs"] / (hi - lo)
    return 100.0 * flops * rate / (obs["chips"] * obs["peaks"]["flops_per_s"])
