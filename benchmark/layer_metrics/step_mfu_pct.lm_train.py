"""The whole step's share of the chip's peak in a language-model train
cell: the configuration file's `train_flops_per_pair` (a pair is one row
with its shifted targets; counted on the plain reference, never read off
the program, so recomputation does not count) times the rows per second of
this run's window, over chips times the published peak."""


def read(obs):
    lo, hi = obs["window"]
    flops = obs["config"].get("train_flops_per_pair")
    if not flops or lo is None or hi is None or not obs["pairs"]:
        return None
    return 100.0 * flops * obs["pairs"] / (hi - lo) / (
        obs["chips"] * obs["peaks"]["flops_per_s"])
