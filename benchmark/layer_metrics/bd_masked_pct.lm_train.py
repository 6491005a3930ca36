"""Loss-bearing positions over the positions of a row, times 100: the
program's counter `bd_masked_share` (in every train record of a model
trained by diffusion over blocks), as the window's mean. About 70 under
t ~ U(0.45, 0.95). None without records or the counter."""

from benchmark.harness.counters import largest_layer_mean


def read(obs):
    share = largest_layer_mean(obs, "bd_masked_share")
    return None if share is None else 100.0 * share
