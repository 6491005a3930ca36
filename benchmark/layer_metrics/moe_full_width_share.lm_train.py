"""How often an expert layer left its compact row list: the program's
counter `moe_full_width` (1.0 on a step where the layer's held slots passed
its row cap and it ran at the full width, one value an expert layer in
every train record), as the window's mean a layer, times 100, the LARGEST
over the layers. 0 says that the cell stayed on its steady path: no layer
changed width inside the window. None without records or the counter."""

from benchmark.harness.counters import largest_layer_mean


def read(obs):
    worst = largest_layer_mean(obs, "moe_full_width")
    return None if worst is None else 100.0 * worst
