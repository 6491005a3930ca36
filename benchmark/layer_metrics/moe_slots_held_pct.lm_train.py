"""How near the fullest expert layer is to its row cap: the program's
counter `moe_slots_held_share` (the token-slots that fall on the held
experts over all, one value an expert layer in every train record), as the
window's mean a layer, times 100, the LARGEST over the layers. 12.5 is an
even share of an 8-chip job, 25 the cap past which a layer runs at the
full width. None without records or the counter."""

from benchmark.harness.counters import largest_layer_mean


def read(obs):
    worst = largest_layer_mean(obs, "moe_slots_held_share")
    return None if worst is None else 100.0 * worst
