"""How uneven the held experts' loads were: the program's counter
`moe_load_max_over_mean` (the fullest held expert's token-slots over the
held experts' mean, one value an expert layer in every train record),
averaged over the window's records and the layers. The runner copies the
window's records into `observed["records"]`; none there, or a program that
writes no such counter, reads nothing."""


def read(obs):
    values = [v for r in obs.get("records", [])
              for v in r.get("moe_load_max_over_mean", [])]
    return sum(values) / len(values) if values else None
