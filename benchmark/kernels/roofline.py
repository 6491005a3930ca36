"""The least time a chip could take for a kernel call: the larger of its
operations over the peak rate and its bytes over the peak bandwidth."""


def least_seconds(counts: dict, peaks: dict) -> tuple[float, str]:
    t_ops = counts["ops"] / peaks["flops_per_s"]
    t_bytes = counts["bytes"] / peaks["bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
