"""The Mosaic kernels' share of their roofline, one number per cell.

Numerator: for every kernel call the configuration's file lists for one
optimizer step (`step_kernels`: which count function under
`benchmark/kernels/`, at which shape), the least time the chip could take
(the larger of operations over the peak rate and bytes over the peak
bandwidth), times the executions of the step's program inside the traced
window. Denominator: the device durations, in that window, of the trace's
events that are Mosaic custom calls (named by `kernel_event_patterns`).
Nothing to read (no such event, no step inside the window) gives None.
"""

import importlib

from benchmark.harness.trace_reduce import executions
from benchmark.kernels.roofline import least_seconds


def kernel_events(obs):
    pats = [p.lower() for p in obs["config"].get(
        "kernel_event_patterns", ["tpu_custom_call"])]
    return {n: sc for n, sc in obs["device"]["ops"].items()
            if any(p in n.lower() for p in pats)}


def least_per_step(obs) -> float:
    total = 0.0
    for k in obs["config"].get("step_kernels", []):
        mod_name, fn = k["kernel"].split(".")
        mod = importlib.import_module("benchmark.kernels." + mod_name)
        counts = getattr(mod, fn)(b=obs["batch"] // obs["chips"], **k["args"])
        total += least_seconds(counts, obs["peaks"])[0]
    return total


def read(obs):
    dev = obs["device"]
    steps = executions(dev, obs["traffic"].get("step_module", "jit_step"))
    spent = sum(s for s, _ in kernel_events(obs).values()) / max(dev["chips"], 1)
    least = least_per_step(obs)
    if not steps or not spent or not least:
        return None
    return 100.0 * least * steps / spent
