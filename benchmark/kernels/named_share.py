"""Named Mosaic kernels' share of their roofline.

As `layer_metrics/pallas_roofline_pct.train.py`, restricted to one kernel:
the numerator takes only the configuration's `step_kernels` entries of that
count function (`warp.forward`), the denominator only the trace's Mosaic
events whose HLO instruction carries the kernel's `name=`
(`%warp_fwd.5 = ... custom-call(...)`). A program whose kernels have no
names (the event is `%name.10 = ...`) gives None. `read_pairs` takes
several (count function, event prefix) pairs into one share: kernels that
are one pass of the program (its forward and its backward).
"""

import importlib

from benchmark.harness.trace_reduce import executions
from benchmark.kernels.roofline import least_seconds


def read(obs, kernel: str, event_prefix: str):
    return read_pairs(obs, [(kernel, event_prefix)])


def read_pairs(obs, pairs):
    dev = obs["device"]
    pats = [p.lower() for p in obs["config"].get(
        "kernel_event_patterns", ["tpu_custom_call"])]
    prefixes = tuple(prefix for _, prefix in pairs)
    spent = sum(s for n, (s, _) in dev["ops"].items()
                if n.startswith(prefixes)
                and any(p in n.lower() for p in pats)) / max(dev["chips"], 1)
    least = 0.0
    for kernel, _ in pairs:
        mod_name, fn = kernel.split(".")
        count = getattr(importlib.import_module("benchmark.kernels." + mod_name), fn)
        least += sum(
            least_seconds(count(b=obs["batch"] // obs["chips"], **k["args"]),
                          obs["peaks"])[0]
            for k in obs["config"].get("step_kernels", [])
            if k["kernel"] == kernel)
    steps = executions(dev, obs["traffic"].get("step_module", "jit_step"))
    if not steps or not spent or not least:
        return None
    return 100.0 * least * steps / spent
