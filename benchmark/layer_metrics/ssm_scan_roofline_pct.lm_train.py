"""The state-space layers' scan against its roofline: the least time of
the recurrence on the doubled row, reckoned from shapes
(`benchmark/kernels/ssd.py`, the configuration's `scan_calls`, forward and
backward a layer), over the device seconds a step of the events traced
under the scope `mamba_scan`, forward, recomputed and transposed. Read by
scope and not by a kernel's name, so that whatever implements the scan
(XLA's fusions today, a kernel later) is judged against the same work.
None where the program has no such scope or the configuration lists no
scan."""

from benchmark.harness.scope_share import seconds_by_scope
from benchmark.harness.trace_reduce import executions
from benchmark.kernels import ssd
from benchmark.kernels.roofline import least_seconds


def read(obs):
    calls = obs["config"].get("scan_calls", [])
    got = seconds_by_scope(obs, ("mamba_scan",))
    steps = executions(obs["device"], obs["traffic"].get("step_module", "jit_step"))
    if not calls or got is None or not got[0]["mamba_scan"] or not steps:
        return None
    b = obs["batch"] // obs["chips"]
    least = sum(least_seconds(getattr(ssd, k["kernel"].split(".")[1])(
        b=b, **k["args"]), obs["peaks"])[0] for k in calls)
    return 100.0 * least * steps / (got[0]["mamba_scan"] / max(obs["device"]["chips"], 1))
