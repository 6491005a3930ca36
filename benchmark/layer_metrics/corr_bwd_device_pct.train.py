"""Share of the traced window's device time that the correlation's
BACKWARD takes: the events whose `op_name` lies in the transposed part of
the step (`transpose(...)`) and under one of
  - the scope `corr` (the program does not name it yet: PERF.md section 7),
  - a kernel's own name that begins with `corr_` (a `pallas_call`'s `name=`
    is a part of its `op_name`),
  - today's mark: the model's one loop directly under the transposed
    forward, `transpose(jvp(forward))/FlowNetC/while` with everything in
    it: the custom VJP's scan over the displacements.
The first two go by scope, so whatever implements the backward later (a
kernel named `corr_bwd`, the forward kernel with its roles swapped) is
still counted. The third is a mark by OPERATION KIND, and all that matches
anything until `jax.named_scope("corr")` stands in the program: it is held
to that one place, so a loop elsewhere in the backward (a scanned decoder,
a remat loop under a module of its own) is not counted as the correlation;
the backward's work OUTSIDE the scan (the cotangent's transpose, the pad of
f2, the final slice and casts) carries `FlowNetC/transpose` and the like
and is not counted until the scope names it. Containers are left out and
their bodies counted (`harness/scope_share.py`). None where the runner gave
no instruction-to-scope map or nothing matched."""

import re

from benchmark.harness.scope_share import seconds_matching

_SCOPED = re.compile(r"(?<![\w])corr(_\w+)?(?![\w])")
_TODAY = re.compile(r"transpose\(jvp\(forward\)\)/FlowNetC/while(/|$)")


def is_corr_backward(op_name: str) -> bool:
    if _TODAY.search(op_name):
        return True
    return "transpose(" in op_name and _SCOPED.search(op_name) is not None


def read(obs):
    got = seconds_matching(obs, {"corr_bwd": is_corr_backward})
    if got is None or not got[0]["corr_bwd"]:
        return None
    return 100.0 * got[0]["corr_bwd"] / got[1]
