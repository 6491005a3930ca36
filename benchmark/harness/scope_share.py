"""Device time by the program's named scopes. A profile's `XLA Ops` event
carries no scope, but its name begins with its HLO instruction's, and the
step executable's text gives that instruction's `op_name` (the scopes it
was traced under, `transpose(jvp(...))` around the backward's). The runner
puts {instruction: op_name} into `observed["op_scopes"]`; a program or a
runner without it gives the readers nothing to read."""

from __future__ import annotations

import functools
import re

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"")
_EVENT = re.compile(r"^%?([\w.\-]+)")
#: containers: their own event covers their bodies' events, which count
CONTAINERS = ("while", "conditional", "call")


def arg_specs(args):
    """Shapes, types and shardings of a step call's arguments: enough to
    lower the step again once the arguments are gone."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        args)


def executable_scopes(step, specs) -> dict:
    """`op_scopes` of the step's executable, compiled again from the specs
    of its first call (a load from the compile cache)."""
    return op_scopes(step.lower(*specs).compile().as_text())


def op_scopes(hlo_text: str) -> dict:
    """{instruction name: op_name} of every instruction that has one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def under(op_name: str, scope: str) -> bool:
    """Is `scope` one of the names `op_name` was traced under (forward or
    transposed), as a whole name and not as part of another?"""
    return re.search(r"(?<![\w])" + re.escape(scope) + r"(?![\w])", op_name) is not None


def seconds_matching(obs: dict, tests: dict) -> tuple[dict, float] | None:
    """({name: device seconds of the events whose `op_name` the name's test
    accepts}, seconds of all events) of the traced window, containers left
    out. None where the runner gave no map or no event found its
    instruction."""
    table = obs.get("op_scopes")
    if not table:
        return None
    out, total, found = {n: 0.0 for n in tests}, 0.0, 0
    for name, (sec, _) in obs["device"]["ops"].items():
        m = _EVENT.match(name)
        instr = m.group(1) if m else ""
        if instr.split(".")[0] in CONTAINERS:
            continue
        total += sec
        op = table.get(instr)
        if op is None:
            continue
        found += 1
        for n, accepts in tests.items():
            if accepts(op):
                out[n] += sec
    return (out, total) if found and total > 0 else None


def seconds_by_scope(obs: dict, scopes) -> tuple[dict, float] | None:
    """`seconds_matching` with a scope's name as its test: the events
    traced under it, forward or transposed."""
    return seconds_matching(
        obs, {s: functools.partial(under, scope=s) for s in scopes})


def breakdown(obs: dict, scopes) -> dict | None:
    """The traced line's `breakdown.scopes`: every scope's device seconds
    beside the seconds of all events."""
    got = seconds_by_scope(obs, scopes)
    return None if got is None else {"seconds": got[0], "all_events_s": got[1]}


def share_pct(obs: dict, scope: str) -> float | None:
    got = seconds_by_scope(obs, (scope,))
    return None if got is None else 100.0 * got[0][scope] / got[1]
