"""The numbers that decide `correct` for a language-model train cell, from
the program's and the plain reference's readings of the first steps:

    {"losses": [step], "row_losses": [[row] step], "grad_norms": {leaf},
     "dparam_norms": {leaf}}

`compare.train_numbers` reads each level's loss of a warp pyramid; here
the finer reading is each row's own loss. The leaf rules are `compare`'s.
"""

from __future__ import annotations

import statistics

from .compare import worst_leaf_gap

#: leaves of the expert layers' routed part: what a fault in the routing
#: (the number chosen, their weights) moves first
ROUTED = ("/moe/experts_w_", "/moe/router")


def train_numbers(prog: dict, ref: dict) -> dict:
    rel = lambda a, b: abs(a - b) / max(abs(b), 1e-30)  # noqa: E731
    same = len(prog["losses"]) == len(ref["losses"]) and \
        [len(r) for r in prog["row_losses"]] == [len(r) for r in ref["row_losses"]]
    loss_gap = max(rel(a, b) for a, b in zip(prog["losses"], ref["losses"]))
    row_gaps = [rel(a, b) for pr, rr in zip(prog["row_losses"], ref["row_losses"])
                for a, b in zip(pr, rr)]
    row_rms = (sum(g * g for g in row_gaps) / len(row_gaps)) ** 0.5
    g_all: list = []
    d_all: list = []
    grad_gap, grad_leaf = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"],
                                         gaps_out=g_all)
    routed = lambda k: any(p in k for p in ROUTED)  # noqa: E731
    routed_gap, routed_leaf = worst_leaf_gap(
        prog["grad_norms"], ref["grad_norms"], routed) \
        if any(routed(k) for k in ref["grad_norms"]) else (0.0, "")
    # leaves whose reference gradient is nought to rounding (the router's
    # bias, which no gradient reaches) move under Adam by round-off alone
    med = statistics.median(ref["grad_norms"].values())
    live = lambda k: ref["grad_norms"][k] >= 1e-3 * med  # noqa: E731
    dp_gap, dp_leaf = worst_leaf_gap(prog["dparam_norms"], ref["dparam_norms"],
                                     live, gaps_out=d_all)
    glob = lambda d: sum(v * v for v in d.values()) ** 0.5  # noqa: E731
    nan = float("nan")
    return {"loss_gap": loss_gap if same else nan,
            "row_loss_rms_gap": row_rms if same else nan,
            "row_loss_gap": max(row_gaps) if same else nan,
            "loss_gap_step1": rel(prog["losses"][0], ref["losses"][0]),
            "routed_grad_norm_gap": routed_gap,
            "grad_norm_gap": grad_gap, "dparam_norm_gap": dp_gap,
            "grad_global_gap": rel(glob(prog["grad_norms"]), glob(ref["grad_norms"])),
            "dparam_global_gap": rel(glob(prog["dparam_norms"]), glob(ref["dparam_norms"])),
            "grad_norm_gap_median": statistics.median(g_all),
            "dparam_norm_gap_median": statistics.median(d_all),
            "_where": {"grad_norm_gap": grad_leaf, "dparam_norm_gap": dp_leaf,
                       "routed_grad_norm_gap": routed_leaf}}
