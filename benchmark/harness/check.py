"""The comparisons that decide `correct`, each number beside its limit.

Limits are data: `benchmark/limits/<workload>.json`, found by the cell's
name, with the readings each limit was set from.
"""

from __future__ import annotations

import statistics

# A leaf whose reference gradient is under this share of the median leaf's
# is nought to rounding (a key's bias under softmax): Adam moves it by
# round-off alone, so it is left out of the parameters' change.
NOUGHT_GRADIENT_SHARE = 1e-3


def _worst_leaf_gap(prog: dict, ref: dict, skip=()) -> tuple[float, str]:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    med = statistics.median(ref.values())
    worst, at = 0.0, ""
    for name, r in ref.items():
        if name in skip:
            continue
        gap = abs(prog[name] - r) / max(r, med)
        if gap > worst:
            worst, at = gap, name
    return worst, at


def compare_train(prog: dict, ref: dict) -> dict:
    """Numbers of a training comparison (no limits applied yet)."""
    out = {}
    for i, (p, r) in enumerate(zip(prog["losses"], ref["losses"]), start=1):
        out[f"loss{i}_gap"] = abs(p - r) / abs(r)
    out["grad_gap"], out["grad_gap_leaf"] = _worst_leaf_gap(
        prog["grad_norms"], ref["grad_norms"]
    )
    med = statistics.median(ref["grad_norms"].values())
    nought = {
        n for n, g in ref["grad_norms"].items() if g < NOUGHT_GRADIENT_SHARE * med
    }
    out["dparam_gap"], out["dparam_gap_leaf"] = _worst_leaf_gap(
        prog["dparam_norms"], ref["dparam_norms"], skip=nought
    )
    out["leaves_left_out"] = sorted(nought)
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Hold every number that has a limit to it. Returns (correct, compared)
    where compared maps a short name to [number, limit]. A limit that the
    numbers lack a reading for fails."""
    compared = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        compared[name] = [value, limit]
        if value is None or not value <= limit:
            ok = False
    return ok, compared
