"""The comparison that decides ``correct``: each number compared has a limit
of its own (``benchmarks/cells/<cell>.json``, key ``limits``), and every
number is printed beside its limit in every run."""
from __future__ import annotations

import statistics


def _worst_leaf_gap(prog: dict, ref: dict, leaves=None) -> tuple[float, str]:
    """Worst leaf of |‖prog‖ − ‖ref‖| / max(‖ref‖ of that leaf, ‖ref‖ of the
    median leaf): the gap between the two norms, not the norm of a
    difference, measured against the larger of the leaf's own and the median
    leaf's reference norm (some leaves are all but zero)."""
    leaves = list(ref) if leaves is None else leaves
    med = statistics.median(ref[k] for k in ref)
    worst, where = 0.0, ""
    for k in leaves:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if gap > worst:
            worst, where = gap, k
    return worst, where


def moving_leaves(ref_first_grad: dict) -> list[str]:
    """Leaves whose change is compared: those whose reference gradient is not
    nought to rounding — at least a thousandth of the median leaf's. (The
    others move under Adam by round-off alone.)"""
    med = statistics.median(ref_first_grad.values())
    return [k for k, v in ref_first_grad.items() if v >= 1e-3 * med]


def training_numbers(prog: dict, ref: dict) -> tuple[dict, dict]:
    """*prog*, *ref*: ``{"losses": [..3], "first_grad": {leaf: norm},
    "change": {leaf: norm}}``. Returns (numbers, notes)."""
    if set(prog["first_grad"]) != set(ref["first_grad"]):
        missing = set(prog["first_grad"]) ^ set(ref["first_grad"])
        raise ValueError(f"program and reference name different leaves: {sorted(missing)[:6]}")
    numbers = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        numbers[f"loss_gap_step{i}"] = abs(a - b) / max(abs(b), 1e-30)
    g, g_leaf = _worst_leaf_gap(prog["first_grad"], ref["first_grad"])
    moving = moving_leaves(ref["first_grad"])
    c, c_leaf = _worst_leaf_gap(prog["change"], ref["change"], moving)
    numbers["first_grad_gap"] = g
    numbers["change_gap"] = c
    notes = {"first_grad_worst_leaf": g_leaf, "change_worst_leaf": c_leaf,
             "leaves": len(ref["first_grad"]), "leaves_moving": len(moving),
             "loss_ref": ref["losses"], "loss_prog": prog["losses"]}
    return numbers, notes


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """-> (correct, {name: {"value", "limit", "ok"}}). A number with no limit
    is not correct: no limit is ever guessed here."""
    table, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        good = (limit is not None and value == value and value <= limit)
        ok = ok and good
        table[name] = {"value": value, "limit": limit, "ok": good}
    return ok and bool(numbers), table
