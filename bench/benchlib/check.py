"""The comparison that decides ``correct`` for a training cell.

The program's readings of its first three steps (taken in set-up, from the
object the window then drives) against the plain reference's, which runs
after the window from the same seed and takes nothing the program made:

- ``loss_step1..3``: each step's loss, relative gap;
- ``grad_worst_leaf``: the norm of the first gradient as the optimizer got
  it, worked out from the program's state after one step, leaf by leaf;
- ``delta_worst_leaf``: the norm of each leaf's change after three steps.

- ``rows_grad_diff`` (systems with tables): the first gradient of each
  table row by row, the norm of the difference over the reference's norm,
  by the worse table. The norms above average a rounding that has no bias
  over 10^5 rows and cannot see it; this number can.

Norm numbers are taken by the worst leaf: the gap between the program's
norm and the reference's (not the norm of a difference), over the
reference's norm of that leaf or of the median leaf, whichever is larger.
Leaves whose first gradient in the reference is under a thousandth of the
median leaf's move by round-off alone and are left out of the change.
Each number has a limit of its own, set from readings (PERF.md).
"""

from __future__ import annotations

import math
import statistics

TINY_GRAD_SHARE = 1e-3


def rel_gap(prog: float, ref: float) -> float:
    if not (math.isfinite(prog) and math.isfinite(ref)):
        return float("inf")
    return abs(prog - ref) / max(abs(ref), 1e-30)


def worst_leaf(prog: dict, ref: dict, leave_out=()) -> tuple:
    """(worst gap, its leaf) of per-leaf norms; a leaf missing on either
    side reads infinity."""
    names = [k for k in ref if k not in leave_out]
    if not names:
        return float("inf"), "none"
    med = statistics.median(ref[k] for k in names)
    worst, at = 0.0, names[0]
    for k in names:
        if k not in prog or not math.isfinite(prog[k]):
            return float("inf"), k
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if gap > worst:
            worst, at = gap, k
    return worst, at


def rows_diff(prog: dict, ref: dict) -> tuple:
    """(worst, its table) of |P - R| / |R| over tables of row gradients;
    a table missing or of another shape on either side reads infinity."""
    import numpy as np
    worst, at = 0.0, ""
    for k, r in ref.items():
        p = prog.get(k)
        if p is None or np.shape(p) != np.shape(r):
            return float("inf"), k
        r64 = np.asarray(r, np.float64)
        gap = float(np.linalg.norm(np.asarray(p, np.float64) - r64)
                    / max(np.linalg.norm(r64), 1e-30))
        if not math.isfinite(gap):
            return float("inf"), k
        if gap >= worst:
            worst, at = gap, k
    return worst, at


def tiny_gradient_leaves(ref_grad: dict) -> set:
    med = statistics.median(ref_grad.values())
    return {k for k, v in ref_grad.items() if v < TINY_GRAD_SHARE * med}


def numbers(prog: dict, ref: dict) -> dict:
    """The numbers compared, by name: value and, where one leaf decides
    it, that leaf. ``prog`` and ``ref`` hold ``loss`` (a list, one per
    step), ``grad`` and ``delta`` (leaf -> norm)."""
    out = {}
    for i, (p, r) in enumerate(zip(prog["loss"], ref["loss"]), 1):
        out[f"loss_step{i}"] = (rel_gap(p, r), "")
    if len(prog["loss"]) != len(ref["loss"]):
        out["loss_step1"] = (float("inf"), "steps differ")
    out["grad_worst_leaf"] = worst_leaf(prog["grad"], ref["grad"])
    out["delta_worst_leaf"] = worst_leaf(
        prog["delta"], ref["delta"], tiny_gradient_leaves(ref["grad"]))
    if "rows" in prog and "rows" in ref:
        out["rows_grad_diff"] = rows_diff(prog["rows"], ref["rows"])
    return out


def decide(prog: dict, ref: dict, limits: dict) -> tuple:
    """(correct, rows): a row is {"name", "value", "limit", "leaf"}. Every
    limit must be met, and every number that has a limit must be there."""
    got = numbers(prog, ref)
    rows, ok = [], True
    for name, limit in limits.items():
        value, leaf = got.get(name, (float("inf"), "not read"))
        passed = value <= limit
        ok = ok and passed
        rows.append({"name": name, "value": value, "limit": limit,
                     "leaf": leaf, "ok": passed})
    return ok, rows


def print_rows(rows, correct: bool, file) -> None:
    for r in rows:
        print(f"check {r['name']}: {r['value']:.6g} (limit {r['limit']:g})"
              f"{' at ' + r['leaf'] if r['leaf'] else ''}"
              f"{'' if r['ok'] else '  EXCEEDED'}", file=file)
    print(f"check correct: {correct}", file=file)


def rows_for_result(rows) -> dict:
    """Short plain names, each with its number and its limit."""
    return {r["name"]: {"value": r["value"], "limit": r["limit"]}
            for r in rows}
