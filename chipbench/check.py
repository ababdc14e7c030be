"""The comparison that decides ``correct``.

A training cell's set-up drives the compiled step from the seed through its
first steps, through the window's own call and feed; the plain reference
follows the same rows from the stored bytes once the window has closed.
Compared are each step's loss, the first gradient as the optimizer gets it
and the parameters' change after those steps, the last two by the worst
leaf: the gap between the program's norm and the reference's, against the
reference's norm of that leaf or of the median leaf, whichever is larger.
Each is read twice: at the worst leaf and at the median leaf. Which of these a
cell is held to, and to what limit, is in ``chipbench/limits/<cell>.json``,
set from readings on the chip (``chipbench.calibrate``, ``PERF.md``); one it
does not name is printed and not compared. The store's guarantees are exact
numbers: their limit is 0 unless the file says otherwise.
"""
from __future__ import annotations

import json
import math
import os
import statistics

FOLLOWED_STEPS = 3
TRAINING_NUMBERS = ("loss_gap", "grad_norm_gap", "update_norm_gap",
                    "grad_norm_gap_median", "update_norm_gap_median")
ZERO_GRADIENT = 1e-3   # of the median leaf's: such a leaf moves by round-off


def leaf_gaps(program: dict, reference: dict, leaves=None) -> dict:
    """``{leaf: gap}``: the gap between the program's norm and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    median = statistics.median(reference.values())
    return {leaf: abs(program[leaf] - reference[leaf])
            / max(reference[leaf], median)
            for leaf in (leaves if leaves is not None else reference)}


def worst_gap(gaps: dict) -> tuple:
    """``(worst gap, its leaf)``; a gap that is not a number is the worst
    there is."""
    for leaf, gap in gaps.items():
        if math.isnan(gap):
            return gap, leaf
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def moved_leaves(reference_grad_norms: dict) -> list:
    """Leaves whose gradient is not nought to rounding in the reference."""
    floor = ZERO_GRADIENT * statistics.median(reference_grad_norms.values())
    return [k for k, v in reference_grad_norms.items() if v >= floor]


def training_numbers(program: dict, reference: dict) -> dict:
    """``program`` / ``reference``: ``{"losses": [...], "grad_norms": {leaf:
    norm}, "delta_norms": {leaf: norm}}`` of the followed steps."""
    loss_gap = max(abs(p - r) / abs(r) for p, r in
                   zip(program["losses"], reference["losses"]))
    moved = moved_leaves(reference["grad_norms"])
    out = {"loss_gap": loss_gap, "_worst_leaves": {}}
    for name, kind in (("grad_norm_gap", "grad_norms"),
                       ("update_norm_gap", "delta_norms")):
        gaps = leaf_gaps(program[kind], reference[kind], moved)
        out[name], out["_worst_leaves"][name] = worst_gap(gaps)
        out[name + "_median"] = statistics.median(gaps.values())
    return out


def load_limits(root: str, cell: str, rehearsal: bool = False) -> dict:
    """The cell's limits; ``rehearsal`` takes the file's ``rehearsal``
    block instead, read at the toy sizes on a CPU (noise grows as sizes
    shrink, so the chip's limits do not carry over)."""
    with open(os.path.join(root, "chipbench", "limits", f"{cell}.json")) as f:
        limits = json.load(f)
    if rehearsal:
        limits = limits["rehearsal"]
    return {k: v for k, v in limits.items()
            if not k.startswith("_") and k != "rehearsal"}


def verdict(numbers: dict, limits: dict) -> tuple:
    """``(correct, {name: {"value": v, "limit": l}})``. A number is within
    its limit when ``value <= limit``; anything not finite is not."""
    compared = {}
    for name, value in numbers.items():
        if name.startswith("_") or (name in TRAINING_NUMBERS
                                    and name not in limits):
            continue
        compared[name] = {"value": value, "limit": limits.get(name, 0)}
    correct = all(isinstance(c["value"], (int, float))
                  and math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in compared.values())
    return correct, compared
