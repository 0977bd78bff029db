"""The comparison that decides ``correct``: each number compared against a
limit of its own, taken from the configuration's file. Pure Python."""

from __future__ import annotations

import statistics


def worst_leaf_gap(program: dict, reference: dict) -> tuple[float, str]:
    """Per leaf, the gap between the program's norm and the reference's
    (not the norm of a difference), against the reference's norm of that
    leaf or of the median leaf, whichever is larger — some leaves'
    gradients are all but zero. Returns the worst gap and its leaf."""
    median = statistics.median(reference.values())
    worst, name = 0.0, ""
    for leaf, ref in reference.items():
        if leaf not in program:
            return float("inf"), f"{leaf} (missing)"
        gap = abs(program[leaf] - ref) / max(ref, median)
        if not gap <= worst:      # a nan is the worst there is
            worst, name = gap, leaf
    return worst, name


def training_numbers(program: dict, reference: dict) -> dict:
    """program / reference: {"losses": [..], "grad_norms": {..},
    "change_norms": {..}}."""
    out = {}
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        out[f"loss{i + 1}_rel"] = abs(a - b) / abs(b)
    if len(program["losses"]) != len(reference["losses"]):
        out["loss_steps_missing"] = float("inf")
    out["grad_norm_gap"], out["grad_norm_leaf"] = worst_leaf_gap(
        program["grad_norms"], reference["grad_norms"])
    out["change_norm_gap"], out["change_norm_leaf"] = worst_leaf_gap(
        program["change_norms"], reference["change_norms"])
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every limit must be met by a number that is there. Returns
    (correct, {name: {"value", "limit", "ok"}}) in the limits' order."""
    table, correct = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        ok = value is not None and value == value and value <= limit
        table[name] = {"value": value, "limit": limit, "ok": ok}
        correct = correct and ok
    return correct, table
