"""The comparison that decides ``correct``. Each number compared has a limit
of its own, kept in the cell's file (``benchmark/workloads/<cell>.json``) with
the readings it was set from in PERF.md."""

from __future__ import annotations

import math
from statistics import median

# a leaf whose reference gradient is under this share of the median leaf's is
# nought to rounding (a key's bias under softmax): Adam moves it by round-off
# alone, so it is left out of the change
DEAD_GRADIENT_SHARE = 1e-3


def worst_leaf_gap(program: dict[str, float], reference: dict[str, float], leaves=None) -> tuple[float, str]:
    """Widest gap between the program's norm and the reference's over the
    leaves, measured against the reference's norm of that leaf or of the
    median leaf, whichever is larger. Returns (gap, leaf)."""
    leaves = list(reference) if leaves is None else list(leaves)
    floor = median(reference[k] for k in leaves)
    worst, where = 0.0, ""
    for k in leaves:
        gap = abs(program[k] - reference[k]) / max(reference[k], floor)
        if not gap <= worst:  # a NaN gap is the worst there is
            worst, where = gap, k
    return worst, where


def live_leaves(reference_grad_norms: dict[str, float]) -> list[str]:
    floor = DEAD_GRADIENT_SHARE * median(reference_grad_norms.values())
    return [k for k, n in reference_grad_norms.items() if n >= floor]


def training_numbers(program: dict, reference: dict) -> dict[str, float]:
    """The numbers of a training cell: the widest relative gap of the first
    steps' losses, and the worst leaf's gap of the first gradient's norm and
    of the parameters' change."""
    loss_gap = max(
        abs(p - r) / abs(r) if math.isfinite(p) else math.inf
        for p, r in zip(program["losses"], reference["losses"])
    )
    live = live_leaves(reference["grad_norms"])
    grad_gap, grad_leaf = worst_leaf_gap(program["grad_norms"], reference["grad_norms"])
    change_gap, change_leaf = worst_leaf_gap(program["change_norms"], reference["change_norms"], live)
    return {
        "loss_gap": loss_gap, "grad_norm_gap": grad_gap, "change_norm_gap": change_gap,
        "_grad_leaf": grad_leaf, "_change_leaf": change_leaf,
    }


def verdict(numbers: dict[str, float], limits: dict[str, float]) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers that have a
    limit. A number that is not finite, or missing, is over its limit."""
    table, correct = {}, True
    for name, limit in limits.items():
        value = numbers.get(name, math.nan)
        ok = isinstance(value, (int, float)) and math.isfinite(value) and value <= limit
        correct = correct and ok
        table[name] = {"value": value, "limit": limit}
    return correct, table
