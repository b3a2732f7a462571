"""The comparison that decides ``correct``, whatever the family: the numbers
of a training cell and of a serving cell, each from the program's readings and
the family's plain reference. Each number compared has a limit of its own,
kept in the cell's file (``benchmark/workloads/<cell>.json``) with the
readings it was set from in PERF.md."""

from __future__ import annotations

import math
from statistics import median

import numpy as np

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


def leaf_norms(tree) -> dict[str, float]:
    """L2 norm of every leaf, by its path."""
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda leaves: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in leaves])(
        [leaf for _, leaf in flat]
    )
    return {jax.tree_util.keystr(path): float(n) for (path, _), n in zip(flat, norms)}


def served_gaps(logits_at, cfg: dict, seed: int, rows: list[tuple[np.ndarray, np.ndarray]], dtype, row_block: int,
                pad_to: int, pad_outputs: int, control: bool = False) -> dict:
    """For each (prompt, served tokens) row, at each served position, the gap
    by which the served token's reference logit lies below the reference's
    best. ``logits_at`` is the family's reference (``logits_at(cfg, seed, ids,
    positions, dtype, control=False)`` -> [B, n, V]). With ``control``, the
    token judged at each position is the one the family's low-precision
    control puts first there, not the served one. Returns the widest gap and
    where it is, the mean gap, the share of tokens that are the reference's
    own first choice, and how many tokens were compared. Every block has the
    one shape [row_block, pad_to] with ``pad_outputs`` positions read, so that
    the reference's programs compile once per checkout."""
    gaps, widest, where = [], 0.0, None
    for lo in range(0, len(rows), row_block):
        block = rows[lo:lo + row_block]
        members = list(range(lo, lo + len(block)))
        ids = np.zeros((row_block, pad_to), np.int32)
        positions = np.zeros((row_block, pad_outputs), np.int32)
        for r, (prompt, generated) in enumerate(block):
            ids[r, : prompt.size] = prompt
            ids[r, prompt.size : prompt.size + generated.size - 1] = generated[:-1]
            positions[r, : generated.size] = prompt.size - 1 + np.arange(generated.size)
        reference = logits_at(cfg, seed, ids, positions, dtype)
        judged = logits_at(cfg, seed, ids, positions, dtype, control=True).argmax(-1) if control else None
        for r, (prompt, generated) in enumerate(block):
            tokens = judged[r, : generated.size] if control else generated
            steps = np.arange(generated.size)
            row_gaps = reference[r, steps].max(-1) - reference[r, steps, tokens]
            gaps.extend(row_gaps.tolist())
            if row_gaps.size and not row_gaps.max() <= widest:
                at = int(row_gaps.argmax())
                widest, where = float(row_gaps.max()), {"row": members[r], "token": at, "prompt_len": int(prompt.size)}
    if not gaps:
        return {"logit_gap_max": float("nan"), "logit_gap_mean": float("nan"), "where": None, "tokens_compared": 0, "agree": 0.0}
    return {
        "logit_gap_max": widest, "logit_gap_mean": float(np.mean(gaps)), "where": where,
        "tokens_compared": len(gaps), "agree": float(np.mean(np.asarray(gaps) == 0.0)),
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
