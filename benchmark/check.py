"""The comparison that decides ``correct`` (no JAX).

The program's first three training steps, driven through the window's own
feed and step call, against the configuration's plain reference following
the same rows from the same seeded weights: each step's loss, the norm of
the first gradient as the optimizer got it, and the norm of the parameters'
change after the three — the last two by the worst leaf.
"""

from __future__ import annotations

import math
import statistics


def worst_leaf_gap(program: dict, reference: dict):
    """``(gap, leaf)``: the largest gap between the program's norm of a leaf
    and the reference's — not the norm of their difference — measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    if set(program) != set(reference):
        raise ValueError("the program and the reference name different "
                         f"leaves: {sorted(set(program) ^ set(reference))}")
    floor = statistics.median(reference.values())
    worst, where = 0.0, None
    for leaf, ref in reference.items():
        scale = max(ref, floor)
        gap = abs(program[leaf] - ref) / scale if scale > 0 else math.inf
        if math.isnan(gap):
            return math.inf, leaf       # a norm that is not a number
        if gap > worst:
            worst, where = gap, leaf
    return worst, where


def numbers(program: dict, reference: dict) -> dict:
    """Every number compared, from the two sides' readings."""
    out = {}
    for i, (p, r) in enumerate(zip(program["losses"], reference["losses"])):
        out[f"loss_step{i + 1}_rel"] = abs(p - r) / abs(r)
    out["first_grad_norm_gap"], out["first_grad_norm_leaf"] = \
        worst_leaf_gap(program["grad_norms"], reference["grad_norms"])
    out["param_change_norm_gap"], out["param_change_norm_leaf"] = \
        worst_leaf_gap(program["change_norms"], reference["change_norms"])
    return out


def judge(values: dict, limits: dict) -> list:
    """One row a number: ``{"name", "value", "limit", "ok"}``.  A number
    without a limit, or a limit without a number, is a failure."""
    rows = []
    for name, limit in limits.items():
        value = values.get(name)
        ok = value is not None and math.isfinite(value) and value <= limit
        rows.append({"name": name, "value": value, "limit": limit,
                     "ok": bool(ok)})
    return rows


def epoch_accounting(id_arrays: list, n_rows: int, same_order: bool) -> dict:
    """Account for every row the feed handed over, by the rows' own
    numbers, in the order they came (full batches and dropped short ones).
    Each complete pass over the data must hold every row exactly once — in
    the first pass's order too where the plane re-feeds without a shuffle —
    and the last, partial pass must hold no row twice (or, unshuffled, be a
    prefix of the first pass).  ``bad_rows`` is 0 when all of that holds."""
    import numpy as np

    ids = (np.concatenate([np.asarray(a, np.int64) for a in id_arrays])
           if id_arrays else np.zeros(0, np.int64))
    bad = int(((ids < 0) | (ids >= n_rows)).sum())
    whole = len(ids) // n_rows
    first = ids[:n_rows]
    for e in range(whole):
        epoch = ids[e * n_rows:(e + 1) * n_rows]
        bad += int(n_rows - len(np.unique(epoch)))
        if same_order and e:
            bad += int((epoch != first).sum())
    tail = ids[whole * n_rows:]
    bad += int(len(tail) - len(np.unique(tail)))
    if same_order and whole:
        bad += int((tail != first[:len(tail)]).sum())
    return {"rows_seen": int(len(ids)), "passes_complete": int(whole),
            "bad_rows": bad}
