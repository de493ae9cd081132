"""The comparison that decides ``correct``: each number beside its limit."""

from __future__ import annotations

import statistics

import numpy as np

# a leaf whose reference first gradient is under this share of the median
# leaf's moves under Adam by round-off alone: left out of the change
ZERO_GRAD_SHARE = 1e-3


def change_norms(final: dict, initial: dict) -> dict:
    """Per leaf, the norm of its change (names as the program ships them)."""
    return {name: float(np.linalg.norm(
        np.asarray(final[name], np.float64)
        - np.asarray(initial[name], np.float64)))
        for name in initial if name in final
        and np.shape(final[name]) == np.shape(initial[name])}


def train_numbers(loss: float, change: dict, ref: dict) -> dict:
    """Numbers of a training round against the reference's ``ref``
    (``loss``: mean of its steps' losses; ``leaf``: name -> ``grad0``,
    ``change`` norms).

    - ``loss_gap``: the round's mean step loss, relative.
    - ``change_gap``: by the worst leaf, the gap between the two norms of
      the leaf's change over the round, against the reference's norm of
      that leaf or of the median leaf, whichever is larger.
    - ``leaves_missing``: reference leaves the community model lacks or
      holds in another shape; exact.
    """
    leaves = ref["leaf"]
    med_grad = statistics.median(v["grad0"] for v in leaves.values())
    med_change = statistics.median(v["change"] for v in leaves.values())
    worst, worst_leaf, kept = 0.0, "", 0
    for name, v in leaves.items():
        if name not in change or v["grad0"] < ZERO_GRAD_SHARE * med_grad:
            continue
        kept += 1
        gap = abs(change[name] - v["change"]) / max(v["change"], med_change)
        if gap >= worst:
            worst, worst_leaf = gap, name
    return {"loss_gap": abs(loss - ref["loss"]) / abs(ref["loss"]),
            "change_gap": worst,
            "leaves_missing": float(sum(1 for n in leaves
                                        if n not in change)),
            "_worst_leaf": worst_leaf, "_leaves_compared": kept}


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: [number, limit]}) over the numbers that have a
    limit; a number that is missing or not finite is not correct."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        compared[name] = [value, limit]
        if value is None or not np.isfinite(value) or value > limit:
            ok = False
    return ok, compared
