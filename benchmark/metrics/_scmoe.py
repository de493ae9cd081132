"""What the readers of family ``scmoe`` share beside ``_moe``'s: a counter
of the program's own, read where the round's profile carries it, and a
kernel's share of its roofline."""

from __future__ import annotations

from benchmark.lib import spec
from benchmark.metrics import _common, _scan


def device_count(ctx: dict, name: str):
    """Counter ``name`` (``moe_local_count``, ``moe_zero_count``: a step,
    summed over the layers) as the learner shipped it under
    ``RoundProfile.learners[*].device``, mean over the window's rounds;
    nothing where the program ships no such counter."""
    def pick(m):
        value = (m.get("profile", {}).get("learners", {})
                 .get(ctx.get("learner", ""), {}).get("device", {})
                 .get(name))
        return float(value) if value is not None else None

    return _common.mean_over_rounds(ctx, pick)


def roofline_share(ctx: dict, seconds: float, cost: dict):
    """100 x the least time the chip could take for ``cost`` (``flops``
    and ``bytes`` of one optimizer step: the larger of FLOPs over peak
    FLOP/s and bytes over peak bytes/s) in the traced window's steps, over
    the ``seconds`` the operations took there."""
    steps = _scan.steps(ctx)
    if steps <= 0:
        return None
    peaks = spec.peaks(ctx["device_kind"])
    least = max(cost["flops"] / peaks["flops_per_s"],
                cost["bytes"] / peaks["bytes_per_s"]) * steps
    return 100.0 * least / seconds
