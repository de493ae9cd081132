"""``dsa_index_roofline``: the choice of keys as a share of its roofline in
the traced rounds: the least time the chip could take for the indexer's
score products over every causal pair, reading its inputs and writing the
packed choice once a step (``lib/flops_dsa_moe.py``; the larger of FLOPs
over peak FLOP/s and bytes over peak bytes/s) over the self time of the
``dsa_select`` kernel: the same work whatever implements it. Reads nothing
where no such kernel ran."""

from benchmark.lib import flops_dsa_moe
from benchmark.metrics import _scmoe

KERNEL = "dsa_select"


def seconds(ctx: dict):
    """Self time of ``dsa_select`` in the traced window, or nothing where
    it did not run."""
    found = (ctx.get("trace") or {}).get("kernel_ops_s", {}).get(KERNEL, 0.0)
    return found if found > 0 else None


def read(ctx: dict):
    spent = seconds(ctx)
    if spent is None:
        return None
    return _scmoe.roofline_share(ctx, spent, flops_dsa_moe.index_cost(
        ctx["cfg"], ctx["traffic"]["shape"]))
