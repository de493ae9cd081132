"""``kda_roofline``: the KDA kernels' share of their roofline in the traced
rounds: the least time the chip could take for the chunked recurrence's
work (``lib/flops_kda_moe.py:kda_cost``: forward twice under remat and the
backward, every KDA layer, at the types the program holds; the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s — bytes) over the self
time of the ``kda_fwd`` and ``kda_bwd`` kernels, read by name: the same
work whatever implements it. Reads nothing where no such kernel ran."""

from benchmark.lib import flops_kda_moe
from benchmark.metrics import _scmoe

KERNELS = ("kda_fwd", "kda_bwd")


def seconds(ctx: dict):
    """Self time of the KDA kernels in the traced window, or nothing
    where they did not run."""
    kernels = (ctx.get("trace") or {}).get("kernel_ops_s", {})
    found = sum(kernels.get(name, 0.0) for name in KERNELS)
    return found if found > 0 else None


def read(ctx: dict):
    spent = seconds(ctx)
    if spent is None:
        return None
    return _scmoe.roofline_share(ctx, spent, flops_kda_moe.kda_cost(
        ctx["cfg"], ctx["traffic"]["shape"],
        remat=bool(ctx["cfg"]["program"].get("remat"))))
