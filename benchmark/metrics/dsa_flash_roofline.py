"""``dsa_flash_roofline``: the selected-flash kernels' share of their
roofline in the traced rounds: the least time the chip could take for
attention over the CHOSEN pairs (``lib/flops_dsa_moe.py``: the pairs the
counter ``dsa_selected_count`` gave, forward twice under remat and the
backward's five products; the larger of FLOPs over peak FLOP/s and bytes
over peak bytes/s) over the self time of the ``flash_*_sel`` events: the
same work whatever implements it, so a walk over more pairs than were
chosen reads as a lower share. Reads nothing where no such event ran or
the program ships no counter."""

from benchmark.lib import flops_dsa_moe
from benchmark.metrics import _scmoe

PREFIX, SUFFIX = "flash_", "_sel"


def read(ctx: dict):
    kernels = (ctx.get("trace") or {}).get("kernel_ops_s", {})
    seconds = sum(v for k, v in kernels.items()
                  if k.startswith(PREFIX) and k.endswith(SUFFIX))
    selected = _scmoe.device_count(ctx, "dsa_selected_count")
    if seconds <= 0 or selected is None:
        return None
    return _scmoe.roofline_share(ctx, seconds, flops_dsa_moe.flash_cost(
        ctx["cfg"], ctx["traffic"]["shape"], selected,
        remat=bool(ctx["cfg"]["program"].get("remat"))))
