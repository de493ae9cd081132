"""``scmoe_flash_roofline``: the flash-attention kernels' share of their
roofline in the traced rounds, at the shortcut-connected decoder's shape
(two latent-attention sublayers a layer, 64 heads, scores over 192
columns, values over 128): the least time the chip could take for their
work (``lib/flops_scmoe.py``: forward, the rematerialized forward and
backward of every sublayer; the larger of FLOPs over peak FLOP/s and bytes
over peak bytes/s) over the self time of the ``flash_*`` events alone (the
step holds other Pallas kernels). Reads nothing where no such event ran."""

from benchmark.lib import flops_scmoe
from benchmark.metrics import _moe, _scmoe


def read(ctx: dict):
    seconds = _moe.flash_seconds(ctx.get("trace"))
    if seconds is None:
        return None
    return _scmoe.roofline_share(ctx, seconds, flops_scmoe.flash_cost(
        ctx["cfg"], ctx["traffic"]["shape"],
        remat=bool(ctx["cfg"]["program"].get("remat"))))
