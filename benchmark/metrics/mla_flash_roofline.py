"""``mla_flash_roofline``: the flash-attention kernels' share of their
roofline in the traced rounds, at latent attention's head shape (scores
over 192 columns, values over 128): the least time the chip could take for
their work (``lib/flops_mla_moe.py``: forward, the rematerialized forward
and backward of every block; the larger of FLOPs over peak FLOP/s and bytes
over peak bytes/s) over the self time of the ``flash_*`` events alone (the
step holds other Pallas kernels). Reads nothing where no such event ran."""

from benchmark.lib import flops_mla_moe, spec
from benchmark.metrics import _moe, _scan


def read(ctx: dict):
    seconds = _moe.flash_seconds(ctx.get("trace"))
    if seconds is None:
        return None
    steps = _scan.steps(ctx)
    if steps <= 0:
        return None
    peaks = spec.peaks(ctx["device_kind"])
    cost = flops_mla_moe.mla_flash_cost(
        ctx["cfg"], ctx["traffic"]["shape"],
        remat=bool(ctx["cfg"]["program"].get("remat")))
    least = max(cost["flops"] / peaks["flops_per_s"],
                cost["bytes"] / peaks["bytes_per_s"]) * steps
    return 100.0 * least / seconds
