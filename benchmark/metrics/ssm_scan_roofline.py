"""``ssm_scan_roofline``: the selective-scan kernels' share of their
roofline in the traced rounds: the least time the chip could take for
their work (``lib/flops_hybrid.py``: forward, the rematerialized forward
and backward of every Mamba block; the larger of operations over peak
FLOP/s and bytes over peak bytes/s — bytes, by a factor of about twenty)
over the self time of the ``ssm_scan_*`` events in the device trace. Reads
nothing where no such event ran."""

from benchmark.lib import flops_hybrid, spec
from benchmark.metrics import _scan


def read(ctx: dict):
    seconds = _scan.kernel_seconds(ctx.get("trace"))
    if seconds is None:
        return None
    steps = _scan.steps(ctx)
    if steps <= 0:
        return None
    peaks = spec.peaks(ctx["device_kind"])
    cost = flops_hybrid.ssm_scan_cost(
        ctx["cfg"], ctx["traffic"]["shape"],
        remat=bool(ctx["cfg"]["program"].get("remat")))
    least = max(cost["flops"] / peaks["flops_per_s"],
                cost["bytes"] / peaks["bytes_per_s"]) * steps
    return 100.0 * least / seconds
