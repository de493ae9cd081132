"""``scmoe_experts_roofline``: the routed experts' products as a share of
their roofline in the traced rounds: the least time the chip could take
(``lib/flops_scmoe.py``: three products a pass, forward, rematerialized
forward and the backward to the activations, with the held assignments the
program's counter ``moe_local_count`` gave; the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s — bytes: every held expert's matrices
are read once a pass, 1.21 GB a layer) over the self time of the
operations the configuration names (``program.trace_ops``): the same work
whatever implements it. Reads nothing where no such operation ran or the
program ships no counter."""

from benchmark.lib import flops_scmoe
from benchmark.metrics import _moe, _scmoe


def read(ctx: dict):
    seconds = _moe.named_seconds(ctx)
    local = _scmoe.device_count(ctx, "moe_local_count")
    if seconds is None or local is None:
        return None
    return _scmoe.roofline_share(ctx, seconds, flops_scmoe.experts_cost(
        ctx["cfg"], local, remat=bool(ctx["cfg"]["program"].get("remat"))))
