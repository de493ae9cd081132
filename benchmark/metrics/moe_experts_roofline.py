"""``moe_experts_roofline``: the routed experts' products as a share of
their roofline in the traced rounds: the least time the chip could take
(``lib/flops_mla_moe.py``: three products a pass, forward, rematerialized
forward and the backward to the activations, with the held assignments the
program's counter ``moe_local_count`` gave; the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s — bytes: every held expert's matrices
are read once a pass) over the self time of the operations the
configuration names (``program.trace_ops``): the same work whatever
implements it. Reads nothing where no such operation ran or the program
ships no counter."""

from benchmark.lib import flops_mla_moe, spec
from benchmark.metrics import _moe, _scan


def read(ctx: dict):
    seconds = _moe.named_seconds(ctx)
    local = _moe.local_count(ctx)
    if seconds is None or local is None:
        return None
    steps = _scan.steps(ctx)
    if steps <= 0:
        return None
    peaks = spec.peaks(ctx["device_kind"])
    cost = flops_mla_moe.moe_experts_cost(
        ctx["cfg"], local, remat=bool(ctx["cfg"]["program"].get("remat")))
    least = max(cost["flops"] / peaks["flops_per_s"],
                cost["bytes"] / peaks["bytes_per_s"]) * steps
    return 100.0 * least / seconds
