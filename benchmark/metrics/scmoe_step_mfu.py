"""``scmoe_step_mfu``: the shortcut-connected decoder's whole local step as
a share of the chip's peak: the model FLOPs of one optimizer step at the
cell's shapes (``lib/flops_scmoe.py``: both sublayers' projections with
their adapters, attention's scores at 192 and values at 128 under the
diagonal, both dense FFNs, the router's 768 columns, the routed products
for the held assignments the counter ``moe_local_count`` gave, a scale and
an add for each assignment ``moe_zero_count`` gave, the head; recomputation
not credited) over ``step_ms`` over the peak of ``lib/peaks.json``. Reads
nothing where the program ships no such counters."""

from benchmark.lib import flops_scmoe, spec
from benchmark.metrics import _common, _scmoe


def read(ctx: dict):
    ms = _common.mean_over_rounds(
        ctx, lambda m: _common.step_ms(m, ctx["learner"]))
    local = _scmoe.device_count(ctx, "moe_local_count")
    zero = _scmoe.device_count(ctx, "moe_zero_count")
    if ms is None or local is None or zero is None:
        return None
    peak = spec.peaks(ctx["device_kind"])["flops_per_s"]
    work = flops_scmoe.train_step_flops(
        ctx["cfg"], ctx["traffic"]["shape"], local, zero)
    return 100.0 * work / (ms / 1e3) / peak
