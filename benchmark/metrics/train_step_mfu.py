"""``train_step_mfu``: the whole local step's share of the chip's peak: the
benchmark's model FLOPs of one optimizer step at the cell's shapes
(``lib/flops.py``; recomputation not credited) over ``step_ms`` over the
peak of ``lib/peaks.json``."""

from benchmark.lib import flops, spec
from benchmark.metrics import _common


def read(ctx: dict):
    ms = _common.mean_over_rounds(
        ctx, lambda m: _common.step_ms(m, ctx["learner"]))
    if ms is None:
        return None
    peak = spec.peaks(ctx["device_kind"])["flops_per_s"]
    work = flops.train_step_flops(ctx["cfg"], ctx["traffic"]["shape"])
    return 100.0 * work / (ms / 1e3) / peak
