"""``hybrid_step_mfu``: the hybrid's whole local step as a share of the
chip's peak: the model FLOPs of one optimizer step at the cell's shapes
(``lib/flops_hybrid.py``: projections, MLPs, the tied head, the attention
block's scores, the recurrence's multiply-adds; recomputation not
credited) over ``step_ms`` over the peak of ``lib/peaks.json``. What
``train_step_mfu`` is to the families ``lib/flops.py`` knows."""

from benchmark.lib import flops_hybrid, spec
from benchmark.metrics import _common


def read(ctx: dict):
    ms = _common.mean_over_rounds(
        ctx, lambda m: _common.step_ms(m, ctx["learner"]))
    if ms is None:
        return None
    peak = spec.peaks(ctx["device_kind"])["flops_per_s"]
    work = flops_hybrid.train_step_flops(ctx["cfg"], ctx["traffic"]["shape"])
    return 100.0 * work / (ms / 1e3) / peak
