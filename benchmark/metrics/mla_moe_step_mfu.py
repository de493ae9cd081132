"""``mla_moe_step_mfu``: the latent-attention decoder's whole local step as
a share of the chip's peak: the model FLOPs of one optimizer step at the
cell's shapes (``lib/flops_mla_moe.py``: projections, attention's scores at
192 and values at 128, the dense MLP, router, shared expert and the held
assignments the shapes expect, the head; recomputation not credited) over
``step_ms`` over the peak of ``lib/peaks.json``."""

from benchmark.lib import flops_mla_moe, spec
from benchmark.metrics import _common


def read(ctx: dict):
    ms = _common.mean_over_rounds(
        ctx, lambda m: _common.step_ms(m, ctx["learner"]))
    if ms is None:
        return None
    peak = spec.peaks(ctx["device_kind"])["flops_per_s"]
    work = flops_mla_moe.train_step_flops(ctx["cfg"],
                                          ctx["traffic"]["shape"])
    return 100.0 * work / (ms / 1e3) / peak
