"""``kda_moe_step_mfu``: the GLM-5 hybrid's whole local step as a share of
the chip's peak: the model FLOPs of one optimizer step at the cell's shapes
(``lib/flops_kda_moe.py``: KDA's projections and its chunked recurrence,
mHC's projections and mixing, latent attention at 256 / 256 over the pairs
the counter ``dsa_selected_count`` gave, the indexer over the pooled keys
once (no gradient), the dense layer, the routers, the shared experts, the
held experts' products for the assignments ``moe_local_count`` gave, the
head; recomputation not credited) over ``step_ms`` over the peak of
``lib/peaks.json``. Reads nothing where the program ships no such
counters."""

from benchmark.lib import flops_kda_moe, spec
from benchmark.metrics import _common, _scmoe


def read(ctx: dict):
    ms = _common.mean_over_rounds(
        ctx, lambda m: _common.step_ms(m, ctx["learner"]))
    selected = _scmoe.device_count(ctx, "dsa_selected_count")
    local = _scmoe.device_count(ctx, "moe_local_count")
    if ms is None or selected is None or local is None:
        return None
    peak = spec.peaks(ctx["device_kind"])["flops_per_s"]
    work = flops_kda_moe.train_step_flops(
        ctx["cfg"], ctx["traffic"]["shape"], selected, local)
    return 100.0 * work / (ms / 1e3) / peak
