"""``dsa_step_mfu``: the sparse-attention decoder's whole local step as a
share of the chip's peak: the model FLOPs of one optimizer step at the
cell's shapes (``lib/flops_dsa_moe.py``: the projections with their
adapters, attention's two products over the pairs the counter
``dsa_selected_count`` gave, the indexer's products over every causal pair
and its projections once (no gradient), the router, the held experts'
products for the assignments ``moe_local_count`` gave, the head;
recomputation not credited) over ``step_ms`` over the peak of
``lib/peaks.json``. Reads nothing where the program ships no such
counters."""

from benchmark.lib import flops_dsa_moe, spec
from benchmark.metrics import _common, _scmoe


def read(ctx: dict):
    ms = _common.mean_over_rounds(
        ctx, lambda m: _common.step_ms(m, ctx["learner"]))
    selected = _scmoe.device_count(ctx, "dsa_selected_count")
    local = _scmoe.device_count(ctx, "moe_local_count")
    if ms is None or selected is None or local is None:
        return None
    peak = spec.peaks(ctx["device_kind"])["flops_per_s"]
    work = flops_dsa_moe.train_step_flops(
        ctx["cfg"], ctx["traffic"]["shape"], selected, local)
    return 100.0 * work / (ms / 1e3) / peak
