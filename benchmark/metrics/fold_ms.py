"""``fold_ms``: ``RoundMetadata.aggregation_duration_ms``, the fold of the
round's uplinks into the community model, mean over the window's rounds."""

from benchmark.metrics import _common


def read(ctx: dict):
    return _common.mean_over_rounds(
        ctx, lambda m: (float(m["aggregation_duration_ms"])
                        if m.get("aggregation_duration_ms") else None))
