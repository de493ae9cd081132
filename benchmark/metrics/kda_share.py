"""``kda_share``: self time of the KDA kernels (``kda_fwd``, ``kda_bwd``)
over the traced window's busy time: how much of the device's work the
linear-attention recurrence is. Reads nothing where no such kernel ran."""

from benchmark.metrics import kda_roofline


def read(ctx: dict):
    spent = kda_roofline.seconds(ctx)
    if spent is None or ctx["trace"]["busy_s"] <= 0:
        return None
    return 100.0 * spent / ctx["trace"]["busy_s"]
