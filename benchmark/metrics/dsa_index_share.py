"""``dsa_index_share``: self time of the choice of keys (the ``dsa_select``
kernel) over the traced window's busy time: how much of the device's work
choosing the keys is. Reads nothing where no such kernel ran."""

from benchmark.metrics import dsa_index_roofline


def read(ctx: dict):
    spent = dsa_index_roofline.seconds(ctx)
    if spent is None or ctx["trace"]["busy_s"] <= 0:
        return None
    return 100.0 * spent / ctx["trace"]["busy_s"]
