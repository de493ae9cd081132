"""``moe_experts_share``: self time of the routed experts' products (the
operations named in ``program.trace_ops``) over the traced window's busy
time: how much of the device's work the chip's share of the experts is.
Reads nothing where no such operation ran."""

from benchmark.metrics import _moe


def read(ctx: dict):
    trace = ctx.get("trace")
    seconds = _moe.named_seconds(ctx)
    if seconds is None or trace["busy_s"] <= 0:
        return None
    return 100.0 * seconds / trace["busy_s"]
