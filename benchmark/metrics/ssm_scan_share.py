"""``ssm_scan_share``: self time of the ``ssm_scan_*`` kernels over the
traced window's busy time: how much of the device's work the new mechanism
is. Reads nothing where no such event ran."""

from benchmark.metrics import _scan


def read(ctx: dict):
    trace = ctx.get("trace")
    seconds = _scan.kernel_seconds(trace)
    if seconds is None or trace["busy_s"] <= 0:
        return None
    return 100.0 * seconds / trace["busy_s"]
