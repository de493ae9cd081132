"""``device_idle_share.serve``: 1 - the union of the device's operation
intervals over the traced window (some seconds of the decode loop), from
the profiler trace the benchmark's probe starts in the gateway."""

from benchmark.metrics import _common


def read(ctx: dict):
    return _common.idle_share(ctx)
