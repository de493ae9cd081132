"""``device_idle_share.train``: 1 - the union of the device's operation
intervals over the traced window (whole rounds), from the profiler trace
the benchmark's probe starts in the learner."""

from benchmark.metrics import _common


def read(ctx: dict):
    return _common.idle_share(ctx)
