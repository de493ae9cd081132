"""``decode_gap_named_share``: 100 x the device idle of the traced window
that the decode loop's stamps assign to a host phase (read, loop, launch)
over all device idle of the window (``metrics/_ticks.py``); what is left
fell inside a program or in a call whose program the trace does not hold."""

from benchmark.metrics import _ticks


def read(ctx: dict):
    got = _ticks.read(ctx)
    if not got or got["idle_s"] <= 0:
        return None
    return 100.0 * (got["read_s"] + got["loop_s"] + got["launch_s"]) \
        / got["idle_s"]
