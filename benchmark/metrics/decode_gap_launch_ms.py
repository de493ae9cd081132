"""``decode_gap_launch_ms``: device idle of the traced window in the
*launch* phase of a decode call (its entry to its program's start: inputs
placed, argument handling, dispatch, eager helpers such as the prefill's
``reshape``), over the window's step runs (``metrics/_ticks.py``)."""

from benchmark.metrics import _ticks


def read(ctx: dict):
    return _ticks.per_step_ms(ctx, "launch")
