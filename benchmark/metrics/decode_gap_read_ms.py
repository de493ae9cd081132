"""``decode_gap_read_ms``: device idle of the traced window in the *read*
phase of a decode call (its program's end to the tokens on the host), over
the window's step runs (``metrics/_ticks.py``)."""

from benchmark.metrics import _ticks


def read(ctx: dict):
    return _ticks.per_step_ms(ctx, "read")
