"""``decode_gap_loop_ms``: device idle of the traced window in the *loop*
phase (a decode call's return to the next call's entry: hand-out,
retirement, admission under the lock), over the window's step runs
(``metrics/_ticks.py``)."""

from benchmark.metrics import _ticks


def read(ctx: dict):
    return _ticks.per_step_ms(ctx, "loop")
