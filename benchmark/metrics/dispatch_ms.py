"""``dispatch_ms``: the controller's ``dispatch`` phase of the round
(``RoundProfile.phases``), mean over the window's rounds."""

from benchmark.metrics import _common


def read(ctx: dict):
    return _common.mean_over_rounds(
        ctx, lambda m: _common.phase_ms(m, "dispatch"))
