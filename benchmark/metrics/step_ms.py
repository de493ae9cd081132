"""``step_ms``: the learner's own time per local step
(``TrainResult.ms_per_step`` as ``RoundProfile.learners[*].device`` carries
it: the host clock around the scan's ``block_until_ready``, over its steps),
mean over the window's rounds."""

from benchmark.metrics import _common


def read(ctx: dict):
    return _common.mean_over_rounds(
        ctx, lambda m: _common.step_ms(m, ctx["learner"]))
