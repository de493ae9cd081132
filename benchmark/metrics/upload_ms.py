"""``upload_ms``: tile ``upload`` of the learner's task waterfall
(``RoundProfile.learners[lid]["task"]``): ``set_variables``: the whole tree
host -> device (``learner.upload``); a copy still in flight when it returns
is part of the steps; mean over the window's rounds. Reads nothing from a
program that ships no waterfall."""

from benchmark.metrics import _task


def read(ctx: dict):
    return _task.tile_ms(ctx, "upload")
