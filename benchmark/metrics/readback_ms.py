"""``readback_ms``: tile ``readback`` of the learner's task waterfall
(``RoundProfile.learners[lid]["task"]``): ``get_variables()`` at the end of
``train``: the whole tree device -> host (``train.readback``); mean over the
window's rounds. Reads nothing from a program that ships no waterfall."""

from benchmark.metrics import _task


def read(ctx: dict):
    return _task.tile_ms(ctx, "readback")
