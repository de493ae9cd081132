"""``encode_ms``: tile ``encode`` of the learner's task waterfall
(``RoundProfile.learners[lid]["task"]``): the uplink blob built from the
engine's tree (``learner.dump_model``); mean over the window's rounds. Reads
nothing from a program that ships no waterfall."""

from benchmark.metrics import _task


def read(ctx: dict):
    return _task.tile_ms(ctx, "encode")
