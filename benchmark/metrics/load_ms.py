"""``load_ms``: tile ``load`` of the learner's task waterfall
(``RoundProfile.learners[lid]["task"]``): the downlink blob decoded,
decrypted and backfilled into the engine's tree (``learner.load_model``);
mean over the window's rounds. Reads nothing from a program that ships no
waterfall."""

from benchmark.metrics import _task


def read(ctx: dict):
    return _task.tile_ms(ctx, "load")
