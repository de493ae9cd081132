"""``feed_ms``: tile ``feed`` of the learner's task waterfall
(``RoundProfile.learners[lid]["task"]``): batches drawn, stacked and placed,
summed over the task's chunks (``train.feed``); mean over the window's
rounds. Reads nothing from a program that ships no waterfall."""

from benchmark.metrics import _task


def read(ctx: dict):
    return _task.tile_ms(ctx, "feed")
