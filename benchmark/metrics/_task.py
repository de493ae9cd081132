"""The learner's task waterfall as the round readers see it: the tiles the
learner cut on its own clock (``metisfl_tpu/learner/learner.py``), in
milliseconds, contiguous from the RunTask RPC's acceptance to the start of
its report, under ``RoundProfile.learners[lid]["task"]`` beside ``start``,
that acceptance as ``time.time()``."""

from __future__ import annotations

from benchmark.metrics import _common


def tiles(round_meta: dict, learner: str):
    """{tile: ms} of the round's train task, or nothing where the program
    ships none."""
    task = (round_meta.get("profile", {}).get("learners", {})
            .get(learner, {}).get("task"))
    if not task:
        return None
    return {k: float(v) for k, v in task.items() if k != "start"}


def tile_ms(ctx: dict, tile: str):
    def pick(m):
        task = tiles(m, ctx.get("learner", ""))
        return None if task is None else task.get(tile)

    return _common.mean_over_rounds(ctx, pick)
