"""``report_ms``: what the round waits for outside the learner's task: the
controller's ``wait_uplinks`` phase less the task's wall time up to its
report (the sum of its tiles): the chunked stream of the uplink, the
controller's decode and insert, and what of the dispatch RPC falls after the
phase began; mean over the window's rounds."""

from benchmark.metrics import _common, _task


def read(ctx: dict):
    def pick(m):
        wait = _common.phase_ms(m, "wait_uplinks")
        task = _task.tiles(m, ctx.get("learner", ""))
        return (None if wait is None or task is None
                else wait - sum(task.values()))

    return _common.mean_over_rounds(ctx, pick)
