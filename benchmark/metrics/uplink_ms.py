"""``uplink_ms``: what the round waits for beyond the learner's own steps:
the controller's ``wait_uplinks`` phase less ``local_steps x step_ms``
(load, host-device copies, dump, codec, the chunked stream and the insert),
mean over the window's rounds."""

from benchmark.metrics import _common


def read(ctx: dict):
    steps = int(ctx["traffic"]["shape"]["local_steps"])

    def pick(m):
        wait = _common.phase_ms(m, "wait_uplinks")
        step = _common.step_ms(m, ctx["learner"])
        return None if wait is None or step is None else wait - steps * step

    return _common.mean_over_rounds(ctx, pick)
