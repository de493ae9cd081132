"""``decode_step_ms``: the window over the decode steps it held (the
difference of ``describe()["decode"]["steps"]`` across it): one iteration of
the loop with the admissions' prefills in it. The gateway's own
``tokens_per_sec`` is an EWMA and is not read."""


def read(ctx: dict):
    steps = ctx.get("decode_steps")
    if not steps or steps <= 0:
        return None
    return 1e3 * ctx["window_s"] / steps
