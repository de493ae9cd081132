"""``decode_launch_ms``: the decode step call's launch, from the loop's own
sums in the ``decode.loop`` events that ended inside the window: the change
of ``step_launch_s`` (from the call's entry, before its inputs are placed,
to the jitted call's return: placing, argument handling, dispatch) over the
change of ``steps``. Reads nothing from a program whose events lack it."""

from benchmark.metrics import _sink


def read(ctx: dict):
    launch = steps = 0.0
    found = False
    for rec in _sink.events(ctx, "decode.loop"):
        attrs = rec.get("attrs", {})
        if "step_launch_s" in attrs:
            found = True
            launch += float(attrs["step_launch_s"])
            steps += float(attrs.get("steps", 0))
    return 1e3 * launch / steps if found and steps > 0 else None
