"""``admit_wait_ms``: mean ``wait_ms`` of the gateway's ``decode.slot``
events that ended inside the window: enqueue to the start of the request's
prefill, the wait for a free slot alone (``slot_ms`` is this wait plus the
slot's occupancy). Reads nothing from a program whose events lack it."""

from benchmark.metrics import _sink


def read(ctx: dict):
    return _sink.mean_attr(ctx, "decode.slot", "wait_ms")
