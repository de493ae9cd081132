"""``prefill_ms``: mean ``prefill_ms`` of the gateway's ``decode.slot``
events that ended inside the window: the request's own prefill, call to
first token on the host, during which the running batch does not step."""

from benchmark.metrics import _sink


def read(ctx: dict):
    return _sink.mean_attr(ctx, "decode.slot", "prefill_ms")
