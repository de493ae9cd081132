"""``decode_host_share``: the decode loop's own account of its ticks, from
the ``decode.loop`` summary events that ended inside the window: 100 x the
rest of the tick (admission under the lock, token hand-out, retirement)
over step + prefill + rest. Step and prefill are host-clock seconds from
the call to the tokens on the host, so the share is the loop's time in
which no program was asked of the device; time parked with nothing queued
and no slot active is in neither side."""

from benchmark.metrics import _sink


def read(ctx: dict):
    host = busy = 0.0
    for rec in _sink.events(ctx, "decode.loop"):
        attrs = rec.get("attrs", {})
        host += float(attrs.get("host_s", 0.0))
        busy += (float(attrs.get("step_s", 0.0))
                 + float(attrs.get("prefill_s", 0.0)))
    return 100.0 * host / (host + busy) if host + busy > 0 else None
