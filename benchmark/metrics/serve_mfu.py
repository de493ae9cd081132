"""``serve_mfu``: the served tokens' share of the chip's peak: the
benchmark's model FLOPs of every prompt and fed-back output token of the
replies that came inside the window, each at its own context length
(``lib/flops.py``), over the window over the peak of ``lib/peaks.json``."""

from benchmark.lib import flops, spec


def read(ctx: dict):
    requests = ctx.get("requests")
    if not requests:
        return None
    peak = spec.peaks(ctx["device_kind"])["flops_per_s"]
    return 100.0 * flops.serve_flops(ctx["cfg"], requests) \
        / ctx["window_s"] / peak
