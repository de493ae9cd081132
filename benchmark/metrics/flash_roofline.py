"""``flash_roofline``: the flash-attention kernels' share of their roofline
in the traced rounds: the least time the chip could take for their work
(``lib/flops.py``: forward, the rematerialized forward and backward, the
larger of FLOPs over peak FLOP/s and bytes over peak bytes/s) over the
self time of their events in the device trace. The kernels' ``pallas_call``s
carry no name, so the events are found by their custom-call target
(``lib/trace.py``: ``is_kernel``); the flash kernels are the only Pallas
kernels in the step. Reads nothing where no such event ran."""

from benchmark.lib import flops, spec


def kernel_seconds(trace: dict) -> float:
    return sum(trace.get("kernel_ops_s", {}).values())


def read(ctx: dict):
    trace = ctx.get("trace")
    if not trace:
        return None
    seconds = kernel_seconds(trace)
    scans = sum(v for k, v in trace["module_runs"].items()
                if "scan_steps" in k)
    steps = scans * int(ctx["traffic"]["shape"]["scan_chunk"])
    if seconds <= 0 or steps <= 0:
        return None
    peaks = spec.peaks(ctx["device_kind"])
    cost = flops.flash_attention_cost(
        ctx["cfg"], ctx["traffic"]["shape"],
        remat=bool(ctx["cfg"]["program"].get("remat")))
    least = max(cost["flops"] / peaks["flops_per_s"],
                cost["bytes"] / peaks["bytes_per_s"]) * steps
    return 100.0 * least / seconds
