"""What the readers of family ``mla_moe`` share: the device time of the
operations the configuration names (``program.trace_ops``: whatever
implements the routed experts' products, found per Pallas kernel under
``kernel_ops_s`` or per XLA operation under ``ops_s``), the flash kernels'
alone, and the program's own counter."""

from __future__ import annotations

from benchmark.metrics import _common

FLASH_PREFIX = "flash_"


def named_seconds(ctx: dict):
    """Self time of the operations named in ``program.trace_ops``, or
    nothing where none ran."""
    trace = ctx.get("trace") or {}
    names = tuple(ctx["cfg"].get("program", {}).get("trace_ops", ()))
    found = {**trace.get("ops_s", {}), **trace.get("kernel_ops_s", {})}
    seconds = sum(v for k, v in found.items() if k in names)
    return seconds if seconds > 0 else None


def flash_seconds(trace: dict):
    seconds = sum(v for k, v in (trace or {}).get("kernel_ops_s", {}).items()
                  if k.startswith(FLASH_PREFIX))
    return seconds if seconds > 0 else None


def local_count(ctx: dict):
    """``moe_local_count``: held assignments a step, summed over the
    expert layers, mean over the window's rounds; nothing where the
    program ships no such counter."""
    def pick(m):
        value = (m.get("profile", {}).get("learners", {})
                 .get(ctx.get("learner", ""), {}).get("device", {})
                 .get("moe_local_count"))
        return float(value) if value else None

    return _common.mean_over_rounds(ctx, pick)
