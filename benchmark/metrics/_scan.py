"""The selective-scan kernels' events in the device trace: self time of the
operations whose name starts ``ssm_scan`` (``ssm_scan_fwd``,
``ssm_scan_bwd``: the ``name`` of their ``pallas_call``s, which the trace
reduction keeps per Pallas kernel under ``kernel_ops_s``), and the
optimizer steps the traced window held."""

from __future__ import annotations

PREFIX = "ssm_scan"


def kernel_seconds(trace: dict):
    """Seconds, or nothing where no such kernel ran (a program without
    the kernels, or a length under their threshold)."""
    seconds = sum(v for k, v in (trace or {}).get("kernel_ops_s", {}).items()
                  if k.startswith(PREFIX))
    return seconds if seconds > 0 else None


def steps(ctx: dict) -> float:
    scans = sum(v for k, v in ctx["trace"]["module_runs"].items()
                if "scan_steps" in k)
    return scans * int(ctx["traffic"]["shape"]["scan_chunk"])
