"""Shared arithmetic of the per-layer metric readers."""

from __future__ import annotations

import statistics


def mean_over_rounds(ctx: dict, pick):
    """Mean over the window's rounds of ``pick(round)``; rounds where it
    finds nothing are skipped, and nothing at all reads as nothing."""
    values = [v for v in (pick(m) for m in ctx.get("rounds", []))
              if v is not None]
    return statistics.fmean(values) if values else None


def step_ms(round_meta: dict, learner: str):
    device = (round_meta.get("profile", {}).get("learners", {})
              .get(learner, {}).get("device", {}))
    value = device.get("ms_per_step", 0.0)
    return float(value) if value and value > 0 else None


def phase_ms(round_meta: dict, phase: str):
    value = round_meta.get("profile", {}).get("phases", {}).get(phase)
    return float(value) if value is not None else None


def idle_share(ctx: dict):
    trace = ctx.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
