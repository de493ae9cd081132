"""Reduction of a JAX profiler trace (``*.xplane.pb``) to what the per-layer
metrics read: the device's busy time (union of the intervals in which an
operation ran), self time by operation name, and the longest idle gaps, each
named by the programs on either side of it and, where the host's phases are
known on the same clock, by the phase the host was in. Checked on a small
recorded trace in ``benchmark/tests``."""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
EPOCH_NS = 10 ** 18          # a timestamp above this is time since 1970


class NoDeviceOps(ValueError):
    pass


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_device_events(path: str) -> list:
    """Per device plane: {"ops": [(start_ns, end_ns, name)], "modules":
    [...]}, both sorted by start."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            into = ops if line.name == OPS_LINE else modules
            for ev in line.events:
                start = int(ev.start_ns)
                into.append((start, start + int(ev.duration_ns),
                             str(ev.name)))
        ops.sort()
        modules.sort()
        planes.append({"name": plane.name, "ops": ops, "modules": modules})
    return planes


def union(intervals: list) -> list:
    """Merged [start, end] intervals of ``(start, end, ...)`` tuples."""
    merged = []
    for item in sorted(intervals):
        start, end = item[0], item[1]
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def self_times(ops: list) -> list:
    """[(name, self_ns)]: each operation's time less what the operations
    running inside it take (a ``while`` holds its body's operations), so
    that the self times add up to the busy time."""
    out, stack = [], []                  # stack of [end, name, self]
    for start, end, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= start:
            out.append(tuple(stack.pop()[1:]))
        if stack:
            stack[-1][2] -= min(end, stack[-1][0]) - start
        stack.append([end, name, end - start])
    out.extend(tuple(item[1:]) for item in stack)
    return out


KERNEL_TARGET = "tpu_custom_call"      # a Pallas (Mosaic) kernel's call


def is_kernel(name: str) -> bool:
    """An event's name is its HLO instruction's text; a Pallas kernel is a
    custom call to the Mosaic target, under whatever instruction name."""
    return KERNEL_TARGET in name


def op_family(name: str) -> str:
    """``fusion.123`` -> ``fusion``; ``%while.5`` -> ``while``: instances of
    one operation kind under one name."""
    name = name.lstrip("%").split(" = ")[0]
    name = re.sub(r"\(\d+\)$", "", name)      # jit_f(<program id>)
    return re.sub(r"[.\d]+$", "", name) or name


def round_phases(rounds: list) -> list:
    """[(start_s, end_s, phase)] on the wall clock from the controller's
    rounds: ``(started_at, completed_at, {phase: ms})``, phases tiled in
    their order from the round's start."""
    out = []
    for started, completed, phases in rounds:
        t = started
        for name, ms in phases.items():
            out.append((t, t + ms / 1e3, name))
            t += ms / 1e3
        if completed > t:
            out.append((t, completed, "between_rounds"))
    return out


def _phase_at(host_phases: list, wall_s: float) -> str:
    for start, end, name in host_phases:
        if start <= wall_s < end:
            return name
    return ""


def reduce_planes(planes: list, wall_start: float, wall_stop: float,
                  host_phases=None, top: int = 10) -> dict:
    """Busy seconds (mean over device planes), the traced window, seconds
    by operation family, and the longest idle gaps."""
    if not planes or not any(p["ops"] for p in planes):
        # a traced run in which nothing ran on the device has no result
        raise NoDeviceOps("the trace holds no device operation")
    window_ns = (wall_stop - wall_start) * 1e9
    first = min(p["ops"][0][0] for p in planes if p["ops"])
    # device timestamps are either time since 1970 or time since the
    # profiler's start: either way the window is the probe's own span
    origin = wall_start * 1e9 if first > EPOCH_NS else 0.0
    busy, by_name, kernels, gaps = [], {}, {}, []
    for plane in planes:
        ops = [(max(s, origin), min(e, origin + window_ns), n)
               for s, e, n in plane["ops"]
               if e > origin and s < origin + window_ns]
        merged = union(ops)
        busy.append(sum(e - s for s, e in merged))
        for n, own in self_times(ops):
            fam = op_family(n)
            by_name[fam] = by_name.get(fam, 0.0) + own
            if is_kernel(n):
                kernels[fam] = kernels.get(fam, 0.0) + own
        modules = plane["modules"]
        by_end = sorted((e, n) for _, e, n in modules)
        ends = [e for e, _ in by_end]
        starts = [s for s, _, _ in modules]
        edges = [[origin, origin]] + merged + [[origin + window_ns] * 2]
        for (_, prev_end), (next_start, _) in zip(edges, edges[1:]):
            if next_start - prev_end <= 0:
                continue
            i = bisect.bisect_right(ends, prev_end + 1000)
            j = bisect.bisect_left(starts, next_start - 1000)
            name = (f"{op_family(by_end[i - 1][1]) if i else 'start'} -> "
                    f"{op_family(modules[j][2]) if j < len(modules) else 'end'}")
            if host_phases:
                mid = wall_start + ((prev_end + next_start) / 2
                                    - origin) / 1e9
                phase = _phase_at(host_phases, mid)
                name = f"{phase}: {name}" if phase else name
            gaps.append((name, (next_start - prev_end) / 1e9))
    n = len(planes)
    # families weigh the same op on several planes once each: mean
    ops_s = sorted(((k, v / 1e9 / n) for k, v in by_name.items()),
                   key=lambda kv: -kv[1])
    by_gap = {}
    for name, s in gaps:
        by_gap[name] = by_gap.get(name, 0.0) + s / n
    gaps_s = sorted(by_gap.items(), key=lambda kv: -kv[1])
    counts = {}
    for plane in planes:
        for s, e, name in plane["modules"]:
            if s >= origin and e <= origin + window_ns:
                fam = op_family(name)
                counts[fam] = counts.get(fam, 0) + 1
    return {"busy_s": sum(busy) / n / 1e9, "window_s": window_ns / 1e9,
            "module_runs": {k: v / n for k, v in counts.items()},
            "ops_s": dict(ops_s),
            "kernel_ops_s": {k: v / 1e9 / n for k, v in kernels.items()},
            "top_ops": [[k, v] for k, v in ops_s[:top]],
            "top_gaps": [[k, v] for k, v in gaps_s[:top]],
            "planes": n}


def reduce_dir(trace_dir: str, wall_start: float, wall_stop: float,
               host_phases=None) -> dict:
    return reduce_planes(load_device_events(find_xplane(trace_dir)),
                         wall_start, wall_stop, host_phases)
