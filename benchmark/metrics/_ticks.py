"""The decode loop's ticks against the device's clock: the idle time of the
serve cell's traced window assigned to the host phase it fell in.

The gateway stamps every tick on its own clock (``decode.loop`` events,
attr ``stamps``: an ``anchor`` of ``time.time()`` and ``perf_counter()``
read together, and one record a tick, ``[start, released, [[kind, entry,
enqueued, returned], ...], end]`` in microseconds after it; kind ``s`` a
step, ``p<L>`` a prefill of L tokens). The profiler records no host span
(the probe starts it with the host tracer off), and the device plane counts
from the profiler's start, so the two are lined up by sequence: the device's
ordered runs of ``jit_decode_step`` and ``jit_decode_prefill`` against the
host's ordered calls, by kind, at the place where the kinds agree.

The clock offset is the largest (device end - host returned) over the
matched calls. The token read returns only after the program has ended, so
no call can read above the true offset, and the call with the quickest read
reads closest to it: the read phase is counted net of that quickest read
(tens of microseconds), which the launch phase takes instead. A median of
(device start - host entry) would put the median call's device start on its
entry, and half the calls' starts before their entries. Where under 95% of
the matched runs fall inside their call's [entry, returned] after the
offset, the alignment is wrong, and nothing is read.

Each idle interval of the window (the complement of the union of the
device's operations, as ``device_idle_share.serve`` has it) is cut by the
host's stamps: *launch* from a call's entry to its program's start (inputs
placed, arguments handled, the dispatch, eager helper programs such as the
prefill's ``reshape``), *read* from the program's end to the tokens on the
host, *loop* from a call's return to the next call's entry (hand-out,
retirement, admission under the lock). What falls outside them (inside a
program, or in a call whose program was not in the trace) is unnamed."""

from __future__ import annotations

import bisect
import os

from benchmark.lib import trace as trace_lib
from benchmark.metrics import _sink

STEP, PREFILL = "jit_decode_step", "jit_decode_prefill"
CONTAINED = 0.95

_CACHE: dict = {}


def host_calls(events: list) -> list:
    """``[(kind, entry, enqueued, returned)]`` of every stamped call of the
    ``decode.loop`` records, in order, kind ``s`` or ``p``; times in
    nanoseconds on the host's ``perf_counter`` clock, from the first
    anchor."""
    batches = sorted((rec["attrs"]["stamps"] for rec in events
                      if "stamps" in rec.get("attrs", {})),
                     key=lambda st: st["anchor"][1])
    calls = []
    for st in batches:
        base = (st["anchor"][1] - batches[0]["anchor"][1]) * 1e6
        for _start, _released, tick_calls, _end in st["ticks"]:
            for kind, entry, enqueued, returned in tick_calls:
                calls.append((kind[0], (base + entry) * 1e3,
                              (base + enqueued) * 1e3,
                              (base + returned) * 1e3))
    calls.sort(key=lambda c: c[1])
    return calls


def device_runs(plane: dict) -> list:
    """``[(kind, start_ns, end_ns)]`` of the decode programs, in order."""
    kinds = {STEP: "s", PREFILL: "p"}
    return [(kinds[fam], s, e) for s, e, fam in
            ((s, e, trace_lib.op_family(n)) for s, e, n in plane["modules"])
            if fam in kinds]


def _align(calls: list, runs: list):
    """``(first call index, offset_ns)`` of the best placement of the
    device's run sequence in the host's call sequence, or ``None``."""
    host = "".join(c[0] for c in calls)
    device = "".join(r[0] for r in runs)
    best, at = None, host.find(device) if device else -1
    while at >= 0:
        pairs = list(zip(calls[at:], runs))
        offset = max(de - ret for (_, _, _, ret), (_, _, de) in pairs)
        inside = sum(1 for (_, entry, _, ret), (_, ds, de) in pairs
                     if entry + offset <= ds and de <= ret + offset)
        share = inside / len(pairs)
        if best is None or share > best[0]:
            best = (share, at, offset)
        at = host.find(device, at + 1)
    if best is None or best[0] < CONTAINED:
        return None
    return best[1], best[2]


def split(calls: list, plane: dict, window_ns: float):
    """Seconds of device idle in the traced window ``[0, window_ns]`` by
    host phase: ``{"read_s", "loop_s", "launch_s", "idle_s", "step_runs",
    "offset_ns"}``, or ``None`` where the calls cannot be lined up with the
    device's runs."""
    runs = device_runs(plane)
    placed = _align(calls, runs)
    if placed is None:
        return None
    first, offset = placed
    busy = trace_lib.union([(max(s, 0.0), min(e, window_ns))
                            for s, e, _ in plane["ops"]
                            if e > 0 and s < window_ns])
    starts = [s for s, _ in busy]
    cum = [0.0]
    for s, e in busy:
        cum.append(cum[-1] + e - s)

    def busy_until(t: float) -> float:
        i = bisect.bisect_right(starts, t)
        if not i:
            return 0.0
        s, e = busy[i - 1]
        return cum[i - 1] + min(t, e) - s

    def idle(a: float, b: float) -> float:
        a, b = max(a, 0.0), min(b, window_ns)
        if b <= a:
            return 0.0
        return (b - a) - (busy_until(b) - busy_until(a))

    phases = {"read": 0.0, "loop": 0.0, "launch": 0.0}
    shifted = [(k, e + offset, q + offset, r + offset)
               for k, e, q, r in calls]
    for i, (_, entry, _, ret) in enumerate(shifted):
        j = i - first
        if 0 <= j < len(runs):
            _, ds, de = runs[j]
            phases["launch"] += idle(entry, ds)
            phases["read"] += idle(max(de, entry), ret)
        if i + 1 < len(shifted):
            phases["loop"] += idle(ret, shifted[i + 1][1])
    step_runs = sum(1 for k, s, e in runs
                    if k == "s" and s >= 0 and e <= window_ns)
    out = {f"{k}_s": v / 1e9 for k, v in phases.items()}
    out.update(idle_s=(window_ns - cum[-1]) / 1e9, step_runs=step_runs,
               offset_ns=offset)
    return out


def read(ctx: dict):
    """:func:`split` of a traced serve run (``None`` off the chip, without
    stamps, as on a program that does not stamp its ticks, or without a
    match), once a process: five readers share it."""
    trace = ctx.get("trace")
    if not trace or not ctx.get("telemetry_dir"):
        return None
    key = (ctx["telemetry_dir"], tuple(ctx.get("window", ())))
    if key not in _CACHE:
        _CACHE[key] = _read(ctx, trace["window_s"] * 1e9)
    return _CACHE[key]


def _read(ctx: dict, window_ns: float):
    calls = host_calls(_sink.events(ctx, "decode.loop"))
    if not calls:
        return None
    work = os.path.dirname(os.path.dirname(ctx["telemetry_dir"]))
    try:
        planes = trace_lib.load_device_events(
            trace_lib.find_xplane(os.path.join(work, "trace")))
    except FileNotFoundError:
        return None
    plane = next((p for p in planes if p["ops"]), None)
    if plane is None or plane["ops"][0][0] > trace_lib.EPOCH_NS:
        # a plane on the epoch's clock would need the probe's wall start,
        # which the driver keeps out of ``ctx``; the v5e's counts from the
        # profiler's start
        return None
    return split(calls, plane, window_ns)


def per_step_ms(ctx: dict, phase: str):
    got = read(ctx)
    if not got or not got["step_runs"]:
        return None
    return 1e3 * got[f"{phase}_s"] / got["step_runs"]
