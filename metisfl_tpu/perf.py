"""Offline performance analyzer: ``python -m metisfl_tpu.perf``.

The reading half of the performance observatory (telemetry/profile.py):

- **run-dir mode** — render the per-round phase waterfall from the
  RoundProfiles a run recorded (``profiles-*.jsonl`` next to the traces,
  or ``experiment.json`` round metadata), plus the top-N span self-time
  table from ``traces.jsonl`` when present::

      python -m metisfl_tpu.perf <workdir>
      python -m metisfl_tpu.perf experiment.json --round 3 --top 10

- **--flame <source>** — render a continuous-profiling capture
  (telemetry/prof.py) as collapsed folded stacks on stdout (the format
  speedscope and FlameGraph's ``flamegraph.pl`` ingest directly) plus a
  terminal top-table (per-frame self/total %) on stderr. Sources: a
  fleet profile dump (``FleetCollector.dump_prof`` / the driver's
  ``prof-fleet.json``), a raw ``prof.collect_state()`` JSON, a
  post-mortem bundle, or a run dir / ``profiles-*.jsonl`` whose
  RoundProfiles carry per-round stack deltas (``--round N`` or a
  ``path@N`` suffix picks one round; otherwise rounds sum)::

      python -m metisfl_tpu.perf --flame <workdir>/prof-fleet.json
      python -m metisfl_tpu.perf --flame <workdir> --round 6

- **--flame-diff A B** — differential profile between two captures or
  rounds (``run@6 run@7`` diffs round profiles from one run): per-frame
  self-time growth, the table that answers "which frames grew when
  rounds/s dropped".

- **--compile-report <source>** — the accelerator-runtime view
  (telemetry/runtime.py): per-fn XLA compile counts/durations and the
  recompile offenders table, from a fleet runtime dump
  (``FleetCollector.dump_runtime`` / the driver's
  ``runtime-fleet.json``), a raw ``runtime.collect_state()`` JSON, or a
  run dir whose span timeline carries ``jax.compile`` events::

      python -m metisfl_tpu.perf --compile-report <workdir>/runtime-fleet.json
      python -m metisfl_tpu.perf --compile-report <workdir>

Library-usable: :func:`load_profiles`, :func:`render_waterfall`,
:func:`span_self_times`.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple


# --------------------------------------------------------------------- #
# round-profile loading + waterfall rendering
# --------------------------------------------------------------------- #

def load_profiles(path: str) -> List[dict]:
    """RoundProfile dicts from a run artifact: a ``profiles-*.jsonl``
    sink file, an ``experiment.json`` (round metadata ``profile`` keys),
    or a run directory holding either (``telemetry/`` searched too)."""
    if os.path.isdir(path):
        candidates = (
            sorted(glob.glob(os.path.join(path, "profiles-*.jsonl")))
            + sorted(glob.glob(
                os.path.join(path, "telemetry", "profiles-*.jsonl"))))
        profiles: List[dict] = []
        for name in candidates:
            profiles.extend(_load_profile_jsonl(name))
        if profiles:
            profiles.sort(key=lambda p: p.get("round", 0))
            return profiles
        exp = os.path.join(path, "experiment.json")
        if os.path.exists(exp):
            return load_profiles(exp)
        return []
    if path.endswith(".jsonl"):
        return _load_profile_jsonl(path)
    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, json.JSONDecodeError) as exc:
        # missing/torn experiment.json: report-and-skip like every other
        # loader here — the CLI's exit codes, not a traceback, are the
        # contract
        print(f"cannot read round profiles from {path}: {exc}",
              file=sys.stderr)
        return []
    if not isinstance(data, dict):
        return []
    return [meta["profile"] for meta in data.get("round_metadata", [])
            if meta.get("profile")]


def _load_profile_jsonl(path: str) -> List[dict]:
    out: List[dict] = []
    try:
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    record = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn tail line from a crashed process
                if isinstance(record, dict) and "phases" in record:
                    out.append(record)
    except OSError:
        pass
    return out


def _fmt_ms(ms: float) -> str:
    return f"{ms / 1e3:.2f}s" if ms >= 1e3 else f"{ms:.1f}ms"


def _fmt_bytes(n: float) -> str:
    if n >= 1e9:
        return f"{n / 1e9:.2f}GB"
    if n >= 1e6:
        return f"{n / 1e6:.2f}MB"
    if n >= 1e3:
        return f"{n / 1e3:.1f}KB"
    return f"{int(n)}B"


def _bars(tiles: Dict[str, float], order: Tuple[str, ...], wall: float,
          width: int) -> List[str]:
    """One bar line per tile of ``order`` that ``tiles`` holds: duration,
    share of ``wall``, and a bar scaled to the longest tile."""
    present = [(name, float(tiles[name])) for name in order
               if name in tiles]
    longest = max((ms for _, ms in present), default=0.0)
    lines = []
    for name, ms in present:
        bar = "#" * (int(round(width * ms / longest)) if longest > 0 else 0)
        share = (ms / wall * 100) if wall > 0 else 0.0
        lines.append(f"  {name:<13} {_fmt_ms(ms):>9} {share:5.1f}%  {bar}")
    return lines


def render_waterfall(profiles: List[dict], width: int = 40,
                     want_round: Optional[int] = None) -> str:
    """The phase waterfall (one bar block per round), each learner's own
    task waterfall under it, and the per-learner attribution table for
    each profiled round."""
    from metisfl_tpu.telemetry.profile import (PHASES, TASK_BYTES,
                                               TASK_TILES)

    lines: List[str] = []
    for prof in profiles:
        round_no = prof.get("round", 0)
        if want_round is not None and round_no != want_round:
            continue
        wall = float(prof.get("wall_ms", 0.0))
        lines.append(
            f"round {round_no}  wall {_fmt_ms(wall)}  coverage "
            f"{float(prof.get('coverage', 0.0)) * 100:.0f}%"
            + ("  [jax trace armed]" if prof.get("trace_armed") else ""))
        lines.extend(_bars(prof.get("phases") or {}, PHASES, wall, width))
        learners = prof.get("learners") or {}
        for lid in sorted(learners):
            # the learner's own account of its train task, on its clock:
            # tiles from the RPC's acceptance to the start of its report
            task = learners[lid].get("task") or {}
            if not task:
                continue
            task_ms = sum(float(task[t]) for t in TASK_TILES if t in task)
            lines.append(f"  task {lid}  wall {_fmt_ms(task_ms)}")
            lines.extend("  " + line
                         for line in _bars(task, TASK_TILES, task_ms, width))
            sizes = learners[lid].get("task_bytes") or {}
            if sizes:
                # what upload placed and kept on the device (the frozen
                # base of a ship-only round), what readback read
                lines.append("    host<->device  " + "  ".join(
                    f"{k[:-len('_bytes')]} {_fmt_bytes(sizes[k])}"
                    for k in TASK_BYTES if k in sizes))
        store = prof.get("store") or {}
        if store:
            lines.append(
                f"  store: insert {_fmt_ms(float(store.get('insert_ms', 0.0)))}"
                f" (overlaps wait), select "
                f"{_fmt_ms(float(store.get('select_ms', 0.0)))}")
        serving = prof.get("serving") or {}
        if serving:
            lines.append(f"  serving: queue_depth="
                         f"{serving.get('queue_depth', 0)}")
        if learners:
            lines.append(f"  {'learner':<24} {'uplink':>9} {'downlink':>9} "
                         f"{'codec':>8} {'insert':>8} {'step_ms':>8} "
                         f"{'mfu':>6} {'hbm':>9}")
            for lid in sorted(learners):
                entry = learners[lid]
                codec_s = (float(entry.get("codec_encode_s", 0.0))
                           + float(entry.get("codec_decode_s", 0.0)))
                device = entry.get("device") or {}
                mfu = float(device.get("mfu") or 0.0)
                step = float(device.get("step_ms_ewma", 0.0))
                hbm = float(device.get("hbm_peak_bytes", 0))
                lines.append(
                    f"  {lid:<24} "
                    f"{_fmt_bytes(entry.get('uplink_bytes', 0)):>9} "
                    f"{_fmt_bytes(entry.get('downlink_bytes', 0)):>9} "
                    f"{(_fmt_ms(codec_s * 1e3) if codec_s else '-'):>8} "
                    f"{(_fmt_ms(float(entry.get('insert_ms', 0.0))) if entry.get('insert_ms') else '-'):>8} "
                    f"{(f'{step:.2f}' if step else '-'):>8} "
                    f"{(f'{mfu:.3f}' if mfu else '-'):>6} "
                    f"{(_fmt_bytes(hbm) if hbm else '-'):>9}")
            for lid in sorted(learners):
                # what the learner's module counted, a step (a routed
                # layer's assignments on held experts, its largest group)
                counts = {k: v for k, v in
                          (learners[lid].get("device") or {}).items()
                          if k.endswith("_count")}
                if counts:
                    lines.append(f"  counts {lid}  " + "  ".join(
                        f"{k[:-len('_count')]} {float(v):.0f}"
                        for k, v in sorted(counts.items())) + "  a step")
        lines.append("")
    return "\n".join(lines).rstrip()


# --------------------------------------------------------------------- #
# span self-time table (from the trace sink)
# --------------------------------------------------------------------- #

def span_self_times(spans: List[dict]) -> List[Dict[str, Any]]:
    """Aggregate self time (own duration minus direct children) by span
    name across a trace dump — the 'where does time actually go' table a
    stitched tree hides in its leaves. Children whose parent never
    landed in the sink count as roots (their time still aggregates)."""
    by_id = {s.get("span"): s for s in spans if s.get("span")}
    child_ms: Dict[str, float] = {}
    for s in spans:
        parent = s.get("parent", "")
        if parent and parent in by_id:
            child_ms[parent] = (child_ms.get(parent, 0.0)
                                + float(s.get("dur_ms", 0.0)))
    agg: Dict[str, Dict[str, float]] = {}
    for s in spans:
        name = s.get("name", "?")
        dur = float(s.get("dur_ms", 0.0))
        # clamp: async children (eval digests) can outlive their parent
        self_ms = max(0.0, dur - child_ms.get(s.get("span", ""), 0.0))
        row = agg.setdefault(name, {"count": 0, "self_ms": 0.0,
                                    "total_ms": 0.0})
        row["count"] += 1
        row["self_ms"] += self_ms
        row["total_ms"] += dur
    rows = [{"name": name, **vals} for name, vals in agg.items()]
    rows.sort(key=lambda r: -r["self_ms"])
    return rows


def render_self_times(rows: List[Dict[str, Any]], top: int = 15) -> str:
    lines = [f"{'span':<28} {'count':>6} {'self':>10} {'total':>10}"]
    for row in rows[:top]:
        lines.append(f"{row['name']:<28} {row['count']:>6} "
                     f"{_fmt_ms(row['self_ms']):>10} "
                     f"{_fmt_ms(row['total_ms']):>10}")
    return "\n".join(lines)


def _load_trace_spans(path: str) -> List[dict]:
    """Spans from a run dir (traces.jsonl / telemetry/*.jsonl) — reuses
    the trace viewer's tolerant loader."""
    from metisfl_tpu.telemetry.__main__ import load_spans

    candidates = []
    if os.path.isdir(path):
        for name in ("traces.jsonl",):
            full = os.path.join(path, name)
            if os.path.exists(full):
                candidates.append(full)
        tel = os.path.join(path, "telemetry")
        if os.path.isdir(tel):
            candidates.append(tel)
    elif path.endswith(".jsonl"):
        candidates.append(path)
    if not candidates:
        return []
    try:
        spans = load_spans(candidates)
    except OSError:
        return []
    # profile sink lines also live under telemetry/ and parse as dicts
    # without a "span" key — load_spans already filters them out
    return spans


# --------------------------------------------------------------------- #
# continuous-profiling renderers (--flame / --flame-diff)
# --------------------------------------------------------------------- #

def _split_round_suffix(path: str) -> Tuple[str, Optional[int]]:
    """``run@6`` → (``run``, 6): the round-selector suffix the
    --flame-diff mode uses to diff two rounds of ONE run."""
    base, sep, tail = path.rpartition("@")
    if sep and base and tail.isdigit() and not os.path.exists(path):
        return base, int(tail)
    return path, None


def load_folded(path: str, want_round: Optional[int] = None
                ) -> Dict[str, float]:
    """A ``{folded_stack: samples}`` map from any profiling artifact
    this repo writes:

    - a fleet profile dump (``{"kind": "prof", "peers"/"stacks"}``) —
      peer-prefixed merged stacks;
    - a raw ``prof.collect_state()`` JSON (``{"stacks": {...}}``);
    - a post-mortem bundle (its ``prof`` section has no raw stacks —
      only the top table — so the TABLE's self counts render);
    - a run dir / ``profiles-*.jsonl`` / ``experiment.json`` whose
      RoundProfiles carry per-round ``prof`` stack deltas (``want_round``
      picks one round, otherwise rounds sum).

    Returns ``{}`` when nothing profiling-shaped is found."""
    from metisfl_tpu.telemetry import prof as _prof

    path, at_round = _split_round_suffix(path)
    if at_round is not None and want_round is None:
        want_round = at_round
    if os.path.isdir(path) or path.endswith(".jsonl") \
            or os.path.basename(path) == "experiment.json":
        folded: Dict[str, float] = {}
        for profile in load_profiles(path):
            if want_round is not None \
                    and int(profile.get("round", -1)) != want_round:
                continue
            section = profile.get("prof") or {}
            for stack, count in section.get("stacks") or []:
                folded[str(stack)] = (folded.get(str(stack), 0.0)
                                      + float(count))
        return folded
    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, json.JSONDecodeError) as exc:
        print(f"cannot read a profile from {path}: {exc}",
              file=sys.stderr)
        return {}
    if not isinstance(data, dict):
        return {}
    if "peers" in data and "stacks" in data:   # fleet dump: merged map
        return {str(k): float(v)
                for k, v in (data.get("stacks") or {}).items()}
    if "stacks" in data:                        # raw collect_state
        return _prof.folded_counts(data)
    if "prof" in data:                          # post-mortem bundle
        section = data["prof"] or {}
        if "stacks" in section:
            return _prof.folded_counts(section)
        return {str(row.get("frame", "?")): float(row.get("self", 0.0))
                for row in section.get("top") or []
                if float(row.get("self", 0.0)) > 0.0}
    return {}


def render_collapsed(folded: Dict[str, float]) -> str:
    """Collapsed-stack export: ``root;...;leaf <count>`` lines, the
    exact format ``flamegraph.pl`` and speedscope ingest."""
    return "\n".join(
        f"{stack} {int(round(count))}"
        for stack, count in sorted(folded.items(),
                                   key=lambda kv: (-kv[1], kv[0]))
        if int(round(count)) > 0)


def render_frame_table(folded: Dict[str, float], top: int = 15) -> str:
    """The terminal top-table: per-frame self/total samples + percents."""
    from metisfl_tpu.telemetry import prof as _prof

    rows = _prof.frame_table(folded)
    total = sum(folded.values())
    lines = [f"{'frame':<52} {'self':>8} {'self%':>7} "
             f"{'total':>8} {'total%':>7}"]
    for row in rows[:top]:
        lines.append(f"{row['frame'][:52]:<52} {row['self']:>8.0f} "
                     f"{row['self_pct']:>6.1f}% {row['total']:>8.0f} "
                     f"{row['total_pct']:>6.1f}%")
    lines.append(f"({len(folded)} folded stacks, "
                 f"{total:.0f} samples)")
    return "\n".join(lines)


def diff_frame_tables(a: Dict[str, float], b: Dict[str, float]
                      ) -> List[Dict[str, Any]]:
    """Per-frame differential profile: self/total sample deltas (B − A),
    biggest absolute self growth first — the table that explains an
    unattributed slowdown between two rounds or two captures."""
    from metisfl_tpu.telemetry import prof as _prof

    rows_a = {r["frame"]: r for r in _prof.frame_table(a)}
    rows_b = {r["frame"]: r for r in _prof.frame_table(b)}
    out: List[Dict[str, Any]] = []
    for frame in set(rows_a) | set(rows_b):
        ra, rb = rows_a.get(frame), rows_b.get(frame)
        d_self = ((rb["self"] if rb else 0.0)
                  - (ra["self"] if ra else 0.0))
        d_total = ((rb["total"] if rb else 0.0)
                   - (ra["total"] if ra else 0.0))
        if d_self == 0.0 and d_total == 0.0:
            continue
        out.append({"frame": frame, "d_self": d_self, "d_total": d_total,
                    "self_a": ra["self"] if ra else 0.0,
                    "self_b": rb["self"] if rb else 0.0})
    out.sort(key=lambda r: (-abs(r["d_self"]), -abs(r["d_total"]),
                            r["frame"]))
    return out


def render_flame_diff(rows: List[Dict[str, Any]],
                      label_a: str = "A", label_b: str = "B",
                      top: int = 15) -> str:
    lines = [f"{'frame':<52} {label_a[:10]:>10} {label_b[:10]:>10} "
             f"{'Δself':>9} {'Δtotal':>9}"]
    for row in rows[:top]:
        lines.append(f"{row['frame'][:52]:<52} {row['self_a']:>10.0f} "
                     f"{row['self_b']:>10.0f} {row['d_self']:>+9.0f} "
                     f"{row['d_total']:>+9.0f}")
    if len(lines) == 1:
        lines.append("(no per-frame difference between the profiles)")
    return "\n".join(lines)


def _flame_main(path: str, want_round: Optional[int], top: int,
                out_path: str = "") -> int:
    folded = load_folded(path, want_round=want_round)
    if not folded:
        print(f"no profiling data found in {path} (is telemetry.prof "
              "enabled and the source a prof dump / bundle / run dir?)",
              file=sys.stderr)
        return 2
    collapsed = render_collapsed(folded)
    if out_path:
        try:
            with open(out_path, "w") as fh:
                fh.write(collapsed + "\n")
        except OSError as exc:
            print(f"cannot write {out_path}: {exc}", file=sys.stderr)
            return 2
        print(render_frame_table(folded, top=top))
    else:
        # collapsed stacks on stdout (pipe straight into flamegraph.pl /
        # speedscope), human table on stderr
        print(collapsed)
        print(render_frame_table(folded, top=top), file=sys.stderr)
    return 0


def load_runtime_state(path: str) -> Dict[str, Any]:
    """An accelerator-runtime state (``runtime.collect_state`` shape)
    from any artifact this repo writes:

    - a fleet runtime dump (``{"kind": "runtime", "peers"/"merged"}`` —
      ``FleetCollector.dump_runtime`` / the driver's
      ``runtime-fleet.json``): the fleet-merged view;
    - a raw ``runtime.collect_state()`` JSON (``{"fns": {...}}``);
    - a run dir / ``traces.jsonl`` whose span timeline carries
      ``jax.compile`` events — rows rebuilt from their attrs.

    Returns ``{}`` when nothing runtime-shaped is found."""
    if os.path.isdir(path) or path.endswith(".jsonl"):
        fns: Dict[str, Dict[str, Any]] = {}
        compiles = recompiles = 0
        for span in _load_trace_spans(path):
            if span.get("name") != "jax.compile":
                continue
            attrs = span.get("attrs") or {}
            fn = str(attrs.get("fn", "(unattributed)"))
            kind = str(attrs.get("kind", "cold"))
            dur_s = float(span.get("dur_ms", 0.0) or 0.0) / 1e3
            row = fns.setdefault(fn, {"cold": 0, "recompiles": 0,
                                      "total_s": 0.0, "max_s": 0.0,
                                      "last_sig": ""})
            if kind == "recompile":
                row["recompiles"] += 1
                recompiles += 1
            else:
                row["cold"] += 1
            row["total_s"] += dur_s
            row["max_s"] = max(row["max_s"], dur_s)
            row["last_sig"] = str(attrs.get("sig", "")) or row["last_sig"]
            compiles += 1
        if not fns:
            return {}
        return {"enabled": True, "compiles": compiles,
                "recompiles": recompiles, "fns": fns}
    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, json.JSONDecodeError) as exc:
        print(f"cannot read a runtime report from {path}: {exc}",
              file=sys.stderr)
        return {}
    if not isinstance(data, dict):
        return {}
    if data.get("kind") == "runtime":            # fleet dump
        merged = data.get("merged") or {}
        if merged.get("fns"):
            merged = dict(merged)
            merged["peers"] = sorted(data.get("peers") or ())
            return merged
        return {}
    if "fns" in data:                            # raw collect_state
        return data
    return {}


def render_compile_report(state: Dict[str, Any], top: int = 15) -> str:
    """The ``--compile-report`` screen: totals, the per-fn compile
    table (recompile offenders first), and the recent-compile tail when
    the source carries one."""
    from metisfl_tpu.telemetry import runtime as _runtime

    rows = _runtime.compile_rows(state)
    lines = [
        f"compiles: {int(state.get('compiles', 0))} total / "
        f"{int(state.get('recompiles', 0))} recompiles / "
        f"{int(state.get('storms', 0) or 0)} storm(s)"
        + (f"  peers={','.join(state['peers'])}"
           if state.get("peers") else "")]
    mem = state.get("memory") or {}
    if isinstance(mem, dict) and mem:
        if "device_bytes" in mem:               # one process's sample
            lines.append(f"memory: {mem.get('plane', '?')} "
                         f"{int(mem.get('device_bytes', 0)) / 1e6:.1f}MB "
                         f"({mem.get('source', '?')})")
        else:                                   # merged per-plane maxima
            cells = [f"{pl}={int(b) / 1e6:.1f}MB"
                     for pl, b in sorted(mem.items())]
            lines.append("memory: " + "  ".join(cells))
    lines.append(f"{'fn':<28} {'compiles':>8} {'cold':>5} "
                 f"{'recomp':>6} {'total_s':>8} {'max_s':>7}  last_sig")
    for row in rows[:top]:
        lines.append(
            f"{row['fn'][:28]:<28} {row['compiles']:>8} {row['cold']:>5} "
            f"{row['recompiles']:>6} {row['total_s']:>8.3f} "
            f"{row['max_s']:>7.3f}  {row['last_sig'][:40]}")
    if len(rows) > top:
        lines.append(f"... {len(rows) - top} more fn(s)")
    offenders = [r for r in rows if r["recompiles"]]
    if offenders:
        worst = offenders[0]
        lines.append(f"worst offender: {worst['fn']} recompiled "
                     f"{worst['recompiles']}x "
                     f"(last sig {worst['last_sig'][:60] or '?'})")
    recent = state.get("recent") or []
    if recent:
        lines.append("recent compiles:")
        for ts, fn, kind, dur_s, sig in recent[-8:]:
            lines.append(f"  {kind:<9} {fn:<28} {float(dur_s) * 1e3:8.1f}ms"
                         f"  {str(sig)[:40]}")
    return "\n".join(lines)


def _compile_report_main(path: str, top: int) -> int:
    state = load_runtime_state(path)
    if not state or not state.get("fns"):
        print(f"no runtime compile data found in {path} (is "
              "telemetry.runtime enabled and the source a runtime dump / "
              "collect_state JSON / run dir with jax.compile spans?)",
              file=sys.stderr)
        return 2
    print(render_compile_report(state, top=top))
    return 0


def _flame_diff_main(path_a: str, path_b: str,
                     want_round: Optional[int], top: int) -> int:
    a = load_folded(path_a, want_round=want_round)
    b = load_folded(path_b, want_round=want_round)
    for path, folded in ((path_a, a), (path_b, b)):
        if not folded:
            print(f"no profiling data found in {path}", file=sys.stderr)
            return 2
    rows = diff_frame_tables(a, b)
    print(render_flame_diff(
        rows, label_a=os.path.basename(_split_round_suffix(path_a)[0]),
        label_b=os.path.basename(_split_round_suffix(path_b)[0]),
        top=top))
    grew = [r for r in rows if r["d_self"] > 0]
    print(f"\n{len(grew)} frame(s) grew, "
          f"{sum(r['d_self'] for r in grew):.0f} self-samples of growth "
          f"({sum(a.values()):.0f} -> {sum(b.values()):.0f} total)",
          file=sys.stderr)
    return 0


# --------------------------------------------------------------------- #
# CLI
# --------------------------------------------------------------------- #

def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        "metisfl_tpu.perf",
        description="performance observatory analyzer: round-profile "
                    "waterfalls, span self-times, flame and compile reports")
    parser.add_argument("paths", nargs="*",
                        help="run dir / profiles .jsonl / experiment.json")
    parser.add_argument("--critical-path", action="store_true",
                        help="causal critical path of one round "
                             "(--round; default: the latest) from a run "
                             "dir or traces .jsonl — per-edge self-time "
                             "and the dominant edge")
    parser.add_argument("--flame", metavar="SOURCE",
                        help="render a continuous-profiling capture as "
                             "collapsed folded stacks (stdout; speedscope/"
                             "FlameGraph format) + a self/total top-table")
    parser.add_argument("--flame-diff", nargs=2, metavar=("A", "B"),
                        help="differential profile between two captures "
                             "or rounds (path@N selects a round)")
    parser.add_argument("--compile-report", metavar="SOURCE",
                        help="per-fn XLA compile counts/durations + the "
                             "recompile offenders table from a fleet "
                             "runtime dump (runtime-fleet.json), a raw "
                             "runtime collect_state JSON, or a run dir's "
                             "jax.compile spans")
    parser.add_argument("--out", default="",
                        help="--flame: write the collapsed stacks to this "
                             "file and print the table to stdout")
    parser.add_argument("--round", type=int, default=None,
                        help="waterfall: only this round")
    parser.add_argument("--top", type=int, default=15,
                        help="span self-time rows to show")
    args = parser.parse_args(argv)

    if args.critical_path:
        if not args.paths:
            parser.print_usage(sys.stderr)
            return 2
        return _critical_path_main(args.paths, args.round)
    if args.flame:
        return _flame_main(args.flame, args.round, args.top,
                           out_path=args.out)
    if args.flame_diff:
        return _flame_diff_main(args.flame_diff[0], args.flame_diff[1],
                                args.round, args.top)
    if args.compile_report:
        return _compile_report_main(args.compile_report, args.top)
    if not args.paths:
        parser.print_usage(sys.stderr)
        return 2
    return _waterfall_main(args.paths, args.round, args.top)


def _critical_path_main(paths: List[str],
                        want_round: Optional[int]) -> int:
    """``--critical-path``: the longest causal chain of one round from
    collected spans (fleet traces.jsonl or per-process sink files)."""
    from metisfl_tpu.telemetry import causal as _causal

    spans: List[dict] = []
    for path in paths:
        spans.extend(_load_trace_spans(path))
    if not spans:
        print("no trace spans found (is tracing enabled and the run dir "
              "right?)", file=sys.stderr)
        return 2
    cp = _causal.round_critical_path(spans, round_no=want_round)
    if cp is None:
        which = (f"round {want_round}" if want_round is not None
                 else "any round root")
        print(f"no trace for {which} in {len(spans)} collected span(s)",
              file=sys.stderr)
        return 2
    print(_causal.render_edges(cp))
    return 0


def _waterfall_main(paths: List[str], want_round: Optional[int],
                    top: int) -> int:
    profiles: List[dict] = []
    spans: List[dict] = []
    for path in paths:
        profiles.extend(load_profiles(path))
        spans.extend(_load_trace_spans(path))
    if not profiles and not spans:
        print("no round profiles or trace spans found (is the "
              "performance observatory enabled and the run dir right?)",
              file=sys.stderr)
        return 2  # unusable input
    if profiles:
        print(render_waterfall(profiles, want_round=want_round))
    if spans:
        if profiles:
            print()
        print(f"top span self-times ({len(spans)} spans):")
        print(render_self_times(span_self_times(spans), top=top))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:
        # stdout piped into head / flamegraph.pl that exited first — the
        # normal life of collapsed-stack output, not an error. Point the
        # fd at devnull so interpreter shutdown doesn't re-raise.
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
