"""Offline performance analyzer: ``python -m metisfl_tpu.perf``.

The reading half of the performance observatory (telemetry/profile.py):

- **run-dir mode** — render the per-round phase waterfall from the
  RoundProfiles a run recorded (``profiles-*.jsonl`` next to the traces,
  or ``experiment.json`` round metadata), plus the top-N span self-time
  table from ``traces.jsonl`` when present::

      python -m metisfl_tpu.perf <workdir>
      python -m metisfl_tpu.perf experiment.json --round 3 --top 10

- **--compare A.json B.json** — diff two bench captures key-by-key with
  direction-aware relative-threshold regression flags and a CI-friendly
  exit code (1 = regression detected, 0 = clean)::

      python -m metisfl_tpu.perf --compare BENCH_r08.json BENCH_r09.json

- **--trajectory <dir-or-files>** — the same diff across a whole series
  of captures (consecutive pairs), e.g. the repo's ``BENCH_r0*.json``
  driver captures. Degraded captures parse via the single-line
  ``METISFL_BENCH`` marker bench.py appends (and older full-JSON tail
  lines); unparseable ones are reported and skipped, never fatal.

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

Bench noise floor: captures may carry a ``details.repeats`` map
(``{key: K}`` — bench.py re-measured ms-scale keys median-of-K on hosts
whose run-to-run spread exceeds the gate). The comparison rows carry
the per-key ``repeats`` field and the renderer marks them ``xK`` so a
gated median is distinguishable from a single shot.

Host provenance: a capture may declare the machine it ran on (a
``host`` string in the result / ``parsed`` payload; bench.py stamps it
from ``METISFL_BENCH_HOST`` or ``platform.node()``). A pair is **gated**
(regressions fail the build) only when both captures name the same
host, or neither names one (the pre-provenance record): absolute
host-sensitive keys — RSS accounting, disk latencies — are not
comparable across a hardware move, so a cross-host pair renders its
rows informationally and never exits 1 on them. A collapsed headline
(``*_failed`` shape) still fails regardless — a bench that stopped
producing results is broken on any host.

Library-usable: :func:`load_profiles`, :func:`render_waterfall`,
:func:`span_self_times`, :func:`load_bench_capture`,
:func:`compare_captures`.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

# bench.py stamps this on every result and prefixes the final marker
# line with it — the trajectory parser's anchor on degraded runs whose
# main JSON line was truncated by the capture harness
BENCH_MARKER = "METISFL_BENCH "

# flattened-capture key carrying the declared capture host (never judged
# — metric_direction reports 0 for it; see "Host provenance" above)
HOST_KEY = "_host"

# flattened-capture key carrying the per-key repeat counts (a dict, so
# the numeric _take filter skips it; comparison rows re-attach it)
REPEATS_KEY = "_repeats"

# default relative-change threshold for regression flags (20% — well
# under the 30% regressions the acceptance gate injects, well over
# normal run-to-run jitter for the judged keys)
DEFAULT_THRESHOLD = 0.2


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
# bench-capture loading (raw results, driver captures, degraded tails)
# --------------------------------------------------------------------- #

def load_bench_capture(path: str) -> Dict[str, Any]:
    """One bench capture as a flat ``{key: float}`` dict, from any of the
    shapes this repo records:

    - a raw ``bench.py`` result line saved as JSON;
    - a driver capture ``{"n", "cmd", "rc", "tail", "parsed"}`` —
      ``parsed`` when present, else the tail scanned for the
      ``METISFL_BENCH`` marker line or a full result JSON line;
    - a watcher/partial capture ``{"details": {...}}``.

    Returns ``{}`` when nothing parseable is found (reported by the
    caller, never fatal)."""
    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return {}
    if not isinstance(data, dict):
        return {}
    if "metric" in data or "value" in data:
        return flatten_bench(data)
    if "parsed" in data or "tail" in data:
        parsed = data.get("parsed")
        if isinstance(parsed, dict) and parsed:
            return flatten_bench(parsed)
        return _parse_capture_tail(str(data.get("tail") or ""))
    if "details" in data:
        return flatten_bench(data)
    return {}


def capture_host(flat: Dict[str, Any]) -> str:
    """The capture's declared host identity ('' = pre-provenance
    capture). Kept under a non-judgeable key by :func:`flatten_bench`."""
    return str(flat.get(HOST_KEY, "") or "")


def _parse_capture_tail(tail: str) -> Dict[str, Any]:
    """Recover a result from a captured stdout tail: the final
    ``METISFL_BENCH`` marker wins (it is small, so it survives
    head-truncation of the capture window); else the last line that
    parses as a full result JSON."""
    marker: Optional[dict] = None
    full: Optional[dict] = None
    for line in tail.splitlines():
        line = line.strip()
        if line.startswith(BENCH_MARKER):
            try:
                candidate = json.loads(line[len(BENCH_MARKER):])
                if isinstance(candidate, dict):
                    marker = candidate
            except json.JSONDecodeError:
                continue
        elif line.startswith("{"):
            try:
                candidate = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(candidate, dict) and ("metric" in candidate
                                                or "details" in candidate):
                full = candidate
    if full is not None:
        flat = flatten_bench(full)
        if marker is not None:
            flat.setdefault("schema_version",
                            marker.get("schema_version", 0))
        return flat
    if marker is not None:
        return flatten_bench(marker)
    return {}


_EXCLUDE_KEYS = {
    # harness bookkeeping, timestamps, and identity keys — never judged
    # (probe_attempts: captures from before PR 21 still carry it)
    "n", "rc", "ts", "schema_version", "errors", "probe_attempts",
    "devices", "bench_wall_s",
}


def flatten_bench(capture: Dict[str, Any]) -> Dict[str, Any]:
    """Numeric keys from a bench result: top-level value/vs_baseline/mfu
    plus every numeric ``details`` entry, excluding harness bookkeeping."""
    flat: Dict[str, Any] = {}

    def _take(key: str, value: Any) -> None:
        if key in _EXCLUDE_KEYS or isinstance(value, bool):
            return
        if isinstance(value, (int, float)):
            flat[key] = float(value)

    for key in ("value", "vs_baseline", "mfu"):
        if key in capture:
            _take(key, capture[key])
    for key, value in (capture.get("details") or {}).items():
        _take(key, value)
    # marker-shaped captures carry their numerics at the top level
    if "details" not in capture:
        for key, value in capture.items():
            _take(key, value)
    if capture.get("host"):
        flat[HOST_KEY] = str(capture["host"])
    repeats = (capture.get("details") or {}).get("repeats")
    if isinstance(repeats, dict) and repeats:
        flat[REPEATS_KEY] = {str(k): int(v) for k, v in repeats.items()
                             if isinstance(v, (int, float))}
    return flat


# --------------------------------------------------------------------- #
# direction-aware comparison
# --------------------------------------------------------------------- #

# substrings that classify a key's improvement direction. Higher-better
# patterns are checked FIRST: throughput keys like samples_per_sec would
# otherwise match the lower-better "_s"/"secs" time patterns.
_HIGHER_BETTER = ("mfu", "per_sec", "tokens_per", "samples_per",
                  "throughput", "vs_baseline", "hit_rate", "tflops",
                  "rows_per", "speedup", "accuracy")
_LOWER_BETTER = ("_ms", "ms_per", "_secs", "seconds", "_bytes", "_mb",
                 "_kb", "rss", "wall", "latency", "pause",
                 # obs section: sketch-vs-exact quantile error — a
                 # growing error means the digest got worse, a regression
                 "relerr",
                 # prof section: nanosecond-scale per-acquire lock costs
                 # (the overhead *percentage* is deliberately unjudged —
                 # a ratio of two noisy medians would flag pure noise;
                 # the chaos_smoke prof gate bounds it absolutely)
                 "_ns",
                 # runtime section: a growing steady-state recompile
                 # count is always a regression (the smoke gate pins the
                 # decode path's at zero absolutely)
                 "recompile",
                 # secure section: the secure-vs-plain round-time
                 # multiplier — masking overhead growing is a regression
                 "multiplier")


def metric_direction(key: str) -> int:
    """+1 = higher is better, -1 = lower is better, 0 = don't judge."""
    k = key.lower()
    if k == "value":
        # the headline bench value is aggregation ms/round
        return -1
    for pat in _HIGHER_BETTER:
        if pat in k:
            return 1
    for pat in _LOWER_BETTER:
        if pat in k:
            return -1
    if k.endswith("_s") or "_s_" in k or k.endswith("_insert_s"):
        return -1
    return 0


def compare_captures(a: Dict[str, Any], b: Dict[str, Any],
                     threshold: float = DEFAULT_THRESHOLD
                     ) -> List[Dict[str, Any]]:
    """Key-by-key relative diff of two flattened captures: one row per
    shared judgeable key, ``regressed=True`` where B is worse than A by
    more than ``threshold`` (relative, direction-aware)."""
    rows: List[Dict[str, Any]] = []
    rep_a = a.get(REPEATS_KEY) or {}
    rep_b = b.get(REPEATS_KEY) or {}
    for key in sorted(set(a) & set(b)):
        direction = metric_direction(key)
        if direction == 0:
            continue
        va, vb = float(a[key]), float(b[key])
        if va <= 0.0:
            continue  # no baseline to be relative to
        if vb <= 0.0 and direction < 0:
            # a lower-better metric at 0 means the subsystem recorded
            # nothing (errored/skipped section, zero-filled degraded
            # capture), not an infinite speedup — don't judge it.
            # Higher-better keys keep judging: throughput collapsing to
            # 0 IS the regression.
            continue
        rel = (vb - va) / abs(va)
        regressed = (rel > threshold if direction < 0
                     else rel < -threshold)
        improved = (rel < -threshold if direction < 0
                    else rel > threshold)
        rows.append({"key": key, "a": va, "b": vb, "rel": rel,
                     "direction": direction, "regressed": regressed,
                     "improved": improved,
                     # bench noise floor: how many measurements back each
                     # side (1 = single shot; >1 = median-of-K, bench.py
                     # re-measured a ms-scale key under the repeat
                     # threshold) — carried so the gate's verdict is
                     # auditable as a median, not a lucky shot
                     "repeats": max(int(rep_a.get(key, 1)),
                                    int(rep_b.get(key, 1)))})
    return rows


def capture_collapsed(a: Dict[str, Any], b: Dict[str, Any]) -> bool:
    """True when capture B's headline collapsed while A had one: the
    later run recorded value<=0 (bench.py's *_failed shape zero-fills
    it) or lost the key entirely. Per-key comparison deliberately skips
    lower-better zeros — this capture-level check is what keeps a bench
    that stopped producing results at all from passing the CI gate."""
    va = a.get("value")
    if va is None or va <= 0.0:
        return False  # no healthy baseline to collapse from
    vb = b.get("value")
    return vb is None or vb <= 0.0


def render_comparison(rows: List[Dict[str, Any]],
                      label_a: str = "A", label_b: str = "B",
                      show_all: bool = False) -> str:
    lines = [f"{'key':<36} {label_a:>12} {label_b:>12} {'change':>9}"]
    for row in rows:
        if not (show_all or row["regressed"] or row["improved"]):
            continue
        tag = ("  REGRESSED" if row["regressed"]
               else "  improved" if row["improved"] else "")
        if int(row.get("repeats", 1)) > 1:
            tag += f"  x{int(row['repeats'])}"
        lines.append(f"{row['key']:<36} {row['a']:>12.4g} "
                     f"{row['b']:>12.4g} {row['rel'] * 100:>+8.1f}%{tag}")
    if len(lines) == 1:
        lines.append("(no judgeable shared keys moved past the threshold)")
    return "\n".join(lines)


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


def _trajectory_paths(args: List[str]) -> List[str]:
    paths: List[str] = []
    for arg in args:
        if os.path.isdir(arg):
            paths.extend(sorted(glob.glob(os.path.join(arg, "*.json"))))
        else:
            paths.append(arg)
    return paths


# --------------------------------------------------------------------- #
# CLI
# --------------------------------------------------------------------- #

def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        "metisfl_tpu.perf",
        description="performance observatory analyzer: round-profile "
                    "waterfalls, span self-times, bench regression diffs")
    parser.add_argument("paths", nargs="*",
                        help="run dir / profiles .jsonl / experiment.json "
                             "(default mode), or capture files for "
                             "--compare/--trajectory")
    parser.add_argument("--compare", nargs=2, metavar=("A", "B"),
                        help="diff two bench captures; exit 1 on regression")
    parser.add_argument("--trajectory", nargs="+", metavar="PATH",
                        help="diff a series of bench captures pairwise "
                             "(files and/or dirs of .json); exit 1 on "
                             "regression")
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
    parser.add_argument("--threshold", type=float,
                        default=DEFAULT_THRESHOLD,
                        help="relative regression threshold "
                             f"(default {DEFAULT_THRESHOLD})")
    parser.add_argument("--round", type=int, default=None,
                        help="waterfall: only this round")
    parser.add_argument("--top", type=int, default=15,
                        help="span self-time rows to show")
    parser.add_argument("--all", action="store_true",
                        help="comparison: show unchanged keys too")
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
    if args.compare:
        return _compare_main(args.compare[0], args.compare[1],
                             args.threshold, args.all)
    if args.trajectory:
        return _trajectory_main(_trajectory_paths(args.trajectory),
                                args.threshold)
    if not args.paths:
        parser.print_usage(sys.stderr)
        return 2
    return _waterfall_main(args.paths, args.round, args.top)


def _compare_main(path_a: str, path_b: str, threshold: float,
                  show_all: bool) -> int:
    a, b = load_bench_capture(path_a), load_bench_capture(path_b)
    for path, flat in ((path_a, a), (path_b, b)):
        if not flat:
            print(f"cannot parse a bench result from {path}",
                  file=sys.stderr)
            return 2
    rows = compare_captures(a, b, threshold=threshold)
    print(render_comparison(rows, label_a=os.path.basename(path_a),
                            label_b=os.path.basename(path_b),
                            show_all=show_all))
    regressions = [r for r in rows if r["regressed"]]
    if capture_collapsed(a, b):
        # gated regardless of host: a bench that stopped producing a
        # headline is broken on any machine
        print(f"REGRESSED: {os.path.basename(path_b)} headline value "
              f"collapsed to {b.get('value', 'absent')} (failed/degraded "
              f"run)", file=sys.stderr)
        return 1
    host_a, host_b = capture_host(a), capture_host(b)
    if host_a != host_b:
        print(f"\nhost changed ({host_a or 'undeclared'} -> "
              f"{host_b or 'undeclared'}): absolute host-sensitive keys "
              "are not comparable — rows above are informational, not "
              "gated", file=sys.stderr)
        return 0
    if regressions:
        print(f"\n{len(regressions)} regression(s) past "
              f"{threshold * 100:.0f}% threshold", file=sys.stderr)
        return 1
    return 0


def _trajectory_main(paths: List[str], threshold: float) -> int:
    captures: List[Tuple[str, Dict[str, Any]]] = []
    for path in paths:
        flat = load_bench_capture(path)
        if flat:
            captures.append((os.path.basename(path), flat))
        else:
            print(f"skipping unparseable capture {path}", file=sys.stderr)
    if len(captures) < 2:
        print("need at least two parseable captures for a trajectory",
              file=sys.stderr)
        return 2
    any_regression = False
    for (name_a, a), (name_b, b) in zip(captures, captures[1:]):
        rows = compare_captures(a, b, threshold=threshold)
        regressions = [r for r in rows if r["regressed"]]
        improvements = [r for r in rows if r["improved"]]
        host_a, host_b = capture_host(a), capture_host(b)
        cross_host = host_a != host_b
        print(f"{name_a} -> {name_b}: {len(regressions)} regression(s), "
              f"{len(improvements)} improvement(s) over "
              f"{len(rows)} judged key(s)"
              + (f"  [host changed: {host_a or 'undeclared'} -> "
                 f"{host_b or 'undeclared'}; informational, not gated]"
                 if cross_host else ""))
        for row in regressions:
            print(f"  REGRESSED {row['key']}: {row['a']:.4g} -> "
                  f"{row['b']:.4g} ({row['rel'] * 100:+.1f}%)")
        if cross_host:
            regressions = []  # collapse check below still gates
        if capture_collapsed(a, b):
            print(f"  REGRESSED {name_b}: headline value collapsed to "
                  f"{b.get('value', 'absent')} (failed/degraded run)")
            regressions.append({"key": "value"})
        any_regression = any_regression or bool(regressions)
    return 1 if any_regression else 0


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
        return 2  # unusable input, same code as the compare modes
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
