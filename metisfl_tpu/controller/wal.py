"""Write-ahead round-state log for controller hot-standby failover.

The controller is the federation's last single point of failure
(docs/RESILIENCE.md): registry, scheduler barriers, model store lineage
and the aggregation root all live in one process. This log replicates
the round state a warm standby (``python -m metisfl_tpu.controller
--standby``) needs to take over mid-run, using the acked⇒durable
atomic-rename discipline the slice-aggregator spool established
(store/durable.py):

- **Registry deltas** (``join`` / ``leave``) are appended synchronously
  on the RPC path, BEFORE the join/leave ack returns — a learner the
  primary acked is a learner the promoted standby recognizes (same id,
  token, party index), never a ghost.
- **Snapshots** carry the full checkpoint state
  (``Controller._checkpoint_state()``: community blob, round counter,
  aggregator/SCAFFOLD state, registry lineage, health scores…) and are
  appended by the same coalesced scheduling-executor hook that writes
  the on-disk checkpoint — at model seed, round close, and membership
  bursts. A snapshot makes every older record dead weight, so the log
  self-compacts on append.

Replay (:meth:`RoundStateLog.replay`) merges the latest snapshot with
every registry delta that follows it. Deltas *behind* the snapshot are
already inside it; deltas *after* it keep the registry exact for the
window before the next snapshot lands. The in-flight round itself is
deliberately NOT replicated uplink-by-uplink: promotion re-dispatches it
from the last snapshot's community model (``resume_round``), and because
training and aggregation are deterministic functions of (model, cohort),
the re-run round completes bit-identical to an undisturbed run — the
same argument (and test pin) as checkpoint ``--resume``.

File format: one record per file, ``<seq:010d>.<kind>.rec`` holding a
codec envelope ``{"seq", "kind", "data"}``. One-file-per-record keeps
every append atomic (rename), keeps a torn tail record from corrupting
the log, and lets the standby tail the directory with nothing but
``listdir``.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Any, Dict, List, Optional, Tuple

from metisfl_tpu.comm.codec import dumps_segments as codec_dumps_segments
from metisfl_tpu.comm.codec import loads as codec_loads
from metisfl_tpu.store import durable as _durable

logger = logging.getLogger("metisfl_tpu.controller.wal")

SNAPSHOT = "snapshot"
# registry deltas appended synchronously before the membership ack
JOIN = "join"
LEAVE = "leave"

_RECORD_SUFFIX = ".rec"


def _record_name(seq: int, kind: str) -> str:
    return f"{seq:010d}.{_durable.sanitize_id(kind)}{_RECORD_SUFFIX}"


def _parse_name(name: str) -> Optional[Tuple[int, str]]:
    if not name.endswith(_RECORD_SUFFIX):
        return None
    stem = name[: -len(_RECORD_SUFFIX)]
    seq_part, dot, kind = stem.partition(".")
    if not dot or not seq_part.isdigit():
        return None
    return int(seq_part), kind


class RoundStateLog:
    """Durable, self-compacting record log in one directory.

    Writer side (the primary): :meth:`append` / :meth:`snapshot`, both
    atomic-rename durable before they return. Reader side (the
    standby): :meth:`poll` for cheap tail progress, :meth:`replay` for
    the promote-time state merge. The two sides share nothing but the
    directory — the standby never dials the primary for state."""

    def __init__(self, wal_dir: str):
        if not wal_dir:
            raise ValueError("RoundStateLog requires a wal_dir")
        self.wal_dir = wal_dir
        os.makedirs(wal_dir, exist_ok=True)
        self._lock = threading.Lock()
        self._seq = self._scan_last_seq()

    # -- writer (primary) --------------------------------------------------

    def append(self, kind: str, data: Any) -> int:
        """Durably append one record; returns its sequence number. The
        record is on disk (atomic rename) before this returns — callers
        on the RPC path ack only after."""
        with self._lock:
            self._seq += 1
            seq = self._seq
        payload = codec_dumps_segments(
            {"seq": seq, "kind": kind, "data": data})
        _durable.atomic_write(os.path.join(self.wal_dir,
                                           _record_name(seq, kind)),
                              payload.parts, prefix=".wal_")
        return seq

    def snapshot(self, state: Dict[str, Any]) -> int:
        """Append a full-state snapshot, then prune every older record —
        the snapshot subsumes them, and an unbounded log would make
        promote-time replay (and disk) grow with run length."""
        seq = self.append(SNAPSHOT, state)
        self._compact(before=seq)
        return seq

    def _compact(self, before: int) -> None:
        for name in self._list_records():
            parsed = _parse_name(name)
            if parsed is not None and parsed[0] < before:
                try:
                    os.unlink(os.path.join(self.wal_dir, name))
                except OSError:  # pragma: no cover - racing reader is fine
                    pass

    # -- reader (standby) --------------------------------------------------

    def poll(self) -> int:
        """Highest sequence number currently on disk (0 = empty) — the
        standby's cheap liveness signal: a healthy primary keeps
        appending, a stale tail triggers the health-probe escalation."""
        return self._scan_last_seq()

    def replay(self) -> Tuple[Optional[Dict[str, Any]], List[Dict[str, Any]]]:
        """``(snapshot_state, deltas_after_it)`` — the latest readable
        snapshot's state (None when none landed yet) plus every
        join/leave delta with a higher sequence number, in order. Torn
        or unreadable records are skipped (store/durable.py posture):
        promotion recovers what landed, it does not abort on what did
        not."""
        records: List[Dict[str, Any]] = []
        for name in self._list_records():
            if _parse_name(name) is None:
                continue
            record = _durable.read_tolerant(
                os.path.join(self.wal_dir, name), codec_loads)
            if isinstance(record, dict) and "seq" in record:
                records.append(record)
        records.sort(key=lambda r: int(r["seq"]))
        state: Optional[Dict[str, Any]] = None
        snap_seq = -1
        for record in records:
            if record.get("kind") == SNAPSHOT:
                state, snap_seq = record.get("data"), int(record["seq"])
        deltas = [r for r in records
                  if r.get("kind") != SNAPSHOT and int(r["seq"]) > snap_seq]
        return state, deltas

    @staticmethod
    def merge(state: Optional[Dict[str, Any]],
              deltas: List[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
        """Fold registry deltas into a snapshot's ``learners`` list —
        the promote-time state the standby restores from. A join delta
        carries the full learner record (insert-or-replace by id); a
        leave delta removes it. With no snapshot yet, deltas alone
        build a model-less state (registry-only promotion: the round
        restarts once a model is seeded, exactly like a fresh
        ``--resume`` with an empty checkpoint)."""
        if state is None and not deltas:
            return None
        merged = dict(state or {"global_iteration": 0,
                                "community_blob": b"",
                                "round_metadata": [],
                                "community_evaluations": []})
        learners = {entry["learner_id"]: dict(entry)
                    for entry in merged.get("learners", [])}
        for delta in deltas:
            data = delta.get("data") or {}
            if delta.get("kind") == JOIN and data.get("learner_id"):
                learners[data["learner_id"]] = dict(data)
            elif delta.get("kind") == LEAVE:
                learners.pop(data.get("learner_id"), None)
        merged["learners"] = list(learners.values())
        return merged

    # -- internals ---------------------------------------------------------

    def _list_records(self) -> List[str]:
        try:
            return sorted(os.listdir(self.wal_dir))
        except OSError:
            return []

    def _scan_last_seq(self) -> int:
        last = 0
        for name in self._list_records():
            parsed = _parse_name(name)
            if parsed is not None:
                last = max(last, parsed[0])
        return last
