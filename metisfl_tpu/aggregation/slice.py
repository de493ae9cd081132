"""Slice aggregator: a driver-booted, chaos-killable aggregation process.

PR 7's tree tier made controller fan-in O(branch), but the "branches"
were worker threads inside the controller process — no aggregation
component could fail independently. This module promotes a tree slice to
a real BytesService role (next to controller/learner/serving): a *slice
aggregator* process owns one contiguous cohort slice, receives its
learners' uplinks over gRPC, folds them with the exact kernels the
in-process tier uses (:meth:`TreeReducer._fold_slice` →
``np_stacked_scaled_add``), and answers one ``FoldPartial`` per round —
the controller fans in O(branch) partials and never holds the slice's
models (``aggregation/distributed.py`` is the controller side).

Durability contract (what makes mid-round re-homing possible,
docs/RESILIENCE.md): every accepted uplink is spooled to
``<spool_dir>/<learner_id>.bin`` via atomic rename BEFORE the submit is
acked, so an acked uplink survives the process. When the aggregator dies
mid-round, the controller re-reads the spool directory (driver-booted
slices share the workdir filesystem) and re-homes the slice — surviving
uplinks re-submit to a replacement aggregator or fold directly at the
root, and the round completes (``SliceRehomed``).

Memory model: one fold-ready model tree per owned learner, latest wins —
exactly the ``required_lineage == 1`` semantics of the weighted-sum
rules the tier applies to (fedavg / scaffold / fedstride). ``Forget``
prunes departed learners (the controller's ``leave()`` path).

Entry point::

    python -m metisfl_tpu.aggregation.slice --port 50070 \
        --spool-dir /tmp/slices/slice_0 --name slice_0
    # or, driver-booted: --config federation_config.bin --index 0
"""

from __future__ import annotations

import argparse
import logging
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from metisfl_tpu import telemetry as _tel
from metisfl_tpu.store import durable as _durable
from metisfl_tpu.aggregation.tree import _DEFAULT_SUBBLOCK, TreeReducer
from metisfl_tpu.comm.codec import dumps, loads
from metisfl_tpu.comm.rpc import StopOnce
from metisfl_tpu.secure.distributed import MaskedAccumulator
from metisfl_tpu.telemetry import metrics as _tmetrics
from metisfl_tpu.telemetry import prof as _prof
from metisfl_tpu.telemetry import trace as _ttrace
from metisfl_tpu.telemetry.sketch import QuantileDigest, SpaceSaving
from metisfl_tpu.tensor.pytree import ModelBlob

logger = logging.getLogger("metisfl_tpu.aggregation.slice")

SLICE_SERVICE = "metisfl_tpu.SliceAggregator"

_REG = _tmetrics.registry()
_M_UPLINKS = _REG.counter(
    _tel.M_SLICE_UPLINKS_TOTAL,
    "Uplinks accepted (spooled + held) by this slice aggregator")
_M_HELD = _REG.gauge(
    _tel.M_SLICE_HELD_MODELS,
    "Learner models currently held fold-ready by this slice aggregator")
_M_MASKED_UPLINKS = _REG.counter(
    _tel.M_SECURE_MASKED_UPLINKS_TOTAL,
    "Masked (secure-agg) uplinks accepted by this process")
_M_MASKED_FOLDS = _REG.counter(
    _tel.M_SECURE_MASKED_FOLDS_TOTAL,
    "Masked partial folds performed, by tier",
    labelnames=("tier",))

# stream-mode accumulators kept per round id; anything older than the
# newest few rounds is dead weight (mask streams are round-keyed)
_STREAM_ROUNDS_KEPT = 4


def spool_path(spool_dir: str, learner_id: str) -> str:
    """The learner's spool file. Learner ids are ``L<idx>_<host>_<port>``
    — path-safe by construction; anything else is sanitized, with a
    short digest suffix so two DISTINCT hostile ids can never collide
    onto one file (a collision would let the second acked uplink
    silently overwrite the first's durability record —
    store/durable.py, shared with the controller WAL). The exact id
    rides inside the record either way."""
    return os.path.join(spool_dir, f"{_durable.sanitize_id(learner_id)}.bin")


def read_spool_records(spool_dir: str) -> Dict[str, tuple]:
    """Recover a (possibly dead) aggregator's spooled uplinks:
    ``{learner_id: (round, model blob bytes)}``. Records are codec
    envelopes carrying the EXACT learner id (filenames are sanitized, so
    an id with filesystem-hostile characters would not round-trip
    through them). Torn or unreadable files are skipped with a warning —
    the blob integrity framing downstream rejects garbage anyway, and
    re-homing must recover what it can, not abort on what it cannot.
    The round matters for masked uplinks (mask streams are round-keyed,
    so a recovered payload must only ever fold into its own round)."""
    out: Dict[str, tuple] = {}
    if not os.path.isdir(spool_dir):
        return out

    def _decode(raw: bytes):
        record = loads(raw)
        blob = record["model"]
        ModelBlob.from_bytes(blob)  # integrity check before recovery
        return str(record["learner_id"]), int(record.get("round", 0)), blob

    for name in sorted(os.listdir(spool_dir)):
        if not name.endswith(".bin"):
            continue
        decoded = _durable.read_tolerant(
            os.path.join(spool_dir, name), _decode)
        if decoded is not None:
            out[decoded[0]] = (decoded[1], decoded[2])
    return out


def read_spool(spool_dir: str) -> Dict[str, bytes]:
    """``{learner_id: model blob bytes}`` — see :func:`read_spool_records`."""
    return {lid: blob
            for lid, (_, blob) in read_spool_records(spool_dir).items()}


class SliceAggregator:
    """The slice aggregator's state machine (transport-free; the server
    below mounts it behind a :class:`BytesService`, tests drive it
    directly). Thread-safe: uplinks arrive on RPC threads while the
    controller's fold request runs on another."""

    def __init__(self, spool_dir: str = "", name: str = "slice"):
        self.name = name
        self.spool_dir = spool_dir
        if spool_dir:
            os.makedirs(spool_dir, exist_ok=True)
        # instrumented (telemetry/prof.py): uplink RPC threads contend
        # with the controller's fold request here
        self._lock = _prof.lock("aggregation.slice")
        # learner_id -> (round, fold-ready model tree) — latest wins,
        # the required_lineage == 1 store semantics
        self._models: Dict[str, tuple] = {}
        # masked partial-fold plane (secure/distributed.py): held masked
        # models (learner_id -> (round, opaque dict)) and the stream-mode
        # fold-on-arrival accumulators, one per round id
        self._masked: Dict[str, tuple] = {}
        self._stream_accs: Dict[int, MaskedAccumulator] = {}
        if spool_dir:
            # the durability contract both ways: a RELAUNCHED aggregator
            # reloads its spool, so acked uplinks survive the process —
            # not just for the controller's re-home path but for the
            # driver's supervised relaunch too (a learner that skips the
            # next round keeps its lineage, exactly like the store path)
            for lid, (rid, blob) in read_spool_records(spool_dir).items():
                try:
                    decoded = ModelBlob.from_bytes(blob)
                    if decoded.opaque:
                        # masked uplinks reload as HELD models even when
                        # the live path streams: the fold-time held scan
                        # picks up exactly the round-matched survivors
                        self._masked[lid] = (rid, dict(decoded.opaque))
                    else:
                        self._models[lid] = (rid, dict(decoded.tensors))
                except ValueError:  # pragma: no cover - checked on read
                    continue
            if self._models or self._masked:
                logger.info("slice %s reloaded %d spooled model(s)",
                            name, len(self._models) + len(self._masked))
                _M_HELD.set(len(self._models) + len(self._masked))
        # per-client stats sharded down from the controller: the slice
        # owns its learners' uplink accounting and ships O(1) mergeable
        # sketches to the root (PR 9's rollup format) instead of the
        # root keeping O(fleet) per-learner series
        self._bytes_digest = QuantileDigest()
        self._top_bytes = SpaceSaving(capacity=32)
        self._uplinks = 0

    # -- uplink path (RPC threads) ----------------------------------------
    def submit(self, learner_id: str, round_id: int, blob: bytes,
               stream: bool = False) -> int:
        """Accept one uplink: spool first (atomic — an acked uplink
        survives this process), then hold the decoded tree fold-ready.
        Masked (opaque) payloads hold as uint64 blobs instead — or, with
        ``stream``, fold straight into the round's modular accumulator
        (O(1) resident models; sound because a re-shipped masked payload
        is byte-identical, so duplicate ids simply skip). Returns the
        held-model count."""
        decoded = ModelBlob.from_bytes(blob)
        masked = bool(decoded.opaque)
        model = dict(decoded.opaque) if masked else dict(decoded.tensors)
        if not model:
            raise ValueError("uplink carries no tensors")
        if self.spool_dir:
            path = spool_path(self.spool_dir, learner_id)
            # codec envelope: the EXACT learner id rides inside the
            # record (the sanitized filename alone would not round-trip
            # a filesystem-hostile id through recovery)
            record = dumps({"learner_id": learner_id,
                            "round": int(round_id), "model": blob})
            _durable.atomic_write(path, record, prefix=".up_")
        rid = int(round_id)
        with self._lock:
            if masked and stream:
                acc = self._stream_accs.get(rid)
                if acc is None:
                    acc = self._stream_accs[rid] = MaskedAccumulator()
                    while len(self._stream_accs) > _STREAM_ROUNDS_KEPT:
                        self._stream_accs.pop(min(self._stream_accs))
                acc.fold(learner_id, model)
            elif masked:
                self._masked[learner_id] = (rid, model)
            else:
                self._models[learner_id] = (rid, model)
            held = len(self._models) + len(self._masked)
            self._uplinks += 1
            self._bytes_digest.add(float(len(blob)))
            self._top_bytes.update(learner_id, float(len(blob)))
        _M_UPLINKS.inc()
        if masked:
            _M_MASKED_UPLINKS.inc()
        _M_HELD.set(held)
        return held

    def forget(self, learner_ids) -> int:
        """Prune departed learners (controller ``leave()``): drop the
        held model and the spool file. Returns how many were held."""
        dropped = 0
        with self._lock:
            for lid in learner_ids:
                if self._models.pop(lid, None) is not None:
                    dropped += 1
                if self._masked.pop(lid, None) is not None:
                    dropped += 1
                # a stream-folded contribution stays in its round's sum
                # (modular folds are not reversible without the payload);
                # masks still cancel and settlement counts the contributor
                self._top_bytes.drop(lid)
            held = len(self._models) + len(self._masked)
        _M_HELD.set(held)
        if self.spool_dir:
            for lid in learner_ids:
                try:
                    os.unlink(spool_path(self.spool_dir, lid))
                except OSError:
                    pass
        return dropped

    # -- fold path (controller's FoldPartial) ------------------------------
    def fold(self, ids, scales: Dict[str, float],
             stride: int = 0) -> Dict[str, Any]:
        """Fold the held models for ``ids`` (in the given order, with the
        in-process tier's sub-block blocking — same kernels, same
        accumulator dtype, so the partial is bit-identical to what a
        :class:`TreeReducer` worker would have produced from the same
        models). Returns the wire-ready partial dict."""
        with self._lock:
            snapshot = {lid: self._models[lid][1] for lid in ids
                        if lid in self._models}

        def fetch(block):
            return {lid: [snapshot[lid]] for lid in block
                    if lid in snapshot}

        subblock = int(stride) or _DEFAULT_SUBBLOCK
        # named fold span under the ambient rpc.server/FoldPartial: the
        # critical-path edge then reads "<slice>/slice.fold", not a bare
        # RPC method
        with _ttrace.span("slice.fold",
                          attrs={"slice": self.name, "ids": len(ids)}):
            partial = TreeReducer._fold_slice(list(ids), scales, fetch,
                                              subblock)
        reply: Dict[str, Any] = {
            "ok": True,
            "count": partial.count,
            "z": float(partial.z),
            "duration_ms": round(partial.duration_ms, 3),
            "dtypes": list(partial.dtypes or ()),
            "present": [lid for lid in ids if lid in snapshot],
            "acc": b"",
            "stats": self.stats(),
        }
        if partial.acc is not None:
            reply["acc"] = ModelBlob(
                tensors=[(name, np.asarray(arr))
                         for name, arr in sorted(partial.acc.items())]
            ).to_bytes()
        return reply

    def fold_masked(self, ids, round_id: int,
                    stream: bool = False) -> Dict[str, Any]:
        """Masked partial fold (secure/distributed.py): per-tensor uint64
        sums mod 2^64 over this slice's contributors — no scales, no
        keys, no new crypto; masks cancel at the root by construction.
        Starts from the round's stream accumulator (fold-on-arrival mode)
        and adds any HELD round-matched masked models for the requested
        ids the stream has not seen (the relaunch-reload path). The
        reply's ``present`` list is the ground truth the root's mask
        settlement reconciles against the dispatched cohort."""
        rid = int(round_id)
        t0 = time.perf_counter()
        out = MaskedAccumulator()
        with self._lock:
            if stream:
                acc = self._stream_accs.get(rid)
                if acc is not None:
                    sums, specs, contributors = acc.snapshot()
                    out.merge_sums(sums, contributors, specs)
            for lid in ids:
                held = self._masked.get(lid)
                if held is None or held[0] != rid:
                    continue
                out.fold(lid, held[1])
        sums, specs, present = out.snapshot()
        duration_ms = (time.perf_counter() - t0) * 1e3
        _M_MASKED_FOLDS.inc(tier="slice")
        reply: Dict[str, Any] = {
            "ok": True,
            "masked": True,
            "count": out.count,
            "duration_ms": round(duration_ms, 3),
            "present": present,
            "acc": b"",
            "stats": self.stats(),
        }
        if sums:
            reply["acc"] = ModelBlob(opaque={
                name: (sums[name].tobytes(), specs[name])
                for name in sorted(sums)}).to_bytes()
        return reply

    def stats(self) -> Dict[str, Any]:
        """The slice's per-client rollup as mergeable sketches (PR 9's
        slice→root format): uplink-bytes quantile digest + top offenders
        by bytes. O(compression), however many learners the slice owns."""
        with self._lock:
            return {
                "name": self.name,
                "held": len(self._models) + len(self._masked),
                "uplinks": self._uplinks,
                "bytes_digest": self._bytes_digest.to_dict(),
                "top_bytes": self._top_bytes.to_dict(),
            }


class SliceServer(StopOnce):
    """Host a :class:`SliceAggregator` behind gRPC: the BytesService role
    (ListMethods / GetMetrics / CollectTelemetry mounted like every other
    role) plus grpc.health.v1 — the controller's slice supervision probes
    it with :func:`metisfl_tpu.comm.health.probe_health`."""

    def __init__(self, spool_dir: str = "", name: str = "slice",
                 host: str = "0.0.0.0", port: int = 0, ssl=None):
        from metisfl_tpu.comm.health import SERVING, HealthServicer
        from metisfl_tpu.comm.rpc import BytesService, RpcServer

        super().__init__()
        self.aggregator = SliceAggregator(spool_dir=spool_dir, name=name)
        self._server = RpcServer(host, port, ssl=ssl)
        self._health = HealthServicer()
        self._health.set_status(SLICE_SERVICE, SERVING)
        self._server.add_service(self._health.service())
        self._server.add_service(BytesService(SLICE_SERVICE, {
            "SubmitUplink": self._submit,
            "FoldPartial": self._fold,
            "Forget": self._forget,
            "DescribeSlice": self._describe,
            "GetHealthStatus": self._health_rpc,
            "GetMetrics": self._get_metrics,
            "ShutDown": self._shutdown_rpc,
        }, role="slice"))
        self.port: Optional[int] = None

    # -- handlers (RPC threads) -------------------------------------------
    def _submit(self, raw: bytes) -> bytes:
        req = loads(raw)
        held = self.aggregator.submit(str(req["learner_id"]),
                                      int(req.get("round", 0)),
                                      req["model"],
                                      stream=bool(req.get("stream", False)))
        return dumps({"ok": True, "held": held})

    def _fold(self, raw: bytes) -> bytes:
        req = loads(raw)
        ids = [str(lid) for lid in req.get("ids", [])]
        if bool(req.get("masked", False)):
            return dumps(self.aggregator.fold_masked(
                ids, int(req.get("round", 0)),
                stream=bool(req.get("stream", False))))
        return dumps(self.aggregator.fold(
            ids,
            {str(k): float(v) for k, v in (req.get("scales") or {}).items()},
            stride=int(req.get("stride", 0))))

    def _forget(self, raw: bytes) -> bytes:
        req = loads(raw)
        dropped = self.aggregator.forget(
            [str(lid) for lid in req.get("learner_ids", [])])
        return dumps({"ok": True, "dropped": dropped})

    def _describe(self, raw: bytes) -> bytes:
        return dumps(self.aggregator.stats())

    def _health_rpc(self, raw: bytes) -> bytes:
        return dumps({"status": "SERVING", "name": self.aggregator.name})

    def _get_metrics(self, raw: bytes) -> bytes:
        return _tel.render_metrics().encode("utf-8")

    def _shutdown_rpc(self, raw: bytes) -> bytes:
        threading.Thread(target=self.stop, daemon=True).start()
        return dumps({"ok": True})

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> int:
        self.port = self._server.start()
        return self.port

    def _teardown(self) -> None:
        from metisfl_tpu.comm.health import NOT_SERVING

        self._health.set_all(NOT_SERVING)
        self._server.stop()


class SliceClient:
    """Controller → slice aggregator transport. No transparent retries —
    the distributed tier owns the retry/backoff/re-home policy, so a dead
    endpoint must surface immediately (``retries=0``, no wait-for-ready:
    liveness counts in seconds, not channel backoff)."""

    def __init__(self, host: str, port: int, ssl=None, comm=None,
                 timeout_s: float = 30.0):
        from metisfl_tpu.comm.rpc import RpcClient

        kwargs = {}
        if comm is not None:
            kwargs["default_deadline_s"] = comm.default_deadline_s
        self.target = f"{host}:{port}"
        self.timeout_s = timeout_s
        self._client = RpcClient(host, port, SLICE_SERVICE, retries=0,
                                 ssl=ssl, **kwargs)

    def submit(self, learner_id: str, round_id: int, blob: bytes,
               stream: bool = False) -> dict:
        return loads(self._client.call(
            "SubmitUplink",
            dumps({"learner_id": learner_id, "round": int(round_id),
                   "model": blob, "stream": bool(stream)}),
            timeout=self.timeout_s, wait_ready=False))

    def fold(self, ids, scales, stride: int = 0,
             timeout: Optional[float] = None) -> dict:
        return loads(self._client.call(
            "FoldPartial",
            dumps({"ids": list(ids), "scales": dict(scales),
                   "stride": int(stride)}),
            timeout=timeout or max(self.timeout_s, 120.0),
            wait_ready=False))

    def fold_masked(self, ids, round_id: int, stream: bool = False,
                    timeout: Optional[float] = None) -> dict:
        return loads(self._client.call(
            "FoldPartial",
            dumps({"ids": list(ids), "masked": True,
                   "round": int(round_id), "stream": bool(stream)}),
            timeout=timeout or max(self.timeout_s, 120.0),
            wait_ready=False))

    def forget(self, learner_ids) -> dict:
        return loads(self._client.call(
            "Forget", dumps({"learner_ids": list(learner_ids)}),
            timeout=self.timeout_s, wait_ready=False))

    def describe(self) -> dict:
        return loads(self._client.call("DescribeSlice", b"",
                                       timeout=self.timeout_s,
                                       wait_ready=False, idempotent=True))

    def shutdown_remote(self) -> None:
        self._client.call("ShutDown", b"", timeout=5.0, wait_ready=False)

    def close(self) -> None:
        self._client.close()


def main(argv: Optional[List[str]] = None) -> int:
    from metisfl_tpu.platform import enter_process
    enter_process()
    parser = argparse.ArgumentParser(
        "metisfl_tpu.aggregation.slice",
        description="slice aggregator process (BytesService role 'slice')")
    parser.add_argument("--config", default="",
                        help="federation config file (wire or YAML); the "
                             "endpoint comes from aggregation.tree."
                             "slices[--index]")
    parser.add_argument("--index", type=int, default=0,
                        help="this aggregator's entry in aggregation."
                             "tree.slices (with --config)")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--spool-dir", default="")
    parser.add_argument("--name", default="")
    args = parser.parse_args(argv)

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    host, port = args.host, args.port
    spool_dir, name = args.spool_dir, args.name
    ssl = None
    if args.config:
        from metisfl_tpu.config import FederationConfig, load_config
        if args.config.endswith((".yaml", ".yml")):
            config = load_config(args.config)
        else:
            with open(args.config, "rb") as fh:
                config = FederationConfig.from_wire(fh.read())
        slices = config.aggregation.tree.slices
        if not 0 <= args.index < len(slices):
            parser.error(f"--index {args.index} out of range for "
                         f"{len(slices)} configured slice(s)")
        spec = slices[args.index]
        port = port or int(spec.get("port", 0))
        spool_dir = spool_dir or str(spec.get("spool_dir", ""))
        name = name or str(spec.get("name", ""))
        ssl = config.ssl
        _tel.apply_config(config.telemetry,
                          service=name or f"slice_{args.index}")
    name = name or f"slice_{os.getpid()}"
    server = SliceServer(spool_dir=spool_dir, name=name, host=host,
                         port=port, ssl=ssl)
    bound = server.start()
    logger.info("slice aggregator %s listening on %s:%d (spool %s)",
                name, host, bound, spool_dir or "<off>")
    try:
        server.wait_for_shutdown()
    except KeyboardInterrupt:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
