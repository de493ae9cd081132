"""Controller gRPC service + RPC-backed learner proxy.

RPC surface of the reference's ``ControllerServicer``
(reference metisfl/controller/core/controller_servicer.cc:110-382,
metisfl/proto/controller.proto:8-49): join/leave federation, mark task
completed, replace/get community model, statistics lineage, health, shutdown.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
import uuid
from typing import Callable, Optional

from metisfl_tpu import telemetry as _tel
from metisfl_tpu.comm import codec as _codec
from metisfl_tpu.comm.codec import dumps, loads
from metisfl_tpu.comm.messages import (
    EvalResult,
    EvalTask,
    JoinReply,
    JoinRequest,
    ModelRefMissing,
    TaskResult,
    TrainTask,
)
from metisfl_tpu.comm.rpc import (BytesService, RpcClient, RpcServer,
                                  StopOnce)
from metisfl_tpu.controller.core import Controller, LearnerRecord
from metisfl_tpu.telemetry import metrics as _tmetrics
from metisfl_tpu.telemetry import profile as _tprofile
from metisfl_tpu.telemetry import trace as _trace

logger = logging.getLogger("metisfl_tpu.controller.service")

_REG = _tmetrics.registry()
_M_EVAL_REF = _REG.counter(
    _tel.M_EVAL_MODEL_REF_TOTAL,
    "Evaluations that named the blob the learner's last RunTask carried, "
    "by outcome (sent | resent: the learner missed it, the bytes went)",
    ("outcome",))
_M_EVAL_REF_SAVED = _REG.counter(
    _tel.M_EVAL_MODEL_REF_SAVED_BYTES_TOTAL,
    "Model bytes that evaluations answered by reference did not send")

CONTROLLER_SERVICE = "metisfl_tpu.Controller"
LEARNER_SERVICE = "metisfl_tpu.Learner"


def _comm_kwargs(comm) -> dict:
    """RpcClient kwargs from a config ``comm`` section (None → library
    defaults) — one translation point so every client construction stays
    deadline-bounded by default."""
    if comm is None:
        return {}
    return {"default_deadline_s": comm.default_deadline_s,
            "retries": comm.retries,
            "retry_sleep_s": comm.retry_sleep_s}


class RpcLearnerProxy:
    """Controller → remote learner over gRPC (async dispatch, mirroring the
    reference's CompletionQueue fan-out, controller.cc:713-759)."""

    def __init__(self, record: LearnerRecord, ssl=None, comm=None):
        # peer=learner_id: the transport attributes this channel's wire
        # bytes (envelopes included) to the learner — the performance
        # observatory's rpc_peer_bytes_total series, pruned on leave.
        # Gated on the ACTIVE collector (set at controller construction,
        # before any proxy exists): with the profile plane off, no
        # per-learner attribution series are ever minted — the opt-out
        # contract — and nothing needs pruning on leave.
        profiled = _tprofile.collector() is not None
        self._client = RpcClient(record.hostname, record.port, LEARNER_SERVICE,
                                 ssl=ssl,
                                 peer=record.learner_id if profiled else "",
                                 **_comm_kwargs(comm))
        # (blob, model_ref) of the last RunTask on this channel: an
        # evaluation of that very object names it (evaluate)
        self._sent_lock = threading.Lock()
        self._sent: Optional[tuple] = None

    @staticmethod
    def _to_wire_attributed(task):
        # segments: the model blob rides the envelope as the object it
        # is, and the transport gathers it (comm/codec.py Segments).
        # attributed(): the envelope encode lands in the learner's
        # codec_learner_seconds_total series; profile off → plain
        # encode, no attribution series minted
        if _tprofile.collector() is None:
            return task.to_segments()
        with _codec.attributed(task.learner_id):
            return task.to_segments()

    def run_task(self, task: TrainTask) -> None:
        self._client.call_async("RunTask", self._to_wire_attributed(task))

    def run_task_with_callback(self, task: TrainTask, on_error) -> None:
        """Dispatch + failure notification: feeds the controller's learner
        liveness tracking (consecutive failed dispatches). The task names
        its blob (``model_ref``), and the proxy remembers which object it
        was until the call fails."""
        ref = uuid.uuid4().hex
        task = dataclasses.replace(task, model_ref=ref)
        payload = self._to_wire_attributed(task)

        def _failed(exc: Exception) -> None:
            self._forget(ref)
            on_error(exc)

        with self._sent_lock:
            self._sent = (task.model, ref)
        # RunTask acks immediately (non-blocking learner dispatch):
        # wait_ready=False surfaces UNAVAILABLE from a dead endpoint at once
        # (liveness counts in seconds, not 60 s deadlines), and the timeout
        # bounds a connected-but-unresponsive peer.
        try:
            self._client.call_async("RunTask", payload,
                                    error_callback=_failed, timeout=60.0,
                                    wait_ready=False)
        except Exception:
            self._forget(ref)
            raise

    def _forget(self, ref: str) -> None:
        with self._sent_lock:
            if self._sent is not None and self._sent[1] == ref:
                self._sent = None

    def evaluate(self, task: EvalTask,
                 callback: Callable[[EvalResult], None]) -> int:
        """Returns the model bytes put on the wire: 0 where the last
        RunTask carried ``task.model`` (the very object) and the task
        names it instead. A learner that no longer holds it answers
        :class:`ModelRefMissing`, and the evaluation goes once more with
        its bytes."""
        with self._sent_lock:
            sent = self._sent
            named = sent is not None and task.model is sent[0]
            if named:
                self._sent = None       # one evaluation per RunTask
        if not named:
            self._evaluate(task, callback)
            return len(task.model)
        nbytes = len(task.model)
        ctx = _trace.current_context()

        def _answered(raw: bytes) -> None:
            _M_EVAL_REF_SAVED.inc(nbytes)
            callback(EvalResult.from_wire(raw))

        def _failed(exc: Exception) -> None:
            if not ModelRefMissing.raised(exc):
                logger.warning("evaluation by reference on %s failed: %s",
                               self._client.target, exc)
                return
            _M_EVAL_REF.inc(outcome="resent")
            collector = _tprofile.collector()
            if self._client.peer and collector is not None:
                # the bytes cross after all (peer attribution is cleared
                # on leave, as for the transport's own series)
                collector.note_downlink(self._client.peer, nbytes)
            with _trace.use_context(ctx):
                self._evaluate(task, callback)

        _M_EVAL_REF.inc(outcome="sent")
        by_ref = dataclasses.replace(task, model=b"", model_ref=sent[1])
        self._client.call_async("EvaluateModel",
                                self._to_wire_attributed(by_ref),
                                callback=_answered, error_callback=_failed)
        return 0

    def _evaluate(self, task: EvalTask,
                  callback: Callable[[EvalResult], None]) -> None:
        self._client.call_async(
            "EvaluateModel", self._to_wire_attributed(task),
            callback=lambda raw: callback(EvalResult.from_wire(raw)))

    def recover_masks(self, round_id: int, surviving, dropped,
                      lengths) -> list:
        """Blocking masking-dropout-recovery request (secure/masking.py):
        one survivor computes the dropped parties' residual masks."""
        from metisfl_tpu.comm.codec import dumps, loads

        raw = self._client.call("RecoverMasks", dumps(
            {"round_id": int(round_id), "surviving": list(surviving),
             "dropped": list(dropped), "lengths": list(lengths)}),
            timeout=60.0, wait_ready=False)
        return loads(raw)["corrections"]

    def detach_peer(self) -> None:
        """Stop attributing this channel's bytes to the learner: called
        on leave, BEFORE the per-peer series are pruned, so an in-flight
        call's completion callback cannot re-mint them afterwards."""
        self._client.peer = ""

    def shutdown(self) -> None:
        try:
            self._client.call_async("ShutDown", b"")
        finally:
            pass


class ControllerServer(StopOnce):
    """Host a :class:`Controller` behind gRPC."""

    def __init__(self, controller: Controller, host: str = "0.0.0.0",
                 port: int = 50051, ssl=None):
        from metisfl_tpu.comm.health import SERVING, HealthServicer

        super().__init__()
        self.controller = controller
        self._server = RpcServer(host, port, ssl=ssl)
        # standard grpc.health.v1 alongside the custom status RPC
        # (reference controller_servicer.cc:7-9,32-33)
        self._health_servicer = HealthServicer()
        self._health_servicer.set_status(CONTROLLER_SERVICE, SERVING)
        self._server.add_service(self._health_servicer.service())
        self._server.add_service(BytesService(CONTROLLER_SERVICE, {
            "JoinFederation": self._join,
            "LeaveFederation": self._leave,
            "MarkTaskCompleted": self._mark_completed,
            "ReplaceCommunityModel": self._replace_model,
            "GetCommunityModel": self._get_model,
            "GetStatistics": self._get_statistics,
            "GetRuntimeMetadata": self._get_runtime_metadata,
            "GetEvaluationLineage": self._get_evaluation_lineage,
            "ListLearners": self._list_learners,
            "GetHealthStatus": self._health,
            "GetMetrics": self._get_metrics,
            "DescribeFederation": self._describe,
            "DescribeRegistry": self._describe_registry,
            "GetRegisteredModel": self._get_registered_model,
            "PromoteVersion": self._promote_version,
            "RollbackVersion": self._rollback_version,
            "ShutDown": self._shutdown_rpc,
        }, role="controller"))
        self.port: Optional[int] = None

    # -- handlers (RPC threads) -------------------------------------------
    def _join(self, raw: bytes) -> bytes:
        return self.controller.join(JoinRequest.from_wire(raw)).to_wire()

    def _leave(self, raw: bytes) -> bytes:
        req = loads(raw)
        ok = self.controller.leave(req["learner_id"], req["auth_token"])
        return dumps({"ok": ok})

    def _mark_completed(self, raw: bytes) -> bytes:
        if _tprofile.collector() is None:
            # profile plane off: one attribute check, no timing, no
            # per-learner attribution series
            result = TaskResult.from_wire(raw)
        else:
            # the decode only reveals WHICH learner the payload belongs
            # to after it runs — attribute the elapsed time post hoc,
            # membership-gated under the controller lock: a late
            # completion racing leave() must not re-mint the series the
            # prune just dropped (the bounded-cardinality posture)
            t0 = time.perf_counter()
            result = TaskResult.from_wire(raw)
            self.controller.attribute_decode(result.learner_id,
                                             time.perf_counter() - t0)
        ok = self.controller.task_completed(result)
        return dumps({"ok": ok})

    def _replace_model(self, raw: bytes) -> bytes:
        self.controller.set_community_model(raw)
        return dumps({"ok": True})

    def _get_model(self, raw: bytes) -> bytes:
        return self.controller.community_model_bytes() or b""

    def _get_statistics(self, raw: bytes) -> bytes:
        return dumps(self.controller.get_statistics())

    def _get_runtime_metadata(self, raw: bytes) -> bytes:
        tail = int(loads(raw).get("tail", 0)) if raw else 0
        return dumps({"global_iteration": self.controller.global_iteration,
                      "round_metadata":
                      self.controller.get_runtime_metadata(tail)})

    def _get_evaluation_lineage(self, raw: bytes) -> bytes:
        tail = int(loads(raw).get("tail", 0)) if raw else 0
        return dumps({"community_evaluations":
                      self.controller.get_evaluation_lineage(tail)})

    def _list_learners(self, raw: bytes) -> bytes:
        return dumps({"learners": self.controller.learner_endpoints()})

    def _health(self, raw: bytes) -> bytes:
        return dumps({"status": "SERVING",
                      "learners": self.controller.active_learners()})

    def _get_metrics(self, raw: bytes) -> bytes:
        # Prometheus text exposition of the process registry (served next
        # to grpc.health.v1 like the scrape surface of a normal service;
        # plain-HTTP scrapers use telemetry.httpd instead)
        from metisfl_tpu.telemetry import render_metrics
        return render_metrics().encode("utf-8")

    def _describe(self, raw: bytes) -> bytes:
        # live status snapshot (round/phase, per-learner straggler +
        # divergence analytics, the learning-health round snapshot,
        # in-flight tasks, event-ring tail) — the status plane behind
        # python -m metisfl_tpu.status
        tail = int(loads(raw).get("event_tail", 50)) if raw else 50
        return dumps(self.controller.describe(event_tail=tail))

    def _describe_registry(self, raw: bytes) -> bytes:
        # model-registry snapshot (channel heads + retained lineage) —
        # the serving gateway's poll target and the status CLI's source
        return dumps(self.controller.describe_registry())

    def _get_registered_model(self, raw: bytes) -> bytes:
        req = loads(raw) if raw else {}
        blob = self.controller.registered_model(
            version=int(req.get("version", 0) or 0),
            channel=str(req.get("channel", "") or ""))
        return blob or b""

    def _promote_version(self, raw: bytes) -> bytes:
        req = loads(raw)
        try:
            info = self.controller.promote_version(
                int(req["version"]), force=bool(req.get("force", False)))
        except ValueError as exc:
            # a rejected gate is an answer, not a transport error
            return dumps({"ok": False, "error": str(exc)})
        return dumps({"ok": True, "version": info.to_dict()})

    def _rollback_version(self, raw: bytes) -> bytes:
        try:
            info = self.controller.rollback_version()
        except ValueError as exc:
            # registry disabled: same {ok: false} answer shape as a
            # rejected promotion, not a transport-level error
            return dumps({"ok": False, "error": str(exc)})
        if info is None:
            return dumps({"ok": False,
                          "error": "nothing to roll back to"})
        return dumps({"ok": True, "version": info.to_dict()})

    def _shutdown_rpc(self, raw: bytes) -> bytes:
        # ack first, then tear down off-thread (servicer :364-375 pattern)
        threading.Thread(target=self.stop, daemon=True).start()
        return dumps({"ok": True})

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> int:
        self.port = self._server.start()
        return self.port

    def _teardown(self) -> None:
        from metisfl_tpu.comm.health import NOT_SERVING

        self._health_servicer.set_all(NOT_SERVING)
        self.controller.shutdown()
        self._server.stop()


class ControllerClient:
    """Learner/driver → controller client (reference
    grpc_controller_client.py:11-297).

    ``standby`` is the hot-standby's ``(host, port)``: when set, a call
    that exhausts the transport's own bounded UNAVAILABLE retries
    re-resolves the controller by grpc.health.v1 probe over BOTH known
    endpoints (primary first, then standby) and re-issues once against
    whichever answers SERVING — the two-endpoint redial contract of
    docs/RESILIENCE.md "Controller hot-standby". Peers never discover
    endpoints at failover time; both are pinned at construction."""

    def __init__(self, host: str, port: int, ssl=None, comm=None,
                 standby: Optional[tuple] = None):
        self._ssl, self._comm = ssl, comm
        self._endpoints = [(host, int(port))]
        if standby and int(standby[1]) > 0:
            self._endpoints.append((standby[0], int(standby[1])))
        self._redial_lock = threading.Lock()
        self._generation = 0
        self._retries = comm.retries if comm is not None else 10
        self._retry_sleep_s = (comm.retry_sleep_s if comm is not None
                               else 1.0)
        self._active = (host, int(port))
        self._client = RpcClient(host, port, CONTROLLER_SERVICE, ssl=ssl,
                                 **_comm_kwargs(comm))

    def endpoint(self) -> tuple:
        """The (host, port) currently dialed."""
        return self._active

    def _call(self, method: str, payload: bytes, **kwargs) -> bytes:
        """One RPC with failover redial: the underlying client already
        retries UNAVAILABLE in place (comm.retries × retry_sleep_s);
        only when that budget is spent — the endpoint is DEAD, not
        blinking — do we probe for the promoted standby and re-issue.
        Without a standby endpoint this is exactly ``RpcClient.call``."""
        import grpc

        if len(self._endpoints) > 1:
            # HA mode: fail FAST on a dead endpoint. wait-for-ready would
            # park the call until the full deadline (120 s default) on a
            # SIGKILLed primary — the bounded in-place UNAVAILABLE
            # retries plus the redial probe below are the failure
            # detector, and they need the UNAVAILABLE immediately. The
            # retry budget covers the standby's promotion window (and
            # its ms-scale stop→start listener gap) with round-seconds
            # to spare. Explicit caller wait_ready always wins.
            kwargs.setdefault("wait_ready", False)
        gen = self._generation
        try:
            return self._client.call(method, payload, **kwargs)
        except (grpc.RpcError, ValueError):
            # ValueError: another thread's redial closed our channel
            # mid-call — fall through and retry on the fresh client
            if not self._redial(gen):
                raise
        return self._client.call(method, payload, **kwargs)

    def _redial(self, gen: int) -> bool:
        """Re-resolve the controller endpoint after a dead-channel call.
        Probes every known endpoint (bounded: ``comm.retries`` rounds at
        ``retry_sleep_s`` cadence — the promotion window the standby
        needs is well inside it) and swaps the transport to whichever
        answers SERVING. Serialized: concurrent failed callers re-dial
        once, the rest piggyback on the fresh channel."""
        if len(self._endpoints) < 2:
            return False
        from metisfl_tpu.comm.health import probe_health

        with self._redial_lock:
            if self._generation != gen:
                return True  # another caller already re-dialed
            for _ in range(max(1, self._retries)):
                for host, port in self._endpoints:
                    if probe_health(host, port, CONTROLLER_SERVICE,
                                    ssl=self._ssl,
                                    comm=self._comm) != "SERVING":
                        continue
                    old = self._client
                    self._client = RpcClient(host, port, CONTROLLER_SERVICE,
                                             ssl=self._ssl,
                                             **_comm_kwargs(self._comm))
                    self._active = (host, port)
                    self._generation += 1
                    try:
                        old.close()
                    except Exception:  # noqa: BLE001 - already dead
                        pass
                    logger.warning("controller re-dialed to %s:%d "
                                   "(failover)", host, port)
                    return True
                time.sleep(self._retry_sleep_s)
            return False

    def join(self, request: JoinRequest) -> JoinReply:
        # idempotent: a re-sent join lands on the rejoin path
        return JoinReply.from_wire(self._call(
            "JoinFederation", request.to_wire(), idempotent=True))

    def leave(self, learner_id: str, auth_token: str) -> bool:
        raw = self._call("LeaveFederation", dumps(
            {"learner_id": learner_id, "auth_token": auth_token}))
        return bool(loads(raw)["ok"])

    def task_completed(self, result: TaskResult) -> bool:
        raw = self._call("MarkTaskCompleted", result.to_segments())
        return bool(loads(raw)["ok"])

    def replace_community_model(self, blob: bytes) -> bool:
        return bool(loads(self._call("ReplaceCommunityModel", blob))["ok"])

    def get_community_model(self) -> bytes:
        return self._call("GetCommunityModel", b"", idempotent=True)

    def get_statistics(self) -> dict:
        return loads(self._call("GetStatistics", b"",
                                       idempotent=True))

    def get_runtime_metadata(self, tail: int = 0,
                             timeout: Optional[float] = None,
                             wait_ready: bool = True) -> dict:
        """{'global_iteration', 'round_metadata': last ``tail`` rounds}
        (0 = full lineage). ``wait_ready=False`` + a short timeout makes
        a poll against a dead controller fail fast instead of parking in
        the channel's wait-for-ready — the driver's supervision loop
        needs the failure signal to trigger the failover restart."""
        raw = self._call("GetRuntimeMetadata", dumps({"tail": tail}),
                                timeout=timeout, wait_ready=wait_ready,
                                idempotent=True)
        return loads(raw)

    def get_evaluation_lineage(self, tail: int = 0) -> list:
        """Last ``tail`` evaluation entries (0 = full lineage)."""
        raw = self._call("GetEvaluationLineage", dumps({"tail": tail}),
                                idempotent=True)
        return loads(raw)["community_evaluations"]

    def list_learners(self, timeout: Optional[float] = None,
                      wait_ready: bool = True) -> list:
        """Registered learner endpoints [{learner_id, hostname, port}] — the
        ports learners actually bound (JoinRequest.port), for shutdown and
        monitoring (replaces any port-arithmetic assumptions driver-side)."""
        return loads(self._call("ListLearners", b"", timeout=timeout,
                                       wait_ready=wait_ready,
                                       idempotent=True))["learners"]

    def health(self, timeout: float = 5.0) -> dict:
        return loads(self._call("GetHealthStatus", b"",
                                       timeout=timeout, idempotent=True))

    def get_metrics(self, timeout: float = 5.0) -> str:
        """The controller's Prometheus text exposition (GetMetrics RPC)."""
        return self._call("GetMetrics", b"", timeout=timeout,
                                 idempotent=True).decode("utf-8")

    def describe_federation(self, event_tail: int = 50,
                            timeout: Optional[float] = None,
                            wait_ready: bool = True) -> dict:
        """Live status snapshot (Controller.describe): round/phase,
        per-learner liveness + straggler scores, in-flight tasks, store
        occupancy, event-ring tail. Fail-fast polling works like
        get_runtime_metadata: short ``timeout`` + ``wait_ready=False``."""
        raw = self._call("DescribeFederation",
                                dumps({"event_tail": int(event_tail)}),
                                timeout=timeout, wait_ready=wait_ready,
                                idempotent=True)
        return loads(raw)

    def describe_registry(self, timeout: Optional[float] = None,
                          wait_ready: bool = True) -> dict:
        """Model-registry snapshot (channel heads + retained version
        lineage); ``{"enabled": False}`` when the registry is off. The
        serving gateway polls this fail-fast (short timeout, no
        wait-for-ready) like the driver's supervision polls."""
        raw = self._call("DescribeRegistry", b"", timeout=timeout,
                                wait_ready=wait_ready, idempotent=True)
        return loads(raw)

    def get_registered_model(self, version: int = 0, channel: str = "",
                             timeout: Optional[float] = None) -> bytes:
        """A registered version's community blob, by version id or channel
        name (b'' when absent)."""
        return self._call(
            "GetRegisteredModel",
            dumps({"version": int(version), "channel": channel}),
            timeout=timeout, idempotent=True)

    def promote_version(self, version: int, force: bool = False,
                        timeout: Optional[float] = None) -> dict:
        """Operator promotion: ``{"ok": bool, ...}`` — a failing gate
        comes back as ``ok=False`` with the reasons, not an exception."""
        return loads(self._call(
            "PromoteVersion", dumps({"version": int(version),
                                     "force": bool(force)}),
            timeout=timeout))

    def rollback_version(self, timeout: Optional[float] = None) -> dict:
        return loads(self._call("RollbackVersion", dumps({}),
                                       timeout=timeout))

    def list_methods(self, timeout: float = 5.0) -> dict:
        """The service's RPC surface (ListMethods reflection): method
        names + transport capability flags, JSON-encoded so non-codec
        tooling can probe it too."""
        import json as _json
        raw = self._call("ListMethods", b"", timeout=timeout,
                                idempotent=True)
        return _json.loads(raw.decode("utf-8"))

    def shutdown_controller(self) -> bool:
        return bool(loads(self._call("ShutDown", b""))["ok"])

    def close(self) -> None:
        self._client.close()
