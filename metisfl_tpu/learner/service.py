"""Learner gRPC service.

RPC surface of the reference's ``LearnerServicer``
(reference metisfl/learner/learner_servicer.py:14-139, learner.proto:9-24):
RunTask (non-blocking), EvaluateModel (blocking), health, shutdown.
"""

from __future__ import annotations

import logging
import threading
from typing import Optional

from metisfl_tpu.comm.codec import dumps, loads
from metisfl_tpu.comm.messages import EvalTask, InferTask, TrainTask
from metisfl_tpu.comm.rpc import BytesService, RpcServer, StopOnce
from metisfl_tpu.controller.service import LEARNER_SERVICE, ControllerClient
from metisfl_tpu.learner.learner import Learner

logger = logging.getLogger("metisfl_tpu.learner.service")


class LearnerServer(StopOnce):
    def __init__(self, learner: Learner, host: str = "0.0.0.0", port: int = 0,
                 ssl=None):
        from metisfl_tpu.comm.health import SERVING, HealthServicer

        super().__init__()
        self.learner = learner
        self._server = RpcServer(host, port, ssl=ssl)
        self._health_servicer = HealthServicer()
        self._health_servicer.set_status(LEARNER_SERVICE, SERVING)
        self._server.add_service(self._health_servicer.service())
        self._server.add_service(BytesService(LEARNER_SERVICE, {
            "RunTask": self._run_task,
            "EvaluateModel": self._evaluate,
            "RunInference": self._infer,
            "RecoverMasks": self._recover_masks,
            "GetHealthStatus": self._health,
            "GetMetrics": self._get_metrics,
            "ShutDown": self._shutdown_rpc,
        }, role="learner"))
        self._tasks_received = 0
        self.port: Optional[int] = None

    def _run_task(self, raw: bytes) -> bytes:
        self._tasks_received += 1
        self.learner.run_task(TrainTask.from_wire(raw))
        return dumps({"ok": True})

    def _evaluate(self, raw: bytes) -> bytes:
        return self.learner.evaluate(EvalTask.from_wire(raw)).to_wire()

    def _infer(self, raw: bytes) -> bytes:
        return self.learner.infer(InferTask.from_wire(raw)).to_wire()

    def _recover_masks(self, raw: bytes) -> bytes:
        req = loads(raw)
        corrections = self.learner.recover_masks(
            req["round_id"], req["surviving"], req["dropped"],
            req["lengths"])
        return dumps({"corrections": corrections})

    def _health(self, raw: bytes) -> bytes:
        return dumps({"status": "SERVING", "tasks_received": self._tasks_received})

    def _get_metrics(self, raw: bytes) -> bytes:
        # same scrape surface as the controller: Prometheus exposition of
        # this learner process's registry
        from metisfl_tpu.telemetry import render_metrics
        return render_metrics().encode("utf-8")

    def _shutdown_rpc(self, raw: bytes) -> bytes:
        logger.info("learner ShutDown RPC received")
        threading.Thread(target=self.stop, daemon=True).start()
        return dumps({"ok": True})

    def start(self) -> int:
        self.port = self._server.start()
        self.learner.port = self.port
        return self.port

    def _teardown(self, leave: bool = True) -> None:
        from metisfl_tpu.comm.health import NOT_SERVING

        self._health_servicer.set_all(NOT_SERVING)
        logger.info("learner server stopping (leave=%s)", leave)
        try:
            if leave:
                self.learner.leave_federation()
        except Exception:  # controller may already be gone
            logger.warning("leave_federation during shutdown failed")
        self.learner.shutdown()
        self._server.stop()
