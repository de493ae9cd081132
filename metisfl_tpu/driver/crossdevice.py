"""Seeded in-process cross-device churn harness.

The cross-silo tests drive a handful of real JAX learners; the
cross-device regime (ROADMAP "massive cross-device simulation") is the
opposite shape — thousands of unreliable *virtual clients*, per-round
sampling, and heavy per-round dropout — and what it stresses is the
controller's scheduling planes (quorum barriers, deadlines, churn
admission, dispatch retry), not the training math. So the harness keeps
the controller 100% real (registry, scheduler, store, aggregation,
telemetry) and replaces each learner with a virtual client: a seeded
softmax-regression shard trained with plain numpy in a small worker
pool. A 1024-client federation under 30% per-round dropout runs in
seconds with bounded RSS, which is what lets churn tolerance sit in
tier-1 CI (``scripts/chaos_smoke.sh``).

Fault model per dispatched task (all draws from the scenario seed):

- **dropout** — with probability ``dropout`` the client silently never
  reports (the cross-device baseline fault; quorum or the deadline
  releases the round without it);
- **flap** — ``flappers`` clients crash-flap: on their first task of
  every round they are sampled into, they ignore the task and
  immediately re-attach with their previous identity (the crash-rejoin
  path, which feeds the churn tracker's ``flap_rejoin`` events and
  re-dispatches them; the re-dispatched task trains normally);
- **partition** — ``partitioned`` clients are unreachable (dispatch
  raises) for rounds ``[1, 1 + partition_rounds)``, exercising the
  dispatch-failure ladder: liveness counting, churn scoring, and
  retry-to-replacement.

Determinism: client shards, fault draws, and cohort-size arithmetic are
all seed-derived, so a fixed scenario replays the same fault schedule;
uplink *arrival order* inside a round follows thread timing, which under
the ``participants`` scaler moves the aggregate only by fp
reassociation. Convergence assertions therefore compare accuracies
within a tolerance, not bit-exact models.

CLI (what ``scripts/chaos_smoke.sh`` gates on)::

    python -m metisfl_tpu.driver.crossdevice --clients 512 --rounds 5
    # runs the churn scenario AND the no-churn same-seed control,
    # prints one JSON line, exits non-zero on a failed round or an
    # accuracy gap beyond --tolerance
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import random
import resource
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from metisfl_tpu.comm.messages import JoinRequest, TaskResult
from metisfl_tpu.config import (
    AggregationConfig,
    EvalConfig,
    FederationConfig,
    HealthConfig,
    ProfileConfig,
    SchedulingConfig,
    TelemetryConfig,
)
from metisfl_tpu.controller.core import Controller, LearnerRecord
from metisfl_tpu.tensor.pytree import ModelBlob, pack_model

logger = logging.getLogger("metisfl_tpu.crossdevice")


@dataclass
class ChurnScenario:
    """One reproducible cross-device run. Defaults are the fast CI shape
    (tests/test_churn.py pins the 1024-client acceptance scenario)."""

    seed: int = 7
    clients: int = 1024
    rounds: int = 5
    # quorum barrier: rounds release at `quorum` reporters out of an
    # over-provisioned dispatch of ceil(quorum * (1 + overprovision))
    quorum: int = 12
    overprovision: float = 1.0
    # per-task silent-dropout probability, plus the named fault clients
    dropout: float = 0.3
    flappers: int = 1
    partitioned: int = 1
    partition_rounds: int = 2
    # the virtual task: seeded softmax regression on per-client shards
    dim: int = 8
    classes: int = 4
    samples_per_client: int = 32
    local_steps: int = 8
    lr: float = 0.25
    # controller knobs under test
    round_deadline_secs: float = 5.0
    quarantine_score: float = 0.55
    quarantine_s: float = 2.0
    dispatch_retries: int = 4
    # >0: run protocol=asynchronous_buffered with this buffer instead of
    # the quorum barrier (FedBuff mode; quorum is then ignored)
    buffer_size: int = 0
    # telemetry at scale (docs/OBSERVABILITY.md): >0 arms
    # telemetry.cardinality_budget so the per-learner metric families
    # collapse to sketches past this many series — the 10k+-client
    # acceptance scenario runs under a budget of 256
    cardinality_budget: int = 0
    # arm the SLO alert smoke rule (a dispatch_retries_total rate rule
    # that provably fires under the partition fault and stays silent in
    # the no-churn control; scripts/chaos_smoke.sh gates on it)
    alert_smoke: bool = False
    alert_window_s: float = 3.0
    # alert-smoke determinism: round 1's virtual clients hold their
    # uplink this long so the round provably outlasts the (shortened)
    # dispatch-retry backoff — the retry that feeds the rate rule must
    # land while its round is still open, not race a 50 ms quorum
    # release. Applied in churn AND control (same wall-clock shape).
    alert_round1_delay_s: float = 0.15
    # distributed slice aggregators (aggregation/slice.py): >0 boots this
    # many REAL slice aggregator subprocesses over gRPC and runs the
    # federation with aggregation.tree.distributed — the slice-kill
    # chaos gate (scripts/chaos_smoke.sh) runs 3 of them
    slices: int = 0
    # SIGKILL one aggregator mid-round (while round `slice_kill_round+1`
    # is waiting on uplinks): the round must complete via re-homing and
    # the community model must match the same-seed no-kill control
    # bit-for-bit (sorted-id fold order makes the bits a pure function
    # of the contributor set; aggregation/distributed.py)
    slice_kill: bool = False
    slice_kill_round: int = 1
    # simulation plumbing
    workers: int = 8
    timeout_s: float = 120.0


def _local_train(weights: Dict[str, np.ndarray], x: np.ndarray,
                 y: np.ndarray, steps: int, lr: float) -> Dict[str, np.ndarray]:
    """Full-batch softmax-regression SGD — deterministic, sub-millisecond
    at harness scale, and genuinely converges when federated."""
    w = np.asarray(weights["w"], np.float32).copy()
    b = np.asarray(weights["b"], np.float32).copy()
    n = len(x)
    rows = np.arange(n)
    for _ in range(max(1, steps)):
        logits = x @ w + b
        logits -= logits.max(axis=1, keepdims=True)
        p = np.exp(logits)
        p /= p.sum(axis=1, keepdims=True)
        p[rows, y] -= 1.0
        p /= n
        w -= lr * (x.T @ p)
        b -= lr * p.sum(axis=0)
    return {"w": w, "b": b}


class _VirtualClientProxy:
    """Controller → virtual-client transport: applies the scenario's
    fault model, then trains on the harness worker pool."""

    def __init__(self, harness: "CrossDeviceHarness", record: LearnerRecord):
        self._h = harness
        self._learner_id = record.learner_id

    def run_task(self, task) -> None:
        self._h._on_dispatch(self._learner_id, task)

    def evaluate(self, task, callback) -> None:
        pass  # community eval is host-side in the harness (eval cfg off)

    def shutdown(self) -> None:
        pass


class CrossDeviceHarness:
    """See module docstring. Lifecycle: construct → :meth:`run` → result
    dict (the harness owns controller startup and shutdown)."""

    def __init__(self, scenario: ChurnScenario):
        self.scenario = scenario
        s = scenario
        # alert-smoke mode needs the retry to land inside its round (see
        # alert_round1_delay_s); the default 0.5 s backoff would lose the
        # race against a fast quorum release every time
        backoff = 0.05 if s.alert_smoke else 0.5
        if s.buffer_size > 0:
            protocol, sched = "asynchronous_buffered", SchedulingConfig(
                buffer_size=s.buffer_size,
                quarantine_score=s.quarantine_score,
                quarantine_s=s.quarantine_s,
                dispatch_retries=s.dispatch_retries,
                retry_backoff_s=backoff)
        else:
            protocol, sched = "synchronous", SchedulingConfig(
                quorum=s.quorum, overprovision=s.overprovision,
                quarantine_score=s.quarantine_score,
                quarantine_s=s.quarantine_s,
                dispatch_retries=s.dispatch_retries,
                retry_backoff_s=backoff)
        alert_rules = []
        if s.alert_smoke:
            # fires only under churn: the partitioned client's dispatch
            # raises, the retry plane replaces it, and the rate of
            # dispatch_retries_total lifts off 0 — the no-churn control
            # run never increments the counter, so the rule stays silent
            # there (scripts/chaos_smoke.sh asserts both halves)
            alert_rules = [{
                "name": "dispatch_retry_burst",
                "metric": "dispatch_retries_total",
                "kind": "rate",
                "window_s": s.alert_window_s,
                "threshold": 0.01,
                "for_s": 0.0,
                "severity": "warning",
            }]
        self._slice_procs: List[Any] = []
        self._slice_tmp = ""
        self._slice_killed = False
        tree_cfg = None
        if s.slices > 0:
            tree_cfg = self._boot_slices()
        agg_kwargs = {"tree": tree_cfg} if tree_cfg is not None else {}
        self.config = FederationConfig(
            protocol=protocol,
            scheduling=sched,
            round_deadline_secs=s.round_deadline_secs,
            aggregation=AggregationConfig(
                rule="fedavg", scaler="participants",
                staleness_decay=0.5 if s.buffer_size > 0 else 0.0,
                **agg_kwargs),
            eval=EvalConfig(every_n_rounds=0),
            # the harness measures scheduling, not observability: the
            # health/profile planes stay off so a 1024-client round costs
            # controller bookkeeping only (the cardinality budget and the
            # alert smoke rule are exactly the planes under test here)
            telemetry=TelemetryConfig(
                health=HealthConfig(enabled=False),
                profile=ProfileConfig(enabled=False),
                cardinality_budget=s.cardinality_budget,
                alerts=alert_rules,
                alerts_interval_s=0.25),
        )
        self.controller = Controller(self.config, self._make_proxy)
        self._pool = ThreadPoolExecutor(
            max_workers=max(1, s.workers),
            thread_name_prefix="virtual-client")
        self._lock = threading.Lock()
        # learner_id -> (client index, live auth token)
        self._clients: Dict[str, int] = {}
        self._tokens: Dict[str, str] = {}
        # fault roles are assigned to the FIRST round-1 dispatched
        # clients (per-round sampling of a huge population would almost
        # never pick a pre-designated index — the faults must provably
        # fire, not probably)
        self._flap_idx: set = set()
        self._part_idx: set = set()
        self._last_flap_round: Dict[int, int] = {}
        self._data_cache: Dict[int, Any] = {}
        self._truth = np.random.default_rng(s.seed).standard_normal(
            (s.dim, s.classes)).astype(np.float32)
        self.faults = {"dropped": 0, "flapped": 0, "partitioned": 0}

    # -- distributed slice aggregators (aggregation/slice.py) -------------

    def _boot_slices(self):
        """Boot ``scenario.slices`` REAL aggregator subprocesses (their
        own interpreters, real gRPC, SIGKILL-able) and return the
        ``aggregation.tree`` config pointing the controller at them."""
        import os
        import socket
        import subprocess
        import sys as _sys
        import tempfile

        from metisfl_tpu.aggregation.slice import SLICE_SERVICE
        from metisfl_tpu.comm.health import probe_health
        from metisfl_tpu.config import TreeAggregationConfig

        s = self.scenario
        self._slice_tmp = tempfile.mkdtemp(prefix="metisfl_slices_")
        specs = []
        try:
            for i in range(s.slices):
                with socket.socket() as sock:
                    sock.bind(("127.0.0.1", 0))
                    port = sock.getsockname()[1]
                spool = os.path.join(self._slice_tmp, f"slice_{i}")
                specs.append({"name": f"slice_{i}", "host": "127.0.0.1",
                              "port": port, "spool_dir": spool})
                self._slice_procs.append(subprocess.Popen(
                    [_sys.executable, "-m",
                     "metisfl_tpu.aggregation.slice",
                     "--host", "127.0.0.1", "--port", str(port),
                     "--spool-dir", spool, "--name", f"slice_{i}"],
                    env={**os.environ, "JAX_PLATFORMS": "cpu"},
                    stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
            deadline = time.time() + 60.0
            pending = list(specs)
            while pending and time.time() < deadline:
                pending = [spec for spec in pending
                           if probe_health(spec["host"], spec["port"],
                                           SLICE_SERVICE) != "SERVING"]
                if pending:
                    time.sleep(0.2)
            if pending:
                raise RuntimeError(f"slice aggregators never came up: "
                                   f"{[p['name'] for p in pending]}")
        except BaseException:
            # a failed boot must not orphan the processes that DID start
            # (run()'s cleanup only covers a constructed harness)
            self._stop_slices()
            raise
        return TreeAggregationConfig(
            enabled=True, branch=s.slices, distributed=True, slices=specs,
            rehome_retries=2, rehome_backoff_s=0.05)

    def _maybe_kill_slice(self) -> None:
        """The chaos trigger: SIGKILL aggregator 0 while the target round
        is mid-flight (uplinks in the air, barrier open)."""
        s = self.scenario
        if (not s.slice_kill or self._slice_killed or not self._slice_procs
                or self.controller.global_iteration < s.slice_kill_round
                or self.controller._phase != "wait_uplinks"):
            return
        self._slice_killed = True
        self._slice_procs[0].kill()
        logger.warning("chaos: SIGKILLed slice aggregator 0 mid-round %d",
                       s.slice_kill_round)

    def _stop_slices(self) -> None:
        for proc in self._slice_procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in self._slice_procs:
            try:
                proc.wait(timeout=5.0)
            except Exception:  # noqa: BLE001 - unkillable: leave to reaper
                proc.kill()

    # -- data ------------------------------------------------------------

    def _client_data(self, idx: int):
        with self._lock:
            cached = self._data_cache.get(idx)
        if cached is not None:
            return cached
        s = self.scenario
        rng = np.random.default_rng((s.seed, idx))
        x = rng.standard_normal((s.samples_per_client, s.dim)).astype(
            np.float32)
        noise = 0.1 * rng.standard_normal((s.samples_per_client, s.classes))
        y = np.argmax(x @ self._truth + noise, axis=-1).astype(np.int32)
        with self._lock:
            self._data_cache[idx] = (x, y)
        return x, y

    def _test_data(self):
        s = self.scenario
        rng = np.random.default_rng((s.seed, 99991))
        x = rng.standard_normal((1024, s.dim)).astype(np.float32)
        y = np.argmax(x @ self._truth, axis=-1).astype(np.int32)
        return x, y

    # -- controller plumbing ---------------------------------------------

    def _make_proxy(self, record: LearnerRecord):
        return _VirtualClientProxy(self, record)

    def _join_all(self) -> None:
        for idx in range(self.scenario.clients):
            reply = self.controller.join(JoinRequest(
                hostname="vclient", port=20000 + idx,
                num_train_examples=self.scenario.samples_per_client))
            with self._lock:
                self._clients[reply.learner_id] = idx
                self._tokens[reply.learner_id] = reply.auth_token

    def _on_dispatch(self, learner_id: str, task) -> None:
        """The scenario's fault model, then a worker-pool training job."""
        s = self.scenario
        with self._lock:
            idx = self._clients.get(learner_id)
            token = self._tokens.get(learner_id, "")
        if idx is None:
            return
        if task.round_id == 1:
            with self._lock:
                if (len(self._part_idx) < s.partitioned
                        and idx not in self._flap_idx):
                    self._part_idx.add(idx)
                elif (len(self._flap_idx) < s.flappers
                        and idx not in self._part_idx):
                    self._flap_idx.add(idx)
        if idx in self._part_idx and (
                1 <= task.round_id < 1 + s.partition_rounds):
            # network partition: the dispatch itself fails, feeding the
            # dispatch-failure ladder (liveness, churn score, retry)
            self.faults["partitioned"] += 1
            raise RuntimeError(f"chaos: client {idx} partitioned")
        if idx in self._flap_idx:
            if self._last_flap_round.get(idx) != task.round_id:
                # crash-flap: ignore the task, re-attach as ourselves —
                # the controller notes flap_rejoin and re-dispatches; the
                # re-dispatched task (same round) trains normally below
                self._last_flap_round[idx] = task.round_id
                self.faults["flapped"] += 1
                self._pool.submit(self._rejoin, learner_id, idx, token)
                return
        if idx not in self._flap_idx and idx not in self._part_idx:
            # int-composed seed (tuple seeding is deprecated and
            # hash-randomized): deterministic per (seed, round, client)
            draw = random.Random(
                (s.seed << 40) ^ (task.round_id << 24) ^ idx).random()
            if draw < s.dropout:
                self.faults["dropped"] += 1
                return  # silent per-round dropout: never reports
        self._pool.submit(self._train_and_complete, learner_id, idx,
                          token, task)

    def _rejoin(self, learner_id: str, idx: int, token: str) -> None:
        try:
            reply = self.controller.join(JoinRequest(
                hostname="vclient", port=20000 + idx,
                num_train_examples=self.scenario.samples_per_client,
                previous_id=learner_id, auth_token=token))
            with self._lock:
                self._clients[reply.learner_id] = idx
                self._tokens[reply.learner_id] = reply.auth_token
        except Exception:  # noqa: BLE001 - harness fault path, never fatal
            logger.exception("virtual client %d rejoin failed", idx)

    def _train_and_complete(self, learner_id: str, idx: int, token: str,
                            task) -> None:
        try:
            blob = ModelBlob.from_bytes(task.model)
            weights = {name: np.asarray(arr) for name, arr in blob.tensors}
            x, y = self._client_data(idx)
            s = self.scenario
            trained = _local_train(weights, x, y, s.local_steps, s.lr)
            if s.alert_smoke and task.round_id == 1:
                # hold round 1 open past the retry backoff (see
                # alert_round1_delay_s) — identical in churn + control
                time.sleep(s.alert_round1_delay_s)
            if s.slices > 0 and task.round_id == s.slice_kill_round:
                # slice-kill determinism: hold the target round's barrier
                # open long enough that the SIGKILL provably lands
                # MID-round (uplinks still in the air). Applied in the
                # kill AND control runs — identical wall-clock shape,
                # and wall timing cannot move the bits (sorted-id folds)
                time.sleep(0.02)
            self.controller.task_completed(TaskResult(
                task_id=task.task_id, learner_id=learner_id,
                auth_token=token, round_id=task.round_id,
                model=pack_model(trained),
                num_train_examples=len(x),
                completed_steps=s.local_steps,
                completed_batches=s.local_steps,
                processing_ms_per_step=1.0))
        except Exception:  # noqa: BLE001 - harness fault path, never fatal
            logger.exception("virtual client %d train failed", idx)

    # -- run -------------------------------------------------------------

    def accuracy(self) -> float:
        """Community-model accuracy on the held-out seeded test set."""
        raw = self.controller.community_model_bytes()
        if raw is None:
            return 0.0
        weights = {name: np.asarray(arr)
                   for name, arr in ModelBlob.from_bytes(raw).tensors}
        x, y = self._test_data()
        pred = np.argmax(x @ weights["w"] + weights["b"], axis=-1)
        return float(np.mean(pred == y))

    def _settle_alerts(self) -> Optional[Dict[str, Any]]:
        """Drain the alert lifecycle before shutdown: with the faults
        over, the rate windows slide empty and every firing alert must
        resolve — the end-to-end firing→resolved proof the chaos smoke
        gates on. None when the alert smoke is not armed."""
        engine = self.controller._alerts
        if engine is None:
            return None
        deadline = time.time() + 3.0 * self.scenario.alert_window_s + 2.0
        while engine.active() and time.time() < deadline:
            engine.poll()
            time.sleep(0.1)
        return {
            "fired": engine.fired_total,
            "resolved": engine.resolved_total,
            "active_at_end": [a["name"] for a in engine.active()],
        }

    def _telemetry_stats(self) -> Optional[Dict[str, Any]]:
        """Exposition-side evidence for the cardinality budget: series
        and bytes in one scrape, plus which families collapsed. None
        when the budget is not armed."""
        if self.scenario.cardinality_budget <= 0:
            return None
        from metisfl_tpu import telemetry as _tel

        text = _tel.render_metrics()
        collapsed = sorted(
            f.name for f in _tel.registry().budget_families()
            if f.collapsed())
        return {
            "budget": self.scenario.cardinality_budget,
            "exposition_bytes": len(text),
            "exposition_series": sum(
                1 for line in text.splitlines()
                if line and not line.startswith("#")),
            "collapsed_families": collapsed,
        }

    def run(self) -> Dict[str, Any]:
        s = self.scenario
        # the controller samples cohorts (and retry replacements) from
        # the process-global `random` — seed it so the dispatch schedule
        # replays for a fixed scenario seed
        random.seed(s.seed)
        rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        t0 = time.time()
        # join BEFORE seeding: an unseeded controller skips the per-join
        # initial dispatch, so round 1 is a SAMPLED cohort, not an
        # all-clients broadcast (the cross-device shape under test).
        # The expected no-model warnings are silenced for the bulk join.
        ctrl_logger = logging.getLogger("metisfl_tpu.controller")
        level = ctrl_logger.level
        ctrl_logger.setLevel(logging.ERROR)
        try:
            self._join_all()
            # drain the per-join initial-dispatch no-ops (single-worker
            # executor) BEFORE seeding: a queued initial dispatch running
            # after the seed would broadcast round 0 outside the sample
            self.controller._pool.submit(lambda: None).result(timeout=60)
        finally:
            ctrl_logger.setLevel(level)
        joined_s = time.time() - t0
        rng = np.random.default_rng((s.seed, 77777))
        seed_model = {
            "w": (0.01 * rng.standard_normal((s.dim, s.classes))).astype(
                np.float32),
            "b": np.zeros((s.classes,), np.float32)}
        self.controller.set_community_model(pack_model(seed_model))
        round_walls: List[float] = []
        halted = False
        try:
            assert self.controller.resume_round(), "nothing to dispatch"
            deadline = time.time() + s.timeout_s
            for target in range(1, s.rounds + 1):
                r0 = time.time()
                while self.controller.global_iteration < target:
                    if time.time() > deadline:
                        break
                    # light-weight phase probe (describe() builds a
                    # 1024-learner snapshot — far too heavy for a 10 ms
                    # poll; a str attribute read is atomic)
                    if self.controller._phase == "halted":
                        halted = True
                        break
                    self._maybe_kill_slice()
                    time.sleep(0.01)
                if halted or self.controller.global_iteration < target:
                    break
                round_walls.append(round(time.time() - r0, 3))
        finally:
            completed = self.controller.global_iteration
            metas = self.controller.get_runtime_metadata()
            acc = self.accuracy()
            alerts_out = self._settle_alerts()
            telemetry_out = self._telemetry_stats()
            slices_out = None
            if self.scenario.slices > 0:
                import hashlib
                raw = self.controller.community_model_bytes() or b""
                tier = self.controller._slices
                slices_out = {
                    "slices": self.scenario.slices,
                    "killed": self._slice_killed,
                    "rehomed_total": tier.rehomed_total if tier else 0,
                    "describe": tier.describe() if tier else {},
                    "model_sha256": hashlib.sha256(raw).hexdigest(),
                }
            self.controller.shutdown()
            self._stop_slices()
            self._pool.shutdown(wait=True)
        rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        reporters = [len(m.get("train_received_at", {})) for m in metas]
        return {
            **({"alerts": alerts_out} if alerts_out is not None else {}),
            **({"telemetry": telemetry_out}
               if telemetry_out is not None else {}),
            **({"slices": slices_out} if slices_out is not None else {}),
            "clients": s.clients,
            "protocol": self.config.protocol,
            "quorum": 0 if s.buffer_size else s.quorum,
            "buffer_size": s.buffer_size,
            "dropout": s.dropout,
            "seed": s.seed,
            "rounds_target": s.rounds,
            "rounds_completed": completed,
            "halted": halted,
            "ok": completed >= s.rounds and not halted,
            "accuracy": round(acc, 4),
            "join_s": round(joined_s, 3),
            "wall_s": round(time.time() - t0, 3),
            "round_walls_s": round_walls,
            "reporters_per_round": reporters[:s.rounds],
            "faults": dict(self.faults),
            "errors": [e for m in metas for e in m.get("errors", [])],
            "peak_rss_kb": rss1,
            "rss_growth_kb": rss1 - rss0,
        }


def run_scenario(scenario: ChurnScenario) -> Dict[str, Any]:
    return CrossDeviceHarness(scenario).run()


def run_slice_smoke(clients: int = 24, rounds: int = 3, slices: int = 3,
                    seed: int = 7, timeout_s: float = 120.0
                    ) -> Dict[str, Any]:
    """The slice-kill chaos gate (ISSUE 12; scripts/chaos_smoke.sh):
    ``slices`` real aggregator subprocesses over gRPC, full-barrier
    rounds with zero churn faults, one aggregator SIGKILLed mid-round —
    versus the same-seed undisturbed control. Passes iff the kill run
    completes every round without operator action, ``slice_rehomed``
    fired exactly as designed (>=1 in the kill run, 0 in the control),
    and the two community models are BIT-IDENTICAL (the distributed
    tier's sorted-id fold order makes the bits a pure function of the
    contributor set, which the spool recovery preserves)."""
    base = ChurnScenario(
        seed=seed, clients=clients, rounds=rounds, slices=slices,
        quorum=0, overprovision=0.0, dropout=0.0, flappers=0,
        partitioned=0, dispatch_retries=0, quarantine_score=0.0,
        round_deadline_secs=30.0, timeout_s=timeout_s)
    kill = run_scenario(dataclasses.replace(base, slice_kill=True))
    control = run_scenario(base)
    ks, cs = kill.get("slices") or {}, control.get("slices") or {}
    bit_identical = (bool(ks.get("model_sha256"))
                     and ks.get("model_sha256") == cs.get("model_sha256"))
    ok = (kill["ok"] and control["ok"]
          and bool(ks.get("killed"))
          and int(ks.get("rehomed_total", 0)) >= 1
          and int(cs.get("rehomed_total", 0)) == 0
          and bit_identical)
    return {"kill": kill, "control": control,
            "bit_identical": bit_identical, "ok": ok}


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        "metisfl_tpu.driver.crossdevice",
        description="seeded cross-device churn harness (chaos smoke gate)")
    parser.add_argument("--clients", type=int, default=1024)
    parser.add_argument("--rounds", type=int, default=5)
    parser.add_argument("--quorum", type=int, default=12)
    parser.add_argument("--overprovision", type=float, default=1.0)
    parser.add_argument("--dropout", type=float, default=0.3)
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--buffer", type=int, default=0,
                        help=">0: FedBuff asynchronous_buffered mode with "
                             "this buffer size")
    parser.add_argument("--deadline", type=float, default=5.0)
    parser.add_argument("--timeout", type=float, default=120.0)
    parser.add_argument("--tolerance", type=float, default=0.2,
                        help="max |accuracy(churn) - accuracy(no churn)|")
    parser.add_argument("--skip-control", action="store_true",
                        help="skip the no-churn same-seed control run")
    parser.add_argument("--budget", type=int, default=0,
                        help=">0: arm telemetry.cardinality_budget — the "
                             "per-learner metric families collapse to "
                             "sketches past this many series")
    parser.add_argument("--alert-smoke", action="store_true",
                        help="arm the dispatch-retry rate alert and FAIL "
                             "unless it fires and resolves under churn "
                             "while staying silent in the control run")
    parser.add_argument("--slice-smoke", action="store_true",
                        help="run the slice-kill chaos gate instead: real "
                             "slice aggregator subprocesses, one SIGKILLed "
                             "mid-round; FAIL unless the round completes "
                             "via re-homing and the community model is "
                             "bit-identical to the no-kill control")
    parser.add_argument("--slices", type=int, default=3,
                        help="aggregator subprocess count for --slice-smoke")
    parser.add_argument("--controller-smoke", action="store_true",
                        help="run the controller-kill chaos gate instead: "
                             "real-gRPC federation with a warm --standby, "
                             "controller SIGKILLed mid-round with uplinks "
                             "in the air; FAIL unless the standby promotes "
                             "itself, every round completes, and the "
                             "community model is bit-identical to the "
                             "same-seed undisturbed control run")
    parser.add_argument("--secure-smoke", action="store_true",
                        help="run the secure-aggregation chaos gate "
                             "instead: real-gRPC federation with "
                             "distributed slices under scheme=masking, "
                             "one learner SIGKILLed with its masked "
                             "uplink in the air; FAIL unless every round "
                             "completes via dropout settlement, the "
                             "community matches the same-seed plain "
                             "control within the fixed-point tolerance, "
                             "and the control emits zero secure events")
    args = parser.parse_args(argv)

    if args.secure_smoke:
        from metisfl_tpu.driver.secure_smoke import run_secure_smoke
        out = run_secure_smoke(rounds=min(args.rounds, 2), seed=args.seed,
                               timeout_s=args.timeout)
        print(json.dumps(out))
        return 0 if out["ok"] else 1

    if args.controller_smoke:
        from metisfl_tpu.driver.ha_smoke import run_ha_smoke
        out = run_ha_smoke(rounds=min(args.rounds, 3), seed=args.seed,
                           timeout_s=args.timeout)
        print(json.dumps(out))
        return 0 if out["ok"] else 1

    if args.slice_smoke:
        out = run_slice_smoke(clients=min(args.clients, 24),
                              rounds=min(args.rounds, 3),
                              slices=args.slices, seed=args.seed,
                              timeout_s=args.timeout)
        print(json.dumps(out))
        return 0 if out["ok"] else 1

    scenario = ChurnScenario(
        seed=args.seed, clients=args.clients, rounds=args.rounds,
        quorum=args.quorum, overprovision=args.overprovision,
        dropout=args.dropout, buffer_size=args.buffer,
        round_deadline_secs=args.deadline, timeout_s=args.timeout,
        cardinality_budget=args.budget, alert_smoke=args.alert_smoke)
    churn = run_scenario(scenario)
    out: Dict[str, Any] = {"churn": churn}
    ok = churn["ok"]
    if args.alert_smoke:
        # the firing→resolved lifecycle, end to end: the partition fault
        # must have tripped the rate rule, and the drained run must have
        # resolved it (an alert that cannot resolve pages forever)
        alerts = churn.get("alerts") or {}
        alert_ok = (alerts.get("fired", 0) >= 1
                    and alerts.get("resolved", 0) >= 1
                    and not alerts.get("active_at_end"))
        out["alert_lifecycle_ok"] = alert_ok
        ok = ok and alert_ok
    if not args.skip_control:
        control = run_scenario(dataclasses.replace(
            scenario, dropout=0.0, flappers=0, partitioned=0))
        out["control"] = control
        gap = abs(churn["accuracy"] - control["accuracy"])
        out["accuracy_gap"] = round(gap, 4)
        out["tolerance"] = args.tolerance
        ok = ok and control["ok"] and gap <= args.tolerance
        if args.alert_smoke:
            # same-seed control has no faults: the rule must stay silent
            control_quiet = (control.get("alerts") or {}).get(
                "fired", 0) == 0
            out["alert_control_quiet"] = control_quiet
            ok = ok and control_quiet
    out["ok"] = ok
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
