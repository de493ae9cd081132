"""Evaluation by reference: the community model crosses a learner's
channel once a round. The RunTask that carries a blob names it
(``TrainTask.model_ref``); the round's evaluation, sent after that
RunTask, names the same bytes (``EvalTask.model_ref``, empty ``model``),
and the learner resolves the name to what the RunTask published."""

import hashlib
import os
import sys
import threading
import time

import grpc
import numpy as np
import pytest

from metisfl_tpu import telemetry
from metisfl_tpu.comm.codec import dumps
from metisfl_tpu.comm.messages import (EvalResult, EvalTask, JoinRequest,
                                       ModelRefMissing, TaskResult,
                                       TrainParams, TrainTask)
from metisfl_tpu.comm.rpc import BytesService, RpcClient, RpcServer
from metisfl_tpu.config import AggregationConfig, EvalConfig, FederationConfig
from metisfl_tpu.controller.core import Controller, LearnerRecord
from metisfl_tpu.controller.service import (LEARNER_SERVICE, ControllerClient,
                                            ControllerServer, RpcLearnerProxy)
from metisfl_tpu.driver import InProcessFederation
from metisfl_tpu.learner.learner import Learner, ModelRefHolder
from metisfl_tpu.learner.service import LearnerServer
from metisfl_tpu.models import ArrayDataset, FlaxModelOps
from metisfl_tpu.models.zoo import MLP
from metisfl_tpu.telemetry import metrics as tmetrics
from metisfl_tpu.tensor.pytree import pack_model


@pytest.fixture()
def fresh_metrics():
    tmetrics.set_enabled(True)
    tmetrics.registry().reset()
    yield
    tmetrics.registry().reset()


def _counter(name, **labels):
    parsed = telemetry.parse_exposition(telemetry.render_metrics())
    return parsed.get(name, {}).get(tuple(sorted(labels.items())), 0.0)


def _wait(predicate, timeout_s=60.0, what="condition"):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if predicate():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


# --------------------------------------------------------------------- #
# the proxy, against a learner service that records what arrived
# --------------------------------------------------------------------- #


class _StubLearner:
    """A learner endpoint that records each RunTask's (model_ref, model)
    and each EvaluateModel's (model_ref, len(model)); with ``miss_refs``
    it refuses every evaluation by reference, with ``fail_runs`` every
    RunTask."""

    def __init__(self, miss_refs=False, fail_runs=False):
        self.runs, self.evals = [], []
        self.miss_refs, self.fail_runs = miss_refs, fail_runs
        self.server = RpcServer("127.0.0.1", 0)
        self.server.add_service(BytesService(LEARNER_SERVICE, {
            "RunTask": self._run, "EvaluateModel": self._evaluate}))
        self.port = self.server.start()

    def _run(self, raw):
        if self.fail_runs:
            raise ValueError("RunTask refused")
        task = TrainTask.from_wire(raw)
        self.runs.append((task.model_ref, bytes(task.model)))
        return dumps({"ok": True})

    def _evaluate(self, raw):
        task = EvalTask.from_wire(raw)
        self.evals.append((task.model_ref, len(task.model)))
        if self.miss_refs and not len(task.model):
            raise ModelRefMissing(task.model_ref, "was never held here")
        return EvalResult(task_id=task.task_id, round_id=task.round_id,
                          evaluations={"test": {
                              "bytes": float(len(task.model))}}).to_wire()

    def proxy(self):
        return RpcLearnerProxy(LearnerRecord("L0", "token", "127.0.0.1",
                                             self.port))


def _blob(seed=0):
    rng = np.random.default_rng(seed)
    return pack_model({"w": rng.standard_normal(4096).astype(np.float32)})


def _run_task(proxy, blob, stub):
    """Dispatch a RunTask carrying ``blob``; return the Event its error
    callback sets. Waits for the stub to have it unless it refuses."""
    failed = threading.Event()
    before = len(stub.runs)
    proxy.run_task_with_callback(
        TrainTask(task_id="t", learner_id="L0", model=blob),
        lambda exc: failed.set())
    if not stub.fail_runs:
        _wait(lambda: len(stub.runs) > before, what="the RunTask")
    return failed


def _evaluate(proxy, blob):
    """One evaluation through the proxy: (bytes it reported, result)."""
    results, done = [], threading.Event()

    def _callback(result):
        results.append(result)
        done.set()

    sent = proxy.evaluate(EvalTask(task_id="e", learner_id="L0", round_id=3,
                                   model=blob), _callback)
    assert done.wait(30), "evaluation never answered"
    return sent, results[0]


def test_proxy_names_only_the_blob_its_last_runtask_carried(fresh_metrics):
    stub = _StubLearner()
    proxy, other = stub.proxy(), stub.proxy()
    blob = _blob()
    try:
        # a fresh proxy sent nothing yet: the bytes go
        assert _evaluate(proxy, blob)[0] == len(blob)
        assert stub.evals[-1] == ("", len(blob))

        _run_task(proxy, blob, stub)
        ref, carried = stub.runs[-1]
        assert ref and carried == blob
        sent, result = _evaluate(proxy, blob)
        assert sent == 0
        assert stub.evals[-1] == (ref, 0)
        assert result.round_id == 3
        # one evaluation by reference a RunTask: the learner gave the
        # bytes up to the first, so a second evaluation carries them
        assert _evaluate(proxy, blob)[0] == len(blob)
        assert stub.evals[-1] == ("", len(blob))

        # equal bytes in another object: not what the RunTask carried
        twin = bytes(bytearray(blob))
        assert twin == blob and twin is not blob
        assert _evaluate(proxy, twin)[0] == len(blob)
        assert stub.evals[-1] == ("", len(blob))

        # another proxy, another channel: it never sent the blob
        assert _evaluate(other, blob)[0] == len(blob)
        assert stub.evals[-1] == ("", len(blob))

        # a newer RunTask takes the name with it
        newer = _blob(1)
        _run_task(proxy, newer, stub)
        assert stub.runs[-1][0] not in ("", ref)
        assert _evaluate(proxy, blob)[0] == len(blob)
        assert _evaluate(proxy, newer)[0] == 0
        assert stub.evals[-1] == (stub.runs[-1][0], 0)

        assert _counter("eval_model_ref_total", outcome="sent") == 2
        assert _counter("eval_model_ref_total", outcome="resent") == 0
        assert _counter("eval_model_ref_saved_bytes_total") == 2 * len(blob)
    finally:
        for p in (proxy, other):
            p._client.close()
        stub.server.stop()


def test_proxy_sends_bytes_after_the_runtask_failed(fresh_metrics):
    stub = _StubLearner(fail_runs=True)
    proxy = stub.proxy()
    blob = _blob()
    try:
        failed = _run_task(proxy, blob, stub)
        assert failed.wait(30), "the RunTask's error callback never fired"
        assert _evaluate(proxy, blob)[0] == len(blob)
        assert stub.evals == [("", len(blob))]
        assert _counter("eval_model_ref_total", outcome="sent") == 0
    finally:
        proxy._client.close()
        stub.server.stop()


def test_proxy_resends_a_missed_reference_once_with_its_bytes(
        fresh_metrics):
    stub = _StubLearner(miss_refs=True)
    proxy = stub.proxy()
    blob = _blob()
    try:
        _run_task(proxy, blob, stub)
        ref = stub.runs[-1][0]
        sent, result = _evaluate(proxy, blob)
        assert sent == 0
        # the answer is the evaluation that carried the bytes
        assert result.evaluations == {"test": {"bytes": float(len(blob))}}
        time.sleep(0.3)     # room for a third call, which must not come
        assert stub.evals == [(ref, 0), ("", len(blob))]
        assert _counter("eval_model_ref_total", outcome="sent") == 1
        assert _counter("eval_model_ref_total", outcome="resent") == 1
        assert _counter("eval_model_ref_saved_bytes_total") == 0
    finally:
        proxy._client.close()
        stub.server.stop()


# --------------------------------------------------------------------- #
# the learner's one-entry holder
# --------------------------------------------------------------------- #


def test_holder_resolves_a_reference_published_before_or_after(
        fresh_metrics):
    holder = ModelRefHolder()
    holder.publish("a", b"A")
    assert holder.resolve("a", 1.0)[0] == b"A"

    timer = threading.Timer(0.2, holder.publish, ("b", b"B"))
    timer.start()
    model, waited = holder.resolve("b", 30.0)
    timer.join(5)
    assert model == b"B" and waited >= 0.15
    assert _counter("learner_model_ref_total", outcome="resolved") == 2
    assert _counter("learner_model_ref_wait_seconds_count") == 2


@pytest.mark.parametrize("case", ["replaced", "taken", "never_came"])
def test_holder_misses_a_replaced_or_late_reference(fresh_metrics, case):
    holder = ModelRefHolder()
    holder.publish("a", b"A")
    t0 = time.perf_counter()
    if case in ("replaced", "taken"):
        holder.publish("b", b"B")
        if case == "taken":
            # the one evaluation of a blob takes it from the holder
            assert holder.resolve("b", 0.0)[0] == b"B"
            holder.publish("a2", b"A")
        # replaced or taken bytes will never arrive: no wait for the bound
        with pytest.raises(ModelRefMissing, match="no longer held"):
            holder.resolve("a" if case == "replaced" else "b", 30.0)
        assert time.perf_counter() - t0 < 5.0
    else:
        with pytest.raises(ModelRefMissing, match="did not arrive"):
            holder.resolve("c", 0.2)
        assert time.perf_counter() - t0 >= 0.2
    assert _counter("learner_model_ref_total", outcome="missed") == 1
    assert holder.resolve({"replaced": "b", "taken": "a2",
                           "never_came": "a"}[case], 0.0)[0]


def test_holder_under_contention_resolves_only_its_own_bytes(fresh_metrics):
    """More resolvers than cores race one publisher with a tiny switch
    interval: every resolve returns the bytes published under its own
    reference or misses, never another's, and the last one resolves."""
    holder = ModelRefHolder()
    refs = [f"r{i}" for i in range(max(32, 4 * (os.cpu_count() or 1)))]
    results = {}

    def _resolve(ref):
        try:
            results[ref] = holder.resolve(ref, 2.0)[0]
        except ModelRefMissing:
            results[ref] = None

    def _publish():
        for ref in refs:
            holder.publish(ref, ref.encode())

    threads = [threading.Thread(target=_resolve, args=(ref,))
               for ref in refs] + [threading.Thread(target=_publish)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(thread.is_alive() for thread in threads)
    assert set(results) == set(refs)
    assert all(model in (None, ref.encode())
               for ref, model in results.items())
    assert results[refs[-1]] == refs[-1].encode()
    resolved = sum(model is not None for model in results.values())
    assert _counter("learner_model_ref_total", outcome="resolved") == resolved
    assert (_counter("learner_model_ref_total", outcome="missed")
            == len(refs) - resolved)


# --------------------------------------------------------------------- #
# the learner: evaluate by reference, before and after its RunTask
# --------------------------------------------------------------------- #


class _QuietController:
    """Learner → controller stub that takes every report."""

    def join(self, request):
        raise AssertionError("not joined in this test")

    def leave(self, learner_id, auth_token):
        return True

    def task_completed(self, result):
        return True


def _data(seed=11):
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((6, 3)).astype(np.float32)

    def draw(n):
        x = rng.standard_normal((n, 6)).astype(np.float32)
        return x, np.argmax(x @ w, axis=-1).astype(np.int32)

    x, y = draw(48)
    xt, yt = draw(32)
    return ArrayDataset(x, y, seed=0), ArrayDataset(xt, yt)


def _engine(x, template=None):
    engine = FlaxModelOps(MLP(features=(16,), num_outputs=3), x[:2])
    if template is not None:
        engine.set_variables(template)
    return engine


def test_learner_resolves_a_reference_before_and_after_its_runtask(
        fresh_metrics):
    train, test = _data()
    engine = _engine(train.x)
    learner = Learner(engine, train, _QuietController(), test_dataset=test)
    blob = pack_model(engine.get_variables())
    server = LearnerServer(learner, host="127.0.0.1", port=0)
    client = None
    try:
        direct = learner.evaluate(EvalTask(model=blob, datasets=["test"]))
        assert direct.evaluations["test"]
        by_ref = EvalTask(model=b"", model_ref="r1", datasets=["test"])

        # before: the evaluation waits for the RunTask that carries it
        early = []
        waiter = threading.Thread(
            target=lambda: early.append(learner.evaluate(by_ref)))
        waiter.start()
        time.sleep(0.3)
        assert waiter.is_alive()
        learner.run_task(TrainTask(
            task_id="t1", model=blob, model_ref="r1",
            params=TrainParams(batch_size=8, local_steps=1)))
        waiter.join(60)
        assert not waiter.is_alive() and early
        assert early[0].evaluations == direct.evaluations

        # after: a RunTask whose bytes came first resolves at once, to
        # the same evaluation
        learner.run_task(TrainTask(
            task_id="t2", model=blob, model_ref="r2",
            params=TrainParams(batch_size=8, local_steps=1)))
        late = EvalTask(model=b"", model_ref="r2", datasets=["test"])
        assert learner.evaluate(late).evaluations == direct.evaluations
        assert _counter("learner_model_ref_total", outcome="resolved") == 2
        # the evaluation took the bytes: a second one by reference misses
        with pytest.raises(ModelRefMissing, match="no longer held"):
            learner.evaluate(late)

        # a miss crosses the wire as the typed error
        learner.model_ref_wait_s = 0.2
        port = server.start()
        client = RpcClient("127.0.0.1", port, LEARNER_SERVICE)
        with pytest.raises(grpc.RpcError) as info:
            client.call("EvaluateModel", EvalTask(
                model=b"", model_ref="r0", datasets=["test"]).to_wire(),
                timeout=30)
        assert info.value.code() == grpc.StatusCode.FAILED_PRECONDITION
        assert ModelRefMissing.raised(info.value)
        assert _counter("learner_model_ref_total", outcome="missed") == 2
    finally:
        if client is not None:
            client.close()
        server.stop(leave=False)


# --------------------------------------------------------------------- #
# the controller: each round's evaluation after the next dispatch
# --------------------------------------------------------------------- #


class _LogProxy:
    """Records dispatches and evaluations in one ordered log and answers
    every evaluation at once."""

    log = []

    def __init__(self, record):
        self.learner_id = record.learner_id

    def run_task(self, task):
        _LogProxy.log.append(("run", task.round_id, task.model))

    def evaluate(self, task, callback):
        _LogProxy.log.append(("eval", task.round_id, task.model))
        callback(EvalResult(task_id=task.task_id, learner_id=self.learner_id,
                            round_id=task.round_id,
                            evaluations={"test": {"round": task.round_id}}))

    def shutdown(self):
        pass


def _submit(ctrl, lid, round_no):
    with ctrl._lock:
        token = ctrl._learners[lid].auth_token
    model = {"w": np.full((4,), float(round_no + 1), np.float32)}
    assert ctrl.task_completed(TaskResult(
        task_id=f"t{round_no}", learner_id=lid, auth_token=token,
        model=pack_model(model), round_id=round_no, completed_batches=1,
        train_metrics={"loss": 0.5}))


def test_controller_sends_a_rounds_evaluation_after_the_next_dispatch(
        fresh_metrics):
    _LogProxy.log = []
    ctrl = Controller(FederationConfig(
        aggregation=AggregationConfig(rule="fedavg", scaler="participants"),
        train=TrainParams(batch_size=4, local_steps=1),
        eval=EvalConfig(every_n_rounds=1)), proxy_factory=_LogProxy)
    try:
        ctrl.set_community_model(pack_model({"w": np.zeros(4, np.float32)}))
        lid = ctrl.join(JoinRequest(hostname="h", port=7700,
                                    num_train_examples=10)).learner_id
        _wait(lambda: len(_LogProxy.log) == 1, what="round 0's dispatch")
        _submit(ctrl, lid, 0)
        _wait(lambda: len(_LogProxy.log) == 3, what="round 0's evaluation")
        assert [e[:2] for e in _LogProxy.log] == [
            ("run", 0), ("run", 1), ("eval", 0)]
        # the evaluation holds the very object round 1's RunTask carried
        assert _LogProxy.log[2][2] is _LogProxy.log[1][2]
        entry = ctrl.community_evaluations[0]
        assert entry["global_iteration"] == 0
        assert entry["evaluations"][lid] == {"test": {"round": 0}}
        meta = ctrl.round_metadata[0]
        assert lid in meta.eval_submitted_at and lid in meta.eval_received_at

        # a controller that stops before the next dispatch still sends
        # the closing round's evaluation, bound to that round
        ctrl._maybe_recompute_semisync = ctrl._shutdown.set
        _submit(ctrl, lid, 1)
        _wait(lambda: len(_LogProxy.log) == 4, what="round 1's evaluation")
        assert _LogProxy.log[3][:2] == ("eval", 1)
        assert ctrl.community_evaluations[1]["global_iteration"] == 1
        assert ctrl.community_evaluations[1]["evaluations"][lid] == {
            "test": {"round": 1}}
        assert lid in ctrl.round_metadata[1].eval_received_at
    finally:
        ctrl.shutdown()


# --------------------------------------------------------------------- #
# a real-gRPC federation against the in-process one at the same seed
# --------------------------------------------------------------------- #


def _federation_config():
    return FederationConfig(
        aggregation=AggregationConfig(rule="fedavg", scaler="participants"),
        train=TrainParams(batch_size=8, local_steps=2, learning_rate=0.1),
        eval=EvalConfig(batch_size=64, datasets=["test"], every_n_rounds=1))


def _record_models(ctrl):
    """sha256 of the community model each round closed with."""
    models = {}
    register = ctrl._register_round_version

    def _recorded():
        with ctrl._lock:
            models[ctrl.global_iteration] = hashlib.sha256(
                ctrl._community_blob).hexdigest()
        register()

    ctrl._register_round_version = _recorded
    return models


def _evaluated(ctrl, rounds):
    with ctrl._lock:
        entries = [dict(e) for e in ctrl.community_evaluations]
    done = {e["global_iteration"]: e["evaluations"] for e in entries
            if e["evaluations"]}
    return done if all(r in done for r in range(rounds)) else None


ROUNDS = 3


def test_grpc_federation_evaluates_by_reference_bit_identical_to_inprocess(
        fresh_metrics):
    train, test = _data()
    template = _engine(train.x).get_variables()

    fed = InProcessFederation(_federation_config())
    local_models = _record_models(fed.controller)
    fed.add_learner(_engine(train.x, template), train, test_dataset=test)
    fed.seed_model(template)
    try:
        fed.start()
        assert fed.wait_until(
            lambda: _evaluated(fed.controller, ROUNDS), timeout_s=120)
        local_evals = _evaluated(fed.controller, ROUNDS)
    finally:
        fed.shutdown()

    tmetrics.registry().reset()
    train, test = _data()
    controller = Controller(_federation_config(),
                            lambda record: RpcLearnerProxy(record))
    wire_models = _record_models(controller)
    ctrl_server = ControllerServer(controller, host="127.0.0.1", port=0)
    ctrl_port = ctrl_server.start()
    controller.set_community_model(pack_model(template))
    ctrl_client = ControllerClient("127.0.0.1", ctrl_port)
    learner = Learner(_engine(train.x, template), train, ctrl_client,
                      test_dataset=test, hostname="127.0.0.1")
    lserver = LearnerServer(learner, host="127.0.0.1", port=0)
    lserver.start()
    try:
        learner.join_federation()
        _wait(lambda: _evaluated(controller, ROUNDS), timeout_s=120,
              what=f"{ROUNDS} evaluated rounds")
        wire_evals = _evaluated(controller, ROUNDS)
        metas = controller.get_statistics()["round_metadata"][:ROUNDS]
        blob_len = len(controller.community_model_bytes())
    finally:
        lserver.stop(leave=False)
        ctrl_server.stop()
        ctrl_client.close()

    for r in range(ROUNDS):
        assert wire_models[r] == local_models[r], f"round {r}"
        # every round's evaluation is recorded under its own round, and
        # evaluated that round's model
        assert set(wire_evals[r]) == {learner.learner_id}
        assert (list(wire_evals[r].values())
                == list(local_evals[r].values())), f"round {r}"
    # one blob a round crosses the channel: the RunTask's
    for meta in metas:
        assert meta["profile"]["learners"][learner.learner_id][
            "downlink_bytes"] == blob_len, meta["global_iteration"]
    assert _counter("eval_model_ref_total", outcome="sent") >= ROUNDS
    assert _counter("eval_model_ref_total", outcome="resent") == 0
    assert _counter("learner_model_ref_total", outcome="resolved") >= ROUNDS
    assert _counter("learner_model_ref_total", outcome="missed") == 0
