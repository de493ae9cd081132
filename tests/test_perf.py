"""Performance observatory (ISSUE 6): per-round cost profiles, device
utilization capture, the perf analyzer CLI, and every surface they flow
into.

Protocol-level tests drive a bare :class:`Controller` over no-op proxies
with crafted uplinks (deterministic byte counts — the wire-attribution
equality the acceptance gate pins); the integration test runs a real
in-process 2-round federation and checks waterfall coverage + device
stats; the rest covers the waterfall CLI, pruning on leave, the
disabled-path inertness contract, post-mortem profile tails, and the doc
catalog drift guard.
"""

import json
import os
import types

import numpy as np
import pytest

from metisfl_tpu import telemetry
from metisfl_tpu.comm import codec as _codec
from metisfl_tpu.comm.messages import JoinRequest, TaskResult, TrainParams
from metisfl_tpu.config import (
    AggregationConfig,
    EvalConfig,
    FederationConfig,
    ProfileConfig,
    TelemetryConfig,
)
from metisfl_tpu.controller.core import Controller
from metisfl_tpu.telemetry import events as tevents
from metisfl_tpu.telemetry import metrics as tmetrics
from metisfl_tpu.telemetry import profile as tprofile
from metisfl_tpu.tensor.pytree import pack_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def clean_telemetry():
    tevents.configure(enabled=True, service="test", dir="", ring_size=512)
    tevents.journal().reset()
    tmetrics.set_enabled(True)
    tmetrics.registry().reset()
    yield
    tprofile.set_collector(None)
    tevents.configure(enabled=True, service="test", dir="", ring_size=512)
    tevents.journal().reset()


# --------------------------------------------------------------------- #
# protocol-level controller (crafted uplinks, deterministic bytes)
# --------------------------------------------------------------------- #


class _RecordingProxy:
    """No-op learner proxy that keeps the dispatched tasks (so tests can
    read the stamped TrainParams)."""

    tasks = []  # class-level: shared across proxies of one test

    def __init__(self, record):
        self.learner_id = record.learner_id

    def run_task(self, task):
        _RecordingProxy.tasks.append(task)

    def evaluate(self, task, callback):
        pass

    def shutdown(self):
        pass


def _profile_controller(profile=True, trace_every=0, tel_dir=""):
    config = FederationConfig(
        protocol="synchronous",
        aggregation=AggregationConfig(rule="fedavg", scaler="participants"),
        train=TrainParams(batch_size=4, local_steps=1),
        eval=EvalConfig(every_n_rounds=0),
        telemetry=TelemetryConfig(
            dir=tel_dir,
            profile=ProfileConfig(enabled=profile,
                                  trace_every_rounds=trace_every)),
    )
    _RecordingProxy.tasks = []
    return Controller(config, proxy_factory=_RecordingProxy)


def _seed_model():
    return {"enc/w": np.zeros((6, 4), np.float32),
            "head/w": np.zeros((4,), np.float32)}


def _wait(predicate, timeout_s=30.0, msg="condition"):
    import time
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if predicate():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


def _run_round(ctrl, round_no, device_stats=True):
    """One crafted sync round: every joined learner submits a model."""
    lids = sorted(ctrl.active_learners())
    with ctrl._lock:
        tokens = {lid: ctrl._learners[lid].auth_token for lid in lids}
    rng = np.random.default_rng(round_no)
    for i, lid in enumerate(lids):
        model = {"enc/w": rng.standard_normal((6, 4)).astype(np.float32),
                 "head/w": rng.standard_normal(4).astype(np.float32)}
        stats = {}
        if device_stats:
            stats = {"steps": 2, "ms_per_step": 2.0 + i,
                     "step_ms_ewma": 2.0 + i, "mfu": 0.01 * (i + 1),
                     "hbm_peak_bytes": 1000 * (i + 1),
                     "device_kind": "cpu"}
        assert ctrl.task_completed(TaskResult(
            task_id=f"t{round_no}_{lid}", learner_id=lid,
            auth_token=tokens[lid], model=pack_model(model),
            round_id=round_no, completed_batches=1,
            train_metrics={"loss": 0.5}, device_stats=stats))
    _wait(lambda: ctrl.global_iteration > round_no,
          msg=f"round {round_no + 1}")
    return lids


def test_round_profiles_attribute_wire_bytes_and_cover_the_round(
        clean_telemetry):
    """Acceptance core: a 2-round federation produces RoundProfiles whose
    per-learner uplink attribution sums EXACTLY to the uplink_bytes_total
    counter, whose phase waterfall covers >= 95% of round wall-clock, and
    whose learner entries carry the shipped device stats."""
    ctrl = _profile_controller()
    try:
        ctrl.set_community_model(pack_model(_seed_model()))
        for i in range(3):
            ctrl.join(JoinRequest(hostname="h", port=7600 + i,
                                  num_train_examples=10))
        _run_round(ctrl, 0)
        lids = _run_round(ctrl, 1)

        metas = ctrl.get_statistics()["round_metadata"]
        assert len(metas) >= 2
        profiles = [m["profile"] for m in metas[:2]]
        parsed = telemetry.parse_exposition(telemetry.render_metrics())

        # per-learner wire-byte attribution == the counter, per learner
        uplink_counter = parsed["uplink_bytes_total"]
        for lid in lids:
            attributed = sum(p["learners"].get(lid, {}).get(
                "uplink_bytes", 0) for p in profiles)
            assert attributed == uplink_counter[(("learner", lid),)], lid
        for prof in profiles:
            assert prof["totals"]["uplink_bytes"] == sum(
                e["uplink_bytes"] for e in prof["learners"].values())

        # waterfall: the five phases cover the round
        for prof in profiles:
            assert set(prof["phases"]) == {"dispatch", "wait_uplinks",
                                           "select", "aggregate", "close"}
            assert prof["coverage"] >= 0.95, prof
            assert prof["wall_ms"] > 0

        # downlink attribution: every learner got the community blob at
        # least once, gauge series exist, and the counter covers the
        # profiled totals (round-3 dispatch lands after round 2 closes)
        down_counter = parsed["downlink_bytes_total"]
        profiled_down = sum(p["totals"]["downlink_bytes"]
                            for p in profiles)
        assert profiled_down > 0
        assert profiled_down <= sum(down_counter.values())
        for lid in lids:
            assert (("learner", lid),) in down_counter

        # device stats flowed into the profile and the gauges
        last = profiles[1]
        for i, lid in enumerate(lids):
            device = last["learners"][lid]["device"]
            assert device["step_ms_ewma"] == pytest.approx(2.0 + i)
            assert parsed["learner_achieved_mfu"][
                (("learner", lid),)] == pytest.approx(0.01 * (i + 1))
            assert parsed["learner_step_ms_ewma"][
                (("learner", lid),)] == pytest.approx(2.0 + i)

        # store timings recorded; insert attributed per learner
        assert last["store"]["insert_ms"] >= 0.0
        assert last["store"]["select_ms"] > 0.0
        assert all("insert_ms" in last["learners"][lid] for lid in lids)

        # live status plane carries the summary
        snap = ctrl.describe()
        assert snap["profile"]["enabled"]
        assert snap["profile"]["rounds_profiled"] >= 2
        assert snap["profile"]["coverage"] >= 0.95
    finally:
        ctrl.shutdown()


def test_profile_jsonl_sink_and_perf_waterfall_render(clean_telemetry,
                                                      tmp_path):
    """Profiles persist next to the traces and the perf CLI's loader +
    waterfall renderer read them back."""
    from metisfl_tpu import perf

    tel_dir = str(tmp_path / "telemetry")
    ctrl = _profile_controller(tel_dir=tel_dir)
    try:
        ctrl.set_community_model(pack_model(_seed_model()))
        for i in range(2):
            ctrl.join(JoinRequest(hostname="h", port=7620 + i,
                                  num_train_examples=10))
        _run_round(ctrl, 0)
    finally:
        ctrl.shutdown()
    path = ctrl._profile.profiles_path()
    assert path and os.path.exists(path)
    profiles = perf.load_profiles(tel_dir)
    assert profiles and profiles[0]["round"] == 0
    # the run-dir form resolves the telemetry/ subdir too
    assert perf.load_profiles(str(tmp_path)) == profiles
    screen = perf.render_waterfall(profiles)
    assert "wait_uplinks" in screen and "coverage" in screen
    for lid in profiles[0]["learners"]:
        assert lid in screen
    # experiment.json round-metadata form loads identically
    exp = tmp_path / "experiment.json"
    exp.write_text(json.dumps(ctrl.get_statistics(), default=str))
    assert perf.load_profiles(str(exp))[0]["round"] == 0
    # CLI end-to-end: exit 0 and renders
    assert perf.main([str(tmp_path)]) == 0


def test_leave_prunes_profile_series(clean_telemetry):
    """Departed learners' wire-byte/MFU/step-time/codec series must not
    accumulate (checked via the metrics exposition — the PR 3/4 pruning
    pattern)."""
    ctrl = _profile_controller()
    try:
        ctrl.set_community_model(pack_model(_seed_model()))
        for i in range(3):
            ctrl.join(JoinRequest(hostname="h", port=7640 + i,
                                  num_train_examples=10))
        # mint a codec-attribution series for the departing learner BEFORE
        # the round (the gRPC service layer does this on real runs), so
        # the round-close assemble snapshots it for per-round diffing
        gone = sorted(ctrl.active_learners())[2]
        _codec.attribute(gone, "decode", 0.01)
        lids = _run_round(ctrl, 0)
        assert any(k[0] == gone for k in ctrl._profile._codec_snapshot)
        with ctrl._lock:
            token = ctrl._learners[gone].auth_token
        parsed = telemetry.parse_exposition(telemetry.render_metrics())
        for series in ("downlink_bytes_total", "learner_achieved_mfu",
                       "learner_step_ms_ewma", "learner_hbm_peak_bytes"):
            assert (("learner", gone),) in parsed[series], series
        assert any(k[0] == ("learner", gone)
                   for k in parsed["codec_learner_seconds_total"])

        assert ctrl.leave(gone, token)
        parsed = telemetry.parse_exposition(telemetry.render_metrics())
        for series in ("downlink_bytes_total", "learner_achieved_mfu",
                       "learner_step_ms_ewma", "learner_hbm_peak_bytes",
                       "uplink_bytes_total"):
            assert (("learner", gone),) not in parsed.get(series, {}), series
        assert not any(k[0] == ("learner", gone)
                       for k in parsed.get("codec_learner_seconds_total",
                                           {}))
        assert (gone, "decode") not in _codec.attributed_totals()
        # the per-round diff snapshot is pruned with the totals — a
        # leave→rejoin between round closes must not diff a fresh total
        # against the stale snapshot and record a negative codec cost
        assert not any(k[0] == gone for k in ctrl._profile._codec_snapshot)
        # survivors keep their series
        assert (("learner", lids[0]),) in parsed["downlink_bytes_total"]
    finally:
        ctrl.shutdown()


def test_disabled_profile_is_one_attribute_check(clean_telemetry,
                                                 monkeypatch):
    """telemetry.profile.enabled=false: no collector is constructed, no
    profile key appears anywhere, and dispatched tasks stamp
    device_stats=false so the learner path is inert too."""
    def _boom(*args, **kwargs):  # pragma: no cover - the point: unreached
        raise AssertionError("profile work ran on the disabled path")

    monkeypatch.setattr(tprofile.ProfileCollector, "__init__", _boom)
    ctrl = _profile_controller(profile=False)
    try:
        assert ctrl._profile is None
        ctrl.set_community_model(pack_model(_seed_model()))
        for i in range(2):
            ctrl.join(JoinRequest(hostname="h", port=7660 + i,
                                  num_train_examples=10))
        _run_round(ctrl, 0, device_stats=False)
        meta = ctrl.get_statistics()["round_metadata"][0]
        assert meta["profile"] == {}
        assert "profile" not in ctrl.describe()
        assert _RecordingProxy.tasks
        assert all(t.params.device_stats is False
                   for t in _RecordingProxy.tasks)
        parsed = telemetry.parse_exposition(telemetry.render_metrics())
        assert "downlink_bytes_total" not in parsed
        # the gRPC proxy layer gates attribution on the active collector:
        # with the plane off nothing was minted
        assert "codec_learner_seconds_total" not in parsed
        # ...and even attribution minted OUTSIDE the gate (e.g. before a
        # config change + resume) is still pruned when the learner leaves
        gone = sorted(ctrl.active_learners())[0]
        _codec.attribute(gone, "decode", 0.01)
        with ctrl._lock:
            token = ctrl._learners[gone].auth_token
        assert ctrl.leave(gone, token)
        assert (gone, "decode") not in _codec.attributed_totals()
        parsed = telemetry.parse_exposition(telemetry.render_metrics())
        assert not any(k[0] == ("learner", gone)
                       for k in parsed.get("codec_learner_seconds_total",
                                           {}))
    finally:
        ctrl.shutdown()


def test_trace_every_rounds_arms_dispatched_profile_dir(clean_telemetry,
                                                        tmp_path):
    """The periodic jax.profiler gate: due rounds stamp profile_dir on
    the dispatched TrainParams, off rounds leave it empty."""
    tel_dir = str(tmp_path / "tel")
    ctrl = _profile_controller(trace_every=2, tel_dir=tel_dir)
    try:
        collector = ctrl._profile
        assert collector.trace_target(0).endswith("round0")
        assert collector.trace_target(1) == ""
        assert collector.trace_target(2).endswith("round2")
        ctrl.set_community_model(pack_model(_seed_model()))
        ctrl.join(JoinRequest(hostname="h", port=7680,
                              num_train_examples=10))
        _wait(lambda: _RecordingProxy.tasks, msg="initial dispatch")
        task = _RecordingProxy.tasks[0]
        assert task.params.profile_dir.endswith(
            os.path.join("jaxprof", "round0"))
        assert task.params.device_stats is True
    finally:
        ctrl.shutdown()


# --------------------------------------------------------------------- #
# in-process federation with real training (coverage + device capture)
# --------------------------------------------------------------------- #


def test_inprocess_two_round_federation_profiles(clean_telemetry):
    from metisfl_tpu.comm.messages import TrainParams as TP
    from metisfl_tpu.config import TerminationConfig
    from metisfl_tpu.driver import InProcessFederation
    from metisfl_tpu.models import ArrayDataset, FlaxModelOps
    from metisfl_tpu.models.zoo import MLP

    rng = np.random.default_rng(7)
    w = rng.standard_normal((6, 3)).astype(np.float32)
    config = FederationConfig(
        protocol="synchronous",
        aggregation=AggregationConfig(rule="fedavg",
                                      scaler="participants"),
        train=TP(batch_size=16, local_steps=4, learning_rate=0.1),
        eval=EvalConfig(every_n_rounds=0),
        termination=TerminationConfig(federation_rounds=2),
    )
    fed = InProcessFederation(config)
    template = None
    for i in range(2):
        x = rng.standard_normal((48, 6)).astype(np.float32)
        y = np.argmax(x @ w, axis=-1).astype(np.int32)
        engine = FlaxModelOps(MLP(features=(8,), num_outputs=3), x[:2])
        if template is None:
            template = engine.get_variables()
        else:
            engine.set_variables(template)
        fed.add_learner(engine, ArrayDataset(x, y, seed=i))
    fed.seed_model(template)
    try:
        fed.start()
        assert fed.wait_for_rounds(2, timeout_s=120)
        metas = fed.statistics()["round_metadata"]
        profiles = [m["profile"] for m in metas[:2] if m.get("profile")]
        assert len(profiles) == 2
        for prof in profiles:
            assert prof["coverage"] >= 0.95, prof
            # the waterfall tiles the wall: five nonnegative segments
            # whose sum is the round (phase DOMINANCE is deliberately not
            # asserted — on a loaded single-core box, round-0 aggregation
            # jit-compile and GIL-contended dispatch are the same order
            # as this tiny model's training time)
            phases = prof["phases"]
            assert set(phases) == {"dispatch", "wait_uplinks", "select",
                                   "aggregate", "close"}
            assert all(v >= 0.0 for v in phases.values()), phases
            assert phases["wait_uplinks"] > 0
            assert sum(phases.values()) == pytest.approx(
                prof["wall_ms"], rel=0.06)
            # attribution is internally consistent with the lineage
            assert prof["totals"]["uplink_bytes"] > 0
            assert prof["totals"]["downlink_bytes"] > 0
            for lid, entry in prof["learners"].items():
                assert entry["uplink_bytes"] > 0
                assert entry["downlink_bytes"] > 0
        # real engines shipped device stats (CPU: mfu 0, EWMA real)
        device = next(iter(profiles[1]["learners"].values()))["device"]
        assert device["steps"] == 4
        assert device["step_ms_ewma"] > 0
        assert device["flops_per_step"] > 0
    finally:
        fed.shutdown()


# --------------------------------------------------------------------- #
# device monitor / tracer units
# --------------------------------------------------------------------- #


def test_device_monitor_ewma_and_mfu_math():
    monitor = tprofile.DeviceMonitor(alpha=0.5)
    monitor._peak_flops = 100e12  # pretend chip
    monitor._device_kind = "fake-tpu"
    s1 = monitor.observe(steps=4, ms_per_step=10.0, flops_per_step=5e11)
    # 5e11 FLOPs / 10ms = 5e13 FLOP/s over 1e14 peak = 0.5
    assert s1["mfu"] == pytest.approx(0.5)
    assert s1["step_ms_ewma"] == pytest.approx(10.0)
    s2 = monitor.observe(steps=4, ms_per_step=20.0, flops_per_step=5e11)
    assert s2["step_ms_ewma"] == pytest.approx(15.0)
    assert s2["mfu"] == pytest.approx(0.25)
    # CPU: there is no peak to divide by — no mfu sample at all, not a 0
    cold = tprofile.DeviceMonitor()
    out = cold.observe(steps=1, ms_per_step=1.0, flops_per_step=1e9)
    assert out["device_kind"] == "cpu"
    assert "mfu" not in out


def test_device_peak_flops_exact_kind_or_error():
    assert tprofile.device_peak_flops("TPU v5 lite") == 197e12
    assert tprofile.device_peak_flops("cpu") is None
    # an accelerator the table does not know is an error, never a default
    # (the old substring match handed any "v5..." the v5p's peak)
    for kind in ("TPU v5", "TPU v7x", "tpu v5 lite", ""):
        with pytest.raises(ValueError, match="no peak FLOP/s on record"):
            tprofile.device_peak_flops(kind)


def test_device_tracer_unique_dirs_and_exception_safe_stop(tmp_path,
                                                           monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))
    base = str(tmp_path / "prof")
    t1 = tprofile.device_tracer(base)
    t2 = tprofile.device_tracer(base)
    assert t1.start() and t2.start()
    # same base dir, same second — still distinct capture sessions
    assert t1.session_dir != t2.session_dir
    assert os.path.isdir(t1.session_dir) and os.path.isdir(t2.session_dir)
    # one capture per handle; stop is idempotent (the finally-path form)
    t1.stop()
    t1.stop()
    assert not t1.start() and t1.captured
    t2.stop()
    assert [c[0] for c in calls].count("start") == 2
    assert [c[0] for c in calls].count("stop") == 2
    # inert handle: no dir, no calls
    inert = tprofile.device_tracer("")
    assert not inert.start()
    inert.stop()
    assert [c[0] for c in calls].count("start") == 2


def test_ops_train_profiles_through_the_tracer(tmp_path, monkeypatch):
    """models/ops.py drives the hoisted tracer: a per-step run captures
    exactly one start/stop pair into a unique session dir."""
    import jax

    from metisfl_tpu.models import ArrayDataset, FlaxModelOps
    from metisfl_tpu.models.zoo import MLP

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((32, 4)).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.int32)
    engine = FlaxModelOps(MLP(features=(4,), num_outputs=2), x[:2])
    out = engine.train(
        ArrayDataset(x, y, seed=0),
        TrainParams(batch_size=8, local_steps=6,
                    profile_dir=str(tmp_path / "jp"), profile_steps=2))
    assert out.completed_steps == 6
    starts = [c for c in calls if c[0] == "start"]
    stops = [c for c in calls if c[0] == "stop"]
    assert len(starts) == 1 and len(stops) == 1
    assert starts[0][1].startswith(str(tmp_path / "jp"))
    # FLOPs accounting backs the MFU estimate
    assert engine.param_count() > 0
    assert engine.step_flops(8) == 6.0 * engine.param_count() * 8


# --------------------------------------------------------------------- #
# codec + rpc wire attribution units
# --------------------------------------------------------------------- #


def test_codec_attribution_context_and_totals(clean_telemetry):
    payload = {"model": b"x" * 4096, "learner_id": "L7"}
    with _codec.attributed("L7"):
        buf = _codec.dumps(payload)
        _codec.loads(buf)
    totals = _codec.attributed_totals()
    assert totals[("L7", "encode")] > 0
    assert totals[("L7", "decode")] > 0
    parsed = telemetry.parse_exposition(telemetry.render_metrics())
    series = parsed["codec_learner_seconds_total"]
    assert (("learner", "L7"), ("op", "encode")) in series
    # outside the context nothing attributes
    _codec.dumps({"a": 1})
    assert set(k for k in _codec.attributed_totals()) == {
        ("L7", "encode"), ("L7", "decode")}
    _codec.prune_attribution("L7")
    assert _codec.attributed_totals() == {}


def test_rpc_peer_byte_series_and_pruning(clean_telemetry):
    from metisfl_tpu.comm import rpc as _rpc

    client = _rpc.RpcClient("localhost", 1, "svc", retries=0, peer="L9")
    try:
        client._count_bytes(100, "sent", method="M")
        client._count_bytes(50, "received", method="M")
    finally:
        client.close()
    parsed = telemetry.parse_exposition(telemetry.render_metrics())
    series = parsed["rpc_peer_bytes_total"]
    assert series[(("direction", "sent"), ("peer", "L9"))] == 100
    assert series[(("direction", "received"), ("peer", "L9"))] == 50
    _rpc.prune_peer_series("L9")
    parsed = telemetry.parse_exposition(telemetry.render_metrics())
    assert "rpc_peer_bytes_total" not in parsed


# --------------------------------------------------------------------- #
# perf CLI: span self-times
# --------------------------------------------------------------------- #

def test_span_self_times_subtract_children():
    from metisfl_tpu import perf

    spans = [
        {"span": "a", "parent": "", "name": "round", "dur_ms": 100.0},
        {"span": "b", "parent": "a", "name": "round.aggregate",
         "dur_ms": 60.0},
        {"span": "c", "parent": "b", "name": "round.agg_block",
         "dur_ms": 50.0},
        {"span": "d", "parent": "a", "name": "round.dispatch",
         "dur_ms": 10.0},
    ]
    rows = {r["name"]: r for r in perf.span_self_times(spans)}
    assert rows["round"]["self_ms"] == pytest.approx(30.0)
    assert rows["round.aggregate"]["self_ms"] == pytest.approx(10.0)
    assert rows["round.agg_block"]["self_ms"] == pytest.approx(50.0)
    table = perf.render_self_times(perf.span_self_times(spans), top=2)
    assert "round.agg_block" in table


# --------------------------------------------------------------------- #
# post-mortem, status, stats, docs surfaces
# --------------------------------------------------------------------- #


def _fake_meta(round_no=4):
    return types.SimpleNamespace(
        global_iteration=round_no, started_at=100.0, completed_at=100.5,
        dispatch_duration_ms=5.0, wait_duration_ms=460.0,
        aggregation_duration_ms=20.0, uplink_bytes={"L0": 1000},
    )


def test_postmortem_bundle_includes_profile_tail(clean_telemetry,
                                                 tmp_path, capsys):
    """Satellite: a crash/chaos-kill bundle carries the latest
    RoundProfile tail and --postmortem renders it."""
    from metisfl_tpu.telemetry import postmortem
    from metisfl_tpu.telemetry.__main__ import main as viewer_main

    collector = tprofile.ProfileCollector(service="controller")
    collector.note_downlink("L0", 2048)
    collector.note_phase("select", 1.0)
    record = collector.assemble_round(_fake_meta(), close_ms=10.0)
    assert record["coverage"] > 0.9
    tprofile.set_collector(collector)
    try:
        pm_dir = str(tmp_path / "pm")
        postmortem.configure(pm_dir, service="controller",
                             install_hooks=False)
        path = postmortem.dump("chaos_kill")
        assert path
        with open(path) as fh:
            bundle = json.load(fh)
        assert bundle["profiles"][-1]["round"] == 4
        assert bundle["profiles"][-1]["learners"]["L0"][
            "downlink_bytes"] == 2048
        assert viewer_main(["--postmortem", pm_dir]) == 0
        out = capsys.readouterr().out
        assert "round cost profiles at death" in out
        assert "round 4" in out
    finally:
        postmortem.configure("", install_hooks=False)
        tprofile.set_collector(None)


def test_status_renders_perf_line(clean_telemetry):
    from metisfl_tpu.status import render_snapshot

    snap = {
        "controller_epoch": "abc12345", "round": 5, "phase": "idle",
        "protocol": "synchronous", "aggregation_rule": "fedavg",
        "learners": [], "in_flight": [], "store": {"models": {}},
        "events": [], "time": 0.0,
        "profile": {"enabled": True, "rounds_profiled": 5,
                    "last_round": 4, "wall_ms": 512.3, "coverage": 0.97,
                    "phases": {"wait_uplinks": 460.0, "aggregate": 20.0},
                    "uplink_bytes": 3.2e6, "downlink_bytes": 6.4e6},
    }
    screen = render_snapshot(snap)
    assert "perf:" in screen
    assert "coverage=97%" in screen
    assert "top_phase=wait_uplinks" in screen
    # pre-profile snapshots render without the line
    del snap["profile"]
    assert "perf:" not in render_snapshot(snap)


def test_stats_summarize_renders_cost_profile_block(clean_telemetry):
    from metisfl_tpu.stats import profile_summary, summarize

    collector = tprofile.ProfileCollector()
    record = collector.assemble_round(_fake_meta(round_no=0),
                                      close_ms=10.0)
    stats = {"global_iteration": 1, "learners": ["L0"],
             "round_metadata": [
                 {"global_iteration": 0, "started_at": 100.0,
                  "completed_at": 100.5, "selected_learners": ["L0"],
                  "aggregation_duration_ms": 20.0, "profile": record}],
             "community_evaluations": []}
    rows = profile_summary(stats)
    assert rows[0]["shares"][0][0] == "wait_uplinks"
    assert rows[0]["coverage"] > 0.9
    text = summarize(stats)
    assert "cost profile" in text
    # pre-profile payloads render without the block (backward compat)
    stats["round_metadata"][0].pop("profile")
    assert "cost profile" not in summarize(stats)


def test_metric_catalog_doc_covers_every_constant():
    """Drift guard satellite: every M_* series name exported by
    metisfl_tpu.telemetry appears in the OBSERVABILITY.md catalog."""
    doc = open(os.path.join(REPO, "docs", "OBSERVABILITY.md")).read()
    names = [getattr(telemetry, n) for n in dir(telemetry)
             if n.startswith("M_")]
    assert len(names) >= 40  # the catalog is real, not a stub
    missing = [name for name in names if name not in doc]
    assert not missing, (
        f"metric constants missing from docs/OBSERVABILITY.md: {missing}")


def test_template_pins_profile_block():
    """template.yaml documents the telemetry.profile block at defaults
    (the full-coverage template test enforces presence; this pins the
    documented defaults match the dataclass)."""
    import yaml

    with open(os.path.join(REPO, "examples", "config",
                           "template.yaml")) as fh:
        raw = yaml.safe_load(fh)
    block = raw["telemetry"]["profile"]
    default = ProfileConfig()
    assert block["enabled"] == default.enabled
    assert block["trace_every_rounds"] == default.trace_every_rounds
    assert block["dir"] == default.dir
    assert raw["train"]["device_stats"] is True
    with pytest.raises(ValueError, match="trace_every_rounds"):
        FederationConfig(telemetry=TelemetryConfig(
            profile=ProfileConfig(trace_every_rounds=-1)))


def test_controller_shutdown_clears_global_collector(clean_telemetry):
    """A controller deregisters the process-global collector handle at
    shutdown: a later controller in the same process with the profile
    plane off must see None (its RPC layer gates per-learner attribution
    on the active collector)."""
    ctrl = _profile_controller()
    try:
        assert tprofile.collector() is ctrl._profile
    finally:
        ctrl.shutdown()
    assert tprofile.collector() is None
    disabled = _profile_controller(profile=False)
    try:
        assert tprofile.collector() is None
    finally:
        disabled.shutdown()


def test_serving_gateway_wires_queue_probe_into_collector(clean_telemetry):
    """An in-process gateway (same process as the controller's collector)
    registers its queue probe so RoundProfiles carry serving occupancy;
    shutdown deregisters it. No collector -> nothing wired."""
    from metisfl_tpu.config import ServingConfig
    from metisfl_tpu.serving.gateway import ServingGateway

    class _Ops:
        def get_variables(self):
            return {"w": np.zeros((2, 2), np.float32)}

    sc = ServingConfig(enabled=True, max_batch=4, max_wait_ms=1.0)
    # no active collector: the gateway stays unwired
    unwired = ServingGateway(_Ops(), sc)
    unwired.shutdown()

    coll = tprofile.ProfileCollector()
    tprofile.set_collector(coll)
    gw = ServingGateway(_Ops(), sc)
    try:
        assert coll.serving_probe is not None
        snap = coll.serving_probe()
        assert snap["queue_depth"] == 0
        assert snap["max_batch"] == 4
        meta = types.SimpleNamespace(
            global_iteration=0, started_at=1.0, completed_at=2.0,
            uplink_bytes={})
        record = coll.assemble_round(meta)
        assert record["serving"]["queue_depth"] == 0
    finally:
        gw.shutdown()
    assert coll.serving_probe is None


def test_perf_waterfall_unreadable_input_exits_2(tmp_path, capsys):
    """A missing or corrupt experiment.json path exits 2 with a clean
    stderr message, never a traceback."""
    from metisfl_tpu import perf

    assert perf.main([str(tmp_path / "nope-experiment.json")]) == 2
    torn = tmp_path / "torn.json"
    torn.write_text('{"round_metadata": [')
    assert perf.main([str(torn)]) == 2
    err = capsys.readouterr().err
    assert "cannot read round profiles" in err
    assert "Traceback" not in err


def test_leave_detaches_peer_and_membership_gates_attribution(
        clean_telemetry, tmp_path):
    """Late RPC/decode activity for a departed learner must not re-mint
    the series leave() pruned: the proxy's peer label is cleared before
    the prune, and the service layer's decode attribution is gated on
    current membership (Controller.is_member)."""
    from metisfl_tpu.comm.rpc import RpcClient
    from metisfl_tpu.controller.core import LearnerRecord
    from metisfl_tpu.controller.service import RpcLearnerProxy

    ctrl = _profile_controller()
    try:
        ctrl.set_community_model(pack_model(_seed_model()))
        for i in range(2):
            ctrl.join(JoinRequest(hostname="h", port=7700 + i,
                                  num_train_examples=10))
        lids = sorted(ctrl.active_learners())
        assert ctrl.is_member(lids[0]) and ctrl.is_member(lids[1])

        record = LearnerRecord(learner_id=lids[0], hostname="localhost",
                               port=7999, auth_token="t",
                               num_train_examples=10)
        proxy = RpcLearnerProxy(record)
        assert proxy._client.peer == lids[0]
        proxy.detach_peer()
        assert proxy._client.peer == ""
        # a detached client records no peer series even if a late
        # callback fires after the prune
        proxy._client._count_bytes(100, "sent")
        parsed = telemetry.parse_exposition(telemetry.render_metrics())
        assert not any(("peer", lids[0]) in k
                       for k in parsed.get("rpc_peer_bytes_total", {}))

        with ctrl._lock:
            token = ctrl._learners[lids[0]].auth_token
        assert ctrl.leave(lids[0], token)
        assert not ctrl.is_member(lids[0])
    finally:
        ctrl.shutdown()


def test_collector_close_releases_sink_handle(tmp_path):
    """Controller shutdown closes the JSONL sink fd (one collector per
    controller incarnation — failover/resume loops must not leak)."""
    coll = tprofile.ProfileCollector(telemetry_dir=str(tmp_path))
    meta = types.SimpleNamespace(global_iteration=0, started_at=1.0,
                                 completed_at=2.0, uplink_bytes={})
    coll.persist(coll.assemble_round(meta))
    assert coll._fh is not None
    coll.close()
    assert coll._fh is None
    coll.close()  # idempotent
    # persist after close reopens — correctness never depends on close
    coll.persist({"round": 1, "phases": {}})
    assert sum(1 for _ in open(coll.profiles_path())) == 2
    coll.close()
