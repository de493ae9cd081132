"""Fleet telemetry fabric (ISSUE 11): cursor-pull CollectTelemetry on
every role, NTP-style skew correction, fleet-merged metrics, live trace
streaming, and the churn posture (stale peers never break collection).

Layers under test, bottom up: ClockSync units (asymmetric RTT, drifting
offset, EWMA convergence, RTT-gate outlier rejection), the trace/journal
cursor APIs, the fleet metrics merge (single-peer bit-identity pin),
cursor resume across a peer restart (epoch reset), the real-gRPC
exporter/collector loop with injected clock skew, and the DriverSession
acceptance federation: controller + 2 subprocess learners with ±500 ms
artificial skew corrected to within the measured RTT bound, one learner
killed mid-run leaving the collector live with the peer marked stale.
"""

import json
import logging
import os
import socket
import time

import numpy as np
import pytest

from metisfl_tpu import telemetry
from metisfl_tpu.telemetry import events as tevents
from metisfl_tpu.telemetry import fabric as tfabric
from metisfl_tpu.telemetry import metrics as tmetrics
from metisfl_tpu.telemetry import trace as ttrace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture
def clean_fabric():
    tmetrics.set_enabled(True)
    tmetrics.registry().reset()
    tevents.configure(enabled=True, service="test", dir="", ring_size=512)
    tevents.journal().reset()
    ttrace.configure(enabled=True, service="test", dir="")
    tfabric.configure(enabled=True)
    yield
    tfabric.configure(enabled=True)
    tevents.journal().reset()
    tmetrics.registry().reset()


# --------------------------------------------------------------------- #
# ClockSync units
# --------------------------------------------------------------------- #

def _exchange(true_offset, out_delay, back_delay, t0=1000.0):
    """One NTP quadruple for a peer whose clock runs ``true_offset``
    ahead, with asymmetric path delays."""
    t1 = t0 + out_delay + true_offset
    t2 = t1 + 0.001  # 1ms server handling
    t3 = (t2 - true_offset) + back_delay
    return t0, t1, t2, t3


def test_clock_sync_symmetric_exchange_recovers_offset():
    sync = tfabric.ClockSync()
    for i in range(5):
        assert sync.observe(*_exchange(0.5, 0.01, 0.01, t0=1000.0 + i))
    assert abs(sync.offset_s - 0.5) < 1e-6
    # measured rtt excludes the peer's handling time (t2 - t1)
    assert sync.best_rtt_s == pytest.approx(0.020, abs=1e-6)


def test_clock_sync_asymmetric_rtt_error_bounded_by_half_rtt():
    sync = tfabric.ClockSync()
    # fully asymmetric path: 2ms out, 40ms back — worst case for the
    # midpoint estimator, error must stay within rtt/2
    for i in range(8):
        sync.observe(*_exchange(0.5, 0.002, 0.040, t0=1000.0 + i))
    rtt = 0.002 + 0.040  # handling time (t2 - t1) is excluded
    assert abs(sync.offset_s - 0.5) <= rtt / 2.0 + 1e-9
    assert sync.bound_s() <= rtt / 2.0 + 1e-9


def test_clock_sync_ewma_tracks_drifting_offset():
    sync = tfabric.ClockSync(alpha=0.4)
    for i in range(10):
        sync.observe(*_exchange(0.1, 0.005, 0.005, t0=1000.0 + i))
    assert sync.offset_s == pytest.approx(0.1, abs=1e-6)
    # the remote clock drifts to +0.2: the EWMA must converge there,
    # smoothly (strictly monotone toward the new offset)
    last = sync.offset_s
    for i in range(20):
        sync.observe(*_exchange(0.2, 0.005, 0.005, t0=2000.0 + i))
        assert sync.offset_s >= last - 1e-9
        last = sync.offset_s
    assert sync.offset_s == pytest.approx(0.2, abs=0.005)


def test_clock_sync_rtt_gate_rejects_outlier_samples():
    sync = tfabric.ClockSync(rtt_gate=3.0)
    for i in range(5):
        sync.observe(*_exchange(0.5, 0.005, 0.005, t0=1000.0 + i))
    before = sync.offset_s
    # a congested exchange: 400ms one-way queueing with a garbage
    # midpoint — the gate must reject it, estimate unmoved
    accepted = sync.observe(*_exchange(0.5, 0.4, 0.002, t0=2000.0))
    assert not accepted
    assert sync.rejected == 1
    assert sync.offset_s == before
    # a clean sample afterwards is accepted again
    assert sync.observe(*_exchange(0.5, 0.005, 0.005, t0=3000.0))


# --------------------------------------------------------------------- #
# cursor APIs (trace ring + journal)
# --------------------------------------------------------------------- #

def test_trace_span_ring_cursor(clean_fabric):
    # the seq counter deliberately survives reconfigures (collector
    # cursors stay monotone): anchor on the live cursor, not 0 — an
    # earlier test in the same process may have recorded ring spans
    _, base, _ = ttrace.spans_since(0)
    for i in range(4):
        ttrace.event(f"work/{i}", 0.001)
    batch, cursor, lost = ttrace.spans_since(base)
    assert [r["name"] for r in batch] == [f"work/{i}" for i in range(4)]
    assert cursor == batch[-1]["seq"] and lost == 0
    # incremental: only the new span comes back, cursor advances
    ttrace.event("work/4", 0.001)
    batch2, cursor2, _ = ttrace.spans_since(cursor)
    assert [r["name"] for r in batch2] == ["work/4"]
    assert cursor2 > cursor
    # idempotent at the tip
    batch3, cursor3, _ = ttrace.spans_since(cursor2)
    assert batch3 == [] and cursor3 == cursor2


def test_trace_ring_eviction_is_reported_not_silent(clean_fabric):
    """A too-slow pull against a too-small ring loses records — the
    loss count comes back with the batch (the collector logs it)."""
    ttrace.configure_ring(4)
    # the seq counter deliberately survives reconfigures: anchor on it
    _, base, _ = ttrace.spans_since(0)
    for i in range(10):
        ttrace.event(f"work/{i}", 0.001)
    batch, cursor, lost = ttrace.spans_since(base)
    assert [r["name"] for r in batch] == [f"work/{i}" for i in range(6, 10)]
    assert lost == 6
    # a caught-up cursor reports no loss
    _, _, lost2 = ttrace.spans_since(cursor)
    assert lost2 == 0


def test_trace_ring_disabled_with_fabric_optout(clean_fabric):
    tfabric.configure(enabled=False)
    ttrace.event("work/off", 0.001)
    batch, cursor, lost = ttrace.spans_since(0)
    assert batch == [] and cursor == 0 and lost == 0


def test_events_tail_since(clean_fabric):
    for i in range(3):
        tevents.emit(tevents.RoundStarted, round=i)
    tail = tevents.tail_since(0)
    assert [r["round"] for r in tail] == [0, 1, 2]
    assert tevents.tail_since(tail[-1]["seq"]) == []
    tevents.emit(tevents.RoundStarted, round=3)
    fresh = tevents.tail_since(tail[-1]["seq"])
    assert [r["round"] for r in fresh] == [3]


# --------------------------------------------------------------------- #
# fleet metrics merge
# --------------------------------------------------------------------- #

def _populate_registry():
    reg = tmetrics.registry()
    c = reg.counter("fab_test_requests_total", "reqs", ("op",))
    c.inc(3.5, op="read")
    c.inc(2, op="write")
    g = reg.gauge("fab_test_depth", "depth", ("chan",))
    g.set(7.25, chan="a")
    g.set(-1.5, chan="b")
    h = reg.histogram("fab_test_latency_seconds", "lat", ("op",))
    for v in (0.002, 0.03, 1.7):
        h.observe(v, op="read")
    # a budget-collapsed per-learner family: the sketch shape
    reg.set_cardinality_budget(8)
    fleet = reg.gauge("fab_test_score", "scores", ("learner",),
                      budget_label="learner")
    rng = np.random.default_rng(5)
    for i in range(32):
        fleet.set(float(rng.gamma(4.0, 0.25)), learner=f"L{i}")
    assert fleet.collapsed()
    return reg


def test_single_peer_fleet_merge_is_bit_identical(clean_fabric):
    """The acceptance pin: a single-peer fleet merge must render
    byte-for-byte identically to that peer's own exposition — exact
    families, histograms, AND budget-collapsed sketch families."""
    reg = _populate_registry()
    merged = tfabric.merge_metrics_states([reg.collect_state()])
    assert merged.render() == reg.render()


def test_two_peer_merge_counters_sum_gauges_max_sketches_merge(
        clean_fabric):
    peer_a = [
        {"name": "reqs_total", "kind": "counter", "help": "h",
         "labels": ["op"], "budget_label": "",
         "series": [[["read"], 3.0], [["write"], 1.0]]},
        {"name": "depth", "kind": "gauge", "help": "h", "labels": ["c"],
         "budget_label": "", "series": [[["q"], 5.0]]},
        {"name": "lat", "kind": "histogram", "help": "h", "labels": [],
         "budget_label": "", "buckets": [0.1, 1.0],
         "cells": [[[], [1.0, 2.0, 2.0, 0.25]]]},
    ]
    peer_b = [
        {"name": "reqs_total", "kind": "counter", "help": "h",
         "labels": ["op"], "budget_label": "",
         "series": [[["read"], 4.0]]},
        {"name": "depth", "kind": "gauge", "help": "h", "labels": ["c"],
         "budget_label": "", "series": [[["q"], 2.0]]},
        {"name": "lat", "kind": "histogram", "help": "h", "labels": [],
         "budget_label": "", "buckets": [0.1, 1.0],
         "cells": [[[], [0.0, 1.0, 1.0, 0.5]]]},
    ]
    merged = tfabric.merge_metrics_states([peer_a, peer_b])
    reqs = merged.get("reqs_total")
    assert reqs.value(op="read") == 7.0      # counters sum
    assert reqs.value(op="write") == 1.0
    assert merged.get("depth").value(c="q") == 5.0  # gauges max
    lat = merged.get("lat")
    assert lat.count() == 3.0                # histogram cells add
    assert lat.sum() == 0.75

    # collapsed families: sketch merge — quantiles over BOTH streams
    reg_a, reg_b = tmetrics.Registry(), tmetrics.Registry()
    for reg, lo in ((reg_a, 0.0), (reg_b, 100.0)):
        reg.set_cardinality_budget(4)
        fam = reg.gauge("score", "h", ("learner",),
                        budget_label="learner")
        for i in range(16):
            fam.set(lo + i, learner=f"{lo}-L{i}")
    fleet = tfabric.merge_metrics_states(
        [reg_a.collect_state(), reg_b.collect_state()])
    fam = fleet.get("score")
    assert fam.collapsed()
    assert fam.series_count() == 32          # distinct counts sum
    q50 = fam.quantile(0.5)
    assert 10.0 < q50 < 105.0                # spans both streams
    assert fam.quantile(0.99) > 100.0        # high stream visible


# --------------------------------------------------------------------- #
# exporter handler: cursors, epoch reset, opt-out
# --------------------------------------------------------------------- #

def _pull(handler, epoch="", ev=0, sp=0, metrics=True):
    raw = handler(json.dumps({"epoch": epoch, "events_cursor": ev,
                              "spans_cursor": sp,
                              "metrics": metrics}).encode())
    return json.loads(raw.decode())


def test_collect_handler_cursor_resume_no_duplicates(clean_fabric):
    handler = lambda raw: tfabric.handle_collect(raw, "svc", "learner")  # noqa: E731
    for i in range(3):
        tevents.emit(tevents.RoundStarted, round=i)
        ttrace.event(f"w/{i}", 0.001)
    r1 = _pull(handler)
    assert len(r1["events"]) == 3 and len(r1["spans"]) == 3
    tevents.emit(tevents.RoundStarted, round=3)
    ttrace.event("w/3", 0.001)
    r2 = _pull(handler, epoch=r1["epoch"], ev=r1["events_cursor"],
               sp=r1["spans_cursor"])
    # exactly the new records, no duplicates
    assert [e["round"] for e in r2["events"]] == [3]
    assert [s["name"] for s in r2["spans"]] == ["w/3"]
    r3 = _pull(handler, epoch=r2["epoch"], ev=r2["events_cursor"],
               sp=r2["spans_cursor"])
    assert r3["events"] == [] and r3["spans"] == []


def test_collect_handler_epoch_change_resets_cursors(clean_fabric):
    """A restarted peer (fresh epoch, fresh rings) must serve from the
    start even when the caller presents large stale cursors — no
    silently skipped records, no duplicates."""
    handler = lambda raw: tfabric.handle_collect(raw, "svc", "learner")  # noqa: E731
    for i in range(5):
        tevents.emit(tevents.RoundStarted, round=i)
        ttrace.event(f"old/{i}", 0.001)
    r1 = _pull(handler)
    old_epoch = r1["epoch"]
    # "restart": new epoch, journal seq restarts, span ring cleared
    tfabric.configure(enabled=True, new_epoch=True)
    tevents.journal().reset()
    ttrace.configure(enabled=True, service="test", dir="")
    for i in range(2):
        tevents.emit(tevents.RoundStarted, round=100 + i)
        ttrace.event(f"fresh/{i}", 0.001)
    r2 = _pull(handler, epoch=old_epoch, ev=r1["events_cursor"],
               sp=r1["spans_cursor"])
    assert r2["epoch"] != old_epoch
    assert [e["round"] for e in r2["events"]] == [100, 101]
    assert [s["name"] for s in r2["spans"]] == ["fresh/0", "fresh/1"]
    # and the resumed cursors keep working against the new incarnation
    r3 = _pull(handler, epoch=r2["epoch"], ev=r2["events_cursor"],
               sp=r2["spans_cursor"])
    assert r3["events"] == [] and r3["spans"] == []


def test_disabled_fabric_serves_stub(clean_fabric):
    tfabric.configure(enabled=False)
    reply = json.loads(
        tfabric.handle_collect(b"", "svc", "learner").decode())
    assert reply == {"enabled": False}


def test_fabric_metric_constants_match_module():
    assert telemetry.M_FABRIC_COLLECTIONS_TOTAL == \
        tfabric.FABRIC_COLLECTIONS_TOTAL
    assert telemetry.M_FABRIC_PEER_OFFSET_MS == tfabric.FABRIC_PEER_OFFSET_MS
    assert telemetry.M_FABRIC_COLLECT_SECONDS == \
        tfabric.FABRIC_COLLECT_SECONDS


# --------------------------------------------------------------------- #
# collector over real gRPC: skew correction, staleness, health
# --------------------------------------------------------------------- #

def _boot_peer(role="learner", port=0):
    from metisfl_tpu.comm.rpc import BytesService, RpcServer

    server = RpcServer("127.0.0.1", port)
    server.add_service(BytesService(f"fab.{role}", {}, role=role))
    bound = server.start()
    return server, bound


def test_collector_grpc_pull_corrects_injected_skew(clean_fabric,
                                                    monkeypatch):
    """In-process gRPC peer with a +0.5 s injected clock skew: the
    collector's offset estimate lands within the measured RTT bound of
    the truth, and absorbed span timestamps come back on the
    collector's timeline."""
    monkeypatch.setattr(tfabric, "_SKEW_S", 0.5)
    server, port = _boot_peer()
    collector = tfabric.FleetCollector(probe_health=False)
    try:
        true_start = time.time()
        ttrace.event("peer.work", 0.002)
        peer = collector.add_peer("p0", "127.0.0.1", port, "fab.learner",
                                  role="learner")
        for _ in range(4):
            assert collector.collect_peer(peer) == "ok"
        bound = max(peer.clock.best_rtt_s, 0.05)
        assert abs(peer.clock.offset_s - 0.5) <= bound
        spans = collector.spans()
        mine = [s for s in spans if s["name"] == "peer.work"]
        assert mine and mine[0]["peer"] == "p0"
        # corrected onto the collector clock: within the bound of the
        # true local start, NOT 0.5s in the future
        assert abs(mine[0]["start"] - true_start) <= bound + 0.05
        assert mine[0].get("clock_offset_ms", 0.0) == pytest.approx(
            500.0, abs=bound * 1e3 + 50)
    finally:
        collector.stop(final_poll=False)
        server.stop(grace=0.1)


def test_collector_marks_dead_peer_stale_and_never_raises(clean_fabric):
    collector = tfabric.FleetCollector(probe_health=False)
    live_server, live_port = _boot_peer()
    dead_port = _free_port()
    try:
        collector.add_peer("live", "127.0.0.1", live_port, "fab.learner",
                           role="learner")
        collector.add_peer("dead", "127.0.0.1", dead_port, "fab.learner",
                           role="learner")
        for _ in range(3):
            outcomes = collector.poll_once(timeout=2.0)  # must not raise
        assert outcomes.get("ok") == 1 and outcomes.get("error") == 1
        dead = next(p for p in collector.peers() if p.name == "dead")
        live = next(p for p in collector.peers() if p.name == "live")
        assert dead.stale and not live.stale
        kinds = [e["kind"] for e in tevents.tail()]
        assert "fabric_peer_stale" in kinds
        # the snapshot keeps the stale row, marked
        snap = collector.snapshot()
        rows = {p["peer"]: p for p in snap["peers"]}
        assert rows["dead"]["stale"] and rows["live"]["live"]
    finally:
        collector.stop(final_poll=False)
        live_server.stop(grace=0.1)


def test_disabled_peer_reports_disabled_not_stale(clean_fabric):
    tfabric.configure(enabled=False)
    server, port = _boot_peer()
    collector = tfabric.FleetCollector(probe_health=False)
    try:
        peer = collector.add_peer("p", "127.0.0.1", port, "fab.learner",
                                  role="learner")
        assert collector.collect_peer(peer) == "disabled"
        assert peer.disabled and not peer.stale
    finally:
        collector.stop(final_poll=False)
        server.stop(grace=0.1)


def test_probe_health_serving_not_serving_unreachable(clean_fabric):
    from metisfl_tpu.comm.health import (NOT_SERVING, HealthServicer,
                                         probe_health)
    from metisfl_tpu.comm.rpc import BytesService, RpcServer

    server = RpcServer("127.0.0.1", 0)
    servicer = HealthServicer()
    server.add_service(servicer.service())
    server.add_service(BytesService("fab.x", {}, role="learner"))
    port = server.start()
    try:
        assert probe_health("127.0.0.1", port) == "SERVING"
        servicer.set_all(NOT_SERVING)
        assert probe_health("127.0.0.1", port) == "NOT_SERVING"
    finally:
        server.stop(grace=0.1)
    assert probe_health("127.0.0.1", port) == "UNREACHABLE"


def test_render_fleet_screen(clean_fabric):
    from metisfl_tpu.status import render_fleet

    snap = {
        "live": 2, "polls": 7,
        "peers": [
            {"peer": "controller", "role": "controller",
             "target": "h:1", "health": "SERVING", "live": True,
             "stale": False, "offset_ms": 0.1, "rtt_ms": 1.2,
             "spans": 10, "events": 5},
            {"peer": "learner-a", "role": "learner", "target": "h:2",
             "health": "UNREACHABLE", "live": False, "stale": True,
             "offset_ms": 500.0, "rtt_ms": 2.0, "spans": 4, "events": 2},
        ],
        "families": {"rounds_total": {"kind": "counter", "series": 1,
                                      "total": 3.0}},
        "spans": [
            {"span": "a", "parent": "", "name": "round", "start": 10.0,
             "dur_ms": 1500.0, "service": "controller"},
            {"span": "b", "parent": "a", "name": "learner.train",
             "start": 10.2, "dur_ms": 900.0, "service": "learner",
             "peer": "learner-a"},
        ],
        "events": [{"kind": "round_started", "ts": 10.0, "seq": 1,
                    "round": 1}],
    }
    screen = render_fleet(snap)
    assert "fleet: 2/2 peers live" in screen
    assert "STALE" in screen and "SERVING" in screen
    assert "rounds_total=3" in screen
    assert "learner.train" in screen and "@learner-a]" in screen
    assert "+   0.200s" in screen  # corrected relative timeline


def test_status_fleet_once_against_live_controller(clean_fabric, capsys):
    """``status --fleet --once`` end to end: a gRPC-served controller is
    discovered, pulled over CollectTelemetry, health-probed, and the
    merged fleet screen renders with its spans on the corrected clock."""
    from metisfl_tpu import status as status_cli
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (EvalConfig, FederationConfig,
                                    TerminationConfig)
    from metisfl_tpu.controller.core import Controller
    from metisfl_tpu.controller.service import ControllerServer

    config = FederationConfig(
        train=TrainParams(batch_size=4, local_steps=1),
        eval=EvalConfig(every_n_rounds=0),
        termination=TerminationConfig(federation_rounds=1),
    )
    controller = Controller(config, proxy_factory=lambda record: None)
    server = ControllerServer(controller, host="127.0.0.1", port=0)
    port = server.start()
    ttrace.configure(enabled=True, service="controller", dir="")
    ttrace.event("ctrl.work", 0.003)
    try:
        rc = status_cli.main(["--host", "127.0.0.1", "--port", str(port),
                              "--fleet", "--once"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "fleet: 1/1 peers live" in out
        assert "controller" in out and "SERVING" in out
        assert "ctrl.work" in out  # the pulled span rendered
    finally:
        server.stop()


def test_template_documents_fabric_defaults():
    """Template pins: the documented telemetry.fabric block must match
    the dataclass defaults (the doc is the contract)."""
    import yaml

    from metisfl_tpu.config import FabricConfig

    path = os.path.join(REPO, "examples", "config", "template.yaml")
    with open(path) as fh:
        data = yaml.safe_load(fh)
    block = data["telemetry"]["fabric"]
    defaults = FabricConfig()
    assert block["enabled"] == defaults.enabled
    assert block["poll_every_s"] == defaults.poll_every_s
    assert block["jitter"] == defaults.jitter
    assert block["offset_alpha"] == defaults.offset_alpha
    assert block["rtt_gate"] == defaults.rtt_gate
    assert block["span_ring"] == defaults.span_ring


def test_fabric_config_validation():
    from metisfl_tpu.config import FabricConfig, FederationConfig, \
        TelemetryConfig

    for bad in ({"poll_every_s": 0.0}, {"jitter": 1.0},
                {"offset_alpha": 0.0}, {"rtt_gate": 0.5},
                {"span_ring": -1}):
        with pytest.raises(ValueError):
            FederationConfig(telemetry=TelemetryConfig(
                fabric=FabricConfig(**bad)))
    FederationConfig(telemetry=TelemetryConfig(fabric=FabricConfig()))


# --------------------------------------------------------------------- #
# acceptance: real-gRPC federation, ±500 ms skew, mid-run kill
# --------------------------------------------------------------------- #

def test_fleet_collection_on_real_federation_with_skew(tmp_path, caplog,
                                                       clean_fabric):
    """The ISSUE 11 acceptance run: controller + 2 subprocess learners
    over real gRPC, learners launched with a +500 ms artificial clock
    skew. The driver's live FleetCollector must assemble one merged
    span timeline containing spans from every process on a corrected
    clock (learner offsets measured ~0.5 s, corrected to within the
    measured RTT bound), stream it into traces.jsonl DURING the run,
    mark a killed learner stale without dropping collection, and log
    the RPC-pulled / file-merged / unreachable coverage split."""
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (AggregationConfig, EvalConfig,
                                    FabricConfig, FederationConfig,
                                    TelemetryConfig, TerminationConfig)
    from metisfl_tpu.driver.session import DriverSession, \
        _terminate_process
    from metisfl_tpu.models import ArrayDataset, FlaxModelOps
    from metisfl_tpu.models.zoo import MLP

    rng = np.random.default_rng(23)
    w = rng.standard_normal((4, 2)).astype(np.float32)

    def make_recipe(seed):
        x = rng.standard_normal((32, 4)).astype(np.float32)
        y = np.argmax(x @ w, -1).astype(np.int32)

        def recipe():
            ops = FlaxModelOps(MLP(features=(8,), num_outputs=2),
                               np.zeros((2, 4), np.float32), rng_seed=0)
            return ops, ArrayDataset(x, y, seed=seed)

        return recipe

    template = FlaxModelOps(MLP(features=(8,), num_outputs=2),
                            np.zeros((2, 4), np.float32),
                            rng_seed=0).get_variables()
    config = FederationConfig(
        controller_port=_free_port(),
        round_deadline_secs=60.0,
        aggregation=AggregationConfig(scaler="participants"),
        train=TrainParams(batch_size=8, local_steps=2, learning_rate=0.1),
        eval=EvalConfig(every_n_rounds=0),
        termination=TerminationConfig(federation_rounds=2,
                                      execution_cutoff_mins=5.0),
        telemetry=TelemetryConfig(
            fabric=FabricConfig(poll_every_s=0.5, jitter=0.1)),
    )
    session = DriverSession(
        config, template, [make_recipe(0), make_recipe(1)],
        workdir=str(tmp_path),
        # the ±500 ms acceptance skew, injected per subprocess: learner
        # clocks run half a second ahead of the driver + controller
        learner_env={tfabric.SKEW_ENV_VAR: "0.5"})
    try:
        session.initialize_federation()
        fleet = session.fleet_collector()
        assert fleet is not None
        session.monitor_federation(poll_every_s=1.0,
                                   eval_drain_timeout_s=0)

        # give the collector one explicit sweep at termination
        fleet.poll_once(timeout=10.0)
        peers = {p.name: p for p in fleet.peers()}
        learner_peers = [p for p in peers.values() if p.role == "learner"]
        assert "controller" in peers and len(learner_peers) == 2

        # skew measured and corrected within the measured RTT bound
        for peer in learner_peers:
            assert peer.clock.samples >= 1
            bound = max(peer.clock.best_rtt_s, 0.05)
            assert abs(peer.clock.offset_s - 0.5) <= bound, (
                peer.name, peer.clock.offset_s, peer.clock.best_rtt_s)
        ctrl = peers["controller"]
        assert abs(ctrl.clock.offset_s) <= max(ctrl.clock.best_rtt_s, 0.05)

        # one merged timeline with spans from EVERY process, corrected:
        # learner train spans must land inside the controller's round
        # window (uncorrected they would float ~0.5 s outside it)
        spans = fleet.spans()
        services = {s.get("service") for s in spans}
        assert "controller" in services
        learner_services = {s for s in services
                            if s and s.startswith("learner")}
        assert len(learner_services) >= 2, services
        ctrl_spans = [s for s in spans if s.get("service") == "controller"]
        window_lo = min(s["start"] for s in ctrl_spans)
        window_hi = max(s["start"] + s.get("dur_ms", 0.0) / 1e3
                        for s in ctrl_spans)
        train_spans = [s for s in spans
                       if s.get("service") in learner_services
                       and "train" in s.get("name", "")]
        assert train_spans
        for s in train_spans:
            assert window_lo - 0.25 <= s["start"] <= window_hi + 0.25, (
                s["name"], s["start"], window_lo, window_hi)

        # live, crash-durable: traces.jsonl exists and holds corrected
        # fleet spans BEFORE shutdown's collect_traces pass
        trace_path = os.path.join(str(tmp_path), "traces.jsonl")
        assert os.path.exists(trace_path)
        streamed = [json.loads(line) for line in open(trace_path)]
        assert any(s.get("peer") for s in streamed)

        # kill one learner mid-flight: collection stays live, the peer
        # goes stale, nothing raises
        victim = next(p for p in session._procs
                      if p.name.startswith("learner_1"))
        _terminate_process(victim.process)
        for _ in range(3):
            fleet.poll_once(timeout=3.0)
        stale = [p for p in fleet.peers()
                 if p.role == "learner" and p.stale]
        assert len(stale) == 1
        assert not peers["controller"].stale
    finally:
        with caplog.at_level(logging.INFO, logger="metisfl_tpu.driver"):
            session.shutdown_federation()
    coverage = [r.message for r in caplog.records
                if "trace collection:" in r.message]
    assert coverage, "collect_traces must log the coverage split"
    assert "RPC-pulled" in coverage[0]
    # the killed learner is named as unreachable, not silently skipped
    assert stale[0].name in coverage[0]
