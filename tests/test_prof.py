"""Continuous profiling plane (ISSUE 13): fleet-wide stack sampling,
lock-contention telemetry, and differential flamegraphs.

Layers under test, bottom up: frame folding + the synchronous sampler
(deterministic hot-frame capture), the instrumented lock wrappers
(contended vs uncontended accounting, RLock reentrancy, Condition wait
NOT counted as contention), the opt-out pins (raw locks + stub reply +
no sampler thread), the CollectTelemetry prof section and the
FleetCollector's per-peer absorption + peer-prefixed merge + dump, the
RoundProfile per-round stack delta, perf --flame / --flame-diff
(including the injected lock-hold differential), post-mortem prof
snapshots, config validation + template pins, and the DriverSession
acceptance federation (controller + 2 learners + 2 slice aggregators
over real gRPC with per-peer hot-frame attribution).
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from metisfl_tpu import telemetry
from metisfl_tpu.telemetry import events as tevents
from metisfl_tpu.telemetry import fabric as tfabric
from metisfl_tpu.telemetry import metrics as tmetrics
from metisfl_tpu.telemetry import prof as tprof
from metisfl_tpu.telemetry import trace as ttrace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture
def clean_prof():
    tmetrics.set_enabled(True)
    tmetrics.registry().reset()
    tevents.configure(enabled=True, service="test", dir="", ring_size=512)
    ttrace.configure(enabled=True, service="test", dir="")
    tfabric.configure(enabled=True)
    tprof.reset()
    yield
    tprof.reset()
    tprof.configure(enabled=False)
    tfabric.configure(enabled=True)
    tmetrics.registry().reset()


def _burn(stop, shape=(512, 512), ready=None):
    """A busy thread parked inside the aggregation fold kernel — the
    'known hot frame' the sampler must catch. The kernel import (jax,
    seconds when cold) happens BEFORE ``ready`` is signalled so the
    sampling window never spends itself watching importlib."""
    from metisfl_tpu.aggregation.base import np_stacked_scaled_add

    rng = np.random.default_rng(0)
    model = {"w": rng.standard_normal(shape).astype(np.float32)}
    if ready is not None:
        ready.set()
    while not stop.is_set():
        np_stacked_scaled_add(None, [model] * 4, [0.25] * 4)


def _start_burn(stop):
    ready = threading.Event()
    thread = threading.Thread(target=_burn, args=(stop,),
                              kwargs={"ready": ready}, daemon=True)
    thread.start()
    assert ready.wait(60.0), "fold kernel import never finished"
    return thread


def _sample_until(predicate, ticks=400):
    """Synchronous sampling loop (deterministic — no daemon timing):
    tick until the predicate over the folded table holds."""
    for _ in range(ticks):
        tprof.sample_once()
        folded = tprof.folded_counts(tprof.collect_state())
        if predicate(folded):
            return folded
    return tprof.folded_counts(tprof.collect_state())


# --------------------------------------------------------------------- #
# sampler units
# --------------------------------------------------------------------- #

def test_sampler_catches_hot_fold_frame(clean_prof):
    tprof.configure(enabled=True)
    stop = threading.Event()
    thread = _start_burn(stop)
    try:
        folded = _sample_until(
            lambda f: any("np_stacked_scaled_add" in s for s in f))
    finally:
        stop.set()
        thread.join()
    hot = [s for s in folded if "np_stacked_scaled_add" in s]
    assert hot, f"fold kernel never sampled: {list(folded)[:5]}"
    # folded format: root-first, module-qualified, prefix stripped
    assert any(s.startswith("threading._bootstrap;") for s in hot)
    assert "metisfl_tpu" not in hot[0]
    state = tprof.collect_state()
    assert state["enabled"] and state["samples"] > 0
    # the sampler's own counter family moved
    assert tmetrics.registry().get(
        telemetry.M_PROF_SAMPLES_TOTAL).total() > 0


def test_frame_table_self_total_semantics(clean_prof):
    folded = {"a;b;c": 10.0, "a;b": 5.0, "a;d": 3.0}
    rows = {r["frame"]: r for r in tprof.frame_table(folded)}
    assert rows["c"]["self"] == 10.0 and rows["c"]["total"] == 10.0
    assert rows["b"]["self"] == 5.0 and rows["b"]["total"] == 15.0
    assert rows["a"]["self"] == 0.0 and rows["a"]["total"] == 18.0
    assert rows["a"]["total_pct"] == pytest.approx(100.0)
    # self-descending order
    ordered = tprof.frame_table(folded)
    assert ordered[0]["frame"] == "c"


def test_sampler_budget_bounds_table(clean_prof):
    tprof.configure(enabled=True, budget=16)
    state = tprof.collect_state()
    assert state["budget"] == 16
    assert state["stacks"]["capacity"] == 16


def test_delta_between_snapshots(clean_prof):
    tprof.configure(enabled=True)
    before = dict(tprof.counts_snapshot())
    stop = threading.Event()
    thread = _start_burn(stop)
    try:
        _sample_until(
            lambda f: any("np_stacked_scaled_add" in s for s in f))
    finally:
        stop.set()
        thread.join()
    delta = tprof.delta(before)
    assert delta["samples"] > 0
    assert any("np_stacked_scaled_add" in stack
               for stack, _count in delta["stacks"])


# --------------------------------------------------------------------- #
# lock-contention telemetry
# --------------------------------------------------------------------- #

def test_contended_acquire_records_wait_and_metrics(clean_prof):
    lk = tprof.lock("t.site")
    holder_in = threading.Event()

    def holder():
        with lk:
            holder_in.set()
            time.sleep(0.12)

    thread = threading.Thread(target=holder)
    thread.start()
    assert holder_in.wait(2.0)
    t0 = time.perf_counter()
    with lk:
        waited = time.perf_counter() - t0
    thread.join()
    assert waited >= 0.05
    sites = tprof.lock_sites()
    row = sites["t.site"]
    assert row["contentions"] == 1
    assert row["acquisitions"] == 2
    assert row["wait_s_total"] >= 0.05
    assert row["wait_s_max"] == pytest.approx(row["wait_s_total"])
    wait_hist = tmetrics.registry().get(telemetry.M_LOCK_WAIT_SECONDS)
    assert wait_hist.count(site="t.site") == 1
    assert wait_hist.sum(site="t.site") >= 0.05
    cont = tmetrics.registry().get(telemetry.M_LOCK_CONTENTION_TOTAL)
    assert cont.value(site="t.site") == 1


def test_uncontended_acquires_never_observe(clean_prof):
    lk = tprof.lock("t.quiet")
    for _ in range(50):
        with lk:
            pass
    row = tprof.lock_sites()["t.quiet"]
    assert row["acquisitions"] == 50
    assert row["contentions"] == 0 and row["wait_s_total"] == 0.0
    wait_hist = tmetrics.registry().get(telemetry.M_LOCK_WAIT_SECONDS)
    assert wait_hist.count(site="t.quiet") == 0


def test_rlock_reentrancy_is_not_contention(clean_prof):
    lk = tprof.rlock("t.rlock")
    with lk:
        with lk:  # reentrant: must not deadlock, must not count
            pass
    row = tprof.lock_sites()["t.rlock"]
    assert row["acquisitions"] == 2
    assert row["contentions"] == 0


def test_condition_wait_is_not_lock_contention(clean_prof):
    cond = threading.Condition(tprof.lock("t.cond"))
    done = threading.Event()

    def waiter():
        with cond:
            cond.wait(timeout=5.0)
        done.set()

    thread = threading.Thread(target=waiter)
    thread.start()
    time.sleep(0.15)  # waiter is parked in wait() with the lock RELEASED
    with cond:
        cond.notify()
    assert done.wait(2.0)
    thread.join()
    row = tprof.lock_sites()["t.cond"]
    # the 150ms park must NOT read as lock wait; any residual handoff
    # contention is micro-scale
    assert row["wait_s_total"] < 0.05


def test_nonblocking_and_locked_protocol(clean_prof):
    lk = tprof.lock("t.proto")
    assert lk.acquire(False)
    assert lk.locked()
    assert not lk.acquire(False)
    lk.release()
    assert not lk.locked()


def test_lock_object_test_hook(clean_prof):
    lk = tprof.lock("t.hook")
    assert tprof.lock_object("t.hook") is lk
    assert tprof.lock_object("never.registered") is None


# --------------------------------------------------------------------- #
# opt-out pins (the one-attribute-check acceptance)
# --------------------------------------------------------------------- #

def test_disabled_prof_returns_raw_locks_and_stub(clean_prof):
    tprof.configure(enabled=False)
    assert type(tprof.lock("t.raw")) is type(threading.Lock())
    assert type(tprof.rlock("t.raw")) is type(threading.RLock())
    assert not tprof.sampling()
    assert tprof.collect_state() == {"enabled": False}
    # the CollectTelemetry reply carries the stub, not a table
    reply = json.loads(tfabric.handle_collect(b"{}", "svc", "learner"))
    assert reply["prof"] == {"enabled": False}


def test_apply_config_arms_and_disarms_prof(clean_prof):
    from metisfl_tpu.config import ProfConfig, TelemetryConfig

    telemetry.apply_config(
        TelemetryConfig(prof=ProfConfig(hz=301.0, budget=64)),
        service="cfged")
    try:
        assert tprof.sampling()
        state = tprof.collect_state()
        assert state["hz"] == 301.0 and state["budget"] == 64
    finally:
        telemetry.apply_config(
            TelemetryConfig(prof=ProfConfig(enabled=False)),
            service="cfged")
    assert not tprof.sampling()
    assert type(tprof.lock("t.after")) is type(threading.Lock())


def test_controller_lock_is_raw_when_prof_disabled(clean_prof):
    """The hot-path pin at the adoption site: a store built with
    profiling off uses raw lineage locks (zero wrapper cost)."""
    from metisfl_tpu.store import EvictionPolicy
    from metisfl_tpu.store.memory import InMemoryModelStore

    tprof.configure(enabled=False)
    store = InMemoryModelStore(EvictionPolicy.LINEAGE_LENGTH, 1)
    assert type(store._lock) is type(threading.Lock())
    store.insert("L0", {"w": np.ones(2, np.float32)})
    assert type(store._learner_locks["L0"][0]) is type(threading.Lock())
    tprof.configure(enabled=True)
    store2 = InMemoryModelStore(EvictionPolicy.LINEAGE_LENGTH, 1)
    assert isinstance(store2._lock, tprof._TimedLock)


# --------------------------------------------------------------------- #
# fabric transport + fleet merge
# --------------------------------------------------------------------- #

def test_collect_reply_prof_section_and_summary(clean_prof):
    tprof.configure(enabled=True)
    stop = threading.Event()
    thread = _start_burn(stop)
    try:
        _sample_until(
            lambda f: any("np_stacked_scaled_add" in s for s in f))
    finally:
        stop.set()
        thread.join()
    lk = tprof.lock("t.fab")

    def _hold():
        with lk:
            time.sleep(0.05)

    hold = threading.Thread(target=_hold)
    hold.start()
    time.sleep(0.01)
    with lk:
        pass
    hold.join()
    reply = json.loads(tfabric.handle_collect(b"{}", "svc", "controller"))
    state = reply["prof"]
    assert state["enabled"] and state["samples"] > 0
    assert "t.fab" in state["locks"]
    summary = tprof.summarize_state(state)
    assert summary["samples"] == state["samples"]
    assert summary["top_frame"]
    assert summary.get("top_lock") == "t.fab"
    assert summary["contentions"] >= 1


def test_fleet_collector_absorbs_prof_and_merges_per_peer(clean_prof,
                                                          tmp_path):
    from metisfl_tpu.comm.rpc import BytesService, RpcServer

    tprof.configure(enabled=True)
    stop = threading.Event()
    thread = _start_burn(stop)
    server = RpcServer("127.0.0.1", 0)
    server.add_service(BytesService("prof.peer", {}, role="learner"))
    port = server.start()
    collector = tfabric.FleetCollector(probe_health=False)
    try:
        _sample_until(
            lambda f: any("np_stacked_scaled_add" in s for s in f))
        collector.add_peer("peer-0", "127.0.0.1", port, "prof.peer",
                           role="learner")
        assert collector.collect_peer(
            next(iter(collector.peers()))) == "ok"
        peer = collector.peers()[0]
        assert peer.prof_state and peer.prof_state["samples"] > 0
        merged = collector.merged_folded()
        assert merged and all(k.startswith("peer-0;") for k in merged)
        assert any("np_stacked_scaled_add" in k for k in merged)
        # the status --fleet snapshot carries the per-peer summary
        snap = collector.snapshot()
        assert snap["prof"]["peer-0"]["top_frame"]
        # and the dump is a --flame-renderable artifact
        dump = tmp_path / "prof-fleet.json"
        assert collector.dump_prof(str(dump))
        from metisfl_tpu import perf
        folded = perf.load_folded(str(dump))
        assert any("np_stacked_scaled_add" in k for k in folded)
    finally:
        stop.set()
        thread.join()
        collector.stop(final_poll=False)
        server.stop(grace=0.1)


def test_render_fleet_prof_line(clean_prof):
    from metisfl_tpu.status import render_fleet

    snap = {
        "peers": [], "live": 0, "polls": 1, "families": {},
        "spans": [], "events": [],
        "prof": {"ctrl": {"enabled": True, "samples": 42, "hz": 67.0,
                          "top_frame": "aggregation.base._native_fold",
                          "top_frame_pct": 61.2,
                          "top_lock": "controller.registry",
                          "top_lock_wait_ms": 12.5, "contentions": 3}},
    }
    screen = render_fleet(snap)
    assert "prof: " in screen
    assert "aggregation.base._native_fold" in screen
    assert "controller.registry" in screen


# --------------------------------------------------------------------- #
# per-round delta in RoundProfile
# --------------------------------------------------------------------- #

class _Meta:
    def __init__(self, round_no):
        self.global_iteration = round_no
        self.started_at = time.time() - 0.2
        self.completed_at = time.time()
        self.dispatch_duration_ms = 1.0
        self.wait_duration_ms = 1.0
        self.aggregation_duration_ms = 1.0
        self.uplink_bytes = {}


def test_round_profile_carries_stack_delta(clean_prof):
    from metisfl_tpu.telemetry.profile import ProfileCollector

    tprof.configure(enabled=True)
    collector = ProfileCollector()
    collector.assemble_round(_Meta(1))  # baseline snapshot
    stop = threading.Event()
    thread = _start_burn(stop)
    try:
        _sample_until(
            lambda f: any("np_stacked_scaled_add" in s for s in f))
    finally:
        stop.set()
        thread.join()
    record = collector.assemble_round(_Meta(2))
    assert record["prof"]["samples"] > 0
    assert any("np_stacked_scaled_add" in stack
               for stack, _d in record["prof"]["stacks"])
    # sampler off: no prof section at all (one attribute check pin)
    tprof.configure(enabled=False)
    record3 = collector.assemble_round(_Meta(3))
    assert record3["prof"] == {}


# --------------------------------------------------------------------- #
# perf --flame / --flame-diff
# --------------------------------------------------------------------- #

def test_flame_cli_renders_collapsed_and_table(clean_prof, tmp_path,
                                               capsys):
    from metisfl_tpu import perf

    state = {"enabled": True, "hz": 67.0, "budget": 512, "samples": 30,
             "stacks": {"capacity": 512,
                        "rows": [["a;b;c", 20.0, 0.0, 0.0],
                                 ["a;d", 10.0, 0.0, 0.0]]},
             "locks": {}}
    src = tmp_path / "prof.json"
    src.write_text(json.dumps(state))
    assert perf.main(["--flame", str(src)]) == 0
    out = capsys.readouterr()
    assert "a;b;c 20" in out.out
    assert "self%" in out.err and "c" in out.err
    # --out writes the collapsed file and prints the table to stdout
    folded_path = tmp_path / "out.folded"
    assert perf.main(["--flame", str(src),
                      "--out", str(folded_path)]) == 0
    assert "a;d 10" in folded_path.read_text()
    assert "self%" in capsys.readouterr().out
    # unusable input is exit 2, the compare-mode contract
    empty = tmp_path / "empty.json"
    empty.write_text("{}")
    assert perf.main(["--flame", str(empty)]) == 2


def test_flame_round_selection_from_profiles_jsonl(clean_prof, tmp_path,
                                                   capsys):
    from metisfl_tpu import perf

    sink = tmp_path / "profiles-1.jsonl"
    records = [
        {"round": 6, "phases": {"aggregate": 1.0},
         "prof": {"samples": 10, "stacks": [["x;slowpath", 10.0]]}},
        {"round": 7, "phases": {"aggregate": 1.0},
         "prof": {"samples": 30, "stacks": [["x;slowpath", 25.0],
                                            ["x;newhot", 5.0]]}},
    ]
    sink.write_text("".join(json.dumps(r) + "\n" for r in records))
    folded6 = perf.load_folded(str(sink), want_round=6)
    assert folded6 == {"x;slowpath": 10.0}
    # path@N suffix selects the round without the explicit flag
    folded7 = perf.load_folded(f"{sink}@7")
    assert folded7["x;newhot"] == 5.0
    # --flame-diff between the two rounds names the grown frames
    assert perf.main(["--flame-diff", f"{sink}@6", f"{sink}@7"]) == 0
    out = capsys.readouterr().out
    assert "slowpath" in out and "newhot" in out


def test_flame_diff_surfaces_injected_lock_hold(clean_prof, tmp_path,
                                                capsys):
    """The acceptance differential: the same seeded workload run twice,
    the second with a lock-hold injected through the test hook — the
    waiting acquire frames appear in run B's profile and --flame-diff
    names them as growth, while the contention histogram records the
    wait."""
    from metisfl_tpu import perf

    def run(inject_hold: bool, out_path: str):
        tprof.reset()
        tprof.configure(enabled=True)
        lk = tprof.lock("t.inject")
        stop = threading.Event()

        def worker():
            rng = np.random.default_rng(7)
            data = rng.standard_normal((128, 128)).astype(np.float32)
            while not stop.is_set():
                with lk:
                    data = data @ data.T / 128.0
        thread = threading.Thread(target=worker, daemon=True)
        thread.start()
        holder = None
        if inject_hold:
            # the test hook: grab the SAME lock object and hold it
            target = tprof.lock_object("t.inject")

            def hold():
                with target:
                    time.sleep(0.4)
            holder = threading.Thread(target=hold)
            holder.start()
        deadline = time.time() + 5.0
        want = (lambda f: any("acquire" in s for s in f)) if inject_hold \
            else (lambda f: any("worker" in s for s in f))
        while time.time() < deadline:
            tprof.sample_once()
            if want(tprof.folded_counts(tprof.collect_state())):
                break
            time.sleep(0.002)
        if holder is not None:
            holder.join()
        stop.set()
        thread.join()
        state = tprof.collect_state()
        with open(out_path, "w") as fh:
            json.dump(state, fh)
        return state

    run(False, str(tmp_path / "a.json"))
    state_b = run(True, str(tmp_path / "b.json"))
    # the injected hold surfaces in the contention telemetry
    assert state_b["locks"]["t.inject"]["contentions"] >= 1
    assert state_b["locks"]["t.inject"]["wait_s_total"] > 0.05
    # ... and in the differential profile as acquire-frame growth
    assert perf.main(["--flame-diff", str(tmp_path / "a.json"),
                      str(tmp_path / "b.json")]) == 0
    out = capsys.readouterr().out
    acquire_rows = [line for line in out.splitlines()
                    if "prof.acquire" in line]
    assert acquire_rows, out
    assert any("+" in line for line in acquire_rows)


# --------------------------------------------------------------------- #
# post-mortem snapshot
# --------------------------------------------------------------------- #

def test_postmortem_bundle_carries_prof(clean_prof, tmp_path, capsys):
    from metisfl_tpu.telemetry import postmortem
    from metisfl_tpu.telemetry.__main__ import render_postmortem

    tprof.configure(enabled=True)
    stop = threading.Event()
    thread = _start_burn(stop)
    lk = tprof.lock("t.pm")
    hold = threading.Thread(target=lambda: (lk.acquire(),
                                            time.sleep(0.08),
                                            lk.release()))
    hold.start()
    time.sleep(0.01)
    with lk:
        pass
    hold.join()
    try:
        _sample_until(lambda f: bool(f))
    finally:
        stop.set()
        thread.join()
    postmortem.configure(str(tmp_path), service="proftest",
                         install_hooks=False)
    path = postmortem.dump("chaos_kill")
    postmortem.configure("", service="proftest", install_hooks=False)
    assert path is not None
    bundle = json.load(open(path))
    assert bundle["prof"]["samples"] > 0
    assert bundle["prof"]["top"]
    assert bundle["prof"]["locks"]["t.pm"]["contentions"] >= 1
    bundle["_path"] = path
    screen = render_postmortem(bundle)
    assert "profiler at death" in screen
    assert "lock contention at death" in screen
    assert "t.pm" in screen


# --------------------------------------------------------------------- #
# config validation + template pins
# --------------------------------------------------------------------- #

def test_prof_config_validation():
    from metisfl_tpu.config import FederationConfig, ProfConfig, \
        TelemetryConfig

    with pytest.raises(ValueError, match="prof.hz"):
        FederationConfig(telemetry=TelemetryConfig(
            prof=ProfConfig(hz=0.0)))
    with pytest.raises(ValueError, match="prof.hz"):
        FederationConfig(telemetry=TelemetryConfig(
            prof=ProfConfig(hz=5000.0)))
    with pytest.raises(ValueError, match="prof.budget"):
        FederationConfig(telemetry=TelemetryConfig(
            prof=ProfConfig(budget=4)))
    # disabled skips the knob validation (nothing is armed)
    FederationConfig(telemetry=TelemetryConfig(
        prof=ProfConfig(enabled=False, hz=0.0, budget=0)))


def test_template_documents_prof_defaults():
    import yaml

    from metisfl_tpu.config import ProfConfig

    with open(os.path.join(REPO, "examples", "config",
                           "template.yaml")) as fh:
        data = yaml.safe_load(fh)
    block = data["telemetry"]["prof"]
    defaults = ProfConfig()
    assert set(block) == {"enabled", "hz", "budget"}
    assert block["enabled"] == defaults.enabled
    assert block["hz"] == defaults.hz
    assert block["budget"] == defaults.budget
    # module defaults mirror the dataclass (one source of truth each way)
    assert tprof.DEFAULT_HZ == defaults.hz
    assert tprof.DEFAULT_BUDGET == defaults.budget


def test_prof_metric_constants_match_module():
    assert telemetry.M_PROF_SAMPLES_TOTAL == tprof.SAMPLES_TOTAL
    assert telemetry.M_LOCK_WAIT_SECONDS == tprof.LOCK_WAIT_SECONDS
    assert telemetry.M_LOCK_CONTENTION_TOTAL == tprof.LOCK_CONTENTION_TOTAL


# --------------------------------------------------------------------- #
# acceptance: real-gRPC federation with per-peer attribution
# --------------------------------------------------------------------- #

def test_prof_fleet_federation_acceptance(clean_prof, tmp_path):
    """ISSUE 13 acceptance: a real-gRPC federation — controller + 2
    subprocess learners + 2 slice-aggregator processes — with profiling
    on yields a fleet-merged folded-stack profile in which the known
    hot frames appear with nonzero self time attributed to the correct
    peer: the aggregation fold kernel in a slice aggregator (the
    distributed tier folds there, not at the root) and codec
    encode/decode in a learner or the controller."""
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (AggregationConfig, EvalConfig,
                                    FabricConfig, FederationConfig,
                                    ProfConfig, TelemetryConfig,
                                    TerminationConfig,
                                    TreeAggregationConfig)
    from metisfl_tpu.driver.session import DriverSession
    from metisfl_tpu.models import ArrayDataset, FlaxModelOps
    from metisfl_tpu.models.zoo import MLP
    from metisfl_tpu.telemetry import prof as _p

    rng = np.random.default_rng(23)
    dim, hidden = 2048, 512  # ~1M params: codec + fold are ms-scale
    w = rng.standard_normal((dim, 2)).astype(np.float32)

    def make_recipe(seed):
        x = rng.standard_normal((16, dim)).astype(np.float32)
        y = np.argmax(x @ w, -1).astype(np.int32)

        def recipe():
            ops = FlaxModelOps(MLP(features=(hidden,), num_outputs=2),
                               np.zeros((2, dim), np.float32), rng_seed=0)
            return ops, ArrayDataset(x, y, seed=seed)

        return recipe

    template = FlaxModelOps(MLP(features=(hidden,), num_outputs=2),
                            np.zeros((2, dim), np.float32),
                            rng_seed=0).get_variables()
    config = FederationConfig(
        controller_port=_free_port(),
        round_deadline_secs=60.0,
        aggregation=AggregationConfig(
            scaler="participants",
            tree=TreeAggregationConfig(enabled=True, branch=2,
                                       distributed=True)),
        train=TrainParams(batch_size=8, local_steps=2, learning_rate=0.1),
        eval=EvalConfig(every_n_rounds=0),
        termination=TerminationConfig(federation_rounds=3,
                                      execution_cutoff_mins=5.0),
        telemetry=TelemetryConfig(
            fabric=FabricConfig(poll_every_s=0.4, jitter=0.1),
            # high-rate sampling for the test: 1.2 ms period makes the
            # ms-scale codec/fold windows statistically unmissable
            prof=ProfConfig(hz=800.0)),
    )
    session = DriverSession(config, template,
                            [make_recipe(0), make_recipe(1)],
                            workdir=str(tmp_path))
    try:
        session.initialize_federation()
        fleet = session.fleet_collector()
        assert fleet is not None
        session.monitor_federation(poll_every_s=1.0,
                                   eval_drain_timeout_s=0)
        fleet.poll_once(timeout=10.0)

        by_role = {}
        for peer in fleet.peers():
            by_role.setdefault(peer.role, []).append(peer)
        assert set(by_role) >= {"controller", "learner", "slice"}
        # every live peer shipped a profile with samples
        for peer in fleet.peers():
            assert peer.prof_state is not None, peer.name
            assert peer.prof_state.get("enabled"), peer.name
            assert peer.prof_state.get("samples", 0) > 0, peer.name

        def frames(peers):
            out = set()
            for peer in peers:
                for stack in _p.folded_counts(peer.prof_state):
                    out.update(stack.split(";"))
            return out

        # fold kernel attributed to the slice tier (the distributed
        # tree folds at the aggregators, not the root)
        slice_frames = frames(by_role["slice"])
        assert any("np_stacked_scaled_add" in f or "_native_fold" in f
                   or "tree._fold" in f for f in slice_frames), \
            sorted(slice_frames)[:40]
        # codec encode/decode attributed to a learner or the controller
        edge_frames = frames(by_role["learner"] + by_role["controller"])
        assert any("codec" in f or "pytree" in f for f in edge_frames), \
            sorted(edge_frames)[:40]
        # nonzero self time lands on a known hot frame in the merge
        merged = fleet.merged_folded()
        rows = {r["frame"]: r for r in _p.frame_table(merged)}
        hot = [r for f, r in rows.items()
               if ("np_stacked_scaled_add" in f or "_native_fold" in f
                   or "codec" in f or "pytree" in f)]
        assert any(r["total"] > 0 for r in hot)
        # per-peer attribution survives the merge (peer = root frame)
        peer_names = {p.name for p in fleet.peers()}
        assert all(stack.split(";", 1)[0] in peer_names
                   for stack in merged)
    finally:
        session.shutdown_federation()
    # the driver persisted the fleet profile artifact
    dump = os.path.join(str(tmp_path), "prof-fleet.json")
    assert os.path.exists(dump)
    from metisfl_tpu import perf
    assert perf.load_folded(dump)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
