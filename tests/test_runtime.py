"""Accelerator runtime observability (ISSUE 19): XLA compile/recompile
tracking, device-memory accounting, and the zero-recompile CI gate.

Layers under test, bottom up: monitored_jit attribution (cold compile
recorded with the abstract shape signature, steady-state calls record
nothing), cold-vs-recompile classification (unattributed compiles never
count as recompiles), storm detection + the jax_recompile_storm journal
event, the per-fn budget's _other fold, memory snapshots + the
mem_every_s gate on the prof-sampler tick, the opt-out pins (stub reply,
pass-through wrapper, listener never installed — subprocess-proven), the
CollectTelemetry runtime section and the FleetCollector's absorb /
merge / dump, status --fleet's runtime: and ha: lines, perf
--compile-report from both a fleet dump and raw jax.compile trace
spans, post-mortem bundles, config validation + template pins, and the
PR 13 slot-decoder regression:
steady-state decode is zero-recompile after warmup while an
over-LRU-bound prompt-length sweep provably shows up in the counters.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from metisfl_tpu import telemetry
from metisfl_tpu.telemetry import events as tevents
from metisfl_tpu.telemetry import fabric as tfabric
from metisfl_tpu.telemetry import metrics as tmetrics
from metisfl_tpu.telemetry import prof as tprof
from metisfl_tpu.telemetry import runtime as truntime
from metisfl_tpu.telemetry import trace as ttrace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture
def clean_runtime():
    tmetrics.set_enabled(True)
    tmetrics.registry().reset()
    tevents.configure(enabled=True, service="test", dir="", ring_size=512)
    tevents.journal().reset()
    ttrace.configure(enabled=True, service="test", dir="")
    tfabric.configure(enabled=True)
    truntime.reset()
    yield
    truntime.reset()
    tprof.reset()
    tprof.configure(enabled=False)
    tmetrics.registry().reset()


def _fresh_monitored(name, scale=3.0):
    """A monitored jit over a FRESH function object (jax.jit caches per
    object: reusing one across tests would never compile again)."""
    import jax.numpy as jnp

    def fn(v):
        return jnp.tanh(v * scale) + 1.0

    return truntime.monitored_jit(fn, name=name)


# --------------------------------------------------------------------- #
# monitored_jit attribution + classification
# --------------------------------------------------------------------- #

def test_monitored_jit_attributes_cold_compile(clean_runtime):
    fn = _fresh_monitored("t.cold")
    v = np.ones((4,), np.float32)
    out = fn(v)
    np.testing.assert_allclose(np.asarray(out),
                               np.tanh(v * 3.0) + 1.0, rtol=1e-6)
    state = truntime.collect_state()
    assert state["enabled"] and state["compiles"] >= 1
    row = state["fns"]["t.cold"]
    assert row["cold"] >= 1 and row["recompiles"] == 0
    assert "float32[4]" in row["last_sig"]
    assert row["total_s"] > 0.0 and row["max_s"] > 0.0
    assert any(r[1] == "t.cold" and r[2] == "cold"
               for r in state["recent"])
    # the metric family carries the same attribution
    fam = tmetrics.registry().get(truntime.JAX_COMPILES_TOTAL)
    assert fam.value(fn="t.cold", kind="cold") >= 1
    # steady state: the same shapes compile nothing new
    before = state["compiles"]
    for _ in range(5):
        fn(v)
    assert truntime.collect_state()["compiles"] == before


def test_recompile_classification_storm_and_event(clean_runtime):
    truntime.configure(enabled=True, storm_threshold=3,
                       storm_window_s=60.0)
    fn = _fresh_monitored("t.shapeshift")
    for width in (4, 8, 12, 16):
        fn(np.ones((width,), np.float32))
    state = truntime.collect_state()
    row = state["fns"]["t.shapeshift"]
    assert row["cold"] == 1
    assert row["recompiles"] >= 3
    assert state["recompiles"] >= 3
    assert state["storms"] >= 1
    storms = [r for r in tevents.tail()
              if r.get("kind") == "jax_recompile_storm"]
    assert storms and storms[-1]["fn"] == "t.shapeshift"
    assert storms[-1]["count"] >= 3
    # mute: the SAME window does not re-fire per extra recompile
    assert len(storms) == 1
    fam = tmetrics.registry().get(truntime.JAX_COMPILES_TOTAL)
    assert fam.value(fn="t.shapeshift", kind="recompile") >= 3
    # each compile also lands in the span timeline as a jax.compile
    # event, so perf --critical-path can name a mid-round recompile
    reply = json.loads(tfabric.handle_collect(b"{}", "svc", "learner"))
    names = [s.get("name") for s in reply.get("spans", [])]
    assert "jax.compile" in names


def test_unattributed_compiles_never_classify_as_recompiles(clean_runtime):
    # the label is a bucket of unrelated functions (jnp internals, model
    # init), not one function compiling twice
    for _ in range(3):
        truntime._record_compile(truntime.UNATTRIBUTED, "", 0.01)
    state = truntime.collect_state()
    assert state["unattributed"] == 3
    assert state["recompiles"] == 0
    assert state["fns"][truntime.UNATTRIBUTED]["cold"] == 3


def test_fn_budget_folds_into_other(clean_runtime):
    truntime.configure(enabled=True, budget=8)
    for i in range(12):
        truntime._record_compile(f"fn.{i}", "sig", 0.001)
    state = truntime.collect_state()
    assert truntime.OTHER in state["fns"]
    assert len(state["fns"]) <= 9  # 8 exact rows + the _other fold
    assert state["compiles"] == 12
    folded = state["fns"][truntime.OTHER]
    assert folded["cold"] + folded["recompiles"] == 4


# --------------------------------------------------------------------- #
# memory accounting
# --------------------------------------------------------------------- #

def test_memory_snapshot_sources_and_gate(clean_runtime):
    snap = truntime.sample_memory(force=True)
    assert snap is not None
    assert snap["host_rss_bytes"] > 0
    assert snap["device_bytes"] > 0
    assert snap["source"] in ("device_stats", "live_arrays", "rss")
    assert snap["plane"] == "host"
    fam = tmetrics.registry().get(truntime.JAX_DEVICE_MEMORY_BYTES)
    assert fam.value(plane="host") > 0
    # the mem_every_s gate: an immediate un-forced resample is a no-op
    assert truntime.sample_memory() is None
    assert truntime.collect_state()["memory"]["device_bytes"] > 0


def test_prof_tick_hook_samples_memory(clean_runtime):
    truntime.configure(enabled=True, mem_every_s=0.001)
    assert truntime._tick in tprof._TICK_HOOKS
    tprof.configure(enabled=True)
    tprof.sample_once()  # the PR 12 sampler cadence drives the sample
    assert truntime.collect_state()["memory"].get("device_bytes", 0) > 0


def test_set_plane_derivation(clean_runtime):
    for service, plane in (("controller", "controller"),
                           ("standby-1", "controller"),
                           ("learner-3", "learner"),
                           ("serving", "serving"),
                           ("gateway-2", "serving"),
                           ("replica-0", "serving"),
                           ("router", "serving"),
                           ("bench", "host")):
        truntime.set_plane(service)
        assert truntime.plane() == plane, service


# --------------------------------------------------------------------- #
# opt-out pins (satellite: enabled=false installs nothing)
# --------------------------------------------------------------------- #

def test_opt_out_stub_and_passthrough(clean_runtime):
    truntime.configure(enabled=False)
    assert truntime.collect_state() == {"enabled": False}
    fn = _fresh_monitored("t.optout")
    out = fn(np.ones((4,), np.float32))  # computes, records nothing
    assert np.asarray(out).shape == (4,)
    assert truntime.sample_memory(force=True) is None
    # the CollectTelemetry reply carries the stub, not a table
    reply = json.loads(tfabric.handle_collect(b"{}", "svc", "learner"))
    assert reply["runtime"] == {"enabled": False}
    truntime.configure(enabled=True)
    assert truntime.collect_state()["compiles"] == 0


def test_opt_out_never_installs_listener_subprocess():
    """The acceptance pin needs a virgin process: in-suite the listener
    is already armed (jax.monitoring has no unregister). A process that
    only ever sees enabled=false must end with listener_mode 'none'."""
    code = (
        "from metisfl_tpu.telemetry import runtime\n"
        "runtime.configure(enabled=False)\n"
        "import numpy as np\n"
        "fn = runtime.monitored_jit(lambda v: v + 1.0, name='optout')\n"
        "out = fn(np.ones((3,), np.float32))\n"
        "assert float(np.asarray(out)[0]) == 2.0\n"
        "assert runtime.listener_mode() == 'none', runtime.listener_mode()\n"
        "assert runtime.collect_state() == {'enabled': False}\n"
        "print('OPTOUT_OK')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "OPTOUT_OK" in proc.stdout


def test_apply_config_arms_runtime_and_derives_plane(clean_runtime):
    from metisfl_tpu.config import RuntimeConfig, TelemetryConfig

    telemetry.apply_config(
        TelemetryConfig(runtime=RuntimeConfig(budget=32, mem_every_s=0.5,
                                              storm_window_s=5.0,
                                              storm_threshold=2)),
        service="learner-3")
    try:
        assert truntime.enabled()
        assert truntime.plane() == "learner"
        assert truntime.collect_state()["budget"] == 32
    finally:
        telemetry.apply_config(
            TelemetryConfig(runtime=RuntimeConfig(enabled=False)),
            service="controller")
    assert truntime.collect_state() == {"enabled": False}
    assert truntime.plane() == "controller"


# --------------------------------------------------------------------- #
# fabric transport + fleet merge
# --------------------------------------------------------------------- #

def test_collect_reply_runtime_section_and_summary(clean_runtime):
    fn = _fresh_monitored("t.fab")
    fn(np.ones((4,), np.float32))
    fn(np.ones((6,), np.float32))  # one recompile → an offender
    reply = json.loads(tfabric.handle_collect(b"{}", "svc", "controller"))
    state = reply["runtime"]
    assert state["enabled"] and state["compiles"] >= 2
    assert "t.fab" in state["fns"]
    assert state["memory"]["device_bytes"] > 0
    summary = truntime.summarize_state(state)
    assert summary["compiles"] == state["compiles"]
    assert summary["top_offender"] == "t.fab"
    assert summary["top_offender_recompiles"] >= 1
    assert summary["mem_bytes"] > 0


def test_merge_states_sums_and_memory_maxima():
    a = {"enabled": True, "compiles": 3, "recompiles": 1, "storms": 1,
         "fns": {"train.step": {"cold": 1, "recompiles": 1,
                                "total_s": 0.5, "max_s": 0.4,
                                "last_sig": "f32[8]"}},
         "memory": {"plane": "learner", "device_bytes": 100}}
    b = {"enabled": True, "compiles": 2, "recompiles": 0, "storms": 0,
         "fns": {"train.step": {"cold": 1, "recompiles": 0,
                                "total_s": 0.2, "max_s": 0.2,
                                "last_sig": "f32[16]"},
                 "infer": {"cold": 1, "recompiles": 0, "total_s": 0.1,
                           "max_s": 0.1, "last_sig": ""}},
         "memory": {"plane": "learner", "device_bytes": 300}}
    merged = truntime.merge_states([a, {"enabled": False}, b, None])
    assert merged["enabled"]
    assert merged["compiles"] == 5 and merged["recompiles"] == 1
    assert merged["storms"] == 1
    row = merged["fns"]["train.step"]
    assert row["cold"] == 2 and row["recompiles"] == 1
    assert row["max_s"] == pytest.approx(0.4)
    assert row["total_s"] == pytest.approx(0.7)
    assert row["last_sig"] == "f32[8]"  # first peer's wins
    assert merged["fns"]["infer"]["cold"] == 1
    # per-plane memory keeps the fleet maximum, not a meaningless sum
    assert merged["memory"] == {"learner": 300}
    # an all-opted-out fleet merges to a disabled view
    assert not truntime.merge_states([{"enabled": False}])["enabled"]


def test_merge_states_respects_budget():
    states = [{"enabled": True, "compiles": 1, "recompiles": 0,
               "fns": {f"fn.{i}": {"cold": 1, "recompiles": 0,
                                   "total_s": 0.01, "max_s": 0.01,
                                   "last_sig": ""}}}
              for i in range(12)]
    merged = truntime.merge_states(states, budget=8)
    assert len(merged["fns"]) <= 9
    assert truntime.OTHER in merged["fns"]
    total = sum(r["cold"] for r in merged["fns"].values())
    assert total == 12  # the fold loses labels, never counts


def test_fleet_collector_absorbs_runtime_merges_and_dump(clean_runtime,
                                                         tmp_path):
    from metisfl_tpu.comm.rpc import BytesService, RpcServer

    fn = _fresh_monitored("t.fleet")
    fn(np.ones((4,), np.float32))
    fn(np.ones((6,), np.float32))  # a recompile for the report table
    server = RpcServer("127.0.0.1", 0)
    server.add_service(BytesService("rt.peer", {}, role="learner"))
    port = server.start()
    collector = tfabric.FleetCollector(probe_health=False)
    try:
        collector.add_peer("peer-0", "127.0.0.1", port, "rt.peer",
                           role="learner")
        assert collector.collect_peer(
            next(iter(collector.peers()))) == "ok"
        peer = collector.peers()[0]
        assert peer.runtime_state and peer.runtime_state["compiles"] >= 2
        merged = collector.merged_runtime()
        assert merged["enabled"] and merged["compiles"] >= 2
        assert "t.fleet" in merged["fns"]
        # the status --fleet snapshot carries the per-peer summary and
        # the merged jax_* metric families
        snap = collector.snapshot()
        assert snap["runtime"]["peer-0"]["compiles"] >= 2
        assert snap["families"][truntime.JAX_COMPILES_TOTAL]["total"] >= 2
        # and the dump is a --compile-report-renderable artifact
        dump = tmp_path / "runtime-fleet.json"
        assert collector.dump_runtime(str(dump))
        from metisfl_tpu import perf
        state = perf.load_runtime_state(str(dump))
        assert state["fns"] and state["peers"] == ["peer-0"]
        screen = perf.render_compile_report(state)
        assert "t.fleet" in screen
        assert "worst offender" in screen
    finally:
        collector.stop(final_poll=False)
        server.stop(grace=0.1)


# --------------------------------------------------------------------- #
# status --fleet rendering (runtime: + the HA satellite's ha: line)
# --------------------------------------------------------------------- #

def test_render_fleet_runtime_line(clean_runtime):
    from metisfl_tpu.status import render_fleet

    snap = {
        "peers": [], "live": 0, "polls": 1, "families": {},
        "spans": [], "events": [],
        "runtime": {"learner-0": {"enabled": True, "compiles": 3,
                                  "recompiles": 2, "storms": 1,
                                  "top_offender": "decode.prefill",
                                  "top_offender_recompiles": 2,
                                  "mem_bytes": 48_000_000,
                                  "mem_source": "rss"}},
    }
    screen = render_fleet(snap)
    assert "runtime: " in screen
    assert "learner-0: 3c/2r" in screen
    assert "STORMS=1" in screen
    assert "worst=decode.prefillx2" in screen
    assert "mem=48MB" in screen


def test_render_fleet_ha_line(clean_runtime):
    from metisfl_tpu.status import render_fleet

    snap = {
        "peers": [], "live": 0, "polls": 1, "spans": [], "events": [],
        "families": {
            "controller_wal_records_total": {"total": 42.0},
            "controller_wal_lag_records": {"total": 3.0},
            "controller_failover_total": {"total": 1.0},
            "controller_failover_promote_seconds": {"sum": 1.5,
                                                    "count": 1.0},
        },
    }
    screen = render_fleet(snap)
    assert "ha: wal=42 records lag=3" in screen
    assert "failovers=1" in screen
    assert "promote=1.5s" in screen
    # lag renders even before any failover fired (the standby's heartbeat)
    snap["families"].pop("controller_failover_total")
    snap["families"].pop("controller_failover_promote_seconds")
    screen = render_fleet(snap)
    assert "lag=3" in screen and "failovers" not in screen


# --------------------------------------------------------------------- #
# perf --compile-report
# --------------------------------------------------------------------- #

def test_compile_report_from_trace_spans(clean_runtime, tmp_path):
    from metisfl_tpu import perf

    path = tmp_path / "traces.jsonl"
    spans = [
        {"span": "a1", "name": "jax.compile", "dur_ms": 150.0,
         "attrs": {"fn": "train.step", "kind": "cold",
                   "sig": "float32[32,128]"}},
        {"span": "a2", "name": "jax.compile", "dur_ms": 90.0,
         "attrs": {"fn": "train.step", "kind": "recompile",
                   "sig": "float32[16,128]"}},
        {"span": "a3", "name": "round", "dur_ms": 500.0},
    ]
    path.write_text("".join(json.dumps(s) + "\n" for s in spans))
    state = perf.load_runtime_state(str(path))
    assert state["compiles"] == 2 and state["recompiles"] == 1
    row = state["fns"]["train.step"]
    assert row["cold"] == 1 and row["recompiles"] == 1
    assert row["max_s"] == pytest.approx(0.15)
    screen = perf.render_compile_report(state)
    assert "train.step" in screen
    assert "worst offender: train.step recompiled 1x" in screen
    # the run-dir form resolves the same file
    assert perf.load_runtime_state(str(tmp_path))["compiles"] == 2
    # no runtime data → exit 2, not a crash
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert perf._compile_report_main(str(empty), top=10) == 2


def test_compile_report_cli_renders_live_state(clean_runtime, tmp_path):
    fn = _fresh_monitored("t.report")
    fn(np.ones((4,), np.float32))
    fn(np.ones((6,), np.float32))
    path = tmp_path / "runtime.json"
    path.write_text(json.dumps(truntime.collect_state()))
    proc = subprocess.run(
        [sys.executable, "-m", "metisfl_tpu.perf", "--compile-report",
         str(path)],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=REPO + os.pathsep + os.environ.get(
                     "PYTHONPATH", "")))
    assert proc.returncode == 0, proc.stderr
    assert "t.report" in proc.stdout
    assert "recent compiles:" in proc.stdout


# --------------------------------------------------------------------- #
# post-mortem bundle
# --------------------------------------------------------------------- #

def test_postmortem_bundle_carries_runtime(clean_runtime, tmp_path):
    from metisfl_tpu.telemetry import postmortem

    fn = _fresh_monitored("t.pm")
    fn(np.ones((4,), np.float32))
    postmortem.configure(str(tmp_path), service="rttest",
                         install_hooks=False)
    path = postmortem.dump("chaos_kill")
    postmortem.configure("", service="rttest", install_hooks=False)
    assert path is not None
    bundle = json.load(open(path))
    assert bundle["runtime"]["compiles"] >= 1
    assert any(r["fn"] == "t.pm" for r in bundle["runtime"]["top"])
    assert bundle["runtime"]["memory"]["host_rss_bytes"] > 0


# --------------------------------------------------------------------- #
# the PR 13 slot-decoder regression (the tentpole's reason to exist)
# --------------------------------------------------------------------- #

def test_slot_decoder_steady_state_is_zero_recompile(clean_runtime):
    """Steady-state decode (fixed prompt length) compiles NOTHING after
    warmup, and a prompt-length sweep past the prefill LRU bound
    (_PREFILL_MAX) is VISIBLE in the recompile counters — the exact
    silent-latency-cliff this plane exists to catch."""
    from metisfl_tpu.models.generate import SlotDecoder

    ops, variables = truntime._smoke_decoder()
    decoder = SlotDecoder(ops.module, slots=2, max_len=24)
    toks = np.zeros(2, np.int32)
    positions = np.full(2, 8, np.int32)
    prompt = np.arange(1, 9, dtype=np.int32)  # length 8
    decoder.prefill(variables, 0, prompt)
    decoder.step(variables, toks, positions)  # warm both programs
    warm = truntime.collect_state()["compiles"]
    assert warm >= 1, "decode warmup compile was never observed"
    for _ in range(5):
        decoder.prefill(variables, 0, prompt)
        decoder.step(variables, toks, positions)
    assert truntime.collect_state()["compiles"] == warm, \
        "steady-state decode recompiled"

    # sweep MORE distinct prompt lengths than the LRU keeps: each new
    # length is one decode.prefill recompile in the counters
    bound = SlotDecoder._PREFILL_MAX
    for length in range(1, bound + 2):
        decoder.prefill(variables, 0,
                        np.arange(1, length + 1, dtype=np.int32))
    state = truntime.collect_state()
    row = state["fns"]["decode.prefill"]
    assert row["recompiles"] >= bound, row
    # the most recent length is cached...
    before = state["compiles"]
    decoder.prefill(variables, 0,
                    np.arange(1, bound + 2, dtype=np.int32))
    assert truntime.collect_state()["compiles"] == before
    # ...but the oldest was LRU-evicted: re-admitting it recompiles,
    # and the counters say so
    decoder.prefill(variables, 0, np.arange(1, 2, dtype=np.int32))
    after = truntime.collect_state()
    assert after["compiles"] > before
    assert after["fns"]["decode.prefill"]["recompiles"] > row["recompiles"]


# --------------------------------------------------------------------- #
# config validation + template pins + constants
# --------------------------------------------------------------------- #

def test_runtime_config_validation():
    from metisfl_tpu.config import (FederationConfig, RuntimeConfig,
                                    TelemetryConfig)

    with pytest.raises(ValueError, match="runtime.budget"):
        FederationConfig(telemetry=TelemetryConfig(
            runtime=RuntimeConfig(budget=4)))
    with pytest.raises(ValueError, match="runtime.mem_every_s"):
        FederationConfig(telemetry=TelemetryConfig(
            runtime=RuntimeConfig(mem_every_s=0.0)))
    with pytest.raises(ValueError, match="runtime.storm_window_s"):
        FederationConfig(telemetry=TelemetryConfig(
            runtime=RuntimeConfig(storm_window_s=-1.0)))
    with pytest.raises(ValueError, match="runtime.storm_threshold"):
        FederationConfig(telemetry=TelemetryConfig(
            runtime=RuntimeConfig(storm_threshold=1)))
    # disabled skips the knob validation (nothing is armed)
    FederationConfig(telemetry=TelemetryConfig(
        runtime=RuntimeConfig(enabled=False, budget=0, mem_every_s=0.0,
                              storm_window_s=0.0, storm_threshold=0)))


def test_template_documents_runtime_defaults():
    import yaml

    from metisfl_tpu.config import RuntimeConfig

    with open(os.path.join(REPO, "examples", "config",
                           "template.yaml")) as fh:
        data = yaml.safe_load(fh)
    block = data["telemetry"]["runtime"]
    defaults = RuntimeConfig()
    assert set(block) == {"enabled", "budget", "mem_every_s",
                          "storm_window_s", "storm_threshold"}
    assert block["enabled"] == defaults.enabled
    assert block["budget"] == defaults.budget
    assert block["mem_every_s"] == defaults.mem_every_s
    assert block["storm_window_s"] == defaults.storm_window_s
    assert block["storm_threshold"] == defaults.storm_threshold
    # module defaults mirror the dataclass (one source of truth each way)
    assert truntime.DEFAULT_BUDGET == defaults.budget
    assert truntime.DEFAULT_MEM_EVERY_S == defaults.mem_every_s
    assert truntime.DEFAULT_STORM_WINDOW_S == defaults.storm_window_s
    assert truntime.DEFAULT_STORM_THRESHOLD == defaults.storm_threshold


def test_runtime_metric_constants_match_module():
    assert telemetry.M_JAX_COMPILES_TOTAL == truntime.JAX_COMPILES_TOTAL
    assert telemetry.M_JAX_COMPILE_SECONDS == truntime.JAX_COMPILE_SECONDS
    assert (telemetry.M_JAX_DEVICE_MEMORY_BYTES
            == truntime.JAX_DEVICE_MEMORY_BYTES)
    # the HA satellite's standby-lag gauge (controller/__main__.py)
    assert (telemetry.M_CONTROLLER_WAL_LAG_RECORDS
            == "controller_wal_lag_records")
