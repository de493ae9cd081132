"""Causal tracing plane (ISSUE 16): W3C-style trace-context propagation
across every RPC hop and per-round critical-path attribution.

Layers under test, bottom up: the SpanContext wire frame (traceparent +
legacy fallback), deterministic round/request trace ids, the fork-join
critical-path walk over synthetic trees (passive skip, detached
subtrees, telescoping self-times), the orphan lint, summarize/render,
per-RPC propagation + the disabled-tracer opt-out, the serving chain
(router forward -> replica -> decode slot) in-process over real gRPC,
the perf --critical-path CLI, config/template/doc pins, the
flash-attention import smoke, and the DriverSession acceptance
federation: controller + subprocess learners + distributed slice
aggregators with a chaos-slowed learner that the critical path must
name as the dominant edge.
"""

import glob
import importlib
import json
import os
import socket
import time

import numpy as np
import pytest

from metisfl_tpu import telemetry
from metisfl_tpu.telemetry import causal as tcausal
from metisfl_tpu.telemetry import trace as ttrace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture
def ring():
    """Enabled tracer + armed finished-span ring; yields a drain callable
    returning every record finished since the fixture armed."""
    ttrace.configure(enabled=True, service="test", dir="")
    ttrace.configure_ring(8192)
    cursor = ttrace.spans_since(0)[1]
    yield lambda: ttrace.spans_since(cursor)[0]
    ttrace.configure(enabled=True, service="test", dir="")


def _rec(i, name, parent, start, dur_ms, trace="c" * 32, service="test",
         attrs=None):
    r = {"trace": trace, "span": f"{i:016x}", "parent": parent,
         "name": name, "service": service, "start": start,
         "dur_ms": dur_ms}
    if attrs:
        r["attrs"] = attrs
    return r


def _round_tree(round_no=3, trace=None, t0=1000.0, base=0):
    """A hand-built round-shaped trace: dispatch whose RunTask subtree
    OUTLIVES it (the fork-join case), a slow learner train, a store
    insert, and an aggregate tail. ``base`` keeps span ids distinct
    across trees built in one test."""
    trace = trace or ttrace.round_trace_id(round_no)
    root = _rec(base + 0, "round", "", t0, 10_000.0, trace=trace,
                service="controller", attrs={"round": round_no})
    dispatch = _rec(base + 1, "round.dispatch", root["span"], t0 + 0.05,
                    100.0, trace=trace, service="controller")
    # RunTask acks fast; its train CHILD runs on for seconds afterwards
    task = _rec(base + 2, "rpc.server/RunTask", dispatch["span"],
                t0 + 0.08, 20.0, trace=trace, service="learner_1")
    train = _rec(base + 3, "learner.train", task["span"], t0 + 0.1,
                 8_000.0, trace=trace, service="learner_1",
                 attrs={"learner": "learner_1"})
    steps = _rec(base + 4, "learner.train_steps", train["span"], t0 + 0.2,
                 2_000.0, trace=trace, service="learner_1")
    insert = _rec(base + 5, "round.store_insert", root["span"], t0 + 8.2,
                  300.0, trace=trace, service="controller",
                  attrs={"learner": "learner_1"})
    agg = _rec(base + 6, "round.aggregate", root["span"], t0 + 8.6,
               1_300.0, trace=trace, service="controller")
    fold = _rec(base + 7, "slice.fold", agg["span"], t0 + 8.7, 1_000.0,
                trace=trace, service="slice_0", attrs={"slice": "slice_0"})
    return [root, dispatch, task, train, steps, insert, agg, fold]


# --------------------------------------------------------------------- #
# wire frame + deterministic ids
# --------------------------------------------------------------------- #

def test_span_context_wire_frame_roundtrip_and_legacy_fallback():
    ctx = ttrace.SpanContext(trace_id="a" * 32, span_id="b" * 16)
    wire = ctx.to_wire()
    assert wire == f"00-{'a' * 32}-{'b' * 16}-01"
    assert ttrace.SpanContext.from_wire(wire) == ctx
    # pre-traceparent peers framed it as "trace/span" — still parses,
    # so a mixed-version fleet keeps stitching
    assert ttrace.SpanContext.from_wire(f"{'a' * 32}/{'b' * 16}") == ctx
    for junk in ("", "no-delims-here", "00--bbbb-01", "00-aaaa--01",
                 "trace/", "/span", "onepart"):
        assert ttrace.SpanContext.from_wire(junk) is None


def test_deterministic_trace_ids():
    rid = ttrace.round_trace_id(7)
    assert rid == f"{7:032x}" and len(rid) == 32
    assert ttrace.round_trace_id(7) == rid  # pure function
    assert ttrace.round_trace_id(8) != rid
    q = ttrace.request_trace_id("req-42")
    assert len(q) == 32 and int(q, 16) >= 0
    assert ttrace.request_trace_id("req-42") == q
    assert ttrace.request_trace_id("req-43") != q


def test_root_span_takes_deterministic_trace_id_children_inherit(ring):
    root = ttrace.span("round", parent=None,
                       trace_id=ttrace.round_trace_id(5),
                       attrs={"round": 5})
    with root.activate():
        with ttrace.span("round.dispatch"):
            pass
    root.end()
    records = ring()
    assert {r["trace"] for r in records} == {ttrace.round_trace_id(5)}
    # a parent's trace always wins over an explicit trace_id
    parent = ttrace.span("outer", parent=None)
    child = ttrace.span("inner", parent=parent,
                        trace_id=ttrace.round_trace_id(9))
    assert child.trace_id == parent.trace_id
    child.end()
    parent.end()


# --------------------------------------------------------------------- #
# critical-path walk
# --------------------------------------------------------------------- #

def test_critical_path_fork_join_attribution_and_telescoping():
    records = _round_tree()
    cp = tcausal.critical_path(records)
    assert cp is not None
    assert cp["root"] == "round" and cp["round"] == 3
    # the slow learner's train gap (8s window minus its 2s steps child)
    # is the dominant edge even though its rpc.server PARENT span ended
    # 20ms in — the walk follows subtree ends, not span ends
    assert cp["dominant"] == "learner_1/learner.train"
    labels = [e["label"] for e in cp["edges"]]
    assert "slice_0/slice.fold" in labels
    # self-times telescope to the root window exactly
    assert sum(e["self_ms"] for e in cp["edges"]) == pytest.approx(
        cp["total_ms"], rel=1e-6)
    assert cp["coverage"] >= 0.9
    assert cp["detached"] == 0


def test_critical_path_of_a_two_thousand_span_round():
    """The walk at the width of a real cohort: one round, four hundred
    learners whose train spans each outlive their RunTask parent (the
    fork-join shape), three step leaves under each, an aggregate tail.
    The slowest learner holds the dominant edge, every edge is
    attributed once, and the self times telescope to the root."""
    t0, learners, slow = 1_000_000.0, 400, 137
    trace = ttrace.round_trace_id(1)
    root = _rec(0, "round", "", t0, 5_000.0, trace=trace,
                service="controller", attrs={"round": 1})
    dispatch = _rec(1, "round.dispatch", root["span"], t0 + 0.001, 80.0,
                    trace=trace, service="controller")
    records, i = [root, dispatch], 2
    for li in range(learners):
        start = t0 + 0.002 + 1e-5 * li
        train_ms = 3_100.0 if li == slow else 2_000.0 + li % 13
        service = f"learner_{li}"
        task = _rec(i, "rpc.server/RunTask", dispatch["span"], start, 20.0,
                    trace=trace, service=service)
        train = _rec(i + 1, "learner.train", task["span"], start + 0.005,
                     train_ms, trace=trace, service=service,
                     attrs={"learner": service})
        records += [task, train] + [
            _rec(i + 2 + leaf, f"learner.step_{leaf}", train["span"],
                 start + 0.01 + 0.3 * leaf, 250.0, trace=trace,
                 service=service) for leaf in range(3)]
        i += 5
    agg = _rec(i, "round.aggregate", root["span"], t0 + 3.2, 1_700.0,
               trace=trace, service="controller")
    records += [agg, _rec(i + 1, "round.agg_block", agg["span"], t0 + 3.25,
                          1_600.0, trace=trace, service="controller")]
    assert len(records) == 2_004
    cp = tcausal.critical_path(records)
    assert cp["root"] == "round" and cp["round"] == 1
    assert cp["dominant"] == f"learner_{slow}/learner.train"
    labels = [e["label"] for e in cp["edges"]]
    assert len(labels) == len(set(labels))
    assert "controller/round.agg_block" in labels
    # the learners that started before the slow one hold the slivers
    # between their starts; together the other 399 explain next to nothing
    others = sum(e["self_ms"] for e in cp["edges"]
                 if e["service"] not in ("controller", f"learner_{slow}"))
    assert others < 0.01 * cp["total_ms"]
    assert sum(e["self_ms"] for e in cp["edges"]) == pytest.approx(
        cp["total_ms"], rel=1e-6)
    assert cp["total_ms"] == pytest.approx(5_000.0)
    assert cp["coverage"] >= 0.9 and cp["detached"] == 0


def test_passive_spans_are_never_chain_candidates():
    records = _round_tree()
    # a barrier wait covering almost the whole round: skipped, so the
    # cause (the train) stays dominant and the wait contributes no edge
    records.append(_rec(40, "round.wait_uplinks", records[0]["span"],
                        1000.1, 9_000.0, trace=records[0]["trace"],
                        service="controller", attrs={"passive": True}))
    cp = tcausal.critical_path(records)
    assert cp["dominant"] == "learner_1/learner.train"
    assert not any(e["name"] == "round.wait_uplinks" for e in cp["edges"])


def test_orphan_lint_and_detached_subtree_attribution():
    records = _round_tree()
    clean = tcausal.orphan_spans(records)
    assert clean == []
    # a hop that dropped the context: same trace, parent never collected,
    # sitting in the round's tail gap no collected subtree covers
    lost = _rec(50, "learner.dump_model", "f" * 16, 1009.91, 80.0,
                trace=records[0]["trace"], service="learner_0")
    records.append(lost)
    orphans = tcausal.orphan_spans(records)
    assert [o["name"] for o in orphans] == ["learner.dump_model"]
    # ...but its time still attributes: it re-parents under the root as
    # a detached subtree, flagged in the result
    cp = tcausal.critical_path(records)
    assert cp["detached"] == 1
    assert any(e["name"] == "learner.dump_model" for e in cp["edges"])
    assert "detached" in tcausal.render_edges(cp)


def test_round_critical_path_selects_round_and_latest_retry():
    # round 3 ran twice (retry bumped the serial): the LATER attempt wins
    first = _round_tree(round_no=3, trace="1" * 32, t0=1000.0, base=100)
    retry = _round_tree(round_no=3, trace="2" * 32, t0=2000.0, base=200)
    other = _round_tree(round_no=4, trace="3" * 32, t0=3000.0, base=300)
    spans = first + retry + other
    cp = tcausal.round_critical_path(spans, round_no=3)
    assert cp is not None and cp["trace"] == "2" * 32
    # omitted round -> the latest completed round overall
    assert tcausal.round_critical_path(spans)["round"] == 4
    assert tcausal.round_critical_path(spans, round_no=99) is None
    assert tcausal.round_critical_path([]) is None


def test_summarize_and_render_shapes():
    cp = tcausal.critical_path(_round_tree())
    summary = tcausal.summarize(cp, top=2)
    assert len(summary["edges"]) == 2
    assert summary["dominant"] == "learner_1/learner.train"
    assert summary["round"] == 3
    # heaviest-first in the summary
    selfs = [e["self_ms"] for e in summary["edges"]]
    assert selfs == sorted(selfs, reverse=True)
    line = tcausal.render(cp)
    assert line.startswith("round 3:") and "learner_1/learner.train" in line
    full = tcausal.render_edges(cp)
    assert len(full.splitlines()) == 1 + len(cp["edges"])


# --------------------------------------------------------------------- #
# propagation + opt-out
# --------------------------------------------------------------------- #

def test_outbound_metadata_roundtrip_and_disabled_optout(ring):
    with ttrace.span("outer", parent=None) as sp:
        with sp.activate():
            md = ttrace.outbound_metadata()
            assert md and md[0][0] == ttrace.METADATA_KEY
            ctx = ttrace.extract(md)
            assert ctx == sp.context()
    assert ttrace.outbound_metadata() is None  # nothing active
    # the opt-out: a disabled tracer hands out null spans, propagates
    # nothing, and event() records nothing — one attribute check per hop
    ttrace.configure(enabled=False)
    try:
        sp = ttrace.span("x", parent=None)
        with sp, sp.activate():
            assert sp.trace_id == "" and sp.span_id == ""
            assert ttrace.current_context() is None
            assert ttrace.outbound_metadata() is None
        ttrace.event("decode.slot", 0.01)
    finally:
        ttrace.configure(enabled=True, service="test", dir="")
    # nothing from the disabled window landed in the ring
    assert not any(r["name"] in ("x", "decode.slot") for r in ring())


def test_propagation_overhead_is_sub_budget():
    # the same measurement the --causal-smoke CI gate takes: inject +
    # extract, per RPC
    ns = tcausal._propagation_overhead_ns(iters=2000)
    assert 0 < ns < 50_000


# --------------------------------------------------------------------- #
# serving chain: request root -> router forward -> replica -> decode
# --------------------------------------------------------------------- #

def test_decode_slot_event_parents_under_submitter_span(ring):
    from metisfl_tpu.models import FlaxModelOps
    from metisfl_tpu.models.zoo.transformer import LlamaLite
    from metisfl_tpu.serving import ContinuousBatcher

    ops = FlaxModelOps(LlamaLite(vocab_size=97, dim=32, depth=2, heads=4),
                       np.zeros((1, 8), np.int32), rng_seed=0)
    engine = ContinuousBatcher(ops, 1, ops.get_variables(), slots=2,
                               max_len=32)
    try:
        gen = ttrace.span("serving.generate", parent=None)
        with gen, gen.activate():
            prompt = np.array([3, 5, 7], np.int32)
            tokens, _ = engine.submit(prompt, 4).result(timeout=60.0)
        assert len(tokens) == 4
    finally:
        engine.close()
    slots = [r for r in ring() if r["name"] == "decode.slot"]
    assert len(slots) == 1, "retirement must emit exactly one slot span"
    slot = slots[0]
    # the decode loop retires on its own thread: the parent link rode on
    # the pending-request record, not on ambient contextvars
    assert slot["trace"] == gen.trace_id
    assert slot["parent"] == gen.span_id
    assert slot["attrs"]["tokens"] == 4
    assert slot["attrs"]["channel"] == "stable"
    assert slot["attrs"]["retired_step"] >= slot["attrs"]["admitted_step"]


def test_router_chain_is_one_deterministic_trace_over_real_grpc(ring):
    from metisfl_tpu.config import ServingConfig, ServingFleetConfig
    from metisfl_tpu.models import FlaxModelOps
    from metisfl_tpu.models.zoo import MLP
    from metisfl_tpu.serving import (RouterServer, ServingClient,
                                     ServingGateway, ServingRouter,
                                     ServingServer)
    from metisfl_tpu.tensor.pytree import pack_model

    ops = FlaxModelOps(MLP(features=(8,), num_outputs=3),
                       np.zeros((2, 4), np.float32), rng_seed=0)
    cfg = ServingConfig(enabled=True, max_batch=4, max_wait_ms=1.0,
                        fleet=ServingFleetConfig(enabled=True, replicas=1))
    gw = ServingGateway(ops, cfg)
    gw.install("stable", 1, pack_model(ops.get_variables()))
    srv = ServingServer(gw, host="127.0.0.1", port=0)
    srv.start()
    router = ServingRouter(cfg)
    router.add_replica("serving_0", "127.0.0.1", srv.port)
    rserver = RouterServer(router, host="127.0.0.1", port=0)
    rserver.start()
    client = ServingClient("127.0.0.1", rserver.port)
    try:
        reply = client.predict(np.zeros((2, 4), np.float32), key="u7",
                               timeout=30.0)
        assert reply.model_version == 1
    finally:
        client.close()
        rserver.stop()
        srv.stop()
    records = ring()
    by_name = {}
    for r in records:
        by_name.setdefault(r["name"], []).append(r)
    root = by_name["serving.request"][0]
    # the edge client names the trace deterministically from its request
    # id — no join table needed to find a request's chain later
    assert root["trace"] == ttrace.request_trace_id(
        root["attrs"]["request_id"])
    assert root["attrs"]["method"] == "Predict"
    chain = [r for r in records if r["trace"] == root["trace"]]
    names = {r["name"] for r in chain}
    # client root -> router's server span -> router.forward -> replica's
    # server span -> gateway predict, all on ONE trace (router and
    # replica are separate gRPC servers; in-process here so one ring
    # sees every hop)
    assert {"serving.request", "router.forward", "rpc.server/Predict",
            "serving.predict"} <= names
    fwd = next(r for r in chain if r["name"] == "router.forward")
    assert fwd["attrs"]["replica"] == "serving_0"
    assert fwd["attrs"]["hops"] == 1
    # two rpc.server/Predict spans: client->router and router->replica;
    # the replica's one parents under router.forward
    predicts = [r for r in chain if r["name"] == "rpc.server/Predict"]
    assert len(predicts) == 2
    assert any(p["parent"] == fwd["span"] for p in predicts)
    cp = tcausal.critical_path(chain)
    assert cp["root"] == "serving.request"
    assert cp["request_id"] == root["attrs"]["request_id"]


# --------------------------------------------------------------------- #
# perf CLI + config/doc pins + flash-attention import smoke
# --------------------------------------------------------------------- #

def test_perf_critical_path_cli(tmp_path, capsys):
    from metisfl_tpu import perf

    path = os.path.join(str(tmp_path), "traces.jsonl")
    with open(path, "w") as fh:
        for r in _round_tree():
            fh.write(json.dumps(r) + "\n")
    assert perf.main(["--critical-path", path, "--round", "3"]) == 0
    out = capsys.readouterr().out
    assert "learner_1/learner.train" in out
    assert "round 3:" in out
    # a run DIR holding traces.jsonl works too
    assert perf.main(["--critical-path", str(tmp_path)]) == 0
    capsys.readouterr()
    assert perf.main(["--critical-path", path, "--round", "99"]) == 2
    assert perf.main(["--critical-path"]) == 2  # no paths: usage error


def test_critical_path_knobs_config_template_and_docs():
    import yaml

    from metisfl_tpu.config import FabricConfig, FederationConfig, \
        TelemetryConfig

    defaults = FabricConfig()
    assert defaults.critical_path is True
    assert defaults.critical_path_edges == 5
    with pytest.raises(ValueError):
        FederationConfig(telemetry=TelemetryConfig(
            fabric=FabricConfig(critical_path_edges=0)))
    with open(os.path.join(REPO, "examples", "config",
                           "template.yaml")) as fh:
        data = yaml.safe_load(fh)
    fab = data["telemetry"]["fabric"]
    assert fab["critical_path"] == defaults.critical_path
    assert fab["critical_path_edges"] == defaults.critical_path_edges
    assert (telemetry.M_ROUND_CRITICAL_PATH_SECONDS
            == "round_critical_path_seconds")
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md")) as fh:
        docs = fh.read()
    assert "## Causal tracing" in docs
    assert "round_critical_path_seconds" in docs
    with open(os.path.join(REPO, "README.md")) as fh:
        readme = fh.read()
    assert "Causal tracing" in readme


def test_flash_attention_imports_cleanly():
    # the module must import on the installed jax (plain import —
    # ``import ... as`` resolves the ops package's custom_vjp ATTRIBUTE,
    # not the module)
    mod = importlib.import_module("metisfl_tpu.ops.flash_attention")
    from jax.experimental.pallas import tpu as pltpu
    assert isinstance(mod._SEQ_PARAMS, pltpu.CompilerParams)
    # (heads, live steps): the live (query block, key block) pairs are one
    # sequential dimension since PR 33
    assert mod._SEQ_PARAMS.dimension_semantics == ("parallel", "arbitrary")


# --------------------------------------------------------------------- #
# acceptance: real federation, chaos-slowed learner named on the path
# --------------------------------------------------------------------- #

def test_causal_attribution_on_real_federation_with_slow_learner(
        tmp_path):
    """The ISSUE 16 acceptance run: controller + 2 subprocess learners +
    2 distributed slice aggregators over real gRPC, learner_1 slowed by
    a chaos rule. One deterministic trace id must span dispatch ->
    train -> uplink -> fold; the critical path must name the slowed
    learner as the dominant edge with >= 90% round-wall-clock coverage;
    the fleet snapshot, the status crit: line, the
    round_critical_path_seconds gauge, the persisted RoundProfile, and
    perf --critical-path over the run dir must all agree."""
    from metisfl_tpu import perf
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (AggregationConfig, ChaosConfig,
                                    EvalConfig, FabricConfig,
                                    FederationConfig, TelemetryConfig,
                                    TerminationConfig,
                                    TreeAggregationConfig)
    from metisfl_tpu.driver.session import DriverSession
    from metisfl_tpu.models import ArrayDataset, FlaxModelOps
    from metisfl_tpu.models.zoo import MLP

    rng = np.random.default_rng(16)
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
        aggregation=AggregationConfig(
            scaler="participants",
            tree=TreeAggregationConfig(enabled=True, branch=2,
                                       distributed=True)),
        train=TrainParams(batch_size=8, local_steps=2, learning_rate=0.1),
        eval=EvalConfig(every_n_rounds=0),
        termination=TerminationConfig(federation_rounds=2,
                                      execution_cutoff_mins=5.0),
        telemetry=TelemetryConfig(
            fabric=FabricConfig(poll_every_s=0.5, jitter=0.1)),
        # the slow SURVIVOR: learner_1 stretches each train task's
        # wall-clock 3x — the attribution target the path must name
        chaos=ChaosConfig(enabled=True, rules=[
            {"fault": "slow", "factor": 3.0, "max_fires": 4,
             "process": "learner_1"}]),
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

        spans = fleet.spans()
        # the chaos rule targeted PROCESS learner_1; its federation
        # identity (Lx_host_port, assigned in racy registration order)
        # resolves through the pid every span record carries
        slow_proc = next(p for p in session._procs
                         if p.name == "learner_1")
        slow_id = next(s.get("peer") or s["service"] for s in spans
                       if s.get("pid") == slow_proc.process.pid
                       and s["name"] == "learner.train")
        # round 0 is where the slow rule + jit compile land — the round
        # whose attribution the acceptance pins
        cp = tcausal.round_critical_path(spans, round_no=0)
        assert cp is not None, "round 0 root missing from the fleet merge"
        # ONE deterministic trace spans the controller's dispatch, the
        # learners' train tasks, and the uplink forwards
        assert cp["trace"] == ttrace.round_trace_id(0)
        trace_spans = [s for s in spans if s["trace"] == cp["trace"]]
        names = {s["name"] for s in trace_spans}
        # the uplink hop under distributed tree aggregation is the
        # slice-submit forward (the store-insert form covers the
        # non-distributed topology, test-pinned by --causal-smoke)
        assert {"round", "round.dispatch", "learner.train",
                "round.slice_submit"} <= names, names
        learner_services = {s.get("peer") or s.get("service")
                            for s in trace_spans
                            if s["name"] == "learner.train"}
        assert len(learner_services) == 2, learner_services
        # the slowed learner is the dominant edge; coverage >= 90%
        assert cp["dominant"] == f"{slow_id}/learner.train", cp["dominant"]
        assert cp["coverage"] >= 0.9, cp
        # orphan lint: every parent resolved (no hop dropped the context)
        assert tcausal.orphan_spans(trace_spans) == []

        # the fleet consumers agree: snapshot crit entry (refreshed per
        # sweep over the LATEST round), status line, the per-edge gauge
        snap = fleet.snapshot()
        assert snap["crit"].get("edges"), snap.get("crit")
        assert snap["crit"]["coverage"] > 0
        from metisfl_tpu.status import render_fleet
        assert "crit:" in render_fleet(snap)
        from metisfl_tpu.telemetry import parse_exposition, render_metrics
        series = parse_exposition(render_metrics())
        crit_series = series.get(telemetry.M_ROUND_CRITICAL_PATH_SECONDS)
        assert crit_series, "critical-path gauge never exported"
    finally:
        session.shutdown_federation()

    # the controller persisted the causal summary into its RoundProfile
    prof_files = glob.glob(os.path.join(str(tmp_path), "**",
                                        "profiles-*.jsonl"),
                           recursive=True)
    assert prof_files, "controller round-profile sink missing"
    prof_records = []
    for path in prof_files:
        with open(path) as fh:
            prof_records += [json.loads(line) for line in fh if
                             line.strip()]
    attributed = [r for r in prof_records if r.get("critical_path")]
    assert attributed, "no RoundProfile carried a critical_path summary"
    # The collector reads only the controller's own span ring — learner
    # subprocess spans live in their own processes — so the attached
    # summary is the controller-local view: round trace id, non-empty
    # edges, a dominant controller-side edge. The cross-process view
    # (slowed learner dominant) is the fleet merge asserted above.
    round0 = [r for r in attributed if r.get("round") == 0]
    assert round0, "round 0 profile lost its critical_path summary"
    for rec in round0:
        summary = rec["critical_path"]
        assert summary["trace"] == ttrace.round_trace_id(0)
        assert summary["edges"], "controller-local walk attributed nothing"
        assert summary["dominant"]
        assert summary["total_ms"] > 0

    # post-hoc: the run dir replays through perf --critical-path, and
    # the shutdown file merge pulled the slice aggregators' fold spans
    # into round traces
    assert perf.main(["--critical-path", str(tmp_path),
                      "--round", "0"]) == 0
    merged = perf._load_trace_spans(str(tmp_path))
    round_traces = {r["trace"] for r in tcausal.round_roots(merged)}
    fold_traces = {s["trace"] for s in merged
                   if s["name"] in ("slice.fold",
                                    "rpc.server/FoldPartial")}
    assert fold_traces & round_traces, \
        "no slice fold span landed on a round trace"


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-v"]))
