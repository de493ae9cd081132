"""Serving fleet (ISSUE 15): consistent-hash router over gateway
replicas (canary coherence, drain-around-death, bounded retry),
deterministic registry-poll staggering, continuous-batching decode
(step-granularity admission, greedy bit-identity vs solo generate,
zero-drop swap), the alert-rule autoscaler, and the DriverSession fleet
end-to-end with scale-up/down."""

import os
import threading
import time

import numpy as np
import pytest

from metisfl_tpu.comm.messages import TrainParams
from metisfl_tpu.config import (
    AggregationConfig,
    EvalConfig,
    FederationConfig,
    PromotionConfig,
    RegistryConfig,
    ServingConfig,
    ServingDecodeConfig,
    ServingFleetConfig,
    TerminationConfig,
)
from metisfl_tpu.models import FlaxModelOps
from metisfl_tpu.models.zoo import MLP
from metisfl_tpu.serving import (
    ContinuousBatcher,
    FleetAutoscaler,
    HashRing,
    RouterServer,
    ServingClient,
    ServingGateway,
    ServingRouter,
    ServingServer,
    canary_channel,
    poll_stagger,
)
from metisfl_tpu.tensor.pytree import pack_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ops(seed=0, outputs=3):
    return FlaxModelOps(MLP(features=(8,), num_outputs=outputs),
                        np.zeros((2, 4), np.float32), rng_seed=seed)


def _lm_ops(seed=0):
    from metisfl_tpu.models.zoo.transformer import LlamaLite
    return FlaxModelOps(LlamaLite(vocab_size=97, dim=32, depth=2, heads=4),
                        np.zeros((1, 8), np.int32), rng_seed=seed)


@pytest.fixture
def clean_telemetry():
    from metisfl_tpu.telemetry import events as _events
    from metisfl_tpu.telemetry import metrics as _metrics
    _metrics.set_enabled(True)
    _metrics.registry().reset()
    _events.set_enabled(True)
    _events.journal().reset()
    yield
    _metrics.registry().reset()
    _events.journal().reset()


def _fleet_of(n, canary_percent=0.0, install=True, ops=None):
    """n in-process gateways behind real gRPC servers + a router."""
    ops = ops or _ops()
    cfg = ServingConfig(enabled=True, max_batch=4, max_wait_ms=1.0,
                        canary_percent=canary_percent,
                        fleet=ServingFleetConfig(enabled=True, replicas=n,
                                                 max_replicas=max(4, n),
                                                 probe_every_s=0.2))
    blob = pack_model(ops.get_variables())
    gateways, servers = [], []
    for _ in range(n):
        gw = ServingGateway(ops, cfg)
        if install:
            gw.install("stable", 1, blob)
        srv = ServingServer(gw, host="127.0.0.1", port=0)
        srv.start()
        gateways.append(gw)
        servers.append(srv)
    router = ServingRouter(cfg)
    for i, srv in enumerate(servers):
        router.add_replica(f"serving_{i}", "127.0.0.1", srv.port)
    rserver = RouterServer(router, host="127.0.0.1", port=0)
    rserver.start()
    return ops, cfg, gateways, servers, router, rserver


def _teardown(servers, rserver):
    rserver.stop()
    for srv in servers:
        srv.stop()


# ---------------------------------------------------------------------- #
# hash ring + poll stagger (satellite: thundering-herd fix, test-pinned)
# ---------------------------------------------------------------------- #

def test_poll_stagger_offsets_are_deterministic_and_spread():
    # replica i of N polls first at i * period / N — pure function, no
    # randomness, full-period spread (the registry sees one replica per
    # period/N instead of N at once)
    assert poll_stagger(0, 3, 1.5) == 0.0
    assert poll_stagger(1, 3, 1.5) == pytest.approx(0.5)
    assert poll_stagger(2, 3, 1.5) == pytest.approx(1.0)
    assert poll_stagger(3, 3, 1.5) == 0.0          # wraps by index % N
    assert poll_stagger(0, 1, 1.5) == 0.0          # solo gateway: no delay
    offsets = {poll_stagger(i, 8, 2.0) for i in range(8)}
    assert len(offsets) == 8                        # all distinct phases
    assert max(offsets) < 2.0


def test_hash_ring_owner_stability_and_minimal_disruption():
    ring = HashRing(vnodes=64)
    for name in ("a", "b", "c"):
        ring.add(name)
    keys = [f"user{i}" for i in range(500)]
    owners = {k: ring.owners(k)[0] for k in keys}
    # deterministic: same ring, same owners
    assert owners == {k: ring.owners(k)[0] for k in keys}
    # every member owns a non-trivial share of the keyspace
    share = {n: sum(1 for o in owners.values() if o == n)
             for n in ("a", "b", "c")}
    assert all(v > 50 for v in share.values()), share
    # removing b moves ONLY b's keys; a/c keys keep their owner
    ring.remove("b")
    after = {k: ring.owners(k)[0] for k in keys}
    for k in keys:
        if owners[k] != "b":
            assert after[k] == owners[k]
        else:
            assert after[k] in ("a", "c")
    # the fallback chain lists distinct members in ring order
    ring.add("b")
    chain = ring.owners("user7")
    assert sorted(chain) == ["a", "b", "c"] and chain[0] == owners["user7"]


def test_fleet_config_validation():
    def cfg(**fleet):
        return FederationConfig(
            registry=RegistryConfig(enabled=True),
            serving=ServingConfig(
                enabled=True, fleet=ServingFleetConfig(**fleet)))

    with pytest.raises(ValueError, match="min_replicas"):
        cfg(enabled=True, min_replicas=0)
    with pytest.raises(ValueError, match="max_replicas"):
        cfg(enabled=True, min_replicas=3, max_replicas=2, replicas=3)
    with pytest.raises(ValueError, match="within"):
        cfg(enabled=True, replicas=9)
    with pytest.raises(ValueError, match="retry_hops"):
        cfg(enabled=True, retry_hops=-1)
    with pytest.raises(ValueError, match="scale rule"):
        cfg(enabled=True, scale_up={"metric": "serving_requests_total",
                                    "kind": "nope", "threshold": 1})
    with pytest.raises(ValueError, match="quantile"):
        cfg(enabled=True, scale_up={"metric": "serving_requests_total",
                                    "kind": "quantile", "threshold": 1})
    # scale rules on a disabled fleet would silently arm nothing
    with pytest.raises(ValueError, match="require"):
        cfg(enabled=False, scale_up={"metric": "serving_requests_total",
                                     "threshold": 1})
    # fleet on a disabled serving plane likewise
    with pytest.raises(ValueError, match="serving.enabled"):
        FederationConfig(serving=ServingConfig(
            enabled=False, fleet=ServingFleetConfig(enabled=True)))
    with pytest.raises(ValueError, match="decode.slots"):
        FederationConfig(
            registry=RegistryConfig(enabled=True),
            serving=ServingConfig(enabled=True,
                                  decode=ServingDecodeConfig(slots=0)))


def test_template_documents_fleet_and_decode_defaults():
    import yaml

    path = os.path.join(REPO, "examples", "config", "template.yaml")
    with open(path) as fh:
        data = yaml.safe_load(fh)
    fleet = data["serving"]["fleet"]
    defaults = ServingFleetConfig()
    for key in ("enabled", "replicas", "min_replicas", "max_replicas",
                "router_port", "vnodes", "retry_hops", "probe_every_s",
                "scale_cooldown_s"):
        assert fleet[key] == getattr(defaults, key), key
    assert fleet["scale_up"] == {} and fleet["scale_down"] == {}
    assert fleet["gateways"] == []
    decode = data["serving"]["decode"]
    d = ServingDecodeConfig()
    assert decode["slots"] == d.slots
    assert decode["max_len"] == d.max_len


# ---------------------------------------------------------------------- #
# router: coherence, drain, retry
# ---------------------------------------------------------------------- #

def test_canary_coherent_across_replicas_including_rolling_swap(
        clean_telemetry):
    """Satellite pin: the same key resolves to the same channel
    whichever replica serves it — including while a rolling swap walks
    the fleet one replica at a time."""
    import jax

    ops, cfg, gateways, servers, router, rserver = _fleet_of(
        3, canary_percent=30.0)
    v1 = ops.get_variables()
    blob_c = pack_model(jax.tree.map(lambda a: np.asarray(a) * 3.0, v1))
    blob_v2 = pack_model(jax.tree.map(lambda a: np.asarray(a) * 2.0, v1))
    for gw in gateways:
        gw.install("candidate", 2, blob_c)
    client = ServingClient("127.0.0.1", rserver.port)
    try:
        keys = [f"user{i}" for i in range(40)]
        expected = {k: canary_channel(k, 30.0) for k in keys}
        assert len(set(expected.values())) == 2  # both sides exercised
        x = np.zeros((1, 4), np.float32)
        seen = {k: set() for k in keys}

        def sweep():
            for k in keys:
                reply = client.predict(x, key=k, timeout=30.0)
                seen[k].add(reply.channel)

        sweep()
        # rolling swap of the STABLE channel, one replica at a time,
        # sweeping traffic between each hop
        for gw in gateways:
            gw.install("stable", 3, blob_v2)
            sweep()
        sweep()
        for k in keys:
            assert seen[k] == {expected[k]}, (k, seen[k], expected[k])
    finally:
        client.close()
        _teardown(servers, rserver)


def test_router_drains_around_dead_replica_with_bounded_retry(
        clean_telemetry):
    ops, cfg, gateways, servers, router, rserver = _fleet_of(3)
    client = ServingClient("127.0.0.1", rserver.port)
    try:
        x = np.zeros((2, 4), np.float32)
        keys = [f"k{i}" for i in range(30)]
        for k in keys:
            client.predict(x, key=k, timeout=30.0)
        # kill replica 1's server cold (its gateway stays up — the
        # ROUTER must route around the dead endpoint)
        servers[1].stop()
        for k in keys:  # every key still serves (retry to next owner)
            client.predict(x, key=k, timeout=30.0)
        desc = router.describe()
        row = next(r for r in desc["replicas"]
                   if r["replica"] == "serving_1")
        assert row["state"] == "dead"
        assert desc["live"] == 2
        from metisfl_tpu.telemetry import events as _events
        dead = [e for e in _events.tail()
                if e["kind"] == "serving_replica_dead"]
        assert dead and dead[-1]["replica"] == "serving_1"
        # retries were counted on the metric surface
        from metisfl_tpu import telemetry
        from metisfl_tpu.telemetry import parse_exposition, render_metrics
        series = parse_exposition(render_metrics())
        assert telemetry.M_ROUTER_RETRIES_TOTAL in series
    finally:
        client.close()
        _teardown(servers, rserver)


def test_router_role_reflection_and_serving_line(clean_telemetry):
    ops, cfg, gateways, servers, router, rserver = _fleet_of(2)
    client = ServingClient("127.0.0.1", rserver.port)
    try:
        reflection = client.list_methods()
        assert reflection["role"] == "router"
        assert {"Predict", "Generate", "AddReplica", "DrainReplica"} <= {
            m["name"] for m in reflection["methods"]}
        router.probe_once()  # cache per-replica installed versions
        desc = client.status()
        assert desc["router"] and desc["live"] == 2
        from metisfl_tpu.status import render_serving_line
        line = render_serving_line(desc)
        assert "2/2 replicas up" in line
        assert "serving_0=up(stable=v1)" in line
        # a plain gateway status renders the single-gateway form
        single = render_serving_line(gateways[0].describe())
        assert "1 gateway" in single and "stable=v1" in single
        # drain semantics: a drained replica leaves the ring but keeps
        # serving its in-flight work; traffic re-routes to the survivor
        assert router.drain_replica("serving_0")
        x = np.zeros((1, 4), np.float32)
        for i in range(10):
            reply = client.predict(x, key=f"d{i}", timeout=30.0)
            assert reply.model_version == 1
        assert router.describe()["live"] == 1
    finally:
        client.close()
        _teardown(servers, rserver)


# ---------------------------------------------------------------------- #
# continuous-batching decode
# ---------------------------------------------------------------------- #

def test_decode_bit_identical_to_solo_generate_greedy():
    from metisfl_tpu.models.generate import generate

    ops = _lm_ops()
    variables = ops.get_variables()
    engine = ContinuousBatcher(ops, 1, variables, slots=3, max_len=32)
    try:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 97, size=(n,)).astype(np.int32)
                   for n in (5, 3, 9)]
        futs = [engine.submit(p, 8) for p in prompts]
        for p, fut in zip(prompts, futs):
            tokens, version = fut.result(timeout=60.0)
            ref = np.asarray(generate(ops.module, variables, p[None], 8,
                                      max_len=32))[0]
            np.testing.assert_array_equal(tokens, ref)  # bit-identical
            assert version == 1
    finally:
        engine.close()


def test_decode_eos_pads_exactly_like_generate():
    from metisfl_tpu.models.generate import generate

    ops = _lm_ops()
    variables = ops.get_variables()
    prompt = np.array([3, 5, 7], np.int32)
    ref = np.asarray(generate(ops.module, variables, prompt[None], 12,
                              max_len=32))[0]
    # pick the first emitted token as eos so the early-stop path runs
    eos = int(ref[0])
    ref_eos = np.asarray(generate(ops.module, variables, prompt[None], 12,
                                  max_len=32, eos_id=eos))[0]
    engine = ContinuousBatcher(ops, 1, variables, slots=2, max_len=32)
    try:
        tokens, _ = engine.submit(prompt, 12,
                                  eos_id=eos).result(timeout=60.0)
        np.testing.assert_array_equal(tokens, ref_eos)
        assert tokens[0] == eos and not tokens[1:].any()  # pad after eos
    finally:
        engine.close()


def test_late_prompt_joins_in_flight_batch_at_step_granularity(
        clean_telemetry):
    """The Orca pin: a prompt arriving mid-generation is admitted
    between decode steps of the RUNNING batch — it does not wait for
    the batch to finish — and both outputs stay bit-identical to solo
    runs."""
    from metisfl_tpu.models.generate import generate

    ops = _lm_ops()
    variables = ops.get_variables()
    engine = ContinuousBatcher(ops, 1, variables, slots=2, max_len=64)
    try:
        a_prompt = np.array([3, 5, 7, 11, 2], np.int32)
        b_prompt = np.array([9, 4, 1], np.int32)
        fut_a = engine.submit(a_prompt, 40)
        deadline = time.time() + 30.0
        while engine.steps < 3 and time.time() < deadline:
            time.sleep(0.002)
        assert engine.steps >= 3, "batch never started stepping"
        fut_b = engine.submit(b_prompt, 5)
        toks_a, _ = fut_a.result(timeout=60.0)
        toks_b, _ = fut_b.result(timeout=60.0)
        admitted = fut_b.request.admitted_step
        retired_a_by = engine.steps
        # B was admitted at STEP granularity: after A started (step > 0)
        # and strictly before the in-flight batch finished
        assert 0 < admitted < retired_a_by, (admitted, retired_a_by)
        ref_a = np.asarray(generate(ops.module, variables, a_prompt[None],
                                    40, max_len=64))[0]
        ref_b = np.asarray(generate(ops.module, variables, b_prompt[None],
                                    5, max_len=64))[0]
        np.testing.assert_array_equal(toks_a, ref_a)
        np.testing.assert_array_equal(toks_b, ref_b)
        # the queue-occupancy / tokens family is live; the tokens are
        # counted once a tick, and a rate of the counter is the throughput
        from metisfl_tpu import telemetry
        from metisfl_tpu.telemetry import parse_exposition, render_metrics
        series = parse_exposition(render_metrics())
        assert telemetry.M_SERVING_DECODE_TOKENS_TOTAL in series
        assert "serving_decode_tokens_per_sec" not in series
    finally:
        engine.close()


def test_decode_swap_finishes_in_flight_on_captured_pair():
    import jax

    ops = _lm_ops()
    v1 = ops.get_variables()
    v2 = jax.tree.map(lambda a: np.asarray(a) * 1.5, v1)
    engine = ContinuousBatcher(ops, 1, v1, slots=2, max_len=64)
    try:
        fut_a = engine.submit(np.array([3, 5, 7], np.int32), 30)
        deadline = time.time() + 30.0
        while engine.steps < 2 and time.time() < deadline:
            time.sleep(0.002)
        engine.swap(2, v2)
        fut_b = engine.submit(np.array([9, 4], np.int32), 4)
        toks_a, ver_a = fut_a.result(timeout=60.0)
        toks_b, ver_b = fut_b.result(timeout=60.0)
        assert ver_a == 1      # in-flight finished on the captured pair
        assert ver_b == 2      # queued request decoded on the new one
        assert len(toks_a) == 30 and len(toks_b) == 4  # zero drops
    finally:
        engine.close()


def test_gateway_generate_routes_swaps_and_describes(clean_telemetry):
    ops = _lm_ops()
    cfg = ServingConfig(enabled=True,
                        decode=ServingDecodeConfig(slots=2, max_len=32))
    gw = ServingGateway(ops, cfg)
    gw.install("stable", 1, pack_model(ops.get_variables()))
    try:
        prompt = np.array([3, 5, 7, 11, 2], np.int32)
        toks, version, channel = gw.generate(prompt, 8, key="u1")
        assert (version, channel) == (1, "stable") and len(toks) == 8
        # install() propagates the swap into the live decode engine
        gw.install("stable", 2, pack_model(ops.get_variables()))
        toks2, version2, _ = gw.generate(prompt, 8, key="u1")
        assert version2 == 2
        np.testing.assert_array_equal(toks, toks2)  # same weights
        desc = gw.describe()
        assert desc["decode"]["stable"]["version"] == 2
        snap = gw.queue_snapshot()
        assert "decode_queue_depth" in snap
        # cache bound is enforced per request, loudly
        with pytest.raises(ValueError, match="max_len"):
            gw.generate(np.arange(1, 30, dtype=np.int32), 8, key="u1")
    finally:
        gw.shutdown()


# ---------------------------------------------------------------------- #
# autoscaler
# ---------------------------------------------------------------------- #

def test_autoscaler_holds_bounds_and_cooldown():
    clock = {"t": 100.0}
    scaler = FleetAutoscaler(
        {"metric": "serving_requests_total", "kind": "rate",
         "window_s": 5, "op": ">", "threshold": 10, "for_s": 2},
        {"metric": "serving_requests_total", "kind": "rate",
         "window_s": 5, "op": "<", "threshold": 1, "for_s": 2},
        min_replicas=1, max_replicas=3, cooldown_s=10,
        clock=lambda: clock["t"])
    total = 0.0

    def tick(qps, replicas, dt=1.0):
        nonlocal total
        clock["t"] += dt
        total += qps * dt
        return scaler.observe({"serving_requests_total": total},
                              replicas=replicas)

    tick(0, 1)                      # seed the rate window
    # a surge must HOLD for_s before firing
    assert tick(50, 1) is None      # breach starts
    assert tick(50, 1) is None      # held 1s < for_s
    assert tick(50, 1) == "up"      # held 2s -> scale up
    # cooldown blocks immediate re-fire; a fired decision also resets
    # the hold, so the NEXT action needs a fresh for_s breach
    assert tick(50, 2) is None
    clock["t"] += 10                # past the cooldown (window empties)
    decisions = [tick(50, 2) for _ in range(4)]
    assert decisions[-1] == "up" and decisions[:3] == [None] * 3
    # ceiling: no up past max_replicas, however hard the breach
    clock["t"] += 10
    for _ in range(6):
        assert tick(50, 3) is None
    # the surge ending drains back (one action per cooldown window) —
    # but never below min_replicas
    clock["t"] += 10
    decisions = [tick(0, 3) for _ in range(5)]
    assert decisions.count("down") == 1 and "up" not in decisions
    clock["t"] += 10
    decisions = [tick(0, 2) for _ in range(5)]
    assert decisions.count("down") == 1 and "up" not in decisions
    clock["t"] += 10
    for _ in range(6):
        assert tick(0, 1) is None   # floor


def test_autoscaler_rejects_quantile_rules():
    with pytest.raises(ValueError, match="quantile"):
        FleetAutoscaler({"metric": "serving_request_latency_seconds",
                         "kind": "quantile", "threshold": 1.0},
                        None, 1, 2)


# ---------------------------------------------------------------------- #
# DriverSession fleet end-to-end: boot, traffic, autoscale up + down
# ---------------------------------------------------------------------- #

def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_driver_fleet_boots_serves_and_autoscales(tmp_path,
                                                  clean_telemetry):
    """The acceptance federation: DriverSession boots 1 gateway replica
    + the router; a synthetic QPS surge fires the serving_* scale-up
    rule and boots a second replica; the surge ending drains it back to
    min_replicas — events + metrics pinned, traffic served throughout
    via the router."""
    from metisfl_tpu.driver.session import DriverSession
    from metisfl_tpu.models import ArrayDataset

    rng = np.random.default_rng(5)
    w = rng.standard_normal((4, 2)).astype(np.float32)
    x = rng.standard_normal((32, 4)).astype(np.float32)
    y = np.argmax(x @ w, -1).astype(np.int32)

    def recipe():
        ops = FlaxModelOps(MLP(features=(8,), num_outputs=2),
                           np.zeros((2, 4), np.float32), rng_seed=0)
        # a test split too: auto-promotion only runs when a round's eval
        # digest folds into its registered version (registry/registry.py
        # note_eval), so the gate needs evals flowing
        return ops, ArrayDataset(x, y, seed=0), None, ArrayDataset(x, y)

    template = FlaxModelOps(MLP(features=(8,), num_outputs=2),
                            np.zeros((2, 4), np.float32),
                            rng_seed=0).get_variables()
    config = FederationConfig(
        controller_port=_free_port(),
        aggregation=AggregationConfig(scaler="participants"),
        train=TrainParams(batch_size=8, local_steps=2, learning_rate=0.1),
        eval=EvalConfig(every_n_rounds=1),
        termination=TerminationConfig(federation_rounds=200),
        registry=RegistryConfig(
            enabled=True,
            promotion=PromotionConfig(require_eval=False)),
        serving=ServingConfig(
            enabled=True, max_batch=4, max_wait_ms=1.0,
            poll_every_s=0.25,
            fleet=ServingFleetConfig(
                enabled=True, replicas=1, min_replicas=1, max_replicas=2,
                probe_every_s=0.25, scale_cooldown_s=0.5,
                scale_up={"metric": "serving_requests_total",
                          "kind": "rate", "window_s": 3.0, "op": ">",
                          "threshold": 5.0, "for_s": 0.0},
                scale_down={"metric": "serving_requests_total",
                            "kind": "rate", "window_s": 3.0, "op": "<",
                            "threshold": 0.5, "for_s": 1.0})),
    )
    session = DriverSession(config, template, [recipe],
                            workdir=str(tmp_path))
    client = None
    try:
        session.initialize_federation()
        assert session._autoscaler is not None
        fleet = config.serving.fleet
        assert len(fleet.gateways) == 1
        assert config.serving.port == fleet.router_port  # client -> router

        # wait for a promoted version to reach the replica via the
        # registry poll, then traffic flows through the router
        client = session.serving_client()
        deadline = time.time() + 120.0
        reply = None
        while time.time() < deadline:
            session._check_procs_alive(
                skip=tuple(session._serving_proc_names()))
            try:
                reply = client.predict(x[:2], key="boot", timeout=5.0)
                break
            except Exception:
                time.sleep(0.5)
        assert reply is not None, "router never served a request"
        assert reply.model_version >= 1 and reply.channel == "stable"

        # ---- synthetic QPS surge -> the scale-up rule fires ---------- #
        stop = threading.Event()

        def hammer():
            h = session.serving_client()
            i = 0
            while not stop.is_set():
                try:
                    h.predict(x[:2], key=f"s{i}", timeout=10.0)
                except Exception:
                    pass
                i += 1
                time.sleep(0.01)
            h.close()

        t = threading.Thread(target=hammer)
        t.start()
        scaled_up = False
        deadline = time.time() + 60.0
        while time.time() < deadline:
            if session._autoscale_serving() == "up":
                scaled_up = True
                break
            time.sleep(0.5)
        assert scaled_up, "surge never fired the scale-up rule"
        assert len(fleet.gateways) == 2
        assert any(p.name == "serving_1" for p in session._procs)

        # ---- the surge ends -> drain back to min_replicas ------------ #
        stop.set()
        t.join(timeout=30.0)
        scaled_down = False
        deadline = time.time() + 60.0
        while time.time() < deadline:
            if session._autoscale_serving() == "down":
                scaled_down = True
                break
            time.sleep(0.5)
        assert scaled_down, "idle fleet never drained"
        assert len(fleet.gateways) == 1
        assert not any(p.name == "serving_1" for p in session._procs)

        # events + metrics pinned
        from metisfl_tpu import telemetry
        from metisfl_tpu.telemetry import events as _events
        kinds = [e["kind"] for e in _events.tail()]
        assert "serving_scaled_up" in kinds
        assert "serving_scaled_down" in kinds
        up_evt = next(e for e in _events.tail()
                      if e["kind"] == "serving_scaled_up")
        assert up_evt["replica"] == "serving_1" and up_evt["value"] > 5.0
        reg = telemetry.metrics.registry()
        assert reg.get(telemetry.M_SERVING_FLEET_REPLICAS).value() == 1
        scale = reg.get(telemetry.M_SERVING_SCALE_TOTAL)
        assert scale.value(direction="up") >= 1
        assert scale.value(direction="down") >= 1

        # the fleet still serves after the scale-down
        reply = client.predict(x[:2], key="after", timeout=30.0)
        assert reply.channel == "stable"

        # fabric peer specs name router + every replica as serving peers
        specs = session._fleet_peer_specs()
        serving_peers = {s["name"] for s in specs
                         if s["role"] == "serving"}
        assert "router" in serving_peers
        assert "serving_0" in serving_peers
    finally:
        if client is not None:
            client.close()
        session.shutdown_federation()


# ---------------------------------------------------------------------- #
# the replica-kill acceptance smoke (the chaos_smoke.sh gate, in-test)
# ---------------------------------------------------------------------- #

@pytest.mark.slow
def test_fleet_smoke_sigkill_replica_mid_canary(tmp_path):
    """The full replica-kill gate (3 real subprocesses + live traffic).
    CI runs it every build via scripts/chaos_smoke.sh; slow-marked here
    so tier-1 keeps its budget."""
    from metisfl_tpu.serving.smoke import run_fleet_smoke

    assert run_fleet_smoke(replicas=3, traffic_threads=3, keys=16,
                           workdir=str(tmp_path)) == 0
