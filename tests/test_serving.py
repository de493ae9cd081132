"""Serving gateway (ISSUE 5): micro-batch coalescing (bit-identical to
unbatched), zero-drop hot-swap, deterministic canary split, the gRPC
surface with role reflection, and the in-process federation → registry →
gateway pipeline."""

import threading
import time

import numpy as np
import pytest

from metisfl_tpu.comm.messages import TrainParams
from metisfl_tpu.config import (
    AggregationConfig,
    EvalConfig,
    FederationConfig,
    RegistryConfig,
    ServingConfig,
)
from metisfl_tpu.models import ArrayDataset, FlaxModelOps
from metisfl_tpu.models.zoo import MLP
from metisfl_tpu.serving import (
    ContinuousBatcher,
    DirectRegistrySource,
    MicroBatcher,
    ServingClient,
    ServingGateway,
    ServingServer,
    canary_channel,
)
from metisfl_tpu.tensor.pytree import pack_model


def _ops(seed=0, outputs=3):
    return FlaxModelOps(MLP(features=(8,), num_outputs=outputs),
                        np.zeros((2, 4), np.float32), rng_seed=seed)


def _sized_ops(size):
    """(engine, input width): the toy every test here serves, or an MLP
    of 1.38M parameters, the width of a model a federation would serve."""
    if size == "toy":
        return _ops(), 4
    return FlaxModelOps(MLP(features=(1024, 1024), num_outputs=64),
                        np.zeros((2, 256), np.float32), rng_seed=0), 256


def _gateway(canary_percent=0.0, max_batch=8, max_wait_ms=5.0, ops=None):
    ops = ops or _ops()
    gw = ServingGateway(ops, ServingConfig(
        enabled=True, max_batch=max_batch, max_wait_ms=max_wait_ms,
        canary_percent=canary_percent))
    return gw, ops


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


# ---------------------------------------------------------------------- #
# micro-batching
# ---------------------------------------------------------------------- #

def test_microbatcher_coalesces_and_splits():
    seen = []

    def run(rows):
        seen.append(len(rows))
        return rows * 2.0

    batcher = MicroBatcher(run, max_batch=16, max_wait_ms=50.0)
    xs = [np.full((3, 2), float(i)) for i in range(4)]
    futures = [batcher.submit(x) for x in xs]
    outs = [f.result(timeout=10.0) for f in futures]
    for x, out in zip(xs, outs):
        np.testing.assert_array_equal(out, x * 2.0)
    batcher.close()
    # the 12 rows coalesced into fewer forwards than requests
    assert sum(seen) == 12 and len(seen) < 4


def test_microbatcher_error_propagates_per_request():
    def run(rows):
        raise RuntimeError("backend down")

    batcher = MicroBatcher(run, max_batch=4, max_wait_ms=1.0)
    fut = batcher.submit(np.zeros((2, 2)))
    with pytest.raises(RuntimeError, match="backend down"):
        fut.result(timeout=10.0)
    batcher.close()


@pytest.mark.parametrize("size", ["toy", "1.38M"])
def test_microbatch_results_bit_identical_to_unbatched(clean_telemetry,
                                                       size):
    """The acceptance contract: coalescing must not change a single bit
    of any request's output (every forward pads to the same fixed-shape
    program, so per-row math is independent of batch composition)."""
    ops, dim = _sized_ops(size)
    gw, ops = _gateway(max_batch=8, max_wait_ms=20.0, ops=ops)
    gw.install("stable", 1, pack_model(ops.get_variables()))
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal((3, dim)).astype(np.float32)
          for _ in range(6)]
    # unbatched: one request at a time through the same gateway
    singles = [gw.predict(x, key=f"k{i}")[0] for i, x in enumerate(xs)]
    # batched: all six concurrently, coalescing in the queue
    results = [None] * len(xs)

    def call(i):
        results[i] = gw.predict(xs[i], key=f"k{i}")[0]

    threads = [threading.Thread(target=call, args=(i,))
               for i in range(len(xs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for a, b in zip(singles, results):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)  # bit-identical
    # occupancy metric observed coalesced batches
    from metisfl_tpu import telemetry
    from metisfl_tpu.telemetry import parse_exposition, render_metrics
    series = parse_exposition(render_metrics())
    assert telemetry.M_SERVING_BATCH_ROWS + "_count" in series
    gw.shutdown()


def test_oversized_request_chunks_through_the_bucket():
    gw, ops = _gateway(max_batch=4)
    gw.install("stable", 1, pack_model(ops.get_variables()))
    x = np.random.default_rng(1).standard_normal((11, 4)).astype(np.float32)
    outs, version, channel = gw.predict(x, key="big")
    assert outs.shape[0] == 11 and version == 1
    # bit equality holds between runs of ONE program: the gateway's last
    # chunk is its 4-row program on 3 rows padded with the last, where a
    # plain ``ops.infer(x, batch_size=4)`` would run a 3-row program
    padded = np.concatenate([x, x[-1:]], axis=0)
    np.testing.assert_array_equal(
        outs, ops.infer(padded, batch_size=4)[:11])
    gw.shutdown()


# ---------------------------------------------------------------------- #
# hot-swap + canary
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("size", ["toy", "1.38M"])
def test_hot_swap_drops_zero_inflight_requests(clean_telemetry, size):
    import jax

    ops, dim = _sized_ops(size)
    gw, ops = _gateway(max_batch=4, max_wait_ms=2.0, ops=ops)
    v1 = ops.get_variables()
    v2 = jax.tree.map(lambda a: np.asarray(a) * 2.0, v1)
    gw.install("stable", 1, pack_model(v1))
    x = np.random.default_rng(2).standard_normal((2, dim)).astype(np.float32)
    errors, versions = [], set()
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            try:
                _, ver, _ = gw.predict(x, key="h")
                versions.add(ver)
            except Exception as exc:  # noqa: BLE001 - the assertion target
                errors.append(exc)

    threads = [threading.Thread(target=hammer) for _ in range(3)]
    for t in threads:
        t.start()
    # swap only after v1 demonstrably served traffic (the first request
    # pays the jit compile, which can outlast any fixed sleep)
    deadline = time.time() + 30.0
    while 1 not in versions and not errors and time.time() < deadline:
        time.sleep(0.01)
    gw.install("stable", 2, pack_model(v2))
    deadline = time.time() + 30.0
    while 2 not in versions and not errors and time.time() < deadline:
        time.sleep(0.01)
    stop.set()
    for t in threads:
        t.join()
    gw.shutdown()
    assert not errors, errors  # zero dropped/failed requests
    assert versions == {1, 2}  # traffic flowed across the swap
    from metisfl_tpu.telemetry import events as _events
    swaps = [e for e in _events.tail() if e["kind"] == "serving_swapped"]
    assert swaps and swaps[-1]["version"] == 2


def test_canary_split_is_deterministic_and_honors_percent():
    keys = [f"user{i}" for i in range(2000)]
    frac = sum(canary_channel(k, 25.0) == "candidate"
               for k in keys) / len(keys)
    assert 0.20 < frac < 0.30
    # deterministic: the same key always routes the same way
    assert all(canary_channel(k, 25.0) == canary_channel(k, 25.0)
               for k in keys[:100])
    assert all(canary_channel(k, 0.0) == "stable" for k in keys[:100])
    assert all(canary_channel(k, 100.0) == "candidate"
               for k in keys[:100])


def test_canary_routes_to_candidate_and_falls_back_when_absent():
    import jax

    gw, ops = _gateway(canary_percent=50.0, max_batch=4)
    v1 = ops.get_variables()
    gw.install("stable", 1, pack_model(v1))
    x = np.zeros((1, 4), np.float32)
    # find keys on each side of the split
    stable_key = next(k for k in (f"s{i}" for i in range(100))
                      if canary_channel(k, 50.0) == "stable")
    canary_key = next(k for k in (f"c{i}" for i in range(100))
                      if canary_channel(k, 50.0) == "candidate")
    # no candidate installed: the canary slice degrades to stable
    _, ver, chan = gw.predict(x, key=canary_key)
    assert (ver, chan) == (1, "stable")
    gw.install("candidate", 2,
               pack_model(jax.tree.map(lambda a: np.asarray(a) * 3.0, v1)))
    _, ver, chan = gw.predict(x, key=canary_key)
    assert (ver, chan) == (2, "candidate")
    _, ver, chan = gw.predict(x, key=stable_key)
    assert (ver, chan) == (1, "stable")
    gw.shutdown()


def test_sync_installs_heads_and_uninstalls_promoted_candidate():
    from metisfl_tpu.registry import ModelRegistry

    reg = ModelRegistry(RegistryConfig(enabled=True, retention=3))

    class Source:
        def describe(self):
            return reg.describe()

        def blob(self, version):
            return reg.blob(version)

    gw, ops = _gateway(canary_percent=10.0)
    blob = pack_model(ops.get_variables())
    reg.register(0, blob, {})
    # candidate head installs even before any stable exists (the canary
    # model); stable-only traffic still fails fast until a promotion
    assert gw.sync(Source()) == {"candidate": 1}
    reg.promote(1, force=True)
    assert gw.sync(Source()) == {"stable": 1}
    reg.register(1, blob, {})
    assert gw.sync(Source()) == {"stable": 1, "candidate": 2}
    reg.promote(2, force=True)
    # candidate promoted away: the gateway uninstalls the canary model
    assert gw.sync(Source()) == {"stable": 2}
    gw.shutdown()


# ---------------------------------------------------------------------- #
# gRPC surface
# ---------------------------------------------------------------------- #

def test_grpc_predict_roundtrip_and_role_reflection(clean_telemetry):
    gw, ops = _gateway(max_batch=4)
    gw.install("stable", 5, pack_model(ops.get_variables()))
    server = ServingServer(gw, host="127.0.0.1", port=0)
    port = server.start()
    client = ServingClient("127.0.0.1", port)
    try:
        x = np.random.default_rng(3).standard_normal(
            (4, 4)).astype(np.float32)
        reply = client.predict(x, key="u1")
        np.testing.assert_array_equal(client.predictions(reply),
                                      ops.infer(x, batch_size=4))
        assert reply.model_version == 5 and reply.channel == "stable"
        status = client.status()
        assert status["installed"] == {"stable": 5}
        assert status["requests"] >= 1
        # ListMethods reflection distinguishes the gateway from
        # learner/controller endpoints (ISSUE satellite)
        reflection = client.list_methods()
        assert reflection["role"] == "serving"
        assert {"Predict", "GetServingStatus"} <= {
            m["name"] for m in reflection["methods"]}
        from metisfl_tpu.status import render_probe
        assert "role=serving" in render_probe(reflection)
        # the scrape surface reports the serving families
        text = client.get_metrics()
        assert "serving_requests_total" in text
        assert "serving_model_version" in text
    finally:
        client.close()
        server.stop()


def test_controller_and_learner_roles_reflected():
    from metisfl_tpu.comm.rpc import BytesService
    import json

    ctrl = BytesService("svc.ctrl", {}, role="controller")
    assert json.loads(ctrl._list_methods(b""))["role"] == "controller"
    plain = BytesService("svc.plain", {})
    assert "role" not in json.loads(plain._list_methods(b""))


# ---------------------------------------------------------------------- #
# end-to-end: federation -> registry -> gateway
# ---------------------------------------------------------------------- #

def test_inprocess_federation_feeds_gateway(clean_telemetry):
    """The whole lifecycle plane in one process: rounds aggregate →
    versions register → eval promotes → the gateway syncs and serves the
    promoted community model."""
    from metisfl_tpu.driver.inprocess import InProcessFederation

    rng = np.random.default_rng(3)
    w = rng.standard_normal((4, 2)).astype(np.float32)
    x = rng.standard_normal((32, 4)).astype(np.float32)
    y = np.argmax(x @ w, -1).astype(np.int32)

    config = FederationConfig(
        aggregation=AggregationConfig(scaler="participants"),
        train=TrainParams(batch_size=8, local_steps=2, learning_rate=0.1),
        eval=EvalConfig(every_n_rounds=1),
        registry=RegistryConfig(enabled=True, retention=3),
        serving=ServingConfig(enabled=True, max_batch=4,
                              canary_percent=20.0),
    )
    fed = InProcessFederation(config)
    for seed in range(2):
        fed.add_learner(_ops(seed=0, outputs=2),
                        ArrayDataset(x, y, seed=seed),
                        test_dataset=ArrayDataset(x, y))
    fed.seed_model(_ops(seed=0, outputs=2).get_variables())
    fed.start()
    try:
        assert fed.wait_for_rounds(3, timeout_s=120.0)
        assert fed.wait_for_evaluations(2, timeout_s=60.0)
        deadline = time.time() + 30.0
        while (fed.controller.describe_registry()["stable"] == 0
               and time.time() < deadline):
            time.sleep(0.05)
        desc = fed.controller.describe_registry()
        assert desc["stable"] > 0, desc

        # the federation runs on under all of this, rounds back to back,
        # and with retention=3 a version that is no longer a head or the
        # rollback target is erased once three newer ones stand: keep what
        # the gateway fetched, for the registry may not have it when asked
        # again (the predict below pays a compile in between)
        fetched = {}

        class KeepingSource(DirectRegistrySource):
            def blob(self, version):
                fetched[version] = super().blob(version)
                return fetched[version]

        source = KeepingSource(fed.controller)
        gw = ServingGateway(_ops(seed=0, outputs=2), config.serving)
        installed = gw.sync(source)
        deadline = time.time() + 30.0
        while "stable" not in installed and time.time() < deadline:
            # the head it was told of was erased under the poll: the next
            # poll, as in the gateway's own loop, finds the newer one
            installed = gw.sync(source)
        # the federation may promote again between the snapshot and the
        # sync — the gateway serves SOME promoted stable version
        assert installed.get("stable", 0) >= desc["stable"]
        outs, version, channel = gw.predict(x[:4], key="user1")
        assert outs.shape == (4, 2) and version == installed["stable"]
        # the served model IS the promoted community blob
        blob = fetched[version]
        assert blob
        still = fed.controller.registered_model(version=version)
        assert still is None or still == blob
        ref_ops = _ops(seed=0, outputs=2)
        ref = ServingGateway(ref_ops, config.serving)
        ref.install("stable", version, blob)
        ref_out, _, _ = ref.predict(x[:4], key="user1")
        np.testing.assert_array_equal(outs, ref_out)
        # per-round lineage reached experiment-side statistics
        from metisfl_tpu.stats import version_lineage
        lineage = version_lineage(fed.statistics())
        assert lineage and lineage[0]["registered"] == 1
        ref.shutdown()
        gw.shutdown()
    finally:
        fed.shutdown()


def test_disabled_serving_config_is_inert():
    # serving off is the default; enabling requires the registry, and the
    # disabled config constructs no gateway anywhere (driver-side guard)
    config = FederationConfig()
    assert not config.serving.enabled
    from metisfl_tpu.driver.session import DriverSession
    session = DriverSession(config, {"w": np.zeros((2, 2), np.float32)},
                            [lambda: None])
    with pytest.raises(RuntimeError, match="not enabled"):
        session.serving_client()


# --------------------------------------------------------------------- #
# the decode loop over a cache its calls consume (SlotDecoder donates it)
# --------------------------------------------------------------------- #

def _decode_engine(family, slots=2):
    """A ContinuousBatcher over a toy served family (``kv`` alone, or
    ``kv`` beside recurrent ``state``) and what it needs checking."""
    import jax
    import jax.numpy as jnp

    from metisfl_tpu.models.zoo import JambaLite, LlamaLite

    if family == "llama":
        module = LlamaLite(vocab_size=61, dim=32, depth=2, heads=4,
                           kv_heads=2)
    else:
        module = JambaLite(vocab_size=61, dim=32, depth=4, heads=4,
                           kv_heads=1, ffn_dim=80, attn_period=2,
                           attn_offset=1, d_state=8, dt_rank=4, lora_rank=2)
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    ops = FlaxModelOps(module, np.zeros((1, 8), np.int32),
                       variables=variables)
    engine = ContinuousBatcher(ops, 1, variables, slots=slots, max_len=32,
                               channel=f"donate-{family}")
    return engine, module, variables


@pytest.mark.parametrize("family", ["llama", "jamba"])
def test_decode_loop_survives_a_consumed_cache(family):
    """A call that fails with the cache's buffers gone costs that tick's
    requests and nothing after it: the decoder starts its slots over, the
    loop has emptied them, and the next request decodes what a solo
    ``generate`` decodes, bit for bit."""
    import jax

    from metisfl_tpu.models.generate import generate

    engine, module, variables = _decode_engine(family)
    try:
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, 61, (n,)).astype(np.int32)
                   for n in (6, 4, 9)]
        engine.submit(prompts[0], 4).result(timeout=120)      # warm, idle
        for leaf in jax.tree.leaves(engine._decoder.caches):
            leaf.delete()         # what a call that died mid-way leaves
        with pytest.raises(RuntimeError):
            engine.submit(prompts[1], 4).result(timeout=120)
        assert engine.describe()["loop"]["cache_resets"] == 1
        assert engine.active() == 0
        for prompt in prompts:
            got, version = engine.submit(prompt, 7).result(timeout=120)
            want = generate(module, variables, prompt[None], 7, max_len=32)
            assert version == 1 and list(got) == [int(t) for t in want[0]]
        loop = engine.describe()["loop"]
        assert loop["cache_resets"] == 1
        assert loop["donated_calls"] == loop["steps"] + loop["prefills"]
    finally:
        engine.close()


@pytest.mark.parametrize("family", ["llama", "jamba"])
def test_decode_loop_counts_every_call_as_donated(family, clean_telemetry):
    """After a small closed run every prefill and every step updated the
    cache in place: ``donated_calls`` = ``steps`` + ``prefills`` in
    ``describe()`` and summed over the ``decode.loop`` events, no reset,
    and the engine holds sizes of the cache, not its arrays."""
    import jax

    from metisfl_tpu.telemetry import trace as ttrace

    ttrace.configure(enabled=True, service="test", dir="")
    ttrace.configure_ring(4096)
    _, cursor, _ = ttrace.spans_since(0)
    engine, _, _ = _decode_engine(family)
    try:
        rng = np.random.default_rng(7)
        asks = [(rng.integers(1, 61, (int(n),)).astype(np.int32), int(o))
                for n, o in zip(rng.integers(2, 9, 6), rng.integers(2, 8, 6))]

        def client(mine):
            for prompt, out_len in mine:
                engine.submit(prompt, out_len).result(timeout=120)

        threads = [threading.Thread(target=client, args=(asks[i::3],))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        loop = engine.describe()["loop"]
        assert loop["prefills"] == 6 and loop["steps"] > 0
        assert loop["donated_calls"] == loop["steps"] + loop["prefills"]
        assert loop["cache_resets"] == 0
        # under load each step after a stretch's first was launched
        # before the last one was read; no eos, so no lane ran late
        assert 0 < loop["overlapped_steps"] < loop["steps"]
        assert loop["late_lanes"] == 0
        held = [v for v in vars(engine).values()
                if any(isinstance(leaf, jax.Array)
                       for leaf in jax.tree.leaves(v))]
        assert held == [engine._pair]       # the weights, never the cache
    finally:
        engine.close()
    events = [s["attrs"] for s in ttrace.spans_since(cursor)[0]
              if s["name"] == "decode.loop"
              and s["attrs"]["channel"] == f"donate-{family}"]
    ttrace.configure_ring(0)
    assert events
    assert sum(a["donated_calls"] for a in events) == loop["donated_calls"]
    assert sum(a["cache_resets"] for a in events) == 0


class _Unreadable:
    """A step's tokens whose device failed: enqueued, copy started, and
    the failure surfaces at the read."""

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("the device gave up")


@pytest.mark.parametrize("family", ["llama", "jamba"])
def test_decode_loop_fails_everything_in_flight_on_a_failed_read(family):
    """A step whose failure surfaces at its read, a tick after the next
    step consumed the state: every request in flight fails, the decoder
    starts its slots over once, and the next requests decode what a solo
    ``generate`` decodes, bit for bit."""
    from metisfl_tpu.models.generate import generate

    engine, module, variables = _decode_engine(family)
    try:
        rng = np.random.default_rng(9)
        prompts = [rng.integers(1, 61, (n,)).astype(np.int32)
                   for n in (5, 8, 3)]
        engine.submit(prompts[0], 4).result(timeout=120)      # warm, idle
        decoder = engine._decoder
        real = decoder._step_fn

        def fails_at_read(variables, state):
            state, _ = real(variables, state)   # the state is handed on
            return state, _Unreadable()

        decoder._step_fn = fails_at_read
        failed = [engine.submit(p, 6) for p in prompts[:2]]
        for fut in failed:
            with pytest.raises(RuntimeError, match="gave up"):
                fut.result(timeout=120)
        loop = engine.describe()["loop"]
        assert loop["cache_resets"] == 1
        assert engine.active() == 0 and engine._inflight is None
        decoder._step_fn = real
        for prompt in prompts:
            got, version = engine.submit(prompt, 7).result(timeout=120)
            want = generate(module, variables, prompt[None], 7, max_len=32)
            assert version == 1 and list(got) == [int(t) for t in want[0]]
        loop = engine.describe()["loop"]
        assert loop["cache_resets"] == 1
        assert loop["donated_calls"] == loop["steps"] + loop["prefills"]
    finally:
        engine.close()
