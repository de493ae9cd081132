"""gRPC bytes-transport unit tests."""

import os
import threading

import pytest

from metisfl_tpu.comm.codec import dumps, loads
from metisfl_tpu.comm.rpc import BytesService, RpcClient, RpcServer


@pytest.fixture()
def echo_server():
    state = {"count": 0}

    def echo(payload: bytes) -> bytes:
        state["count"] += 1
        return payload

    def boom(payload: bytes) -> bytes:
        raise RuntimeError("kaboom")

    server = RpcServer("127.0.0.1", 0)
    server.add_service(BytesService("test.Echo", {"Echo": echo, "Boom": boom}))
    port = server.start()
    yield port, state
    server.stop()


def test_unary_roundtrip(echo_server):
    port, state = echo_server
    client = RpcClient("127.0.0.1", port, "test.Echo")
    payload = dumps({"x": 1, "blob": b"\x00" * 1000})
    assert loads(client.call("Echo", payload)) == loads(payload)
    assert state["count"] == 1
    client.close()


def test_async_call(echo_server):
    port, _ = echo_server
    client = RpcClient("127.0.0.1", port, "test.Echo")
    done = threading.Event()
    result = {}

    def cb(raw):
        result["raw"] = raw
        done.set()

    client.call_async("Echo", b"hello", callback=cb)
    assert done.wait(10)
    assert result["raw"] == b"hello"
    client.close()


def test_handler_error_propagates(echo_server):
    import grpc

    port, _ = echo_server
    client = RpcClient("127.0.0.1", port, "test.Echo")
    with pytest.raises(grpc.RpcError) as err:
        client.call("Boom", b"")
    assert err.value.code() == grpc.StatusCode.INTERNAL
    assert "kaboom" in err.value.details()
    client.close()


def test_async_error_callback(echo_server):
    port, _ = echo_server
    client = RpcClient("127.0.0.1", port, "test.Echo")
    done = threading.Event()
    errors = []

    client.call_async("Boom", b"", callback=lambda r: done.set(),
                      error_callback=lambda e: (errors.append(e), done.set()))
    assert done.wait(10)
    assert errors
    client.close()


def test_large_payload(echo_server):
    # >4MB default gRPC limit must pass (unlimited message size option)
    port, _ = echo_server
    client = RpcClient("127.0.0.1", port, "test.Echo")
    payload = b"\xab" * (8 * 1024 * 1024)
    assert client.call("Echo", payload) == payload
    client.close()


def test_lineage_tail_rpcs():
    """Tail-bounded lineage getters (reference controller.proto:27-44):
    polling must not ship the whole round history."""
    from metisfl_tpu.config import FederationConfig
    from metisfl_tpu.controller.core import Controller, RoundMetadata
    from metisfl_tpu.controller.service import ControllerClient, ControllerServer

    controller = Controller(FederationConfig(), lambda record: None)
    # synthesize a 5-round history
    for i in range(5):
        controller.round_metadata.append(RoundMetadata(global_iteration=i))
        controller.community_evaluations.append(
            {"global_iteration": i, "evaluations": {}})
    controller.global_iteration = 5
    server = ControllerServer(controller, host="127.0.0.1", port=0)
    port = server.start()
    client = ControllerClient("127.0.0.1", port)
    try:
        out = client.get_runtime_metadata(tail=2)
        assert out["global_iteration"] == 5
        assert [m["global_iteration"] for m in out["round_metadata"]] == [3, 4]
        assert len(client.get_runtime_metadata()["round_metadata"]) == 5
        evals = client.get_evaluation_lineage(tail=3)
        assert [e["global_iteration"] for e in evals] == [2, 3, 4]
    finally:
        client.close()
        server.stop()


# ---------------------------------------------------------------------- #
# chunked transfer (SURVEY.md §7: budget for chunked/streaming transfer)
# ---------------------------------------------------------------------- #


def test_chunked_roundtrip_multi_frame(echo_server, monkeypatch):
    """Payloads above the stream threshold frame into chunks and
    reassemble exactly, both directions."""
    from metisfl_tpu.comm import rpc

    monkeypatch.setattr(rpc, "STREAM_THRESHOLD", 1024)
    monkeypatch.setattr(rpc, "CHUNK_BYTES", 4096)
    port, state = echo_server
    client = RpcClient("127.0.0.1", port, "test.Echo")
    import os

    payload = os.urandom(64 * 1024 + 7)  # 17 frames, ragged tail
    assert client.call("Echo", payload) == payload
    assert state["count"] == 1
    client.close()


def test_oversize_unary_response_retries_chunked(echo_server, monkeypatch):
    """A small request whose RESPONSE exceeds unary framing is refused
    with RESOURCE_EXHAUSTED server-side and transparently re-issued over
    the chunked stream."""
    from metisfl_tpu.comm import rpc

    monkeypatch.setattr(rpc, "UNARY_RESPONSE_LIMIT", 100)
    monkeypatch.setattr(rpc, "CHUNK_BYTES", 64)
    port, state = echo_server
    client = RpcClient("127.0.0.1", port, "test.Echo")
    payload = b"\xab" * 1000  # small request, >limit response
    assert client.call("Echo", payload) == payload
    assert state["count"] == 2  # unary attempt + chunked retry
    # the client remembers the method needs chunking: the next call goes
    # straight to the stream — no second wasted handler execution
    assert client.call("Echo", payload) == payload
    assert state["count"] == 3
    client.close()


def test_async_chunked(echo_server, monkeypatch):
    from metisfl_tpu.comm import rpc

    monkeypatch.setattr(rpc, "STREAM_THRESHOLD", 1024)
    monkeypatch.setattr(rpc, "CHUNK_BYTES", 2048)
    port, _ = echo_server
    client = RpcClient("127.0.0.1", port, "test.Echo")
    done = threading.Event()
    result = {}

    def cb(raw):
        result["raw"] = raw
        done.set()

    payload = b"\xcd" * 10_000
    client.call_async("Echo", payload, callback=cb)
    assert done.wait(30)
    assert result["raw"] == payload
    client.close()


def test_async_future_resolves_with_final_outcome(echo_server, monkeypatch):
    """Regression (the double signal): the future call_async returns
    must resolve only with the FINAL outcome. On the unary-oversize →
    chunked retry the old code handed back the grpc future of the FAILED
    unary attempt, so a caller inspecting it saw RESOURCE_EXHAUSTED for a
    call that then succeeded via callback."""
    from metisfl_tpu.comm import rpc

    monkeypatch.setattr(rpc, "UNARY_RESPONSE_LIMIT", 100)
    monkeypatch.setattr(rpc, "CHUNK_BYTES", 64)
    port, state = echo_server
    client = RpcClient("127.0.0.1", port, "test.Echo")
    payload = b"\xab" * 1000  # small request, >limit response
    future = client.call_async("Echo", payload)
    assert future.result(timeout=30) == payload  # NOT the oversize error
    assert future.exception() is None
    assert state["count"] == 2  # unary attempt + chunked retry happened

    # plain success resolves the wrapper too
    small = client.call_async("Boom", b"", error_callback=lambda e: None)
    with pytest.raises(Exception, match="kaboom"):
        small.result(timeout=30)

    # remembered-chunked path: straight to the stream, still one future
    again = client.call_async("Echo", payload)
    assert again.result(timeout=30) == payload
    client.close()


def test_list_methods_reflection(echo_server):
    """Every BytesService answers ListMethods (gRPC-reflection parity):
    JSON method names + transport capability flags, including itself."""
    import json

    port, _ = echo_server
    client = RpcClient("127.0.0.1", port, "test.Echo")
    raw = client.call("ListMethods", b"", timeout=10)
    reflection = json.loads(raw.decode("utf-8"))
    assert reflection["service"] == "test.Echo"
    names = {m["name"] for m in reflection["methods"]}
    assert {"Echo", "Boom", "ListMethods"} <= names
    for m in reflection["methods"]:
        assert m["transports"] == ["unary", "chunked"]
        assert m["oversize_unary_fallback"] is True
    client.close()


def test_chunked_handler_error_propagates(echo_server, monkeypatch):
    import grpc

    from metisfl_tpu.comm import rpc

    monkeypatch.setattr(rpc, "STREAM_THRESHOLD", 16)
    port, _ = echo_server
    client = RpcClient("127.0.0.1", port, "test.Echo", retries=0)
    with pytest.raises(grpc.RpcError) as err:
        client.call("Boom", b"x" * 64, timeout=10)
    assert err.value.code() == grpc.StatusCode.INTERNAL
    assert "kaboom" in err.value.details()
    client.close()


# ---------------------------------------------------------------------- #
# RPC telemetry (metisfl_tpu/telemetry): logical-call accounting
# ---------------------------------------------------------------------- #


@pytest.fixture()
def rpc_metrics():
    from metisfl_tpu import telemetry
    from metisfl_tpu.telemetry import metrics as tmetrics

    tmetrics.set_enabled(True)
    telemetry.registry().reset()
    yield telemetry.registry()
    telemetry.registry().reset()


def test_oversize_retry_counts_one_logical_call(echo_server, monkeypatch,
                                                rpc_metrics):
    """Regression contract: the documented fail-then-retry path (unary
    oversize → chunked retry, see _OVERSIZE_MARK) reports ONE logical
    client call with retried="1" — not two — while the server-side
    handler-invocation counter visibly shows both executions."""
    from metisfl_tpu.comm import rpc

    monkeypatch.setattr(rpc, "UNARY_RESPONSE_LIMIT", 100)
    monkeypatch.setattr(rpc, "CHUNK_BYTES", 64)
    port, state = echo_server
    client = RpcClient("127.0.0.1", port, "test.Echo")
    payload = b"\xab" * 1000  # small request, >limit response
    assert client.call("Echo", payload) == payload
    calls = rpc_metrics.counter("rpc_client_calls_total", "",
                                ("service", "method", "retried"))
    assert calls.value(service="test.Echo", method="Echo", retried="1") == 1
    assert calls.value(service="test.Echo", method="Echo", retried="0") == 0
    invocations = rpc_metrics.counter("rpc_server_calls_total", "",
                                      ("service", "method", "transport"))
    assert invocations.value(service="test.Echo", method="Echo",
                             transport="unary") == 1
    assert invocations.value(service="test.Echo", method="Echo",
                             transport="chunked") == 1
    # the remembered-chunked second call is one more logical call, now
    # without a retry and with exactly one more handler invocation
    assert client.call("Echo", payload) == payload
    assert calls.value(service="test.Echo", method="Echo", retried="0") == 1
    assert invocations.value(service="test.Echo", method="Echo",
                             transport="chunked") == 2
    client.close()


def test_async_error_without_callback_is_counted_and_logged(
        echo_server, rpc_metrics, caplog):
    """call_async with no error_callback must not swallow the failure:
    warning log + rpc_client_errors_total increment."""
    import logging as _logging

    port, _ = echo_server
    client = RpcClient("127.0.0.1", port, "test.Echo")
    errors = rpc_metrics.counter("rpc_client_errors_total", "",
                                 ("service", "method", "code"))
    with caplog.at_level(_logging.WARNING, logger="metisfl_tpu.rpc"):
        future = client.call_async("Boom", b"")
        deadline = threading.Event()
        for _ in range(100):
            if errors.value(service="test.Echo", method="Boom",
                            code="INTERNAL") >= 1:
                break
            deadline.wait(0.1)
    assert errors.value(service="test.Echo", method="Boom",
                        code="INTERNAL") == 1
    # the failed call still counts as one logical call, keeping
    # errors_total/calls_total a valid rate (<= 1)
    calls = rpc_metrics.counter("rpc_client_calls_total", "",
                                ("service", "method", "retried"))
    assert calls.value(service="test.Echo", method="Boom", retried="0") == 1
    assert any("no error_callback" in r.getMessage()
               for r in caplog.records)
    client.close()


# ---------------------------------------------------------------------- #
# default deadlines (comm.default_deadline_s) + status mapping
# ---------------------------------------------------------------------- #


@pytest.fixture()
def slow_server():
    import time as _time

    from metisfl_tpu.comm.rpc import BytesService, RpcServer

    state = {"calls": 0}

    def sleepy(payload: bytes) -> bytes:
        _time.sleep(1.0)
        return b"late"

    def flaky(payload: bytes) -> bytes:
        # first invocation hangs past the client deadline; the retry is fast
        state["calls"] += 1
        if state["calls"] == 1:
            _time.sleep(1.0)
        return b"ok"

    def reject(payload: bytes) -> bytes:
        raise ValueError("malformed widget")

    server = RpcServer("127.0.0.1", 0)
    server.add_service(BytesService(
        "test.Slow", {"Sleepy": sleepy, "Flaky": flaky, "Reject": reject}))
    port = server.start()
    yield port, state
    server.stop()


def test_default_deadline_bounds_unbounded_calls(slow_server):
    """timeout=None no longer means unbounded: the client-level default
    deadline applies, so one hung peer cannot park a thread forever."""
    import grpc

    from metisfl_tpu.comm.rpc import RpcClient

    port, _ = slow_server
    client = RpcClient("127.0.0.1", port, "test.Slow", retries=0,
                       default_deadline_s=0.2)
    try:
        with pytest.raises(grpc.RpcError) as err:
            client.call("Sleepy", b"")  # no explicit timeout
        assert err.value.code() == grpc.StatusCode.DEADLINE_EXCEEDED
    finally:
        client.close()


def test_deadline_default_can_be_disabled(slow_server):
    """default_deadline_s <= 0 restores the old unbounded behavior."""
    from metisfl_tpu.comm.rpc import RpcClient

    port, _ = slow_server
    client = RpcClient("127.0.0.1", port, "test.Slow", retries=0,
                       default_deadline_s=0)
    try:
        assert client.call("Sleepy", b"") == b"late"
    finally:
        client.close()


def test_deadline_exceeded_retried_only_for_idempotent(slow_server):
    import grpc

    from metisfl_tpu.comm.rpc import RpcClient

    port, state = slow_server
    client = RpcClient("127.0.0.1", port, "test.Slow", retries=3,
                       retry_sleep_s=0.05, default_deadline_s=0.4)
    try:
        # non-idempotent (default): DEADLINE_EXCEEDED is terminal
        with pytest.raises(grpc.RpcError) as err:
            client.call("Flaky", b"")
        assert err.value.code() == grpc.StatusCode.DEADLINE_EXCEEDED
        state["calls"] = 0
        # idempotent: the deadline miss is retried and the retry lands
        assert client.call("Flaky", b"", idempotent=True) == b"ok"
        assert state["calls"] == 2
    finally:
        client.close()


def test_value_error_maps_to_invalid_argument(slow_server):
    """Malformed-input rejections (codec framing, blob integrity) surface
    as INVALID_ARGUMENT, not INTERNAL — retry ladders must not treat a
    corrupt payload as a transient server failure."""
    import grpc

    from metisfl_tpu.comm.rpc import RpcClient

    port, _ = slow_server
    client = RpcClient("127.0.0.1", port, "test.Slow", retries=0)
    try:
        with pytest.raises(grpc.RpcError) as err:
            client.call("Reject", b"", timeout=10)
        assert err.value.code() == grpc.StatusCode.INVALID_ARGUMENT
        assert "malformed widget" in err.value.details()
    finally:
        client.close()


# ---------------------------------------------------------------------- #
# segmented payloads (comm/codec.py Segments): gathered by the transport
# ---------------------------------------------------------------------- #

def _segmented(n_bulk: int = 2):
    """A blob-bearing message as the codec hands it to the transport:
    heads and tails around bulk segments that are the caller's objects."""
    import os

    from metisfl_tpu.comm.codec import BORROW_MIN_BYTES, dumps_segments

    value = {"task_id": "t1", "round": 3}
    for i in range(n_bulk):
        value[f"model{i}"] = os.urandom(BORROW_MIN_BYTES + 1000 * i + 17)
    value["tail"] = "x" * 100
    segments = dumps_segments(value)
    assert len(segments.parts) == 2 * n_bulk + 1
    return segments, dumps(value)


def test_chunk_frames_cross_segment_borders(monkeypatch):
    """The chunker fills frames across segment borders: every frame but
    the last is CHUNK_BYTES, and they are the frames of the joined
    payload."""
    from metisfl_tpu.comm import rpc
    from metisfl_tpu.comm.codec import Segments

    monkeypatch.setattr(rpc, "CHUNK_BYTES", 100_000)
    segments, wire = _segmented()
    frames = list(rpc._iter_chunks(segments))
    assert frames == list(rpc._iter_chunks(wire))
    assert all(type(f) is bytes for f in frames)
    assert {len(f) for f in frames[:-1]} == {100_000}
    assert 0 < len(frames[-1]) <= 100_000 and b"".join(frames) == wire
    # re-iterable (a retry re-sends), and the empty payload is one frame
    assert list(rpc._iter_chunks(segments)) == frames
    assert list(rpc._iter_chunks(Segments([b""]))) == [b""]
    assert list(rpc._iter_chunks(b"")) == [b""]
    # a frame boundary landing exactly on a segment border
    exact = Segments([b"a" * 100_000, b"b" * 200_000, b"c" * 5])
    assert [len(f) for f in rpc._iter_chunks(exact)] == [
        100_000, 100_000, 100_000, 5]


@pytest.mark.parametrize("transport", ["unary", "chunked"])
@pytest.mark.parametrize("mode", ["call", "call_async"])
def test_segmented_payload_roundtrip(echo_server, monkeypatch, rpc_metrics,
                                     transport, mode):
    """A segment list round-trips over both methods, sync and async, as
    the bytes of its join; one logical call, the message's bytes counted."""
    from metisfl_tpu.comm import rpc

    if transport == "chunked":
        monkeypatch.setattr(rpc, "STREAM_THRESHOLD", 1024)
        monkeypatch.setattr(rpc, "CHUNK_BYTES", 100_000)
    port, state = echo_server
    client = RpcClient("127.0.0.1", port, "test.Echo")
    segments, wire = _segmented()
    if mode == "call":
        assert client.call("Echo", segments) == wire
    else:
        got = {}
        future = client.call_async(
            "Echo", segments, callback=lambda raw: got.update(raw=raw))
        assert future.result(timeout=30) == wire
        assert got["raw"] == wire
    assert state["count"] == 1
    calls = rpc_metrics.counter("rpc_client_calls_total", "",
                                ("service", "method", "retried"))
    assert calls.value(service="test.Echo", method="Echo", retried="0") == 1
    sent = rpc_metrics.counter("rpc_client_bytes_total", "",
                               ("service", "method", "direction"))
    assert sent.value(service="test.Echo", method="Echo",
                      direction="sent") == len(wire)
    invocations = rpc_metrics.counter("rpc_server_calls_total", "",
                                      ("service", "method", "transport"))
    assert invocations.value(service="test.Echo", method="Echo",
                             transport=transport) == 1
    client.close()


@pytest.mark.parametrize("transport", ["unary", "chunked"])
def test_segmented_payload_survives_unavailable_retry(monkeypatch,
                                                      rpc_metrics, transport):
    """UNAVAILABLE on the first attempt: the retry re-sends the same
    segments and the handler gets the message intact, once; still one
    logical call, marked retried."""
    import grpc

    from metisfl_tpu.comm import rpc

    class _Unavailable(Exception):
        def code(self):
            return grpc.StatusCode.UNAVAILABLE

    seen = []

    def flaky(payload: bytes) -> bytes:
        seen.append(payload)
        if len(seen) == 1:
            raise _Unavailable("try again")
        return dumps({"n": len(payload)})

    if transport == "chunked":
        monkeypatch.setattr(rpc, "STREAM_THRESHOLD", 1024)
        monkeypatch.setattr(rpc, "CHUNK_BYTES", 100_000)
    server = RpcServer("127.0.0.1", 0)
    server.add_service(BytesService("test.Flaky", {"Put": flaky}))
    port = server.start()
    client = RpcClient("127.0.0.1", port, "test.Flaky", retry_sleep_s=0.01)
    try:
        segments, wire = _segmented()
        assert loads(client.call("Put", segments)) == {"n": len(wire)}
        assert seen == [wire, wire]
        calls = rpc_metrics.counter("rpc_client_calls_total", "",
                                    ("service", "method", "retried"))
        assert calls.value(service="test.Flaky", method="Put",
                           retried="1") == 1
        assert calls.value(service="test.Flaky", method="Put",
                           retried="0") == 0
    finally:
        client.close()
        server.stop()


@pytest.mark.parametrize("transport", ["unary", "chunked"])
@pytest.mark.parametrize("mode", ["call", "call_async"])
def test_chaos_injector_sees_bytes(echo_server, monkeypatch, transport, mode):
    """With an injector installed the payload is joined first:
    ``intercept`` keeps seeing (and may corrupt) plain bytes."""
    from metisfl_tpu.comm import rpc

    class _Recorder:
        def __init__(self):
            self.seen = []

        def intercept(self, side, service, method, payload):
            self.seen.append((side, type(payload), len(payload)))
            return payload

    recorder = _Recorder()
    monkeypatch.setattr(rpc._chaos, "get", lambda: recorder)
    if transport == "chunked":
        monkeypatch.setattr(rpc, "STREAM_THRESHOLD", 1024)
        monkeypatch.setattr(rpc, "CHUNK_BYTES", 100_000)
    port, _ = echo_server
    client = RpcClient("127.0.0.1", port, "test.Echo")
    segments, wire = _segmented(n_bulk=1)
    if mode == "call":
        assert client.call("Echo", segments) == wire
    else:
        assert client.call_async("Echo", segments).result(timeout=30) == wire
    assert recorder.seen == [("client", bytes, len(wire)),
                             ("server", bytes, len(wire))]
    client.close()


def test_checkpoint_written_from_segments(tmp_path):
    """save_checkpoint writes the state's segments in order: the file is
    ``dumps(state)`` byte for byte, the community blob went to it as the
    object it is, and a fresh controller restores the same state."""
    import numpy as np

    from metisfl_tpu.comm import codec
    from metisfl_tpu.comm.messages import JoinRequest, TrainParams
    from metisfl_tpu.config import (AggregationConfig, CheckpointConfig,
                                    EvalConfig, FederationConfig)
    from metisfl_tpu.controller.core import Controller
    from metisfl_tpu.tensor.pytree import pack_model

    class _Proxy:
        def run_task(self, task):
            pass

        def evaluate(self, task, callback):
            pass

        def shutdown(self):
            pass

    def controller():
        return Controller(FederationConfig(
            protocol="asynchronous",
            aggregation=AggregationConfig(rule="fedavg",
                                          scaler="participants"),
            train=TrainParams(batch_size=4, local_steps=1),
            eval=EvalConfig(every_n_rounds=0),
            checkpoint=CheckpointConfig(dir=str(tmp_path / "ckpt"),
                                        every_n_rounds=1)),
            lambda record: _Proxy())

    rng = np.random.default_rng(0)
    blob = pack_model({"w": rng.standard_normal((300, 300)).astype(np.float32)})
    assert len(blob) >= codec.BORROW_MIN_BYTES
    ctrl = controller()
    try:
        ctrl.set_community_model(blob)
        joins = [ctrl.join(JoinRequest(hostname="h", port=7000 + i,
                                       num_train_examples=5))
                 for i in range(2)]
        state = ctrl._checkpoint_state()
        segments = codec.dumps_segments(state)
        assert any(p is state["community_blob"] for p in segments.parts)
        # a path of its own: the controller's coalesced saver (armed by
        # the joins) writes checkpoint.dir on its own thread
        path = ctrl.save_checkpoint(path=str(tmp_path / "mine" / "ckpt.bin"),
                                    state=state)
        with open(path, "rb") as fh:
            on_disk = fh.read()
        assert on_disk == codec.dumps(state) == bytes(segments)
        assert os.listdir(tmp_path / "mine") == ["ckpt.bin"]
    finally:
        ctrl.shutdown()
    ctrl2 = controller()
    try:
        assert ctrl2.restore_checkpoint(path)
        assert ctrl2.community_model_bytes() == blob
        assert sorted(ctrl2.active_learners()) == sorted(
            j.learner_id for j in joins)
        restored = ctrl2._checkpoint_state()
        for key in ("global_iteration", "community_blob", "round_metadata",
                    "community_evaluations"):
            assert restored[key] == state[key]
    finally:
        ctrl2.shutdown()


def _available_ram_gb() -> float:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) / 1e6
    except OSError:
        pass
    return 0.0


@pytest.mark.skipif(_available_ram_gb() < 12.0,
                    reason="needs ~8 GB free RAM for the 2 GiB round-trip")
def test_beyond_2gib_roundtrip(echo_server):
    """THE wall the reference never solved: a single blob past protobuf's
    ~2 GiB per-message framing (an 8.8B-param bf16 model is ~17.6 GB)
    round-trips through the standard call() API via chunked streaming —
    real constants, no tuned-down thresholds."""
    port, state = echo_server
    client = RpcClient("127.0.0.1", port, "test.Echo")
    n = (2 << 30) + (1 << 20)  # 2 GiB + 1 MiB
    payload = bytearray(n)
    payload[:8] = b"HEADMARK"
    payload[-8:] = b"TAILMARK"
    payload = bytes(payload)
    result = client.call("Echo", payload, timeout=600)
    assert len(result) == n
    assert result[:8] == b"HEADMARK" and result[-8:] == b"TAILMARK"
    assert result == payload
    assert state["count"] == 1
    client.close()
