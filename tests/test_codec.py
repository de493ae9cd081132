"""Codec + message schema round-trip tests."""

import pytest

from metisfl_tpu.comm import dumps, loads
from metisfl_tpu.comm.messages import (
    EvalResult,
    EvalTask,
    JoinReply,
    JoinRequest,
    TaskResult,
    TrainParams,
    TrainTask,
)


@pytest.mark.parametrize(
    "value",
    [
        None,
        True,
        False,
        0,
        1,
        -1,
        127,
        -128,
        2**40,
        -(2**40),
        3.5,
        -0.0,
        "",
        "héllo wörld",
        b"",
        b"\x00\xff\x80",
        [],
        [1, "two", 3.0, None, [True]],
        {},
        {"a": 1, "b": {"c": [1, 2, 3]}, "d": b"raw"},
    ],
)
def test_codec_roundtrip(value):
    assert loads(dumps(value)) == value


def test_codec_rejects_non_str_keys():
    with pytest.raises(TypeError):
        dumps({1: "x"})


def test_codec_large_nested():
    value = {"k%d" % i: list(range(i)) for i in range(50)}
    assert loads(dumps(value)) == value


def test_train_task_roundtrip():
    task = TrainTask(
        task_id="t1",
        learner_id="L0",
        round_id=3,
        global_iteration=7,
        model=b"\x01\x02blob",
        params=TrainParams(batch_size=64, local_steps=10, learning_rate=0.1,
                           optimizer="adam", optimizer_kwargs={"b1": 0.9},
                           proximal_mu=0.01),
    )
    out = TrainTask.from_wire(task.to_wire())
    assert out == task
    assert isinstance(out.params, TrainParams)


def test_task_result_roundtrip():
    result = TaskResult(
        task_id="t1", learner_id="L0", round_id=3, model=b"m",
        num_train_examples=1000, completed_steps=20, completed_epochs=1.5,
        completed_batches=20, processing_ms_per_step=12.5,
        train_metrics={"loss": 0.5}, epoch_metrics=[{"loss": 0.9}, {"loss": 0.5}],
    )
    assert TaskResult.from_wire(result.to_wire()) == result


def test_join_roundtrip():
    req = JoinRequest(hostname="h", port=50052, num_train_examples=600,
                      previous_id="L9", auth_token="tok")
    assert JoinRequest.from_wire(req.to_wire()) == req
    rep = JoinReply(learner_id="L1", auth_token="abc", rejoined=True)
    assert JoinReply.from_wire(rep.to_wire()) == rep


def test_eval_roundtrip():
    task = EvalTask(task_id="e1", model=b"m", datasets=["train", "test"],
                    metrics=["loss"])
    assert EvalTask.from_wire(task.to_wire()) == task
    res = EvalResult(task_id="e1", evaluations={"test": {"loss": 0.25, "accuracy": 0.9}},
                     duration_ms=42.0)
    assert EvalResult.from_wire(res.to_wire()) == res


def test_codec_int64_bounds():
    assert loads(dumps(-(2**63))) == -(2**63)
    assert loads(dumps(2**63 - 1)) == 2**63 - 1
    with pytest.raises(OverflowError):
        dumps(2**63)
    with pytest.raises(OverflowError):
        dumps(-(2**63) - 1)


def test_codec_truncation_raises():
    for value in ["hello world", b"abcdef", [1, 2, 3], {"k": 1.5}, 3.25]:
        buf = dumps(value)
        for cut in (1, 3, 4):
            if cut < len(buf):
                with pytest.raises(ValueError):
                    loads(buf[:-cut])


def test_codec_numpy_scalars():
    import numpy as np
    out = loads(dumps({"loss": np.float32(0.5), "n": np.int64(3), "b": np.bool_(True)}))
    assert out == {"loss": 0.5, "n": 3, "b": True}


def test_codec_memoryview_itemsize():
    import numpy as np
    mv = np.arange(4, dtype=np.int32).data
    assert loads(dumps({"p": mv})) == {"p": np.arange(4, dtype=np.int32).tobytes()}


def test_codec_varint_overflow_rejected():
    with pytest.raises(ValueError):
        loads(b"\x03" + b"\xff" * 30 + b"\x01")


def test_codec_random_garbage_never_crashes():
    """The wire boundary sees attacker-controlled bytes: decoding garbage
    must raise a clean ValueError (never hang, crash, or silently decode a
    prefix). Anything that does decode must round-trip losslessly."""
    import numpy as np

    rng = np.random.default_rng(0)
    for n in (0, 1, 3, 17, 256, 4096):
        for _ in range(50):
            blob = rng.bytes(n) if n else b""
            try:
                value = loads(blob)
            except ValueError:
                continue
            assert loads(dumps(value)) == value


def test_codec_rejects_trailing_bytes():
    """A decoded value must consume the whole buffer — accepting trailing
    junk would silently return wrong values on framing errors."""
    with pytest.raises(ValueError, match="trailing"):
        loads(dumps({"a": 1}) + b"\xde\xad")


def test_codec_deep_nesting_bounded():
    """Nesting is bounded: real messages round-trip, crafted ~2-bytes-per-
    level nesting raises a clean ValueError instead of RecursionError."""
    value = 1
    for _ in range(60):
        value = [value]
    assert loads(dumps(value)) == value
    bomb = b"\x07\x01" * 2000 + b"\x00"
    with pytest.raises(ValueError, match="nesting"):
        loads(bomb)


# ---------------------------------------------------------------------- #
# segments and views: the codec never copies a bulk bytes value
# ---------------------------------------------------------------------- #

from metisfl_tpu.comm import codec  # noqa: E402
from metisfl_tpu.comm.codec import BORROW_MIN_BYTES as _T  # noqa: E402
from metisfl_tpu.comm.messages import (  # noqa: E402
    GenerateReply,
    GenerateRequest,
    InferResult,
    InferTask,
    ServeReply,
    ServeRequest,
)


def _reference_dumps(value) -> bytes:
    """The encoder as it stood before segments (one growing buffer, every
    value copied in): the reference the wire bytes are held to."""
    import struct

    import numpy as np

    out = bytearray()

    def varint(n):
        while True:
            byte, n = n & 0x7F, n >> 7
            out.append(byte | 0x80 if n else byte)
            if not n:
                return

    def enc(v):
        if isinstance(v, np.generic):
            v = v.item()
        if v is None:
            out.append(0x00)
        elif v is True:
            out.append(0x02)
        elif v is False:
            out.append(0x01)
        elif isinstance(v, int):
            out.append(0x03)
            varint((v << 1) ^ (v >> 63) if v < 0 else v << 1)
        elif isinstance(v, float):
            out.append(0x04)
            out.extend(struct.pack("<d", v))
        elif isinstance(v, str):
            raw = v.encode("utf-8")
            out.append(0x05)
            varint(len(raw))
            out.extend(raw)
        elif isinstance(v, (bytes, bytearray, memoryview)):
            raw = bytes(v)
            out.append(0x06)
            varint(len(raw))
            out.extend(raw)
        elif isinstance(v, (list, tuple)):
            out.append(0x07)
            varint(len(v))
            for item in v:
                enc(item)
        else:
            out.append(0x08)
            varint(len(v))
            for key, item in v.items():
                raw = key.encode("utf-8")
                varint(len(raw))
                out.extend(raw)
                enc(item)

    enc(value)
    return bytes(out)


def _blob(n: int, salt: int = 0) -> bytes:
    return bytes((i * 31 + salt) & 0xFF for i in range(251)) * (n // 251 + 1)


def _nested(sizes):
    """A nested message with one bytes field per entry of ``sizes``, at
    different depths, scalars and strings between them."""
    fields = [_blob(n, salt)[:n] for salt, n in enumerate(sizes)]
    value = {"task_id": "t1", "round": 3, "lr": 0.5, "ok": True,
             "inner": {"names": ["a", "b"], "none": None},
             "tail": "after the blobs"}
    if fields:
        value["inner"]["model"] = fields[0]
    if len(fields) > 1:
        value["list"] = [1, fields[1], {"deep": [fields[1][:7]]}]
    return value, fields


@pytest.mark.parametrize("sizes", [
    (), (_T - 1,), (_T,), (_T + 1,), (3 * _T + 5,),
    (_T - 1, _T - 1), (_T, _T - 1), (_T - 1, _T), (_T, _T), (_T + 1, 2 * _T),
], ids=lambda s: "x".join(map(str, s)) or "none")
def test_segments_join_equals_dumps(sizes):
    """0, 1 and 2 bytes fields, sizes on either side of the threshold:
    the segments' join, ``dumps`` and the old encoder give the same
    bytes, and each bulk segment IS the object that was passed in."""
    value, fields = _nested(sizes)
    segments = codec.dumps_segments(value)
    wire = codec.dumps(value)
    assert wire == _reference_dumps(value)
    assert bytes(segments) == wire and len(segments) == len(wire)
    bulk = [f for f in fields if len(f) >= _T]
    borrowed = [p for p in segments.parts if any(p is f for f in bulk)]
    assert len(borrowed) == len(bulk)
    assert len(segments.parts) == 2 * len(bulk) + 1
    assert segments.borrowed_bytes == sum(map(len, bulk))
    assert loads(wire) == value


@pytest.mark.parametrize("kind", [bytearray, memoryview])
def test_segments_borrow_any_byte_buffer(kind):
    blob = kind(_blob(_T)[:_T])
    segments = codec.dumps_segments({"model": blob, "n": 1})
    assert segments.parts[1] is blob
    assert bytes(segments) == _reference_dumps({"model": blob, "n": 1})
    # a view that is not plain bytes (wider items) is copied, as before
    import numpy as np
    wide = np.arange(_T, dtype=np.int32).data
    segments = codec.dumps_segments({"p": wide})
    assert bytes(segments) == _reference_dumps({"p": wide})
    assert all(isinstance(p, bytes) for p in segments.parts)


_BULK = _blob(_T + 123)[:_T + 123]


@pytest.mark.parametrize("message", [
    TrainTask(task_id="t", learner_id="L0", model=_BULK, control=_BULK,
              params=TrainParams(optimizer_kwargs={"b1": 0.9})),
    EvalTask(task_id="e", model=_BULK, datasets=["test", "valid"]),
    TaskResult(task_id="t", learner_id="L0", model=_BULK,
               control_delta=_BULK, train_metrics={"loss": 0.5},
               epoch_metrics=[{"loss": 0.9}], task_tiles={"steps": 1.5}),
    InferTask(task_id="i", model=_BULK, inputs=_BULK),
    InferResult(task_id="i", predictions=_BULK),
    ServeRequest(request_id="r", inputs=_BULK),
    ServeReply(request_id="r", predictions=_BULK),
    GenerateRequest(request_id="g", prompt=b"small"),
    GenerateReply(request_id="g", tokens=b"small"),
    JoinRequest(hostname="h", port=1, capabilities={"party_index": 1}),
    JoinReply(learner_id="L0", auth_token="tok"),
    EvalResult(task_id="e", evaluations={"test": {"loss": 0.5}}),
], ids=lambda m: type(m).__name__)
def test_every_message_keeps_its_wire_bytes(message):
    """Each message type, bulk fields and all: ``to_wire`` and the join
    of ``to_segments`` are the old encoder's bytes, and decode back."""
    reference = _reference_dumps(message.to_dict())
    assert message.to_wire() == reference
    assert bytes(message.to_segments()) == reference
    assert type(message).from_wire(reference) == message


@pytest.mark.parametrize("cls", [TrainTask, EvalTask, TaskResult])
def test_model_decodes_as_view_of_the_request(cls):
    """A bulk ``model`` comes back as a read-only slice of the request
    buffer; small values, other fields and plain ``loads`` stay bytes."""
    import numpy as np

    message = cls(task_id="t", model=_BULK)
    if cls is not EvalTask:
        setattr(message, "control" if cls is TrainTask else "control_delta",
                _BULK)
    wire = message.to_wire()
    out = cls.from_wire(wire)
    assert isinstance(out.model, memoryview) and out.model.readonly
    assert out.model.obj is wire and out.model == _BULK
    assert np.shares_memory(np.frombuffer(out.model, np.uint8),
                            np.frombuffer(wire, np.uint8))
    with pytest.raises(TypeError):
        out.model[0] = 1
    assert out == message and isinstance(out.task_id, str)
    other = [getattr(out, f) for f in ("control", "control_delta")
             if hasattr(out, f)]
    assert all(type(v) is bytes and v == _BULK for v in other)
    # below the threshold: a copy, as ever
    small = cls.from_wire(cls(model=_BULK[:_T - 1]).to_wire())
    assert type(small.model) is bytes
    # every other caller of loads, and a writable request buffer
    assert type(loads(wire)["model"]) is bytes
    writable = codec.loads(bytearray(wire), views=("model",))["model"]
    assert isinstance(writable, memoryview) and writable.readonly
    # a message that names no view field never sees one
    assert type(InferTask.from_wire(
        InferTask(model=_BULK).to_wire()).model) is bytes


def test_view_decode_holds_the_wire_contract():
    """Asking for views changes no check: truncation and trailing bytes
    still raise, and a non-bytes value under a view key decodes as it is."""
    wire = TrainTask(model=_BULK).to_wire()
    for bad in (wire[:-1], wire[:len(wire) // 2], wire + b"\x00"):
        with pytest.raises(ValueError):
            TrainTask.from_wire(bad)
    assert codec.loads(dumps({"model": 7}), views=("model",)) == {"model": 7}
    assert codec.loads(dumps([1, 2]), views=("model",)) == [1, 2]
    assert codec.loads(dumps({"a": {"model": _BULK}}),
                       views=("model",)) == {"a": {"model": _BULK}}
    assert type(codec.loads(dumps({"a": {"model": _BULK}}),
                            views=("model",))["a"]["model"]) is bytes


@pytest.fixture
def codec_telemetry():
    """Metrics on and reset, tracer on with the finished-span ring armed;
    yields (registry, drain) where drain() returns the codec spans
    recorded since."""
    from metisfl_tpu import telemetry
    from metisfl_tpu.telemetry import metrics as tmetrics
    from metisfl_tpu.telemetry import trace as ttrace

    tmetrics.set_enabled(True)
    telemetry.registry().reset()
    ttrace.configure(enabled=True, service="test", dir="")
    ttrace.configure_ring(8192)
    cursor = ttrace.spans_since(0)[1]
    yield telemetry.registry(), lambda: [
        r for r in ttrace.spans_since(cursor)[0]
        if r["name"].startswith("codec.")]
    telemetry.registry().reset()
    ttrace.configure(enabled=True, service="test", dir="")


def test_borrowed_bytes_are_counted(codec_telemetry):
    """The events and the byte counter say how often the mechanism
    engages: borrowed_bytes beside bytes, and the two new ops."""
    registry, drain = codec_telemetry
    task = TrainTask(task_id="t", model=_BULK, control=b"small")
    segments = task.to_segments()
    wire = task.to_wire()          # joined: the codec copied, borrowed 0
    TrainTask.from_wire(wire)      # model as a view
    loads(wire)                    # every other caller: nothing borrowed
    events = [(r["name"], r["attrs"]["bytes"], r["attrs"]["borrowed_bytes"])
              for r in drain()]
    assert events == [("codec.encode", len(wire), len(_BULK)),
                      ("codec.encode", len(wire), 0),
                      ("codec.decode", len(wire), len(_BULK)),
                      ("codec.decode", len(wire), 0)]
    assert segments.borrowed_bytes == len(_BULK)
    counted = registry.counter("codec_bytes_total", "", ("op",))
    assert counted.value(op="encode") == 2 * len(wire)
    assert counted.value(op="decode") == 2 * len(wire)
    assert counted.value(op="encode_borrowed") == len(_BULK)
    assert counted.value(op="decode_borrowed") == len(_BULK)
    # a message with nothing bulk in it mints neither op
    registry.reset()
    JoinRequest.from_wire(JoinRequest(hostname="h").to_segments().parts[0])
    assert counted.value(op="encode_borrowed") == 0
    assert counted.value(op="decode_borrowed") == 0


def test_blob_bearing_message_is_not_copied_by_the_codec():
    """One allocation count: a TrainTask with a 64 MB model goes
    to_segments -> chunk frames under 1.5x the blob at the peak (the old
    encoder passed 3x: the growing buffer, its bytes() copy, a frame),
    and decodes under 0.5x (the model is a view)."""
    import tracemalloc

    from metisfl_tpu.comm import rpc

    n = 64 << 20
    task = TrainTask(task_id="t", learner_id="L0", model=bytes(n))
    tracemalloc.start()
    try:
        sent = 0
        for frame in rpc._iter_chunks(task.to_segments()):
            assert len(frame) <= rpc.CHUNK_BYTES
            sent += len(frame)
        encode_peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    wire = task.to_wire()
    assert sent == len(wire)
    tracemalloc.start()
    try:
        out = TrainTask.from_wire(wire)
        decode_peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert len(out.model) == n
    assert encode_peak < 1.5 * n, encode_peak / n
    assert decode_peak < 0.5 * n, decode_peak / n
