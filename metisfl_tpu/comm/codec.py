"""Self-describing binary codec for federation messages.

A minimal tagged format (msgpack-flavored, but ours — stable and trivially
implementable in C++): values are ``None``, bools, signed ints (zigzag
varint), float64, utf-8 strings, bytes, lists and string-keyed dicts. Bulk
tensors never pass through this codec — they travel as raw tensor blobs
(:mod:`metisfl_tpu.tensor`) referenced from messages as ``bytes`` fields, so
the codec stays small and the hot path stays memcpy-shaped.

Replaces the reference's protobuf layer (metisfl/proto/*.proto) at the
message level; see messages.py for the concrete message schemas.
"""

from __future__ import annotations

import contextlib
import contextvars
import struct
import threading
import time
from typing import Any, Dict, Tuple

import numpy as np

from metisfl_tpu import telemetry as _tel
from metisfl_tpu.telemetry import metrics as _tmetrics
from metisfl_tpu.telemetry import trace as _ttrace

# codec hot-path telemetry: histograms always (cheap), spans only for
# payloads big enough to matter in a round trace — every tiny ack would
# otherwise flood the JSONL sink
_M_CODEC = _tmetrics.registry().histogram(
    _tel.M_CODEC_DURATION_SECONDS, "Message codec encode/decode time", ("op",))
_M_CODEC_BYTES = _tmetrics.registry().counter(
    _tel.M_CODEC_BYTES_TOTAL, "Message codec bytes by operation", ("op",))
_SPAN_MIN_BYTES = 1 << 18
# The codec never copies a bytes value this large (a model blob riding a
# message): ``dumps_segments`` hands the object through as a segment of
# its own, and a decode asked for views (``loads(buf, views=...)``)
# returns a slice of the request buffer. Segments and views are an
# in-process matter; the bytes on the wire and on disk are ``dumps``'s.
BORROW_MIN_BYTES = _SPAN_MIN_BYTES

# Per-learner codec attribution (performance observatory): call sites
# that know which learner a message belongs to wrap the encode in
# ``attributed(learner_id)`` (or report a self-timed decode via
# ``attribute``), and the time lands in a labeled counter + the process
# totals the profile collector diffs per round. Series are pruned on
# learner leave (``prune_attribution``) — bounded cardinality under
# churn, the same posture as the controller's per-learner gauges.
_M_CODEC_LEARNER = _tmetrics.registry().counter(
    _tel.M_CODEC_LEARNER_SECONDS,
    "Codec encode/decode seconds attributed to one learner's messages",
    ("learner", "op"), budget_label="learner")
_ATTR: "contextvars.ContextVar[str]" = contextvars.ContextVar(
    "metisfl_tpu_codec_attr", default="")
_ATTR_LOCK = threading.Lock()
_ATTR_TOTALS: Dict[Tuple[str, str], float] = {}


@contextlib.contextmanager
def attributed(learner_id: str):
    """Attribute every dumps/loads inside the block to ``learner_id``."""
    token = _ATTR.set(learner_id or "")
    try:
        yield
    finally:
        _ATTR.reset(token)


def attribute(learner_id: str, op: str, seconds: float) -> None:
    """Record codec time for a learner's message (post-hoc form, for
    decode sites that only learn the learner id FROM the decode)."""
    if not learner_id or not _tmetrics.enabled():
        return
    _M_CODEC_LEARNER.inc(seconds, learner=learner_id, op=op)
    with _ATTR_LOCK:
        key = (learner_id, op)
        _ATTR_TOTALS[key] = _ATTR_TOTALS.get(key, 0.0) + seconds


def attributed_totals() -> Dict[Tuple[str, str], float]:
    """Cumulative attributed seconds ``{(learner_id, op): s}`` — the
    profile collector snapshots this per round and diffs."""
    with _ATTR_LOCK:
        return dict(_ATTR_TOTALS)


def prune_attribution(learner_id: str) -> None:
    for op in ("encode", "decode"):
        _M_CODEC_LEARNER.remove(learner=learner_id, op=op)
    with _ATTR_LOCK:
        for key in [k for k in _ATTR_TOTALS if k[0] == learner_id]:
            del _ATTR_TOTALS[key]

_T_NONE = 0x00
_T_FALSE = 0x01
_T_TRUE = 0x02
_T_INT = 0x03
_T_FLOAT = 0x04
_T_STR = 0x05
_T_BYTES = 0x06
_T_LIST = 0x07
_T_DICT = 0x08


def _write_varint(out: bytearray, value: int) -> None:
    while True:
        byte = value & 0x7F
        value >>= 7
        if value:
            out.append(byte | 0x80)
        else:
            out.append(byte)
            return


_INT64_MIN = -(2**63)
_INT64_MAX = 2**63 - 1


def _zigzag(value: int) -> int:
    if not _INT64_MIN <= value <= _INT64_MAX:
        raise OverflowError(f"codec ints are 64-bit; {value} out of range")
    return (value << 1) ^ (value >> 63) if value < 0 else value << 1


def _unzigzag(value: int) -> int:
    return (value >> 1) ^ -(value & 1)


class Segments:
    """One encoded message as ordered buffers: ``b"".join(parts)`` is the
    message. Heads and tails are ``bytes`` the encoder wrote; a bulk
    value is the very object the caller passed in, so whoever holds a
    ``Segments`` keeps those alive and must not mutate them. ``len()``
    is the message's size in bytes; re-iterable (a retry re-sends)."""

    __slots__ = ("parts", "nbytes", "borrowed_bytes")

    def __init__(self, parts, borrowed_bytes: int = 0):
        self.parts = tuple(parts)
        self.nbytes = sum(len(p) for p in self.parts)
        self.borrowed_bytes = borrowed_bytes

    def __len__(self) -> int:
        return self.nbytes

    def __bytes__(self) -> bytes:
        return b"".join(self.parts)


def _encode(out: bytearray, value: Any, segs: list) -> None:
    """Append ``value``'s encoding to ``out``. A bulk bytes value closes
    the head written so far into ``segs``, follows it as a segment of its
    own, and ``out`` (emptied) goes on as the tail."""
    # Coerce numpy scalars (jit outputs land here via metric dicts).
    if isinstance(value, np.generic):
        value = value.item()
    if value is None:
        out.append(_T_NONE)
    elif value is True:
        out.append(_T_TRUE)
    elif value is False:
        out.append(_T_FALSE)
    elif isinstance(value, int):
        out.append(_T_INT)
        _write_varint(out, _zigzag(value))
    elif isinstance(value, float):
        out.append(_T_FLOAT)
        out.extend(struct.pack("<d", value))
    elif isinstance(value, str):
        encoded = value.encode("utf-8")
        out.append(_T_STR)
        _write_varint(out, len(encoded))
        out.extend(encoded)
    elif isinstance(value, (bytes, bytearray, memoryview)):
        if isinstance(value, memoryview) and (
                value.itemsize != 1 or value.ndim != 1
                or not value.c_contiguous):
            value = bytes(value)  # measure/extend in bytes, not elements
        out.append(_T_BYTES)
        _write_varint(out, len(value))
        if len(value) >= BORROW_MIN_BYTES:
            segs.append(bytes(out))
            segs.append(value)
            out.clear()
        else:
            out.extend(value)
    elif isinstance(value, (list, tuple)):
        out.append(_T_LIST)
        _write_varint(out, len(value))
        for item in value:
            _encode(out, item, segs)
    elif isinstance(value, dict):
        out.append(_T_DICT)
        _write_varint(out, len(value))
        for key, item in value.items():
            if not isinstance(key, str):
                raise TypeError(f"dict keys must be str, got {type(key)!r}")
            encoded = key.encode("utf-8")
            _write_varint(out, len(encoded))
            out.extend(encoded)
            _encode(out, item, segs)
    else:
        raise TypeError(f"codec cannot encode {type(value)!r}")


def _encode_segments(value: Any) -> Segments:
    out = bytearray()
    segs: list = []
    _encode(out, value, segs)
    # head, bulk, head, bulk, ..., tail: the bulk values sit at the odd
    # places, each behind the (never empty) head that frames it
    borrowed = sum(len(p) for p in segs[1::2])
    if out or not segs:
        segs.append(bytes(out))
    return Segments(segs, borrowed)


def _dumps(value: Any, join: bool):
    if not _tmetrics.enabled():
        message = _encode_segments(value)
        return bytes(message) if join else message
    t0 = time.perf_counter()
    message = _encode_segments(value)
    # joined, the bulk values are copied here after all: nothing borrowed
    borrowed = 0 if join else message.borrowed_bytes
    if join:
        message = bytes(message)
    elapsed = time.perf_counter() - t0
    nbytes = len(message)
    _M_CODEC.observe(elapsed, op="encode")
    _M_CODEC_BYTES.inc(nbytes, op="encode")
    if borrowed:
        _M_CODEC_BYTES.inc(borrowed, op="encode_borrowed")
    lid = _ATTR.get()
    if lid:
        attribute(lid, "encode", elapsed)
    if nbytes >= _SPAN_MIN_BYTES:
        _ttrace.event("codec.encode", elapsed,
                      attrs={"bytes": nbytes, "borrowed_bytes": borrowed})
    return message


def dumps(value: Any) -> bytes:
    return _dumps(value, join=True)


def dumps_segments(value: Any) -> Segments:
    """``dumps`` without the copies: ``bytes(dumps_segments(v)) ==
    dumps(v)`` for any value, and every bytes value of at least
    ``BORROW_MIN_BYTES`` is a segment that ``is`` the object passed in.
    For sinks that gather (the chunked stream, a file): the one copy left
    is theirs."""
    return _dumps(value, join=False)


def _read_varint(view: memoryview, offset: int) -> tuple[int, int]:
    result = 0
    shift = 0
    while True:
        if offset >= len(view):
            raise ValueError("codec: truncated varint")
        if shift > 63:  # match the encoder's 64-bit contract (C++ interop)
            raise ValueError("codec: varint exceeds 64 bits")
        byte = view[offset]
        offset += 1
        result |= (byte & 0x7F) << shift
        if not byte & 0x80:
            if result > 0xFFFFFFFFFFFFFFFF:
                raise ValueError("codec: varint exceeds 64 bits")
            return result, offset
        shift += 7


def _take(view: memoryview, offset: int, length: int) -> tuple[memoryview, int]:
    end = offset + length
    if end > len(view):
        raise ValueError(
            f"codec: truncated buffer (need {end} bytes, have {len(view)})"
        )
    return view[offset:end], end


# Nesting bound for the RECURSIVE decoder: crafted deep nesting (~2 bytes
# per level) must raise a clean ValueError at the wire boundary, not blow
# the interpreter stack with RecursionError. Far above any real message
# (messages nest < 10 deep).
_MAX_DEPTH = 100


def _decode(view: memoryview, offset: int, depth: int = 0,
            views=()) -> tuple[Any, int]:
    """``views``: keys of the TOP-LEVEL dict whose bytes value, when it
    is bulk, comes back as a read-only slice of ``view`` (no copy; it
    keeps the whole buffer alive). Everything else decodes to ``bytes``."""
    if depth > _MAX_DEPTH:
        raise ValueError(f"codec: nesting exceeds {_MAX_DEPTH} levels")
    if offset >= len(view):
        raise ValueError("codec: truncated buffer (empty value)")
    tag = view[offset]
    offset += 1
    if tag == _T_NONE:
        return None, offset
    if tag == _T_TRUE:
        return True, offset
    if tag == _T_FALSE:
        return False, offset
    if tag == _T_INT:
        raw, offset = _read_varint(view, offset)
        return _unzigzag(raw), offset
    if tag == _T_FLOAT:
        raw, offset = _take(view, offset, 8)
        return struct.unpack("<d", raw)[0], offset
    if tag == _T_STR:
        length, offset = _read_varint(view, offset)
        raw, offset = _take(view, offset, length)
        return bytes(raw).decode("utf-8"), offset
    if tag == _T_BYTES:
        length, offset = _read_varint(view, offset)
        raw, offset = _take(view, offset, length)
        return bytes(raw), offset
    if tag == _T_LIST:
        length, offset = _read_varint(view, offset)
        items = []
        for _ in range(length):
            item, offset = _decode(view, offset, depth + 1)
            items.append(item)
        return items, offset
    if tag == _T_DICT:
        length, offset = _read_varint(view, offset)
        result = {}
        for _ in range(length):
            klen, offset = _read_varint(view, offset)
            raw, offset = _take(view, offset, klen)
            key = bytes(raw).decode("utf-8")
            if (views and depth == 0 and key in views
                    and offset < len(view) and view[offset] == _T_BYTES):
                vlen, offset = _read_varint(view, offset + 1)
                raw, offset = _take(view, offset, vlen)
                result[key] = (raw.toreadonly() if vlen >= BORROW_MIN_BYTES
                               else bytes(raw))
            else:
                result[key], offset = _decode(view, offset, depth + 1)
        return result, offset
    raise ValueError(f"codec: unknown tag 0x{tag:02x} at offset {offset - 1}")


def loads(buf, views=()) -> Any:
    """Decode one value. ``views`` names keys of a top-level dict whose
    bulk bytes value (``BORROW_MIN_BYTES`` and up) the caller takes as a
    read-only ``memoryview`` over ``buf`` in place of a copy: for a
    consumer that reads it once and lets go (``ModelBlob.from_bytes``),
    since the slice keeps all of ``buf`` alive. Without it every bytes
    value is ``bytes``."""
    if not _tmetrics.enabled():
        return _loads(buf, views)
    t0 = time.perf_counter()
    value = _loads(buf, views)
    elapsed = time.perf_counter() - t0
    nbytes = memoryview(buf).nbytes
    borrowed = 0
    if views and isinstance(value, dict):
        borrowed = sum(value[k].nbytes for k in views
                       if isinstance(value.get(k), memoryview))
    _M_CODEC.observe(elapsed, op="decode")
    _M_CODEC_BYTES.inc(nbytes, op="decode")
    if borrowed:
        _M_CODEC_BYTES.inc(borrowed, op="decode_borrowed")
    lid = _ATTR.get()
    if lid:
        attribute(lid, "decode", elapsed)
    if nbytes >= _SPAN_MIN_BYTES:
        _ttrace.event("codec.decode", elapsed,
                      attrs={"bytes": nbytes, "borrowed_bytes": borrowed})
    return value


def _loads(buf, views=()) -> Any:
    view = memoryview(buf)
    value, offset = _decode(view, 0, 0, views)
    if offset != len(view):
        # trailing bytes mean a framing error (truncated write spliced with
        # the next frame, corrupt length prefix): decoding a prefix and
        # silently discarding the rest would return a wrong value
        raise ValueError(
            f"codec: {len(view) - offset} trailing byte(s) after value")
    return value
