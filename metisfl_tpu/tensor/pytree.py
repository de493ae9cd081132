"""Pytree ⇄ named-tensor model blobs.

The federation wire contract moves *models* — ordered, named, flat tensors —
while the JAX learner works on *pytrees* (Flax param dicts). This module is
the bridge. It replaces the reference's ``Model``/``Model.Variable`` proto
(reference metisfl/proto/model.proto:100-152) and the get/set weight paths in
``ModelOps`` (metisfl/models/model_ops.py:24-110): names are derived from the
pytree key path, so a blob round-trips through any transport back into the
exact same tree structure.
"""

from __future__ import annotations

import os
import struct
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

import numpy as np
import jax

from metisfl_tpu import telemetry as _tel
from metisfl_tpu.telemetry import metrics as _tmetrics
from metisfl_tpu.tensor.spec import (
    TensorKind,
    TensorSpec,
    opaque_tensor_to_bytes,
    tensor_from_bytes,
    tensor_to_bytes,
)

NamedTensors = List[Tuple[str, np.ndarray]]

_MAGIC = b"MTFB"  # metisfl-tpu federated blob
# v2 adds integrity framing: a <u64 body_len, u32 crc32> trailer-header
# over the tensor body, so a bit-flipped or truncated blob is rejected at
# the wire boundary instead of deserializing into garbage weights that
# would silently poison an aggregation. v1 blobs (pre-integrity
# checkpoints) still parse — unverified.
_BLOB_VERSION = 2
# v3: length-framed, crc field written as zero and never verified —
# store-local files only (write_named_tensors(checksum=False)); the wire
# always ships v2
_BLOB_VERSION_NOCRC = 3

# Payloads rejected by the integrity framing (length or checksum). The
# RPC layer surfaces the ValueError as INVALID_ARGUMENT; the controller's
# malformed-result path drops the contribution without stalling the round.
_M_CORRUPT = _tmetrics.registry().counter(
    _tel.M_CORRUPT_PAYLOADS_TOTAL,
    "Model blobs rejected by length/checksum integrity framing")


def _escape(part: str) -> str:
    # '/' joins path components; escape literal '/' (and the escape char) so
    # {'a': {'b': x}} and {'a/b': y} can never collide.
    return part.replace("%", "%25").replace("/", "%2F")


def _key_to_name(path) -> str:
    parts = []
    for entry in path:
        if isinstance(entry, jax.tree_util.DictKey):
            parts.append(_escape(str(entry.key)))
        elif isinstance(entry, jax.tree_util.SequenceKey):
            parts.append(str(entry.idx))
        elif isinstance(entry, jax.tree_util.GetAttrKey):
            parts.append(_escape(str(entry.name)))
        elif isinstance(entry, jax.tree_util.FlattenedIndexKey):
            parts.append(str(entry.key))
        else:  # pragma: no cover - future key types
            parts.append(_escape(str(entry)))
    return "/".join(parts)


def _check_unique(names) -> None:
    if len(set(names)) != len(names):
        seen, dupes = set(), set()
        for n in names:
            (dupes if n in seen else seen).add(n)
        raise ValueError(f"duplicate tensor names in model: {sorted(dupes)[:5]}")


def pytree_to_named_tensors(tree) -> NamedTensors:
    """Flatten a pytree of arrays to ``[(name, np.ndarray), ...]`` (ordered)."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    named = [(_key_to_name(path), np.asarray(leaf)) for path, leaf in flat]
    _check_unique([n for n, _ in named])
    return named


def named_tensors_to_pytree(named: NamedTensors, treedef_like):
    """Rebuild a pytree structured like ``treedef_like`` from named tensors."""
    flat = jax.tree_util.tree_flatten_with_path(treedef_like)
    paths = [_key_to_name(p) for p, _ in flat[0]]
    _check_unique([n for n, _ in named])
    by_name = dict(named)
    missing = [p for p in paths if p not in by_name]
    if missing:
        raise KeyError(f"model blob is missing tensors: {missing[:5]}")
    leaves = [by_name[p] for p in paths]
    return jax.tree_util.tree_unflatten(flat[1], leaves)


@dataclass
class ModelBlob:
    """A serializable model: ordered named tensors plus opaque entries.

    ``tensors`` holds plaintext arrays; ``opaque`` holds ciphertext/masked
    payloads keyed by the same names (a blob is either all-plaintext or
    all-opaque in practice, but the container does not force it).
    """

    tensors: NamedTensors = field(default_factory=list)
    opaque: Dict[str, tuple] = field(default_factory=dict)  # name -> (payload, spec)

    @property
    def names(self) -> List[str]:
        seen = [n for n, _ in self.tensors]
        seen.extend(self.opaque.keys())
        return seen

    @property
    def num_parameters(self) -> int:
        return sum(int(a.size) for _, a in self.tensors) + sum(
            spec.size for _, spec in self.opaque.values()
        )

    def to_bytes(self) -> bytes:
        chunks = []
        for name, arr in self.tensors:
            nb = name.encode("utf-8")
            chunks.append(struct.pack("<H", len(nb)))
            chunks.append(nb)
            chunks.append(tensor_to_bytes(arr))
        for name, (payload, spec) in self.opaque.items():
            nb = name.encode("utf-8")
            chunks.append(struct.pack("<H", len(nb)))
            chunks.append(nb)
            chunks.append(opaque_tensor_to_bytes(spec, payload))
        body = b"".join(chunks)
        return b"".join([
            _MAGIC,
            struct.pack("<BI", _BLOB_VERSION, len(self.names)),
            struct.pack("<QI", len(body), zlib.crc32(body)),
            body,
        ])

    @classmethod
    def from_bytes(cls, buf, copy: bool = True,
                   allow_nocrc: bool = False) -> "ModelBlob":
        """``allow_nocrc=True`` accepts the v3 store-local variant; the
        default REJECTS it so a wire payload whose version byte got
        flipped (or a peer deliberately shipping v3) cannot sidestep the
        v2 integrity framing — only the disk store's own read path,
        whose files it wrote itself, opts in (docs/SCALE.md)."""
        view = memoryview(buf)
        if bytes(view[:4]) != _MAGIC:
            raise ValueError("not a metisfl-tpu model blob")
        version, count = struct.unpack_from("<BI", view, 4)
        offset = 9
        if version == 3 and not allow_nocrc:
            _M_CORRUPT.inc()
            raise ValueError(
                "unchecksummed v3 model blob rejected outside the store "
                "read path (wire payloads must carry the v2 crc framing)")
        if version in (2, 3):
            try:
                body_len, crc = struct.unpack_from("<QI", view, offset)
            except struct.error:
                _M_CORRUPT.inc()
                raise ValueError("truncated model blob header") from None
            offset += 12
            body = view[offset:]
            if len(body) != body_len:
                _M_CORRUPT.inc()
                raise ValueError(
                    f"model blob length mismatch (framed {body_len} body "
                    f"bytes, have {len(body)}) — truncated or spliced "
                    "payload")
            # v3 (store-local, write_named_tensors(checksum=False)) is
            # length-framed only: truncation still rejects, the model was
            # crc-verified at the wire before it ever reached the store
            if version == 2 and zlib.crc32(body) != crc:
                _M_CORRUPT.inc()
                raise ValueError(
                    "model blob checksum mismatch — corrupt payload "
                    "rejected before deserialization")
        elif version != 1:  # v1: legacy pre-integrity blobs parse unverified
            raise ValueError(f"unsupported blob version {version}")
        blob = cls()
        for _ in range(count):
            (nlen,) = struct.unpack_from("<H", view, offset)
            offset += 2
            name = bytes(view[offset : offset + nlen]).decode("utf-8")
            offset += nlen
            value, spec, offset = tensor_from_bytes(view, offset, copy=copy)
            if spec.kind is TensorKind.PLAINTEXT:
                blob.tensors.append((name, value))
            else:
                blob.opaque[name] = (value, spec)
        return blob


def write_named_tensors(fd: int, named: NamedTensors,
                        checksum: bool = True) -> int:
    """Stream a tensors-only blob to an open file descriptor with ZERO
    staging copies; with ``checksum=True`` the file bytes are identical
    to ``ModelBlob(tensors=named).to_bytes()``.

    ``to_bytes`` pays three full-model memcpys (per-tensor ``tobytes``,
    the body join, the framing join) before the file write — ~3x the
    model size in pure memory traffic, which is what capped disk-store
    ingest. Here each
    tensor contributes a read-only ``memoryview`` straight over its
    buffer: the crc folds incrementally across the views and ``writev``
    gathers them into the file, so the only model-sized copy left is the
    kernel's. Returns the number of bytes written.

    ``checksum=False`` writes the v3 length-framed variant: same layout,
    crc field zero and never verified. For STORE-LOCAL files only
    (docs/SCALE.md): the uplink was already crc-checked at the RPC
    decode, ``os.replace`` keeps half-written files from ever appearing
    under their final name, and the length frame still rejects
    truncation — re-hashing the model on every insert AND select was
    pure hot-path overhead.
    Wire blobs keep the v2 checksum."""
    chunks: List = []
    for name, arr in named:
        arr = np.asarray(arr)
        if arr.dtype.byteorder == ">":  # wire is little-endian (spec.py)
            arr = arr.astype(arr.dtype.newbyteorder("="))
        # header shape BEFORE ascontiguousarray: it promotes 0-d scalars
        # to 1-d, which would change the wire header vs tensor_to_bytes
        shape = arr.shape
        arr = np.ascontiguousarray(arr)
        nb = name.encode("utf-8")
        from metisfl_tpu.tensor.spec import _header_bytes, wire_dtype_of

        chunks.append(struct.pack("<H", len(nb)) + nb + _header_bytes(
            TensorSpec(shape, wire_dtype_of(arr.dtype),
                       TensorKind.PLAINTEXT), arr.nbytes))
        # flat byte view — keeps the (possibly temporary contiguous)
        # array alive through the write, no serialization copy
        chunks.append(arr.data.cast("B"))
    body_len = sum(len(c) for c in chunks)
    crc = 0
    if checksum:
        for c in chunks:
            crc = zlib.crc32(c, crc)
    header = b"".join([
        _MAGIC,
        struct.pack("<BI",
                    _BLOB_VERSION if checksum else _BLOB_VERSION_NOCRC,
                    len(named)),
        struct.pack("<QI", body_len, crc),
    ])
    total = len(header) + body_len
    buffers: List = [header] + chunks
    if hasattr(os, "writev"):
        while buffers:
            written = os.writev(fd, buffers[:64])
            while buffers and written >= len(buffers[0]):
                written -= len(buffers[0])
                buffers.pop(0)
            if written:
                buffers[0] = memoryview(buffers[0])[written:]
    else:  # pragma: no cover - non-POSIX fallback
        for buf in buffers:
            os.write(fd, buf)
    return total


def pack_model(params_tree) -> bytes:
    """One-call pytree → wire bytes."""
    return ModelBlob(tensors=pytree_to_named_tensors(params_tree)).to_bytes()


def unpack_model(buf, treedef_like):
    """One-call wire bytes → pytree shaped like ``treedef_like``."""
    blob = ModelBlob.from_bytes(buf)
    return named_tensors_to_pytree(blob.tensors, treedef_like)
