"""Cohort-scale ingest plane (docs/SCALE.md): the copy-free blob writer,
the bounded parallel ingest pipeline, the per-learner store thread-safety
contract (store/base.py), and the controller's opt-in/opt-out wiring.

The concurrency hammer here is the regression test the store/base.py
contract docstring points at: concurrent insert/select/erase on the disk
and cached backends must never observe a torn lineage.
"""

import os
import threading
import time

import numpy as np
import pytest

from metisfl_tpu.store.base import EvictionPolicy, ModelStore
from metisfl_tpu.store.cached import CachedDiskStore
from metisfl_tpu.store.disk import DiskModelStore
from metisfl_tpu.store.ingest import IngestPipeline
from metisfl_tpu.store.memory import InMemoryModelStore
from metisfl_tpu.tensor.pytree import ModelBlob, write_named_tensors


def _model(tag: int, n: int = 64):
    """Two arrays derived from one tag: a select that ever returns
    mismatched halves has observed a torn lineage."""
    return {"a/w": np.full((n,), np.float32(tag)),
            "b/w": np.full((n // 2,), np.float32(tag))}


def _tag_of(model):
    a = float(np.asarray(model["a/w"])[0])
    b = float(np.asarray(model["b/w"])[0])
    assert a == b, f"torn model: halves tagged {a} vs {b}"
    assert np.all(np.asarray(model["a/w"]) == a)
    assert np.all(np.asarray(model["b/w"]) == b)
    return int(a)


# --------------------------------------------------------------------- #
# copy-free blob writer
# --------------------------------------------------------------------- #

def test_write_named_tensors_bytes_identical(tmp_path):
    """The streamed write's file bytes are identical to the staged
    ``ModelBlob.to_bytes`` — same framing, same crc — including
    non-contiguous and big-endian inputs (normalized like the blob path)."""
    rng = np.random.default_rng(3)
    named = [
        ("enc/w", rng.standard_normal((17, 9)).astype(np.float32)),
        ("enc/slice", np.ascontiguousarray(
            rng.standard_normal((12, 12)).astype(np.float32))[::2, ::3]),
        ("head/b", rng.standard_normal(5).astype(">f4")),
        ("step", np.int32(7)),
    ]
    want = ModelBlob(tensors=[(k, np.asarray(v)) for k, v in named]
                     ).to_bytes()
    path = tmp_path / "blob.bin"
    fd = os.open(str(path), os.O_WRONLY | os.O_CREAT, 0o644)
    try:
        wrote = write_named_tensors(fd, named)
    finally:
        os.close(fd)
    data = path.read_bytes()
    assert wrote == len(data) == len(want)
    assert data == want
    back = ModelBlob.from_bytes(data)
    for (name, arr), (bname, barr) in zip(named, back.tensors):
        assert name == bname
        np.testing.assert_array_equal(np.asarray(arr, dtype="<f4")
                                      if np.asarray(arr).dtype.byteorder
                                      == ">" else np.asarray(arr), barr)


def test_nocrc_blob_roundtrip_and_length_framing(tmp_path):
    """checksum=False writes the v3 store-local variant: same layout
    with a zero crc that is never verified — decodes to the same
    tensors, and a TRUNCATED v3 file still rejects loudly (the length
    frame is the part of the integrity check the store keeps)."""
    named = [("a/w", np.arange(12, dtype=np.float32)),
             ("b", np.float32(3.5))]
    path = tmp_path / "v3.bin"
    fd = os.open(str(path), os.O_WRONLY | os.O_CREAT, 0o644)
    try:
        write_named_tensors(fd, named, checksum=False)
    finally:
        os.close(fd)
    data = path.read_bytes()
    assert data[4] == 3  # version byte
    back = ModelBlob.from_bytes(data, allow_nocrc=True)
    for (name, arr), (bname, barr) in zip(named, back.tensors):
        assert name == bname
        np.testing.assert_array_equal(np.asarray(arr), barr)
    with pytest.raises(ValueError, match="length mismatch"):
        ModelBlob.from_bytes(data[:-4], allow_nocrc=True)
    # the wire decode must NOT accept v3: a flipped version byte (or a
    # peer shipping v3 deliberately) cannot sidestep the crc framing
    with pytest.raises(ValueError, match="v3"):
        ModelBlob.from_bytes(data)


def test_disk_fast_path_roundtrips_flat_dicts(tmp_path):
    """A flat tensor dict inserted through DiskModelStore takes the
    streamed v3 fast path; the shared read path decodes it to the same
    tensors a staged v2 write would have produced."""
    store = DiskModelStore(str(tmp_path / "s"),
                           EvictionPolicy.LINEAGE_LENGTH, lineage_length=2)
    model = _model(11)
    store.insert("L0", model)
    blob_file = next(f for f in os.listdir(store._dir("L0"))
                     if f.endswith(".blob"))
    with open(os.path.join(store._dir("L0"), blob_file), "rb") as fh:
        data = fh.read()
    assert data[4] == 3  # store-local files are the no-crc variant
    picked = store.select(["L0"], k=1)
    assert _tag_of(picked["L0"][0]) == 11
    for key, arr in model.items():
        np.testing.assert_array_equal(picked["L0"][0][key], arr)
    store.shutdown()


# --------------------------------------------------------------------- #
# ingest pipeline
# --------------------------------------------------------------------- #

def test_ingest_lands_models_and_attributes_worker_time(tmp_path):
    """Every submitted model is selectable after drain, and the
    attribution callback fires once per successful write with the
    WORKER's measured duration (satellite: no double count — the
    enqueueing thread records nothing; the callback is the only sample)."""
    store = DiskModelStore(str(tmp_path / "s"),
                           EvictionPolicy.LINEAGE_LENGTH, lineage_length=1)
    samples = []
    pipe = IngestPipeline(store, workers=4,
                          on_insert=lambda lid, ms: samples.append((lid, ms)))
    ids = [f"L{i}" for i in range(16)]
    for i, lid in enumerate(ids):
        pipe.submit(lid, _model(i))
    assert pipe.drain(timeout=30.0)
    assert pipe.queue_depth() == 0
    picked = store.select(ids, k=1)
    assert sorted(picked) == sorted(ids)
    for i, lid in enumerate(ids):
        assert _tag_of(picked[lid][0]) == i
    assert sorted(lid for lid, _ in samples) == sorted(ids)
    assert all(ms >= 0.0 for _, ms in samples)
    pipe.shutdown()
    store.shutdown()


def test_ingest_backpressure_bounds_queue():
    """The queue is bounded: submit blocks once max_pending writes are
    queued or in flight, so a flood of uplinks throttles at the
    transport instead of buffering the cohort in controller RAM."""
    gate = threading.Event()

    class SlowStore(InMemoryModelStore):
        def _append(self, learner_id, model):
            gate.wait(10.0)
            super()._append(learner_id, model)

    store = SlowStore()
    pipe = IngestPipeline(store, workers=1, max_pending=3)
    for i in range(3):
        pipe.submit(f"L{i}", _model(i))
    assert pipe.queue_depth() == 3
    blocked = threading.Event()

    def overflow():
        pipe.submit("L3", _model(3))
        blocked.set()

    t = threading.Thread(target=overflow, daemon=True)
    t.start()
    assert not blocked.wait(0.3), "submit past max_pending did not block"
    gate.set()
    assert blocked.wait(10.0), "blocked submit never unblocked"
    assert pipe.drain(timeout=10.0)
    assert len(store.learner_ids()) == 4
    pipe.shutdown()


def test_ingest_per_learner_drain():
    """drain(learner_id) waits only for THAT learner's queued writes —
    the leave() path must not stall behind the whole queue."""
    slow_gate = threading.Event()

    class GatedStore(InMemoryModelStore):
        def _append(self, learner_id, model):
            if learner_id == "slow":
                slow_gate.wait(10.0)
            super()._append(learner_id, model)

    store = GatedStore()
    pipe = IngestPipeline(store, workers=2)
    pipe.submit("slow", _model(0))
    time.sleep(0.05)  # let the slow write occupy its worker
    pipe.submit("fast", _model(1))
    assert pipe.drain("fast", timeout=10.0)
    assert "fast" in store.learner_ids()
    assert "slow" not in store.learner_ids()  # still gated
    slow_gate.set()
    assert pipe.drain(timeout=10.0)
    assert "slow" in store.learner_ids()
    pipe.shutdown()


def test_ingest_write_failure_is_failsoft():
    """A raising insert is counted, logged, and does NOT wedge the drain
    fence or feed the attribution callback; other learners land."""

    class FlakyStore(InMemoryModelStore):
        def _append(self, learner_id, model):
            if learner_id == "bad":
                raise RuntimeError("disk on fire")
            super()._append(learner_id, model)

    store = FlakyStore()
    samples = []
    pipe = IngestPipeline(store, workers=2,
                          on_insert=lambda lid, ms: samples.append(lid))
    pipe.submit("good", _model(1))
    pipe.submit("bad", _model(2))
    assert pipe.drain(timeout=10.0)
    count, tail = pipe.errors()
    assert count == 1 and "bad" in tail[0]
    assert store.learner_ids() == ["good"]
    assert samples == ["good"]
    pipe.shutdown()


def test_ingest_membership_gate_drops_departed_writes():
    """The worker re-checks ``accept`` right before the write: a queued
    write whose learner was erased between enqueue and execution (a
    completion racing leave()) must not land and resurrect the lineage."""
    gate = threading.Event()
    started = threading.Event()
    members = {"blocker", "alive", "leaving"}

    class GatedStore(InMemoryModelStore):
        def _append(self, learner_id, model):
            if learner_id == "blocker":
                started.set()
                gate.wait(10.0)
            super()._append(learner_id, model)

    store = GatedStore()
    pipe = IngestPipeline(store, workers=1,
                          accept=lambda lid: lid in members)
    pipe.submit("blocker", _model(9))   # occupies the single worker
    assert started.wait(10.0)
    pipe.submit("leaving", _model(0))   # queued behind the blocker
    pipe.submit("alive", _model(1))
    members.discard("leaving")          # leave() erased it while queued
    gate.set()
    assert pipe.drain(timeout=10.0)
    assert sorted(store.learner_ids()) == ["alive", "blocker"]
    count, _ = pipe.errors()
    assert count == 0  # a gate drop is not an error
    pipe.shutdown()


def test_ingest_on_success_fires_only_when_write_lands():
    """Per-submit on_success runs before the drain fence returns, and
    ONLY for writes that landed — the controller pairs result metadata
    with the stored model through it, so a fail-soft write failure must
    not trigger it."""

    class FlakyStore(InMemoryModelStore):
        def _append(self, learner_id, model):
            if learner_id == "bad":
                raise RuntimeError("disk on fire")
            super()._append(learner_id, model)

    store = FlakyStore()
    pipe = IngestPipeline(store, workers=2)
    landed = []
    pipe.submit("good", _model(1), on_success=lambda ms: landed.append(ms))
    pipe.submit("bad", _model(2), on_success=lambda ms: landed.append(-1.0))
    assert pipe.drain(timeout=10.0)
    assert len(landed) == 1 and landed[0] >= 0.0
    pipe.shutdown()


def test_controller_failed_ingest_write_keeps_old_metadata():
    """Controller-level pin for the metadata-pairing invariant: when the
    worker's write fails (fail-soft), the learner's completed_batches /
    last_result_round must keep pairing with the older stored model."""
    import numpy as np

    from metisfl_tpu.comm.messages import JoinRequest, TaskResult, TrainParams
    from metisfl_tpu.config import (AggregationConfig, EvalConfig,
                                    FederationConfig, TelemetryConfig)
    from metisfl_tpu.controller.core import Controller
    from metisfl_tpu.tensor.pytree import pack_model

    class _NullProxy:
        def __init__(self, record):
            self.learner_id = record.learner_id

        def run_task(self, task):
            pass

        def evaluate(self, task, callback):
            pass

        def shutdown(self):
            pass

    cfg = FederationConfig(
        protocol="synchronous",
        aggregation=AggregationConfig(rule="fedavg", scaler="participants"),
        train=TrainParams(batch_size=4, local_steps=1),
        eval=EvalConfig(every_n_rounds=0),
        telemetry=TelemetryConfig(enabled=False),
    )
    cfg.model_store.ingest_workers = 2
    ctrl = Controller(cfg, proxy_factory=_NullProxy)
    try:
        ctrl.set_community_model(pack_model(
            {"w": np.zeros(4, np.float32)}))
        for i in range(2):
            ctrl.join(JoinRequest(hostname="h", port=7600 + i,
                                  num_train_examples=10))
        lids = sorted(ctrl.active_learners())
        with ctrl._lock:
            tokens = {lid: ctrl._learners[lid].auth_token for lid in lids}
        victim = lids[0]
        real_insert = ctrl._store.insert

        def flaky_insert(lid, model):
            if lid == victim:
                raise RuntimeError("disk on fire")
            real_insert(lid, model)

        ctrl._store.insert = flaky_insert
        for i, lid in enumerate(lids):
            assert ctrl.task_completed(TaskResult(
                task_id=f"t0_{lid}", learner_id=lid,
                auth_token=tokens[lid],
                model=pack_model({"w": np.full(4, float(i + 1),
                                               np.float32)}),
                round_id=0, completed_batches=7))
        # completions process on the scheduling executor: the round
        # advancing proves both handlers (and the drain fence before the
        # aggregate) ran
        deadline = time.monotonic() + 30.0
        while ctrl.global_iteration < 1:
            assert time.monotonic() < deadline, "round never completed"
            time.sleep(0.02)
        assert ctrl._ingest.drain(timeout=30.0)
        with ctrl._lock:
            assert ctrl._learners[victim].completed_batches == 0
            assert ctrl._learners[lids[1]].completed_batches == 7
    finally:
        ctrl._store.insert = real_insert
        ctrl.shutdown()


def test_ingest_shutdown_rejects_submits():
    store = InMemoryModelStore()
    pipe = IngestPipeline(store, workers=1)
    pipe.submit("L0", _model(0))
    pipe.shutdown()
    assert "L0" in store.learner_ids()  # shutdown drained first
    with pytest.raises(RuntimeError):
        pipe.submit("L1", _model(1))


def test_ingest_rejects_zero_workers():
    with pytest.raises(ValueError):
        IngestPipeline(InMemoryModelStore(), workers=0)


# --------------------------------------------------------------------- #
# store thread-safety contract (store/base.py)
# --------------------------------------------------------------------- #

def _make_backend(kind: str, root) -> ModelStore:
    if kind == "disk":
        return DiskModelStore(str(root), EvictionPolicy.LINEAGE_LENGTH,
                              lineage_length=1)
    if kind == "cached":
        return CachedDiskStore(str(root), EvictionPolicy.LINEAGE_LENGTH,
                               lineage_length=1, cache_bytes=16 * 1024)
    return InMemoryModelStore()


@pytest.mark.parametrize("kind", ["memory", "disk", "cached"])
def test_select_at_model_width_returns_each_learners_two_newest(tmp_path,
                                                                kind):
    """Three rounds of eight learners' twelve-tensor CNN models (1.41M
    parameters each) under a lineage of two: the select of all returns
    every learner's two newest, newest first, every tensor whole; the
    cached store, budgeted to that working set, serves it without one
    read of the disk."""
    from tests.test_aggregation import CNN_SHAPES, cnn_models

    base = cnn_models(8, seed=5)
    ids = [f"learner_{i}" for i in range(len(base))]
    nbytes = sum(a.nbytes for a in base[0].values())
    policy = dict(policy=EvictionPolicy.LINEAGE_LENGTH, lineage_length=2)
    if kind == "memory":
        store = InMemoryModelStore(**policy)
    elif kind == "disk":
        store = DiskModelStore(str(tmp_path), **policy)
    else:
        store = CachedDiskStore(str(tmp_path), **policy,
                                cache_bytes=nbytes * (2 * len(ids) + 1))
    for r in range(3):
        for lid, model in zip(ids, base):
            store.insert(lid, {n: a + np.float32(r)
                               for n, a in model.items()})
    selected = store.select(ids, k=2)
    assert sorted(selected) == ids
    for lid, model in zip(ids, base):
        lineage = selected[lid]
        assert len(lineage) == 2
        for got, r in zip(lineage, (2, 1)):
            assert set(got) == set(CNN_SHAPES)
            for name, arr in model.items():
                np.testing.assert_array_equal(
                    np.asarray(got[name]), arr + np.float32(r),
                    err_msg=f"{lid} {name} round {r}")
    if kind == "cached":
        assert store.cache_misses == 0 and store.cache_hits >= 2 * len(ids)
        assert store._cached_total == 2 * len(ids) * nbytes


@pytest.mark.parametrize("kind", ["disk", "cached", "memory"])
def test_concurrent_insert_select_erase_hammer(tmp_path, kind):
    """The contract regression test: 8 threads hammer insert/select/erase
    over a shared learner set. No exception may escape, and every value a
    select returns must be internally consistent (both halves carry the
    same tag — a mismatch means a torn lineage was observed)."""
    store = _make_backend(kind, tmp_path / kind)
    ids = [f"L{i}" for i in range(12)]
    stop = time.monotonic() + 2.0
    failures = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        while time.monotonic() < stop:
            lid = ids[int(rng.integers(len(ids)))]
            op = int(rng.integers(10))
            try:
                if op < 5:
                    store.insert(lid, _model(int(rng.integers(1000))))
                elif op < 9:
                    picked = store.select(
                        list(rng.choice(ids, size=3, replace=False)), k=1)
                    for lineage in picked.values():
                        _tag_of(lineage[0])
                else:
                    store.erase([lid])
            except Exception as exc:  # noqa: BLE001 - the assertion
                failures.append(repr(exc))
                return

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not failures, failures
    # post-hammer: the store still works, lineage-length eviction held
    store.insert("L0", _model(42))
    picked = store.select(["L0"], k=4)
    assert _tag_of(picked["L0"][0]) == 42
    assert store.size("L0") == 1
    store.shutdown()


def test_erase_prunes_learner_lock_table(tmp_path):
    """Long-churn federations must not accumulate one lock per learner
    that ever existed (the contract's lock-table hygiene clause)."""
    store = DiskModelStore(str(tmp_path / "s"),
                           EvictionPolicy.LINEAGE_LENGTH, lineage_length=1)
    for i in range(5):
        store.insert(f"L{i}", _model(i))
    assert len(store._learner_locks) == 5
    store.erase([f"L{i}" for i in range(5)])
    assert not store._learner_locks
    assert not store.learner_ids()
    store.shutdown()


def test_disk_flush_batches_directory_fsyncs(tmp_path):
    """Inserts mark their directory dirty instead of fsyncing inline;
    flush() drains the dirty set in one pass (and tolerates a directory
    erased between the write and the flush)."""
    store = DiskModelStore(str(tmp_path / "s"),
                           EvictionPolicy.LINEAGE_LENGTH, lineage_length=1)
    store.insert("L0", _model(0))
    store.insert("L1", _model(1))
    assert len(store._dirty_dirs) == 2
    store.erase(["L1"])  # flush must survive the vanished directory
    store.flush()
    assert not store._dirty_dirs
    store.flush()  # idempotent on a clean store
    assert InMemoryModelStore().flush() is None  # base no-op contract
    store.shutdown()


def test_disk_insert_seq_cache_survives_concurrency(tmp_path):
    """The per-learner sequence cache (no listdir per insert) stays
    monotonic under concurrent same-learner inserts and reseeds from the
    directory after an erase."""
    store = DiskModelStore(str(tmp_path / "s"),
                           EvictionPolicy.LINEAGE_LENGTH, lineage_length=4)
    threads = [threading.Thread(
        target=lambda k=i: [store.insert("L0", _model(k * 10 + j))
                            for j in range(5)]) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert store.size("L0") == 4  # eviction to lineage_length held
    store.erase(["L0"])
    store.insert("L0", _model(99))
    assert _tag_of(store.select(["L0"], k=1)["L0"][0]) == 99
    store.shutdown()


@pytest.mark.slow
def test_ingest_soak_throughput_and_consistency(tmp_path):
    """Soak-scale: 512 learners x 2 generations through a 8-worker
    pipeline with interleaved selects; every final lineage holds the
    second-generation tag (per-learner linearization: generation 2 was
    submitted after generation 1 for each learner)."""
    store = CachedDiskStore(str(tmp_path / "s"),
                            EvictionPolicy.LINEAGE_LENGTH, lineage_length=1,
                            cache_bytes=1 << 20)
    pipe = IngestPipeline(store, workers=8)
    ids = [f"L{i}" for i in range(512)]
    for gen in range(2):
        for i, lid in enumerate(ids):
            pipe.submit(lid, _model(gen * 1000 + i, n=256))
        if gen == 0:
            store.select(ids[:64], k=1)  # selects race the writers
    assert pipe.drain(timeout=120.0)
    picked = store.select(ids, k=1)
    assert sorted(picked) == sorted(ids)
    for i, lid in enumerate(ids):
        assert _tag_of(picked[lid][0]) == 1000 + i
    pipe.shutdown()
    store.shutdown()
