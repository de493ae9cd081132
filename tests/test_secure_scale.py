"""Secure aggregation at distributed scale (docs/SECURITY.md "Secure
aggregation at scale"): the masked partial-fold plane — chunked pair
streams, the k-regular mask graph, masked accumulators at the slice
tier and the distributed reducer, dropout settlement, the config
capability matrix, and the federation-level quorum/deadline recovery
pins."""

import os
import shutil
import tempfile

import numpy as np
import pytest

from metisfl_tpu.comm.messages import TrainParams
from metisfl_tpu.config import (
    AggregationConfig,
    EvalConfig,
    FederationConfig,
    RegistryConfig,
    SchedulingConfig,
    SecureAggConfig,
    TerminationConfig,
    TreeAggregationConfig,
)
from metisfl_tpu.secure import MaskingBackend
from metisfl_tpu.secure import recovery
from metisfl_tpu.secure.distributed import (
    FP_SCALE,
    MaskedAccumulator,
    MaskedStreamingAggregator,
    combine_partials,
    decode_fixed,
    encode_fixed,
    iter_pair_stream,
    mask_partners,
    pair_sign,
    pair_stream,
    unmask,
)
from metisfl_tpu.tensor.pytree import ModelBlob
from metisfl_tpu.tensor.spec import TensorKind, TensorSpec, wire_dtype_of

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------- #
# primitives
# --------------------------------------------------------------------- #

class TestPairStreams:
    def test_chunked_stream_matches_whole_stream(self):
        """Chunks are independently seeded: any range regenerates without
        its prefix, and reassembling the chunks IS the stream."""
        n = 3000
        whole = pair_stream("s", 1, 4, round_id=9, tensor_idx=2, n=n,
                            chunk=256)
        again = np.empty(n, np.uint64)
        for start, values in iter_pair_stream("s", 4, 1, 9, 2, n,
                                              chunk=256):
            again[start:start + len(values)] = values
        np.testing.assert_array_equal(whole, again)
        # a mid-stream chunk regenerates alone, O(chunk) not O(prefix)
        chunks = list(iter_pair_stream("s", 1, 4, 9, 2, n, chunk=256))
        start, values = chunks[5]
        np.testing.assert_array_equal(whole[start:start + 256], values)

    def test_stream_keys_are_pair_round_tensor_scoped(self):
        base = pair_stream("s", 0, 1, 1, 0, 64)
        assert not np.array_equal(base, pair_stream("s", 0, 2, 1, 0, 64))
        assert not np.array_equal(base, pair_stream("s", 0, 1, 2, 0, 64))
        assert not np.array_equal(base, pair_stream("s", 0, 1, 1, 1, 64))
        assert not np.array_equal(base, pair_stream("t", 0, 1, 1, 0, 64))
        # (i, j) and (j, i) are the SAME stream — cancellation needs it
        np.testing.assert_array_equal(base, pair_stream("s", 1, 0, 1, 0, 64))

    def test_pair_sign_antisymmetric(self):
        assert pair_sign(1, 5) == -pair_sign(5, 1)

    def test_mask_partners_complete_and_ring(self):
        # 0 = complete Bonawitz graph
        assert mask_partners(2, 5, 0) == [0, 1, 3, 4]
        # k-regular ring is symmetric: j in partners(i) <=> i in partners(j)
        n, k = 11, 4
        for i in range(n):
            for j in mask_partners(i, n, k):
                assert i in mask_partners(j, n, k)
        # degree is k (radius (k+1)//2 each way on the ring)
        assert len(mask_partners(0, 100, 8)) == 8
        # k >= n-1 degenerates to complete
        assert mask_partners(0, 4, 99) == [1, 2, 3]

    def test_fixed_point_roundtrip(self):
        rng = np.random.default_rng(0)
        values = rng.standard_normal(512)
        decoded = decode_fixed(encode_fixed(values))
        np.testing.assert_allclose(decoded, values, atol=2.0 / FP_SCALE)

    def test_pairwise_masks_cancel_in_ring_graph(self):
        """Sum of every party's masked encoding equals the plain sum mod
        2^64 — under the k-regular graph, not just the complete one."""
        n, dim, k = 7, 96, 4
        rng = np.random.default_rng(1)
        vecs = [rng.standard_normal(dim) for _ in range(n)]
        total = np.zeros(dim, np.uint64)
        for i in range(n):
            acc = encode_fixed(vecs[i])
            for j in mask_partners(i, n, k):
                stream = pair_stream("sec", i, j, 3, 0, dim)
                acc = (acc + stream if pair_sign(i, j) > 0
                       else acc - stream)
            total = total + acc
        got = decode_fixed(total, 1.0 / n)
        np.testing.assert_allclose(got, np.mean(vecs, axis=0), atol=1e-9)


# --------------------------------------------------------------------- #
# masked accumulators + settlement
# --------------------------------------------------------------------- #

N_DIM = 64
SECRET = "scale-secret"


def _masked_blob(backends, idx, rid, plains):
    spec = TensorSpec((N_DIM,), wire_dtype_of(np.dtype(np.float32)),
                      TensorKind.MASKED)
    backends[idx].begin_round(rid)
    payload = backends[idx].encrypt(plains[idx])
    return ModelBlob(opaque={"w": (payload, spec)}).to_bytes()


def _cohort(n):
    rng = np.random.default_rng(0)
    plains = [rng.standard_normal(N_DIM) * 0.1 for _ in range(n)]
    backends = [MaskingBackend(federation_secret=SECRET, party_index=i,
                               num_parties=n, min_parties=2)
                for i in range(n)]
    return backends, plains


class TestMaskedAccumulator:
    def test_fold_skips_duplicates_and_unmasks_to_mean(self):
        n = 3
        backends, plains = _cohort(n)
        acc = MaskedAccumulator()
        for i in range(n):
            blob = ModelBlob.from_bytes(_masked_blob(backends, i, 5, plains))
            assert acc.fold(f"L{i}", dict(blob.opaque))
        # one-time-pad discipline: the re-ship is byte-identical, so the
        # duplicate skip is sound — and must not double-count
        blob = ModelBlob.from_bytes(_masked_blob(backends, 1, 5, plains))
        assert not acc.fold("L1", dict(blob.opaque))
        assert acc.count == n
        sums, _specs, contributors = acc.snapshot()
        assert sorted(contributors) == ["L0", "L1", "L2"]
        payloads = unmask(sums, None, 1.0 / n)
        got = np.frombuffer(payloads["w"], np.float64)
        np.testing.assert_allclose(got, np.mean(plains, axis=0), atol=1e-9)

    def test_fold_rejects_mismatched_tensor_set(self):
        acc = MaskedAccumulator()
        spec = object()
        acc.fold("L0", {"w": (b"\0" * 16, spec)})
        with pytest.raises(ValueError, match="tensor set"):
            acc.fold("L1", {"v": (b"\0" * 16, spec)})
        with pytest.raises(ValueError, match="values"):
            acc.fold("L2", {"w": (b"\0" * 24, spec)})

    def test_combine_partials_matches_single_accumulator(self):
        n = 3
        backends, plains = _cohort(n)
        a1, a2 = MaskedAccumulator(), MaskedAccumulator()
        for i in (0, 1):
            blob = ModelBlob.from_bytes(_masked_blob(backends, i, 9, plains))
            a1.fold(f"L{i}", dict(blob.opaque))
        blob = ModelBlob.from_bytes(_masked_blob(backends, 2, 9, plains))
        a2.fold("L2", dict(blob.opaque))
        root = MaskedAccumulator()
        for part in (a1, a2):
            s, sp, c = part.snapshot()
            root.merge_sums(s, c, sp)
        sums, _specs, contributors = root.snapshot()
        assert sorted(contributors) == ["L0", "L1", "L2"]
        np.testing.assert_array_equal(
            combine_partials([a1.snapshot()[0], a2.snapshot()[0]])["w"],
            sums["w"])
        got = np.frombuffer(unmask(sums, None, 1.0 / n)["w"], np.float64)
        np.testing.assert_allclose(got, np.mean(plains, axis=0), atol=1e-9)

    def test_settle_full_cohort_and_dropout(self):
        n = 4
        backends, plains = _cohort(n)
        acc = MaskedAccumulator()
        for i in range(n - 1):  # party 3 dropped
            blob = ModelBlob.from_bytes(_masked_blob(backends, i, 2, plains))
            acc.fold(f"L{i}", dict(blob.opaque))
        sums, _specs, _c = acc.snapshot()

        def recover_fn(rid, surviving, dropped, lengths):
            return backends[0].recovery_correction(rid, surviving,
                                                   dropped, lengths)

        payloads, report = recovery.settle(
            sums, {f"L{i}": i for i in range(n - 1)}, n, 2, 2, recover_fn)
        got = np.frombuffer(payloads["w"], np.float64)
        np.testing.assert_allclose(got, np.mean(plains[:3], axis=0),
                                   atol=1e-9)
        assert report.dropped == [3] and report.recovered

    def test_settle_at_a_thousand_clients_names_every_dropout(self):
        """A thousand virtual clients on the k-regular mask graph (eight
        partners each, so a client's masking does not grow with the
        cohort), ten of them expired: the root's masked fold of the 990
        settles to their plain mean, the residual regenerated by one
        survivor, and the report names the ten."""
        n, k, rid = 1000, 8, 7
        rng = np.random.default_rng(29)
        plains = rng.standard_normal((n, N_DIM)) * 0.1
        backends = [MaskingBackend(federation_secret=SECRET, party_index=i,
                                   num_parties=n, min_parties=2,
                                   neighbors=k) for i in range(n)]
        dropped = list(range(50, n, 100))
        survivors = [i for i in range(n) if i not in dropped]
        acc = MaskedAccumulator()
        for i in survivors:
            blob = ModelBlob.from_bytes(_masked_blob(backends, i, rid,
                                                     plains))
            assert acc.fold(f"L{i:04d}", dict(blob.opaque))
        sums, _specs, contributors = acc.snapshot()
        assert len(contributors) == len(survivors) == 990
        # masked: the sum as it stands is nowhere near the plain one
        raw = np.frombuffer(unmask(sums, None, 1.0 / len(survivors))["w"],
                            np.float64)
        assert not np.allclose(raw, plains[survivors].mean(axis=0),
                               atol=1e-3)
        payloads, report = recovery.settle(
            sums, {f"L{i:04d}": i for i in survivors}, n, 2, rid,
            lambda *a: backends[survivors[0]].recovery_correction(*a))
        assert report.recovered and report.dropped == dropped
        assert report.surviving == survivors
        np.testing.assert_allclose(
            np.frombuffer(payloads["w"], np.float64),
            plains[survivors].mean(axis=0), atol=1e-9)

    def test_settle_refuses_below_threshold(self):
        n = 4
        backends, plains = _cohort(n)
        acc = MaskedAccumulator()
        blob = ModelBlob.from_bytes(_masked_blob(backends, 0, 2, plains))
        acc.fold("L0", dict(blob.opaque))
        sums, _specs, _c = acc.snapshot()
        with pytest.raises(RuntimeError, match="surviving"):
            recovery.settle(sums, {"L0": 0}, n, 2, 2, lambda *a: None)


class TestMaskedStreaming:
    def test_stream_folds_to_same_bits_as_batch(self):
        n = 3
        backends, plains = _cohort(n)
        stream = MaskedStreamingAggregator()
        stream.begin_round(6)
        for i in range(n):
            blob = ModelBlob.from_bytes(_masked_blob(backends, i, 6, plains))
            assert stream.fold(f"L{i}", dict(blob.opaque), 6)
        sums, _specs, contributors = stream.finish([f"L{i}" for i in range(n)])
        batch = MaskedAccumulator()
        for i in range(n):
            blob = ModelBlob.from_bytes(_masked_blob(backends, i, 6, plains))
            batch.fold(f"L{i}", dict(blob.opaque))
        np.testing.assert_array_equal(sums["w"], batch.snapshot()[0]["w"])
        assert sorted(contributors) == ["L0", "L1", "L2"]

    def test_begin_round_rotates_and_finish_rejects_strangers(self):
        n = 2
        backends, plains = _cohort(n)
        stream = MaskedStreamingAggregator()
        stream.begin_round(1)
        blob = ModelBlob.from_bytes(_masked_blob(backends, 0, 1, plains))
        stream.fold("L0", dict(blob.opaque), 1)
        stream.begin_round(2)  # rotation: round-1 masks are dead
        assert stream.stats()["folded"] == 0
        blob = ModelBlob.from_bytes(_masked_blob(backends, 1, 2, plains))
        stream.fold("L1", dict(blob.opaque), 2)
        with pytest.raises(RuntimeError, match="L1"):
            stream.finish(["L0"])  # L1 folded but is not selected


# --------------------------------------------------------------------- #
# slice tier + distributed reducer (real gRPC loopback)
# --------------------------------------------------------------------- #

class TestSliceMasked:
    def test_hold_stream_and_spool_reload(self, tmp_path):
        from metisfl_tpu.aggregation.slice import SliceAggregator

        n = 3
        backends, plains = _cohort(n)
        spool = str(tmp_path / "s0")
        agg = SliceAggregator(spool_dir=spool, name="s0")
        for i in range(n):
            agg.submit(f"L{i}", 7, _masked_blob(backends, i, 7, plains))
        reply = agg.fold_masked([f"L{i}" for i in range(n)], 7)
        assert reply["masked"] and reply["count"] == n
        acc = ModelBlob.from_bytes(reply["acc"])
        sums = {name: np.frombuffer(p, np.uint64).copy()
                for name, (p, _s) in acc.opaque.items()}
        got = np.frombuffer(unmask(sums, None, 1.0 / n)["w"], np.float64)
        np.testing.assert_allclose(got, np.mean(plains, axis=0), atol=1e-9)

        # stream mode folds on arrival; the duplicate re-ship is skipped
        agg2 = SliceAggregator(spool_dir=str(tmp_path / "s1"), name="s1")
        for i in range(n):
            agg2.submit(f"L{i}", 7, _masked_blob(backends, i, 7, plains),
                        stream=True)
        agg2.submit("L1", 7, _masked_blob(backends, 1, 7, plains),
                    stream=True)
        reply2 = agg2.fold_masked([f"L{i}" for i in range(n)], 7,
                                  stream=True)
        assert reply2["count"] == n
        acc2 = ModelBlob.from_bytes(reply2["acc"])
        np.testing.assert_array_equal(
            np.frombuffer(acc2.opaque["w"][0], np.uint64), sums["w"])

        # relaunch from the same spool dir: bit-identical recovery
        agg3 = SliceAggregator(spool_dir=spool, name="s0")
        reply3 = agg3.fold_masked([f"L{i}" for i in range(n)], 7)
        acc3 = ModelBlob.from_bytes(reply3["acc"])
        np.testing.assert_array_equal(
            np.frombuffer(acc3.opaque["w"][0], np.uint64), sums["w"])


class TestReducerMasked:
    def _boot(self, tmp, n_slices=2):
        from metisfl_tpu.aggregation.slice import SliceServer

        servers, specs = [], []
        for i in range(n_slices):
            spool = os.path.join(tmp, f"slice_{i}")
            server = SliceServer(spool_dir=spool, name=f"slice_{i}",
                                 host="127.0.0.1", port=0)
            port = server.start()
            servers.append(server)
            specs.append({"name": f"slice_{i}", "host": "127.0.0.1",
                          "port": port, "spool_dir": spool})
        return servers, specs

    def test_masked_reduce_full_dropout_and_rehome(self):
        from metisfl_tpu.aggregation.distributed import (
            DistributedSliceReducer)

        n = 4
        backends, plains = _cohort(n)
        tmp = tempfile.mkdtemp(prefix="test_reducer_masked_")
        servers, specs = self._boot(tmp)
        red = DistributedSliceReducer(
            TreeAggregationConfig(enabled=True, branch=2, distributed=True,
                                  slices=list(specs), rehome_retries=2,
                                  rehome_backoff_s=0.02),
            masked=True, stream=True)
        ids = [f"L{i}" for i in range(n)]
        try:
            # full cohort, one byte-identical re-ship
            red.assign(ids)
            for i in range(n):
                assert red.submit(f"L{i}", _masked_blob(backends, i, 3,
                                                        plains), 3)
            red.submit("L2", _masked_blob(backends, 2, 3, plains), 3)
            sums, _specs, present, errors = red.reduce_masked(ids, 3)
            assert sorted(present) == ids and not errors
            payloads, report = recovery.settle(
                sums, {lid: i for i, lid in enumerate(ids)}, n, 2, 3,
                lambda *a: None)
            got = np.frombuffer(payloads["w"], np.float64)
            np.testing.assert_allclose(got, np.mean(plains, axis=0),
                                       atol=1e-9)
            assert not report.dropped

            # dropout: 3 of 4 contribute; root settles via recovery
            red.assign(ids)
            for i in range(n - 1):
                red.submit(f"L{i}", _masked_blob(backends, i, 4, plains), 4)
            sums, _specs, present, errors = red.reduce_masked(ids, 4)
            assert sorted(present) == ids[:3]
            payloads, report = recovery.settle(
                sums, {lid: i for i, lid in enumerate(ids[:3])}, n, 2, 4,
                lambda *a, **k: backends[0].recovery_correction(*a))
            got = np.frombuffer(payloads["w"], np.float64)
            np.testing.assert_allclose(got, np.mean(plains[:3], axis=0),
                                       atol=1e-9)
            assert report.dropped == [3] and report.recovered

            # slice death mid-round: spool recovery keeps the sums exact
            red.assign(ids)
            for i in range(n):
                red.submit(f"L{i}", _masked_blob(backends, i, 5, plains), 5)
            servers[0].stop()
            sums, _specs, present, _errors = red.reduce_masked(ids, 5)
            assert sorted(present) == ids
            payloads, _report = recovery.settle(
                sums, {lid: i for i, lid in enumerate(ids)}, n, 2, 5,
                lambda *a: None)
            got = np.frombuffer(payloads["w"], np.float64)
            np.testing.assert_allclose(got, np.mean(plains, axis=0),
                                       atol=1e-9)
        finally:
            red.shutdown()
            for server in servers:
                try:
                    server.stop()
                except Exception:  # noqa: BLE001 - already-dead slice
                    pass
            shutil.rmtree(tmp, ignore_errors=True)


# --------------------------------------------------------------------- #
# capability matrix (config/federation.py) — messages test-pinned
# --------------------------------------------------------------------- #

def _cfg(**kw):
    secure = kw.pop("secure", None)
    agg = kw.pop("aggregation", None)
    return FederationConfig(
        aggregation=agg or AggregationConfig(),
        secure=secure or SecureAggConfig(),
        eval=EvalConfig(every_n_rounds=0), **kw)


def _masking(**kw):
    return SecureAggConfig(enabled=True, scheme="masking", **kw)


class TestCapabilityMatrix:
    def test_masking_composes_with_streaming(self):
        _cfg(secure=_masking(), aggregation=AggregationConfig(
            rule="secure_agg", scaler="participants", streaming=True))

    def test_masking_composes_with_distributed_tree(self):
        _cfg(secure=_masking(), aggregation=AggregationConfig(
            rule="secure_agg", scaler="participants",
            tree=TreeAggregationConfig(enabled=True, branch=2,
                                       distributed=True)))

    def test_masking_composes_with_streaming_and_distributed(self):
        _cfg(secure=_masking(), aggregation=AggregationConfig(
            rule="secure_agg", scaler="participants", streaming=True,
            tree=TreeAggregationConfig(enabled=True, branch=2,
                                       distributed=True)))

    def test_masking_composes_with_registry(self):
        _cfg(secure=_masking(), aggregation=AggregationConfig(
            rule="secure_agg", scaler="participants"),
            registry=RegistryConfig(enabled=True))

    def test_ckks_registry_rejected_naming_masking(self):
        with pytest.raises(ValueError, match="use scheme: masking"):
            _cfg(secure=SecureAggConfig(enabled=True, scheme="ckks"),
                 aggregation=AggregationConfig(rule="secure_agg"),
                 registry=RegistryConfig(enabled=True))

    def test_ckks_streaming_rejected_naming_masking(self):
        with pytest.raises(ValueError,
                           match="requires\nsecure.scheme: masking"
                                 "|requires secure.scheme: masking"):
            _cfg(secure=SecureAggConfig(enabled=True, scheme="ckks"),
                 aggregation=AggregationConfig(rule="secure_agg",
                                               streaming=True))

    def test_ckks_distributed_rejected_naming_masking(self):
        with pytest.raises(ValueError, match="secure.scheme: masking"):
            _cfg(secure=SecureAggConfig(enabled=True, scheme="ckks"),
                 aggregation=AggregationConfig(
                     rule="secure_agg",
                     tree=TreeAggregationConfig(enabled=True, branch=2,
                                                distributed=True)))

    def test_plain_distributed_streaming_still_rejected(self):
        with pytest.raises(ValueError, match="masking secure"):
            _cfg(aggregation=AggregationConfig(
                streaming=True,
                tree=TreeAggregationConfig(enabled=True, branch=2,
                                           distributed=True)))

    def test_distributed_ingest_rejected_scheme_independent(self):
        from metisfl_tpu.config import ModelStoreConfig
        with pytest.raises(ValueError, match="every secure scheme"):
            _cfg(secure=_masking(), aggregation=AggregationConfig(
                rule="secure_agg", scaler="participants",
                tree=TreeAggregationConfig(enabled=True, branch=2,
                                           distributed=True)),
                model_store=ModelStoreConfig(ingest_workers=2))

    def test_scaler_message_names_the_composing_config(self):
        """Satellite pin: the rejection tells the operator the supported
        alternative, not just what is rejected."""
        with pytest.raises(ValueError) as err:
            _cfg(secure=_masking(), aggregation=AggregationConfig(
                rule="secure_agg", scaler="train_dataset_size"))
        msg = str(err.value)
        assert "aggregation.scaler: participants" in msg
        assert "composes with aggregation.streaming" in msg
        assert "aggregation.tree.distributed" in msg
        assert "quorum dropout" in msg

    def test_async_message_names_semi_synchronous_and_ckks(self):
        with pytest.raises(ValueError) as err:
            _cfg(secure=_masking(), aggregation=AggregationConfig(
                rule="secure_agg", scaler="participants"),
                protocol="asynchronous")
        msg = str(err.value)
        assert "semi_synchronous" in msg
        assert "seed-share recovery" in msg
        assert "scheme: ckks" in msg

    def test_staleness_message_names_settlement_path(self):
        with pytest.raises(ValueError) as err:
            _cfg(secure=_masking(), aggregation=AggregationConfig(
                rule="secure_agg", scaler="participants",
                staleness_decay=0.5), protocol="semi_synchronous")
        msg = str(err.value)
        assert "min_recovery_parties" in msg

    def test_mask_neighbors_validated(self):
        with pytest.raises(ValueError, match="mask_neighbors"):
            _cfg(secure=_masking(mask_neighbors=-1),
                 aggregation=AggregationConfig(rule="secure_agg",
                                               scaler="participants"))
        _cfg(secure=_masking(mask_neighbors=8),
             aggregation=AggregationConfig(rule="secure_agg",
                                           scaler="participants"))


def test_template_pins_secure_block_both_ways():
    """template.yaml's secure block matches the dataclass defaults field
    for field, and every SecureAggConfig field is documented there."""
    import yaml

    with open(os.path.join(REPO, "examples", "config",
                           "template.yaml")) as f:
        template = yaml.safe_load(f)
    block = template["secure"]
    defaults = SecureAggConfig()
    for name in defaults.__dataclass_fields__:
        assert name in block, f"template.yaml secure block missing {name}"
        assert block[name] == getattr(defaults, name), (
            f"template.yaml secure.{name} documents {block[name]!r}, "
            f"dataclass default is {getattr(defaults, name)!r}")


# --------------------------------------------------------------------- #
# federation-level dropout settlement — both schedulers
# --------------------------------------------------------------------- #

def _build_federation(secure: bool, scheduling: SchedulingConfig,
                      round_deadline_secs: float):
    from metisfl_tpu.driver import InProcessFederation
    from metisfl_tpu.models import ArrayDataset, FlaxModelOps
    from metisfl_tpu.models.zoo import MLP

    n = 3
    if secure:
        agg = AggregationConfig(rule="secure_agg", scaler="participants",
                                streaming=True)
        sec = SecureAggConfig(enabled=True, scheme="masking",
                              min_recovery_parties=2)
        backends = [MaskingBackend(federation_secret="fed", party_index=i,
                                   num_parties=n) for i in range(n)]
        controller_backend = MaskingBackend(num_parties=n)
    else:
        agg = AggregationConfig(rule="fedavg", scaler="participants")
        sec = SecureAggConfig()
        backends = [None] * n
        controller_backend = None
    config = FederationConfig(
        protocol="synchronous",
        aggregation=agg,
        secure=sec,
        scheduling=scheduling,
        round_deadline_secs=round_deadline_secs,
        train=TrainParams(batch_size=16, local_steps=3, learning_rate=0.05),
        eval=EvalConfig(every_n_rounds=0),
        termination=TerminationConfig(federation_rounds=1),
    )
    fed = InProcessFederation(config, secure_backend=controller_backend)
    rng = np.random.default_rng(3)
    w = rng.standard_normal((5, 3)).astype(np.float32)
    template = None
    for i in range(n):
        x = rng.standard_normal((48, 5)).astype(np.float32)
        y = np.argmax(x @ w, axis=-1).astype(np.int32)
        ds = ArrayDataset(x, y, seed=i)
        engine = FlaxModelOps(MLP(features=(8,), num_outputs=3), ds.x[:2])
        if template is None:
            template = engine.get_variables()
        else:
            engine.set_variables(template)
        fed.add_learner(engine, ds, secure_backend=backends[i])
    fed.seed_model(template)
    return fed


def _gate_learners(fed):
    """Learner 2 hangs on EVERY task (the expired dropout); learners 0/1
    run exactly their first train task then hang too, freezing the
    community at round 1's settled aggregate for a race-free read."""
    for idx, learner in enumerate(fed.learners):
        orig = learner.run_task
        count = [0]

        def gated(task, _orig=orig, _count=count, _hang=(idx == 2)):
            _count[0] += 1
            if _hang or _count[0] > 1:
                return  # accepted, never reports
            _orig(task)

        learner.run_task = gated


def _flat_community(blob_bytes):
    blob = ModelBlob.from_bytes(blob_bytes)
    out = {}
    for name, arr in blob.tensors:
        out[name] = np.asarray(arr, np.float64).ravel()
    for name, (payload, _spec) in blob.opaque.items():
        out[name] = np.frombuffer(bytes(payload), np.float64).copy()
    return out


def _round1_community(secure, scheduling, round_deadline_secs):
    fed = _build_federation(secure, scheduling, round_deadline_secs)
    _gate_learners(fed)
    try:
        fed.start()
        assert fed.wait_for_rounds(1, timeout_s=120), (
            "federation stalled before settling the dropout "
            f"(secure={secure})")
        stats = fed.statistics()
        meta0 = stats["round_metadata"][0]
        assert len(meta0["selected_learners"]) == 2, meta0
        assert not any("aggregation failed" in err
                       for err in meta0["errors"]), meta0["errors"]
        return _flat_community(fed.controller.community_model_bytes())
    finally:
        fed.shutdown()


SCHEDULERS = {
    # quorum release: the round frees at 2 reporters, long before the
    # generous deadline — the hung learner expires via the quorum path
    "quorum": (SchedulingConfig(quorum=2, overprovision=0.5), 30.0),
    # deadline: full barrier, the hung learner expires when the round
    # deadline fires
    "deadline": (SchedulingConfig(), 2.0),
}


@pytest.mark.parametrize("scheduler", sorted(SCHEDULERS))
def test_masking_dropout_settles_to_survivors_plain_fold(scheduler):
    """Satellite pin: a learner expired by quorum release AND one expired
    by the round deadline each have their masks settled — the masked
    community equals the same-seed survivors-only PLAIN fold within the
    fixed-point tolerance, under the streaming masked plane."""
    scheduling, deadline = SCHEDULERS[scheduler]
    masked = _round1_community(True, scheduling, deadline)
    plain = _round1_community(False, scheduling, deadline)
    assert set(masked) == set(plain)
    for name in sorted(masked):
        np.testing.assert_allclose(
            masked[name], plain[name], atol=1e-5,
            err_msg=f"{scheduler}: tensor {name} diverged from the "
                    "survivors-only plain fold")
