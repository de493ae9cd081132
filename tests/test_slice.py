"""Distributed slice aggregators (ISSUE 12; docs/RESILIENCE.md
"Distributed slice aggregators", docs/SCALE.md §4): the spool durability
contract, fold bit-identity vs the in-process tier, mid-round re-homing
(kill one of N, round completes, community bits unchanged), graceful
degradation to the root, the one-attribute-check opt-out, config
rejections, TreeReducer error-path hardening, and the bench-artifact
gitignore regression."""

import logging
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from metisfl_tpu.aggregation.distributed import (
    ROOT,
    DistributedSliceReducer,
)
from metisfl_tpu.aggregation.slice import (
    SliceAggregator,
    SliceClient,
    SliceServer,
    read_spool,
    spool_path,
)
from metisfl_tpu.aggregation.tree import _DEFAULT_SUBBLOCK, TreeReducer
from metisfl_tpu.comm.messages import JoinRequest, TaskResult, TrainParams
from metisfl_tpu.config import (
    AggregationConfig,
    EvalConfig,
    FederationConfig,
    SecureAggConfig,
    TelemetryConfig,
    TreeAggregationConfig,
)
from metisfl_tpu.controller.core import Controller
from metisfl_tpu.telemetry import events as _tevents
from metisfl_tpu.tensor.pytree import ModelBlob, pack_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _model(i, r=0, integer=True):
    rng = np.random.default_rng(1000 * r + i)
    if integer:
        return {"enc/w": rng.integers(-8, 8, (6, 4)).astype(np.float32),
                "head/b": rng.integers(-8, 8, 4).astype(np.float32)}
    return {"enc/w": rng.standard_normal((6, 4)).astype(np.float32),
            "head/b": rng.standard_normal(4).astype(np.float32)}


def _blob(model):
    return ModelBlob(tensors=sorted(model.items())).to_bytes()


def _boot_servers(tmp_path, n):
    servers, specs = [], []
    for i in range(n):
        spool = str(tmp_path / f"slice_{i}")
        server = SliceServer(spool_dir=spool, name=f"slice_{i}",
                             host="127.0.0.1", port=0)
        port = server.start()
        servers.append(server)
        specs.append({"name": f"slice_{i}", "host": "127.0.0.1",
                      "port": port, "spool_dir": spool})
    return servers, specs


def _reducer(specs, retries=2, backoff=0.02):
    return DistributedSliceReducer(
        TreeAggregationConfig(enabled=True, branch=len(specs),
                              distributed=True, slices=list(specs),
                              rehome_retries=retries,
                              rehome_backoff_s=backoff))


def _stop_all(servers, reducer=None):
    if reducer is not None:
        reducer.shutdown()
    for server in servers:
        server.stop()


# --------------------------------------------------------------------- #
# slice aggregator: spool durability + fold kernel identity
# --------------------------------------------------------------------- #

def test_spool_written_before_ack_and_recoverable(tmp_path):
    agg = SliceAggregator(spool_dir=str(tmp_path / "s0"), name="s0")
    models = {f"L{i}": _model(i) for i in range(4)}
    for lid, m in models.items():
        held = agg.submit(lid, 0, _blob(m))
        # acked ⇒ durable: the spool file exists the moment submit returns
        assert os.path.exists(spool_path(str(tmp_path / "s0"), lid))
    assert held == 4
    recovered = read_spool(str(tmp_path / "s0"))
    assert sorted(recovered) == sorted(models)
    for lid, raw in recovered.items():
        got = dict(ModelBlob.from_bytes(raw).tensors)
        for k in models[lid]:
            np.testing.assert_array_equal(got[k], models[lid][k])


def test_spool_skips_torn_files(tmp_path):
    agg = SliceAggregator(spool_dir=str(tmp_path / "s0"), name="s0")
    agg.submit("LA", 0, _blob(_model(1)))
    with open(tmp_path / "s0" / "torn.bin", "wb") as fh:
        fh.write(b"\x00garbage")
    recovered = read_spool(str(tmp_path / "s0"))
    assert sorted(recovered) == ["LA"]


def test_spool_roundtrips_hostile_learner_ids(tmp_path):
    """The exact learner id rides inside the spool record — an id the
    filename sanitizer would mangle (e.g. an IPv6 host) must still key
    its recovered uplink correctly — and two DISTINCT hostile ids that
    sanitize identically must not collide onto one durability record."""
    agg = SliceAggregator(spool_dir=str(tmp_path / "s0"), name="s0")
    hostile = "L0_[::1]:443_50052"
    agg.submit(hostile, 0, _blob(_model(3)))
    assert sorted(read_spool(str(tmp_path / "s0"))) == [hostile]
    agg.submit("a:b", 0, _blob(_model(4)))
    agg.submit("a?b", 0, _blob(_model(5)))
    recovered = read_spool(str(tmp_path / "s0"))
    assert {"a:b", "a?b"} <= set(recovered)
    for lid, ref in (("a:b", _model(4)), ("a?b", _model(5))):
        got = dict(ModelBlob.from_bytes(recovered[lid]).tensors)
        np.testing.assert_array_equal(got["enc/w"], ref["enc/w"])


def test_relaunched_aggregator_reloads_spool(tmp_path):
    """Acked ⇒ durable works across a process relaunch too: a fresh
    SliceAggregator over the same spool dir holds the dead
    incarnation's models fold-ready (the store path's cross-round
    lineage semantics)."""
    spool = str(tmp_path / "s0")
    first = SliceAggregator(spool_dir=spool, name="s0")
    models = {f"L{i}": _model(i, integer=False) for i in range(3)}
    for lid, m in models.items():
        first.submit(lid, 0, _blob(m))
    relaunched = SliceAggregator(spool_dir=spool, name="s0")
    reply = relaunched.fold(sorted(models),
                            {lid: 1.0 for lid in models})
    assert reply["count"] == 3
    ref = TreeReducer._fold_slice(
        sorted(models), {lid: 1.0 for lid in models},
        lambda b: {l: [models[l]] for l in b}, _DEFAULT_SUBBLOCK)
    acc = dict(ModelBlob.from_bytes(reply["acc"]).tensors)
    for k in acc:
        np.testing.assert_array_equal(acc[k], ref.acc[k], err_msg=k)


def test_slice_fold_bit_identical_to_tree_worker(tmp_path):
    """A slice's FoldPartial must be byte-for-byte the partial a
    TreeReducer worker computes from the same models (same kernels,
    same sub-block blocking, same accumulator dtype)."""
    agg = SliceAggregator(spool_dir="", name="s0")
    ids = [f"L{i:02d}" for i in range(9)]
    models = {lid: _model(i, integer=False) for i, lid in enumerate(ids)}
    scales = {lid: 0.25 for lid in ids}
    for lid in ids:
        agg.submit(lid, 0, _blob(models[lid]))
    for stride in (0, 4):
        reply = agg.fold(ids, scales, stride=stride)
        ref = TreeReducer._fold_slice(
            ids, scales, lambda b: {l: [models[l]] for l in b},
            int(stride) or _DEFAULT_SUBBLOCK)
        assert reply["count"] == ref.count == 9
        assert reply["z"] == ref.z
        assert tuple(reply["dtypes"]) == ref.dtypes
        acc = dict(ModelBlob.from_bytes(reply["acc"]).tensors)
        for k in acc:
            np.testing.assert_array_equal(acc[k], ref.acc[k], err_msg=k)
    # latest-wins lineage semantics: a re-submission replaces
    agg.submit(ids[0], 1, _blob(_model(77, integer=False)))
    reply = agg.fold([ids[0]], {ids[0]: 1.0})
    acc = dict(ModelBlob.from_bytes(reply["acc"]).tensors)
    np.testing.assert_array_equal(
        acc["enc/w"], _model(77, integer=False)["enc/w"].astype(np.float32))


def test_slice_server_grpc_roundtrip(tmp_path):
    servers, specs = _boot_servers(tmp_path, 1)
    client = SliceClient(specs[0]["host"], specs[0]["port"])
    try:
        client.submit("LA", 0, _blob(_model(1)))
        client.submit("LB", 0, _blob(_model(2)))
        reply = client.fold(["LA", "LB"], {"LA": 1.0, "LB": 1.0})
        assert reply["count"] == 2 and reply["present"] == ["LA", "LB"]
        stats = client.describe()
        assert stats["held"] == 2 and stats["uplinks"] == 2
        assert stats["bytes_digest"]  # the mergeable rollup rides along
        assert client.forget(["LA"])["dropped"] == 1
        assert client.describe()["held"] == 1
        assert not os.path.exists(spool_path(specs[0]["spool_dir"], "LA"))
    finally:
        client.close()
        _stop_all(servers)


# --------------------------------------------------------------------- #
# distributed reduce: bit-identity, re-homing, degradation
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("branch", [2, 3])
def test_distributed_reduce_bit_identical_to_tree(tmp_path, branch):
    """The pinned config (integer payloads, uniform power-of-two
    weights): distributed fan-in == in-process TreeReducer == any other
    blocking, bit for bit."""
    servers, specs = _boot_servers(tmp_path, branch)
    red = _reducer(specs)
    tree = TreeReducer(branch=branch)
    try:
        ids = [f"L{i:02d}" for i in range(8)]
        models = {lid: _model(i) for i, lid in enumerate(ids)}
        scales = {lid: 1.0 for lid in ids}
        red.assign(ids)
        for lid in ids:
            assert red.submit(lid, models[lid], 0)
        got, partials, errors = red.reduce(ids, scales, stride=0)
        assert not errors and len(partials) == branch
        ref, _ = tree.reduce(sorted(ids), scales,
                             lambda b: {l: [models[l]] for l in b})
        for k in got:
            np.testing.assert_array_equal(got[k], ref[k], err_msg=k)
    finally:
        _stop_all(servers, red)
        tree.shutdown()


def test_rehome_mid_round_completes_bit_identical(tmp_path, caplog):
    """The tentpole pin: kill one of three aggregators after half the
    uplinks landed — the slice re-homes (spool recovery → survivor),
    the reduce completes, slice_rehomed fires, and the community bits
    equal the undisturbed run's (f32 models — sorted-id folds make the
    bits a pure function of the contributor set)."""
    ids = [f"L{i:02d}" for i in range(12)]
    models = {lid: _model(i, integer=False) for i, lid in enumerate(ids)}
    scales = {lid: 1.0 / 12 for lid in ids}

    def run(kill):
        servers, specs = _boot_servers(tmp_path / str(kill), 3)
        red = _reducer(specs)
        try:
            red.assign(ids)
            for lid in ids[:6]:
                red.submit(lid, models[lid], 0)
            if kill:
                servers[0].stop()
            for lid in ids[6:]:
                red.submit(lid, models[lid], 0)
            out = red.reduce(ids, scales, stride=0, round_id=0)
            assert out is not None
            community, partials, _ = out
            # group boundaries are assignment-keyed: 3 partials even
            # with one aggregator dead
            assert len(partials) == 3
            assert sum(p.count for p in partials) == 12
            return community, red.rehomed_total
        finally:
            _stop_all(servers, red)

    # Cursor, not a length snapshot: the journal ring is bounded
    # (DEFAULT_RING_SIZE), so once earlier tests fill it a [len:]
    # slice is empty forever even as new records land.
    before_seq = max((e.get("seq", 0) for e in _tevents.tail(0)), default=0)
    killed, rehomed = run(kill=True)
    control, control_rehomed = run(kill=False)
    assert rehomed == 1 and control_rehomed == 0
    kinds = [e["kind"] for e in _tevents.tail_since(before_seq)]
    assert "slice_aggregator_lost" in kinds
    assert "slice_rehomed" in kinds
    for k in control:
        np.testing.assert_array_equal(killed[k], control[k], err_msg=k)


def test_rehome_at_a_thousand_clients_equals_the_flat_fold(tmp_path):
    """One of three aggregators dies with its third of a thousand
    clients' uplinks spooled and the reduce about to run: the slice
    re-homes once, from the spool, and the reduced model is the flat
    mean of all thousand, as the clean reduce before it was."""
    rng = np.random.default_rng(23)
    ids = [f"L{i:05d}" for i in range(1000)]
    models = {lid: {"w": rng.standard_normal(256).astype(np.float32)}
              for lid in ids}
    scales = {lid: 1.0 / len(ids) for lid in ids}
    flat = np.mean([m["w"] for m in models.values()], axis=0,
                   dtype=np.float64)
    servers, specs = _boot_servers(tmp_path, 3)
    red = _reducer(specs)
    try:
        red.assign(ids)
        for lid in ids:
            assert red.submit(lid, models[lid], 0)
        clean, partials, errors = red.reduce(ids, scales, stride=0,
                                             round_id=0)
        assert not errors and red.rehomed_total == 0
        assert sum(p.count for p in partials) == len(ids)
        servers[0].stop()
        rehomed, partials, _ = red.reduce(ids, scales, stride=0, round_id=1)
        assert red.rehomed_total == 1
        assert len(partials) == 3
        assert sum(p.count for p in partials) == len(ids)
    finally:
        _stop_all(servers, red)
    np.testing.assert_allclose(clean["w"], flat, atol=1e-6)
    np.testing.assert_array_equal(rehomed["w"], clean["w"])


def test_rehome_event_records_target_and_recovery(tmp_path):
    servers, specs = _boot_servers(tmp_path, 2)
    red = _reducer(specs)
    try:
        ids = ["LA", "LB"]
        red.assign(ids)
        for i, lid in enumerate(ids):
            red.submit(lid, _model(i), 0)
        servers[0].stop()
        out = red.reduce(ids, {lid: 1.0 for lid in ids}, round_id=3)
        assert out is not None
        record = next(e for e in reversed(_tevents.tail(0))
                      if e["kind"] == "slice_rehomed")
        assert record["slice"] == "slice_0"
        assert record["target"] == "slice_1"
        assert record["round"] == 3
        assert record["recovered"] >= 1
        desc = red.describe()
        row = next(r for r in desc["slices"] if r["name"] == "slice_0")
        assert row["dead"] and row["rehomed_to"] == "slice_1"
        assert desc["rehomed_total"] == 1
    finally:
        _stop_all(servers, red)


def test_all_aggregators_dead_degrades_to_root(tmp_path):
    """Every aggregator dead: the re-home chain dead-ends at the root,
    which folds each group from the recovered spools with the same
    kernels — the federation completes, nothing is lost."""
    servers, specs = _boot_servers(tmp_path, 3)
    red = _reducer(specs)
    ids = [f"L{i:02d}" for i in range(6)]
    models = {lid: _model(i) for i, lid in enumerate(ids)}
    scales = {lid: 1.0 for lid in ids}
    try:
        red.assign(ids)
        for lid in ids:
            red.submit(lid, models[lid], 0)
        for server in servers:
            server.stop()
        out = red.reduce(ids, scales, stride=0, round_id=0)
        assert out is not None
        community, partials, errors = out
        assert errors  # the degradation is reported, never silent
        tree = TreeReducer(branch=3)
        ref, _ = tree.reduce(sorted(ids), scales,
                             lambda b: {l: [models[l]] for l in b})
        tree.shutdown()
        for k in community:
            np.testing.assert_array_equal(community[k], ref[k], err_msg=k)
    finally:
        _stop_all(servers, red)


def test_submit_to_dead_fleet_parks_at_root(tmp_path):
    """An accepted uplink is never dropped: with the whole fleet down at
    submit time it lands in the root's residual buffer and folds there."""
    servers, specs = _boot_servers(tmp_path, 2)
    red = _reducer(specs, retries=1, backoff=0.01)
    try:
        for server in servers:
            server.stop()
        red.assign(["LA"])
        assert red.submit("LA", _model(1), 0) is False
        out = red.reduce(["LA"], {"LA": 1.0}, round_id=0)
        assert out is not None
        community = out[0]
        np.testing.assert_array_equal(
            community["enc/w"], _model(1)["enc/w"].astype(np.float32))
        assert red.describe()["root_residual"] == 1
        red.round_complete()
        assert red.describe()["root_residual"] == 0
    finally:
        _stop_all(servers, red)


def test_forget_reaches_slices_outside_current_assignment(tmp_path):
    """A learner that last reported in an EARLIER round is held by a
    slice the current owner map no longer names — leave() pruning must
    broadcast, or the model + spool record leak for the process life."""
    servers, specs = _boot_servers(tmp_path, 2)
    red = _reducer(specs)
    try:
        red.assign(["LA", "LB"])
        red.submit("LA", _model(1), 0)
        owner = red._base_owner("LA")
        # next round samples a cohort WITHOUT LA: the map forgets it
        red.assign(["LC", "LD"])
        assert red._base_owner("LA") == ROOT
        red.forget("LA")
        client = SliceClient(specs[owner]["host"], specs[owner]["port"])
        try:
            assert client.describe()["held"] == 0
        finally:
            client.close()
        assert not os.path.exists(
            spool_path(specs[owner]["spool_dir"], "LA"))
    finally:
        _stop_all(servers, red)


def test_assignment_ignores_liveness_for_group_boundaries(tmp_path):
    """assign() after a death partitions over the CONFIGURED branch (the
    dead slice's group just executes at its redirect target) — group
    boundaries never move, which is what the bit-identity pin rests on."""
    servers, specs = _boot_servers(tmp_path, 3)
    red = _reducer(specs)
    try:
        ids = [f"L{i:02d}" for i in range(9)]
        red.assign(ids)
        owners_before = [red._base_owner(lid) for lid in sorted(ids)]
        servers[1].stop()
        for i, lid in enumerate(ids):
            red.submit(lid, _model(i), 0)  # slice_1's group re-homes
        assert red.rehomed_total == 1
        red.assign(ids)  # next round's assignment, one aggregator dead
        assert [red._base_owner(lid) for lid in sorted(ids)] \
            == owners_before
        # the dead slice's base group executes at its redirect target
        assert red._resolve_executor(1) != 1
    finally:
        _stop_all(servers, red)


# --------------------------------------------------------------------- #
# controller integration
# --------------------------------------------------------------------- #

class _NullProxy:
    def __init__(self, record):
        self.learner_id = record.learner_id

    def run_task(self, task):
        pass

    def evaluate(self, task, callback):
        pass

    def shutdown(self):
        pass


def _config(tree=None, rule="fedavg"):
    cfg = FederationConfig(
        aggregation=AggregationConfig(rule=rule, scaler="participants"),
        train=TrainParams(batch_size=4, local_steps=1),
        eval=EvalConfig(every_n_rounds=0),
        telemetry=TelemetryConfig(enabled=False),
    )
    if tree is not None:
        cfg.aggregation.tree = tree
    return cfg


def _run_rounds(ctrl, rounds=2, n=8):
    seed = {"enc/w": np.zeros((6, 4), np.float32),
            "head/b": np.zeros((4,), np.float32)}
    ctrl.set_community_model(pack_model(seed))
    for i in range(n):
        ctrl.join(JoinRequest(hostname="h", port=7500 + i,
                              num_train_examples=10))
    lids = sorted(ctrl.active_learners())
    with ctrl._lock:
        tokens = {lid: ctrl._learners[lid].auth_token for lid in lids}
    for r in range(rounds):
        for i, lid in enumerate(lids):
            assert ctrl.task_completed(TaskResult(
                task_id=f"t{r}_{lid}", learner_id=lid,
                auth_token=tokens[lid], model=pack_model(_model(i, r)),
                round_id=r, completed_batches=1))
        deadline = time.time() + 30.0
        while ctrl.global_iteration <= r:
            assert time.time() < deadline, f"round {r} never completed"
            time.sleep(0.01)
    return {k: np.asarray(v).copy()
            for k, v in ctrl._community_flat.items()}


def test_controller_distributed_bit_identical_and_storeless(tmp_path):
    """End-to-end through the controller: the distributed tier produces
    the flat path's bits in the pinned config, and the root store never
    sees an uplink (the O(branch) memory claim)."""
    servers, specs = _boot_servers(tmp_path, 3)
    treed = Controller(_config(TreeAggregationConfig(
        enabled=True, branch=3, distributed=True, slices=specs,
        rehome_retries=2, rehome_backoff_s=0.02)),
        proxy_factory=_NullProxy)
    flat = Controller(_config(), proxy_factory=_NullProxy)
    try:
        assert treed._slices is not None
        got = _run_rounds(treed, rounds=2, n=8)
        assert treed._store.learner_ids() == []  # storeless root
        snap = treed.describe()
        assert snap["slices"]["alive"] == 3
        assert snap["slices"]["uplinks_total"] >= 8
        ref = _run_rounds(flat, rounds=2, n=8)
        for k in got:
            np.testing.assert_array_equal(got[k], ref[k], err_msg=k)
    finally:
        treed.shutdown()
        flat.shutdown()
        _stop_all(servers)


def test_controller_distributed_survives_mid_run_kill(tmp_path):
    """Controller-level re-homing: one aggregator dies between rounds'
    uplinks; both rounds complete and the bits match a flat controller."""
    servers, specs = _boot_servers(tmp_path, 3)
    treed = Controller(_config(TreeAggregationConfig(
        enabled=True, branch=3, distributed=True, slices=specs,
        rehome_retries=2, rehome_backoff_s=0.02)),
        proxy_factory=_NullProxy)
    flat = Controller(_config(), proxy_factory=_NullProxy)
    try:
        seed = {"enc/w": np.zeros((6, 4), np.float32),
                "head/b": np.zeros((4,), np.float32)}
        treed.set_community_model(pack_model(seed))
        for i in range(8):
            treed.join(JoinRequest(hostname="h", port=7600 + i,
                                   num_train_examples=10))
        lids = sorted(treed.active_learners())
        with treed._lock:
            tokens = {lid: treed._learners[lid].auth_token for lid in lids}
        for r in range(2):
            for i, lid in enumerate(lids):
                if r == 1 and i == 3:
                    servers[0].stop()  # dies with uplinks in flight
                assert treed.task_completed(TaskResult(
                    task_id=f"t{r}_{lid}", learner_id=lid,
                    auth_token=tokens[lid],
                    model=pack_model(_model(i, r)), round_id=r,
                    completed_batches=1))
            deadline = time.time() + 30.0
            while treed.global_iteration <= r:
                assert time.time() < deadline
                time.sleep(0.01)
        got = {k: np.asarray(v).copy()
               for k, v in treed._community_flat.items()}
        assert treed._slices.rehomed_total == 1
        ref = _run_rounds(flat, rounds=2, n=8)
        for k in got:
            np.testing.assert_array_equal(got[k], ref[k], err_msg=k)
    finally:
        treed.shutdown()
        flat.shutdown()
        _stop_all(servers)


def test_distributed_off_is_one_attribute_check():
    ctrl = Controller(_config(), proxy_factory=_NullProxy)
    try:
        assert ctrl._slices is None
    finally:
        ctrl.shutdown()


def test_distributed_unsupported_rule_falls_back(tmp_path, caplog):
    """Config load rejects the combination outright; a config object
    mutated past validation (programmatic misuse) still hits the
    controller's defensive gate: log once, keep the in-process path."""
    cfg = _config(rule="median")
    cfg.aggregation.tree = TreeAggregationConfig(
        enabled=True, branch=2, workers=0)
    # mutate past __post_init__ — the only route an invalid combination
    # can reach the controller by
    cfg.aggregation.tree.distributed = True
    cfg.aggregation.tree.slices = [
        {"name": "s0", "host": "127.0.0.1", "port": 1}]
    with caplog.at_level(logging.INFO, "metisfl_tpu.controller"):
        ctrl = Controller(cfg, proxy_factory=_NullProxy)
    try:
        assert ctrl._slices is None
        assert ctrl._tree is not None
        assert "cannot slice-fold" in caplog.text
    finally:
        ctrl.shutdown()


# --------------------------------------------------------------------- #
# config validation
# --------------------------------------------------------------------- #

def test_distributed_config_rejections():
    with pytest.raises(ValueError, match="tree.enabled"):
        FederationConfig(aggregation=AggregationConfig(
            tree=TreeAggregationConfig(distributed=True)))
    with pytest.raises(ValueError, match="streaming"):
        FederationConfig(aggregation=AggregationConfig(
            streaming=True,
            tree=TreeAggregationConfig(enabled=True, distributed=True)))
    # masking composes with the distributed tier (slices fold masked
    # partial sums); ciphertext schemes do not — the rejection names
    # the scheme that does
    FederationConfig(
        aggregation=AggregationConfig(
            rule="secure_agg", scaler="participants",
            tree=TreeAggregationConfig(enabled=True, distributed=True)),
        secure=SecureAggConfig(enabled=True, scheme="masking"))
    with pytest.raises(ValueError, match="secure.scheme: masking"):
        FederationConfig(
            aggregation=AggregationConfig(
                rule="secure_agg", scaler="participants",
                tree=TreeAggregationConfig(enabled=True, distributed=True)),
            secure=SecureAggConfig(enabled=True, scheme="ckks"))
    with pytest.raises(ValueError, match="ingest_workers"):
        from metisfl_tpu.config import ModelStoreConfig
        FederationConfig(
            aggregation=AggregationConfig(
                tree=TreeAggregationConfig(enabled=True, distributed=True)),
            model_store=ModelStoreConfig(ingest_workers=2))
    with pytest.raises(ValueError, match="rehome_backoff_s"):
        FederationConfig(aggregation=AggregationConfig(
            tree=TreeAggregationConfig(enabled=True, distributed=True,
                                       rehome_backoff_s=0.0)))
    with pytest.raises(ValueError, match="weighted-sum rule"):
        # a rule that cannot slice-fold would boot a fleet that never
        # receives a byte — rejected at load, not silently ignored
        FederationConfig(aggregation=AggregationConfig(
            rule="median",
            tree=TreeAggregationConfig(enabled=True, distributed=True)))


def test_template_documents_tree_distributed_defaults():
    import yaml

    with open(os.path.join(REPO, "examples", "config",
                           "template.yaml")) as fh:
        raw = yaml.safe_load(fh)
    block = raw["aggregation"]["tree"]
    default = TreeAggregationConfig()
    assert block["distributed"] == default.distributed
    assert block["slices"] == default.slices == []
    assert block["spool_dir"] == default.spool_dir
    assert block["rehome_retries"] == default.rehome_retries
    assert block["rehome_backoff_s"] == default.rehome_backoff_s


# --------------------------------------------------------------------- #
# TreeReducer error-path hardening (satellite)
# --------------------------------------------------------------------- #

def test_tree_worker_exception_propagates_without_wedging():
    """A worker raising mid-fold must propagate (the aggregation-failure
    retry path), with every sibling settled first — and the reducer must
    stay usable for the retry."""
    tree = TreeReducer(branch=4)
    ids = [f"L{i}" for i in range(8)]
    models = {lid: _model(i) for i, lid in enumerate(ids)}
    calls = {"n": 0}

    def bad_fetch(block):
        calls["n"] += 1
        if any(lid in ("L2", "L3") for lid in block):
            raise RuntimeError("store select failed")
        return {lid: [models[lid]] for lid in block}

    try:
        with pytest.raises(RuntimeError, match="store select failed"):
            tree.reduce(ids, {lid: 1.0 for lid in ids}, bad_fetch, stride=2)
        # pool survives the raise: the retry's clean fold works
        out = tree.reduce(ids, {lid: 1.0 for lid in ids},
                          lambda b: {l: [models[l]] for l in b}, stride=2)
        assert out is not None
        community, partials = out
        assert sum(p.count for p in partials) == 8
    finally:
        tree.shutdown()


def test_tree_close_is_idempotent_and_reusable():
    tree = TreeReducer(branch=2)
    models = {"LA": _model(1), "LB": _model(2)}
    fetch = lambda b: {l: [models[l]] for l in b}  # noqa: E731
    assert tree.reduce(["LA", "LB"], {"LA": 1.0, "LB": 1.0}, fetch)
    tree.close()
    tree.close()      # double-close: no raise, no leak
    tree.shutdown()   # alias spelling too
    # reusable after close: the pool re-creates lazily
    assert tree.reduce(["LA", "LB"], {"LA": 1.0, "LB": 1.0}, fetch)
    tree.close()


# --------------------------------------------------------------------- #
# status render
# --------------------------------------------------------------------- #

def test_status_renders_slices_line():
    from metisfl_tpu.status import render_snapshot

    snap = {
        "controller_epoch": "abc12345", "round": 4, "phase": "aggregate",
        "protocol": "synchronous", "aggregation_rule": "fedavg",
        "learners": [], "in_flight": [], "events": [], "time": 0.0,
        "store": {"models": {}, "total": 0},
        "slices": {
            "enabled": True, "alive": 2, "rehomed_total": 1,
            "root_residual": 0, "uplinks_total": 48,
            "slices": [
                {"name": "slice_0", "dead": True, "rehomed_to": "slice_1",
                 "failures": 2, "held": 0},
                {"name": "slice_1", "dead": False, "rehomed_to": "",
                 "failures": 0, "held": 16},
                {"name": "slice_2", "dead": False, "rehomed_to": "",
                 "failures": 0, "held": 8},
            ],
            "uplink_bytes": {"p50": 207.0, "p99": 207.0, "top": []},
        },
    }
    text = render_snapshot(snap)
    assert "slices: 2/3 up" in text
    assert "rehomed=1" in text
    assert "slice_0=DEAD→slice_1" in text
    assert "uplink_p50=207" in text


# --------------------------------------------------------------------- #
# acceptance: real subprocess aggregators, SIGKILL mid-round
# --------------------------------------------------------------------- #

def test_slice_kill_acceptance_smoke():
    """The ISSUE acceptance gate, in-process (scripts/chaos_smoke.sh runs
    the same thing from the CLI): 3 real aggregator subprocesses over
    gRPC, one SIGKILLed mid-round — the slice re-homes, every round
    completes without operator action, slice_rehomed fires only in the
    kill run, and the community model is bit-identical to the same-seed
    undisturbed control."""
    from metisfl_tpu.driver.crossdevice import run_slice_smoke

    out = run_slice_smoke(clients=12, rounds=2, slices=3, seed=7,
                          timeout_s=90.0)
    assert out["kill"]["slices"]["killed"]
    assert out["kill"]["slices"]["rehomed_total"] >= 1
    assert out["control"]["slices"]["rehomed_total"] == 0
    assert out["kill"]["rounds_completed"] == 2
    assert out["bit_identical"], (
        out["kill"]["slices"]["model_sha256"],
        out["control"]["slices"]["model_sha256"])
    assert out["ok"]


def test_driver_boots_and_shuts_down_slice_fleet(tmp_path):
    """DriverSession end-to-end: a real 2-learner federation with
    aggregation.tree.distributed — the driver fills the slice endpoints,
    boots the aggregator processes, the federation completes its rounds
    through them, and shutdown reaps the fleet."""
    from metisfl_tpu.config import TerminationConfig
    from metisfl_tpu.driver.session import DriverSession
    from metisfl_tpu.models import ArrayDataset, FlaxModelOps
    from metisfl_tpu.models.zoo import MLP

    import socket

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    rng = np.random.default_rng(5)
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
        controller_port=free_port(),
        round_deadline_secs=30.0,
        aggregation=AggregationConfig(
            scaler="participants",
            tree=TreeAggregationConfig(enabled=True, branch=2,
                                       distributed=True)),
        train=TrainParams(batch_size=8, local_steps=2, learning_rate=0.1),
        eval=EvalConfig(every_n_rounds=0),
        termination=TerminationConfig(federation_rounds=2),
    )
    session = DriverSession(config, template,
                            [make_recipe(0), make_recipe(1)],
                            workdir=str(tmp_path))
    try:
        session.initialize_federation()
        # the driver filled + booted the fleet
        assert len(config.aggregation.tree.slices) == 2
        slice_procs = [p for p in session._procs
                       if p.name.startswith("slice_")]
        assert len(slice_procs) == 2
        assert all(p.process.poll() is None for p in slice_procs)
        deadline = time.time() + 120
        while time.time() < deadline:
            if session.get_statistics()["global_iteration"] >= 2:
                break
            time.sleep(0.5)
        stats = session.get_statistics()
        assert stats["global_iteration"] >= 2, "rounds never completed"
    finally:
        session.shutdown_federation()
    assert all(p.process.poll() is not None for p in session._procs)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
