"""Streaming aggregation + tree-aggregation tier (docs/SCALE.md): the
bit-identity pins, the stale/malformed drop semantics, the eligibility
matrix, and the one-attribute-check opt-outs.

Bit-identity is pinned in the documented configurations: integer-valued
payloads (every partial sum exactly representable) and a power-of-two
cohort under the uniform ``participants`` scaler — the same accumulator
kernels then produce the same bits regardless of blocking. Real-valued /
non-power-of-two federations agree up to fp reassociation (~1 ulp),
asserted separately.
"""

import time

import numpy as np
import pytest

from metisfl_tpu.aggregation.fedavg import FedAvg
from metisfl_tpu.aggregation.rolling import FedStride
from metisfl_tpu.aggregation.streaming import (
    StreamingAggregator,
    streaming_supported,
)
from metisfl_tpu.aggregation.tree import TreeReducer
from metisfl_tpu.comm.messages import JoinRequest, TaskResult, TrainParams
from metisfl_tpu.config import (
    AggregationConfig,
    EvalConfig,
    FederationConfig,
    SecureAggConfig,
    TelemetryConfig,
)
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


def _config(rule="fedavg", streaming=False, ingest_workers=0,
            tree_branch=0, scaler="participants", protocol="synchronous"):
    cfg = FederationConfig(
        protocol=protocol,
        aggregation=AggregationConfig(rule=rule, scaler=scaler,
                                      streaming=streaming),
        train=TrainParams(batch_size=4, local_steps=1),
        eval=EvalConfig(every_n_rounds=0),
        telemetry=TelemetryConfig(enabled=False),
    )
    cfg.model_store.ingest_workers = ingest_workers
    if tree_branch:
        cfg.aggregation.tree.enabled = True
        cfg.aggregation.tree.branch = tree_branch
    return cfg


def _controller(**kwargs):
    return Controller(_config(**kwargs), proxy_factory=_NullProxy)


def _seed():
    return {"enc/w": np.zeros((6, 4), np.float32),
            "head/w": np.zeros((4,), np.float32)}


def _update(i, r, integer=True):
    rng = np.random.default_rng(1000 * r + i)
    if integer:
        return {"enc/w": rng.integers(-8, 8, (6, 4)).astype(np.float32),
                "head/w": rng.integers(-8, 8, 4).astype(np.float32)}
    return {"enc/w": rng.standard_normal((6, 4)).astype(np.float32),
            "head/w": rng.standard_normal(4).astype(np.float32)}


def _wait_round(ctrl, r, timeout=30.0):
    deadline = time.time() + timeout
    while ctrl.global_iteration <= r:
        assert time.time() < deadline, f"round {r} never completed"
        time.sleep(0.01)


def _join(ctrl, n):
    for i in range(n):
        ctrl.join(JoinRequest(hostname="h", port=7400 + i,
                              num_train_examples=10))
    lids = sorted(ctrl.active_learners())
    with ctrl._lock:
        tokens = {lid: ctrl._learners[lid].auth_token for lid in lids}
    return lids, tokens


def _submit(ctrl, lid, token, model_bytes, r, task_id=None):
    assert ctrl.task_completed(TaskResult(
        task_id=task_id or f"t{r}_{lid}", learner_id=lid, auth_token=token,
        model=model_bytes, round_id=r, completed_batches=1))


def _run_rounds(ctrl, rounds=2, n=4, integer=True, mutate_round=None):
    """Drive ``rounds`` direct-submit rounds; ``mutate_round(ctrl, r,
    lids, tokens)`` may inject its own submissions for a round and must
    return True to claim it."""
    ctrl.set_community_model(pack_model(_seed()))
    lids, tokens = _join(ctrl, n)
    for r in range(rounds):
        if mutate_round is None or not mutate_round(ctrl, r, lids, tokens):
            for i, lid in enumerate(lids):
                _submit(ctrl, lid, tokens[lid],
                        pack_model(_update(i, r, integer)), r)
        _wait_round(ctrl, r)
    return {k: np.asarray(v).copy()
            for k, v in ctrl._community_flat.items()}


def _communities_equal(a, b, *, exact=True):
    assert sorted(a) == sorted(b)
    for k in a:
        if exact:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)
        else:
            np.testing.assert_allclose(a[k], b[k], rtol=1e-5, atol=1e-6,
                                       err_msg=k)


# --------------------------------------------------------------------- #
# eligibility matrix
# --------------------------------------------------------------------- #

def test_streaming_supported_matrix():
    ok = dict(protocol="synchronous", secure_enabled=False,
              store_lineage_length=1, required_lineage=1)
    assert streaming_supported("fedavg", **ok)
    assert streaming_supported("fedstride", **ok)
    assert streaming_supported(
        "fedrec", "asynchronous", False, 2, 2)
    # full-cohort / stateful rules need the store
    for rule in ("median", "krum", "fednova", "fedadam", "scaffold"):
        assert not streaming_supported(rule, **ok)
    # opaque payloads cannot fold on arrival
    assert not streaming_supported("fedavg", "synchronous", True, 1, 1)
    # operator keeps MORE lineage than the rule needs → store is load-bearing
    assert not streaming_supported("fedavg", "synchronous", False, 3, 1)
    # round-scoped sums cannot serve the async all-active selector
    assert not streaming_supported("fedavg", "asynchronous", False, 1, 1)
    assert not streaming_supported("fedstride", "asynchronous", False, 1, 1)
    # fedrec + checkpointing: crash-restore rehydrates the rolling sum
    # FROM store lineage, so the store must be written
    assert not streaming_supported("fedrec", "asynchronous", False, 2, 2,
                                   checkpointed=True)
    assert streaming_supported("fedavg", "synchronous", False, 1, 1,
                               checkpointed=True)  # round-scoped: safe


def test_fedrec_streaming_disabled_under_checkpointing(tmp_path):
    """A checkpointed fedrec federation silently falls back to the store
    path: --resume rebuilds the cross-round rolling sum from store
    lineage, which a zero-store streaming round path would leave empty."""
    from metisfl_tpu.config import CheckpointConfig

    cfg = _config(rule="fedrec", streaming=True)
    cfg.checkpoint = CheckpointConfig(dir=str(tmp_path / "ckpt"),
                                      every_n_rounds=1)
    ctrl = Controller(cfg, proxy_factory=_NullProxy)
    try:
        assert ctrl._streaming is None
    finally:
        ctrl.shutdown()


def test_streaming_composes_with_masking_but_not_ckks():
    # masking folds on arrival as modular sums — streaming composes
    FederationConfig(
        aggregation=AggregationConfig(rule="secure_agg", streaming=True,
                                      scaler="participants"),
        secure=SecureAggConfig(enabled=True, scheme="masking",
                               num_parties=3))
    # ciphertext schemes cannot stream-fold; the rejection names the
    # scheme that can
    with pytest.raises(ValueError, match="secure.scheme: masking"):
        FederationConfig(
            aggregation=AggregationConfig(rule="secure_agg", streaming=True,
                                          scaler="participants"),
            secure=SecureAggConfig(enabled=True, scheme="ckks"))


def test_tree_branch_validation():
    from metisfl_tpu.config import TreeAggregationConfig

    with pytest.raises(ValueError, match="branch"):
        FederationConfig(aggregation=AggregationConfig(
            tree=TreeAggregationConfig(enabled=True, branch=1)))
    with pytest.raises(ValueError):
        TreeReducer(branch=1)


# --------------------------------------------------------------------- #
# opt-out pins: every hot path is one attribute check
# --------------------------------------------------------------------- #

def test_default_config_builds_no_scale_plane():
    """``ingest_workers: 0`` + ``streaming: false`` + ``tree.enabled:
    false`` (the defaults) leave all three hooks None — each hot-path
    branch is a single ``is not None`` attribute check."""
    ctrl = _controller()
    try:
        assert ctrl._ingest is None
        assert ctrl._streaming is None
        assert ctrl._tree is None
        snap = ctrl.describe()
        assert "ingest" not in snap and "streaming" not in snap
    finally:
        ctrl.shutdown()


def test_unsupported_rule_falls_back_to_store_path():
    """streaming requested for a full-cohort rule quietly uses the store
    path (the documented automatic fallback)."""
    ctrl = _controller(rule="median", streaming=True)
    try:
        assert ctrl._streaming is None
    finally:
        ctrl.shutdown()


def test_scale_plane_surfaces_in_describe():
    ctrl = _controller(streaming=True, ingest_workers=2)
    try:
        assert ctrl._streaming is not None and ctrl._ingest is not None
        snap = ctrl.describe()
        assert snap["ingest"]["workers"] == 2
        assert snap["ingest"]["queue_depth"] == 0
        assert snap["streaming"]["rule"] == "fedavg"
    finally:
        ctrl.shutdown()


# --------------------------------------------------------------------- #
# seeded bit-identity: streaming-fold & parallel ingest vs the store path
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("rule", ["fedavg", "fedstride", "fedrec"])
def test_streaming_bit_identical_to_store_path(rule):
    base = _controller(rule=rule)
    try:
        want = _run_rounds(base, rounds=2)
    finally:
        base.shutdown()
    stream = _controller(rule=rule, streaming=True)
    try:
        got = _run_rounds(stream, rounds=2)
        assert stream._streaming is not None  # the path actually ran
        assert stream._streaming.stats()["fold_count"] == 8
    finally:
        stream.shutdown()
    _communities_equal(want, got, exact=True)


@pytest.mark.parametrize("rule", ["fedavg", "fedstride", "fedrec"])
def test_parallel_ingest_bit_identical_to_sync_insert(rule):
    base = _controller(rule=rule)
    try:
        want = _run_rounds(base, rounds=2)
    finally:
        base.shutdown()
    par = _controller(rule=rule, ingest_workers=4)
    try:
        assert par._ingest is not None
        got = _run_rounds(par, rounds=2)
    finally:
        par.shutdown()
    _communities_equal(want, got, exact=True)


def test_streaming_weighted_real_valued_allclose():
    """Outside the pinned configurations (real payloads, non-uniform
    train_dataset_size weights, non-power-of-two cohort) the raw-weight
    z-division agrees with the normalized store path to fp tolerance."""
    def run(streaming):
        cfg = _config(rule="fedavg", streaming=streaming,
                      scaler="train_dataset_size")
        ctrl = Controller(cfg, proxy_factory=_NullProxy)
        try:
            ctrl.set_community_model(pack_model(_seed()))
            for i in range(5):
                ctrl.join(JoinRequest(hostname="h", port=7500 + i,
                                      num_train_examples=10 * (i + 1)))
            lids = sorted(ctrl.active_learners())
            with ctrl._lock:
                tokens = {l: ctrl._learners[l].auth_token for l in lids}
            for i, lid in enumerate(lids):
                _submit(ctrl, lid, tokens[lid],
                        pack_model(_update(i, 0, integer=False)), 0)
            _wait_round(ctrl, 0)
            return {k: np.asarray(v).copy()
                    for k, v in ctrl._community_flat.items()}
        finally:
            ctrl.shutdown()

    _communities_equal(run(False), run(True), exact=False)


# --------------------------------------------------------------------- #
# mid-round degradations: stale uplink, malformed payload
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("rule", ["fedavg", "fedstride", "fedrec"])
def test_stale_then_fresh_uplink_equivalence(rule):
    """A straggler's completion for an EXPIRED task arrives mid-round,
    followed by its fresh re-dispatched result. Round-scoped streaming
    drops the stale model (no store lineage to park it in) and folds the
    fresh one; the store path parks then overwrites it — same community
    bit-for-bit. (fedrec folds the stale model too — recency semantics —
    and the fresh fold replaces it, converging identically.)"""
    def mutate(ctrl, r, lids, tokens):
        if r != 1:
            return False
        straggler = lids[0]
        stale_tid = f"expired_{straggler}"
        with ctrl._lock:
            ctrl._expired_tasks[stale_tid] = time.time()
        # stale-first ordering: the expired task's late completion lands
        # BEFORE the re-dispatched fresh one (the store path's
        # latest-wins lineage then matches streaming's drop+fold)
        _submit(ctrl, straggler, tokens[straggler],
                pack_model(_update(77, 0)), 0, task_id=stale_tid)
        for i, lid in enumerate(lids):
            _submit(ctrl, lid, tokens[lid], pack_model(_update(i, r)), r)
        return True

    base = _controller(rule=rule)
    try:
        want = _run_rounds(base, rounds=2, mutate_round=mutate)
    finally:
        base.shutdown()
    stream = _controller(rule=rule, streaming=True)
    try:
        got = _run_rounds(stream, rounds=2, mutate_round=mutate)
    finally:
        stream.shutdown()
    _communities_equal(want, got, exact=True)


@pytest.mark.parametrize("rule", ["fedavg", "fedstride", "fedrec"])
def test_malformed_payload_drop_equivalence(rule):
    """One cohort member ships codec garbage in the FIRST round (so
    neither path has prior lineage for it): both paths drop exactly that
    contribution, the barrier still releases, and the communities stay
    bit-identical through a second, clean round."""
    def mutate(ctrl, r, lids, tokens):
        if r != 0:
            return False
        for i, lid in enumerate(lids):
            payload = (b"\xde\xad\xbe\xef-not-a-blob" if i == 1
                       else pack_model(_update(i, r)))
            _submit(ctrl, lid, tokens[lid], payload, r)
        return True

    base = _controller(rule=rule)
    try:
        want = _run_rounds(base, rounds=2, mutate_round=mutate)
    finally:
        base.shutdown()
    stream = _controller(rule=rule, streaming=True)
    try:
        got = _run_rounds(stream, rounds=2, mutate_round=mutate)
    finally:
        stream.shutdown()
    _communities_equal(want, got, exact=True)


# --------------------------------------------------------------------- #
# rolling-rule streaming kernels
# --------------------------------------------------------------------- #

def test_rolling_fold_replace_and_forget():
    rule = FedStride()
    rule.reset()
    a = {"w": np.full(4, 2.0, np.float32)}
    b = {"w": np.full(4, 6.0, np.float32)}
    rule.fold("A", a, 1.0)
    rule.fold("B", b, 1.0)
    np.testing.assert_array_equal(rule.fold_result()["w"], np.full(4, 4.0))
    # re-submission replaces (recency), not double-counts
    rule.fold("A", {"w": np.full(4, 4.0, np.float32)}, 1.0)
    np.testing.assert_array_equal(rule.fold_result()["w"], np.full(4, 5.0))
    assert rule.contributors() == {"A", "B"}
    rule.forget("B")
    np.testing.assert_array_equal(rule.fold_result()["w"], np.full(4, 4.0))
    rule.forget("A")
    with pytest.raises(ValueError):
        rule.fold_result()


def test_streaming_fedavg_keeps_departed_fold_and_completes():
    """A fold outside the released cohort can only come from a learner
    that uplinked then LEFT mid-round. The stacked sum cannot subtract
    it, so finish() keeps the accepted contribution and COMPLETES the
    round (warning logged) — aborting would march churny federations
    into the aggregation-failure halt. Documented divergence from the
    store path, which erases the departed lineage (docs/SCALE.md)."""
    agg = StreamingAggregator(FedAvg(), stride=0)
    agg.fold("A", {"w": np.full(2, 1.0, np.float32)}, 1.0)
    agg.fold("B", {"w": np.full(2, 3.0, np.float32)}, 1.0)
    community = agg.finish(["A"])  # B left after uplinking
    np.testing.assert_array_equal(community["w"], np.full(2, 2.0))
    # round state was reset: a fresh round starts clean
    agg.fold("A", {"w": np.full(2, 5.0, np.float32)}, 1.0)
    np.testing.assert_array_equal(agg.finish(["A"])["w"], np.full(2, 5.0))


def test_streaming_round_survives_mid_round_leave():
    """Controller-level: with streaming on, a learner that uplinks and
    then leaves mid-round must not abort the round — the barrier
    releases with the survivors and a community model lands."""
    ctrl = _controller(rule="fedavg", streaming=True)
    try:
        ctrl.set_community_model(pack_model(_seed()))
        lids, tokens = _join(ctrl, 4)
        leaver = lids[0]
        _submit(ctrl, leaver, tokens[leaver], pack_model(_update(0, 0)), 0)
        assert ctrl.leave(leaver, tokens[leaver])
        for i, lid in enumerate(lids[1:], start=1):
            _submit(ctrl, lid, tokens[lid], pack_model(_update(i, 0)), 0)
        _wait_round(ctrl, 0)
        assert ctrl._community_flat  # a model landed, no agg-failure halt
        assert ctrl._agg_failures == 0
    finally:
        ctrl.shutdown()


def test_raw_weight_zero_quantity_matches_store_scaler():
    """A learner reporting a zero quantity gets raw weight 0 — the batch
    scalers give it scale 0 whenever the cohort total is positive, so the
    streaming fold skips it instead of silently granting uniform weight."""
    from metisfl_tpu.scaling import raw_weight

    assert raw_weight("batches", {"completed_batches": 0}) == 0.0
    assert raw_weight("batches", {"completed_batches": 3}) == 3.0
    assert raw_weight("train_dataset_size", {}) == 0.0
    assert raw_weight("participants", {}) == 1.0
    with pytest.raises(ValueError):
        raw_weight("nope", {})


# --------------------------------------------------------------------- #
# tree tier
# --------------------------------------------------------------------- #

def _flat_fold(models, weights, stride=16):
    agg = FedAvg()
    agg.reset()
    ids = sorted(models)
    for i in range(0, len(ids), stride):
        block = ids[i:i + stride]
        agg.accumulate([([models[lid]], weights[lid]) for lid in block])
    return agg.result()


@pytest.mark.parametrize("branch", [2, 8, 32])
def test_tree_reduce_bit_identical_to_flat_fold(branch):
    """The satellite pin: tree-reduce == flat-fold at branch ∈ {2, 8, 32}
    on integer-valued payloads (exactly representable partial sums, so
    any reassociation yields the same bits)."""
    rng = np.random.default_rng(branch)
    ids = [f"L{i:03d}" for i in range(64)]
    models = {lid: {"enc/w": rng.integers(-16, 16, (8, 4)
                                          ).astype(np.float32),
                    "head/b": rng.integers(-16, 16, 4).astype(np.float32)}
              for lid in ids}
    weights = {lid: 1.0 for lid in ids}
    want = _flat_fold(models, weights)
    tree = TreeReducer(branch=branch)
    try:
        fetched_blocks = []

        def fetch(block):
            fetched_blocks.append(len(block))
            return {lid: [models[lid]] for lid in block}

        community, partials = tree.reduce(ids, weights, fetch, stride=16)
        assert sum(p.count for p in partials) == 64
        assert len(partials) == min(branch, 64)
        assert max(fetched_blocks) <= 16  # residency bounded by stride
        _communities_equal(want, community, exact=True)
    finally:
        tree.shutdown()


def test_tree_reduce_skips_absent_learners_and_empty_cohort():
    tree = TreeReducer(branch=4)
    try:
        assert tree.reduce([], {}, lambda b: {}) is None
        assert tree.reduce(["A", "B"], {"A": 1.0, "B": 1.0},
                           lambda b: {}) is None
        only_a = {"A": [{"w": np.full(2, 5.0, np.float32)}]}
        community, partials = tree.reduce(
            ["A", "B"], {"A": 1.0, "B": 1.0},
            lambda b: {lid: only_a[lid] for lid in b if lid in only_a})
        np.testing.assert_array_equal(community["w"], np.full(2, 5.0))
        assert sum(p.count for p in partials) == 1
    finally:
        tree.shutdown()


def test_tree_default_subblock_bounds_residency():
    """stride_length=0 must NOT stack a whole slice: the tier applies its
    own bounded sub-block."""
    from metisfl_tpu.aggregation.tree import _DEFAULT_SUBBLOCK

    tree = TreeReducer(branch=2)
    try:
        ids = [f"L{i}" for i in range(_DEFAULT_SUBBLOCK * 3)]
        sizes = []

        def fetch(block):
            sizes.append(len(block))
            return {lid: [{"w": np.ones(2, np.float32)}] for lid in block}

        community, _ = tree.reduce(ids, {lid: 1.0 for lid in ids}, fetch,
                                   stride=0)
        assert max(sizes) <= _DEFAULT_SUBBLOCK
        np.testing.assert_array_equal(community["w"], np.ones(2))
    finally:
        tree.shutdown()


@pytest.mark.parametrize("rule,branch", [("fedavg", 2), ("fedavg", 8),
                                         ("fedstride", 2), ("fedstride", 8)])
def test_controller_tree_tier_bit_identical(rule, branch):
    """End-to-end: the tree tier wired through the controller produces the
    same community bits as the flat store path (8-learner cohort so every
    branch width actually splits)."""
    base = _controller(rule=rule)
    try:
        want = _run_rounds(base, rounds=2, n=8)
    finally:
        base.shutdown()
    treed = _controller(rule=rule, tree_branch=branch)
    try:
        assert treed._tree is not None
        got = _run_rounds(treed, rounds=2, n=8)
    finally:
        treed.shutdown()
    _communities_equal(want, got, exact=True)


def test_tree_tier_ignored_for_full_cohort_rules():
    """A robust rule with the tree tier enabled must take the
    full-cohort path (a median cannot fold slice-wise)."""
    ctrl = _controller(rule="median", tree_branch=4)
    try:
        assert ctrl._tree is not None  # built, but the dispatch skips it
        got = _run_rounds(ctrl, rounds=1, n=4)
        assert got  # the round completed through the robust path
    finally:
        ctrl.shutdown()


# --------------------------------------------------------------------- #
# soak scale (tier-2)
# --------------------------------------------------------------------- #

@pytest.mark.slow
def test_streaming_1024_learner_round_completes():
    """Soak: a 1024-learner direct-submit round through the streaming +
    parallel-ingest plane completes and produces the exact cohort mean."""
    ctrl = _controller(streaming=True, ingest_workers=4)
    try:
        ctrl.set_community_model(pack_model({"w": np.zeros(64, np.float32)}))
        lids, tokens = _join(ctrl, 1024)
        for i, lid in enumerate(lids):
            _submit(ctrl, lid, tokens[lid],
                    pack_model({"w": np.full(64, np.float32(i % 32))}), 0)
        _wait_round(ctrl, 0, timeout=180.0)
        want = float(np.mean([i % 32 for i in range(1024)]))
        np.testing.assert_allclose(
            np.asarray(ctrl._community_flat["w"]),
            np.full(64, want, np.float32), rtol=1e-6)
    finally:
        ctrl.shutdown()
