"""Learning-health plane (ISSUE 4): per-update statistics, divergence
scores, anomaly analytics, and every surface they flow into.

Units for telemetry/health.py (statistics, robust z, EWMA, state
round-trip); protocol-level tests drive a bare :class:`Controller` over
no-op proxies with crafted uplinks (one poisoned learner among three) and
assert the score separation, the ``UpdateAnomalous``/``RoundHealth``
events, gauge export + churn pruning, checkpoint persistence, advisory
inertness, and bit-identical aggregates with the plane on or off; the
integration tests run a real in-process federation with a deliberately
diverging learner and a gRPC ``DescribeFederation`` + ``status --once``
round trip rendering the health fields.
"""

import numpy as np
import pytest

from metisfl_tpu import telemetry
from metisfl_tpu.comm.messages import JoinRequest, TaskResult, TrainParams
from metisfl_tpu.config import (
    AggregationConfig,
    CheckpointConfig,
    EvalConfig,
    FederationConfig,
    HealthConfig,
    TelemetryConfig,
    TerminationConfig,
)
from metisfl_tpu.controller.core import Controller
from metisfl_tpu.telemetry import events as tevents
from metisfl_tpu.telemetry import metrics as tmetrics
from metisfl_tpu.telemetry.health import (
    HealthMonitor,
    cosine,
    layer_key,
    participation_entropy,
    robust_z,
)
from metisfl_tpu.tensor.pytree import pack_model


@pytest.fixture()
def clean_telemetry():
    tevents.configure(enabled=True, service="test", dir="", ring_size=512)
    tevents.journal().reset()
    tmetrics.set_enabled(True)
    yield
    tevents.configure(enabled=True, service="test", dir="", ring_size=512)
    tevents.journal().reset()


# --------------------------------------------------------------------- #
# statistics units
# --------------------------------------------------------------------- #


def test_update_statistics_norms_layers_and_cosines():
    monitor = HealthMonitor()
    reference = {"enc/w": np.zeros((2, 2), np.float32),
                 "enc/b": np.zeros((2,), np.float32),
                 "head/w": np.zeros((2,), np.float32)}
    model = {"enc/w": np.full((2, 2), 2.0, np.float32),
             "enc/b": np.zeros((2,), np.float32),
             "head/w": np.full((2,), 3.0, np.float32)}
    summary = monitor.observe_update("L0", model, reference,
                                     train_metrics={"loss": 0.7})
    # ‖u‖ = sqrt(4·4 + 2·9)
    assert summary["update_norm"] == pytest.approx(np.sqrt(16 + 18), rel=1e-5)
    assert summary["layer_norms"]["enc/w"] == pytest.approx(4.0, rel=1e-5)
    assert summary["layer_norms"]["head/w"] == pytest.approx(
        np.sqrt(18), rel=1e-5)
    assert "enc/b" in summary["layer_norms"]  # zero update still attributed
    assert summary["cos_prev_delta"] == 0.0   # no previous community delta
    assert summary["train_metrics"] == {"loss": 0.7}

    assert layer_key("params/Dense_0/kernel") == "params/Dense_0"
    assert layer_key("w") == "w"
    assert cosine(np.ones(3, np.float32), np.ones(3, np.float32)) == \
        pytest.approx(1.0)
    assert cosine(np.zeros(3, np.float32), np.ones(3, np.float32)) == 0.0
    assert cosine(np.ones(3, np.float32), np.ones(4, np.float32)) == 0.0


def test_robust_z_separates_the_outlier_without_inflating_the_yardstick():
    # two benign deviations + one huge: the outlier cannot inflate the
    # median/MAD denominator it is scored against
    z = robust_z({"a": 1.0, "b": 1.1, "c": 50.0})
    assert z["c"] > 10.0
    assert abs(z["a"]) < 2.0 and abs(z["b"]) < 2.0
    # degenerate cohorts score 0 — nothing to diverge from; at n=2 the
    # deviations from the cohort mean are equal by symmetry, so
    # divergence is unattributable and scoring needs >= 3 participants
    assert robust_z({"solo": 9.0}) == {"solo": 0.0}
    assert robust_z({"a": 1.0, "b": 500.0}) == {"a": 0.0, "b": 0.0}
    assert robust_z({}) == {}
    same = robust_z({"a": 2.0, "b": 2.0, "c": 2.0})
    assert all(v == 0.0 for v in same.values())


def test_participation_entropy_bounds():
    assert participation_entropy({"a": 0.5, "b": 0.5}) == pytest.approx(1.0)
    skewed = participation_entropy({"a": 0.999, "b": 0.001})
    assert 0.0 < skewed < 0.1
    assert participation_entropy({}) == 0.0
    assert participation_entropy({"a": 1.0}) == 1.0


def test_monitor_round_fold_scores_and_state_roundtrip():
    monitor = HealthMonitor(alpha=0.5, anomaly_threshold=3.0)
    ref = {"w": np.zeros((8,), np.float32)}
    monitor.note_community(ref)
    rng = np.random.default_rng(0)
    for lid, scale in (("L0", 0.1), ("L1", 0.1), ("L2", 30.0)):
        model = {"w": (scale * (1.0 + 0.01 * rng.standard_normal(8))
                       ).astype(np.float32)}
        monitor.observe_update(lid, model, ref, train_metrics={"loss": 1.0})
    health, anomalies = monitor.complete_round(
        0, {"w": np.full((8,), 0.5, np.float32)},
        {"L0": 1 / 3, "L1": 1 / 3, "L2": 1 / 3})
    scores = monitor.scores()
    assert scores["L2"] >= 3.0 > max(scores["L0"], scores["L1"])
    assert [a["learner_id"] for a in anomalies] == ["L2"]
    assert health["anomalous"] == ["L2"]
    assert health["round_update_norm"] > 0
    assert health["cohort_loss"]["p50"] == pytest.approx(1.0)
    # update vectors are released at the fold (bounded memory)
    assert not monitor._pending

    # state round-trips through a fresh monitor (checkpoint path)
    restored = HealthMonitor()
    restored.restore_state(monitor.export_state())
    assert restored.scores() == pytest.approx(scores)
    assert restored.snapshot()["anomalous"] == ["L2"]

    # a recovered learner's EWMA decays instead of sticking
    for lid, scale in (("L0", 0.1), ("L1", 0.1), ("L2", 0.1)):
        monitor.observe_update(
            lid, {"w": np.full((8,), scale, np.float32)}, ref)
    monitor.complete_round(1, {"w": np.full((8,), 0.6, np.float32)},
                           {"L0": 1 / 3, "L1": 1 / 3, "L2": 1 / 3})
    assert monitor.scores()["L2"] < scores["L2"]


def test_nonfinite_losses_and_zero_seed_do_not_poison_the_snapshot():
    """One zero-step learner shipping loss=NaN must not NaN the whole
    cohort's loss quantiles, and a zero-seeded community model (zero
    reference norm) reports effective_step 0.0, not a ~1e12 blowup."""
    monitor = HealthMonitor()
    zeros = {"w": np.zeros((4,), np.float32)}
    monitor.note_community(zeros)
    monitor.observe_update("L0", {"w": np.full((4,), 0.2, np.float32)},
                           zeros, train_metrics={"loss": 0.5})
    monitor.observe_update("L1", {"w": np.full((4,), 0.3, np.float32)},
                           zeros, train_metrics={"loss": float("nan")})
    health, _ = monitor.complete_round(
        0, {"w": np.full((4,), 0.25, np.float32)}, {"L0": 0.5, "L1": 0.5})
    assert health["cohort_loss"] == {"min": 0.5, "p50": 0.5, "max": 0.5}
    assert health["effective_step"] == 0.0  # zero-norm reference
    # with a nonzero reference the ratio is defined again
    health2, _ = monitor.complete_round(
        1, {"w": np.full((4,), 0.5, np.float32)}, {"L0": 1.0})
    assert health2["effective_step"] == pytest.approx(1.0)


def test_nan_weight_uplink_is_flagged_not_cohort_poisoning():
    """An uplink with NaN/Inf weights (exploding gradients — the most
    diverged update possible) must fire the anomaly itself instead of
    NaN-ing every learner's score, and every snapshot value must stay
    finite (strict-JSON serializable)."""
    import json

    monitor = HealthMonitor(anomaly_threshold=3.0)
    ref = {"w": np.zeros((4,), np.float32)}
    monitor.note_community(ref)
    monitor.observe_update("ok1", {"w": np.full((4,), 0.1, np.float32)}, ref,
                           train_metrics={"loss": 0.4})
    monitor.observe_update("ok2", {"w": np.full((4,), 0.2, np.float32)}, ref,
                           train_metrics={"loss": 0.6})
    monitor.observe_update(
        "bad", {"w": np.array([np.nan, np.inf, 0, 0], np.float32)}, ref,
        train_metrics={"loss": float("nan")})
    health, anomalies = monitor.complete_round(
        0, {"w": np.full((4,), 0.1, np.float32)},
        {"ok1": 1 / 3, "ok2": 1 / 3, "bad": 1 / 3})
    assert [a["learner_id"] for a in anomalies] == ["bad"]
    assert health["divergence_raw"]["bad"] == pytest.approx(30.0)
    # the finite cohort still gets real (finite, small) scores
    for lid in ("ok1", "ok2"):
        assert np.isfinite(health["divergence_raw"][lid])
        assert health["divergence_score"][lid] < 3.0
    # the finite cohort losses still fold; the NaN one is excluded
    assert health["cohort_loss"] == {"min": 0.4, "p50": 0.5, "max": 0.6}
    # strict JSON round-trips: no NaN/Infinity tokens anywhere — the
    # NaN loss never entered the summaries or the checkpointable state
    json.loads(json.dumps(health, allow_nan=False))
    json.loads(json.dumps(monitor.last_stats(), allow_nan=False))
    json.loads(json.dumps(monitor.export_state(), allow_nan=False))


def test_sketch_bounds_buffer_memory_and_still_separates(monkeypatch):
    """Updates wider than _SKETCH_DIM buffer as a seeded coordinate
    subsample — O(cohort x SKETCH_DIM) memory, not O(cohort x params) —
    while exact norms and the outlier separation survive."""
    from metisfl_tpu.telemetry import health as health_mod

    monkeypatch.setattr(health_mod, "_SKETCH_DIM", 16)
    monitor = HealthMonitor()
    d = 512
    ref = {"w": np.zeros((d,), np.float32)}
    monitor.note_community(ref)
    rng = np.random.default_rng(5)
    for lid, scale in (("L0", 0.1), ("L1", 0.1), ("L2", 40.0)):
        model = {"w": (scale * (1.0 + 0.05 * rng.standard_normal(d))
                       ).astype(np.float32)}
        summary = monitor.observe_update(lid, model, ref)
        # the reported norm is EXACT (computed before sketching)...
        assert summary["update_norm"] == pytest.approx(
            float(np.linalg.norm(model["w"])), rel=1e-5)
        # ...but the buffered vector is the bounded sketch
        assert monitor._pending[lid][0].size == 16
    health, anomalies = monitor.complete_round(
        0, {"w": np.full((d,), 0.2, np.float32)},
        {lid: 1 / 3 for lid in ("L0", "L1", "L2")})
    assert [a["learner_id"] for a in anomalies] == ["L2"]
    assert monitor.scores()["L2"] >= 3.0 > monitor.scores()["L0"]
    # the next round's cos_prev_delta compares in the same sketched
    # subspace instead of silently zeroing on a shape mismatch
    s = monitor.observe_update(
        "L0", {"w": np.full((d,), 0.3, np.float32)}, ref)
    assert abs(s["cos_prev_delta"]) > 0.0


def test_fold_at_model_width_scores_every_learner_and_the_outlier():
    """The plane's O(params) pass at the width of a real model (the
    twelve-tensor CNN, 1.41M parameters, 86 times the sketch): exact
    norms, a buffer of the sketch's size, every learner scored in each of
    two rounds, and the learner that moved forty times as far flagged."""
    from metisfl_tpu.telemetry import health as health_mod
    from tests.test_aggregation import CNN_SHAPES, cnn_models

    reference = cnn_models(1, seed=10)[0]
    assert sum(a.size for a in reference.values()) > 1_000_000
    steps = cnn_models(8, seed=9)
    ids = [f"learner_{i}" for i in range(len(steps))]
    monitor = HealthMonitor()
    monitor.note_community(reference)
    for r in range(2):
        for i, (lid, step) in enumerate(zip(ids, steps)):
            scale = np.float32(0.4 if i == 5 else 0.01)
            model = {n: reference[n] + scale * step[n] for n in reference}
            summary = monitor.observe_update(
                lid, model, reference, train_metrics={"loss": 1.0 - 0.1 * r})
            exact = np.sqrt(sum(
                float(np.sum(np.square(model[n] - reference[n],
                                       dtype=np.float64)))
                for n in reference))
            assert summary["update_norm"] == pytest.approx(exact, rel=1e-4)
            assert set(summary["layer_norms"]) == {
                layer_key(n) for n in CNN_SHAPES}
            assert monitor._pending[lid][0].size == health_mod._SKETCH_DIM
        health, anomalies = monitor.complete_round(
            r, reference, {lid: 1.0 / len(ids) for lid in ids})
        assert sorted(health["divergence_score"]) == ids
        assert [a["learner_id"] for a in anomalies] == ["learner_5"]
        assert not monitor._pending


def test_off_width_update_is_unscored_not_falsely_anomalous(monkeypatch):
    """A different-width update (partial tensor set: version skew,
    malformed uplink) sketches to the SAME shape as the cohort but
    samples different coordinates — it must be excluded from the
    cohort fold by its pre-sketch width, not fire a subspace-noise
    anomaly or pollute the others' scores."""
    from metisfl_tpu.telemetry import health as health_mod

    monkeypatch.setattr(health_mod, "_SKETCH_DIM", 16)
    monitor = HealthMonitor()
    d = 256
    ref = {"w": np.zeros((d,), np.float32),
           "extra": np.zeros((64,), np.float32)}
    rng = np.random.default_rng(7)
    for lid in ("L0", "L1", "L2"):
        model = {"w": (0.1 * (1.0 + 0.05 * rng.standard_normal(d))
                       ).astype(np.float32),
                 "extra": np.zeros((64,), np.float32)}
        monitor.observe_update(lid, model, ref)
    # L3 ships only "w" — a narrower tensor set, different pre-sketch
    # width, same sketched shape
    monitor.observe_update(
        "L3", {"w": (0.1 * np.ones(d)).astype(np.float32)}, ref)
    assert monitor._pending["L3"][0].size == 16  # sketched alike
    health, anomalies = monitor.complete_round(
        0, ref, {lid: 0.25 for lid in ("L0", "L1", "L2", "L3")})
    assert anomalies == []               # no subspace-noise anomaly
    assert "L3" not in health["divergence_raw"]  # unscored, not flagged
    assert set(health["divergence_raw"]) == {"L0", "L1", "L2"}


def test_pending_buffer_eviction_is_surfaced(monkeypatch):
    """Overflowing the pending buffer must be visible in the round
    snapshot — silent truncation would read as 'everyone scored'."""
    from metisfl_tpu.telemetry import health as health_mod

    monkeypatch.setattr(health_mod, "_MAX_PENDING", 2)
    monitor = HealthMonitor()
    ref = {"w": np.zeros((4,), np.float32)}
    for i in range(3):
        monitor.observe_update(f"L{i}", {"w": np.full((4,), 0.1 * (i + 1),
                                                      np.float32)}, ref)
    health, _ = monitor.complete_round(
        0, {"w": np.full((4,), 0.1, np.float32)},
        {f"L{i}": 1 / 3 for i in range(3)})
    assert health["pending_evicted"] == 1
    assert "L0" not in health["divergence_raw"]  # oldest was evicted
    assert set(health["divergence_raw"]) == {"L1", "L2"}
    # the counter resets: the next round reports no eviction
    monitor.observe_update("L1", {"w": np.full((4,), 0.1, np.float32)}, ref)
    health2, _ = monitor.complete_round(
        1, {"w": np.full((4,), 0.1, np.float32)}, {"L1": 1.0})
    assert "pending_evicted" not in health2


def test_monitor_drop_forgets_the_learner():
    monitor = HealthMonitor()
    ref = {"w": np.zeros((4,), np.float32)}
    monitor.observe_update("L0", {"w": np.ones((4,), np.float32)}, ref)
    monitor.drop("L0")
    assert monitor.scores() == {}
    assert monitor.last_stats() == {}


# --------------------------------------------------------------------- #
# controller protocol-level (crafted uplinks, one poisoned learner)
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


def _sync_controller(tmp_path=None, rule="fedavg", health=True,
                     advisory=False, tag="h"):
    cfg_kwargs = {}
    if tmp_path is not None:
        cfg_kwargs["checkpoint"] = CheckpointConfig(
            dir=str(tmp_path / f"ckpt_{tag}"), every_n_rounds=1)
    config = FederationConfig(
        protocol="synchronous",
        aggregation=AggregationConfig(rule=rule, scaler="participants"),
        train=TrainParams(batch_size=4, local_steps=1),
        eval=EvalConfig(every_n_rounds=0),
        telemetry=TelemetryConfig(health=HealthConfig(
            enabled=health, advisory=advisory)),
        **cfg_kwargs,
    )
    return Controller(config, proxy_factory=_NullProxy)


def _seed_model():
    return {"enc/w": np.zeros((6, 4), np.float32),
            "head/w": np.zeros((4,), np.float32)}


def _crafted_model(seed, poisoned=False):
    rng = np.random.default_rng(seed)
    scale = 8.0 if poisoned else 0.05
    return {"enc/w": (scale * (1.0 + 0.02 * rng.standard_normal((6, 4)))
                      ).astype(np.float32),
            "head/w": (scale * (1.0 + 0.02 * rng.standard_normal(4))
                       ).astype(np.float32)}


def _wait(predicate, timeout_s=30.0, msg="condition"):
    import time
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if predicate():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


def _run_poisoned_round(ctrl, round_no=0, poisoned_idx=2):
    """Submit one crafted uplink per joined learner (learner index
    ``poisoned_idx`` diverges) and wait for the sync round to complete."""
    lids = sorted(ctrl.active_learners())
    with ctrl._lock:
        tokens = {lid: ctrl._learners[lid].auth_token for lid in lids}
    for i, lid in enumerate(lids):
        model = _crafted_model(seed=100 * round_no + i,
                               poisoned=(i == poisoned_idx))
        assert ctrl.task_completed(TaskResult(
            task_id=f"t{round_no}_{lid}", learner_id=lid,
            auth_token=tokens[lid], model=pack_model(model),
            round_id=round_no, completed_batches=1,
            train_metrics={"loss": 5.0 if i == poisoned_idx else 0.5},
            epoch_metrics=[{"loss": 0.9}, {"loss": 0.5}]))
    _wait(lambda: ctrl.global_iteration > round_no,
          msg=f"round {round_no + 1}")
    return lids


def test_controller_divergence_scores_events_and_surfaces(clean_telemetry):
    """Acceptance: a 3-learner cohort with one poisoned update yields a
    divergence score above the cohort past the documented threshold,
    emits UpdateAnomalous + RoundHealth, exports the gauges, and lands
    health + train/epoch metrics in the round's lineage."""
    ctrl = _sync_controller()
    try:
        ctrl.set_community_model(pack_model(_seed_model()))
        for i in range(3):
            ctrl.join(JoinRequest(hostname="h", port=7300 + i,
                                  num_train_examples=10))
        lids = _run_poisoned_round(ctrl, round_no=0, poisoned_idx=2)
        poisoned = lids[2]

        snap = ctrl.describe()
        by_id = {l["learner_id"]: l for l in snap["learners"]}
        threshold = ctrl.config.telemetry.health.anomaly_threshold
        assert by_id[poisoned]["divergence_score"] >= threshold
        for lid in lids[:2]:
            assert by_id[lid]["divergence_score"] < 1.0
        assert by_id[poisoned]["last_update_norm"] > \
            10 * by_id[lids[0]]["last_update_norm"]
        # the live round snapshot
        health = snap["health"]
        assert health["anomalous"] == [poisoned]
        assert health["round_update_norm"] > 0
        assert health["cohort_loss"]["max"] == pytest.approx(5.0)
        assert 0.99 <= health["participation_entropy"] <= 1.0

        # events: the journal reconstructs the anomaly
        kinds = [e["kind"] for e in tevents.tail()]
        assert "update_anomalous" in kinds and "round_health" in kinds
        anomaly = next(e for e in tevents.tail()
                       if e["kind"] == "update_anomalous")
        assert anomaly["learner_id"] == poisoned
        assert anomaly["raw"] >= threshold

        # gauges: both per-learner series + the round norm are scraped
        parsed = telemetry.parse_exposition(telemetry.render_metrics())
        div = parsed["learner_divergence_score"]
        assert div[(("learner", poisoned),)] >= threshold
        assert parsed["round_update_norm"][()] > 0

        # lineage: experiment.json rounds carry health + train metrics
        meta = ctrl.get_statistics()["round_metadata"][0]
        assert meta["health"]["anomalous"] == [poisoned]
        assert meta["train_metrics"][poisoned]["loss"] == 5.0
        assert meta["epoch_metrics"][poisoned][-1]["loss"] == 0.5
    finally:
        ctrl.shutdown()


def test_aggregates_bit_identical_with_health_on_or_off(clean_telemetry):
    """The health plane observes; it must never touch the aggregate."""
    blobs = {}
    for health in (True, False):
        ctrl = _sync_controller(health=health)
        try:
            ctrl.set_community_model(pack_model(_seed_model()))
            for i in range(3):
                ctrl.join(JoinRequest(hostname="h", port=7310 + i,
                                      num_train_examples=10))
            _run_poisoned_round(ctrl)
            blobs[health] = ctrl.community_model_bytes()
        finally:
            ctrl.shutdown()
    assert blobs[True] == blobs[False]


def test_disabled_health_performs_no_statistics_work(clean_telemetry,
                                                     monkeypatch):
    """telemetry.health.enabled=false → the uplink path is one attribute
    check: no monitor exists and no statistics function ever runs."""
    def _boom(*args, **kwargs):  # pragma: no cover - the point is: unreached
        raise AssertionError("health statistics ran on the disabled path")

    monkeypatch.setattr(HealthMonitor, "observe_update", _boom)
    monkeypatch.setattr(HealthMonitor, "complete_round", _boom)
    ctrl = _sync_controller(health=False)
    try:
        assert ctrl._health is None
        ctrl.set_community_model(pack_model(_seed_model()))
        for i in range(3):
            ctrl.join(JoinRequest(hostname="h", port=7320 + i,
                                  num_train_examples=10))
        _run_poisoned_round(ctrl)
        snap = ctrl.describe()
        assert "health" not in snap
        assert all("divergence_score" not in l for l in snap["learners"])
        meta = ctrl.get_statistics()["round_metadata"][0]
        assert meta["health"] == {}
        # train/epoch metrics still surface — they are lineage, not
        # statistics work (the satellite's backward-compatible reader)
        assert meta["train_metrics"]
    finally:
        ctrl.shutdown()


def test_leave_prunes_divergence_and_straggler_series(clean_telemetry):
    """Departed learners' label series must not accumulate (checked via
    the metrics exposition, not just the python objects)."""
    ctrl = _sync_controller()
    try:
        ctrl.set_community_model(pack_model(_seed_model()))
        for i in range(3):
            ctrl.join(JoinRequest(hostname="h", port=7330 + i,
                                  num_train_examples=10))
        lids = _run_poisoned_round(ctrl)
        gone = lids[2]
        with ctrl._lock:
            token = ctrl._learners[gone].auth_token
        parsed = telemetry.parse_exposition(telemetry.render_metrics())
        assert (("learner", gone),) in parsed["learner_divergence_score"]

        assert ctrl.leave(gone, token)
        parsed = telemetry.parse_exposition(telemetry.render_metrics())
        for series in ("learner_divergence_score", "learner_straggler_score",
                       "uplink_bytes_total"):
            assert (("learner", gone),) not in parsed.get(series, {}), series
        # survivors keep their series
        assert (("learner", lids[0]),) in parsed["learner_divergence_score"]
        assert gone not in ctrl._health.scores()
    finally:
        ctrl.shutdown()


def test_divergence_scores_survive_checkpoint_failover(tmp_path,
                                                       clean_telemetry):
    """Acceptance: scores + round health snapshots survive a controller
    kill + restore (the in-checkpoint persistence the kill-controller
    integration test exercises end-to-end)."""
    ctrl = _sync_controller(tmp_path, tag="fo")
    ctrl.set_community_model(pack_model(_seed_model()))
    for i in range(3):
        ctrl.join(JoinRequest(hostname="h", port=7340 + i,
                              num_train_examples=10))
    lids = _run_poisoned_round(ctrl)
    poisoned = lids[2]
    scores = ctrl._health.scores()
    assert scores[poisoned] >= 3.0
    ctrl.shutdown()

    ctrl2 = _sync_controller(tmp_path, tag="fo")
    try:
        assert ctrl2.restore_checkpoint()
        assert ctrl2._health.scores() == pytest.approx(scores)
        snap = ctrl2.describe()
        by_id = {l["learner_id"]: l for l in snap["learners"]}
        assert by_id[poisoned]["divergence_score"] >= 3.0
        assert snap["health"]["anomalous"] == [poisoned]
        # round health snapshots ride in the restored lineage too
        meta = ctrl2.get_statistics()["round_metadata"][0]
        assert meta["health"]["anomalous"] == [poisoned]
        # the restored gauge is scraped without waiting for a new round
        parsed = telemetry.parse_exposition(telemetry.render_metrics())
        assert parsed["learner_divergence_score"][
            (("learner", poisoned),)] >= 3.0
    finally:
        ctrl2.shutdown()


def test_advisory_hook_reaches_rules_without_changing_results(
        clean_telemetry):
    """telemetry.health.advisory=true threads the scores into selection
    + robust aggregation; the combine stays bit-identical."""
    from metisfl_tpu.aggregation.robust import CoordinateMedian, Krum

    # rule-level: advisory in, identical result out, scores recorded
    rng = np.random.default_rng(3)
    pairs = [([{"w": rng.standard_normal((4, 3)).astype(np.float32)}], 1.0)
             for _ in range(4)]
    for rule in (CoordinateMedian(), Krum(byzantine_f=1)):
        plain = rule.aggregate(pairs)
        advised = rule.aggregate(
            pairs, learner_ids=[f"L{i}" for i in range(4)],
            advisory_scores={"L1": 5.0, "L0": 0.0})
        np.testing.assert_array_equal(plain["w"], advised["w"])
        assert rule.last_advisory == {"L1": 5.0, "L0": 0.0}

    # controller-level: the flag threads scores into the selector and
    # the robust rule across a real round
    ctrl = _sync_controller(rule="median", advisory=True)
    try:
        ctrl.set_community_model(pack_model(_seed_model()))
        for i in range(3):
            ctrl.join(JoinRequest(hostname="h", port=7350 + i,
                                  num_train_examples=10))
        lids = _run_poisoned_round(ctrl)
        _run_poisoned_round(ctrl, round_no=1)
        assert ctrl._selector.last_advisory_scores is not None
        assert ctrl._aggregator.last_advisory is not None
        assert ctrl._aggregator.last_advisory[lids[2]] >= 3.0
    finally:
        ctrl.shutdown()


def test_garbage_metric_values_never_stall_the_round(clean_telemetry):
    """The wire never validates TaskResult.train_metrics/epoch_metrics;
    a None/str value must be dropped, not raise inside the completion
    handler (a swallowed exception there would skip schedule_next and
    stall the sync barrier forever)."""
    ctrl = _sync_controller()
    try:
        ctrl.set_community_model(pack_model(_seed_model()))
        for i in range(3):
            ctrl.join(JoinRequest(hostname="h", port=7370 + i,
                                  num_train_examples=10))
        lids = sorted(ctrl.active_learners())
        with ctrl._lock:
            tokens = {lid: ctrl._learners[lid].auth_token for lid in lids}
        for i, lid in enumerate(lids):
            # learner 0 ships garbage VALUES; learner 1 ships garbage
            # CONTAINERS (wire messages validate neither)
            if i == 1:
                bad = {"train_metrics": ["not", "a", "dict"],
                       "epoch_metrics": "junk"}
            else:
                bad = {"train_metrics": {"loss": None, "acc": "junk",
                                         "ok": 1.5, "nan": float("nan")},
                       "epoch_metrics": [{"loss": None}, {"loss": 0.3}]}
            assert ctrl.task_completed(TaskResult(
                task_id=f"tg_{lid}", learner_id=lid,
                auth_token=tokens[lid],
                model=pack_model(_crafted_model(seed=i)),
                completed_batches=1, **bad))
        _wait(lambda: ctrl.global_iteration > 0, msg="round 1")
        meta = ctrl.get_statistics()["round_metadata"][0]
        # only the finite float survived; the round completed regardless
        assert meta["train_metrics"][lids[0]] == {"ok": 1.5}
        assert meta["epoch_metrics"][lids[0]] == [{}, {"loss": 0.3}]
        assert lids[1] not in meta["train_metrics"]
        assert lids[1] not in meta["epoch_metrics"]
    finally:
        ctrl.shutdown()


# --------------------------------------------------------------------- #
# integration: in-process federation with a diverging learner
# --------------------------------------------------------------------- #


class _DivergingOps:
    """Wraps a model-ops engine so every shipped snapshot is offset far
    from what training produced — a deliberately diverging learner."""

    def __init__(self, inner, offset=3.0):
        self._inner = inner
        self._offset = float(offset)

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def get_variables(self):
        import jax

        def shift(x):
            arr = np.asarray(x)
            if np.issubdtype(arr.dtype, np.floating):
                return arr + np.asarray(self._offset, arr.dtype)
            return x

        return jax.tree.map(shift, self._inner.get_variables())


def test_inprocess_federation_flags_the_diverging_learner(clean_telemetry):
    """Acceptance: a real 3-learner federation with one diverging
    learner — the score separates it, UpdateAnomalous fires, and rounds
    keep completing (plain fedavg; the plane observes, never blocks)."""
    from metisfl_tpu.driver import InProcessFederation
    from metisfl_tpu.models import FlaxModelOps
    from metisfl_tpu.models.zoo import MLP
    from tests.test_federation_inprocess import _shards

    config = FederationConfig(
        aggregation=AggregationConfig(scaler="participants"),
        train=TrainParams(batch_size=16, local_steps=2, learning_rate=0.1),
        eval=EvalConfig(every_n_rounds=0),
        termination=TerminationConfig(federation_rounds=2),
    )
    fed = InProcessFederation(config)
    shards, test = _shards(3)
    template = None
    for i, shard in enumerate(shards):
        engine = FlaxModelOps(MLP(features=(8,), num_outputs=3),
                              shard.x[:2], rng_seed=0)
        if template is None:
            template = engine.get_variables()
        else:
            engine.set_variables(template)
        if i == 2:
            engine = _DivergingOps(engine)
        fed.add_learner(engine, shard, test_dataset=test)
    fed.seed_model(template)
    try:
        fed.start()
        assert fed.wait_for_rounds(2, timeout_s=120)
        snap = fed.controller.describe()
    finally:
        fed.shutdown()
    by_id = {l["learner_id"]: l for l in snap["learners"]}
    scores = sorted(by_id.items(), key=lambda kv: -kv[1]["divergence_score"])
    diverging_id, top = scores[0]
    # the diverging learner separates from the cohort past the threshold
    assert top["divergence_score"] >= 3.0, scores
    assert all(r["divergence_score"] < top["divergence_score"] / 2
               for _lid, r in scores[1:]), scores
    anomalous = [e for e in tevents.tail() if e["kind"] == "update_anomalous"]
    assert anomalous and all(e["learner_id"] == diverging_id
                             for e in anomalous)
    assert snap["round"] >= 2  # the federation kept aggregating


def test_describe_health_over_grpc_and_status_cli(clean_telemetry, capsys):
    """Real-gRPC DescribeFederation round trip: the health fields ride
    the wire and ``status --once`` renders the diverg column + health
    line."""
    from metisfl_tpu import status as status_cli
    from metisfl_tpu.controller.service import (ControllerClient,
                                                ControllerServer)

    ctrl = _sync_controller()
    server = ControllerServer(ctrl, host="127.0.0.1", port=0)
    port = server.start()
    client = ControllerClient("127.0.0.1", port)
    try:
        ctrl.set_community_model(pack_model(_seed_model()))
        for i in range(3):
            ctrl.join(JoinRequest(hostname="h", port=7360 + i,
                                  num_train_examples=10))
        lids = _run_poisoned_round(ctrl)
        snap = client.describe_federation(timeout=10.0)
        by_id = {l["learner_id"]: l for l in snap["learners"]}
        assert by_id[lids[2]]["divergence_score"] >= 3.0
        assert snap["health"]["anomalous"] == [lids[2]]

        rc = status_cli.main(["--host", "127.0.0.1", "--port", str(port),
                              "--once"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "diverg" in out and "upd_norm" in out
        assert "health:" in out and "ANOMALOUS=" in out
        assert lids[2] in out
    finally:
        client.close()
        server.stop()


def test_render_snapshot_without_health_is_unchanged():
    """Pre-health snapshots (older controller, plane disabled) render
    with the original columns — no health line, no diverg column."""
    from metisfl_tpu.status import render_snapshot

    snap = {
        "controller_epoch": "abcdef012345", "round": 1, "phase": "idle",
        "protocol": "synchronous", "aggregation_rule": "fedavg",
        "time": 10.0, "round_started_at": 0.0,
        "learners": [{"learner_id": "L0", "live": True,
                      "straggler_score": 1.0, "ewma_train_s": 1.0,
                      "ewma_eval_s": 0.1, "dispatch_failures": 0,
                      "last_result_round": 0}],
        "in_flight": [], "store": {"models": {}, "total": 0}, "events": [],
    }
    text = render_snapshot(snap)
    assert "diverg" not in text and "health:" not in text
    assert "L0" in text and "straggler" in text
