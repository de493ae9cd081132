"""Telemetry at cross-device scale (ISSUE 10): mergeable sketches,
cardinality-budgeted metric families, the SLO alert plane, the bounded
time-series ring, digest-mode DescribeFederation/status, checkpoint
persistence of collapsed families, and the join→leave series drift
guard."""

import json
import os
import time

import numpy as np
import pytest

from metisfl_tpu import telemetry
from metisfl_tpu.telemetry import events as tevents
from metisfl_tpu.telemetry import metrics as tmetrics
from metisfl_tpu.telemetry import profile as tprofile
from metisfl_tpu.telemetry.alerts import (
    AlertEngine,
    AlertRule,
    validate_rules,
)
from metisfl_tpu.telemetry.metrics import Registry
from metisfl_tpu.telemetry.sketch import QuantileDigest, SpaceSaving
from metisfl_tpu.telemetry.timeseries import TimeSeriesRing, sparkline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def clean_telemetry():
    tevents.configure(enabled=True, service="test", dir="", ring_size=512)
    tevents.journal().reset()
    tmetrics.set_enabled(True)
    tmetrics.registry().reset()
    yield
    tprofile.set_collector(None)
    tmetrics.registry().reset()
    tevents.configure(enabled=True, service="test", dir="", ring_size=512)
    tevents.journal().reset()


# --------------------------------------------------------------------- #
# sketches: exact <-> sketch equivalence, merge, serialization
# --------------------------------------------------------------------- #


def test_quantile_digest_matches_exact_quantiles():
    """The documented error contract: p50/p90/p99 of a 100k seeded
    stream within 2% relative of exact (observed ~0.2%)."""
    rng = np.random.default_rng(7)
    for values in (rng.gamma(2.0, 0.5, 100000),
                   rng.normal(5.0, 2.0, 100000),
                   rng.lognormal(0.0, 1.0, 50000)):
        digest = QuantileDigest(compression=128)
        for v in values:
            digest.add(float(v))
        for q in (0.5, 0.9, 0.99):
            exact = float(np.quantile(values, q))
            rel = abs(digest.quantile(q) - exact) / abs(exact)
            assert rel < 0.02, (q, rel)
        assert digest.quantile(0.0) == float(values.min())
        assert digest.quantile(1.0) == float(values.max())
        assert digest.count == pytest.approx(len(values))


def test_quantile_digest_merge_equals_single_stream():
    rng = np.random.default_rng(11)
    values = rng.gamma(2.0, 0.5, 80000)
    parts = np.array_split(values, 4)
    merged = QuantileDigest(128)
    for part in parts:
        shard = QuantileDigest(128)
        for v in part:
            shard.add(float(v))
        merged.merge(shard)
    assert merged.count == pytest.approx(len(values))
    for q in (0.5, 0.9, 0.99):
        exact = float(np.quantile(values, q))
        assert abs(merged.quantile(q) - exact) / abs(exact) < 0.02


def test_quantile_digest_serialization_roundtrip():
    digest = QuantileDigest(64)
    rng = np.random.default_rng(3)
    for v in rng.standard_normal(5000):
        digest.add(float(v))
    clone = QuantileDigest.from_dict(
        json.loads(json.dumps(digest.to_dict())))
    for q in (0.1, 0.5, 0.9, 0.99):
        assert clone.quantile(q) == pytest.approx(digest.quantile(q))
    # bounded state: the serialized form is O(compression), not O(n)
    assert len(digest.to_dict()["means"]) < 5000 / 4


def test_quantile_digest_edge_cases():
    empty = QuantileDigest()
    assert empty.quantile(0.5) == 0.0
    one = QuantileDigest()
    one.add(42.0)
    assert one.quantile(0.5) == 42.0
    nan = QuantileDigest()
    nan.add(float("nan"))
    assert nan.count == 0.0


def test_space_saving_heavy_hitters_and_error_bound():
    tracker = SpaceSaving(capacity=8)
    import random
    stream = ["hot"] * 500 + ["warm"] * 200 + [f"k{i}" for i in range(1000)]
    random.Random(3).shuffle(stream)
    for key in stream:
        tracker.offer(key)
    rows = tracker.top(2)
    assert rows[0][0] == "hot" and rows[1][0] == "warm"
    # space-saving invariant: true_count >= count - error
    for key, count, error, _last in tracker.top(0):
        true = {"hot": 500, "warm": 200}.get(key, 1)
        assert count - error <= true <= count
    tracker.drop("hot")
    assert "hot" not in tracker
    clone = SpaceSaving.from_dict(json.loads(json.dumps(tracker.to_dict())))
    assert clone.top(3) == tracker.top(3)


def test_space_saving_merge():
    a, b = SpaceSaving(8), SpaceSaving(8)
    for _ in range(10):
        a.offer("x")
    for _ in range(7):
        b.offer("x")
    for _ in range(5):
        b.offer("y")
    a.merge(b)
    rows = dict((k, c) for k, c, _e, _l in a.top(0))
    assert rows["x"] == 17.0 and rows["y"] == 5.0


# --------------------------------------------------------------------- #
# cardinality budgets in the metrics registry
# --------------------------------------------------------------------- #


def _fleet_registry(budget=0):
    reg = Registry()
    gauge = reg.gauge("learner_straggler_score", "scores", ("learner",),
                      budget_label="learner")
    counter = reg.counter("uplink_bytes_total", "bytes", ("learner",),
                          budget_label="learner")
    if budget:
        reg.set_cardinality_budget(budget)
    return reg, gauge, counter


def test_budget_disabled_and_sub_budget_are_bit_identical():
    """The opt-out pin: budget off, and budget armed but not exceeded,
    both render the exact per-series exposition byte-for-byte."""
    captures = []
    for budget in (0, 64):
        reg, gauge, counter = _fleet_registry(budget)
        for i in range(32):
            gauge.set(i * 0.25, learner=f"L{i}")
            counter.inc(100 + i, learner=f"L{i}")
        assert not gauge.collapsed() and not counter.collapsed()
        captures.append(reg.render())
    assert captures[0] == captures[1]
    assert 'learner_straggler_score{learner="L31"} 7.75' in captures[0]


def test_budget_collapse_bounds_exposition():
    reg, gauge, counter = _fleet_registry(budget=32)
    rng = np.random.default_rng(5)
    values = rng.gamma(2.0, 0.5, 5000)
    for i, v in enumerate(values):
        gauge.set(float(v), learner=f"L{i}")
        counter.inc(10.0, learner=f"L{i}")
    assert gauge.collapsed() and counter.collapsed()
    text = reg.render()
    # O(budget) output series however large the fleet
    lines = [l for l in text.splitlines()
             if l and not l.startswith("#")]
    assert len(lines) < 100
    parsed = tmetrics.parse_exposition(text)
    # gauge family: quantile series + top-K offenders
    quantiles = {k: v for k, v in parsed["learner_straggler_score"].items()
                 if k and k[0][0] == "quantile"}
    assert set(q for (label,) in quantiles for q in [label[1]]) == {
        "0.5", "0.9", "0.99"}
    for q in (0.5, 0.9, 0.99):
        exact = float(np.quantile(values, q))
        got = quantiles[(("quantile", f"{q:g}"),)]
        assert abs(got - exact) / exact < 0.02
    # counter family: offenders + "_other" remainder preserve sum()
    total = sum(v for v in parsed["uplink_bytes_total"].values())
    assert total == pytest.approx(5000 * 10.0)
    assert counter.total() == pytest.approx(5000 * 10.0)
    # companion families
    assert parsed["metrics_series_overflow_total"][
        (("family", "learner_straggler_score"),)] >= 5000 - 32
    assert parsed["metrics_family_series"][
        (("family", "learner_straggler_score"),)] == 5000
    assert gauge.series_count() == 5000
    assert gauge.quantile(0.9) == pytest.approx(
        float(np.quantile(values, 0.9)), rel=0.02)


def test_budget_prune_and_remove_past_collapse():
    reg, gauge, _counter = _fleet_registry(budget=8)
    for i in range(20):
        gauge.set(float(i), learner=f"L{i}")
    assert gauge.collapsed()
    before = gauge.series_count()
    gauge.remove(learner="L19")
    reg.prune_label_value("L18")
    assert gauge.series_count() == before - 2
    # the offender table forgets pruned learners too
    text = reg.render()
    assert 'learner="L19"' not in text and 'learner="L18"' not in text


def test_budget_state_roundtrip_restores_digests():
    reg, gauge, counter = _fleet_registry(budget=16)
    rng = np.random.default_rng(9)
    values = rng.gamma(3.0, 1.0, 2000)
    for i, v in enumerate(values):
        gauge.set(float(v), learner=f"L{i}")
        counter.inc(float(v), learner=f"L{i}")
    state = json.loads(json.dumps(reg.budget_state(), default=str))
    assert set(state) == {"learner_straggler_score", "uplink_bytes_total"}
    # O(budget) checkpoint bytes, not O(fleet)
    assert len(json.dumps(state)) < 60_000
    reg2, gauge2, counter2 = _fleet_registry(budget=16)
    reg2.restore_budget_state(state)
    assert gauge2.collapsed()
    assert gauge2.series_count() == 2000
    assert gauge2.quantile(0.9) == pytest.approx(gauge.quantile(0.9))
    assert counter2.total() == pytest.approx(counter.total())


def test_collapsed_counter_quantile_is_inert_not_garbage():
    """A collapsed counter family's quantile() must return 0.0, not
    the eviction-biased top-K counts: a digest-quantile alert over it
    would otherwise false-fire on garbage (review finding)."""
    reg, _gauge, counter = _fleet_registry(budget=8)
    for i in range(1000):
        counter.inc(float(i % 10 + 1), learner=f"L{i}")
    assert counter.collapsed()
    assert counter.quantile(0.5) == 0.0
    # exact mode still answers exactly
    reg2, _g2, counter2 = _fleet_registry(budget=0)
    for i in range(9):
        counter2.inc(float(i + 1), learner=f"L{i}")
    assert counter2.quantile(0.5) == 5.0


def test_collapsed_counter_remainder_is_per_rest_label():
    """Multi-label counter families keep ONE `_other` remainder per
    non-budget label combination with the full label set, so
    `sum by (op)` stays exact past the budget and the family's label
    sets stay consistent (review finding)."""
    reg = Registry()
    counter = reg.counter("codec_learner_seconds_total", "",
                          ("learner", "op"), budget_label="learner")
    reg.set_cardinality_budget(8)
    for i in range(200):
        counter.inc(1.0, learner=f"L{i}", op="encode")
        counter.inc(3.0, learner=f"L{i}", op="decode")
    assert counter.collapsed()
    parsed = tmetrics.parse_exposition(reg.render())
    series = parsed["codec_learner_seconds_total"]
    by_op = {"encode": 0.0, "decode": 0.0}
    for labels, value in series.items():
        label_map = dict(labels)
        assert set(label_map) == {"learner", "op"}, labels  # consistent
        by_op[label_map["op"]] += value
    assert by_op["encode"] == pytest.approx(200.0)
    assert by_op["decode"] == pytest.approx(600.0)
    # state roundtrip preserves the per-rest totals
    reg2 = Registry()
    c2 = reg2.counter("codec_learner_seconds_total", "",
                      ("learner", "op"), budget_label="learner")
    reg2.set_cardinality_budget(8)
    reg2.restore_budget_state(
        json.loads(json.dumps(reg.budget_state(), default=str)))
    assert c2.total() == pytest.approx(800.0)


def test_collapsed_gauge_offenders_rank_by_current_value():
    """A frequent low-score reporter must not evict the true worst
    offender from a collapsed gauge's top-K: gauges rank by CURRENT
    value, not accumulated sum of set() calls (review finding)."""
    reg, gauge, _counter = _fleet_registry(budget=8)
    for i in range(30):
        gauge.set(0.5, learner=f"L{i}")     # collapse the family
    for _ in range(200):
        gauge.set(0.9, learner="fast")      # reports every "round"
    for _ in range(3):
        gauge.set(5.0, learner="straggler")  # reports rarely
    top = dict((k, last) for k, _c, _e, last in gauge._sketch.topk.top(3))
    assert top.get("straggler") == 5.0, top
    text = reg.render()
    assert 'learner="straggler"} 5' in text
    # and a recovered offender follows its value DOWN
    gauge.set(0.1, learner="straggler")
    assert gauge._sketch.topk.top(1)[0][0] != "straggler" or \
        gauge._sketch.topk.top(1)[0][3] == 0.1


def test_alert_poll_isolates_broken_rules(clean_telemetry):
    """A rule mistargeting a family whose read path cannot answer
    (e.g. a histogram) must not stop OTHER rules from evaluating
    (review finding: poll() used to abort on the first TypeError)."""
    reg = tmetrics.registry()
    reg.histogram("round_latency_hist", "", ()).observe(1.0)
    gauge = reg.gauge("depth3", "", ())
    gauge.set(9.0)
    engine = AlertEngine([
        AlertRule.from_spec({"name": "hist_rule",
                             "metric": "round_latency_hist",
                             "kind": "quantile", "threshold": 1.0}),
        AlertRule.from_spec({"name": "works", "metric": "depth3",
                             "kind": "value", "op": ">", "threshold": 1.0}),
    ], registry=reg, interval_s=10.0)
    out = engine.poll(now=500.0)
    assert [t["alert"] for t in out if t["transition"] == "firing"] == [
        "works"]
    # histogram reads are inert (0.0), never a raise; and even a rule
    # that genuinely raises is skipped, not fatal
    engine.rules[0] = AlertRule.from_spec(
        {"name": "hist_rule", "metric": "round_latency_hist",
         "kind": "value", "threshold": 1.0})
    engine._states[engine.rules[0].name] = engine._states["hist_rule"]
    assert engine.poll(now=501.0) == []  # no transitions, no crash


def test_registry_reset_disarms_budget():
    reg, gauge, _counter = _fleet_registry(budget=4)
    for i in range(10):
        gauge.set(1.0, learner=f"L{i}")
    assert gauge.collapsed()
    reg.reset()
    assert not gauge.collapsed()
    for i in range(10):
        gauge.set(1.0, learner=f"L{i}")
    assert not gauge.collapsed()  # budget disarmed with the reset


# --------------------------------------------------------------------- #
# drift guard (satellite): every per-learner family prunes centrally
# --------------------------------------------------------------------- #


def test_every_per_learner_family_is_budget_labeled(clean_telemetry):
    """Drift guard: a family keyed by learner/peer that is NOT
    registered with a budget_label would escape both the cardinality
    budget and the central telemetry.prune_learner — importing every
    registering module, assert none exists."""
    import metisfl_tpu.chaos.injector  # noqa: F401
    import metisfl_tpu.comm.codec  # noqa: F401
    import metisfl_tpu.comm.rpc  # noqa: F401
    import metisfl_tpu.controller.core  # noqa: F401
    import metisfl_tpu.learner.learner  # noqa: F401
    import metisfl_tpu.serving.gateway  # noqa: F401
    import metisfl_tpu.store.cached  # noqa: F401
    import metisfl_tpu.telemetry.profile  # noqa: F401

    reg = tmetrics.registry()
    offenders = []
    for name in list(reg._metrics):
        family = reg.get(name)
        fleet_labels = {"learner", "peer"} & set(family.labelnames)
        if fleet_labels and not family.budget_label:
            offenders.append(name)
    assert not offenders, (
        f"per-learner families without a cardinality label (they leak "
        f"series past leave() and ignore the budget): {offenders}")
    budgeted = {f.name for f in reg.budget_families()}
    # the full catalog of per-learner families this PR budgets
    for expected in (telemetry.M_UPLINK_BYTES_TOTAL,
                     telemetry.M_LEARNER_STRAGGLER_SCORE,
                     telemetry.M_LEARNER_DIVERGENCE_SCORE,
                     telemetry.M_LEARNER_CHURN_SCORE,
                     telemetry.M_DOWNLINK_BYTES_TOTAL,
                     telemetry.M_LEARNER_ACHIEVED_MFU,
                     telemetry.M_LEARNER_STEP_MS_EWMA,
                     telemetry.M_LEARNER_HBM_PEAK_BYTES,
                     telemetry.M_CODEC_LEARNER_SECONDS,
                     telemetry.M_RPC_PEER_BYTES_TOTAL):
        assert expected in budgeted, expected


def test_prune_learner_clears_every_family(clean_telemetry):
    """One call drops a departed learner's series across ALL budgeted
    families (exact mode and collapsed mode both)."""
    from metisfl_tpu.comm import codec as _codec
    reg = tmetrics.registry()
    gone, kept = "Lgone_h_1", "Lkept_h_2"
    for family in reg.budget_families():
        idx = family.labelnames.index(family.budget_label)
        for lid in (gone, kept):
            labels = {name: (lid if i == idx else "x")
                      for i, name in enumerate(family.labelnames)}
            if family.kind == "gauge":
                family.set(1.5, **labels)
            else:
                family.inc(3.0, **labels)
    _codec.attribute(gone, "decode", 0.01)
    telemetry.prune_learner(gone)
    parsed = tmetrics.parse_exposition(telemetry.render_metrics())
    for name, series in parsed.items():
        for labels in series:
            assert ("learner", gone) not in labels, (name, labels)
            assert ("peer", gone) not in labels, (name, labels)
    # the survivor keeps its series, and the codec totals are gone too
    assert any(("learner", kept) in labels
               for labels in parsed["learner_straggler_score"])
    assert (gone, "decode") not in _codec.attributed_totals()


def test_join_leave_leaks_no_series(clean_telemetry):
    """Controller-level drift guard: a full join→uplink→leave cycle
    leaves ZERO per-learner series for the departed learner in the
    exposition (the satellite's end-to-end assertion)."""
    from metisfl_tpu.comm.messages import JoinRequest
    from metisfl_tpu.config import FederationConfig, EvalConfig
    from metisfl_tpu.controller.core import Controller

    cfg = FederationConfig(eval=EvalConfig(every_n_rounds=0))
    ctrl = Controller(cfg, proxy_factory=lambda record: None)
    try:
        replies = [ctrl.join(JoinRequest(hostname="h", port=9000 + i,
                                         num_train_examples=8))
                   for i in range(3)]
        gone = replies[0].learner_id
        # mint per-learner series the way the planes do
        from metisfl_tpu.controller.core import (_M_CHURN, _M_STRAGGLER,
                                                 _M_UPLINK)
        for reply in replies:
            _M_UPLINK.inc(100, learner=reply.learner_id)
            _M_STRAGGLER.set(1.0, learner=reply.learner_id)
            _M_CHURN.set(0.1, learner=reply.learner_id)
        assert ctrl.leave(gone, replies[0].auth_token)
        parsed = tmetrics.parse_exposition(telemetry.render_metrics())
        leaked = [(name, labels) for name, series in parsed.items()
                  for labels in series
                  if ("learner", gone) in labels or ("peer", gone) in labels]
        assert not leaked, leaked
    finally:
        ctrl.shutdown()


# --------------------------------------------------------------------- #
# time-series ring + sparklines
# --------------------------------------------------------------------- #


def test_timeseries_ring_bounds_and_rate():
    ring = TimeSeriesRing(capacity=8, max_series=2)
    for i in range(20):
        ring.record("a", float(i), ts=100.0 + i)
    assert len(ring.points("a")) == 8  # capacity-bounded
    ring.record("b", 1.0, ts=120.0)
    ring.record("c", 1.0, ts=120.0)  # past max_series: dropped
    assert ring.names() == ["a", "b"]
    assert ring.dropped_series == 1
    # counter rate over a window
    assert ring.rate("a", 5.0, now=119.0) == pytest.approx(1.0)
    assert ring.rate("a", 5.0, now=500.0) == 0.0  # window empty
    snap = ring.snapshot(points=3)
    assert snap["a"]["points"] == [17.0, 18.0, 19.0]


def test_sparkline_render():
    assert sparkline([]) == ""
    assert sparkline([1.0, 1.0, 1.0]) == "▁▁▁"
    line = sparkline([0, 1, 2, 3, 4, 5, 6, 7])
    assert line[0] == "▁" and line[-1] == "█" and len(line) == 8


# --------------------------------------------------------------------- #
# alert rules + engine lifecycle
# --------------------------------------------------------------------- #


def test_alert_rule_validation_rejects_typos():
    with pytest.raises(ValueError, match="unknown keys"):
        AlertRule.from_spec({"name": "x", "metric": "m", "threshold": 1,
                             "thresold": 2})
    with pytest.raises(ValueError, match="needs a 'metric'"):
        AlertRule.from_spec({"name": "x", "threshold": 1})
    with pytest.raises(ValueError, match="kind"):
        AlertRule.from_spec({"name": "x", "metric": "m", "threshold": 1,
                             "kind": "burn"})
    with pytest.raises(ValueError, match="duplicate"):
        validate_rules([{"name": "x", "metric": "m", "threshold": 1},
                        {"name": "x", "metric": "m", "threshold": 2}])
    from metisfl_tpu.config import FederationConfig, TelemetryConfig
    with pytest.raises(ValueError, match="invalid telemetry.alerts"):
        FederationConfig(telemetry=TelemetryConfig(
            alerts=[{"name": "x", "metric": "m"}]))


def test_alert_for_hold_and_resolve_hysteresis():
    reg = Registry()
    gauge = reg.gauge("queue_depth", "", ())
    rule = AlertRule.from_spec({
        "name": "deep_queue", "metric": "queue_depth", "kind": "value",
        "op": ">", "threshold": 10.0, "for_s": 5.0, "resolve_ratio": 0.5})
    engine = AlertEngine([rule], registry=reg, interval_s=10.0)
    t0 = 1000.0
    gauge.set(20.0)
    assert engine.poll(now=t0) == []           # breach starts: pending
    assert engine.active(now=t0) == []
    gauge.set(5.0)
    assert engine.poll(now=t0 + 2) == []       # de-breached before for_s
    gauge.set(20.0)
    engine.poll(now=t0 + 3)                    # pending again
    out = engine.poll(now=t0 + 9)              # held >= 5s: fires
    assert out and out[0]["transition"] == "firing"
    # hysteresis: 10 > value >= 5 keeps it firing
    gauge.set(7.0)
    assert engine.poll(now=t0 + 10) == []
    assert engine.active(now=t0 + 10)
    gauge.set(4.0)                             # below 0.5 * threshold
    out = engine.poll(now=t0 + 11)
    assert out and out[0]["transition"] == "resolved"
    assert engine.fired_total == 1 and engine.resolved_total == 1


def test_alert_hysteresis_negative_threshold_does_not_flap():
    """Margin-form hysteresis stays monotone for negative thresholds —
    a multiplicative bound would invert and flap the alert every poll
    (review finding)."""
    reg = Registry()
    gauge = reg.gauge("headroom", "", ())
    rule = AlertRule.from_spec({
        "name": "low_headroom", "metric": "headroom", "kind": "value",
        "op": ">", "threshold": -1.0, "resolve_ratio": 0.8})
    engine = AlertEngine([rule], registry=reg, interval_s=10.0)
    gauge.set(-0.9)                             # breaches (-0.9 > -1.0)
    out = engine.poll(now=100.0)
    assert out and out[0]["transition"] == "firing"
    for step in range(5):                       # steady value: no flap
        assert engine.poll(now=101.0 + step) == []
    gauge.set(-1.3)                             # below -1.0 - 0.2 margin
    out = engine.poll(now=110.0)
    assert out and out[0]["transition"] == "resolved"
    assert engine.fired_total == 1 and engine.resolved_total == 1


def test_sub_budget_straggler_gauge_keeps_full_refresh(clean_telemetry):
    """Budget ARMED but fleet below it: the straggler family is exact,
    so the per-uplink refresh must keep re-normalizing EVERY learner
    against the moving median — only a genuinely collapsed family takes
    the reporter-only fast path (review finding)."""
    from metisfl_tpu.controller.core import _M_STRAGGLER

    ctrl = _controller(budget=64)
    try:
        replies = _join_n(ctrl, 3)
        with ctrl._lock:
            for i, reply in enumerate(replies):
                ctrl._learners[reply.learner_id].ewma_train_s = 1.0 + i
        ctrl._update_straggler_gauge(completed=replies[0].learner_id)
        assert not _M_STRAGGLER.collapsed()
        # all three series refreshed against the shared median (2.0)
        for i, reply in enumerate(replies):
            got = _M_STRAGGLER.value(learner=reply.learner_id)
            assert got == pytest.approx((1.0 + i) / 2.0, abs=1e-3)
    finally:
        ctrl.shutdown()


def test_alert_engine_events_gauge_and_quantile_rules(clean_telemetry):
    reg = tmetrics.registry()
    gauge = reg.gauge("learner_straggler_score", "", ("learner",),
                      budget_label="learner")
    reg.set_cardinality_budget(8)
    rule = AlertRule.from_spec({
        "name": "straggler_tail", "metric": "learner_straggler_score",
        "kind": "quantile", "quantile": 0.9, "op": ">", "threshold": 3.0,
        "severity": "critical"})
    engine = AlertEngine([rule], registry=reg, interval_s=10.0)
    for i in range(50):
        gauge.set(5.0, learner=f"L{i}")       # whole fleet straggling
    assert gauge.collapsed()                   # rule reads the digest
    out = engine.poll(now=2000.0)
    assert out[0]["transition"] == "firing"
    expo = telemetry.render_metrics()
    assert 'alerts_active{alert="straggler_tail"} 1' in expo
    assert 'alerts_fired_total{alert="straggler_tail"} 1' in expo
    kinds = [r["kind"] for r in tevents.tail()]
    assert "alert_firing" in kinds
    summary = engine.summary(now=2001.0)
    assert summary["active"][0]["name"] == "straggler_tail"
    # shutdown prunes the active-gauge series
    engine.shutdown()
    assert 'alerts_active{alert="straggler_tail"}' \
        not in telemetry.render_metrics()


def test_postmortem_bundle_carries_alerts_at_death(clean_telemetry,
                                                   tmp_path):
    from metisfl_tpu.telemetry import alerts as talerts
    from metisfl_tpu.telemetry import postmortem
    from metisfl_tpu.telemetry.__main__ import render_postmortem

    reg = tmetrics.registry()
    gauge = reg.gauge("queue_depth2", "", ())
    gauge.set(99.0)
    engine = AlertEngine([AlertRule.from_spec(
        {"name": "dead_queue", "metric": "queue_depth2", "kind": "value",
         "op": ">", "threshold": 1.0})], registry=reg, interval_s=10.0)
    engine.poll(now=3000.0)
    talerts.set_engine(engine)
    try:
        postmortem.configure(str(tmp_path), service="test",
                             install_hooks=False)
        path = postmortem.dump("chaos_kill")
        bundle = json.load(open(path))
        assert bundle["alerts"]["active"][0]["name"] == "dead_queue"
        text = render_postmortem({**bundle, "_path": path})
        assert "alerts at death" in text and "FIRING dead_queue" in text
    finally:
        talerts.set_engine(None)
        postmortem.configure("", service="test", install_hooks=False)


# --------------------------------------------------------------------- #
# controller: digest-mode describe, round metadata, checkpoint
# --------------------------------------------------------------------- #


def _controller(budget=0, alerts=(), checkpoint_dir=""):
    from metisfl_tpu.config import (CheckpointConfig, EvalConfig,
                                    FederationConfig, TelemetryConfig)
    from metisfl_tpu.controller.core import Controller

    cfg = FederationConfig(
        eval=EvalConfig(every_n_rounds=0),
        checkpoint=CheckpointConfig(dir=checkpoint_dir),
        telemetry=TelemetryConfig(cardinality_budget=budget,
                                  alerts=list(alerts),
                                  alerts_interval_s=60.0))
    return Controller(cfg, proxy_factory=lambda record: None)


def _join_n(ctrl, n):
    from metisfl_tpu.comm.messages import JoinRequest

    return [ctrl.join(JoinRequest(hostname="h", port=20000 + i,
                                  num_train_examples=8))
            for i in range(n)]


def test_describe_digest_mode_above_budget(clean_telemetry):
    ctrl = _controller(budget=8)
    try:
        _join_n(ctrl, 24)
        snap = ctrl.describe(event_tail=0)
        digest = snap["learners_digest"]
        assert digest["count"] == 24 and digest["budget"] == 8
        assert digest["live"] == 24
        assert set(digest["columns"]) >= {"straggler_score",
                                          "ewma_train_s",
                                          "dispatch_failures"}
        # the learner table is the bounded top-offender list, not O(fleet)
        assert len(snap["learners"]) <= 10
        # the store occupancy map is elided too
        assert snap["store"]["models"] == {}
        payload = len(json.dumps(snap, default=str))
        assert payload < 20_000
    finally:
        ctrl.shutdown()


def test_describe_sub_budget_is_exact_shape(clean_telemetry):
    ctrl = _controller(budget=64)
    try:
        _join_n(ctrl, 5)
        snap = ctrl.describe(event_tail=0)
        assert "learners_digest" not in snap
        assert len(snap["learners"]) == 5
        assert "models" in snap["store"]
    finally:
        ctrl.shutdown()


def test_checkpoint_persists_and_restores_digests(clean_telemetry,
                                                  tmp_path):
    from metisfl_tpu.controller.core import _M_STRAGGLER
    from metisfl_tpu.tensor.pytree import pack_model

    ckpt = str(tmp_path / "ckpt")
    ctrl = _controller(budget=8, checkpoint_dir=ckpt)
    try:
        _join_n(ctrl, 4)
        ctrl.set_community_model(pack_model(
            {"w": np.zeros((2, 2), np.float32)}))
        rng = np.random.default_rng(5)
        values = rng.gamma(2.0, 0.5, 200)
        for i, v in enumerate(values):
            _M_STRAGGLER.set(float(v), learner=f"L{i}")
        assert _M_STRAGGLER.collapsed()
        q90 = _M_STRAGGLER.quantile(0.9)
        ctrl.save_checkpoint()
    finally:
        ctrl.shutdown()
    # fresh "incarnation": series zeroed, digests restored from disk
    tmetrics.registry().reset()
    ctrl2 = _controller(budget=8, checkpoint_dir=ckpt)
    try:
        assert ctrl2.restore_checkpoint()
        assert _M_STRAGGLER.collapsed()
        assert _M_STRAGGLER.series_count() == 200
        assert _M_STRAGGLER.quantile(0.9) == pytest.approx(q90)
    finally:
        ctrl2.shutdown()


def test_round_metadata_metrics_digest(clean_telemetry):
    from metisfl_tpu.controller.core import _M_STRAGGLER

    ctrl = _controller(budget=4)
    try:
        for i in range(12):
            _M_STRAGGLER.set(1.0 + i, learner=f"L{i}")
        ctrl._note_round_telemetry()
        with ctrl._lock:
            digest = dict(ctrl._current_meta.metrics_digest)
        assert "learner_straggler_score" in digest
        entry = digest["learner_straggler_score"]
        assert entry["series"] == 12
        assert set(entry["quantiles"]) == {"0.5", "0.9", "0.99"}
        assert entry["top"]
    finally:
        ctrl.shutdown()


# --------------------------------------------------------------------- #
# status CLI: byte-identity below budget, digest + alerts above
# --------------------------------------------------------------------- #

_SUB_BUDGET_SNAPSHOT = {
    "controller_epoch": "abcdef0123456789",
    "round": 4, "phase": "wait_uplinks", "protocol": "synchronous",
    "round_started_at": 1000.0, "aggregation_rule": "fedavg",
    "shutdown": False,
    "learners": [
        {"learner_id": "L0_host_1", "hostname": "host", "port": 1,
         "live": True, "dispatch_failures": 0, "num_train_examples": 32,
         "last_result_round": 3, "ewma_train_s": 1.25, "ewma_eval_s": 0.4,
         "straggler_score": 1.0, "churn_score": 0.0, "quarantined": False},
        {"learner_id": "L1_host_2", "hostname": "host", "port": 2,
         "live": False, "dispatch_failures": 3, "num_train_examples": 32,
         "last_result_round": 2, "ewma_train_s": 3.75, "ewma_eval_s": 0.0,
         "straggler_score": 3.0, "churn_score": 0.31, "quarantined": True},
    ],
    "in_flight": [{"task_id": "t123456789", "learner_id": "L0_host_1",
                   "age_s": 2.5}],
    "store": {"models": {"L0_host_1": 2, "L1_host_2": 1}, "total": 3},
    "events": [],
    "time": 1010.0,
}

# what python -m metisfl_tpu.status --once printed for this snapshot
# BEFORE this PR — the sub-budget render must stay byte-identical
_SUB_BUDGET_GOLDEN = (
    "federation @ localhost:50051  epoch=abcdef01  round=4  "
    "phase=wait_uplinks  round_age=10.0s  protocol=synchronous  "
    "rule=fedavg  learners=1/2 live\n"
    "\n"
    "learner                      live straggler  churn ewma_train "
    "ewma_eval fails last_round stored\n"
    "L0_host_1                     yes     1.00x      -       1.2s      "
    "0.4s     0          3      2\n"
    "L1_host_2                      NO     3.00x   QUAR       3.8s         "
    "-     3          2      1\n"
    "\n"
    "in-flight (1): L0_host_1:t1234567 (2.5s)")


def test_status_sub_budget_render_byte_identical():
    from metisfl_tpu.status import render_snapshot

    out = render_snapshot(dict(_SUB_BUDGET_SNAPSHOT),
                          target="localhost:50051")
    assert out == _SUB_BUDGET_GOLDEN


def test_status_digest_mode_render():
    from metisfl_tpu.status import render_snapshot

    snap = dict(_SUB_BUDGET_SNAPSHOT)
    snap["learners_digest"] = {
        "count": 10000, "live": 9800, "budget": 256, "quarantined": 3,
        "columns": {
            "straggler_score": {"p50": 1.0, "p90": 2.5, "p99": 7.25,
                                "max": 31.0},
            "ewma_train_s": {"p50": 1.2, "p90": 2.0, "p99": 4.0,
                             "max": 9.0}}}
    snap["store"] = {"models": {}, "learners": 10000, "total": 10000}
    snap["alerts"] = {
        "enabled": True, "rules": 2, "pending": 0, "fired_total": 3,
        "resolved_total": 2,
        "active": [{"name": "straggler_tail", "severity": "critical",
                    "expr": "q0.9(learner_straggler_score) > 3",
                    "value": 7.25, "threshold": 3.0, "active_s": 42.0}]}
    snap["timeseries"] = {
        "rounds_total": {"points": [1, 2, 3, 4, 5, 6, 7, 8],
                         "last_ts": 1010.0}}
    out = render_snapshot(snap, target="localhost:50051")
    assert "alerts: FIRING 1: straggler_tail[critical]" in out
    assert "q0.9(learner_straggler_score) > 3" in out
    assert "cardinality budget 256" in out
    assert "9800/10000 live" in out
    assert "straggler_score" in out and "7.25" in out
    assert "top offenders by straggler score" in out
    assert "rounds_total" in out and "▁" in out  # sparkline block chars
    # the bounded offender table still renders under the digest header
    assert "L0_host_1" in out


def test_status_alerts_quiet_line():
    from metisfl_tpu.status import render_snapshot

    snap = dict(_SUB_BUDGET_SNAPSHOT)
    snap["alerts"] = {"enabled": True, "rules": 2, "active": [],
                      "pending": 0, "fired_total": 1, "resolved_total": 1}
    out = render_snapshot(snap)
    assert "alerts: none firing  rules=2  fired=1  resolved=1" in out


# --------------------------------------------------------------------- #
# cross-device harness at scale (the tentpole's acceptance scenario)
# --------------------------------------------------------------------- #


def test_crossdevice_budget_and_alert_smoke(clean_telemetry):
    """Fast acceptance shape: 512 virtual clients under a budget of 64
    with the alert smoke armed — families collapse, the alert fires and
    resolves, and the run stays correct."""
    from metisfl_tpu.driver.crossdevice import ChurnScenario, run_scenario

    result = run_scenario(ChurnScenario(
        seed=7, clients=512, rounds=3, quorum=8, overprovision=1.0,
        dropout=0.3, cardinality_budget=64, alert_smoke=True,
        timeout_s=90.0))
    assert result["ok"], result
    alerts = result["alerts"]
    assert alerts["fired"] >= 1 and alerts["resolved"] >= 1
    assert not alerts["active_at_end"]
    tel = result["telemetry"]
    assert tel["budget"] == 64
    assert "learner_straggler_score" in tel["collapsed_families"]
    # bounded scrape despite 512 clients: O(budget) series per family
    assert tel["exposition_series"] < 600


@pytest.mark.slow
def test_crossdevice_10k_clients_under_budget(clean_telemetry):
    """The ISSUE 10 acceptance scenario: 10k+ virtual clients under a
    cardinality budget of 256 — rounds complete, the exposition stays
    O(budget), and RSS growth stays bounded."""
    from metisfl_tpu.driver.crossdevice import ChurnScenario, run_scenario

    result = run_scenario(ChurnScenario(
        seed=7, clients=10000, rounds=3, quorum=300, overprovision=1.0,
        dropout=0.3, cardinality_budget=256, timeout_s=240.0))
    assert result["ok"], result
    tel = result["telemetry"]
    assert tel["collapsed_families"]
    assert tel["exposition_series"] < 1500
    assert tel["exposition_bytes"] < 1 << 20
    assert result["rss_growth_kb"] < (512 << 10)


# --------------------------------------------------------------------- #
# template.yaml pins (satellite)
# --------------------------------------------------------------------- #


def test_template_documents_budget_and_alerts_at_defaults():
    import yaml

    from metisfl_tpu.config import FederationConfig
    from metisfl_tpu.config.federation import _from_plain

    path = os.path.join(REPO, "examples", "config", "template.yaml")
    with open(path) as fh:
        data = yaml.safe_load(fh)
    tel = data["telemetry"]
    assert tel["cardinality_budget"] == 0      # exact series by default
    assert tel["alerts"] == []                 # no engine by default
    assert tel["alerts_interval_s"] == 1.0
    cfg = _from_plain(FederationConfig, data)
    assert cfg.telemetry.cardinality_budget == 0
    assert cfg.telemetry.alerts == []
    assert cfg.telemetry.alerts_interval_s == 1.0
