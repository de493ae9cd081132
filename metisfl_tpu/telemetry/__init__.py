"""Federation-wide telemetry: trace spans + metrics registry + events.

Zero-dependency observability for the federation runtime (ROADMAP
north-star: a production service must tell you *where* a round is stuck
while it is stuck, not after the experiment ends):

- :mod:`metisfl_tpu.telemetry.trace` — context-manager spans with
  federation-wide trace/span ids, a process-local JSONL sink, and
  propagation over gRPC metadata (controller dispatch → learner train →
  aggregation stitch into one tree per round, rooted at the controller's
  round span; the driver collects every process's sink files).
- :mod:`metisfl_tpu.telemetry.metrics` — thread-safe counters / gauges /
  histograms with Prometheus text exposition, served via the
  ``GetMetrics`` RPC on controller and learner and the optional
  plain-HTTP ``/metrics`` listener (:mod:`metisfl_tpu.telemetry.httpd`).
- :mod:`metisfl_tpu.telemetry.events` — typed, structured event journal
  (joins, rounds, dispatches, retries, faults) in a bounded ring buffer
  + JSONL sink; the tail rides in ``DescribeFederation`` snapshots and
  post-mortem bundles.
- :mod:`metisfl_tpu.telemetry.postmortem` — the flight recorder: on an
  unhandled crash, chaos kill, or failover relaunch, a process dumps its
  event tail + open spans + metrics into ``<workdir>/postmortem/``.
- :mod:`metisfl_tpu.telemetry.health` — the learning-health plane:
  per-uplink update statistics, per-learner divergence scores, and
  per-round convergence snapshots, computed controller-side and
  surfaced through every plane above (opt-out via
  ``telemetry.health.enabled=false``; controller-local, so
  :func:`apply_config` has nothing process-global to arm for it).
- :mod:`metisfl_tpu.telemetry.sketch` + cardinality budgets in the
  metrics registry — past ``telemetry.cardinality_budget`` the
  per-learner families collapse to mergeable quantile digests and
  top-K heavy-hitter sketches, bounding exposition / status /
  checkpoints at O(budget) for 100k-client fleets
  (docs/OBSERVABILITY.md "Telemetry at scale").
- :mod:`metisfl_tpu.telemetry.alerts` — the SLO alerting plane:
  config-driven threshold / rate / digest-quantile rules with ``for:``
  holds and resolve hysteresis, evaluated over the bounded
  :mod:`metisfl_tpu.telemetry.timeseries` ring that also feeds the
  ``status --watch`` sparklines.
- ``python -m metisfl_tpu.telemetry <trace dir or .jsonl>`` renders a
  round's span tree from the sink; ``--postmortem`` renders the
  pre-crash timeline from bundles (including alerts at death);
  ``python -m metisfl_tpu.status`` live-watches a running federation
  over ``DescribeFederation``.

Everything is opt-out via federation config ``telemetry.enabled=false``
(:func:`apply_config`), and the event journal separately via
``telemetry.events.enabled=false``; the disabled paths are
attribute-check cheap.
"""

from __future__ import annotations

from metisfl_tpu.telemetry import (
    events,
    health,
    metrics,
    postmortem,
    sketch,
    timeseries,
    trace,
)
from metisfl_tpu.telemetry import alerts  # needs events/metrics/timeseries
from metisfl_tpu.telemetry.metrics import parse_exposition, registry
from metisfl_tpu.telemetry.trace import (
    METADATA_KEY,
    SpanContext,
    current_context,
    extract,
    outbound_metadata,
    span,
)

# --------------------------------------------------------------------- #
# Canonical metric series names. SURVEY.md §5.5 flags stringly-typed
# metric names as a reference defect (config/federation.py:16 cites it):
# every registration site and every scrape-side consumer imports these,
# so a typo fails at import time instead of silently minting a new
# series. The full catalog (types, labels, semantics) lives in
# docs/OBSERVABILITY.md "Metric names and labels".
# --------------------------------------------------------------------- #

# controller round lifecycle (controller/core.py)
M_ROUND_DURATION_SECONDS = "round_duration_seconds"
M_ROUNDS_TOTAL = "rounds_total"
M_ROUND_PHASE_DURATION_SECONDS = "round_phase_duration_seconds"
M_UPLINK_BYTES_TOTAL = "uplink_bytes_total"
M_CONTROLLER_ACTIVE_LEARNERS = "controller_active_learners"
M_AGGREGATION_FAILURES_TOTAL = "aggregation_failures_total"
M_LEARNER_STRAGGLER_SCORE = "learner_straggler_score"
# churn-tolerant scheduling (controller/core.py + selection.py)
M_LEARNER_DROPPED_TOTAL = "learner_dropped_total"
M_DISPATCH_RETRIES_TOTAL = "dispatch_retries_total"
M_ROUNDS_REDISPATCHED_TOTAL = "rounds_redispatched_total"
M_LEARNER_CHURN_SCORE = "learner_churn_score"
# learning-health plane (controller/core.py + telemetry/health.py)
M_LEARNER_DIVERGENCE_SCORE = "learner_divergence_score"
M_ROUND_UPDATE_NORM = "round_update_norm"
# causal tracing plane (telemetry/causal.py + telemetry/fabric.py)
M_ROUND_CRITICAL_PATH_SECONDS = "round_critical_path_seconds"
# performance observatory (telemetry/profile.py + controller/core.py)
M_DOWNLINK_BYTES_TOTAL = "downlink_bytes_total"
M_CODEC_LEARNER_SECONDS = "codec_learner_seconds_total"
M_LEARNER_ACHIEVED_MFU = "learner_achieved_mfu"
M_LEARNER_STEP_MS_EWMA = "learner_step_ms_ewma"
M_LEARNER_HBM_PEAK_BYTES = "learner_hbm_peak_bytes"
# learner runtime (learner/learner.py)
M_LEARNER_TRAIN_DURATION_SECONDS = "learner_train_duration_seconds"
M_LEARNER_STEP_MILLISECONDS = "learner_step_milliseconds"
M_LEARNER_TASKS_TOTAL = "learner_tasks_total"
M_LEARNER_EVAL_DURATION_SECONDS = "learner_eval_duration_seconds"
M_LEARNER_REATTACH_TOTAL = "learner_reattach_total"
# RPC transport (comm/rpc.py)
M_RPC_PEER_BYTES_TOTAL = "rpc_peer_bytes_total"
M_RPC_CLIENT_CALLS_TOTAL = "rpc_client_calls_total"
M_RPC_CLIENT_LATENCY_SECONDS = "rpc_client_latency_seconds"
M_RPC_CLIENT_BYTES_TOTAL = "rpc_client_bytes_total"
M_RPC_CLIENT_ERRORS_TOTAL = "rpc_client_errors_total"
M_RPC_SERVER_CALLS_TOTAL = "rpc_server_calls_total"
M_RPC_SERVER_LATENCY_SECONDS = "rpc_server_latency_seconds"
M_RPC_SERVER_BYTES_TOTAL = "rpc_server_bytes_total"
M_RPC_SERVER_ERRORS_TOTAL = "rpc_server_errors_total"
# wire codec (comm/codec.py)
M_CODEC_DURATION_SECONDS = "codec_duration_seconds"
M_CODEC_BYTES_TOTAL = "codec_bytes_total"
# model store cache (store/cached.py)
M_STORE_CACHE_HITS_TOTAL = "store_cache_hits_total"
M_STORE_CACHE_MISSES_TOTAL = "store_cache_misses_total"
M_STORE_CACHE_RESIDENT_BYTES = "store_cache_resident_bytes"
M_STORE_CACHE_ENTRIES = "store_cache_entries"
# integrity framing (tensor/pytree.py)
M_CORRUPT_PAYLOADS_TOTAL = "corrupt_payloads_total"
# chaos injector (chaos/injector.py)
M_CHAOS_FAULTS_INJECTED_TOTAL = "chaos_faults_injected_total"
# driver failover supervision (driver/session.py)
M_CONTROLLER_RESTARTS_TOTAL = "controller_restarts_total"
M_GATEWAY_RESTARTS_TOTAL = "gateway_restarts_total"
# controller hot-standby (controller/wal.py + __main__.py --standby)
M_CONTROLLER_WAL_RECORDS_TOTAL = "controller_wal_records_total"
M_CONTROLLER_WAL_LAG_RECORDS = "controller_wal_lag_records"
M_CONTROLLER_FAILOVER_TOTAL = "controller_failover_total"
M_CONTROLLER_FAILOVER_PROMOTE_SECONDS = "controller_failover_promote_seconds"
# model registry (registry/registry.py)
M_REGISTRY_VERSIONS_TOTAL = "registry_versions_total"
M_REGISTRY_VERSION_STATE = "registry_version_state"
M_REGISTRY_PROMOTIONS_TOTAL = "registry_promotions_total"
M_REGISTRY_ROLLBACKS_TOTAL = "registry_rollbacks_total"
# telemetry-at-scale plane (telemetry/metrics.py cardinality budgets +
# telemetry/alerts.py; docs/OBSERVABILITY.md "Telemetry at scale")
M_METRICS_SERIES_OVERFLOW_TOTAL = metrics.SERIES_OVERFLOW_TOTAL
M_METRICS_FAMILY_SERIES = metrics.FAMILY_SERIES
M_ALERTS_ACTIVE = alerts.ALERTS_ACTIVE
M_ALERTS_FIRED_TOTAL = alerts.ALERTS_FIRED_TOTAL
# continuous profiling plane (telemetry/prof.py sampler + lock wrappers)
M_PROF_SAMPLES_TOTAL = "prof_samples_total"
M_LOCK_WAIT_SECONDS = "lock_wait_seconds"
M_LOCK_CONTENTION_TOTAL = "lock_contention_total"
# accelerator runtime observability (telemetry/runtime.py)
M_JAX_COMPILES_TOTAL = "jax_compiles_total"
M_JAX_COMPILE_SECONDS = "jax_compile_seconds"
M_JAX_DEVICE_MEMORY_BYTES = "jax_device_memory_bytes"
# fleet telemetry fabric (telemetry/fabric.py FleetCollector)
M_FABRIC_COLLECTIONS_TOTAL = "fabric_collections_total"
M_FABRIC_PEER_OFFSET_MS = "fabric_peer_clock_offset_ms"
M_FABRIC_COLLECT_SECONDS = "fabric_collect_duration_seconds"
# distributed slice aggregators (aggregation/slice.py + distributed.py)
M_SLICE_UPLINKS_TOTAL = "slice_uplinks_total"
M_SLICE_HELD_MODELS = "slice_held_models"
M_SLICE_FAILURES_TOTAL = "slice_failures_total"
M_SLICE_REHOMING_SECONDS = "slice_rehoming_seconds"
# masked partial-fold plane (secure/distributed.py + recovery.py)
M_SECURE_MASKED_UPLINKS_TOTAL = "secure_masked_uplinks_total"
M_SECURE_MASKED_FOLDS_TOTAL = "secure_masked_folds_total"
M_SECURE_SETTLEMENT_SECONDS = "secure_settlement_seconds"
M_SECURE_RECOVERED_PARTIES_TOTAL = "secure_recovered_parties_total"
M_SECURE_MASK_GEN_SECONDS = "secure_mask_gen_seconds"
# serving gateway (serving/gateway.py)
M_SERVING_REQUESTS_TOTAL = "serving_requests_total"
M_SERVING_REQUEST_LATENCY_SECONDS = "serving_request_latency_seconds"
M_SERVING_BATCH_ROWS = "serving_batch_rows"
M_SERVING_MODEL_VERSION = "serving_model_version"
M_SERVING_SWAPS_TOTAL = "serving_swaps_total"
M_SERVING_QUEUE_DEPTH = "serving_queue_depth"
# continuous-batching decode (serving/decode.py)
M_SERVING_DECODE_QUEUE_DEPTH = "serving_decode_queue_depth"
M_SERVING_DECODE_ACTIVE_SLOTS = "serving_decode_active_slots"
M_SERVING_DECODE_TOKENS_TOTAL = "serving_decode_tokens_total"
M_SERVING_DECODE_CACHE_BYTES = "serving_decode_cache_bytes"
# serving fleet: router + autoscaler (serving/fleet.py + driver/session.py)
M_ROUTER_REQUESTS_TOTAL = "serving_router_requests_total"
M_ROUTER_RETRIES_TOTAL = "serving_router_retries_total"
M_ROUTER_REQUEST_LATENCY_SECONDS = "serving_router_request_latency_seconds"
M_SERVING_REPLICA_UP = "serving_replica_up"
M_SERVING_FLEET_REPLICAS = "serving_fleet_replicas"
M_SERVING_SCALE_TOTAL = "serving_scale_total"

__all__ = [
    "metrics",
    "trace",
    "events",
    "health",
    "postmortem",
    "alerts",
    "sketch",
    "timeseries",
    "registry",
    "prune_learner",
    "parse_exposition",
    "span",
    "current_context",
    "extract",
    "outbound_metadata",
    "SpanContext",
    "METADATA_KEY",
    "apply_config",
    "render_metrics",
] + [name for name in dir() if name.startswith("M_")]


def render_metrics() -> str:
    """The process registry's Prometheus exposition (GetMetrics RPC body)."""
    return registry().render()


def prune_learner(learner_id: str) -> None:
    """Drop every per-learner metric series for a departed learner, in
    ONE place: all registry families registered with a cardinality
    label (``budget_label`` — the "learner"/"peer" families) plus the
    codec/RPC attribution state that backs them. The straggler /
    divergence / churn / profile planes used to hand-prune their own
    gauges on ``leave()``; they all call (or are covered by) this
    helper now, and the drift-guard test in tests/test_scaletel.py
    asserts no ``M_*`` per-learner family leaks a series after a
    join→leave cycle."""
    registry().prune_label_value(learner_id)
    # codec encode/decode process totals + any per-peer RPC byte state —
    # non-series attribution that would re-mint series if left behind
    from metisfl_tpu.telemetry import profile as _profile

    _profile.prune_attribution_series(learner_id)


def apply_config(telemetry_config, service: str = "",
                 config_hash: str = "") -> None:
    """Configure process-wide telemetry from a federation config's
    ``telemetry`` section (config/federation.py TelemetryConfig): one call
    in each process entry point (controller/learner ``__main__``,
    in-process federation, tests). ``config_hash`` stamps post-mortem
    bundles so incidents from different configs are tellable apart."""
    enabled = bool(getattr(telemetry_config, "enabled", True))
    metrics.set_enabled(enabled)
    # cardinality budget (docs/OBSERVABILITY.md "Telemetry at scale"):
    # 0 (default) keeps every per-learner family exact — today's
    # behavior, bit-identical exposition
    registry().set_cardinality_budget(
        int(getattr(telemetry_config, "cardinality_budget", 0) or 0))
    sink_dir = getattr(telemetry_config, "dir", "")
    ev_cfg = getattr(telemetry_config, "events", None)
    ev_enabled = enabled and bool(getattr(ev_cfg, "enabled", True))
    events.configure(enabled=ev_enabled, service=service,
                     dir=sink_dir if ev_enabled else "",
                     ring_size=int(getattr(ev_cfg, "ring_size", 0) or 0))
    if enabled:
        trace.configure(enabled=True, service=service, dir=sink_dir)
    else:
        # disable without forgetting any previously configured sink dir:
        # a later re-enable (set_enabled / a default-enabled config in
        # the same process) restores it
        trace.set_enabled(False)
    pm_dir = getattr(telemetry_config, "postmortem_dir", "")
    if enabled and pm_dir:
        postmortem.configure(pm_dir, service=service,
                             config_hash=config_hash)
    # fleet telemetry fabric (telemetry/fabric.py): arm the process
    # exporter + the finished-span ring, and mint a fresh epoch so
    # collectors treat this configuration as a new incarnation
    fab_cfg = getattr(telemetry_config, "fabric", None)
    fabric.configure(
        enabled=enabled and bool(getattr(fab_cfg, "enabled", True)),
        span_ring=int(getattr(fab_cfg, "span_ring", 0) or 0))
    # continuous profiling plane (telemetry/prof.py): arm (or stop) the
    # stack sampler and flip the instrumented-lock factories — hot locks
    # constructed after this call adopt the configured mode
    prof_cfg = getattr(telemetry_config, "prof", None)
    prof.configure(
        enabled=enabled and bool(getattr(prof_cfg, "enabled", True)),
        hz=float(getattr(prof_cfg, "hz", 0.0) or 0.0),
        budget=int(getattr(prof_cfg, "budget", 0) or 0))
    # accelerator runtime observability (telemetry/runtime.py): arm the
    # XLA compile listener + memory accounting; the service name picks
    # the memory-attribution plane (controller / learner / serving)
    rt_cfg = getattr(telemetry_config, "runtime", None)
    runtime.set_plane(service)
    runtime.configure(
        enabled=enabled and bool(getattr(rt_cfg, "enabled", True)),
        budget=int(getattr(rt_cfg, "budget", 0) or 0),
        mem_every_s=float(getattr(rt_cfg, "mem_every_s", 0.0) or 0.0),
        storm_window_s=float(
            getattr(rt_cfg, "storm_window_s", 0.0) or 0.0),
        storm_threshold=int(
            getattr(rt_cfg, "storm_threshold", 0) or 0))


# Imported at the BOTTOM so profile.py (which reads the M_* constants at
# its own import time) sees a fully-initialized package — the other
# submodules import nothing back from this package. fabric imports only
# sibling submodules at module level (its RPC client is lazy), so the
# same late import keeps the comm <-> telemetry layering acyclic. prof
# loads FIRST: fabric, profile, and runtime all reference it; runtime
# loads before fabric (fabric's CollectTelemetry serves its section).
from metisfl_tpu.telemetry import prof  # noqa: E402
from metisfl_tpu.telemetry import runtime  # noqa: E402
from metisfl_tpu.telemetry import fabric, profile  # noqa: E402

__all__ += ["profile", "fabric", "prof", "runtime"]
