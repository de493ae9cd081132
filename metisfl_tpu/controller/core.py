"""Federation controller core.

The orchestration state machine: learner registry, task lifecycle, model
store, aggregation driver, round-metadata lineage. Capability equivalent of
the reference's C++ ``Controller``/``ControllerDefaultImpl``
(reference metisfl/controller/core/controller.cc: AddLearner :98-168,
RemoveLearner :170-199, LearnerCompletedTask :201-259, ScheduleTasks
:428-518, UpdateLearnersTaskTemplates :520-569, ComputeCommunityModel
:795-950), redesigned:

- Models are flat ``{name: np.ndarray}`` dicts controller-side (no byte-blob
  per-variable arithmetic); aggregation is one jit-compiled XLA computation.
- Concurrency: RPC threads only enqueue; a single-worker scheduling executor
  owns all round logic, so a learner's completion ack never blocks on
  aggregation (the reference pushes ScheduleTasks onto a thread pool for the
  same reason, controller.cc:246-255) and state needs one lock, not two.
- Transport is pluggable (:class:`LearnerProxy`): in-process calls for tests
  and pod-mode, gRPC for cross-host federations.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os
import random
import resource
import threading
import time
import uuid
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from functools import partial
from typing import (Any, Callable, Dict, List, Optional, Protocol, Sequence,
                    Tuple)

import numpy as np

from metisfl_tpu.aggregation import make_aggregation_rule
from metisfl_tpu.aggregation.base import host_fold_backend
from metisfl_tpu.aggregation.secure import SecureAgg
from metisfl_tpu.comm.codec import dumps_segments as codec_dumps_segments
from metisfl_tpu.comm.codec import loads as codec_loads
from metisfl_tpu.comm.messages import (
    EvalResult,
    EvalTask,
    JoinReply,
    JoinRequest,
    TaskResult,
    TrainParams,
    TrainTask,
)
from metisfl_tpu.config import FederationConfig
from metisfl_tpu.scaling import (apply_staleness_decay, make_scaler,
                                 raw_weight, staleness_factor)
from metisfl_tpu.scheduling import SemiSynchronousScheduler, make_scheduler
from metisfl_tpu.selection import ChurnTracker, make_selector
from metisfl_tpu.store import EvictionPolicy, make_store
from metisfl_tpu.store import durable as _durable
from metisfl_tpu import telemetry as _tel
from metisfl_tpu.telemetry import events as _tevents
from metisfl_tpu.telemetry import metrics as _tmetrics
from metisfl_tpu.telemetry import prof as _tprof
from metisfl_tpu.telemetry import profile as _tprofile
from metisfl_tpu.telemetry import trace as _ttrace
from metisfl_tpu.telemetry.health import HealthMonitor, finite_metrics
from metisfl_tpu.tensor.pytree import ModelBlob
from metisfl_tpu.tensor.spec import quantify

logger = logging.getLogger("metisfl_tpu.controller")

# Round-lifecycle metrics: scraped live via GetMetrics / the /metrics
# listener while the lineage equivalents (RoundMetadata) stay post-hoc.
# Names come from the shared constants in telemetry/__init__.py — a typo
# fails at import instead of minting a new series (SURVEY.md §5.5).
_REG = _tmetrics.registry()
_M_ROUND_DURATION = _REG.histogram(
    _tel.M_ROUND_DURATION_SECONDS, "Federation round wall-clock")
_M_ROUNDS = _REG.counter(_tel.M_ROUNDS_TOTAL, "Completed federation rounds")
_M_PHASE = _REG.histogram(
    _tel.M_ROUND_PHASE_DURATION_SECONDS,
    "Per-phase round durations (dispatch/wait_uplinks/select/aggregate/"
    "aggregate_block/store_insert/close)", ("phase",))
_M_UPLINK = _REG.counter(
    _tel.M_UPLINK_BYTES_TOTAL, "Model bytes received from learners",
    ("learner",), budget_label="learner")
_M_ACTIVE_LEARNERS = _REG.gauge(
    _tel.M_CONTROLLER_ACTIVE_LEARNERS, "Currently registered learners")
_M_AGG_FAILURES = _REG.counter(
    _tel.M_AGGREGATION_FAILURES_TOTAL, "Aggregation attempts that raised")
_M_STRAGGLER = _REG.gauge(
    _tel.M_LEARNER_STRAGGLER_SCORE,
    "Round-relative straggler score: EWMA train duration over the "
    "cohort median (1.0 = typical, >1 = slower)", ("learner",),
    budget_label="learner")
_M_DIVERGENCE = _REG.gauge(
    _tel.M_LEARNER_DIVERGENCE_SCORE,
    "Learning-health divergence score: EWMA of the cohort-median/MAD "
    "robust z of each update's deviation from the cohort mean "
    "(0 = typical, higher = pulling against the cohort)", ("learner",),
    budget_label="learner")
_M_ROUND_UPDATE_NORM = _REG.gauge(
    _tel.M_ROUND_UPDATE_NORM,
    "L2 norm of the latest community-model update (telemetry/health.py)")
# churn-tolerant scheduling (quorum barriers, dispatch retry, admission)
_M_DROPPED = _REG.counter(
    _tel.M_LEARNER_DROPPED_TOTAL,
    "Learner contributions dropped from rounds, by cause "
    "(deadline straggler, quorum straggler, leave, quarantine)",
    ("reason",))
_M_DISPATCH_RETRIES = _REG.counter(
    _tel.M_DISPATCH_RETRIES_TOTAL,
    "Failed train dispatches retried to replacement learners "
    "(scheduling.dispatch_retries)")
_M_REDISPATCH = _REG.counter(
    _tel.M_ROUNDS_REDISPATCHED_TOTAL,
    "Rounds abandoned and re-dispatched to a fresh cohort (no-reporter "
    "deadline, whole-cohort departure, aggregation-failure retry)")
_M_CHURN = _REG.gauge(
    _tel.M_LEARNER_CHURN_SCORE,
    "Churn/flap score: EWMA of leave, flap-rejoin, and failed-dispatch "
    "events (0 = stable, approaching 1 = flapping; selection.py "
    "ChurnTracker)", ("learner",), budget_label="learner")
_M_WAL_RECORDS = _REG.counter(
    _tel.M_CONTROLLER_WAL_RECORDS_TOTAL,
    "Hot-standby round-state WAL records appended, by kind "
    "(snapshot/join/leave; controller/wal.py)", ("kind",))
# masked partial-fold plane (secure/distributed.py + secure/recovery.py)
_M_SECURE_SETTLEMENT = _REG.histogram(
    _tel.M_SECURE_SETTLEMENT_SECONDS,
    "Mask settlement duration: contributor reconciliation through "
    "residual disclosure and fixed-point decode")
_M_SECURE_RECOVERED = _REG.counter(
    _tel.M_SECURE_RECOVERED_PARTIES_TOTAL,
    "Dropped mask parties recovered via seed-share disclosure")
_M_SECURE_FOLDS = _REG.counter(
    _tel.M_SECURE_MASKED_FOLDS_TOTAL,
    "Masked partial folds performed, by tier", ("tier",))

# EWMA smoothing for per-learner train/eval durations (straggler
# analytics): ~the last 3-4 rounds dominate, so a recovered learner's
# score decays within a few rounds instead of dragging forever
_EWMA_ALPHA = 0.3


def _ewma(prev: float, observation: float) -> float:
    """First observation seeds the average; later ones alpha-blend."""
    if prev <= 0.0:
        return observation
    return _EWMA_ALPHA * observation + (1.0 - _EWMA_ALPHA) * prev


class LearnerProxy(Protocol):
    """Controller → learner transport for one registered learner."""

    def run_task(self, task: TrainTask) -> None:
        """Fire-and-forget local-training dispatch."""
        ...

    def evaluate(self, task: EvalTask, callback: Callable[[EvalResult], None]) -> None:
        """Non-blocking evaluation; ``callback`` runs on completion."""
        ...

    def shutdown(self) -> None:
        ...


@dataclass
class LearnerRecord:
    learner_id: str
    auth_token: str
    hostname: str = "localhost"
    port: int = 0
    num_train_examples: int = 0
    num_val_examples: int = 0
    num_test_examples: int = 0
    # latest task execution metadata (feeds scalers + semi-sync recompute)
    completed_batches: int = 0
    ms_per_step: float = 0.0
    # consecutive failed train dispatches (liveness; reset on completion)
    dispatch_failures: int = 0
    # round the latest accepted contribution was DISPATCHED from (async
    # staleness: a result computed against an old community model)
    last_result_round: int = -1
    # masking secure-agg party index (-1: not a masking party) — maps this
    # learner to its pairwise-mask identity for dropout recovery
    party_index: int = -1
    # per-learner train overrides (semi-sync step budgets)
    local_steps_override: int = 0
    # EWMA dispatch→completion durations (straggler analytics; feeds the
    # DescribeFederation snapshot and learner_straggler_score)
    ewma_train_s: float = 0.0
    ewma_eval_s: float = 0.0
    proxy: Optional[LearnerProxy] = None


@dataclass
class RoundMetadata:
    """Per-round runtime trace — the reference's FederatedTaskRuntimeMetadata
    (metis.proto:342-365) rebuilt as a plain record."""

    global_iteration: int = 0
    started_at: float = 0.0
    completed_at: float = 0.0
    train_submitted_at: Dict[str, float] = field(default_factory=dict)
    train_received_at: Dict[str, float] = field(default_factory=dict)
    eval_submitted_at: Dict[str, float] = field(default_factory=dict)
    eval_received_at: Dict[str, float] = field(default_factory=dict)
    selected_learners: List[str] = field(default_factory=list)
    aggregation_block_sizes: List[int] = field(default_factory=list)
    aggregation_block_duration_ms: List[float] = field(default_factory=list)
    aggregation_duration_ms: float = 0.0
    # which implementation folds host-resident trees in this controller
    # ("native" = native/hostfold.cc, "numpy" = its build failed here)
    host_fold: str = ""
    # phase breakdown sourced from the round's telemetry spans (trace and
    # lineage agree by construction): total train-dispatch time and the
    # dispatch-to-barrier-release wait. Absent in pre-telemetry payloads —
    # stats.py renders those unchanged.
    dispatch_duration_ms: float = 0.0
    wait_duration_ms: float = 0.0
    # the contribution weights actually applied this round (post scaler and
    # staleness damping) — reference lineage has nothing comparable
    scales: Dict[str, float] = field(default_factory=dict)
    # per-uplink dispatch-version lag at aggregation time (rounds the
    # community model advanced between a task's dispatch and its uplink
    # entering this aggregate) — nonzero only under the asynchronous
    # protocols / quorum stragglers; zero entries are omitted so silo
    # runs' lineage is unchanged
    staleness: Dict[str, float] = field(default_factory=dict)
    model_insertion_duration_ms: Dict[str, float] = field(default_factory=dict)
    model_size: Dict[str, int] = field(default_factory=dict)
    # bytes each learner actually sent this round (the wire-compression
    # ladder — ship_dtype bf16/int8q/topk — shows up here as 2-32x
    # smaller uplinks; the reference tracks only decoded tensor sizes)
    uplink_bytes: Dict[str, int] = field(default_factory=dict)
    peak_rss_kb: int = 0
    # per-learner training metrics as shipped in TaskResult: the final
    # train_metrics dict and the per-epoch trajectory. Previously
    # collected on the wire but dropped controller-side — now they land
    # in experiment.json so stats.py can render per-learner convergence
    # (absent in pre-health payloads; readers fall back gracefully).
    train_metrics: Dict[str, Dict[str, float]] = field(default_factory=dict)
    epoch_metrics: Dict[str, List[Dict[str, float]]] = field(
        default_factory=dict)
    # learning-health snapshot for this round (telemetry/health.py):
    # community update norm, effective step, participation entropy,
    # per-learner update norms / cohort cosines / divergence scores.
    # Empty when telemetry.health is off or under secure aggregation.
    health: Dict[str, Any] = field(default_factory=dict)
    # model-lifecycle lineage (registry/registry.py): the candidate
    # version this round's aggregate registered as, and the stable head
    # at round close. 0 when the registry is off — pre-registry payloads
    # lack the keys entirely and stats.py renders them unchanged.
    registered_version: int = 0
    stable_version: int = 0
    # per-round cost profile (telemetry/profile.py RoundProfile): phase
    # waterfall, per-learner wire-byte/codec/device attribution, store
    # timings. Empty when the performance observatory is off — pre-profile
    # payloads lack the key and stats.py renders them unchanged.
    profile: Dict[str, Any] = field(default_factory=dict)
    # cardinality-budget snapshot (telemetry/metrics.py): per collapsed
    # per-learner family, the round-close quantiles / top offenders /
    # distinct-series count. Empty below budget (and with the budget
    # off) — pre-budget payloads lack the key and render unchanged.
    metrics_digest: Dict[str, Any] = field(default_factory=dict)
    # non-fatal round errors (e.g. partial-cohort secure aggregation after a
    # deadline) — surfaced in lineage instead of vanishing into a log line
    errors: List[str] = field(default_factory=list)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


class Controller:
    """See module docstring. Lifecycle: ``start()`` → learners ``join()`` →
    rounds run event-driven off ``task_completed()`` → ``shutdown()``."""

    def __init__(self, config: FederationConfig,
                 proxy_factory: Callable[[LearnerRecord], LearnerProxy],
                 secure_backend=None):
        self.config = config
        self._proxy_factory = proxy_factory
        # the registry lock — every uplink, join/leave, and round close
        # serializes here, which makes it THE contention site to watch:
        # instrumented by the continuous profiler (telemetry/prof.py;
        # with telemetry.prof.enabled=false this is a raw RLock)
        self._lock = _tprof.rlock("controller.registry")
        self._learners: Dict[str, LearnerRecord] = {}
        self._tokens: Dict[str, str] = {}
        # Controller incarnation id, minted fresh per process (never
        # restored from a checkpoint — the whole point is that a restart
        # CHANGES it). Rides in JoinReply and every task envelope so
        # learners detect a controller crash+restart and re-attach.
        self.controller_epoch = uuid.uuid4().hex

        agg = config.aggregation
        if config.secure.enabled:
            if secure_backend is None:
                raise ValueError("secure aggregation enabled but no backend given")
            self._aggregator = SecureAgg(secure_backend)
        elif agg.rule.lower() in ("fedavgm", "fedadam", "fedyogi"):
            # normalized like make_aggregation_rule, so a mixed-case rule
            # string cannot silently drop the server hyperparameters
            self._aggregator = make_aggregation_rule(
                agg.rule, learning_rate=agg.server_learning_rate,
                beta1=agg.server_beta1, beta2=agg.server_beta2,
                tau=agg.server_tau)
        elif agg.rule.lower() == "trimmed_mean":
            self._aggregator = make_aggregation_rule(
                agg.rule, trim_ratio=agg.trim_ratio)
        elif agg.rule.lower() in ("krum", "multikrum"):
            self._aggregator = make_aggregation_rule(
                agg.rule, byzantine_f=agg.byzantine_f)
        else:
            self._aggregator = make_aggregation_rule(agg.rule)
        self._scaler = make_scaler(agg.scaler)
        # SCAFFOLD server control variate c (name -> f32 array) and the
        # cohort's latest unconsumed control deltas (learner_id -> blob)
        self._scaffold_c: Optional[Dict[str, np.ndarray]] = None
        self._scaffold_c_blob: Optional[bytes] = None   # pack cache
        self._scaffold_deltas: Dict[str, bytes] = {}
        self._selector = make_selector("scheduled_cardinality")
        sched_cfg = config.scheduling
        if config.protocol == "semi_synchronous":
            self._scheduler = make_scheduler(
                "semi_synchronous", lambda_=config.semi_sync_lambda,
                recompute_every_round=config.semi_sync_recompute_every_round,
                quorum=sched_cfg.quorum)
        elif config.protocol == "asynchronous_buffered":
            self._scheduler = make_scheduler(
                "asynchronous_buffered", buffer_size=sched_cfg.buffer_size)
        elif config.protocol == "synchronous":
            self._scheduler = make_scheduler("synchronous",
                                             quorum=sched_cfg.quorum)
        else:
            self._scheduler = make_scheduler(config.protocol)
        # quorum barrier (scheduling.quorum): 0 = full-cohort barrier —
        # every quorum hot path below is then one attribute check, and
        # round behavior is bit-identical to the plain synchronous path
        self._quorum = (sched_cfg.quorum
                        if config.protocol in ("synchronous",
                                               "semi_synchronous") else 0)
        # churn-aware admission (selection.py ChurnTracker): per-learner
        # churn/flap scores + optional quarantine. None when opted out —
        # every membership path then costs one attribute check.
        self._churn: Optional[ChurnTracker] = None
        if sched_cfg.churn_tracking:
            self._churn = ChurnTracker(
                alpha=sched_cfg.churn_alpha,
                quarantine_score=sched_cfg.quarantine_score,
                quarantine_s=sched_cfg.quarantine_s)

        store_cfg = config.model_store
        lineage = store_cfg.lineage_length or self._aggregator.required_lineage
        lineage = max(lineage, self._aggregator.required_lineage)
        store_kwargs = {"lineage_length": lineage}
        if store_cfg.store in ("disk", "cached_disk"):
            store_kwargs["root"] = store_cfg.root or "/tmp/metisfl_tpu_store"
        if store_cfg.store == "cached_disk":
            store_kwargs["cache_bytes"] = store_cfg.cache_mb << 20
        if store_cfg.store == "remote":
            store_kwargs["host"] = store_cfg.host
            store_kwargs["port"] = store_cfg.port
        self._store = make_store(store_cfg.store, **store_kwargs)

        # Cohort-scale ingest plane (docs/SCALE.md). All three are None
        # when opted out — every hot path then costs one attribute check.
        # (a) parallel store ingest: completions enqueue, a bounded
        # writer pool persists, aggregation fences on drain
        self._ingest = None
        ingest_workers = int(getattr(store_cfg, "ingest_workers", 0) or 0)
        if ingest_workers > 0:
            from metisfl_tpu.store.ingest import IngestPipeline
            # accept: the worker re-checks membership right before the
            # write, so a queued write racing leave() cannot land after
            # the erase and resurrect the pruned lineage
            self._ingest = IngestPipeline(
                self._store, ingest_workers,
                on_insert=self._note_ingest_insert,
                accept=self.is_member)
        # (b) streaming aggregation: fold accepted uplinks on arrival —
        # no store round-trip — for the weighted-sum rules; unsupported
        # rule/protocol/lineage combinations fall back to the store path
        self._streaming = None
        if getattr(agg, "streaming", False):
            from metisfl_tpu.aggregation.streaming import (
                StreamingAggregator,
                streaming_supported,
            )
            if streaming_supported(self._aggregator.name, config.protocol,
                                   config.secure.enabled, lineage,
                                   self._aggregator.required_lineage,
                                   checkpointed=bool(config.checkpoint.dir),
                                   buffer_size=sched_cfg.buffer_size):
                self._streaming = StreamingAggregator(
                    self._aggregator, stride=agg.stride_length)
            else:
                logger.info(
                    "aggregation.streaming requested but rule=%s/"
                    "protocol=%s/lineage=%d/checkpointed=%s does not "
                    "support it; using the store path",
                    self._aggregator.name, config.protocol, lineage,
                    bool(config.checkpoint.dir))
        # (c) tree-aggregation tier: O(branch) fan-in for the store path
        self._tree = None
        tree_cfg = getattr(agg, "tree", None)
        if tree_cfg is not None and getattr(tree_cfg, "enabled", False):
            from metisfl_tpu.aggregation.tree import TreeReducer
            self._tree = TreeReducer(branch=tree_cfg.branch,
                                     workers=tree_cfg.workers)
        # (d) distributed slice-aggregation tier (aggregation/
        # distributed.py): the tree's branches as driver-booted slice
        # aggregator PROCESSES — uplinks forward to their slice over
        # gRPC, the root fans in O(branch) partials, and a dead
        # aggregator's slice re-homes mid-round. None when opted out (or
        # when the rule cannot slice-fold) — every hot path is then one
        # attribute check; with it armed the in-process tree above stays
        # constructed as the fully-degraded fallback.
        self._slices = None
        masked_tier = (config.secure.enabled
                       and config.secure.scheme == "masking")
        if (tree_cfg is not None and getattr(tree_cfg, "distributed", False)
                and getattr(tree_cfg, "slices", None)):
            if (self._aggregator.name in ("fedavg", "scaffold", "fedstride")
                    and not config.secure.enabled) or masked_tier:
                from metisfl_tpu.aggregation.distributed import (
                    DistributedSliceReducer,
                )
                # masked mode (secure/distributed.py): slices fold raw
                # masked blobs as modular uint64 sums — key-free, masks
                # cancel at the root settlement; with streaming they
                # additionally fold on arrival
                self._slices = DistributedSliceReducer(
                    tree_cfg, ssl=config.ssl, comm=config.comm,
                    masked=masked_tier,
                    stream=masked_tier and bool(getattr(agg, "streaming",
                                                        False)))
            else:
                logger.info(
                    "aggregation.tree.distributed requested but rule=%s "
                    "cannot slice-fold; using the in-process path",
                    self._aggregator.name)
        # (e) masked streaming (secure/distributed.py): under scheme:
        # masking with aggregation.streaming and NO slice tier, the
        # controller folds masked uplinks on arrival itself — modular
        # sums are exact and order-free, so the stream accumulates the
        # bits the store path's one-combine would. With slices armed the
        # fold-on-arrival happens slice-side instead (submit streams).
        self._masked_stream = None
        if (masked_tier and getattr(agg, "streaming", False)
                and self._slices is None):
            from metisfl_tpu.secure.distributed import (
                MaskedStreamingAggregator,
            )
            self._masked_stream = MaskedStreamingAggregator()

        # community model state
        self._community_flat: Optional[Dict[str, np.ndarray]] = None
        self._community_blob: Optional[bytes] = None
        self._community_opaque = None      # secure path
        # (full-width blob, narrowed bytes) — see _dispatch_blob
        self._downlink_cache: Optional[Tuple[bytes, bytes]] = None
        self.global_iteration = 0

        # lineage / statistics
        self.round_metadata: List[RoundMetadata] = []
        self.community_evaluations: List[Dict[str, Any]] = []
        self._current_meta = RoundMetadata(global_iteration=0)
        # telemetry: the open round span (root of the round's trace tree;
        # learner train spans parent under it via RPC metadata) and the
        # open dispatch→barrier-release wait span
        self._round_span = None
        self._wait_span = None

        # single-worker pool serializes all scheduling/aggregation work
        self._pool = ThreadPoolExecutor(max_workers=1,
                                        thread_name_prefix="ctrl-sched")
        self._shutdown = threading.Event()
        self._tasks_in_flight: Dict[str, str] = {}  # task_id -> learner_id
        # task_id -> dispatch wall-clock, maintained in lockstep with
        # _tasks_in_flight: DescribeFederation reports in-flight ages and
        # completions feed the per-learner EWMA train durations from it
        self._task_dispatched_at: Dict[str, float] = {}
        # coarse live phase for the status plane ("what is the controller
        # doing RIGHT NOW"): idle | dispatch | wait_uplinks | select |
        # aggregate | halted
        self._phase = "idle"
        # straggler-deadline state: each dispatch bumps the serial so a
        # deadline timer from a completed round never fires on the next one
        self._round_serial = 0
        self._deadline_timer: Optional[threading.Timer] = None
        self._expired_tasks: Dict[str, None] = {}  # ordered set of task_ids
        # consecutive aggregation failures (reset on success): distinguishes
        # transient partial-cohort failures from a deterministically broken
        # federation, which must halt instead of retraining forever
        self._agg_failures = 0
        # consecutive zero-reporter round deadlines (reset whenever a round
        # completes): scheduling.max_empty_redispatch bounds the re-dispatch
        # loop the deadline path would otherwise spin forever. The halt it
        # triggers is recoverable: _halted_no_reporters marks it so a later
        # delivered uplink resumes dispatch (scheduling-executor-only state)
        self._empty_deadlines = 0
        self._halted_no_reporters = False
        # dispatch-retry budget used this round (scheduling.dispatch_retries)
        # and the live backoff timers shutdown() must cancel
        self._dispatch_retries_used = 0
        self._retry_timers: Dict[object, None] = {}
        # round-scoped cache of the fleet's median observed train EWMA
        # (collapsed-straggler-gauge fast path: the median only moves
        # meaningfully at round granularity, so per-uplink O(fleet)
        # median recomputation is wasted work under the controller lock)
        self._straggler_median_cache: Optional[float] = None
        # guards against recursive checkpointing while restore itself
        # replays the community model through set_community_model
        self._in_restore = False
        # coalesces queued async checkpoint saves: N learners joining in
        # a burst (or re-attaching after a failover) must cost one
        # community-blob write on the scheduling executor, not N
        self._ckpt_queued = False

        # Hot-standby round-state WAL (controller/wal.py): registry
        # deltas land synchronously on the join/leave RPC path (before
        # the ack), full snapshots ride the same coalesced executor hook
        # as the checkpoint. None when no standby is configured — every
        # membership path then costs one attribute check.
        self._wal = None
        standby = config.controller.standby
        if standby.enabled and standby.wal_dir:
            from metisfl_tpu.controller.wal import RoundStateLog
            self._wal = RoundStateLog(standby.wal_dir)

        # Learning-health plane (telemetry/health.py): per-uplink update
        # statistics + per-learner divergence scores. None when opted
        # out or under secure aggregation (opaque payloads) — the uplink
        # hot path then costs exactly one attribute check.
        hc = getattr(config.telemetry, "health", None)
        self._health: Optional[HealthMonitor] = None
        if (config.telemetry.enabled and hc is not None
                and getattr(hc, "enabled", False)
                and not config.secure.enabled):
            self._health = HealthMonitor(
                alpha=hc.alpha, anomaly_threshold=hc.anomaly_threshold)
        self._health_advisory = bool(
            self._health is not None and getattr(hc, "advisory", False))

        # Performance observatory (telemetry/profile.py): per-round cost
        # profiles — phase waterfall, per-learner wire bytes + codec
        # attribution, store timings, device stats. None when opted out —
        # every hot-path hook is then one attribute check.
        pc = getattr(config.telemetry, "profile", None)
        self._profile: Optional[_tprofile.ProfileCollector] = None
        if (config.telemetry.enabled and pc is not None
                and getattr(pc, "enabled", False)):
            self._profile = _tprofile.ProfileCollector(
                pc, telemetry_dir=config.telemetry.dir,
                service="controller")
            # the flight recorder snapshots the active collector's tail
            # into crash bundles
            _tprofile.set_collector(self._profile)

        # Telemetry-at-scale plane (docs/OBSERVABILITY.md "Telemetry at
        # scale"): (a) cardinality budget — past it the per-learner
        # metric families serve sketches, DescribeFederation serves
        # digest columns, and the checkpoint persists digests instead of
        # per-learner series. 0 (default) keeps everything exact.
        self._cardinality_budget = 0
        if config.telemetry.enabled:
            self._cardinality_budget = int(
                getattr(config.telemetry, "cardinality_budget", 0) or 0)
            if self._cardinality_budget > 0:
                _REG.set_cardinality_budget(self._cardinality_budget)
        # (b) SLO alert engine (telemetry/alerts.py): None when no rules
        # are configured — the round-close hook is one attribute check.
        self._alerts = None
        alert_specs = getattr(config.telemetry, "alerts", None) or []
        if config.telemetry.enabled and alert_specs:
            from metisfl_tpu.telemetry import alerts as _talerts
            self._alerts = _talerts.AlertEngine(
                _talerts.validate_rules(alert_specs),
                registry=_REG,
                interval_s=getattr(config.telemetry, "alerts_interval_s",
                                   1.0))
            # the flight recorder snapshots the live engine's active
            # alerts into crash bundles ("alerts at death")
            _talerts.set_engine(self._alerts)
            self._alerts.start()

        # Model lifecycle plane (registry/registry.py): versioned
        # community-model lineage with eval-gated promotion. None when
        # opted out — the post-aggregation path then costs exactly one
        # attribute check (same posture as the health monitor above).
        self._registry = None
        rc = getattr(config, "registry", None)
        if rc is not None and getattr(rc, "enabled", False):
            import hashlib

            from metisfl_tpu.registry import ModelRegistry
            self._registry = ModelRegistry(
                rc, config_hash=hashlib.sha256(
                    config.to_wire()).hexdigest()[:16])

    # ------------------------------------------------------------------ #
    # lifecycle
    # ------------------------------------------------------------------ #

    def start(self) -> None:
        pass  # transport servers are owned by the service layer

    def shutdown(self) -> None:
        self._shutdown.set()
        with self._lock:
            if self._deadline_timer is not None:
                self._deadline_timer.cancel()
            # dispatch-retry backoff timers must not fire into the
            # torn-down pool either (their submit is guarded anyway,
            # but cancel keeps shutdown deterministic)
            for timer in list(self._retry_timers):
                timer.cancel()
            self._retry_timers.clear()
        self._pool.shutdown(wait=True)
        # A task that was already draining on the pool when the first
        # cancel ran may have re-armed the timer (complete-round →
        # dispatch → arm); _arm_round_deadline now refuses post-shutdown
        # arming, but cancel again for the window between the first
        # cancel and the shutdown flag propagating — no timer may outlive
        # shutdown() (it would fire into the torn-down pool).
        with self._lock:
            if self._deadline_timer is not None:
                self._deadline_timer.cancel()
        # the alert engine's evaluation daemon must not outlive the
        # controller (and its active-alert gauge series must prune so a
        # later in-process controller starts clean)
        if self._alerts is not None:
            from metisfl_tpu.telemetry import alerts as _talerts
            if _talerts.engine() is self._alerts:
                _talerts.set_engine(None)
            self._alerts.shutdown()
        # ingest workers write INTO the store: stop them (bounded drain)
        # before the store's own shutdown
        if self._ingest is not None:
            self._ingest.shutdown()
        self._store.shutdown()
        if self._tree is not None:
            self._tree.shutdown()
        if self._slices is not None:
            # clients close; the processes themselves are driver-owned
            # (the driver ShutDowns + reaps them like learners)
            self._slices.shutdown()
        if self._registry is not None:
            self._registry.shutdown()
        # Deregister the process-global collector handle if it is still
        # ours: a later controller in the same process (the in-process
        # test/driver pattern) with the profile plane off must see None —
        # otherwise its RPC layer would keep minting per-learner
        # attribution series into this dead collector.
        if self._profile is not None:
            if _tprofile.collector() is self._profile:
                _tprofile.set_collector(None)
            self._profile.close()

    # ------------------------------------------------------------------ #
    # membership (RPC thread)
    # ------------------------------------------------------------------ #

    def join(self, request: JoinRequest) -> JoinReply:
        """Register (or re-register) a learner; schedules its initial task.

        Mirrors AddLearner (controller.cc:98-168) + the rejoin path the
        reference drives through ALREADY_EXISTS (grpc_controller_client.py:96-107).
        """
        with self._lock:
            if (request.previous_id
                    and request.previous_id in self._learners
                    and self._tokens.get(request.previous_id) == request.auth_token):
                record = self._learners[request.previous_id]
                record.hostname, record.port = request.hostname, request.port
                record.proxy = self._proxy_factory(record)
                record.dispatch_failures = 0  # fresh endpoint, assume live
                logger.info("learner %s rejoined", record.learner_id)
                _tevents.emit(_tevents.LearnerJoined,
                              learner_id=record.learner_id,
                              hostname=record.hostname, port=record.port,
                              rejoined=True)
                self._note_churn(record.learner_id, "flap_rejoin")
                # Re-dispatch the current community model so a crash-restarted
                # learner rejoins the in-flight round instead of idling until
                # the next dispatch (the reference leaves the sync round
                # stalled after a crash — SURVEY.md §5.3).
                if not self._shutdown.is_set():
                    self._pool.submit(self._guard, self._schedule_initial,
                                      record.learner_id)
                self._wal_join(record)
                self._checkpoint_async()
                return JoinReply(learner_id=record.learner_id,
                                 auth_token=record.auth_token, rejoined=True,
                                 controller_epoch=self.controller_epoch)
            # Endpoint-keyed rejoin: a credential-less join from a
            # host:port already in the registry is the same learner
            # reincarnated without its token (crash that lost the creds
            # file, or a registry restored from a controller checkpoint
            # that the learner never knew about). One process owns one
            # endpoint, so registering a SECOND id for it would leave a
            # ghost in the barrier and double-dispatch the endpoint; the
            # reference's ALREADY_EXISTS rejoin is endpoint-keyed for the
            # same reason (grpc_controller_client.py:96-107). The token
            # rotates — the stale one stops validating. Trust model: join
            # is open, so endpoint reclamation grants nothing an attacker
            # could not get by registering fresh — admission control is
            # the transport's job (TLS + network ACLs, docs/RESILIENCE.md).
            if request.port:
                match = next(
                    (r for r in self._learners.values()
                     if r.hostname == request.hostname
                     and r.port == request.port), None)
                if match is not None:
                    token = uuid.uuid4().hex
                    match.auth_token = token
                    self._tokens[match.learner_id] = token
                    match.num_train_examples = request.num_train_examples
                    match.num_val_examples = request.num_val_examples
                    match.num_test_examples = request.num_test_examples
                    match.party_index = int(
                        request.capabilities.get("party_index",
                                                 match.party_index))
                    match.proxy = self._proxy_factory(match)
                    match.dispatch_failures = 0
                    logger.info("learner %s re-registered from its endpoint "
                                "%s:%d (token rotated)", match.learner_id,
                                request.hostname, request.port)
                    _tevents.emit(_tevents.LearnerJoined,
                                  learner_id=match.learner_id,
                                  hostname=match.hostname, port=match.port,
                                  rejoined=True)
                    self._note_churn(match.learner_id, "flap_rejoin")
                    if not self._shutdown.is_set():
                        self._pool.submit(self._guard, self._schedule_initial,
                                          match.learner_id)
                    self._wal_join(match)
                    self._checkpoint_async()
                    return JoinReply(learner_id=match.learner_id,
                                     auth_token=token, rejoined=True,
                                     controller_epoch=self.controller_epoch)
            learner_id = f"L{len(self._tokens)}_{request.hostname}_{request.port}"
            token = uuid.uuid4().hex
            record = LearnerRecord(
                learner_id=learner_id, auth_token=token,
                hostname=request.hostname, port=request.port,
                num_train_examples=request.num_train_examples,
                num_val_examples=request.num_val_examples,
                num_test_examples=request.num_test_examples,
                party_index=int(request.capabilities.get("party_index", -1)),
            )
            record.proxy = self._proxy_factory(record)
            self._learners[learner_id] = record
            self._tokens[learner_id] = token
            _M_ACTIVE_LEARNERS.set(len(self._learners))
        logger.info("learner %s joined (%d train examples)",
                    learner_id, request.num_train_examples)
        _tevents.emit(_tevents.LearnerJoined, learner_id=learner_id,
                      hostname=request.hostname, port=request.port)
        # Control handoff exactly like controller.cc:163-164: initial task is
        # scheduled off the join path.
        if not self._shutdown.is_set():
            self._pool.submit(self._guard, self._schedule_initial, learner_id)
        # registry durability: a controller crash between here and the next
        # round checkpoint must not forget this learner's identity/token
        self._wal_join(record)
        self._checkpoint_async()
        return JoinReply(learner_id=learner_id, auth_token=token,
                         controller_epoch=self.controller_epoch)

    def leave(self, learner_id: str, auth_token: str) -> bool:
        """RemoveLearner (controller.cc:170-199): drop registry + models."""
        with self._lock:
            record = self._learners.get(learner_id)
            if record is None or record.auth_token != auth_token:
                return False
            proxy = record.proxy
            del self._learners[learner_id]
            _M_ACTIVE_LEARNERS.set(len(self._learners))
            # a departed learner's tasks can never complete: without this
            # prune (and with no round deadline configured) they would sit
            # in the in-flight map forever, and DescribeFederation would
            # report ghost tasks with ever-growing ages
            for tid in [t for t, lid in self._tasks_in_flight.items()
                        if lid == learner_id]:
                self._tasks_in_flight.pop(tid, None)
                self._task_dispatched_at.pop(tid, None)
        # the standby must forget this learner too, before the ack — a
        # promoted registry resurrecting a departed learner would ghost
        # the barrier exactly like the duplicate-id case join() guards
        self._wal_leave(learner_id)
        # bounded metric cardinality under churn: a departed learner's
        # per-learner series (uplink bytes, straggler AND divergence
        # scores) must not accumulate for the process lifetime. Detach
        # the proxy's peer label FIRST: an in-flight RPC's completion
        # callback firing after the prune would otherwise re-mint the
        # peer wire-byte series for the process lifetime.
        if proxy is not None and hasattr(proxy, "detach_peer"):
            proxy.detach_peer()
        self._prune_learner_series(learner_id)
        # drain the departing learner's queued ingest writes BEFORE the
        # erase — a write landing after the prune would resurrect the
        # lineage (and its attribution series) for the process lifetime
        if self._ingest is not None:
            if not self._ingest.drain(learner_id, timeout=30.0):
                # a wedged writer: proceed with the erase — the queued
                # write cannot resurrect the lineage, the worker's
                # membership gate drops it (store/ingest.py accept)
                logger.error("ingest drain for departing %s timed out; "
                             "its queued writes will be gate-dropped",
                             learner_id)
        self._store.erase([learner_id])
        if self._slices is not None:
            # prune the departed learner's held model from its slice
            # owner + the root residual (best-effort: a dead owner's copy
            # dies with it, and the fold path skips departed ids anyway)
            self._slices.forget(learner_id)
        if self._streaming is not None and not self._shutdown.is_set():
            # subtract the departed learner's streamed contribution on
            # the scheduling executor (fold state is single-threaded)
            self._pool.submit(self._guard, self._streaming.forget,
                              learner_id)
        logger.info("learner %s left", learner_id)
        _tevents.emit(_tevents.LearnerLost, learner_id=learner_id)
        _M_DROPPED.inc(reason="leave")
        # churn memory deliberately SURVIVES the leave (a flapper's
        # history is the signal); only the gauge series is pruned above
        self._note_churn(learner_id, "leave")
        # Re-evaluate the round barrier: if the departed learner was the last
        # pending one, no completion event would ever release the round.
        if not self._shutdown.is_set():
            self._pool.submit(self._guard, self._handle_membership_change)
        return True

    def _prune_learner_series(self, learner_id: str) -> None:
        """Drop every per-learner gauge/counter series and plane state
        for a learner that left or was replaced — long-churn runs must
        not accumulate stale labels in the exposition. The series prune
        itself is ONE central call (telemetry.prune_learner covers every
        family registered with a learner/peer cardinality label, plus
        the codec/RPC attribution state behind them — the drift guard in
        tests/test_scaletel.py keeps future per-learner families from
        escaping it); the planes only drop their own non-series state."""
        _tel.prune_learner(learner_id)
        if self._health is not None:
            self._health.drop(learner_id)
        if self._profile is not None:
            # per-learner byte/insert/device attribution inside the
            # collector (its gauge series are already pruned above)
            self._profile.drop(learner_id)

    def _note_churn(self, learner_id: str, event: str) -> None:
        """Fold one membership event into the learner's churn/flap score
        (selection.py ChurnTracker) and surface it: gauge (membership-
        gated under the registry lock, same prune-race posture as the
        straggler gauge), quarantine event + drop counter when the score
        newly crosses the threshold. One attribute check when the churn
        plane is off."""
        if self._churn is None:
            return
        was_quarantined = self._churn.quarantined(learner_id)
        score = self._churn.note(learner_id, event)
        with self._lock:
            if learner_id in self._learners:
                _M_CHURN.set(round(score, 4), learner=learner_id)
        if not was_quarantined and self._churn.quarantined(learner_id):
            _M_DROPPED.inc(reason="quarantine")
            _tevents.emit(_tevents.LearnerQuarantined,
                          learner_id=learner_id, score=round(score, 4),
                          until_s=self._churn.quarantine_s)
            logger.warning(
                "learner %s quarantined for %.1fs (churn score %.2f >= "
                "%.2f after %s)", learner_id, self._churn.quarantine_s,
                score, self._churn.quarantine_score, event)

    def active_learners(self) -> List[str]:
        with self._lock:
            return list(self._learners.keys())

    def is_member(self, learner_id: str) -> bool:
        """Cheap membership probe (RPC threads gate per-learner metric
        attribution on it so departed learners' series stay pruned)."""
        with self._lock:
            return learner_id in self._learners

    def attribute_decode(self, learner_id: str, seconds: float) -> None:
        """Codec decode attribution under the registry lock: leave()
        deletes the record under this lock and prunes the series only
        afterwards, so an attribution recorded here either precedes the
        prune (erased with it) or sees the learner gone — it can never
        resurrect a pruned series."""
        from metisfl_tpu.comm import codec as _codec

        with self._lock:
            if learner_id in self._learners:
                _codec.attribute(learner_id, "decode", seconds)

    def learner_endpoints(self) -> List[Dict[str, Any]]:
        """Registered endpoints with the ports learners reported on join."""
        with self._lock:
            return [
                {"learner_id": r.learner_id, "hostname": r.hostname,
                 "port": r.port}
                for r in self._learners.values()
            ]

    # ------------------------------------------------------------------ #
    # community model management (RPC thread)
    # ------------------------------------------------------------------ #

    def set_community_model(self, blob_bytes: bytes) -> None:
        """ReplaceCommunityModel (controller.cc:85-96): seed or overwrite.

        Under ship_tensor_regex the controller is subset-resident from the
        seed on: the frozen base never occupies controller memory, store,
        checkpoints, or any wire hop — a full-model seed (the usual driver
        flow) is filtered down immediately and re-encoded, so round-1
        dispatch is already adapter-sized."""
        blob = ModelBlob.from_bytes(blob_bytes)
        ship_regex = self.config.train.ship_tensor_regex
        if ship_regex and blob.tensors:
            import re

            subset = [(n, a) for n, a in blob.tensors
                      if re.search(ship_regex, n)]
            if not subset:
                raise ValueError(
                    f"ship_tensor_regex {ship_regex!r} matches no tensor "
                    "in the seeded model — nothing would ever federate")
            if len(subset) != len(blob.tensors):
                blob = ModelBlob(tensors=subset)
                blob_bytes = blob.to_bytes()
        with self._lock:
            self._community_blob = bytes(blob_bytes)
            if blob.tensors:
                self._community_flat = dict(blob.tensors)
                if hasattr(self._aggregator, "seed_community"):
                    # server-opt rules step FROM the seeded model (a mid-run
                    # replacement intentionally re-anchors the optimizer)
                    self._aggregator.seed_community(self._community_flat)
            if blob.opaque:
                self._community_opaque = dict(blob.opaque)
        if self._health is not None and blob.tensors:
            # anchor the health plane's delta reference at the seeded
            # model (round/effective-step norms measure from here)
            self._health.note_community(dict(blob.tensors))
        # Checkpoint the freshly seeded/replaced model immediately: the
        # per-round auto-checkpoint only starts after round 1 completes,
        # so a controller crash during round 1 would otherwise restore to
        # a model-less state a failover restart cannot train from.
        self._checkpoint_async()

    def _wal_join(self, record: LearnerRecord) -> None:
        """Append the learner's full registry entry to the hot-standby
        WAL on the join path, BEFORE the JoinReply ack returns: a
        learner the primary acked must exist in a promoted standby's
        registry as ITSELF (same id, token, party index). Append
        failures are logged, not raised — a disk hiccup must not reject
        the join (the checkpoint save's best-effort posture)."""
        if self._wal is None:
            return
        from metisfl_tpu.controller import wal as _walmod
        try:
            self._wal.append(_walmod.JOIN, self._learner_entry(record))
            _M_WAL_RECORDS.inc(kind="join")
        except Exception:  # noqa: BLE001 - best-effort durability
            logger.exception("WAL join append for %s failed",
                             record.learner_id)

    def _wal_leave(self, learner_id: str) -> None:
        """Append a leave delta before the leave ack (see _wal_join)."""
        if self._wal is None:
            return
        from metisfl_tpu.controller import wal as _walmod
        try:
            self._wal.append(_walmod.LEAVE, {"learner_id": learner_id})
            _M_WAL_RECORDS.inc(kind="leave")
        except Exception:  # noqa: BLE001 - best-effort durability
            logger.exception("WAL leave append for %s failed", learner_id)

    def _checkpoint_async(self) -> None:
        """Queue a round-state save onto the scheduling executor (off
        the RPC path; serialized with round logic): the on-disk
        checkpoint when checkpoint.dir is set, a WAL snapshot when a
        standby is configured — both from ONE state capture. Coalescing:
        while a save is already queued, further requests are no-ops —
        the queued save snapshots state at RUN time, so it covers them.
        No-op when neither sink is armed, during restore, or at
        shutdown."""
        if ((not self.config.checkpoint.dir and self._wal is None)
                or self._in_restore or self._shutdown.is_set()):
            return
        with self._lock:
            if self._ckpt_queued:
                return
            self._ckpt_queued = True

        def _save():
            with self._lock:
                self._ckpt_queued = False
            try:
                state = self._checkpoint_state()
                if self.config.checkpoint.dir:
                    self.save_checkpoint(state=state)
                if self._wal is not None:
                    self._wal.snapshot(state)
                    _M_WAL_RECORDS.inc(kind="snapshot")
            except Exception:  # noqa: BLE001 - best-effort durability
                logger.exception("round-state save failed")

        try:
            self._pool.submit(self._guard, _save)
        except RuntimeError:  # pool already shut down
            with self._lock:
                self._ckpt_queued = False

    def community_model_bytes(self) -> Optional[bytes]:
        with self._lock:
            return self._community_blob

    # ------------------------------------------------------------------ #
    # task completion (RPC thread → scheduling executor)
    # ------------------------------------------------------------------ #

    def task_completed(self, result: TaskResult) -> bool:
        """MarkTaskCompleted (controller.cc:201-259). Returns ack; all heavy
        work happens on the scheduling executor."""
        if self._shutdown.is_set():
            return False
        with self._lock:
            record = self._learners.get(result.learner_id)
            if record is None:
                logger.warning("completion from unknown learner %s",
                               result.learner_id)
                return False
            # Validate the (learner_id, auth_token) composite key before
            # accepting a model (the reference's ValidateLearner on
            # MarkTaskCompleted, controller.cc:205, controller.proto:146-148)
            # — without it any client could poison the community model.
            if record.auth_token != result.auth_token:
                logger.warning("completion from %s with bad auth token",
                               result.learner_id)
                return False
        self._pool.submit(self._guard, self._handle_completed, result)
        return True

    # ------------------------------------------------------------------ #
    # scheduling executor internals
    # ------------------------------------------------------------------ #

    # consecutive aggregation failures tolerated before halting re-dispatch
    _MAX_AGG_FAILURES = 10

    def _guard(self, fn, *args) -> None:
        try:
            fn(*args)
        except Exception:  # pragma: no cover - logged, never kills the pool
            logger.exception("controller executor task failed")

    def _schedule_initial(self, learner_id: str) -> None:
        if self._shutdown.is_set():
            return
        with self._lock:
            record = self._learners.get(learner_id)
        if record is None:
            return
        self._dispatch_train([learner_id], restart_deadline=False)

    def _handle_completed(self, result: TaskResult) -> None:
        start = time.time()
        with self._lock:
            record = self._learners.get(result.learner_id)
            if record is None:
                return
            record.dispatch_failures = 0  # provably reachable
            if result.control_delta:
                self._scaffold_deltas[result.learner_id] = result.control_delta
            if result.processing_ms_per_step > 0:
                record.ms_per_step = result.processing_ms_per_step
            self._tasks_in_flight.pop(result.task_id, None)
            dispatched_at = self._task_dispatched_at.pop(result.task_id, 0.0)
            if dispatched_at:
                # EWMA dispatch→completion duration (straggler analytics).
                # Expired-task completions count too — a straggler's late
                # arrival is exactly the observation the score needs.
                record.ewma_train_s = _ewma(record.ewma_train_s,
                                            max(0.0, start - dispatched_at))
            # A completion for a task the deadline already expired: keep the
            # model (fresh data for later rounds) but do not advance the
            # current round's barrier — and keep its timings out of the
            # current round's metadata (it belongs to an abandoned round).
            # Same verdict for an uplink dispatched by ANOTHER controller
            # incarnation (hot-standby promotion / --resume relaunch):
            # the restored controller re-dispatched that round itself, so
            # folding the dead incarnation's copy too would double-count
            # it — and shift every later round's bits off the same-seed
            # undisturbed run (the chaos gate's bit-identity pin).
            stale = (result.task_id in self._expired_tasks
                     or bool(result.controller_epoch
                             and result.controller_epoch
                             != self.controller_epoch))
            self._expired_tasks.pop(result.task_id, None)
            if not stale:
                self._current_meta.train_received_at[result.learner_id] = start
                self._current_meta.uplink_bytes[result.learner_id] = \
                    len(result.model)
            # under the lock: leave() deletes the record under this lock
            # and prunes the series after — an unlocked inc here could
            # interleave and resurrect a departed learner's series
            _M_UPLINK.inc(len(result.model), learner=result.learner_id)
            if self._profile is not None:
                if result.device_stats:
                    # learner-shipped device utilization (step EWMA, MFU,
                    # HBM watermark) → per-learner gauges + the round
                    # profile
                    self._profile.note_device(result.learner_id,
                                              result.device_stats)
                if result.task_tiles and not stale:
                    # the learner's own task waterfall → the round
                    # profile (a stale one belongs to an abandoned round)
                    self._profile.note_task(result.learner_id,
                                            result.task_tiles)
        _tevents.emit(_tevents.TaskCompleted, task_id=result.task_id,
                      learner_id=result.learner_id, round=result.round_id,
                      stale=stale, uplink_bytes=len(result.model))
        self._update_straggler_gauge(completed=result.learner_id)
        # a delivered uplink is the churn score's decay tick: a learner
        # that reports steadily recovers from past flaps within a few
        # rounds (same recovery posture as the straggler EWMA)
        self._note_churn(result.learner_id, "completion")

        if stale and self._topk_uplink():
            # a topk payload is a delta against the community model AT
            # DISPATCH; the deadline path has since advanced it, so the
            # reconstruction reference is gone — storing the densification
            # would poison any later aggregation that selects it
            logger.info("late topk completion from %s for expired task %s "
                        "dropped (reconstruction reference advanced)",
                        result.learner_id, result.task_id)
            return
        try:
            model = self._parse_result_model(result)
        except ValueError as exc:
            # A malformed payload (bad sparse indices, missing companions,
            # codec garbage) must cost its OWN contribution, not the round:
            # the learner already got its ack and the task left
            # _tasks_in_flight, so raising here would stall a sync barrier
            # forever (no deadline by default). Drop the model, keep the
            # barrier moving; aggregation proceeds with whatever lineage
            # exists for this learner.
            logger.warning("dropping malformed result from %s for task %s: "
                           "%s", result.learner_id, result.task_id, exc)
            with self._lock:
                self._current_meta.errors.append(
                    f"malformed result from {result.learner_id}: {exc}")
            model = None
        deferred_meta = False
        if model is not None and self._masked_stream is not None:
            # masked streaming (secure/distributed.py): the raw masked
            # blob folds on arrival as a modular uint64 sum. Stale
            # uplinks carry dead masks (streams are round-keyed) and
            # must NEVER enter a live sum — drop them like the plain
            # streaming path drops round-scoped stragglers.
            folded = False
            if not stale and isinstance(model, (bytes, bytearray)):
                try:
                    opaque = dict(ModelBlob.from_bytes(model).opaque)
                    folded = bool(opaque) and self._masked_stream.fold(
                        result.learner_id, opaque, result.round_id)
                except ValueError as exc:
                    logger.warning("undecodable masked uplink from %s: %s",
                                   result.learner_id, exc)
            if folded:
                _M_SECURE_FOLDS.inc(tier="stream")
            else:
                logger.info("masked uplink from %s dropped (stale or "
                            "malformed; masks are round-keyed)",
                            result.learner_id)
            model = None if not folded else model
        elif model is not None and self._streaming is not None:
            # streaming aggregation (docs/SCALE.md): the accepted uplink
            # folds straight into the community accumulator — the store
            # round-trip is skipped entirely. A dropped fold (stale on a
            # round-scoped rule, opaque payload) contributes nothing,
            # exactly like a malformed payload on the store path.
            if not self._stream_fold(result, model, stale):
                model = None
        elif model is not None and self._slices is not None:
            # distributed slice tier (aggregation/distributed.py): the
            # accepted uplink forwards to its slice aggregator over gRPC
            # — the root never stores it, so controller memory and store
            # traffic stay O(branch). submit() never raises and never
            # drops an accepted uplink: an unreachable owner re-homes
            # (bounded retry/backoff) and the fold-of-last-resort is the
            # root's residual buffer.
            # parent on the uplink's server span when one is active (the
            # causal chain: learner train → uplink RPC → slice submit),
            # falling back to the round root for in-process deliveries
            fwd_sp = _ttrace.span(
                "round.slice_submit",
                parent=_ttrace.current_context() or self._round_span,
                attrs={"learner": result.learner_id})
            with fwd_sp, fwd_sp.activate():
                self._slices.submit(result.learner_id, model,
                                    result.round_id)
            _M_PHASE.observe(fwd_sp.duration_ms / 1e3, phase="slice_submit")
        elif model is not None:
            if self._ingest is not None:
                # parallel ingest: enqueue and return — the writer pool
                # records the ACTUAL write time via _note_ingest_insert
                # (no store_insert sample from this thread: no double
                # count), and aggregation fences on drain before select.
                # The result metadata is applied by on_success ONLY when
                # the write lands: a fail-soft write failure must not
                # pair fresh step counts with the older stored model.
                self._ingest.submit(
                    result.learner_id, model,
                    on_success=partial(self._ingest_landed, result))
                deferred_meta = True
            else:
                insert_sp = _ttrace.span(
                    "round.store_insert",
                    parent=_ttrace.current_context() or self._round_span,
                    attrs={"learner": result.learner_id})
                with insert_sp:
                    self._store.insert(result.learner_id, model)
                _M_PHASE.observe(insert_sp.duration_ms / 1e3,
                                 phase="store_insert")
                if self._profile is not None:
                    self._profile.note_store_insert(result.learner_id,
                                                    insert_sp.duration_ms)
        if model is not None:
            if not deferred_meta:
                with self._lock:
                    # step count and result round pair with the STORED
                    # (or streamed) model: dropped payloads (late topk,
                    # malformed, stale-on-streaming) must not refresh
                    # them, or FedNova's τ / the batches scaler /
                    # staleness decay would weight the older stored model
                    # with metadata from a different task (the ingest
                    # path applies them in _ingest_landed, write-fenced)
                    record.completed_batches = result.completed_batches
                    record.last_result_round = result.round_id
            if self._health is not None and isinstance(model, dict) and model:
                # learning-health statistics for this uplink (host numpy,
                # read-only — the stored model is untouched). Reference is
                # the live community model: under sync/semi-sync exactly
                # what the task trained from; a late/async uplink measures
                # against wherever the federation has moved since, which
                # is the divergence that matters for the NEXT aggregation.
                # The dict is safe to read un-copied: community updates
                # REPLACE _community_flat, they never mutate it in place.
                with self._lock:
                    reference = self._community_flat or {}
                try:
                    self._health.observe_update(
                        result.learner_id, model, reference,
                        train_metrics=result.train_metrics)
                except Exception:  # noqa: BLE001 - telemetry never fatal
                    logger.exception("health statistics failed for %s",
                                     result.learner_id)
        if not stale:
            with self._lock:
                self._current_meta.model_insertion_duration_ms[result.learner_id] = (
                    (time.time() - start) * 1e3)
                # surface the shipped training metrics into the round's
                # lineage (experiment.json) — previously dropped on the
                # controller floor (ISSUE 4 satellite). Values that are
                # not finite floats are skipped: a zero-step task ships
                # loss=NaN (strict JSON parsers reject NaN tokens), and
                # a raising conversion here would swallow schedule_next
                # via _guard and stall the sync barrier forever — the
                # wire never validates these learner-shipped dicts
                if result.train_metrics:
                    finite = finite_metrics(result.train_metrics)
                    if finite:
                        self._current_meta.train_metrics[
                            result.learner_id] = finite
                if result.epoch_metrics and isinstance(
                        result.epoch_metrics, (list, tuple)):
                    self._current_meta.epoch_metrics[result.learner_id] = [
                        finite_metrics(epoch)
                        for epoch in result.epoch_metrics]
        if self._halted_no_reporters:
            # the no-reporter halt is recoverable by evidence of life: a
            # delivered uplink (stale or not — every in-flight task was
            # expired at the halt) proves the federation is reachable
            # again, so resume dispatch with a fresh sample. The model
            # above was already stored/streamed like any other.
            self._halted_no_reporters = False
            self._empty_deadlines = 0
            logger.warning("completion from %s after no-reporter halt; "
                           "resuming dispatch", result.learner_id)
            self._scheduler.reset()
            if self._streaming is not None:
                self._streaming.abandon()
            if self._masked_stream is not None:
                self._masked_stream.abandon()
            self._dispatch_train(self._sample_cohort())
            return
        if stale:
            logger.info("late completion from %s for expired task %s stored "
                        "but not scheduled", result.learner_id, result.task_id)
            return

        to_schedule = self._scheduler.schedule_next(
            result.learner_id, self.active_learners())
        if not to_schedule:
            if getattr(self._scheduler, "redispatch_on_completion", False):
                # buffered async (FedBuff): the reporter never idles on
                # the buffer barrier — it trains against the current
                # community model while the buffer keeps filling
                self._dispatch_train([result.learner_id],
                                     restart_deadline=False)
            return
        if self._quorum > 0:
            # quorum release: tasks still in flight belong to the round
            # that just closed — expire them so their late completions
            # are stored (fresh lineage) but never advance the NEXT
            # round's barrier (exactly the deadline path's semantics)
            self._expire_unreported(to_schedule)
        self._complete_round(to_schedule)

    def _handle_membership_change(self) -> None:
        active = self.active_learners()
        if not active or self._shutdown.is_set():
            return
        cohort = self._scheduler.handle_leave(active)
        if cohort:
            if self._quorum > 0:
                self._expire_unreported(cohort)
            self._complete_round(cohort)
            return
        if self._scheduler.round_stalled(active):
            # every dispatched learner departed before the round could
            # complete: abandon it and dispatch a fresh sample so the
            # surviving learners keep making progress
            logger.info("round abandoned (dispatched cohort left); re-dispatching")
            _M_REDISPATCH.inc()
            self._scheduler.reset()
            if self._streaming is not None:
                self._streaming.abandon()
            if self._masked_stream is not None:
                self._masked_stream.abandon()
            self._dispatch_train(self._sample_cohort())

    def _expire_tasks_locked(self, pending: Dict[str, str]) -> None:
        """Move ``pending`` (task_id -> learner_id) to the bounded expired
        set and prune dispatch stamps down to tasks a completion can
        still reference (in-flight or expired — the EWMA pop needs
        them). ONE definition for the quorum and deadline triggers, so
        their bookkeeping can never diverge. Call with ``self._lock``
        held."""
        for tid in pending:
            self._tasks_in_flight.pop(tid, None)
        self._expired_tasks.update(dict.fromkeys(pending))
        while len(self._expired_tasks) > 512:
            self._expired_tasks.pop(next(iter(self._expired_tasks)))
        keep = set(self._tasks_in_flight) | set(self._expired_tasks)
        self._task_dispatched_at = {
            tid: t for tid, t in self._task_dispatched_at.items()
            if tid in keep}

    def _expire_unreported(self, cohort: Sequence[str]) -> None:
        """Quorum release (scheduling.quorum): the releasing cohort is the
        first K reporters — every task still in flight to a learner
        outside it belongs to the round that just closed. Move those to
        the expired set so a straggler's late completion is stored (fresh
        lineage for later rounds) but never advances the next round's
        barrier — the same bookkeeping `_handle_deadline` does, with the
        quorum instead of the clock as the trigger."""
        cohort_set = set(cohort)
        with self._lock:
            pending = {tid: lid for tid, lid in self._tasks_in_flight.items()
                       if lid not in cohort_set}
            if not pending:
                return
            self._expire_tasks_locked(pending)
        dropped = sorted(set(pending.values()))
        _M_DROPPED.inc(len(dropped), reason="quorum")
        logger.info("quorum reached: expiring %d straggler task(s) from %s",
                    len(pending), dropped)

    # -- straggler deadline ----------------------------------------------

    def _arm_round_deadline(self, restart: bool = True) -> None:
        """Start (or restart) the per-round straggler timer after a dispatch.
        Only sync/semi-sync rounds have a barrier a straggler can stall.

        ``restart=False`` (join/rejoin single-learner dispatches) only arms
        when no timer is live — otherwise a crash-looping learner rejoining
        inside the deadline window would keep postponing it forever, and a
        mid-round join would silently extend the in-flight round's deadline.
        """
        deadline = self.config.round_deadline_secs
        if deadline <= 0 or self._scheduler.name == "asynchronous":
            return
        with self._lock:
            # shutdown() cancels the live timer under this lock; a round
            # task draining on the pool concurrently with shutdown must
            # not arm a replacement after that cancel (the regression
            # tests/test_failover.py pins: no timer outlives shutdown)
            if self._shutdown.is_set():
                return
            if (not restart and self._deadline_timer is not None
                    and self._deadline_timer.is_alive()):
                return
            # the serial advanced in _dispatch_train (every fresh round
            # dispatch, deadline configured or not) — capture, don't bump
            serial = self._round_serial
            if self._deadline_timer is not None:
                self._deadline_timer.cancel()

            def _fire():
                if self._shutdown.is_set():
                    return
                try:
                    self._pool.submit(self._guard, self._handle_deadline, serial)
                except RuntimeError:  # pool already shut down
                    pass

            timer = threading.Timer(deadline, _fire)
            timer.daemon = True
            self._deadline_timer = timer
            timer.start()

    def _handle_deadline(self, serial: int) -> None:
        """Round deadline expired: drop unreported learners from the barrier
        and proceed with whoever reported (or re-dispatch if nobody did)."""
        if self._shutdown.is_set():
            return
        with self._lock:
            if serial != self._round_serial:
                return  # round already completed; stale timer
            pending = dict(self._tasks_in_flight)
            self._expire_tasks_locked(pending)
        cohort = self._scheduler.expire_pending(self.active_learners())
        dropped = sorted(set(pending.values()))
        if dropped:
            _M_DROPPED.inc(len(dropped), reason="deadline")
        if cohort:
            logger.warning(
                "round deadline (%.1fs) expired; aggregating %d reporter(s), "
                "dropping stragglers %s", self.config.round_deadline_secs,
                len(cohort), dropped)
            # masking secure-agg recovers partial cohorts via the dropout
            # correction (_masking_dropout_correction); when recovery is
            # impossible (< min_recovery_parties survivors) aggregation
            # fails and _complete_round re-dispatches a fresh full cohort
            self._complete_round(cohort)
            if (getattr(self._scheduler, "redispatch_on_completion", False)
                    and dropped and not self._shutdown.is_set()):
                # buffered async: the post-aggregation dispatch only
                # covers buffer reporters — the expired (dropped)
                # learners are lost training concurrency and must be
                # re-dispatched or they idle for the rest of the run
                revive = self._idle_reporters(dropped)
                if revive:
                    self._dispatch_train(revive, restart_deadline=False)
        else:
            self._empty_deadlines += 1
            limit = self.config.scheduling.max_empty_redispatch
            if limit > 0 and self._empty_deadlines >= limit:
                # nothing has reported for `limit` consecutive deadline
                # windows: the federation is not making progress and
                # re-dispatching forever would never terminate — halt
                # with a clear lineage error (the driver's wall-clock
                # cutoff or an operator takes it from here; a learner
                # DELIVERING an uplink later resumes dispatch via the
                # _halted_no_reporters check in _handle_completed — all
                # the halted round's tasks were just expired, so the
                # resume trigger must be explicit, not the barrier)
                reason = (f"{self._empty_deadlines} consecutive round "
                          f"deadlines expired with no reporters "
                          f"(last dropped: {dropped})")
                logger.error("halting re-dispatch: %s", reason)
                self._halted_no_reporters = True
                with self._lock:
                    self._current_meta.errors.append(
                        f"round halted: {reason}")
                    round_sp, self._round_span = self._round_span, None
                    # close the wait span WITH its round: left open it
                    # would outlive its ended parent, and the first
                    # post-resume round would inherit it and book the
                    # whole halted idle period as wait_uplinks time
                    wait_sp, self._wait_span = self._wait_span, None
                    self._phase = "halted"
                _tevents.emit(_tevents.RoundHalted,
                              round=self.global_iteration, reason=reason)
                if wait_sp is not None:
                    wait_sp.end()
                    with self._lock:
                        self._current_meta.wait_duration_ms += \
                            wait_sp.duration_ms
                if round_sp is not None:
                    round_sp.set_attr("error", f"halted: {reason}")
                    round_sp.end()
                return
            logger.warning(
                "round deadline (%.1fs) expired with no reporters (%s); "
                "re-dispatching (%d/%s)", self.config.round_deadline_secs,
                dropped, self._empty_deadlines, limit or "unbounded")
            _M_REDISPATCH.inc()
            if self._streaming is not None:
                self._streaming.abandon()
            if self._masked_stream is not None:
                self._masked_stream.abandon()
            self._dispatch_train(self._sample_cohort())

    def _ingest_landed(self, result: TaskResult, ms: float) -> None:
        """Ingest-write success hook (runs on the writer, strictly before
        the drain fence covering the write can return): pair the result's
        step count and round with the NOW-stored model. A fail-soft write
        failure never reaches here, so the older stored model keeps its
        older metadata."""
        with self._lock:
            record = self._learners.get(result.learner_id)
            if record is None:
                return
            record.completed_batches = result.completed_batches
            record.last_result_round = result.round_id

    def _note_ingest_insert(self, learner_id: str, ms: float) -> None:
        """Ingest-worker write attribution: the phase histogram and the
        round profile record the worker's ACTUAL write duration (the
        completion handler only enqueued — it records nothing)."""
        _M_PHASE.observe(ms / 1e3, phase="store_insert")
        if self._profile is not None:
            # membership gate under the registry lock (same posture as
            # _M_UPLINK): leave() prunes the profile series strictly
            # after deleting the record, so a late worker write cannot
            # re-mint a departed learner's series
            with self._lock:
                if learner_id in self._learners:
                    self._profile.note_store_insert(learner_id, ms)

    def _stream_fold(self, result: TaskResult, model, stale: bool) -> bool:
        """Fold one accepted uplink into the streaming accumulator.
        Returns False when the contribution was dropped (stale on a
        round-scoped rule — the streaming path has no store to park a
        late model in; or a non-tree payload)."""
        if stale and self._streaming.rule_name != "fedrec":
            # fedavg/fedstride sums are round-scoped: the expired round
            # this model belongs to was already abandoned. (fedrec's
            # recency semantics WANT the late model — newest wins.)
            logger.info("late completion from %s dropped (streaming "
                        "path keeps no store lineage)", result.learner_id)
            return False
        if not isinstance(model, dict) or not model:
            return False
        with self._lock:
            record = self._learners.get(result.learner_id)
            if record is None:
                return False
            entry = {"num_train_examples": record.num_train_examples,
                     "completed_batches": result.completed_batches}
        # raw (unnormalized) weight — the cohort normalizer is unknown
        # until barrier release; finish() divides by z = Σw (docs/SCALE.md)
        weight = raw_weight(self.config.aggregation.scaler, entry)
        if weight <= 0.0:
            # the batch scalers would give this learner scale 0 (e.g.
            # completed_batches=0): accept the completion — the record
            # update below still pairs metadata with it — but fold
            # nothing, matching a scale-0 contribution on the store path
            return True
        decay = self.config.aggregation.staleness_decay
        if decay > 0.0:
            # dispatch-version lag, damped by the same kernel the batch
            # path applies (scaling.staleness_factor — one definition)
            staleness = max(0, self.global_iteration - result.round_id)
            weight *= staleness_factor(staleness, decay)
        t0 = time.perf_counter()
        self._streaming.fold(result.learner_id, model, weight)
        fold_ms = (time.perf_counter() - t0) * 1e3
        _M_PHASE.observe(fold_ms / 1e3, phase="stream_fold")
        if self._profile is not None:
            self._profile.note_phase("stream_fold", fold_ms)
        return True

    def _topk_uplink(self) -> bool:
        from metisfl_tpu.tensor.sparse import parse_topk

        return (not self.config.secure.enabled
                and parse_topk(self.config.train.ship_dtype) is not None)

    def _parse_result_model(self, result: TaskResult):
        blob = ModelBlob.from_bytes(result.model)
        if self.config.secure.enabled:
            # the opaque blob is stored as it came: a copy of its own,
            # never a view that would pin the whole request buffer
            return bytes(result.model) if blob.opaque else dict(blob.tensors)
        tensors = dict(blob.tensors)
        if self.config.train.ship_dtype.lower() == "int8q":
            # int8q uplink: restore exact f32 before storage/aggregation.
            # Gated on the CONFIG (not payload sniffing) so a model that
            # legitimately owns a '#qscale'-suffixed tensor cannot be
            # silently mangled when quantization is off.
            from metisfl_tpu.tensor.quantize import dequantize_named

            tensors = dequantize_named(tensors)
        else:
            from metisfl_tpu.tensor.sparse import densify_named, parse_topk

            if parse_topk(self.config.train.ship_dtype) is not None:
                # topk uplink: dense weights = dispatched community model
                # + scatter(sparse update). Valid because sync/semi-sync
                # (config-enforced) guarantees the community model has not
                # advanced since this task's dispatch. Same config gating
                # rationale as int8q above.
                with self._lock:
                    community = dict(self._community_flat or {})
                tensors = densify_named(tensors, community)
        return tensors

    def _complete_round(self, cohort: Sequence[str]) -> None:
        """One ScheduleTasks pass (controller.cc:428-518): select, aggregate,
        record metadata, evaluate, re-dispatch.

        Aggregation failure must never strand the federation: the error is
        recorded in round metadata and the round re-dispatches — async
        re-dispatches the reporters (so they are not left idle forever
        waiting for a completion ack that aborted), sync abandons the round
        and re-dispatches a fresh full cohort (mask streams are keyed on the
        round counter, which did not advance, so secure retries are clean).
        """
        # the round barrier just released: close the wait-for-uplinks span
        with self._lock:
            wait_sp, self._wait_span = self._wait_span, None
        if wait_sp is not None:
            wait_sp.end()
            _M_PHASE.observe(wait_sp.duration_ms / 1e3, phase="wait_uplinks")
            with self._lock:
                # accumulate like dispatch_duration_ms: an intra-round
                # aggregation-failure retry opens a second wait barrier
                # and both belong to this round's total
                self._current_meta.wait_duration_ms += wait_sp.duration_ms
        if self._profile is not None:
            self._profile.note_mark("wait_end")
        with self._lock:
            self._phase = "select"
        select_sp = _ttrace.span("round.select", parent=self._round_span,
                                 attrs={"cohort": len(cohort)})
        with select_sp:
            if self._health_advisory:
                # advisory only: the default selector records the scores
                # for operators/tests without changing its selection
                selected = self._selector.select(
                    cohort, self.active_learners(),
                    advisory_scores=self._health.scores())
            else:
                selected = self._selector.select(cohort,
                                                 self.active_learners())
        _M_PHASE.observe(select_sp.duration_ms / 1e3, phase="select")
        if self._profile is not None:
            self._profile.note_phase("select", select_sp.duration_ms)
            self._profile.note_mark("select_end")
        with self._lock:
            self._phase = "aggregate"
        try:
            self._compute_community_model(selected)
        except Exception as exc:
            _M_AGG_FAILURES.inc()
            self._agg_failures += 1
            if self._streaming is not None:
                # drop round-scoped fold state so the retry starts clean
                # (fedrec's cross-round rolling state survives)
                self._streaming.abandon()
            if self._masked_stream is not None:
                self._masked_stream.abandon()
            with self._lock:
                self._current_meta.errors.append(f"aggregation failed: {exc!r}")
            if self._agg_failures >= self._MAX_AGG_FAILURES:
                # deterministic breakage (version skew, corrupt payloads):
                # retraining forever would never terminate — halt dispatch
                # and leave the error trail; the driver's wall-clock cutoff
                # (or an operator) takes it from here
                logger.error(
                    "aggregation failed %d consecutive times (%r); halting "
                    "re-dispatch", self._agg_failures, exc)
                # flush the halted round's trace tree: the round span is
                # the root carrying the round attr, and the operator
                # debugging THIS round needs it in the sink
                with self._lock:
                    round_sp, self._round_span = self._round_span, None
                    self._phase = "halted"
                if round_sp is not None:
                    round_sp.set_attr("error", f"aggregation halted: {exc!r}")
                    round_sp.end()
                return
            logger.warning("aggregation failed (%r); re-dispatching", exc)
            if self._shutdown.is_set():
                return
            _M_REDISPATCH.inc()
            if self._scheduler.name.startswith("asynchronous"):
                self._dispatch_train(self._idle_reporters(cohort))
            else:
                self._scheduler.reset()
                self._dispatch_train(self._sample_cohort())
            return
        self._agg_failures = 0
        self._empty_deadlines = 0
        if self._slices is not None:
            # drop the root's residual fold buffer — its uplinks were
            # just folded (or superseded); the slice processes keep their
            # latest-per-learner models exactly like the store keeps
            # lineage across rounds
            self._slices.round_complete()
        if self._profile is not None:
            self._profile.note_mark("aggregate_end")
        with self._lock:
            agg_ms = self._current_meta.aggregation_duration_ms
        _tevents.emit(_tevents.AggregationDone,
                      round=self.global_iteration,
                      selected=len(selected), duration_ms=round(agg_ms, 3))
        # round close: everything between the aggregate landing and the
        # round counter advancing (health fold, version registration,
        # eval dispatch, lineage bookkeeping) — the last measured phase
        # of the cost-profile waterfall, and a real span in the trace
        close_sp = _ttrace.span("round.close", parent=self._round_span)
        self._fold_round_health()
        self._register_round_version()
        self._note_round_telemetry()
        self._send_eval_tasks()
        close_ms = close_sp.end()
        _M_PHASE.observe(close_ms / 1e3, phase="close")
        profile_record = None
        with self._lock:
            self.global_iteration += 1
            self._current_meta.completed_at = time.time()
            self._current_meta.peak_rss_kb = resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss
            round_wall_s = max(0.0, self._current_meta.completed_at
                               - self._current_meta.started_at)
            if self._profile is not None:
                # assemble under the lock (cheap dict building; the meta
                # object stays reachable through round_metadata, so a
                # concurrent to_dict must never race the write)
                profile_record = self._profile.assemble_round(
                    self._current_meta, close_ms=close_ms)
                self._current_meta.profile = profile_record
            self.round_metadata.append(self._current_meta)
            self._current_meta = RoundMetadata(
                global_iteration=self.global_iteration)
            # next round's uplinks re-derive the straggler median once
            self._straggler_median_cache = None
            round_sp, self._round_span = self._round_span, None
        if round_sp is not None:
            # end the round root BEFORE the critical-path walk: the walk
            # reads the finished-span ring, and the root must be in it
            round_sp.set_attr("learners", len(selected))
            round_sp.end()
        if profile_record is not None:
            # critical-path walk + JSONL sink write stay off the
            # controller lock
            self._profile.attach_critical_path(profile_record)
            self._profile.persist(profile_record)
        _M_ROUND_DURATION.observe(round_wall_s)
        _M_ROUNDS.inc()
        ckpt = self.config.checkpoint
        if ckpt.dir and self.global_iteration % max(1, ckpt.every_n_rounds) == 0:
            try:
                self.save_checkpoint()
            except Exception:
                logger.exception("checkpoint save failed")
        self._maybe_recompute_semisync()
        if self._shutdown.is_set():
            return
        if self._scheduler.name.startswith("asynchronous"):
            # async: re-dispatch only the reporting learner(s). Buffered
            # async re-dispatched most reporters the moment they uplinked
            # (redispatch_on_completion) — only the fill-triggering
            # reporter is still idle here, so filter out the busy ones
            # (plain async cohorts are never in flight at this point).
            next_ids = self._idle_reporters(cohort)
        else:
            next_ids = self._sample_cohort()
        self._dispatch_train(next_ids)

    def _note_round_telemetry(self) -> None:
        """Round-close hook for the telemetry-at-scale plane: one
        synchronous alert evaluation (round-paced even when the engine
        daemon lags behind a fast federation) and the collapsed metric
        families' digest snapshot into ``RoundMetadata.metrics_digest``.
        Two attribute checks when the plane is off; never raises
        (telemetry must not trip the aggregation-failure retry path)."""
        if self._alerts is not None:
            try:
                self._alerts.poll()
            except Exception:  # noqa: BLE001 - alerting never fails a round
                logger.exception("round-close alert poll failed")
        if self._cardinality_budget <= 0:
            return
        try:
            digest: Dict[str, Any] = {}
            for family in _REG.budget_families():
                summary = family.sketch_summary()
                if summary is not None:
                    digest[family.name] = summary
            if digest:
                with self._lock:
                    self._current_meta.metrics_digest = digest
        except Exception:  # noqa: BLE001 - telemetry never fails a round
            logger.exception("round-close metrics digest failed")

    def _idle_reporters(self, cohort: Sequence[str]) -> List[str]:
        """The cohort members that are active and NOT already carrying an
        in-flight task — the only ones an async-family re-dispatch may
        target (a double dispatch would cancel a training run mid-task)."""
        active = set(self.active_learners())
        with self._lock:
            busy = set(self._tasks_in_flight.values())
        return [lid for lid in cohort if lid in active and lid not in busy]

    def _admission_pool(self) -> List[str]:
        """Dispatchable learners: active, under the consecutive-dispatch-
        failure limit, and not churn-quarantined. Degrades instead of
        emptying — an all-dead / all-quarantined registry keeps trying
        rather than halting."""
        limit = self.config.max_dispatch_failures
        with self._lock:
            pool = [lid for lid, r in self._learners.items()
                    if limit <= 0 or r.dispatch_failures < limit]
            if not pool:
                # every learner looks dead: keep trying rather than halting
                pool = list(self._learners.keys())
        if self._churn is not None:
            quarantined = set(self._churn.quarantined_ids())
            if quarantined:
                healthy = [lid for lid in pool if lid not in quarantined]
                if healthy:  # never quarantine the whole federation
                    pool = healthy
        return pool

    def _sample_cohort(self) -> List[str]:
        """Sample next round's participants from reachable active learners
        (ControllerParams.participation_ratio). The scheduler barriers on the
        dispatched sample, so ratio < 1 cannot stall a synchronous round.

        Learners with ``max_dispatch_failures`` consecutive failed dispatches
        are skipped until they complete a task or rejoin — a dead endpoint
        must not keep re-entering sync barriers (SURVEY.md §5.3) — and
        churn-quarantined learners sit out until their window expires.

        With a quorum configured the dispatch is over-provisioned
        (Oort-style): ``ceil(quorum * (1 + overprovision))`` learners get
        tasks so the expected per-round dropout still leaves K reporters;
        ``participation_ratio`` is ignored in that mode (the quorum gives
        an absolute cohort size, the ratio a relative one)."""
        pool = self._admission_pool()
        if self._quorum > 0:
            k = math.ceil(self._quorum
                          * (1.0 + self.config.scheduling.overprovision))
            k = max(1, min(len(pool), k))
            if k >= len(pool):
                return pool
            return random.sample(pool, k)
        ratio = self.config.aggregation.participation_ratio
        if ratio >= 1.0 or not pool:
            return pool
        k = max(1, int(round(ratio * len(pool))))
        return random.sample(pool, k)

    def _maybe_recompute_semisync(self) -> None:
        if not isinstance(self._scheduler, SemiSynchronousScheduler):
            return
        batch = self.config.train.batch_size
        with self._lock:
            timings = {
                lid: {
                    "ms_per_step": r.ms_per_step,
                    "steps_per_epoch": max(1.0, r.num_train_examples / max(1, batch)),
                }
                for lid, r in self._learners.items()
            }
        overrides = self._scheduler.recompute_steps(timings)
        if not overrides:
            return
        with self._lock:
            for lid, steps in overrides.items():
                if lid in self._learners:
                    self._learners[lid].local_steps_override = steps
        logger.info("semi-sync step budgets: %s", overrides)

    # -- aggregation ------------------------------------------------------

    def _compute_community_model(self, selected: Sequence[str]) -> None:
        """ComputeCommunityModel (controller.cc:795-950), stride-blocked.

        Timing comes from telemetry spans (the aggregate span and one span
        per stride block) which ALSO populate the RoundMetadata fields the
        ad-hoc ``time.time()`` deltas used to fill — ``experiment.json``
        is unchanged."""
        agg_sp = _ttrace.span("round.aggregate", parent=self._round_span,
                              attrs={"rule": self._aggregator.name,
                                     "selected": len(selected)})
        try:
            self._compute_community_model_traced(selected, agg_sp)
        finally:
            agg_sp.end()
            _M_PHASE.observe(agg_sp.duration_ms / 1e3, phase="aggregate")

    def _timed_select(self, block, k):
        """Store lineage select with cost-profile attribution (the select
        share of aggregation time is the 100k-learner ingest wall's
        counterpart on the read side)."""
        if self._profile is None:
            return self._store.select(block, k=k)
        t0 = time.perf_counter()
        picked = self._store.select(block, k=k)
        self._profile.note_store_select((time.perf_counter() - t0) * 1e3)
        return picked

    def _compute_community_model_traced(self, selected: Sequence[str],
                                        agg_sp) -> None:
        if self._ingest is not None:
            # lineage visibility fence: every queued write must land (and
            # the store flush its batched fsyncs) before any select — a
            # torn lineage must never enter an aggregate. A timeout means
            # a wedged writer; raising routes into the aggregation-failure
            # retry instead of silently aggregating a partial cohort.
            t0 = time.perf_counter()
            if not self._ingest.drain(timeout=300.0):
                raise RuntimeError(
                    "ingest drain fence timed out; store lineage would be "
                    "torn")
            drain_ms = (time.perf_counter() - t0) * 1e3
            _M_PHASE.observe(drain_ms / 1e3, phase="ingest_drain")
            if self._profile is not None:
                self._profile.note_phase("ingest_drain", drain_ms)
        lineage_k = self._aggregator.required_lineage
        stride = self.config.aggregation.stride_length or len(selected) or 1
        metadata = self._scaling_metadata(selected)
        scales = self._scaler(metadata)
        decay = self.config.aggregation.staleness_decay
        if decay > 0.0:
            scales = apply_staleness_decay(scales, metadata, decay)
        # FedStride state resets between rounds (federated_stride.cc:52-68);
        # FedRec carries state across rounds; FedAvg resets in its own
        # branch. Under streaming the rolling state HOLDS this round's
        # folds — finish() owns the reset.
        if self._aggregator.name == "fedstride" and self._streaming is None:
            self._aggregator.reset()

        community = None
        meta_blocks: List[int] = []
        meta_durations: List[float] = []
        ids = [lid for lid in selected if lid in scales]

        def block_span(block):
            """One aggregation-block span; ``end()`` feeds both the phase
            metric and the lineage block-duration list."""
            sp = _ttrace.span("round.agg_block", parent=agg_sp,
                              attrs={"size": len(block)})
            return sp

        def end_block(sp, block):
            sp.end()
            _M_PHASE.observe(sp.duration_ms / 1e3, phase="aggregate_block")
            meta_blocks.append(len(block))
            meta_durations.append(sp.duration_ms)

        def collect_all_pairs():
            """Whole-cohort collection (secure + robust rules): stride only
            bounds store-select batching; every selected model enters ONE
            combine call. Returns (pairs, present_ids)."""
            pairs, present_ids = [], []
            for i in range(0, len(ids), stride):
                block = ids[i : i + stride]
                sp = block_span(block)
                picked = self._timed_select(block, k=lineage_k)
                for lid in block:
                    if lid in picked:
                        pairs.append((picked[lid], scales[lid]))
                        present_ids.append(lid)
                end_block(sp, block)
            return pairs, present_ids

        if self.config.secure.enabled and (
                self._masked_stream is not None
                or (self._slices is not None
                    and getattr(self._slices, "masked", False))):
            # Masked partial-fold plane (secure/distributed.py): the
            # round's per-tensor uint64 sums were accumulated where the
            # uplinks landed (controller stream or slice processes);
            # barrier release reconciles contributors against the
            # dispatched cohort and settles the masks
            # (secure/recovery.py) — dropouts recovered via seed-share
            # disclosure, never silently folded in.
            if self._masked_stream is not None:
                folded = self._masked_stream.stats()["folded"]
                sp = block_span(range(folded))
                with sp:
                    snap = self._masked_stream.finish(selected)
                end_block(sp, range(folded))
            else:
                slice_sp = _ttrace.span(
                    "round.slice_reduce", parent=agg_sp,
                    attrs={"cohort": len(ids), "masked": True})
                with slice_sp, slice_sp.activate():
                    reduced = self._slices.reduce_masked(
                        ids, self.global_iteration)
                _M_SECURE_FOLDS.inc(tier="root")
                if reduced is None:
                    snap = None
                else:
                    m_sums, m_specs, m_present, slice_errors = reduced
                    snap = (m_sums, m_specs, m_present)
                    if slice_errors:
                        with self._lock:
                            self._current_meta.errors.extend(slice_errors)
            if snap is None:
                logger.warning("no masked contributions for cohort %s",
                               list(selected))
                return
            community = self._settle_masked(*snap)
        elif self.config.secure.enabled:
            # Secure: masking sums must cancel across ALL parties.
            pairs, present_ids = collect_all_pairs()
            if not pairs:
                logger.warning("no stored models for cohort %s", list(selected))
                return
            parsed = self._parse_secure(pairs)
            correction = None
            if self.config.secure.scheme == "masking":
                correction = self._masking_dropout_correction(
                    present_ids, parsed)
            community = self._aggregator.aggregate(parsed,
                                                   correction=correction)
        elif self._streaming is not None:
            # Streaming: the community model is already accumulated —
            # barrier release just finalizes it. Zero store reads.
            folded = self._streaming.stats()["folded"]
            sp = block_span(range(folded))
            with sp:
                community = self._streaming.finish(selected)
            end_block(sp, range(folded))
            if community is None:
                logger.warning("no streamed contributions for cohort %s",
                               list(selected))
                return
        elif getattr(self._aggregator, "requires_full_cohort", False):
            # Robust rules (median / trimmed_mean / krum): a median cannot
            # fold stride-wise.
            pairs, present_ids = collect_all_pairs()
            if not pairs:
                logger.warning("no stored models for cohort %s", list(selected))
                return
            if self._health_advisory:
                # advisory hook (telemetry.health.advisory): the rule
                # records which flagged learners entered the cohort —
                # the combine itself is bit-identical either way
                community = self._aggregator.aggregate(
                    pairs, learner_ids=present_ids,
                    advisory_scores=self._health.scores())
            else:
                community = self._aggregator.aggregate(pairs)
        elif self._slices is not None:
            # Distributed slice tier (aggregation/distributed.py): fan in
            # O(branch) FoldPartial replies; a slice aggregator dying
            # between submit and fold re-homes inside reduce() and the
            # round completes from its recovered spool. The rule gate ran
            # at construction (fedavg/scaffold/fedstride only).
            if self._aggregator.name == "fedstride":
                self._aggregator.reset()  # round-scoped state unused here
            slice_sp = _ttrace.span(
                "round.slice_reduce", parent=agg_sp,
                attrs={"cohort": len(ids)})
            with slice_sp, slice_sp.activate():
                reduced = self._slices.reduce(
                    ids, scales,
                    stride=self.config.aggregation.stride_length,
                    round_id=self.global_iteration)
            if reduced is None:
                logger.warning("no held slice models for cohort %s",
                               list(selected))
                return
            community, partials, slice_errors = reduced
            for partial in partials:
                meta_blocks.append(partial.count)
                meta_durations.append(round(partial.duration_ms, 3))
                _M_PHASE.observe(partial.duration_ms / 1e3,
                                 phase="aggregate_block")
            if slice_errors:
                with self._lock:
                    self._current_meta.errors.extend(slice_errors)
        elif (self._tree is not None
              and self._aggregator.name in ("fedavg", "scaffold",
                                            "fedstride")):
            # Tree tier (aggregation/tree.py): B-way slice folds in
            # workers, O(branch) root fan-in; applies to the pure
            # weighted-sum rules on the store path. stride_length=0 is
            # passed through as 0 so the tier applies its own bounded
            # sub-block instead of stacking whole slices.
            if self._aggregator.name == "fedstride":
                self._aggregator.reset()  # round-scoped state unused here
            tree_sp = _ttrace.span("round.tree_reduce", parent=agg_sp,
                                   attrs={"cohort": len(ids),
                                          "branch": self._tree.branch})
            with tree_sp:
                reduced = self._tree.reduce(
                    ids, scales,
                    lambda block: self._timed_select(block, k=lineage_k),
                    stride=self.config.aggregation.stride_length)
            if reduced is None:
                logger.warning("no stored models for cohort %s",
                               list(selected))
                return
            community, partials = reduced
            for partial in partials:
                meta_blocks.append(partial.count)
                meta_durations.append(round(partial.duration_ms, 3))
                _M_PHASE.observe(partial.duration_ms / 1e3,
                                 phase="aggregate_block")
        elif hasattr(self._aggregator, "accumulate"):
            # Fold rules (FedAvg and the ServerOpt family wrapping it):
            # accumulate block-by-block so only one stride block of models is
            # ever resident (the point of the reference's stride loop,
            # controller.cc:842-936). ServerOpt applies its optimizer step
            # once, inside result().
            self._aggregator.reset()
            accumulated = 0
            needs_steps = getattr(self._aggregator, "needs_local_steps",
                                  False)
            for i in range(0, len(ids), stride):
                block = ids[i : i + stride]
                sp = block_span(block)
                picked = self._timed_select(block, k=lineage_k)
                pairs = [(picked[lid], scales[lid]) for lid in block if lid in picked]
                if pairs:
                    if needs_steps:
                        # fednova: per-learner completed local steps (one
                        # optimizer step per batch in this engine)
                        steps = [
                            max(1.0, float(metadata.get(lid, {}).get(
                                "completed_batches", 0.0)) or 1.0)
                            for lid in block if lid in picked]
                        self._aggregator.accumulate(pairs, steps=steps)
                    else:
                        self._aggregator.accumulate(pairs)
                    accumulated += len(pairs)
                end_block(sp, block)
            if not accumulated:
                logger.warning("no stored models for cohort %s", list(selected))
                return
            community = self._aggregator.result()
            self._aggregator.reset()
            # ServerOpt stages its optimizer step inside result(); it is
            # committed below only after the community model is installed,
            # so an aggregation-failure retry does not double-step moments.
        else:
            # rolling rules (fedstride / fedrec): incremental block updates
            for i in range(0, len(ids), stride):
                block = ids[i : i + stride]
                sp = block_span(block)
                picked = self._timed_select(block, k=lineage_k)
                pairs = [(picked[lid], scales[lid]) for lid in block if lid in picked]
                present = [lid for lid in block if lid in picked]
                if pairs:
                    community = self._aggregator.aggregate(
                        pairs, learner_ids=present)
                end_block(sp, block)
            if community is None:
                logger.warning("no stored models for cohort %s", list(selected))
                return

        if self._aggregator.name == "scaffold":
            self._fold_scaffold_controls(ids)

        blob = self._community_to_blob(community)
        # close the span here so its duration covers collection +
        # combine + blob encode — the same interval the old t0 delta did
        agg_sp.end()
        host_fold = host_fold_backend()  # may build the library: no lock
        with self._lock:
            if self.config.secure.enabled:
                self._community_opaque = community
            else:
                self._community_flat = community
            self._community_blob = blob
            if hasattr(self._aggregator, "commit"):
                self._aggregator.commit()
            meta = self._current_meta
            meta.selected_learners = list(selected)
            meta.scales = {lid: round(float(w), 6)
                           for lid, w in scales.items()}
            # per-uplink dispatch-version lag (FedBuff staleness-aware
            # scaling's input) — nonzero entries only, so synchronous
            # silo lineage serializes unchanged
            meta.staleness = {
                lid: float(m["staleness"])
                for lid, m in metadata.items() if m.get("staleness")}
            meta.aggregation_block_sizes = meta_blocks
            meta.aggregation_block_duration_ms = meta_durations
            meta.aggregation_duration_ms = agg_sp.duration_ms
            meta.host_fold = host_fold
            if not self.config.secure.enabled:
                sizes = {"values": 0, "non_zeros": 0, "zeros": 0, "bytes": 0}
                for arr in community.values():
                    q = quantify(np.asarray(arr))
                    for key in sizes:
                        sizes[key] += q[key]
                meta.model_size = sizes

    def _settle_masked(self, sums, specs, contributors):
        """Settle one round's masked partial-fold sums (secure/recovery.py)
        into the opaque community payload: reconcile the contributor set
        against the registered mask parties, recover dropouts via
        seed-share disclosure, unmask, and re-wrap under the SecureAgg
        output contract (float64 payloads, CIPHERTEXT-kind specs).
        Raises when the cohort cannot settle so the aggregation-failure
        retry re-runs the round clean."""
        from metisfl_tpu.secure import recovery as _recovery
        from metisfl_tpu.tensor.spec import TensorKind, TensorSpec

        cfg = self.config.secure
        with self._lock:
            idx_of = {lid: self._learners[lid].party_index
                      for lid in contributors if lid in self._learners}
            registered = {r.party_index for r in self._learners.values()
                          if r.party_index >= 0}
        missing = [lid for lid in contributors if lid not in idx_of]
        if missing:
            raise RuntimeError(
                f"masked contributors {missing} have no registration "
                "record; their party indices are unknown and the sum "
                "cannot settle")
        n = cfg.num_parties or (max(registered) + 1 if registered else 0)
        if n <= 0:
            raise RuntimeError(
                "mask settlement needs the registered party count "
                "(secure.num_parties, driver-filled) or joined "
                "capabilities['party_index'] values")
        round_id = self.global_iteration

        def recover_fn(rid, surviving, dropped, lengths):
            return self._request_mask_recovery(
                rid, surviving, dropped, lengths, list(contributors))

        payloads, report = _recovery.settle(
            sums, idx_of, n, max(2, cfg.min_recovery_parties),
            round_id, recover_fn)
        _M_SECURE_SETTLEMENT.observe(report.duration_ms / 1e3)
        if report.recovered:
            _M_SECURE_RECOVERED.inc(len(report.dropped))
        _tevents.emit(
            _tevents.SecureSettlement, round=round_id,
            contributors=len(report.contributors),
            dropped=len(report.dropped), recovered=report.recovered,
            tier="stream" if self._masked_stream is not None else "slice",
            duration_ms=round(report.duration_ms, 3))
        community = {}
        for name, payload in payloads.items():
            spec = specs[name]
            community[name] = (payload, TensorSpec(
                tuple(spec.shape), spec.dtype, TensorKind.CIPHERTEXT))
        return community

    def _request_mask_recovery(self, round_id, surviving, dropped,
                               lengths, candidates):
        """Walk the surviving learners' proxies for ONE residual
        disclosure (MaskingBackend.recovery_correction — the learner
        side enforces the privacy thresholds). Returns the per-tensor
        correction list, None when the transport cannot recover
        (full-cohort semantics apply downstream), and raises when every
        survivor refused or errored."""
        last_error = None
        for lid in candidates:
            record = self._learners.get(lid)
            if record is None or record.proxy is None:
                continue
            if not hasattr(record.proxy, "recover_masks"):
                return None  # transport cannot recover
            try:
                corrections = record.proxy.recover_masks(
                    int(round_id), list(surviving), list(dropped),
                    list(lengths))
            except Exception as exc:  # noqa: BLE001 - try the next one
                last_error = exc
                continue
            logger.warning(
                "masking dropout recovery: %s computed residuals for "
                "dropped parties %s (surviving %d)", lid, list(dropped),
                len(surviving))
            _tevents.emit(_tevents.SecureMasksRecovered,
                          round=int(round_id), survivor=lid,
                          surviving=len(surviving), dropped=len(dropped))
            return corrections
        raise RuntimeError(
            f"masking dropout recovery failed on every survivor: "
            f"{last_error!r}")

    def _masking_dropout_correction(self, present_ids, parsed):
        """Masking dropout recovery: when the aggregating cohort is missing
        registered mask parties (deadline stragglers, crashes), ask ONE
        surviving learner for the dropped parties' residual-mask correction
        (secure/masking.py recovery_correction — the Bonawitz unmasking
        round in this trust model). Returns ``{tensor_name: bytes}`` or
        None when the full cohort is present (masks cancel on their own).
        Raises when recovery is impossible so the aggregation-failure
        full-cohort retry takes over."""
        cfg = self.config.secure
        with self._lock:
            idx_of = {lid: self._learners[lid].party_index
                      for lid in present_ids if lid in self._learners}
            registered = {r.party_index for r in self._learners.values()
                          if r.party_index >= 0}
        surviving = sorted(idx_of.values())
        # party count: driver-filled config, else derived from the joined
        # parties' indices (in-process federations skip the driver)
        n = cfg.num_parties or (max(registered) + 1 if registered else 0)
        if n <= 0 or not surviving or -1 in surviving:
            return None  # party indices unknown: full-cohort semantics
        if len(surviving) == n:
            return None  # nobody dropped
        min_parties = max(2, cfg.min_recovery_parties)
        if len(surviving) < min_parties:
            raise RuntimeError(
                f"masking dropout recovery needs >= {min_parties} surviving "
                f"parties, have {len(surviving)}")
        dropped = sorted(set(range(n)) - set(surviving))
        first_model = parsed[0][0][0]
        names = list(first_model)
        lengths = [int(first_model[name][1].size) for name in names]
        round_id = self.global_iteration
        corrections = self._request_mask_recovery(
            round_id, surviving, dropped, lengths, list(present_ids))
        if corrections is None:
            return None  # transport cannot recover: full-cohort semantics
        return dict(zip(names, corrections))

    def _parse_secure(self, pairs):
        parsed = []
        for lineage, scale in pairs:
            models = []
            for item in lineage:
                if isinstance(item, (bytes, bytearray)):
                    blob = ModelBlob.from_bytes(item)
                    models.append(dict(blob.opaque))
                else:
                    models.append(item)
            parsed.append((models, scale))
        return parsed

    def _community_to_blob(self, community) -> bytes:
        if self.config.secure.enabled:
            return ModelBlob(opaque=dict(community)).to_bytes()
        named = [(name, np.asarray(arr)) for name, arr in community.items()]
        return ModelBlob(tensors=named).to_bytes()

    def _pack_scaffold_c(self) -> bytes:
        """Wire bytes of the server control variate (empty until the first
        cohort's deltas fold in — learners treat empty as zeros). Cached —
        c only changes at fold/restore, and re-serializing a model-sized
        tree per learner per dispatch inside the lock would stall the RPC
        handlers. Call with ``self._lock`` held (dispatch does)."""
        if self._scaffold_c is None:
            return b""
        if self._scaffold_c_blob is None:
            from metisfl_tpu.tensor.pytree import ModelBlob
            self._scaffold_c_blob = ModelBlob(
                tensors=sorted(self._scaffold_c.items())).to_bytes()
        return self._scaffold_c_blob

    def _fold_scaffold_controls(self, cohort: Sequence[str]) -> None:
        """c += (1/N) * sum over the cohort's control deltas (SCAFFOLD
        server update, |S|/N * mean over S — N = active learners)."""
        from metisfl_tpu.tensor.pytree import ModelBlob
        with self._lock:
            blobs = [self._scaffold_deltas.pop(lid)
                     for lid in cohort if lid in self._scaffold_deltas]
            n_active = max(1, len(self._learners))
        if not blobs:
            return
        total: Dict[str, np.ndarray] = {}
        for raw in blobs:
            for name, arr in ModelBlob.from_bytes(raw).tensors:
                arr = np.asarray(arr, np.float32)
                total[name] = total.get(name, 0.0) + arr
        with self._lock:
            if self._scaffold_c is None:
                self._scaffold_c = {n: np.zeros_like(a)
                                    for n, a in total.items()}
            for name, summed in total.items():
                if name in self._scaffold_c:
                    self._scaffold_c[name] = (
                        self._scaffold_c[name] + summed / n_active)
            self._scaffold_c_blob = None  # invalidate the pack cache

    def _scaling_metadata(self, selected: Sequence[str]) -> Dict[str, Dict[str, float]]:
        with self._lock:
            # a learner may leave between cohort selection and aggregation —
            # skip departed ids instead of KeyErroring the round
            records = [(lid, self._learners[lid]) for lid in selected
                       if lid in self._learners]
            return {
                lid: {
                    "num_train_examples": r.num_train_examples,
                    "completed_batches": r.completed_batches,
                    "staleness": float(max(
                        0, self.global_iteration - r.last_result_round))
                    if r.last_result_round >= 0 else 0.0,
                }
                for lid, r in records
                if lid in self._learners
            }

    # -- dispatch ---------------------------------------------------------

    def _dispatch_blob(self) -> Optional[bytes]:
        """The community blob as dispatched: downlink_dtype narrows the
        broadcast wire width (e.g. bf16 halves it across the cohort); the
        narrowed encoding is cached per community model so N dispatches
        re-encode once. Internal state (_community_flat, checkpoints,
        stores) stays full-width."""
        with self._lock:
            blob = self._community_blob
            target_name = self.config.train.downlink_dtype
            if blob is None or not target_name or self.config.secure.enabled:
                return blob
            cached = self._downlink_cache
            if cached is not None and cached[0] is blob:
                return cached[1]
        from metisfl_tpu.tensor.pytree import ModelBlob
        from metisfl_tpu.tensor.spec import narrow_named, resolve_ship_dtype

        parsed = ModelBlob.from_bytes(blob)
        narrowed = ModelBlob(tensors=narrow_named(
            parsed.tensors, resolve_ship_dtype(target_name))).to_bytes()
        with self._lock:
            self._downlink_cache = (blob, narrowed)
        return narrowed

    def _dispatch_train(self, learner_ids: Sequence[str],
                        restart_deadline: bool = True) -> None:
        """SendRunTasks (controller.cc:696-759)."""
        blob = self._dispatch_blob()
        if blob is None:
            logger.warning("no community model yet; cannot dispatch train tasks")
            return
        if restart_deadline:
            with self._lock:
                # a fresh round dispatch renews the per-round retry budget
                # (rejoin/replacement single-learner dispatches do not) and
                # advances the round serial — the staleness fence for BOTH
                # the deadline timer and the retry backoff timers. The bump
                # lives here, not in _arm_round_deadline, so the fence works
                # even with round_deadline_secs=0 (no deadline to arm).
                self._dispatch_retries_used = 0
                self._round_serial += 1
            if self._slices is not None:
                # distributed slice tier: partition the fresh round's
                # cohort into contiguous slices over the live aggregators
                # (and revive any the driver has relaunched). Rejoin /
                # replacement single-learner dispatches keep the round's
                # map — their uplinks route by it (unknowns go to root).
                self._slices.assign(list(learner_ids))
            if self._masked_stream is not None:
                # rotate the masked fold-on-arrival accumulator for the
                # fresh round (mask streams are round-keyed; a stale
                # fold into the new accumulator would never cancel)
                self._masked_stream.begin_round(self.global_iteration)
        # The dispatched set is the synchronous round barrier (participation
        # sampling means it can be a strict subset of the active learners).
        self._scheduler.notify_dispatched(list(learner_ids))
        with self._lock:
            self._phase = "dispatch"
            if not self._current_meta.started_at:
                # first dispatch of this round == round start
                # (reference controller.cc:406-418); the round span is the
                # root of this round's trace — learner train spans parent
                # under it via the RPC metadata the dispatch carries
                self._current_meta.started_at = time.time()
                # deterministic root: the trace id IS the round serial
                # (telemetry/causal.py selects a round's tree by id; a
                # retry dispatch bumped the serial, so its trace never
                # collides with the aborted attempt's)
                self._round_span = _ttrace.span(
                    "round", parent=None,
                    trace_id=_ttrace.round_trace_id(self._round_serial),
                    attrs={"round": self.global_iteration,
                           "serial": self._round_serial})
                _tevents.emit(_tevents.RoundStarted,
                              round=self.global_iteration,
                              cohort=len(learner_ids))
            round_span = self._round_span
        # performance observatory: periodic jax.profiler capture — when
        # this round is due, the dispatched tasks carry a profile_dir and
        # the learners trace one steady-state window each
        profile_trace_dir = ""
        if self._profile is not None:
            profile_trace_dir = self._profile.trace_target(
                self.global_iteration)
        dispatch_sp = _ttrace.span("round.dispatch", parent=round_span,
                                   attrs={"learners": len(learner_ids)})
        with dispatch_sp, dispatch_sp.activate():
            for lid in learner_ids:
                with self._lock:
                    record = self._learners.get(lid)
                    if record is None:
                        continue
                    params = dataclasses.replace(self.config.train)
                    if record.local_steps_override:
                        params.local_steps = record.local_steps_override
                    if self._profile is None:
                        # opt-out contract: the learner's device-stats
                        # path reduces to this one attribute check
                        params.device_stats = False
                    elif profile_trace_dir and not params.profile_dir:
                        params.profile_dir = profile_trace_dir
                    task = TrainTask(
                        task_id=uuid.uuid4().hex,
                        learner_id=lid,
                        round_id=self.global_iteration,
                        global_iteration=self.global_iteration,
                        model=blob,
                        params=params,
                        scaffold=self._aggregator.name == "scaffold",
                        control=self._pack_scaffold_c(),
                        controller_epoch=self.controller_epoch,
                    )
                    self._tasks_in_flight[task.task_id] = lid
                    self._task_dispatched_at[task.task_id] = time.time()
                    self._current_meta.train_submitted_at[lid] = time.time()
                    proxy = record.proxy
                    if self._profile is not None:
                        # downlink wire bytes attributed per learner (the
                        # uplink counterpart lives in _handle_completed).
                        # Under the lock for the same reason as _M_UPLINK:
                        # leave() prunes the series under it, and an
                        # unlocked inc could resurrect a departed
                        # learner's series
                        self._profile.note_downlink(lid, len(blob))
                # journaled BEFORE the send: if the send (or an injected
                # fault) kills the process, the flight recorder still
                # shows what was dispatched
                _tevents.emit(_tevents.TaskDispatched, task_id=task.task_id,
                              learner_id=lid, round=task.round_id)
                try:
                    if hasattr(proxy, "run_task_with_callback"):
                        # async transports surface failures via callback
                        proxy.run_task_with_callback(
                            task, lambda exc, lid=lid, tid=task.task_id:
                            self._note_dispatch_failure(lid, exc, tid))
                    else:
                        proxy.run_task(task)
                except Exception as exc:
                    # Failed dispatches are logged and counted (the reference
                    # only logs and keeps scheduling them, controller.cc:783-786);
                    # async protocols recover, sync rounds rely on the round
                    # deadline / membership changes, and _sample_cohort skips
                    # learners past the consecutive-failure limit.
                    logger.exception("train dispatch to %s failed", lid)
                    self._note_dispatch_failure(lid, exc, task.task_id)
        _M_PHASE.observe(dispatch_sp.duration_ms / 1e3, phase="dispatch")
        with self._lock:
            self._phase = "wait_uplinks"
            # accumulate: join/rejoin re-dispatches add to the same round
            self._current_meta.dispatch_duration_ms += dispatch_sp.duration_ms
            if self._wait_span is None and learner_ids:
                # passive: the wait measures the barrier, not a cause —
                # the critical-path walk (telemetry/causal.py) skips it
                # and descends into the dispatch subtree instead
                self._wait_span = _ttrace.span("round.wait_uplinks",
                                               parent=round_span,
                                               attrs={"passive": True})
        if self._profile is not None:
            # waterfall boundary: the round's FIRST dispatch end (a
            # mid-round rejoin re-dispatch lands inside the wait window
            # and must not move the boundary)
            self._profile.note_mark("dispatch_end", first=True)
        self._arm_round_deadline(restart=restart_deadline)

    def _note_dispatch_failure(self, learner_id: str, exc: Exception,
                               task_id: str = "") -> None:
        with self._lock:
            if task_id:
                # the task never reached the learner, so no completion can
                # ever pop it — without this (and with no round deadline)
                # it would be a forever-"in-flight" ghost in the status
                # plane. The scheduler's round barrier is unaffected: it
                # tracks the dispatched cohort, not task ids.
                self._tasks_in_flight.pop(task_id, None)
                self._task_dispatched_at.pop(task_id, None)
            record = self._learners.get(learner_id)
            if record is None:
                return
            record.dispatch_failures += 1
            count = record.dispatch_failures
        limit = self.config.max_dispatch_failures
        if limit > 0 and count == limit:
            logger.warning(
                "learner %s unreachable after %d failed dispatches (%r); "
                "excluded from cohort sampling until it reports or rejoins",
                learner_id, count, exc)
        self._note_churn(learner_id, "dispatch_failure")
        self._maybe_retry_dispatch(learner_id)

    def _maybe_retry_dispatch(self, failed_id: str) -> None:
        """Bounded dispatch retry-with-backoff (scheduling.dispatch_retries):
        a provably failed dispatch schedules a replacement dispatch after
        doubling backoff, up to the per-round budget. Off (the default)
        this is one attribute check and a failed dispatch keeps today's
        stall-until-deadline behavior."""
        cfg = self.config.scheduling
        if cfg.dispatch_retries <= 0 or self._shutdown.is_set():
            return
        with self._lock:
            if self._dispatch_retries_used >= cfg.dispatch_retries:
                return
            self._dispatch_retries_used += 1
            attempt = self._dispatch_retries_used
            # staleness fence, same posture as the deadline timer: a
            # backoff timer armed for round N must not fire actions into
            # round N+1 (the serial advances per deadline re-arm)
            serial = self._round_serial
        delay = cfg.retry_backoff_s * (2 ** (attempt - 1))

        def _fire():
            with self._lock:
                self._retry_timers.pop(timer, None)
            if self._shutdown.is_set():
                return
            try:
                self._pool.submit(self._guard, self._retry_dispatch,
                                  failed_id, attempt, serial)
            except RuntimeError:  # pool already shut down
                pass

        timer = threading.Timer(delay, _fire)
        timer.daemon = True
        with self._lock:
            if self._shutdown.is_set():
                return
            self._retry_timers[timer] = None
        timer.start()

    def _retry_dispatch(self, failed_id: str, attempt: int,
                        serial: int = 0) -> None:
        """Runs on the scheduling executor after the backoff: drop the
        dead endpoint from the round barrier (the round must not wait on
        a task that was never delivered) and dispatch a replacement
        learner in its place — the reporter pool stays at strength under
        endpoint churn instead of shrinking toward the deadline."""
        if self._shutdown.is_set():
            return
        with self._lock:
            if serial != self._round_serial:
                return  # the round that armed this retry already closed
            busy = set(self._tasks_in_flight.values())
            record = self._learners.get(failed_id)
            healed = record is not None and record.dispatch_failures == 0
        if failed_id in busy or healed:
            # the endpoint healed since the failure (a completion reset
            # its failure count, or a rejoin re-dispatch gave it a LIVE
            # task): ejecting it from the barrier now would silently
            # exclude a deliverable — or already delivered — contribution
            return
        drop = getattr(self._scheduler, "drop_dispatched", None)
        released: List[str] = []
        if drop is not None:
            released = drop(failed_id, self.active_learners())
        dispatched: set = set()
        getter = getattr(self._scheduler, "dispatched_ids", None)
        if getter is not None:
            dispatched = getter()
        pool = [lid for lid in self._admission_pool()
                if lid != failed_id and lid not in dispatched
                and lid not in busy]
        replacement = random.choice(pool) if pool else ""
        _M_DISPATCH_RETRIES.inc()
        _tevents.emit(_tevents.DispatchRetried, learner_id=failed_id,
                      replacement=replacement, attempt=attempt)
        if released:
            # dropping the dead endpoint satisfied the (quorum) barrier:
            # finish the round instead of growing it by a replacement
            if self._quorum > 0:
                self._expire_unreported(released)
            self._complete_round(released)
            return
        if not replacement:
            logger.warning("dispatch retry %d for %s: no replacement "
                           "learner available", attempt, failed_id)
            return
        logger.info("dispatch retry %d: replacing unreachable %s with %s",
                    attempt, failed_id, replacement)
        self._dispatch_train([replacement], restart_deadline=False)

    def _send_eval_tasks(self) -> None:
        """SendEvaluationTasks (controller.cc:571-647) + digest callback."""
        cfg = self.config.eval
        if cfg.every_n_rounds <= 0:
            return
        if (self.global_iteration + 1) % cfg.every_n_rounds != 0:
            return
        blob = self._dispatch_blob()
        with self._lock:
            learners = list(self._learners.values())
            iteration = self.global_iteration
            # bind eval timestamps to the SUBMITTING round's metadata — the
            # digest callback may fire after _complete_round swapped
            # _current_meta, and the received_at must land in the same round
            # record as its submitted_at (the reference keeps this lineage
            # clean, controller.cc:582-586, :673-675)
            meta = self._current_meta
        if blob is None:
            return
        entry: Dict[str, Any] = {"global_iteration": iteration, "evaluations": {}}
        with self._lock:
            self.community_evaluations.append(entry)
        eval_sp = _ttrace.span("round.eval_dispatch",
                               parent=self._round_span,
                               attrs={"learners": len(learners)})
        for record in learners:
            task = EvalTask(
                task_id=uuid.uuid4().hex,
                learner_id=record.learner_id,
                round_id=iteration,
                model=blob,
                batch_size=cfg.batch_size,
                datasets=list(cfg.datasets),
                metrics=list(cfg.metrics),
                local_tensor_regex=self.config.train.local_tensor_regex,
                ship_tensor_regex=self.config.train.ship_tensor_regex,
                controller_epoch=self.controller_epoch,
            )
            with self._lock:
                meta.eval_submitted_at[record.learner_id] = time.time()

            def _digest(result: EvalResult, lid=record.learner_id,
                        entry=entry, meta=meta):
                with self._lock:
                    entry["evaluations"][lid] = result.evaluations
                    now = time.time()
                    meta.eval_received_at[lid] = now
                    rec = self._learners.get(lid)
                    sent = meta.eval_submitted_at.get(lid, 0.0)
                    if rec is not None and sent:
                        rec.ewma_eval_s = _ewma(rec.ewma_eval_s,
                                                max(0.0, now - sent))
                # outside the controller lock: the fold takes the registry
                # lock and may emit promotion events — one attribute check
                # when the registry is off
                if self._registry is not None:
                    self._note_registry_eval(entry, expected=len(learners))

            try:
                with eval_sp.activate():
                    record.proxy.evaluate(task, _digest)
                if self._profile is not None:
                    # eval broadcasts are downlink wire bytes too — under
                    # the lock with a membership re-check (same posture
                    # as _M_UPLINK): leave() prunes the series strictly
                    # after deleting the record, so attributing only to
                    # a still-registered learner cannot resurrect a
                    # pruned series
                    with self._lock:
                        if record.learner_id in self._learners:
                            self._profile.note_downlink(
                                record.learner_id, len(blob))
            except Exception:
                logger.exception("eval dispatch to %s failed", record.learner_id)
        eval_sp.end()

    # ------------------------------------------------------------------ #
    # checkpoint / resume
    # ------------------------------------------------------------------ #

    _CKPT_NAME = "controller_ckpt.bin"

    def _checkpoint_state(self) -> Dict[str, Any]:
        """One serializable capture of everything round bit-identity
        depends on — community model, round counter + lineage metadata,
        learner registry + auth tokens, aggregator/SCAFFOLD state,
        registry lineage, health scores, metric-budget sketches. Shared
        verbatim by the on-disk checkpoint (save_checkpoint) and the
        hot-standby WAL snapshot (controller/wal.py): a promoted standby
        restores exactly what ``--resume`` restores."""
        with self._lock:
            state = {
                "global_iteration": self.global_iteration,
                "community_blob": self._community_blob or b"",
                "round_metadata": [m.to_dict() for m in self.round_metadata],
                "community_evaluations": self._snapshot_evaluations(),
                # Learner registry + auth tokens (crash-failover): a
                # restarted controller must recognize rejoining learners
                # as THEMSELVES — same id, same token, same masking/
                # SCAFFOLD party index — or every credentialed rejoin
                # would register a ghost duplicate and secure-agg party
                # maps would break. Proxies are rebuilt at restore.
                "learners": [self._learner_entry(r)
                             for r in self._learners.values()],
            }
            # Rolling rules (FedRec) carry cross-round state; persist the
            # contribution scales so resume can rebuild wc_scaled/z from the
            # store's lineage (aggregation/rolling.py rehydrate).
            if hasattr(self._aggregator, "export_scales"):
                state["agg_scales"] = self._aggregator.export_scales()
            # server-opt rules persist their moments + step-from model
            if hasattr(self._aggregator, "export_state"):
                state["agg_state"] = self._aggregator.export_state()
            if self._scaffold_c is not None:
                state["scaffold_c"] = self._pack_scaffold_c()
            if self._health is not None:
                # divergence scores + last-uplink summaries + the latest
                # round snapshot survive a failover restart (same posture
                # as the straggler EWMAs above) — scores must not reset
                # to "everyone is typical" after a crash
                state["health"] = self._health.export_state()
        if self._registry is not None:
            # model-lifecycle lineage (+ retained blobs, retention-
            # bounded): channel heads and rollback targets must survive
            # --resume failover or the serving plane would lose its
            # promoted model across a controller crash. Outside the
            # controller lock — the export takes the registry's own.
            state["registry"] = self._registry.export_state()
        if self._cardinality_budget > 0:
            # collapsed per-learner families persist as sketches —
            # O(budget) checkpoint bytes however large the fleet, and
            # the digest quantiles survive --resume failover (empty dict
            # below budget: nothing has collapsed, series are exact)
            budget_state = _REG.budget_state()
            if budget_state:
                state["metrics_budget"] = budget_state
        return state

    @staticmethod
    def _learner_entry(r: LearnerRecord) -> Dict[str, Any]:
        """The learner's serialized registry entry — one shape shared by
        checkpoint/WAL-snapshot state and the WAL's per-join delta, so
        replay merge (wal.py) and restore agree field-for-field.
        Straggler EWMAs ride along so scores do not reset to "everyone
        is typical" after a failover."""
        return {"learner_id": r.learner_id,
                "auth_token": r.auth_token,
                "hostname": r.hostname,
                "port": r.port,
                "num_train_examples": r.num_train_examples,
                "num_val_examples": r.num_val_examples,
                "num_test_examples": r.num_test_examples,
                "completed_batches": r.completed_batches,
                "ms_per_step": float(r.ms_per_step),
                "last_result_round": r.last_result_round,
                "party_index": r.party_index,
                "local_steps_override": r.local_steps_override,
                "ewma_train_s": float(r.ewma_train_s),
                "ewma_eval_s": float(r.ewma_eval_s)}

    def save_checkpoint(self, path: Optional[str] = None,
                        state: Optional[Dict[str, Any]] = None) -> str:
        """Persist community model + round counter + lineage metadata.

        Closes the reference's resume gap (SURVEY.md §5.4: resume there is
        manual re-seeding via ReplaceCommunityModel, controller.cc:85-96 —
        the round counter and metadata lineage are lost). ``state`` lets
        the coalesced saver reuse one capture for checkpoint + WAL
        snapshot; the write is atomic-rename durable (store/durable.py)."""
        if path is None:
            path = os.path.join(self.config.checkpoint.dir, self._CKPT_NAME)
        if state is None:
            state = self._checkpoint_state()
        # segments: the community blob (and the registry's retained ones)
        # go to the file as the objects they are; same file bytes
        buf = codec_dumps_segments(state)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        _durable.atomic_write(path, buf.parts, prefix=".ckpt_")
        return path

    def restore_checkpoint(self, path: Optional[str] = None) -> bool:
        """Restore from ``save_checkpoint`` output; returns False when no
        checkpoint exists (fresh start)."""
        if path is None:
            path = self.config.checkpoint.dir
        if os.path.isdir(path):
            path = os.path.join(path, self._CKPT_NAME)
        if not os.path.exists(path):
            return False
        with open(path, "rb") as f:
            state = codec_loads(f.read())
        self._restore_state(state)
        with self._lock:
            n_learners = len(self._learners)
        logger.info("restored checkpoint %s at round %d (%d learner(s) in "
                    "registry, epoch %s)", path, self.global_iteration,
                    n_learners, self.controller_epoch[:8])
        return True

    def restore_from_wal(self) -> bool:
        """Promote-time restore for the hot standby: merge the WAL's
        latest snapshot with every registry delta appended after it
        (controller/wal.py replay/merge) and restore exactly like
        ``--resume`` does from a checkpoint. Returns False when the log
        is empty (primary died before anything durable happened — the
        standby then serves a fresh federation and learners re-attach
        via their own join path)."""
        if self._wal is None:
            return False
        from metisfl_tpu.controller.wal import RoundStateLog
        snapshot, deltas = self._wal.replay()
        state = RoundStateLog.merge(snapshot, deltas)
        if state is None:
            return False
        self._restore_state(state)
        with self._lock:
            n_learners = len(self._learners)
        logger.info("restored WAL round state at round %d (%d learner(s), "
                    "%d registry delta(s) past the snapshot, epoch %s)",
                    self.global_iteration, n_learners, len(deltas),
                    self.controller_epoch[:8])
        return True

    def _restore_state(self, state: Dict[str, Any]) -> None:
        """Apply one ``_checkpoint_state``-shaped dict to this (fresh)
        controller — shared by checkpoint restore and WAL promotion."""
        blob = state.get("community_blob") or None
        with self._lock:
            self.global_iteration = int(state["global_iteration"])
            self.round_metadata = [
                RoundMetadata(**m) for m in state.get("round_metadata", [])]
            self.community_evaluations = list(
                state.get("community_evaluations", []))
            self._current_meta = RoundMetadata(
                global_iteration=self.global_iteration)
        known_fields = {f.name for f in dataclasses.fields(LearnerRecord)}
        for entry in state.get("learners", []):
            record = LearnerRecord(**{k: v for k, v in entry.items()
                                      if k in known_fields})
            try:
                # the checkpointed endpoint may still be live (controller
                # crashed, learners did not): a working proxy lets the
                # restored controller re-dispatch the in-flight round
                # immediately; a dead endpoint surfaces as a dispatch
                # failure and heals when the learner re-attaches
                record.proxy = self._proxy_factory(record)
            except Exception:  # noqa: BLE001 - proxy rebuilt on rejoin
                logger.warning("could not rebuild proxy for %s; waiting "
                               "for re-attach", record.learner_id)
            with self._lock:
                self._learners[record.learner_id] = record
                self._tokens[record.learner_id] = record.auth_token
        with self._lock:
            _M_ACTIVE_LEARNERS.set(len(self._learners))
        if blob:
            self._in_restore = True
            try:
                self.set_community_model(blob)
            finally:
                self._in_restore = False
        agg_scales = state.get("agg_scales")
        if agg_scales and hasattr(self._aggregator, "rehydrate"):
            # FedRec restart-correctness: without this, the rolling sum would
            # silently rebuild from scratch and stragglers' prior
            # contributions would double-count on their next report.
            restored = self._aggregator.rehydrate(self._store, agg_scales)
            logger.info("rehydrated %d/%d rolling contributions from store",
                        restored, len(agg_scales))
        scaffold_c = state.get("scaffold_c")
        if scaffold_c:
            from metisfl_tpu.tensor.pytree import ModelBlob
            with self._lock:
                self._scaffold_c = {
                    name: np.asarray(arr, np.float32)
                    for name, arr in ModelBlob.from_bytes(scaffold_c).tensors}
                self._scaffold_c_blob = None
        agg_state = state.get("agg_state")
        if agg_state and hasattr(self._aggregator, "restore_state"):
            # server-opt restart-correctness: moments + step counter resume
            # the exact update sequence of an uninterrupted run
            self._aggregator.restore_state(agg_state)
        registry_state = state.get("registry")
        if registry_state and self._registry is not None:
            # lifecycle lineage survives failover: version ids stay
            # monotonic across incarnations and the serving gateway's
            # next poll sees the same stable head it served before
            self._registry.restore_state(registry_state)
        metrics_budget = state.get("metrics_budget")
        if metrics_budget and self._cardinality_budget > 0:
            # rehydrate the collapsed families' sketches: the restored
            # controller keeps answering digest quantiles for the whole
            # pre-crash fleet instead of restarting from "no history"
            _REG.restore_budget_state(metrics_budget)
        health_state = state.get("health")
        if health_state and self._health is not None:
            self._health.restore_state(health_state)
            with self._lock:
                for lid, score in self._health.scores().items():
                    if lid in self._learners:
                        _M_DIVERGENCE.set(round(score, 4), learner=lid)

    def resume_round(self) -> bool:
        """Kick the restored federation: dispatch a fresh round to the
        checkpointed cohort (the crash abandoned whatever round was in
        flight — its tasks carry the dead epoch and their completions,
        if any arrive, fold in as regular contributions). Returns False
        when there is nothing to resume (no community model or empty
        registry); rejoining learners then restart rounds via their own
        initial dispatch."""
        with self._lock:
            ready = (self._community_blob is not None
                     and bool(self._learners))
        if not ready or self._shutdown.is_set():
            return False
        self._pool.submit(self._guard, self._resume_dispatch)
        return True

    def _resume_dispatch(self) -> None:
        if self._shutdown.is_set():
            return
        self._scheduler.reset()
        cohort = self._sample_cohort()
        if not cohort:
            return
        logger.info("resuming round %d after restore: dispatching to %s",
                    self.global_iteration, cohort)
        self._dispatch_train(cohort)

    # ------------------------------------------------------------------ #
    # live status plane (DescribeFederation)
    # ------------------------------------------------------------------ #

    def _straggler_scores(self) -> Dict[str, float]:
        """Round-relative straggler scores: each learner's EWMA train
        duration over the registry median (1.0 = typical, >1 = slower,
        0.0 = no observation yet). Call with ``self._lock`` held."""
        from statistics import median

        ewmas = {lid: r.ewma_train_s for lid, r in self._learners.items()}
        positive = [v for v in ewmas.values() if v > 0.0]
        mid = median(positive) if positive else 0.0
        return {lid: (v / mid if (v > 0.0 and mid > 0.0) else 0.0)
                for lid, v in ewmas.items()}

    def _describe_digest_locked(self, scores: Dict[str, float],
                                div_scores: Dict[str, float],
                                churn_scores: Dict[str, float],
                                quarantined: set, limit: int
                                ) -> Dict[str, Any]:
        """Quantile columns for the above-budget DescribeFederation
        snapshot: the registry records are exact controller state, so
        the p50/p90/p99 here are exact — it is the *payload*, not the
        math, the budget bounds. Call with ``self._lock`` held."""
        def _q(values: List[float]) -> Dict[str, float]:
            if not values:
                return {"p50": 0.0, "p90": 0.0, "p99": 0.0, "max": 0.0}
            ordered = sorted(values)
            at = partial(_tmetrics.exact_quantile, ordered)
            return {"p50": round(at(0.5), 4), "p90": round(at(0.9), 4),
                    "p99": round(at(0.99), 4), "max": round(ordered[-1], 4)}

        records = self._learners
        live = sum(1 for r in records.values()
                   if limit <= 0 or r.dispatch_failures < limit)
        columns = {
            "straggler_score": _q([scores.get(lid, 0.0) for lid in records]),
            "ewma_train_s": _q([r.ewma_train_s for r in records.values()]),
            "dispatch_failures": _q([float(r.dispatch_failures)
                                     for r in records.values()]),
        }
        if self._health is not None:
            columns["divergence_score"] = _q(
                [div_scores.get(lid, 0.0) for lid in records])
        if self._churn is not None:
            columns["churn_score"] = _q(
                [churn_scores.get(lid, 0.0) for lid in records])
        return {
            "count": len(records),
            "live": live,
            "budget": self._cardinality_budget,
            "quarantined": len(quarantined),
            "columns": columns,
        }

    def _fold_round_health(self) -> None:
        """Learning-health cohort fold for the round that just aggregated
        (telemetry/health.py): per-learner cohort cosines + robust-z
        divergence scores, the round's convergence snapshot into
        ``RoundMetadata.health``, the ``learner_divergence_score`` /
        ``round_update_norm`` gauges, and the ``UpdateAnomalous`` /
        ``RoundHealth`` journal events. Runs on the scheduling executor
        with ``global_iteration`` still naming the completing round;
        never raises (telemetry must not trip the aggregation-failure
        retry path)."""
        if self._health is None:
            return
        try:
            with self._lock:
                # replaced, never mutated in place — safe un-copied
                community = self._community_flat or {}
                scales = dict(self._current_meta.scales)
            health, anomalies = self._health.complete_round(
                self.global_iteration, community, scales)
            with self._lock:
                self._current_meta.health = health
                # set() under the controller lock for the same
                # churn-prune race reason as the straggler gauge
                for lid, score in health["divergence_score"].items():
                    if lid in self._learners:
                        _M_DIVERGENCE.set(score, learner=lid)
            _M_ROUND_UPDATE_NORM.set(health["round_update_norm"])
            for anomaly in anomalies:
                logger.warning(
                    "learner %s update anomalous in round %d (robust z "
                    "%.2f >= %.2f; divergence score %.2f)",
                    anomaly["learner_id"], anomaly["round"], anomaly["raw"],
                    self._health.anomaly_threshold, anomaly["score"])
                _tevents.emit(_tevents.UpdateAnomalous, **anomaly)
            _tevents.emit(
                _tevents.RoundHealth, round=health["round"],
                update_norm=health["round_update_norm"],
                effective_step=health["effective_step"],
                participation_entropy=health["participation_entropy"],
                anomalous=len(anomalies))
        except Exception:  # noqa: BLE001 - telemetry never fails a round
            logger.exception("round health fold failed")

    # ------------------------------------------------------------------ #
    # model lifecycle plane (registry/registry.py)
    # ------------------------------------------------------------------ #

    def _register_round_version(self) -> None:
        """Mint a registry candidate from the round that just aggregated
        and record the lifecycle lineage into ``RoundMetadata``. Runs on
        the scheduling executor with ``global_iteration`` still naming
        the completing round; never raises (lifecycle bookkeeping must
        not trip the aggregation-failure retry path). One attribute
        check when the registry is off."""
        if self._registry is None:
            return
        try:
            with self._lock:
                blob = self._community_blob
                health = dict(self._current_meta.health)
            if blob is None:
                return
            info = self._registry.register(self.global_iteration, blob,
                                           health)
            from metisfl_tpu.registry import CHANNEL_STABLE
            stable = self._registry.head(CHANNEL_STABLE)
            with self._lock:
                self._current_meta.registered_version = info.version
                self._current_meta.stable_version = (
                    stable.version if stable is not None else 0)
        except Exception:  # noqa: BLE001 - lifecycle never fails a round
            logger.exception("model version registration failed")

    def _note_registry_eval(self, entry: Dict[str, Any],
                            expected: int = 0) -> None:
        """Fold a round's community evaluation into its registered
        version ({"<dataset>/<metric>": mean across learners}); under
        promotion.auto this is what tips a candidate to stable — but the
        gate only arms once ALL ``expected`` digests landed, so a single
        fast learner's partial mean can never promote a model the full
        cohort would have rejected. Runs on eval-digest threads; never
        raises."""
        if self._registry is None:
            return
        try:
            with self._lock:
                evals = {lid: dict(v)
                         for lid, v in entry["evaluations"].items()}
                round_id = int(entry["global_iteration"])
            per: Dict[str, List[float]] = {}
            for learner_evals in evals.values():
                for ds, metrics in learner_evals.items():
                    for name, value in metrics.items():
                        try:
                            per.setdefault(f"{ds}/{name}", []).append(
                                float(value))
                        except (TypeError, ValueError):
                            continue
            if not per:
                return
            folded = {k: sum(v) / len(v) for k, v in per.items()}
            promoted = self._registry.note_eval(
                round_id, folded, gate=len(evals) >= expected)
            if promoted is not None:
                logger.info("round %d eval promoted model version v%d to "
                            "stable", round_id, promoted.version)
        except Exception:  # noqa: BLE001 - eval digest must never break
            logger.exception("registry eval fold failed")

    def describe_registry(self) -> Dict[str, Any]:
        """Registry snapshot for the DescribeRegistry RPC / status CLI /
        serving-gateway polls; ``{"enabled": False}`` when off."""
        if self._registry is None:
            return {"enabled": False}
        return self._registry.describe()

    def registered_model(self, version: int = 0,
                         channel: str = "") -> Optional[bytes]:
        """A registered version's blob, by id or channel head."""
        if self._registry is None:
            return None
        if not version and channel:
            head = self._registry.head(channel)
            if head is None:
                return None
            version = head.version
        return self._registry.blob(version) if version else None

    def promote_version(self, version: int, force: bool = False):
        if self._registry is None:
            raise ValueError("model registry is not enabled")
        info = self._registry.promote(version, force=force)
        # durability: the new stable head must survive a crash landing
        # between this promotion and the next round's auto-checkpoint
        # (the queued save snapshots state at run time, post-promotion)
        self._checkpoint_async()
        return info

    def rollback_version(self):
        if self._registry is None:
            raise ValueError("model registry is not enabled")
        info = self._registry.rollback()
        if info is not None:
            self._checkpoint_async()
        return info

    def _update_straggler_gauge(self, completed: Optional[str] = None
                                ) -> None:
        # set() under the controller lock, like _M_UPLINK.inc: leave()
        # deletes the record under this lock and prunes the series after,
        # so an unlocked set here could interleave and resurrect a
        # departed learner's series (unbounded cardinality under churn)
        with self._lock:
            if completed is not None and _M_STRAGGLER.collapsed():
                # cross-device scale: a full-fleet refresh per uplink is
                # O(fleet) work 600 times a round at 10k clients. Once
                # the family is actually COLLAPSED (not merely budget-
                # armed: a sub-budget fleet keeps exact series, and
                # exact series must keep re-normalizing against the
                # moving median) only the reporter's score is
                # re-observed — against the median of OBSERVED ewmas,
                # which is what the full refresh normalizes by too.
                record = self._learners.get(completed)
                if record is None or record.ewma_train_s <= 0.0:
                    return
                mid = self._straggler_median_cache
                if mid is None:
                    # recomputed at most once per round (invalidated at
                    # round close): the O(fleet) scan must not run per
                    # uplink under the controller lock
                    from statistics import median

                    positive = [r.ewma_train_s
                                for r in self._learners.values()
                                if r.ewma_train_s > 0.0]
                    mid = median(positive) if positive else 0.0
                    self._straggler_median_cache = mid
                score = record.ewma_train_s / mid if mid > 0.0 else 0.0
                _M_STRAGGLER.set(round(score, 4), learner=completed)
                return
            for lid, score in self._straggler_scores().items():
                _M_STRAGGLER.set(round(score, 4), learner=lid)

    def describe(self, event_tail: int = 50) -> Dict[str, Any]:
        """Live federation snapshot for the ``DescribeFederation`` RPC /
        ``python -m metisfl_tpu.status`` watch CLI: current round + phase,
        per-learner liveness and straggler analytics, in-flight tasks,
        store occupancy, and the event-ring tail. Read-only and cheap —
        safe to poll every couple of seconds."""
        now = time.time()
        div_scores: Dict[str, float] = {}
        div_last: Dict[str, Dict[str, Any]] = {}
        if self._health is not None:
            div_scores = self._health.scores()
            div_last = self._health.last_stats()
        churn_scores: Dict[str, float] = {}
        quarantined: set = set()
        if self._churn is not None:
            churn_scores = self._churn.scores()
            quarantined = set(self._churn.quarantined_ids(now))
        budget = self._cardinality_budget
        learners_digest: Optional[Dict[str, Any]] = None
        with self._lock:
            scores = self._straggler_scores()
            limit = self.config.max_dispatch_failures

            def _row(lid: str, r: "LearnerRecord") -> Dict[str, Any]:
                return {
                    "learner_id": r.learner_id,
                    "hostname": r.hostname,
                    "port": r.port,
                    # liveness mirrors _sample_cohort's exclusion rule
                    "live": limit <= 0 or r.dispatch_failures < limit,
                    "dispatch_failures": r.dispatch_failures,
                    "num_train_examples": r.num_train_examples,
                    "last_result_round": r.last_result_round,
                    "ewma_train_s": round(r.ewma_train_s, 3),
                    "ewma_eval_s": round(r.ewma_eval_s, 3),
                    "straggler_score": round(scores.get(lid, 0.0), 4),
                    # learning-health analytics (0.0 until observed;
                    # keys present iff the health plane is on)
                    **({"divergence_score":
                        round(div_scores.get(lid, 0.0), 4),
                        "last_update_norm":
                        div_last.get(lid, {}).get("update_norm", 0.0)}
                       if self._health is not None else {}),
                    # churn-aware admission analytics (keys present iff
                    # the churn plane is on)
                    **({"churn_score": round(churn_scores.get(lid, 0.0), 4),
                        "quarantined": lid in quarantined}
                       if self._churn is not None else {}),
                }

            if budget > 0 and len(self._learners) > budget:
                # cardinality-safe snapshot (docs/OBSERVABILITY.md
                # "Telemetry at scale"): above budget the per-learner
                # table would make every status poll O(fleet) — ship
                # quantile columns + the top offenders instead. Below
                # budget (or budget off) the snapshot is byte-identical
                # to the exact shape (test-pinned).
                learners_digest = self._describe_digest_locked(
                    scores, div_scores, churn_scores, quarantined, limit)
                offenders = sorted(
                    self._learners,
                    key=lambda lid: -scores.get(lid, 0.0))[:10]
                learners = [_row(lid, self._learners[lid])
                            for lid in sorted(offenders)]
            else:
                learners = [_row(lid, r)
                            for lid, r in sorted(self._learners.items())]
            in_flight = [
                {"task_id": tid, "learner_id": lid,
                 "age_s": round(max(
                     0.0, now - self._task_dispatched_at.get(tid, now)), 3)}
                for tid, lid in self._tasks_in_flight.items()
            ]
            snapshot = {
                "controller_epoch": self.controller_epoch,
                "round": self.global_iteration,
                "phase": self._phase,
                "protocol": self.config.protocol,
                "round_started_at": self._current_meta.started_at,
                "aggregation_rule": self._aggregator.name,
                "shutdown": self._shutdown.is_set(),
            }
        # store occupancy OUTSIDE our lock (the store has its own). In
        # digest mode the per-learner map is elided too — it is the same
        # O(fleet) payload the learner table was.
        occupancy = {lid: self._store.size(lid)
                     for lid in self._store.learner_ids()}
        snapshot.update({
            "learners": learners,
            "in_flight": in_flight,
            "store": ({"models": {}, "learners": len(occupancy),
                       "total": sum(occupancy.values())}
                      if learners_digest is not None else
                      {"models": occupancy,
                       "total": sum(occupancy.values())}),
            "events": _tevents.tail(event_tail) if event_tail else [],
            "time": round(now, 6),
        })
        if learners_digest is not None:
            snapshot["learners_digest"] = learners_digest
        if self._alerts is not None:
            # SLO alerting plane: active alerts + lifecycle counts, and
            # the bounded time-series ring behind status sparklines
            snapshot["alerts"] = self._alerts.summary(now=now)
            snapshot["timeseries"] = self._alerts.series_snapshot()
        sched_cfg = self.config.scheduling
        if (self._quorum > 0 or sched_cfg.dispatch_retries > 0
                or self._scheduler.name == "asynchronous_buffered"
                or quarantined):
            # churn-tolerant scheduling section: present only when one of
            # its planes is armed, so silo-regime snapshots are unchanged
            section: Dict[str, Any] = {}
            if self._quorum > 0:
                section["quorum"] = self._quorum
                section["overprovision"] = sched_cfg.overprovision
            if self._scheduler.name == "asynchronous_buffered":
                section["buffer_size"] = self._scheduler.buffer_size
                section["buffer_pending"] = self._scheduler.pending()
            if sched_cfg.dispatch_retries > 0:
                with self._lock:
                    section["dispatch_retries_used"] = \
                        self._dispatch_retries_used
                section["dispatch_retries"] = sched_cfg.dispatch_retries
            if quarantined:
                section["quarantined"] = sorted(quarantined)
            snapshot["scheduling"] = section
        if self._ingest is not None:
            errors, _ = self._ingest.errors()
            snapshot["ingest"] = {"workers": self._ingest.workers,
                                  "queue_depth": self._ingest.queue_depth(),
                                  "errors": errors}
        if self._slices is not None:
            # distributed slice tier: per-aggregator liveness/re-home
            # state + the O(branch) merged uplink-byte rollup
            snapshot["slices"] = self._slices.describe()
        if self._streaming is not None:
            snapshot["streaming"] = self._streaming.stats()
        if self._masked_stream is not None:
            snapshot["secure_stream"] = self._masked_stream.stats()
        if self._health is not None:
            # latest round's convergence snapshot ({} before round 1)
            snapshot["health"] = self._health.snapshot()
        if self._registry is not None:
            # model-lifecycle snapshot (channel heads + version lineage)
            snapshot["registry"] = self._registry.describe()
        if self._profile is not None:
            # latest round's cost profile (phase waterfall + wire totals)
            snapshot["profile"] = self._profile.summary()
        return snapshot

    # ------------------------------------------------------------------ #
    # statistics (driver)
    # ------------------------------------------------------------------ #

    def _snapshot_evaluations(self, tail: int = 0) -> List[dict]:
        """Copy evaluation entries deep enough to detach the mutable
        ``evaluations`` dict, which eval-digest callbacks keep inserting into
        under the lock — a caller serializing a shallow copy outside the lock
        would race those inserts. Call with ``self._lock`` held."""
        entries = (self.community_evaluations[-tail:] if tail > 0
                   else self.community_evaluations)
        return [{**e, "evaluations": dict(e["evaluations"])}
                for e in entries]

    def get_statistics(self) -> dict:
        with self._lock:
            return {
                "global_iteration": self.global_iteration,
                "learners": sorted(self._learners.keys()),
                "round_metadata": [m.to_dict() for m in self.round_metadata],
                "community_evaluations": self._snapshot_evaluations(),
            }

    def get_runtime_metadata(self, tail: int = 0) -> List[dict]:
        """Round-metadata lineage, optionally only the last ``tail`` rounds
        (the reference's granular lineage getters, controller.proto:27-44 —
        a 10k-round federation must not ship its whole history per poll)."""
        with self._lock:
            metas = (self.round_metadata[-tail:] if tail > 0
                     else list(self.round_metadata))
            return [m.to_dict() for m in metas]

    def get_evaluation_lineage(self, tail: int = 0) -> List[dict]:
        """Community-model evaluation lineage, optionally tail-bounded
        (reference GetCommunityModelEvaluationLineage, controller.proto:27)."""
        with self._lock:
            return self._snapshot_evaluations(tail)
