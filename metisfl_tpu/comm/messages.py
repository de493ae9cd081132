"""Federation message schemas.

Typed dataclass messages serialized through :mod:`metisfl_tpu.comm.codec`.
Capability map to the reference's protos:

- ``JoinRequest``/``JoinReply``  ≈ JoinFederationRequest/Response
  (reference metisfl/proto/controller.proto:120-150, metis.proto ServerEntity).
- ``TrainParams``/``TrainTask``  ≈ LearningTask + Hyperparameters + RunTaskRequest
  (metis.proto:95-147, learner.proto:9-24).
- ``TaskResult``                 ≈ CompletedLearningTask + TaskExecutionMetadata
  (metis.proto:104-147).
- ``EvalTask``/``EvalResult``    ≈ EvaluateModelRequest/Response + ModelEvaluations
  (metis.proto:149-196).

Unlike the reference, ML metric values are typed floats, not strings
(SURVEY.md §5.5 flags the reference's stringly-typed metrics as a defect).
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, get_type_hints

from metisfl_tpu.comm.codec import Segments, dumps, dumps_segments, loads


@functools.lru_cache(maxsize=None)
def _hints_for(cls):
    return get_type_hints(cls)


class Message:
    """Base: dataclass ⇄ codec bytes, with nested-message support."""

    # bytes fields that ``from_wire`` may hand over as a read-only view
    # of the request buffer (codec.loads ``views``) when they are bulk:
    # only where every consumer takes a buffer and none keeps it
    _VIEW_FIELDS = ()

    def to_dict(self) -> dict:
        out = {}
        for f in dataclasses.fields(self):
            value = getattr(self, f.name)
            if isinstance(value, Message):
                value = value.to_dict()
            elif isinstance(value, list) and value and isinstance(value[0], Message):
                value = [v.to_dict() for v in value]
            out[f.name] = value
        return out

    @classmethod
    def from_dict(cls, data: dict):
        hints = _hints_for(cls)
        kwargs = {}
        for f in dataclasses.fields(cls):
            if f.name not in data:
                continue
            value = data[f.name]
            hint = hints.get(f.name)
            nested = _nested_message_type(hint)
            if nested is not None and isinstance(value, dict):
                value = nested.from_dict(value)
            elif isinstance(value, list):
                item_type = _list_item_message_type(hint)
                if item_type is not None:
                    value = [item_type.from_dict(v) for v in value]
            kwargs[f.name] = value
        return cls(**kwargs)

    def to_wire(self) -> bytes:
        return dumps(self.to_dict())

    def to_segments(self) -> Segments:
        """``to_wire`` for a sink that gathers (``RpcClient``): the same
        bytes, with a bulk field riding as the object it is."""
        return dumps_segments(self.to_dict())

    @classmethod
    def from_wire(cls, buf):
        return cls.from_dict(loads(buf, views=cls._VIEW_FIELDS))


def _nested_message_type(hint):
    if isinstance(hint, type) and issubclass(hint, Message):
        return hint
    for arg in getattr(hint, "__args__", ()):  # Optional[Msg]
        if isinstance(arg, type) and issubclass(arg, Message):
            return arg
    return None


def _list_item_message_type(hint):
    args = getattr(hint, "__args__", ())
    if args and isinstance(args[0], type) and issubclass(args[0], Message):
        return args[0]
    return None


@dataclass
class TrainParams(Message):
    """Local-training hyperparameters shipped with every task."""

    batch_size: int = 32
    local_steps: int = 0        # exact optimizer steps; 0 → derive from epochs
    local_epochs: float = 1.0   # used when local_steps == 0
    optimizer: str = "sgd"
    learning_rate: float = 0.01
    optimizer_kwargs: Dict[str, Any] = field(default_factory=dict)
    # FedProx proximal term weight; 0 disables (reference fed_prox.py:10-103).
    proximal_mu: float = 0.0
    # weight on sown auxiliary losses (MoE router load balancing); 0 disables
    moe_aux_weight: float = 0.01
    # jax.profiler trace capture (SURVEY.md §5.1): when set, each training
    # task traces ``profile_steps`` steady-state (post-compile) steps into
    # this directory — TensorBoard/xprof-readable. With scan_chunk > 1 the
    # trace covers exactly ONE steady-state fused chunk (scan_chunk steps),
    # since steps inside a compiled scan cannot be traced individually; a
    # run whose only chunk is the compiling one captures no trace rather
    # than a compile-dominated one.
    profile_dir: str = ""
    profile_steps: int = 3
    # Performance-observatory gating (telemetry/profile.py): when true the
    # learner captures device utilization per train task (step-time EWMA,
    # achieved-MFU estimate, HBM watermark) and ships it back in
    # ``TaskResult.device_stats``. The controller stamps this false when
    # ``telemetry.profile.enabled=false``, reducing the learner hot path
    # to this one attribute check.
    device_stats: bool = True
    # Fuse this many optimizer steps into ONE jit-compiled lax.scan program.
    # Cuts host→device dispatch to 1/scan_chunk of the per-step path — the
    # difference is pure overhead on TPU. Cancellation is checked between
    # chunks.
    scan_chunk: int = 1
    # Wire dtype for shipped model weights (a DType name: "bf16", "f16",
    # "f32", ..., or "int8q" for int8 absmax quantization with per-tensor
    # scales — tensor/quantize.py). "" ships the training dtype unchanged.
    # bf16 halves federation bandwidth; int8q quarters it (the controller
    # dequantizes before aggregating). Aggregation still accumulates in
    # f32 and each learner restores its own training dtypes on receipt, so
    # only the wire representation is narrowed. Ignored under secure
    # aggregation (HE/masking payloads have their own fixed-point
    # encoding; int8q+secure is rejected at config time).
    ship_dtype: str = ""
    # Wire dtype for the DOWNLINK (controller → learner community-model
    # broadcast): a float DType name, typically "bf16" to halve broadcast
    # bandwidth across the cohort. "" ships the stored dtype unchanged.
    # Like ship_dtype, only the wire narrows — the controller's own
    # community state stays f32 and each learner restores its training
    # dtypes on receipt. Learners also evaluate the narrowed weights (the
    # model they actually received). Rejected with secure aggregation
    # (opaque payloads) and with ship_dtype='topk...' (sparse updates
    # reconstruct against the controller's exact f32 model).
    downlink_dtype: str = ""
    # FedBN-style personalization (Li et al., ICLR 2021): tensors whose
    # flattened name matches this regex stay LOCAL to each learner — they
    # never ship to the controller, drop out of the community model after
    # round 1, and each learner retains (and evaluates with) its own
    # values. The canonical use is BatchNorm under feature-shift non-IID:
    # local_tensor_regex="batch_stats|/bn" keeps running stats AND the
    # learnable scale/bias per-learner. Incompatible with secure
    # aggregation and with stateful server rules (fedavgm/fedadam/
    # fedyogi/fednova/scaffold track a full model tree) — config-checked.
    local_tensor_regex: str = ""
    # Ship-only-trainable transport (the selective complement of
    # local_tensor_regex — that one RETAINS, this one SELECTS): tensors
    # whose flattened name matches this regex are the ONLY federated
    # state. Learners ship just the matching subset, the controller holds
    # and aggregates ONLY that subset (the frozen base never occupies
    # controller memory or the wire), and the downlink broadcasts the
    # aggregated subset; each learner backfills non-matching tensors from
    # its own construction-time values. Contract: every learner holds the
    # IDENTICAL base (the usual LoRA/linear-probe setting —
    # ship_tensor_regex="lora_" with FlaxModelOps(trainable_regex="lora_")
    # turns an 8B-param federation into an adapter-sized one, MBs instead
    # of GBs both directions). Non-matching tensors are effectively
    # frozen by the transport regardless of the optimizer mask; one the
    # engine's mask freezes too is placed on the learner's device once
    # and kept there from task to task (Learner._resident_names).
    # Composes with secure aggregation (the subset is identical across
    # parties, so the uniform-shape masking/HE payload contract holds —
    # and encrypting adapters instead of the full model is what makes
    # secure LoRA federations practical); incompatible with
    # local_tensor_regex, scaffold, and client-level DP — config-checked.
    # The reference hit the full-model-blob wall and worked around it
    # with a stub-per-request hack (reference
    # metisfl/controller/core/controller.cc:594-604); shipping only the
    # trainable subset removes the wall instead.
    ship_tensor_regex: str = ""
    # Client-level differential privacy on the shipped update
    # (secure/dp.py): the delta vs the received community model is
    # L2-clipped to dp_clip_norm (> 0 enables; also a robustness tool on
    # its own) and Gaussian noise with per-coordinate std
    # dp_noise_multiplier * dp_clip_norm is added. Composes with secure
    # aggregation (privatize, then encrypt/mask). Account the guarantee
    # with secure.dp.rdp_epsilon(noise_multiplier, rounds, delta).
    dp_clip_norm: float = 0.0
    dp_noise_multiplier: float = 0.0


@dataclass
class JoinRequest(Message):
    hostname: str = "localhost"
    port: int = 0
    num_train_examples: int = 0
    num_val_examples: int = 0
    num_test_examples: int = 0
    # Rejoin support: a learner that restarts presents its previous identity
    # (reference grpc_controller_client.py:96-107 rejoin-on-ALREADY_EXISTS).
    previous_id: str = ""
    auth_token: str = ""
    capabilities: Dict[str, Any] = field(default_factory=dict)


@dataclass
class JoinReply(Message):
    learner_id: str = ""
    auth_token: str = ""
    rejoined: bool = False
    # Controller incarnation id: a fresh uuid per controller process. A
    # learner that observes a DIFFERENT epoch in a later task envelope
    # knows the controller crashed and restarted, and re-attaches
    # (re-runs join_federation) instead of trusting stale registration.
    controller_epoch: str = ""


@dataclass
class TrainTask(Message):
    task_id: str = ""
    learner_id: str = ""
    round_id: int = 0
    global_iteration: int = 0
    model: bytes = b""          # ModelBlob wire bytes (community model)
    _VIEW_FIELDS = ("model",)   # read by _load_model, then let go
    params: TrainParams = field(default_factory=TrainParams)
    # SCAFFOLD (aggregation.rule='scaffold'): ``scaffold`` marks the task
    # as control-variate-corrected (the learner must report a delta even
    # while the server variate is still zero), ``control`` carries the
    # server variate c as a ModelBlob (empty = zeros).
    scaffold: bool = False
    control: bytes = b""
    # controller incarnation id (see JoinReply.controller_epoch): a
    # mismatch against the epoch the learner joined under triggers
    # learner-side re-attach before the task runs
    controller_epoch: str = ""


@dataclass
class TaskResult(Message):
    task_id: str = ""
    learner_id: str = ""
    # Composite-key auth: the controller validates (learner_id, auth_token)
    # before accepting a model (reference controller.proto:146-148).
    auth_token: str = ""
    # Incarnation the answered task was dispatched under (the TrainTask's
    # controller_epoch, echoed back). A controller that restored another
    # incarnation's state (hot-standby promotion, --resume relaunch)
    # re-dispatches the abandoned round itself — an uplink the DEAD
    # incarnation dispatched must land as a stale store, never advance
    # the restored round's barrier, or it double-folds against the
    # re-trained copy. Empty (legacy/test producers) means no check.
    controller_epoch: str = ""
    round_id: int = 0
    model: bytes = b""          # locally trained ModelBlob
    _VIEW_FIELDS = ("model",)   # read by _parse_result_model, then let go
    num_train_examples: int = 0
    completed_steps: int = 0
    completed_epochs: float = 0.0
    completed_batches: int = 0
    processing_ms_per_step: float = 0.0
    # Final train-task metrics and the per-epoch trajectory. Consumed
    # controller-side: recorded into RoundMetadata (experiment.json,
    # stats.py per-learner convergence tables) and — train_metrics'
    # "loss" specifically — folded into the learning-health plane's
    # cohort loss quantiles (telemetry/health.py).
    train_metrics: Dict[str, float] = field(default_factory=dict)
    epoch_metrics: List[Dict[str, float]] = field(default_factory=list)
    # SCAFFOLD client control-variate delta (c_i_new - c_i, ModelBlob);
    # the controller folds the cohort's deltas into the server variate.
    control_delta: bytes = b""
    # Device-utilization capture (telemetry/profile.py DeviceMonitor):
    # step_ms_ewma, achieved mfu, hbm_peak_bytes, device_kind — folded
    # into the controller's RoundProfile so the cost profile is
    # federation-wide. Empty when TrainParams.device_stats is false
    # (profile plane opted out) or the task completed zero steps.
    device_stats: Dict[str, Any] = field(default_factory=dict)
    # The task's waterfall on the learner's clock (learner/learner.py):
    # milliseconds per tile (telemetry/profile.py TASK_TILES), contiguous
    # from the RunTask RPC's acceptance to the start of this report, and
    # ``start``, that acceptance as ``time.time()``; beside them what
    # crossed host <-> device (profile.py TASK_BYTES). Lands in
    # ``RoundProfile.learners[lid]["task"]`` and ``["task_bytes"]``.
    # Empty from a learner that predates it.
    task_tiles: Dict[str, float] = field(default_factory=dict)


@dataclass
class EvalTask(Message):
    task_id: str = ""
    learner_id: str = ""
    round_id: int = 0
    model: bytes = b""
    _VIEW_FIELDS = ("model",)   # read by _load_model, then let go
    batch_size: int = 256
    datasets: List[str] = field(default_factory=lambda: ["test"])
    metrics: List[str] = field(default_factory=lambda: ["loss", "accuracy"])
    # FedBN (TrainParams.local_tensor_regex): round-2+ community blobs
    # omit the local tensors, and a learner that has never trained (not
    # yet sampled, or crash-rejoined) must still be able to reconstruct
    # the model — the regex rides every eval/infer task too
    local_tensor_regex: str = ""
    # Ship-only-trainable (TrainParams.ship_tensor_regex): community blobs
    # carry ONLY the federated subset; a never-trained learner must know
    # to backfill the frozen base from its own initial values
    ship_tensor_regex: str = ""
    # controller incarnation id (see JoinReply.controller_epoch)
    controller_epoch: str = ""


@dataclass
class EvalResult(Message):
    task_id: str = ""
    learner_id: str = ""
    round_id: int = 0
    # dataset name -> {metric -> value}
    evaluations: Dict[str, Dict[str, float]] = field(default_factory=dict)
    duration_ms: float = 0.0


@dataclass
class InferTask(Message):
    """Inference request — the reference learner's third task type
    (reference metisfl/learner/learner.py:311-330 run_inference_task)."""

    task_id: str = ""
    learner_id: str = ""
    round_id: int = 0
    model: bytes = b""          # ModelBlob to infer with (may be encrypted)
    batch_size: int = 256
    # either a named local dataset split ("train"/"valid"/"test")...
    dataset: str = "test"
    # ...or explicit inputs shipped as a packed {"x": array} ModelBlob
    inputs: bytes = b""
    max_examples: int = 0       # 0 = all
    # > 0 turns the task into autoregressive generation on a causal-LM
    # engine (models/generate.py): inputs are token prompts, the result
    # packs the generated continuations instead of logits
    generate_tokens: int = 0
    # FedBN merge for partial community blobs (see EvalTask)
    local_tensor_regex: str = ""
    # ship-only-trainable backfill for subset community blobs (see EvalTask)
    ship_tensor_regex: str = ""
    temperature: float = 0.0    # 0 = greedy
    top_k: int = 0
    top_p: float = 0.0          # nucleus sampling mass; 0/1 = off
    eos_id: int = -1            # < 0 = no early stop


@dataclass
class ServeRequest(Message):
    """Serving-gateway inference request (serving/gateway.py). Unlike
    :class:`InferTask`, no model rides along — the gateway serves the
    registry's promoted community model, hot-swapped server-side."""

    request_id: str = ""
    # deterministic canary routing key (a session/user id); "" falls back
    # to request_id so every request still routes deterministically
    key: str = ""
    inputs: bytes = b""         # packed {"x": array} ModelBlob


@dataclass
class ServeReply(Message):
    request_id: str = ""
    predictions: bytes = b""    # packed {"predictions": array} ModelBlob
    # which registry version / channel actually served this request —
    # canary observability is per-response, not config inference
    model_version: int = 0
    channel: str = ""
    duration_ms: float = 0.0


@dataclass
class GenerateRequest(Message):
    """Serving-gateway generation request (serving/decode.py): an
    autoregressive continuation of ``prompt`` through the gateway's
    continuous-batching decode loop. Greedy by contract — a shared
    in-flight batch cannot reproduce any single request's sampling
    stream, and serving replies must be replica-independent."""

    request_id: str = ""
    # deterministic canary/consistent-hash routing key (see ServeRequest)
    key: str = ""
    prompt: bytes = b""         # packed {"tokens": (L,) int32} ModelBlob
    max_new_tokens: int = 16
    eos_id: int = -1            # < 0 = no early stop


@dataclass
class GenerateReply(Message):
    request_id: str = ""
    # packed {"tokens": (max_new_tokens,) int32} ModelBlob; pad (0) after
    # an emitted eos — models/generate.py's exact contract
    tokens: bytes = b""
    model_version: int = 0
    channel: str = ""
    duration_ms: float = 0.0


@dataclass
class InferResult(Message):
    task_id: str = ""
    learner_id: str = ""
    round_id: int = 0
    predictions: bytes = b""    # packed {"predictions": array} ModelBlob
    num_examples: int = 0
    duration_ms: float = 0.0

