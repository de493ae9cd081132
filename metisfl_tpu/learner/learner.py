"""Learner runtime: executes train/eval tasks against local data.

Capability equivalent of the reference's learner process
(reference metisfl/learner/learner.py:21-417, learner_servicer.py:14-139):
join/leave the federation, run training tasks non-blocking with
cancel-on-new-task, run evaluations, ship results back. Redesigned:

- The reference isolates every task in a fresh "spawn" subprocess (1-worker
  pebble pools, learner.py:77-89) because TF/Torch leak state; a JAX learner
  keeps one process and one compiled-step cache — task isolation is the
  functional purity of jit, and weights move by value through the wire
  contract.
- Training runs on a single worker thread; a new train task cancels the
  running one between steps (the reference cancels the subprocess future,
  learner_servicer.py:84-110).
- Secure aggregation: when an HE backend is configured the learner encrypts
  outgoing weights and decrypts incoming community models (the controller
  never sees plaintext), mirroring model_ops.py:24-60 / ckks hookpoints.
- Weights move by value, but no farther than they must: under
  ``ship_tensor_regex`` a leaf that is unshipped AND frozen by the engine's
  own mask is placed on the device once and stays there; every later train
  task decodes, places, reads back and encodes the other leaves alone
  (``Learner._resident_names``, ``_vouched``). A full-model federation has
  no such leaf and moves the whole tree, as does any task the learner
  cannot vouch for. Evaluations and inferences keep their explicit whole
  trees (a concurrent train donates the engine's slot).
"""

from __future__ import annotations

import dataclasses
import logging
import re
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, Optional, Protocol

import numpy as np

from metisfl_tpu.comm.messages import (
    EvalResult,
    EvalTask,
    InferResult,
    InferTask,
    JoinReply,
    JoinRequest,
    TaskResult,
    TrainTask,
)
from metisfl_tpu.models.dataset import ArrayDataset
from metisfl_tpu.models.ops import FlaxModelOps
from metisfl_tpu import telemetry as _tel
from metisfl_tpu.telemetry import events as _tevents
from metisfl_tpu.telemetry import metrics as _tmetrics
from metisfl_tpu.telemetry import trace as _ttrace
from metisfl_tpu.tensor.spec import resolve_ship_dtype
from metisfl_tpu.tensor.pytree import (
    ModelBlob,
    NamedTensors,
    named_tensors_to_pytree,
    pytree_to_named_tensors,
)

logger = logging.getLogger("metisfl_tpu.learner")

_REG = _tmetrics.registry()
_M_TRAIN_DURATION = _REG.histogram(
    _tel.M_LEARNER_TRAIN_DURATION_SECONDS, "End-to-end train-task time")
_M_TRAIN_STEP_MS = _REG.histogram(
    _tel.M_LEARNER_STEP_MILLISECONDS, "Median per-optimizer-step time",
    buckets=(0.1, 0.25, 0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000,
             5000))
_M_TASKS = _REG.counter(
    _tel.M_LEARNER_TASKS_TOTAL, "Train tasks by outcome",
    ("outcome",))
_M_EVALS = _REG.histogram(
    _tel.M_LEARNER_EVAL_DURATION_SECONDS, "Community-model evaluation time")
_M_REATTACH = _REG.counter(
    _tel.M_LEARNER_REATTACH_TOTAL,
    "Re-attach joins after a controller crash/restart was detected",
    ("reason",))
_M_MASK_GEN = _REG.histogram(
    _tel.M_SECURE_MASK_GEN_SECONDS,
    "Secure-uplink encode time per train task: fixed-point encoding + "
    "pairwise mask stream generation (secure/distributed.py)")


class ControllerProxy(Protocol):
    """Learner → controller transport."""

    def join(self, request: JoinRequest) -> JoinReply: ...
    def leave(self, learner_id: str, auth_token: str) -> bool: ...
    def task_completed(self, result: TaskResult) -> bool: ...


class Learner:
    def __init__(
        self,
        model_ops: FlaxModelOps,
        train_dataset: ArrayDataset,
        controller: ControllerProxy,
        val_dataset: Optional[ArrayDataset] = None,
        test_dataset: Optional[ArrayDataset] = None,
        hostname: str = "localhost",
        port: int = 0,
        secure_backend=None,
    ):
        self.model_ops = model_ops
        self.datasets: Dict[str, Optional[ArrayDataset]] = {
            "train": train_dataset,
            "valid": val_dataset,
            "test": test_dataset,
        }
        self.controller = controller
        self.hostname = hostname
        self.port = port
        self.secure_backend = secure_backend

        self.learner_id: str = ""
        self.auth_token: str = ""
        # controller incarnation id observed at (re)join; a different
        # epoch in a later task envelope means the controller crashed and
        # restarted → re-attach before proceeding
        self.controller_epoch: str = ""
        # invoked with the JoinReply after every reattach join —
        # __main__ points this at credential persistence so an identity
        # refreshed mid-run survives the NEXT learner restart too
        self.on_join: Optional[Callable[[JoinReply], None]] = None
        # bounded reattach loop (tests tighten these)
        self.reattach_retries = 10
        self.reattach_backoff_s = 1.0
        # deliberate departure: a straggling completion rejected AFTER
        # leave_federation must not re-register us behind the operator's
        # back (reset by the next explicit join)
        self._left = False
        self._executor = ThreadPoolExecutor(max_workers=1,
                                            thread_name_prefix="learner-train")
        self._cancel = threading.Event()
        self._task_lock = threading.Lock()
        self._current_future = None
        self._shutdown = threading.Event()
        # reference treedef for wire ↔ pytree (captured at construction),
        # and the same host arrays by wire name, in tree order
        self._treedef_like = model_ops.get_variables()
        self._template = pytree_to_named_tensors(self._treedef_like)
        # SCAFFOLD client control variate c_i (params-shaped, f32; zeros
        # until the first scaffold task). In-memory only: a restarted
        # learner restarts its variate at zero, which SCAFFOLD tolerates.
        self._scaffold_ci = None
        # top-k uplink error-feedback residuals {tensor name: flat f32}
        # (tensor/sparse.py). In-memory only: a restarted learner drops
        # deferred coordinates, which error feedback tolerates (they were
        # never acknowledged anywhere).
        self._ef_residual: Dict[str, np.ndarray] = {}
        # FedBN-style local parameters (TrainParams.local_tensor_regex):
        # matching tensors never ship and are retained at their local
        # values when absent from an incoming community model. Remembered
        # from the last train task so eval-task model loads merge too.
        # _local_values holds the learner's current copies: evals run on
        # fire-and-forget threads CONCURRENTLY with training, and the
        # engine's variable slot points at donated (deleted) buffers while
        # a train step is in flight — merging must never read it from an
        # eval thread. The dict is rebound atomically on the serialized
        # train thread only.
        self._local_regex: str = ""
        self._local_values: Dict[str, np.ndarray] = {}
        # the regex _local_values was snapshotted under: a widened regex
        # (controller reconfigured mid-run) must trigger a re-snapshot or
        # merges miss the newly-local names
        self._snapshot_regex: str = ""
        # Ship-only-trainable (TrainParams.ship_tensor_regex): only
        # matching tensors federate; community blobs carry just that
        # subset and non-matching tensors backfill from the
        # construction-time tree (_treedef_like — immutable, never
        # donated, so the merge is race-free from any thread). Contract:
        # every learner holds the identical frozen base.
        self._ship_regex: str = ""
        self._warned_unfrozen = False
        # The frozen base stays on the device. A leaf the round can
        # neither change nor ship (_resident_names) holds, once a task
        # has placed it, the construction-time value the backfill would
        # place again: the next task places, reads back, snapshots and
        # encodes the other leaves alone. This is what the last train
        # that returned vouches for — (ship regex, local regex, those
        # leaves' names, the engine's variables_epoch) — and None before
        # the first task and after a train that raised (its inputs were
        # donated). A task whose key differs (a regex or the freeze mask
        # changed, the tree was assigned from outside) places the whole
        # tree from the construction-time values and vouches anew.
        self._vouched: Optional[tuple] = None
        # device-utilization capture (telemetry/profile.py DeviceMonitor):
        # lazily constructed on the first train task whose params carry
        # device_stats=true — the opted-out hot path is one attribute
        # check on the TrainParams flag
        self._device_monitor = None

    # ------------------------------------------------------------------ #
    # membership
    # ------------------------------------------------------------------ #

    def join_federation(self, previous_id: str = "", auth_token: str = "") -> JoinReply:
        capabilities = {}
        party_index = getattr(self.secure_backend, "party_index", None)
        if party_index is not None and hasattr(self.secure_backend,
                                               "recovery_correction"):
            # masking dropout recovery: the controller needs to map learner
            # ids to mask party indices to request residual corrections
            capabilities["party_index"] = int(party_index)
        reply = self.controller.join(JoinRequest(
            hostname=self.hostname,
            port=self.port,
            num_train_examples=len(self.datasets["train"]),
            num_val_examples=len(self.datasets["valid"] or []),
            num_test_examples=len(self.datasets["test"] or []),
            previous_id=previous_id,
            auth_token=auth_token,
            capabilities=capabilities,
        ))
        self.learner_id = reply.learner_id
        self.auth_token = reply.auth_token
        if reply.controller_epoch:
            if (self.controller_epoch
                    and reply.controller_epoch != self.controller_epoch):
                # journal the incarnation change: a post-mortem reading
                # this learner's ring can tell WHICH controller each of
                # its tasks belonged to
                _tevents.emit(_tevents.EpochChanged,
                              learner_id=reply.learner_id,
                              old_epoch=self.controller_epoch[:8],
                              new_epoch=reply.controller_epoch[:8],
                              reason="join_reply")
            self.controller_epoch = reply.controller_epoch
        self._left = False
        return reply

    def leave_federation(self) -> bool:
        if not self.learner_id:
            return False
        self._left = True
        return self.controller.leave(self.learner_id, self.auth_token)

    # ------------------------------------------------------------------ #
    # controller-failover re-attach
    # ------------------------------------------------------------------ #

    def reattach(self, reason: str) -> bool:
        """Re-run ``join_federation`` as ourselves after losing the
        controller (persistent UNAVAILABLE, auth rejection, or an epoch
        mismatch in a task envelope). A restarted controller that
        checkpointed its registry recognizes the (previous_id, token)
        pair and keeps our identity — including the masking/SCAFFOLD
        party index; one that lost it assigns a fresh identity, which we
        adopt (and hand to ``on_join`` for persistence)."""
        previous_id, token = self.learner_id, self.auth_token
        for attempt in range(1, max(1, self.reattach_retries) + 1):
            if self._shutdown.is_set():
                return False
            try:
                reply = self.join_federation(previous_id=previous_id,
                                             auth_token=token)
            except Exception as exc:  # noqa: BLE001 - retried
                logger.warning("%s: re-attach attempt %d/%d failed: %s",
                               previous_id, attempt, self.reattach_retries,
                               exc)
                self._shutdown.wait(self.reattach_backoff_s)
                continue
            _M_REATTACH.inc(reason=reason)
            logger.info(
                "%s: re-attached to controller (epoch %s, rejoined=%s, "
                "reason=%s)", self.learner_id,
                (reply.controller_epoch or "?")[:8], reply.rejoined, reason)
            if self.on_join is not None:
                try:
                    self.on_join(reply)
                except Exception:  # noqa: BLE001 - persistence best-effort
                    logger.exception("on_join callback failed")
            return True
        logger.error("%s: re-attach gave up after %d attempts (reason=%s)",
                     previous_id, self.reattach_retries, reason)
        return False

    def _check_controller_epoch(self, task_epoch: str) -> None:
        """A task stamped with a different controller incarnation than the
        one we joined: the controller restarted (and restored our
        registration well enough to dispatch to us) — refresh the
        registration instead of trusting the stale one."""
        if (task_epoch and self.controller_epoch
                and task_epoch != self.controller_epoch):
            logger.warning(
                "%s: task from controller epoch %s but joined under %s — "
                "re-attaching", self.learner_id, task_epoch[:8],
                self.controller_epoch[:8])
            _tevents.emit(_tevents.EpochChanged,
                          learner_id=self.learner_id,
                          old_epoch=self.controller_epoch[:8],
                          new_epoch=task_epoch[:8],
                          reason="task_envelope")
            self.reattach("epoch_mismatch")

    def _report_completion(self, result: TaskResult) -> bool:
        """Deliver a TaskResult, surviving a controller crash between
        dispatch and completion: on transport failure or rejection,
        re-attach and resubmit once under the refreshed credentials. The
        in-flight round's work is preserved — the new controller
        incarnation stores the model like any other contribution."""
        try:
            if self.controller.task_completed(result):
                return True
            if self._left or self._shutdown.is_set():
                # rejected because WE left / are shutting down — not a
                # controller failure; do not re-register ourselves
                return False
            reason = "completion_rejected"
            logger.warning("%s: completion for task %s rejected; "
                           "re-attaching", self.learner_id, result.task_id)
        except Exception as exc:  # noqa: BLE001 - transport failure
            if self._left or self._shutdown.is_set():
                # departed/stopping learners never re-register themselves,
                # whether the delivery was rejected OR undeliverable
                return False
            reason = "completion_unavailable"
            logger.warning("%s: completion delivery for task %s failed "
                           "(%s); re-attaching", self.learner_id,
                           result.task_id, exc)
        if not self.reattach(reason):
            logger.error("%s: dropping result for task %s (re-attach "
                         "failed)", self.learner_id, result.task_id)
            return False
        result = dataclasses.replace(result, learner_id=self.learner_id,
                                     auth_token=self.auth_token)
        try:
            return bool(self.controller.task_completed(result))
        except Exception:  # noqa: BLE001 - the round deadline recovers
            logger.exception("%s: completion resubmit failed for task %s",
                             self.learner_id, result.task_id)
            return False

    # ------------------------------------------------------------------ #
    # model wire I/O (+ optional HE)
    # ------------------------------------------------------------------ #

    def _load_model(self, blob_bytes: bytes, with_wire: bool = False,
                    resident: frozenset = frozenset()):
        """Decode (and decrypt) a model blob → variables pytree, restored
        to the engine's own training dtypes (a community model may arrive
        in a narrower wire dtype — TrainParams.ship_dtype). With
        ``resident`` (leaves the engine kept on the device,
        ``_resident_names``) the result is the OTHER leaves alone, as
        named tensors in tree order: nothing is backfilled for a leaf
        that will not be placed. With
        ``with_wire`` also returns the exact wire-dtype tensors by name:
        the top-k sparsifier must difference against what the controller
        densifies against (its exact f32 community model), not the
        engine-dtype cast — with bf16 training dtypes the cast would bake
        the base weights' rounding into every shipped coordinate as a
        systematic error the error-feedback residual never sees."""
        blob = ModelBlob.from_bytes(blob_bytes)
        if blob.opaque:
            if self.secure_backend is None:
                raise RuntimeError("received encrypted model without a backend")
            named = []
            for name, (payload, spec) in blob.opaque.items():
                flat = self.secure_backend.decrypt(payload, spec.size)
                from metisfl_tpu.tensor.spec import np_dtype_of
                named.append((name, np.asarray(flat, np_dtype_of(spec.dtype))
                              .reshape(spec.shape)))
        else:
            named = blob.tensors
        named = self._merge_frozen(self._merge_local(named), resident)

        def restore(a, t):
            return a if a.dtype == t.dtype else np.asarray(a, t.dtype)

        if resident:
            by_name = dict(named)
            want = [(n, t) for n, t in self._template if n not in resident]
            missing = [n for n, _ in want if n not in by_name]
            if missing or len(by_name) != len(named):
                raise KeyError("model blob is missing tensors "
                               f"{missing[:5]} or names one twice")
            loaded = [(n, restore(by_name[n], t)) for n, t in want]
        else:
            import jax

            loaded = jax.tree.map(
                restore, named_tensors_to_pytree(named, self._treedef_like),
                self._treedef_like)
        if with_wire:
            return loaded, {n: np.asarray(a) for n, a in named}
        return loaded

    def _merge_local(self, named):
        """FedBN merge (Li et al., ICLR 2021): tensors the federation
        leaves local (local_tensor_regex) are absent from community blobs
        after round 1 — fill them from this learner's own snapshot copies
        (_local_values) so the reconstructed tree is complete and
        personalized. Reads only the snapshot dict, never the live engine
        slot (see the field comment: concurrent evals vs donation)."""
        if not self._local_regex:
            return named
        have = {n for n, _ in named}
        out = list(named)
        for name, arr in self._local_values.items():
            if name not in have:
                out.append((name, arr))
        return out

    def _adopt_local_regex(self, regex: str) -> None:
        """Adopt the FedBN regex from an eval/infer task (a learner that
        has never trained — not yet sampled, crash-rejoined — still
        receives partial round-2+ blobs; a reconfigured controller can
        also widen the regex mid-run). Snapshots from the live engine only
        when no train is in flight — the engine slot holds donated buffers
        mid-step — falling back to the construction-time initial values
        (never donated: every train replaces the slot via set_variables
        first), which the in-flight train's own post-run snapshot then
        supersedes."""
        if regex:
            self._local_regex = regex
        if not self._local_regex or self._snapshot_regex == self._local_regex:
            return
        with self._task_lock:
            # check AND snapshot under the task lock: run_task also
            # submits under it, so no train can start (and begin donating
            # the engine buffers) between the busy check and the engine
            # read — and a train submitted after our snapshot will
            # re-snapshot itself post-run, so ordering stays correct
            fut = self._current_future
            if fut is None or fut.done():
                self._snapshot_local()
                return
        values = {
            name: np.array(arr)
            for name, arr in self._template
            if re.search(self._local_regex, name)
        }
        with self._task_lock:
            # the in-flight train may have finished and run its own
            # post-run _snapshot_local while we built the fallback from
            # initial values; that snapshot is fresher — writing ours over
            # it would have evals merge untrained tensors until the next
            # train lands. A landed snapshot sets _snapshot_regex, so only
            # install the fallback while it is still unset.
            if self._snapshot_regex != self._local_regex:
                self._local_values = values
                self._snapshot_regex = self._local_regex

    def _snapshot_local(self) -> None:
        """Refresh _local_values from the engine. Call ONLY on the
        serialized train-executor thread with no train step in flight."""
        if not self._local_regex:
            self._local_values = {}
            self._snapshot_regex = ""
            return
        self._local_values = {
            name: np.array(arr)
            for name, arr in self._engine_leaves(
                {n for n, _ in self._template
                 if re.search(self._local_regex, n)})
        }
        self._snapshot_regex = self._local_regex

    def _engine_leaves(self, names) -> NamedTensors:
        """Host copies of the engine's leaves of those names; an engine
        that reads named leaves (FlaxModelOps) reads back no others."""
        if hasattr(self.model_ops, "place_variables"):
            return self.model_ops.get_variables(names)
        return [(n, a) for n, a in pytree_to_named_tensors(
            self.model_ops.get_variables()) if n in names]

    def _merge_frozen(self, named, resident: frozenset = frozenset()):
        """Ship-only-trainable backfill: community blobs carry only the
        federated subset; fill non-matching names from the
        construction-time initial values (but for ``resident`` ones,
        which hold those values on the device already). Strictly gated on
        the ship regex — and only NON-matching names backfill, so a
        corrupt blob missing a federated tensor still fails loudly
        downstream."""
        if not self._ship_regex:
            return named
        have = {n for n, _ in named} | resident
        out = list(named)
        for name, arr in self._template:
            if name not in have and not re.search(self._ship_regex, name):
                out.append((name, arr))
        return out

    def _resident_names(self) -> frozenset:
        """The leaves a round can neither change nor ship, so that their
        value on the device stays the construction-time one: not matched
        by the ship regex, under the engine's own freeze mask (in no
        collection the step mutates: ``frozen_names``), and not local.
        Empty with no ship regex (a full-model federation: the whole-tree
        path) and with an engine that cannot place named leaves
        (multi-host ``LeaderOps`` broadcasts the whole blob). An unshipped
        leaf that is NOT frozen is never resident: it is reset on every
        receipt, as ever."""
        if not self._ship_regex or not hasattr(self.model_ops,
                                               "place_variables"):
            return frozenset()
        return frozenset(
            n for n in self.model_ops.frozen_names()
            if not re.search(self._ship_regex, n)
            and not (self._local_regex
                     and re.search(self._local_regex, n)))

    def _keep_ship(self, named):
        """Uplink filter: only ship_tensor_regex matches federate."""
        if not self._ship_regex:
            return named
        kept = [(n, a) for n, a in named
                if re.search(self._ship_regex, n)]
        if not kept:
            raise ValueError(
                f"ship_tensor_regex {self._ship_regex!r} matches no "
                "tensor — nothing would ever be aggregated")
        return kept

    def _drop_local(self, named):
        """Uplink filter: local tensors never ship."""
        if not self._local_regex:
            return named
        kept = [(n, a) for n, a in named
                if not re.search(self._local_regex, n)]
        if not kept:
            raise ValueError(
                f"local_tensor_regex {self._local_regex!r} matches every "
                "tensor — nothing would ever be aggregated")
        return kept

    def _shipped(self, variables=None) -> NamedTensors:
        """What federates of ``variables``: a whole tree, or the named
        leaves an engine read back (``train(..., read=names)``); by
        default the engine's whole tree."""
        if variables is None:
            variables = self.model_ops.get_variables()
        if not isinstance(variables, list):
            variables = pytree_to_named_tensors(variables)
        return self._keep_ship(self._drop_local(variables))

    def _dump_model(self, ship_dtype: str = "",
                    variables=None) -> bytes:
        named = self._shipped(variables)
        if self.secure_backend is not None:
            from metisfl_tpu.tensor.spec import TensorSpec, wire_dtype_of, TensorKind
            t0 = time.perf_counter()
            opaque = {}
            for name, arr in named:
                payload = self.secure_backend.encrypt(
                    np.asarray(arr, np.float64).ravel())
                spec = TensorSpec(arr.shape, wire_dtype_of(arr.dtype),
                                  TensorKind.CIPHERTEXT)
                opaque[name] = (payload, spec)
            _M_MASK_GEN.observe(time.perf_counter() - t0)
            return ModelBlob(opaque=opaque).to_bytes()
        if ship_dtype:
            from metisfl_tpu.tensor.quantize import SHIP_INT8Q, quantize_named

            if ship_dtype.lower() == SHIP_INT8Q:
                # int8 absmax quantization: 4x less uplink than f32; the
                # controller dequantizes before aggregating
                named = quantize_named(named)
            else:
                from metisfl_tpu.tensor.spec import narrow_named

                named = narrow_named(named, resolve_ship_dtype(ship_dtype))
        return ModelBlob(tensors=named).to_bytes()

    def _dump_sparse(self, wire_ref, variables, denom: int) -> bytes:
        """Top-k sparsified update vs the round's dispatched model, with
        error-feedback residuals carried across rounds (tensor/sparse.py);
        ~denom/2x less uplink than the dense f32 blob. ``wire_ref`` is the
        wire-dtype tensor dict from ``_load_model(..., with_wire=True)`` —
        the controller densifies against its exact community model, so the
        difference must be taken against the same bytes."""
        from metisfl_tpu.tensor.sparse import sparsify_update

        return ModelBlob(tensors=sparsify_update(
            self._shipped(variables), wire_ref, denom,
            self._ef_residual)).to_bytes()

    # ------------------------------------------------------------------ #
    # task execution
    # ------------------------------------------------------------------ #

    def run_task(self, task: TrainTask) -> None:
        """Non-blocking: cancels any running training, schedules this one."""
        if self._shutdown.is_set():
            return
        # capture the dispatch-time span context (the controller's round
        # span — via gRPC metadata cross-process, via the live contextvar
        # in-process): the train executor thread has its own contextvars
        # context, so the parent link must travel explicitly
        trace_ctx = _ttrace.current_context()
        # where the task's waterfall begins: the RPC is accepted here and
        # the task may wait for the train thread (the ``queued`` tile)
        accepted = (time.time(), time.perf_counter())
        with self._task_lock:
            if self._current_future is not None and not self._current_future.done():
                self._cancel.set()
            self._current_future = self._executor.submit(
                self._train_and_report, task, trace_ctx, accepted)

    def _train_and_report(self, task: TrainTask, trace_ctx=None,
                          accepted=None) -> None:
        self._cancel.clear()
        if accepted is None:
            accepted = (time.time(), time.perf_counter())
        queued_ms = (time.perf_counter() - accepted[1]) * 1e3
        task_sp = _ttrace.span(
            "learner.train", parent=trace_ctx,
            attrs={"task_id": task.task_id, "round": task.round_id,
                   "learner": self.learner_id,
                   "queued_ms": round(queued_ms, 3)})
        with task_sp, task_sp.activate():
            self._run_train_task(task, task_sp, accepted, queued_ms)
        # the whole task — load + train + dump + report — matching the
        # metric's end-to-end contract (learner.train_steps has its own
        # step histogram)
        _M_TRAIN_DURATION.observe(task_sp.duration_ms / 1e3)

    def _run_train_task(self, task: TrainTask, task_sp, accepted,
                        queued_ms: float) -> None:
        try:
            # on the serialized train thread, BEFORE paying for training:
            # a task from a restarted controller refreshes registration
            # first (the restart re-dispatches after rejoin, and that
            # fresh task supersedes this one via the cancel event)
            self._check_controller_epoch(task.controller_epoch)
            params = task.params
            # set BEFORE _load_model: round-2+ community blobs omit the
            # local tensors and the load must merge them back (snapshot
            # refreshes whenever the effective regex differs from the one
            # the current snapshot was taken under — no train step is in
            # flight on this serialized thread)
            self._local_regex = params.local_tensor_regex
            if self._local_regex != self._snapshot_regex:
                with self._task_lock:
                    self._snapshot_local()
            if params.local_tensor_regex:
                # fail BEFORE paying for local training (and before the
                # round stalls to its deadline): a regex that localizes
                # every tensor means nothing would ever aggregate.
                # _drop_local raises on exactly that condition.
                self._drop_local(self._template)
            self._ship_regex = params.ship_tensor_regex
            if self._ship_regex:
                # same fail-fast: a subset regex matching nothing means
                # nothing would ever aggregate
                self._keep_ship(self._template)
                # probe through wrappers (multi-host LeaderOps exposes the
                # real engine as .inner) so a correctly-frozen multi-host
                # federation is not nagged about a nonexistent problem
                engine = getattr(self.model_ops, "inner", self.model_ops)
                if not self._warned_unfrozen and not getattr(
                        engine, "_trainable_regex", ""):
                    self._warned_unfrozen = True
                    logger.warning(
                        "%s: ship_tensor_regex=%r but the engine has no "
                        "trainable_regex freeze mask — non-shipped tensors "
                        "train locally and are discarded every round "
                        "(reset to initial values on each receipt); freeze "
                        "them to save the wasted compute",
                        self.learner_id, self._ship_regex)
            from metisfl_tpu.tensor.sparse import parse_topk

            if params.ship_dtype:
                from metisfl_tpu.tensor.quantize import SHIP_INT8Q

                # fail a bad dtype name BEFORE paying for local training
                if (params.ship_dtype.lower() != SHIP_INT8Q
                        and parse_topk(params.ship_dtype) is None):
                    resolve_ship_dtype(params.ship_dtype)
            if params.profile_dir:
                # per-learner trace subdir: collision-freedom is owned by
                # the DeviceTracer's unique per-capture session dirs
                # (telemetry/profile.py — same-second starts used to
                # clobber each other); the subdir keeps captures
                # attributable to a learner at a glance
                import dataclasses as _dc
                import os as _os
                params = _dc.replace(
                    params, profile_dir=_os.path.join(
                        params.profile_dir,
                        self.learner_id or f"port_{self.port}"))
            topk_denom = (parse_topk(params.ship_dtype)
                          if params.ship_dtype else None)
            # the frozen base the engine keeps on the device: ``kept`` is
            # what of it the last train vouches for (see _vouched), and so
            # is not decoded, backfilled or placed by this task. SCAFFOLD's
            # variate and client-level DP want the whole tree on the host,
            # before and after: those tasks place and read all of it.
            resident = self._resident_names()
            key = (self._ship_regex, self._local_regex, resident)
            whole = bool(task.scaffold or task.control
                         or params.dp_clip_norm > 0.0)
            kept = frozenset()
            if resident and not whole and self._vouched == key + (
                    self.model_ops.variables_epoch,):
                kept = resident
            self._vouched = None
            wire_ref = None
            load_sp = _ttrace.span("learner.load_model",
                                   attrs={"bytes": len(task.model)})
            with load_sp:
                if topk_denom is not None and self.secure_backend is None:
                    incoming, wire_ref = self._load_model(
                        task.model, with_wire=True, resident=kept)
                else:
                    incoming = self._load_model(task.model, resident=kept)
            # host -> device: the whole tree, or the leaves not kept. No
            # sync marks its end: a copy still in flight when this returns
            # is waited for by what first needs the arrays (the engine's
            # eager optimizer init, which no tile claims: ``other``, or
            # the first program call: ``steps``)
            kept_bytes = sum(a.nbytes for n, a in self._template if n in kept)
            placed_bytes = sum(a.nbytes for _, a in self._template) - kept_bytes
            upload_sp = _ttrace.span(
                "learner.upload", attrs={"bytes": placed_bytes,
                                         "kept_bytes": kept_bytes})
            with upload_sp:
                if kept:
                    self.model_ops.place_variables(incoming)
                else:
                    self.model_ops.set_variables(incoming)
            grad_offset = None
            scaffold_c = None
            if task.scaffold or task.control:
                scaffold_c, grad_offset = self._scaffold_offset(task.control)
            elif self._scaffold_ci is not None:
                # the federation stopped running scaffold (e.g. controller
                # restarted under another rule): a stale variate must not
                # keep correcting gradients
                self._scaffold_ci = None
            # grad_offset rides as a kwarg only when present: multi-host
            # LeaderOps.train has no such parameter (scaffold + multi-host
            # is rejected at config time)
            train_kwargs = ({"grad_offset": grad_offset}
                            if grad_offset is not None else {})
            if resident and not whole:
                # read back what ships and no more (``kept`` or placed
                # anew, the base is not wanted on the host)
                train_kwargs["read"] = {
                    n for n, _ in self._shipped(self._template)}
            train_sp = _ttrace.span("learner.train_steps")
            # activated: the engine's train.feed / train.steps /
            # train.readback events (models/ops.py) parent under it
            with train_sp, train_sp.activate():
                out = self.model_ops.train(self.datasets["train"], params,
                                           cancel_event=self._cancel,
                                           **train_kwargs)
                # attrs must land BEFORE the span ends: end() is what
                # serializes the record to the sink
                train_sp.set_attr("steps", out.completed_steps)
                train_sp.set_attr("ms_per_step", round(out.ms_per_step, 3))
            if resident:
                # the train returned: the base is on the device, placed
                # from the construction-time values and frozen since
                self._vouched = key + (self.model_ops.variables_epoch,)
            if out.completed_steps > 0 and out.ms_per_step > 0:
                # a zero-step task (instant cancel, empty dataset) has no
                # step baseline
                _M_TRAIN_STEP_MS.observe(out.ms_per_step)
            # chaos 'slow' fault (chaos/injector.py): stretch this task's
            # wall-clock by the armed factor — a slow SURVIVOR, the churn
            # case only straggler deadlines / quorum barriers can defend
            # against (a dead wire is the retry ladder's job). One
            # attribute read + is-None check when chaos is off.
            from metisfl_tpu import chaos as _chaos
            injector = _chaos.get()
            if injector is not None:
                slow = injector.train_slowdown()
                if slow > 1.0:
                    time.sleep(min(300.0, (train_sp.duration_ms / 1e3)
                               * (slow - 1.0)))
            device_stats = {}
            if (getattr(params, "device_stats", False)
                    and out.completed_steps > 0 and out.ms_per_step > 0):
                device_stats = self._capture_device_stats(params, out)
            # training updated the local tensors (e.g. BatchNorm stats):
            # refresh the snapshot evals and later merges read from —
            # under the task lock so _adopt_local_regex's fallback install
            # can never interleave with (and overwrite) this fresh snapshot
            snapshot_sp = _ttrace.span("learner.snapshot")
            with snapshot_sp, self._task_lock:
                self._snapshot_local()
            # round-scoped mask derivation (pairwise-masking secure agg)
            if self.secure_backend is not None and hasattr(
                    self.secure_backend, "begin_round"):
                self.secure_backend.begin_round(task.round_id)
            if self._cancel.is_set():
                logger.info("%s: task %s cancelled", self.learner_id, task.task_id)
                _M_TASKS.inc(outcome="cancelled")
                task_sp.set_attr("outcome", "cancelled")
                return
            control_delta = b""
            if scaffold_c is not None:
                control_delta = self._scaffold_update(
                    incoming, params, out.completed_steps, scaffold_c)
            # what ships: the leaves ``train`` read back for it, or (None)
            # the engine's whole tree, read where it is dumped
            ship_vars = out.variables if "read" in train_kwargs else None
            if params.dp_clip_norm > 0.0:
                # client-level DP: clip + noise the update BEFORE any
                # encryption/masking or wire narrowing (secure/dp.py)
                from metisfl_tpu.secure.dp import privatize_update
                ship_vars = privatize_update(
                    self.model_ops.get_variables(), incoming,
                    params.dp_clip_norm, params.dp_noise_multiplier)
            dump_sp = _ttrace.span("learner.dump_model")
            with dump_sp:
                if wire_ref is not None:
                    model_bytes = self._dump_sparse(wire_ref, ship_vars,
                                                    topk_denom)
                else:
                    model_bytes = self._dump_model(
                        ship_dtype=params.ship_dtype, variables=ship_vars)
                dump_sp.set_attr("bytes", len(model_bytes))
            task_sp.set_attr("uplink_bytes", len(model_bytes))
            # the task's waterfall: contiguous tiles from the RPC's
            # acceptance to here, on this process's clock. ``other`` is
            # what no tile claims (scaffold, DP, secure masks, glue), so
            # they sum to the elapsed time by construction; no device
            # sync is added to find a tile's end.
            tiles = {"queued": queued_ms,
                     "load": load_sp.duration_ms,
                     "upload": upload_sp.duration_ms,
                     "feed": out.feed_ms, "steps": out.steps_ms,
                     "readback": out.readback_ms,
                     "snapshot": snapshot_sp.duration_ms,
                     "encode": dump_sp.duration_ms}
            task_ms = (time.perf_counter() - accepted[1]) * 1e3
            tiles["other"] = task_ms - sum(tiles.values())
            task_tiles = {k: round(v, 3) for k, v in tiles.items()}
            task_tiles["start"] = round(accepted[0], 6)
            # beside the tiles, what crossed host <-> device for them
            task_tiles.update(placed_bytes=placed_bytes,
                              kept_bytes=kept_bytes,
                              read_bytes=out.readback_bytes)
            result = TaskResult(
                task_id=task.task_id,
                learner_id=self.learner_id,
                auth_token=self.auth_token,
                controller_epoch=task.controller_epoch,
                round_id=task.round_id,
                model=model_bytes,
                num_train_examples=len(self.datasets["train"]),
                completed_steps=out.completed_steps,
                completed_epochs=out.completed_epochs,
                completed_batches=out.completed_batches,
                processing_ms_per_step=out.ms_per_step,
                train_metrics=out.train_metrics,
                epoch_metrics=out.epoch_metrics,
                control_delta=control_delta,
                device_stats=device_stats,
                task_tiles=task_tiles,
            )
            report_sp = _ttrace.span(
                "learner.report", attrs={"task_ms": round(task_ms, 3),
                                         "bytes": len(model_bytes)})
            with report_sp:
                self._report_completion(result)
            _M_TASKS.inc(outcome="completed")
            task_sp.set_attr("outcome", "completed")
        except Exception:
            _M_TASKS.inc(outcome="failed")
            task_sp.set_attr("outcome", "failed")
            logger.exception("%s: training task %s failed",
                             self.learner_id, task.task_id)

    def _capture_device_stats(self, params, out) -> Dict[str, float]:
        """Device-utilization snapshot for one train task (performance
        observatory): step-time EWMA, achieved-MFU estimate from the
        engine's FLOPs accounting, HBM watermark. A device query that
        fails raises like any other step of the task: a chip that stopped
        answering is not a zero in a gauge."""
        from metisfl_tpu.telemetry import profile as _tprofile

        if self._device_monitor is None:
            self._device_monitor = _tprofile.DeviceMonitor()
        flops = 0.0
        # probe through wrappers like the freeze-mask check above
        # (multi-host LeaderOps has no FLOPs accounting — no mfu sample)
        engine = getattr(self.model_ops, "inner", self.model_ops)
        step_flops = getattr(engine, "step_flops", None)
        if callable(step_flops):
            flops = float(step_flops(params.batch_size))
        # the module's own counters (``TrainOutput.counts``, a step) ride
        # beside ``ms_per_step`` into ``RoundProfile.learners[*].device``
        return {**getattr(out, "counts", {}),
                **self._device_monitor.observe(
                    steps=out.completed_steps, ms_per_step=out.ms_per_step,
                    flops_per_step=flops)}

    def _scaffold_offset(self, control_bytes: bytes):
        """(c, c - c_i) for this task — both params-shaped f32 trees.
        An empty control blob means the server variate is still zero
        (first rounds); c_i initializes to zeros on first use."""
        import jax

        params_tpl = self._treedef_like["params"]
        zeros = lambda: jax.tree.map(
            lambda p: np.zeros(np.shape(p), np.float32), params_tpl)
        if control_bytes:
            blob = ModelBlob.from_bytes(control_bytes)
            c = named_tensors_to_pytree(blob.tensors, params_tpl)
            c = jax.tree.map(lambda a: np.asarray(a, np.float32), c)
        else:
            c = zeros()
        if self._scaffold_ci is None:
            self._scaffold_ci = zeros()
        offset = jax.tree.map(lambda a, b: a - b, c, self._scaffold_ci)
        return c, offset

    def _scaffold_update(self, incoming, params_cfg, completed_steps: int,
                         c) -> bytes:
        """Option-II variate update (Karimireddy et al. eq. 4):
        c_i+ = c_i - c + (x - y_i) / (K * lr); ships dc = c_i+ - c_i.
        Assumes SGD local steps (the standard SCAFFOLD setting) — with an
        adaptive local optimizer the variate is a heuristic."""
        import jax

        k_lr = max(1, completed_steps) * float(params_cfg.learning_rate)
        x = incoming["params"]
        y = self.model_ops.get_variables()["params"]
        ci = self._scaffold_ci
        ci_new = jax.tree.map(
            lambda ci_l, c_l, x_l, y_l: ci_l - c_l
            + (np.asarray(x_l, np.float32) - np.asarray(y_l, np.float32))
            / k_lr,
            ci, c, x, y)
        dc = jax.tree.map(lambda a, b: a - b, ci_new, ci)
        self._scaffold_ci = ci_new
        return ModelBlob(tensors=pytree_to_named_tensors(dc)).to_bytes()

    def evaluate(self, task: EvalTask) -> EvalResult:
        """Blocking community-model evaluation over requested datasets."""
        t0 = time.time()
        eval_sp = _ttrace.span(
            "learner.eval", attrs={"task_id": task.task_id,
                                   "round": task.round_id,
                                   "learner": self.learner_id})
        with eval_sp, eval_sp.activate():
            self._check_controller_epoch(task.controller_epoch)
            self._adopt_local_regex(task.local_tensor_regex)
            # Unconditional, mirroring the train path:
            # never-trained learners get the regex from the task (backfill
            # reads the immutable construction tree — no snapshot needed),
            # and a task WITHOUT one clears any stale regex from an
            # earlier configuration instead of silently reactivating
            # subset semantics on a full blob.
            self._ship_regex = task.ship_tensor_regex
            # Evaluate on an explicit variables tree so a concurrently running
            # training task never races on the engine's model slot.
            variables = self._load_model(task.model)
            evaluations: Dict[str, Dict[str, float]] = {}
            for name in task.datasets:
                ds = self.datasets.get(name)
                if ds is None or len(ds) == 0:
                    continue
                evaluations[name] = self.model_ops.evaluate(
                    ds, task.batch_size, task.metrics, variables=variables)
        _M_EVALS.observe(eval_sp.duration_ms / 1e3)
        return EvalResult(
            task_id=task.task_id,
            learner_id=self.learner_id,
            round_id=task.round_id,
            evaluations=evaluations,
            duration_ms=(time.time() - t0) * 1e3,
        )

    def recover_masks(self, round_id: int, surviving, dropped,
                      lengths) -> list:
        """Masking dropout recovery (secure/masking.py): the residual mask
        of the round's dropped parties, computable by any survivor because
        the federation secret is shared. The controller subtracts it from
        the partial sum — the Bonawitz unmasking round as one RPC."""
        backend = self.secure_backend
        if backend is None or not hasattr(backend, "recovery_correction"):
            raise RuntimeError("this learner has no masking backend")
        return backend.recovery_correction(round_id, list(surviving),
                                           list(dropped), list(lengths))

    def infer(self, task: InferTask) -> InferResult:
        """Blocking inference on a shipped model (the reference learner's
        third task type, learner.py:311-330): predictions over explicit
        inputs or a named local split."""
        t0 = time.time()
        self._adopt_local_regex(task.local_tensor_regex)
        # unconditional, like run_eval: a regex-less task clears stale state
        self._ship_regex = task.ship_tensor_regex
        variables = self._load_model(task.model) if task.model else None
        if task.inputs:
            blob = ModelBlob.from_bytes(task.inputs)
            tensors = dict(blob.tensors)
            if "x" not in tensors:
                raise ValueError("InferTask.inputs must pack an 'x' tensor")
            x = tensors["x"]
        else:
            name = task.dataset or "test"
            ds = self.datasets.get(name)
            if ds is None or len(ds) == 0:
                raise ValueError(
                    f"inference requested on dataset {name!r} but this "
                    "learner has no such split (available: "
                    f"{[k for k, v in self.datasets.items() if v]})")
            x = ds.x
        if task.max_examples > 0:
            x = x[: task.max_examples]
        if task.generate_tokens > 0:
            # generation task: x is a (B, L) int prompt batch; the result
            # packs continuations, not logits. Chunked by batch_size like
            # the infer path — one unbounded (B, L+new) KV-cache program
            # over a whole split would blow device memory.
            prompts = np.asarray(x, np.int32)
            bs = max(1, int(task.batch_size))
            chunks = [
                self.model_ops.generate(
                    prompts[i : i + bs], task.generate_tokens,
                    variables=variables,
                    temperature=task.temperature, top_k=task.top_k,
                    top_p=task.top_p,
                    eos_id=None if task.eos_id < 0 else task.eos_id)
                for i in range(0, len(prompts), bs)
            ]
            preds = np.concatenate(chunks, axis=0)
        else:
            preds = self.model_ops.infer(x, task.batch_size,
                                         variables=variables)
        return InferResult(
            task_id=task.task_id,
            learner_id=self.learner_id,
            round_id=task.round_id,
            predictions=ModelBlob(
                tensors=[("predictions", np.asarray(preds))]).to_bytes(),
            num_examples=int(len(x)),
            duration_ms=(time.time() - t0) * 1e3,
        )

    def shutdown(self) -> None:
        self._shutdown.set()
        self._cancel.set()
        self._executor.shutdown(wait=True)
