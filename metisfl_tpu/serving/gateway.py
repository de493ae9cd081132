"""Serving gateway core: micro-batching, hot-swap, canary routing.

Design notes:

- **Micro-batching.** Concurrent requests coalesce into one forward pass:
  the batcher waits ``max_wait_ms`` from the first queued row (or until
  ``max_batch`` rows accumulate) and executes one padded forward. Every
  forward pads to exactly ``max_batch`` rows, so ONE jitted program
  serves every batch occupancy — no shape-churn recompiles — and each
  row's computation is identical whether it arrived alone or coalesced
  (per-row outputs of a fixed-shape forward do not depend on what else
  is in the batch), which is what makes the batched results bit-identical
  to unbatched ones (tests/test_serving.py pins it).
- **Hot-swap.** A channel's ``(version, variables)`` pair is replaced
  atomically under the gateway lock; a batch in flight already captured
  the old pair and completes on it, so no request is ever dropped or
  served a half-installed model.
- **Canary.** Requests carry a routing key; ``crc32(key) % 10000`` below
  ``canary_percent * 100`` routes to the ``candidate`` channel when one
  is installed. Deterministic: the same key always lands on the same
  side, so a session's traffic never flaps between models mid-canary.
"""

from __future__ import annotations

import functools
import logging
import threading
import time
import zlib
from concurrent import futures
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from metisfl_tpu import telemetry as _tel
from metisfl_tpu.registry import CHANNEL_CANDIDATE, CHANNEL_STABLE
from metisfl_tpu.telemetry import events as _tevents
from metisfl_tpu.telemetry import metrics as _tmetrics
from metisfl_tpu.telemetry import prof as _prof
from metisfl_tpu.telemetry import profile as _tprofile
from metisfl_tpu.telemetry import trace as _ttrace
from metisfl_tpu.tensor.pytree import (
    ModelBlob,
    named_tensors_to_pytree,
    pytree_to_named_tensors,
)

logger = logging.getLogger("metisfl_tpu.serving")

_REG = _tmetrics.registry()
_M_REQUESTS = _REG.counter(
    _tel.M_SERVING_REQUESTS_TOTAL, "Inference requests by routed channel",
    ("channel",))
_M_LATENCY = _REG.histogram(
    _tel.M_SERVING_REQUEST_LATENCY_SECONDS,
    "End-to-end request latency (enqueue -> reply)")
_M_BATCH_ROWS = _REG.histogram(
    _tel.M_SERVING_BATCH_ROWS,
    "Rows per executed micro-batch (occupancy of the max_batch bucket)",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024))
_M_VERSION = _REG.gauge(
    _tel.M_SERVING_MODEL_VERSION,
    "Registry version currently installed per channel", ("channel",))
_M_SWAPS = _REG.counter(
    _tel.M_SERVING_SWAPS_TOTAL, "Hot-swaps by channel", ("channel",))
_M_QUEUE_DEPTH = _REG.gauge(
    _tel.M_SERVING_QUEUE_DEPTH,
    "Requests currently queued per micro-batcher channel — the occupancy "
    "signal the round cost profile and fleet scale-out key on (series "
    "removed when the channel's batcher closes)", ("channel",))


def canary_channel(key: str, canary_percent: float) -> str:
    """Deterministic traffic split: the candidate channel owns the lowest
    ``canary_percent`` of the crc32 keyspace (basis-point resolution).
    Pure function of (key, percent) — tests and operators can predict any
    request's routing. Keyless requests serve stable: ``crc32(b"") == 0``
    sits inside EVERY canary slice, so defaulting them in would send
    100% of unkeyed traffic to the candidate the moment a canary arms."""
    if canary_percent <= 0.0 or not key:
        return CHANNEL_STABLE
    slot = zlib.crc32(key.encode("utf-8")) % 10000
    return (CHANNEL_CANDIDATE if slot < canary_percent * 100.0
            else CHANNEL_STABLE)


class _Pending:
    """One queued request: input rows + the future its caller blocks on."""

    __slots__ = ("rows", "future", "enqueued_at")

    def __init__(self, rows: np.ndarray):
        self.rows = rows
        self.future: "futures.Future" = futures.Future()
        self.enqueued_at = time.perf_counter()


class MicroBatcher:
    """Coalesce concurrent requests into padded fixed-size forwards.

    ``run_batch(rows)`` is the model-executing callback: it receives the
    concatenated request rows (<= max_batch of them) and returns per-row
    outputs. One worker thread per batcher drains the queue; requests
    above ``max_batch`` rows are chunked internally so a single fat
    request cannot wedge the queue."""

    def __init__(self, run_batch: Callable[[np.ndarray], np.ndarray],
                 max_batch: int = 8, max_wait_ms: float = 5.0,
                 name: str = "batcher"):
        self._run_batch = run_batch
        self.name = name
        self.max_batch = max(1, int(max_batch))
        self.max_wait_s = max(0.0, float(max_wait_ms)) / 1e3
        self._queue: List[_Pending] = []
        # condition over an instrumented lock (telemetry/prof.py):
        # submit-vs-drain contention on the micro-batch queue is
        # measured; the worker's wait() park re-acquires untimed
        self._cv = threading.Condition(_prof.lock("serving.queue"))
        self._closed = False
        self._worker = threading.Thread(target=self._loop, daemon=True,
                                        name=f"serving-{name}")
        self._worker.start()

    def submit(self, rows: np.ndarray) -> "futures.Future":
        rows = np.asarray(rows)
        if rows.ndim == 0:
            # reject on the caller's thread: a 0-d array has no len()
            # and would otherwise blow up inside the shared worker
            raise ValueError("batcher input must be at least 1-d "
                             "(a batch of rows)")
        pending = _Pending(rows)
        with self._cv:
            if self._closed:
                pending.future.set_exception(
                    RuntimeError("batcher closed"))
                return pending.future
            self._queue.append(pending)
            _M_QUEUE_DEPTH.set(len(self._queue), channel=self.name)
            self._cv.notify()
        return pending.future

    def depth(self) -> int:
        """Requests currently queued (the occupancy probe the round cost
        profile samples)."""
        with self._cv:
            return len(self._queue)

    def _gather(self) -> List[_Pending]:
        """Wait for work, then coalesce until the bucket is full or the
        wait window (from the FIRST request) expires."""
        with self._cv:
            while not self._queue and not self._closed:
                self._cv.wait(0.1)
            if self._closed and not self._queue:
                return []
            deadline = self._queue[0].enqueued_at + self.max_wait_s
            while (sum(len(p.rows) for p in self._queue) < self.max_batch
                   and not self._closed):
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                self._cv.wait(remaining)
            batch: List[_Pending] = []
            rows = 0
            while self._queue and (not batch
                                   or rows + len(self._queue[0].rows)
                                   <= self.max_batch):
                item = self._queue.pop(0)
                rows += len(item.rows)
                batch.append(item)
            _M_QUEUE_DEPTH.set(len(self._queue), channel=self.name)
            return batch

    def _loop(self) -> None:
        while True:
            batch = self._gather()
            if not batch:
                with self._cv:
                    if self._closed and not self._queue:
                        return
                continue
            try:
                self._execute(batch)
            except Exception as exc:  # noqa: BLE001 - worker must survive
                # one poisoned batch (shape-mismatched concat, anything
                # _execute's own guard missed) fails ITS requests only —
                # a dead worker would hang every later request on this
                # channel until its timeout
                logger.exception("micro-batch execution failed")
                for p in batch:
                    if not p.future.done():
                        p.future.set_exception(exc)

    def _execute(self, batch: List[_Pending]) -> None:
        try:
            rows = np.concatenate([p.rows for p in batch], axis=0)
            _M_BATCH_ROWS.observe(len(rows))
            outs = self._run_batch(rows)
        except Exception as exc:  # noqa: BLE001 - surfaced per request
            for p in batch:
                if not p.future.done():
                    p.future.set_exception(exc)
            return
        # run_batch may return (outs, extra) — extra (e.g. the model
        # version the forward actually captured) rides to every request
        # of the batch, so callers report the TRUE served version even
        # when a hot-swap lands between enqueue and execution
        extra = None
        if isinstance(outs, tuple):
            outs, extra = outs
        offset = 0
        for p in batch:
            n = len(p.rows)
            sliced = np.asarray(outs[offset:offset + n])
            p.future.set_result(sliced if extra is None
                                else (sliced, extra))
            offset += n

    def close(self) -> None:
        """Drain: queued requests still execute, then the worker exits."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        self._worker.join(timeout=30.0)
        # bounded cardinality: an uninstalled channel's depth series must
        # not linger in the exposition at its last value
        _M_QUEUE_DEPTH.remove(channel=self.name)


# --------------------------------------------------------------------- #
# the install's one cast program
# --------------------------------------------------------------------- #

@functools.lru_cache(maxsize=None)
def _cast_program():
    import jax

    return jax.jit(
        lambda arrays, types: [a.astype(t) for a, t in zip(arrays, types)],
        static_argnums=1)


def _cast(leaves: list, dtypes: list) -> list:
    """``leaves`` (device arrays) with each one that ``dtypes`` names held
    in that type instead: ONE jitted call over all of them, not awaited.
    Its float32 inputs are referred to from here alone, so they leave the
    device as the program ends."""
    chosen = [i for i, dtype in enumerate(dtypes) if dtype is not None]
    if chosen:
        narrow = _cast_program()([leaves[i] for i in chosen],
                                 tuple(dtypes[i] for i in chosen))
        for i, arr in zip(chosen, narrow):
            leaves[i] = arr
    return leaves


# --------------------------------------------------------------------- #
# registry sources (where the gateway learns about promoted versions)
# --------------------------------------------------------------------- #

class DirectRegistrySource:
    """In-process source: reads a live :class:`Controller` (tests, pod
    mode)."""

    def __init__(self, controller):
        self._controller = controller

    def describe(self) -> Dict[str, Any]:
        return self._controller.describe_registry()

    def blob(self, version: int) -> Optional[bytes]:
        return self._controller.registered_model(version)


class ControllerRegistrySource:
    """RPC source: polls the controller's DescribeRegistry /
    GetRegisteredModel surface (the gateway process's view)."""

    def __init__(self, client):
        self._client = client

    def describe(self) -> Dict[str, Any]:
        return self._client.describe_registry(timeout=15.0,
                                              wait_ready=False)

    def blob(self, version: int) -> Optional[bytes]:
        return self._client.get_registered_model(version=version,
                                                 timeout=60.0)


class ServingGateway:
    """Serve inference over registry channels. ``model_ops`` supplies the
    architecture + jitted forward (the same engine a learner trains
    with); ``config`` is a :class:`metisfl_tpu.config.ServingConfig`."""

    def __init__(self, model_ops, config, ship_tensor_regex: str = ""):
        self.model_ops = model_ops
        self.config = config
        self._ship_regex = ship_tensor_regex
        self._lock = _prof.lock("serving.gateway")
        # channel -> (version id, variables pytree)
        self._models: Dict[str, Tuple[int, Any]] = {}
        self._treedef_like = model_ops.get_variables()
        # per leaf the type this gateway holds it in, or None for the
        # engine's own (``_cast_dtypes``): read from the served program by
        # one abstract trace at the first install. Installs and the check
        # of a decoding module's plain forward (``_check_forward``) take
        # turns under ``_install_lock``; until that check a channel's blob
        # is kept (on the host, whole), so that a leaf can be had back in
        # the engine's type
        self._cast_once: Optional[list] = None
        self._forward_checked = False
        self._install_lock = threading.Lock()
        self._blobs: Dict[str, Tuple[int, bytes]] = {}
        self._batchers: Dict[str, MicroBatcher] = {}
        # continuous-batching decode engines (serving/decode.py), one per
        # channel, created lazily on the first Generate for that channel
        self._decoders: Dict[str, Any] = {}
        self._requests = 0
        self._shut_down = False
        self._started_at = time.time()
        self._sync_stop = threading.Event()
        self._sync_thread: Optional[threading.Thread] = None
        self._last_sync_error = ""
        # In-process deployments (gateway sharing the controller's
        # process, the test/InProcessFederation shape): register the
        # queue probe with the active profile collector so RoundProfiles
        # carry serving pressure next to training cost. The driver's
        # subprocess gateway has no collector in its process — no-op.
        coll = _tprofile.collector()
        if coll is not None and coll.serving_probe is None:
            coll.serving_probe = self.queue_snapshot

    # -- model install / hot-swap ------------------------------------- #

    def _traced_dtypes(self, decode: bool) -> list:
        """ONE abstract trace of a program this gateway serves (the decode
        call, or the engine's plain forward) and what it lets each leaf be
        held in (``models.generate.cast_once_dtypes``)."""
        from metisfl_tpu.models.generate import (cast_once_dtypes,
                                                 decode_call)
        ops = self.model_ops
        if decode:
            program, args = decode_call(ops.module)
        else:
            def program(variables, x):
                return ops._apply(variables, x, train=False)
            args = (ops.sample_spec,)
        return cast_once_dtypes(program, self._treedef_like, *args)

    def _cast_dtypes(self) -> list:
        """Which leaves this gateway serves in the module's compute type.
        It depends on the module alone, so it is read once, at the first
        install, and a hot-swap pays no trace. A module that lays out a
        decode state (``init_cache``) is read from its decode call, which
        prefill and step both are; its plain forward (``predict`` on an
        LM) is read when the first ``predict`` asks for it
        (``_check_forward``). Any other module is served through its
        forward alone, and that is the trace."""
        if self._cast_once is None:
            decodes = hasattr(self.model_ops.module, "init_cache")
            self._cast_once = self._traced_dtypes(decode=decodes)
            self._forward_checked = not decodes
        return self._cast_once

    def _check_forward(self) -> None:
        """Before the first ``predict`` of a decoding module is answered:
        trace its plain forward too. A leaf the decode call let the
        gateway cast and the forward uses otherwise (or casts to another
        type) goes back to the engine's type, and every channel is
        installed again from the blob kept for this. Where the two agree,
        as they do for both LMs of the zoo, nothing happens."""
        if self._forward_checked:
            return
        with self._install_lock:
            if self._forward_checked:
                return
            forward = self._traced_dtypes(decode=False)
            agreed = [dt if dt == fw else None
                      for dt, fw in zip(self._cast_dtypes(), forward)]
            if agreed != self._cast_once:
                self._cast_once = agreed
                for channel, (version, blob) in list(self._blobs.items()):
                    self._install_locked(channel, version, blob)
            self._forward_checked = True
            self._blobs.clear()

    def _load_variables(self, blob_bytes: bytes):
        """Community blob -> the variables to serve, on the device. Under
        ship_tensor_regex the blob carries only the federated subset:
        backfill the frozen base from the construction-time tree (the
        learner's _merge_frozen contract), then conform every leaf to the
        engine's type.

        The tree that is served is the module's own casts made once. A
        module that computes in bfloat16 over float32 parameters converts
        each kernel on every call; between installs the weights do not
        change, so the gateway makes that conversion here, on the device,
        for exactly the leaves the served programs use through it and
        nothing else (``_cast_dtypes``), and holds the result in place of
        the float32 leaf. The programs' converts are then the identity:
        every product sees the operands it saw before, each token reads
        half the weight bytes, and replies are those of the float32 tree
        to the last bit. A leaf a program uses in float32 stays float32
        (``lm_head`` multiplies the logits in float32 by design; norm
        scales; ``JambaLite``'s tied embedding, its mixers' thin
        projections, recurrence parameters and convolution); a module that
        computes in float32 has no such leaf and is served as before.

        What it costs: the tree is placed first (host -> device copies
        that need no GIL), the one trace of a gateway's life runs beneath
        them, then the chosen leaves are cast (``_cast``) and nothing is
        awaited: a request that arrives first waits on the device, not
        here."""
        import jax
        import jax.numpy as jnp

        named = list(ModelBlob.from_bytes(blob_bytes).tensors)
        if self._ship_regex:
            import re

            have = {n for n, _ in named}
            for name, arr in pytree_to_named_tensors(self._treedef_like):
                if name not in have and not re.search(self._ship_regex,
                                                      name):
                    named.append((name, arr))
        tree = named_tensors_to_pytree(named, self._treedef_like)
        tree = jax.tree.map(
            lambda a, t: a if a.dtype == t.dtype else np.asarray(a, t.dtype),
            tree, self._treedef_like)
        # device-convert ONCE at install: the engine's per-call
        # `jnp.asarray` then no-ops, instead of re-uploading the whole
        # model host->device on every executed micro-batch
        leaves, treedef = jax.tree.flatten(tree)
        placed = [jnp.asarray(leaf) for leaf in leaves]   # copies fly
        dtypes = self._cast_dtypes()          # ... beneath the trace
        return jax.tree.unflatten(treedef, _cast(placed, dtypes))

    def _held(self, variables) -> Dict[str, int]:
        """What a served tree holds: bytes and leaves in the module's
        compute type (cast once at install, ``_load_variables``) and kept
        in the engine's."""
        import jax

        held = dict.fromkeys(("cast_bytes", "kept_bytes", "cast_leaves",
                              "kept_leaves"), 0)
        for arr, like in zip(jax.tree.leaves(variables),
                             jax.tree.leaves(self._treedef_like)):
            kind = "kept" if arr.dtype == like.dtype else "cast"
            held[kind + "_bytes"] += int(arr.nbytes)
            held[kind + "_leaves"] += 1
        return held

    def install(self, channel: str, version: int, blob: bytes) -> None:
        """Atomically hot-swap ``channel`` to ``version``. Decoding,
        placing and the cast to the served types (the slow part) happen
        OUTSIDE the gateway's lock; in-flight batches keep the pair they
        already captured, so zero requests drop across the swap.

        Span ``serving.install`` runs from the blob in hand to the swap;
        its attrs and ``describe()["weights"][channel]`` say what the
        channel then holds (``_held``). An install costs one placement of
        the tree and one cast program; a gateway's first also the one
        abstract trace that chooses the leaves (``_cast_dtypes``)."""
        with self._install_lock:
            self._install_locked(channel, version, blob)

    def _install_locked(self, channel: str, version: int,
                        blob: bytes) -> None:
        with _ttrace.span("serving.install",
                          attrs={"channel": channel,
                                 "version": int(version)}) as sp:
            variables = self._load_variables(blob)
            for key, value in self._held(variables).items():
                sp.set_attr(key, value)
            with self._lock:
                previous = self._models.get(channel, (0, None))[0]
                self._models[channel] = (int(version), variables)
                decoder = self._decoders.get(channel)
                if not self._forward_checked:
                    self._blobs[channel] = (int(version), blob)
            if decoder is not None:
                # the decode loop's zero-drop swap: in-flight generations
                # finish on the pair they captured, queued ones drain onto
                # this one (serving/decode.py)
                decoder.swap(int(version), variables)
        _M_VERSION.set(int(version), channel=channel)
        if previous != version:
            _M_SWAPS.inc(channel=channel)
            _tevents.emit(_tevents.ServingSwapped, channel=channel,
                          version=int(version), previous=previous)
            logger.info("serving %s hot-swapped to v%d (was v%d)",
                        channel, version, previous)

    def uninstall(self, channel: str) -> None:
        # after any install under way: ``_check_forward`` must not bring
        # a channel back from a blob that was on its way out
        with self._install_lock, self._lock:
            gone = self._models.pop(channel, None)
            decoder = self._decoders.pop(channel, None)
            self._blobs.pop(channel, None)
        if decoder is not None:
            # drain: queued/in-flight generations on the departing
            # channel still finish on their captured pair
            decoder.close()
        if gone is not None:
            _M_VERSION.remove(channel=channel)
            logger.info("serving %s uninstalled (was v%d)", channel,
                        gone[0])

    def installed(self) -> Dict[str, int]:
        with self._lock:
            return {ch: v for ch, (v, _) in self._models.items()}

    # -- registry sync ------------------------------------------------- #

    def sync(self, source) -> Dict[str, int]:
        """One poll: compare channel heads against the registry source and
        hot-swap any channel whose head changed. Returns the installed
        map after the poll."""
        desc = source.describe()
        if not desc.get("enabled", False):
            return self.installed()
        current = self.installed()
        for channel in (CHANNEL_STABLE, CHANNEL_CANDIDATE):
            head = int(desc.get(channel, 0) or 0)
            if not head:
                if channel == CHANNEL_CANDIDATE and channel in current:
                    # promoted or superseded away: stop canarying it
                    self.uninstall(channel)
                continue
            if current.get(channel) == head:
                continue
            blob = source.blob(head)
            if blob:
                self.install(channel, head, blob)
        return self.installed()

    def start_sync(self, source, poll_every_s: Optional[float] = None,
                   initial_delay_s: float = 0.0) -> None:
        """Background registry polling (the gateway process's main loop).
        ``initial_delay_s`` phases the FIRST poll — fleet replicas pass
        :func:`metisfl_tpu.serving.fleet.poll_stagger` offsets so a
        promotion rolls through the fleet one replica at a time instead
        of every replica hitting the registry in the same instant."""
        period = (self.config.poll_every_s if poll_every_s is None
                  else poll_every_s)

        def _loop():
            if initial_delay_s > 0.0:
                self._sync_stop.wait(initial_delay_s)
            while not self._sync_stop.is_set():
                try:
                    self.sync(source)
                    self._last_sync_error = ""
                except Exception as exc:  # noqa: BLE001 - keep polling
                    self._last_sync_error = str(exc)
                    logger.warning("registry sync failed: %s", exc)
                self._sync_stop.wait(max(0.05, period))

        self._sync_thread = threading.Thread(target=_loop, daemon=True,
                                             name="serving-sync")
        self._sync_thread.start()

    # -- request path --------------------------------------------------- #

    def _batcher_for(self, channel: str) -> MicroBatcher:
        with self._lock:
            if self._shut_down:
                # a Predict racing shutdown must not resurrect a worker
                # thread on a torn-down gateway
                raise RuntimeError("serving gateway is shut down")
            batcher = self._batchers.get(channel)
            if batcher is None:
                batcher = MicroBatcher(
                    lambda rows, ch=channel: self._forward(ch, rows),
                    max_batch=self.config.max_batch,
                    max_wait_ms=self.config.max_wait_ms,
                    name=channel)
                self._batchers[channel] = batcher
            return batcher

    def _forward(self, channel: str,
                 rows: np.ndarray) -> Tuple[np.ndarray, Tuple[int, str]]:
        """One padded fixed-shape forward per ``max_batch`` chunk. The
        (version, variables) pair is captured once per call — a hot-swap
        mid-batch affects the NEXT batch, never this one — and the
        captured (version, channel) rides back so replies report what
        ACTUALLY served them, fallback included."""
        with self._lock:
            entry = self._models.get(channel)
            if entry is None and channel == CHANNEL_CANDIDATE:
                # the candidate was uninstalled (promoted/superseded)
                # between routing and execution: degrade the queued
                # canary batch to stable instead of failing user traffic
                channel = CHANNEL_STABLE
                entry = self._models.get(channel)
        if entry is None:
            raise RuntimeError(f"no model installed on channel {channel!r}")
        version, variables = entry
        bucket = self.config.max_batch
        outs = []
        for start in range(0, len(rows), bucket):
            chunk = rows[start:start + bucket]
            pad = bucket - len(chunk)
            if pad > 0:
                chunk = np.concatenate(
                    [chunk, np.repeat(chunk[-1:], pad, axis=0)], axis=0)
            # batch_size=bucket: the engine sees exactly one fixed-shape
            # program however the rows were coalesced
            full = self.model_ops.infer(chunk, batch_size=bucket,
                                        variables=variables)
            outs.append(np.asarray(full)[:bucket - pad if pad else bucket])
        return np.concatenate(outs, axis=0), (version, channel)

    def predict(self, x: np.ndarray, key: str = "",
                timeout_s: float = 60.0) -> Tuple[np.ndarray, int, str]:
        """Route, micro-batch, and run one request. Returns
        ``(outputs, served version, channel)``."""
        t0 = time.perf_counter()
        channel = canary_channel(key or "", self.config.canary_percent)
        with self._lock:
            if channel not in self._models:
                # canary slice with no candidate installed (or a gateway
                # relaunched mid-canary): serve stable — degrading the
                # canary beats failing user traffic
                channel = CHANNEL_STABLE
            entry = self._models.get(channel)
        if entry is None:
            raise RuntimeError("no model installed (registry has no "
                               "stable version yet)")
        self._check_forward()
        # the batcher worker runs on its own thread: the span brackets
        # submit→result on THIS thread, which is the request's true wait
        with _ttrace.span("serving.predict", attrs={"channel": channel}):
            outs, (version, served_channel) = self._batcher_for(
                channel).submit(np.asarray(x)).result(timeout=timeout_s)
        with self._lock:
            self._requests += 1
        # label by what ACTUALLY served it: a canary request degraded to
        # stable mid-swap must not skew candidate traffic analytics
        _M_REQUESTS.inc(channel=served_channel)
        _M_LATENCY.observe(time.perf_counter() - t0)
        return outs, version, served_channel

    def _decoder_for(self, channel: str):
        """The channel's continuous-batching decode engine, created on
        first use from the channel's installed (version, variables)
        pair (serving/decode.py)."""
        from metisfl_tpu.serving.decode import ContinuousBatcher
        with self._lock:
            if self._shut_down:
                raise RuntimeError("serving gateway is shut down")
            decoder = self._decoders.get(channel)
            if decoder is None:
                entry = self._models.get(channel)
                if entry is None:
                    raise RuntimeError(
                        f"no model installed on channel {channel!r}")
                version, variables = entry
                decode_cfg = getattr(self.config, "decode", None)
                decoder = ContinuousBatcher(
                    self.model_ops, version, variables,
                    slots=getattr(decode_cfg, "slots", 4),
                    max_len=getattr(decode_cfg, "max_len", 512),
                    channel=channel)
                self._decoders[channel] = decoder
            return decoder

    def generate(self, prompt, max_new_tokens: int, key: str = "",
                 eos_id: Optional[int] = None,
                 timeout_s: float = 120.0) -> Tuple[np.ndarray, int, str]:
        """Route one generation request through the continuous-batching
        decode loop. Returns ``(tokens, served version, channel)`` —
        tokens are the (max_new_tokens,) greedy continuation, pad after
        eos (bit-identical to a solo models/generate.py call at the
        same max_len)."""
        t0 = time.perf_counter()
        channel = canary_channel(key or "", self.config.canary_percent)
        with self._lock:
            if channel not in self._models:
                channel = CHANNEL_STABLE  # same degrade rule as predict
            if channel not in self._models:
                raise RuntimeError("no model installed (registry has no "
                                   "stable version yet)")
        # activated (not just opened): the decode loop retires slots on
        # its own thread, so ContinuousBatcher.submit must capture the
        # ambient context here to parent the decode.slot span
        gen_sp = _ttrace.span("serving.generate",
                              attrs={"channel": channel})
        with gen_sp, gen_sp.activate():
            try:
                tokens, version = self._decoder_for(channel).submit(
                    prompt, max_new_tokens,
                    eos_id=eos_id).result(timeout=timeout_s)
            except RuntimeError:
                # the candidate was uninstalled (promoted/superseded)
                # between routing and decode — its engine is gone or
                # drained closed: degrade the canary request to stable
                # instead of failing user traffic, predict()'s exact rule
                if channel != CHANNEL_CANDIDATE:
                    raise
                channel = CHANNEL_STABLE
                with self._lock:
                    if channel not in self._models:
                        raise RuntimeError(
                            "no model installed (registry has no stable "
                            "version yet)") from None
                tokens, version = self._decoder_for(channel).submit(
                    prompt, max_new_tokens,
                    eos_id=eos_id).result(timeout=timeout_s)
        with self._lock:
            self._requests += 1
        _M_REQUESTS.inc(channel=channel)
        _M_LATENCY.observe(time.perf_counter() - t0)
        return tokens, version, channel

    # -- status --------------------------------------------------------- #

    def describe(self) -> Dict[str, Any]:
        with self._lock:
            models = dict(self._models)
            requests = self._requests
            decoders = dict(self._decoders)
        out = {
            "installed": {ch: v for ch, (v, _) in models.items()},
            # per channel: bytes and leaves held in the module's compute
            # type (cast once at install) and kept in the engine's
            "weights": {ch: self._held(v) for ch, (_, v) in models.items()},
            "canary_percent": float(self.config.canary_percent),
            "max_batch": int(self.config.max_batch),
            "max_wait_ms": float(self.config.max_wait_ms),
            "requests": requests,
            "uptime_s": round(time.time() - self._started_at, 3),
            "last_sync_error": self._last_sync_error,
        }
        if decoders:
            # continuous-batching decode section (serving/decode.py) —
            # present only once a Generate armed an engine, so pre-decode
            # gateways describe byte-identically to before
            out["decode"] = {ch: d.describe()
                             for ch, d in decoders.items()}
        return out

    def queue_snapshot(self) -> Dict[str, Any]:
        """Micro-batch queue occupancy (per channel + total) — wired as
        the profile collector's ``serving_probe`` in in-process
        deployments so RoundProfiles carry serving pressure next to
        training cost."""
        with self._lock:
            batchers = dict(self._batchers)
            decoders = dict(self._decoders)
        depths = {ch: b.depth() for ch, b in batchers.items()}
        out = {"queue_depth": sum(depths.values()),
               "queue_depth_by_channel": depths,
               "max_batch": int(self.config.max_batch)}
        if decoders:
            out["decode_queue_depth"] = sum(d.depth()
                                            for d in decoders.values())
            out["decode_active_slots"] = sum(d.active()
                                             for d in decoders.values())
        return out

    def shutdown(self) -> None:
        coll = _tprofile.collector()
        if coll is not None and coll.serving_probe == self.queue_snapshot:
            coll.serving_probe = None
        self._sync_stop.set()
        if self._sync_thread is not None:
            self._sync_thread.join(timeout=10.0)
        with self._lock:
            self._shut_down = True
            batchers = list(self._batchers.values())
            self._batchers.clear()
            decoders = list(self._decoders.values())
            self._decoders.clear()
        for batcher in batchers:
            batcher.close()
        for decoder in decoders:
            decoder.close()
