"""Continuous-batching autoregressive decode for the serving gateway.

Orca-style (Yu et al., OSDI 2022) iteration-level scheduling over the
KV-cache decode loop :mod:`metisfl_tpu.models.generate` already jits:
the gateway's ``Generate`` endpoint feeds a slot-based in-flight batch
where finished sequences retire and queued prompts join **at step
granularity** — a late-arriving prompt prefills between two decode
steps of the running batch instead of waiting for the whole batch to
finish. The decode step itself stays ONE jitted program at fixed slot
shapes (:class:`~metisfl_tpu.models.generate.SlotDecoder`), so
admission and retirement never recompile anything.

Hot-swap follows the gateway's zero-drop contract: a ``swap()`` marks a
pending (version, variables) pair; the in-flight batch FINISHES on the
pair it captured (one shared-variables program cannot mix versions
mid-batch), admission pauses, and the queue drains onto the new pair —
no request is dropped, every reply reports the version that actually
decoded it.

Greedy only by contract (temperature sampling inside a shared batch
would draw from per-slot rng streams no single-request call could
reproduce); output is bit-identical to a solo
:func:`metisfl_tpu.models.generate.generate` call at the same
``max_len`` (tests/test_fleet.py pins it).
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from concurrent import futures
from typing import Any, Dict, List, Optional

import numpy as np

from metisfl_tpu import telemetry as _tel
from metisfl_tpu.models.generate import SlotDecoder, cache_bytes_by_kind
from metisfl_tpu.telemetry import metrics as _tmetrics
from metisfl_tpu.telemetry import prof as _prof
from metisfl_tpu.telemetry import trace as _ttrace

logger = logging.getLogger("metisfl_tpu.serving")

_REG = _tmetrics.registry()
_M_DECODE_QUEUE = _REG.gauge(
    _tel.M_SERVING_DECODE_QUEUE_DEPTH,
    "Generation requests queued for a free decode slot, per channel "
    "(series removed when the channel's decode engine closes)",
    ("channel",))
_M_DECODE_SLOTS = _REG.gauge(
    _tel.M_SERVING_DECODE_ACTIVE_SLOTS,
    "Decode slots currently occupied by in-flight sequences, per channel",
    ("channel",))
_M_DECODE_TOKENS = _REG.counter(
    _tel.M_SERVING_DECODE_TOKENS_TOTAL,
    "Tokens emitted by the continuous-batching decode loop", ("channel",))
_M_DECODE_CACHE = _REG.gauge(
    _tel.M_SERVING_DECODE_CACHE_BYTES,
    "Device bytes the decode slots' state holds, per channel and kind "
    "(kv: keys and values, grows with max_len; state: recurrent state of "
    "state-space blocks, fixed)", ("channel", "kind"))

PAD_ID = 0

# what the decode loop keeps of its own time, per tick: seconds inside
# ``SlotDecoder.step`` (entry to tokens on the host, on the decoder's own
# stamps, ``last_call``), seconds inside ``prefill``, the rest of the tick
# on the host (admission under the lock, token hand-out, retirement),
# seconds parked with no queue and no active slot (in neither side of a
# host share), and counts; the rest are the decoder's own: calls that
# updated the slots' cache in place (``donated_calls`` = ``steps`` +
# ``prefills``), failed calls after which the slots were started over, and
# each kind of call cut in two, launch (entry to enqueued) and read (to the
# tokens on the host): ``step_launch_s`` + ``step_read_s`` = ``step_s``,
# and the same for prefill.
DECODER_SUMS = ("donated_calls", "cache_resets", "step_launch_s",
                "step_read_s", "prefill_launch_s", "prefill_read_s")
LOOP_SUMS = ("step_s", "prefill_s", "host_s", "parked_s", "ticks",
             "steps", "prefills", "admitted", "retired") + DECODER_SUMS
LOOP_EVENT_EVERY_S = 1.0


class _GenPending:
    """One queued generation request + the future its caller blocks on."""

    __slots__ = ("prompt", "max_new", "eos_id", "future", "enqueued_at",
                 "admitted_step", "trace_ctx", "wait_ms", "prefill_ms")

    def __init__(self, prompt: np.ndarray, max_new: int,
                 eos_id: Optional[int]):
        self.prompt = prompt
        self.max_new = int(max_new)
        self.eos_id = eos_id
        self.future: "futures.Future" = futures.Future()
        self.enqueued_at = time.perf_counter()
        self.admitted_step = -1          # step index at admission (test pin)
        self.wait_ms = 0.0               # enqueue -> the start of its prefill
        self.prefill_ms = 0.0
        # the submitter's span context: the decode loop retires slots on
        # its own thread, where contextvars are empty — the causal link
        # (serving.generate → decode.slot) rides on the request record
        self.trace_ctx = _ttrace.current_context()


class _Slot:
    """One occupied decode slot's host-side state."""

    __slots__ = ("req", "tokens", "position", "last_tok", "version")

    def __init__(self, req: _GenPending, first_tok: int, position: int,
                 version: int):
        self.req = req
        self.tokens: List[int] = [first_tok]
        self.position = position         # next cache write position
        self.last_tok = first_tok
        self.version = version


class ContinuousBatcher:
    """Slot-based continuous-batching decode over one serving channel.

    ``model_ops`` supplies the flax module (the gateway's engine);
    ``(version, variables)`` is the channel's installed pair at
    construction: the tree as the gateway holds it, cast once at install
    to what the programs use (serving/gateway.py ``_load_variables``).
    One worker thread owns the decode loop: each
    iteration admits queued prompts into free slots (prefill), then
    advances every active slot one token through the single jitted step
    program. Per-request ``max_new_tokens`` retire sequences
    independently — nobody waits for the slowest request in the batch.
    """

    def __init__(self, model_ops, version: int, variables: Any,
                 slots: int = 4, max_len: int = 512,
                 channel: str = "stable"):
        self.channel = channel
        self.slots = max(1, int(slots))
        self.max_len = int(max_len)
        module = model_ops.module
        if not all(hasattr(module, a)
                   for a in ("init_cache", "cache_kinds")):
            # fail with the real story, not an AttributeError from deep
            # inside cache allocation, when the federation's model is a
            # classifier rather than a causal LM
            raise TypeError(
                "serving decode needs a causal-LM module that lays out "
                "its own decode state (models.zoo LlamaLite, JambaLite); "
                f"{type(module).__name__} has no init_cache")
        self._decoder = SlotDecoder(module, self.slots, self.max_len)
        self._cache_bytes = cache_bytes_by_kind(module,
                                                self._decoder.caches)
        for kind, nbytes in self._cache_bytes.items():
            _M_DECODE_CACHE.set(nbytes, channel=channel, kind=kind)
        self._pair = (int(version), variables)
        self._pending_pair: Optional[tuple] = None
        self._queue: deque = deque()
        # condition over an instrumented lock (telemetry/prof.py), the
        # serving.queue posture: submit-vs-decode-loop contention is
        # measured, the worker's wait() park is queue occupancy
        self._cv = threading.Condition(_prof.lock("serving.decode"))
        self._slots: List[Optional[_Slot]] = [None] * self.slots
        self._closed = False
        self.tokens_emitted = 0
        # the loop's own account of its time (``LOOP_SUMS``): running
        # totals the worker alone writes, read whole by describe() and as
        # differences by the once-a-second ``decode.loop`` event
        self._sums = {k: 0.0 if k.endswith("_s") else 0 for k in LOOP_SUMS
                      if k not in DECODER_SUMS}
        self._emitted = self._totals()
        self._emitted_at = time.perf_counter()
        # with the tracer on, the worker stamps each tick (``_stamp``)
        # and the next ``decode.loop`` event carries them as ``stamps``
        self._ticks: list = []
        self._anchor = (time.time(), self._emitted_at)
        self._worker = threading.Thread(target=self._loop, daemon=True,
                                        name=f"decode-{channel}")
        self._worker.start()

    @property
    def steps(self) -> int:
        """Decode steps taken so far (test pin; one of the loop's sums)."""
        return self._sums["steps"]

    def _totals(self) -> Dict[str, Any]:
        """``LOOP_SUMS`` as they stand: the worker's sums and, read where
        they are kept, the decoder's."""
        return dict(self._sums, **{k: getattr(self._decoder, k)
                                   for k in DECODER_SUMS})

    # -- request side --------------------------------------------------- #

    def submit(self, prompt, max_new_tokens: int,
               eos_id: Optional[int] = None) -> "futures.Future":
        """Queue one prompt; resolves to ``(tokens, version)`` where
        ``tokens`` is the (max_new_tokens,) int32 continuation (``PAD_ID``
        after an emitted ``eos_id`` — exactly generate()'s contract) and
        ``version`` the registry version that decoded it."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        if int(max_new_tokens) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.size + int(max_new_tokens) > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({int(max_new_tokens)}) exceeds the decode cache "
                f"(serving.decode.max_len={self.max_len})")
        req = _GenPending(prompt, max_new_tokens, eos_id)
        # the request record rides on the future (``admitted_step`` is
        # the step-granularity admission pin tests and operators read)
        req.future.request = req
        with self._cv:
            if self._closed:
                req.future.set_exception(RuntimeError("decode engine "
                                                      "closed"))
                return req.future
            self._queue.append(req)
            _M_DECODE_QUEUE.set(len(self._queue), channel=self.channel)
            self._cv.notify()
        return req.future

    def swap(self, version: int, variables: Any) -> None:
        """Zero-drop hot-swap: the in-flight batch finishes on the pair
        it captured; queued prompts decode on the new one."""
        with self._cv:
            self._pending_pair = (int(version), variables)
            self._cv.notify()

    # -- decode loop ---------------------------------------------------- #

    def _admit_locked(self) -> List[_GenPending]:
        """Pop admittable requests (called under the lock); prefill runs
        OUTSIDE the lock so submit() never blocks behind device work."""
        admitted = []
        if self._pending_pair is not None:
            return admitted              # draining toward the swap
        free = sum(1 for s in self._slots if s is None)
        while free and self._queue:
            admitted.append(self._queue.popleft())
            free -= 1
        _M_DECODE_QUEUE.set(len(self._queue), channel=self.channel)
        return admitted

    def _retire(self, idx: int, slot: _Slot) -> None:
        self._slots[idx] = None
        req = slot.req
        out = np.full((req.max_new,), PAD_ID, np.int32)
        out[: len(slot.tokens)] = slot.tokens
        if req.trace_ctx is not None:
            # enqueue→retire as one already-measured interval, parented
            # on the submitter's serving.generate span: the queue wait
            # AND slot occupancy land on the request's causal chain
            _ttrace.event(
                "decode.slot", time.perf_counter() - req.enqueued_at,
                parent=req.trace_ctx,
                attrs={"channel": self.channel,
                       "admitted_step": req.admitted_step,
                       "retired_step": self.steps,
                       "tokens": len(slot.tokens),
                       "wait_ms": round(req.wait_ms, 3),
                       "prefill_ms": round(req.prefill_ms, 3)})
        self._sums["retired"] += 1
        if not req.future.done():
            req.future.set_result((out, slot.version))

    def _loop(self) -> None:
        sums = self._sums
        while True:
            tick_at = time.perf_counter()
            parked = 0.0
            with self._cv:
                while (not self._queue
                       and all(s is None for s in self._slots)
                       and self._pending_pair is None
                       and not self._closed):
                    t0 = time.perf_counter()
                    self._cv.wait(0.1)
                    parked += time.perf_counter() - t0
                if (self._closed and not self._queue
                        and all(s is None for s in self._slots)):
                    if sums["ticks"] > self._emitted["ticks"]:
                        self._emit_loop(time.perf_counter())
                    return
                if (self._pending_pair is not None
                        and all(s is None for s in self._slots)):
                    # drained: install the new pair, resume admission
                    self._pair = self._pending_pair
                    self._pending_pair = None
                admitted = self._admit_locked()
            # the tick's stamps: its start, the lock released, then one
            # (kind, entry, enqueued, returned) a call (``_stamp``)
            stamp = ([tick_at, time.perf_counter()] if _ttrace.enabled()
                     else None)
            try:
                called_s = self._tick(admitted, stamp)
                # a tick boundary: what of it was neither parked nor
                # inside prefill or step is the host's
                now = time.perf_counter()
                sums["ticks"] += 1
                sums["admitted"] += len(admitted)
                sums["parked_s"] += parked
                sums["host_s"] += now - tick_at - parked - called_s
                if stamp is not None:
                    self._stamp(stamp, now)
                if now - self._emitted_at >= LOOP_EVENT_EVERY_S:
                    self._emit_loop(now)
            except Exception as exc:  # noqa: BLE001 - worker must survive
                # one poisoned tick (bad prompt dtype, an OOM'd step)
                # fails ITS requests only — a dead worker would hang
                # every later Generate on this channel. Every slot is
                # emptied: a call that failed after it had consumed the
                # cache left the decoder with zeroed slots
                # (``SlotDecoder._call``), which is what empty slots need
                logger.exception("decode tick failed")
                with self._cv:
                    for req in admitted:
                        if not req.future.done():
                            req.future.set_exception(exc)
                    for idx, slot in enumerate(self._slots):
                        if slot is not None:
                            if not slot.req.future.done():
                                slot.req.future.set_exception(exc)
                            self._slots[idx] = None

    def _stamp(self, stamp: list, end: float) -> None:
        """Keep one tick's stamps as whole microseconds after the batch's
        anchor: ``[start, released, [[kind, entry, enqueued, returned],
        ...], end]``, kind ``s`` a step and ``p<L>`` a prefill of L
        tokens (the calls' stamps are ``SlotDecoder.last_call``)."""
        base = self._anchor[1]
        start, released, *calls = stamp
        self._ticks.append(
            [round((start - base) * 1e6), round((released - base) * 1e6),
             [[kind] + [round((t - base) * 1e6) for t in times]
              for kind, *times in calls],
             round((end - base) * 1e6)])

    def _emit_loop(self, now: float) -> None:
        """The ``decode.loop`` summary event: the sums' change since the
        last one, at most once a second, from the worker thread; with the
        ticks' stamps of the same interval under ``stamps`` (``anchor``:
        ``time.time()`` and ``perf_counter()`` read together, the origin
        of the offsets; one record in ``ticks`` a tick)."""
        totals = self._totals()
        attrs = {k: round(totals[k] - self._emitted[k], 6)
                 for k in LOOP_SUMS}
        attrs["channel"] = self.channel
        if self._ticks:
            attrs["stamps"] = {"anchor": list(self._anchor),
                               "ticks": self._ticks}
            self._ticks = []
        _ttrace.event("decode.loop", now - self._emitted_at, parent=None,
                      attrs=attrs)
        self._emitted = totals
        self._emitted_at = now
        self._anchor = (time.time(), time.perf_counter())

    def _tick(self, admitted: List[_GenPending],
              stamp: Optional[list]) -> float:
        """One iteration of the loop; returns the seconds it spent inside
        the decoder's ``prefill`` and ``step`` calls, and appends each
        call's stamps to ``stamp`` where it is given."""
        version, variables = self._pair
        sums = self._sums
        called_s = 0.0
        emitted = 0
        # 1. prefill admissions between decode steps (step granularity:
        #    the running batch did NOT have to finish first)
        for req in admitted:
            idx = next(i for i, s in enumerate(self._slots) if s is None)
            first = self._decoder.prefill(variables, idx, req.prompt)
            entry, _, returned = call = self._decoder.last_call
            if stamp is not None:
                stamp.append((f"p{req.prompt.size}",) + call)
            prefill_s = returned - entry
            req.wait_ms = (entry - req.enqueued_at) * 1e3
            req.prefill_ms = prefill_s * 1e3
            sums["prefill_s"] += prefill_s
            sums["prefills"] += 1
            called_s += prefill_s
            req.admitted_step = self.steps
            slot = _Slot(req, first, int(req.prompt.size), version)
            emitted += 1
            if ((req.eos_id is not None and first == req.eos_id)
                    or req.max_new == 1):
                self._retire(idx, slot)
            else:
                self._slots[idx] = slot
        active = [(i, s) for i, s in enumerate(self._slots)
                  if s is not None]
        _M_DECODE_SLOTS.set(len(active), channel=self.channel)
        if active:
            # 2. one decode step for the whole in-flight batch (one
            #    program; free lanes carry zeros and are never read)
            toks = np.zeros((self.slots,), np.int32)
            poss = np.zeros((self.slots,), np.int32)
            for i, s in active:
                toks[i], poss[i] = s.last_tok, s.position
            nxt = self._decoder.step(variables, toks, poss)
            entry, _, returned = call = self._decoder.last_call
            if stamp is not None:
                stamp.append(("s",) + call)
            sums["step_s"] += returned - entry
            sums["steps"] += 1
            called_s += returned - entry
            for i, s in active:
                tok = int(nxt[i])
                s.tokens.append(tok)
                s.last_tok = tok
                s.position += 1
                emitted += 1
                done = (len(s.tokens) >= s.req.max_new
                        or (s.req.eos_id is not None
                            and tok == s.req.eos_id))
                if done:
                    self._retire(i, s)
        self.tokens_emitted += emitted
        _M_DECODE_TOKENS.inc(emitted, channel=self.channel)
        return called_s

    # -- status --------------------------------------------------------- #

    def depth(self) -> int:
        with self._cv:
            return len(self._queue)

    def active(self) -> int:
        with self._cv:
            return sum(1 for s in self._slots if s is not None)

    def describe(self) -> Dict[str, Any]:
        with self._cv:
            return {"slots": self.slots, "max_len": self.max_len,
                    "queued": len(self._queue),
                    "active": sum(1 for s in self._slots if s is not None),
                    "steps": self.steps,
                    "tokens_emitted": self.tokens_emitted,
                    "version": self._pair[0],
                    "swap_pending": self._pending_pair is not None,
                    # the slots' device state by kind (kv, state)
                    "cache_bytes": dict(self._cache_bytes),
                    # the loop's account of its own time, as totals
                    "loop": {k: round(v, 6)
                             for k, v in self._totals().items()}}

    def close(self) -> None:
        """Drain: queued + in-flight generations still finish, then the
        worker exits."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        self._worker.join(timeout=60.0)
        _M_DECODE_QUEUE.remove(channel=self.channel)
        _M_DECODE_SLOTS.remove(channel=self.channel)
        for kind in self._cache_bytes:
            _M_DECODE_CACHE.remove(channel=self.channel, kind=kind)
