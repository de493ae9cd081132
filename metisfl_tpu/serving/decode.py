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

The loop runs one step ahead of its reads: each lane's last token lives on
the device, so step k+1 is launched from step k's outputs before the host
blocks on step k's tokens, and whenever the host waits, the device has the
next program queued behind the one it is reading. A request that ends by
its token count leaves its lane before its last token is read; one that
emits ``eos_id`` at step k has already been launched into step k+1, whose
token for it is thrown away (``late_lanes``).

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
# the decoder's step calls (their launches and the time the host blocked
# on their tokens), the same for prefill, the rest of the tick on the host
# (admission under the lock, token hand-out, retirement), seconds parked
# with nothing queued, no slot active and nothing in flight (in neither
# side of a host share), and counts: ``overlapped_steps`` the steps
# launched while the previous step's tokens were still unread,
# ``late_lanes`` the lane-steps computed for a request that had already
# emitted eos, whose tokens were thrown away. The rest are the decoder's
# own: calls that updated the slots' state in place (``donated_calls`` =
# ``steps`` + ``prefills``), failed calls after which the slots were
# started over, and each kind of call cut in two, launch (entry to
# enqueued) and read (blocked on the tokens): ``step_launch_s`` +
# ``step_read_s`` = ``step_s``, and the same for prefill.
DECODER_SUMS = ("donated_calls", "cache_resets", "step_launch_s",
                "step_read_s", "prefill_launch_s", "prefill_read_s")
LOOP_SUMS = ("step_s", "prefill_s", "host_s", "parked_s", "ticks",
             "steps", "prefills", "admitted", "retired", "overlapped_steps",
             "late_lanes") + DECODER_SUMS
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
    """One admitted request's host-side state: the tokens read so far, and
    how many its launched calls will have given (``launched``)."""

    __slots__ = ("req", "tokens", "launched", "version", "done")

    def __init__(self, req: _GenPending, version: int):
        self.req = req
        self.tokens: List[int] = []
        self.launched = 1                # its prefill's token
        self.version = version
        self.done = False


class ContinuousBatcher:
    """Slot-based continuous-batching decode over one serving channel.

    ``model_ops`` supplies the flax module (the gateway's engine);
    ``(version, variables)`` is the channel's installed pair at
    construction: the tree as the gateway holds it, cast once at install
    to what the programs use (serving/gateway.py ``_load_variables``).
    One worker thread owns the decode loop: each
    iteration admits queued prompts into free slots (prefill), launches
    the next step of every active slot through the single jitted step
    program, and only then reads the step launched the iteration before.
    Per-request ``max_new_tokens`` retire sequences independently —
    nobody waits for the slowest request in the batch.
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
        # the step launched and not yet read, with the lanes it advanced;
        # and every admitted request whose reply is not out yet
        self._inflight: Optional[tuple] = None
        self._open: set = set()
        self._closed = False
        self.tokens_emitted = 0
        # the loop's own account of its time (``LOOP_SUMS``): running
        # totals the worker alone writes, read whole by describe() and as
        # differences by the once-a-second ``decode.loop`` event
        self._sums = {k: 0.0 if k.endswith("_s") else 0 for k in LOOP_SUMS
                      if k not in DECODER_SUMS + ("step_s", "prefill_s")}
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
        they are kept, the decoder's (``step_s`` and ``prefill_s`` are its
        launches and reads)."""
        d = self._decoder
        return dict(self._sums, step_s=d.step_launch_s + d.step_read_s,
                    prefill_s=d.prefill_launch_s + d.prefill_read_s,
                    **{k: getattr(d, k) for k in DECODER_SUMS})

    def _idle(self) -> bool:
        """No slot occupied and nothing in flight (called under the lock
        or by the worker)."""
        return self._inflight is None and all(s is None for s in self._slots)

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
        slot.done = True
        if self._slots[idx] is slot:
            self._slots[idx] = None
        self._open.discard(slot)
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
                while (not self._queue and self._idle()
                       and self._pending_pair is None
                       and not self._closed):
                    t0 = time.perf_counter()
                    self._cv.wait(0.1)
                    parked += time.perf_counter() - t0
                if self._closed and not self._queue and self._idle():
                    if sums["ticks"] > self._emitted["ticks"]:
                        self._emit_loop(time.perf_counter())
                    return
                if self._pending_pair is not None and self._idle():
                    # drained, the last step read: install the new pair,
                    # resume admission
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
                # one poisoned tick (bad prompt dtype, an OOM'd step, a
                # device error surfacing at a read) fails the requests in
                # flight only — a dead worker would hang every later
                # Generate on this channel. Every slot is emptied and the
                # pipeline dropped: a call that failed after it had
                # consumed the state left the decoder with zeroed slots
                # (``SlotDecoder._launch``, ``read``), which is what empty
                # slots need
                logger.exception("decode tick failed")
                with self._cv:
                    for req in admitted:
                        if not req.future.done():
                            req.future.set_exception(exc)
                    for slot in self._open:
                        slot.done = True
                        if not slot.req.future.done():
                            slot.req.future.set_exception(exc)
                    self._open.clear()
                    self._slots = [None] * self.slots
                    self._inflight = None

    def _stamp(self, stamp: list, end: float) -> None:
        """Keep one tick's stamps as whole microseconds after the batch's
        anchor: ``[start, released, [[kind, entry, enqueued, returned],
        ...], end]``, kind ``s`` a step and ``p<L>`` a prefill of L
        tokens: the calls READ in the tick, in entry order (the
        :class:`~metisfl_tpu.models.generate.DecodeCall` stamps). A step
        read here was entered in the tick before, so its entry precedes
        ``start``, and its ``returned`` follows the next step's
        ``enqueued``."""
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
        """One iteration of the loop, one step ahead of its reads: launch
        the admitted prompts' prefills into free lanes and step k+1 over
        every occupied lane, and only then block on step k's tokens (the
        last tick's launch) and the prefills' first tokens, hand them out
        and retire. Returns the seconds spent inside the decoder's calls
        (launches and reads), and appends each call read to ``stamp``
        where it is given."""
        version, variables = self._pair
        decoder, sums = self._decoder, self._sums
        called_s = 0.0
        # 1. prefill admissions between decode steps (step granularity:
        #    the running batch did NOT have to finish first)
        prefills = []
        for req in admitted:
            idx = self._slots.index(None)
            call = decoder.launch_prefill(variables, idx, req.prompt)
            called_s += call.enqueued - call.entry
            req.wait_ms = (call.entry - req.enqueued_at) * 1e3
            req.admitted_step = self.steps
            slot = self._slots[idx] = _Slot(req, version)
            self._open.add(slot)
            prefills.append((idx, slot, call))
            sums["prefills"] += 1
        # 2. step k+1 over the lanes whose request wants more tokens, from
        #    the tokens and positions on the device (one program; free
        #    lanes are never read)
        previous, self._inflight = self._inflight, None
        active = [(i, s) for i, s in enumerate(self._slots)
                  if s is not None and s.launched < s.req.max_new]
        _M_DECODE_SLOTS.set(len(active), channel=self.channel)
        if active:
            call = decoder.launch_step(variables)
            called_s += call.enqueued - call.entry
            sums["steps"] += 1
            sums["overlapped_steps"] += previous is not None
            for _, s in active:
                s.launched += 1
            self._inflight = (call, active)
        self._free_counted()
        # 3. only now block: step k's tokens, then the prefills' first
        #    tokens, with step k+1 queued behind them on the device
        emitted = 0
        read = []
        if previous is not None:
            call, lanes = previous
            toks = decoder.read(call)
            read.append(("s", call))
            for i, s in lanes:
                if s.done:           # emitted eos at step k-1 or prefill
                    sums["late_lanes"] += 1
                else:
                    self._hand_out(i, s, int(toks[i]))
                    emitted += 1
        for idx, slot, call in prefills:
            tok = int(decoder.read(call))
            slot.req.prefill_ms = (call.returned - call.entry) * 1e3
            read.append((f"p{slot.req.prompt.size}", call))
            self._hand_out(idx, slot, tok)
            emitted += 1
        for kind, call in read:
            called_s += call.returned - call.read_at
            if stamp is not None:
                stamp.append((kind, call.entry, call.enqueued,
                              call.returned))
        self.tokens_emitted += emitted
        _M_DECODE_TOKENS.inc(emitted, channel=self.channel)
        return called_s

    def _free_counted(self) -> None:
        """Free the lanes whose request the launched calls complete by its
        token count: its last tokens are read later, and the next tick may
        admit into the lane (its prefill runs after them on the device)."""
        for i, s in enumerate(self._slots):
            if s is not None and s.launched >= s.req.max_new:
                self._slots[i] = None

    def _hand_out(self, idx: int, slot: _Slot, tok: int) -> None:
        """One token read for ``slot``; retire it on its count or eos."""
        slot.tokens.append(tok)
        req = slot.req
        if (len(slot.tokens) >= req.max_new
                or (req.eos_id is not None and tok == req.eos_id)):
            self._retire(idx, slot)

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
