"""Autoregressive decoding with static-shape per-block state (TPU-native).

The reference's third learner task type is inference
(reference metisfl/learner/learner.py:311-330); for the causal-LM family
that means incremental decoding, which a full-forward ``infer`` cannot do
efficiently (O(L^2) work per emitted token). This module adds the decode
path the TPU way:

- the KV cache is a fixed (B, kv_heads, max_len, head_dim) buffer per
  block, written with ``dynamic_update_slice`` at a traced position — one
  compiled program serves every step, no shape respecialization;
- the whole generation (prefill + N decode steps) is ONE jitted program:
  ``lax.scan`` drives the token loop, sampling included, so the host
  dispatches once per *sequence*, not once per token;
- GQA caches stay at kv-head size in HBM — decode is memory-bound, and
  heads/kv_heads is exactly the cache-bandwidth saving Llama-3 GQA buys;
- early termination via an ``eos_id`` done-mask (scan has no data-dependent
  exit; finished rows emit padding and their cache writes are masked out by
  the causal mask being irrelevant past the emitted eos).

Works with any :class:`~metisfl_tpu.models.zoo.LlamaLite` configuration
(LoRA, GQA, MoE, bf16) on the same trained parameters — the cache mode
reuses the module's own projections, so there is no separate "inference
model" to convert to.

The module says what each block's decode state is (``init_cache``): a
``(K, V)`` pair for an attention block, ``(conv_state, ssm_state)`` for a
Mamba block of :class:`~metisfl_tpu.models.zoo.JambaLite`. Everything here
carries that state as a pytree and looks inside none of it.
"""

from __future__ import annotations

import time
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jex_core

from metisfl_tpu.telemetry import runtime as _runtime

Pytree = Any


def init_cache(module, batch: int, max_len: int):
    """Zeroed per-block decode state of ``module``, as the module itself
    lays it out (``LlamaLite.init_cache``, ``JambaLite.init_cache``)."""
    return module.init_cache(batch, max_len)


def cache_bytes_by_kind(module, caches) -> dict:
    """Bytes of ``caches`` (one entry a block) by what the module says
    each entry is: ``kv`` grows with ``max_len``, ``state`` does not."""
    out: dict = {}
    for kind, entry in zip(module.cache_kinds(), caches):
        out[kind] = out.get(kind, 0) + sum(
            int(leaf.nbytes) for leaf in jax.tree.leaves(entry))
    return out


def _sampler(temperature: float, top_k: int, top_p: float = 0.0):
    """logits (B, V), rng → tokens (B,). temperature 0 = greedy; top_k
    truncates to the k most likely tokens, top_p (nucleus, Holtzman et
    al.) to the smallest set whose probability mass reaches p — both may
    combine (top_k applies first)."""
    def sample(logits, rng):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits / float(temperature)
        if top_k > 0 or 0.0 < top_p < 1.0:
            # ONE descending sort serves both filters (a vocab-sized sort
            # per decoded token is the sampler's dominant cost)
            sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
        if top_k > 0:
            kth = sorted_desc[:, top_k - 1][:, None]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        if 0.0 < top_p < 1.0:
            # nucleus: drop tokens outside the smallest probability-mass-p
            # prefix of the sorted distribution. The token that CROSSES
            # the p threshold stays in (cumulative mass up to and
            # including it first reaches p), matching the standard
            # formulation. Under a combined top_k, the nucleus operates on
            # the already-truncated distribution: masking the sorted array
            # by POSITION >= top_k equals re-sorting the masked logits.
            if top_k > 0:
                sorted_desc = jnp.where(
                    jnp.arange(sorted_desc.shape[-1])[None, :] < top_k,
                    sorted_desc, -jnp.inf)
            probs = jax.nn.softmax(sorted_desc, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # keep[i] = True while the mass BEFORE token i is < p
            keep = (cum - probs) < float(top_p)
            # per-row cutoff logit = smallest kept sorted logit
            cutoff = jnp.min(
                jnp.where(keep, sorted_desc, jnp.inf), axis=-1)[:, None]
            logits = jnp.where(logits < cutoff, -jnp.inf, logits)
        return jax.random.categorical(rng, logits).astype(jnp.int32)
    return sample


def generate(module, variables: Pytree, prompt, max_new_tokens: int, *,
             temperature: float = 0.0, top_k: int = 0, top_p: float = 0.0,
             eos_id: Optional[int] = None, pad_id: int = 0,
             rng=None, max_len: Optional[int] = None):
    """Generate ``max_new_tokens`` continuations of ``prompt`` (B, L_p).

    Returns (B, max_new_tokens) int32 tokens; after a row emits ``eos_id``
    the remainder of that row is ``pad_id``. Greedy by default;
    ``temperature > 0`` samples (optionally top-k and/or nucleus top-p
    truncated) using ``rng``.

    The returned function of this call is fully jit-compiled: repeated calls
    with the same (shapes, max_new_tokens, sampling config) hit the
    compilation cache.
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt.ndim != 2:
        raise ValueError(f"prompt must be (batch, length), got {prompt.shape}")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    B, Lp = prompt.shape
    total = Lp + max_new_tokens
    if max_len is not None and max_len < total:
        raise ValueError(f"max_len {max_len} < prompt+new = {total}")
    max_len = max_len or total
    if rng is None:
        rng = jax.random.PRNGKey(0)
    sample = _sampler(temperature, top_k, top_p)

    def run(variables, prompt, rng):
        caches = init_cache(module, B, max_len)
        # prefill: one full-width pass writes the prompt's K/V and yields
        # the first next-token distribution
        logits, caches = module.apply(variables, prompt, caches=caches,
                                      position=0)
        rng, sub = jax.random.split(rng)
        tok = sample(logits[:, -1], sub)
        done = jnp.zeros((B,), bool)
        if eos_id is not None:
            done = tok == eos_id

        def step(carry, _):
            caches, tok, pos, rng, done = carry
            logits, caches = module.apply(variables, tok[:, None],
                                          caches=caches, position=pos)
            rng, sub = jax.random.split(rng)
            nxt = sample(logits[:, -1], sub)
            if eos_id is not None:
                nxt = jnp.where(done, pad_id, nxt)
                done = done | (nxt == eos_id)
            return (caches, nxt, pos + 1, rng, done), nxt

        carry = (caches, tok, jnp.asarray(Lp, jnp.int32), rng, done)
        _, rest = jax.lax.scan(step, carry, None,
                               length=max_new_tokens - 1)
        return jnp.concatenate([tok[:, None], rest.T], axis=1)

    if max_new_tokens == 1:
        def run(variables, prompt, rng):  # noqa: F811 — scan-free case
            caches = init_cache(module, B, max_len)
            logits, _ = module.apply(variables, prompt, caches=caches,
                                     position=0)
            return sample(logits[:, -1], jax.random.split(rng)[1])[:, None]

    # jax.jit caches on the function OBJECT: a fresh closure per call would
    # retrace and recompile every time. Key the compiled program on
    # everything the closure bakes in (flax modules hash by config).
    key = (module, B, Lp, max_len, max_new_tokens, float(temperature),
           int(top_k), float(top_p), eos_id, pad_id)
    compiled = _COMPILED.get(key)
    if compiled is None:
        while len(_COMPILED) >= _COMPILED_MAX:  # LRU bound: a long-lived
            # server with many (shape, sampling) combos must not retain
            # every XLA executable forever
            _COMPILED.pop(next(iter(_COMPILED)))
        compiled = _COMPILED[key] = _runtime.monitored_jit(
            run, name="generate")
    else:
        _COMPILED[key] = _COMPILED.pop(key)  # refresh LRU position
    return compiled(variables, prompt, rng)


# compiled generation programs, keyed on (module config, shapes, sampling);
# insertion-ordered dict used as an LRU with _COMPILED_MAX entries. Callers
# with many distinct prompt lengths should bucket them via ``max_len`` +
# left-padding rather than compiling one program per length.
_COMPILED: dict = {}
_COMPILED_MAX = 32


# --------------------------------------------------------------------- #
# which leaves a served tree may hold in the compute type
# --------------------------------------------------------------------- #

# primitives that merely call an inner jaxpr with their own inputs, in
# their own order, and the parameter that holds it. Anything with loop,
# branch or custom-rule semantics (scan, while, cond, custom_vjp_call, a
# Pallas call) is not among them: a leaf that enters one stays as it is.
_CALLS = {"jit": "jaxpr", "pjit": "jaxpr", "remat2": "jaxpr",
          "checkpoint": "jaxpr", "closed_call": "call_jaxpr",
          "core_call": "call_jaxpr"}

_TRACED_TOKENS = 4      # any count > 1: the module sees a prompt


def _consumers(jaxpr, tables: dict) -> dict:
    """variable -> [(equation, operand index)] of ``jaxpr``; an output of
    the jaxpr counts as a consumer with no equation."""
    table = tables.get(id(jaxpr))
    if table is None:
        table = tables[id(jaxpr)] = {}
        for eqn in jaxpr.eqns:
            for i, var in enumerate(eqn.invars):
                if isinstance(var, jex_core.Var):     # not a literal
                    table.setdefault(var, []).append((eqn, i))
        for var in jaxpr.outvars:
            if isinstance(var, jex_core.Var):
                table.setdefault(var, []).append((None, 0))
    return table


def _converted_to(jaxpr, var, tables: dict) -> Optional[set]:
    """The types ``var`` is converted to where every consumer of it in
    ``jaxpr`` is a ``convert_element_type`` (followed through ``_CALLS``);
    None where anything else consumes it. Empty: nothing consumes it."""
    found: set = set()
    for eqn, i in _consumers(jaxpr, tables).get(var, ()):
        name = eqn.primitive.name if eqn is not None else ""
        if name == "convert_element_type":
            found.add(np.dtype(eqn.params["new_dtype"]))
            continue
        if name not in _CALLS:
            return None
        inner = eqn.params[_CALLS[name]]
        inner = getattr(inner, "jaxpr", inner)        # a ClosedJaxpr's own
        if len(inner.invars) != len(eqn.invars):
            return None
        below = _converted_to(inner, inner.invars[i], tables)
        if below is None:
            return None
        found |= below
    return found


def cast_once_dtypes(program: Callable, variables: Pytree,
                     *args) -> List[Optional[np.dtype]]:
    """For each leaf of ``variables`` (in ``jax.tree.leaves`` order) the
    type a server of ``program(variables, *args)`` may hold it in, or None
    where it has to stay as it is.

    A module that computes in bfloat16 over float32 parameters converts a
    parameter on every call (``nn.Dense(dtype=...)``, ``nn.Embed``,
    ``LoRADense``). While the weights do not change, the converted values
    do not either, so whoever serves them can convert once and hand the
    program the result: its own convert is then the identity and every
    product sees the operands it saw before, to the last bit. That holds
    only for a leaf the program uses through that convert and nothing else
    (``lm_head`` multiplies in float32, ``JambaLite`` transposes its tied
    embedding in float32, a norm reshapes its ``scale`` first), so the
    answer is read from the program and not from a name: ONE abstract
    trace (``jax.make_jaxpr`` over shapes; nothing runs, nothing is
    placed), in which a leaf is named iff something consumes it, it is no
    output, and every consumer is a ``convert_element_type`` to one and the
    same floating type narrower than its own. ``variables`` and ``args``
    may be arrays or ``jax.ShapeDtypeStruct``s.
    """
    struct = lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype)  # noqa: E731
    spec, args = jax.tree.map(struct, (variables, args))
    leaves = jax.tree.leaves(spec)
    jaxpr = jax.make_jaxpr(program)(spec, *args).jaxpr
    tables: dict = {}
    out: List[Optional[np.dtype]] = []
    for leaf, var in zip(leaves, jaxpr.invars):
        # None (consumed otherwise) and empty (not consumed) both keep it
        types = _converted_to(jaxpr, var, tables) or set()
        dtype = types.pop() if len(types) == 1 else None
        narrower = (dtype is not None
                    and jnp.issubdtype(leaf.dtype, jnp.floating)
                    and jnp.issubdtype(dtype, jnp.floating)
                    and dtype.itemsize < np.dtype(leaf.dtype).itemsize)
        out.append(dtype if narrower else None)
    return out


def decode_call(module) -> Tuple[Callable, tuple]:
    """``(program, args)`` of the one call every decode program of
    ``module`` is: ``module.apply(v, tokens, caches=caches,
    position=position)`` over the state the module lays out itself.
    :func:`generate`'s and :class:`SlotDecoder`'s prefill are this call at
    a prompt's length and position 0, their step at one token and a traced
    position; what the call does with a parameter depends on neither, so
    :func:`cast_once_dtypes` reads all of them from this one, at
    ``_TRACED_TOKENS`` tokens and a traced position (tests/
    test_serving_cast.py holds the four choices equal leaf for leaf)."""
    def program(variables, tokens, caches, position):
        return module.apply(variables, tokens, caches=caches,
                            position=position)

    caches = jax.eval_shape(
        lambda: init_cache(module, 1, 2 * _TRACED_TOKENS))
    return program, (jax.ShapeDtypeStruct((1, _TRACED_TOKENS), jnp.int32),
                     caches, jax.ShapeDtypeStruct((), jnp.int32))


class DecodeCall:
    """One launched :class:`SlotDecoder` call: its tokens stay on the
    device until :meth:`SlotDecoder.read`. Stamps on the host's
    ``perf_counter``: ``entry`` (before anything is placed), ``enqueued``
    (the jitted call returned), ``read_at`` (the read began) and
    ``returned`` (the tokens on the host)."""

    __slots__ = ("out", "prefill", "entry", "enqueued", "read_at",
                 "returned")

    def __init__(self, out, prefill: bool, entry: float, enqueued: float):
        self.out = out
        self.prefill = prefill
        self.entry, self.enqueued = entry, enqueued
        self.read_at = self.returned = 0.0


class SlotDecoder:
    """Fixed-slot KV-cache decode programs for continuous batching (Orca,
    Yu et al. OSDI 2022 — iteration-level scheduling over an in-flight
    batch).

    :func:`generate` compiles one program per *whole generation*: every
    request runs prefill + all its decode steps alone, and a prompt that
    arrives mid-generation waits for the running batch to finish. This
    class exposes the two primitives a continuous batcher schedules at
    *step* granularity instead:

    - ``prefill(variables, slot, prompt)`` — write one prompt's K/V into
      slot ``slot`` of the shared cache and return its first greedy
      token (one program per prompt length, LRU-bounded);
    - ``step(variables, tokens, positions)`` — ONE jitted program
      advancing every slot a single token, each at its own cache
      position (``vmap`` over the slot axis carries the per-slot
      position the module's scalar ``position`` argument cannot).

    The caches are allocated once at fixed slot shapes (a slot axis in
    front of the module's own batch-1 state: ``(slots, 1, kv_heads,
    max_len, head_dim)`` per attention block), so however requests come
    and go the step stays one compiled program. A retiring slot needs no
    cleanup: attention masks every cache position beyond the occupant's
    frontier to ``finfo.min`` (exactly-zero softmax weight), and a new
    occupant's prefill + sequential decode writes overwrite every position
    before it becomes attendable — which is also why the outputs are
    bit-identical to a solo :func:`generate` call at the same ``max_len``
    (tests/test_fleet.py pins it). Recurrent state has no frontier to hide
    behind: a Mamba block starts from zero at position 0 whatever the slot
    held (``MambaMixer``), which is the same guarantee by other means.

    The decoder's ``state`` is ``(caches, tokens, positions)``: the cache,
    each lane's last token and the position its next token is written at,
    (slots,) int32 each. A prefill writes its first token and its prompt's
    length into its lane; a step reads both vectors, advances every lane
    and writes the next tokens and positions back (a free lane keeps
    decoding what it held, at a position held below ``max_len``; nothing
    reads it). So a step needs nothing from the host, and the two halves of
    a call are separate: ``launch_prefill`` / ``launch_step`` enqueue the
    program and return a :class:`DecodeCall`, whose tokens (an output of
    their own, not part of the state) ``read`` fetches later. A caller can
    launch step k+1 before it reads step k (``ContinuousBatcher`` does);
    ``prefill`` and ``step`` are the two halves back to back, and ``step``
    places the tokens and positions it is given first.

    The state is this object's alone, and every call consumes it: prefill
    and step donate the whole tree (``kv`` and ``state`` leaves and the two
    vectors alike), the compiled programs alias each leaf's output to its
    input, and a call changes the buffers that are there (a step one row a
    slot, ``zoo.transformer._write_kv``; a prefill one slot) where an
    undonated call wrote 1.07 GB of new cache at 8 slots of 2048. The
    arrays a call was given are deleted when it is enqueued, so nobody else
    may keep them: hold sizes (:func:`cache_bytes_by_kind`), not leaves. A
    launch that fails after the runtime took the buffers (an out-of-memory
    step), and any read that fails (a device error surfaces there, and the
    state has been handed on to whatever was launched since), would leave
    deleted or poisoned arrays behind; the decoder then starts over with
    zeroed slots before it re-raises, and whoever schedules the slots must
    empty all of them and fail every call in flight (``ContinuousBatcher``
    does, on any failed tick). A launch that fails before (the length
    check, a trace or compile error) consumed nothing and resets nothing.
    ``donated_calls`` counts the launches after which the input's buffers
    were gone (one ``is_deleted()`` a call; equal to the calls made),
    ``cache_resets`` the times the slots were started over.

    Each call is cut in two on the host's clock, with no sync added: the
    *launch*, from its entry (before any input is placed) to the jitted
    call's return (enqueued, the input state let go, the tokens' copy to
    the host started), and the *read*, the time the host blocks on the
    tokens. ``step_launch_s``, ``step_read_s``, ``prefill_launch_s`` and
    ``prefill_read_s`` sum them; the :class:`DecodeCall` holds the stamps.

    ``variables`` is whatever tree the caller serves. The gateway hands
    in the module's own casts made once (serving/gateway.py
    ``_load_variables``): a leaf that prefill and step use only through a
    ``convert_element_type`` to the compute type arrives already in it, so
    both programs convert nothing and read half the weight bytes a token;
    a leaf they use in float32 (``lm_head``, norm scales, ``JambaLite``'s
    tied embedding, thin projections, recurrence parameters and
    convolution) arrives in float32. Tokens are those of the float32 tree
    to the last bit (:func:`cast_once_dtypes`, read from
    :func:`decode_call` by one abstract trace a gateway; one cast program
    an install).

    Greedy only: a shared in-flight batch samples per-slot rng streams,
    which would no longer be comparable to any single-request call;
    serving-plane generation (serving/decode.py) is deterministic by
    contract.
    """

    _PREFILL_MAX = 16  # compiled prefill programs kept (per prompt length)

    def __init__(self, module, slots: int, max_len: int):
        self.module = module
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.state = self._zeroed()
        self.donated_calls = 0   # calls that left their input state deleted
        self.cache_resets = 0    # failed calls that had consumed it
        self.step_launch_s = self.step_read_s = 0.0
        self.prefill_launch_s = self.prefill_read_s = 0.0
        self._prefill_fns: dict = {}
        self._step_fn = None

    @property
    def caches(self):
        """The slots' per-block state, one entry a block as the module
        lays it out (the first part of ``state``)."""
        return self.state[0]

    def _zeroed(self):
        """``(caches, tokens, positions)`` of empty slots: slot-major
        caches, each slot the batch-1 state one solo generate(B=1) call
        sees, and zeroed lanes."""
        caches = jax.tree.map(
            lambda a: jnp.zeros((self.slots,) + a.shape, a.dtype),
            init_cache(self.module, 1, self.max_len))
        return (caches, jnp.zeros((self.slots,), jnp.int32),
                jnp.zeros((self.slots,), jnp.int32))

    def _start_over(self) -> None:
        self.state = self._zeroed()
        self.cache_resets += 1

    def _launch(self, fn, variables, *args, prefill: bool,
                entry: float) -> DecodeCall:
        """Enqueue one donating program on the state: the one place the
        state changes hands. A launch that raises after the runtime took
        the buffers starts the slots over; one that raises before (a trace
        or compile error) leaves them as they were."""
        taken = self.state
        try:
            self.state, out = fn(variables, taken, *args)
            out.copy_to_host_async()
        except Exception:
            if any(leaf.is_deleted() for leaf in jax.tree.leaves(taken)):
                self._start_over()
            raise
        enqueued = time.perf_counter()
        self.donated_calls += jax.tree.leaves(taken)[0].is_deleted()
        if prefill:
            self.prefill_launch_s += enqueued - entry
        else:
            self.step_launch_s += enqueued - entry
        return DecodeCall(out, prefill, entry, enqueued)

    def read(self, call: DecodeCall) -> np.ndarray:
        """Block on ``call``'s tokens: a step's (slots,), a prefill's one.
        A device failure surfaces here, after later launches may have
        consumed the state it poisoned: any failed read starts the slots
        over before it re-raises."""
        call.read_at = time.perf_counter()
        try:
            out = np.asarray(call.out)
        except Exception:
            self._start_over()
            raise
        call.returned = time.perf_counter()
        if call.prefill:
            self.prefill_read_s += call.returned - call.read_at
        else:
            self.step_read_s += call.returned - call.read_at
        return out

    def launch_prefill(self, variables, slot: int, prompt) -> DecodeCall:
        """Enqueue a prompt's admission into ``slot``: its K/V, its first
        greedy token and its length into the slot's lane. The prompt runs
        at its EXACT length (no padding) — the same program a solo
        generate's prefill compiles — which is what keeps slot outputs
        bit-identical to single-request decode."""
        entry = time.perf_counter()
        prompt = jnp.asarray(prompt, jnp.int32).reshape(1, -1)
        L = int(prompt.shape[1])
        if L < 1 or L >= self.max_len:
            raise ValueError(
                f"prompt length {L} must be in [1, max_len={self.max_len})")
        fn = self._prefill_fns.get(L)
        if fn is None:
            module = self.module

            def run(variables, state, prompt, slot):
                caches, toks, poss = state
                sub = jax.tree.map(
                    lambda c: jax.lax.dynamic_index_in_dim(
                        c, slot, 0, keepdims=False), caches)
                logits, sub = module.apply(variables, prompt, caches=sub,
                                           position=0)
                caches = jax.tree.map(
                    lambda c, s: jax.lax.dynamic_update_index_in_dim(
                        c, s, slot, 0), caches, sub)
                tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                toks = jax.lax.dynamic_update_index_in_dim(toks, tok, slot, 0)
                poss = jax.lax.dynamic_update_index_in_dim(
                    poss, jnp.full((1,), L, jnp.int32), slot, 0)
                return (caches, toks, poss), tok[0]

            while len(self._prefill_fns) >= self._PREFILL_MAX:
                self._prefill_fns.pop(next(iter(self._prefill_fns)))
            fn = self._prefill_fns[L] = _runtime.monitored_jit(
                run, name="decode.prefill", donate_argnums=(1,))
        else:
            self._prefill_fns[L] = self._prefill_fns.pop(L)  # LRU refresh
        return self._launch(fn, variables, prompt,
                            jnp.asarray(slot, jnp.int32), prefill=True,
                            entry=entry)

    def launch_step(self, variables) -> DecodeCall:
        """Enqueue one step of EVERY lane (one fixed-shape jitted program)
        from the tokens and positions the state holds; free lanes compute
        garbage that is never read, and their cache writes land at
        positions a future prefill overwrites."""
        entry = time.perf_counter()
        if self._step_fn is None:
            module, last = self.module, self.max_len - 1

            def run(variables, state):
                caches, toks, poss = state

                def one(sub, tok, pos):
                    logits, sub = module.apply(
                        variables, tok.reshape(1, 1), caches=sub,
                        position=pos)
                    nxt = jnp.argmax(logits[:, -1], axis=-1)
                    return sub, nxt.astype(jnp.int32)[0]

                caches, nxt = jax.vmap(one, in_axes=(0, 0, 0))(caches, toks,
                                                               poss)
                # an occupant never passes ``last``; a free lane stays there
                return (caches, nxt, jnp.minimum(poss + 1, last)), nxt

            self._step_fn = _runtime.monitored_jit(
                run, name="decode.step", donate_argnums=(1,))
        return self._launch(self._step_fn, variables, prefill=False,
                            entry=entry)

    def prefill(self, variables, slot: int, prompt) -> int:
        """Admit a prompt into ``slot`` and return its first greedy token
        (:meth:`launch_prefill`, then :meth:`read`)."""
        return int(self.read(self.launch_prefill(variables, slot, prompt)))

    def step(self, variables, tokens, positions):
        """Advance EVERY slot one decode token from ``tokens``/``positions``,
        (slots,) int arrays placed as the lanes' own; free slots pass any
        value. Returns the (slots,) next greedy tokens
        (:meth:`launch_step`, then :meth:`read`)."""
        self.state = (self.caches, jnp.asarray(tokens, jnp.int32),
                      jnp.asarray(positions, jnp.int32))
        return self.read(self.launch_step(variables))
