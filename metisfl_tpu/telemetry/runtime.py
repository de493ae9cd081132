"""Accelerator runtime observability: XLA compiles + device memory.

The causal-tracing (PR 14) and continuous-profiling (PR 12) planes
attribute Python frames and RPC edges — but the runtime layer *beneath*
them is blind: an XLA recompile storm or an HBM leak shows up only as
unexplained wall-clock. Jit-heavy stacks are exactly where silent
recompiles turn a 12 ms round into a multi-second one (the PR 13 slot
decoder compiles per exact prompt length, ``models/ops.py`` jits the
round kernels, learners jit train steps). This module is the runtime
layer's telemetry, native to the existing planes:

- **Compile tracking** — ``jax.monitoring`` fires a
  ``/jax/core/compile/backend_compile_duration`` duration event exactly
  once per real XLA compile, but carries NO function attribution. The
  attribution contract here is :func:`monitored_jit`: a wrapper around
  the jit entrypoints we own that names the function in a thread-local
  context for the duration of the call — the registered listener
  attributes any compile that fires inside that window. The fast path
  (steady-state call, nothing compiling) costs one attribute check plus
  a thread-local set/restore; the abstract shape signature is computed
  ONLY when a compile actually fired during the call. Compiles outside
  any wrapper record as ``(unattributed)``. A compile the persistent
  compilation cache served (``metisfl_tpu.platform.enter_process``
  places it) still fires the duration event — its duration is the
  retrieval — and is counted per function as a ``cache_hits``.

- **Classification** — the first compile for a function name is
  ``cold``; every later compile of the same name is a **recompile**
  (same function, new abstract signature — including an LRU-evicted
  one). A function recompiling ``storm_threshold`` times inside
  ``storm_window_s`` emits a ``jax_recompile_storm`` journal event
  (once per window per function).

- **Bounded mergeable state** — per-function rows (cold/recompile
  counts, total/max compile seconds, last signature) keep exact labels
  up to ``budget``; the crowd folds into ``_other`` (PR 9's posture).
  A small ring of recent compile events backs the offenders table.

- **Memory accounting** — :func:`memory_snapshot` prefers per-device
  ``memory_stats()['bytes_in_use']`` (TPU/GPU), falls back to
  ``jax.live_arrays()`` nbytes, and always reports host RSS (the CPU
  story). Sampled on the PR 12 sampler cadence (a prof tick hook,
  time-gated by ``mem_every_s``) and refreshed on every
  ``collect_state()`` pull; attributed per plane (learner train /
  controller fold / serving decode) via the service name
  :func:`metisfl_tpu.telemetry.apply_config` passes down.

Every surface ships it: a ``runtime`` section rides ``CollectTelemetry``
(merged fleet-wide by the FleetCollector), the
``jax_compiles_total{fn,kind}`` / ``jax_compile_seconds`` /
``jax_device_memory_bytes{plane}`` families are alertable, ``status
--fleet`` prints a ``runtime:`` line, each compile lands in the span
timeline as a ``jax.compile`` event (so ``perf --critical-path`` can
name a mid-round recompile), and ``perf --compile-report`` renders the
per-fn table + offenders from a live run dir.

Opt-out ``telemetry.runtime.enabled=false``: no listener is ever
installed, wrapped jits pass straight through (one attribute check),
and the ``CollectTelemetry`` section is an ``{"enabled": false}`` stub.
The CI gate ``python -m metisfl_tpu.telemetry --runtime-smoke``
(scripts/chaos_smoke.sh) runs a synthetic round loop plus a
continuous-batching decode burst and fails the build if steady-state
(post-warmup) compiles are nonzero, if a deliberately shape-shifting
control run does NOT trip the detector, or if wrapper overhead exceeds
the pinned budget.
"""

from __future__ import annotations

import json
import logging
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from metisfl_tpu.telemetry import events as _events
from metisfl_tpu.telemetry import metrics as _metrics
from metisfl_tpu.telemetry import trace as _trace

logger = logging.getLogger("metisfl_tpu.telemetry.runtime")

# defaults (config/federation.py RuntimeConfig mirrors them, test-pinned)
DEFAULT_BUDGET = 256          # exact per-fn rows kept; the crowd → _other
DEFAULT_MEM_EVERY_S = 1.0     # memory-sample gate on the prof tick cadence
DEFAULT_STORM_WINDOW_S = 10.0
DEFAULT_STORM_THRESHOLD = 4   # recompiles of ONE fn inside the window

# the one duration event that fires exactly once per real XLA compile
# (jaxpr trace / MLIR lowering fire their own events; counting those
# would triple every compile)
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# fires inside that compile's window when the persistent cache served it
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

UNATTRIBUTED = "(unattributed)"
OTHER = "_other"

# metric families (telemetry/__init__.py re-exports them as M_*
# constants; catalog rows in docs/OBSERVABILITY.md)
JAX_COMPILES_TOTAL = "jax_compiles_total"
JAX_COMPILE_SECONDS = "jax_compile_seconds"
JAX_DEVICE_MEMORY_BYTES = "jax_device_memory_bytes"

_REG = _metrics.registry()
_M_COMPILES = _REG.counter(
    JAX_COMPILES_TOTAL,
    "XLA compilations by wrapped-function name and kind (cold = first "
    "compile of the fn, recompile = any later one — a new abstract "
    "signature or an LRU-evicted program)", ("fn", "kind"),
    budget_label="fn")
_M_COMPILE_SECONDS = _REG.histogram(
    JAX_COMPILE_SECONDS,
    "Backend (XLA) compile duration per compilation")
_M_DEVICE_MEMORY = _REG.gauge(
    JAX_DEVICE_MEMORY_BYTES,
    "Accelerator memory in use by plane (device memory_stats where the "
    "backend reports it, live-array bytes else, host RSS on CPU)",
    ("plane",))


# --------------------------------------------------------------------- #
# state
# --------------------------------------------------------------------- #

class _State:
    def __init__(self):
        self.enabled = True       # always-on posture; apply_config re-arms
        self.budget = DEFAULT_BUDGET
        self.mem_every_s = DEFAULT_MEM_EVERY_S
        self.storm_window_s = DEFAULT_STORM_WINDOW_S
        self.storm_threshold = DEFAULT_STORM_THRESHOLD
        self.plane = "host"
        self.lock = threading.Lock()
        # fn -> {"cold", "recompiles", "cache_hits", "total_s", "max_s",
        #        "last_sig"}
        self.fns: Dict[str, Dict[str, Any]] = {}
        self.compiles = 0
        self.recompiles = 0
        self.unattributed = 0
        self.storms = 0
        self.recent: deque = deque(maxlen=64)
        self.recompile_ts: deque = deque()    # (ts, fn) inside the window
        self.storm_mute: Dict[str, float] = {}
        self.memory: Dict[str, Any] = {}
        self.mem_sampled_ts = 0.0
        self.started_ts = 0.0


_STATE = _State()
_TLS = threading.local()
# "none" (never armed) | "monitoring"
_LISTENER_MODE = "none"
_LISTENER_LOCK = threading.Lock()


def enabled() -> bool:
    return _STATE.enabled


def listener_mode() -> str:
    """How compiles are observed: ``monitoring`` (jax.monitoring duration
    listener) or ``none`` (never armed — the opt-out pin)."""
    return _LISTENER_MODE


def plane() -> str:
    return _STATE.plane


def set_plane(service: str) -> None:
    """Derive the memory-attribution plane from a process's service name
    (apply_config passes it): learner train / controller fold / serving
    decode, ``host`` for anything else (tests, CLIs)."""
    s = (service or "").lower()
    if s.startswith("controller") or s.startswith("standby"):
        _STATE.plane = "controller"
    elif s.startswith("learner"):
        _STATE.plane = "learner"
    elif s.startswith("serving") or s.startswith("gateway") \
            or s.startswith("replica") or s.startswith("router"):
        _STATE.plane = "serving"
    else:
        _STATE.plane = "host"


def _install_listener() -> None:
    """Arm the jax.monitoring duration listener exactly once per process
    (jax.monitoring has no unregister; the listener itself gates on
    ``_STATE.enabled``, so a later opt-out costs one call per compile —
    and compiles are the rare event this plane exists to catch)."""
    global _LISTENER_MODE
    with _LISTENER_LOCK:
        if _LISTENER_MODE != "none":
            return
        from jax import monitoring as _monitoring

        _monitoring.register_event_duration_secs_listener(_on_duration)
        _monitoring.register_event_listener(_on_event)
        _LISTENER_MODE = "monitoring"


def _on_event(event: str, **kwargs) -> None:
    """Persistent-cache hit: flag the compile in flight on this thread;
    the backend-compile duration event that closes it consumes the flag."""
    if _STATE.enabled and event == _CACHE_HIT_EVENT:
        _TLS.cache_hit = True


def _on_duration(event: str, duration: float, **kwargs) -> None:
    """The registered jax.monitoring listener. Fires in the thread that
    triggered the compile; attribution comes from the thread-local
    context a :func:`monitored_jit` wrapper set around its call."""
    if not _STATE.enabled or event != _BACKEND_COMPILE_EVENT:
        return
    cached = getattr(_TLS, "cache_hit", False)
    _TLS.cache_hit = False
    pending = getattr(_TLS, "pending", None)
    if pending is not None:
        # inside a monitored call window: the wrapper records it (with
        # the signature it only computes because this fired)
        pending.append((float(duration), cached))
    else:
        _record_compile(UNATTRIBUTED, "", float(duration), cached)


def configure(enabled: bool = True, budget: int = 0,
              mem_every_s: float = 0.0, storm_window_s: float = 0.0,
              storm_threshold: int = 0) -> None:
    """(Re)arm the runtime plane from ``telemetry.runtime``: installs the
    compile listener (once) and sizes the bounded state. Zero values keep
    the defaults. ``enabled=False`` installs nothing — wrapped jits pass
    straight through at one attribute check."""
    _STATE.enabled = bool(enabled)
    if not enabled:
        return
    _STATE.budget = int(budget or 0) or DEFAULT_BUDGET
    _STATE.mem_every_s = float(mem_every_s or 0.0) or DEFAULT_MEM_EVERY_S
    _STATE.storm_window_s = (float(storm_window_s or 0.0)
                             or DEFAULT_STORM_WINDOW_S)
    _STATE.storm_threshold = (int(storm_threshold or 0)
                              or DEFAULT_STORM_THRESHOLD)
    if not _STATE.started_ts:
        _STATE.started_ts = time.time()
    _install_listener()
    # memory sampling rides the PR 12 sampler cadence (time-gated here)
    from metisfl_tpu.telemetry import prof as _prof

    _prof.register_tick_hook(_tick)


def ensure_started() -> None:
    """Lazy arming (the span-ring/prof posture): a process nobody
    configured arms the listener once a collector actually pulls it."""
    if _STATE.enabled and _LISTENER_MODE == "none":
        configure(enabled=True)


def reset() -> None:
    """Tests: clear every table/counter and restore defaults. The
    process-level listener stays installed (jax.monitoring has no
    unregister) but re-arms against the fresh state."""
    st = _STATE
    with st.lock:
        st.fns.clear()
        st.recent.clear()
        st.recompile_ts.clear()
        st.storm_mute.clear()
        st.compiles = st.recompiles = st.unattributed = st.storms = 0
        st.memory = {}
        st.mem_sampled_ts = 0.0
        st.started_ts = 0.0
    st.enabled = True
    st.budget = DEFAULT_BUDGET
    st.mem_every_s = DEFAULT_MEM_EVERY_S
    st.storm_window_s = DEFAULT_STORM_WINDOW_S
    st.storm_threshold = DEFAULT_STORM_THRESHOLD
    st.plane = "host"


# --------------------------------------------------------------------- #
# compile recording
# --------------------------------------------------------------------- #

def _fn_row(fn: str) -> Dict[str, Any]:
    """The (locked) per-fn row, folding past-budget names into _other."""
    st = _STATE
    row = st.fns.get(fn)
    if row is None:
        if len(st.fns) >= st.budget and fn not in (OTHER,):
            fn = OTHER
            row = st.fns.get(OTHER)
        if row is None:
            row = st.fns[fn] = {"cold": 0, "recompiles": 0,
                                "cache_hits": 0,
                                "total_s": 0.0, "max_s": 0.0,
                                "last_sig": ""}
    return row


def _record_compile(fn: str, sig: str, duration_s: float,
                    cached: bool = False) -> None:
    st = _STATE
    now = time.time()
    with st.lock:
        known = fn in st.fns or (len(st.fns) >= st.budget
                                 and OTHER in st.fns and fn != UNATTRIBUTED)
        row = _fn_row(fn)
        # an unattributed compile is never a "recompile": the label is a
        # bucket of many unrelated functions (jnp internals, model init),
        # not one function compiling twice
        kind = ("recompile"
                if (known and row["cold"] and fn != UNATTRIBUTED)
                else "cold")
        if kind == "cold":
            row["cold"] += 1
        else:
            row["recompiles"] += 1
            st.recompiles += 1
        row["cache_hits"] += int(cached)
        row["total_s"] += duration_s
        row["max_s"] = max(row["max_s"], duration_s)
        row["last_sig"] = sig
        st.compiles += 1
        if fn == UNATTRIBUTED:
            st.unattributed += 1
        st.recent.append([round(now, 3), fn, kind,
                          round(duration_s, 6), sig])
        storm = None
        if kind == "recompile":
            window = st.storm_window_s
            st.recompile_ts.append((now, fn))
            while st.recompile_ts and st.recompile_ts[0][0] < now - window:
                st.recompile_ts.popleft()
            count = sum(1 for _ts, name in st.recompile_ts if name == fn)
            if (count >= st.storm_threshold
                    and now - st.storm_mute.get(fn, 0.0) > window):
                st.storm_mute[fn] = now
                st.storms += 1
                storm = count
    _M_COMPILES.inc(fn=fn, kind=kind)
    _M_COMPILE_SECONDS.observe(duration_s)
    # the span-timeline record: a mid-round compile becomes a child of
    # whatever span is active in this thread, so perf --critical-path
    # can name it as the dominant edge
    attrs = {"fn": fn, "kind": kind}
    if sig:
        attrs["sig"] = sig
    _trace.event("jax.compile", duration_s, attrs=attrs)
    if storm is not None:
        _events.emit(_events.RecompileStorm, fn=fn, count=storm,
                     window_s=round(st.storm_window_s, 1),
                     last_sig=sig)


def _abstract_sig(args, kwargs) -> str:
    """Abstract (shape, dtype) signature of a call's array leaves —
    computed only when a compile actually fired during the call."""
    try:
        import jax

        leaves = jax.tree_util.tree_leaves((args, kwargs))
    except Exception:  # noqa: BLE001 - a signature is diagnostic sugar
        return "?"
    parts: List[str] = []
    for leaf in leaves[:64]:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{dtype}[{','.join(str(d) for d in shape)}]")
        else:
            parts.append(type(leaf).__name__)
    if len(leaves) > 64:
        parts.append(f"+{len(leaves) - 64}")
    return ";".join(parts)


def monitored_jit(fn: Callable, *, name: str = "", **jit_kwargs):
    """``jax.jit`` with compile attribution: any XLA compile that fires
    during a call is recorded under ``name`` (default: the function's
    ``__name__``) with the call's abstract shape signature. Steady-state
    calls (nothing compiling) pay one attribute check plus a
    thread-local set/restore; with the plane disabled, the check alone.
    """
    import functools

    import jax

    label = name or getattr(fn, "__name__", "jit_fn")
    if name:
        # the label is also the program's name on the device: XLA calls
        # the module ``jit_<function name>``, so ``decode.step`` reads
        # ``jit_decode_step`` in a profiler trace, whatever the closure
        # behind it is called (``wraps`` keeps the signature jit reads)
        inner = fn

        @functools.wraps(inner)
        def fn(*args, **kwargs):
            return inner(*args, **kwargs)

        fn.__name__ = fn.__qualname__ = name.replace(".", "_")
    compiled = jax.jit(fn, **jit_kwargs)
    # lazy arming: a process that jits through us observes its own
    # compiles even before any collector pull (no-op when opted out)
    ensure_started()

    def wrapper(*args, **kwargs):
        if not _STATE.enabled:
            return compiled(*args, **kwargs)
        prev_pending = getattr(_TLS, "pending", None)
        _TLS.pending = []
        try:
            out = compiled(*args, **kwargs)
        finally:
            fired, _TLS.pending = _TLS.pending, prev_pending
            if fired:
                sig = _abstract_sig(args, kwargs)
                for duration, cached in fired:
                    _record_compile(label, sig, duration, cached)
        return out

    wrapper.__name__ = label
    wrapper.__wrapped__ = compiled
    return wrapper


# --------------------------------------------------------------------- #
# memory accounting
# --------------------------------------------------------------------- #

def _host_rss_bytes() -> int:
    try:
        with open("/proc/self/statm") as fh:
            pages = int(fh.read().split()[1])
        import resource

        return pages * resource.getpagesize()
    except (OSError, ValueError, IndexError, ImportError):
        try:
            import resource

            return resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss * 1024
        except Exception:  # noqa: BLE001
            return 0


def memory_snapshot() -> Dict[str, Any]:
    """One memory sample: device bytes-in-use where the backend reports
    them (TPU/GPU ``memory_stats``), live-array nbytes else, host RSS
    always. ``source`` names what ``device_bytes`` came from."""
    device_bytes = 0
    live_bytes = 0
    live_n = 0
    backend = ""
    source = "rss"
    try:
        import jax

        backend = jax.default_backend()
        stats_bytes = 0
        for dev in jax.local_devices():
            stats = None
            try:
                stats = dev.memory_stats()
            except Exception:  # noqa: BLE001 - per-device support varies
                stats = None
            if stats:
                stats_bytes += int(stats.get("bytes_in_use", 0) or 0)
        arrays = jax.live_arrays()
        live_n = len(arrays)
        live_bytes = sum(int(getattr(a, "nbytes", 0) or 0) for a in arrays)
        if stats_bytes:
            device_bytes, source = stats_bytes, "device_stats"
        elif live_bytes:
            device_bytes, source = live_bytes, "live_arrays"
    except Exception:  # noqa: BLE001 - no jax: RSS is the whole story
        pass
    rss = _host_rss_bytes()
    if not device_bytes:
        device_bytes = rss
        source = "rss"
    return {"ts": round(time.time(), 3), "plane": _STATE.plane,
            "backend": backend, "source": source,
            "device_bytes": int(device_bytes),
            "live_arrays": live_n, "live_array_bytes": int(live_bytes),
            "host_rss_bytes": int(rss)}


def sample_memory(force: bool = False) -> Optional[Dict[str, Any]]:
    """Refresh the memory sample when the ``mem_every_s`` gate allows
    (``force`` skips the gate) and export the per-plane gauge. Returns
    the sample taken, or None when gated off / disabled."""
    if not _STATE.enabled:
        return None
    now = time.time()
    if not force and now - _STATE.mem_sampled_ts < _STATE.mem_every_s:
        return None
    snap = memory_snapshot()
    with _STATE.lock:
        _STATE.memory = snap
        _STATE.mem_sampled_ts = now
    _M_DEVICE_MEMORY.set(float(snap["device_bytes"]), plane=snap["plane"])
    return snap


def _tick() -> None:
    """The prof-sampler tick hook (PR 12 cadence), time-gated by
    ``mem_every_s`` so a 67 Hz sampler costs one memory walk per
    second, not 67."""
    try:
        sample_memory()
    except Exception:  # noqa: BLE001 - telemetry must never take the
        # sampler thread down
        logger.exception("runtime memory sample failed")


# --------------------------------------------------------------------- #
# the CollectTelemetry section + fleet merge + analytics
# --------------------------------------------------------------------- #

def collect_state() -> Dict[str, Any]:
    """The ``runtime`` section of a ``CollectTelemetry`` reply: bounded
    per-fn compile rows, totals, the recent-compile ring, and the latest
    memory sample. ``{"enabled": false}`` stub when opted out."""
    if not _STATE.enabled:
        return {"enabled": False}
    sample_memory()
    st = _STATE
    with st.lock:
        return {
            "enabled": True,
            "listener": _LISTENER_MODE,
            "plane": st.plane,
            "budget": st.budget,
            "compiles": st.compiles,
            "recompiles": st.recompiles,
            "unattributed": st.unattributed,
            "storms": st.storms,
            "fns": {fn: dict(row) for fn, row in st.fns.items()},
            "recent": [list(r) for r in st.recent],
            "memory": dict(st.memory),
        }


def merge_states(states: List[Dict[str, Any]],
                 budget: int = 0) -> Dict[str, Any]:
    """Fold several peers' ``collect_state`` dicts into one (key-wise
    sums, max of maxima, budget + ``_other`` rollup preserved) — the
    FleetCollector's merged ``runtime`` view. Disabled stubs pass
    through without contributing."""
    budget = int(budget or 0) or DEFAULT_BUDGET
    out: Dict[str, Any] = {"enabled": True, "compiles": 0,
                           "recompiles": 0, "unattributed": 0,
                           "storms": 0, "fns": {}, "memory": {}}
    fns: Dict[str, Dict[str, Any]] = out["fns"]
    any_enabled = False
    for state in states:
        if not state or not state.get("enabled"):
            continue
        any_enabled = True
        for key in ("compiles", "recompiles", "unattributed", "storms"):
            out[key] += int(state.get(key, 0) or 0)
        for fn, row in (state.get("fns") or {}).items():
            if fn not in fns and len(fns) >= budget and fn != OTHER:
                fn = OTHER
            dst = fns.setdefault(fn, {"cold": 0, "recompiles": 0,
                                      "cache_hits": 0,
                                      "total_s": 0.0, "max_s": 0.0,
                                      "last_sig": ""})
            dst["cold"] += int(row.get("cold", 0) or 0)
            dst["recompiles"] += int(row.get("recompiles", 0) or 0)
            dst["cache_hits"] += int(row.get("cache_hits", 0) or 0)
            dst["total_s"] += float(row.get("total_s", 0.0) or 0.0)
            dst["max_s"] = max(dst["max_s"],
                               float(row.get("max_s", 0.0) or 0.0))
            dst["last_sig"] = dst["last_sig"] or str(
                row.get("last_sig", ""))
        mem = state.get("memory") or {}
        if mem.get("device_bytes"):
            mem_plane = str(mem.get("plane", "host"))
            out["memory"][mem_plane] = max(
                int(out["memory"].get(mem_plane, 0)),
                int(mem.get("device_bytes", 0)))
    out["enabled"] = any_enabled
    return out


def compile_rows(state: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Per-fn report rows from a ``collect_state``/``merge_states``
    dict, recompile-count descending then total-time descending — the
    ``perf --compile-report`` table."""
    rows = []
    for fn, row in (state.get("fns") or {}).items():
        rows.append({
            "fn": fn,
            "compiles": int(row.get("cold", 0)) + int(
                row.get("recompiles", 0)),
            "cold": int(row.get("cold", 0)),
            "recompiles": int(row.get("recompiles", 0)),
            "cache_hits": int(row.get("cache_hits", 0)),
            "total_s": round(float(row.get("total_s", 0.0)), 4),
            "max_s": round(float(row.get("max_s", 0.0)), 4),
            "last_sig": str(row.get("last_sig", "")),
        })
    rows.sort(key=lambda r: (-r["recompiles"], -r["total_s"], r["fn"]))
    return rows


def summarize_state(state: Dict[str, Any]) -> Dict[str, Any]:
    """One peer's runtime plane in one line's worth of fields for
    ``status --fleet``: compile totals, the worst recompile offender,
    and the latest memory sample."""
    out: Dict[str, Any] = {
        "enabled": bool(state.get("enabled", False)),
        "compiles": int(state.get("compiles", 0) or 0),
        "recompiles": int(state.get("recompiles", 0) or 0),
        "storms": int(state.get("storms", 0) or 0),
    }
    rows = compile_rows(state)
    offenders = [r for r in rows if r["recompiles"]]
    if offenders:
        out["top_offender"] = offenders[0]["fn"]
        out["top_offender_recompiles"] = offenders[0]["recompiles"]
    mem = state.get("memory") or {}
    if mem.get("device_bytes"):
        out["mem_bytes"] = int(mem["device_bytes"])
        out["mem_source"] = str(mem.get("source", ""))
    return out


def postmortem_snapshot() -> Optional[Dict[str, Any]]:
    """The runtime plane's view at death (None when disabled or nothing
    ever compiled — a silent bundle key beats an empty section)."""
    if not _STATE.enabled:
        return None
    state = collect_state()
    if not state.get("compiles"):
        return None
    return {"compiles": state["compiles"],
            "recompiles": state["recompiles"],
            "storms": state["storms"],
            "top": compile_rows(state)[:10],
            "memory": state.get("memory") or {}}


# --------------------------------------------------------------------- #
# CI gate (scripts/chaos_smoke.sh --runtime-smoke stanza)
# --------------------------------------------------------------------- #

def _smoke_round_kernel():
    """A synthetic jitted round kernel: one monitored train-ish step
    over a synthetic two-tensor model (the models/ops.py posture)."""
    import jax
    import jax.numpy as jnp

    def step(params, x):
        h = jnp.tanh(x @ params["w"] + params["b"])
        loss = jnp.mean(jnp.square(h))
        grads = jax.grad(
            lambda p: jnp.mean(jnp.square(
                jnp.tanh(x @ p["w"] + p["b"]))))(params)
        params = {k: v - 0.01 * grads[k] for k, v in params.items()}
        return params, loss

    return monitored_jit(step, name="runtime.smoke_step")


def _smoke_decoder(vocab: int = 97):
    """A tiny slot decoder + its variables (the PR 13 decode path)."""
    import numpy as np

    from metisfl_tpu.models.ops import FlaxModelOps
    from metisfl_tpu.models.zoo.transformer import LlamaLite

    ops = FlaxModelOps(LlamaLite(vocab_size=vocab, dim=32, depth=1,
                                 heads=4),
                       np.zeros((1, 8), np.int32), rng_seed=7)
    return ops, ops.get_variables()


def _smoke(overhead_budget_ns: float = 50_000.0, trials: int = 5,
           steady_iters: int = 30) -> int:
    """The CI gate: (1) the synthetic round loop + a continuous-batching
    decode burst must report ZERO post-warmup compiles; (2) a
    deliberately shape-shifting control run must report NONZERO
    recompiles (the detector provably fires, storm event included);
    (3) steady-state wrapper overhead must stay under
    ``overhead_budget_ns`` per call (minima judged, the prof-smoke
    posture). Exit 0 = gate passed, 1 = failed."""
    import numpy as np

    reset()
    configure(enabled=True, storm_threshold=3, storm_window_s=60.0)
    _events.configure(enabled=True, service="runtime-smoke", dir="")
    failures: List[str] = []

    # --- round loop: warmup compiles, then steady shapes -------------- #
    step = _smoke_round_kernel()
    rng = np.random.default_rng(5)
    params = {"w": rng.standard_normal((128, 64)).astype(np.float32),
              "b": rng.standard_normal((64,)).astype(np.float32)}
    x = rng.standard_normal((32, 128)).astype(np.float32)
    params, _ = step(params, x)      # warmup (the one cold compile)
    warm_state = collect_state()
    warm_compiles = warm_state["compiles"]
    for _ in range(steady_iters):
        params, _ = step(params, x)
    steady_state = collect_state()
    round_steady = steady_state["compiles"] - warm_compiles
    if round_steady:
        failures.append(f"round loop compiled {round_steady}x "
                        "post-warmup (expected 0)")
    if not warm_compiles:
        failures.append("round-loop warmup compile was never observed "
                        "(listener blind)")

    # --- continuous-batching decode burst ----------------------------- #
    decode_steady = -1
    try:
        from metisfl_tpu.serving.decode import ContinuousBatcher

        ops, variables = _smoke_decoder()
        batcher = ContinuousBatcher(ops, version=1, variables=variables,
                                    slots=2, max_len=64)
        try:
            prompt = np.arange(1, 9, dtype=np.int32)  # fixed length 8
            # warmup burst: prefill@8 + the step program compile
            for fut in [batcher.submit(prompt, 4) for _ in range(2)]:
                fut.result(timeout=60)
            warm = collect_state()["compiles"]
            for fut in [batcher.submit(prompt, 4) for _ in range(6)]:
                fut.result(timeout=60)
            decode_steady = collect_state()["compiles"] - warm
        finally:
            batcher.close()
        if decode_steady:
            failures.append(f"decode burst compiled {decode_steady}x "
                            "post-warmup (expected 0)")
    except Exception as exc:  # noqa: BLE001 - the decode path must run
        failures.append(f"decode burst crashed: {exc}")

    # --- shape-shifting control: the detector must FIRE --------------- #
    control = _smoke_round_kernel()
    pre = collect_state()["recompiles"]
    pre_storms = collect_state()["storms"]
    for width in (8, 16, 24, 40, 48):
        xs = rng.standard_normal((width, 128)).astype(np.float32)
        control(params, xs)
    control_recompiles = collect_state()["recompiles"] - pre
    control_storms = collect_state()["storms"] - pre_storms
    if not control_recompiles:
        failures.append("shape-shifting control run reported zero "
                        "recompiles (the detector never fired)")
    if not control_storms:
        failures.append("recompile storm never detected for the "
                        "shape-shifting control run")

    # --- wrapper overhead: monitored vs raw, minima judged ------------ #
    import jax

    def tiny(v):
        return v * 2.0 + 1.0

    raw = jax.jit(tiny)
    mon = monitored_jit(tiny, name="runtime.smoke_tiny")
    v = np.ones((16,), np.float32)
    raw(v), mon(v)  # both compiled before timing
    iters = 2000

    def _per_call_ns(fn) -> float:
        t0 = time.perf_counter()
        for _ in range(iters):
            fn(v)
        return (time.perf_counter() - t0) / iters * 1e9

    raw_ns = min(_per_call_ns(raw) for _ in range(trials))
    mon_ns = min(_per_call_ns(mon) for _ in range(trials))
    overhead_ns = max(0.0, mon_ns - raw_ns)
    if overhead_ns > overhead_budget_ns:
        failures.append(f"wrapper overhead {overhead_ns:.0f}ns/call over "
                        f"the {overhead_budget_ns:.0f}ns budget")

    state = collect_state()
    summary = {
        "listener": listener_mode(),
        "warmup_compiles": warm_compiles,
        "round_steady_compiles": round_steady,
        "decode_steady_compiles": decode_steady,
        "control_recompiles": control_recompiles,
        "control_storms": control_storms,
        "overhead_ns_per_call": round(overhead_ns, 1),
        "overhead_budget_ns": overhead_budget_ns,
        "compiles_total": state["compiles"],
        "recompiles_total": state["recompiles"],
        "memory": state.get("memory") or {},
        "failures": failures,
    }
    print(json.dumps(summary, indent=2))
    return 1 if failures else 0


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    import sys

    parser = argparse.ArgumentParser(
        "metisfl_tpu.telemetry.runtime",
        description="accelerator runtime observability utilities")
    parser.add_argument("--smoke", action="store_true",
                        help="run the CI gate (zero steady-state "
                             "recompiles + detector fires + overhead "
                             "budget; exit 1 on failure)")
    parser.add_argument("--overhead-budget-ns", type=float,
                        default=50_000.0,
                        help="smoke: max tolerated wrapper overhead per "
                             "steady-state call")
    parser.add_argument("--trials", type=int, default=5,
                        help="smoke: overhead timing trials (minima "
                             "judged)")
    args = parser.parse_args(argv)
    if args.smoke:
        return _smoke(overhead_budget_ns=args.overhead_budget_ns,
                      trials=args.trials)
    parser.print_usage()
    return 2


if __name__ == "__main__":
    import sys

    sys.exit(main())
