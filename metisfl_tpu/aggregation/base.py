"""Aggregation interfaces and the shared jit-compiled pytree kernels.

Design: every rule consumes ``(model_pytree, scale)`` pairs and produces a
community model pytree. Arithmetic runs in an accumulator dtype (f32, or f64
for f64 inputs) and is cast back to each tensor's storage dtype at the end —
integer tensors round-to-nearest, matching the reference's behavior of
aggregating every dtype (federated_average_test.cc exercises uint16 models).

The two kernels (`scaled_add`, `finalize`) are jit-compiled once per model
tree-structure/shape and reused across rounds and rules; XLA fuses the whole
model into one executable instead of the reference's per-variable OpenMP loop
(federated_average.cc:101).
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Dict, Iterable, List, Optional, Protocol, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger("metisfl_tpu.aggregation")

Pytree = Any


def _acc_dtype(dtype) -> jnp.dtype:
    dtype = jnp.dtype(dtype)
    if dtype == jnp.float64:
        return jnp.float64
    return jnp.float32


_WIDE = tuple(np.dtype(d) for d in (np.float64, np.int64, np.uint64))


def use_numpy_fold(tree) -> bool:
    """True when the tree carries 64-bit tensors but jax x64 is disabled.

    The aggregation contract is dtype-preserving (the reference aggregates
    all 10 wire dtypes — federated_average_test.cc); jit kernels would
    silently truncate f64 under the default x32 mode, and flipping the
    process-global ``jax_enable_x64`` flag mid-run can change the semantics
    of every other compiled function in the controller process. Instead,
    wide trees fold on host numpy (they are a rare cross-silo compatibility
    case, not the TPU hot path)."""
    if jax.config.jax_enable_x64:
        return False
    return any(np.dtype(leaf.dtype) in _WIDE for leaf in jax.tree.leaves(tree))


def is_host_tree(tree) -> bool:
    """True when every leaf is host-resident (plain numpy, not jax.Array).

    Fold locale policy: models that arrived over the wire (gRPC transport)
    are host numpy and fold on host BLAS — FedAvg is a ~1 FLOP/byte streaming
    op, so shipping N models over PCIe to reduce them on the device
    wastes exactly the bandwidth the seed's target budgets (BASELINE.md:
    ≤2 s a round at 64 learners). Device-resident trees (co-located
    learner output, pod mode) fold on device; cross-learner pod aggregation
    is the psum in :mod:`metisfl_tpu.parallel.collectives`."""
    leaves = jax.tree.leaves(tree)
    return bool(leaves) and all(
        isinstance(leaf, np.ndarray) and not isinstance(leaf, jax.Array)
        for leaf in leaves)


@jax.jit
def scaled_init(model: Pytree, scale) -> Pytree:
    """acc = scale * model, in accumulator dtype."""
    return jax.tree.map(
        lambda x: jnp.asarray(x, _acc_dtype(x.dtype)) * scale, model
    )


@jax.jit
def scaled_add(acc: Pytree, model: Pytree, scale) -> Pytree:
    """acc += scale * model (single fused XLA computation over the tree)."""
    return jax.tree.map(
        lambda a, x: a + jnp.asarray(x, a.dtype) * scale, acc, model
    )


@jax.jit
def scaled_sub(acc: Pytree, model: Pytree, scale) -> Pytree:
    """acc -= scale * model."""
    return jax.tree.map(
        lambda a, x: a - jnp.asarray(x, a.dtype) * scale, acc, model
    )


@jax.jit
def stacked_scaled_init(scales, *block) -> Pytree:
    """acc = Σᵢ scalesᵢ · blockᵢ for a whole block in one fused program.

    ``block`` is a sequence of model pytrees; stacking happens INSIDE jit so
    device-resident models never round-trip through the host, and the
    weighted reduce is a single fused tensordot per leaf (MXU-friendly)."""
    return jax.tree.map(
        lambda *xs: jnp.tensordot(
            scales.astype(_acc_dtype(xs[0].dtype)),
            jnp.stack([jnp.asarray(x, _acc_dtype(x.dtype)) for x in xs]),
            axes=1),
        *block)


@jax.jit
def stacked_scaled_add(acc: Pytree, scales, *block) -> Pytree:
    """acc += Σᵢ scalesᵢ · blockᵢ (fused block fold, stack inside jit)."""
    return jax.tree.map(
        lambda a, *xs: a + jnp.tensordot(
            scales.astype(a.dtype),
            jnp.stack([jnp.asarray(x, a.dtype) for x in xs]), axes=1),
        acc, *block)


def finalize(acc: Pytree, z, like: Optional[Pytree] = None,
             dtypes: Optional[Tuple[str, ...]] = None) -> Pytree:
    """community = acc / z, cast back to storage dtypes (from ``like`` or an
    explicit ``dtypes`` tuple in leaf order)."""
    acc_leaves, treedef = jax.tree.flatten(acc)
    if dtypes is None:
        dtypes = tuple(str(x.dtype) for x in jax.tree.leaves(like))
    out_leaves = _finalize_flat(tuple(acc_leaves), z, dtypes)
    return jax.tree.unflatten(treedef, out_leaves)


@functools.partial(jax.jit, static_argnames=("dtypes",))
def _finalize_flat(acc_leaves, z, dtypes):
    out = []
    for a, dtype in zip(acc_leaves, dtypes):
        value = a / z
        if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
            value = jnp.round(value)
        out.append(value.astype(dtype))
    return tuple(out)


# -- host-numpy fold (64-bit trees under x32 mode; see use_numpy_fold) -------

def _np_acc_dtype(dtype) -> np.dtype:
    return np.dtype(np.float64 if np.dtype(dtype) in _WIDE else np.float32)


def np_scaled_init(model: Pytree, scale) -> Pytree:
    return jax.tree.map(
        lambda x: np.asarray(x, _np_acc_dtype(np.asarray(x).dtype)) * scale,
        model)


def np_scaled_add(acc: Pytree, model: Pytree, scale) -> Pytree:
    return jax.tree.map(lambda a, x: a + np.asarray(x, a.dtype) * scale,
                        acc, model)


def np_scaled_sub(acc: Pytree, model: Pytree, scale) -> Pytree:
    return jax.tree.map(lambda a, x: a - np.asarray(x, a.dtype) * scale,
                        acc, model)


_hostfold_lib = None


def _get_hostfold():
    """Native streaming-fold library (metisfl_tpu/native/hostfold.cc), or
    None when it cannot be built here — the numpy fold then serves. Which
    of the two it is gets logged once (and rides every round's metadata,
    :func:`host_fold_backend`): the two differ several-fold in speed, so a
    failed build must never be a silent one."""
    global _hostfold_lib
    if _hostfold_lib is None:
        try:
            from metisfl_tpu.native import load_hostfold
            _hostfold_lib = load_hostfold()
            logger.info("host fold: native (native/hostfold.cc)")
        except (OSError, RuntimeError) as exc:  # no g++ / build failure
            _hostfold_lib = False
            logger.warning("host fold: numpy — the native hostfold.cc "
                           "build failed: %s", exc)
    return _hostfold_lib or None


def host_fold_backend() -> str:
    """Which implementation folds host-resident f32/f64 trees in this
    process: ``"native"`` (hostfold.cc) or ``"numpy"``."""
    return "native" if _get_hostfold() is not None else "numpy"


def _native_fold(a, arrs, scales):
    """acc (+)= Σ scalesᵢ·arrsᵢ via hostfold.cc; None if not applicable.

    Streams each model once with no staging copy (the numpy path pays a
    full ``np.stack`` pass before its GEMV) — this is the controller's
    cross-host aggregation hot loop (``fold_ms`` of the benchmark's round
    cells)."""
    import ctypes

    lib = _get_hostfold()
    if lib is None:
        return None
    dt = arrs[0].dtype
    if any(x.dtype != dt for x in arrs):
        return None
    if dt == np.float32:
        fold, cptr = lib.hostfold_f32, ctypes.c_float
    elif dt == np.float64:
        fold, cptr = lib.hostfold_f64, ctypes.c_double
    else:
        return None
    if a is None:
        out, init = np.empty(arrs[0].shape, dt), 1
    elif a.dtype == dt and a.flags["C_CONTIGUOUS"]:
        out, init = a, 0
    else:
        return None
    ptr_t = ctypes.POINTER(cptr)
    contig = [np.ascontiguousarray(x) for x in arrs]
    ptrs = (ptr_t * len(contig))(*[x.ctypes.data_as(ptr_t) for x in contig])
    sc = np.ascontiguousarray(scales, np.float64)
    fold(out.ctypes.data_as(ptr_t), ptrs,
         sc.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
         len(contig), out.size, init)
    return out


def np_stacked_scaled_add(acc: Optional[Pytree], block: Sequence[Pytree],
                          scales: np.ndarray) -> Pytree:
    """Host block fold: acc += Σᵢ scalesᵢ · blockᵢ.

    Fast path: the native streaming fold (hostfold.cc — one pass per model,
    no staging copy). Fallback: one stacked (L, n) matvec per leaf, still ~an
    order of magnitude faster than per-model axpy for f32 models."""
    def fold(a, *xs):
        arrs = [np.asarray(x) for x in xs]
        native = _native_fold(a, arrs, scales)
        if native is not None:
            return native
        stack = np.stack(arrs)
        acc_dt = _np_acc_dtype(stack.dtype)
        flat = stack.reshape(len(xs), -1)
        v = (scales.astype(acc_dt) @ flat).reshape(stack.shape[1:])
        v = np.asarray(v, acc_dt)
        return v if a is None else a + v

    if acc is None:
        return jax.tree.map(lambda *xs: fold(None, *xs), *block)
    return jax.tree.map(lambda a, *xs: fold(a, *xs), acc, *block)


def np_finalize(acc: Pytree, z, like: Optional[Pytree] = None,
                dtypes: Optional[Tuple[str, ...]] = None) -> Pytree:
    leaves, treedef = jax.tree.flatten(acc)
    if dtypes is None:
        dtypes = tuple(str(np.asarray(x).dtype) for x in jax.tree.leaves(like))
    out = []
    for a, dtype in zip(leaves, dtypes):
        value = a / z
        if np.issubdtype(np.dtype(dtype), np.integer):
            value = np.rint(value)
        out.append(np.asarray(value).astype(dtype))
    return jax.tree.unflatten(treedef, out)


class AggState:
    """Mutable rolling-aggregation state kept across calls.

    Equivalent of the reference's ``FederatedRollingAverageBase`` members
    (federated_rolling_average_base.cc:175-291): the scaled community sum
    (``wc_scaled``) and the running normalization factor (``z``).
    """

    def __init__(self):
        self.wc_scaled: Optional[Pytree] = None
        self.z: float = 0.0
        # whether this state folds on host numpy (wide dtypes under x32)
        self.use_numpy: bool = False
        # learner_id -> (scale, model) of the latest counted contribution
        self.contributions: Dict[str, Tuple[float, Pytree]] = {}

    def reset(self) -> None:
        self.wc_scaled = None
        self.z = 0.0
        self.use_numpy = False
        self.contributions.clear()


class AggregationRule(Protocol):
    """One federation aggregation policy.

    ``required_lineage`` mirrors the reference's
    ``RequiredLearnerLineageLength`` (aggregation_function.h): how many recent
    models per learner the store must retain for this rule.
    """

    name: str
    required_lineage: int

    def aggregate(
        self,
        models: Sequence[Tuple[Sequence[Pytree], float]],
        state: Optional[AggState] = None,
    ) -> Pytree:
        """Aggregate ``models`` = [(lineage, scale), ...] → community pytree.

        ``lineage`` is the learner's most-recent-first model list (length ≥ 1;
        only :class:`FedRec` looks past index 0).
        """
        ...

    def reset(self) -> None:
        ...
