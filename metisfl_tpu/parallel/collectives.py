"""Federated collectives: aggregation that never leaves the device mesh.

The reference ships every model as a protobuf blob through gRPC and sums
byte-deserialized vectors on the controller's CPU (reference
controller.cc:795-950 + proto_tensor_serde.h). When learners co-reside on a
TPU pod slice, that entire path collapses into ONE jit-compiled weighted
``psum`` over the ``fed`` mesh axis riding ICI — no serialization, no host
round trip, no controller CPU in the loop. This module provides that kernel.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def to_varying(tree, axis_names):
    """Mark a replicated tree as device-varying over ``axis_names``.

    Required before ``jax.grad`` inside ``shard_map``: differentiating w.r.t.
    an *unvarying* (replicated) input transposes the implicit broadcast into
    a psum over the mesh — per-device gradients silently become cross-device
    sums. (VMA semantics; fixed here by casting params to varying so the
    cotangent stays per-device.)"""
    return jax.tree.map(
        lambda t: jax.lax.pcast(t, axis_names, to="varying"), tree)


def federated_mean_psum(params, scale, axis_name: str = "fed"):
    """Inside shard_map/pjit: weighted mean of per-learner params over the
    federation axis. ``scale`` is this learner's normalized weight."""
    return jax.tree.map(
        lambda x: jax.lax.psum(x * scale, axis_name), params)


def make_pod_aggregator(mesh: Mesh, param_specs, axis_name: str = "fed"
                        ) -> Callable:
    """Compile ``(stacked_params, scales) → community_params``.

    ``stacked_params``: every leaf has a leading learner axis of size
    ``mesh.shape[axis_name]``, sharded over ``fed`` (learner *i*'s model
    lives on its own slice). ``scales``: (L,) normalized weights. The
    returned community model is fully replicated — each learner reads its
    next-round weights locally with zero transfer.
    """
    fed = mesh.shape[axis_name]

    def _in_spec(spec):
        inner = spec if isinstance(spec, P) else P()
        return P(axis_name, *inner)

    in_specs = jax.tree.map(_in_spec, param_specs,
                            is_leaf=lambda x: isinstance(x, P))
    out_specs = param_specs

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(in_specs, P(axis_name)),
        out_specs=out_specs,
    )
    def _aggregate(stacked, scales):
        # each fed shard holds its learner's model: leading axis length 1
        local = jax.tree.map(lambda x: x[0], stacked)
        scale = scales[0]
        return jax.tree.map(
            lambda x: jax.lax.psum(
                (x * scale).astype(_acc(x.dtype)), axis_name).astype(x.dtype),
            local)

    return jax.jit(_aggregate)


def _acc(dtype):
    dtype = jnp.dtype(dtype)
    if dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)):
        return jnp.float32
    return dtype


def make_robust_pod_combine(mesh: Mesh, rule: str, trim: int = 0,
                            byzantine_f: int = 0, multi: int = 0,
                            axis_name: str = "fed") -> Callable:
    """Device-resident byzantine-robust combine for the ICI fast path.

    ``stacked`` trees carry a leading learner axis sharded over ``fed``
    (each learner's trained model on its own slice); the combine is a
    coordinate-wise median / trimmed mean over that axis, or (Multi-)Krum
    distance selection — XLA inserts the all-gather over ICI, sorts (or
    runs Krum's single Gram matmul on the MXU) on device, and the
    community model comes out replicated. Host-path parity: the same leaf
    math and scoring as aggregation/robust.py (one definition each);
    scales are ignored by construction — robustness comes precisely from
    not letting any learner claim more weight (robust.py module
    contract). Memory note: the gather materializes L models per device,
    the price of a sort/selection none of the psum algebra can pay."""
    if rule not in ("median", "trimmed_mean", "krum", "multikrum"):
        raise ValueError(f"unknown robust pod rule {rule!r}")
    # the ONE leaf/scoring definition shared with the host rules — parity
    # by construction, not by synchronized copies
    from metisfl_tpu.aggregation.robust import (
        Krum,
        _krum_scores,
        median_leaf,
        trimmed_mean_leaf,
    )

    if rule in ("krum", "multikrum"):
        L = mesh.shape[axis_name]
        host_rule = Krum(byzantine_f=byzantine_f, multi=multi, name=rule)
        f = host_rule._effective_f(L)
        m = host_rule._select_count(L)

        def combine(stacked):
            flat = jnp.concatenate(
                [s.astype(jnp.float32).reshape(s.shape[0], -1)
                 for s in jax.tree.leaves(stacked)], axis=1)
            scores = _krum_scores(flat, f)
            picked = jnp.argsort(scores)[:m]

            def leaf(s):
                # take the m picked rows FIRST, then cast — touching m
                # models instead of an f32 copy of all L gathered ones
                sel = jnp.take(s, picked, axis=0).astype(_acc(s.dtype))
                return sel.mean(axis=0).astype(s.dtype)

            return jax.tree.map(leaf, stacked)
    else:
        def combine(stacked):
            def leaf(s):
                acc = s.astype(_acc(s.dtype))
                r = (median_leaf(acc) if rule == "median"
                     else trimmed_mean_leaf(acc, trim))
                return r.astype(s.dtype)

            return jax.tree.map(leaf, stacked)

    return jax.jit(combine, out_shardings=NamedSharding(mesh, P()))


def replicate_to_fed(mesh: Mesh, params, axis_name: str = "fed"):
    """Place a host pytree fully replicated on the mesh."""
    sharding = NamedSharding(mesh, P())
    return jax.device_put(params, sharding)
