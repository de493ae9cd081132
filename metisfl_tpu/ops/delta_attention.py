"""Kimi Delta Attention (KDA): the gated delta rule with a decay per
channel, as Pallas TPU kernels and a plain-JAX chunked twin.

Per head, with the state ``S`` (Dk, Dv) starting at zero::

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = scale * S_t^T q_t

``g_t`` (Dk) is a log decay (``<= 0``), ``beta_t`` a scalar in (0, 1).
Nothing of shape (L, Dk, Dv) is materialized: time is cut into chunks of
``CHUNK`` positions, the state crosses chunk boundaries, and inside a
chunk the recurrence is solved at once in the WY (UT) form. With
``gamma`` the cumulative sum of ``g`` from the chunk's start (inclusive),
``S0`` the state at the start and rows the chunk's positions::

    N[t, i] = beta_t sum_c k_t[c] k_i[c] exp(gamma_t[c] - gamma_i[c])   i < t
    P[t, i] =        sum_c q_t[c] k_i[c] exp(gamma_t[c] - gamma_i[c])   i <= t
    T = (I + N)^-1
    U = T (beta V) - T (beta K exp(gamma)) S0
    O = scale (Q exp(gamma) S0 + P U)
    S_end = Diag(exp gamma_C) S0 + (K exp(gamma_C - gamma))^T U

A decay of ``exp(gamma_t - gamma_i)`` is never split into two factors
that leave the float range: ``N`` and ``P`` are built a block of ``SUB``
rows at a time against the cumulative decay at the block's start, so one
factor is at most 1 and the other at most ``exp(SUB * 5)`` under KDA's
gate bound of -5 (``exp(80)``, inside float32). ``T`` is solved by block
rows: each diagonal block's inverse as a product of ``(I + N^(2^i))``,
then the blocks below it in order.

- ``kda_fwd`` walks a head group's chunks in order, the state (Dv, Dk)
  resident in VMEM, and writes each chunk's starting state.
- ``kda_bwd`` walks them in reverse: it recomputes a chunk from its
  stored starting state and pulls the output's and the state's cotangents
  back through it (``jax.vjp`` of the very function the forward runs),
  carrying the state's cotangent to the chunk before.

All arithmetic is float32 at ``HIGHEST`` precision whatever the caller
computes in; ``HEADS`` heads share a grid step, so that their chains of
small products interleave. A caller takes :func:`delta_attention_kernels`
on a TPU (elsewhere they run in Pallas's interpreter) and the twin,
:func:`delta_attention_plain`, where the kernels are not asked for.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
SUB = 16                # rows of a block of N and P; a divisor of CHUNK
HEADS = 8               # heads a grid step
_HI = jax.lax.Precision.HIGHEST
_PARAMS = dict(dimension_semantics=("parallel", "arbitrary"),
               vmem_limit_bytes=64 * 1024 * 1024)


def _mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


def _unit_lower_inverse(n, rows, cols, at):
    """``(I + n)^-1`` of a strictly lower ``n`` (h, C, C)."""
    C = n.shape[1]
    eye = jnp.where(rows == cols, 1.0, 0.0)
    block = jnp.where(rows // SUB == cols // SUB, n, 0.0)
    # (I + B)^-1 = (I - B)(I + B^2)(I + B^4)(I + B^8) where B^16 = 0
    diag, power = eye - block, block
    for _ in range(int(np.log2(SUB)) - 1):
        power = _mm("hts,hsu->htu", power, power)
        diag = _mm("hts,hsu->htu", diag, eye + power)
    t = jnp.zeros_like(n)
    for j in range(C // SUB):
        rhs = eye - _mm("hts,hsu->htu", n, t) if j else eye
        rows_j = (at >= j * SUB) & (at < (j + 1) * SUB)
        t = t + jnp.where(rows_j, _mm("hts,hsu->htu", diag, rhs), 0.0)
    return t


def _chunk(q, k, v, g, beta, st, scale: float):
    """One chunk of ``h`` heads, float32: ``q``, ``k``, ``g`` (h, C, Dk),
    ``v`` (h, C, Dv), ``beta`` (h, C, 1), the starting state transposed
    ``st`` (h, Dv, Dk) -> (outputs (h, C, Dv), the ending state
    transposed)."""
    h, C, _ = k.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (h, C, C), 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (h, C, C), 2)
    at = jax.lax.broadcasted_iota(jnp.int32, (h, C, 1), 1)
    gamma = _mm("hts,hsd->htd", jnp.where(rows >= cols, 1.0, 0.0), g)
    kb = k * beta
    n = p = jnp.zeros((h, C, C), jnp.float32)
    for j in range(C // SUB):
        lo, hi = j * SUB, (j + 1) * SUB
        ref = (jnp.sum(jnp.where(at == lo - 1, gamma, 0.0), axis=1,
                       keepdims=True) if j else 0.0)
        here, upto = (at >= lo) & (at < hi), at < hi
        down = jnp.exp(jnp.where(here, gamma - ref, 0.0))          # <= 1
        up = jnp.where(upto, k * jnp.exp(jnp.where(upto, ref - gamma,
                                                   0.0)), 0.0)
        n = n + _mm("htd,hsd->hts", jnp.where(here, kb * down, 0.0), up)
        p = p + _mm("htd,hsd->hts", jnp.where(here, q * down, 0.0), up)
    n = jnp.where(rows > cols, n, 0.0)
    p = jnp.where(rows >= cols, p, 0.0)
    t = _unit_lower_inverse(n, rows, cols, at)
    decay = jnp.exp(gamma)                                          # <= 1
    w = _mm("hts,hsd->htd", t, kb * decay)
    u = _mm("hts,hsv->htv", t, v * beta) - _mm("htd,hvd->htv", w, st)
    o = scale * (_mm("htd,hvd->htv", q * decay, st)
                 + _mm("hts,hsv->htv", p, u))
    last = jnp.sum(jnp.where(at == C - 1, gamma, 0.0), axis=1,
                   keepdims=True)                               # (h, 1, Dk)
    st = st * jnp.exp(last) + _mm("htv,htd->hvd", u,
                                  k * jnp.exp(last - gamma))
    return o, st


# --------------------------------------------------------------------- #
# the plain chunked twin
# --------------------------------------------------------------------- #

def _flat(x, dtype):
    """(B, H, L, .) -> (B H, Lp, .) in ``dtype``, L padded to whole chunks
    (a padded position has k = 0 and g = 0: it leaves the state alone)."""
    B, H, L = x.shape[:3]
    x = x.reshape((B * H, L) + x.shape[3:]).astype(dtype)
    return jnp.pad(x, ((0, 0), (0, -L % CHUNK)) + ((0, 0),) * (x.ndim - 2))


def delta_attention_plain(q, k, v, g, beta, scale: Optional[float] = None):
    """The recurrence above in plain JAX, a chunk at a time (``lax.scan``
    over :func:`_chunk`); differentiated by JAX."""
    B, H, L, Dk = q.shape
    Dv = v.shape[-1]
    scale = Dk ** -0.5 if scale is None else float(scale)
    xs = [_flat(x, jnp.float32).reshape((B * H, -1, CHUNK) + x.shape[3:])
          .swapaxes(0, 1) for x in (q, k, v, g, beta[..., None])]

    def step(st, chunk):
        o, st = _chunk(*chunk, st, scale)
        return st, o

    _, o = jax.lax.scan(step, jnp.zeros((B * H, Dv, Dk), jnp.float32), xs)
    o = o.swapaxes(0, 1).reshape(B, H, -1, Dv)[:, :, :L]
    return o.astype(q.dtype)


# --------------------------------------------------------------------- #
# the kernels
# --------------------------------------------------------------------- #

def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, start_ref, st_s,
                *, scale: float):
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        st_s[...] = jnp.zeros(st_s.shape, jnp.float32)

    start_ref[...] = st_s[...]
    o, st = _chunk(q_ref[...].astype(jnp.float32),
                   k_ref[...].astype(jnp.float32),
                   v_ref[...].astype(jnp.float32), g_ref[...], b_ref[...],
                   st_s[...], scale)
    o_ref[...] = o.astype(o_ref.dtype)
    st_s[...] = st


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, start_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dst_s, *,
                scale: float):
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        dst_s[...] = jnp.zeros(dst_s.shape, jnp.float32)

    _, pull = jax.vjp(
        lambda *a: _chunk(*a, scale),
        q_ref[...].astype(jnp.float32), k_ref[...].astype(jnp.float32),
        v_ref[...].astype(jnp.float32), g_ref[...], b_ref[...],
        start_ref[...])
    dq, dk, dv, dg, db, dst = pull((do_ref[...].astype(jnp.float32),
                                    dst_s[...]))
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)
    dg_ref[...] = dg
    db_ref[...] = db
    dst_s[...] = dst


def _heads_a_step(bh: int) -> int:
    return max(d for d in range(1, HEADS + 1) if bh % d == 0)


def _specs(x_shapes, hb: int, reverse: bool, n: int):
    def index(i, c):
        return (i, n - 1 - c if reverse else c, 0)

    return [pl.BlockSpec((hb, CHUNK, s[-1]), index) for s in x_shapes]


# jitted so that a model's layers of one shape share one traced and
# lowered kernel (as the selective scan's are)
@functools.partial(jax.jit, static_argnums=(5, 6))
def _forward(q, k, v, g, beta, scale: float, interpret: bool):
    bh, lp, dk = k.shape
    dv = v.shape[-1]
    n, hb = lp // CHUNK, _heads_a_step(bh)
    ins = _specs([x.shape for x in (q, k, v, g, beta)], hb, False, n)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        out_shape=[jax.ShapeDtypeStruct((bh, lp, dv), q.dtype),
                   jax.ShapeDtypeStruct((bh, n * dv, dk), jnp.float32)],
        grid=(bh // hb, n),
        in_specs=ins,
        out_specs=[ins[2],
                   pl.BlockSpec((hb, dv, dk), lambda i, c: (i, c, 0))],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            **_PARAMS),
        interpret=interpret,
        name="kda_fwd",
    )(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnums=(7, 8))
def _backward(q, k, v, g, beta, starts, do, scale: float, interpret: bool):
    bh, lp, dk = k.shape
    dv = v.shape[-1]
    n, hb = lp // CHUNK, _heads_a_step(bh)
    ins = _specs([x.shape for x in (q, k, v, g, beta)], hb, True, n)
    start = pl.BlockSpec((hb, dv, dk), lambda i, c: (i, n - 1 - c, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q, k, v, g, beta)],
        grid=(bh // hb, n),
        in_specs=ins + [start, ins[2]],
        out_specs=ins,
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            **_PARAMS),
        interpret=interpret,
        name="kda_bwd",
    )(q, k, v, g, beta, starts, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernels(q, k, v, g, beta, scale: float, interpret: bool):
    return _forward(q, k, v, g, beta, scale, interpret)[0]


def _vjp_fwd(q, k, v, g, beta, scale, interpret):
    o, starts = _forward(q, k, v, g, beta, scale, interpret)
    return o, (q, k, v, g, beta, starts)


def _vjp_bwd(scale, interpret, res, do):
    return _backward(*res, do, scale, interpret)


_kernels.defvjp(_vjp_fwd, _vjp_bwd)


def delta_attention_kernels(q, k, v, g, beta, scale: Optional[float] = None,
                            interpret: Optional[bool] = None):
    """The kernel path: ``q``, ``k`` (B, H, L, Dk) and ``v`` (B, H, L, Dv)
    in the compute type, ``g`` (B, H, L, Dk) and ``beta`` (B, H, L)
    float32 -> (B, H, L, Dv) in ``q``'s type; in Pallas's interpreter off
    the TPU unless ``interpret`` says otherwise."""
    B, H, L, Dk = q.shape
    Dv = v.shape[-1]
    scale = Dk ** -0.5 if scale is None else float(scale)
    o = _kernels(_flat(q, q.dtype), _flat(k, q.dtype), _flat(v, q.dtype),
                 _flat(g, jnp.float32), _flat(beta[..., None], jnp.float32),
                 scale, jax.default_backend() != "tpu" if interpret is None
                 else bool(interpret))
    return o.reshape(B, H, -1, Dv)[:, :, :L]
