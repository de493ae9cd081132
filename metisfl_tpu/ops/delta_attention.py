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
rows at a time, from that block's own rows, against the cumulative decay
at the block's start, so one factor is at most 1 and the other at most
``exp(SUB * 5)`` under KDA's gate bound of -5 (``exp(80)``, inside
float32). ``T`` is solved by block rows: each diagonal block's inverse as
a product of ``(I + N^(2^i))``, then the block rows below it in order.

The chunk splits where the state enters. ``gamma``, ``N``, ``P`` and
``T`` need no state (:func:`_free`); ``W = T (beta K exp(gamma))`` and
``T (beta V)`` need none either, but are formed where they are used, so
that only ``T`` and ``P`` cross between kernels (:func:`_state` holds
them and the products with ``S0``):

- ``kda_fwd`` is two kernels. The first takes every chunk of every head
  at once, a grid whose steps are all independent, ``HEADS`` heads by
  up to ``CHUNKS`` chunks of each a step, as many as keep the step's
  blocks within half the scoped VMEM (:func:`_chunks_a_head`), and writes
  ``[T | P]``, (C, 2C) float32 a chunk. The second walks a head group's
  chunks in order, the state (Dv, Dk) resident in VMEM, forms ``W``,
  ``U``, ``O`` and the next state from ``T`` and ``P``, and writes each
  chunk's starting state.
- ``kda_bwd`` is two kernels too, and JAX derives every cotangent: the
  first walks the chunks in reverse and pulls the output's and the
  state's cotangents back through ``jax.vjp`` of the state's part alone,
  carrying the state's cotangent to the chunk before and writing the
  cotangents of ``T`` and ``P`` beside the direct parts of dq, dk, dv,
  dg and dbeta; the second, chunk-parallel, pulls those of ``T`` and
  ``P`` back through ``jax.vjp`` of the state-free part and adds them.

So a walk step runs the state's 7 of a chunk's 24 products
(:func:`kda_walk_census`). All arithmetic is float32 at
``HIGHEST`` precision whatever the caller computes in; ``HEADS`` heads
share a walk step, so that their chains of small products interleave. A
caller takes :func:`delta_attention_kernels` on a TPU (elsewhere they run
in Pallas's interpreter) and the twin, :func:`delta_attention_plain`,
which runs both parts a chunk at a time, where the kernels are not asked
for.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.extend.core as jex
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
SUB = 16                # rows of a block of N and P; a divisor of CHUNK
HEADS = 8               # heads a grid step
CHUNKS = 4              # chunks of a head a chunk-parallel grid step, at most
VMEM = 64 * 1024 * 1024
_HI = jax.lax.Precision.HIGHEST


def _mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


def _grid(h: int, C: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (h, C, C), 1),
            jax.lax.broadcasted_iota(jnp.int32, (h, C, C), 2))


def _row(x, i: int):
    """Row ``i`` of ``x`` (h, C, D) as (h, 1, D)."""
    at = jax.lax.broadcasted_iota(jnp.int32, x.shape[:2] + (1,), 1)
    return jnp.sum(jnp.where(at == i, x, 0.0), axis=1, keepdims=True)


def _cumulative(g):
    """``gamma``: the sum of ``g`` (h, C, Dk) over a chunk's rows up to
    each row, inclusive."""
    rows, cols = _grid(*g.shape[:2])
    return _mm("hts,hsd->htd", jnp.where(rows >= cols, 1.0, 0.0), g)


def _unit_lower_inverse(n):
    """``(I + n)^-1`` of a strictly lower ``n`` (h, C, C), by block rows."""
    h, C, _ = n.shape
    rows, cols = _grid(h, C)
    eye = jnp.where(rows == cols, 1.0, 0.0)
    block = jnp.where(rows // SUB == cols // SUB, n, 0.0)
    # (I + B)^-1 = (I - B)(I + B^2)(I + B^4)(I + B^8) where B^16 = 0
    diag, power = eye - block, block
    for _ in range(int(np.log2(SUB)) - 1):
        power = _mm("hts,hsu->htu", power, power)
        diag = _mm("hts,hsu->htu", diag, eye + power)
    zeros = lambda m: [jnp.zeros((h, m, C), jnp.float32)] if m else []  # noqa
    t = []
    for lo in range(0, C, SUB):
        hi = lo + SUB
        rhs = eye[:, lo:hi]
        if lo:
            rhs = rhs - _mm("hts,hsu->htu", n[:, lo:hi],
                            jnp.concatenate(t + zeros(C - lo), 1))
        # a block row of ``diag`` holds its diagonal block alone
        t.append(_mm("hts,hsu->htu", diag[:, lo:hi],
                     jnp.concatenate(zeros(lo) + [rhs] + zeros(C - hi), 1)))
    return jnp.concatenate(t, 1)


def _free(q, k, gamma, beta):
    """The state-free part of ``h`` chunks, float32: ``q``, ``k``,
    ``gamma`` (h, C, Dk), ``beta`` (h, C, 1) -> ``T``, ``P`` (h, C, C).
    A block of ``SUB`` rows of ``N`` and ``P`` is made from its own rows,
    against the cumulative decay at the block's start."""
    h, C, _ = k.shape
    at = jax.lax.broadcasted_iota(jnp.int32, (h, C, 1), 1)
    kb = k * beta
    n, p = [], []
    for lo in range(0, C, SUB):
        hi = lo + SUB
        ref = _row(gamma, lo - 1) if lo else 0.0
        down = jnp.exp(gamma[:, lo:hi] - ref)                       # <= 1
        upto = at < hi
        up = jnp.where(upto, k * jnp.exp(jnp.where(upto, ref - gamma,
                                                   0.0)), 0.0)
        # the block's rows of N and of P in one product
        both = _mm("htd,hsd->hts", jnp.concatenate(
            [kb[:, lo:hi] * down, q[:, lo:hi] * down], 1), up)
        n.append(both[:, :SUB])
        p.append(both[:, SUB:])
    rows, cols = _grid(h, C)
    n = jnp.where(rows > cols, jnp.concatenate(n, 1), 0.0)
    p = jnp.where(rows >= cols, jnp.concatenate(p, 1), 0.0)
    return _unit_lower_inverse(n), p


def _state(q, k, v, gamma, beta, t, p, st, scale: float):
    """The part of ``h`` chunks that touches the state, float32: ``T``
    and ``P`` (h, C, C) from :func:`_free`, ``v`` (h, C, Dv), the starting
    state transposed ``st`` (h, Dv, Dk) -> (outputs (h, C, Dv), the ending
    state transposed). ``W`` and ``T (beta V)`` wait for no state."""
    decay = jnp.exp(gamma)                                          # <= 1
    w = _mm("hts,hsd->htd", t, k * beta * decay)
    u = _mm("hts,hsv->htv", t, v * beta) - _mm("htd,hvd->htv", w, st)
    o = scale * (_mm("htd,hvd->htv", q * decay, st)
                 + _mm("hts,hsv->htv", p, u))
    last = _row(gamma, k.shape[1] - 1)                          # (h, 1, Dk)
    st = st * jnp.exp(last) + _mm("htv,htd->hvd", u,
                                  k * jnp.exp(last - gamma))
    return o, st


def _chunk(q, k, v, g, beta, st, scale: float):
    """One chunk of ``h`` heads, float32: ``q``, ``k``, ``g`` (h, C, Dk),
    ``v`` (h, C, Dv), ``beta`` (h, C, 1), the starting state transposed
    ``st`` (h, Dv, Dk) -> (outputs (h, C, Dv), the ending state
    transposed)."""
    gamma = _cumulative(g)
    return _state(q, k, v, gamma, beta, *_free(q, k, gamma, beta), st,
                  scale)


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

def _f32(ref, at=slice(None)):
    return ref[:, at].astype(jnp.float32)


def _free_kernel(q_ref, k_ref, g_ref, b_ref, tp_ref):
    for c in range(q_ref.shape[1] // CHUNK):
        at = pl.ds(c * CHUNK, CHUNK)
        t, p = _free(_f32(q_ref, at), _f32(k_ref, at),
                     _cumulative(g_ref[:, at]), b_ref[:, at])
        tp_ref[:, at] = jnp.concatenate([t, p], -1)


def _walk_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, tp_ref, o_ref,
                 start_ref, st_s, *, scale: float):
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        st_s[...] = jnp.zeros(st_s.shape, jnp.float32)

    start_ref[...] = st_s[...]
    o, st = _state(_f32(q_ref), _f32(k_ref), _f32(v_ref),
                   _cumulative(g_ref[...]), b_ref[...],
                   tp_ref[:, :, :CHUNK], tp_ref[:, :, CHUNK:], st_s[...],
                   scale)
    o_ref[...] = o.astype(o_ref.dtype)
    st_s[...] = st


def _walk_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, tp_ref, start_ref,
                     do_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dtp_ref,
                     dst_s, *, scale: float):
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        dst_s[...] = jnp.zeros(dst_s.shape, jnp.float32)

    _, pull = jax.vjp(
        lambda q, k, v, g, *a: _state(q, k, v, _cumulative(g), *a, scale),
        _f32(q_ref), _f32(k_ref), _f32(v_ref), g_ref[...], b_ref[...],
        tp_ref[:, :, :CHUNK], tp_ref[:, :, CHUNK:], start_ref[...])
    dq, dk, dv, dg, db, dt, dp, dst = pull((_f32(do_ref), dst_s[...]))
    dq_ref[...] = dq
    dk_ref[...] = dk
    dv_ref[...] = dv.astype(dv_ref.dtype)
    dg_ref[...] = dg
    db_ref[...] = db
    dtp_ref[...] = jnp.concatenate([dt, dp], -1)
    dst_s[...] = dst


def _free_bwd_kernel(q_ref, k_ref, g_ref, b_ref, dtp_ref, dq_ref, dk_ref,
                     dg_ref, db_ref, dq_out, dk_out, dg_out, db_out):
    for c in range(q_ref.shape[1] // CHUNK):
        at = pl.ds(c * CHUNK, CHUNK)
        _, pull = jax.vjp(
            lambda q, k, g, b: _free(q, k, _cumulative(g), b),
            _f32(q_ref, at), _f32(k_ref, at), g_ref[:, at], b_ref[:, at])
        dtp = dtp_ref[:, at]
        dq, dk, dg, db = pull((dtp[..., :CHUNK], dtp[..., CHUNK:]))
        dq_out[:, at] = (dq_ref[:, at] + dq).astype(dq_out.dtype)
        dk_out[:, at] = (dk_ref[:, at] + dk).astype(dk_out.dtype)
        dg_out[:, at] = dg_ref[:, at] + dg
        db_out[:, at] = db_ref[:, at] + db


def _heads_a_step(bh: int) -> int:
    return max(d for d in range(1, HEADS + 1) if bh % d == 0)


def _row_bytes(arrays) -> int:
    """Bytes a row of these (bh, rows, width) arrays takes in VMEM, each
    width padded to whole lanes of 128."""
    return sum(-(-x.shape[2] // 128) * 128 * jnp.dtype(x.dtype).itemsize
               for x in arrays)


def _chunks_a_head(n: int, hb: int, row_bytes: int) -> int:
    """Chunks of each of ``hb`` heads a step of a chunk-parallel kernel
    takes: the most, up to ``CHUNKS``, that divide the ``n`` chunks while
    the step's blocks, ``row_bytes`` a row and double-buffered, fill at
    most half the scoped VMEM (the other half holds the chunks' own
    work)."""
    fit = VMEM // 2 // (2 * hb * CHUNK * row_bytes)
    return max(d for d in range(1, min(max(fit, 1), CHUNKS) + 1)
               if n % d == 0)


def _products(fn, *shapes) -> int:
    """The matrix products ``fn`` runs at ``shapes``, nested calls
    included: a count of the traced program, nothing run."""
    def count(jaxpr) -> int:
        found = 0
        for eqn in jaxpr.eqns:
            found += eqn.primitive.name == "dot_general"
            for param in eqn.params.values():
                for sub in (param if isinstance(param, (tuple, list))
                            else (param,)):
                    if isinstance(sub, jex.ClosedJaxpr):
                        found += count(sub.jaxpr)
                    elif isinstance(sub, jex.Jaxpr):
                        found += count(sub)
        return found

    return count(jax.make_jaxpr(fn)(*shapes).jaxpr)


def kda_walk_census(bh: int, L: int, dk: int = 128, dv: int = 128,
                    dtype=jnp.bfloat16) -> dict:
    """What the kernels do for ``bh`` heads of ``L`` positions, ``q``,
    ``k`` and ``v`` in ``dtype``, from the numbers their grids are built
    from: ``walk_steps``, the grid steps of a walk (forward and backward
    alike), one after another; the products a walk step runs for each
    chunk when it ran the whole chunk (``products_before``, the twin's
    :func:`_chunk`) and now that it runs the state's part alone
    (``products_after``); the chunks a step of the forward's and the
    backward's chunk-parallel kernels take (``chunks_a_step_fwd``,
    ``chunks_a_step_bwd``); and the bytes one call writes for the next
    kernel of its pass to read: ``T`` and ``P`` forward (``bytes_fwd``),
    their cotangents and the float32 direct parts of dq, dk, dg and dbeta
    backward (``bytes_bwd``)."""
    n = -(-L // CHUNK)
    hb = _heads_a_step(bh)
    f32 = lambda *s: jax.ShapeDtypeStruct((hb,) + s, jnp.float32)  # noqa
    x, xv, col = f32(CHUNK, dk), f32(CHUNK, dv), f32(CHUNK, 1)
    mat, st = f32(CHUNK, CHUNK), f32(dv, dk)
    rows = bh * n * CHUNK
    # the chunk-parallel kernels' blocks, as _forward and _backward pass them
    qk = jax.ShapeDtypeStruct((bh, n * CHUNK, dk), dtype)
    g, tp = _like(qk, width=dk), _like(qk, width=2 * CHUNK)
    beta = _like(qk, width=1)
    fwd = [qk, qk, g, beta, tp]
    bwd = [qk, qk, g, beta, tp, g, g, g, beta, qk, qk, g, beta]
    return {
        "walk_steps": bh // hb * n,
        "products_before": _products(
            functools.partial(_chunk, scale=1.0), x, x, xv, x, col, st),
        "products_after": _products(
            lambda q, k, v, g, *a: _state(q, k, v, _cumulative(g), *a, 1.0),
            x, x, xv, x, col, mat, mat, st),
        "chunks_a_step_fwd": hb * _chunks_a_head(n, hb, _row_bytes(fwd)),
        "chunks_a_step_bwd": hb * _chunks_a_head(n, hb, _row_bytes(bwd)),
        "bytes_fwd": rows * 2 * CHUNK * 4,
        "bytes_bwd": rows * (2 * CHUNK + 3 * dk + 1) * 4,
    }


def _params(semantics, interpret: bool):
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=VMEM)


def _walk(kernel, ins, outs, scratch, reverse: bool, hb: int, scale: float,
          interpret: bool, name: str, aliases=None):
    """A pass over a head group's chunks in order (or in reverse), a
    float32 carry in VMEM; every array (bh, n * rows, width) in blocks of
    a chunk's rows."""
    bh, lp = ins[0].shape[:2]
    n = lp // CHUNK

    def spec(x):
        return pl.BlockSpec((hb, x.shape[1] // n, x.shape[2]), lambda i, c: (
            i, n - 1 - c if reverse else c, 0))

    return pl.pallas_call(
        functools.partial(kernel, scale=scale),
        out_shape=outs,
        grid=(bh // hb, n),
        in_specs=[spec(x) for x in ins],
        out_specs=[spec(x) for x in outs],
        scratch_shapes=[pltpu.VMEM(scratch, jnp.float32)],
        input_output_aliases=aliases or {},
        compiler_params=_params(("parallel", "arbitrary"), interpret),
        interpret=interpret,
        name=name,
    )(*ins)


def _parallel(kernel, ins, outs, hb: int, interpret: bool, name: str,
              aliases=None):
    """Every chunk of every head at once: ``hb`` heads by
    :func:`_chunks_a_head` chunks a step, no carry."""
    bh, lp = ins[0].shape[:2]
    n = lp // CHUNK
    cc = _chunks_a_head(n, hb, _row_bytes(list(ins) + list(outs)))

    def spec(x):
        return pl.BlockSpec((hb, cc * CHUNK, x.shape[2]),
                            lambda i, c: (i, c, 0))

    return pl.pallas_call(
        kernel,
        out_shape=outs,
        grid=(bh // hb, n // cc),
        in_specs=[spec(x) for x in ins],
        out_specs=[spec(x) for x in outs],
        input_output_aliases=aliases or {},
        compiler_params=_params(("parallel", "parallel"), interpret),
        interpret=interpret,
        name=name,
    )(*ins)


def _like(x, dtype=jnp.float32, width=None):
    return jax.ShapeDtypeStruct(x.shape[:2] + (width or x.shape[2],), dtype)


# jitted so that a model's layers of one shape share one traced and
# lowered kernel (as the selective scan's are)
@functools.partial(jax.jit, static_argnums=(5, 6))
def _forward(q, k, v, g, beta, scale: float, interpret: bool):
    bh, lp, dk = k.shape
    dv = v.shape[-1]
    hb = _heads_a_step(bh)
    [tp] = _parallel(_free_kernel, (q, k, g, beta),
                     [_like(q, width=2 * CHUNK)], hb, interpret, "kda_fwd")
    starts = jax.ShapeDtypeStruct((bh, lp // CHUNK * dv, dk), jnp.float32)
    o, starts = _walk(_walk_kernel, (q, k, v, g, beta, tp),
                      [_like(v, q.dtype), starts], (hb, dv, dk), False, hb,
                      scale, interpret, "kda_fwd")
    return o, starts, tp


@functools.partial(jax.jit, static_argnums=(8, 9))
def _backward(q, k, v, g, beta, starts, tp, do, scale: float,
              interpret: bool):
    hb = _heads_a_step(k.shape[0])
    dq, dk, dv, dg, db, dtp = _walk(
        _walk_bwd_kernel, (q, k, v, g, beta, tp, starts, do),
        [_like(q), _like(k), _like(v, v.dtype), _like(g), _like(beta),
         _like(tp)], (hb, v.shape[-1], k.shape[-1]), True, hb, scale,
        interpret, "kda_bwd", aliases={5: 5})
    dq, dk, dg, db = _parallel(
        _free_bwd_kernel, (q, k, g, beta, dtp, dq, dk, dg, db),
        [_like(q, q.dtype), _like(k, k.dtype), _like(g), _like(beta)],
        hb, interpret, "kda_bwd", aliases={7: 2, 8: 3})
    return dq, dk, dv, dg, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernels(q, k, v, g, beta, scale: float, interpret: bool):
    return _forward(q, k, v, g, beta, scale, interpret)[0]


def _vjp_fwd(q, k, v, g, beta, scale, interpret):
    o, starts, tp = _forward(q, k, v, g, beta, scale, interpret)
    return o, (q, k, v, g, beta, starts, tp)


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
