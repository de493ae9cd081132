"""Selective scan (Mamba-1's recurrence): Pallas TPU kernels and a plain-JAX
chunked path.

For one sequence, with ``x`` and ``dt`` of shape (L, D), ``A`` (D, N),
``B`` and ``C`` (L, N) and the state ``S`` (D, N) starting at zero::

    S_t = exp(dt_t (x) A) . S_{t-1} + (dt_t . x_t) (x) B_t
    y_t = S_t . C_t

Nothing of shape (L, D, N) is ever materialized for the whole sequence
(1.34 GB of float32 a layer at L 4096, D 5120, N 16). Time is cut into
chunks; the state crosses chunk boundaries and everything wider lives for
one chunk.

- :func:`selective_scan` routes on what it can observe, as
  :func:`metisfl_tpu.ops.attention` does: the kernels on a TPU at
  ``L >= SCAN_MIN_SEQ`` (one chunk), the plain-JAX path on any other
  backend and below the threshold. ``interpret=True`` runs the kernels in
  Pallas's interpreter, which is how the CPU tests reach them.
- The kernels are a ``custom_vjp`` pair, ``ssm_scan_fwd`` and
  ``ssm_scan_bwd`` (the names the device trace shows). The state tile is
  (N, lanes): N on sublanes, D on lanes, resident in VMEM scratch across
  the sequential chunk axis of the grid and in registers across a chunk's
  time loop. The forward writes the state at each chunk's start; the
  backward walks the chunks in reverse, recomputes a chunk's states from
  its start, and runs the adjoint recurrence back through it.
- ``B_t`` and ``C_t`` enter the kernels broadcast along 128 lanes,
  (L, N, 128): a step needs them down the sublanes of the state tile, and a
  relayout from lanes to sublanes at every step would cost more than the
  33 MB a layer the broadcast copy does. The backward returns ``dB`` and
  ``dC`` the same way, as per-lane partial sums that XLA folds.

All arithmetic is float32 whatever the caller computes in: the products
``exp(dt A)`` compound over thousands of positions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from metisfl_tpu.ops.flash_attention import _pad_len

_LANES = 128
_ROWS = 8               # time steps handled per aligned (8, lanes) tile
DEFAULT_CHUNK = 64
# kernels-vs-plain crossover (sequence length), swept on a v5e at D 5120,
# N 16 (PERF.md section 5): at one chunk of 64 positions the two forwards
# tie (0.23 against 0.24 ms) and the kernels' backward already wins
# (0.60 against 1.35 ms); from there on the kernels stay near 0.3 ms to
# 1,024 positions while the plain path doubles with the length. Below one
# chunk the kernels would pad up to it.
SCAN_MIN_SEQ = 64
_VMEM_LIMIT = 48 * 1024 * 1024


# --------------------------------------------------------------------- #
# plain JAX: lax.scan over chunks, an associative scan inside each
# --------------------------------------------------------------------- #

def _combine(left, right):
    a_l, b_l = left
    a_r, b_r = right
    return a_l * a_r, a_r * b_l + b_r


def _chunk(state, xs, a_t):
    """One chunk from ``state`` (B, N, D): (state after it, y (B, T, D)).
    The (B, T, N, D) products live for this chunk alone; under autodiff
    the chunk is rematerialized, so the backward holds one chunk too."""
    x, dt, b, c = xs                           # (B,T,D) (B,T,D) (B,T,N) x2
    decay = jnp.exp(dt[:, :, None, :] * a_t)   # (B, T, N, D)
    drive = (dt * x)[:, :, None, :] * b[..., None]
    cum, s = jax.lax.associative_scan(_combine, (decay, drive), axis=1)
    s = s + cum * state[:, None]
    return s[:, -1], jnp.einsum("btnd,btn->btd", s, c)


def scan_chunked(x, dt, a, b, c, *, chunk: int = DEFAULT_CHUNK, state=None):
    """The plain-JAX path. ``x``, ``dt`` (B, L, D); ``a`` (D, N); ``b``,
    ``c`` (B, L, N); ``state`` (B, D, N) or None for zero. Returns
    ``(y (B, L, D) float32, state after position L-1 (B, D, N))``.
    Differentiable by JAX itself (each chunk under ``jax.checkpoint``)."""
    f32 = jnp.float32
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    bsz, length, d = x.shape
    n = a.shape[1]
    t = min(int(chunk), length)
    padded = _pad_len(length, t)
    if padded != length:
        # dt = 0 leaves the state as it is and x = 0 drives nothing
        pad = ((0, 0), (0, padded - length), (0, 0))
        x, dt, b, c = (jnp.pad(v, pad) for v in (x, dt, b, c))
    a_t = a.astype(f32).T                      # (N, D)
    s0 = (jnp.zeros((bsz, n, d), f32) if state is None
          else jnp.swapaxes(state.astype(f32), 1, 2))

    def chunks(v):                             # (B, L, .) -> (L/T, B, T, .)
        return jnp.swapaxes(v.reshape(bsz, padded // t, t, -1), 0, 1)

    body = jax.checkpoint(lambda s, xs: _chunk(s, xs, a_t))
    s_last, ys = jax.lax.scan(body, s0, tuple(map(chunks, (x, dt, b, c))))
    y = jnp.swapaxes(ys, 0, 1).reshape(bsz, padded, d)[:, :length]
    return y, jnp.swapaxes(s_last, 1, 2)


def scan_step(state, x, dt, a, b, c):
    """One position of the recurrence (cached decode). ``state`` (B, D, N);
    ``x``, ``dt`` (B, D); ``b``, ``c`` (B, N). Returns ``(y (B, D),
    state)``, float32."""
    f32 = jnp.float32
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    state = (jnp.exp(dt[..., None] * a.astype(f32)) * state.astype(f32)
             + (dt * x)[..., None] * b[:, None, :])
    return jnp.einsum("bdn,bn->bd", state, c), state


# --------------------------------------------------------------------- #
# the kernels
# --------------------------------------------------------------------- #

def _tile_lanes(v, width: int):
    """(N, 128) -> (N, width): the same vreg in every lane group."""
    reps = width // _LANES
    return v if reps == 1 else jnp.concatenate([v] * reps, axis=1)


def _fold_lanes(v):
    """(N, width) -> (N, 128): the lane groups added up."""
    out = v[:, :_LANES]
    for k in range(1, v.shape[1] // _LANES):
        out = out + v[:, k * _LANES:(k + 1) * _LANES]
    return out


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, bound_ref, s_ref,
                *, chunk: int, width: int):
    """Blocks: x, dt, y (1, T, Dt); a (N, Dt); b, c (1, T, N, 128); bound
    (1, 1, N, Dt): the state at this chunk's start. ``s_ref`` (N, Dt) is
    the state, kept across the chunk axis (grid axis 2, sequential)."""
    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_ref[...] = jnp.zeros(s_ref.shape, jnp.float32)

    bound_ref[0, 0] = s_ref[...]
    for g in range(s_ref.shape[1] // width):
        lanes = slice(g * width, (g + 1) * width)
        a = a_ref[:, lanes]

        def rows(i, s, lanes=lanes, a=a):
            r0 = pl.multiple_of(i * _ROWS, _ROWS)
            xt = x_ref[0, pl.ds(r0, _ROWS), lanes]
            dtt = dt_ref[0, pl.ds(r0, _ROWS), lanes]
            out = []
            for j in range(_ROWS):
                dt, x = dtt[j:j + 1], xt[j:j + 1]          # (1, W)
                b = _tile_lanes(b_ref[0, r0 + j], width)
                c = _tile_lanes(c_ref[0, r0 + j], width)
                s = jnp.exp(dt * a) * s + (dt * x) * b
                out.append(jnp.sum(s * c, axis=0, keepdims=True))
            y_ref[0, pl.ds(r0, _ROWS), lanes] = jnp.concatenate(out, axis=0)
            return s

        s_ref[:, lanes] = jax.lax.fori_loop(0, chunk // _ROWS, rows,
                                            s_ref[:, lanes])


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, dy_ref, bound_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref, g_ref, st_ref,
                *, chunk: int, width: int):
    """The chunks arrive last first (the index maps reverse grid axis 2).
    Blocks as the forward's, plus dy, dx, ddt (1, T, Dt); da (1, N, Dt),
    resident across the chunk axis; db, dc (1, 1, T, N, 128): per-lane
    partial sums over this tile's D. ``g_ref`` (N, Dt): what later
    positions pass back to the state at this chunk's end; ``st_ref``
    (T, N, W): the chunk's recomputed states, one lane group at a time."""
    @pl.when(pl.program_id(2) == 0)
    def _start():
        g_ref[...] = jnp.zeros(g_ref.shape, jnp.float32)
        da_ref[...] = jnp.zeros(da_ref.shape, jnp.float32)

    for g in range(g_ref.shape[1] // width):
        lanes = slice(g * width, (g + 1) * width)
        a = a_ref[:, lanes]

        def recompute(i, s, lanes=lanes, a=a):
            r0 = pl.multiple_of(i * _ROWS, _ROWS)
            xt = x_ref[0, pl.ds(r0, _ROWS), lanes]
            dtt = dt_ref[0, pl.ds(r0, _ROWS), lanes]
            for j in range(_ROWS):
                st_ref[r0 + j] = s                 # the state BEFORE r0 + j
                dt, x = dtt[j:j + 1], xt[j:j + 1]
                b = _tile_lanes(b_ref[0, r0 + j], width)
                s = jnp.exp(dt * a) * s + (dt * x) * b
            return s

        s_end = jax.lax.fori_loop(0, chunk // _ROWS, recompute,
                                  bound_ref[0, 0][:, lanes])

        def adjoint(i, carry, lanes=lanes, a=a, first=(g == 0)):
            back, s_t, da = carry
            r0 = pl.multiple_of(chunk - _ROWS - i * _ROWS, _ROWS)
            xt = x_ref[0, pl.ds(r0, _ROWS), lanes]
            dtt = dt_ref[0, pl.ds(r0, _ROWS), lanes]
            dyt = dy_ref[0, pl.ds(r0, _ROWS), lanes]
            dxs, ddts = [None] * _ROWS, [None] * _ROWS
            for j in reversed(range(_ROWS)):
                dt, x, dy = dtt[j:j + 1], xt[j:j + 1], dyt[j:j + 1]
                b = _tile_lanes(b_ref[0, r0 + j], width)
                c = _tile_lanes(c_ref[0, r0 + j], width)
                s_prev = st_ref[r0 + j]
                decay = jnp.exp(dt * a)
                grad_s = back + dy * c               # dL/dS_t, all of it
                dc = _fold_lanes(dy * s_t)
                db = _fold_lanes(grad_s * (dt * x))
                if first:
                    dc_ref[0, 0, r0 + j] = dc
                    db_ref[0, 0, r0 + j] = db
                else:
                    dc_ref[0, 0, r0 + j] += dc
                    db_ref[0, 0, r0 + j] += db
                d_drive = jnp.sum(grad_s * b, axis=0, keepdims=True)
                back = grad_s * decay                # on to S_{t-1}
                d_exp = back * s_prev                # dL/d(dt (x) A)
                ddts[j] = (jnp.sum(d_exp * a, axis=0, keepdims=True)
                           + d_drive * x)
                dxs[j] = d_drive * dt
                da = da + d_exp * dt
                s_t = s_prev
            dx_ref[0, pl.ds(r0, _ROWS), lanes] = jnp.concatenate(dxs, axis=0)
            ddt_ref[0, pl.ds(r0, _ROWS), lanes] = jnp.concatenate(ddts,
                                                                  axis=0)
            return back, s_t, da

        back, _, da = jax.lax.fori_loop(
            0, chunk // _ROWS, adjoint,
            (g_ref[:, lanes], s_end, jnp.zeros((a.shape[0], width),
                                               jnp.float32)))
        g_ref[:, lanes] = back
        da_ref[0, :, lanes] += da


def _d_tile(d: int, cap: int = 2560) -> int:
    """The largest multiple of 128 that divides ``d`` (itself one) and is
    at most ``cap``: what of D one grid step holds."""
    for cand in range(min(cap, d), 0, -_LANES):
        if d % cand == 0:
            return cand
    return _LANES


def _group_width(d_tile: int, want: int) -> int:
    for cand in range(min(want, d_tile), 0, -_LANES):
        if d_tile % cand == 0:
            return cand
    return _LANES


_PARAMS = dict(dimension_semantics=("parallel", "parallel", "arbitrary"),
               vmem_limit_bytes=_VMEM_LIMIT)


def _prepare(x, dt, a, b, c, chunk: int):
    """Float32, padded to whole chunks and whole lane tiles, A transposed,
    B and C broadcast along 128 lanes."""
    f32 = jnp.float32
    bsz, length, d = x.shape
    n = a.shape[1]
    lp, dp = _pad_len(length, chunk), _pad_len(d, _LANES)
    pad = ((0, 0), (0, lp - length), (0, dp - d))
    x = jnp.pad(x.astype(f32), pad)
    dt = jnp.pad(dt.astype(f32), pad)
    a_t = jnp.pad(a.astype(f32).T, ((0, 0), (0, dp - d)))
    wide = lambda v: jnp.broadcast_to(                       # noqa: E731
        jnp.pad(v.astype(f32), ((0, 0), (0, lp - length), (0, 0)))[..., None],
        (bsz, lp, n, _LANES))
    return x, dt, a_t, wide(b), wide(c)


# jitted so that a model's layers of one shape share one traced and lowered
# kernel: tracing the kernels' unrolled bodies anew for each of 13 layers,
# forward, rematerialized forward and backward, was most of the hybrid's
# time to its first step (PERF.md section 6, PR 27)
@functools.partial(jax.jit, static_argnums=(5, 6))
def _forward(x, dt, a, b, c, chunk: int, interpret: bool):
    bsz, length, d = x.shape
    n = a.shape[1]
    xp, dtp, a_t, bw, cw = _prepare(x, dt, a, b, c, chunk)
    lp, dp = xp.shape[1], xp.shape[2]
    tile, n_l = _d_tile(dp), lp // chunk
    row = pl.BlockSpec((1, chunk, tile), lambda i, j, k: (i, k, j))
    wide = pl.BlockSpec((1, chunk, n, _LANES), lambda i, j, k: (i, k, 0, 0))
    y, bound = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk,
                          width=_group_width(tile, 512)),
        out_shape=[jax.ShapeDtypeStruct((bsz, lp, dp), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, n_l, n, dp), jnp.float32)],
        grid=(bsz, dp // tile, n_l),
        in_specs=[row, row,
                  pl.BlockSpec((n, tile), lambda i, j, k: (0, j)),
                  wide, wide],
        out_specs=[row,
                   pl.BlockSpec((1, 1, n, tile),
                                lambda i, j, k: (i, k, 0, j))],
        scratch_shapes=[pltpu.VMEM((n, tile), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            **_PARAMS),
        interpret=interpret,
        name="ssm_scan_fwd",
    )(xp, dtp, a_t, bw, cw)
    return y[:, :length, :d], bound


@functools.partial(jax.jit, static_argnums=(7, 8))
def _backward(x, dt, a, b, c, bound, dy, chunk: int, interpret: bool):
    bsz, length, d = x.shape
    n = a.shape[1]
    xp, dtp, a_t, bw, cw = _prepare(x, dt, a, b, c, chunk)
    lp, dp = xp.shape[1], xp.shape[2]
    dyp = jnp.pad(dy.astype(jnp.float32),
                  ((0, 0), (0, lp - length), (0, dp - d)))
    tile, n_l = _d_tile(dp), lp // chunk
    n_d = dp // tile
    last = n_l - 1
    row = pl.BlockSpec((1, chunk, tile), lambda i, j, k: (i, last - k, j))
    wide = pl.BlockSpec((1, chunk, n, _LANES),
                        lambda i, j, k: (i, last - k, 0, 0))
    part = pl.BlockSpec((1, 1, chunk, n, _LANES),
                        lambda i, j, k: (i, j, last - k, 0, 0))
    width = _group_width(tile, 256)
    dx, ddt, da, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, width=width),
        out_shape=[jax.ShapeDtypeStruct((bsz, lp, dp), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, lp, dp), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, n, dp), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, n_d, lp, n, _LANES),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((bsz, n_d, lp, n, _LANES),
                                        jnp.float32)],
        grid=(bsz, n_d, n_l),
        in_specs=[row, row,
                  pl.BlockSpec((n, tile), lambda i, j, k: (0, j)),
                  wide, wide, row,
                  pl.BlockSpec((1, 1, n, tile),
                               lambda i, j, k: (i, last - k, 0, j))],
        out_specs=[row, row,
                   pl.BlockSpec((1, n, tile), lambda i, j, k: (i, 0, j)),
                   part, part],
        scratch_shapes=[pltpu.VMEM((n, tile), jnp.float32),
                        pltpu.VMEM((chunk, n, width), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            **_PARAMS),
        interpret=interpret,
        name="ssm_scan_bwd",
    )(xp, dtp, a_t, bw, cw, dyp, bound)
    fold = lambda v: v.sum(axis=(1, 4))[:, :length]          # noqa: E731
    return (dx[:, :length, :d], ddt[:, :length, :d],
            da.sum(axis=0)[:, :d].T, fold(db), fold(dc))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def scan_kernels(x, dt, a, b, c, chunk: int = DEFAULT_CHUNK,
                 interpret: bool = False):
    """The kernel path: y (B, L, D) float32 from a zero state."""
    return _forward(x, dt, a, b, c, chunk, interpret)[0]


def _vjp_fwd(x, dt, a, b, c, chunk, interpret):
    y, bound = _forward(x, dt, a, b, c, chunk, interpret)
    return y, (x, dt, a, b, c, bound)


def _vjp_bwd(chunk, interpret, res, dy):
    x, dt, a, b, c, bound = res
    grads = _backward(x, dt, a, b, c, bound, dy, chunk, interpret)
    return tuple(g.astype(v.dtype) for g, v in zip(grads, (x, dt, a, b, c)))


scan_kernels.defvjp(_vjp_fwd, _vjp_bwd)


def selective_scan(x, dt, a, b, c, *, chunk: int = DEFAULT_CHUNK,
                   interpret: bool = False):
    """y (B, L, D) float32 of the recurrence above from a zero state:
    the kernels on a TPU at ``L >= SCAN_MIN_SEQ`` or wherever
    ``interpret`` asks for them, the plain-JAX chunked path otherwise."""
    if chunk % _ROWS:
        raise ValueError(f"chunk ({chunk}) must be a multiple of {_ROWS}")
    if interpret or (jax.default_backend() == "tpu"
                     and x.shape[1] >= SCAN_MIN_SEQ):
        return scan_kernels(x, dt, a, b, c, chunk, interpret)
    return scan_chunked(x, dt, a, b, c, chunk=chunk)[0]
