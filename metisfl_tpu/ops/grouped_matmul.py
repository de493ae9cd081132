"""Grouped matrix products for a routed-expert layer that holds a share of
the experts: tokens sorted by expert, one product a group, no token
dropped and no ``(tokens, experts, capacity)`` tensor anywhere.

A layer that holds experts ``first .. first + count`` of a router's ``E``
gets, a token, the ``K`` experts the router chose. :func:`plan_dispatch`
turns those choices into a :class:`Dispatch`: the assignments that fell on
held experts, sorted by expert, laid out in rows so that every group starts
on a tile of ``TILE`` rows (the rows between a group's end and the next
tile stay zero). A row count is static, and the one that can never drop
a token is ``T * min(K, count)`` and a tile a group of padding (every
token could choose held experts only): 34,304 rows in ``kimi-k2.7-code``
where an even routing fills about 1,800, with gathers, elementwise passes
and buffers paid by the row (11.5 ms a layer's forward where the three
products take 2.0; my chip run, PR 32). So :func:`routed_experts` walks the
sorted rows in chunks of what an even routing needs (2,560 rows there), as
many chunks as the live tiles ask for: one, as a rule, and thirteen if
every token chose held experts only. The routing decides the trip count,
no token is dropped whatever it is, and nothing is ever as large as the
worst case. Inside a chunk what moves is how many tiles are live, and the
kernels' grid is that number.

- :func:`to_rows` gathers the tokens' vectors into the rows;
  :func:`to_tokens` sums, a token, the rows of its held assignments. Each
  is the other's transpose and is differentiated as such (no scatter-add
  in either direction). ``to_tokens`` is a gather of ``T * K`` rows where
  the rows are many, and a product with the rows' one-hot token matrix
  where they are few (a chunk's: on the MXU, where the gather of 32,768
  rows took 4.6 ms; my chip run, PR 32).
- :func:`grouped_matmul` multiplies each tile of rows by its group's
  matrix: the Pallas kernels ``moe_gmm_fwd`` / ``moe_gmm_bwd`` (the names
  the device trace shows) on a TPU, ``jax.lax.ragged_dot`` elsewhere, as
  :func:`metisfl_tpu.ops.selective_scan.selective_scan` keeps its plain
  path; ``interpret=True`` runs the kernels in Pallas's interpreter (the
  CPU tests). It is differentiated to the rows alone: the matrices are the
  frozen experts, and their cotangent is none.

The kernels read a tile of rows whole (``(TILE, k)``: the contraction is
not cut, so there is no accumulator) and stream the group's matrix past it
in column blocks; with a tile a group, which is what an even routing gives
a chip's share, each matrix is read once and the product is bound by that
read (12 x 44 M bfloat16 values a layer in ``kimi-k2.7-code``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128
_BLOCK_BYTES = 4 * 1024 * 1024      # of a group's matrix in VMEM, a buffer
_VMEM_LIMIT = 64 * 1024 * 1024


class Dispatch(NamedTuple):
    """Where the held assignments sit. ``T`` tokens, ``K`` choices a
    token, ``M`` rows, ``G`` held experts."""
    token: jax.Array        # (M,) int32: the row's token; 0 where not valid
    valid: jax.Array        # (M,) bool: the row carries an assignment
    choice: jax.Array       # (M,) int32: its index into the (T * K) choices
    row: jax.Array          # (T, K) int32: the assignment's row; M: not held
    held: jax.Array         # (T, K) bool
    sizes: jax.Array        # (G,) int32: assignments a held expert
    tile_group: jax.Array   # (M / TILE,) int32: the tile's group
    tiles: jax.Array        # () int32: live tiles (the kernels' grid)


def rows_for(tokens: int, top_k: int, count: int) -> int:
    """The static row count: every token on held experts, and a tile of
    padding a group."""
    return (-(-tokens * min(top_k, count) // TILE) + count) * TILE


class Routing(NamedTuple):
    """The sort, which layouts of any row count share."""
    order: jax.Array        # (T * K,) int32: choices sorted by held expert
    dest: jax.Array         # (T * K,) int32: sorted choice -> row; -1: not held
    held: jax.Array         # (T, K) bool
    sizes: jax.Array        # (G,) int32
    padded_ends: jax.Array  # (G,) int32: group ends, tile-aligned
    tiles: jax.Array        # () int32: live tiles


def sort_choices(chosen, first: int, count: int) -> Routing:
    """``chosen`` (T, K): the router's expert ids, a token. Assignments to
    experts outside ``first .. first + count`` are discarded before the
    sort."""
    T, K = chosen.shape
    local = chosen.astype(jnp.int32) - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    padded = -(-sizes // TILE) * TILE
    padded_ends = jnp.cumsum(padded)
    key_sorted = key[order]
    group = jnp.minimum(key_sorted, count - 1)
    rank = jnp.arange(T * K, dtype=jnp.int32) - (ends - sizes)[group]
    dest = jnp.where(key_sorted < count,
                     (padded_ends - padded)[group] + rank, -1)
    return Routing(order, dest, held, sizes, padded_ends,
                   (padded_ends[-1] // TILE).astype(jnp.int32))


def layout(routing: Routing, rows: int, start=0) -> Dispatch:
    """Rows ``start .. start + rows`` of the sort (``rows`` static, both
    multiples of ``TILE``; ``start`` may be traced): the assignments whose
    row falls there, the others as if not held."""
    T, K = routing.held.shape
    M, count = rows, routing.sizes.shape[0]
    dest = routing.dest - start
    dest = jnp.where((routing.dest < 0) | (dest < 0) | (dest >= M), M, dest)
    choice = jnp.full((M,), T * K, jnp.int32).at[dest].set(
        routing.order, mode="drop")
    valid = choice < T * K
    row = jnp.full((T * K,), M, jnp.int32).at[routing.order].set(dest)
    tile_start = start + jnp.arange(M // TILE, dtype=jnp.int32) * TILE
    tile_group = jnp.minimum(
        jnp.searchsorted(routing.padded_ends, tile_start, side="right"),
        count - 1).astype(jnp.int32)
    row = row.reshape(T, K)
    return Dispatch(token=jnp.where(valid, choice // K, 0), valid=valid,
                    choice=jnp.where(valid, choice, 0), row=row,
                    held=row < M, sizes=routing.sizes, tile_group=tile_group,
                    tiles=jnp.clip(routing.tiles - start // TILE, 0,
                                   M // TILE).astype(jnp.int32))


def plan_dispatch(chosen, first: int, count: int) -> Dispatch:
    """The layout that holds whatever the router chose."""
    return layout(sort_choices(chosen, first, count),
                  rows_for(*chosen.shape, count))


def _rows(x, plan: Dispatch):
    return jnp.where(plan.valid[:, None], x[plan.token], 0).astype(x.dtype)


def _tokens(rows, plan: Dispatch):
    M, T = rows.shape[0], plan.row.shape[0]
    if M <= 2 * T:
        # few rows: their one-hot token matrix times the rows, on the MXU;
        # rows past the live tiles may hold anything, and 0 x nan is nan
        onehot = ((plan.token[None, :] == jnp.arange(T)[:, None])
                  & plan.valid[None, :]).astype(rows.dtype)
        rows = jnp.where(plan.valid[:, None], rows, 0)
        return jnp.dot(onehot, rows, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32
                       ).astype(rows.dtype)
    picked = rows[jnp.minimum(plan.row, M - 1)]            # (T, K, d)
    return jnp.sum(jnp.where(plan.held[..., None], picked, 0),
                   axis=1, dtype=jnp.float32).astype(rows.dtype)


@jax.custom_vjp
def to_rows(x, plan: Dispatch):
    """(T, d) tokens -> (M, d) rows in the plan's order, zero where a row
    carries no assignment."""
    return _rows(x, plan)


@jax.custom_vjp
def to_tokens(rows, plan: Dispatch):
    """(M, d) rows -> (T, d): a token's sum over its held assignments'
    rows. Rows beyond the live tiles may hold anything: they are not
    read."""
    return _tokens(rows, plan)


to_rows.defvjp(lambda x, plan: (_rows(x, plan), plan),
               lambda plan, g: (_tokens(g, plan), None))
to_tokens.defvjp(lambda rows, plan: (_tokens(rows, plan), plan),
                 lambda plan, g: (_rows(g, plan), None))


def row_weights(weights, plan: Dispatch):
    """(T, K) weights of the choices -> (M,) by row, zero where not
    valid."""
    return jnp.where(plan.valid, weights.reshape(-1)[plan.choice], 0)


# --------------------------------------------------------------------- #
# the products
# --------------------------------------------------------------------- #

def _kernel(tile_group_ref, lhs_ref, rhs_ref, out_ref, *, transpose: bool):
    del tile_group_ref
    dims = (((1,), (1,)), ((), ())) if transpose else (((1,), (0,)), ((), ()))
    out_ref[...] = jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], dims,
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _block(n: int, k: int, itemsize: int) -> int:
    """Columns of a group's matrix a block: the most under ``_BLOCK_BYTES``
    that divide ``n`` in lanes of 128 (all of ``n`` where it is small)."""
    if n % 128:
        return n
    best = 128
    for cand in range(128, n + 1, 128):
        if n % cand == 0 and cand * k * itemsize <= _BLOCK_BYTES:
            best = cand
    return best


@functools.partial(jax.jit, static_argnums=(4, 5))
def _gmm(lhs, rhs, tile_group, tiles, transpose: bool, interpret: bool):
    M, k = lhs.shape
    n = rhs.shape[1] if transpose else rhs.shape[2]
    tn = _block(n, k, rhs.dtype.itemsize)
    if transpose:
        rhs_spec = pl.BlockSpec((None, tn, k), lambda i, j, g: (g[i], j, 0))
    else:
        rhs_spec = pl.BlockSpec((None, k, tn), lambda i, j, g: (g[i], 0, j))
    return pl.pallas_call(
        functools.partial(_kernel, transpose=transpose),
        out_shape=jax.ShapeDtypeStruct((M, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles, n // tn),
            in_specs=[pl.BlockSpec((TILE, k), lambda i, j, g: (i, 0)),
                      rhs_spec],
            out_specs=pl.BlockSpec((TILE, tn), lambda i, j, g: (i, j))),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm_bwd" if transpose else "moe_gmm_fwd",
    )(tile_group, lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gmm_kernels(lhs, rhs, plan: Dispatch, interpret: bool = False):
    """The Pallas pair. Rows beyond the plan's live tiles are not written:
    whoever reads the result reads it through the plan (``to_tokens``, or
    a ``where`` on ``plan.valid``)."""
    return _gmm(lhs, rhs, plan.tile_group, plan.tiles, False, interpret)


gmm_kernels.defvjp(
    lambda lhs, rhs, plan, interpret: (
        _gmm(lhs, rhs, plan.tile_group, plan.tiles, False, interpret),
        (rhs, plan)),
    lambda interpret, res, g: (
        _gmm(g, res[0], res[1].tile_group, res[1].tiles, True, interpret),
        None, None))


@jax.jit
def gmm_ragged(lhs, rhs, plan: Dispatch):
    """The plain path: ``jax.lax.ragged_dot`` over the groups' live tiles
    (a layout may start or end inside a group)."""
    G = rhs.shape[0]
    live = jnp.arange(plan.tile_group.shape[0]) < plan.tiles
    rows = TILE * jnp.bincount(jnp.where(live, plan.tile_group, G),
                               length=G + 1)[:G].astype(jnp.int32)
    return jax.lax.ragged_dot(lhs, jax.lax.stop_gradient(rhs), rows,
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)


def grouped_matmul(lhs, rhs, plan: Dispatch, *,
                   interpret: Optional[bool] = None):
    """(M, k) rows by (G, k, n) matrices -> (M, n): each tile of rows by
    its group's matrix. The kernels on a TPU (and, in the interpreter,
    where ``interpret`` is true), ``ragged_dot`` elsewhere."""
    if interpret or (interpret is None and jax.default_backend() == "tpu"):
        return gmm_kernels(lhs, rhs, plan, bool(interpret))
    return gmm_ragged(lhs, rhs, plan)


def chunk_rows(tokens: int, top_k: int, count: int, num_experts: int) -> int:
    """The rows an even routing needs: its assignments on held experts and
    a tile of padding a group; never more than holds anything."""
    even = (-(-tokens * top_k * count // (num_experts * TILE)) + count) * TILE
    return min(even, rows_for(tokens, top_k, count))


def _chunk(h, gates, w_gate, w_up, w_down, routing: Routing, index,
           rows: int, interpret, limit: float = 0.0):
    """Chunk ``index`` of the sorted rows: its part of the tokens' sums."""
    with jax.named_scope("moe_dispatch"):
        plan = layout(routing, rows, index * rows)
        x = to_rows(h, plan)
    with jax.named_scope("moe_experts"):
        mm = functools.partial(grouped_matmul, plan=plan, interpret=interpret)
        if limit:
            gate = jnp.minimum(mm(x, w_gate), limit)
            y = mm(jax.nn.silu(gate) * jnp.clip(mm(x, w_up), -limit, limit),
                   w_down)
        else:
            y = mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)
    with jax.named_scope("moe_combine"):
        # rows past the live tiles hold whatever was there: chosen out,
        # not multiplied out
        y = jnp.where(plan.valid[:, None],
                      y * row_weights(gates, plan)[:, None], 0)
        return to_tokens(y.astype(h.dtype), plan)


def _chunks(routing: Routing, rows: int):
    return -(-routing.tiles * TILE // rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _routed(h, gates, w_gate, w_up, w_down, routing: Routing, rows: int,
            interpret, limit: float = 0.0):
    def body(i, acc):
        return acc + _chunk(h, gates, w_gate, w_up, w_down, routing, i,
                            rows, interpret, limit).astype(jnp.float32)

    return jax.lax.fori_loop(0, _chunks(routing, rows), body,
                             jnp.zeros(h.shape, jnp.float32)).astype(h.dtype)


def _routed_fwd(h, gates, w_gate, w_up, w_down, routing, rows, interpret,
                limit=0.0):
    return (_routed(h, gates, w_gate, w_up, w_down, routing, rows, interpret,
                    limit),
            (h, gates, w_gate, w_up, w_down, routing))


def _routed_bwd(rows, interpret, limit, res, g):
    h, gates, w_gate, w_up, w_down, routing = res

    def body(i, carry):
        # a chunk's products are computed again here: nothing of a chunk
        # outlives it
        _, vjp = jax.vjp(lambda h, gates: _chunk(
            h, gates, w_gate, w_up, w_down, routing, i, rows, interpret,
            limit), h, gates)
        dh, dgates = vjp(g)
        return carry[0] + dh.astype(jnp.float32), carry[1] + dgates

    dh, dgates = jax.lax.fori_loop(
        0, _chunks(routing, rows), body,
        (jnp.zeros(h.shape, jnp.float32), jnp.zeros_like(gates)))
    return dh.astype(h.dtype), dgates, None, None, None, None


_routed.defvjp(_routed_fwd, _routed_bwd)


def routed_experts(h, chosen, gates, w_gate, w_up, w_down, *, first: int,
                   num_experts: int, interpret: Optional[bool] = None,
                   limit: float = 0.0):
    """``sum_{e chosen and held} g_e W_down_e(silu(W_gate_e h) * W_up_e
    h)`` (``limit`` > 0 clamps the two branches as ``SwiGLU.limit`` does)
    for tokens ``h`` (T, d) with choices ``chosen`` and gates
    ``gates`` (T, K) over ``num_experts`` experts, of which ``w_*`` (G, .,
    .) are experts ``first .. first + G``. Returns the (T, d) sum and the
    held assignments a group, ``sizes`` (G,). Differentiated to ``h`` and
    ``gates``; the experts are frozen.

    One sort, walked in chunks of the rows an even routing needs (see the
    module's docstring): as many as the routing asks for."""
    T, K = chosen.shape
    G = w_gate.shape[0]
    with jax.named_scope("moe_dispatch"):
        routing = sort_choices(chosen, first, G)
    out = _routed(h, gates, w_gate, w_up, w_down, routing,
                  chunk_rows(T, K, G, num_experts), interpret, float(limit))
    return out, routing.sizes
