"""Learned sparse attention on the TPU: the lightning indexer's choice of
keys (DeepSeek-V3.2's DSA) and flash attention over the chosen keys.

The indexer scores every causal (query, key) pair::

    I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])        s <= t

and query ``t`` attends to the ``top_k`` keys of largest ``I[t, s]`` (every
causal key while ``t < top_k``). Two kernels and their plain-JAX twins:

- ``dsa_select`` (:func:`select`): a grid step holds one block of query
  rows and scores one block of keys into VMEM (the (L, L) scores never
  reach HBM). After the row block's last causal key block it finds each
  row's ``top_k``-th largest score exactly, by a 32-step bisection over
  the bits of an order-keeping int32 image of the float32 scores (no
  sort), and writes the choice as a bit-packed mask ``(B, L / 32, L)``
  int32: 33.5 MB a layer at 16,384 tokens where a float32 (L, L) is 1 GB.
- ``flash_fwd_sel``, ``flash_bwd_dq_sel``, ``flash_bwd_dkv_sel``
  (:func:`selected_attention`): the flash kernels' walk over causal
  (query block, key block) pairs with the mask read tile by tile. The
  step tables are made on the device from the mask: a pair that holds no
  chosen key keeps its grid step but computes nothing and fetches nothing
  (its block indices repeat the last live step's).

Two edges. The choice's edge (``BLOCK``, 512) is ``dsa_select``'s row
block and the mask's packing. The walk's edge (:func:`walk_edge`) is the
kernels' tile: 1024 where it divides the sequence and scores and values
are at most 256 wide, as for the causal flash kernels (a step's cost
outside its two products is paid a quarter as often per query row), else
the choice's edge. A tile of 1024 query rows holds two of the choice's
blocks one after the other; :func:`selected_walk_census` counts the walk.

Packing: within a block of ``edge`` query rows, word row ``g`` (of
``edge / 32``) holds row ``b * (edge / 32) + g`` at bit ``b``, so that a
kernel unpacks a block by shifting a broadcast along a leading axis.

The kernels' step machinery and constants are the flash kernels'
(:mod:`metisfl_tpu.ops.flash_attention`), imported and left as they are.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from metisfl_tpu.ops.flash_attention import (_F_FIRST, _F_LAST, _LANES,
                                             _NEG, _STAT_LANES,
                                             _WIDE_EDGE_MAX_WIDTH,
                                             _block_classes, _gqa_shapes,
                                             _kv_head_index, _score_scale,
                                             _step_tables)

BLOCK = 512             # the choice's edge: ``dsa_select``'s row block and
                        # the mask's packing (``sa_config``'s chunks)
WALK = 1024             # the selected kernels' tile edge where it fits
WORD = 32
_MIN = int(np.iinfo(np.int32).min)
_F_LIVE = 8             # a step whose pair holds a chosen key
# the selection keeps a row block's scores (edge x L int32) in VMEM: 32 MB
# at 16,384 tokens
_SELECT_VMEM = 100 * 2 ** 20
# a walk's tile of 1024 keeps the float32 scores, probabilities, unpacked
# mask and, backward, dp and ds, 4 MB each: at scores and values 256 wide
# the forward asks 17.7 MB, over a v5e's default scoped 16 MB
_WALK_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=32 * 2 ** 20)


def block_edge(L: int, edge: Optional[int] = None) -> int:
    """The block edge at sequence length ``L``: ``edge`` (512 by default),
    or ``L`` below it."""
    edge = min(edge or BLOCK, L)
    if L % edge or edge % WORD:
        raise ValueError(f"sequence length {L} must be a multiple of the "
                         f"block edge {edge}, and the edge of {WORD}")
    return edge


def walk_edge(L: int, D: int = 128, Dv: int = 128,
              edge: Optional[int] = None) -> int:
    """The selected kernels' tile edge at sequence length ``L``, scores
    ``D`` and values ``Dv`` wide, over a choice packed at ``edge``:
    ``WALK`` where ``WALK`` divides ``L``, the choice's edge divides
    ``WALK`` and scores and values are at most 256 wide (a 1024 x 1024
    float32 tile is 4 MB of VMEM and the kernels keep a few), else the
    choice's edge."""
    edge = block_edge(L, edge)
    if (L % WALK == 0 and WALK % edge == 0
            and max(D, Dv) <= _WIDE_EDGE_MAX_WIDTH):
        return WALK
    return edge


def selected_walk_census(L: int, D: int = 128, Dv: int = 128) -> dict:
    """The walk one head's selected kernels take at sequence length ``L``:
    its tile ``edge`` and its causal ``steps``, counted from the very
    classes the step tables are built from (1024 and 136 at 16,384 tokens
    and heads of 128, where a walk at 512 takes 528)."""
    edge = walk_edge(L, D, Dv)
    return {"edge": edge, "steps": len(_causal_steps(L, edge)[0])}


def _interpret(interpret: Optional[bool]) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret


# --------------------------------------------------------------------- #
# the packed mask
# --------------------------------------------------------------------- #

def pack(mask, edge: int):
    """(B, L, S) bool -> (B, L / 32, S) int32 in the blocks' layout."""
    B, L, S = mask.shape
    m = mask.astype(jnp.int32).reshape(B, L // edge, WORD, edge // WORD, S)
    shifts = jnp.arange(WORD, dtype=jnp.int32)[:, None, None]
    return jnp.sum(m << shifts, axis=2, dtype=jnp.int32).reshape(
        B, L // WORD, S)


def unpack(words, edge: int):
    """(B, L / 32, S) int32 -> (B, L, S) bool."""
    B, n, S = words.shape
    w = words.reshape(B, n * WORD // edge, 1, edge // WORD, S)
    shifts = jnp.arange(WORD, dtype=jnp.int32)[:, None, None]
    return ((w >> shifts) & 1).reshape(B, n * WORD, S) != 0


def _unpack_tile(words, edge: int):
    """A kernel's tile of a choice packed at ``edge``: (walk / 32, n)
    int32 -> (walk, n) bool, its word rows ``edge / 32`` at a time (the
    choice's blocks, one after the other)."""
    rows, n = edge // WORD, words.shape[1]

    def bits(block):                    # (rows, n) -> (edge, n) int32
        spread = jnp.broadcast_to(block[None], (WORD, rows, n))
        shifts = jax.lax.broadcasted_iota(jnp.int32, (WORD, rows, n), 0)
        return ((spread >> shifts) & 1).reshape(edge, n)

    blocks = [bits(words[r:r + rows])
              for r in range(0, words.shape[0], rows)]
    return (blocks[0] if len(blocks) == 1
            else jnp.concatenate(blocks, axis=0)) != 0


def live_tiles(words, edge: int):
    """(B, L / edge, L / edge) bool: which (query block, key block) pairs
    hold a chosen key."""
    B, n, L = words.shape
    tiles = words.reshape(B, L // edge, edge // WORD, L // edge, edge)
    return jnp.any(tiles != 0, axis=(2, 4))


def counts(words, edge: int) -> dict:
    """The counters a selection sows: chosen pairs and live tiles."""
    chosen = jnp.sum(jax.lax.population_count(words).astype(jnp.float32))
    return {"dsa_selected_count": chosen,
            "dsa_live_tiles_count": jnp.sum(
                live_tiles(words, edge).astype(jnp.float32))}


# --------------------------------------------------------------------- #
# the choice: scores, an exact per-row threshold, the mask
# --------------------------------------------------------------------- #

def order_keys(s):
    """float32 -> int32 in the same order (negative floats' magnitude
    bits flipped); ``_MIN`` is below every float's image."""
    b = jax.lax.bitcast_convert_type(s, jnp.int32)
    return jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)


def _bit_steps():
    """The bisection's 32 steps, sign bit first (``_MIN + _MIN`` wraps to
    0: the first candidate is the image of +0.0)."""
    return [np.int32(_MIN)] + [np.int32(1 << b) for b in range(30, -1, -1)]


def threshold(keys, top_k: int):
    """Per row of ``keys`` (..., S) int32: the largest ``theta`` with at
    least ``top_k`` keys >= ``theta`` (``_MIN`` where fewer exist)."""
    theta = jnp.full(keys.shape[:-1] + (1,), _MIN, jnp.int32)
    for step in _bit_steps():
        cand = theta + step
        enough = jnp.sum((keys >= cand).astype(jnp.float32), -1,
                         keepdims=True) >= top_k
        theta = jnp.where(enough, cand, theta)
    return theta


def index_scores(qi, ki, w):
    """``I`` (B, L, L) float32 of the indexer's queries ``qi`` (B, L, Hi,
    Di), keys ``ki`` (B, L, Di) and weights ``w`` (B, L, Hi), heads summed
    in order as the kernel sums them; non-causal pairs are not masked."""
    acc = None
    for h in range(qi.shape[2]):
        s = jnp.einsum("btd,bsd->bts", qi[:, :, h], ki,
                       preferred_element_type=jnp.float32)
        part = jnp.maximum(s, 0.0) * w[:, :, h, None].astype(jnp.float32)
        acc = part if acc is None else acc + part
    return acc


def select_plain(qi, ki, w, top_k: int, edge: Optional[int] = None):
    """The choice in plain JAX, (B, L, L) scores at once: small L only."""
    B, L = qi.shape[:2]
    edge = block_edge(L, edge)
    causal = jnp.tril(jnp.ones((L, L), bool))
    keys = jnp.where(causal, order_keys(index_scores(qi, ki, w)), _MIN)
    floor = jnp.maximum(threshold(keys, top_k), _MIN + 1)
    return pack(keys >= floor, edge)


def select_pooled(qi, ki, w, top_k: int, pool: int,
                  edge: Optional[int] = None):
    """The choice over keys pooled by ``pool`` in XLA: the indexer scores
    ``kbar_j``, the mean of ``ki`` over positions ``pool j .. pool j +
    pool - 1``, for the groups complete at ``t``; query ``t`` takes the
    keys of the ``top_k / pool`` best groups (ties all taken; every
    complete group while there are no more) and its own group's keys
    ``<= t`` while that group is incomplete. -> (B, L / 32, L) int32, the
    blocks' packing."""
    B, L, _, D = qi.shape
    if L % pool or top_k % pool:
        raise ValueError(f"the sequence ({L}) and top_k ({top_k}) must be "
                         f"multiples of the pool ({pool})")
    edge = block_edge(L, edge)
    groups = L // pool
    kbar = jnp.mean(ki.astype(jnp.float32).reshape(B, groups, pool, D),
                    axis=2).astype(ki.dtype)
    t = jnp.arange(L)[:, None]
    complete = pool * jnp.arange(groups)[None, :] + pool - 1 <= t
    keys = jnp.where(complete, order_keys(index_scores(qi, kbar, w)), _MIN)
    floor = jnp.maximum(threshold(keys, top_k // pool), _MIN + 1)
    s = jnp.arange(L)[None, :]
    tail = (s // pool == t // pool) & (s <= t) & (t % pool != pool - 1)
    return pack(jnp.repeat(keys >= floor, pool, axis=-1) | tail[None], edge)


def _select_kernel(q_ref, k_ref, w_ref, out_ref, keys_s, *, top_k: int,
                   edge: int, heads: int, nk: int):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j <= i)
    def _score():
        k = k_ref[0]                                    # (edge, Di)
        w = w_ref[0]                                    # (edge, Hi)
        acc = jnp.zeros((edge, edge), jnp.float32)
        for h in range(heads):
            s = jax.lax.dot_general(q_ref[0, h], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(s, 0.0) * w[:, h:h + 1]
        row = jax.lax.broadcasted_iota(jnp.int32, (edge, edge), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (edge, edge), 1)
        keys = jnp.where((j < i) | (col <= row), order_keys(acc), _MIN)
        keys_s[:, pl.ds(pl.multiple_of(j * edge, edge), edge)] = keys

    @pl.when(j == nk - 1)
    def _choose():
        def tile(t):
            return keys_s[:, pl.ds(pl.multiple_of(t * edge, edge), edge)]

        def count(cand):
            def body(t, c):
                return c + jnp.sum((tile(t) >= cand).astype(jnp.float32),
                                   axis=1, keepdims=True)
            return jax.lax.fori_loop(0, i + 1, body,
                                     jnp.zeros((edge, 1), jnp.float32))

        theta = jnp.full((edge, 1), _MIN, jnp.int32)
        for step in _bit_steps():
            cand = theta + step
            theta = jnp.where(count(cand) >= top_k, cand, theta)
        floor = jnp.maximum(theta, _MIN + 1)
        shifts = jax.lax.broadcasted_iota(
            jnp.int32, (WORD, edge // WORD, edge), 0)

        def write(t, carry):
            chosen = ((tile(t) >= floor) & (t <= i)).astype(jnp.int32)
            words = jnp.sum(chosen.reshape(WORD, edge // WORD, edge)
                            << shifts, axis=0, dtype=jnp.int32)
            out_ref[0, :, pl.ds(pl.multiple_of(t * edge, edge), edge)] = words
            return carry

        jax.lax.fori_loop(0, nk, write, 0)


def select(qi, ki, w, top_k: int, interpret: Optional[bool] = None,
           edge: Optional[int] = None):
    """The choice as the kernel ``dsa_select`` makes it: ``qi`` (B, L, Hi,
    Di) and ``ki`` (B, L, Di) in the compute type, ``w`` (B, L, Hi)
    float32 -> (B, L / 32, L) int32."""
    B, L, Hi, Di = qi.shape
    edge = block_edge(L, edge)
    n = L // edge
    qh = jnp.swapaxes(qi, 1, 2)                         # (B, Hi, L, Di)
    interpret = _interpret(interpret)
    return pl.pallas_call(
        functools.partial(_select_kernel, top_k=int(top_k), edge=edge,
                          heads=Hi, nk=n),
        out_shape=jax.ShapeDtypeStruct((B, L // WORD, L), jnp.int32),
        grid=(B, n, n),
        in_specs=[
            pl.BlockSpec((1, Hi, edge, Di), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, edge, Di),
                         lambda b, i, j: (b, jnp.minimum(i, j), 0)),
            pl.BlockSpec((1, edge, Hi), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, edge // WORD, L),
                               lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((edge, L), jnp.int32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_SELECT_VMEM),
        interpret=interpret,
        name="dsa_select",
    )(qh, ki, w.astype(jnp.float32))


# --------------------------------------------------------------------- #
# attention over the chosen keys
# --------------------------------------------------------------------- #

def attend_plain(q, k, v, words, scale: Optional[float] = None,
                 edge: Optional[int] = None):
    """Softmax over the chosen keys in plain JAX: ``q`` (B, H, L, D), ``k``
    and ``v`` (B, Hkv, L, .), ``words`` the packed choice."""
    B, H, Hkv, L, D, Dv = _gqa_shapes(q, k, v)
    mask = unpack(words, block_edge(L, edge))[:, None]
    k, v = (jnp.repeat(x, H // Hkv, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(
        jnp.float32) * _score_scale(q, scale)
    p = jax.nn.softmax(jnp.where(mask, s, _NEG), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _causal_steps(L: int, edge: int, members=None):
    """The flash walk's step tables over the causal pairs of ``edge``."""
    return _step_tables(_block_classes(L, edge, edge, L, True), members)


def _walk_tables(live, L: int, edge: int, members=None):
    """The flash walk over causal pairs (static) with the choice's live
    pairs marked (on the device): (query side, key side, flags), each
    (B * steps,) int32. A dead step's moving side repeats the last live
    step's of its output block, so that it fetches nothing."""
    static = _causal_steps(L, edge, members)
    q_of, k_of, flags = (jnp.asarray(x) for x in static)
    nq = L // edge
    steps = q_of.shape[0]
    first = (static[2] & _F_FIRST) != 0
    seg = jnp.asarray(np.maximum.accumulate(
        np.where(first, np.arange(steps), 0)).astype(np.int32))
    at = jnp.arange(steps, dtype=jnp.int32)
    live = live[:, q_of % nq, k_of]                     # (B, steps)
    last = jax.lax.cummax(jnp.where(live, at, -1), axis=1)
    src = jnp.where(last >= seg, last, at)
    if members is None:                 # the key block moves
        q_tab = jnp.broadcast_to(q_of, live.shape)
        k_tab = k_of[src]
    else:                               # the query block moves
        q_tab = q_of[src]
        k_tab = jnp.broadcast_to(k_of, live.shape)
    f_tab = (flags & (_F_FIRST | _F_LAST)) | jnp.where(live, _F_LIVE, 0)
    return tuple(x.astype(jnp.int32).reshape(-1)
                 for x in (q_tab, k_tab, f_tab)), steps


def _sel_fwd_kernel(q_of, k_of, flags, q_ref, k_ref, v_ref, w_ref, o_ref,
                    lse_ref, m_s, l_s, acc_s, *, scale: float, heads: int,
                    steps: int, edge: int):
    t = (pl.program_id(0) // heads) * steps + pl.program_id(1)
    flag = flags[t]

    @pl.when((flag & _F_FIRST) != 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, _NEG, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    @pl.when((flag & _F_LIVE) != 0)
    def _attend():
        v = v_ref[0]
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _unpack_tile(w_ref[0], edge)
        s = jnp.where(mask, s, _NEG)
        m_prev = m_s[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.where(mask, jnp.exp(s - m_next[:, :1]), 0.0)
        alpha = jnp.exp(m_prev - m_next)
        m_s[...] = m_next
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=1)[:, None]
        acc_s[...] = acc_s[...] * alpha[:, :1] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when((flag & _F_LAST) != 0)
    def _store():
        l_fin = l_s[...]
        l_inv = jnp.where(l_fin == 0.0, 0.0, 1.0 / jnp.maximum(l_fin, 1e-30))
        o_ref[0] = (acc_s[...] * l_inv[:, :1]).astype(o_ref.dtype)
        lse_ref[0] = (m_s[...] + jnp.log(jnp.maximum(l_fin, 1e-30)))[
            :, :_STAT_LANES]


def _probabilities(q_ref, k_ref, w_ref, lse_ref, *, scale: float,
                   edge: int):
    """The backward's probabilities of one tile, zero off the choice."""
    s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    p = jnp.exp(s - lse_ref[0][:, :1])
    return jnp.where(_unpack_tile(w_ref[0], edge), p, 0.0)


def _sel_dq_kernel(q_of, k_of, flags, q_ref, k_ref, v_ref, w_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, dq_s, *, scale: float,
                   heads: int, steps: int, edge: int):
    t = (pl.program_id(0) // heads) * steps + pl.program_id(1)
    flag = flags[t]

    @pl.when((flag & _F_FIRST) != 0)
    def _init():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)

    @pl.when((flag & _F_LIVE) != 0)
    def _accumulate():
        k = k_ref[0]
        p = _probabilities(q_ref, k_ref, w_ref, lse_ref, scale=scale,
                           edge=edge)
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dq_s[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                 preferred_element_type=jnp.float32)

    @pl.when((flag & _F_LAST) != 0)
    def _store():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _sel_dkv_kernel(qm_of, k_of, flags, q_ref, k_ref, v_ref, w_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_s, dv_s, *,
                    scale: float, heads: int, steps: int, edge: int):
    t = (pl.program_id(0) // heads) * steps + pl.program_id(1)
    flag = flags[t]

    @pl.when((flag & _F_FIRST) != 0)
    def _init():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    @pl.when((flag & _F_LIVE) != 0)
    def _accumulate():
        q, do = q_ref[0], do_ref[0]
        p = _probabilities(q_ref, k_ref, w_ref, lse_ref, scale=scale,
                           edge=edge)
        dv_s[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dk_s[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((flag & _F_LAST) != 0)
    def _store():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _sel_forward(q, k, v, words, scale, interpret, edge):
    B, H, Hkv, L, D, Dv = _gqa_shapes(q, k, v)
    edge = block_edge(L, edge)
    walk = walk_edge(L, D, Dv, edge)
    scale = _score_scale(q, scale)
    kv_ix = _kv_head_index(H, Hkv)
    tables, steps = _walk_tables(live_tiles(words, walk), L, walk)
    at = lambda b, t: (b // H) * steps + t                  # noqa: E731
    q_index = lambda b, t, q_of, k_of, f: (b, q_of[at(b, t)], 0)  # noqa
    kv_index = lambda b, t, q_of, k_of, f: (             # noqa: E731
        kv_ix(b), k_of[at(b, t)], 0)
    w_index = lambda b, t, q_of, k_of, f: (             # noqa: E731
        b // H, q_of[at(b, t)], k_of[at(b, t)])
    out, lse = pl.pallas_call(
        functools.partial(_sel_fwd_kernel, scale=scale, heads=H,
                          steps=steps, edge=edge),
        out_shape=[jax.ShapeDtypeStruct((B * H, L, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B * H, L, _STAT_LANES),
                                        jnp.float32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B * H, steps),
            in_specs=[pl.BlockSpec((1, walk, D), q_index),
                      pl.BlockSpec((1, walk, D), kv_index),
                      pl.BlockSpec((1, walk, Dv), kv_index),
                      pl.BlockSpec((1, walk // WORD, walk), w_index)],
            out_specs=[pl.BlockSpec((1, walk, Dv), q_index),
                       pl.BlockSpec((1, walk, _STAT_LANES), q_index)],
            scratch_shapes=[pltpu.VMEM((walk, _LANES), jnp.float32),
                            pltpu.VMEM((walk, _LANES), jnp.float32),
                            pltpu.VMEM((walk, Dv), jnp.float32)]),
        compiler_params=None if interpret else _WALK_PARAMS,
        interpret=interpret, name="flash_fwd_sel",
    )(*tables, q.reshape(B * H, L, D), k.reshape(B * Hkv, L, D),
      v.reshape(B * Hkv, L, Dv), words)
    return out.reshape(B, H, L, Dv), lse[:, :, 0].reshape(B, H, L)


def _sel_backward(q, k, v, words, out, lse, g, scale, interpret, edge):
    B, H, Hkv, L, D, Dv = _gqa_shapes(q, k, v)
    G = H // Hkv
    edge = block_edge(L, edge)
    walk = walk_edge(L, D, Dv, edge)
    nq = L // walk
    scale = _score_scale(q, scale)
    kv_ix = _kv_head_index(H, Hkv)
    flat = lambda x: x.reshape(-1, L, x.shape[-1])          # noqa: E731
    qf, kf, vf, of, gf = map(flat, (q, k, v, out, g))
    delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32), -1)
    stat = lambda x: jnp.broadcast_to(                      # noqa: E731
        x.reshape(B * H, L)[..., None], (B * H, L, _STAT_LANES))
    lse, delta = stat(lse.astype(jnp.float32)), stat(delta)
    live = live_tiles(words, walk)
    operands = (qf, kf, vf, words, gf, lse, delta)
    static = dict(scale=scale, edge=edge)

    tables, steps = _walk_tables(live, L, walk)
    at = lambda b, t: (b // H) * steps + t                  # noqa: E731
    q_index = lambda b, t, q_of, k_of, f: (b, q_of[at(b, t)], 0)  # noqa
    kv_index = lambda b, t, q_of, k_of, f: (             # noqa: E731
        kv_ix(b), k_of[at(b, t)], 0)
    w_index = lambda b, t, q_of, k_of, f: (             # noqa: E731
        b // H, q_of[at(b, t)], k_of[at(b, t)])
    dq = pl.pallas_call(
        functools.partial(_sel_dq_kernel, heads=H, steps=steps, **static),
        out_shape=jax.ShapeDtypeStruct((B * H, L, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B * H, steps),
            in_specs=[pl.BlockSpec((1, walk, D), q_index),
                      pl.BlockSpec((1, walk, D), kv_index),
                      pl.BlockSpec((1, walk, Dv), kv_index),
                      pl.BlockSpec((1, walk // WORD, walk), w_index),
                      pl.BlockSpec((1, walk, Dv), q_index),
                      pl.BlockSpec((1, walk, _STAT_LANES), q_index),
                      pl.BlockSpec((1, walk, _STAT_LANES), q_index)],
            out_specs=pl.BlockSpec((1, walk, D), q_index),
            scratch_shapes=[pltpu.VMEM((walk, D), jnp.float32)]),
        compiler_params=None if interpret else _WALK_PARAMS,
        interpret=interpret, name="flash_bwd_dq_sel",
    )(*tables, *operands)

    # dK/dV: a KV head's key block walks (group member, query block),
    # member-major, as the flash kernels' own dK/dV does
    tables, steps = _walk_tables(live, L, walk, members=G)
    at = lambda b, t: (b // Hkv) * steps + t                # noqa: E731

    def qm_index(b, t, qm_of, k_of, f):
        qm = qm_of[at(b, t)]
        return ((b // Hkv) * H + (b % Hkv) * G + qm // nq, qm % nq, 0)

    k_index = lambda b, t, qm_of, k_of, f: (b, k_of[at(b, t)], 0)  # noqa
    wk_index = lambda b, t, qm_of, k_of, f: (            # noqa: E731
        b // Hkv, qm_of[at(b, t)] % nq, k_of[at(b, t)])
    dk, dv = pl.pallas_call(
        functools.partial(_sel_dkv_kernel, heads=Hkv, steps=steps, **static),
        out_shape=[jax.ShapeDtypeStruct((B * Hkv, L, D), k.dtype),
                   jax.ShapeDtypeStruct((B * Hkv, L, Dv), v.dtype)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B * Hkv, steps),
            in_specs=[pl.BlockSpec((1, walk, D), qm_index),
                      pl.BlockSpec((1, walk, D), k_index),
                      pl.BlockSpec((1, walk, Dv), k_index),
                      pl.BlockSpec((1, walk // WORD, walk), wk_index),
                      pl.BlockSpec((1, walk, Dv), qm_index),
                      pl.BlockSpec((1, walk, _STAT_LANES), qm_index),
                      pl.BlockSpec((1, walk, _STAT_LANES), qm_index)],
            out_specs=[pl.BlockSpec((1, walk, D), k_index),
                       pl.BlockSpec((1, walk, Dv), k_index)],
            scratch_shapes=[pltpu.VMEM((walk, D), jnp.float32),
                            pltpu.VMEM((walk, Dv), jnp.float32)]),
        compiler_params=None if interpret else _WALK_PARAMS,
        interpret=interpret, name="flash_bwd_dkv_sel",
    )(*tables, *operands)
    return (dq.reshape(B, H, L, D), dk.reshape(B, Hkv, L, D),
            dv.reshape(B, Hkv, L, Dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def selected_attention(q, k, v, words, scale: Optional[float] = None,
                       interpret: Optional[bool] = None,
                       edge: Optional[int] = None):
    """Flash attention of ``q`` (B, H, L, D) over the keys ``words`` (the
    packed choice, (B, L / 32, L), at the choice's ``edge``) marks,
    GQA-native as :func:`~metisfl_tpu.ops.flash_attention.flash_attention`
    is, walked in tiles of :func:`walk_edge`; the choice carries no
    gradient."""
    return _sel_forward(q, k, v, words, scale, _interpret(interpret),
                        edge)[0]


def _fwd(q, k, v, words, scale, interpret, edge):
    out, lse = _sel_forward(q, k, v, words, scale, _interpret(interpret),
                            edge)
    return out, (q, k, v, words, out, lse)


def _bwd(scale, interpret, edge, res, g):
    q, k, v, words, out, lse = res
    dq, dk, dv = _sel_backward(q, k, v, words, out, lse, g, scale,
                               _interpret(interpret), edge)
    return dq, dk, dv, np.zeros(words.shape, jax.dtypes.float0)


selected_attention.defvjp(_fwd, _bwd)
