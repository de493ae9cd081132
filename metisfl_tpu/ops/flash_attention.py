"""Pallas flash attention (TPU kernels, interpret-mode on CPU).

Blockwise attention with online softmax: the (L, L) score matrix never
reaches HBM. Forward and backward are Mosaic-native grid-accumulation
kernels — the sequential GRID dimension walks (query block, key block)
pairs, running statistics live in VMEM scratch across grid steps, and K/V
stream through VMEM one block per step, so VMEM stays bounded at any
sequence length. No dynamic loop trip counts anywhere.

The block structure is decided once, at trace time, from shapes
(:func:`_block_classes`): a pair with no unmasked element is DEAD and gets
no grid step at all — the sequential dimension enumerates live pairs only,
through small int32 tables handed to the kernels as scalar prefetch
(:func:`_step_tables`: which query block, which key block, and whether
the step is the first or last of its output block), so causal mode costs
about half the steps, FLOPs and HBM traffic of full attention. A pair
with every element unmasked is WHOLE and runs a body with no mask at all;
only a pair the diagonal or the ragged tail cuts through is MASKED and
builds its mask from iotas. :func:`flash_block_census` counts the three
(10 live of 16 a head at 4,096 by the automatic edge of 1024, causal:
6 whole, 4 masked; 36 of 64 at 512: 28 and 8). The
arithmetic of a live element is the same in both bodies, bit for bit.

The backward is the FlashAttention-2 scheme: dQ accumulates over KV blocks,
dK/dV accumulate over Q blocks, both recomputing probabilities from the
forward's saved logsumexp — training memory is O(L·D) end to end. The
forward accumulator is FA2's unnormalized numerator (one alpha rescale per
step, a single divide at the store).

Where it wins: the kernel's value is O(L·D) memory (the (L, L) score
matrix never materializes), which is what makes long sequences fit at all;
on raw speed XLA's fused dense attention is competitive at moderate L
(dense against flash at 2,048 is not measured on today's code), with the
kernel's causal block skip paying off as L grows past the score-matrix
memory wall. At 4,096, in the learner's step of the cell
``internlm2-1.8b.lora-round``, the kernels run at 52.05 % of their
roofline (``flash_roofline``; the builder's chip run, PERF.md PR 33;
36.56 % before it, PERF_LEDGER.jsonl, PR 32). Use
:func:`attention` to route between the two on sequence length instead of
hand-picking.

Sequence lengths that do not divide the block size are zero-padded up to
the next block boundary and masked inside the kernels (padded rows are
sliced off on the way out), so any L works on both paths.

The reference framework has no custom kernels at all (its hot loop is
byte-blob C++ arithmetic, SURVEY.md §2.1 C3); this is the TPU-native hot
path for the transformer ladder. Best with head_dim a multiple of 128
(lane width); block sizes are multiples of 8 (f32 sublanes).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
_LANES = 128      # statistics SCRATCH: one value replicated across a vreg
_STAT_LANES = 8   # lse/delta in HBM: minimal tile-legal lane replication
# ((blk_q, 8) blocks satisfy Mosaic's tiling because the minor dim equals
# the full array dim; 128-lane replication in HBM would put the VJP's lse
# residual on par with Q itself at long sequence lengths)

# a (query block, key block) pair's class, by the mask `_mask_for` would
# build for it: no element unmasked, every element, or some
_DEAD, _WHOLE, _MASKED = 0, 1, 2
# a grid step's flags (third prefetched table): first / last step of its
# output block, and whether its pair is _MASKED
_F_FIRST, _F_LAST, _F_MASKED = 1, 2, 4


def _mask_for(qi, blk_q, kj, blk_k, kv_len, causal):
    """The mask of one _MASKED block. ``kv_len`` is None where the keys
    have no padded tail (the comparison would be all true)."""
    k_pos = kj * blk_k + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 1)
    mask = None if kv_len is None else k_pos < kv_len   # tail-padding mask
    if causal:
        q_pos = qi * blk_q + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 0)
        mask = q_pos >= k_pos if mask is None else mask & (q_pos >= k_pos)
    return mask


def _for_class(flag, has_whole: bool, has_masked: bool, body):
    """Run ``body(masked)`` for the class ``flag`` names. A class the
    step tables do not hold (known at trace time) gets no code."""
    if has_whole and has_masked:
        pl.when((flag & _F_MASKED) == 0)(lambda: body(False))
        pl.when((flag & _F_MASKED) != 0)(lambda: body(True))
    else:
        body(has_masked)


def _fwd_kernel(q_of, k_of, flags, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_s, l_s, acc_s, *, causal: bool, scale: float, kv_len,
                has_whole: bool, has_masked: bool):
    t = pl.program_id(1)
    flag = flags[t]
    blk_q = q_ref.shape[1]
    blk_k = k_ref.shape[1]

    @pl.when((flag & _F_FIRST) != 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, _NEG, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def _attend(masked: bool):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = (_mask_for(q_of[t], blk_q, k_of[t], blk_k, kv_len, causal)
                if masked else None)
        if mask is not None:
            s = jnp.where(mask, s, _NEG)
        m_prev = m_s[...]                       # (blk_q, LANES), lanes equal
        l_prev = l_s[...]
        m_curr = jnp.max(s, axis=1)[:, None]    # (blk_q, 1)
        m_next = jnp.maximum(m_prev, m_curr)    # (blk_q, LANES)
        p = jnp.exp(s - m_next[:, :1])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_next)        # (blk_q, LANES)
        m_s[...] = m_next
        l_s[...] = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        # acc holds the UNNORMALIZED running numerator (FlashAttention-2):
        # one alpha rescale per step, a single divide at the final store —
        # two fewer vector multiplies per grid step than keeping the
        # running average normalized
        acc_s[...] = acc_s[...] * alpha[:, :1] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    _for_class(flag, has_whole, has_masked, _attend)

    @pl.when((flag & _F_LAST) != 0)
    def _store():
        l_fin = l_s[...]
        # fully-masked rows (tail padding) have l == 0: emit 0, not nan
        l_inv = jnp.where(l_fin == 0.0, 0.0, 1.0 / jnp.maximum(l_fin, 1e-30))
        o_ref[0] = (acc_s[...] * l_inv[:, :1]).astype(o_ref.dtype)
        lse_ref[0] = (m_s[...] + jnp.log(jnp.maximum(l_fin, 1e-30)))[
            :, :_STAT_LANES]


def _dq_kernel(q_of, k_of, flags, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, dq_s, *, causal: bool, scale: float,
               kv_len, has_whole: bool, has_masked: bool):
    t = pl.program_id(1)
    flag = flags[t]
    blk_q = q_ref.shape[1]
    blk_k = k_ref.shape[1]

    @pl.when((flag & _F_FIRST) != 0)
    def _init():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)

    def _accumulate(masked: bool):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]                 # (blk_q, 1)
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)
        mask = (_mask_for(q_of[t], blk_q, k_of[t], blk_k, kv_len, causal)
                if masked else None)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_s[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                 preferred_element_type=jnp.float32)

    _for_class(flag, has_whole, has_masked, _accumulate)

    @pl.when((flag & _F_LAST) != 0)
    def _store():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _dkv_kernel(qm_of, k_of, flags, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_s, dv_s, *, causal: bool,
                scale: float, kv_len, nq: int, has_whole: bool,
                has_masked: bool):
    # the dK/dV of one KV head accumulates over every query head in its
    # group: the steps of a key block walk (group member, query block),
    # MEMBER-MAJOR, and ``qm_of`` holds member * nq + query block. Within
    # one member's segment the head component of the block index is
    # constant (q-block-major would cycle heads every step)
    t = pl.program_id(1)
    flag = flags[t]
    blk_k = k_ref.shape[1]
    blk_q = q_ref.shape[1]

    @pl.when((flag & _F_FIRST) != 0)
    def _init():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    def _accumulate(masked: bool):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)
        mask = (_mask_for(qm_of[t] % nq, blk_q, k_of[t], blk_k, kv_len,
                          causal) if masked else None)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        # dV += P^T @ dO
        dv_s[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        # dK += dS^T @ Q
        dk_s[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _for_class(flag, has_whole, has_masked, _accumulate)

    @pl.when((flag & _F_LAST) != 0)
    def _store():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _score_scale(q, scale: Optional[float]) -> float:
    """The softmax scale: a caller's own (latent attention's carries its
    rotary scaling's factor), else ``1/sqrt`` of the score width."""
    return float(1.0 / np.sqrt(q.shape[-1]) if scale is None else scale)


def _dense_attention(q, k, v, causal: bool, scale: Optional[float] = None):
    """XLA reference implementation (tests oracle + the routed dense path).
    ``v`` may be of another width than ``q`` and ``k``.

    Softmax in fp32 regardless of compute dtype — bf16 exp/normalize loses
    too much precision (same policy as the flash kernel's fp32 online
    statistics and the model zoo's dense branch); probabilities cast back
    so the PV matmul stays on the MXU's native path."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(
        jnp.float32) * _score_scale(q, scale)
    if causal:
        L = q.shape[2]
        mask = jnp.tril(jnp.ones((L, L), bool))
        s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _pad_len(L: int, blk: int) -> int:
    return (L + blk - 1) // blk * blk


_WIDE_EDGE_MAX_WIDTH = 256   # widest head (scores or values) at edge 1024


def _auto_blk(L: int, D: int = 128, Dv: int = 128) -> int:
    """Largest block edge in {1024, 512, 256, 128} that divides the
    8-aligned sequence length. Bigger blocks cut grid steps: a step's
    cost outside the two products (statistics and accumulator read,
    rescaled and written back, the pipeline's bookkeeping) is per step,
    and at 4,096 causal a layer's forward and backward take 2.54 ms at
    1024 against 3.08 at 512 and 6.29 at 256 (16 query heads on 8 KV
    heads of 128; PERF.md, PR 33), though 1024 computes 40 blocks' worth
    of scores for 32 under the diagonal where 512 computes 36. 1024 holds
    while scores and values are at most 256 wide (a 1024 x 1024 float32
    score block is 4 MB of VMEM and the kernels keep a few; the chip's
    compiler refuses 384 in float32 and 512 in bfloat16). An edge that
    does NOT divide L would pad the grid up to the next multiple and burn
    the padding as masked FLOPs (e.g. L=640 at blk 512 pads to 1024:
    ~2.5x the work), so divisibility wins over size."""
    L8 = _pad_len(L, 8)
    edges = ((1024, 512, 256, 128) if max(D, Dv) <= _WIDE_EDGE_MAX_WIDTH
             else (512, 256, 128))
    for cand in edges:
        if cand <= L8 and L8 % cand == 0:
            return cand
    return min(128, L8)


def _resolve_blocks(L: int, blk_q: Optional[int], blk_k: Optional[int],
                    D: int = 128, Dv: int = 128):
    blk_q = min(blk_q or _auto_blk(L, D, Dv), _pad_len(L, 8))
    blk_k = min(blk_k or _auto_blk(L, D, Dv), _pad_len(L, 8))
    Lp = max(_pad_len(L, blk_q), _pad_len(L, blk_k))
    return blk_q, blk_k, Lp


def _block_classes(L: int, blk_q: int, blk_k: int, Lp: int,
                   causal: bool) -> np.ndarray:
    """(query blocks, key blocks) array of _DEAD / _WHOLE / _MASKED: what
    `_mask_for`'s mask (key inside ``L``, and under the diagonal where
    causal) holds of each block — nothing, everything, or some. Padded
    QUERY rows are not masked (their results are cut off), so they do not
    enter."""
    qi = np.arange(Lp // blk_q)[:, None]
    kj = np.arange(Lp // blk_k)[None, :]
    some = np.broadcast_to(kj * blk_k < L, (qi.size, kj.size))
    every = np.broadcast_to((kj + 1) * blk_k <= L, some.shape)
    if causal:
        some = some & (kj * blk_k <= (qi + 1) * blk_q - 1)
        every = every & ((kj + 1) * blk_k - 1 <= qi * blk_q)
    return np.where(every, _WHOLE, np.where(some, _MASKED, _DEAD))


def flash_block_census(L: int, blk_q: Optional[int] = None,
                       blk_k: Optional[int] = None,
                       causal: bool = False) -> dict:
    """How many (query block, key block) pairs one head's kernels walk:
    ``live`` grid steps, of them ``whole`` on the body without a mask and
    ``masked`` on the body with one, against the ``walked_before`` of a
    grid over every pair. Counted from the very classes the step tables
    are built from; an edge left None is the automatic one at widths of
    128."""
    blk_q, blk_k, Lp = _resolve_blocks(L, blk_q, blk_k)
    classes = _block_classes(L, blk_q, blk_k, Lp, causal)
    whole = int((classes == _WHOLE).sum())
    masked = int((classes == _MASKED).sum())
    return {"live": whole + masked, "whole": whole, "masked": masked,
            "walked_before": int(classes.size)}


def _step_tables(classes: np.ndarray, members: Optional[int] = None):
    """The live pairs in walking order, as the three int32 tables the
    kernels prefetch. ``members=None`` walks by query block, key blocks
    ascending within it (forward, dQ). ``members=G`` walks by key block,
    member-major and query blocks ascending within it (dK/dV), and the
    first table holds ``member * nq + query block``. The third holds
    _F_FIRST / _F_LAST on the first and last step of each output block
    and _F_MASKED on a _MASKED pair. A key block wholly in the padding
    has no live pair and gets no step: its rows of dK/dV are never
    written, and are cut off with the padding."""
    nq, nk = classes.shape
    classes = classes.tolist()      # plain ints: this loop runs per trace
    if members is None:     # an output block is a query block
        walks = [[(i, i, j) for j in range(nk)] for i in range(nq)]
    else:                   # an output block is a key block
        walks = [[(m * nq + i, i, j)
                  for m in range(members) for i in range(nq)]
                 for j in range(nk)]
    q_of, k_of, flags = [], [], []
    for walk in walks:
        live = [(entry, j, classes[i][j]) for entry, i, j in walk
                if classes[i][j] != _DEAD]
        for at, (entry, j, cls) in enumerate(live):
            q_of.append(entry)
            k_of.append(j)
            flags.append(_F_FIRST * (at == 0)
                         | _F_LAST * (at == len(live) - 1)
                         | _F_MASKED * (cls == _MASKED))
    return tuple(np.asarray(x, np.int32) for x in (q_of, k_of, flags))


_SEQ_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _kv_head_index(Hq: int, Hkv: int):
    """Flat (batch*q-head) grid index → flat (batch*kv-head) array index:
    query head h reads KV group h // (Hq // Hkv). The KV tensors stay at
    kv-head size in HBM — no repeat is ever materialized."""
    G = Hq // Hkv
    return lambda b: (b // Hq) * Hkv + (b % Hq) // G


def _walk_call(kernel, name: str, classes: np.ndarray, members, heads: int,
               interpret: bool, operands, *, in_specs, out_specs, out_shape,
               scratch_shapes, **static):
    """One kernel over ``heads`` × the live steps of ``classes``: the step
    tables ride in front of the operands as scalar prefetch (index maps
    and kernel both read them), and the kernel holds a body only for the
    classes the tables hold."""
    tables = _step_tables(classes, members)
    masked = (tables[2] & _F_MASKED) != 0
    return pl.pallas_call(
        functools.partial(kernel, has_whole=bool((~masked).any()),
                          has_masked=bool(masked.any()), **static),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=(heads, len(tables[0])),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        compiler_params=None if interpret else _SEQ_PARAMS,
        interpret=interpret,
        name=name,
    )(*tables, *operands)


def _gqa_shapes(q, k, v):
    """(B, Hq, Hkv, L, D, Dv): ``D`` is the width of the scores (q and k),
    ``Dv`` the width of the values and of the output."""
    B, Hq, L, D = q.shape
    Hkv = k.shape[1]
    if Hq % Hkv:
        raise ValueError(
            f"query heads ({Hq}) must be a multiple of KV heads ({Hkv})")
    if k.shape[-1] != D or v.shape[1] != Hkv:
        raise ValueError(
            f"k {k.shape} must share q's width ({D}) and v's heads "
            f"({v.shape[1]})")
    return B, Hq, Hkv, L, D, v.shape[-1]


def _flash_forward(q, k, v, causal: bool, blk_q: int, blk_k: int,
                   interpret: bool, scale: Optional[float] = None):
    B, H, Hkv, L, D, Dv = _gqa_shapes(q, k, v)
    blk_q, blk_k, Lp = _resolve_blocks(L, blk_q, blk_k, D, Dv)
    scale = _score_scale(q, scale)
    kv_ix = _kv_head_index(H, Hkv)
    qf = q.reshape(B * H, L, D)
    kf = k.reshape(B * Hkv, L, D)
    vf = v.reshape(B * Hkv, L, Dv)
    if Lp != L:
        pad = ((0, 0), (0, Lp - L), (0, 0))
        qf, kf, vf = (jnp.pad(x, pad) for x in (qf, kf, vf))
    q_index = lambda b, t, q_of, k_of, flags: (b, q_of[t], 0)
    kv_index = lambda b, t, q_of, k_of, flags: (kv_ix(b), k_of[t], 0)
    out, lse = _walk_call(
        _fwd_kernel, "flash_fwd",
        _block_classes(L, blk_q, blk_k, Lp, causal), None, B * H, interpret,
        (qf, kf, vf),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), q_index),
            pl.BlockSpec((1, blk_k, D), kv_index),
            pl.BlockSpec((1, blk_k, Dv), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, Dv), q_index),
            pl.BlockSpec((1, blk_q, _STAT_LANES), q_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Lp, Dv), q.dtype),
            # logsumexp replicated across the lane dim (2D-tiled layout;
            # callers slice [:, :, 0])
            jax.ShapeDtypeStruct((B * H, Lp, _STAT_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, _LANES), jnp.float32),   # m
            pltpu.VMEM((blk_q, _LANES), jnp.float32),   # l
            pltpu.VMEM((blk_q, Dv), jnp.float32),       # acc
        ],
        causal=causal, scale=scale, kv_len=None if L == Lp else L)
    return out[:, :L].reshape(B, H, L, Dv), lse


def _flash_backward(q, k, v, out, lse, g, causal: bool, blk_q: int,
                    blk_k: int, interpret: bool, delta=None,
                    scale: Optional[float] = None):
    """``lse`` (and the optional precomputed ``delta``) arrive in LOGICAL
    layout — (B, H, L) fp32; the kernel HBM layout (padded, lane-
    replicated) is produced here so callers never touch it. Padded query
    rows get a large lse sentinel: their g/delta are zero, but a small pad
    value could overflow p = exp(s - lse) into inf·0 = nan."""
    B, H, Hkv, L, D, Dv = _gqa_shapes(q, k, v)
    G = H // Hkv
    blk_q, blk_k, Lp = _resolve_blocks(L, blk_q, blk_k, D, Dv)
    scale = _score_scale(q, scale)
    kv_ix = _kv_head_index(H, Hkv)
    flat = lambda x: x.reshape(-1, L, x.shape[-1])
    qf, kf, vf, of, gf = map(flat, (q, k, v, out, g))
    if delta is None:
        # delta_i = rowsum(dO_i * O_i)
        delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                        axis=-1)
    delta = jnp.asarray(delta, jnp.float32).reshape(B * H, L)
    lse = jnp.asarray(lse, jnp.float32).reshape(B * H, L)
    if Lp != L:
        pad3 = ((0, 0), (0, Lp - L), (0, 0))
        qf, kf, vf, gf = (jnp.pad(x, pad3) for x in (qf, kf, vf, gf))
        delta = jnp.pad(delta, ((0, 0), (0, Lp - L)))
        lse = jnp.pad(lse, ((0, 0), (0, Lp - L)), constant_values=1e30)
    delta = jnp.broadcast_to(delta[..., None], (B * H, Lp, _STAT_LANES))
    lse = jnp.broadcast_to(lse[..., None], (B * H, Lp, _STAT_LANES))
    nq = Lp // blk_q
    classes = _block_classes(L, blk_q, blk_k, Lp, causal)
    static = dict(causal=causal, scale=scale, kv_len=None if L == Lp else L)

    q_index = lambda b, t, q_of, k_of, flags: (b, q_of[t], 0)
    kv_index = lambda b, t, q_of, k_of, flags: (kv_ix(b), k_of[t], 0)
    dq = _walk_call(
        _dq_kernel, "flash_bwd_dq", classes, None, B * H, interpret,
        (qf, kf, vf, gf, lse, delta),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), q_index),
            pl.BlockSpec((1, blk_k, D), kv_index),
            pl.BlockSpec((1, blk_k, Dv), kv_index),
            pl.BlockSpec((1, blk_q, Dv), q_index),
            pl.BlockSpec((1, blk_q, _STAT_LANES), q_index),
            pl.BlockSpec((1, blk_q, _STAT_LANES), q_index),
        ],
        out_specs=pl.BlockSpec((1, blk_q, D), q_index),
        out_shape=jax.ShapeDtypeStruct((B * H, Lp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, D), jnp.float32)],
        **static)

    # dK/dV: grid b runs over B*Hkv KV heads, and a key block's steps
    # over (group member, live query block), member-major
    # (qm_of[t] = member * nq + query block)
    def qm_index(b, t, qm_of, k_of, flags):
        return ((b // Hkv) * H + (b % Hkv) * G + qm_of[t] // nq,
                qm_of[t] % nq, 0)

    k_index = lambda b, t, qm_of, k_of, flags: (b, k_of[t], 0)
    dk, dv = _walk_call(
        _dkv_kernel, "flash_bwd_dkv", classes, G, B * Hkv, interpret,
        (qf, kf, vf, gf, lse, delta),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), qm_index),
            pl.BlockSpec((1, blk_k, D), k_index),
            pl.BlockSpec((1, blk_k, Dv), k_index),
            pl.BlockSpec((1, blk_q, Dv), qm_index),
            pl.BlockSpec((1, blk_q, _STAT_LANES), qm_index),
            pl.BlockSpec((1, blk_q, _STAT_LANES), qm_index),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_k, D), k_index),
            pl.BlockSpec((1, blk_k, Dv), k_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, Lp, D), k.dtype),
            jax.ShapeDtypeStruct((B * Hkv, Lp, Dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, D), jnp.float32),
            pltpu.VMEM((blk_k, Dv), jnp.float32),
        ],
        nq=nq, **static)

    return (dq[:, :L].reshape(B, H, L, D),
            dk[:, :L].reshape(B, Hkv, L, D),
            dv[:, :L].reshape(B, Hkv, L, Dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = False,
                    blk_q: Optional[int] = None,
                    blk_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    scale: Optional[float] = None):
    """Flash attention over (B, H, L, D). ``v`` (and so the output) may be
    of another width than ``q`` and ``k`` (latent attention scores over 192
    columns and carries 128); ``scale`` replaces the softmax's
    ``1/sqrt(D)``. Grouped-query attention is
    native: ``k``/``v`` may carry fewer heads than ``q`` (Hq a multiple of
    Hkv) and stay at kv-head size in HBM — block index maps route each
    query head to its KV group; dK/dV accumulate over the group in the
    backward. ``blk_q``/``blk_k=None`` auto-size blocks (:func:`_auto_blk`,
    capped to the padded sequence). ``interpret=None`` auto-selects interpret mode
    off-TPU so the same call works in CI and on chip."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out, _ = _flash_forward(q, k, v, causal, blk_q, blk_k, interpret, scale)
    return out


def _fwd(q, k, v, causal, blk_q, blk_k, interpret, scale):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out, lse = _flash_forward(q, k, v, causal, blk_q, blk_k, interpret,
                              scale)
    B, H, L, _ = q.shape
    # residual lse in logical layout: 8x smaller than the kernel's
    # lane-replicated padded buffer, and the layout knowledge stays here
    return out, (q, k, v, out, lse[:, :L, 0].reshape(B, H, L))


def _bwd(causal, blk_q, blk_k, interpret, scale, res, g):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal, blk_q, blk_k,
                           interpret, scale=scale)


flash_attention.defvjp(_fwd, _bwd)


# Flash-vs-dense crossover (sequence length). Below it XLA's fused dense
# attention is at least as fast and compiles quicker; at/above it the dense
# path's (L, L) score matrix starts to dominate memory and the kernel's
# causal block skip pays off. The crossover itself is not measured on
# today's code: the benchmark's cells run the kernel at 4,096 and no cell
# at 2,048 (ROADMAP.md).
FLASH_MIN_SEQ = 4096


def attention(q, k, v, causal: bool = False, *,
              min_flash_seq: Optional[int] = None,
              blk_q: Optional[int] = None,
              blk_k: Optional[int] = None,
              scale: Optional[float] = None):
    """Sequence-length-routed attention: the pallas flash kernel at
    ``L >= min_flash_seq`` (default :data:`FLASH_MIN_SEQ`), XLA's fused
    dense attention below. GQA inputs (fewer K/V heads) and a value width
    other than the scores' work on both paths — dense broadcasts the KV
    groups at compute time."""
    threshold = FLASH_MIN_SEQ if min_flash_seq is None else int(min_flash_seq)
    if q.shape[2] >= threshold:
        return flash_attention(q, k, v, causal, blk_q, blk_k, None, scale)
    if k.shape[1] != q.shape[1]:
        group = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    return _dense_attention(q, k, v, causal, scale)
