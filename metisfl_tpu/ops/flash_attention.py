"""Pallas flash attention (TPU kernels, interpret-mode on CPU).

Blockwise attention with online softmax: the (L, L) score matrix never
reaches HBM. Forward and backward are Mosaic-native grid-accumulation
kernels — the KV (resp. Q) block index is a sequential GRID dimension,
running statistics live in VMEM scratch across grid steps, and causal
skipping is ``pl.when`` predication of whole blocks. No dynamic loop trip
counts anywhere (an earlier revision drove a ``fori_loop`` with a
program-id-dependent bound; grid predication is the pattern the TPU
toolchain is built for), and K/V stream through VMEM one block per step, so
VMEM stays bounded at any sequence length.

The backward is the FlashAttention-2 scheme: dQ accumulates over KV blocks,
dK/dV accumulate over Q blocks, both recomputing probabilities from the
forward's saved logsumexp — training memory is O(L·D) end to end. The
forward accumulator is FA2's unnormalized numerator (one alpha rescale per
step, a single divide at the store). Causal mode skips fully-masked blocks
in all three kernels (~half the FLOPs), and the skipped steps' block
index maps clamp to the last valid block so the pipeline elides their
DMAs too (~half the HBM traffic).

Where it wins: the kernel's value is O(L·D) memory (the (L, L) score
matrix never materializes), which is what makes long sequences fit at all;
on raw speed XLA's fused dense attention is competitive at moderate L
(dense against flash at 2,048 is not measured on today's code), with the
kernel's causal block skip paying off as L grows past the score-matrix
memory wall. At 4,096, in the learner's step of the cell
``internlm2-1.8b.lora-round``, the kernels run at 36.57 % of their roofline
(``flash_roofline``; PERF_LEDGER.jsonl, PR 29). Use
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


def _causal_overlap(qi, blk_q, kj, blk_k):
    """True when key block kj has any unmasked column for query block qi."""
    return kj * blk_k <= (qi + 1) * blk_q - 1


def _mask_for(qi, blk_q, kj, blk_k, kv_len, causal):
    q_pos = qi * blk_q + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 0)
    k_pos = kj * blk_k + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 1)
    mask = k_pos < kv_len                       # tail-padding mask
    if causal:
        mask &= q_pos >= k_pos
    return mask


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
                causal: bool, scale: float, kv_len: int, nk: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    blk_q, D = q_ref.shape[1], q_ref.shape[2]
    blk_k = k_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, _NEG, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    run = _causal_overlap(qi, blk_q, kj, blk_k) if causal else True

    @pl.when(run)
    def _attend():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _mask_for(qi, blk_q, kj, blk_k, kv_len, causal)
        s = jnp.where(mask, s, _NEG)
        m_prev = m_s[...]                       # (blk_q, LANES), lanes equal
        l_prev = l_s[...]
        m_curr = jnp.max(s, axis=1)[:, None]    # (blk_q, 1)
        m_next = jnp.maximum(m_prev, m_curr)    # (blk_q, LANES)
        p = jnp.exp(s - m_next[:, :1])
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

    @pl.when(kj == nk - 1)
    def _store():
        l_fin = l_s[...]
        # fully-masked rows (tail padding) have l == 0: emit 0, not nan
        l_inv = jnp.where(l_fin == 0.0, 0.0, 1.0 / jnp.maximum(l_fin, 1e-30))
        o_ref[0] = (acc_s[...] * l_inv[:, :1]).astype(o_ref.dtype)
        lse_ref[0] = (m_s[...] + jnp.log(jnp.maximum(l_fin, 1e-30)))[
            :, :_STAT_LANES]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_s, *, causal: bool, scale: float, kv_len: int, nk: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    blk_q = q_ref.shape[1]
    blk_k = k_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)

    run = _causal_overlap(qi, blk_q, kj, blk_k) if causal else True

    @pl.when(run)
    def _accumulate():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]                 # (blk_q, 1)
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _mask_for(qi, blk_q, kj, blk_k, kv_len, causal)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_s[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                 preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _store():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_s, dv_s, *, causal: bool, scale: float,
                kv_len: int, nq: int, g_size: int = 1):
    kj = pl.program_id(1)
    # sequential dim enumerates (group member × q block), MEMBER-MAJOR
    # (t = member * nq + qi): the dK/dV of one KV head accumulates over
    # every query head in its group, and within one member's segment the
    # head component of the block index is constant — so the causal
    # clamp's repeated indices actually elide DMAs (q-block-major would
    # cycle heads every step and never repeat an index)
    t = pl.program_id(2)
    qi = t % nq
    blk_k = k_ref.shape[1]
    blk_q = q_ref.shape[1]

    @pl.when(t == 0)
    def _init():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    run = _causal_overlap(qi, blk_q, kj, blk_k) if causal else True

    @pl.when(run)
    def _accumulate():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _mask_for(qi, blk_q, kj, blk_k, kv_len, causal)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
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

    @pl.when(t == nq * g_size - 1)
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


def _auto_blk(L: int) -> int:
    """Largest block edge in {512, 256, 128} that divides the 8-aligned
    sequence length. Bigger blocks cut grid steps (less per-step predication
    / scratch traffic, larger MXU matmuls) and stay well inside VMEM —
    q/k/v/do blocks at 512x128 bf16 are 128 KB each, the f32 scratch
    accumulators 256 KB — but an edge that does NOT divide L would pad the
    grid up to the next multiple and burn the padding as masked FLOPs
    (e.g. L=640 at blk 512 pads to 1024: ~2.5x the work), so divisibility
    wins over size."""
    L8 = _pad_len(L, 8)
    for cand in (512, 256, 128):
        if cand <= L8 and L8 % cand == 0:
            return cand
    return min(128, L8)


def _resolve_blocks(L: int, blk_q: Optional[int], blk_k: Optional[int]):
    blk_q = min(blk_q or _auto_blk(L), _pad_len(L, 8))
    blk_k = min(blk_k or _auto_blk(L), _pad_len(L, 8))
    Lp = max(_pad_len(L, blk_q), _pad_len(L, blk_k))
    return blk_q, blk_k, Lp


_SEQ_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _kv_head_index(Hq: int, Hkv: int):
    """Flat (batch*q-head) grid index → flat (batch*kv-head) array index:
    query head h reads KV group h // (Hq // Hkv). The KV tensors stay at
    kv-head size in HBM — no repeat is ever materialized."""
    G = Hq // Hkv
    return lambda b: (b // Hq) * Hkv + (b % Hq) // G


def _kv_block_index(kv_ix, blk_q: int, blk_k: int, causal: bool):
    """K/V block index map for the forward and dQ kernels. In causal mode
    the index clamps to the last unmasked block for the current query
    block: skipped steps (`pl.when` predicated off) then re-request the
    SAME block and the Mosaic pipeline elides the copy — causal saves
    ~half the HBM traffic, not just half the FLOPs. The clamp bound must
    match `_causal_overlap`'s run predicate (identical on live steps)."""
    if causal:
        def ix(b, i, j):
            return (kv_ix(b), jnp.minimum(j, ((i + 1) * blk_q - 1)
                                          // blk_k), 0)
    else:
        def ix(b, i, j):
            return (kv_ix(b), j, 0)
    return ix


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
    blk_q, blk_k, Lp = _resolve_blocks(L, blk_q, blk_k)
    scale = _score_scale(q, scale)
    kv_ix = _kv_head_index(H, Hkv)
    qf = q.reshape(B * H, L, D)
    kf = k.reshape(B * Hkv, L, D)
    vf = v.reshape(B * Hkv, L, Dv)
    if Lp != L:
        pad = ((0, 0), (0, Lp - L), (0, 0))
        qf, kf, vf = (jnp.pad(x, pad) for x in (qf, kf, vf))
    nk = Lp // blk_k
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                               kv_len=L, nk=nk)
    kv_index = _kv_block_index(kv_ix, blk_q, blk_k, causal)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Lp, Dv), q.dtype),
            # logsumexp replicated across the lane dim (2D-tiled layout;
            # callers slice [:, :, 0])
            jax.ShapeDtypeStruct((B * H, Lp, _STAT_LANES), jnp.float32),
        ],
        grid=(B * H, Lp // blk_q, nk),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, D), kv_index),
            pl.BlockSpec((1, blk_k, Dv), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_q, _STAT_LANES), lambda b, i, j: (b, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, _LANES), jnp.float32),   # m
            pltpu.VMEM((blk_q, _LANES), jnp.float32),   # l
            pltpu.VMEM((blk_q, Dv), jnp.float32),       # acc
        ],
        compiler_params=None if interpret else _SEQ_PARAMS,
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf)
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
    blk_q, blk_k, Lp = _resolve_blocks(L, blk_q, blk_k)
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
    nk = Lp // blk_k

    kv_index = _kv_block_index(kv_ix, blk_q, blk_k, causal)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale,
                          kv_len=L, nk=nk),
        out_shape=jax.ShapeDtypeStruct((B * H, Lp, D), q.dtype),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, D), kv_index),
            pl.BlockSpec((1, blk_k, Dv), kv_index),
            pl.BlockSpec((1, blk_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_q, _STAT_LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_q, _STAT_LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((blk_q, D), jnp.float32)],
        compiler_params=None if interpret else _SEQ_PARAMS,
        interpret=interpret,
        name="flash_bwd_dq",
    )(qf, kf, vf, gf, lse, delta)

    # dK/dV accumulate over (group member × q block), member-major
    # (t = member * nq + qi): grid b runs over B*Hkv KV heads. In causal
    # mode, Q blocks strictly above the diagonal are skipped — clamp
    # their index up to the first contributing block; within a member's
    # segment the head component is constant, so those repeated indices
    # elide the leading DMAs of every segment.
    def q_ix(b, j, t):
        qi = t % nq
        if causal:
            qi = jnp.maximum(qi, (j * blk_k) // blk_q)
        return ((b // Hkv) * H + (b % Hkv) * G + t // nq, qi, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=scale,
                          kv_len=L, nq=nq, g_size=G),
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, Lp, D), k.dtype),
            jax.ShapeDtypeStruct((B * Hkv, Lp, Dv), v.dtype),
        ],
        grid=(B * Hkv, nk, nq * G),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), q_ix),
            pl.BlockSpec((1, blk_k, D), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, blk_k, Dv), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, blk_q, Dv), q_ix),
            pl.BlockSpec((1, blk_q, _STAT_LANES), q_ix),
            pl.BlockSpec((1, blk_q, _STAT_LANES), q_ix),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_k, D), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, blk_k, Dv), lambda b, j, t: (b, j, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, D), jnp.float32),
            pltpu.VMEM((blk_k, Dv), jnp.float32),
        ],
        compiler_params=None if interpret else _SEQ_PARAMS,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qf, kf, vf, gf, lse, delta)

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
    backward. ``blk_q``/``blk_k=None`` auto-size blocks (512 capped to the
    padded sequence). ``interpret=None`` auto-selects interpret mode
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
