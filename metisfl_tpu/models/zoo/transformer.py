"""Transformer family: ViT-lite, BERT-lite, Llama-lite (+LoRA), the
attention/state-space hybrid Jamba-lite, the latent-attention decoder
with a share of its routed experts, MlaMoe-lite, and the shortcut-connected
decoder ScMoe-lite (two latent-attention sublayers and two dense FFNs a
layer beside one routed layer with zero-computation experts).

The BASELINE.md scale ladder (ViT-B/16 semi-sync, BERT async + secure,
Llama-3-8B-LoRA with in-learner sharding) needs transformer workloads the
reference never had (its zoo tops out at an IMDB LSTM,
reference examples/keras/models/imdb_lstm.py). Designed TPU-first:

- attention projections are single 2D matmuls (MXU-friendly, and the TP
  partition rules in :data:`TRANSFORMER_RULES` shard them over ``tp``:
  column-parallel qkv/gate/up, row-parallel out/down — XLA inserts the
  all-reduce over ICI);
- static shapes everywhere; causal masking via a static bool mask;
- LoRA adapters (:class:`LoRADense`) add low-rank deltas whose params match
  ``lora_`` so an optimizer mask can freeze the base model
  (``FlaxModelOps(trainable_regex="lora_")``).
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

# TP partition rules (first match wins; see parallel/sharding.py).
# Megatron-style: column-parallel into the head/hidden dimension,
# row-parallel back out, embeddings sharded over vocab rows. LoRA wraps the
# base kernel under ``<name>/base/kernel``, hence the optional segment.
# MoE expert stacks shard their leading expert axis over ``ep`` (expert
# parallelism) and their hidden axis over ``tp`` — XLA inserts the
# dispatch/combine all-to-alls between token- and expert-sharded layouts.
TRANSFORMER_RULES = [
    (r"experts_(w1|gate|up)", P("ep", None, "tp")),
    (r"experts_(w2|down)", P("ep", "tp", None)),
    # latent attention: the up-projections carry the heads (column-
    # parallel), o_proj brings them back; the two down-projections are thin
    # and stay whole
    (r"(q_b_proj|kv_b_proj)(/base)?/kernel", P(None, "tp")),
    (r"o_proj/kernel", P("tp", None)),
    (r"(wq|wk|wv|gate|up|fc1)(/base)?/kernel", P(None, "tp")),
    (r"(wo|down|fc2)(/base)?/kernel", P("tp", None)),
    # Mamba mixer: d_inner is the sharded axis throughout (column-parallel
    # in, row-parallel out; every per-channel tensor follows its channels),
    # so the scan itself needs no collective; x_proj's output (dt, B, C) is
    # the one all-reduce
    (r"(in_proj|dt_proj)(/base)?/kernel|mamba/conv_kernel", P(None, "tp")),
    (r"(out_proj|x_proj)(/base)?/kernel|mamba/A_log", P("tp", None)),
    (r"mamba/(conv_bias|D)$|dt_proj/bias", P("tp")),
    (r"lora_b", P(None, "tp")),
    (r"embed/embedding", P("tp", None)),
    (r"lm_head/kernel", P(None, "tp")),
]


class LoRADense(nn.Module):
    """Dense with an optional low-rank adapter: y = xW + scale·(xA)B.

    ``lora_a``/``lora_b`` params match the ``lora_`` trainable-mask regex;
    the base kernel stays frozen under LoRA fine-tuning."""

    features: int
    rank: int = 0
    alpha: float = 16.0
    use_bias: bool = True
    # computation dtype (mixed precision: fp32 params, e.g. bf16 compute —
    # the MXU-native mode); None keeps full fp32
    dtype: Any = None
    # the base kernel's own type (a frozen base held in the type the step
    # uses it in has no cast in the step); the adapters stay float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        y = nn.Dense(self.features, use_bias=self.use_bias,
                     dtype=self.dtype, param_dtype=self.param_dtype,
                     name="base")(x)
        if self.rank > 0:
            a = self.param("lora_a", nn.initializers.normal(0.02),
                           (x.shape[-1], self.rank))
            b = self.param("lora_b", nn.initializers.zeros,
                           (self.rank, self.features))
            if self.dtype is not None:
                a, b = a.astype(self.dtype), b.astype(self.dtype)
            y = y + (x @ a) @ b * (self.alpha / self.rank)
        return y


def _rotary(x, positions, freqs=None):
    """Rotary position embedding over the last (head) dimension, half-split;
    ``freqs`` (half the width) replaces the base-10000 ladder."""
    half = x.shape[-1] // 2
    if freqs is None:
        freqs = 1.0 / (10000 ** (np.arange(0, half) / half))
    angles = positions[..., None] * freqs  # (..., L, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


class Attention(nn.Module):
    """Multi-head attention with 2D projection kernels (TP-shardable).

    ``sp_mesh`` switches the score/softmax/value stage to ring attention
    over the mesh's ``sp`` axis (sequence parallelism — exact attention
    with O(L/sp) per-device memory; see parallel/ringattn.py). Rotary runs
    on the logically-global arrays before the shard_map island, so
    positions stay global. Attention-weight dropout is a no-op on the ring
    path (the (L, L) matrix never exists to drop from).
    """

    dim: int
    heads: int
    causal: bool = False
    rotary: bool = False
    dropout: float = 0.0
    lora_rank: int = 0
    sp_mesh: object = None
    sp_axis: str = "sp"
    # "ring" (blockwise ppermute rotation, O(L/sp) memory, scales with L)
    # or "ulysses" (all-to-all head scatter, fewer collectives when
    # sp <= heads) — see parallel/{ringattn,ulysses}.py for the trade-off
    sp_strategy: str = "ring"
    # run each ring hop's block attention on the pallas flash kernels
    # (ringattn.make_ring_attention(block_kernels=True)); ring-only — the
    # ulysses local attention routes to the flash kernel on its own
    sp_block_kernels: bool = False
    use_flash: bool = False
    dtype: Any = None
    # Grouped-query attention (Llama-3 style): K/V project to kv_heads
    # groups, shrinking the wk/wv kernels and the shipped/optimizer state
    # by heads/kv_heads. The flash kernel and the ring schedule are
    # GQA-native (K/V stay at kv-head size in HBM / on the ICI ring); only
    # the dense path broadcasts K/V across each group's query heads at
    # compute time. 0 → kv_heads = heads (plain MHA); 1 = MQA.
    kv_heads: int = 0

    @nn.compact
    def __call__(self, x, train: bool = False, cache=None, position=None):
        B, L, _ = x.shape
        head_dim = self.dim // self.heads
        kv_heads = self.kv_heads or self.heads
        if kv_heads <= 0 or self.heads % kv_heads:
            raise ValueError(
                f"heads ({self.heads}) must be a multiple of kv_heads "
                f"({kv_heads})")
        if self.dropout > 0.0 and (self.use_flash or self.sp_mesh is not None):
            # neither kernelized path materializes the (L, L) weight matrix,
            # so attention-weight dropout cannot be applied there
            raise ValueError(
                "attention dropout > 0 is only supported on the dense "
                "attention path; set dropout=0 or disable use_flash/sp_mesh")

        def proj(name, features, rank=0):
            return LoRADense(features, rank=rank, use_bias=False,
                             dtype=self.dtype, name=name)

        # LoRA on q/v only (standard practice)
        q = proj("wq", self.dim, self.lora_rank)(x)
        k = proj("wk", kv_heads * head_dim)(x)
        v = proj("wv", kv_heads * head_dim, self.lora_rank)(x)
        q = q.reshape(B, L, self.heads, head_dim).transpose(0, 2, 1, 3)
        k = k.reshape(B, L, kv_heads, head_dim).transpose(0, 2, 1, 3)
        v = v.reshape(B, L, kv_heads, head_dim).transpose(0, 2, 1, 3)
        if cache is not None:
            out, cache = self._cached_attention(q, k, v, cache, position,
                                                head_dim)
            out = out.transpose(0, 2, 1, 3).reshape(B, L, self.dim)
            return nn.Dense(self.dim, use_bias=False, dtype=self.dtype,
                            name="wo")(out), cache
        if self.rotary:
            positions = jnp.arange(L, dtype=jnp.float32)
            dt = q.dtype
            q = _rotary(q, positions).astype(dt)
            k = _rotary(k, positions).astype(dt)
        if (kv_heads != self.heads and self.sp_mesh is None
                and not self.use_flash):
            # dense path only: broadcast each KV group across its query
            # heads AFTER rotary (rotary is per-head pointwise, so they
            # commute — this keeps the rotary work at kv_heads size); XLA
            # fuses the repeat into the einsums. The flash kernel and the
            # ring schedule are both GQA-native — K/V stay at kv-head size
            # in HBM / on the ICI ring, mapped to query heads by kernel
            # index arithmetic.
            group = self.heads // kv_heads
            k = jnp.repeat(k, group, axis=1)
            v = jnp.repeat(v, group, axis=1)
        if self.sp_mesh is not None:
            if self.sp_strategy == "ulysses":
                if self.sp_block_kernels:
                    raise ValueError(
                        "sp_block_kernels is ring-specific (per-hop block "
                        "kernels); the ulysses local attention already "
                        "routes to the flash kernel by sequence length")
                from metisfl_tpu.parallel.ulysses import (
                    make_ulysses_attention,
                )
                out = make_ulysses_attention(
                    self.sp_mesh, self.sp_axis,
                    causal=self.causal)(q, k, v)
            elif self.sp_strategy == "ring":
                from metisfl_tpu.parallel.ringattn import (
                    make_ring_attention,
                )
                out = make_ring_attention(
                    self.sp_mesh, self.sp_axis, causal=self.causal,
                    block_kernels=self.sp_block_kernels)(q, k, v)
            else:
                raise ValueError(
                    f"unknown sp_strategy {self.sp_strategy!r}; "
                    "have 'ring' | 'ulysses'")
        elif self.use_flash:
            if self.use_flash == "auto":
                # sequence-length routing: dense below the measured
                # crossover (ops/flash_attention.py FLASH_MIN_SEQ), the
                # pallas kernel above it
                from metisfl_tpu.ops import attention
                out = attention(q, k, v, self.causal)
            else:
                from metisfl_tpu.ops import flash_attention
                out = flash_attention(q, k, v, self.causal)
        else:
            # softmax in fp32 regardless of compute dtype (bf16 exp/normalize
            # loses too much precision), then back to the compute dtype so
            # the PV matmul stays on the MXU's native path
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(
                jnp.float32) * float(1.0 / np.sqrt(head_dim))
            if self.causal:
                mask = jnp.tril(jnp.ones((L, L), bool))
                scores = jnp.where(mask, scores,
                                   jnp.finfo(scores.dtype).min)
            weights = nn.softmax(scores, axis=-1).astype(v.dtype)
            weights = nn.Dropout(self.dropout,
                                 deterministic=not train)(weights)
            out = jnp.einsum("bhqk,bhkd->bhqd", weights, v)
        out = out.transpose(0, 2, 1, 3).reshape(B, L, self.dim)
        return nn.Dense(self.dim, use_bias=False, dtype=self.dtype,
                        name="wo")(out)

    def _cached_attention(self, q, k, v, cache, position, head_dim):
        """Incremental attention against a KV cache (autoregressive decode).

        ``cache`` is ``(ck, cv)`` of shape (B, kv_heads, L_max, head_dim);
        ``position`` is the (traced) index of the first query position. The
        new K/V land in the cache via ``dynamic_update_slice`` and q attends
        over the full cache under the mask ``key_pos <= position + q_idx``
        — static shapes throughout, so one compiled program serves every
        decode step. Handles both prefill (L = prompt length at position 0)
        and single-token decode (L = 1). Dense math only: at L = 1 there is
        no (L, L) matrix for flash/ring to save."""
        if self.sp_mesh is not None:
            raise ValueError("cached decode does not compose with sp_mesh; "
                             "decode on a replicated module instead")
        ck, cv = cache
        L = q.shape[2]
        L_max = ck.shape[2]
        pos0 = jnp.asarray(position, jnp.int32)
        if self.rotary:
            positions = (pos0 + jnp.arange(L)).astype(jnp.float32)
            dt = q.dtype
            q = _rotary(q, positions).astype(dt)
            k = _rotary(k, positions).astype(dt)
        # ``_write_kv`` (at the end of this file, so that no line above it
        # moves): this dynamic_update_slice, and under ``vmap`` over decode
        # slots one such write a slot, in place
        ck = _write_kv(ck, k.astype(ck.dtype), pos0)
        cv = _write_kv(cv, v.astype(cv.dtype), pos0)
        # grouped einsums read the cache at kv-head size (decode is
        # HBM-bound; repeating K/V to all query heads would rewrite the
        # whole cache heads/kv_heads times per step and erase the GQA
        # bandwidth win). Query heads group contiguously per kv head —
        # the same layout jnp.repeat gives the dense training path.
        kv_heads = ck.shape[1]
        B = q.shape[0]
        group = self.heads // kv_heads
        qg = q.reshape(B, kv_heads, group, L, head_dim)
        scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, ck).astype(
            jnp.float32) * float(1.0 / np.sqrt(head_dim))
        # causal over absolute positions; also hides the cache's unwritten
        # (zero) tail beyond position + L
        mask = (jnp.arange(L_max)[None, :]
                <= pos0 + jnp.arange(L)[:, None])
        scores = jnp.where(mask[None, None, None], scores,
                           jnp.finfo(scores.dtype).min)
        weights = nn.softmax(scores, axis=-1).astype(cv.dtype)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", weights, cv)
        return out.reshape(B, self.heads, L, head_dim), (ck, cv)


class SwiGLU(nn.Module):
    """Llama-style gated MLP (gate/up column-parallel, down row-parallel)."""

    dim: int
    hidden: int
    dtype: Any = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype,
                                  param_dtype=self.param_dtype)
        gate = dense(self.hidden, name="gate")(x)
        up = dense(self.hidden, name="up")(x)
        return dense(self.dim, name="down")(nn.silu(gate) * up)


class MoEMLP(nn.Module):
    """Top-k mixture-of-experts FFN (expert parallelism).

    Expert weights are stacked on a leading expert axis (``experts_w1`` /
    ``experts_w2``) that :data:`TRANSFORMER_RULES` shards over ``ep``.
    Dispatch and combine are one-hot einsums over a fixed per-expert
    capacity — static shapes, MXU-shaped (E, C, D) @ (E, D, H) batched
    matmuls, and when token shardings (dp) and expert shardings (ep) differ
    XLA inserts the all-to-alls over ICI. ``top_k=1`` is the Switch
    transformer (default); ``top_k=2`` is GShard-style routing with gates
    renormalized over the chosen experts and second choices queued behind
    first choices in each expert's capacity buffer. Tokens beyond capacity
    are dropped (residual connections carry them through), and the standard
    load-balance auxiliary loss is sown under
    ``intermediates/moe_aux_loss``.
    """

    dim: int
    hidden: int
    num_experts: int = 8
    capacity_factor: float = 1.25
    top_k: int = 1
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        B, L, D = x.shape
        T = B * L
        E = self.num_experts
        K = self.top_k
        if not 1 <= K <= E:
            raise ValueError(f"top_k ({K}) must be in [1, {E}]")
        tokens = x.reshape(T, D)
        # routing in fp32: tiny matmul, precision-sensitive softmax
        logits = nn.Dense(E, use_bias=False, name="router")(
            tokens.astype(jnp.float32))
        probs = nn.softmax(logits, axis=-1)
        top_vals, top_idx = jax.lax.top_k(probs, K)              # (T, K)
        # gates renormalized over the chosen experts (GShard); for K=1 this
        # reduces to dividing by itself only when normalizing — keep the
        # Switch convention of the raw top prob at K=1
        gates = (top_vals if K == 1
                 else top_vals / jnp.sum(top_vals, -1, keepdims=True))
        onehots = jax.nn.one_hot(top_idx.T, E, dtype=jnp.float32)  # (K, T, E)

        # load-balance aux loss (Switch eq. 4) on FIRST choices:
        # E * Σ_e fraction_e * prob_e
        density = onehots[0].mean(axis=0)
        router_prob = probs.mean(axis=0)
        self.sow("intermediates", "moe_aux_loss",
                 E * jnp.sum(density * router_prob))

        capacity = int(np.ceil(T / E * self.capacity_factor * K))
        # choice-major buffer order: every first choice queues before any
        # second choice, within a choice tokens queue in order — computed by
        # one running cumsum over the (K*T, E) choice-major assignment
        flat = onehots.reshape(K * T, E)
        pos_flat = (jnp.cumsum(flat, axis=0) - 1.0) * flat       # (K*T, E)
        keep_flat = (pos_flat < capacity).astype(jnp.float32) * flat
        pos_cap = jax.nn.one_hot(
            (pos_flat * keep_flat).sum(-1).astype(jnp.int32), capacity,
            dtype=jnp.float32)                                   # (K*T, C)
        # (K*T, E, C) → sum over choices → (T, E, C); gate-weighted combine
        disp_flat = (keep_flat[:, :, None] * pos_cap[:, None, :]).reshape(
            K, T, E, capacity)
        dispatch = disp_flat.sum(0)
        gate_disp = (disp_flat * gates.T[:, :, None, None]).sum(0)

        dt = self.dtype or tokens.dtype
        expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(dt),
                               tokens.astype(dt))                # (E, C, D)
        w1 = self.param("experts_w1",
                        nn.initializers.normal(1.0 / np.sqrt(D)),
                        (E, D, self.hidden))
        w2 = self.param("experts_w2",
                        nn.initializers.normal(1.0 / np.sqrt(self.hidden)),
                        (E, self.hidden, D))
        h = nn.gelu(jnp.einsum("ecd,edh->ech", expert_in, w1.astype(dt)))
        out = jnp.einsum("ech,ehd->ecd", h, w2.astype(dt))       # (E, C, D)
        mixed = jnp.einsum("tec,ecd->td", gate_disp.astype(dt), out)
        return mixed.reshape(B, L, D)


def yarn_frequencies(width: int, base: float, factor: float,
                     original_max: int, beta_fast: float = 32.0,
                     beta_slow: float = 1.0) -> np.ndarray:
    """The ``width / 2`` rotary frequencies under YaRN: ladder step ``i``
    keeps its frequency ``f_i = base ** (-2 i / width)`` where it turns
    more than ``beta_fast`` times over the original context, is divided by
    ``factor`` where it turns fewer than ``beta_slow`` times, and is
    blended on a linear ramp between the two steps."""
    half = width // 2
    f = base ** (-np.arange(half, dtype=np.float64) * 2.0 / width)

    def turns_at(beta: float) -> float:
        return (width * np.log(original_max / (beta * 2 * np.pi))
                / (2 * np.log(base)))

    low = max(int(np.floor(turns_at(beta_fast))), 0)
    high = min(int(np.ceil(turns_at(beta_slow))), width - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (f / factor * ramp + f * (1.0 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention factor: ``0.1 mscale ln(factor) + 1`` past 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * float(np.log(factor)) + 1.0


class LatentAttention(nn.Module):
    """Multi-head latent attention, with ``h`` the block's normed input::

        c_q = RMSNorm(h W_qa);  q = (c_q W_qb) s_q      heads of [nope | rope]
        [c_kv | k_rope] = h W_kva;  c_kv = RMSNorm(c_kv) s_kv
        c_kv W_kvb                                  heads of [k_nope | v]
        key of a head = [k_nope | rotary(k_rope)], k_rope shared by all
        out = softmax(q k^T scale, causal) v W_o

    The rotary columns take YaRN's frequencies (:func:`yarn_frequencies`)
    in the half-split rotation of :func:`_rotary`; ``scale`` is
    ``(nope + rope) ** -0.5 * m ** 2`` with ``m = yarn_mscale(factor,
    mscale_all_dim)``, and cos and sin carry ``yarn_mscale(factor, mscale)
    / m``. ``s_q`` and ``s_kv`` (``q_scale``, ``kv_scale``; 1 leaves them
    out) are the two latent scales of a family that sets them, ``(dim /
    rank) ** 0.5`` there; ``k_rope`` is not scaled. No bias anywhere.
    ``lora_rank`` puts adapters on the four latent projections; ``o_proj``
    has none.

    ``cache`` is ``(c_kv (B, L_max, kv_rank), k_rope (B, L_max, rope))``:
    the latents, not the heads' keys and values (576 values a position
    where 64 heads would hold 64 x 320). The cached path expands keys and
    values from the cached latents at every call."""

    dim: int
    heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_base: float = 10000.0
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    q_scale: float = 1.0
    kv_scale: float = 1.0
    eps: float = 1e-6
    lora_rank: int = 0
    lora_alpha: float = 16.0
    use_flash: Any = False
    dtype: Any = None
    param_dtype: Any = jnp.float32

    def rotary_frequencies(self) -> np.ndarray:
        return yarn_frequencies(self.rope_dim, self.rope_base,
                                self.rope_factor, self.rope_original_max,
                                self.rope_beta_fast, self.rope_beta_slow)

    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return float((self.nope_dim + self.rope_dim) ** -0.5 * m * m)

    def _rotate(self, x, positions):
        """``x`` (B, L, heads, rope) at ``positions`` (L,)."""
        amp = (yarn_mscale(self.rope_factor, self.rope_mscale)
               / yarn_mscale(self.rope_factor, self.rope_mscale_all_dim))
        out = _rotary(jnp.swapaxes(x, 1, 2), positions,
                      self.rotary_frequencies())
        if amp != 1.0:
            out = out * amp
        return jnp.swapaxes(out, 1, 2).astype(x.dtype)

    def init_cache(self, batch: int, max_len: int):
        dtype = self.dtype or jnp.float32
        return (jnp.zeros((batch, max_len, self.kv_rank), dtype),
                jnp.zeros((batch, max_len, self.rope_dim), dtype))

    @nn.compact
    def __call__(self, h, cache=None, position=None):
        B, L, _ = h.shape
        H, nope, rope, vd = (self.heads, self.nope_dim, self.rope_dim,
                             self.v_dim)

        def proj(name, features):
            return LoRADense(features, rank=self.lora_rank,
                             alpha=self.lora_alpha, use_bias=False,
                             dtype=self.dtype, param_dtype=self.param_dtype,
                             name=name)

        def norm(name):
            return nn.RMSNorm(epsilon=self.eps, dtype=self.dtype, name=name)

        with jax.named_scope("mla"):
            c_q = norm("q_a_norm")(proj("q_a_proj", self.q_rank)(h))
            q = proj("q_b_proj", H * (nope + rope))(c_q).reshape(
                B, L, H, nope + rope)
            if self.q_scale != 1.0:
                q = (q.astype(jnp.float32) * self.q_scale).astype(q.dtype)
            kva = proj("kv_a_proj_with_mqa", self.kv_rank + rope)(h)
            c_kv = norm("kv_a_norm")(kva[..., :self.kv_rank])
            if self.kv_scale != 1.0:
                # in float32, rounded once (12 ** 0.5 is 0.13% off in
                # bfloat16: every key and value alike); the cache holds
                # the latent scaled
                c_kv = (c_kv.astype(jnp.float32)
                        * self.kv_scale).astype(c_kv.dtype)
            k_rope = kva[..., None, self.kv_rank:]          # (B, L, 1, rope)
            pos0 = (jnp.zeros((), jnp.int32) if cache is None
                    else jnp.asarray(position, jnp.int32))
            positions = (pos0 + jnp.arange(L)).astype(jnp.float32)
            q = jnp.concatenate(
                [q[..., :nope], self._rotate(q[..., nope:], positions)], -1)
            k_rope = self._rotate(k_rope, positions)
            if cache is not None:
                zero = jnp.zeros((), jnp.int32)
                cc, cr = cache
                cc = jax.lax.dynamic_update_slice(
                    cc, c_kv.astype(cc.dtype), (zero, pos0, zero))
                cr = jax.lax.dynamic_update_slice(
                    cr, k_rope[:, :, 0].astype(cr.dtype), (zero, pos0, zero))
                cache, c_kv, k_rope = (cc, cr), cc, cr[:, :, None]
            S = c_kv.shape[1]                   # keys: L, or the cache's
            kv = proj("kv_b_proj", H * (nope + vd))(c_kv).reshape(
                B, S, H, nope + vd)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_rope.astype(kv.dtype),
                                  (B, S, H, rope))], -1)
            q, k, v = (jnp.swapaxes(t, 1, 2)
                       for t in (q, k, kv[..., nope:]))    # (B, H, ., .)
            scale = self.softmax_scale()
            if cache is not None:
                s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(
                    jnp.float32) * scale
                mask = (jnp.arange(S)[None, :]
                        <= pos0 + jnp.arange(L)[:, None])
                s = jnp.where(mask[None, None], s, jnp.finfo(s.dtype).min)
                out = jnp.einsum("bhqk,bhkd->bhqd",
                                 nn.softmax(s, axis=-1).astype(v.dtype), v)
            elif self.use_flash == "auto":
                from metisfl_tpu.ops import attention
                out = attention(q, k, v, True, scale=scale)
            elif self.use_flash:
                from metisfl_tpu.ops import flash_attention
                out = flash_attention(q, k, v, True, None, None, None, scale)
            else:
                from metisfl_tpu.ops.flash_attention import _dense_attention
                out = _dense_attention(q, k, v, True, scale)
            out = jnp.swapaxes(out, 1, 2).reshape(B, L, H * vd)
            out = nn.Dense(self.dim, use_bias=False, dtype=self.dtype,
                           param_dtype=self.param_dtype, name="o_proj")(out)
        return out if cache is None else (out, cache)


class ExpertShareMLP(nn.Module):
    """A routed-expert FFN that holds ``count`` of the router's
    ``num_experts`` experts, ``first .. first + count``, beside a shared
    expert::

        s = sigmoid(h W_g)                  float32, over ALL the experts
        chosen = top_k(s + b)               b chooses and does not weigh
        g_e = s_e / (sum_chosen s + 1e-20) * routed_scale
        out = shared(h) + sum_{e chosen and held} g_e expert_e(h)

    each expert and the shared one ``W_down(silu(W_gate h) * W_up h)``.
    ``score_func="softmax"`` scores by a softmax over the router's columns
    in the sigmoid's place, and ``norm_topk=False`` leaves the chosen
    scores as they are (``g_e = s_e * routed_scale``). With
    ``zero_experts`` Z > 0 the router is ``num_experts + Z`` wide, the
    columns past ``num_experts`` are zero-computation experts that return
    their input, and the layer adds::

        (sum_{e chosen, e >= num_experts} g_e) * h

    one masked sum of gates times the input, which every chip computes
    for its own tokens (like a shared expert: no weights, no exchange).

    What the experts held elsewhere would add is left out: on one chip the
    layer runs without its exchange, and the partial result goes on. No
    token is dropped and no capacity exists: the held assignments are
    sorted by expert and multiplied group by group
    (:mod:`metisfl_tpu.ops.grouped_matmul`). The held experts are stacked
    on a leading axis (``experts_gate``, ``experts_up``, ``experts_down``)
    that :data:`TRANSFORMER_RULES` shards over ``ep``. The router and ``b``
    (``e_score_correction_bias``) are float32 whatever ``param_dtype`` is.

    Sows, under ``intermediates``, ``moe_local_count`` (the assignments
    that fell on held experts), ``moe_max_group_count`` (the largest
    group) and, where there are zero-computation experts,
    ``moe_zero_count`` (the assignments that fell on them): counters,
    which ``FlaxModelOps`` sums and returns beside the loss
    (``models/ops.py``)."""

    dim: int
    hidden: int
    num_experts: int
    top_k: int
    first: int = 0
    count: int = 0              # 0 = all of them
    shared_hidden: int = 0      # 0 = no shared expert
    routed_scale: float = 1.0
    score_func: str = "sigmoid"     # or "softmax"
    norm_topk: bool = True      # the chosen scores normalised to sum to 1
    zero_experts: int = 0       # router columns past num_experts: identity
    dtype: Any = None
    param_dtype: Any = jnp.float32
    # None: the kernels on a TPU, ``ragged_dot`` elsewhere; True runs the
    # kernels in Pallas's interpreter (CPU tests)
    gmm_interpret: Any = None

    @nn.compact
    def __call__(self, x):
        from metisfl_tpu.ops import grouped_matmul as gm
        B, L, D = x.shape
        E, K = self.num_experts, self.top_k
        G = self.count or E
        width = E + self.zero_experts
        if self.score_func not in ("sigmoid", "softmax"):
            raise ValueError(f"unknown score_func {self.score_func!r}")
        score = nn.sigmoid if self.score_func == "sigmoid" else nn.softmax
        h = x.reshape(B * L, D)
        with jax.named_scope("moe_router"):
            s = score(nn.Dense(
                width, use_bias=False, dtype=jnp.float32,
                precision=jax.lax.Precision.HIGHEST, name="router")(
                    h.astype(jnp.float32)))
            bias = self.param("e_score_correction_bias",
                              nn.initializers.zeros, (width,), jnp.float32)
            _, chosen = jax.lax.top_k(s + bias, K)
            gates = jnp.take_along_axis(s, chosen, axis=-1)
            if self.norm_topk:
                gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
            gates = gates * self.routed_scale
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=0)
        stack = lambda name, a, b: self.param(             # noqa: E731
            name, init, (G, a, b), self.param_dtype)
        w_gate = stack("experts_gate", D, self.hidden)
        w_up = stack("experts_up", D, self.hidden)
        w_down = stack("experts_down", self.hidden, D)
        dt = self.dtype or h.dtype
        # scopes moe_dispatch, moe_experts, moe_combine inside; a choice
        # outside the held range (a zero-computation one too) falls through
        out, sizes = gm.routed_experts(
            h.astype(dt), chosen, gates, w_gate.astype(dt), w_up.astype(dt),
            w_down.astype(dt), first=self.first, num_experts=width,
            interpret=self.gmm_interpret)
        self.sow("intermediates", "moe_local_count",
                 jnp.sum(sizes).astype(jnp.float32))
        self.sow("intermediates", "moe_max_group_count",
                 jnp.max(sizes).astype(jnp.float32))
        if self.zero_experts:
            with jax.named_scope("moe_zero"):
                zero = chosen >= E
                out = out + (jnp.sum(jnp.where(zero, gates, 0.0), -1,
                                     keepdims=True) * h).astype(out.dtype)
                self.sow("intermediates", "moe_zero_count",
                         jnp.sum(zero).astype(jnp.float32))
        if self.shared_hidden:
            with jax.named_scope("moe_shared"):
                out = out + SwiGLU(D, self.shared_hidden, dtype=self.dtype,
                                   param_dtype=self.param_dtype,
                                   name="shared")(h)
        return out.reshape(B, L, D)


class GeluMLP(nn.Module):
    dim: int
    hidden: int
    dropout: float = 0.0
    dtype: Any = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.gelu(nn.Dense(self.hidden, dtype=self.dtype, name="fc1")(x))
        x = nn.Dropout(self.dropout, deterministic=not train)(x)
        return nn.Dense(self.dim, dtype=self.dtype, name="fc2")(x)


class EncoderBlock(nn.Module):
    """Pre-LN encoder block (ViT/BERT style)."""

    dim: int
    heads: int
    mlp_ratio: int = 4
    dropout: float = 0.0
    use_flash: bool = False
    dtype: Any = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x + Attention(self.dim, self.heads, dropout=self.dropout,
                          use_flash=self.use_flash, dtype=self.dtype,
                          name="attn")(
            nn.LayerNorm(dtype=self.dtype)(x), train=train)
        x = x + GeluMLP(self.dim, self.mlp_ratio * self.dim, self.dropout,
                        dtype=self.dtype, name="mlp")(
            nn.LayerNorm(dtype=self.dtype)(x), train=train)
        return x


def _dt_bias_init(key, shape, dtype=jnp.float32, lo: float = 1e-3,
                  hi: float = 1e-1):
    """The family's seeding of ``dt_proj``'s bias: softplus(bias) is
    log-uniform in [lo, hi], so a fresh recurrence neither forgets at once
    nor never."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * float(np.log(hi / lo)) + float(np.log(lo)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class MambaMixer(nn.Module):
    """Mamba-1 mixer as the Jamba family's modelling code has it::

        [x, z] = in_proj(u)                        d -> 2 d_inner, no bias
        x = silu(conv1d_causal_depthwise(x))       width d_conv, with bias
        [dt, B, C] = x_proj(x)                     d_inner -> R + 2 N
        dt, B, C = RMSNorm(dt), RMSNorm(B), RMSNorm(C)
        delta = softplus(dt_proj(dt))              R -> d_inner, with bias
        S_t = exp(delta_t (x) A) . S_{t-1} + (delta_t . x_t) (x) B_t
        y_t = S_t . C_t + D . x_t,   A = -exp(A_log)
        out = out_proj(y . silu(z))                d_inner -> d, no bias

    ``in_proj`` and ``out_proj`` compute in ``dtype`` (the MXU's); the
    convolution, ``x_proj``, the three norms, ``dt_proj``, ``delta``,
    ``exp(delta A)``, the state and the gate are float32 whatever ``dtype``
    is: the recurrence compounds its rounding over the whole sequence. ``lora_rank`` puts adapters on ``in_proj``
    and ``out_proj``.

    ``cache`` is ``(conv_state (B, d_inner, d_conv - 1), ssm_state (B,
    d_inner, N))``, float32: the last inputs of the convolution and the
    recurrence's state after the positions seen so far. A call at
    ``position`` 0 starts from zero state whatever the cache held (a slot
    reused after another occupant needs no cleanup), a call of one token is
    one step of the recurrence, and either returns the cache after its last
    position."""

    dim: int
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0            # 0 -> ceil(dim / 16), the family's default
    eps: float = 1e-6
    lora_rank: int = 0
    lora_alpha: float = 16.0
    dtype: Any = None
    # the scan routes on the backend and the length (ops.selective_scan);
    # True runs its kernels in Pallas's interpreter instead (CPU tests)
    scan_interpret: bool = False

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    def init_cache(self, batch: int):
        return (jnp.zeros((batch, self.d_inner, self.d_conv - 1),
                          jnp.float32),
                jnp.zeros((batch, self.d_inner, self.d_state), jnp.float32))

    @nn.compact
    def __call__(self, u, cache=None, position=None):
        from metisfl_tpu.ops import selective_scan as scan_ops
        f32 = jnp.float32
        B, L, _ = u.shape
        d_inner, N, K = self.d_inner, self.d_state, self.d_conv
        R = self.dt_rank or -(-self.dim // 16)
        dt_ = self.dtype or f32

        xz = LoRADense(2 * d_inner, rank=self.lora_rank,
                       alpha=self.lora_alpha, use_bias=False,
                       dtype=self.dtype, name="in_proj")(u)
        x, z = xz[..., :d_inner].astype(f32), xz[..., d_inner:].astype(f32)

        conv_w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                            (K, d_inner))
        conv_b = self.param("conv_bias", nn.initializers.zeros, (d_inner,))
        if cache is None:
            past = jnp.zeros((B, K - 1, d_inner), f32)
        else:
            # a call at position 0 opens a sequence: nothing came before
            live = (jnp.asarray(position, jnp.int32) != 0).astype(f32)
            past = jnp.swapaxes(cache[0], 1, 2) * live
            state = cache[1] * live
        window = jnp.concatenate([past, x], axis=1)       # (B, K-1+L, D)
        x = conv_b + sum(window[:, k:k + L] * conv_w[k] for k in range(K))
        x = nn.silu(x)

        # the two thin projections (0.4% of a block's FLOPs) stay float32
        # at ``highest``: their outputs steer a recurrence that compounds
        # their rounding. With bfloat16 operands the round's worst-leaf
        # ``change_gap`` read 0.00113 and 0.00238 on two seeds, in float32
        # 0.00069 and 0.00164 (PERF.md section 6, PR 27)
        thin = dict(dtype=f32, precision=jax.lax.Precision.HIGHEST)
        dbc = nn.Dense(R + 2 * N, use_bias=False, name="x_proj", **thin)(x)
        norm = lambda v, name: nn.RMSNorm(                   # noqa: E731
            epsilon=self.eps, dtype=f32, name=name)(v)
        dt = norm(dbc[..., :R], "dt_norm")
        b = norm(dbc[..., R:R + N], "b_norm")
        c = norm(dbc[..., R + N:], "c_norm")
        delta = jax.nn.softplus(nn.Dense(
            d_inner, bias_init=_dt_bias_init, name="dt_proj", **thin)(dt))
        a = -jnp.exp(self.param(
            "A_log", lambda key, shape: jnp.log(jnp.broadcast_to(
                jnp.arange(1, N + 1, dtype=f32), shape)), (d_inner, N)))
        skip = self.param("D", nn.initializers.ones, (d_inner,))

        if cache is None:
            y = scan_ops.selective_scan(x, delta, a, b, c,
                                        interpret=self.scan_interpret)
        elif L == 1:
            y, state = scan_ops.scan_step(state, x[:, 0], delta[:, 0], a,
                                          b[:, 0], c[:, 0])
            y = y[:, None]
        else:
            y, state = scan_ops.scan_chunked(x, delta, a, b, c, state=state)
        y = (y + skip * x) * nn.silu(z)
        out = LoRADense(self.dim, rank=self.lora_rank, alpha=self.lora_alpha,
                        use_bias=False, dtype=self.dtype,
                        name="out_proj")(y.astype(dt_))
        if cache is None:
            return out
        return out, (jnp.swapaxes(window[:, L:], 1, 2), state)


class DecoderBlock(nn.Module):
    """Pre-RMSNorm causal block: a mixer (attention, Llama style with
    rotary by default, the Mamba mixer or latent attention) and an FFN
    (SwiGLU, MoE, or the module the model hands in)."""

    dim: int
    heads: int
    mlp_ratio: int = 4
    lora_rank: int = 0
    sp_mesh: object = None
    sp_strategy: str = "ring"
    sp_block_kernels: bool = False
    use_flash: bool = False
    # > 0 replaces the SwiGLU FFN with a Switch MoE of this many experts
    moe_experts: int = 0
    moe_top_k: int = 1          # experts per token (1 = Switch, 2 = GShard)
    dtype: Any = None
    kv_heads: int = 0           # grouped-query attention; 0 = MHA
    ffn_dim: int = 0            # FFN width; 0 = mlp_ratio x dim
    eps: float = 1e-6           # RMSNorm epsilon
    rotary: bool = True         # the attention mixer's position embedding
    # a MambaMixer (unbound, as the model builds it) in the attention
    # mixer's place; flax adopts it under the field's name, "mamba"
    mamba: Any = None
    # likewise a LatentAttention in the attention mixer's place ("mla"),
    # and an FFN module in SwiGLU's or MoEMLP's ("ffn")
    mla: Any = None
    ffn: Any = None

    @nn.compact
    def __call__(self, x, train: bool = False, cache=None, position=None):
        normed = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype)(x)
        if self.mamba is not None:
            with jax.named_scope("mamba_mixer"):
                a = (self.mamba(normed) if cache is None
                     else self.mamba(normed, cache=cache, position=position))
        elif self.mla is not None:
            with jax.named_scope("attention_mixer"):
                a = (self.mla(normed) if cache is None
                     else self.mla(normed, cache=cache, position=position))
        else:
            attn = Attention(self.dim, self.heads, causal=True,
                             rotary=self.rotary,
                             lora_rank=self.lora_rank, sp_mesh=self.sp_mesh,
                             sp_strategy=self.sp_strategy,
                             sp_block_kernels=self.sp_block_kernels,
                             use_flash=self.use_flash, dtype=self.dtype,
                             kv_heads=self.kv_heads,
                             name="attn")
            with jax.named_scope("attention_mixer"):
                a = (attn(normed, train=train) if cache is None
                     else attn(normed, train=train, cache=cache,
                               position=position))
        if cache is not None:
            a, cache = a
        x = x + a
        hidden = self.ffn_dim or self.mlp_ratio * self.dim
        if self.ffn is not None:
            ffn = self.ffn
        elif self.moe_experts > 0:
            ffn = MoEMLP(self.dim, hidden,
                         num_experts=self.moe_experts, top_k=self.moe_top_k,
                         dtype=self.dtype, name="moe")
        else:
            ffn = SwiGLU(self.dim, hidden, dtype=self.dtype, name="mlp")
        with jax.named_scope("mlp"):
            x = x + ffn(nn.RMSNorm(epsilon=self.eps, dtype=self.dtype)(x))
        return x if cache is None else (x, cache)


class ViTLite(nn.Module):
    """Patch-embedding vision transformer classifier (ViT ladder config;
    default sizes give a fast CI-scale model — scale dim/depth/heads up for
    the ViT-B/16 configuration: dim=768, depth=12, heads=12, patch=16)."""

    num_classes: int = 10
    dim: int = 64
    depth: int = 4
    heads: int = 4
    patch: int = 4
    dropout: float = 0.0
    dtype: Any = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        if x.ndim == 3:
            x = x[..., None]
        x = nn.Conv(self.dim, (self.patch,) * 2, strides=(self.patch,) * 2,
                    dtype=self.dtype, name="patch_embed")(x)
        x = x.reshape(x.shape[0], -1, self.dim)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (1, x.shape[1], self.dim))
        x = x + pos.astype(x.dtype)
        for i in range(self.depth):
            x = EncoderBlock(self.dim, self.heads, dropout=self.dropout,
                             dtype=self.dtype, name=f"block_{i}")(
                x, train=train)
        x = nn.LayerNorm(dtype=self.dtype)(x).mean(axis=1)
        return nn.Dense(self.num_classes, name="head")(x)


class BertLite(nn.Module):
    """Bidirectional text-encoder classifier (BERT ladder config)."""

    vocab_size: int = 8192
    num_classes: int = 2
    dim: int = 64
    depth: int = 4
    heads: int = 4
    max_len: int = 512
    dropout: float = 0.0
    dtype: Any = None

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        L = tokens.shape[1]
        if L > self.max_len:
            raise ValueError(f"sequence length {L} exceeds max_len "
                             f"{self.max_len}")
        x = nn.Embed(self.vocab_size, self.dim, dtype=self.dtype,
                     name="embed")(tokens)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (1, self.max_len, self.dim))
        x = x + pos[:, :L].astype(x.dtype)
        for i in range(self.depth):
            x = EncoderBlock(self.dim, self.heads, dropout=self.dropout,
                             dtype=self.dtype, name=f"block_{i}")(
                x, train=train)
        x = nn.LayerNorm(dtype=self.dtype)(x).mean(axis=1)
        return nn.Dense(self.num_classes, name="head")(x)


class LlamaLite(nn.Module):
    """Decoder-only causal LM (RMSNorm + rotary + SwiGLU), the Llama-LoRA
    ladder shape. ``lora_rank > 0`` adds adapters on q/v; train with
    ``FlaxModelOps(trainable_regex="lora_")`` to freeze the base."""

    vocab_size: int = 8192
    dim: int = 64
    depth: int = 4
    heads: int = 4
    lora_rank: int = 0
    # sequence parallelism: a Mesh with an "sp" axis routes every block's
    # attention through the chosen schedule (long-context configs) —
    # sp_strategy "ring" (ppermute rotation) or "ulysses" (all-to-all
    # head scatter); sp_block_kernels runs each ring hop on the pallas
    # flash kernels
    sp_mesh: object = None
    sp_strategy: str = "ring"
    sp_block_kernels: bool = False
    # single-chip pallas flash-attention kernel (ops/flash_attention.py)
    use_flash: bool = False
    # expert parallelism: > 0 gives every block a MoE FFN of this many
    # experts (weights shardable over the mesh's "ep" axis); moe_top_k
    # routes each token to that many experts (1 = Switch, 2 = GShard)
    moe_experts: int = 0
    moe_top_k: int = 1
    # rematerialize each block's activations in the backward pass
    # (jax.checkpoint): trades ~1/3 more FLOPs for O(depth) less activation
    # HBM — the lever that fits bigger batches/sequences on one chip
    remat: bool = False
    # computation dtype; jnp.bfloat16 is the MXU-native mixed-precision mode
    # (params stay fp32, activations/matmuls run bf16; loss/logits fp32)
    dtype: Any = None
    # grouped-query attention (Llama-3 style): K/V heads; 0 = heads (MHA)
    kv_heads: int = 0

    @nn.compact
    def __call__(self, tokens, train: bool = False, caches=None,
                 position=None):
        x = nn.Embed(self.vocab_size, self.dim, dtype=self.dtype,
                     name="embed")(tokens)
        # decode mode never wraps in remat (inference has no backward pass)
        block_cls = (nn.remat(DecoderBlock, static_argnums=(2,))
                     if self.remat and caches is None else DecoderBlock)
        new_caches = []
        for i in range(self.depth):
            block = block_cls(self.dim, self.heads,
                              lora_rank=self.lora_rank,
                              sp_mesh=self.sp_mesh,
                              sp_strategy=self.sp_strategy,
                              sp_block_kernels=self.sp_block_kernels,
                              use_flash=self.use_flash,
                              moe_experts=self.moe_experts,
                              moe_top_k=self.moe_top_k,
                              dtype=self.dtype,
                              kv_heads=self.kv_heads,
                              name=f"block_{i}")
            if caches is not None:
                x, c = block(x, train, cache=caches[i], position=position)
                new_caches.append(c)
            else:
                x = block(x, train)
        x = nn.RMSNorm(dtype=self.dtype)(x)
        # logits in fp32: softmax-cross-entropy over a large vocab is
        # precision-sensitive, and this final cast is cheap
        logits = nn.Dense(self.vocab_size, use_bias=False,
                          name="lm_head")(x.astype(jnp.float32))
        return logits if caches is None else (logits, tuple(new_caches))

    def init_cache(self, batch: int, max_len: int):
        """Zeroed decode state, one entry a block: ``(K, V)``, each
        (batch, kv_heads, max_len, head_dim) in the compute dtype."""
        return tuple(_kv_cache(batch, self.kv_heads or self.heads, max_len,
                               self.dim // self.heads, self.dtype)
                     for _ in range(self.depth))

    def cache_kinds(self):
        """What each block's cache entry is: ``"kv"`` (grows with the
        sequence, masked past the frontier) or ``"state"`` (fixed size,
        overwritten every position)."""
        return ("kv",) * self.depth


def _kv_cache(batch: int, kv_heads: int, max_len: int, head_dim: int, dtype):
    shape = (batch, kv_heads, max_len, head_dim)
    dtype = dtype or jnp.float32
    return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


class JambaLite(nn.Module):
    """Decoder-only hybrid of the Jamba family: block ``l`` mixes by
    attention where ``(l - attn_offset) % attn_period == 0`` and by the
    Mamba mixer elsewhere; every block has a dense SwiGLU FFN (the
    family's one-expert case). Attention is causal GQA/MQA with no rotary
    and no position embedding of any kind (the recurrence carries order);
    the head is tied to the embedding. ``lora_rank > 0`` adds adapters on
    ``in_proj``/``out_proj`` (Mamba) and ``wq``/``wv`` (attention); train
    with ``FlaxModelOps(trainable_regex="lora_")`` to freeze the base.

    Decoding: ``init_cache`` gives ``(K, V)`` for an attention block and
    ``(conv_state, ssm_state)`` for a Mamba block; ``models.generate`` and
    ``serving.decode`` carry either as a pytree."""

    vocab_size: int = 8192
    dim: int = 64
    depth: int = 4
    heads: int = 4
    kv_heads: int = 0
    ffn_dim: int = 0            # 0 = 4 x dim
    attn_period: int = 2
    attn_offset: int = 1
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0            # 0 = ceil(dim / 16)
    eps: float = 1e-6
    lora_rank: int = 0
    lora_alpha: float = 16.0
    use_flash: Any = False
    remat: bool = False
    dtype: Any = None
    scan_interpret: bool = False    # see MambaMixer

    def is_attention(self, layer: int) -> bool:
        return (layer - self.attn_offset) % self.attn_period == 0

    def _mamba(self) -> MambaMixer:
        return MambaMixer(self.dim, d_state=self.d_state, d_conv=self.d_conv,
                          expand=self.expand, dt_rank=self.dt_rank,
                          eps=self.eps, lora_rank=self.lora_rank,
                          lora_alpha=self.lora_alpha, dtype=self.dtype,
                          scan_interpret=self.scan_interpret,
                          parent=None)      # the block adopts it

    @nn.compact
    def __call__(self, tokens, train: bool = False, caches=None,
                 position=None):
        embed = nn.Embed(self.vocab_size, self.dim, dtype=self.dtype,
                         name="embed")
        x = embed(tokens)
        block_cls = (nn.remat(DecoderBlock, static_argnums=(2,))
                     if self.remat and caches is None else DecoderBlock)
        new_caches = []
        for i in range(self.depth):
            block = block_cls(
                self.dim, self.heads, lora_rank=self.lora_rank,
                use_flash=self.use_flash, dtype=self.dtype,
                kv_heads=self.kv_heads, ffn_dim=self.ffn_dim, eps=self.eps,
                rotary=False,
                mamba=None if self.is_attention(i) else self._mamba(),
                name=f"block_{i}")
            if caches is not None:
                x, c = block(x, train, cache=caches[i], position=position)
                new_caches.append(c)
            else:
                x = block(x, train)
        x = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype)(x)
        # tied head, logits in fp32 as LlamaLite's
        logits = jnp.dot(x.astype(jnp.float32),
                         embed.embedding.astype(jnp.float32).T)
        return logits if caches is None else (logits, tuple(new_caches))

    def init_cache(self, batch: int, max_len: int):
        return tuple(
            _kv_cache(batch, self.kv_heads or self.heads, max_len,
                      self.dim // self.heads, self.dtype)
            if self.is_attention(i) else self._mamba().init_cache(batch)
            for i in range(self.depth))

    def cache_kinds(self):
        return tuple("kv" if self.is_attention(i) else "state"
                     for i in range(self.depth))


class MlaMoeLite(nn.Module):
    """Decoder-only causal LM with latent attention
    (:class:`LatentAttention`) in every block, a dense SwiGLU FFN in the
    first ``first_dense`` blocks and a routed-expert FFN that holds a share
    of the experts (:class:`ExpertShareMLP`) in the rest; pre-RMSNorm, an
    untied head with float32 logits. ``lora_rank > 0`` adds adapters on the
    four latent projections; train with
    ``FlaxModelOps(trainable_regex="lora_")`` to freeze the base.
    ``param_dtype`` is the type of the frozen matrices and the embedding
    (norm scales, the router, the head and the adapters stay float32).

    Decoding: ``init_cache`` gives a block's latent cache ``(c_kv,
    k_rope)``, kind ``"kv"``."""

    vocab_size: int = 8192
    dim: int = 64
    depth: int = 3
    heads: int = 4
    q_rank: int = 16
    kv_rank: int = 8
    nope_dim: int = 16
    rope_dim: int = 8
    v_dim: int = 16
    ffn_dim: int = 0            # the dense blocks' width; 0 = 4 x dim
    first_dense: int = 1
    moe_hidden: int = 32
    num_experts: int = 16
    top_k: int = 4
    experts_first: int = 0
    experts_count: int = 0      # 0 = all of them
    shared_experts: int = 1
    routed_scale: float = 1.0
    rope_base: float = 10000.0
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    eps: float = 1e-6
    lora_rank: int = 0
    lora_alpha: float = 16.0
    use_flash: Any = False
    remat: bool = False
    dtype: Any = None
    param_dtype: Any = jnp.float32
    gmm_interpret: Any = None       # see ExpertShareMLP

    def _mla(self) -> LatentAttention:
        return LatentAttention(
            self.dim, self.heads, self.q_rank, self.kv_rank, self.nope_dim,
            self.rope_dim, self.v_dim, rope_base=self.rope_base,
            rope_factor=self.rope_factor,
            rope_original_max=self.rope_original_max,
            rope_beta_fast=self.rope_beta_fast,
            rope_beta_slow=self.rope_beta_slow,
            rope_mscale=self.rope_mscale,
            rope_mscale_all_dim=self.rope_mscale_all_dim, eps=self.eps,
            lora_rank=self.lora_rank, lora_alpha=self.lora_alpha,
            use_flash=self.use_flash, dtype=self.dtype,
            param_dtype=self.param_dtype, parent=None)  # the block adopts it

    def _ffn(self, layer: int):
        if layer < self.first_dense:
            return SwiGLU(self.dim, self.ffn_dim or 4 * self.dim,
                          dtype=self.dtype, param_dtype=self.param_dtype,
                          parent=None)
        return ExpertShareMLP(
            self.dim, self.moe_hidden, self.num_experts, self.top_k,
            first=self.experts_first, count=self.experts_count,
            shared_hidden=self.shared_experts * self.moe_hidden,
            routed_scale=self.routed_scale, dtype=self.dtype,
            param_dtype=self.param_dtype, gmm_interpret=self.gmm_interpret,
            parent=None)

    @nn.compact
    def __call__(self, tokens, train: bool = False, caches=None,
                 position=None):
        x = nn.Embed(self.vocab_size, self.dim, dtype=self.dtype,
                     param_dtype=self.param_dtype, name="embed")(tokens)
        block_cls = (nn.remat(DecoderBlock, static_argnums=(2,))
                     if self.remat and caches is None else DecoderBlock)
        new_caches = []
        for i in range(self.depth):
            block = block_cls(self.dim, self.heads, dtype=self.dtype,
                              eps=self.eps, mla=self._mla(),
                              ffn=self._ffn(i), name=f"block_{i}")
            if caches is not None:
                x, c = block(x, train, cache=caches[i], position=position)
                new_caches.append(c)
            else:
                x = block(x, train)
        x = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype)(x)
        logits = nn.Dense(self.vocab_size, use_bias=False,
                          name="lm_head")(x.astype(jnp.float32))
        return logits if caches is None else (logits, tuple(new_caches))

    def init_cache(self, batch: int, max_len: int):
        return tuple(self._mla().init_cache(batch, max_len)
                     for _ in range(self.depth))

    def cache_kinds(self):
        return ("kv",) * self.depth


class ShortcutMoEBlock(nn.Module):
    """One shortcut-connected layer: two latent-attention sublayers and two
    dense FFNs on the residual line, and one routed layer that reads the
    first sublayer's normed state and joins at the end of the second::

        x1 = x  + MLA_0(norm_in0(x));     u = norm_post0(x1)
        m  = MoE(u)                       the shortcut
        x2 = x1 + SwiGLU_0(u)
        x3 = x2 + MLA_1(norm_in1(x2));    w = norm_post1(x3)
        y  = x3 + SwiGLU_1(w) + m

    Every norm is an RMSNorm with its own scale. Nothing orders the routed
    layer against the dense work between its call and its use: the
    compiler may place it beside them. ``mla_0``, ``mla_1`` and ``moe`` are
    the modules the model builds (unbound; flax adopts them under the
    fields' names); ``cache`` is the two sublayers' latent caches."""

    dim: int
    ffn_dim: int
    mla_0: Any
    mla_1: Any
    moe: Any
    eps: float = 1e-6
    dtype: Any = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, cache=None, position=None):
        def norm(name):
            return nn.RMSNorm(epsilon=self.eps, dtype=self.dtype, name=name)

        def mixer(mla, h, entry):
            with jax.named_scope("attention_mixer"):
                if entry is None:
                    return mla(h), None
                return mla(h, cache=entry, position=position)

        def mlp(name, h):
            with jax.named_scope("mlp"):
                return SwiGLU(self.dim, self.ffn_dim, dtype=self.dtype,
                              param_dtype=self.param_dtype, name=name)(h)

        c0, c1 = (None, None) if cache is None else cache
        a0, c0 = mixer(self.mla_0, norm("input_norm_0")(x), c0)
        x = x + a0
        u = norm("post_norm_0")(x)
        with jax.named_scope("moe_shortcut"):
            m = self.moe(u)
        x = x + mlp("mlp_0", u)
        a1, c1 = mixer(self.mla_1, norm("input_norm_1")(x), c1)
        x = x + a1
        x = x + mlp("mlp_1", norm("post_norm_1")(x)) + m
        return x if cache is None else (x, (c0, c1))


class ScMoeLite(nn.Module):
    """Decoder-only causal LM of shortcut-connected layers
    (:class:`ShortcutMoEBlock`): latent attention
    (:class:`LatentAttention`, with its two latent scales ``(dim / rank) **
    0.5`` where ``scale_q_lora`` / ``scale_kv_lora`` say so, plain rotary)
    twice a layer, a dense SwiGLU twice, and a routed layer that holds a
    share of the experts (:class:`ExpertShareMLP`: softmax scores over
    ``num_experts + zero_experts`` columns, the chosen gates not
    normalised, no shared expert); an untied head with float32 logits.
    ``lora_rank > 0`` adds adapters on the four latent projections of both
    sublayers; train with ``FlaxModelOps(trainable_regex="lora_")`` to
    freeze the base. ``param_dtype`` is the type of the frozen matrices and
    the embedding (norm scales, the router, its bias, the head and the
    adapters stay float32).

    Decoding: ``init_cache`` gives a layer's two latent caches ``((c_kv,
    k_rope), (c_kv, k_rope))``, kind ``"kv"``."""

    vocab_size: int = 8192
    dim: int = 64
    depth: int = 2
    heads: int = 4
    q_rank: int = 16
    kv_rank: int = 8
    nope_dim: int = 16
    rope_dim: int = 8
    v_dim: int = 16
    ffn_dim: int = 0            # the dense FFNs' width; 0 = 2 x dim
    moe_hidden: int = 32
    num_experts: int = 16
    zero_experts: int = 8
    top_k: int = 4
    experts_first: int = 0
    experts_count: int = 0      # 0 = all of them
    routed_scale: float = 1.0
    scale_q_lora: bool = True
    scale_kv_lora: bool = True
    rope_base: float = 10000.0
    eps: float = 1e-5
    lora_rank: int = 0
    lora_alpha: float = 16.0
    use_flash: Any = False
    remat: bool = False
    dtype: Any = None
    param_dtype: Any = jnp.float32
    gmm_interpret: Any = None       # see ExpertShareMLP

    def _mla(self) -> LatentAttention:
        return LatentAttention(
            self.dim, self.heads, self.q_rank, self.kv_rank, self.nope_dim,
            self.rope_dim, self.v_dim, rope_base=self.rope_base,
            q_scale=((self.dim / self.q_rank) ** 0.5
                     if self.scale_q_lora else 1.0),
            kv_scale=((self.dim / self.kv_rank) ** 0.5
                      if self.scale_kv_lora else 1.0),
            eps=self.eps, lora_rank=self.lora_rank,
            lora_alpha=self.lora_alpha, use_flash=self.use_flash,
            dtype=self.dtype, param_dtype=self.param_dtype,
            parent=None)                        # the block adopts it

    def _moe(self) -> ExpertShareMLP:
        return ExpertShareMLP(
            self.dim, self.moe_hidden, self.num_experts, self.top_k,
            first=self.experts_first, count=self.experts_count,
            routed_scale=self.routed_scale, score_func="softmax",
            norm_topk=False, zero_experts=self.zero_experts,
            dtype=self.dtype, param_dtype=self.param_dtype,
            gmm_interpret=self.gmm_interpret, parent=None)

    @nn.compact
    def __call__(self, tokens, train: bool = False, caches=None,
                 position=None):
        del train                               # no dropout anywhere
        x = nn.Embed(self.vocab_size, self.dim, dtype=self.dtype,
                     param_dtype=self.param_dtype, name="embed")(tokens)
        block_cls = (nn.remat(ShortcutMoEBlock)
                     if self.remat and caches is None else ShortcutMoEBlock)
        new_caches = []
        for i in range(self.depth):
            block = block_cls(self.dim, self.ffn_dim or 2 * self.dim,
                              self._mla(), self._mla(), self._moe(),
                              eps=self.eps, dtype=self.dtype,
                              param_dtype=self.param_dtype,
                              name=f"block_{i}")
            if caches is not None:
                x, c = block(x, cache=caches[i], position=position)
                new_caches.append(c)
            else:
                x = block(x)
        x = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype)(x)
        logits = nn.Dense(self.vocab_size, use_bias=False,
                          name="lm_head")(x.astype(jnp.float32))
        return logits if caches is None else (logits, tuple(new_caches))

    def init_cache(self, batch: int, max_len: int):
        one = lambda: self._mla().init_cache(batch, max_len)   # noqa: E731
        return tuple((one(), one()) for _ in range(self.depth))

    def cache_kinds(self):
        return ("kv",) * self.depth


@jax.custom_batching.custom_vmap
def _write_kv(cache, new, position):
    """``new`` (B, kv_heads, L, head_dim) into ``cache`` (B, kv_heads,
    L_max, head_dim) from ``position`` on: one ``dynamic_update_slice``.

    Batched over decode slots (``SlotDecoder.step`` vmaps the module over
    a slot-major cache, each slot at its own position) the same values are
    written by one ``dynamic_update_slice`` a slot on the slot-major array
    (``_write_kv_slots``), not by the scatter ``vmap`` makes of a batched
    start index. The chip's compiler expands that scatter into a loop over
    a copy of the whole leaf staged in fast memory and writes the leaf back
    whole (33.5 MB a leaf, 1.07 GB a step at 8 slots of 2048) although the
    output is the donated input; the unrolled writes stay in the buffer
    that is there, 2 KB a slot (PERF.md section 6, PR 36)."""
    zero = jnp.zeros((), position.dtype)      # index dtypes must all match
    return jax.lax.dynamic_update_slice(cache, new,
                                        (zero, zero, position, zero))


@_write_kv.def_vmap
def _write_kv_slots(slots, batched, cache, new, position):
    if not all(batched):
        # some other batching than a decoder's slots: the stock rule
        axes = tuple(0 if b else None for b in batched)
        return jax.vmap(_write_kv.fun, in_axes=axes)(cache, new,
                                                     position), True
    zero = jnp.zeros((), position.dtype)
    for slot in range(slots):
        cache = jax.lax.dynamic_update_slice(
            cache, new[slot:slot + 1],
            (jnp.asarray(slot, position.dtype), zero, zero, position[slot],
             zero))
    return cache, True
