#!/usr/bin/env python
"""Federation performance harness — prints ONE JSON line + a marker.

TPU-native counterpart of the reference's scenario benchmark
(reference metisfl/controller/scenarios/sync_model_aggregation_performance_main.cc:13-87
+ scenarios_common.cc: N synthetic learners x T tensors x V values, timing the
aggregation hot loop and RSS).

Headline metric (BASELINE.md north star): federation aggregation wall-clock
per round at 64 learners, target <= 2000 ms. ``vs_baseline`` is the speedup
against that target (>1 means beating it). Secondary metrics: learner
training throughput, causal-LM MFU on an MXU-sized transformer (bf16),
pallas flash-attention vs dense timings, CKKS secure-aggregation wall-clock,
and model-store scale (64 learners x 1.6M params + 26 MB ciphertexts).

Full mode runs every section in its own child process, headline first
(aggregation @64, LM MFU), each child streaming partial JSON so a section
killed at its timeout keeps what it measured. A device section's child is
the one process that owns the chip while it runs; this parent never
initializes a backend. Device sections measure the TPU or fail: there is
no CPU fallback, every section failure lands in ``details.errors``, and
any such entry makes the exit code non-zero. ``--quick`` is the
in-process CPU plumbing check the tests use; its output says
``platform: cpu``. Host sections whose ms keys land under the repeat
threshold are re-measured median-of-K (``METISFL_BENCH_REPEATS`` /
``METISFL_BENCH_REPEAT_MS``) so the 20% regression gate judges medians,
not single shots, on noisy hosts.
"""

from __future__ import annotations

import json
import os
import platform as platform_mod
import resource
import statistics
import subprocess
import sys
import time
import traceback

import numpy as np

BASELINE_MS = 2000.0          # <= 2 s aggregation/round @ 64 learners
NUM_LEARNERS = 64
ROUNDS = 5
STRIDE = 8

# CIFAR-10-CNN-scale synthetic model (~1.64M params), the same workload the
# reference's anecdote measures (controller.cc:594-604 — 1.6M-param model).
MODEL_SHAPES = {
    "conv1/kernel": (3, 3, 3, 32), "conv1/bias": (32,),
    "conv2/kernel": (3, 3, 32, 64), "conv2/bias": (64,),
    "conv3/kernel": (3, 3, 64, 128), "conv3/bias": (128,),
    "dense1/kernel": (2048, 512), "dense1/bias": (512,),
    "dense2/kernel": (512, 512), "dense2/bias": (512,),
    "head/kernel": (512, 10), "head/bias": (10,),
}

# bf16 peak FLOP/s per chip: ONE table, shared with the performance
# observatory's learner MFU gauge (telemetry/profile.py, jax-free import)
# so bench MFU and learner_achieved_mfu can never silently diverge.
from metisfl_tpu.telemetry.profile import (  # noqa: E402
    device_peak_flops as _chip_peak_flops,
)


def synth_models(num_learners: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    models = []
    for _ in range(num_learners):
        models.append({name: rng.standard_normal(shape).astype(np.float32)
                       for name, shape in MODEL_SHAPES.items()})
    return models


def aggregate_once(agg, models, scales, stride: int):
    """The controller's stride-blocked fold (controller/core.py
    _compute_community_model): one block resident at a time."""
    agg.reset()
    for i in range(0, len(models), stride):
        block = [([models[j]], scales[j])
                 for j in range(i, min(i + stride, len(models)))]
        agg.accumulate(block)
    out = agg.result()
    agg.reset()
    return out


def bench_aggregation(num_learners: int, rounds: int, stride: int):
    import jax
    from metisfl_tpu.aggregation.fedavg import FedAvg

    models = synth_models(num_learners)
    scales = np.full((num_learners,), 1.0 / num_learners, np.float64)
    params = sum(int(np.prod(s)) for s in MODEL_SHAPES.values())

    agg = FedAvg()
    # warm-up (host path needs none, but keeps timings honest)
    out = aggregate_once(agg, models, scales, stride)
    jax.block_until_ready(jax.tree.leaves(out))

    times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        out = aggregate_once(agg, models, scales, stride)
        jax.block_until_ready(jax.tree.leaves(out))
        times.append((time.perf_counter() - t0) * 1e3)

    # device-resident variant: models already live on the chip (co-located
    # learner output / pod mode) — the fold runs as fused stacked reduces
    import jax.numpy as jnp
    dev_models = jax.block_until_ready(
        [jax.tree.map(jnp.asarray, m) for m in models])
    jax.block_until_ready(jax.tree.leaves(
        aggregate_once(agg, dev_models, scales, stride)))  # compile
    dev_times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        out_dev = aggregate_once(agg, dev_models, scales, stride)
        jax.block_until_ready(jax.tree.leaves(out_dev))
        dev_times.append((time.perf_counter() - t0) * 1e3)

    # full-fuse: all N models in ONE stacked weighted reduce (stride =
    # N ⇒ a single dispatched program — the stride-blocked number above
    # pays N/stride dispatches purely for the memory bounding that
    # device-resident plaintext models do not need). Guarded: an HBM OOM
    # stacking N models must not forfeit the headline numbers already
    # measured, and at stride >= N it would duplicate the run above.
    fuse_times: list = []
    if stride < num_learners:
        try:
            jax.block_until_ready(jax.tree.leaves(
                aggregate_once(agg, dev_models, scales, num_learners)))
            for _ in range(rounds):
                t0 = time.perf_counter()
                out_dev = aggregate_once(agg, dev_models, scales,
                                         num_learners)
                jax.block_until_ready(jax.tree.leaves(out_dev))
                fuse_times.append((time.perf_counter() - t0) * 1e3)
        except Exception:
            fuse_times = []

    # correctness guard: community == mean of the synthetic models
    expect = np.mean([m["head/bias"] for m in models], axis=0)
    np.testing.assert_allclose(np.asarray(out["head/bias"]), expect, atol=1e-4)

    return {
        "ms_per_round_median": float(np.median(times)),
        "ms_per_round_min": float(np.min(times)),
        "ms_per_round_all": [round(t, 2) for t in times],
        "ms_per_round_device_resident": float(np.median(dev_times)),
        **({"ms_per_round_device_fullfuse": float(np.median(fuse_times))}
           if fuse_times else {}),
        "params_per_model": params,
        "num_learners": num_learners,
        "stride": stride,
    }


def bench_train_step():
    """Learner local-training throughput (samples/sec/chip) on the
    FashionMNIST CNN — the reference ladder's first rung."""
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.models.dataset import ArrayDataset
    from metisfl_tpu.models.ops import FlaxModelOps
    from metisfl_tpu.models.zoo import FashionMnistCNN

    rng = np.random.default_rng(1)
    batch = 256
    x = rng.standard_normal((batch * 8, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(batch * 8,))
    ops = FlaxModelOps(FashionMnistCNN(), x[:2])
    # scan_chunk=4: 3 fused chunks, the first compiles, the rest time the
    # chip rather than per-step host dispatch
    out = ops.train(ArrayDataset(x, y),
                    TrainParams(batch_size=batch, local_steps=12,
                                scan_chunk=4,
                                optimizer="sgd", learning_rate=0.01))
    if out.ms_per_step <= 0:
        return {}
    return {
        "train_samples_per_sec": round(batch / (out.ms_per_step / 1e3)),
        "train_ms_per_step": round(out.ms_per_step, 2),
        "train_batch_size": batch,
    }


def _lm_step_flops(B, L, dim, depth, vocab) -> int:
    """MODEL FLOPs per training step (2*M*N*K per matmul; backward = 2x
    forward; causal attention counted at half the full L x L matmuls).
    One accounting for every variant: a dense kernel that executes the
    masked half anyway eats that as lower MFU, and remat recompute is
    overhead, not credited work — so MFU ranks variants exactly like
    tokens/sec."""
    tokens = B * L
    per_layer = (8 * tokens * dim * dim            # wq/wk/wv/wo
                 + 2 * B * L * L * dim             # causal scores + PV
                 + 24 * tokens * dim * dim)        # SwiGLU (hidden = 4*dim)
    fwd = depth * per_layer + 2 * tokens * dim * vocab
    return 3 * fwd


# MFU sweep variants. Scan variants fuse 8 optimizer steps into one
# lax.scan program (TrainParams.scan_chunk), taking per-step host dispatch
# out of the timing. The per-variant children run in this order: the
# cheapest-to-compile variant first, the strongest MFU candidate (largest
# batch, scan-fused) second, so a sweep cut short at its section budget
# has measured those.
_MFU_VARIANTS = [
    ("b8_dense", dict(B=8, flash=False, remat=False)),
    ("b32_dense_remat_scan8", dict(B=32, flash=False, remat=True, scan=8)),
    ("b8_dense_scan8", dict(B=8, flash=False, remat=False, scan=8)),
    ("b8_flash_scan8", dict(B=8, flash=True, remat=False, scan=8)),
    ("b16_flash_remat_scan8", dict(B=16, flash=True, remat=True, scan=8)),
    # seq-length-routed attention (ops/flash_attention.attention):
    # dense below FLASH_MIN_SEQ, the pallas kernel above — the default
    # a user should pick
    ("b16_auto_remat_scan8", dict(B=16, flash="auto", remat=True, scan=8)),
]


def _mfu_finalize(out: dict, L=1024, dim=1024, depth=8, vocab=32768) -> None:
    """Compute the best-variant rollup (lm_best_*, mfu) from per-variant
    fields already in ``out``. Separated from bench_mfu so the parent can
    recompute it after merging per-variant child results."""
    best = None
    for label, v in _MFU_VARIANTS:
        ms = out.get(f"lm_{label}_ms_per_step")
        if not ms:
            continue
        flops = _lm_step_flops(v["B"], L, dim, depth, vocab)
        tps = out.get(f"lm_{label}_tokens_per_sec", 0)
        if best is None or tps > best[1]:
            best = (label, tps, flops, ms)
    if best is None:
        return
    label, tps, flops, ms = best
    peak = _chip_peak_flops(out["device_kind"])
    out.update({
        "lm_best_variant": label,
        "lm_ms_per_step": round(ms, 2),
        "lm_tokens_per_sec": round(tps),
        "lm_flops_per_step": flops,
        "lm_achieved_tflops": round(flops / (ms / 1e3) / 1e12, 1),
    })
    if peak:
        out["mfu"] = round((flops / (ms / 1e3)) / peak, 4)


def bench_mfu(L=1024, dim=1024, depth=8, heads=16, vocab=32768,
              require_tpu=True, on_update=None, only=None):
    """Causal-LM MFU on an MXU-sized LlamaLite (dim 1024 / depth 8 /
    seq 1024, bf16): a small config sweep (dense/flash attention, batch,
    remat) — each variant individually guarded — reporting every variant's
    step time and the best variant's MFU. This is the perf axis the first
    two rounds never measured (VERDICT r2 #1). The size parameters exist so
    CI can smoke the sweep plumbing at toy shapes off-TPU. ``only`` runs a
    single named variant (the parent runs each variant in its own child,
    so a variant that fails or times out costs itself, not the section)."""
    import jax
    import jax.numpy as jnp

    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.models.dataset import ArrayDataset
    from metisfl_tpu.models.ops import FlaxModelOps
    from metisfl_tpu.models.zoo import LlamaLite

    if require_tpu:
        _require_tpu("mfu")  # MFU against a TPU peak is meaningless elsewhere
    kind = jax.devices()[0].device_kind
    peak = _chip_peak_flops(kind)
    rng = np.random.default_rng(4)

    variants = [(lbl, v) for lbl, v in _MFU_VARIANTS
                if only is None or lbl == only]
    out = {"device_kind": kind,
           "lm_config": f"dim{dim}/depth{depth}/heads{heads}/seq{L}/bf16"}
    if peak:
        out["chip_peak_bf16_tflops"] = round(peak / 1e12)
    for label, v in variants:
        try:
            B = v["B"]
            x = rng.integers(0, vocab, (B * 2, L)).astype(np.int32)
            ds = ArrayDataset(x, np.roll(x, -1, axis=1))
            ops = FlaxModelOps(
                LlamaLite(vocab_size=vocab, dim=dim, depth=depth,
                          heads=heads, use_flash=v["flash"],
                          remat=v["remat"], dtype=jnp.bfloat16), ds.x[:1])
            if "lm_params" not in out:
                out["lm_params"] = sum(int(np.prod(p.shape))
                                       for p in jax.tree.leaves(ops.variables))
            scan = int(v.get("scan", 1))
            # 2 chunks when scanned: the first compiles, the second is the
            # steady-state timing sample
            res = ops.train(ds, TrainParams(
                batch_size=B, local_steps=2 * scan if scan > 1 else 8,
                optimizer="adam", learning_rate=1e-4, scan_chunk=scan))
            if res.ms_per_step <= 0:
                continue
            tokens = B * L
            flops = _lm_step_flops(B, L, dim, depth, vocab)
            tps = tokens / (res.ms_per_step / 1e3)
            out[f"lm_{label}_ms_per_step"] = round(res.ms_per_step, 2)
            out[f"lm_{label}_tokens_per_sec"] = round(tps)
            if peak:
                out[f"lm_{label}_mfu"] = round(
                    (flops / (res.ms_per_step / 1e3)) / peak, 4)
        except Exception:
            out[f"lm_{label}_error"] = traceback.format_exc(limit=2)[-200:]
        if on_update is not None:
            on_update(out)
    if only is None:
        _mfu_finalize(out, L=L, dim=dim, depth=depth, vocab=vocab)
    return out


def bench_flash(seq: int = 2048, reps: int = 8, on_update=None):
    """Pallas flash-attention kernel vs dense XLA attention, fwd and
    fwd+bwd, at seq >= 1024 (VERDICT r2 #5). TPU only — interpret mode is a
    debugging path, far too slow to time.

    Each measurement runs ``reps`` dependency-chained applications INSIDE
    one jit program (lax.scan) and subtracts the single-application time:
    per-op cost = (t_reps - t_1) / (reps - 1), which takes the per-call
    host dispatch out of the op's time."""
    import jax
    import jax.numpy as jnp

    from metisfl_tpu.ops import flash_attention
    from metisfl_tpu.ops.flash_attention import _dense_attention

    _require_tpu("flash")
    B, H, D = 4, 16, 128
    rng = jax.random.PRNGKey(0)
    qkv = [jax.random.normal(jax.random.fold_in(rng, i), (B, H, seq, D),
                             jnp.bfloat16) for i in range(3)]

    def dense(q, k, v):
        return _dense_attention(q, k, v, True)

    def flash(q, k, v):
        return flash_attention(q, k, v, True)

    def chained_fwd(fn, n):
        def run(q, k, v):
            def body(c, _):
                return fn(c, k, v).astype(q.dtype), ()
            out, _ = jax.lax.scan(body, q, None, length=n)
            return out
        return jax.jit(run)

    def chained_fwd_bwd(fn, n):
        def run(q, k, v):
            def body(c, _):
                cq, ck, cv = c
                o, vjp = jax.vjp(fn, cq, ck, cv)
                dq, dk, dv = vjp(o)  # output as cotangent; all three grads
                # feed the carry so none of the backward is DCE'd
                return ((cq + 1e-3 * dq).astype(q.dtype),
                        (ck + 1e-3 * dk).astype(k.dtype),
                        (cv + 1e-3 * dv).astype(v.dtype)), ()
            out, _ = jax.lax.scan(body, (q, k, v), None, length=n)
            return out
        return jax.jit(run)

    def timed(fn, args=None):
        args = qkv if args is None else args
        jax.block_until_ready(fn(*args))         # compile
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append((time.perf_counter() - t0) * 1e3)
        return float(np.median(times))

    reps = max(2, reps)
    out = {"flash_seq": seq, "flash_reps": reps}
    for label, fn in (("flash", flash), ("dense", dense)):
        for tag, chain in (("fwd", chained_fwd), ("fwd_bwd", chained_fwd_bwd)):
            t_many = timed(chain(fn, reps))
            t_one = timed(chain(fn, 1))
            per_op = (t_many - t_one) / (reps - 1)
            out[f"attn_{label}_{tag}_ms"] = round(max(per_op, 0.0), 3)
            # one dispatch + ONE op execution (not dispatch alone)
            out[f"attn_{label}_{tag}_single_call_ms"] = round(t_one, 2)
            if on_update is not None:
                on_update(out)

    # GQA-native flash (4 of 16 KV heads): K/V at quarter size in HBM,
    # index-mapped to query heads inside the kernels
    gqa_args = (qkv[0], qkv[1][:, :4], qkv[2][:, :4])
    t_many = timed(chained_fwd(flash, reps), gqa_args)
    t_one = timed(chained_fwd(flash, 1), gqa_args)
    out["attn_flash_gqa4of16_fwd_ms"] = round(
        max((t_many - t_one) / (reps - 1), 0.0), 3)
    if on_update is not None:
        on_update(out)

    # block-size sweep (VERDICT r3 #1: tune until flash earns its keep or
    # the crossover is known): per-config fwd per-op time + the best
    best_blk = None
    for bq, bk in ((256, 256), (256, 512), (512, 512), (512, 1024),
                   (1024, 512)):
        if bq > seq or bk > seq:
            continue
        try:
            def flash_blk(q, k, v, _bq=bq, _bk=bk):
                return flash_attention(q, k, v, True, _bq, _bk)

            t_many = timed(chained_fwd(flash_blk, reps))
            t_one = timed(chained_fwd(flash_blk, 1))
            per_op = max((t_many - t_one) / (reps - 1), 0.0)
            out[f"attn_flash_blk{bq}x{bk}_fwd_ms"] = round(per_op, 3)
            if best_blk is None or per_op < best_blk[1]:
                best_blk = ((bq, bk), per_op)
        except Exception:
            out[f"attn_flash_blk{bq}x{bk}_error"] = \
                traceback.format_exc(limit=1)[-160:]
        if on_update is not None:
            on_update(out)
    if best_blk is not None:
        out["attn_flash_best_blk"] = f"{best_blk[0][0]}x{best_blk[0][1]}"
        out["attn_flash_best_blk_fwd_ms"] = round(best_blk[1], 3)
        dense_fwd = out.get("attn_dense_fwd_ms")
        if dense_fwd:
            # the routing decision FLASH_MIN_SEQ encodes, re-measured
            out["attn_flash_beats_dense_at_seq"] = bool(
                best_blk[1] < dense_fwd)
    return out


def bench_decode(B=8, prompt_len=128, new_tokens=128, dim=1024, depth=8,
                 heads=16, kv_heads=4, vocab=32768):
    """KV-cache autoregressive decode throughput (models/generate.py) on
    the MXU-sized GQA LlamaLite: tokens/sec and per-token latency for one
    jitted prefill+scan program. TPU only. Decode is HBM-bandwidth-bound;
    GQA's kv_heads/heads shrinks the cache traffic by 4x here."""
    import jax
    import jax.numpy as jnp

    from metisfl_tpu.models.generate import generate
    from metisfl_tpu.models.zoo import LlamaLite

    _require_tpu("decode")
    module = LlamaLite(vocab_size=vocab, dim=dim, depth=depth, heads=heads,
                       kv_heads=kv_heads, dtype=jnp.bfloat16)
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, vocab, (B, prompt_len)).astype(np.int32)
    variables = module.init(jax.random.PRNGKey(0), jnp.asarray(prompt[:1]))

    out = generate(module, variables, prompt, new_tokens)  # compile
    jax.block_until_ready(out)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(generate(module, variables, prompt,
                                       new_tokens))
        times.append(time.perf_counter() - t0)
    sec = float(np.median(times))
    total_new = B * new_tokens
    return {
        "decode_config": (f"dim{dim}/depth{depth}/h{heads}kv{kv_heads}"
                          f"/prompt{prompt_len}/new{new_tokens}/bf16"),
        "decode_tokens_per_sec": round(total_new / sec),
        "decode_ms_per_token": round(sec / new_tokens * 1e3, 3),
        "decode_batch": B,
    }


def bench_secure_ckks(num_learners: int = 8):
    """Native CKKS secure aggregation on the same 1.64M-param model:
    encrypt / keyless homomorphic weighted-sum / decrypt wall-clock
    (reference PWA+Palisade path, private_weighted_average.cc:22-111 —
    whose ~100MB ciphertexts forced the stub-per-request hack,
    controller.cc:594-604; here the ciphertext is ~26MB)."""
    import tempfile

    from metisfl_tpu.secure.ckks import CKKSBackend, generate_keys

    n_values = sum(int(np.prod(s)) for s in MODEL_SHAPES.values())
    vec = np.random.default_rng(2).standard_normal(n_values)
    with tempfile.TemporaryDirectory() as key_dir:
        generate_keys(key_dir)
        learner = CKKSBackend(key_dir=key_dir, role="learner")
        controller = CKKSBackend(role="controller")
        t0 = time.perf_counter()
        ct = learner.encrypt(vec)
        t_enc = (time.perf_counter() - t0) * 1e3
        payloads = [ct] * num_learners
        scales = [1.0 / num_learners] * num_learners
        t0 = time.perf_counter()
        combined = controller.weighted_sum(payloads, scales)
        t_sum = (time.perf_counter() - t0) * 1e3
        t0 = time.perf_counter()
        out = learner.decrypt(combined, n_values)
        t_dec = (time.perf_counter() - t0) * 1e3
    np.testing.assert_allclose(out, vec, atol=1e-4)
    return {
        "ckks_encrypt_ms": round(t_enc, 1),
        "ckks_weighted_sum_ms": round(t_sum, 1),
        "ckks_decrypt_ms": round(t_dec, 1),
        "ckks_ciphertext_mb": round(len(ct) / 1e6, 1),
        "ckks_parties": num_learners,
    }


def bench_store(num_learners: int = 64):
    """Model-store scale: insert/select/evict at 64 learners x 1.64M-param
    models for the in-memory store, plus the disk store with a 26 MB
    ciphertext-sized blob (reference redis_model_store.cc:120-260 scale
    story; VERDICT r2 #8)."""
    import tempfile

    from metisfl_tpu.store.base import EvictionPolicy
    from metisfl_tpu.store.disk import DiskModelStore
    from metisfl_tpu.store.memory import InMemoryModelStore

    models = synth_models(num_learners, seed=5)
    ids = [f"learner_{i}" for i in range(num_learners)]
    out = {"store_learners": num_learners}

    mem = InMemoryModelStore(EvictionPolicy.LINEAGE_LENGTH, lineage_length=2)
    t0 = time.perf_counter()
    for _ in range(3):  # 3 rounds -> exercises eviction at lineage 2
        for lid, m in zip(ids, models):
            mem.insert(lid, m)
    out["store_mem_insert_ms"] = round(
        (time.perf_counter() - t0) * 1e3 / (3 * num_learners), 3)
    t0 = time.perf_counter()
    sel = mem.select(ids, k=2)
    out["store_mem_select_all_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
    assert len(sel) == num_learners and all(len(v) == 2 for v in sel.values())

    with tempfile.TemporaryDirectory() as root:
        disk = DiskModelStore(root, EvictionPolicy.LINEAGE_LENGTH,
                              lineage_length=1)
        t0 = time.perf_counter()
        for lid, m in zip(ids, models):
            disk.insert(lid, m)
        out["store_disk_insert_ms"] = round(
            (time.perf_counter() - t0) * 1e3 / num_learners, 2)
        t0 = time.perf_counter()
        sel = disk.select(ids, k=1)
        # the mmap read path defers IO to first touch — fold every byte
        # inside the timed region so the metric covers what aggregation
        # actually pays, not just the (now lazy) mapping setup
        acc = {name: np.zeros(arr.shape, np.float32)
               for name, arr in sel[ids[0]][0].items()}
        for lid in ids:
            for name, arr in sel[lid][0].items():
                acc[name] += arr
        out["store_disk_select_all_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 1)
        assert len(sel) == num_learners

        # 26 MB opaque ciphertext blob (the CKKS model size measured above)
        blob = np.random.default_rng(6).bytes(26_000_000)
        t0 = time.perf_counter()
        disk.insert("secure_learner", blob)
        out["store_disk_ciphertext_insert_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 1)
        t0 = time.perf_counter()
        got = disk.select(["secure_learner"], k=1)["secure_learner"][0]
        out["store_disk_ciphertext_select_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 1)
        assert isinstance(got, (bytes, bytearray)) and len(got) == len(blob)

    # cached_disk: persistence + byte-bounded LRU (RedisModelStore role).
    # Budgeted to the full working set: select serves from memory at disk
    # durability (the byte-bound eviction itself is unit-tested; an LRU under
    # a sequential scan of a larger-than-budget set degrades to disk reads)
    from metisfl_tpu.store.cached import CachedDiskStore

    model_bytes = sum(int(np.prod(s)) * 4 for s in MODEL_SHAPES.values())
    with tempfile.TemporaryDirectory() as root:
        cached = CachedDiskStore(root, EvictionPolicy.LINEAGE_LENGTH,
                                 lineage_length=1,
                                 cache_bytes=model_bytes * (num_learners + 1))
        for lid, m in zip(ids, models):
            cached.insert(lid, m)
        t0 = time.perf_counter()
        sel = cached.select(ids, k=1)
        out["store_cached_select_all_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 1)
        assert len(sel) == num_learners
        out["store_cached_hit_rate"] = round(
            cached.cache_hits / max(1, cached.cache_hits
                                    + cached.cache_misses), 3)
        out["store_cached_resident_mb"] = round(
            cached._cached_total / 1e6, 1)

    # wire-size ladder: the same 1.4M-param model blob under each uplink
    # encoding (ship_dtype) — quantifies the compression story end to end
    from metisfl_tpu.tensor.pytree import ModelBlob
    from metisfl_tpu.tensor.quantize import quantize_named
    from metisfl_tpu.tensor.sparse import sparsify_update
    from metisfl_tpu.tensor.spec import narrow_named, resolve_ship_dtype

    named = [(name, np.asarray(arr)) for name, arr in models[0].items()]
    ref = {name: np.zeros_like(arr) for name, arr in named}
    out["wire_f32_mb"] = round(
        len(ModelBlob(tensors=named).to_bytes()) / 1e6, 2)
    out["wire_bf16_mb"] = round(len(ModelBlob(tensors=narrow_named(
        named, resolve_ship_dtype("bf16"))).to_bytes()) / 1e6, 2)
    out["wire_int8q_mb"] = round(len(ModelBlob(
        tensors=quantize_named(named)).to_bytes()) / 1e6, 2)
    for denom in (16, 64):
        out[f"wire_topk{denom}_mb"] = round(len(ModelBlob(
            tensors=sparsify_update(named, ref, denom, {})).to_bytes())
            / 1e6, 2)
    return out


def bench_e2e_round(rounds: int = 4, learners: int = 3):
    """A REAL federation round on the live backend (VERDICT r4 #4): a
    3-learner InProcessFederation — learner train steps jit-compiled on
    the device, blob uplink through the product codec, stride fold,
    downlink dispatch — timed per round with the per-phase breakdown from
    the controller's own round-metadata lineage (the reference records the
    same lineage, metis.proto:342-365). The on-chip agg microbench
    (bench_aggregation) times one phase; this times the product loop."""
    import jax

    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (AggregationConfig, EvalConfig,
                                    FederationConfig, TerminationConfig)
    from metisfl_tpu.driver import InProcessFederation
    from metisfl_tpu.models import ArrayDataset, FlaxModelOps
    from metisfl_tpu.models.zoo import FashionMnistCNN

    rng = np.random.default_rng(11)
    if jax.default_backend() == "cpu":
        # direct CPU calls (tests) exercise the product loop at a size a
        # CPU finishes; the full bench runs this section on the chip only
        batch, steps, rounds = 32, 4, min(rounds, 2)
    else:
        batch, steps = 128, 8
    config = FederationConfig(
        aggregation=AggregationConfig(rule="fedavg", scaler="participants"),
        # scan_chunk amortizes host->device dispatch. On chip: 2
        # chunks/task (first compiles, second times). The CPU size runs a
        # single chunk/task — its wall numbers are sanity only, and the
        # recorded shapes say so.
        train=TrainParams(batch_size=batch, local_steps=steps, scan_chunk=4,
                          optimizer="sgd", learning_rate=0.05),
        eval=EvalConfig(every_n_rounds=0),
        termination=TerminationConfig(federation_rounds=rounds),
    )
    fed = InProcessFederation(config)
    template = None
    for i in range(learners):
        x = rng.standard_normal((batch * 8, 28, 28, 1)).astype(np.float32)
        y = rng.integers(0, 10, size=(batch * 8,)).astype(np.int32)
        engine = FlaxModelOps(FashionMnistCNN(), x[:2])
        if template is None:
            template = engine.get_variables()
        else:
            engine.set_variables(template)
        fed.add_learner(engine, ArrayDataset(x, y, seed=i))
    fed.seed_model(template)
    try:
        fed.start()
        ok = fed.wait_for_rounds(rounds, timeout_s=420)
        metas = fed.controller.get_runtime_metadata()
    finally:
        fed.shutdown()
    if not metas:
        return {}
    # round 1 pays the jit compile; steady-state rounds are the metric
    steady = [m for m in metas[1:rounds]
              if m.get("completed_at") and m.get("started_at")] or metas[:1]
    walls = [m["completed_at"] - m["started_at"] for m in steady]
    trains = []
    for m in steady:
        sub, rec = m.get("train_submitted_at", {}), m.get("train_received_at", {})
        common = set(sub) & set(rec)
        if common:
            trains.append(max(rec[k] for k in common)
                          - min(sub[k] for k in common))
    aggs = [m.get("aggregation_duration_ms", 0.0) for m in steady]
    out = {
        "e2e_learners": learners,
        # effective workload shapes: the CPU fallback runs smaller ones,
        # so captures are only comparable at equal shapes
        "e2e_batch_size": batch,
        "e2e_local_steps": steps,
        "e2e_rounds_completed": int(len(metas)),
        "e2e_rounds_ok": bool(ok),
        "e2e_round_wall_clock_s": round(float(np.median(walls)), 3),
        "e2e_round_wall_first_s": round(
            metas[0]["completed_at"] - metas[0]["started_at"], 3)
        if metas[0].get("completed_at") else None,
        "e2e_train_phase_s": round(float(np.median(trains)), 3)
        if trains else None,
        "e2e_agg_ms": round(float(np.median(aggs)), 2),
        "e2e_uplink_bytes": int(sum(
            metas[-1].get("uplink_bytes", {}).values())),
    }
    return out


def bench_health(num_learners: int = 16, rounds: int = 3):
    """Learning-health plane cost (telemetry/health.py): the per-uplink
    statistics pass (update norm + per-layer breakdown + cosine) and the
    per-round cohort fold at bench model size — the O(params) host work
    every health-enabled uplink pays, tracked here so a regression shows
    up in BENCH_r*.json instead of silently taxing every round."""
    from metisfl_tpu.telemetry.health import HealthMonitor

    params = sum(int(np.prod(s)) for s in MODEL_SHAPES.values())
    models = synth_models(num_learners, seed=9)
    reference = synth_models(1, seed=10)[0]
    monitor = HealthMonitor()
    monitor.note_community(reference)

    observe_times = []
    fold_times = []
    for r in range(rounds):
        for i, model in enumerate(models):
            t0 = time.perf_counter()
            monitor.observe_update(f"learner_{i}", model, reference,
                                   train_metrics={"loss": 1.0 - 0.1 * r})
            observe_times.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        health, _anomalies = monitor.complete_round(
            r, reference, {f"learner_{i}": 1.0
                           for i in range(num_learners)})
        fold_times.append(time.perf_counter() - t0)
        assert len(health["divergence_score"]) == num_learners
    return {
        "health_params": params,
        "health_learners": num_learners,
        "health_observe_ms": round(
            1e3 * sum(observe_times) / len(observe_times), 3),
        "health_observe_max_ms": round(1e3 * max(observe_times), 3),
        "health_round_fold_ms": round(
            1e3 * sum(fold_times) / len(fold_times), 3),
    }


def bench_serving(requests: int = 64, rows_per_request: int = 4,
                  max_batch: int = 32):
    """Serving-gateway section (serving/gateway.py): micro-batched vs
    unbatched forward throughput and the hot-swap pause at bench model
    size. The batched/unbatched ratio is the amortization the
    micro-batching queue buys (one padded jitted forward per bucket vs
    one per request); the swap pause is how long a promotion blocks the
    NEXT batch (in-flight ones keep the old model — zero drops)."""
    import threading as _threading

    import jax

    from metisfl_tpu.config import ServingConfig
    from metisfl_tpu.models import FlaxModelOps
    from metisfl_tpu.models.zoo import MLP
    from metisfl_tpu.serving import ServingGateway
    from metisfl_tpu.tensor.pytree import pack_model

    # bench model size: a ~1.3M-param MLP forward (the MODEL_SHAPES scale
    # the aggregation/health sections use)
    dim, hidden = 256, (1024, 1024)
    ops = FlaxModelOps(MLP(features=hidden, num_outputs=64),
                       np.zeros((2, dim), np.float32), rng_seed=0)
    params = sum(int(np.prod(np.shape(a))) for a in
                 jax.tree.leaves(ops.get_variables()))
    blob = pack_model(ops.get_variables())
    # max_wait_ms=0: the sequential baseline must not pay a coalescing
    # window per request (it would measure the wait, not the forward);
    # concurrent requests still coalesce from the queue backlog, which
    # is the amortization actually being claimed
    gw = ServingGateway(ops, ServingConfig(
        enabled=True, max_batch=max_batch, max_wait_ms=0.0))
    gw.install("stable", 1, blob)
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal((rows_per_request, dim)).astype(np.float32)
          for _ in range(requests)]
    gw.predict(xs[0], key="warmup")  # compile outside the timed window

    t0 = time.perf_counter()
    for i, x in enumerate(xs):
        gw.predict(x, key=f"seq{i}")
    unbatched_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    threads = [_threading.Thread(
        target=lambda x=x, i=i: gw.predict(x, key=f"par{i}"))
        for i, x in enumerate(xs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    batched_s = time.perf_counter() - t0

    # hot-swap pause: how long install() (decode + install) takes, and
    # the worst request latency observed while swapping under load
    stop = _threading.Event()
    worst_ms = [0.0]

    def hammer():
        while not stop.is_set():
            t1 = time.perf_counter()
            gw.predict(xs[0], key="hammer")
            worst_ms[0] = max(worst_ms[0],
                              (time.perf_counter() - t1) * 1e3)

    t = _threading.Thread(target=hammer)
    t.start()
    time.sleep(0.05)
    t0 = time.perf_counter()
    gw.install("stable", 2, blob)
    swap_s = time.perf_counter() - t0
    time.sleep(0.05)
    stop.set()
    t.join()
    gw.shutdown()
    total_rows = requests * rows_per_request
    return {
        "serving_params": params,
        "serving_requests": requests,
        "serving_unbatched_rows_per_sec": round(total_rows / unbatched_s, 1),
        "serving_batched_rows_per_sec": round(total_rows / batched_s, 1),
        "serving_batch_speedup": round(unbatched_s / batched_s, 2),
        "serving_swap_pause_ms": round(swap_s * 1e3, 3),
        "serving_swap_worst_request_ms": round(worst_ms[0], 3),
    }


def bench_fleet(replica_counts=(1, 2, 4), requests: int = 96,
                rows_per_request: int = 4, threads: int = 8,
                decode_prompts=(8, 64), decode_new: int = 24):
    """Serving-fleet section (serving/fleet.py, docs/DEPLOYMENT.md
    "Serving fleet"): router-fronted throughput vs replica count over
    REAL gRPC loopback (in-process gateways + router, wire-realistic
    client traffic), the worst request latency observed during a
    zero-drop ROLLING hot-swap across the fleet, and continuous-batching
    decode tokens/s at two prompt lengths (serving/decode.py)."""
    import threading as _threading

    from metisfl_tpu.config import (ServingConfig, ServingDecodeConfig,
                                    ServingFleetConfig)
    from metisfl_tpu.models import FlaxModelOps
    from metisfl_tpu.models.zoo import MLP
    from metisfl_tpu.models.zoo.transformer import LlamaLite
    from metisfl_tpu.serving import (ContinuousBatcher, RouterServer,
                                     ServingClient, ServingGateway,
                                     ServingRouter, ServingServer)
    from metisfl_tpu.tensor.pytree import pack_model

    dim = 64
    ops = FlaxModelOps(MLP(features=(256, 256), num_outputs=16),
                       np.zeros((2, dim), np.float32), rng_seed=0)
    blob = pack_model(ops.get_variables())
    cfg = ServingConfig(enabled=True, max_batch=16, max_wait_ms=0.5,
                        fleet=ServingFleetConfig(enabled=True))
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal((rows_per_request, dim)).astype(np.float32)
          for _ in range(requests)]
    out = {"fleet_requests": requests,
           "fleet_replica_counts": list(replica_counts)}

    def _boot(n):
        gateways, servers = [], []
        for _ in range(n):
            gw = ServingGateway(ops, cfg)
            gw.install("stable", 1, blob)
            srv = ServingServer(gw, host="127.0.0.1", port=0)
            port = srv.start()
            gateways.append(gw)
            servers.append((srv, port))
        router = ServingRouter(cfg)
        for i, (_, port) in enumerate(servers):
            router.add_replica(f"r{i}", "127.0.0.1", port)
        rserver = RouterServer(router, host="127.0.0.1", port=0)
        rport = rserver.start()
        return gateways, servers, rserver, rport

    def _drive(rport, tag):
        client = ServingClient("127.0.0.1", rport)
        client.predict(xs[0], key="warmup")  # compile outside the window
        client.close()
        t0 = time.perf_counter()
        errs = []

        def worker(w):
            cl = ServingClient("127.0.0.1", rport)
            try:
                for i in range(w, requests, threads):
                    cl.predict(xs[i], key=f"{tag}{i}")
            except Exception as exc:  # noqa: BLE001 - recorded, fatal
                errs.append(exc)
            finally:
                cl.close()

        ts = [_threading.Thread(target=worker, args=(w,))
              for w in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if errs:
            raise errs[0]
        return time.perf_counter() - t0

    for n in replica_counts:
        gateways, servers, rserver, rport = _boot(n)
        try:
            elapsed = _drive(rport, f"n{n}_")
            out[f"fleet_router_rows_per_sec_r{n}"] = round(
                requests * rows_per_request / elapsed, 1)
        finally:
            rserver.stop()
            for srv, _ in servers:
                srv.stop()

    # rolling hot-swap across 2 replicas under hammer: worst request
    # latency while replicas swap ONE AT A TIME (the staggered-poll
    # posture), plus the total roll duration
    gateways, servers, rserver, rport = _boot(2)
    try:
        cl = ServingClient("127.0.0.1", rport)
        cl.predict(xs[0], key="warmup")
        stop = _threading.Event()
        worst_ms = [0.0]

        def hammer():
            h = ServingClient("127.0.0.1", rport)
            i = 0
            while not stop.is_set():
                t1 = time.perf_counter()
                h.predict(xs[i % len(xs)], key=f"h{i}")
                worst_ms[0] = max(worst_ms[0],
                                  (time.perf_counter() - t1) * 1e3)
                i += 1
            h.close()

        t = _threading.Thread(target=hammer)
        t.start()
        time.sleep(0.05)
        t0 = time.perf_counter()
        for gw in gateways:            # one replica at a time
            gw.install("stable", 2, blob)
        roll_s = time.perf_counter() - t0
        time.sleep(0.05)
        stop.set()
        t.join()
        cl.close()
        out["fleet_rolling_swap_ms"] = round(roll_s * 1e3, 3)
        out["fleet_rolling_swap_worst_request_ms"] = round(worst_ms[0], 3)
    finally:
        rserver.stop()
        for srv, _ in servers:
            srv.stop()

    # continuous-batching decode throughput at two prompt lengths
    module = LlamaLite(vocab_size=512, dim=64, depth=2, heads=4)
    lm_ops = FlaxModelOps(module, np.zeros((1, 8), np.int32), rng_seed=0)
    for plen in decode_prompts:
        engine = ContinuousBatcher(
            lm_ops, 1, lm_ops.get_variables(),
            slots=ServingDecodeConfig().slots,
            max_len=plen + decode_new + 1, channel=f"bench{plen}")
        try:
            prompt = rng.integers(1, 512, size=(plen,)).astype(np.int32)
            engine.submit(prompt, 4).result(timeout=120.0)  # compile
            t0 = time.perf_counter()
            futs = [engine.submit(
                rng.integers(1, 512, size=(plen,)).astype(np.int32),
                decode_new) for _ in range(8)]
            toks = sum(len(f.result(timeout=120.0)[0]) for f in futs)
            out[f"fleet_decode_tokens_per_sec_p{plen}"] = round(
                toks / (time.perf_counter() - t0), 1)
        finally:
            engine.close()
    return out


def bench_cohort(sizes=(1024, 4096), stride: int = 64,
                 ingest_workers=(1, 4, 16)):
    """Cohort-scale ingest + fold (VERDICT r4 #6 / weak #5, docs/SCALE.md):
    1k-4k distinct 1.64M-param models onto the DISK store — now through
    the parallel ingest pipeline, swept across worker counts {1, 4, 16}
    (w=1 isolates the copy-free write path; the headline
    ``cohort_{n}_insert_s`` is the 16-worker figure the controller's
    ingest plane runs at) — then folded stride-blocked with peak RSS
    bounded by the stride block, not the cohort. Host-only; runs in its
    own child so ru_maxrss is clean."""
    import gc
    import shutil as _shutil
    import tempfile

    from metisfl_tpu.aggregation.fedavg import FedAvg
    from metisfl_tpu.store.base import EvictionPolicy
    from metisfl_tpu.store.disk import DiskModelStore
    from metisfl_tpu.store.ingest import IngestPipeline

    rng = np.random.default_rng(9)
    base = {name: rng.standard_normal(shape).astype(np.float32)
            for name, shape in MODEL_SHAPES.items()}
    model_bytes = sum(a.nbytes for a in base.values())
    out = {"cohort_stride": stride,
           "cohort_model_mb": round(model_bytes / 1e6, 2),
           "cohort_ingest_workers": list(ingest_workers)}

    def _timed_ingest(root, n, workers):
        """Insert n distinct models through a w-worker pipeline; returns
        (elapsed_s, store) with every write drained + flushed."""
        store = DiskModelStore(root, EvictionPolicy.LINEAGE_LENGTH,
                               lineage_length=1)
        pipe = IngestPipeline(store, workers)
        t0 = time.perf_counter()
        for i in range(n):
            # distinct per-learner content at generation cost O(model)
            pipe.submit(f"L{i}", {k: v + np.float32(i % 17)
                                  for k, v in base.items()})
        if not pipe.drain(timeout=1800.0):
            raise RuntimeError("ingest drain timed out")
        elapsed = time.perf_counter() - t0
        pipe.shutdown()
        return elapsed, store

    for n in sizes:
        need = int(n * model_bytes * 1.15)
        free = _shutil.disk_usage(tempfile.gettempdir()).free
        if free < need:
            out[f"cohort_{n}_skipped"] = (
                f"needs {need >> 30} GiB free disk, have {free >> 30}")
            continue
        # worker sweep: all but the last run are timing-only (their
        # stores are freed immediately to keep one cohort of disk in use)
        for w in ingest_workers[:-1]:
            with tempfile.TemporaryDirectory(prefix=f"cohort{n}w{w}_") as rt:
                elapsed, store = _timed_ingest(rt, n, w)
                store.shutdown()
            out[f"cohort_{n}_insert_w{w}_s"] = round(elapsed, 1)
            # settle the page cache between sweeps: the previous sweep's
            # GBs of dirty pages would otherwise throttle the next one's
            # writes and skew the comparison
            os.sync()
        with tempfile.TemporaryDirectory(prefix=f"cohort{n}_") as root:
            headline_w = ingest_workers[-1]
            elapsed, store = _timed_ingest(root, n, headline_w)
            out[f"cohort_{n}_insert_w{headline_w}_s"] = round(elapsed, 1)
            out[f"cohort_{n}_insert_s"] = round(elapsed, 1)
            out[f"cohort_{n}_insert_models_per_sec"] = round(n / elapsed, 1)
            gc.collect()
            rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
            agg = FedAvg()
            agg.reset()
            ids = [f"L{i}" for i in range(n)]
            scale = 1.0 / n
            t0 = time.perf_counter()
            for i in range(0, n, stride):
                block = ids[i : i + stride]
                picked = store.select(block, k=1)
                agg.accumulate([(picked[lid], scale) for lid in block])
            result = agg.result()
            agg.reset()
            dt = time.perf_counter() - t0
            rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
            # correctness: mean of base + (i % 17) offsets
            want = base["head/bias"] + np.float32(
                np.mean([i % 17 for i in range(n)]))
            np.testing.assert_allclose(np.asarray(result["head/bias"]),
                                       want, rtol=1e-4, atol=1e-3)
            out[f"cohort_{n}_agg_ms"] = round(dt * 1e3, 1)
            out[f"cohort_{n}_peak_rss_kb"] = rss1
            out[f"cohort_{n}_rss_growth_kb"] = rss1 - rss0
            # the bounding claim: fold-time RSS growth is a small fraction
            # of the cohort working set (models stream through per-block
            # mmap views); comparing the recorded growth across the 1024
            # and 4096 rows shows it tracks the STRIDE, not the cohort
            out[f"cohort_{n}_growth_vs_cohort"] = round(
                (rss1 - rss0) * 1024 / (n * model_bytes), 4)
            out[f"cohort_{n}_bounded"] = bool(
                (rss1 - rss0) * 1024 < n * model_bytes / 4)
            store.shutdown()

    # 10k-learner in-process round probe (ROADMAP open item 3): fold 10k
    # distinct uplinks through the STREAMING path — each model enters the
    # accumulator as it "arrives" and is dropped, zero store traffic —
    # and show the round completes with RSS bounded by one stride block
    # (~stride x model), not the 10k-model cohort (~66 GiB here).
    from metisfl_tpu.aggregation.streaming import StreamingAggregator

    n10k = 10_000
    gc.collect()
    rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    streamer = StreamingAggregator(FedAvg(), stride=stride)
    t0 = time.perf_counter()
    for i in range(n10k):
        streamer.fold(f"L{i}", {k: v + np.float32(i % 17)
                                for k, v in base.items()}, 1.0)
    community = streamer.finish([f"L{i}" for i in range(n10k)])
    wall = time.perf_counter() - t0
    rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    want = base["head/bias"] + np.float32(
        np.mean([i % 17 for i in range(n10k)]))
    np.testing.assert_allclose(np.asarray(community["head/bias"]), want,
                               rtol=1e-4, atol=1e-3)
    out["round_10k_wall_s"] = round(wall, 1)
    out["round_10k_uplinks_per_sec"] = round(n10k / wall, 1)
    out["round_10k_peak_rss_kb"] = rss1
    out["round_10k_rss_growth_kb"] = rss1 - rss0
    out["round_10k_bounded"] = bool(
        (rss1 - rss0) * 1024 < n10k * model_bytes / 16)
    return out


def bench_churn():
    """Cross-device churn probe (ISSUE 9): the seeded 1024-virtual-client
    federation from metisfl_tpu/driver/crossdevice.py — per-round
    sampling at quorum 12 (over-provisioned 2x), 30% per-round dropout
    plus one flapping and one partitioned learner — measured for quorum
    round wall-clock and the RSS bound. Host-side (the harness stresses
    the controller's scheduling planes, not device math); keys are
    direction-classified for ``python -m metisfl_tpu.perf --trajectory``
    (wall/rss lower-better, rounds_per_sec/accuracy higher-better)."""
    import statistics

    from metisfl_tpu.driver.crossdevice import ChurnScenario, run_scenario

    res = run_scenario(ChurnScenario(
        seed=7, clients=1024, rounds=5, quorum=12, overprovision=1.0,
        dropout=0.3, timeout_s=180.0))
    walls = res.get("round_walls_s") or [0.0]
    out = {
        "round_churn_clients": res["clients"],
        "round_churn_quorum": res["quorum"],
        "round_churn_rounds": res["rounds_completed"],
        "round_churn_ok": bool(res["ok"]),
        "round_churn_wall_s": res["wall_s"],
        "round_churn_join_s": res["join_s"],
        "round_churn_round_ms_median": round(
            1e3 * statistics.median(walls), 1),
        "round_churn_rounds_per_sec": round(
            res["rounds_completed"] / max(res["wall_s"], 1e-9), 2),
        "round_churn_accuracy": res["accuracy"],
        "round_churn_faults_injected": sum(res["faults"].values()),
        "round_churn_peak_rss_kb": res["peak_rss_kb"],
        "round_churn_rss_growth_kb": res["rss_growth_kb"],
        # the bounding claim: a 1024-client churn federation must not
        # grow the controller by more than 256 MiB over the run
        "round_churn_bounded": bool(res["rss_growth_kb"] < (256 << 10)),
    }
    return out


def bench_obs(sizes=(1000, 10000, 100000), budget=256):
    """Telemetry-at-scale section (ISSUE 10; docs/OBSERVABILITY.md
    "Telemetry at scale"): exposition time, exposition bytes, simulated
    ``describe()`` payload bytes, and checkpoint bytes for a per-learner
    gauge family at 1k/10k/100k simulated learner series — exact vs
    sketch (``telemetry.cardinality_budget``) — plus the sketch's
    quantile error against exact. Host-side and self-contained (fresh
    registries, no process-global state); keys are direction-classified
    for ``python -m metisfl_tpu.perf --trajectory`` (ms/bytes
    lower-better, relerr lower-better) so ``scripts/check_bench.sh``
    gates a regression in either representation."""
    import json as _json

    from metisfl_tpu.telemetry.metrics import Registry

    labels = {1000: "1k", 10000: "10k", 100000: "100k"}
    rng = np.random.default_rng(11)
    out = {"obs_budget": budget}
    for n in sizes:
        tag = labels.get(n, str(n))
        # straggler-score-shaped fleet: most learners near 1x, a long
        # tail of stragglers — the distribution the digest must hold
        values = rng.gamma(4.0, 0.25, size=n).astype(np.float64)
        exact_q = {q: float(np.quantile(values, q)) for q in (0.5, 0.99)}
        sketch_q = {}
        for mode in ("exact", "sketch"):
            reg = Registry()
            gauge = reg.gauge("learner_straggler_score", "",
                              ("learner",), budget_label="learner")
            if mode == "sketch":
                reg.set_cardinality_budget(budget)
            for i in range(n):
                gauge.set(float(values[i]), learner=f"L{i}")
            t0 = time.perf_counter()
            text = reg.render()
            expose_s = time.perf_counter() - t0
            # describe() payload: the per-learner table vs the digest
            # columns + top offenders the budget substitutes for it
            if mode == "exact":
                payload = [{"learner_id": f"L{i}",
                            "straggler_score": round(float(values[i]), 4),
                            "live": True, "dispatch_failures": 0}
                           for i in range(n)]
                ckpt = {f"L{i}": {"ewma_train_s": float(values[i])}
                        for i in range(n)}
            else:
                sketch_q = {q: gauge.quantile(q) for q in (0.5, 0.99)}
                payload = {"count": n, "budget": budget,
                           "columns": {"straggler_score": {
                               f"p{int(q * 100)}": sketch_q[q]
                               for q in sketch_q}},
                           "top": gauge.sketch_summary(10)}
                ckpt = reg.budget_state()
            out[f"obs_expose_ms_{tag}_{mode}"] = round(expose_s * 1e3, 2)
            out[f"obs_expose_bytes_{tag}_{mode}"] = len(text)
            out[f"obs_describe_bytes_{tag}_{mode}"] = len(
                _json.dumps(payload))
            out[f"obs_ckpt_bytes_{tag}_{mode}"] = len(
                _json.dumps(ckpt, default=str))
        for q in (0.5, 0.99):
            rel = (abs(sketch_q[q] - exact_q[q])
                   / max(abs(exact_q[q]), 1e-12))
            out[f"obs_q{int(q * 100)}_relerr_{tag}"] = round(rel, 6)
    return out


def bench_fabric(peer_counts=(2, 8, 32), spans=1500, events=400,
                 series=2000, budget=256):
    """Fleet-telemetry-fabric section (ISSUE 11; docs/OBSERVABILITY.md
    "Fleet fabric"): CollectTelemetry pull latency and reply bytes vs
    simulated peer count. Boots N real-gRPC endpoints over this
    process's telemetry (pre-filled with a span/event backlog plus a
    budget-collapsed per-learner gauge family, so replies carry the
    sketch shape they would at cross-device scale), then measures a
    FleetCollector's full-backlog sweep and the steady-state
    incremental sweep separately, plus the fleet-wide metrics merge.
    Host-side; keys are direction-classified for
    ``python -m metisfl_tpu.perf --trajectory`` (ms/kb lower-better,
    spans_per_sec higher-better)."""
    from metisfl_tpu.comm.rpc import BytesService, RpcServer
    from metisfl_tpu.telemetry import events as tevents
    from metisfl_tpu.telemetry import fabric as tfabric
    from metisfl_tpu.telemetry import metrics as tmetrics
    from metisfl_tpu.telemetry import trace as ttrace

    tfabric.configure(enabled=True)
    ttrace.configure(enabled=True, service="bench-fabric", dir="")
    tevents.configure(enabled=True, service="bench-fabric", dir="")
    reg = tmetrics.registry()
    reg.set_cardinality_budget(budget)
    gauge = reg.gauge("learner_straggler_score", "", ("learner",),
                      budget_label="learner")
    rng = np.random.default_rng(17)
    for i in range(series):
        gauge.set(float(rng.gamma(4.0, 0.25)), learner=f"L{i}")
    for i in range(spans):
        ttrace.event(f"bench.work/{i % 11}", 0.001)
    for i in range(events):
        tevents.emit(tevents.TaskDispatched, task_id=f"t{i}",
                     learner_id=f"L{i % 64}", round=i // 50)

    out = {"fabric_span_backlog": spans, "fabric_event_backlog": events,
           "fabric_series": series, "fabric_budget": budget}
    max_k = max(peer_counts)
    servers = []
    try:
        for i in range(max_k):
            server = RpcServer("127.0.0.1", 0)
            server.add_service(BytesService(f"bench.Fabric{i}", {},
                                            role="learner"))
            servers.append((server, server.start(), i))
        for k in peer_counts:
            collector = tfabric.FleetCollector(probe_health=False)
            for server, port, i in servers[:k]:
                collector.add_peer(f"peer-{i}", "127.0.0.1", port,
                                   f"bench.Fabric{i}", role="learner")
            t0 = time.perf_counter()
            collector.poll_once(timeout=30.0)
            backlog_s = time.perf_counter() - t0
            backlog_bytes = sum(p.bytes_collected
                                for p in collector.peers())
            t0 = time.perf_counter()
            collector.poll_once(timeout=30.0)
            incr_s = time.perf_counter() - t0
            out[f"fabric_peers_{k}_backlog_ms"] = round(backlog_s * 1e3, 2)
            out[f"fabric_peers_{k}_incr_ms"] = round(incr_s * 1e3, 2)
            out[f"fabric_peers_{k}_backlog_kb"] = round(
                backlog_bytes / 1024.0, 1)
            if k == max_k:
                total_spans = sum(p.spans_collected
                                  for p in collector.peers())
                out["fabric_spans_per_sec"] = int(
                    total_spans / max(backlog_s, 1e-9))
                t0 = time.perf_counter()
                text = collector.merged_exposition()
                out["fabric_merge_ms"] = round(
                    (time.perf_counter() - t0) * 1e3, 2)
                out["fabric_merged_kb"] = round(len(text) / 1024.0, 1)
            collector.stop(final_poll=False)
    finally:
        for server, _port, _i in servers:
            try:
                server.stop(grace=0.1)
            except Exception:  # noqa: BLE001
                pass
    return out


def bench_tree_dist(branches=(2, 8), client_counts=(1000, 10000),
                    rehome_slices=3, rehome_clients=1000, dim=256):
    """Distributed slice-aggregation section (ISSUE 12;
    docs/RESILIENCE.md "Distributed slice aggregators"): rounds/s of the
    slice tier — submit every simulated client's uplink over real gRPC
    to its slice aggregator, then fan in O(branch) FoldPartial replies —
    vs branch ∈ {2, 8} at 1k/10k simulated clients, plus the mid-round
    re-homing pause (reduce with one aggregator freshly dead, spool
    recovery included, minus the clean reduce). In-process
    :class:`SliceServer` endpoints (real gRPC loopback, the fabric
    section's posture). Keys are direction-classified for
    ``python -m metisfl_tpu.perf --trajectory`` (round_ms/pause_ms
    lower-better, per_sec higher-better)."""
    import shutil
    import tempfile

    from metisfl_tpu.aggregation.distributed import DistributedSliceReducer
    from metisfl_tpu.aggregation.slice import SliceServer

    rng = np.random.default_rng(23)
    model = {"w": rng.standard_normal((dim,)).astype(np.float32)}

    def build(n_slices, tmp):
        servers, specs = [], []
        for i in range(n_slices):
            spool = os.path.join(tmp, f"slice_{i}")
            server = SliceServer(spool_dir=spool, name=f"slice_{i}",
                                 host="127.0.0.1", port=0)
            port = server.start()
            servers.append(server)
            specs.append({"name": f"slice_{i}", "host": "127.0.0.1",
                          "port": port, "spool_dir": spool})

        class _Cfg:
            slices = specs
            rehome_retries = 2
            rehome_backoff_s = 0.02

        return servers, DistributedSliceReducer(_Cfg())

    out = {"tree_dist_model_bytes": int(model["w"].nbytes)}
    labels = {1000: "1k", 10000: "10k"}
    for branch in branches:
        for clients in client_counts:
            tag = f"b{branch}_c{labels.get(clients, clients)}"
            tmp = tempfile.mkdtemp(prefix="bench_tree_dist_")
            servers, red = build(branch, tmp)
            try:
                ids = [f"L{i:05d}" for i in range(clients)]
                scales = {lid: 1.0 / clients for lid in ids}
                red.assign(ids)
                t0 = time.perf_counter()
                for lid in ids:
                    red.submit(lid, model, 0)
                submit_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                reduced = red.reduce(ids, scales, stride=0, round_id=0)
                round_s = time.perf_counter() - t0
                assert reduced is not None
                red.round_complete()
                out[f"tree_dist_{tag}_submit_per_sec"] = int(
                    clients / max(submit_s, 1e-9))
                out[f"tree_dist_{tag}_round_ms"] = round(round_s * 1e3, 2)
                out[f"tree_dist_{tag}_rounds_per_sec"] = round(
                    1.0 / max(submit_s + round_s, 1e-9), 2)
            finally:
                red.shutdown()
                for server in servers:
                    server.stop()
                shutil.rmtree(tmp, ignore_errors=True)
    # re-homing pause: one aggregator freshly dead at reduce time — the
    # pause covers death detection (probe), spool recovery, and the
    # re-folded group, measured against the same fleet's clean reduce
    tmp = tempfile.mkdtemp(prefix="bench_tree_dist_")
    servers, red = build(rehome_slices, tmp)
    try:
        ids = [f"L{i:05d}" for i in range(rehome_clients)]
        scales = {lid: 1.0 / rehome_clients for lid in ids}
        red.assign(ids)
        for lid in ids:
            red.submit(lid, model, 0)
        t0 = time.perf_counter()
        red.reduce(ids, scales, stride=0, round_id=0)
        clean_s = time.perf_counter() - t0
        servers[0].stop()
        t0 = time.perf_counter()
        reduced = red.reduce(ids, scales, stride=0, round_id=1)
        rehome_s = time.perf_counter() - t0
        assert reduced is not None and red.rehomed_total == 1
        out["tree_dist_rehome_round_ms"] = round(rehome_s * 1e3, 2)
        out["tree_dist_rehome_pause_ms"] = round(
            max(0.0, rehome_s - clean_s) * 1e3, 2)
    finally:
        red.shutdown()
        for server in servers:
            server.stop()  # idempotent: covers the deliberately-killed one
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def bench_secure(client_counts=(1000, 10000), dim=16384, neighbors=8,
                 drop_frac=0.01):
    """Secure-aggregation-at-scale section (docs/SECURITY.md): the
    masked partial-fold plane's host-side cost model at 1k/10k simulated
    clients — per-learner mask generation (k-regular pair streams,
    ``secure.mask_neighbors``), the root's masked modular fold of every
    uplink, and dropout settlement (1% of the cohort expired, residual
    recovered via seed-share regeneration) — against the same cohort's
    plain float64 fold. ``secure_vs_plain_multiplier_*`` is the judged
    round-time ratio (lower-better via perf.py's ``multiplier``
    pattern); the component keys are lower-better ms."""
    from metisfl_tpu.secure.distributed import (MaskedAccumulator,
                                                encode_fixed,
                                                mask_partners, pair_sign,
                                                pair_stream)
    from metisfl_tpu.secure import recovery as _recovery

    rng = np.random.default_rng(29)
    update = rng.standard_normal((dim,)).astype(np.float64)
    secret = "bench-secure-agreed"
    out = {"secure_model_dim": int(dim),
           "secure_mask_neighbors": int(neighbors)}
    labels = {1000: "1k", 10000: "10k"}
    for n in client_counts:
        tag = labels.get(n, str(n))
        me = n // 2
        # per-learner mask generation: fixed-point encode + k pair
        # streams — constant in the cohort size, which is the entire
        # point of the Bell-style mask graph
        t0 = time.perf_counter()
        masked = encode_fixed(update)
        for j in mask_partners(me, n, neighbors):
            stream = pair_stream(secret, me, j, round_id=1, tensor_idx=0,
                                 n=dim)
            if pair_sign(me, j) > 0:
                masked = masked + stream
            else:
                masked = masked - stream
        gen_s = time.perf_counter() - t0
        payload = masked.astype(np.uint64).tobytes()

        # the root's masked fold: n opaque uplinks into the modular
        # accumulator (byte-identical payloads time identically to
        # distinct ones — the adds don't care)
        spec = object()
        acc = MaskedAccumulator()
        t0 = time.perf_counter()
        for i in range(n):
            acc.fold(f"L{i:05d}", {"w": (payload, spec)})
        fold_s = time.perf_counter() - t0
        sums, _specs, _ids = acc.snapshot()

        # settlement with 1% of the cohort expired: residual regenerated
        # from the dropped parties' surviving pair streams
        dropped_n = max(1, int(n * drop_frac))
        present = {f"L{i:05d}": i for i in range(dropped_n, n)}
        dropped_set = set(range(dropped_n))

        def recover_fn(rid, surviving, dropped, lengths):
            survivors = set(surviving)
            residual = np.zeros(lengths[0], np.uint64)
            for d in dropped:
                for p in mask_partners(d, n, neighbors):
                    if p not in survivors:
                        continue
                    stream = pair_stream(secret, d, p, rid, 0, lengths[0])
                    if pair_sign(d, p) > 0:
                        residual = residual + stream
                    else:
                        residual = residual - stream
            return [residual.tobytes()]

        t0 = time.perf_counter()
        _payloads, report = _recovery.settle(
            sums, present, num_parties=n, min_parties=2, round_id=1,
            recover_fn=recover_fn)
        settle_s = time.perf_counter() - t0
        assert report.recovered and len(report.dropped) == dropped_n

        # the plain control: the same cohort's float64 fold + mean
        t0 = time.perf_counter()
        plain = np.zeros(dim, np.float64)
        for _ in range(n):
            plain = plain + update
        plain = plain / n
        plain_s = time.perf_counter() - t0

        secure_s = gen_s + fold_s + settle_s
        out[f"secure_mask_gen_ms_{tag}"] = round(gen_s * 1e3, 3)
        out[f"secure_masked_fold_ms_{tag}"] = round(fold_s * 1e3, 3)
        out[f"secure_settlement_ms_{tag}"] = round(settle_s * 1e3, 3)
        out[f"secure_plain_fold_ms_{tag}"] = round(plain_s * 1e3, 3)
        out[f"secure_vs_plain_multiplier_{tag}"] = round(
            secure_s / max(plain_s, 1e-9), 2)
    return out


def bench_lora(require_tpu: bool = True):
    """Single-chip LoRA execution proof (VERDICT r4 #7): a ~1.2B-param
    frozen bf16 LlamaLite base + rank-16 adapters on q/v, real optimizer
    steps on ONE chip (the largest geometry that comfortably fits 16 GB
    v5e HBM with activations), turning the 8B AOT proof
    (tests/test_parallel.py) into an execution data point. MFU here uses
    the LoRA FLOP accounting — forward + activation-gradient backward
    (weight-gradient matmuls only exist for the adapters, negligible),
    i.e. 2x forward instead of full training's 3x."""
    import jax
    import jax.numpy as jnp

    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.models.dataset import ArrayDataset
    from metisfl_tpu.models.ops import FlaxModelOps
    from metisfl_tpu.models.zoo import LlamaLite

    if require_tpu:
        _require_tpu("lora")  # ~minutes/step on a CPU; a chip-only metric
    kind = jax.devices()[0].device_kind
    peak = _chip_peak_flops(kind)
    dim, depth, heads, vocab, L, B = 2048, 16, 16, 32768, 1024, 4
    rng = np.random.default_rng(12)
    x = rng.integers(0, vocab, (B * 2, L)).astype(np.int32)
    ds = ArrayDataset(x, np.roll(x, -1, axis=1))
    ops = FlaxModelOps(
        LlamaLite(vocab_size=vocab, dim=dim, depth=depth, heads=heads,
                  lora_rank=16, remat=True, dtype=jnp.bfloat16),
        ds.x[:1], trainable_regex="lora_")
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(ops.variables))
    res = ops.train(ds, TrainParams(
        batch_size=B, local_steps=8, scan_chunk=4,
        optimizer="adam", learning_rate=1e-4))
    if res.ms_per_step <= 0:
        return {"lora_params": n_params}
    tokens = B * L
    # fwd + dgrad only (no base wgrad): 2x forward = 2/3 of the 3x-forward
    # full-training accounting (adapter wgrads are negligible)
    flops = _lm_step_flops(B, L, dim, depth, vocab) * 2 // 3
    out = {
        "lora_params": n_params,
        "lora_config": f"dim{dim}/depth{depth}/seq{L}/rank16/bf16",
        "lora_1b_ms_per_step": round(res.ms_per_step, 2),
        "lora_1b_tokens_per_sec": round(tokens / (res.ms_per_step / 1e3)),
        "lora_1b_samples_per_sec": round(B / (res.ms_per_step / 1e3), 2),
    }
    if peak:
        out["lora_1b_mfu"] = round(
            (flops / (res.ms_per_step / 1e3)) / peak, 4)
    return out


def bench_prof(trials=5, acquire_iters=200_000, sample_iters=300):
    """Continuous-profiling section (ISSUE 13; docs/OBSERVABILITY.md
    "Continuous profiling"): the profiler's own cost, measured — the
    bench round loop (stride-blocked stacked scaled adds under a lock)
    with the sampler + instrumented locks ON vs OFF (interleaved
    median-of-``trials``), the per-tick stack-fold cost, and the
    uncontended acquire cost of a raw vs instrumented lock. Host-side
    and self-contained. ``prof_overhead_pct`` is informational (a ratio
    of two noisy medians; the chaos_smoke prof gate bounds it
    absolutely); the ms/ns keys are direction-classified for
    ``python -m metisfl_tpu.perf --trajectory``."""
    import threading as _threading

    from metisfl_tpu.telemetry import prof as tprof

    tprof.reset()
    tprof._smoke_round_loop(_threading.Lock())  # warm-up (allocator, jit-free)
    off_s, on_s = [], []
    for _ in range(trials):
        tprof.configure(enabled=False)
        off_s.append(tprof._smoke_round_loop(tprof.lock("bench.prof")))
        tprof.configure(enabled=True)  # default 67 Hz / 512 budget
        on_s.append(tprof._smoke_round_loop(tprof.lock("bench.prof")))
    state = tprof.collect_state()
    # per-tick fold cost (all threads walked + folded, synchronously)
    t0 = time.perf_counter()
    for _ in range(sample_iters):
        tprof.sample_once()
    sample_ms = (time.perf_counter() - t0) / sample_iters * 1e3
    tprof.configure(enabled=False)

    def _acquire_ns(lk):
        t0 = time.perf_counter()
        for _ in range(acquire_iters):
            lk.acquire()
            lk.release()
        return (time.perf_counter() - t0) / acquire_iters * 1e9

    plain_ns = _acquire_ns(_threading.Lock())
    tprof.configure(enabled=True)
    timed = tprof.lock("bench.prof.acquire")
    tprof.configure(enabled=False)
    timed_ns = _acquire_ns(timed)
    tprof.reset()
    tprof.configure(enabled=False)
    off_ms = statistics.median(off_s) * 1e3
    on_ms = statistics.median(on_s) * 1e3
    return {
        "prof_round_ms_off": round(off_ms, 2),
        "prof_round_ms_on": round(on_ms, 2),
        "prof_overhead_pct": round(
            100.0 * (on_ms - off_ms) / off_ms, 2) if off_ms else 0.0,
        "prof_sample_ms": round(sample_ms, 4),
        "prof_acquire_ns_plain": round(plain_ns, 1),
        "prof_acquire_ns_timed": round(timed_ns, 1),
        "prof_samples": int(state.get("samples", 0)),
        "prof_stacks_tracked": len(tprof.folded_counts(state)),
        "prof_hz": state.get("hz", 0.0),
    }


def bench_runtime(trials=5, call_iters=2000, steady_iters=20):
    """Accelerator-runtime section (docs/OBSERVABILITY.md "Runtime
    observability"): the compile-listener's own cost, measured — the
    per-call overhead of a monitored_jit wrapper vs a raw jitted call
    (minima over ``trials``), the round kernel's cold-compile vs
    cached-call ms (the gap every recompile re-pays), and the decode
    path's recompile count at prompt lengths {8, 64} after warmup (0 =
    the slot decoder's per-length LRU is doing its job). The ns/ms/
    recompile keys are direction-classified for ``perf --trajectory``."""
    import numpy as _np

    from metisfl_tpu.telemetry import runtime as truntime

    truntime.reset()
    truntime.configure(enabled=True)

    # cold compile vs cached call for the bench round kernel
    step = truntime._smoke_round_kernel()
    rng = _np.random.default_rng(11)
    params = {"w": rng.standard_normal((128, 64)).astype(_np.float32),
              "b": rng.standard_normal((64,)).astype(_np.float32)}
    x = rng.standard_normal((32, 128)).astype(_np.float32)
    t0 = time.perf_counter()
    params, _ = step(params, x)
    cold_ms = (time.perf_counter() - t0) * 1e3
    times = []
    for _ in range(steady_iters):
        t0 = time.perf_counter()
        params, _ = step(params, x)
        times.append((time.perf_counter() - t0) * 1e3)
    cached_ms = min(times)

    # wrapper overhead: monitored vs raw compiled call (minima judged)
    import jax as _jax

    def tiny(v):
        return v * 2.0 + 1.0

    raw = _jax.jit(tiny)
    mon = truntime.monitored_jit(tiny, name="bench.runtime_tiny")
    v = _np.ones((16,), _np.float32)
    raw(v), mon(v)

    def _per_call_ns(fn):
        t0 = time.perf_counter()
        for _ in range(call_iters):
            fn(v)
        return (time.perf_counter() - t0) / call_iters * 1e9

    raw_ns = min(_per_call_ns(raw) for _ in range(trials))
    mon_ns = min(_per_call_ns(mon) for _ in range(trials))

    # decode-path recompiles at prompt lengths {8, 64}: warm each
    # length once, then repeated prompts must reuse the per-length LRU
    out = {}
    try:
        from metisfl_tpu.models.generate import SlotDecoder

        ops, variables = truntime._smoke_decoder()
        decoder = SlotDecoder(ops.module, slots=2, max_len=128)
        toks = _np.zeros(2, _np.int32)
        for length in (8, 64):
            prompt = _np.arange(1, length + 1,
                                dtype=_np.int32)[None, :]
            positions = _np.full(2, length, _np.int32)
            decoder.prefill(variables, 0, prompt)
            decoder.step(variables, toks, positions)  # warm both programs
            warm = truntime.collect_state()["compiles"]
            for _ in range(4):
                decoder.prefill(variables, 0, prompt)
                decoder.step(variables, toks, positions)
            after = truntime.collect_state()
            out[f"runtime_decode_recompiles_len{length}"] = (
                after["compiles"] - warm)
    except Exception as exc:  # noqa: BLE001 - report, don't fail bench
        out["runtime_decode_failed"] = 1
        print(f"bench runtime: decode leg failed: {exc}", file=sys.stderr)

    state = truntime.collect_state()
    out.update({
        "runtime_listener_overhead_ns": round(max(0.0, mon_ns - raw_ns),
                                              1),
        "runtime_call_ns_raw": round(raw_ns, 1),
        "runtime_call_ns_monitored": round(mon_ns, 1),
        "runtime_cold_compile_ms": round(cold_ms, 3),
        "runtime_cached_call_ms": round(cached_ms, 4),
        "runtime_compiles": int(state.get("compiles", 0)),
        "runtime_recompiles_total": int(state.get("recompiles", 0)),
        "runtime_listener_mode_monitoring": int(
            truntime.listener_mode() == "monitoring"),
    })
    truntime.reset()
    return out


def _synth_trace(n_spans: int) -> list:
    """A synthetic round-shaped trace of ~``n_spans`` records: one round
    root, fan-out dispatch/learner subtrees (each train span outliving
    its dispatch parent — the fork-join shape the walk is built for),
    and an aggregate tail. Deterministic: same n, same tree."""
    spans = []
    t0 = 1_000_000.0

    def rec(i, name, parent, start, dur_ms, attrs=None):
        r = {"trace": "b" * 32, "span": f"{i:016x}", "parent": parent,
             "name": name, "service": "bench", "start": start,
             "dur_ms": round(dur_ms, 3)}
        if attrs:
            r["attrs"] = attrs
        spans.append(r)
        return r["span"]

    root = rec(0, "round", "", t0, 5000.0, {"round": 1})
    i = 1
    disp = rec(i, "round.dispatch", root, t0 + 1.0, 80.0)
    i += 1
    # each learner subtree: rpc.server/RunTask > learner.train > leaves
    per_learner = 4
    learners = max(1, (n_spans - 4) // (per_learner + 1))
    for li in range(learners):
        start = t0 + 2.0 + 0.01 * li
        task = rec(i, "rpc.server/RunTask", disp, start,
                   3000.0 + 7.0 * (li % 13))
        i += 1
        train = rec(i, "learner.train", task, start + 0.005,
                    2990.0 + 7.0 * (li % 13),
                    {"learner": f"learner_{li}"})
        i += 1
        for leaf in range(per_learner - 1):
            rec(i, f"learner.step_{leaf}", train,
                start + 0.01 + leaf * 0.9, 850.0)
            i += 1
    agg = rec(i, "round.aggregate", root, t0 + 3.2, 1700.0)
    i += 1
    rec(i, "round.agg_block", agg, t0 + 3.25, 1600.0)
    return spans


def bench_trace(trials=5, cp_trials=7):
    """Causal-tracing section (docs/OBSERVABILITY.md "Causal tracing"):
    the per-RPC context-propagation cost (inject + extract, the tax
    every hop pays) and the critical-path analysis cost at 1k / 10k
    spans (the ``perf --critical-path`` / fleet-sweep consumer side).
    Host-side and self-contained; the ns/ms keys are direction-
    classified (lower better) for ``perf --trajectory``."""
    from metisfl_tpu.telemetry import causal as tcausal
    from metisfl_tpu.telemetry import trace as ttrace

    ttrace.configure(enabled=True, service="bench-trace", dir="")
    propagate_ns = min(tcausal._propagation_overhead_ns(iters=20000)
                       for _ in range(trials))
    out = {"trace_propagate_ns": round(propagate_ns, 1)}
    for label, n in (("1k", 1000), ("10k", 10000)):
        spans = _synth_trace(n)
        times = []
        for _ in range(cp_trials):
            t0 = time.perf_counter()
            cp = tcausal.critical_path(spans)
            times.append((time.perf_counter() - t0) * 1e3)
        assert cp is not None and cp["edges"], "walk must attribute"
        out[f"trace_critical_path_{label}_ms"] = round(min(times), 3)
        out[f"trace_spans_{label}"] = len(spans)
    out["trace_coverage_synth"] = round(cp["coverage"], 4)
    return out


_SECTIONS = {
    "train": lambda a: bench_train_step(),
    "ckks": lambda a: bench_secure_ckks(),
    "store": lambda a: bench_store(),
    "mfu": lambda a: bench_mfu(on_update=a),
    "flash": lambda a: bench_flash(on_update=a),
    "decode": lambda a: bench_decode(),
    "e2e": lambda a: bench_e2e_round(),
    "cohort": lambda a: bench_cohort(),
    "health": lambda a: bench_health(),
    "serving": lambda a: bench_serving(),
    "churn": lambda a: bench_churn(),
    "obs": lambda a: bench_obs(),
    "fabric": lambda a: bench_fabric(),
    "prof": lambda a: bench_prof(),
    "tree_dist": lambda a: bench_tree_dist(),
    "secure": lambda a: bench_secure(),
    "fleet": lambda a: bench_fleet(),
    "trace": lambda a: bench_trace(),
    "runtime": lambda a: bench_runtime(),
    "lora": lambda a: bench_lora(),
}


def _require_tpu(section: str) -> None:
    """Device sections measure the chip: anything else is an error, never
    an empty result and never a CPU number under a device metric's name."""
    import jax

    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"bench section {section!r} measures the TPU and found backend "
            f"{jax.default_backend()!r}")


def _run_section_child(name: str, out_path: str, quick: bool,
                       variant: str = None) -> int:
    """Child mode: run ONE section, streaming partial results to
    ``out_path`` (write + atomic rename) so a section killed at its
    timeout still leaves everything measured so far for the parent. One
    child per device section is also one process per chip: the parent
    never initializes a backend."""
    def dump(d):
        tmp = out_path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(d, fh)
        os.replace(tmp, out_path)

    if name in _DEVICE_SECTIONS and not quick:
        _require_tpu(name)
    if name == "agg":
        num_learners = 8 if quick else NUM_LEARNERS
        rounds = 2 if quick else ROUNDS
        out = bench_aggregation(num_learners, rounds, STRIDE)
    elif name == "mfu" and variant:
        out = bench_mfu(on_update=dump, only=variant)
    else:
        out = _SECTIONS[name](dump)
    if out:
        import jax
        out["backend"] = jax.default_backend()
        out["devices"] = len(jax.devices())
    dump(out)
    return 0


def _run_section(name: str, quick: bool, timeout: int, errors: dict,
                 variant: str = None, err_key: str = None) -> dict:
    """Run a section in a subprocess; on timeout the child is killed and
    whatever partials it streamed out are kept. A child that fails (a
    device section without a chip included) lands in ``errors``, and any
    entry there makes the bench exit non-zero."""
    import tempfile

    err_key = err_key or name
    fd, out_path = tempfile.mkstemp(suffix=f".bench.{name}.json")
    os.close(fd)
    os.unlink(out_path)
    argv = [sys.executable, os.path.abspath(__file__),
            "--section", name, "--out", out_path]
    if variant:
        argv += ["--variant", variant]
    if quick:
        argv.append("--quick")
    proc = subprocess.Popen(argv, stdout=subprocess.DEVNULL,
                            stderr=subprocess.PIPE, text=True)
    try:
        _, stderr = proc.communicate(timeout=timeout)
        if proc.returncode != 0:
            errors[err_key] = (stderr or "")[-400:] or f"rc={proc.returncode}"
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        errors[err_key] = f"section timed out after {timeout}s (killed)"
    try:
        with open(out_path) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return {}
    finally:
        try:
            os.unlink(out_path)
        except OSError:
            pass


# bench capture schema (trajectory tooling: python -m metisfl_tpu.perf).
# v2 adds the schema_version key and the final single-line marker below.
SCHEMA_VERSION = 2
# the marker prefix the perf CLI's capture parser anchors on — one
# definition, shared with the parser (metisfl_tpu.perf is stdlib-only)
from metisfl_tpu.perf import BENCH_MARKER  # noqa: E402


def _emit(result) -> None:
    print(json.dumps(result), flush=True)
    # Final single-line marker, ALWAYS last on stdout: capture harnesses
    # keep only a bounded tail, and a truncated main result line leaves
    # the whole run unparseable. The marker is small enough to survive
    # any tail window and carries the headline numbers. Keys mirror the
    # top-level result keys.
    marker = {
        "schema_version": result.get("schema_version", SCHEMA_VERSION),
        "metric": result.get("metric", ""),
        "value": result.get("value", 0.0),
        "unit": result.get("unit", ""),
        "vs_baseline": result.get("vs_baseline", 0.0),
        "errors": len(result.get("details", {}).get("errors", {}) or {}),
    }
    if "mfu" in result:
        marker["mfu"] = result["mfu"]
    if result.get("host"):
        # host provenance must survive tail truncation too: a
        # marker-only capture still declares where it ran, so the
        # cross-host comparison rule keeps applying
        marker["host"] = result["host"]
    backend = result.get("details", {}).get("backend")
    if backend:
        marker["backend"] = backend
    print(BENCH_MARKER + json.dumps(marker), flush=True)


def _result_from(details, errors, num_learners):
    value = details.get("ms_per_round_median", 0.0)
    result = {
        "schema_version": SCHEMA_VERSION,
        "metric": f"aggregation_ms_per_round_{num_learners}learners",
        "value": round(value, 2),
        "unit": "ms",
        "vs_baseline": round(BASELINE_MS / value, 2) if value else 0.0,
        # host provenance: perf gates regressions only between captures
        # naming the SAME host (absolute RSS/disk keys are incomparable
        # across a hardware move); override for stable fleet identities
        "host": os.environ.get("METISFL_BENCH_HOST")
        or platform_mod.node(),
        "details": dict(details),
    }
    if "mfu" in details:
        result["mfu"] = details["mfu"]
    if errors:
        result["details"]["errors"] = dict(errors)
    return result


# per-section kill timeouts (full mode): generous for compile-heavy
# sections, bounded so one hung section cannot eat the whole run
_SECTION_TIMEOUTS = {"agg": 600, "train": 300, "ckks": 240, "store": 240,
                     "mfu": 1500, "flash": 900, "decode": 600,
                     "e2e": 600, "cohort": 1200, "health": 240,
                     "serving": 300, "churn": 240, "obs": 240,
                     "fabric": 240, "prof": 240, "tree_dist": 300,
                     "secure": 240, "fleet": 300, "trace": 240,
                     "runtime": 300,
                     "lora": 600}
# the MFU sweep runs one child per variant (see _run_mfu_variants); a
# single variant — one 201M-param compile + a handful of steps — gets this
# much of the section budget
_MFU_VARIANT_TIMEOUT = 420

# sections that run on the chip, headline first (aggregation @64, LM MFU);
# the 1.2B-param lora compile is the heaviest, so it goes last
_DEVICE_SECTIONS = ("agg", "mfu", "e2e", "train", "flash", "decode", "lora")
# host-only sections: they time the host planes on whatever CPU is at hand
_HOST_SECTIONS = ("ckks", "store", "cohort", "health", "serving", "churn",
                  "obs", "fabric", "prof", "tree_dist", "secure", "fleet",
                  "trace", "runtime")


# Bench noise floor (ISSUE 13 satellite): ms-scale keys on gVisor-class
# hosts exceed the 20% regression gate run-to-run (the r06→r07
# obs_expose_ms_10k_exact flag was pure noise). Host sections whose keys
# land under the threshold are re-run K-1 more times and those keys
# report the per-key MEDIAN; the capture records {key: K} in
# details["repeats"] so `perf --compare` can mark gated medians (xK).
_REPEAT_DEFAULT_K = 3
_REPEAT_MS_THRESHOLD = 50.0


def _repeat_config():
    try:
        k = int(os.environ.get("METISFL_BENCH_REPEATS", "")
                or _REPEAT_DEFAULT_K)
    except ValueError:
        k = _REPEAT_DEFAULT_K
    try:
        thr = float(os.environ.get("METISFL_BENCH_REPEAT_MS", "")
                    or _REPEAT_MS_THRESHOLD)
    except ValueError:
        thr = _REPEAT_MS_THRESHOLD
    return max(1, k), thr


def _repeat_noisy_keys(name: str, first: dict, quick: bool,
                       details: dict) -> None:
    """Median-of-K for a host section's sub-threshold ms keys: re-run the
    section's child up to K-1 more times and replace each noisy key with
    the median of its samples. A failing repeat run only costs its own
    samples (its errors are discarded — the first, recorded pass stands);
    device sections never repeat (chip time is the scarce resource)."""
    k, thr = _repeat_config()
    if k < 2:
        return
    keys = [key for key, value in first.items()
            if isinstance(value, (int, float))
            and not isinstance(value, bool)
            and "_ms" in key and 0.0 < float(value) <= thr]
    if not keys:
        return
    samples = {key: [float(first[key])] for key in keys}
    for _ in range(k - 1):
        rerun_errors: dict = {}
        out = _run_section(name, quick, _SECTION_TIMEOUTS[name],
                           rerun_errors)
        for key in keys:
            value = out.get(key)
            if isinstance(value, (int, float)) \
                    and not isinstance(value, bool):
                samples[key].append(float(value))
    repeats = details.setdefault("repeats", {})
    for key in keys:
        if len(samples[key]) < 2:
            continue  # repeats failed to re-measure it: single shot stands
        details[key] = round(statistics.median(samples[key]), 4)
        repeats[key] = len(samples[key])


def _run_and_record(name: str, quick: bool, details: dict,
                    errors: dict) -> None:
    """One section in its own child, merged into ``details``."""
    if name == "mfu" and not quick:
        _run_mfu_variants(quick, details, errors)
        return
    out = _run_section(name, quick, _SECTION_TIMEOUTS[name], errors)
    if "backend" in out:
        details[f"{name}_backend"] = out["backend"]
    details.update(out)
    if name in _HOST_SECTIONS and name not in errors:
        # noise floor: sub-threshold ms keys re-measure median-of-K
        _repeat_noisy_keys(name, out, quick, details)


def _run_mfu_variants(quick: bool, details: dict, errors: dict) -> None:
    """The MFU sweep, one child per variant: each variant is its own
    201M-parameter compile, so a variant that fails or times out costs
    itself, not the variants measured before it. The section budget
    _SECTION_TIMEOUTS['mfu'] caps the sweep cumulatively; each variant
    gets at most _MFU_VARIANT_TIMEOUT of it. The parent computes the
    best-variant rollup (children see only their own variant)."""
    deadline = time.time() + _SECTION_TIMEOUTS["mfu"]
    for label, _ in _MFU_VARIANTS:
        remaining = deadline - time.time()
        if remaining <= 30:
            errors["mfu"] = "section budget exhausted before all variants"
            break
        out = _run_section("mfu", quick,
                           int(min(_MFU_VARIANT_TIMEOUT, remaining)),
                           errors, variant=label, err_key=f"mfu.{label}")
        if "backend" in out:
            details["mfu_backend"] = out.pop("backend")
        details.update(out)
    _mfu_finalize(details)


def run_bench(quick: bool):
    num_learners = 8 if quick else NUM_LEARNERS
    rounds = 2 if quick else ROUNDS
    details: dict = {}
    errors: dict = {}

    if not quick:
        # full mode: every section in its own child process; this parent
        # never initializes a backend, so each device section's child is
        # the one process that owns the chip while it runs
        for name in _DEVICE_SECTIONS + _HOST_SECTIONS:
            _run_and_record(name, quick, details, errors)
        return _result_from(details, errors, num_learners)

    # quick mode: the in-process CPU plumbing check the tests use (small
    # sizes, aggregation + CKKS only); its output says which platform ran
    import jax

    details.update(bench_aggregation(num_learners, rounds, STRIDE))
    details.update(bench_secure_ckks())
    details["platform"] = jax.default_backend()
    details["backend"] = jax.default_backend()
    details["devices"] = len(jax.devices())
    return _result_from(details, errors, num_learners)


def main():
    t_start = time.time()
    import argparse

    from metisfl_tpu.platform import enter_process
    enter_process()

    parser = argparse.ArgumentParser("bench")
    parser.add_argument("--quick", action="store_true",
                        help="small sizes, in-process, for the CPU "
                             "plumbing check (the full bench runs every "
                             "section in its own child, device sections "
                             "on the TPU or not at all)")
    parser.add_argument("--section", choices=["agg", *_SECTIONS],
                        help="internal: run ONE section (child mode)")
    parser.add_argument("--out", help="internal: child-mode output path")
    parser.add_argument("--variant",
                        help="internal: single MFU sweep variant")
    args, _ = parser.parse_known_args()

    if args.section:
        return _run_section_child(args.section, args.out, args.quick,
                                  args.variant)

    result = run_bench(args.quick)
    # full mode: sections report their own backend (<name>_backend) —
    # querying jax here would take the chip in the one process that must
    # leave it to its children
    result["details"]["peak_rss_kb"] = resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss
    result["details"]["bench_wall_s"] = round(time.time() - t_start, 1)
    _emit(result)
    # a failed section (a device section that found no TPU included) is a
    # failed bench: the capture still prints, the exit code says so
    return 1 if result["details"].get("errors") else 0


if __name__ == "__main__":
    sys.exit(main())
