"""Operations and bytes of the GLM-5 hybrid (family ``kda_moe``), from
shapes alone: the numerators of ``kda_moe_step_mfu`` and ``kda_roofline``.
A layer is a mixer and an FFN, each under mHC. The mixer is KDA (its
projections and its chunked recurrence) or sparse latent attention over
the pairs the pooling indexer chose (the shapes expect
:func:`selected_pairs` a row and layer; the program's counter
``dsa_selected_count`` gives what a run really had, ties included); the
FFN a dense SwiGLU or a router, a shared expert and the held experts'
products for the assignments that fall on held experts (``tokens * top_k
* held / experts`` a layer, or the counter ``moe_local_count``).
Recomputation (remat) is never credited to the model; a kernel's own
recomputation is the kernel's work. The indexer has no gradient: it is
counted once a step. Checked against XLA's ``cost_analysis()`` at toy
depth in ``tests/test_kda_moe.py``."""

from __future__ import annotations

# the chunked recurrence's chunk (``metisfl_tpu.ops.delta_attention``)
CHUNK = 64


def _dims(cfg: dict) -> dict:
    la = cfg["linear_attn_config"]
    kept = [int(i) for i in cfg["layers_kept"]]
    mixers = [cfg["layer_types"][i] for i in kept]
    ffns = [cfg["mlp_layer_types"][i] for i in kept]
    return dict(
        d=int(cfg["hidden_size"]), H=int(la["num_heads"]),
        D=int(la["head_dim"]), conv=int(la["short_conv_kernel_size"]),
        r=int(cfg["kda_gate_rank"]), Hm=int(cfg["num_attention_heads"]),
        qr=int(cfg["q_lora_rank"]), kvr=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), vd=int(cfg["v_head_dim"]),
        Hi=int(cfg["index_n_heads"]), Di=int(cfg["index_head_dim"]),
        topk=int(cfg["index_topk"]), pool=int(cfg["index_kpool"]),
        f=int(cfg["intermediate_size"]), m=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["n_shared_experts"]),
        E=int(cfg["published"]["n_routed_experts"]),
        K=int(cfg["num_experts_per_tok"]),
        held=int(cfg["experts_held"]["count"]), n=int(cfg["hc_mult"]),
        rank=int(cfg.get("lora", {}).get("rank", 0)),
        vocab=int(cfg["vocab_size"]),
        kda=mixers.count("linear_attention"),
        mla=mixers.count("deepseek_sparse_attention"),
        dense=ffns.count("dense"), moe=ffns.count("sparse"),
        layers=len(kept))


def selected_pairs(seq: int, topk: int, pool: int) -> int:
    """Chosen (query, key) pairs of one row and layer: query ``t`` takes
    ``pool`` keys of each of ``min(complete groups, topk / pool)`` groups,
    and its incomplete group's ``(t + 1) % pool`` keys."""
    return sum(min((t + 1) // pool, topk // pool) * pool + (t + 1) % pool
               for t in range(seq))


def complete_groups(seq: int, pool: int) -> int:
    """(query, group) pairs the indexer scores in a row and layer."""
    return sum((t + 1) // pool for t in range(seq))


def expected_counts(cfg: dict, batch: int, seq: int) -> tuple:
    """(chosen pairs, held assignments), summed over the layers, that the
    shapes expect."""
    s = _dims(cfg)
    return (s["mla"] * batch * selected_pairs(seq, s["topk"], s["pool"]),
            s["moe"] * batch * seq * s["K"] * s["held"] / s["E"])


def recurrence_flops(cfg: dict) -> float:
    """FLOPs a head and position of KDA's chunked recurrence (one pass):
    the chunk's scores and key products, the solve's two products and the
    intra-chunk output (``10 C D``), the state read twice and written once
    (``6 D^2``), the chunk's triangular inverse (``C^2 / 3``)."""
    D = _dims(cfg)["D"]
    return 10.0 * CHUNK * D + 6.0 * D * D + CHUNK * CHUNK / 3.0


def forward_flops(cfg: dict, batch: int, seq: int, selected=None,
                  local=None) -> dict:
    """One forward pass over ``batch`` rows of ``seq`` tokens with
    ``selected`` chosen pairs and ``local`` held assignments summed over
    the layers (the shapes' expectation where not given), split into what
    has a backward (``trained``) and the indexer, which has none."""
    s = _dims(cfg)
    want_sel, want_local = expected_counts(cfg, batch, seq)
    selected = want_sel if selected is None else selected
    local = want_local if local is None else local
    d, H, D, r, rank = s["d"], s["H"], s["D"], s["r"], s["rank"]
    W = H * D
    tokens = batch * seq
    kda = (4 * d * W + 2 * (d * r + r * W) + d * H + 3 * s["conv"] * W
           + rank * 2 * (d + W))
    Hm, nope, vd = s["Hm"], s["nope"], s["vd"]
    mla = (d * s["qr"] + s["qr"] * Hm * nope + d * s["kvr"]
           + s["kvr"] * Hm * (nope + vd) + Hm * vd * d
           + rank * (d + s["qr"] + s["qr"] + Hm * nope + d + s["kvr"]
                     + s["kvr"] + Hm * (nope + vd)))
    n = s["n"]
    hyper = n * d * (2 * n + n * n) + n * n * d + 2 * n * d
    ffn = (s["dense"] * 3 * d * s["f"]
           + s["moe"] * (d * s["E"] + 3 * d * s["m"] * s["shared"]))
    per_token = (2.0 * (s["kda"] * kda + s["mla"] * mla + ffn
                        + 2 * s["layers"] * hyper + d * s["vocab"])
                 + s["kda"] * H * recurrence_flops(cfg))
    trained = (tokens * per_token + selected * 2.0 * Hm * (nope + vd)
               + local * 2.0 * 3 * d * s["m"])
    index = (s["mla"] * tokens * 2.0 * (s["qr"] * s["Hi"] * s["Di"]
                                        + d * (s["Di"] + s["Hi"]))
             + s["mla"] * batch * complete_groups(seq, s["pool"]) * 2.0
             * s["Hi"] * s["Di"])
    return {"trained": trained, "indexer": index}


def train_step_flops(cfg: dict, shape: dict, selected=None,
                     local=None) -> float:
    """Model FLOPs of one optimizer step: adapter training pays weight
    gradients for the adapters alone, so forward plus activation gradients,
    2 x forward (``lib/flops.py`` has the same rule), but for the indexer,
    whose choice carries no gradient: once."""
    fwd = forward_flops(cfg, int(shape["batch"]), int(shape["seq"]),
                        selected, local)
    return 2.0 * fwd["trained"] + fwd["indexer"]


def kda_cost(cfg: dict, shape: dict, remat: bool) -> dict:
    """The KDA kernels' least work in one optimizer step: the chunked
    recurrence forward (twice when the layer is rematerialized) and its
    backward (two products for each forward one), every KDA layer.
    Bytes at the types the program holds: a pass forward reads q, k, v
    (bfloat16), g (float32, a channel), beta (float32, a head) and writes
    o (bfloat16); the backward reads the same and do, and writes dq, dk,
    dv, dg and dbeta. What an implementation keeps between the two (the
    chunks' states) is not the work."""
    s = _dims(cfg)
    heads = s["kda"] * int(shape["batch"]) * int(shape["seq"]) * s["H"]
    D = s["D"]
    fwd_bytes = 2 * 3 * D + 4 * D + 4 + 2 * D
    bwd_bytes = 2 * (2 * 3 * D + 4 * D + 4) + 2 * D
    n_fwd = 2 if remat else 1
    return {"flops": heads * recurrence_flops(cfg) * (n_fwd + 2),
            "bytes": heads * (n_fwd * fwd_bytes + bwd_bytes)}
