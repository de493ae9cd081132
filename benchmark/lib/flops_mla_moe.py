"""Operations and bytes of the latent-attention decoder with a share of
its routed experts (family ``mla_moe``), from shapes alone: the numerators
of ``mla_moe_step_mfu``, ``mla_flash_roofline`` and
``moe_experts_roofline``. Recomputation (remat) is never credited to the
model; a kernel's own recomputation is the kernel's work. Of the routed
experts only the assignments that fall on held experts count: the shapes
expect ``tokens * top_k * held / experts`` a layer, and the program's
counter (``moe_local_count``) gives what a run really had. Checked against
XLA's ``cost_analysis()`` at toy depth in ``tests/test_mla_moe.py``."""

from __future__ import annotations


def _dims(cfg: dict) -> dict:
    L, dense = int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"])
    return dict(
        d=int(cfg["hidden_size"]), H=int(cfg["num_attention_heads"]),
        qr=int(cfg["q_lora_rank"]), kvr=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        vd=int(cfg["v_head_dim"]), f=int(cfg["intermediate_size"]),
        m=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["n_shared_experts"]),
        E=int(cfg["published"]["n_routed_experts"]),
        K=int(cfg["num_experts_per_tok"]),
        held=int(cfg["experts_held"]["count"]),
        rank=int(cfg.get("lora", {}).get("rank", 0)),
        vocab=int(cfg["vocab_size"]), dense=dense, moe=L - dense, L=L)


def mla_flops_per_token(cfg: dict, context: float) -> float:
    """One block's latent attention: the five projections with adapters
    on four, and the score (nope + rope wide) and value (v wide) products
    over ``context`` keys."""
    s = _dims(cfg)
    d, H, qr, kvr, rank = s["d"], s["H"], s["qr"], s["kvr"], s["rank"]
    qk, kv = s["nope"] + s["rope"], s["nope"] + s["vd"]
    mm = (d * qr + qr * H * qk + d * (kvr + s["rope"]) + kvr * H * kv
          + H * s["vd"] * d)
    mm += rank * ((d + qr) + (qr + H * qk) + (d + kvr + s["rope"])
                  + (kvr + H * kv))
    return 2.0 * mm + 2.0 * H * (qk + s["vd"]) * context


def expected_local(cfg: dict, tokens: int) -> float:
    """Assignments a layer that the shapes expect on held experts."""
    s = _dims(cfg)
    return tokens * s["K"] * s["held"] / s["E"]


def expert_layer_flops_per_token(cfg: dict) -> float:
    """Router, shared expert and the active held experts of one token."""
    s = _dims(cfg)
    one = 3.0 * s["d"] * s["m"]
    return 2.0 * (s["d"] * s["E"] + s["shared"] * one
                  + s["K"] * s["held"] / s["E"] * one)


def forward_flops(cfg: dict, batch: int, seq: int) -> float:
    """One causal forward pass over ``batch`` rows of ``seq`` tokens."""
    s = _dims(cfg)
    attn = mla_flops_per_token(cfg, (seq + 1) / 2)
    per_token = (s["L"] * attn + s["dense"] * 2.0 * 3 * s["d"] * s["f"]
                 + s["moe"] * expert_layer_flops_per_token(cfg)
                 + 2.0 * s["d"] * s["vocab"])
    return batch * seq * per_token


def train_step_flops(cfg: dict, shape: dict) -> float:
    """Model FLOPs of one optimizer step; adapter training pays weight
    gradients for the adapters alone, so forward plus activation
    gradients, 2 x forward (``lib/flops.py`` has the same rule)."""
    fwd = forward_flops(cfg, int(shape["batch"]), int(shape["seq"]))
    return (2.0 if cfg.get("lora", {}).get("rank") else 3.0) * fwd


def mla_flash_cost(cfg: dict, shape: dict, remat: bool) -> dict:
    """The flash kernels' work in one optimizer step: forward (twice when
    the block is rematerialized) and backward, every block. Causal: half
    the score matrix. A score-wide product (q k^T, dq, dk) contracts or
    produces nope + rope columns, a value-wide one (p v, dp, dv) v's:
    forward one of each, backward three and two (the scores recomputed
    once). Bytes: q, k, v, o (and their gradients backward) once each in
    the compute type, at their own widths, nothing padded."""
    s = _dims(cfg)
    B, T = int(shape["batch"]), int(shape["seq"])
    qk, vd = s["nope"] + s["rope"], s["vd"]
    half = 2.0 * B * s["H"] * T * T / 2          # one T x T x 1 product
    fwd_flops = half * (qk + vd)
    bwd_flops = half * (3 * qk + 2 * vd)
    wide, narrow = 2.0 * B * s["H"] * T * qk, 2.0 * B * s["H"] * T * vd
    fwd_bytes = 2 * wide + 2 * narrow             # q k, v o
    bwd_bytes = 4 * wide + 4 * narrow             # q k dq dk, v o do dv
    n_fwd = 2 if remat else 1
    return {"flops": s["L"] * (n_fwd * fwd_flops + bwd_flops),
            "bytes": s["L"] * (n_fwd * fwd_bytes + bwd_bytes)}


def moe_experts_cost(cfg: dict, local: float, remat: bool) -> dict:
    """The routed experts' products in one optimizer step, with ``local``
    held assignments summed over the expert layers: three products a
    pass (gate, up, down), the forward (twice under remat) and the
    backward to the activations (the experts are frozen: no weight
    gradient). Bytes: every held expert's three matrices read once a pass
    in the type they are held in, which bounds the product at this cell's
    85 rows an expert, and the rows in and out of each product."""
    s = _dims(cfg)
    passes = (2 if remat else 1) + 1
    one = 3.0 * s["d"] * s["m"]
    weights = 2.0 * s["moe"] * s["held"] * one
    rows = 2.0 * local * (2 * s["d"] + 4 * s["m"])    # x, g, u / act, y
    return {"flops": passes * 2.0 * local * one,
            "bytes": passes * (weights + rows)}
