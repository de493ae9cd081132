"""Operations and bytes the algorithm needs, from shapes alone: the
numerators of every ``*_mfu`` and ``*_roofline`` metric. Recomputation
(remat) is never credited. Checked against XLA's ``cost_analysis()`` at toy
depth in ``benchmark/tests``."""

from __future__ import annotations


def decoder_matmul_flops_per_token(cfg: dict) -> float:
    """Forward matrix-product FLOPs of one token outside attention's
    score and value products (GQA-aware: wk and wv at KV width)."""
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    kvd = int(cfg.get("num_key_value_heads") or heads) * (d // heads)
    f, L = int(cfg["intermediate_size"]), int(cfg["num_hidden_layers"])
    r = int(cfg.get("lora", {}).get("rank", 0))
    per_layer = 2 * d * d + 2 * d * kvd + 3 * d * f      # wq wo, wk wv, mlp
    per_layer += r * (2 * d + d + kvd)                   # adapters on q, v
    return 2.0 * (L * per_layer + d * int(cfg["vocab_size"]))


def decoder_attention_flops_per_token(cfg: dict, context: float) -> float:
    """Score and value products of one token attending ``context`` keys."""
    d, L = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    return 2.0 * 2.0 * d * context * L


def decoder_forward_flops(cfg: dict, batch: int, seq: int) -> float:
    """One causal forward pass over ``batch`` rows of ``seq`` tokens."""
    tokens = batch * seq
    return tokens * (decoder_matmul_flops_per_token(cfg)
                     + decoder_attention_flops_per_token(cfg, (seq + 1) / 2))


def vit_forward_flops(cfg: dict, batch: int) -> float:
    d, f = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    L = int(cfg["num_hidden_layers"])
    p, ch = int(cfg["patch_size"]), int(cfg["num_channels"])
    T = (int(cfg["image_size"]) // p) ** 2
    per_token = L * (4 * d * d + 2 * d * f + 2 * d * T) + p * p * ch * d
    return 2.0 * batch * (T * per_token + d * int(cfg["num_labels"]))


def train_step_flops(cfg: dict, shape: dict) -> float:
    """Model FLOPs of one optimizer step: forward plus backward. Full
    training pays the backward twice (activation and weight gradients),
    3 x forward; adapter training pays weight gradients only for the
    adapters, so forward plus activation gradients, 2 x forward."""
    batch = int(shape["batch"])
    if cfg["family"] == "decoder_lm":
        fwd = decoder_forward_flops(cfg, batch, int(shape["seq"]))
        return (2.0 if cfg.get("lora", {}).get("rank") else 3.0) * fwd
    if cfg["family"] == "vit":
        return 3.0 * vit_forward_flops(cfg, batch)
    raise KeyError(f"no FLOPs function for family {cfg['family']!r}")


def serve_flops(cfg: dict, requests: list) -> float:
    """Model FLOPs of serving ``requests`` (each ``prompt_len``,
    ``out_len``): every prompt token and every output token that is fed
    back, each at its own context length."""
    mm = decoder_matmul_flops_per_token(cfg)
    total = 0.0
    for r in requests:
        n = int(r["prompt_len"]) + int(r["out_len"]) - 1   # tokens processed
        total += n * mm + decoder_attention_flops_per_token(
            cfg, (n + 1) / 2) * n
    return total


def flash_attention_cost(cfg: dict, shape: dict, remat: bool) -> dict:
    """The flash kernels' work in one optimizer step: forward (twice when
    the block is rematerialized: that recomputation is the kernel's own
    time, so it is the kernel's work) and backward. Causal: half the
    score matrix. Bytes: q, k, v, o (and their gradients backward) once
    each in the compute type."""
    d, L = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    heads = int(cfg["num_attention_heads"])
    hd = d // heads
    kv = int(cfg.get("num_key_value_heads") or heads)
    B, T = int(shape["batch"]), int(shape["seq"])
    pair = 2.0 * B * heads * T * T * hd / 2          # one T x T x hd product
    fwd_flops, bwd_flops = 2 * pair, 5 * pair
    q_bytes = 2.0 * B * heads * T * hd
    kv_bytes = 2.0 * B * kv * T * hd
    fwd_bytes = 2 * q_bytes + 2 * kv_bytes           # q o, k v
    bwd_bytes = 4 * q_bytes + 4 * kv_bytes           # q o do dq, k v dk dv
    n_fwd = 2 if remat else 1
    return {"flops": L * (n_fwd * fwd_flops + bwd_flops),
            "bytes": L * (n_fwd * fwd_bytes + bwd_bytes)}
