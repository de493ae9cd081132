"""Operations and bytes of the attention/state-space hybrid (family
``jamba``), from shapes alone: the numerators of ``hybrid_step_mfu`` and
``ssm_scan_roofline``. Recomputation (remat) is never credited to the
model; a kernel's own recomputation is the kernel's work. Checked against
XLA's ``cost_analysis()`` at toy depth in ``tests/test_hybrid.py``."""

from __future__ import annotations


def _dims(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    L = int(cfg["num_hidden_layers"])
    attn = sum(1 for l in range(L)
               if (l - int(cfg["attn_layer_offset"]))
               % int(cfg["attn_layer_period"]) == 0)
    return dict(d=d, kvd=int(cfg["num_key_value_heads"]) * (d // heads),
                f=int(cfg["intermediate_size"]),
                di=int(cfg["mamba_expand"]) * d,
                n=int(cfg["mamba_d_state"]), k=int(cfg["mamba_d_conv"]),
                r=int(cfg["mamba_dt_rank"]),
                rank=int(cfg.get("lora", {}).get("rank", 0)),
                vocab=int(cfg["vocab_size"]), attn=attn, mamba=L - attn)


# multiply-adds of one position of the recurrence for one (channel, state)
# pair: decay . S, + drive, drive = (dt x) . B, S . C and its sum
SCAN_FLOPS_FWD = 6.0
# the backward's: the adjoint recurrence, dB, dC, d(dt), dx, dA
SCAN_FLOPS_BWD = 16.0


def mamba_layer_flops_per_token(cfg: dict) -> float:
    """Forward FLOPs of one token in one Mamba block's mixer: the four
    projections with their adapters, the convolution, the recurrence."""
    s = _dims(cfg)
    d, di, n, k, r, rank = s["d"], s["di"], s["n"], s["k"], s["r"], s["rank"]
    mm = d * 2 * di + di * (r + 2 * n) + r * di + di * d
    mm += rank * (d + 2 * di) + rank * (di + d)          # adapters in, out
    return 2.0 * (mm + k * di) + SCAN_FLOPS_FWD * di * n


def attention_layer_flops_per_token(cfg: dict, context: float) -> float:
    """One attention block's mixer: MQA-aware projections with adapters on
    q and v, and the score and value products over ``context`` keys."""
    s = _dims(cfg)
    d, kvd, rank = s["d"], s["kvd"], s["rank"]
    mm = 2 * d * d + 2 * d * kvd + rank * (2 * d + d + kvd)
    return 2.0 * mm + 2.0 * 2.0 * d * context


def hybrid_forward_flops(cfg: dict, batch: int, seq: int) -> float:
    """One causal forward pass over ``batch`` rows of ``seq`` tokens."""
    s = _dims(cfg)
    mlp = 2.0 * 3 * s["d"] * s["f"]
    per_token = (s["mamba"] * (mamba_layer_flops_per_token(cfg) + mlp)
                 + s["attn"] * (attention_layer_flops_per_token(
                     cfg, (seq + 1) / 2) + mlp)
                 + 2.0 * s["d"] * s["vocab"])             # the tied head
    return batch * seq * per_token


def train_step_flops(cfg: dict, shape: dict) -> float:
    """Model FLOPs of one optimizer step; adapter training pays weight
    gradients for the adapters alone, so forward plus activation
    gradients, 2 x forward (``lib/flops.py`` has the same rule)."""
    fwd = hybrid_forward_flops(cfg, int(shape["batch"]), int(shape["seq"]))
    return (2.0 if cfg.get("lora", {}).get("rank") else 3.0) * fwd


def ssm_scan_cost(cfg: dict, shape: dict, remat: bool) -> dict:
    """The scan kernels' work in one optimizer step: forward (twice when
    the block is rematerialized) and backward, over every Mamba block.
    Bytes: what the recurrence has to read and write once, in float32,
    since the configuration keeps every one of them there
    (``float32_in_the_program``: the convolution's output x, delta, B, C,
    A, and y into the float32 gate): x and delta in and y out forward; x,
    delta and dy in and dx and d(delta) out backward; B, C, A and their
    gradients. The kernels move the chunk-boundary states and B, C
    broadcast along lanes besides: that is their cost, not the
    algorithm's."""
    s = _dims(cfg)
    B, T = int(shape["batch"]), int(shape["seq"])
    cells = float(B) * T * s["di"] * s["n"]
    rows = 4.0 * B * T * s["di"]                 # one (B, T, d_inner) pass
    small = 4.0 * (2 * B * T * s["n"] + s["di"] * s["n"])  # B, C, A
    fwd_bytes = 3 * rows + small
    bwd_bytes = 5 * rows + 2 * small
    n_fwd = 2 if remat else 1
    return {"flops": s["mamba"] * cells * (n_fwd * SCAN_FLOPS_FWD
                                           + SCAN_FLOPS_FWD
                                           + SCAN_FLOPS_BWD),
            "bytes": s["mamba"] * (n_fwd * fwd_bytes + bwd_bytes)}
