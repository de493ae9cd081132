"""Operations and bytes of the decoder with learned sparse attention and
routed experts (family ``dsa_moe``), from shapes alone: the numerators of
``dsa_step_mfu``, ``dsa_flash_roofline``, ``dsa_index_roofline`` and
``dsa_experts_roofline``. A
layer is GQA attention over the pairs the indexer chose, the indexer over
every causal pair, a router and the held experts' products. Of attention
only the chosen pairs count (the shapes expect ``sum_t min(t + 1, topk)``
a row and layer; the program's counter ``dsa_selected_count`` gives what
a run really had, ties included); of the routed experts only the
assignments that fall on held experts (``tokens * top_k * held /
experts`` a layer, or the counter ``moe_local_count``). Recomputation
(remat) is never credited to the model; a kernel's own recomputation is
the kernel's work. The indexer has no gradient: it is counted once a step.
Checked against XLA's ``cost_analysis()`` at toy depth in
``tests/test_dsa.py``."""

from __future__ import annotations


def _dims(cfg: dict) -> dict:
    sa = cfg["sa_config"]
    return dict(
        d=int(cfg["hidden_size"]), H=int(cfg["num_attention_heads"]),
        kv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        m=int(cfg["moe_intermediate_size"]),
        E=int(cfg["published"]["num_experts"]),
        K=int(cfg["num_experts_per_tok"]),
        held=int(cfg["experts_held"]["count"]),
        Hi=int(sa["indexer_num_heads"]), Di=int(sa["indexer_head_dim"]),
        topk=int(sa["topk"]), rank=int(cfg.get("lora", {}).get("rank", 0)),
        vocab=int(cfg["vocab_size"]), L=int(cfg["num_hidden_layers"]))


def selected_pairs(seq: int, topk: int) -> int:
    """Chosen (query, key) pairs of one row and layer: ``min(t + 1,
    topk)`` for query ``t``."""
    head = min(seq, topk)
    return head * (head + 1) // 2 + (seq - head) * topk


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def expected_counts(cfg: dict, batch: int, seq: int) -> tuple:
    """(chosen pairs, held assignments), summed over the layers, that the
    shapes expect."""
    s = _dims(cfg)
    return (s["L"] * batch * selected_pairs(seq, s["topk"]),
            s["L"] * batch * seq * s["K"] * s["held"] / s["E"])


def indexer_projection_params(cfg: dict) -> int:
    s = _dims(cfg)
    return s["d"] * (s["Hi"] * s["Di"] + s["Di"] + s["Hi"])


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
    d, H, kv, hd = s["d"], s["H"], s["kv"], s["hd"]
    tokens = batch * seq
    attn = d * H * hd * 2 + d * kv * hd * 2 + s["rank"] * (
        (d + H * hd) + (d + kv * hd))
    per_token = s["L"] * 2.0 * (attn + d * s["E"]) + 2.0 * d * s["vocab"]
    trained = (tokens * per_token + selected * 4.0 * H * hd
               + local * 2.0 * 3 * d * s["m"])
    indexer = s["L"] * (tokens * 2.0 * indexer_projection_params(cfg)
                        + batch * causal_pairs(seq) * 2.0 * s["Hi"] * s["Di"])
    return {"trained": trained, "indexer": indexer}


def train_step_flops(cfg: dict, shape: dict, selected=None,
                     local=None) -> float:
    """Model FLOPs of one optimizer step: adapter training pays weight
    gradients for the adapters alone, so forward plus activation gradients,
    2 x forward (``lib/flops.py`` has the same rule), but for the indexer,
    whose choice carries no gradient: once."""
    fwd = forward_flops(cfg, int(shape["batch"]), int(shape["seq"]),
                        selected, local)
    return 2.0 * fwd["trained"] + fwd["indexer"]


def flash_cost(cfg: dict, shape: dict, selected: float, remat: bool) -> dict:
    """The selected-flash kernels' least work in one optimizer step, over
    ``selected`` chosen pairs summed over the layers: forward (twice when
    the layer is rematerialized) two products a pair and head, backward
    five (the scores recomputed once). Bytes: q, k, v, o (and their
    gradients backward) once each in the compute type, and the packed
    choice once a pass."""
    s = _dims(cfg)
    B, T = int(shape["batch"]), int(shape["seq"])
    pair = 2.0 * s["H"] * s["hd"] * selected        # one product, all heads
    q_bytes = 2.0 * B * s["H"] * T * s["hd"]
    kv_bytes = 2.0 * B * s["kv"] * T * s["hd"]
    mask = B * T * T / 8.0
    fwd_bytes = 2 * q_bytes + 2 * kv_bytes + mask
    bwd_bytes = 4 * q_bytes + 4 * kv_bytes + 2 * mask   # dq's walk, dkv's
    n_fwd = 2 if remat else 1
    return {"flops": pair * (2 * n_fwd + 5),
            "bytes": s["L"] * (n_fwd * fwd_bytes + bwd_bytes)}


def index_cost(cfg: dict, shape: dict) -> dict:
    """The choice's least work in one optimizer step (once: the choice is
    kept for the backward): the indexer's score products over every
    causal pair, and its inputs read and the packed choice written once."""
    s = _dims(cfg)
    B, T = int(shape["batch"]), int(shape["seq"])
    flops = s["L"] * B * causal_pairs(T) * 2.0 * s["Hi"] * s["Di"]
    read = B * T * (2.0 * s["Hi"] * s["Di"] + 2.0 * s["Di"] + 4.0 * s["Hi"])
    return {"flops": flops, "bytes": s["L"] * (read + B * T * T / 8.0)}


def experts_cost(cfg: dict, local: float, remat: bool) -> dict:
    """The held experts' products in one optimizer step, with ``local``
    held assignments summed over the layers, as
    ``flops_mla_moe.moe_experts_cost`` counts them: three products a pass
    (gate, up, down), the forward (twice under remat) and the backward to
    the activations (the experts are frozen: no weight gradient). Bytes:
    every held expert's three bfloat16 matrices read once a pass, and the
    rows in and out of each product."""
    s = _dims(cfg)
    passes = (2 if remat else 1) + 1
    one = 3.0 * s["d"] * s["m"]
    weights = 2.0 * s["L"] * s["held"] * one
    rows = 2.0 * local * (2 * s["d"] + 4 * s["m"])     # x, g, u / act, y
    return {"flops": passes * 2.0 * local * one,
            "bytes": passes * (weights + rows)}
