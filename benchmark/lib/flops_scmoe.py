"""Operations and bytes of the shortcut-connected decoder (family
``scmoe``), from shapes alone: the numerators of ``scmoe_step_mfu``,
``scmoe_flash_roofline`` and ``scmoe_experts_roofline``. A layer is two
latent-attention sublayers, two dense FFNs, a router over the routed and
the zero-computation experts' columns, the held experts' products and the
identity experts' scaled sum. The sublayer, the flash kernels and the held
experts' products are the latent family's, counted by its functions
(``lib/flops_mla_moe.py``) on this family's keys under that family's names.
Recomputation (remat) is never credited to the model; a kernel's own
recomputation is the kernel's work. Of the routed experts only the
assignments that fall on held experts count: the shapes expect ``tokens *
top_k * held / columns`` a layer, and the program's counters
(``moe_local_count``, ``moe_zero_count``) give what a run really had.
Checked against XLA's ``cost_analysis()`` at toy depth in
``tests/test_scmoe.py``."""

from __future__ import annotations

from benchmark.lib import flops_mla_moe


def _as_latent(cfg: dict, blocks: int) -> dict:
    """The configuration as ``lib/flops_mla_moe.py`` reads one: ``blocks``
    blocks, each with latent attention and the held experts, no dense
    block and no shared expert."""
    return {**cfg, "num_hidden_layers": blocks, "first_k_dense_replace": 0,
            "intermediate_size": cfg["ffn_hidden_size"],
            "moe_intermediate_size": cfg["expert_ffn_hidden_size"],
            "num_experts_per_tok": cfg["moe_topk"], "n_shared_experts": 0}


def mla_flops_per_token(cfg: dict, context: float) -> float:
    """One sublayer's latent attention: the five projections with adapters
    on four, and the score and value products over ``context`` keys."""
    return flops_mla_moe.mla_flops_per_token(_as_latent(cfg, 1), context)


def expected_counts(cfg: dict, tokens: int) -> tuple:
    """Assignments, summed over the layers, that the shapes expect on held
    experts and on zero-computation experts."""
    columns = (int(cfg["published"]["n_routed_experts"])
               + int(cfg["zero_expert_num"]))
    each = int(cfg["num_layers"]) * tokens * int(cfg["moe_topk"]) / columns
    return (each * int(cfg["experts_held"]["count"]),
            each * int(cfg["zero_expert_num"]))


def forward_flops(cfg: dict, batch: int, seq: int, local=None,
                  zero=None) -> float:
    """One causal forward pass over ``batch`` rows of ``seq`` tokens with
    ``local`` held and ``zero`` zero-computation assignments summed over
    the layers (the shapes' expectation where not given): a held
    assignment is one expert's three products, a zero-computation one a
    scale and an add over the hidden width."""
    d, layers = int(cfg["hidden_size"]), int(cfg["num_layers"])
    columns = (int(cfg["published"]["n_routed_experts"])
               + int(cfg["zero_expert_num"]))
    tokens = batch * seq
    want_local, want_zero = expected_counts(cfg, tokens)
    local = want_local if local is None else local
    zero = want_zero if zero is None else zero
    per_token = (layers * (2 * mla_flops_per_token(cfg, (seq + 1) / 2)
                           + 2 * 2.0 * 3 * d * int(cfg["ffn_hidden_size"])
                           + 2.0 * d * columns)
                 + 2.0 * d * int(cfg["vocab_size"]))
    return (tokens * per_token
            + local * 2.0 * 3 * d * int(cfg["expert_ffn_hidden_size"])
            + zero * 2.0 * d)


def train_step_flops(cfg: dict, shape: dict, local=None, zero=None) -> float:
    """Model FLOPs of one optimizer step; adapter training pays weight
    gradients for the adapters alone, so forward plus activation
    gradients, 2 x forward (``lib/flops.py`` has the same rule)."""
    fwd = forward_flops(cfg, int(shape["batch"]), int(shape["seq"]), local,
                        zero)
    return (2.0 if cfg.get("lora", {}).get("rank") else 3.0) * fwd


def flash_cost(cfg: dict, shape: dict, remat: bool) -> dict:
    """The flash kernels' work in one optimizer step: forward (twice when
    the layer is rematerialized) and backward, both sublayers of every
    layer; the products and bytes a sublayer as
    ``flops_mla_moe.mla_flash_cost`` counts a block's."""
    return flops_mla_moe.mla_flash_cost(
        _as_latent(cfg, 2 * int(cfg["num_layers"])), shape, remat)


def experts_cost(cfg: dict, local: float, remat: bool) -> dict:
    """The routed experts' products in one optimizer step, with ``local``
    held assignments summed over the layers, as
    ``flops_mla_moe.moe_experts_cost`` counts them: three products a pass,
    forward (twice under remat) and the backward to the activations; every
    held expert's three matrices read once a pass, which bounds the
    product at this cell's 64 rows an expert."""
    return flops_mla_moe.moe_experts_cost(
        _as_latent(cfg, int(cfg["num_layers"])), local, remat)
