"""The decoder with learned sparse attention and routed experts (family
``dsa_moe``) on the program's side: which module the program builds for a
configuration, and where each seeded tensor sits in its parameter tree.
The numbers come from ``benchmark/reference/dsa_moe.py``; nothing here is
arithmetic."""

from __future__ import annotations

import functools

import numpy as np

# a program without the family fails here, at once, before the harness
# starts any process (importing the zoo initializes no JAX backend)
from metisfl_tpu.models.zoo import DsaMoeLite

from benchmark.lib import data
from benchmark.reference import dsa_moe as ref

TRAINABLE_REGEX = "lora_"

# adapter's name in the reference -> (projection, factor) in the program
_LORA = {"lora_q_a": ("wq", "lora_a"), "lora_q_b": ("wq", "lora_b"),
         "lora_v_a": ("wv", "lora_a"), "lora_v_b": ("wv", "lora_b")}


def build_module(cfg: dict):
    import jax.numpy as jnp
    s = ref.sizes(cfg)
    prog = cfg["program"]
    if s["alpha"] != 16.0:
        raise ValueError("Attention's adapters scale by alpha 16 / rank")
    return DsaMoeLite(
        vocab_size=s["vocab"], dim=s["d"], depth=s["layers"],
        heads=s["heads"], kv_heads=s["kv"], head_dim=s["hd"],
        moe_hidden=s["moe"], num_experts=s["experts"], top_k=s["top_k"],
        experts_first=s["first"], experts_count=s["count"],
        index_heads=s["index_heads"], index_head_dim=s["index_dim"],
        index_top_k=s["index_top_k"], rope_base=s["theta"],
        rope_sections=s["sections"], eps=s["eps"], lora_rank=s["rank"],
        use_flash=prog["use_flash"], remat=bool(prog["remat"]),
        dtype=getattr(jnp, cfg["compute_dtype"]),
        param_dtype=getattr(jnp, cfg["param_dtype"]["frozen"]))


def _block(w: dict) -> dict:
    """One layer's base tensors in the program's tree (adapters absent)."""
    kernel = lambda name: {"kernel": w[name]}              # noqa: E731
    return {
        "RMSNorm_0": {"scale": w["attn_norm"]},
        "RMSNorm_1": {"scale": w["mlp_norm"]},
        "attention": {
            "wq": {"base": kernel("wq")}, "wk": {"base": kernel("wk")},
            "wv": {"base": kernel("wv")}, "wo": kernel("wo"),
            "q_norm": {"scale": w["q_norm"]},
            "k_norm": {"scale": w["k_norm"]},
            "indexer": {"wq": kernel("idx_wq"), "wk": kernel("idx_wk"),
                        "weights_proj": kernel("idx_w"),
                        "k_norm": {"scale": w["idx_k_norm"],
                                   "bias": w["idx_k_bias"]}}},
        "ffn": {"router": kernel("router"),
                "e_score_correction_bias": np.zeros(
                    w["router"].shape[-1:], np.float32),
                "experts_gate": w["experts_gate"],
                "experts_up": w["experts_up"],
                "experts_down": w["experts_down"]},
    }


def _place_lora(params: dict, lora: list, convert) -> None:
    for l, adapters in enumerate(lora):
        for name, value in adapters.items():
            proj, factor = _LORA[name]
            params[f"block_{l}"].setdefault("attention", {}).setdefault(
                proj, {})[factor] = convert(value)


def variables(cfg: dict, seed: int) -> dict:
    """The program's variables on the device: the base a layer a jitted
    call from the seed (drawn in float32, rounded to the type it is held
    in), the adapters from the host draw."""
    import jax
    import jax.numpy as jnp
    key = ref.seed_key(seed)
    one = jax.jit(lambda key, l: _block(ref.draw_layer(cfg, key, l)))
    params = {f"block_{l}": one(key, l)
              for l in range(ref.sizes(cfg)["layers"])}
    top = jax.jit(functools.partial(ref.draw_top, cfg))(key)
    params["embed"] = {"embedding": top["embed"]}
    params["RMSNorm_0"] = {"scale": top["final_norm"]}
    params["lm_head"] = {"kernel": top["lm_head"]}
    _place_lora(params, ref.lora_host(cfg, seed), jnp.asarray)
    return {"params": params}


def shipped_host(cfg: dict, seed: int) -> dict:
    """The shipped subset (the adapters) as the initial community model:
    host numpy in the program's tree, no JAX backend touched."""
    lora = ref.lora_host(cfg, seed)
    params = {f"block_{l}": {} for l in range(len(lora))}
    _place_lora(params, lora, np.asarray)
    return {"params": params}


def by_program_name(trainable: list) -> dict:
    """Reference trainable leaves under the program's wire names."""
    return {f"params/block_{l}/attention/" + "/".join(_LORA[name]):
            np.asarray(value)
            for l, adapters in enumerate(trainable)
            for name, value in adapters.items()}


def datasets(cfg: dict, shape: dict, seed: int):
    from metisfl_tpu.models import ArrayDataset
    x, y, tx, ty = data.lm_rows(ref.sizes(cfg)["vocab"], shape, seed)
    return (ArrayDataset(x, y, seed=int(seed)),
            ArrayDataset(tx, ty, seed=int(seed)))


def sample_input(cfg: dict, shape: dict):
    return np.zeros((1, int(shape["seq"])), np.int32)
