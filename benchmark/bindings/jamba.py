"""The Jamba family on the program's side: which module the program builds
for a configuration, and where each seeded tensor sits in its parameter
tree. The numbers come from ``benchmark/reference/jamba.py``; nothing here
is arithmetic."""

from __future__ import annotations

import numpy as np

# a program without the hybrid fails here, at once, before the harness
# starts any process (importing the zoo initializes no JAX backend)
from metisfl_tpu.models.zoo import JambaLite

from benchmark.lib import data
from benchmark.reference import jamba as ref

TRAINABLE_REGEX = "lora_"

# adapter name in the reference -> (mixer, projection, factor) in the program
_LORA_AT = {
    "lora_q_a": ("attn", "wq", "lora_a"), "lora_q_b": ("attn", "wq", "lora_b"),
    "lora_v_a": ("attn", "wv", "lora_a"), "lora_v_b": ("attn", "wv", "lora_b"),
    "lora_in_a": ("mamba", "in_proj", "lora_a"),
    "lora_in_b": ("mamba", "in_proj", "lora_b"),
    "lora_out_a": ("mamba", "out_proj", "lora_a"),
    "lora_out_b": ("mamba", "out_proj", "lora_b"),
}


def build_module(cfg: dict):
    import jax.numpy as jnp
    s = ref.sizes(cfg)
    prog = cfg["program"]
    return JambaLite(
        vocab_size=s["vocab"], dim=s["d"], depth=s["layers"],
        heads=s["heads"], kv_heads=s["kv"], ffn_dim=s["ffn"],
        attn_period=s["period"], attn_offset=s["offset"], d_state=s["n"],
        d_conv=s["k"], expand=int(cfg["mamba_expand"]), dt_rank=s["r"],
        eps=s["eps"], lora_rank=s["rank"], lora_alpha=s["alpha"],
        use_flash=prog["use_flash"], remat=bool(prog["remat"]),
        dtype=getattr(jnp, cfg["compute_dtype"]))


def _block(w: dict, attention: bool) -> dict:
    """One block's base tensors in the program's tree (adapters absent)."""
    out = {"RMSNorm_0": {"scale": w["mix_norm"]},
           "RMSNorm_1": {"scale": w["mlp_norm"]},
           "mlp": {"gate": {"kernel": w["gate"]}, "up": {"kernel": w["up"]},
                   "down": {"kernel": w["down"]}}}
    if attention:
        out["attn"] = {"wq": {"base": {"kernel": w["wq"]}},
                       "wk": {"base": {"kernel": w["wk"]}},
                       "wv": {"base": {"kernel": w["wv"]}},
                       "wo": {"kernel": w["wo"]}}
    else:
        out["mamba"] = {
            "in_proj": {"base": {"kernel": w["in_proj"]}},
            "conv_kernel": w["conv_w"], "conv_bias": w["conv_b"],
            "x_proj": {"kernel": w["x_proj"]},
            "dt_norm": {"scale": w["dt_norm"]},
            "b_norm": {"scale": w["b_norm"]},
            "c_norm": {"scale": w["c_norm"]},
            "dt_proj": {"kernel": w["dt_proj"], "bias": w["dt_bias"]},
            "A_log": w["A_log"], "D": w["D"],
            "out_proj": {"base": {"kernel": w["out_proj"]}}}
    return out


def _place_lora(params: dict, lora: list, convert) -> None:
    for l, adapters in enumerate(lora):
        for name, value in adapters.items():
            mixer, proj, factor = _LORA_AT[name]
            params[f"block_{l}"].setdefault(mixer, {}).setdefault(
                proj, {})[factor] = convert(value)


def variables(cfg: dict, seed: int) -> dict:
    """The program's variables on the device: the base in one jitted call
    from the seed, layer by layer, the adapters from the host draw."""
    import jax
    import jax.numpy as jnp
    L = ref.sizes(cfg)["layers"]

    def make(key):
        params = {f"block_{l}": _block(ref.draw_layer(cfg, key, l),
                                       ref.is_attention(cfg, l))
                  for l in range(L)}
        top = ref.draw_top(cfg, key)
        params["embed"] = {"embedding": top["embed"]}
        params["RMSNorm_0"] = {"scale": top["final_norm"]}
        return params

    params = jax.jit(make)(ref.seed_key(seed))
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
    return {f"params/block_{l}/" + "/".join(_LORA_AT[name]): np.asarray(value)
            for l, adapters in enumerate(trainable)
            for name, value in adapters.items()}


def datasets(cfg: dict, shape: dict, seed: int):
    from metisfl_tpu.models import ArrayDataset
    x, y, tx, ty = data.lm_rows(ref.sizes(cfg)["vocab"], shape, seed)
    return (ArrayDataset(x, y, seed=int(seed)),
            ArrayDataset(tx, ty, seed=int(seed)))


def sample_input(cfg: dict, shape: dict):
    return np.zeros((1, int(shape["seq"])), np.int32)
