"""The shortcut-connected decoder (``longcat_flash`` family) on the
program's side: which module the program builds for a configuration, and
where each seeded tensor sits in its parameter tree. The numbers come from
``benchmark/reference/scmoe.py``; nothing here is arithmetic."""

from __future__ import annotations

import functools

import numpy as np

# a program without the family fails here, at once, before the harness
# starts any process (importing the zoo initializes no JAX backend)
from metisfl_tpu.models.zoo import ScMoeLite

from benchmark.lib import data
from benchmark.reference import scmoe as ref

TRAINABLE_REGEX = "lora_"

# projection's name in the reference -> in the program
_PROJ = {"q_a": "q_a_proj", "q_b": "q_b_proj",
         "kv_a": "kv_a_proj_with_mqa", "kv_b": "kv_b_proj"}


def _lora_at(name: str) -> tuple:
    """``lora_<proj>_<sublayer>_<a|b>`` -> (sublayer's mixer, projection,
    factor) in the program."""
    proj, sub, factor = name[len("lora_"):].rsplit("_", 2)
    return "mla_" + sub, _PROJ[proj], "lora_" + factor


def build_module(cfg: dict):
    import jax.numpy as jnp
    s = ref.sizes(cfg)
    prog = cfg["program"]
    return ScMoeLite(
        vocab_size=s["vocab"], dim=s["d"], depth=s["layers"],
        heads=s["heads"], q_rank=s["qr"], kv_rank=s["kvr"],
        nope_dim=s["nope"], rope_dim=s["rope"], v_dim=s["vd"],
        ffn_dim=s["ffn"], moe_hidden=s["moe"], num_experts=s["experts"],
        zero_experts=s["zero"], top_k=s["top_k"],
        experts_first=s["first"], experts_count=s["count"],
        routed_scale=s["route_scale"], scale_q_lora=s["scale_q"],
        scale_kv_lora=s["scale_kv"], rope_base=s["theta"], eps=s["eps"],
        lora_rank=s["rank"], lora_alpha=s["alpha"],
        use_flash=prog["use_flash"], remat=bool(prog["remat"]),
        dtype=getattr(jnp, cfg["compute_dtype"]),
        param_dtype=getattr(jnp, cfg["param_dtype"]["frozen"]))


def _block(w: dict) -> dict:
    """One layer's base tensors in the program's tree (adapters absent)."""
    out = {"moe": {"router": {"kernel": w["router"]},
                   "e_score_correction_bias": w["router_bias"],
                   "experts_gate": w["experts_gate"],
                   "experts_up": w["experts_up"],
                   "experts_down": w["experts_down"]}}
    for i in (0, 1):
        mla = {prog: {"base": {"kernel": w[f"{name}_{i}"]}}
               for name, prog in _PROJ.items()}
        mla.update(q_a_norm={"scale": w[f"q_a_norm_{i}"]},
                   kv_a_norm={"scale": w[f"kv_a_norm_{i}"]},
                   o_proj={"kernel": w[f"o_{i}"]})
        out[f"mla_{i}"] = mla
        out[f"mlp_{i}"] = {k: {"kernel": w[f"{k}_{i}"]}
                           for k in ("gate", "up", "down")}
        out[f"input_norm_{i}"] = {"scale": w[f"in_norm_{i}"]}
        out[f"post_norm_{i}"] = {"scale": w[f"post_norm_{i}"]}
    return out


def _place_lora(params: dict, lora: list, convert) -> None:
    for l, adapters in enumerate(lora):
        for name, value in adapters.items():
            mixer, proj, factor = _lora_at(name)
            params[f"block_{l}"].setdefault(mixer, {}).setdefault(
                proj, {})[factor] = convert(value)


def variables(cfg: dict, seed: int) -> dict:
    """The program's variables on the device: the base a layer a jitted
    call from the seed (drawn in float32, rounded to the type it is held
    in; the float32 draw of the whole tree would not fit), the adapters
    from the host draw."""
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
    return {f"params/block_{l}/" + "/".join(_lora_at(name)):
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
