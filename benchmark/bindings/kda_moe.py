"""The GLM-5 hybrid (family ``kda_moe``) on the program's side: which
module the program builds for a configuration, and where each seeded
tensor sits in its parameter tree. The numbers come from
``benchmark/reference/kda_moe.py``; nothing here is arithmetic."""

from __future__ import annotations

import functools

import numpy as np

# a program without the family fails here, at once, before the harness
# starts any process (importing the zoo initializes no JAX backend)
from metisfl_tpu.models.zoo import KdaMoeLite

from benchmark.lib import data
from benchmark.reference import kda_moe as ref

TRAINABLE_REGEX = "lora_"

# projection in the reference -> its module in the program's mixer
_PROJ = {"q_proj": "q_proj", "k_proj": "k_proj", "v_proj": "v_proj",
         "f_a": "f_a_proj", "f_b": "f_b_proj", "b_proj": "b_proj",
         "g_a": "g_a_proj", "g_b": "g_b_proj",
         "q_a": "q_a_proj", "q_b": "q_b_proj", "kv_a": "kv_a_proj_with_mqa",
         "kv_b": "kv_b_proj"}


def build_module(cfg: dict):
    import jax.numpy as jnp
    s = ref.sizes(cfg)
    prog = cfg["program"]
    return KdaMoeLite(
        vocab_size=s["vocab"], dim=s["d"], layer_types=s["mixers"],
        mlp_layer_types=s["ffns"], heads=s["heads"], head_dim=s["hd"],
        conv=s["conv"], gate_rank=s["gate_rank"],
        gate_lower_bound=s["lower"], mla_heads=s["mla_heads"],
        q_rank=s["qr"], kv_rank=s["kvr"], nope_dim=s["nope"],
        v_dim=s["vd"], index_heads=s["index_heads"],
        index_head_dim=s["index_dim"], index_top_k=s["index_top_k"],
        index_pool=s["pool"], ffn_dim=s["ffn"],
        moe_hidden=s["moe"], num_experts=s["experts"], top_k=s["top_k"],
        experts_first=s["first"], experts_count=s["count"],
        shared_experts=s["shared"], routed_scale=s["route_scale"],
        swiglu_limit=s["limit"], streams=s["streams"],
        sinkhorn_iters=s["iters"], hc_eps=s["hc_eps"], eps=s["eps"],
        lora_rank=s["rank"], lora_alpha=s["alpha"],
        use_flash=prog["use_flash"], remat=bool(prog["remat"]),
        dtype=getattr(jnp, cfg["compute_dtype"]),
        param_dtype=getattr(jnp, cfg["param_dtype"]["frozen"]))


def _block(w: dict) -> dict:
    """One layer's base tensors in the program's tree (adapters absent)."""
    kernel = lambda name: {"kernel": w[name]}              # noqa: E731
    based = lambda name: {"base": kernel(name)}            # noqa: E731

    def hyper(side):
        return {"norm": {"scale": w[f"{side}_norm"]},
                "phi": kernel(f"{side}_phi"), "alpha": w[f"{side}_alpha"],
                "b_pre": w[f"{side}_b_pre"], "b_post": w[f"{side}_b_post"],
                "b_res": w[f"{side}_b_res"]}

    out = {"mix": hyper("mix"), "mlp": hyper("mlp")}
    if "q_proj" in w:
        mixer = {_PROJ[n]: based(n) for n in ("q_proj", "k_proj", "v_proj",
                                               "f_a", "f_b", "b_proj", "g_a",
                                               "g_b")}
        mixer.update({n: w[n] for n in ("q_conv", "k_conv", "v_conv",
                                        "A_log", "dt_bias")})
        mixer.update({"o_norm": {"scale": w["o_norm"]},
                      "o_proj": kernel("o_proj")})
    else:
        mixer = {_PROJ[n]: based(n) for n in ("q_a", "q_b", "kv_a", "kv_b")}
        mixer.update({
            "q_a_norm": {"scale": w["q_a_norm"]},
            "kv_a_norm": {"scale": w["kv_a_norm"]}, "o_proj": kernel("o"),
            "indexer": {"wq": kernel("idx_wq"), "wk": kernel("idx_wk"),
                        "weights_proj": kernel("idx_w"),
                        "k_norm": {"scale": w["idx_k_norm"],
                                   "bias": w["idx_k_bias"]}}})
    out["mixer"] = mixer
    if "gate" in w:
        out["ffn"] = {n: kernel(n) for n in ("gate", "up", "down")}
    else:
        out["ffn"] = {"router": kernel("router"),
                      "e_score_correction_bias": w["router_bias"],
                      "experts_gate": w["experts_gate"],
                      "experts_up": w["experts_up"],
                      "experts_down": w["experts_down"],
                      "shared": {"gate": kernel("shared_gate"),
                                 "up": kernel("shared_up"),
                                 "down": kernel("shared_down")}}
    return out


def _place_lora(params: dict, lora: list, convert) -> None:
    for l, adapters in enumerate(lora):
        for name, value in adapters.items():
            proj, factor = name[len("lora_"):].rsplit("_", 1)
            params[f"block_{l}"].setdefault("mixer", {}).setdefault(
                _PROJ[proj], {})[f"lora_{factor}"] = convert(value)


def variables(cfg: dict, seed: int) -> dict:
    """The program's variables on the device: the base a layer a jitted
    call from the seed (drawn in float32, rounded to the type it is held
    in), the adapters from the host draw."""
    import jax
    import jax.numpy as jnp
    key = ref.seed_key(seed)
    one = jax.jit(lambda key, l, layer_kind: _block(
        ref.draw_layer(cfg, key, l, layer_kind)),
        static_argnames="layer_kind")
    params = {f"block_{l}": one(key, l, layer_kind=ref.kind(cfg, l))
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
    out = {}
    for l, adapters in enumerate(trainable):
        for name, value in adapters.items():
            proj, factor = name[len("lora_"):].rsplit("_", 1)
            out[f"params/block_{l}/mixer/{_PROJ[proj]}/lora_{factor}"] = \
                np.asarray(value)
    return out


def datasets(cfg: dict, shape: dict, seed: int):
    from metisfl_tpu.models import ArrayDataset
    x, y, tx, ty = data.lm_rows(ref.sizes(cfg)["vocab"], shape, seed)
    return (ArrayDataset(x, y, seed=int(seed)),
            ArrayDataset(tx, ty, seed=int(seed)))


def sample_input(cfg: dict, shape: dict):
    return np.zeros((1, int(shape["seq"])), np.int32)
