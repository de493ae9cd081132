"""The decoder-LM family on the program's side: which module the program
builds for a configuration, and where each seeded tensor sits in its
parameter tree. The numbers come from ``benchmark/reference/decoder_lm.py``;
nothing here is arithmetic."""

from __future__ import annotations

import numpy as np

from benchmark.lib import data
from benchmark.reference import decoder_lm as ref

TRAINABLE_REGEX = "lora_"


def build_module(cfg: dict):
    import jax.numpy as jnp
    from metisfl_tpu.models.zoo import LlamaLite
    s = ref.sizes(cfg)
    if s["ffn"] != 4 * s["d"]:
        raise ValueError("DecoderBlock fixes the FFN at 4 x hidden")
    prog = cfg["program"]
    return LlamaLite(vocab_size=s["vocab"], dim=s["d"], depth=s["layers"],
                     heads=s["heads"], kv_heads=s["kv"], lora_rank=s["rank"],
                     remat=bool(prog["remat"]), use_flash=prog["use_flash"],
                     dtype=getattr(jnp, cfg["compute_dtype"]))


def _block(get, l: int) -> dict:
    return {
        "RMSNorm_0": {"scale": get("attn_norm", l)},
        "RMSNorm_1": {"scale": get("mlp_norm", l)},
        "attn": {
            "wq": {"base": {"kernel": get("wq", l)},
                   "lora_a": get("lora_q_a", l), "lora_b": get("lora_q_b", l)},
            "wk": {"base": {"kernel": get("wk", l)}},
            "wv": {"base": {"kernel": get("wv", l)},
                   "lora_a": get("lora_v_a", l), "lora_b": get("lora_v_b", l)},
            "wo": {"kernel": get("wo", l)},
        },
        "mlp": {"gate": {"kernel": get("gate", l)},
                "up": {"kernel": get("up", l)},
                "down": {"kernel": get("down", l)}},
    }


def variables(cfg: dict, seed: int) -> dict:
    """The program's variables on the device: the base in one jitted call
    from the seed, per layer (never stacked: a stacked copy beside the
    tree would not fit), the adapters from the host draw."""
    import jax
    import jax.numpy as jnp
    L = ref.sizes(cfg)["layers"]
    lshapes, tshapes = ref.layer_shapes(cfg), ref.top_shapes(cfg)
    lora = ref.lora_host(cfg, seed)

    def make(key):
        def get(name, l):
            if name in ref.LORA_TENSORS:
                return None
            shape, std = lshapes[name]
            return ref.draw(key, ref.BASE_TENSORS.index(name), l, shape, std)

        def top(name):
            shape, std = tshapes[name]
            return ref.draw(key, 100 + ref.TOP_TENSORS.index(name), -1,
                            shape, std)

        params = {f"block_{l}": _block(get, l) for l in range(L)}
        params["embed"] = {"embedding": top("embed")}
        params["RMSNorm_0"] = {"scale": top("final_norm")}
        params["lm_head"] = {"kernel": top("lm_head")}
        return params

    params = jax.jit(make)(ref.seed_key(seed))
    for l in range(L):
        attn = params[f"block_{l}"]["attn"]
        for proj, tag in (("wq", "q"), ("wv", "v")):
            attn[proj]["lora_a"] = jnp.asarray(lora[f"lora_{tag}_a"][l])
            attn[proj]["lora_b"] = jnp.asarray(lora[f"lora_{tag}_b"][l])
    return {"params": params}


def adapters_only(cfg: dict, seed: int):
    """(module, variables, sample input) of a stand-in that holds exactly
    the shipped subset, under the program's names: what the serving cells'
    minting learner trains for one round at learning rate 0 on the CPU, so
    that the registry mints the seeded adapters as a version while no
    learner holds the chip. It computes nothing of the model."""
    import flax.linen as nn
    import jax.numpy as jnp
    s = ref.sizes(cfg)
    d, kvd, r, L = s["d"], s["kv"] * s["hd"], s["rank"], s["layers"]

    class Proj(nn.Module):
        out: int

        @nn.compact
        def __call__(self):
            a = self.param("lora_a", nn.initializers.zeros, (d, r))
            b = self.param("lora_b", nn.initializers.zeros, (r, self.out))
            return jnp.sum(a) + jnp.sum(b)

    class Attn(nn.Module):
        @nn.compact
        def __call__(self):
            return Proj(d, name="wq")() + Proj(kvd, name="wv")()

    class Block(nn.Module):
        @nn.compact
        def __call__(self):
            return Attn(name="attn")()

    class AdaptersOnly(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            total = sum(Block(name=f"block_{l}")() for l in range(L))
            return jnp.zeros(x.shape + (2,), jnp.float32) + total

    return (AdaptersOnly(), shipped_host(cfg, seed),
            np.zeros((1, 8), np.int32))


def shipped_host(cfg: dict, seed: int) -> dict:
    """The shipped subset (the adapters) as the initial community model:
    host numpy in the program's tree, no JAX backend touched."""
    lora = ref.lora_host(cfg, seed)
    return {"params": program_names_tree(lora)}


def program_names_tree(lora: dict) -> dict:
    L = len(lora["lora_q_a"])
    return {f"block_{l}": {"attn": {
        "wq": {"lora_a": np.asarray(lora["lora_q_a"][l]),
               "lora_b": np.asarray(lora["lora_q_b"][l])},
        "wv": {"lora_a": np.asarray(lora["lora_v_a"][l]),
               "lora_b": np.asarray(lora["lora_v_b"][l])}}}
        for l in range(L)}


def by_program_name(trainable: dict) -> dict:
    """Reference trainable leaves under the program's wire names."""
    out = {}
    for l in range(len(trainable["lora_q_a"])):
        for proj, tag in (("wq", "q"), ("wv", "v")):
            for ab in "ab":
                out[f"params/block_{l}/attn/{proj}/lora_{ab}"] = np.asarray(
                    trainable[f"lora_{tag}_{ab}"][l])
    return out


def datasets(cfg: dict, shape: dict, seed: int):
    from metisfl_tpu.models import ArrayDataset
    x, y, tx, ty = data.lm_rows(ref.sizes(cfg)["vocab"], shape, seed)
    return (ArrayDataset(x, y, seed=int(seed)),
            ArrayDataset(tx, ty, seed=int(seed)))


def sample_input(cfg: dict, shape: dict):
    return np.zeros((1, int(shape["seq"])), np.int32)
