"""The ViT family on the program's side: the module the program builds and
where each seeded tensor sits in its parameter tree."""

from __future__ import annotations

import numpy as np

from benchmark.lib import data
from benchmark.reference import vit as ref

TRAINABLE_REGEX = ""


def build_module(cfg: dict):
    import jax.numpy as jnp
    from metisfl_tpu.models.zoo import ViTLite
    s = ref.sizes(cfg)
    if s["ffn"] != 4 * s["d"]:
        raise ValueError("EncoderBlock fixes the FFN at 4 x hidden")
    return ViTLite(num_classes=s["classes"], dim=s["d"], depth=s["layers"],
                   heads=s["heads"], patch=s["patch"],
                   dtype=getattr(jnp, cfg["compute_dtype"]))


def _tree(w: dict) -> dict:
    L = len(w["wq"])
    params = {
        "patch_embed": {"kernel": w["patch_kernel"], "bias": w["patch_bias"]},
        "pos_embed": w["pos_embed"],
        "LayerNorm_0": {"scale": w["ln_scale"], "bias": w["ln_bias"]},
        "head": {"kernel": w["head"], "bias": w["head_bias"]},
    }
    for l in range(L):
        params[f"block_{l}"] = {
            "LayerNorm_0": {"scale": w["ln1_scale"][l],
                            "bias": w["ln1_bias"][l]},
            "LayerNorm_1": {"scale": w["ln2_scale"][l],
                            "bias": w["ln2_bias"][l]},
            "attn": {"wq": {"base": {"kernel": w["wq"][l]}},
                     "wk": {"base": {"kernel": w["wk"][l]}},
                     "wv": {"base": {"kernel": w["wv"][l]}},
                     "wo": {"kernel": w["wo"][l]}},
            "mlp": {"fc1": {"kernel": w["fc1"][l], "bias": w["fc1_bias"][l]},
                    "fc2": {"kernel": w["fc2"][l], "bias": w["fc2_bias"][l]}},
        }
    return params


def shipped_host(cfg: dict, seed: int) -> dict:
    """The whole model as the initial community model, host numpy."""
    return {"params": _tree(ref.weights_host(cfg, seed))}


def variables(cfg: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    return jax.tree.map(jnp.asarray, shipped_host(cfg, seed))


def by_program_name(trainable: dict) -> dict:
    out = {}

    def walk(prefix, node):
        for k, v in node.items():
            if isinstance(v, dict):
                walk(f"{prefix}/{k}", v)
            else:
                out[f"{prefix}/{k}"] = np.asarray(v)

    walk("params", _tree({k: np.asarray(v) for k, v in trainable.items()}))
    return out


def datasets(cfg: dict, shape: dict, seed: int):
    from metisfl_tpu.models import ArrayDataset
    x, y, tx, ty = data.image_rows(cfg, shape, seed)
    return (ArrayDataset(x, y, seed=int(seed)),
            ArrayDataset(tx, ty, seed=int(seed)))


def sample_input(cfg: dict, shape: dict):
    side = int(cfg["image_size"])
    return np.zeros((1, side, side, int(cfg["num_channels"])), np.float32)
