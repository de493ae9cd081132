"""Plain reference of the ViT encoder-classifier family as the configuration
file states it (pre-LayerNorm blocks, mean pooling, no class token, attention
projections without bias, tanh-approximated GELU). float32, ``highest``
matmul precision. Imports nothing of the program.

Every weight is drawn by numpy on the host from ``--seed``: the whole model
is trained and shipped, the benchmark's parent has to build it without a JAX
backend, and 86.5e6 normals take about two seconds.
"""

from __future__ import annotations

import numpy as np

from benchmark.lib.precision import make_ein

LAYER_TENSORS = ("ln1_scale", "ln1_bias", "wq", "wk", "wv", "wo",
                 "ln2_scale", "ln2_bias", "fc1", "fc1_bias", "fc2",
                 "fc2_bias")
TOP_TENSORS = ("patch_kernel", "patch_bias", "pos_embed", "ln_scale",
               "ln_bias", "head", "head_bias")


def sizes(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    side, patch = int(cfg["image_size"]), int(cfg["patch_size"])
    return dict(d=d, heads=int(cfg["num_attention_heads"]),
                hd=d // int(cfg["num_attention_heads"]),
                ffn=int(cfg["intermediate_size"]),
                layers=int(cfg["num_hidden_layers"]),
                patch=patch, ch=int(cfg["num_channels"]),
                positions=(side // patch) ** 2,
                classes=int(cfg["num_labels"]),
                eps=float(cfg["assumed"]["layer_norm_eps"]))


def shapes(cfg: dict):
    """(per-layer, top): name -> (shape, std); std None = ones, 0 = zeros."""
    s = sizes(cfg)
    d, f = s["d"], s["ffn"]
    pin = s["patch"] * s["patch"] * s["ch"]
    layer = {
        "ln1_scale": ((d,), None), "ln1_bias": ((d,), 0.02),
        "ln2_scale": ((d,), None), "ln2_bias": ((d,), 0.02),
        "wq": ((d, d), d ** -0.5), "wk": ((d, d), d ** -0.5),
        "wv": ((d, d), d ** -0.5), "wo": ((d, d), d ** -0.5),
        "fc1": ((d, f), d ** -0.5), "fc1_bias": ((f,), 0.02),
        "fc2": ((f, d), f ** -0.5), "fc2_bias": ((d,), 0.02),
    }
    top = {
        "patch_kernel": ((s["patch"], s["patch"], s["ch"], d), pin ** -0.5),
        "patch_bias": ((d,), 0.02),
        "pos_embed": ((1, s["positions"], d), 0.02),
        "ln_scale": ((d,), None), "ln_bias": ((d,), 0.02),
        "head": ((d, s["classes"]), d ** -0.5),
        "head_bias": ((s["classes"],), 0.02),
    }
    return layer, top


def weights_host(cfg: dict, seed: int) -> dict:
    """All weights as host numpy float32; per-layer tensors stacked."""
    rng = np.random.default_rng([int(seed), 0x517])
    L = sizes(cfg)["layers"]
    layer, top = shapes(cfg)

    def one(shape, std):
        if std is None:
            return np.ones(shape, np.float32)
        return rng.standard_normal(shape, dtype=np.float32) * np.float32(std)

    out = {n: one((L,) + layer[n][0], layer[n][1]) for n in LAYER_TENSORS}
    out.update({n: one(*top[n]) for n in TOP_TENSORS})
    return out


def _ln(x, scale, bias, eps):
    import jax.numpy as jnp
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def logits(w: dict, images, cfg: dict, quant: str = ""):
    import jax
    import jax.numpy as jnp
    s = sizes(cfg)
    ein = make_ein(quant)
    B = images.shape[0]
    n = int(round(s["positions"] ** 0.5))
    p = s["patch"]
    patches = images.reshape(B, n, p, n, p, s["ch"]).transpose(
        0, 1, 3, 2, 4, 5).reshape(B, n * n, p * p * s["ch"])
    x = ein("bnp,pd->bnd", patches,
            w["patch_kernel"].reshape(-1, s["d"])) + w["patch_bias"]
    x = x + w["pos_embed"]
    T = x.shape[1]

    @jax.checkpoint
    def layer(x, lw):
        h = _ln(x, lw["ln1_scale"], lw["ln1_bias"], s["eps"])

        def heads(t):
            return t.reshape(B, T, s["heads"], s["hd"]).transpose(0, 2, 1, 3)

        q = heads(ein("btd,de->bte", h, lw["wq"]))
        k = heads(ein("btd,de->bte", h, lw["wk"]))
        v = heads(ein("btd,de->bte", h, lw["wv"]))
        sc = ein("bhqd,bhkd->bhqk", q, k) * (s["hd"] ** -0.5)
        o = ein("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), v)
        o = o.transpose(0, 2, 1, 3).reshape(B, T, s["d"])
        x = x + ein("btd,de->bte", o, lw["wo"])
        h = _ln(x, lw["ln2_scale"], lw["ln2_bias"], s["eps"])
        a = jax.nn.gelu(ein("btd,df->btf", h, lw["fc1"]) + lw["fc1_bias"],
                        approximate=True)
        return x + ein("btf,fd->btd", a, lw["fc2"]) + lw["fc2_bias"], None

    x, _ = jax.lax.scan(layer, x, {n_: w[n_] for n_ in LAYER_TENSORS})
    x = jnp.mean(_ln(x, w["ln_scale"], w["ln_bias"], s["eps"]), axis=1)
    return ein("bd,dc->bc", x, w["head"]) + w["head_bias"]


def loss(trainable, frozen, x, y, cfg, quant: str = "", keep=None):
    """Mean cross-entropy over the batch. ``keep`` (a fault of the tests):
    the mean over that share of the rows only."""
    import jax
    import jax.numpy as jnp
    if keep is not None:
        n = int(x.shape[0] * keep)
        x, y = x[:n], y[:n]
    lg = logits(trainable, x, cfg, quant)
    return -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(lg, -1),
                                         y[:, None], -1))


def make_weights(cfg: dict, seed: int):
    import jax.numpy as jnp
    return {k: jnp.asarray(v) for k, v in weights_host(cfg, seed).items()}, {}


def train_batches(cfg: dict, shape: dict, seed: int):
    from benchmark.lib import data
    return data.image_batches(cfg, shape, seed)
