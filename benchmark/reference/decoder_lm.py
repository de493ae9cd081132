"""Plain reference of the decoder-only LM family (RMSNorm, rotary, grouped
query attention, SwiGLU, LoRA on q and v), as the configuration file states
it. float32, ``highest`` matmul precision, no kernels, no cache, no batching.
Imports nothing of the program.

Weights come from ``--seed`` alone. The frozen base is drawn on the device
(``jax.random``, one key per tensor and layer, so a per-layer draw and the
stacked draw here give the same numbers); the adapters, which the benchmark's
parent has to build without a JAX backend, are drawn by numpy on the host.

``quant="fp8"`` is the control: every matrix product takes both operands
rounded to 4 significant bits (e4m3) under a per-tensor scale — the nearest
precision below the bfloat16 the configuration states.
"""

from __future__ import annotations

import numpy as np

from benchmark.lib.precision import make_ein

# order fixes each tensor's key: never reorder, only append
BASE_TENSORS = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "gate",
                "up", "down")
LORA_TENSORS = ("lora_q_a", "lora_q_b", "lora_v_a", "lora_v_b")
TOP_TENSORS = ("embed", "final_norm", "lm_head")


def sizes(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    kv = int(cfg.get("num_key_value_heads") or heads)
    hd = d // heads
    return dict(d=d, heads=heads, kv=kv, hd=hd,
                ffn=int(cfg["intermediate_size"]),
                vocab=int(cfg["vocab_size"]),
                layers=int(cfg["num_hidden_layers"]),
                rank=int(cfg["lora"]["rank"]),
                alpha=float(cfg["lora"]["alpha"]),
                eps=float(cfg["assumed"]["rms_norm_eps"]),
                theta=float(cfg["assumed"]["rope_theta"]))


def layer_shapes(cfg: dict) -> dict:
    """name -> (shape of one layer's tensor, std; std None = ones)."""
    s = sizes(cfg)
    d, kvd, f, r = s["d"], s["kv"] * s["hd"], s["ffn"], s["rank"]
    return {
        "attn_norm": ((d,), None), "mlp_norm": ((d,), None),
        "wq": ((d, d), d ** -0.5), "wk": ((d, kvd), d ** -0.5),
        "wv": ((d, kvd), d ** -0.5), "wo": ((d, d), d ** -0.5),
        "gate": ((d, f), d ** -0.5), "up": ((d, f), d ** -0.5),
        "down": ((f, d), f ** -0.5),
        "lora_q_a": ((d, r), 0.02), "lora_q_b": ((r, d), 0.02),
        "lora_v_a": ((d, r), 0.02), "lora_v_b": ((r, kvd), 0.02),
    }


def top_shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    return {"embed": ((s["vocab"], s["d"]), 1.0),
            "final_norm": ((s["d"],), None),
            "lm_head": ((s["d"], s["vocab"]), s["d"] ** -0.5)}


def seed_key(seed: int):
    """A key from any whole number up to past 2**31."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def draw(key, tensor_index: int, layer: int, shape, std):
    """One tensor of one layer, float32 (``layer`` -1 for the top)."""
    import jax
    import jax.numpy as jnp
    if std is None:
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(jax.random.fold_in(key, tensor_index), layer + 1)
    return jax.random.normal(k, shape, jnp.float32) * std


def lora_host(cfg: dict, seed: int) -> dict:
    """The adapters, stacked over layers, as host numpy float32. Both
    factors are drawn non-zero so that a served adapter changes the
    output (a zero ``b`` would make the install invisible)."""
    rng = np.random.default_rng([int(seed), 0x10A])
    L = sizes(cfg)["layers"]
    shapes = layer_shapes(cfg)
    return {name: (rng.standard_normal((L,) + shapes[name][0])
                   .astype(np.float32) * shapes[name][1])
            for name in LORA_TENSORS}


def base_device(cfg: dict, seed: int) -> dict:
    """The frozen base, stacked over layers, made on the device in one
    jitted call."""
    import jax
    import jax.numpy as jnp
    L = sizes(cfg)["layers"]
    lshapes, tshapes = layer_shapes(cfg), top_shapes(cfg)

    def make(key):
        out = {}
        for ti, name in enumerate(BASE_TENSORS):
            shape, std = lshapes[name]
            out[name] = jax.vmap(
                lambda l, ti=ti, shape=shape, std=std:
                draw(key, ti, l, shape, std))(jnp.arange(L))
        for ti, name in enumerate(TOP_TENSORS):
            shape, std = tshapes[name]
            out[name] = draw(key, 100 + ti, -1, shape, std)
        return out

    return jax.jit(make)(seed_key(seed))


# --------------------------------------------------------------------- #
# arithmetic
# --------------------------------------------------------------------- #

def _rms(x, scale, eps):
    import jax.numpy as jnp
    return x * jnp.reciprocal(jnp.sqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * scale


def _rotary(x, theta):
    """x: (B, H, T, hd); split-half convention, positions 0..T-1."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (np.arange(half) / half))
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def hidden(base: dict, lora: dict, tokens, cfg: dict, quant: str = ""):
    """Final-norm hidden states (B, T, d) of ``tokens`` (B, T)."""
    import jax
    import jax.numpy as jnp
    s = sizes(cfg)
    ein = make_ein(quant)
    scale = s["alpha"] / s["rank"]
    B, T = tokens.shape
    x = base["embed"][tokens]
    mask = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def layer(x, w):
        h = _rms(x, w["attn_norm"], s["eps"])
        q = ein("btd,de->bte", h, w["wq"]) + scale * ein(
            "btr,re->bte", ein("btd,dr->btr", h, w["lora_q_a"]),
            w["lora_q_b"])
        k = ein("btd,de->bte", h, w["wk"])
        v = ein("btd,de->bte", h, w["wv"]) + scale * ein(
            "btr,re->bte", ein("btd,dr->btr", h, w["lora_v_a"]),
            w["lora_v_b"])
        q = _rotary(q.reshape(B, T, s["heads"], s["hd"])
                    .transpose(0, 2, 1, 3), s["theta"])
        k = _rotary(k.reshape(B, T, s["kv"], s["hd"])
                    .transpose(0, 2, 1, 3), s["theta"])
        v = v.reshape(B, T, s["kv"], s["hd"]).transpose(0, 2, 1, 3)
        g = s["heads"] // s["kv"]
        qg = q.reshape(B, s["kv"], g, T, s["hd"])
        sc = ein("bkgqd,bktd->bkgqt", qg, k) * (s["hd"] ** -0.5)
        sc = jnp.where(mask, sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        o = ein("bkgqt,bktd->bkgqd", p, v).reshape(B, s["heads"], T, s["hd"])
        o = o.transpose(0, 2, 1, 3).reshape(B, T, s["d"])
        x = x + ein("btd,de->bte", o, w["wo"])
        h = _rms(x, w["mlp_norm"], s["eps"])
        a = jax.nn.silu(ein("btd,df->btf", h, w["gate"])) * ein(
            "btd,df->btf", h, w["up"])
        return x + ein("btf,fd->btd", a, w["down"]), None

    stacked = {n: base[n] for n in BASE_TENSORS}
    stacked.update({n: lora[n] for n in LORA_TENSORS})
    x, _ = jax.lax.scan(layer, x, stacked)
    return _rms(x, base["final_norm"], s["eps"])


def logits(base, lora, tokens, cfg, quant: str = ""):
    return make_ein(quant)("btd,dv->btv",
                           hidden(base, lora, tokens, cfg, quant),
                           base["lm_head"])


def loss(trainable, frozen, x, y, cfg, quant: str = "", keep=None):
    """Mean next-token cross-entropy of batch ``x`` against ``y``.
    ``keep`` (a fault of the tests): the mean over those rows' positions
    only — positions, since the batch may hold one row."""
    import jax
    import jax.numpy as jnp
    lg = logits(frozen, trainable, x, cfg, quant)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(lg, -1), y[..., None],
                               -1)[..., 0]
    if keep is not None:
        nll = nll.reshape(-1)[: int(nll.size * keep)]
    return jnp.mean(nll)


# --------------------------------------------------------------------- #
# what the harness asks of a family
# --------------------------------------------------------------------- #

def make_weights(cfg: dict, seed: int):
    """(trainable, frozen) as the reference holds them, on the device."""
    import jax.numpy as jnp
    lora = {k: jnp.asarray(v) for k, v in lora_host(cfg, seed).items()}
    return lora, base_device(cfg, seed)


def train_batches(cfg: dict, shape: dict, seed: int):
    """The round's feed, in the order the program's loader gives it:
    ``local_steps`` batches of (x, y), then the test batch."""
    from benchmark.lib import data
    return data.lm_batches(sizes(cfg)["vocab"], shape, seed)


def served_gaps(cfg: dict, seed: int, requests: list, quant: str = "",
                rows_per_block: int = 4, pad_to: int = 128) -> dict:
    """For each served token of each request, how far its reference logit
    lies below the reference's best at that position, in units of that
    position's logit spread. With ``quant`` (the control) the token judged
    is the one the lower precision puts first."""
    import jax
    import jax.numpy as jnp
    lora, base = make_weights(cfg, seed)
    longest = max(len(r["prompt"]) + len(r["tokens"]) for r in requests)
    T = -(-longest // pad_to) * pad_to

    @jax.jit
    def block(toks, served, base, lora):    # weights as arguments
        ref = logits(base, lora, toks, cfg)
        judged = (jnp.argmax(logits(base, lora, toks, cfg, quant), -1)
                  if quant else served)
        got = jnp.take_along_axis(ref, judged[..., None], -1)[..., 0]
        # the gap at every position, so that only rows x T numbers (not
        # the logits, 0.4 GB a row) come back to the host
        return (jnp.max(ref, -1) - got) / jnp.std(ref, -1)

    gaps, n_tokens = [], 0
    for start in range(0, len(requests), rows_per_block):
        rows = requests[start:start + rows_per_block]
        toks = np.zeros((rows_per_block, T), np.int32)
        served = np.zeros((rows_per_block, T), np.int32)
        for i, r in enumerate(rows):
            lp, n = len(r["prompt"]), len(r["tokens"])
            toks[i, :lp + n] = np.concatenate([r["prompt"], r["tokens"]])
            # position p judges the token served after it
            served[i, lp - 1:lp - 1 + n] = r["tokens"]
        gap = np.asarray(block(jnp.asarray(toks), jnp.asarray(served),
                               base, lora))
        for i, r in enumerate(rows):
            lp, n = len(r["prompt"]), len(r["tokens"])
            gaps.append(float(np.max(gap[i, lp - 1:lp - 1 + n])))
            n_tokens += n
    return {"token_gap": max(gaps), "tokens_compared": n_tokens,
            "requests_compared": len(requests)}
