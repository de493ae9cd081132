"""Plain reference of the ``kimi_k2`` family as one chip of a deployment
holds it: a decoder with multi-head latent attention in every block, a
dense SwiGLU MLP in the first ``first_k_dense_replace`` blocks and, in the
rest, a routed-expert layer of which this chip holds ``experts_held.count``
of the ``published.n_routed_experts`` experts beside the shared expert;
pre-RMSNorm, untied head; LoRA on the four latent projections. As the
configuration file states it. float32, ``highest`` matmul precision, no
kernels, no cache, no batching. Imports nothing of the program.

With ``h`` a block's normed input (epsilon ``rms_norm_eps``)::

    c_q = RMSNorm(h W_qa);  q = c_q W_qb          heads of [nope | rope]
    [c_kv | k_rope] = h W_kva;  c_kv = RMSNorm(c_kv)
    c_kv W_kvb                                      heads of [k_nope | v]
    key = [k_nope | rot(k_rope)], k_rope shared by the heads
    scores = softmax(q k^T (nope + rope)^-1/2 m^2), causal
    m = 0.1 mscale_all_dim ln(factor) + 1;  out = (heads' values) W_o

    s = sigmoid(h W_g)                              over ALL the experts
    chosen = top_k(s + b);  g_e = s_e / (sum_chosen s + 1e-20) * scale
    ffn = shared(h) + sum_{e chosen and held} g_e expert_e(h)

each expert ``W_down(silu(W_gate h) * W_up h)``. What the experts held on
the deployment's other chips would add is left out, here as in the
program; the routed experts are a loop over the held ones on dense masks
(every held expert multiplies every token; the mask is the gate or zero).
The rotary columns turn at YaRN's frequencies, half-split (``rot``).

The expert blocks are alike, so the reference holds their tensors stacked
on a layer axis and scans one block's body over them, and scans one
expert's body over a block's held experts: the mathematics of a loop, and
a program the compiler finishes in time.

The frozen matrices are bfloat16 VALUES (drawn in float32, rounded once,
as the configuration's ``param_dtype`` says): both sides hold the same
numbers. The reference keeps them in the bfloat16 they are drawn in (a
float32 copy of 4.17e9 parameters does not fit the chip) and widens one
layer at a time to float32 inside ``jax.checkpoint``; attention runs in
blocks of ``HEAD_BLOCK`` heads (64 x 4096^2 float32 scores are 4.3 GB at
once).

``quant="fp8"`` is the control: every matrix product the program runs in
bfloat16 takes both operands rounded to 4 significant bits (e4m3) under a
per-tensor scale. The router's product, float32 in the program, stays
float32 in the control.
"""

from __future__ import annotations

import numpy as np

from benchmark.lib.precision import make_ein

# order fixes each tensor's key: never reorder, only append
BASE_TENSORS = ("mix_norm", "mlp_norm", "q_a", "q_a_norm", "q_b", "kv_a",
                "kv_a_norm", "kv_b", "o", "gate", "up", "down", "router",
                "router_bias", "experts_gate", "experts_up", "experts_down",
                "shared_gate", "shared_up", "shared_down")
ATTN_TENSORS = BASE_TENSORS[:9]
DENSE_TENSORS = ATTN_TENSORS + ("gate", "up", "down")
MOE_TENSORS = ATTN_TENSORS + BASE_TENSORS[12:]
LORA_ON = ("q_a", "q_b", "kv_a", "kv_b")
LORA_TENSORS = tuple(f"lora_{p}_{f}" for p in LORA_ON for f in "ab")
TOP_TENSORS = ("embed", "final_norm", "lm_head")
# what stays float32 whatever ``param_dtype`` says (its ``float32`` list)
FLOAT32 = ("mix_norm", "mlp_norm", "q_a_norm", "kv_a_norm", "router",
           "router_bias", "final_norm", "lm_head")
HEAD_BLOCK = 8


def sizes(cfg: dict) -> dict:
    rope = cfg["rope_scaling"]
    held = cfg["experts_held"]
    return dict(
        d=int(cfg["hidden_size"]), heads=int(cfg["num_attention_heads"]),
        qr=int(cfg["q_lora_rank"]), kvr=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        vd=int(cfg["v_head_dim"]), ffn=int(cfg["intermediate_size"]),
        moe=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["n_shared_experts"]),
        experts=int(cfg["published"]["n_routed_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        first=int(held["first"]), count=int(held["count"]),
        route_scale=float(cfg["routed_scaling_factor"]),
        vocab=int(cfg["vocab_size"]), layers=int(cfg["num_hidden_layers"]),
        dense=int(cfg["first_k_dense_replace"]),
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        factor=float(rope["factor"]),
        original=int(rope["original_max_position_embeddings"]),
        beta_fast=float(rope["beta_fast"]), beta_slow=float(rope["beta_slow"]),
        mscale=float(rope["mscale"]),
        mscale_all_dim=float(rope["mscale_all_dim"]),
        rank=int(cfg["lora"]["rank"]), alpha=float(cfg["lora"]["alpha"]))


def is_dense(cfg: dict, layer: int) -> bool:
    return layer < sizes(cfg)["dense"]


def layer_shapes(cfg: dict) -> dict:
    """name -> (shape of one layer's tensor, how it is drawn): a float is
    the std of a normal draw; "ones" is named."""
    s = sizes(cfg)
    d, H, qr, kvr = s["d"], s["heads"], s["qr"], s["kvr"]
    qk, kv = s["nope"] + s["rope"], s["nope"] + s["vd"]
    f, m, sh, G = s["ffn"], s["moe"], s["shared"] * s["moe"], s["count"]
    rank = s["rank"]
    dims = {"q_a": (d, qr), "q_b": (qr, H * qk), "kv_a": (d, kvr + s["rope"]),
            "kv_b": (kvr, H * kv)}
    out = {
        "mix_norm": ((d,), "ones"), "mlp_norm": ((d,), "ones"),
        "q_a_norm": ((qr,), "ones"), "kv_a_norm": ((kvr,), "ones"),
        "o": ((H * s["vd"], d), (H * s["vd"]) ** -0.5),
        "gate": ((d, f), d ** -0.5), "up": ((d, f), d ** -0.5),
        "down": ((f, d), f ** -0.5),
        "router": ((d, s["experts"]), d ** -0.5),
        "router_bias": ((s["experts"],), 0.01),
        "experts_gate": ((G, d, m), d ** -0.5),
        "experts_up": ((G, d, m), d ** -0.5),
        "experts_down": ((G, m, d), m ** -0.5),
        "shared_gate": ((d, sh), d ** -0.5), "shared_up": ((d, sh), d ** -0.5),
        "shared_down": ((sh, d), max(sh, 1) ** -0.5),
    }
    for name, (a, b) in dims.items():
        out[name] = ((a, b), a ** -0.5)
        out[f"lora_{name}_a"] = ((a, rank), 0.02)
        out[f"lora_{name}_b"] = ((rank, b), 0.02)
    return out


def top_shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    return {"embed": ((s["vocab"], s["d"]), 1.0),
            "final_norm": ((s["d"],), "ones"),
            "lm_head": ((s["d"], s["vocab"]), s["d"] ** -0.5)}


def seed_key(seed: int):
    """A key from any whole number up to past 2**31."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _dtype(cfg: dict, name: str):
    import jax.numpy as jnp
    return jnp.float32 if name in FLOAT32 else getattr(
        jnp, cfg["param_dtype"]["frozen"])


def draw(cfg: dict, key, name: str, tensor_index: int, layer, shape, how):
    """One tensor of one layer (``layer`` -1 for the top; it may be
    traced), drawn in float32 and rounded once to the type it is held
    in."""
    import jax
    import jax.numpy as jnp
    if how == "ones":
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(jax.random.fold_in(key, tensor_index), layer + 1)
    return (jax.random.normal(k, shape, jnp.float32) * how).astype(
        _dtype(cfg, name))


def draw_layer(cfg: dict, key, layer, dense=None) -> dict:
    """The frozen tensors of block ``layer``; ``dense`` says which kind of
    block it is where ``layer`` is traced."""
    shapes = layer_shapes(cfg)
    if dense is None:
        dense = is_dense(cfg, int(layer))
    return {name: draw(cfg, key, name, BASE_TENSORS.index(name), layer,
                       *shapes[name])
            for name in (DENSE_TENSORS if dense else MOE_TENSORS)}


def draw_top(cfg: dict, key) -> dict:
    return {name: draw(cfg, key, name, 100 + i, -1, *top_shapes(cfg)[name])
            for i, name in enumerate(TOP_TENSORS)}


def lora_host(cfg: dict, seed: int) -> list:
    """The adapters, one dict a layer, as host numpy float32, both factors
    non-zero (a zero ``b`` would leave the first step's ``a`` without a
    gradient)."""
    rng = np.random.default_rng([int(seed), 0x10A])
    shapes = layer_shapes(cfg)
    return [{name: (rng.standard_normal(shapes[name][0]).astype(np.float32)
                    * shapes[name][1]) for name in LORA_TENSORS}
            for _ in range(sizes(cfg)["layers"])]


def base_device(cfg: dict, seed: int) -> dict:
    """The frozen base on the device: ``dense`` a list of the leading
    dense blocks, ``moe`` the expert blocks' tensors stacked on a leading
    layer axis (the same draws, a layer a jitted call: the float32 draw of
    the whole tree at once would not fit), and the top."""
    import functools

    import jax
    import jax.numpy as jnp
    key, s = seed_key(seed), sizes(cfg)
    one = jax.jit(functools.partial(draw_layer, cfg),
                  static_argnames="dense")
    layers = [one(key, l, dense=is_dense(cfg, l)) for l in range(s["layers"])]
    moe = layers[s["dense"]:]
    out = {"dense": layers[:s["dense"]],
           **jax.jit(functools.partial(draw_top, cfg))(key)}
    if moe:
        # stacked a tensor at a time, the layers' own copies dropped as it
        # goes: never two whole trees
        out["moe"] = {}
        for name in list(moe[0]):
            out["moe"][name] = jnp.stack([w.pop(name) for w in moe])
    return out


def layer_of(base: dict, layer: int) -> dict:
    """Block ``layer``'s frozen tensors out of ``base_device``'s tree."""
    n_dense = len(base["dense"])
    if layer < n_dense:
        return base["dense"][layer]
    return {k: v[layer - n_dense] for k, v in base["moe"].items()}


# --------------------------------------------------------------------- #
# arithmetic
# --------------------------------------------------------------------- #

def yarn_frequencies(cfg: dict) -> np.ndarray:
    """The ``rope / 2`` rotary frequencies: ``f_i = theta^(-2i/rope)``;
    ``low``, ``high`` = floor, ceil of ``rope ln(original / (2 pi beta)) /
    (2 ln theta)`` at ``beta_fast``, ``beta_slow``, clipped to ``[0, rope -
    1]``; ``f_i / factor`` on the ramp's far side, ``f_i`` on its near
    side, blended between."""
    s = sizes(cfg)
    w, half = s["rope"], s["rope"] // 2
    i = np.arange(half, dtype=np.float64)
    f = s["theta"] ** (-2.0 * i / w)

    def at(beta):
        return (w * np.log(s["original"] / (2 * np.pi * beta))
                / (2 * np.log(s["theta"])))

    low = max(int(np.floor(at(s["beta_fast"]))), 0)
    high = min(int(np.ceil(at(s["beta_slow"]))), w - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f / s["factor"] * ramp + f * (1.0 - ramp)).astype(np.float32)


def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * float(np.log(factor)) + 1.0


def softmax_scale(cfg: dict) -> float:
    s = sizes(cfg)
    m = mscale(s["factor"], s["mscale_all_dim"])
    return float((s["nope"] + s["rope"]) ** -0.5 * m * m)


def _rms(x, scale, eps):
    import jax.numpy as jnp
    return x * jnp.reciprocal(jnp.sqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * scale


def _rot(x, cfg: dict, start: int = 0):
    """Half-split rotation of the last axis of ``x`` (B, T, ., rope)."""
    import jax.numpy as jnp
    s = sizes(cfg)
    half = x.shape[-1] // 2
    pos = jnp.arange(start, start + x.shape[1], dtype=jnp.float32)
    ang = pos[:, None] * jnp.asarray(yarn_frequencies(cfg))   # (T, half)
    amp = mscale(s["factor"], s["mscale"]) / mscale(s["factor"],
                                                    s["mscale_all_dim"])
    cos, sin = (jnp.cos(ang) * amp)[:, None], (jnp.sin(ang) * amp)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _adapted(ein, h, w, name, scale):
    return ein("btd,de->bte", h, w[name]) + scale * ein(
        "btr,re->bte", ein("btd,dr->btr", h, w[f"lora_{name}_a"]),
        w[f"lora_{name}_b"])


def mla(h, w, cfg: dict, quant: str = ""):
    """Latent attention on normed input ``h`` (B, T, d) -> (B, T, d)."""
    import jax
    import jax.numpy as jnp
    s, ein = sizes(cfg), make_ein(quant)
    scale = s["alpha"] / s["rank"]
    B, T, _ = h.shape
    H, nope, rope, vd = s["heads"], s["nope"], s["rope"], s["vd"]
    c_q = _rms(_adapted(ein, h, w, "q_a", scale), w["q_a_norm"], s["eps"])
    q = _adapted(ein, c_q, w, "q_b", scale).reshape(B, T, H, nope + rope)
    kva = _adapted(ein, h, w, "kv_a", scale)
    c_kv = _rms(kva[..., :s["kvr"]], w["kv_a_norm"], s["eps"])
    k_rope = _rot(kva[..., None, s["kvr"]:], cfg)           # (B, T, 1, rope)
    kv = _adapted(ein, c_kv, w, "kv_b", scale).reshape(B, T, H, nope + vd)
    q = jnp.concatenate([q[..., :nope], _rot(q[..., nope:], cfg)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (B, T, H, rope))], -1)
    v = kv[..., nope:]
    hb = HEAD_BLOCK if H % HEAD_BLOCK == 0 else 1
    mask = jnp.tril(jnp.ones((T, T), bool))
    sm = softmax_scale(cfg)

    @jax.checkpoint
    def heads(args):
        qb, kb, vb = args                                   # (B, T, hb, .)
        sc = ein("bqhd,bkhd->bhqk", qb, kb) * sm
        p = jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1)
        return ein("bhqk,bkhd->bqhd", p, vb)

    cut = lambda t: jnp.moveaxis(                       # noqa: E731
        t.reshape(B, T, H // hb, hb, t.shape[-1]), 2, 0)
    o = jax.lax.map(heads, (cut(q), cut(k), cut(v)))        # (H/hb, B, T, hb, vd)
    o = jnp.moveaxis(o, 0, 2).reshape(B, T, H * vd)
    return ein("bte,ed->btd", o, w["o"])


def _swiglu(ein, h, gate, up, down):
    import jax
    return ein("tf,fd->td", jax.nn.silu(ein("td,df->tf", h, gate))
               * ein("td,df->tf", h, up), down)


def route(h, w, cfg: dict):
    """(chosen (T, K) expert ids, gates (T, K)) of tokens ``h`` (T, d):
    float32 at ``highest``, in the control too."""
    import jax
    import jax.numpy as jnp
    s = sizes(cfg)
    sc = jax.nn.sigmoid(make_ein("")("td,de->te", h, w["router"]))
    _, chosen = jax.lax.top_k(sc + w["router_bias"], s["top_k"])
    picked = jnp.take_along_axis(sc, chosen, axis=-1)
    return chosen, picked / (jnp.sum(picked, -1, keepdims=True)
                             + 1e-20) * s["route_scale"]


def expert_layer(h, w, cfg: dict, quant: str = "", held=None):
    """The routed layer on normed tokens ``h`` (T, d): the shared expert
    and the held experts' part; ``held`` = (first, count) overrides the
    configuration's share (the tests add the shares up). Returns the
    output and how many assignments fell on held experts."""
    import jax
    import jax.numpy as jnp
    s, ein = sizes(cfg), make_ein(quant)
    first, count = held if held is not None else (s["first"], s["count"])
    chosen, gates = route(h, w, cfg)

    @jax.checkpoint
    def one(out, expert):
        e, gate, up, down = expert      # widened here, an expert at a time
        weight = jnp.sum(jnp.where(chosen == first + e, gates, 0.0), -1)
        wide = lambda t: t.astype(jnp.float32)             # noqa: E731
        return out + weight[:, None] * _swiglu(
            ein, h, wide(gate), wide(up), wide(down)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(count), w["experts_gate"], w["experts_up"],
        w["experts_down"]))
    local = jnp.sum((chosen >= first) & (chosen < first + count))
    if s["shared"]:
        out = out + _swiglu(ein, h, w["shared_gate"], w["shared_up"],
                            w["shared_down"])
    return out, local


def block(x, w, cfg: dict, dense: bool, quant: str = ""):
    """One decoder block on ``x`` (B, T, d); frozen tensors of ``w`` may
    arrive in bfloat16 and are widened here. Returns the output and the
    layer's held assignments."""
    import jax
    import jax.numpy as jnp
    s, ein = sizes(cfg), make_ein(quant)
    w = {k: v if k.startswith("experts_") else v.astype(jnp.float32)
         for k, v in w.items()}
    B, T, d = x.shape
    x = x + mla(_rms(x, w["mix_norm"], s["eps"]), w, cfg, quant)
    h = _rms(x, w["mlp_norm"], s["eps"]).reshape(B * T, d)
    if dense:
        y, local = _swiglu(ein, h, w["gate"], w["up"], w["down"]), 0
    else:
        y, local = expert_layer(h, w, cfg, quant)
    return x + y.reshape(B, T, d), jnp.asarray(local, jnp.int32)


def hidden(base: dict, lora: list, tokens, cfg: dict, quant: str = ""):
    """(final-norm hidden states (B, T, d), held assignments summed over
    the layers) of ``tokens`` (B, T)."""
    import jax
    import jax.numpy as jnp
    x = base["embed"][tokens].astype(jnp.float32)
    held = jnp.zeros((), jnp.int32)
    n_dense = len(base["dense"])
    for w, adapters in zip(base["dense"], lora):
        x, _ = jax.checkpoint(lambda x, w: block(x, w, cfg, True, quant))(
            x, {**w, **adapters})
    if len(lora) > n_dense:
        # the expert layers are alike: one body, scanned over their
        # stacked tensors (the adapters stacked here, the base as drawn)
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *lora[n_dense:])

        @jax.checkpoint
        def one(x, w):
            return block(x, w, cfg, False, quant)

        x, local = jax.lax.scan(one, x, {**base["moe"], **stacked})
        held = held + jnp.sum(local)
    return _rms(x, base["final_norm"], sizes(cfg)["eps"]), held


def logits(base, lora, tokens, cfg, quant: str = ""):
    h, _ = hidden(base, lora, tokens, cfg, quant)
    return make_ein(quant)("btd,dv->btv", h, base["lm_head"])


def held_count(base, lora, tokens, cfg):
    """Assignments on held experts, summed over the layers: what the
    program's ``moe_local_count`` counts."""
    return hidden(base, lora, tokens, cfg)[1]


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
    import jax
    import jax.numpy as jnp
    return (jax.tree.map(jnp.asarray, lora_host(cfg, seed)),
            base_device(cfg, seed))


def train_batches(cfg: dict, shape: dict, seed: int):
    """The round's feed, in the order the program's loader gives it."""
    from benchmark.lib import data
    return data.lm_batches(sizes(cfg)["vocab"], shape, seed)
