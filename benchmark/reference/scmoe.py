"""Plain reference of the shortcut-connected decoder (``longcat_flash``
family) as one chip of a deployment holds it: every layer is two
latent-attention sublayers and two dense SwiGLU FFNs on the residual line
beside one routed layer, of which this chip holds ``experts_held.count`` of
the ``published.n_routed_experts`` experts and computes every
zero-computation expert for its own tokens; untied head; LoRA on the four
latent projections of both sublayers. As the configuration file states it.
float32, ``highest`` matmul precision, no kernels, no cache, no batching.
Imports nothing of the program and nothing of the other references.

One layer, with ``x`` the residual stream (every norm an RMSNorm with its
own scale, epsilon ``rms_norm_eps``; no bias anywhere)::

    x1 = x  + MLA_0(norm_in0(x));      u = norm_post0(x1)
    m  = MoE(u)                        the shortcut: joins at the layer's end
    x2 = x1 + SwiGLU_0(u)              W_down(silu(W_gate u) * W_up u)
    x3 = x2 + MLA_1(norm_in1(x2));     w = norm_post1(x3)
    y  = x3 + SwiGLU_1(w) + m

``MLA_i``, on its normed input ``h`` (D = ``hidden_size``)::

    c_q = RMSNorm(h W_qa);  q = (c_q W_qb) * s_q      s_q = (D / q_lora_rank)^0.5
    [c_kv | k_r] = h W_kva; c_kv = RMSNorm(c_kv) * s_kv
                                                      s_kv = (D / kv_lora_rank)^0.5
    [k_nope | v] = c_kv W_kvb                         a head: nope + v columns
    q = [q_nope | rot(q_r)], k = [k_nope | rot(k_r)]  k_r shared by the heads,
                                                      not scaled
    out = softmax(q k^T (nope + rope)^-0.5, causal) v W_o

``rot`` is the half-split rotation at ``f_i = rope_theta^(-2i / rope)``, no
scaling. ``MoE(u)``, E routed and Z zero-computation experts::

    p = softmax(u W_r)                  float32, over all E + Z columns
    chosen = top_k(p + b)               b chooses and does not weigh
    g_e = routed_scaling_factor * p_e   not renormalised over the chosen
    m = sum_{e chosen, e held} g_e Expert_e(u) + (sum_{e chosen, e >= E} g_e) u

each expert ``W_down_e(silu(W_gate_e u) * W_up_e u)``. What the experts held
on the deployment's other chips would add is left out, here as in the
program; the held experts are a loop on dense masks (every held expert
multiplies every token; the mask is the gate or zero). The identity
experts take the routed layer's own input ``u``.

The layers are a Python loop (scanned over tensors stacked on a layer axis,
the program keeps a second copy of a layer's slices and of what the loop
saves: 6.2 GB of temporaries beside 10.6 GB of base, more than the chip
holds; looped, 4.9 GB); a layer's held experts are alike and one expert's
body is scanned over them.

The frozen matrices are bfloat16 VALUES (drawn in float32, rounded once, as
the configuration's ``param_dtype`` says): both sides hold the same
numbers. The reference keeps them in the bfloat16 they are drawn in (a
float32 copy of 5.2e9 parameters does not fit the chip) and widens a
sublayer's at a time to float32 inside ``jax.checkpoint`` (a layer is
checkpointed whole, and each of its five parts again); attention runs in
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
ATTN_TENSORS = ("q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "o")
MLP_TENSORS = ("gate", "up", "down")
SUBLAYER_TENSORS = ("in_norm", "post_norm") + ATTN_TENSORS + MLP_TENSORS
MOE_TENSORS = ("router", "router_bias", "experts_gate", "experts_up",
               "experts_down")
BASE_TENSORS = tuple(f"{name}_{i}" for i in (0, 1)
                     for name in SUBLAYER_TENSORS) + MOE_TENSORS
LORA_ON = ("q_a", "q_b", "kv_a", "kv_b")
LORA_TENSORS = tuple(f"lora_{p}_{i}_{f}" for i in (0, 1) for p in LORA_ON
                     for f in "ab")
TOP_TENSORS = ("embed", "final_norm", "lm_head")
# what stays float32 whatever ``param_dtype`` says (its ``float32`` list)
FLOAT32 = ("in_norm", "post_norm", "q_a_norm", "kv_a_norm", "router",
           "router_bias", "final_norm", "lm_head")
HEAD_BLOCK = 4


def sizes(cfg: dict) -> dict:
    held = cfg["experts_held"]
    return dict(
        d=int(cfg["hidden_size"]), heads=int(cfg["num_attention_heads"]),
        qr=int(cfg["q_lora_rank"]), kvr=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        vd=int(cfg["v_head_dim"]), ffn=int(cfg["ffn_hidden_size"]),
        moe=int(cfg["expert_ffn_hidden_size"]),
        experts=int(cfg["published"]["n_routed_experts"]),
        zero=int(cfg["zero_expert_num"]), top_k=int(cfg["moe_topk"]),
        first=int(held["first"]), count=int(held["count"]),
        route_scale=float(cfg["routed_scaling_factor"]),
        scale_q=bool(cfg["mla_scale_q_lora"]),
        scale_kv=bool(cfg["mla_scale_kv_lora"]),
        vocab=int(cfg["vocab_size"]), layers=int(cfg["num_layers"]),
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        rank=int(cfg["lora"]["rank"]), alpha=float(cfg["lora"]["alpha"]))


def router_bias_std(cfg: dict) -> float:
    """A tenth of the standard deviation of the scores at the router's
    draw: unit-variance logits over ``n`` columns give softmax scores of
    mean ``1 / n`` and standard deviation ``(e - 1)^0.5 / n`` (a lognormal
    over its mean), so ``b`` moves near-ties only."""
    s = sizes(cfg)
    return 0.1 * float(np.sqrt(np.e - 1.0)) / (s["experts"] + s["zero"])


def layer_shapes(cfg: dict) -> dict:
    """name -> (shape of one layer's tensor, how it is drawn): a float is
    the std of a normal draw; "ones" is named."""
    s = sizes(cfg)
    d, H, qr, kvr = s["d"], s["heads"], s["qr"], s["kvr"]
    qk, kv = s["nope"] + s["rope"], s["nope"] + s["vd"]
    f, m, G, rank = s["ffn"], s["moe"], s["count"], s["rank"]
    width = s["experts"] + s["zero"]
    dims = {"q_a": (d, qr), "q_b": (qr, H * qk), "kv_a": (d, kvr + s["rope"]),
            "kv_b": (kvr, H * kv)}
    # the two latent scales stand for the full-rank fan-in: where one is
    # on, its up-projection is drawn at that fan-in, so that the scaled q,
    # k_nope and v have the unit variance k_rope has (``assumed``)
    fan_in = {**({"q_b": d} if s["scale_q"] else {}),
              **({"kv_b": d} if s["scale_kv"] else {})}
    out = {
        "router": ((d, width), d ** -0.5),
        "router_bias": ((width,), router_bias_std(cfg)),
        "experts_gate": ((G, d, m), d ** -0.5),
        "experts_up": ((G, d, m), d ** -0.5),
        "experts_down": ((G, m, d), m ** -0.5),
    }
    for i in (0, 1):
        out.update({
            f"in_norm_{i}": ((d,), "ones"), f"post_norm_{i}": ((d,), "ones"),
            f"q_a_norm_{i}": ((qr,), "ones"),
            f"kv_a_norm_{i}": ((kvr,), "ones"),
            f"o_{i}": ((H * s["vd"], d), (H * s["vd"]) ** -0.5),
            f"gate_{i}": ((d, f), d ** -0.5), f"up_{i}": ((d, f), d ** -0.5),
            f"down_{i}": ((f, d), f ** -0.5)})
        for name, (a, b) in dims.items():
            out[f"{name}_{i}"] = ((a, b), fan_in.get(name, a) ** -0.5)
            out[f"lora_{name}_{i}_a"] = ((a, rank), 0.02)
            out[f"lora_{name}_{i}_b"] = ((rank, b), 0.02)
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
    plain = name[:-2] if name[-2:] in ("_0", "_1") else name
    return jnp.float32 if plain in FLOAT32 else getattr(
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


def draw_layer(cfg: dict, key, layer) -> dict:
    """The frozen tensors of layer ``layer``."""
    shapes = layer_shapes(cfg)
    return {name: draw(cfg, key, name, i, layer, *shapes[name])
            for i, name in enumerate(BASE_TENSORS)}


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
    """The frozen base on the device: ``layers`` a list of the layers'
    tensors (a layer a jitted call: the float32 draw of the whole tree at
    once would not fit), and the top."""
    import functools

    import jax
    key = seed_key(seed)
    one = jax.jit(functools.partial(draw_layer, cfg))
    return {"layers": [one(key, l) for l in range(sizes(cfg)["layers"])],
            **jax.jit(functools.partial(draw_top, cfg))(key)}


# --------------------------------------------------------------------- #
# arithmetic
# --------------------------------------------------------------------- #

def rotary_frequencies(cfg: dict) -> np.ndarray:
    s = sizes(cfg)
    i = np.arange(s["rope"] // 2, dtype=np.float64)
    return (s["theta"] ** (-2.0 * i / s["rope"])).astype(np.float32)


def latent_scales(cfg: dict) -> tuple:
    """(s_q, s_kv)."""
    s = sizes(cfg)
    return ((s["d"] / s["qr"]) ** 0.5 if s["scale_q"] else 1.0,
            (s["d"] / s["kvr"]) ** 0.5 if s["scale_kv"] else 1.0)


def softmax_scale(cfg: dict) -> float:
    s = sizes(cfg)
    return float((s["nope"] + s["rope"]) ** -0.5)


def _rms(x, scale, eps):
    import jax.numpy as jnp
    return x * jnp.reciprocal(jnp.sqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * scale


def _rot(x, cfg: dict, start: int = 0):
    """Half-split rotation of the last axis of ``x`` (B, T, ., rope)."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    pos = jnp.arange(start, start + x.shape[1], dtype=jnp.float32)
    ang = pos[:, None] * jnp.asarray(rotary_frequencies(cfg))   # (T, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _wide(w: dict) -> dict:
    import jax.numpy as jnp
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def _adapted(ein, h, w, name, scale):
    return ein("btd,de->bte", h, w[name]) + scale * ein(
        "btr,re->bte", ein("btd,dr->btr", h, w[f"lora_{name}_a"]),
        w[f"lora_{name}_b"])


def mla(h, w, cfg: dict, quant: str = ""):
    """Latent attention on normed input ``h`` (B, T, d) -> (B, T, d); ``w``
    holds one sublayer's tensors under their plain names (``q_a`` ...,
    ``lora_q_a_a`` ...), bfloat16 or float32."""
    import jax
    import jax.numpy as jnp
    s, ein = sizes(cfg), make_ein(quant)
    w = _wide(w)
    scale = s["alpha"] / s["rank"]
    s_q, s_kv = latent_scales(cfg)
    B, T, _ = h.shape
    H, nope, rope, vd = s["heads"], s["nope"], s["rope"], s["vd"]
    c_q = _rms(_adapted(ein, h, w, "q_a", scale), w["q_a_norm"], s["eps"])
    q = (_adapted(ein, c_q, w, "q_b", scale) * s_q).reshape(
        B, T, H, nope + rope)
    kva = _adapted(ein, h, w, "kv_a", scale)
    c_kv = _rms(kva[..., :s["kvr"]], w["kv_a_norm"], s["eps"]) * s_kv
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


def swiglu(h, w, quant: str = ""):
    """A dense FFN on tokens ``h`` (T, d); ``w``: ``gate``, ``up``,
    ``down``, bfloat16 or float32."""
    w = _wide(w)
    return _swiglu(make_ein(quant), h, w["gate"], w["up"], w["down"])


def route(h, w, cfg: dict):
    """(chosen (T, K) column ids, gates (T, K)) of tokens ``h`` (T, d):
    float32 at ``highest``, in the control too."""
    import jax
    import jax.numpy as jnp
    s = sizes(cfg)
    p = jax.nn.softmax(make_ein("")("td,de->te", h, w["router"]), axis=-1)
    _, chosen = jax.lax.top_k(p + w["router_bias"], s["top_k"])
    return chosen, jnp.take_along_axis(p, chosen, axis=-1) * s["route_scale"]


def routed_layer(h, w, cfg: dict, quant: str = "", held=None,
                 identity: bool = True):
    """The routed layer on normed tokens ``h`` (T, d): the held experts'
    part and the zero-computation experts' (``identity`` false leaves the
    latter out); ``held`` = (first, count) overrides the configuration's
    share (the tests add the shares up). Returns the output, how many
    assignments fell on held experts and how many on zero-computation
    ones."""
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
    zero = chosen >= s["experts"]
    if identity:
        out = out + jnp.sum(jnp.where(zero, gates, 0.0), -1)[:, None] * h
    local = jnp.sum((chosen >= first) & (chosen < first + count))
    return out, local, jnp.sum(zero)


def _sub(w: dict, i: int, names) -> dict:
    """Sublayer ``i``'s tensors of ``names`` under their plain names."""
    return {name: w[f"{name}_{i}"] for name in names}


def _mixer(w: dict, i: int) -> dict:
    """Sublayer ``i``'s latent attention: its tensors and its adapters."""
    return {**_sub(w, i, ATTN_TENSORS),
            **{f"lora_{p}_{f}": w[f"lora_{p}_{i}_{f}"]
               for p in LORA_ON for f in "ab"}}


def layer(x, w, cfg: dict, quant: str = ""):
    """One layer on ``x`` (B, T, d); the frozen tensors of ``w`` may
    arrive in bfloat16 and are widened a sublayer at a time. Returns the
    output, the layer's held assignments and its zero-computation ones."""
    import jax
    s = sizes(cfg)
    B, T, d = x.shape
    norm = lambda name, t: _rms(t, w[name], s["eps"])       # noqa: E731
    attend = jax.checkpoint(lambda h, ws: mla(h, ws, cfg, quant))
    dense = jax.checkpoint(lambda h, ws: swiglu(h, ws, quant))
    x = x + attend(norm("in_norm_0", x), _mixer(w, 0))
    u = norm("post_norm_0", x).reshape(B * T, d)
    m, local, zero = routed_layer(u, w, cfg, quant)
    x = x + dense(u, _sub(w, 0, MLP_TENSORS)).reshape(B, T, d)
    x = x + attend(norm("in_norm_1", x), _mixer(w, 1))
    v = norm("post_norm_1", x).reshape(B * T, d)
    x = x + (dense(v, _sub(w, 1, MLP_TENSORS)) + m).reshape(B, T, d)
    return x, (local, zero)


def hidden(base: dict, lora: list, tokens, cfg: dict, quant: str = ""):
    """(final-norm hidden states (B, T, d), (held, zero-computation)
    assignments summed over the layers) of ``tokens`` (B, T)."""
    import jax
    import jax.numpy as jnp
    x = base["embed"][tokens].astype(jnp.float32)
    one = jax.checkpoint(lambda x, w: layer(x, w, cfg, quant))
    local = zero = jnp.zeros((), jnp.int32)
    for w, adapters in zip(base["layers"], lora):
        x, (here, there) = one(x, {**w, **adapters})
        local, zero = local + here, zero + there
    return _rms(x, base["final_norm"], sizes(cfg)["eps"]), (local, zero)


def logits(base, lora, tokens, cfg, quant: str = ""):
    h, _ = hidden(base, lora, tokens, cfg, quant)
    return make_ein(quant)("btd,dv->btv", h, base["lm_head"])


def counts(base, lora, tokens, cfg):
    """(assignments on held experts, on zero-computation experts), summed
    over the layers: what the program's ``moe_local_count`` and
    ``moe_zero_count`` count."""
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
