"""Plain reference of the GLM-5 hybrid (``kda_moe`` family: GLM-5.3-Flash's
language model) as one chip of a deployment holds it: the published layers
``layers_kept`` (their mixer by ``layer_types``, their FFN by
``mlp_layer_types``), ``experts_held.count`` of the router's
``published.n_routed_experts`` experts beside the shared one, a slice of
the vocabulary, untied head, LoRA on KDA's ``q_proj`` and ``v_proj`` and on
the four latent projections. As the configuration file states it.
float32, ``highest`` matmul precision, no kernels, no cache, no batching.
Imports nothing of the program and nothing of the other references.

The token's state is ``hc_mult`` = n residual streams, ``x`` (n, d); the
embedding is copied into each. Every sublayer F (a mixer, then an FFN) is
wrapped in mHC (``hc_sinkhorn_iters``, ``hc_eps``)::

    u      = x / rms(x)                                over all n d values
    H_pre  = sigmoid(a_pre u phi_pre + b_pre)           (n,)
    H_post = 2 sigmoid(a_post u phi_post + b_post)      (n,)
    H_res  = Sinkhorn(exp(a_res mat(u phi_res) + b_res))   rows, then columns
    x     <- H_res x + H_post^T F(RMSNorm(H_pre x))

and the head reads ``RMSNorm(sum of the streams)``. Every norm is an
RMSNorm with its own scale, epsilon ``rms_norm_eps``; no bias anywhere.

KDA (``linear_attention``; ``linear_attn_config``), on the sublayer's
normed input ``h``::

    q, k, v = SiLU(conv(h W_q)), SiLU(conv(h W_k)), SiLU(conv(h W_v))
    q, k    <- q / sqrt(|q|^2 + 1e-6), per head
    g    = gate_lower_bound * sigmoid(exp(A_log) ((h W_fa) W_fb + dt_bias))
    beta = sigmoid(h W_b)
    S_t  = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t  = head_dim^-1/2 S_t^T q_t
    out  = (RMSNorm_head(o) * sigmoid((h W_ga) W_gb)) W_o

``conv`` a causal depthwise convolution of ``short_conv_kernel_size`` taps.
The recurrence runs token by token (``jax.lax.scan``), under
``jax.checkpoint`` every ``CHECKPOINT`` tokens so that its backward fits.

Sparse latent attention with no rotary columns
(``deepseek_sparse_attention``)::

    c_q = RMSNorm(h W_qa);  q = c_q W_qb            heads of qk_nope_head_dim
    c_kv = RMSNorm(h W_kva);  [k | v] = c_kv W_kvb
    out = softmax(q k / qk_head_dim^1/2 over the chosen keys) v W_o

and its indexer, with no gradient::

    q^I = c_q W^QI (index_n_heads of index_head_dim);  k^I = LayerNorm(h W^KI)
    w   = (h W^w) index_n_heads^-1/2 index_head_dim^-1/2
    kbar_j = mean of k^I over positions P j .. P j + P - 1   (P = index_kpool)
    I[t, j] = sum_i w_i relu(q^I_i . kbar_j)         groups complete at t

Query ``t`` attends to the keys of the ``index_topk / P`` groups of
largest ``I[t, j]`` (``jax.lax.top_k``; ties at the last value all taken;
every complete group while there are no more) and to its own group's keys
``<= t`` while that group is incomplete.

The FFN is a dense SwiGLU (``dense``) or the routed layer (``sparse``)::

    s = sigmoid(u W_r)                float32, over ALL the experts
    chosen = top_k(s + b);  g_e = s_e / (sum_chosen s + 1e-20) * scale
    ffn = shared(u) + sum_{e chosen and held} g_e expert_e(u)

every SwiGLU ``W_down(silu(min(W_gate u, L)) * clip(W_up u, -L, L))`` with
``L`` = ``swiglu_limit``. What the experts held on the deployment's other
chips would add is left out, here as in the program; the held experts are
a loop on dense masks.

The frozen matrices are bfloat16 VALUES (drawn in float32, rounded once,
as the configuration's ``param_dtype`` says): both sides hold the same
numbers. The reference keeps them in bfloat16 and widens a sublayer at a
time inside ``jax.checkpoint``; attention runs in blocks of
``QUERY_BLOCK`` queries. Alike consecutive layers are stacked and scanned.

``quant="fp8"`` is the control: every matrix product the program runs in
bfloat16 (the projections, attention's two, the indexer's, the experts',
the head's) takes both operands rounded to fp8 (e4m3); what the program
computes in float32 (KDA's recurrence, the convolutions, mHC's product
and mixing, the router's product) stays float32.
"""

from __future__ import annotations

import numpy as np

from benchmark.lib.precision import make_ein

HYPER = ("norm", "phi", "alpha", "b_pre", "b_post", "b_res")
# order fixes each tensor's key: never reorder, only append
BASE_TENSORS = (
    tuple(f"mix_{n}" for n in HYPER) + tuple(f"mlp_{n}" for n in HYPER)
    + ("q_proj", "k_proj", "v_proj", "q_conv", "k_conv", "v_conv", "f_a",
       "f_b", "A_log", "dt_bias", "b_proj", "g_a", "g_b", "o_norm",
       "o_proj")
    + ("q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "o",
       "idx_wq", "idx_wk", "idx_k_norm", "idx_k_bias", "idx_w")
    + ("gate", "up", "down")
    + ("router", "router_bias", "experts_gate", "experts_up",
       "experts_down", "shared_gate", "shared_up", "shared_down"))
KDA_TENSORS = BASE_TENSORS[12:27]
MLA_TENSORS = BASE_TENSORS[27:39]
DENSE_TENSORS = BASE_TENSORS[39:42]
MOE_TENSORS = BASE_TENSORS[42:]
LORA_ON = {"linear_attention": ("q_proj", "v_proj"),
           "deepseek_sparse_attention": ("q_a", "q_b", "kv_a", "kv_b")}
TOP_TENSORS = ("embed", "final_norm", "lm_head")
# what stays float32 whatever ``param_dtype`` says (its ``float32`` list)
FLOAT32 = (tuple(f"{s}_{n}" for s in ("mix", "mlp") for n in HYPER)
           + ("q_conv", "k_conv", "v_conv", "A_log", "dt_bias", "o_norm",
              "q_a_norm", "kv_a_norm", "idx_k_norm", "idx_k_bias", "router",
              "router_bias", "final_norm", "lm_head"))
QUERY_BLOCK = 256
CHECKPOINT = 64


def sizes(cfg: dict) -> dict:
    la, held = cfg["linear_attn_config"], cfg["experts_held"]
    kept = [int(i) for i in cfg["layers_kept"]]
    if len(kept) != int(cfg["num_hidden_layers"]):
        raise ValueError("layers_kept names num_hidden_layers layers")
    if (int(cfg["qk_rope_head_dim"]) or int(cfg["n_group"]) != 1
            or not cfg["index_kpool_always_select_tail"]):
        raise ValueError("no rotary columns, one routing group and the "
                         "query's own group always chosen")
    return dict(
        d=int(cfg["hidden_size"]), heads=int(la["num_heads"]),
        hd=int(la["head_dim"]), conv=int(la["short_conv_kernel_size"]),
        lower=float(la["gate_lower_bound"]),
        gate_rank=int(cfg["kda_gate_rank"]),
        mla_heads=int(cfg["num_attention_heads"]),
        qr=int(cfg["q_lora_rank"]), kvr=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), qk=int(cfg["qk_head_dim"]),
        vd=int(cfg["v_head_dim"]),
        index_heads=int(cfg["index_n_heads"]),
        index_dim=int(cfg["index_head_dim"]),
        index_top_k=int(cfg["index_topk"]), pool=int(cfg["index_kpool"]),
        ffn=int(cfg["intermediate_size"]),
        moe=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["n_shared_experts"]),
        experts=int(cfg["published"]["n_routed_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        first=int(held["first"]), count=int(held["count"]),
        route_scale=float(cfg["routed_scaling_factor"]),
        limit=float(cfg["swiglu_limit"]),
        streams=int(cfg["hc_mult"]), iters=int(cfg["hc_sinkhorn_iters"]),
        hc_eps=float(cfg["hc_eps"]),
        vocab=int(cfg["vocab_size"]), layers=len(kept),
        mixers=tuple(cfg["layer_types"][i] for i in kept),
        ffns=tuple(cfg["mlp_layer_types"][i] for i in kept),
        eps=float(cfg["rms_norm_eps"]),
        rank=int(cfg["lora"]["rank"]), alpha=float(cfg["lora"]["alpha"]))


def kind(cfg: dict, layer: int) -> tuple:
    """(mixer, FFN) of kept layer ``layer``."""
    s = sizes(cfg)
    return s["mixers"][layer], s["ffns"][layer]


def segments(cfg: dict) -> list:
    """Runs of alike consecutive layers: [(kind, [layer, ...]), ...]."""
    out = []
    for layer in range(sizes(cfg)["layers"]):
        k = kind(cfg, layer)
        if out and out[-1][0] == k:
            out[-1][1].append(layer)
        else:
            out.append((k, [layer]))
    return out


def tensors_of(layer_kind: tuple) -> tuple:
    mixer, ffn = layer_kind
    return (BASE_TENSORS[:12]
            + (KDA_TENSORS if mixer == "linear_attention" else MLA_TENSORS)
            + (DENSE_TENSORS if ffn == "dense" else MOE_TENSORS))


def lora_tensors(layer_kind: tuple) -> tuple:
    return tuple(f"lora_{p}_{f}" for p in LORA_ON[layer_kind[0]]
                 for f in "ab")


def layer_shapes(cfg: dict) -> dict:
    """name -> (shape of one layer's tensor, how it is drawn): a float is
    the std of a normal draw; "ones", "zeros", "eye" are named, and
    ("uniform", lo, hi) a uniform draw."""
    s = sizes(cfg)
    d, H, D, r, n = s["d"], s["heads"], s["hd"], s["gate_rank"], s["streams"]
    W = H * D
    Hm, qr, kvr, nope, vd = (s["mla_heads"], s["qr"], s["kvr"], s["nope"],
                             s["vd"])
    Hi, Di = s["index_heads"], s["index_dim"]
    f, m, sh, G, E = (s["ffn"], s["moe"], s["shared"] * s["moe"], s["count"],
                      s["experts"])
    out = {}
    for side in ("mix", "mlp"):
        out.update({
            f"{side}_norm": ((d,), "ones"),
            f"{side}_phi": ((n * d, 2 * n + n * n), (n * d) ** -0.5),
            f"{side}_alpha": ((3,), "ones"),
            f"{side}_b_pre": ((n,), "zeros"),
            f"{side}_b_post": ((n,), "zeros"),
            f"{side}_b_res": ((n, n), "eye")})
    out.update({
        "q_proj": ((d, W), d ** -0.5), "k_proj": ((d, W), d ** -0.5),
        "v_proj": ((d, W), d ** -0.5),
        "q_conv": ((s["conv"], W), s["conv"] ** -0.5),
        "k_conv": ((s["conv"], W), s["conv"] ** -0.5),
        "v_conv": ((s["conv"], W), s["conv"] ** -0.5),
        "f_a": ((d, r), d ** -0.5), "f_b": ((r, W), r ** -0.5),
        "A_log": ((H,), "zeros"), "dt_bias": ((W,), ("uniform", -6.0, -2.0)),
        "b_proj": ((d, H), d ** -0.5),
        "g_a": ((d, r), d ** -0.5), "g_b": ((r, W), r ** -0.5),
        "o_norm": ((D,), "ones"), "o_proj": ((W, d), W ** -0.5),
        "q_a": ((d, qr), d ** -0.5), "q_a_norm": ((qr,), "ones"),
        "q_b": ((qr, Hm * nope), qr ** -0.5),
        "kv_a": ((d, kvr), d ** -0.5), "kv_a_norm": ((kvr,), "ones"),
        "kv_b": ((kvr, Hm * (nope + vd)), kvr ** -0.5),
        "o": ((Hm * vd, d), (Hm * vd) ** -0.5),
        "idx_wq": ((qr, Hi * Di), qr ** -0.5), "idx_wk": ((d, Di), d ** -0.5),
        "idx_k_norm": ((Di,), "ones"), "idx_k_bias": ((Di,), "zeros"),
        "idx_w": ((d, Hi), d ** -0.5),
        "gate": ((d, f), d ** -0.5), "up": ((d, f), d ** -0.5),
        "down": ((f, d), f ** -0.5),
        "router": ((d, E), d ** -0.5), "router_bias": ((E,), 0.01),
        "experts_gate": ((G, d, m), d ** -0.5),
        "experts_up": ((G, d, m), d ** -0.5),
        "experts_down": ((G, m, d), m ** -0.5),
        "shared_gate": ((d, sh), d ** -0.5), "shared_up": ((d, sh), d ** -0.5),
        "shared_down": ((sh, d), max(sh, 1) ** -0.5)})
    for name in ("q_proj", "v_proj", "q_a", "q_b", "kv_a", "kv_b"):
        (a, b), _ = out[name]
        out[f"lora_{name}_a"] = ((a, s["rank"]), 0.02)
        out[f"lora_{name}_b"] = ((s["rank"], b), 0.02)
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
    if how in ("ones", "zeros"):
        return getattr(jnp, how)(shape, jnp.float32)
    if how == "eye":
        return jnp.eye(shape[0], dtype=jnp.float32)
    k = jax.random.fold_in(jax.random.fold_in(key, tensor_index), layer + 1)
    if isinstance(how, (tuple, list)):
        _, lo, hi = how
        return jax.random.uniform(k, shape, jnp.float32, lo, hi)
    return (jax.random.normal(k, shape, jnp.float32) * how).astype(
        _dtype(cfg, name))


def draw_layer(cfg: dict, key, layer, layer_kind=None) -> dict:
    """The frozen tensors of kept layer ``layer``; ``layer_kind`` says
    which kind it is where ``layer`` is traced."""
    shapes = layer_shapes(cfg)
    if layer_kind is None:
        layer_kind = kind(cfg, int(layer))
    return {name: draw(cfg, key, name, BASE_TENSORS.index(name), layer,
                       *shapes[name])
            for name in tensors_of(layer_kind)}


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
                    * shapes[name][1])
             for name in lora_tensors(kind(cfg, layer))}
            for layer in range(sizes(cfg)["layers"])]


def base_device(cfg: dict, seed: int) -> dict:
    """The frozen base on the device: ``segments``, each a run of alike
    layers with its tensors stacked on a leading axis (a layer a jitted
    call, then stacked a tensor at a time: never two whole trees), and the
    top."""
    import functools

    import jax
    import jax.numpy as jnp
    key = seed_key(seed)
    one = jax.jit(functools.partial(draw_layer, cfg),
                  static_argnames="layer_kind")
    out = {"segments": [],
           **jax.jit(functools.partial(draw_top, cfg))(key)}
    for layer_kind, layers in segments(cfg):
        drawn = [one(key, l, layer_kind=layer_kind) for l in layers]
        out["segments"].append({name: jnp.stack([w.pop(name) for w in drawn])
                                for name in tensors_of(layer_kind)})
    return out


def layer_of(base: dict, cfg: dict, layer: int) -> dict:
    """Kept layer ``layer``'s frozen tensors out of ``base_device``'s
    tree."""
    for (_, layers), seg in zip(segments(cfg), base["segments"]):
        if layer in layers:
            return {k: v[layers.index(layer)] for k, v in seg.items()}
    raise IndexError(layer)


# --------------------------------------------------------------------- #
# arithmetic
# --------------------------------------------------------------------- #

def _rms(x, scale, eps):
    import jax.numpy as jnp
    return x * jnp.reciprocal(jnp.sqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * scale


def _layer_norm(x, scale, bias, eps):
    import jax.numpy as jnp
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jnp.reciprocal(jnp.sqrt(var + eps)) * scale + bias


def _adapted(ein, h, w, name, scale):
    out = ein("btd,de->bte", h, w[name])
    if f"lora_{name}_a" in w:
        out = out + scale * ein("btr,re->bte", ein(
            "btd,dr->btr", h, w[f"lora_{name}_a"]), w[f"lora_{name}_b"])
    return out


def sinkhorn(m, iters: int, eps: float):
    import jax.numpy as jnp
    for _ in range(iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
    return m


def hyper(x, w, side: str, cfg: dict, sublayer):
    """mHC around ``sublayer`` (a function of the normed (B, T, d) input)
    on the streams ``x`` (B, T, n, d). Returns the new streams and what
    the sublayer returned beside its output."""
    import jax
    import jax.numpy as jnp
    s = sizes(cfg)
    B, T, n, d = x.shape
    u = x.reshape(B, T, n * d)
    u = u * jnp.reciprocal(jnp.sqrt(jnp.mean(u * u, -1, keepdims=True)
                                    + s["eps"]))
    z = make_ein("")("btk,kc->btc", u, w[f"{side}_phi"])
    a = w[f"{side}_alpha"]
    pre = jax.nn.sigmoid(a[0] * z[..., :n] + w[f"{side}_b_pre"])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[..., n:2 * n] + w[f"{side}_b_post"])
    res = sinkhorn(jnp.exp(a[2] * z[..., 2 * n:].reshape(B, T, n, n)
                           + w[f"{side}_b_res"]), s["iters"], s["hc_eps"])
    h = _rms(jnp.einsum("btn,btnd->btd", pre, x), w[f"{side}_norm"],
             s["eps"])
    y, extra = sublayer(h)
    return (jnp.einsum("btij,btjd->btid", res, x)
            + post[..., None] * y[:, :, None]), extra


def _conv(x, w):
    """Causal depthwise convolution of ``x`` (B, T, C) by taps ``w`` (K,
    C): out_t = sum_i w_i x_{t - K + 1 + i}."""
    import jax.numpy as jnp
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, i:i + T] * w[i] for i in range(K))


def recurrence(q, k, v, g, beta, scale: float):
    """KDA token by token: ``q``, ``k``, ``g`` (B, T, H, Dk), ``v`` (B, T,
    H, Dv), ``beta`` (B, T, H) -> o (B, T, H, Dv); the state (B, H, Dk,
    Dv) from zero, checkpointed every ``CHECKPOINT`` tokens."""
    import jax
    import jax.numpy as jnp
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    pad = -T % CHECKPOINT
    xs = [jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
          for t in (q, k, v, g, beta)]
    n = (T + pad) // CHECKPOINT
    xs = [jnp.moveaxis(t.reshape((B, n, CHECKPOINT) + t.shape[2:]), (1, 2),
                       (0, 1)) for t in xs]           # (n, C, B, H, .)
    hi = jax.lax.Precision.HIGHEST

    def token(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[..., None]
        kts = jnp.einsum("bhd,bhdv->bhv", kt, s, precision=hi)
        s = s + (bt[..., None, None] * kt[..., None]
                 * (vt - kts)[..., None, :])
        return s, scale * jnp.einsum("bhd,bhdv->bhv", qt, s, precision=hi)

    @jax.checkpoint
    def block(s, x):
        return jax.lax.scan(token, s, x)

    _, o = jax.lax.scan(block, jnp.zeros((B, H, Dk, Dv), jnp.float32), xs)
    return jnp.moveaxis(o.reshape((n * CHECKPOINT, B, H, Dv)), 0, 1)[:, :T]


def kda(h, w, cfg: dict, quant: str = ""):
    """KDA on normed input ``h`` (B, T, d) -> (B, T, d); ``w`` one layer's
    tensors, float32."""
    import jax
    import jax.numpy as jnp
    s, ein = sizes(cfg), make_ein(quant)
    scale = s["alpha"] / s["rank"]
    B, T, _ = h.shape
    H, D = s["heads"], s["hd"]

    def heads(name, normed):
        x = jax.nn.silu(_conv(_adapted(ein, h, w, name, scale),
                              w[name.replace("proj", "conv")]))
        x = x.reshape(B, T, H, D)
        if normed:
            x = x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
        return x

    q, k, v = heads("q_proj", True), heads("k_proj", True), heads(
        "v_proj", False)
    f = ein("btr,re->bte", ein("btd,dr->btr", h, w["f_a"]), w["f_b"])
    f = (f + w["dt_bias"]).reshape(B, T, H, D) * jnp.exp(
        w["A_log"])[:, None]
    g = s["lower"] * jax.nn.sigmoid(f)
    beta = jax.nn.sigmoid(ein("btd,dh->bth", h, w["b_proj"]))
    o = _rms(recurrence(q, k, v, g, beta, D ** -0.5), w["o_norm"], s["eps"])
    gate = ein("btr,re->bte", ein("btd,dr->btr", h, w["g_a"]), w["g_b"])
    o = o * jax.nn.sigmoid(gate).reshape(B, T, H, D)
    return ein("bte,ed->btd", o.reshape(B, T, H * D), w["o_proj"])


def indexer(h, c_q, w, cfg: dict, quant: str = ""):
    """(q^I (B, T, Hi, Di), kbar (B, T / P, Di), w (B, T, Hi)) of the
    normed input ``h`` and the query latent ``c_q``; no gradient."""
    import jax
    import jax.numpy as jnp
    s, ein = sizes(cfg), make_ein(quant)
    h, c_q = jax.lax.stop_gradient(h), jax.lax.stop_gradient(c_q)
    B, T, _ = h.shape
    Hi, Di, P = s["index_heads"], s["index_dim"], s["pool"]
    qi = ein("btd,de->bte", c_q, w["idx_wq"]).reshape(B, T, Hi, Di)
    ki = _layer_norm(ein("btd,de->bte", h, w["idx_wk"]), w["idx_k_norm"],
                     w["idx_k_bias"], s["eps"])
    kbar = jnp.mean(ki.reshape(B, T // P, P, Di), axis=2)
    wt = ein("btd,dh->bth", h, w["idx_w"]) * (Hi ** -0.5 * Di ** -0.5)
    return (jax.lax.stop_gradient(qi), jax.lax.stop_gradient(kbar),
            jax.lax.stop_gradient(wt))


def choose(qi, kbar, wt, start: int, T: int, cfg: dict, quant: str = ""):
    """The keys queries ``start ..`` (``qi``, ``wt`` a block of them)
    attend to: (B, block, T) bool."""
    import jax
    import jax.numpy as jnp
    s, ein = sizes(cfg), make_ein(quant)
    P = s["pool"]
    groups = kbar.shape[1]
    prod = ein("bqjd,bgd->bqjg", qi, kbar)
    score = jnp.sum(jax.nn.relu(prod) * wt[..., None], axis=2)
    rows = start + jnp.arange(qi.shape[1])
    complete = P * jnp.arange(groups)[None, :] + P - 1 <= rows[:, None]
    score = jnp.where(complete, score, -jnp.inf)
    kth = jax.lax.top_k(score, min(s["index_top_k"] // P, groups))[0][
        ..., -1:]
    keys = jnp.arange(T)[None, :]
    own = ((keys // P == rows[:, None] // P) & (keys <= rows[:, None])
           & (rows[:, None] % P != P - 1))
    return jnp.repeat((score >= kth) & complete, P, axis=-1) | own[None]


def mla(h, w, cfg: dict, quant: str = ""):
    """Sparse latent attention of normed input ``h`` (B, T, d) -> ((B, T,
    d), chosen pairs); ``w`` one layer's tensors, float32."""
    import jax
    import jax.numpy as jnp
    s, ein = sizes(cfg), make_ein(quant)
    scale = s["alpha"] / s["rank"]
    B, T, _ = h.shape
    H, nope, vd = s["mla_heads"], s["nope"], s["vd"]
    c_q = _rms(_adapted(ein, h, w, "q_a", scale), w["q_a_norm"], s["eps"])
    q = _adapted(ein, c_q, w, "q_b", scale).reshape(B, T, H, nope)
    c_kv = _rms(_adapted(ein, h, w, "kv_a", scale), w["kv_a_norm"],
                s["eps"])
    kv = _adapted(ein, c_kv, w, "kv_b", scale).reshape(B, T, H, nope + vd)
    k, v = kv[..., :nope], kv[..., nope:]
    qi, kbar, wt = indexer(h, c_q, w, cfg, quant)
    qb = min(QUERY_BLOCK, T)
    cut = lambda t: jnp.moveaxis(                           # noqa: E731
        t.reshape((B, T // qb, qb) + t.shape[2:]), 1, 0)
    sm = s["qk"] ** -0.5

    @jax.checkpoint
    def block(args):
        start, qq, qii, wti = args
        chosen = choose(qii, kbar, wti, start, T, cfg, quant)   # (B, qb, T)
        sc = ein("bqhd,bshd->bhqs", qq, k) * sm
        p = jax.nn.softmax(jnp.where(chosen[:, None], sc, -1e30), -1)
        return ein("bhqs,bshd->bqhd", p, v), jnp.sum(chosen)

    o, chosen = jax.lax.map(block, (jnp.arange(0, T, qb), cut(q), cut(qi),
                                    cut(wt)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, H * vd)
    return ein("bte,ed->btd", o, w["o"]), jnp.sum(chosen)


def _swiglu(ein, h, gate, up, down, limit: float):
    import jax
    import jax.numpy as jnp
    a, b = ein("td,df->tf", h, gate), ein("td,df->tf", h, up)
    if limit:
        a, b = jnp.minimum(a, limit), jnp.clip(b, -limit, limit)
    return ein("tf,fd->td", jax.nn.silu(a) * b, down)


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
            ein, h, wide(gate), wide(up), wide(down), s["limit"]), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(count), w["experts_gate"], w["experts_up"],
        w["experts_down"]))
    local = jnp.sum((chosen >= first) & (chosen < first + count))
    if s["shared"]:
        out = out + _swiglu(ein, h, w["shared_gate"], w["shared_up"],
                            w["shared_down"], s["limit"])
    return out, local


def layer(x, w, cfg: dict, layer_kind: tuple, quant: str = ""):
    """One layer on the streams ``x`` (B, T, n, d); frozen tensors of
    ``w`` may arrive in bfloat16 and are widened here, the experts one at
    a time. Returns the streams, the layer's held assignments and its
    chosen pairs."""
    import jax.numpy as jnp
    s, ein = sizes(cfg), make_ein(quant)
    mixer, ffn = layer_kind
    w = {k: v if k.startswith("experts_") else v.astype(jnp.float32)
         for k, v in w.items()}
    zero = jnp.zeros((), jnp.int32)
    if mixer == "linear_attention":
        x, chosen = hyper(x, w, "mix", cfg,
                          lambda h: (kda(h, w, cfg, quant), zero))
    else:
        x, chosen = hyper(x, w, "mix", cfg, lambda h: mla(h, w, cfg, quant))

    def feed(h):
        B, T, d = h.shape
        u = h.reshape(B * T, d)
        if ffn == "dense":
            y, local = _swiglu(ein, u, w["gate"], w["up"], w["down"],
                               s["limit"]), zero
        else:
            y, local = expert_layer(u, w, cfg, quant)
        return y.reshape(B, T, d), local

    x, local = hyper(x, w, "mlp", cfg, feed)
    return x, (jnp.asarray(local, jnp.int32), jnp.asarray(chosen, jnp.int32))


def hidden(base: dict, lora: list, tokens, cfg: dict, quant: str = ""):
    """(final-norm hidden states (B, T, d), (held assignments, chosen
    pairs) summed over the layers) of ``tokens`` (B, T)."""
    import jax
    import jax.numpy as jnp
    s = sizes(cfg)
    x = base["embed"][tokens].astype(jnp.float32)
    B, T, d = x.shape
    x = jnp.broadcast_to(x[:, :, None], (B, T, s["streams"], d))
    local = chosen = jnp.zeros((), jnp.int32)
    for (layer_kind, layers), seg in zip(segments(cfg), base["segments"]):
        stacked = {**seg, **jax.tree.map(
            lambda *a: jnp.stack(a), *[lora[l] for l in layers])}

        @jax.checkpoint
        def one(x, w, layer_kind=layer_kind):
            return layer(x, w, cfg, layer_kind, quant)

        x, (n_local, n_chosen) = jax.lax.scan(one, x, stacked)
        local, chosen = local + jnp.sum(n_local), chosen + jnp.sum(n_chosen)
    return (_rms(jnp.sum(x, axis=2), base["final_norm"], s["eps"]),
            (local, chosen))


def logits(base, lora, tokens, cfg, quant: str = ""):
    h, _ = hidden(base, lora, tokens, cfg, quant)
    return make_ein(quant)("btd,dv->btv", h, base["lm_head"])


def counts(base, lora, tokens, cfg):
    """(assignments on held experts, chosen pairs), summed over the
    layers: what the program's ``moe_local_count`` and
    ``dsa_selected_count`` count."""
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
