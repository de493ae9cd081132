"""Plain reference of the decoder with learned sparse attention and routed
experts (``dsa_moe`` family: Keye-VL-2.0's language model, the Qwen3-MoE
layer under DeepSeek-V3.2's lightning indexer) as one chip of a deployment
holds it: ``experts_held.count`` of the router's ``published.num_experts``
experts, a slice of the vocabulary, untied head, LoRA on ``wq`` and ``wv``.
As the configuration file states it. float32, ``highest`` matmul
precision, no kernels, no cache, no batching. Imports nothing of the
program and nothing of the other references.

One layer, with ``x`` the residual stream (every norm an RMSNorm with its
own scale, epsilon ``rms_norm_eps``; no bias anywhere)::

    h = norm_attn(x)
    q = h W_q   (heads x head_dim),  k = h W_k,  v = h W_v  (kv heads)
    q, k = RMSNorm per head (q_norm, k_norm), then M-RoPE
    x = x + attend(q, k, v over the keys the indexer chooses) W_o
    x = x + MoE(norm_mlp(x))

M-RoPE turns frequency pair ``i`` (of ``head_dim / 2``, ``f_i =
rope_theta^(-2 i / head_dim)``, half-split rotation) with position stream
``s(i)`` of ``rope_scaling.mrope_section``; text positions make the three
streams equal. The indexer (``sa_config``), on ``h`` and with no
gradient::

    q^I_j = rot(h W^QI_j)          j = 1 .. indexer_num_heads
    k^I   = rot(LayerNorm(h W^KI)) one head (indexer_num_kv_heads 1)
    w_j   = (h W^w)_j * heads^-0.5 * head_dim^-0.5
    I[t, s] = sum_j w_j relu(q^I_j . k^I_s),  s <= t

and query ``t`` attends, with softmax(q k / head_dim^0.5), to the keys of
the ``topk`` largest ``I[t, s]`` (``jax.lax.top_k``; every causal key while
``t < topk``; ties at the ``topk``-th value all taken). One choice serves
every head. ``MoE(u)``::

    p = softmax(u W_r)             float32, over all num_experts columns
    chosen = top_k(p, num_experts_per_tok)
    g_e = p_e / sum_chosen p       norm_topk_prob
    m = sum_{e chosen, e held} g_e W_down_e(silu(W_gate_e u) * W_up_e u)

What the experts held on the deployment's other chips would add is left
out, here as in the program; the held experts are a loop on dense masks.

Attention and the indexer run in blocks of ``QUERY_BLOCK`` queries (at
16,384 tokens a float32 (T, T) is 1 GB a head). The layers are scanned
over tensors stacked on a layer axis (1.6 GB of bfloat16 base), a layer
widened to float32 inside ``jax.checkpoint``.

The frozen matrices are bfloat16 VALUES (drawn in float32, rounded once,
as the configuration's ``param_dtype`` says): both sides hold the same
numbers. ``quant="fp8"`` is the control: every matrix product the program
runs in bfloat16, the indexer's among them, takes both operands rounded to
4 significant bits (e4m3) under a per-tensor scale; the router's product,
float32 in the program, stays float32.
"""

from __future__ import annotations

import numpy as np

from benchmark.lib.precision import make_ein

# order fixes each tensor's key: never reorder, only append
BASE_TENSORS = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
                "idx_wq", "idx_wk", "idx_k_norm", "idx_k_bias", "idx_w",
                "mlp_norm", "router", "experts_gate", "experts_up",
                "experts_down")
LORA_TENSORS = ("lora_q_a", "lora_q_b", "lora_v_a", "lora_v_b")
TOP_TENSORS = ("embed", "final_norm", "lm_head")
# what stays float32 whatever ``param_dtype`` says (its ``float32`` list)
FLOAT32 = ("attn_norm", "q_norm", "k_norm", "idx_k_norm", "idx_k_bias",
           "mlp_norm", "router", "final_norm", "lm_head")
QUERY_BLOCK = 256


def sizes(cfg: dict) -> dict:
    held, sa = cfg["experts_held"], cfg["sa_config"]
    if int(sa["indexer_num_kv_heads"]) != 1:
        raise ValueError("the indexer's keys are one head shared by all")
    return dict(
        d=int(cfg["hidden_size"]), heads=int(cfg["num_attention_heads"]),
        kv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        moe=int(cfg["moe_intermediate_size"]),
        experts=int(cfg["published"]["num_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        first=int(held["first"]), count=int(held["count"]),
        index_heads=int(sa["indexer_num_heads"]),
        index_dim=int(sa["indexer_head_dim"]), index_top_k=int(sa["topk"]),
        sections=tuple(int(n) for n in
                       cfg["rope_scaling"]["mrope_section"]),
        vocab=int(cfg["vocab_size"]), layers=int(cfg["num_hidden_layers"]),
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        rank=int(cfg["lora"]["rank"]), alpha=float(cfg["lora"]["alpha"]))


def layer_shapes(cfg: dict) -> dict:
    """name -> (shape of one layer's tensor, how it is drawn): a float is
    the std of a normal draw; "ones" and "zeros" are named."""
    s = sizes(cfg)
    d, H, kv, hd, m = s["d"], s["heads"], s["kv"], s["hd"], s["moe"]
    G, r, Hi, Di = s["count"], s["rank"], s["index_heads"], s["index_dim"]
    return {
        "attn_norm": ((d,), "ones"), "mlp_norm": ((d,), "ones"),
        "wq": ((d, H * hd), d ** -0.5), "wk": ((d, kv * hd), d ** -0.5),
        "wv": ((d, kv * hd), d ** -0.5),
        "wo": ((H * hd, d), (H * hd) ** -0.5),
        "q_norm": ((hd,), "ones"), "k_norm": ((hd,), "ones"),
        "idx_wq": ((d, Hi * Di), d ** -0.5), "idx_wk": ((d, Di), d ** -0.5),
        "idx_k_norm": ((Di,), "ones"), "idx_k_bias": ((Di,), "zeros"),
        "idx_w": ((d, Hi), d ** -0.5),
        "router": ((d, s["experts"]), d ** -0.5),
        "experts_gate": ((G, d, m), d ** -0.5),
        "experts_up": ((G, d, m), d ** -0.5),
        "experts_down": ((G, m, d), m ** -0.5),
        "lora_q_a": ((d, r), 0.02), "lora_q_b": ((r, H * hd), 0.02),
        "lora_v_a": ((d, r), 0.02), "lora_v_b": ((r, kv * hd), 0.02),
    }


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
    """The frozen base on the device: ``layers`` the layers' tensors
    stacked on a leading axis (a per-layer draw gives the same numbers),
    and the top."""
    import functools

    import jax
    import jax.numpy as jnp
    key = seed_key(seed)
    layers = jax.jit(jax.vmap(functools.partial(draw_layer, cfg),
                              in_axes=(None, 0)))(
        key, jnp.arange(sizes(cfg)["layers"]))
    return {"layers": layers,
            **jax.jit(functools.partial(draw_top, cfg))(key)}


# --------------------------------------------------------------------- #
# arithmetic
# --------------------------------------------------------------------- #

def rotary_frequencies(width: int, theta: float) -> np.ndarray:
    i = np.arange(width // 2, dtype=np.float64)
    return (theta ** (-2.0 * i / width)).astype(np.float32)


def _rot(x, theta: float, sections=()):
    """Half-split rotation of the last axis of ``x`` (B, T, ., width) at
    positions 0 .. T-1; with ``sections``, M-RoPE over three equal streams
    of text positions."""
    import jax.numpy as jnp
    width, T = x.shape[-1], x.shape[1]
    f = jnp.asarray(rotary_frequencies(width, theta))
    pos = jnp.arange(T, dtype=jnp.float32)
    if sections:
        streams = jnp.stack([pos] * len(sections))           # (3, T)
        which = np.repeat(np.arange(len(sections)), sections)
        ang = streams[which].T * f                           # (T, width/2)
    else:
        ang = pos[:, None] * f
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :width // 2], x[..., width // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _rms(x, scale, eps):
    import jax.numpy as jnp
    return x * jnp.reciprocal(jnp.sqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * scale


def _layer_norm(x, scale, bias, eps):
    import jax.numpy as jnp
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jnp.reciprocal(jnp.sqrt(var + eps)) * scale + bias


def _adapted(ein, h, w, name, tag, scale):
    return ein("btd,de->bte", h, w[name]) + scale * ein(
        "btr,re->bte", ein("btd,dr->btr", h, w[f"lora_{tag}_a"]),
        w[f"lora_{tag}_b"])


def indexer(h, w, cfg: dict, quant: str = ""):
    """(q^I (B, T, Hi, Di), k^I (B, T, Di), w (B, T, Hi)) of normed input
    ``h``; no gradient."""
    import jax
    s, ein = sizes(cfg), make_ein(quant)
    h = jax.lax.stop_gradient(h)
    B, T, _ = h.shape
    Hi, Di = s["index_heads"], s["index_dim"]
    qi = _rot(ein("btd,de->bte", h, w["idx_wq"]).reshape(B, T, Hi, Di),
              s["theta"])
    ki = _layer_norm(ein("btd,de->bte", h, w["idx_wk"]), w["idx_k_norm"],
                     w["idx_k_bias"], s["eps"])
    ki = _rot(ki[:, :, None], s["theta"])[:, :, 0]
    wt = ein("btd,dh->bth", h, w["idx_w"]) * (Hi ** -0.5 * Di ** -0.5)
    return (jax.lax.stop_gradient(qi), jax.lax.stop_gradient(ki),
            jax.lax.stop_gradient(wt))


def choose(qi, ki, wt, start: int, cfg: dict, quant: str = ""):
    """The keys queries ``start ..`` (``qi``, ``wt`` a block of them)
    attend to: (B, block, T) bool, by ``jax.lax.top_k`` of their indexer
    scores."""
    import jax
    import jax.numpy as jnp
    s, ein = sizes(cfg), make_ein(quant)
    T = ki.shape[1]
    prod = ein("bqjd,bsd->bqjs", qi, ki)
    score = jnp.sum(jax.nn.relu(prod) * wt[..., None], axis=2)
    rows = start + jnp.arange(qi.shape[1])
    causal = jnp.arange(T)[None, :] <= rows[:, None]
    score = jnp.where(causal, score, -jnp.inf)
    kth = jax.lax.top_k(score, min(s["index_top_k"], T))[0][..., -1:]
    return (score >= kth) & causal


def attention(h, w, cfg: dict, quant: str = ""):
    """Attention of normed input ``h`` (B, T, d) over the indexer's choice
    -> ((B, T, d), chosen pairs); ``w`` one layer's tensors, float32."""
    import jax
    import jax.numpy as jnp
    s, ein = sizes(cfg), make_ein(quant)
    scale = s["alpha"] / s["rank"]
    B, T, _ = h.shape
    H, kv, hd = s["heads"], s["kv"], s["hd"]
    q = _adapted(ein, h, w, "wq", "q", scale).reshape(B, T, H, hd)
    k = ein("btd,de->bte", h, w["wk"]).reshape(B, T, kv, hd)
    v = _adapted(ein, h, w, "wv", "v", scale).reshape(B, T, kv, hd)
    q = _rot(_rms(q, w["q_norm"], s["eps"]), s["theta"], s["sections"])
    k = _rot(_rms(k, w["k_norm"], s["eps"]), s["theta"], s["sections"])
    qi, ki, wt = indexer(h, w, cfg, quant)
    qb = min(QUERY_BLOCK, T)
    cut = lambda t: jnp.moveaxis(                           # noqa: E731
        t.reshape((B, T // qb, qb) + t.shape[2:]), 1, 0)

    @jax.checkpoint
    def block(args):
        start, qq, qii, wti = args
        chosen = choose(qii, ki, wti, start, cfg, quant)    # (B, qb, T)
        qg = qq.reshape(B, qb, kv, H // kv, hd)
        sc = ein("bqkgd,bskd->bkgqs", qg, k) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(chosen[:, None, None], sc, -1e30), -1)
        o = ein("bkgqs,bskd->bqkgd", p, v).reshape(B, qb, H * hd)
        return o, jnp.sum(chosen)

    starts = jnp.arange(0, T, qb)
    o, chosen = jax.lax.map(block, (starts, cut(q), cut(qi), cut(wt)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, H * hd)
    return ein("bte,ed->btd", o, w["wo"]), jnp.sum(chosen)


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
    p = jax.nn.softmax(make_ein("")("td,de->te", h, w["router"]), axis=-1)
    vals, chosen = jax.lax.top_k(p, s["top_k"])
    return chosen, vals / jnp.sum(vals, -1, keepdims=True)


def routed_layer(h, w, cfg: dict, quant: str = "", held=None):
    """The held experts' part of the routed layer on normed tokens ``h``
    (T, d); ``held`` = (first, count) overrides the configuration's share
    (the tests add the shares up). Returns the output and how many
    assignments fell on held experts."""
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
    return out, local


def layer(x, w, cfg: dict, quant: str = ""):
    """One layer on ``x`` (B, T, d); the frozen tensors of ``w`` may
    arrive in bfloat16. Returns the output, the layer's held assignments
    and its chosen pairs."""
    import jax.numpy as jnp
    s = sizes(cfg)
    B, T, d = x.shape
    experts = {n: w.pop(n) for n in ("experts_gate", "experts_up",
                                     "experts_down")}
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    a, chosen = attention(_rms(x, w["attn_norm"], s["eps"]), w, cfg, quant)
    x = x + a
    u = _rms(x, w["mlp_norm"], s["eps"]).reshape(B * T, d)
    m, local = routed_layer(u, {**w, **experts}, cfg, quant)
    return x + m.reshape(B, T, d), (local, chosen)


def hidden(base: dict, lora: list, tokens, cfg: dict, quant: str = ""):
    """(final-norm hidden states (B, T, d), (held assignments, chosen
    pairs) summed over the layers) of ``tokens`` (B, T)."""
    import jax
    import jax.numpy as jnp
    x = base["embed"][tokens].astype(jnp.float32)
    stacked = {**base["layers"],
               **{n: jnp.stack([jnp.asarray(a[n]) for a in lora])
                  for n in LORA_TENSORS}}

    @jax.checkpoint
    def one(x, w):
        x, counted = layer(x, dict(w), cfg, quant)
        return x, counted

    x, (local, chosen) = jax.lax.scan(one, x, stacked)
    return (_rms(x, base["final_norm"], sizes(cfg)["eps"]),
            (jnp.sum(local), jnp.sum(chosen)))


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
