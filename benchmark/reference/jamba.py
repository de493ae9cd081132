"""Plain reference of the Jamba family's dense case (``num_experts`` 1): a
decoder whose block ``l`` mixes by causal multi-query attention where
``(l - attn_layer_offset) % attn_layer_period == 0`` and by a Mamba-1 mixer
elsewhere, each followed by a SwiGLU MLP, pre-RMSNorm, no rotary and no
position embedding, head tied to the embedding; LoRA on ``in_proj`` and
``out_proj`` (Mamba) and on ``wq`` and ``wv`` (attention). As the
configuration file states it. float32, ``highest`` matmul precision, no
kernels, no cache, no batching. Imports nothing of the program.

The recurrence is a step-by-step ``lax.scan`` over time::

    S_t = exp(delta_t (x) A) . S_{t-1} + (delta_t . x_t) (x) B_t
    y_t = S_t . C_t + D . x_t

one position after another, no closed form over a chunk. So that its
backward pass fits the chip beside 6.4 GB of weights, the scan is written
as blocks of ``TIME_BLOCK`` positions under ``jax.checkpoint`` (a memory
policy: the backward recomputes a block's steps from the state at its
start instead of keeping every step's (d_inner, N) state, 1.34 GB a layer
at 4096 positions), and each layer is under ``jax.checkpoint`` too.

Weights come from ``--seed`` alone: the frozen base is drawn on the device
(``jax.random``, one key per tensor and layer), the adapters by numpy on
the host. ``A_log`` and ``dt_proj``'s bias are seeded as the family seeds
them (A = 1..N in every channel; softplus(bias) log-uniform in [1e-3,
1e-1]) so that the recurrence neither dies nor saturates under random
weights.

``quant="fp8"`` is the control: every matrix product takes both operands
rounded to 4 significant bits (e4m3) under a per-tensor scale — the nearest
precision below the bfloat16 the configuration states. The recurrence
itself is elementwise and stays float32 in the control, as it does in the
program.
"""

from __future__ import annotations

import numpy as np

from benchmark.lib.precision import make_ein

# order fixes each tensor's key: never reorder, only append
BASE_TENSORS = ("mix_norm", "mlp_norm", "gate", "up", "down",
                "wq", "wk", "wv", "wo",
                "in_proj", "conv_w", "conv_b", "x_proj", "dt_norm", "b_norm",
                "c_norm", "dt_proj", "dt_bias", "A_log", "D", "out_proj")
MLP_TENSORS = ("mix_norm", "mlp_norm", "gate", "up", "down")
ATTN_TENSORS = MLP_TENSORS + ("wq", "wk", "wv", "wo")
MAMBA_TENSORS = MLP_TENSORS + BASE_TENSORS[9:]
ATTN_LORA = ("lora_q_a", "lora_q_b", "lora_v_a", "lora_v_b")
MAMBA_LORA = ("lora_in_a", "lora_in_b", "lora_out_a", "lora_out_b")
TOP_TENSORS = ("embed", "final_norm")
TIME_BLOCK = 64
DT_MIN, DT_MAX = 1e-3, 1e-1


def sizes(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    return dict(d=d, heads=heads, kv=int(cfg["num_key_value_heads"]),
                hd=d // heads, ffn=int(cfg["intermediate_size"]),
                vocab=int(cfg["vocab_size"]),
                layers=int(cfg["num_hidden_layers"]),
                period=int(cfg["attn_layer_period"]),
                offset=int(cfg["attn_layer_offset"]),
                di=int(cfg["mamba_expand"]) * d,
                n=int(cfg["mamba_d_state"]), k=int(cfg["mamba_d_conv"]),
                r=int(cfg["mamba_dt_rank"]),
                eps=float(cfg["rms_norm_eps"]),
                rank=int(cfg["lora"]["rank"]),
                alpha=float(cfg["lora"]["alpha"]))


def is_attention(cfg: dict, layer: int) -> bool:
    s = sizes(cfg)
    return (layer - s["offset"]) % s["period"] == 0


def layer_shapes(cfg: dict) -> dict:
    """name -> (shape of one layer's tensor, how it is drawn): a float is
    the std of a normal draw; "ones", "a_log" and "dt_bias" are named."""
    s = sizes(cfg)
    d, kvd, f, di = s["d"], s["kv"] * s["hd"], s["ffn"], s["di"]
    n, k, r, rank = s["n"], s["k"], s["r"], s["rank"]
    return {
        "mix_norm": ((d,), "ones"), "mlp_norm": ((d,), "ones"),
        "gate": ((d, f), d ** -0.5), "up": ((d, f), d ** -0.5),
        "down": ((f, d), f ** -0.5),
        "wq": ((d, d), d ** -0.5), "wk": ((d, kvd), d ** -0.5),
        "wv": ((d, kvd), d ** -0.5), "wo": ((d, d), d ** -0.5),
        "in_proj": ((d, 2 * di), d ** -0.5),
        "conv_w": ((k, di), k ** -0.5), "conv_b": ((di,), 0.1),
        "x_proj": ((di, r + 2 * n), di ** -0.5),
        "dt_norm": ((r,), "ones"), "b_norm": ((n,), "ones"),
        "c_norm": ((n,), "ones"),
        "dt_proj": ((r, di), r ** -0.5), "dt_bias": ((di,), "dt_bias"),
        "A_log": ((di, n), "a_log"), "D": ((di,), "ones"),
        "out_proj": ((di, d), di ** -0.5),
        "lora_q_a": ((d, rank), 0.02), "lora_q_b": ((rank, d), 0.02),
        "lora_v_a": ((d, rank), 0.02), "lora_v_b": ((rank, kvd), 0.02),
        "lora_in_a": ((d, rank), 0.02), "lora_in_b": ((rank, 2 * di), 0.02),
        "lora_out_a": ((di, rank), 0.02), "lora_out_b": ((rank, d), 0.02),
    }


def top_shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    return {"embed": ((s["vocab"], s["d"]), 1.0),
            "final_norm": ((s["d"],), "ones")}


def seed_key(seed: int):
    """A key from any whole number up to past 2**31."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def draw(key, tensor_index: int, layer: int, shape, how):
    """One tensor of one layer, float32 (``layer`` -1 for the top)."""
    import jax
    import jax.numpy as jnp
    if how == "ones":
        return jnp.ones(shape, jnp.float32)
    if how == "a_log":
        return jnp.log(jnp.broadcast_to(
            jnp.arange(1, shape[1] + 1, dtype=jnp.float32), shape))
    k = jax.random.fold_in(jax.random.fold_in(key, tensor_index), layer + 1)
    if how == "dt_bias":
        # Python floats: a numpy scalar would widen the draw under x64
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32)
                     * float(np.log(DT_MAX / DT_MIN))
                     + float(np.log(DT_MIN)))
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus's inverse
    return jax.random.normal(k, shape, jnp.float32) * how


def layer_tensors(cfg: dict, layer: int) -> tuple:
    """(base tensor names, adapter names) of block ``layer``."""
    return ((ATTN_TENSORS, ATTN_LORA) if is_attention(cfg, layer)
            else (MAMBA_TENSORS, MAMBA_LORA))


def draw_layer(cfg: dict, key, layer: int) -> dict:
    shapes = layer_shapes(cfg)
    return {name: draw(key, BASE_TENSORS.index(name), layer, *shapes[name])
            for name in layer_tensors(cfg, layer)[0]}


def draw_top(cfg: dict, key) -> dict:
    return {name: draw(key, 100 + i, -1, *top_shapes(cfg)[name])
            for i, name in enumerate(TOP_TENSORS)}


def lora_host(cfg: dict, seed: int) -> list:
    """The adapters, one dict a layer, as host numpy float32, both factors
    non-zero (a zero ``b`` would leave the first step's ``a`` without a
    gradient)."""
    rng = np.random.default_rng([int(seed), 0x10A])
    shapes = layer_shapes(cfg)
    return [{name: (rng.standard_normal(shapes[name][0]).astype(np.float32)
                    * shapes[name][1])
             for name in layer_tensors(cfg, l)[1]}
            for l in range(sizes(cfg)["layers"])]


def base_device(cfg: dict, seed: int) -> dict:
    """The frozen base, made on the device in one jitted call."""
    import jax

    def make(key):
        return {"layers": [draw_layer(cfg, key, l)
                           for l in range(sizes(cfg)["layers"])],
                **draw_top(cfg, key)}

    return jax.jit(make)(seed_key(seed))


# --------------------------------------------------------------------- #
# arithmetic
# --------------------------------------------------------------------- #

def _rms(x, scale, eps):
    import jax.numpy as jnp
    return x * jnp.reciprocal(jnp.sqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * scale


def recurrence(x, delta, a, b, c, state=None):
    """y (B, T, D) and the last state (B, D, N) of the selective scan,
    one position after another. ``x``, ``delta`` (B, T, D); ``a`` (D, N);
    ``b``, ``c`` (B, T, N)."""
    import jax
    import jax.numpy as jnp
    bsz, length, d = x.shape

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = (jnp.exp(dt_t[..., None] * a) * s
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def block(s, inp):
        return jax.lax.scan(step, s, inp)

    blk = TIME_BLOCK if length % TIME_BLOCK == 0 else 1
    # time first, cut into blocks: (T/blk, blk, B, .)
    cut = lambda v: jnp.swapaxes(v, 0, 1).reshape(      # noqa: E731
        length // blk, blk, bsz, v.shape[-1])
    s0 = jnp.zeros((bsz, d, a.shape[1]), x.dtype) if state is None \
        else state
    s, ys = jax.lax.scan(block, s0, tuple(map(cut, (x, delta, b, c))))
    return jnp.swapaxes(ys.reshape(length, bsz, d), 0, 1), s


def _mlp(x, w, s, ein):
    import jax
    h = _rms(x, w["mlp_norm"], s["eps"])
    a = jax.nn.silu(ein("btd,df->btf", h, w["gate"])) * ein(
        "btd,df->btf", h, w["up"])
    return x + ein("btf,fd->btd", a, w["down"])


def _adapted(ein, h, w, a, b, scale):
    return ein("btd,de->bte", h, w) + scale * ein(
        "btr,re->bte", ein("btd,dr->btr", h, a), b)


def attention_layer(x, w, cfg: dict, quant: str = ""):
    import jax
    import jax.numpy as jnp
    s, ein = sizes(cfg), make_ein(quant)
    scale = s["alpha"] / s["rank"]
    B, T, _ = x.shape
    h = _rms(x, w["mix_norm"], s["eps"])
    q = _adapted(ein, h, w["wq"], w["lora_q_a"], w["lora_q_b"], scale)
    k = ein("btd,de->bte", h, w["wk"])
    v = _adapted(ein, h, w["wv"], w["lora_v_a"], w["lora_v_b"], scale)
    g = s["heads"] // s["kv"]
    q = q.reshape(B, T, s["kv"], g, s["hd"]).transpose(0, 2, 3, 1, 4)
    k = k.reshape(B, T, s["kv"], s["hd"]).transpose(0, 2, 1, 3)
    v = v.reshape(B, T, s["kv"], s["hd"]).transpose(0, 2, 1, 3)
    sc = ein("bkgqd,bktd->bkgqt", q, k) * (s["hd"] ** -0.5)
    sc = jnp.where(jnp.tril(jnp.ones((T, T), bool)), sc, -1e30)
    o = ein("bkgqt,bktd->bkgqd", jax.nn.softmax(sc, axis=-1), v)
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, T, s["d"])
    return _mlp(x + ein("btd,de->bte", o, w["wo"]), w, s, ein)


def mamba_mix(h, w, cfg: dict, quant: str = ""):
    """The Mamba mixer on normed input ``h`` (B, T, d) -> (B, T, d)."""
    import jax
    import jax.numpy as jnp
    s, ein = sizes(cfg), make_ein(quant)
    scale = s["alpha"] / s["rank"]
    di, n, k, r = s["di"], s["n"], s["k"], s["r"]
    T = h.shape[1]
    xz = _adapted(ein, h, w["in_proj"], w["lora_in_a"], w["lora_in_b"],
                  scale)
    x, z = xz[..., :di], xz[..., di:]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))   # causal: left pad
    x = w["conv_b"] + sum(padded[:, j:j + T] * w["conv_w"][j]
                          for j in range(k))
    x = jax.nn.silu(x)
    dbc = ein("bte,ef->btf", x, w["x_proj"])
    dt = _rms(dbc[..., :r], w["dt_norm"], s["eps"])
    b = _rms(dbc[..., r:r + n], w["b_norm"], s["eps"])
    c = _rms(dbc[..., r + n:], w["c_norm"], s["eps"])
    delta = jax.nn.softplus(ein("btr,re->bte", dt, w["dt_proj"])
                            + w["dt_bias"])
    y, _ = recurrence(x, delta, -jnp.exp(w["A_log"]), b, c)
    y = (y + w["D"] * x) * jax.nn.silu(z)
    return _adapted(ein, y, w["out_proj"], w["lora_out_a"], w["lora_out_b"],
                    scale)


def mamba_layer(x, w, cfg: dict, quant: str = ""):
    s = sizes(cfg)
    x = x + mamba_mix(_rms(x, w["mix_norm"], s["eps"]), w, cfg, quant)
    return _mlp(x, w, s, make_ein(quant))


def hidden(base: dict, lora: list, tokens, cfg: dict, quant: str = ""):
    """Final-norm hidden states (B, T, d) of ``tokens`` (B, T)."""
    import jax
    x = base["embed"][tokens]
    for l, (w, adapters) in enumerate(zip(base["layers"], lora)):
        layer = attention_layer if is_attention(cfg, l) else mamba_layer
        x = jax.checkpoint(
            lambda x, w, layer=layer: layer(x, w, cfg, quant))(
                x, {**w, **adapters})
    return _rms(x, base["final_norm"], sizes(cfg)["eps"])


def logits(base, lora, tokens, cfg, quant: str = ""):
    """Tied head: the embedding, transposed."""
    return make_ein(quant)("btd,vd->btv",
                           hidden(base, lora, tokens, cfg, quant),
                           base["embed"])


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
