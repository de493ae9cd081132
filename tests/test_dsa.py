"""The decoder with learned sparse attention and routed experts
(``DsaMoeLite``, ``SparseIndexer``, ``Attention`` with its head width,
rotary base, QK-norm, M-RoPE sections and selection, and the kernels of
``ops/sparse_attention.py``): the model against the benchmark's plain
reference on seeded weights (logits, loss, adapter gradients, counters),
the chosen set against the reference's ``top_k``, the dense causal head of
the choice, M-RoPE with equal streams against the plain rotary, the kernels
in interpret mode against plain masked attention (dead tiles among them),
the shares of the experts adding up to the uncut layer, the parents'
programs left as they were, the chip's compiler at the published widths,
one federated LoRA round, the benchmark's new readers and the
configuration against its published widths. CPU, tiny sizes."""

import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.bindings import dsa_moe as bind
from benchmark.lib import flops_dsa_moe, spec
from benchmark.reference import dsa_moe as ref
from metisfl_tpu.models.ops import _sown_counts
from metisfl_tpu.models.zoo import ExpertShareMLP, JambaLite, LlamaLite
from metisfl_tpu.models.zoo.transformer import Attention, _rotary
from metisfl_tpu.ops import sparse_attention as sa

CELL = "keye-vl-2.0-30b-a3b.lora-round-16k"
SEQ = 64


def _cfg(**over):
    """The cell's configuration at its toy widths, float32 throughout (the
    comparisons below are about the mathematics, not about bfloat16)."""
    cfg = dict(spec.cell(CELL, rehearse=True)["cfg"])
    cfg["compute_dtype"] = "float32"
    cfg["param_dtype"] = {**cfg["param_dtype"], "frozen": "float32"}
    cfg.update(over)
    return cfg


def _flash(cfg, on: bool):
    return {**cfg, "program": {**cfg["program"], "use_flash": on}}


@pytest.fixture(autouse=True)
def _highest(request):
    """float32 products as float32 on both sides (but where the chip's
    compiler is asked, or a program's text is compared with the parent's:
    there the program's own precision)."""
    if any(part in request.node.name
           for part in ("compile_for_the_chip", "parents_program")):
        yield
        return
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(seed, shape=(2, SEQ)):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 256, shape),
                       jnp.int32)


def _named(tree):
    from metisfl_tpu.tensor.pytree import pytree_to_named_tensors
    return dict(pytree_to_named_tensors(jax.device_get(tree)))


def _random(seed, *shape, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       dtype)


# --------------------------------------------------------------------- #
# the model against the reference
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("kernels", [False, True],
                         ids=["plain", "kernels"])
def test_model_logits_loss_gradients_and_counters_match_the_reference(
        kernels):
    """The whole model at toy widths, both the plain path and the Pallas
    kernels (interpret mode), against the float32 reference: logits,
    loss, every adapter's gradient, and the counters against the
    reference's own count of chosen pairs and held assignments."""
    cfg = _flash(_cfg(), kernels)
    module = bind.build_module(cfg)
    variables = bind.variables(cfg, 11)
    lora, base = ref.make_weights(cfg, 11)
    x = _tokens(3)
    y = jnp.roll(x, -1, 1)
    assert set(_named(variables)) == set(_named(jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), x))))
    got, mutated = module.apply(variables, x, mutable=["intermediates"])
    want = ref.logits(base, lora, x, cfg)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    counts = _sown_counts(mutated["intermediates"])
    local, chosen = ref.counts(base, lora, x, cfg)
    assert counts["dsa_selected_count"] == float(chosen)
    assert counts["moe_local_count"] == float(local)
    layers, topk = cfg["num_hidden_layers"], cfg["sa_config"]["topk"]
    # every causal (query block, key block) pair of the one block at 64
    assert counts["dsa_live_tiles_count"] == layers * 2

    def program_loss(params):
        lg = module.apply({"params": params}, x)
        return jnp.mean(-jnp.take_along_axis(jax.nn.log_softmax(lg, -1),
                                             y[..., None], -1))

    loss, grads = jax.value_and_grad(program_loss)(variables["params"])
    want_loss, want_grads = jax.value_and_grad(
        lambda t: ref.loss(t, base, x, y, cfg))(lora)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    got_g, want_g = _named({"params": grads}), bind.by_program_name(
        want_grads)
    assert len(want_g) == 4 * layers
    for name, value in want_g.items():
        np.testing.assert_allclose(got_g[name], value, atol=1e-6,
                                   rtol=1e-4, err_msg=name)
    # at 64 tokens and top 16 with no tie, the count is the closed form
    assert flops_dsa_moe.selected_pairs(SEQ, topk) == 16 * 17 // 2 + 48 * 16


def test_the_chosen_set_is_the_references_top_k_and_dense_below_it():
    """The program's indexer (``SparseIndexer`` under the binding's
    weights) and the reference's ``top_k`` choose the same keys, row for
    row; the first ``topk`` rows take every causal key; the kernel
    ``dsa_select`` (interpret mode, blocks of 32) chooses as the plain
    path does."""
    cfg = _cfg()
    s = ref.sizes(cfg)
    variables = bind.variables(cfg, 5)
    w = {k: v.astype(jnp.float32)
         for k, v in ref.draw_layer(cfg, ref.seed_key(5), 0).items()}
    h = _random(7, 2, SEQ, s["d"])
    indexer = bind.build_module(cfg)._attention().indexer
    words, _ = indexer.apply(
        {"params": variables["params"]["block_0"]["attention"]["indexer"]},
        h, mutable=["intermediates"])
    chosen = np.asarray(sa.unpack(words, SEQ))
    qi, ki, wt = ref.indexer(h, w, cfg)
    want = np.asarray(ref.choose(qi, ki, wt, 0, cfg))
    np.testing.assert_array_equal(chosen, want)
    topk = s["index_top_k"]
    rows = np.arange(SEQ)
    causal = rows[None, :] <= rows[:, None]
    np.testing.assert_array_equal(chosen[:, :topk], causal[None, :topk]
                                  .repeat(2, 0))
    assert (chosen.sum(-1)[:, topk:] == topk).all()
    assert not (chosen & ~causal).any()
    q = jnp.asarray(qi, jnp.float32)
    for edge in (32, 64):
        np.testing.assert_array_equal(
            sa.select(q, ki, wt, topk, interpret=True, edge=edge),
            sa.select_plain(q, ki, wt, topk, edge=edge))


def test_threshold_is_the_k_th_largest_and_takes_ties():
    keys = sa.order_keys(jnp.asarray(
        [[3.0, -1.0, 2.0, 2.0, -0.5, 7.0, -8.0, 0.0]], jnp.float32))
    theta = sa.threshold(keys, 3)
    assert int(theta[0, 0]) == int(sa.order_keys(jnp.float32(2.0)))
    assert int(jnp.sum(keys >= theta)) == 4           # the tie at 2.0
    assert int(sa.threshold(keys, 8)[0, 0]) == int(
        sa.order_keys(jnp.float32(-8.0)))
    assert int(sa.threshold(keys, 9)[0, 0]) == int(np.iinfo(np.int32).min)
    floats = jnp.asarray(np.random.default_rng(0).standard_normal(4096),
                         jnp.float32)
    order = np.argsort(np.asarray(floats))
    assert (np.diff(np.asarray(sa.order_keys(floats))[order]) > 0).all()


def test_mrope_with_equal_streams_is_the_plain_rotary_bit_for_bit():
    x = _random(1, 2, 4, 32, 16)
    positions = jnp.arange(32, dtype=jnp.float32)
    freqs = 1.0 / (1e7 ** (np.arange(0, 8) / 8))
    plain = _rotary(x, positions, freqs)
    three = jnp.broadcast_to(positions, (3, 32))
    np.testing.assert_array_equal(_rotary(x, three, freqs, (2, 3, 3)), plain)
    # streams that differ turn the sections' pairs apart
    moved = three.at[1].multiply(2.0)
    assert not np.array_equal(_rotary(x, moved, freqs, (2, 3, 3)), plain)


def test_cached_decode_names_the_missing_indexer_cache():
    module = bind.build_module(_cfg())
    with pytest.raises(ValueError, match="indexer cache"):
        module.init_cache(1, 32)
    attn = module._attention()
    h = jnp.zeros((1, 1, 64))
    with pytest.raises(ValueError, match="indexer cache"):
        attn.init(jax.random.PRNGKey(0), h, cache=(jnp.zeros(
            (1, 2, 8, 16)),) * 2, position=0)


# --------------------------------------------------------------------- #
# the kernels against plain masked attention
# --------------------------------------------------------------------- #

def _scattered_choice(L, edge, dead=()):
    """A causal choice of about a third of the keys, with the (query
    block, key block) pairs in ``dead`` left empty."""
    rng = np.random.default_rng(4)
    mask = (rng.random((2, L, L)) < 0.35) | np.eye(L, dtype=bool)[None]
    mask &= np.tril(np.ones((L, L), bool))[None]
    for i, j in dead:
        mask[:, i * edge:(i + 1) * edge, j * edge:(j + 1) * edge] = False
    return sa.pack(jnp.asarray(mask), edge)


# the (query block, key block) pairs of 32 left empty, and how many tiles
# of the walk that kills: at 64 the tile (1, 0) holds four of them
_DEAD = {32: (((2, 0), (3, 1), (3, 0)), 3),
         64: (((2, 0), (2, 1), (3, 0), (3, 1)), 1)}


@pytest.mark.parametrize("dead, walk", [
    (False, 32), (True, 32), (False, 64), (True, 64),
], ids=["all-live", "dead-tiles", "all-live-walk-64", "dead-tiles-walk-64"])
def test_selected_flash_kernels_match_plain_masked_attention(
        dead, walk, monkeypatch):
    """GQA 4 on 2 heads, 128 tokens, the choice packed in blocks of 32 and
    walked in tiles of 32 or, with the walk's edge set to 64, of 64 (two
    of the choice's blocks a tile, as 1024 holds two of 512): forward and
    the gradients of q, k and v; with dead pairs the step tables mark the
    tiles that hold none and the kernels skip them."""
    L, edge = 128, 32
    if walk != edge:
        monkeypatch.setattr(sa, "WALK", walk)
    assert sa.walk_edge(L, 16, 16, edge) == walk
    pairs, killed = _DEAD[walk] if dead else ((), 0)
    words = _scattered_choice(L, edge, pairs)
    live = np.asarray(sa.live_tiles(words, walk))
    n = L // walk
    assert live.sum() == 2 * (n * (n + 1) // 2 - killed)
    q = _random(1, 2, 4, L, 16)
    k, v = _random(2, 2, 2, L, 16), _random(3, 2, 2, L, 16)
    ct = _random(4, 2, 4, L, 16)

    def run(fn):
        out, pull = jax.vjp(fn, q, k, v)
        return (out,) + pull(ct)

    got = run(lambda q, k, v: sa.selected_attention(q, k, v, words, None,
                                                    True, edge))
    want = run(lambda q, k, v: sa.attend_plain(q, k, v, words, edge=edge))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=1e-5)
    tables, steps = sa._walk_tables(jnp.asarray(live), L, walk)
    flags = np.asarray(tables[2]).reshape(2, steps)
    assert steps == n * (n + 1) // 2
    assert ((flags & sa._F_LIVE) != 0).sum() == live.sum()


@pytest.mark.parametrize("L, D, Dv, edge, steps", [
    (16384, 128, 128, 1024, 136),       # keye: 528 steps at 512
    (4096, 256, 256, 1024, 10),         # glm's latent layer
    (4096, 384, 128, 512, 36),          # wider than 256: the choice's edge
    (1536, 128, 128, 512, 6),           # 1024 does not divide
    (512, 128, 128, 512, 1),
    (64, 128, 128, 64, 1),              # the toy cells' SEQ: the parent's
])
def test_walk_edge_and_its_census(L, D, Dv, edge, steps):
    """The walk's edge follows the shapes as the causal kernels' does, and
    at or under 512 tokens it is the choice's own (the parent's walk)."""
    assert sa.walk_edge(L, D, Dv) == edge
    assert sa.selected_walk_census(L, D, Dv) == {"edge": edge,
                                                 "steps": steps}
    if L <= sa.BLOCK:
        assert edge == sa.block_edge(L)


def test_packing_round_trips_and_counts():
    mask = np.random.default_rng(2).random((1, 128, 128)) < 0.5
    words = sa.pack(jnp.asarray(mask), 64)
    assert words.shape == (1, 4, 128) and words.dtype == jnp.int32
    np.testing.assert_array_equal(sa.unpack(words, 64), mask)
    counts = sa.counts(words, 64)
    assert counts["dsa_selected_count"] == mask.sum()
    assert counts["dsa_live_tiles_count"] == 4


# --------------------------------------------------------------------- #
# the shares of the experts
# --------------------------------------------------------------------- #

def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts in 4 shares of 4: the four chips' routed parts, the
    program's and the reference's, add up to what the reference gives for
    the whole layer; the four shares' counts add up to T x top_k."""
    cfg = _cfg(experts_held={"first": 0, "count": 16})
    w = {k: v.astype(jnp.float32)
         for k, v in ref.draw_layer(cfg, ref.seed_key(2), 0).items()}
    h = _random(5, 64, 64)
    whole, whole_n = ref.routed_layer(h, w, cfg)
    assert int(whole_n) == 64 * 4
    parts, n = 0.0, 0
    for first in (0, 4, 8, 12):
        cut = {k: w[k][first:first + 4] for k in (
            "experts_gate", "experts_up", "experts_down")}
        share = {**w, **cut}
        part, count = ref.routed_layer(h, share, cfg, held=(first, 4))
        layer = ExpertShareMLP(64, 32, 16, 4, first=first, count=4,
                               score_func="softmax", norm_topk=True)
        params = bind._block(share)["ffn"]
        program = layer.apply({"params": params}, h[None])[0]
        np.testing.assert_allclose(program, part, atol=2e-5, rtol=1e-4)
        parts, n = parts + part, n + int(count)
    np.testing.assert_allclose(parts, whole, atol=2e-5, rtol=1e-4)
    assert n == int(whole_n)


# --------------------------------------------------------------------- #
# the parents' programs
# --------------------------------------------------------------------- #

# sha256 of ``jax.jit(f).lower(...).as_text()`` (no source locations in
# it) of the programs below, made on the parent commit 1d75aa1 under this
# suite's x64: forward with counters, the gradient of a loss, and a cached
# decode step of ``LlamaLite`` on the flash kernels and on the dense path
# and of ``JambaLite``. An edit that changes what ``Attention`` or
# ``DecoderBlock`` traces at their defaults changes them: make them again
# on the tree before the edit and say why they moved
_PARENT = {
    "llama": (
        "458020bb4cafb8886d8658695ab004498c626e9fd79b6629025d9c4817d992dc",
        "8c71fd3f5966ef7754b4b44730f5c04b2003a674401225c974ec2fe1841be90f",
        "17136ec3d11b4a7393c2f53f21267680af3cb8c5d2a331607a2922e10fc2ac61"),
    "llama_dense": (
        "a114f934a3a55df45ad9d372355c1a9afa95448deaace94166945d927673dfff",
        "cd8a11787300bce0fa87f14bd53c29761ec166f59df01342d227e89572c0569a",
        "17136ec3d11b4a7393c2f53f21267680af3cb8c5d2a331607a2922e10fc2ac61"),
    "jamba": (
        "3d240c23e48fabb0402e3b3cff134fbd9888389637eae6c5feb56fa099d67f1e",
        "bdf6ddabc19e08ec64c439e840fa0de90efc0cd75563172ee91bdf52f8590665",
        "eefe618f16966988983cfe2087bf77ed4f9382d0728e26fc78ee85a438a787c9"),
}


def test_defaults_trace_the_parents_program_on_the_gqa_families():
    """``Attention`` and ``DecoderBlock`` at their defaults: the GQA
    decoder (flash kernels and dense), with remat, and the hybrid lower to
    the text the parent's lowered to, so the four flash cells' programs are
    the parent's bit for bit; and the new fields do move the program."""
    if not jax.config.jax_enable_x64:
        pytest.skip("the digests were made under x64, as the suite runs")
    digest = lambda text: hashlib.sha256(text.encode()).hexdigest()  # noqa
    x = jnp.zeros((2, 16), jnp.int32)
    models = {
        "llama": LlamaLite(vocab_size=64, dim=32, depth=2, heads=4,
                           kv_heads=2, lora_rank=2, remat=True,
                           use_flash=True, dtype=jnp.bfloat16),
        "llama_dense": LlamaLite(vocab_size=64, dim=32, depth=2, heads=4,
                                 kv_heads=2, lora_rank=2,
                                 dtype=jnp.bfloat16),
        "jamba": JambaLite(vocab_size=64, dim=32, depth=2, heads=4,
                           kv_heads=1, lora_rank=2, remat=True,
                           use_flash=True, dtype=jnp.bfloat16)}
    for name, m in models.items():
        shapes = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0), x))
        forward = jax.jit(lambda v, t: m.apply(
            v, t, mutable=["intermediates"])).lower(shapes, x).as_text()
        grad = jax.jit(jax.grad(lambda v, t: jnp.sum(
            m.apply(v, t).astype(jnp.float32)))).lower(shapes, x).as_text()
        cached = jax.jit(lambda v, t, c: m.apply(
            v, t, caches=c, position=3)).lower(
                shapes, x[:, :1], m.init_cache(2, 24)).as_text()
        assert (digest(forward), digest(grad), digest(cached)) == \
            _PARENT[name], name
    layer = Attention(32, 4, causal=True, rotary=True, kv_heads=2)
    h = jnp.zeros((1, 8, 32))
    text = lambda mod: jax.jit(lambda: mod.init_with_output(   # noqa: E731
        jax.random.PRNGKey(0), h)[0]).lower().as_text()
    assert text(layer) == text(layer.clone(
        head_dim=8, rope_base=10000.0, rope_sections=(), qk_norm=False,
        indexer=None, param_dtype=jnp.float32))
    for moved in (dict(head_dim=16), dict(rope_base=1e6),
                  dict(rope_sections=(1, 1, 2)), dict(qk_norm=True),
                  dict(param_dtype=jnp.bfloat16)):
        assert text(layer) != text(layer.clone(**moved)), moved


# --------------------------------------------------------------------- #
# the chip's compiler at the published widths, no chip
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - any failure is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_for_the_chip(one_chip, H, Hkv, L, D, Dv, choose):
    """Compile the choice (when ``choose``: 16 indexer heads of 64, top
    2,048) and attention over it, forward and backward, for one v5e chip:
    (the choice's executable or None, attention's)."""
    from jax.experimental.compilation_cache import compilation_cache
    before = {k: getattr(jax.config, k)
              for k in ("jax_enable_x64", "jax_enable_compilation_cache")}
    for k in before:
        jax.config.update(k, False)
    compilation_cache.reset_cache()
    try:
        shape = lambda dtype, *s: jax.ShapeDtypeStruct(     # noqa: E731
            s, dtype, sharding=one_chip)
        bf = jnp.bfloat16
        select = choose and jax.jit(lambda qi, ki, w: sa.select(
            qi, ki, w, 2048, interpret=False)).lower(
                shape(bf, 1, L, 16, 64), shape(bf, 1, L, 64),
                shape(jnp.float32, 1, L, 16)).compile()

        def attend(q, k, v, words, ct):
            return jnp.sum(sa.selected_attention(
                q, k, v, words, None, False).astype(jnp.float32) * ct)

        attention = jax.jit(jax.grad(attend, argnums=(0, 1, 2))).lower(
            shape(bf, 1, H, L, D), shape(bf, 1, Hkv, L, D),
            shape(bf, 1, Hkv, L, Dv), shape(jnp.int32, 1, L // 32, L),
            shape(jnp.float32, 1, H, L, Dv)).compile()
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    call = 'custom_call_target="tpu_custom_call"'
    assert attention.as_text().count(call) == 3
    assert attention.memory_analysis().temp_size_in_bytes < 1e9
    return select or None, attention


def test_kernels_compile_for_the_chip_at_published_widths(one_chip):
    """16,384 tokens: the choice (16 indexer heads of 64, top 2,048; a row
    block's scores in VMEM) and attention over it (32 heads on 4 of 128),
    forward and backward: one ``dsa_select`` and three selected-flash
    kernels, walked in tiles of 1024."""
    assert sa.walk_edge(16384, 128, 128) == 1024
    select, _ = _compile_for_the_chip(one_chip, 32, 4, 16384, 128, 128,
                                      True)
    assert select.as_text().count('custom_call_target="tpu_custom_call"') \
        == 1


def test_kernels_compile_for_the_chip_at_the_latent_layer_widths(one_chip):
    """glm's latent layer, 64 heads of 256 / 256 at 4,096 tokens over a
    pooled choice: the three selected-flash kernels at the walk's edge
    fit the chip's VMEM."""
    assert sa.walk_edge(4096, 256, 256) == 1024
    _compile_for_the_chip(one_chip, 64, 64, 4096, 256, 256, False)


# --------------------------------------------------------------------- #
# FLOPs
# --------------------------------------------------------------------- #

def test_dsa_flops_at_the_cells_shape_and_against_cost_analysis():
    """The counts at 16,384 tokens a layer (GFLOP, forward):
    projections 701, attention over the chosen pairs 515, the indexer's
    products 275, the held experts' 155; and at toy depth XLA's count of
    the plain path (the whole score matrix, the indexer over every pair and
    every held expert over every token) bounds it from above."""
    full = spec.cell(CELL)
    cfg, shape = full["cfg"], full["traffic"]["shape"]
    T = 16384
    assert flops_dsa_moe.selected_pairs(T, 2048) == 31_458_304
    assert flops_dsa_moe.causal_pairs(T) == 134_225_920
    selected, local = flops_dsa_moe.expected_counts(cfg, 1, T)
    assert (selected, local) == (251_666_432, 131_072.0)
    fwd = flops_dsa_moe.forward_flops(cfg, 1, T)
    head = 2.0 * 2048 * 18992 * T
    per_layer = (fwd["trained"] - head) / 8
    assert per_layer == pytest.approx((701 - 74 + 515 + 155) * 1e9, rel=0.01)
    assert fwd["indexer"] / 8 == pytest.approx((275 + 74) * 1e9, rel=0.01)
    assert flops_dsa_moe.train_step_flops(cfg, shape) == pytest.approx(
        2 * fwd["trained"] + fwd["indexer"])
    flash = flops_dsa_moe.flash_cost(cfg, shape, selected, remat=True)
    assert flash["flops"] == pytest.approx(9 * 2 * 32 * 128 * selected)
    assert flash["flops"] / 197e12 > flash["bytes"] / 819e9
    index = flops_dsa_moe.index_cost(cfg, shape)
    assert index["flops"] == pytest.approx(8 * 275e9, rel=0.01)
    small = _cfg()
    module = bind.build_module(small)
    x = jnp.zeros((2, SEQ), jnp.int32)
    shapes = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), x))
    cost = jax.jit(lambda v, t: module.apply(v, t)).lower(
        shapes, x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    ours = flops_dsa_moe.forward_flops(small, 2, SEQ)
    # XLA also counts the non-causal half of every (T, T) product, the
    # choice's 32 counting passes and the plain path's grouped products
    # over every row: about five times ours at this size
    assert 0.15 * float(cost["flops"]) <= ours["trained"] + ours["indexer"] \
        <= float(cost["flops"])


# --------------------------------------------------------------------- #
# one federated LoRA round through DriverSession
# --------------------------------------------------------------------- #

def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_a_federated_lora_round_ships_adapters_and_keeps_the_base(tmp_path):
    """Two rounds of the cell's own recipe at toy widths, through
    ``DriverSession`` -> ``Learner`` -> ``FlaxModelOps.train``: the
    community model holds ``lora_`` leaves alone, the learner keeps the
    bfloat16 base on the device from the second round on, and the counters
    of the choice and of the routed layers arrive in the round's
    profile."""
    from benchmark.lib.recipes import Recipe
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (EvalConfig, FederationConfig,
                                    TerminationConfig)
    from metisfl_tpu.driver.session import DriverSession
    from metisfl_tpu.tensor.pytree import ModelBlob
    cell = spec.cell(CELL, rehearse=True)
    cfg, shape = cell["cfg"], cell["traffic"]["shape"]
    initial = bind.shipped_host(cfg, 9)
    config = FederationConfig(
        controller_port=_free_port(),
        train=TrainParams(batch_size=shape["batch"],
                          local_steps=shape["local_steps"],
                          scan_chunk=shape["scan_chunk"], optimizer="adam",
                          learning_rate=1e-3, ship_tensor_regex="lora_"),
        eval=EvalConfig(every_n_rounds=0),
        termination=TerminationConfig(federation_rounds=2,
                                      execution_cutoff_mins=5.0))
    session = DriverSession(config, initial, [Recipe(cfg, shape, 9)],
                            workdir=str(tmp_path))
    try:
        session.initialize_federation(launch_serving=False)
        session.monitor_federation(poll_every_s=0.5, eval_drain_timeout_s=0)
        blob = session._client.get_community_model()
        metas = session._client.get_runtime_metadata(tail=0, timeout=30.0)
    finally:
        session.shutdown_federation()
    if isinstance(metas, dict):
        metas = metas.get("round_metadata", [])
    named = dict(ModelBlob.from_bytes(blob).tensors)
    sent = _named(initial)
    assert set(named) == set(sent) and all("lora_" in n for n in named)
    assert any(not np.array_equal(named[n], sent[n]) for n in named)
    done = [m for m in metas if m.get("completed_at", 0) > 0]
    assert len(done) >= 2
    sizes = {n: int(a.nbytes)
             for n, a in _named(bind.variables(cfg, 9)).items()}
    shipped = sum(v for n, v in sizes.items() if "lora_" in n)
    lid = done[1]["selected_learners"][0]
    profile = done[1]["profile"]
    assert profile["learners"][lid]["task_bytes"]["kept_bytes"] == \
        sum(sizes.values()) - shipped
    device = profile["learners"][lid]["device"]
    layers, topk = cfg["num_hidden_layers"], cfg["sa_config"]["topk"]
    pairs = shape["batch"] * layers * flops_dsa_moe.selected_pairs(
        shape["seq"], topk)
    # a step's chosen pairs: the closed form, and ties above it
    assert pairs <= device["dsa_selected_count"] <= 1.01 * pairs
    assert device["dsa_live_tiles_count"] == shape["batch"] * layers
    assert 0 < device["moe_max_group_count"] <= device["moe_local_count"]


# --------------------------------------------------------------------- #
# the benchmark's new readers on hand-made contexts
# --------------------------------------------------------------------- #

def _ctx(kernel_ops_s, selected=251_700_000.0, local=131_000.0):
    cell = spec.cell(CELL)
    device = {"ms_per_step": 1300.0}
    if selected is not None:
        device["dsa_selected_count"] = selected
    if local is not None:
        device["moe_local_count"] = local
    rounds = [{"profile": {"learners": {"L0": {"device": device}}}}]
    return {"cell": cell, "cfg": cell["cfg"], "traffic": cell["traffic"],
            "rounds": rounds, "learner": "L0", "device_kind": "TPU v5 lite",
            "trace": {"busy_s": 5.0, "window_s": 5.4,
                      "module_runs": {"jit_train_scan_steps": 1.0},
                      "kernel_ops_s": kernel_ops_s, "ops_s": {}}}


def test_new_readers_read_their_operations_and_nothing_else():
    from benchmark.metrics import (dsa_experts_roofline, dsa_flash_roofline,
                                   dsa_index_roofline, dsa_index_share,
                                   dsa_step_mfu, moe_experts_share)
    cell = spec.cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    new = {"dsa_step_mfu", "dsa_flash_roofline", "dsa_index_roofline",
           "dsa_index_share", "dsa_experts_roofline"}
    # the new five, and the experts' share the latent cell reads too
    assert new | {"moe_experts_share"} <= names
    assert len(names) == 13 + 6
    assert {m["name"] for m in cell["end_to_end"]} == {"round_s", "setup_s"}
    for other in spec.benchmark()["workloads"]:
        if other["name"] != CELL:
            assert not new & {m["name"] for m in
                              spec.cell(other["name"])["per_layer"]}
    # the configuration names the grouped products; the choice is read by
    # its kernel's name
    assert cell["cfg"]["program"]["trace_ops"] == ["moe_gmm_fwd",
                                                   "moe_gmm_bwd"]
    kernels = {"flash_fwd_sel": 1.6, "flash_bwd_dq_sel": 0.9,
               "flash_bwd_dkv_sel": 1.1, "flash_fwd": 7.0,
               "dsa_select": 0.3, "moe_gmm_fwd": 0.1, "moe_gmm_bwd": 0.15}
    ctx = _ctx(kernels)
    shape = cell["traffic"]["shape"]
    assert dsa_index_share.read(ctx) == pytest.approx(100 * 0.3 / 5.0)
    index = flops_dsa_moe.index_cost(cell["cfg"], shape)
    assert dsa_index_roofline.read(ctx) == pytest.approx(
        100 * 4 * index["flops"] / 197e12 / 0.3)
    assert moe_experts_share.read(ctx) == pytest.approx(100 * 0.25 / 5.0)
    experts = flops_dsa_moe.experts_cost(cell["cfg"], 131_000.0, remat=True)
    assert dsa_experts_roofline.read(ctx) == pytest.approx(
        100 * 4 * max(experts["flops"] / 197e12,
                      experts["bytes"] / 819e9) / 0.25)
    flash = flops_dsa_moe.flash_cost(cell["cfg"], shape, 251_700_000.0,
                                     remat=True)
    assert dsa_flash_roofline.read(ctx) == pytest.approx(
        100 * 4 * flash["flops"] / 197e12 / 3.6)
    work = flops_dsa_moe.train_step_flops(cell["cfg"], shape, 251_700_000.0,
                                          131_000.0)
    assert dsa_step_mfu.read(ctx) == pytest.approx(100 * work / 1.3 / 197e12)
    readers = (dsa_index_share, dsa_index_roofline, dsa_flash_roofline,
               dsa_step_mfu, dsa_experts_roofline, moe_experts_share)
    for reader in readers:
        assert 0 < reader.read(ctx) < 100
    # a program without the kernels or the counters (the parent): nothing,
    # and no raise
    quiet = _ctx({"flash_fwd": 0.4}, selected=None, local=None)
    for reader in readers:
        assert reader.read(quiet) is None
    assert dsa_flash_roofline.read(_ctx(kernels, selected=None)) is None
    assert dsa_step_mfu.read(_ctx(kernels, local=None)) is None
    assert dsa_experts_roofline.read(_ctx(kernels, local=None)) is None
    assert dsa_index_share.read({"trace": None, "cfg": cell["cfg"]}) is None


def test_configuration_file_keeps_the_published_widths():
    cfg = spec.cell(CELL)["cfg"]
    bench = spec.benchmark()
    entry = next(c for c in bench["configs"]
                 if c["name"] == "keye-vl-2.0-30b-a3b")
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (work["config"], work["traffic"], work["chips"]) == (
        "keye-vl-2.0-30b-a3b", "lora-round-16k", 1)
    reduced = ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["reduced"] == cfg["reduced"] == reduced
    assert entry["source"] == cfg["source"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 151936}
    # every published width unchanged
    s = ref.sizes(cfg)
    assert (s["d"], s["heads"], s["kv"], s["hd"], s["moe"], s["experts"],
            s["top_k"], s["index_heads"], s["index_dim"],
            s["index_top_k"], s["sections"], s["eps"], s["theta"]) == (
        2048, 32, 4, 128, 768, 128, 8, 16, 64, 2048, (16, 24, 24), 1e-6,
        1e7)
    # the floors of a cut: a whole period, 4 layers, 8 experts, 1/8 vocab
    assert cfg["num_hidden_layers"] >= 4
    assert cfg["num_experts"] == cfg["experts_held"]["count"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    for key in ("deployment", "assumed", "departures", "parameters"):
        assert cfg[key]
    assert "8 chips" in cfg["deployment"] and "1/8" in cfg["deployment"]
    assert {"qk_norm", "indexer rotary", "indexer key norm",
            "indexer scales", "indexer input", "chunks", "draws"} <= set(
        cfg["assumed"])
    assert any("vision tower" in d for d in cfg["departures"])
    assert any("M-RoPE streams are equal" in d for d in cfg["departures"])
    assert cell_shape() == (1, 16384, 4, 4)


def cell_shape():
    shape = spec.cell(CELL)["traffic"]["shape"]
    return (shape["batch"], shape["seq"], shape["local_steps"],
            shape["scan_chunk"])
