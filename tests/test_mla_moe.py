"""The latent-attention decoder with a chip's share of its routed experts
(``MlaMoeLite``, ``LatentAttention``, ``ExpertShareMLP``,
``ops/grouped_matmul.py``, the flash kernels at unequal widths): YaRN's
frequencies and the softmax scale against numbers worked by hand, the
grouped product against a loop over experts, the mixer, the expert layer
and the whole model against the benchmark's plain reference on seeded
weights, the shares of the experts adding up to the uncut layer, prefill
and cached decode against the full forward pass, one federated LoRA round
that leaves the bfloat16 base where it was, and the benchmark's new
readers on hand-made contexts. CPU, tiny sizes; the Pallas kernels run in
interpret mode."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.bindings import mla_moe as bind
from benchmark.lib import flops_mla_moe, spec
from benchmark.reference import mla_moe as ref
from metisfl_tpu.models.zoo import (ExpertShareMLP, LatentAttention,
                                    MlaMoeLite)
from metisfl_tpu.models.zoo.transformer import yarn_frequencies, yarn_mscale
from metisfl_tpu.ops import flash_attention, grouped_matmul as gm
from metisfl_tpu.ops.flash_attention import _dense_attention

CELL = "kimi-k2.7-code.lora-round"


def _cfg(**over):
    """The cell's configuration at its toy widths, float32 throughout (the
    comparisons below are about the mathematics, not about bfloat16)."""
    cfg = dict(spec.cell(CELL, rehearse=True)["cfg"])
    cfg["compute_dtype"] = "float32"
    cfg["param_dtype"] = {**cfg["param_dtype"], "frozen": "float32"}
    cfg.update(over)
    return cfg


@pytest.fixture(autouse=True)
def _highest(request):
    """float32 products as float32 on both sides (but where the chip's
    compiler is asked: there the program's own precision)."""
    if "compile_for_the_chip" in request.node.name:
        yield
        return
    with jax.default_matmul_precision("highest"):
        yield


# --------------------------------------------------------------------- #
# YaRN and the softmax scale, from the catalog row's constants by hand
# --------------------------------------------------------------------- #

def test_yarn_frequencies_and_the_softmax_scale_by_hand():
    """theta 50000, 64 rotary columns, factor 64 over 4096, beta 32 and 1:
    low = floor(64 ln(4096 / (2 pi 32)) / (2 ln 50000)) = floor(8.914) = 8,
    high = ceil(64 ln(4096 / (2 pi)) / (2 ln 50000)) = ceil(19.165) = 20;
    steps 0-8 keep f_i = 50000^(-i/32), steps 20-31 are f_i / 64, step 14
    sits halfway up the ramp. m = 0.1 ln 64 + 1 = 1.415888, so the scale
    is 192^-1/2 x m^2 = 0.0721688 x 2.004740 = 0.144680."""
    full = spec.cell(CELL)["cfg"]
    for freqs in (yarn_frequencies(64, 50000.0, 64.0, 4096, 32.0, 1.0),
                  ref.yarn_frequencies(full)):
        assert freqs.shape == (32,)
        plain = 50000.0 ** (-np.arange(32) / 32.0)
        np.testing.assert_allclose(freqs[:9], plain[:9], rtol=1e-6)
        np.testing.assert_allclose(freqs[20:], plain[20:] / 64, rtol=1e-6)
        np.testing.assert_allclose(
            freqs[14], plain[14] * (0.5 / 64 + 0.5), rtol=1e-6)
        assert freqs[0] == pytest.approx(1.0)
        assert freqs[31] == pytest.approx(50000.0 ** (-31 / 32) / 64,
                                          rel=1e-6)
    assert yarn_mscale(64.0, 1.0) == pytest.approx(1.4158883, rel=1e-6)
    mixer = bind.build_module(full)._mla()
    assert mixer.softmax_scale() == pytest.approx(0.144680, rel=1e-5)
    assert ref.softmax_scale(full) == pytest.approx(0.144680, rel=1e-5)
    # mscale / mscale_all_dim = 1: cos and sin carry no factor
    assert yarn_mscale(64.0, 1.0) / yarn_mscale(64.0, 1.0) == 1.0


# --------------------------------------------------------------------- #
# the grouped product and the dispatch
# --------------------------------------------------------------------- #

def _routing(T, K, E, seed=0):
    rng = np.random.default_rng(seed)
    return np.stack([rng.choice(E, K, replace=False)
                     for _ in range(T)]).astype(np.int32)


def _by_loop(x, gates, chosen, w, first):
    out = jnp.zeros((x.shape[0], w.shape[2]), x.dtype)
    for e in range(w.shape[0]):
        weight = jnp.sum(jnp.where(chosen == first + e, gates, 0.0), -1)
        out = out + weight[:, None] * (x @ w[e])
    return out


def _routed(x, gates, chosen, w, first, interpret):
    plan = gm.plan_dispatch(jnp.asarray(chosen), first, w.shape[0])
    y = gm.grouped_matmul(gm.to_rows(x, plan), w, plan, interpret=interpret)
    y = jnp.where(plan.valid[:, None],
                  y * gm.row_weights(gates, plan)[:, None], 0)
    return gm.to_tokens(y, plan)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["ragged_dot", "kernels"])
@pytest.mark.parametrize("case", ["even", "empty_groups", "one_expert"])
def test_grouped_product_matches_a_loop_over_experts(case, interpret):
    """Values and the gradients to the rows and the gates; groups that got
    nothing; every token on one held expert (no capacity: none dropped)."""
    T, K, E, first, count, d, h = 48, 4, 16, 4, 4, 32, 24
    chosen = _routing(T, K, E, seed=1)
    if case == "empty_groups":      # experts 5 and 6 get nothing
        chosen = np.where((chosen == 5) | (chosen == 6), 12, chosen)
    if case == "one_expert":        # every token's first choice is expert 7
        chosen[:, 0] = 7
        chosen[:, 1:] = np.where(chosen[:, 1:] == 7, 15, chosen[:, 1:])
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(k[0], (T, d), jnp.float32)
    gates = jax.random.uniform(k[1], (T, K), jnp.float32)
    w = jax.random.normal(k[2], (count, d, h), jnp.float32) * d ** -0.5
    plan = gm.plan_dispatch(jnp.asarray(chosen), first, count)
    held = (chosen >= first) & (chosen < first + count)
    assert int(plan.sizes.sum()) == held.sum() == int(plan.valid.sum())
    if case == "one_expert":
        assert int(plan.sizes[3]) == T            # all of them, none lost
    if case == "empty_groups":
        assert int(plan.sizes[1]) == int(plan.sizes[2]) == 0
    assert plan.token.shape[0] == gm.rows_for(T, K, count)
    got = _routed(x, gates, chosen, w, first, interpret)
    want = _by_loop(x, gates, chosen, w, first)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    grads = lambda f: jax.grad(                             # noqa: E731
        lambda x, g: jnp.sum(f(x, g) ** 2), argnums=(0, 1))(x, gates)
    for a, b in zip(grads(lambda x, g: _routed(x, g, chosen, w, first,
                                               interpret)),
                    grads(lambda x, g: _by_loop(x, g, chosen, w, first))):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["ragged_dot", "kernels"])
@pytest.mark.parametrize("routing", ["even", "skewed"])
def test_routed_experts_in_one_chunk_and_in_several(routing, interpret):
    """One sort, walked in chunks of what an even routing needs (768 rows
    here): an even routing takes one chunk, a routing that puts two
    choices of every token on held experts needs 2,048 rows and takes
    three: the same sums, nothing dropped."""
    T, K, E, first, count, d, h = 1024, 4, 64, 8, 4, 16, 24
    assert gm.rows_for(T, K, count) == 4608
    assert gm.chunk_rows(T, K, count, E) == 768
    chosen = _routing(T, K, E, seed=4)
    if routing == "skewed":
        chosen[:, 0], chosen[:, 1] = 9, 10
        chosen[:, 2:] = np.where((chosen[:, 2:] >= 8) & (chosen[:, 2:] < 12),
                                 40, chosen[:, 2:])
    tiles = int(gm.sort_choices(jnp.asarray(chosen), first, count).tiles)
    assert -(-tiles * gm.TILE // 768) == (1 if routing == "even" else 3)
    k = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(k[0], (T, d), jnp.float32)
    gates = jax.random.uniform(k[1], (T, K), jnp.float32)
    w = [jax.random.normal(k[i], s, jnp.float32) * s[1] ** -0.5
         for i, s in ((2, (count, d, h)), (3, (count, d, h)),
                      (4, (count, h, d)))]

    def routed(x, gates):
        out, sizes = gm.routed_experts(
            x, jnp.asarray(chosen), gates, *w, first=first, num_experts=E,
            interpret=interpret)
        return out, sizes

    def by_loop(x, gates):
        out = jnp.zeros_like(x)
        for e in range(count):
            weight = jnp.sum(jnp.where(chosen == first + e, gates, 0.0), -1)
            out = out + weight[:, None] * (
                (jax.nn.silu(x @ w[0][e]) * (x @ w[1][e])) @ w[2][e])
        return out

    got, sizes = jax.jit(routed)(x, gates)
    held = (chosen >= first) & (chosen < first + count)
    assert int(sizes.sum()) == held.sum()
    np.testing.assert_allclose(got, by_loop(x, gates), rtol=1e-4, atol=1e-5)
    grads = lambda f: jax.grad(                             # noqa: E731
        lambda x, g: jnp.sum(f(x, g) ** 2), argnums=(0, 1))(x, gates)
    for a, b in zip(grads(lambda x, g: routed(x, g)[0]), grads(by_loop)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-4)


# --------------------------------------------------------------------- #
# the flash kernels at a value width other than the scores'
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("heads,kv_heads,dq,dv", [(2, 2, 192, 128),
                                                  (8, 2, 48, 32)])
def test_flash_kernels_at_unequal_widths(heads, kv_heads, dq, dv):
    """Forward, dq, dk and dv against dense attention: latent attention's
    192 / 128, and grouped queries (8 on 2) with unequal widths; a scale
    of the caller's own."""
    L, scale = 64, 0.11
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(k[0], (1, heads, L, dq), jnp.float32)
    kk = jax.random.normal(k[1], (1, kv_heads, L, dq), jnp.float32)
    v = jax.random.normal(k[2], (1, kv_heads, L, dv), jnp.float32)
    weight = jax.random.normal(k[3], (1, heads, L, dv), jnp.float32)
    rep = heads // kv_heads

    def dense(q, kk, v):
        return _dense_attention(q, jnp.repeat(kk, rep, 1),
                                jnp.repeat(v, rep, 1), True, scale)

    flash = lambda q, kk, v: flash_attention(              # noqa: E731
        q, kk, v, True, 32, 16, True, scale)
    np.testing.assert_allclose(flash(q, kk, v), dense(q, kk, v),
                               rtol=2e-5, atol=2e-5)
    assert flash(q, kk, v).shape == (1, heads, L, dv)
    grads = lambda f: jax.grad(                             # noqa: E731
        lambda *a: jnp.sum(f(*a) * weight), argnums=(0, 1, 2))(q, kk, v)
    for a, b in zip(grads(flash), grads(dense)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------- #
# the chip's compiler on the new kernels at the cell's widths, no chip
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


def test_new_kernels_compile_for_the_chip_at_published_widths(one_chip):
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
        attn = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, True, None, None, False, 0.1).astype(jnp.float32)),
            argnums=(0, 1, 2))).lower(
                shape(bf, 1, 64, 4096, 192), shape(bf, 1, 64, 4096, 192),
                shape(bf, 1, 64, 4096, 128)).compile()

        def experts(x, chosen, gates, w_gate, w_up, w_down):
            return jnp.sum(gm.routed_experts(
                x, chosen, gates, w_gate, w_up, w_down, first=0,
                num_experts=384, interpret=False)[0].astype(jnp.float32))

        moe = jax.jit(jax.grad(experts)).lower(
            shape(bf, 4096, 7168), shape(jnp.int32, 4096, 8),
            shape(jnp.float32, 4096, 8), shape(bf, 12, 7168, 2048),
            shape(bf, 12, 7168, 2048), shape(bf, 12, 2048, 7168)).compile()
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in attn.as_text()
    # (inside the chunks' loop XLA's text keeps no name of theirs: the
    # three products, computed again in the backward, and their three
    # transposes)
    assert moe.as_text().count('custom_call_target="tpu_custom_call"') == 6
    # no (tokens, experts, capacity) tensor (4096 x 384 x 107 of them
    # would be 337 MB in bfloat16 a layer, and 32 times that uncut)
    assert moe.memory_analysis().temp_size_in_bytes < 1.5e9


def test_decode_programs_compile_for_the_chip_with_the_cache_in_place(
        one_chip):
    """``SlotDecoder``'s step and prefill at the serve cell's widths (8
    slots of 2048, 8 KV heads of 128; depth and vocabulary cut): the chip's
    compiler aliases every cache leaf's output to its donated input, and
    the step holds one ``dynamic_update_slice`` a slot a leaf and no loop
    (what it makes of the scatter a batched start index gives; at the
    cell's depth it runs that loop on a staged copy of the leaf and writes
    the leaf back whole, PERF.md section 6, PR 36). Kept beside the other
    compiles for the chip: one file, one worker, one load of the library."""
    from jax.experimental.compilation_cache import compilation_cache

    from metisfl_tpu.models.generate import DecodeCall, SlotDecoder
    from metisfl_tpu.models.zoo import LlamaLite

    before = {k: getattr(jax.config, k)
              for k in ("jax_enable_x64", "jax_enable_compilation_cache")}
    for k in before:
        jax.config.update(k, False)
    compilation_cache.reset_cache()
    try:
        on_chip = lambda tree: jax.tree.map(                # noqa: E731
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=one_chip), tree)
        module = LlamaLite(vocab_size=4096, dim=2048, depth=2, heads=16,
                           kv_heads=8, lora_rank=16, dtype=jnp.bfloat16)
        variables = on_chip(jax.eval_shape(lambda: module.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))))

        class Probe(SlotDecoder):
            """Shapes for arrays, and a call compiled where it would run."""

            def _zeroed(self):
                return on_chip(jax.eval_shape(super()._zeroed))

            def _launch(self, fn, variables, *args, prefill, entry):
                self.compiled = fn.__wrapped__.lower(
                    variables, on_chip(self.state), *on_chip(args)).compile()
                # a prefill's token, a step's
                return DecodeCall(np.zeros(() if prefill else (self.slots,),
                                           np.int32), prefill, entry, entry)

        decoder = Probe(module, slots=8, max_len=2048)
        decoder.step(variables, np.zeros(8, np.int32), np.zeros(8, np.int32))
        step = decoder.compiled
        decoder.prefill(variables, 3, np.ones(64, np.int32))
        prefill = decoder.compiled
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    # the cache, and the lanes' tokens and positions: (8,) int32 each,
    # laid out in the 128 lanes of a tile row
    state_bytes = 2 * 2 * 8 * 8 * 2048 * 128 * 2 + 2 * 128 * 4
    for program in (step, prefill):
        assert program.memory_analysis().alias_size_in_bytes == state_bytes
    text = step.as_text()
    assert " while(" not in text and " scatter(" not in text
    leaf = r"bf16\[8,1,8,2048,128\]\{[^}]*\} dynamic-update-slice\("
    assert len(re.findall(leaf, text)) == 4 * 8


# --------------------------------------------------------------------- #
# mixer, expert layer and model against the plain reference
# --------------------------------------------------------------------- #

def _program_and_reference(cfg, seed=11):
    module = bind.build_module(cfg)
    variables = bind.variables(cfg, seed)
    trainable, frozen = ref.make_weights(cfg, seed)
    return module, variables, trainable, frozen


def _named(tree):
    from metisfl_tpu.tensor.pytree import pytree_to_named_tensors
    return dict(pytree_to_named_tensors(jax.device_get(tree)))


@pytest.mark.parametrize("flash", [False, True], ids=["dense", "flash"])
def test_mixer_matches_the_reference_mixer(flash):
    """Values and the gradients to the adapters and to the input."""
    cfg = _cfg()
    module, variables, trainable, frozen = _program_and_reference(cfg)
    mixer = module._mla().clone(use_flash=flash)
    params = variables["params"]["block_1"]["mla"]
    w = {**ref.layer_of(frozen, 1), **trainable[1]}
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 64), jnp.float32)
    weight = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 64))

    def program(params, h):
        return jnp.sum(mixer.apply({"params": params}, h) * weight)

    def reference(adapters, h):
        return jnp.sum(ref.mla(h, {**w, **adapters}, cfg) * weight)

    np.testing.assert_allclose(mixer.apply({"params": params}, h),
                               ref.mla(h, w, cfg), rtol=2e-4, atol=2e-5)
    gp, gh = jax.grad(program, argnums=(0, 1))(params, h)
    gr, gh_ref = jax.grad(reference, argnums=(0, 1))(trainable[1], h)
    np.testing.assert_allclose(gh, gh_ref, rtol=2e-3, atol=2e-5)
    got = _named({"params": {"block_1": {"mla": gp}}})
    for name, value in bind.by_program_name([{}, gr]).items():
        np.testing.assert_allclose(got[name], value, rtol=2e-3, atol=2e-6)


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["ragged_dot", "kernels"])
def test_expert_layer_matches_the_reference_layer(interpret):
    cfg = _cfg()
    module, variables, _, frozen = _program_and_reference(cfg)
    layer = module._ffn(1).clone(gmm_interpret=interpret)
    params = variables["params"]["block_1"]["ffn"]
    w = ref.layer_of(frozen, 1)
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 32, 64), jnp.float32)
    got, sown = layer.apply({"params": params}, h, mutable=["intermediates"])
    want, local = ref.expert_layer(h.reshape(64, 64), w, cfg)
    np.testing.assert_allclose(got.reshape(64, 64), want, rtol=2e-4,
                               atol=2e-5)
    counts = sown["intermediates"]
    assert float(counts["moe_local_count"][0]) == float(local) > 0
    assert 0 < float(counts["moe_max_group_count"][0]) <= float(local)
    grad = jax.grad(lambda h: jnp.sum(layer.apply({"params": params}, h)
                                      ** 2))(h)
    grad_ref = jax.grad(lambda h: jnp.sum(ref.expert_layer(
        h.reshape(64, 64), w, cfg)[0] ** 2))(h)
    np.testing.assert_allclose(grad, grad_ref, rtol=2e-3, atol=2e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts in 4 shares of 4: the four chips' routed parts, with the
    shared expert counted once, are what the uncut reference gives for the
    whole layer; so are the program's four layers."""
    cfg = _cfg()
    s = ref.sizes(cfg)
    assert (s["experts"], s["count"]) == (16, 4)
    whole = _cfg(experts_held={"first": 0, "count": 16})
    key = ref.seed_key(21)
    w = ref.draw_layer(whole, key, 1)
    h = jax.random.normal(jax.random.PRNGKey(7), (64, 64), jnp.float32)
    uncut, local = ref.expert_layer(h, w, whole)
    assert int(local) == 64 * s["top_k"]          # every assignment is held
    shared = ref._swiglu(ref.make_ein(""), h, w["shared_gate"],
                         w["shared_up"], w["shared_down"])
    cut = lambda t, first: t[first:first + 4]               # noqa: E731
    parts, program_parts, held = [], [], 0
    for first in (0, 4, 8, 12):
        share = {**w, **{k: cut(w[k], first) for k in (
            "experts_gate", "experts_up", "experts_down")}}
        part, n = ref.expert_layer(h, share, cfg, held=(first, 4))
        parts.append(part - shared)
        held += int(n)
        layer = ExpertShareMLP(64, s["moe"], 16, s["top_k"], first=first,
                               count=4, shared_hidden=s["moe"],
                               routed_scale=s["route_scale"])
        params = bind._block(share)["ffn"]
        program_parts.append(
            layer.apply({"params": params}, h[None])[0] - shared)
    assert held == 64 * s["top_k"]
    np.testing.assert_allclose(sum(parts) + shared, uncut, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(sum(program_parts) + shared, uncut,
                               rtol=2e-4, atol=2e-5)


def test_model_logits_and_lora_gradients_match_the_reference():
    """3 layers (one dense, two with experts), the loss's gradient to every
    adapter leaf under the program's wire names."""
    import optax
    cfg = _cfg()
    module, variables, trainable, frozen = _program_and_reference(cfg)
    tokens = jnp.asarray(np.random.default_rng(8).integers(
        0, 256, (2, 32)), jnp.int32)
    targets = jnp.roll(tokens, -1, 1)
    np.testing.assert_allclose(module.apply(variables, tokens),
                               ref.logits(frozen, trainable, tokens, cfg),
                               rtol=2e-4, atol=2e-4)

    def program_loss(params):
        return optax.softmax_cross_entropy_with_integer_labels(
            module.apply({"params": params}, tokens), targets).mean()

    got = _named({"params": jax.grad(program_loss)(variables["params"])})
    want = bind.by_program_name(jax.grad(
        lambda t: ref.loss(t, frozen, tokens, targets, cfg))(trainable))
    assert len(want) == 3 * 8
    for name, value in want.items():
        np.testing.assert_allclose(got[name], value, rtol=5e-3, atol=1e-7)
    # the frozen leaves' names: everything the reference draws is placed
    assert set(_named(variables)) == set(_named(jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), tokens))))


def test_shipped_subset_and_wire_names_agree():
    cfg = _cfg()
    shipped = _named(bind.shipped_host(cfg, 3))
    program = _named(bind.variables(cfg, 3))
    assert {n for n in program if "lora_" in n} == set(shipped)
    assert all(np.array_equal(program[n], shipped[n]) for n in shipped)
    assert set(bind.by_program_name(ref.lora_host(cfg, 3))) == set(shipped)


def test_the_base_is_held_in_the_type_the_configuration_states():
    """bfloat16 for every frozen matrix and the embedding; float32 for the
    norm scales, the router and its bias, the head and the adapters."""
    cfg = spec.cell(CELL, rehearse=True)["cfg"]
    for name, leaf in _named(bind.variables(cfg, 3)).items():
        narrow = not any(part in name for part in (
            "Norm", "norm", "router", "e_score_correction_bias", "lm_head",
            "lora_"))
        assert leaf.dtype == (jnp.bfloat16 if narrow else np.float32), name
    module = bind.build_module(cfg)
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    # what the module itself would make (but the adapters, which flax
    # draws in float64 under this suite's x64)
    assert {n: a.dtype for n, a in _named_shapes(shapes).items()
            if "lora_" not in n} == {
        n: a.dtype for n, a in _named(bind.variables(cfg, 3)).items()
        if "lora_" not in n}


def _named_shapes(tree):
    from metisfl_tpu.tensor.pytree import _key_to_name
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {_key_to_name(path): leaf for path, leaf in flat}


# --------------------------------------------------------------------- #
# decoding through the latent cache
# --------------------------------------------------------------------- #

def test_prefill_then_cached_decode_matches_the_full_forward_pass():
    cfg = _cfg()
    module, variables, _, _ = _program_and_reference(cfg)
    tokens = jnp.asarray(np.random.default_rng(9).integers(
        0, 256, (2, 24)), jnp.int32)
    full = module.apply(variables, tokens)
    caches = module.init_cache(2, 32)
    assert module.cache_kinds() == ("kv",) * 3
    s = ref.sizes(cfg)
    assert [c.shape for c in caches[0]] == [(2, 32, s["kvr"]),
                                            (2, 32, s["rope"])]
    logits, caches = module.apply(variables, tokens[:, :16], caches=caches,
                                  position=0)
    steps = [logits]
    for t in range(16, 24):
        logits, caches = module.apply(variables, tokens[:, t:t + 1],
                                      caches=caches, position=t)
        steps.append(logits)
    np.testing.assert_allclose(jnp.concatenate(steps, 1), full, rtol=2e-4,
                               atol=2e-4)


# --------------------------------------------------------------------- #
# FLOPs, against XLA's own count
# --------------------------------------------------------------------- #

def test_mla_moe_flops_against_cost_analysis():
    """``lib/flops_mla_moe.py`` against XLA's count at toy depth, every
    block dense (XLA counts the plain path's grouped products over all the
    static rows and every group; the routed layer's count is checked by
    hand below): XLA counts the whole score matrix and the elementwise
    work, so the benchmark's count may not pass it."""
    cfg = _cfg(first_k_dense_replace=3)
    module = bind.build_module(cfg)
    x = jnp.zeros((2, 64), jnp.int32)
    shapes = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), x))
    cost = jax.jit(lambda v, t: module.apply(v, t)).lower(
        shapes, x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    got = flops_mla_moe.forward_flops(cfg, 2, 64)
    assert 0.4 * float(cost["flops"]) <= got <= float(cost["flops"])
    full = spec.cell(CELL)
    shape = full["traffic"]["shape"]
    assert flops_mla_moe.forward_flops(full["cfg"], 1, 4096) == \
        pytest.approx(13.85e12, rel=0.01)           # ISSUE: 13.85 TFLOP
    assert flops_mla_moe.train_step_flops(full["cfg"], shape) == \
        pytest.approx(27.7e12, rel=0.01)
    assert flops_mla_moe.expected_local(full["cfg"], 4096) == 1024
    # a token in an expert layer: the router, the shared expert and a
    # quarter of a routed one (8 x 12 / 384)
    assert flops_mla_moe.expert_layer_flops_per_token(full["cfg"]) == \
        pytest.approx(2 * (7168 * 384 + 1.25 * 3 * 7168 * 2048))
    moe = flops_mla_moe.moe_experts_cost(full["cfg"], 5 * 1024, remat=True)
    # the experts' matrices, read once a pass, bind the products
    assert moe["bytes"] / 819e9 > 2 * moe["flops"] / 197e12
    assert moe["bytes"] == pytest.approx(3 * 5 * 1.057e9, rel=0.05)
    flash = flops_mla_moe.mla_flash_cost(full["cfg"], shape, remat=True)
    assert flash["flops"] / 197e12 > flash["bytes"] / 819e9


# --------------------------------------------------------------------- #
# one federated LoRA round through DriverSession
# --------------------------------------------------------------------- #

def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_a_train_task_leaves_every_frozen_leaf_its_type_and_buffer():
    """Through the learner: a frozen leaf that arrives in bfloat16 stays
    bfloat16, on the device buffer it had, from the second task on."""
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.models import ArrayDataset, FlaxModelOps
    cfg = spec.cell(CELL, rehearse=True)["cfg"]
    module = bind.build_module(cfg)
    rng = np.random.default_rng(10)
    x = rng.integers(0, 256, (8, 32)).astype(np.int32)
    # buffers the device owns (a host array placed on the CPU backend is
    # borrowed, and a borrowed buffer cannot be donated)
    ops = FlaxModelOps(module, x[:2], variables=bind.variables(cfg, 3),
                       trainable_regex="lora_")
    frozen = ops.frozen_names()
    assert frozen and not any("lora_" in n for n in frozen)
    where = lambda: {n: (leaf.dtype, leaf.unsafe_buffer_pointer())  # noqa
                     for n, leaf in ops._named_leaves()[0] if n in frozen}
    before = where()
    out = ops.train(ArrayDataset(x, np.roll(x, -1, 1), seed=0),
                    TrainParams(batch_size=2, local_steps=4, scan_chunk=4,
                                optimizer="adam", learning_rate=1e-3))
    assert where() == before
    assert sum(1 for d, _ in before.values() if d == jnp.bfloat16) > 20
    # the module's counters, a step: two expert layers of 64 tokens
    assert set(out.counts) == {"moe_local_count", "moe_max_group_count"}
    assert 0 < out.counts["moe_max_group_count"] \
        <= out.counts["moe_local_count"] <= 2 * 64 * 4


def test_a_federated_lora_round_ships_adapters_and_keeps_the_base(tmp_path):
    """Two rounds of the cell's own recipe at toy widths, through
    ``DriverSession`` -> ``Learner`` -> ``FlaxModelOps.train``: the
    community model holds ``lora_`` leaves alone, from the second round on
    the learner keeps the bfloat16 base on the device (``kept_bytes``), and
    the routed layers' counters arrive in the round's profile."""
    from benchmark.lib.recipes import Recipe
    from metisfl_tpu import perf
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
    assert profile["learners"][lid]["task_bytes"] == {
        "placed_bytes": shipped, "kept_bytes": sum(sizes.values()) - shipped,
        "read_bytes": shipped}
    # the base counted at 2 bytes a frozen matrix value, not 4
    narrow = sum(v for n, v in sizes.items() if "kernel" in n
                 and "lora_" not in n and "router" not in n
                 and "lm_head" not in n)
    assert narrow == 2 * sum(
        int(np.prod(a.shape))
        for n, a in _named(bind.variables(cfg, 9)).items()
        if "kernel" in n and "lora_" not in n and "router" not in n
        and "lm_head" not in n)
    device = profile["learners"][lid]["device"]
    tokens = shape["batch"] * shape["seq"]
    assert 0 < device["moe_max_group_count"] <= device["moe_local_count"] \
        <= 2 * tokens * cfg["num_experts_per_tok"]
    assert device["ms_per_step"] > 0
    assert "counts " + lid in perf.render_waterfall([profile])
    assert all(np.isfinite(v["loss"])
               for m in done for v in m["train_metrics"].values())


# --------------------------------------------------------------------- #
# the benchmark's new readers on hand-made contexts
# --------------------------------------------------------------------- #

def _ctx(kernel_ops_s, ops_s=None, local=5000.0):
    cell = spec.cell(CELL)
    device = {"ms_per_step": 400.0}
    if local is not None:
        device["moe_local_count"] = local
    rounds = [{"profile": {"learners": {"L0": {"device": device}}}}]
    return {"cell": cell, "cfg": cell["cfg"], "traffic": cell["traffic"],
            "rounds": rounds, "learner": "L0", "device_kind": "TPU v5 lite",
            "trace": {"busy_s": 4.0, "window_s": 5.0,
                      "module_runs": {"jit_train_scan_steps": 1.0},
                      "kernel_ops_s": kernel_ops_s, "ops_s": ops_s or {}}}


def test_new_readers_read_their_operations_and_nothing_else():
    from benchmark.metrics import (mla_flash_roofline, mla_moe_step_mfu,
                                   moe_experts_roofline, moe_experts_share)
    cell = spec.cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    new = {"mla_moe_step_mfu", "mla_flash_roofline", "moe_experts_roofline",
           "moe_experts_share"}
    assert new <= names
    assert not {"train_step_mfu", "flash_roofline", "hybrid_step_mfu",
                "ssm_scan_share"} & names
    for other in ("internlm2-1.8b.lora-round", "jamba2-3b.lora-round"):
        assert not new & {m["name"] for m in spec.cell(other)["per_layer"]}
    assert cell["cfg"]["program"]["trace_ops"] == ["moe_gmm_fwd",
                                                  "moe_gmm_bwd"]
    kernels = {"flash_fwd": 0.5, "flash_bwd_dq": 0.4, "flash_bwd_dkv": 0.6,
               "moe_gmm_fwd": 0.2, "moe_gmm_bwd": 0.1, "ssm_scan_fwd": 9.0}
    ctx = _ctx(kernels, ops_s={**kernels, "fusion": 1.0})
    shape = cell["traffic"]["shape"]
    assert moe_experts_share.read(ctx) == pytest.approx(100 * 0.3 / 4.0)
    moe = flops_mla_moe.moe_experts_cost(cell["cfg"], 5000.0, remat=True)
    assert moe_experts_roofline.read(ctx) == pytest.approx(
        100 * 8 * moe["bytes"] / 819e9 / 0.3)
    flash = flops_mla_moe.mla_flash_cost(cell["cfg"], shape, remat=True)
    assert mla_flash_roofline.read(ctx) == pytest.approx(
        100 * 8 * flash["flops"] / 197e12 / 1.5)
    work = flops_mla_moe.train_step_flops(cell["cfg"], shape)
    assert mla_moe_step_mfu.read(ctx) == pytest.approx(
        100 * work / 0.4 / 197e12)
    for reader in (moe_experts_share, moe_experts_roofline,
                   mla_flash_roofline, mla_moe_step_mfu):
        assert 0 < reader.read(ctx) < 100
    # the same work under another implementation's name, found among the
    # XLA operations
    cfg = {**cell["cfg"], "program": {**cell["cfg"]["program"],
                                      "trace_ops": ["ragged-dot"]}}
    plain = {**_ctx({"flash_fwd": 0.5}, ops_s={"ragged-dot": 0.4,
                                               "fusion": 1.0}), "cfg": cfg}
    assert moe_experts_share.read(plain) == pytest.approx(100 * 0.4 / 4.0)
    # a program without the kernels or the counter (the parent): nothing,
    # and no raise
    quiet = _ctx({"ssm_scan_fwd": 0.4}, local=None)
    assert moe_experts_share.read(quiet) is None
    assert moe_experts_roofline.read(quiet) is None
    assert mla_flash_roofline.read(quiet) is None
    assert moe_experts_roofline.read(_ctx(kernels, local=None)) is None
    assert moe_experts_share.read({"trace": None, "cfg": cell["cfg"]}) is None
    assert mla_flash_roofline.read({"trace": None}) is None


def test_configuration_file_keeps_the_catalog_row():
    cfg = spec.cell(CELL)["cfg"]
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == "kimi-k2.7-code")
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["reduced"] == cfg["reduced"] == reduced
    assert entry["source"] == cfg["source"]
    assert cfg["published"] == {"num_hidden_layers": 61,
                                "n_routed_experts": 384,
                                "vocab_size": 163840}
    # the floors of a cut: 4 expert layers, 8 experts, 1/8 of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] == cfg["experts_held"]["count"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    s = ref.sizes(cfg)
    assert (s["experts"], s["top_k"], s["count"]) == (384, 8, 12)
    for key in ("deployment", "assumed", "departures", "parameters"):
        assert cfg[key]
    assert "32 chips" in cfg["deployment"] and "1/32" in cfg["deployment"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-K2.7-Code")
    assert entry["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k, "?") != v}
    assert differs == set(reduced)
    assert {k: row["config"][k] for k in reduced} == cfg["published"]
