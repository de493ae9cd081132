"""The shortcut-connected decoder (``ScMoeLite``, ``ShortcutMoEBlock``,
``LatentAttention`` with its two latent scales, ``ExpertShareMLP`` with
softmax scores, unnormalised gates and zero-computation experts): the
scales and the rotary ladder against numbers worked by hand, the mixer,
the routed layer, one whole layer and the whole model against the
benchmark's plain reference on seeded weights, the shares of the experts
adding up to the uncut layer with the identity term counted once, prefill
and cached decode against the full forward pass, the counters against the
reference's own count, the latent family's program left as the parent had
it, one federated LoRA round that leaves the bfloat16 base where it was,
and the benchmark's new readers on hand-made contexts. CPU, tiny sizes;
the Pallas kernels run in interpret mode."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.bindings import scmoe as bind
from benchmark.lib import flops_scmoe, spec
from benchmark.reference import scmoe as ref
from metisfl_tpu.models.zoo import (TRANSFORMER_RULES, ExpertShareMLP,
                                    MlaMoeLite, ScMoeLite)
from metisfl_tpu.ops import grouped_matmul as gm

CELL = "longcat-flash-chat.lora-round"
TOKENS = 64         # 2 x 32, the toy batch below


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
    compiler is asked, or a program's text is compared with the parent's:
    there the program's own precision)."""
    if any(part in request.node.name
           for part in ("compile_for_the_chip", "parents_program")):
        yield
        return
    with jax.default_matmul_precision("highest"):
        yield


def _program_and_reference(cfg, seed=11):
    return (bind.build_module(cfg), bind.variables(cfg, seed),
            *ref.make_weights(cfg, seed))


def _named(tree):
    from metisfl_tpu.tensor.pytree import pytree_to_named_tensors
    return dict(pytree_to_named_tensors(jax.device_get(tree)))


def _tokens(seed, shape=(2, 32)):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 256, shape),
                       jnp.int32)


# --------------------------------------------------------------------- #
# the scales, the ladder and the bias's draw, from the row's constants
# --------------------------------------------------------------------- #

def test_latent_scales_and_rotary_ladder_by_hand():
    """s_q = (6144 / 1536)^0.5 = 2, s_kv = (6144 / 512)^0.5 = 12^0.5 =
    3.4641; 32 rotary frequencies 1e7^(-i/32), no scaling; the softmax
    scale 192^-0.5 = 0.0721688; b's sigma a tenth of (e - 1)^0.5 / 768."""
    full = spec.cell(CELL)["cfg"]
    assert ref.latent_scales(full) == pytest.approx((2.0, 3.4641016))
    mixer = bind.build_module(full)._mla()
    assert (mixer.q_scale, mixer.kv_scale) == pytest.approx(
        (2.0, 3.4641016))
    for freqs in (mixer.rotary_frequencies(), ref.rotary_frequencies(full)):
        np.testing.assert_allclose(freqs, 1e7 ** (-np.arange(32) / 32.0),
                                   rtol=1e-6)
    assert mixer.softmax_scale() == pytest.approx(0.0721688, rel=1e-6)
    assert ref.softmax_scale(full) == pytest.approx(0.0721688, rel=1e-6)
    assert ref.router_bias_std(full) == pytest.approx(1.7068e-4, rel=1e-4)
    plain = {**full, "mla_scale_q_lora": False, "mla_scale_kv_lora": False}
    off = bind.build_module(plain)._mla()
    assert (off.q_scale, off.kv_scale) == (1.0, 1.0)
    # the draw the scales stand for: the up-projections at the full-rank
    # fan-in, so that scaled q, k_nope and v come out at unit variance
    # (1536 / 6144 x 4 = 512 / 6144 x 12 = 1); lecun-normal without them
    drawn, lecun = ref.layer_shapes(full), ref.layer_shapes(plain)
    for i in (0, 1):
        assert drawn[f"q_b_{i}"][1] == drawn[f"kv_b_{i}"][1] == 6144 ** -0.5
        assert (lecun[f"q_b_{i}"][1], lecun[f"kv_b_{i}"][1]) == (
            1536 ** -0.5, 512 ** -0.5)
        assert drawn[f"q_a_{i}"][1] == drawn[f"kv_a_{i}"][1] == 6144 ** -0.5


# --------------------------------------------------------------------- #
# mixer, routed layer, one layer and the model against the reference
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("flash", [False, True], ids=["dense", "flash"])
def test_mixer_with_both_scales_matches_the_reference_mixer(flash):
    """Values and the gradients to the adapters and to the input; with the
    scales off on one side the outputs part."""
    cfg = _cfg()
    module, variables, trainable, frozen = _program_and_reference(cfg)
    mixer = module._mla().clone(use_flash=flash)
    params = variables["params"]["block_1"]["mla_1"]
    w = ref._mixer({**frozen["layers"][1], **trainable[1]}, 1)
    adapters = {k: v for k, v in w.items() if k.startswith("lora_")}
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 64), jnp.float32)
    weight = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 64))

    def program(params, h):
        return jnp.sum(mixer.apply({"params": params}, h) * weight)

    def reference(adapters, h):
        return jnp.sum(ref.mla(h, {**w, **adapters}, cfg) * weight)

    got = mixer.apply({"params": params}, h)
    np.testing.assert_allclose(got, ref.mla(h, w, cfg), rtol=2e-4,
                               atol=2e-5)
    plain = mixer.clone(q_scale=1.0, kv_scale=1.0).apply(
        {"params": params}, h)
    assert float(jnp.max(jnp.abs(plain - got))) > 0.05
    gp, gh = jax.grad(program, argnums=(0, 1))(params, h)
    gr, gh_ref = jax.grad(reference, argnums=(0, 1))(adapters, h)
    np.testing.assert_allclose(gh, gh_ref, rtol=2e-3, atol=2e-5)
    got = _named({"params": {"block_1": {"mla_1": gp}}})
    want = bind.by_program_name([{}, {
        f"lora_{p}_1_{f}": gr[f"lora_{p}_{f}"]
        for p in ref.LORA_ON for f in "ab"}])
    assert len(want) == 8
    for name, value in want.items():
        np.testing.assert_allclose(got[name], value, rtol=2e-3, atol=2e-6)


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["ragged_dot", "kernels"])
def test_routed_layer_matches_the_reference_loop(interpret):
    """Softmax scores over 24 columns, unnormalised gates times the
    scaling factor, 8 identity experts, 4 of 16 routed experts held:
    values, the three counters and the gradient to the input."""
    cfg = _cfg()
    module, variables, _, frozen = _program_and_reference(cfg)
    layer = module._moe().clone(gmm_interpret=interpret)
    assert (layer.score_func, layer.norm_topk, layer.zero_experts) == (
        "softmax", False, 8)
    params = variables["params"]["block_1"]["moe"]
    assert params["router"]["kernel"].shape == (64, 24)
    assert params["e_score_correction_bias"].shape == (24,)
    w = frozen["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 32, 64), jnp.float32)
    got, sown = layer.apply({"params": params}, h, mutable=["intermediates"])
    want, local, zero = ref.routed_layer(h.reshape(64, 64), w, cfg)
    np.testing.assert_allclose(got.reshape(64, 64), want, rtol=2e-4,
                               atol=2e-5)
    counts = sown["intermediates"]
    assert float(counts["moe_local_count"][0]) == float(local) > 0
    assert float(counts["moe_zero_count"][0]) == float(zero) > 0
    assert 0 < float(counts["moe_max_group_count"][0]) <= float(local)
    # the gates are the scores themselves, times the factor: not normalised
    chosen, gates = ref.route(h.reshape(64, 64), w, cfg)
    p = jax.nn.softmax(h.reshape(64, 64) @ w["router"], -1)
    np.testing.assert_allclose(
        gates, ref.sizes(cfg)["route_scale"]
        * jnp.take_along_axis(p, chosen, -1), rtol=1e-5)
    assert float(jnp.max(jnp.sum(gates, -1))) < ref.sizes(cfg)["route_scale"]
    grad = jax.grad(lambda h: jnp.sum(layer.apply({"params": params}, h)
                                      ** 2))(h)
    grad_ref = jax.grad(lambda h: jnp.sum(ref.routed_layer(
        h.reshape(64, 64), w, cfg)[0] ** 2))(h)
    np.testing.assert_allclose(grad, grad_ref, rtol=2e-3, atol=2e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """16 routed experts in 4 shares of 4: the four chips' routed parts,
    with the identity experts' term (which every chip computes alike)
    counted once, are what the uncut reference gives for the whole layer;
    so are the program's four layers. Every assignment falls somewhere:
    the four shares' counts and the identity count add up to T x top_k."""
    cfg = _cfg()
    s = ref.sizes(cfg)
    assert (s["experts"], s["count"], s["zero"]) == (16, 4, 8)
    whole = _cfg(experts_held={"first": 0, "count": 16})
    w = ref.draw_layer(whole, ref.seed_key(21), 1)
    h = jax.random.normal(jax.random.PRNGKey(7), (64, 64), jnp.float32)
    uncut, local, zero = ref.routed_layer(h, w, whole)
    assert int(local) + int(zero) == TOKENS * s["top_k"]
    identity = uncut - ref.routed_layer(h, w, whole, identity=False)[0]
    assert float(jnp.max(jnp.abs(identity))) > 1e-3
    cut = lambda t, first: t[first:first + 4]               # noqa: E731
    parts, program_parts, held = [], [], 0
    for first in (0, 4, 8, 12):
        share = {**w, **{k: cut(w[k], first) for k in (
            "experts_gate", "experts_up", "experts_down")}}
        part, n, z = ref.routed_layer(h, share, cfg, held=(first, 4))
        assert int(z) == int(zero)
        parts.append(part - identity)
        held += int(n)
        layer = ExpertShareMLP(64, s["moe"], 16, s["top_k"], first=first,
                               count=4, routed_scale=s["route_scale"],
                               score_func="softmax", norm_topk=False,
                               zero_experts=8)
        program_parts.append(layer.apply(
            {"params": bind._block(share)["moe"]}, h[None])[0] - identity)
    assert held == int(local)
    np.testing.assert_allclose(sum(parts) + identity, uncut, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(sum(program_parts) + identity, uncut,
                               rtol=2e-4, atol=2e-5)


def test_one_layer_joins_the_shortcut_at_the_end():
    """The program's layer against the reference's, and against a layer
    that adds the routed sum to ``x2`` (after the first sublayer, so that
    the second sublayer's attention and FFN see it): the first agrees, the
    second does not."""
    cfg = _cfg()
    module, variables, trainable, frozen = _program_and_reference(cfg)
    s = ref.sizes(cfg)
    w = {**frozen["layers"][0], **trainable[0]}
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 32, 64), jnp.float32)

    from metisfl_tpu.models.zoo import ShortcutMoEBlock
    block = ShortcutMoEBlock(s["d"], s["ffn"], module._mla(), module._mla(),
                             module._moe(), eps=s["eps"])
    got = block.apply({"params": variables["params"]["block_0"]}, x)
    want, _ = ref.layer(x, w, cfg)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def joined_early(x):
        norm = lambda name, t: ref._rms(t, w[name], s["eps"])  # noqa: E731
        x = x + ref.mla(norm("in_norm_0", x),
                        ref._mixer(w, 0), cfg)
        u = norm("post_norm_0", x).reshape(TOKENS, 64)
        m = ref.routed_layer(u, w, cfg)[0]
        x = x + (ref.swiglu(u, ref._sub(w, 0, ref.MLP_TENSORS))
                 + m).reshape(x.shape)
        x = x + ref.mla(norm("in_norm_1", x),
                        ref._mixer(w, 1), cfg)
        v = norm("post_norm_1", x).reshape(TOKENS, 64)
        return x + ref.swiglu(v, ref._sub(w, 1, ref.MLP_TENSORS)).reshape(
            x.shape)

    wrong = joined_early(x)
    assert float(jnp.max(jnp.abs(wrong - want))) > 100 * float(
        jnp.max(jnp.abs(got - want)))
    assert float(jnp.max(jnp.abs(wrong - want))) > 1e-2


def test_model_logits_and_lora_gradients_match_the_reference():
    """2 layers, the loss's gradient to every adapter leaf under the
    program's wire names (8 projections a layer, two factors each)."""
    import optax
    cfg = _cfg()
    module, variables, trainable, frozen = _program_and_reference(cfg)
    tokens = _tokens(8)
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
    assert len(want) == 2 * 16
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
    assert len(shipped) == 2 * 16
    full = spec.cell(CELL)["cfg"]
    rank = full["lora"]["rank"]
    per_sublayer = rank * sum(
        a + b for (a, b), _ in (ref.layer_shapes(full)[f"{p}_0"]
                                for p in ref.LORA_ON))
    assert per_sublayer == 721_920
    assert 2 * full["num_layers"] * per_sublayer == 5_775_360   # 23.1 MB


def _named_shapes(tree):
    from metisfl_tpu.tensor.pytree import _key_to_name
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {_key_to_name(path): leaf for path, leaf in flat}


def test_the_base_is_held_in_the_type_the_configuration_states():
    """bfloat16 for every frozen matrix and the embedding; float32 for the
    norm scales, the router and its bias, the head and the adapters."""
    cfg = spec.cell(CELL, rehearse=True)["cfg"]
    held = _named(bind.variables(cfg, 3))
    for name, leaf in held.items():
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
        n: a.dtype for n, a in held.items() if "lora_" not in n}


def test_stacked_experts_take_the_ep_axis_as_the_latent_familys_do():
    """``TRANSFORMER_RULES`` on the new model's names: the stacked experts
    on ``ep``, the up-projections and the dense FFNs on ``tp`` as the
    latent family's, the router, ``b`` and the norms whole."""
    from jax.sharding import Mesh

    from metisfl_tpu.parallel.sharding import tree_shardings
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("ep", "tp"))
    x = jnp.zeros((1, 8), jnp.int32)

    def specs(module):
        shapes = jax.eval_shape(
            lambda: module.init(jax.random.PRNGKey(0), x))
        flat = jax.tree_util.tree_flatten_with_path(
            tree_shardings(shapes, mesh, TRANSFORMER_RULES))[0]
        return {jax.tree_util.keystr(p): tuple(s.spec) for p, s in flat}

    new = specs(ScMoeLite(vocab_size=64, lora_rank=2, experts_count=4))
    old = specs(MlaMoeLite(vocab_size=64, lora_rank=2, experts_count=4))

    def pick(table, part):
        found = {v for k, v in table.items() if part in k}
        assert found, part
        return found

    for part in ("experts_gate", "experts_up", "experts_down",
                 "q_b_proj']['base", "kv_b_proj']['base", "o_proj",
                 "q_a_proj']['base", "router", "e_score_correction_bias",
                 "embedding", "lm_head"):
        assert pick(new, part) == pick(old, part), part
    assert pick(new, "experts_gate") == {("ep", None, "tp")}
    assert pick(new, "experts_down") == {("ep", "tp", None)}
    for i in (0, 1):
        assert pick(new, f"mlp_{i}']['gate") == {(None, "tp")}
        assert pick(new, f"mlp_{i}']['down") == {("tp", None)}
        assert pick(new, f"mla_{i}']['o_proj") == {("tp", None)}
    for part in ("input_norm_0", "post_norm_1", "router"):
        assert all(a is None for v in pick(new, part) for a in v)


# --------------------------------------------------------------------- #
# decoding through the two latent caches of a layer
# --------------------------------------------------------------------- #

def test_prefill_then_cached_decode_matches_the_full_forward_pass():
    cfg = _cfg()
    module, variables, _, _ = _program_and_reference(cfg)
    assert module._mla().kv_scale == pytest.approx(8 ** 0.5)    # scales on
    tokens = _tokens(9, (2, 24))
    full = module.apply(variables, tokens)
    caches = module.init_cache(2, 32)
    assert module.cache_kinds() == ("kv",) * 2
    s = ref.sizes(cfg)
    assert [[c.shape for c in sub] for sub in caches[0]] == [
        [(2, 32, s["kvr"]), (2, 32, s["rope"])]] * 2
    logits, caches = module.apply(variables, tokens[:, :16], caches=caches,
                                  position=0)
    steps = [logits]
    for t in range(16, 24):
        logits, caches = module.apply(variables, tokens[:, t:t + 1],
                                      caches=caches, position=t)
        steps.append(logits)
    np.testing.assert_allclose(jnp.concatenate(steps, 1), full, rtol=2e-4,
                               atol=2e-4)
    from metisfl_tpu.models.generate import cache_bytes_by_kind
    assert cache_bytes_by_kind(module, caches) == {
        "kv": 2 * 2 * 2 * 32 * (s["kvr"] + s["rope"]) * 4}


# --------------------------------------------------------------------- #
# the counters
# --------------------------------------------------------------------- #

def test_counters_add_up_to_every_assignment_and_agree_with_the_reference():
    """Through ``FlaxModelOps.train``: a step's ``moe_local_count`` and
    ``moe_zero_count`` with the assignments on experts held elsewhere are T
    x top_k x layers; with every expert held nothing is absent; the held
    share's counts are the reference's own."""
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.models import ArrayDataset, FlaxModelOps
    cfg = _cfg()
    s = ref.sizes(cfg)
    total = TOKENS * s["top_k"] * s["layers"]
    rng = np.random.default_rng(10)
    x = rng.integers(0, 256, (2, 32)).astype(np.int32)
    params = TrainParams(batch_size=2, local_steps=1, scan_chunk=1,
                         optimizer="sgd", learning_rate=0.0)

    def counts(cfg):
        ops = FlaxModelOps(bind.build_module(cfg), x,
                           variables=bind.variables(cfg, 5),
                           trainable_regex="lora_")
        out = ops.train(ArrayDataset(x, np.roll(x, -1, 1), seed=0), params)
        assert set(out.counts) == {"moe_local_count", "moe_max_group_count",
                                   "moe_zero_count"}
        return out.counts

    share = counts(cfg)
    trainable, frozen = ref.make_weights(cfg, 5)
    local, zero = ref.counts(frozen, trainable, jnp.asarray(x), cfg)
    assert (share["moe_local_count"], share["moe_zero_count"]) == (
        float(local), float(zero))
    absent = total - int(local) - int(zero)
    assert 0 < absent < total and 0 < int(local) and 0 < int(zero)
    whole = counts(_cfg(experts_held={"first": 0, "count": 16},
                        n_routed_experts=16))
    assert whole["moe_local_count"] + whole["moe_zero_count"] == total


# --------------------------------------------------------------------- #
# the latent family's program is the parent's
# --------------------------------------------------------------------- #

# sha256 of ``jax.jit(f).lower(...).as_text()`` (no source locations in
# it) of the two programs below, made on the parent commit f4c37ee under
# this suite's x64. An edit that changes what ``MlaMoeLite`` traces at its
# defaults changes them: make them again on the tree before the edit and
# say why they moved
_PARENT_FORWARD = \
    "6ccc42fe416418291f0ac6f94aef9e8817c60826101b7e59093c8382afc4ee22"
_PARENT_CACHED = \
    "f14ad6e25353a119074ea0c5180846f9d28b157ba0b89776550bd6ad7de21a05"


def test_defaults_trace_the_parents_program_on_the_latent_family():
    """``ExpertShareMLP`` (sigmoid scores, normalised gates, no
    zero-computation expert) and ``LatentAttention`` (both scales 1) at
    their defaults: ``MlaMoeLite``'s forward pass with its counters and its
    cached decode step lower to the text the parent's lowered to, so the
    outputs are the parent's bit for bit."""
    m = MlaMoeLite(vocab_size=64, dim=32, depth=2, heads=2, q_rank=8,
                   kv_rank=8, nope_dim=8, rope_dim=4, v_dim=8, moe_hidden=16,
                   num_experts=8, top_k=2, experts_count=4,
                   routed_scale=2.5, rope_factor=4.0,
                   rope_mscale_all_dim=1.0, lora_rank=2, dtype=jnp.bfloat16,
                   param_dtype=jnp.bfloat16)
    x = jnp.zeros((2, 16), jnp.int32)
    shapes = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0), x))
    digest = lambda text: hashlib.sha256(text.encode()).hexdigest()  # noqa
    forward = jax.jit(lambda v, t: m.apply(
        v, t, mutable=["intermediates"])).lower(shapes, x).as_text()
    cached = jax.jit(lambda v, t, c: m.apply(
        v, t, caches=c, position=3)).lower(
            shapes, x[:, :1], m.init_cache(2, 24)).as_text()
    if not jax.config.jax_enable_x64:
        pytest.skip("the digests were made under x64, as the suite runs")
    assert digest(forward) == _PARENT_FORWARD
    assert digest(cached) == _PARENT_CACHED
    # and the new fields do move the program when set
    layer = ExpertShareMLP(32, 16, 8, 2, count=4)
    h = jnp.zeros((1, 4, 32))
    text = lambda mod: jax.jit(lambda: mod.init_with_output(   # noqa: E731
        jax.random.PRNGKey(0), h)[0]).lower().as_text()
    assert text(layer) == text(layer.clone(score_func="sigmoid",
                                           norm_topk=True, zero_experts=0))
    for moved in (dict(score_func="softmax"), dict(norm_topk=False),
                  dict(zero_experts=4)):
        assert text(layer) != text(layer.clone(**moved))
    with pytest.raises(ValueError, match="score_func"):
        layer.clone(score_func="tanh").init(jax.random.PRNGKey(0), h)


# --------------------------------------------------------------------- #
# the chip's compiler on the routed layer at the cell's widths, no chip
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


def test_routed_layer_compile_for_the_chip_at_published_widths(one_chip):
    """16 groups of about 64 rows, 6144 x 2048, top-12 over 768 columns of
    which 256 compute nothing: the even-routing chunk is sized by the
    router's width (3,072 rows: 8 tiles of assignments and a tile of
    padding a group), not by the 512 routed experts."""
    from jax.experimental.compilation_cache import compilation_cache
    assert gm.chunk_rows(4096, 12, 16, 768) == 3072
    assert gm.chunk_rows(4096, 12, 16, 512) == 3584
    before = {k: getattr(jax.config, k)
              for k in ("jax_enable_x64", "jax_enable_compilation_cache")}
    for k in before:
        jax.config.update(k, False)
    compilation_cache.reset_cache()
    try:
        shape = lambda dtype, *s: jax.ShapeDtypeStruct(     # noqa: E731
            s, dtype, sharding=one_chip)
        bf = jnp.bfloat16

        def experts(x, chosen, gates, w_gate, w_up, w_down):
            return jnp.sum(gm.routed_experts(
                x, chosen, gates, w_gate, w_up, w_down, first=0,
                num_experts=768, interpret=False)[0].astype(jnp.float32))

        moe = jax.jit(jax.grad(experts)).lower(
            shape(bf, 4096, 6144), shape(jnp.int32, 4096, 12),
            shape(jnp.float32, 4096, 12), shape(bf, 16, 6144, 2048),
            shape(bf, 16, 6144, 2048), shape(bf, 16, 2048, 6144)).compile()
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert moe.as_text().count('custom_call_target="tpu_custom_call"') == 6
    assert moe.memory_analysis().temp_size_in_bytes < 1.5e9


# --------------------------------------------------------------------- #
# FLOPs, against XLA's own count
# --------------------------------------------------------------------- #

def test_scmoe_flops_against_cost_analysis():
    """``lib/flops_scmoe.py`` against XLA's count at toy depth, outside the
    routed layers (XLA counts the plain path's grouped products and
    one-hot sums over all the static rows, so the routed layer's own XLA
    count is taken out and its count is checked by hand below): XLA counts
    the whole score matrix and the elementwise work, so the benchmark's
    count may not pass it."""
    cfg = _cfg()
    s = ref.sizes(cfg)
    module = bind.build_module(cfg)
    x = jnp.zeros((2, 64), jnp.int32)

    def xla_flops(mod, arg):
        shapes = jax.eval_shape(lambda: mod.init(jax.random.PRNGKey(0), arg))
        cost = jax.jit(lambda v, t: mod.apply(v, t)).lower(
            shapes, arg).compile().cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        return float(cost["flops"])

    routed = xla_flops(module._moe().clone(parent=None),
                       jnp.zeros((2, 64, 64), jnp.float32))
    outside = xla_flops(module, x) - s["layers"] * routed
    router = 2.0 * 128 * s["d"] * (s["experts"] + s["zero"])
    got = (flops_scmoe.forward_flops(cfg, 2, 64, local=0.0, zero=0.0)
           - s["layers"] * router)
    assert 0.5 * outside <= got <= outside, (got, outside)
    full = spec.cell(CELL)
    shape = full["traffic"]["shape"]
    # ISSUE 35: 24.8 TFLOP forward: dense FFNs 14.8, latent attention 8.7,
    # the head 0.8, routed products and routers 0.3 + 0.15
    assert flops_scmoe.forward_flops(full["cfg"], 1, 4096) == pytest.approx(
        24.8e12, rel=0.01)
    assert 8 * 4096 * flops_scmoe.mla_flops_per_token(
        full["cfg"], 4097 / 2) == pytest.approx(8.7e12, rel=0.01)
    assert flops_scmoe.train_step_flops(full["cfg"], shape) == \
        pytest.approx(2 * flops_scmoe.forward_flops(full["cfg"], 1, 4096))
    assert flops_scmoe.expected_counts(full["cfg"], 4096) == (4096, 65536)
    # the counters' part: a held assignment is one expert's three
    # products, a zero-computation one a scale and an add over 6144
    base = flops_scmoe.forward_flops(full["cfg"], 1, 4096, 0.0, 0.0)
    assert flops_scmoe.forward_flops(full["cfg"], 1, 4096, 1000.0, 0.0) \
        - base == pytest.approx(1000 * 2 * 3 * 6144 * 2048)
    assert flops_scmoe.forward_flops(full["cfg"], 1, 4096, 0.0, 1000.0) \
        - base == pytest.approx(1000 * 2 * 6144)
    moe = flops_scmoe.experts_cost(full["cfg"], 4096.0, remat=True)
    # the experts' matrices, read once a pass, bind the products: 16 x
    # 37.75 M bfloat16 values a layer, 1.21 GB, four layers, three passes
    assert moe["bytes"] / 819e9 > 2 * moe["flops"] / 197e12
    assert moe["bytes"] == pytest.approx(3 * 4 * 1.208e9, rel=0.05)
    flash = flops_scmoe.flash_cost(full["cfg"], shape, remat=True)
    assert flash["flops"] / 197e12 > flash["bytes"] / 819e9
    from benchmark.lib import flops_mla_moe
    kimi = spec.cell("kimi-k2.7-code.lora-round")
    assert flash["flops"] == pytest.approx(8 / 6 * flops_mla_moe.mla_flash_cost(
        kimi["cfg"], kimi["traffic"]["shape"], remat=True)["flops"])


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
    community model holds ``lora_`` leaves alone, from the second round on
    the learner keeps the bfloat16 base on the device (``kept_bytes``), and
    the routed layers' three counters arrive in the round's profile and in
    ``perf``'s round view."""
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
    device = profile["learners"][lid]["device"]
    total = (shape["batch"] * shape["seq"] * cfg["moe_topk"]
             * cfg["num_layers"])
    assert 0 < device["moe_max_group_count"] <= device["moe_local_count"]
    assert 0 < device["moe_zero_count"]
    assert device["moe_local_count"] + device["moe_zero_count"] < total
    assert device["ms_per_step"] > 0
    view = perf.render_waterfall([profile])
    assert "counts " + lid in view
    assert "moe_local " in view and "moe_zero " in view
    assert all(np.isfinite(v["loss"])
               for m in done for v in m["train_metrics"].values())


# --------------------------------------------------------------------- #
# the benchmark's new readers on hand-made contexts
# --------------------------------------------------------------------- #

def _ctx(kernel_ops_s, ops_s=None, local=4100.0, zero=65000.0):
    cell = spec.cell(CELL)
    device = {"ms_per_step": 650.0}
    if local is not None:
        device["moe_local_count"] = local
    if zero is not None:
        device["moe_zero_count"] = zero
    rounds = [{"profile": {"learners": {"L0": {"device": device}}}}]
    return {"cell": cell, "cfg": cell["cfg"], "traffic": cell["traffic"],
            "rounds": rounds, "learner": "L0", "device_kind": "TPU v5 lite",
            "trace": {"busy_s": 5.0, "window_s": 5.5,
                      "module_runs": {"jit_train_scan_steps": 1.0},
                      "kernel_ops_s": kernel_ops_s, "ops_s": ops_s or {}}}


def test_new_readers_read_their_operations_and_nothing_else():
    from benchmark.metrics import (scmoe_experts_roofline,
                                   scmoe_experts_share,
                                   scmoe_flash_roofline, scmoe_step_mfu)
    cell = spec.cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    new = {"scmoe_step_mfu", "scmoe_flash_roofline",
           "scmoe_experts_roofline", "scmoe_experts_share"}
    assert new <= names and len(names) == 13 + 4
    assert {m["name"] for m in cell["end_to_end"]} == {"round_s", "setup_s"}
    # not the latent family's own four (their cost functions read that
    # family's keys), nor the other families'
    assert not {"mla_moe_step_mfu", "mla_flash_roofline",
                "moe_experts_roofline", "moe_experts_share",
                "train_step_mfu", "flash_roofline", "hybrid_step_mfu",
                "ssm_scan_share"} & names
    for other in ("internlm2-1.8b.lora-round", "jamba2-3b.lora-round",
                  "kimi-k2.7-code.lora-round"):
        assert not new & {m["name"] for m in spec.cell(other)["per_layer"]}
    assert cell["cfg"]["program"]["trace_ops"] == ["moe_gmm_fwd",
                                                  "moe_gmm_bwd"]
    kernels = {"flash_fwd": 0.5, "flash_bwd_dq": 0.3, "flash_bwd_dkv": 0.4,
               "moe_gmm_fwd": 0.2, "moe_gmm_bwd": 0.1, "ssm_scan_fwd": 9.0}
    ctx = _ctx(kernels, ops_s={**kernels, "fusion": 1.0})
    shape = cell["traffic"]["shape"]
    assert scmoe_experts_share.read(ctx) == pytest.approx(100 * 0.3 / 5.0)
    moe = flops_scmoe.experts_cost(cell["cfg"], 4100.0, remat=True)
    assert scmoe_experts_roofline.read(ctx) == pytest.approx(
        100 * 8 * moe["bytes"] / 819e9 / 0.3)
    flash = flops_scmoe.flash_cost(cell["cfg"], shape, remat=True)
    assert scmoe_flash_roofline.read(ctx) == pytest.approx(
        100 * 8 * flash["flops"] / 197e12 / 1.2)
    work = flops_scmoe.train_step_flops(cell["cfg"], shape, 4100.0, 65000.0)
    assert scmoe_step_mfu.read(ctx) == pytest.approx(
        100 * work / 0.65 / 197e12)
    # the counters are in the count: more held assignments, more work
    assert scmoe_step_mfu.read(_ctx(kernels, local=8200.0)) > \
        scmoe_step_mfu.read(ctx)
    for reader in (scmoe_experts_share, scmoe_experts_roofline,
                   scmoe_flash_roofline, scmoe_step_mfu):
        assert 0 < reader.read(ctx) < 100
    # the same work under another implementation's name, found among the
    # XLA operations
    cfg = {**cell["cfg"], "program": {**cell["cfg"]["program"],
                                      "trace_ops": ["ragged-dot"]}}
    plain = {**_ctx({"flash_fwd": 0.5}, ops_s={"ragged-dot": 0.4,
                                               "fusion": 1.0}), "cfg": cfg}
    assert scmoe_experts_share.read(plain) == pytest.approx(100 * 0.4 / 5.0)
    # a program without the kernels or the counters (the parent): nothing,
    # and no raise
    quiet = _ctx({"ssm_scan_fwd": 0.4}, local=None, zero=None)
    assert scmoe_experts_share.read(quiet) is None
    assert scmoe_experts_roofline.read(quiet) is None
    assert scmoe_flash_roofline.read(quiet) is None
    assert scmoe_step_mfu.read(quiet) is None
    assert scmoe_step_mfu.read(_ctx(kernels, zero=None)) is None
    assert scmoe_experts_roofline.read(_ctx(kernels, local=None)) is None
    assert scmoe_experts_share.read({"trace": None,
                                     "cfg": cell["cfg"]}) is None
    assert scmoe_flash_roofline.read({"trace": None}) is None


def test_configuration_file_keeps_the_catalog_row():
    cfg = spec.cell(CELL)["cfg"]
    bench = spec.benchmark()
    entry = next(c for c in bench["configs"]
                 if c["name"] == "longcat-flash-chat")
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (work["config"], work["traffic"], work["chips"]) == (
        "longcat-flash-chat", "lora-round", 1)
    reduced = ["num_layers", "n_routed_experts", "vocab_size"]
    assert entry["reduced"] == cfg["reduced"] == reduced
    assert entry["source"] == cfg["source"]
    assert cfg["published"] == {"num_layers": 28, "n_routed_experts": 512,
                                "vocab_size": 131072}
    # every published width unchanged
    assert [cfg[k] for k in (
        "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "ffn_hidden_size", "expert_ffn_hidden_size", "zero_expert_num",
        "moe_topk", "routed_scaling_factor")] == [
        6144, 64, 1536, 512, 128, 64, 128, 12288, 2048, 256, 12, 6]
    # the floors of a cut: 4 layers, 8 experts, 1/8 of the vocabulary
    assert cfg["num_layers"] >= 4
    assert cfg["n_routed_experts"] == cfg["experts_held"]["count"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    s = ref.sizes(cfg)
    assert (s["experts"], s["zero"], s["top_k"], s["count"]) == (
        512, 256, 12, 16)
    for key in ("deployment", "assumed", "departures", "parameters"):
        assert cfg[key]
    assert "32 chips" in cfg["deployment"] and "1/32" in cfg["deployment"]
    assert {"gates", "router product", "latent scales", "rotation",
            "e_score_correction_bias"} <= set(cfg["assumed"])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LongCat-Flash-Chat")
    assert entry["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k, "?") != v}
    assert differs == set(reduced)
    assert {k: row["config"][k] for k in reduced} == cfg["published"]


def test_the_benchmarks_entries_keep_the_form_the_driver_holds_them_to():
    """What the driver refuses before any run, for the entries of every
    configuration, cell and metric: a name of at most 64 letters, digits,
    ``_``, ``.`` and ``-``, a unit of at most 16, and a ``why``, a ``layer``
    and a ``source`` of 1 to 200 printable characters on one line (the
    first form of this cell's ``why`` had 209)."""
    import re
    bench = spec.benchmark()
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
    unit = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")

    def one_line(text):
        return 1 <= len(text) <= 200 and text.isprintable()

    for entry in bench["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert name.fullmatch(entry["name"]), entry["name"]
        assert one_line(entry["source"]) and one_line(entry["why"]), entry
        assert len(entry["reduced"]) <= 16
        assert all(name.fullmatch(k) for k in entry["reduced"])
    for work in bench["workloads"]:
        assert set(work) == {"name", "config", "traffic", "chips", "why"}
        assert name.fullmatch(work["name"]) and name.fullmatch(work["traffic"])
        assert one_line(work["why"]), (work["name"], len(work["why"]))
        assert work["chips"] in (1, 4)
    metrics = bench["end_to_end"] + bench["per_layer"]
    for metric in metrics:
        assert name.fullmatch(metric["name"]) and unit.fullmatch(metric["unit"])
        assert metric["better"] in ("lower", "higher")
        assert one_line(metric.get("layer", "-")), metric["name"]
    for names in ([m["name"] for m in metrics],
                  [w["name"] for w in bench["workloads"]],
                  [c["name"] for c in bench["configs"]]):
        assert len(names) == len(set(names))
