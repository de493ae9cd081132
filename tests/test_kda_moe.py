"""The GLM-5 hybrid (``KdaMoeLite``: ``DeltaMixer``, ``HyperConnection``,
``LatentAttention`` with no rotary columns and a pooling ``SparseIndexer``,
``ExpertShareMLP.swiglu_limit``): the model against the benchmark's plain
reference on seeded weights (logits, loss, adapter gradients, counters),
the shares of the experts adding up to the uncut layer, latent attention
without rotary columns, the parents' programs left as they were, the FLOP
count, the benchmark's new readers and the configuration and cell entries
against the published widths. CPU, tiny sizes."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.bindings import kda_moe as bind
from benchmark.lib import flops_kda_moe, spec
from benchmark.lib.precision import make_ein
from benchmark.reference import kda_moe as ref
from metisfl_tpu.models.ops import _sown_counts
from metisfl_tpu.models.zoo import ExpertShareMLP, LatentAttention
from metisfl_tpu.models.zoo.transformer import yarn_frequencies

CELL = "glm-5.3-flash.lora-round"
SEQ = 64


def _cfg(**over):
    """The cell's configuration at its toy widths, float32 throughout (the
    comparisons below are about the mathematics, not about bfloat16), two
    layers: KDA beside the dense FFN, sparse latent attention beside the
    routed layer."""
    cfg = dict(spec.cell(CELL, rehearse=True)["cfg"])
    cfg.update(compute_dtype="float32", num_hidden_layers=2,
               layers_kept=[0, 3],
               param_dtype={**cfg["param_dtype"], "frozen": "float32"},
               program={**cfg["program"], "remat": False})
    cfg.update(over)
    return cfg


def _named(tree):
    from metisfl_tpu.tensor.pytree import pytree_to_named_tensors
    return dict(pytree_to_named_tensors(jax.device_get(tree)))


# --------------------------------------------------------------------- #
# the model against the reference
# --------------------------------------------------------------------- #

def test_model_logits_loss_gradients_and_counters_match_the_reference():
    """Both layer kinds at toy widths under mHC, the plain paths, against
    the float32 reference: logits, loss, every adapter's gradient, and the
    counters against the reference's count of chosen pairs and held
    assignments."""
    cfg = _cfg()
    module = bind.build_module(cfg)
    variables = bind.variables(cfg, 11)
    lora, base = ref.make_weights(cfg, 11)
    x = jnp.asarray(np.random.default_rng(3).integers(0, 256, (1, 32)),
                    jnp.int32)
    y = jnp.roll(x, -1, 1)
    assert set(_named(variables)) == set(_named(jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), x))))

    def program_loss(params):
        lg, mutated = module.apply({"params": params}, x,
                                   mutable=["intermediates"])
        nll = -jnp.take_along_axis(jax.nn.log_softmax(lg, -1), y[..., None],
                                   -1)
        return jnp.mean(nll), (lg, _sown_counts(mutated["intermediates"]))

    def reference_loss(trainable, frozen):
        h, counted = ref.hidden(frozen, trainable, x, cfg)
        lg = make_ein("")("btd,dv->btv", h, frozen["lm_head"])
        nll = -jnp.take_along_axis(jax.nn.log_softmax(lg, -1), y[..., None],
                                   -1)
        return jnp.mean(nll), (lg, counted)

    with jax.default_matmul_precision("highest"):
        (loss, (got, counts)), grads = jax.jit(jax.value_and_grad(
            program_loss, has_aux=True))(variables["params"])
        (want_loss, (want, (local, chosen))), want_grads = jax.jit(
            jax.value_and_grad(reference_loss, has_aux=True))(lora, base)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert counts["dsa_selected_count"] == float(chosen)
    assert counts["moe_local_count"] == float(local)
    pool, topk = cfg["index_kpool"], cfg["index_topk"]
    assert flops_kda_moe.selected_pairs(32, topk, pool) <= float(chosen)
    got_g = _named({"params": grads})
    want_g = bind.by_program_name(want_grads)
    assert len(want_g) == 4 + 8 and set(want_g) <= set(got_g)
    for name, value in want_g.items():
        np.testing.assert_allclose(got_g[name], value, atol=1e-6,
                                   rtol=1e-4, err_msg=name)
        assert float(np.max(np.abs(value))) > 0, name


# --------------------------------------------------------------------- #
# the shares of the experts, the clamp, attention without rotary columns
# --------------------------------------------------------------------- #

def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts in 4 shares of 4, the clamp at 0.5 so that it bites: the
    four chips' parts, the program's and the reference's, with the shared
    expert (which every chip computes) counted once, add up to what the
    reference gives for the whole layer; the shares' counts add up to
    T x top_k."""
    cfg = _cfg(experts_held={"first": 0, "count": 16}, swiglu_limit=0.5)
    w = {k: v.astype(jnp.float32)
         for k, v in ref.draw_layer(cfg, ref.seed_key(2), 1).items()}
    h = jnp.asarray(np.random.default_rng(5).standard_normal((SEQ, 64)),
                    jnp.float32)
    no_shared = {**w, "shared_gate": w["shared_gate"] * 0}
    with jax.default_matmul_precision("highest"):
        whole, whole_n = ref.expert_layer(h, w, cfg)
        shared = ref._swiglu(make_ein(""), h, w["shared_gate"],
                             w["shared_up"], w["shared_down"], 0.5)
        parts, n = shared, 0
        for first in (0, 4, 8, 12):
            cut = {k: w[k][first:first + 4] for k in (
                "experts_gate", "experts_up", "experts_down")}
            part, count = ref.expert_layer(h, {**no_shared, **cut}, cfg,
                                           held=(first, 4))
            layer = ExpertShareMLP(64, 32, 16, 4, first=first, count=4,
                                   shared_hidden=32, routed_scale=2.5,
                                   swiglu_limit=0.5)
            params = bind._block({**w, **cut})["ffn"]
            program = layer.apply({"params": params}, h[None])[0]
            np.testing.assert_allclose(program, part + shared, atol=2e-5,
                                       rtol=1e-4)
            parts, n = parts + part, n + int(count)
        unclamped, _ = ref.expert_layer(h, w, _cfg(
            experts_held={"first": 0, "count": 16}, swiglu_limit=0.0))
    np.testing.assert_allclose(parts, whole, atol=2e-5, rtol=1e-4)
    assert n == int(whole_n) == SEQ * 4
    assert float(jnp.max(jnp.abs(unclamped - whole))) > 1e-3


def test_latent_attention_without_rotary_columns_is_plain_causal():
    """``rope_dim`` 0: no rotary frequencies, the keys the latent's
    up-projection alone, softmax over every causal key at ``nope^-1/2``;
    no cached path."""
    assert yarn_frequencies(0, 10000.0, 1.0, 4096).shape == (0,)
    layer = LatentAttention(32, 4, 16, 8, 16, 0, 16)
    h = jnp.asarray(np.random.default_rng(1).standard_normal((1, 12, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        params = layer.init(jax.random.PRNGKey(0), h)["params"]
        got = layer.apply({"params": params}, h)
        p = jax.tree.map(np.asarray, params)
        rms = lambda x, s: x / np.sqrt(np.mean(x * x, -1,  # noqa: E731
                                               keepdims=True) + 1e-6) * s
        c_q = rms(h @ p["q_a_proj"]["base"]["kernel"],
                  p["q_a_norm"]["scale"])
        q = (c_q @ p["q_b_proj"]["base"]["kernel"]).reshape(1, 12, 4, 16)
        c_kv = rms(h @ p["kv_a_proj_with_mqa"]["base"]["kernel"],
                   p["kv_a_norm"]["scale"])
        kv = (c_kv @ p["kv_b_proj"]["base"]["kernel"]).reshape(1, 12, 4, 32)
        s = np.einsum("bqhd,bkhd->bhqk", q, kv[..., :16]) / 4.0
        s = np.where(np.tril(np.ones((12, 12), bool)), s, -np.inf)
        a = np.exp(s - s.max(-1, keepdims=True))
        a = a / a.sum(-1, keepdims=True)
        o = np.einsum("bhqk,bkhd->bqhd", a, kv[..., 16:]).reshape(1, 12, 64)
        want = o @ p["o_proj"]["kernel"]
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)
    with pytest.raises(ValueError, match="no cached path"):
        layer.apply({"params": params}, h, cache=layer.init_cache(1, 16),
                    position=0)


# --------------------------------------------------------------------- #
# the parents' programs
# --------------------------------------------------------------------- #

# sha256 of ``jax.jit(jax.grad(...)).lower(...).as_text()`` (no source
# locations in it) of the gradient of a sum of the logits, made on the
# parent commit b56609b under this suite's x64, of each existing family's
# module as its binding builds it at the cell's toy widths, on the kernels
# (interpreted here): the fields this family added (``SwiGLU.limit``,
# ``ExpertShareMLP.swiglu_limit``, ``LatentAttention.indexer`` and no
# rotary columns, ``SparseIndexer``'s query input, rotary switch and key
# pool) trace nothing at their defaults
_PARENT = {
    "internlm2-1.8b.lora-round":
        "f794384686ba5503a84098426280abbd8408c012b078dc0faf410c29a4d76434",
    "jamba2-3b.lora-round":
        "ba2a9a0d933c0a91bb1fdd3938b5b7b1edfd387ce072134ffcde760c2ba8fbb6",
    "kimi-k2.7-code.lora-round":
        "219a9963e893932fe513c05b3190a961de975fd8defa7ad119dd7561d29e170a",
    "longcat-flash-chat.lora-round":
        "06cbd5e56a0bb8a0d2582e6ddf1d02aa159d0d6d5e0a1c37782cd773af759291",
    "keye-vl-2.0-30b-a3b.lora-round-16k":
        "b3432467d09736bf413fc59567d344490773bec9f8a00f3c8da2abc559dc6cc4",
}


@pytest.mark.parametrize("cell", sorted(_PARENT))
def test_defaults_trace_the_parents_train_programs(cell):
    if not jax.config.jax_enable_x64:
        pytest.skip("the digests were made under x64, as the suite runs")
    cfg = spec.cell(cell, rehearse=True)["cfg"]
    cfg = {**cfg, "program": {**cfg["program"], "use_flash": True}}
    module = spec.binding(cfg).build_module(cfg)
    x = jnp.zeros((1, SEQ), jnp.int32)
    shapes = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), x))
    text = jax.jit(jax.grad(lambda v, t: jnp.sum(module.apply(v, t).astype(
        jnp.float32)))).lower(shapes, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT[cell]


# --------------------------------------------------------------------- #
# FLOPs
# --------------------------------------------------------------------- #

def test_flops_at_the_cells_shape_and_against_cost_analysis():
    """At 4,096 tokens: 6,295,552 chosen pairs (query t takes min(its
    complete groups, 512) groups of 4 and its tail), 16,384 held
    assignments a step; 19.5 TFLOP a step, the hand reckoning; KDA's
    kernels bytes-bound at 6.19 GB a step (7.6 ms at the peak). At toy
    depth XLA's count of the plain path bounds the forward from above."""
    full = spec.cell(CELL)
    cfg, shape = full["cfg"], full["traffic"]["shape"]
    assert flops_kda_moe.selected_pairs(4096, 2048, 4) == 6_295_552
    assert flops_kda_moe.selected_pairs(8, 8, 4) == sum(
        min(t + 1, 8) for t in range(8))
    assert flops_kda_moe.expected_counts(cfg, 1, 4096) == (6_295_552,
                                                           16_384.0)
    assert flops_kda_moe.train_step_flops(cfg, shape) == pytest.approx(
        19.5e12, rel=0.01)
    kda = flops_kda_moe.kda_cost(cfg, shape, remat=True)
    assert kda["bytes"] == pytest.approx(6.19e9, rel=0.01)
    assert kda["bytes"] / 819e9 > kda["flops"] / 197e12
    small = _cfg()
    module = bind.build_module(small)
    x = jnp.zeros((1, SEQ), jnp.int32)
    shapes = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), x))
    cost = jax.jit(lambda v, t: module.apply(v, t)).lower(
        shapes, x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    ours = flops_kda_moe.forward_flops(small, 1, SEQ)
    assert 0.1 * float(cost["flops"]) <= ours["trained"] + ours["indexer"] \
        <= float(cost["flops"])


# --------------------------------------------------------------------- #
# the benchmark's new readers on hand-made contexts
# --------------------------------------------------------------------- #

def _ctx(kernel_ops_s, selected=6_300_000.0, local=16_400.0):
    cell = spec.cell(CELL)
    device = {"ms_per_step": 400.0}
    if selected is not None:
        device["dsa_selected_count"] = selected
    if local is not None:
        device["moe_local_count"] = local
    rounds = [{"profile": {"learners": {"L0": {"device": device}}}}]
    return {"cell": cell, "cfg": cell["cfg"], "traffic": cell["traffic"],
            "rounds": rounds, "learner": "L0", "device_kind": "TPU v5 lite",
            "trace": {"busy_s": 3.0, "window_s": 3.3,
                      "module_runs": {"jit_train_scan_steps": 1.0},
                      "kernel_ops_s": kernel_ops_s, "ops_s": {}}}


def test_new_readers_read_their_operations_and_nothing_else():
    from benchmark.metrics import kda_moe_step_mfu, kda_roofline, kda_share
    cell = spec.cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    new = {"kda_moe_step_mfu", "kda_roofline", "kda_share"}
    assert new | {"moe_experts_share"} <= names
    assert len(names) == 13 + 4
    assert {m["name"] for m in cell["end_to_end"]} == {"round_s", "setup_s"}
    for other in spec.benchmark()["workloads"]:
        if other["name"] != CELL:
            assert not new & {m["name"] for m in
                              spec.cell(other["name"])["per_layer"]}
    kernels = {"kda_fwd": 0.05, "kda_bwd": 0.07, "flash_fwd_sel": 0.2,
               "moe_gmm_fwd": 0.01}
    ctx = _ctx(kernels)
    shape = cell["traffic"]["shape"]
    assert kda_share.read(ctx) == pytest.approx(100 * 0.12 / 3.0)
    cost = flops_kda_moe.kda_cost(cell["cfg"], shape, remat=True)
    assert kda_roofline.read(ctx) == pytest.approx(
        100 * 8 * cost["bytes"] / 819e9 / 0.12)
    work = flops_kda_moe.train_step_flops(cell["cfg"], shape, 6_300_000.0,
                                          16_400.0)
    assert kda_moe_step_mfu.read(ctx) == pytest.approx(
        100 * work / 0.4 / 197e12)
    for reader in (kda_share, kda_roofline, kda_moe_step_mfu):
        assert 0 < reader.read(ctx) < 100
    # a program without the kernels or the counters (the parent): nothing,
    # and no raise
    quiet = _ctx({"flash_fwd": 0.4}, selected=None, local=None)
    for reader in (kda_share, kda_roofline, kda_moe_step_mfu):
        assert reader.read(quiet) is None
    assert kda_share.read({"trace": None, "cfg": cell["cfg"]}) is None


# --------------------------------------------------------------------- #
# the configuration and the cell
# --------------------------------------------------------------------- #

def test_configuration_keeps_the_published_widths_and_the_entries_form():
    cfg = spec.cell(CELL)["cfg"]
    bench = spec.benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "glm-5.3-flash")
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (work["config"], work["traffic"], work["chips"]) == (
        "glm-5.3-flash", "lora-round", 1)
    assert len(work["why"]) <= 200 and len(entry["why"]) <= 200
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["reduced"] == cfg["reduced"] == reduced
    assert entry["source"] == cfg["source"]
    assert cfg["published"] == {"num_hidden_layers": 45,
                                "n_routed_experts": 288,
                                "vocab_size": 154880}
    s = ref.sizes(cfg)
    assert (s["d"], s["heads"], s["hd"], s["conv"], s["lower"], s["qr"],
            s["kvr"], s["mla_heads"], s["nope"], s["qk"], s["vd"],
            s["index_heads"], s["index_dim"], s["index_top_k"], s["pool"],
            s["experts"], s["top_k"], s["moe"], s["ffn"], s["streams"],
            s["iters"]) == (4096, 64, 128, 4, -5.0, 1536, 512, 64, 256, 256,
                            256, 32, 128, 2048, 4, 288, 8, 2048, 12288, 4, 20)
    assert s["mixers"] == ("linear_attention", "deepseek_sparse_attention",
                           "linear_attention", "linear_attention",
                           "linear_attention")
    assert s["ffns"] == ("dense",) + ("sparse",) * 4
    # the floors of a cut: a whole period, 4 layers after the leading dense
    # ones, 8 experts, 1/8 of the vocabulary
    assert cfg["layers_kept"] == [0, 3, 4, 5, 6]
    assert cfg["n_routed_experts"] == cfg["experts_held"]["count"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert "8 chips" in cfg["deployment"] and "1/8" in cfg["deployment"]
    for key in ("kda gate", "kda gate rank", "mhc streams", "mhc draws",
                "indexer", "key pool", "clamp", "draws", "draw check"):
        assert cfg["assumed"][key], key
    assert cfg["draw_check"]
    assert any("vision tower" in d for d in cfg["departures"])
    assert any("multi-token prediction" in d for d in cfg["departures"])
    # 2,555,904 adapter parameters
    shapes = ref.layer_shapes(cfg)
    adapters = sum(int(np.prod(shapes[n][0])) for l in range(s["layers"])
                   for n in ref.lora_tensors(ref.kind(cfg, l)))
    assert adapters == 2_555_904
