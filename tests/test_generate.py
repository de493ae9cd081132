"""KV-cache autoregressive decoding (models/generate.py).

The decode path must emit EXACTLY the tokens a full re-forward would pick
(the cache is an optimization, not an approximation), across MHA, GQA, and
LoRA configurations, honor eos/pad semantics, and run as one jitted
program.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metisfl_tpu.models import FlaxModelOps, generate
from metisfl_tpu.models.zoo import LlamaLite


def _oracle_greedy(module, variables, prompt, n):
    """Greedy decode by full re-forward over the growing sequence."""
    seq = np.asarray(prompt)
    out = []
    for _ in range(n):
        logits = module.apply(variables, jnp.asarray(seq))
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1), np.int32)
        out.append(nxt)
        seq = np.concatenate([seq, nxt[:, None]], axis=1)
    return np.stack(out, axis=1)


def _init(module, B=2, Lp=5, seed=0):
    rng = np.random.default_rng(seed)
    prompt = rng.integers(1, module.vocab_size, (B, Lp)).astype(np.int32)
    variables = module.init(jax.random.PRNGKey(seed), jnp.asarray(prompt))
    return variables, prompt


@pytest.mark.parametrize("kv_heads", [0, 1], ids=["mha", "gqa"])
def test_greedy_decode_matches_full_forward(kv_heads):
    module = LlamaLite(vocab_size=64, dim=32, depth=2, heads=4,
                       kv_heads=kv_heads)
    variables, prompt = _init(module)
    want = _oracle_greedy(module, variables, prompt, 6)
    got = generate(module, variables, prompt, 6)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_lora_module_decodes():
    module = LlamaLite(vocab_size=64, dim=32, depth=2, heads=4, lora_rank=4)
    variables, prompt = _init(module, seed=1)
    want = _oracle_greedy(module, variables, prompt, 4)
    got = generate(module, variables, prompt, 4)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_cache_longer_than_needed_is_equivalent():
    """A max_len larger than prompt+new tokens (server-style fixed cache)
    changes nothing: the causal mask hides the unwritten tail."""
    module = LlamaLite(vocab_size=64, dim=32, depth=2, heads=4)
    variables, prompt = _init(module, seed=2)
    tight = generate(module, variables, prompt, 5)
    loose = generate(module, variables, prompt, 5, max_len=64)
    np.testing.assert_array_equal(np.asarray(tight), np.asarray(loose))


def test_eos_rows_pad_after_stopping():
    """Force eos to be the first greedy pick: every later position in the
    row must be pad_id."""
    module = LlamaLite(vocab_size=16, dim=16, depth=1, heads=2)
    variables, prompt = _init(module, B=3, Lp=4, seed=3)
    first = np.asarray(generate(module, variables, prompt, 1))[:, 0]
    eos = int(first[0])
    out = np.asarray(generate(module, variables, prompt, 6, eos_id=eos,
                              pad_id=15))
    done = False
    for t in range(6):
        if done:
            assert out[0, t] == 15
        if out[0, t] == eos:
            done = True
    assert done and out[0, 0] == eos


def test_sampling_is_seeded_and_in_vocab():
    module = LlamaLite(vocab_size=32, dim=16, depth=1, heads=2)
    variables, prompt = _init(module, seed=4)
    kw = dict(temperature=0.8, top_k=5, rng=jax.random.PRNGKey(7))
    a = np.asarray(generate(module, variables, prompt, 8, **kw))
    b = np.asarray(generate(module, variables, prompt, 8, **kw))
    np.testing.assert_array_equal(a, b)
    assert a.shape == (2, 8) and (a >= 0).all() and (a < 32).all()
    # near-uniform sampling: different seeds must give different streams
    c = np.asarray(generate(module, variables, prompt, 8, temperature=50.0,
                            rng=jax.random.PRNGKey(8)))
    d = np.asarray(generate(module, variables, prompt, 8, temperature=50.0,
                            rng=jax.random.PRNGKey(9)))
    assert not np.array_equal(c, d)


def test_moe_and_bf16_decode_smoke():
    """MoE routing is capacity-dependent so no exact oracle; the decode
    must still run and emit in-vocab tokens under bf16 + GQA + MoE."""
    module = LlamaLite(vocab_size=32, dim=16, depth=2, heads=4, kv_heads=2,
                       moe_experts=2, dtype=jnp.bfloat16)
    variables, prompt = _init(module, seed=5)
    out = np.asarray(generate(module, variables, prompt, 4))
    assert out.shape == (2, 4) and (out >= 0).all() and (out < 32).all()


def test_model_ops_generate_wrapper():
    module = LlamaLite(vocab_size=64, dim=32, depth=2, heads=4)
    rng = np.random.default_rng(6)
    prompt = rng.integers(1, 64, (2, 5)).astype(np.int32)
    ops = FlaxModelOps(module, prompt[:1])
    want = _oracle_greedy(module, ops.variables, prompt, 4)
    got = ops.generate(prompt, 4)
    assert isinstance(got, np.ndarray)
    np.testing.assert_array_equal(got, want)


def test_repeat_calls_hit_compiled_cache():
    """Same (module, shapes, sampling) must reuse the compiled program —
    serving pays trace+compile once, not per request."""
    import importlib

    # the package re-exports the generate() function under the same name,
    # so attribute-style import would bind the function, not the module
    gen_mod = importlib.import_module("metisfl_tpu.models.generate")

    module = LlamaLite(vocab_size=32, dim=16, depth=1, heads=2)
    variables, prompt = _init(module, seed=8)
    gen_mod._COMPILED.clear()
    generate(module, variables, prompt, 3)
    assert len(gen_mod._COMPILED) == 1
    generate(module, variables, prompt, 3)
    assert len(gen_mod._COMPILED) == 1  # second call reused the entry
    generate(module, variables, prompt, 4)
    assert len(gen_mod._COMPILED) == 2  # different config compiles anew


def test_compiled_cache_is_bounded():
    import importlib

    gen_mod = importlib.import_module("metisfl_tpu.models.generate")
    module = LlamaLite(vocab_size=32, dim=16, depth=1, heads=2)
    variables, prompt = _init(module, seed=10)
    gen_mod._COMPILED.clear()
    old_max = gen_mod._COMPILED_MAX
    gen_mod._COMPILED_MAX = 2
    try:
        for n in (2, 3, 4):  # 3 distinct configs, bound 2
            generate(module, variables, prompt, n)
        assert len(gen_mod._COMPILED) == 2
        # the oldest (n=2) was evicted, the newest two remain
        kept = {k[4] for k in gen_mod._COMPILED}
        assert kept == {3, 4}
    finally:
        gen_mod._COMPILED_MAX = old_max


def test_ops_generate_advances_rng_between_sampled_calls():
    module = LlamaLite(vocab_size=32, dim=16, depth=1, heads=2)
    rng = np.random.default_rng(11)
    prompt = rng.integers(1, 32, (2, 5)).astype(np.int32)
    ops = FlaxModelOps(module, prompt[:1])
    train_rng_before = np.asarray(ops._rng)
    a = ops.generate(prompt, 8, temperature=50.0)
    b = ops.generate(prompt, 8, temperature=50.0)
    assert not np.array_equal(a, b)  # generation rng advanced
    # rng=None explicitly must behave like omitting it (kwargs forwarding)
    c = ops.generate(prompt, 8, temperature=50.0, rng=None)
    assert not np.array_equal(b, c)
    # ...without touching the TRAINING stream: dropout reproducibility
    # across learners must not depend on how much inference each served
    np.testing.assert_array_equal(np.asarray(ops._rng), train_rng_before)
    # greedy calls stay deterministic
    d = ops.generate(prompt, 8)
    e = ops.generate(prompt, 8)
    np.testing.assert_array_equal(d, e)


def test_zero_new_tokens_rejected():
    module = LlamaLite(vocab_size=32, dim=16, depth=1, heads=2)
    variables, prompt = _init(module, seed=9)
    with pytest.raises(ValueError, match="max_new_tokens"):
        generate(module, variables, prompt, 0)


def test_tp_sharded_engine_decodes_identically():
    """generate on a dp x tp mesh-sharded engine (the Llama-LoRA ladder
    config) emits the same tokens as a replicated engine: the jitted decode
    program consumes the sharded variables directly (GSPMD propagates their
    shardings), no gather-to-host needed."""
    from jax.sharding import Mesh

    from metisfl_tpu.models.zoo import TRANSFORMER_RULES

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "tp"))
    module = LlamaLite(vocab_size=64, dim=32, depth=2, heads=4, lora_rank=4)
    rng = np.random.default_rng(12)
    prompt = rng.integers(1, 64, (2, 5)).astype(np.int32)
    ops = FlaxModelOps(module, prompt[:1], mesh=mesh,
                       partition_rules=TRANSFORMER_RULES)
    sharded = ops.generate(prompt, 6)
    replicated = FlaxModelOps(
        module, prompt[:1],
        variables=jax.tree.map(np.asarray, ops.variables)).generate(prompt, 6)
    np.testing.assert_array_equal(sharded, replicated)


def test_training_params_unchanged_by_decode_support():
    """The cache mode reuses the module's own projections: a params tree
    init'd before the decode feature loads identically (no new params)."""
    module = LlamaLite(vocab_size=64, dim=32, depth=2, heads=4)
    variables, prompt = _init(module, seed=7)
    names = sorted(jax.tree_util.keystr(p)
                   for p, _ in jax.tree_util.tree_flatten_with_path(
                       variables)[0])
    assert not any("cache" in n for n in names)
    # and the plain forward is untouched by the new kwargs' default path
    logits = module.apply(variables, jnp.asarray(prompt))
    assert logits.shape == (2, 5, 64)


def test_top_p_nucleus_restricts_support():
    """top_p keeps exactly the smallest prefix whose mass reaches p: with
    probs [.6, .3, .05, .05] and p=.7, only tokens {0, 1} can be drawn."""
    import jax
    import jax.numpy as jnp

    from metisfl_tpu.models.generate import _sampler

    probs = jnp.asarray([[0.6, 0.3, 0.05, 0.05]], jnp.float32)
    logits = jnp.log(probs)
    sample = _sampler(temperature=1.0, top_k=0, top_p=0.7)
    draws = {int(sample(logits, jax.random.PRNGKey(i))[0])
             for i in range(64)}
    assert draws <= {0, 1} and draws, draws
    # p=0 / p=1: no truncation — all four tokens reachable
    free = _sampler(temperature=1.0, top_k=0, top_p=0.0)
    draws = {int(free(logits, jax.random.PRNGKey(i))[0])
             for i in range(256)}
    assert draws == {0, 1, 2, 3}


def test_generate_with_top_p_runs():
    import jax
    import numpy as np

    from metisfl_tpu.models.generate import generate
    from metisfl_tpu.models.zoo import LlamaLite

    module = LlamaLite(vocab_size=64, dim=32, depth=1, heads=4)
    prompt = np.ones((2, 4), np.int32)
    variables = module.init(jax.random.PRNGKey(0), prompt)
    out = generate(module, variables, prompt, 6, temperature=0.8,
                   top_p=0.9, rng=jax.random.PRNGKey(1))
    assert out.shape == (2, 6)
    assert ((0 <= np.asarray(out)) & (np.asarray(out) < 64)).all()


# --------------------------------------------------------------------- #
# the decode slots' cache is updated in place (SlotDecoder donates it)
# --------------------------------------------------------------------- #

FAMILIES = ("llama", "jamba")


def _toy(family):
    """(module, variables, a prompt) of a toy served family: ``kv`` alone
    or ``kv`` beside recurrent ``state``."""
    from metisfl_tpu.models.zoo import JambaLite

    if family == "llama":
        module = LlamaLite(vocab_size=61, dim=32, depth=2, heads=4,
                           kv_heads=2)
    else:
        module = JambaLite(vocab_size=61, dim=32, depth=4, heads=4,
                           kv_heads=1, ffn_dim=80, attn_period=2,
                           attn_offset=1, d_state=8, dt_rank=4, lora_rank=2)
    prompt = np.random.default_rng(1).integers(1, 61, (7,)).astype(np.int32)
    variables = module.init(jax.random.PRNGKey(0), jnp.asarray(prompt[None]))
    return module, variables, prompt


def _all_deleted(tree):
    return all(leaf.is_deleted() for leaf in jax.tree.leaves(tree))


@pytest.mark.parametrize("call", ["prefill", "step"])
@pytest.mark.parametrize("family", FAMILIES)
def test_slot_decoder_call_consumes_the_cache(family, call, recwarn):
    """Every leaf of the cache a call was given is gone after it (the
    program wrote into those buffers), whatever kind the leaf is, and JAX
    found every donated buffer usable."""
    from metisfl_tpu.models.generate import SlotDecoder

    module, variables, prompt = _toy(family)
    decoder = SlotDecoder(module, slots=2, max_len=32)
    tok = decoder.prefill(variables, 1, prompt)
    given = decoder.caches
    if call == "prefill":
        decoder.prefill(variables, 0, prompt[:3])
    else:
        decoder.step(variables, [0, tok], [0, len(prompt)])
    assert _all_deleted(given)
    assert not _all_deleted(decoder.caches)
    assert (decoder.donated_calls, decoder.cache_resets) == (2, 0)
    assert not [w for w in recwarn if "donated" in str(w.message)]


@pytest.mark.parametrize("how", ["deleted_under_it", "raised_after_taking"])
@pytest.mark.parametrize("family", FAMILIES)
def test_slot_decoder_starts_over_after_a_call_that_consumed(family, how):
    """A call that fails once the cache's buffers are gone leaves zeroed
    slots, not deleted arrays: the next occupant decodes what a solo
    ``generate`` decodes, bit for bit."""
    from metisfl_tpu.models.generate import SlotDecoder

    module, variables, prompt = _toy(family)
    decoder = SlotDecoder(module, slots=2, max_len=32)
    tok = decoder.prefill(variables, 1, prompt)
    if how == "deleted_under_it":
        for leaf in jax.tree.leaves(decoder.caches):
            leaf.delete()
    else:
        decoder.step(variables, [0, tok], [0, len(prompt)])  # builds it
        real = decoder._step_fn

        def fails_late(*args):
            real(*args)                  # the runtime took the buffers
            raise RuntimeError("the device gave up")

        decoder._step_fn = fails_late
    with pytest.raises(RuntimeError):
        decoder.step(variables, [0, tok], [0, len(prompt)])
    assert decoder.cache_resets == 1
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree.leaves(decoder.caches))
    assert not any(np.asarray(leaf).any()
                   for leaf in jax.tree.leaves(decoder.caches))
    if how == "raised_after_taking":
        decoder._step_fn = real
    tok = decoder.prefill(variables, 0, prompt)
    seq = [tok]
    for pos in range(len(prompt), len(prompt) + 5):
        tok = int(decoder.step(variables, [tok, 0], [pos, 0])[0])
        seq.append(tok)
    solo = generate(module, variables, prompt[None], 6, max_len=32)
    assert seq == [int(t) for t in solo[0]]
    assert decoder.cache_resets == 1


@pytest.mark.parametrize("family", FAMILIES)
def test_slot_decoder_error_before_the_call_resets_nothing(family):
    """A prompt of ``max_len`` is refused before any program runs: the
    cache is the very arrays it was, and nothing is counted."""
    from metisfl_tpu.models.generate import SlotDecoder

    module, variables, prompt = _toy(family)
    decoder = SlotDecoder(module, slots=2, max_len=32)
    decoder.prefill(variables, 0, prompt)
    held = decoder.caches
    with pytest.raises(ValueError):
        decoder.prefill(variables, 1, np.ones((32,), np.int32))
    assert all(a is b for a, b in zip(jax.tree.leaves(held),
                                      jax.tree.leaves(decoder.caches)))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(held))
    assert (decoder.donated_calls, decoder.cache_resets) == (1, 0)


@pytest.mark.parametrize("batched", ["all", "cache_and_new", "position"])
def test_write_kv_over_slots_is_the_batched_dynamic_update_slice(batched):
    """``_write_kv`` under ``vmap``: one write a slot on the slot-major
    array where cache, rows and position are all batched (a decoder's
    step), the stock rule otherwise; either way the values ``vmap`` of
    the plain ``dynamic_update_slice`` gives."""
    from metisfl_tpu.models.zoo.transformer import _write_kv

    rng = np.random.default_rng(3)
    cache = jnp.asarray(rng.normal(size=(3, 1, 2, 8, 4)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(3, 1, 2, 1, 4)), jnp.float32)
    position = jnp.asarray([5, 0, 7], jnp.int32)
    axes = {"all": (0, 0, 0), "cache_and_new": (0, 0, None),
            "position": (None, None, 0)}[batched]
    args = tuple(a if ax == 0 else a[0]
                 for a, ax in zip((cache, new, position), axes))
    got = jax.jit(jax.vmap(_write_kv, in_axes=axes))(*args)
    want = jax.vmap(_write_kv.fun, in_axes=axes)(*args)
    assert got.shape == want.shape == cache.shape
    assert jnp.array_equal(got, want)
    text = jax.jit(jax.vmap(_write_kv, in_axes=axes)).lower(*args).as_text()
    assert ("scatter" not in text) == (batched == "all")
