"""The gateway casts a model's weights once at install (ISSUE 29): which
leaves the served programs let it hold in the compute type
(``models.generate.cast_once_dtypes``: read from one traced program, never
from a name; the decode call chooses what forward, prefill and step each
choose), that the served tree's logits equal the float32 tree's bit for
bit, the install through ``ServingGateway`` (tokens, what ``describe()``
and the ``serving.install`` span report, one copy of each weight, the
zero-drop swap), what an install costs by count (one trace a gateway, one
cast program an install, nothing awaited), and the check of a decoding
module's plain forward before its first ``predict`` is answered."""

import gc
import threading
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metisfl_tpu.config import ServingConfig, ServingDecodeConfig
from metisfl_tpu.models import FlaxModelOps
from metisfl_tpu.models.generate import (cast_once_dtypes, decode_call,
                                         generate)
from metisfl_tpu.serving import gateway as gateway_mod
from metisfl_tpu.models.zoo.transformer import JambaLite, LlamaLite
from metisfl_tpu.serving import ServingGateway
from metisfl_tpu.telemetry import trace as ttrace
from metisfl_tpu.tensor.pytree import pack_model, pytree_to_named_tensors

BF16 = np.dtype(jnp.bfloat16)
SAMPLE = np.zeros((1, 8), np.int32)


def _module(kind: str, remat: bool = False):
    sizes = dict(vocab_size=97, dim=32, depth=2, heads=4, kv_heads=2,
                 lora_rank=4, remat=remat)
    if kind == "jamba-bf16":
        return JambaLite(dtype=jnp.bfloat16, **sizes)
    return LlamaLite(dtype=jnp.bfloat16 if kind == "llama-bf16" else None,
                     **sizes)


KINDS = ("llama-bf16", "jamba-bf16", "llama-f32")

# leaves (by the end of their wire name) that a program uses in float32;
# every other leaf is used through a cast to the compute type alone
KEPT = {
    "llama-bf16": ("lm_head/kernel", "scale"),
    "jamba-bf16": ("embed/embedding", "x_proj/kernel", "dt_proj/kernel",
                   "dt_proj/bias", "A_log", "mamba/D", "conv_kernel",
                   "conv_bias", "scale"),
    "llama-f32": ("",),         # float32 compute: nothing to make once
}
# and some that have to be among the cast
CAST = {
    "llama-bf16": ("embed/embedding", "wq/base/kernel", "wk/base/kernel",
                   "wo/kernel", "mlp/down/kernel", "lora_a", "lora_b"),
    "jamba-bf16": ("in_proj/base/kernel", "out_proj/base/kernel",
                   "wq/base/kernel", "mlp/gate/kernel", "in_proj/lora_a",
                   "wv/lora_b"),
    "llama-f32": (),
}


def _variables(module, seed: int = 0):
    """Float32 variables on the host, adapters drawn non-zero."""
    tree = jax.device_get(module.init(jax.random.PRNGKey(seed),
                                      jnp.asarray(SAMPLE)))
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda a: (a if a.any() else
                   0.05 * rng.standard_normal(a.shape).astype(a.dtype)),
        tree)


def _programs(module):
    """name -> (program, args): the call the gateway traces for a module
    that decodes, and each program it then serves."""
    caches = jax.eval_shape(lambda: module.init_cache(1, 16))
    tokens = lambda n: jax.ShapeDtypeStruct((1, n), jnp.int32)  # noqa: E731

    def prefill(v, toks, caches):
        return module.apply(v, toks, caches=caches, position=0)

    def step(v, toks, caches, position):
        return module.apply(v, toks, caches=caches, position=position)

    return {
        "decode_call": decode_call(module),
        "forward": (lambda v, x: module.apply(v, x, train=False),
                    (SAMPLE,)),
        "prefill": (prefill, (tokens(6), caches)),
        "step": (step, (tokens(1), caches,
                        jax.ShapeDtypeStruct((), jnp.int32))),
    }


def _chosen(module, variables, program: str = "decode_call"):
    fn, args = _programs(module)[program]
    dtypes = cast_once_dtypes(fn, variables, *args)
    names = [n for n, _ in pytree_to_named_tensors(variables)]
    assert len(names) == len(dtypes)
    return dict(zip(names, dtypes))


def _served(variables, dtypes):
    """The tree as an install would hold it: the gateway's own cast."""
    leaves, treedef = jax.tree.flatten(variables)
    return jax.tree.unflatten(treedef, gateway_mod._cast(
        [jnp.asarray(leaf) for leaf in leaves], dtypes))


# ---------------------------------------------------------------------- #
# (a) the choice
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("kind", KINDS)
def test_the_programs_choose_the_leaves(kind):
    module = _module(kind)
    chosen = _chosen(module, _variables(module))
    for name, dtype in chosen.items():
        if name.endswith(KEPT[kind]):
            assert dtype is None, f"{name} is used in float32"
        else:
            assert dtype == BF16, f"{name} is only ever cast"
    for end in KEPT[kind] + CAST[kind]:
        assert any(name.endswith(end) for name in chosen), end


@pytest.mark.parametrize("program", ("forward", "prefill", "step"))
@pytest.mark.parametrize("kind,remat", [
    ("llama-bf16", True), ("llama-bf16", False), ("jamba-bf16", True),
    ("llama-f32", False)])
def test_the_one_decode_trace_chooses_what_each_program_chooses(
        kind, remat, program):
    """Prefill and step are the decode call at two token counts, and the
    plain forward of both zoo LMs treats its parameters alike: leaf for
    leaf the one trace an install makes stands for all three."""
    module = _module(kind, remat=remat)
    variables = _variables(module)
    assert _chosen(module, variables, program) == _chosen(module, variables)


# ---------------------------------------------------------------------- #
# (b) the served tree's logits are the float32 tree's
# ---------------------------------------------------------------------- #

def _logits(module, variables, program: str):
    tokens = jnp.asarray(np.arange(6, dtype=np.int32)[None] * 7 % 97)
    if program == "forward":
        return module.apply(variables, tokens, train=False)
    caches = module.init_cache(1, 16)
    logits, caches = module.apply(variables, tokens, caches=caches,
                                  position=0)
    if program == "prefill":
        return logits
    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    return module.apply(variables, nxt, caches=caches,
                        position=jnp.asarray(6, jnp.int32))[0]


@pytest.mark.parametrize("program", ("forward", "prefill", "step"))
@pytest.mark.parametrize("kind", KINDS)
def test_served_logits_equal_float32_logits_bit_for_bit(kind, program):
    module = _module(kind)
    variables = _variables(module)
    served = _served(variables, list(_chosen(module, variables).values()))
    ref = np.asarray(jax.jit(lambda v: _logits(module, v, program))(
        variables))
    out = np.asarray(jax.jit(lambda v: _logits(module, v, program))(served))
    assert ref.dtype == np.float32 and np.isfinite(ref).all()
    np.testing.assert_array_equal(out, ref)


# ---------------------------------------------------------------------- #
# (c) through ServingGateway.install
# ---------------------------------------------------------------------- #

def _gateway(module, variables, slots: int = 2, max_len: int = 48):
    ops = FlaxModelOps(module, SAMPLE, variables=variables)
    return ServingGateway(ops, ServingConfig(
        enabled=True, max_batch=2,
        decode=ServingDecodeConfig(slots=slots, max_len=max_len)))


@pytest.fixture
def ring():
    ttrace.configure(enabled=True, service="test", dir="")
    ttrace.configure_ring(8192)
    cursor = ttrace.spans_since(0)[1]
    yield lambda: ttrace.spans_since(cursor)[0]
    ttrace.configure(enabled=True, service="test", dir="")


@pytest.mark.parametrize("kind", KINDS)
def test_install_serves_the_float32_tokens_and_says_what_it_holds(kind,
                                                                  ring):
    module = _module(kind)
    variables = _variables(module)
    chosen = _chosen(module, variables)
    gw = _gateway(module, variables)
    gc.collect()
    before = {id(a) for a in jax.live_arrays()}
    try:
        gw.install("stable", 1, pack_model(variables))
        held_tree = gw._models["stable"][1]
        held = gw.describe()["weights"]["stable"]
        leaves = jax.tree.leaves(held_tree)
        n_cast = sum(d is not None for d in chosen.values())
        assert held["cast_leaves"] == n_cast
        assert held["kept_leaves"] == len(leaves) - n_cast
        assert held["cast_bytes"] + held["kept_bytes"] == sum(
            int(a.nbytes) for a in leaves)
        assert held["cast_bytes"] == sum(
            int(a.nbytes) for a in leaves if a.dtype == BF16)
        (span,) = [r for r in ring() if r["name"] == "serving.install"]
        assert {k: span["attrs"][k] for k in held} == held
        assert span["attrs"]["channel"] == "stable"
        # each weight once: of what the install left on the device, no
        # float32 array has the shape of a leaf that is held cast
        gc.collect()
        cast_shapes = {a.shape for a in leaves if a.dtype == BF16}
        kept_shapes = {a.shape for a in leaves if a.dtype != BF16}
        left = [a for a in jax.live_arrays() if id(a) not in before]
        assert not [a.shape for a in left if a.dtype == np.float32
                    and a.shape in cast_shapes - kept_shapes]
        # tokens are a solo generate's on the float32 variables
        prompt = np.array([3, 5, 7, 11, 2], np.int32)
        tokens, version, _ = gw.generate(prompt, 10)
        ref = np.asarray(generate(module, variables, prompt[None], 10,
                                  max_len=48))[0]
        np.testing.assert_array_equal(tokens, ref)
        assert version == 1
        # and predict's logits the engine's own on the float32 tree
        x = (np.arange(16, dtype=np.int32).reshape(2, 8) * 5) % 97
        outs, _, _ = gw.predict(x)
        np.testing.assert_array_equal(
            outs, gw.model_ops.infer(x, batch_size=2, variables=variables))
    finally:
        gw.shutdown()


@pytest.mark.parametrize("kind", ("llama-bf16", "jamba-bf16"))
def test_hot_swap_mid_generation_finishes_on_the_captured_pair(kind):
    module = _module(kind)
    v1, v2 = _variables(module, 0), _variables(module, 1)
    gw = _gateway(module, v1, max_len=64)
    try:
        gw.install("stable", 1, pack_model(v1))
        a_prompt = np.array([3, 5, 7], np.int32)
        b_prompt = np.array([9, 4], np.int32)
        got = {}

        def long_one():
            got["a"] = gw.generate(a_prompt, 40)

        thread = threading.Thread(target=long_one)
        thread.start()
        deadline = time.time() + 60.0
        while time.time() < deadline and not (
                gw._decoders.get("stable") is not None
                and gw._decoders["stable"].steps >= 2):
            time.sleep(0.002)
        gw.install("stable", 2, pack_model(v2))     # mid-generation
        toks_b, ver_b, _ = gw.generate(b_prompt, 6)
        thread.join(timeout=120.0)
        toks_a, ver_a, _ = got["a"]
        assert (ver_a, ver_b) == (1, 2)
        np.testing.assert_array_equal(toks_a, np.asarray(generate(
            module, v1, a_prompt[None], 40, max_len=64))[0])
        np.testing.assert_array_equal(toks_b, np.asarray(generate(
            module, v2, b_prompt[None], 6, max_len=64))[0])
        assert gw.describe()["weights"]["stable"]["cast_leaves"] > 0
    finally:
        gw.shutdown()


def test_uninstall_forgets_what_the_channel_held():
    module = _module("llama-bf16")
    variables = _variables(module)
    gw = _gateway(module, variables)
    try:
        gw.install("candidate", 3, pack_model(variables))
        assert "candidate" in gw.describe()["weights"]
        gw.uninstall("candidate")
        assert gw.describe()["weights"] == {}
    finally:
        gw.shutdown()


# ---------------------------------------------------------------------- #
# (d) what the reader follows and what it does not
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("kind", ("llama-bf16", "jamba-bf16"))
def test_remat_is_followed_in_the_forward(kind):
    """With ``remat=True`` the plain forward wraps every block in
    ``nn.remat``: each block kernel is consumed by a ``remat2`` equation.
    The choice has to come out as without it, and as prefill and step
    (which never remat) alone would give."""
    plain, remat = _module(kind), _module(kind, remat=True)
    variables = _variables(plain)
    jaxpr = jax.make_jaxpr(
        lambda v: remat.apply(v, jnp.asarray(SAMPLE), train=False))(
            variables)
    assert any(e.primitive.name in ("remat2", "checkpoint")
               for e in jaxpr.jaxpr.eqns)
    assert (_chosen(remat, variables, "forward")
            == _chosen(plain, variables, "forward")
            == _chosen(plain, variables))
    assert any(n.endswith("mlp/up/kernel") and d == BF16
               for n, d in _chosen(remat, variables, "forward").items())


class _Toy(nn.Module):
    """``plain`` is only ever cast; ``w`` is cast too, but in a way the
    case names."""

    how: str

    @nn.compact
    def __call__(self, x, train: bool = False):
        bf = jnp.bfloat16
        w = self.param("w", nn.initializers.normal(1.0), (4, 4))
        plain = self.param("plain", nn.initializers.normal(1.0), (4, 4))
        x = x.astype(bf)
        y = x @ plain.astype(bf)
        if self.how == "jit":
            y = y + jax.jit(lambda x, w: x @ w.astype(bf))(x, w)
        elif self.how == "scan":
            y = y + jax.lax.scan(
                lambda c, _: (c @ w.astype(bf), None), x, None, length=2)[0]
        elif self.how == "custom_vjp":
            f = jax.custom_vjp(lambda x, w: x @ w.astype(bf))
            f.defvjp(lambda x, w: (x @ w.astype(bf), None),
                     lambda _, g: (g, None))
            y = y + f(x, w)
        elif self.how == "cond":
            y = y + jax.lax.cond(x.sum() > 0, lambda: x @ w.astype(bf),
                                 lambda: x)
        elif self.how == "two_types":
            y = y + x @ w.astype(bf) + (
                x.astype(jnp.float16) @ w.astype(jnp.float16)).astype(bf)
        elif self.how == "also_float32":
            y = y + x @ w.astype(bf) + (x.astype(jnp.float32) @ w).astype(bf)
        elif self.how == "unused":
            pass
        return y.astype(jnp.float32)


@pytest.mark.parametrize("how,w_cast", [
    ("jit", True), ("scan", False), ("custom_vjp", False), ("cond", False),
    ("two_types", False), ("also_float32", False), ("unused", False)])
def test_what_the_reader_follows(how, w_cast):
    module = _Toy(how)
    x = np.ones((2, 4), np.float32)
    variables = jax.device_get(module.init(jax.random.PRNGKey(0), x))
    names = [n for n, _ in pytree_to_named_tensors(variables)]
    chosen = dict(zip(names, cast_once_dtypes(
        lambda v, x: module.apply(v, x), variables, x)))
    assert chosen["params/plain"] == BF16
    assert chosen["params/w"] == (BF16 if w_cast else None)


# ---------------------------------------------------------------------- #
# (e) what an install costs, by count
# ---------------------------------------------------------------------- #

@pytest.fixture
def counts(monkeypatch):
    """Calls of ``jax.make_jaxpr`` (an abstract trace), of the gateway's
    cast (``_cast``: one an install) and of ``jax.block_until_ready``."""
    seen = {"traces": 0, "casts": 0, "cast_leaves": 0, "awaited": 0}

    def counting(fn, key, more=lambda *a: 0):
        def wrapped(*args, **kwargs):
            seen[key] += 1
            more(*args)
            return fn(*args, **kwargs)
        return wrapped

    def chosen(leaves, dtypes):
        seen["cast_leaves"] += sum(d is not None for d in dtypes)

    monkeypatch.setattr(jax, "make_jaxpr", counting(jax.make_jaxpr, "traces"))
    monkeypatch.setattr(jax, "block_until_ready",
                        counting(jax.block_until_ready, "awaited"))
    monkeypatch.setattr(gateway_mod, "_cast",
                        counting(gateway_mod._cast, "casts", chosen))
    return seen


class _Classifier(nn.Module):
    """No decode state: served through its forward alone."""

    dtype: object = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.Dense(16, dtype=self.dtype)(x)
        return nn.Dense(3, dtype=self.dtype)(nn.relu(x)).astype(jnp.float32)


def _classifier_gateway(dtype):
    module = _Classifier(dtype=dtype)
    x = np.ones((2, 4), np.float32)
    variables = jax.device_get(module.init(jax.random.PRNGKey(0), x))
    ops = FlaxModelOps(module, x, variables=variables)
    return ServingGateway(ops, ServingConfig(enabled=True, max_batch=2)), \
        variables


@pytest.mark.parametrize("kind", KINDS + ("classifier-bf16", "classifier-f32"))
def test_an_install_costs_one_trace_a_gateway_and_one_cast_an_install(
        kind, counts):
    if kind.startswith("classifier"):
        gw, variables = _classifier_gateway(
            jnp.bfloat16 if kind.endswith("bf16") else None)
    else:
        module = _module(kind)
        variables = _variables(module)
        gw = _gateway(module, variables)
    try:
        assert counts["traces"] == 0         # nothing before an install
        gw.install("stable", 1, pack_model(variables))
        assert (counts["traces"], counts["casts"]) == (1, 1)
        n_cast = gw.describe()["weights"]["stable"]["cast_leaves"]
        assert counts["cast_leaves"] == n_cast
        assert (n_cast > 0) == kind.endswith("bf16")
        # a hot-swap and a second channel: the placement and the cast only
        gw.install("stable", 2, pack_model(variables))
        gw.install("candidate", 3, pack_model(variables))
        assert (counts["traces"], counts["casts"]) == (1, 3)
        assert counts["cast_leaves"] == 3 * n_cast
        if not kind.startswith("classifier"):
            gw.generate(np.array([3, 5, 7], np.int32), 4)
            assert counts["traces"] == 1
        # nothing is awaited, leaf by leaf or at all
        assert counts["awaited"] == 0
    finally:
        gw.shutdown()


def test_a_classifier_is_read_from_its_forward_at_install(counts):
    """A module without a decode state has no later check to make: its
    first ``predict`` traces nothing, and answers as the float32 tree."""
    gw, variables = _classifier_gateway(jnp.bfloat16)
    try:
        gw.install("stable", 1, pack_model(variables))
        held = gw.describe()["weights"]["stable"]
        assert (held["cast_leaves"], held["kept_leaves"]) == (4, 0)
        x = np.random.default_rng(0).standard_normal((2, 4)).astype(
            np.float32)
        outs, _, _ = gw.predict(x)
        assert counts["traces"] == 1 and not gw._blobs
        np.testing.assert_array_equal(
            outs, gw.model_ops.infer(x, batch_size=2, variables=variables))
    finally:
        gw.shutdown()


# ---------------------------------------------------------------------- #
# (f) the plain forward of a module that also decodes
# ---------------------------------------------------------------------- #

class _TwoFaced(nn.Module):
    """Lays out a decode state, so an install reads its decode call, which
    only ever casts ``w``; its plain forward multiplies by ``w`` in
    float32."""

    @nn.compact
    def __call__(self, tokens, caches=None, position=0,
                 train: bool = False):
        bf = jnp.bfloat16
        normal = nn.initializers.normal(1.0, jnp.float32)
        emb = self.param("emb", normal, (97, 8))
        w = self.param("w", normal, (8, 8))
        head = self.param("head", normal, (8, 97))
        x = emb.astype(bf)[tokens]
        if caches is None:
            y = (x.astype(jnp.float32) @ w).astype(bf)
            return (y @ head.astype(bf)).astype(jnp.float32)
        y = x @ w.astype(bf)
        return (y @ head.astype(bf)).astype(jnp.float32), caches

    def init_cache(self, batch: int, max_len: int):
        return [(jnp.zeros((batch, 1), jnp.float32),)]

    def cache_kinds(self):
        return ["state"]


def _leaf(gw, name: str, channel: str = "stable"):
    return dict(pytree_to_named_tensors(gw._models[channel][1]))[name]


def test_a_leaf_the_forward_uses_in_float32_is_back_before_predict_answers(
        counts, ring):
    module = _TwoFaced()
    variables = jax.device_get(
        module.init(jax.random.PRNGKey(0), jnp.asarray(SAMPLE)))
    gw = _gateway(module, variables)
    x = (np.arange(16, dtype=np.int32).reshape(2, 8) * 5) % 97
    try:
        gw.install("stable", 1, pack_model(variables))
        gw.install("candidate", 2, pack_model(variables))
        for channel in ("stable", "candidate"):
            assert gw.describe()["weights"][channel]["cast_leaves"] == 3
            assert _leaf(gw, "params/w", channel).dtype == BF16
        assert counts["traces"] == 1 and set(gw._blobs) == {"stable",
                                                            "candidate"}
        # the cast the decode call allowed is not the forward's to use
        ref = gw.model_ops.infer(x, batch_size=2, variables=variables)
        assert not np.array_equal(ref, gw.model_ops.infer(
            x, batch_size=2, variables=gw._models["stable"][1]))
        outs, version, _ = gw.predict(x)
        np.testing.assert_array_equal(outs, ref)
        assert version == 1 and counts["traces"] == 2
        for channel in ("stable", "candidate"):
            held = gw.describe()["weights"][channel]
            assert (held["cast_leaves"], held["kept_leaves"]) == (2, 1)
            assert _leaf(gw, "params/w", channel).dtype == np.float32
            assert _leaf(gw, "params/emb", channel).dtype == BF16
        assert gw.installed() == {"stable": 1, "candidate": 2}
        assert not gw._blobs                 # the blobs were for this alone
        # one install a channel, then one more each: no swap among them
        installs = [r for r in ring() if r["name"] == "serving.install"]
        assert [r["attrs"]["cast_leaves"] for r in installs] == [3, 3, 2, 2]
        # later predicts and installs: the choice stands, nothing is traced
        gw.predict(x)
        gw.install("stable", 3, pack_model(variables))
        assert counts["traces"] == 2 and not gw._blobs
        assert _leaf(gw, "params/w").dtype == np.float32
    finally:
        gw.shutdown()


@pytest.mark.parametrize("kind", ("llama-bf16", "jamba-bf16"))
def test_where_forward_and_decode_agree_the_first_predict_changes_nothing(
        kind, counts):
    module = _module(kind)
    variables = _variables(module)
    gw = _gateway(module, variables)
    x = (np.arange(16, dtype=np.int32).reshape(2, 8) * 5) % 97
    try:
        gw.install("stable", 1, pack_model(variables))
        held_tree = gw._models["stable"][1]
        outs, _, _ = gw.predict(x)
        assert (counts["traces"], counts["casts"]) == (2, 1)
        assert gw._models["stable"][1] is held_tree and not gw._blobs
        np.testing.assert_array_equal(
            outs, gw.model_ops.infer(x, batch_size=2, variables=variables))
        gw.predict(x)
        assert counts["traces"] == 2
    finally:
        gw.shutdown()
