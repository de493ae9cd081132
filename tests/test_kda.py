"""Kimi Delta Attention's kernel family (``ops/delta_attention.py``: the
``kda_fwd`` / ``kda_bwd`` kernels in interpret mode and the plain chunked
twin) against the token-by-token recurrence, forward and gradients, at a
length of whole chunks and at one that is not; Sinkhorn's doubly
stochastic matrices; and the pooled choice of keys
(``sparse_attention.select_pooled``) against a plain top-k with its tail
rule. CPU, tiny sizes."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metisfl_tpu.models.zoo.transformer import sinkhorn
from metisfl_tpu.ops import delta_attention as da
from metisfl_tpu.ops import sparse_attention as sa


def _inputs(seed, L, B=1, H=2, D=16):
    """Unit q and k, a decay per channel over the whole of (-5, 0), beta in
    (0, 1): the ranges the mixer feeds the recurrence."""
    r = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = unit(r.standard_normal((B, H, L, D)))
    k = unit(r.standard_normal((B, H, L, D)))
    v = r.standard_normal((B, H, L, D))
    g = -5.0 / (1.0 + np.exp(-r.normal(-1.0, 2.0, (B, H, L, D))))
    beta = 1.0 / (1.0 + np.exp(-r.standard_normal((B, H, L))))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]


def _recurrence(q, k, v, g, beta, scale=None):
    """The recurrence token by token, float32: what the chunked forms are
    tested against."""
    hi = jax.lax.Precision.HIGHEST
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    steps = lambda x: jnp.moveaxis(x, 2, 0)                 # noqa: E731

    def step(s, xs):
        qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[..., None]
        kts = jnp.einsum("bhd,bhdv->bhv", kt, s, precision=hi)
        s = s + bt[..., None, None] * kt[..., None] * (vt - kts)[..., None, :]
        return s, scale * jnp.einsum("bhd,bhdv->bhv", qt, s, precision=hi)

    B, H, _, D = q.shape
    s0 = jnp.zeros((B, H, D, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, s0, tuple(map(steps, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 2)


def _with_grads(fn, args, seed=9):
    ct = jnp.asarray(np.random.default_rng(seed).standard_normal(
        args[2].shape), jnp.float32)
    out, pull = jax.vjp(fn, *args)
    return (out,) + pull(ct)


@pytest.mark.parametrize("path", ["kernels", "plain"])
@pytest.mark.parametrize("B,H,L", [(1, 2, 128), (1, 2, 100), (2, 6, 384),
                                   (2, 6, 1100)],
                         ids=["whole-chunks", "ragged", "two-head-groups",
                              "chunk-blocked-ragged"])
def test_chunked_forms_match_the_recurrence(path, B, H, L):
    """Output and the gradients of q, k, v, g and beta, against the
    recurrence run token by token: the kernels in Pallas's interpreter and
    the chunked twin, over two chunks of 64 and over 100 positions (padded
    to two chunks), over six chunks of 12 heads (two groups of six a walk
    step, the state's cotangent carried back over five chunk boundaries),
    and over 1,100 positions of 12 heads, padded to 18 chunks, where the
    chunk-parallel kernels block the chunks too, 3 of each head a step;
    decays down to exp(-5) a step span exp(-320) a chunk, so a
    factor taken the wrong way overflows here."""
    if path == "kernels" and H == 6:
        census = da.kda_walk_census(B * H, L, 16, 16, jnp.float32)
        assert da._heads_a_step(B * H) == 6
        assert census["walk_steps"] == 2 * -(-L // da.CHUNK)
        if L == 1100:
            assert census["chunks_a_step_fwd"] == 6 * 3
            assert census["chunks_a_step_bwd"] == 6 * 3
    args = _inputs(0, L, B, H)
    fn = (da.delta_attention_plain if path == "plain" else
          lambda *a: da.delta_attention_kernels(*a, interpret=True))
    with jax.default_matmul_precision("highest"):
        want = _with_grads(_recurrence, args)
        got = _with_grads(fn, args)
    for name, g, w in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(g, w, atol=5e-5 * float(jnp.max(
            jnp.abs(w))) + 1e-6, rtol=1e-4, err_msg=name)


def test_the_kernels_and_the_twin_agree_and_the_scale_scales():
    """One chunk: the kernels (interpreted) and the twin give the same
    output; the scale multiplies it; head groups divide the heads."""
    args = _inputs(1, 64)
    with jax.default_matmul_precision("highest"):
        plain = da.delta_attention_plain(*args)
        kernels = da.delta_attention_kernels(*args, interpret=True)
        half = da.delta_attention_plain(*args, scale=0.5 * 16 ** -0.5)
    np.testing.assert_allclose(plain, kernels, atol=1e-5)
    np.testing.assert_allclose(half, 0.5 * plain, atol=1e-6)
    assert da.CHUNK % da.SUB == 0
    assert da._heads_a_step(64) == da.HEADS and da._heads_a_step(6) == 6


def test_walk_census_at_the_cells_shape():
    """glm's KDA layer, 64 heads of 4,096 positions at 128 / 128 in
    bfloat16: the walks take 8 heads a step over 64 chunks, 512 steps one
    after another, each running the state's 7 products for a chunk where
    the whole chunk's 24 used to wait on the state; the chunk-parallel
    kernels take 8 heads by 4 chunks a step, forward and backward (at 256
    wide the backward's blocks fill half the scoped VMEM at 2); T and P (128 MiB float32)
    cross between the forward's kernels, their cotangents and the direct
    parts (513 MiB) between the backward's."""
    assert da.kda_walk_census(64, 4096, 128, 128) == {
        "walk_steps": 512, "products_before": 24, "products_after": 7,
        "chunks_a_step_fwd": 32, "chunks_a_step_bwd": 32,
        "bytes_fwd": 134_217_728, "bytes_bwd": 537_919_488}
    # a length that is not whole chunks counts its padded chunks
    assert da.kda_walk_census(64, 4033)["walk_steps"] == 512
    wide = da.kda_walk_census(64, 4096, 256, 256)
    assert (wide["chunks_a_step_fwd"], wide["chunks_a_step_bwd"]) == (32, 16)


def _kernel_names(fn, *args):
    """The names of the Pallas kernels in ``fn``'s traced program, and the
    matrix products it runs outside them (nested calls included)."""
    names, outside = [], 0

    def walk(jaxpr):
        nonlocal outside
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
                continue
            outside += eqn.primitive.name == "dot_general"
            for param in eqn.params.values():
                for sub in (param if isinstance(param, (tuple, list))
                            else (param,)):
                    if hasattr(sub, "eqns"):
                        walk(sub)
                    elif hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                        walk(sub.jaxpr)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names, outside


def test_kernels_are_named_for_the_roofline_and_hold_the_whole_chunk():
    """The kernel path as compiled for the chip (traced only, never run):
    the forward holds two kernels named ``kda_fwd``, forward and backward
    four, the backward's named ``kda_bwd``, no other kernel and no matrix
    product outside them; what ``kda_roofline`` and ``kda_share`` read by
    name is the whole recurrence."""
    args = _inputs(2, 200, 1, 4)

    def forward(*a):
        return da.delta_attention_kernels(*a, interpret=False)

    def both(*a):
        out, pull = jax.vjp(forward, *a)
        return pull(out)

    names, outside = _kernel_names(forward, *args)
    assert names == ["kda_fwd", "kda_fwd"] and outside == 0
    names, outside = _kernel_names(both, *args)
    assert sorted(names) == ["kda_bwd", "kda_bwd", "kda_fwd", "kda_fwd"]
    assert outside == 0


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


def test_kernels_compile_for_the_chip_at_the_cells_widths(one_chip):
    """glm's KDA layer, 64 heads of 4,096 positions at 128 / 128 in
    bfloat16, forward and backward, compiled for one v5e chip: four
    kernels, the chunk-parallel ones at the census's chunks a step within
    the scoped VMEM they ask for."""
    from jax.experimental.compilation_cache import compilation_cache
    before = {k: getattr(jax.config, k)
              for k in ("jax_enable_x64", "jax_enable_compilation_cache")}
    for k in before:
        jax.config.update(k, False)
    compilation_cache.reset_cache()
    try:
        shape = lambda dtype, *s: jax.ShapeDtypeStruct(     # noqa: E731
            s, dtype, sharding=one_chip)
        x = shape(jnp.bfloat16, 1, 64, 4096, 128)
        f32 = shape(jnp.float32, 1, 64, 4096, 128)

        def loss(q, k, v, g, beta, ct):
            return jnp.sum(da.delta_attention_kernels(
                q, k, v, g, beta, interpret=False).astype(jnp.float32) * ct)

        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            x, x, x, f32, shape(jnp.float32, 1, 64, 4096), f32).compile()
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 4


def test_sinkhorn_rows_and_columns_sum_to_one():
    """Twenty rounds of rows then columns on exp of unit-normal logits
    around the identity (mHC's residual mixing): columns sum to one within
    a few ``hc_eps`` (they are normalised last), rows within the rounds'
    convergence, every entry positive."""
    logits = jnp.asarray(np.random.default_rng(2).standard_normal(
        (3, 7, 4, 4)), jnp.float32) + jnp.eye(4)
    m = sinkhorn(jnp.exp(logits), 20, 1e-6)
    assert bool(jnp.all(m > 0))
    np.testing.assert_allclose(jnp.sum(m, -2), 1.0, atol=4e-6)
    np.testing.assert_allclose(jnp.sum(m, -1), 1.0, atol=1e-4)
    # far from a one-hot permutation at this draw
    assert float(jnp.max(m)) < 0.95


def _pooled_choice_by_hand(qi, ki, w, top_k, pool):
    """A plain top-k over the groups complete at each query (ties at the
    last score all taken: a group whose every head's product is negative
    scores 0), and the query's incomplete group's keys up to it."""
    qi, ki, w = (np.asarray(x, np.float64) for x in (qi, ki, w))
    B, L, H, D = qi.shape
    G = L // pool
    kbar = ki.reshape(B, G, pool, D).mean(2)
    out = np.zeros((B, L, L), bool)
    for b in range(B):
        for t in range(L):
            done = (t + 1) // pool
            score = np.sum(np.maximum(qi[b, t] @ kbar[b, :done].T, 0.0)
                           * w[b, t][:, None], axis=0)
            kth = np.sort(score)[::-1][:top_k // pool][-1] if done else 0
            for j in np.flatnonzero(score >= kth):
                out[b, t, j * pool:(j + 1) * pool] = True
            if (t + 1) % pool:
                out[b, t, done * pool:t + 1] = True
    return out


def test_pooled_choice_is_a_top_k_of_groups_with_its_tail():
    """64 queries over keys pooled by 4, top 16 keys (4 groups): the
    choice ``select_pooled`` packs is a plain top-k of the complete groups'
    scores, with the query's incomplete group's keys up to it; the first
    queries take every complete group; nothing above the diagonal."""
    r = np.random.default_rng(3)
    L, pool, top_k = 64, 4, 16
    qi = jnp.asarray(r.standard_normal((2, L, 3, 8)), jnp.float32)
    ki = jnp.asarray(r.standard_normal((2, L, 8)), jnp.float32)
    w = jnp.asarray(r.standard_normal((2, L, 3)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        words = sa.select_pooled(qi, ki, w, top_k, pool)
    got = np.asarray(sa.unpack(words, L))
    want = _pooled_choice_by_hand(qi, ki, w, top_k, pool)
    np.testing.assert_array_equal(got, want)
    causal = np.tril(np.ones((L, L), bool))
    assert not (got & ~causal).any()
    # a query with four complete groups or fewer takes all of them
    np.testing.assert_array_equal(got[:, :16], np.broadcast_to(
        causal[:16], (2, 16, L)))
    # later queries: 16 keys of the groups (more on a tie) and the tail's
    # (t + 1) % 4
    t = np.arange(16, L)
    assert (got[:, 16:].sum(-1) >= 16 + (t + 1) % 4).all()
    assert (got[:, 16:].sum(-1) == 16 + (t + 1) % 4).mean() > 0.8
    counts = sa.counts(words, L)
    assert counts["dsa_selected_count"] == got.sum()
