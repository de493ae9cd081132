"""Pallas flash attention (ops/flash_attention.py): exactness, gradients,
and the zoo integration (interpret mode on the CPU host)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metisfl_tpu.ops import flash_attention
from metisfl_tpu.ops.flash_attention import _dense_attention


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(3)
    return tuple(jnp.asarray(rng.standard_normal((2, 2, 64, 16)), jnp.float32)
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blk", [16, 32, 64])
def test_flash_matches_dense(qkv, causal, blk):
    q, k, v = qkv
    out = flash_attention(q, k, v, causal, blk, blk)
    want = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_flash_gradients_match(qkv):
    q, k, v = qkv
    g_flash = jax.grad(
        lambda q, k, v: flash_attention(q, k, v, True, 16, 16).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(
        lambda q, k, v: _dense_attention(q, k, v, True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("L", [40, 48, 80])
def test_flash_handles_ragged_lengths(causal, L):
    """Sequence lengths that do not divide the block size are padded and
    masked inside the kernel (round 2 raised ValueError for these)."""
    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 2, L, 16)), jnp.float32)
               for _ in range(3))
    out = flash_attention(q, k, v, causal, 32, 32)
    want = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    g_flash = jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal, 32, 32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(
        lambda q, k, v: _dense_attention(q, k, v, causal).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def test_llama_flash_forward_matches_plain():
    from metisfl_tpu.models.zoo import LlamaLite

    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 64, (2, 32)), jnp.int32)
    plain = LlamaLite(vocab_size=64, dim=16, depth=2, heads=2)
    flash = LlamaLite(vocab_size=64, dim=16, depth=2, heads=2,
                      use_flash=True)
    variables = plain.init(jax.random.PRNGKey(0), tokens)
    np.testing.assert_allclose(
        np.asarray(flash.apply(variables, tokens)),
        np.asarray(plain.apply(variables, tokens)),
        atol=1e-4, rtol=1e-4)


def test_llama_flash_trains():
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.models import ArrayDataset, FlaxModelOps
    from metisfl_tpu.models.zoo import LlamaLite

    rng = np.random.default_rng(2)
    x = rng.integers(0, 64, (32, 16)).astype(np.int32)
    ds = ArrayDataset(x, np.roll(x, -1, axis=1))
    ops = FlaxModelOps(
        LlamaLite(vocab_size=64, dim=16, depth=2, heads=2, use_flash=True),
        ds.x[:2])
    out = ops.train(ds, TrainParams(batch_size=8, local_steps=2,
                                    learning_rate=0.05))
    assert out.completed_steps == 2
    assert np.isfinite(out.train_metrics["loss"])


class TestGroupedQueryFlash:
    """GQA-native kernels: K/V at kv-head size, index-mapped to q heads."""

    def _inputs(self, Hq=4, Hkv=2, L=64, D=16, seed=11):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((2, Hq, L, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, Hkv, L, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, Hkv, L, D)), jnp.float32)
        return q, k, v

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("hkv", [1, 2])
    def test_forward_matches_repeated_oracle(self, causal, hkv):
        q, k, v = self._inputs(Hkv=hkv)
        out = flash_attention(q, k, v, causal, 32, 32)
        rep = 4 // hkv
        want = _dense_attention(q, jnp.repeat(k, rep, axis=1),
                                jnp.repeat(v, rep, axis=1), causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_gradients_match_repeated_oracle(self):
        q, k, v = self._inputs(Hkv=2, L=48)  # 48: exercises tail padding
        weight = jnp.asarray(
            np.random.default_rng(13).standard_normal(q.shape), jnp.float32)

        def flash_loss(q, k, v):
            return (flash_attention(q, k, v, True, 16, 16) * weight).sum()

        def dense_loss(q, k, v):
            return (_dense_attention(q, jnp.repeat(k, 2, axis=1),
                                     jnp.repeat(v, 2, axis=1), True)
                    * weight).sum()

        g_flash = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(g_flash[0]),
                                   np.asarray(g_dense[0]),
                                   atol=1e-4, rtol=1e-4)
        for got, full in zip(g_flash[1:], g_dense[1:]):
            B, Hq, L, D = full.shape
            want = np.asarray(full).reshape(B, 2, Hq // 2, L, D).sum(axis=2)
            np.testing.assert_allclose(np.asarray(got), want,
                                       atol=1e-4, rtol=1e-4)

    def test_llama_gqa_flash_matches_dense(self):
        from metisfl_tpu.models.zoo import LlamaLite

        tokens = jnp.asarray(
            np.random.default_rng(17).integers(0, 64, (2, 32)), jnp.int32)
        plain = LlamaLite(vocab_size=64, dim=32, depth=1, heads=4, kv_heads=2)
        flash = LlamaLite(vocab_size=64, dim=32, depth=1, heads=4, kv_heads=2,
                          use_flash=True)
        variables = plain.init(jax.random.PRNGKey(0), tokens)
        np.testing.assert_allclose(
            np.asarray(flash.apply(variables, tokens)),
            np.asarray(plain.apply(variables, tokens)),
            atol=2e-3, rtol=2e-3)


def test_attention_routes_on_sequence_length():
    """ops.attention: dense XLA below the crossover, the pallas kernel at
    or above it — both numerically the oracle, incl. GQA inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from metisfl_tpu.ops.flash_attention import (_dense_attention,
                                                 attention)

    rng = jax.random.PRNGKey(3)
    B, H, L, D = 2, 4, 64, 32
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i), (B, H, L, D),
                                 jnp.float32) for i in range(3))
    want = _dense_attention(q, k, v, True)
    # below threshold -> dense path (exact match)
    np.testing.assert_allclose(
        np.asarray(attention(q, k, v, True, min_flash_seq=4 * L)),
        np.asarray(want), rtol=1e-6, atol=1e-6)
    # at/above threshold -> flash kernel (oracle match within fp tolerance)
    np.testing.assert_allclose(
        np.asarray(attention(q, k, v, True, min_flash_seq=L)),
        np.asarray(want), rtol=2e-2, atol=2e-3)

    # GQA (2 of 4 KV heads) on the dense route broadcasts groups
    kg, vg = k[:, :2], v[:, :2]
    want_gqa = _dense_attention(q, jnp.repeat(kg, 2, axis=1),
                                jnp.repeat(vg, 2, axis=1), True)
    np.testing.assert_allclose(
        np.asarray(attention(q, kg, vg, True, min_flash_seq=4 * L)),
        np.asarray(want_gqa), rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------- #
# the three block classes: dead pairs get no grid step, whole pairs no
# mask, and only the pairs the diagonal or the ragged tail cuts build one
# --------------------------------------------------------------------- #

def _flash_module():
    # ``metisfl_tpu.ops`` exports the function under the module's name
    import importlib
    return importlib.import_module("metisfl_tpu.ops.flash_attention")


def _mask_every_live_block(monkeypatch):
    """The same step tables with every live pair classed masked: the
    kernels as they were before a whole pair had a body of its own."""
    fa = _flash_module()
    classes = fa._block_classes
    monkeypatch.setattr(
        fa, "_block_classes",
        lambda *shape: np.where(classes(*shape) == fa._WHOLE, fa._MASKED,
                                classes(*shape)))


def _case_inputs(Hq, Hkv, L, D, Dv, seed=23):
    rng = np.random.default_rng(seed)
    draw = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa
    return (draw(2, Hq, L, D), draw(2, Hkv, L, D), draw(2, Hkv, L, Dv),
            draw(2, Hq, L, Dv))


def _out_and_grads(attend, q, k, v, weight):
    out, vjp = jax.vjp(attend, q, k, v)
    return (out, *vjp(weight))


# (Hq, Hkv, L, D, Dv, blk_q, blk_k, causal, scale)
_CLASS_CASES = {
    "dead-whole-diagonal": (2, 2, 64, 16, 16, 16, 16, True, None),
    "query-edge-32-key-edge-16": (2, 2, 64, 16, 16, 32, 16, True, None),
    "query-edge-16-key-edge-32": (2, 2, 64, 16, 16, 16, 32, True, None),
    "ragged-causal": (2, 2, 40, 16, 16, 16, 16, True, None),
    "ragged-full": (2, 2, 40, 16, 16, 16, 16, False, None),
    "ragged-unequal-edges": (2, 2, 40, 16, 16, 32, 16, True, None),
    "gqa-4-2": (4, 2, 64, 16, 16, 16, 16, True, None),
    "mqa-4-1": (4, 1, 64, 16, 16, 16, 16, True, None),
    "widths-48-32-own-scale": (2, 2, 64, 48, 32, 16, 16, True, 0.2),
    "one-block": (2, 2, 16, 16, 16, 16, 16, True, None),
    "full-every-block-whole": (2, 2, 64, 16, 16, 16, 16, False, None),
}


@pytest.mark.parametrize("case", list(_CLASS_CASES))
def test_block_classes_match_dense(case):
    """Forward and all three gradients against the dense oracle wherever
    the classes differ: dead, whole and diagonal pairs together, unequal
    edges both ways, a masked tail, groups of query heads on one KV head
    (the member-major dK/dV walk), unequal widths, a single step that is
    first and last at once."""
    Hq, Hkv, L, D, Dv, blk_q, blk_k, causal, scale = _CLASS_CASES[case]
    q, k, v, weight = _case_inputs(Hq, Hkv, L, D, Dv)
    group = Hq // Hkv
    got = _out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal, blk_q, blk_k, None,
                                        scale), q, k, v, weight)
    want = _out_and_grads(
        lambda q, k, v: _dense_attention(
            q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1),
            causal, scale), q, k, v, weight)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=2e-5, err_msg=f"{case}: {name}")


@pytest.mark.parametrize("case", ["dead-whole-diagonal",
                                  "query-edge-32-key-edge-16",
                                  "ragged-full", "gqa-4-2"])
def test_whole_blocks_are_bit_identical_to_masking_them(case, monkeypatch):
    """A whole pair's body drops a mask that is all true: out, lse, dq,
    dk and dv equal, bit for bit, what the kernels give with every live
    pair masked. (Score width 16, so ``scale`` is 1/4: the CPU compiler
    that stands in for Mosaic here contracts ``dot * scale - m`` into one
    fused multiply-add where no ``where`` stands between them, and only
    a power of two makes that the same number. The chip is held to bit
    equality at the cells' own scales: PERF.md, PR 33.)"""
    fa = _flash_module()
    Hq, Hkv, L, D, Dv, blk_q, blk_k, causal, scale = _CLASS_CASES[case]
    q, k, v, weight = _case_inputs(Hq, Hkv, L, D, Dv)

    def run():
        out, lse = fa._flash_forward(q, k, v, causal, blk_q, blk_k, True,
                                     scale)
        return (out, lse, *fa._flash_backward(
            q, k, v, out, lse[:, :L, 0].reshape(2, Hq, L), weight, causal,
            blk_q, blk_k, True, scale=scale))

    got = run()
    census = fa.flash_block_census(L, blk_q, blk_k, causal)
    assert census["whole"] > 0
    _mask_every_live_block(monkeypatch)
    assert fa.flash_block_census(L, blk_q, blk_k, causal) == {
        **census, "whole": 0, "masked": census["live"]}
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, run()):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (case, name)


def _brute_force_classes(L, blk_q, blk_k, causal):
    """Every block's class from the whole boolean mask."""
    Lp = max(-(-L // blk_q) * blk_q, -(-L // blk_k) * blk_k)
    row, col = np.mgrid[:Lp, :Lp]
    mask = col < L
    if causal:
        mask &= row >= col
    blocks = mask.reshape(Lp // blk_q, blk_q, Lp // blk_k, blk_k)
    some, every = blocks.any(axis=(1, 3)), blocks.all(axis=(1, 3))
    return some, every


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blk_q,blk_k", [(16, 16), (32, 16), (16, 32),
                                         (8, 64)])
@pytest.mark.parametrize("L", [16, 40, 64, 100, 128])
def test_census_and_step_tables_against_brute_force(L, blk_q, blk_k, causal):
    fa = _flash_module()
    blk_q, blk_k, Lp = fa._resolve_blocks(L, blk_q, blk_k)
    some, every = _brute_force_classes(L, blk_q, blk_k, causal)
    nq, nk = some.shape
    assert fa.flash_block_census(L, blk_q, blk_k, causal) == {
        "live": int(some.sum()), "whole": int(every.sum()),
        "masked": int((some & ~every).sum()), "walked_before": nq * nk}

    classes = fa._block_classes(L, blk_q, blk_k, Lp, causal)

    def flags(walk):
        return [fa._F_FIRST * (n == 0) | fa._F_LAST * (n == len(walk) - 1)
                | fa._F_MASKED * (not every[i, j])
                for n, (_, i, j) in enumerate(walk)]

    # forward and dQ: by query block, key blocks ascending (the parent's
    # order over the steps its predicate let run)
    walks = [[(i, i, j) for j in range(nk) if some[i, j]] for i in range(nq)]
    q_of, k_of, got_flags = fa._step_tables(classes)
    assert q_of.dtype == k_of.dtype == got_flags.dtype == np.int32
    assert list(q_of) == [e for w in walks for e, _, _ in w]
    assert list(k_of) == [j for w in walks for _, _, j in w]
    assert list(got_flags) == [f for w in walks for f in flags(w)]

    # dK/dV: by key block, member-major, query blocks ascending; a key
    # block wholly in the padding has no step
    for members in (1, 3):
        walks = [[(m * nq + i, i, j) for m in range(members)
                  for i in range(nq) if some[i, j]] for j in range(nk)]
        walks = [w for w in walks if w]
        qm_of, k_of, got_flags = fa._step_tables(classes, members)
        assert list(qm_of) == [e for w in walks for e, _, _ in w]
        assert list(k_of) == [j for w in walks for _, _, j in w]
        assert list(got_flags) == [f for w in walks for f in flags(w)]
        assert len(qm_of) == members * int(some.sum())


def test_census_at_the_cells_shape():
    """4,096 positions, causal. At the edge of 512 the kernels walked
    before this change: 36 live pairs a head of the 64 a grid over every
    pair walked, 28 of them whole. At the automatic edge, 1024 while the
    heads are at most 256 wide: 10 of 16, 6 whole. Without causality
    every pair is live and whole."""
    fa = _flash_module()
    assert fa.flash_block_census(4096, 512, 512, True) == {
        "live": 36, "whole": 28, "masked": 8, "walked_before": 64}
    assert fa.flash_block_census(4096, None, None, True) == {
        "live": 10, "whole": 6, "masked": 4, "walked_before": 16}
    assert fa.flash_block_census(4096, None, None, False) == {
        "live": 16, "whole": 16, "masked": 0, "walked_before": 16}
    assert fa.flash_block_census(4000, None, None, False)["masked"] == 32


@pytest.mark.parametrize("L,D,Dv,edge", [
    (4096, 128, 128, 1024), (4096, 192, 128, 1024), (4096, 256, 256, 1024),
    (4096, 512, 128, 512), (4096, 128, 512, 512), (2048, 64, 64, 1024),
    (1536, 128, 128, 512), (768, 128, 128, 256), (640, 128, 128, 128),
    (4000, 128, 128, 128), (40, 16, 16, 40), (37, 16, 16, 40)])
def test_automatic_edge_from_length_and_widths(L, D, Dv, edge):
    """The largest edge that divides the length, 1024 only while scores
    and values are at most 256 wide (what the chip's compiler was seen to
    take, PERF.md PR 33); short sequences are one block."""
    fa = _flash_module()
    blk_q, blk_k, Lp = fa._resolve_blocks(L, None, None, D, Dv)
    assert blk_q == blk_k == edge
    assert Lp % edge == 0 and 0 <= Lp - L < edge
