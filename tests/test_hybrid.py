"""The attention / state-space hybrid (``JambaLite``, ``MambaMixer``,
``ops/selective_scan.py``): the scan paths against a step-by-step
recurrence, the mixer and the whole model against the benchmark's plain
reference on seeded weights, the flash kernel at the hybrid's 20-to-1 head
grouping, prefill and cached decode against the full forward pass, a slot
reused after a longer occupant, one federated LoRA round, and the
benchmark's new readers on hand-made contexts. CPU, tiny sizes; the Pallas
kernels run in interpret mode."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.bindings import jamba as bind
from benchmark.lib import flops_hybrid, spec
from benchmark.reference import jamba as ref
from metisfl_tpu.models.generate import (SlotDecoder, cache_bytes_by_kind,
                                         generate, init_cache)
from metisfl_tpu.models.zoo import JambaLite, LlamaLite, MambaMixer
from metisfl_tpu.ops import flash_attention, selective_scan as scan_ops
from metisfl_tpu.ops.flash_attention import _dense_attention

CELL = "jamba2-3b.lora-round"


def _cfg(**over):
    """The cell's configuration at its toy widths, float32 compute (the
    comparisons below are about the mathematics, not about bfloat16)."""
    cfg = dict(spec.cell(CELL, rehearse=True)["cfg"])
    cfg["compute_dtype"] = "float32"
    cfg.update(over)
    return cfg


# --------------------------------------------------------------------- #
# the scan: plain chunked path and kernels against one step at a time
# --------------------------------------------------------------------- #

def _scan_inputs(B, L, D, N, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda key, shape: jax.random.normal(          # noqa: E731
        key, shape, jnp.float32)        # the suite runs with x64 enabled
    return (normal(k[0], (B, L, D)),
            jax.nn.softplus(normal(k[1], (B, L, D)) - 2.0),
            -jnp.exp(0.5 * normal(k[2], (D, N))),
            normal(k[3], (B, L, N)), normal(k[4], (B, L, N)),
            normal(k[5], (B, L, D)))


# float32 throughout on both sides; what differs is the order of the adds
# (an associative scan inside a chunk, lane groups folded in the kernel):
# a few ulps of the largest value, which 2e-5 relative covers
SCAN_TOL = 2e-5


@pytest.mark.parametrize("path", ["plain", "kernels"])
@pytest.mark.parametrize("B,L,D,N,chunk", [
    (2, 48, 256, 16, 16),       # whole chunks, two lane groups
    (1, 37, 200, 16, 16),       # a ragged tail in time and in lanes
    (1, 24, 128, 8, 24),        # one chunk, a state of 8
])
def test_scan_paths_match_the_step_by_step_recurrence(path, B, L, D, N,
                                                      chunk):
    x, dt, a, b, c, w = _scan_inputs(B, L, D, N)
    if path == "plain":
        run = lambda *v: scan_ops.scan_chunked(*v, chunk=chunk)[0]  # noqa
    else:
        run = lambda *v: scan_ops.selective_scan(                   # noqa
            *v, chunk=chunk, interpret=True)
    want, want_state = ref.recurrence(x, dt, a, b, c)
    np.testing.assert_allclose(run(x, dt, a, b, c), want,
                               atol=SCAN_TOL * float(jnp.abs(want).max()))
    if path == "plain":
        _, state = scan_ops.scan_chunked(x, dt, a, b, c, chunk=chunk)
        np.testing.assert_allclose(state, want_state, atol=1e-5)
    grads = jax.grad(lambda *v: jnp.sum(run(*v) * w), argnums=range(5))
    wants = jax.grad(lambda *v: jnp.sum(ref.recurrence(*v)[0] * w),
                     argnums=range(5))(x, dt, a, b, c)
    for name, got, want in zip("x dt a b c".split(),
                               grads(x, dt, a, b, c), wants):
        np.testing.assert_allclose(
            got, want, atol=SCAN_TOL * float(jnp.abs(want).max()),
            err_msg=f"d{name}")


def test_scan_routes_to_the_plain_path_off_the_chip():
    """On the CPU nothing asks for the kernels: the routed call is the
    plain path's result bit for bit, at any length."""
    x, dt, a, b, c, _ = _scan_inputs(1, 40, 128, 16)
    routed = scan_ops.selective_scan(x, dt, a, b, c, chunk=8)
    plain, _ = scan_ops.scan_chunked(x, dt, a, b, c, chunk=8)
    assert jnp.array_equal(routed, plain)
    with pytest.raises(ValueError):
        scan_ops.selective_scan(x, dt, a, b, c, chunk=12)


def test_scan_continues_from_a_state_and_steps_one_position():
    x, dt, a, b, c, _ = _scan_inputs(2, 32, 128, 16, seed=3)
    whole, last = ref.recurrence(x, dt, a, b, c)
    y0, s0 = scan_ops.scan_chunked(x[:, :20], dt[:, :20], a, b[:, :20],
                                   c[:, :20], chunk=8)
    y1, s1 = scan_ops.scan_chunked(x[:, 20:31], dt[:, 20:31], a, b[:, 20:31],
                                   c[:, 20:31], chunk=8, state=s0)
    y2, s2 = scan_ops.scan_step(s1, x[:, 31], dt[:, 31], a, b[:, 31],
                                c[:, 31])
    got = jnp.concatenate([y0, y1, y2[:, None]], axis=1)
    np.testing.assert_allclose(got, whole, atol=1e-5)
    np.testing.assert_allclose(s2, last, atol=1e-5)


# the chip's compiler on the kernels at the cell's widths, with no chip:
# what interpret mode cannot refuse (tiling, VMEM) it does. The topology is
# described inside a fixture, never at import (one process may load libtpu)

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


def test_scan_kernels_compile_for_the_chip_at_published_widths(one_chip):
    from jax.experimental.compilation_cache import compilation_cache
    # as the program runs: no x64 (the suite's conftest turns it on, and
    # Mosaic refuses the i64 index constants it makes), and no persistent
    # cache (an entry compiled without a chip cannot be read back)
    before = {k: getattr(jax.config, k)
              for k in ("jax_enable_x64", "jax_enable_compilation_cache")}
    for k in before:
        jax.config.update(k, False)
    compilation_cache.reset_cache()
    try:
        shape = lambda *s: jax.ShapeDtypeStruct(             # noqa: E731
            s, jnp.float32, sharding=one_chip)
        args = (shape(1, 4096, 5120), shape(1, 4096, 5120), shape(5120, 16),
                shape(1, 4096, 16), shape(1, 4096, 16))
        run = lambda *v: scan_ops.scan_kernels(*v, 64, False)  # noqa: E731
        fwd = jax.jit(run).lower(*args).compile()
        bwd = jax.jit(jax.grad(lambda *v: jnp.sum(run(*v)),
                               argnums=range(5))).lower(*args).compile()
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert "ssm_scan_fwd" in fwd.as_text()
    assert "ssm_scan_bwd" in bwd.as_text()
    # nothing of (L, D, N) for the whole sequence: 1.34 GB if it were
    assert bwd.memory_analysis().temp_size_in_bytes < 0.5e9


# --------------------------------------------------------------------- #
# the flash kernel at the hybrid's grouping: 20 query heads on 1 KV head
# --------------------------------------------------------------------- #

def test_flash_kernel_at_twenty_query_heads_on_one_kv_head():
    """No rotary (the hybrid applies none), causal, a group of 20: wider
    than any grouping the kernel's own tests run. Both sides float32; the
    kernel's online softmax and the dense softmax differ by rounding in
    the running maximum's rescale, 1e-5 relative."""
    k = jax.random.split(jax.random.PRNGKey(20), 4)
    q = jax.random.normal(k[0], (1, 20, 160, 64), jnp.float32)
    kk = jax.random.normal(k[1], (1, 1, 160, 64), jnp.float32)
    v = jax.random.normal(k[2], (1, 1, 160, 64), jnp.float32)
    w = jax.random.normal(k[3], q.shape, jnp.float32)

    def dense(q, kk, v):
        return _dense_attention(q, jnp.repeat(kk, 20, 1),
                                jnp.repeat(v, 20, 1), True)

    flash = lambda q, kk, v: flash_attention(q, kk, v, True, 64, 32)  # noqa
    np.testing.assert_allclose(flash(q, kk, v), dense(q, kk, v), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(
        q, kk, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), argnums=(0, 1, 2))(
        q, kk, v)
    for name, g, r in zip("q k v".split(), got, want):
        # dK and dV sum over the 20 heads of the group
        np.testing.assert_allclose(
            g, r, atol=2e-5 * float(jnp.abs(r).max()) + 2e-5,
            err_msg=f"d{name}")


# --------------------------------------------------------------------- #
# mixer and model against the plain reference, on seeded weights
# --------------------------------------------------------------------- #

def _program_and_reference(cfg, seed=11):
    module = bind.build_module(cfg)
    variables = bind.variables(cfg, seed)
    lora, base = ref.make_weights(cfg, seed)
    return module, variables, lora, base


# float32 on both sides, default CPU matmul precision (float32) against
# ``highest``: the gap is summation order over at most 160 terms a dot
# and a few dozen dots deep; 2e-4 of the logits' spread is two orders
# above it and two below what a bfloat16 product would give
MODEL_TOL = 2e-4


@pytest.mark.parametrize("kernels", [False, True])
def test_mixer_matches_the_reference_mixer(kernels):
    cfg = _cfg()
    s = ref.sizes(cfg)
    _, variables, lora, base = _program_and_reference(cfg)
    mixer = MambaMixer(s["d"], d_state=s["n"], d_conv=s["k"],
                       expand=int(cfg["mamba_expand"]), dt_rank=s["r"],
                       eps=s["eps"], lora_rank=s["rank"],
                       lora_alpha=s["alpha"], scan_interpret=kernels)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 19, s["d"]),
                          jnp.float32)
    got = mixer.apply({"params": variables["params"]["block_0"]["mamba"]}, u)
    want = ref.mamba_mix(u, {**base["layers"][0], **lora[0]}, cfg)
    np.testing.assert_allclose(got, want,
                               atol=MODEL_TOL * float(jnp.abs(want).max()))


@pytest.mark.parametrize("layers,period,offset", [(2, 2, 1), (4, 3, 1)])
def test_model_logits_and_lora_gradients_match_the_reference(layers, period,
                                                             offset):
    cfg = _cfg(num_hidden_layers=layers, attn_layer_period=period,
               attn_layer_offset=offset)
    module, variables, lora, base = _program_and_reference(cfg)
    assert [module.is_attention(l) for l in range(layers)] == \
        [ref.is_attention(cfg, l) for l in range(layers)]
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.integers(0, cfg["vocab_size"], (2, 24)), jnp.int32)
    y = jnp.roll(x, -1, axis=1)
    want = ref.logits(base, lora, x, cfg)
    got = module.apply(variables, x)
    np.testing.assert_allclose(got, want,
                               atol=MODEL_TOL * float(jnp.std(want)))

    def program_loss(params):
        lg = module.apply({"params": params}, x)
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(lg, -1), y[..., None], -1))

    from metisfl_tpu.tensor.pytree import pytree_to_named_tensors
    grads = dict(pytree_to_named_tensors(
        {"params": jax.grad(program_loss)(variables["params"])}))
    wants = bind.by_program_name(jax.grad(
        lambda p: ref.loss(p, base, x, y, cfg))(lora))
    assert set(wants) == {n for n in grads if "lora_" in n}
    for name, want in wants.items():
        assert float(np.abs(want).max()) > 0, name
        np.testing.assert_allclose(
            grads[name], want, atol=5 * MODEL_TOL * float(np.abs(want).max()),
            err_msg=name)


def test_shipped_subset_and_wire_names_agree():
    cfg = _cfg()
    from metisfl_tpu.tensor.pytree import pytree_to_named_tensors
    shipped = dict(pytree_to_named_tensors(bind.shipped_host(cfg, 3)))
    by_name = bind.by_program_name(ref.lora_host(cfg, 3))
    assert set(shipped) == set(by_name)
    assert all(np.array_equal(shipped[n], by_name[n]) for n in shipped)
    program = dict(pytree_to_named_tensors(jax.device_get(
        bind.variables(cfg, 3))))
    assert {n for n in program if "lora_" in n} == set(shipped)
    assert all(np.array_equal(program[n], shipped[n]) for n in shipped)


def test_hybrid_flops_against_cost_analysis():
    """``lib/flops_hybrid.py`` against XLA's own count at toy depth: XLA
    counts the whole score matrix, the elementwise work and the scan's
    exponentials, so the benchmark's count may not pass it."""
    cfg = _cfg()
    module = bind.build_module(cfg)
    x = jnp.zeros((2, 64), jnp.int32)
    shapes = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), x))
    cost = jax.jit(lambda v, t: module.apply(v, t)).lower(
        shapes, x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    got = flops_hybrid.hybrid_forward_flops(cfg, 2, 64)
    assert 0.5 * float(cost["flops"]) <= got <= float(cost["flops"])
    full = spec.cell(CELL)
    step = flops_hybrid.train_step_flops(full["cfg"],
                                         full["traffic"]["shape"])
    assert step == pytest.approx(26.5e12, rel=0.02)     # ISSUE: 26.2 TFLOP
    work = flops_hybrid.ssm_scan_cost(full["cfg"], full["traffic"]["shape"],
                                      remat=True)
    # bytes bind the kernels' roofline, not operations
    assert work["bytes"] / 819e9 > 10 * work["flops"] / 197e12


# --------------------------------------------------------------------- #
# decoding: prefill, cached steps, a reused slot
# --------------------------------------------------------------------- #

def _tiny(**over):
    kw = dict(vocab_size=61, dim=32, depth=4, heads=4, kv_heads=1,
              ffn_dim=80, attn_period=2, attn_offset=1, d_state=8,
              dt_rank=4, lora_rank=2)
    kw.update(over)
    module = JambaLite(**kw)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 61, (2, 21)),
                         jnp.int32)
    return module, module.init(jax.random.PRNGKey(0), tokens), tokens


def test_cache_layout_is_what_the_module_declares():
    module, _, _ = _tiny()
    caches = init_cache(module, 3, 16)
    assert module.cache_kinds() == ("state", "kv", "state", "kv")
    assert [tuple(a.shape for a in c) for c in caches] == [
        ((3, 64, 3), (3, 64, 8)), ((3, 1, 16, 8), (3, 1, 16, 8))] * 2
    kv = 2 * 2 * 3 * 16 * 8 * 4
    state = 2 * 3 * 64 * (3 + 8) * 4
    assert cache_bytes_by_kind(module, caches) == {"state": state, "kv": kv}
    llama = LlamaLite(vocab_size=61, dim=32, depth=2, heads=4, kv_heads=2)
    assert set(cache_bytes_by_kind(llama, init_cache(llama, 1, 8))) == {"kv"}


def test_prefill_then_cached_decode_matches_the_full_forward_pass():
    """Logits, not tokens: with random weights the largest logit changes
    on rounding. float32 on both sides; the cached path adds the same
    terms in another order (a chunked scan from a carried state, one-step
    recurrences, attention over a zero-padded cache): 1e-5 absolute on
    logits of order one."""
    module, variables, tokens = _tiny()
    full = module.apply(variables, tokens)
    caches = init_cache(module, 2, 32)
    logits, caches = module.apply(variables, tokens[:, :13], caches=caches,
                                  position=0)
    np.testing.assert_allclose(logits, full[:, :13], atol=1e-5)
    for t in range(13, 21):
        logits, caches = module.apply(variables, tokens[:, t:t + 1],
                                      caches=caches, position=t)
        np.testing.assert_allclose(logits[:, 0], full[:, t], atol=1e-5)
    # a prompt continued in a second piece (position > 0, L > 1)
    caches = init_cache(module, 2, 32)
    _, caches = module.apply(variables, tokens[:, :9], caches=caches,
                             position=0)
    logits, _ = module.apply(variables, tokens[:, 9:], caches=caches,
                             position=9)
    np.testing.assert_allclose(logits, full[:, 9:], atol=1e-5)


def test_a_reused_slot_gives_what_a_fresh_decoder_gives():
    """Recurrent state has no frontier to hide behind: a slot that held a
    longer occupant must start its next one from zero. Same program, same
    inputs but the slot's stale content: the logits are equal bit for
    bit, and so are the tokens and the state the slot is left with."""
    module, variables, tokens = _tiny()
    long_prompt, short_prompt = tokens[0, :17], tokens[1, :5]
    stale = init_cache(module, 1, 32)
    _, stale = module.apply(variables, long_prompt[None], caches=stale,
                            position=0)
    for prompt in (short_prompt, short_prompt[:1]):      # one token too
        fresh_logits, fresh = module.apply(
            variables, prompt[None], caches=init_cache(module, 1, 32),
            position=0)
        reused_logits, reused = module.apply(variables, prompt[None],
                                             caches=stale, position=0)
        assert jnp.array_equal(fresh_logits, reused_logits)
        for kind, a, b in zip(module.cache_kinds(), fresh, reused):
            if kind == "state":
                assert all(jnp.array_equal(u, v) for u, v in zip(a, b))

    used, fresh = (SlotDecoder(module, slots=2, max_len=32)
                   for _ in range(2))
    tok = used.prefill(variables, 1, np.asarray(long_prompt))
    for pos in range(17, 23):
        tok = used.step(variables, [0, tok], [0, pos])[1]
    outs = []
    for decoder in (used, fresh):
        tok = decoder.prefill(variables, 1, np.asarray(short_prompt))
        seq = [tok]
        for pos in range(5, 11):
            tok = int(decoder.step(variables, [0, tok], [0, pos])[1])
            seq.append(tok)
        outs.append(seq)
    assert outs[0] == outs[1]
    solo = generate(module, variables, short_prompt[None], 7, max_len=32)
    assert outs[1] == [int(t) for t in solo[0]]
    assert set(cache_bytes_by_kind(module, used.caches)) == {"kv", "state"}


def test_continuous_batcher_serves_the_hybrid_and_reports_cache_bytes():
    from metisfl_tpu.models import FlaxModelOps
    from metisfl_tpu.serving.decode import ContinuousBatcher
    module, variables, tokens = _tiny()
    ops = FlaxModelOps(module, np.asarray(tokens[:1]), variables=variables)
    batcher = ContinuousBatcher(ops, 1, variables, slots=2, max_len=32,
                                channel="hybrid")
    try:
        prompts = [np.asarray(tokens[0, :9]), np.asarray(tokens[1, :4]),
                   np.asarray(tokens[0, 5:16])]
        futures = [batcher.submit(p, 6) for p in prompts]
        for prompt, future in zip(prompts, futures):
            got, version = future.result(timeout=120)
            want = generate(module, variables, prompt[None], 6, max_len=32)
            assert version == 1 and list(got) == [int(t) for t in want[0]]
        described = batcher.describe()
        assert described["cache_bytes"] == cache_bytes_by_kind(
            module, batcher._decoder.caches)
        assert described["cache_bytes"]["state"] == 2 * 2 * 64 * (3 + 8) * 4
        from metisfl_tpu import telemetry
        from metisfl_tpu.telemetry import metrics as tmetrics
        text = tmetrics.registry().render()
        assert (telemetry.M_SERVING_DECODE_CACHE_BYTES
                + '{channel="hybrid",kind="state"}') in text
    finally:
        batcher.close()


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
    community model holds ``lora_`` leaves alone, and from the second
    round on the learner places the shipped leaves and keeps the rest of
    the tree on the device (``task_bytes.kept_bytes``)."""
    from benchmark.lib.recipes import Recipe
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (EvalConfig, FederationConfig,
                                    TerminationConfig)
    from metisfl_tpu.driver.session import DriverSession
    from metisfl_tpu.tensor.pytree import (ModelBlob,
                                           pytree_to_named_tensors)
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
    sent = dict(pytree_to_named_tensors(initial))
    assert set(named) == set(sent) and all("lora_" in n for n in named)
    assert any(not np.array_equal(named[n], sent[n]) for n in named)
    done = [m for m in metas if m.get("completed_at", 0) > 0]
    assert len(done) >= 2
    sizes = {n: int(np.asarray(a).nbytes) for n, a in
             pytree_to_named_tensors(jax.device_get(bind.variables(cfg, 9)))}
    shipped = sum(v for n, v in sizes.items() if "lora_" in n)
    lid = done[1]["selected_learners"][0]
    assert done[1]["profile"]["learners"][lid]["task_bytes"] == {
        "placed_bytes": shipped, "kept_bytes": sum(sizes.values()) - shipped,
        "read_bytes": shipped}
    assert all(np.isfinite(v["loss"])
               for m in done for v in m["train_metrics"].values())


# --------------------------------------------------------------------- #
# the benchmark's new readers on hand-made contexts
# --------------------------------------------------------------------- #

def _ctx(kernel_ops_s):
    cell = spec.cell(CELL)
    rounds = [{"profile": {"learners": {"L0": {"device": {
        "ms_per_step": 500.0}}}}}]
    return {"cell": cell, "cfg": cell["cfg"], "traffic": cell["traffic"],
            "rounds": rounds, "learner": "L0", "device_kind": "TPU v5 lite",
            "trace": {"busy_s": 4.0, "window_s": 5.0,
                      "module_runs": {"jit_train_scan_steps": 1.0},
                      "kernel_ops_s": kernel_ops_s}}


def test_new_readers_read_their_kernels_and_nothing_else():
    from benchmark.metrics import (hybrid_step_mfu, ssm_scan_roofline,
                                   ssm_scan_share)
    cell = spec.cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    assert {"hybrid_step_mfu", "ssm_scan_roofline", "ssm_scan_share"} <= names
    assert not {"train_step_mfu", "flash_roofline"} & names
    ctx = _ctx({"ssm_scan_fwd": 0.6, "ssm_scan_bwd": 1.0, "flash_fwd": 0.4})
    assert ssm_scan_share.read(ctx) == pytest.approx(100 * 1.6 / 4.0)
    cost = flops_hybrid.ssm_scan_cost(cell["cfg"], cell["traffic"]["shape"],
                                      remat=True)
    assert ssm_scan_roofline.read(ctx) == pytest.approx(
        100 * 8 * cost["bytes"] / 819e9 / 1.6)
    assert 0 < ssm_scan_roofline.read(ctx) < 100
    work = flops_hybrid.train_step_flops(cell["cfg"],
                                         cell["traffic"]["shape"])
    assert hybrid_step_mfu.read(ctx) == pytest.approx(
        100 * work / 0.5 / 197e12)
    # a program without the kernels (the parent): nothing, and no raise
    quiet = _ctx({"flash_fwd": 0.4})
    assert ssm_scan_share.read(quiet) is None
    assert ssm_scan_roofline.read(quiet) is None
    assert ssm_scan_share.read({"trace": None}) is None


def test_configuration_file_keeps_the_catalog_row():
    cfg = spec.cell(CELL)["cfg"]
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == "jamba2-3b")
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "AI21-Jamba2-3B")
    assert entry["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k, "?") != v}
    assert differs == {"num_hidden_layers"}
    assert cfg["published"]["num_hidden_layers"] == 28
    # one whole period: every kind of layer in its published ratio
    assert cfg["num_hidden_layers"] == cfg["attn_layer_period"]
    assert sum(ref.is_attention(cfg, l) for l in range(14)) == 1
    assert ref.is_attention(cfg, 7)
