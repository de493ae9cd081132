"""The benchmark's per-layer readers against what the program really emits.

``tests/test_task_waterfall.py`` and ``tests/test_hybrid.py`` feed the
readers of ``benchmark/metrics/`` hand-made contexts; here each reader that
needs neither the device trace nor a chip's peak reads a context built, key
for key as ``benchmark/lib/round_driver.py`` and
``benchmark/lib/serve_driver.py`` build theirs, from a real in-process
federation (the LoRA path and the whole-tree path) and a real
``ServingGateway``. A span, a phase, a tile or a counter renamed in the
program leaves a ``None`` here, on the CPU, and not under ``per_layer`` in
the ledger after a chip run. The metric names come from ``BENCHMARK.json``.
"""

import math
import threading
import time

import numpy as np
import pytest

from benchmark.lib import common, spec

# readers of the device trace (``ctx["trace"]``) or of a chip's peak
# (``benchmark/lib/peaks.json`` by ``device_kind``): a chip run alone
TRACE_ONLY = {
    "train_step_mfu", "hybrid_step_mfu", "flash_roofline",
    "ssm_scan_roofline", "ssm_scan_share", "serve_mfu",
    "mla_moe_step_mfu", "mla_flash_roofline", "moe_experts_roofline",
    "moe_experts_share",
    "scmoe_step_mfu", "scmoe_flash_roofline", "scmoe_experts_roofline",
    "scmoe_experts_share",
    "dsa_step_mfu", "dsa_flash_roofline", "dsa_index_roofline",
    "dsa_index_share", "dsa_experts_roofline",
    "kda_moe_step_mfu", "kda_roofline", "kda_share",
    "device_idle_share.train", "device_idle_share.serve",
    "decode_gap_read_ms", "decode_gap_loop_ms", "decode_gap_launch_ms",
    "decode_gap_named_share"}
# what a CPU's clock leaves of each reading
POSITIVE = {"step_ms", "decode_step_ms", "slot_ms", "prefill_ms",
            "encode_ms", "load_ms", "decode_launch_ms"}
DIFFERENCES = {"uplink_ms", "report_ms"}

LOCAL_STEPS = 4
ROUNDS = 3


def _declared() -> dict:
    """{per-layer metric: ``round`` or ``serve``}, from ``BENCHMARK.json``:
    the driver of the traffic of the cells the metric is declared for."""
    bench = spec.benchmark()
    driver = {w["name"]: spec.cell(w["name"])["traffic"]["driver"]
              for w in bench["workloads"]}
    out = {}
    for metric in bench["per_layer"]:
        kinds = {driver[w] for w in metric["workloads"]}
        assert len(kinds) == 1, metric["name"]
        out[metric["name"]] = kinds.pop()
    return out


DECLARED = _declared()


def _cases() -> list:
    fed = {n: d for n, d in DECLARED.items() if n not in TRACE_ONLY}
    rounds = [n for n, d in fed.items() if d == "round"]
    out = [("lora", n) for n in rounds]
    out += [("serve", n) for n, d in fed.items() if d == "serve"]
    # the whole-tree path cuts ``upload``, ``readback`` and ``encode`` in
    # other code (PERF.md section 3); the counter's contract is one
    out += [("whole", n) for n in rounds
            if not n.startswith("window_compiles")]
    return out


CASES = _cases()


def _compiles() -> float:
    from metisfl_tpu import telemetry
    return common.compiles_total(telemetry.render_metrics())


def _lm(lora_rank: int):
    import jax.numpy as jnp

    from metisfl_tpu.models.zoo import LlamaLite
    return LlamaLite(vocab_size=32, dim=16, depth=1, heads=2,
                     lora_rank=lora_rank, dtype=jnp.float32)


def _round_ctx(regex: str, module=None, cfg=None, seq: int = 8) -> dict:
    """Three rounds of one learner, the rounds after the first as the
    window; ``regex`` is both what trains and what ships. ``module`` (32
    token ids, rows of ``seq``) and its configuration ``cfg`` stand in for
    the toy decoder and its family."""
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (EvalConfig, FederationConfig,
                                    TerminationConfig)
    from metisfl_tpu.driver import InProcessFederation
    from metisfl_tpu.models import ArrayDataset, FlaxModelOps

    rng = np.random.default_rng(30)
    tokens = rng.integers(0, 32, (16, seq + 1)).astype(np.int32)
    x, y = tokens[:, :-1], tokens[:, 1:]
    config = FederationConfig(
        train=TrainParams(batch_size=2, local_steps=LOCAL_STEPS,
                          scan_chunk=LOCAL_STEPS, optimizer="adam",
                          learning_rate=1e-3, ship_tensor_regex=regex),
        eval=EvalConfig(every_n_rounds=0),
        termination=TerminationConfig(federation_rounds=ROUNDS))
    fed = InProcessFederation(config)
    engine = FlaxModelOps(module or _lm(lora_rank=2), x[:2],
                          trainable_regex=regex)
    fed.add_learner(engine, ArrayDataset(x, y, seed=0))
    fed.seed_model(engine.get_variables())
    try:
        fed.start()
        assert fed.wait_for_rounds(1, timeout_s=180)
        compiles_before = _compiles()
        assert fed.wait_for_rounds(ROUNDS, timeout_s=180)
        metas = fed.controller.get_runtime_metadata()
    finally:
        fed.shutdown()
    done = [m for m in metas if m.get("completed_at", 0) > 0][:ROUNDS]
    window = done[1:]
    assert len(window) == ROUNDS - 1
    traffic = {"driver": "round", "ship_tensor_regex": regex,
               "shape": {"batch": 2, "seq": int(x.shape[1]),
                         "local_steps": LOCAL_STEPS,
                         "scan_chunk": LOCAL_STEPS}}
    return {"cell": {"name": "contract." + (regex or "whole")},
            "cfg": cfg or {"family": "decoder_lm"}, "traffic": traffic,
            "rounds": window, "learner": done[0]["selected_learners"][0],
            "window_s": window[-1]["completed_at"] - done[0]["completed_at"],
            "compiles": _compiles() - compiles_before,
            "device_kind": "cpu", "trace": None,
            # beside the driver's keys: what the counter has seen in all
            "compiles_seen": compiles_before}


@pytest.fixture(scope="module")
def lora():
    return _round_ctx("lora_")


@pytest.fixture(scope="module")
def whole():
    return _round_ctx("")


SCMOE_CELL = "longcat-flash-chat.lora-round"


@pytest.fixture(scope="module")
def scmoe():
    """The LoRA path again with the shortcut-connected decoder at the
    cell's toy widths (its binding builds the module), so that the round's
    profile carries the routed layers' counters."""
    cfg = {**spec.cell(SCMOE_CELL, rehearse=True)["cfg"], "vocab_size": 32}
    return _round_ctx("lora_", spec.binding(cfg).build_module(cfg), cfg)


DSA_CELL = "keye-vl-2.0-30b-a3b.lora-round-16k"


@pytest.fixture(scope="module")
def dsa():
    """The LoRA path with the sparse-attention decoder at the cell's toy
    widths, rows of 32 tokens (the choice packs 32 query rows a word), so
    that the round's profile carries the choice's counters."""
    cfg = {**spec.cell(DSA_CELL, rehearse=True)["cfg"], "vocab_size": 32}
    return _round_ctx("lora_", spec.binding(cfg).build_module(cfg), cfg,
                      seq=32)


@pytest.fixture(scope="module")
def serve(tmp_path_factory):
    """A dozen generations over 2 slots from 4 threads, the gateway's
    spans in a sink of its own; the window closes after the gateway has
    shut down, which flushes the decode loop's last summary event."""
    from metisfl_tpu.config import ServingConfig, ServingDecodeConfig
    from metisfl_tpu.models import FlaxModelOps
    from metisfl_tpu.serving import ServingGateway
    from metisfl_tpu.telemetry import trace as ttrace
    from metisfl_tpu.tensor.pytree import pack_model

    sink = tmp_path_factory.mktemp("serve") / "telemetry"
    ops = FlaxModelOps(_lm(lora_rank=0), np.zeros((1, 4), np.int32))
    gateway = ServingGateway(ops, ServingConfig(
        enabled=True, decode=ServingDecodeConfig(slots=2, max_len=32)))
    rng = np.random.default_rng(31)
    asks = [(rng.integers(1, 32, int(n)).astype(np.int32), int(o))
            for n, o in zip(rng.integers(3, 9, 12), rng.integers(4, 10, 12))]
    sent, lock = [], threading.Lock()

    def client(mine: list) -> None:
        for prompt, out_len in mine:
            t = time.time()
            tokens, _, _ = gateway.generate(prompt, out_len, timeout_s=180)
            with lock:
                sent.append({"prompt_len": len(prompt), "out_len": out_len,
                             "ms": (time.time() - t) * 1e3,
                             "tokens": len(tokens)})

    ttrace.configure(enabled=True, service="serving", dir=str(sink))
    try:
        gateway.install("stable", 1, pack_model(ops.get_variables()))
        gateway.generate(asks[0][0], 4, timeout_s=180)      # the warm-up
        before = gateway.describe()["decode"]["stable"]
        compiles_before = _compiles()
        t0 = time.time()
        threads = [threading.Thread(target=client, args=(asks[i::4],))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        after = gateway.describe()["decode"]["stable"]
        compiles = _compiles() - compiles_before
    finally:
        gateway.shutdown()
        ttrace.flush()
        ttrace.configure(enabled=True, service="test", dir="")
    t1 = time.time()
    assert len(sent) == len(asks)
    assert all(r["tokens"] == r["out_len"] for r in sent)
    traffic = {"driver": "serve", "clients": 4, "slots": 2, "max_len": 32}
    return {"cell": {"name": "contract.serve"},
            "cfg": {"family": "decoder_lm"}, "traffic": traffic,
            "window_s": t1 - t0, "window": (t0, t1), "compiles": compiles,
            "device_kind": "cpu", "trace": None,
            "decode_steps": after["steps"] - before["steps"],
            "requests": [{"prompt_len": r["prompt_len"],
                          "out_len": r["out_len"]} for r in sent],
            "telemetry_dir": str(sink),
            "reply_p50_ms": float(np.percentile([r["ms"] for r in sent],
                                                50)),
            "compiles_seen": compiles_before}


@pytest.mark.parametrize("path,name", CASES,
                         ids=[f"{p}-{n}" for p, n in CASES])
def test_reader_reads_what_the_program_emits(path, name, request):
    ctx = request.getfixturevalue(path)
    value = spec.metric_reader(name).read(ctx)
    assert value is not None, f"{name} finds nothing in a real {path} run"
    assert isinstance(value, float) and math.isfinite(value), value
    if name in POSITIVE:
        assert value > 0, value
    elif name not in DIFFERENCES:
        assert value >= 0, value
    if name.startswith("window_compiles"):
        # the reader passes on what the driver counted with
        # ``compiles_total``: that sum must find the program's counter,
        # which had counted the warm-up's compiles when the window opened
        assert ctx["compiles_seen"] > 0


def test_counter_readers_read_the_counters_of_a_real_round(scmoe):
    """``moe_local_count`` and ``moe_zero_count`` ride
    ``RoundProfile.learners[*].device`` of a real round, where the readers
    of the shortcut-connected family look for them; given beside them what
    a chip run alone has (a device kind with peaks, the kernels' events of
    a traced round), each of the four reads a number."""
    from benchmark.metrics import _scmoe
    shape, cfg = scmoe["traffic"]["shape"], scmoe["cfg"]
    device = scmoe["rounds"][0]["profile"]["learners"][scmoe["learner"]][
        "device"]
    assignments = (shape["batch"] * shape["seq"] * cfg["moe_topk"]
                   * cfg["num_layers"])
    local = _scmoe.device_count(scmoe, "moe_local_count")
    zero = _scmoe.device_count(scmoe, "moe_zero_count")
    assert 0 < device["moe_local_count"] and 0 < device["moe_zero_count"]
    assert 0 < local and 0 < zero and local + zero < assignments
    assert _scmoe.device_count(scmoe, "moe_absent_count") is None
    assert _scmoe.device_count({**scmoe, "learner": "nobody"},
                               "moe_zero_count") is None
    assert set(cfg["program"]["trace_ops"]) == {"moe_gmm_fwd", "moe_gmm_bwd"}
    traced = {**scmoe, "device_kind": "TPU v5 lite", "trace": {
        "busy_s": 0.9, "window_s": 1.0,
        "module_runs": {"jit_train_scan_steps": 1.0},
        "kernel_ops_s": {"flash_fwd": 0.1, "flash_bwd_dq": 0.1,
                         "moe_gmm_fwd": 0.05, "moe_gmm_bwd": 0.05},
        "ops_s": {"fusion": 0.6}}}
    for name in ("scmoe_step_mfu", "scmoe_flash_roofline",
                 "scmoe_experts_roofline", "scmoe_experts_share"):
        assert DECLARED[name] == "round"
        value = spec.metric_reader(name).read(traced)
        assert isinstance(value, float) and math.isfinite(value), name
        assert value > 0, name
        # and without the chip's part, as every run here is: nothing
        try:
            assert spec.metric_reader(name).read(scmoe) is None, name
        except spec.UnknownDevice:
            assert name == "scmoe_step_mfu"


def test_dsa_readers_read_the_counters_of_a_real_round(dsa):
    """``dsa_selected_count``, ``dsa_live_tiles_count`` and
    ``moe_local_count`` ride ``RoundProfile.learners[*].device`` of a real
    round; given what a chip run alone has beside them, each of the
    family's readers and the experts' share reads a number, and without it
    nothing."""
    from benchmark.lib import flops_dsa_moe
    from benchmark.metrics import _scmoe
    shape, cfg = dsa["traffic"]["shape"], dsa["cfg"]
    layers = cfg["num_hidden_layers"]
    pairs = shape["batch"] * layers * flops_dsa_moe.selected_pairs(
        shape["seq"], cfg["sa_config"]["topk"])
    selected = _scmoe.device_count(dsa, "dsa_selected_count")
    assert pairs <= selected <= 1.05 * pairs
    assert _scmoe.device_count(dsa, "dsa_live_tiles_count") == \
        shape["batch"] * layers
    assert 0 < _scmoe.device_count(dsa, "moe_local_count")
    traced = {**dsa, "device_kind": "TPU v5 lite", "trace": {
        "busy_s": 0.9, "window_s": 1.0,
        "module_runs": {"jit_train_scan_steps": 1.0},
        "kernel_ops_s": {"flash_fwd_sel": 0.1, "flash_bwd_dq_sel": 0.1,
                         "flash_bwd_dkv_sel": 0.1, "dsa_select": 0.05,
                         "moe_gmm_fwd": 0.02, "moe_gmm_bwd": 0.03},
        "ops_s": {"fusion": 0.6}}}
    for name in ("dsa_step_mfu", "dsa_flash_roofline", "dsa_index_roofline",
                 "dsa_index_share", "dsa_experts_roofline",
                 "moe_experts_share"):
        assert DECLARED[name] == "round"
        value = spec.metric_reader(name).read(traced)
        assert isinstance(value, float) and math.isfinite(value), name
        assert value > 0, name
        try:
            assert spec.metric_reader(name).read(dsa) is None, name
        except spec.UnknownDevice:
            assert name == "dsa_step_mfu"


def test_the_readers_left_out_are_the_trace_readers_by_name(lora, serve):
    """A per-layer metric is fed above or named in ``TRACE_ONLY``: a new
    one must land on one side or the other. Each one left out finds, in a
    real run's context, no trace to read or no peak for this device."""
    fed = {name for _, name in CASES}
    assert set(DECLARED) - fed == TRACE_ONLY
    for name in sorted(TRACE_ONLY):
        ctx = lora if DECLARED[name] == "round" else serve
        try:
            assert spec.metric_reader(name).read(ctx) is None, name
        except spec.UnknownDevice:
            pass
