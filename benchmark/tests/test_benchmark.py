"""The benchmark's own tests: CPU only, tens of seconds. Run with
``python3 -m pytest benchmark/tests -q`` from the root of the checkout
(``JAX_PLATFORMS=cpu``)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.lib import check, common, flops, spec, trace, traffic  # noqa: E402

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]
TRAIN_CELLS = [c for c in CELLS
               if spec.cell(c)["traffic"]["driver"] == "round"]
SERVE_CELLS = [c for c in CELLS
               if spec.cell(c)["traffic"]["driver"] == "serve"]
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# -- the trace reduction ------------------------------------------------ #

def test_union_and_gaps_on_a_made_plane():
    ms = 1_000_000
    plane = {"name": "/device:TPU:0",
             "ops": [(0 * ms, 10 * ms, "%fusion.1"),
                     (5 * ms, 20 * ms, "%fusion.2"),
                     (60 * ms, 80 * ms, '%attn.7 = bf16[8] custom-call(), '
                      'custom_call_target="tpu_custom_call"')],
             "modules": [(0, 20 * ms, "jit_scan_steps(1)"),
                         (60 * ms, 80 * ms, "jit_run(2)")]}
    out = trace.reduce_planes([plane], 0.0, 0.1,
                              host_phases=[(0.0, 0.05, "wait_uplinks"),
                                           (0.05, 0.1, "aggregate")])
    assert out["busy_s"] == pytest.approx(0.040)
    assert out["window_s"] == pytest.approx(0.1)
    # self times: what overlaps a running operation counts once
    assert out["ops_s"]["fusion"] == pytest.approx(0.020)
    assert sum(out["ops_s"].values()) == pytest.approx(out["busy_s"])
    assert out["ops_s"]["attn"] == pytest.approx(0.020)
    assert out["kernel_ops_s"] == {"attn": pytest.approx(0.020)}
    gaps = dict(map(tuple, out["top_gaps"]))
    assert gaps["wait_uplinks: jit_scan_steps -> jit_run"] == \
        pytest.approx(0.040)
    assert gaps["aggregate: jit_run -> end"] == pytest.approx(0.020)
    assert out["module_runs"] == {"jit_scan_steps": 1.0, "jit_run": 1.0}


def test_reduction_of_the_recorded_trace():
    """A trace recorded on a v5e (three runs of one jitted 1024 x 1024
    bf16 product with tanh, 10 ms apart)."""
    path = os.path.join(DATA, "tiny.xplane.pb")
    planes = trace.load_device_events(path)
    assert len(planes) == 1 and planes[0]["ops"]
    with open(os.path.join(DATA, "tiny.json")) as f:
        want = json.load(f)
    first = planes[0]["ops"][0][0]
    last = max(e for _, e, _ in planes[0]["ops"])
    epoch = first > trace.EPOCH_NS
    start = first / 1e9 if epoch else 0.0
    out = trace.reduce_planes(planes, start, last / 1e9)
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert 0 < out["busy_s"] < out["window_s"]
    assert sum(out["module_runs"].values()) == want["module_runs"]
    assert out["top_gaps"], "three runs 10 ms apart leave idle gaps"


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(trace.NoDeviceOps):
        trace.reduce_planes([], 0.0, 1.0)


# -- the FLOPs functions against XLA's count at toy depth --------------- #

def _xla_flops(fn, *args):
    import jax
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return float(cost["flops"])


def test_decoder_flops_against_cost_analysis():
    import jax
    import jax.numpy as jnp
    cfg = dict(spec.cell("internlm2-1.8b.lora-round", rehearse=True)["cfg"])
    cfg["compute_dtype"] = "float32"
    bind = spec.binding(cfg)
    module = bind.build_module(cfg)
    x = jnp.zeros((2, 64), jnp.int32)
    shapes = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), x))
    got = flops.decoder_forward_flops(cfg, 2, 64)
    xla = _xla_flops(lambda v, t: module.apply(v, t), shapes, x)
    # XLA counts the full score matrix (not the causal half) and the
    # elementwise work; the benchmark's count may not pass it
    assert 0.6 * xla <= got <= 1.0 * xla, (got, xla)


def test_vit_flops_against_cost_analysis():
    import jax
    import jax.numpy as jnp
    cfg = dict(spec.cell("vit-b16.full-round", rehearse=True)["cfg"])
    cfg["compute_dtype"] = "float32"
    bind = spec.binding(cfg)
    module = bind.build_module(cfg)
    x = jnp.zeros((4, 16, 16, 3), jnp.float32)
    shapes = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), x))
    got = flops.vit_forward_flops(cfg, 4)
    xla = _xla_flops(lambda v, t: module.apply(v, t), shapes, x)
    assert 0.7 * xla <= got <= 1.0 * xla, (got, xla)


def test_train_step_flops_factors():
    lora = spec.cell("internlm2-1.8b.lora-round")
    full = spec.cell("vit-b16.full-round")
    s = lora["traffic"]["shape"]
    assert flops.train_step_flops(lora["cfg"], s) == pytest.approx(
        2 * flops.decoder_forward_flops(lora["cfg"], s["batch"], s["seq"]))
    assert flops.train_step_flops(full["cfg"], full["traffic"]["shape"]) == \
        pytest.approx(3 * flops.vit_forward_flops(
            full["cfg"], full["traffic"]["shape"]["batch"]))
    # GQA: wk and wv at KV width, not at full width
    mha = dict(lora["cfg"], num_key_value_heads=16)
    assert flops.decoder_matmul_flops_per_token(lora["cfg"]) < \
        flops.decoder_matmul_flops_per_token(mha)


# -- traffic from the seed ---------------------------------------------- #

@pytest.mark.parametrize("name", SERVE_CELLS)
def test_traffic_is_reproducible_and_seed_keeps_the_work(name):
    t = spec.cell(name)["traffic"]
    block = traffic.request_block(t)
    assert len(block) == t["block"]
    counts = {g: sum(1 for p, _ in block if p == g)
              for g in t["prompt_grid"]}
    for g, w in zip(t["prompt_grid"], t["prompt_weights"]):
        assert abs(counts[g] - w * t["block"]) < 1
    assert all(t["out_min"] <= o <= t["out_max"] for _, o in block)

    def first(seed, n=len(block)):
        stream = traffic.SharedStream(t, 1000, seed)
        return [stream.take() for _ in range(n)]

    a, b, c = first(2 ** 31 + 7), first(2 ** 31 + 7), first(11)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1]
               for x, y in zip(a, b))
    sizes = lambda reqs: sorted((len(p), o) for p, o in reqs)  # noqa: E731
    assert sizes(a) == sizes(c) == sorted(block)
    assert [len(p) for p, _ in a] != [len(p) for p, _ in c]


# -- the harness refuses what it was not written for -------------------- #

def test_unknown_device_kind_is_an_error():
    with pytest.raises(spec.UnknownDevice):
        spec.peaks("TPU v9 imaginary")
    assert spec.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    report = {"platform": "tpu", "device_kind": "TPU v9 imaginary",
              "device_ids": [0]}
    with pytest.raises(spec.UnknownDevice):
        common.check_device(report, "tpu", 1)


def test_a_non_tpu_device_is_refused():
    report = {"platform": "cpu", "device_kind": "cpu", "device_ids": [0]}
    with pytest.raises(common.BenchFailure):
        common.check_device(report, "tpu", 1)
    report = {"platform": "tpu", "device_kind": "TPU v5 lite",
              "device_ids": [0]}
    with pytest.raises(common.BenchFailure):
        common.check_device(report, "tpu", 4)


def _run(*args, timeout=600):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, "benchmark/run.py", *args],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_the_measuring_command_fails_without_a_tpu():
    done = _run("--workload", TRAIN_CELLS[0], "--seed", "5", "--seconds",
                "1", "--trace", "0")
    assert done.returncode != 0
    assert '"correct"' not in done.stdout


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_drives_the_plumbing_and_prints_no_metric(name):
    done = _run("--workload", name, "--seed", str(2 ** 31 + 5),
                "--seconds", "3", "--trace", "1", "--rehearse")
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["compared"]) == set(spec.cell(name)["limits"])
    stderr_tail = done.stderr.strip().splitlines()[-len(line["compared"]):]
    assert all(ln.startswith("compared ") for ln in stderr_tail)


# -- the control and the faults come out as not correct ----------------- #

@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_training_control_and_reference_fault_fail_a_limit(name):
    """The reference in the nearest precision below (fp8) put in the
    program's place, and the reference with half the batch left out, each
    fail one of the cell's limits; the reference itself passes all."""
    from benchmark.lib import refproc
    cell = spec.cell(name, rehearse=True)
    out = refproc._train({"cfg": cell["cfg"],
                          "shape": cell["traffic"]["shape"],
                          "seed": 2 ** 31 + 9, "extras": True,
                          "learning_rate": cell["traffic"]["learning_rate"]})

    def verdict(key):
        numbers = check.train_numbers(
            out[key]["loss"],
            {n: v["change"] for n, v in out[key]["leaf"].items()},
            out["reference"])
        return check.verdict(numbers, cell["limits"])[0]

    assert verdict("reference")
    assert not verdict("control")
    assert not verdict("fault_half_batch")
    unchanged = check.train_numbers(
        out["reference"]["loss"],
        {n: 0.0 for n in out["reference"]["leaf"]}, out["reference"])
    assert unchanged["change_gap"] == pytest.approx(1.0)
    assert not check.verdict(unchanged, cell["limits"])[0]


@pytest.mark.parametrize("name", SERVE_CELLS)
def test_serving_control_fails_the_limit(name):
    from benchmark.lib import refproc
    from benchmark.reference import decoder_lm
    cell = spec.cell(name, rehearse=True)
    cfg = cell["cfg"]
    rng = np.random.default_rng(3)
    lora, base = decoder_lm.make_weights(cfg, 17)
    import jax.numpy as jnp
    requests = []
    for plen in (5, 9, 12):
        seq = rng.integers(0, cfg["vocab_size"], (plen,)).tolist()
        for _ in range(6):      # greedy by the reference itself
            lg = decoder_lm.logits(base, lora, jnp.asarray([seq]), cfg)
            seq.append(int(jnp.argmax(lg[0, -1])))
        requests.append({"prompt": seq[:plen], "tokens": seq[plen:]})
    out = refproc._serve({"cfg": cfg, "seed": 17, "requests": requests,
                          "extras": True})
    assert out["reference"]["token_gap"] == pytest.approx(0.0, abs=1e-5)
    limit = cell["limits"]["token_gap"]
    assert out["control"]["token_gap"] > limit


@pytest.mark.parametrize("name,fault", (
    [(c, f) for c in TRAIN_CELLS for f in ("state_unchanged", "half_batch")]
    + [(c, "token_altered") for c in SERVE_CELLS]))
def test_a_fault_under_the_timed_path_reads_not_correct(name, fault):
    """Past the look for a chip, the rest of a run with the timed path
    broken underneath (in a process of its own: the harness's parent may
    not hold a JAX backend, and this test process does)."""
    code = (
        "import json, shutil, sys; sys.path.insert(0, %r)\n"
        "from benchmark.lib import common\n"
        "from benchmark.run import drive\n"
        "common.prepare_environment()\n"
        "out = drive(%r, 2 ** 31 + 21, 3.0, False, rehearse=True, "
        "fault=%r)\n"
        "shutil.rmtree(out['workdir'], ignore_errors=True)\n"
        "print(json.dumps([out['correct'], out['attempted'], "
        "out['compared']]))\n" % (ROOT, name, fault))
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    correct, attempted, compared = json.loads(
        done.stdout.strip().splitlines()[-1])
    assert correct is False, compared
    assert attempted > 0
