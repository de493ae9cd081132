#!/usr/bin/env python3
"""First proof that the system starts on the chip: ``python3 chip_smoke.py``.

Drives the main path once, through the entry points a user calls, at the
full width of LlamaLite-201M (vocab 32768, dim 1024, depth 8, heads 16,
bf16, sequence 1024, batch 8 — 201,344,000 parameters, random weights from
a seed), and checks what comes out:

1. **device phase** (one child that owns every chip): the flash-attention
   kernels compiled by Mosaic — forward and ``jax.grad`` against
   ``_dense_attention`` — at the 201M model's attention shape, with 4 KV
   heads, and at L=4096; the ring and Ulysses block kernels over
   ``sp=<chips>`` when there are several chips; two ``FlaxModelOps.train``
   steps of the 201M model with ``use_flash=True``; one
   ``PodFederationDriver`` round over ``fed=<chips>`` against a per-learner
   reference.
2. **federation phase**: ``DriverSession`` boots
   ``python -m metisfl_tpu.controller`` on the CPU and one
   ``python -m metisfl_tpu.learner`` per chip; two rounds of real local
   steps on the scan path, full-model uplinks over gRPC, the host fold, the
   downlink, evaluation, and a registry version per round.
3. **serving phase**: the learners exit, ``python -m metisfl_tpu.serving``
   takes a chip, installs the promoted community model from the running
   controller's registry and answers a burst of ``ServingClient.generate``
   calls through the continuous-batching decoder.

One process owns a chip at a time, so this parent never initializes a JAX
backend (asserted before the first launch) and every chip holder exits
cleanly before the next phase starts. Every chip process is launched with
``JAX_PLATFORMS=tpu`` said outright: without a chip it fails; it never
falls back to the CPU. Any failed check exits non-zero with the offending
process's log tail. The last stdout line is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Wall times printed per phase are set-up facts of a cold (or cache-warm)
start, not performance measurements.

``--rehearse`` runs the same script at toy shapes on the CPU (kernels in
interpret mode) to debug the plumbing off-chip; its result line says
``"platform": "cpu"`` and ``"rehearsal": true``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

# a directory that holds this script and nothing else of the repo fails here
from metisfl_tpu.comm.messages import TrainParams  # noqa: E402
from metisfl_tpu.config import (EvalConfig, FederationConfig,  # noqa: E402
                                PromotionConfig, RegistryConfig,
                                ServingConfig, ServingDecodeConfig,
                                TerminationConfig)
from metisfl_tpu.driver.session import (DriverSession,  # noqa: E402
                                        _terminate_process)
from metisfl_tpu.platform import DEVICE_MARKER  # noqa: E402
from metisfl_tpu.tensor.pytree import ModelBlob  # noqa: E402

DEVICE_RESULT_MARKER = "CHIP_SMOKE_DEVICE_PHASE"

FULL = dict(vocab=32768, dim=1024, depth=8, heads=16, seq=1024, batch=8,
            local_steps=8, scan_chunk=4, params=201_344_000,
            flash_shapes=[(2, 16, 16, 1024, 64), (2, 16, 4, 1024, 64),
                          (2, 16, 16, 4096, 64)],
            sp_shape=(2, 16, 2048, 64),
            prompt_lens=(8, 24, 64), new_tokens=16, slots=4, max_len=128)
TOY = dict(vocab=256, dim=64, depth=2, heads=4, seq=32, batch=2,
           local_steps=4, scan_chunk=2, params=None,
           flash_shapes=[(1, 4, 4, 128, 16), (1, 4, 2, 128, 16)],
           sp_shape=(1, 4, 256, 16),
           prompt_lens=(3, 5, 9), new_tokens=4, slots=2, max_len=32)

ROUNDS = 2
DEVICE_PHASE_DEADLINE_S = 600
ROUNDS_DEADLINE_S = 480
SERVING_READY_DEADLINE_S = 240
BURST_DEADLINE_S = 240
# flash vs dense, both in bf16 with fp32 softmax statistics: worst
# element over the largest reference magnitude
KERNEL_TOL = 5e-2


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def log(message: str) -> None:
    print(f"[chip_smoke +{time.time() - _T0:6.1f}s] {message}", flush=True)


_T0 = time.time()


# --------------------------------------------------------------------- #
# the model, as learner and gateway processes build it
# --------------------------------------------------------------------- #

def make_recipe(shape: dict, seed: int):
    """Learner recipe: LlamaLite at ``shape`` + a seeded random-token
    shard. Runs inside the learner / gateway / init-model child."""
    vocab, dim, depth, heads = (shape["vocab"], shape["dim"],
                                shape["depth"], shape["heads"])
    seq = shape["seq"]
    n_train = shape["batch"] * shape["local_steps"]
    n_test = shape["batch"]

    def recipe():
        import jax.numpy as jnp

        from metisfl_tpu.models import ArrayDataset, FlaxModelOps
        from metisfl_tpu.models.zoo import LlamaLite

        rng = np.random.default_rng(seed)
        x = rng.integers(0, vocab, (n_train + n_test, seq)).astype(np.int32)
        y = np.roll(x, -1, axis=1)
        ops = FlaxModelOps(
            LlamaLite(vocab_size=vocab, dim=dim, depth=depth, heads=heads,
                      dtype=jnp.bfloat16), x[:1], rng_seed=0)
        return (ops, ArrayDataset(x[:n_train], y[:n_train], seed=seed), None,
                ArrayDataset(x[n_train:], y[n_train:], seed=seed))

    return recipe


# --------------------------------------------------------------------- #
# device phase: one child process that owns every chip
# --------------------------------------------------------------------- #

def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / max(1e-6, float(np.max(np.abs(want)))))


def _attention_pair(fn, reference, args, expect_mosaic: bool, label: str):
    """Forward + VJP of ``fn`` against ``reference`` on the same inputs;
    the compiled program must carry the Mosaic custom call."""
    import jax

    def with_grads(f):
        def run(q, k, v, ct):
            out, vjp = jax.vjp(f, q, k, v)
            return (out, *vjp(ct))
        return jax.jit(run)

    t0 = time.time()
    compiled = with_grads(fn).lower(*args).compile()
    compile_s = time.time() - t0
    if expect_mosaic:
        check("tpu_custom_call" in compiled.as_text(),
              f"{label}: no Mosaic custom call in the compiled program — "
              "the kernel fell to interpret mode")
    got = jax.block_until_ready(compiled(*args))
    want = jax.block_until_ready(with_grads(reference)(*args))
    errs = {}
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        check(bool(np.all(np.isfinite(np.asarray(g, np.float32)))),
              f"{label}: non-finite {name}")
        errs[name] = round(_rel_err(g, w), 5)
        check(errs[name] <= KERNEL_TOL,
              f"{label}: {name} off the dense reference by {errs[name]} "
              f"(tolerance {KERNEL_TOL})")
    return {"check": label, "compile_s": round(compile_s, 2), "err": errs}


def device_phase(shape: dict, expect_platform: str) -> dict:
    from metisfl_tpu.platform import announce_devices, enter_process

    cache_dir = enter_process()
    report = announce_devices("chip_smoke.device")
    check(report["platform"] == expect_platform,
          f"device phase is on {report['platform']!r}, "
          f"expected {expect_platform!r}")

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from metisfl_tpu.ops import flash_attention
    from metisfl_tpu.ops.flash_attention import _dense_attention
    from metisfl_tpu.telemetry import runtime

    n_dev = jax.device_count()
    on_chip = expect_platform == "tpu"
    result = {"devices": report, "device_count": n_dev,
              "cache_dir": cache_dir, "kernels": [], "walls_s": {}}

    def rand(key, shp):
        return jax.random.normal(jax.random.PRNGKey(key), shp, jnp.bfloat16)

    # F. flash kernels, compiled, forward and backward
    t0 = time.time()
    for B, H, Hkv, L, D in shape["flash_shapes"]:
        args = (rand(1, (B, H, L, D)), rand(2, (B, Hkv, L, D)),
                rand(3, (B, Hkv, L, D)), rand(4, (B, H, L, D)))

        def dense(q, k, v, group=H // Hkv):
            if group > 1:
                k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
            return _dense_attention(q, k, v, True)

        result["kernels"].append(_attention_pair(
            lambda q, k, v: flash_attention(q, k, v, True), dense, args,
            on_chip, f"flash B{B} H{H} KV{Hkv} L{L} D{D}"))
    result["walls_s"]["flash_kernels"] = round(time.time() - t0, 1)

    # ring / Ulysses block kernels inside shard_map over real devices
    if n_dev > 1:
        from metisfl_tpu.parallel.ringattn import (make_ring_attention,
                                                   reference_attention)
        from metisfl_tpu.parallel.ulysses import make_ulysses_attention

        t0 = time.time()
        mesh = Mesh(np.array(jax.devices()), ("sp",))
        B, H, L, D = shape["sp_shape"]
        sharded = NamedSharding(mesh, P(None, None, "sp", None))
        args = tuple(jax.device_put(rand(10 + i, (B, H, L, D)), sharded)
                     for i in range(4))
        ref = lambda q, k, v: reference_attention(q, k, v, causal=True)
        result["kernels"].append(_attention_pair(
            make_ring_attention(mesh, causal=True, block_kernels=True),
            ref, args, on_chip, f"ring sp={n_dev} L{L}"))
        result["kernels"].append(_attention_pair(
            make_ulysses_attention(mesh, causal=True,
                                   min_flash_seq=L // n_dev),
            ref, args, on_chip, f"ulysses sp={n_dev} L{L}"))
        result["walls_s"]["sp_kernels"] = round(time.time() - t0, 1)

    # two optimizer steps of the full model on the flash path
    from metisfl_tpu.models import ArrayDataset, FlaxModelOps
    from metisfl_tpu.models.zoo import LlamaLite

    t0 = time.time()
    rng = np.random.default_rng(4)
    x = rng.integers(0, shape["vocab"],
                     (2 * shape["batch"], shape["seq"])).astype(np.int32)
    module = LlamaLite(vocab_size=shape["vocab"], dim=shape["dim"],
                       depth=shape["depth"], heads=shape["heads"],
                       use_flash=True, dtype=jnp.bfloat16)
    ops = FlaxModelOps(module, x[:1])
    if on_chip:
        lowered = jax.jit(lambda v, t: module.apply(v, t)).lower(
            ops.variables, x[:shape["batch"]])
        check("tpu_custom_call" in lowered.as_text(),
              "use_flash=True model lowers without the Mosaic custom call")
    out = ops.train(ArrayDataset(x, np.roll(x, -1, axis=1)),
                    TrainParams(batch_size=shape["batch"], local_steps=2,
                                optimizer="adam", learning_rate=1e-4))
    check(out.completed_steps == 2, "flash train steps did not complete")
    check(bool(np.isfinite(out.train_metrics["loss"])),
          f"flash train loss is {out.train_metrics['loss']}")
    result["flash_train"] = {"params": ops.param_count(),
                             "loss": round(out.train_metrics["loss"], 4)}
    result["walls_s"]["flash_train_2_steps"] = round(time.time() - t0, 1)
    del ops, out

    # the pod round: shard_map + psum over every device
    from metisfl_tpu.config import AggregationConfig
    from metisfl_tpu.driver.pod import PodFederationDriver
    from metisfl_tpu.models.zoo import MLP
    from metisfl_tpu.parallel.mesh import federation_mesh
    from metisfl_tpu.parallel.podfed import PodFederation

    t0 = time.time()
    w = rng.standard_normal((16, 4)).astype(np.float32)
    shards = []
    for i in range(n_dev):
        sx = rng.standard_normal((64 + 16 * i, 16)).astype(np.float32)
        shards.append(ArrayDataset(sx, np.argmax(sx @ w, -1).astype(np.int32),
                                   seed=i))
    pod_cfg = FederationConfig(
        aggregation=AggregationConfig(scaler="train_dataset_size"),
        train=TrainParams(batch_size=16, local_steps=4, learning_rate=0.05),
        eval=EvalConfig(every_n_rounds=0))
    mlp = MLP(features=(32,), num_outputs=4)
    driver = PodFederationDriver(pod_cfg, mlp, shards,
                                 mesh=federation_mesh(n_dev))
    xs, ys = driver._draw_round_batches(0)
    scales = driver._scales()
    pod_out = driver.run_round()
    check(bool(np.isfinite(pod_out["mean_loss"])),
          f"pod round loss is {pod_out['mean_loss']}")
    community = jax.tree.leaves(driver.pod.community_params())
    # reference: each learner's local training alone on a one-device
    # fed=1 mesh, folded with the same scales on the host
    expected = None
    for i in range(n_dev):
        solo = PodFederation(mlp, shards[0].x[:2], 1,
                             train_params=pod_cfg.train,
                             mesh=federation_mesh(
                                 1, devices=jax.devices()[:1]))
        solo.run_round(xs[i:i + 1], ys[i:i + 1])
        leaves = [np.asarray(t, np.float64) * float(scales[i])
                  for t in jax.tree.leaves(solo.community_params())]
        expected = leaves if expected is None else [
            a + b for a, b in zip(expected, leaves)]
    pod_err = max(_rel_err(g, e) for g, e in zip(community, expected))
    check(pod_err <= 1e-4,
          f"pod community model off its per-learner reference by {pod_err}")
    result["pod"] = {"fed": n_dev, "mean_loss": round(pod_out["mean_loss"], 4),
                     "ref_err": pod_err}
    result["walls_s"]["pod_round"] = round(time.time() - t0, 1)

    result["compiles"] = _compile_rows(runtime.collect_state())
    return result


def run_device_phase(workdir: str, platform: str, rehearse: bool) -> dict:
    """Launch the device phase in a child that owns every chip; return
    its result record."""
    env = {**os.environ, "JAX_PLATFORMS": platform}
    if rehearse:
        env.setdefault("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    argv = [sys.executable, os.path.abspath(__file__), "--phase", "device"]
    if rehearse:
        argv.append("--rehearse")
    log_path = os.path.join(workdir, "device_phase.log")
    with open(log_path, "w") as log_f:
        child = subprocess.Popen(argv, env=env, stdout=log_f,
                                 stderr=subprocess.STDOUT)
        try:
            code = child.wait(timeout=DEVICE_PHASE_DEADLINE_S)
        except subprocess.TimeoutExpired:
            # SIGTERM first: a SIGKILLed chip holder can leave the chip
            # locked for the next phase
            _terminate_process(child, grace_s=30.0)
            raise SmokeFailure(
                f"device phase exceeded {DEVICE_PHASE_DEADLINE_S}s\n"
                + _tail(log_path))
    if code != 0:
        raise SmokeFailure(f"device phase exited {code}\n" + _tail(log_path))
    with open(log_path) as f:
        lines = f.read().splitlines()
    records = [ln for ln in lines if ln.startswith(DEVICE_RESULT_MARKER)]
    check(len(records) == 1, "device phase printed no result\n"
          + _tail(log_path))
    return json.loads(records[0][len(DEVICE_RESULT_MARKER):])


# --------------------------------------------------------------------- #
# federation + serving phases (this process: no JAX backend, ever)
# --------------------------------------------------------------------- #

def _tail(path: str, nbytes: int = 3000) -> str:
    try:
        with open(path, errors="replace") as f:
            return f"--- tail of {path}\n{f.read()[-nbytes:]}"
    except OSError as exc:
        return f"--- {path}: {exc}"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _device_lines(session: DriverSession, prefix: str) -> dict:
    """{process name: device report} parsed from child logs."""
    out = {}
    for proc in session._procs:
        if not proc.name.startswith(prefix):
            continue
        with open(proc.log_path, errors="replace") as f:
            hits = [ln for ln in f.read().splitlines()
                    if ln.startswith(DEVICE_MARKER)]
        check(bool(hits), f"{proc.name} printed no device report\n"
              + _tail(proc.log_path))
        out[proc.name] = json.loads(hits[-1][len(DEVICE_MARKER):])
    return out


def _check_owned_devices(reports: dict, platform: str, kind: str,
                         split: bool) -> None:
    for name, rep in reports.items():
        check(rep["platform"] == platform,
              f"{name} runs on {rep['platform']!r}, expected {platform!r}")
        check(rep["device_kind"] == kind,
              f"{name} reports device_kind {rep['device_kind']!r}, the "
              f"probe saw {kind!r}")
        if split:
            check(len(rep["device_ids"]) == 1,
                  f"{name} sees {len(rep['device_ids'])} chips, not 1")
    if split:
        chips = [rep["visible_chips"] for rep in reports.values()]
        check(len(set(chips)) == len(chips),
              f"chips shared between processes: {chips}")


def federation_and_serving(workdir: str, shape: dict, platform: str,
                           n_chips: int, kind: str) -> dict:
    import jax._src.xla_bridge as xla_bridge

    n_learners = n_chips
    recipes = [make_recipe(shape, seed=100 + i) for i in range(n_learners)]
    config = FederationConfig(
        controller_port=_free_port(),
        train=TrainParams(batch_size=shape["batch"],
                          local_steps=shape["local_steps"],
                          scan_chunk=shape["scan_chunk"],
                          optimizer="adam", learning_rate=1e-4),
        eval=EvalConfig(batch_size=shape["batch"], datasets=["test"],
                        metrics=["accuracy"]),
        # every evaluated version is promoted: the burst must be served
        # by a model the rounds produced
        registry=RegistryConfig(
            enabled=True, promotion=PromotionConfig(metric="")),
        serving=ServingConfig(
            enabled=True, poll_every_s=1.0,
            decode=ServingDecodeConfig(slots=shape["slots"],
                                       max_len=shape["max_len"])),
        # monitor_federation returns normally at the cutoff; the round
        # count is checked below
        termination=TerminationConfig(
            federation_rounds=ROUNDS,
            execution_cutoff_mins=ROUNDS_DEADLINE_S / 60.0),
    )
    result: dict = {"learners": n_learners, "walls_s": {}}
    t0 = time.time()
    session = DriverSession(config, None, recipes,
                            workdir=os.path.join(workdir, "federation"),
                            accelerator=platform, host_chips=n_chips)
    model_bytes = len(session.initial_blob)
    n_params = sum(int(np.prod(a.shape)) for _, a in
                   ModelBlob.from_bytes(session.initial_blob).tensors)
    if shape["params"] is not None:
        check(n_params == shape["params"],
              f"model has {n_params} parameters, not {shape['params']}")
    result.update(params=n_params, model_bytes=model_bytes)
    result["walls_s"]["initial_model_cpu_child"] = round(time.time() - t0, 1)
    log(f"initial model built in a CPU child: {n_params} parameters, "
        f"{model_bytes} wire bytes")

    check(not xla_bridge.backends_are_initialized(),
          "the launching parent initialized a JAX backend")
    try:
        # -- rounds ---------------------------------------------------- #
        t0 = time.time()
        session.initialize_federation(launch_serving=False)
        stats = session.monitor_federation(poll_every_s=1.0,
                                           eval_drain_timeout_s=120.0)
        result["walls_s"]["rounds"] = round(time.time() - t0, 1)
        done = stats["global_iteration"]
        check(done >= ROUNDS, f"only {done} of {ROUNDS} rounds completed "
              f"within {ROUNDS_DEADLINE_S}s")
        learner_ids = sorted(stats["learners"])
        check(len(learner_ids) == n_learners,
              f"{len(learner_ids)} learners registered, not {n_learners}")
        rounds = []
        for meta in stats["round_metadata"][:ROUNDS]:
            rid = meta["global_iteration"]
            check(sorted(meta["selected_learners"]) == learner_ids,
                  f"round {rid} folded {meta['selected_learners']}, not "
                  f"every learner {learner_ids}")
            check(not meta["errors"], f"round {rid}: {meta['errors']}")
            check(meta["registered_version"] > 0,
                  f"round {rid} minted no registry version")
            profile = meta["profile"]["learners"]
            for lid in learner_ids:
                loss = meta["train_metrics"][lid]["loss"]
                check(bool(np.isfinite(loss)),
                      f"round {rid}: {lid} reports loss {loss}")
                uplink = meta["uplink_bytes"][lid]
                check(abs(uplink - model_bytes) <= model_bytes // 1000,
                      f"round {rid}: {lid} shipped {uplink} bytes, the "
                      f"model is {model_bytes}")
                dev_kind = profile[lid]["device"]["device_kind"]
                check(dev_kind == kind,
                      f"round {rid}: {lid} trained on {dev_kind!r}, "
                      f"not {kind!r}")
            rounds.append({
                "round": rid,
                "wall_s": round(meta["completed_at"] - meta["started_at"],
                                1),
                "loss": {lid: round(meta["train_metrics"][lid]["loss"], 4)
                         for lid in learner_ids},
                "uplink_bytes": meta["uplink_bytes"][learner_ids[0]],
                "version": meta["registered_version"],
                "host_fold": meta["host_fold"],
                "phases_ms": {k: round(v) for k, v in
                              meta["profile"]["phases"].items()},
            })
        result["rounds"] = rounds
        check(any(e["evaluations"] for e in stats["community_evaluations"]),
              "no community evaluation reported back")
        learners = _device_lines(session, "learner_")
        _check_owned_devices(learners, platform, kind, split=n_chips > 1)
        result["learner_devices"] = learners
        ctrl_log = next(p.log_path for p in session._procs
                        if p.name == "controller")
        with open(ctrl_log, errors="replace") as f:
            check(DEVICE_MARKER not in f.read(),
                  "the controller reported owning a device")
        log(f"{done} rounds folded {n_learners} learner(s): "
            + json.dumps(rounds))

        # -- serving --------------------------------------------------- #
        t0 = time.time()
        session.stop_learners(timeout_s=120.0)
        result["walls_s"]["stop_learners"] = round(time.time() - t0, 1)
        # with no learner left nothing can be promoted any more: this is
        # the head the gateway must install, and it cannot swap mid-burst
        head = session._client.describe_registry()["stable"]
        first_version = min(r["version"] for r in rounds)
        check(head >= first_version,
              f"registry stable head v{head} is older than the rounds' "
              f"first version v{first_version}")
        t0 = time.time()
        session.launch_serving()
        client = session.serving_client()
        installed = {}
        deadline = time.time() + SERVING_READY_DEADLINE_S
        while time.time() < deadline:
            session._check_procs_alive()
            try:
                installed = client.status(timeout=5.0, wait_ready=False)[
                    "installed"]
            except Exception:  # noqa: BLE001 - still booting; retried
                installed = {}
            if installed.get("stable") == head:
                break
            time.sleep(1.0)
        check(installed.get("stable") == head,
              f"the gateway did not install stable v{head} within "
              f"{SERVING_READY_DEADLINE_S}s (installed: {installed})")
        result["walls_s"]["gateway_ready"] = round(time.time() - t0, 1)

        t0 = time.time()
        burst = serving_burst(client, shape)
        result["walls_s"]["burst"] = round(time.time() - t0, 1)
        status = client.status()
        decode = status.get("decode", {})
        check(bool(decode), "the gateway armed no decode engine")
        metrics = client.get_metrics()
        recompiles = [ln for ln in metrics.splitlines()
                      if ln.startswith("jax_compiles_total")
                      and 'fn="decode.step"' in ln
                      and 'kind="recompile"' in ln]
        check(not recompiles,
              f"decode.step recompiled after its first step: {recompiles}")
        client.close()
        gateways = _device_lines(session, "serving")
        _check_owned_devices(gateways, platform, kind, split=n_chips > 1)
        result["gateway_devices"] = gateways
        result["burst"] = burst
        result["decode"] = {ch: {k: d[k] for k in
                                 ("slots", "steps", "tokens_emitted",
                                  "version")}
                            for ch, d in decode.items()}
        log(f"gateway served v{head}: " + json.dumps(burst))
    except Exception:
        for proc in session._procs:
            print(_tail(proc.log_path), file=sys.stderr, flush=True)
        raise
    finally:
        session.shutdown_federation()
    codes = session.process_exit_codes()
    check(all(c == 0 for c in codes.values()),
          f"processes did not all exit cleanly: {codes}")
    runtime_path = os.path.join(session.workdir, "runtime-fleet.json")
    if os.path.exists(runtime_path):
        with open(runtime_path) as f:
            result["compiles"] = _compile_summary(json.load(f))
    return result


def _compile_rows(state: dict) -> dict:
    """{fn: [compiles, of which persistent-cache hits, seconds]} of one
    process's runtime plane (telemetry/runtime.py)."""
    return {fn: [int(r.get("cold", 0)) + int(r.get("recompiles", 0)),
                 int(r.get("cache_hits", 0)),
                 round(float(r.get("total_s", 0.0)), 1)]
            for fn, r in ((state or {}).get("fns") or {}).items()}


def _compile_summary(fleet: dict) -> dict:
    """Per peer, what each process of the federation compiled."""
    return {peer: _compile_rows(state)
            for peer, state in (fleet.get("peers") or {}).items()}


def serving_burst(client, shape: dict) -> dict:
    """More requests than slots, three prompt lengths, all in flight at
    once; one prompt is also sent alone first and must come back
    bit-identical from inside the burst (the decoder's contract)."""
    rng = np.random.default_rng(7)
    new = shape["new_tokens"]
    prompts = [rng.integers(0, shape["vocab"], (L,)).astype(np.int32)
               for L in shape["prompt_lens"]]

    def generate(prompt):
        reply = client.generate(prompt, max_new_tokens=new,
                                timeout=BURST_DEADLINE_S)
        toks = np.asarray(client.tokens(reply)).reshape(-1)
        check(len(toks) == new,
              f"generate returned {len(toks)} tokens, asked for {new}")
        check(bool(np.all((toks >= 0) & (toks < shape["vocab"]))),
              f"generate returned out-of-vocabulary tokens {toks}")
        return toks

    solo = generate(prompts[0])
    n_requests = 2 * shape["slots"] - 1
    outs: list = [None] * n_requests
    errors: list = []

    def worker(i):
        try:
            outs[i] = generate(prompts[i % len(prompts)])
        except Exception as exc:  # noqa: BLE001 - re-raised below
            errors.append(f"request {i}: {exc!r}")

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(n_requests)]
    for t in threads:
        t.start()
    deadline = time.time() + BURST_DEADLINE_S
    for t in threads:
        t.join(timeout=max(0.1, deadline - time.time()))
    check(not any(t.is_alive() for t in threads),
          f"burst did not finish within {BURST_DEADLINE_S}s")
    check(not errors, f"generate failed: {errors}")
    for i in range(0, n_requests, len(prompts)):
        check(np.array_equal(outs[i], solo),
              f"request {i} (prompt 0 inside the burst) decoded "
              f"{outs[i].tolist()}, alone it decoded {solo.tolist()}")
    return {"requests": n_requests + 1, "slots": shape["slots"],
            "prompt_lens": list(shape["prompt_lens"]), "new_tokens": new,
            "first_tokens": solo[:4].tolist()}


# --------------------------------------------------------------------- #

def main() -> int:
    parser = argparse.ArgumentParser("chip_smoke")
    parser.add_argument("--phase", choices=["all", "device"], default="all")
    parser.add_argument("--rehearse", action="store_true",
                        help="toy shapes on the CPU, kernels in interpret "
                             "mode: debugs the plumbing, proves nothing "
                             "about the chip")
    parser.add_argument("--logs-to", default="",
                        help="copy every process log here on exit "
                             "(e.g. chiprun_out/smoke_logs)")
    args = parser.parse_args()
    shape = TOY if args.rehearse else FULL
    platform = "cpu" if args.rehearse else "tpu"

    if args.phase == "device":
        result = device_phase(shape, platform)
        print(DEVICE_RESULT_MARKER + json.dumps(result), flush=True)
        return 0

    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    log(f"workdir {workdir}")
    try:
        return run_all(workdir, shape, platform, args.rehearse)
    finally:
        if args.logs_to:
            _copy_logs(workdir, args.logs_to)
        shutil.rmtree(workdir, ignore_errors=True)


def _copy_logs(workdir: str, dest: str) -> None:
    os.makedirs(dest, exist_ok=True)
    for dirpath, _dirs, files in os.walk(workdir):
        for name in files:
            if name.endswith(".log") or name in ("runtime-fleet.json",
                                                 "experiment.json"):
                shutil.copy(os.path.join(dirpath, name),
                            os.path.join(dest, name))


def run_all(workdir: str, shape: dict, platform: str,
            rehearse: bool) -> int:
    t0 = time.time()
    device = run_device_phase(workdir, platform, rehearse)
    report = device["devices"]
    n_chips = device["device_count"]
    log(f"device phase passed in {time.time() - t0:.0f}s on "
        f"{n_chips} x {report['device_kind']} (jax {report['jax']}, jaxlib "
        f"{report['jaxlib']}, libtpu {report['libtpu']}): "
        + json.dumps({k: device[k] for k in
                      ("kernels", "flash_train", "pod", "walls_s",
                       "compiles", "cache_dir")}))

    fed = federation_and_serving(workdir, shape, platform, n_chips,
                                 report["device_kind"])
    log("federation + serving passed: " + json.dumps(
        {k: fed[k] for k in ("walls_s", "learner_devices",
                             "gateway_devices", "compiles", "decode")
         if k in fed}))
    log(f"total {time.time() - _T0:.0f}s")
    final = {"ok": True,
             "device": {"platform": report["platform"],
                        "kind": report["device_kind"], "count": n_chips}}
    if rehearse:
        final["rehearsal"] = True
    print(json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as exc:
        print(f"chip_smoke FAILED: {exc}", file=sys.stderr, flush=True)
        sys.exit(1)
