"""Driver of the ``serve`` kind of traffic: the gateway on the chip behind
gRPC, clients in this process. Set-up mints the registry's stable version
the only way the registry allows, by a round: at learning rate 0, by a
learner on the CPU that holds the shipped subset alone, so that the version
is exactly the seeded adapters and no learner ever holds the chip. Then it
boots the gateway (the seeded base made on the device, the adapters
installed over it from the registry) and warms every prompt length of the
grid and the step."""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from benchmark.lib import check, common, spec, traffic as traffic_lib
from benchmark.lib import trace as trace_lib
from benchmark.lib.common import BenchFailure, log
from benchmark.lib.recipes import MINT_ENV, Probe, Recipe

READY_DEADLINE_S = 600.0
REQUEST_TIMEOUT_S = 120.0


def _wait(what: str, cond, session, deadline_s: float = READY_DEADLINE_S,
          logs=()):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        session._check_procs_alive()
        got = cond()
        if got:
            return got
        time.sleep(0.2)
    raise BenchFailure(f"{what} not reached within {deadline_s}s\n"
                       + "\n".join(common.tail(p) for p in logs))


def run(cell: dict, seed: int, seconds: float, trace: bool, platform: str,
        started: float, work: str, extras: bool = False,
        fault: str = "") -> dict:
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (EvalConfig, FederationConfig,
                                    PromotionConfig, RegistryConfig,
                                    ServingConfig, ServingDecodeConfig,
                                    TerminationConfig)
    from metisfl_tpu.driver.session import DriverSession
    from metisfl_tpu.serving.service import ServingClient

    cfg, traffic = cell["cfg"], cell["traffic"]
    bind, ref = spec.binding(cfg), spec.reference(cfg)
    vocab = ref.sizes(cfg)["vocab"]
    probe = Probe(os.path.join(work, "control"))
    mint = traffic["mint_shape"]
    config = FederationConfig(
        controller_port=common.free_port(),
        train=TrainParams(batch_size=int(mint["batch"]),
                          local_steps=int(mint["local_steps"]),
                          scan_chunk=int(mint["scan_chunk"]),
                          optimizer="sgd", learning_rate=0.0,
                          ship_tensor_regex=traffic["ship_tensor_regex"]),
        eval=EvalConfig(every_n_rounds=0),
        registry=RegistryConfig(enabled=True,
                                promotion=PromotionConfig(auto=False)),
        serving=ServingConfig(
            enabled=True, poll_every_s=0.5,
            decode=ServingDecodeConfig(slots=int(traffic["slots"]),
                                       max_len=int(traffic["max_len"]))),
        termination=TerminationConfig(federation_rounds=10 ** 6))
    session = DriverSession(
        config, bind.shipped_host(cfg, seed),
        [Recipe(cfg, mint, seed, probe.control)],
        workdir=os.path.join(work, "federation"), accelerator=platform,
        # the minting learner stays off the chip (see ``MINT_ENV``)
        learner_env={"JAX_PLATFORMS": "cpu", MINT_ENV: "1"})
    rss = common.RssWatch(session)
    common.assert_no_backend()
    clients = []
    try:
        # one round at learning rate 0 mints the seeded adapters as a
        # registry version; then the learner leaves the chip
        session.initialize_federation(launch_serving=False)
        ctl = session._client
        learner_log = next(p.log_path for p in session._procs
                           if p.name == "learner_0")

        def minted():
            metas = ctl.get_runtime_metadata(tail=0, timeout=30.0)
            if isinstance(metas, dict):
                metas = metas.get("round_metadata", [])
            return any(m.get("completed_at", 0) > 0
                       and m.get("registered_version", 0) > 0
                       for m in metas)

        _wait("the minting round", minted, session, logs=[learner_log])
        # the controller goes on minting versions (all of them the seeded
        # adapters) until the learner is gone: stop it, then promote the
        # newest, which is the candidate channel's head and so leaves that
        # channel empty. The gateway then installs one model, not two.
        session.stop_learners(timeout_s=120.0)
        for _ in range(3):      # a last uplink may still be in the fold
            version = ctl.describe_registry()["next_version"] - 1
            promoted = ctl.promote_version(version, force=True)
            registry = ctl.describe_registry()
            if registry["stable"] == version and not registry["candidate"]:
                break
            time.sleep(0.5)
        else:
            raise BenchFailure(
                f"v{version} is not the only channel head: {promoted}; "
                f"stable v{registry['stable']}, candidate "
                f"v{registry['candidate']}, next v{registry['next_version']}")
        log(f"registry stable v{version} minted; learner stopped")
        session.launch_serving()
        serving_log = next(p.log_path for p in session._procs
                           if p.name == "serving")
        admin = session.serving_client()

        def installed():
            try:
                return admin.status(timeout=5.0, wait_ready=False)[
                    "installed"].get("stable") == version
            except Exception:  # noqa: BLE001 - still booting; retried
                return False

        _wait("the gateway's install", installed, session,
              logs=[serving_log])
        report = common.check_device(common.device_line(serving_log),
                                     platform, cell["chips"])
        # warm every prompt length of the grid, and the step
        warm_rng = np.random.default_rng([int(seed), 0x3A])
        for plen in traffic["prompt_grid"]:
            reply = admin.generate(
                warm_rng.integers(0, vocab, (int(plen),), dtype=np.int32),
                max_new_tokens=2, timeout=READY_DEADLINE_S)
            if len(np.asarray(admin.tokens(reply)).reshape(-1)) != 2:
                raise BenchFailure("warm-up reply of the wrong length")
        compiles_before = common.compiles_total(admin.get_metrics())
        decode_before = admin.status()["decode"]["stable"]

        n_clients = int(traffic["clients"])
        clients = [ServingClient("localhost", config.serving.port,
                                 comm=config.comm) for _ in range(n_clients)]
        records = [[] for _ in range(n_clients)]
        t0 = time.time()
        t_end = t0 + seconds
        setup_s = t0 - started
        log(f"window opens; set-up {setup_s:.1f}s")

        stream = traffic_lib.SharedStream(traffic, vocab, seed)

        def loop(i: int) -> None:
            while time.time() < t_end:
                prompt, olen = stream.take()
                sent = time.time()
                rec = {"prompt": prompt, "out_len": olen, "sent": sent,
                       "tokens": None, "error": ""}
                try:
                    reply = clients[i].generate(prompt, max_new_tokens=olen,
                                                timeout=REQUEST_TIMEOUT_S)
                    toks = np.asarray(clients[i].tokens(reply)).reshape(-1)
                    if fault == "token_altered" and len(toks) > 1:
                        from benchmark.tests import faults
                        toks = faults.alter_tokens(toks, vocab)
                    rec["tokens"] = toks
                except Exception as exc:  # noqa: BLE001 - counted as failed
                    rec["error"] = repr(exc)
                rec["done"] = time.time()
                records[i].append(rec)

        threads = [threading.Thread(target=loop, args=(i,), daemon=True)
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        traced = None
        if trace:
            time.sleep(min(1.0, seconds / 4))
            trace_dir = os.path.join(work, "trace")
            t_start = probe.ask("trace_start", trace_dir, "trace_started")
            time.sleep(min(float(traffic["trace_seconds"]), seconds / 2))
            t_stop = probe.ask("trace_stop", "", "trace_stopped",
                               timeout_s=200.0)
            log(f"the profiler took "
                f"{t_start['ready'] - t_start['wall']:.1f}s to start and "
                f"{t_stop['ready'] - t_stop['wall']:.1f}s to stop")
            traced = (trace_dir, t_start["wall"], t_stop["wall"])
        time.sleep(max(0.0, t_end - time.time()))
        decode_after = admin.status()["decode"]["stable"]
        compiles = common.compiles_total(admin.get_metrics()) \
            - compiles_before
        for t in threads:
            t.join(timeout=REQUEST_TIMEOUT_S + 60.0)
        if any(t.is_alive() for t in threads):
            raise BenchFailure("a client did not come back after the window")
        device = probe.ask("device", "", "device")
        log(f"device memory {device['memory_stats']}")
        rss.stop()
    except Exception:
        rss.stop()
        for proc in session._procs:
            log(common.tail(proc.log_path))
        session.shutdown_federation()
        raise
    finally:
        for c in clients:
            c.close()
    session.shutdown_federation(timeout_s=60.0)
    codes = session.process_exit_codes()
    if any(c != 0 for c in codes.values()):
        raise BenchFailure(f"processes did not all exit cleanly: {codes}")
    common.assert_no_backend()

    # all requests whose reply came (or failed) inside the window
    inside = [r for recs in records for r in recs if r["done"] <= t_end]
    good = [r for r in inside if r["tokens"] is not None]
    failed = len(inside) - len(good)
    bad = sum(1 for r in good
              if len(r["tokens"]) != r["out_len"]
              or np.any(r["tokens"] < 0) or np.any(r["tokens"] >= vocab))
    if not good:
        raise BenchFailure("no reply came inside the window")
    latency_ms = sorted(
        [(r["done"] - r["sent"]) * 1e3 for r in good]
        + [REQUEST_TIMEOUT_S * 1e3] * failed)
    metrics = {
        "reply_p95_ms": float(np.percentile(latency_ms, 95)),
        "serve_tokens_per_s": sum(r["out_len"] for r in good) / seconds,
        "setup_s": setup_s}
    ctx = {"cell": cell, "cfg": cfg, "traffic": traffic,
           "window_s": seconds, "window": (t0, t_end), "compiles": compiles,
           "device_kind": report["device_kind"], "trace": None,
           "decode_steps": decode_after["steps"] - decode_before["steps"],
           "requests": [{"prompt_len": len(r["prompt"]),
                         "out_len": r["out_len"]} for r in good],
           "telemetry_dir": os.path.join(work, "federation", "telemetry"),
           "reply_p50_ms": float(np.percentile(latency_ms, 50))}
    device_out = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"],
                  "memory_peak_bytes": device["memory_peak_bytes"]}
    breakdown = None
    if traced:
        try:
            ctx["trace"] = trace_lib.reduce_dir(*traced)
        except trace_lib.NoDeviceOps:
            if platform != "cpu":       # a rehearsal has no device plane
                raise
    if ctx["trace"]:
        device_out["busy_s"] = ctx["trace"]["busy_s"]
        device_out["window_s"] = ctx["trace"]["window_s"]
        breakdown = {"device_ops": ctx["trace"]["top_ops"],
                     "idle_gaps": ctx["trace"]["top_gaps"]}

    # a sample of the finished requests, the longest in it, drawn from
    # the seed; the reference runs once over each prompt with its tokens
    rng = np.random.default_rng([int(seed), 0xC4EC])
    order = sorted(range(len(good)),
                   key=lambda i: -(len(good[i]["prompt"])
                                   + good[i]["out_len"]))
    picked = [order[0]] + [int(i) for i in rng.permutation(order[1:])[
        : int(traffic["check_requests"]) - 1]]
    ref_out = common.run_reference(
        {"mode": "serve", "cfg": cfg, "seed": seed, "extras": extras,
         "requests": [{"prompt": good[i]["prompt"].tolist(),
                       "tokens": good[i]["tokens"].tolist()}
                      for i in picked if len(good[i]["tokens"])]},
        work, platform)
    log(f"reference took {ref_out['seconds']:.1f}s")
    numbers = {"token_gap": ref_out["reference"]["token_gap"],
               "bad_replies": float(bad)}
    readings = {"program": {**numbers, **ref_out["reference"]}}
    if "control" in ref_out:
        readings["control"] = ref_out["control"]
    ok, compared = check.verdict(numbers, cell["limits"])
    return {"correct": ok and failed == 0, "attempted": len(inside),
            "failed": failed, "metrics": metrics, "ctx": ctx,
            "device": device_out, "compared": compared,
            "breakdown": breakdown, "readings": readings,
            "reference_s": ref_out["seconds"], "workdir": work}
