"""Driver of the ``round`` kind of traffic: federated rounds back to back
through ``DriverSession`` (controller on the CPU, one learner process on the
chip). The window opens at a round boundary after the warm-up rounds and
closes at the last boundary reached within ``--seconds``."""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark.lib import check, common, spec, trace as trace_lib
from benchmark.lib.common import BenchFailure, log
from benchmark.lib.recipes import ROUND0_READ, Probe, Recipe

ROUND_DEADLINE_S = 900.0


def _completed(client, tail: int = 0) -> list:
    metas = client.get_runtime_metadata(tail=tail, timeout=30.0)
    if isinstance(metas, dict):
        metas = metas.get("round_metadata", [])
    return [m for m in metas if m.get("completed_at", 0) > 0]


def _named(blob: bytes) -> dict:
    from metisfl_tpu.tensor.pytree import ModelBlob
    return {n: np.asarray(a) for n, a in ModelBlob.from_bytes(blob).tensors}


def run(cell: dict, seed: int, seconds: float, trace: bool, platform: str,
        started: float, work: str, extras: bool = False,
        fault: str = "") -> dict:
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (EvalConfig, FederationConfig,
                                    TerminationConfig)
    from metisfl_tpu.driver.session import DriverSession
    from metisfl_tpu.tensor.pytree import pytree_to_named_tensors

    cfg, traffic = cell["cfg"], cell["traffic"]
    shape = traffic["shape"]
    bind = spec.binding(cfg)
    probe = Probe(os.path.join(work, "control"))
    initial = bind.shipped_host(cfg, seed)
    initial_named = {n: np.asarray(a)
                     for n, a in pytree_to_named_tensors(initial)}
    config = FederationConfig(
        controller_port=common.free_port(),
        train=TrainParams(batch_size=int(shape["batch"]),
                          local_steps=int(shape["local_steps"]),
                          scan_chunk=int(shape["scan_chunk"]),
                          optimizer=traffic["optimizer"],
                          learning_rate=float(traffic["learning_rate"]),
                          ship_tensor_regex=traffic["ship_tensor_regex"]),
        eval=EvalConfig(batch_size=int(shape["test_rows"]),
                        datasets=["test"], metrics=["accuracy"],
                        every_n_rounds=int(traffic["eval_every_n_rounds"])),
        termination=TerminationConfig(federation_rounds=10 ** 6))
    session = DriverSession(
        config, initial,
        [Recipe(cfg, shape, seed, probe.control, fault)],
        workdir=os.path.join(work, "federation"), accelerator=platform)
    rss = common.RssWatch(session)
    common.assert_no_backend()
    warmup = int(traffic["warmup_rounds"])
    try:
        session.initialize_federation(launch_serving=False)
        client = session._client
        learner_log = next(p.log_path for p in session._procs
                           if p.name == "learner_0")

        def wait_rounds(n: int) -> list:
            deadline = time.time() + ROUND_DEADLINE_S
            while time.time() < deadline:
                session._check_procs_alive()
                common.check_no_failed_task(learner_log)
                done = _completed(client)
                if len(done) >= n:
                    return done
                time.sleep(0.1)
            raise BenchFailure(f"round {n} did not complete within "
                               f"{ROUND_DEADLINE_S}s\n"
                               + common.tail(learner_log))

        # the first round, driven from the seed: what the reference follows
        first = wait_rounds(1)
        log(f"round 0 closed {time.time() - started:.1f}s after the start")
        community = _named(client.get_community_model())
        probe.flag(ROUND0_READ)         # round 1 may start its feed
        if len(_completed(client)) != 1:
            raise BenchFailure("round 1 closed while the community model "
                               "of round 0 was read")
        round0 = first[0]
        report = common.check_device(common.device_line(learner_log),
                                     platform, cell["chips"])
        rounds = wait_rounds(warmup)
        t0 = rounds[warmup - 1]["completed_at"]
        setup_s = t0 - started
        collector = session.fleet_collector()
        collector.poll_once()
        compiles_before = common.compiles_total(
            collector.merged_exposition())
        log(f"window opens after {warmup} rounds; set-up {setup_s:.1f}s")

        traced = None
        if trace:
            # whole rounds, boundary to boundary: start at the next round
            # boundary, stop ``trace_rounds`` boundaries later
            trace_dir = os.path.join(work, "trace")
            cap = time.time() + min(seconds, 40.0)

            def boundary(n: int) -> None:
                while len(_completed(client)) < n and time.time() < cap:
                    time.sleep(0.05)

            boundary(len(_completed(client)) + 1)
            t_start = probe.ask("trace_start", trace_dir, "trace_started")
            boundary(len(_completed(client)) + int(traffic["trace_rounds"]))
            t_stop = probe.ask("trace_stop", "", "trace_stopped",
                               timeout_s=200.0)
            log(f"traced {t_stop['wall'] - t_start['wall']:.1f}s; the "
                f"profiler took {t_start['ready'] - t_start['wall']:.1f}s "
                f"to start and {t_stop['ready'] - t_stop['wall']:.1f}s "
                "to stop")
            traced = (trace_dir, t_start["wall"], t_stop["wall"])
        while time.time() < t0 + seconds:
            session._check_procs_alive()
            common.check_no_failed_task(learner_log)
            time.sleep(0.05)
        rounds = _completed(client)
        window = [m for m in rounds[warmup:]
                  if m["completed_at"] <= t0 + seconds]
        if not window:
            raise BenchFailure(f"no round completed within {seconds}s")
        t1 = window[-1]["completed_at"]
        collector.poll_once()
        compiles = common.compiles_total(
            collector.merged_exposition()) - compiles_before
        device = probe.ask("device", "", "device")
        log(f"device memory {device['memory_stats']}")
        rss.stop()
    except Exception:
        rss.stop()
        for proc in session._procs:
            log(common.tail(proc.log_path))
        session.shutdown_federation()
        raise
    # the learner first, while the controller is still there to take the
    # uplink of the round in flight: left to shut down together, a learner
    # in mid-stream has outlived the drain budget and been killed
    session.stop_learners(timeout_s=180.0)
    session.shutdown_federation(timeout_s=60.0)
    codes = session.process_exit_codes()
    if any(c != 0 for c in codes.values()):
        raise BenchFailure(f"processes did not all exit cleanly: {codes}")
    common.assert_no_backend()

    lid = round0["selected_learners"][0]
    failed = sum(1 for m in window
                 if m["errors"] or not m["selected_learners"]
                 or not all(np.isfinite(v["loss"])
                            for v in m["train_metrics"].values()))
    metrics = {"round_s": (t1 - t0) / len(window), "setup_s": setup_s}
    ends = [t0] + [m["completed_at"] for m in window]
    log("rounds of the window, s: "
        + " ".join(f"{b - a:.2f}" for a, b in zip(ends, ends[1:])))
    log("their wait_uplinks, ms: " + " ".join(
        f"{m['profile']['phases'].get('wait_uplinks', 0):.0f}"
        for m in window))
    ctx = {"cell": cell, "cfg": cfg, "traffic": traffic, "rounds": window,
           "learner": lid, "window_s": t1 - t0, "compiles": compiles,
           "device_kind": report["device_kind"], "trace": None}
    device_out = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"],
                  "memory_peak_bytes": device["memory_peak_bytes"]}
    breakdown = None
    if traced:
        spans = [(m["started_at"], m["completed_at"],
                  m["profile"]["phases"]) for m in rounds]
        try:
            ctx["trace"] = trace_lib.reduce_dir(
                traced[0], traced[1], traced[2],
                host_phases=trace_lib.round_phases(spans))
        except trace_lib.NoDeviceOps:
            if platform != "cpu":       # a rehearsal has no device plane
                raise
    if ctx["trace"]:
        device_out["busy_s"] = ctx["trace"]["busy_s"]
        device_out["window_s"] = ctx["trace"]["window_s"]
        breakdown = {"device_ops": ctx["trace"]["top_ops"],
                     "idle_gaps": ctx["trace"]["top_gaps"]}

    # the reference, on the chip the learner has left
    ref = common.run_reference(
        {"mode": "train", "cfg": cfg, "shape": shape, "seed": seed,
         "learning_rate": float(traffic["learning_rate"]),
         "extras": extras}, work, platform)
    log(f"reference took {ref['seconds']:.1f}s")
    numbers = check.train_numbers(
        float(round0["train_metrics"][lid]["loss"]),
        check.change_norms(community, initial_named), ref["reference"])
    readings = {"program": numbers}
    for key in ("control", "fault_half_batch"):
        if key in ref:
            readings[key] = check.train_numbers(
                ref[key]["loss"],
                {n: v["change"] for n, v in ref[key]["leaf"].items()},
                ref["reference"])
    ok, compared = check.verdict(numbers, cell["limits"])
    return {"correct": ok and failed == 0, "attempted": len(window),
            "failed": failed, "metrics": metrics, "ctx": ctx,
            "device": device_out, "compared": compared,
            "breakdown": breakdown, "readings": readings,
            "reference_s": ref["seconds"], "workdir": work}
