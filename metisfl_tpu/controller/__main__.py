"""Controller process entry point: ``python -m metisfl_tpu.controller``.

Reference: metisfl/controller/__main__.py:12-94 — but configuration arrives
as one file (codec-serialized ``FederationConfig`` or YAML), not hex-proto
CLI flags (SURVEY.md §5.6 flags that design as user-hostile).

``--standby`` runs the warm hot-standby instead (docs/RESILIENCE.md
"Controller hot-standby"): tail the primary's write-ahead round-state
log (controller/wal.py), answer grpc.health.v1 with NOT_SERVING for the
controller service (alive, not promoted — probes can tell a warm standby
from a corpse), and promote when the WAL tail goes stale AND
``probe_failures`` consecutive health probes of the primary come back
non-SERVING — the exact staleness→probe escalation the slice re-homing
and serving-fleet paths standardized. Promotion restores the replicated
state, starts the full controller on the standby's own pinned port
(every peer holds both endpoints up front), and re-dispatches the
abandoned round.
"""

from __future__ import annotations

import argparse
import logging
import signal
import sys
import threading
import time

from metisfl_tpu.config import FederationConfig, load_config
from metisfl_tpu.controller.core import Controller
from metisfl_tpu.controller.service import ControllerServer, RpcLearnerProxy


def _build_controller(config, parser) -> Controller:
    """Construct the Controller exactly as the primary path does — the
    promoted standby must run the same aggregation/secure stack or the
    resumed round could not be bit-identical."""
    secure_backend = None
    if config.secure.enabled:
        from metisfl_tpu.secure import make_backend
        kwargs = {}
        if config.secure.scheme == "masking":
            num_parties = config.secure.num_parties or len(config.learners)
            if num_parties <= 0:
                parser.error(
                    "masking secure aggregation needs secure.num_parties "
                    "(the driver fills it in) or a configured learner list")
            kwargs["num_parties"] = num_parties
        secure_backend = make_backend(config.secure, role="controller",
                                      **kwargs)
    return Controller(
        config,
        lambda record: RpcLearnerProxy(record, ssl=config.ssl,
                                       comm=config.comm),
        secure_backend=secure_backend)


def _standby_main(args, config, parser, metrics_http) -> int:
    from metisfl_tpu import telemetry
    from metisfl_tpu.comm.health import (NOT_SERVING, HealthServicer,
                                         probe_health)
    from metisfl_tpu.comm.rpc import BytesService, RpcServer
    from metisfl_tpu.controller.service import CONTROLLER_SERVICE
    from metisfl_tpu.controller.wal import RoundStateLog
    from metisfl_tpu.telemetry import events as tevents
    from metisfl_tpu.telemetry import metrics as tmetrics

    standby = config.controller.standby
    if not (standby.enabled and standby.wal_dir):
        parser.error("--standby requires controller.standby.enabled and "
                     "controller.standby.wal_dir (the driver pins both)")
    log = logging.getLogger("metisfl_tpu.controller.standby")
    wal = RoundStateLog(standby.wal_dir)

    # Warm phase: health-only server on the standby's pinned port. The
    # overall server ("") answers SERVING — the driver's boot wait and
    # the fleet collector's liveness column see a live process — while
    # the controller service answers NOT_SERVING until promotion, so
    # nobody re-dials here early.
    health = HealthServicer()
    health.set_status(CONTROLLER_SERVICE, NOT_SERVING)
    idle = RpcServer(args.host, args.port or standby.port, ssl=config.ssl)
    idle.add_service(health.service())
    # role-tagged methodless service: the fleet collector's
    # CollectTelemetry pulls (and the status CLI's --probe) see the warm
    # standby as a live role="standby" peer — without mounting a single
    # controller method, so a misdirected RPC stays loudly UNIMPLEMENTED
    idle.add_service(BytesService(CONTROLLER_SERVICE, {}, role="standby"))
    port = idle.start()
    print(f"METISFL_TPU_CONTROLLER_STANDBY_READY port={port}", flush=True)

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())

    # Tail loop: WAL progress is the cheap liveness signal (the primary
    # snapshots every membership change and round close); only a stale
    # tail escalates to health probes, so a healthy primary costs one
    # listdir per tick and zero RPCs.
    last_seq = wal.poll()
    last_progress = time.monotonic()
    failures = 0
    promoted = False
    # standby replication lag: primary WAL head vs the tail position
    # this loop has caught up to — records that landed inside one probe
    # interval. Rides CollectTelemetry off the warm standby's server, so
    # the status --fleet ha: line can show the standby keeping up (or
    # not) while the primary is still alive.
    lag_gauge = tmetrics.registry().gauge(
        telemetry.M_CONTROLLER_WAL_LAG_RECORDS,
        "Standby tail position behind the primary's WAL head (records "
        "observed landing per probe tick; 0 = caught up)")
    lag_gauge.set(0.0)
    while not stop.is_set():
        stop.wait(standby.probe_interval_s)
        if stop.is_set():
            break
        seq = wal.poll()
        lag_gauge.set(float(max(0, seq - last_seq)))
        if seq != last_seq:
            last_seq, last_progress, failures = seq, time.monotonic(), 0
            continue
        if time.monotonic() - last_progress < standby.stale_after_s:
            continue
        verdict = probe_health(config.controller_host,
                               config.controller_port, CONTROLLER_SERVICE,
                               ssl=config.ssl, comm=config.comm)
        if verdict == "SERVING":
            # healthy but quiet (long round, idle federation): reset the
            # staleness clock, keep tailing
            failures, last_progress = 0, time.monotonic()
            continue
        failures += 1
        log.warning("primary %s:%d %s after %.1fs WAL stall (%d/%d "
                    "consecutive probe failures)", config.controller_host,
                    config.controller_port, verdict,
                    time.monotonic() - last_progress, failures,
                    standby.probe_failures)
        if failures >= standby.probe_failures:
            promoted = True
            break

    if not promoted:  # clean shutdown while warm
        idle.stop()
        if metrics_http is not None:
            metrics_http.close()
        telemetry.trace.flush()
        telemetry.events.flush()
        return 0

    # Promote: stop the health-only server, restore the WAL state into a
    # full controller, and serve on the SAME pinned port — peers redial
    # a known endpoint, not a discovered one. The brief UNREACHABLE
    # window between stop() and start() is covered by every client's
    # bounded UNAVAILABLE retry.
    t0 = time.monotonic()
    idle.stop()
    log.warning("promoting: restoring WAL round state from %s",
                standby.wal_dir)
    controller = _build_controller(config, parser)
    restored = controller.restore_from_wal()
    server = ControllerServer(controller, host=args.host, port=port,
                              ssl=config.ssl)
    port = server.start()
    promote_s = time.monotonic() - t0
    n_learners = len(controller.active_learners())
    reg = tmetrics.registry()
    reg.counter(telemetry.M_CONTROLLER_FAILOVER_TOTAL,
                "Standby promotions to controller, by role of the "
                "emitting process", ("role",)).inc(role="standby")
    reg.histogram(telemetry.M_CONTROLLER_FAILOVER_PROMOTE_SECONDS,
                  "Wall-clock from promotion decision to the promoted "
                  "controller serving").observe(promote_s)
    tevents.emit(tevents.ControllerFailover, role="standby",
                 host=standby.host, port=port,
                 round=controller.global_iteration, learners=n_learners,
                 wal_records=last_seq, promote_s=round(promote_s, 4),
                 reason="wal_stale_probe_failed")
    print(f"METISFL_TPU_CONTROLLER_PROMOTED port={port}", flush=True)
    log.warning("promoted in %.2fs at round %d (%d learner(s) restored)",
                promote_s, controller.global_iteration, n_learners)
    if restored:
        # re-dispatch the round the dead primary abandoned (same posture
        # as --resume); the fresh controller_epoch makes surviving
        # learners re-attach and completions fold in deterministically
        controller.resume_round()

    signal.signal(signal.SIGTERM, lambda *_: server.stop())
    signal.signal(signal.SIGINT, lambda *_: server.stop())
    server.wait_for_shutdown()
    if metrics_http is not None:
        metrics_http.close()
    telemetry.trace.flush()
    telemetry.events.flush()
    return 0


def main(argv=None) -> int:
    from metisfl_tpu.platform import enter_process
    enter_process()
    parser = argparse.ArgumentParser("metisfl_tpu.controller")
    parser.add_argument("--config", required=True,
                        help="path to FederationConfig (.bin codec or .yaml)")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=0,
                        help="override config controller_port (primary) or "
                             "controller.standby.port (--standby)")
    parser.add_argument("--resume", action="store_true",
                        help="restore community model + round counter from "
                             "config.checkpoint.dir before serving")
    parser.add_argument("--standby", action="store_true",
                        help="run as the warm hot-standby: tail the WAL, "
                             "promote on primary death")
    args = parser.parse_args(argv)

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    if args.config.endswith((".yaml", ".yml")):
        config = load_config(args.config)
    else:
        with open(args.config, "rb") as f:
            config = FederationConfig.from_wire(f.read())

    from metisfl_tpu import telemetry
    import hashlib
    config_hash = hashlib.sha256(config.to_wire()).hexdigest()[:16]
    telemetry.apply_config(config.telemetry,
                           service="standby" if args.standby
                           else "controller",
                           config_hash=config_hash)
    metrics_http = None
    if config.telemetry.enabled and config.telemetry.http_port > 0:
        from metisfl_tpu.telemetry.httpd import start_metrics_http
        metrics_http = start_metrics_http(config.telemetry.http_port,
                                          host=args.host)

    if args.standby:
        return _standby_main(args, config, parser, metrics_http)

    controller = _build_controller(config, parser)
    restored = False
    if args.resume:
        if not config.checkpoint.dir:
            parser.error("--resume requires config.checkpoint.dir")
        restored = controller.restore_checkpoint()
        if not restored:
            logging.getLogger("metisfl_tpu.controller").warning(
                "--resume: no checkpoint found under %r — starting FRESH "
                "at round 0", config.checkpoint.dir)
    server = ControllerServer(controller, host=args.host,
                              port=args.port or config.controller_port,
                              ssl=config.ssl)
    port = server.start()
    print(f"METISFL_TPU_CONTROLLER_READY port={port}", flush=True)
    if restored:
        # crash-failover: re-dispatch the abandoned round to the restored
        # registry (learners that stayed alive resume immediately; dead
        # endpoints heal via re-attach). AFTER start(): dispatches dial
        # out and completions dial back in through the live server.
        controller.resume_round()

    signal.signal(signal.SIGTERM, lambda *_: server.stop())
    signal.signal(signal.SIGINT, lambda *_: server.stop())
    server.wait_for_shutdown()
    if metrics_http is not None:
        metrics_http.close()
    telemetry.trace.flush()
    telemetry.events.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
