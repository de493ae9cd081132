"""Learner process entry point: ``python -m metisfl_tpu.learner``.

Reference: metisfl/learner/__main__.py:10-90. The model + datasets arrive as
a cloudpickled *recipe*: a zero-arg callable returning
``(model_ops, train_ds, val_ds, test_ds)`` — the same mechanism as the
reference's dataset recipes (driver_session.py:71-90) extended to the model.

Credentials (learner_id + auth token) persist to ``--credentials-dir`` so a
crash-restarted learner transparently rejoins as itself (the reference's
``/tmp/metis/learner_<port>_credentials/`` flow, learner.py:96-103).
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import signal
import socket
import sys

import cloudpickle

from metisfl_tpu.controller.service import ControllerClient
from metisfl_tpu.learner.learner import Learner
from metisfl_tpu.learner.service import LearnerServer

_CREDS_NAME = "credentials.json"


def load_credentials(creds_dir: str) -> tuple[str, str]:
    """(learner_id, auth_token) from a previous run, or ("", "")."""
    path = os.path.join(creds_dir, _CREDS_NAME)
    try:
        with open(path) as f:
            data = json.load(f)
        return str(data.get("learner_id", "")), str(data.get("auth_token", ""))
    except (OSError, ValueError):
        return "", ""


def save_credentials(creds_dir: str, learner_id: str, auth_token: str) -> None:
    os.makedirs(creds_dir, exist_ok=True)
    path = os.path.join(creds_dir, _CREDS_NAME)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"learner_id": learner_id, "auth_token": auth_token}, f)
    os.chmod(tmp, 0o600)
    os.replace(tmp, path)


def main(argv=None) -> int:
    from metisfl_tpu.platform import enter_process
    enter_process()
    parser = argparse.ArgumentParser("metisfl_tpu.learner")
    parser.add_argument("--controller-host", default="localhost")
    parser.add_argument("--controller-port", type=int, required=True)
    parser.add_argument("--standby-host", default="",
                        help="controller hot-standby endpoint: a call that "
                             "exhausts its UNAVAILABLE retries re-resolves "
                             "to whichever endpoint answers SERVING")
    parser.add_argument("--standby-port", type=int, default=0)
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--advertise-host", default="",
                        help="hostname the controller should dial back")
    parser.add_argument("--port", type=int, default=0,
                        help="0 → bind an ephemeral port (reported to the "
                             "controller via JoinRequest.port)")
    parser.add_argument("--recipe", required=True,
                        help="cloudpickled callable -> (ops, train, val, test)")
    parser.add_argument("--previous-id", default="")
    parser.add_argument("--auth-token", default="")
    parser.add_argument("--credentials-dir", default="",
                        help="persist learner_id/auth_token here for "
                             "crash-restart rejoin")
    parser.add_argument("--ssl-cert", default="",
                        help="federation TLS cert (enables TLS client+server)")
    parser.add_argument("--ssl-key", default="")
    parser.add_argument("--secure-config", default="",
                        help="codec file with the driver-distributed secure-"
                             "aggregation material (scheme + keys/secret)")
    parser.add_argument("--telemetry-dir", default="",
                        help="JSONL trace-sink directory (the driver points "
                             "this at <workdir>/telemetry)")
    parser.add_argument("--telemetry-off", action="store_true",
                        help="disable spans + metrics + events (federation "
                             "config telemetry.enabled=false, forwarded by "
                             "the driver)")
    parser.add_argument("--events-off", action="store_true",
                        help="disable only the event journal (federation "
                             "config telemetry.events.enabled=false)")
    parser.add_argument("--postmortem-dir", default="",
                        help="flight-recorder bundle directory (the driver "
                             "points this at <workdir>/postmortem; crash/"
                             "chaos-kill bundles land here)")
    parser.add_argument("--metrics-port", type=int, default=0,
                        help="plain-HTTP /metrics listener port (0 = off; "
                             "metrics stay reachable via the GetMetrics RPC)")
    parser.add_argument("--rpc-deadline-s", type=float, default=None,
                        help="default RPC deadline toward the controller "
                             "(config comm.default_deadline_s, forwarded "
                             "by the driver; <= 0 = unbounded, same "
                             "convention as the config; omitted = library "
                             "default)")
    args = parser.parse_args(argv)

    from metisfl_tpu import telemetry
    from metisfl_tpu.config import EventsConfig, TelemetryConfig
    telemetry.apply_config(
        TelemetryConfig(enabled=not args.telemetry_off,
                        dir=args.telemetry_dir,
                        events=EventsConfig(enabled=not args.events_off),
                        postmortem_dir=args.postmortem_dir),
        service=f"learner-{args.port or os.getpid()}")
    metrics_http = None
    if not args.telemetry_off and args.metrics_port > 0:
        from metisfl_tpu.telemetry.httpd import start_metrics_http
        metrics_http = start_metrics_http(args.metrics_port, host=args.host)

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    # multi-host learner (one learner owning a multi-host TPU slice): join
    # the global runtime before any jax use (after logging setup so the
    # confirmation line is visible)
    from metisfl_tpu.platform import announce_devices, maybe_init_distributed
    maybe_init_distributed()
    # first backend use: on JAX_PLATFORMS=tpu a missing chip fails here
    announce_devices("learner")

    with open(args.recipe, "rb") as f:
        recipe = cloudpickle.load(f)
    built = recipe()
    model_ops, train_ds = built[0], built[1]
    val_ds = built[2] if len(built) > 2 else None
    test_ds = built[3] if len(built) > 3 else None
    secure_backend = built[4] if len(built) > 4 else None

    # multi-host world: rank 0 continues as THE learner (gRPC + controller
    # traffic) with its engine wrapped to broadcast every compute call;
    # follower ranks replay those calls and never touch the federation
    import jax as _jax
    ds_by_name = {"train": train_ds, "val": val_ds, "test": test_ds}
    if _jax.process_count() > 1:
        from metisfl_tpu.parallel.replicated import follower_loop, lead
        if _jax.process_index() > 0:
            print(f"METISFL_TPU_FOLLOWER_READY "
                  f"rank={_jax.process_index()}", flush=True)
            follower_loop(model_ops, ds_by_name)
            # exit WITHOUT interpreter teardown: the jax.distributed client's
            # atexit talks to rank 0's coordinator, and rank 0 exits right
            # after its shutdown broadcast — losing that race leaves this
            # rank blocked in native code until the driver SIGKILLs it
            logging.shutdown()
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(0)
        model_ops = lead(model_ops, ds_by_name)

    if secure_backend is None and args.secure_config:
        # driver-distributed secure material (reference ships HE keys to
        # learners the same way, driver_session.py:134-140)
        from metisfl_tpu.comm.codec import loads as codec_loads
        from metisfl_tpu.config import SecureAggConfig
        from metisfl_tpu.secure import make_backend
        with open(args.secure_config, "rb") as f:
            sc = codec_loads(f.read())
        secure_backend = make_backend(
            SecureAggConfig(enabled=True, scheme=sc["scheme"],
                            key_dir=sc.get("key_dir", "")),
            role="learner", **sc.get("kwargs", {}))

    ssl = None
    if args.ssl_cert:
        from metisfl_tpu.comm.ssl import SSLConfig
        ssl = SSLConfig(enabled=True, cert_path=args.ssl_cert,
                        key_path=args.ssl_key)

    previous_id, auth_token = args.previous_id, args.auth_token
    if args.credentials_dir and not previous_id:
        previous_id, auth_token = load_credentials(args.credentials_dir)
        if previous_id:
            logging.getLogger("metisfl_tpu.learner").info(
                "found persisted credentials for %s; attempting rejoin",
                previous_id)

    comm = None
    if args.rpc_deadline_s is not None:
        from metisfl_tpu.config import CommConfig
        comm = CommConfig(default_deadline_s=args.rpc_deadline_s)
    controller = ControllerClient(args.controller_host, args.controller_port,
                                  ssl=ssl, comm=comm,
                                  standby=((args.standby_host,
                                            args.standby_port)
                                           if args.standby_port else None))
    advertise = args.advertise_host or socket.gethostname()
    learner = Learner(
        model_ops=model_ops,
        train_dataset=train_ds,
        val_dataset=val_ds,
        test_dataset=test_ds,
        hostname=advertise,
        controller=controller,
        secure_backend=secure_backend,
    )
    server = LearnerServer(learner, host=args.host, port=args.port, ssl=ssl)
    port = server.start()
    print(f"METISFL_TPU_LEARNER_READY port={port}", flush=True)

    if args.credentials_dir:
        # persist refreshed identity after every re-attach too: a
        # controller that lost its registry hands out a NEW id, and the
        # next learner restart must rejoin as that one
        learner.on_join = lambda reply: save_credentials(
            args.credentials_dir, reply.learner_id, reply.auth_token)

    try:
        reply = learner.join_federation(previous_id=previous_id,
                                        auth_token=auth_token)
        if args.credentials_dir:
            save_credentials(args.credentials_dir, reply.learner_id,
                             reply.auth_token)
        print(f"METISFL_TPU_LEARNER_JOINED id={reply.learner_id} "
              f"rejoined={reply.rejoined}", flush=True)

        def _on_signal(signum, _frame):
            logging.getLogger("metisfl_tpu.learner").info(
                "received signal %d; shutting down", signum)
            server.stop()

        signal.signal(signal.SIGTERM, _on_signal)
        signal.signal(signal.SIGINT, _on_signal)
        server.wait_for_shutdown()
    finally:
        # release follower ranks even when join fails (a stuck leader must
        # not leave followers parked in their broadcast loop); a failed
        # release (e.g. collective timeout against an already-dead rank)
        # must not turn THIS rank's clean exit into a crash — the driver's
        # drain-and-kill is the backstop for stuck followers
        if hasattr(model_ops, "shutdown_replicas"):
            try:
                model_ops.shutdown_replicas()
            except Exception:
                logging.getLogger("metisfl_tpu.learner").exception(
                    "follower release broadcast failed")
        if metrics_http is not None:
            metrics_http.close()
        telemetry.trace.flush()
        telemetry.events.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
