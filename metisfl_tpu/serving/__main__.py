"""Serving processes: ``python -m metisfl_tpu.serving``.

Three roles share this entry point:

- **Gateway replica** (default): booted by the driver like a learner —
  the model architecture arrives as a cloudpickled recipe (only its
  ``model_ops`` is used), configuration as the federation config file.
  The gateway polls the controller's registry (``DescribeRegistry``),
  installs the stable/candidate channel heads, and serves ``Predict`` /
  ``Generate``. In a fleet, ``--replica-index``/``--replicas`` phase the
  registry polls deterministically (serving/fleet.py ``poll_stagger``)
  so a promotion rolls through the fleet one replica at a time. A
  relaunch after a crash needs no state of its own: the first poll pins
  it back to the last promoted version.
- **Router** (``--router``): the consistent-hash front of the fleet
  (serving/fleet.py) — no model, no recipe; it forwards traffic to the
  replica fleet from ``serving.fleet.gateways`` and health-probes it.
- **Fleet smoke** (``--fleet-smoke``): the CI replica-kill gate
  (serving/smoke.py, wired into scripts/chaos_smoke.sh).
"""

from __future__ import annotations

import argparse
import logging
import signal
import sys

import cloudpickle

from metisfl_tpu.config import FederationConfig, load_config


def _load_cfg(path: str) -> FederationConfig:
    if path.endswith((".yaml", ".yml")):
        return load_config(path)
    with open(path, "rb") as f:
        return FederationConfig.from_wire(f.read())


def _apply_telemetry(config, service: str) -> None:
    import hashlib

    from metisfl_tpu import telemetry
    config_hash = hashlib.sha256(config.to_wire()).hexdigest()[:16]
    telemetry.apply_config(config.telemetry, service=service,
                           config_hash=config_hash)


def run_router(config, host: str = "", port: int = -1) -> int:
    """Router process main loop (``--router``)."""
    from metisfl_tpu import telemetry
    from metisfl_tpu.serving.fleet import RouterServer, ServingRouter

    _apply_telemetry(config, service="router")
    router = ServingRouter(config.serving, ssl=config.ssl,
                           comm=config.comm)
    router.set_replicas(config.serving.fleet.gateways)
    server = RouterServer(
        router, host=host or config.serving.host,
        port=(config.serving.fleet.router_port if port < 0 else port),
        ssl=config.ssl)
    bound = server.start()
    print(f"METISFL_TPU_ROUTER_READY port={bound}", flush=True)

    signal.signal(signal.SIGTERM, lambda *_: server.stop())
    signal.signal(signal.SIGINT, lambda *_: server.stop())
    server.wait_for_shutdown()
    telemetry.trace.flush()
    telemetry.events.flush()
    return 0


def main(argv=None) -> int:
    from metisfl_tpu.platform import announce_devices, enter_process
    enter_process()
    parser = argparse.ArgumentParser("metisfl_tpu.serving")
    parser.add_argument("--config", default="",
                        help="path to FederationConfig (.bin codec or .yaml)")
    parser.add_argument("--recipe", default="",
                        help="cloudpickled callable -> (model_ops, ...); "
                             "only the engine is used (gateway role)")
    parser.add_argument("--host", default="")
    parser.add_argument("--port", type=int, default=-1,
                        help="override config serving.port (-1: use config)")
    parser.add_argument("--router", action="store_true",
                        help="run the fleet router instead of a gateway "
                             "replica (no recipe needed)")
    parser.add_argument("--replica-index", type=int, default=0,
                        help="this replica's index in the fleet (registry-"
                             "poll stagger phase)")
    parser.add_argument("--replicas", type=int, default=1,
                        help="fleet size for the poll stagger")
    parser.add_argument("--fleet-smoke", action="store_true",
                        help="run the CI replica-kill smoke "
                             "(serving/smoke.py) and exit 0/1")
    parser.add_argument("--smoke-replicas", type=int, default=3,
                        help="--fleet-smoke: replica count")
    args = parser.parse_args(argv)

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    if args.fleet_smoke:
        from metisfl_tpu.serving.smoke import run_fleet_smoke
        return run_fleet_smoke(replicas=args.smoke_replicas)

    if not args.config:
        parser.error("--config is required")
    config = _load_cfg(args.config)

    if args.router:
        return run_router(config, host=args.host, port=args.port)

    if not args.recipe:
        parser.error("--recipe is required for the gateway role")
    _apply_telemetry(config, service="serving")
    # first backend use: on JAX_PLATFORMS=tpu a missing chip fails here
    announce_devices("serving")

    with open(args.recipe, "rb") as f:
        recipe = cloudpickle.load(f)
    model_ops = recipe()[0]

    from metisfl_tpu.controller.service import ControllerClient
    from metisfl_tpu.serving.fleet import poll_stagger
    from metisfl_tpu.serving.gateway import (ControllerRegistrySource,
                                             ServingGateway)
    from metisfl_tpu.serving.service import ServingServer

    standby = config.controller.standby
    controller = ControllerClient(
        config.controller_host or "localhost", config.controller_port,
        ssl=config.ssl, comm=config.comm,
        # registry poller redial contract: a controller failover must not
        # strand the gateway on the dead primary's endpoint
        standby=((standby.host, standby.port) if standby.enabled else None))
    gateway = ServingGateway(
        model_ops, config.serving,
        ship_tensor_regex=config.train.ship_tensor_regex)
    server = ServingServer(gateway, host=args.host or config.serving.host,
                           port=(config.serving.port if args.port < 0
                                 else args.port),
                           ssl=config.ssl)
    port = server.start()
    print(f"METISFL_TPU_SERVING_READY port={port}", flush=True)
    gateway.start_sync(
        ControllerRegistrySource(controller),
        initial_delay_s=poll_stagger(args.replica_index, args.replicas,
                                     config.serving.poll_every_s))

    signal.signal(signal.SIGTERM, lambda *_: server.stop())
    signal.signal(signal.SIGINT, lambda *_: server.stop())
    server.wait_for_shutdown()
    controller.close()
    from metisfl_tpu import telemetry
    telemetry.trace.flush()
    telemetry.events.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
