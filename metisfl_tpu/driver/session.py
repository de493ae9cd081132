"""DriverSession — federation lifecycle from the user's script.

Capability equivalent of the reference's ``DriverSession``
(reference metisfl/driver/driver_session.py:29-585): boot the controller and
learners, ship the initial model, monitor the three termination criteria
(rounds / metric cutoff / wall-clock, :443-477), collect statistics, shut
everything down. Redesigned:

- processes launch via a pluggable launcher: localhost ``subprocess`` by
  default, SSH command launcher for remote hosts (the reference hard-wires
  fabric SSH);
- model + data travel as a cloudpickled recipe per learner + one wire-format
  model blob — no tarballs;
- statistics land in ``experiment.json`` like the reference
  (driver_session.py:408-418).
"""

from __future__ import annotations

import json
import logging
import os
import shlex
import subprocess
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence

import cloudpickle
import numpy as np

from metisfl_tpu import telemetry as _tel
from metisfl_tpu.chaos import ENV_VAR as _CHAOS_ENV_VAR
from metisfl_tpu.comm.messages import TrainParams
from metisfl_tpu.config import FederationConfig
from metisfl_tpu.controller.service import ControllerClient
from metisfl_tpu.platform import (CACHE_DIR_ENV, chip_env,
                                  package_pythonpath)
from metisfl_tpu.telemetry import events as _tevents
from metisfl_tpu.telemetry import metrics as _tmetrics
from metisfl_tpu.telemetry import postmortem as _tpostmortem
from metisfl_tpu.tensor.pytree import pack_model

logger = logging.getLogger("metisfl_tpu.driver")

# Controller failover events, scrapable from the driver process's
# registry (docs/RESILIENCE.md): each supervised relaunch-with-resume
# increments this exactly once.
_M_CTRL_RESTARTS = _tmetrics.registry().counter(
    _tel.M_CONTROLLER_RESTARTS_TOTAL,
    "Supervised controller relaunches after a crash")
_M_CTRL_FAILOVER = _tmetrics.registry().counter(
    _tel.M_CONTROLLER_FAILOVER_TOTAL,
    "Standby promotions to controller, by role of the emitting process",
    ("role",))
_M_GATEWAY_RESTARTS = _tmetrics.registry().counter(
    _tel.M_GATEWAY_RESTARTS_TOTAL,
    "Supervised serving-gateway relaunches after a crash")
_M_FLEET_REPLICAS = _tmetrics.registry().gauge(
    _tel.M_SERVING_FLEET_REPLICAS,
    "Serving-fleet replica count as the autoscaler maintains it")
_M_SCALE_TOTAL = _tmetrics.registry().counter(
    _tel.M_SERVING_SCALE_TOTAL,
    "Autoscaler actions on the serving fleet", ("direction",))


@dataclass
class _Proc:
    name: str
    process: subprocess.Popen
    log_path: str


def _terminate_process(process: subprocess.Popen,
                       grace_s: float = 5.0) -> None:
    """terminate → wait → kill → reap, never raising: a process stuck in
    the kernel (e.g. D-state on a wedged device ioctl) must not abort the
    caller's loop, and the final wait records returncode instead of
    leaving a zombie."""
    if process.poll() is not None:
        return
    process.terminate()
    try:
        process.wait(timeout=grace_s)
        return
    except subprocess.TimeoutExpired:
        pass
    process.kill()
    try:
        process.wait(timeout=grace_s)
    except subprocess.TimeoutExpired:  # pragma: no cover - unkillable
        pass


_INIT_MODEL_SNIPPET = """
import sys, cloudpickle
from metisfl_tpu.tensor.pytree import pack_model
with open(sys.argv[1], "rb") as f:
    recipe = cloudpickle.load(f)
with open(sys.argv[2], "wb") as f:
    f.write(pack_model(recipe()[0].get_variables()))
"""


def build_initial_model(recipe: Callable[[], tuple], workdir: str) -> bytes:
    """Wire blob of the model ``recipe`` builds, computed in a short-lived
    CPU child. The launching process must never initialize a JAX backend
    itself: a parent that has touched the chip holds it, and the learners
    it launches then fail or hang."""
    recipe_path = os.path.join(workdir, "initial_model_recipe.pkl")
    blob_path = os.path.join(workdir, "initial_model.bin")
    with open(recipe_path, "wb") as f:
        cloudpickle.dump(recipe, f)
    subprocess.run(
        [sys.executable, "-c", _INIT_MODEL_SNIPPET, recipe_path, blob_path],
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": package_pythonpath()},
        check=True)
    with open(blob_path, "rb") as f:
        blob = f.read()
    os.unlink(blob_path)  # a full model: not left behind in the workdir
    return blob


class LocalLauncher:
    """Launch federation processes as localhost subprocesses."""

    def __init__(self, workdir: str):
        self.workdir = workdir
        self.python = sys.executable

    def launch(self, name: str, argv: Sequence[str], env: Dict[str, str]) -> _Proc:
        log_path = os.path.join(self.workdir, f"{name}.log")
        log = open(log_path, "w")
        process = subprocess.Popen(
            list(argv), stdout=log, stderr=subprocess.STDOUT,
            env={**os.environ, **env})
        return _Proc(name, process, log_path)


class SSHLauncher:
    """Launch federation processes on a remote host over ``ssh`` (the
    reference's fabric path, driver_session.py:506-582). Assumes the repo and
    interpreter exist remotely and recipe/config files are on a shared FS."""

    def __init__(self, host: str, workdir: str, python: str = "python3",
                 ssh_options: Sequence[str] = ()):
        self.host = host
        self.workdir = workdir
        self.python = python
        self.ssh_options = list(ssh_options)

    def command(self, argv: Sequence[str], env: Dict[str, str]) -> List[str]:
        env_prefix = " ".join(f"{k}={shlex.quote(v)}" for k, v in env.items())
        remote_cmd = f"{env_prefix} {' '.join(shlex.quote(a) for a in argv)}".strip()
        return ["ssh", *self.ssh_options, self.host, remote_cmd]

    def _scp_options(self) -> List[str]:
        """ssh_options translated for scp: the flags overlap except the port
        (`ssh -p` vs `scp -P`; to scp, `-p` means preserve-times and the port
        number would parse as a stray source operand)."""
        out: List[str] = []
        it = iter(self.ssh_options)
        for opt in it:
            if opt == "-p":
                out += ["-P", next(it, "")]
            else:
                out.append(opt)
        return out

    def ship_commands(self, paths: Sequence[str]) -> List[List[str]]:
        """Commands copying local files to the SAME absolute paths remotely
        (the reference `put`s model tarballs + recipes the same way,
        driver_session.py:542-556)."""
        dirs = sorted({os.path.dirname(os.path.abspath(p)) for p in paths})
        mkdir = " && ".join(f"mkdir -p {shlex.quote(d)}" for d in dirs)
        cmds: List[List[str]] = [["ssh", *self.ssh_options, self.host, mkdir]]
        scp_opts = self._scp_options()
        for p in paths:
            p = os.path.abspath(p)
            cmds.append(["scp", "-q", *scp_opts, p, f"{self.host}:{p}"])
        return cmds

    def ship(self, paths: Sequence[str]) -> None:
        for cmd in self.ship_commands(paths):
            subprocess.run(cmd, check=True)

    def launch(self, name: str, argv: Sequence[str], env: Dict[str, str]) -> _Proc:
        log_path = os.path.join(self.workdir, f"{name}.log")
        log = open(log_path, "w")
        process = subprocess.Popen(
            self.command(argv, env), stdout=log, stderr=subprocess.STDOUT)
        return _Proc(name, process, log_path)


class DriverSession:
    """Run a multi-process federation on localhost (or via custom launchers).

    ``learner_recipes``: one zero-arg callable per learner returning
    ``(model_ops, train_ds, val_ds, test_ds[, secure_backend])`` — executed
    inside the learner process.
    """

    _LOCAL_HOSTS = ("", "localhost", "127.0.0.1")

    def __init__(
        self,
        config: FederationConfig,
        initial_model_variables: Any,
        learner_recipes: Sequence[Callable[[], tuple]],
        workdir: Optional[str] = None,
        learner_env: Optional[Dict[str, str]] = None,
        launcher_factory: Optional[Callable[[str], Any]] = None,
        resume: bool = False,
        accelerator: str = "",
        host_chips: int = 0,
    ):
        """``initial_model_variables``: the host-numpy model the federation
        starts from; ``None`` takes what learner recipe 0 builds
        (:func:`build_initial_model`). ``accelerator``: the JAX platform
        learners and the serving gateway are launched on (``"tpu"``: a
        missing chip is then an error in the child, never a silent CPU
        run). Empty leaves ``JAX_PLATFORMS`` to each child's launch
        environment. Host roles
        (controller, standby, router, slice aggregators) always run on
        the CPU. ``host_chips`` > 1 splits that many chips of the local
        host one per accelerator process (learner ``idx`` gets chip
        ``idx % host_chips``, the gateway the next ones); 0 or 1 leaves
        every accelerator process all the chips it can see — the
        one-learner-owns-the-host shape."""
        self.config = config
        self.learner_recipes = list(learner_recipes)
        self.workdir = workdir or tempfile.mkdtemp(prefix="metisfl_tpu_")
        os.makedirs(self.workdir, exist_ok=True)
        if initial_model_variables is None:
            # what learner recipe 0 builds, computed off this process
            self.initial_blob = build_initial_model(self.learner_recipes[0],
                                                    self.workdir)
        else:
            self.initial_blob = pack_model(initial_model_variables)
        self.learner_env = learner_env or {}
        self.accelerator = accelerator
        self.host_chips = int(host_chips)
        self.resume = resume
        self._launcher_factory = launcher_factory
        self._local_launcher = LocalLauncher(self.workdir)
        self._procs: List[_Proc] = []
        self._client: Optional[ControllerClient] = None
        self._started_at = 0.0
        # last successfully observed learner endpoints — the shutdown
        # fallback when the controller has already died
        self._known_endpoints: List[dict] = []
        # controller crash-failover supervision state
        self._controller_restarts = 0
        # controller hot-standby state (controller.standby): pre-promotion
        # standby crashes get bounded relaunches (warm redundancy must not
        # silently evaporate); once the driver hands the controller
        # endpoint over to a promoted standby there is no third
        # incarnation — the next controller death is a double fault
        self._standby_restarts = 0
        self._standby_restart_after = 0.0
        self._standby_promoted = False
        self._chaos_armed_standby = False
        # serving supervision state, PER PROCESS NAME ("serving" for the
        # single gateway; "serving_<idx>" per fleet replica; "router"):
        # doubling capped backoff — a deterministically-crashing gateway
        # must not crash-loop at the monitor's poll rate, but unlike the
        # controller it never fails the run (serving is auxiliary)
        self._serving_restarts: Dict[str, int] = {}
        self._serving_restart_after: Dict[str, float] = {}
        # serving-fleet autoscaler (serving/fleet.py FleetAutoscaler):
        # constructed at initialize when scale rules are configured
        self._autoscaler = None
        self._shutting_down = False
        # chaos arms ORIGINAL incarnations only (see _chaos_env): learner
        # indices that already got their armed launch
        self._chaos_armed_learners: set = set()
        self._chaos_armed_slices: set = set()
        self._chaos_armed_serving: set = set()
        # slice-aggregator supervision (stateless-ish relaunch: the spool
        # persists on disk and the controller re-adopts a relaunched
        # aggregator at its next round's assign). PER-SLICE counters and
        # backoff windows — one crash-looping aggregator must not delay
        # another's relaunch
        self._slice_restarts: Dict[int, int] = {}
        self._slice_restart_after: Dict[int, float] = {}
        # fleet telemetry fabric (telemetry/fabric.py): live cross-process
        # collection during the run — constructed at initialize, None when
        # telemetry.fabric is opted out
        self._fleet = None

    # ------------------------------------------------------------------ #
    # bootstrap
    # ------------------------------------------------------------------ #

    def _launcher_for(self, hostname: str):
        """Local subprocess for localhost endpoints, SSH otherwise
        (the reference always SSHes, even to localhost — driver_session.py:506)."""
        if self._launcher_factory is not None:
            return self._launcher_factory(hostname)
        if hostname in self._LOCAL_HOSTS:
            return self._local_launcher
        return SSHLauncher(hostname, self.workdir)

    def _endpoint(self, idx: int):
        if idx < len(self.config.learners):
            return self.config.learners[idx]
        from metisfl_tpu.config import LearnerEndpoint
        return LearnerEndpoint()

    def _ssl_files(self) -> List[str]:
        if not self.config.ssl.enabled:
            return []
        return [p for p in (self.config.ssl.cert_path,
                            self.config.ssl.key_path) if p]

    def _base_env(self) -> Dict[str, str]:
        env = {"PYTHONPATH": package_pythonpath()}
        # SSH children receive only this dict: an externally placed
        # compile cache must reach them too
        if os.environ.get(CACHE_DIR_ENV):
            env[CACHE_DIR_ENV] = os.environ[CACHE_DIR_ENV]
        return env

    def _host_env(self) -> Dict[str, str]:
        """Controller, standby, router, slice aggregators: wire models are
        host numpy and fold on the host (aggregation/base.py), so these
        never touch — or hold — a chip."""
        return {**self._base_env(), "JAX_PLATFORMS": "cpu"}

    def _accelerator_env(self, slot: int) -> Dict[str, str]:
        """Learners and gateways: the platform the operator asked for and,
        on a split host, the chip that is theirs. ``slot`` numbers the
        accelerator processes: learners first, then gateway replicas."""
        env = self._base_env()
        if self.accelerator:
            env["JAX_PLATFORMS"] = self.accelerator
        if self.host_chips > 1:
            env.update(chip_env(slot % self.host_chips))
        return env

    def _prepare_secure(self) -> None:
        """Generate + distribute secure-aggregation material (the reference's
        driver-side HE keygen and key shipping, driver_session.py:110-140):
        CKKS keys or the masking federation secret go into per-learner files;
        the controller's config carries only what it must know (party count /
        scheme) — never decryption capability."""
        cfg = self.config.secure
        if not cfg.enabled:
            return
        if cfg.scheme == "ckks":
            key_dir = cfg.key_dir or os.path.join(self.workdir, "he_keys")
            if not os.path.exists(os.path.join(key_dir, "sk.bin")):
                from metisfl_tpu.secure.ckks import generate_keys
                generate_keys(key_dir)
            cfg.key_dir = key_dir
            per_learner = {"scheme": "ckks", "key_dir": key_dir, "kwargs": {}}
            learner_files = [per_learner] * len(self.learner_recipes)
        elif cfg.scheme == "masking":
            import secrets as _secrets
            cfg.num_parties = len(self.learner_recipes)
            secret = _secrets.token_hex(32)
            learner_files = [
                {"scheme": "masking", "kwargs": {
                    "federation_secret": secret, "party_index": idx,
                    "num_parties": cfg.num_parties,
                    "min_parties": cfg.min_recovery_parties,
                    "neighbors": cfg.mask_neighbors}}
                for idx in range(len(self.learner_recipes))
            ]
        else:  # identity
            learner_files = [{"scheme": cfg.scheme, "kwargs": {}}
                             for _ in self.learner_recipes]
        from metisfl_tpu.comm.codec import dumps as codec_dumps
        for idx, payload in enumerate(learner_files):
            path = os.path.join(self.workdir, f"learner_{idx}_secure.bin")
            with open(path, "wb") as f:
                f.write(codec_dumps(payload))
            os.chmod(path, 0o600)

    def _secure_files(self, idx: int) -> List[str]:
        """Files learner ``idx`` needs for secure aggregation (for SSH ship)."""
        if not self.config.secure.enabled:
            return []
        files = [os.path.join(self.workdir, f"learner_{idx}_secure.bin")]
        if self.config.secure.scheme == "ckks":
            key_dir = self.config.secure.key_dir
            files += [os.path.join(key_dir, "pk.bin"),
                      os.path.join(key_dir, "sk.bin")]
        return files

    def initialize_federation(self, health_retries: int = 30,
                              health_sleep_s: float = 1.0,
                              launch_serving: bool = True) -> None:
        """``launch_serving=False`` pins the serving ports but leaves the
        gateway to a later :meth:`launch_serving` — for a host whose
        chips the learners fill: train, :meth:`stop_learners`, then
        serve the registry's model from a freed chip."""
        self._prepare_secure()
        # telemetry trace sinks default into the experiment workdir so
        # controller + learner spans stitch into one tree on disk; the
        # same path ships to learners via --telemetry-dir (local
        # launchers share the filesystem; SSH learners keep their files
        # remote and collect_traces skips them)
        if self.config.telemetry.enabled and not self.config.telemetry.dir:
            self.config.telemetry.dir = os.path.join(self.workdir,
                                                     "telemetry")
        if self.config.telemetry.enabled and self.config.telemetry.dir:
            os.makedirs(self.config.telemetry.dir, exist_ok=True)
        # flight recorder: bundle dir defaults into the workdir so
        # controller/learner crash bundles land in the experiment dir the
        # driver already collects (docs/OBSERVABILITY.md). The driver
        # process arms its own recorder too — failover relaunches dump a
        # driver-side bundle with the FailoverBegan event tail.
        if (self.config.telemetry.enabled
                and not self.config.telemetry.postmortem_dir):
            self.config.telemetry.postmortem_dir = os.path.join(
                self.workdir, "postmortem")
        if self.config.telemetry.enabled:
            os.makedirs(self.config.telemetry.postmortem_dir, exist_ok=True)
            _tpostmortem.configure(self.config.telemetry.postmortem_dir,
                                   service="driver", install_hooks=False)
        # TLS: generate the federation's self-signed pair on first boot
        # (reference driver keygen posture, ssl_configurator.py:21-30)
        if self.config.ssl.enabled and not self.config.ssl.cert_path:
            from metisfl_tpu.comm.ssl import generate_self_signed
            hosts = sorted(
                {ep.hostname for ep in self.config.learners}
                | {self.config.controller_host} | set(self.config.ssl.hosts)
            )
            cert, key = generate_self_signed(
                os.path.join(self.workdir, "tls"),
                hosts=[h for h in hosts if h not in self._LOCAL_HOSTS])
            self.config.ssl.cert_path, self.config.ssl.key_path = cert, key

        # Controller supervision needs a checkpoint to restore from:
        # default the checkpoint dir into the workdir so a relaunched
        # controller resumes the community model, round counter, AND the
        # learner registry instead of starting a ghost federation.
        if (self.config.failover.supervise_controller
                and not self.config.checkpoint.dir):
            self.config.checkpoint.dir = os.path.join(self.workdir,
                                                      "checkpoint")
        if self.config.checkpoint.dir:
            os.makedirs(self.config.checkpoint.dir, exist_ok=True)

        # Controller hot-standby (controller/wal.py + controller/__main__
        # --standby): pin the standby's endpoint and WAL dir BEFORE the
        # config write below. The config ships to the standby (it tails
        # wal_dir), to the controller (it arms its WAL appends), and to
        # learners + the serving gateway (they hold BOTH controller
        # endpoints up front — failover is a re-dial to a known port,
        # never a discovery).
        standby = self.config.controller.standby
        if standby.enabled:
            if not standby.wal_dir:
                standby.wal_dir = os.path.join(self.workdir, "wal")
            os.makedirs(standby.wal_dir, exist_ok=True)
            if not standby.port:
                if (standby.host or
                        "localhost") not in self._LOCAL_HOSTS:
                    # same guard as serving/coordinator ports: a port
                    # probed on the driver machine says nothing about
                    # the remote host the standby will bind on
                    raise ValueError(
                        "controller.standby on remote host "
                        f"{standby.host!r} requires an explicit "
                        "controller.standby.port")
                import socket as _socket
                with _socket.socket() as s:
                    s.bind(("127.0.0.1", 0))
                    standby.port = s.getsockname()[1]

        # serving gateway/fleet: the config file below ships to the
        # gateway (and router) processes too, so every port must be
        # pinned BEFORE the write — an ephemeral bind would leave the
        # driver (and clients) unable to dial it for shutdown or traffic
        if self.config.serving.enabled:
            fleet = self.config.serving.fleet
            needs_ports = (not self.config.serving.port
                           or (fleet.enabled
                               and (not fleet.router_port
                                    or not fleet.gateways)))
            if needs_ports and (self.config.controller_host or
                                "localhost") not in self._LOCAL_HOSTS:
                # same guard as the multi-host coordinator port: a port
                # probed on the driver machine says nothing about the
                # remote host the gateway will bind on
                raise ValueError(
                    "serving on remote host "
                    f"{self.config.controller_host!r} requires explicit "
                    "serving ports (serving.port / serving.fleet."
                    "router_port + gateways)")
            import socket as _socket

            def _free_port() -> int:
                with _socket.socket() as s:
                    s.bind(("127.0.0.1", 0))
                    return s.getsockname()[1]

            if fleet.enabled:
                if not fleet.gateways:
                    fleet.gateways = [
                        {"name": f"serving_{idx}", "host": "localhost",
                         "port": _free_port()}
                        for idx in range(fleet.replicas)]
                if not fleet.router_port:
                    fleet.router_port = _free_port()
                # serving.port is what serving_client() (and every other
                # consumer) dials — in a fleet that is the ROUTER
                self.config.serving.port = fleet.router_port
            elif not self.config.serving.port:
                self.config.serving.port = _free_port()

        # distributed slice aggregators (aggregation/slice.py): pin their
        # endpoints + spool dirs BEFORE the config write — the config
        # file ships to the slice processes AND tells the controller
        # where to dial, so nothing here may stay ephemeral
        tree = self.config.aggregation.tree
        if tree.enabled and tree.distributed and not tree.slices:
            if (self.config.controller_host or
                    "localhost") not in self._LOCAL_HOSTS:
                # same guard as serving/coordinator ports: a port probed
                # on the driver machine says nothing about a remote host
                # — remote aggregator fleets list tree.slices explicitly
                raise ValueError(
                    "aggregation.tree.distributed on remote host "
                    f"{self.config.controller_host!r} requires explicit "
                    "aggregation.tree.slices endpoints")
            import socket as _socket
            spool_root = tree.spool_dir or os.path.join(self.workdir,
                                                        "slices")
            tree.spool_dir = spool_root
            for idx in range(tree.branch):
                with _socket.socket() as s:
                    s.bind(("127.0.0.1", 0))
                    port = s.getsockname()[1]
                tree.slices.append({
                    "name": f"slice_{idx}", "host": "localhost",
                    "port": port,
                    "spool_dir": os.path.join(spool_root, f"slice_{idx}")})
        if tree.enabled and tree.distributed:
            for spec in tree.slices:
                if spec.get("spool_dir"):
                    os.makedirs(spec["spool_dir"], exist_ok=True)

        config_path = os.path.join(self.workdir, "federation_config.bin")
        with open(config_path, "wb") as f:
            f.write(self.config.to_wire())
        self._config_path = config_path

        if tree.enabled and tree.distributed:
            # the aggregator fleet boots before the controller so round
            # 1's first uplink never races a half-up slice (a dead slice
            # would still re-home, but the clean path should be clean)
            for idx in range(len(tree.slices)):
                self._launch_slice(idx)
            self._wait_slices_healthy()

        ctrl_host = self.config.controller_host or "localhost"
        self._launch_controller(resume=self.resume)
        if standby.enabled:
            # boot the standby right behind the primary so it tails the
            # WAL from record one; the driver's own client carries the
            # standby endpoint too and re-dials on failover like any peer
            self._launch_standby()
        self._client = ControllerClient(ctrl_host, self.config.controller_port,
                                        ssl=self.config.ssl,
                                        comm=self.config.comm,
                                        standby=((standby.host or "localhost",
                                                  standby.port)
                                                 if standby.enabled else None))
        self._wait_healthy(health_retries, health_sleep_s)

        # ship initial model (reference _ship_model_to_controller :334-342)
        # unless resuming from a checkpointed community model (cheap check:
        # a restored controller reports its checkpointed round counter)
        if not (self.resume
                and self._client.get_statistics()["global_iteration"] > 0):
            self._client.replace_community_model(self.initial_blob)

        for idx in range(len(self.learner_recipes)):
            self.launch_learner(idx)
        if launch_serving:
            self.launch_serving()
        self._start_fleet_collector()
        self._started_at = time.time()

    def launch_serving(self) -> None:
        """Boot the serving plane (single gateway, or fleet replicas +
        router) against the running controller's registry."""
        if not self.config.serving.enabled:
            return
        fleet = self.config.serving.fleet
        if fleet.enabled:
            for idx in range(len(fleet.gateways)):
                self._launch_gateway(idx)
            self._launch_router()
            self._setup_autoscaler()
        else:
            self._launch_gateway()

    # ------------------------------------------------------------------ #
    # fleet telemetry fabric (telemetry/fabric.py)
    # ------------------------------------------------------------------ #

    def _fleet_peer_specs(self) -> List[dict]:
        """Peer specs for the fleet collector's per-poll discovery:
        controller + every registered learner + the serving gateway.
        Learners that join mid-run appear on the next poll; departed
        ones stay listed and go visibly stale."""
        from metisfl_tpu.controller.service import (CONTROLLER_SERVICE,
                                                    LEARNER_SERVICE)

        ctrl_host = self.config.controller_host or "localhost"
        specs = [{"name": "controller", "host": ctrl_host,
                  "port": self.config.controller_port,
                  "service_name": CONTROLLER_SERVICE,
                  "role": "controller"}]
        standby = self.config.controller.standby
        if standby.enabled and not self._standby_promoted:
            # the warm standby answers CollectTelemetry on a role-tagged
            # methodless service (controller/__main__.py), so `status
            # --fleet` shows it as a live role="standby" peer; after the
            # handoff the controller row above IS the promoted standby
            specs.append({"name": "standby",
                          "host": standby.host or "localhost",
                          "port": standby.port,
                          "service_name": CONTROLLER_SERVICE,
                          "role": "standby"})
        try:
            endpoints = self._client.list_learners(timeout=5.0,
                                                   wait_ready=False)
            self._known_endpoints = endpoints
        except Exception:  # noqa: BLE001 - keep the stale snapshot; the
            # already-known peers keep getting polled either way
            endpoints = list(self._known_endpoints)
        for ep in endpoints:
            if not ep.get("port"):
                continue
            specs.append({"name": ep.get("learner_id") or
                          f"{ep['hostname']}:{ep['port']}",
                          "host": ep["hostname"], "port": ep["port"],
                          "service_name": LEARNER_SERVICE,
                          "role": "learner"})
        if self.config.serving.enabled and self.config.serving.port:
            from metisfl_tpu.serving.service import SERVING_SERVICE
            fleet = self.config.serving.fleet
            if fleet.enabled:
                # router + EVERY gateway replica as role="serving" peers:
                # the fabric pulls (spans/events/metrics/prof) cover the
                # whole fleet and status --fleet prints per-replica
                # prof: lines
                specs.append({"name": "router", "host": ctrl_host,
                              "port": fleet.router_port,
                              "service_name": SERVING_SERVICE,
                              "role": "serving"})
                for idx, spec in enumerate(fleet.gateways):
                    specs.append({
                        "name": spec.get("name") or f"serving_{idx}",
                        "host": spec.get("host", "localhost"),
                        "port": spec["port"],
                        "service_name": SERVING_SERVICE,
                        "role": "serving"})
            else:
                specs.append({"name": "serving", "host": ctrl_host,
                              "port": self.config.serving.port,
                              "service_name": SERVING_SERVICE,
                              "role": "serving"})
        tree = self.config.aggregation.tree
        if tree.enabled and tree.distributed:
            from metisfl_tpu.aggregation.slice import SLICE_SERVICE
            for spec in tree.slices:
                if spec.get("port"):
                    specs.append({"name": spec.get("name") or
                                  f"{spec['host']}:{spec['port']}",
                                  "host": spec.get("host", "localhost"),
                                  "port": spec["port"],
                                  "service_name": SLICE_SERVICE,
                                  "role": "slice"})
        return specs

    def _start_fleet_collector(self) -> None:
        tel = self.config.telemetry
        if not (tel.enabled and tel.fabric.enabled):
            return
        from metisfl_tpu.telemetry.fabric import FleetCollector

        self._fleet = FleetCollector(
            poll_every_s=tel.fabric.poll_every_s,
            jitter=tel.fabric.jitter,
            offset_alpha=tel.fabric.offset_alpha,
            rtt_gate=tel.fabric.rtt_gate,
            # live, crash-durable span stream — the experiment dir's
            # traces.jsonl exists (and grows) WHILE the run is alive
            trace_out=os.path.join(self.workdir, "traces.jsonl"),
            ssl=self.config.ssl, comm=self.config.comm,
            discover_fn=self._fleet_peer_specs,
            critical_path=tel.fabric.critical_path,
            critical_path_edges=tel.fabric.critical_path_edges)
        self._fleet.start()

    def fleet_collector(self):
        """The live :class:`~metisfl_tpu.telemetry.fabric.FleetCollector`
        (None when ``telemetry.fabric`` is opted out)."""
        return self._fleet

    def _chaos_env(self, process: str, idx: Optional[int] = None) -> Dict[str, str]:
        """METISFL_TPU_CHAOS env for one subprocess: the configured chaos
        rules whose ``process`` selector matches (empty selector = every
        process; ``learner`` = any learner; ``learner_<idx>`` = one).
        Applied only to ORIGINAL incarnations — a supervised relaunch
        runs clean, otherwise a kill rule would re-fire on every restart
        and no failover could ever be proven to converge."""
        cfg = self.config.chaos
        if not cfg.enabled or not cfg.rules:
            return {}
        wanted = {"", process}
        if idx is not None:
            wanted.add(f"{process}_{idx}")
        rules = [r for r in cfg.rules if r.get("process", "") in wanted]
        if not rules:
            return {}
        return {_CHAOS_ENV_VAR: json.dumps({"seed": cfg.seed,
                                            "rules": rules})}

    def _launch_controller(self, resume: bool = False) -> _Proc:
        """(Re)launch the controller; replaces any tracked (dead) process
        of the same name. ``resume=True`` restores from the latest
        checkpoint (community model + round counter + learner registry)
        and re-dispatches the abandoned round."""
        ctrl_host = self.config.controller_host or "localhost"
        launcher = self._launcher_for(ctrl_host)
        argv = [getattr(launcher, "python", sys.executable),
                "-m", "metisfl_tpu.controller",
                "--config", self._config_path,
                "--port", str(self.config.controller_port)]
        if resume:
            argv.append("--resume")
        if isinstance(launcher, SSHLauncher):
            launcher.ship([self._config_path] + self._ssl_files())
        env = self._host_env()
        if self._controller_restarts == 0:
            env.update(self._chaos_env("controller"))
        self._procs = [p for p in self._procs if p.name != "controller"]
        proc = launcher.launch("controller", argv, env=env)
        self._procs.append(proc)
        return proc

    def _launch_standby(self) -> _Proc:
        """(Re)launch the warm hot-standby (controller/__main__.py
        ``--standby``): it tails the WAL at ``controller.standby.wal_dir``
        and promotes itself on primary death — the driver never promotes
        it by RPC, it only observes the promotion (probe-driven, the same
        staleness→health escalation every peer uses)."""
        standby = self.config.controller.standby
        host = standby.host or "localhost"
        launcher = self._launcher_for(host)
        argv = [getattr(launcher, "python", sys.executable),
                "-m", "metisfl_tpu.controller",
                "--config", self._config_path,
                "--port", str(standby.port),
                "--standby"]
        if isinstance(launcher, SSHLauncher):
            launcher.ship([self._config_path] + self._ssl_files())
        env = self._host_env()
        if not self._chaos_armed_standby:
            # original incarnation only, same posture as every other
            # chaos-killable process: a supervised relaunch runs clean
            self._chaos_armed_standby = True
            env.update(self._chaos_env("standby"))
        self._procs = [p for p in self._procs if p.name != "standby"]
        proc = launcher.launch("standby", argv, env=env)
        self._procs.append(proc)
        return proc

    def _supervise_controller(self) -> bool:
        """Crash failover (docs/RESILIENCE.md): when the controller
        process has died, either hand the federation over to the hot
        standby (``controller.standby.enabled`` — wait for its probe-
        driven promotion, then swap the configured controller endpoint)
        or relaunch it with ``--resume`` under a bounded restart budget
        with doubling backoff. Returns True when a restart/handoff
        happened this call; raises once the budget is exhausted or no
        standby is left (a deterministically-crashing controller must
        fail the run, not crash-loop forever)."""
        ctrl = next((p for p in self._procs if p.name == "controller"), None)
        if (ctrl is None or self._shutting_down
                or ctrl.process.poll() is None):
            return False
        if self.config.controller.standby.enabled:
            # hot-standby posture: the primary is never relaunched — the
            # warm standby promotes and the driver re-points everything
            return self._failover_to_standby(ctrl)
        fo = self.config.failover
        if not fo.supervise_controller:
            return False  # _check_procs_alive reports the death as fatal
        code = ctrl.process.poll()
        if self._controller_restarts >= fo.max_controller_restarts:
            with open(ctrl.log_path) as f:
                tail = f.read()[-2000:]
            raise RuntimeError(
                f"controller died (exit {code}) with the restart budget "
                f"({fo.max_controller_restarts}) exhausted; log tail:\n"
                f"{tail}")
        self._controller_restarts += 1
        backoff = fo.restart_backoff_s * (2 ** (self._controller_restarts - 1))
        logger.warning(
            "controller died (exit %s); supervised restart %d/%d with "
            "--resume in %.1fs", code, self._controller_restarts,
            fo.max_controller_restarts, backoff)
        # journal + flight-record the failover from the driver's side:
        # the dead controller dumped (or couldn't); the supervisor's own
        # bundle records WHEN it saw the death and what it did about it
        _tevents.emit(_tevents.FailoverBegan,
                      restart=self._controller_restarts, exit_code=code)
        _tpostmortem.dump("failover_relaunch",
                          extra={"exit_code": code,
                                 "restart": self._controller_restarts})
        time.sleep(backoff)
        self._launch_controller(resume=True)
        _M_CTRL_RESTARTS.inc()
        try:
            self._wait_healthy(30, 1.0)
        except RuntimeError as exc:
            # the relaunch itself died (stale port, corrupt checkpoint, a
            # learner crashed mid-wait): consume the budget across
            # supervision cycles instead of aborting with restarts left —
            # the next monitor iteration re-evaluates (and the budget
            # check above fails the run once it is truly exhausted)
            if self._controller_restarts >= fo.max_controller_restarts:
                raise
            logger.warning("relaunched controller not healthy (%s); "
                           "supervision will retry", exc)
            return True
        logger.info("controller restarted and healthy (restart %d)",
                    self._controller_restarts)
        return True

    def _failover_to_standby(self, ctrl: _Proc) -> bool:
        """Controller death with a hot standby configured: wait (bounded)
        for the standby's self-promotion to answer SERVING on the
        controller service, then swap ``controller_host``/``_port`` to
        the standby endpoint — every config consumer (fleet peer specs,
        shutdown dialing, learner relaunch argv) follows automatically,
        and live peers re-dial on their own via the two-endpoint client
        contract. A dead standby (or a second controller death after the
        handoff) is a double fault: fail fast, there is no third
        incarnation."""
        code = ctrl.process.poll()
        standby = self.config.controller.standby
        host = standby.host or "localhost"
        sb = next((p for p in self._procs if p.name == "standby"), None)
        if self._standby_promoted or sb is None or (
                sb.process.poll() is not None):
            with open(ctrl.log_path) as f:
                tail = f.read()[-2000:]
            raise RuntimeError(
                f"controller died (exit {code}) with no live standby "
                "left (double fault); log tail:\n" + tail)
        logger.warning("controller died (exit %s); waiting for standby "
                       "%s:%d to promote", code, host, standby.port)
        _tevents.emit(_tevents.FailoverBegan, restart=1, exit_code=code)
        _tpostmortem.dump("failover_handoff", extra={"exit_code": code})
        from metisfl_tpu.comm.health import probe_health
        from metisfl_tpu.controller.service import CONTROLLER_SERVICE
        # promotion budget: one full staleness window + the probe
        # escalation, plus headroom for the WAL restore itself
        budget = (standby.stale_after_s
                  + standby.probe_interval_s * (standby.probe_failures + 2)
                  + 30.0)
        t0 = time.monotonic()
        while time.monotonic() - t0 < budget:
            if sb.process.poll() is not None:
                break  # died mid-promotion → double-fault below
            if probe_health(host, standby.port, CONTROLLER_SERVICE,
                            ssl=self.config.ssl,
                            comm=self.config.comm) == "SERVING":
                waited = time.monotonic() - t0
                self.config.controller_host = host
                self.config.controller_port = standby.port
                self._standby_promoted = True
                # the promoted standby IS the controller now: retag the
                # tracked process (dropping the dead primary) so shutdown
                # waits on it and a later death trips the double-fault
                # branch above instead of "standby died" supervision
                self._procs = [p for p in self._procs
                               if p.name != "controller"]
                sb.name = "controller"
                _M_CTRL_FAILOVER.inc(role="driver")
                _tevents.emit(_tevents.ControllerFailover, role="driver",
                              host=host, port=standby.port,
                              promote_s=round(waited, 4),
                              reason=f"controller_exit_{code}")
                logger.warning(
                    "standby promoted at %s:%d after %.1fs; controller "
                    "endpoint handed over", host, standby.port, waited)
                return True
            time.sleep(min(1.0, standby.probe_interval_s))
        with open(sb.log_path) as f:
            tail = f.read()[-2000:]
        raise RuntimeError(
            f"controller died (exit {code}) and the standby at "
            f"{host}:{standby.port} never promoted within {budget:.0f}s; "
            "standby log tail:\n" + tail)

    def _supervise_standby(self) -> bool:
        """Pre-promotion standby supervision: a crashed WARM standby is
        relaunched (bounded, capped doubling backoff) — it re-tails the
        WAL and is promote-ready again with no handoff. Budget exhausted
        = the federation runs on without hot-standby cover (logged
        loudly; the next controller death is then fatal). Never fails
        the run: the standby is redundancy, not the service."""
        standby = self.config.controller.standby
        if (not standby.enabled or self._standby_promoted
                or self._shutting_down):
            return False
        sb = next((p for p in self._procs if p.name == "standby"), None)
        if sb is None or sb.process.poll() is None:
            return False
        if time.time() < self._standby_restart_after:
            return False
        code = sb.process.poll()
        fo = self.config.failover
        if self._standby_restarts >= fo.max_controller_restarts:
            logger.error(
                "standby died (exit %s) with its relaunch budget (%d) "
                "exhausted; continuing WITHOUT hot-standby cover — the "
                "next controller death is fatal", code,
                fo.max_controller_restarts)
            self._procs = [p for p in self._procs if p.name != "standby"]
            return False
        self._standby_restarts += 1
        backoff = fo.restart_backoff_s * (2 ** (self._standby_restarts - 1))
        self._standby_restart_after = time.time() + min(backoff, 60.0)
        logger.warning("standby died (exit %s); relaunch %d/%d", code,
                       self._standby_restarts, fo.max_controller_restarts)
        self._launch_standby()
        return True

    def _recipe_path(self, idx: int) -> str:
        """Cloudpickle learner recipe ``idx`` into the workdir (idempotent
        — the gateway and the learner launch share one file)."""
        path = os.path.join(self.workdir, f"learner_{idx}_recipe.pkl")
        if not os.path.exists(path):
            with open(path, "wb") as f:
                cloudpickle.dump(self.learner_recipes[idx], f)
        return path

    def _launch_gateway(self, replica: Optional[int] = None) -> _Proc:
        """(Re)launch a serving gateway (serving/__main__.py) — the
        single supervised gateway (``replica=None``) or fleet replica
        ``replica``. It needs no state handoff: the first registry poll
        pins a relaunch back to the last promoted stable version."""
        cfg = self.config.serving
        if cfg.recipe_index >= len(self.learner_recipes):
            # same rationale as the config's negative-index rejection: a
            # silently clamped index would boot the gateway on the wrong
            # architecture and every registry sync would fail decoding
            raise ValueError(
                f"serving.recipe_index={cfg.recipe_index} but only "
                f"{len(self.learner_recipes)} learner recipe(s) exist")
        recipe_path = self._recipe_path(cfg.recipe_index)
        launcher = self._launcher_for(self.config.controller_host or
                                      "localhost")
        argv = [getattr(launcher, "python", sys.executable),
                "-m", "metisfl_tpu.serving",
                "--config", self._config_path,
                "--recipe", recipe_path]
        name = "serving"
        chaos_idx = None
        if replica is not None:
            spec = cfg.fleet.gateways[replica]
            name = spec.get("name") or f"serving_{replica}"
            # each replica binds its pinned port and staggers its
            # registry polls by its fleet index (serving/fleet.py
            # poll_stagger — the thundering-herd fix, and what makes
            # promotion a ROLLING swap across the fleet)
            argv += ["--port", str(spec["port"]),
                     "--replica-index", str(replica),
                     "--replicas", str(len(cfg.fleet.gateways))]
            chaos_idx = replica
        if isinstance(launcher, SSHLauncher):
            launcher.ship([self._config_path, recipe_path]
                          + self._ssl_files())
        env = self._accelerator_env(len(self.learner_recipes)
                                    + (replica or 0))
        if name not in self._chaos_armed_serving:
            # original incarnation only — a supervised relaunch runs
            # clean, same contract as the controller/learner chaos
            # arming (process="serving" arms every replica,
            # "serving_<idx>" exactly one)
            self._chaos_armed_serving.add(name)
            env.update(self._chaos_env("serving", chaos_idx))
        self._procs = [p for p in self._procs if p.name != name]
        proc = launcher.launch(name, argv, env=env)
        self._procs.append(proc)
        return proc

    def _launch_router(self) -> _Proc:
        """(Re)launch the serving-fleet router (``python -m
        metisfl_tpu.serving --router``). Stateless: it re-reads the
        initial fleet from the config and the driver re-syncs any
        autoscaled replicas right after (_sync_router_fleet)."""
        launcher = self._launcher_for(self.config.controller_host or
                                      "localhost")
        argv = [getattr(launcher, "python", sys.executable),
                "-m", "metisfl_tpu.serving", "--router",
                "--config", self._config_path]
        if isinstance(launcher, SSHLauncher):
            launcher.ship([self._config_path] + self._ssl_files())
        env = self._host_env()
        if "router" not in self._chaos_armed_serving:
            self._chaos_armed_serving.add("router")
            env.update(self._chaos_env("router"))
        self._procs = [p for p in self._procs if p.name != "router"]
        proc = launcher.launch("router", argv, env=env)
        self._procs.append(proc)
        return proc

    def _serving_proc_names(self) -> List[str]:
        """Names of every serving-plane process the driver supervises."""
        if not self.config.serving.enabled:
            return []
        fleet = self.config.serving.fleet
        if not fleet.enabled:
            return ["serving"]
        return [spec.get("name") or f"serving_{i}"
                for i, spec in enumerate(fleet.gateways)] + ["router"]

    def _router_admin(self):
        """A fail-fast RpcClient against the router's admin surface."""
        from metisfl_tpu.comm.rpc import RpcClient
        from metisfl_tpu.serving.service import SERVING_SERVICE
        return RpcClient(self.config.controller_host or "localhost",
                         self.config.serving.fleet.router_port,
                         SERVING_SERVICE, retries=0, ssl=self.config.ssl)

    def _sync_router_fleet(self) -> None:
        """AddReplica every current replica (idempotent) — how a
        relaunched router learns about autoscaled replicas its config
        file predates."""
        from metisfl_tpu.comm.codec import dumps as _dumps
        client = self._router_admin()
        try:
            for idx, spec in enumerate(self.config.serving.fleet.gateways):
                client.call("AddReplica", _dumps(
                    {"name": spec.get("name") or f"serving_{idx}",
                     "host": spec.get("host", "localhost"),
                     "port": spec["port"]}), timeout=5.0,
                    wait_ready=False)
        except Exception:  # noqa: BLE001 - probes re-adopt eventually
            logger.warning("router fleet re-sync failed; the router "
                           "keeps its config-file fleet")
        finally:
            client.close()

    def _launch_slice(self, idx: int) -> _Proc:
        """(Re)launch slice aggregator ``idx`` (aggregation/slice.py). It
        needs no state handoff: its spool directory persists on disk and
        the controller re-adopts a relaunched aggregator at the next
        round's slice assignment (health-probe revival)."""
        launcher = self._launcher_for(self.config.controller_host or
                                      "localhost")
        name = f"slice_{idx}"
        argv = [getattr(launcher, "python", sys.executable),
                "-m", "metisfl_tpu.aggregation.slice",
                "--config", self._config_path,
                "--index", str(idx)]
        if isinstance(launcher, SSHLauncher):
            launcher.ship([self._config_path] + self._ssl_files())
        env = self._host_env()
        if idx not in self._chaos_armed_slices:
            # original incarnation only: kill-at-slice rules
            # (process="slice" / "slice_<idx>") must not re-fire on the
            # supervised relaunch, or re-homing could never converge
            self._chaos_armed_slices.add(idx)
            env.update(self._chaos_env("slice", idx))
        self._procs = [p for p in self._procs if p.name != name]
        proc = launcher.launch(name, argv, env=env)
        self._procs.append(proc)
        return proc

    def _wait_slices_healthy(self, retries: int = 30,
                             sleep_s: float = 0.5) -> None:
        from metisfl_tpu.aggregation.slice import SLICE_SERVICE
        from metisfl_tpu.comm.health import probe_health

        pending = list(self.config.aggregation.tree.slices)
        for _ in range(retries):
            pending = [
                spec for spec in pending
                if probe_health(spec["host"], spec["port"], SLICE_SERVICE,
                                ssl=self.config.ssl) != "SERVING"]
            if not pending:
                return
            self._check_procs_alive()
            time.sleep(sleep_s)
        raise RuntimeError(
            f"slice aggregator(s) never became healthy: "
            f"{[s.get('name') for s in pending]}")

    def _supervise_slices(self) -> bool:
        """Slice-aggregator crash failover: a dead aggregator process is
        relaunched (backoff-bounded like the gateway). The federation
        does NOT wait for it — the controller already re-homed its slice
        mid-round; the relaunch rejoins the tier at a later round's
        assignment. Returns True when a relaunch happened this call."""
        tree = self.config.aggregation.tree
        if not (tree.enabled and tree.distributed) or self._shutting_down:
            return False
        restarted = False
        for idx in range(len(tree.slices)):
            proc = next((p for p in self._procs
                         if p.name == f"slice_{idx}"), None)
            if proc is None or proc.process.poll() is None:
                continue
            if time.time() < self._slice_restart_after.get(idx, 0.0):
                continue  # this slice's backoff window: relaunch later
            code = proc.process.poll()
            restarts = self._slice_restarts.get(idx, 0) + 1
            self._slice_restarts[idx] = restarts
            self._slice_restart_after[idx] = time.time() + min(
                30.0, 0.5 * (2 ** (restarts - 1)))
            logger.warning("slice aggregator %d died (exit %s); "
                           "supervised relaunch %d", idx, code, restarts)
            self._launch_slice(idx)
            restarted = True
        return restarted

    def _supervise_gateway(self) -> bool:
        """Serving-plane crash failover: a dead gateway (single, or any
        fleet replica, or the router) is relaunched (unbounded — all are
        stateless; the registry re-pins a replica and the probe loop
        re-adopts it into the ring), so a chaos kill mid-canary costs
        one restart, not the serving plane. Per-process backoff: one
        crash-looping replica never delays another's relaunch. Returns
        True when any restart happened this call."""
        if not self.config.serving.enabled or self._shutting_down:
            return False
        fleet = self.config.serving.fleet
        restarted = False
        for name in self._serving_proc_names():
            proc = next((p for p in self._procs if p.name == name), None)
            if proc is None or proc.process.poll() is None:
                continue
            if time.time() < self._serving_restart_after.get(name, 0.0):
                continue  # this process's backoff window
            code = proc.process.poll()
            restarts = self._serving_restarts.get(name, 0) + 1
            self._serving_restarts[name] = restarts
            self._serving_restart_after[name] = time.time() + min(
                30.0, 0.5 * (2 ** (restarts - 1)))
            logger.warning("%s died (exit %s); supervised relaunch %d",
                           name, code, restarts)
            _tpostmortem.dump("gateway_relaunch",
                              extra={"process": name, "exit_code": code,
                                     "restart": restarts})
            if name == "router":
                self._launch_router()
                # a relaunched router re-reads the config-file fleet;
                # autoscaled replicas are re-added once it answers
                self._sync_router_fleet()
            elif fleet.enabled:
                idx = next(
                    (i for i, spec in enumerate(fleet.gateways)
                     if (spec.get("name") or f"serving_{i}") == name),
                    None)
                if idx is None:
                    continue  # scaled away between poll and relaunch
                self._launch_gateway(idx)
            else:
                self._launch_gateway()
            _M_GATEWAY_RESTARTS.inc()
            restarted = True
        return restarted

    # ------------------------------------------------------------------ #
    # serving-fleet autoscaling (serving/fleet.py FleetAutoscaler)
    # ------------------------------------------------------------------ #

    def _setup_autoscaler(self) -> None:
        fleet = self.config.serving.fleet
        if not (self.config.serving.enabled and fleet.enabled
                and (fleet.scale_up or fleet.scale_down)):
            return
        from metisfl_tpu.serving.fleet import FleetAutoscaler
        self._autoscaler = FleetAutoscaler(
            fleet.scale_up or None, fleet.scale_down or None,
            fleet.min_replicas, fleet.max_replicas,
            cooldown_s=fleet.scale_cooldown_s)
        _M_FLEET_REPLICAS.set(len(fleet.gateways))

    def _scrape_serving_families(self) -> Dict[str, float]:
        """Fleet-summed ``serving_*`` family values: one GetMetrics
        scrape per live replica, counters/gauges summed across series
        and replicas — the sample the autoscaler's alert rules judge."""
        from metisfl_tpu.comm.rpc import RpcClient
        from metisfl_tpu.serving.service import SERVING_SERVICE
        totals: Dict[str, float] = {}
        fleet = self.config.serving.fleet
        # replicas + the ROUTER: serving_router_* families (fleet QPS as
        # the router sees it) live in the router process — a rule over
        # them must not silently sample 0 forever
        targets = ([(spec.get("host", "localhost"), spec["port"])
                    for spec in fleet.gateways]
                   + [(self.config.controller_host or "localhost",
                       fleet.router_port)])
        for host, port in targets:
            client = RpcClient(host, port, SERVING_SERVICE, retries=0,
                               ssl=self.config.ssl)
            try:
                text = client.call("GetMetrics", b"", timeout=5.0,
                                   wait_ready=False,
                                   idempotent=True).decode("utf-8")
            except Exception:  # noqa: BLE001 - a dead replica scrapes 0
                continue
            finally:
                client.close()
            try:
                series = _tmetrics.parse_exposition(text)
            except ValueError:
                continue
            for name, cells in series.items():
                if not name.startswith("serving_"):
                    continue
                if name.endswith(("_bucket", "_sum", "_count")):
                    continue  # histogram internals are not family sums
                totals[name] = totals.get(name, 0.0) + sum(cells.values())
        return totals

    def _autoscale_serving(self) -> Optional[str]:
        """One autoscaler evaluation + action (called per monitor poll).
        Returns the action taken ("up"/"down") or None."""
        if self._autoscaler is None or self._shutting_down:
            return None
        fleet = self.config.serving.fleet
        values = self._scrape_serving_families()
        decision = self._autoscaler.observe(values,
                                            replicas=len(fleet.gateways))
        if decision == "up":
            return self._scale_up_serving(values)
        if decision == "down":
            return self._scale_down_serving(values)
        return None

    def _scale_up_serving(self, values: Dict[str, float]) -> str:
        from metisfl_tpu.comm.codec import dumps as _dumps
        fleet = self.config.serving.fleet
        import socket as _socket
        with _socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        idx = len(fleet.gateways)
        name = f"serving_{idx}"
        while any((sp.get("name") or "") == name for sp in fleet.gateways):
            idx += 1
            name = f"serving_{idx}"
        fleet.gateways.append({"name": name, "host": "localhost",
                               "port": port})
        self._launch_gateway(len(fleet.gateways) - 1)
        # hand the replica to the router immediately but OUT of the ring
        # (wait_serving): the router's own probe loop admits it on its
        # first SERVING probe, so the supervision thread never blocks on
        # a cold boot and no keys route to a replica that cannot answer
        client = self._router_admin()
        try:
            client.call("AddReplica", _dumps({"name": name,
                                              "host": "localhost",
                                              "port": port,
                                              "wait_serving": True}),
                        timeout=5.0, wait_ready=False)
        except Exception:  # noqa: BLE001 - probes re-adopt eventually
            logger.warning("router AddReplica(%s) failed", name)
        finally:
            client.close()
        rule = self._autoscaler.up_rule
        _tevents.emit(_tevents.ServingScaledUp, replica=name,
                      replicas=len(fleet.gateways),
                      rule=rule.describe_expr() if rule else "",
                      value=self._autoscaler.last_values.get("up", 0.0))
        _M_FLEET_REPLICAS.set(len(fleet.gateways))
        _M_SCALE_TOTAL.inc(direction="up")
        logger.warning("serving fleet scaled UP to %d replicas (+%s): "
                       "%s", len(fleet.gateways), name, values)
        return "up"

    def _scale_down_serving(self, values: Dict[str, float]) -> str:
        from metisfl_tpu.comm.codec import dumps as _dumps
        from metisfl_tpu.comm.rpc import RpcClient
        from metisfl_tpu.serving.service import SERVING_SERVICE
        fleet = self.config.serving.fleet
        if len(fleet.gateways) <= fleet.min_replicas:
            return "down"  # raced the floor; the autoscaler re-checks
        spec = fleet.gateways[-1]  # newest replica drains first (LIFO)
        name = spec.get("name") or f"serving_{len(fleet.gateways) - 1}"
        client = self._router_admin()
        try:
            # ring removal FIRST: no new requests route to it; its
            # in-flight work (queued micro-batches, multi-second decode
            # sequences) gets a bounded idle wait below before shutdown
            # — the zero-drop drain contract
            client.call("DrainReplica", _dumps({"name": name}),
                        timeout=5.0, wait_ready=False)
        except Exception:  # noqa: BLE001 - a dead router still drains:
            logger.warning("router drain(%s) failed", name)  # probes
        finally:                       # see the replica NOT_SERVING next
            client.close()
        from metisfl_tpu.comm.codec import loads as _loads
        rc = RpcClient(spec.get("host", "localhost"), spec["port"],
                       SERVING_SERVICE, retries=0, ssl=self.config.ssl)
        try:
            # wait (bounded) for the drained replica to go idle: router
            # forwards already dispatched to it — a long Generate
            # included — must finish on it, not be cancelled mid-decode
            deadline = time.time() + 15.0
            while time.time() < deadline:
                try:
                    desc = _loads(rc.call("GetServingStatus", b"",
                                          timeout=5.0, wait_ready=False,
                                          idempotent=True))
                except Exception:  # noqa: BLE001 - already gone
                    break
                # decode sequences are the multi-second in-flight work
                # (predict micro-batches finish in milliseconds and the
                # gateway's own ShutDown drains them regardless)
                decode = desc.get("decode") or {}
                if not any(d.get("queued", 0) or d.get("active", 0)
                           for d in decode.values()):
                    break
                time.sleep(0.25)
            rc.call("ShutDown", b"", timeout=5.0, wait_ready=False)
        except Exception:  # noqa: BLE001 - already gone
            pass
        finally:
            rc.close()
        # router-side cleanup LAST: RemoveReplica closes the router's
        # channel to the replica, which must not cancel a forward the
        # drain window above was letting finish
        client = self._router_admin()
        try:
            client.call("RemoveReplica", _dumps({"name": name}),
                        timeout=5.0, wait_ready=False)
        except Exception:  # noqa: BLE001
            pass
        finally:
            client.close()
        fleet.gateways.remove(spec)
        proc = next((p for p in self._procs if p.name == name), None)
        if proc is not None:
            try:
                proc.process.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                _terminate_process(proc.process)
            self._procs = [p for p in self._procs if p.name != name]
        # a later scale-up may reuse the name: stale backoff windows
        # must not delay the fresh replica's supervision
        self._serving_restarts.pop(name, None)
        self._serving_restart_after.pop(name, None)
        rule = self._autoscaler.down_rule
        _tevents.emit(_tevents.ServingScaledDown, replica=name,
                      replicas=len(fleet.gateways),
                      rule=rule.describe_expr() if rule else "",
                      value=self._autoscaler.last_values.get("down", 0.0))
        _M_FLEET_REPLICAS.set(len(fleet.gateways))
        _M_SCALE_TOTAL.inc(direction="down")
        logger.warning("serving fleet scaled DOWN to %d replicas (-%s)",
                       len(fleet.gateways), name)
        return "down"

    def serving_client(self):
        """A :class:`metisfl_tpu.serving.ServingClient` dialing this
        session's gateway (serving must be enabled)."""
        from metisfl_tpu.serving.service import ServingClient
        if not self.config.serving.enabled:
            raise RuntimeError("serving is not enabled in this federation")
        return ServingClient(self.config.controller_host or "localhost",
                             self.config.serving.port, ssl=self.config.ssl,
                             comm=self.config.comm)

    def launch_learner(self, idx: int) -> _Proc:
        """(Re)launch learner ``idx`` on its configured endpoint. Ports come
        from the endpoint config or are ephemeral (the learner reports its
        bound port on join); credentials persist in the workdir so a
        relaunched learner rejoins as itself."""
        recipe_path = self._recipe_path(idx)
        ep = self._endpoint(idx)
        launcher = self._launcher_for(ep.hostname)
        name = f"learner_{idx}"
        argv = [getattr(launcher, "python", sys.executable),
                "-m", "metisfl_tpu.learner",
                "--controller-host", self.config.controller_host or "localhost",
                "--controller-port", str(self.config.controller_port),
                "--advertise-host", ep.hostname or "localhost",
                *(["--standby-host",
                   self.config.controller.standby.host or "localhost",
                   "--standby-port",
                   str(self.config.controller.standby.port)]
                  if (self.config.controller.standby.enabled
                      and not self._standby_promoted) else []),
                "--port", str(ep.port),
                "--recipe", recipe_path,
                "--rpc-deadline-s", str(self.config.comm.default_deadline_s),
                "--credentials-dir",
                os.path.join(self.workdir, f"{name}_creds")]
        if self.config.ssl.enabled:
            argv += ["--ssl-cert", self.config.ssl.cert_path,
                     "--ssl-key", self.config.ssl.key_path]
        if self.config.secure.enabled:
            argv += ["--secure-config",
                     os.path.join(self.workdir, f"learner_{idx}_secure.bin")]
        if not self.config.telemetry.enabled:
            argv += ["--telemetry-off"]
        else:
            if self.config.telemetry.dir:
                argv += ["--telemetry-dir", self.config.telemetry.dir]
            if not self.config.telemetry.events.enabled:
                argv += ["--events-off"]
            if self.config.telemetry.postmortem_dir:
                argv += ["--postmortem-dir",
                         self.config.telemetry.postmortem_dir]
        if isinstance(launcher, SSHLauncher):
            # remote host: copy the recipe + TLS/secure material to the same
            # absolute paths (metisfl_tpu itself must be installed remotely)
            launcher.ship([recipe_path] + self._ssl_files()
                          + self._secure_files(idx))
        env = {**self._accelerator_env(idx), **self.learner_env}
        if idx not in self._chaos_armed_learners:
            # original incarnation only: a relaunch (crash-rejoin) runs
            # clean, or a kill rule would re-fire on every restart and
            # the recovery under test could never converge
            self._chaos_armed_learners.add(idx)
            env.update(self._chaos_env("learner", idx))
        world = max(1, int(getattr(ep, "world_size", 1)))
        if world > 1:
            # multi-host learner: one process per rank (rank 0 = the
            # learner, others replay via parallel/replicated.py). All ranks
            # need the recipe + the same jax.distributed world config.
            port = ep.coordinator_port
            is_local = ep.hostname in self._LOCAL_HOSTS
            if not port:
                if not is_local:
                    # a port probed on the driver machine says nothing about
                    # the remote host where rank 0's coordinator will bind
                    raise ValueError(
                        f"learner {idx}: world_size > 1 on remote host "
                        f"{ep.hostname!r} requires an explicit "
                        "coordinator_port")
                import socket as _socket
                with _socket.socket() as s:
                    s.bind(("127.0.0.1", 0))
                    port = s.getsockname()[1]
                ep.coordinator_port = port
            coord_host = "127.0.0.1" if is_local else ep.hostname
            env = {**env,
                   "METISFL_JAX_COORDINATOR": f"{coord_host}:{port}",
                   "METISFL_JAX_NUM_PROCESSES": str(world)}
            for rank in range(1, world):
                rname = f"{name}_rank{rank}"
                for old in [p for p in self._procs if p.name == rname]:
                    # a relaunch must not orphan a live old follower (it
                    # would keep holding the slice's devices while parked
                    # on a dead coordinator's collective)
                    _terminate_process(old.process)
                self._procs = [p for p in self._procs if p.name != rname]
                self._procs.append(launcher.launch(
                    rname, argv,
                    env={**env, "METISFL_JAX_PROCESS_ID": str(rank)}))
            env["METISFL_JAX_PROCESS_ID"] = "0"
        # a relaunch replaces the tracked (dead) process of the same name
        self._procs = [p for p in self._procs if p.name != name]
        proc = launcher.launch(name, argv, env=env)
        self._procs.append(proc)
        return proc

    def _wait_healthy(self, retries: int, sleep_s: float) -> None:
        last_exc: Optional[Exception] = None
        for _ in range(retries):
            try:
                status = self._client.health(timeout=5.0)
                if status.get("status") == "SERVING":
                    return
            except Exception as exc:  # noqa: BLE001
                last_exc = exc
            self._check_procs_alive()
            time.sleep(sleep_s)
        raise RuntimeError(f"controller never became healthy: {last_exc}")

    def _check_procs_alive(self, skip: Sequence[str] = ()) -> None:
        skip = tuple(skip)
        if self.config.controller.standby.enabled:
            # hot-standby configured: a controller death is a FAILOVER
            # event (_supervise_controller waits for the standby's
            # promotion and hands the endpoint over), never an instant
            # abort — and the standby itself is supervised. With no
            # standby the fail-fast below stands: a dead controller with
            # supervision off must kill the run immediately.
            skip += ("controller", "standby")
        for proc in self._procs:
            if proc.name in skip:
                continue
            code = proc.process.poll()
            if code is not None and code != 0:
                with open(proc.log_path) as f:
                    tail = f.read()[-2000:]
                raise RuntimeError(
                    f"{proc.name} exited with code {code}; log tail:\n{tail}")

    # ------------------------------------------------------------------ #
    # monitoring (reference monitor_federation :423-480)
    # ------------------------------------------------------------------ #

    def monitor_federation(self, poll_every_s: float = 2.0,
                           eval_drain_timeout_s: float = 90.0) -> dict:
        term = self.config.termination
        poll_failures = 0
        while True:
            time.sleep(poll_every_s)
            # crash failover first: a dead controller is either relaunched
            # (supervision on, budget left) or reported fatally by the
            # liveness check below. Under supervision the liveness check
            # skips the controller entirely — a death in the gap between
            # the two calls belongs to the NEXT supervision cycle, not to
            # an instant abort that bypasses the restart budget.
            self._supervise_controller()
            self._supervise_standby()
            self._supervise_gateway()
            self._supervise_slices()
            self._autoscale_serving()
            skip = (("controller",)
                    if self.config.failover.supervise_controller else ())
            if self.config.serving.enabled:
                # every serving-plane process (gateway, fleet replicas,
                # router) is always supervised (stateless relaunch) —
                # and fleet replicas are chaos-killable BY DESIGN
                skip = tuple(skip) + tuple(self._serving_proc_names())
            tree = self.config.aggregation.tree
            if tree.enabled and tree.distributed:
                # slice aggregators are chaos-killable BY DESIGN: a death
                # re-homes mid-round and the supervisor relaunches — it
                # must never fail the run
                skip = tuple(skip) + tuple(
                    f"slice_{i}" for i in range(len(tree.slices)))
            if self.config.chaos.enabled:
                # chaos-killed processes are expected casualties: a kill
                # rule names its victim up front, and the resilience plane
                # under test (dropout settlement, re-homing, failover)
                # must absorb the death — the liveness check aborting on
                # it would gate the wrong thing
                skip = tuple(skip) + tuple(
                    str(r["process"]) for r in self.config.chaos.rules
                    if r.get("fault") == "kill" and r.get("process"))
            self._check_procs_alive(skip=skip)
            # poll the tail-bounded lineage RPCs — a long-running federation
            # must not ship its full history every 2 s (the unbounded
            # GetStatistics dump is fetched once, at termination)
            try:
                # fail-fast polls (short deadline, no wait-for-ready): a
                # dead controller must surface as an error promptly so
                # the next iteration's supervision can relaunch it — a
                # blocking wait-for-ready would park this loop instead
                progress = self._client.get_runtime_metadata(
                    tail=1, timeout=15.0, wait_ready=False)
                try:
                    self._known_endpoints = self._client.list_learners(
                        timeout=15.0, wait_ready=False)
                except Exception:  # noqa: BLE001 - keep the stale snapshot
                    pass
                poll_failures = 0
            except Exception as exc:  # noqa: BLE001 - bounded retry
                # the controller can die between the supervision check and
                # this poll; give the next iteration's supervision a chance
                # instead of aborting the run on one lost poll
                poll_failures += 1
                if poll_failures > 5:
                    raise
                logger.warning("monitor poll failed (%s); retrying", exc)
                continue

            if progress["global_iteration"] >= term.federation_rounds > 0:
                logger.info("termination: reached %d rounds",
                            term.federation_rounds)
                break

            if term.execution_cutoff_mins > 0 and (
                    time.time() - self._started_at
                    > term.execution_cutoff_mins * 60):
                logger.info("termination: wall-clock cutoff")
                break

            if term.metric_cutoff_score > 0:
                evals = self._client.get_evaluation_lineage(tail=5)
                score = self._latest_mean_metric(
                    {"community_evaluations": evals}, term.metric_name)
                if score is not None and score >= term.metric_cutoff_score:
                    logger.info("termination: %s=%.4f ≥ cutoff",
                                term.metric_name, score)
                    break
        self._drain_evaluations(eval_drain_timeout_s)
        return self.get_statistics()

    def _drain_evaluations(self, timeout_s: float) -> None:
        """Give in-flight evaluation tasks a bounded grace period before
        shutdown: rounds terminate on training completion, but the matching
        eval round trip (which may still be compiling on the learner) lags —
        without the drain the final statistics ship empty evaluations."""
        if timeout_s <= 0:
            return
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            try:
                evals = self._client.get_evaluation_lineage(tail=2)
            except Exception:  # noqa: BLE001 - controller already gone
                return
            if not evals or evals[-1].get("evaluations"):
                return
            time.sleep(1.0)
        logger.warning("evaluations still pending after %.0fs drain window",
                       timeout_s)

    @staticmethod
    def _latest_mean_metric(stats: dict, metric: str) -> Optional[float]:
        for entry in reversed(stats.get("community_evaluations", [])):
            values = [
                ds_metrics[metric]
                for learner_evals in entry.get("evaluations", {}).values()
                for ds_name, ds_metrics in learner_evals.items()
                if ds_name == "test" and metric in ds_metrics
            ]
            if values:
                return float(np.mean(values))
        return None

    # ------------------------------------------------------------------ #
    # statistics / shutdown
    # ------------------------------------------------------------------ #

    def get_statistics(self) -> dict:
        return self._client.get_statistics()

    def process_exit_codes(self) -> Dict[str, Optional[int]]:
        """name → exit code (None while running) for every launched
        federation process, incl. multi-host follower ranks."""
        return {p.name: p.process.poll() for p in self._procs}

    def run_inference(self, learner_index: int = 0, inputs=None,
                      dataset: str = "test", batch_size: int = 256,
                      max_examples: int = 0, timeout_s: float = 120.0,
                      generate_tokens: int = 0, temperature: float = 0.0,
                      top_k: int = 0, top_p: float = 0.0,
                      eos_id: Optional[int] = None):
        """Run the community model's inference on one learner and return its
        predictions as a numpy array (the reference driver's counterpart to
        the learner's third task type, reference learner.py:311-330).

        ``inputs`` (optional numpy array) ships explicit examples; otherwise
        the learner infers over its local ``dataset`` split.
        ``generate_tokens > 0`` makes it a generation task on a causal-LM
        learner: ``inputs`` are (B, L) token prompts and the returned array
        holds the sampled/greedy continuations (models/generate.py).
        """
        import uuid as _uuid

        import numpy as np

        from metisfl_tpu.comm.messages import InferResult, InferTask
        from metisfl_tpu.comm.rpc import RpcClient
        from metisfl_tpu.controller.service import LEARNER_SERVICE
        from metisfl_tpu.tensor.pytree import ModelBlob

        endpoints = self._client.list_learners()
        if not endpoints:
            raise RuntimeError("no learners registered")
        ep = endpoints[learner_index % len(endpoints)]
        model = self._client.get_community_model()
        task = InferTask(
            task_id=_uuid.uuid4().hex,
            learner_id=ep.get("learner_id", ""),
            model=model,
            batch_size=batch_size,
            dataset=dataset,
            inputs=(ModelBlob(tensors=[("x", np.asarray(inputs))]).to_bytes()
                    if inputs is not None else b""),
            max_examples=max_examples,
            generate_tokens=generate_tokens,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            eos_id=-1 if eos_id is None else int(eos_id),
            local_tensor_regex=self.config.train.local_tensor_regex,
            ship_tensor_regex=self.config.train.ship_tensor_regex,
        )
        client = RpcClient(ep["hostname"], ep["port"], LEARNER_SERVICE,
                           ssl=self.config.ssl)
        try:
            result = InferResult.from_wire(
                client.call("RunInference", task.to_wire(), timeout=timeout_s))
        finally:
            client.close()
        return dict(ModelBlob.from_bytes(result.predictions).tensors)[
            "predictions"]

    def save_experiment(self, path: Optional[str] = None) -> str:
        path = path or os.path.join(self.workdir, "experiment.json")
        with open(path, "w") as f:
            json.dump(self.get_statistics(), f, indent=2, default=str)
        return path

    def collect_traces(self, dest: Optional[str] = None) -> Optional[str]:
        """Assemble the experiment's ``traces.jsonl``. With the fleet
        fabric on, spans were streamed there live (skew-corrected,
        straight off each peer's ``CollectTelemetry`` pull — remote
        learners included) all run long; this final pass rebuilds the
        file so every LOCAL process's sink file — which is complete,
        unlike a cursor stream that can miss ring-evicted or
        post-final-poll spans — replaces that process's streamed
        records, while remote peers (no local file) keep their streamed,
        skew-corrected records. It logs exactly which peers were
        file-merged vs RPC-streamed vs unreachable, plus any reported
        ring losses — no silent coverage caps. With the fabric off it
        is the old shutdown-time file merge of ``<telemetry.dir>/
        *.jsonl``. Returns the merged path, or None when there is
        nothing to collect."""
        if not self.config.telemetry.enabled:
            return None
        import glob as _glob
        import json as _json
        tel_dir = self.config.telemetry.dir
        files = (sorted(_glob.glob(os.path.join(tel_dir, "*.jsonl")))
                 if tel_dir and os.path.isdir(tel_dir) else [])
        dest = dest or os.path.join(self.workdir, "traces.jsonl")
        if self._fleet is None:
            if not files:
                return None
            with open(dest, "w") as out:
                for name in files:
                    try:
                        with open(name) as f:
                            out.write(f.read())
                    except OSError:  # noqa: PERF203 - torn file skippable
                        logger.warning("could not collect trace file %s",
                                       name)
            return dest
        local_bases = {os.path.basename(name) for name in files}
        rpc_streamed: List[str] = []
        file_covered: List[str] = []
        disabled: List[str] = []
        unreachable: List[str] = []
        lost_total = 0
        for peer in self._fleet.peers():
            lost_total += peer.spans_lost
            sink_base = (f"{peer.trace_service}-{peer.pid}.jsonl"
                         if peer.trace_service and peer.pid else "")
            if peer.disabled:
                disabled.append(peer.name)
            elif peer.last_ok_ts and not peer.stale:
                if sink_base and sink_base in local_bases:
                    file_covered.append(peer.name)
                else:
                    rpc_streamed.append(peer.name)
            else:
                unreachable.append(peer.name)
        # keep streamed records only for processes WITHOUT a local sink
        # file (remote peers): local files are the complete record and
        # win over the lossy cursor stream
        kept_streamed: List[str] = []
        try:
            with open(dest) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = _json.loads(line)
                    except _json.JSONDecodeError:
                        continue  # torn live-stream tail line
                    base = (f"{rec.get('service')}-{rec.get('pid')}.jsonl"
                            if rec.get("service") and rec.get("pid")
                            else "")
                    if not base or base not in local_bases:
                        kept_streamed.append(line)
        except OSError:
            pass
        tmp = dest + ".tmp"
        with open(tmp, "w") as out:
            for line in kept_streamed:
                out.write(line + "\n")
            for name in files:
                try:
                    with open(name) as f:
                        out.write(f.read())
                except OSError:  # noqa: PERF203 - torn file skippable
                    logger.warning("could not collect trace file %s",
                                   name)
        os.replace(tmp, dest)
        # no silent coverage caps: every peer's collection route is
        # named (docs/OBSERVABILITY.md "Fleet fabric")
        logger.info(
            "trace collection: file-merged (local, complete) %s; "
            "RPC-pulled (remote stream) %s; fabric-disabled %s; "
            "unreachable %s%s",
            sorted(file_covered) or "[]", sorted(rpc_streamed) or "[]",
            sorted(disabled) or "[]", sorted(unreachable) or "[]",
            f"; {lost_total} span(s) ring-evicted between pulls "
            "(local files keep them)" if lost_total else "")
        return dest

    def collect_postmortems(self) -> List[str]:
        """Post-mortem bundle paths collected into the experiment dir.
        Local processes already write into
        ``telemetry.postmortem_dir`` (defaulted to
        ``<workdir>/postmortem``); a custom dir outside the workdir is
        copied in so the experiment directory stays self-contained."""
        src = self.config.telemetry.postmortem_dir
        if not (self.config.telemetry.enabled and src
                and os.path.isdir(src)):
            return []
        import glob as _glob
        import shutil as _shutil
        dest = os.path.join(self.workdir, "postmortem")
        paths = sorted(_glob.glob(os.path.join(src, "*.json")))
        if os.path.abspath(src) != os.path.abspath(dest) and paths:
            os.makedirs(dest, exist_ok=True)
            collected = []
            for p in paths:
                target = os.path.join(dest, os.path.basename(p))
                try:
                    _shutil.copyfile(p, target)
                    collected.append(target)
                except OSError:
                    logger.warning("could not collect bundle %s", p)
            paths = collected
        if paths:
            logger.warning(
                "%d post-mortem bundle(s) in %s — render with "
                "python -m metisfl_tpu.telemetry --postmortem %s",
                len(paths), dest, dest)
        return paths

    def _shutdown_learners(self) -> None:
        """ShutDown RPC to every learner, dialing the endpoints learners
        actually registered on join, not assumed port arithmetic. An
        in-flight train task cancels between steps and the learner exits
        on its own — the clean exit that releases its chip."""
        from metisfl_tpu.comm.rpc import RpcClient
        from metisfl_tpu.controller.service import LEARNER_SERVICE

        endpoints: List[dict] = []
        try:
            endpoints = self._client.list_learners() if self._client else []
        except Exception:  # noqa: BLE001 - controller may already be gone
            # fall back to the last snapshot (+ any statically configured
            # endpoints) so remote learners still get a ShutDown even when
            # the controller died first
            endpoints = list(self._known_endpoints)
            known = {(e["hostname"], e["port"]) for e in endpoints}
            for ep in self.config.learners:
                if ep.port and (ep.hostname, ep.port) not in known:
                    endpoints.append({"hostname": ep.hostname,
                                      "port": ep.port})
        for ep in endpoints:
            try:
                client = RpcClient(ep["hostname"], ep["port"], LEARNER_SERVICE,
                                   retries=0, ssl=self.config.ssl)
                client.call("ShutDown", b"", timeout=5.0, wait_ready=False)
                client.close()
            except Exception:  # noqa: BLE001 - learner may already be gone
                pass

    def stop_learners(self, timeout_s: float = 60.0) -> None:
        """Shut every learner down and wait until its process has exited
        (the controller, and so the registry, stays up). Raises unless
        every learner exits cleanly within ``timeout_s``: a killed chip
        holder can leave the chip locked for the next process."""
        self._shutdown_learners()
        deadline = time.time() + timeout_s
        for proc in self._procs:
            if not proc.name.startswith("learner_"):
                continue
            try:
                proc.process.wait(timeout=max(0.5, deadline - time.time()))
            except subprocess.TimeoutExpired:
                _terminate_process(proc.process, grace_s=30.0)
            if proc.process.returncode != 0:
                raise RuntimeError(
                    f"{proc.name} exited with code "
                    f"{proc.process.returncode} while stopping")

    def shutdown_federation(self, timeout_s: Optional[float] = None) -> None:
        # Default drain budget: 15 s, or 150 s when any learner is a
        # multi-host world — its leader can only release the followers
        # after an in-flight replicated task drains (the release broadcast
        # serializes behind the task's lock, and a cold jit compile inside
        # that task can take tens of seconds), and killing followers
        # earlier aborts them mid-collective. An explicit timeout_s is
        # honored as given.
        self._shutting_down = True  # supervision must not resurrect it now
        if self._fleet is not None:
            # final tail pull while the fleet is still up, then stop the
            # poll loop — shutdown must not race live collection
            try:
                self._fleet.stop(final_poll=True)
            except Exception:  # noqa: BLE001 - collection never blocks
                logger.exception("fleet collector stop failed")
            # persist the fleet's continuous profiles (telemetry/prof.py)
            # next to traces.jsonl: per-peer folded-stack tables + the
            # peer-prefixed merge, the artifact `python -m
            # metisfl_tpu.perf --flame <workdir>/prof-fleet.json` renders
            try:
                if self._fleet.dump_prof(
                        os.path.join(self.workdir, "prof-fleet.json")):
                    logger.info("fleet profile written: %s",
                                os.path.join(self.workdir,
                                             "prof-fleet.json"))
            except Exception:  # noqa: BLE001 - profiling never blocks
                logger.exception("fleet profile dump failed")
            # and the accelerator-runtime sections (telemetry/runtime.py):
            # per-peer compile tables + the fleet merge, the artifact
            # `python -m metisfl_tpu.perf --compile-report
            # <workdir>/runtime-fleet.json` renders
            try:
                if self._fleet.dump_runtime(
                        os.path.join(self.workdir, "runtime-fleet.json")):
                    logger.info("fleet runtime report written: %s",
                                os.path.join(self.workdir,
                                             "runtime-fleet.json"))
            except Exception:  # noqa: BLE001 - telemetry never blocks
                logger.exception("fleet runtime dump failed")
        if timeout_s is None:
            multihost = any(int(getattr(ep, "world_size", 1)) > 1
                            for ep in self.config.learners)
            timeout_s = 150.0 if multihost else 15.0
        # learners first (reference _shutdown :344-364), then the controller
        from metisfl_tpu.comm.rpc import RpcClient

        self._shutdown_learners()
        tree = self.config.aggregation.tree
        if tree.enabled and tree.distributed:
            # slice aggregators get the same fail-fast ShutDown as
            # learners (a chaos-killed one is simply already gone)
            from metisfl_tpu.aggregation.slice import SLICE_SERVICE
            for spec in tree.slices:
                if not spec.get("port"):
                    continue
                try:
                    sc = RpcClient(spec.get("host", "localhost"),
                                   spec["port"], SLICE_SERVICE,
                                   retries=0, ssl=self.config.ssl)
                    sc.call("ShutDown", b"", timeout=5.0, wait_ready=False)
                    sc.close()
                except Exception:  # noqa: BLE001 - already gone
                    pass
        if self.config.serving.enabled:
            # fail-fast like the learner loop above: a dead gateway must
            # not park shutdown in the transport's default deadline. In
            # a fleet: replicas first, then the router (serving.port IS
            # the router there, so the single-gateway branch covers it)
            from metisfl_tpu.serving.service import SERVING_SERVICE
            targets: List[tuple] = []
            fleet = self.config.serving.fleet
            if fleet.enabled:
                targets = [(spec.get("host", "localhost"), spec["port"])
                           for spec in fleet.gateways]
            if self.config.serving.port:
                targets.append((self.config.controller_host or
                                "localhost", self.config.serving.port))
            for host, port in targets:
                try:
                    gw = RpcClient(host, port, SERVING_SERVICE,
                                   retries=0, ssl=self.config.ssl)
                    gw.call("ShutDown", b"", timeout=5.0,
                            wait_ready=False)
                    gw.close()
                except Exception:  # noqa: BLE001 - already gone
                    pass
        try:
            if self._client is not None:
                self._client.shutdown_controller()
        except Exception:  # noqa: BLE001
            logger.warning("controller shutdown RPC failed; killing processes")
        for proc in self._procs:
            if proc.name == "standby" and proc.process.poll() is None:
                # the warm standby has no ShutDown RPC surface — SIGTERM
                # is its clean exit (and must come BEFORE the wait loop,
                # or the primary's death above would read as a WAL stall
                # and the standby would promote into the shutdown)
                _terminate_process(proc.process)
        deadline = time.time() + timeout_s
        for proc in self._procs:
            remaining = max(0.5, deadline - time.time())
            try:
                proc.process.wait(timeout=remaining)
            except subprocess.TimeoutExpired:
                _terminate_process(proc.process)
        try:
            self.collect_traces()
        except Exception:  # noqa: BLE001 - collection must not fail shutdown
            logger.exception("trace collection failed")
        try:
            self.collect_postmortems()
        except Exception:  # noqa: BLE001 - collection must not fail shutdown
            logger.exception("post-mortem collection failed")

    def run(self) -> dict:
        """initialize → monitor → save stats → shutdown, one call."""
        self.initialize_federation()
        try:
            stats = self.monitor_federation()
            self.save_experiment()
            return stats
        finally:
            self.shutdown_federation()
