"""Process entry and device ownership (metisfl_tpu/platform.py,
DriverSession's per-role environments, chip_smoke.py's no-chip contract).

None of this needs a chip: what is pinned here is that the program can
only reach the CPU when it was *told* to, and that the compile cache has
one home."""

import ast
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CACHE_PROBE = (
    "import os, jax; from metisfl_tpu.platform import enter_process; "
    "d = enter_process(); "
    "print(d); print(jax.config.jax_compilation_cache_dir); "
    "print(os.getpid())")


def _probe(cwd, env_extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update({"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu", **env_extra})
    return subprocess.Popen([sys.executable, "-c", _CACHE_PROBE], cwd=cwd,
                            env=env, stdout=subprocess.PIPE, text=True)


def test_compile_cache_has_one_home(tmp_path):
    """Unset, the cache sits at ONE path inside the checkout whatever the
    working directory or pid; set, JAX_COMPILATION_CACHE_DIR is left alone
    and no other directory is set in code."""
    elsewhere = tmp_path / "elsewhere"
    elsewhere.mkdir()
    placed = str(tmp_path / "placed_cache")
    procs = [_probe(REPO, {}), _probe(str(elsewhere), {}),
             _probe(str(elsewhere), {"JAX_COMPILATION_CACHE_DIR": placed})]
    outs = []
    for proc in procs:
        out, _ = proc.communicate(timeout=120)
        assert proc.returncode == 0
        outs.append(out.split())
    (d1, cfg1, pid1), (d2, cfg2, pid2), (d3, cfg3, _) = outs
    assert pid1 != pid2
    assert d1 == d2 == cfg1 == cfg2 == os.path.join(REPO, ".jax_cache")
    # placed from outside: jax reads the variable itself
    assert d3 == cfg3 == placed
    rc = subprocess.run(["git", "check-ignore", "-q", ".jax_cache/x"],
                        cwd=REPO).returncode
    assert rc == 0, ".jax_cache/ is not gitignored"


class _Recorder:
    """Launcher double: records each process's env, starts nothing."""

    python = sys.executable

    def __init__(self):
        self.envs = {}

    def launch(self, name, argv, env):
        from metisfl_tpu.driver.session import _Proc

        self.envs[name] = dict(env)

        class _Done:
            returncode = 0

            def poll(self):
                return 0

        return _Proc(name, _Done(), "")


def _session(tmp_path, **kwargs):
    from metisfl_tpu.config import FederationConfig
    from metisfl_tpu.driver.session import DriverSession

    recorder = _Recorder()
    config = FederationConfig()
    config.controller.standby.port = 1
    session = DriverSession(
        config, {"w": np.zeros(2, np.float32)}, [lambda: None] * 2,
        workdir=str(tmp_path), launcher_factory=lambda host: recorder,
        **kwargs)
    session._config_path = str(tmp_path / "config.bin")
    session._launch_controller()
    session._launch_standby()
    session._launch_router()
    session._launch_slice(0)
    session.launch_learner(0)
    session.launch_learner(1)
    session._launch_gateway()
    return recorder.envs


def test_session_env_by_role(tmp_path, monkeypatch):
    """Host roles are pinned to the CPU; learners and the gateway are
    never defaulted to it, and get the platform the operator named."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/cache")
    envs = _session(tmp_path / "a")
    for host_role in ("controller", "standby", "router", "slice_0"):
        assert envs[host_role]["JAX_PLATFORMS"] == "cpu", host_role
    for chip_role in ("learner_0", "learner_1", "serving"):
        assert "JAX_PLATFORMS" not in envs[chip_role], chip_role
        assert "TPU_VISIBLE_CHIPS" not in envs[chip_role]
    # an externally placed compile cache reaches every child, including
    # SSH children that receive only this dict
    assert all(env["JAX_COMPILATION_CACHE_DIR"] == "/somewhere/cache"
               for env in envs.values())

    # the parent's own platform does not decide for accelerator roles
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    envs = _session(tmp_path / "b", accelerator="tpu")
    for chip_role in ("learner_0", "learner_1", "serving"):
        assert envs[chip_role]["JAX_PLATFORMS"] == "tpu", chip_role
    assert envs["controller"]["JAX_PLATFORMS"] == "cpu"


def test_session_splits_a_host_one_chip_per_process(tmp_path):
    envs = _session(tmp_path, accelerator="tpu", host_chips=4)
    chips = [envs[name]["TPU_VISIBLE_CHIPS"]
             for name in ("learner_0", "learner_1", "serving")]
    assert chips == ["0", "1", "2"]
    for name in ("learner_0", "learner_1", "serving"):
        assert envs[name]["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert envs[name]["TPU_PROCESS_BOUNDS"] == "1,1,1"
    # host roles own no chip
    for host_role in ("controller", "standby", "router", "slice_0"):
        assert "TPU_VISIBLE_CHIPS" not in envs[host_role]


def test_chip_smoke_fails_without_a_chip_and_parent_stays_off_jax():
    """`python chip_smoke.py` in a sandbox with no accelerator — even one
    whose environment says JAX_PLATFORMS=cpu — exits non-zero before it
    builds a model or launches a federation, and prints no result. The
    parent's import path initializes no JAX backend."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    smoke = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    imports = subprocess.Popen(
        [sys.executable, "-c",
         "import sys, chip_smoke, jax._src.xla_bridge as xb; "
         "sys.exit(int(xb.backends_are_initialized()))"],
        cwd=REPO, env=env)
    out, err = smoke.communicate(timeout=120)
    assert smoke.returncode not in (0, None)
    assert "device phase exited" in err
    assert "initial model built" not in out
    lines = [ln for ln in out.splitlines() if ln.startswith("{")]
    assert not lines, f"a result was printed without a chip: {lines}"
    assert imports.wait(timeout=120) == 0


def test_chip_smoke_source_keeps_jax_out_of_the_parent():
    """Source pins: module level imports no jax; every chip process is
    launched with the platform said outright; the parent asserts it is
    off the backend before the first launch."""
    with open(os.path.join(REPO, "chip_smoke.py")) as fh:
        source = fh.read()
    tree = ast.parse(source)
    top_level = set()
    for node in tree.body:
        if isinstance(node, ast.Import):
            top_level |= {alias.name.split(".")[0] for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            top_level.add((node.module or "").split(".")[0])
    assert "jax" not in top_level and "flax" not in top_level
    assert top_level <= {"__future__", "argparse", "json", "os", "shutil",
                         "socket",
                         "subprocess", "sys", "tempfile", "threading",
                         "time", "numpy", "metisfl_tpu"}
    assert source.index("backends_are_initialized()") < source.index(
        "session.initialize_federation(")
    assert '"JAX_PLATFORMS": platform' in source
    assert "accelerator=platform" in source
    # the result line carries the device as JAX reported it
    assert '"device": {"platform": report["platform"]' in source


def test_wait_for_shutdown_returns_after_teardown_finished():
    """A server's main thread exits on wait_for_shutdown; it must not
    return while a ShutDown-RPC thread is still tearing the server down
    (interpreter finalization under a live gRPC server hangs the process —
    and the chip it holds)."""
    import threading
    import time

    from metisfl_tpu.comm.rpc import StopOnce

    class Server(StopOnce):
        torn_down = 0

        def _teardown(self, leave=True):
            time.sleep(0.3)
            self.torn_down += 1

    server = Server()
    stopper = threading.Thread(target=server.stop, daemon=True)
    stopper.start()
    assert not server.wait_for_shutdown(timeout=0.05)  # still tearing down
    server.stop(leave=False)  # a second stop is a no-op, never a re-entry
    assert server.wait_for_shutdown(timeout=5.0)
    stopper.join(timeout=5.0)
    assert not stopper.is_alive()
    assert server.torn_down == 1
