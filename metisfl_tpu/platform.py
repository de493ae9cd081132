"""Process entry and device ownership.

One chip belongs to one process at a time: a process that has initialized
a JAX backend on the TPU holds it until it exits, and a second process
reaching for the same chip fails or hangs. So every federation process is
either a *host* role (controller, standby, router, slice aggregator —
pinned to ``JAX_PLATFORMS=cpu`` by the launcher) or an *accelerator* role
(learner, serving gateway) that owns the chip(s) it was handed. This
module holds what every entry point needs for that:

- :func:`enter_process` — the first call of every ``__main__``: places the
  persistent compile cache;
- :func:`chip_env` — the environment that confines one process to one chip
  of a multi-chip host;
- :func:`announce_devices` — what the process actually got, printed once
  at start so a launcher (or ``chip_smoke.py``) can check it;
- :func:`maybe_init_distributed` — multi-host learner worlds.
"""

from __future__ import annotations

import json
import logging
import os
from typing import Any, Dict

logger = logging.getLogger("metisfl_tpu.platform")

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# stdout marker of announce_devices lines (launchers grep child logs for it)
DEVICE_MARKER = "METISFL_TPU_DEVICES"


def package_root() -> str:
    """The directory that holds the ``metisfl_tpu`` package (the checkout)."""
    import metisfl_tpu

    return os.path.dirname(os.path.dirname(
        os.path.abspath(metisfl_tpu.__file__)))


def package_pythonpath() -> str:
    """PYTHONPATH that makes the package importable in a child process
    regardless of its cwd."""
    return os.pathsep.join(
        p for p in (package_root(), os.environ.get("PYTHONPATH", "")) if p)


def checkout_cache_dir() -> str:
    """The compile cache's home when the environment names none: one fixed
    path inside the checkout — a cache under a temp workdir, a pid or a
    timestamp is a new, empty cache on every run."""
    return os.path.join(package_root(), ".jax_cache")


def enter_process() -> str:
    """Call first in every entry point, before any JAX computation.

    Places JAX's persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` says when it is set (JAX reads the
    variable itself; no other directory is set in code), otherwise at
    :func:`checkout_cache_dir`. Returns the directory in effect. Does not
    initialize a backend."""
    import jax

    cache_dir = os.environ.get(CACHE_DIR_ENV, "")
    if not cache_dir:
        cache_dir = checkout_cache_dir()
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def chip_env(chip: int) -> Dict[str, str]:
    """Environment confining one process to chip ``chip`` of this host, so
    several accelerator processes (learners, a gateway) each own one chip
    of a multi-chip host. Without it every process sees — and reaches
    for — all of the host's chips. libtpu renumbers what stays visible:
    inside the process the chip is device 0, so ``TPU_VISIBLE_CHIPS`` is
    the only record of which physical chip it is."""
    return {
        "TPU_VISIBLE_CHIPS": str(int(chip)),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def announce_devices(role: str) -> Dict[str, Any]:
    """Print (and return) the one-line report of what an accelerator
    process owns, as JAX reports it. Initializes the backend."""
    import importlib.metadata as _md

    import jax
    import jaxlib

    devices = jax.local_devices()
    try:
        libtpu = _md.version("libtpu")
    except _md.PackageNotFoundError:
        libtpu = ""
    report = {
        "role": role,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_ids": [int(d.id) for d in devices],
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS", ""),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "pid": os.getpid(),
    }
    print(f"{DEVICE_MARKER} {json.dumps(report, sort_keys=True)}",
          flush=True)
    return report


def maybe_init_distributed() -> bool:
    """Join a multi-host JAX runtime when the environment asks for it.

    A learner that owns a multi-host TPU slice (SURVEY.md §7: one learner
    per host, in-learner sharding across its slice) must call
    ``jax.distributed.initialize`` before any backend use so every host
    sees the global device set. Env-driven so launchers (SSH or k8s) wire
    it without new CLI surface:

    - ``METISFL_JAX_COORDINATOR``   — ``host:port`` of process 0
    - ``METISFL_JAX_NUM_PROCESSES`` — world size
    - ``METISFL_JAX_PROCESS_ID``    — this process's rank

    Returns True when initialization ran. No-op (False) when unset.
    """
    coordinator = os.environ.get("METISFL_JAX_COORDINATOR")
    if not coordinator:
        return False
    try:
        num = int(os.environ["METISFL_JAX_NUM_PROCESSES"])
        pid = int(os.environ["METISFL_JAX_PROCESS_ID"])
    except (KeyError, ValueError) as exc:
        raise RuntimeError(
            "METISFL_JAX_COORDINATOR is set, so METISFL_JAX_NUM_PROCESSES "
            "and METISFL_JAX_PROCESS_ID must both be set to integers "
            f"(got NUM_PROCESSES={os.environ.get('METISFL_JAX_NUM_PROCESSES')!r}, "
            f"PROCESS_ID={os.environ.get('METISFL_JAX_PROCESS_ID')!r})"
        ) from exc
    if num < 1 or not (0 <= pid < num):
        raise RuntimeError(
            f"invalid multi-host world: NUM_PROCESSES={num}, PROCESS_ID={pid}")
    # Multi-process worlds: rank 0 serves the federation; ranks > 0 replay
    # its compute calls via parallel/replicated.py (the learner __main__
    # branches on jax.process_index() after this returns).
    import jax

    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num, process_id=pid)
    logger.info("jax.distributed initialized: process %d/%d via %s",
                pid, num, coordinator)
    return True
