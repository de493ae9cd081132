"""What both drivers share: the working directory, child environments, the
device report, the reference child, and the result line."""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time

from benchmark.lib import spec

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEVICE_MARKER = "METISFL_TPU_DEVICES"


class BenchFailure(RuntimeError):
    """The run cannot give a result (no chip, a process died, a deadline)."""


def log(message: str) -> None:
    print(f"[benchmark +{time.time() - T0:6.1f}s] {message}",
          file=sys.stderr, flush=True)


T0 = time.time()


def prepare_environment() -> None:
    """One compile cache for every process of the run, at a fixed path
    inside the checkout unless the environment places it; every compile
    is kept (the flash kernels compile in under JAX's default one-second
    threshold and would otherwise compile again in every run)."""
    os.environ.setdefault(CACHE_ENV, os.path.join(spec.ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def workdir() -> str:
    return tempfile.mkdtemp(prefix="metisfl_bench_")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def assert_no_backend() -> None:
    import jax._src.xla_bridge as xla_bridge
    if xla_bridge.backends_are_initialized():
        raise BenchFailure("the benchmark's parent initialized a JAX "
                           "backend: its children could not hold the chip")


def tail(path: str, nbytes: int = 3000) -> str:
    try:
        with open(path, errors="replace") as f:
            return f"--- tail of {path}\n{f.read()[-nbytes:]}"
    except OSError as exc:
        return f"--- {path}: {exc}"


def check_no_failed_task(learner_log: str) -> None:
    """A learner whose task raised logs it and goes on serving: the round
    then never closes. Fail the run at once, with the log."""
    with open(learner_log, errors="replace") as f:
        text = f.read()
    if "training task" in text and " failed" in text:
        raise BenchFailure("the learner's task failed\n"
                           + tail(learner_log, 6000))


def device_line(log_path: str) -> dict:
    """What a chip-holding child said it owns (its first log lines)."""
    with open(log_path, errors="replace") as f:
        hits = [ln for ln in f.read().splitlines()
                if ln.startswith(DEVICE_MARKER)]
    if not hits:
        raise BenchFailure("no device report\n" + tail(log_path))
    return json.loads(hits[-1][len(DEVICE_MARKER):])


def check_device(report: dict, platform: str, chips: int) -> dict:
    """Refuse a device the cell was not written for: another platform, a
    kind without peaks, fewer chips."""
    if report["platform"] != platform:
        raise BenchFailure(f"the cell ran on {report['platform']!r}, "
                           f"not {platform!r}")
    if platform == "tpu":
        spec.peaks(report["device_kind"])       # UnknownDevice if absent
    if len(report["device_ids"]) < chips:
        raise BenchFailure(f"{len(report['device_ids'])} chip(s) visible, "
                           f"the cell asks for {chips}")
    return report


def run_reference(job: dict, work: str, platform: str,
                  timeout_s: float = 600.0) -> dict:
    """The reference child, on the chip the program has left."""
    job_path = os.path.join(work, "reference_job.json")
    out_path = os.path.join(work, "reference_out.json")
    log_path = os.path.join(work, "reference.log")
    with open(job_path, "w") as f:
        json.dump({**job, "platform": platform}, f)
    env = {**os.environ, "JAX_PLATFORMS": platform,
           "PYTHONPATH": os.pathsep.join(
               p for p in (spec.ROOT, os.environ.get("PYTHONPATH", "")) if p)}
    script = os.path.join(spec.BENCH_DIR, "lib", "refproc.py")
    with open(log_path, "w") as log_f:
        child = subprocess.Popen([sys.executable, script, job_path, out_path],
                                 env=env, stdout=log_f,
                                 stderr=subprocess.STDOUT)
        try:
            code = child.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            child.terminate()
            try:
                child.wait(timeout=30)
            except subprocess.TimeoutExpired:
                child.kill()
                child.wait()
            raise BenchFailure(f"the reference exceeded {timeout_s}s\n"
                               + tail(log_path))
    if code != 0:
        raise BenchFailure(f"the reference exited {code}\n" + tail(log_path))
    with open(out_path) as f:
        return json.load(f)


class RssWatch:
    """Samples the resident set of a session's children twice a second and
    keeps each one's peak: a one-chip machine gives a run 40 GiB, and the
    learner's host copies of the model are most of what a run holds."""

    def __init__(self, session):
        self._session, self.peaks = session, {}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="benchmark-rss")
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.wait(0.5):
            for proc in list(self._session._procs):
                try:
                    with open(f"/proc/{proc.process.pid}/status") as f:
                        rss = [ln for ln in f if ln.startswith("VmRSS:")]
                    now = int(rss[0].split()[1]) * 1024
                except (OSError, IndexError, ValueError):
                    continue
                self.peaks[proc.name] = max(self.peaks.get(proc.name, 0),
                                            now)

    def stop(self) -> dict:
        self._stop.set()
        self._thread.join(timeout=5.0)
        log("peak host RSS " + ", ".join(
            f"{name} {b / 2 ** 30:.1f} GiB"
            for name, b in self.peaks.items()))
        return self.peaks


def compiles_total(exposition: str) -> float:
    """Sum of every ``jax_compiles_total`` series of a metrics exposition."""
    total = 0.0
    for line in exposition.splitlines():
        if line.startswith("jax_compiles_total"):
            try:
                total += float(line.rsplit(" ", 1)[1])
            except (IndexError, ValueError):
                continue
    return total


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, compared: dict, breakdown=None) -> dict:
    """The run's last line of standard output, to be printed as JSON; the
    numbers compared come last, and go to standard error as the last
    lines there too."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    out["compared"] = compared
    for name, (value, limit) in compared.items():
        print(f"compared {name} = {value} (limit {limit})",
              file=sys.stderr, flush=True)
    return out
