"""The benchmark's only code inside a chip-holding process: the recipe the
learner and the gateway build their engine from, and a probe thread that
starts and stops the JAX profiler and reads the device's memory when the
parent asks through files in a control directory."""

from __future__ import annotations

import json
import os
import threading
import time


def _probe_loop(control: str, stop: threading.Event) -> None:
    import jax
    import jax._src.xla_bridge as xla_bridge

    # the chip's fullest moment: arrays (``bytes_in_use``) and what the
    # loaded programs keep reserved for their temporaries
    # (``bytes_reserved``, which ``peak_bytes_in_use`` leaves out), read
    # together every pass of this loop
    peak_total = 0

    def done(name: str, payload: dict) -> None:
        tmp = os.path.join(control, name + ".tmp")
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, os.path.join(control, name))

    while not stop.wait(0.02):
        if xla_bridge.backends_are_initialized():
            for dev in jax.local_devices():
                stats = dev.memory_stats() or {}
                peak_total = max(peak_total,
                                 int(stats.get("bytes_in_use", 0))
                                 + int(stats.get("bytes_reserved", 0)))
        try:
            asks = [n for n in os.listdir(control) if n.startswith("ask_")]
        except OSError:
            return
        for ask in sorted(asks):
            path = os.path.join(control, ask)
            with open(path) as f:
                arg = f.read().strip()
            os.unlink(path)
            if ask == "ask_trace_start":
                # the trace's clock starts where the profiler's session
                # does: at this call
                # device planes are all the reduction reads: no Python
                # tracer, no host tracer, no HLO copy (each costs host
                # memory and time in a process that already holds copies
                # of the model; with the host tracer on, stopping the
                # trace of one 17 s round took 104 s, without it 12 s)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 0
                options.enable_hlo_proto = False
                wall = time.time()
                jax.profiler.start_trace(arg, profiler_options=options)
                done("trace_started", {"wall": wall,
                                       "ready": time.time()})
            elif ask == "ask_trace_stop":
                wall = time.time()
                jax.profiler.stop_trace()
                done("trace_stopped", {"wall": wall, "ready": time.time()})
            elif ask == "ask_device":
                dev = jax.local_devices()
                stats = [d.memory_stats() or {} for d in dev]
                done("device", {
                    "platform": dev[0].platform, "kind": dev[0].device_kind,
                    "count": len(dev),
                    "memory_peak_bytes": max(
                        [peak_total] + [int(s.get("peak_bytes_in_use", 0))
                                        for s in stats]),
                    "memory_stats": stats[0]})


def _start_probe(control: str) -> None:
    """The probe as a daemon thread that is stopped and joined when the
    interpreter exits: a daemon thread still inside a JAX call when the
    interpreter finalizes is unwound by force and aborts the process
    (exit code -6, seen in a rehearsal's learner)."""
    import atexit
    stop = threading.Event()
    thread = threading.Thread(target=_probe_loop, args=(control, stop),
                              daemon=True, name="benchmark-probe")
    thread.start()

    def halt() -> None:
        stop.set()
        thread.join(timeout=5.0)

    atexit.register(halt)


ROUND0_READ = "round0_read"


def _hold_second_round(dataset, control: str, timeout_s: float = 120.0):
    """Round 1, a warm-up round, starts its feed only when the parent has
    read the community model of round 0 (the one the reference follows):
    the controller hands out the newest model alone, and a short round
    would close under the read. One look at a file per round; the batches
    come from the dataset's own generator."""
    feed, calls = dataset.infinite_batches, [0]

    def infinite_batches(*args, **kwargs):
        calls[0] += 1
        deadline = time.time() + timeout_s
        while (calls[0] == 2 and time.time() < deadline
               and not os.path.exists(os.path.join(control, ROUND0_READ))):
            time.sleep(0.01)
        return feed(*args, **kwargs)

    dataset.infinite_batches = infinite_batches


# set in the environment of a serving cell's minting learner alone
MINT_ENV = "BENCHMARK_MINT_ADAPTERS"


class Recipe:
    """Zero-argument callable the program unpickles and runs in the learner
    or gateway process: ``(model_ops, train, None, test)``."""

    def __init__(self, cfg: dict, shape: dict, seed: int, control: str = "",
                 fault: str = ""):
        self.cfg, self.shape, self.seed = cfg, shape, int(seed)
        self.control, self.fault = control, fault

    def __call__(self):
        from benchmark.lib import spec
        from metisfl_tpu.models import FlaxModelOps
        bind = spec.binding(self.cfg)
        if os.environ.get(MINT_ENV):
            return self._minting(bind)
        if self.control and os.path.isdir(self.control):
            _start_probe(self.control)
        module = bind.build_module(self.cfg)
        # made on the device from the seed, then handed over as host
        # values: the learner and the gateway each keep their own device
        # copy of what they train or serve (set_variables, install), and a
        # second one left on the device by the recipe would not fit beside
        # it at 7.56 GB
        import jax
        variables = jax.device_get(bind.variables(self.cfg, self.seed))
        ops = FlaxModelOps(module, bind.sample_input(self.cfg, self.shape),
                           variables=variables,
                           trainable_regex=bind.TRAINABLE_REGEX)
        train, test = bind.datasets(self.cfg, self.shape, self.seed)
        if self.control:
            _hold_second_round(train, self.control)
        if self.fault:
            from benchmark.tests import faults
            ops, train = faults.plant(self.fault, ops, train)
        return ops, train, None, test


    def _minting(self, bind):
        """The serving cells' minting learner: the shipped subset alone
        (``bindings``: ``adapters_only``), one dummy row."""
        import numpy as np
        from metisfl_tpu.models import ArrayDataset, FlaxModelOps
        module, variables, sample = bind.adapters_only(self.cfg, self.seed)
        ops = FlaxModelOps(module, sample, variables=variables,
                           trainable_regex=bind.TRAINABLE_REGEX)
        rows = ArrayDataset(sample, np.zeros(sample.shape, np.int32),
                            seed=self.seed)
        return ops, rows, None, rows


class Probe:
    """The parent's end of the control directory."""

    def __init__(self, control: str):
        self.control = control
        os.makedirs(control, exist_ok=True)

    def flag(self, name: str) -> None:
        with open(os.path.join(self.control, name), "w"):
            pass

    def ask(self, what: str, arg: str = "", answer: str = "",
            timeout_s: float = 120.0) -> dict:
        reply = os.path.join(self.control, answer)
        if answer and os.path.exists(reply):
            os.unlink(reply)
        tmp = os.path.join(self.control, "tmp_" + what)
        with open(tmp, "w") as f:
            f.write(arg)
        os.replace(tmp, os.path.join(self.control, "ask_" + what))
        if not answer:
            return {}
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            if os.path.exists(reply):
                with open(reply) as f:
                    return json.load(f)
            time.sleep(0.02)
        raise TimeoutError(f"the probe did not answer {what!r} "
                           f"within {timeout_s}s")
