#!/usr/bin/env python3
"""The benchmark's command:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell on the machine it is started on and prints, as the last line
of standard output, one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and the
numbers compared beside their limits under ``compared``). Without a TPU it
exits non-zero and prints no result. ``--rehearse`` drives the same code at
toy shapes on the CPU to debug the plumbing: its line carries no metric and
says so; it is never a result.
"""

from __future__ import annotations

import time

STARTED = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import common, spec  # noqa: E402


def drive(workload: str, seed: int, seconds: float, trace: bool,
          rehearse: bool = False, extras: bool = False, fault: str = "",
          started: float = 0.0, work: str = "") -> dict:
    """One run of one cell; the drivers are found by the traffic's kind."""
    import importlib
    cell = spec.cell(workload, rehearse=rehearse)
    driver = importlib.import_module(
        f"benchmark.lib.{cell['traffic']['driver']}_driver")
    platform = "cpu" if rehearse else "tpu"
    out = driver.run(cell, seed, seconds, trace, platform,
                     started or time.time(), work or common.workdir(),
                     extras=extras, fault=fault)
    wanted = cell["per_layer"] if trace else cell["end_to_end"]
    units = {m["name"]: m["unit"] for m in wanted}
    metrics = {}
    if trace:
        for m in wanted:
            try:
                value = spec.metric_reader(m["name"]).read(out["ctx"])
            except spec.UnknownDevice:
                if not rehearse:        # the CPU has no peaks, by design
                    raise
                continue
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in out["metrics"].items() if k in units}
    out["metrics_out"] = metrics
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser("benchmark")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="toy shapes on the CPU: plumbing only, the "
                             "line carries no metric and is never a result")
    parser.add_argument("--readings", default="",
                        help="also run the control and the faults planted "
                             "in the reference, and append every reading "
                             "to this file (how the limits are set)")
    parser.add_argument("--extras", type=int, choices=(0, 1), default=1,
                        help="with --readings: 0 leaves the control and "
                             "the faults out (the program's reading alone)")
    parser.add_argument("--keep", default="",
                        help="copy the run's logs here on exit")
    args = parser.parse_args(argv)
    common.prepare_environment()
    seconds = (args.seconds if args.seconds is not None
               else spec.benchmark()["run_seconds"])
    work = common.workdir()
    try:
        out = drive(args.workload, args.seed, seconds, bool(args.trace),
                    rehearse=args.rehearse,
                    extras=bool(args.readings) and bool(args.extras),
                    started=STARTED, work=work)
    except (common.BenchFailure, spec.UnknownDevice) as exc:
        print(f"benchmark FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    finally:
        if args.keep:
            _keep_logs(work, args.keep)
        shutil.rmtree(work, ignore_errors=True)
    if args.readings:
        print("readings " + json.dumps({"seed": args.seed,
                                        **out["readings"]}),
              file=sys.stderr, flush=True)
        os.makedirs(os.path.dirname(os.path.abspath(args.readings)),
                    exist_ok=True)
        with open(args.readings, "a") as f:
            f.write(json.dumps({
                "workload": args.workload, "seed": args.seed,
                "readings": out["readings"], "correct": out["correct"],
                "reference_s": out["reference_s"],
                "metrics": out["metrics"]}) + "\n")
    metrics = {} if args.rehearse else out["metrics_out"]
    line = common.result_line(
        out["correct"], out["attempted"], out["failed"], metrics,
        out["device"], out["compared"], out["breakdown"])
    if args.rehearse:
        line["rehearsal"] = True
    print(json.dumps(line), flush=True)
    return 0


def _keep_logs(work: str, dest: str) -> None:
    os.makedirs(dest, exist_ok=True)
    for dirpath, _dirs, files in os.walk(work):
        for name in files:
            if name.endswith((".log", ".json", ".jsonl")):
                shutil.copy(os.path.join(dirpath, name),
                            os.path.join(dest, name))


if __name__ == "__main__":
    sys.exit(main())
