"""Everything the harness knows about a cell comes from data found by name:
``BENCHMARK.json`` -> the configuration's file, the traffic mix's file
(``traffic/<traffic>.json``), the cell's limits (``limits/<workload>.json``),
the family's reference and binding (``reference/<family>.py``,
``bindings/<family>.py``) and one reader per per-layer metric
(``metrics/<metric>.py``). A later PR adds files and entries; it edits none."""

from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class UnknownDevice(RuntimeError):
    pass


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def _overlay(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = (_overlay(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


def cell(name: str, rehearse: bool = False) -> dict:
    """The cell ``name`` with its configuration, traffic and limits."""
    bench = benchmark()
    work = next((w for w in bench["workloads"] if w["name"] == name), None)
    if work is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == work["config"])
    cfg = _load(os.path.join(ROOT, conf["file"]))
    traffic = _load(os.path.join(BENCH_DIR, "traffic",
                                 work["traffic"] + ".json"))
    limits = _load(os.path.join(BENCH_DIR, "limits", name + ".json"))
    if rehearse:
        cfg = _overlay(cfg, cfg.get("rehearse", {}))
        traffic = _overlay(traffic, traffic.get("rehearse", {}))
        limits = _overlay(limits, limits.get("rehearse", {}))
    return {"name": name, "config_name": work["config"], "cfg": cfg,
            "traffic": traffic, "chips": int(work["chips"]),
            "limits": limits["limits"],
            "run_seconds": int(bench["run_seconds"]),
            "end_to_end": [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])],
            "per_layer": [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]}


def reference(cfg: dict):
    return importlib.import_module("benchmark.reference." + cfg["family"])


def binding(cfg: dict):
    return importlib.import_module("benchmark.bindings." + cfg["family"])


def metric_reader(name: str):
    """The reader of per-layer metric ``name``: ``metrics/<name>.py`` with
    dots in the name written as underscores."""
    return importlib.import_module(
        "benchmark.metrics." + name.replace(".", "_").replace("-", "_"))


def peaks(device_kind: str) -> dict:
    table = _load(os.path.join(BENCH_DIR, "lib", "peaks.json"))
    if device_kind not in table:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in benchmark/lib/peaks.json "
            f"(have {sorted(table)}): add its peaks with their source")
    return table[device_kind]
