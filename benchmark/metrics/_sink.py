"""Events of one name from the gateway's span sink that ended inside the
window (``slot_ms`` reads the same files)."""

from __future__ import annotations

import glob
import json
import os


def events(ctx: dict, name: str) -> list:
    directory = ctx.get("telemetry_dir", "")
    t0, t1 = ctx.get("window", (0.0, 0.0))
    marker, out = f'"{name}"', []
    for path in glob.glob(os.path.join(directory, "*.jsonl")):
        with open(path, errors="replace") as f:
            for line in f:
                if marker not in line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                end = (float(rec.get("start", 0.0))
                       + float(rec.get("dur_ms", 0.0)) / 1e3)
                if rec.get("name") == name and t0 <= end <= t1:
                    out.append(rec)
    return out


def mean_attr(ctx: dict, name: str, attr: str):
    values = [float(r["attrs"][attr]) for r in events(ctx, name)
              if attr in r.get("attrs", {})]
    return sum(values) / len(values) if values else None
