"""``slot_ms``: mean of the gateway's ``decode.slot`` events that ended
inside the window: enqueue to retire, which is the wait for a slot plus the
slot's occupancy (``serving/decode.py``). What the client's latency has
beyond it is RPC and codec. Read from the gateway's span sink."""

import glob
import json
import os


def read(ctx: dict):
    directory = ctx.get("telemetry_dir", "")
    t0, t1 = ctx.get("window", (0.0, 0.0))
    total, count = 0.0, 0
    for path in glob.glob(os.path.join(directory, "*.jsonl")):
        with open(path, errors="replace") as f:
            for line in f:
                if '"decode.slot"' not in line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                dur_ms = float(rec.get("dur_ms", 0.0))
                end = float(rec.get("start", 0.0)) + dur_ms / 1e3
                if rec.get("name") == "decode.slot" and t0 <= end <= t1:
                    total += dur_ms
                    count += 1
    return total / count if count else None
