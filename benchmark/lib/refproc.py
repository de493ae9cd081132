"""The plain reference's process: started once the program's processes have
left the chip. Reads a job, runs the family's reference (and, when asked,
the control and the faults planted in the reference), writes the readings."""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def _train(job: dict) -> dict:
    import numpy as np

    from benchmark.lib import spec, trainref
    cfg, shape, seed = job["cfg"], job["shape"], job["seed"]
    ref, bind = spec.reference(cfg), spec.binding(cfg)
    trainable, frozen = ref.make_weights(cfg, seed)
    batches = ref.train_batches(cfg, shape, seed)

    def reading(quant: str = "", keep=None, steps=None) -> dict:
        out = trainref.follow(
            lambda p, f, x, y: ref.loss(p, f, x, y, cfg, quant, keep),
            trainable, frozen, batches[:steps], job["learning_rate"])
        final = bind.by_program_name(out["final"])
        initial = bind.by_program_name(out["initial"])
        grad0 = bind.by_program_name(out["first_grad"])
        return {"loss": float(np.mean(out["losses"])),
                "losses": out["losses"],
                "leaf": {n: {"grad0": float(np.linalg.norm(
                                 grad0[n].astype(np.float64))),
                             "change": float(np.linalg.norm(
                                 final[n].astype(np.float64)
                                 - initial[n].astype(np.float64)))}
                         for n in final}}

    result = {"reference": reading()}
    if job.get("extras"):
        result["control"] = reading(quant=cfg["control_precision"])
        result["fault_half_batch"] = reading(keep=0.5)
    return result


def _serve(job: dict) -> dict:
    import numpy as np

    from benchmark.lib import spec
    cfg = job["cfg"]
    ref = spec.reference(cfg)
    requests = [{"prompt": np.asarray(r["prompt"], np.int32),
                 "tokens": np.asarray(r["tokens"], np.int32)}
                for r in job["requests"]]
    result = {"reference": ref.served_gaps(cfg, job["seed"], requests)}
    if job.get("extras"):
        result["control"] = ref.served_gaps(
            cfg, job["seed"], requests, quant=cfg["control_precision"])
    return result


def main() -> int:
    t0 = time.time()
    with open(sys.argv[1]) as f:
        job = json.load(f)
    import jax
    dev = jax.devices()[0]
    if dev.platform != job["platform"]:
        print(f"the reference is on {dev.platform!r}, not "
              f"{job['platform']!r}", file=sys.stderr)
        return 3
    result = _train(job) if job["mode"] == "train" else _serve(job)
    result["seconds"] = time.time() - t0
    with open(sys.argv[2], "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
