"""Scalability sweep: N synthetic learners × model size, in-process.

Mirror of the reference's scalability harness
(reference examples/keras/scalability_testing.py:1-115 + the aggregation
scenario binary controller/scenarios/sync_model_aggregation_performance_main.cc:13-87):
sweeps learner counts over a parameterized MLP and reports per-round
aggregation time from the controller's round-metadata lineage.

    python examples/scalability.py --learners 2 4 8 --hidden 256
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> int:
    parser = argparse.ArgumentParser("scalability sweep")
    parser.add_argument("--learners", type=int, nargs="+", default=[2, 4, 8])
    parser.add_argument("--rounds", type=int, default=3)
    parser.add_argument("--hidden", type=int, default=256)
    parser.add_argument("--local-steps", type=int, default=2)
    args = parser.parse_args()

    from metisfl_tpu.platform import enter_process
    enter_process()

    import jax
    import numpy as np

    from examples.utils.data import iid_partition
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (AggregationConfig, EvalConfig,
                                    FederationConfig, TerminationConfig)
    from metisfl_tpu.driver import InProcessFederation
    from metisfl_tpu.models import ArrayDataset, FlaxModelOps
    from metisfl_tpu.models.zoo import HousingMLP

    rng = np.random.default_rng(0)
    x = rng.standard_normal((4000, 32)).astype(np.float32)
    w = rng.standard_normal(32).astype(np.float32)
    y = (x @ w + 0.1 * rng.standard_normal(4000)).astype(np.float32)

    print(f"{'learners':>8} {'params':>10} {'agg ms/round':>14} "
          f"{'round wall s':>13}")
    for n in args.learners:
        config = FederationConfig(
            aggregation=AggregationConfig(scaler="train_dataset_size"),
            train=TrainParams(batch_size=64, local_steps=args.local_steps,
                              learning_rate=0.01),
            eval=EvalConfig(every_n_rounds=0),
            termination=TerminationConfig(federation_rounds=args.rounds),
        )
        fed = InProcessFederation(config)
        shards = iid_partition(x, y, n)
        template = None
        n_params = 0
        for shard in shards:
            ops = FlaxModelOps(HousingMLP(features=(args.hidden, args.hidden)),
                               shard.x[:2], loss="mse", variables=template)
            if template is None:
                template = ops.get_variables()
                n_params = sum(int(np.size(l))
                               for l in jax.tree.leaves(template))
            fed.add_learner(ops, shard)
        fed.seed_model(template)
        import time
        t0 = time.time()
        fed.start()
        # budget scales with the WORK (a flat cap cut the 1024-learner
        # sweep mid-flight on the single-core host): ~0.2 s of sequential
        # per-learner cost per local step at the default shapes
        ok = fed.wait_for_rounds(
            args.rounds,
            timeout_s=max(600, n * args.rounds
                          * max(1, args.local_steps) // 2))
        wall = time.time() - t0
        stats = fed.statistics()
        fed.shutdown()
        agg_ms = [m["aggregation_duration_ms"]
                  for m in stats["round_metadata"]]
        print(f"{n:>8} {n_params:>10} "
              f"{float(np.median(agg_ms)) if agg_ms else float('nan'):>14.2f} "
              f"{wall / max(1, stats['global_iteration']):>13.2f}"
              + ("" if ok else "  (timeout)"))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
