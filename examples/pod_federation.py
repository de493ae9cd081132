"""Pod-mode federation: the TPU-native ICI fast path.

All learners co-reside on one device mesh; a federation round is ONE XLA
call — per-learner local SGD via ``lax.scan`` sharded over the ``fed`` axis,
weighted-psum FedAvg over ICI. No wire serialization, no gRPC, no host round
trips (replaces reference controller.cc:795-950's byte-blob aggregation).

Runs anywhere via the virtual host mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/pod_federation.py --learners 8 --rounds 5
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> int:
    parser = argparse.ArgumentParser("pod federation")
    parser.add_argument("--learners", type=int, default=8)
    parser.add_argument("--rounds", type=int, default=5)
    parser.add_argument("--local-steps", type=int, default=8)
    parser.add_argument("--batch-size", type=int, default=32)
    args = parser.parse_args()

    from metisfl_tpu.platform import enter_process
    enter_process()

    import jax
    import numpy as np

    from examples.utils.data import iid_partition, synthetic_image_classification
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (AggregationConfig, EvalConfig,
                                    FederationConfig, TerminationConfig)
    from metisfl_tpu.driver.pod import PodFederationDriver
    from metisfl_tpu.models import ArrayDataset
    from metisfl_tpu.models.zoo import FashionMnistCNN

    n_dev = len(jax.devices())
    if n_dev % args.learners and args.learners % n_dev:
        print(f"note: {args.learners} learners on {n_dev} devices — "
              "the fed axis must divide the device count")
    x_all, y_all = synthetic_image_classification(n=args.learners * 600 + 1000)
    x, y, tx, ty = x_all[:-1000], y_all[:-1000], x_all[-1000:], y_all[-1000:]
    shards = iid_partition(x, y, args.learners)

    config = FederationConfig(
        aggregation=AggregationConfig(scaler="train_dataset_size"),
        train=TrainParams(batch_size=args.batch_size,
                          local_steps=args.local_steps, learning_rate=0.05),
        eval=EvalConfig(datasets=["test"]),
        termination=TerminationConfig(federation_rounds=args.rounds),
    )
    driver = PodFederationDriver(config, FashionMnistCNN(), shards,
                                 test_dataset=ArrayDataset(tx, ty))
    stats = driver.run()
    per_round = [m["aggregation_duration_ms"]
                 for m in stats["round_metadata"]]
    print(f"{stats['global_iteration']} rounds on a "
          f"{args.learners}-learner pod mesh ({n_dev} devices)")
    print(f"round wall-clock ms: first={per_round[0]:.1f} "
          f"steady={np.median(per_round[1:]):.1f}" if len(per_round) > 1
          else f"round wall-clock ms: {per_round[0]:.1f}")
    evals = [e for e in stats["community_evaluations"] if e.get("evaluations")]
    if evals:
        metrics = evals[-1]["evaluations"].get("community", {}).get("test", {})
        if "accuracy" in metrics:
            print(f"community test accuracy: {metrics['accuracy']:.3f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
