"""Byzantine-robust federation demo: one poisoned learner, three rules.

The reference's aggregation rules are all weighted averages, so a single
poisoned learner steers the community model arbitrarily (SURVEY.md §2.1
C3-C7); this rebuild adds coordinate-median / trimmed-mean / (Multi-)Krum
(aggregation/robust.py) on the host path AND device-resident in pod mode
(parallel/collectives.py). This demo runs the same 6-learner federation —
learner 0 ships garbage-scaled updates — under fedavg, median, and krum,
and prints the final community-model test accuracy for each:

    python examples/robust_federation.py --rounds 3
    python examples/robust_federation.py --pod      # device-resident rules
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> int:
    parser = argparse.ArgumentParser("byzantine-robust federation demo")
    parser.add_argument("--learners", type=int, default=6)
    parser.add_argument("--rounds", type=int, default=3)
    parser.add_argument("--rules", default="fedavg,median,krum")
    parser.add_argument("--pod", action="store_true",
                        help="pod mode: rules run device-resident over the "
                             "fed mesh axis (all-gather + sort / Krum "
                             "Gram matmul) instead of on the host")
    args = parser.parse_args()

    from metisfl_tpu.platform import enter_process
    enter_process()

    import numpy as np

    rng = np.random.default_rng(0)
    d, classes = 12, 4
    w_true = rng.standard_normal((d, classes)).astype(np.float32)

    def make_xy(n, seed):
        r = np.random.default_rng(seed)
        x = r.standard_normal((n, d)).astype(np.float32)
        return x, np.argmax(x @ w_true, axis=-1).astype(np.int32)

    test_x, test_y = make_xy(512, 999)

    if args.pod:
        return run_pod(args, make_xy, test_x, test_y)
    return run_host(args, make_xy, test_x, test_y)


def run_host(args, make_xy, test_x, test_y) -> int:
    import numpy as np

    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (AggregationConfig, EvalConfig,
                                    FederationConfig, TerminationConfig)
    from metisfl_tpu.driver import InProcessFederation
    from metisfl_tpu.models import ArrayDataset, FlaxModelOps
    from metisfl_tpu.models.zoo import MLP

    class PoisonedDataset(ArrayDataset):
        """Learner 0's shard: labels shuffled, features exploded — its
        local updates are garbage at huge magnitude (the classic
        model-poisoning shape a mean cannot survive)."""

        def __init__(self, x, y, seed=0):
            r = np.random.default_rng(seed)
            super().__init__(x * 50.0, r.permutation(y), seed=seed)

    for rule in args.rules.split(","):
        rule = rule.strip()
        config = FederationConfig(
            aggregation=AggregationConfig(rule=rule, scaler="participants"),
            train=TrainParams(batch_size=16, local_steps=6,
                              learning_rate=0.2),
            eval=EvalConfig(every_n_rounds=0),
            termination=TerminationConfig(federation_rounds=args.rounds),
        )
        fed = InProcessFederation(config)
        template = None
        test_ds = ArrayDataset(test_x, test_y)
        for i in range(args.learners):
            x, y = make_xy(96, seed=i)
            ds = PoisonedDataset(x, y, seed=i) if i == 0 \
                else ArrayDataset(x, y, seed=i)
            engine = FlaxModelOps(MLP(features=(16,), num_outputs=4),
                                  x[:2])
            if template is None:
                template = engine.get_variables()
            else:
                engine.set_variables(template)
            fed.add_learner(engine, ds, test_dataset=test_ds)
        fed.seed_model(template)
        try:
            fed.start()
            ok = fed.wait_for_rounds(args.rounds, timeout_s=300)
            learner = fed.learners[1]  # an honest learner evaluates
            merged = learner._load_model(
                fed.controller.community_model_bytes())
            acc = learner.model_ops.evaluate(
                test_ds, 128, ["accuracy"], variables=merged)["accuracy"]
        finally:
            fed.shutdown()
        print(f"[host] rule={rule:<12} rounds_ok={ok} "
              f"community test accuracy: {acc:.3f}")
    return 0


def run_pod(args, make_xy, test_x, test_y) -> int:
    import numpy as np

    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.models.zoo import MLP
    from metisfl_tpu.parallel.podfed import PodFederation

    L, K, B = args.learners, 6, 16
    xs, ys = [], []
    for i in range(L):
        x, y = make_xy(K * B, seed=i)
        xs.append(x.reshape(K, B, -1))
        ys.append(y.reshape(K, B))
    x = np.stack(xs)
    y = np.stack(ys)
    x[0] *= 50.0  # poisoned learner 0
    y[0] = np.random.default_rng(0).permutation(y[0].ravel()).reshape(
        y[0].shape)
    for rule in args.rules.split(","):
        rule = rule.strip()
        pod = PodFederation(
            MLP(features=(16,), num_outputs=4),
            sample_input=np.zeros((2, 12), np.float32),
            num_learners=L,
            train_params=TrainParams(optimizer="sgd", learning_rate=0.2,
                                     batch_size=B, local_steps=K),
            rule=rule,
        )
        for _ in range(args.rounds):
            pod.run_round(x, y)
        metrics = pod.evaluate(test_x, test_y)
        print(f"[pod]  rule={rule:<12} community test accuracy: "
              f"{metrics['accuracy']:.3f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
