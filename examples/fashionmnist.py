"""FashionMNIST federation: the flagship runnable example.

Mirror of the reference's flagship (reference examples/keras/fashionmnist.py:1-97):
partition the dataset across N learners, boot a controller + N learner
processes on localhost, run R synchronous FedAvg rounds, print the community
model's test accuracy, dump ``experiment.json``.

Runs fully offline (synthetic structured data unless --data points at an
.npz); add ``--secure masking|ckks`` for an encrypted federation and
``--non-iid`` for label-skew shards.

    python examples/fashionmnist.py --learners 3 --rounds 3
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser("fashionmnist federation")
    parser.add_argument("--learners", type=int, default=3)
    parser.add_argument("--rounds", type=int, default=3)
    parser.add_argument("--protocol", default="synchronous",
                        choices=["synchronous", "semi_synchronous",
                                 "asynchronous"])
    parser.add_argument("--secure", default="",
                        choices=["", "masking", "ckks"])
    parser.add_argument("--non-iid", action="store_true",
                        help="label-skew shards (2 classes/learner)")
    parser.add_argument("--data", default="",
                        help=".npz with x_train/y_train/x_test/y_test "
                             "(default: offline synthetic stand-in)")
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--examples-per-learner", type=int, default=600)
    parser.add_argument("--workdir", default="")
    parser.add_argument("--profile-dir", default="",
                        help="capture jax.profiler traces of steady-state "
                             "training steps into this directory "
                             "(TensorBoard/xprof-readable)")
    args = parser.parse_args()

    from metisfl_tpu.platform import enter_process
    enter_process()

    from examples.utils.data import (iid_partition, load_fashion_mnist,
                                     non_iid_partition)
    from examples.utils.environment import generate_localhost_env
    from metisfl_tpu.driver.session import DriverSession
    from metisfl_tpu.models import ArrayDataset, FlaxModelOps
    from metisfl_tpu.models.zoo import FashionMnistCNN

    n_total = args.examples_per_learner * args.learners
    x_train, y_train, x_test, y_test = load_fashion_mnist(
        args.data or None, n_synthetic=n_total)
    part = non_iid_partition if args.non_iid else iid_partition
    shards = part(x_train, y_train, args.learners)
    print(f"partitioned {len(x_train)} examples into "
          f"{[len(s) for s in shards]} ({'non-IID' if args.non_iid else 'IID'})")

    def make_recipe(shard: ArrayDataset):
        sx, sy = shard.x, shard.y
        seed = shard.seed
        tx, ty = x_test, y_test

        def recipe():
            ops = FlaxModelOps(FashionMnistCNN(),
                               np.zeros((2, 28, 28, 1), np.float32),
                               rng_seed=0)
            return (ops, ArrayDataset(sx, sy, seed=seed), None,
                    ArrayDataset(tx, ty))

        return recipe

    config = generate_localhost_env(
        args.learners, rounds=args.rounds, protocol=args.protocol,
        batch_size=args.batch_size, secure_scheme=args.secure)
    if args.profile_dir:
        config.train.profile_dir = args.profile_dir
    # no template built here: this process launches the learners, so it
    # must never touch a JAX backend itself (initial model = recipe 0's,
    # built in a CPU child)
    session = DriverSession(config, None,
                            [make_recipe(s) for s in shards],
                            workdir=args.workdir or None)
    stats = session.run()

    rounds_done = stats["global_iteration"]
    accs = [
        m["test"]["accuracy"]
        for entry in stats["community_evaluations"] if entry["evaluations"]
        for m in entry["evaluations"].values() if "test" in m
    ]
    print(f"completed {rounds_done} rounds "
          f"({args.learners} learners, protocol={args.protocol}, "
          f"secure={args.secure or 'off'})")
    if accs:
        print(f"community test accuracy: first={accs[0]:.3f} "
              f"last={np.mean(accs[-args.learners:]):.3f}")
    print(f"experiment.json: {os.path.join(session.workdir, 'experiment.json')}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
