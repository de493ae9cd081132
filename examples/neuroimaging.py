"""Neuroimaging-style regression federation: 3D-CNN brain-age prediction.

Mirror of the reference's neuroimaging workload (reference
examples/keras/neuroimaging.py:1-90 driving the BrainAge CNNs of
examples/keras/models/brainage_cnns.py): N sites each hold private MRI-like
volumes with scalar age targets; the federation trains a volumetric 3D-CNN
regressor with MSE loss and reports community-model MAE.

The non-IID mode shards by **target range** (each site sees a contiguous
age band — the realistic covariate shift across scanning sites), which is
where federated averaging actually has to earn its keep for regression.

Runs fully offline on synthetic volumes whose age signal is a deterministic
function of ventricle-like structure, or point ``--data`` at an .npz with
``x_train/y_train/x_test/y_test``.

    python examples/neuroimaging.py --learners 3 --rounds 3
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402


def synthetic_brain_volumes(n: int, shape=(16, 16, 16), seed: int = 0):
    """Volumes with an age-correlated structural signal: a central cavity
    whose radius grows with age plus cortical noise — enough structure for
    a 3D-CNN to regress, zero download."""
    rng = np.random.default_rng(seed)
    ages = rng.uniform(20.0, 90.0, n).astype(np.float32)
    coords = np.stack(np.meshgrid(*[np.linspace(-1, 1, s) for s in shape],
                                  indexing="ij"))
    radius = np.sqrt((coords ** 2).sum(axis=0))  # distance from center
    x = np.empty((n, *shape), np.float32)
    for i, age in enumerate(ages):
        cavity = (radius < 0.15 + 0.35 * (age - 20.0) / 70.0)
        vol = np.where(cavity, 0.1, 1.0)
        vol = vol + rng.normal(0.0, 0.15, shape)
        x[i] = vol.astype(np.float32)
    # normalized targets keep the MSE surface well-scaled for SGD
    return x, (ages - 55.0) / 35.0, ages


def partition_by_target(x, y, num_learners, iid: bool, seed: int = 0):
    rng = np.random.default_rng(seed)
    if iid:
        order = rng.permutation(len(x))
    else:
        order = np.argsort(y)  # contiguous target bands per site
    return [
        (x[idx], y[idx])
        for idx in np.array_split(order, num_learners)
    ]


def main() -> int:
    parser = argparse.ArgumentParser("neuroimaging regression federation")
    parser.add_argument("--learners", type=int, default=3)
    parser.add_argument("--rounds", type=int, default=3)
    parser.add_argument("--protocol", default="synchronous",
                        choices=["synchronous", "semi_synchronous",
                                 "asynchronous"])
    parser.add_argument("--iid", action="store_true",
                        help="uniform shards (default: age-band skew)")
    parser.add_argument("--examples-per-learner", type=int, default=120)
    parser.add_argument("--batch-size", type=int, default=16)
    parser.add_argument("--data", default="",
                        help=".npz with x_train/y_train/x_test/y_test")
    parser.add_argument("--workdir", default="")
    args = parser.parse_args()

    from metisfl_tpu.platform import enter_process
    enter_process()

    from examples.utils.environment import generate_localhost_env
    from metisfl_tpu.config import EvalConfig
    from metisfl_tpu.driver.session import DriverSession
    from metisfl_tpu.models import ArrayDataset, FlaxModelOps
    from metisfl_tpu.models.zoo import BrainAge3DCNN

    if args.data:
        with np.load(args.data) as d:
            x_train, y_train = d["x_train"], d["y_train"]
            x_test, y_test = d["x_test"], d["y_test"]
    else:
        n = args.examples_per_learner * args.learners
        x_all, y_all, _ = synthetic_brain_volumes(n + max(64, n // 5))
        x_train, y_train = x_all[:n], y_all[:n]
        x_test, y_test = x_all[n:], y_all[n:]

    shards = partition_by_target(x_train, y_train, args.learners,
                                 iid=args.iid)
    print(f"partitioned {len(x_train)} volumes into "
          f"{[len(sx) for sx, _ in shards]} "
          f"({'IID' if args.iid else 'age-band skew'})")

    sample = np.zeros((2, *x_train.shape[1:]), np.float32)

    def make_recipe(sx, sy, seed):
        tx, ty = x_test, y_test

        def recipe():
            ops = FlaxModelOps(BrainAge3DCNN(), sample, loss="mse",
                               rng_seed=0)
            return (ops, ArrayDataset(sx, sy, seed=seed), None,
                    ArrayDataset(tx, ty))

        return recipe

    config = generate_localhost_env(
        args.learners, rounds=args.rounds, protocol=args.protocol,
        batch_size=args.batch_size, learning_rate=0.02)
    config.eval = EvalConfig(batch_size=64, datasets=["test"],
                             metrics=["loss", "mse", "mae"])
    # no template built here: this process launches the learners, so it
    # must never touch a JAX backend itself (initial model = recipe 0's,
    # built in a CPU child)
    session = DriverSession(
        config, None,
        [make_recipe(sx, sy, seed=i) for i, (sx, sy) in enumerate(shards)],
        workdir=args.workdir or None)
    stats = session.run()

    rounds_done = stats["global_iteration"]
    maes = [
        m["test"]["mae"]
        for entry in stats["community_evaluations"] if entry["evaluations"]
        for m in entry["evaluations"].values() if "test" in m
    ]
    print(f"completed {rounds_done} rounds "
          f"({args.learners} learners, protocol={args.protocol})")
    if maes:
        # report in years (targets are normalized by /35)
        print(f"community test MAE: first={maes[0] * 35.0:.2f}y "
              f"last={np.mean(maes[-args.learners:]) * 35.0:.2f}y")
    print(f"experiment.json: "
          f"{os.path.join(session.workdir, 'experiment.json')}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
