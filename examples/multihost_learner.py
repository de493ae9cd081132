#!/usr/bin/env python
"""A federation whose learner is a multi-host world.

One learner owns a multi-process ``jax.distributed`` world (the stand-in
for a multi-host TPU slice): rank 0 runs the learner service and leads,
rank 1+ replay its compute calls over the distributed runtime
(metisfl_tpu/parallel/replicated.py) so the world's cross-host collectives
stay in lockstep. The driver launches every rank via
``LearnerEndpoint.world_size``.

The reference has no intra-learner distribution at all (one process per
silo); this is the rebuild's scale-out for learners whose model needs more
than one host.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python examples/multihost_learner.py --world 2 --rounds 2
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from examples.utils.environment import free_port  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser("multi-host learner federation")
    parser.add_argument("--world", type=int, default=2,
                        help="processes in the learner's world")
    parser.add_argument("--rounds", type=int, default=2)
    parser.add_argument("--workdir", default="")
    args = parser.parse_args()

    from metisfl_tpu.platform import enter_process
    enter_process()

    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (
        AggregationConfig,
        EvalConfig,
        FederationConfig,
        LearnerEndpoint,
        TerminationConfig,
    )
    from metisfl_tpu.driver import DriverSession

    rng = np.random.default_rng(0)
    w = rng.standard_normal((8, 3)).astype(np.float32)
    x = rng.standard_normal((96, 8)).astype(np.float32)
    y = np.argmax(x @ w, -1).astype(np.int32)

    def recipe():
        # runs in EVERY rank of the world; with >1 process the engine
        # spans the global device mesh
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from metisfl_tpu.models import ArrayDataset, FlaxModelOps
        from metisfl_tpu.models.zoo import MLP

        kwargs = {}
        if jax.process_count() > 1:
            kwargs = dict(mesh=Mesh(np.array(jax.devices()), ("dp",)),
                          partition_rules=[])
        ops = FlaxModelOps(MLP(features=(16,), num_outputs=3),
                           np.zeros((2, 8), np.float32), rng_seed=0, **kwargs)
        return ops, ArrayDataset(x, y, seed=0), None, ArrayDataset(x, y)

    config = FederationConfig(
        controller_port=free_port(),
        aggregation=AggregationConfig(scaler="participants"),
        train=TrainParams(batch_size=16, local_steps=4, learning_rate=0.1,
                          scan_chunk=2),
        # eval off: a fresh eval-program compile under the leader
        # lock at shutdown time can delay follower release under load
        eval=EvalConfig(every_n_rounds=0),
        termination=TerminationConfig(federation_rounds=args.rounds),
        learners=[LearnerEndpoint(world_size=args.world)],
    )
    # no template built here: this process launches the learner world, so
    # it must never touch a JAX backend itself (initial model = the
    # recipe's, built in a CPU child)
    session = DriverSession(
        config, None, [recipe],
        workdir=args.workdir or None,
        learner_env={
            "XLA_FLAGS": os.environ.get(
                "XLA_FLAGS", "--xla_force_host_platform_device_count=4"),
        })
    session.initialize_federation()
    try:
        session.monitor_federation(poll_every_s=0.5)
        stats = session.get_statistics()
        rounds = stats["global_iteration"]
        print(f"completed {rounds} rounds with "
              f"{len(stats['learners'])} learner(s); world={args.world}")
        session.save_experiment()
    finally:
        session.shutdown_federation()
    for name, code in sorted(session.process_exit_codes().items()):
        if "_rank" in name:
            print(f"{name}: exit {code}")
    if rounds < args.rounds:
        print(f"ERROR: only {rounds}/{args.rounds} rounds completed")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
