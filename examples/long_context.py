"""Long-context causal-LM training with ring attention (sequence parallel).

The sequence dimension is sharded over the mesh's ``sp`` axis; every
attention layer runs the ring schedule (parallel/ringattn.py) — K/V chunks
rotate over ICI with ``ppermute`` while softmax statistics accumulate
online, so no chip ever holds an (L, L) score matrix. Compare peak memory /
step time against the plain path with ``--no-ring``.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/long_context.py --seq-len 512 --steps 4
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> int:
    parser = argparse.ArgumentParser("long-context ring attention")
    parser.add_argument("--seq-len", type=int, default=512)
    parser.add_argument("--batch-size", type=int, default=4)
    parser.add_argument("--dim", type=int, default=64)
    parser.add_argument("--depth", type=int, default=2)
    parser.add_argument("--heads", type=int, default=4)
    parser.add_argument("--vocab", type=int, default=256)
    parser.add_argument("--steps", type=int, default=4)
    parser.add_argument("--dp", type=int, default=2)
    parser.add_argument("--sp", type=int, default=0,
                        help="0 = absorb remaining devices")
    parser.add_argument("--no-ring", action="store_true",
                        help="plain full attention baseline")
    parser.add_argument("--strategy", choices=["ring", "ulysses"],
                        default="ring",
                        help="sequence-parallel schedule: ring (ppermute "
                             "rotation, O(L/sp) memory) or ulysses "
                             "(all-to-all head scatter)")
    parser.add_argument("--block-kernels", action="store_true",
                        help="run each ring hop on the pallas flash "
                             "kernels (no (Lc, Lc) score matrix, ever)")
    args = parser.parse_args()
    if args.no_ring and args.block_kernels:
        parser.error("--block-kernels selects the ring hop kernel; it "
                     "cannot combine with --no-ring (dense baseline)")
    if args.strategy == "ulysses" and args.block_kernels:
        parser.error("--block-kernels is ring-specific (per-hop block "
                     "kernels); the ulysses local attention routes to "
                     "the flash kernel on its own")

    from metisfl_tpu.platform import enter_process
    enter_process()

    import numpy as np

    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.models import ArrayDataset, FlaxModelOps
    from metisfl_tpu.models.zoo import TRANSFORMER_RULES, LlamaLite
    from metisfl_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(("dp", "sp"), (args.dp, args.sp)))
    print(f"mesh: {dict(mesh.shape)} | seq len {args.seq_len} "
          f"({args.seq_len // mesh.shape['sp']} per sp shard)")

    rng = np.random.default_rng(0)
    x = rng.integers(0, args.vocab,
                     (args.batch_size * 8, args.seq_len)).astype(np.int32)
    y = np.roll(x, -1, axis=1)
    ds = ArrayDataset(x, y)

    module = LlamaLite(vocab_size=args.vocab, dim=args.dim, depth=args.depth,
                       heads=args.heads,
                       sp_mesh=None if args.no_ring else mesh,
                       sp_strategy=args.strategy,
                       sp_block_kernels=args.block_kernels)
    ops = FlaxModelOps(module, ds.x[:2], mesh=mesh,
                       partition_rules=TRANSFORMER_RULES)
    t0 = time.time()
    out = ops.train(ds, TrainParams(batch_size=args.batch_size,
                                    local_steps=args.steps,
                                    learning_rate=0.01, optimizer="adam"))
    wall = time.time() - t0
    tokens = args.steps * args.batch_size * args.seq_len
    print(f"{args.strategy if not args.no_ring else 'full'} attention: "
          f"{out.completed_steps} steps, loss {out.train_metrics['loss']:.3f}, "
          f"{tokens / wall:.0f} tok/s incl. compile, "
          f"{out.ms_per_step:.1f} ms/step steady")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
