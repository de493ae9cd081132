"""Examples + data tooling (reference examples/utils/data_partitioning.py,
examples/keras/fashionmnist.py — the de-facto integration suite)."""

import os
import re
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from examples.utils.data import (  # noqa: E402
    iid_partition,
    load_fashion_mnist,
    non_iid_partition,
    synthetic_image_classification,
)


class TestPartitioning:
    def test_iid_covers_all_examples_evenly(self):
        x, y = synthetic_image_classification(n=1000)
        shards = iid_partition(x, y, 4)
        assert [len(s) for s in shards] == [250, 250, 250, 250]
        # IID: every shard sees (almost) every class
        for s in shards:
            assert len(np.unique(s.y)) >= 9

    def test_non_iid_skews_labels_and_covers_everything(self):
        x, y = synthetic_image_classification(n=2000)
        shards = non_iid_partition(x, y, 5, classes_per_learner=2)
        # no example dropped, and the union covers all classes
        assert sum(len(s) for s in shards) == 2000
        assert set(np.concatenate([np.unique(s.y) for s in shards])) == set(
            np.unique(y))
        # skew: each learner sees only a few contiguous label regions
        # (a ~200-example shard can straddle up to 3 uneven class spans),
        # far from the IID ~10 classes — and learners differ
        class_counts = [len(np.unique(s.y)) for s in shards]
        assert max(class_counts) <= 6
        assert np.mean(class_counts) < 5
        owned = [tuple(sorted(np.unique(s.y))) for s in shards]
        assert len(set(owned)) > 1

    def test_non_iid_shards_are_disjoint(self):
        x, y = synthetic_image_classification(n=2000)
        # tag examples by index through a side channel: x values are unique
        # enough; compare via row bytes
        shards = non_iid_partition(x, y, 4, classes_per_learner=2)
        seen = set()
        for s in shards:
            for row in s.x.reshape(len(s), -1)[:, :4]:
                key = row.tobytes()
                assert key not in seen
                seen.add(key)

    def test_synthetic_fallback_is_learnable_shapes(self):
        xtr, ytr, xte, yte = load_fashion_mnist(n_synthetic=500)
        assert xtr.shape == (500, 28, 28, 1) and ytr.shape == (500,)
        assert len(xte) == 100
        assert xtr.dtype == np.float32 and ytr.dtype == np.int32


def test_fashionmnist_example_completes_rounds(tmp_path):
    """VERDICT item 6 'done' criterion: the flagship example completes its
    rounds on CPU as real subprocesses."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "fashionmnist.py"),
         "--learners", "2", "--rounds", "2",
         "--examples-per-learner", "150", "--batch-size", "16",
         "--workdir", str(tmp_path)],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "completed" in proc.stdout
    assert os.path.exists(tmp_path / "experiment.json")


def test_llama_lora_example_with_the_latent_decoder():
    """``--latent``: the latent-attention decoder with a held share of its
    routed experts and a bfloat16 base through the same federation, shipped
    subset and decode as the Llama-style model."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "llama_lora.py"),
         "--latent", "--learners", "2", "--rounds", "1", "--dp", "2",
         "--tp", "1", "--lora-rank", "4"],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "completed 1 rounds" in proc.stdout
    assert "B of adapters" in proc.stdout
    assert "greedy continuation" in proc.stdout


def test_llama_lora_example_with_the_shortcut_decoder():
    """``--shortcut``: the shortcut-connected decoder (two latent-attention
    sublayers, two dense FFNs and a routed layer with zero-computation
    experts a layer) through the same federation and shipped subset, and a
    decode that carries two latent caches a layer."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "llama_lora.py"),
         "--shortcut", "--learners", "2", "--rounds", "1", "--dp", "2",
         "--tp", "1", "--lora-rank", "4"],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "completed 1 rounds" in proc.stdout
    assert "B of adapters" in proc.stdout
    assert "greedy continuation" in proc.stdout


def test_ladder_rungs_execute(tmp_path):
    """BASELINE.md config ladder (VERDICT r3 #2): each rung's protocol x
    model combination actually executes and records round wall-clock. The
    vit (semi-sync) and bert (async + CKKS secure agg) rungs run here; the
    heavier resnet x16 rung runs in examples/ladder.py's default set."""
    import json

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "ladder.py"),
         "--rungs", "vit,bert", "--rounds", "1",
         "--workdir", str(tmp_path)],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    with open(tmp_path / "ladder.json") as f:
        summary = json.load(f)
    assert {r["rung"] for r in summary} == {"vitlite_x8_semisync",
                                           "bertlite_x8_async_ckks"}
    for record in summary:
        assert record["rounds_completed"] >= 1
        assert record["round_wall_clock_s"][0] > 0
    for key in ("vit", "bert"):
        assert os.path.exists(tmp_path / f"experiment_{key}.json")


def test_multihost_learner_example(tmp_path):
    """The multi-host learner example completes rounds with a 2-process
    world and both ranks exit cleanly."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "examples", "multihost_learner.py"),
         "--world", "2", "--rounds", "2", "--workdir", str(tmp_path)],
        capture_output=True, text=True, timeout=360, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "completed" in proc.stdout
    assert "ERROR" not in proc.stdout  # exits 1 on incomplete rounds
    assert "learner_0_rank1: exit 0" in proc.stdout


def test_neuroimaging_regression_example(tmp_path):
    """VERDICT r3 #7: a regression federation end to end — 3D-CNN, mse
    loss, mae metric, non-IID (age-band) split — mirroring the reference's
    neuroimaging driver (examples/keras/neuroimaging.py:1-90)."""
    import json

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "neuroimaging.py"),
         "--learners", "2", "--rounds", "2",
         "--examples-per-learner", "48", "--batch-size", "8",
         "--workdir", str(tmp_path)],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    # >= 2: training keeps running during the bounded eval-drain window,
    # so ANY number of extra rounds may complete before shutdown (under
    # load the drain can fit 8+ tiny rounds — a [2-9] single-digit match
    # here flaked when the counter hit double digits)
    m = re.search(r"completed (\d+) rounds", proc.stdout)
    assert m and int(m.group(1)) >= 2, proc.stdout[-500:]
    assert "community test MAE" in proc.stdout
    with open(tmp_path / "experiment.json") as f:
        experiment = json.load(f)
    evals = [m for entry in experiment["community_evaluations"]
             for m in entry["evaluations"].values()]
    assert any("mae" in m.get("test", {}) for m in evals)


def test_yaml_template_loads_to_defaults(tmp_path):
    """examples/config/template.yaml (the reference's template.yaml role)
    parses through load_config, every documented default matches the
    dataclass tree's actual defaults, AND every dataclass field appears in
    the YAML — a field added to the tree without a template entry fails
    here, so the template cannot drift by omission either."""
    import dataclasses

    import yaml

    from metisfl_tpu.config import FederationConfig, load_config

    path = os.path.join(REPO, "examples", "config", "template.yaml")
    cfg = load_config(path)
    assert len(cfg.learners) == 2
    default = FederationConfig(learners=cfg.learners)
    for f in dataclasses.fields(FederationConfig):
        assert getattr(cfg, f.name) == getattr(default, f.name), f.name

    # full key coverage, recursively (absent keys load as defaults, so the
    # equality check above alone cannot catch omissions)
    with open(path) as fh:
        raw = yaml.safe_load(fh)

    def assert_covered(cls, mapping, where):
        import typing

        hints = typing.get_type_hints(cls)
        for f in dataclasses.fields(cls):
            assert f.name in mapping, f"{where}.{f.name} missing from template"
            hint = hints[f.name]
            if dataclasses.is_dataclass(hint):
                assert_covered(hint, mapping[f.name] or {},
                               f"{where}.{f.name}")

    assert_covered(FederationConfig, raw, "config")
    from metisfl_tpu.config import LearnerEndpoint

    assert_covered(LearnerEndpoint, raw["learners"][0], "learners[0]")

    # overrides round-trip (incl. round-4 fields) and validation still bites
    override = tmp_path / "fed.yaml"
    override.write_text(
        "protocol: asynchronous\n"
        "aggregation: {rule: fedadam, staleness_decay: 0.5}\n"
        "model_store: {store: remote, host: stores.example, port: 50099}\n"
        "secure: {min_recovery_parties: 3}\n")
    cfg2 = load_config(str(override))
    assert cfg2.aggregation.rule == "fedadam"
    assert cfg2.model_store.host == "stores.example"
    assert cfg2.secure.min_recovery_parties == 3

    bad = tmp_path / "bad.yaml"
    bad.write_text("aggregation: {rule: scaffold}\n"
                   "train: {optimizer: adam}\n")
    import pytest

    with pytest.raises(ValueError, match="scaffold requires optimizer"):
        load_config(str(bad))


def test_robust_federation_example(tmp_path):
    """The byzantine demo: a poisoned learner collapses fedavg but not
    median — asserted on the script's own printed accuracies."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "examples", "robust_federation.py"),
         "--learners", "4", "--rounds", "2", "--rules", "fedavg,median"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    rows = re.findall(
        r"rule=(\w+)\s+rounds_ok=(\w+) community test accuracy: ([\d.]+)",
        proc.stdout)
    accs = {rule: acc for rule, _, acc in rows}
    assert set(accs) == {"fedavg", "median"}, proc.stdout[-500:]
    # a timed-out run must fail HERE (self-explanatory), not at the
    # accuracy gap with barely-trained models
    assert all(ok == "True" for _, ok, _ in rows), rows
    assert float(accs["median"]) > float(accs["fedavg"]) + 0.15, accs
