"""FlaxModelOps engine tests: exact-N steps, FedProx, metrics, eval."""

import numpy as np
import pytest

from metisfl_tpu.comm.messages import TrainParams
from metisfl_tpu.models import ArrayDataset, FlaxModelOps
from metisfl_tpu.models.zoo import MLP


def _toy_classification(n=64, d=8, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    w = rng.standard_normal((d, classes)).astype(np.float32)
    y = np.argmax(x @ w, axis=-1).astype(np.int32)
    return ArrayDataset(x, y, seed=seed)


@pytest.fixture(scope="module")
def ops():
    ds = _toy_classification()
    return FlaxModelOps(MLP(features=(16,), num_outputs=3), ds.x[:2]), ds


def test_exact_step_count(ops):
    engine, ds = ops
    out = engine.train(ds, TrainParams(batch_size=16, local_steps=7,
                                       learning_rate=0.05))
    assert out.completed_steps == 7
    assert out.completed_batches == 7
    assert 0 < out.ms_per_step < 10_000


def test_epochs_to_steps(ops):
    engine, ds = ops
    # 64 examples / bs16 = 4 steps per epoch; 1.5 epochs → 6 steps
    out = engine.train(ds, TrainParams(batch_size=16, local_epochs=1.5,
                                       learning_rate=0.05))
    assert out.completed_steps == 6
    assert out.completed_epochs == pytest.approx(1.5)
    assert len(out.epoch_metrics) == 2  # one full + one partial epoch record


def test_training_reduces_loss():
    ds = _toy_classification(n=128)
    engine = FlaxModelOps(MLP(features=(32,), num_outputs=3), ds.x[:2])
    before = engine.evaluate(ds, batch_size=64)
    engine.train(ds, TrainParams(batch_size=32, local_steps=60,
                                 learning_rate=0.1))
    after = engine.evaluate(ds, batch_size=64)
    assert after["loss"] < before["loss"]
    assert after["accuracy"] > before["accuracy"]


def test_fedprox_pulls_toward_anchor():
    import jax

    ds = _toy_classification(n=64)
    engine_plain = FlaxModelOps(MLP(features=(16,), num_outputs=3), ds.x[:2])
    engine_prox = FlaxModelOps(MLP(features=(16,), num_outputs=3), ds.x[:2])
    engine_prox.set_variables(engine_plain.get_variables())
    start = engine_plain.get_variables()

    engine_plain.train(ds, TrainParams(batch_size=16, local_steps=30,
                                       learning_rate=0.1))
    engine_prox.train(ds, TrainParams(batch_size=16, local_steps=30,
                                      learning_rate=0.1, proximal_mu=10.0))

    def dist(a, b):
        return sum(float(np.sum((np.asarray(x) - np.asarray(y)) ** 2))
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    # strong proximal term keeps the model closer to the round-start weights
    assert dist(engine_prox.get_variables(), start) < dist(
        engine_plain.get_variables(), start)


def test_cancel_event_stops_training(ops):
    import threading

    engine, ds = ops
    cancel = threading.Event()
    cancel.set()
    out = engine.train(ds, TrainParams(batch_size=16, local_steps=50),
                       cancel_event=cancel)
    assert out.completed_steps == 0


def test_evaluate_explicit_variables(ops):
    engine, ds = ops
    variables = engine.get_variables()
    out = engine.evaluate(ds, batch_size=32, variables=variables)
    assert set(out) == {"loss", "accuracy"}
    assert np.isfinite(out["loss"])


def test_variables_roundtrip_through_wire(ops):
    from metisfl_tpu.tensor.pytree import pack_model, unpack_model

    engine, _ = ops
    variables = engine.get_variables()
    restored = unpack_model(pack_model(variables), variables)
    for a, b in zip(np.asarray(list(variables["params"].values())[0]["kernel"]),
                    np.asarray(list(restored["params"].values())[0]["kernel"])):
        np.testing.assert_array_equal(a, b)


def test_eval_metric_registry(ops):
    engine, ds = ops
    out = engine.evaluate(ds, metrics=["accuracy", "top5_accuracy"])
    assert set(out) == {"loss", "accuracy", "top5_accuracy"}
    # 3 classes → top-5 clips to top-3 == always correct
    assert out["top5_accuracy"] == pytest.approx(1.0)
    # unregistered metrics are skipped (eval runs on fire-and-forget
    # threads; raising would make evaluations silently vanish)
    out = engine.evaluate(ds, metrics=["not_a_metric", "accuracy"])
    assert set(out) == {"loss", "accuracy"}


def test_register_custom_metric(ops):
    import jax.numpy as jnp

    from metisfl_tpu.models.ops import register_metric

    engine, ds = ops
    register_metric("const_half", lambda logits, y: jnp.float32(0.5))
    out = engine.evaluate(ds, metrics=["const_half"])
    assert out["const_half"] == pytest.approx(0.5)
    assert "loss" in out


def test_train_profiler_traces(tmp_path):
    """profile_dir captures jax.profiler traces of steady-state steps
    (SURVEY.md §5.1 asks the rebuild to add exactly this)."""
    import glob

    ds = _toy_classification(seed=5)
    engine = FlaxModelOps(MLP(features=(8,), num_outputs=3), ds.x[:2])
    out = engine.train(ds, TrainParams(batch_size=16, local_steps=5,
                                       profile_dir=str(tmp_path),
                                       profile_steps=2))
    assert out.completed_steps == 5
    traces = glob.glob(str(tmp_path) + "/**/*.xplane.pb", recursive=True)
    assert traces, "no profiler trace captured"


def test_scan_chunk_matches_per_step():
    """scan_chunk fuses K steps into one lax.scan program; the math is the
    per-step function, so final params and metrics must match the chunk=1
    path exactly (including the non-divisible remainder steps)."""
    def run(scan_chunk):
        ds = _toy_classification(seed=9)
        engine = FlaxModelOps(MLP(features=(16,), num_outputs=3), ds.x[:2],
                              rng_seed=3)
        out = engine.train(ds, TrainParams(batch_size=16, local_steps=7,
                                           learning_rate=0.05,
                                           scan_chunk=scan_chunk))
        return engine.get_variables(), out

    vars1, out1 = run(1)
    vars3, out3 = run(3)  # 2 chunks of 3 + 1 remainder step
    assert out3.completed_steps == out1.completed_steps == 7
    for a, b in zip(__import__("jax").tree.leaves(vars1),
                    __import__("jax").tree.leaves(vars3)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    assert out3.train_metrics["loss"] == pytest.approx(
        out1.train_metrics["loss"], rel=1e-5)
    assert len(out3.epoch_metrics) == len(out1.epoch_metrics)


def test_scan_chunk_whole_run():
    """local_steps an exact multiple of scan_chunk: no remainder path."""
    ds = _toy_classification(seed=11)
    engine = FlaxModelOps(MLP(features=(8,), num_outputs=3), ds.x[:2])
    out = engine.train(ds, TrainParams(batch_size=16, local_steps=6,
                                       scan_chunk=3, learning_rate=0.05))
    assert out.completed_steps == 6
    assert out.ms_per_step > 0
    assert np.isfinite(out.train_metrics["loss"])


def test_profiler_runs_when_scan_chunk_exceeds_steps(tmp_path):
    """scan_chunk > total_steps falls back to the per-step path; the
    profiler must still capture a trace there."""
    import glob

    ds = _toy_classification(seed=13)
    engine = FlaxModelOps(MLP(features=(8,), num_outputs=3), ds.x[:2])
    out = engine.train(ds, TrainParams(batch_size=16, local_steps=3,
                                       scan_chunk=8,
                                       profile_dir=str(tmp_path),
                                       profile_steps=1))
    assert out.completed_steps == 3
    traces = glob.glob(str(tmp_path) + "/**/*.xplane.pb", recursive=True)
    assert traces, "no profiler trace captured on the fallback path"


# --------------------------------------------------------------------- #
# named leaves: placed and read alone, the rest stays on the device
# --------------------------------------------------------------------- #

HEAD_NAMES = {"params/Dense_1/bias", "params/Dense_1/kernel"}
BASE_NAMES = {"params/Dense_0/bias", "params/Dense_0/kernel"}


def _frozen_engine(**kwargs):
    ds = _toy_classification(seed=21)
    engine = FlaxModelOps(MLP(features=(16,), num_outputs=3), ds.x[:2],
                          trainable_regex="Dense_1", **kwargs)
    engine.set_variables(engine.get_variables())    # device arrays
    return engine, ds


def test_frozen_names_are_the_masked_params():
    engine, _ = _frozen_engine()
    assert engine.frozen_names() == BASE_NAMES
    plain, _ = _frozen_engine()
    plain._trainable_regex = ""
    assert plain.frozen_names() == frozenset()


def test_place_variables_keeps_the_other_device_arrays():
    engine, _ = _frozen_engine()
    before = engine.variables["params"]
    epoch = engine.variables_epoch
    head = [(n, a + 1.0) for n, a in engine.get_variables(HEAD_NAMES)]
    engine.place_variables(head)
    after = engine.variables["params"]
    for leaf in ("bias", "kernel"):
        assert after["Dense_0"][leaf] is before["Dense_0"][leaf]
        assert after["Dense_1"][leaf] is not before["Dense_1"][leaf]
    assert engine.get_variables(HEAD_NAMES)[1][0] == "params/Dense_1/kernel"
    np.testing.assert_array_equal(engine.get_variables(HEAD_NAMES)[1][1],
                                  head[1][1])
    # the engine's own writes leave the epoch; a whole-tree assignment
    # moves it
    assert engine.variables_epoch == epoch
    engine.variables = engine.variables
    assert engine.variables_epoch == epoch + 1
    engine.set_variables(engine.get_variables())
    assert engine.variables_epoch == epoch + 2
    with pytest.raises(KeyError, match="no_such_leaf"):
        engine.place_variables([("params/no_such_leaf", head[0][1])])


def test_train_reads_back_the_named_leaves_alone():
    engine, ds = _frozen_engine()
    twin, _ = _frozen_engine()
    cfg = TrainParams(batch_size=16, local_steps=4, learning_rate=0.1)
    base = dict(engine.get_variables(BASE_NAMES))
    head = dict(engine.get_variables(HEAD_NAMES))
    epoch = engine.variables_epoch
    out = engine.train(ds, cfg, read=HEAD_NAMES)
    whole = twin.train(ds, cfg)
    assert [n for n, _ in out.variables] == sorted(HEAD_NAMES)
    assert out.readback_bytes == sum(a.nbytes for _, a in out.variables)
    assert out.readback_ms > 0 and engine.variables_epoch == epoch
    from metisfl_tpu.tensor.pytree import pytree_to_named_tensors
    named = dict(pytree_to_named_tensors(whole.variables))
    assert whole.readback_bytes == sum(a.nbytes for a in named.values())
    for n, a in out.variables:
        np.testing.assert_array_equal(a, named[n])
        assert not np.array_equal(a, head[n])       # it trained
    # the mask held: the base on the device is what it was
    for n, a in engine.get_variables(BASE_NAMES):
        np.testing.assert_array_equal(a, base[n])


def test_place_variables_on_a_mesh_keeps_the_rules_sharding():
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    engine, _ = _frozen_engine(
        mesh=mesh, partition_rules=[("Dense_1/kernel", P("tp", None))])
    before = engine.variables["params"]
    spec = before["Dense_1"]["kernel"].sharding.spec
    assert spec == P("tp", None)
    head = [(n, a * 2.0) for n, a in engine.get_variables(HEAD_NAMES)]
    engine.place_variables(head)
    after = engine.variables["params"]
    assert after["Dense_0"]["kernel"] is before["Dense_0"]["kernel"]
    assert after["Dense_1"]["kernel"].sharding.spec == spec
    np.testing.assert_array_equal(np.asarray(after["Dense_1"]["kernel"]),
                                  head[1][1])
