"""Aggregation-rule tests, modeled on the reference's fixture style
(federated_average_test.cc, federated_stride_test.cc, federated_recency_test.cc):
small hand-computed models across dtypes, incremental sequences for the
rolling rules.
"""

import numpy as np
import pytest

from metisfl_tpu.aggregation import FedAvg, FedRec, FedStride, make_aggregation_rule


def model(values, dtype=np.float32):
    return {"layer": {"w": np.asarray(values, dtype=dtype)}}


def weights(m):
    return np.asarray(m["layer"]["w"])


# the twelve tensors of a CIFAR-10-scale CNN, 1.41M parameters: the size
# of model the reference's aggregation anecdote measures
# (controller.cc:594-604)
CNN_SHAPES = {
    "conv1/kernel": (3, 3, 3, 32), "conv1/bias": (32,),
    "conv2/kernel": (3, 3, 32, 64), "conv2/bias": (64,),
    "conv3/kernel": (3, 3, 64, 128), "conv3/bias": (128,),
    "dense1/kernel": (2048, 512), "dense1/bias": (512,),
    "dense2/kernel": (512, 512), "dense2/bias": (512,),
    "head/kernel": (512, 10), "head/bias": (10,),
}


def cnn_models(count, seed=0):
    rng = np.random.default_rng(seed)
    return [{name: rng.standard_normal(shape).astype(np.float32)
             for name, shape in CNN_SHAPES.items()} for _ in range(count)]


def test_fedavg_equal_weights_identical_models():
    m = model(range(1, 11))
    out = FedAvg().aggregate([([m], 0.5), ([m], 0.5)])
    np.testing.assert_allclose(weights(out), np.arange(1, 11), rtol=1e-6)


def test_fedavg_two_models_hand_computed():
    m1, m2 = model(range(1, 11)), model(range(11, 21))
    out = FedAvg().aggregate([([m1], 0.5), ([m2], 0.5)])
    np.testing.assert_allclose(weights(out), np.arange(6, 16), rtol=1e-6)


def test_fedavg_unnormalized_scales():
    m1, m2 = model([2.0, 4.0]), model([4.0, 8.0])
    out = FedAvg().aggregate([([m1], 1.0), ([m2], 3.0)])
    np.testing.assert_allclose(weights(out), [3.5, 7.0], rtol=1e-6)


@pytest.mark.parametrize("dtype", [np.uint16, np.int32, np.int8, np.float64,
                                   np.float16])
def test_fedavg_dtype_preserved(dtype):
    m1, m2 = model([1, 2, 3, 4], dtype), model([3, 4, 5, 6], dtype)
    out = FedAvg().aggregate([([m1], 0.5), ([m2], 0.5)])
    assert weights(out).dtype == dtype
    np.testing.assert_allclose(np.asarray(weights(out), np.float64),
                               [2, 3, 4, 5], atol=0.01)


def test_fedavg_bfloat16():
    import ml_dtypes
    m1 = model([1.0, 2.0], ml_dtypes.bfloat16)
    m2 = model([3.0, 4.0], ml_dtypes.bfloat16)
    out = FedAvg().aggregate([([m1], 0.5), ([m2], 0.5)])
    assert weights(out).dtype == ml_dtypes.bfloat16
    np.testing.assert_allclose(weights(out).astype(np.float32), [2.0, 3.0])


def test_fedavg_empty_raises():
    with pytest.raises(ValueError):
        FedAvg().aggregate([])


def test_fedavg_blockwise_fold_equals_one_shot():
    # the controller streams stride blocks through accumulate(); the result
    # must be identical to a single aggregate() over everything
    models = [model(np.random.default_rng(i).standard_normal(16))
              for i in range(5)]
    scales = [0.1, 0.3, 0.2, 0.25, 0.15]
    pairs = [([m], s) for m, s in zip(models, scales)]
    expected = FedAvg().aggregate(pairs)

    rule = FedAvg()
    rule.reset()
    rule.accumulate(pairs[:2])
    rule.accumulate(pairs[2:4])
    rule.accumulate(pairs[4:])
    out = rule.result()
    np.testing.assert_allclose(weights(out), weights(expected), rtol=1e-6)


def test_fedavg_stride_blocked_over_the_cnn_equals_the_plain_mean():
    # the controller's fold (controller/core.py _compute_community_model)
    # at the width of a real model: sixteen learners, stride 8, one block
    # resident at a time, every one of the twelve tensors
    models = cnn_models(16)
    rule = FedAvg()
    rule.reset()
    for start in range(0, len(models), 8):
        rule.accumulate([([m], 1.0 / len(models))
                         for m in models[start:start + 8]])
    out = rule.result()
    assert set(out) == set(CNN_SHAPES)
    for name in CNN_SHAPES:
        got = np.asarray(out[name])
        assert got.dtype == np.float32 and got.shape == CNN_SHAPES[name]
        np.testing.assert_allclose(
            got, np.mean([m[name] for m in models], axis=0), atol=1e-5)


def test_fedavg_result_before_accumulate_raises():
    rule = FedAvg()
    with pytest.raises(ValueError):
        rule.result()


def test_numpy_fold_kernels_match_jit():
    # the host-numpy fold (used for 64-bit trees under x32 mode) must agree
    # with the jit kernels
    from metisfl_tpu.aggregation import base
    m1 = {"w": np.asarray([1.0, 2.0], np.float64),
          "n": np.asarray([10, 20], np.int64)}
    m2 = {"w": np.asarray([3.0, 6.0], np.float64),
          "n": np.asarray([30, 40], np.int64)}
    acc = base.np_scaled_init(m1, 0.5)
    acc = base.np_scaled_add(acc, m2, 0.5)
    out = base.np_finalize(acc, 1.0, like=m1)
    np.testing.assert_allclose(out["w"], [2.0, 4.0])
    np.testing.assert_array_equal(out["n"], [20, 30])
    assert out["w"].dtype == np.float64 and out["n"].dtype == np.int64
    # subtraction retires a contribution exactly
    acc2 = base.np_scaled_sub(acc, m2, 0.5)
    out2 = base.np_finalize(acc2, 0.5, like=m1)
    np.testing.assert_allclose(out2["w"], [1.0, 2.0])


def test_fedstride_blocked_equals_fedavg():
    models = [model(np.random.default_rng(i).standard_normal(8)) for i in range(3)]
    pairs = [([m], 1 / 3) for m in models]
    expected = FedAvg().aggregate(pairs)

    rule = FedStride()
    rule.aggregate(pairs[:2], learner_ids=["L0", "L1"])       # first stride block
    out = rule.aggregate(pairs[2:], learner_ids=["L2"])       # second block
    np.testing.assert_allclose(weights(out), weights(expected), rtol=1e-5)


def test_fedstride_reset_between_rounds():
    rule = FedStride()
    rule.aggregate([([model([10.0])], 1.0)], learner_ids=["L0"])
    rule.reset()
    out = rule.aggregate([([model([2.0])], 1.0)], learner_ids=["L0"])
    np.testing.assert_allclose(weights(out), [2.0])


def test_fedrec_replaces_previous_contribution():
    m1, m2, m3 = model([2.0, 2.0]), model([4.0, 4.0]), model([8.0, 8.0])
    rule = FedRec()
    out = rule.aggregate([([m1], 0.5)], learner_ids=["L1"])
    np.testing.assert_allclose(weights(out), [2.0, 2.0])      # only L1 so far
    out = rule.aggregate([([m2], 0.5)], learner_ids=["L2"])
    np.testing.assert_allclose(weights(out), [3.0, 3.0])      # avg(m1, m2)
    out = rule.aggregate([([m3], 0.5)], learner_ids=["L1"])   # L1's new model wins
    np.testing.assert_allclose(weights(out), [6.0, 6.0])      # avg(m3, m2)


def test_fedrec_scale_change_on_resubmit():
    rule = FedRec()
    rule.aggregate([([model([1.0])], 0.25)], learner_ids=["L1"])
    rule.aggregate([([model([3.0])], 0.75)], learner_ids=["L2"])
    # L1 resubmits with a different scale; old 0.25 contribution fully retired.
    out = rule.aggregate([([model([5.0])], 0.25)], learner_ids=["L1"])
    np.testing.assert_allclose(weights(out), [(0.25 * 5 + 0.75 * 3) / 1.0])


def test_fedrec_required_lineage():
    assert FedRec().required_lineage == 2
    assert FedAvg().required_lineage == 1


def test_make_aggregation_rule():
    assert isinstance(make_aggregation_rule("fedavg"), FedAvg)
    with pytest.raises(ValueError):
        make_aggregation_rule("nope")


def test_multi_tensor_tree_aggregation():
    m1 = {"a": np.ones((2, 2), np.float32), "b": {"c": np.full(3, 2.0, np.float64)}}
    m2 = {"a": np.full((2, 2), 3.0, np.float32), "b": {"c": np.full(3, 6.0, np.float64)}}
    out = FedAvg().aggregate([([m1], 0.5), ([m2], 0.5)])
    np.testing.assert_allclose(out["a"], np.full((2, 2), 2.0))
    np.testing.assert_allclose(out["b"]["c"], np.full(3, 4.0))
    assert np.asarray(out["b"]["c"]).dtype == np.float64


def test_native_hostfold_matches_numpy_fold():
    """The native streaming fold (hostfold.cc) must produce the numpy
    fallback's result bit-for-bit-close on the host aggregation path."""
    import metisfl_tpu.aggregation.base as base
    from metisfl_tpu.aggregation.base import np_stacked_scaled_add

    rng = np.random.default_rng(13)
    block = [{"w": rng.standard_normal((64, 32)).astype(np.float32),
              "b": rng.standard_normal((7,)).astype(np.float64)}
             for _ in range(5)]
    scales = rng.random(5)

    saved = base._hostfold_lib
    try:
        base._hostfold_lib = None  # force (re)load: native path
        native_init = np_stacked_scaled_add(None, block, scales)
        native_acc = np_stacked_scaled_add(native_init, block, scales)
        base._hostfold_lib = False  # force numpy fallback
        np_init = np_stacked_scaled_add(None, block, scales)
        np_acc = np_stacked_scaled_add(np_init, block, scales)
    finally:
        base._hostfold_lib = saved
    for key in ("w", "b"):
        assert native_acc[key].dtype == np_acc[key].dtype
        np.testing.assert_allclose(native_acc[key], np_acc[key],
                                   atol=1e-4, rtol=1e-5)
