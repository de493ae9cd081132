"""bench.py harness plumbing — the sweep/guard logic must be CI-covered so
the driver's one TPU run per round can't be the first execution of it."""

import os

import numpy as np


def test_mfu_sweep_plumbing_toy_shapes():
    """All three variants run, report per-variant timings, and a best
    variant is selected (toy shapes, CPU — no chip peak, so no mfu key)."""
    from bench import bench_mfu

    out = bench_mfu(L=32, dim=16, depth=1, heads=2, vocab=64,
                    require_tpu=False)
    for label in ("b8_dense", "b8_dense_scan8", "b8_flash_scan8",
                  "b16_flash_remat_scan8"):
        assert f"lm_{label}_ms_per_step" in out, out.get(
            f"lm_{label}_error", f"variant {label} missing")
        assert out[f"lm_{label}_tokens_per_sec"] > 0
    assert out["lm_best_variant"].startswith("b")
    assert out["lm_ms_per_step"] > 0
    assert out["lm_flops_per_step"] > 0
    assert out["lm_params"] > 0


def test_lm_step_flops_accounting():
    """One FLOPs accounting for every variant: causal-halved attention,
    backward = 2x forward."""
    from bench import _lm_step_flops

    B, L, dim, depth, vocab = 2, 64, 32, 3, 128
    tokens = B * L
    per_layer = 8 * tokens * dim * dim + 2 * B * L * L * dim \
        + 24 * tokens * dim * dim
    want = 3 * (depth * per_layer + 2 * tokens * dim * vocab)
    assert _lm_step_flops(B, L, dim, depth, vocab) == want


def test_store_bench_section():
    from bench import bench_store

    out = bench_store(4)
    assert out["store_learners"] == 4
    assert out["store_cached_hit_rate"] == 1.0
    assert out["store_disk_insert_ms"] > 0


def test_health_bench_section():
    import bench

    out = bench.bench_health(num_learners=3, rounds=2)
    assert out["health_learners"] == 3
    assert out["health_params"] > 1_000_000        # bench model size
    assert out["health_observe_ms"] > 0
    assert out["health_round_fold_ms"] > 0


def test_section_subprocess_roundtrip():
    """Child mode runs one section and the parent reads its JSON back —
    one process per section, and the parent stays off the backend."""
    from bench import _run_section

    errors = {}
    out = _run_section("ckks", quick=True, timeout=240, errors=errors)
    assert errors == {}
    assert out["ckks_parties"] == 8
    assert out["ckks_encrypt_ms"] > 0


def test_section_timeout_is_killed_and_recorded():
    """A section that exceeds its budget is killed; the parent records
    the error and keeps going instead of hanging the whole bench."""
    import time as _time

    from bench import _run_section

    errors = {}
    t0 = _time.monotonic()
    out = _run_section("store", quick=False, timeout=1, errors=errors)
    # the child streams partials; whatever survived must be a dict
    assert isinstance(out, dict)
    assert "store" in errors and "timed out" in errors["store"]
    # kill must be prompt: well under the in-process section runtime
    assert _time.monotonic() - t0 < 120


def test_aggregation_headline_correctness():
    from bench import STRIDE, aggregate_once, synth_models

    from metisfl_tpu.aggregation.fedavg import FedAvg

    models = synth_models(4)
    scales = np.full((4,), 0.25)
    out = aggregate_once(FedAvg(), models, scales, STRIDE)
    expect = np.mean([m["head/bias"] for m in models], axis=0)
    np.testing.assert_allclose(np.asarray(out["head/bias"]), expect,
                               atol=1e-5)


def test_device_sections_lead_and_host_sections_cover_all():
    """Headline sections run first on a healthy backend; the two orderings
    cover exactly the full section set."""
    import bench

    assert bench._DEVICE_SECTIONS[0] == "agg"      # headline metric first
    assert bench._DEVICE_SECTIONS[1] == "mfu"      # then the MFU story
    assert set(bench._DEVICE_SECTIONS + bench._HOST_SECTIONS) == (
        set(bench._SECTIONS) | {"agg"})


def test_serving_bench_section():
    import bench

    out = bench.bench_serving(requests=6, rows_per_request=2, max_batch=8)
    assert out["serving_params"] > 1_000_000       # bench model size
    assert out["serving_unbatched_rows_per_sec"] > 0
    assert out["serving_batched_rows_per_sec"] > 0
    assert out["serving_swap_pause_ms"] > 0
    assert "serving" in bench._SECTIONS
    assert "serving" in bench._SECTION_TIMEOUTS
    assert "serving" in bench._HOST_SECTIONS


def test_device_sections_fail_without_a_tpu():
    """Off-TPU a device section raises: it reports neither an empty
    result nor a CPU number under a device metric's name. It must still
    be wired into the full-mode section tables."""
    import pytest

    import bench

    with pytest.raises(RuntimeError, match="measures the TPU"):
        bench.bench_decode()
    with pytest.raises(RuntimeError, match="measures the TPU"):
        bench.bench_flash()
    with pytest.raises(RuntimeError, match="measures the TPU"):
        bench.bench_mfu()
    assert "decode" in bench._SECTIONS
    assert "decode" in bench._SECTION_TIMEOUTS


def test_full_mode_device_child_fails_without_a_tpu():
    """The full-mode child of ANY device section (the aggregation
    headline included) exits non-zero on a CPU, and the parent records it
    — which is what makes the bench's exit code non-zero."""
    from bench import _run_section

    errors = {}
    out = _run_section("agg", quick=False, timeout=240, errors=errors)
    assert out == {}
    assert "measures the TPU" in errors["agg"]


def test_main_exit_code_reflects_section_errors(monkeypatch, capsys):
    import sys

    import bench

    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.setattr(
        bench, "run_bench",
        lambda quick: bench._result_from({}, {"mfu": "no chip"}, 64))
    assert bench.main() == 1
    monkeypatch.setattr(
        bench, "run_bench",
        lambda quick: bench._result_from({"ms_per_round_median": 1.0},
                                         {}, 64))
    assert bench.main() == 0
    capsys.readouterr()


def test_run_and_record_attributes_backend_per_section(monkeypatch):
    import bench

    monkeypatch.setattr(bench, "_run_section",
                        lambda *a, **k: {"x": 2, "backend": "tpu"})
    details, errors = {"x": 1}, {}
    bench._run_and_record("agg", False, details, errors)
    assert errors == {}
    assert details["x"] == 2 and details["agg_backend"] == "tpu"


def test_mfu_variant_children_merge_and_rollup(monkeypatch):
    """The parent merges each variant child's fields, attributes the
    backend per-section, and computes the best-variant rollup itself
    (children see only their own variant); a failing variant costs
    itself only."""
    import bench

    labels = [lbl for lbl, _ in bench._MFU_VARIANTS]

    def fake_section(name, quick, timeout, errors, variant=None,
                     err_key=None):
        assert name == "mfu" and variant
        if variant == labels[1]:
            errors[err_key] = "section timed out after 420s (killed)"
            return {}
        ms = {"b8_dense": 100.0}.get(variant, 50.0)
        return {f"lm_{variant}_ms_per_step": ms,
                f"lm_{variant}_tokens_per_sec": 1000.0 / ms,
                "device_kind": "TPU v5 lite", "backend": "tpu"}

    monkeypatch.setattr(bench, "_run_section", fake_section)
    details, errors = {}, {}
    bench._run_mfu_variants(False, details, errors)
    assert list(errors) == [f"mfu.{labels[1]}"]
    assert details["mfu_backend"] == "tpu"
    for label in labels[:1] + labels[2:]:
        assert details[f"lm_{label}_ms_per_step"] > 0
    # best = highest tokens/sec = any 50ms variant, not the 100ms one
    assert details["lm_best_variant"] != "b8_dense"
    assert details["lm_ms_per_step"] == 50.0
    assert details["mfu"] > 0  # v5e peak known -> real MFU computed


def test_new_sections_registered():
    import bench

    for name in ("e2e", "cohort", "lora", "health"):
        assert name in bench._SECTIONS
        assert name in bench._SECTION_TIMEOUTS
    assert "lora" == bench._DEVICE_SECTIONS[-1]  # heaviest compile last
