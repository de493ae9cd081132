"""The learner's task and the decode loop account for their own time: the
tiled task waterfall under ``RoundProfile.learners[lid]["task"]``, the
``decode.slot`` / ``decode.loop`` accounting, programs named by their
compile label, and the benchmark's readers of all three on hand-made
contexts (tier-1 never runs ``benchmark/tests``)."""

import json
import time

import numpy as np
import pytest

from metisfl_tpu.comm.messages import JoinRequest, TaskResult, TrainParams
from metisfl_tpu.config import (
    AggregationConfig,
    EvalConfig,
    FederationConfig,
    TerminationConfig,
)
from metisfl_tpu.telemetry import profile as tprofile
from metisfl_tpu.telemetry import trace as ttrace


@pytest.fixture()
def span_ring():
    """Finished spans in memory (the ring is off outside the fabric)."""
    ttrace.configure(enabled=True, service="test", dir="")
    ttrace.configure_ring(8192)
    _, cursor, _ = ttrace.spans_since(0)
    yield lambda: ttrace.spans_since(cursor)[0]
    ttrace.configure_ring(0)


# --------------------------------------------------------------------- #
# the learner's task waterfall
# --------------------------------------------------------------------- #

def test_task_tiles_sum_to_the_task_and_land_in_the_round_profile(
        span_ring):
    from metisfl_tpu.driver import InProcessFederation
    from metisfl_tpu.models import ArrayDataset, FlaxModelOps
    from metisfl_tpu.models.zoo import MLP

    rng = np.random.default_rng(3)
    w = rng.standard_normal((6, 3)).astype(np.float32)
    config = FederationConfig(
        protocol="synchronous",
        aggregation=AggregationConfig(rule="fedavg", scaler="participants"),
        train=TrainParams(batch_size=8, local_steps=4, scan_chunk=2,
                          learning_rate=0.05),
        eval=EvalConfig(every_n_rounds=0),
        termination=TerminationConfig(federation_rounds=2))
    fed = InProcessFederation(config)
    template = None
    for i in range(2):
        x = rng.standard_normal((48, 6)).astype(np.float32)
        y = np.argmax(x @ w, axis=-1).astype(np.int32)
        engine = FlaxModelOps(MLP(features=(8,), num_outputs=3), x[:2])
        if template is None:
            template = engine.get_variables()
        else:
            engine.set_variables(template)
        fed.add_learner(engine, ArrayDataset(x, y, seed=i))
    fed.seed_model(template)
    import jax
    full = sum(leaf.nbytes for leaf in jax.tree.leaves(template))
    try:
        fed.start()
        assert fed.wait_for_rounds(2, timeout_s=120)
        metas = fed.statistics()["round_metadata"][:2]
    finally:
        fed.shutdown()
    spans = span_ring()
    trains = {(s["attrs"]["round"], s["attrs"]["learner"]): s
              for s in spans if s["name"] == "learner.train"}
    by_parent = {}
    for s in spans:
        by_parent.setdefault(s["parent"], []).append(s)
    seen = 0
    for meta in metas:
        learners = meta["profile"]["learners"]
        assert len(learners) == 2
        for lid, entry in learners.items():
            task = entry["task"]
            assert set(task) == set(tprofile.TASK_TILES) | {"start"}
            tiles = {k: task[k] for k in tprofile.TASK_TILES}
            assert all(v >= 0.0 for k, v in tiles.items()
                       if k != "other"), tiles
            assert tiles["steps"] > 0 and tiles["readback"] > 0
            train = trains.get((meta["global_iteration"], lid))
            if train is None:
                continue        # the profile's round numbering moved on
            seen += 1
            children = {s["name"]: s for s in by_parent[train["span"]]}
            report = children["learner.report"]
            # by construction: the tiles are the task's wall time from the
            # RPC's acceptance to the start of the report
            assert sum(tiles.values()) == pytest.approx(
                report["attrs"]["task_ms"], abs=1.0)
            assert tiles["queued"] == pytest.approx(
                train["attrs"]["queued_ms"], abs=0.01)
            assert task["start"] <= train["start"]
            # and on the spans' own clock (another thread may run between
            # the two stamps: a looser bound)
            assert sum(tiles.values()) == pytest.approx(
                tiles["queued"]
                + (report["start"] - train["start"]) * 1e3, abs=50.0)
            # each tile is its span
            for tile, name in (("load", "learner.load_model"),
                               ("upload", "learner.upload"),
                               ("snapshot", "learner.snapshot"),
                               ("encode", "learner.dump_model")):
                assert tiles[tile] == pytest.approx(
                    children[name]["dur_ms"], abs=0.01), tile
            inner = {s["name"]: s for s in by_parent[
                children["learner.train_steps"]["span"]]}
            for tile in ("feed", "steps", "readback"):
                assert tiles[tile] == pytest.approx(
                    inner["train." + tile]["dur_ms"], abs=0.01), tile
            assert inner["train.steps"]["attrs"]["steps"] == 4
            # a full-model task: the whole tree placed and read back,
            # nothing kept on the device
            assert entry["task_bytes"] == {
                "placed_bytes": full, "kept_bytes": 0, "read_bytes": full}
            assert children["learner.upload"]["attrs"] == {
                "bytes": full, "kept_bytes": 0}
            assert inner["train.readback"]["attrs"] == {"bytes": full}
            assert "jit_compile_s_est" not in children[
                "learner.train_steps"]["attrs"]
    assert seen >= 2


def test_a_resident_task_keeps_its_base_arrays_and_all_nine_tiles(
        span_ring):
    """A frozen, ship-only learner's second task: the base on the device
    is the very arrays the first task left, ``learner.upload`` and
    ``train.readback`` count the shipped leaves alone, and the waterfall
    has its nine tiles summing to the task's wall time, as on the
    whole-tree task before it."""
    from tests.test_shiponly import _lone_learner, _named_bytes, _task

    learner = _lone_learner()
    engine = learner.model_ops
    head = _named_bytes([(n, a) for n, a in learner._template
                         if "Dense_1" in n])
    full = _named_bytes(learner._template)
    at_train = []
    real = engine.train

    def spy(*args, **kwargs):
        at_train.append(dict(engine.variables["params"]["Dense_0"]))
        return real(*args, **kwargs)

    engine.train = spy
    left = []
    for r in range(2):
        result = _task(learner, r)
        assert set(result.task_tiles) == (set(tprofile.TASK_TILES)
                                         | set(tprofile.TASK_BYTES)
                                         | {"start"})
        left.append(dict(engine.variables["params"]["Dense_0"]))
    # what task 0 left is what task 1 trained on: not placed again
    assert all(at_train[1][k] is left[0][k] for k in left[0])
    assert all(at_train[0][k] is not left[0][k] for k in left[0])
    spans = span_ring()
    by_parent = {}
    for s in spans:
        by_parent.setdefault(s["parent"], []).append(s)
    trains = sorted((s for s in spans if s["name"] == "learner.train"),
                    key=lambda s: s["attrs"]["round"])
    assert len(trains) == 2
    for r, (train, result) in enumerate(zip(trains,
                                            learner.controller.results)):
        children = {s["name"]: s for s in by_parent[train["span"]]}
        tiles = {k: result.task_tiles[k] for k in tprofile.TASK_TILES}
        assert sum(tiles.values()) == pytest.approx(
            children["learner.report"]["attrs"]["task_ms"], abs=1.0)
        assert tiles["upload"] == pytest.approx(
            children["learner.upload"]["dur_ms"], abs=0.01)
        assert tiles["upload"] > 0 and tiles["readback"] > 0
        inner = {s["name"]: s for s in by_parent[
            children["learner.train_steps"]["span"]]}
        assert tiles["readback"] == pytest.approx(
            inner["train.readback"]["dur_ms"], abs=0.01)
        assert inner["train.readback"]["attrs"] == {"bytes": head}
        assert children["learner.upload"]["attrs"] == (
            {"bytes": head, "kept_bytes": full - head} if r
            else {"bytes": full, "kept_bytes": 0})


class _SilentProxy:
    def __init__(self, record):
        self.learner_id = record.learner_id

    def run_task(self, task):
        pass

    def evaluate(self, task, callback):
        pass

    def shutdown(self):
        pass


def test_result_without_tiles_decodes_and_yields_no_task_key():
    from metisfl_tpu.comm.codec import dumps
    from metisfl_tpu.config import ProfileConfig, TelemetryConfig
    from metisfl_tpu.controller.core import Controller
    from metisfl_tpu.tensor.pytree import pack_model

    model = {"w": np.ones((3,), np.float32)}
    # what an older learner puts on the wire: no ``task_tiles`` at all
    old = TaskResult(task_id="t", learner_id="L").to_dict()
    del old["task_tiles"]
    assert TaskResult.from_wire(dumps(old)).task_tiles == {}

    config = FederationConfig(
        protocol="synchronous",
        aggregation=AggregationConfig(rule="fedavg", scaler="participants"),
        train=TrainParams(batch_size=4, local_steps=1),
        eval=EvalConfig(every_n_rounds=0),
        telemetry=TelemetryConfig(profile=ProfileConfig(enabled=True)))
    ctrl = Controller(config, proxy_factory=_SilentProxy)
    try:
        ctrl.set_community_model(pack_model(model))
        for i in range(2):
            ctrl.join(JoinRequest(hostname="h", port=7700 + i,
                                  num_train_examples=10))
        lids = sorted(ctrl.active_learners())
        with ctrl._lock:
            tokens = {lid: ctrl._learners[lid].auth_token for lid in lids}
        shipped = {"start": 12.5, "queued": 0.1, "steps": 7.0, "other": 0.4}
        sizes = {"placed_bytes": 64, "kept_bytes": 4096, "read_bytes": 64}
        for lid, tiles in zip(lids, ({}, {**shipped, **sizes})):
            wire = TaskResult(
                task_id=f"t_{lid}", learner_id=lid, auth_token=tokens[lid],
                model=pack_model(model), round_id=0, completed_batches=1,
                train_metrics={"loss": 0.5}, task_tiles=tiles).to_dict()
            if not tiles:
                del wire["task_tiles"]
            assert ctrl.task_completed(TaskResult.from_wire(dumps(wire)))
        deadline = time.time() + 30.0
        while ctrl.global_iteration < 1 and time.time() < deadline:
            time.sleep(0.02)
        profile = ctrl.get_statistics()["round_metadata"][0]["profile"]
    finally:
        ctrl.shutdown()
    assert "task" not in profile["learners"][lids[0]]
    # the tiles apart from the byte counts: readers sum ``task``
    assert profile["learners"][lids[1]]["task"] == shipped
    assert profile["learners"][lids[1]]["task_bytes"] == sizes
    assert "task_bytes" not in profile["learners"][lids[0]]


def test_perf_round_view_prints_each_learners_task_waterfall():
    from metisfl_tpu import perf

    prof = {"round": 3, "wall_ms": 100.0, "coverage": 1.0,
            "phases": {"dispatch": 10.0, "wait_uplinks": 90.0},
            "learners": {
                "L0": {"uplink_bytes": 1, "downlink_bytes": 1,
                       "task": {"start": 1.0, "queued": 1.0, "load": 4.0,
                                "steps": 60.0, "readback": 15.0},
                       "task_bytes": {"placed_bytes": 7_300_000,
                                      "kept_bytes": 5_530_000_000,
                                      "read_bytes": 7_300_000}},
                "L1": {"uplink_bytes": 1, "downlink_bytes": 1}}}
    screen = perf.render_waterfall([prof]).splitlines()
    at = next(i for i, line in enumerate(screen)
              if line.startswith("  task L0"))
    assert "wall 80.0ms" in screen[at]
    names = [line.split()[0] for line in screen[at + 1: at + 5]]
    assert names == ["queued", "load", "steps", "readback"]
    steps = screen[at + 3]
    assert "60.0ms" in steps and "75.0%" in steps and "#" * 40 in steps
    assert screen[at + 5].split() == [
        "host<->device", "placed", "7.30MB", "kept", "5.53GB", "read",
        "7.30MB"]
    assert not any(line.startswith("  task L1") for line in screen)


# --------------------------------------------------------------------- #
# the decode loop
# --------------------------------------------------------------------- #

def _lm_ops():
    import jax.numpy as jnp

    from metisfl_tpu.models import FlaxModelOps
    from metisfl_tpu.models.zoo import LlamaLite

    module = LlamaLite(vocab_size=32, dim=16, depth=1, heads=2,
                       dtype=jnp.float32)
    return FlaxModelOps(module, np.zeros((1, 4), np.int32))


def test_decode_slot_and_loop_account_for_their_time(span_ring,
                                                     monkeypatch):
    from metisfl_tpu.serving import decode

    monkeypatch.setattr(decode, "LOOP_EVENT_EVERY_S", 0.01)
    ops = _lm_ops()
    engine = decode.ContinuousBatcher(ops, 1, ops.get_variables(),
                                      slots=2, max_len=32)
    try:
        before = engine.describe()
        assert set(before) == {
            "slots", "max_len", "queued", "active", "steps",
            "tokens_emitted", "version", "swap_pending",
            "cache_bytes", "loop"}
        assert set(before["cache_bytes"]) == {"kv"}     # an all-attention LM
        assert set(before["loop"]) == set(decode.LOOP_SUMS)
        # a root span stands in for the gateway's serving.generate: the
        # slot event parents on the submitter's context
        with ttrace.span("serving.generate") as sp, sp.activate():
            futures = [engine.submit(np.arange(1, 4 + i, dtype=np.int32),
                                     6 + i) for i in range(4)]
        for fut in futures:
            fut.result(timeout=120.0)
    finally:
        engine.close()      # the worker's last partial event is flushed
    after = engine.describe()
    spans = span_ring()
    slots = [s for s in spans if s["name"] == "decode.slot"]
    assert len(slots) == 4
    for s in slots:
        attrs = s["attrs"]
        assert attrs["wait_ms"] >= 0.0 and attrs["prefill_ms"] > 0.0
        assert attrs["wait_ms"] + attrs["prefill_ms"] <= s["dur_ms"] + 0.01
    # four requests on two slots: two of them waited for a retirement
    assert sum(1 for s in slots
               if s["attrs"]["wait_ms"] > s["attrs"]["prefill_ms"]) >= 2
    loops = [s["attrs"] for s in spans if s["name"] == "decode.loop"]
    assert loops
    total = {k: sum(a[k] for a in loops) for k in decode.LOOP_SUMS}
    assert total["steps"] == after["steps"] - before["steps"] > 0
    assert total["prefills"] == total["admitted"] == total["retired"] == 4
    for key in decode.LOOP_SUMS:
        assert total[key] == pytest.approx(
            after["loop"][key] - before["loop"][key], abs=1e-4), key
    assert total["step_s"] > 0 and total["prefill_s"] > 0
    assert total["host_s"] >= 0 and total["parked_s"] >= 0


@pytest.fixture(scope="module")
def stamped_run():
    """Four requests over two slots with the tracer on, events every
    10 ms: the decode loop's sums and ticks as a gateway's sink has them."""
    from metisfl_tpu.serving import decode

    ttrace.configure(enabled=True, service="test", dir="")
    ttrace.configure_ring(8192)
    _, cursor, _ = ttrace.spans_since(0)
    every, decode.LOOP_EVENT_EVERY_S = decode.LOOP_EVENT_EVERY_S, 0.01
    ops = _lm_ops()
    engine = decode.ContinuousBatcher(ops, 1, ops.get_variables(),
                                      slots=2, max_len=32)
    prompts = [np.arange(1, 4 + i, dtype=np.int32) for i in range(4)]
    try:
        before = engine.describe()
        futures = [engine.submit(p, 6 + i) for i, p in enumerate(prompts)]
        for fut in futures:
            fut.result(timeout=120.0)
    finally:
        engine.close()
        decode.LOOP_EVENT_EVERY_S = every
    spans = ttrace.spans_since(cursor)[0]
    ttrace.configure_ring(0)
    return {"before": before, "after": engine.describe(),
            "loops": [s for s in spans if s["name"] == "decode.loop"],
            "prompts": prompts}


def test_decode_calls_are_cut_into_launch_and_read(stamped_run):
    from metisfl_tpu.serving import decode

    new = ("step_launch_s", "step_read_s", "prefill_launch_s",
           "prefill_read_s")
    assert set(new) <= set(decode.DECODER_SUMS) <= set(decode.LOOP_SUMS)
    before, after = stamped_run["before"], stamped_run["after"]
    assert set(new) <= set(after["loop"])
    loops = [rec["attrs"] for rec in stamped_run["loops"]]
    assert loops and all(set(new) <= set(a) for a in loops)
    total = {k: sum(a[k] for a in loops) for k in decode.LOOP_SUMS}
    for key in new:
        assert total[key] == pytest.approx(
            after["loop"][key] - before["loop"][key], abs=1e-4), key
    # the two halves of a call are the call (no sync, no gap between)
    for kind in ("step", "prefill"):
        assert total[f"{kind}_launch_s"] > 0 and total[f"{kind}_read_s"] > 0
        assert total[f"{kind}_launch_s"] + total[f"{kind}_read_s"] == \
            pytest.approx(total[f"{kind}_s"], rel=0.01), kind


def test_decode_ticks_are_stamped_in_order(stamped_run):
    """The loop runs one step ahead of its reads: a tick's record holds the
    calls READ in it, a step entered in the tick before and the prefills
    entered in it. Within a call entry <= enqueued <= returned; the calls,
    tick after tick, come in entry order, which is the order they were
    dispatched in; a step launched while the last one was unread
    (``overlapped_steps``) is enqueued before the last one's tokens return;
    and the ticks' time is the sums' to the microsecond."""
    loops = [rec["attrs"] for rec in stamped_run["loops"]]
    kinds, entries, steps, last = [], [], [], None
    ticked = summed = 0.0
    for attrs in loops:
        stamps = attrs["stamps"]
        wall, perf = stamps["anchor"]
        assert wall > 1e9 and perf > 0
        assert len(stamps["ticks"]) == attrs["ticks"] > 0
        summed += sum(attrs[k] for k in ("step_s", "prefill_s", "host_s",
                                         "parked_s"))
        assert attrs["host_s"] >= 0 and attrs["parked_s"] >= 0
        for start, released, calls, end in stamps["ticks"]:
            assert all(isinstance(t, int) for t in (start, released, end))
            assert start <= released <= end
            ticked += (end - start) / 1e6
            for kind, entry, enqueued, returned in calls:
                assert all(isinstance(t, int)
                           for t in (entry, enqueued, returned))
                assert entry <= enqueued <= returned
                assert released <= returned <= end     # read in this tick
                if kind == "s":     # launched in an earlier tick
                    assert entry < start
                    steps.append((perf + enqueued / 1e6,
                                  perf + returned / 1e6))
                else:
                    assert released <= entry
                kinds.append(kind)
                entries.append(perf + entry / 1e6)
            # ticks follow one another across batches too
            at = [perf + t / 1e6 for t in (start, end)]
            assert last is None or at[0] >= last - 1e-6
            last = at[1]
    assert entries == sorted(entries)
    assert kinds.count("s") == sum(a["steps"] for a in loops)
    # admitted first come, first served: the prefills in submission order
    assert [k for k in kinds if k != "s"] == [
        f"p{p.size}" for p in stamped_run["prompts"]]
    overlapped = sum(1 for (_, returned), (enqueued, _) in zip(steps,
                                                              steps[1:])
                     if enqueued < returned)
    assert overlapped == sum(a["overlapped_steps"] for a in loops) > 0
    # the sums cut the ticks' wall time whole (stamps are whole µs)
    assert summed == pytest.approx(ticked, abs=2e-6 * len(kinds) + 1e-6
                                   * sum(a["ticks"] for a in loops))


def test_nothing_is_stamped_with_the_tracer_off(monkeypatch):
    from metisfl_tpu.serving import decode

    monkeypatch.setattr(decode, "LOOP_EVENT_EVERY_S", 0.01)
    ttrace.configure(enabled=False, service="test", dir="")
    ops = _lm_ops()
    engine = decode.ContinuousBatcher(ops, 1, ops.get_variables(),
                                      slots=2, max_len=32)
    try:
        engine.submit(np.arange(1, 5, dtype=np.int32), 5).result(
            timeout=120.0)
        assert engine._ticks == []
    finally:
        engine.close()
        ttrace.configure(enabled=True, service="test", dir="")
    assert engine._ticks == []
    # the sums are the loop's own and run all the same
    loop = engine.describe()["loop"]
    assert loop["steps"] > 0 and loop["step_launch_s"] > 0


def _plane_from(calls, offset, lags):
    """A device plane of one operation a call, one after another as a
    device runs them: from the call's enqueue (or the last run's end, if
    later) to its return less ``lags[i]`` of what is left, shifted by
    ``offset``."""
    modules, free = [], float("-inf")
    for (kind, _, enqueued, returned), lag in zip(calls, lags):
        name = "jit_decode_step(7)" if kind == "s" else "jit_decode_prefill(9)"
        start = max(enqueued + offset, free)
        free = returned + offset - lag * (returned + offset - start)
        modules.append((start, free, name))
    return {"name": "/device:TPU:0", "modules": modules,
            "ops": [(s, e, "%fusion.1") for s, e, _ in modules]}


def _idle_of(runs, window):
    """Idle of ``runs`` (sorted, apart) in ``[a, b]`` clipped to the
    window: the plain sum the reader's prefix sums stand for."""
    def idle(a, b):
        a, b = max(a, 0.0), min(b, window)
        if b <= a:
            return 0.0
        return (b - a) - sum(max(0.0, min(b, e) - max(a, s))
                             for s, e, _ in runs)
    return idle


def test_ticks_reader_recovers_offset_and_idle_of_a_made_plane(stamped_run):
    """The reader over the stamps of a real pipelined run and a plane whose
    programs run one after another inside their calls: the offset back,
    and each phase the idle of its intervals, summed call by call."""
    from benchmark.metrics import _ticks

    calls = _ticks.host_calls(stamped_run["loops"])
    loops = [rec["attrs"] for rec in stamped_run["loops"]]
    assert [c[0] for c in calls].count("s") == sum(a["steps"]
                                                   for a in loops)
    assert [c[0] for c in calls].count("p") == 4
    ms = 1e6
    offset = 5 * ms - calls[0][1]        # the first entry 5 ms in
    # every second program ends half its time before the tokens come back
    lags = [0.5 if i % 2 else 0.0 for i in range(len(calls))]
    plane = _plane_from(calls, offset, lags)
    runs = plane["modules"]
    window = calls[-1][3] + offset + 5 * ms
    got = _ticks.split(calls, plane, window)
    assert got["offset_ns"] == pytest.approx(offset, abs=1.0)
    idle = _idle_of(runs, window)
    shifted = [(e + offset, r + offset) for _, e, _, r in calls]
    launch = sum(idle(e, s) for (e, _), (s, _, _) in zip(shifted, runs))
    read = sum(idle(max(d, e), r)
               for (e, r), (_, d, _) in zip(shifted, runs))
    loop = sum(idle(a[1], b[0]) for a, b in zip(shifted, shifted[1:]))
    assert got["launch_s"] == pytest.approx(launch / 1e9, abs=1e-9)
    assert got["read_s"] == pytest.approx(read / 1e9, abs=1e-9)
    assert got["loop_s"] == pytest.approx(loop / 1e9, abs=1e-9)
    assert got["read_s"] > 0 and got["launch_s"] > 0
    # the 5 ms before the first call and after the last are nobody's
    busy = sum(e - s for s, e, _ in runs)
    assert got["idle_s"] == pytest.approx((window - busy) / 1e9, abs=1e-9)
    assert got["idle_s"] >= 0.010 - 1e-9
    assert got["step_runs"] == sum(a["steps"] for a in loops)
    # a window that cuts the calls short cuts their idle with them
    half = _ticks.split(calls, plane, window / 2)
    assert half["launch_s"] + half["read_s"] + half["loop_s"] < \
        got["launch_s"] + got["read_s"] + got["loop_s"]
    assert half["step_runs"] < got["step_runs"]


def test_ticks_reader_counts_overlapped_calls_idle_twice():
    """Two steps, the second launched before the first is read (ms): step
    1 entry 0, enqueued 1, returned 10, runs 1.5-6; step 2 entry 2,
    enqueued 3, returned 12, runs 8-12. The idle 6-8 lies in step 1's read
    and in step 2's launch, and the reader counts it in both: under
    overlap the phases bound the idle rather than cut it."""
    from benchmark.metrics import _ticks

    ms = 1e6
    calls = [("s", 0.0, 1 * ms, 10 * ms), ("s", 2 * ms, 3 * ms, 12 * ms)]
    runs = [(1.5 * ms, 6 * ms, "jit_decode_step(7)"),
            (8 * ms, 12 * ms, "jit_decode_step(7)")]
    plane = {"name": "/device:TPU:0", "modules": runs,
             "ops": [(s, e, "%fusion.1") for s, e, _ in runs]}
    got = _ticks.split(calls, plane, 20 * ms)
    assert got["offset_ns"] == 0.0       # step 2's tokens back at its end
    assert got["launch_s"] == pytest.approx(3.5e-3)    # 0-1.5, then 6-8
    assert got["read_s"] == pytest.approx(2e-3)        # 6-8, and 12-12
    assert got["loop_s"] == 0.0          # step 2 entered before 1 returned
    assert got["idle_s"] == pytest.approx(11.5e-3)     # 0-1.5, 6-8, 12-20
    named = got["launch_s"] + got["read_s"] + got["loop_s"]
    assert named == pytest.approx(3.5e-3 + 2e-3)       # 6-8 twice
    assert named > 1.5e-3 + 2e-3         # the idle up to the last return
    assert got["step_runs"] == 2


def test_ticks_reader_reads_nothing_it_cannot_line_up(stamped_run,
                                                       tmp_path):
    from benchmark.metrics import _ticks

    calls = _ticks.host_calls(stamped_run["loops"])
    plane = _plane_from(calls, 0.0, [0.0] * len(calls))
    # a kind sequence the host never ran: five prefills in a row
    prefills = {**plane, "modules": [(0.0, 1.0, "jit_decode_prefill(9)")] * 5}
    assert _ticks.split(calls, prefills, 1e12) is None
    # the right kinds, each run 1 ms long and 2 ms after the last, whatever
    # the host did: the runs do not fall inside their calls
    ms = 1e6
    even = {**plane, "modules": [(2 * ms * i, 2 * ms * i + ms, n)
                                 for i, (_, _, n) in enumerate(
                                     plane["modules"])]}
    assert _ticks.split(calls, even, 1e12) is None
    # no trace (off the chip), and no stamps (a program that does not
    # stamp its ticks): nothing, and nothing raised
    assert _ticks.read({"trace": None, "telemetry_dir": str(tmp_path)}) \
        is None
    traced = {"trace": {"window_s": 1.0, "busy_s": 0.5},
              "telemetry_dir": str(tmp_path / "telemetry"),
              "window": (0.0, 1e12)}
    (tmp_path / "telemetry").mkdir()
    (tmp_path / "telemetry" / "serving-1.jsonl").write_text(json.dumps(
        {"name": "decode.loop", "start": 5.0, "dur_ms": 1.0,
         "attrs": {"steps": 3, "step_s": 0.1}}) + "\n")
    assert _ticks.read(traced) is None
    for name in ("decode_gap_read_ms", "decode_gap_loop_ms",
                 "decode_gap_launch_ms", "decode_gap_named_share"):
        from benchmark.lib import spec
        assert spec.metric_reader(name).read(traced) is None, name


# --------------------------------------------------------------------- #
# programs named by layer
# --------------------------------------------------------------------- #

def test_monitored_jit_names_the_program_by_its_label():
    import jax.numpy as jnp

    from metisfl_tpu.telemetry import runtime

    def run(state, x, *, scale=1.0):
        return state + x * scale

    fn = runtime.monitored_jit(run, name="a.b", donate_argnums=(0,),
                               static_argnames=("scale",))
    lowered = fn.__wrapped__.lower(jnp.ones(3), jnp.ones(3), scale=2.0)
    assert "module @jit_a_b " in lowered.as_text()
    np.testing.assert_allclose(fn(jnp.ones(3), jnp.ones(3), scale=2.0), 3.0)
    assert run.__name__ == "run"        # the caller's function is not renamed
    # without a label the function's own name stands
    plain = runtime.monitored_jit(run)
    assert "module @jit_run " in plain.__wrapped__.lower(
        jnp.ones(3), jnp.ones(3)).as_text()


# --------------------------------------------------------------------- #
# the benchmark's readers, on hand-made contexts
# --------------------------------------------------------------------- #

def _round(wait_ms, task):
    entry = {"device": {"ms_per_step": 10.0}}
    if task is not None:
        entry["task"] = task
    return {"profile": {"phases": {"wait_uplinks": wait_ms},
                        "learners": {"L0": entry}}}


def _task(scale):
    return {"start": 1.0, "queued": 1.0, "load": 10.0 * scale,
            "upload": 20.0 * scale, "feed": 2.0 * scale,
            "steps": 80.0, "readback": 30.0 * scale, "snapshot": 0.0,
            "encode": 4.0 * scale, "other": 1.0}


def _round_ctx():
    return {"learner": "L0",
            "traffic": {"shape": {"local_steps": 8}},
            "rounds": [_round(200.0, _task(1.0)), _round(280.0, _task(2.0)),
                       _round(999.0, None)]}


def _serve_ctx(tmp_path):
    records = [
        {"name": "decode.slot", "start": 100.0, "dur_ms": 2000.0,
         "attrs": {"wait_ms": 900.0, "prefill_ms": 30.0}},
        {"name": "decode.slot", "start": 101.0, "dur_ms": 2000.0,
         "attrs": {"wait_ms": 1100.0, "prefill_ms": 50.0}},
        # ended after the window, and one from before this PR's program
        {"name": "decode.slot", "start": 140.0, "dur_ms": 20000.0,
         "attrs": {"wait_ms": 5.0, "prefill_ms": 5.0}},
        {"name": "decode.slot", "start": 102.0, "dur_ms": 1000.0,
         "attrs": {"tokens": 3}},
        {"name": "decode.loop", "start": 100.0, "dur_ms": 1000.0,
         "attrs": {"step_s": 0.6, "prefill_s": 0.1, "host_s": 0.3,
                   "parked_s": 5.0, "steps": 30, "step_launch_s": 0.15}},
        {"name": "decode.loop", "start": 101.0, "dur_ms": 1000.0,
         "attrs": {"step_s": 0.7, "prefill_s": 0.2, "host_s": 0.1,
                   "parked_s": 0.0, "steps": 32, "step_launch_s": 0.17}},
        {"name": "decode.loop", "start": 10.0, "dur_ms": 1000.0,
         "attrs": {"step_s": 0.0, "prefill_s": 0.0, "host_s": 9.0}},
    ]
    sink = tmp_path / "serving-1.jsonl"
    sink.write_text("torn line\n" + "".join(json.dumps(r) + "\n"
                                            for r in records))
    return {"telemetry_dir": str(tmp_path), "window": (99.0, 150.0)}


READERS = [
    ("load_ms", "round", 15.0),
    ("upload_ms", "round", 30.0),
    ("feed_ms", "round", 3.0),
    ("readback_ms", "round", 45.0),
    ("encode_ms", "round", 6.0),
    # wait_uplinks less the tiles' sum: 200 - 148 and 280 - 214
    ("report_ms", "round", 59.0),
    ("admit_wait_ms", "serve", 1000.0),
    ("prefill_ms", "serve", 40.0),
    ("decode_host_share", "serve", 20.0),
    # the step calls' launch: 0.32 s over 62 steps
    ("decode_launch_ms", "serve", 320.0 / 62),
]


@pytest.mark.parametrize("name,kind,value", READERS,
                         ids=[r[0] for r in READERS])
def test_benchmark_reader_reads_a_value_and_nothing_from_nothing(
        name, kind, value, tmp_path):
    from benchmark.lib import spec

    reader = spec.metric_reader(name)
    ctx = _round_ctx() if kind == "round" else _serve_ctx(tmp_path)
    assert reader.read(ctx) == pytest.approx(value)
    # a program without the waterfall or the events (the parent commit)
    empty = tmp_path / "empty"
    empty.mkdir()
    for nothing in ({}, {"learner": "L0", "rounds": [_round(5.0, None)],
                         "telemetry_dir": str(empty),
                         "window": (0.0, 1e12)}):
        assert reader.read(nothing) is None


def test_every_new_reader_is_declared_in_the_benchmark():
    from benchmark.lib import spec

    declared = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    for name, kind, _ in READERS:
        entry = declared[name]
        assert entry["better"] == "lower"
        cells = entry["workloads"]
        assert all(("round" in c) == (kind == "round") for c in cells), name
