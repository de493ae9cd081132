#!/usr/bin/env bash
# CI churn-tolerance gate (ISSUE 9 satellite; docs/RESILIENCE.md
# "Cross-device churn").
#
# Runs the seeded cross-device churn scenario — 1024 virtual clients,
# per-round sampling at quorum, 30% per-round dropout plus one flapping
# and one partitioned learner — AND the no-churn same-seed control, then
# fails the build when any round fails to complete or the final accuracy
# drifts past the tolerance from the control run. Deterministic fault
# schedule (fixed seed), finishes in well under 60 s on one CPU core.
#
# ISSUE 10 additions, gated in the same run:
#  - SLO alert lifecycle (--alert-smoke): the partition fault must trip
#    the dispatch_retries_total rate rule AND the drained run must
#    resolve it (firing -> resolved, end to end), while the same-seed
#    no-churn control stays silent — alerting that cannot fire, or
#    cannot resolve, fails the build;
#  - cardinality budget (--budget 256): the run's per-learner metric
#    families serve sketches past the budget, proving the exposition
#    stays bounded under churn.
#
# Usage:
#   scripts/chaos_smoke.sh                  # the pinned CI scenario
#   scripts/chaos_smoke.sh --clients 256    # any crossdevice CLI override
#
# Exit codes: 0 all rounds completed within tolerance and the alert
# lifecycle proved out, 1 a round failed / halted / accuracy drifted /
# alert did not fire+resolve (or fired in the control), 2 harness
# crashed (fails the build too).
set -u -o pipefail

PYTHON="${PYTHON:-python}"

# CPU-pinned and time-bounded: the harness measures scheduling, not
# accelerator math, and a wedged run must fail, not hang the build.
JAX_PLATFORMS=cpu timeout -k 10 120 "$PYTHON" -m metisfl_tpu.driver.crossdevice \
  --clients 1024 --rounds 5 --quorum 12 --dropout 0.3 --seed 7 \
  --tolerance 0.2 --budget 256 --alert-smoke "$@"
rc=$?
case "$rc" in
  0) echo "chaos_smoke: PASS (all rounds completed at quorum, accuracy" \
          "within tolerance of the no-churn control, alert fired and" \
          "resolved under churn and stayed silent in the control)" ;;
  1) echo "chaos_smoke: FAIL — a round failed/halted, accuracy drifted" \
          "past tolerance, or the alert lifecycle did not prove out" \
          "(see JSON above)" >&2 ;;
  *) echo "chaos_smoke: FAIL — harness crashed or timed out (rc=$rc)" >&2
     rc=2 ;;
esac
[ "$rc" -eq 0 ] || exit "$rc"

# ISSUE 12 slice-kill gate (docs/RESILIENCE.md "Distributed slice
# aggregators"): three real slice-aggregator subprocesses over gRPC, one
# SIGKILLed mid-round. The build fails unless the round completes
# without operator action, slice_rehomed fires (and stays silent in the
# control), and the community model is BIT-IDENTICAL to the same-seed
# undisturbed run.
JAX_PLATFORMS=cpu timeout -k 10 180 "$PYTHON" -m metisfl_tpu.driver.crossdevice \
  --slice-smoke --slices 3 --seed 7
rc=$?
case "$rc" in
  0) echo "chaos_smoke: slice-kill PASS (aggregator killed mid-round," \
          "slice re-homed, round completed, community model bit-identical" \
          "to the no-kill control)" ;;
  1) echo "chaos_smoke: slice-kill FAIL — re-homing did not complete the" \
          "round or the community model diverged from the control (see" \
          "JSON above)" >&2 ;;
  *) echo "chaos_smoke: slice-kill FAIL — smoke crashed or timed out" \
          "(rc=$rc)" >&2
     rc=2 ;;
esac
[ "$rc" -eq 0 ] || exit "$rc"

# ISSUE 20 secure-aggregation gate (docs/SECURITY.md "Secure
# aggregation at scale"): a real-gRPC federation under scheme=masking
# composed with distributed slice aggregators AND streaming
# fold-on-arrival, one learner SIGKILLed with its masked uplink in the
# air. The build fails unless every round completes via dropout
# settlement (seed-share disclosure from a survivor), the masks cancel
# (each round-pinned community equals the same-seed PLAIN control run
# within the fixed-point tolerance), and the control emits zero
# secure_* events.
JAX_PLATFORMS=cpu timeout -k 10 420 "$PYTHON" -m metisfl_tpu.driver.crossdevice \
  --secure-smoke --seed 7 --timeout 150
rc=$?
case "$rc" in
  0) echo "chaos_smoke: secure-agg PASS (learner SIGKILLed mid-uplink," \
          "round settled via mask recovery, community equals the plain" \
          "control within fixed-point tolerance, control secure-silent)" ;;
  1) echo "chaos_smoke: secure-agg FAIL — a round did not settle, masks" \
          "failed to cancel against the plain control, or the control" \
          "emitted secure events (see JSON above)" >&2 ;;
  *) echo "chaos_smoke: secure-agg FAIL — smoke crashed or timed out" \
          "(rc=$rc)" >&2
     rc=2 ;;
esac
[ "$rc" -eq 0 ] || exit "$rc"

# ISSUE 11 fleet-tail gate (docs/OBSERVABILITY.md "Fleet fabric"): a
# three-peer real-gRPC fleet with one flapping learner — the collector
# must keep assembling the merged view while the peer is down (stale
# marked, collection never raises, the peer recovers on relaunch) and
# the mean incremental poll must stay under the pinned 400 ms bound.
JAX_PLATFORMS=cpu timeout -k 10 60 "$PYTHON" -m metisfl_tpu.telemetry \
  --fabric-smoke --budget-ms 400
rc=$?
case "$rc" in
  0) echo "chaos_smoke: fleet-tail PASS (stale marked + recovered under" \
          "flap, merged view never dropped, poll overhead within bound)" ;;
  1) echo "chaos_smoke: fleet-tail FAIL — the collector dropped the" \
          "merged view under flap or blew the poll budget (see JSON" \
          "above)" >&2 ;;
  *) echo "chaos_smoke: fleet-tail FAIL — smoke crashed or timed out" \
          "(rc=$rc)" >&2
     rc=2 ;;
esac
[ "$rc" -eq 0 ] || exit "$rc"

# ISSUE 15 serving-fleet replica-kill gate (docs/DEPLOYMENT.md "Serving
# fleet"): three real gateway-replica subprocesses over gRPC behind the
# consistent-hash router, live canary traffic, one replica SIGKILLed
# mid-canary. The build fails unless ZERO requests drop (the router
# drains around the corpse with bounded retry to the next hash owner),
# the router marks the replica dead, every key's replies stay on one
# canary channel, the surviving replicas roll to the mid-run promotion,
# and the relaunched replica re-pins to the promoted version.
JAX_PLATFORMS=cpu timeout -k 10 180 "$PYTHON" -m metisfl_tpu.serving \
  --fleet-smoke --smoke-replicas 3
rc=$?
case "$rc" in
  0) echo "chaos_smoke: replica-kill PASS (replica SIGKILLed mid-canary," \
          "zero requests dropped, router drained around it, channels" \
          "stayed coherent, relaunch re-pinned to the promoted version)" ;;
  1) echo "chaos_smoke: replica-kill FAIL — requests dropped, channels" \
          "mixed, or the relaunch did not re-pin (see JSON above)" >&2 ;;
  *) echo "chaos_smoke: replica-kill FAIL — smoke crashed or timed out" \
          "(rc=$rc)" >&2
     rc=2 ;;
esac
[ "$rc" -eq 0 ] || exit "$rc"

# ISSUE 13 continuous-profiling overhead gate (docs/OBSERVABILITY.md
# "Continuous profiling"): the bench round loop with the sampler (67 Hz
# default) + instrumented locks ON vs OFF, interleaved trials, minima
# judged. The build fails when profiling costs more than the pinned 3%
# bound, when the sampler collects nothing, or when the fold kernel's
# frame never appears in the profile (a blind profiler gates nothing).
JAX_PLATFORMS=cpu timeout -k 10 120 "$PYTHON" -m metisfl_tpu.telemetry \
  --prof-smoke --bound-pct 3
rc=$?
case "$rc" in
  0) echo "chaos_smoke: prof-overhead PASS (sampler + lock telemetry" \
          "within the 3% bound, hot frames visible in the profile)" ;;
  1) echo "chaos_smoke: prof-overhead FAIL — profiling overhead past the" \
          "bound or the sampler ran blind (see JSON above)" >&2 ;;
  *) echo "chaos_smoke: prof-overhead FAIL — smoke crashed or timed out" \
          "(rc=$rc)" >&2
     rc=2 ;;
esac
[ "$rc" -eq 0 ] || exit "$rc"

# ISSUE 16 causal-tracing gate (docs/OBSERVABILITY.md "Causal tracing"):
# two same-seed synthetic rounds — one with a slowed learner, one
# control — walked by the critical-path analyzer. The build fails when
# the slow run's dominant edge is not the slowed learner's train span,
# when the control attributes a dominant learner at all, when chain
# coverage drops under 90% of round wall-clock, when the orphan lint
# trips outside the spans_lost budget, or when per-RPC context
# propagation costs more than the pinned 50 µs.
JAX_PLATFORMS=cpu timeout -k 10 60 "$PYTHON" -m metisfl_tpu.telemetry \
  --causal-smoke --overhead-budget-ns 50000
rc=$?
case "$rc" in
  0) echo "chaos_smoke: causal-trace PASS (slowed learner named dominant" \
          "edge, control unattributed, chain coverage >= 90%, no orphan" \
          "spans, propagation overhead within budget)" ;;
  1) echo "chaos_smoke: causal-trace FAIL — wrong/missing dominant edge," \
          "coverage or orphan lint failed, or propagation overhead past" \
          "budget (see JSON above)" >&2 ;;
  *) echo "chaos_smoke: causal-trace FAIL — smoke crashed or timed out" \
          "(rc=$rc)" >&2
     rc=2 ;;
esac
[ "$rc" -eq 0 ] || exit "$rc"

# ISSUE 19 accelerator-runtime gate (docs/OBSERVABILITY.md "Runtime
# observability"): the bench round loop plus a continuous-batching
# decode burst under the XLA compile listener. The build fails when any
# steady-state (post-warmup) compile fires on either path, when a
# deliberately shape-shifting control run does NOT trip the recompile
# detector (+ its storm event), or when the monitored_jit wrapper costs
# more than the pinned 50 µs per steady-state call.
JAX_PLATFORMS=cpu timeout -k 10 240 "$PYTHON" -m metisfl_tpu.telemetry \
  --runtime-smoke --overhead-budget-ns 50000
rc=$?
case "$rc" in
  0) echo "chaos_smoke: runtime PASS (zero steady-state compiles on the" \
          "round + decode paths, the recompile detector provably fires," \
          "wrapper overhead within budget)" ;;
  1) echo "chaos_smoke: runtime FAIL — a steady-state recompile, a blind" \
          "detector, or wrapper overhead past budget (see JSON above)" >&2 ;;
  *) echo "chaos_smoke: runtime FAIL — smoke crashed or timed out" \
          "(rc=$rc)" >&2
     rc=2 ;;
esac
[ "$rc" -eq 0 ] || exit "$rc"

# ISSUE 17 controller-kill gate (docs/RESILIENCE.md "Controller
# hot-standby"): a real-gRPC federation with a warm --standby tailing
# the round-state WAL; the seeded injector SIGKILLs the controller on
# its first MarkTaskCompleted — mid-round, with uplinks in the air. The
# build fails unless the standby promotes itself (controller_failover
# fired from BOTH the promoted process and the driver's handoff), every
# round completes without operator action, the same-seed undisturbed
# control run stays failover-silent, and each round's community model
# is bit-identical between the two runs.
JAX_PLATFORMS=cpu timeout -k 10 420 "$PYTHON" -m metisfl_tpu.driver.crossdevice \
  --controller-smoke --rounds 3 --seed 7 --timeout 240
rc=$?
case "$rc" in
  0) echo "chaos_smoke: controller-kill PASS (standby promoted, failover" \
          "events from both roles, all rounds completed, community model" \
          "bit-identical to the undisturbed control)" ;;
  1) echo "chaos_smoke: controller-kill FAIL — no promotion, missing" \
          "failover events, a noisy control run, or a bit-level model" \
          "divergence (see JSON above)" >&2 ;;
  *) echo "chaos_smoke: controller-kill FAIL — smoke crashed or timed" \
          "out (rc=$rc)" >&2
     rc=2 ;;
esac
exit "$rc"
