#!/usr/bin/env bash
# Chaos harness (round 11, reliability layer; serving-under-fire arms
# added round 13): a tier-1-sized fault matrix — one injected fault per
# seam class (chunk read, spill write/read, cache load/store,
# checkpoint save, async IO worker, serving model-load/frontend-read/
# dispatch) — driven end-to-end through the GLM, GAME and serving
# drivers (replay, stdin deadline mix, the TCP front-end under
# flood + mid-flood swap + SIGTERM drain, and the shard-routed
# scatter/gather fleet under flood + two-step flip + SIGKILL),
# asserting:
#
#   1. every faulted run COMPLETES (transient faults retry; corrupt
#      cache artifacts quarantine to *.corrupt and rebuild);
#   2. faulted runs are BITWISE equal to their fault-free twins
#      (models-text, model containers, objective histories);
#   3. every injected fault / retry / quarantine is ACCOUNTED in the
#      run's metrics.json reliability block;
#   4. (ISSUE 13) every fleet process's FLIGHT RECORDER captured the
#      injected sequence in order — the SIGKILLed shard's auto-dumped
#      ring survives the kill showing stage->commit, and
#      check_conservation() (admitted == named terminal outcomes)
#      holds across the mid-flood generation swap (arm 14's obs leg).
#
# CPU-only by design (JAX_PLATFORMS=cpu in the matrix): the seams under
# test are host-side IO; chip rounds inherit the same code path.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== chaos matrix (fault injection x both drivers) =="
python dev-scripts/chaos_matrix.py

echo "== interleaving matrix (deterministic schedules, ISSUE 11) =="
# the runtime twin of lint rules PL008-PL010: >=200 seeded cooperative
# schedules over submit/close/swap/rollback on the REAL serving/
# registry thread plane — every submitted request reaches exactly one
# terminal outcome, generations stay monotonic under concurrent swaps,
# at most one rollback per health regression, zero deadlocks. Failures
# name their seed; replay with InterleaveScheduler(seed=<seed>).
python dev-scripts/interleave_matrix.py --schedules "${PHOTON_INTERLEAVE_SCHEDULES:-200}"

echo "chaos: PASS"
