#!/usr/bin/env bash
# Host-device overlap A/B (parallel/overlap.py): runs the config-5-shaped
# GAME coordinate-descent step with overlap OFF vs ON (bench.py
# --overlap-ab: deferred readbacks, prefetched host prep, async IO,
# pipelined streaming populate) and asserts the measured speedup plus the
# readback discipline and the streaming-populate wall bound.
#
# The speedup gate is host-class-aware, because the costs overlap removes
# are ASYNC-DEVICE latencies (a synchronous readback per bank update and
# host gaps between dispatches, while the device sits idle):
#   - accelerator attached -> the GAME step must be >= 1.15x faster
#     (PHOTON_OVERLAP_MIN_SPEEDUP overrides);
#   - single-core CPU-only host
#     -> compute/compute overlap is physically unavailable; the gate is
#     PARITY (overlap must not lose more than 5%) and the populate wall
#     must stay within the decode+consume sum bound. The >= 1.15x claim
#     is then carried by the next chip-attached round's BENCH artifact.
# Readback discipline is asserted unconditionally: 1 batched readback per
# CD iteration with overlap on, strictly more with it off.
set -euo pipefail
cd "$(dirname "$0")/.."

OUT=$(mktemp -t photon-overlap-XXXXXX.json)
trap 'rm -f "$OUT"' EXIT

python bench.py --overlap-ab ${PHOTON_OVERLAP_FULL:+--full} | tail -1 > "$OUT"

python - "$OUT" <<'EOF'
import json, os, sys

r = json.load(open(sys.argv[1]))
d = r["detail"]
game = d["game_step"]
pop = d["streaming_populate"]
cpu_only_single_core = (d["host"]["cpu_count"] or 1) <= 1
print(json.dumps(r, indent=2))

# -- readback discipline (host-class independent) -----------------------
assert game["readbacks_per_step_on"] == 1, game
assert game["readbacks_per_step_off"] > 1, game

# -- GAME step speedup gate --------------------------------------------
default_gate = "0.95" if cpu_only_single_core else "1.15"
gate = float(os.environ.get("PHOTON_OVERLAP_MIN_SPEEDUP", default_gate))
sp = game["speedup"]
kind = "parity" if cpu_only_single_core else "speedup"
print(f"GAME CD step: off {game['step_s_overlap_off']}s -> "
      f"on {game['step_s_overlap_on']}s ({sp}x; {kind} gate >= {gate}x)")
assert sp >= gate, f"overlap speedup {sp}x below the {gate}x gate"

# -- streaming populate wall bound -------------------------------------
wall = pop["cold_populate_wall_s_pipelined"]
serial = pop["cold_populate_wall_s_serial"]
if cpu_only_single_core:
    # one core: decode cannot hide under consume, so the wall bound is
    # unattainable by physics; the gate is NO REGRESSION vs the serial
    # populate (the sum/max bound booleans stay recorded for the chip
    # rounds). 15%+50ms slack absorbs 1-core scheduler noise.
    assert wall <= serial * 1.15 + 0.05, pop
    print(f"populate wall {wall}s vs serial {serial}s "
          f"[single-core host: no-regression gate]")
else:
    assert pop["wall_within_max_bound"], pop
    print(f"populate wall {wall}s within max(decode, consume) bound "
          f"({pop['bound_max_decode_consume_s']}s)")
print("OK: overlap A/B gates passed")
EOF
