#!/usr/bin/env bash
# Out-of-core GAME coordinate-descent A/B (game/streaming.py): runs the
# streamed CD vs the in-memory CD on the same synthetic Avro files
# (bench.py --streaming-game) and gates the result.
#
# Host-class-aware gates, because what streaming trades is HOST work
# (per-pass Avro decode + python staging) that a multi-core host hides
# behind the solves but a single core pays serially:
#   - multi-core host -> streamed throughput must be >= 0.8x the
#     in-memory fit (PHOTON_STREAM_GAME_MIN_RATIO overrides);
#   - single-core CPU container ->
#     the gate is PARITY: the streamed objective must match the
#     in-memory objective (rel diff < 1e-3) — the machinery is correct
#     and the throughput claim is carried by the next multi-core round.
# The RSS assertion runs unconditionally: the streamed fit's RSS
# high-water delta must stay in the budget + interpreter/XLA slack
# class, NOT the dataset class (the strict subprocess-isolated bound is
# pinned in tests/test_streaming_game.py::TestStreamingGameBoundedMemory).
set -euo pipefail
cd "$(dirname "$0")/.."

OUT=$(mktemp -t photon-stream-game-XXXXXX.json)
trap 'rm -f "$OUT"' EXIT

python bench.py --streaming-game | tail -1 > "$OUT"

python - "$OUT" <<'EOF'
import json, os, sys

r = json.load(open(sys.argv[1]))
d = r["detail"]
print(json.dumps(r, indent=2))

assert d["num_chunks"] >= 3, f"A/B must stream >= 3 chunks: {d['num_chunks']}"

# -- objective parity (host-class independent) --------------------------
assert d["objective_rel_diff"] < 1e-3, d["objective_rel_diff"]

# -- RSS bound ----------------------------------------------------------
slack = 192 << 20  # interpreter + jit compile + model class
budget = d["memory_budget_bytes"]
assert d["rss_delta_bytes"] < budget + slack, (
    f"RSS delta {d['rss_delta_bytes']} exceeds budget {budget} + slack"
)
print(f"RSS delta {d['rss_delta_bytes'] >> 20} MiB within "
      f"budget {budget >> 20} MiB + {slack >> 20} MiB slack")

# -- throughput gate ----------------------------------------------------
single_core = (d["host"]["cpu_count"] or 1) <= 1
if single_core:
    print(f"single-core host: throughput ratio {d['throughput_ratio']}x "
          "recorded (parity gate only; >= 0.8x gate applies on "
          "multi-core hosts)")
else:
    gate = float(os.environ.get("PHOTON_STREAM_GAME_MIN_RATIO", "0.8"))
    ratio = d["throughput_ratio"]
    print(f"streamed {d['examples_per_s']} ex/s vs in-memory "
          f"{d['in_memory_examples_per_s']} ex/s ({ratio}x; gate >= {gate}x)")
    assert ratio >= gate, f"throughput ratio {ratio}x below {gate}x"

print("bench_streaming_game: PASS")
EOF
