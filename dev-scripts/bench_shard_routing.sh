#!/usr/bin/env bash
# Planet-scale serving bench (photon_ml_tpu/serving/routing, ISSUE 12):
# runs bench.py --shard-routing — the scatter/gather router over REAL
# shard-server subprocesses at N in {1, 2, 4}, flooded with a zipf
# (head-skewed) open-loop replay, plus a SIGKILL-one-shard leg — and
# gates the routing contract.
#
# Host-class-aware gates:
#   - EVERYWHERE (the routing contract is host-independent):
#       * every submitted request reached exactly one terminal outcome
#         in EVERY fleet (terminal == submitted) — zero hangs, and the
#         kill leg too;
#       * per-request fan-out p99 bounded
#         (<= PHOTON_ROUTING_MAX_P99_MS; default 250 ms: the shard
#         fleet always runs on the CPU);
#       * hot-entity cache hit rate > 0 under the zipf replay (head
#         traffic MUST be absorbed; a zero rate means the cache plane
#         is dead);
#       * 0 request-path lowerings per shard-server
#         (cold_dispatch_compiles == 0 on every shard that drained);
#       * kill leg: the SIGKILLed shard's entities DEGRADE (FE-only,
#         counted > 0) with zero request errors — one dead shard is
#         never an outage;
#   - SCALING gate (aggregate QPS at N=4 >= PHOTON_ROUTING_MIN_SCALING
#     x the N=1 fleet, default 2.0): applied only when the host can
#     actually run 4 scorer processes concurrently (cpu_count >= 8)
#     — on a 1-core container all fleets share one core
#     and the ratio is RECORDED, not gated.
set -euo pipefail
cd "$(dirname "$0")/.."

OUT=$(mktemp -t photon-shard-routing-XXXXXX.json)
trap 'rm -f "$OUT"' EXIT

python bench.py --shard-routing | tail -1 > "$OUT"

python - "$OUT" <<'EOF'
import json, os, sys

r = json.load(open(sys.argv[1]))
d = r["detail"]
print(json.dumps(r, indent=2))

host = d["host"]

# -- exactly one terminal outcome per submitted request, every fleet ----
for n, f in sorted(d["fleets"].items()):
    assert f["terminal"] == f["submitted"], (n, f["terminal"], f["submitted"])
    errs = {k: v for k, v in f["outcomes"].items() if k.startswith("error")}
    assert not errs, (n, errs)
    print(f"fleet N={n}: {f['submitted']} submitted -> {f['terminal']} "
          f"terminal, qps {f['qps']}, fanout p99 {f['fanout_p99_ms']}ms, "
          f"cache hit rate {f['cache_hit_rate']}")

# -- fan-out latency stays bounded --------------------------------------
default_p99 = 250.0  # the shard fleet always runs on the CPU
max_p99 = float(os.environ.get("PHOTON_ROUTING_MAX_P99_MS", default_p99))
for n, f in sorted(d["fleets"].items()):
    p99 = f["fanout_p99_ms"]
    assert p99 is not None and p99 <= max_p99, (
        f"fleet N={n}: fan-out p99 {p99}ms above {max_p99}ms"
    )
print(f"latency OK: every fleet's fan-out p99 <= {max_p99}ms")

# -- the hot-entity cache absorbs head traffic --------------------------
for n, f in sorted(d["fleets"].items()):
    assert f["cache_hit_rate"] > 0, (
        f"fleet N={n}: zero cache hits under a zipf replay — the "
        "hot-entity cache is not engaging"
    )
print("cache OK: hit rate > 0 under zipf replay in every fleet")

# -- fixed-shape contract per shard -------------------------------------
for n, f in sorted(d["fleets"].items()):
    for s in f["shards"]:
        assert s["cold_dispatch_compiles"] == 0, (n, s)
        assert s["dispatches"] > 0, (n, s)
print("contract OK: 0 request-path lowerings on every drained shard")

# -- one dead shard degrades, never an outage ---------------------------
k = d["kill_leg"]
assert k is not None, "kill leg missing"
assert k["terminal"] == k["submitted"], (k["terminal"], k["submitted"])
assert k["degraded"] > 0, (
    "SIGKILLed shard produced zero degraded outcomes — degradation is "
    "not engaging"
)
assert k["errors"] == 0, k
print(f"degradation OK: shard {k['killed_shard']} SIGKILLed -> "
      f"{k['degraded']} FE-only degraded, 0 errors, "
      f"{k['terminal']}/{k['submitted']} terminal")

# -- aggregate QPS scales with shard count (multi-core only) -------------
min_scaling = float(os.environ.get("PHOTON_ROUTING_MIN_SCALING", "2.0"))
scaling = d["scaling_4_over_1"]
can_gate = (host["cpu_count"] or 1) >= 8
if can_gate:
    assert scaling >= min_scaling, (
        f"aggregate QPS at N=4 only {scaling}x the N=1 fleet "
        f"(gate {min_scaling}x)"
    )
    print(f"scaling OK: N=4 / N=1 = {scaling}x >= {min_scaling}x")
else:
    print(f"scaling recorded (not gated on {host['cpu_count']}-core "
          f"host): N=4 / N=1 = {scaling}x, N=2 / N=1 = "
          f"{d['scaling_2_over_1']}x")

print("bench_shard_routing: PASS")
EOF
