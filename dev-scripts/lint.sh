#!/usr/bin/env bash
# Static gates, runnable anywhere the package runs:
#   1. photon-lint — the project-specific JAX hot-path invariants
#      (readback seam, recompile hazards, spill/IO hygiene) PLUS the
#      whole-package concurrency pass (PL008 unguarded-shared-state,
#      PL009 lock-order-inversion, PL010 atomicity-hygiene) AND the
#      whole-package SPMD pass (PL011 mesh-axis-discipline, PL012
#      sharded-bank-host-gather, PL013 reduction-completeness, PL014
#      donation-hygiene) AND the whole-package determinism pass
#      (PL015 unordered-iteration-to-artifact, PL016 ambient-entropy-
#      in-artifact with the '# photon: entropy(<reason>)' declaration
#      grammar, PL017 float-accumulation-order, PL018 wire-contract
#      completeness), all ON BY DEFAULT (opt out per-invocation with
#      --no-concurrency / --no-spmd / --no-determinism); rules and
#      suppression/baseline mechanics in photon_ml_tpu/lint/. PL009,
#      PL012, PL016 and PL018 findings are never baseline-able. The
#      determinism pass's runtime twin is dev-scripts/determinism.sh
#      (hash-seed twin-run byte-diff over every artifact class).
#      The SPMD pass covers the unified-mesh plane (parallel/
#      unified_mesh.py, game/unified.py) at ZERO baseline and ZERO
#      allows — every grid-sharded program carries a machine-checked
#      '# photon: sharding(...)' contract like the pod plane.
#   2. SHARDING.md drift gate — the committed sharding-contract
#      inventory must match a fresh render of the SPMD pass's entry-
#      point scan (regenerate with --write-sharding-md). Skipped when
#      --no-spmd was passed.
#   3. ruff — generic hygiene (import order, unused imports/variables,
#      mutable default args; [tool.ruff] in pyproject.toml). Soft-skips
#      when ruff is not installed so minimal CI containers still gate
#      on photon-lint.
set -euo pipefail
cd "$(dirname "$0")/.."

python -m photon_ml_tpu.lint photon_ml_tpu "$@"

skip_spmd=0
for arg in "$@"; do
    [ "$arg" = "--no-spmd" ] && skip_spmd=1
done
if [ "$skip_spmd" = 0 ]; then
    python -m photon_ml_tpu.lint photon_ml_tpu \
        --check-sharding-md SHARDING.md
fi

if command -v ruff >/dev/null 2>&1; then
    ruff check photon_ml_tpu tests dev-scripts
elif python -c "import ruff" >/dev/null 2>&1; then
    python -m ruff check photon_ml_tpu tests dev-scripts
else
    echo "lint.sh: ruff not installed — skipping ruff check" >&2
fi
