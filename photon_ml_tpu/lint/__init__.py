"""photon-lint: AST-based static checks for the JAX hot-path invariants.

PRs 1-3 established performance invariants that only runtime tests
enforced: every device->host fetch routes through the counted
``parallel/overlap.py`` seam, every spill scratch dir registers for the
atexit sweep, every ``submit_io`` is drained before exit. This package
makes those invariants machine-checked at review time — a
project-specific analyzer over the stdlib ``ast``, no new runtime deps.

Rules (see ``photon_ml_tpu/lint/rules/``):

==========  ======================  ===========================================
id          slug                    protects
==========  ======================  ===========================================
``PL001``   hidden-host-sync        all device->host fetches go through the
                                    counted ``overlap.device_get`` seam
``PL002``   recompile-hazard        no jit-of-lambda / jit-in-loop / unhashable
                                    static_argnums (silent recompilations)
``PL003``   tracer-leak             no tracers stored on ``self``/globals or
                                    Python-branched inside jitted bodies
``PL004``   spill-hygiene           scratch dirs under ``io/`` / GAME streaming
                                    register for the atexit sweep
``PL005``   undrained-io            ``submit_io`` scopes reach a ``drain_io``
``PL006``   reliability-hygiene     artifact writes publish atomically; IO
                                    failures are never silently swallowed
``PL007``   request-path-hygiene    no untimed waits in ``serving/``
``PL008``   unguarded-shared-state  every shared-attr access holds its
                                    declared/inferred guard (whole-package
                                    pass; ``# photon: guarded-by(...)``)
``PL009``   lock-order-inversion    acyclic lock-acquisition order across
                                    modules — NEVER baseline-able
``PL010``   atomicity-hygiene       no stale check-then-act across a lock
                                    release; no callbacks/blocking/foreign
                                    locks inside Condition-backed sections
``PL011``   mesh-axis-discipline    axis names reference the mesh constants;
                                    every jit/shard_map entry point carries a
                                    cross-checked ``# photon: sharding(...)``
                                    contract (the SHARDING.md inventory)
``PL012``   sharded-bank-host-      no host/replicated materialization of an
            gather                  entity-/feature-sharded bank outside a
                                    declared export/checkpoint scope — NEVER
                                    baseline-able
``PL013``   reduction-completeness  shard_map bodies psum what their out_specs
                                    claim replicated, only over sharded axes
``PL014``   donation-hygiene        donated arguments are dead after the
                                    donating call
``PL015``   unordered-iteration-    set/listdir/glob iteration order never
            to-artifact             reaches a serialization or digest sink
                                    without ``sorted()``
``PL016``   ambient-entropy-in-     clocks/pids/uuids/``hash()`` never reach
            artifact                signatures, manifests, cache keys or wire
                                    payloads undeclared
                                    (``# photon: entropy(<reason>)``) — NEVER
                                    baseline-able
``PL017``   float-accumulation-     host-side ``sum()``/``fsum``/``np.sum``
            order                   over unordered collections iterates a
                                    declared canonical order
``PL018``   wire-contract-          every ``MSG_*`` type has encoder, decoder,
            completeness            dispatch and fuzz-corpus entry; every
                                    ``WireError`` kind a frontend mapping —
                                    NEVER baseline-able
==========  ======================  ===========================================

PL008-PL010 are the concurrency pass (two-pass whole-package analysis:
class guard maps, the cross-module lock graph, thread-escape); their
runtime twin is the deterministic interleaving harness in
``photon_ml_tpu/testing/interleave.py``. PL011-PL014 are the SPMD pass
(``lint/spmd.py``): axis-constant resolution, the mesh entry-point
inventory behind the generated ``SHARDING.md``
(``lint/sharding_contracts.py``), sharded-bank taint and per-body
reduction dataflow. PL015-PL018 are the determinism pass
(``lint/determinism.py``): unordered/entropy taint into artifact sinks,
the ``# photon: entropy(<reason>)`` declaration grammar, and the
machine-built wire-message inventory; their runtime twin is the
hash-seed twin-run harness in ``photon_ml_tpu/testing/determinism.py``.
Opt out per-invocation with ``--no-concurrency`` / ``--no-spmd`` /
``--no-determinism``.

Usage::

    python -m photon_ml_tpu.lint photon_ml_tpu
    python -m photon_ml_tpu.lint --json photon_ml_tpu
    dev-scripts/lint.sh            # photon-lint + ruff (when installed)

Suppress a single line with ``# photon: allow(<rule>)`` (id or slug);
grandfathered sites live in the checked-in ``.photon-lint-baseline.json``
(regenerate with ``--write-baseline``). ``tests/test_lint_clean.py`` runs
the analyzer over the whole package under tier-1, so a new raw readback
fails CI instead of landing silently.
"""

from photon_ml_tpu.lint.core import (
    FileContext,
    PackageContext,
    PackageRule,
    PACKAGE_RULES,
    Report,
    Rule,
    RULES,
    Violation,
    all_rules,
    analyze_paths,
    analyze_source,
    iter_python_files,
    register,
    register_package,
)
from photon_ml_tpu.lint.baseline import (
    BaselineRefused,
    apply_baseline,
    baseline_key,
    load_baseline,
    write_baseline,
)

__all__ = [
    "FileContext",
    "PackageContext",
    "PackageRule",
    "PACKAGE_RULES",
    "Report",
    "Rule",
    "RULES",
    "Violation",
    "all_rules",
    "analyze_paths",
    "analyze_source",
    "iter_python_files",
    "register",
    "register_package",
    "BaselineRefused",
    "apply_baseline",
    "baseline_key",
    "load_baseline",
    "write_baseline",
]
