"""Tier-1 gate: the whole package is photon-lint clean.

This is what turns the PR 1-3 perf invariants from tribal knowledge into
CI: a new raw readback, jit-of-lambda, unswept spill dir or undrained
submit_io anywhere in photon_ml_tpu/ fails this test
unless it is explicitly allow()-ed or baselined. The flip-side tests pin
that the enforcement is real: removing a baseline entry or a suppression
comment makes the analyzer report again."""

import json
import os
import re
import subprocess
import sys

import pytest

from photon_ml_tpu.lint import (
    Report,
    analyze_paths,
    analyze_source,
    apply_baseline,
    load_baseline,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, ".photon-lint-baseline.json")
TARGETS = ["photon_ml_tpu"]


@pytest.fixture()
def repo_cwd(monkeypatch):
    # baseline entries use repo-root-relative paths
    monkeypatch.chdir(REPO)


@pytest.fixture(scope="module")
def full_report():
    cwd = os.getcwd()
    os.chdir(REPO)
    try:
        return analyze_paths(TARGETS)
    finally:
        os.chdir(cwd)


def _fmt(violations):
    return "\n".join(
        f"{v.location()}: {v.rule} {v.message}" for v in violations
    )


class TestLintClean:
    def test_package_is_clean(self, full_report):
        report = Report(
            files=full_report.files,
            violations=list(full_report.violations),
            allow_sites=full_report.allow_sites,
        )
        assert not full_report.errors, full_report.errors
        apply_baseline(report, load_baseline(BASELINE))
        assert report.violations == [], (
            "non-baselined photon-lint violations:\n"
            + _fmt(report.violations)
        )

    def test_default_paths_exist(self):
        """`python -m photon_ml_tpu.lint` with no argument checks what
        DEFAULT_PATHS names, silently skipping a path that is gone: a
        deleted default would shrink the gate without failing it."""
        from photon_ml_tpu.lint.cli import DEFAULT_PATHS

        assert list(DEFAULT_PATHS) == TARGETS
        missing = [
            p for p in DEFAULT_PATHS
            if not os.path.exists(os.path.join(REPO, p))
        ]
        assert missing == [], missing

    def test_baseline_has_no_stale_entries(self, full_report):
        report = Report(violations=list(full_report.violations))
        apply_baseline(report, load_baseline(BASELINE))
        assert report.unused_baseline == [], (
            "stale baseline entries (fixed sites?): "
            f"{report.unused_baseline}"
        )

    def test_deleting_any_baseline_entry_fails(self, full_report):
        """EVERY baseline entry is load-bearing: removing any one of
        them must resurface at least one violation."""
        entries = json.load(open(BASELINE))["entries"]
        assert entries, "baseline unexpectedly empty"
        for i in range(len(entries)):
            pruned = entries[:i] + entries[i + 1:]
            allow = {
                (e["file"], e["rule"], e["snippet"]): e.get("count", 1)
                for e in pruned
            }
            from collections import Counter

            report = Report(violations=list(full_report.violations))
            apply_baseline(report, Counter(allow))
            assert report.violations, (
                f"baseline entry {entries[i]} is not load-bearing"
            )

    def test_deleting_a_suppression_comment_fails(self, repo_cwd):
        """The in-tree allow() comments are load-bearing too: stripping
        them from the glm driver resurfaces the PL005 findings."""
        path = "photon_ml_tpu/cli/glm_driver.py"
        src = open(path).read()
        assert "# photon: allow(undrained-io)" in src
        clean = analyze_source(path, src)
        assert not [v for v in clean.violations if v.rule == "PL005"]
        stripped = re.sub(r"\s*# photon: allow\(undrained-io\)[^\n]*", "",
                          src)
        dirty = analyze_source(path, stripped)
        assert [v for v in dirty.violations if v.rule == "PL005"]

    def test_cli_end_to_end(self, repo_cwd, tmp_path):
        """The shipped CLI exits 0 against the checked-in baseline, and
        non-zero when one baseline entry is deleted — the exact command
        the acceptance criteria name."""
        r = subprocess.run(
            [sys.executable, "-m", "photon_ml_tpu.lint",
             *TARGETS, "--baseline", BASELINE],
            capture_output=True, text=True, cwd=REPO,
        )
        assert r.returncode == 0, r.stdout + r.stderr
        data = json.load(open(BASELINE))
        data["entries"] = data["entries"][1:]
        pruned = tmp_path / "pruned.json"
        pruned.write_text(json.dumps(data))
        r = subprocess.run(
            [sys.executable, "-m", "photon_ml_tpu.lint",
             *TARGETS, "--baseline", str(pruned)],
            capture_output=True, text=True, cwd=REPO,
        )
        assert r.returncode == 1, r.stdout + r.stderr

    def test_pl002_allow_sites_are_gone(self, full_report):
        """Round 10 deleted the 12 constructor-time jit(lambda) allow
        sites: the streaming objectives became pytree jit ARGUMENTS with
        shared module-level programs (ops.objective partials,
        io.streaming._tiled_fold_jit, game.streaming._chunk_jit), so the
        recompile-hazard allow-count must stay ZERO — a new allow is a
        regression, not a style choice."""
        pl002 = [
            s for s in full_report.allow_sites
            if s.rules & {"PL002", "recompile-hazard"}
        ]
        assert pl002 == [], (
            "recompile-hazard allow() sites reappeared (round 9 had 12, "
            f"round 10 removed all): {pl002}"
        )

    def test_pl001_baseline_shrank_to_one_entry(self):
        """Round 10 rewrote the host-driven optimizers to batch their
        control scalars through the counted overlap.device_get seam,
        retiring all 40 grandfathered host_lbfgs/host_tron float() pulls
        (round-9 baseline: 41 entries / 43 sites). The PL001 slice of
        the baseline must never grow back past the single remaining
        entry (round 11 added PL006 entries for the spill stream
        writers — a different rule, tested separately below)."""
        entries = [
            e for e in json.load(open(BASELINE))["entries"]
            if e["rule"] == "PL001"
        ]
        assert len(entries) == 1, entries
        assert sum(e.get("count", 1) for e in entries) == 1
        assert not any(
            "host_lbfgs" in e["file"] or "host_tron" in e["file"]
            for e in entries
        )

    def test_pl006_baseline_is_only_the_spill_stream_writers(self):
        """Round 11's reliability-hygiene rule grandfathers EXACTLY the
        spill-store stream writers (append-at-fixed-offset files behind
        the spill_write seam, progress-manifested rather than rename-
        published). Any new PL006 baseline entry is a regression: new
        artifact writes must go through the atomic helpers."""
        entries = [
            e for e in json.load(open(BASELINE))["entries"]
            if e["rule"] == "PL006"
        ]
        assert len(entries) == 3, entries
        assert {e["file"] for e in entries} == {
            "photon_ml_tpu/game/streaming.py",
            "photon_ml_tpu/io/streaming.py",
        }
        assert all("open(" in e["snippet"] for e in entries)

    def test_pl006_allow_site_is_the_atomic_helper_itself(
        self, full_report
    ):
        """The one in-tree PL006 allow() is atomic_writer's own error-
        path tmp cleanup — the helper every other site routes through.
        More allow sites mean someone is opting out of the contract."""
        pl006 = [
            s for s in full_report.allow_sites
            if s.rules & {"PL006", "reliability-hygiene"}
        ]
        assert len(pl006) == 1, pl006
        assert pl006[0].path.endswith("reliability/artifacts.py")

    def test_serving_subsystem_is_covered_and_clean(self, full_report):
        """ISSUE 7: photon_ml_tpu/serving/ + the serving driver are in
        the analyzed file set (PL001/PL002 and friends apply to the
        request path) and contribute ZERO baseline entries and ZERO
        allow() sites — the new subsystem starts at the post-round-10
        hygiene bar, not grandfathered."""
        serving_files = [
            f for f in full_report.files
            if "photon_ml_tpu/serving/" in f.replace(os.sep, "/")
        ]
        assert len(serving_files) >= 5, serving_files
        # the wire codec (ISSUE 17) is part of the request path and is
        # pinned at the same zero bar
        assert any(
            f.replace(os.sep, "/").endswith("serving/wire.py")
            for f in serving_files
        ), serving_files
        assert any(
            f.replace(os.sep, "/").endswith("cli/serving_driver.py")
            for f in full_report.files
        )
        entries = json.load(open(BASELINE))["entries"]
        assert not [
            e for e in entries
            if "serving" in e["file"]
        ], "serving code must not be baselined"
        assert not [
            s for s in full_report.allow_sites
            if "serving" in s.path.replace(os.sep, "/")
        ], "serving code must not carry allow() suppressions"

    def test_pod_sharding_modules_covered_and_clean(self, full_report):
        """ISSUE 9: the pod-scale sharding modules (game/pod.py and the
        extended residual router) are in the analyzed set and contribute
        ZERO baseline entries and ZERO allow() sites — the routed hot
        path's no-hidden-host-sync discipline is structural, not
        grandfathered."""
        files = [f.replace(os.sep, "/") for f in full_report.files]
        assert any(f.endswith("game/pod.py") for f in files)
        assert any(f.endswith("game/residual_routing.py") for f in files)
        entries = json.load(open(BASELINE))["entries"]
        for mod in ("game/pod.py", "game/residual_routing.py"):
            assert not [
                e for e in entries if e["file"].replace(os.sep, "/").endswith(mod)
            ], f"{mod} must not be baselined"
            assert not [
                s for s in full_report.allow_sites
                if s.path.replace(os.sep, "/").endswith(mod)
            ], f"{mod} must not carry allow() suppressions"

    def test_registry_subsystem_covered_and_clean(self, full_report):
        """ISSUE 10: photon_ml_tpu/registry/ (model registry, stats
        cache, warm-start alignment, gates, watcher) is in the analyzed
        set and contributes ZERO baseline entries and ZERO allow()
        sites — in particular every artifact write in the publish
        protocol goes through the atomic helpers (PL006) with no
        except-and-pass, structurally."""
        registry_files = [
            f for f in full_report.files
            if "photon_ml_tpu/registry/" in f.replace(os.sep, "/")
        ]
        assert len(registry_files) >= 5, registry_files
        entries = json.load(open(BASELINE))["entries"]
        assert not [
            e for e in entries if "registry" in e["file"]
        ], "registry code must not be baselined"
        assert not [
            s for s in full_report.allow_sites
            if "photon_ml_tpu/registry/" in s.path.replace(os.sep, "/")
        ], "registry code must not carry allow() suppressions"

    def test_pl007_lands_at_zero(self, full_report):
        """ISSUE 8: the request-path-hygiene rule (no untimed
        Condition.wait / Future.result in serving/) ships with a ZERO
        baseline and zero allow() sites — every wait the request path
        performs is bounded from day one, and any new unbounded wait is
        a lint failure, not a grandfathered hang."""
        from photon_ml_tpu.lint.core import RULES, _load_rules

        _load_rules()
        assert "PL007" in RULES, sorted(RULES)
        entries = [
            e for e in json.load(open(BASELINE))["entries"]
            if e["rule"] == "PL007"
        ]
        assert entries == [], entries
        pl007_allows = [
            s for s in full_report.allow_sites
            if s.rules & {"PL007", "request-path-hygiene"}
        ]
        assert pl007_allows == [], pl007_allows
        # the rule applies to the live request path: frontend + batcher
        # + programs are all in the analyzed set
        serving = [
            f for f in full_report.files
            if "photon_ml_tpu/serving/" in f.replace(os.sep, "/")
        ]
        assert any(f.endswith("frontend.py") for f in serving), serving
        assert any(f.endswith("admission.py") for f in serving), serving

    def test_concurrency_rules_land_at_zero(self, full_report):
        """ISSUE 11: PL008-PL010 ship with ZERO baseline entries
        package-wide and ZERO allow() sites in `serving/` and
        `registry/` — the thread plane's guard discipline is
        structural from day one. PL009 additionally can never GAIN a
        baseline entry (write/load both refuse), so the pin here is
        belt-and-braces."""
        from photon_ml_tpu.lint import all_rules

        rules = all_rules()
        for rid in ("PL008", "PL009", "PL010"):
            assert rid in rules, sorted(rules)
        entries = [
            e for e in json.load(open(BASELINE))["entries"]
            if e["rule"] in ("PL008", "PL009", "PL010")
        ]
        assert entries == [], entries
        slugs = {
            "PL008", "unguarded-shared-state",
            "PL009", "lock-order-inversion",
            "PL010", "atomicity-hygiene",
        }
        allows = [
            s for s in full_report.allow_sites if s.rules & slugs
        ]
        assert allows == [], allows
        for subsystem in ("photon_ml_tpu/serving/",
                          "photon_ml_tpu/registry/"):
            assert not [
                s for s in full_report.allow_sites
                if subsystem in s.path.replace(os.sep, "/")
            ], f"{subsystem} must not carry allow() suppressions"

    def test_concurrency_pass_is_enforced_not_decorative(self):
        """Stripping ONE guard from the real watcher resurfaces PL008:
        the zero-violation state above is load-bearing analysis, not a
        rule that never fires on real code."""
        path = "photon_ml_tpu/registry/watcher.py"
        src = open(path).read()
        clean = analyze_source(path, src)
        assert not [
            v for v in clean.violations if v.rule == "PL008"
        ], _fmt(clean.violations)
        stripped = src.replace(
            "        with self._lock:\n"
            "            if not self._watching_swap:\n"
            "                return",
            "        if not self._watching_swap:\n"
            "            return",
        )
        assert stripped != src, "watcher guard shape changed; update me"
        dirty = analyze_source(path, stripped)
        assert [v for v in dirty.violations if v.rule == "PL008"]

    def test_obs_subsystem_covered_clean_and_host_only(self, full_report):
        """ISSUE 13: photon_ml_tpu/obs/ (trace, registry, flight
        recorder, folded events) is in the analyzed set at the
        zero-baseline bar — ZERO baseline entries and ZERO allow()
        sites — and is structurally host-arithmetic-only: no obs module
        imports jax in any form, so no obs code can ever touch a jax
        value (the PL001 concern made impossible rather than merely
        clean). Telemetry must never add a device sync, a lowering, or
        a readback."""
        obs_files = [
            f for f in full_report.files
            if "photon_ml_tpu/obs/" in f.replace(os.sep, "/")
        ]
        # ISSUE 15 adds fleet.py (collector/stitching) + slo.py
        # (burn-rate engine) to the set — both at the same bar
        assert len(obs_files) >= 7, obs_files
        names = {os.path.basename(f) for f in obs_files}
        assert {"fleet.py", "slo.py"} <= names, names
        entries = json.load(open(BASELINE))["entries"]
        assert not [
            e for e in entries
            if "photon_ml_tpu/obs/" in e["file"].replace(os.sep, "/")
        ], "obs code must not be baselined"
        assert not [
            s for s in full_report.allow_sites
            if "photon_ml_tpu/obs/" in s.path.replace(os.sep, "/")
        ], "obs code must not carry allow() suppressions"
        jax_import = re.compile(r"^\s*(import\s+jax|from\s+jax)", re.M)
        for f in obs_files:
            src = open(os.path.join(REPO, f)).read()
            assert not jax_import.search(src), (
                f"{f}: obs code imports jax — telemetry is host "
                "arithmetic only"
            )

    def test_spmd_rules_land_at_zero(self, full_report):
        """ISSUE 14: PL011-PL014 ship with ZERO baseline entries
        package-wide and ZERO allow() sites anywhere — the SPMD
        discipline (axis constants, sharding contracts, shard-local
        bank access, donation hygiene) is structural from day one.
        PL012 additionally can never GAIN a baseline entry (write/load
        both refuse), so the pin here is belt-and-braces."""
        from photon_ml_tpu.lint import all_rules

        rules = all_rules()
        for rid in ("PL011", "PL012", "PL013", "PL014"):
            assert rid in rules, sorted(rules)
        entries = [
            e for e in json.load(open(BASELINE))["entries"]
            if e["rule"] in ("PL011", "PL012", "PL013", "PL014")
        ]
        assert entries == [], entries
        slugs = {
            "PL011", "mesh-axis-discipline",
            "PL012", "sharded-bank-host-gather",
            "PL013", "reduction-completeness",
            "PL014", "donation-hygiene",
        }
        allows = [
            s for s in full_report.allow_sites if s.rules & slugs
        ]
        assert allows == [], allows

    def test_spmd_subsystems_carry_no_allow_sites(self, full_report):
        """The acceptance bar: serving/, game/, parallel/, registry/
        and obs/ carry NO allow() suppressions of any rule — the five
        subsystems the sharding contracts cover hold the zero bar
        wholesale."""
        for subsystem in ("photon_ml_tpu/serving/",
                          "photon_ml_tpu/game/",
                          "photon_ml_tpu/parallel/",
                          "photon_ml_tpu/registry/",
                          "photon_ml_tpu/obs/"):
            assert not [
                s for s in full_report.allow_sites
                if subsystem in s.path.replace(os.sep, "/")
            ], f"{subsystem} must not carry allow() suppressions"

    def test_sharding_inventory_is_complete(self, full_report):
        """Every jit/shard_map mesh entry point in the package is
        present in the contract inventory with a declaration, and the
        committed SHARDING.md matches a fresh render (the CI drift
        gate's in-process twin)."""
        from photon_ml_tpu.lint import sharding_contracts as sc

        assert full_report.package is not None
        rows = sc.inventory(full_report.package)
        # the count is asserted exactly: a NEW jit/shard_map entry
        # point must land here (with a declaration) or fail PL011.
        # ISSUE 20 shrank the inventory 38 -> 36: five legacy
        # distributed fit builders collapsed into feature_sharded_glm_fit
        # wrappers and the problem.py hdiag variants merged, while the
        # unified-mesh grid programs (game/unified.py) added six
        # declared entries; ISSUE 31 deleted the four entry points no
        # driver reached (data_parallel_*, dense feature_sharded_*):
        # 36 -> 32
        assert len(rows) == 32, [
            (r["module"], r["entry"]) for r in rows
        ]
        assert all(r["declared"] == "yes" for r in rows), [
            r for r in rows if r["declared"] != "yes"
        ]
        modules = {r["module"] for r in rows}
        for expected in (
            "photon_ml_tpu/game/pod.py",
            "photon_ml_tpu/game/residual_routing.py",
            "photon_ml_tpu/game/random_effect.py",
            "photon_ml_tpu/game/unified.py",
            "photon_ml_tpu/optim/problem.py",
            "photon_ml_tpu/parallel/distributed.py",
            "photon_ml_tpu/parallel/shuffle.py",
            "photon_ml_tpu/ops/tiled_sparse.py",
            "photon_ml_tpu/serving/programs.py",
            "photon_ml_tpu/serving/swap.py",
        ):
            assert expected in modules, sorted(modules)
        scopes = sc.export_scopes(full_report.package)
        assert len(scopes) == 6, scopes
        drift = sc.check_sharding_md(
            os.path.join(REPO, "SHARDING.md"), full_report.package
        )
        assert drift is None, drift

    def test_stripping_a_sharding_declaration_resurfaces_pl011(self):
        """The contract layer is enforced, not decorative: removing one
        real declaration from the pod update program resurfaces the
        missing-declaration violation."""
        path = "photon_ml_tpu/game/pod.py"
        src = open(path).read()
        decl = ("    # photon: sharding(axes=[entity], in=?, "
                "out=[entity,r,r,r], donates=[0])\n")
        assert decl in src, "pod declaration shape changed; update me"
        clean = analyze_source(path, src)
        assert not [v for v in clean.violations if v.rule == "PL011"], \
            _fmt(clean.violations)
        dirty = analyze_source(path, src.replace(decl, ""))
        assert [
            v for v in dirty.violations
            if v.rule == "PL011" and "no '# photon: sharding" in v.message
        ]

    def test_stripping_an_export_declaration_resurfaces_pl012(self):
        """The export scopes are audited declarations: removing the one
        on the pod model's bank property makes its to_global() a PL012
        violation again."""
        path = "photon_ml_tpu/game/pod.py"
        src = open(path).read()
        clean = analyze_source(path, src)
        assert not [v for v in clean.violations if v.rule == "PL012"], \
            _fmt(clean.violations)
        stripped = src.replace(
            "    @property\n"
            "    # photon: sharding(export)\n"
            "    def bank(self) -> Array:",
            "    @property\n"
            "    def bank(self) -> Array:",
        )
        assert stripped != src, "pod bank property changed; update me"
        dirty = analyze_source(path, stripped)
        assert [v for v in dirty.violations if v.rule == "PL012"]

    def test_reverting_tiled_sparse_axis_constants_resurfaces_pl011(self):
        """Round 19's real PL011 findings: the tiled batch builders
        bound their axis parameters to string literals. Reverting the
        constant references fails the literal rule again."""
        path = "photon_ml_tpu/ops/tiled_sparse.py"
        src = open(path).read()
        assert 'data_axis: str = DATA_AXIS' in src
        clean = analyze_source(path, src)
        assert not [v for v in clean.violations if v.rule == "PL011"], \
            _fmt(clean.violations)
        reverted = src.replace(
            "    data_axis: str = DATA_AXIS,\n"
            "    model_axis: str = MODEL_AXIS,",
            '    data_axis: str = "data",\n'
            '    model_axis: str = "model",',
        )
        assert reverted != src
        dirty = analyze_source(path, reverted)
        lits = [
            v for v in dirty.violations
            if v.rule == "PL011" and "literal" in v.message
        ]
        assert len(lits) == 2, _fmt(dirty.violations)

    def test_interleave_harness_is_analyzed(self, full_report):
        """The testing/ package (interleaving harness) is part of the
        analyzed set and holds the same bar — its own thread-shared
        flags carry guarded-by declarations, not suppressions."""
        files = [f.replace(os.sep, "/") for f in full_report.files]
        assert any(
            f.endswith("testing/interleave.py") for f in files
        ), files

    def test_determinism_rules_land_at_zero(self, full_report):
        """ISSUE 19: PL015-PL018 ship with ZERO baseline entries
        package-wide and ZERO allow() sites anywhere — artifact-order
        and entropy discipline is structural, expressed through fixes
        and '# photon: entropy(<reason>)' declarations, never through
        suppressions. PL016/PL018 additionally can never GAIN a
        baseline entry (write/load both refuse)."""
        from photon_ml_tpu.lint import all_rules

        rules = all_rules()
        for rid in ("PL015", "PL016", "PL017", "PL018"):
            assert rid in rules, sorted(rules)
        entries = [
            e for e in json.load(open(BASELINE))["entries"]
            if e["rule"] in ("PL015", "PL016", "PL017", "PL018")
        ]
        assert entries == [], entries
        slugs = {
            "PL015", "unordered-iteration-to-artifact",
            "PL016", "ambient-entropy-in-artifact",
            "PL017", "float-accumulation-order",
            "PL018", "wire-contract-completeness",
        }
        allows = [
            s for s in full_report.allow_sites if s.rules & slugs
        ]
        assert allows == [], allows

    def test_stripping_an_entropy_declaration_resurfaces_pl016(self):
        """The declaration grammar is enforced, not decorative:
        removing the span-epoch declaration from the tracer makes its
        epoch exports PL016 violations again."""
        path = "photon_ml_tpu/obs/trace.py"
        src = open(path).read()
        decl = ("  # photon: entropy(per-boot span-epoch anchor; "
                "the wall/perf pair IS the timeline contract)")
        assert decl in src, "trace epoch declaration changed; update me"
        clean = analyze_source(path, src)
        assert not [v for v in clean.violations if v.rule == "PL016"], \
            _fmt(clean.violations)
        dirty = analyze_source(path, src.replace(decl, ""))
        assert [
            v for v in dirty.violations
            if v.rule == "PL016" and "time.time()" in v.message
        ], _fmt(dirty.violations)

    def test_reverting_retry_jitter_seed_resurfaces_pl016(self):
        """Regression pin for the real defect PL016 caught on its first
        package run: the backoff jitter was seeded from builtin
        hash((seam, attempt)) — PYTHONHASHSEED-randomized, so the
        'deterministic' retry schedule differed per process. Reverting
        the crc32 fix resurfaces the finding."""
        path = "photon_ml_tpu/reliability/retry.py"
        src = open(path).read()
        fixed = 'seed = zlib.crc32(f"{seam}:{attempt}".encode("utf-8"))'
        assert fixed in src, "retry jitter seed changed; update me"
        clean = analyze_source(path, src)
        assert not [v for v in clean.violations if v.rule == "PL016"], \
            _fmt(clean.violations)
        dirty = analyze_source(
            path, src.replace(fixed, "seed = hash((seam, attempt))")
        )
        assert [
            v for v in dirty.violations
            if v.rule == "PL016" and "seeds Random" in v.message
        ], _fmt(dirty.violations)

    def test_hash_seeded_default_rng_is_pl016(self):
        """The same pin on a payload generator, as an inline source:
        a hash(key)-seeded default_rng builds DIFFERENT payloads for
        the same key in a parent and a relaunched child process
        (PYTHONHASHSEED differs); the crc32 seed does not."""
        template = (
            "import zlib\n"
            "import numpy as np\n"
            "\n"
            "def payload(key, pool):\n"
            "    seed = {seed}\n"
            "    prng = np.random.default_rng(seed & 0x7FFFFFFF)\n"
            "    pool[key] = float(prng.standard_normal())\n"
            "    return pool[key]\n"
        )
        path = "photon_ml_tpu/serving/_flood_payload.py"
        clean = analyze_source(path, template.format(
            seed='zlib.crc32(f"{key[0]}:{key[1]}".encode("utf-8"))'
        ))
        assert not [v for v in clean.violations if v.rule == "PL016"], \
            _fmt(clean.violations)
        dirty = analyze_source(path, template.format(seed="hash(key)"))
        assert [
            v for v in dirty.violations
            if v.rule == "PL016" and "default_rng" in v.message
        ], _fmt(dirty.violations)

    def test_unsorting_the_signature_walk_resurfaces_pl015(self):
        """The PL015 pin on the lineage-critical artifact: the registry
        content signature digests a sorted os.walk. Dropping the sort
        makes the digest OS-iteration-order dependent — the same tree
        would sign differently across hosts — and the analyzer flags
        the walk again."""
        path = "photon_ml_tpu/registry/registry.py"
        src = open(path).read()
        fixed = "for root, dirs, files in sorted(os.walk(model_dir)):"
        assert fixed in src, "signature walk changed; update me"
        clean = analyze_source(path, src)
        assert not [v for v in clean.violations if v.rule == "PL015"], \
            _fmt(clean.violations)
        dirty = analyze_source(
            path,
            src.replace(
                fixed, "for root, dirs, files in os.walk(model_dir):"
            ),
        )
        assert [
            v for v in dirty.violations
            if v.rule == "PL015" and "os.walk" in v.message
        ], _fmt(dirty.violations)

    def test_reverting_native_index_partition_sort_resurfaces_pl015(self):
        """Round 22's real PL015 finding: the partitioned index builder
        iterated ``set(keys)`` straight into the per-partition stores,
        so the same key set produced byte-different index files per
        process. Reverting the sort resurfaces the finding."""
        path = "photon_ml_tpu/utils/native_index.py"
        src = open(path).read()
        fixed = ("    for key in sorted(set(keys)):\n"
                 "        parts[zlib.crc32")
        assert fixed in src, "partition loop changed; update me"
        clean = analyze_source(path, src)
        assert not [v for v in clean.violations if v.rule == "PL015"], \
            _fmt(clean.violations)
        dirty = analyze_source(
            path,
            src.replace(
                fixed,
                "    for key in set(keys):\n        parts[zlib.crc32",
            ),
        )
        assert [
            v for v in dirty.violations
            if v.rule == "PL015" and "set(...)" in v.message
        ], _fmt(dirty.violations)

    def test_stripping_routing_allowlist_resurfaces_pl018(self, tmp_path):
        """The transport fix PL018 forced: without the response-type
        allowlist in _read_frames, routing.py references NO response
        MSG_* constants — the dispatch leg flags all three response
        types (and the original protocol-confusion hole returns)."""
        import shutil

        serving = os.path.join(REPO, "photon_ml_tpu", "serving")
        pkg_dir = tmp_path / "serving"
        pkg_dir.mkdir()
        for name in ("wire.py", "frontend.py", "routing.py"):
            shutil.copy(os.path.join(serving, name), pkg_dir / name)
        clean = analyze_paths([str(pkg_dir)])
        assert not [v for v in clean.violations if v.rule == "PL018"], \
            _fmt(clean.violations)
        routing_src = (pkg_dir / "routing.py").read_text()
        allowlist = (
            "                if mtype not in (\n"
            "                    wirefmt.MSG_JSON,\n"
            "                    wirefmt.MSG_SCORE_RESPONSE,\n"
            "                    wirefmt.MSG_PARTIAL_RESPONSE,\n"
            "                    wirefmt.MSG_TRACE_RESPONSE,\n"
            "                ):\n"
            "                    self.unmatched_responses += 1\n"
            "                    continue\n"
        )
        assert allowlist in routing_src, "routing allowlist changed"
        (pkg_dir / "routing.py").write_text(
            routing_src.replace(allowlist, "")
        )
        dirty = analyze_paths([str(pkg_dir)])
        undispatched = {
            v.message.split(" ", 1)[0]
            for v in dirty.violations
            if v.rule == "PL018" and "never dispatched" in v.message
        }
        assert undispatched == {
            "MSG_SCORE_RESPONSE", "MSG_PARTIAL_RESPONSE",
            "MSG_TRACE_RESPONSE",
        }, _fmt(dirty.violations)

    def test_determinism_harness_is_analyzed(self, full_report):
        """The twin-run harness and its artifact targets are part of
        the analyzed set and hold the zero bar themselves — the gate
        that checks determinism is checked for determinism."""
        files = [f.replace(os.sep, "/") for f in full_report.files]
        for mod in ("testing/determinism.py",
                    "testing/determinism_targets.py"):
            assert any(f.endswith(mod) for f in files), (mod, files)

    def test_json_lists_allow_sites_with_seam_accounting(self, repo_cwd):
        r = subprocess.run(
            [sys.executable, "-m", "photon_ml_tpu.lint",
             *TARGETS, "--baseline", BASELINE, "--json"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert r.returncode == 0, r.stdout + r.stderr
        data = json.loads(r.stdout)
        assert data["violations"] == []
        assert data["baselined"] > 0
        sites = data["allow_sites"]
        assert sites, "expected in-tree allow() sites"
        # every hidden-host-sync allow in package code is seam-accounted
        for s in sites:
            if set(s["rules"]) & {"PL001", "hidden-host-sync"}:
                if s["file"].startswith("photon_ml_tpu/"):
                    assert s["seam_ok"] is True, s
