"""photon-lint CLI: text (clickable file:line:col) and --json modes.

Exit codes: 0 clean, 1 non-baselined violations, 2 analysis/usage error
(a file that does not parse is an error, not a pass).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional, Sequence

from photon_ml_tpu.lint.baseline import (
    BaselineRefused,
    apply_baseline,
    load_baseline,
    write_baseline,
)
from photon_ml_tpu.lint.core import all_rules, analyze_paths

DEFAULT_BASELINE = ".photon-lint-baseline.json"
DEFAULT_PATHS = ("photon_ml_tpu",)


def _default_paths() -> List[str]:
    return [p for p in DEFAULT_PATHS if os.path.exists(p)]


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m photon_ml_tpu.lint",
        description=(
            "AST-based invariant checker for the JAX hot path "
            "(readback seam, recompile hazards, spill/IO hygiene), "
            "the thread plane (guard discipline, lock ordering, "
            "atomicity) and the SPMD plane (mesh-axis discipline, "
            "sharded-bank host gathers, reduction completeness, "
            "donation hygiene) and the determinism plane (unordered "
            "iteration into artifacts, ambient entropy in signatures, "
            "float accumulation order, wire-contract completeness) — "
            "all whole-package passes on by default. Suppress a line "
            "with '# photon: allow(<rule>)'; declare guard discipline "
            "with '# photon: guarded-by(<lock>)', sharding contracts "
            "with '# photon: sharding(axes=..., in=..., out=...)' and "
            "legitimate entropy with '# photon: entropy(<reason>)'."
        ),
    )
    p.add_argument(
        "paths", nargs="*",
        help="files or directories (default: photon_ml_tpu)",
    )
    p.add_argument(
        "--json", action="store_true", dest="as_json",
        help="machine-readable report (violations, baselined count, "
             "allow-sites with seam accounting, unused baseline entries)",
    )
    p.add_argument(
        "--baseline", default=None, metavar="PATH",
        help=f"baseline file (default: {DEFAULT_BASELINE} when present)",
    )
    p.add_argument(
        "--no-baseline", action="store_true",
        help="ignore any baseline file",
    )
    p.add_argument(
        "--write-baseline", action="store_true",
        help="write the current violation set as the new baseline "
             "and exit 0",
    )
    p.add_argument(
        "--list-rules", action="store_true",
        help="print the rule table and exit",
    )
    p.add_argument(
        "--no-concurrency", action="store_true",
        help="skip the whole-package concurrency pass (PL008-PL010); "
             "the pass runs by default",
    )
    p.add_argument(
        "--no-spmd", action="store_true",
        help="skip the whole-package SPMD pass (PL011-PL014 + sharding "
             "contracts); the pass runs by default",
    )
    p.add_argument(
        "--no-determinism", action="store_true",
        help="skip the whole-package determinism pass (PL015-PL018 + "
             "entropy declarations + wire contract); the pass runs by "
             "default",
    )
    p.add_argument(
        "--write-sharding-md", nargs="?", const="SHARDING.md",
        default=None, metavar="PATH",
        help="regenerate the sharding-contract inventory (default "
             "SHARDING.md) from the analyzed paths and exit",
    )
    p.add_argument(
        "--check-sharding-md", nargs="?", const="SHARDING.md",
        default=None, metavar="PATH",
        help="exit 1 if the committed sharding inventory drifted from "
             "a fresh render (the CI drift gate)",
    )
    return p


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    if args.list_rules:
        for rule in sorted(all_rules().values(), key=lambda r: r.id):
            print(f"{rule.id}  {rule.slug:24s}  {rule.doc}")
        return 0

    paths = args.paths or _default_paths()
    if not paths:
        print(
            "photon-lint: no paths given and no default targets found",
            file=sys.stderr,
        )
        return 2

    if args.write_sharding_md or args.check_sharding_md:
        from photon_ml_tpu.lint import sharding_contracts as sc

        pkg = sc.package_context(paths)
        if pkg is None:
            print("photon-lint: no parseable files", file=sys.stderr)
            return 2
        if args.write_sharding_md:
            content = sc.write_sharding_md(args.write_sharding_md, pkg)
            n = len(sc.inventory(pkg))
            print(
                f"photon-lint: wrote {n} sharding contract(s) "
                f"({len(content.splitlines())} lines) to "
                f"{args.write_sharding_md}"
            )
            return 0
        drift = sc.check_sharding_md(args.check_sharding_md, pkg)
        if drift is not None:
            print(f"photon-lint: {drift}", file=sys.stderr)
            return 1
        print(f"photon-lint: {args.check_sharding_md} is up to date")
        return 0

    report = analyze_paths(
        paths,
        package_pass=not args.no_concurrency,
        spmd_pass=not args.no_spmd,
        determinism_pass=not args.no_determinism,
    )

    baseline_path = args.baseline or (
        DEFAULT_BASELINE if os.path.exists(DEFAULT_BASELINE) else None
    )
    if args.write_baseline:
        target = args.baseline or DEFAULT_BASELINE
        try:
            data = write_baseline(target, report.violations)
        except BaselineRefused as e:
            print(f"photon-lint: {e}", file=sys.stderr)
            return 2
        print(
            f"photon-lint: wrote {len(data['entries'])} baseline "
            f"entr{'y' if len(data['entries']) == 1 else 'ies'} "
            f"({len(report.violations)} violation(s)) to {target}"
        )
        return 0

    if baseline_path and not args.no_baseline:
        try:
            allow = load_baseline(baseline_path)
        except (OSError, ValueError, KeyError, json.JSONDecodeError) as e:
            print(f"photon-lint: bad baseline {baseline_path}: {e}",
                  file=sys.stderr)
            return 2
        apply_baseline(report, allow)

    exit_code = 0
    if report.violations:
        exit_code = 1
    if report.errors:
        exit_code = 2

    if args.as_json:
        payload = {
            "version": 1,
            "files_checked": len(report.files),
            "violations": [v.to_dict() for v in report.violations],
            "baselined": report.baselined,
            "allow_sites": [
                s.to_dict() for s in report.allow_sites
            ],
            "unused_baseline": report.unused_baseline,
            "errors": [
                {"file": f, "message": m} for f, m in report.errors
            ],
            "exit_code": exit_code,
        }
        if report.package is not None and not args.no_spmd:
            from photon_ml_tpu.lint import sharding_contracts as sc

            payload["sharding_contracts"] = sc.inventory(report.package)
            payload["export_scopes"] = sc.export_scopes(report.package)
        if report.package is not None and not args.no_determinism:
            from photon_ml_tpu.lint import determinism

            contract = determinism.wire_contract(report.package)
            payload["wire_contract"] = (
                contract.to_dict() if contract is not None else None
            )
            payload["entropy_declarations"] = (
                determinism.entropy_inventory(report.package)
            )
        print(json.dumps(payload, indent=2))
        return exit_code

    for f, m in report.errors:
        print(f"{f}:1:0: ERROR {m}")
    for v in report.violations:
        print(f"{v.location()}: {v.rule} [{v.slug}] {v.message}")
    for e in report.unused_baseline:
        print(
            f"warning: unused baseline entry {e['file']} {e['rule']} "
            f"{e['snippet']!r} x{e['count']} — fixed? remove it",
        )
    n = len(report.violations)
    print(
        f"photon-lint: {n} violation(s), {report.baselined} baselined, "
        f"{len(report.files)} file(s) checked"
    )
    return exit_code


if __name__ == "__main__":  # pragma: no cover - exercised via __main__
    sys.exit(main())
