#!/usr/bin/env python3
"""Run the invariant linter suite (throttlecrab_tpu/analysis) over the
repo and report findings.

    python scripts/check_invariants.py            # report, exit 0
    python scripts/check_invariants.py --strict   # exit 1 on unwaived
                                                  # findings or stale
                                                  # waivers
    python scripts/check_invariants.py --json     # machine-readable
                                                  # (per-checker
                                                  # timings + stable
                                                  # finding ids)
    python scripts/check_invariants.py --checks i64,twin,lock
    python scripts/check_invariants.py --max-seconds 30

Pure stdlib and AST-based: finishes in seconds and must never import
jax/numpy (verified at exit — the CI `invariants` job runs this on a
bare interpreter before any heavyweight install).  Audited pre-existing
exceptions live in throttlecrab_tpu/analysis/baseline.toml; everything
else fails strict mode, so the suite ratchets from zero.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="throttlecrab-tpu invariant linter suite"
    )
    parser.add_argument(
        "--root",
        type=Path,
        default=REPO_ROOT,
        help="repo root to analyze (default: this checkout)",
    )
    parser.add_argument(
        "--strict",
        action="store_true",
        help="exit 1 on unwaived findings or stale waivers",
    )
    parser.add_argument(
        "--json", action="store_true", help="JSON output"
    )
    parser.add_argument(
        "--checks",
        default="",
        help="comma-separated subset of checkers "
        "(i64,twin,jit,registry,lock,block,async,"
        "wire,harden,status,fault)",
    )
    parser.add_argument(
        "--baseline",
        type=Path,
        default=None,
        help="waiver file (default: throttlecrab_tpu/analysis/"
        "baseline.toml under --root)",
    )
    parser.add_argument(
        "--max-seconds",
        type=float,
        default=0.0,
        help="runtime budget: exit 1 when the suite takes longer "
        "(0 disables; CI pins 30 so the call-graph pass can't "
        "silently balloon)",
    )
    args = parser.parse_args(argv)

    # Load the analysis package WITHOUT importing throttlecrab_tpu's
    # __init__ (which configures jax at import time) — the suite must
    # run on a bare interpreter in seconds (the CI `invariants` job has
    # no jax install at all).
    analysis = _load_analysis()
    CHECKERS = analysis.CHECKERS
    apply_baseline = analysis.apply_baseline
    load_baseline = analysis.load_baseline
    run_timed = analysis.run_timed

    checks = None
    if args.checks:
        checks = {c.strip() for c in args.checks.split(",") if c.strip()}
        unknown = checks - set(CHECKERS)
        if unknown:
            parser.error(
                f"unknown checks {sorted(unknown)}; "
                f"available: {sorted(CHECKERS)}"
            )

    baseline_path = args.baseline
    if baseline_path is None:
        baseline_path = (
            args.root / "throttlecrab_tpu" / "analysis" / "baseline.toml"
        )

    t0 = time.monotonic()
    findings, timings = run_timed(args.root, checks=checks)
    waivers = load_baseline(baseline_path)
    if checks is not None:
        # Partial runs can't judge waiver staleness for skipped checkers.
        # CHECKER_CODES lives next to CHECKERS in the analysis package,
        # so a registered checker always has its code prefixes declared.
        waivers = [w for w in waivers if w.code.split("-")[0] in {
            c for check in checks for c in analysis.CHECKER_CODES[check]
        }]
    unwaived, stale = apply_baseline(findings, waivers)
    elapsed = time.monotonic() - t0

    # The whole point of an AST suite: no heavyweight imports.  jax
    # sneaking in means a checker started executing the tree under
    # analysis instead of parsing it.
    jax_loaded = "jax" in sys.modules

    if args.json:
        print(
            json.dumps(
                {
                    # `id` is the stable finding identity
                    # (path:symbol:rule, line fallback) so baselines
                    # can be diffed mechanically across revisions
                    # where line numbers move.
                    "findings": [
                        {**vars(f), "id": _finding_id(f)}
                        for f in unwaived
                    ],
                    "waived": len(findings) - len(unwaived),
                    "stale_waivers": [vars(w) for w in stale],
                    "elapsed_s": round(elapsed, 3),
                    "checker_s": timings,
                    "jax_imported": jax_loaded,
                },
                indent=2,
            )
        )
    else:
        for f in unwaived:
            print(f.format())
        for w in stale:
            print(
                f"{baseline_path.name}: violated waiver "
                f"({w.code} {w.path} {w.symbol or w.line}): matches no "
                "current finding (stale — delete the entry) or a "
                "different number than its pinned count (new "
                "unaudited arithmetic — re-audit and update)"
            )
        print(
            f"invariants: {len(unwaived)} unwaived finding(s), "
            f"{len(findings) - len(unwaived)} waived, "
            f"{len(stale)} violated waiver(s) in {elapsed:.2f}s"
        )
    if jax_loaded:
        print(
            "invariants: INTERNAL ERROR — the analysis imported jax",
            file=sys.stderr,
        )
        return 2
    if args.max_seconds and elapsed > args.max_seconds:
        print(
            f"invariants: runtime budget exceeded — {elapsed:.1f}s > "
            f"{args.max_seconds:.0f}s "
            f"(per-checker: {timings})",
            file=sys.stderr,
        )
        return 1
    if args.strict and (unwaived or stale):
        return 1
    return 0


def _finding_id(f) -> str:
    return f"{f.path}:{f.symbol or f.line}:{f.code}"


def _load_analysis():
    import importlib.util

    pkg_dir = REPO_ROOT / "throttlecrab_tpu" / "analysis"
    name = "throttlecrab_tpu_analysis"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name,
        pkg_dir / "__init__.py",
        submodule_search_locations=[str(pkg_dir)],
    )
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


if __name__ == "__main__":
    sys.exit(main())
