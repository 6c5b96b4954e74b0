"""Tier-1 tests for the static invariant suite (throttlecrab_tpu/analysis).

Three layers:

  * the real tree is clean — zero unwaived findings, zero stale
    waivers, well under the 30 s budget, and the CLI runs strict on a
    bare interpreter without importing jax;
  * per-checker synthetic fixtures — known-bad snippets are flagged
    with the right code and line, and the sanctioned patterns
    (saturating helpers, 2**61 guards, plain-int coercions, pragmas,
    static_argnames, shape-based control flow) pass;
  * the round-5 regression — stripping the big-tolerance refusal from
    ``fits_w32_wire`` (the ADVICE round-5 high finding) must produce a
    finding again;
  * the wave-3 protocol-surface family (wire / harden / status /
    fault) — real anchor files are copied into a temp tree and
    mutated (an OP_* with no decoder, a decoder without the
    trailing-bytes check, a STATUS_* absent from one transport, a
    fault site with no hook), and each rule must fire with the right
    code and symbol.
"""

from __future__ import annotations

import json
import re
import shutil
import subprocess
import sys
import textwrap
import time
from pathlib import Path

from throttlecrab_tpu.analysis import (
    CHECKER_CODES,
    CHECKERS,
    DEFAULT_BASELINE,
    apply_baseline,
    load_baseline,
    run_all,
    run_timed,
)
from throttlecrab_tpu.analysis import (
    fault_surface,
    i64_hygiene,
    jit_boundary,
    registry,
    status_surface,
    twin_drift,
    wire_surface,
)
from throttlecrab_tpu.analysis.common import parse_baseline

REPO = Path(__file__).resolve().parent.parent
KERNEL_REL = "throttlecrab_tpu/tpu/kernel.py"


def _write(root: Path, rel: str, source: str) -> None:
    path = root / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))


# ------------------------------------------------------------------ #
# The real tree


class TestRepoClean:
    def test_zero_unwaived_findings_and_fast(self):
        t0 = time.monotonic()
        findings = run_all(REPO)
        waivers = load_baseline(DEFAULT_BASELINE)
        unwaived, stale = apply_baseline(findings, waivers)
        elapsed = time.monotonic() - t0
        assert unwaived == [], "\n".join(f.format() for f in unwaived)
        assert stale == [], f"stale baseline waivers: {stale}"
        assert elapsed < 30.0, f"suite took {elapsed:.1f}s (budget 30s)"

    def test_baseline_waivers_all_used(self):
        """Every baseline entry must match >= 1 live finding (ratchet:
        audited exceptions that no longer exist must be deleted)."""
        findings = run_all(REPO)
        for w in load_baseline(DEFAULT_BASELINE):
            assert any(w.matches(f) for f in findings), (
                f"stale waiver: {w.code} {w.path} {w.symbol or w.line}"
            )

    def test_cli_strict_runs_without_jax(self):
        """The CLI must exit 0 in strict mode and must never import
        jax — the CI invariants job runs it with no jax installed."""
        proc = subprocess.run(
            [
                sys.executable,
                str(REPO / "scripts" / "check_invariants.py"),
                "--strict",
                "--json",
            ],
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        report = json.loads(proc.stdout)
        assert report["findings"] == []
        assert report["stale_waivers"] == []
        assert report["jax_imported"] is False
        assert report["elapsed_s"] < 30.0


# ------------------------------------------------------------------ #
# i64 hygiene fixtures


class TestI64Hygiene:
    def test_raw_op_flagged_with_code_and_line(self, tmp_path):
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            def advance(tat, tol):
                new_tat = tat + tol
                return new_tat
            """,
        )
        findings = i64_hygiene.check(tmp_path)
        assert len(findings) == 1
        f = findings[0]
        assert f.code == "i64-raw-op"
        assert f.path == KERNEL_REL
        assert f.line == 2
        assert f.symbol == "advance"

    def test_augmented_assign_flagged(self, tmp_path):
        """`tat += tol` is the same wrap class with no BinOp node."""
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            def advance(tat, tol):
                tat += tol
                return tat
            """,
        )
        findings = i64_hygiene.check(tmp_path)
        assert len(findings) == 1
        assert findings[0].code == "i64-raw-op"
        assert findings[0].line == 2

    def test_guarded_augmented_assign_passes(self, tmp_path):
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            def advance(tat, tol):
                if tat >= (1 << 61) or tol >= (1 << 61):
                    return None
                tat += tol
                return tat
            """,
        )
        assert i64_hygiene.check(tmp_path) == []

    def test_saturating_helper_passes(self, tmp_path):
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            from .sat import sat_add

            def advance(tat, tol):
                return sat_add(tat, tol)
            """,
        )
        assert i64_hygiene.check(tmp_path) == []

    def test_dominating_guard_passes(self, tmp_path):
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            def bound(tat, tol):
                if tat >= (1 << 61) or tol >= (1 << 61):
                    return None
                return tat + tol
            """,
        )
        assert i64_hygiene.check(tmp_path) == []

    def test_guard_on_wrong_identifier_still_flags(self, tmp_path):
        """A 2**61 guard on one name must not license arithmetic on
        another — the precise shape of the round-5 bug."""
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            def bound(tat, tol, hwm):
                if hwm >= (1 << 61):
                    return None
                return tol + hwm
            """,
        )
        findings = i64_hygiene.check(tmp_path)
        assert len(findings) == 1
        assert "tol" in findings[0].message
        assert findings[0].line == 4

    def test_telemetry_comparison_is_not_a_guard(self, tmp_path):
        """A 2**61 comparison whose result is never acted on must not
        license later arithmetic — only a refusing guard dominates."""
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            def bound(tat, tol):
                big = (tat >= (1 << 61)) or (tol >= (1 << 61))
                log(big)
                return tat + tol
            """,
        )
        findings = i64_hygiene.check(tmp_path)
        assert len(findings) == 1
        assert findings[0].line == 4

    def test_clamp_without_refusal_is_not_a_guard(self, tmp_path):
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            def bound(tat, tol):
                if tol >= (1 << 61):
                    tol = 0
                return tat + tol
            """,
        )
        findings = i64_hygiene.check(tmp_path)
        assert len(findings) == 1
        assert "tat" in findings[0].message

    def test_overflow_branch_is_not_licensed(self, tmp_path):
        """In `if tol >= 2**61: <body>` the body is the OVERFLOW side;
        raw arithmetic there is wrap-guaranteed and must flag even
        though the branch refuses."""
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            def bound(tat, tol):
                if tol >= (1 << 61) or tat >= (1 << 61):
                    return tat + tol
                return 0
            """,
        )
        findings = i64_hygiene.check(tmp_path)
        assert len(findings) == 1
        assert findings[0].line == 3

    def test_guard_inside_conditional_branch_does_not_leak(self, tmp_path):
        """A refusal guard that only runs when `flag` is true must not
        license arithmetic on the unconditional path."""
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            def bound(tat, tol, flag):
                if flag:
                    if tol >= (1 << 61) or tat >= (1 << 61):
                        return None
                return tat + tol
            """,
        )
        findings = i64_hygiene.check(tmp_path)
        assert len(findings) == 1
        assert findings[0].line == 5

    def test_reassignment_kills_guard_license(self, tmp_path):
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            def bound(tat, tol):
                if tol >= (1 << 61) or tat >= (1 << 61):
                    return None
                tol = load_foreign()
                return tat + tol
            """,
        )
        findings = i64_hygiene.check(tmp_path)
        assert len(findings) == 1
        # tat kept its license (never reassigned); only tol lost it.
        assert "value(s) tol without" in findings[0].message

    def test_branch_reassignment_survives_branch_exit(self, tmp_path):
        """A license revoked by an in-branch reassignment must stay
        revoked after the branch — restore intersects, never resurrects."""
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            def bound(tat, tol, flag, x):
                if tol >= (1 << 61):
                    return None
                if flag:
                    tol = x
                return tol + 1
            """,
        )
        findings = i64_hygiene.check(tmp_path)
        assert [f.line for f in findings] == [6]

    def test_branch_coercion_does_not_leak(self, tmp_path):
        """int() on one branch must not mark the name safe on the
        other path."""
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            def bound(tol, cheap, x):
                if cheap:
                    tol = int(x)
                return tol + 1
            """,
        )
        findings = i64_hygiene.check(tmp_path)
        assert [f.line for f in findings] == [4]

    def test_np_all_guard_bounds_nothing(self, tmp_path):
        """np.all(x >= bound) false means only SOME lane is below —
        unlike np.any, it must not license the false branch."""
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            import numpy as np

            def bound(tol, x):
                if np.all(tol >= (1 << 61)):
                    raise ValueError()
                return tol + x
            """,
        )
        findings = i64_hygiene.check(tmp_path)
        assert [f.line for f in findings] == [6]

    def test_np_any_refusal_licenses(self, tmp_path):
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            import numpy as np

            def bound(tol, x):
                if np.any(tol >= (1 << 61)):
                    raise ValueError()
                return tol + x
            """,
        )
        assert i64_hygiene.check(tmp_path) == []

    def test_tuple_rebind_revokes_safety(self, tmp_path):
        """A tuple-unpack rebinding a previously-coerced name must
        revoke its plain-Python-safe status."""
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            def bound(x, pairs):
                tol = int(x)
                tat, tol = pairs
                return tat + tol
            """,
        )
        findings = i64_hygiene.check(tmp_path)
        assert [f.line for f in findings] == [4]

    def test_for_target_rebind_revokes_safety(self, tmp_path):
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            def bound(x, tols):
                tol = int(x)
                out = 0
                for tol in tols:
                    out = tol + 1
                return out
            """,
        )
        findings = i64_hygiene.check(tmp_path)
        assert [f.line for f in findings] == [5]

    def test_match_case_bodies_are_scanned(self, tmp_path):
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            def bound(tat, tol, kind):
                match kind:
                    case 0:
                        return tat + tol
                    case _:
                        return 0
            """,
        )
        findings = i64_hygiene.check(tmp_path)
        assert len(findings) == 1
        assert findings[0].line == 4

    def test_bounded_branch_is_licensed(self, tmp_path):
        """Inside `if x < bound:` one branch IS the bounded side; the
        compare licenses the branch bodies even without a refusal."""
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            def bound(tat, tol):
                out = 0
                if tat < (1 << 61) and tol < (1 << 61):
                    out = tat + tol
                return out
            """,
        )
        assert i64_hygiene.check(tmp_path) == []

    def test_plain_python_int_math_passes(self, tmp_path):
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            def bound(max_tol, tol_hwm):
                hwm = int(tol_hwm)
                hwm = max(hwm, int(max_tol))
                return int(max_tol) + hwm
            """,
        )
        assert i64_hygiene.check(tmp_path) == []

    def test_pragma_passes(self, tmp_path):
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            def wrap(now, tol):
                return now + tol  # inv: allow(i64-raw-op)
            """,
        )
        assert i64_hygiene.check(tmp_path) == []

    def test_insensitive_names_not_flagged(self, tmp_path):
        _write(
            tmp_path,
            KERNEL_REL,
            """\
            def pad(n, width):
                return n + width * 2
            """,
        )
        assert i64_hygiene.check(tmp_path) == []


# ------------------------------------------------------------------ #
# Twin drift fixtures


def _twin_tree(tmp_path: Path) -> Path:
    """A minimal tree with the real twin anchors copied in."""
    for rel in (
        KERNEL_REL,
        "throttlecrab_tpu/tpu/limiter.py",
        "throttlecrab_tpu/tpu/table.py",
        "throttlecrab_tpu/native.py",
        "throttlecrab_tpu/server/resp.py",
        "throttlecrab_tpu/server/engine.py",
        "throttlecrab_tpu/front/admission.py",
        "native/keymap.cpp",
        "native/wire_server.cpp",
    ):
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(REPO / rel, dst)
    return tmp_path


class TestTwinDrift:
    def test_real_tree_in_sync(self):
        assert twin_drift.check(REPO) == []

    def test_constant_drift_flagged(self, tmp_path):
        root = _twin_tree(tmp_path)
        kernel = root / KERNEL_REL
        kernel.write_text(
            kernel.read_text().replace("PACK_WIDTH = 9", "PACK_WIDTH = 10")
        )
        findings = [
            f for f in twin_drift.check(root) if f.code == "twin-drift"
        ]
        assert any("PACK_WIDTH" in f.message for f in findings)

    def test_status_code_drift_flagged(self, tmp_path):
        root = _twin_tree(tmp_path)
        cpp = root / "native/keymap.cpp"
        cpp.write_text(
            cpp.read_text().replace(
                "STATUS_NEGATIVE_QUANTITY = 1", "STATUS_NEGATIVE_QUANTITY = 9"
            )
        )
        findings = [
            f for f in twin_drift.check(root) if f.code == "twin-drift"
        ]
        assert any(
            "STATUS_NEGATIVE_QUANTITY" in f.message for f in findings
        )

    def test_error_string_drift_flagged(self, tmp_path):
        root = _twin_tree(tmp_path)
        cpp = root / "native/wire_server.cpp"
        cpp.write_text(
            cpp.read_text().replace(
                "-ERR server overloaded", "-ERR overloaded"
            )
        )
        findings = [
            f for f in twin_drift.check(root) if f.code == "twin-drift"
        ]
        assert any("STATUS_OVERLOADED" in f.message for f in findings)

    def test_missing_anchor_is_loud(self, tmp_path):
        root = _twin_tree(tmp_path)
        (root / "native/keymap.cpp").unlink()
        findings = twin_drift.check(root)
        assert any(
            f.code == "twin-missing" and f.path == "native/keymap.cpp"
            for f in findings
        )

    def test_round5_fits_w32_wire_wrap_reintroduction_caught(
        self, tmp_path
    ):
        """Strip the tol >= 2**61 refusal from fits_w32_wire — the
        exact round-5 high finding — and the suite must flag it even
        though the function keeps its other 2**61 compares."""
        root = _twin_tree(tmp_path)
        kernel = root / KERNEL_REL
        src = kernel.read_text()
        pattern = re.compile(
            r"    if int\(tol\.max\(initial=0\)\) >= \(1 << 61\):\n"
            r"(        #.*\n)*        return False\n"
        )
        assert pattern.search(src), "guard block moved; update the test"
        kernel.write_text(pattern.sub("", src))
        findings = twin_drift.check(root)
        hits = [
            f
            for f in findings
            if f.code == "twin-guard-missing"
            and f.symbol == "fits_w32_wire"
            and "`tol`" in f.message
        ]
        assert hits, "round-5 wrap reintroduction was not caught"


# ------------------------------------------------------------------ #
# jit boundary fixtures


class TestJitBoundary:
    def test_branch_on_traced_value_flagged(self, tmp_path):
        _write(
            tmp_path,
            "throttlecrab_tpu/tpu/mod.py",
            """\
            import jax

            @jax.jit
            def decide(x):
                if x > 0:
                    return x
                return -x
            """,
        )
        findings = jit_boundary.check(tmp_path)
        assert len(findings) == 1
        f = findings[0]
        assert f.code == "jit-branch"
        assert f.line == 5
        assert f.symbol == "decide"

    def test_derived_traced_local_flagged(self, tmp_path):
        _write(
            tmp_path,
            "throttlecrab_tpu/tpu/mod.py",
            """\
            import jax

            @jax.jit
            def decide(x):
                y = x * 2
                assert y > 0
                return y
            """,
        )
        findings = jit_boundary.check(tmp_path)
        assert [f.code for f in findings] == ["jit-branch"]
        assert findings[0].line == 6

    def test_host_call_flagged(self, tmp_path):
        _write(
            tmp_path,
            "throttlecrab_tpu/tpu/mod.py",
            """\
            import time

            import jax

            @jax.jit
            def decide(x):
                t = time.monotonic()
                return x + t
            """,
        )
        findings = jit_boundary.check(tmp_path)
        assert any(
            f.code == "jit-host-call" and "time.monotonic" in f.message
            for f in findings
        )

    def test_static_argnames_branch_passes(self, tmp_path):
        _write(
            tmp_path,
            "throttlecrab_tpu/tpu/mod.py",
            """\
            from functools import partial

            import jax

            @partial(jax.jit, static_argnames=("compact",))
            def decide(x, *, compact=False):
                if compact:
                    return x
                return x + 1
            """,
        )
        assert jit_boundary.check(tmp_path) == []

    def test_shape_based_control_flow_passes(self, tmp_path):
        _write(
            tmp_path,
            "throttlecrab_tpu/tpu/mod.py",
            """\
            import jax

            @jax.jit
            def decide(packed):
                W = packed.shape[1]
                if W % 5:
                    raise ValueError("misaligned")
                B = W * 4 // 5
                return packed[:B]
            """,
        )
        assert jit_boundary.check(tmp_path) == []

    def test_pallas_kernel_body_scanned(self, tmp_path):
        _write(
            tmp_path,
            "throttlecrab_tpu/tpu/mod.py",
            """\
            import jax
            from jax.experimental import pallas as pl

            def _kernel(x_ref, o_ref):
                if x_ref[0] > 0:
                    o_ref[0] = x_ref[0]

            def run(x):
                return pl.pallas_call(_kernel, out_shape=x)(x)
            """,
        )
        findings = jit_boundary.check(tmp_path)
        assert any(
            f.code == "jit-branch" and f.symbol == "_kernel"
            for f in findings
        )

    def test_branch_on_traced_loop_variable_flagged(self, tmp_path):
        _write(
            tmp_path,
            "throttlecrab_tpu/tpu/mod.py",
            """\
            import jax

            @jax.jit
            def decide(xs):
                total = 0
                for v in xs:
                    if v > 0:
                        total = total + v
                return total
            """,
        )
        findings = jit_boundary.check(tmp_path)
        assert any(
            f.code == "jit-branch" and f.line == 7 for f in findings
        )

    def test_static_loop_variable_passes(self, tmp_path):
        _write(
            tmp_path,
            "throttlecrab_tpu/tpu/mod.py",
            """\
            import jax

            @jax.jit
            def decide(x):
                out = x
                for i in range(3):
                    if i % 2:
                        out = out + 1
                return out
            """,
        )
        assert jit_boundary.check(tmp_path) == []

    def test_undecorated_function_ignored(self, tmp_path):
        _write(
            tmp_path,
            "throttlecrab_tpu/tpu/mod.py",
            """\
            def host_side(x):
                if x > 0:
                    return x
                return -x
            """,
        )
        assert jit_boundary.check(tmp_path) == []


# ------------------------------------------------------------------ #
# Knob / metric registry fixtures

_METRICS_FIXTURE = """\
METRIC_NAMES = (
    "throttlecrab_requests_total",
    "throttlecrab_ghost_metric",
)


def export():
    out = []
    out.append("throttlecrab_requests_total 5")
    n = 2
    out.append(f'throttlecrab_novel_metric{{shard="{n}"}} 1')
    return out
"""


class TestRegistry:
    def _tree(self, tmp_path, readme: str = "") -> Path:
        _write(
            tmp_path,
            "throttlecrab_tpu/server/config.py",
            """\
            import os

            KNOB = os.environ.get("THROTTLECRAB_BOGUS_KNOB")
            """,
        )
        (tmp_path / "throttlecrab_tpu/server/metrics.py").parent.mkdir(
            parents=True, exist_ok=True
        )
        (tmp_path / "throttlecrab_tpu/server/metrics.py").write_text(
            _METRICS_FIXTURE
        )
        (tmp_path / "README.md").write_text(readme)
        return tmp_path

    def test_undocumented_knob_flagged(self, tmp_path):
        root = self._tree(tmp_path)
        findings = registry.check(root)
        assert any(
            f.code == "knob-undocumented"
            and "THROTTLECRAB_BOGUS_KNOB" in f.message
            for f in findings
        )

    def test_documented_knob_passes(self, tmp_path):
        root = self._tree(
            tmp_path, readme="`THROTTLECRAB_BOGUS_KNOB` does things\n"
        )
        findings = registry.check(root)
        assert not any(f.code == "knob-undocumented" for f in findings)

    def test_prefix_of_documented_knob_still_flagged(self, tmp_path):
        """Documenting THROTTLECRAB_BOGUS_KNOB_EXTRA must not count as
        documentation for THROTTLECRAB_BOGUS_KNOB (substring trap)."""
        root = self._tree(
            tmp_path, readme="`THROTTLECRAB_BOGUS_KNOB_EXTRA` only\n"
        )
        findings = registry.check(root)
        assert any(
            f.code == "knob-undocumented"
            and "THROTTLECRAB_BOGUS_KNOB " in f.message
            for f in findings
        )

    def test_unregistered_metric_flagged(self, tmp_path):
        root = self._tree(tmp_path)
        findings = registry.check(root)
        hits = [f for f in findings if f.code == "metric-unregistered"]
        assert any(
            "throttlecrab_novel_metric" in f.message for f in hits
        )

    def test_stale_registry_entry_flagged(self, tmp_path):
        root = self._tree(tmp_path)
        findings = registry.check(root)
        assert any(
            f.code == "metric-stale"
            and "throttlecrab_ghost_metric" in f.message
            for f in findings
        )

    def test_docstring_mention_does_not_mask_stale_entry(self, tmp_path):
        """Prose in a docstring starting with a metric name is not an
        emission — the stale registry entry must still be flagged."""
        root = self._tree(tmp_path)
        (root / "throttlecrab_tpu/server/metrics.py").write_text(
            '"""throttlecrab_ghost_metric is incremented on sweeps."""\n'
            + _METRICS_FIXTURE
        )
        findings = registry.check(root)
        assert any(
            f.code == "metric-stale"
            and "throttlecrab_ghost_metric" in f.message
            for f in findings
        )

    def test_registered_and_emitted_metric_passes(self, tmp_path):
        root = self._tree(tmp_path)
        findings = registry.check(root)
        assert not any(
            "throttlecrab_requests_total" in f.message for f in findings
        )

    def test_fstring_prose_is_not_an_emission(self, tmp_path):
        """An f-string whose head merely starts with a metric-shaped
        token is prose, not an emission — no spurious unregistered
        finding."""
        root = self._tree(tmp_path)
        path = root / "throttlecrab_tpu/server/metrics.py"
        path.write_text(
            _METRICS_FIXTURE
            + "\n\ndef log(n):\n"
            + '    return f"throttlecrab_bogus_thing prose {n}"\n'
        )
        findings = registry.check(root)
        assert not any(
            "throttlecrab_bogus_thing" in f.message for f in findings
        )


# ------------------------------------------------------------------ #
# Baseline machinery


class TestBaseline:
    def test_parse_and_match(self):
        waivers = parse_baseline(
            '# comment\n'
            '[[waiver]]\n'
            'code = "i64-raw-op"\n'
            'path = "a/b.py"\n'
            'symbol = "f"\n'
            'reason = "audited"\n'
        )
        assert len(waivers) == 1
        w = waivers[0]
        from throttlecrab_tpu.analysis.common import Finding

        assert w.matches(Finding("i64-raw-op", "a/b.py", 3, "m", "Cls.f"))
        assert not w.matches(Finding("i64-raw-op", "a/b.py", 3, "m", "g"))
        assert not w.matches(Finding("jit-branch", "a/b.py", 3, "m", "f"))

    def test_stale_waiver_detected(self):
        from throttlecrab_tpu.analysis.common import Finding, Waiver

        findings = [Finding("i64-raw-op", "a.py", 1, "m", "f")]
        waivers = [
            Waiver("i64-raw-op", "a.py", symbol="f", reason="r"),
            Waiver("i64-raw-op", "gone.py", symbol="g", reason="r"),
        ]
        unwaived, stale = apply_baseline(findings, waivers)
        assert unwaived == []
        assert len(stale) == 1
        assert stale[0].path == "gone.py"

    def test_count_mismatch_violates_waiver(self):
        """A pinned count must match exactly: new unaudited arithmetic
        inside a waived function fails instead of riding the audit."""
        from throttlecrab_tpu.analysis.common import Finding, Waiver

        findings = [
            Finding("i64-raw-op", "a.py", 1, "m", "f"),
            Finding("i64-raw-op", "a.py", 2, "m", "f"),
        ]
        ok = [Waiver("i64-raw-op", "a.py", symbol="f", count=2, reason="r")]
        unwaived, violated = apply_baseline(findings, ok)
        assert unwaived == [] and violated == []
        pinned = [
            Waiver("i64-raw-op", "a.py", symbol="f", count=1, reason="r")
        ]
        unwaived, violated = apply_baseline(findings, pinned)
        assert unwaived == []  # still absorbed, but…
        assert violated == pinned  # …the outgrown waiver is reported

    def test_repo_baseline_counts_are_pinned(self):
        """Every symbol-scoped waiver in the shipped baseline must pin
        its match count — an unpinned one would absorb future raw ops
        in the most overflow-critical functions silently."""
        for w in load_baseline(DEFAULT_BASELINE):
            if w.symbol and not w.line:
                assert w.count > 0, (
                    f"waiver {w.symbol} must pin `count`"
                )

    def test_malformed_baseline_rejected(self):
        import pytest

        with pytest.raises(ValueError):
            parse_baseline("[[waiver]]\ncode = [1, 2]\n")
        with pytest.raises(ValueError):
            parse_baseline('code = "orphan key"\n')


# ------------------------------------------------------------------ #
# Concurrency checkers (lock / block / async)

_LOCKORDER_FIXTURE = """\
[[lock]]
name = "outer"
class = "Svc"
rank = 10
allow = "net"

[[lock]]
name = "inner"
class = "Svc"
rank = 20

[[blocking]]
call = "sendall"
kind = "net"

[[blocking]]
call = "time.sleep"
kind = "sleep"
"""

_SVC_REL = "throttlecrab_tpu/svc.py"
_LOCKORDER_REL = "throttlecrab_tpu/analysis/lockorder.toml"

_SVC_HEADER = """\
import threading


class Svc:
    def __init__(self):
        self.outer = threading.Lock()
        self.inner = threading.Lock()

"""


def _conc_tree(tmp_path, body: str, toml: str = _LOCKORDER_FIXTURE):
    _write(tmp_path, _SVC_REL, _SVC_HEADER + body)
    if toml is not None:
        _write(tmp_path, _LOCKORDER_REL, toml)
    return tmp_path


class TestLockOrder:
    def test_real_tree_clean_with_baseline(self):
        from throttlecrab_tpu.analysis import lock_order

        findings = run_all(REPO, checks={"lock"})
        waivers = load_baseline(DEFAULT_BASELINE)
        unwaived, _ = apply_baseline(findings, waivers)
        assert unwaived == [], "\n".join(f.format() for f in unwaived)
        assert lock_order  # imported and runnable

    def test_direct_inversion_flagged(self, tmp_path):
        from throttlecrab_tpu.analysis import lock_order

        root = _conc_tree(
            tmp_path,
            """\
    def bad(self):
        with self.inner:
            with self.outer:
                pass
""",
        )
        findings = lock_order.check(root)
        hits = [f for f in findings if f.code == "lock-order"]
        assert len(hits) == 1
        assert hits[0].path == _SVC_REL
        assert "Svc.outer" in hits[0].message
        assert "Svc.inner" in hits[0].message

    def test_canonical_order_passes(self, tmp_path):
        from throttlecrab_tpu.analysis import lock_order

        root = _conc_tree(
            tmp_path,
            """\
    def good(self):
        with self.outer:
            with self.inner:
                pass
""",
        )
        assert [
            f for f in lock_order.check(root) if f.code == "lock-order"
        ] == []

    def test_transitive_inversion_through_call_graph(self, tmp_path):
        """The PR-6/8 deadlock class: the nested acquisition hides one
        call away — the graph must still surface it, with the witness
        chain in the message."""
        from throttlecrab_tpu.analysis import lock_order

        root = _conc_tree(
            tmp_path,
            """\
    def helper(self):
        with self.outer:
            pass

    def bad(self):
        with self.inner:
            self.helper()
""",
        )
        hits = [
            f
            for f in lock_order.check(root)
            if f.code == "lock-order"
        ]
        assert len(hits) == 1
        assert "via" in hits[0].message and "helper" in hits[0].message

    def test_sticky_acquire_region(self, tmp_path):
        """.acquire() holds to end of function (the cluster held-dict
        pattern): a later with-block on a lower rank must flag."""
        from throttlecrab_tpu.analysis import lock_order

        root = _conc_tree(
            tmp_path,
            """\
    def bad(self):
        self.inner.acquire()
        try:
            with self.outer:
                pass
        finally:
            self.inner.release()
""",
        )
        assert any(
            f.code == "lock-order" for f in lock_order.check(root)
        )

    def test_pragma_waives_inversion(self, tmp_path):
        from throttlecrab_tpu.analysis import lock_order

        root = _conc_tree(
            tmp_path,
            """\
    def audited(self):
        with self.inner:
            with self.outer:  # inv: allow(lock-order)
                pass
""",
        )
        assert [
            f for f in lock_order.check(root) if f.code == "lock-order"
        ] == []

    def test_unranked_lock_flagged(self, tmp_path):
        """A new threading.Lock creation site without a [[lock]] entry
        must fail: every lock takes a position in the order."""
        from throttlecrab_tpu.analysis import lock_order

        root = _conc_tree(
            tmp_path,
            """\
    def boot(self):
        self.extra = threading.Lock()
""",
        )
        hits = [
            f
            for f in lock_order.check(root)
            if f.code == "lock-unranked"
        ]
        assert len(hits) == 1
        assert "Svc.extra" in hits[0].message

    def test_stale_lockorder_decl_flagged(self, tmp_path):
        """lockorder.toml staleness: an entry whose creation site is
        gone fails, so the declaration tracks the tree."""
        from throttlecrab_tpu.analysis import lock_order

        root = _conc_tree(
            tmp_path,
            """\
    def nop(self):
        pass
""",
            toml=_LOCKORDER_FIXTURE
            + '\n[[lock]]\nname = "ghost"\nclass = "Gone"\nrank = 30\n',
        )
        hits = [
            f
            for f in lock_order.check(root)
            if f.code == "lock-decl-stale"
        ]
        assert any("Gone.ghost" in f.message for f in hits)

    def test_missing_lockorder_toml_is_loud(self, tmp_path):
        from throttlecrab_tpu.analysis import lock_order

        root = _conc_tree(tmp_path, "    pass\n", toml=None)
        assert any(
            f.code == "lock-config-missing"
            for f in lock_order.check(root)
        )


class TestBlockingUnderLock:
    def test_send_under_unsanctioned_lock_flagged(self, tmp_path):
        """The PR-8 review-fix class: a socket send while a lock whose
        allow list lacks `net` is held."""
        from throttlecrab_tpu.analysis import blocking

        root = _conc_tree(
            tmp_path,
            """\
    def push(self, sock):
        with self.inner:
            sock.sendall(b"x")
""",
        )
        hits = [
            f
            for f in blocking.check(root)
            if f.code == "block-under-lock"
        ]
        assert len(hits) == 1
        assert "sendall" in hits[0].message
        assert "Svc.inner" in hits[0].message

    def test_allowed_kind_passes(self, tmp_path):
        from throttlecrab_tpu.analysis import blocking

        root = _conc_tree(
            tmp_path,
            """\
    def push(self, sock):
        with self.outer:
            sock.sendall(b"x")
""",
        )
        assert blocking.check(root) == []

    def test_transitive_blocking_flagged(self, tmp_path):
        from throttlecrab_tpu.analysis import blocking

        root = _conc_tree(
            tmp_path,
            """\
    def slow(self):
        import time

        time.sleep(1)

    def bad(self):
        with self.inner:
            self.slow()
""",
        )
        hits = [
            f
            for f in blocking.check(root)
            if f.code == "block-under-lock"
        ]
        assert len(hits) == 1
        assert "time.sleep" in hits[0].message
        assert "via" in hits[0].message


class TestAsyncBoundary:
    def test_lock_across_await_flagged(self, tmp_path):
        from throttlecrab_tpu.analysis import async_boundary

        root = _conc_tree(
            tmp_path,
            """\
    async def bad(self):
        with self.inner:
            await self.refresh()

    async def refresh(self):
        pass
""",
        )
        hits = [
            f
            for f in async_boundary.check(root)
            if f.code == "async-lock-await"
        ]
        assert len(hits) == 1
        assert "Svc.inner" in hits[0].message

    def test_ranked_lock_acquire_in_async_flagged(self, tmp_path):
        from throttlecrab_tpu.analysis import async_boundary

        root = _conc_tree(
            tmp_path,
            """\
    async def bad(self):
        with self.inner:
            pass
""",
        )
        assert any(
            f.code == "async-lock-acquire"
            for f in async_boundary.check(root)
        )

    def test_async_ok_lock_passes(self, tmp_path):
        from throttlecrab_tpu.analysis import async_boundary

        root = _conc_tree(
            tmp_path,
            """\
    async def fine(self):
        with self.leaf:
            pass

    def boot(self):
        self.leaf = threading.Lock()
""",
            toml=_LOCKORDER_FIXTURE
            + '\n[[lock]]\nname = "leaf"\nclass = "Svc"\nrank = 90\n'
            + "async_ok = 1\n",
        )
        assert [
            f
            for f in async_boundary.check(root)
            if f.code == "async-lock-acquire"
        ] == []

    def test_blocking_call_in_async_flagged(self, tmp_path):
        from throttlecrab_tpu.analysis import async_boundary

        root = _conc_tree(
            tmp_path,
            """\
    async def bad(self):
        import time

        time.sleep(0.1)
""",
        )
        hits = [
            f
            for f in async_boundary.check(root)
            if f.code == "async-blocking-call"
        ]
        assert len(hits) == 1
        assert "time.sleep" in hits[0].message

    def test_executor_routed_blocking_passes(self, tmp_path):
        """run_in_executor REFERENCES the blocking function; it must
        not count as a loop-side call."""
        from throttlecrab_tpu.analysis import async_boundary

        root = _conc_tree(
            tmp_path,
            """\
    async def fine(self, loop):
        import time

        await loop.run_in_executor(None, time.sleep, 0.1)
""",
        )
        assert [
            f
            for f in async_boundary.check(root)
            if f.code == "async-blocking-call"
        ] == []

    def test_transitive_lock_acquire_on_loop_flagged(self, tmp_path):
        """The OP_RING class fixed this PR: an async handler calling a
        sync helper that takes a ranked lock."""
        from throttlecrab_tpu.analysis import async_boundary

        root = _conc_tree(
            tmp_path,
            """\
    def adopt(self):
        with self.inner:
            pass

    async def handle(self):
        self.adopt()
""",
        )
        hits = [
            f
            for f in async_boundary.check(root)
            if f.code == "async-lock-acquire"
        ]
        assert len(hits) == 1
        assert "via" in hits[0].message and "adopt" in hits[0].message

    def test_loop_affine_api_from_thread_flagged(self, tmp_path):
        from throttlecrab_tpu.analysis import async_boundary

        root = _conc_tree(
            tmp_path,
            """\
    def worker(self):
        import asyncio

        asyncio.get_running_loop()

    async def spawn(self, loop):
        await loop.run_in_executor(None, self.worker)
""",
        )
        hits = [
            f
            for f in async_boundary.check(root)
            if f.code == "async-loop-affinity"
        ]
        assert len(hits) == 1
        assert "get_running_loop" in hits[0].message


class TestRegistryParity:
    _CONFIG = """\
    _SPEC = [
        ("cluster_vnodes", "THROTTLECRAB_CLUSTER_VNODES", 128, int,
         "vnodes"),
        ("shards", "THROTTLECRAB_NSHARDS", 1, int, "shards"),
    ]
    """

    def _tree(self, tmp_path, readme: str) -> Path:
        _write(
            tmp_path,
            "throttlecrab_tpu/server/config.py",
            self._CONFIG,
        )
        _write(
            tmp_path,
            "throttlecrab_tpu/server/metrics.py",
            'METRIC_NAMES = ()\n',
        )
        (tmp_path / "README.md").write_text(readme)
        return tmp_path

    def test_flag_knob_mismatch_flagged(self, tmp_path):
        """--shards paired with THROTTLECRAB_NSHARDS: the canonical
        derivation is THROTTLECRAB_SHARDS — both directions of the
        flag<->knob contract break, so it fails."""
        root = self._tree(
            tmp_path,
            "`THROTTLECRAB_CLUSTER_VNODES` and `THROTTLECRAB_NSHARDS`\n",
        )
        findings = registry.check(root)
        hits = [f for f in findings if f.code == "flag-knob-mismatch"]
        assert len(hits) == 1
        assert "THROTTLECRAB_SHARDS" in hits[0].message
        assert "--shards" in hits[0].message

    def test_matching_flag_knob_passes(self, tmp_path):
        root = self._tree(
            tmp_path,
            "`THROTTLECRAB_CLUSTER_VNODES` and `THROTTLECRAB_NSHARDS`\n",
        )
        findings = registry.check(root)
        assert not any(
            f.code == "flag-knob-mismatch"
            and "cluster_vnodes" in f.message
            for f in findings
        )

    def test_documented_but_unread_knob_flagged(self, tmp_path):
        root = self._tree(
            tmp_path,
            "`THROTTLECRAB_CLUSTER_VNODES`, `THROTTLECRAB_NSHARDS`,\n"
            "and `THROTTLECRAB_GHOST_KNOB` control things\n",
        )
        findings = registry.check(root)
        hits = [f for f in findings if f.code == "knob-stale"]
        assert len(hits) == 1
        assert "THROTTLECRAB_GHOST_KNOB" in hits[0].message
        assert hits[0].path == "README.md"
        assert hits[0].line == 2

    def test_wildcard_doc_reference_is_not_a_knob(self, tmp_path):
        """Prose like `THROTTLECRAB_CLUSTER_*` names a family, not a
        knob — it must not produce a stale-doc finding."""
        root = self._tree(
            tmp_path,
            "`THROTTLECRAB_CLUSTER_VNODES`, `THROTTLECRAB_NSHARDS`;\n"
            "see the `THROTTLECRAB_CLUSTER_*` family and the\n"
            "`THROTTLECRAB_*` prefix convention\n",
        )
        findings = registry.check(root)
        assert not any(f.code == "knob-stale" for f in findings)


class TestCliOutput:
    def test_json_carries_timings_and_stable_ids(self):
        proc = subprocess.run(
            [
                sys.executable,
                str(REPO / "scripts" / "check_invariants.py"),
                "--json",
                "--checks",
                "lock,block,async",
            ],
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        report = json.loads(proc.stdout)
        assert set(report["checker_s"]) == {"lock", "block", "async"}
        for f in report["findings"]:
            assert f["id"].count(":") >= 2

    def test_runtime_budget_enforced(self):
        proc = subprocess.run(
            [
                sys.executable,
                str(REPO / "scripts" / "check_invariants.py"),
                "--checks",
                "twin",
                "--max-seconds",
                "0.000001",
            ],
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert proc.returncode == 1
        assert "runtime budget exceeded" in proc.stderr


# ------------------------------------------------------------------ #
# Wave 3: protocol-surface family (wire / harden / status / fault).
# Each fixture copies the real anchor files into a temp tree
# and mutates them — the mutation is the exact defect class the rule
# exists to catch, so these double as regression pins for the rules.

CLUSTER_REL = "throttlecrab_tpu/parallel/cluster.py"
INJECTOR_REL = "throttlecrab_tpu/faults/injector.py"

_WIRE_RELS = (
    CLUSTER_REL,
    "throttlecrab_tpu/replay/trace.py",
    "throttlecrab_tpu/replay/player.py",
    "scripts/fuzz_wire_tiers.py",
)
_STATUS_RELS = (
    "throttlecrab_tpu/tpu/limiter.py",
    "throttlecrab_tpu/front/admission.py",
    "throttlecrab_tpu/server/engine.py",
    "throttlecrab_tpu/server/http.py",
    "throttlecrab_tpu/server/grpc.py",
    "throttlecrab_tpu/server/redis.py",
    "throttlecrab_tpu/server/native_redis.py",
    "native/wire_server.cpp",
)
_FAULT_RELS = (
    INJECTOR_REL,
    CLUSTER_REL,
    "throttlecrab_tpu/tpu/limiter.py",
    "throttlecrab_tpu/tpu/snapshot.py",
    "README.md",
)


def _copy_tree(tmp_path: Path, rels) -> Path:
    for rel in rels:
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(REPO / rel, dst)
    return tmp_path


def _mutate(root: Path, rel: str, old: str, new: str) -> None:
    path = root / rel
    src = path.read_text()
    assert old in src, f"mutation anchor moved in {rel}: {old!r}"
    path.write_text(src.replace(old, new))


class TestWireSurface:
    def test_real_tree_clean(self):
        assert wire_surface.check_surface(REPO) == []

    def test_fixture_tree_clean(self, tmp_path):
        root = _copy_tree(tmp_path, _WIRE_RELS)
        assert wire_surface.check_surface(root) == []

    def test_op_without_decoder_fails_every_rung(self, tmp_path):
        """A new OP_* constant with no FRAME_DECODERS entry must fail
        the decoder, encoder, dispatch, and fuzzer rungs at once."""
        root = _copy_tree(tmp_path, _WIRE_RELS)
        path = root / CLUSTER_REL
        path.write_text(path.read_text() + "\nOP_PING = 99\n")
        codes = {
            f.code
            for f in wire_surface.check_surface(root)
            if f.symbol == "OP_PING"
        }
        assert codes == {
            "wire-decoder", "wire-encoder", "wire-dispatch", "wire-fuzz",
        }

    def test_missing_fuzzer_arm_flagged(self, tmp_path):
        """Dropping one maker from the fuzzer's op-keyed table — the
        exact OP_LEAVE/OP_DROUTE review-round gap — must fire."""
        root = _copy_tree(tmp_path, _WIRE_RELS)
        _mutate(
            root, "scripts/fuzz_wire_tiers.py",
            "        OP_RING: mk_ring,\n", "",
        )
        findings = wire_surface.check_surface(root)
        assert any(
            f.code == "wire-fuzz" and f.symbol == "OP_RING"
            for f in findings
        )

    def test_unwired_table_entry_orphans_decoder(self, tmp_path):
        root = _copy_tree(tmp_path, _WIRE_RELS)
        _mutate(
            root, CLUSTER_REL,
            '    OP_ROUTE_BATCH: ("route", decode_route),\n', "",
        )
        findings = wire_surface.check_surface(root)
        assert any(
            f.code == "wire-decoder" and f.symbol == "OP_ROUTE_BATCH"
            for f in findings
        )
        assert any(
            f.code == "wire-orphan" and f.symbol == "decode_route"
            for f in findings
        )

    def test_replayer_arm_loss_flagged(self, tmp_path):
        """Renaming the player's cluster-leave arm orphans OP_LEAVE's
        membership round-trip."""
        root = _copy_tree(tmp_path, _WIRE_RELS)
        _mutate(
            root, "throttlecrab_tpu/replay/player.py",
            'elif event.kind == "cluster-leave":',
            'elif event.kind == "cluster-depart":',
        )
        findings = wire_surface.check_surface(root)
        assert any(
            f.code == "wire-replayer"
            and f.symbol == "OP_LEAVE"
            and f.path == "throttlecrab_tpu/replay/player.py"
            for f in findings
        )

    def test_recorder_loss_flagged(self, tmp_path):
        root = _copy_tree(tmp_path, _WIRE_RELS)
        path = root / CLUSTER_REL
        path.write_text(
            path.read_text().replace(
                'maybe_record_event("cluster-join"',
                'maybe_record_event("cluster-joined"',
            )
        )
        findings = wire_surface.check_surface(root)
        assert any(
            f.code == "wire-replayer"
            and f.symbol == "OP_JOIN"
            and f.path == CLUSTER_REL
            for f in findings
        )

    def test_missing_anchor_is_loud(self, tmp_path):
        root = _copy_tree(tmp_path, _WIRE_RELS)
        (root / "throttlecrab_tpu/replay/trace.py").unlink()
        findings = wire_surface.check_surface(root)
        assert any(
            f.code == "wire-missing"
            and f.path == "throttlecrab_tpu/replay/trace.py"
            for f in findings
        )


class TestDecodeHardening:
    def test_real_tree_clean(self):
        assert wire_surface.check_hardening(REPO) == []

    def test_trailing_bytes_check_required(self, tmp_path):
        """Stripping decode_batch's trailing-bytes rejection — the
        defect this PR fixed — must fire harden-trailing."""
        root = _copy_tree(tmp_path, _WIRE_RELS)
        _mutate(
            root, CLUSTER_REL,
            "    if off != len(body):\n"
            '        raise ClusterProtocolError'
            '("trailing bytes after batch items")\n',
            "",
        )
        findings = wire_surface.check_hardening(root)
        assert any(
            f.code == "harden-trailing" and f.symbol == "decode_batch"
            for f in findings
        )

    def test_untyped_raise_flagged(self, tmp_path):
        root = _copy_tree(tmp_path, _WIRE_RELS)
        _mutate(
            root, CLUSTER_REL,
            'raise ClusterProtocolError("trailing bytes after batch items")',
            'raise ValueError("trailing bytes after batch items")',
        )
        findings = wire_surface.check_hardening(root)
        assert any(
            f.code == "harden-typed" and f.symbol == "decode_batch"
            for f in findings
        )

    def test_len_guard_before_unpack_required(self, tmp_path):
        root = _copy_tree(tmp_path, _WIRE_RELS)
        _mutate(
            root, CLUSTER_REL,
            "    if len(body) < _REQ_HEAD.size:\n"
            '        raise ClusterProtocolError("short batch frame")\n',
            "",
        )
        findings = wire_surface.check_hardening(root)
        assert any(
            f.code == "harden-guard" and f.symbol == "decode_batch"
            for f in findings
        )

    def test_count_guard_before_allocation_required(self, tmp_path):
        """An unpacked count sizing np.empty without its raise-guard is
        the attacker-sized-allocation shape the RPC port must refuse."""
        root = _copy_tree(tmp_path, _WIRE_RELS)
        _mutate(
            root, CLUSTER_REL,
            "    if n > (len(body) - _REQ_HEAD.size) // min_item:\n"
            "        raise ClusterProtocolError"
            '(f"batch count {n} exceeds frame size")\n',
            "",
        )
        findings = wire_surface.check_hardening(root)
        assert any(
            f.code == "harden-count" and f.symbol == "decode_batch"
            for f in findings
        )


class TestStatusSurface:
    def test_real_tree_clean(self):
        assert status_surface.check(REPO) == []

    def test_fixture_tree_clean(self, tmp_path):
        root = _copy_tree(tmp_path, _STATUS_RELS)
        assert status_surface.check(root) == []

    def test_missing_message_entry_flagged(self, tmp_path):
        root = _copy_tree(tmp_path, _STATUS_RELS)
        _mutate(
            root, "throttlecrab_tpu/server/engine.py",
            '    STATUS_TENANT_QUOTA: "tenant capacity quota exceeded",\n',
            "",
        )
        findings = status_surface.check(root)
        assert any(
            f.code == "status-message"
            and f.symbol == "STATUS_TENANT_QUOTA"
            for f in findings
        )

    def test_transport_arm_loss_flagged(self, tmp_path):
        """An HTTP transport that stops catching OverloadError would
        turn 503s into generic 500s — the hand-wired arm is pinned."""
        root = _copy_tree(tmp_path, _STATUS_RELS)
        path = root / "throttlecrab_tpu/server/http.py"
        path.write_text(
            path.read_text().replace("OverloadError", "OverloadGoneError")
        )
        findings = status_surface.check(root)
        assert any(
            f.code == "status-transport"
            and f.symbol == "OverloadError"
            and f.path == "throttlecrab_tpu/server/http.py"
            for f in findings
        )

    def test_cpp_branch_loss_and_undeclared_value(self, tmp_path):
        root = _copy_tree(tmp_path, _STATUS_RELS)
        path = root / "native/wire_server.cpp"
        path.write_text(
            path.read_text().replace("status[i] == 5", "status[i] == 57")
        )
        findings = status_surface.check(root)
        assert any(
            f.code == "status-cpp" and f.symbol == "STATUS_TENANT_QUOTA"
            for f in findings
        )
        assert any(
            f.code == "status-cpp" and "57" in f.message
            for f in findings
        )

    def test_native_driver_branch_required(self, tmp_path):
        root = _copy_tree(tmp_path, _STATUS_RELS)
        path = root / "throttlecrab_tpu/server/native_redis.py"
        path.write_text(
            path.read_text().replace("STATUS_DEADLINE", "STATUS_DEADLINE_X")
        )
        findings = status_surface.check(root)
        codes = {
            (f.code, f.symbol)
            for f in findings
            if f.code == "status-native"
        }
        assert ("status-native", "STATUS_DEADLINE") in codes
        assert ("status-native", "STATUS_DEADLINE_X") in codes

    def test_duplicate_status_value_is_orphan(self, tmp_path):
        root = _copy_tree(tmp_path, _STATUS_RELS)
        _mutate(
            root, "throttlecrab_tpu/front/admission.py",
            "STATUS_OVERLOADED = 4", "STATUS_OVERLOADED = 6",
        )
        findings = status_surface.check(root)
        assert any(f.code == "status-orphan" for f in findings)


class TestFaultSurface:
    def test_real_tree_clean(self):
        assert fault_surface.check(REPO) == []

    def test_fixture_tree_clean(self, tmp_path):
        root = _copy_tree(tmp_path, _FAULT_RELS)
        assert fault_surface.check(root) == []

    def test_declared_but_unarmed_site_flagged(self, tmp_path):
        root = _copy_tree(tmp_path, _FAULT_RELS)
        _mutate(
            root, INJECTOR_REL,
            '"snapshot", "migrate", "leave",',
            '"snapshot", "migrate", "leave", "gremlin",',
        )
        findings = fault_surface.check(root)
        assert any(
            f.code == "fault-site" and f.symbol == "gremlin"
            for f in findings
        )
        assert any(
            f.code == "fault-doc" and f.symbol == "gremlin"
            for f in findings
        )

    def test_typod_hook_site_flagged_both_directions(self, tmp_path):
        """A typo'd site string at a hook call leaves the declared site
        dead AND arms an undeclared one — both must fire."""
        root = _copy_tree(tmp_path, _FAULT_RELS)
        _mutate(
            root, "throttlecrab_tpu/tpu/limiter.py",
            'maybe_fail("keymap")', 'maybe_fail("keymapp")',
        )
        findings = fault_surface.check(root)
        symbols = {
            f.symbol for f in findings if f.code == "fault-site"
        }
        assert {"keymap", "keymapp"} <= symbols

    def test_doc_row_removal_flagged(self, tmp_path):
        root = _copy_tree(tmp_path, _FAULT_RELS)
        readme = root / "README.md"
        kept = [
            line
            for line in readme.read_text().splitlines()
            if not line.startswith("| `migrate`")
        ]
        readme.write_text("\n".join(kept) + "\n")
        findings = fault_surface.check(root)
        assert any(
            f.code == "fault-doc" and f.symbol == "migrate"
            for f in findings
        )

    def test_mode_without_fire_arm_flagged(self, tmp_path):
        root = _copy_tree(tmp_path, _FAULT_RELS)
        _mutate(
            root, INJECTOR_REL,
            '"truncate", "fsyncfail",',
            '"truncate", "fsyncfail", "jitter",',
        )
        findings = fault_surface.check(root)
        assert any(
            f.code == "fault-mode" and f.symbol == "jitter"
            for f in findings
        )


class TestWave3Registry:
    def test_checker_codes_registry_total(self):
        """Every registered checker declares its code prefixes — the
        partial-run waiver filter depends on this map being total."""
        assert set(CHECKER_CODES) == set(CHECKERS)
        for name in ("wire", "harden", "status", "fault"):
            assert name in CHECKER_CODES

    def test_stale_wave3_waivers_ratchet(self):
        """A waiver written against any wave-3 rule that matches no
        finding must be reported stale — the new family ratchets from
        zero exactly like the older checkers."""
        from throttlecrab_tpu.analysis.common import Waiver

        findings = run_all(REPO)
        for code, path in (
            ("wire-fuzz", CLUSTER_REL),
            ("harden-trailing", CLUSTER_REL),
            ("status-transport", "throttlecrab_tpu/server/http.py"),
            ("fault-site", INJECTOR_REL),
        ):
            w = Waiver(code, path, symbol="ghost", reason="r")
            unwaived, stale = apply_baseline(findings, [w])
            assert stale == [w], f"{code} waiver did not ratchet"

    def test_run_timed_rejects_unknown_checker(self):
        import pytest

        with pytest.raises(ValueError, match="unknown checks"):
            run_timed(REPO, checks={"nope"})

    def test_cli_rejects_unknown_checks_with_roster(self):
        proc = subprocess.run(
            [
                sys.executable,
                str(REPO / "scripts" / "check_invariants.py"),
                "--checks",
                "nope",
            ],
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert proc.returncode == 2
        assert "unknown checks" in proc.stderr
        assert "fault" in proc.stderr  # the valid roster is listed

    def test_cli_wave3_partial_run_times_each_checker(self):
        proc = subprocess.run(
            [
                sys.executable,
                str(REPO / "scripts" / "check_invariants.py"),
                "--json",
                "--strict",
                "--checks",
                "wire,harden,status,fault",
            ],
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        report = json.loads(proc.stdout)
        assert set(report["checker_s"]) == {
            "wire", "harden", "status", "fault",
        }
        assert report["findings"] == []
        assert report["jax_imported"] is False
