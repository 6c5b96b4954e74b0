"""Static invariant analysis for the throttlecrab-tpu tree.

Every high-severity bug the advisor rounds have surfaced so far was one
of two hand-maintained invariants silently breaking: raw numpy int64
arithmetic on TAT/tolerance values escaping the saturating helpers
(core/i64.py, tpu/sat.py), or the Python kernel drifting from its C++
twin (native/keymap.cpp, native/wire_server.cpp).  This package checks
those invariants mechanically, on every PR, in seconds:

  * ``i64_hygiene``  — raw ``+``/``-``/``*`` on int64 TAT/tolerance/
    expiry expressions in hot-path modules that are neither routed
    through the saturating helpers nor dominated by an explicit
    ``>= 2**61`` refusal guard (the exact class of the round-5
    ``fits_w32_wire`` wrap);
  * ``twin_drift``   — wire constants, status codes, prep flags, error
    strings and the 2^61/2^62 certificates extracted from BOTH the
    Python kernel and the C++ twins, failing on any divergence;
  * ``jit_boundary`` — Python ``if``/``while``/``assert`` on traced
    values and host calls (``time.*``, ``np.random``, I/O) inside
    ``@jax.jit``/Pallas-decorated functions;
  * ``registry``     — every ``THROTTLECRAB_*`` knob the package reads
    must be documented (README/ARCHITECTURE), every documented knob
    must still be read, every ``config._SPEC`` CLI flag must pair with
    its canonically-named env knob, and every ``throttlecrab_*``
    metric emitted must match the ``server/metrics.py`` METRIC_NAMES
    registry (both directions);
  * ``lock``         — every nested lock acquisition, threaded through
    a conservative intra-package call graph, validated against the
    canonical total order in ``lockorder.toml`` (inversions and
    therefore cycles fail; new/removed locks ratchet the declaration);
  * ``block``        — blocking calls (socket send/recv, device
    launch/fetch, ``sleep``, ``Future.result``, subprocess…) reachable
    while a ranked lock is held must be kinds that lock's audited
    ``allow`` list sanctions — the PR-8 send-under-device_lock class;
  * ``async``        — no threading lock held across ``await``, no
    ranked non-``async_ok`` lock or blocking call on the event loop
    outside ``run_in_executor``, no loop-affine asyncio API from
    executor threads;
  * ``wire``         — wire-frame exhaustiveness: every ``OP_*`` /
    ``REC_*`` frame kind resolves to an encoder, a decoder table
    entry, a dispatch arm, a fuzzer mutation arm, and (membership
    ops) a replayer handler, with orphans in either direction;
  * ``harden``       — the decode-hardening contract per ``decode_*``:
    length guard before unpack, count-vs-size before allocation,
    trailing-bytes rejection, typed errors only;
  * ``status``       — status-taxonomy totality: every ``STATUS_*``
    has its engine message, transport exception arms, native-driver
    branches, and C++ responder branches (both directions);
  * ``fault``        — fault-site registry: ``SITES``/``MODES``
    bidirectionally consistent with the armed hook call sites, the
    typed ``_site_error`` arms, the replay path, and the README
    fault-site table.

Pure stdlib, AST-based plus a small C++ token scanner: importing this
package (or running ``scripts/check_invariants.py``) must never import
jax, numpy, or the package under analysis — sources are parsed, not
executed.  Audited pre-existing exceptions live in ``baseline.toml``
next to this file; the suite ratchets from zero unwaived findings.
"""

from __future__ import annotations

import time
from pathlib import Path
from typing import Dict, List, Tuple

from .common import Finding, apply_baseline, load_baseline
from . import (
    async_boundary,
    blocking,
    fault_surface,
    i64_hygiene,
    jit_boundary,
    lock_order,
    registry,
    status_surface,
    twin_drift,
    wire_surface,
)

#: name -> check(root) callables, in report order.
CHECKERS = {
    "i64": i64_hygiene.check,
    "twin": twin_drift.check,
    "jit": jit_boundary.check,
    "registry": registry.check,
    "lock": lock_order.check,
    "block": blocking.check,
    "async": async_boundary.check,
    "wire": wire_surface.check_surface,
    "harden": wire_surface.check_hardening,
    "status": status_surface.check,
    "fault": fault_surface.check,
}

#: checker name -> the finding-code prefixes it emits.  The CLI uses
#: this to scope baseline waivers on partial ``--checks`` runs; keeping
#: it next to CHECKERS means registering a checker without declaring
#: its codes is a KeyError at import time, not a silent waiver leak.
CHECKER_CODES = {
    "i64": ("i64",),
    "twin": ("twin",),
    "jit": ("jit",),
    "registry": ("knob", "metric", "flag"),
    "lock": ("lock",),
    "block": ("block",),
    "async": ("async",),
    "wire": ("wire",),
    "harden": ("harden",),
    "status": ("status",),
    "fault": ("fault",),
}
assert set(CHECKER_CODES) == set(CHECKERS)

DEFAULT_BASELINE = Path(__file__).with_name("baseline.toml")


def run_timed(
    root, checks=None
) -> Tuple[List[Finding], Dict[str, float]]:
    """Run the selected checkers (default: all); findings plus
    per-checker wall time (the CI budget assertion and ``--json``
    timings both read it).  Unknown checker names raise ValueError —
    a typo'd programmatic selection must not silently run nothing."""
    root = Path(root)
    if checks is not None:
        unknown = set(checks) - set(CHECKERS)
        if unknown:
            raise ValueError(
                f"unknown checks {sorted(unknown)}; "
                f"available: {sorted(CHECKERS)}"
            )
    findings: List[Finding] = []
    timings: Dict[str, float] = {}
    for name, fn in CHECKERS.items():
        if checks is None or name in checks:
            t0 = time.monotonic()
            findings.extend(fn(root))
            timings[name] = round(time.monotonic() - t0, 3)
    findings.sort(key=lambda f: (f.path, f.line, f.code))
    return findings, timings


def run_all(root, checks=None) -> List[Finding]:
    """Run the selected checkers (default: all) over a repo tree."""
    return run_timed(root, checks=checks)[0]


__all__ = [
    "CHECKERS",
    "CHECKER_CODES",
    "DEFAULT_BASELINE",
    "Finding",
    "apply_baseline",
    "load_baseline",
    "run_all",
    "run_timed",
]
