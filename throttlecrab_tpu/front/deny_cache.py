"""Exact negative-decision cache: GCRA denials answered without the engine.

GCRA makes an *exact* deny cache possible where generic response caching
cannot be: a denial does not mutate the bucket, so once a key is denied
for `(params, quantity)` at stored TAT `S`, every identical request is
provably denied — with closed-form decayed `remaining`/`reset`/`retry`
fields — until the earliest of

  * ``allow_at = S + inc - tol``   (the deny window ends),
  * ``S + tol``                    (the request's own clamp horizon:
                                    past it the oracle clamps the stored
                                    TAT and the closed form changes),
  * the bucket's true expiry       (past it the engine sees an absent
                                    key and first-touch semantics apply),
  * any *allowed* decision for the key (the one thing that writes).

The oracle is `core/rate_limiter.py` and every served field reproduces
its math (and therefore the kernel's, which is validated against it) bit
for bit:

    tat_eff   = S                      (unclamped inside the window)
    remaining = max((now + tol - S) // em, 0)
    reset     = S + tol - now
    retry     = S + inc - tol - now

Exactness discipline — an entry is created only when ALL of:

  * the key's **last allowed write was observed with its exact new TAT**
    (the limiter's compact="cur" tier exposes it host-side for free, and
    the full-ns result planes recover it from `reset_after_ns`); the
    denial's observed TAT must equal it.  This rules out foreign state
    (snapshot restores, writes that predate the front tier) and the
    stored-vs-first-touch ambiguity;
  * the writing request's tolerance is known, so the bucket's *true*
    expiry `tat + tol_write` is known — a later denial under different
    params must not outlive the writer's TTL;
  * every quantity involved sits far below i64 saturation (< 2^61), so
    the reference's saturating arithmetic degenerates to plain ints.

Anything that fails a check simply misses to the engine: the cache can
only ever be *conservative*, never wrong.

The state and every operation live in native/deny_cache.cpp; this module
is its ctypes facade.  The GCRA parameters come from the same C++
function the keymap's batch prep uses (native/gcra_params.h), and the
arithmetic is held to the plain Python implementation
(tests/deny_cache_oracle.py) by a differential test, not by the i64
lint.  Eviction is FIFO in O(1) on intrusive lists: write records by
last write, cached denials by insertion.  Per-key state sits in a pool
behind an open-addressing index, and the frame path carries each miss
row's pool index (its handle) from filter_frame to observe_frame, which
checks it against the row's key before it trusts it.

Keys: bytes are stored as they are; str keys (a python keymap's
identity) as their UTF-8 `surrogateescape` bytes, the exact inverse of
how the native wire driver decodes them, so the wire's raw bytes name
the same row.  A key that cannot be encoded never hits and is never
recorded (conservative).  Parameters outside i64 never hit either.

Concurrency: one native mutex guards all state; it is taken and released
inside each call and never held across Python, and ctypes drops the GIL
around the call.  Observations are ordered by a dispatch-time sequence
number so a slow fetch on one transport can never overwrite a newer
write record from another with stale state.
"""

from __future__ import annotations

import ctypes
from dataclasses import dataclass
from typing import NamedTuple, Optional

import numpy as np

NS_PER_SEC = 1_000_000_000

_I64_MIN = -(1 << 63)
_I64_MAX = (1 << 63) - 1

# Row marker dc_filter_frame writes for a row served from the cache.
ROW_SERVED = 255


@dataclass(frozen=True)
class DenyHit:
    """A cache-served denial, in exact nanoseconds (transports truncate
    to whole seconds exactly like `ThrottleResponse.from_ns`)."""

    limit: int
    remaining: int
    reset_after_ns: int
    retry_after_ns: int

    @property
    def reset_after_s(self) -> int:
        return self.reset_after_ns // NS_PER_SEC

    @property
    def retry_after_s(self) -> int:
        return self.retry_after_ns // NS_PER_SEC


_LIB = None


def _lib():
    global _LIB
    if _LIB is None:
        from ..native import deny_build_error, get_deny_lib

        _LIB = get_deny_lib()
        if _LIB is None:
            raise RuntimeError(
                f"native deny cache unavailable: {deny_build_error()}"
            )
    return _LIB


def _ptr(a: Optional[np.ndarray]):
    return None if a is None else a.ctypes.data


def _handles(ks, n: int) -> Optional[np.ndarray]:
    """A miss frame's handles as i64[n] (None stays None)."""
    if ks is None:
        return None
    ks = np.ascontiguousarray(ks, np.int64)
    if ks.shape != (n,):
        raise ValueError(f"{ks.shape} handles for a frame of {n} rows")
    return ks


def _key_bytes(key) -> Optional[bytes]:
    if isinstance(key, str):
        try:
            return key.encode("utf-8", "surrogateescape")
        except UnicodeEncodeError:
            return None
    if isinstance(key, (bytes, bytearray, memoryview)):
        return bytes(key)
    return None


def _clamp(v: int) -> int:
    """An int in i64 (a `now` or quantity beyond it compares the same
    way against every bound the cache checks)."""
    v = int(v)
    return _I64_MIN if v < _I64_MIN else _I64_MAX if v > _I64_MAX else v


def _fits(*vals) -> bool:
    return all(_I64_MIN <= int(v) <= _I64_MAX for v in vals)


def _frame(keys):
    """(blob, offsets i64[m+1], rows) of the encodable keys, where rows
    lists their positions in `keys`."""
    enc = [_key_bytes(k) for k in keys]
    rows = [i for i, b in enumerate(enc) if b is not None]
    parts = enc if len(rows) == len(enc) else [enc[i] for i in rows]
    offsets = np.zeros(len(parts) + 1, np.int64)
    if parts:
        np.cumsum([len(b) for b in parts], out=offsets[1:])
    return b"".join(parts), offsets, rows


def _param_rows(cols, n: int) -> tuple:
    """i64[n, 4] from four per-row columns, and a bool[n] of rows whose
    values do not fit i64 (None when all do)."""
    try:
        return (
            np.ascontiguousarray(
                np.stack([np.asarray(c, np.int64) for c in cols], axis=1)
            ).reshape(n, 4),
            None,
        )
    except (OverflowError, TypeError, ValueError):
        pass
    out = np.zeros((n, 4), np.int64)
    bad = np.zeros(n, bool)
    for i, row in enumerate(zip(*cols)):
        if _fits(*row):
            out[i] = [int(v) for v in row]
        else:
            bad[i] = True
    return out, bad


class FrameFilter(NamedTuple):
    """One wire frame through the front tier (see filter_frame)."""

    status: np.ndarray      # u8[n]: 0 miss, ROW_SERVED, or a shed status
    vals: np.ndarray        # i64[n, 5] wire reply values of served rows
    miss_idx: np.ndarray    # i64[m] rows the device decides
    miss_frame: Optional[tuple]  # (blob, offsets, params) of those rows
    n_hits: int
    n_stale: int
    shed_peek: int
    shed_consume: int
    hit_first: np.ndarray   # first row of each distinct served key
    hit_count: np.ndarray   # its served rows
    miss_ks: Optional[np.ndarray]  # i64[m] handles (None without a cache)


def filter_frame(cache, blob, offsets, params, budgets, now_ns,
                 st_deadline: int, st_overloaded: int,
                 admit_consume: bool = True,
                 admit_peek: bool = True) -> FrameFilter:
    """Run one captured wire frame (keys as blob + offsets, params
    i64[n, 4], deadline budgets i64[n]) through the deny cache (`cache`,
    or None for none) and the window's two admission answers, in ONE
    native call: expired rows answer `st_deadline`, cached denials are
    served, rows of a refused class answer `st_overloaded`, and the rest
    compact into the miss frame the device decides.  Every miss row's
    key holds an in-flight hold until observe_frame/fail_frame, which
    take `miss_ks` back so they need not look the keys up again."""
    lib = _lib()
    n = len(offsets) - 1
    offsets = np.ascontiguousarray(offsets, np.int64)
    params = np.ascontiguousarray(params, np.int64)
    budgets = np.ascontiguousarray(budgets, np.int64)
    # Two workspaces carved into the outputs: each array's address
    # costs a ctypes round trip (~1 us), which a frame of tens of rows
    # would otherwise pay a dozen times.
    ws = np.empty(14 * n + 7, np.int64)
    vals = ws[: 5 * n].reshape(n, 5)
    miss_params = ws[5 * n: 9 * n].reshape(n, 4)
    miss_idx, hit_first, hit_count = ws[9 * n: 12 * n].reshape(3, n)
    miss_off = ws[12 * n: 13 * n + 1]
    counts = ws[13 * n + 1: 13 * n + 7]
    miss_ks = ws[13 * n + 7:]
    bs = np.empty(n + len(blob) + 1, np.uint8)
    status, miss_blob = bs[:n], bs[n:]
    w, b = ws.ctypes.data, bs.ctypes.data
    m = lib.dc_filter_frame(
        cache._h if cache is not None else None, blob, offsets.ctypes.data,
        n, params.ctypes.data, budgets.ctypes.data, _clamp(now_ns),
        int(bool(admit_consume)) | (int(bool(admit_peek)) << 1),
        st_deadline, st_overloaded, b, w, w + 8 * 9 * n, b + n,
        w + 8 * 12 * n, w + 8 * 5 * n, w + 8 * 10 * n, w + 8 * 11 * n,
        w + 8 * (13 * n + 1), w + 8 * (13 * n + 7),
    )
    if m == n:
        miss_frame = (blob, offsets, params)
    elif m:
        miss_frame = (
            miss_blob[: miss_off[m]].tobytes(),
            miss_off[: m + 1],
            miss_params[:m],
        )
    else:
        miss_frame = None
    n_hit_keys = int(counts[5])
    return FrameFilter(
        status, vals, miss_idx[:m], miss_frame, int(counts[0]),
        int(counts[1]), int(counts[2]), int(counts[3]),
        hit_first[:n_hit_keys], hit_count[:n_hit_keys],
        miss_ks[:m] if cache is not None else None,
    )


class DenyCache:
    """Bounded O(1) map from (key, params, quantity) to an exact deny
    window, plus the per-key last-write records that certify entries."""

    def __init__(self, capacity: int = 65536) -> None:
        if capacity <= 0:
            raise ValueError("deny cache capacity must be positive")
        self._lib = _lib()
        self._h = self._lib.dc_create(int(capacity))

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.dc_destroy(h)
            self._h = None

    # ------------------------------------------------------------------ #

    @property
    def capacity(self) -> int:
        """Entries (and write records) kept; live-settable — the control
        plane's `deny_cache.capacity` actuator moves it.  Shrinking
        evicts one oldest item per later insertion."""
        return int(self._lib.dc_capacity(self._h))

    @capacity.setter
    def capacity(self, value: int) -> None:
        if int(value) <= 0:
            raise ValueError("deny cache capacity must be positive")
        self._lib.dc_set_capacity(self._h, int(value))

    def _counters(self) -> np.ndarray:
        out = np.zeros(5, np.int64)
        self._lib.dc_counters(self._h, _ptr(out))
        return out

    def __len__(self) -> int:
        return int(self._counters()[0])

    @property
    def hits(self) -> int:
        """Denials served so far."""
        return int(self._counters()[2])

    @property
    def stale_evictions(self) -> int:
        """Entries dropped because their proven window or their bucket's
        TTL lapsed."""
        return int(self._counters()[3])

    @property
    def handle_fallbacks(self) -> int:
        """Frame rows whose filter_frame handle no longer named their
        key when observed or failed (its hold was released out of band),
        so the key was looked up instead; 0 on the serving path."""
        return int(self._counters()[4])

    def next_seq(self) -> int:
        """Dispatch-order stamp: call once per launch window, *before*
        dispatch, and pass to observe() so late-arriving results from a
        concurrent transport can't roll a write record backwards."""
        return int(self._lib.dc_next_seq(self._h))

    def begin_inflight(self, key) -> None:
        """While any same-key request is being decided, lookups must
        miss (it may be allowed and mutate the bucket)."""
        k = _key_bytes(key)
        if k is not None:
            self._lib.dc_inflight(self._h, k, len(k), 1)

    def end_inflight(self, key) -> None:
        k = _key_bytes(key)
        if k is not None:
            self._lib.dc_inflight(self._h, k, len(k), -1)

    # ------------------------------------------------------------------ #

    def lookup(self, key, max_burst, count_per_period, period, quantity,
               now_ns):
        """Serve an exact denial, or None (engine decides).

        Misses when no entry, when any same-key request is in flight, or
        when `now_ns` has left the proven window (stale entries evict),
        and for pre-epoch clocks (the oracle's wall-clock fallback is
        not reproducible here)."""
        k = _key_bytes(key)
        if k is None or not _fits(
            max_burst, count_per_period, period, quantity
        ):
            return None
        out = np.empty(4, np.int64)
        if not self._lib.dc_lookup(
            self._h, k, len(k), int(max_burst), int(count_per_period),
            int(period), int(quantity), _clamp(now_ns), _ptr(out),
        ):
            return None
        limit, remaining, reset, retry = out.tolist()
        return DenyHit(limit, remaining, reset, retry)

    def lookup_window(self, keys, max_burst, count_per_period, period,
                      quantity, now_ns, mark_inflight: bool = True):
        """Bulk lookup for one serving window (shared timestamp), in one
        native call.

        The window memo is exact BECAUSE the timestamp is shared: a
        served denial is identical for every repeat (denials don't
        mutate), and once a combo misses, its repeats keep missing (the
        miss row is about to reach the engine and may mutate the bucket).

        `max_burst`/`count_per_period`/`period`/`quantity` are per-row
        sequences; `keys` is a sequence of normalized keys.  Returns
        (rows, n_hits) where rows[i] is None for engine-bound rows or a
        (limit, remaining, reset_after_ns, retry_after_ns) tuple.  With
        `mark_inflight` (the serving default), every missing key is
        marked in-flight before returning — callers MUST release each
        one (observe_window/end_inflight) after the engine decides."""
        n = len(keys)
        out = [None] * n
        if n == 0:
            return out, 0
        blob, offsets, rows = _frame(keys)
        params, bad = _param_rows(
            (max_burst, count_per_period, period, quantity), n
        )
        if bad is not None:
            keep = [i for i in rows if not bad[i]]
            if len(keep) != len(rows):
                blob, offsets, _ = _frame([keys[i] for i in keep])
                rows = keep
        m = len(rows)
        if m == 0:
            return out, 0
        if m != n:
            params = params[rows]
        hit = np.empty(m, np.uint8)
        vals = np.empty((m, 4), np.int64)
        stale = np.zeros(1, np.int64)
        n_hits = int(self._lib.dc_lookup_window(
            self._h, blob, _ptr(offsets), m, _ptr(params), _clamp(now_ns),
            int(bool(mark_inflight)), _ptr(hit), _ptr(vals), _ptr(stale),
        ))
        if n_hits:
            for j in np.flatnonzero(hit).tolist():
                out[rows[j]] = tuple(vals[j].tolist())
        return out, n_hits

    # ------------------------------------------------------------------ #

    def observe_window(self, rows, now_ns, seq) -> None:
        """Bulk observe for one decided window, in one native call,
        releasing each row's in-flight hold (the bulk twin of observe +
        end_inflight).  `rows` is an iterable of (key, max_burst,
        count_per_period, period, quantity, allowed, cur_ns) tuples in
        arrival order; cur_ns may be None (allowed rows then invalidate
        without certifying; denied rows are skipped)."""
        rows = [r for r in rows if _key_bytes(r[0]) is not None]
        n = len(rows)
        if n == 0:
            return
        keys, mb, cpp, per, q, allowed, cur = zip(*rows)
        blob, offsets, _ = _frame(keys)
        params, bad = _param_rows((mb, cpp, per, q), n)
        allowed_u8 = np.fromiter(
            (bool(a) for a in allowed), np.uint8, count=n
        )
        has_cur = np.fromiter(
            (c is not None for c in cur), np.uint8, count=n
        )
        cur_i64 = np.fromiter(
            (_clamp(c) if c is not None else 0 for c in cur), np.int64,
            count=n,
        )
        if bad is not None:
            # Beyond i64: an uncertifiable (quantity-0) row — allowed
            # still invalidates and poisons, denied is a no-op.
            params[bad] = 0
        self._observe(blob, offsets, n, params, allowed_u8, cur_i64,
                      has_cur, now_ns, seq, None)

    def _observe(self, blob, offsets, n, params, allowed, cur, has_cur,
                 now_ns, seq, ks) -> None:
        self._lib.dc_observe_window(
            self._h, blob, _ptr(offsets), n, _ptr(params), _ptr(allowed),
            _ptr(cur), _ptr(has_cur), _clamp(now_ns), _clamp(seq), _ptr(ks),
        )

    def observe_frame(self, frame, status, allowed, cur_ns, now_ns,
                      seq, ks=None) -> None:
        """observe_window for a device-decided miss frame (blob,
        offsets, params) and its result planes: a non-OK row never
        reached the table, so it only releases its hold; without a
        cur plane allowed rows invalidate and denials cannot certify.
        `ks` is the frame's FrameFilter.miss_ks, or None to look every
        key up."""
        blob, offsets, params = frame
        n = len(offsets) - 1
        ok = np.asarray(status) == 0
        allowed_u8 = (ok & (np.asarray(allowed) != 0)).astype(np.uint8)
        cur = has_cur = None
        if cur_ns is not None:
            cur = np.ascontiguousarray(cur_ns, np.int64)
            has_cur = ok.astype(np.uint8)
        self._observe(
            blob, np.ascontiguousarray(offsets, np.int64), n,
            np.ascontiguousarray(params, np.int64), allowed_u8, cur,
            has_cur, now_ns, seq, _handles(ks, n),
        )

    def fail_frame(self, frame, ks=None) -> None:
        """fail_window for a miss frame whose launch failed after its
        writes may have committed (`ks` as in observe_frame)."""
        blob, offsets, _params = frame
        offsets = np.ascontiguousarray(offsets, np.int64)
        n = len(offsets) - 1
        ks = _handles(ks, n)
        self._lib.dc_fail_window(self._h, blob, _ptr(offsets), n, _ptr(ks))

    def release_window(self, keys) -> None:
        """Release in-flight holds for rows that never reached a launch
        (shed rows): the bulk twin of end_inflight.  For rows whose
        launch may have COMMITTED before the failure, use fail_window —
        a plain release would leave entries/records that an unobserved
        write has invalidated."""
        blob, offsets, rows = _frame(keys)
        if rows:
            self._lib.dc_release_window(
                self._h, blob, _ptr(offsets), len(rows)
            )

    def fail_window(self, keys) -> None:
        """A launch failed after its writes may have committed (e.g. a
        post-launch fetch error): release each row's in-flight hold AND
        conservatively drop the key's cached denials and write record —
        an unobserved allow may have moved the TAT, so neither can
        certify exactness any longer."""
        blob, offsets, rows = _frame(keys)
        if rows:
            self._lib.dc_fail_window(
                self._h, blob, _ptr(offsets), len(rows), None
            )

    # ------------------------------------------------------------------ #

    def observe(self, key, max_burst, count_per_period, period, quantity,
                now_ns, allowed, seq, cur_ns=None, reset_after_ns=None,
                retry_after_ns=None) -> None:
        """Feed one engine-decided OK result, in arrival order.

        `cur_ns` is the request's exact observed TAT when the launch
        used the compact="cur" tier (new TAT for allowed rows, effective
        TAT for denied rows); full-ns results recover the same values
        from `reset_after_ns`/`retry_after_ns` instead.  Rows offering
        neither still invalidate on allowed — they just can't certify."""
        k = _key_bytes(key)
        if k is None:
            return
        if not _fits(max_burst, count_per_period, period):
            # Beyond i64: treated as an uncertifiable (quantity-0) row.
            max_burst = count_per_period = period = quantity = 0
        flags = (
            (cur_ns is not None)
            | (reset_after_ns is not None) << 1
            | (retry_after_ns is not None) << 2
        )
        self._lib.dc_observe(
            self._h, k, len(k), int(max_burst), int(count_per_period),
            int(period), _clamp(quantity), _clamp(now_ns), int(bool(allowed)),
            _clamp(seq), flags,
            _clamp(cur_ns) if cur_ns is not None else 0,
            _clamp(reset_after_ns) if reset_after_ns is not None else 0,
            _clamp(retry_after_ns) if retry_after_ns is not None else 0,
        )

    def prewarm(self, keys) -> int:
        """Refresh confirmed-hot keys against FIFO eviction (the
        insight tier's feedback loop): every live entry and write
        record for `keys` moves to the END of its eviction queue, so
        under cache pressure the hottest abuse keys — the ones the
        cache pays off most for — are the last evicted.  Exactness is
        untouched: nothing is created, only re-ordered; a key with no
        certified state is a no-op.  Returns the number of refreshed
        keys."""
        blob, offsets, rows = _frame(keys)
        if not rows:
            return 0
        return int(
            self._lib.dc_prewarm(self._h, blob, _ptr(offsets), len(rows))
        )

    def on_sweep(self, now_ns: int) -> int:
        """Expiry sweep ran on the table at `now_ns`: drop every entry
        whose bucket it vacated (the slot is gone even for a later
        regressed clock).  Returns the eviction count."""
        return int(self._lib.dc_sweep(self._h, _clamp(now_ns)))

    def clear(self) -> None:
        """Full invalidation: snapshot restore / param-surface changes —
        anything that rewrites bucket state out from under the cache."""
        self._lib.dc_clear(self._h)

    # ------------------------------------------------------------------ #
    # Read-only views (tests, debugging): keys come back as the stored
    # bytes, lists in eviction order (oldest first).

    def record(self, key):
        """The key's write record (tat, tol, seq), or None."""
        k = _key_bytes(key)
        if k is None:
            return None
        out = np.empty(3, np.int64)
        if not self._lib.dc_record(self._h, k, len(k), _ptr(out)):
            return None
        return tuple(out.tolist())

    def _export(self, what: int, width: int) -> list:
        rows_n = ctypes.c_int64()
        bytes_n = ctypes.c_int64()
        while True:
            self._lib.dc_export(
                self._h, what, None, 0, None, None, 0,
                ctypes.byref(rows_n), ctypes.byref(bytes_n),
            )
            rows, size = rows_n.value, bytes_n.value
            blob = np.empty(max(size, 1), np.uint8)
            off = np.empty(rows + 1, np.int64)
            vals = np.empty((max(rows, 1), width), np.int64)
            got = self._lib.dc_export(
                self._h, what, _ptr(blob), size, _ptr(off), _ptr(vals),
                rows, ctypes.byref(rows_n), ctypes.byref(bytes_n),
            )
            if got >= 0:
                break
        raw = blob.tobytes()
        off = off.tolist()
        return [
            (raw[off[i]: off[i + 1]], tuple(vals[i].tolist()))
            for i in range(got)
        ]

    def records(self) -> list:
        """[(key, (tat, tol, seq))], least recently written first."""
        return self._export(0, 3)

    def entries(self) -> list:
        """[(key, (mb, cpp, period, q, tat, em, tol, inc, limit,
        expiry))], oldest insertion first."""
        return self._export(1, 10)

    def inflight(self) -> dict:
        """{key: holds} for every key with an in-flight hold."""
        return {k: v[0] for k, v in self._export(2, 1)}
