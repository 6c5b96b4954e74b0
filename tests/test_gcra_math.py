"""GCRA engine tests, ported from the reference's `core/tests.rs`.

Virtual time: `now_ns` is an explicit input, so tests add whole-second /
millisecond offsets to a fixed base timestamp exactly like the reference
passes `now + Duration::from_secs(n)`.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from throttlecrab_tpu import (
    AdaptiveStore,
    CellError,
    PeriodicStore,
    ProbabilisticStore,
    RateLimiter,
)
from throttlecrab_tpu.core.i64 import I64_MAX
from throttlecrab_tpu.tpu import sat

NS = 1_000_000_000
# Fixed virtual base; all time travel is expressed as offsets from it.
BASE = 1_753_700_000 * NS


def secs(n: float) -> int:
    return int(n * NS)


def millis(n: int) -> int:
    return n * NS // 1000


@pytest.fixture
def limiter():
    return RateLimiter(PeriodicStore())


def test_basic_rate_limiting(limiter):
    allowed, result = limiter.rate_limit("test", 5, 10, 60, 1, BASE)
    assert allowed
    assert result.limit == 5
    assert result.remaining == 4


def test_burst_capacity(limiter):
    for i in range(5):
        allowed, result = limiter.rate_limit("burst_test", 5, 10, 60, 1, BASE)
        assert allowed, f"request {i + 1} should be allowed"
        assert result.remaining == 5 - (i + 1)

    allowed, result = limiter.rate_limit("burst_test", 5, 10, 60, 1, BASE)
    assert not allowed
    assert result.remaining == 0
    assert result.retry_after_secs > 0


def test_rate_replenishment(limiter):
    allowed1, _ = limiter.rate_limit("replenish_test", 2, 60, 60, 1, BASE)
    allowed2, _ = limiter.rate_limit("replenish_test", 2, 60, 60, 1, BASE)
    assert allowed1 and allowed2

    allowed3, _ = limiter.rate_limit("replenish_test", 2, 60, 60, 1, BASE)
    assert not allowed3

    allowed4, _ = limiter.rate_limit(
        "replenish_test", 2, 60, 60, 1, BASE + secs(1)
    )
    assert allowed4


def test_different_keys(limiter):
    allowed1, _ = limiter.rate_limit("key1", 2, 2, 60, 1, BASE)
    allowed2, _ = limiter.rate_limit("key2", 2, 2, 60, 1, BASE)
    assert allowed1 and allowed2

    allowed3, _ = limiter.rate_limit("key1", 2, 2, 60, 1, BASE)
    assert allowed3
    allowed4, _ = limiter.rate_limit("key1", 2, 2, 60, 1, BASE)
    assert not allowed4

    allowed5, _ = limiter.rate_limit("key2", 2, 2, 60, 1, BASE)
    assert allowed5
    allowed6, _ = limiter.rate_limit("key2", 2, 2, 60, 1, BASE)
    assert not allowed6


def test_quantity_parameter(limiter):
    allowed1, result1 = limiter.rate_limit("quantity_test", 10, 10, 60, 5, BASE)
    assert allowed1
    assert result1.remaining == 5

    allowed2, result2 = limiter.rate_limit("quantity_test", 10, 10, 60, 6, BASE)
    assert not allowed2
    assert result2.remaining == 5

    allowed3, result3 = limiter.rate_limit("quantity_test", 10, 10, 60, 5, BASE)
    assert allowed3
    assert result3.remaining == 0


def test_negative_quantity_error(limiter):
    with pytest.raises(CellError):
        limiter.rate_limit("test", 10, 10, 60, -1, BASE)


def test_invalid_parameters(limiter):
    with pytest.raises(CellError):
        limiter.rate_limit("test", 0, 10, 60, 1, BASE)
    with pytest.raises(CellError):
        limiter.rate_limit("test", 10, 0, 60, 1, BASE)
    with pytest.raises(CellError):
        limiter.rate_limit("test", 10, 10, 0, 1, BASE)


def test_large_quantity_overflow_protection(limiter):
    allowed, _ = limiter.rate_limit(
        "overflow_test", 10, 10, 60, I64_MAX // 2, BASE
    )
    assert not allowed


def test_saturating_arithmetic(limiter):
    # Large burst capacity and large count per period must not blow up.
    limiter.rate_limit("saturate_test", I64_MAX // 1000, 100, 60, 1, BASE)
    limiter.rate_limit("saturate_test2", 10, I64_MAX // 1000, 60, 1, BASE)


def test_remaining_count_accuracy(limiter):
    burst, rate, period = 5, 10, 60  # 1 token / 6 s

    allowed, result = limiter.rate_limit("remaining_test", burst, rate, period, 1, BASE)
    assert allowed
    assert result.remaining == 4

    for i in range(2, 6):
        allowed, result = limiter.rate_limit(
            "remaining_test", burst, rate, period, 1, BASE
        )
        assert allowed, f"request {i} should be allowed"
        assert result.remaining == 5 - i

    allowed, result = limiter.rate_limit("remaining_test", burst, rate, period, 1, BASE)
    assert not allowed
    assert result.remaining == 0
    assert result.retry_after_secs > 0

    after_replenish = BASE + secs(6)
    allowed, result = limiter.rate_limit(
        "remaining_test", burst, rate, period, 1, after_replenish
    )
    assert allowed
    assert result.remaining == 0

    allowed, result = limiter.rate_limit(
        "remaining_test", burst, rate, period, 1, after_replenish
    )
    assert not allowed
    assert result.remaining == 0

    # Larger quantities.
    allowed, result = limiter.rate_limit(
        "quantity_remaining", burst, rate, period, 3, BASE
    )
    assert allowed
    assert result.remaining == 2

    allowed, result = limiter.rate_limit(
        "quantity_remaining", burst, rate, period, 3, BASE
    )
    assert not allowed
    assert result.remaining == 2

    allowed, result = limiter.rate_limit(
        "quantity_remaining", burst, rate, period, 2, BASE
    )
    assert allowed
    assert result.remaining == 0

    # High rate: 600/60s = 10/s.
    allowed, result = limiter.rate_limit("high_rate", 10, 600, 60, 1, BASE)
    assert allowed
    assert result.remaining == 9

    for _ in range(9):
        limiter.rate_limit("high_rate", 10, 600, 60, 1, BASE)

    allowed, result = limiter.rate_limit("high_rate", 10, 600, 60, 1, BASE + secs(1))
    assert allowed
    assert result.remaining < 10


@pytest.mark.parametrize(
    "store_factory", [PeriodicStore, AdaptiveStore, ProbabilisticStore]
)
def test_remaining_count_all_stores(store_factory):
    limiter = RateLimiter(store_factory())
    for i in range(1, 4):
        allowed, result = limiter.rate_limit("test_key", 3, 6, 60, 1, BASE)
        assert allowed, f"request {i} should be allowed"
        assert result.remaining == 3 - i

    allowed, result = limiter.rate_limit("test_key", 3, 6, 60, 1, BASE)
    assert not allowed
    assert result.remaining == 0

    # 6/60s = 1 token / 10 s.
    allowed, result = limiter.rate_limit("test_key", 3, 6, 60, 1, BASE + secs(10))
    assert allowed
    assert result.remaining == 0


def test_edge_cases_zero_remaining(limiter):
    # Exact replenishment timing: 120/60s = 2/s.
    allowed, result = limiter.rate_limit("exact_timing", 2, 120, 60, 1, BASE)
    assert allowed and result.remaining == 1
    allowed, result = limiter.rate_limit("exact_timing", 2, 120, 60, 1, BASE)
    assert allowed and result.remaining == 0

    allowed, result = limiter.rate_limit(
        "exact_timing", 2, 120, 60, 1, BASE + millis(500)
    )
    assert allowed and result.remaining == 0

    # Division-by-zero protection.
    with pytest.raises(CellError):
        limiter.rate_limit("zero_period", 10, 10, 0, 1, BASE)

    # Fractional tokens: 7/60s ≈ 8.57 s per token.
    allowed, result = limiter.rate_limit("fractional", 3, 7, 60, 1, BASE)
    assert allowed and result.remaining == 2
    limiter.rate_limit("fractional", 3, 7, 60, 1, BASE)
    limiter.rate_limit("fractional", 3, 7, 60, 1, BASE)

    allowed, _ = limiter.rate_limit("fractional", 3, 7, 60, 1, BASE + secs(8))
    assert not allowed
    allowed, result = limiter.rate_limit("fractional", 3, 7, 60, 1, BASE + secs(9))
    assert allowed and result.remaining == 0

    # Maximum values.
    allowed, result = limiter.rate_limit("max_burst", I64_MAX // 1000, 100, 60, 1, BASE)
    assert allowed
    assert result.remaining > 0


def test_quantity_variations_and_replenishment(limiter):
    # burst=10, 60/60s = 1/s.
    allowed, result = limiter.rate_limit("multi_quantity", 10, 60, 60, 5, BASE)
    assert allowed and result.remaining == 5

    allowed, result = limiter.rate_limit("multi_quantity", 10, 60, 60, 6, BASE)
    assert not allowed and result.remaining == 5

    allowed, result = limiter.rate_limit("multi_quantity", 10, 60, 60, 5, BASE)
    assert allowed and result.remaining == 0

    allowed, result = limiter.rate_limit(
        "multi_quantity", 10, 60, 60, 2, BASE + secs(3)
    )
    assert allowed and result.remaining == 1

    # Gradual replenishment: burst=5, 120/60s = 2/s.
    for ms, expected_available, expected_remaining in [
        (500, 1, 0),
        (1000, 2, 1),
        (1500, 3, 2),
        (2000, 4, 3),
        (2500, 5, 4),
    ]:
        key = f"gradual_replenish_{ms}"
        for _ in range(5):
            limiter.rate_limit(key, 5, 120, 60, 1, BASE)
        allowed, result = limiter.rate_limit(key, 5, 120, 60, 1, BASE + millis(ms))
        assert allowed, f"at {ms}ms should be allowed"
        assert result.remaining == expected_remaining, (
            f"at {ms}ms: {expected_available} available, expected "
            f"{expected_remaining} remaining after use"
        )


def test_complex_replenishment_scenarios(limiter):
    # Partial burst usage: burst=8, 240/60s = 4/s.
    allowed, result = limiter.rate_limit("partial_burst", 8, 240, 60, 6, BASE)
    assert allowed and result.remaining == 2

    allowed, result = limiter.rate_limit(
        "partial_burst", 8, 240, 60, 1, BASE + millis(500)
    )
    assert allowed and result.remaining == 3

    allowed, result = limiter.rate_limit(
        "partial_burst", 8, 240, 60, 1, BASE + millis(1500)
    )
    assert allowed and result.remaining == 6

    # Slow replenishment: burst=3, 6/60s = 1 per 10 s.
    for _ in range(3):
        limiter.rate_limit("slow_replenish", 3, 6, 60, 1, BASE)
    allowed, _ = limiter.rate_limit("slow_replenish", 3, 6, 60, 1, BASE + secs(5))
    assert not allowed
    allowed, result = limiter.rate_limit("slow_replenish", 3, 6, 60, 1, BASE + secs(10))
    assert allowed and result.remaining == 0
    allowed, result = limiter.rate_limit("slow_replenish", 3, 6, 60, 1, BASE + secs(20))
    assert allowed and result.remaining == 0

    # Fractional accumulation: burst=5, 100/60s = 0.6 s per token.
    for ms, should_allow, expected_remaining in [
        (600, True, 0),
        (1200, True, 1),
        (1800, True, 2),
        (2400, True, 3),
        (3000, True, 4),
    ]:
        key = f"fractional_accumulation_{ms}"
        for _ in range(5):
            limiter.rate_limit(key, 5, 100, 60, 1, BASE)
        allowed, result = limiter.rate_limit(key, 5, 100, 60, 1, BASE + millis(ms))
        assert allowed == should_allow, f"at {ms}ms"
        if allowed:
            assert result.remaining == expected_remaining, f"at {ms}ms"


def test_quantity_edge_cases(limiter):
    # Zero quantity is a free probe.
    allowed, result = limiter.rate_limit("zero_quantity", 10, 100, 60, 0, BASE)
    assert allowed
    assert result.remaining == 10

    with pytest.raises(CellError):
        limiter.rate_limit("neg_quantity", 10, 100, 60, -5, BASE)

    allowed, result = limiter.rate_limit("large_quantity", 5, 100, 60, 10, BASE)
    assert not allowed
    assert result.remaining == 5

    allowed, result = limiter.rate_limit("exact_burst", 10, 100, 60, 10, BASE)
    assert allowed
    assert result.remaining == 0

    # burst=20, 600/60s = 10/s.
    key = "large_quantity_replenish"
    allowed, result = limiter.rate_limit(key, 20, 600, 60, 15, BASE)
    assert allowed and result.remaining == 5

    allowed, result = limiter.rate_limit(key, 20, 600, 60, 12, BASE + secs(1))
    assert allowed and result.remaining == 3

    allowed, result = limiter.rate_limit(key, 20, 600, 60, 5, BASE + secs(1))
    assert not allowed and result.remaining == 3


def test_rapid_time_changes(limiter):
    allowed1, _ = limiter.rate_limit("time_jump", 3, 10, 60, 1, BASE)
    assert allowed1

    # Jump backward 5 seconds: must not raise.
    limiter.rate_limit("time_jump", 3, 10, 60, 1, BASE - secs(5))

    allowed2, _ = limiter.rate_limit("time_jump", 3, 10, 60, 1, BASE + secs(10))
    assert allowed2

    for i in range(5):
        jittered = BASE + secs(i) if i % 2 == 0 else BASE - secs(i)
        limiter.rate_limit("time_jitter", 10, 10, 60, 1, jittered)


def test_pre_epoch_clock_fallback(limiter):
    # A pre-epoch (negative) timestamp falls back to wall-clock minus one
    # period (rate_limiter.rs:126-144) instead of erroring.
    allowed, _ = limiter.rate_limit("skew", 5, 10, 60, 1, -NS)
    assert allowed


def test_burst_one_ttl_zero_quirk(limiter):
    # burst=1 means tolerance 0; the first allowed write stores TAT=now with
    # TTL 0, which is already expired at the same instant — so a second
    # check at the exact same timestamp is allowed again.  This mirrors the
    # reference's TTL formula (rate_limiter.rs:179-183) + expiry-filtering
    # get (periodic.rs:175-181).
    allowed, _ = limiter.rate_limit("b1", 1, 1, 60, 1, BASE)
    assert allowed
    allowed, _ = limiter.rate_limit("b1", 1, 1, 60, 1, BASE)
    assert allowed
    # In fact with burst=1 the stored TAT always equals `now` and the TTL is
    # always 0, so a burst-1 limiter never denies — at any timestamp.
    allowed, _ = limiter.rate_limit("b1", 1, 1, 60, 1, BASE + 1)
    assert allowed


def test_retry_after_when_denied(limiter):
    # burst=2, 60/60s: E=1s, tolerance=1s.
    allowed, result = limiter.rate_limit("retry", 2, 60, 60, 1, BASE)
    assert allowed
    assert result.retry_after_ns == 0
    assert result.reset_after_ns == NS  # tat=now, +tolerance
    assert result.remaining == 1

    allowed, result = limiter.rate_limit("retry", 2, 60, 60, 1, BASE)
    assert allowed
    assert result.remaining == 0
    assert result.reset_after_ns == 2 * NS

    allowed, result = limiter.rate_limit("retry", 2, 60, 60, 1, BASE)
    assert not allowed
    assert result.retry_after_ns == NS
    assert result.reset_after_ns == 2 * NS
    assert result.remaining == 0


# ---------------------------------------------------------------------- #
# The device kernels' saturating closed forms (tpu/sat.py) against exact
# Python integer arithmetic, on an edge grid plus random pairs inside
# each form's documented domain.

I64_MIN = -(1 << 63)
I64_EDGES = [
    0, 1, -1, 2, -2, (1 << 31) - 1, 1 << 31, -(1 << 31),
    (1 << 32) - 1, 1 << 32, (1 << 62), -(1 << 62),
    I64_MAX, I64_MIN, NS, -NS, (1 << 61), 977,
]


def _clamp(x: int) -> int:
    return max(I64_MIN, min(I64_MAX, x))


def _div_trunc(a: int, b: int) -> int:
    q = abs(a) // b
    return q if a >= 0 else -q


# form -> (exact value, domain of the pair)
_SAT_FORMS = {
    "sat_add": (lambda a, b: _clamp(a + b), lambda a, b: True),
    "sat_sub": (lambda a, b: _clamp(a - b), lambda a, b: True),
    "sat_add_nn": (lambda a, b: _clamp(a + b), lambda a, b: b >= 0),
    "sat_sub_nn": (lambda a, b: _clamp(a - b), lambda a, b: b >= 0),
    "sat_mul_nonneg": (
        lambda a, b: _clamp(a * b), lambda a, b: a >= 0 and b >= 0
    ),
    "div_trunc": (_div_trunc, lambda a, b: b > 0),
}


@pytest.mark.parametrize("form", list(_SAT_FORMS))
def test_sat_closed_form_matches_exact_int(form):
    """Each saturating form (clamped to i64) and div_trunc (truncated
    toward zero) equals exact integer arithmetic on every pair."""
    exact, in_domain = _SAT_FORMS[form]
    rng = np.random.default_rng(sum(map(ord, form)))
    rand = rng.integers(
        I64_MIN, I64_MAX, 1024, dtype=np.int64, endpoint=True
    )
    pairs = [(a, b) for a in I64_EDGES for b in I64_EDGES]
    pairs += list(zip(rand[:512].tolist(), rand[512:].tolist()))
    # Random pairs outside the domain are folded into it.
    pairs += [(abs(a) >> 1, abs(b) >> 1) for a, b in pairs]
    pairs += [(a, (abs(b) >> 1) + 1) for a, b in pairs]
    pairs = [(a, b) for a, b in pairs if in_domain(a, b)]
    a = jnp.asarray([p[0] for p in pairs], jnp.int64)
    b = jnp.asarray([p[1] for p in pairs], jnp.int64)
    got = np.asarray(getattr(sat, form)(a, b)).tolist()
    want = [exact(x, y) for x, y in pairs]
    bad = [(p, g, w) for p, g, w in zip(pairs, got, want) if g != w]
    assert not bad, f"{form}: {len(bad)} of {len(pairs)} differ: {bad[:3]}"
