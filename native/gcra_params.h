// GCRA parameter derivation shared by the keymap's batch prep
// (keymap.cpp tk_prepare_batch) and the deny cache (deny_cache.cpp), so
// the cache's certified arithmetic and the kernel's launch rows can never
// drift apart.
//
// The reference's exact f64 pipeline (rate/mod.rs:164-176: f64 multiply
// and divide, truncating cast; rate_limiter.rs:122: tolerance = emission
// * ((burst - 1) as u32), the product wrapping at 64 bits), bit-identical
// to limiter.derive_params: numpy and C++ both follow IEEE-754 double
// semantics here.

#pragma once

#include <cstdint>

// Valid limiter parameters (the reference's InvalidRateLimitParameters
// check); gcra_derive is only meaningful for these.
inline bool gcra_params_valid(int64_t burst, int64_t count, int64_t period) {
    return burst > 0 && count > 0 && period > 0;
}

// (emission_ns, tolerance_ns) for valid parameters.
inline void gcra_derive(int64_t burst, int64_t count, int64_t period,
                        int64_t* em_out, int64_t* tol_out) {
    const double emission_f =
        static_cast<double>(period) * 1e9 / static_cast<double>(count);
    int64_t em;
    if (emission_f >= 9223372036854775808.0)  // 2^63
        em = INT64_MAX;
    else
        em = static_cast<int64_t>(emission_f);
    if (em < 0) em = 0;
    const uint64_t b32 = static_cast<uint64_t>(burst - 1) & 0xFFFFFFFFull;
    *em_out = em;
    *tol_out = static_cast<int64_t>(
        static_cast<uint64_t>(em) * b32);  // wrapping, as reference
}
