// Exact negative-decision cache of the front tier: state and window
// operations in native code.
//
// throttlecrab_tpu/front/deny_cache.py documents the semantics (when a
// denial is provably repeatable, and the closed-form fields a cached
// denial serves) and wraps this library; tests/deny_cache_oracle.py is
// the plain Python implementation the differential test holds it to,
// operation by operation, including both eviction orders.
//
// State:
//   - one KeyState per key with a write record, an in-flight hold or a
//     cached denial (freed when it has none of the three);
//   - the key's last observed allowed write (tat, tol, seq), on a FIFO
//     list ordered by last write (a refresh moves the record to the back);
//   - cached denials (Entry), keyed by (key, burst, count, period,
//     quantity), on a FIFO list ordered by insertion, and on their key's
//     own list for O(1) invalidation.
// Both FIFO lists are intrusive, so eviction at capacity is O(1): a
// Python dict popped from its front (`next(iter(d))`) walks every hole
// earlier pops left, ~12 us a call on the serving path.
//
// Layout.  On uniform traffic every row is a key the cache has long
// forgotten: the filter creates its KeyState for the hold, the observe
// records its write, and the oldest record's key is freed.  So KeyStates
// live in one pool (a vector with a free list, reached by u32 index: the
// vector moves as it grows, so links are indices, never pointers), keys
// up to KEY_INLINE bytes sit inside their KeyState, and the key index is
// open addressing over u32 pool indices with hash tags (linear probing,
// backward-shift deletion, doubled at half load).  Once the pool and the
// index reach the working set, a row allocates nothing.  The frame path
// hands the filter's pool indices to the observe, which checks each
// (live, same key bytes) before it trusts it.
//
// One mutex guards all state.  Every entry point takes it and releases
// it before returning, and nothing here calls back into Python, so it is
// a leaf lock; ctypes drops the GIL around each call.
//
// Exposed as a C ABI for ctypes (no pybind11 in this environment).

#include <cstdint>
#include <cstring>
#include <mutex>
#include <string_view>
#include <unordered_map>
#include <vector>

#include "gcra_params.h"

namespace {

// All cached quantities sit far below i64 saturation (2^61 ns is ~73
// years), so the reference's saturating arithmetic is plain arithmetic.
constexpr int64_t BOUND = int64_t(1) << 61;
constexpr int64_t I32_MAX = 2147483647ll;
constexpr int64_t NSEC = 1000000000ll;

// Cached-denial outcome of one row in the frame filter.
constexpr uint8_t ROW_SERVED = 255;

// No KeyState: an empty index slot, the end of a list.
constexpr uint32_t NIL = UINT32_MAX;
// Keys up to this many bytes live inside their KeyState.
constexpr size_t KEY_INLINE = 24;
// Rows ahead whose index slot a window loop prefetches.
constexpr int64_t AHEAD = 8;

struct Entry {
    uint32_t ks;  // the key's pool index
    int64_t mb, cpp, per, q;  // the combo (with the key)
    int64_t tat, em, tol, inc, limit, expiry;
    Entry* prev = nullptr;  // entry FIFO
    Entry* next = nullptr;
    Entry* kprev = nullptr;  // the key's combos, in first-insert order
    Entry* knext = nullptr;
    uint64_t served = 0;  // window stamp: served a hit in this window
};

struct KeyState {
    uint32_t hash = 0;  // low half of the key's hash: home slot and tag
    uint32_t klen = 0;
    union {
        char kin[KEY_INLINE];  // klen <= KEY_INLINE
        char* kfar;            // else a buffer of its own
    };
    bool live = false;
    bool has_rec = false;
    int64_t inflight = 0;
    int64_t rec_tat = 0, rec_tol = 0, rec_seq = 0;
    uint32_t rprev = NIL;  // record FIFO (by last write)
    uint32_t rnext = NIL;  // ... and, while free, the free list
    Entry* ehead = nullptr;
    Entry* etail = nullptr;
    uint64_t hit_stamp = 0;  // frame filter: distinct served keys
    int64_t hit_slot = 0;

    const char* key() const { return klen <= KEY_INLINE ? kin : kfar; }
    std::string_view key_view() const { return {key(), klen}; }
};

struct Combo {
    uint32_t ks;
    int64_t mb, cpp, per, q;
    bool operator==(const Combo& o) const {
        return ks == o.ks && mb == o.mb && cpp == o.cpp && per == o.per &&
               q == o.q;
    }
};

inline uint64_t mix(uint64_t h, uint64_t v) {
    h ^= v + 0x9e3779b97f4a7c15ull + (h << 6) + (h >> 2);
    return h;
}

struct ComboHash {
    size_t operator()(const Combo& c) const {
        uint64_t h = c.ks;
        h = mix(h, static_cast<uint64_t>(c.mb));
        h = mix(h, static_cast<uint64_t>(c.cpp));
        h = mix(h, static_cast<uint64_t>(c.per));
        h = mix(h, static_cast<uint64_t>(c.q));
        return static_cast<size_t>(h);
    }
};

// Eight bytes a step, then a 64-bit finaliser (murmur3's fmix64), so
// keys that differ in one digit (`gw:0000001`, `gw:0000002`) land far
// apart in the low bits the index takes its slot from.
inline uint64_t key_hash(std::string_view s) {
    const char* p = s.data();
    size_t n = s.size();
    uint64_t h = 0x9e3779b97f4a7c15ull ^ n;
    for (; n >= 8; p += 8, n -= 8) {
        uint64_t w;
        memcpy(&w, p, 8);
        h = (h ^ w) * 0xff51afd7ed558ccdull;
        h ^= h >> 29;
    }
    uint64_t w = 0;
    memcpy(&w, p, n);
    h = (h ^ w) * 0xc4ceb9fe1a85ec53ull;
    h ^= h >> 33;
    h *= 0xff51afd7ed558ccdull;
    h ^= h >> 33;
    h *= 0xc4ceb9fe1a85ec53ull;
    h ^= h >> 33;
    return h;
}

struct Hit {
    int64_t limit, remaining, reset, retry;
};

inline std::string_view row_key(const char* blob, const int64_t* off,
                                int64_t i) {
    return std::string_view(blob + off[i],
                            static_cast<size_t>(off[i + 1] - off[i]));
}

// How a denied observation names its exact effective TAT.
enum TatFrom { TAT_CUR, TAT_PLANES, TAT_NONE };

// One slot of the key index: a pool index (NIL: empty) and its key's
// hash tag.
struct Slot {
    uint32_t ks;
    uint32_t tag;
};

struct DenyCache {
    std::mutex mu;
    int64_t capacity;
    std::vector<KeyState> pool;
    uint32_t free_head = NIL;
    std::vector<Slot> index;  // a power of two, at most half full
    size_t mask = 0;
    size_t n_keys = 0;
    std::unordered_map<Combo, Entry*, ComboHash> entries;
    Entry* ehead = nullptr;
    Entry* etail = nullptr;
    uint32_t rhead = NIL;
    uint32_t rtail = NIL;
    int64_t n_records = 0;
    int64_t seq = 0;
    int64_t hits = 0;
    int64_t stale = 0;
    int64_t handle_fallbacks = 0;
    uint64_t stamp = 0;
    // Window scratch, reused across calls (guarded by mu).
    std::vector<uint32_t> row_hash;
    std::vector<uint8_t> hit_row;
    std::vector<int64_t> hit_vals;
    std::vector<uint32_t> row_ks;

    explicit DenyCache(int64_t cap) : capacity(cap) {
        const size_t pre = static_cast<size_t>(cap < (1 << 20) ? cap : 1 << 20);
        size_t slots = 16;
        while (slots < pre * 2) slots <<= 1;
        index.assign(slots, Slot{NIL, 0});
        mask = slots - 1;
        pool.reserve(pre);
        entries.reserve(pre);
    }

    ~DenyCache() {
        for (KeyState& s : pool) {
            if (!s.live) continue;
            for (Entry* e = s.ehead; e != nullptr;) {
                Entry* nx = e->knext;
                delete e;
                e = nx;
            }
            if (s.klen > KEY_INLINE) delete[] s.kfar;
        }
    }

    // -- keys ---------------------------------------------------------- //

    bool key_is(uint32_t ks, std::string_view k) const {
        const KeyState& s = pool[ks];
        return s.klen == k.size() && memcmp(s.key(), k.data(), k.size()) == 0;
    }

    // The key's pool index, or NIL with *slot the empty slot that ends
    // its probe (where insert puts it, while the index is unchanged).
    uint32_t probe(std::string_view k, uint32_t h, size_t* slot) const {
        for (size_t b = h & mask;; b = (b + 1) & mask) {
            const Slot s = index[b];
            if (s.ks == NIL) {
                *slot = b;
                return NIL;
            }
            if (s.tag == h && key_is(s.ks, k)) return s.ks;
        }
    }

    uint32_t find(std::string_view k) const {
        size_t b;
        return probe(k, static_cast<uint32_t>(key_hash(k)), &b);
    }

    // A new KeyState for `k` (absent), placed in index slot `slot`.
    uint32_t insert(std::string_view k, uint32_t h, size_t slot) {
        uint32_t ks = free_head;
        if (ks != NIL) {
            free_head = pool[ks].rnext;
            pool[ks] = KeyState();
        } else {
            ks = static_cast<uint32_t>(pool.size());
            pool.emplace_back();
        }
        KeyState& s = pool[ks];
        s.live = true;
        s.hash = h;
        s.klen = static_cast<uint32_t>(k.size());
        char* dst = s.kin;
        if (k.size() > KEY_INLINE) dst = s.kfar = new char[k.size()];
        memcpy(dst, k.data(), k.size());
        index[slot] = Slot{ks, h};
        if (++n_keys * 2 > index.size()) grow();
        return ks;
    }

    uint32_t get(std::string_view k) {
        const uint32_t h = static_cast<uint32_t>(key_hash(k));
        size_t b;
        const uint32_t ks = probe(k, h, &b);
        return ks != NIL ? ks : insert(k, h, b);
    }

    void grow() {
        std::vector<Slot> old(index.size() * 2, Slot{NIL, 0});
        old.swap(index);
        mask = index.size() - 1;
        for (const Slot s : old) {
            if (s.ks == NIL) continue;
            size_t b = s.tag & mask;
            while (index[b].ks != NIL) b = (b + 1) & mask;
            index[b] = s;
        }
    }

    // Take `ks` out of the index: backward-shift deletion, so a probe
    // never needs a tombstone to walk past.
    void unindex(uint32_t ks) {
        size_t i = pool[ks].hash & mask;
        while (index[i].ks != ks) i = (i + 1) & mask;
        for (size_t j = (i + 1) & mask; index[j].ks != NIL;
             j = (j + 1) & mask) {
            // The slot at j may fill the hole at i unless its home lies
            // after i (cyclically, in (i, j]).
            const size_t home = index[j].tag & mask;
            if (((j - home) & mask) >= ((j - i) & mask)) {
                index[i] = index[j];
                i = j;
            }
        }
        index[i] = Slot{NIL, 0};
        n_keys--;
    }

    void maybe_free(uint32_t ks) {
        KeyState& s = pool[ks];
        if (s.inflight == 0 && !s.has_rec && s.ehead == nullptr) {
            unindex(ks);
            if (s.klen > KEY_INLINE) delete[] s.kfar;
            s.live = false;
            s.rnext = free_head;
            free_head = ks;
        }
    }

    void release_hold(uint32_t ks) {
        if (pool[ks].inflight > 0) pool[ks].inflight--;
    }

    // A row's KeyState through the handle the frame filter gave it,
    // when that still names the row's key; else by probing (counted).
    uint32_t by_handle(int64_t ks, std::string_view k, uint32_t* h,
                       size_t* slot) {
        if (0 <= ks && ks < static_cast<int64_t>(pool.size()) &&
            pool[ks].live && key_is(static_cast<uint32_t>(ks), k))
            return static_cast<uint32_t>(ks);
        handle_fallbacks++;
        *h = static_cast<uint32_t>(key_hash(k));
        return probe(k, *h, slot);
    }

    // Hash every row's key into row_hash (one pass, so the row loop can
    // prefetch index slots ahead of itself).
    const uint32_t* hash_rows(const char* blob, const int64_t* off,
                              int64_t n) {
        row_hash.resize(static_cast<size_t>(n));
        for (int64_t i = 0; i < n; i++)
            row_hash[i] = static_cast<uint32_t>(key_hash(row_key(blob, off, i)));
        return row_hash.data();
    }

    void prefetch_slot(uint32_t h) const {
        __builtin_prefetch(&index[h & mask]);
    }

    // -- write records ------------------------------------------------- //

    void rec_unlink(uint32_t ks) {
        KeyState& s = pool[ks];
        (s.rprev != NIL ? pool[s.rprev].rnext : rhead) = s.rnext;
        (s.rnext != NIL ? pool[s.rnext].rprev : rtail) = s.rprev;
        s.rprev = s.rnext = NIL;
        s.has_rec = false;
        n_records--;
    }

    void rec_pop(uint32_t ks) {
        if (pool[ks].has_rec) rec_unlink(ks);
    }

    // Pop-then-append: a refreshed key moves to the back, so eviction
    // tracks last-write age and a hot key is never the first evicted.
    void rec_push(uint32_t ks, int64_t tat, int64_t tol, int64_t s) {
        if (pool[ks].has_rec) rec_unlink(ks);
        KeyState& r = pool[ks];
        r.rec_tat = tat;
        r.rec_tol = tol;
        r.rec_seq = s;
        r.rprev = rtail;
        r.rnext = NIL;
        (rtail != NIL ? pool[rtail].rnext : rhead) = ks;
        rtail = ks;
        r.has_rec = true;
        n_records++;
    }

    // Evict the oldest record; `keep` is the key the caller holds (never
    // the head while capacity >= 1, since it was just appended).
    void rec_evict_head(uint32_t keep) {
        const uint32_t h = rhead;
        rec_unlink(h);
        if (rhead != NIL) {
            // The next eviction's index slot, and the one after's key.
            const KeyState& nx = pool[rhead];
            prefetch_slot(nx.hash);
            if (nx.rnext != NIL) __builtin_prefetch(&pool[nx.rnext]);
        }
        if (h != keep) maybe_free(h);
    }

    // -- cached denials ------------------------------------------------ //

    Entry* find_entry(uint32_t ks, int64_t mb, int64_t cpp, int64_t per,
                      int64_t q) {
        if (pool[ks].ehead == nullptr) return nullptr;
        auto it = entries.find(Combo{ks, mb, cpp, per, q});
        return it == entries.end() ? nullptr : it->second;
    }

    void fifo_unlink(Entry* e) {
        (e->prev ? e->prev->next : ehead) = e->next;
        (e->next ? e->next->prev : etail) = e->prev;
        e->prev = e->next = nullptr;
    }

    void fifo_append(Entry* e) {
        e->prev = etail;
        e->next = nullptr;
        (etail ? etail->next : ehead) = e;
        etail = e;
    }

    // Remove one entry (its key state stays; callers maybe_free it).
    void drop_entry(Entry* e) {
        KeyState& s = pool[e->ks];
        entries.erase(Combo{e->ks, e->mb, e->cpp, e->per, e->q});
        fifo_unlink(e);
        (e->kprev ? e->kprev->knext : s.ehead) = e->knext;
        (e->knext ? e->knext->kprev : s.etail) = e->kprev;
        delete e;
    }

    // An allowed decision mutated the bucket: every cached denial dies.
    void invalidate(uint32_t ks) {
        while (pool[ks].ehead != nullptr) drop_entry(pool[ks].ehead);
    }

    // The exact denial `e` proves at `now`, or false once `now` has left
    // the proven window (the entry is then stale).
    static bool serve(const Entry* e, int64_t now, Hit* out) {
        const int64_t allow_at = e->tat + e->inc - e->tol;
        int64_t horizon = allow_at;
        if (e->tat + e->tol < horizon) horizon = e->tat + e->tol;
        if (e->expiry < horizon) horizon = e->expiry;
        if (now >= horizon) return false;
        const int64_t room = now + e->tol - e->tat;
        out->limit = e->limit;
        out->remaining = room >= 0 ? room / e->em : 0;
        out->reset = e->tat + e->tol - now;
        out->retry = allow_at - now;
        return true;
    }

    // -- lookups ------------------------------------------------------- //

    // One window at a shared timestamp.  A combo served once is served
    // for the rest of the window (denials do not mutate); a combo that
    // missed keeps missing (its first miss is either absent, in flight
    // or stale-evicted, and none of that is undone inside the window).
    // With `mark`, every missing row takes an in-flight hold on its key
    // and row_ks_out[i] names that key; served rows name theirs too.
    int64_t lookup_rows(const char* blob, const int64_t* off, int64_t n,
                        const int64_t* params, int64_t now, bool mark,
                        uint8_t* hit_out, int64_t* vals_out,
                        uint32_t* row_ks_out, int64_t* stale_out) {
        int64_t n_hits = 0;
        int64_t n_stale = 0;
        if (now < 0) {
            // Pre-epoch clocks take the oracle's wall-clock fallback:
            // not reproducible here, so every row misses.
            for (int64_t i = 0; i < n; i++) {
                hit_out[i] = 0;
                uint32_t ks = NIL;
                if (mark) {
                    ks = get(row_key(blob, off, i));
                    pool[ks].inflight++;
                }
                if (row_ks_out) row_ks_out[i] = ks;
            }
            *stale_out = 0;
            return 0;
        }
        const uint64_t st = ++stamp;
        const uint32_t* hs = hash_rows(blob, off, n);
        for (int64_t i = 0; i < n; i++) {
            if (i + AHEAD < n) prefetch_slot(hs[i + AHEAD]);
            const std::string_view k = row_key(blob, off, i);
            const int64_t* p = params + i * 4;
            size_t slot = 0;
            uint32_t ks = probe(k, hs[i], &slot);
            Entry* e =
                ks != NIL ? find_entry(ks, p[0], p[1], p[2], p[3]) : nullptr;
            Hit h;
            bool served = false;
            if (e != nullptr && e->served == st) {
                served = serve(e, now, &h);
            } else if (e != nullptr && pool[ks].inflight == 0) {
                if (serve(e, now, &h)) {
                    e->served = st;
                    served = true;
                } else {
                    drop_entry(e);
                    n_stale++;
                }
            }
            if (served) {
                hit_out[i] = 1;
                int64_t* v = vals_out + i * 4;
                v[0] = h.limit;
                v[1] = h.remaining;
                v[2] = h.reset;
                v[3] = h.retry;
                n_hits++;
                if (row_ks_out) row_ks_out[i] = ks;
                continue;
            }
            hit_out[i] = 0;
            if (mark) {
                if (ks == NIL) ks = insert(k, hs[i], slot);
                pool[ks].inflight++;
            } else if (ks != NIL) {
                maybe_free(ks);
                ks = NIL;
            }
            if (row_ks_out) row_ks_out[i] = ks;
        }
        hits += n_hits;
        stale += n_stale;
        *stale_out = n_stale;
        return n_hits;
    }

    // -- observations -------------------------------------------------- //

    // A denial certifies an entry only when the key's last allowed write
    // was observed exactly and the denial saw that same TAT.
    void observe_denied(uint32_t ks, int64_t mb, int64_t cpp, int64_t per,
                        int64_t q, int64_t now, TatFrom from, int64_t cur,
                        int64_t reset, int64_t retry) {
        // q=0 denials are no-ops; q > i32::MAX could push `remaining`
        // past where the wire tiers saturate and the ns planes don't.
        if (q < 1 || q > I32_MAX) return;
        if (!pool[ks].has_rec) return;
        if (!gcra_params_valid(mb, cpp, per)) return;
        int64_t em, tol;
        gcra_derive(mb, cpp, per, &em, &tol);
        if (!(0 < em && em < BOUND && 0 < tol && tol < BOUND && 0 <= now &&
              now < BOUND))
            return;
        if (em > (BOUND - 1) / q) return;  // inc = em * q >= BOUND
        const int64_t inc = em * q;
        int64_t tat;
        if (from == TAT_CUR) {
            tat = cur;
        } else if (from == TAT_PLANES && 0 < reset && reset < BOUND &&
                   0 < retry && retry < BOUND &&
                   // Both planes must name the SAME TAT, or something
                   // saturated or clamped on the way.
                   now + reset - tol == now + retry - inc + tol) {
            tat = now + reset - tol;
        } else {
            return;
        }
        if (tat != pool[ks].rec_tat) return;  // an unobserved write intervened
        const int64_t rec_tol = pool[ks].rec_tol;
        if (!(0 <= tat && tat < BOUND) || rec_tol >= BOUND) return;
        if (now >= tat + inc - tol) return;  // inconsistent with a denial
        Entry* e = find_entry(ks, mb, cpp, per, q);
        if (e == nullptr && static_cast<int64_t>(entries.size()) >= capacity &&
            ehead != nullptr) {
            const uint32_t victim = ehead->ks;
            drop_entry(ehead);
            if (victim != ks) maybe_free(victim);
        }
        if (e == nullptr) {
            KeyState& s = pool[ks];
            e = new Entry;
            e->ks = ks;
            e->mb = mb;
            e->cpp = cpp;
            e->per = per;
            e->q = q;
            e->kprev = s.etail;
            (s.etail ? s.etail->knext : s.ehead) = e;
            s.etail = e;
            entries.emplace(Combo{ks, mb, cpp, per, q}, e);
        } else {
            fifo_unlink(e);
        }
        fifo_append(e);
        e->tat = tat;
        e->em = em;
        e->tol = tol;
        e->inc = inc;
        e->limit = mb;
        e->expiry = tat + rec_tol;  // the bucket's true expiry
    }

    // One decided window, in arrival order: the bulk twin of observe +
    // end_inflight.  `cur` may be null (no row carries its exact TAT);
    // `has_cur` may be null (every row does, when `cur` is given);
    // `handles` may be null (every row's key is probed).
    void observe_rows(const char* blob, const int64_t* off, int64_t n,
                      const int64_t* params, const uint8_t* allowed,
                      const int64_t* cur, const uint8_t* has_cur, int64_t now,
                      int64_t s, const int64_t* handles) {
        const bool now_ok = 0 <= now && now < BOUND;
        const uint32_t* hs = handles ? nullptr : hash_rows(blob, off, n);
        for (int64_t i = 0; i < n; i++) {
            if (i + AHEAD < n) {
                if (hs != nullptr) {
                    prefetch_slot(hs[i + AHEAD]);
                } else if (0 <= handles[i + AHEAD] &&
                           handles[i + AHEAD] <
                               static_cast<int64_t>(pool.size())) {
                    __builtin_prefetch(&pool[handles[i + AHEAD]]);
                }
            }
            const std::string_view k = row_key(blob, off, i);
            const int64_t* p = params + i * 4;
            const bool hc = cur != nullptr && (has_cur == nullptr || has_cur[i]);
            const int64_t c = hc ? cur[i] : 0;
            uint32_t h = hs != nullptr ? hs[i] : 0;
            size_t slot = 0;
            uint32_t ks = hs != nullptr ? probe(k, h, &slot)
                                        : by_handle(handles[i], k, &h, &slot);
            if (allowed[i]) {
                // The one mutating outcome: cached denials die.
                if (ks != NIL) invalidate(ks);
                if (ks != NIL && pool[ks].has_rec && s < pool[ks].rec_seq) {
                    // stale cross-transport observation
                } else if (p[3] < 1 || !hc || !now_ok) {
                    // Unquantified / uncertified write: poison.
                    if (ks != NIL) rec_pop(ks);
                } else if (gcra_params_valid(p[0], p[1], p[2])) {
                    int64_t em, tol;
                    gcra_derive(p[0], p[1], p[2], &em, &tol);
                    if (0 < em && em < BOUND && 0 <= tol && tol < BOUND &&
                        0 <= c && c < BOUND) {
                        if (ks == NIL) ks = insert(k, h, slot);
                        rec_push(ks, c, tol, s);
                        if (n_records > capacity) rec_evict_head(ks);
                    } else if (ks != NIL) {
                        rec_pop(ks);
                    }
                }
            } else if (hc && ks != NIL) {
                observe_denied(ks, p[0], p[1], p[2], p[3], now, TAT_CUR, c, 0,
                               0);
            }
            if (ks != NIL) {
                release_hold(ks);
                maybe_free(ks);
            }
        }
    }

    // Rows that leave flight without an observation: release each row's
    // hold and, after a failed launch (`fail`), drop the key's cached
    // denials and write record.  `handles` as in observe_rows.
    void release_rows(const char* blob, const int64_t* off, int64_t n,
                      bool fail, const int64_t* handles) {
        const uint32_t* hs = handles ? nullptr : hash_rows(blob, off, n);
        for (int64_t i = 0; i < n; i++) {
            if (hs != nullptr && i + AHEAD < n) prefetch_slot(hs[i + AHEAD]);
            const std::string_view k = row_key(blob, off, i);
            uint32_t h = hs != nullptr ? hs[i] : 0;
            size_t slot = 0;
            const uint32_t ks = hs != nullptr
                                    ? probe(k, h, &slot)
                                    : by_handle(handles[i], k, &h, &slot);
            if (ks == NIL) continue;
            release_hold(ks);
            if (fail) {
                invalidate(ks);
                rec_pop(ks);
            }
            maybe_free(ks);
        }
    }

    // One engine-decided result, outside any window (no hold released).
    void observe_one(std::string_view k, int64_t mb, int64_t cpp, int64_t per,
                     int64_t q, int64_t now, bool allowed, int64_t s,
                     bool has_cur, int64_t cur, bool has_reset, int64_t reset,
                     bool has_retry, int64_t retry) {
        uint32_t ks = find(k);
        if (!allowed) {
            if (ks == NIL) return;  // no write record: can't certify
            TatFrom from = has_cur ? TAT_CUR
                           : (has_reset && has_retry) ? TAT_PLANES
                                                      : TAT_NONE;
            observe_denied(ks, mb, cpp, per, q, now, from, cur, reset, retry);
            return;
        }
        if (ks != NIL) invalidate(ks);
        if (ks != NIL && pool[ks].has_rec && s < pool[ks].rec_seq) {
            maybe_free(ks);
            return;  // stale cross-transport observation; record is newer
        }
        bool poison = false;
        int64_t tat = 0, tol = 0;
        if (q < 1) {
            // A quantity-0 probe may or may not refresh the TTL on a
            // given backend; an unquantified write poisons the record.
            poison = true;
        } else if (!gcra_params_valid(mb, cpp, per)) {
            if (ks != NIL) maybe_free(ks);
            return;
        } else {
            int64_t em;
            gcra_derive(mb, cpp, per, &em, &tol);
            if (!(0 < em && em < BOUND && 0 <= tol && tol < BOUND &&
                  0 <= now && now < BOUND)) {
                poison = true;
            } else if (has_cur) {
                tat = cur;
            } else if (has_reset && 0 < reset && reset < BOUND) {
                // allowed => current_tat = new_tat, reset = new_tat+tol-now
                tat = now + reset - tol;
            } else {
                poison = true;
            }
            if (!poison && !(0 <= tat && tat < BOUND)) poison = true;
        }
        if (poison) {
            if (ks != NIL) {
                rec_pop(ks);
                maybe_free(ks);
            }
            return;
        }
        if (ks == NIL) ks = get(k);
        rec_push(ks, tat, tol, s);
        while (n_records > capacity && rhead != NIL && rhead != ks)
            rec_evict_head(ks);
    }

    // -- whole-cache operations ---------------------------------------- //

    int64_t sweep(int64_t now) {
        int64_t dead = 0;
        for (Entry* e = ehead; e != nullptr;) {
            Entry* nx = e->next;
            if (e->expiry <= now) {
                const uint32_t ks = e->ks;
                // The next entry may belong to the same key: only free
                // the key once it holds no entry the walk can still see.
                drop_entry(e);
                dead++;
                if (pool[ks].ehead == nullptr) maybe_free(ks);
            }
            e = nx;
        }
        for (uint32_t ks = rhead; ks != NIL;) {
            const uint32_t nx = pool[ks].rnext;
            if (pool[ks].rec_tat + pool[ks].rec_tol <= now) {
                rec_unlink(ks);
                maybe_free(ks);
            }
            ks = nx;
        }
        stale += dead;
        return dead;
    }

    void clear() {
        for (size_t i = 0; i < pool.size(); i++) {
            const uint32_t ks = static_cast<uint32_t>(i);
            if (!pool[ks].live) continue;
            invalidate(ks);
            rec_pop(ks);
            maybe_free(ks);
        }
    }
};

inline DenyCache* C(void* h) { return static_cast<DenyCache*>(h); }

}  // namespace

extern "C" {

void* dc_create(int64_t capacity) { return new DenyCache(capacity); }

void dc_destroy(void* h) { delete C(h); }

void dc_set_capacity(void* h, int64_t capacity) {
    std::lock_guard<std::mutex> g(C(h)->mu);
    C(h)->capacity = capacity;
}

int64_t dc_capacity(void* h) {
    std::lock_guard<std::mutex> g(C(h)->mu);
    return C(h)->capacity;
}

// out[5]: entries, records, hits, stale evictions, handle fallbacks
// (frame rows whose handle no longer named their key, so were probed).
void dc_counters(void* h, int64_t* out) {
    DenyCache* c = C(h);
    std::lock_guard<std::mutex> g(c->mu);
    out[0] = static_cast<int64_t>(c->entries.size());
    out[1] = c->n_records;
    out[2] = c->hits;
    out[3] = c->stale;
    out[4] = c->handle_fallbacks;
}

// Dispatch-order stamp: one per launch window, taken before dispatch.
int64_t dc_next_seq(void* h) {
    std::lock_guard<std::mutex> g(C(h)->mu);
    return ++C(h)->seq;
}

// delta +1 takes an in-flight hold on the key, -1 releases one.
void dc_inflight(void* h, const char* key, int64_t len, int64_t delta) {
    DenyCache* c = C(h);
    std::lock_guard<std::mutex> g(c->mu);
    const std::string_view k(key, static_cast<size_t>(len));
    if (delta > 0) {
        c->pool[c->get(k)].inflight++;
    } else {
        const uint32_t ks = c->find(k);
        if (ks != NIL) {
            c->release_hold(ks);
            c->maybe_free(ks);
        }
    }
}

// One request: 1 and out[4] = (limit, remaining, reset_ns, retry_ns)
// when its exact denial is cached, else 0.
int64_t dc_lookup(void* h, const char* key, int64_t len, int64_t mb,
                  int64_t cpp, int64_t per, int64_t q, int64_t now,
                  int64_t* out) {
    if (now < 0) return 0;
    DenyCache* c = C(h);
    std::lock_guard<std::mutex> g(c->mu);
    const uint32_t ks = c->find(std::string_view(key, static_cast<size_t>(len)));
    if (ks == NIL) return 0;
    Entry* e = c->find_entry(ks, mb, cpp, per, q);
    if (e == nullptr || c->pool[ks].inflight > 0) return 0;
    Hit hit;
    if (!DenyCache::serve(e, now, &hit)) {
        c->drop_entry(e);
        c->stale++;
        c->maybe_free(ks);
        return 0;
    }
    c->hits++;
    out[0] = hit.limit;
    out[1] = hit.remaining;
    out[2] = hit.reset;
    out[3] = hit.retry;
    return 1;
}

// A window of keys (blob + offsets[n+1]) with params i64[n, 4] at one
// timestamp: hit[i] = 1 with vals[i, 4] for served rows.  Returns the
// number served; *stale_out the entries evicted as stale.
int64_t dc_lookup_window(void* h, const char* blob, const int64_t* off,
                         int64_t n, const int64_t* params, int64_t now,
                         int64_t mark, uint8_t* hit, int64_t* vals,
                         int64_t* stale_out) {
    DenyCache* c = C(h);
    std::lock_guard<std::mutex> g(c->mu);
    return c->lookup_rows(blob, off, n, params, now, mark != 0, hit, vals,
                          nullptr, stale_out);
}

// A decided window, in arrival order (see DenyCache::observe_rows);
// `handles` (nullable) are dc_filter_frame's miss_ks for these rows.
void dc_observe_window(void* h, const char* blob, const int64_t* off,
                       int64_t n, const int64_t* params,
                       const uint8_t* allowed, const int64_t* cur,
                       const uint8_t* has_cur, int64_t now, int64_t seq,
                       const int64_t* handles) {
    DenyCache* c = C(h);
    std::lock_guard<std::mutex> g(c->mu);
    c->observe_rows(blob, off, n, params, allowed, cur, has_cur, now, seq,
                    handles);
}

// flags: bit0 has_cur, bit1 has_reset, bit2 has_retry.
void dc_observe(void* h, const char* key, int64_t len, int64_t mb,
                int64_t cpp, int64_t per, int64_t q, int64_t now,
                int64_t allowed, int64_t seq, int64_t flags, int64_t cur,
                int64_t reset, int64_t retry) {
    DenyCache* c = C(h);
    std::lock_guard<std::mutex> g(c->mu);
    c->observe_one(std::string_view(key, static_cast<size_t>(len)), mb, cpp,
                   per, q, now, allowed != 0, seq, flags & 1, cur,
                   (flags >> 1) & 1, reset, (flags >> 2) & 1, retry);
}

// Rows that never reached a launch: release each row's hold.
void dc_release_window(void* h, const char* blob, const int64_t* off,
                       int64_t n) {
    DenyCache* c = C(h);
    std::lock_guard<std::mutex> g(c->mu);
    c->release_rows(blob, off, n, false, nullptr);
}

// A launch failed after its writes may have committed: release each
// row's hold and drop the key's cached denials and write record.
// `handles` as in dc_observe_window.
void dc_fail_window(void* h, const char* blob, const int64_t* off,
                    int64_t n, const int64_t* handles) {
    DenyCache* c = C(h);
    std::lock_guard<std::mutex> g(c->mu);
    c->release_rows(blob, off, n, true, handles);
}

// Move each key's write record and cached denials (in the key's own
// order) to the back of their eviction queues.  Returns the keys moved.
int64_t dc_prewarm(void* h, const char* blob, const int64_t* off,
                   int64_t n) {
    DenyCache* c = C(h);
    std::lock_guard<std::mutex> g(c->mu);
    int64_t moved = 0;
    for (int64_t i = 0; i < n; i++) {
        const uint32_t ks = c->find(row_key(blob, off, i));
        if (ks == NIL) continue;
        bool touched = false;
        const KeyState& s = c->pool[ks];
        if (s.has_rec) {
            c->rec_push(ks, s.rec_tat, s.rec_tol, s.rec_seq);
            touched = true;
        }
        for (Entry* e = s.ehead; e != nullptr; e = e->knext) {
            c->fifo_unlink(e);
            c->fifo_append(e);
            touched = true;
        }
        if (touched) moved++;
    }
    return moved;
}

int64_t dc_sweep(void* h, int64_t now) {
    DenyCache* c = C(h);
    std::lock_guard<std::mutex> g(c->mu);
    return c->sweep(now);
}

void dc_clear(void* h) {
    DenyCache* c = C(h);
    std::lock_guard<std::mutex> g(c->mu);
    c->clear();
}

// The key's write record: 1 and out[3] = (tat, tol, seq), or 0.
int64_t dc_record(void* h, const char* key, int64_t len, int64_t* out) {
    DenyCache* c = C(h);
    std::lock_guard<std::mutex> g(c->mu);
    const uint32_t ks = c->find(std::string_view(key, static_cast<size_t>(len)));
    if (ks == NIL || !c->pool[ks].has_rec) return 0;
    out[0] = c->pool[ks].rec_tat;
    out[1] = c->pool[ks].rec_tol;
    out[2] = c->pool[ks].rec_seq;
    return 1;
}

// Read-only export, in eviction order: what = 0 write records (vals
// [tat, tol, seq]), 1 cached denials (vals [mb, cpp, period, q, tat, em,
// tol, inc, limit, expiry]), 2 in-flight holds (vals [count]).  With
// blob == nullptr it only sizes: *rows_out and *bytes_out.  Returns the
// rows written, or -1 when they no longer fit the caller's buffers.
int64_t dc_export(void* h, int64_t what, char* blob, int64_t bytes_cap,
                  int64_t* off, int64_t* vals, int64_t rows_cap,
                  int64_t* rows_out, int64_t* bytes_out) {
    DenyCache* c = C(h);
    std::lock_guard<std::mutex> g(c->mu);
    int64_t rows = 0, bytes = 0;
    auto emit = [&](uint32_t ks, const int64_t* v, int width) {
        const std::string_view k = c->pool[ks].key_view();
        if (blob != nullptr && rows < rows_cap &&
            bytes + static_cast<int64_t>(k.size()) <= bytes_cap) {
            off[rows] = bytes;
            memcpy(blob + bytes, k.data(), k.size());
            for (int j = 0; j < width; j++) vals[rows * width + j] = v[j];
        }
        rows++;
        bytes += static_cast<int64_t>(k.size());
    };
    if (what == 0) {
        for (uint32_t ks = c->rhead; ks != NIL; ks = c->pool[ks].rnext) {
            const KeyState& s = c->pool[ks];
            const int64_t v[3] = {s.rec_tat, s.rec_tol, s.rec_seq};
            emit(ks, v, 3);
        }
    } else if (what == 1) {
        for (const Entry* e = c->ehead; e != nullptr; e = e->next) {
            const int64_t v[10] = {e->mb,  e->cpp, e->per, e->q,     e->tat,
                                   e->em,  e->tol, e->inc, e->limit, e->expiry};
            emit(e->ks, v, 10);
        }
    } else {
        for (size_t i = 0; i < c->pool.size(); i++) {
            const KeyState& s = c->pool[i];
            if (!s.live || s.inflight == 0) continue;
            const int64_t v[1] = {s.inflight};
            emit(static_cast<uint32_t>(i), v, 1);
        }
    }
    *rows_out = rows;
    *bytes_out = bytes;
    if (blob == nullptr) return 0;
    if (rows > rows_cap || bytes > bytes_cap) return -1;
    off[rows] = bytes;
    return rows;
}

// The native driver's front tier for one captured wire frame: keys as
// blob + offsets[n+1], params i64[n, 4] (burst, count, period, quantity),
// budgets i64[n] (negative: the client's deadline lapsed before pop).
//
// With a cache (h != nullptr) the whole frame is first looked up as one
// window, taking an in-flight hold for every missing row.  Then per row,
// in order: an expired budget answers `st_deadline`; a served row gets
// status 255 and vals[i] = (0, limit, remaining, reset_s, retry_s); a
// row whose class admission refuses (admit bit0: consuming rows, bit1:
// quantity-0 peeks) answers `st_overloaded`; the rest compact into the
// miss frame (miss_blob, miss_off[m+1], miss_params[m, 4], miss_idx[m])
// the device decides, and miss_ks[m] names the key each holds (the
// handles dc_observe_window and dc_fail_window take back).  Rows that
// answer without the device release the hold the lookup took.  Served
// keys are listed once each: hit_first[j] is the first row of the j-th
// distinct served key, hit_count[j] its rows.  counts[6]: served rows,
// stale evictions, shed peeks, shed consuming rows, expired rows,
// distinct served keys.  Returns m.
int64_t dc_filter_frame(void* h, const char* blob, const int64_t* off,
                        int64_t n, const int64_t* params,
                        const int64_t* budgets, int64_t now, int64_t admit,
                        int64_t st_deadline, int64_t st_overloaded,
                        uint8_t* status, int64_t* vals, int64_t* miss_idx,
                        char* miss_blob, int64_t* miss_off,
                        int64_t* miss_params, int64_t* hit_first,
                        int64_t* hit_count, int64_t* counts,
                        int64_t* miss_ks) {
    DenyCache* c = h != nullptr ? C(h) : nullptr;
    std::unique_lock<std::mutex> g;
    int64_t n_hits = 0, n_stale = 0, n_hit_keys = 0;
    uint8_t* hit = nullptr;
    int64_t* hv = nullptr;
    uint32_t* rk = nullptr;
    uint64_t st = 0;
    if (c != nullptr) {
        g = std::unique_lock<std::mutex>(c->mu);
        c->hit_row.resize(static_cast<size_t>(n));
        c->hit_vals.resize(static_cast<size_t>(n) * 4);
        c->row_ks.resize(static_cast<size_t>(n));
        hit = c->hit_row.data();
        hv = c->hit_vals.data();
        rk = c->row_ks.data();
        n_hits = c->lookup_rows(blob, off, n, params, now, true, hit, hv, rk,
                                &n_stale);
        st = c->stamp;
    }
    const bool admit_consume = admit & 1;
    const bool admit_peek = (admit >> 1) & 1;
    int64_t m = 0, shed_peek = 0, shed_consume = 0, expired = 0;
    miss_off[0] = 0;
    for (int64_t i = 0; i < n; i++) {
        int64_t* v = vals + i * 5;
        v[0] = v[1] = v[2] = v[3] = v[4] = 0;
        const bool served = hit != nullptr && hit[i];
        if (served) {
            KeyState& ks = c->pool[rk[i]];
            if (ks.hit_stamp != st) {
                ks.hit_stamp = st;
                ks.hit_slot = n_hit_keys;
                hit_first[n_hit_keys] = i;
                hit_count[n_hit_keys] = 0;
                n_hit_keys++;
            }
            hit_count[ks.hit_slot]++;
        }
        bool answered = true;
        if (budgets[i] < 0) {
            status[i] = static_cast<uint8_t>(st_deadline);
            expired++;
        } else if (served) {
            status[i] = ROW_SERVED;
            const int64_t* hvi = hv + i * 4;
            v[1] = hvi[0];
            v[2] = hvi[1];
            v[3] = hvi[2] / NSEC;
            v[4] = hvi[3] / NSEC;
            continue;
        } else if (params[i * 4 + 3] == 0 ? !admit_peek : !admit_consume) {
            status[i] = static_cast<uint8_t>(st_overloaded);
            (params[i * 4 + 3] == 0 ? shed_peek : shed_consume)++;
        } else {
            answered = false;
        }
        if (answered) {
            if (!served && c != nullptr) {
                // The lookup marked this miss in flight; it will never
                // be observed, so free the hold.
                c->release_hold(rk[i]);
                c->maybe_free(rk[i]);
            }
            continue;
        }
        status[i] = 0;
        const int64_t len = off[i + 1] - off[i];
        memcpy(miss_blob + miss_off[m], blob + off[i], static_cast<size_t>(len));
        miss_off[m + 1] = miss_off[m] + len;
        memcpy(miss_params + m * 4, params + i * 4, 4 * sizeof(int64_t));
        miss_idx[m] = i;
        if (c != nullptr) miss_ks[m] = rk[i];
        m++;
    }
    counts[0] = n_hits;
    counts[1] = n_stale;
    counts[2] = shed_peek;
    counts[3] = shed_consume;
    counts[4] = expired;
    counts[5] = n_hit_keys;
    return m;
}

}  // extern "C"
