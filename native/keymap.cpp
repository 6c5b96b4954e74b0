// Host-side key→slot table for the TPU rate limiter.
//
// The reference's native hot path is its Rust HashMap keyed by string
// (throttlecrab/src/core/store/periodic.rs:39-47); in the TPU design the
// device owns the GCRA state and the host's per-request work shrinks to
// resolving string keys to dense slot indices.  At the 10M+ req/s target
// that resolution must not become the new bottleneck (SURVEY.md §7.4 hard
// part 2), hence this C++ open-addressing table with a batch API: one FFI
// call resolves a whole batch and emits the duplicate-segment structure
// (occurrence rank + last-occurrence flag) the device kernel needs — the
// Python fallback (throttlecrab_tpu/tpu/keymap.py) does the same with dicts.
//
// Design:
//   - open addressing, power-of-two bucket count, linear probing;
//   - FNV-1a 64-bit hashing;
//   - keys interned in an arena (offset, len per entry), appended to on
//     insert; a sweep's frees count the bytes they orphan, and once those
//     outweigh the live keys' bytes the arena is compacted (only there,
//     never on the insert or lookup path), so it follows the live keys;
//   - slot free-list for sweep recycling;
//   - per-batch segment tracking via a batch-stamp on each entry: no
//     per-call allocation, O(1) per request.
//
// Exposed as a C ABI for ctypes (no pybind11 in this environment).

#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <vector>

#include "gcra_params.h"

namespace {

constexpr uint64_t FNV_OFFSET = 1469598103934665603ULL;
constexpr uint64_t FNV_PRIME = 1099511628211ULL;

inline uint64_t fnv1a(const char* data, int64_t len) {
    uint64_t h = FNV_OFFSET;
    for (int64_t i = 0; i < len; i++) {
        h ^= static_cast<unsigned char>(data[i]);
        h *= FNV_PRIME;
    }
    return h;
}

struct Entry {
    uint64_t hash = 0;
    int64_t key_off = -1;   // -1: bucket empty
    int32_t key_len = 0;
    int32_t slot = -1;
};

struct KeyMap {
    std::vector<Entry> buckets;       // size is a power of two
    uint64_t mask = 0;
    std::vector<char> arena;          // interned key bytes
    int64_t arena_dead = 0;           // bytes of freed keys still in it
    int64_t arena_reserve = 0;        // the arena's capacity at creation
    std::vector<int32_t> free_slots;  // stack, low indices on top
    std::vector<int64_t> slot_bucket; // slot -> bucket index (-1 free)
    int64_t size = 0;                 // live keys
    int64_t capacity = 0;             // max slots
    uint64_t batch_stamp = 0;
    // id→key registry for tk_assemble: key bytes appended in intern order.
    std::vector<char> id_arena;
    std::vector<int64_t> id_off;      // n_ids + 1 offsets into id_arena
    // id→slot cache: after a key's first probe its slot is an O(1) array
    // read (the equivalent of the reference holding a HashMap entry
    // pointer).  slot_id is the reverse map so tk_free_slots can
    // invalidate exactly the freed keys' cache lines.
    std::vector<int32_t> id_slot;     // -1 = not cached
    std::vector<int32_t> slot_id;     // -1 = slot not owned by an id
    // Per-batch duplicate-segment tracking, indexed by slot (a slot
    // uniquely identifies a key within a batch, and slot indexing works
    // for both the probe path and the id-cache fast path).
    std::vector<uint64_t> slot_stamp;
    std::vector<int32_t> slot_count;
    std::vector<int32_t> slot_last_pos;
    std::vector<int32_t> slot_first_pos;

    explicit KeyMap(int64_t cap) { init(cap); }

    void init(int64_t cap) {
        id_off.assign(1, 0);
        capacity = cap;
        uint64_t nbuckets = 16;
        while (nbuckets < static_cast<uint64_t>(cap) * 2) nbuckets <<= 1;
        buckets.assign(nbuckets, Entry{});
        mask = nbuckets - 1;
        free_slots.resize(cap);
        for (int64_t i = 0; i < cap; i++)
            free_slots[i] = static_cast<int32_t>(cap - 1 - i);
        slot_bucket.assign(cap, -1);
        slot_id.assign(cap, -1);
        slot_stamp.assign(cap, 0);
        slot_count.assign(cap, 0);
        slot_last_pos.assign(cap, -1);
        slot_first_pos.assign(cap, -1);
        arena_reserve = cap * 16;
        arena.reserve(arena_reserve);
    }

    // Drop freed keys' bytes once they outweigh the live keys': copy
    // every live key into a fresh arena of the creation reserve (or
    // twice the live bytes, if more) and re-point its entry.  A scan of
    // the buckets and a copy of the live bytes, after a sweep's frees.
    void compact_arena() {
        const int64_t live = static_cast<int64_t>(arena.size()) - arena_dead;
        if (arena_dead <= live) return;
        std::vector<char> fresh;
        fresh.reserve(static_cast<size_t>(
            arena_reserve > 2 * live ? arena_reserve : 2 * live));
        for (Entry& e : buckets) {
            if (e.key_off < 0) continue;
            const char* src = arena.data() + e.key_off;
            e.key_off = static_cast<int64_t>(fresh.size());
            fresh.insert(fresh.end(), src, src + e.key_len);
        }
        arena.swap(fresh);
        arena_dead = 0;
    }

    // Shared probe path for resolve / assemble / prepare: find the key's
    // entry, inserting on miss.  Returns nullptr with *full=true when the
    // slot table is exhausted.  Any change to probing or insertion
    // invariants happens HERE, once.
    Entry* find_or_insert(const char* key, int64_t len, bool* full) {
        *full = false;
        const uint64_t hash = fnv1a(key, len);
        uint64_t b = hash & mask;
        Entry* e;
        for (;;) {
            e = &buckets[b];
            if (e->key_off < 0) break;  // miss
            if (e->hash == hash && e->key_len == len &&
                memcmp(arena.data() + e->key_off, key, len) == 0)
                break;  // hit
            b = (b + 1) & mask;
        }
        if (e->key_off < 0) {
            if (free_slots.empty()) {
                *full = true;
                return nullptr;
            }
            const int32_t slot = free_slots.back();
            free_slots.pop_back();
            e->hash = hash;
            e->key_off = static_cast<int64_t>(arena.size());
            e->key_len = static_cast<int32_t>(len);
            e->slot = slot;
            arena.insert(arena.end(), key, key + len);
            slot_bucket[slot] = static_cast<int64_t>(b);
            size++;
        }
        return e;
    }

    void rehash(uint64_t nbuckets) {
        std::vector<Entry> old = std::move(buckets);
        buckets.assign(nbuckets, Entry{});
        mask = nbuckets - 1;
        for (const Entry& e : old) {
            if (e.key_off < 0) continue;
            uint64_t b = e.hash & mask;
            while (buckets[b].key_off >= 0) b = (b + 1) & mask;
            buckets[b] = e;
            slot_bucket[e.slot] = static_cast<int64_t>(b);
        }
    }

    void grow_slots(int64_t new_cap) {
        if (new_cap <= capacity) return;
        free_slots.reserve(new_cap);
        for (int64_t i = new_cap - 1; i >= capacity; i--)
            free_slots.push_back(static_cast<int32_t>(i));
        slot_bucket.resize(new_cap, -1);
        slot_id.resize(new_cap, -1);
        slot_stamp.resize(new_cap, 0);
        slot_count.resize(new_cap, 0);
        slot_last_pos.resize(new_cap, -1);
        slot_first_pos.resize(new_cap, -1);
        capacity = new_cap;
        // Keep nbuckets >= 2 * capacity (load factor <= 0.5): the probe
        // loops rely on an empty bucket always existing — at load factor
        // 1.0 a miss probe never terminates.
        if (static_cast<uint64_t>(new_cap) * 2 > buckets.size()) {
            uint64_t n = buckets.size();
            while (n < static_cast<uint64_t>(new_cap) * 2) n <<= 1;
            rehash(n);
        }
    }
};

}  // namespace

extern "C" {

void* tk_create(int64_t capacity) { return new KeyMap(capacity); }

void tk_destroy(void* h) { delete static_cast<KeyMap*>(h); }

int64_t tk_len(void* h) { return static_cast<KeyMap*>(h)->size; }

int64_t tk_capacity(void* h) { return static_cast<KeyMap*>(h)->capacity; }

void tk_grow(void* h, int64_t new_capacity) {
    static_cast<KeyMap*>(h)->grow_slots(new_capacity);
}

// Resolve a batch of keys (concatenated bytes + offsets[n+1]) to slots,
// allocating on miss.  valid[i] == 0 skips a request (slot -1).  Emits the
// kernel's segment structure: rank (occurrence number within this batch) and
// is_last (final occurrence within this batch).  Returns the number of
// requests that could not be allocated because the table is full (their
// slots are -1; caller grows and retries just those, passing them as the
// only valid ones).
int64_t tk_lookup_insert_batch(
    void* h, const char* keys, const int64_t* offsets, int64_t n,
    const uint8_t* valid, int32_t* out_slots, int32_t* out_rank,
    uint8_t* out_is_last) {
    KeyMap* m = static_cast<KeyMap*>(h);
    m->batch_stamp++;
    const uint64_t stamp = m->batch_stamp;
    int64_t full = 0;
    for (int64_t i = 0; i < n; i++) {
        out_rank[i] = 0;
        out_is_last[i] = 1;
        if (!valid[i]) {
            out_slots[i] = -1;
            continue;
        }
        const char* key = keys + offsets[i];
        const int64_t len = offsets[i + 1] - offsets[i];
        bool is_full = false;
        Entry* e = m->find_or_insert(key, len, &is_full);
        if (is_full) {
            out_slots[i] = -1;
            full++;
            continue;
        }
        const int32_t slot = e->slot;
        out_slots[i] = slot;
        if (m->slot_stamp[slot] == stamp) {
            out_rank[i] = ++m->slot_count[slot] - 1;
            out_is_last[m->slot_last_pos[slot]] = 0;
            m->slot_last_pos[slot] = static_cast<int32_t>(i);
        } else {
            m->slot_stamp[slot] = stamp;
            m->slot_count[slot] = 1;
            m->slot_last_pos[slot] = static_cast<int32_t>(i);
        }
    }
    return full;
}

// ---------------------------------------------------------------------
// Id-based launch assembly: the round-4 host fast path.
//
// The Python list-comprehension batch assembly (`[key_src[i] for i in sel]`
// + per-sub-batch resolve) capped the host at ~1.7 M decisions/s.  Here the
// caller interns its key universe once (tk_intern_keys) and then builds an
// entire K×B launch buffer with ONE call (tk_assemble) straight from an id
// array: per request the interned key bytes are re-hashed through the table
// (the same per-request probe work the serving path pays — interning skips
// only the Python object traffic), slots are allocated on miss, the
// duplicate-segment structure is tracked per micro-batch of `batch`
// requests, and the kernel's packed i32[PACK_WIDTH] row is written in
// place (layout must match kernel.py PACK_WIDTH/pack_requests:
//   w0 slot | w1 rank | w2 flags(bit0 is_last, bit1 valid)
//   w3/4 emission lo/hi | w5/6 tolerance lo/hi | w7/8 quantity lo/hi).

constexpr int64_t PACK_W = 9;

// Resolve an interned id to its slot: O(1) via the id→slot cache after
// the first touch, else hash + probe (allocating on miss) and cache.
// Returns -1 when the slot table is full.  Shared by tk_assemble,
// tk_assemble_ids and tk_resolve_all so the caching rule cannot drift.
static int32_t resolve_interned(KeyMap* m, int64_t id) {
    int32_t slot = m->id_slot[id];
    if (slot >= 0) return slot;
    const char* key = m->id_arena.data() + m->id_off[id];
    const int64_t len = m->id_off[id + 1] - m->id_off[id];
    bool is_full = false;
    Entry* e = m->find_or_insert(key, len, &is_full);
    if (is_full) return -1;
    slot = e->slot;
    // Cache only an unclaimed slot: two interned ids with identical key
    // bytes share a slot, and the reverse map can hold just one of them
    // — the other stays slow-path.
    if (m->slot_id[slot] < 0) {
        m->slot_id[slot] = static_cast<int32_t>(id);
        m->id_slot[id] = slot;
    }
    return slot;
}

// Register `n` keys; ids are assigned sequentially.  Returns the first id.
int64_t tk_intern_keys(void* h, const char* keys, const int64_t* offsets,
                       int64_t n) {
    KeyMap* m = static_cast<KeyMap*>(h);
    const int64_t first = static_cast<int64_t>(m->id_off.size()) - 1;
    for (int64_t i = 0; i < n; i++) {
        const int64_t len = offsets[i + 1] - offsets[i];
        m->id_arena.insert(m->id_arena.end(), keys + offsets[i],
                           keys + offsets[i] + len);
        m->id_off.push_back(static_cast<int64_t>(m->id_arena.size()));
        m->id_slot.push_back(-1);
    }
    return first;
}

// Build a launch buffer of `total` requests (micro-batches of `batch`) from
// interned key ids.  em/tol are per-id parameter tables; `quantity` is a
// uniform per-request quantity (the serving engine certifies uniformity
// before taking this path).  ids < 0 are padding (written invalid, not
// counted).  Returns the number of requests dropped — slot table full, or
// a non-negative id that was never interned (both written invalid) — so a
// forgotten intern() fails the caller's `n_full == 0` check instead of
// silently reporting undecided requests.
int64_t tk_assemble(void* h, const int32_t* ids, int64_t total, int64_t batch,
                    const int64_t* em_by_id, const int64_t* tol_by_id,
                    int64_t quantity, int32_t* out) {
    KeyMap* m = static_cast<KeyMap*>(h);
    const int64_t n_ids = static_cast<int64_t>(m->id_off.size()) - 1;
    const int32_t qlo = static_cast<int32_t>(quantity & 0xFFFFFFFFll);
    const int32_t qhi = static_cast<int32_t>(quantity >> 32);
    int64_t full = 0;
    for (int64_t base = 0; base < total; base += batch) {
        m->batch_stamp++;
        const uint64_t stamp = m->batch_stamp;
        const int64_t end = base + batch < total ? base + batch : total;
        for (int64_t i = base; i < end; i++) {
            int32_t* w = out + i * PACK_W;
            const int64_t id = ids[i];
            if (id < 0 || id >= n_ids) {
                w[0] = -1;
                for (int j = 1; j < PACK_W; j++) w[j] = 0;
                if (id >= n_ids) full++;  // un-interned id: surface it
                continue;
            }
            const int32_t slot = resolve_interned(m, id);
            if (slot < 0) {
                w[0] = -1;
                for (int j = 1; j < PACK_W; j++) w[j] = 0;
                full++;
                continue;
            }
            w[0] = slot;
            w[2] = 3;  // is_last | valid
            if (m->slot_stamp[slot] == stamp) {
                w[1] = ++m->slot_count[slot] - 1;
                out[static_cast<int64_t>(m->slot_last_pos[slot]) * PACK_W +
                    2] &= ~1;
                m->slot_last_pos[slot] = static_cast<int32_t>(i);
            } else {
                w[1] = 0;
                m->slot_stamp[slot] = stamp;
                m->slot_count[slot] = 1;
                m->slot_last_pos[slot] = static_cast<int32_t>(i);
            }
            const int64_t em = em_by_id[id];
            const int64_t tol = tol_by_id[id];
            w[3] = static_cast<int32_t>(em & 0xFFFFFFFFll);
            w[4] = static_cast<int32_t>(em >> 32);
            w[5] = static_cast<int32_t>(tol & 0xFFFFFFFFll);
            w[6] = static_cast<int32_t>(tol >> 32);
            w[7] = qlo;
            w[8] = qhi;
        }
    }
    return full;
}

// ---------------------------------------------------------------------
// By-id launch assembly: the minimum-bytes request path.
//
// The 36 B/request packed row is most of a launch's host→device bytes.
// When the key universe is
// interned and its parameter rows are resident on the DEVICE
// (tpu/table.py upload_id_rows), a request needs only its id plus the
// duplicate-segment structure: ONE i64 word
//   low 32 bits: id | high 32: rank(14) | is_last<<14 | valid<<15
// — 8 B/request, 4.5x less than the packed row.  The device gathers
// (slot, emission, tolerance) from the resident rows by id.
//
// Contract (the bench/serving caller certifies): every id interned, ids
// canonical enough that ids sharing a SLOT share parameters (segments
// are tracked per slot, exactly like tk_assemble, so duplicate key
// BYTES under different ids still serialize correctly).

// Resolve every interned id to a slot (allocating on miss) and fill the
// caller's id→slot array — the host half of the device id-row upload.
// Returns the number of ids that could not get a slot (table full);
// their slots_out entry is -1.
int64_t tk_resolve_all(void* h, int32_t* slots_out) {
    KeyMap* m = static_cast<KeyMap*>(h);
    const int64_t n_ids = static_cast<int64_t>(m->id_off.size()) - 1;
    int64_t failed = 0;
    for (int64_t id = 0; id < n_ids; id++) {
        const int32_t slot = resolve_interned(m, id);
        slots_out[id] = slot;
        if (slot < 0) failed++;
    }
    return failed;
}

// Build the i64 request words for a launch of `total` requests
// (micro-batches of `batch`) straight from an id array.  ids < 0 are
// padding (valid=0).  Returns the number of requests dropped (id never
// interned / table full — written invalid so the caller's n_bad check
// catches a forgotten intern or resolve).
int64_t tk_assemble_ids(void* h, const int32_t* ids, int64_t total,
                        int64_t batch, int64_t* out) {
    KeyMap* m = static_cast<KeyMap*>(h);
    const int64_t n_ids = static_cast<int64_t>(m->id_off.size()) - 1;
    int64_t bad = 0;
    for (int64_t base = 0; base < total; base += batch) {
        m->batch_stamp++;
        const uint64_t stamp = m->batch_stamp;
        const int64_t end = base + batch < total ? base + batch : total;
        for (int64_t i = base; i < end; i++) {
            const int64_t id = ids[i];
            if (id < 0 || id >= n_ids) {
                out[i] = 0;  // valid=0
                if (id >= n_ids) bad++;
                continue;
            }
            const int32_t slot = resolve_interned(m, id);
            if (slot < 0) {
                out[i] = 0;
                bad++;
                continue;
            }
            int64_t meta;
            if (m->slot_stamp[slot] == stamp) {
                const int32_t rank = m->slot_count[slot]++;
                // Clear the previous occurrence's is_last bit.
                out[m->slot_last_pos[slot]] &=
                    ~(static_cast<int64_t>(1) << 46);
                m->slot_last_pos[slot] = static_cast<int32_t>(i);
                meta = rank | (1 << 14) | (1 << 15);
            } else {
                m->slot_stamp[slot] = stamp;
                m->slot_count[slot] = 1;
                m->slot_last_pos[slot] = static_cast<int32_t>(i);
                meta = (1 << 14) | (1 << 15);
            }
            out[i] = (meta << 32) | static_cast<uint32_t>(id);
        }
    }
    return bad;
}

// One request's wire completion from its `cur*2+allowed` word: the exact
// arithmetic shared by tk_finish (packed rows) and tk_finish_ids (by-id
// tables) so the two paths cannot drift.  Under the fits_cur_wire +
// with_degen=False certificate (kernel.py) no intermediate leaves i64.
static inline void finish_one(int64_t em, int64_t tol, int64_t qty,
                              int64_t c2, int64_t now, int32_t* o) {
    constexpr int64_t I32MAX = 2147483647ll;
    constexpr int64_t NSEC = 1000000000ll;
    const int64_t allowed = c2 & 1;
    const int64_t cur = c2 >> 1;  // arithmetic: exact for negatives
    const int64_t room = now + tol - cur;
    int64_t remaining = em > 0 ? room / em : 0;
    if (remaining < 0) remaining = 0;
    int64_t reset = cur - now + tol;
    if (reset < 0) reset = 0;
    int64_t retry = allowed ? 0 : cur + em * qty - tol - now;
    if (retry < 0) retry = 0;
    o[0] = static_cast<int32_t>(allowed);
    o[1] = static_cast<int32_t>(remaining < I32MAX ? remaining : I32MAX);
    const int64_t reset_s = reset / NSEC;
    o[2] = static_cast<int32_t>(reset_s < I32MAX ? reset_s : I32MAX);
    const int64_t retry_s = retry / NSEC;
    o[3] = static_cast<int32_t>(retry_s < I32MAX ? retry_s : I32MAX);
}

// tk_finish for the raw-ids path (gcra_scan_ids): the request stream is
// bare i32 ids (negative = padding), parameters from the host tables.
void tk_finish_raw(const int32_t* ids, const int64_t* em_by_id,
                   const int64_t* tol_by_id, int64_t quantity,
                   const int64_t* cur2, int64_t n, int64_t now,
                   int32_t* out) {
    for (int64_t i = 0; i < n; i++) {
        const int32_t id = ids[i];
        const bool valid = id >= 0;
        const int64_t em = valid ? em_by_id[id] : 0;
        const int64_t tol = valid ? tol_by_id[id] : 0;
        finish_one(em, tol, quantity, cur2[i], now, out + i * 4);
    }
}

// tk_finish for the by-id path: emission/tolerance come from the host
// parameter tables indexed by the id in each request word; quantity is
// the launch-uniform scalar.
void tk_finish_ids(const int64_t* words, const int64_t* em_by_id,
                   const int64_t* tol_by_id, int64_t quantity,
                   const int64_t* cur2, int64_t n, int64_t now,
                   int32_t* out) {
    for (int64_t i = 0; i < n; i++) {
        const int64_t word = words[i];
        const int64_t id = static_cast<uint32_t>(word);
        const bool valid = (word >> 47) & 1;
        const int64_t em = valid ? em_by_id[id] : 0;
        const int64_t tol = valid ? tol_by_id[id] : 0;
        finish_one(em, tol, quantity, cur2[i], now, out + i * 4);
    }
}

// Host-side completion of the kernel's compact="cur" device output:
// reconstruct the exact 4-plane wire values (allowed, remaining,
// reset_after_secs, retry_after_secs — i32, saturated exactly like the
// kernel's compact branch) from ONE i64 `cur*2 + allowed` per request,
// reading emission/tolerance/quantity back out of the packed request
// rows the caller already holds.  Under the fits_cur_wire +
// with_degen=False certificate (kernel.py) no intermediate can leave
// i64, so plain arithmetic reproduces the device's saturating ops
// bit-for-bit.  Moving these two i64 divisions off the device halves
// the launch's device→host bytes AND removes emulated 64-bit VPU work.
void tk_finish(const int32_t* packed, const int64_t* cur2, int64_t n,
               int64_t now, int32_t* out) {
    for (int64_t i = 0; i < n; i++) {
        const int32_t* w = packed + i * PACK_W;
        const int64_t em =
            (static_cast<int64_t>(w[4]) << 32) |
            static_cast<uint32_t>(w[3]);
        const int64_t tol =
            (static_cast<int64_t>(w[6]) << 32) |
            static_cast<uint32_t>(w[5]);
        const int64_t qty =
            (static_cast<int64_t>(w[8]) << 32) |
            static_cast<uint32_t>(w[7]);
        finish_one(em, tol, qty, cur2[i], now, out + i * 4);
    }
}

// ---------------------------------------------------------------------
// Wire-batch preparation: the fully-native serving host path.
//
// One call takes a micro-batch exactly as the C++ wire layer hands it
// over (concatenated key bytes + offsets + i64 (burst, count, period,
// quantity) per request) and produces the kernel's packed launch rows:
// per request it validates (reference error taxonomy), derives the GCRA
// parameters with the exact f64 pipeline (rate/mod.rs:164-176 semantics:
// f64 multiply/divide, truncating cast, wrapping tolerance product —
// bit-identical to limiter.derive_params), resolves the slot, emits the
// duplicate-segment structure, and writes the packed row.  Python's
// per-batch work drops to padding + the device launch.
//
// Returns a flag bitmask; a nonzero TK_PREP_CONFLICT or TK_PREP_FULL
// tells the caller to fall back to the exact Python path (param changes
// mid-batch need the multi-round sub-protocol; full tables need growth).

constexpr int64_t TK_PREP_DEGEN = 1;     // needs the exact kernel path
constexpr int64_t TK_PREP_CONFLICT = 2;  // same key, different params
constexpr int64_t TK_PREP_FULL = 4;      // slot table full
constexpr int64_t TK_PREP_BIGTOL = 8;    // tol >= 2^61: no "cur" wire mode

constexpr uint8_t STATUS_OK = 0;
constexpr uint8_t STATUS_NEGATIVE_QUANTITY = 1;
constexpr uint8_t STATUS_INVALID_PARAMS = 2;

// agg (i64[4], may be null): aggregate bounds over STATUS_OK lanes for
// the caller's O(1) compact="w32" certificate (kernel.fits_w32_wire's
// native twin): [max_tol, min_tol, max_inc (saturated), max of the
// per-lane remaining bound (tol + max(em, tol)) / em].  Lanes the
// validator rejects never reach the kernel, so they are excluded.
int64_t tk_prepare_batch(void* h, const char* keys, const int64_t* offsets,
                         int64_t n, const int64_t* params, int32_t* out,
                         uint8_t* status, int64_t* agg) {
    KeyMap* m = static_cast<KeyMap*>(h);
    m->batch_stamp++;
    const uint64_t stamp = m->batch_stamp;
    int64_t flags = 0;
    int64_t max_tol = 0, min_tol = INT64_MAX, max_inc = 0, max_remb = 0;
    // Per-slot first-occurrence params for conflict detection, reset via
    // the same stamp the segment tracking uses.
    for (int64_t i = 0; i < n; i++) {
        int32_t* w = out + i * PACK_W;
        const int64_t burst = params[i * 4 + 0];
        const int64_t count = params[i * 4 + 1];
        const int64_t period = params[i * 4 + 2];
        const int64_t qty = params[i * 4 + 3];

        uint8_t st = STATUS_OK;
        if (!gcra_params_valid(burst, count, period))
            st = STATUS_INVALID_PARAMS;
        if (qty < 0) st = STATUS_NEGATIVE_QUANTITY;
        status[i] = st;
        if (st != STATUS_OK) {
            w[0] = -1;
            for (int j = 1; j < PACK_W; j++) w[j] = 0;
            continue;
        }

        // Exact f64 derivation (matches limiter.derive_params), shared
        // with the deny cache through gcra_params.h.
        int64_t em, tol;
        gcra_derive(burst, count, period, &em, &tol);

        if (em == 0 || tol <= 0 || qty == 0) flags |= TK_PREP_DEGEN;
        // Segment-arithmetic overflow certificate (must mirror
        // limiter.has_degenerate): inc * MAX_SEGMENT must stay below
        // 2^62 or the kernel's certified plain multiplies could wrap.
        if (static_cast<double>(em) * static_cast<double>(qty > 1 ? qty : 1)
                * 65536.0
            >= 4611686018427387904.0)  // 2^62
            flags |= TK_PREP_DEGEN;
        // fits_cur_wire half of the compact="cur" certificate (kernel.py):
        // tol >= 2^61 would overflow the cur*2+allowed wire word.  (The
        // now < 2^61 half is the caller's, since `now` arrives at launch
        // time.)
        if (tol >= (int64_t(1) << 61)) flags |= TK_PREP_BIGTOL;

        // w32-certificate aggregates (see header comment).
        if (tol > max_tol) max_tol = tol;
        if (tol < min_tol) min_tol = tol;
        {
            // Saturating em * qty (the bound only needs the clamp).
            const double inc_f =
                static_cast<double>(em) * static_cast<double>(qty);
            const int64_t inc = inc_f >= 9223372036854775807.0
                                    ? INT64_MAX
                                    : static_cast<int64_t>(inc_f);
            if (inc > max_inc) max_inc = inc;
            if (em > 0 && tol >= 0 && tol < (int64_t(1) << 61)) {
                // Saturating sum: em is only bounded by i64, so
                // tol + em can overflow (UB on signed i64) — the same
                // double-probe pattern as max_inc above.  (Such lanes
                // are also PREP_DEGEN via the big-inc certificate, but
                // the aggregate must stay well-defined regardless.)
                const int64_t big = em > tol ? em : tol;
                const int64_t room =
                    static_cast<double>(tol) + static_cast<double>(big)
                            >= 9223372036854775807.0
                        ? INT64_MAX
                        : tol + big;
                const int64_t remb = room / em;
                if (remb > max_remb) max_remb = remb;
            } else {
                max_remb = INT64_MAX;  // degen/bigtol lane: refuse w32
            }
        }

        const char* key = keys + offsets[i];
        const int64_t len = offsets[i + 1] - offsets[i];
        bool is_full = false;
        Entry* e = m->find_or_insert(key, len, &is_full);
        if (is_full) {
            w[0] = -1;
            for (int j = 1; j < PACK_W; j++) w[j] = 0;
            flags |= TK_PREP_FULL;
            continue;
        }
        const int32_t slot = e->slot;
        w[0] = slot;
        w[2] = 3;  // is_last | valid
        if (m->slot_stamp[slot] == stamp) {
            w[1] = ++m->slot_count[slot] - 1;
            out[static_cast<int64_t>(m->slot_last_pos[slot]) * PACK_W + 2] &=
                ~1;
            // Conflict: this occurrence's derived params must match the
            // first occurrence's packed row (the kernel requires uniform
            // params per slot per batch).
            const int64_t f =
                static_cast<int64_t>(m->slot_first_pos[slot]) * PACK_W;
            const int32_t em_lo = static_cast<int32_t>(em & 0xFFFFFFFFll);
            const int32_t em_hi = static_cast<int32_t>(em >> 32);
            const int32_t tol_lo = static_cast<int32_t>(tol & 0xFFFFFFFFll);
            const int32_t tol_hi = static_cast<int32_t>(tol >> 32);
            const int32_t q_lo = static_cast<int32_t>(qty & 0xFFFFFFFFll);
            const int32_t q_hi = static_cast<int32_t>(qty >> 32);
            if (out[f + 3] != em_lo || out[f + 4] != em_hi ||
                out[f + 5] != tol_lo || out[f + 6] != tol_hi ||
                out[f + 7] != q_lo || out[f + 8] != q_hi)
                flags |= TK_PREP_CONFLICT;
            m->slot_last_pos[slot] = static_cast<int32_t>(i);
        } else {
            w[1] = 0;
            m->slot_stamp[slot] = stamp;
            m->slot_count[slot] = 1;
            m->slot_last_pos[slot] = static_cast<int32_t>(i);
            m->slot_first_pos[slot] = static_cast<int32_t>(i);
        }
        w[3] = static_cast<int32_t>(em & 0xFFFFFFFFll);
        w[4] = static_cast<int32_t>(em >> 32);
        w[5] = static_cast<int32_t>(tol & 0xFFFFFFFFll);
        w[6] = static_cast<int32_t>(tol >> 32);
        w[7] = static_cast<int32_t>(qty & 0xFFFFFFFFll);
        w[8] = static_cast<int32_t>(qty >> 32);
    }
    if (agg) {
        agg[0] = max_tol;
        agg[1] = min_tol == INT64_MAX ? 0 : min_tol;
        agg[2] = max_inc;
        agg[3] = max_remb;
    }
    return flags;
}

// Snapshot export: first call tk_export_sizes to size the buffers, then
// tk_export fills slot ids, key offsets (n+1 entries) and key bytes for
// every live entry, in unspecified order.
void tk_export_sizes(void* h, int64_t* n_out, int64_t* bytes_out) {
    KeyMap* m = static_cast<KeyMap*>(h);
    int64_t bytes = 0;
    for (const Entry& e : m->buckets)
        if (e.key_off >= 0) bytes += e.key_len;
    *n_out = m->size;
    *bytes_out = bytes;
}

void tk_export(void* h, int32_t* slots_out, int64_t* offsets_out,
               char* keys_out) {
    KeyMap* m = static_cast<KeyMap*>(h);
    int64_t i = 0;
    int64_t off = 0;
    for (const Entry& e : m->buckets) {
        if (e.key_off < 0) continue;
        slots_out[i] = e.slot;
        offsets_out[i] = off;
        memcpy(keys_out + off, m->arena.data() + e.key_off, e.key_len);
        off += e.key_len;
        i++;
    }
    offsets_out[i] = off;
}

// Keys of a few given slots (the insight top-K), read through the
// slot_bucket column: found_out[i] is 1 and keys_out[offsets_out[i],
// offsets_out[i+1]) the key bytes for a live slot; a free or
// out-of-range slot gets 0 and an empty span.  Bytes are copied only
// while they fit in keys_cap; the return is the total bytes, so a
// caller whose buffer was too small calls again with that many.
int64_t tk_keys_for_slots(void* h, const int32_t* slots, int64_t n,
                          int64_t* offsets_out, uint8_t* found_out,
                          char* keys_out, int64_t keys_cap) {
    const KeyMap* m = static_cast<const KeyMap*>(h);
    int64_t off = 0;
    for (int64_t i = 0; i < n; i++) {
        offsets_out[i] = off;
        const int32_t slot = slots[i];
        const int64_t b =
            (slot >= 0 && slot < m->capacity) ? m->slot_bucket[slot] : -1;
        found_out[i] = b >= 0;
        if (b < 0) continue;
        const Entry& e = m->buckets[b];
        if (off + e.key_len <= keys_cap)
            memcpy(keys_out + off, m->arena.data() + e.key_off, e.key_len);
        off += e.key_len;
    }
    offsets_out[n] = off;
    return off;
}

// Free the given slots (from a sweep's expired mask).  Tombstone-free
// removal for linear probing: re-place any displaced cluster members.
int64_t tk_free_slots(void* h, const int32_t* slots, int64_t n) {
    KeyMap* m = static_cast<KeyMap*>(h);
    int64_t freed = 0;
    for (int64_t i = 0; i < n; i++) {
        const int32_t slot = slots[i];
        if (slot < 0 || slot >= m->capacity) continue;
        int64_t b = m->slot_bucket[slot];
        if (b < 0) continue;  // not allocated
        // Backward-shift deletion keeps probe chains intact.
        uint64_t hole = static_cast<uint64_t>(b);
        m->arena_dead += m->buckets[hole].key_len;
        m->buckets[hole] = Entry{};
        uint64_t j = (hole + 1) & m->mask;
        while (m->buckets[j].key_off >= 0) {
            const uint64_t home = m->buckets[j].hash & m->mask;
            // Can entry at j move into the hole without breaking its probe
            // sequence?  (standard backward-shift condition)
            const bool movable =
                ((j - home) & m->mask) >= ((j - hole) & m->mask);
            if (movable) {
                m->buckets[hole] = m->buckets[j];
                m->slot_bucket[m->buckets[hole].slot] =
                    static_cast<int64_t>(hole);
                m->buckets[j] = Entry{};
                hole = j;
            }
            j = (j + 1) & m->mask;
        }
        m->slot_bucket[slot] = -1;
        if (m->slot_id[slot] >= 0) {
            m->id_slot[m->slot_id[slot]] = -1;
            m->slot_id[slot] = -1;
        }
        m->free_slots.push_back(slot);
        m->size--;
        freed++;
    }
    if (freed) m->compact_arena();
    return freed;
}

// The key arena's bytes: out[0] in use (live and freed keys), out[1] of
// freed keys not yet reclaimed, out[2] allocated.
void tk_arena_stats(void* h, int64_t* out) {
    const KeyMap* m = static_cast<KeyMap*>(h);
    out[0] = static_cast<int64_t>(m->arena.size());
    out[1] = m->arena_dead;
    out[2] = static_cast<int64_t>(m->arena.capacity());
}

}  // extern "C"
