// Native key→slot table: the host-side hot path of the tick engine.
//
// The engine's device kernel is fast; what bounds end-to-end throughput is
// the per-request host work of resolving string keys to table slots (the
// role the reference's Go map + worker hash routing plays, lrucache.go /
// workers.go:180-184).  This is that path in C++: an open-addressing hash
// table (fnv1a, linear probing, tombstones) over a fixed slot arena, with a
// batch API so one C call resolves a whole tick's keys.
//
// Exposed as a plain C ABI for ctypes (no pybind11 in the image).

#include <algorithm>
#include <chrono>
#include <cstdint>
#include <cstring>
#include <string>
#include <vector>

namespace {

constexpr int64_t kEmpty = -1;
constexpr int64_t kTomb = -2;

inline uint64_t fnv1a(const char* data, int64_t len) {
  uint64_t h = 0xcbf29ce484222325ULL;
  for (int64_t i = 0; i < len; ++i) {
    h ^= static_cast<unsigned char>(data[i]);
    h *= 0x100000001b3ULL;
  }
  return h;
}

// CRC-32 (ISO-HDLC: poly 0xEDB88320, init/xorout 0xFFFFFFFF),
// bit-identical to Python's zlib.crc32, which the mesh engine's
// key->shard router is defined by.
uint32_t crc32_table[256];
[[maybe_unused]] const bool crc32_init_done = [] {
  for (uint32_t i = 0; i < 256; ++i) {
    uint32_t c = i;
    for (int k = 0; k < 8; ++k) c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
    crc32_table[i] = c;
  }
  return true;
}();

inline uint32_t crc32(const char* data, int64_t len) {
  uint32_t c = 0xFFFFFFFFu;
  for (int64_t i = 0; i < len; ++i) {
    c = crc32_table[(c ^ static_cast<uint8_t>(data[i])) & 0xFFu] ^ (c >> 8);
  }
  return c ^ 0xFFFFFFFFu;
}

inline uint64_t next_pow2(uint64_t v) {
  v--;
  v |= v >> 1; v |= v >> 2; v |= v >> 4;
  v |= v >> 8; v |= v >> 16; v |= v >> 32;
  return v + 1;
}

struct SlotMap {
  int64_t capacity;              // number of slots
  uint64_t mask;                 // hash table size - 1 (pow2, ≥ 2*capacity)
  std::vector<int64_t> table;    // hash bucket → slot | kEmpty | kTomb
  std::vector<uint64_t> hashes;  // per-bucket cached hash (valid when slot ≥ 0)
  std::vector<std::string> keys; // per-slot key (empty = unassigned)
  std::vector<int64_t> free_list;
  int64_t count = 0;
  int64_t tombs = 0;

  explicit SlotMap(int64_t cap) : capacity(cap) {
    uint64_t tsize = next_pow2(static_cast<uint64_t>(cap) * 2 + 16);
    mask = tsize - 1;
    table.assign(tsize, kEmpty);
    hashes.assign(tsize, 0);
    keys.resize(cap);
    free_list.reserve(cap);
    for (int64_t s = cap - 1; s >= 0; --s) free_list.push_back(s);
  }

  // Find the bucket holding key, or the first insertable bucket.
  // Returns (bucket, found).
  std::pair<uint64_t, bool> probe(const char* key, int64_t len,
                                  uint64_t h) const {
    uint64_t idx = h & mask;
    uint64_t first_tomb = UINT64_MAX;
    for (;;) {
      int64_t s = table[idx];
      if (s == kEmpty) {
        return {first_tomb != UINT64_MAX ? first_tomb : idx, false};
      }
      if (s == kTomb) {
        if (first_tomb == UINT64_MAX) first_tomb = idx;
      } else if (hashes[idx] == h &&
                 keys[s].size() == static_cast<size_t>(len) &&
                 std::memcmp(keys[s].data(), key, len) == 0) {
        return {idx, true};
      }
      idx = (idx + 1) & mask;
    }
  }

  void maybe_rehash() {
    // Tombstone buildup degrades probes; rebuild in place when they
    // outnumber live entries.
    if (tombs < static_cast<int64_t>(mask / 4)) return;
    std::fill(table.begin(), table.end(), kEmpty);
    tombs = 0;
    for (int64_t s = 0; s < capacity; ++s) {
      if (keys[s].empty()) continue;
      uint64_t h = fnv1a(keys[s].data(), keys[s].size());
      uint64_t idx = h & mask;
      while (table[idx] >= 0) idx = (idx + 1) & mask;
      table[idx] = s;
      hashes[idx] = h;
    }
  }

  int64_t get(const char* key, int64_t len) const {
    auto [idx, found] = probe(key, len, fnv1a(key, len));
    return found ? table[idx] : -1;
  }

  // get-or-assign with one hash and one probe: the slot (or -1 when the
  // key is new and the table is full); *known says it had a mapping.
  int64_t resolve(const char* key, int64_t len, uint8_t* known) {
    uint64_t h = fnv1a(key, len);
    auto [idx, found] = probe(key, len, h);
    *known = found;
    if (found) return table[idx];
    if (free_list.empty()) return -1;
    int64_t s = free_list.back();
    free_list.pop_back();
    if (table[idx] == kTomb) --tombs;
    table[idx] = s;
    hashes[idx] = h;
    keys[s].assign(key, len);
    ++count;
    return s;
  }

  int64_t assign(const char* key, int64_t len) {
    uint8_t known;
    return resolve(key, len, &known);
  }

  void release(int64_t slot) {
    if (slot < 0 || slot >= capacity || keys[slot].empty()) return;
    uint64_t h = fnv1a(keys[slot].data(), keys[slot].size());
    auto [idx, found] = probe(keys[slot].data(), keys[slot].size(), h);
    if (found) {
      table[idx] = kTomb;
      ++tombs;
    }
    keys[slot].clear();
    free_list.push_back(slot);
    --count;
    maybe_rehash();
  }
};

}  // namespace

extern "C" {

void* guber_slotmap_new(int64_t capacity) { return new SlotMap(capacity); }

void guber_slotmap_free(void* p) { delete static_cast<SlotMap*>(p); }

int64_t guber_slotmap_get(void* p, const char* key, int64_t len) {
  return static_cast<SlotMap*>(p)->get(key, len);
}

int64_t guber_slotmap_assign(void* p, const char* key, int64_t len) {
  return static_cast<SlotMap*>(p)->assign(key, len);
}

void guber_slotmap_release(void* p, int64_t slot) {
  static_cast<SlotMap*>(p)->release(slot);
}

int64_t guber_slotmap_size(void* p) { return static_cast<SlotMap*>(p)->count; }

// Copy slot's key into buf (≤ buflen bytes); returns key length or -1.
int64_t guber_slotmap_key_of(void* p, int64_t slot, char* buf, int64_t buflen) {
  auto* m = static_cast<SlotMap*>(p);
  if (slot < 0 || slot >= m->capacity || m->keys[slot].empty()) return -1;
  const std::string& k = m->keys[slot];
  int64_t n = static_cast<int64_t>(k.size());
  if (n > buflen) return -1;
  std::memcpy(buf, k.data(), n);
  return n;
}

// Batch resolve: keys arrive as one concatenated blob with n+1 offsets.
// out_slots[i] = slot (or -1 when the table is full); out_known[i] = 1 when
// the key already had a mapping.  One call per tick replaces n dict lookups.
void guber_slotmap_resolve_batch(void* p, const char* blob,
                                 const int64_t* offsets, int64_t n,
                                 int64_t* out_slots, uint8_t* out_known) {
  auto* m = static_cast<SlotMap*>(p);
  for (int64_t i = 0; i < n; ++i) {
    out_slots[i] = m->resolve(blob + offsets[i], offsets[i + 1] - offsets[i],
                              &out_known[i]);
  }
}

// ---------------------------------------------------------------------
// The window pass: one call from a window's request columns to everything
// its tick needs (engine.TickEngine._build_cols).  It is the numpy chain
// resolve_blob -> pack_cols_req32 -> sort_packed_by_slot ->
// build_group_plan of ops/engine.py, which stays as the fallback and as
// the reference tests/test_group_plan.py holds this pass to, array for
// array.  Bound through ctypes twice (native/__init__.py): a wide
// window's call runs with the GIL released, a narrow one's keeps it.
//
// Row layout of the (19, b) int32 request slab: engine.REQ32_INDEX.
// Narrow rows, then (lo, hi) pairs of the int64 columns.
// ---------------------------------------------------------------------
}  // extern "C"

namespace {

enum Req32Row : int {
  kSlot = 0, kKnown = 1, kAlgorithm = 2, kBehavior = 3, kValid = 4,
  kHits = 5, kLimit = 7, kDuration = 9, kCreatedAt = 11, kBurst = 13,
  kGregExp = 15, kGregDur = 17, kReq32Rows = 19,
};
constexpr int64_t kCreatedUnset = -1;       // reqcols.CREATED_UNSET
constexpr int64_t kGregorian = 4;           // Behavior.DURATION_IS_GREGORIAN
constexpr int64_t kResetRemaining = 8;      // Behavior.RESET_REMAINING
constexpr int32_t kLeaky = 1;               // Algorithm.LEAKY_BUCKET

// What guber_slotmap_pack_window returns.
enum PackStatus : int64_t {
  kPackUnique = 0,        // slab packed and sorted, no slot repeats
  kPackGrouped = 1,       // ... slots repeat, and the grouped plan is built
  kPackDupsNoPlan = 2,    // ... slots repeat, no grouped plan (not eligible)
  kPackResolvedOnly = -1, // keys resolved, nothing packed: a key found no
                          // slot, or the caller asked to stop at a miss
  kPackNotTaken = -2,     // nothing done: a row needs the host's calendar
};

inline int64_t pad_pow2(int64_t v) {
  return v <= 1 ? 1 : static_cast<int64_t>(next_pow2(static_cast<uint64_t>(v)));
}

// Sort (slot << 32 | request row) keys: the stable sort of the lanes by
// slot, arrival order breaking ties.  LSD radix over the slot's bits
// (11 at a time; slots are below capacity), a comparison sort for the
// few rows of a narrow window.
void sort_lanes(std::vector<uint64_t>& keys, int64_t capacity) {
  const size_t n = keys.size();
  if (n <= 64) {
    std::sort(keys.begin(), keys.end());
    return;
  }
  constexpr int kBits = 11;
  constexpr uint64_t kDigits = 1 << kBits;
  std::vector<uint64_t> tmp(n);
  for (int shift = 32; (capacity - 1) >> (shift - 32) > 0; shift += kBits) {
    uint32_t at[kDigits + 1] = {0};
    for (uint64_t k : keys) ++at[((k >> shift) & (kDigits - 1)) + 1];
    for (uint64_t d = 0; d < kDigits; ++d) at[d + 1] += at[d];
    for (uint64_t k : keys) tmp[at[(k >> shift) & (kDigits - 1)]++] = k;
    keys.swap(tmp);
  }
}

// The seven int64 request columns of a window, n rows each.
struct WindowCols {
  const int64_t *hits, *limit, *duration, *algorithm, *behavior, *created_at,
      *burst;
};

inline bool any_gregorian(const WindowCols& c, int64_t n) {
  for (int64_t i = 0; i < n; ++i) {
    if (c.behavior[i] & kGregorian) return true;
  }
  return false;
}

// Steps 2 + 3 of a window pass, shared by its two entries: the slab
// cleaned (zeros, every lane aimed at the sentinel, ``capacity``), the
// lanes sorted by slot and the REQ32 rows written straight into them.
// order: (slot << 32 | request row) of every row, sorted here; the slots
// are the one-chip map's own, or GLOBAL ones over the shards' maps.
// Stamps last_access[slot] = tick for every lane and, for the one-chip
// entry (kDirty), marks dirty[slot] where the row moves state; out_inv
// maps request order to sorted lanes.  Returns whether a slot repeats.
//
// Every loop walks ONE row of the slab at a time: its rows lie a power
// of two apart, so a loop that touched all nineteen at one lane would
// keep evicting its own cache lines.
template <bool kDirty>
bool write_sorted_lanes(std::vector<uint64_t>& order, int64_t capacity,
                        const WindowCols& c, const uint8_t* known,
                        int64_t now, int32_t* m32, int64_t b,
                        int64_t* out_inv, int64_t* last_access, int64_t tick,
                        uint8_t* dirty, int64_t* n_leaky) {
  const int64_t n = static_cast<int64_t>(order.size());
  std::fill_n(m32, kReq32Rows * b, 0);
  std::fill_n(m32 + kSlot * b, b, static_cast<int32_t>(capacity));
  sort_lanes(order, capacity);
  // src[j] is the request row that sorted lane j holds.
  std::vector<int32_t> src(n);
  int32_t* slot_row = m32 + kSlot * b;
  bool has_dups = false;
  for (int64_t j = 0; j < n; ++j) {
    int32_t i = src[j] = static_cast<int32_t>(order[j] & 0xFFFFFFFFu);
    int32_t slot = slot_row[j] = static_cast<int32_t>(order[j] >> 32);
    has_dups |= j > 0 && slot == slot_row[j - 1];
    out_inv[i] = j;
    last_access[slot] = tick;
    if constexpr (kDirty) {
      if (c.hits[i] != 0 || !known[i] || (c.behavior[i] & kResetRemaining)) {
        dirty[slot] = 1;
      }
    }
  }
  auto put_narrow = [&](int row, auto&& value) {
    int32_t* out = m32 + row * b;
    for (int64_t j = 0; j < n; ++j) out[j] = static_cast<int32_t>(value(src[j]));
  };
  auto put_wide = [&](int row, auto&& value) {
    int32_t* lo = m32 + row * b;
    int32_t* hi = lo + b;
    for (int64_t j = 0; j < n; ++j) {
      int64_t v = value(src[j]);
      lo[j] = static_cast<int32_t>(static_cast<uint32_t>(v));
      hi[j] = static_cast<int32_t>(v >> 32);
    }
  };
  put_narrow(kKnown, [&](int32_t i) { return known[i]; });
  int64_t leaky = 0;
  put_narrow(kAlgorithm, [&](int32_t i) {
    leaky += c.algorithm[i] == kLeaky;
    return c.algorithm[i];
  });
  *n_leaky = leaky;
  put_narrow(kBehavior, [&](int32_t i) { return c.behavior[i]; });
  std::fill_n(m32 + kValid * b, n, 1);
  put_wide(kHits, [&](int32_t i) { return c.hits[i]; });
  put_wide(kLimit, [&](int32_t i) { return c.limit[i]; });
  put_wide(kDuration, [&](int32_t i) { return c.duration[i]; });
  put_wide(kCreatedAt, [&](int32_t i) {
    return c.created_at[i] != kCreatedUnset ? c.created_at[i] : now;
  });
  put_wide(kBurst, [&](int32_t i) { return c.burst[i]; });
  return has_dups;
}

}  // namespace

extern "C" {

// cols: the seven int64 request columns of n rows each, in the order
// hits, limit, duration, algorithm, behavior, created_at, burst.
// m32: a leased (19, b) slab, in any state: a pass that packs cleans it
//   first, a pass that returns a negative status leaves it untouched.
// out_slots / out_known / out_inv: n entries each, request order.
// last_access / dirty: the engine's per-slot arrays (capacity entries);
//   every packed slot is stamped with tick, and marked dirty where its
//   row moves state (hits != 0, a new key, or RESET_REMAINING).
// plan: plan_cap int32 of scratch, written only for kPackGrouped as
//   uidx[b] rank[b] count[upad] mhead[19][upad]  (upad in info[2]).
// info: n_miss, u, upad, n_leaky (rows packed with algorithm LEAKY).
int64_t guber_slotmap_pack_window(
    void* p, const char* blob, const int64_t* offsets, int64_t n,
    const int64_t* hits, const int64_t* limit, const int64_t* duration,
    const int64_t* algorithm, const int64_t* behavior,
    const int64_t* created_at, const int64_t* burst, int64_t now,
    int64_t stop_on_miss, int32_t* m32, int64_t b, int64_t* out_slots,
    uint8_t* out_known, int64_t* out_inv, int64_t* last_access, int64_t tick,
    uint8_t* dirty, int32_t* plan, int64_t plan_cap, int64_t* info) {
  auto* m = static_cast<SlotMap*>(p);
  const WindowCols c{hits, limit, duration, algorithm, behavior, created_at,
                     burst};
  if (any_gregorian(c, n)) return kPackNotTaken;

  // 1. keys -> (slot, known), as guber_slotmap_resolve_batch.
  int64_t n_miss = 0;
  bool unplaced = false;
  for (int64_t i = 0; i < n; ++i) {
    out_slots[i] = m->resolve(blob + offsets[i], offsets[i + 1] - offsets[i],
                              &out_known[i]);
    unplaced |= out_slots[i] < 0;
    n_miss += !out_known[i];
  }
  info[0] = n_miss;
  if (unplaced || (stop_on_miss && n_miss)) return kPackResolvedOnly;

  // 2 + 3. The slab cleaned, the lanes in slot order, the REQ32 rows.
  std::vector<uint64_t> order(n);
  for (int64_t i = 0; i < n; ++i) {
    order[i] = (static_cast<uint64_t>(out_slots[i]) << 32) |
               static_cast<uint64_t>(i);
  }
  const bool has_dups = write_sorted_lanes<true>(
      order, m->capacity, c, out_known, now, m32, b, out_inv, last_access,
      tick, dirty, &info[3]);
  const int32_t* slot_row = m32 + kSlot * b;
  if (!has_dups) return kPackUnique;

  // 4. The grouped plan, by engine.build_group_plan's rules: worth it
  // only when followers are at least an eighth of the rows ...
  int64_t u = 1;
  for (int64_t j = 1; j < n; ++j) u += slot_row[j] != slot_row[j - 1];
  if (n - u < std::max<int64_t>(1, n / 8)) return kPackDupsNoPlan;
  // ... and only when every follower folds into its head: identical to
  // its neighbour in every parameter row (both halves), known, hits > 0,
  // no RESET_REMAINING / Gregorian, a head that provably comes out
  // alive (duration > 0, created_at >= now), token or leaky.
  std::vector<uint8_t> folds(n, 1);
  for (int r = kAlgorithm; r < kReq32Rows; ++r) {
    if (r == kValid) continue;
    const int32_t* row = m32 + r * b;
    for (int64_t j = 1; j < n; ++j) folds[j] &= row[j] == row[j - 1];
  }
  auto wide_at = [&](int row, int64_t j) {
    return (static_cast<int64_t>(m32[(row + 1) * b + j]) << 32) |
           static_cast<uint32_t>(m32[row * b + j]);
  };
  for (int64_t j = 1; j < n; ++j) {
    if (slot_row[j] != slot_row[j - 1]) continue;
    if (!folds[j] || m32[kKnown * b + j] == 0 || wide_at(kHits, j) <= 0 ||
        (m32[kBehavior * b + j] & (kResetRemaining | kGregorian)) != 0 ||
        wide_at(kDuration, j) <= 0 || wide_at(kCreatedAt, j) < now ||
        m32[kAlgorithm * b + j] > kLeaky) {
      return kPackDupsNoPlan;
    }
  }
  int64_t upad = pad_pow2(std::max({u, int64_t{256}, b / 4}));
  if (2 * b + (kReq32Rows + 1) * upad > plan_cap) return kPackDupsNoPlan;
  int32_t* uidx = plan;
  int32_t* rank = plan + b;
  int32_t* count = plan + 2 * b;
  int32_t* mhead = plan + 2 * b + upad;
  std::vector<int32_t> starts(u + 1, static_cast<int32_t>(n));
  for (int64_t j = 0, g = -1; j < n; ++j) {
    if (j == 0 || slot_row[j] != slot_row[j - 1]) starts[++g] = j;
    uidx[j] = static_cast<int32_t>(g);
    rank[j] = static_cast<int32_t>(j - starts[g]);
  }
  // Lanes past n read the last head column; heads past u aim at the
  // guard row with a count of one.
  std::fill(uidx + n, uidx + b, static_cast<int32_t>(upad - 1));
  std::fill(rank + n, rank + b, 0);
  for (int64_t g = 0; g < u; ++g) count[g] = starts[g + 1] - starts[g];
  std::fill(count + u, count + upad, 1);
  for (int r = 0; r < kReq32Rows; ++r) {
    const int32_t* row = m32 + r * b;
    int32_t* head = mhead + r * upad;
    for (int64_t g = 0; g < u; ++g) head[g] = row[starts[g]];
    std::fill(head + u, head + upad,
              r == kSlot ? static_cast<int32_t>(m->capacity) : 0);
  }
  info[1] = u;
  info[2] = upad;
  return kPackGrouped;
}

// The sharded window pass (mesh_engine.MeshTickEngine._pack_window): the
// sibling of guber_slotmap_pack_window over n_shards slot maps, one a
// shard of local_capacity slots.  It is the numpy chain crc32_batch ->
// _group_by_shard -> one resolve_blob a shard -> pack_cols_req32 ->
// sort_packed_by_slot -> RaggedExtents.counts of parallel/mesh_engine.py,
// which stays as the fallback and as the reference
// tests/test_mesh_reference.py holds this pass to, array for array.
//
// 1. CRC-32 of every key % n_shards -> out_sh[i]; the key resolved in
//    THAT shard's map straight from the blob -> out_slots[i] (LOCAL),
//    out_known[i].  A shard's keys meet its map in arrival order, as the
//    regrouped batch's did, so new keys get the same slots.
// 2 + 3. write_sorted_lanes over the GLOBAL slots sh * local_capacity +
//    local, the sentinel the global capacity; last_access (global
//    capacity entries) is stamped with tick.
// No grouped plan and no dirty marks: the sharded engine has neither.
//
// maps: n_shards SlotMap handles.  m32, cols, out_inv: as above.
// out_counts: n_shards entries, the rows a shard (the extents' widths).
// info: n_miss, and the nanoseconds from entry to the end of step 1 (the
//   flight recorder's ``route`` stage; the rest of the call is its
//   ``pack``).
// Returns kPackUnique or kPackDupsNoPlan (packed; slots repeat or not),
// kPackResolvedOnly or kPackNotTaken (as above: the slab untouched).
int64_t guber_slotmap_pack_window_sharded(
    void* const* maps, int64_t n_shards, int64_t local_capacity,
    const char* blob, const int64_t* offsets, int64_t n,
    const int64_t* hits, const int64_t* limit, const int64_t* duration,
    const int64_t* algorithm, const int64_t* behavior,
    const int64_t* created_at, const int64_t* burst, int64_t now,
    int64_t stop_on_miss, int32_t* m32, int64_t b, int64_t* out_sh,
    int64_t* out_slots, uint8_t* out_known, int64_t* out_inv,
    int64_t* last_access, int64_t tick, int64_t* out_counts, int64_t* info) {
  using clock = std::chrono::steady_clock;
  const auto t0 = clock::now();
  const WindowCols c{hits, limit, duration, algorithm, behavior, created_at,
                     burst};
  if (any_gregorian(c, n)) return kPackNotTaken;

  int64_t n_miss = 0;
  bool unplaced = false;
  std::vector<uint64_t> order(n);
  std::fill_n(out_counts, n_shards, 0);
  for (int64_t i = 0; i < n; ++i) {
    const char* key = blob + offsets[i];
    const int64_t len = offsets[i + 1] - offsets[i];
    const int64_t shard = crc32(key, len) % static_cast<uint32_t>(n_shards);
    const int64_t local =
        static_cast<SlotMap*>(maps[shard])->resolve(key, len, &out_known[i]);
    out_sh[i] = shard;
    out_slots[i] = local;
    ++out_counts[shard];
    unplaced |= local < 0;
    n_miss += !out_known[i];
    order[i] = (static_cast<uint64_t>(shard * local_capacity + local) << 32) |
               static_cast<uint64_t>(i);
  }
  info[0] = n_miss;
  info[1] = std::chrono::duration_cast<std::chrono::nanoseconds>(
                clock::now() - t0).count();
  if (unplaced || (stop_on_miss && n_miss)) return kPackResolvedOnly;

  int64_t n_leaky;
  const bool has_dups = write_sorted_lanes<false>(
      order, n_shards * local_capacity, c, out_known, now, m32, b, out_inv,
      last_access, tick, nullptr, &n_leaky);
  return has_dups ? kPackDupsNoPlan : kPackUnique;
}

// Fill out[slot] = 1 for every slot that currently has a key (the engine's
// reclaim scan wants the live-slot mask as one array).
void guber_slotmap_mapped(void* p, uint8_t* out) {
  auto* m = static_cast<SlotMap*>(p);
  for (int64_t s = 0; s < m->capacity; ++s) out[s] = !m->keys[s].empty();
}

// Release a batch of slots in one call (reclaim's victim free list; the
// per-slot ctypes round trip dominates at 10M-slot scale otherwise).
void guber_slotmap_release_batch(void* p, const int64_t* slots, int64_t n) {
  auto* m = static_cast<SlotMap*>(p);
  for (int64_t i = 0; i < n; ++i) m->release(slots[i]);
}

// Copy the keys of n slots into one concatenated blob + n+1 offsets
// (snapshot export).  Returns total bytes required; when that exceeds
// blob_cap nothing is written and the caller retries with a bigger buffer.
// Unassigned slots contribute zero-length spans.
int64_t guber_slotmap_keys_batch(void* p, const int64_t* slots, int64_t n,
                                 char* blob, int64_t blob_cap,
                                 int64_t* offsets) {
  auto* m = static_cast<SlotMap*>(p);
  int64_t total = 0;
  for (int64_t i = 0; i < n; ++i) {
    int64_t s = slots[i];
    if (s >= 0 && s < m->capacity) total += m->keys[s].size();
  }
  if (total > blob_cap) return total;
  int64_t off = 0;
  for (int64_t i = 0; i < n; ++i) {
    offsets[i] = off;
    int64_t s = slots[i];
    if (s >= 0 && s < m->capacity && !m->keys[s].empty()) {
      std::memcpy(blob + off, m->keys[s].data(), m->keys[s].size());
      off += m->keys[s].size();
    }
  }
  offsets[n] = off;
  return total;
}

// Assign a batch of keys (snapshot restore); out_slots[i] = slot or -1 when
// the table is full.
void guber_slotmap_assign_batch(void* p, const char* blob,
                                const int64_t* offsets, int64_t n,
                                int64_t* out_slots) {
  auto* m = static_cast<SlotMap*>(p);
  for (int64_t i = 0; i < n; ++i) {
    out_slots[i] = m->assign(blob + offsets[i], offsets[i + 1] - offsets[i]);
  }
}

// CRC-32 over each key of a packed blob (crc32 above).  One call replaces
// a per-key Python loop on the columnar submit path.
void guber_crc32_batch(const char* blob, const int64_t* offsets, int64_t n,
                       uint32_t* out) {
  for (int64_t i = 0; i < n; ++i) {
    out[i] = crc32(blob + offsets[i], offsets[i + 1] - offsets[i]);
  }
}

}  // extern "C"
