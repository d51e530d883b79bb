// Native protobuf wire codec for the serving hot path.
//
// The gRPC edge's cost is NOT the device tick (~0.1 ms for 4K requests)
// but the per-request Python: materializing 1000 pb message objects and
// walking their attributes costs ~1.5 ms per batch, and building the
// response objects another ~1.4 ms (scripts/service_profile.py).  This
// codec parses the serialized GetRateLimitsReq straight into int64
// columns + a packed key blob (the engine's ReqColumns layout,
// ops/reqcols.py) and the GetRateLimitsResp wire bytes straight from the
// (5, n) response matrix — no message objects on either side.
//
// Wire contract (gubernator.proto; field numbers preserved from the
// reference's python/gubernator/gubernator.proto):
//
//   GetRateLimitsReq:  1 repeated RateLimitReq (len-delimited)
//   RateLimitReq:      1 name (string), 2 unique_key (string),
//                      3 hits, 4 limit, 5 duration (varint int64),
//                      6 algorithm, 7 behavior (varint enum),
//                      8 burst (varint int64), 9 metadata (map),
//                      10 created_at (optional varint int64)
//   GetRateLimitsResp: 1 repeated RateLimitResp (len-delimited)
//   RateLimitResp:     1 status (varint enum), 2 limit, 3 remaining,
//                      4 reset_time (varint int64), 5 error (string),
//                      6 metadata (map)
//
// Unknown fields are skipped by wire type (forward compatibility, the
// same guarantee protobuf gives).  Malformed input returns a negative
// count and the caller falls back to the protobuf library parser.

#include <cstdint>
#include <cstring>

namespace {

struct Reader {
  const uint8_t* p;
  const uint8_t* end;
  bool ok = true;

  uint64_t varint() {
    uint64_t v = 0;
    int shift = 0;
    while (p < end && shift < 64) {
      uint8_t b = *p++;
      v |= static_cast<uint64_t>(b & 0x7F) << shift;
      if (!(b & 0x80)) return v;
      shift += 7;
    }
    ok = false;
    return 0;
  }

  // Skip one field of the given wire type; groups (3/4) and unknown
  // types are malformed here.
  void skip(uint32_t wt) {
    switch (wt) {
      case 0: varint(); break;
      case 1: if (end - p < 8) ok = false; else p += 8; break;
      case 2: {
        uint64_t n = varint();
        if (!ok || static_cast<uint64_t>(end - p) < n) { ok = false; break; }
        p += n;
        break;
      }
      case 5: if (end - p < 4) ok = false; else p += 4; break;
      default: ok = false;
    }
  }
};

struct Writer {
  uint8_t* p;
  uint8_t* end;
  bool ok = true;

  void varint(uint64_t v) {
    while (true) {
      if (p >= end) { ok = false; return; }
      if (v < 0x80) { *p++ = static_cast<uint8_t>(v); return; }
      *p++ = static_cast<uint8_t>(v) | 0x80;
      v >>= 7;
    }
  }

  void bytes(const uint8_t* src, int64_t n) {
    if (end - p < n) { ok = false; return; }
    std::memcpy(p, src, n);
    p += n;
  }
};

inline int varint_size(uint64_t v) {
  int n = 1;
  while (v >= 0x80) { v >>= 7; ++n; }
  return n;
}

}  // namespace

extern "C" {

// Flag bits in out_flags.
enum : uint8_t {
  kNameEmpty = 1,
  kKeyEmpty = 2,
  kHasMetadata = 4,
  kHasCreatedAt = 8,
};

// Count the repeated field-1 submessages of a GetRateLimitsReq /
// GetRateLimitsResp (identical outer shape).  Returns -1 on malformed
// input.
int64_t guber_wire_count(const uint8_t* buf, int64_t len) {
  Reader r{buf, buf + len};
  int64_t n = 0;
  while (r.p < r.end) {
    uint64_t tag = r.varint();
    if (!r.ok) return -1;
    if (tag == ((1u << 3) | 2)) {
      uint64_t sz = r.varint();
      if (!r.ok || static_cast<uint64_t>(r.end - r.p) < sz) return -1;
      r.p += sz;
      ++n;
    } else {
      r.skip(tag & 7);
      if (!r.ok) return -1;
    }
  }
  return n;
}

// Parse a serialized GetRateLimitsReq into columns.
//
//   key_blob   caller buffer of at least len + n bytes ("name_unique");
//   key_off    (n+1) int64 offsets into key_blob;
//   name_len   n int64: byte length of the name part of each key (the
//              '_' splitter position — lets an encoder reconstruct the
//              two wire fields from the packed key);
//   cols       7 arrays of n int64: hits, limit, duration, algorithm,
//              behavior, burst, created_at (created_at left as-is where
//              absent — caller pre-fills the sentinel);
//   out_flags  n uint8 of kNameEmpty/kKeyEmpty/kHasMetadata/kHasCreatedAt.
//
// Returns the number of requests parsed (== guber_wire_count) or -1 on
// malformed input.  Metadata contents are NOT decoded (the caller routes
// metadata-bearing batches to the object path, which re-parses with
// protobuf); only presence is recorded.
int64_t guber_parse_req(const uint8_t* buf, int64_t len,
                        uint8_t* key_blob, int64_t key_cap,
                        int64_t* key_off, int64_t* name_len_out,
                        int64_t* hits, int64_t* limit, int64_t* duration,
                        int64_t* algorithm, int64_t* behavior,
                        int64_t* burst, int64_t* created_at,
                        uint8_t* out_flags) {
  Reader outer{buf, buf + len};
  int64_t n = 0;
  int64_t blob_at = 0;
  key_off[0] = 0;
  while (outer.p < outer.end) {
    uint64_t tag = outer.varint();
    if (!outer.ok) return -1;
    if (tag != ((1u << 3) | 2)) {
      outer.skip(tag & 7);
      if (!outer.ok) return -1;
      continue;
    }
    uint64_t sz = outer.varint();
    if (!outer.ok || static_cast<uint64_t>(outer.end - outer.p) < sz)
      return -1;
    Reader r{outer.p, outer.p + sz};
    outer.p += sz;

    const uint8_t* name_p = nullptr;
    int64_t name_n = 0;
    const uint8_t* key_p = nullptr;
    int64_t key_n = 0;
    uint8_t flags = 0;
    while (r.p < r.end) {
      uint64_t t = r.varint();
      if (!r.ok) return -1;
      uint32_t field = static_cast<uint32_t>(t >> 3);
      uint32_t wt = t & 7;
      if (wt == 2 && (field == 1 || field == 2 || field == 9)) {
        uint64_t fn = r.varint();
        if (!r.ok || static_cast<uint64_t>(r.end - r.p) < fn) return -1;
        if (field == 1) { name_p = r.p; name_n = fn; }
        else if (field == 2) { key_p = r.p; key_n = fn; }
        else flags |= kHasMetadata;
        r.p += fn;
      } else if (wt == 0 && field >= 3 && field <= 10 && field != 9) {
        uint64_t v = r.varint();
        if (!r.ok) return -1;
        int64_t sv = static_cast<int64_t>(v);
        switch (field) {
          case 3: hits[n] = sv; break;
          case 4: limit[n] = sv; break;
          case 5: duration[n] = sv; break;
          case 6: algorithm[n] = sv; break;
          case 7: behavior[n] = sv; break;
          case 8: burst[n] = sv; break;
          case 10: created_at[n] = sv; flags |= kHasCreatedAt; break;
        }
      } else {
        r.skip(wt);
        if (!r.ok) return -1;
      }
    }
    if (name_n == 0) flags |= kNameEmpty;
    if (key_n == 0) flags |= kKeyEmpty;
    name_len_out[n] = name_n;
    if (!(flags & (kNameEmpty | kKeyEmpty))) {
      if (blob_at + name_n + 1 + key_n > key_cap) return -1;
      std::memcpy(key_blob + blob_at, name_p, name_n);
      blob_at += name_n;
      key_blob[blob_at++] = '_';
      std::memcpy(key_blob + blob_at, key_p, key_n);
      blob_at += key_n;
    }
    out_flags[n] = flags;
    ++n;
    key_off[n] = blob_at;
  }
  return n;
}

// Slots of guber_decode_req's summary, before the algorithm histogram.
enum : int { kSumN = 0, kSumFlagsOr, kSumBadAlgorithm, kSumSpecial,
             kSumBlobLen, kSumHist };

// The serving edge's whole decode in one call: count the items, parse
// them into the caller's slab, and tell the caller what it would
// otherwise walk the columns to learn.
//
//   ints        9 rows of int64, row_stride elements apart: name_len,
//               hits, limit, duration, algorithm, behavior, burst,
//               created_at (n each) and key_off (n + 1); rows hold
//               max_rows + 1 elements.  The first n + 1 of every row
//               are zeroed here (proto3 absents must read 0);
//   flags       max_rows uint8 (guber_parse_req's out_flags);
//   blob        blob_cap staging bytes for the packed keys;
//   summary     kSumHist + algorithm_max + 1 int64: n, the OR of the
//               flags, whether any algorithm lies outside
//               [0, algorithm_max], whether any item is special (a
//               behavior bit of special_behavior, or metadata), the
//               blob's length, and the count of each valid algorithm.
//
// created_at reads created_unset where absent or 0 ("the server stamps
// now").  Returns n, -1 on malformed input, or -2 with summary[kSumN]
// set when n > max_rows or len + n > blob_cap (the slab is too small;
// nothing was written).
int64_t guber_decode_req(const uint8_t* buf, int64_t len,
                         int64_t* ints, int64_t row_stride,
                         uint8_t* flags, uint8_t* blob,
                         int64_t max_rows, int64_t blob_cap,
                         int64_t algorithm_max, int64_t special_behavior,
                         int64_t created_unset, int64_t* summary) {
  const int64_t n = guber_wire_count(buf, len);
  if (n < 0) return -1;
  summary[kSumN] = n;
  if (n > max_rows || len + n > blob_cap) return -2;
  for (int r = 0; r < 9; ++r)
    std::memset(ints + r * row_stride, 0, (n + 1) * sizeof(int64_t));
  std::memset(flags, 0, n);
  int64_t* const algorithm = ints + 4 * row_stride;
  int64_t* const behavior = ints + 5 * row_stride;
  int64_t* const created_at = ints + 7 * row_stride;
  int64_t* const key_off = ints + 8 * row_stride;
  if (guber_parse_req(buf, len, blob, blob_cap, key_off, ints,
                      ints + row_stride, ints + 2 * row_stride,
                      ints + 3 * row_stride, algorithm, behavior,
                      ints + 6 * row_stride, created_at, flags) != n)
    return -1;
  int64_t* const hist = summary + kSumHist;
  for (int64_t a = 0; a <= algorithm_max; ++a) hist[a] = 0;
  uint8_t flags_or = 0;
  int64_t behavior_or = 0;
  bool bad_algorithm = false;
  for (int64_t i = 0; i < n; ++i) {
    if (created_at[i] == 0) created_at[i] = created_unset;
    flags_or |= flags[i];
    behavior_or |= behavior[i];
    if (static_cast<uint64_t>(algorithm[i]) >
        static_cast<uint64_t>(algorithm_max))
      bad_algorithm = true;
    else
      ++hist[algorithm[i]];
  }
  summary[kSumFlagsOr] = flags_or;
  summary[kSumBadAlgorithm] = bad_algorithm;
  summary[kSumSpecial] =
      (flags_or & kHasMetadata) || (behavior_or & special_behavior);
  summary[kSumBlobLen] = key_off[n];
  return n;
}

// Parse a serialized GetRateLimitsResp (or GetPeerRateLimitsResp — same
// shape, field 1 repeated RateLimitResp) into a (5, n) column block:
// status, limit, remaining, reset_time, and a has-error flag (1 when the
// item carries a non-empty error string or metadata — the caller
// re-parses those rare items with protobuf for the strings).
// Returns n or -1 on malformed input.
int64_t guber_parse_resp(const uint8_t* buf, int64_t len,
                         int64_t* status, int64_t* limit,
                         int64_t* remaining, int64_t* reset_time,
                         uint8_t* special) {
  Reader outer{buf, buf + len};
  int64_t n = 0;
  while (outer.p < outer.end) {
    uint64_t tag = outer.varint();
    if (!outer.ok) return -1;
    if (tag != ((1u << 3) | 2)) {
      outer.skip(tag & 7);
      if (!outer.ok) return -1;
      continue;
    }
    uint64_t sz = outer.varint();
    if (!outer.ok || static_cast<uint64_t>(outer.end - outer.p) < sz)
      return -1;
    Reader r{outer.p, outer.p + sz};
    outer.p += sz;
    status[n] = limit[n] = remaining[n] = reset_time[n] = 0;
    special[n] = 0;
    while (r.p < r.end) {
      uint64_t t = r.varint();
      if (!r.ok) return -1;
      uint32_t field = static_cast<uint32_t>(t >> 3);
      uint32_t wt = t & 7;
      if (wt == 0 && field >= 1 && field <= 4) {
        uint64_t v = r.varint();
        if (!r.ok) return -1;
        int64_t sv = static_cast<int64_t>(v);
        switch (field) {
          case 1: status[n] = sv; break;
          case 2: limit[n] = sv; break;
          case 3: remaining[n] = sv; break;
          case 4: reset_time[n] = sv; break;
        }
      } else if (wt == 2 && (field == 5 || field == 6)) {
        uint64_t fn = r.varint();
        if (!r.ok || static_cast<uint64_t>(r.end - r.p) < fn) return -1;
        if (fn > 0) special[n] = 1;
        r.p += fn;
      } else {
        r.skip(wt);
        if (!r.ok) return -1;
      }
    }
    ++n;
  }
  return n;
}

// Serialize a GetRateLimitsReq (or GetPeerRateLimitsReq — same shape)
// from columns.  Key blob carries "name_unique" per request with the
// SPLIT position given separately (name_len[i]); proto3 zero-valued
// scalar fields are omitted; created_at is written when has_created[i]
// (optional presence).  Returns bytes written, or -needed when the
// buffer is too small (caller retries with a bigger one), or -1 on
// internal error.
int64_t guber_encode_req(const uint8_t* key_blob, const int64_t* key_off,
                         const int64_t* name_len,
                         const int64_t* hits, const int64_t* limit,
                         const int64_t* duration, const int64_t* algorithm,
                         const int64_t* behavior, const int64_t* burst,
                         const int64_t* created_at,
                         const uint8_t* has_created,
                         int64_t n, uint8_t* out, int64_t out_cap) {
  // Sizing pass.
  int64_t total = 0;
  for (int64_t i = 0; i < n; ++i) {
    int64_t nm = name_len[i];
    int64_t uk = key_off[i + 1] - key_off[i] - nm - 1;
    if (uk < 0) return -1;
    int64_t sz = 0;
    if (nm) sz += 1 + varint_size(nm) + nm;
    if (uk) sz += 1 + varint_size(uk) + uk;
    if (hits[i]) sz += 1 + varint_size(static_cast<uint64_t>(hits[i]));
    if (limit[i]) sz += 1 + varint_size(static_cast<uint64_t>(limit[i]));
    if (duration[i])
      sz += 1 + varint_size(static_cast<uint64_t>(duration[i]));
    if (algorithm[i])
      sz += 1 + varint_size(static_cast<uint64_t>(algorithm[i]));
    if (behavior[i])
      sz += 1 + varint_size(static_cast<uint64_t>(behavior[i]));
    if (burst[i]) sz += 1 + varint_size(static_cast<uint64_t>(burst[i]));
    if (has_created[i])
      sz += 1 + varint_size(static_cast<uint64_t>(created_at[i]));
    total += 1 + varint_size(sz) + sz;
  }
  if (total > out_cap) return -total;

  Writer w{out, out + out_cap};
  for (int64_t i = 0; i < n; ++i) {
    int64_t nm = name_len[i];
    int64_t uk = key_off[i + 1] - key_off[i] - nm - 1;
    const uint8_t* base = key_blob + key_off[i];
    int64_t sz = 0;
    if (nm) sz += 1 + varint_size(nm) + nm;
    if (uk) sz += 1 + varint_size(uk) + uk;
    if (hits[i]) sz += 1 + varint_size(static_cast<uint64_t>(hits[i]));
    if (limit[i]) sz += 1 + varint_size(static_cast<uint64_t>(limit[i]));
    if (duration[i])
      sz += 1 + varint_size(static_cast<uint64_t>(duration[i]));
    if (algorithm[i])
      sz += 1 + varint_size(static_cast<uint64_t>(algorithm[i]));
    if (behavior[i])
      sz += 1 + varint_size(static_cast<uint64_t>(behavior[i]));
    if (burst[i]) sz += 1 + varint_size(static_cast<uint64_t>(burst[i]));
    if (has_created[i])
      sz += 1 + varint_size(static_cast<uint64_t>(created_at[i]));

    w.varint((1u << 3) | 2);
    w.varint(sz);
    if (nm) { w.varint((1u << 3) | 2); w.varint(nm); w.bytes(base, nm); }
    if (uk) {
      w.varint((2u << 3) | 2);
      w.varint(uk);
      w.bytes(base + nm + 1, uk);
    }
    if (hits[i]) {
      w.varint((3u << 3) | 0);
      w.varint(static_cast<uint64_t>(hits[i]));
    }
    if (limit[i]) {
      w.varint((4u << 3) | 0);
      w.varint(static_cast<uint64_t>(limit[i]));
    }
    if (duration[i]) {
      w.varint((5u << 3) | 0);
      w.varint(static_cast<uint64_t>(duration[i]));
    }
    if (algorithm[i]) {
      w.varint((6u << 3) | 0);
      w.varint(static_cast<uint64_t>(algorithm[i]));
    }
    if (behavior[i]) {
      w.varint((7u << 3) | 0);
      w.varint(static_cast<uint64_t>(behavior[i]));
    }
    if (burst[i]) {
      w.varint((8u << 3) | 0);
      w.varint(static_cast<uint64_t>(burst[i]));
    }
    if (has_created[i]) {
      w.varint((10u << 3) | 0);
      w.varint(static_cast<uint64_t>(created_at[i]));
    }
    if (!w.ok) return -1;
  }
  return w.p - out;
}

// Serialize a GetRateLimitsResp from the engine's (5, n) int64 response
// matrix: rows status, limit, remaining, reset_time and over_limit
// (row 4: not a wire field, summed into *over_limit), row_stride
// elements apart, each contiguous (the tick loop hands out column
// slices of one wider matrix).  Proto3 zero-omission matches the
// protobuf library byte for byte for items with no error/metadata.  An
// item is at most 46 bytes (four fields of 1 tag + 10 varint bytes, and
// a 2-byte header since 44 < 128), so out_cap >= 46 n always fits.
// Returns bytes written, or -1 when out_cap is smaller than that.
int64_t guber_encode_resp_mat(const int64_t* mat, int64_t row_stride,
                              int64_t n, uint8_t* out, int64_t out_cap,
                              int64_t* over_limit) {
  if (out_cap < 46 * n) return -1;
  const int64_t* const over = mat + 4 * row_stride;
  Writer w{out, out + out_cap};
  int64_t over_sum = 0;
  for (int64_t i = 0; i < n; ++i) {
    int64_t sz = 0;
    for (int f = 0; f < 4; ++f) {
      const int64_t v = mat[f * row_stride + i];
      if (v) sz += 1 + varint_size(static_cast<uint64_t>(v));
    }
    w.varint((1u << 3) | 2);
    w.varint(sz);
    for (int f = 0; f < 4; ++f) {
      const int64_t v = mat[f * row_stride + i];
      if (v) {
        w.varint(((f + 1u) << 3) | 0);
        w.varint(static_cast<uint64_t>(v));
      }
    }
    over_sum += over[i];
  }
  if (!w.ok) return -1;
  *over_limit = over_sum;
  return w.p - out;
}

}  // extern "C"
