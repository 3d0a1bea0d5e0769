// guberhost — native host ingress/egress for gubernator-tpu.
//
// The serving hot path's host-side cost is per-item Python work: protobuf
// message traversal, string hashing, and response object construction
// (~1-2 µs/item), which caps a host at ~1M checks/s regardless of kernel
// speed. This module parses the GetRateLimitsReq WIRE BYTES directly into
// flat column buffers (consumed via np.frombuffer), computes both hashes
// (63-bit seeded XXH64 fingerprint — ops/hashing.py parity; fnv1a_32 ring
// point — peers/hash_ring.py parity) in the same pass, and serializes
// GetRateLimitsResp straight from response columns.
//
// Wire schema parsed (proto/gubernator.proto):
//   GetRateLimitsReq { repeated RateLimitReq requests = 1; }
//   RateLimitReq { name=1 str; unique_key=2 str; hits=3; limit=4;
//                  duration=5; algorithm=6; behavior=7; burst=8;
//                  metadata=9 (skipped); created_at=10 }
//   GetRateLimitsResp { repeated RateLimitResp responses = 1; }
//   RateLimitResp { status=1; limit=2; remaining=3; reset_time=4;
//                   error=5 str; metadata=6 }
//
// No libprotobuf dependency: varint/length-delimited framing is ~60 lines.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <algorithm>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <memory>
#include <string>
#include <utility>
#include <vector>

// ------------------------------------------------------------------ XXH64
// Standard XXH64 (public algorithm; matches python-xxhash output).

static const uint64_t P1 = 11400714785074694791ULL;
static const uint64_t P2 = 14029467366897019727ULL;
static const uint64_t P3 = 1609587929392839161ULL;
static const uint64_t P4 = 9650029242287828579ULL;
static const uint64_t P5 = 2870177450012600261ULL;

static inline uint64_t rotl64(uint64_t x, int r) {
  return (x << r) | (x >> (64 - r));
}
static inline uint64_t read64(const uint8_t* p) {
  uint64_t v;
  memcpy(&v, p, 8);
  return v;  // little-endian hosts only (x86/ARM)
}
static inline uint32_t read32(const uint8_t* p) {
  uint32_t v;
  memcpy(&v, p, 4);
  return v;
}
static inline uint64_t xxh_round(uint64_t acc, uint64_t input) {
  acc += input * P2;
  acc = rotl64(acc, 31);
  return acc * P1;
}
static inline uint64_t xxh_merge(uint64_t acc, uint64_t val) {
  acc ^= xxh_round(0, val);
  return acc * P1 + P4;
}

static uint64_t xxh64(const uint8_t* p, size_t len, uint64_t seed) {
  const uint8_t* end = p + len;
  uint64_t h;
  if (len >= 32) {
    uint64_t v1 = seed + P1 + P2, v2 = seed + P2, v3 = seed, v4 = seed - P1;
    const uint8_t* limit = end - 32;
    do {
      v1 = xxh_round(v1, read64(p)); p += 8;
      v2 = xxh_round(v2, read64(p)); p += 8;
      v3 = xxh_round(v3, read64(p)); p += 8;
      v4 = xxh_round(v4, read64(p)); p += 8;
    } while (p <= limit);
    h = rotl64(v1, 1) + rotl64(v2, 7) + rotl64(v3, 12) + rotl64(v4, 18);
    h = xxh_merge(h, v1); h = xxh_merge(h, v2);
    h = xxh_merge(h, v3); h = xxh_merge(h, v4);
  } else {
    h = seed + P5;
  }
  h += (uint64_t)len;
  while (p + 8 <= end) {
    h ^= xxh_round(0, read64(p));
    h = rotl64(h, 27) * P1 + P4;
    p += 8;
  }
  if (p + 4 <= end) {
    h ^= (uint64_t)read32(p) * P1;
    h = rotl64(h, 23) * P2 + P3;
    p += 4;
  }
  while (p < end) {
    h ^= (*p) * P5;
    h = rotl64(h, 11) * P1;
    p++;
  }
  h ^= h >> 33;
  h *= P2;
  h ^= h >> 29;
  h *= P3;
  h ^= h >> 32;
  return h;
}

static inline uint32_t fnv1a_32(const uint8_t* p, size_t len) {
  uint32_t h = 2166136261u;
  for (size_t i = 0; i < len; i++) {
    h ^= p[i];
    h *= 16777619u;
  }
  return h;
}

// ------------------------------------------------------------ proto frames

struct Cursor {
  const uint8_t* p;
  const uint8_t* end;
  bool ok = true;

  uint64_t varint() {
    uint64_t v = 0;
    int shift = 0;
    while (p < end && shift < 64) {
      uint8_t b = *p++;
      v |= (uint64_t)(b & 0x7F) << shift;
      if (!(b & 0x80)) return v;
      shift += 7;
    }
    ok = false;
    return 0;
  }

  bool skip(uint32_t wt) {
    switch (wt) {
      case 0: varint(); return ok;
      case 1: if (end - p < 8) return ok = false; p += 8; return true;
      case 2: {
        uint64_t n = varint();
        if (!ok || (uint64_t)(end - p) < n) return ok = false;
        p += n;
        return true;
      }
      case 5: if (end - p < 4) return ok = false; p += 4; return true;
      default: return ok = false;
    }
  }
};

static const uint64_t FP_SEED = 0x6775626572ULL;  // hashing.py _SEED
static const uint64_t MASK63 = (1ULL << 63) - 1;

// err codes — ops/batch.py ERR_*
enum { ERR_OK = 0, ERR_EMPTY_KEY = 1, ERR_EMPTY_NAME = 2 };

// Compact-wire layout constants — MUST mirror ops/wire.py (DUR_BITS,
// HITS_BITS, behavior bit budget). The parser pre-packs each item into the
// 5-lane int32 ingress row IN THE SAME PASS so the serving path can stage a
// dispatch grid without ever materializing per-column int64 arrays; the
// created_at delta (lane 4 bits 18-27) is left zero — the flush loop ORs it
// in once the batch base is known; bits 28-29 carry the priority tier. Lane 3 is duration[0:27] | algo << 27
// (3 bits — five in-kernel algorithms) | cascade_level << 30; the parser
// always emits level 0 (cascade requests take the pb path — see field 11
// below).
static const int WIRE_LANES = 5;       // ops/wire.WIRE_LANES
static const int WIRE_DUR_BITS = 27;   // ops/wire.DUR_BITS
static const int WIRE_HITS_BITS = 18;  // ops/wire.HITS_BITS
static const int64_t WIRE_DUR_MASK = (1LL << WIRE_DUR_BITS) - 1;
static const int64_t WIRE_HITS_MASK = (1LL << WIRE_HITS_BITS) - 1;
// lane 4's created-at delta, biased, above the hits (ops/wire.DELTA_BITS,
// DELTA_BIAS): the staging stamps it (stage_wire_chunk), the parser leaves 0
static const int64_t WIRE_DELTA_MASK = (1LL << 10) - 1;
static const int64_t WIRE_DELTA_BIAS = 1LL << 9;
static const uint32_t WIRE_RESET_BIT = 1u << 30;  // lane 4: RESET_REMAINING
static const int WIRE_LEVEL_SHIFT = 30;  // lane 3: cascade level, 2 bits
static const int64_t WIRE_I32_MAX = 2147483647LL;
// RESET_REMAINING | DRAIN_OVER_LIMIT | kernel-inert bits | the 2-bit
// priority tier (ops/wire.py _ENCODABLE_BEHAVIOR); anything else
// (Gregorian, unknown) → full-width
static const int32_t WIRE_ENC_BEHAVIOR = 8 | 32 | 1 | 2 | 16 | 64 | 128;
// known client-facing behavior bits: flag values 1..32 plus the 2-bit
// priority tier at bits 6-7 (types.PRIORITY_SHIFT) — anything above is
// masked at ingress: the behavior word's high bits carry the INTERNAL
// cascade level (types.CASCADE_LEVEL_SHIFT), which clients must not be
// able to forge
static const int32_t BEHAVIOR_CLIENT_MASK = 255;
// highest algorithm enum this build speaks (types.MAX_ALGORITHM); larger
// values are per-item errors on the full path, so never fused
static const int32_t MAX_ALGORITHM = 4;
static const int32_t ALGO_CONCURRENCY_LEASE = 4;  // types.Algorithm
// gubernator_tpu_decisions_total's labels (service/runner._ALGO_LABELS): one
// an algorithm, and `invalid` last for a value that is none of them
static const int DECISION_LABELS = 6;

struct Item {
  const uint8_t* name = nullptr; size_t name_len = 0;
  const uint8_t* key = nullptr; size_t key_len = 0;
  const uint8_t* traceparent = nullptr; size_t traceparent_len = 0;
  int64_t hits = 0, limit = 0, duration = 0, burst = 0, created_at = 0;
  int32_t algorithm = 0, behavior = 0;
  bool has_cascade = false;  // repeated CascadeLevel cascade = 11 present
  size_t start = 0, len = 0;  // byte span of the item message in the input
};

// metadata map entry {1: key str, 2: value str} — only "traceparent" is
// routing-relevant (trace propagation; docs/tracing.md)
static void parse_metadata_entry(const uint8_t* p, const uint8_t* end,
                                 Item& it) {
  Cursor c{p, end};
  const uint8_t* k = nullptr; size_t klen = 0;
  const uint8_t* v = nullptr; size_t vlen = 0;
  while (c.p < c.end && c.ok) {
    uint64_t tag = c.varint();
    if (!c.ok) return;
    uint32_t field = (uint32_t)(tag >> 3), wt = (uint32_t)(tag & 7);
    if ((field == 1 || field == 2) && wt == 2) {
      uint64_t n = c.varint();
      if (!c.ok || (uint64_t)(c.end - c.p) < n) return;
      if (field == 1) { k = c.p; klen = n; } else { v = c.p; vlen = n; }
      c.p += n;
    } else if (!c.skip(wt)) {
      return;
    }
  }
  if (k && v && klen == 11 && memcmp(k, "traceparent", 11) == 0) {
    it.traceparent = v;
    it.traceparent_len = vlen;
  }
}

static bool parse_item(Cursor& c, Item& it) {
  while (c.p < c.end && c.ok) {
    uint64_t tag = c.varint();
    if (!c.ok) return false;
    uint32_t field = (uint32_t)(tag >> 3), wt = (uint32_t)(tag & 7);
    switch (field) {
      case 1: case 2: {  // name / unique_key
        if (wt != 2) return false;
        uint64_t n = c.varint();
        if (!c.ok || (uint64_t)(c.end - c.p) < n) return false;
        if (field == 1) { it.name = c.p; it.name_len = n; }
        else { it.key = c.p; it.key_len = n; }
        c.p += n;
        break;
      }
      case 3: it.hits = (int64_t)c.varint(); break;
      case 4: it.limit = (int64_t)c.varint(); break;
      case 5: it.duration = (int64_t)c.varint(); break;
      case 6: it.algorithm = (int32_t)c.varint(); break;
      case 7: it.behavior = (int32_t)c.varint(); break;
      case 8: it.burst = (int64_t)c.varint(); break;
      case 9: {  // metadata map entry
        if (wt != 2) return false;
        uint64_t n = c.varint();
        if (!c.ok || (uint64_t)(c.end - c.p) < n) return false;
        parse_metadata_entry(c.p, c.p + n, it);
        c.p += n;
        break;
      }
      case 10: it.created_at = (int64_t)c.varint(); break;
      case 11:  // repeated CascadeLevel cascade — flag it; the daemon
                // materializes the pb item and expands the levels itself
        it.has_cascade = true;
        if (!c.skip(wt)) return false;
        break;
      default:
        if (!c.skip(wt)) return false;
    }
  }
  return c.ok;
}

// parse_get_rate_limits(data: bytes, now_ms: int = 0)
//   -> (n, fp, algo, behavior, hits, limit, burst, duration, created_at,
//       err, ring_hash, spans, traceparent, lanes, enc, summary)
// Buffer layouts (np.frombuffer): fp/hits/limit/burst/duration/created_at
// int64; algo/behavior int32; err int8; ring_hash uint32; spans int64 pairs
// (start, len) of each item's bytes for lazy pb materialization; lanes a
// (5, n) row-major int32 pre-packed compact-wire image (ops/wire.py lanes,
// created-delta field zero); enc int8 per-item compact-wire encodability.
// summary is the batch reduced over its items in the fill loop
// (service/wire.RowSummary, in field order): rows with err set, OR of the
// behavior words, lease rows, rows the client sent with created_at 0, enc
// rows, highest priority tier, rows that carry a cascade field (such a batch
// takes the pb path, where the daemon expands the levels), the earliest and
// the latest created_at as served, the first row's fingerprint (the
// batcher's tenant bucket), and the rows by decision label
// (service/runner._ALGO_LABELS: one count an algorithm, an out-of-range value
// under the last) — what the handler, the enqueue and the dispatch's
// decision counters would otherwise scan the columns for on the event-loop
// thread.
// now_ms is the handler's clock at request entry (upstream stamps there,
// gubernator.go:225-227): a row the client sent with created_at 0 is served
// with it, so the column comes back stamped and stamp_lo / stamp_hi are over
// the stamps as served. 0 stamps nothing (the rows stay 0 and are counted
// in the range as 0); an empty batch reads now_ms for both ends.
// The scan + fill loops run with the GIL RELEASED — N front-door workers
// parse concurrently (service/daemon.py door pool).
static PyObject* parse_get_rate_limits(PyObject*, PyObject* args) {
  Py_buffer buf;
  long long now_ms = 0;
  if (!PyArg_ParseTuple(args, "y*|L", &buf, &now_ms)) return nullptr;
  const uint8_t* data = (const uint8_t*)buf.buf;

  std::vector<Item> items;
  items.reserve(64);
  bool ok = true;
  Py_BEGIN_ALLOW_THREADS;
  Cursor top{data, data + buf.len};
  while (top.p < top.end && top.ok) {
    uint64_t tag = top.varint();
    if (!top.ok) break;
    uint32_t field = (uint32_t)(tag >> 3), wt = (uint32_t)(tag & 7);
    if (field == 1 && wt == 2) {
      uint64_t n = top.varint();
      if (!top.ok || (uint64_t)(top.end - top.p) < n) { top.ok = false; break; }
      Item it;
      it.start = (size_t)(top.p - data);
      it.len = (size_t)n;
      Cursor ic{top.p, top.p + n};
      if (!parse_item(ic, it)) { top.ok = false; break; }
      items.push_back(it);
      top.p += n;
    } else if (!top.skip(wt)) {
      break;
    }
  }
  ok = top.ok;
  Py_END_ALLOW_THREADS;
  if (!ok) {
    PyBuffer_Release(&buf);
    PyErr_SetString(PyExc_ValueError, "malformed GetRateLimitsReq");
    return nullptr;
  }

  size_t n = items.size();
  // first propagated trace context in the batch (the daemon adopts one
  // scope per request, same as the pb path's first-match extraction)
  PyObject* tp = nullptr;
  for (size_t i = 0; i < n && !tp; i++) {
    if (items[i].traceparent) {
      tp = PyUnicode_DecodeUTF8((const char*)items[i].traceparent,
                                (Py_ssize_t)items[i].traceparent_len,
                                "replace");
      if (!tp) PyErr_Clear();
    }
  }
  if (!tp) {
    tp = Py_None;
    Py_INCREF(Py_None);
  }
  PyObject* out = PyTuple_New(16);
  PyObject* fp_b = PyBytes_FromStringAndSize(nullptr, n * 8);
  PyObject* algo_b = PyBytes_FromStringAndSize(nullptr, n * 4);
  PyObject* beh_b = PyBytes_FromStringAndSize(nullptr, n * 4);
  PyObject* hits_b = PyBytes_FromStringAndSize(nullptr, n * 8);
  PyObject* lim_b = PyBytes_FromStringAndSize(nullptr, n * 8);
  PyObject* burst_b = PyBytes_FromStringAndSize(nullptr, n * 8);
  PyObject* dur_b = PyBytes_FromStringAndSize(nullptr, n * 8);
  PyObject* ca_b = PyBytes_FromStringAndSize(nullptr, n * 8);
  PyObject* err_b = PyBytes_FromStringAndSize(nullptr, n);
  PyObject* ring_b = PyBytes_FromStringAndSize(nullptr, n * 4);
  PyObject* span_b = PyBytes_FromStringAndSize(nullptr, n * 16);
  PyObject* lanes_b = PyBytes_FromStringAndSize(nullptr, n * 5 * 4);
  PyObject* enc_b = PyBytes_FromStringAndSize(nullptr, n);
  if (!out || !fp_b || !algo_b || !beh_b || !hits_b || !lim_b || !burst_b ||
      !dur_b || !ca_b || !err_b || !ring_b || !span_b || !lanes_b || !enc_b) {
    PyBuffer_Release(&buf);
    Py_XDECREF(out);
    return nullptr;
  }
  int64_t* fp = (int64_t*)PyBytes_AS_STRING(fp_b);
  int32_t* algo = (int32_t*)PyBytes_AS_STRING(algo_b);
  int32_t* beh = (int32_t*)PyBytes_AS_STRING(beh_b);
  int64_t* hits = (int64_t*)PyBytes_AS_STRING(hits_b);
  int64_t* lim = (int64_t*)PyBytes_AS_STRING(lim_b);
  int64_t* burst = (int64_t*)PyBytes_AS_STRING(burst_b);
  int64_t* dur = (int64_t*)PyBytes_AS_STRING(dur_b);
  int64_t* ca = (int64_t*)PyBytes_AS_STRING(ca_b);
  int8_t* err = (int8_t*)PyBytes_AS_STRING(err_b);
  uint32_t* ring = (uint32_t*)PyBytes_AS_STRING(ring_b);
  int64_t* span = (int64_t*)PyBytes_AS_STRING(span_b);
  int32_t* lanes = (int32_t*)PyBytes_AS_STRING(lanes_b);
  int8_t* enc = (int8_t*)PyBytes_AS_STRING(enc_b);

  long long n_err = 0, n_lease = 0, n_unstamped = 0, n_enc = 0, n_casc = 0;
  long long by_label[DECISION_LABELS] = {0};
  int64_t stamp_lo = now_ms, stamp_hi = now_ms;
  int32_t beh_or = 0, max_tier = 0;
  Py_BEGIN_ALLOW_THREADS;
  std::string hk;
  for (size_t i = 0; i < n; i++) {
    const Item& it = items[i];
    algo[i] = it.algorithm;
    by_label[it.algorithm >= 0 && it.algorithm < DECISION_LABELS - 1
                 ? it.algorithm
                 : DECISION_LABELS - 1]++;
    // client-facing flag bits only: the high bits are the internal cascade
    // level field, which must never arrive from the wire
    beh[i] = it.behavior & BEHAVIOR_CLIENT_MASK;
    beh_or |= beh[i];
    if (((beh[i] >> 6) & 3) > max_tier) max_tier = (beh[i] >> 6) & 3;
    n_lease += it.algorithm == ALGO_CONCURRENCY_LEASE;
    n_unstamped += it.created_at == 0;
    n_casc += it.has_cascade;
    hits[i] = it.hits;
    lim[i] = it.limit;
    burst[i] = it.burst;
    dur[i] = it.duration;
    ca[i] = it.created_at ? it.created_at : now_ms;
    if (i == 0 || ca[i] < stamp_lo) stamp_lo = ca[i];
    if (i == 0 || ca[i] > stamp_hi) stamp_hi = ca[i];
    span[2 * i] = (int64_t)it.start;
    span[2 * i + 1] = (int64_t)it.len;
    fp[i] = 0;
    ring[i] = 0;
    lanes[i] = lanes[n + i] = lanes[2 * n + i] = lanes[3 * n + i] =
        lanes[4 * n + i] = 0;
    if (it.key_len == 0 || it.name_len == 0) {
      err[i] = it.key_len == 0 ? ERR_EMPTY_KEY : ERR_EMPTY_NAME;
      enc[i] = 1;
      n_err++;
      n_enc++;
      continue;
    }
    err[i] = ERR_OK;
    hk.clear();
    hk.append((const char*)it.name, it.name_len);
    hk.push_back('_');
    hk.append((const char*)it.key, it.key_len);
    uint64_t h =
        xxh64((const uint8_t*)hk.data(), hk.size(), FP_SEED) & MASK63;
    fp[i] = (int64_t)(h ? h : 1);
    ring[i] = fnv1a_32((const uint8_t*)hk.data(), hk.size());
    // compact-wire encodability, the ops/wire.wire_encodable checks the
    // parser can settle per-item (created_at skew is batch-relative — the
    // flush loop checks it). Validation-error fields (|limit|/|burst|
    // beyond int32) ALSO fall back: the full path turns them into
    // per-item errors the fused path has no pack stage to produce.
    bool e = (beh[i] & ~WIRE_ENC_BEHAVIOR) == 0 &&
             it.duration >= 0 && it.duration <= WIRE_DUR_MASK &&
             it.hits >= 0 && it.hits <= WIRE_HITS_MASK &&
             it.limit >= 0 && it.limit <= WIRE_I32_MAX &&
             it.burst >= -WIRE_I32_MAX && it.burst <= WIRE_I32_MAX &&
             (it.algorithm >= 0 && it.algorithm <= MAX_ALGORITHM) &&
             // burst lane rules: token ignores burst; leaky/GCRA default
             // burst 0 → limit in-trace (explicit bursts → full-width);
             // window/lease never read burst (keep 0 for byte fidelity)
             (it.algorithm == 0 || it.burst == 0) &&
             !it.has_cascade;
    enc[i] = e ? 1 : 0;
    n_enc += e;
    // pre-packed 5-lane int32 row (ops/wire.pack_wire_rows layout);
    // lane 4's created-delta bits stay 0 until the flush stamps them
    uint64_t ufp = (uint64_t)fp[i];
    lanes[i] = (int32_t)(uint32_t)(ufp & 0xFFFFFFFFu);
    lanes[n + i] = (int32_t)(uint32_t)(ufp >> 32);
    lanes[2 * n + i] = (int32_t)it.limit;
    lanes[3 * n + i] = (int32_t)(uint32_t)(
        ((uint64_t)(it.duration & WIRE_DUR_MASK)) |
        ((uint64_t)(uint32_t)it.algorithm << WIRE_DUR_BITS));
    uint32_t l4 = (uint32_t)(it.hits & WIRE_HITS_MASK);
    l4 |= (uint32_t)((it.behavior >> 6) & 3) << 28;  // priority tier
    if (it.behavior & 8) l4 |= WIRE_RESET_BIT;
    if (it.behavior & 32) l4 |= 1u << 31;  // DRAIN_OVER_LIMIT
    lanes[4 * n + i] = (int32_t)l4;
  }
  Py_END_ALLOW_THREADS;
  PyBuffer_Release(&buf);

  PyTuple_SET_ITEM(out, 0, PyLong_FromSize_t(n));
  PyTuple_SET_ITEM(out, 1, fp_b);
  PyTuple_SET_ITEM(out, 2, algo_b);
  PyTuple_SET_ITEM(out, 3, beh_b);
  PyTuple_SET_ITEM(out, 4, hits_b);
  PyTuple_SET_ITEM(out, 5, lim_b);
  PyTuple_SET_ITEM(out, 6, burst_b);
  PyTuple_SET_ITEM(out, 7, dur_b);
  PyTuple_SET_ITEM(out, 8, ca_b);
  PyTuple_SET_ITEM(out, 9, err_b);
  PyTuple_SET_ITEM(out, 10, ring_b);
  PyTuple_SET_ITEM(out, 11, span_b);
  PyTuple_SET_ITEM(out, 12, tp);
  PyTuple_SET_ITEM(out, 13, lanes_b);
  PyTuple_SET_ITEM(out, 14, enc_b);
  static_assert(DECISION_LABELS == 6, "the summary's tuple names each label");
  PyObject* summary = Py_BuildValue(
      "(LiLLLiLLLL(LLLLLL))", n_err, (int)beh_or, n_lease, n_unstamped, n_enc,
      (int)max_tier, n_casc, (long long)stamp_lo, (long long)stamp_hi,
      (long long)(n ? fp[0] : 0), by_label[0], by_label[1], by_label[2],
      by_label[3], by_label[4], by_label[5]);
  if (!summary) {
    Py_DECREF(out);
    return nullptr;
  }
  PyTuple_SET_ITEM(out, 15, summary);
  return out;
}

// ------------------------------------------------------------- encode side

static inline void put_varint(std::string& out, uint64_t v) {
  while (v >= 0x80) {
    out.push_back((char)(v | 0x80));
    v >>= 7;
  }
  out.push_back((char)v);
}
static inline void put_tag(std::string& out, uint32_t field, uint32_t wt) {
  put_varint(out, ((uint64_t)field << 3) | wt);
}

// One RateLimitResp as item `responses` (field 1) of a GetRateLimitsResp,
// appended to `out`; `item` is scratch. `err` is the row's error string or
// null. With now_ms >= 0 a DENIED row additionally carries
// metadata["retry_after_ms"] = max(0, reset_time - now_ms) — for GCRA
// denials reset_time is the exact TAT-derived conforming instant
// (ops/math.py), so clients honoring it back off precisely.
static inline void put_response(std::string& out, std::string& item,
                                int64_t st, int64_t li, int64_t re, int64_t rt,
                                const std::string* err, long long now_ms) {
  item.clear();
  if (st) { put_tag(item, 1, 0); put_varint(item, (uint64_t)st); }
  if (li) { put_tag(item, 2, 0); put_varint(item, (uint64_t)li); }
  if (re) { put_tag(item, 3, 0); put_varint(item, (uint64_t)re); }
  if (rt) { put_tag(item, 4, 0); put_varint(item, (uint64_t)rt); }
  if (err && !err->empty()) {
    put_tag(item, 5, 2);
    put_varint(item, err->size());
    item += *err;
  }
  if (now_ms >= 0 && st == 1) {
    // metadata map entry {1: "retry_after_ms", 2: decimal-ms}
    static const char RA_KEY[] = "retry_after_ms";
    long long d = rt - now_ms;
    if (d < 0) d = 0;
    char vbuf[24];
    int vlen = snprintf(vbuf, sizeof vbuf, "%lld", d);
    std::string entry;
    put_tag(entry, 1, 2);
    put_varint(entry, sizeof(RA_KEY) - 1);
    entry.append(RA_KEY, sizeof(RA_KEY) - 1);
    put_tag(entry, 2, 2);
    put_varint(entry, (uint64_t)vlen);
    entry.append(vbuf, (size_t)vlen);
    put_tag(item, 6, 2);
    put_varint(item, entry.size());
    item += entry;
  }
  put_tag(out, 1, 2);
  put_varint(out, item.size());
  out += item;
}

// encode_responses(status_i64, limit_i64, remaining_i64, reset_i64,
//                  errors: dict[int, str], now_ms: int = -1)
//                  -> bytes(GetRateLimitsResp)
// The column buffers are raw little-endian int64 — any buffer-protocol
// object works (contiguous numpy int64 arrays pass ZERO-COPY; no .tobytes()
// round trip). Error strings are gathered under the GIL up front; the
// varint/field assembly then runs with the GIL RELEASED so N responder
// workers encode concurrently. `now_ms` as in put_response.
static PyObject* encode_responses(PyObject*, PyObject* args) {
  Py_buffer sb, lb, rb, tb;
  PyObject* errs;
  long long now_ms = -1;
  if (!PyArg_ParseTuple(args, "y*y*y*y*O|L", &sb, &lb, &rb, &tb, &errs,
                        &now_ms))
    return nullptr;
  size_t n = (size_t)(sb.len / 8);
  const int64_t* st = (const int64_t*)sb.buf;
  const int64_t* li = (const int64_t*)lb.buf;
  const int64_t* re = (const int64_t*)rb.buf;
  const int64_t* rt = (const int64_t*)tb.buf;

  // sparse {row: message} dict → C-side (row, utf8) list, GIL held
  std::vector<std::pair<size_t, std::string>> errv;
  bool bad = false;
  if (errs != Py_None) {
    PyObject *key, *val;
    Py_ssize_t pos = 0;
    while (PyDict_Next(errs, &pos, &key, &val)) {
      size_t row = (size_t)PyLong_AsSize_t(key);
      if (row == (size_t)-1 && PyErr_Occurred()) { bad = true; break; }
      Py_ssize_t elen;
      const char* ep = PyUnicode_AsUTF8AndSize(val, &elen);
      if (!ep) { bad = true; break; }
      if (elen) errv.emplace_back(row, std::string(ep, (size_t)elen));
    }
  }
  if (bad) {
    PyBuffer_Release(&sb); PyBuffer_Release(&lb);
    PyBuffer_Release(&rb); PyBuffer_Release(&tb);
    return nullptr;
  }
  std::vector<const std::string*> err_at(errv.empty() ? 0 : n, nullptr);
  for (const auto& kv : errv)
    if (kv.first < n) err_at[kv.first] = &kv.second;

  std::string out;
  Py_BEGIN_ALLOW_THREADS;
  out.reserve(n * 24);
  std::string item;
  for (size_t i = 0; i < n; i++)
    put_response(out, item, st[i], li[i], re[i], rt[i],
                 err_at.empty() ? nullptr : err_at[i], now_ms);
  Py_END_ALLOW_THREADS;
  PyBuffer_Release(&sb);
  PyBuffer_Release(&lb);
  PyBuffer_Release(&rb);
  PyBuffer_Release(&tb);
  return PyBytes_FromStringAndSize(out.data(), (Py_ssize_t)out.size());
}

// The wire strings of the engine's error codes (ops/batch.ERROR_STRINGS),
// indexed by code; native.load() hands them in once. Replaced and read
// (copied) under the GIL, so an encode that runs without it keeps the
// table it started with.
static std::shared_ptr<const std::vector<std::string>> error_strings;

// set_error_strings(strings: sequence[str]) — entry i is code i's message
static PyObject* set_error_strings(PyObject*, PyObject* arg) {
  PyObject* seq = PySequence_Fast(arg, "a sequence of str expected");
  if (!seq) return nullptr;
  auto table = std::make_shared<std::vector<std::string>>();
  for (Py_ssize_t i = 0; i < PySequence_Fast_GET_SIZE(seq); i++) {
    Py_ssize_t len;
    const char* p =
        PyUnicode_AsUTF8AndSize(PySequence_Fast_GET_ITEM(seq, i), &len);
    if (!p) { Py_DECREF(seq); return nullptr; }
    table->emplace_back(p, (size_t)len);
  }
  Py_DECREF(seq);
  error_strings = std::move(table);
  Py_RETURN_NONE;
}

// One integer column behind the buffer protocol: one dimension, any
// stride, 1/2/4/8-byte items, signed or not (a numpy int8/int32/int64/bool
// array as it is, no copy). Opened and released with the GIL held; read
// without it.
struct IntCol {
  Py_buffer view;
  bool held = false, sign = true;
  ~IntCol() { if (held) PyBuffer_Release(&view); }

  bool open(PyObject* obj, const char* what) {
    if (PyObject_GetBuffer(obj, &view, PyBUF_STRIDES | PyBUF_FORMAT) < 0)
      return false;
    held = true;
    const char* f = view.format ? view.format : "B";
    if (*f == '@' || *f == '=' || *f == '<') f++;  // this host's byte order
    if (strchr("bhilqn", *f) && *f) sign = true;
    else if (strchr("BHILQN?", *f) && *f) sign = false;
    else f = nullptr;
    Py_ssize_t w = view.itemsize;
    if (view.ndim != 1 || !f || f[1] || (w != 1 && w != 2 && w != 4 && w != 8)) {
      PyErr_Format(PyExc_TypeError,
                   "%s: a one-dimensional integer column expected", what);
      return false;
    }
    return true;
  }
  Py_ssize_t size() const { return view.shape[0]; }
  inline int64_t at(Py_ssize_t i) const {
    const char* p = (const char*)view.buf + i * view.strides[0];
    switch (view.itemsize) {
      case 8: { int64_t v; memcpy(&v, p, 8); return v; }
      case 4: { int32_t v; memcpy(&v, p, 4);
                return sign ? (int64_t)v : (int64_t)(uint32_t)v; }
      case 2: { int16_t v; memcpy(&v, p, 2);
                return sign ? (int64_t)v : (int64_t)(uint16_t)v; }
      default: return sign ? (int64_t)*(const int8_t*)p
                           : (int64_t)*(const uint8_t*)p;
    }
  }
};

// encode_responses_many(status, limit, remaining, reset, err,
//                       offsets: sequence[int], now_ms: int = -1)
//                       -> (list[bytes(GetRateLimitsResp)], list[int])
// One dispatch's answers in one call: the five response columns of a
// coalesced chunk as the engine hands them over (ResponseColumns: any
// integer width, widened here) and E+1 ascending row offsets; entry k is
// rows offsets[k] .. offsets[k+1]. For every entry the bytes are what
// encode_responses writes for that slice of the columns with the error
// strings of its non-zero `err` codes (set_error_strings), and the second
// list counts the entry's OVER_LIMIT rows. The whole assembly runs with
// the GIL released.
static PyObject* encode_responses_many(PyObject*, PyObject* args) {
  PyObject *so, *lo, *ro, *to, *eo, *offs;
  long long now_ms = -1;
  if (!PyArg_ParseTuple(args, "OOOOOO|L", &so, &lo, &ro, &to, &eo, &offs,
                        &now_ms))
    return nullptr;
  IntCol st, li, re, rt, er;
  if (!st.open(so, "status") || !li.open(lo, "limit") ||
      !re.open(ro, "remaining") || !rt.open(to, "reset_time") ||
      !er.open(eo, "err"))
    return nullptr;
  Py_ssize_t n = st.size();
  if (li.size() != n || re.size() != n || rt.size() != n || er.size() != n) {
    PyErr_SetString(PyExc_ValueError, "response columns differ in length");
    return nullptr;
  }
  PyObject* seq = PySequence_Fast(offs, "offsets: a sequence of int expected");
  if (!seq) return nullptr;
  std::vector<Py_ssize_t> bounds((size_t)PySequence_Fast_GET_SIZE(seq));
  for (size_t k = 0; k < bounds.size(); k++) {
    bounds[k] = PyNumber_AsSsize_t(
        PySequence_Fast_GET_ITEM(seq, (Py_ssize_t)k), PyExc_OverflowError);
    if ((bounds[k] == -1 && PyErr_Occurred()) || bounds[k] < 0 ||
        bounds[k] > n || (k && bounds[k] < bounds[k - 1])) {
      if (!PyErr_Occurred())
        PyErr_SetString(PyExc_ValueError,
                        "offsets must ascend within the columns");
      Py_DECREF(seq);
      return nullptr;
    }
  }
  Py_DECREF(seq);
  size_t entries = bounds.empty() ? 0 : bounds.size() - 1;
  auto strings = error_strings;  // this call's table (see above)

  std::string out;  // every entry's bytes, one after another
  std::vector<size_t> ends(entries);
  std::vector<Py_ssize_t> over(entries, 0);
  bool unknown_code = false;
  Py_BEGIN_ALLOW_THREADS;
  if (entries) out.reserve((size_t)(bounds.back() - bounds[0]) * 24);
  std::string item;
  for (size_t k = 0; k < entries; k++) {
    for (Py_ssize_t i = bounds[k]; i < bounds[k + 1]; i++) {
      int64_t code = er.at(i), status = st.at(i);
      const std::string* msg = nullptr;
      if (code) {
        if (!strings || code < 0 || (size_t)code >= strings->size()) {
          unknown_code = true;
          continue;
        }
        msg = &(*strings)[(size_t)code];
      }
      over[k] += status == 1;
      put_response(out, item, status, li.at(i), re.at(i), rt.at(i), msg,
                   now_ms);
    }
    ends[k] = out.size();
  }
  Py_END_ALLOW_THREADS;
  if (unknown_code) {
    PyErr_SetString(PyExc_ValueError,
                    "err column holds a code with no error string");
    return nullptr;
  }
  PyObject* bodies = PyList_New((Py_ssize_t)entries);
  PyObject* counts = PyList_New((Py_ssize_t)entries);
  if (!bodies || !counts) { Py_XDECREF(bodies); Py_XDECREF(counts); return nullptr; }
  size_t start = 0;
  for (size_t k = 0; k < entries; k++) {
    PyObject* b = PyBytes_FromStringAndSize(out.data() + start,
                                            (Py_ssize_t)(ends[k] - start));
    PyObject* c = PyLong_FromSsize_t(over[k]);
    if (!b || !c) {
      Py_XDECREF(b); Py_XDECREF(c); Py_DECREF(bodies); Py_DECREF(counts);
      return nullptr;
    }
    PyList_SET_ITEM(bodies, (Py_ssize_t)k, b);
    PyList_SET_ITEM(counts, (Py_ssize_t)k, c);
    start = ends[k];
  }
  return Py_BuildValue("(NN)", bodies, counts);
}

// ------------------------------------------------------ fused chunk staging

// One part's (5, n) int32 lane block behind the buffer protocol, any
// strides (a selection of a parsed batch's rows is not contiguous). Opened
// and released with the GIL held; read without it.
struct LaneBlock {
  Py_buffer view;
  bool held = false;
  ~LaneBlock() { if (held) PyBuffer_Release(&view); }

  bool open(PyObject* obj) {
    if (PyObject_GetBuffer(obj, &view, PyBUF_STRIDES | PyBUF_FORMAT) < 0)
      return false;
    held = true;
    const char* f = view.format ? view.format : "B";
    if (*f == '@' || *f == '=' || *f == '<') f++;
    if (view.ndim != 2 || view.shape[0] != WIRE_LANES || view.itemsize != 4 ||
        !*f || !strchr("il", *f) || f[1]) {
      PyErr_SetString(PyExc_TypeError, "lanes: a (5, n) int32 block expected");
      return false;
    }
    return true;
  }
  Py_ssize_t cols() const { return view.shape[1]; }
  inline int32_t at(int lane, Py_ssize_t j) const {
    int32_t v;
    memcpy(&v, (const char*)view.buf + lane * view.strides[0] +
                   j * view.strides[1], 4);
    return v;
  }
  void copy_lane(int lane, int32_t* dst) const {
    const char* p = (const char*)view.buf + lane * view.strides[0];
    if (view.strides[1] == 4) {
      memcpy(dst, p, (size_t)cols() * 4);
      return;
    }
    for (Py_ssize_t j = 0; j < cols(); j++, p += view.strides[1])
      memcpy(dst + j, p, 4);
  }
};

struct ChunkPart {
  LaneBlock lanes;
  IntCol fp, err, created;
};

// One pass behind the grid: `rows` answer it; the aggregate's `members`
// share row g's answer group after group (`starts`, `counts`). `block` is
// its (5, pad+1) ingress image, empty when the lanes cannot carry it.
struct LaterPass {
  std::vector<int64_t> rows, members, starts, counts;
  std::vector<int32_t> block;
  bool aggregate = false, in_lanes = false;
  int64_t pad = 0;
  int math = -1;
};

struct ChunkStage {
  // in
  int64_t n = 0, now = 0, tol = 0, pad = 0, max_exact = 0, pad_floor = 0;
  bool keep_copies = false;
  // out
  std::vector<int32_t> grid;
  std::vector<int8_t> err;
  std::vector<int64_t> act_fp;
  std::vector<uint8_t> first;
  std::vector<LaterPass> passes;
  int64_t clamped = 0, later = 0;
  int math = 0;
  bool casc = false;
};

// ops/wire.grid_math_mode over the n data columns of a (5, w) block, as an
// index into ops/wire.MATH_MODES: token, mixed, gcra, int
static int block_math_mode(const int32_t* b, int64_t w, int64_t n) {
  bool leaky = false, any = false, act_gcra = true;
  int64_t act = 0;
  for (int64_t j = 0; j < n; j++) {
    uint32_t algo = ((uint32_t)b[3 * w + j] >> WIRE_DUR_BITS) & 7;
    leaky |= algo == 1;
    any |= algo != 0;
    if (b[j] || b[w + j]) {
      act++;
      act_gcra &= algo == 2;
    }
  }
  if (leaky) return 1;
  if (!any) return 0;
  return act && act_gcra ? 2 : 3;
}

// The staging itself; touches no Python object. False: the chunk cannot
// fuse (the cases ops/engine._assemble_wire_parts names).
static bool stage_chunk(const ChunkPart* parts, size_t k, ChunkStage& s) {
  const int64_t n = s.n, w = s.pad + 1;
  std::vector<int64_t> fp(n), clipped(n);
  std::vector<uint8_t> active(n);
  s.err.resize(n);
  s.grid.assign((size_t)(WIRE_LANES * w), 0);
  int32_t* grid = s.grid.data();
  const int64_t lo = s.now - s.tol, hi = s.now + s.tol;
  int64_t off = 0, first_active = -1;
  for (size_t p = 0; p < k; p++) {
    const ChunkPart& part = parts[p];
    const int64_t m = part.lanes.cols();
    for (int l = 0; l < WIRE_LANES; l++)
      part.lanes.copy_lane(l, grid + l * w + off);
    for (int64_t j = 0; j < m; j++) {
      const int64_t i = off + j, code = part.err.at(j);
      fp[i] = part.fp.at(j);
      s.err[i] = (int8_t)code;
      active[i] = code == 0;
      // an unset stamp is the ingress instant; a client's is held within
      // the tolerance (ops/batch.pack_columns), and counted where it was not
      const int64_t created = part.created.at(j);
      const int64_t stamped = created == 0 ? s.now : created;
      clipped[i] = std::min(std::max(stamped, lo), hi);
      s.clamped += clipped[i] != stamped;
      if (active[i]) {
        if (first_active < 0) first_active = i;
        s.act_fp.push_back(fp[i]);
      }
    }
    off += m;
  }
  if (first_active < 0) return false;  // all-error chunk

  // ops/plan.occurrence_rank without its sort: a row's rank is the number of
  // rows before it that carry its fingerprint (what its place in a stable
  // sort's run of that fingerprint is), counted in an open-addressed table;
  // an error row is counted and is no copy. Where the program folds a key's
  // copies itself (`keep_copies`) every copy is a row of the grid: rank 0.
  std::vector<int64_t> rank(n, 0);
  int64_t top = 0;
  if (!s.keep_copies) {
    size_t cap = 16;
    while (cap < 2 * (size_t)n) cap *= 2;
    std::vector<int64_t> seen_fp(cap);
    std::vector<int32_t> seen(cap, 0);
    for (int64_t i = 0; i < n; i++) {
      size_t at = (size_t)(((uint64_t)fp[i] * 0x9E3779B97F4A7C15ULL) >> 20) & (cap - 1);
      while (seen[at] && seen_fp[at] != fp[i]) at = (at + 1) & (cap - 1);
      seen_fp[at] = fp[i];
      const int64_t run = seen[at]++;
      if (run && active[i]) {
        rank[i] = run;
        s.later++;
        top = std::max(top, run);
      }
    }
  }
  if (s.later && s.max_exact < 2) return false;

  // the first active row's stamp is the base; lane 4 takes each active
  // row's delta from it, which a row of the grid has to fit
  const int64_t base = clipped[first_active];
  std::vector<uint8_t> fits(n);
  bool later_fit = true;
  s.first.resize(n);
  for (int64_t i = 0; i < n; i++) {
    const int64_t d = clipped[i] - base;
    fits[i] = d >= -WIRE_DELTA_BIAS && d < WIRE_DELTA_BIAS;
    s.first[i] = active[i] && !rank[i];
    if (s.first[i] && !fits[i]) return false;
    later_fit &= !rank[i] || fits[i];
    if (active[i])
      grid[4 * w + i] |= (int32_t)(((d + WIRE_DELTA_BIAS) & WIRE_DELTA_MASK)
                                   << WIRE_HITS_BITS);
    s.casc |= ((uint32_t)grid[3 * w + i] >> WIRE_LEVEL_SHIFT) != 0;
  }
  if (s.later && s.casc) return false;  // the in-trace fold is one pass
  // ops/wire.stamp_base
  grid[s.pad] = (int32_t)(uint32_t)((uint64_t)base & 0xFFFFFFFFu);
  grid[w + s.pad] = (int32_t)(uint32_t)((uint64_t)(base >> 32) & 0xFFFFFFFFu);

  if (s.later) {
    // ops/plan.split_rows: rank r below max_exact-1 is exact pass r, rows in
    // arrival order; every rank from there up is the aggregate's, key after
    // key, whose groups are the runs of equal fingerprint lanes
    const int64_t n_exact = std::min(top, s.max_exact - 2);
    const bool tail = top >= s.max_exact - 1;
    s.passes.resize((size_t)(n_exact + tail));
    for (int64_t i = 0; i < n; i++)
      if (rank[i] >= 1 && rank[i] <= n_exact)
        s.passes[rank[i] - 1].rows.push_back(i);
    if (tail) {
      LaterPass& t = s.passes.back();
      t.aggregate = true;
      // key after key as the stable sort by fingerprint leaves them
      std::vector<std::pair<int64_t, int64_t>> order;
      for (int64_t i = 0; i < n; i++)
        if (rank[i] >= s.max_exact - 1) order.push_back({fp[i], i});
      std::sort(order.begin(), order.end());
      for (const auto& row : order) t.members.push_back(row.second);
      const int64_t m = (int64_t)t.members.size();
      for (int64_t q = 0; q < m; q++) {
        const int64_t i = t.members[q], j = q ? t.members[q - 1] : 0;
        if (!q || grid[i] != grid[j] || grid[w + i] != grid[w + j])
          t.starts.push_back(q);
      }
      for (size_t g = 0; g < t.starts.size(); g++) {
        const int64_t end = g + 1 < t.starts.size() ? t.starts[g + 1] : m;
        t.counts.push_back(end - t.starts[g]);
        t.rows.push_back(t.members[end - 1]);  // the newest answers
      }
    }
    // ops/wire.gather_wire_block for each, under the grid's base column
    for (LaterPass& p : s.passes) {
      const std::vector<int64_t>& all = p.aggregate ? p.members : p.rows;
      const int64_t m = (int64_t)p.rows.size();
      for (p.pad = s.pad_floor; p.pad < m;) p.pad *= 2;
      p.in_lanes = later_fit || std::all_of(all.begin(), all.end(), [&](int64_t i) {
        return fits[i] != 0;
      });
      if (!p.in_lanes) continue;
      const int64_t bw = p.pad + 1;
      p.block.assign((size_t)(WIRE_LANES * bw), 0);
      for (int l = 0; l < WIRE_LANES; l++) {
        int32_t* dst = p.block.data() + l * bw;
        const int32_t* src = grid + l * w;
        for (int64_t j = 0; j < m; j++) dst[j] = src[p.rows[j]];
        dst[p.pad] = src[s.pad];
      }
      // the aggregate: a group's summed hits and its RESET bits OR-ed
      for (int64_t g = 0; p.aggregate && g < m; g++) {
        int64_t hits = 0;
        uint32_t reset = 0;
        for (int64_t q = p.starts[g]; q < p.starts[g] + p.counts[g]; q++) {
          const uint32_t l4 = (uint32_t)grid[4 * w + p.members[q]];
          hits += l4 & WIRE_HITS_MASK;
          reset |= l4 & WIRE_RESET_BIT;
        }
        if (hits > WIRE_HITS_MASK) {
          p.in_lanes = false;
          break;
        }
        uint32_t& cell = (uint32_t&)p.block[4 * bw + g];
        cell = (cell & ~(uint32_t)WIRE_HITS_MASK) | (uint32_t)hits | reset;
      }
      if (!p.in_lanes) p.block.clear();
    }
    // where all later copies name one algorithm, every pass selects the
    // mode the first does (ops/engine._later_blocks)
    bool same = true;
    int64_t algo0 = -1;
    for (int64_t i = 0; i < n; i++) {
      if (!rank[i]) continue;
      const int64_t algo = (uint32_t)grid[3 * w + i] >> WIRE_DUR_BITS;
      if (algo0 < 0) algo0 = algo;
      same &= algo == algo0;
    }
    int first_mode = -1;
    for (LaterPass& p : s.passes) {
      if (!p.in_lanes) continue;
      p.math = same && first_mode >= 0
                   ? first_mode
                   : block_math_mode(p.block.data(), p.pad + 1, p.pad);
      if (first_mode < 0) first_mode = p.math;
    }
    // the grid is pass 0: a later copy has no lane in it
    for (int64_t i = 0; i < n; i++)
      if (rank[i])
        for (int l = 0; l < WIRE_LANES; l++) grid[l * w + i] = 0;
  }
  s.math = block_math_mode(grid, w, n);
  return true;
}

template <typename T>
static PyObject* bytes_of(const std::vector<T>& v) {
  return PyBytes_FromStringAndSize((const char*)v.data(),
                                   (Py_ssize_t)(v.size() * sizeof(T)));
}

// stage_wire_chunk(parts: sequence[(lanes, fp, err, created_at)], now: int,
//                  tolerance: int, pad: int, max_exact: int, pad_floor: int,
//                  keep_copies: bool = False)
//   -> None | (grid, err, act_fp, first, clamped, math, cascade, later,
//              passes)
// The host staging of one fused chunk (ops/engine._stage_chunk_numpy and
// its _later_blocks stay as the NumPy twin the tests hold this to, byte
// for byte): the parts' (5, n_i) int32 lane
// blocks and their fp / err / created_at columns become the (5, pad+1)
// int32 pass-0 grid (later copies of a key zeroed, deltas and base
// stamped), a bytearray copy of `err` (the finish half writes to it), the
// active fingerprints, the (n,) bool mask of rows with a lane in the grid,
// the clamped-stamp count, the grid's math mode (an index into
// ops/wire.MATH_MODES), the cascade flag, the number of later copies, and
// for each pass behind the grid (rows, block | None, pad, math, members |
// None, starts | None, counts | None): int64 row indices, the (5, pad+1)
// block ready to put — None where the lanes cannot carry the pass (a stamp
// beyond the delta budget, summed hits past the lane) and the caller packs
// that pass alone as columns — and the aggregate's fan-out. None: the chunk
// cannot fuse. Later passes pad to pad_floor doubled until the rows fit.
// Holds no state, and runs with the GIL released from the first row to the
// last.
static PyObject* stage_wire_chunk(PyObject*, PyObject* args) {
  PyObject* parts_o;
  ChunkStage s;
  long long now, tol, pad, max_exact, pad_floor;
  int keep_copies = 0;
  if (!PyArg_ParseTuple(args, "OLLLLL|p", &parts_o, &now, &tol, &pad,
                        &max_exact, &pad_floor, &keep_copies))
    return nullptr;
  s.now = now; s.tol = tol; s.pad = pad;
  s.keep_copies = keep_copies != 0;
  s.max_exact = max_exact; s.pad_floor = pad_floor;
  PyObject* seq = PySequence_Fast(parts_o, "parts: a sequence expected");
  if (!seq) return nullptr;
  const size_t k = (size_t)PySequence_Fast_GET_SIZE(seq);
  std::unique_ptr<ChunkPart[]> parts(new ChunkPart[k]);
  bool ok = true;
  for (size_t p = 0; ok && p < k; p++) {
    PyObject* part = PySequence_Fast_GET_ITEM(seq, (Py_ssize_t)p);
    PyObject *lanes, *fp, *err, *created;
    ok = PyArg_ParseTuple(part, "OOOO", &lanes, &fp, &err, &created) &&
         parts[p].lanes.open(lanes) && parts[p].fp.open(fp, "fp") &&
         parts[p].err.open(err, "err") &&
         parts[p].created.open(created, "created_at");
    if (!ok) break;
    const Py_ssize_t m = parts[p].lanes.cols();
    if (parts[p].fp.size() != m || parts[p].err.size() != m ||
        parts[p].created.size() != m) {
      PyErr_SetString(PyExc_ValueError, "a part's columns differ in length");
      ok = false;
    }
    s.n += m;
  }
  Py_DECREF(seq);
  if (ok && (s.n > s.pad || s.pad_floor < 1)) {
    PyErr_SetString(PyExc_ValueError, "pad below the chunk's rows");
    ok = false;
  }
  if (!ok) return nullptr;

  bool fused;
  Py_BEGIN_ALLOW_THREADS;
  fused = stage_chunk(parts.get(), k, s);
  Py_END_ALLOW_THREADS;
  if (!fused) Py_RETURN_NONE;

  PyObject* passes = PyList_New((Py_ssize_t)s.passes.size());
  if (!passes) return nullptr;
  for (size_t i = 0; i < s.passes.size(); i++) {
    const LaterPass& p = s.passes[i];
    PyObject* block = p.in_lanes ? bytes_of(p.block) : Py_NewRef(Py_None);
    PyObject* item =
        p.aggregate
            ? Py_BuildValue("(NNLiNNN)", bytes_of(p.rows), block,
                            (long long)p.pad, p.math, bytes_of(p.members),
                            bytes_of(p.starts), bytes_of(p.counts))
            : Py_BuildValue("(NNLiOOO)", bytes_of(p.rows), block,
                            (long long)p.pad, p.math, Py_None, Py_None,
                            Py_None);
    if (!item) {
      Py_DECREF(passes);
      return nullptr;
    }
    PyList_SET_ITEM(passes, (Py_ssize_t)i, item);
  }
  return Py_BuildValue(
      "(NNNNLiOLN)", bytes_of(s.grid),
      PyByteArray_FromStringAndSize((const char*)s.err.data(),
                                    (Py_ssize_t)s.err.size()),
      bytes_of(s.act_fp), bytes_of(s.first), (long long)s.clamped, s.math,
      s.casc ? Py_True : Py_False, (long long)s.later, passes);
}

// ---------------------------------------------------------------------
// The shadow tier's fingerprint index (gubernator_tpu/tier/shadow.py,
// `_FpIndex`): one open-addressing table in two flat arrays that the caller
// owns, `keys` (int64: the fingerprint, 0 = never used, -1 = removed) and
// `vals` (int32: the row id), 2^bits slots, linear probing from a
// multiplicative hash. Both entry points take the arrays through the buffer
// protocol (contiguous, this host's byte order), hold no state and run with
// the GIL released: the engine thread probes and fills the index inside the
// miss path while the loop, door and fetch threads are busy, and the NumPy
// twin's few hundred small array calls each queue for the GIL again.
static const uint64_t FP_INDEX_MULT = 0x9E3779B97F4A7C15ull;

struct FlatBuf {
  Py_buffer view;
  bool held = false;
  ~FlatBuf() { if (held) PyBuffer_Release(&view); }
  bool open(PyObject* obj, Py_ssize_t itemsize, bool writable, const char* what) {
    if (PyObject_GetBuffer(obj, &view,
                           writable ? PyBUF_CONTIG : PyBUF_CONTIG_RO) < 0)
      return false;
    held = true;
    if (view.itemsize != itemsize) {
      PyErr_Format(PyExc_TypeError, "%s: items of %zd bytes expected", what,
                   itemsize);
      return false;
    }
    return true;
  }
  Py_ssize_t size() const { return view.len / view.itemsize; }
};

// fp_index_find(keys, bits, fps, out): out[i] = the slot that holds fps[i],
// or -1 where the table does not hold it
static PyObject* fp_index_find(PyObject*, PyObject* args) {
  PyObject *keys_o, *fps_o, *out_o;
  int bits;
  if (!PyArg_ParseTuple(args, "OiOO", &keys_o, &bits, &fps_o, &out_o))
    return nullptr;
  FlatBuf keys, fps, out;
  if (!keys.open(keys_o, 8, false, "keys") || !fps.open(fps_o, 8, false, "fps") ||
      !out.open(out_o, 8, true, "out"))
    return nullptr;
  if (bits < 1 || bits > 40 || keys.size() != ((Py_ssize_t)1 << bits) ||
      out.size() != fps.size()) {
    PyErr_SetString(PyExc_ValueError, "fp_index_find: sizes disagree");
    return nullptr;
  }
  const int64_t* k = (const int64_t*)keys.view.buf;
  const int64_t* f = (const int64_t*)fps.view.buf;
  int64_t* o = (int64_t*)out.view.buf;
  const Py_ssize_t n = fps.size();
  const uint64_t mask = ((uint64_t)1 << bits) - 1;
  Py_BEGIN_ALLOW_THREADS;
  for (Py_ssize_t i = 0; i < n; i++) {
    uint64_t pos = ((uint64_t)f[i] * FP_INDEX_MULT) >> (64 - bits);
    int64_t at = -1;
    for (;; pos = (pos + 1) & mask) {
      if (k[pos] == f[i]) { at = (int64_t)pos; break; }
      if (k[pos] == 0) break;
    }
    o[i] = at;
  }
  Py_END_ALLOW_THREADS;
  Py_RETURN_NONE;
}

// fp_index_place(keys, vals, bits, fps, ids) -> removed marks reused: puts
// fingerprints the table does not hold (each once) on the first free slot
// of their chain. The caller keeps the table under 0.7 full.
static PyObject* fp_index_place(PyObject*, PyObject* args) {
  PyObject *keys_o, *vals_o, *fps_o, *ids_o;
  int bits;
  if (!PyArg_ParseTuple(args, "OOiOO", &keys_o, &vals_o, &bits, &fps_o, &ids_o))
    return nullptr;
  FlatBuf keys, vals, fps, ids;
  if (!keys.open(keys_o, 8, true, "keys") || !vals.open(vals_o, 4, true, "vals") ||
      !fps.open(fps_o, 8, false, "fps") || !ids.open(ids_o, 4, false, "ids"))
    return nullptr;
  if (bits < 1 || bits > 40 || keys.size() != ((Py_ssize_t)1 << bits) ||
      vals.size() != keys.size() || ids.size() != fps.size()) {
    PyErr_SetString(PyExc_ValueError, "fp_index_place: sizes disagree");
    return nullptr;
  }
  int64_t* k = (int64_t*)keys.view.buf;
  int32_t* v = (int32_t*)vals.view.buf;
  const int64_t* f = (const int64_t*)fps.view.buf;
  const int32_t* id = (const int32_t*)ids.view.buf;
  const Py_ssize_t n = fps.size();
  const uint64_t mask = ((uint64_t)1 << bits) - 1;
  long long reused = 0;
  Py_BEGIN_ALLOW_THREADS;
  for (Py_ssize_t i = 0; i < n; i++) {
    uint64_t pos = ((uint64_t)f[i] * FP_INDEX_MULT) >> (64 - bits);
    while (k[pos] > 0) pos = (pos + 1) & mask;
    reused += k[pos] == -1;
    k[pos] = f[i];
    v[pos] = id[i];
  }
  Py_END_ALLOW_THREADS;
  return PyLong_FromLongLong(reused);
}

// ------------------------------------------------------ fused chunk finish

// Flag bits of an egress row's 4th cell (ops/kernel2.FLAG_*) and the reset
// cell's "reset_time == 0" (ops/wire.RESET_SENTINEL)
static const int32_t FLAG_STATUS = 1, FLAG_HIT = 2, FLAG_DROPPED = 4,
                     FLAG_UNPROCESSED = 8, FLAG_MEMBER = 16;
static const int32_t RESET_SENTINEL = INT32_MIN;

// One pass's fetched compact egress behind the buffer protocol, int32, any
// strides: an (R, 4) block whose first rows answer the pass, with the
// kernel's stats row at R-2 and the base row at R-1 (a tiered block keeps
// its evictee sidecar between the rows and those two), or a mesh's
// (D, c+2, 4) grid of D such blocks, pass row i at [i / c][i % c]. Opened
// and released with the GIL held; read without it.
struct EgressBlock {
  Py_buffer view;
  bool held = false;
  ~EgressBlock() { if (held) PyBuffer_Release(&view); }

  // 1: opened; 0: a block this call does not read (not int32: a full-width
  // pass), no error set; -1: not a block at all, error set
  int open(PyObject* obj) {
    if (PyObject_GetBuffer(obj, &view, PyBUF_STRIDES | PyBUF_FORMAT) < 0)
      return -1;
    held = true;
    const char* f = view.format ? view.format : "B";
    if (*f == '@' || *f == '=' || *f == '<') f++;
    if ((view.ndim != 2 && view.ndim != 3) || view.shape[view.ndim - 1] != 4 ||
        view.shape[view.ndim - 2] < 2) {
      PyErr_SetString(PyExc_TypeError,
                      "block: (rows + 2, 4) or (devices, rows + 2, 4) expected");
      return -1;
    }
    return view.itemsize == 4 && *f && strchr("il", *f) && !f[1];
  }
  bool grid() const { return view.ndim == 3; }
  Py_ssize_t devices() const { return grid() ? view.shape[0] : 1; }
  Py_ssize_t height() const { return view.shape[view.ndim - 2]; }
  inline int32_t at(Py_ssize_t d, Py_ssize_t r, int k) const {
    const char* p = (const char*)view.buf + r * view.strides[view.ndim - 2] +
                    k * view.strides[view.ndim - 1];
    if (grid()) p += d * view.strides[0];
    int32_t v;
    memcpy(&v, p, 4);
    return v;
  }
};

// One pass of the dispatch: its block, its `n` real rows and their place in
// request order: `rows` (row i answers request row rows[i]) or, for the
// aggregate, `members` group after group, `counts` to a group (row g
// answers every member of group g). A grid also names its `base` (a block
// carries its own) and may name the chunk's `lanes`, whose zeroed rows the
// kernel never saw.
struct FinishPass {
  EgressBlock block;
  IntCol rows, members, counts;
  LaneBlock lanes;
  bool aggregate = false, has_lanes = false;
  int64_t n = 0, base = 0;
};

struct ChunkFinish {
  // the dispatch's response columns, `width` request rows each
  int32_t* status = nullptr;
  int64_t *limit = nullptr, *remaining = nullptr, *reset = nullptr;
  int64_t width = 0;
  // cache_hits, cache_misses, over_limit, evicted_unexpired, summed
  int64_t stats[4] = {0, 0, 0, 0};
  int64_t later = 0, aggregate = 0, overflow = 0;
  std::vector<int64_t> dropped;  // (pass, row of the pass, its flags)
  const char* bad = nullptr;
};

// ops/wire.unpack_wire_out and the scatter of ops/engine's finish loop for
// one pass (a grid: parallel/sharded._unroute and finish_staged's
// accounting); touches no Python object.
static void finish_pass(const FinishPass& p, int64_t pi, ChunkFinish& f) {
  const EgressBlock& b = p.block;
  // c: a grid's rows a device; either way the stats row, the base row after
  const int64_t n = p.n, c = b.height() - 2;
  int64_t base = p.base;
  if (b.grid()) {
    // per-row accounting below; the one stat no row carries is summed over
    // the devices' stats rows
    for (Py_ssize_t d = 0; d < b.devices(); d++) f.stats[3] += b.at(d, c, 3);
  } else {
    for (int k = 0; k < 4; k++) f.stats[k] += b.at(0, c, k);
    base = (int64_t)((uint64_t)(uint32_t)b.at(0, c + 1, 1) |
                     ((uint64_t)(int64_t)b.at(0, c + 1, 2) << 32));
  }
  const int64_t answered = p.aggregate ? p.members.size() : n;
  if (pi) {
    f.later += answered;
    if (p.aggregate) f.aggregate += answered;
  }
  int64_t start = 0;  // the aggregate: where group i's members begin
  for (int64_t i = 0; i < n; i++) {
    const Py_ssize_t d = b.grid() ? i / c : 0, r = b.grid() ? i % c : i;
    const int64_t limit = b.at(d, r, 0), remaining = b.at(d, r, 1);
    const int32_t delta = b.at(d, r, 2), flags = b.at(d, r, 3);
    const int64_t reset =
        delta == RESET_SENTINEL ? 0 : (int64_t)((uint64_t)base + (uint64_t)(int64_t)delta);
    const int32_t status = flags & FLAG_STATUS;
    if (flags & FLAG_DROPPED) {
      f.dropped.push_back(pi);
      f.dropped.push_back(i);
      f.dropped.push_back(flags);
    }
    if (b.grid()) {
      const bool unproc = flags & FLAG_UNPROCESSED, member = flags & FLAG_MEMBER;
      f.overflow += unproc && !member;
      if (!unproc && !member &&
          (!p.has_lanes || p.lanes.at(0, i) || p.lanes.at(1, i))) {
        f.stats[(flags & FLAG_HIT) ? 0 : 1]++;
        f.stats[2] += status == 1;
      }
    }
    const int64_t fan = p.aggregate ? p.counts.at(i) : 1;
    if (fan < 0 || start + fan > answered) {
      f.bad = "an aggregate's counts pass its members";
      return;
    }
    for (int64_t q = start; q < start + fan; q++) {
      const int64_t at = p.aggregate ? p.members.at(q) : p.rows.at(i);
      if (at < 0 || at >= f.width) {
        f.bad = "a pass names a row outside the columns";
        return;
      }
      f.status[at] = status;
      f.limit[at] = limit;
      f.remaining[at] = remaining;
      f.reset[at] = reset;
    }
    if (p.aggregate) start += fan;
  }
  if (p.aggregate && start != answered)
    f.bad = "an aggregate's counts fall short of its members";
}

// finish_wire_chunk(passes: sequence[(block, n, rows | None, members | None,
//                                     counts | None, base | None,
//                                     lanes | None)],
//                   status, limit, remaining, reset_time)
//   -> None | (cache_hits, cache_misses, over_limit, evicted_unexpired,
//              later_rows, aggregate_rows, overflow_rows, dropped)
// The finish half of one fused dispatch (ops/engine._finish_numpy stays as
// the NumPy twin the tests hold this to, byte for byte), the way out of
// what stage_wire_chunk staged: every pass's fetched compact egress block
// decoded (limit and remaining widened, the base-relative reset made
// absolute, RESET_SENTINEL -> 0, the status bit split from the flags) and
// written in place into the dispatch's response columns (status int32; limit,
// remaining, reset_time int64; contiguous, writable) at the rows the pass
// answers, each member of an aggregate from its group's row. Returns the
// passes' stats rows summed, the rows answered behind the first pass and of
// those the aggregate's members, and `dropped`: int64 triples (pass, row of
// the pass, the row's flags) of the rows whose FLAG_DROPPED is set, in pass
// and row order, for the caller's retry (their columns hold the dropped
// answer until it patches them). A (D, c+2, 4) grid is a mesh's pass: its
// rows are counted one by one (hit, miss, over) unless unprocessed, a
// member of an in-trace aggregate or a zeroed lane of `lanes`,
// evicted_unexpired is summed over its D stats rows, `base` is named, and
// overflow_rows counts its unprocessed rows that are no members. None: a
// block is not int32 (a full-width pass) and the caller's Python finish
// runs. Holds no state, and runs with the GIL released from the first row
// to the last.
static PyObject* finish_wire_chunk(PyObject*, PyObject* args) {
  PyObject *passes_o, *so, *lo, *ro, *to;
  if (!PyArg_ParseTuple(args, "OOOOO", &passes_o, &so, &lo, &ro, &to))
    return nullptr;
  FlatBuf st, li, re, rt;
  if (!st.open(so, 4, true, "status") || !li.open(lo, 8, true, "limit") ||
      !re.open(ro, 8, true, "remaining") || !rt.open(to, 8, true, "reset_time"))
    return nullptr;
  ChunkFinish f;
  f.width = st.size();
  if (li.size() != f.width || re.size() != f.width || rt.size() != f.width) {
    PyErr_SetString(PyExc_ValueError, "response columns differ in length");
    return nullptr;
  }
  f.status = (int32_t*)st.view.buf;
  f.limit = (int64_t*)li.view.buf;
  f.remaining = (int64_t*)re.view.buf;
  f.reset = (int64_t*)rt.view.buf;
  PyObject* seq = PySequence_Fast(passes_o, "passes: a sequence expected");
  if (!seq) return nullptr;
  const size_t k = (size_t)PySequence_Fast_GET_SIZE(seq);
  std::unique_ptr<FinishPass[]> passes(new FinishPass[k]);
  bool ok = true, readable = true;
  for (size_t i = 0; i < k; i++) {
    FinishPass& p = passes[i];
    PyObject *block, *rows, *members, *counts, *base, *lanes;
    long long n;
    ok = PyArg_ParseTuple(PySequence_Fast_GET_ITEM(seq, (Py_ssize_t)i),
                          "OLOOOOO", &block, &n, &rows, &members, &counts,
                          &base, &lanes);
    if (!ok) break;
    const int opened = p.block.open(block);
    if (opened <= 0) {
      ok = opened == 0;
      readable = false;
      break;
    }
    p.n = n;
    p.aggregate = members != Py_None;
    p.has_lanes = lanes != Py_None;
    ok = p.aggregate ? p.members.open(members, "members") &&
                           p.counts.open(counts, "member_counts")
                     : p.rows.open(rows, "rows");
    if (ok && p.has_lanes) ok = p.lanes.open(lanes);
    if (ok && base != Py_None) {
      p.base = PyLong_AsLongLong(base);
      ok = !(p.base == -1 && PyErr_Occurred());
    }
    if (!ok) break;
    const EgressBlock& b = p.block;
    if (n < 0 || n > (b.grid() ? b.devices() * (b.height() - 2) : b.height() - 2) ||
        (p.aggregate ? p.counts.size() : p.rows.size()) != n ||
        (p.has_lanes && p.lanes.cols() < n) || (b.grid() && base == Py_None)) {
      PyErr_SetString(PyExc_ValueError,
                      "a pass's rows, block, lanes and base disagree");
      ok = false;
      break;
    }
  }
  Py_DECREF(seq);
  if (!ok) return nullptr;
  if (!readable) Py_RETURN_NONE;

  Py_BEGIN_ALLOW_THREADS;
  for (size_t i = 0; i < k && !f.bad; i++) finish_pass(passes[i], (int64_t)i, f);
  Py_END_ALLOW_THREADS;
  if (f.bad) {
    PyErr_SetString(PyExc_ValueError, f.bad);
    return nullptr;
  }
  return Py_BuildValue("(LLLLLLLN)", (long long)f.stats[0], (long long)f.stats[1],
                       (long long)f.stats[2], (long long)f.stats[3],
                       (long long)f.later, (long long)f.aggregate,
                       (long long)f.overflow, bytes_of(f.dropped));
}

// fingerprint64(data: bytes) -> int — parity check hook for tests
static PyObject* fingerprint64(PyObject*, PyObject* args) {
  Py_buffer buf;
  if (!PyArg_ParseTuple(args, "y*", &buf)) return nullptr;
  uint64_t h = xxh64((const uint8_t*)buf.buf, (size_t)buf.len, FP_SEED) & MASK63;
  PyBuffer_Release(&buf);
  return PyLong_FromUnsignedLongLong(h ? h : 1);
}

static PyObject* fnv1a32_py(PyObject*, PyObject* args) {
  Py_buffer buf;
  if (!PyArg_ParseTuple(args, "y*", &buf)) return nullptr;
  uint32_t h = fnv1a_32((const uint8_t*)buf.buf, (size_t)buf.len);
  PyBuffer_Release(&buf);
  return PyLong_FromUnsignedLong(h);
}

static PyMethodDef methods[] = {
    {"parse_get_rate_limits", parse_get_rate_limits, METH_VARARGS,
     "GetRateLimitsReq wire bytes -> column buffers"},
    {"encode_responses", encode_responses, METH_VARARGS,
     "response columns -> GetRateLimitsResp wire bytes"},
    {"encode_responses_many", encode_responses_many, METH_VARARGS,
     "a chunk's response columns + entry offsets -> each entry's "
     "GetRateLimitsResp wire bytes and OVER_LIMIT count"},
    {"stage_wire_chunk", stage_wire_chunk, METH_VARARGS,
     "a fused chunk's lane blocks and columns -> its grid and every pass "
     "behind it, staged"},
    {"finish_wire_chunk", finish_wire_chunk, METH_VARARGS,
     "a fused dispatch's fetched egress blocks -> its response columns, "
     "written in place, and the rows to retry"},
    {"set_error_strings", set_error_strings, METH_O,
     "the error code -> wire string table encode_responses_many uses"},
    {"fp_index_find", fp_index_find, METH_VARARGS,
     "the shadow tier's index: the slots that hold a batch of fingerprints"},
    {"fp_index_place", fp_index_place, METH_VARARGS,
     "the shadow tier's index: a batch of absent fingerprints put in"},
    {"fingerprint64", fingerprint64, METH_VARARGS, "seeded 63-bit XXH64"},
    {"fnv1a32", fnv1a32_py, METH_VARARGS, "fnv1a 32-bit"},
    {nullptr, nullptr, 0, nullptr}};

static struct PyModuleDef mod = {PyModuleDef_HEAD_INIT, "_guberhost",
                                 "native host ingress/egress", -1, methods};

PyMODINIT_FUNC PyInit__guberhost(void) { return PyModule_Create(&mod); }
