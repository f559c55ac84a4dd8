// Gear CDC boundary scan, CPU-native, with runtime ISA dispatch.
//
// The accelerator formulation (makisu_tpu/ops/gear.py) computes
//   h_i = sum_{m=0}^{31} G[b_{i-m}] << m   (mod 2^32)
// as five doubling steps over whole vectors — the right shape for the
// VPU. On a CPU host the same function is one scalar recurrence
//   h = (h << 1) + G[b]                    (mod 2^32)
// (terms older than 32 bytes leave via the shift). The recurrence is a
// loop-carried dependency (~5 cycles/byte), so faster routes break the
// chain: the window is exactly 32 bytes — h_i depends on bytes i-31..i
// and nothing older — so any position can be recomputed from a 32-byte
// warmup, and stripes/lanes are invisible in the output.
//
// Three gear routes, resolved once per process (overridable at runtime
// for tests/bench via gear_set_gear_isa):
//   scalar  — one sequential chain (the reference everything must match)
//   striped — 4 interleaved scalar chains (~4x IPC; the r05 route)
//   avx2    — 8 u32 lanes in gear_simd.cpp (per-file -mavx2)
// and three SHA-256 batch routes (gear_set_sha_isa):
//   scalar  — FIPS 180-4 fallback (sha256_common.h)
//   evp     — OpenSSL via dlopen, one hoisted ctx, per-slice fallback
//   shani   — 3-way multi-buffer SHA-NI scheduler (sha_ni.cpp)
// Every route emits bit-identical cut positions and byte-identical
// digests by construction — ISA is a throughput knob and must NEVER
// enter cache identity.
//
// The table is passed in from Python (gear.gear_table()) so there is
// exactly one site that defines the boundary function's constants.

#include <atomic>
#include <cstddef>
#include <cstdint>
#include <cstring>

#if defined(__x86_64__) || defined(__i386__)
#include <cpuid.h>
#endif

#include "gear_isa.h"
#include "sha256_common.h"

namespace {

constexpr size_t kWindow = 32;   // bytes of history in a 32-bit h
constexpr size_t kStripes = 4;   // striped-route chain count
// Below this, striping/vectorizing costs more than it saves; the
// sequential chain handles it on every route (output is identical).
constexpr size_t kStripedMin = kStripes * 4 * kWindow;
constexpr size_t kSimdMin = 8 * 4 * kWindow;

enum GearIsa { kGearScalar = 0, kGearStriped = 1, kGearAvx2 = 2 };
enum ShaIsa { kShaScalar = 0, kShaEvp = 1, kShaNi = 2 };

std::atomic<int> g_gear_isa{-1};  // -1 = resolve on first use
std::atomic<int> g_sha_isa{-1};

bool cpu_avx2() {
#if defined(__x86_64__) || defined(__i386__)
  return __builtin_cpu_supports("avx2") != 0;
#else
  return false;
#endif
}

bool cpu_sha_ni() {
#if defined(__x86_64__) || defined(__i386__)
  // GCC's __builtin_cpu_supports has no "sha" probe; read CPUID
  // directly: leaf 7.0 EBX bit 29 (SHA), leaf 1 ECX bit 19 (SSE4.1).
  unsigned int a, b, c, d;
  if (!__get_cpuid_count(7, 0, &a, &b, &c, &d)) return false;
  if ((b & (1u << 29)) == 0) return false;
  if (!__get_cpuid(1, &a, &b, &c, &d)) return false;
  return (c & (1u << 19)) != 0;
#else
  return false;
#endif
}

int resolve_gear_auto() {
  return (cpu_avx2() && makisu_native::gear_avx2_compiled()) ? kGearAvx2
                                                             : kGearStriped;
}

int resolve_sha_auto() {
  if (cpu_sha_ni() && makisu_native::sha_ni_compiled()) return kShaNi;
  return makisu_native::evp().ok ? kShaEvp : kShaScalar;
}

int gear_isa() {
  int v = g_gear_isa.load(std::memory_order_relaxed);
  if (v < 0) {
    v = resolve_gear_auto();
    g_gear_isa.store(v, std::memory_order_relaxed);
  }
  return v;
}

int sha_isa() {
  int v = g_sha_isa.load(std::memory_order_relaxed);
  if (v < 0) {
    v = resolve_sha_auto();
    g_sha_isa.store(v, std::memory_order_relaxed);
  }
  return v;
}

inline void scan_range(const uint8_t *data, size_t begin, size_t end,
                       const uint32_t *table, uint32_t mask,
                       uint8_t *out) {
  // Emit out[i] for i in [begin, end); warm h up over the (up to) 32
  // bytes before begin so the stripe seam is invisible.
  uint32_t h = 0;
  size_t warm = begin >= kWindow ? begin - kWindow : 0;
  for (size_t i = warm; i < begin; ++i) h = (h << 1) + table[data[i]];
  for (size_t i = begin; i < end; ++i) {
    h = (h << 1) + table[data[i]];
    out[i] = (h & mask) == 0 ? 1 : 0;
  }
}

// Position emitter over `nslots` ascending disjoint output ranges:
// slot t owns stream range [sbounds[t], sbounds[t+1]) and appends into
// out_pos[t*cap ..]. Each chain emits ascending positions within its
// own slot range, so the concatenated slots stay sorted.
struct SlotSink {
  uint32_t *out_pos;
  size_t cap;
  uint32_t *counts;
  const size_t *sbounds;
  size_t nslots;
  size_t cur;

  bool emit(size_t pos) {
    while (cur + 1 < nslots && pos >= sbounds[cur + 1]) ++cur;
    if (counts[cur] == cap) return false;
    out_pos[cur * cap + counts[cur]++] = static_cast<uint32_t>(pos);
    return true;
  }
};

int scan_pos_seq(const uint8_t *data, size_t n, const uint32_t *table,
                 uint32_t mask, uint32_t *out_pos, size_t cap,
                 uint32_t *counts, const size_t *sbounds, size_t nslots) {
  SlotSink sink{out_pos, cap, counts, sbounds, nslots, 0};
  uint32_t h = 0;
  for (size_t i = 0; i < n; ++i) {
    h = (h << 1) + table[data[i]];
    if ((h & mask) == 0 && !sink.emit(i)) return 1;
  }
  return 0;
}

int scan_pos_striped(const uint8_t *data, size_t n, const uint32_t *table,
                     uint32_t mask, uint32_t *out_pos, size_t cap,
                     uint32_t *counts, const size_t *sbounds,
                     size_t nslots) {
  // Requires stripe boundaries to coincide with slot boundaries
  // (nslots % kStripes == 0) so chains own disjoint slot ranges.
  size_t bounds[kStripes + 1];
  for (size_t s = 0; s <= kStripes; ++s) bounds[s] = n * s / kStripes;
  uint32_t h[kStripes];
  SlotSink sink[kStripes];
  for (size_t s = 0; s < kStripes; ++s) {
    h[s] = 0;
    sink[s] = SlotSink{out_pos, cap, counts, sbounds, nslots,
                       s * nslots / kStripes};
    size_t begin = bounds[s];
    size_t warm = begin >= kWindow ? begin - kWindow : 0;
    for (size_t i = warm; i < begin; ++i)
      h[s] = (h[s] << 1) + table[data[i]];
  }
  size_t len = n;  // shortest stripe
  for (size_t s = 0; s < kStripes; ++s)
    if (bounds[s + 1] - bounds[s] < len) len = bounds[s + 1] - bounds[s];
  // Interleaved: four independent dependency chains in one loop body.
  // The hit branch is ~1-in-2^avg_bits, so it predicts perfectly.
  for (size_t k = 0; k < len; ++k) {
    for (size_t s = 0; s < kStripes; ++s) {
      size_t i = bounds[s] + k;
      h[s] = (h[s] << 1) + table[data[i]];
      if ((h[s] & mask) == 0 && !sink[s].emit(i)) return 1;
    }
  }
  // Stripe tails (uneven division): finish sequentially per stripe.
  for (size_t s = 0; s < kStripes; ++s) {
    for (size_t i = bounds[s] + len; i < bounds[s + 1]; ++i) {
      h[s] = (h[s] << 1) + table[data[i]];
      if ((h[s] & mask) == 0 && !sink[s].emit(i)) return 1;
    }
  }
  return 0;
}

}  // namespace

extern "C" {

// Bumped whenever the dispatch surface changes; native.py refuses a
// stale library loudly instead of silently serving the old routes.
int gear_abi_version() { return 2; }

// ---- ISA introspection / override (tests, bench, the env knob) ------

int gear_isa_supported(const char *name) {
  if (!name) return 0;
  if (std::strcmp(name, "scalar") == 0 ||
      std::strcmp(name, "striped") == 0)
    return 1;
  if (std::strcmp(name, "avx2") == 0)
    return cpu_avx2() && makisu_native::gear_avx2_compiled();
  if (std::strcmp(name, "evp") == 0) return makisu_native::evp().ok;
  if (std::strcmp(name, "shani") == 0)
    return cpu_sha_ni() && makisu_native::sha_ni_compiled();
  return 0;
}

// Returns 0 when the route is now active, -1 for an unknown name, -2
// when this host/build cannot run it (route unchanged).
int gear_set_gear_isa(const char *name) {
  if (!name) return -1;
  if (std::strcmp(name, "auto") == 0) {
    g_gear_isa.store(resolve_gear_auto(), std::memory_order_relaxed);
    return 0;
  }
  if (std::strcmp(name, "scalar") == 0) {
    g_gear_isa.store(kGearScalar, std::memory_order_relaxed);
    return 0;
  }
  if (std::strcmp(name, "striped") == 0) {
    g_gear_isa.store(kGearStriped, std::memory_order_relaxed);
    return 0;
  }
  if (std::strcmp(name, "avx2") == 0) {
    if (!gear_isa_supported("avx2")) return -2;
    g_gear_isa.store(kGearAvx2, std::memory_order_relaxed);
    return 0;
  }
  return -1;
}

int gear_set_sha_isa(const char *name) {
  if (!name) return -1;
  if (std::strcmp(name, "auto") == 0) {
    g_sha_isa.store(resolve_sha_auto(), std::memory_order_relaxed);
    return 0;
  }
  if (std::strcmp(name, "scalar") == 0) {
    g_sha_isa.store(kShaScalar, std::memory_order_relaxed);
    return 0;
  }
  if (std::strcmp(name, "evp") == 0) {
    if (!makisu_native::evp().ok) return -2;
    g_sha_isa.store(kShaEvp, std::memory_order_relaxed);
    return 0;
  }
  if (std::strcmp(name, "shani") == 0) {
    if (!gear_isa_supported("shani")) return -2;
    g_sha_isa.store(kShaNi, std::memory_order_relaxed);
    return 0;
  }
  return -1;
}

const char *gear_gear_isa(void) {
  switch (gear_isa()) {
    case kGearAvx2: return "avx2";
    case kGearStriped: return "striped";
    default: return "scalar";
  }
}

const char *gear_sha_isa(void) {
  switch (sha_isa()) {
    case kShaNi: return "shani";
    case kShaEvp: return "evp";
    default: return "scalar";
  }
}

// ---- scans -----------------------------------------------------------

// Candidate POSITIONS (not bits): one pass, no bit-array write + host
// rescan. Positions are emitted into `nslots` ascending disjoint
// slots — slot t appends into out_pos[t*slot_cap ..] and counts[t]
// says how many — and the caller concatenates (slots cover ascending
// disjoint ranges, so the result is sorted). Returns 0 on success, 1
// when any slot overflows its capacity (adversarial data denser than
// the mask's expected rate) — the caller falls back to the bit scan.
int gear_scan_pos2(const uint8_t *data, size_t n, const uint32_t *table,
                   uint32_t mask, uint32_t *out_pos, size_t slot_cap,
                   uint32_t *counts, size_t nslots) {
  if (nslots == 0 || nslots > 64) return 1;
  std::memset(counts, 0, nslots * sizeof(uint32_t));
  size_t sbounds[65];
  for (size_t t = 0; t <= nslots; ++t) sbounds[t] = n * t / nslots;
  int isa = gear_isa();
  // The AVX2 kernel emits lane L into slot L directly, which needs
  // exactly 8 slots; the striped route needs slot boundaries aligned
  // to its 4 stripe boundaries. Anything else runs sequential —
  // positions are identical either way.
  if (isa == kGearAvx2 && nslots == 8 && n >= kSimdMin)
    return makisu_native::gear_scan_pos_avx2(data, n, table, mask,
                                             out_pos, slot_cap, counts,
                                             nslots);
  if (isa >= kGearStriped && nslots % kStripes == 0 && n >= kStripedMin)
    return scan_pos_striped(data, n, table, mask, out_pos, slot_cap,
                            counts, sbounds, nslots);
  return scan_pos_seq(data, n, table, mask, out_pos, slot_cap, counts,
                      sbounds, nslots);
}

// out[i] = 1 iff position i is a boundary candidate ((h_i & mask) == 0).
// The caller hands the same halo-prefixed buffer the device path scans
// and slices off the halo positions itself.
void gear_scan(const uint8_t *data, size_t n, const uint32_t *table,
               uint32_t mask, uint8_t *out) {
  int isa = gear_isa();
  if (isa == kGearAvx2 && n >= kSimdMin) {
    makisu_native::gear_scan_avx2(data, n, table, mask, out);
    return;
  }
  if (isa < kGearStriped || n < kStripedMin) {
    scan_range(data, 0, n, table, mask, out);
    return;
  }
  // Four stripes, interleaved in one loop: independent chains the core
  // can overlap. Stripe s covers [bounds[s], bounds[s+1]).
  size_t bounds[kStripes + 1];
  for (size_t s = 0; s <= kStripes; ++s) bounds[s] = n * s / kStripes;
  uint32_t h[kStripes];
  for (size_t s = 0; s < kStripes; ++s) {
    h[s] = 0;
    size_t begin = bounds[s];
    size_t warm = begin >= kWindow ? begin - kWindow : 0;
    for (size_t i = warm; i < begin; ++i)
      h[s] = (h[s] << 1) + table[data[i]];
  }
  size_t len = n;  // shortest stripe
  for (size_t s = 0; s < kStripes; ++s)
    if (bounds[s + 1] - bounds[s] < len) len = bounds[s + 1] - bounds[s];
  for (size_t k = 0; k < len; ++k) {
    for (size_t s = 0; s < kStripes; ++s) {
      size_t i = bounds[s] + k;
      h[s] = (h[s] << 1) + table[data[i]];
      out[i] = (h[s] & mask) == 0 ? 1 : 0;
    }
  }
  // Stripe tails (uneven division): finish sequentially per stripe.
  for (size_t s = 0; s < kStripes; ++s) {
    size_t done = bounds[s] + len;
    if (done < bounds[s + 1])
      scan_range(data, done, bounds[s + 1], table, mask, out);
  }
}

// Batch SHA-256 over `count` slices of one contiguous buffer:
// digest i covers data[offsets[i] .. offsets[i]+lengths[i]) and lands
// at out[32*i]. One call per ~hundreds-of-KiB batch is what makes the
// commit pipeline's pooled chunk hashing scale: the caller (ctypes)
// releases the GIL for the WHOLE batch, so worker threads spend
// microseconds — not the whole batch — contending with the producer.
// Route: SHA-NI 3-way multi-buffer when the CPU has it, else OpenSSL
// EVP with one hoisted ctx (per-slice scalar fallback on EVP failure),
// else the scalar implementation — all byte-identical to hashlib.
// Returns 0 on success.
int gear_sha256_batch(const uint8_t *data, const uint64_t *offsets,
                      const uint64_t *lengths, size_t count,
                      uint8_t *out) {
  int isa = sha_isa();
  if (isa == kShaNi &&
      makisu_native::sha256_ni_batch(data, offsets, lengths, count,
                                     out) == 0)
    return 0;
  if (isa >= kShaEvp && makisu_native::evp().ok) {
    makisu_native::sha256_batch_evp_or_scalar(data, offsets, lengths,
                                              count, out);
    return 0;
  }
  for (size_t i = 0; i < count; ++i) {
    makisu_native::Sha256 d;
    d.update(data + offsets[i], lengths[i]);
    d.final(out + 32 * i);
  }
  return 0;
}

}  // extern "C"
