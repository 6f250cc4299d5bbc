// HighwayHash — portable C++ implementation (algorithm is public domain).
//
// Role in this framework: HighwayHash-256 is the default per-shard bitrot
// checksum (reference behavior: cmd/bitrot.go:30-58 — algorithm
// "highwayhash256S" keyed with the magic pi-digest key). The hot GET/PUT
// paths checksum every shard block; this library provides the CPU engine
// (single-shot + batched) that the Python layer binds via ctypes. A
// device-side batched implementation is the TPU counterpart.
//
// Layout notes: state is 4 u64 lanes per register (v0, v1, mul0, mul1).
// The batched entry points hash many equal-length shards in one call to
// amortize FFI overhead (one call per encode step, not per shard).

#include <cstdint>
#include <cstring>
#include <cstddef>

#if defined(__x86_64__) || defined(_M_X64)
#include <immintrin.h>
#define HH_X86 1
#endif

namespace {

struct HHState {
  uint64_t v0[4];
  uint64_t v1[4];
  uint64_t mul0[4];
  uint64_t mul1[4];
};

static const uint64_t kMul0[4] = {
    0xdbe6d5d5fe4cce2full, 0xa4093822299f31d0ull,
    0x13198a2e03707344ull, 0x243f6a8885a308d3ull};
static const uint64_t kMul1[4] = {
    0x3bd39e10cb0ef593ull, 0xc0acf169b5f18a8cull,
    0xbe5466cf34e90c6cull, 0x452821e638d01377ull};

inline uint64_t Rot32(uint64_t x) { return (x >> 32) | (x << 32); }

inline void Reset(const uint64_t key[4], HHState* s) {
  for (int i = 0; i < 4; ++i) {
    s->mul0[i] = kMul0[i];
    s->mul1[i] = kMul1[i];
    s->v0[i] = kMul0[i] ^ key[i];
    s->v1[i] = kMul1[i] ^ Rot32(key[i]);
  }
}

inline void ZipperMergeAndAdd(const uint64_t v1, const uint64_t v0,
                              uint64_t* add1, uint64_t* add0) {
  *add0 += (((v0 & 0xff000000ull) | (v1 & 0xff00000000ull)) >> 24) |
           (((v0 & 0xff0000000000ull) | (v1 & 0xff000000000000ull)) >> 16) |
           (v0 & 0xff0000ull) | ((v0 & 0xff00ull) << 32) |
           ((v1 & 0xff00000000000000ull) >> 8) | (v0 << 56);
  *add1 += (((v1 & 0xff000000ull) | (v0 & 0xff00000000ull)) >> 24) |
           (v1 & 0xff0000ull) | ((v1 & 0xff0000000000ull) >> 16) |
           ((v1 & 0xff00ull) << 24) | ((v0 & 0xff000000000000ull) >> 8) |
           ((v1 & 0xffull) << 48) | (v0 & 0xff00000000000000ull);
}

inline void Update(const uint64_t lanes[4], HHState* s) {
  for (int i = 0; i < 4; ++i) {
    s->v1[i] += s->mul0[i] + lanes[i];
    s->mul0[i] ^= (s->v1[i] & 0xffffffff) * (s->v0[i] >> 32);
    s->v0[i] += s->mul1[i];
    s->mul1[i] ^= (s->v0[i] & 0xffffffff) * (s->v1[i] >> 32);
  }
  ZipperMergeAndAdd(s->v1[1], s->v1[0], &s->v0[1], &s->v0[0]);
  ZipperMergeAndAdd(s->v1[3], s->v1[2], &s->v0[3], &s->v0[2]);
  ZipperMergeAndAdd(s->v0[1], s->v0[0], &s->v1[1], &s->v1[0]);
  ZipperMergeAndAdd(s->v0[3], s->v0[2], &s->v1[3], &s->v1[2]);
}

inline uint64_t Read64LE(const uint8_t* p) {
  uint64_t v;
  std::memcpy(&v, p, 8);
  return v;  // little-endian hosts only (x86/ARM LE)
}

inline void UpdatePacket(const uint8_t* packet, HHState* s) {
  uint64_t lanes[4] = {Read64LE(packet), Read64LE(packet + 8),
                       Read64LE(packet + 16), Read64LE(packet + 24)};
  Update(lanes, s);
}

inline void Rotate32By(uint64_t count, uint64_t lanes[4]) {
  for (int i = 0; i < 4; ++i) {
    uint32_t half0 = static_cast<uint32_t>(lanes[i] & 0xffffffff);
    uint32_t half1 = static_cast<uint32_t>(lanes[i] >> 32);
    lanes[i] = (count == 0)
                   ? lanes[i]
                   : ((static_cast<uint64_t>((half0 << count) |
                                             (half0 >> (32 - count)))) |
                      (static_cast<uint64_t>((half1 << count) |
                                             (half1 >> (32 - count)))
                       << 32));
  }
}

inline void UpdateRemainder(const uint8_t* bytes, const size_t size_mod32,
                            HHState* s) {
  const size_t size_mod4 = size_mod32 & 3;
  const uint8_t* remainder = bytes + (size_mod32 & ~3ull);
  uint8_t packet[32] = {0};
  for (int i = 0; i < 4; ++i) {
    s->v0[i] += (static_cast<uint64_t>(size_mod32) << 32) + size_mod32;
  }
  Rotate32By(size_mod32, s->v1);
  std::memcpy(packet, bytes, size_mod32 & ~3ull);
  if (size_mod32 & 16) {
    for (int i = 0; i < 4; ++i) {
      // signed offset: reaches back into the already-copied bytes when
      // size_mod4 < 4 (the upstream algorithm's unsigned wraparound,
      // made explicit)
      packet[28 + i] =
          remainder[static_cast<ptrdiff_t>(size_mod4) + i - 4];
    }
  } else if (size_mod4) {
    packet[16 + 0] = remainder[0];
    packet[16 + 1] = remainder[size_mod4 >> 1];
    packet[16 + 2] = remainder[size_mod4 - 1];
  }
  UpdatePacket(packet, s);
}

inline void Permute(const uint64_t v[4], uint64_t permuted[4]) {
  permuted[0] = Rot32(v[2]);
  permuted[1] = Rot32(v[3]);
  permuted[2] = Rot32(v[0]);
  permuted[3] = Rot32(v[1]);
}

inline void PermuteAndUpdate(HHState* s) {
  uint64_t permuted[4];
  Permute(s->v0, permuted);
  Update(permuted, s);
}

inline void ModularReduction(uint64_t a3_unmasked, uint64_t a2, uint64_t a1,
                             uint64_t a0, uint64_t* m1, uint64_t* m0) {
  const uint64_t a3 = a3_unmasked & 0x3FFFFFFFFFFFFFFFull;
  *m1 = a1 ^ ((a3 << 1) | (a2 >> 63)) ^ ((a3 << 2) | (a2 >> 62));
  *m0 = a0 ^ (a2 << 1) ^ (a2 << 2);
}

#ifdef HH_X86
// AVX2 packet loop: the whole HHState maps onto four __m256i (one per
// 4 x u64 register file). The zipper-merge byte permutation — derived
// from the scalar mask/shift cascade above — is a single in-lane
// per-128-bit pshufb:
//   dst byte j of each half <- src byte {3,12,2,5,14,1,15,0,
//                                        11,4,10,13,9,6,8,7}[j]
// and Update's cross-half pairing (lanes {1,0} and {3,2}) is exactly
// the two 128-bit lanes of a 256-bit register.
__attribute__((target("avx2"))) inline __m256i ZipperMergeV(__m256i v) {
  const __m256i mask = _mm256_setr_epi8(
      3, 12, 2, 5, 14, 1, 15, 0, 11, 4, 10, 13, 9, 6, 8, 7,
      3, 12, 2, 5, 14, 1, 15, 0, 11, 4, 10, 13, 9, 6, 8, 7);
  return _mm256_shuffle_epi8(v, mask);
}

__attribute__((target("avx2")))
void ProcessPacketsAVX2(const uint8_t* data, size_t n_packets, HHState* s) {
  __m256i v0 = _mm256_loadu_si256(reinterpret_cast<const __m256i*>(s->v0));
  __m256i v1 = _mm256_loadu_si256(reinterpret_cast<const __m256i*>(s->v1));
  __m256i mul0 =
      _mm256_loadu_si256(reinterpret_cast<const __m256i*>(s->mul0));
  __m256i mul1 =
      _mm256_loadu_si256(reinterpret_cast<const __m256i*>(s->mul1));
  for (size_t i = 0; i < n_packets; ++i) {
    const __m256i lanes = _mm256_loadu_si256(
        reinterpret_cast<const __m256i*>(data + i * 32));
    // v1 += mul0 + lanes
    v1 = _mm256_add_epi64(v1, _mm256_add_epi64(mul0, lanes));
    // mul0 ^= (v1 & 0xffffffff) * (v0 >> 32)   [mul_epu32 = lo32*lo32]
    mul0 = _mm256_xor_si256(
        mul0, _mm256_mul_epu32(v1, _mm256_srli_epi64(v0, 32)));
    // v0 += mul1
    v0 = _mm256_add_epi64(v0, mul1);
    // mul1 ^= (v0 & 0xffffffff) * (v1 >> 32)
    mul1 = _mm256_xor_si256(
        mul1, _mm256_mul_epu32(v0, _mm256_srli_epi64(v1, 32)));
    // v0 += zipper(v1); then v1 += zipper(updated v0)
    v0 = _mm256_add_epi64(v0, ZipperMergeV(v1));
    v1 = _mm256_add_epi64(v1, ZipperMergeV(v0));
  }
  _mm256_storeu_si256(reinterpret_cast<__m256i*>(s->v0), v0);
  _mm256_storeu_si256(reinterpret_cast<__m256i*>(s->v1), v1);
  _mm256_storeu_si256(reinterpret_cast<__m256i*>(s->mul0), mul0);
  _mm256_storeu_si256(reinterpret_cast<__m256i*>(s->mul1), mul1);
}

bool DetectAVX2() {
  return __builtin_cpu_supports("avx2");
}
const bool g_has_avx2 = DetectAVX2();
#endif  // HH_X86

inline void ProcessPackets(const uint8_t* data, size_t n_packets,
                           HHState* s) {
#ifdef HH_X86
  if (g_has_avx2) {
    ProcessPacketsAVX2(data, n_packets, s);
    return;
  }
#endif
  for (size_t i = 0; i < n_packets; ++i) UpdatePacket(data + i * 32, s);
}

inline void ProcessAll(const uint8_t* data, size_t size, HHState* s) {
  const size_t n_packets = size / 32;
  ProcessPackets(data, n_packets, s);
  if ((size & 31) != 0)
    UpdateRemainder(data + n_packets * 32, size & 31, s);
}

inline uint64_t Finalize64(HHState* s) {
  for (int i = 0; i < 4; ++i) PermuteAndUpdate(s);
  return s->v0[0] + s->v1[0] + s->mul0[0] + s->mul1[0];
}

inline void Finalize256(HHState* s, uint64_t hash[4]) {
  for (int i = 0; i < 10; ++i) PermuteAndUpdate(s);
  ModularReduction(s->v1[1] + s->mul1[1], s->v1[0] + s->mul1[0],
                   s->v0[1] + s->mul0[1], s->v0[0] + s->mul0[0],
                   &hash[1], &hash[0]);
  ModularReduction(s->v1[3] + s->mul1[3], s->v1[2] + s->mul1[2],
                   s->v0[3] + s->mul0[3], s->v0[2] + s->mul0[2],
                   &hash[3], &hash[2]);
}

}  // namespace

extern "C" {

// 64-bit variant, used for self-test against published vectors.
uint64_t hh64(const uint8_t* key32, const uint8_t* data, size_t size) {
  uint64_t key[4];
  std::memcpy(key, key32, 32);
  HHState s;
  Reset(key, &s);
  ProcessAll(data, size, &s);
  return Finalize64(&s);
}

// 256-bit digest of one buffer (32-byte output, little-endian u64 x4).
void hh256(const uint8_t* key32, const uint8_t* data, size_t size,
           uint8_t* out32) {
  uint64_t key[4];
  std::memcpy(key, key32, 32);
  HHState s;
  Reset(key, &s);
  ProcessAll(data, size, &s);
  uint64_t hash[4];
  Finalize256(&s, hash);
  std::memcpy(out32, hash, 32);
}

// Batched 256-bit digests: n buffers of equal length `size`, laid out
// contiguously with stride `stride` bytes; out = n x 32 bytes.
// One FFI call per erasure-encode step (n = shards).
void hh256_batch(const uint8_t* key32, const uint8_t* data, size_t n,
                 size_t size, size_t stride, uint8_t* out) {
  for (size_t j = 0; j < n; ++j) {
    hh256(key32, data + j * stride, size, out + j * 32);
  }
}

// Digests of n buffers of equal length `size`, each at its own address
// (rows[j]); out = n x 32 bytes. One FFI call hashes shards where a
// reader left them, with no copy into a contiguous (n, size) array.
void hh256_rows(const uint8_t* key32, const uint8_t* const* rows, size_t n,
                size_t size, uint8_t* out) {
  for (size_t j = 0; j < n; ++j) {
    hh256(key32, rows[j], size, out + j * 32);
  }
}

// Streaming interface: caller owns an opaque 128-byte state blob.
void hh_init(const uint8_t* key32, uint8_t* state128) {
  uint64_t key[4];
  std::memcpy(key, key32, 32);
  HHState s;
  Reset(key, &s);
  std::memcpy(state128, &s, sizeof(HHState));
}

// Append full 32-byte packets only (size % 32 == 0).
void hh_update_packets(uint8_t* state128, const uint8_t* data, size_t size) {
  HHState s;
  std::memcpy(&s, state128, sizeof(HHState));
  ProcessPackets(data, size / 32, &s);
  std::memcpy(state128, &s, sizeof(HHState));
}

// 1 when the AVX2 packet loop is in use (tests/bench introspection).
int hh_has_avx2() {
#ifdef HH_X86
  return g_has_avx2 ? 1 : 0;
#else
  return 0;
#endif
}

// Final call: append remainder (< 32 bytes) and emit 256-bit digest.
void hh_final256(uint8_t* state128, const uint8_t* remainder, size_t rem_size,
                 uint8_t* out32) {
  HHState s;
  std::memcpy(&s, state128, sizeof(HHState));
  if (rem_size) UpdateRemainder(remainder, rem_size & 31, &s);
  uint64_t hash[4];
  Finalize256(&s, hash);
  std::memcpy(out32, hash, 32);
}

}  // extern "C"
