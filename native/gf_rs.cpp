// GF(2^8) Reed-Solomon matrix-multiply kernels for the host CPU.
//
// Role: (a) the CPU fallback / small-object path of the framework (the
// device pipeline wins only when batches amortize transfer+dispatch), and
// (b) the "SIMD reedsolomon" baseline bench.py compares the TPU path
// against (reference behavior: the codec library wrapped at the
// reference's cmd/erasure-coding.go:56 runs AVX2 table-lookup kernels).
//
// Two paths, runtime-dispatched:
//   * GFNI+AVX512BW: one vgf2p8affineqb per (input-shard x output-shard)
//     per 64 bytes — the 8x8 GF(2) bit-matrix form this framework also
//     uses on the MXU (ops/rs_pallas.py), in silicon.
//   * Portable: 4-bit split lookup tables (the classic SSSE3 formulation,
//     in scalar C so it runs anywhere; compilers autovectorize the XORs).
//
// The GF(2^8) field (poly 0x11D, generator 2) matches ops/gf256.py; the
// Python layer passes fully-built coding matrices, so this file contains
// no matrix algebra — only the byte-level matmul.

#include <cstdint>
#include <cstring>
#include <cstddef>
#include <vector>

#if defined(__x86_64__)
#include <immintrin.h>
#include <cpuid.h>
#endif

namespace {

// ---------------------------------------------------------------------------
// Field tables (built once at load; poly 0x11D, generator 2)
// ---------------------------------------------------------------------------

uint8_t g_mul[256][256];

struct TableInit {
  TableInit() {
    uint8_t exp_t[512];
    int log_t[256];
    int x = 1;
    for (int i = 0; i < 255; ++i) {
      exp_t[i] = static_cast<uint8_t>(x);
      log_t[x] = i;
      x <<= 1;
      if (x & 0x100) x ^= 0x11D;
    }
    for (int i = 255; i < 510; ++i) exp_t[i] = exp_t[i - 255];
    log_t[0] = 0;
    for (int a = 0; a < 256; ++a) {
      for (int b = 0; b < 256; ++b) {
        g_mul[a][b] = (a && b)
            ? exp_t[log_t[a] + log_t[b]]
            : 0;
      }
    }
  }
} g_table_init;

// 8x8 bit-matrix of multiply-by-c packed for GF2P8AFFINEQB: output-bit q's
// row lives in byte (7-q) of the qword; row bit p = bit q of c*(2^p).
uint64_t AffineQword(uint8_t c) {
  uint64_t qw = 0;
  for (int q = 0; q < 8; ++q) {
    uint8_t row = 0;
    for (int p = 0; p < 8; ++p) {
      uint8_t prod = g_mul[c][static_cast<uint8_t>(1u << p)];
      if ((prod >> q) & 1) row |= static_cast<uint8_t>(1u << p);
    }
    qw |= static_cast<uint64_t>(row) << (8 * (7 - q));
  }
  return qw;
}

// ---------------------------------------------------------------------------
// CPU feature detection
// ---------------------------------------------------------------------------

bool DetectGfniAvx512() {
#if defined(__x86_64__)
  unsigned eax, ebx, ecx, edx;
  if (!__get_cpuid_count(7, 0, &eax, &ebx, &ecx, &edx)) return false;
  const bool avx512f = ebx & (1u << 16);
  const bool avx512bw = ebx & (1u << 30);
  const bool gfni = ecx & (1u << 8);
  return avx512f && avx512bw && gfni;
#else
  return false;
#endif
}

const bool g_has_gfni = DetectGfniAvx512();

// ---------------------------------------------------------------------------
// GFNI/AVX512 path
// ---------------------------------------------------------------------------

#if defined(__x86_64__)
__attribute__((target("avx512f,avx512bw,gfni")))
void MatmulGfni(const uint8_t* matrix, size_t r, size_t k,
                const uint8_t* data, size_t stride_in,
                uint8_t* out, size_t stride_out, size_t len) {
  // Precompute affine qwords for the whole matrix (r*k tiny; heap so an
  // arbitrarily large recovery matrix can never overrun the stack).
  std::vector<uint64_t> aff(r * k);
  for (size_t j = 0; j < r; ++j)
    for (size_t i = 0; i < k; ++i)
      aff[j * k + i] = AffineQword(matrix[j * k + i]);

  size_t s = 0;
  for (; s + 64 <= len; s += 64) {
    for (size_t j = 0; j < r; ++j) {
      __m512i acc = _mm512_setzero_si512();
      for (size_t i = 0; i < k; ++i) {
        __m512i v = _mm512_loadu_si512(
            reinterpret_cast<const void*>(data + i * stride_in + s));
        __m512i a = _mm512_set1_epi64(static_cast<long long>(aff[j * k + i]));
        acc = _mm512_xor_si512(acc, _mm512_gf2p8affine_epi64_epi8(v, a, 0));
      }
      _mm512_storeu_si512(reinterpret_cast<void*>(out + j * stride_out + s),
                          acc);
    }
  }
  if (s < len) {
    // tail: bounce through a 64-byte scratch
    const size_t tail = len - s;
    for (size_t j = 0; j < r; ++j) {
      uint8_t accbuf[64];
      __m512i acc = _mm512_setzero_si512();
      for (size_t i = 0; i < k; ++i) {
        uint8_t buf[64] = {0};
        std::memcpy(buf, data + i * stride_in + s, tail);
        __m512i v = _mm512_loadu_si512(reinterpret_cast<const void*>(buf));
        __m512i a = _mm512_set1_epi64(static_cast<long long>(aff[j * k + i]));
        acc = _mm512_xor_si512(acc, _mm512_gf2p8affine_epi64_epi8(v, a, 0));
      }
      _mm512_storeu_si512(reinterpret_cast<void*>(accbuf), acc);
      std::memcpy(out + j * stride_out + s, accbuf, tail);
    }
  }
}
#endif  // __x86_64__

// ---------------------------------------------------------------------------
// Portable path: 4-bit split tables (low/high nibble), XOR-accumulate
// ---------------------------------------------------------------------------

void MatmulPortable(const uint8_t* matrix, size_t r, size_t k,
                    const uint8_t* data, size_t stride_in,
                    uint8_t* out, size_t stride_out, size_t len) {
  for (size_t j = 0; j < r; ++j) {
    uint8_t* dst = out + j * stride_out;
    std::memset(dst, 0, len);
    for (size_t i = 0; i < k; ++i) {
      const uint8_t c = matrix[j * k + i];
      if (c == 0) continue;
      const uint8_t* src = data + i * stride_in;
      // nibble tables for constant c
      uint8_t lo[16], hi[16];
      for (int t = 0; t < 16; ++t) {
        lo[t] = g_mul[c][t];
        hi[t] = g_mul[c][t << 4];
      }
      if (c == 1) {
        for (size_t s = 0; s < len; ++s) dst[s] ^= src[s];
      } else {
        for (size_t s = 0; s < len; ++s) {
          const uint8_t b = src[s];
          dst[s] ^= static_cast<uint8_t>(lo[b & 0xf] ^ hi[b >> 4]);
        }
      }
    }
  }
}

}  // namespace

extern "C" {

// out(r x len) = matrix(r x k) (x) data(k x len) over GF(2^8).
// data/out are row-major with explicit strides (numpy-compatible).
// force_path: 0 = auto, 1 = portable, 2 = gfni (for benchmarking).
void gf_matmul(const uint8_t* matrix, size_t r, size_t k,
               const uint8_t* data, size_t stride_in,
               uint8_t* out, size_t stride_out, size_t len,
               int force_path) {
#if defined(__x86_64__)
  const bool use_gfni =
      (force_path == 2) || (force_path == 0 && g_has_gfni);
  if (use_gfni && g_has_gfni) {
    MatmulGfni(matrix, r, k, data, stride_in, out, stride_out, len);
    return;
  }
#endif
  MatmulPortable(matrix, r, k, data, stride_in, out, stride_out, len);
}

int gf_has_gfni() { return g_has_gfni ? 1 : 0; }

// Gather n buffers of `size` bytes, each at its own address (rows[j]),
// into consecutive rows of out (n x size): a decode launch's survivor
// input assembled in ONE call, the interpreter lock released once.
void gather_rows(const uint8_t* const* rows, size_t n, size_t size,
                 uint8_t* out) {
  for (size_t j = 0; j < n; ++j) {
    std::memcpy(out + j * size, rows[j], size);
  }
}

uint8_t gf_mul_one(uint8_t a, uint8_t b) { return g_mul[a][b]; }

}  // extern "C"
