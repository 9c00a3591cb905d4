// fd_sha256.h — SHA-256 for the native lanes that hash on the host:
// the shredder's merkle tree (fd_shred.cpp) and the replay intake's PoH
// check (fd_verify.cpp).  One copy, included by both; static, so each
// .so carries its own (no cross-library linkage, no ODR hazard).
//
// FIPS 180-4, constants generated from the frac(cbrt/sqrt(prime))
// definition (cross-checked against hashlib).  On x86-64 the block
// compression is SHA-NI where the CPU has it (runtime-dispatched; the
// scalar path is the portable ground truth and the differential tests
// cover both).

#pragma once

#include <cstdint>
#include <cstring>

#if defined(__x86_64__)
#include <cpuid.h>
#include <immintrin.h>
#endif

namespace fdsha {

typedef uint8_t u8;
typedef uint32_t u32;
typedef uint64_t u64;

static const uint32_t K256[64] = {
    0x428a2f98u, 0x71374491u, 0xb5c0fbcfu, 0xe9b5dba5u,
    0x3956c25bu, 0x59f111f1u, 0x923f82a4u, 0xab1c5ed5u,
    0xd807aa98u, 0x12835b01u, 0x243185beu, 0x550c7dc3u,
    0x72be5d74u, 0x80deb1feu, 0x9bdc06a7u, 0xc19bf174u,
    0xe49b69c1u, 0xefbe4786u, 0x0fc19dc6u, 0x240ca1ccu,
    0x2de92c6fu, 0x4a7484aau, 0x5cb0a9dcu, 0x76f988dau,
    0x983e5152u, 0xa831c66du, 0xb00327c8u, 0xbf597fc7u,
    0xc6e00bf3u, 0xd5a79147u, 0x06ca6351u, 0x14292967u,
    0x27b70a85u, 0x2e1b2138u, 0x4d2c6dfcu, 0x53380d13u,
    0x650a7354u, 0x766a0abbu, 0x81c2c92eu, 0x92722c85u,
    0xa2bfe8a1u, 0xa81a664bu, 0xc24b8b70u, 0xc76c51a3u,
    0xd192e819u, 0xd6990624u, 0xf40e3585u, 0x106aa070u,
    0x19a4c116u, 0x1e376c08u, 0x2748774cu, 0x34b0bcb5u,
    0x391c0cb3u, 0x4ed8aa4au, 0x5b9cca4fu, 0x682e6ff3u,
    0x748f82eeu, 0x78a5636fu, 0x84c87814u, 0x8cc70208u,
    0x90befffau, 0xa4506cebu, 0xbef9a3f7u, 0xc67178f2u,
};
static const uint32_t H256[8] = {
    0x6a09e667u, 0xbb67ae85u, 0x3c6ef372u, 0xa54ff53au,
    0x510e527fu, 0x9b05688cu, 0x1f83d9abu, 0x5be0cd19u,
};

static inline u32 rotr32(u32 x, int n) { return (x >> n) | (x << (32 - n)); }

#if defined(__x86_64__)
// SHA-NI block compression (runtime-dispatched; the scalar path below
// is the portable ground truth and the differential tests cover both).
// The merkle tree is the shredder's hash-heaviest loop — ~2 sha256
// invocations per shred — so the hardware rounds are worth the dispatch.
__attribute__((target("sha,sse4.1")))
static void sha256_blocks_ni(u32 state[8], const u8* data) {
  __m128i STATE0, STATE1, MSG, TMP, ABEF_SAVE, CDGH_SAVE;
  __m128i W[4];
  const __m128i MASK =
      _mm_set_epi64x(0x0c0d0e0f08090a0bULL, 0x0405060700010203ULL);
  TMP = _mm_loadu_si128((const __m128i*)&state[0]);
  STATE1 = _mm_loadu_si128((const __m128i*)&state[4]);
  TMP = _mm_shuffle_epi32(TMP, 0xB1);           // CDAB
  STATE1 = _mm_shuffle_epi32(STATE1, 0x1B);     // EFGH
  STATE0 = _mm_alignr_epi8(TMP, STATE1, 8);     // ABEF
  STATE1 = _mm_blend_epi16(STATE1, TMP, 0xF0);  // CDGH
  ABEF_SAVE = STATE0;
  CDGH_SAVE = STATE1;
  for (int i = 0; i < 16; i++) {
    int j = i & 3;
    if (i < 4) {
      W[j] = _mm_shuffle_epi8(
          _mm_loadu_si128((const __m128i*)(data + 16 * i)), MASK);
    } else {
      __m128i t = _mm_alignr_epi8(W[(j + 3) & 3], W[(j + 2) & 3], 4);
      W[j] = _mm_sha256msg1_epu32(W[j], W[(j + 1) & 3]);
      W[j] = _mm_add_epi32(W[j], t);
      W[j] = _mm_sha256msg2_epu32(W[j], W[(j + 3) & 3]);
    }
    MSG = _mm_add_epi32(
        W[j], _mm_set_epi32((int)K256[4 * i + 3], (int)K256[4 * i + 2],
                            (int)K256[4 * i + 1], (int)K256[4 * i]));
    STATE1 = _mm_sha256rnds2_epu32(STATE1, STATE0, MSG);
    MSG = _mm_shuffle_epi32(MSG, 0x0E);
    STATE0 = _mm_sha256rnds2_epu32(STATE0, STATE1, MSG);
  }
  STATE0 = _mm_add_epi32(STATE0, ABEF_SAVE);
  STATE1 = _mm_add_epi32(STATE1, CDGH_SAVE);
  TMP = _mm_shuffle_epi32(STATE0, 0x1B);        // FEBA
  STATE1 = _mm_shuffle_epi32(STATE1, 0xB1);     // DCHG
  STATE0 = _mm_blend_epi16(TMP, STATE1, 0xF0);  // DCBA
  STATE1 = _mm_alignr_epi8(STATE1, TMP, 8);     // HGFE
  _mm_storeu_si128((__m128i*)&state[0], STATE0);
  _mm_storeu_si128((__m128i*)&state[4], STATE1);
}

static bool have_shani_probe() {
  // CPUID.(EAX=7,ECX=0):EBX bit 29 (this gcc's __builtin_cpu_supports
  // has no "sha" token)
  unsigned a, b, c, d;
  if (!__get_cpuid_count(7, 0, &a, &b, &c, &d)) return false;
  return (b >> 29) & 1;
}

static bool have_shani() {
  static const bool ok = have_shani_probe();
  return ok;
}
#endif

struct Sha256 {
  u32 h[8];
  u8 buf[64];
  u64 len;
  Sha256() { reset(); }
  void reset() {
    std::memcpy(h, H256, sizeof(h));
    len = 0;
  }
  void block(const u8* p) {
#if defined(__x86_64__)
    if (have_shani()) {
      sha256_blocks_ni(h, p);
      return;
    }
#endif
    u32 w[64];
    for (int i = 0; i < 16; i++)
      w[i] = (u32)p[4 * i] << 24 | (u32)p[4 * i + 1] << 16 |
             (u32)p[4 * i + 2] << 8 | (u32)p[4 * i + 3];
    for (int i = 16; i < 64; i++) {
      u32 s0 = rotr32(w[i - 15], 7) ^ rotr32(w[i - 15], 18) ^ (w[i - 15] >> 3);
      u32 s1 = rotr32(w[i - 2], 17) ^ rotr32(w[i - 2], 19) ^ (w[i - 2] >> 10);
      w[i] = w[i - 16] + s0 + w[i - 7] + s1;
    }
    u32 a = h[0], b = h[1], c = h[2], d = h[3], e = h[4], f = h[5], g = h[6],
        hh = h[7];
    for (int i = 0; i < 64; i++) {
      u32 S1 = rotr32(e, 6) ^ rotr32(e, 11) ^ rotr32(e, 25);
      u32 ch = (e & f) ^ (~e & g);
      u32 t1 = hh + S1 + ch + K256[i] + w[i];
      u32 S0 = rotr32(a, 2) ^ rotr32(a, 13) ^ rotr32(a, 22);
      u32 maj = (a & b) ^ (a & c) ^ (b & c);
      u32 t2 = S0 + maj;
      hh = g; g = f; f = e; e = d + t1;
      d = c; c = b; b = a; a = t1 + t2;
    }
    h[0] += a; h[1] += b; h[2] += c; h[3] += d;
    h[4] += e; h[5] += f; h[6] += g; h[7] += hh;
  }
  void update(const u8* p, u64 n) {
    u64 have = len & 63;
    len += n;
    if (have) {
      u64 need = 64 - have;
      if (n < need) { std::memcpy(buf + have, p, n); return; }
      std::memcpy(buf + have, p, need);
      block(buf);
      p += need; n -= need;
    }
    while (n >= 64) { block(p); p += 64; n -= 64; }
    if (n) std::memcpy(buf, p, n);
  }
  void final(u8 out[32]) {
    u64 bits = len * 8;
    u8 pad = 0x80;
    update(&pad, 1);
    u8 z = 0;
    while ((len & 63) != 56) update(&z, 1);
    u8 lb[8];
    for (int i = 0; i < 8; i++) lb[i] = (u8)(bits >> (56 - 8 * i));
    update(lb, 8);
    for (int i = 0; i < 8; i++) {
      out[4 * i] = (u8)(h[i] >> 24); out[4 * i + 1] = (u8)(h[i] >> 16);
      out[4 * i + 2] = (u8)(h[i] >> 8); out[4 * i + 3] = (u8)h[i];
    }
  }
};

}  // namespace fdsha
