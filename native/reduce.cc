// The plain ring's accumulate for bfloat16 (torchft_tpu/process_group.py,
// _ring_pass): dst[i] = bf16(float(dst[i]) + float(src[i])), bit for bit
// what ml_dtypes' `dst += src` gives, but outside the interpreter (ctypes
// lets go of its lock for the call, ml_dtypes' loop holds it) and in
// vector registers.
//
// No intrinsics: the loop is plain integer and float arithmetic with
// selects, which gcc vectorises at -O3 (the Makefile gives this one file
// the flag; at -O2 gcc before 12 vectorises nothing). target_clones makes
// a second copy for AVX2 and picks one at load time from what the host's
// CPU reports, so a host without AVX2 runs the baseline copy.

#include "reduce.h"

#include <cstring>

namespace tft {

namespace {

inline uint32_t widen(uint16_t v) { return static_cast<uint32_t>(v) << 16; }

inline bool is_nan(uint32_t bits) { return (bits & 0x7fffffffu) > 0x7f800000u; }

}  // namespace

#if defined(__x86_64__) && defined(__GNUC__) && !defined(__clang__)
__attribute__((target_clones("avx2", "default")))
#endif
void bf16_add(uint16_t* dst, const uint16_t* src, size_t n) {
  for (size_t i = 0; i < n; ++i) {
    const uint32_t a = widen(dst[i]), b = widen(src[i]);
    float fa, fb;
    std::memcpy(&fa, &a, 4);
    std::memcpy(&fb, &b, 4);
    const float fs = fa + fb;
    uint32_t s;
    std::memcpy(&s, &fs, 4);
    // round to nearest, ties to even, on the bits: subnormals and the
    // carry into infinity come out of the same add
    const uint32_t rounded = (s + 0x7fffu + ((s >> 16) & 1u)) >> 16;
    // a NaN is made quiet and keeps one sign: src's if src is one, else
    // dst's, else (infinities of opposite sign) the negative default.
    // Spelled out, because which operand's NaN a hardware add passes on
    // depends on the order the compiler gives them.
    const uint32_t nan_sign =
        is_nan(b) ? (b >> 16) & 0x8000u
                  : is_nan(a) ? (a >> 16) & 0x8000u : 0x8000u;
    dst[i] = static_cast<uint16_t>(is_nan(s) ? (nan_sign | 0x7fc0u) : rounded);
  }
}

}  // namespace tft
