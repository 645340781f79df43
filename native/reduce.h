#pragma once
// Elementwise reductions of the host ring, outside the interpreter.

#include <cstddef>
#include <cstdint>

namespace tft {

// dst[i] = bfloat16(float(dst[i]) + float(src[i])), round to nearest even,
// bit for bit ml_dtypes' `dst += src`. The ranges may not overlap partly.
void bf16_add(uint16_t* dst, const uint16_t* src, size_t n);

}  // namespace tft
