// Specialized deflate encoder for PNG-filtered scanlines: distance-1
// (RLE) matching + per-stream dynamic Huffman, emitted as one final
// block inside a zlib wrapper. Matches zlib Z_RLE's ratios on filtered
// image data at a fraction of the cost — the generic match-finder,
// lazy evaluation, and incremental-flush machinery are all skipped.
//
// Returns the number of bytes written to `out`, or 0 if `cap` is too
// small (callers fall back to zlib). Output always inflates to exactly
// the input (oracle-tested against zlib).
#ifndef OMPB_FAST_DEFLATE_H_
#define OMPB_FAST_DEFLATE_H_

#include <cstddef>
#include <cstdint>

namespace ompb {

// Safe capacity for any input: worst case is all-literal at <= 15
// bits/symbol, but Huffman averages <= 8.6 bits on any byte stream;
// head-room for trees + wrapper.
inline size_t FastDeflateBound(size_t n) { return n + n / 4 + 2048; }

size_t FastDeflate(const uint8_t* in, size_t n, uint8_t* out, size_t cap);

// The host plan of the device's two-pass dynamic deflate, for one lane:
// from its pass-1 counts (286 lit/len symbols, non-negative) and match
// extra bits, the length-limited canonical codes, the run-coded block
// header as (value, nbits) tokens, and the exact dynamic and fixed bit
// totals. Returns false, writing nothing, when the fixed code is no
// longer or the header needs more than `hdr_cap` tokens; else fills the
// lane's rows: header tokens (zeros past the last), literal codes and
// lengths (256), match-length codes and bit counts (259) and the EOB
// code. Bit-identical to ops/device_deflate._lane_dynamic_plan.
bool DynamicPlanLane(const int64_t* counts, int64_t extra_bits, int hdr_cap,
                     uint32_t* hdr_b, int32_t* hdr_n, uint32_t* lit_b,
                     int32_t* lit_n, uint32_t* ml_b, int32_t* ml_n,
                     uint32_t* eob_b, int32_t* eob_n);

}  // namespace ompb

#endif  // OMPB_FAST_DEFLATE_H_
