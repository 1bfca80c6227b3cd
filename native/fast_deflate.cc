// See fast_deflate.h. RFC 1951 (deflate) + RFC 1950 (zlib wrapper).
//
// Shape of the encoder:
//   pass 1: scan input for distance-1 runs, histogram literal/length
//           symbols (distance tree is trivial: only symbol 0 is used);
//   build:  length-limited canonical Huffman codes for the literal
//           tree and the code-length tree;
//   pass 2: emit the dynamic-block header and the symbol stream.

#include "fast_deflate.h"

#include <algorithm>
#include <cstdlib>
#include <cstring>
#include <vector>

#include <zlib.h>  // adler32

#if defined(__x86_64__) || defined(__i386__)
#include <immintrin.h>
#define OMPB_X86 1
#elif defined(__aarch64__)
#include <arm_neon.h>
#define OMPB_NEON 1
#endif

namespace ompb {
namespace {

constexpr int kMinRun = 4;     // shortest run worth a length/dist pair
constexpr int kMaxRun = 258;   // deflate max match length
constexpr int kNumLit = 286;   // 0-255 literals, 256 EOB, 257-285 lengths

// -- bit writer (LSB-first, as deflate wants) ---------------------------

struct BitWriter {
  uint8_t* out;
  size_t cap;
  size_t pos = 0;
  uint64_t acc = 0;
  int nbits = 0;
  bool overflow = false;

  BitWriter(uint8_t* o, size_t c) : out(o), cap(c) {}

  // Bulk flush: store the whole 64-bit accumulator unaligned and
  // advance by the 4 completed bytes (little-endian layout matches
  // deflate's LSB-first bit order). Single Put must stay <= 32 bits.
  inline void Put(uint32_t code, int n) {
    acc |= static_cast<uint64_t>(code) << nbits;
    nbits += n;
    if (nbits >= 32) {
      if (pos + 8 > cap) {
        overflow = true;
        nbits = 0;
        return;
      }
      std::memcpy(out + pos, &acc, 8);
      pos += 4;
      acc >>= 32;
      nbits -= 32;
    }
  }

  // Wide put for packed literal groups: up to 56 bits per call. The
  // accumulator is kept byte-drained (nbits < 8 after every call), so
  // 56 + 7 = 63 bits always fit.
  inline void Put56(uint64_t code, int n) {
    acc |= code << nbits;
    nbits += n;
    int bytes = nbits >> 3;
    if (pos + 8 > cap) {
      overflow = true;
      nbits &= 7;
      return;
    }
    std::memcpy(out + pos, &acc, 8);
    pos += bytes;
    acc >>= bytes * 8;  // bytes <= 7 here (nbits <= 63)
    nbits &= 7;
  }

  // Drain to the byte boundary so Put and Put56 can interleave.
  inline void Align() {
    while (nbits >= 8) {
      if (pos >= cap) {
        overflow = true;
        nbits = 0;
        return;
      }
      out[pos++] = static_cast<uint8_t>(acc);
      acc >>= 8;
      nbits -= 8;
    }
  }

  void FlushByte() {
    while (nbits > 0) {
      if (pos >= cap) {
        overflow = true;
        return;
      }
      out[pos++] = static_cast<uint8_t>(acc);
      acc >>= 8;
      nbits -= 8;
    }
    nbits = 0;
  }
};

// -- length -> (symbol, extra bits, extra value) ------------------------

struct LenCode {
  uint16_t sym;
  uint8_t extra_bits;
  uint16_t extra_val;
};

// Deflate length table (RFC 1951 §3.2.5), expanded per length 3..258.
const LenCode* LengthTable() {
  static LenCode table[kMaxRun + 1];
  static bool init = [] {
    struct Row {
      int sym, extra, base;
    };
    static const Row rows[] = {
        {257, 0, 3},   {258, 0, 4},   {259, 0, 5},   {260, 0, 6},
        {261, 0, 7},   {262, 0, 8},   {263, 0, 9},   {264, 0, 10},
        {265, 1, 11},  {266, 1, 13},  {267, 1, 15},  {268, 1, 17},
        {269, 2, 19},  {270, 2, 23},  {271, 2, 27},  {272, 2, 31},
        {273, 3, 35},  {274, 3, 43},  {275, 3, 51},  {276, 3, 59},
        {277, 4, 67},  {278, 4, 83},  {279, 4, 99},  {280, 4, 115},
        {281, 5, 131}, {282, 5, 163}, {283, 5, 195}, {284, 5, 227},
        {285, 0, 258},
    };
    for (const Row& r : rows) {
      int hi = (r.sym == 285) ? 258 : r.base + (1 << r.extra) - 1;
      for (int len = r.base; len <= hi && len <= kMaxRun; ++len) {
        table[len] = {static_cast<uint16_t>(r.sym),
                      static_cast<uint8_t>(r.extra),
                      static_cast<uint16_t>(len - r.base)};
      }
    }
    return true;
  }();
  (void)init;
  return table;
}

// -- run tokens + AVX2 literal sweep ------------------------------------

struct RunTok {
  uint32_t pos;
  uint16_t len;
};

#if defined(OMPB_X86)
inline bool HasAvx2() {
  static const bool v = __builtin_cpu_supports("avx2");
  return v;
}

// Advance through guaranteed-literal positions, histogramming as it
// goes; stops at (or just before) any 4-equal byte group — every run
// the scalar loop could trigger implies such a group at the trigger
// or one before it, so stopping there is conservative and exact.
__attribute__((target("avx2"))) static size_t LiteralSweepAvx2(
    const uint8_t* in, size_t i, size_t n, uint32_t* h0, uint32_t* h1,
    uint32_t* h2, uint32_t* h3) {
  while (i + 35 <= n) {
    const __m256i a =
        _mm256_loadu_si256(reinterpret_cast<const __m256i*>(in + i));
    const __m256i b =
        _mm256_loadu_si256(reinterpret_cast<const __m256i*>(in + i + 1));
    const __m256i c =
        _mm256_loadu_si256(reinterpret_cast<const __m256i*>(in + i + 2));
    const __m256i d =
        _mm256_loadu_si256(reinterpret_cast<const __m256i*>(in + i + 3));
    const __m256i eq = _mm256_and_si256(
        _mm256_and_si256(_mm256_cmpeq_epi8(a, b), _mm256_cmpeq_epi8(a, c)),
        _mm256_cmpeq_epi8(a, d));
    const uint32_t mask =
        static_cast<uint32_t>(_mm256_movemask_epi8(eq));
    if (mask == 0) {
      for (int k = 0; k < 32; k += 4) {
        h0[in[i + k]]++;
        h1[in[i + k + 1]]++;
        h2[in[i + k + 2]]++;
        h3[in[i + k + 3]]++;
      }
      i += 32;
      continue;
    }
    const int first = __builtin_ctz(mask);
    for (int k = 0; k < first; ++k) h0[in[i + k]]++;
    return i + first;
  }
  return i;
}
#endif

// Runtime gate for every vector path: CPU capability plus the
// OMPB_NO_SIMD=1 escape hatch (read per call — tests flip it to pin
// the scalar path byte-identical against the vector one).
inline bool SimdEnabled() {
  const char* off = std::getenv("OMPB_NO_SIMD");
  if (off && off[0] == '1') return false;
#if defined(OMPB_X86)
  return HasAvx2();
#elif defined(OMPB_NEON)
  return true;
#else
  return false;
#endif
}

// -- SIMD literal emit (fpnge-style packed Huffman concatenation) -------
//
// Pass 2's literal spans dominate the emit on filtered noisy samples.
// The vector path processes 8 literals per step: gather their
// (code | len << 24) table entries, concatenate PAIRS of codes inside
// 64-bit lanes with variable shifts (code_lo | code_hi << len_lo — the
// fpnge trick: a Huffman code concatenation is just a shift + or), then
// merge the four pair lanes through the 56-bit wide writer exactly as
// the scalar quad loop does. The BITSTREAM is the in-order code
// concatenation either way, so vector and scalar paths are
// byte-identical by construction (and pinned so in tests/CI).

#if defined(OMPB_X86)
__attribute__((target("avx2"))) static size_t EmitLiteralsAvx2(
    BitWriter& bw, const uint32_t* packed, const uint8_t* p, size_t m) {
  const __m256i mask24 = _mm256_set1_epi32(0xFFFFFF);
  const __m256i mask32 = _mm256_set1_epi64x(0xFFFFFFFFll);
  size_t k = 0;
  for (; k + 8 <= m; k += 8) {
    const __m128i bytes =
        _mm_loadl_epi64(reinterpret_cast<const __m128i*>(p + k));
    const __m256i idx = _mm256_cvtepu8_epi32(bytes);
    const __m256i e = _mm256_i32gather_epi32(
        reinterpret_cast<const int*>(packed), idx, 4);
    const __m256i code = _mm256_and_si256(e, mask24);
    const __m256i len = _mm256_srli_epi32(e, 24);
    // concatenate lane pairs (0,1)(2,3)(4,5)(6,7) inside u64 lanes
    const __m256i code_even = _mm256_and_si256(code, mask32);
    const __m256i code_odd = _mm256_srli_epi64(code, 32);
    const __m256i len_even = _mm256_and_si256(len, mask32);
    const __m256i len_odd = _mm256_srli_epi64(len, 32);
    const __m256i pair =
        _mm256_or_si256(code_even, _mm256_sllv_epi64(code_odd, len_even));
    const __m256i plen = _mm256_add_epi64(len_even, len_odd);
    const uint64_t c01 = _mm256_extract_epi64(pair, 0);
    const uint64_t c23 = _mm256_extract_epi64(pair, 1);
    const uint64_t c45 = _mm256_extract_epi64(pair, 2);
    const uint64_t c67 = _mm256_extract_epi64(pair, 3);
    const int n01 = static_cast<int>(_mm256_extract_epi64(plen, 0));
    const int n23 = static_cast<int>(_mm256_extract_epi64(plen, 1));
    const int n45 = static_cast<int>(_mm256_extract_epi64(plen, 2));
    const int n67 = static_cast<int>(_mm256_extract_epi64(plen, 3));
    // a pair is <= 30 bits; a quad can exceed the 56-bit writer
    // budget only with >= 14-bit average codes (rare) — split then
    if (n01 + n23 <= 56) {
      bw.Put56(c01 | (c23 << n01), n01 + n23);
    } else {
      bw.Put56(c01, n01);
      bw.Put56(c23, n23);
    }
    if (n45 + n67 <= 56) {
      bw.Put56(c45 | (c67 << n45), n45 + n67);
    } else {
      bw.Put56(c45, n45);
      bw.Put56(c67, n67);
    }
  }
  return k;
}
#endif

#if defined(OMPB_NEON)
static size_t EmitLiteralsNeon(
    BitWriter& bw, const uint32_t* packed, const uint8_t* p, size_t m) {
  size_t k = 0;
  for (; k + 8 <= m; k += 8) {
    uint32_t e[8];
    for (int j = 0; j < 8; ++j) e[j] = packed[p[k + j]];
    const uint64x2_t ce0 = {e[0] & 0xFFFFFFu, e[2] & 0xFFFFFFu};
    const uint64x2_t co0 = {e[1] & 0xFFFFFFu, e[3] & 0xFFFFFFu};
    const int64x2_t ne0 = {static_cast<int64_t>(e[0] >> 24),
                           static_cast<int64_t>(e[2] >> 24)};
    const uint64x2_t pr0 = vorrq_u64(ce0, vshlq_u64(co0, ne0));
    const uint64x2_t ce1 = {e[4] & 0xFFFFFFu, e[6] & 0xFFFFFFu};
    const uint64x2_t co1 = {e[5] & 0xFFFFFFu, e[7] & 0xFFFFFFu};
    const int64x2_t ne1 = {static_cast<int64_t>(e[4] >> 24),
                           static_cast<int64_t>(e[6] >> 24)};
    const uint64x2_t pr1 = vorrq_u64(ce1, vshlq_u64(co1, ne1));
    const uint64_t c01 = vgetq_lane_u64(pr0, 0);
    const uint64_t c23 = vgetq_lane_u64(pr0, 1);
    const uint64_t c45 = vgetq_lane_u64(pr1, 0);
    const uint64_t c67 = vgetq_lane_u64(pr1, 1);
    const int n01 = static_cast<int>((e[0] >> 24) + (e[1] >> 24));
    const int n23 = static_cast<int>((e[2] >> 24) + (e[3] >> 24));
    const int n45 = static_cast<int>((e[4] >> 24) + (e[5] >> 24));
    const int n67 = static_cast<int>((e[6] >> 24) + (e[7] >> 24));
    if (n01 + n23 <= 56) {
      bw.Put56(c01 | (c23 << n01), n01 + n23);
    } else {
      bw.Put56(c01, n01);
      bw.Put56(c23, n23);
    }
    if (n45 + n67 <= 56) {
      bw.Put56(c45 | (c67 << n45), n45 + n67);
    } else {
      bw.Put56(c45, n45);
      bw.Put56(c67, n67);
    }
  }
  return k;
}
#endif

inline uint32_t Reverse(uint32_t code, int len) {
  uint32_t r = 0;
  for (int i = 0; i < len; ++i) {
    r = (r << 1) | (code & 1);
    code >>= 1;
  }
  return r;
}

// -- length-limited Huffman ---------------------------------------------

// Build code lengths for `n` symbols with the given frequencies, no
// code longer than `limit`. Frequency-damping: halve-and-rebuild until
// the tree fits the limit (converges fast; ratio impact negligible).
// `tie_by_id` breaks equal frequencies on (freq, id) — a leaf's id is
// its symbol, an internal node's is n + its creation order, which is
// exactly the order of `nodes` — so the tree is the one the device
// plan's Python builder (ops/device_deflate._build_lengths_np) makes.
// Without it ties fall where the heap leaves them, as the host
// encoder's streams always have.
template <typename F>
void BuildLengths(const F* freq_in, int n, int limit, uint8_t* lengths,
                  bool tie_by_id = false) {
  std::vector<uint64_t> freq(freq_in, freq_in + n);
  std::memset(lengths, 0, n);
  for (;;) {
    // collect used symbols
    struct Node {
      uint64_t f;
      int left, right, sym;  // sym >= 0 for leaves
    };
    std::vector<Node> nodes;
    std::vector<int> heap;  // indices into nodes, min-heap by freq
    for (int i = 0; i < n; ++i) {
      if (freq[i]) {
        nodes.push_back({freq[i], -1, -1, i});
        heap.push_back(static_cast<int>(nodes.size()) - 1);
      }
    }
    if (nodes.empty()) return;
    if (nodes.size() == 1) {
      lengths[nodes[0].sym] = 1;
      return;
    }
    auto cmp = [&](int a, int b) {
      if (tie_by_id && nodes[a].f == nodes[b].f) return a > b;
      return nodes[a].f > nodes[b].f;
    };
    std::make_heap(heap.begin(), heap.end(), cmp);
    while (heap.size() > 1) {
      std::pop_heap(heap.begin(), heap.end(), cmp);
      int a = heap.back();
      heap.pop_back();
      std::pop_heap(heap.begin(), heap.end(), cmp);
      int b = heap.back();
      heap.pop_back();
      nodes.push_back({nodes[a].f + nodes[b].f, a, b, -1});
      heap.push_back(static_cast<int>(nodes.size()) - 1);
      std::push_heap(heap.begin(), heap.end(), cmp);
    }
    // depth-assign iteratively
    int root = heap[0];
    std::vector<std::pair<int, int>> stack = {{root, 0}};
    int maxdepth = 0;
    while (!stack.empty()) {
      auto [idx, depth] = stack.back();
      stack.pop_back();
      const Node& nd = nodes[idx];
      if (nd.sym >= 0) {
        lengths[nd.sym] = static_cast<uint8_t>(depth == 0 ? 1 : depth);
        maxdepth = std::max(maxdepth, std::max(depth, 1));
      } else {
        stack.push_back({nd.left, depth + 1});
        stack.push_back({nd.right, depth + 1});
      }
    }
    if (maxdepth <= limit) return;
    for (int i = 0; i < n; ++i) {
      if (freq[i]) freq[i] = (freq[i] + 1) >> 1;  // damp, keep nonzero
    }
  }
}

// Canonical codes from lengths (RFC 1951 §3.2.2), pre-bit-reversed for
// LSB-first emission.
void BuildCodes(const uint8_t* lengths, int n, int max_len,
                uint32_t* codes) {
  std::vector<int> bl_count(max_len + 1, 0);
  for (int i = 0; i < n; ++i) bl_count[lengths[i]]++;
  bl_count[0] = 0;
  std::vector<uint32_t> next_code(max_len + 1, 0);
  uint32_t code = 0;
  for (int bits = 1; bits <= max_len; ++bits) {
    code = (code + bl_count[bits - 1]) << 1;
    next_code[bits] = code;
  }
  for (int i = 0; i < n; ++i) {
    if (lengths[i]) {
      codes[i] = Reverse(next_code[lengths[i]]++, lengths[i]);
    }
  }
}

// RLE-encode the code-length sequence with CL symbols 16/17/18
// (RFC 1951 §3.2.7). Emits (symbol, extra_bits, extra_val) triples.
struct ClOp {
  uint8_t sym;
  uint8_t extra_bits;
  uint8_t extra_val;
};

void EncodeCodeLengths(const uint8_t* lens, int n, std::vector<ClOp>* ops,
                       uint32_t* cl_freq) {
  int i = 0;
  while (i < n) {
    uint8_t v = lens[i];
    int run = 1;
    while (i + run < n && lens[i + run] == v) run++;
    if (v == 0) {
      while (run >= 3) {
        int take = std::min(run, 138);
        if (take >= 11) {
          ops->push_back({18, 7, static_cast<uint8_t>(take - 11)});
        } else {
          ops->push_back({17, 3, static_cast<uint8_t>(take - 3)});
        }
        cl_freq[take >= 11 ? 18 : 17]++;
        run -= take;
        i += take;
      }
      while (run-- > 0) {
        ops->push_back({0, 0, 0});
        cl_freq[0]++;
        i++;
      }
    } else {
      ops->push_back({v, 0, 0});
      cl_freq[v]++;
      i++;
      run--;
      while (run >= 3) {
        int take = std::min(run, 6);
        ops->push_back({16, 2, static_cast<uint8_t>(take - 3)});
        cl_freq[16]++;
        run -= take;
        i += take;
      }
      while (run-- > 0) {
        ops->push_back({v, 0, 0});
        cl_freq[v]++;
        i++;
      }
    }
  }
}

const int kClOrder[19] = {16, 17, 18, 0, 8,  7, 9,  6, 10, 5,
                          11, 4,  12, 3, 13, 2, 14, 1, 15};

// Fixed-Huffman code length of each lit/len symbol (RFC 1951 §3.2.6).
inline int FixedSymLen(int s) {
  return s < 144 ? 8 : s < 256 ? 9 : s < 280 ? 7 : 8;
}

}  // namespace

bool DynamicPlanLane(const int64_t* counts, int64_t extra_bits, int hdr_cap,
                     uint32_t* hdr_b, int32_t* hdr_n, uint32_t* lit_b,
                     int32_t* lit_n, uint32_t* ml_b, int32_t* ml_n,
                     uint32_t* eob_b, int32_t* eob_n) {
  int64_t match_tokens = 0;
  for (int s = 257; s < kNumLit; ++s) match_tokens += counts[s];
  uint64_t freq[kNumLit];
  for (int s = 0; s < kNumLit; ++s) freq[s] = static_cast<uint64_t>(counts[s]);
  freq[256] = 1;  // end-of-block (pass 1 histograms payload tokens only)
  uint8_t lit_len[kNumLit];
  BuildLengths(freq, kNumLit, 15, lit_len, /*tie_by_id=*/true);

  // exact totals: code bits per symbol + match extra bits + one 1-bit
  // distance code per match (dynamic) or 5-bit fixed distance code
  int64_t dyn_body = extra_bits + match_tokens + lit_len[256];
  int64_t fixed_total = 3 + extra_bits + match_tokens * 5 + 7;
  for (int s = 0; s < kNumLit; ++s) {
    dyn_body += counts[s] * lit_len[s];
    fixed_total += counts[s] * FixedSymLen(s);
  }

  int hlit = kNumLit;
  while (hlit > 257 && lit_len[hlit - 1] == 0) hlit--;
  uint8_t all_lens[kNumLit + 1];
  std::memcpy(all_lens, lit_len, hlit);
  all_lens[hlit] = match_tokens > 0 ? 1 : 0;  // the one distance code
  std::vector<ClOp> ops;
  ops.reserve(kNumLit + 1);
  uint32_t cl_freq[19] = {0};
  EncodeCodeLengths(all_lens, hlit + 1, &ops, cl_freq);
  uint8_t cl_len[19];
  BuildLengths(cl_freq, 19, 7, cl_len, /*tie_by_id=*/true);
  int used = 0, only = 0;
  for (int s = 0; s < 19; ++s) {
    if (cl_len[s]) {
      used++;
      only = s;
    }
  }
  // a lone 1-bit CL code is an incomplete tree, which inflate rejects:
  // a dummy 1-bit code on an unused symbol completes it
  if (used == 1) cl_len[only != 0 ? 0 : 1] = 1;
  uint32_t cl_code[19] = {0};
  BuildCodes(cl_len, 19, 7, cl_code);
  int hclen = 19;
  while (hclen > 4 && cl_len[kClOrder[hclen - 1]] == 0) hclen--;

  const int ntok = 4 + hclen + static_cast<int>(ops.size());
  if (ntok > hdr_cap) return false;
  int64_t hdr_bits = 3 + 5 + 5 + 4 + 3 * hclen;
  for (const ClOp& op : ops) hdr_bits += cl_len[op.sym] + op.extra_bits;
  if (hdr_bits + dyn_body >= fixed_total) return false;

  int t = 0;
  auto put = [&](uint32_t v, int nb) {
    hdr_b[t] = v;
    hdr_n[t++] = nb;
  };
  put(5, 3);  // BFINAL=1, BTYPE=10
  put(static_cast<uint32_t>(hlit - 257), 5);
  put(0, 5);  // HDIST - 1: one distance code
  put(static_cast<uint32_t>(hclen - 4), 4);
  for (int k = 0; k < hclen; ++k) put(cl_len[kClOrder[k]], 3);
  for (const ClOp& op : ops) {
    const int cn = cl_len[op.sym];
    put(cl_code[op.sym] | (static_cast<uint32_t>(op.extra_val) << cn),
        cn + op.extra_bits);
  }
  for (; t < hdr_cap; ++t) hdr_b[t] = hdr_n[t] = 0;

  uint32_t lit_code[kNumLit] = {0};
  BuildCodes(lit_len, kNumLit, 15, lit_code);
  for (int s = 0; s < 256; ++s) {
    lit_b[s] = lit_code[s];
    lit_n[s] = lit_len[s];
  }
  const LenCode* len_table = LengthTable();
  for (int ln = 0; ln <= kMaxRun; ++ln) {
    ml_b[ln] = 0;
    ml_n[ln] = 0;
    if (ln < 3) continue;
    const LenCode& lc = len_table[ln];
    const int cn = lit_len[lc.sym];
    if (cn == 0) continue;  // symbol absent from this lane
    ml_b[ln] = lit_code[lc.sym] | (static_cast<uint32_t>(lc.extra_val) << cn);
    ml_n[ln] = cn + lc.extra_bits + 1;  // + the 1-bit distance-1 code
  }
  *eob_b = lit_code[256];
  *eob_n = lit_len[256];
  return true;
}

size_t FastDeflate(const uint8_t* in, size_t n, uint8_t* out, size_t cap) {
  if (cap < 64) return 0;
  const LenCode* len_table = LengthTable();

  // ---- pass 1: tokenize + histogram in one scan ----
  // Representation: a list of (pos, len) distance-1 runs; the bytes
  // between runs are literal spans read straight from the input in
  // pass 2 (no per-byte token buffer). The AVX2 sweep skips 32
  // literal bytes at a time when no 4-equal group is present — the
  // dominant case for PNG-filtered noisy samples — with four
  // interleaved histograms to break the increment dependency chain.
  std::vector<RunTok> runs;
  runs.reserve(64);
  uint32_t lit_freq[kNumLit] = {0};
  uint32_t h1[256] = {0}, h2[256] = {0}, h3[256] = {0};
  bool any_run = false;
  {
#if defined(OMPB_X86)
    const bool use_avx2 = HasAvx2() && SimdEnabled();
#endif
    size_t i = 0;
    size_t scalar_until = 0;  // backoff after a failed run candidate
    while (i < n) {
#if defined(OMPB_X86)
      if (use_avx2 && i >= scalar_until) {
        i = LiteralSweepAvx2(in, i, n, lit_freq, h1, h2, h3);
        if (i >= n) break;
      }
#endif
      if (i > 0 && in[i] == in[i - 1]) {
        size_t run = 1;
        const uint8_t v = in[i - 1];
        while (i + run < n && in[i + run] == v &&
               run < static_cast<size_t>(kMaxRun)) {
          run++;
        }
        if (run >= kMinRun) {
          lit_freq[len_table[run].sym]++;
          runs.push_back({static_cast<uint32_t>(i),
                          static_cast<uint16_t>(run)});
          any_run = true;
          i += run;
          continue;
        }
        // 4-equal group too short for a match: take its bytes as
        // literals scalar-side before re-entering the sweep (the
        // sweep would re-flag the same group forever)
        scalar_until = i + run + 1;
      }
      lit_freq[in[i]]++;
      i++;
    }
    for (int s = 0; s < 256; ++s) {
      lit_freq[s] += h1[s] + h2[s] + h3[s];
    }
  }
  lit_freq[256] = 1;  // end-of-block

  // ---- literal + distance trees ----
  uint8_t lit_len[kNumLit] = {0};
  BuildLengths(lit_freq, kNumLit, 15, lit_len);
  uint32_t lit_code[kNumLit] = {0};
  BuildCodes(lit_len, kNumLit, 15, lit_code);

  // distance tree: only symbol 0 (distance 1), or none at all
  uint8_t dist_len[1] = {static_cast<uint8_t>(any_run ? 1 : 0)};
  // code for the single 1-bit distance symbol is 0

  // trim trailing zero-length literal codes (HLIT >= 257)
  int hlit = kNumLit;
  while (hlit > 257 && lit_len[hlit - 1] == 0) hlit--;
  const int hdist = 1;

  // ---- code-length tree over (lit lengths ++ dist lengths) ----
  std::vector<uint8_t> all_lens(lit_len, lit_len + hlit);
  all_lens.push_back(dist_len[0]);
  std::vector<ClOp> cl_ops;
  uint32_t cl_freq[19] = {0};
  EncodeCodeLengths(all_lens.data(), static_cast<int>(all_lens.size()),
                    &cl_ops, cl_freq);
  uint8_t cl_len[19] = {0};
  BuildLengths(cl_freq, 19, 7, cl_len);
  uint32_t cl_code[19] = {0};
  BuildCodes(cl_len, 19, 7, cl_code);
  int hclen = 19;
  while (hclen > 4 && cl_len[kClOrder[hclen - 1]] == 0) hclen--;

  // ---- emit ----
  if (cap < 6) return 0;
  out[0] = 0x78;  // CM=8 CINFO=7
  out[1] = 0x01;  // FLEVEL=0, FCHECK makes the pair % 31 == 0
  BitWriter bw(out + 2, cap - 6);  // reserve adler32 tail

  bw.Put(1, 1);  // BFINAL
  bw.Put(2, 2);  // BTYPE=10 dynamic
  bw.Put(static_cast<uint32_t>(hlit - 257), 5);
  bw.Put(static_cast<uint32_t>(hdist - 1), 5);
  bw.Put(static_cast<uint32_t>(hclen - 4), 4);
  for (int i = 0; i < hclen; ++i) bw.Put(cl_len[kClOrder[i]], 3);
  for (const ClOp& op : cl_ops) {
    bw.Put(cl_code[op.sym], cl_len[op.sym]);
    if (op.extra_bits) bw.Put(op.extra_val, op.extra_bits);
  }

  // symbol stream: literal spans (straight from the input) between
  // run tokens. Literals emit four-at-a-time through one wide
  // bit-writer call — codes are <= 15 bits each and usually far
  // shorter, so a quad nearly always fits the 56-bit budget.
  {
    bw.Align();  // Put56 needs the accumulator byte-drained
    uint32_t packed[256];
    for (int s = 0; s < 256; ++s) {
      packed[s] =
          lit_code[s] | (static_cast<uint32_t>(lit_len[s]) << 24);
    }
    const bool simd = SimdEnabled();
    auto emit_literals = [&](const uint8_t* p, size_t m) {
      size_t k = 0;
      if (simd) {
        // vector fast path: 8 literals per step; the scalar loop
        // below finishes the (< 8) tail — identical bitstream either
        // way (in-order code concatenation)
#if defined(OMPB_X86)
        k = EmitLiteralsAvx2(bw, packed, p, m);
#elif defined(OMPB_NEON)
        k = EmitLiteralsNeon(bw, packed, p, m);
#endif
      }
      for (; k + 4 <= m; k += 4) {
        const uint32_t e0 = packed[p[k]], e1 = packed[p[k + 1]];
        const uint32_t e2 = packed[p[k + 2]], e3 = packed[p[k + 3]];
        const int n0 = e0 >> 24, n1 = e1 >> 24;
        const int n2 = e2 >> 24, n3 = e3 >> 24;
        if (n0 + n1 + n2 + n3 <= 56) {
          uint64_t bits = e0 & 0xFFFFFF;
          bits |= static_cast<uint64_t>(e1 & 0xFFFFFF) << n0;
          bits |= static_cast<uint64_t>(e2 & 0xFFFFFF) << (n0 + n1);
          bits |= static_cast<uint64_t>(e3 & 0xFFFFFF)
                  << (n0 + n1 + n2);
          bw.Put56(bits, n0 + n1 + n2 + n3);
        } else {
          bw.Put56(
              (e0 & 0xFFFFFF) |
                  (static_cast<uint64_t>(e1 & 0xFFFFFF) << n0),
              n0 + n1);
          bw.Put56(
              (e2 & 0xFFFFFF) |
                  (static_cast<uint64_t>(e3 & 0xFFFFFF) << n2),
              n2 + n3);
        }
      }
      for (; k < m; ++k) {
        bw.Put56(packed[p[k]] & 0xFFFFFF, packed[p[k]] >> 24);
      }
    };
    size_t cur = 0;
    for (const RunTok& r : runs) {
      emit_literals(in + cur, r.pos - cur);
      // one fused write: length code + extra bits + the 1-bit
      // distance-1 code (a zero bit) — <= 21 bits total
      const LenCode& lc = len_table[r.len];
      uint64_t bits = lit_code[lc.sym];
      int nb = lit_len[lc.sym];
      bits |= static_cast<uint64_t>(lc.extra_val) << nb;
      nb += lc.extra_bits + 1;
      bw.Put56(bits, nb);
      cur = r.pos + r.len;
    }
    emit_literals(in + cur, n - cur);
    bw.Put56(lit_code[256], lit_len[256]);  // EOB
  }
  bw.FlushByte();
  if (bw.overflow) return 0;

  uLong adler = adler32(1L, in, static_cast<uInt>(n));
  size_t pos = 2 + bw.pos;
  if (pos + 4 > cap) return 0;
  out[pos++] = static_cast<uint8_t>(adler >> 24);
  out[pos++] = static_cast<uint8_t>(adler >> 16);
  out[pos++] = static_cast<uint8_t>(adler >> 8);
  out[pos++] = static_cast<uint8_t>(adler);
  return pos;
}

}  // namespace ompb
