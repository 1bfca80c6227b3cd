// Native encode/IO runtime for the TPU pixel-buffer service.
//
// Replaces the JVM-side byte machinery the reference leans on
// (Bio-Formats ImageWriter in-memory encode, TileRequestHandler.java
// writeImage; per-block codec work inside ome.io.nio readers) with a
// thread-pooled C++ engine driven from Python via ctypes:
//
//   - ompb_deflate_batch:  N buffers -> zlib/deflate streams, parallel
//   - ompb_inflate_batch:  N compressed blocks -> caller-owned output
//                          buffers (zero-copy into numpy), parallel
//   - ompb_png_assemble_batch: N filtered scanline buffers -> complete
//                          PNG byte streams (deflate + CRC + chunking)
//
// ctypes releases the GIL for the duration of each call, so the whole
// batch runs on native threads while Python (and the TPU pipeline)
// keep moving. Pool size: OMPB_NATIVE_THREADS or hardware concurrency.
//
// Build: make -C native  (g++ -O3 -shared, links -lz). No third-party
// deps beyond zlib.

#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <functional>
#include <mutex>
#include <queue>
#include <thread>
#include <vector>

#include <zlib.h>

#include "fast_deflate.h"

namespace {

// Strategy code for the in-house RLE+dynamic-Huffman encoder (zlib's
// own strategies are 0..4).
constexpr int kStrategyFast = 100;

class ThreadPool {
 public:
  explicit ThreadPool(size_t n) {
    for (size_t i = 0; i < n; ++i) {
      workers_.emplace_back([this] { Loop(); });
    }
  }
  ~ThreadPool() {
    {
      std::unique_lock<std::mutex> lk(mu_);
      stop_ = true;
    }
    cv_.notify_all();
    for (auto& w : workers_) w.join();
  }
  void Submit(std::function<void()> fn) {
    {
      std::unique_lock<std::mutex> lk(mu_);
      queue_.push(std::move(fn));
    }
    cv_.notify_one();
  }
  size_t size() const { return workers_.size(); }

 private:
  void Loop() {
    for (;;) {
      std::function<void()> fn;
      {
        std::unique_lock<std::mutex> lk(mu_);
        cv_.wait(lk, [this] { return stop_ || !queue_.empty(); });
        if (stop_ && queue_.empty()) return;
        fn = std::move(queue_.front());
        queue_.pop();
      }
      fn();
    }
  }
  std::vector<std::thread> workers_;
  std::queue<std::function<void()>> queue_;
  std::mutex mu_;
  std::condition_variable cv_;
  bool stop_ = false;
};

ThreadPool& Pool() {
  static ThreadPool* pool = [] {
    size_t n = std::thread::hardware_concurrency();
    if (const char* env = std::getenv("OMPB_NATIVE_THREADS")) {
      long v = std::strtol(env, nullptr, 10);
      if (v > 0) n = static_cast<size_t>(v);
    }
    if (n == 0) n = 1;
    return new ThreadPool(n);
  }();
  return *pool;
}

// Run fn(i) for i in [0, n) across the pool, block until done. Work
// state is shared-owned by every worker lambda so stragglers that lose
// the work-stealing race never touch freed stack frames.
void ParallelFor(size_t n, std::function<void(size_t)> fn) {
  if (n == 0) return;
  if (n == 1 || Pool().size() == 1) {
    for (size_t i = 0; i < n; ++i) fn(i);
    return;
  }
  struct State {
    std::atomic<size_t> next{0};
    std::atomic<size_t> done{0};
    std::mutex mu;
    std::condition_variable cv;
    size_t n;
    std::function<void(size_t)> fn;
  };
  auto st = std::make_shared<State>();
  st->n = n;
  st->fn = std::move(fn);
  size_t lanes = std::min(n, Pool().size());
  for (size_t l = 0; l < lanes; ++l) {
    Pool().Submit([st] {
      for (;;) {
        size_t i = st->next.fetch_add(1);
        if (i >= st->n) break;
        st->fn(i);
        if (st->done.fetch_add(1) + 1 == st->n) {
          std::lock_guard<std::mutex> lk(st->mu);
          st->cv.notify_one();
        }
      }
    });
  }
  std::unique_lock<std::mutex> lk(st->mu);
  st->cv.wait(lk, [&] { return st->done.load() == st->n; });
}

// One-shot zlib-format compress; returns malloc'd buffer. Strategy is
// Z_DEFAULT_STRATEGY for generic payloads, Z_FILTERED for PNG-filtered
// scanlines (small-residual data; skips the literal-heavy heuristics).
bool DeflateOne(const uint8_t* in, size_t in_len, int level, uint8_t** out,
                size_t* out_len, int strategy = Z_DEFAULT_STRATEGY) {
  if (strategy == kStrategyFast) {
    size_t bound = ompb::FastDeflateBound(in_len);
    uint8_t* buf = static_cast<uint8_t*>(std::malloc(bound));
    if (!buf) return false;
    size_t written = ompb::FastDeflate(in, in_len, buf, bound);
    if (written > 0) {
      *out = buf;
      *out_len = written;
      return true;
    }
    std::free(buf);          // pathological input: fall back to zlib
    strategy = Z_RLE;
  }
  z_stream zs;
  std::memset(&zs, 0, sizeof(zs));
  if (deflateInit2(&zs, level, Z_DEFLATED, 15, 9, strategy) != Z_OK) {
    return false;
  }
  // deflateBound, not compressBound: Z_FILTERED/memLevel-9 streams can
  // exceed the generic bound on incompressible data.
  uLong bound = deflateBound(&zs, in_len);
  uint8_t* buf = static_cast<uint8_t*>(std::malloc(bound));
  if (!buf) {
    deflateEnd(&zs);
    return false;
  }
  zs.next_in = const_cast<Bytef*>(in);
  zs.avail_in = static_cast<uInt>(in_len);
  zs.next_out = buf;
  zs.avail_out = static_cast<uInt>(bound);
  int rc = deflate(&zs, Z_FINISH);
  deflateEnd(&zs);
  if (rc != Z_STREAM_END) {
    std::free(buf);
    return false;
  }
  *out = buf;
  *out_len = zs.total_out;
  return true;
}

void PutU32BE(uint8_t* p, uint32_t v) {
  p[0] = v >> 24;
  p[1] = (v >> 16) & 0xFF;
  p[2] = (v >> 8) & 0xFF;
  p[3] = v & 0xFF;
}

// length + tag + data + crc32(tag|data); returns bytes written.
size_t WriteChunk(uint8_t* dst, const char* tag, const uint8_t* data,
                  size_t len) {
  PutU32BE(dst, static_cast<uint32_t>(len));
  std::memcpy(dst + 4, tag, 4);
  if (len) std::memcpy(dst + 8, data, len);
  uLong crc = crc32(0L, reinterpret_cast<const Bytef*>(tag), 4);
  // zlib defines crc32(crc, nullptr, 0) as "return initial value", not
  // identity — guard so zero-length chunks (IEND) keep the tag CRC.
  if (len) crc = crc32(crc, data, static_cast<uInt>(len));
  PutU32BE(dst + 8 + len, static_cast<uint32_t>(crc));
  return 12 + len;
}

// Assemble a complete PNG stream around a ready IDAT payload.
uint8_t* AssemblePng(const uint8_t* idat, size_t idat_len, uint32_t width,
                     uint32_t height, uint8_t bit_depth, uint8_t color_type,
                     size_t* total_len) {
  static const uint8_t kSig[8] = {0x89, 'P', 'N', 'G', '\r', '\n', 0x1A, '\n'};
  size_t total = 8 + (12 + 13) + (12 + idat_len) + 12;
  uint8_t* out = static_cast<uint8_t*>(std::malloc(total));
  if (!out) return nullptr;
  uint8_t* p = out;
  std::memcpy(p, kSig, 8);
  p += 8;
  uint8_t ihdr[13];
  PutU32BE(ihdr, width);
  PutU32BE(ihdr + 4, height);
  ihdr[8] = bit_depth;
  ihdr[9] = color_type;
  ihdr[10] = ihdr[11] = ihdr[12] = 0;  // deflate/adaptive/no-interlace
  p += WriteChunk(p, "IHDR", ihdr, 13);
  p += WriteChunk(p, "IDAT", idat, idat_len);
  p += WriteChunk(p, "IEND", nullptr, 0);
  *total_len = static_cast<size_t>(p - out);
  return out;
}

// Byteswap one row of `width*channels` samples of `itemsize` bytes from
// native little-endian to PNG big-endian (identity for itemsize 1).
void SwapRowBE(const uint8_t* src, uint8_t* dst, size_t samples,
               size_t itemsize) {
  if (itemsize == 1) {
    std::memcpy(dst, src, samples);
    return;
  }
  for (size_t s = 0; s < samples; ++s) {
    for (size_t b = 0; b < itemsize; ++b) {
      dst[s * itemsize + b] = src[s * itemsize + (itemsize - 1 - b)];
    }
  }
}

// ---- TIFF block codecs (LZW, PackBits) --------------------------------
//
// TIFF 6.0 §9 (PackBits) and §13 (LZW with the "early change" width
// bump at 510/1022/2046 that libtiff/Bio-Formats writers use).

bool PackBitsDecode(const uint8_t* in, size_t in_len, uint8_t* out,
                    size_t cap, size_t* produced) {
  size_t i = 0, o = 0;
  while (i < in_len && o < cap) {
    uint8_t b = in[i++];
    if (b == 128) continue;  // -128: no-op
    if (b < 128) {
      size_t run = static_cast<size_t>(b) + 1;
      if (i + run > in_len) return false;
      if (run > cap - o) run = cap - o;
      std::memcpy(out + o, in + i, run);
      // advance the input by the full literal even when clamped
      i += static_cast<size_t>(b) + 1;
      o += run;
    } else {
      size_t run = 257 - static_cast<size_t>(b);
      if (i >= in_len) return false;
      if (run > cap - o) run = cap - o;
      std::memset(out + o, in[i++], run);
      o += run;
    }
  }
  *produced = o;
  return true;
}

// LZW dictionary as a prefix-linked table: entry = (prefix code,
// suffix byte, depth). Strings materialize by walking the chain
// backwards — no per-entry allocation, bounded memory (4096 entries).
bool LzwDecode(const uint8_t* in, size_t in_len, uint8_t* out, size_t cap,
               size_t* produced) {
  constexpr int kClear = 256, kEoi = 257, kFirst = 258, kMax = 4096;
  int16_t prefix[kMax];
  uint8_t suffix[kMax];
  uint8_t first_char[kMax];
  for (int i = 0; i < 256; ++i) {
    prefix[i] = -1;
    suffix[i] = static_cast<uint8_t>(i);
    first_char[i] = static_cast<uint8_t>(i);
  }
  int next_code = kFirst;
  int width = 9;
  uint32_t bitbuf = 0;
  int nbits = 0;
  size_t pos = 0, o = 0;
  int old_code = -1;
  uint8_t stack[kMax];

  auto emit = [&](int code) -> bool {  // expand `code` into out
    size_t depth = 0;
    for (int c = code; c >= 0; c = prefix[c]) {
      if (depth >= sizeof(stack)) return false;  // cycle guard
      stack[depth++] = suffix[c];
    }
    while (depth && o < cap) out[o++] = stack[--depth];
    return true;
  };

  while (true) {
    while (nbits < width) {
      if (pos >= in_len) {
        // tolerate missing EOI only when the block is complete; a
        // truncated stream must fail the lane, not serve partial pixels
        *produced = o;
        return o >= cap;
      }
      bitbuf = (bitbuf << 8) | in[pos++];
      nbits += 8;
    }
    int code = (bitbuf >> (nbits - width)) & ((1u << width) - 1);
    nbits -= width;
    if (code == kEoi) break;
    if (code == kClear) {
      next_code = kFirst;
      width = 9;
      old_code = -1;
      continue;
    }
    if (old_code < 0) {
      if (code >= 256) return false;  // must start with a literal
      if (!emit(code)) return false;
      old_code = code;
    } else if (code < next_code && code != kClear && code != kEoi) {
      if (!emit(code)) return false;
      if (next_code < kMax) {
        prefix[next_code] = static_cast<int16_t>(old_code);
        suffix[next_code] = first_char[code];
        first_char[next_code] = first_char[old_code];
        ++next_code;
      }
      old_code = code;
    } else if (code == next_code && next_code < kMax) {
      prefix[next_code] = static_cast<int16_t>(old_code);
      suffix[next_code] = first_char[old_code];
      first_char[next_code] = first_char[old_code];
      ++next_code;
      if (!emit(code)) return false;
      old_code = code;
    } else {
      return false;  // code beyond table: corrupt stream
    }
    if (o >= cap) break;
    // early change (libtiff-calibrated): bump at 511/1023/2047
    if (next_code == (1 << width) - 1 && width < 12) ++width;
  }
  *produced = o;
  return true;
}

}  // namespace

extern "C" {

// ABI history: v2 zlib-strategy arg + fused PNG encode; v3 per-block
// codec dispatch; v4 JPEG entropy-scan decoder (jpeg_scan.cc); v5 the
// device deflate's dynamic-Huffman plan (ompb_dynamic_plan_batch)
int ompb_version() { return 5; }

int ompb_pool_size() { return static_cast<int>(Pool().size()); }

void ompb_free(void* p) { std::free(p); }

void ompb_free_batch(void** ptrs, int n) {
  for (int i = 0; i < n; ++i) std::free(ptrs[i]);
}

// N independent zlib-format compressions in parallel.
// outputs[i] is malloc'd; caller frees via ompb_free_batch.
// Returns 0 on success, else the first failing lane index + 1.
int ompb_deflate_batch(int n, const uint8_t** inputs, const size_t* in_lens,
                       int level, uint8_t** outputs, size_t* out_lens) {
  std::atomic<int> failed{0};
  ParallelFor(static_cast<size_t>(n), [&](size_t i) {
    if (!DeflateOne(inputs[i], in_lens[i], level, &outputs[i], &out_lens[i])) {
      outputs[i] = nullptr;
      out_lens[i] = 0;
      int expected = 0;
      failed.compare_exchange_strong(expected, static_cast<int>(i) + 1);
    }
  });
  return failed.load();
}

// N independent zlib-format decompressions into caller-owned buffers
// (numpy arrays); out_lens[i] holds capacity on entry, actual size on
// return. Returns 0 on success, else first failing lane index + 1.
int ompb_inflate_batch(int n, const uint8_t** inputs, const size_t* in_lens,
                       uint8_t** outputs, size_t* out_lens) {
  std::atomic<int> failed{0};
  ParallelFor(static_cast<size_t>(n), [&](size_t i) {
    uLongf dst_len = out_lens[i];
    int rc = uncompress(outputs[i], &dst_len, inputs[i],
                        static_cast<uLong>(in_lens[i]));
    if (rc != Z_OK) {
      out_lens[i] = 0;
      int expected = 0;
      failed.compare_exchange_strong(expected, static_cast<int>(i) + 1);
    } else {
      out_lens[i] = dst_len;
    }
  });
  return failed.load();
}

// N compressed TIFF blocks -> caller-owned buffers, with a per-block
// codec code: 8 = zlib/deflate, 5 = TIFF LZW (early change), 32773 =
// PackBits. Mirrors the per-block codec dispatch Bio-Formats does
// inside ome.io.nio readers (TileRequestHandler.java:104-112 is the
// consumer). out_lens[i] carries capacity in, decoded length out;
// a failed lane reports out_lens[i] = 0 (per-lane degradation).
int ompb_decode_batch(int n, const uint8_t** inputs, const size_t* in_lens,
                      const int* codecs, uint8_t** outputs,
                      size_t* out_lens) {
  std::atomic<int> failed{0};
  ParallelFor(static_cast<size_t>(n), [&](size_t i) {
    const uint8_t* in = inputs[i];
    const size_t in_len = in_lens[i];
    uint8_t* out = outputs[i];
    const size_t cap = out_lens[i];
    bool ok = false;
    size_t produced = 0;
    switch (codecs[i]) {
      case 8: {
        uLongf dst_len = cap;
        ok = uncompress(out, &dst_len, in, static_cast<uLong>(in_len)) ==
             Z_OK;
        produced = dst_len;
        break;
      }
      case 32773:
        ok = PackBitsDecode(in, in_len, out, cap, &produced);
        break;
      case 5:
        ok = LzwDecode(in, in_len, out, cap, &produced);
        break;
      default:
        ok = false;
    }
    if (!ok) {
      out_lens[i] = 0;
      int expected = 0;
      failed.compare_exchange_strong(expected, static_cast<int>(i) + 1);
    } else {
      out_lens[i] = produced;
    }
  });
  return failed.load();
}

// N complete PNG streams from already-filtered scanlines (filter byte
// + row bytes per row, the device kernel's output layout).
// widths/heights/bit_depths/color_types are per-lane; outputs malloc'd.
// Returns 0 on success, else first failing lane index + 1.
// `strategy` is the zlib strategy code (0 default, 1 filtered,
// 2 huffman-only, 3 RLE). On PNG-filtered scanlines of microscopy-like
// data, RLE matches level-6/filtered's ratio at ~5x the speed.
int ompb_png_assemble_batch(int n, const uint8_t** filtered,
                            const size_t* filtered_lens, const uint32_t* widths,
                            const uint32_t* heights, const uint8_t* bit_depths,
                            const uint8_t* color_types, int level, int strategy,
                            uint8_t** outputs, size_t* out_lens) {
  std::atomic<int> failed{0};
  ParallelFor(static_cast<size_t>(n), [&](size_t i) {
    auto fail = [&] {
      outputs[i] = nullptr;
      out_lens[i] = 0;
      int expected = 0;
      failed.compare_exchange_strong(expected, static_cast<int>(i) + 1);
    };
    uint8_t* idat = nullptr;
    size_t idat_len = 0;
    if (!DeflateOne(filtered[i], filtered_lens[i], level, &idat, &idat_len,
                    strategy)) {
      fail();
      return;
    }
    size_t total = 0;
    uint8_t* out = AssemblePng(idat, idat_len, widths[i], heights[i],
                               bit_depths[i], color_types[i], &total);
    std::free(idat);
    if (!out) {
      fail();
      return;
    }
    outputs[i] = out;
    out_lens[i] = total;
  });
  return failed.load();
}

// N raw tiles -> N complete PNG streams, fused: big-endian byteswap +
// scanline filter (0=none, 1=sub, 2=up) + deflate (Z_FILTERED) + chunk
// framing, one pass per lane on the pool. Tiles are native-endian
// contiguous (height x width x channels) arrays of `itemsize`-byte
// samples — the shape the pixel readers hand back — so the Python side
// passes numpy pointers with zero staging copies.
// Returns 0 on success, else first failing lane index + 1.
int ompb_png_encode_batch(int n, const uint8_t** tiles,
                          const uint32_t* widths, const uint32_t* heights,
                          const uint8_t* channels, const uint8_t* itemsizes,
                          int filter, int level, int strategy, int swap_to_be,
                          uint8_t** outputs, size_t* out_lens) {
  std::atomic<int> failed{0};
  ParallelFor(static_cast<size_t>(n), [&](size_t i) {
    auto fail = [&] {
      outputs[i] = nullptr;
      out_lens[i] = 0;
      int expected = 0;
      failed.compare_exchange_strong(expected, static_cast<int>(i) + 1);
    };
    const size_t w = widths[i], h = heights[i];
    const size_t ch = channels[i], isz = itemsizes[i];
    const size_t row_bytes = w * ch * isz;
    const size_t bpp = ch * isz;  // PNG filter unit
    uint8_t* filtered =
        static_cast<uint8_t*>(std::malloc(h * (1 + row_bytes)));
    // two scratch rows (current/previous, big-endian) for the filters
    uint8_t* scratch = static_cast<uint8_t*>(std::malloc(2 * row_bytes));
    if (!filtered || !scratch) {
      std::free(filtered);
      std::free(scratch);
      fail();
      return;
    }
    uint8_t* cur = scratch;
    uint8_t* prev = scratch + row_bytes;
    std::memset(prev, 0, row_bytes);
    for (size_t r = 0; r < h; ++r) {
      const uint8_t* src = tiles[i] + r * row_bytes;
      if (swap_to_be) {
        SwapRowBE(src, cur, w * ch, isz);
      } else {
        std::memcpy(cur, src, row_bytes);
      }
      uint8_t* dst = filtered + r * (1 + row_bytes);
      dst[0] = static_cast<uint8_t>(filter);
      switch (filter) {
        case 0:  // none
          std::memcpy(dst + 1, cur, row_bytes);
          break;
        case 1:  // sub
          std::memcpy(dst + 1, cur, bpp);
          for (size_t b = bpp; b < row_bytes; ++b) {
            dst[1 + b] = static_cast<uint8_t>(cur[b] - cur[b - bpp]);
          }
          break;
        default:  // 2 = up
          for (size_t b = 0; b < row_bytes; ++b) {
            dst[1 + b] = static_cast<uint8_t>(cur[b] - prev[b]);
          }
          break;
      }
      std::swap(cur, prev);
    }
    uint8_t* idat = nullptr;
    size_t idat_len = 0;
    bool ok = DeflateOne(filtered, h * (1 + row_bytes), level, &idat,
                         &idat_len, strategy);
    std::free(filtered);
    std::free(scratch);
    if (!ok) {
      fail();
      return;
    }
    size_t total = 0;
    uint8_t* out =
        AssemblePng(idat, idat_len, widths[i], heights[i],
                    static_cast<uint8_t>(isz * 8),
                    ch == 3 ? 2 : 0, &total);
    std::free(idat);
    if (!out) {
      fail();
      return;
    }
    outputs[i] = out;
    out_lens[i] = total;
  });
  return failed.load();
}

// The dynamic-Huffman plan of the first `real` lanes of a device group,
// into the caller's (numpy) emit tables, which hold the fixed tables on
// entry: a lane whose dynamic code wins gets its rows rewritten, any
// other lane (and every lane from `real` on) keeps them. counts is
// (lanes, 286), extras (lanes,); hdr_b/hdr_n are (lanes, hdr_cap),
// lit_* (lanes, 256), ml_* (lanes, 259), eob_* (lanes,). Runs on the
// calling thread (tens of microseconds a lane), not the pool, so a plan
// never queues behind host encodes.
void ompb_dynamic_plan_batch(int real, int hdr_cap, const int64_t* counts,
                             const int64_t* extras, uint32_t* hdr_b,
                             int32_t* hdr_n, uint32_t* lit_b, int32_t* lit_n,
                             uint32_t* ml_b, int32_t* ml_n, uint32_t* eob_b,
                             int32_t* eob_n) {
  constexpr int kLit = 286, kCodes = 256, kLens = 259;
  for (int i = 0; i < real; ++i) {
    const size_t h = static_cast<size_t>(i) * hdr_cap;
    ompb::DynamicPlanLane(counts + static_cast<size_t>(i) * kLit, extras[i],
                          hdr_cap, hdr_b + h, hdr_n + h,
                          lit_b + i * kCodes, lit_n + i * kCodes,
                          ml_b + i * kLens, ml_n + i * kLens, eob_b + i,
                          eob_n + i);
  }
}

}  // extern "C"
