"""ctypes bindings for the native C++ encode/IO engine.

The reference's byte-level hot work (Bio-Formats in-memory encode,
TileRequestHandler.java:176-199; per-block codec work inside
ome.io.nio readers) runs on JVM threads. Here it runs in
``native/libompb_native.so``: a C++ thread pool doing batched
deflate / inflate / PNG assembly, entered via ctypes (which drops the
GIL), so codec bytes never serialize behind the interpreter.

The library is built on demand from ``native/`` with ``make`` (g++ +
zlib only). Every caller must handle ``get_engine() is None`` and fall
back to the pure-Python path — the service stays correct without a
toolchain, just slower.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
import zlib
from typing import List, Optional, Sequence

import numpy as np

log = logging.getLogger("omero_ms_pixel_buffer_tpu.native")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "build", "libompb_native.so")

_U8P = ctypes.POINTER(ctypes.c_uint8)

_PNG_FILTER_CODES = {"none": 0, "sub": 1, "up": 2}

# zlib strategy codes (zlib.h) plus 100 = the in-house RLE+dynamic-
# Huffman encoder (native/fast_deflate.cc), which matches Z_RLE ratios
# on PNG-filtered microscopy data at a fraction of the cost — the
# service default
ZLIB_STRATEGIES = {
    "default": 0, "filtered": 1, "huffman": 2, "rle": 3, "fixed": 4,
    "fast": 100,
}


def _build_library() -> bool:
    """Compile the library if sources exist and a toolchain is around."""
    if not os.path.exists(os.path.join(_NATIVE_DIR, "Makefile")):
        return False
    try:
        proc = subprocess.run(
            ["make", "-C", _NATIVE_DIR],
            capture_output=True,
            timeout=120,
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warning("native build unavailable: %s", e)
        return False
    if proc.returncode != 0:
        log.warning(
            "native build failed:\n%s", proc.stderr.decode(errors="replace")
        )
        return False
    return os.path.exists(_LIB_PATH)


class NativeEngine:
    """Thin, typed wrapper over the C API. Thread-safe (the C side has
    its own pool; per-call state is stack-local)."""

    def __init__(self, lib: ctypes.CDLL):
        self._lib = lib
        lib.ompb_version.restype = ctypes.c_int
        lib.ompb_pool_size.restype = ctypes.c_int
        lib.ompb_free_batch.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_int,
        ]
        lib.ompb_deflate_batch.restype = ctypes.c_int
        lib.ompb_inflate_batch.restype = ctypes.c_int
        lib.ompb_png_assemble_batch.restype = ctypes.c_int
        self.version = lib.ompb_version()
        # ABI v2 added the zlib-strategy argument and the fused encode
        # entry point; a stale v1 .so (prebuilt deploy without sources
        # to trigger the mtime rebuild) must get v1-shaped calls.
        self._has_fused_encode = self.version >= 2 and hasattr(
            lib, "ompb_png_encode_batch"
        )
        if self._has_fused_encode:
            lib.ompb_png_encode_batch.restype = ctypes.c_int
        # ABI v3 added the per-block codec dispatch (zlib/LZW/PackBits)
        self._has_decode_batch = self.version >= 3 and hasattr(
            lib, "ompb_decode_batch"
        )
        if self._has_decode_batch:
            lib.ompb_decode_batch.restype = ctypes.c_int
        # ABI v4 added the JPEG entropy-scan decoder + crc32c
        self.has_jpeg_scan = self.version >= 4 and hasattr(
            lib, "ompb_jpeg_scan"
        )
        if self.has_jpeg_scan:
            lib.ompb_jpeg_scan.restype = ctypes.c_int
        self.has_crc32c = hasattr(lib, "ompb_crc32c")
        if self.has_crc32c:
            lib.ompb_crc32c.restype = ctypes.c_uint32
        # ABI v5 added the device deflate's dynamic-Huffman plan
        self.has_dynamic_plan = self.version >= 5 and hasattr(
            lib, "ompb_dynamic_plan_batch"
        )
        if self.has_dynamic_plan:
            lib.ompb_dynamic_plan_batch.restype = None
            lib.ompb_dynamic_plan_batch.argtypes = (
                [ctypes.c_int] * 2 + [ctypes.c_void_p] * 10
            )
        self.pool_size = lib.ompb_pool_size()

    # -- helpers -----------------------------------------------------------

    @staticmethod
    def _in_arrays(buffers: Sequence[bytes]):
        n = len(buffers)
        ins = (_U8P * n)()
        lens = (ctypes.c_size_t * n)()
        # zero-copy: point at the immutable bytes objects' own storage;
        # `keep` pins them (and the c_char_p views) for the call
        keep = []
        for i, b in enumerate(buffers):
            view = ctypes.c_char_p(b)
            keep.append((b, view))
            ins[i] = ctypes.cast(view, _U8P)
            lens[i] = len(b)
        return ins, lens, keep

    def _collect(self, outs, out_lens, n: int) -> List[Optional[bytes]]:
        results: List[Optional[bytes]] = []
        try:
            for i in range(n):
                if outs[i]:
                    results.append(
                        ctypes.string_at(outs[i], out_lens[i])
                    )
                else:
                    results.append(None)
        finally:
            self._lib.ompb_free_batch(
                ctypes.cast(outs, ctypes.POINTER(ctypes.c_void_p)),
                ctypes.c_int(n),
            )
        return results

    # -- API ---------------------------------------------------------------

    def deflate_batch(
        self, buffers: Sequence[bytes], level: int = 6
    ) -> List[Optional[bytes]]:
        """zlib-compress N buffers on the native pool; None per failed
        lane."""
        n = len(buffers)
        if n == 0:
            return []
        ins, lens, _keep = self._in_arrays(buffers)
        outs = (_U8P * n)()
        out_lens = (ctypes.c_size_t * n)()
        self._lib.ompb_deflate_batch(
            ctypes.c_int(n), ins, lens, ctypes.c_int(level), outs, out_lens
        )
        return self._collect(outs, out_lens, n)

    def inflate_batch(
        self,
        buffers: Sequence[bytes],
        out_sizes: Sequence[int],
    ) -> List[Optional[np.ndarray]]:
        """zlib-decompress N blocks into fresh numpy uint8 arrays of the
        given capacities (decompressed tile sizes are known from the
        storage layout). None per failed lane; arrays are trimmed to
        the actual decompressed length."""
        n = len(buffers)
        if n == 0:
            return []
        ins, lens, _keep = self._in_arrays(buffers)
        outs = (_U8P * n)()
        out_lens = (ctypes.c_size_t * n)()
        arrays = []
        for i, size in enumerate(out_sizes):
            arr = np.empty(int(size), dtype=np.uint8)
            arrays.append(arr)
            outs[i] = arr.ctypes.data_as(_U8P)
            out_lens[i] = int(size)
        rc = self._lib.ompb_inflate_batch(
            ctypes.c_int(n), ins, lens, outs, out_lens
        )
        results: List[Optional[np.ndarray]] = []
        for i, arr in enumerate(arrays):
            if rc and out_lens[i] == 0:
                results.append(None)
            else:
                results.append(arr[: out_lens[i]])
        return results

    def decode_batch(
        self,
        buffers: Sequence[bytes],
        out_sizes: Sequence[int],
        codecs: Sequence[int],
    ) -> List[Optional[np.ndarray]]:
        """Decode N TIFF blocks with per-block codec dispatch (8 =
        zlib, 5 = LZW, 32773 = PackBits) into fresh uint8 arrays of the
        given capacities. None per failed lane. Falls back to the
        pure-Python codecs on an ABI-v2 library."""
        n = len(buffers)
        if n == 0:
            return []
        if not self._has_decode_batch:
            if all(c == 8 for c in codecs):
                return self.inflate_batch(buffers, out_sizes)
            from ..ops import codecs as py

            results: List[Optional[np.ndarray]] = []
            for buf, size, codec in zip(buffers, out_sizes, codecs):
                try:
                    if codec == 8:
                        # bounded like the native uncompress path — a
                        # hostile stream must not balloon past `size`
                        raw: Optional[bytes] = py.bounded_inflate(
                            buf, int(size)
                        )
                    elif codec == py.LZW:
                        raw = py.lzw_decode(buf, int(size))
                    elif codec == py.PACKBITS:
                        raw = py.packbits_decode(buf, int(size))
                    else:
                        raw = None
                except Exception:
                    raw = None
                results.append(
                    None if raw is None
                    else np.frombuffer(raw, dtype=np.uint8)
                )
            return results
        ins, lens, _keep = self._in_arrays(buffers)
        outs = (_U8P * n)()
        out_lens = (ctypes.c_size_t * n)()
        codec_arr = (ctypes.c_int * n)(*[int(c) for c in codecs])
        arrays = []
        for i, size in enumerate(out_sizes):
            arr = np.empty(int(size), dtype=np.uint8)
            arrays.append(arr)
            outs[i] = arr.ctypes.data_as(_U8P)
            out_lens[i] = int(size)
        rc = self._lib.ompb_decode_batch(
            ctypes.c_int(n), ins, lens, codec_arr, outs, out_lens
        )
        results = []
        for i, arr in enumerate(arrays):
            if rc and out_lens[i] == 0:
                results.append(None)
            else:
                results.append(arr[: out_lens[i]])
        return results

    def crc32c(self, data: bytes) -> int:
        """CRC-32C over ``data`` (zarr v3 checksum codec)."""
        return int(
            self._lib.ompb_crc32c(data, ctypes.c_size_t(len(data)))
        )

    def dynamic_plan_batch(
        self, counts: np.ndarray, extras: np.ndarray, real: int, tables
    ) -> None:
        """The dynamic-Huffman plan of the first ``real`` lanes, written
        into ``tables`` — the eight emit arrays ``build_dynamic_tables``
        allocates and prefills with the fixed code — in ONE
        GIL-released call. Requires ``has_dynamic_plan``."""
        cap = tables[0].shape[1]  # header tokens a lane
        rows = [(cap,), (cap,), (256,), (256,), (259,), (259,), (), ()]
        for k, (arr, row) in enumerate(zip(tables, rows)):
            # C writes through these pointers: a wrong layout would
            # corrupt the heap, so it is a hard error, never an assert
            if (
                arr.dtype != (np.uint32 if k % 2 == 0 else np.int32)
                or not arr.flags["C_CONTIGUOUS"]
                or arr.shape[1:] != row or arr.shape[0] < real
            ):
                raise ValueError(f"plan table {k} of the wrong layout")
        counts = np.ascontiguousarray(counts[:real], dtype=np.int64)
        extras = np.ascontiguousarray(extras[:real], dtype=np.int64)
        if counts.shape != (real, 286) or extras.shape != (real,):
            raise ValueError("plan counts of the wrong shape")
        self._lib.ompb_dynamic_plan_batch(
            real, cap, counts.ctypes.data, extras.ctypes.data,
            *(a.ctypes.data for a in tables),
        )

    def jpeg_scan(
        self,
        scan: bytes,
        seg_offsets: Sequence[int],
        seg_mcu_ranges: Sequence[tuple],
        mcux: int,
        comp_h: Sequence[int],
        comp_v: Sequence[int],
        comp_bw: Sequence[int],
        dc_luts: Sequence[tuple],
        ac_luts: Sequence[tuple],
        out_blocks: Sequence[np.ndarray],
    ) -> int:
        """Baseline JPEG entropy scan (io/jpeg's byte-serial half) over
        destuffed restart segments; fills the caller's zeroed int32
        (nblocks, 64) coefficient arrays in natural order. LUTs are
        the 16-bit-peek (sym, nbits) pairs io/jpeg builds. Returns the
        C error code (0 = ok); the GIL is released for the walk."""
        if not self.has_jpeg_scan:
            return -100
        ncomp = len(comp_h)
        n_segs = len(seg_offsets)
        offs = (ctypes.c_int64 * n_segs)(*seg_offsets)
        m0 = (ctypes.c_int32 * n_segs)(
            *[a for a, _ in seg_mcu_ranges]
        )
        m1 = (ctypes.c_int32 * n_segs)(
            *[b for _, b in seg_mcu_ranges]
        )
        ch = (ctypes.c_int32 * ncomp)(*comp_h)
        cv = (ctypes.c_int32 * ncomp)(*comp_v)
        cbw = (ctypes.c_int32 * ncomp)(*comp_bw)

        def lut_ptrs(luts, idx):
            arr = (_U8P * ncomp)()
            for i, pair in enumerate(luts):
                arr[i] = pair[idx].ctypes.data_as(_U8P)
            return arr

        i32p = ctypes.POINTER(ctypes.c_int32)
        outs = (i32p * ncomp)()
        for i, blocks in enumerate(out_blocks):
            if (
                blocks.dtype != np.int32
                or not blocks.flags["C_CONTIGUOUS"]
            ):
                # a bad array here means C writes through wrong strides
                # (heap corruption) — hard error, never an assert
                raise ValueError(
                    "jpeg_scan out_blocks must be C-contiguous int32"
                )
            outs[i] = blocks.ctypes.data_as(i32p)
        return self._lib.ompb_jpeg_scan(
            scan, ctypes.c_size_t(len(scan)), offs,
            ctypes.c_int(n_segs), m0, m1, ctypes.c_int(mcux),
            ctypes.c_int(ncomp), ch, cv, cbw,
            lut_ptrs(dc_luts, 0), lut_ptrs(dc_luts, 1),
            lut_ptrs(ac_luts, 0), lut_ptrs(ac_luts, 1), outs,
        )

    def png_assemble_batch(
        self,
        filtered: Sequence[bytes],
        widths: Sequence[int],
        heights: Sequence[int],
        bit_depths: Sequence[int],
        color_types: Sequence[int],
        level: int = 6,
        strategy: str = "rle",
    ) -> List[Optional[bytes]]:
        """N filtered scanline buffers -> N complete PNG streams."""
        n = len(filtered)
        if n == 0:
            return []
        ins, lens, _keep = self._in_arrays(filtered)
        outs = (_U8P * n)()
        out_lens = (ctypes.c_size_t * n)()
        args = [
            ctypes.c_int(n), ins, lens,
            (ctypes.c_uint32 * n)(*[int(w) for w in widths]),
            (ctypes.c_uint32 * n)(*[int(h) for h in heights]),
            (ctypes.c_uint8 * n)(*[int(b) for b in bit_depths]),
            (ctypes.c_uint8 * n)(*[int(c) for c in color_types]),
            ctypes.c_int(level),
        ]
        if self.version >= 2:  # v1 ABI has no strategy argument
            args.append(ctypes.c_int(ZLIB_STRATEGIES.get(strategy, 0)))
        args += [outs, out_lens]
        self._lib.ompb_png_assemble_batch(*args)
        return self._collect(outs, out_lens, n)

    def png_encode_batch(
        self,
        tiles: Sequence[np.ndarray],
        filter_mode: str = "up",
        level: int = 6,
        strategy: str = "rle",
    ) -> Optional[List[Optional[bytes]]]:
        """Fused host encode: N raw tiles (2D grayscale or HxWx3 RGB,
        u8/u16) -> N complete PNGs in ONE GIL-released native call —
        byteswap + filter + deflate + framing with no numpy
        temporaries. Returns None when the loaded library or the inputs
        aren't eligible (caller falls back to the split
        filter/assemble path)."""
        if (
            not self._has_fused_encode
            or filter_mode not in _PNG_FILTER_CODES
        ):
            return None
        n = len(tiles)
        if n == 0:
            return []
        widths = (ctypes.c_uint32 * n)()
        heights = (ctypes.c_uint32 * n)()
        channels = (ctypes.c_uint8 * n)()
        itemsizes = (ctypes.c_uint8 * n)()
        ins = (_U8P * n)()
        keep = []
        for i, t in enumerate(tiles):
            if t.ndim == 2:
                ch = 1
            elif t.ndim == 3 and t.shape[2] == 3:
                ch = 3
            else:
                return None
            if t.dtype.itemsize not in (1, 2):
                return None
            if t.dtype.byteorder == ">":
                # the C side assumes native little-endian input and
                # swaps to PNG big-endian itself
                t = t.astype(t.dtype.newbyteorder("<"))
            arr = np.ascontiguousarray(t)
            keep.append(arr)
            ins[i] = arr.ctypes.data_as(_U8P)
            heights[i], widths[i] = arr.shape[0], arr.shape[1]
            channels[i], itemsizes[i] = ch, arr.dtype.itemsize
        outs = (_U8P * n)()
        out_lens = (ctypes.c_size_t * n)()
        self._lib.ompb_png_encode_batch(
            ctypes.c_int(n), ins, widths, heights, channels, itemsizes,
            ctypes.c_int(_PNG_FILTER_CODES[filter_mode]),
            ctypes.c_int(level),
            ctypes.c_int(ZLIB_STRATEGIES.get(strategy, 0)),
            ctypes.c_int(1),  # numpy arrays are native little-endian
            outs, out_lens,
        )
        return self._collect(outs, out_lens, n)


_engine: Optional[NativeEngine] = None
_engine_failed = False
_engine_lock = threading.Lock()


def get_engine() -> Optional[NativeEngine]:
    """The process-wide native engine, building/loading it on first use;
    None when the library can't be built (pure-Python fallback)."""
    global _engine, _engine_failed
    if _engine is not None or _engine_failed:
        return _engine
    with _engine_lock:
        if _engine is not None or _engine_failed:
            return _engine
        if os.environ.get("OMPB_DISABLE_NATIVE"):
            _engine_failed = True
            return None
        try:
            if not os.path.exists(_LIB_PATH) and not _build_library():
                _engine_failed = True
                return None
            # rebuild stale library (any source newer than the .so)
            sources = [
                os.path.join(_NATIVE_DIR, f)
                for f in ("ompb_native.cc", "fast_deflate.cc",
                          "jpeg_scan.cc", "fast_deflate.h")
            ]
            stale = any(
                os.path.exists(src)
                and os.path.getmtime(src) > os.path.getmtime(_LIB_PATH)
                for src in sources
            )
            if stale and not _build_library():
                _engine_failed = True
                return None
            _engine = NativeEngine(ctypes.CDLL(_LIB_PATH))
            log.info(
                "native engine v%d loaded (%d threads)",
                _engine.version, _engine.pool_size,
            )
        except OSError as e:
            log.warning("native engine unavailable: %s", e)
            _engine_failed = True
    return _engine
