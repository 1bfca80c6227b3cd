"""OME-TIFF pixel buffer (reader + writer), pyramid-aware.

Replaces the Bio-Formats-backed side of ``ome.io.nio.PixelsService``
(reference usage: TileRequestHandler.java:201-211): resolve an OME-TIFF
on disk to a random-access, resolution-aware tile reader.

Layout understood/produced:

- classic multi-page TIFF, planes ordered XYCZT (C fastest — the
  dimension order the reference's createMetadata declares,
  TileRequestHandler.java:158);
- per-plane pyramid levels in SubIFDs (tag 330), 2x downsampled — the
  layout Bio-Formats writes for pyramidal OME-TIFF;
- tiled (TileWidth/TileLength) or stripped storage; compression none
  or zlib/deflate (8); big- or little-endian;
- OME-XML in the first IFD's ImageDescription carrying SizeX/Y/Z/C/T
  and Type (used for dimensions; falls back to page counting).

Self-contained: no tifffile/Bio-Formats in the environment, and the
tile hot path wants direct (offset, bytecount) access per on-disk tile
so reads can be chunk-aligned and batched (SURVEY.md §7 step 3).
"""

from __future__ import annotations

import base64
import concurrent.futures
import hashlib
import logging
import mmap
import os
import json
import re
import struct
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from .pixel_buffer import (
    BlockCache,
    PixelBuffer,
    PixelsMeta,
    check_bounds,
)
from ..ops import codecs as _codecs
from ..ops.convert import dtype_for, omero_type_for

_T = {"WIDTH": 256, "LENGTH": 257, "BITS": 258, "COMPRESSION": 259,
      "PHOTOMETRIC": 262, "DESCRIPTION": 270, "STRIP_OFFSETS": 273,
      "SAMPLES": 277, "ROWS_PER_STRIP": 278, "STRIP_COUNTS": 279,
      "PREDICTOR": 317, "TILE_WIDTH": 322, "TILE_LENGTH": 323,
      "TILE_OFFSETS": 324, "TILE_COUNTS": 325, "SUB_IFDS": 330,
      "SAMPLE_FORMAT": 339, "JPEG_TABLES": 347}

# TIFF compression codes this reader serves (TileRequestHandler.java:
# 104-112 reads them through Bio-Formats): 1 none, 5 LZW,
# 7 new-style JPEG (baseline, incl. abbreviated streams with tag 347),
# 8 deflate, 32773 PackBits, 50000 zstd (the libtiff/Bio-Formats
# registered code).
_SUPPORTED_COMPRESSIONS = (1, 5, 7, 8, 32773, 50000)

# codecs the native batch decoder does NOT handle; their blocks decode
# in-tree on the Python side of the batched read
_PYTHON_SIDE_CODECS = (7, 50000)

# a single-region read of at least this many compressed blocks goes
# through the batched decode
_BATCH_DECODE_BLOCKS = 16

_TYPE_SIZES = {1: 1, 2: 1, 3: 2, 4: 4, 5: 8, 6: 1, 7: 1, 8: 2, 9: 4,
               10: 8, 11: 4, 12: 8, 16: 8, 17: 8, 18: 8}
_TYPE_FMT = {1: "B", 3: "H", 4: "I", 16: "Q"}

import collections  # noqa: E402

# Classic vs BigTIFF structural layout, shared by reader and writer:
# entry-count field format/width, IFD entry width, inline-value width,
# offset format, and the TIFF type used for offset/count arrays.
_Flavor = collections.namedtuple(
    "_Flavor", "cnt_fmt cnt_len entry_len inline off_fmt off_typ"
)
_TIFF_FLAVORS = {
    False: _Flavor("H", 2, 12, 4, "I", 4),    # classic, magic 42
    True: _Flavor("Q", 8, 20, 8, "Q", 16),    # BigTIFF, magic 43
}


class TiffError(ValueError):
    pass


class _Ifd:
    """One parsed IFD: tag dict + lazy pixel access."""

    def __init__(self, tags: Dict[int, list]):
        self.tags = tags

    def first(self, tag: str, default=None):
        v = self.tags.get(_T[tag])
        return v[0] if v else default

    def values(self, tag: str) -> list:
        return self.tags.get(_T[tag], [])

    @property
    def width(self) -> int:
        return self.first("WIDTH")

    @property
    def height(self) -> int:
        return self.first("LENGTH")

    @property
    def tiled(self) -> bool:
        return _T["TILE_OFFSETS"] in self.tags


def _parse_ifds(data: bytes) -> Tuple[str, List[_Ifd]]:
    """Parse the main IFD chain plus SubIFD chains; returns (byteorder,
    flat list of main IFDs with their .sub_ifds attached)."""
    if data[:2] == b"II":
        bo = "<"
    elif data[:2] == b"MM":
        bo = ">"
    else:
        raise TiffError("Not a TIFF file")
    try:
        return _parse_ifds_inner(data, bo)
    except (struct.error, IndexError, MemoryError, OverflowError) as e:
        raise TiffError(f"Corrupt TIFF structure: {e}") from None


def _parse_ifds_inner(data, bo: str) -> Tuple[str, List[_Ifd]]:
    """Classic TIFF (magic 42, 32-bit offsets, 12-byte entries) and
    BigTIFF (magic 43, 64-bit offsets, 20-byte entries — whole-slide
    pyramids routinely exceed classic TIFF's 4 GB address space)."""
    (magic,) = struct.unpack(bo + "H", data[2:4])
    if magic == 42:
        big = False
        (first_off,) = struct.unpack(bo + "I", data[4:8])
    elif magic == 43:
        big = True
        offsize, reserved = struct.unpack(bo + "HH", data[4:8])
        if offsize != 8 or reserved != 0:
            raise TiffError("Malformed BigTIFF header")
        (first_off,) = struct.unpack(bo + "Q", data[8:16])
    else:
        raise TiffError(f"Unknown TIFF magic: {magic}")

    fl = _TIFF_FLAVORS[big]

    def parse_one(off: int) -> Tuple[_Ifd, int]:
        (n,) = struct.unpack(bo + fl.cnt_fmt, data[off : off + fl.cnt_len])
        if n > 65536:  # corrupt 64-bit entry count must not spin
            raise TiffError(f"IFD claims {n} entries")
        tags: Dict[int, list] = {}
        for i in range(n):
            eo = off + fl.cnt_len + fl.entry_len * i
            tag, typ = struct.unpack(bo + "HH", data[eo : eo + 4])
            (count,) = struct.unpack(
                bo + fl.off_fmt, data[eo + 4 : eo + 4 + fl.inline]
            )
            size = _TYPE_SIZES.get(typ, 1) * count
            if size > len(data):
                # a (corrupt) 64-bit count must never drive allocation
                raise TiffError(
                    f"Tag {tag} claims {size} value bytes in a "
                    f"{len(data)}-byte file"
                )
            val_off = eo + 4 + fl.inline
            raw = data[val_off : val_off + fl.inline]
            if size > fl.inline:
                (ptr,) = struct.unpack(bo + fl.off_fmt, raw)
                raw = data[ptr : ptr + size]
            else:
                raw = raw[:size]
            if typ in _TYPE_FMT:
                # repeat-count form allocates O(1) and bounds-checks
                tags[tag] = list(
                    struct.unpack(bo + f"{count}{_TYPE_FMT[typ]}", raw)
                )
            elif typ == 2:  # ASCII
                tags[tag] = [raw.rstrip(b"\x00").decode("utf-8", "replace")]
            elif typ == 7:  # UNDEFINED: opaque bytes (e.g. JPEGTables)
                tags[tag] = [bytes(raw)]
        nxt_off = off + fl.cnt_len + fl.entry_len * n
        (nxt,) = struct.unpack(
            bo + fl.off_fmt, data[nxt_off : nxt_off + fl.inline]
        )
        return _Ifd(tags), nxt

    ifds: List[_Ifd] = []
    off = first_off
    while off:
        ifd, off = parse_one(off)
        subs = []
        for so in ifd.values("SUB_IFDS"):
            sub, _ = parse_one(so)
            subs.append(sub)
        ifd.sub_ifds = subs  # type: ignore[attr-defined]
        ifds.append(ifd)
        if len(ifds) > 1_000_000:
            raise TiffError("IFD chain too long")
    return bo, ifds


_OME_RE = {
    k: re.compile(rf'{k}="([^"]+)"')
    for k in ("SizeX", "SizeY", "SizeZ", "SizeC", "SizeT", "Type",
              "DimensionOrder")
}


def _parse_ome(desc: str) -> Optional[dict]:
    if "OME" not in desc or "Pixels" not in desc:
        return None
    out = {}
    for k, rx in _OME_RE.items():
        m = rx.search(desc)
        if m:
            out[k] = m.group(1)
    return out or None


_reader_log = logging.getLogger("omero_ms_pixel_buffer_tpu.io.ometiff")
_pure_lzw_warned = False


def _warn_pure_python_lzw_once() -> None:
    """The sequential read path inflates LZW in pure Python; without
    the native engine that is a seconds-per-tile cliff an operator
    should hear about exactly once (batched reads use the native pool
    when it exists)."""
    global _pure_lzw_warned
    if _pure_lzw_warned:
        return
    from ..runtime.native import get_engine

    if get_engine() is None:
        _pure_lzw_warned = True
        _reader_log.warning(
            "serving LZW-compressed TIFF with the pure-Python decoder "
            "(native engine unavailable) — expect seconds-per-tile "
            "latency; check the native build (OMPB_DISABLE_NATIVE, "
            "g++ availability)"
        )
    else:
        # native exists: the batched path uses it; stay quiet but do
        # not re-check per block
        _pure_lzw_warned = True


class _LevelReader:
    """Random tile access within one IFD (one plane at one level).

    Block access is split into *plan* (which on-disk blocks a region
    touches, with spans and decoded capacities) and *assemble* (crop
    decoded block bytes into the output array), so batched callers can
    decode many blocks at once — on the native engine's thread pool —
    across every tile/plane in a coalesced request batch.
    """

    def __init__(
        self, fh, bo: str, ifd: _Ifd, dtype: np.dtype, samples: int,
        cache: Optional[BlockCache] = None, cache_ns: int = 0,
    ):
        self.fh = fh
        self.bo = bo
        self.ifd = ifd
        self.dtype = dtype.newbyteorder(bo)
        self.samples = samples
        self.cache = cache
        self.cache_ns = cache_ns
        self.compression = ifd.first("COMPRESSION", 1)
        if self.compression not in _SUPPORTED_COMPRESSIONS:
            raise TiffError(f"Unsupported compression: {self.compression}")
        self.predictor = ifd.first("PREDICTOR", 1)
        if self.predictor not in (1, 2):
            raise TiffError(f"Unsupported predictor: {self.predictor}")
        self._jpeg_tables = None  # parsed lazily from tag 347
        if self.compression == 7:
            if self.predictor == 2:
                raise TiffError("predictor 2 is invalid with JPEG")
            if dtype != np.dtype(np.uint8):
                raise TiffError("JPEG-in-TIFF requires 8-bit samples")
        if self.compression == 50000:
            try:  # fail fast, not per block as "corrupt"
                import zstandard  # noqa: F401
            except ImportError:  # pragma: no cover
                raise TiffError(
                    "zstd-compressed TIFF requires the zstandard "
                    "package"
                ) from None

    def decode_zstd_block(self, raw, cap: int) -> Optional[bytes]:
        """One zstd block (compression 50000) -> raw bytes truly
        bounded at the block capacity (ops/codecs.bounded_zstd — the
        shared declared-size check), or None when corrupt."""
        return _codecs.bounded_zstd(bytes(raw), cap)

    def decode_jpeg_block(self, raw: bytes) -> Optional[np.ndarray]:
        """One JPEG block (compression 7) -> flat uint8 pixel bytes at
        the block's decoded capacity, or None when corrupt. Tables
        from tag 347 (abbreviated streams) seed the decoder; tile
        streams smaller than the block pad bottom/right."""
        from .jpeg import JpegError, decode_jpeg, parse_tables

        if self._jpeg_tables is None:
            # cache the parsed tables on the long-lived _Ifd (readers
            # are per-request; rebuilding the 16-bit Huffman LUTs per
            # tile would waste the hot path)
            cached = getattr(self.ifd, "_jpeg_tables_cache", None)
            if cached is not None:
                self._jpeg_tables = cached
            else:
                blobs = self.ifd.values("JPEG_TABLES")
                if blobs and isinstance(blobs[0], (bytes, bytearray)):
                    self._jpeg_tables = parse_tables(bytes(blobs[0]))
                elif blobs:  # written as BYTE values (ints)
                    self._jpeg_tables = parse_tables(bytes(blobs))
                else:
                    self._jpeg_tables = False  # standalone streams
                self.ifd._jpeg_tables_cache = self._jpeg_tables
        tables = self._jpeg_tables or None
        # photometric 6 (YCbCr) converts; 2 means components are RGB
        ycbcr = self.ifd.first("PHOTOMETRIC", 6) != 2
        ifd = self.ifd
        if ifd.tiled:
            cap_px = ifd.first("TILE_WIDTH") * ifd.first("TILE_LENGTH")
        else:
            cap_px = ifd.width * min(
                ifd.first("ROWS_PER_STRIP", ifd.height), ifd.height
            )
        try:
            pixels = decode_jpeg(
                bytes(raw), tables=tables, ycbcr=ycbcr,
                # SOF dims may not exceed the block: a hostile stream
                # must not size the coefficient buffers
                max_pixels=cap_px,
            )
        except JpegError:
            return None
        if pixels.ndim == 2:
            pixels = pixels[:, :, None]
        if pixels.shape[2] != self.samples:
            return None
        if ifd.tiled:
            bw, bh = ifd.first("TILE_WIDTH"), ifd.first("TILE_LENGTH")
        else:
            bw = ifd.width
            bh = min(ifd.first("ROWS_PER_STRIP", ifd.height), ifd.height)
        if pixels.shape[0] > bh or pixels.shape[1] > bw:
            pixels = pixels[:bh, :bw]
        if pixels.shape[:2] != (bh, bw):
            padded = np.zeros((bh, bw, self.samples), np.uint8)
            padded[: pixels.shape[0], : pixels.shape[1]] = pixels
            pixels = padded
        return np.ascontiguousarray(pixels).reshape(-1)

    @property
    def compressed(self) -> bool:
        return self.compression != 1

    def row_samples(self) -> int:
        """Samples per decoded-block row (tile width or image width)."""
        ifd = self.ifd
        width = ifd.first("TILE_WIDTH") if ifd.tiled else ifd.width
        return width * self.samples

    def postprocess(self, arr: np.ndarray) -> np.ndarray:
        """Undo the horizontal-differencing predictor (tag 317 = 2) on
        freshly decoded block bytes. Cached blocks are post-predictor."""
        if self.predictor != 2 or not self.compressed:
            return arr
        rs = self.row_samples()
        row_bytes = rs * self.dtype.itemsize
        usable = (len(arr) // row_bytes) * row_bytes
        return _codecs.undo_predictor2(
            arr[:usable], rs, self.dtype.itemsize, self.samples,
            self.bo,
        )

    # -- block planning ----------------------------------------------------

    def plan_region(self, x: int, y: int, w: int, h: int) -> List[int]:
        """Indices of the on-disk blocks (tiles or strips) the region
        touches."""
        ifd = self.ifd
        W, H = ifd.width, ifd.height
        if ifd.tiled:
            tw, th = ifd.first("TILE_WIDTH"), ifd.first("TILE_LENGTH")
            tiles_across = (W + tw - 1) // tw
            return [
                ty * tiles_across + tx
                for ty in range(y // th, (y + h - 1) // th + 1)
                for tx in range(x // tw, (x + w - 1) // tw + 1)
            ]
        rps = ifd.first("ROWS_PER_STRIP", H)
        return list(range(y // rps, (y + h - 1) // rps + 1))

    def block_span(self, i: int) -> Tuple[int, int, int]:
        """(file offset, byte count, decoded capacity) for block i."""
        ifd = self.ifd
        itemsize = self.dtype.itemsize
        S = self.samples
        if ifd.tiled:
            tw, th = ifd.first("TILE_WIDTH"), ifd.first("TILE_LENGTH")
            cap = th * tw * S * itemsize
            offs, cnts = ifd.values("TILE_OFFSETS"), ifd.values("TILE_COUNTS")
        else:
            H = ifd.height
            rps = ifd.first("ROWS_PER_STRIP", H)
            rows_here = min(rps, H - i * rps)
            cap = rows_here * ifd.width * S * itemsize
            offs, cnts = ifd.values("STRIP_OFFSETS"), ifd.values("STRIP_COUNTS")
        return offs[i], cnts[i], cap

    def _read_block(self, i: int):
        # decoded-block LRU: inflating a source chunk is the dominant
        # read cost; pay it once per chunk, not once per overlapping
        # tile request (uncompressed blocks are mmap slices — cheap)
        key = (self.cache_ns, id(self.ifd), i)
        if self.cache is not None and self.compressed:
            cached = self.cache.get(key)
            if cached is not None:
                return cached
        offset, count, cap = self.block_span(i)
        raw = self.fh[offset : offset + count]
        if not self.compressed:
            return raw
        if self.compression == 8:
            # bounded at the block capacity (hostile-stream defence)
            plain: Optional[bytes] = _codecs.bounded_inflate(
                bytes(raw), cap
            )
        elif self.compression == 5:
            _warn_pure_python_lzw_once()
            plain = _codecs.lzw_decode(bytes(raw), cap)
        elif self.compression == 7:
            decoded_jpeg = self.decode_jpeg_block(raw)
            if decoded_jpeg is None:
                raise TiffError(f"Corrupt JPEG block {i}")
            if self.cache is not None:
                self.cache[key] = decoded_jpeg
            return decoded_jpeg
        elif self.compression == 50000:
            plain = self.decode_zstd_block(raw, cap)
        else:  # 32773
            plain = _codecs.packbits_decode(bytes(raw), cap)
        if plain is None:
            raise TiffError(
                f"Corrupt block {i} (compression {self.compression})"
            )
        decoded = self.postprocess(
            np.frombuffer(plain, dtype=np.uint8)
        )
        if self.cache is not None:
            self.cache[key] = decoded
        return decoded

    # -- assembly ----------------------------------------------------------

    def read_region(
        self, x: int, y: int, w: int, h: int, get_block=None
    ) -> np.ndarray:
        """Crop the region from decoded blocks. ``get_block(i)`` supplies
        decoded block bytes (defaults to inline mmap read + inflate)."""
        if get_block is None:
            get_block = self._read_block
        ifd = self.ifd
        W, H = ifd.width, ifd.height
        S = self.samples
        shape = (h, w, S) if S > 1 else (h, w)
        out = np.zeros(shape, dtype=self.dtype.newbyteorder("="))
        if ifd.tiled:
            tw, th = ifd.first("TILE_WIDTH"), ifd.first("TILE_LENGTH")
            tiles_across = (W + tw - 1) // tw
            for ty in range(y // th, (y + h - 1) // th + 1):
                for tx in range(x // tw, (x + w - 1) // tw + 1):
                    ti = ty * tiles_across + tx
                    raw = get_block(ti)
                    shape_t = (th, tw, S) if S > 1 else (th, tw)
                    tile = np.frombuffer(raw, dtype=self.dtype)[
                        : th * tw * S
                    ].reshape(shape_t)
                    y0, x0 = ty * th, tx * tw
                    lo_y, hi_y = max(y, y0), min(y + h, y0 + th, H)
                    lo_x, hi_x = max(x, x0), min(x + w, x0 + tw, W)
                    if hi_y <= lo_y or hi_x <= lo_x:
                        continue
                    out[lo_y - y : hi_y - y, lo_x - x : hi_x - x] = tile[
                        lo_y - y0 : hi_y - y0, lo_x - x0 : hi_x - x0
                    ]
        else:
            rps = ifd.first("ROWS_PER_STRIP", H)
            for si in range(y // rps, (y + h - 1) // rps + 1):
                raw = get_block(si)
                rows_here = min(rps, H - si * rps)
                shape_s = (rows_here, W, S) if S > 1 else (rows_here, W)
                strip = np.frombuffer(raw, dtype=self.dtype)[
                    : rows_here * W * S
                ].reshape(shape_s)
                y0 = si * rps
                lo_y, hi_y = max(y, y0), min(y + h, y0 + rows_here)
                if hi_y <= lo_y:
                    continue
                out[lo_y - y : hi_y - y, :] = strip[
                    lo_y - y0 : hi_y - y0, x : x + w
                ]
        return out


_memo_log = logging.getLogger("omero_ms_pixel_buffer_tpu.io.memoizer")


def _memo_key(path: str) -> str:
    # stable per-path name (rewrites overwrite rather than orphan);
    # freshness is validated from the stamp saved inside the memo
    return hashlib.sha256(os.path.abspath(path).encode()).hexdigest()


def _memo_stamp(path: str):
    st = os.stat(path)
    return (st.st_mtime_ns, st.st_size)


_MEMO_BYTES_MARKER = "\x00b64:"  # NUL prefix: impossible in TIFF ASCII


def _memo_tags_to_json(tags: Dict[int, list]) -> dict:
    out: dict = {}
    for k, v in tags.items():
        out[str(k)] = [
            _MEMO_BYTES_MARKER + base64.b64encode(item).decode()
            if isinstance(item, (bytes, bytearray)) else item
            for item in v
        ]
    return out


def _memo_tags_from_json(obj: dict) -> Dict[int, list]:
    tags: Dict[int, list] = {}
    for k, v in obj.items():
        if not isinstance(v, list):
            raise ValueError("tag values must be lists")
        vals = []
        for item in v:
            if isinstance(item, str) and item.startswith(
                _MEMO_BYTES_MARKER
            ):
                vals.append(
                    base64.b64decode(item[len(_MEMO_BYTES_MARKER):])
                )
            elif isinstance(item, (int, str)):
                vals.append(item)
            else:
                raise ValueError("tag values must be int/str")
        tags[int(k)] = vals
    return tags


def _memo_load(path: str, memo_dir: str):
    """(byteorder, ifds) from the memo cache, or None. The memo dir is
    service-owned state (like the Bio-Formats Memoizer's .bfmemo
    files); a memo whose recorded mtime/size don't match the file is
    stale and ignored. The format is JSON, not pickle: loading a memo
    must never execute code, even if the memo dir is writable by
    others (same posture as auth/django.py's non-resolving unpickler).
    """
    memo = os.path.join(memo_dir, _memo_key(path) + ".ifd.json")
    try:
        with open(memo, "rb") as f:
            doc = json.load(f)
        # v2: v1 memos were written by a parser that dropped type-7
        # (UNDEFINED) tags, losing JPEGTables (347) — accepting one
        # would permanently break JPEG decode for that file
        if doc.get("v") != 2 or tuple(doc["stamp"]) != _memo_stamp(path):
            return None  # image was rewritten (or format drifted)
        bo = doc["bo"]
        if bo not in ("<", ">"):
            return None
        ifds = []
        for entry in doc["ifds"]:
            ifd = _Ifd(_memo_tags_from_json(entry["tags"]))
            ifd.sub_ifds = [
                _Ifd(_memo_tags_from_json(t)) for t in entry["sub"]
            ]
            ifds.append(ifd)
        return bo, ifds
    except Exception:
        # any malformed/foreign memo (shape drift across releases,
        # torn writes) must degrade to a reparse, never an open error
        return None


def _memo_save(path: str, memo_dir: str, bo: str, ifds) -> None:
    try:
        os.makedirs(memo_dir, mode=0o700, exist_ok=True)
        doc = {
            "v": 2,
            "stamp": list(_memo_stamp(path)),
            "bo": bo,
            "ifds": [
                {
                    "tags": _memo_tags_to_json(ifd.tags),
                    "sub": [
                        _memo_tags_to_json(s.tags)
                        for s in getattr(ifd, "sub_ifds", [])
                    ],
                }
                for ifd in ifds
            ],
        }
        memo = os.path.join(memo_dir, _memo_key(path) + ".ifd.json")
        # unique tmp per writer (two threads can race the first open
        # of one image); os.replace keeps publication atomic
        import tempfile

        fd, tmp = tempfile.mkstemp(dir=memo_dir, suffix=".tmp")
        try:
            with os.fdopen(fd, "w", encoding="utf-8") as f:
                json.dump(doc, f, separators=(",", ":"))
            os.replace(tmp, memo)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
    except OSError as e:
        _memo_log.debug("memo save failed for %s: %s", path, e)


class OmeTiffPixelBuffer(PixelBuffer):
    """OME-TIFF (optionally pyramidal) as a PixelBuffer.

    ``memo_dir`` enables the Bio-Formats-Memoizer-style persistent
    metadata cache (SURVEY.md §5.4): the parsed IFD chain is saved as JSON
    next to first use, so re-opening a large pyramid after a restart
    skips the full-structure walk (the reference's memoizer wait bean,
    beanRefContext.xml:20-22).
    """

    def __init__(
        self, path: str, image_id: int = 0, image_name: str = "",
        cache_bytes: Optional[int] = None,
        block_cache: Optional[BlockCache] = None,
        memo_dir: Optional[str] = None,
    ):
        self.path = path
        self.memo_dir = memo_dir or os.environ.get("OMPB_MEMO_DIR")
        # shared (service-owned, process-bounded) or private cache
        self.block_cache = (
            block_cache if block_cache is not None else BlockCache(cache_bytes)
        )
        self._file = open(path, "rb")
        try:
            # mmap: IFD parse and tile reads never copy the whole file
            self.mm = mmap.mmap(
                self._file.fileno(), 0, access=mmap.ACCESS_READ
            )
            try:
                self._init_from_mmap(image_id, image_name)
            except BaseException:
                self.mm.close()
                raise
        except BaseException:
            self._file.close()
            raise

    def _init_from_mmap(self, image_id: int, image_name: str) -> None:
        loaded = (
            _memo_load(self.path, self.memo_dir) if self.memo_dir else None
        )
        if loaded is not None:
            self.bo, self.ifds = loaded
        else:
            self.bo, self.ifds = _parse_ifds(self.mm)
            if self.memo_dir:
                _memo_save(self.path, self.memo_dir, self.bo, self.ifds)
        if not self.ifds:
            raise TiffError(f"No IFDs in {self.path}")
        first = self.ifds[0]
        bits = first.first("BITS", 8)
        samples = first.first("SAMPLES", 1)
        fmt = first.first("SAMPLE_FORMAT", 1)
        kind = {1: "u", 2: "i", 3: "f"}[fmt]
        base_dtype = np.dtype(f"{kind}{bits // 8}")
        self.samples = samples

        ome = _parse_ome(first.first("DESCRIPTION", "") or "")
        if ome and "Type" in ome:
            ptype = ome["Type"]
        else:
            ptype = omero_type_for(base_dtype)
        sz = int(ome["SizeZ"]) if ome and "SizeZ" in ome else 1
        sc = int(ome["SizeC"]) if ome and "SizeC" in ome else 1
        st = int(ome["SizeT"]) if ome and "SizeT" in ome else 1
        self.dim_order = (ome or {}).get("DimensionOrder", "XYCZT")
        # OMERO models RGB as SizeC=3 with per-channel reads; an
        # interleaved TIFF stores those channels inside the samples of
        # one page. When the page count reconciles that way, requests
        # for channel c slice sample c out of the shared page.
        self._channels_per_plane = 1
        if (
            samples > 1 and sc % samples == 0
            and sz * (sc // samples) * st == len(self.ifds)
        ):
            self._channels_per_plane = samples
            n_planes = len(self.ifds)
        elif sz * sc * st > len(self.ifds):
            # metadata lies — fall back to page count as plane count
            n_planes = len(self.ifds)
            sz, sc, st = 1, 1, n_planes
        else:
            n_planes = sz * sc * st
        self.n_planes = n_planes

        meta = PixelsMeta(
            image_id=image_id,
            size_x=first.width, size_y=first.height,
            size_z=sz, size_c=sc, size_t=st,
            pixels_type=ptype,
            image_name=image_name or os.path.basename(self.path),
        )
        super().__init__(meta)
        self._base_dtype = dtype_for(ptype)

    # plane index for XYCZT-family orders (X/Y always first two)
    def _plane_index(self, z: int, c: int, t: int) -> int:
        m = self.meta
        s = self._channels_per_plane
        order = self.dim_order[2:]  # e.g. "CZT"
        dims = {
            "Z": (z, m.size_z),
            "C": (c // s, max(1, m.size_c // s)),
            "T": (t, m.size_t),
        }
        idx, stride = 0, 1
        for d in order:
            val, size = dims[d]
            idx += val * stride
            stride *= size
        return idx

    @property
    def resolution_levels(self) -> int:
        return 1 + len(getattr(self.ifds[0], "sub_ifds", []))

    def level_size(self, level: Optional[int] = None) -> Tuple[int, int]:
        lv = self._resolution_level if level is None else level
        ifd = self.ifds[0] if lv == 0 else self.ifds[0].sub_ifds[lv - 1]
        return ifd.width, ifd.height

    def _level_ifd(self, plane: int, level: int) -> _Ifd:
        main = self.ifds[plane]
        return main if level == 0 else main.sub_ifds[level - 1]

    def _reader_for(self, z, c, t, x, y, w, h, level) -> _LevelReader:
        m = self.meta
        if not 0 <= level < self.resolution_levels:
            raise ValueError(
                f"Resolution level {level} out of range "
                f"[0, {self.resolution_levels})"
            )
        sx, sy = self.level_size(level)
        check_bounds(z, c, t, x, y, w, h, sx, sy, m.size_z, m.size_c, m.size_t)
        plane = self._plane_index(z, c, t)
        ifd = self._level_ifd(plane, level)
        return _LevelReader(
            self.mm, self.bo, ifd, self._base_dtype, self.samples,
            cache=self.block_cache, cache_ns=self.cache_ns,
        )

    def _extract_channel(self, region: np.ndarray, c: int) -> np.ndarray:
        if self._channels_per_plane > 1 and region.ndim == 3:
            return np.ascontiguousarray(
                region[:, :, c % self._channels_per_plane]
            )
        return region

    def get_tile_at(self, level, z, c, t, x, y, w, h) -> np.ndarray:
        reader = self._reader_for(z, c, t, x, y, w, h, level)
        if (
            reader.compressed
            and len(reader.plan_region(x, y, w, h)) >= _BATCH_DECODE_BLOCKS
        ):
            # a region of many blocks (a whole plane on its way to
            # HBM): decode them on the native pool, not one by one
            tile = self.read_tiles([(z, c, t, x, y, w, h)], level)[0]
            if tile is not None:
                return tile
        return self._extract_channel(reader.read_region(x, y, w, h), c)

    def read_tiles(self, coords, level: int = 0):
        """Batched read: every compressed block any requested tile
        touches — across tiles AND planes (the cross-Z coalescing axis,
        SURVEY.md §5.7) — is deduplicated and inflated in ONE native
        thread-pool call, then tiles are assembled from the decoded
        blocks. Falls back to the sequential path without the native
        engine or for uncompressed storage."""
        from ..runtime.native import get_engine

        engine = get_engine()
        readers = [
            self._reader_for(z, c, t, x, y, w, h, level)
            for (z, c, t, x, y, w, h) in coords
        ]
        # regions assembled once per (page, rect) and shared across the
        # channel lanes of one composite request (tiles are read-only
        # downstream); channels slice out of the shared region
        regions: Dict[Tuple, np.ndarray] = {}

        def assemble(r, c, x, y, w, h, get_block=None):
            rk = (id(r.ifd), x, y, w, h)
            region = regions.get(rk)
            if region is None:
                region = r.read_region(x, y, w, h, get_block=get_block)
                regions[rk] = region
            return self._extract_channel(region, c)

        if engine is None or not any(r.compressed for r in readers):
            return [
                assemble(r, c, x, y, w, h)
                for r, (_, c, _, x, y, w, h) in zip(readers, coords)
            ]

        # plan: dedup compressed blocks across the whole batch, serving
        # already-decoded blocks from the persistent LRU; each span
        # remembers its codec and owning reader (for the predictor)
        cache = {}
        spans: Dict[Tuple, Tuple[int, int, int, int, object]] = {}
        for r, (_, _, _, x, y, w, h) in zip(readers, coords):
            if not r.compressed:
                continue
            ifd_key = id(r.ifd)
            for bi in r.plan_region(x, y, w, h):
                key = (self.cache_ns, ifd_key, bi)
                if key in cache or key in spans:
                    continue
                hit = self.block_cache.get(key)
                if hit is not None:
                    cache[key] = hit
                else:
                    off, cnt, cap = r.block_span(bi)
                    spans[key] = (off, cnt, cap, r.compression, r)

        # JPEG (7) and zstd (50000) blocks decode in-tree; the other
        # codecs batch onto the native pool
        keys = [
            k for k in spans if spans[k][3] not in _PYTHON_SIDE_CODECS
        ]
        raws = [
            bytes(self.mm[off : off + cnt])
            for (off, cnt, _, _, _) in (spans[k] for k in keys)
        ]
        caps = [spans[k][2] for k in keys]
        codecs = [spans[k][3] for k in keys]
        decoded = engine.decode_batch(raws, caps, codecs)
        for key, arr in zip(keys, decoded):
            if arr is None:  # corrupt block: fail only the lanes that
                # touch it (per-lane degradation, not batch-wide)
                continue
            arr = spans[key][4].postprocess(arr)
            cache[key] = arr
            self.block_cache[key] = arr
        for key, (off, cnt, cap, codec, reader) in spans.items():
            if codec not in _PYTHON_SIDE_CODECS:
                continue
            if codec == 7:
                arr = reader.decode_jpeg_block(self.mm[off : off + cnt])
            else:  # 50000 zstd
                plain = reader.decode_zstd_block(
                    self.mm[off : off + cnt], cap
                )
                arr = (
                    reader.postprocess(np.frombuffer(plain, np.uint8))
                    if plain is not None else None
                )
            if arr is None:
                continue
            cache[key] = arr
            self.block_cache[key] = arr

        out: List[Optional[np.ndarray]] = []
        for r, (_, c, _, x, y, w, h) in zip(readers, coords):
            if r.compressed:
                ifd_key = id(r.ifd)
                get_block = (  # noqa: E731
                    lambda i, _k=ifd_key: cache[(self.cache_ns, _k, i)]
                )
            else:
                get_block = None
            try:
                out.append(assemble(r, c, x, y, w, h, get_block=get_block))
            except KeyError:  # a needed block failed to inflate
                out.append(None)
        return out

    def close(self) -> None:
        self.mm.close()
        self._file.close()


# ---------------------------------------------------------------------------
# Writer
# ---------------------------------------------------------------------------


def write_ome_tiff(
    path: str,
    data: np.ndarray,
    tile_size: Optional[Tuple[int, int]] = (256, 256),
    pyramid_levels: int = 1,
    compression: Optional[str] = None,  # None|zlib|lzw|packbits|jpeg|zstd
    big_endian: bool = True,
    bigtiff: bool = False,
    predictor: int = 1,  # 2 = horizontal differencing (zlib/lzw/zstd)
    jpeg_quality: int = 90,
    jpeg_subsampling: int = 0,  # 0=4:4:4, 1=4:2:2, 2=4:2:0
    workers: Optional[int] = None,
) -> None:
    """Write 5D TCZYX (or 6D TCZYXS for RGB, S=3) data as a (pyramidal)
    OME-TIFF: planes in XYCZT page order, pyramid levels as SubIFDs,
    tiled storage. ``bigtiff`` emits the 64-bit-offset layout
    (magic 43) used by whole-slide pyramids past 4 GB.

    Blocks are cut, byte-ordered and compressed on ``workers`` threads
    (default: one per core; zlib, zstd and numpy release the GIL) and
    streamed to the file in the serial order, a bounded number in
    flight: the bytes are those of ``workers=1``, and the writer holds
    a few blocks beyond its input, never a copy of the image.
    """
    if data.ndim == 6:
        if data.shape[5] != 3:
            raise TiffError("6D input must be TCZYXS with S=3 (RGB)")
    elif data.ndim != 5:
        raise TiffError("write_ome_tiff expects TCZYX(S) data")
    T, C, Z, Y, X = data.shape[:5]
    bo = ">" if big_endian else "<"
    dtype = data.dtype
    be_dtype = dtype.newbyteorder(bo)
    comp_code = {
        None: 1, "zlib": 8, "lzw": 5, "packbits": 32773, "jpeg": 7,
        "zstd": 50000,
    }[compression]
    if predictor not in (1, 2):
        raise TiffError(f"Unsupported predictor: {predictor}")
    if predictor == 2 and comp_code in (1, 7, 32773):
        raise TiffError(
            "predictor 2 requires zlib, lzw, or zstd compression"
        )
    if comp_code == 7 and dtype != np.dtype(np.uint8):
        raise TiffError("JPEG compression requires uint8 samples")
    if workers is None:
        workers = min(32, os.cpu_count() or 1)
    # JPEG tile streams ship abbreviated: tables go once into tag 347
    # (the reference reads this form through Bio-Formats); all tiles
    # share one table set because quality/subsampling are constant
    jpeg_state: Dict[str, Optional[bytes]] = {"tables": None}
    kind_fmt = {"u": 1, "i": 2, "f": 3}[dtype.kind]

    samples = 3 if data.ndim == 6 else 1
    ome = (
        '<?xml version="1.0" encoding="UTF-8"?>'
        '<OME xmlns="http://www.openmicroscopy.org/Schemas/OME/2016-06">'
        '<Image ID="Image:0">'
        f'<Pixels ID="Pixels:0" DimensionOrder="XYCZT" '
        f'Type="{omero_type_for(dtype)}" '
        f'SizeX="{X}" SizeY="{Y}" SizeZ="{Z}" '
        f'SizeC="{C * samples}" SizeT="{T}" '
        f'BigEndian="{"true" if big_endian else "false"}">'
        + "".join(
            f'<Channel ID="Channel:0:{c}" SamplesPerPixel="{samples}"/>'
            for c in range(C)
        )
        + "<TiffData/></Pixels></Image></OME>"
    )

    fl = _TIFF_FLAVORS[bigtiff]
    cnt_fmt, cnt_len, entry_len = fl.cnt_fmt, fl.cnt_len, fl.entry_len
    inline, off_fmt, off_typ = fl.inline, fl.off_fmt, fl.off_typ

    def pack(fmt, *vals):
        return struct.pack(bo + fmt, *vals)

    def encode_block(raw: bytes, row_samples: int, nsamples: int) -> bytes:
        if comp_code == 7:
            from io import BytesIO

            from PIL import Image

            from .jpeg import split_tables

            width = row_samples // nsamples
            pixels = np.frombuffer(raw, np.uint8).reshape(
                -1, width, nsamples
            )
            img = Image.fromarray(
                pixels if nsamples == 3 else pixels[:, :, 0],
                "RGB" if nsamples == 3 else "L",
            )
            out = BytesIO()
            img.save(
                out, "JPEG", quality=jpeg_quality,
                subsampling=jpeg_subsampling if nsamples == 3 else -1,
            )
            tables, stripped = split_tables(out.getvalue())
            if jpeg_state["tables"] is None:
                jpeg_state["tables"] = tables
            return stripped
        if predictor == 2:
            arr = np.frombuffer(raw, dtype=np.uint8)
            raw = _codecs.apply_predictor2(
                arr, row_samples, dtype.itemsize, nsamples, bo
            ).tobytes()
        if comp_code == 8:
            return zlib.compress(raw, 1)
        if comp_code == 5:
            return _codecs.lzw_encode(raw)
        if comp_code == 50000:
            import zstandard

            return zstandard.ZstdCompressor(level=3).compress(raw)
        if comp_code == 32773:
            return _codecs.packbits_encode(
                raw, row_samples * dtype.itemsize
            )
        return raw

    def encode_tile(plane2d: np.ndarray, ty: int, tx: int) -> bytes:
        tw, th = tile_size
        nsamples = plane2d.shape[2] if plane2d.ndim == 3 else 1
        block = np.zeros((th, tw) + plane2d.shape[2:], dtype=be_dtype)
        sub = plane2d[ty : ty + th, tx : tx + tw]
        block[: sub.shape[0], : sub.shape[1]] = sub  # byte-orders too
        return encode_block(block.tobytes(), tw * nsamples, nsamples)

    def encode_strip(plane2d: np.ndarray) -> bytes:
        nsamples = plane2d.shape[2] if plane2d.ndim == 3 else 1
        return encode_block(
            plane2d.astype(be_dtype, copy=False).tobytes(),
            plane2d.shape[1] * nsamples, nsamples,
        )

    workers = max(workers, 1)
    pool = concurrent.futures.ThreadPoolExecutor(
        workers, thread_name_prefix="tiff-write"
    )
    in_flight = 4 * workers
    pos = 0  # the file position: everything below appends

    def append(raw: bytes) -> None:
        nonlocal pos
        out.write(raw)
        pos += len(raw)

    def pad_even() -> None:
        if pos % 2:
            append(b"\x00")

    def write_blocks(plane2d: np.ndarray):
        """Write tiles (or one strip) for a 2D/3D plane; returns
        (offsets, counts)."""
        offsets, counts = [], []
        if tile_size:
            tw, th = tile_size
            jobs = (
                (encode_tile, plane2d, ty, tx)
                for ty in range(0, plane2d.shape[0], th)
                for tx in range(0, plane2d.shape[1], tw)
            )
        else:
            jobs = iter([(encode_strip, plane2d)])

        def emit(raw: bytes) -> None:
            offsets.append(pos)
            counts.append(len(raw))
            append(raw)
            if tile_size and len(raw) % 2:
                append(b"\x00")

        pending: collections.deque = collections.deque()
        for fn, *args in jobs:
            pending.append(pool.submit(fn, *args))
            if len(pending) >= in_flight:
                emit(pending.popleft().result())
        while pending:
            emit(pending.popleft().result())
        return offsets, counts

    next_pointers = []  # file position of each main IFD's next pointer

    def build_ifd(plane2d, description=None, sub_ifd_offsets=None) -> int:
        """Append pixel data + IFD for one plane image; returns the IFD
        offset. The caller links it into a chain afterwards."""
        h, w = plane2d.shape[:2]
        samples = plane2d.shape[2] if plane2d.ndim == 3 else 1
        offsets, counts = write_blocks(plane2d)
        entries = []  # (tag, type, count, values|bytes)
        bits = dtype.itemsize * 8
        entries.append((_T["WIDTH"], 4, 1, [w]))
        entries.append((_T["LENGTH"], 4, 1, [h]))
        entries.append((_T["BITS"], 3, samples, [bits] * samples))
        entries.append((_T["COMPRESSION"], 3, 1, [comp_code]))
        if predictor == 2:
            entries.append((_T["PREDICTOR"], 3, 1, [2]))
        if comp_code == 7:
            # JPEG: 6 = YCbCr (the encoder's colorspace) for RGB
            entries.append(
                (_T["PHOTOMETRIC"], 3, 1, [6 if samples == 3 else 1])
            )
            if jpeg_state["tables"]:
                tbl = jpeg_state["tables"]
                entries.append((_T["JPEG_TABLES"], 7, len(tbl), tbl))
        else:
            entries.append(
                (_T["PHOTOMETRIC"], 3, 1, [2 if samples == 3 else 1])
            )
        if description:
            entries.append(
                (_T["DESCRIPTION"], 2, len(description) + 1,
                 description.encode() + b"\x00")
            )
        if tile_size:
            entries.append((_T["TILE_WIDTH"], 3, 1, [tile_size[0]]))
            entries.append((_T["TILE_LENGTH"], 3, 1, [tile_size[1]]))
            entries.append(
                (_T["TILE_OFFSETS"], off_typ, len(offsets), offsets)
            )
            entries.append(
                (_T["TILE_COUNTS"], off_typ, len(counts), counts)
            )
        else:
            entries.append(
                (_T["STRIP_OFFSETS"], off_typ, len(offsets), offsets)
            )
            entries.append((_T["ROWS_PER_STRIP"], 4, 1, [h]))
            entries.append(
                (_T["STRIP_COUNTS"], off_typ, len(counts), counts)
            )
        entries.append((_T["SAMPLES"], 3, 1, [samples]))
        entries.append((_T["SAMPLE_FORMAT"], 3, samples, [kind_fmt] * samples))
        if sub_ifd_offsets:
            entries.append(
                (_T["SUB_IFDS"], off_typ, len(sub_ifd_offsets),
                 sub_ifd_offsets)
            )
        entries.sort(key=lambda e: e[0])

        # out-of-line values first
        fields = []
        for tag, typ, count, values in entries:
            if typ in (2, 7):  # ASCII / UNDEFINED: raw bytes
                raw = values
            else:
                raw = struct.pack(
                    f"{bo}{len(values)}{_TYPE_FMT[typ]}", *values
                )
            if len(raw) <= inline:
                fields.append(raw + b"\x00" * (inline - len(raw)))
            else:
                pad_even()
                fields.append(pack(off_fmt, pos))
                append(raw)
        pad_even()
        ifd_off = pos
        table = [pack(cnt_fmt, len(entries))]
        for (tag, typ, count, _), field in zip(entries, fields):
            table.append(pack("HH", tag, typ) + pack(off_fmt, count) + field)
        append(b"".join(table))
        append(pack(off_fmt, 0))  # next pointer (patched at chaining)
        return ifd_off

    try:
        with open(path, "wb") as out:
            if bigtiff:
                append(b"MM\x00+" if big_endian else b"II+\x00")
                append(struct.pack(bo + "HH", 8, 0) + b"\x00" * 8)  # ifd0 @8
            else:
                append(
                    (b"MM\x00*" if big_endian else b"II*\x00")
                    + b"\x00" * 4
                )
            main_offsets = []
            first = True
            for t in range(T):
                for z in range(Z):
                    for c in range(C):  # XYCZT: C fastest
                        plane = data[t, c, z]
                        subs = []
                        level = plane
                        for _ in range(1, pyramid_levels):
                            level = level[::2, ::2]
                            subs.append(build_ifd(level))
                        main_offsets.append(
                            build_ifd(
                                plane,
                                description=ome if first else None,
                                sub_ifd_offsets=subs or None,
                            )
                        )
                        # the next pointer is the last field written
                        next_pointers.append(pos - struct.calcsize(off_fmt))
                        first = False

            # chain main IFDs: the header names the first, each one's
            # next pointer the one after it
            for at, target in zip(
                [8 if bigtiff else 4] + next_pointers, main_offsets
            ):
                out.seek(at)
                out.write(pack(off_fmt, target))
    finally:
        pool.shutdown(wait=True, cancel_futures=True)
