"""The OME-TIFF writer: blocks encoded on a thread pool and streamed to
the file give the bytes of the serial writer, and a BigTIFF whose tile
offsets lie past 2**32 reads back."""

import hashlib
import os
import struct
import threading

import numpy as np
import pytest

from omero_ms_pixel_buffer_tpu.io import ometiff
from omero_ms_pixel_buffer_tpu.io.ometiff import (
    OmeTiffPixelBuffer,
    write_ome_tiff,
)


def _image(shape=(1, 3, 2, 150, 201), dtype=np.uint16):
    rng = np.random.default_rng(2147485301)
    base = rng.standard_normal(shape) * 120.0 + 2000.0
    return np.clip(base, 0, np.iinfo(dtype).max).astype(dtype)


def _sha(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


TILED = dict(tile_size=(64, 64), pyramid_levels=2)
CASES = {
    "classic-zlib": dict(compression="zlib", **TILED),
    "bigtiff-zlib": dict(compression="zlib", bigtiff=True, **TILED),
    "classic-none": dict(compression=None, **TILED),
    "bigtiff-none": dict(compression=None, bigtiff=True, **TILED),
    "bigtiff-zlib-predictor2": dict(
        compression="zlib", predictor=2, tile_size=(64, 64), bigtiff=True),
    "classic-strips-little-endian": dict(
        compression="zlib", tile_size=None, big_endian=False),
    "classic-packbits": dict(compression="packbits", **TILED),
}
# sha256 of what the one-thread, whole-file-in-memory writer this one
# replaced wrote for the same input (no codec: no library version in
# the bytes)
BEFORE = {
    "classic-none":
        "b2c3461acd648a8aa6500498826a7d3b5a9fd6210033a5a5a6caba3302322cf0",
    "bigtiff-none":
        "840543d7af8b7b939127d3f9b7937d89c8ffcc26fb08df0891967653f97ebdb1",
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_parallel_bytes_are_the_serial_writers(tmp_path, name):
    data = _image()
    serial = str(tmp_path / "serial.tiff")
    write_ome_tiff(serial, data, workers=1, **CASES[name])
    for workers in (3, None):
        parallel = str(tmp_path / f"parallel-{workers}.tiff")
        write_ome_tiff(parallel, data, workers=workers, **CASES[name])
        assert _sha(parallel) == _sha(serial)
    if name in BEFORE:
        assert _sha(serial) == BEFORE[name]
    buf = OmeTiffPixelBuffer(serial)
    try:
        for z in range(2):
            for c in range(3):
                np.testing.assert_array_equal(
                    buf.get_tile_at(0, z, c, 0, 0, 0, 201, 150),
                    data[0, c, z],
                )
    finally:
        buf.close()


def test_an_unknown_codec_name_is_refused_before_a_byte_is_written(tmp_path):
    path = str(tmp_path / "a.tiff")
    with pytest.raises(KeyError):
        write_ome_tiff(path, _image(), compression="gzip", **TILED)
    assert not os.path.exists(path)


def test_blocks_in_flight_are_bounded(tmp_path, monkeypatch):
    """The writer holds a few blocks beyond its input, never the file:
    with 2 workers at most 8 encoded blocks wait to be written."""
    waiting, most, lock = [0], [0], threading.Lock()
    real = ometiff.zlib.compress

    def counted(raw, level):
        out = real(raw, level)
        with lock:
            waiting[0] += 1
            most[0] = max(most[0], waiting[0])
        return out

    class Sink:
        def __init__(self, f):
            self._f = f

        def write(self, raw):
            if len(raw) > 256:  # a block, not an IFD field
                with lock:
                    waiting[0] -= 1
            return self._f.write(raw)

        def __getattr__(self, name):
            return getattr(self._f, name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return self._f.__exit__(*exc)

    real_open = open
    monkeypatch.setattr(ometiff.zlib, "compress", counted)
    monkeypatch.setattr(
        ometiff, "open", lambda *a, **k: Sink(real_open(*a, **k)),
        raising=False,
    )
    data = _image((1, 1, 1, 1024, 1024))
    path = str(tmp_path / "bounded.tiff")
    write_ome_tiff(
        path, data, tile_size=(64, 64), compression="zlib", workers=2)
    assert 1 <= most[0] <= 8
    monkeypatch.undo()
    serial = str(tmp_path / "serial.tiff")
    write_ome_tiff(
        serial, data, tile_size=(64, 64), compression="zlib", workers=1)
    assert _sha(path) == _sha(serial)


def test_a_failing_block_fails_the_write(tmp_path, monkeypatch):
    calls = [0]
    real = ometiff.zlib.compress

    def flaky(raw, level):
        calls[0] += 1
        if calls[0] == 5:
            raise MemoryError("no room")
        return real(raw, level)

    monkeypatch.setattr(ometiff.zlib, "compress", flaky)
    with pytest.raises(MemoryError):
        write_ome_tiff(
            str(tmp_path / "x.tiff"), _image(), compression="zlib",
            workers=4, **TILED)


def _ifds(raw: bytes):
    """[(ifd offset, {tag: (type, count, position of its value field)})]
    of a big-endian BigTIFF's main chain."""
    assert raw[:4] == b"MM\x00+"
    out, (at,) = [], struct.unpack_from(">Q", raw, 8)
    while at:
        (n,) = struct.unpack_from(">Q", raw, at)
        tags = {}
        for k in range(n):
            entry = at + 8 + 20 * k
            tag, typ, count = struct.unpack_from(">HHQ", raw, entry)
            tags[tag] = (typ, count, entry + 12)
        out.append((at, tags))
        (at,) = struct.unpack_from(">Q", raw, at + 8 + 20 * n)
    return out


def test_bigtiff_tiles_past_4gib_read_back(tmp_path):
    """A sparse file: the written BigTIFF, a hole up to 2**32, then a
    copy of every tile, with the TileOffsets arrays patched to the
    copies. Nothing near 4 GiB is written."""
    data = _image((1, 2, 2, 200, 260))
    small = str(tmp_path / "small.tiff")
    write_ome_tiff(
        small, data, tile_size=(64, 64), compression="zlib", bigtiff=True)
    with open(small, "rb") as f:
        raw = bytearray(f.read())
    far = str(tmp_path / "far.tiff")
    at = (1 << 32) + 4096
    moved = 0
    with open(far, "wb") as f:
        for _, tags in _ifds(bytes(raw)):
            typ, count, field = tags[324]  # TileOffsets
            assert typ == 16 and count == 20  # LONG8, out of line
            (offsets_at,) = struct.unpack_from(">Q", raw, field)
            (counts_at,) = struct.unpack_from(">Q", raw, tags[325][2])
            for k in range(count):
                (off,) = struct.unpack_from(">Q", raw, offsets_at + 8 * k)
                (cnt,) = struct.unpack_from(">Q", raw, counts_at + 8 * k)
                f.seek(at)
                f.write(raw[off : off + cnt])
                raw[off : off + cnt] = b"\xff" * cnt  # the old copy dies
                struct.pack_into(">Q", raw, offsets_at + 8 * k, at)
                at += cnt + (cnt % 2)
                moved += 1
        f.seek(0)
        f.write(raw)
    assert moved == 4 * 20
    if os.stat(far).st_blocks * 512 > (64 << 20):
        os.remove(far)
        pytest.skip("this filesystem keeps no holes")
    buf = OmeTiffPixelBuffer(far)
    try:
        for ifd in buf.ifds:
            assert min(ifd.values("TILE_OFFSETS")) > 1 << 32
        for z in range(2):
            for c in range(2):
                # the many-block read (batched decode) and a tile's
                np.testing.assert_array_equal(
                    buf.get_tile_at(0, z, c, 0, 0, 0, 260, 200),
                    data[0, c, z],
                )
                np.testing.assert_array_equal(
                    buf.get_tile_at(0, z, c, 0, 70, 130, 64, 64),
                    data[0, c, z, 130:194, 70:134],
                )
        tiles = buf.read_tiles(
            [(z, c, 0, 64, 0, 128, 128) for z in range(2) for c in range(2)])
        for tile, (z, c) in zip(tiles, [(0, 0), (0, 1), (1, 0), (1, 1)]):
            np.testing.assert_array_equal(tile, data[0, c, z, :128, 64:192])
    finally:
        buf.close()
