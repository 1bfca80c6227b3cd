"""On-device zlib streams (ops/device_deflate): the encode hot loop the
reference runs on a JVM worker thread (TileRequestHandler.java:176-199)
built entirely on the accelerator.

Correctness contract: ``zlib.decompress`` of every lane's stream equals
the input payload — any spec-valid stream is acceptable (clients only
decode), so tests pin decoded equality, not bytes. Runs on the CPU
backend (conftest); the same XLA program serves the TPU.
"""

import io
import os
import zlib

import numpy as np
import pytest
from PIL import Image

from omero_ms_pixel_buffer_tpu.ops.device_deflate import (
    deflate_filtered_batch,
    fused_filter_deflate_batch,
    max_stream_len,
    stored_stream_len,
    zlib_rle_batch,
    zlib_stored_batch,
)

rng = np.random.default_rng(41)


def _synth_tiles(b: int, h: int, w: int, seed: int) -> np.ndarray:
    """Microscopy-like uint16 content: a smooth field + sensor noise."""
    r = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    base = 2000 + 1500 * np.sin(xx / 97.0) + 1500 * np.cos(yy / 131.0)
    return (base[None] + r.normal(0, 120.0, (b, h, w))).clip(
        0, 65535
    ).astype(np.uint16)


def _synth_rgb_tiles(b: int, h: int, w: int, seed: int) -> np.ndarray:
    """Rendered-RGB-like content (three smooth composited channels +
    light noise, what /render emits after window/LUT compositing):
    far less run-heavy than raw greyscale planes, which is where the
    fixed-Huffman stream paid 1.38x of the host's bytes."""
    r = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    chans = [
        120 + 60 * np.sin(xx / fx + ph) + 50 * np.cos(yy / fy)
        for ph, (fx, fy) in enumerate(
            ((97.0, 131.0), (61.0, 89.0), (151.0, 47.0))
        )
    ]
    img = np.stack(chans, -1)[None] + r.normal(0, 6.0, (b, h, w, 3))
    return img.clip(0, 255).astype(np.uint8)


def _payload_families(n: int = 1500):
    """The payload shapes that break packers: runs, noise, no-runs,
    constants, run/match boundary tails."""
    return np.stack([
        np.zeros(n, np.uint8),
        rng.integers(0, 256, n).astype(np.uint8),
        np.repeat(rng.integers(0, 9, (n + 19) // 20), 20)[:n].astype(
            np.uint8
        ),
        np.tile(np.array([200, 201], np.uint8), (n + 1) // 2)[:n],
        np.full(n, 7, np.uint8),
    ])


def _roundtrip_rle(payloads: np.ndarray):
    streams, lengths = (
        np.asarray(a) for a in zlib_rle_batch(payloads)
    )
    assert streams.shape[1] == max_stream_len(payloads.shape[1])
    for lane, (stream, length) in enumerate(zip(streams, lengths)):
        assert 6 < length <= streams.shape[1]
        got = zlib.decompress(bytes(stream[:length]))
        assert got == payloads[lane].tobytes(), f"lane {lane}"
    return lengths


class TestRleStreams:
    def test_run_heavy_payload_compresses(self):
        # 20-byte runs: the Z_RLE sweet spot (Up-filtered microscopy
        # tiles look like this)
        payloads = np.repeat(
            rng.integers(0, 4, (3, 64)), 20, axis=1
        ).astype(np.uint8)
        lengths = _roundtrip_rle(payloads)
        assert (lengths < payloads.shape[1] // 2).all()

    def test_incompressible_payload_bounded(self):
        payloads = rng.integers(0, 256, (2, 4096)).astype(np.uint8)
        lengths = _roundtrip_rle(payloads)
        # all-literal worst case: 9 bits/byte + framing
        assert (lengths <= max_stream_len(4096)).all()

    def test_constant_payload(self):
        _roundtrip_rle(np.full((1, 100_000), 7, np.uint8))

    def test_alternating_no_runs(self):
        _roundtrip_rle(
            np.tile(np.array([1, 2], np.uint8), 2048)[None]
        )

    @pytest.mark.parametrize(
        "n",
        # run/match boundary cases: tails of 1-2 bytes after a match,
        # exact 258 chunks, one-past, tiny payloads
        [1, 2, 3, 4, 5, 257, 258, 259, 260, 261, 262, 516, 517, 518, 777],
    )
    def test_run_boundaries(self, n):
        _roundtrip_rle(np.zeros((1, n), np.uint8))
        _roundtrip_rle(rng.integers(0, 2, (1, n)).astype(np.uint8))

    def test_mixed_batch_lanes_independent(self):
        payloads = np.stack(
            [
                np.zeros(1500, np.uint8),
                rng.integers(0, 256, 1500).astype(np.uint8),
                np.repeat(rng.integers(0, 9, 75), 20).astype(np.uint8),
            ]
        )
        _roundtrip_rle(payloads)


class TestMinStreamSelection:
    """Per-lane min(rle, stored): RLE on no-run content expands past
    9 bits/byte, and before r9 the stream could exceed the stored
    bound; now every lane's length is <= stored_stream_len(L)."""

    def test_pathological_no_runs_takes_stored(self):
        # alternating high-value bytes: every byte a 9-bit literal, no
        # matches -> RLE would expand ~12.5%; the stored stream wins
        n = 4096
        payloads = np.tile(np.array([200, 201], np.uint8), n // 2)[None]
        streams, lengths = (
            np.asarray(a) for a in zlib_rle_batch(payloads)
        )
        assert lengths[0] == stored_stream_len(n)
        assert zlib.decompress(bytes(streams[0][: lengths[0]])) == \
            payloads[0].tobytes()

    def test_randomized_lanes_never_exceed_stored_bound(self):
        local = np.random.default_rng(97)
        n = 2048
        payloads = np.stack([
            local.integers(0, 256, n).astype(np.uint8),
            local.integers(128, 256, n).astype(np.uint8),
            np.repeat(local.integers(0, 4, n // 16), 16).astype(np.uint8),
            (local.integers(0, 2, n) + 180).astype(np.uint8),
        ])
        streams, lengths = (
            np.asarray(a) for a in zlib_rle_batch(payloads)
        )
        bound = stored_stream_len(n)
        for lane in range(payloads.shape[0]):
            assert lengths[lane] <= bound, f"lane {lane}"
            got = zlib.decompress(bytes(streams[lane][: lengths[lane]]))
            assert got == payloads[lane].tobytes()

    def test_compressible_lanes_still_beat_stored(self):
        payloads = np.repeat(
            rng.integers(0, 4, (2, 128)), 20, axis=1
        ).astype(np.uint8)
        _, lengths = (np.asarray(a) for a in zlib_rle_batch(payloads))
        assert (lengths < stored_stream_len(payloads.shape[1]) // 2).all()


class TestRleStreamsAreTheNumpyTwins:
    """The fixed-Huffman stream of every payload family is the numpy
    twin's byte for byte (``zlib_rle_np``: the same word math, its
    boundaries from ``np.searchsorted``) and inflates to its payload.
    The sizes are the union of what the comparisons with the removed
    packers used."""

    @pytest.mark.parametrize(
        "n", [1, 5, 17, 256, 258, 777, 1000, 1500, 2048, 4096, 5000, 70000]
    )
    def test_stream_bytes(self, n):
        from omero_ms_pixel_buffer_tpu.ops.device_deflate import zlib_rle_np

        payloads = _payload_families(n)
        streams, lengths = (np.asarray(a) for a in zlib_rle_batch(payloads))
        bound = stored_stream_len(n)
        for lane in range(payloads.shape[0]):
            assert lengths[lane] <= bound
            got = bytes(streams[lane][: lengths[lane]])
            assert got == zlib_rle_np(payloads[lane]), f"lane {lane}"
            assert zlib.decompress(got) == payloads[lane].tobytes()


def _cell_constant_nbits():
    """Token bit counts of a 524800-byte constant payload (the cell's
    512x512 uint16 shape) and the packer's word count for it: one
    literal, then a 258-byte match every 258 positions and zero-length
    tokens between."""
    from omero_ms_pixel_buffer_tpu.ops.device_deflate import (
        _lane_tokens,
        _packing_maxbits,
    )

    nbits = np.asarray(_lane_tokens(np.full(524800, 7, np.uint8))[1])
    return nbits, _packing_maxbits(524800) // 32


# name -> (nbits, nwords): where a search is right for free and a
# count can be wrong
_BOUNDARY_CASES = {
    "32 one-bit tokens in each word": lambda: (np.ones(32 * 5, np.int32), 6),
    "21-bit tokens straddle every edge": lambda: (
        np.full(400, 21, np.int32), 400 * 21 // 32 + 2),
    "long stretches of zero-length tokens": _cell_constant_nbits,
    "the stream fills maxbits exactly": lambda: (
        np.full(2048 // 16, 16, np.int32), 2048 // 32),
    "offsets at and beyond the last edge": lambda: (
        np.full(10, 16, np.int32), 4),
    "all-zero tail after total_bits": lambda: (
        np.array([3, 9, 0, 0, 12, 1, 0], np.int32), 64),
    "zero-length tokens first and last": lambda: (
        np.array([0, 0, 31, 1, 0, 32 - 11, 11, 0, 0], np.int32), 5),
}


class TestBoundaryCount:
    """The packer's boundary step, ``c[w]`` = tokens starting below bit
    32 * (w + 1), is one scatter of each word's last token and a
    running maximum; it must answer what the binary search it replaced
    answers."""

    @pytest.mark.parametrize("case", sorted(_BOUNDARY_CASES))
    def test_matches_searchsorted(self, case):
        from omero_ms_pixel_buffer_tpu.ops.device_deflate import (
            _tokens_below_edges,
        )

        nbits, nwords = _BOUNDARY_CASES[case]()
        offs = np.cumsum(nbits) - nbits
        edges = (np.arange(nwords) + 1) * 32
        want = np.searchsorted(offs, edges, side="left")
        got = np.asarray(_tokens_below_edges(offs.astype(np.int32), nwords))
        assert got.dtype == np.int32 and got.shape == (nwords,)
        np.testing.assert_array_equal(got, want)


class TestPackerWords:
    """``_pack_bits_scan`` against ``_pack_bits_scan_np``: the packed
    words and the bit total, where ``TestBoundaryCount`` holds one step
    of it."""

    @staticmethod
    def _both(bits, nbits, maxbits):
        """A batch through the programs' own entry, lane by lane
        against the numpy packer."""
        from omero_ms_pixel_buffer_tpu.ops.device_deflate import (
            _pack_bits_scan_np,
            _pack_dispatch,
        )

        packed, totals = (
            np.asarray(a) for a in _pack_dispatch(bits, nbits, maxbits)
        )
        for lane in range(bits.shape[0]):
            want, want_total = _pack_bits_scan_np(
                bits[lane], nbits[lane].astype(np.int64), maxbits
            )
            assert totals[lane] == want_total, lane
            assert packed[lane].tobytes() == want, lane

    @pytest.mark.parametrize("case", sorted(_BOUNDARY_CASES))
    def test_boundary_cases(self, case):
        nbits, nwords = _BOUNDARY_CASES[case]()
        # a token's value has at most 20 significant bits, and a
        # zero-length token carries none
        r = np.random.default_rng(len(case))
        bits = (
            r.integers(0, 1 << 20, nbits.shape[0])
            & ((1 << np.minimum(nbits, 20)) - 1)
        ).astype(np.uint32)
        self._both(bits[None], nbits.astype(np.int32)[None], nwords * 32)

    @pytest.mark.parametrize("n", [17, 258, 1200, 5000])
    def test_dynamic_token_arrays(self, n):
        """Pass 2's tokens: header ++ body through per-lane dynamic
        codes (1 to 20 bits where a fixed token has 7 or more) ++ an
        explicit EOB."""
        from omero_ms_pixel_buffer_tpu.ops.device_deflate import (
            _dyn_stats,
            _dyn_tokens,
            _packing_maxbits,
            build_dynamic_tables,
        )

        payloads = _dyn_corpus(n)
        counts, extras = (np.asarray(a) for a in _dyn_stats(payloads))
        tables = build_dynamic_tables(counts, extras)
        bits, nbits = (
            np.asarray(a) for a in _dyn_tokens(payloads, *tables)
        )
        body = nbits[:, tables[0].shape[1] : -1]
        assert ((body > 0) & (body < 7)).any()
        self._both(bits, nbits, _packing_maxbits(n))


def _cell_tiles(lanes: int) -> np.ndarray:
    """512x512 uint16 tiles as the cell crops them: gaussian noise
    (sigma 120) over a smooth base, and flat stretches of runs."""
    r = np.random.default_rng(2700 + lanes)
    yy, xx = np.mgrid[0:512, 0:512]
    out = []
    for lane in range(lanes):
        tile = 2000 + 3 * xx + 2 * yy + r.normal(0, 120, (512, 512))
        tile[100 * lane : 100 * lane + 200] = 4095  # run-heavy rows
        out.append(tile.clip(0, 65535).astype(np.uint16))
    return np.stack(out)


def _cell_payloads(lanes: int) -> np.ndarray:
    """The Up-filtered scanlines of ``_cell_tiles``, the cell's
    payloads (L = 524800)."""
    from omero_ms_pixel_buffer_tpu.ops.png import filter_rows_np

    return np.stack([
        filter_rows_np(
            tile.astype(">u2").view(np.uint8).reshape(512, 1024), 2, "up"
        ).ravel()
        for tile in _cell_tiles(lanes)
    ])


_RUN_L = 700


def _no_run(n: int = _RUN_L) -> np.ndarray:
    """No two neighbours equal, no zero byte; four values, so that a
    dynamic code beats the fixed one and both beat stored blocks."""
    return (np.arange(n) % 4 + 1).astype(np.uint8)


def _one_run(run: int) -> np.ndarray:
    """``_no_run`` with one run of exactly ``run`` zero bytes in it."""
    payload = _no_run()
    payload[40 : 40 + run] = 0
    return payload


# case -> (B, L) payloads. The runs sit where the decomposition turns:
# 2 (head + one literal), 3 (head + two literals), 4 (the shortest
# match), 258/259/260 (a match one short of full, full, full + a
# literal tail), 517 (two full matches), 600 (two full and a third)
_PINNED_PAYLOADS = {
    **{f"run{r}": (lambda r=r: _one_run(r)[None])
       for r in (2, 3, 4, 258, 259, 260, 517, 600)},
    "constant": lambda: np.full((1, _RUN_L), 7, np.uint8),
    "no_run": lambda: _no_run()[None],
    "L1": lambda: np.array([[9]], np.uint8),
    "cell_1lane": lambda: _cell_payloads(1),
    "cell_2lanes": lambda: _cell_payloads(2),
}

# SHA-256 of every lane's stream (true length only), lanes joined:
# recorded on the code before PR 29 changed the token lookup, so a
# lookup that gives other tokens gives other bytes here
_PINNED_SHA = {
    "L1": {
        "rle":
            "b86a04326126d03e40d27cc79f526832b217a623d467405fe18a3dbf8bb8ae1f",
        "dynamic":
            "b86a04326126d03e40d27cc79f526832b217a623d467405fe18a3dbf8bb8ae1f",
    },
    "cell_1lane": {
        "rle":
            "9383b8bc2054e5335c0092d2a22bfb95400d6259ccda8630a7aca74eb44b9d8d",
        "dynamic":
            "7a7162eca620354cded020778cf564061ebf4dbfbfe05ed45b26872c4c095a03",
    },
    "cell_2lanes": {
        "rle":
            "b84a6f6b4e90d074be2d1842556140c2f9f8d4ee2b58f03528bb19664d2bcc77",
        "dynamic":
            "e9c06b2fc2a5226ad1744e444f4ad3af3fa1ec13eaedd122f165b6df64a3ca2a",
    },
    "constant": {
        "rle":
            "0e877d73b2183f548d3f3334a5662b4fabfc94fa241324015d97067ca11bca7c",
        "dynamic":
            "0e877d73b2183f548d3f3334a5662b4fabfc94fa241324015d97067ca11bca7c",
    },
    "no_run": {
        "rle":
            "fea80d8b626d9204250e2c724e1eda9a3bb6b27f93f695b2c7c0fd842eec9f78",
        "dynamic":
            "d09313d99a60b2be4dd442f61ba08154ac5b28a20d1fe07ef6c107ab6ca5b09c",
    },
    "run2": {
        "rle":
            "ca8b1ba08fc1515cff5bede6f96ec7d16d005036f1672e2f207f4b3aa0ce3272",
        "dynamic":
            "c6564052d9f7830fe45a1f335b1ea4d076aa8f9d73717a99eb34f1150abcc7f7",
    },
    "run258": {
        "rle":
            "a3bb49a4d95efd8fcfb182fa18385ff74f335dbb22babdc69154010115023494",
        "dynamic":
            "c3d1fd83e32a74054ee1108089361767814c4c53f5ad81d3ae77e060fdda2bc2",
    },
    "run259": {
        "rle":
            "3be24bb7ea7517730be65608b7f53e669ca8cfc09371625d98c98e074d5b8953",
        "dynamic":
            "749c7e25983d9c936fb7b6d2b3ed58e626bf8d61ca557608bc28c39b61209481",
    },
    "run260": {
        "rle":
            "73e8d1dc0dbf80dbf8612dc6066651fccf07ed7c37bf45e7d4f057391c42b316",
        "dynamic":
            "f460b708474983dcc4ae8ef473b4427eb2f9481c8a30afbbe371db9410fa6cf6",
    },
    "run3": {
        "rle":
            "fab2900fc783bf04085b7c084f34e3ba3713a8d14a70b8f53522e1d219615f22",
        "dynamic":
            "12746d24a9ad9324e6baf631ca8473b9551d45876c5707bc0c90b1e340a2ad5f",
    },
    "run4": {
        "rle":
            "5fdccffda809e714986ff82a8e73efc041c75aba1b161453d6b63b1f1701cf13",
        "dynamic":
            "2068fbeb680557edc9abe1932e6c0593d5474c06e06617cb478cd789cd61fde6",
    },
    "run517": {
        "rle":
            "5df9f34e773726ef2c20b1ff53e41449b6cb6bdd23be889a920be8c3b864fc7f",
        "dynamic":
            "9e495c840d48d059cfe7473a96cde25b1066d727a06960b5dcbfb1f46718b004",
    },
    "run600": {
        "rle":
            "db21dda0c3a314f1956d6dfb9dd02ff191e5dfe06b2ee2cdb6790e013a3690e3",
        "dynamic":
            "5b3e7012bdc50bb9575b5cd8cfee190485c9796ccb8bdb0f1631dc22ab64c916",
    },
}


def _streams_sha(streams, lengths) -> str:
    import hashlib

    streams, lengths = np.asarray(streams), np.asarray(lengths)
    return hashlib.sha256(b"".join(
        bytes(s[:n]) for s, n in zip(streams, lengths)
    )).hexdigest()


class TestPinnedStreams:
    """The streams are bytes a client may have cached under an ETag:
    a faster lookup must give the same ones, in both modes."""

    @pytest.mark.parametrize("mode", ["rle", "dynamic"])
    @pytest.mark.parametrize("case", sorted(_PINNED_PAYLOADS))
    def test_stream_bytes_are_pinned(self, case, mode):
        from omero_ms_pixel_buffer_tpu.ops.device_deflate import (
            zlib_dynamic_batch,
        )

        payloads = _PINNED_PAYLOADS[case]()
        encode = zlib_rle_batch if mode == "rle" else zlib_dynamic_batch
        streams, lengths = (np.asarray(a) for a in encode(payloads))
        for lane, payload in enumerate(payloads):
            got = zlib.decompress(bytes(streams[lane][: lengths[lane]]))
            assert got == payload.tobytes(), lane
        assert _streams_sha(streams, lengths) == _PINNED_SHA[case][mode]


def _symbols_of_the_numpy_twin(payload: np.ndarray):
    """((286,) symbol counts, match extra bits) of the tokens
    ``_rle_tokens_np`` decomposes ``payload`` into: each fixed-Huffman
    (bits, nbits) pair names one literal or one match length."""
    from omero_ms_pixel_buffer_tpu.ops import device_deflate as dd

    lengths = np.arange(3, 259)

    def key(bits, nbits):
        return np.asarray(bits, np.int64) | (np.asarray(nbits, np.int64) << 32)

    keys = np.concatenate([
        key(dd._LIT_BITS, dd._LIT_NBITS),
        key(dd._MATCH_BITS[lengths], dd._MATCH_NBITS[lengths]),
    ])
    symbol = np.concatenate([np.arange(256), dd._MLEN_SYM[lengths]])
    extra = np.concatenate([np.zeros(256, int), dd._MLEN_EXTRA[lengths]])
    order = np.argsort(keys)
    assert (np.diff(keys[order]) > 0).all()  # no two tokens share a code
    bits, nbits = dd._rle_tokens_np(payload)
    emitted = key(bits[nbits > 0], nbits[nbits > 0])
    at = np.searchsorted(keys[order], emitted)
    assert (keys[order][at] == emitted).all()
    return (
        np.bincount(symbol[order][at], minlength=286),
        int(extra[order][at].sum()),
    )


# counts a host plan may be handed -> (286,) frequencies
_PLAN_COUNTS = {
    "uniform": lambda r: np.full(286, 1000),
    "random": lambda r: r.integers(0, 5000, 286),
    "sparse random": lambda r: r.integers(0, 3, 286) * r.integers(0, 900, 286),
    "one literal": lambda r: np.bincount([65], minlength=286) * 524800,
    "one literal, one long match": lambda r: (
        np.bincount([0, 285], minlength=286) * np.r_[1, [2034] * 285]),
    "nothing": lambda r: np.zeros(286, int),
    # frequencies that double: the tree wants depth 285, the damping
    # brings it to 15, and the deepest codes land on the length
    # symbols with five extra bits (20-bit values, 21-bit tokens)
    "doubling, rare long matches": lambda r: (
        2 ** np.minimum(np.arange(286)[::-1] // 9, 30)),
    "doubling, rare literals": lambda r: (
        2 ** np.minimum(np.arange(286) // 9, 30)),
}


class TestTokenIndex:
    """One index a position (0-255 literal, 256 + L match, 515 none)
    feeds the histogram and the one table both emits look up."""

    @pytest.mark.parametrize("case", sorted(_PINNED_PAYLOADS))
    def test_counts_are_the_numpy_twins_symbols(self, case):
        from omero_ms_pixel_buffer_tpu.ops.device_deflate import _dyn_stats

        payloads = _PINNED_PAYLOADS[case]()
        counts, extras = (np.asarray(a) for a in _dyn_stats(payloads))
        assert counts.shape == (len(payloads), 286)
        for lane, payload in enumerate(payloads):
            want, want_extra = _symbols_of_the_numpy_twin(payload)
            np.testing.assert_array_equal(counts[lane], want)
            assert extras[lane] == want_extra
            assert counts[lane][256] == 0  # EOB is the plan's to add

    @pytest.mark.parametrize("case", sorted(_PLAN_COUNTS))
    def test_every_planned_code_fits_the_packed_table(self, case):
        from omero_ms_pixel_buffer_tpu.ops import device_deflate as dd

        freq = np.asarray(_PLAN_COUNTS[case](np.random.default_rng(29)))
        counts = np.stack([freq, freq[::-1], np.roll(freq, 143)])
        counts[:, 256] = 0
        extras = (counts[:, 257:] * np.asarray(dd._LEN_EXTRA)).sum(axis=1)
        _, _, lit_b, lit_n, ml_b, ml_n, _, _ = dd.build_dynamic_tables(
            counts, extras
        )
        for lane in range(len(counts)):
            bits = np.concatenate([lit_b[lane], ml_b[lane], [0]])
            nbits = np.concatenate([lit_n[lane], ml_n[lane], [0]])
            assert bits.max() < 1 << dd._TOKEN_VALUE_BITS
            assert 0 <= nbits.min() and nbits.max() <= dd._TOKEN_MAX_NBITS
            table = dd._token_table(
                lit_b[lane], lit_n[lane], ml_b[lane], ml_n[lane]
            )
            assert table.shape == (dd._TOKEN_KINDS,)
            got_b, got_n = dd._coded_tokens(
                np.arange(dd._TOKEN_KINDS, dtype=np.int32), table
            )
            np.testing.assert_array_equal(np.asarray(got_b), bits)
            np.testing.assert_array_equal(np.asarray(got_n), nbits)

    def test_the_fixed_table_is_the_fixed_code(self):
        from omero_ms_pixel_buffer_tpu.ops import device_deflate as dd

        payload = np.concatenate([np.arange(256), np.zeros(700)]).astype(
            np.uint8
        )
        bits, nbits = (np.asarray(a) for a in dd._rle_tokens(payload))
        want_b, want_n = dd._rle_tokens_np(payload)
        np.testing.assert_array_equal(bits, want_b)
        np.testing.assert_array_equal(nbits, want_n)
        assert bits.dtype == np.uint32 and nbits.dtype == np.int32


def _token_cases():
    """case -> a function giving (L,) int32 token indices: run-heavy,
    noisy and constant payloads through ``_token_index``, the shortest
    payloads, a length one short of and one past each chunk edge of
    the dense forms, and stretches that hold nothing but
    ``_NO_TOKEN``."""
    from omero_ms_pixel_buffer_tpu.ops import device_deflate as dd

    kinds = dd._TOKEN_KINDS

    def random(n):
        return lambda: np.random.default_rng(n).integers(0, kinds, n)

    cases = {f"random L={n}": random(n) for n in (1, 5, 17)}
    for edge in sorted({dd._COUNT_CHUNK, dd._LOOKUP_CHUNK}):
        for n in (edge - 1, edge + 1):
            cases[f"chunk edge L={n}"] = random(n)
    cases["a no-token stretch"] = lambda: np.concatenate([
        random(300)(), np.full(2000, dd._NO_TOKEN), random(301)(),
    ])
    cases["no token at all"] = lambda: np.full(777, dd._NO_TOKEN)
    for lane, name in enumerate(
        ("zeros", "noise", "runs of 20", "alternating", "constant")
    ):
        cases[f"family {name}"] = lambda lane=lane: dd._token_index(
            _payload_families(1500)[lane]
        )
    return cases


_TOKEN_CASES = _token_cases()


def _token_lanes(case: str, lanes: int) -> np.ndarray:
    """``lanes`` different lanes of a case: each rolled a little."""
    tok = np.asarray(_TOKEN_CASES[case](), np.int32)
    return np.stack([np.roll(tok, 7 * lane) for lane in range(lanes)])


class TestDenseKinds:
    """The count and the lookup over the 516 token kinds are
    contractions with a one-hot (PR 31), not a scatter-add and a
    gather: the same numbers as numpy's, at every length around a
    chunk edge, under vmap and called outside any jit."""

    @pytest.mark.parametrize("lanes", [1, 2, 4])
    @pytest.mark.parametrize("case", sorted(_TOKEN_CASES))
    def test_the_count_is_numpys_bincount(self, case, lanes):
        import jax

        from omero_ms_pixel_buffer_tpu.ops import device_deflate as dd

        tok = _token_lanes(case, lanes)
        counts, extras = (
            np.asarray(a) for a in jax.vmap(dd._symbol_counts)(tok)
        )
        assert counts.shape == (lanes, 286) and counts.dtype == np.int32
        for lane in range(lanes):
            raw = np.bincount(tok[lane], minlength=dd._TOKEN_KINDS)
            by_len = raw[256 : dd._NO_TOKEN]
            np.testing.assert_array_equal(
                counts[lane],
                np.concatenate([raw[:256], by_len @ dd._MLEN_FOLD]),
            )
            assert extras[lane] == by_len @ dd._MLEN_EXTRA
        alone, alone_extra = dd._symbol_counts(tok[0])  # outside any jit
        np.testing.assert_array_equal(np.asarray(alone), counts[0])
        assert int(alone_extra) == extras[0]

    @pytest.mark.parametrize("lanes", [1, 2, 4])
    @pytest.mark.parametrize("case", sorted(_TOKEN_CASES))
    def test_the_lookup_is_numpys_take(self, case, lanes):
        """Each lane under its own table, whose entries reach the top
        of both fields: 20-bit values and counts up to 31 (bits 0-24
        of the packed word all set in entry 0 and in ``_NO_TOKEN - 1``)."""
        import jax

        from omero_ms_pixel_buffer_tpu.ops import device_deflate as dd

        tok = _token_lanes(case, lanes)
        r = np.random.default_rng(len(case) + lanes)
        table = r.integers(0, 1 << 25, (lanes, dd._TOKEN_KINDS)).astype(
            np.uint32
        )
        table[:, [0, dd._NO_TOKEN - 1]] = (1 << 25) - 1
        table[:, dd._NO_TOKEN] = 0
        bits, nbits = (
            np.asarray(a) for a in jax.vmap(dd._coded_tokens)(tok, table)
        )
        assert bits.dtype == np.uint32 and nbits.dtype == np.int32
        for lane in range(lanes):
            want = table[lane][tok[lane]]
            np.testing.assert_array_equal(
                bits[lane], want & ((1 << dd._TOKEN_VALUE_BITS) - 1)
            )
            np.testing.assert_array_equal(
                nbits[lane], want >> dd._TOKEN_VALUE_BITS
            )
        alone_b, alone_n = dd._coded_tokens(tok[0], table[0])
        np.testing.assert_array_equal(np.asarray(alone_b), bits[0])
        np.testing.assert_array_equal(np.asarray(alone_n), nbits[0])


class TestCellShape:
    """The benchmark cell's shape, 1 and 2 lanes: the fixed-Huffman
    stream is the numpy twin's byte for byte (the twin still finds its
    word boundaries with ``np.searchsorted``), and the dynamic stream
    inflates to its payload."""

    @pytest.mark.parametrize("lanes", [1, 2])
    def test_rle_streams_are_the_numpy_twins(self, lanes):
        from omero_ms_pixel_buffer_tpu.ops.device_deflate import zlib_rle_np

        payloads = _cell_payloads(lanes)
        assert payloads.shape == (lanes, 524800)
        streams, lengths = (np.asarray(a) for a in zlib_rle_batch(payloads))
        for lane in range(lanes):
            got = bytes(streams[lane][: lengths[lane]])
            assert got == zlib_rle_np(payloads[lane]), lane
            assert zlib.decompress(got) == payloads[lane].tobytes()

    @pytest.mark.parametrize("lanes", [1, 2])
    def test_dynamic_streams_round_trip(self, lanes):
        from omero_ms_pixel_buffer_tpu.ops.device_deflate import (
            zlib_dynamic_batch,
        )

        payloads = _cell_payloads(lanes)
        streams, lengths = (
            np.asarray(a) for a in zlib_dynamic_batch(payloads)
        )
        for lane in range(lanes):
            assert lengths[lane] <= stored_stream_len(524800)
            got = zlib.decompress(bytes(streams[lane][: lengths[lane]]))
            assert got == payloads[lane].tobytes(), lane

    @pytest.mark.parametrize("lanes", [1, 2])
    def test_the_fused_chain_gives_the_pinned_png_body(self, lanes):
        """Filter and both passes from the uint16 tiles, as the served
        path runs them: the IDAT bodies are the pinned streams."""
        from omero_ms_pixel_buffer_tpu.ops.device_deflate import (
            fused_filter_deflate_dynamic,
        )

        streams, lengths = fused_filter_deflate_dynamic(
            _cell_tiles(lanes), 512, 1 + 512 * 2, 2
        )
        case = {1: "cell_1lane", 2: "cell_2lanes"}[lanes]
        assert _streams_sha(streams, lengths) == _PINNED_SHA[case]["dynamic"]


class TestStoredStreams:
    @pytest.mark.parametrize("n", [1, 100, 65535, 65536, 70000, 131071])
    def test_roundtrip(self, n):
        payloads = rng.integers(0, 256, (2, n)).astype(np.uint8)
        streams = np.asarray(zlib_stored_batch(payloads))
        assert streams.shape[1] == stored_stream_len(n)
        for lane in range(2):
            assert (
                zlib.decompress(bytes(streams[lane]))
                == payloads[lane].tobytes()
            )


class TestDeflateFiltered:
    def _filtered(self, tiles: np.ndarray, mode: str = "up"):
        import jax.numpy as jnp

        from omero_ms_pixel_buffer_tpu.ops.convert import to_big_endian_bytes
        from omero_ms_pixel_buffer_tpu.ops.png import filter_batch

        rows = to_big_endian_bytes(jnp.asarray(tiles))
        return filter_batch(rows, tiles.dtype.itemsize, mode)

    @staticmethod
    def _assert_inflates_to(out, want):
        streams, lengths = (np.asarray(a) for a in out)
        want = np.asarray(want)
        assert streams.shape[0] == want.shape[0]
        for lane in range(want.shape[0]):
            got = zlib.decompress(bytes(streams[lane][: lengths[lane]]))
            assert got == want[lane].tobytes(), lane

    def test_matches_host_payload(self):
        tiles = rng.integers(0, 60000, (4, 64, 64), dtype=np.uint16)
        filtered = self._filtered(tiles)
        self._assert_inflates_to(
            deflate_filtered_batch(filtered, 64, 1 + 64 * 2), filtered
        )

    def test_bucket_padding_sliced_away(self):
        # real region 40x30 inside a 64x64 bucket: the stream must cover
        # only the leading rows x row_bytes
        tiles = np.zeros((2, 64, 64), np.uint16)
        tiles[:, :30, :40] = rng.integers(0, 60000, (2, 30, 40))
        filtered = self._filtered(tiles)
        self._assert_inflates_to(
            deflate_filtered_batch(filtered, 30, 1 + 40 * 2),
            np.asarray(filtered)[:, :30, : 1 + 40 * 2],
        )

    def test_stored_mode(self):
        tiles = rng.integers(0, 255, (2, 32, 32), dtype=np.uint8)
        filtered = self._filtered(tiles)
        streams, lengths = (
            np.asarray(a)
            for a in deflate_filtered_batch(
                filtered, 32, 33, mode="stored"
            )
        )
        host = np.asarray(filtered)
        for lane in range(2):
            assert lengths[lane] == stored_stream_len(32 * 33)
            got = zlib.decompress(bytes(streams[lane][: lengths[lane]]))
            assert got == host[lane].tobytes()

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError):
            deflate_filtered_batch(np.zeros((1, 8, 8), np.uint8), 8, 8,
                                   mode="huffman")

    def test_pallas_filtered_microscopy_tiles_decode(self):
        # smooth field + sensor noise through the Pallas filter kernel
        from omero_ms_pixel_buffer_tpu.ops.pallas.filter import (
            filter_tiles,
        )

        filtered = filter_tiles(_synth_tiles(4, 32, 32, seed=5), "up")
        self._assert_inflates_to(
            deflate_filtered_batch(filtered, 32, 1 + 64),
            np.asarray(filtered)[:, :32, : 1 + 64],
        )

    def test_compression_on_run_heavy_content(self):
        # noisy 16-bit content defeats RLE at tiny tiles; run-heavy
        # content must compress
        from omero_ms_pixel_buffer_tpu.ops.pallas.filter import (
            filter_tiles,
        )

        tiles = np.full((4, 32, 32), 777, np.uint16)  # flat field
        filtered = filter_tiles(tiles, "up")
        _, lengths = deflate_filtered_batch(filtered, 32, 1 + 64)
        assert np.asarray(lengths).mean() < 0.2 * 32 * (1 + 64)

    def test_fused_chain_inflates_to_the_filtered_rows(self):
        import jax.numpy as jnp

        tiles = rng.integers(0, 60000, (3, 48, 48), dtype=np.uint16)
        self._assert_inflates_to(
            fused_filter_deflate_batch(
                jnp.asarray(tiles), 48, 1 + 48 * 2, 2
            ),
            self._filtered(tiles),
        )


class TestPipelineDeviceDeflate:
    """End-to-end: handle_batch with the knob on serves pixel-identical
    PNGs through the device bucket path."""

    @pytest.fixture(scope="class")
    def service(self, tmp_path_factory):
        from omero_ms_pixel_buffer_tpu.io.ometiff import write_ome_tiff
        from omero_ms_pixel_buffer_tpu.io.pixels_service import (
            ImageRegistry,
            PixelsService,
        )

        root = tmp_path_factory.mktemp("devdeflate")
        path = str(root / "img.ome.tiff")
        img = rng.integers(0, 60000, (1, 1, 1, 300, 300), dtype=np.uint16)
        write_ome_tiff(path, img, tile_size=(64, 64))
        registry = ImageRegistry()
        registry.add(1, path)
        svc = PixelsService(registry)
        yield svc, img
        svc.close()

    def _ctxs(self):
        from omero_ms_pixel_buffer_tpu.tile_ctx import RegionDef, TileCtx

        return [
            TileCtx(image_id=1, z=0, c=0, t=0,
                    region=RegionDef(x, y, w, h), format="png",
                    omero_session_key="k")
            for x, y, w, h in [
                (0, 0, 64, 64), (64, 64, 64, 64),
                (128, 0, 100, 80),   # padded lane, same bucket
                (0, 128, 256, 128),  # larger bucket
            ]
        ]

    def test_pixel_equality_vs_source(self, service):
        from omero_ms_pixel_buffer_tpu.models.tile_pipeline import (
            TilePipeline,
        )

        svc, img = service
        pipe = TilePipeline(svc, engine="device", device_deflate=True)
        pipe.mesh = None
        ctxs = self._ctxs()
        results = pipe.handle_batch(ctxs)
        assert all(r is not None for r in results)
        for ctx, png in zip(ctxs, results):
            decoded = np.array(Image.open(io.BytesIO(png)))
            r = ctx.region
            expect = img[0, 0, 0, r.y : r.y + r.height,
                         r.x : r.x + r.width]
            np.testing.assert_array_equal(decoded, expect)

    def test_matches_host_engine_pixels(self, service):
        from omero_ms_pixel_buffer_tpu.models.tile_pipeline import (
            TilePipeline,
        )

        svc, _ = service
        dev = TilePipeline(svc, engine="device", device_deflate=True)
        dev.mesh = None
        host = TilePipeline(svc, engine="host")
        ctxs = self._ctxs()
        for d, h in zip(dev.handle_batch(ctxs), host.handle_batch(self._ctxs())):
            dp = np.array(Image.open(io.BytesIO(d)))
            hp = np.array(Image.open(io.BytesIO(h)))
            np.testing.assert_array_equal(dp, hp)

    def test_mesh_path_with_device_deflate(self, service):
        import jax

        from omero_ms_pixel_buffer_tpu.models.tile_pipeline import (
            TilePipeline,
        )

        svc, img = service
        assert len(jax.devices()) == 8
        pipe = TilePipeline(svc, engine="device", device_deflate=True)
        assert pipe._get_mesh() is not None
        results = pipe.handle_batch(self._ctxs())
        assert all(r is not None for r in results)
        for ctx, png in zip(self._ctxs(), results):
            decoded = np.array(Image.open(io.BytesIO(png)))
            r = ctx.region
            np.testing.assert_array_equal(
                decoded,
                img[0, 0, 0, r.y : r.y + r.height, r.x : r.x + r.width],
            )

    def test_adaptive_cap_across_batches(self, service):
        """The one-sync transfer's compressed-size guess adapts: the
        first batch may overflow it (incompressible noise), later
        batches reuse the learned cap — all pixel-exact either way."""
        from omero_ms_pixel_buffer_tpu.models.tile_pipeline import (
            TilePipeline,
        )

        svc, img = service
        pipe = TilePipeline(svc, engine="device", device_deflate=True)
        pipe.mesh = None
        for _ in range(3):  # fresh guess -> overflow -> learned cap
            results = pipe.handle_batch(self._ctxs())
            for ctx, png in zip(self._ctxs(), results):
                decoded = np.array(Image.open(io.BytesIO(png)))
                r = ctx.region
                np.testing.assert_array_equal(
                    decoded,
                    img[0, 0, 0, r.y : r.y + r.height,
                        r.x : r.x + r.width],
                )
        assert pipe._dd_cap  # the guess was learned

    def test_config_knob_reaches_pipeline(self):
        from omero_ms_pixel_buffer_tpu.http.server import PixelBufferApp
        from omero_ms_pixel_buffer_tpu.utils.config import Config

        config = Config.from_dict(
            {"session-store": {"type": "memory"},
             "backend": {"engine": "host"}}
        )
        assert config.backend.png.device_deflate is True  # default on
        app = PixelBufferApp(config)
        assert app.pipeline.device_deflate is True

        config_off = Config.from_dict(
            {"session-store": {"type": "memory"},
             "backend": {"engine": "host",
                         "png": {"device-deflate": False}}}
        )
        assert config_off.backend.png.device_deflate is False
        app_off = PixelBufferApp(config_off)
        assert app_off.pipeline.device_deflate is False


class TestShardedEncode:
    """Real multi-chip dispatch: the fused filter+deflate chain
    shard_mapped over the 8-way CPU host-platform mesh must produce
    BYTE-identical streams to the single-device program."""

    def test_shard_map_roundtrip_byte_identical(self):
        import jax
        import jax.numpy as jnp

        from omero_ms_pixel_buffer_tpu.parallel.mesh import make_mesh
        from omero_ms_pixel_buffer_tpu.parallel.sharding import (
            pad_batch,
            shard_batch,
            sharded_filter_deflate,
        )

        assert len(jax.devices()) == 8
        mesh = make_mesh(("data",))
        tiles = rng.integers(0, 60000, (13, 32, 32), dtype=np.uint16)
        padded, real = pad_batch(jnp.asarray(tiles), 8)
        sharded = shard_batch(mesh, padded)
        s_mesh, l_mesh = (
            np.asarray(a)
            for a in sharded_filter_deflate(mesh, sharded, 32, 65, 2)
        )
        s_one, l_one = (
            np.asarray(a)
            for a in fused_filter_deflate_batch(
                jnp.asarray(tiles), 32, 65, 2
            )
        )
        np.testing.assert_array_equal(l_mesh[:real], l_one)
        np.testing.assert_array_equal(s_mesh[:real], s_one)
        for lane in range(real):
            got = zlib.decompress(
                bytes(s_mesh[lane][: l_mesh[lane]])
            )
            assert len(got) == 32 * 65

    def test_per_device_lane_counts(self):
        from omero_ms_pixel_buffer_tpu.parallel.mesh import lane_counts

        assert lane_counts(13, 8) == [2, 2, 2, 2, 2, 2, 1, 0]
        assert lane_counts(16, 8) == [2] * 8
        assert lane_counts(3, 8) == [1, 1, 1, 0, 0, 0, 0, 0]
        assert sum(lane_counts(9, 8)) == 9


@pytest.mark.resilience
class TestMeshDegradation:
    """Chaos: one mesh chip's fault point fires; the batch completes
    on the surviving chips instead of failing the requests."""

    @pytest.fixture(autouse=True)
    def _clean(self):
        from omero_ms_pixel_buffer_tpu.resilience import BOARD, INJECTOR

        yield
        INJECTOR.clear()
        BOARD.reset()
        BOARD.configure(enabled=True)

    def test_sick_chip_degrades_to_survivors(self):
        import jax
        import jax.numpy as jnp

        from omero_ms_pixel_buffer_tpu.models.device_dispatch import (
            DeviceEncodeDispatcher,
        )
        from omero_ms_pixel_buffer_tpu.parallel.mesh import MeshManager
        from omero_ms_pixel_buffer_tpu.resilience import INJECTOR
        from omero_ms_pixel_buffer_tpu.resilience.faultinject import (
            always,
            first_n,
        )

        devices = jax.devices()
        assert len(devices) == 8
        sick = devices[3]
        # the first sharded dispatch blows up (a wedged chip surfaces
        # as the whole program failing)...
        INJECTOR.install(
            "device.mesh-dispatch", first_n(1, RuntimeError("ICI wedge"))
        )
        # ...and the probe pass finds exactly chip 3 dead
        INJECTOR.install(
            f"device.chip:{sick.id}", always(RuntimeError("chip down"))
        )
        mgr = MeshManager(devices=devices)
        disp = DeviceEncodeDispatcher({}, mesh_manager=mgr)
        tiles = rng.integers(0, 60000, (16, 32, 32), dtype=np.uint16)
        fut = disp.submit(
            tiles, 32, 65, 2, "up", "rle",
            lanes=list(range(16)), sizes=[(32, 32)] * 16,
            bit_depth=16, color_type=0,
        )
        out = fut.result(timeout=120)
        assert sorted(out) == list(range(16))
        assert mgr.last_dispatch["executed"] is True
        assert mgr.last_dispatch["n_devices"] == 7
        assert sick.id not in mgr.last_dispatch["device_ids"]
        assert sum(mgr.last_dispatch["lanes_per_device"]) == 16
        # byte-identical to the single-device encode of the same lanes
        s_one, l_one = (
            np.asarray(a)
            for a in fused_filter_deflate_batch(
                jnp.asarray(tiles), 32, 65, 2
            )
        )
        from omero_ms_pixel_buffer_tpu.ops.png import frame_png

        for lane in range(16):
            assert out[lane] == frame_png(
                bytes(s_one[lane][: l_one[lane]]), 32, 32, 16, 0
            )
        disp.close()

    def test_all_chips_down_raises(self):
        import jax

        from omero_ms_pixel_buffer_tpu.parallel.mesh import (
            MeshHealthError,
            MeshManager,
        )
        from omero_ms_pixel_buffer_tpu.resilience import INJECTOR
        from omero_ms_pixel_buffer_tpu.resilience.faultinject import always

        INJECTOR.install(
            "device.mesh-dispatch", always(RuntimeError("bus fire"))
        )
        for dev in jax.devices():
            INJECTOR.install(
                f"device.chip:{dev.id}", always(RuntimeError("down"))
            )
        mgr = MeshManager()
        with pytest.raises((MeshHealthError, RuntimeError)):
            mgr.dispatch(lambda mesh: mesh)

    def test_pipeline_batch_survives_sick_chip(self, tmp_path):
        """End-to-end: handle_batch with a serving mesh completes (and
        stays pixel-exact) while one chip is injected dead."""
        import jax

        from omero_ms_pixel_buffer_tpu.io.ometiff import write_ome_tiff
        from omero_ms_pixel_buffer_tpu.io.pixels_service import (
            ImageRegistry,
            PixelsService,
        )
        from omero_ms_pixel_buffer_tpu.models.tile_pipeline import (
            TilePipeline,
        )
        from omero_ms_pixel_buffer_tpu.resilience import INJECTOR
        from omero_ms_pixel_buffer_tpu.resilience.faultinject import (
            always,
            first_n,
        )
        from omero_ms_pixel_buffer_tpu.tile_ctx import RegionDef, TileCtx

        img = rng.integers(0, 60000, (1, 1, 1, 128, 128), dtype=np.uint16)
        path = str(tmp_path / "chaos.ome.tiff")
        write_ome_tiff(path, img, tile_size=(32, 32))
        registry = ImageRegistry()
        registry.add(1, path)
        svc = PixelsService(registry)
        try:
            pipe = TilePipeline(
                svc, engine="device", device_deflate=True,
                use_plane_cache=False,
            )
            assert pipe._get_mesh() is not None
            sick = jax.devices()[5]
            INJECTOR.install(
                "device.mesh-dispatch",
                first_n(1, RuntimeError("ICI wedge")),
            )
            INJECTOR.install(
                f"device.chip:{sick.id}", always(RuntimeError("down"))
            )
            ctxs = [
                TileCtx(image_id=1, z=0, c=0, t=0,
                        region=RegionDef(32 * (i % 4), 32 * (i // 4),
                                         32, 32),
                        format="png", omero_session_key="k")
                for i in range(16)
            ]
            results = pipe.handle_batch(ctxs)
            assert all(isinstance(r, bytes) for r in results)
            assert pipe.last_mesh_dispatch["n_devices"] == 7
            for ctx, png in zip(ctxs, results):
                decoded = np.array(Image.open(io.BytesIO(png)))
                r = ctx.region
                np.testing.assert_array_equal(
                    decoded,
                    img[0, 0, 0, r.y : r.y + r.height,
                        r.x : r.x + r.width],
                )
        finally:
            svc.close()


class _Svc:  # pipeline construction needs only the signature probe
    def get_pixel_buffer(self, image_id):
        return None


class TestCompilationCache:
    """runtime/jax_cache: the cache is placed from outside.
    ``JAX_COMPILATION_CACHE_DIR`` set -> JAX's own handling, no
    directory set in code; unset -> the config key
    ``jax.compilation-cache-dir`` on any backend, else one fixed path
    inside the checkout, TPU backend only."""

    @pytest.fixture
    def fresh_cache(self, monkeypatch):
        """Un-engage the module for the test; put jax's process-global
        cache settings back afterwards."""
        import jax
        from jax.experimental.compilation_cache import (
            compilation_cache,
        )

        from omero_ms_pixel_buffer_tpu.runtime import jax_cache

        names = (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs",
        )
        saved = {n: getattr(jax.config, n) for n in names}
        monkeypatch.setattr(jax_cache, "_enabled_path", None)
        monkeypatch.setattr(jax_cache, "_default_declined", False)
        monkeypatch.setattr(jax_cache, "_ignored", None)
        monkeypatch.delenv(jax_cache.ENV_VAR, raising=False)
        yield jax_cache
        for n, v in saved.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()

    def test_config_key_validated(self):
        from omero_ms_pixel_buffer_tpu.utils.config import (
            Config,
            ConfigError,
        )

        cfg = Config.from_dict(
            {"session-store": {"type": "memory"},
             "jax": {"compilation-cache-dir": "/tmp/x"}}
        )
        assert cfg.jax.compilation_cache_dir == "/tmp/x"
        with pytest.raises(ConfigError):
            Config.from_dict(
                {"session-store": {"type": "memory"},
                 "jax": {"compilation-cache-dir": 17}}
            )
        with pytest.raises(ConfigError):
            Config.from_dict(
                {"session-store": {"type": "memory"},
                 "jax": {"compilation-cache-dirr": "/tmp/x"}}
            )

    def test_second_pipeline_hits_cache_dir(self, tmp_path, fresh_cache):
        import jax

        from omero_ms_pixel_buffer_tpu.models.tile_pipeline import (
            TilePipeline,
        )

        cache_dir = str(tmp_path / "xla-cache")
        TilePipeline(_Svc(), compilation_cache_dir=cache_dir)
        assert fresh_cache.enabled_path() == cache_dir
        assert jax.config.jax_compilation_cache_dir == cache_dir
        # a device encode program persists into the dir...
        payload = np.zeros((1, 513), np.uint8)
        zlib_rle_batch(payload)
        entries = set(os.listdir(cache_dir))
        assert entries, "no compile-cache entries written"
        # ...and a second pipeline construction reuses the SAME dir
        # (idempotent enable), so a re-jit after dropping the in-
        # memory caches reloads from disk instead of recompiling
        TilePipeline(_Svc(), compilation_cache_dir=cache_dir)
        assert fresh_cache.enabled_path() == cache_dir
        jax.clear_caches()
        zlib_rle_batch(payload)
        assert set(os.listdir(cache_dir)) == entries, (
            "second run recompiled instead of hitting the cache dir"
        )

    def test_env_var_places_cache_and_code_sets_no_dir(
        self, tmp_path, fresh_cache, monkeypatch
    ):
        import jax

        env_dir = str(tmp_path / "from-env")
        # what jax itself does with the variable at import time
        monkeypatch.setenv(fresh_cache.ENV_VAR, env_dir)
        jax.config.update("jax_compilation_cache_dir", env_dir)
        updates = []
        real_update = jax.config.update
        monkeypatch.setattr(
            jax.config, "update",
            lambda name, val: (
                updates.append(name), real_update(name, val)
            ),
        )
        fresh_cache.enable_persistent_cache(str(tmp_path / "from-config"))
        assert "jax_compilation_cache_dir" not in updates
        assert fresh_cache.enabled_path() == env_dir
        assert not (tmp_path / "from-config").exists()
        jax.clear_caches()
        zlib_rle_batch(np.zeros((1, 515), np.uint8))
        assert os.listdir(env_dir), "the env-placed dir did not fill"

    def test_default_is_in_checkout_and_tpu_only(self, fresh_cache):
        import jax

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert fresh_cache.DEFAULT_DIR == os.path.join(repo, ".jax_cache")
        before = jax.config.jax_compilation_cache_dir
        fresh_cache.enable_persistent_cache(None)  # CPU backend here
        assert fresh_cache.enabled_path() is None
        assert jax.config.jax_compilation_cache_dir == before
        # a declined default never blocks a later configured opt-in
        assert fresh_cache._default_declined


# ---------------------------------------------------------------------------
# Dynamic-Huffman two-pass encode (r12)
# ---------------------------------------------------------------------------


def _dyn_corpus(n: int = 1500):
    """Randomized + pathological lanes for the dynamic bitstream: runs,
    no-runs, white noise, single-value, skewed alphabets."""
    r = np.random.default_rng(97)
    return np.stack([
        np.zeros(n, np.uint8),                          # all one run
        r.integers(0, 256, n).astype(np.uint8),         # white noise
        np.tile(np.array([5, 9], np.uint8), (n + 1) // 2)[:n],  # no runs
        np.repeat(r.integers(0, 4, (n + 39) // 40), 40)[:n].astype(
            np.uint8
        ),                                              # long runs
        (r.integers(0, 4, n) ** 3 % 7).astype(np.uint8),  # skewed alphabet
        np.arange(n, dtype=np.uint64).view(np.uint8)[:n],  # structured
    ])


class TestDynamicHuffman:
    """The two-pass canonical-code path: decode-exactness over the
    corpus, the per-lane min(dynamic, fixed, stored) guarantee, and the
    ratio win on low-run content it exists for."""

    # incl. single-byte + >64K
    @pytest.mark.parametrize("n", [1, 2, 3, 257, 1500, 70000])
    def test_randomized_corpus_decodes_exact(self, n):
        from omero_ms_pixel_buffer_tpu.ops.device_deflate import (
            zlib_dynamic_batch,
        )

        batch = _dyn_corpus(1500)[:, :n] if n <= 1500 else np.stack(
            [np.resize(lane, n) for lane in _dyn_corpus(1500)]
        )
        streams, lengths = (
            np.asarray(a) for a in zlib_dynamic_batch(batch)
        )
        for i in range(batch.shape[0]):
            got = zlib.decompress(bytes(streams[i][: lengths[i]]))
            assert got == batch[i].tobytes(), i

    def test_selection_never_exceeds_stored_bound(self):
        from omero_ms_pixel_buffer_tpu.ops.device_deflate import (
            zlib_dynamic_batch,
        )

        r = np.random.default_rng(11)
        for trial in range(6):
            n = int(r.integers(1, 4000))
            batch = np.stack([
                r.integers(0, 256, n).astype(np.uint8),
                np.tile(np.array([1, 2], np.uint8), (n + 1) // 2)[:n],
                r.integers(0, 2, n).astype(np.uint8),
            ])
            _, lengths = zlib_dynamic_batch(batch)
            assert (
                np.asarray(lengths) <= stored_stream_len(n)
            ).all(), (trial, n)

    def test_dynamic_never_worse_than_fixed(self):
        from omero_ms_pixel_buffer_tpu.ops.device_deflate import (
            zlib_dynamic_batch,
        )

        batch = _dyn_corpus(2000)
        _, dyn = zlib_dynamic_batch(batch)
        _, rle = zlib_rle_batch(batch)
        assert (np.asarray(dyn) <= np.asarray(rle)).all()

    def test_ratio_bound_on_rendered_rgb(self):
        """THE acceptance pin: <= 1.10x host zlib-6 bytes on the
        rendered-RGB fixture (the fixed-Huffman stream measured ~1.4x
        there)."""
        import jax.numpy as jnp

        from omero_ms_pixel_buffer_tpu.ops.convert import (
            to_big_endian_bytes,
        )
        from omero_ms_pixel_buffer_tpu.ops.device_deflate import (
            fused_filter_deflate_dynamic,
        )
        from omero_ms_pixel_buffer_tpu.ops.png import filter_batch

        b, tile = 4, 128
        rgb = _synth_rgb_tiles(b, tile, tile, seed=5)
        rows = 1 + tile * 3
        _, lengths = fused_filter_deflate_dynamic(rgb, tile, rows, 3)
        filt = np.asarray(filter_batch(
            to_big_endian_bytes(jnp.asarray(rgb)).reshape(
                b, tile, tile * 3
            ),
            3, "up",
        ))
        host = np.array(
            [len(zlib.compress(filt[i].tobytes(), 6)) for i in range(b)]
        )
        ratio = float(np.asarray(lengths, np.int64).mean() / host.mean())
        assert ratio <= 1.10, f"dynamic ratio {ratio:.3f} > 1.10x host"

    def test_deflate_filtered_batch_dynamic_mode(self):
        from omero_ms_pixel_buffer_tpu.ops.pallas.filter import (
            filter_tiles,
        )

        tiles = rng.integers(0, 60000, (3, 32, 32)).astype(np.uint16)
        filtered = filter_tiles(tiles, "up")
        streams, lengths = (
            np.asarray(a)
            for a in deflate_filtered_batch(
                filtered, 32, 1 + 64, mode="dynamic"
            )
        )
        payloads = np.asarray(filtered)[:, :32, : 1 + 64]
        for i in range(3):
            got = zlib.decompress(bytes(streams[i][: lengths[i]]))
            assert got == payloads[i].tobytes()

# ---------------------------------------------------------------------------
# Streaming cross-batch encode queue (r12)
# ---------------------------------------------------------------------------


class TestStreamingQueue:
    """The persistent submit/readback queue: bounded in-flight groups,
    non-blocking submission, clean drain, cross-batch reuse, and
    byte-identity with the direct fused encode."""

    def _dispatcher(self, queue_depth=2):
        from omero_ms_pixel_buffer_tpu.models.device_dispatch import (
            DeviceEncodeDispatcher,
        )

        return DeviceEncodeDispatcher({}, queue_depth=queue_depth)

    def _tiles(self, b=2, n=16):
        return rng.integers(0, 60000, (b, n, n)).astype(np.uint16)

    def _submit(self, disp, tiles, mode="rle"):
        b, n = tiles.shape[0], tiles.shape[1]
        return disp.submit(
            tiles, n, 1 + n * 2, 2, "up", mode,
            list(range(b)), [(n, n)] * b, 16, 0,
        )

    def test_groups_resolve_to_pngs(self):
        disp = self._dispatcher()
        try:
            tiles = self._tiles()
            for mode in ("rle", "dynamic", "stored"):
                out = self._submit(disp, tiles, mode).result(timeout=120)
                assert set(out) == {0, 1}
                for i, png in out.items():
                    decoded = np.array(Image.open(io.BytesIO(png)))
                    np.testing.assert_array_equal(decoded, tiles[i])
        finally:
            disp.close()

    def test_bounded_inflight_and_nonblocking_submit(self):
        """queue_depth bounds the groups in flight: with the readback
        worker wedged, the third group's staging must WAIT (on the
        queue's submit thread, not the caller), and the caller-facing
        submit returns immediately."""
        import threading
        import time as _time

        from omero_ms_pixel_buffer_tpu.models import device_dispatch as dd

        disp = self._dispatcher(queue_depth=2)
        gate = threading.Event()
        real = dd.DeviceEncodeDispatcher._readback_group

        def gated(self, *args, **kwargs):
            gate.wait(timeout=60)
            return real(self, *args, **kwargs)

        try:
            disp._readback_group = gated.__get__(disp)
            tiles = self._tiles()
            t0 = _time.perf_counter()
            futs = [self._submit(disp, tiles) for _ in range(3)]
            submit_dt = _time.perf_counter() - t0
            assert submit_dt < 5.0, "submit must not block the caller"
            deadline = _time.perf_counter() + 30
            while disp._groups < 2 and _time.perf_counter() < deadline:
                _time.sleep(0.01)
            _time.sleep(0.2)  # give group 3 a chance to (wrongly) launch
            assert disp._groups == 2, "3rd group launched past the bound"
            assert disp._inflight == 2
            gate.set()
            for fut in futs:
                assert set(fut.result(timeout=120)) == {0, 1}
            snap = disp.snapshot()
            assert snap["groups"] == 3
            assert snap["inflight"] == 0
        finally:
            gate.set()
            disp.close()

    def test_close_drains_pending_groups(self):
        disp = self._dispatcher()
        tiles = self._tiles()
        futs = [self._submit(disp, tiles) for _ in range(3)]
        disp.close()  # must DRAIN, not abandon
        for fut in futs:
            assert set(fut.result(timeout=5)) == {0, 1}
        with pytest.raises(RuntimeError):
            self._submit(disp, tiles)

    def test_close_drain_deadline_on_wedged_group(self):
        """A group wedged inside the device wait must not hold close()
        hostage: past the drain deadline the leftover futures resolve
        exceptionally (callers host-fall-back) and close() returns."""
        import threading
        import time as _time

        disp = self._dispatcher(queue_depth=2)
        gate = threading.Event()
        real = disp._readback_group

        def wedged(*args, **kwargs):
            gate.wait(timeout=60)  # simulates a hung device program
            return real(*args, **kwargs)

        disp._readback_group = wedged
        try:
            tiles = self._tiles()
            futs = [self._submit(disp, tiles) for _ in range(3)]
            t0 = _time.perf_counter()
            disp.close(drain_timeout=0.5)
            assert _time.perf_counter() - t0 < 10.0, (
                "close() blocked past the drain deadline"
            )
            for fut in futs:
                with pytest.raises(TimeoutError):
                    fut.result(timeout=5)
        finally:
            # unwedge so the abandoned worker threads exit (their late
            # set_result loses the race benignly — the guarded path)
            gate.set()

    def test_cross_batch_queue_persistence(self, ):
        """Consecutive handle_batch calls feed the SAME queue: the
        dispatcher (and its telemetry) survives the batcher boundary."""
        from omero_ms_pixel_buffer_tpu.models.tile_pipeline import (
            TilePipeline,
        )

        pipe, img = _mini_pipeline()
        try:
            ctxs = _mini_ctxs(4)
            pipe.handle_batch(ctxs[:2])
            disp1 = pipe._dispatcher
            g1 = disp1._groups
            assert disp1 is not None and g1 >= 1
            pipe.handle_batch(ctxs[2:])
            assert pipe._dispatcher is disp1, "queue rebuilt per batch"
            assert disp1._groups > g1, "second batch bypassed the queue"
        finally:
            pipe.close()
            pipe.pixels_service.close()

    def test_byte_identity_vs_direct_fused_encode(self):
        """The queue path's PNGs are byte-identical to framing the
        fused program's streams directly (the r05 single-batch path):
        the queue changes WHEN work runs, never what it computes."""
        from omero_ms_pixel_buffer_tpu.ops.device_deflate import (
            fused_filter_deflate_batch,
        )
        from omero_ms_pixel_buffer_tpu.ops.png import frame_png

        for mode in ("rle", "dynamic"):
            disp = self._dispatcher()
            try:
                tiles = self._tiles(b=3, n=16)
                out = self._submit(disp, tiles, mode).result(timeout=120)
                streams, lengths = (
                    np.asarray(a) for a in fused_filter_deflate_batch(
                        tiles, 16, 1 + 32, 2, mode=mode
                    )
                )
                for i in range(3):
                    direct = frame_png(
                        streams[i][: lengths[i]].tobytes(), 16, 16, 16, 0
                    )
                    assert out[i] == direct, (mode, i)
            finally:
                disp.close()


def _mini_pipeline():
    """A tiny device pipeline over a generated OME-TIFF (module-level
    so several suites share it without the class fixture plumbing)."""
    import tempfile

    from omero_ms_pixel_buffer_tpu.io.ometiff import write_ome_tiff
    from omero_ms_pixel_buffer_tpu.io.pixels_service import (
        ImageRegistry,
        PixelsService,
    )
    from omero_ms_pixel_buffer_tpu.models.tile_pipeline import TilePipeline

    root = tempfile.mkdtemp(prefix="ompb_queue_")
    path = os.path.join(root, "img.ome.tiff")
    img = rng.integers(0, 60000, (1, 1, 1, 128, 128), dtype=np.uint16)
    write_ome_tiff(path, img, tile_size=(64, 64))
    registry = ImageRegistry()
    registry.add(1, path)
    svc = PixelsService(registry)
    pipe = TilePipeline(
        svc, engine="device", device_deflate=True, buckets=(64,)
    )
    pipe.mesh = None
    return pipe, img


def _mini_ctxs(n):
    from omero_ms_pixel_buffer_tpu.tile_ctx import RegionDef, TileCtx

    coords = [(0, 0), (64, 0), (0, 64), (64, 64)]
    return [
        TileCtx(image_id=1, z=0, c=0, t=0,
                region=RegionDef(*coords[i % 4], 64, 64), format="png",
                omero_session_key="k")
        for i in range(n)
    ]


@pytest.mark.resilience
class TestQueueChaos:
    """Chaos lane: a wedged in-flight group degrades THAT group to the
    host fallback without stalling or reordering later batches."""

    @pytest.fixture(autouse=True)
    def _clean(self):
        from omero_ms_pixel_buffer_tpu.resilience import INJECTOR

        yield
        INJECTOR.clear()

    def test_wedged_group_degrades_to_host_without_stalling(self):
        from omero_ms_pixel_buffer_tpu.resilience import INJECTOR
        from omero_ms_pixel_buffer_tpu.resilience.faultinject import (
            first_n,
        )

        pipe, img = _mini_pipeline()
        try:
            # wedge exactly the FIRST group the queue ever stages
            INJECTOR.install(
                "device.encode-group",
                first_n(1, RuntimeError("wedged in-flight group")),
            )
            ctxs = _mini_ctxs(4)
            results = pipe.handle_batch(ctxs[:2])
            assert all(r is not None for r in results), (
                "wedged group must host-fall-back, not 404"
            )
            for ctx, png in zip(ctxs[:2], results):
                decoded = np.array(Image.open(io.BytesIO(png)))
                r = ctx.region
                np.testing.assert_array_equal(
                    decoded,
                    img[0, 0, 0, r.y : r.y + r.height,
                        r.x : r.x + r.width],
                )
            # later batches flow through the SAME queue unharmed
            results2 = pipe.handle_batch(ctxs[2:])
            assert all(r is not None for r in results2)
            assert INJECTOR.calls("device.encode-group") >= 2
        finally:
            pipe.close()
            pipe.pixels_service.close()


@pytest.mark.resilience
class TestMeshResizeWarmup:
    """A probe-shrink (or heal) changes the padded batch width; the
    dispatcher must pre-warm known group shapes for the NEW width on a
    background thread instead of paying the compile inline."""

    @pytest.fixture(autouse=True)
    def _clean(self):
        from omero_ms_pixel_buffer_tpu.resilience import BOARD, INJECTOR

        yield
        INJECTOR.clear()
        BOARD.reset()
        BOARD.configure(enabled=True)

    def test_width_change_prewarms_seen_shapes(self):
        import jax

        from omero_ms_pixel_buffer_tpu.models.device_dispatch import (
            DeviceEncodeDispatcher,
        )
        from omero_ms_pixel_buffer_tpu.parallel.mesh import MeshManager
        from omero_ms_pixel_buffer_tpu.resilience import INJECTOR
        from omero_ms_pixel_buffer_tpu.resilience.faultinject import (
            first_n,
        )

        devices = jax.devices()
        assert len(devices) == 8
        mgr = MeshManager(devices=devices)
        mgr.mesh()  # establish the 8-wide baseline
        disp = DeviceEncodeDispatcher({}, mesh_manager=mgr)
        try:
            tiles = rng.integers(0, 60000, (8, 16, 16)).astype(np.uint16)
            out = disp.submit(
                tiles, 16, 1 + 32, 2, "up", "rle",
                list(range(8)), [(16, 16)] * 8, 16, 0,
            ).result(timeout=120)
            assert len(out) == 8
            assert disp._seen_mesh, "mesh group shape not registered"
            # chip 3 fails its probe -> width 8 -> 7 -> warmup fires
            INJECTOR.install(
                f"device.chip:{devices[3].id}",
                first_n(1, RuntimeError("dead chip")),
            )
            assert mgr.probe_device(devices[3]) is False
            warm = getattr(disp, "_warm_thread", None)
            assert warm is not None, "width change spawned no warmup"
            warm.join(timeout=120)
            assert any(w == 7 for (w, _) in disp._warmed), (
                "no shape pre-warmed for the shrunken width"
            )
            # the chip heals -> width back to 8 -> warmup again
            assert mgr.probe_device(devices[3]) is True
            warm = disp._warm_thread
            warm.join(timeout=120)
            assert any(w == 8 for (w, _) in disp._warmed)
        finally:
            disp.close()
