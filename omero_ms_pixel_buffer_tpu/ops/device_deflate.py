"""Deflate on the accelerator — the encode hot loop moved on-device.

The reference compresses every PNG on a JVM worker thread inside
Bio-Formats (TileRequestHandler.java:176-199). The TPU-native split
kept deflate on the host (zlib / the native fast_deflate pool) because
deflate is byte-serial — until this module: a **complete zlib stream
built on device** with static shapes, in two modes:

- ``rle`` (default): a data-parallel reformulation of zlib's Z_RLE
  match policy + fixed-Huffman coding. Maximal runs of identical bytes
  become distance-1 matches (literal head + length-3..258 matches,
  short tails literal), found with associative scans (cummax/cummin)
  instead of a serial scan; every position gets ONE token index
  (0-255 a literal, 256 + L a match of length L, 515 none) and looks
  up one packed ``bits | nbits << 20`` table of the fixed-Huffman
  code with it, not by a gather (one element at a time on the chip)
  but densely: the table's byte columns times the one-hot of the index
  over the 516 kinds, on the MXU; token bit offsets are
  an exclusive cumsum; and the bitstream is packed by the **carry-free
  prefix-sum packer** (``_pack_bits_scan``): because tokens occupy
  disjoint bit ranges, the sum of their word-aligned contributions has
  no carries, so each output word is an exact difference of wrapping
  prefix sums — two cumsums over tokens, the token count below each
  word boundary (``_tokens_below_edges``: one scatter of each word's
  last token and one running maximum over the words), two monotone
  gathers. O(tokens + words) work with no loop, no sort and no wide
  gather windows. It is the one packer: plain XLA, the same program
  on every backend (``_pack_bits_scan_np`` is its numpy twin, the
  tests' reference). Up-filtered microscopy tiles are run-heavy, so
  this genuinely
  compresses (typically 2-4x) while leaving the host only PNG chunk
  framing. **Per lane**, if the RLE stream would come out larger than
  the stored-block encoding (pathological no-run payloads expand past
  9 bits/byte), the stored stream is emitted instead — every lane's
  length is bounded by ``stored_stream_len(L)``.
- ``dynamic`` (the r12 ratio path): a TWO-PASS canonical
  dynamic-Huffman encode. Pass 1 runs ON DEVICE fused with the PNG
  filter (``fused_filter_histogram_batch``): the same Z_RLE run
  decomposition, but instead of emitting code bits it histograms the
  token indices per lane (a dense count: the index in two digits, the
  516 bins as the product of the digits' one-hots over the positions;
  nothing scattered, no table looked up a position) and folds the 259
  per-length bins into the 29 length symbols and the match extra-bits
  by constant maps — only ``(B, 286)`` counts cross the link.
  The HOST then builds per-lane length-limited (15) canonical Huffman
  codes from the counts (heap build + frequency damping, the same
  algorithm as native/fast_deflate.cc), the RFC 1951 §3.2.7 dynamic
  block header (code-length tree, CL 16/17/18 run coding) as a
  zero-padded token array, and per-lane code TABLES. Pass 2 re-runs
  the decomposition on device, packs each lane's four tables into one
  token table there and emits through it, again one dense lookup a
  position — header tokens ++ body tokens ++ explicit EOB — into the
  same carry-free packer. Per lane the host picks min(dynamic, fixed)
  analytically from the counts BEFORE emitting (a fixed-winning lane
  just gets the fixed tables + 3-bit header), and the framing keeps
  the stored fallback, so every lane is min(dynamic, rle, stored) in
  ONE emit dispatch and no content regresses past
  ``stored_stream_len``. Closes the 1.38x-of-host-bytes gap on
  low-run (rendered-RGB) content to ~parity with host zlib level 6.
- ``stored``: BTYPE=00 stored blocks — no compression, but the
  simplest possible spec-valid stream; kept as the paranoia fallback
  and as the reference point in tests.

Both modes compute adler32 on device with chunked modular arithmetic
(the weighted byte sum overflows int32 unless reduced every few dozen
bytes — weights are pre-reduced mod 65521 and partial sums folded per
chunk).

Shapes are static per payload length L, so each distinct tile size
compiles once:

    payloads (B, L) uint8 -> streams (B, max_stream_len(L)) uint8,
                             lengths (B,) int32

``fused_filter_deflate_batch`` additionally fuses the byteswap + PNG
scanline filter into the SAME jit program, so the device encode chain
is one dispatch from native-dtype tiles to complete zlib streams (and
``filter_deflate_local`` exposes the un-jitted core for ``shard_map``
in parallel/sharding.py).

Correctness contract: ``zlib.decompress(bytes(streams[i][:lengths[i]]))``
equals the input payload for every lane AND ``lengths[i] <=
stored_stream_len(L)`` — pinned against the CPU backend in
tests/test_device_deflate.py.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .kernel_scope import kernel

_MOD = 65521  # largest prime < 2^16 (adler32 modulus)
_BLOCK = 65535  # max stored-block payload (16-bit LEN)
_MAX_MATCH = 258  # deflate maximum match length

# chunk sizes chosen so int32 partial sums cannot overflow:
# s1: 255 * 8192 ~ 2.1e6 << 2^31
# s2: terms are (weight mod 65521) * byte <= 65520*255 ~ 1.67e7;
#     128 of them ~ 2.1e9 is the int32 edge, so use 64
_S1_CHUNK = 8192
_S2_CHUNK = 64


# ---------------------------------------------------------------------------
# Fixed-Huffman code tables (RFC 1951 §3.2.6), precomputed on host.
# Huffman codes are emitted MSB-first into deflate's LSB-first bit
# stream, so the table stores them pre-bit-reversed; extra bits append
# above the code (they are emitted LSB-first as-is). A match token's
# bits include the 5-bit distance-1 code (symbol 0 -> reversed 0, so it
# contributes only to the bit count).
# ---------------------------------------------------------------------------


def _bit_reverse(code: int, nbits: int) -> int:
    r = 0
    for _ in range(nbits):
        r = (r << 1) | (code & 1)
        code >>= 1
    return r


_LEN_BASE = [3, 4, 5, 6, 7, 8, 9, 10, 11, 13, 15, 17, 19, 23, 27, 31,
             35, 43, 51, 59, 67, 83, 99, 115, 131, 163, 195, 227, 258]
_LEN_EXTRA = [0, 0, 0, 0, 0, 0, 0, 0, 1, 1, 1, 1, 2, 2, 2, 2,
              3, 3, 3, 3, 4, 4, 4, 4, 5, 5, 5, 5, 0]
_NUM_LITLEN = 286  # 0-255 literals, 256 EOB, 257-285 length symbols


def _length_code_index(length: int) -> int:
    """RFC 1951 length -> index into the 29-entry length-code rows."""
    if length == _MAX_MATCH:
        return 28  # code 285, exact, 0 extra
    return max(
        k for k in range(28)
        if _LEN_BASE[k] <= length
        and length < _LEN_BASE[k] + (1 << _LEN_EXTRA[k])
    )


def _build_tables():
    lit_bits = np.zeros(256, np.uint32)
    lit_nbits = np.zeros(256, np.int32)
    for v in range(256):
        if v < 144:
            code, n = 0x30 + v, 8
        else:
            code, n = 0x190 + (v - 144), 9
        lit_bits[v] = _bit_reverse(code, n)
        lit_nbits[v] = n

    match_bits = np.zeros(_MAX_MATCH + 1, np.uint32)
    match_nbits = np.zeros(_MAX_MATCH + 1, np.int32)
    # per match length: the SYMBOL id, the extra-bit count, and the
    # base offset — shared by the fixed emit, the dynamic histogram
    # pass, and the dynamic per-lane table build
    mlen_sym = np.zeros(_MAX_MATCH + 1, np.int32)
    mlen_extra = np.zeros(_MAX_MATCH + 1, np.int32)
    mlen_base = np.zeros(_MAX_MATCH + 1, np.int32)
    for length in range(3, _MAX_MATCH + 1):
        i = _length_code_index(length)
        symbol = 257 + i
        mlen_sym[length] = symbol
        mlen_extra[length] = _LEN_EXTRA[i]
        mlen_base[length] = _LEN_BASE[i]
        if symbol <= 279:
            rev, n = _bit_reverse(symbol - 256, 7), 7
        else:
            rev, n = _bit_reverse(0xC0 + (symbol - 280), 8), 8
        extra_val = length - _LEN_BASE[i]
        match_bits[length] = rev | (extra_val << n)
        # + len_extra extra bits + 5-bit distance code (value 0)
        match_nbits[length] = n + _LEN_EXTRA[i] + 5
    return (lit_bits, lit_nbits, match_bits, match_nbits,
            mlen_sym, mlen_extra, mlen_base)


(_LIT_BITS, _LIT_NBITS, _MATCH_BITS, _MATCH_NBITS,
 _MLEN_SYM, _MLEN_EXTRA, _MLEN_BASE) = _build_tables()

# fixed-Huffman CODE length per lit/len symbol (RFC 1951 §3.2.6) — the
# analytic side of the per-lane dynamic-vs-fixed decision
_FIXED_SYM_LEN = np.zeros(_NUM_LITLEN, np.int64)
_FIXED_SYM_LEN[:144] = 8
_FIXED_SYM_LEN[144:256] = 9
_FIXED_SYM_LEN[256:280] = 7
_FIXED_SYM_LEN[280:] = 8


def stored_stream_len(payload_len: int) -> int:
    """Total zlib-stream bytes for a stored-block encode of
    ``payload_len`` payload bytes."""
    nblocks = max(1, -(-payload_len // _BLOCK))
    return 2 + 5 * nblocks + payload_len + 4


def _packing_maxbits(payload_len: int) -> int:
    """Worst-case deflate bits (all-literal at 9 bits/byte + 3 header
    + 7 EOB), rounded up to a multiple of 1024: the capacity of every
    stream buffer."""
    raw = 3 + 9 * payload_len + 7
    return ((raw + 1023) // 1024) * 1024


def max_stream_len(payload_len: int) -> int:
    """Worst-case zlib-stream bytes for the RLE/fixed-Huffman encode:
    the packing capacity + 2-byte zlib header + 4-byte adler32."""
    return 2 + _packing_maxbits(payload_len) // 8 + 4


@kernel("ompb_frame")
def _adler32_lane(payload: jax.Array) -> jax.Array:
    """adler32 for one lane: (L,) uint8 -> uint32 scalar.

    s1 = (1 + sum d_i) mod 65521
    s2 = (L + sum (L - i) * d_i) mod 65521   (s2 accumulates s1 per
    byte, which telescopes to the weighted form)
    """
    n = payload.shape[0]
    data = payload.astype(jnp.int32)

    def chunked_mod_sum(values: jax.Array, chunk: int) -> jax.Array:
        pad = (-values.shape[0]) % chunk
        v = jnp.pad(values, (0, pad))
        parts = v.reshape(-1, chunk).sum(axis=1) % _MOD
        return parts.sum() % _MOD

    s1 = (1 + chunked_mod_sum(data, _S1_CHUNK)) % _MOD
    weights = jnp.asarray(
        (np.arange(n, 0, -1, dtype=np.int64) % _MOD).astype(np.int32)
    )
    s2 = (n % _MOD + chunked_mod_sum(data * weights, _S2_CHUNK)) % _MOD
    return (s2.astype(jnp.uint32) << 16) | s1.astype(jnp.uint32)


@kernel("ompb_frame")
def _adler_bytes(adler: jax.Array) -> jax.Array:
    return jnp.stack(
        [
            (adler >> 24).astype(jnp.uint8),
            (adler >> 16).astype(jnp.uint8),
            (adler >> 8).astype(jnp.uint8),
            adler.astype(jnp.uint8),
        ]
    )


# ---------------------------------------------------------------------------
# RLE + fixed-Huffman encode (the compressive path)
# ---------------------------------------------------------------------------


@kernel("ompb_tokens")
def _run_decompose(payload: jax.Array):
    """Z_RLE run decomposition without a serial scan.

    A maximal run of r identical bytes becomes: 1 literal head, then
    the match region of m = r-1 bytes split into chunks of <= 258;
    chunks >= 3 are (length, dist=1) matches, shorter tails are
    literals. Per byte position we derive, from two associative scans,
    whether it emits a token and which:

      start_pos  = cummax of run-start indices      (position of run head)
      next_start = reverse-cummin of later starts   (where the run ends)

    Returns per-position ``(is_lit, is_match, mlen)`` — the SAME
    decomposition (as ``_token_index``) feeds the fixed-Huffman emit,
    the dynamic histogram pass, and the dynamic emit, which is what
    makes pass 2 of the two-pass encode consistent with pass 1's
    counts by construction.
    """
    n = payload.shape[0]
    arange = jnp.arange(n, dtype=jnp.int32)
    same = jnp.concatenate(
        [jnp.zeros(1, bool), payload[1:] == payload[:-1]]
    )
    run_start = ~same
    start_pos = lax.cummax(jnp.where(run_start, arange, -1))
    p_in_run = arange - start_pos  # 0 at the run head
    starts = jnp.where(run_start, arange, n)
    after = jnp.concatenate([starts[1:], jnp.full(1, n, jnp.int32)])
    next_start = lax.cummin(after[::-1])[::-1]
    rem = next_start - arange  # bytes from here to run end, inclusive
    q = p_in_run - 1  # 0-based offset inside the match region
    qmod = q % _MAX_MATCH
    chunk_size = jnp.minimum(_MAX_MATCH, rem + qmod)
    is_lit = (p_in_run == 0) | (chunk_size < 3)
    is_match = (p_in_run >= 1) & (qmod == 0) & (chunk_size >= 3)
    mlen = jnp.clip(jnp.minimum(_MAX_MATCH, rem), 0, _MAX_MATCH)
    return is_lit, is_match, mlen


# One index a position names its token: 0-255 a literal of that value,
# 256 + L a distance-1 match of length L (3..258), _NO_TOKEN a position
# inside a match, which emits nothing. Every lookup below is by it.
_NO_TOKEN = 256 + _MAX_MATCH + 1
_TOKEN_KINDS = _NO_TOKEN + 1

# An indexed operation over the 516 kinds is a contraction with the
# one-hot of the token index: the chip runs a scatter-add or a gather
# one element at a time (5-10 ns each whatever the table, half of its
# busy time at PR 30) and a compare against every kind as dense work on
# the vector unit and the MXU. 0 / 1 and a byte are exact in bfloat16,
# one product a position is non-zero, and float32 sums stay exact below
# 2^24, so both contractions are integer-exact. A payload is cut into
# chunks whose tail is padded with _NO_TOKEN (a zero table entry, a bin
# that is thrown away); the chip's compiler fuses the compare into the
# product, so no one-hot reaches HBM.
_COUNT_CHUNK = 1 << 16  # positions a product of the count: < 2^24
_LOOKUP_CHUNK = 1 << 15  # positions an iteration of the lookup holds
_KIND_LO = 32  # the count's two digits: tok = 32 * hi + lo
_KIND_HI = -(-_TOKEN_KINDS // _KIND_LO)


def _token_chunks(tok: jax.Array, chunk: int) -> jax.Array:
    """(L,) token indices -> (ceil(L / chunk), chunk), the tail padded
    with ``_NO_TOKEN``."""
    pad = (-tok.shape[0]) % chunk
    return jnp.pad(tok, (0, pad), constant_values=_NO_TOKEN).reshape(
        -1, chunk
    )


@kernel("ompb_tokens")
def _token_index(payload: jax.Array) -> jax.Array:
    """(L,) uint8 -> (L,) int32 token index of each position, from the
    Z_RLE decomposition."""
    is_lit, is_match, mlen = _run_decompose(payload)
    return jnp.where(
        is_lit,
        payload.astype(jnp.int32),
        jnp.where(is_match, 256 + mlen, _NO_TOKEN),
    )


# Maximum significant bits in any token's code value: a FIXED match
# emits rev(code) | extra<<n with n <= 8 and extra < 2^5 (13 bits); a
# DYNAMIC match can reach 15-bit codes + 5 extra (20 bits). BIT COUNTS
# additionally include the distance code (5 bits fixed / 1 bit
# dynamic), whose bits are zero (symbol 0 reverses to 0). The packer
# only requires value < 2^32 and a <= 2-word span, which 20-bit values
# satisfy at any alignment; and a value and its count share one uint32
# of the token table (20 + 12 bits).
_TOKEN_VALUE_BITS = 20
_TOKEN_MAX_NBITS = 21


@kernel("ompb_tokens")
def _token_table(lit_b, lit_n, ml_b, ml_n) -> jax.Array:
    """The (516,) uint32 code table the token index looks up, ``bits |
    nbits << 20``: the 256 literals, the 259 match-length rows, and a
    zero for ``_NO_TOKEN`` (no bits, no length)."""
    def packed(b, n):
        return b.astype(jnp.uint32) | (
            n.astype(jnp.uint32) << _TOKEN_VALUE_BITS
        )

    return jnp.concatenate(
        [packed(lit_b, lit_n), packed(ml_b, ml_n), jnp.zeros(1, jnp.uint32)]
    )


@kernel("ompb_tokens")
def _coded_tokens(tok: jax.Array, table: jax.Array):
    """Per-position (bits, nbits) of the token indices ``tok`` under
    ``table``, as a dense lookup: the table's four byte columns (they
    carry all 32 bits of ``bits | nbits << 20``) times the one-hot of
    ``tok`` over the 516 kinds, on the MXU, the uint32 put together
    after; ``_LOOKUP_CHUNK`` positions an iteration, so the byte rows
    of one chunk are all it holds whatever the payload's length."""
    n = tok.shape[0]
    kinds = jnp.arange(_TOKEN_KINDS, dtype=jnp.int32)
    shifts = jnp.arange(4, dtype=jnp.uint32) * 8
    columns = ((table[None, :] >> shifts[:, None]) & 0xFF).astype(
        jnp.bfloat16
    )  # (4, 516)

    def lookup(chunk):
        one_hot = (chunk[None, :] == kinds[:, None]).astype(jnp.bfloat16)
        b = jnp.dot(
            columns, one_hot, preferred_element_type=jnp.float32
        ).astype(jnp.uint32)  # (4, chunk): exact bytes
        return b[0] | (b[1] << 8) | (b[2] << 16) | (b[3] << 24)

    g = lax.map(lookup, _token_chunks(tok, _LOOKUP_CHUNK)).reshape(-1)[:n]
    bits = g & jnp.uint32((1 << _TOKEN_VALUE_BITS) - 1)
    return bits, (g >> _TOKEN_VALUE_BITS).astype(jnp.int32)


@kernel("ompb_tokens")
def _rle_tokens(payload: jax.Array):
    """Per-position fixed-Huffman (bits, nbits) token arrays from the
    Z_RLE decomposition."""
    table = _token_table(_LIT_BITS, _LIT_NBITS, _MATCH_BITS, _MATCH_NBITS)
    return _coded_tokens(_token_index(payload), table)


def _tokens_below_edges(offs: jax.Array, nwords: int) -> jax.Array:
    """c[w] = how many tokens start below bit 32 * (w + 1): what
    ``searchsorted(offs, edges, side="left")`` answers, without the
    search. ``offs`` is non-decreasing, so the tokens below an edge are
    those whose word index ``offs >> 5`` is at most w, and the last of
    them is the last token of the nearest occupied word at or before
    w: each word's last token writes its position + 1 (one scatter;
    every other token, and any token at or beyond the last edge, aims
    out of range and is dropped), and a running maximum carries the
    count across the words no token starts in."""
    word = offs >> 5
    last_in_word = jnp.concatenate(
        [word[1:] != word[:-1], jnp.ones(1, bool)]
    )
    ends = jnp.zeros(nwords, jnp.int32).at[
        jnp.where(last_in_word, word, nwords)
    ].set(jnp.arange(1, offs.shape[0] + 1, dtype=jnp.int32), mode="drop")
    return lax.cummax(ends)


def _pack_bits_scan(bits: jax.Array, nbits: jax.Array, maxbits: int):
    """Carry-free prefix-sum bit packer: token (bits, nbits) arrays ->
    (LSB-first packed bytes, total body bits).

    Token bit ranges are disjoint, so within any output word the sum
    of token contributions equals their OR — no carries — and wrapping
    uint32 prefix sums recover exact per-word segment sums by
    subtraction (mod 2^32 differences of a carry-free segment are
    exact). Per token: its word-w part ``lo = val << (off & 31)`` and
    spill ``hi`` into word w+1 (values are <= 13 significant bits, so
    two words always suffice). Then

        words[w] =  (Tl[c[w]]   - Tl[c[w-1]])    # tokens starting in w
                 +  (Th[c[w-1]] - Th[c[w-2]])    # spill from w-1

    with Tl/Th the wrapping cumsums and c[w] the token count below
    each 32-bit boundary (``_tokens_below_edges``). Everything is a
    scan, one scatter, a monotone gather, or elementwise — no loop,
    no sort, no per-bit work. Zero-length tokens (run interiors)
    contribute zero and need no compaction."""
    zero = jnp.zeros(1, jnp.uint32)
    # second-level scopes: under `ompb_pack` a trace names the packer's
    # three steps (`searchsorted` is the boundary count: the name says
    # what the step answers, and the benchmark's reader knows it)
    with jax.named_scope("offsets"):
        offs = jnp.cumsum(nbits) - nbits  # exclusive; non-decreasing
        total_bits = offs[-1] + nbits[-1]
        s = (offs & 31).astype(jnp.uint32)
        val = bits.astype(jnp.uint32)
        lo = val << s
        # logical right shift by 32 - s without the s=0 UB: >> (31-s) >> 1
        hi = (val >> (jnp.uint32(31) - s)) >> jnp.uint32(1)
        tl = jnp.concatenate([zero, jnp.cumsum(lo)])  # (ntok+1,)
        th = jnp.concatenate([zero, jnp.cumsum(hi)])
    with jax.named_scope("searchsorted"):
        c = _tokens_below_edges(offs, maxbits // 32)
    with jax.named_scope("gather"):
        gl = tl[c]
        gh = th[c]
        gl1 = jnp.concatenate([zero, gl[:-1]])  # Tl[c[w-1]]
        gh1 = jnp.concatenate([zero, gh[:-1]])  # Th[c[w-1]]
        gh2 = jnp.concatenate([zero, gh1[:-1]])  # Th[c[w-2]]
        words = (gl - gl1) + (gh1 - gh2)
        shifts = (jnp.arange(4, dtype=jnp.uint32) * 8)[None, :]
        packed = ((words[:, None] >> shifts) & 0xFF).astype(jnp.uint8)
    return packed.reshape(-1), total_bits


@kernel("ompb_tokens")
def _lane_tokens(payload: jax.Array) -> tuple:
    """(L,) payload -> (L+1,) (bits, nbits) token arrays including the
    block-header token (BFINAL=1, BTYPE=01 -> LSB-first value 3)."""
    tok_bits, tok_nbits = _rle_tokens(payload)
    bits = jnp.concatenate([jnp.full(1, 3, jnp.uint32), tok_bits])
    nbits = jnp.concatenate([jnp.full(1, 3, jnp.int32), tok_nbits])
    return bits, nbits


@kernel("ompb_frame", static_argnames=("cap",))
def _stored_lane(payload: jax.Array, adler: jax.Array, cap: int):
    """One lane's stored-block zlib stream, zero-padded to ``cap``
    bytes — the per-lane fallback when RLE would expand past the
    stored bound."""
    n = payload.shape[0]
    nblocks = max(1, -(-n // _BLOCK))
    pieces = [jnp.asarray([0x78, 0x01], jnp.uint8)]
    for i in range(nblocks):
        start = i * _BLOCK
        size = min(_BLOCK, n - start)
        final = 1 if i == nblocks - 1 else 0
        header = np.array(
            [final, size & 0xFF, size >> 8,
             (size & 0xFF) ^ 0xFF, (size >> 8) ^ 0xFF],
            dtype=np.uint8,
        )
        pieces.append(jnp.asarray(header))
        pieces.append(payload[start : start + size])
    pieces.append(adler)
    stream = jnp.concatenate(pieces)
    return jnp.pad(stream, (0, cap - stream.shape[0]))


@kernel("ompb_frame", static_argnames=("eob_bits",))
def _frame_lane(payload: jax.Array, packed: jax.Array, body_bits,
                eob_bits: int = 7):
    """Zlib-frame one lane's packed deflate body, then pick per lane
    the smaller of the coded and stored streams (a coded stream on
    no-run content can expand past 9 bits/byte; the stored bound must
    hold for every lane): (stream padded to max_stream_len(L), true
    length). ``eob_bits``: the FIXED emit leaves the end-of-block
    symbol implicit (7-bit all-zero code, appended here as length
    only); the dynamic emit carries EOB as an explicit token and
    passes 0."""
    n = payload.shape[0]
    total_bits = body_bits + eob_bits
    deflate_nbytes = (total_bits + 7) // 8
    cap = 2 + packed.shape[0] + 4
    rle_len = 2 + deflate_nbytes + 4
    adler = _adler_bytes(_adler32_lane(payload))
    out = jnp.zeros(cap, jnp.uint8)
    out = out.at[0].set(0x78).at[1].set(0x01)
    out = lax.dynamic_update_slice(out, packed, (2,))
    out = lax.dynamic_update_slice(out, adler, (2 + deflate_nbytes,))
    stored_len = stored_stream_len(n)
    use_rle = rle_len <= stored_len
    out = jnp.where(use_rle, out, _stored_lane(payload, adler, cap))
    length = jnp.where(use_rle, rle_len, stored_len)
    return out, length.astype(jnp.int32)


@jax.jit
def _zlib_rle(payloads: jax.Array) -> tuple:
    # vmap, not lax.map: the scan packer is scans, one scatter and
    # monotone gathers, so batching lanes costs no extra residency.
    # Compiling is what costs: on the v5e the packer alone takes
    # 28-38 s at 1 lane and 49-59 s at 2 for the 512-tile shape
    # (PERF.md §6, PR 27)
    bits, nbits = jax.vmap(_lane_tokens)(payloads)
    maxbits = _packing_maxbits(payloads.shape[1])
    packed, body_bits = _pack_dispatch(bits, nbits, maxbits)
    return jax.vmap(_frame_lane)(payloads, packed, body_bits)


@kernel("ompb_pack", static_argnames=("maxbits",))
def _pack_dispatch(bits, nbits, maxbits: int):
    """Pack a batch's token arrays, a lane at a time under vmap."""
    return jax.vmap(
        lambda b, nb: _pack_bits_scan(b, nb, maxbits)
    )(bits, nbits)


# ---------------------------------------------------------------------------
# Stored-block encode (the paranoia fallback / test reference point)
# ---------------------------------------------------------------------------


def _adler32_device(payloads: jax.Array) -> jax.Array:
    """adler32 per lane: (B, L) uint8 -> (B,) uint32."""
    return jax.vmap(_adler32_lane)(payloads)


@kernel("ompb_frame")
def _stored_streams(payloads: jax.Array) -> jax.Array:
    b, n = payloads.shape
    nblocks = max(1, -(-n // _BLOCK))
    pieces = [
        jnp.broadcast_to(
            jnp.asarray([0x78, 0x01], jnp.uint8), (b, 2)
        )  # CM=8 CINFO=7, no preset dict, level check bits
    ]
    for i in range(nblocks):
        start = i * _BLOCK
        size = min(_BLOCK, n - start)
        final = 1 if i == nblocks - 1 else 0
        header = np.array(
            [final, size & 0xFF, size >> 8,
             (size & 0xFF) ^ 0xFF, (size >> 8) ^ 0xFF],
            dtype=np.uint8,
        )
        pieces.append(jnp.broadcast_to(jnp.asarray(header), (b, 5)))
        pieces.append(payloads[:, start : start + size])
    adler = _adler32_device(payloads)
    pieces.append(jax.vmap(_adler_bytes)(adler))
    return jnp.concatenate(pieces, axis=1)


_zlib_stored = jax.jit(_stored_streams)


def zlib_stored_batch(payloads) -> jax.Array:
    """Complete zlib streams (stored blocks) for a batch of equal-length
    payloads, built on device. (B, L) uint8 -> (B, stored_stream_len(L))
    uint8. jit-cached per L."""
    payloads = jnp.asarray(payloads, dtype=jnp.uint8)
    if payloads.ndim != 2:
        raise ValueError("payloads must be (B, L)")
    if payloads.shape[1] == 0:
        raise ValueError("empty payload")
    return _zlib_stored(payloads)


def zlib_rle_batch(payloads) -> tuple:
    """Compressive zlib streams (Z_RLE match policy, fixed Huffman,
    per-lane stored fallback) for a batch of equal-length payloads,
    built on device. (B, L) uint8 -> ((B, max_stream_len(L)) uint8,
    (B,) int32 lengths). jit-cached per L."""
    payloads = jnp.asarray(payloads, dtype=jnp.uint8)
    if payloads.ndim != 2:
        raise ValueError("payloads must be (B, L)")
    if payloads.shape[1] == 0:
        raise ValueError("empty payload")
    return _zlib_rle(payloads)


# ---------------------------------------------------------------------------
# Dynamic-Huffman encode (two-pass): device histogram -> host canonical
# codes + header tokens -> device emit with per-lane code tables
# ---------------------------------------------------------------------------

# Header token capacity: 1 (BFINAL|BTYPE) + 3 (HLIT/HDIST/HCLEN) + 19
# (CL code lengths) + <= 287 CL ops (hlit <= 286 literal/length lengths
# + 1 distance length, each op covering >= 1 entry) = 310; rounded up.
# A lane whose header would not fit (impossible by the bound, but the
# plan checks) simply takes the fixed tables.
_HDR_TOKENS = 320

_CL_ORDER = (16, 17, 18, 0, 8, 7, 9, 6, 10, 5, 11, 4, 12, 3, 13, 2, 14, 1, 15)


def _dyn_stats_lane(payload: jax.Array):
    """Pass 1 for one lane: (L,) uint8 -> ((286,) int32 literal/length
    symbol counts, () int32 total match extra bits). Counts the same
    token indices the emit pass looks up, so the counts describe
    exactly the tokens pass 2 will produce."""
    return _symbol_counts(_token_index(payload))


# match length -> its place among [EOB, the 29 length symbols]: the
# constant map that folds the per-length bins into symbol counts
_MLEN_FOLD = np.zeros((_MAX_MATCH + 1, _NUM_LITLEN - 256), np.int32)
_MLEN_FOLD[np.arange(3, _MAX_MATCH + 1), _MLEN_SYM[3:] - 256] = 1


@kernel("ompb_hist")
def _symbol_counts(tok: jax.Array):
    """The histogram half of pass 1 as a dense count: the token index
    in two digits, ``tok = 32 * hi + lo``, and the (17, 32) table of
    raw bins as the product ``one_hot(hi) . one_hot(lo)^T`` over the
    positions, on the MXU (float32 a chunk, int32 across chunks); no
    table looked up a position, nothing scattered. Literal bins are
    symbol counts as they stand; the 259 per-length bins fold into the
    29 length symbols, and weigh into the match extra bits, by
    constant maps (EOB is not a payload token: 0)."""
    chunks = _token_chunks(tok, _COUNT_CHUNK)

    def one_hot(digit, kinds):
        return (
            digit[:, None, :] == jnp.arange(kinds, dtype=jnp.int32)[:, None]
        ).astype(jnp.bfloat16)  # (chunks, kinds, chunk)

    part = lax.dot_general(
        one_hot(chunks // _KIND_LO, _KIND_HI),
        one_hot(chunks % _KIND_LO, _KIND_LO),
        (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # (chunks, 17, 32)
    raw = part.astype(jnp.int32).sum(axis=0).reshape(-1)
    by_len = raw[256:_NO_TOKEN]
    counts = jnp.concatenate([raw[:256], by_len @ jnp.asarray(_MLEN_FOLD)])
    return counts, by_len @ jnp.asarray(_MLEN_EXTRA)


@jax.jit
def _dyn_stats(payloads: jax.Array):
    return jax.vmap(_dyn_stats_lane)(payloads)


def _build_lengths_np(freq_in, limit: int) -> np.ndarray:
    """Length-limited canonical Huffman code lengths from symbol
    frequencies: heap tree build + frequency damping (halve-and-
    rebuild) until the depth fits — the native fast_deflate.cc
    algorithm, deterministic via (freq, insertion-order) heap keys."""
    import heapq

    n = len(freq_in)
    lengths = np.zeros(n, np.int32)
    freq = np.asarray(freq_in, np.int64).copy()
    while True:
        sym = np.flatnonzero(freq)
        if sym.size == 0:
            return lengths
        if sym.size == 1:
            lengths[:] = 0
            lengths[sym[0]] = 1
            return lengths
        heap = [(int(freq[s]), int(s), int(s)) for s in sym]
        heapq.heapify(heap)
        children = {}
        next_id = n
        while len(heap) > 1:
            fa, _, a = heapq.heappop(heap)
            fb, _, b = heapq.heappop(heap)
            children[next_id] = (a, b)
            heapq.heappush(heap, (fa + fb, next_id, next_id))
            next_id += 1
        lengths[:] = 0
        maxdepth = 0
        stack = [(heap[0][2], 0)]
        while stack:
            node, d = stack.pop()
            kids = children.get(node)
            if kids is None:
                lengths[node] = max(d, 1)
                maxdepth = max(maxdepth, max(d, 1))
            else:
                stack.append((kids[0], d + 1))
                stack.append((kids[1], d + 1))
        if maxdepth <= limit:
            return lengths
        freq[freq > 0] = (freq[freq > 0] + 1) >> 1  # damp, keep nonzero


def _build_codes_np(lengths: np.ndarray, max_len: int) -> np.ndarray:
    """Canonical codes from lengths (RFC 1951 §3.2.2), pre-bit-reversed
    for LSB-first emission."""
    bl_count = np.bincount(lengths, minlength=max_len + 1).astype(np.int64)
    bl_count[0] = 0
    next_code = np.zeros(max_len + 1, np.int64)
    code = 0
    for bits in range(1, max_len + 1):
        code = (code + int(bl_count[bits - 1])) << 1
        next_code[bits] = code
    codes = np.zeros(len(lengths), np.uint32)
    for i, ln in enumerate(lengths):
        if ln:
            codes[i] = _bit_reverse(int(next_code[ln]), int(ln))
            next_code[ln] += 1
    return codes


def _encode_code_lengths_np(lens: np.ndarray):
    """RFC 1951 §3.2.7 run coding of the code-length sequence with CL
    symbols 16/17/18 -> ([(sym, extra_bits, extra_val)], (19,) freq)."""
    ops = []
    cl_freq = np.zeros(19, np.int64)
    i, n = 0, len(lens)
    while i < n:
        v = int(lens[i])
        run = 1
        while i + run < n and lens[i + run] == v:
            run += 1
        if v == 0:
            while run >= 3:
                take = min(run, 138)
                if take >= 11:
                    ops.append((18, 7, take - 11))
                    cl_freq[18] += 1
                else:
                    ops.append((17, 3, take - 3))
                    cl_freq[17] += 1
                run -= take
                i += take
            while run > 0:
                ops.append((0, 0, 0))
                cl_freq[0] += 1
                i += 1
                run -= 1
        else:
            ops.append((v, 0, 0))
            cl_freq[v] += 1
            i += 1
            run -= 1
            while run >= 3:
                take = min(run, 6)
                ops.append((16, 2, take - 3))
                cl_freq[16] += 1
                run -= take
                i += take
            while run > 0:
                ops.append((v, 0, 0))
                cl_freq[v] += 1
                i += 1
                run -= 1
    return ops, cl_freq


def _lane_dynamic_plan(counts: np.ndarray, extra_bits: int):
    """One lane's dynamic-vs-fixed decision from the pass-1 counts.

    Returns ``None`` when the fixed tables win (both totals are exact
    bit counts computed analytically — no trial emit), else
    ``(header_tokens, lit_code, lit_len, ml_bits, ml_nbits, eob_bits,
    eob_len)`` ready to drop into the per-lane emit tables."""
    counts = counts.astype(np.int64)
    match_tokens = int(counts[257:].sum())
    any_run = match_tokens > 0
    freq = counts.copy()
    freq[256] = 1  # end-of-block (pass 1 histograms payload tokens only)
    lit_len = _build_lengths_np(freq, 15)
    # exact body bits: code bits per symbol + match extra bits + one
    # 1-bit distance code per match + the explicit EOB code
    dyn_body = (
        int((counts * lit_len.astype(np.int64)).sum())
        + int(extra_bits) + match_tokens + int(lit_len[256])
    )
    fixed_total = (
        3 + int((counts * _FIXED_SYM_LEN).sum())
        + int(extra_bits) + match_tokens * 5 + 7
    )
    # dynamic block header: BFINAL|BTYPE=10, HLIT/HDIST/HCLEN, the CL
    # tree, and the run-coded code-length sequence — all as <= 14-bit
    # tokens for the same packer the body goes through
    hlit = _NUM_LITLEN
    while hlit > 257 and lit_len[hlit - 1] == 0:
        hlit -= 1
    all_lens = np.concatenate(
        [lit_len[:hlit], np.asarray([1 if any_run else 0], np.int32)]
    )
    ops, cl_freq = _encode_code_lengths_np(all_lens)
    cl_len = _build_lengths_np(cl_freq, 7)
    nz = np.flatnonzero(cl_len)
    if nz.size == 1:
        # a single 1-bit CL code is an INCOMPLETE code-length tree,
        # which inflate rejects (incomplete sets are only legal for
        # single-code LENS/DISTS trees); a dummy 1-bit code on an
        # unused symbol completes it at zero body cost
        cl_len[0 if nz[0] != 0 else 1] = 1
    cl_code = _build_codes_np(cl_len, 7)
    hclen = 19
    while hclen > 4 and cl_len[_CL_ORDER[hclen - 1]] == 0:
        hclen -= 1
    hdr = [(5, 3), (hlit - 257, 5), (0, 5), (hclen - 4, 4)]
    hdr += [(int(cl_len[_CL_ORDER[k]]), 3) for k in range(hclen)]
    for s, eb, ev in ops:
        cn = int(cl_len[s])
        hdr.append((int(cl_code[s]) | (ev << cn), cn + eb))
    dyn_total = sum(t[1] for t in hdr) + dyn_body
    if dyn_total >= fixed_total or len(hdr) > _HDR_TOKENS:
        return None
    lit_code = _build_codes_np(lit_len, 15)
    ml_bits = np.zeros(_MAX_MATCH + 1, np.uint32)
    ml_nbits = np.zeros(_MAX_MATCH + 1, np.int32)
    for ln in range(3, _MAX_MATCH + 1):
        s = int(_MLEN_SYM[ln])
        cn = int(lit_len[s])
        if cn == 0:
            continue  # symbol absent from this lane: length never occurs
        ev = ln - int(_MLEN_BASE[ln])
        ml_bits[ln] = int(lit_code[s]) | (ev << cn)
        # + extra bits + the 1-bit distance-1 code (value 0)
        ml_nbits[ln] = cn + int(_MLEN_EXTRA[ln]) + 1
    return (
        hdr, lit_code[:256], lit_len[:256], ml_bits, ml_nbits,
        int(lit_code[256]), int(lit_len[256]),
    )


def _native_planner():
    """The native engine when it carries the plan (ABI v5), else None:
    ``build_dynamic_tables`` then plans in Python."""
    from ..runtime.native import get_engine

    engine = get_engine()
    return engine if engine is not None and engine.has_dynamic_plan else None


def plan_impl() -> str:
    """Which implementation ``build_dynamic_tables`` plans lanes with:
    ``"native"`` or ``"python"``."""
    return "python" if _native_planner() is None else "native"


def build_dynamic_tables(
    counts: np.ndarray, extras: np.ndarray, real: Optional[int] = None
):
    """Per-lane emit tables from the pass-1 stats: lanes where the
    canonical dynamic code wins get their own header tokens + code
    tables; lanes where fixed wins get the fixed tables and the 3-bit
    fixed header — ONE emit program serves both, so the per-lane
    min(dynamic, fixed) costs no extra dispatch. Only the first
    ``real`` lanes get a host Huffman plan (pow2 PAD lanes keep the
    prefilled fixed tables — their streams are discarded, so building
    codes for them would be pure waste on the plan worker). The plan
    is one GIL-released native call when the engine has it, with
    tables bit-identical to ``_lane_dynamic_plan``'s, which plans
    otherwise. Returns the 8-tuple of tables ``_zlib_dynamic`` takes
    after the payloads."""
    b = counts.shape[0]
    hdr_b = np.zeros((b, _HDR_TOKENS), np.uint32)
    hdr_n = np.zeros((b, _HDR_TOKENS), np.int32)
    # every lane starts as a valid FIXED emit (header BFINAL=1 BTYPE=01)
    hdr_b[:, 0] = 3
    hdr_n[:, 0] = 3
    lit_b = np.tile(_LIT_BITS, (b, 1))
    lit_n = np.tile(_LIT_NBITS, (b, 1))
    ml_b = np.tile(_MATCH_BITS, (b, 1))
    ml_n = np.tile(_MATCH_NBITS, (b, 1))
    eob_b = np.zeros(b, np.uint32)
    eob_n = np.full(b, 7, np.int32)  # fixed EOB: 7-bit all-zero code
    tables = hdr_b, hdr_n, lit_b, lit_n, ml_b, ml_n, eob_b, eob_n
    real = b if real is None else max(min(real, b), 0)
    engine = _native_planner()
    if engine is not None:
        if real:
            engine.dynamic_plan_batch(counts, extras, real, tables)
        return tables
    for i in range(real):
        plan = _lane_dynamic_plan(counts[i], int(extras[i]))
        if plan is None:
            continue  # fixed wins: the prefilled tables ARE the plan
        hdr, lcode, llen, mbits, mnbits, ebits, elen = plan
        hdr_b[i, 0] = hdr_n[i, 0] = 0
        for j, (v, nb) in enumerate(hdr):
            hdr_b[i, j], hdr_n[i, j] = v, nb
        lit_b[i], lit_n[i] = lcode, llen
        ml_b[i], ml_n[i] = mbits, mnbits
        eob_b[i], eob_n[i] = ebits, elen
    return tables


@kernel("ompb_tokens")
def _dyn_lane_tokens(payload, lit_b, lit_n, ml_b, ml_n):
    """Pass-2 body tokens for one lane through ITS code tables, packed
    on the device into one token table: one lookup a position."""
    return _coded_tokens(
        _token_index(payload), _token_table(lit_b, lit_n, ml_b, ml_n)
    )


@kernel("ompb_tokens")
def _dyn_tokens(payloads, hdr_b, hdr_n, lit_b, lit_n, ml_b, ml_n, eob_b, eob_n):
    """Pass-2 token arrays of a batch: header ++ body ++ explicit EOB."""
    body_b, body_n = jax.vmap(_dyn_lane_tokens)(
        payloads, lit_b, lit_n, ml_b, ml_n
    )
    bits = jnp.concatenate(
        [hdr_b, body_b, eob_b[:, None].astype(jnp.uint32)], axis=1
    )
    nbits = jnp.concatenate([hdr_n, body_n, eob_n[:, None]], axis=1)
    return bits, nbits


def dynamic_emit_local(
    payloads, hdr_b, hdr_n, lit_b, lit_n, ml_b, ml_n, eob_b, eob_n
):
    """Un-jitted pass-2 core: emit header ++ body ++ explicit EOB
    through the per-lane tables and pack. Traceable under jit, vmap,
    and shard_map — every table operand is (B, ...)-shaped along the
    lane axis, so parallel/sharding.py shards ALL of them with the
    payloads and each chip emits its slice with its lanes' own codes
    (what lets mesh lanes keep dynamic instead of downgrading to
    rle). Capacity argument: the host plan only selects dynamic when
    its exact total (header included) beats fixed, so every lane's
    bits fit the fixed worst-case ``_packing_maxbits`` and the stream
    cap stays ``max_stream_len(L)``."""
    bits, nbits = _dyn_tokens(
        payloads, hdr_b, hdr_n, lit_b, lit_n, ml_b, ml_n, eob_b, eob_n
    )
    maxbits = _packing_maxbits(payloads.shape[1])
    packed, body_bits = _pack_dispatch(bits, nbits, maxbits)
    return jax.vmap(partial(_frame_lane, eob_bits=0))(
        payloads, packed, body_bits
    )


_zlib_dynamic = jax.jit(dynamic_emit_local)


def zlib_dynamic_batch(payloads, real: Optional[int] = None) -> tuple:
    """Canonical dynamic-Huffman zlib streams (Z_RLE match policy,
    per-lane two-pass code construction, per-lane min(dynamic, fixed,
    stored) selection) for a batch of equal-length payloads. (B, L)
    uint8 -> ((B, max_stream_len(L)) uint8, (B,) int32 lengths). TWO
    device dispatches with one small (B, 286) host hop between — the
    price of content-adaptive codes. ``real`` bounds the host plan
    work to the leading real lanes (pad lanes keep the prefilled
    fixed tables); the full padded batch is still emitted."""
    payloads = jnp.asarray(payloads, dtype=jnp.uint8)
    if payloads.ndim != 2:
        raise ValueError("payloads must be (B, L)")
    if payloads.shape[1] == 0:
        raise ValueError("empty payload")
    counts, extras = _dyn_stats(payloads)
    counts_np, extras_np = jax.device_get((counts, extras))
    tables = build_dynamic_tables(counts_np, extras_np, real=real)
    return _zlib_dynamic(payloads, *tables)


def _streams_core(flat: jax.Array, mode: str):
    if mode == "stored":
        streams = _zlib_stored(flat)
        lengths = jnp.full(
            flat.shape[0], stored_stream_len(flat.shape[1]), jnp.int32
        )
        return streams, lengths
    return _zlib_rle(flat)


@kernel("ompb_filter", static_argnames=("rows", "row_bytes"))
def _flatten_rows(filtered: jax.Array, rows: int, row_bytes: int):
    """The leading rows x row_bytes region of each lane's filtered
    scanlines as one payload: (B, H, RB) -> (B, rows * row_bytes)."""
    return filtered[:, :rows, :row_bytes].reshape(filtered.shape[0], -1)


@kernel(
    "ompb_filter", static_argnames=("rows", "row_bytes", "bpp", "filter_mode")
)
def _filter_flat(tiles, rows: int, row_bytes: int, bpp: int, filter_mode: str):
    """The filter half of the fused chains: native-dtype tiles
    (B, H, W[, S]) -> big-endian byte rows -> filtered scanlines ->
    flat payloads (B, rows * row_bytes)."""
    from .convert import to_big_endian_bytes
    from .png import _filter_batch

    rows_be = to_big_endian_bytes(tiles)
    if rows_be.ndim == 4:
        # (B, H, W, S*itemsize) interleaved sample bytes -> scanrows
        rows_be = rows_be.reshape(*rows_be.shape[:2], -1)
    filtered = _filter_batch(rows_be, bpp, filter_mode)
    return _flatten_rows(filtered, rows, row_bytes)


@partial(jax.jit, static_argnums=(1, 2, 3))
def _filtered_to_streams(
    filtered: jax.Array, rows: int, row_bytes: int, mode: str
):
    flat = _flatten_rows(filtered, rows, row_bytes)
    return _streams_core(flat, mode)


def _pad_pow2_lanes(arr: jax.Array):
    """Pad the lane axis to a power of two: the encode program costs
    tens of seconds to compile per shape on TPU, and serving batches
    arrive in every size — pow2 padding caps the specializations at
    log2(max_batch) per payload length."""
    b = arr.shape[0]
    padded_b = 1 << max(b - 1, 0).bit_length()
    if padded_b != b:
        arr = jnp.pad(
            arr, ((0, padded_b - b),) + ((0, 0),) * (arr.ndim - 1)
        )
    return arr, b


_filtered_to_flat = jax.jit(_flatten_rows, static_argnums=(1, 2))


def deflate_filtered_batch(
    filtered: jax.Array, rows: int, row_bytes: int, mode: str = "rle"
) -> tuple:
    """Fuse the payload flatten with the stream build: filtered
    scanlines (B, H, 1 + W*itemsize) (device-resident, possibly
    bucket-padded) -> ((B, stream_cap) uint8 complete zlib streams,
    (B,) int32 true lengths) for the leading ``rows`` x ``row_bytes``
    region of each lane. Mode ``dynamic`` takes the two-pass path
    (device histogram, host code build, device emit)."""
    if mode not in ("rle", "stored", "dynamic"):
        raise ValueError(f"Unknown device deflate mode: {mode}")
    filtered, b = _pad_pow2_lanes(filtered)
    if mode == "dynamic":
        flat = _filtered_to_flat(filtered, rows, row_bytes)
        streams, lengths = zlib_dynamic_batch(flat, real=b)
    else:
        streams, lengths = _filtered_to_streams(
            filtered, rows, row_bytes, mode
        )
    return streams[:b], lengths[:b]


# ---------------------------------------------------------------------------
# Fused filter + deflate — the whole device encode chain in ONE jit
# ---------------------------------------------------------------------------


def filter_deflate_local(
    tiles: jax.Array, rows: int, row_bytes: int, bpp: int,
    filter_mode: str, mode: str,
):
    """Un-jitted fused core: native-dtype tiles (B, H, W[, S]) ->
    (streams, lengths). Traceable under jit, vmap, and shard_map —
    parallel/sharding.py maps exactly this over the mesh, which is
    what makes multi-chip bytes identical to single-device bytes."""
    flat = _filter_flat(tiles, rows, row_bytes, bpp, filter_mode)
    return _streams_core(flat, mode)


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _fused_filter_deflate(tiles, rows, row_bytes, bpp, filter_mode, mode):
    return filter_deflate_local(
        tiles, rows, row_bytes, bpp, filter_mode, mode
    )


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5), donate_argnums=(0,))
def _fused_filter_deflate_donated(
    tiles, rows, row_bytes, bpp, filter_mode, mode
):
    # identical program; the staged input buffer is donated so the
    # filter's big-endian intermediate reuses it instead of doubling
    # HBM residency per in-flight bucket (the double-buffered
    # dispatcher keeps two buckets in flight)
    return filter_deflate_local(
        tiles, rows, row_bytes, bpp, filter_mode, mode
    )


def fused_filter_deflate_batch(
    tiles: jax.Array, rows: int, row_bytes: int, bpp: int,
    filter_mode: str = "up", mode: str = "rle", donate: bool = False,
) -> tuple:
    """The device encode chain as ONE dispatched program: byteswap +
    PNG scanline filter + deflate, nothing surfacing between stages.
    tiles (B, H, W[, S]) native dtype -> ((B, cap) uint8 zlib streams,
    (B,) int32 lengths) for the leading ``rows`` x ``row_bytes``
    region. ``donate=True`` donates the input buffer (TPU; XLA ignores
    donation on backends that can't honor it). Mode ``dynamic``
    delegates to the two-pass chain (two dispatches + one small host
    hop; the streaming dispatcher drives the stages separately so the
    hop overlaps other groups' compute)."""
    if mode == "dynamic":
        return fused_filter_deflate_dynamic(
            tiles, rows, row_bytes, bpp, filter_mode=filter_mode,
            donate=donate,
        )
    if mode not in ("rle", "stored"):
        raise ValueError(f"Unknown device deflate mode: {mode}")
    tiles, b = _pad_pow2_lanes(tiles)
    fn = _fused_filter_deflate_donated if donate else _fused_filter_deflate
    streams, lengths = fn(tiles, rows, row_bytes, bpp, filter_mode, mode)
    return streams[:b], lengths[:b]


# -- dynamic two-pass entry points (the streaming dispatcher drives the
# stages separately so the counts hop overlaps other groups' compute) --


def _filter_histogram_core(tiles, rows, row_bytes, bpp, filter_mode):
    flat = _filter_flat(tiles, rows, row_bytes, bpp, filter_mode)
    counts, extras = jax.vmap(_dyn_stats_lane)(flat)
    return flat, counts, extras


_fused_filter_histogram = partial(jax.jit, static_argnums=(1, 2, 3, 4))(
    _filter_histogram_core
)
_fused_filter_histogram_donated = partial(
    jax.jit, static_argnums=(1, 2, 3, 4), donate_argnums=(0,)
)(_filter_histogram_core)


def fused_filter_histogram_batch(
    tiles: jax.Array, rows: int, row_bytes: int, bpp: int,
    filter_mode: str = "up", donate: bool = False,
) -> tuple:
    """Pass 1 of the dynamic encode as ONE dispatched program:
    byteswap + PNG filter + flatten + symbol histogram. Returns
    ``(flat, counts, extras, real_b)`` with the payload lanes pow2-
    padded — ``flat`` stays device-resident for pass 2; only
    ``counts``/``extras`` (a few KB) need to cross to the host."""
    tiles, b = _pad_pow2_lanes(tiles)
    fn = (
        _fused_filter_histogram_donated if donate
        else _fused_filter_histogram
    )
    flat, counts, extras = fn(tiles, rows, row_bytes, bpp, filter_mode)
    return flat, counts, extras, b


def dynamic_emit_batch(
    flat: jax.Array, counts_np: np.ndarray, extras_np: np.ndarray,
    real: Optional[int] = None,
) -> tuple:
    """Pass 2: host code/table build from the pulled counts, then the
    single emit dispatch. ``real`` bounds the host plan work to the
    real lanes AND slices the pow2 padding back off the outputs."""
    tables = build_dynamic_tables(
        np.asarray(counts_np), np.asarray(extras_np), real=real
    )
    return dynamic_emit_planned(flat, tables, real=real)


def dynamic_emit_planned(
    flat: jax.Array, tables: tuple, real: Optional[int] = None
) -> tuple:
    """Pass 2's single emit dispatch alone, from ``tables`` the caller
    has built (``build_dynamic_tables``): the device queue plans as a
    stage of its own and launches here without waiting. ``real``
    slices the pow2 padding back off the outputs."""
    streams, lengths = _zlib_dynamic(flat, *tables)
    if real is not None:
        return streams[:real], lengths[:real]
    return streams, lengths


def fused_filter_deflate_dynamic(
    tiles: jax.Array, rows: int, row_bytes: int, bpp: int,
    filter_mode: str = "up", donate: bool = False,
) -> tuple:
    """Both passes back to back (tests, non-streamed callers): pass
    1, ONE small host pull of the counts, pass 2."""
    flat, counts, extras, b = fused_filter_histogram_batch(
        tiles, rows, row_bytes, bpp, filter_mode=filter_mode,
        donate=donate,
    )
    counts_np, extras_np = jax.device_get((counts, extras))
    return dynamic_emit_batch(flat, counts_np, extras_np, real=b)


# ---------------------------------------------------------------------------
# Host (numpy) mirror of the RLE + fixed-Huffman stream — byte-identical
# ---------------------------------------------------------------------------


def _rle_tokens_np(payload: np.ndarray):
    """Numpy port of ``_rle_tokens`` (same run decomposition, same
    tables, same token order) — the host half of the byte-identity
    contract ``zlib_rle_np`` provides."""
    n = payload.shape[0]
    arange = np.arange(n, dtype=np.int64)
    same = np.concatenate(
        [np.zeros(1, bool), payload[1:] == payload[:-1]]
    )
    run_start = ~same
    start_pos = np.maximum.accumulate(np.where(run_start, arange, -1))
    p_in_run = arange - start_pos
    starts = np.where(run_start, arange, n)
    after = np.concatenate([starts[1:], np.full(1, n, np.int64)])
    next_start = np.minimum.accumulate(after[::-1])[::-1]
    rem = next_start - arange
    q = p_in_run - 1
    qmod = q % _MAX_MATCH
    chunk_size = np.minimum(_MAX_MATCH, rem + qmod)
    is_lit = (p_in_run == 0) | (chunk_size < 3)
    is_match = (p_in_run >= 1) & (qmod == 0) & (chunk_size >= 3)
    mlen = np.clip(np.minimum(_MAX_MATCH, rem), 0, _MAX_MATCH)
    bits = np.where(
        is_lit, _LIT_BITS[payload],
        np.where(is_match, _MATCH_BITS[mlen], 0),
    ).astype(np.uint32)
    nbits = np.where(
        is_lit, _LIT_NBITS[payload],
        np.where(is_match, _MATCH_NBITS[mlen], 0),
    ).astype(np.int64)
    return bits, nbits


def _pack_bits_scan_np(bits: np.ndarray, nbits: np.ndarray, maxbits: int):
    """Numpy port of the carry-free prefix-sum packer: identical word
    math on wrapping uint32 cumsums, so the packed bytes are identical
    to the device packer's."""
    offs = np.cumsum(nbits) - nbits
    total_bits = int(offs[-1] + nbits[-1])
    s = (offs & 31).astype(np.uint32)
    val = bits.astype(np.uint32)
    lo = val << s
    hi = (val >> (np.uint32(31) - s)) >> np.uint32(1)
    zero = np.zeros(1, np.uint32)
    tl = np.concatenate([zero, np.cumsum(lo, dtype=np.uint32)])
    th = np.concatenate([zero, np.cumsum(hi, dtype=np.uint32)])
    nwords = maxbits // 32
    edges = (np.arange(nwords, dtype=np.int64) + 1) * 32
    c = np.searchsorted(offs, edges, side="left")
    gl, gh = tl[c], th[c]
    gl1 = np.concatenate([zero, gl[:-1]])
    gh1 = np.concatenate([zero, gh[:-1]])
    gh2 = np.concatenate([zero, gh1[:-1]])
    words = (gl - gl1) + (gh1 - gh2)
    return words.astype("<u4").tobytes(), total_bits


def zlib_rle_np(payload) -> bytes:
    """Host (numpy) build of EXACTLY the stream the device encoder
    emits for one lane: Z_RLE tokenization + fixed Huffman + the
    carry-free packer + per-lane min(rle, stored) selection. This is
    what lets a host fallback stay byte-identical to the device path
    (the render engine's contract) instead of merely decoded-equal."""
    import zlib as _zlib

    data = np.frombuffer(payload, dtype=np.uint8) if isinstance(
        payload, (bytes, bytearray, memoryview)
    ) else np.ascontiguousarray(payload, dtype=np.uint8).ravel()
    n = data.shape[0]
    if n == 0:
        raise ValueError("empty payload")
    tok_bits, tok_nbits = _rle_tokens_np(data)
    bits = np.concatenate([np.full(1, 3, np.uint32), tok_bits])
    nbits = np.concatenate([np.full(1, 3, np.int64), tok_nbits])
    packed, body_bits = _pack_bits_scan_np(
        bits, nbits, _packing_maxbits(n)
    )
    total_bits = body_bits + 7  # + the 7-bit all-zero EOB code
    deflate_nbytes = (total_bits + 7) // 8
    rle_len = 2 + deflate_nbytes + 4
    stored_len = stored_stream_len(n)
    adler = (_zlib.adler32(data.tobytes()) & 0xFFFFFFFF).to_bytes(
        4, "big"
    )
    if rle_len <= stored_len:
        return b"\x78\x01" + packed[:deflate_nbytes] + adler
    out = bytearray(b"\x78\x01")
    nblocks = max(1, -(-n // _BLOCK))
    for i in range(nblocks):
        start = i * _BLOCK
        size = min(_BLOCK, n - start)
        final = 1 if i == nblocks - 1 else 0
        out += bytes(
            [final, size & 0xFF, size >> 8,
             (size & 0xFF) ^ 0xFF, (size >> 8) ^ 0xFF]
        )
        out += data[start : start + size].tobytes()
    out += adler
    return bytes(out)
