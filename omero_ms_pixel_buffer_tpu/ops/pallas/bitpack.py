"""Pallas TPU kernels: deflate token bit-packing in VMEM.

The scan packer (ops/device_deflate._pack_bits_scan) expresses bit
packing as cumsums + a count of the tokens below each word edge (one
scatter, one running maximum) + gathers — all XLA ops, and is what every
backend runs. The kernels here are the intended
TPU-native alternative: one lane's packed words stay RESIDENT in VMEM
across a sequential grid walk over fixed-size token blocks, so the
emit is a chain of small dense block computations with zero HBM
traffic for intermediates. NEITHER LOWERS ON THE v5e TODAY (Mosaic
refuses the (1, TB) block shape, the in-kernel 1-D gathers and the
unaligned dynamic output strip — KNOWN_GAPS, ROADMAP S1,
tests/test_chip_compile.py), so they run in interpret mode on the CPU
backend only and are reachable by the explicit ``OMPB_BITPACK`` name.
Two formulations:

``pack_tokens_sp`` — the r12 scalar-prefetch kernel (packer name
"pallas"). The per-block starting bit offsets are
precomputed OUTSIDE the kernel (one XLA cumsum over the token bit
counts) and handed to a ``pltpu.PrefetchScalarGridSpec`` as the
scalar-prefetch operand, so every grid step knows its word window
before the body runs. In-kernel, the dense (SPAN x TB) one-hot
compare-reduce of the r9 kernel is replaced by **token-window
gathers**: block-local prefix sums of the word-aligned token
contributions (log-step, int32 wrap-exact) plus a log2(TB)-step
branchless binary search that finds, per output word, how many tokens
start below its edge — each output word then GATHERS two prefix-sum
boundary values instead of comparing against every token. Work per
block drops from O(SPAN * TB) compare-select-add cells to
O(TB log TB + SPAN log TB); see ``emit_ops_per_token`` for the pinned
analytical comparison the microbench records.

``pack_tokens`` — the r9 dense-emit kernel, kept as the pinned
comparison point (packer name "pallas_dense"): per grid step the
block's words are two (SPAN, TB) compare-mask reductions — carry-free
sums, because token bit ranges are disjoint.

Both kernels OR their SPAN-word strip into the lane's VMEM-resident
output at a dynamic word offset and handle zero-length tokens (run
interiors, header padding) with no compaction. ``interpret=True``
runs the same kernels on CPU; tier-1 tests pin their streams
bit-exact against the XLA scan packer and ``zlib.decompress``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tokens per block. Smaller blocks shrink the dense compare (total
# work is ntok * SPAN), larger blocks amortize per-step overhead.
_TB = 256
# Max deflate token bit count: a DYNAMIC match = 15-bit code + 5 extra
# + 1-bit distance (a fixed match is 8 + 5 + 5 = 18).
_MAX_TOKEN_BITS = 21
# Words one block can touch: TB tokens * MAX bits, +31 bits of initial
# misalignment, +1 spill word.
_SPAN = (_TB * _MAX_TOKEN_BITS + 31) // 32 + 2
_LOG_TB = _TB.bit_length() - 1


def emit_ops_per_token(kind: str) -> float:
    """Analytical int-op count per token for the in-kernel emit —
    the pinned microbench comparison (runtime constants, not a
    measurement, so the claim survives noisy CI boxes).

    - ``dense``: the (SPAN, TB) one-hot emit touches every
      (word, token) cell twice (start + spill), ~3 ops per touch
      (compare, select, add), plus the log-step offset cumsum.
    - ``sp``: three log-step block prefix sums over TB lanes, plus
      per WORD a log2(TB)-step binary search (~4 ops per step:
      gather, compare, select, add) and two boundary gathers,
      amortized over the block's TB tokens.
    """
    if kind == "dense":
        return 2 * 3 * _SPAN + 2 * _LOG_TB
    if kind == "sp":
        per_block = (
            3 * 2 * _LOG_TB * _TB          # inc/tl/th log-step cumsums
            + _SPAN * (4 * _LOG_TB + 8)    # binary search + 2 gathers
            + 6 * _TB                      # shift/mask/split elementwise
        )
        return per_block / _TB
    raise ValueError(f"unknown emit kind: {kind}")


def _shift_right(v, by: int):
    """Values ``by`` lanes earlier along the last axis (zero fill) —
    the doubling step of the in-kernel prefix sum. ``pltpu.roll``
    wraps, so the leading lanes are re-zeroed with an iota mask."""
    rolled = pltpu.roll(v, by, 1)
    idx = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    return jnp.where(idx < by, 0, rolled)


def _cumsum_lanes(v):
    """Inclusive log-step prefix sum along the last axis (int32,
    wrapping — mod-2^32 exact, which is all the carry-free packer
    math needs)."""
    k = 1
    while k < v.shape[-1]:
        v = v + _shift_right(v, k)
        k *= 2
    return v


# ---------------------------------------------------------------------------
# r12 kernel: scalar-prefetched block offsets + token-window gathers
# ---------------------------------------------------------------------------


def _kernel_sp(base_ref, bits_ref, nbits_ref, out_ref):
    lb = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    # the scalar-prefetched block bit offset replaces the r9 kernel's
    # SMEM carry: the window placement is known before the body runs
    base = base_ref[lb, i]
    nb = nbits_ref[...]  # (1, TB) int32
    val = bits_ref[...].astype(jnp.int32)  # <= 20 significant bits
    inc = _cumsum_lanes(nb)
    offs = base + inc - nb  # global exclusive bit offsets, sorted
    s = offs & 31
    lo = val << s  # int32 shift wraps mod 2^32: exact bit pattern
    # logical right shift by 32-s without s=0 UB; val is non-negative
    hi = (val >> (31 - s)) >> 1
    wstart = base >> 5
    # block-local inclusive prefix sums of the word contributions
    tl = _cumsum_lanes(lo)
    th = _cumsum_lanes(hi)
    offs_f = offs.reshape(_TB)
    tl_f = tl.reshape(_TB)
    th_f = th.reshape(_TB)
    # c[w] = tokens starting below word w's upper edge — a branchless
    # binary search over the sorted offsets, log2(TB) gather steps for
    # ALL SPAN words at once (vs comparing every token against every
    # word in the dense kernel)
    edge = (
        wstart + 1 + jax.lax.broadcasted_iota(jnp.int32, (1, _SPAN), 1)
    ) * 32
    c = jnp.zeros((1, _SPAN), jnp.int32)
    k = _TB
    while k >= 1:
        cand = c + k
        probe = jnp.take(offs_f, jnp.clip(cand - 1, 0, _TB - 1))
        c = jnp.where((cand <= _TB) & (probe < edge), cand, c)
        k //= 2
    # token-window gathers: per word, the covering tokens are the
    # contiguous range [c[w-1], c[w]) (starts) and [c[w-2], c[w-1])
    # (spill from the word below) — sums recovered from the prefix
    # sums at the three boundaries
    cm = jnp.clip(c - 1, 0, _TB - 1)
    gl = jnp.where(c > 0, jnp.take(tl_f, cm), 0)
    gh = jnp.where(c > 0, jnp.take(th_f, cm), 0)
    gl1 = _shift_right(gl, 1)
    gh1 = _shift_right(gh, 1)
    gh2 = _shift_right(gh, 2)
    acc = (gl - gl1) + (gh1 - gh2)
    strip = (slice(0, 1), pl.ds(wstart, _SPAN))
    out_ref[strip] = out_ref[strip] | acc


@partial(jax.jit, static_argnames=("maxbits", "interpret"))
def pack_tokens_sp(
    bits: jax.Array, nbits: jax.Array, maxbits: int,
    interpret: bool = False,
):
    """Batched token arrays (B, ntok) -> ((B, maxbits // 8) uint8
    LSB-first packed bytes, (B,) int32 body bit totals) via the
    scalar-prefetch token-window kernel. Zero-length tokens contribute
    nothing and need no compaction; the token axis pads to the block
    size with zero tokens."""
    b, ntok = bits.shape
    pad = (-ntok) % _TB
    if pad:
        widths = ((0, 0), (0, pad))
        bits = jnp.pad(bits, widths)
        nbits = jnp.pad(nbits, widths)
    nblocks = (ntok + pad) // _TB
    nwords = maxbits // 32
    nw_pad = nwords + _SPAN  # headroom so the last strip stays in-bounds
    # the scalar-prefetch operand: every block's starting bit offset,
    # one XLA cumsum — computable ahead of the walk, unlike the r9
    # kernel's sequentially-carried SMEM scalar
    offs_excl = jnp.cumsum(nbits, axis=1, dtype=jnp.int32) - nbits
    base = offs_excl[:, ::_TB].astype(jnp.int32)  # (B, nblocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nblocks),
        in_specs=[
            pl.BlockSpec((1, _TB), lambda lb, i, base_ref: (lb, i)),
            pl.BlockSpec((1, _TB), lambda lb, i, base_ref: (lb, i)),
        ],
        out_specs=pl.BlockSpec(
            (1, nw_pad), lambda lb, i, base_ref: (lb, 0)
        ),
    )
    words = pl.pallas_call(
        _kernel_sp,
        out_shape=jax.ShapeDtypeStruct((b, nw_pad), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(base, bits, nbits)
    shifts = (jnp.arange(4, dtype=jnp.int32) * 8)[None, None, :]
    packed = (
        ((words[:, :nwords, None] >> shifts) & 0xFF)
        .astype(jnp.uint8)
        .reshape(b, nwords * 4)
    )
    return packed, jnp.sum(nbits, axis=1, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# r9 kernel: dense (SPAN, TB) one-hot emit — the pinned comparison
# ---------------------------------------------------------------------------


def _kernel(bits_ref, nbits_ref, out_ref, off_ref):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        # fresh lane: zero the resident output strip and the carry
        out_ref[...] = jnp.zeros_like(out_ref)
        off_ref[0] = 0

    nb = nbits_ref[...]  # (1, TB) int32
    val = bits_ref[...].astype(jnp.int32)
    inc = _cumsum_lanes(nb)
    base = off_ref[0]
    offs = base + inc - nb  # global exclusive bit offsets
    s = offs & 31
    lo = val << s  # int32 left shift wraps mod 2^32: exact bit pattern
    # logical right shift by 32-s without s=0 UB; val is non-negative
    hi = (val >> (31 - s)) >> 1
    wstart = base >> 5
    rel = (offs >> 5) - wstart  # in [0, SPAN-2]
    widx = jax.lax.broadcasted_iota(jnp.int32, (_SPAN, _TB), 0)
    relb = jnp.broadcast_to(rel.reshape(1, _TB), (_SPAN, _TB))
    # carry-free: token bit ranges are disjoint, so + == | per word
    acc = (
        jnp.where(relb == widx, jnp.broadcast_to(lo, (_SPAN, _TB)), 0)
        .sum(axis=1)
        + jnp.where(
            relb + 1 == widx, jnp.broadcast_to(hi, (_SPAN, _TB)), 0
        ).sum(axis=1)
    )
    strip = (slice(0, 1), pl.ds(wstart, _SPAN))
    out_ref[strip] = out_ref[strip] | acc.reshape(1, _SPAN)
    off_ref[0] = base + jnp.sum(nb)


@partial(jax.jit, static_argnames=("maxbits", "interpret"))
def pack_tokens(
    bits: jax.Array, nbits: jax.Array, maxbits: int,
    interpret: bool = False,
):
    """Batched token arrays (B, ntok) -> ((B, maxbits // 8) uint8
    LSB-first packed bytes, (B,) int32 body bit totals) via the r9
    dense-emit kernel (packer name "pallas_dense" — kept as the pinned
    comparison point for the scalar-prefetch kernel)."""
    b, ntok = bits.shape
    pad = (-ntok) % _TB
    if pad:
        widths = ((0, 0), (0, pad))
        bits = jnp.pad(bits, widths)
        nbits = jnp.pad(nbits, widths)
    nblocks = (ntok + pad) // _TB
    nwords = maxbits // 32
    nw_pad = nwords + _SPAN  # headroom so the last strip stays in-bounds
    words = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((b, nw_pad), jnp.int32),
        grid=(b, nblocks),
        in_specs=[
            pl.BlockSpec((1, _TB), lambda lb, i: (lb, i)),
            pl.BlockSpec((1, _TB), lambda lb, i: (lb, i)),
        ],
        out_specs=pl.BlockSpec((1, nw_pad), lambda lb, i: (lb, 0)),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(bits, nbits)
    shifts = (jnp.arange(4, dtype=jnp.int32) * 8)[None, None, :]
    packed = (
        ((words[:, :nwords, None] >> shifts) & 0xFF)
        .astype(jnp.uint8)
        .reshape(b, nwords * 4)
    )
    return packed, jnp.sum(nbits, axis=1, dtype=jnp.int32)
