"""shard_map tile pipelines — the multi-chip execution plane.

Two sharded programs cover the service's scaling axes (SURVEY.md §2.3,
§5.7):

- ``sharded_batch_filter`` — **data parallel**: a coalesced tile batch
  (B, H, W) shards its batch axis across chips; each chip runs the
  fused byteswap+filter kernel on its lanes. No collectives needed —
  the embarrassing parallelism of independent tile requests, mapped
  onto ICI instead of worker threads.

- ``distributed_filter_plane`` — **space parallel**: one huge plane
  (whole-slide full-plane request) shards its rows across chips. PNG's
  Up filter makes row r depend on row r-1, so each shard needs the
  last row of the previous shard: a single-row halo exchange via
  ``lax.ppermute`` over ICI, then every shard filters locally. This is
  the ring-attention-style neighbor exchange pattern applied to image
  filtering — O(W) bytes over ICI per chip for O(H·W/n) compute.

Both run under ``jit`` with explicit in/out shardings, so XLA inserts
exactly the collectives written here and nothing else.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
try:  # stable location (jax >= 0.6)
    from jax import shard_map
except ImportError:  # pragma: no cover
    from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.convert import to_big_endian_bytes
from ..ops.png import FILTER_UP, _filter_batch


@partial(jax.jit, static_argnums=(0, 2, 3, 4))
def _sharded_batch_filter(mesh, tiles, bpp, mode, axis):
    def local(tiles_blk):
        rows = to_big_endian_bytes(tiles_blk)
        if rows.ndim == 4:
            # (B, H, W, S*itemsize) interleaved sample bytes -> scanrows
            rows = rows.reshape(*rows.shape[:2], -1)
        return _filter_batch(rows, bpp, mode)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(axis),
    )
    return fn(tiles)


def sharded_batch_filter(
    mesh: Mesh,
    tiles: jax.Array,
    bpp: int,
    mode: str = "up",
    axis: str = "data",
) -> jax.Array:
    """Batch-parallel PNG prep: (B, H, W) grayscale or (B, H, W, S)
    interleaved-sample tiles -> (B, H, 1 + W*bpp) filtered scanlines,
    batch sharded over ``axis``; ``bpp`` is the full filter unit
    (samples * itemsize). B must be divisible by the axis size — pad
    partial batches with ``pad_batch`` first. Jit-cached per
    (mesh, shape, bpp, mode)."""
    return _sharded_batch_filter(mesh, tiles, bpp, mode, axis)


def pad_batch(tiles, multiple: int):
    """Pad the batch dimension up to a multiple with zero lanes;
    returns (padded, real_count). Padded lanes are sliced away after
    the sharded call."""
    b = tiles.shape[0]
    pad = (-b) % multiple
    if pad == 0:
        return tiles, b
    widths = [(0, pad)] + [(0, 0)] * (tiles.ndim - 1)
    return jnp.pad(tiles, widths), b


@partial(jax.jit, static_argnums=(0, 2, 3))
def _distributed_filter(mesh, plane, mode, axis):
    if mode != "up":
        raise ValueError("distributed filtering supports mode='up'")
    n = mesh.shape[axis]

    def local(plane_blk):
        # byteswap fused with the filter inside the sharded program
        rows_blk = to_big_endian_bytes(plane_blk)
        # halo: receive the last row of the previous shard (ring
        # neighbor exchange over ICI); shard 0 receives zeros since
        # PNG defines the row above the image as zero
        idx = jax.lax.axis_index(axis)
        last_row = rows_blk[-1:, :]
        prev_last = jax.lax.ppermute(
            last_row, axis, [(i, (i + 1) % n) for i in range(n)]
        )
        prev_last = jnp.where(idx == 0, jnp.zeros_like(prev_last), prev_last)
        # Up filter with the halo row prepended
        above = jnp.concatenate([prev_last, rows_blk[:-1, :]], axis=0)
        res = rows_blk - above
        filt = jnp.full((rows_blk.shape[0], 1), FILTER_UP, dtype=jnp.uint8)
        return jnp.concatenate([filt, res], axis=1)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=P(axis, None),
        out_specs=P(axis, None),
    )
    return fn(plane)


def distributed_filter_plane(
    mesh: Mesh,
    plane: jax.Array,
    mode: str = "up",
    axis: str = "data",
) -> jax.Array:
    """Space-parallel PNG prep for one huge plane: (H, W) native dtype,
    rows sharded over ``axis`` -> (H, 1 + W*itemsize) filtered
    scanlines, same sharding. H must be divisible by the axis size.
    One fused jitted program (byteswap + halo exchange + filter)."""
    return _distributed_filter(mesh, plane, mode, axis)


@partial(jax.jit, static_argnums=(0, 2, 3, 4, 5, 6, 7))
def _sharded_filter_deflate(
    mesh, tiles, rows, row_bytes, bpp, filter_mode, deflate_mode, axis
):
    from ..ops.device_deflate import filter_deflate_local

    fn = shard_map(
        lambda blk: filter_deflate_local(
            blk, rows, row_bytes, bpp, filter_mode, deflate_mode
        ),
        mesh=mesh,
        in_specs=P(axis),
        out_specs=(P(axis), P(axis)),
    )
    return fn(tiles)


def sharded_filter_deflate(
    mesh: Mesh,
    tiles: jax.Array,
    rows: int,
    row_bytes: int,
    bpp: int,
    filter_mode: str = "up",
    deflate_mode: str = "rle",
    axis: str = "data",
) -> tuple:
    """The REAL multi-chip encode dispatch: the fused byteswap +
    filter + deflate chain (ops/device_deflate.filter_deflate_local)
    mapped over the mesh with ``shard_map`` — each chip builds the
    complete zlib streams for its slice of the batch, and only
    compressed bytes ever leave the devices. Per-lane math is chip-
    independent (no collectives), so the sharded bytes are identical
    to the single-device bytes on the same lanes.

    tiles (B, H, W[, S]) with B divisible by the mesh axis (pad with
    ``pad_batch``) -> ((B, cap) uint8 streams, (B,) int32 lengths),
    both batch-sharded."""
    return _sharded_filter_deflate(
        mesh, tiles, rows, row_bytes, bpp, filter_mode, deflate_mode, axis
    )


@partial(jax.jit, static_argnums=(0, 4, 5, 6, 7, 8))
def _sharded_render_filter_deflate(
    mesh, planes, index_tables, color_luts, rows, row_bytes,
    filter_mode, deflate_mode, axis,
):
    from ..render.engine import render_filter_deflate_local

    fn = shard_map(
        lambda blk, tab, lut: render_filter_deflate_local(
            blk, tab, lut, rows, row_bytes, filter_mode, deflate_mode
        ),
        mesh=mesh,
        in_specs=(P(axis), P(), P()),  # tables replicate to every chip
        out_specs=(P(axis), P(axis)),
    )
    return fn(planes, index_tables, color_luts)


@partial(jax.jit, static_argnums=(0, 5, 6, 7, 8, 9))
def _sharded_render_filter_deflate_masked(
    mesh, planes, index_tables, color_luts, mask, rows, row_bytes,
    filter_mode, deflate_mode, axis,
):
    from ..render.engine import render_filter_deflate_local

    fn = shard_map(
        lambda blk, tab, lut, msk: render_filter_deflate_local(
            blk, tab, lut, rows, row_bytes, filter_mode, deflate_mode,
            mask=msk,
        ),
        mesh=mesh,
        # the (B, H, W) ROI mask batch shards WITH its lanes; only the
        # per-channel tables replicate
        in_specs=(P(axis), P(), P(), P(axis)),
        out_specs=(P(axis), P(axis)),
    )
    return fn(planes, index_tables, color_luts, mask)


def sharded_render_filter_deflate(
    mesh: Mesh,
    planes: jax.Array,
    index_tables,
    color_luts,
    rows: int,
    row_bytes: int,
    filter_mode: str = "up",
    deflate_mode: str = "rle",
    axis: str = "data",
    mask=None,
) -> tuple:
    """The multi-chip RENDER dispatch: the fused composite + filter +
    deflate chain (render/engine.render_filter_deflate_local) mapped
    over the mesh — each chip renders and compresses its slice of the
    lane batch, with the per-channel tables replicated over ICI. The
    per-lane math is integer-only and chip-independent, so sharded
    bytes are identical to single-device bytes on the same lanes.

    planes (B, C, H, W) unsigned with B divisible by the mesh axis
    (pad with ``pad_batch``) -> ((B, cap) uint8 streams, (B,) int32
    lengths), both batch-sharded. ``mask`` (optional) is a (B, H, W)
    uint8 ROI batch sharded along with its lanes — the mask multiply
    is pointwise int, so masked mesh bytes stay identical to the
    single-device and host-mirror bytes (masked groups no longer
    split to one chip)."""
    if mask is not None:
        return _sharded_render_filter_deflate_masked(
            mesh, planes, jnp.asarray(index_tables),
            jnp.asarray(color_luts), jnp.asarray(mask), rows,
            row_bytes, filter_mode, deflate_mode, axis,
        )
    return _sharded_render_filter_deflate(
        mesh, planes, jnp.asarray(index_tables),
        jnp.asarray(color_luts), rows, row_bytes, filter_mode,
        deflate_mode, axis,
    )


# ---------------------------------------------------------------------------
# Two-pass dynamic deflate on the mesh — the host Huffman-plan hop rides
# BETWEEN two sharded programs, so mesh lanes keep content-adaptive codes
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnums=(0, 2, 3, 4, 5, 6))
def _sharded_filter_histogram(
    mesh, tiles, rows, row_bytes, bpp, filter_mode, axis
):
    from ..ops.device_deflate import _filter_histogram_core

    fn = shard_map(
        lambda blk: _filter_histogram_core(
            blk, rows, row_bytes, bpp, filter_mode
        ),
        mesh=mesh,
        in_specs=P(axis),
        out_specs=(P(axis), P(axis), P(axis)),
    )
    return fn(tiles)


def sharded_filter_histogram(
    mesh: Mesh,
    tiles: jax.Array,
    rows: int,
    row_bytes: int,
    bpp: int,
    filter_mode: str = "up",
    axis: str = "data",
) -> tuple:
    """Dynamic pass 1 over the mesh: byteswap + PNG filter + flatten +
    per-lane symbol histogram as ONE sharded program. tiles (B, H,
    W[, S]) with B divisible by the axis -> ((B, L) uint8 payloads,
    (B, 286) int32 counts, (B,) extra-bit totals), all batch-sharded.
    The payloads STAY device-resident for pass 2; only the counts (a
    few KB) cross to the host for the per-lane Huffman plan."""
    return _sharded_filter_histogram(
        mesh, tiles, rows, row_bytes, bpp, filter_mode, axis
    )


@partial(jax.jit, static_argnums=(0, 10))
def _sharded_dynamic_emit(
    mesh, flat, hdr_b, hdr_n, lit_b, lit_n, ml_b, ml_n, eob_b, eob_n,
    axis,
):
    from ..ops.device_deflate import dynamic_emit_local

    fn = shard_map(
        dynamic_emit_local,
        mesh=mesh,
        # every emit table is (B, ...)-shaped along the lane axis, so
        # each chip carries ITS lanes' codes — no replication at all
        in_specs=tuple([P(axis)] * 9),
        out_specs=(P(axis), P(axis)),
    )
    return fn(flat, hdr_b, hdr_n, lit_b, lit_n, ml_b, ml_n, eob_b, eob_n)


def sharded_dynamic_emit(
    mesh: Mesh,
    flat: jax.Array,
    tables: tuple,
    axis: str = "data",
) -> tuple:
    """Dynamic pass 2 over the mesh: the per-lane-table emit
    (ops/device_deflate.dynamic_emit_local) sharded along the lane
    axis, with the 8 host-built table arrays sharded alongside their
    lanes. Per-lane math is chip-independent, so mesh dynamic bytes
    are identical to the single-device two-pass bytes on the same
    lanes."""
    table_dev = tuple(
        jax.device_put(t, NamedSharding(mesh, P(axis))) for t in tables
    )
    return _sharded_dynamic_emit(mesh, flat, *table_dev, axis)


# ---------------------------------------------------------------------------
# Mesh-fused super-tile: composite + carve + filter + deflate, sharded
# over per-chip overlapped sub-rects of the bounding rectangle
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnums=(0, 5, 6, 7, 8, 9, 10, 11))
def _sharded_supertile_carve_deflate(
    mesh, sub_stacks, index_tables, color_luts, coords, bh, bw,
    rows, row_bytes, filter_mode, deflate_mode, axis,
):
    from jax import lax

    from ..ops.device_deflate import _streams_core
    from ..ops.png import _filter_batch
    from ..render.engine import render_local

    def local(blk, coords_blk, tab, lut):
        # blk: (1, C, Hs, Ws) — this chip's overlapped sub-rect of the
        # super-tile; coords_blk: (1, L, 2) local (y, x) tile origins
        rgb = render_local(blk, tab, lut)[0]
        # pad beyond the sub-rect so an edge tile's static-size carve
        # never clamps (dynamic_slice would silently shift the origin);
        # pad pixels can only reach a carved tile's own pad region,
        # whose bytes the stream build slices away
        rgb = jnp.pad(rgb, ((0, bh), (0, bw), (0, 0)))

        def one(y0, x0):
            return lax.dynamic_slice(rgb, (y0, x0, 0), (bh, bw, 3))

        carved = jax.vmap(one)(coords_blk[0, :, 0], coords_blk[0, :, 1])
        scanrows = carved.reshape(carved.shape[0], bh, bw * 3)
        filtered = _filter_batch(scanrows, 3, filter_mode)
        flat = filtered[:, :rows, :row_bytes].reshape(
            filtered.shape[0], -1
        )
        return _streams_core(flat, deflate_mode)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P()),
        out_specs=(P(axis), P(axis)),
    )
    return fn(sub_stacks, coords, index_tables, color_luts)


def sharded_supertile_carve_deflate(
    mesh: Mesh,
    sub_stacks: jax.Array,
    index_tables,
    color_luts,
    coords: jax.Array,
    bh: int,
    bw: int,
    filter_mode: str = "up",
    deflate_mode: str = "rle",
    axis: str = "data",
) -> tuple:
    """The mesh-fused super-tile chain: each chip composites ITS
    overlapped sub-rect of the super-tile bounding rectangle, carves
    its lanes' (bh, bw) tiles out with a vmapped ``dynamic_slice``,
    PNG-filters, and deflates — composite through zlib stream as ONE
    sharded program, with only the per-channel tables replicated.

    ``sub_stacks`` is (n_chips, C, Hs, Ws) unsigned — the per-chip
    sub-rect windows (overlap between neighboring chips' windows IS
    the halo: the composite itself is pointwise, so the halo exists
    purely so every lane's rectangle lies wholly inside one chip's
    window). ``coords`` is (n_chips, L, 2) int32 per-chip local
    (y, x) tile origins, slot-padded with (0, 0) dummies. Returns
    ((n_chips*L, cap) uint8 streams, (n_chips*L,) int32 lengths) in
    chip-major slot order — the caller keeps only its real slots.

    Byte identity is the single-device fused argument verbatim: the
    composite is pointwise (a pixel's value cannot depend on which
    sub-rect rendered it), PNG filters reference only up/left inside
    the carved tile, and the stream consumes exactly the tile's
    sliced scanline bytes."""
    return _sharded_supertile_carve_deflate(
        mesh, sub_stacks, jnp.asarray(index_tables),
        jnp.asarray(color_luts), coords, bh, bw, bh, 1 + bw * 3,
        filter_mode, deflate_mode, axis,
    )


def shard_batch(mesh: Mesh, tiles, axis: str = "data"):
    """Place a host batch onto the mesh with its batch dim sharded."""
    return jax.device_put(tiles, NamedSharding(mesh, P(axis)))


def shard_rows(mesh: Mesh, plane, axis: str = "data"):
    """Place a host plane onto the mesh with rows sharded."""
    return jax.device_put(plane, NamedSharding(mesh, P(axis, None)))
