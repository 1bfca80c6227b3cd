"""The rendering engine: channel stacks -> composited RGB -> PNG/JPEG.

The OMERO rendering model (omeis.providers.re) per channel is

    dtype-normalize -> window/level -> (reverse) -> quantization
    (linear or gamma) -> LUT / solid color -> additive composite ->
    clamp to 8-bit RGB

Every per-channel stage up to the LUT is a pure function of the pixel
VALUE, so — exactly like OMERO's own QuantumStrategy — it folds into a
per-channel **value -> level lookup table** built once per
(spec, dtype) on the host in float64 (256 entries for 8-bit pixels,
65536 for 16-bit). The device program is then pure integer work:

    level = index_table[c][pixel]          # gather
    rgb   = color_lut[c][level]            # gather, (256, 3)
    out   = clamp(sum_c rgb, 255)          # int32 add + min

which makes the rendered pixels BYTE-IDENTICAL across the jitted
device program, the numpy host mirror, and the shard_map multi-chip
path — no float opcode ever runs on a device, so there is nothing to
drift. The fused serving program chains straight into the device PNG
encode (``ops/png._filter_batch`` + ``ops/device_deflate``): one
dispatch from native-dtype channel planes to complete zlib streams.
The host fallback mirrors the WHOLE chain (numpy render + numpy filter
+ ``zlib_rle_np``), so fallback PNGs are byte-identical too — one tile
has one ETag no matter which engine produced it.

JPEG output renders through the same tables and hands the RGB array to
Pillow (quality from the spec); both engines produce the same RGB, so
JPEG bytes also match across engines.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.device_deflate import (
    _pad_pow2_lanes,
    _streams_core,
    zlib_rle_np,
)
from ..ops.png import _filter_batch, filter_rows_np, frame_png
from ..utils.metrics import REGISTRY
from .luts import LUT_SIZE, LutRegistry
from .model import ChannelSpec, RenderSpec

RENDER_TILES = REGISTRY.counter(
    "render_tiles_total", "Rendered tiles by engine path and format"
)
RENDER_FALLBACK = REGISTRY.counter(
    "render_fallback_total",
    "Render lanes that fell back from the device engine to the host",
)
RENDER_SECONDS = REGISTRY.histogram(
    "render_seconds", "Render stage wall time (stage=tables|host|jpeg)"
)

# position-default channel colors when a spec names none (the OMERO
# viewer's conventional rotation); a single active channel defaults to
# grey like webgateway does
DEFAULT_COLORS: Tuple[Tuple[int, int, int], ...] = (
    (255, 0, 0), (0, 255, 0), (0, 0, 255),
    (255, 0, 255), (0, 255, 255), (255, 255, 0), (255, 255, 255),
)

MAX_COMPOSITE_CHANNELS = 16  # int32 composite headroom is ~8e6 — this
# bound exists for request sanity, not arithmetic safety


class RenderError(ValueError):
    """Unrenderable combination (pixel type, unknown LUT at build
    time) — surfaces as the pipeline's lane-level None -> 404."""


def unsigned_view(arr: np.ndarray) -> np.ndarray:
    """Reinterpret signed integer pixels as their two's-complement
    unsigned bit pattern (the index the device gathers with; the
    tables are built over the same mapping)."""
    if arr.dtype.kind == "i":
        return arr.view(arr.dtype.str.replace("i", "u"))
    return arr


def default_window(dtype: np.dtype) -> Tuple[float, float]:
    if dtype.kind == "u":
        return (0.0, float((1 << (8 * dtype.itemsize)) - 1))
    half = 1 << (8 * dtype.itemsize - 1)
    return (float(-half), float(half - 1))


def renderable_dtype(dtype: np.dtype) -> bool:
    """The engine's DIRECT table domain: integer pixels up to 16-bit
    (a value->table gather needs a bounded index space). Wider and
    float pixels render through ``quantize_to_u16`` instead."""
    dtype = np.dtype(dtype)
    return dtype.kind in "ui" and dtype.itemsize <= 2


def quantizable_dtype(dtype: np.dtype) -> bool:
    """Pixel types the engine windows through the host value->bin
    quantization (float32/float64/int32/uint32): the channel window
    maps values onto ``QUANT_BINS`` uint16 bins on the host, and the
    device program stays the same pure-integer gather chain."""
    dtype = np.dtype(dtype)
    return (
        dtype.kind in "uif"
        and dtype.itemsize in (4, 8)
        and not renderable_dtype(dtype)
    )


QUANT_BINS = 65536  # the quantized (u16) index space


def quantize_to_u16(
    plane: np.ndarray, window: Tuple[float, float]
) -> np.ndarray:
    """Window a float/int32 plane onto the uint16 bin space: clip to
    the window, scale to [0, 65535], round half-up — all in host
    float64, so every engine gathers from identical indices. NaNs map
    to bin 0 (below-window), infinities clip to the window edges."""
    lo, hi = float(window[0]), float(window[1])
    if not lo < hi or not (np.isfinite(lo) and np.isfinite(hi)):
        raise RenderError(f"Degenerate quantization window [{lo}:{hi}]")
    x = (plane.astype(np.float64) - lo) / (hi - lo)
    x = np.nan_to_num(x, nan=0.0, posinf=1.0, neginf=0.0)
    x = np.clip(x, 0.0, 1.0)
    return np.floor(x * float(QUANT_BINS - 1) + 0.5).astype(np.uint16)


def _channel_lut(
    ch: ChannelSpec,
    position: int,
    n_channels: int,
    greyscale: bool,
    registry: Optional[LutRegistry],
) -> np.ndarray:
    if greyscale:
        r = g = b = 255
    elif ch.lut is not None:
        table = registry.get(ch.lut) if registry is not None else None
        if table is None:
            raise RenderError(f"Unknown LUT: {ch.lut!r}")
        return np.asarray(table, dtype=np.uint8)
    elif ch.color is not None:
        r, g, b = (int(ch.color[i : i + 2], 16) for i in (0, 2, 4))
    elif n_channels == 1:
        r = g = b = 255
    else:
        r, g, b = DEFAULT_COLORS[position % len(DEFAULT_COLORS)]
    i = np.arange(LUT_SIZE, dtype=np.float64)
    return np.stack(
        [np.floor(i * c / 255.0 + 0.5) for c in (r, g, b)], axis=1
    ).astype(np.uint8)


def build_tables(
    spec: RenderSpec,
    dtype: np.dtype,
    registry: Optional[LutRegistry] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """(index_tables (C, K) uint8, color_luts (C, 256, 3) uint8) for
    the spec's composited channels over pixel type ``dtype``. All the
    float math of the rendering model happens HERE, in host float64 —
    the per-value table is the quantization, so every engine that
    gathers from these tables renders identical pixels."""
    dtype = np.dtype(dtype)
    if not renderable_dtype(dtype):
        raise RenderError(f"Unrenderable pixel type: {dtype}")
    channels = (
        spec.channels[:1] if spec.model == "g" else spec.channels
    )
    if len(channels) > MAX_COMPOSITE_CHANNELS:
        raise RenderError(
            f"{len(channels)} channels exceed the composite bound "
            f"({MAX_COMPOSITE_CHANNELS})"
        )
    k = 1 << (8 * dtype.itemsize)
    greyscale = spec.model == "g"
    with RENDER_SECONDS.time(stage="tables"):
        tables, luts = [], []
        u = np.arange(k, dtype=np.int64)
        values = (
            u if dtype.kind == "u" else ((u + k // 2) % k) - k // 2
        )
        for pos, ch in enumerate(channels):
            wmin, wmax = (
                ch.window if ch.window is not None
                else default_window(dtype)
            )
            if not wmin < wmax:
                raise RenderError(
                    f"Degenerate window [{wmin}:{wmax}]"
                )
            x = np.clip(
                (values.astype(np.float64) - wmin) / (wmax - wmin),
                0.0, 1.0,
            )
            if ch.reverse:
                x = 1.0 - x
            if ch.family in ("exponential", "polynomial"):
                # the gamma curve; "polynomial" is OMERO's canonical
                # name for it, "exponential" this service's historical
                # spelling — identical tables by design
                x = np.power(x, ch.coefficient)
            elif ch.family == "logarithmic":
                # normalized log map: log(1 + k*x) / log(1 + k);
                # monotone on [0, 1] with slope set by k (> 0,
                # validated at parse)
                x = np.log1p(ch.coefficient * x) / np.log1p(
                    ch.coefficient
                )
            tables.append(
                np.clip(np.floor(x * 255.0 + 0.5), 0, 255).astype(
                    np.uint8
                )
            )
            luts.append(
                _channel_lut(
                    ch, pos, len(channels), greyscale, registry
                )
            )
    return np.stack(tables), np.stack(luts)


# ---------------------------------------------------------------------------
# The composite core — traceable (jit / vmap / shard_map) AND a numpy
# mirror with identical integer semantics
# ---------------------------------------------------------------------------


def render_local(
    planes: jax.Array,
    index_tables: jax.Array,
    color_luts: jax.Array,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """(B, C, H, W) unsigned pixels + (C, K)/(C, 256, 3) tables ->
    (B, H, W, 3) uint8 composited RGB. Pure gathers + an int32 sum;
    un-jitted so parallel/sharding can shard_map it and the fused
    serving program can inline it. ``mask`` (B, H, W) uint8 0/1
    multiplies the composite (ROI masking): still pure integer ops,
    so masked lanes keep the byte-identity contract."""

    def one(tab, lut, plane):  # (K,), (256, 3), (B, H, W)
        return lut[tab[plane]].astype(jnp.int32)  # (B, H, W, 3)

    # composite exactly the tables' channels: the greyscale model
    # builds ONE table, and callers may hand the full stack
    contrib = jax.vmap(one, in_axes=(0, 0, 1))(
        index_tables, color_luts,
        planes[:, : index_tables.shape[0]],
    )  # (C, B, H, W, 3)
    comp = jnp.minimum(contrib.sum(axis=0), 255)
    if mask is not None:
        comp = comp * mask[:, :, :, None].astype(jnp.int32)
    return comp.astype(jnp.uint8)


def render_host(
    planes: np.ndarray,
    index_tables: np.ndarray,
    color_luts: np.ndarray,
    mask: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Numpy mirror of ``render_local`` for one lane: (C, H, W)
    unsigned pixels (+ optional (H, W) uint8 mask) -> (H, W, 3)
    uint8, byte-identical pixels."""
    acc = None
    for c in range(index_tables.shape[0]):  # greyscale: 1 table
        contrib = color_luts[c][index_tables[c][planes[c]]].astype(
            np.int32
        )
        acc = contrib if acc is None else acc + contrib
    comp = np.minimum(acc, 255)
    if mask is not None:
        comp = comp * mask[:, :, None].astype(np.int32)
    return comp.astype(np.uint8)


@jax.jit
def _render_batch(planes, index_tables, color_luts):
    return render_local(planes, index_tables, color_luts)


def render_batch(planes, index_tables, color_luts) -> jax.Array:
    """Jitted batched composite (no encode): (B, C, H, W) -> device-
    resident (B, H, W, 3) uint8."""
    return _render_batch(
        jnp.asarray(planes),
        jnp.asarray(index_tables),
        jnp.asarray(color_luts),
    )


# ---------------------------------------------------------------------------
# Fused render -> filter -> deflate: ONE device dispatch to zlib streams
# ---------------------------------------------------------------------------


def render_filter_deflate_local(
    planes: jax.Array,
    index_tables: jax.Array,
    color_luts: jax.Array,
    rows: int,
    row_bytes: int,
    filter_mode: str,
    mode: str,
    mask: Optional[jax.Array] = None,
):
    """Un-jitted fused core: unsigned channel planes (B, C, H, W) ->
    (streams, lengths) — composite, optional ROI mask multiply, PNG
    filter (bpp=3, RGB8 needs no byteswap), and the deflate stream
    build in one traceable body. shard_map maps exactly this over the
    mesh (parallel/sharding), so multi-chip bytes are identical to
    single-device bytes."""
    rgb = render_local(planes, index_tables, color_luts, mask)
    b, h = rgb.shape[0], rgb.shape[1]
    scanrows = rgb.reshape(b, h, -1)
    filtered = _filter_batch(scanrows, 3, filter_mode)
    flat = filtered[:, :rows, :row_bytes].reshape(b, -1)
    return _streams_core(flat, mode)


@partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _fused_render_filter_deflate(
    planes, index_tables, color_luts, rows, row_bytes, filter_mode,
    mode, mask,
):
    return render_filter_deflate_local(
        planes, index_tables, color_luts, rows, row_bytes,
        filter_mode, mode, mask,
    )


def fused_render_filter_deflate_batch(
    planes,
    index_tables,
    color_luts,
    rows: int,
    row_bytes: int,
    filter_mode: str = "up",
    mode: str = "rle",
    mask=None,
) -> tuple:
    """The render serving chain as ONE dispatched program. planes
    (B, C, H, W) unsigned (bucket-padded; pointwise rendering of pad
    pixels cannot reach the real region's filtered bytes — filters
    only look up/left) -> ((B, cap) uint8 zlib streams, (B,) int32
    lengths) for the leading ``rows`` x ``row_bytes`` of each lane.
    Lane axis pads to a power of two like every device encode program
    (compile-specialization cap)."""
    if mode not in ("rle", "stored"):
        raise ValueError(f"Unknown device deflate mode: {mode}")
    planes, b = _pad_pow2_lanes(jnp.asarray(planes))
    if mask is not None:
        # pad the mask's lane axis identically (pad lanes mask to 0 —
        # their bytes are sliced away regardless)
        mask, _ = _pad_pow2_lanes(jnp.asarray(mask))
    streams, lengths = _fused_render_filter_deflate(
        planes, jnp.asarray(index_tables), jnp.asarray(color_luts),
        rows, row_bytes, filter_mode, mode, mask,
    )
    return streams[:b], lengths[:b]


# ---------------------------------------------------------------------------
# Host fallback — the same chain, mirrored; byte-identical output
# ---------------------------------------------------------------------------


def png_from_rgb_host(rgb: np.ndarray, filter_mode: str = "up") -> bytes:
    """The encode tail of the host mirror alone: composited (H, W, 3)
    uint8 RGB -> PNG bytes through the numpy scanline filter + the
    numpy mirror of the device RLE/fixed-Huffman stream. Split out so
    the super-tile path (render/supertile) can composite ONCE and
    encode each carved region through exactly this chain — carved
    bytes stay identical to ``render_png_host`` of the same region."""
    h, w = rgb.shape[:2]
    filtered = filter_rows_np(
        np.ascontiguousarray(rgb).reshape(h, w * 3), 3, filter_mode
    )
    stream = zlib_rle_np(filtered.tobytes())
    return frame_png(stream, w, h, 8, 2)


def render_png_host(
    planes: np.ndarray,
    index_tables: np.ndarray,
    color_luts: np.ndarray,
    filter_mode: str = "up",
    mask: Optional[np.ndarray] = None,
) -> bytes:
    """One lane rendered and PNG-encoded entirely on the host,
    byte-identical to the fused device chain: numpy composite (+
    optional ROI mask) + numpy scanline filter + the numpy mirror of
    the device RLE/fixed-Huffman stream
    (``ops.device_deflate.zlib_rle_np``)."""
    with RENDER_SECONDS.time(stage="host"):
        rgb = render_host(planes, index_tables, color_luts, mask)
        return png_from_rgb_host(rgb, filter_mode)


def encode_jpeg(rgb: np.ndarray, quality: int) -> Optional[bytes]:
    """JPEG container encode via Pillow (the one optional host codec
    dependency; absent -> None -> 404 for jpeg renders). Input RGB is
    engine-identical, so jpeg bytes match across engines too."""
    try:
        from PIL import Image
    except ImportError:  # pragma: no cover - pillow ships in the image
        return None
    import io

    with RENDER_SECONDS.time(stage="jpeg"):
        buf = io.BytesIO()
        Image.fromarray(rgb, mode="RGB").save(
            buf, format="JPEG", quality=int(quality)
        )
        return buf.getvalue()
