"""The tile pipeline — this framework's "model".

Re-implements the reference's per-request pipeline
(TileRequestHandler.java:80-139):

    pixels metadata -> pixel buffer -> resolution select -> region
    default (w/h==0 -> full plane) -> tile read -> raw | PNG | TIFF

with the same null-propagation semantics (missing image, unknown
format, or any pipeline failure -> ``None`` -> 404 "Cannot find
Image:<id>", PixelBufferVerticle.java:111-114) and the same span
taxonomy — then adds what the reference cannot do: a **batched device
path** where concurrent tiles are coalesced into fixed-shape batches,
filtered for PNG on the TPU in one fused kernel, and deflate-compressed
on host threads that overlap with device compute.

Bucket padding trick: PNG filters only reference bytes above/left, so
right/bottom zero-padding to a bucket shape leaves the filtered bytes
of the real region unchanged — one jit specialization per
(bucket, dtype, filter) serves every smaller tile shape, and the
padded lanes' bytes are sliced away before deflate.
"""

from __future__ import annotations

import concurrent.futures
import logging
import os
import threading
import time
from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..db.postgres import PostgresUnavailableError
from ..errors import RequestTooLargeError, ServiceUnavailableError
from ..io.pixel_buffer import PixelBuffer
from ..io.pixels_service import PixelsService
from ..io.stores import StoreUnavailableError
from ..resilience.deadline import DeadlineExceeded, current_deadline
from ..ops.convert import to_big_endian_bytes, to_big_endian_bytes_np
from ..ops.crop import resolve_region
from ..ops.pallas import (
    filter_tiles as pallas_filter_tiles,
    supports as pallas_supports,
)
from ..ops.png import (
    PngEncodeError,
    _PNG_DTYPES,
    assemble_png,
    encode_png,
    filter_batch,
)
from ..obs.recorder import stage_all, stage_of
from ..ops.tiff import TiffEncodeError, encode_tiff
from ..runtime.native import get_engine
from ..tile_ctx import TileCtx
from ..utils.metrics import REGISTRY
from ..utils.tracing import TRACER
from .device_cache import device_of

log = logging.getLogger("omero_ms_pixel_buffer_tpu.pipeline")

TILE_DEVICE_LANES = REGISTRY.counter(
    "tile_device_lanes_total",
    "PNG tile lanes whose zlib stream was built on the device",
)
TILE_DEVICE_FALLBACK = REGISTRY.counter(
    "tile_device_fallback_total",
    "Lanes a device-path failure degraded to the host, by catch site",
)


def _host_fallback(site: str, lanes: int, what: str) -> None:
    """A device-path failure degrading to the host engine: the sick
    chip keeps serving (safety), but never invisibly — traceback in
    the log, lanes on the counter. Call from the ``except`` block."""
    log.exception("%s; host fallback", what)
    TILE_DEVICE_FALLBACK.inc(lanes, site=site)



def _auto_verdict(found: dict) -> Tuple[str, str]:
    """What ``engine: auto`` makes of a device probe: (engine, why) —
    the chip when it is a TPU whose measured link clears
    ``OMPB_DEVICE_MIN_MBPS``, the host otherwise."""
    min_mbps = float(os.environ.get("OMPB_DEVICE_MIN_MBPS", "1000"))
    link = found["link_mbps"]
    if found["platform"] != "tpu":
        return "host", f"backend is {found['platform']}, not tpu"
    if link < min_mbps:
        return "host", f"link {link} MB/s < {min_mbps:g} MB/s"
    return "device", f"tpu, link {link} MB/s >= {min_mbps:g} MB/s"


FORMATS = (None, "png", "tif")

# Dependency-down markers: a lane that failed because a breaker is
# open (store / Postgres) must answer 503 + Retry-After, NOT the 404 a
# truly unknown image gets — a 404 reads as "image gone" to viewers
# and caches, for the whole open duration.
_UNAVAILABLE = (StoreUnavailableError, PostgresUnavailableError)


def _lane_unavailable(e: Exception) -> ServiceUnavailableError:
    return ServiceUnavailableError(
        str(e), retry_after_s=getattr(e, "retry_after_s", 1.0) or 1.0
    )


class ResolvedTile:
    """A ctx bound to its image: metadata, buffer, level, resolved
    region. ``degrade_level`` (hybrid-resolution fallback,
    resilience/scheduler) is the COARSER pyramid level this tile's
    pixels will actually be read from — the region/level fields keep
    describing the *requested* resource, so keys, filenames, and the
    encode tail never notice."""

    __slots__ = (
        "ctx", "meta", "buffer", "level", "x", "y", "w", "h",
        "degrade_level",
    )

    def __init__(self, ctx, meta, buffer, level, x, y, w, h,
                 degrade_level=None):
        self.ctx, self.meta, self.buffer = ctx, meta, buffer
        self.level, self.x, self.y, self.w, self.h = level, x, y, w, h
        self.degrade_level = degrade_level




class RenderLane:
    """One staged render lane: the (C, H, W) unsigned channel stack
    plus everything the encode needs to stay byte-identical across
    engines — the TABLE spec/dtype (quantized float/int32 lanes build
    their tables over the u16 bin space with windows erased, because
    the windows are already baked into the host quantization) and the
    rasterized ROI mask, when the spec carries shapes. ``device``
    marks a stack that is ALREADY a device array (plane-cache
    projection crops kept resident, r19) — staged into its fused
    group with jnp ops and submitted ``staged=True``, never pulled
    back to the host."""

    __slots__ = ("stack", "tspec", "tdtype", "mask", "device")

    def __init__(self, stack, tspec, tdtype, mask=None, device=False):
        self.stack, self.tspec, self.tdtype = stack, tspec, tdtype
        self.mask = mask
        self.device = device


class DeferredTile:
    """A lane whose device-encode group is still in flight when
    ``handle_batch(..., defer=True)`` returns. ``future`` resolves to
    the lane's final ``bytes | None`` — device bytes on success, the
    host-fallback encode on any group failure — on the encode queue's
    readback callback, so the dispatch layer chains its reply instead
    of the whole batch blocking on the slowest trailing group (the
    KNOWN_GAPS r12 "singleton trailing group drains inline" fix)."""

    __slots__ = ("future",)

    def __init__(self, future: "concurrent.futures.Future"):
        self.future = future


def _png_native_eligible(tile: np.ndarray) -> bool:
    return (
        tile.dtype in _PNG_DTYPES
        and (tile.ndim == 2 or (tile.ndim == 3 and tile.shape[2] == 3))
    )


class TilePipeline:
    """Engines:

    - ``auto`` — ``device`` on a TPU backend whose measured transfer
      bandwidth clears ``OMPB_DEVICE_MIN_MBPS`` (default 1000 MB/s),
      else ``host``; decided once by ``resolve_engine`` (the server
      calls it before the port opens).
    - ``device`` — coalesced tiles padded to shape buckets, filtered
      on the accelerator (Pallas/XLA); deflate either on host threads
      or, with ``device_deflate``, on the accelerator itself so only
      compressed bytes cross the link.
    - ``host`` — one fused native call per batch (byteswap + filter +
      deflate + PNG framing on the C++ pool, GIL released).

    ``use_device`` is the legacy spelling: True -> ``device``,
    False -> ``host``, None -> ``engine`` as given.
    """

    def __init__(
        self,
        pixels_service: PixelsService,
        png_filter: str = "up",
        png_level: int = 6,
        png_strategy: str = "fast",
        encode_workers: int = 8,
        use_device: Optional[bool] = None,
        use_pallas: Optional[bool] = None,
        buckets: Sequence[int] = (256, 512, 1024),
        engine: str = "auto",
        use_plane_cache: bool = True,
        plane_cache_bytes: Optional[int] = None,
        max_tile_bytes: int = 256 << 20,
        device_deflate: bool = False,
        device_deflate_mode: str = "dynamic",
        queue_depth: int = 2,
        compilation_cache_dir: Optional[str] = None,
        lut_dir: Optional[str] = None,
        supertile_mesh: bool = True,
        max_batch: int = 8,
    ):
        self.pixels_service = pixels_service
        self.png_filter = png_filter
        self.png_level = png_level
        self.png_strategy = png_strategy
        if use_device is not None:
            engine = "device" if use_device else "host"
        if engine not in ("auto", "device", "host"):
            raise ValueError(f"Unknown engine: {engine}")
        # guards the lazily-resolved executor-shared state (_engine,
        # mesh, _dispatcher): concurrent first batches race the
        # auto-resolution from different executor threads (the
        # KNOWN_GAPS "Locking" inventory this closes). Reentrant:
        # _get_dispatcher -> _get_mesh -> engine all take it.
        self._state_lock = threading.RLock()
        self._engine = engine
        self._use_pallas_arg = use_pallas
        # Build the zlib stream on the accelerator (ops/device_deflate)
        # for device PNG lanes: filtered scanlines never come back raw —
        # only the (compressed) stream crosses the link, and the host's
        # role shrinks to PNG chunk framing. Replaces the host half of
        # the reference's encode hot loop (TileRequestHandler.java:176-199).
        self.device_deflate = device_deflate
        # which stream the accelerator builds for RAW PNG lanes:
        # "dynamic" (two-pass canonical Huffman, ~host-parity ratio,
        # the default), "rle" (fixed Huffman, single dispatch), or
        # "stored". Render lanes always use "rle" — their host mirror
        # (zlib_rle_np) is what pins device == host byte identity.
        if device_deflate_mode not in ("dynamic", "rle", "stored"):
            raise ValueError(
                f"Unknown device deflate mode: {device_deflate_mode}"
            )
        self.device_deflate_mode = device_deflate_mode
        # bounded in-flight groups for the streaming encode queue
        self.queue_depth = max(1, int(queue_depth))
        self._device_deflate_logged = False
        # resolve_engine()'s verdict: engine, reason, device, link
        self._engine_info: Optional[dict] = None
        # adaptive compressed-size guess per payload shape: lets the
        # deflate tail pull lengths AND stream bytes in ONE host sync
        self._dd_cap: Dict[Tuple[int, int], int] = {}
        # streaming device-encode queue (built lazily on the first
        # device-deflate batch; owns the submit, plan + pull workers)
        self._dispatcher = None
        # persistent XLA compilation cache: an operator-configured dir
        # (config `jax.compilation-cache-dir`) engages at construction
        # on ANY backend, so bucket-shape specializations survive
        # restarts; without one, runtime/jax_cache places it
        self.compilation_cache_dir = compilation_cache_dir
        if compilation_cache_dir:
            from ..runtime.jax_cache import enable_persistent_cache

            enable_persistent_cache(compilation_cache_dir)
        self.use_plane_cache = use_plane_cache
        # byte budget of the HBM plane cache (config
        # `backend.plane-cache-mb`); None = the cache's default
        self.plane_cache_bytes = plane_cache_bytes
        self._plane_cache = None  # built lazily on first device batch
        # the most lanes one batch brings (config
        # `backend.batching.max-batch`, which the server passes): on a
        # host with several chips every lane count up to it is
        # compiled on a chip before the chip's first plane counts as
        # resident (_warm_plane_chip)
        self.max_batch = max(1, int(max_batch))
        # (device id, bh, bw, w, h, dtype): the lane classes whose
        # programs a chip already holds, and a lock a chip so planes
        # staged side by side onto one cold chip compile once
        self._warm_chips: set = set()
        self._warm_chip_locks: Dict[int, threading.Lock] = {}
        # serving mesh: "auto" -> built on first device batch when >1
        # accelerator is visible (tests inject one via `pipeline.mesh =
        # make_mesh(...)`, or force single-device with `= None`)
        self.mesh = "auto"
        # r23: whether super-tile groups fuse ON the mesh (the sharded
        # composite+carve+deflate chain). False reverts to the r19
        # behavior of per-lane sharding winning over fusion (config
        # `supertile.mesh` — the escape hatch, not the expectation)
        self.supertile_mesh = bool(supertile_mesh)
        # Allocation guard the reference lacks (its tile-size policy
        # beans only steer pyramid writing; a full-plane request still
        # allocates w*h*bpp unchecked, TileRequestHandler.java:98-103).
        # 0 disables.
        self.max_tile_bytes = max_tile_bytes
        self.buckets = tuple(sorted(buckets))
        # whether the service's buffer plane takes the caller's
        # session key (the ACL seam, io/pixels_service.py); duck-typed
        # stand-ins in tests/benches may not
        import inspect

        try:
            self._buffer_scoped = "session_key" in inspect.signature(
                pixels_service.get_pixel_buffer
            ).parameters
        except (TypeError, ValueError, AttributeError):
            self._buffer_scoped = False
        self._encode_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=encode_workers, thread_name_prefix="encode"
        )
        # rendering engine state (render/): LUT registry (built lazily
        # — host-only raw-tile serving never touches it) and the
        # per-(spec, dtype) quantization-table cache
        self.lut_dir = lut_dir
        self._lut_registry = None
        self._render_tables: Dict[Tuple[str, str], tuple] = {}
        # analysis plane (render/analysis): memoized value->bin tables
        # for the histogram reduction, same bound/clear policy as the
        # render tables
        self._hist_tables: Dict[Tuple, np.ndarray] = {}
        # ROI mask rasters (render/masks), memoized per (image,
        # shape-set, region) and dropped with the image on
        # invalidation like every other cached artifact
        from ..render.masks import MaskRasterCache

        self._mask_cache = MaskRasterCache()
        # r19 observability: host pulls of plane-cache projection
        # crops. The device-resident path keeps crops in HBM end to
        # end, so a warm projection pan holds this at zero (the
        # regression test pins it). Bare int on purpose: racing
        # increments may undercount, but zero-vs-nonzero — the pinned
        # signal — is exact.
        self._proj_host_pulls = 0

    def close(self) -> None:
        """Release owned threads: the encode pool and (if the device
        path ever ran) the streaming queue — DRAINED, so every
        submitted group's future resolves before the threads die.
        Idempotent; the server's cleanup hook calls it."""
        with self._state_lock:
            disp, planes = self._dispatcher, self._plane_cache
        if disp is not None:
            disp.close()
        if planes is not None:
            planes.close()
        self._encode_pool.shutdown(wait=False)

    def encode_signature(self) -> str:
        """The 'quality' component of the result-cache key schema
        (cache/result_cache): encoded bytes depend on the PNG encode
        policy, so a config change must produce new keys (and new
        ETags), never serve bytes rendered under the old policy."""
        return f"{self.png_filter}.{self.png_level}.{self.png_strategy}"

    def invalidate_image(self, image_id: int) -> None:
        """Cache-invalidation hook (a changed ``pixels`` row): drop
        the image's open buffer — its parsed structure is stale — any
        device-resident planes staged from it, and its decoded blocks
        (r14: including cached NEGATIVES — a backfilled chunk must not
        keep reading as fill_value until the TTL)."""
        self._mask_cache.invalidate_image(image_id)
        svc = self.pixels_service
        ns = None
        if hasattr(svc, "invalidate"):
            ns = svc.invalidate(image_id)
        if ns is None:
            return
        with self._state_lock:
            planes = self._plane_cache
        if planes is not None:
            planes.invalidate_ns(ns)
        block_cache = getattr(svc, "block_cache", None)
        if block_cache is not None and hasattr(block_cache, "purge_ns"):
            block_cache.purge_ns(ns)

    def _get_plane_cache(self):
        """The HBM plane cache, built on the first device batch (the
        server builds it at start-up, for `check_plane_budget`). Where
        a serving mesh is up its planes spread over the mesh's chips,
        an even share of the budget each; on one device it is the
        cache it always was."""
        with self._state_lock:
            if self._plane_cache is None:
                from .device_cache import DevicePlaneCache

                mesh = self._get_mesh()
                self._plane_cache = DevicePlaneCache(
                    max_bytes=self.plane_cache_bytes,
                    devices=(
                        None if mesh is None else list(mesh.devices.flat)
                    ),
                )
            return self._plane_cache

    def check_plane_budget(self) -> Optional[str]:
        """Start-up check of `backend.plane-cache-mb` against the
        chips' memory (the device engine only): the error, also kept
        for /healthz `cache.device_planes.error`, or None."""
        if not (self.use_device and self.use_plane_cache):
            return None
        return self._get_plane_cache().check_budget()

    def plane_cache_snapshot(self) -> Optional[dict]:
        """/healthz view of the HBM plane tier; None when the device
        path hasn't staged anything (host serving never builds it)."""
        with self._state_lock:
            cache = self._plane_cache
        return None if cache is None else cache.snapshot()

    @property
    def lut_registry(self):
        """The LUT registry (render/luts), built on first render."""
        if self._lut_registry is None:
            from ..render.luts import LutRegistry

            self._lut_registry = LutRegistry(self.lut_dir)
        return self._lut_registry

    def _render_tables_for(self, spec, dtype) -> tuple:
        """(index_tables, color_luts) for a (spec, pixel type) pair,
        memoized — table construction is the render model's float
        math and must not re-run per tile."""
        key = (spec.signature(), np.dtype(dtype).str)
        hit = self._render_tables.get(key)
        if hit is None:
            from ..render.engine import build_tables

            hit = build_tables(spec, np.dtype(dtype), self.lut_registry)
            if len(self._render_tables) >= 256:
                self._render_tables.clear()  # coarse but bounded
            self._render_tables[key] = hit
        return hit

    def _render_filter_mode(self) -> str:
        """Render lanes use the configured PNG filter when the device
        program supports it; 'adaptive' (host-only, and its per-row
        cost would read the padded bytes) pins to 'up' so the host
        fallback and device path stay byte-identical."""
        if self.png_filter in ("none", "sub", "up", "average", "paeth"):
            return self.png_filter
        return "up"

    def render_snapshot(self) -> dict:
        """/healthz view of the rendering engine."""
        return {
            "specs_cached": len(self._render_tables),
            "luts": (
                len(self._lut_registry)
                if self._lut_registry is not None else None
            ),
            "lut_dir": self.lut_dir,
            "masks": self._mask_cache.snapshot(),
            "projection_host_pulls": self._proj_host_pulls,
        }

    def analysis_snapshot(self) -> dict:
        """/healthz view of the analysis plane (histograms)."""
        return {
            "hist_tables_cached": len(self._hist_tables),
        }

    @property
    def engine(self) -> str:
        """The resolved engine ('auto' resolves on first read)."""
        # Double-checked fast path: once resolved, _engine never
        # reverts to "auto", so a stale read is at worst one extra
        # lock acquisition — and it keeps per-batch engine reads from
        # serializing behind _get_dispatcher/_get_mesh, which hold
        # the lock across multi-second first-time device init.
        resolved = self._engine  # ompb-lint: disable=lock-discipline -- benign double-checked read: monotonic auto->resolved transition; blocking here would stall every host batch behind device bring-up
        if resolved != "auto":
            return resolved
        return self.resolve_engine()["engine"]

    def resolve_engine(self) -> dict:
        """Decide the engine once, in THIS process (a chip belongs to
        one process at a time), and say why: ``{engine, reason, device,
        link_mbps, auto_verdict}`` — the /healthz engine block. The
        server calls it at start-up, before the port opens.

        - ``host``: as configured; JAX is never initialised.
        - ``device``: strict. If JAX finds no ``tpu`` backend and
          nobody asked for another one (``JAX_PLATFORMS`` unset or
          naming ``tpu``) it raises — found no chip though nobody
          asked for the CPU. With ``JAX_PLATFORMS`` naming another
          platform explicitly (tests: ``cpu``) the XLA programs run
          there. It never resolves to host.
        - ``auto``: ``device`` on a TPU whose measured link clears
          ``OMPB_DEVICE_MIN_MBPS``, else ``host`` (logged at WARNING
          with the measured link). A ``JAX_PLATFORMS`` that names no
          ``tpu`` is host without initialising a backend."""
        with self._state_lock:
            if self._engine_info is not None:
                return self._engine_info
            configured = self._engine
            asked = [
                p.strip()
                for p in os.environ.get("JAX_PLATFORMS", "").split(",")
                if p.strip()
            ]
            chip_wanted = not asked or "tpu" in asked
            info = {
                "engine": configured, "reason": "configured",
                "device": None, "link_mbps": None, "auto_verdict": None,
            }
            if configured == "auto" and not chip_wanted:
                info["engine"] = info["auto_verdict"] = "host"
                info["reason"] = (
                    f"auto: JAX_PLATFORMS={','.join(asked)} names no tpu"
                )
            elif configured != "host":
                from ..runtime.device_probe import probe

                found = probe()
                info["device"] = {
                    k: found[k] for k in ("platform", "kind", "count")
                }
                info["link_mbps"] = found["link_mbps"]
                verdict, why = _auto_verdict(found)
                info["auto_verdict"] = verdict
                if configured == "auto":
                    info["engine"] = verdict
                    info["reason"] = f"auto: {why}"
                    if verdict == "host":
                        log.warning(
                            "engine auto resolved to host: %s", why
                        )
                elif found["platform"] != "tpu" and chip_wanted:
                    raise RuntimeError(
                        "backend.engine: device found no TPU (JAX "
                        f"backend is {found['platform']!r}) though "
                        "nobody asked for another platform; set "
                        "JAX_PLATFORMS=cpu to run the device programs "
                        "on the CPU backend, or engine: host"
                    )
            self._engine = info["engine"]
            self._engine_info = info
            log.info(
                "engine '%s' (%s)", info["engine"], info["reason"]
            )
            return info

    @property
    def use_device(self) -> bool:
        return self.engine == "device"

    @property
    def use_pallas(self) -> bool:
        if self._use_pallas_arg is not None:
            return bool(self._use_pallas_arg)
        if not self.use_device:
            return False
        # The Pallas filter kernel is the default on a real TPU;
        # interpret mode is far too slow for serving, so other backends
        # take the XLA-fusion path. Only ask the backend when the
        # device path is in play — that initialises PJRT, which
        # host-only configurations must never pay for.
        import jax

        return jax.default_backend() == "tpu"

    def _get_mesh(self):
        """The serving mesh — the multi-chip worker pool
        (PixelBufferMicroserviceVerticle.java:224-233's analog over
        ICI instead of threads). Built once, only when the device
        engine is active and more than one accelerator is visible;
        None keeps every device stage single-chip."""
        with self._state_lock:
            if self.mesh == "auto":
                self.mesh = None
                if self.use_device:
                    try:
                        import jax

                        if len(jax.devices()) > 1:
                            from ..parallel.mesh import make_mesh

                            self.mesh = make_mesh(("data",))
                            log.info(
                                "serving mesh: %s over %d devices",
                                dict(self.mesh.shape),
                                len(jax.devices()),
                            )
                    except Exception:
                        log.exception(
                            "mesh init failed; single-device serving"
                        )
                        TILE_DEVICE_FALLBACK.inc(site="mesh_init")
            return self.mesh

    def _get_dispatcher(self):
        """The streaming device-encode queue (persistent across
        batches — groups of batch N+1 stage and launch while batch N
        is still in flight); with a serving mesh it carries a
        MeshManager so encode batches shard across chips and a sick
        chip degrades to the survivors."""
        with self._state_lock:
            if self._dispatcher is None:
                from .device_dispatch import DeviceEncodeDispatcher

                mesh = self._get_mesh()
                mgr = None
                if mesh is not None:
                    from ..parallel.mesh import MeshManager

                    mgr = MeshManager(devices=list(mesh.devices.flat))
                self._dispatcher = DeviceEncodeDispatcher(
                    self._dd_cap, mesh_manager=mgr,
                    queue_depth=self.queue_depth,
                    # the groups of resident planes name their chip:
                    # their pipe is a worker a chip wide
                    chips=1 if mesh is None else mesh.devices.size,
                )
            return self._dispatcher

    def device_queue_snapshot(self) -> Optional[dict]:
        """/healthz view of the streaming encode queue; None until the
        device-deflate path has dispatched at least once. Deliberately
        lock-free: _get_dispatcher holds _state_lock across first-time
        jax backend init (seconds on a cold TPU), and a health probe
        must never block behind device bring-up — a GIL-atomic
        reference read (possibly one snapshot stale) is exactly what a
        snapshot wants."""
        disp = self._dispatcher  # ompb-lint: disable=lock-discipline -- atomic reference read; blocking on _state_lock would stall /healthz behind multi-second device init
        return None if disp is None else disp.snapshot()

    @property
    def last_mesh_dispatch(self) -> Optional[dict]:
        """Accounting of the most recent sharded encode dispatch
        (n_devices, device_ids, lanes_per_device) — what
        `chip_smoke.py --mesh` requires as proof of real multi-chip
        execution.
        Lock-free read, same rationale as device_queue_snapshot."""
        disp = self._dispatcher  # ompb-lint: disable=lock-discipline -- atomic reference read; reporting path must not block behind device init
        if disp is None or disp.mesh_manager is None:
            return None
        return disp.mesh_manager.last_dispatch

    # ------------------------------------------------------------------
    # resolve / read — the metadata + I/O stages
    # ------------------------------------------------------------------

    @staticmethod
    def _check_deadline(ctx: TileCtx, what: str) -> None:
        """Stop work the moment the request budget is spent — the
        stage raising ``DeadlineExceeded`` degrades to None per lane,
        and the dispatch layer answers 504 (expired) instead of 404."""
        deadline = ctx.deadline or current_deadline()
        if deadline is not None:
            deadline.check(what)

    def resolve(self, ctx: TileCtx) -> Optional[ResolvedTile]:
        """Metadata + buffer + region resolution. ``None`` when the image
        is unknown; raises on invalid coordinates (callers map to the
        reference's broad-catch -> None -> 404)."""
        self._check_deadline(ctx, "resolve")
        with stage_of(ctx, "resolve"):
            return self._resolve_inner(ctx)

    def _resolve_inner(self, ctx: TileCtx) -> Optional[ResolvedTile]:
        with TRACER.start_span("get_pixels"):
            # the session key scopes permission-aware resolvers — the
            # reference's HQL runs inside the joined session, so ACLs
            # filter what resolves (TileRequestHandler.java:220-241)
            meta = self.pixels_service.get_pixels(
                ctx.image_id, session_key=ctx.omero_session_key
            )
        if meta is None:
            log.debug("Cannot find Image:%s", ctx.image_id)
            return None
        with TRACER.start_span("get_pixel_buffer"):
            # session key again at the buffer seam: the metadata check
            # above already authorized, but the cached re-check is
            # near-free and keeps the ACL invariant local to every
            # buffer open (io/pixels_service.get_pixel_buffer)
            if self._buffer_scoped:
                buffer = self.pixels_service.get_pixel_buffer(
                    ctx.image_id, session_key=ctx.omero_session_key
                )
            else:
                buffer = self.pixels_service.get_pixel_buffer(
                    ctx.image_id
                )
        if buffer is None:
            return None
        level = 0
        if ctx.resolution is not None:
            # setResolutionLevel analog (TileRequestHandler.java:89-91)
            if not 0 <= ctx.resolution < buffer.resolution_levels:
                raise ValueError(
                    f"Resolution level {ctx.resolution} out of range"
                )
            level = ctx.resolution
        size_x, size_y = buffer.level_size(level)
        x, y, w, h = resolve_region(ctx.region, size_x, size_y)
        # guard the true allocation: interleaved multi-sample pages
        # materialize w*h*samples before channel extraction
        samples = getattr(buffer, "samples", 1)
        if (
            self.max_tile_bytes
            and w * h * samples * meta.bytes_per_pixel
            > self.max_tile_bytes
        ):
            raise ValueError(
                f"Tile {w}x{h} exceeds max-tile-bytes "
                f"({self.max_tile_bytes})"
            )
        # reflect defaulting back into the ctx (the reference mutates
        # region in place, TileRequestHandler.java:92-97, and the
        # filename header carries the resolved w/h)
        ctx.region.x, ctx.region.y = x, y
        ctx.region.width, ctx.region.height = w, h
        degrade_level = None
        if ctx.degraded:
            target = level + int(ctx.degraded)
            if 0 < target < buffer.resolution_levels:
                degrade_level = target
            else:
                # no coarser level to fall back to: serve full
                # resolution (the ctx flag clears so the HTTP layer
                # doesn't tag a body that isn't degraded)
                ctx.degraded = 0
        return ResolvedTile(
            ctx, meta, buffer, level, x, y, w, h,
            degrade_level=degrade_level,
        )

    def read(self, rt: ResolvedTile) -> np.ndarray:
        self._check_deadline(rt.ctx, "read")
        with stage_of(rt.ctx, "read"):
            if rt.degrade_level is not None:
                return self._read_degraded(rt)
            with TRACER.start_span("get_tile_direct"):
                return rt.buffer.get_tile_at(
                    rt.level, rt.ctx.z, rt.ctx.c, rt.ctx.t,
                    rt.x, rt.y, rt.w, rt.h,
                )

    # -- hybrid-resolution degradation (resilience/scheduler) ----------

    @staticmethod
    def _degrade_plan_rect(buffer, level, degrade_level, x, y, w, h):
        """The coarse-read + upscale plan for ANY rectangle at
        ``level`` served from ``degrade_level``: the covering coarse
        region and the per-axis nearest-neighbor index maps back to
        (h, w). Pure integer math from the two levels' actual
        extents, so non-power-of-two pyramids map correctly; for the
        standard 2x stride pyramid this is exactly pixel (y, x) ->
        coarse (y//2, x//2). Rect-parameterized (not just per-lane)
        because the fused degraded super-tile plans ITS bounding
        rectangle through the same math — each output pixel's coarse
        index is the absolute ``Y * sy1 // sy0``, independent of the
        rectangle it was planned inside, which is what makes the
        fused degraded gather byte-identical to per-lane degraded
        reads."""
        sx0, sy0 = buffer.level_size(level)
        sx1, sy1 = buffer.level_size(degrade_level)
        cx0 = x * sx1 // sx0
        cy0 = y * sy1 // sy0
        cx1 = min(sx1, ((x + w) * sx1 + sx0 - 1) // sx0)
        cy1 = min(sy1, ((y + h) * sy1 + sy0 - 1) // sy0)
        cx1 = max(cx1, cx0 + 1)
        cy1 = max(cy1, cy0 + 1)
        xs = np.minimum(
            (x + np.arange(w)) * sx1 // sx0, cx1 - 1
        ) - cx0
        ys = np.minimum(
            (y + np.arange(h)) * sy1 // sy0, cy1 - 1
        ) - cy0
        return cx0, cy0, cx1 - cx0, cy1 - cy0, ys, xs

    @classmethod
    def _degrade_plan(cls, rt: ResolvedTile):
        """One lane's coarse-read + upscale plan (the rect helper on
        the lane's own rectangle)."""
        return cls._degrade_plan_rect(
            rt.buffer, rt.level, rt.degrade_level,
            rt.x, rt.y, rt.w, rt.h,
        )

    def _read_degraded(self, rt: ResolvedTile) -> np.ndarray:
        """Serve the requested region from the next-lower pyramid
        level, upscaled back to the requested size. The deliberate
        contract (pinned in tests): the result is byte-for-byte the
        coarse tile with rows/columns replicated — the SAME bytes a
        client would get by fetching the lower level and upscaling —
        so a degraded response is honest about its information
        content, and identical across engines."""
        cx0, cy0, cw, ch, ys, xs = self._degrade_plan(rt)
        with TRACER.start_span("get_tile_degraded"):
            coarse = rt.buffer.get_tile_at(
                rt.degrade_level, rt.ctx.z, rt.ctx.c, rt.ctx.t,
                cx0, cy0, cw, ch,
            )
        # np.ix_ indexes the leading (row, col) axes; a trailing
        # samples axis (interleaved RGB) rides along untouched
        return coarse[np.ix_(ys, xs)]

    # ------------------------------------------------------------------
    # single-request path (reference parity; also the fallback)
    # ------------------------------------------------------------------

    def handle(self, ctx: TileCtx):
        """getTile analog: bytes, None (-> 404), or a
        ``ServiceUnavailableError`` marker (-> 503, dependency breaker
        open). Broad-catch like the reference
        (TileRequestHandler.java:133-137)."""
        if ctx.render is not None or ctx.analysis is not None:
            # render/analysis lanes always take the batched machinery
            # (multi-channel plane fetch, grouped device reduction,
            # host fallback); a singleton batch is the same code path
            return self.handle_batch([ctx])[0]
        with TRACER.start_span("get_tile"):
            try:
                rt = self.resolve(ctx)
                if rt is None:
                    return None
                tile = self.read(rt)
                return self.encode(ctx, tile)
            except DeadlineExceeded:
                # expected under overload: the dispatch layer turns
                # the expired lane into a 504 — no stack-trace noise
                log.debug("deadline exceeded for image %s", ctx.image_id)
                return None
            except _UNAVAILABLE as e:
                log.warning("dependency unavailable: %s", e)
                return _lane_unavailable(e)
            except Exception:
                log.exception("Exception while retrieving tile")
                return None

    def encode(self, ctx: TileCtx, tile: np.ndarray) -> Optional[bytes]:
        with stage_of(ctx, "encode"):
            return self._encode_inner(ctx, tile)

    def _encode_inner(self, ctx: TileCtx, tile: np.ndarray) -> Optional[bytes]:
        fmt = ctx.format
        if fmt is None:
            # raw big-endian bytes (OMERO convention)
            return to_big_endian_bytes_np(tile).tobytes()
        if fmt == "png":
            with TRACER.start_span("write_image"):
                try:
                    return encode_png(
                        tile, filter_mode=self.png_filter,
                        level=self.png_level, strategy=self.png_strategy,
                    )
                except PngEncodeError:
                    log.error("PNG encode failed for %s", tile.dtype)
                    return None
        if fmt == "tif":
            # create_metadata + write_image (the OME-XML ImageDescription
            # is synthesized inside encode_tiff, mirroring
            # TileRequestHandler.java:145-170)
            with TRACER.start_span("write_image"):
                try:
                    return encode_tiff(tile)
                except TiffEncodeError:
                    return None
        log.error("Unknown output format: %s", fmt)
        return None

    # ------------------------------------------------------------------
    # batched device path
    # ------------------------------------------------------------------

    def _bucket(self, w: int, h: int) -> Optional[Tuple[int, int]]:
        """Smallest bucket covering (w, h); None when too large for any
        bucket (falls back to the single-request path)."""
        for b in self.buckets:
            if w <= b and h <= b:
                return (b, b)
        return None

    def handle_batch(
        self, ctxs: Sequence[TileCtx], defer: bool = False
    ) -> List[Optional[object]]:
        """Coalesced execution of many tile requests.

        Stages: resolve all -> group reads by image (chunk-dedup) ->
        PNG lanes padded to shape buckets and filtered on device in one
        jit call per bucket -> host deflate in parallel threads ->
        per-lane container assembly. Raw/TIFF lanes take the host
        byte path (pure memcpy). Per-lane failures degrade to None
        (404) without failing the batch — except dependency-down
        failures (open breaker), which become per-lane
        ``ServiceUnavailableError`` markers (-> 503 + Retry-After).

        ``defer=True`` (the batching worker's mode): lanes whose
        device-encode group is still in flight return ``DeferredTile``
        placeholders instead of blocking here — each group's results
        (or its host fallback) deliver through the streaming queue's
        readback callback, so a trailing singleton group no longer
        serializes the whole batch's HTTP futures behind it.
        """
        n = len(ctxs)
        results: List[Optional[bytes]] = [None] * n
        resolved: List[Optional[ResolvedTile]] = [None] * n
        for i, ctx in enumerate(ctxs):
            try:
                resolved[i] = self.resolve(ctx)
            except DeadlineExceeded:
                resolved[i] = None  # lane -> 504 at the dispatch layer
            except _UNAVAILABLE as e:
                resolved[i] = None
                results[i] = _lane_unavailable(e)  # lane -> 503
            except Exception:
                log.exception("resolve failed for lane %d", i)
                resolved[i] = None

        use_device = self.use_device  # resolves 'auto' once per batch
        if use_device:
            # long device compiles (filter + deflate programs) survive
            # process restarts via the on-disk executable cache; only
            # the device path pays this (host serving never needs jax)
            from ..runtime.jax_cache import enable_persistent_cache

            enable_persistent_cache(self.compilation_cache_dir)
        mesh = self._get_mesh() if use_device else None

        # render lanes (ctx.render set) split off here: they fetch one
        # plane per active channel (x z/t-range under projection) and
        # composite on device, so the single-plane read grouping and
        # the PNG bucket split below never see them. Analysis lanes
        # (ctx.analysis set — histograms) split the same way: their
        # result is a JSON body built from a batched integer
        # reduction, never an encoded tile.
        render_idx = [
            i for i, ctx in enumerate(ctxs)
            if ctx.render is not None
            and ctx.analysis is None
            and resolved[i] is not None
            and results[i] is None
        ]
        render_set = set(render_idx)
        analysis_idx = [
            i for i, ctx in enumerate(ctxs)
            if ctx.analysis is not None
            and resolved[i] is not None
            and results[i] is None
        ]
        analysis_set = set(analysis_idx)

        # HBM-resident path: lanes whose plane is (or becomes) device-
        # resident skip the host read entirely — crop + filter happen
        # on the accelerator and only filtered bytes come back. With a
        # multi-chip mesh a plane lives on one of its chips and its
        # lanes run there, a group a plane; what is not eligible (edge
        # lanes, degraded lanes, a cold plane) shards over the mesh
        # below, as it does without the cache.
        plane_groups: Dict[Tuple, List[int]] = {}
        plane_handles: Dict[Tuple, object] = {}
        if use_device and self.use_plane_cache:
            plane_groups, plane_handles = self._stage_plane_lanes(
                ctxs, resolved
            )
        in_plane = {i for lanes in plane_groups.values() for i in lanes}

        # group reads by (image, level) to hit readers' batched path;
        # degraded lanes read their coarse level + upscale per lane
        # (they only exist under overload, and their reads are 4x
        # smaller — grouping them would complicate the coord schema
        # for no measurable win)
        with TRACER.start_span("batch_stage"):
            by_image: Dict[Tuple[int, int], List[int]] = {}
            tiles: List[Optional[np.ndarray]] = [None] * n
            for i, rt in enumerate(resolved):
                if (
                    rt is None or i in in_plane or i in render_set
                    or i in analysis_set
                ):
                    continue
                if rt.degrade_level is not None:
                    try:
                        tiles[i] = self.read(rt)
                    except DeadlineExceeded:
                        pass  # lane -> 504 at the dispatch layer
                    except _UNAVAILABLE as e:
                        results[i] = _lane_unavailable(e)
                    except Exception:
                        log.exception(
                            "degraded read failed; lane -> 404"
                        )
                    continue
                by_image.setdefault(
                    (rt.meta.image_id, rt.level), []
                ).append(i)
            for (image_id, level), lanes in by_image.items():
                buf = resolved[lanes[0]].buffer
                coords = [
                    (resolved[i].ctx.z, resolved[i].ctx.c, resolved[i].ctx.t,
                     resolved[i].x, resolved[i].y, resolved[i].w, resolved[i].h)
                    for i in lanes
                ]
                try:
                    with stage_all([ctxs[i] for i in lanes], "read"):
                        batch = buf.read_tiles(coords, level=level)
                    for i, tile in zip(lanes, batch):
                        tiles[i] = tile
                except _UNAVAILABLE as e:
                    log.warning("store unavailable for image %d: %s",
                                image_id, e)
                    marker = _lane_unavailable(e)
                    for i in lanes:
                        results[i] = marker  # lanes -> 503
                except Exception:
                    log.exception("batched read failed; lanes -> 404")

        # split lanes: device-PNG buckets / distributed full-plane /
        # host fused encode / python
        png_groups: Dict[Tuple, List[int]] = {}
        host_lanes: List[int] = []
        sp_lanes: List[int] = []
        for i, (ctx, tile) in enumerate(zip(ctxs, tiles)):
            if tile is None or resolved[i] is None:
                continue
            device_png = (
                use_device
                and ctx.format == "png"
                and tile.dtype in _PNG_DTYPES
                and (
                    tile.ndim == 2
                    or (tile.ndim == 3 and tile.shape[2] == 3)
                )
            )
            bucket = (
                self._bucket(tile.shape[1], tile.shape[0])
                if device_png else None
            )
            if bucket is not None:
                bw, bh = bucket
                samples = 1 if tile.ndim == 2 else 3
                png_groups.setdefault(
                    ((bh, bw), tile.dtype.str, samples), []
                ).append(i)
            elif (
                device_png
                and tile.ndim == 2
                and mesh is not None
                and self.png_filter == "up"
            ):
                # bigger than every bucket: shard the plane's rows
                # across the mesh (space parallel, halo over ICI)
                sp_lanes.append(i)
            elif ctx.format == "png" and _png_native_eligible(tile):
                host_lanes.append(i)
            else:
                results[i] = self.encode(ctx, tile)

        if host_lanes:
            self._host_png_lanes(host_lanes, tiles, ctxs, results)

        for i in sp_lanes:
            try:
                self._distributed_plane_lane(mesh, i, tiles[i], results)
            except Exception:
                _host_fallback(
                    "distributed_plane", 1, "distributed plane lane failed"
                )
                results[i] = self.encode(ctxs[i], tiles[i])

        # device-deflate groups go through the streaming encode queue:
        # each group's H2D + fused compute launches while earlier
        # groups — including groups of a PREVIOUS batch still being
        # drained — are in their D2H/framing tail, so the device never
        # waits on host framing or on the batcher boundary
        use_fused = use_device and self.device_deflate
        pending: List[Tuple[List[int], object]] = []
        for ((bh, bw), dtype_str, samples), lanes in png_groups.items():
            if use_fused:
                try:
                    pending.extend(self._submit_bucket_groups(
                        lanes, tiles, bh, bw, np.dtype(dtype_str),
                        samples,
                    ))
                    continue
                except Exception:
                    _host_fallback(
                        "bucket_dispatch", len(lanes),
                        "device encode dispatch failed",
                    )
                    for i in lanes:
                        results[i] = self.encode(ctxs[i], tiles[i])
                    continue
            try:
                self._device_png_lanes(
                    lanes, tiles, ctxs, results, bh, bw,
                    np.dtype(dtype_str), samples,
                )
            except Exception:
                _host_fallback(
                    "bucket_png", len(lanes), "device PNG batch failed"
                )
                for i in lanes:
                    results[i] = self.encode(ctxs[i], tiles[i])

        for key, lanes in plane_groups.items():
            (_, bh, bw, dtype_str) = key[-4:]
            if use_fused:
                try:
                    pending.extend(self._submit_plane_groups(
                        plane_handles[key], lanes, resolved, bh, bw,
                        np.dtype(dtype_str),
                    ))
                    continue
                except Exception:
                    _host_fallback(
                        "plane_dispatch", len(lanes),
                        "plane-cache dispatch failed",
                    )
                    self._plane_fallback(lanes, resolved, ctxs, results)
                    continue
            try:
                self._device_plane_png_lanes(
                    plane_handles[key], lanes, resolved, ctxs, results,
                    bh, bw, np.dtype(dtype_str),
                )
            except Exception:
                _host_fallback(
                    "plane_png", len(lanes), "plane-cache PNG batch failed"
                )
                self._plane_fallback(lanes, resolved, ctxs, results)

        render_pending: List[Tuple[List[int], object]] = []
        render_stacks: Dict[int, RenderLane] = {}
        if render_idx:
            # coarse per-lane attribution: plane reads + table build +
            # compose/submit — the device drain below stamps "device"
            # separately for fused groups
            with stage_all([ctxs[i] for i in render_idx], "render"):
                render_pending, render_stacks = self._render_batch_lanes(
                    render_idx, resolved, ctxs, results,
                    use_fused=use_fused,
                )

        if analysis_idx:
            with stage_all([ctxs[i] for i in analysis_idx], "render"):
                self._analysis_batch_lanes(
                    analysis_idx, resolved, ctxs, results,
                    use_device=use_device,
                )

        if defer:
            for idxs, fut in pending:
                self._defer_group(
                    idxs, fut, tiles, resolved, ctxs, results,
                )
            for idxs, fut in render_pending:
                self._defer_group(
                    idxs, fut, tiles, resolved, ctxs, results,
                    render_stacks=render_stacks,
                )
            return results

        for idxs, fut in pending:
            try:
                # audited: handle_batch runs on a BATCHER executor
                # thread and the future resolves on the dispatcher's
                # pull pool — distinct pools, no self-deadlock
                with stage_all([ctxs[i] for i in idxs], "device"):
                    group = fut.result()  # ompb-lint: disable=loop-block -- executor-thread wait on a different pool
                for i, png in group.items():
                    results[i] = png
                self._count_device_lanes(fut, len(group))
            except Exception:
                _host_fallback(
                    "encode_group", len(idxs), "device encode group failed"
                )
                for i in idxs:
                    try:
                        tile = tiles[i]
                        if tile is None:
                            tile = self.read(resolved[i])
                        results[i] = self.encode(ctxs[i], tile)
                    except Exception:
                        results[i] = None

        for idxs, fut in render_pending:
            try:
                # audited: same two-pool shape as the drain above
                with stage_all([ctxs[i] for i in idxs], "device"):
                    group = fut.result()  # ompb-lint: disable=loop-block -- executor-thread wait on a different pool
                for i, png in group.items():
                    results[i] = png
                from ..render.engine import RENDER_TILES

                RENDER_TILES.inc(
                    len(group), path="device", format="png"
                )
            except Exception:
                _host_fallback(
                    "render_group", len(idxs), "device render group failed"
                )
                from ..render.engine import RENDER_FALLBACK

                RENDER_FALLBACK.inc(len(idxs))
                for i in idxs:
                    self._render_host_lane(
                        i, ctxs[i], resolved[i], render_stacks.get(i),
                        results,
                    )
        return results

    # -- deferred group delivery (defer=True) ---------------------------

    def _defer_group(
        self, idxs, fut, tiles, resolved, ctxs, results,
        render_stacks=None,
    ) -> None:
        """Swap one in-flight group's lanes for ``DeferredTile``
        placeholders and chain delivery onto the group future: device
        bytes distribute from the readback callback; a group failure
        submits the host fallback to the encode pool (never encoding
        on the pull worker — it must stay free to drain the next
        group)."""
        lane_futs = {}
        for i in idxs:
            lf: "concurrent.futures.Future" = concurrent.futures.Future()
            lane_futs[i] = lf
            results[i] = DeferredTile(lf)
        t_submit = time.perf_counter()

        def deliver(gfut):
            # device-stage attribution: submit -> group resolution is
            # the request's wall time inside the encode queue (the
            # queue's own snapshot breaks the interior into
            # h2d/compute/d2h with exemplar-carrying histograms)
            dt = time.perf_counter() - t_submit
            for i in idxs:
                rec = getattr(ctxs[i], "obs", None)
                if rec is not None:
                    rec.stamp("device", dt)
            try:
                group = gfut.result()
            except Exception:
                _host_fallback(
                    "render_group" if render_stacks is not None
                    else "encode_group",
                    len(idxs), "deferred device group failed",
                )
                fb = (
                    self._deferred_render_fallback
                    if render_stacks is not None
                    else self._deferred_fallback
                )
                try:
                    self._encode_pool.submit(
                        fb, idxs, lane_futs, tiles, resolved, ctxs,
                        render_stacks,
                    )
                except RuntimeError:
                    # encode pool already shut down (close raced the
                    # drain): the lanes resolve to None -> 404
                    for lf in lane_futs.values():
                        if not lf.done():
                            lf.set_result(None)
                return
            if render_stacks is not None:
                from ..render.engine import RENDER_TILES

                RENDER_TILES.inc(
                    len(group), path="device", format="png"
                )
            else:
                self._count_device_lanes(gfut, len(group))
            for i in idxs:
                lf = lane_futs[i]
                if not lf.done():
                    lf.set_result(group.get(i))

        fut.add_done_callback(deliver)

    def _count_device_lanes(self, fut, lanes: int) -> None:
        """`tile_device_lanes_total` for one delivered group; a group
        of a resident plane (`_submit_plane_groups` leaves the plane on
        its future) also counts on the plane's chip: `per_chip` on
        /healthz, and the `chip` label where the host has several."""
        plane = getattr(fut, "plane", None)
        labels = (
            {} if plane is None
            else self._get_plane_cache().note_lanes(plane, lanes)
        )
        TILE_DEVICE_LANES.inc(lanes, **labels)

    def _deferred_fallback(
        self, idxs, lane_futs, tiles, resolved, ctxs, _stacks
    ) -> None:
        for i in idxs:
            res = None
            try:
                tile = tiles[i]
                if tile is None:
                    tile = self.read(resolved[i])
                res = self.encode(ctxs[i], tile)
            except Exception:
                log.exception("deferred host fallback failed for lane %d", i)
                TILE_DEVICE_FALLBACK.inc(site="deferred_host_encode")
            lf = lane_futs[i]
            if not lf.done():
                lf.set_result(res)

    def _deferred_render_fallback(
        self, idxs, lane_futs, _tiles, resolved, ctxs, stacks
    ) -> None:
        from ..render.engine import RENDER_FALLBACK

        RENDER_FALLBACK.inc(len(idxs))
        out: Dict[int, Optional[bytes]] = {}
        for i in idxs:
            try:
                self._render_host_lane(
                    i, ctxs[i], resolved[i], stacks.get(i), out
                )
            except Exception:
                out[i] = None
            lf = lane_futs[i]
            if not lf.done():
                lf.set_result(out.get(i))

    def _plane_fallback(self, lanes, resolved, ctxs, results) -> None:
        for i in lanes:
            try:
                results[i] = self.encode(ctxs[i], self.read(resolved[i]))
            except Exception:
                results[i] = None

    # ------------------------------------------------------------------
    # render lanes (render/): multi-channel fetch -> projection ->
    # fused device composite+filter+deflate, host mirror fallback
    # ------------------------------------------------------------------

    def _render_batch_lanes(
        self, idxs, resolved, ctxs, results, use_fused: bool
    ):
        """Plan and read every render lane's channel planes (grouped
        per image like the raw path; z/t-projection lanes consult —
        and fill — the HBM plane cache first), project, quantize
        float/int32 pixels onto the u16 bin space, rasterize ROI
        masks, then either submit fused device render groups
        (returned as [(lanes, future)] for handle_batch's drain) or
        encode on the host in place. Per-lane failures degrade to
        None (404) without failing the batch; dependency-down reads
        become 503 markers like raw lanes; over-budget projection
        stacks become 413 markers."""
        from ..render.engine import (
            RENDER_FALLBACK,
            quantizable_dtype,
            renderable_dtype,
        )
        from ..resilience.faultinject import INJECTOR

        pending: List[Tuple[List[int], object]] = []
        stacks: Dict[int, RenderLane] = {}
        # -- super-tile fusion (r19, mesh-fused since r23): spatially
        # adjacent lanes the batcher stamped execute as ONE plane
        # gather + ONE composite, carved back into per-lane encodes.
        # Handled lanes leave ``idxs``; any lane (or whole group) the
        # fusion declines falls through to the independent path below
        # unchanged. On a serving mesh the fused chain itself
        # shard_maps over per-chip sub-rects of the bounding
        # rectangle (every chip composites ITS window), so fusion no
        # longer idles n-1 chips; `supertile.mesh: false` restores
        # the old per-lane-sharded preference.
        fused_done: set = set()
        mesh = self._get_mesh() if self.use_device else None
        if mesh is None or self.supertile_mesh:
            st_groups: Dict[int, List[int]] = {}
            st_order: List[int] = []
            for i in idxs:
                tok = getattr(ctxs[i], "supertile", None)
                if tok is not None:
                    if id(tok) not in st_groups:
                        st_order.append(id(tok))
                    st_groups.setdefault(id(tok), []).append(i)
            for gid in st_order:
                done = self._supertile_group(
                    st_groups[gid], resolved, ctxs, results,
                    use_fused, pending, stacks,
                )
                fused_done.update(done)
            if fused_done:
                idxs = [i for i in idxs if i not in fused_done]
        plans: Dict[int, tuple] = {}
        lane_dev: Dict[int, bool] = {}
        by_image: Dict[Tuple[int, int], List[int]] = {}
        for i in idxs:
            rt, ctx = resolved[i], ctxs[i]
            spec = ctx.render
            try:
                chans = spec.resolve_channels(rt.meta.size_c)
                zts = spec.plane_range(
                    ctx.z, ctx.t, rt.meta.size_z, rt.meta.size_t
                )
            except Exception:
                log.debug("unrenderable spec for image %d",
                          ctx.image_id, exc_info=True)
                continue  # lane -> 404
            dtype = rt.meta.dtype
            quantized = False
            if not renderable_dtype(dtype):
                if not quantizable_dtype(dtype):
                    log.debug("unrenderable pixel type %s", dtype)
                    continue  # lane -> 404
                quantized = True
                if dtype.kind == "f" and any(
                    ch.window is None for ch in chans
                ):
                    # float windowing needs an explicit window: float
                    # pixels have no bounded pixel-type default
                    log.debug(
                        "float render without an explicit window "
                        "for image %d", ctx.image_id,
                    )
                    continue  # lane -> 404
            # Bound the TOTAL projected stack, not just one plane:
            # resolve() guards w*h*bpp, but a z/t-projection
            # materializes len(chans) * len(zts) planes before the
            # reduction (the KNOWN_GAPS r10 per-plane gap). Over
            # budget is 413, not 404 — the resource exists, the ask
            # is too big.
            nplanes = len(chans) * len(zts)
            if (
                self.max_tile_bytes
                and rt.w * rt.h * rt.meta.bytes_per_pixel * nplanes
                > self.max_tile_bytes
            ):
                results[i] = RequestTooLargeError(
                    f"Projection stack {rt.w}x{rt.h} x {nplanes} "
                    f"planes exceeds max-tile-bytes "
                    f"({self.max_tile_bytes})"
                )
                continue
            upscale = None
            if rt.degrade_level is not None:
                # hybrid-resolution fallback: read every channel
                # plane from the coarse level, upscale after staging
                cx0, cy0, crw, crh, ys, xs = self._degrade_plan(rt)
                coords = [
                    (z, ch.index, t, cx0, cy0, crw, crh)
                    for ch in chans for (z, t) in zts
                ]
                upscale = (ys, xs, crh, crw)
            else:
                coords = [
                    (z, ch.index, t, rt.x, rt.y, rt.w, rt.h)
                    for ch in chans for (z, t) in zts
                ]
            plans[i] = (chans, zts, coords, upscale, quantized)
            by_image.setdefault(
                (
                    rt.meta.image_id,
                    rt.level if upscale is None else rt.degrade_level,
                ), []
            ).append(i)

        with TRACER.start_span("render_stage"):
            for (image_id, level), lanes in by_image.items():
                buf = resolved[lanes[0]].buffer
                # projection lanes consult the HBM plane cache per
                # (z, c, t) plane BEFORE the host read (and get_plane
                # fills it on repeat touches): a repeated projection
                # pan stops re-reading its whole plane range per tile
                # (the KNOWN_GAPS r10 bypass). Misses fall into ONE
                # batched read_tiles call like before.
                per_lane: Dict[int, list] = {}
                flat: List[tuple] = []
                owners: List[Tuple[int, int]] = []
                for i in lanes:
                    chans, zts, coords, upscale, _q = plans[i]
                    slots = [None] * len(coords)
                    per_lane[i] = slots
                    use_hbm = (
                        ctxs[i].render.projection is not None
                        and upscale is None
                        and self.use_device
                        and self.use_plane_cache
                        and getattr(buf, "samples", 1) == 1
                        # 64-bit planes must stay on the host path:
                        # with x64 disabled, device_put silently
                        # canonicalizes f8->f4 / i8->i4 (truncating),
                        # so a cached crop would differ from the host
                        # read and flip bytes after plane admission
                        and resolved[i].meta.dtype.itemsize <= 4
                    )
                    # r19: keep fully-resident lanes' crops ON device —
                    # project + composite + deflate chain without a
                    # host round trip. Needs the fused encode path
                    # (the host mirror consumes host arrays), the
                    # gather-table dtype (unsigned_view is a no-op),
                    # no quantization (host float math), no ROI mask
                    # raster (host-built), and a bucket to land in.
                    want_dev = (
                        use_hbm
                        and use_fused
                        # a lane's channels may live on different
                        # chips: only a one-chip cache keeps the crops
                        # resident through the composite
                        and not self._get_plane_cache().spread
                        and not _q
                        and ctxs[i].render.format == "png"
                        and not ctxs[i].render.masks
                        and resolved[i].meta.dtype.kind == "u"
                        and self._bucket(resolved[i].w, resolved[i].h)
                        is not None
                    )
                    lane_dev[i] = want_dev
                    for j, coord in enumerate(coords):
                        arr = (
                            self._plane_cache_region(
                                buf, level, coord, device=want_dev
                            )
                            if use_hbm else None
                        )
                        if arr is not None:
                            slots[j] = arr
                        else:
                            flat.append(coord)
                            owners.append((i, j))
                try:
                    planes = (
                        buf.read_tiles(flat, level=level)
                        if flat else []
                    )
                except _UNAVAILABLE as e:
                    log.warning(
                        "store unavailable for image %d: %s", image_id, e
                    )
                    marker = _lane_unavailable(e)
                    for i in lanes:
                        results[i] = marker  # lanes -> 503
                    continue
                except Exception:
                    log.exception(
                        "render read failed for image %d; lanes -> 404",
                        image_id,
                    )
                    continue
                for (i, j), arr in zip(owners, planes):
                    per_lane[i][j] = arr
                for i in lanes:
                    chans, zts, coords, upscale, quantized = plans[i]
                    lane_planes = per_lane[i]
                    if any(p is None for p in lane_planes):
                        continue  # a read slot failed -> 404
                    rt = resolved[i]
                    spec = ctxs[i].render
                    if lane_dev.get(i):
                        if all(
                            not isinstance(p, np.ndarray)
                            for p in lane_planes
                        ):
                            # every slot is a resident crop: stack +
                            # project on device, stay resident (r19 —
                            # the warm-projection-pan zero-pull path)
                            try:
                                from ..render.projection import (
                                    project_jax,
                                )

                                stack_d = jnp.stack(
                                    lane_planes
                                ).reshape(
                                    len(chans), len(zts), rt.h, rt.w
                                )
                                if spec.projection is not None:
                                    stack_d = project_jax(
                                        stack_d, spec.projection
                                    )
                                else:
                                    stack_d = stack_d[:, 0]
                                stacks[i] = RenderLane(
                                    stack_d, spec, rt.meta.dtype,
                                    None, device=True,
                                )
                                continue
                            except Exception:
                                log.exception(
                                    "device-resident staging failed "
                                    "for lane %d; host staging", i
                                )
                        # mixed cold pan (or the fallback above):
                        # materialize the resident slots once, counted
                        lane_planes = [
                            self._pull_crop(p) for p in lane_planes
                        ]
                    try:
                        if upscale is not None:
                            ys, xs, crh, crw = upscale
                            stack = np.stack(lane_planes).reshape(
                                len(chans), len(zts), crh, crw
                            )[:, :, ys[:, None], xs[None, :]]
                        else:
                            stack = np.stack(lane_planes).reshape(
                                len(chans), len(zts), rt.h, rt.w
                            )
                        # quantize/project/unsign through the ONE
                        # shared staging tail (byte identity with the
                        # super-tile path depends on it)
                        stack, tspec, tdtype = self._stage_stack(
                            stack, spec, chans, rt.meta.dtype,
                            device_project=use_fused,
                        )
                        mask = None
                        if spec.masks:
                            mask = self._mask_cache.get(
                                rt.meta.image_id, spec.masks,
                                (rt.x, rt.y, rt.w, rt.h),
                            )
                        stacks[i] = RenderLane(
                            stack, tspec, tdtype, mask,
                        )
                    except Exception:
                        log.exception(
                            "render staging failed for lane %d", i
                        )

        # encode groups: (spec signature, TABLE dtype, real size,
        # bucket, masked?, device-resident?) — one fused dispatch per
        # group, one jit specialization per (shape, C). Masked lanes
        # ride the fused dispatch too since r19 (``submit_render``
        # carries the (B, H, W) mask batch; the device multiply is
        # pinned byte-identical to the host mirror). JPEG and
        # over-bucket lanes still serve through the host mirror.
        groups: Dict[Tuple, List[int]] = {}
        for i, lane in stacks.items():
            if i in fused_done:
                continue  # super-tile lanes already executed/queued
            rt, spec = resolved[i], ctxs[i].render
            bucket = (
                self._bucket(rt.w, rt.h)
                if use_fused and spec.format == "png"
                else None
            )
            if bucket is None:
                self._render_host_lane(
                    i, ctxs[i], rt, lane, results
                )
                continue
            groups.setdefault(
                (
                    spec.signature(), lane.tdtype.str,
                    (rt.w, rt.h), bucket,
                    lane.mask is not None, lane.device,
                ),
                [],
            ).append(i)

        fmode = self._render_filter_mode()
        for (
            (sig, tdtype_str, (w, h), (bw, bh), has_mask, is_dev),
            lanes,
        ) in groups.items():
            lane0 = stacks[lanes[0]]
            try:
                # the chaos seam: failing `render.engine` here proves
                # the host mirror serves byte-identical tiles
                INJECTOR.fire("render.engine")
                tables, luts = self._render_tables_for(
                    lane0.tspec, np.dtype(tdtype_str)
                )
                c = tables.shape[0]
                if is_dev:
                    # device-resident stacks (plane-cache projection
                    # crops): pad into the bucket with jnp ops — the
                    # lanes never touch the host
                    batch = jnp.stack(
                        [stacks[i].stack for i in lanes]
                    )
                    if (h, w) != (bh, bw):
                        batch = jnp.pad(
                            batch,
                            ((0, 0), (0, 0), (0, bh - h), (0, bw - w)),
                        )
                else:
                    batch = np.zeros(
                        (len(lanes), c, bh, bw), dtype=lane0.stack.dtype
                    )
                    for j, i in enumerate(lanes):
                        batch[j, :, :h, :w] = stacks[i].stack
                mask_batch = None
                if has_mask:
                    from ..render.masks import bucket_mask_batch

                    mask_batch = bucket_mask_batch(
                        [stacks[i].mask for i in lanes], bh, bw
                    )
                disp = self._get_dispatcher()
                with TRACER.start_span("render_device"):
                    fut = disp.submit_render(
                        batch, tables, luts, h, 1 + w * 3, fmode,
                        "rle", lanes, [(w, h)] * len(lanes),
                        mask=mask_batch, staged=is_dev,
                    )
                pending.append((lanes, fut))
            except Exception:
                _host_fallback(
                    "render_dispatch", len(lanes),
                    "render device dispatch failed",
                )
                RENDER_FALLBACK.inc(len(lanes))
                for i in lanes:
                    self._render_host_lane(
                        i, ctxs[i], resolved[i], stacks[i], results
                    )
        return pending, stacks

    def _render_host_lane(self, i, ctx, rt, lane, results) -> None:
        """One lane through the host mirror: numpy composite (+ ROI
        mask) + the numpy twin of the device stream builder (PNG
        bytes identical to the fused device chain) or Pillow JPEG.
        ``lane`` is the staged RenderLane (None -> 404)."""
        from ..render import engine as rengine

        if lane is None:
            results[i] = None
            return
        spec = ctx.render
        try:
            stack = lane.stack
            if not isinstance(stack, np.ndarray):
                # a device-resident lane degrading to the host mirror
                # pays the one pull the happy path avoided
                stack = self._pull_crop(stack)
            tables, luts = self._render_tables_for(
                lane.tspec, lane.tdtype
            )
            if spec.format == "png":
                results[i] = rengine.render_png_host(
                    stack, tables, luts,
                    self._render_filter_mode(), lane.mask,
                )
            else:
                rgb = rengine.render_host(
                    stack, tables, luts, lane.mask
                )
                results[i] = rengine.encode_jpeg(rgb, spec.quality)
            rengine.RENDER_TILES.inc(path="host", format=spec.format)
        except Exception:
            log.exception("host render failed for lane %d", i)
            results[i] = None

    @staticmethod
    def _stage_stack(stack, spec, chans, dtype, device_project):
        """The shared pointwise tail of render staging: quantize
        float/int32 channels onto the u16 bin space (host float64 —
        engine byte identity), z/t-project in integer arithmetic,
        reinterpret signed pixels as their unsigned gather index.
        ONE implementation serving both the per-lane path and the
        super-tile path — fused-vs-independent byte identity depends
        on these transforms never diverging. (C, Z, H, W) ->
        ((C, H, W) unsigned, table spec, table dtype)."""
        from ..render.engine import (
            default_window,
            quantize_to_u16,
            renderable_dtype,
            unsigned_view,
        )
        from ..render.projection import project

        tspec, tdtype = spec, dtype
        if not renderable_dtype(dtype):
            q = np.empty(stack.shape, dtype=np.uint16)
            for ci, ch in enumerate(chans):
                win = (
                    ch.window if ch.window is not None
                    else default_window(dtype)
                )
                q[ci] = quantize_to_u16(stack[ci], win)
            stack = q
            tspec = spec.without_windows()
            tdtype = np.dtype(np.uint16)
        if spec.projection is not None:
            stack = project(
                stack, spec.projection, device=device_project
            )
        else:
            stack = stack[:, 0]
        return unsigned_view(np.ascontiguousarray(stack)), tspec, tdtype

    # -- super-tile fusion (r19) ---------------------------------------

    def _supertile_group(
        self, lanes, resolved, ctxs, results, use_fused, pending,
        stacks,
    ) -> set:
        """Execute one batcher-stamped super-tile: ONE plane gather
        over the group's bounding rectangle (through the HBM plane
        cache when resident), ONE composite, per-lane regions carved
        out and fed to the existing per-lane encode paths. Returns
        the lane indices this fusion HANDLED (result written or fused
        group queued); everything else — a lane that re-validates out
        (off-modal degrade level, spent deadline, failed resolve) or
        a whole group the fusion declines (over budget, unrenderable
        spec, gather failure) — is left for the independent path, so
        a split lane never poisons its neighbors. Degraded groups
        fuse per resolved pyramid level (one coarse gather + one
        upscale, byte-identical to per-lane degraded reads by the
        absolute-index argument in ``_degrade_plan_rect``).
        Registered per-lane carved stacks back the host-mirror
        fallback of the fused device group (byte-identical by the
        engine contract)."""
        from ..render import engine as rengine
        from ..render import supertile as stile
        from ..render.engine import (
            RENDER_SECONDS,
            quantizable_dtype,
            renderable_dtype,
        )
        from ..resilience.faultinject import INJECTOR

        # re-validate against RESOLVED state: the stamp is pre-resolve
        live = []
        for i in lanes:
            rt, ctx = resolved[i], ctxs[i]
            if rt is None or results[i] is not None:
                continue  # failed/expired resolve, or already marked
            if ctx.deadline is not None and ctx.deadline.expired:
                continue
            live.append(i)
        # degraded lanes fuse per PYRAMID LEVEL: the stamp key carries
        # only the degraded flag (pre-resolve), but the resolved
        # degrade level can differ per lane (and resolve may clear the
        # flag entirely when no coarser level exists) — keep the modal
        # level's lanes, return the rest to the independent path
        by_level: Dict[Optional[int], List[int]] = {}
        for i in live:
            by_level.setdefault(resolved[i].degrade_level, []).append(i)
        if len(by_level) > 1:
            keep = max(by_level.values(), key=len)
            stile.SUPERTILE_FALLBACK.inc(len(live) - len(keep))
            live = keep
        if len(live) < 2:
            stile.SUPERTILE_FALLBACK.inc(len(live))
            return set()
        rt0, ctx0 = resolved[live[0]], ctxs[live[0]]
        spec = ctx0.render
        dtype = rt0.meta.dtype
        try:
            chans = spec.resolve_channels(rt0.meta.size_c)
            zts = spec.plane_range(
                ctx0.z, ctx0.t, rt0.meta.size_z, rt0.meta.size_t
            )
        except Exception:
            stile.SUPERTILE_FALLBACK.inc(len(live))
            return set()  # unrenderable spec: independent path 404s it
        if not renderable_dtype(dtype):
            if not quantizable_dtype(dtype):
                stile.SUPERTILE_FALLBACK.inc(len(live))
                return set()
            if dtype.kind == "f" and any(
                ch.window is None for ch in chans
            ):
                stile.SUPERTILE_FALLBACK.inc(len(live))
                return set()
        rects = [
            (resolved[i].x, resolved[i].y, resolved[i].w, resolved[i].h)
            for i in live
        ]
        bx, by, bw_, bh_ = stile.bounding_rect(rects)
        nplanes = len(chans) * len(zts)
        if (
            self.max_tile_bytes
            and bw_ * bh_ * rt0.meta.bytes_per_pixel * nplanes
            > self.max_tile_bytes
        ):
            # the SUPER-rect blew the allocation guard; the individual
            # tiles may still be fine — serve them independently
            stile.SUPERTILE_FALLBACK.inc(len(live))
            return set()
        # ONE plane gather over the bounding rectangle, through the
        # HBM plane cache when the planes are resident. A degraded
        # group gathers the COARSE covering rect of the bounding
        # rectangle and upscales once — each output pixel's coarse
        # index is absolute (see _degrade_plan_rect), so the fused
        # upscale is byte-identical to per-lane degraded reads.
        buf = rt0.buffer
        dlevel = rt0.degrade_level
        upscale = None
        if dlevel is not None:
            cx0, cy0, crw, crh, uys, uxs = self._degrade_plan_rect(
                buf, rt0.level, dlevel, bx, by, bw_, bh_
            )
            coords = [
                (z, ch.index, t, cx0, cy0, crw, crh)
                for ch in chans for (z, t) in zts
            ]
            upscale = (uys, uxs, crh, crw)
        else:
            coords = [
                (z, ch.index, t, bx, by, bw_, bh_)
                for ch in chans for (z, t) in zts
            ]
        use_hbm = (
            upscale is None
            and self.use_device
            and self.use_plane_cache
            and getattr(buf, "samples", 1) == 1
            and dtype.itemsize <= 4
        )
        read_level = rt0.level if dlevel is None else dlevel
        slots: List[Optional[np.ndarray]] = [None] * len(coords)
        missing, owners = [], []
        for j, coord in enumerate(coords):
            arr = (
                self._plane_cache_region(buf, read_level, coord)
                if use_hbm else None
            )
            if arr is not None:
                slots[j] = arr
            else:
                missing.append(coord)
                owners.append(j)
        try:
            if missing:
                fetched = buf.read_tiles(missing, level=read_level)
                for j, arr in zip(owners, fetched):
                    slots[j] = arr
        except _UNAVAILABLE as e:
            log.warning(
                "store unavailable for super-tile of image %d: %s",
                rt0.meta.image_id, e,
            )
            marker = _lane_unavailable(e)
            for i in live:
                results[i] = marker  # lanes -> 503, like a grouped read
            return set(live)
        except Exception:
            log.exception(
                "super-tile gather failed; independent fallback"
            )
            stile.SUPERTILE_FALLBACK.inc(len(live))
            return set()
        try:
            if upscale is not None:
                uys, uxs, crh, crw = upscale
                raw = np.stack(slots).reshape(
                    len(chans), len(zts), crh, crw
                )[:, :, uys[:, None], uxs[None, :]]
            else:
                raw = np.stack(slots).reshape(
                    len(chans), len(zts), bh_, bw_
                )
            stack, tspec, tdtype = self._stage_stack(
                raw, spec, chans, dtype, device_project=use_fused,
            )
        except Exception:
            log.exception(
                "super-tile staging failed; independent fallback"
            )
            stile.SUPERTILE_FALLBACK.inc(len(live))
            return set()
        # per-lane carved stacks (views into the shared stack): the
        # host mirror AND every fused-group failure path render from
        # these — byte-identical to an independent lane's stack
        rel = [
            (resolved[i].x - bx, resolved[i].y - by) for i in live
        ]
        for (rx, ry), i in zip(rel, live):
            rt = resolved[i]
            stacks[i] = RenderLane(
                stack[:, ry : ry + rt.h, rx : rx + rt.w],
                tspec, tdtype, None,
            )
        stile.SUPERTILE_SIZE.observe(len(live))
        fmode = self._render_filter_mode()
        max_w = max(r[2] for r in rects)
        max_h = max(r[3] for r in rects)
        bucket = (
            self._bucket(max_w, max_h)
            if use_fused and spec.format == "png" else None
        )
        if bucket is not None:
            try:
                # the chaos seam: failing `render.supertile` proves
                # the host carve serves byte-identical tiles
                INJECTOR.fire("render.supertile")
                import jax

                tables, luts = self._render_tables_for(tspec, tdtype)
                disp = self._get_dispatcher()
                size_groups: Dict[Tuple[int, int], List[int]] = {}
                for j, i in enumerate(live):
                    rt = resolved[i]
                    size_groups.setdefault((rt.w, rt.h), []).append(j)
                if (
                    self.supertile_mesh
                    and disp.mesh_manager is not None
                ):
                    # mesh-fused chain: composite + carve + filter +
                    # deflate shard over per-chip overlapped sub-rects
                    # of the bounding stack (one sharded program per
                    # homogeneous size class); byte-identical to the
                    # single-device fused path by the same pointwise
                    # carve argument, pinned in tests/test_mesh_fusion
                    with TRACER.start_span("supertile_mesh"):
                        for (w, h), js in size_groups.items():
                            lane_ids = [live[j] for j in js]
                            rel_rects = [
                                (rel[j][0], rel[j][1], w, h)
                                for j in js
                            ]
                            try:
                                fut = disp.submit_supertile(
                                    stack, tables, luts, rel_rects,
                                    w, h, fmode, "rle", lane_ids,
                                )
                            except Exception as e:
                                # this subgroup alone degrades through
                                # the normal drain fallback
                                fut = concurrent.futures.Future()
                                fut.set_exception(e)
                            pending.append((lane_ids, fut))
                    stile.SUPERTILE_LANES.inc(len(live), path="mesh")
                    return set(live)
                bw_b, bh_b = bucket
                with TRACER.start_span("supertile_device"):
                    stack_dev = jax.device_put(stack)
                    carved = stile.composite_carve_batch(
                        stack_dev, tables, luts,
                        [(ry, rx) for (rx, ry) in rel], bh_b, bw_b,
                    )
                    for (w, h), js in size_groups.items():
                        lane_ids = [live[j] for j in js]
                        try:
                            sub = (
                                carved
                                if len(js) == carved.shape[0]
                                else carved[jnp.asarray(js)]
                            )
                            fut = disp.submit(
                                sub, h, 1 + w * 3, 3, fmode, "rle",
                                lane_ids, [(w, h)] * len(lane_ids),
                                8, 2, staged=True,
                            )
                        except Exception as e:
                            # a raise here must not re-render lanes of
                            # subgroups ALREADY submitted above: this
                            # subgroup alone degrades through the
                            # normal drain fallback (the
                            # _submit_bucket_groups shape)
                            fut = concurrent.futures.Future()
                            fut.set_exception(e)
                        pending.append((lane_ids, fut))
                stile.SUPERTILE_LANES.inc(len(live), path="device")
                return set(live)
            except Exception:
                log.exception(
                    "super-tile device dispatch failed; host carve"
                )
        # host path (host engine, jpeg, fused-dispatch failure): ONE
        # composite, per-lane carve through the host mirror tail —
        # timed under the same stage as render_png_host, so the
        # render_seconds{stage="host"} attribution covers the fused
        # burst path too
        try:
            with RENDER_SECONDS.time(stage="host"):
                tables, luts = self._render_tables_for(tspec, tdtype)
                rgb = rengine.render_host(stack, tables, luts)
        except Exception:
            log.exception(
                "super-tile composite failed; independent fallback"
            )
            for i in live:
                stacks.pop(i, None)
            stile.SUPERTILE_FALLBACK.inc(len(live))
            return set()
        with RENDER_SECONDS.time(stage="host"):
            for (rx, ry), i in zip(rel, live):
                rt = resolved[i]
                try:
                    tile_rgb = stile.carve_host(
                        rgb, rx, ry, rt.w, rt.h
                    )
                    if spec.format == "png":
                        results[i] = rengine.png_from_rgb_host(
                            tile_rgb, fmode
                        )
                    else:
                        results[i] = rengine.encode_jpeg(
                            np.ascontiguousarray(tile_rgb),
                            spec.quality,
                        )
                    rengine.RENDER_TILES.inc(
                        path="host", format=spec.format
                    )
                except Exception:
                    log.exception(
                        "super-tile carve encode failed for lane %d", i
                    )
                    results[i] = None
        stile.SUPERTILE_LANES.inc(len(live), path="host")
        return set(live)

    def _plane_cache_region(self, buf, level, coord, device=False):
        """One (z, c, t) plane region served from (and filling) the
        HBM plane-cache namespace — the projection read path: the
        cache's admission counter sees every touch, so a repeated
        z/t-projection pan stages its plane range once and then crops
        on-device instead of re-reading planes through the host per
        tile. None on any miss/ineligibility (edge-clamped crop, cold
        plane, budget); the caller falls back to the batched host
        read. The crop's values are identical to the host read by
        construction (the plane IS the host read, staged once).

        ``device=True`` (r19) returns the crop as a DEVICE array —
        the projection/composite chain consumes it resident, so a
        warm projection pan never round-trips through the host."""
        z, c, t, x, y, w, h = coord
        try:
            cache = self._get_plane_cache()
            size_x, size_y = buf.level_size(level)
            if x + w > size_x or y + h > size_y:
                return None  # crop would clamp at the plane edge
            plane = cache.get_plane(buf, level, z, c, t)
            if plane is None:
                return None
            crop = cache.crop_batch(plane, [(y, x)], h, w)
            if device:
                return crop[0]  # stays resident; no host sync
            self._proj_host_pulls += 1
            # ompb-lint: disable=jax-hotpath -- the ONE intended pull of this path: the cached plane region returns to host staging
            return np.asarray(crop)[0]
        except Exception:
            log.debug("plane-cache region read failed", exc_info=True)
            return None

    def _pull_crop(self, arr):
        """Host-materialize one slot that MAY be a device crop (the
        mixed cold-pan case: some planes resident, some freshly read)
        — counted, because it is exactly the round trip the resident
        path exists to avoid."""
        if isinstance(arr, np.ndarray):
            return arr
        self._proj_host_pulls += 1
        # ompb-lint: disable=jax-hotpath -- mixed cold-pan fallback: a partially-resident lane degrades to host staging once
        return np.asarray(arr)

    # ------------------------------------------------------------------
    # analysis lanes (render/analysis): per-channel histograms as a
    # batched integer reduction — device bincount, host mirror
    # integer-identical, canonical JSON bodies through the same
    # cache/ETag machinery as tiles
    # ------------------------------------------------------------------

    def _hist_table_for(self, dtype, window, bins: int) -> np.ndarray:
        """Memoized value->bin table for integer pixel types (float/
        int32 planes quantize first and use ``_quant_hist_table_for``);
        same bound/clear policy as the render tables."""
        from ..render import analysis as ran

        key = (
            np.dtype(dtype).str, float(window[0]), float(window[1]),
            bins,
        )
        hit = self._hist_tables.get(key)
        if hit is None:
            hit = ran.build_bin_table(np.dtype(dtype), window, bins)
            if len(self._hist_tables) >= 256:
                self._hist_tables.clear()  # coarse but bounded
            self._hist_tables[key] = hit
        return hit

    def _quant_hist_table_for(self, bins: int) -> np.ndarray:
        from ..render import analysis as ran

        key = ("quant", bins)
        hit = self._hist_tables.get(key)
        if hit is None:
            hit = ran.quant_bin_table(bins)
            if len(self._hist_tables) >= 256:
                self._hist_tables.clear()
            self._hist_tables[key] = hit
        return hit

    def _analysis_batch_lanes(
        self, idxs, resolved, ctxs, results, use_device: bool
    ) -> None:
        """Histogram lanes: read each lane's channel-plane regions
        (grouped per image like render lanes), map values onto bins
        through host-built tables, reduce in batched device bincounts
        (host mirror integer-identical — the ``analysis.engine``
        chaos seam proves it byte-for-byte), and write the canonical
        JSON body into the lane's result slot. Failure taxonomy
        matches render lanes: per-lane 404s, dependency-down 503
        markers, over-budget 413 markers."""
        from ..render import analysis as ran
        from ..render.engine import (
            quantizable_dtype,
            quantize_to_u16,
            renderable_dtype,
            unsigned_view,
        )

        plans: Dict[int, tuple] = {}
        by_image: Dict[Tuple[int, int], List[int]] = {}
        for i in idxs:
            rt, ctx = resolved[i], ctxs[i]
            spec = ctx.analysis
            try:
                chans = spec.resolve_channels(rt.meta.size_c)
            except Exception:
                log.debug("bad histogram channel for image %d",
                          ctx.image_id, exc_info=True)
                continue  # lane -> 404
            d = rt.meta.dtype
            if not (renderable_dtype(d) or quantizable_dtype(d)):
                log.debug("unhistogrammable pixel type %s", d)
                continue  # lane -> 404
            if (
                self.max_tile_bytes
                and rt.w * rt.h * rt.meta.bytes_per_pixel * len(chans)
                > self.max_tile_bytes
            ):
                results[i] = RequestTooLargeError(
                    f"Histogram region {rt.w}x{rt.h} x {len(chans)} "
                    f"channels exceeds max-tile-bytes "
                    f"({self.max_tile_bytes})"
                )
                continue
            coords = [
                (ctx.z, ch.index, ctx.t, rt.x, rt.y, rt.w, rt.h)
                for ch in chans
            ]
            plans[i] = (chans, coords)
            by_image.setdefault(
                (rt.meta.image_id, rt.level), []
            ).append(i)

        jobs: List[Tuple[int, list]] = []
        with TRACER.start_span("analysis_stage"):
            for (image_id, level), lanes in by_image.items():
                buf = resolved[lanes[0]].buffer
                flat = [c for i in lanes for c in plans[i][1]]
                try:
                    planes = buf.read_tiles(flat, level=level)
                except _UNAVAILABLE as e:
                    log.warning(
                        "store unavailable for image %d: %s",
                        image_id, e,
                    )
                    marker = _lane_unavailable(e)
                    for i in lanes:
                        results[i] = marker  # lanes -> 503
                    continue
                except Exception:
                    log.exception(
                        "histogram read failed for image %d; "
                        "lanes -> 404", image_id,
                    )
                    continue
                pos = 0
                for i in lanes:
                    chans, coords = plans[i]
                    lane_planes = planes[pos : pos + len(coords)]
                    pos += len(coords)
                    rt, spec = resolved[i], ctxs[i].analysis
                    try:
                        entry = []
                        for ch, plane in zip(chans, lane_planes):
                            window = ran.resolve_window(
                                ch, rt.meta.dtype,
                                spec.use_pixel_range, plane=plane,
                            )
                            if renderable_dtype(rt.meta.dtype):
                                tab = self._hist_table_for(
                                    rt.meta.dtype, window, spec.bins
                                )
                                idx_plane = unsigned_view(
                                    np.ascontiguousarray(plane)
                                )
                            else:
                                idx_plane = quantize_to_u16(
                                    plane, window
                                )
                                tab = self._quant_hist_table_for(
                                    spec.bins
                                )
                            entry.append((ch, window, idx_plane, tab))
                        jobs.append((i, entry))
                    except Exception:
                        log.exception(
                            "histogram staging failed for lane %d", i
                        )
        if jobs:
            self._reduce_histogram_jobs(
                jobs, ctxs, resolved, results, use_device
            )

    def _reduce_histogram_jobs(
        self, jobs, ctxs, resolved, results, use_device: bool
    ) -> None:
        """Group staged (plane, table) pairs by shape and reduce each
        group in ONE batched call — device bincounts when the device
        engine serves (sharded over the mesh when one is up), the
        numpy mirror otherwise or on any device failure (counts are
        integer-identical, so the JSON bytes cannot differ)."""
        from ..render import analysis as ran
        from ..resilience.faultinject import INJECTOR

        counts_map: Dict[Tuple[int, int], np.ndarray] = {}
        groups: Dict[Tuple, List[Tuple[int, int]]] = {}
        for j, (i, entry) in enumerate(jobs):
            for e, (_ch, _win, idx_plane, tab) in enumerate(entry):
                key = (
                    idx_plane.shape, idx_plane.dtype.str,
                    tab.shape[0], ctxs[i].analysis.bins,
                )
                groups.setdefault(key, []).append((j, e))
        for (_shape, _dstr, _k, bins), members in groups.items():
            planes_arr = np.stack(
                [jobs[j][1][e][2] for j, e in members]
            )
            tabs = np.stack([jobs[j][1][e][3] for j, e in members])
            path = "host"
            counts = None
            if use_device:
                try:
                    # the chaos seam: failing `analysis.engine` proves
                    # the host mirror answers identical counts/bytes
                    INJECTOR.fire("analysis.engine")
                    mesh = self._get_mesh()
                    if mesh is not None:
                        counts = ran.sharded_histogram_batch(
                            mesh, planes_arr, tabs, bins
                        )
                        path = "mesh"
                    else:
                        counts = ran.histogram_batch(
                            planes_arr, tabs, bins
                        )
                        path = "device"
                except Exception:
                    log.exception(
                        "device histogram failed; host mirror"
                    )
                    counts = None
            if counts is None:
                counts = ran.histogram_host(planes_arr, tabs, bins)
                path = "host"
            ran.HIST_TILES.inc(len(members), path=path)
            for (j, e), c in zip(members, counts):
                counts_map[(j, e)] = c
        for j, (i, entry) in enumerate(jobs):
            try:
                spec, ctx, rt = ctxs[i].analysis, ctxs[i], resolved[i]
                ch_results = []
                for e, (ch, window, _p, _t) in enumerate(entry):
                    counts = counts_map.get((j, e))
                    if counts is None:
                        raise RuntimeError(
                            "histogram reduction incomplete"
                        )
                    ch_results.append({
                        "index": ch.index,
                        "window": [
                            round(float(window[0]), 6),
                            round(float(window[1]), 6),
                        ],
                        "counts": [int(x) for x in counts],
                        "stats": ran.stats_from_counts(
                            counts, window, spec.bins
                        ),
                    })
                results[i] = ran.histogram_body(
                    ctx.image_id, ctx.z, ctx.t,
                    (rt.x, rt.y, rt.w, rt.h), ctx.resolution,
                    spec, ch_results,
                )
            except Exception:
                log.exception(
                    "histogram assembly failed for lane %d", i
                )

    def _stage_plane_lanes(self, ctxs, resolved):
        """Group device-eligible PNG lanes by resident plane; the
        planes a batch admits to HBM are staged side by side. Lanes
        whose crop would clamp at the plane edge (region + bucket
        exceeding the plane) stay on the host path — PNG filters
        require the region at crop origin."""
        cache = self._get_plane_cache()
        eligible: List[Tuple[int, Tuple]] = []  # (lane, plane key)
        # one admission touch per PLANE per batch (a plane serves every
        # bucket group; keying attempts on the group would double-touch)
        wanted: Dict[Tuple, Tuple] = {}
        # per plane, the lane classes (bh, bw, w, h, dtype) this batch
        # asks of it: what a chip compiles before the plane is resident
        classes: Dict[Tuple, set] = {}
        for i, (ctx, rt) in enumerate(zip(ctxs, resolved)):
            if rt is None or ctx.format != "png" or ctx.render is not None:
                # render lanes (format is also "png") have their own
                # multi-channel path — staging them here would encode
                # the RAW plane into their result slot
                continue
            if rt.degrade_level is not None:
                # degraded lanes read the COARSE level; cropping the
                # full-resolution resident plane would serve full-res
                # bytes under the degraded cache key
                continue
            meta_dtype = rt.meta.dtype
            if (
                meta_dtype not in _PNG_DTYPES
                or getattr(rt.buffer, "samples", 1) != 1
            ):
                continue
            bucket = self._bucket(rt.w, rt.h)
            if bucket is None:
                continue
            bw, bh = bucket
            size_x, size_y = rt.buffer.level_size(rt.level)
            if rt.x + bw > size_x or rt.y + bh > size_y:
                continue  # edge lane: host path keeps filter semantics
            plane_key = (rt.meta.image_id, rt.level, ctx.z, ctx.c, ctx.t)
            wanted.setdefault(
                plane_key, (rt.buffer, rt.level, ctx.z, ctx.c, ctx.t)
            )
            classes.setdefault(plane_key, set()).add(
                (bh, bw, rt.w, rt.h, meta_dtype.str)
            )
            eligible.append((i, plane_key + (bh, bw, meta_dtype.str)))
        groups: Dict[Tuple, List[int]] = {}
        handles: Dict[Tuple, object] = {}
        if not wanted:
            return groups, handles

        def staging_failed(exc):
            # that plane's lanes stay host-staged; the others go on
            log.error("plane staging failed; host path", exc_info=exc)
            TILE_DEVICE_FALLBACK.inc(site="plane_staging")

        warm = None
        if cache.spread:
            # a program is compiled once a device: on one chip the
            # first requests compile as they always did, on several a
            # plane is resident only once its chip can serve it
            by_n = [classes[key] for key in wanted]

            def warm(n, plane):
                self._warm_plane_chip(plane, by_n[n])

        planes = dict(zip(
            wanted,
            cache.get_planes(
                list(wanted.values()), on_error=staging_failed, warm=warm
            ),
        ))
        for i, key in eligible:
            plane = planes[key[:-3]]
            if plane is None:
                continue  # cold this batch: the lane stays host-staged
            handles[key] = plane
            groups.setdefault(key, []).append(i)
        return groups, handles

    def _warm_plane_chip(self, plane, classes) -> None:
        """Compile (or load from the persistent cache), on the chip
        that holds ``plane``, every program a lane of ``classes`` can
        run there, by running the serving path itself on crops at the
        plane's origin: groups of 1 to ``max_batch`` lanes, so the
        crop, both encode passes and every slice of the pull exist at
        each lane count before the plane counts as resident. A lane of
        a class that first shows later warms its chip the same way,
        when it is staged."""
        chip = device_of(plane).id
        with self._state_lock:
            lock = self._warm_chip_locks.setdefault(chip, threading.Lock())
        for cls in sorted(classes):
            with lock:
                if (chip,) + cls in self._warm_chips:
                    continue
                t0 = time.perf_counter()
                self._run_plane_lanes(plane, cls)
                self._warm_chips.add((chip,) + cls)
                log.info(
                    "chip %d warm for %s lanes %s in %.1f s",
                    chip, cls[4], cls[:4], time.perf_counter() - t0,
                )

    def _run_plane_lanes(self, plane, cls) -> None:
        bh, bw, w, h, dtype_str = cls
        dtype = np.dtype(dtype_str)
        origin = SimpleNamespace(x=0, y=0, w=w, h=h)
        for n in range(1, self.max_batch + 1):
            lanes, resolved = list(range(n)), [origin] * n
            if self.device_deflate:
                for _, fut in self._submit_plane_groups(
                    plane, lanes, resolved, bh, bw, dtype
                ):
                    fut.result()  # ompb-lint: disable=loop-block -- a stager thread (or the batch's own) waits on the dispatcher's pools
            else:
                self._device_plane_png_lanes(
                    plane, lanes, resolved, None, [None] * n, bh, bw, dtype
                )

    def _device_plane_png_lanes(
        self, plane, lanes, resolved, ctxs, results, bh, bw, dtype
    ):
        """Crop + byteswap + filter on device from a resident plane;
        only the filtered scanline bytes cross back to the host."""
        itemsize = dtype.itemsize
        coords = [(resolved[i].y, resolved[i].x) for i in lanes]
        with TRACER.start_span("batch_device"):
            device_batch = self._get_plane_cache().crop_batch(
                plane, coords, bh, bw
            )
            if self.use_pallas and pallas_supports((bh, bw), dtype):
                filtered = pallas_filter_tiles(device_batch, self.png_filter)
            else:
                rows = to_big_endian_bytes(device_batch)
                filtered = filter_batch(rows, itemsize, self.png_filter)
        sizes = [(resolved[i].w, resolved[i].h) for i in lanes]
        self._finish_png_lanes(
            # ompb-lint: disable=jax-hotpath -- the ONE intended device->host pull of this path (filtered scanlines for the host deflate tail)
            np.asarray(filtered), lanes, sizes, results, itemsize
        )

    def _finish_png_lanes(
        self, filtered, lanes, sizes, results, itemsize, samples=1
    ):
        """Deflate + frame filtered device output (shared tail of both
        device paths). Padding slices away per lane: filters never look
        right or down, so the real region's bytes are identical."""
        bit_depth = itemsize * 8
        color_type = 0 if samples == 1 else 2
        bpp = samples * itemsize
        payloads = [
            filtered[j, :h, : 1 + w * bpp].tobytes()
            for j, (w, h) in enumerate(sizes)
        ]
        engine = get_engine()
        if engine is not None:
            with TRACER.start_span("batch_encode"):
                pngs = engine.png_assemble_batch(
                    payloads,
                    widths=[w for w, _ in sizes],
                    heights=[h for _, h in sizes],
                    bit_depths=[bit_depth] * len(lanes),
                    color_types=[color_type] * len(lanes),
                    level=self.png_level,
                    strategy=self.png_strategy,
                )
            for (j, i), png in zip(enumerate(lanes), pngs):
                if png is None:
                    w, h = sizes[j]
                    results[i] = assemble_png(
                        payloads[j], w, h, bit_depth, color_type,
                        self.png_level, self.png_strategy,
                    )
                else:
                    results[i] = png
            return
        with TRACER.start_span("batch_encode"):
            futs = {
                i: self._encode_pool.submit(
                    assemble_png, payloads[j], sizes[j][0], sizes[j][1],
                    bit_depth, color_type, self.png_level,
                    self.png_strategy,
                )
                for j, i in enumerate(lanes)
            }
            for i, fut in futs.items():
                try:
                    # audited: this runs on a BATCHER executor thread,
                    # never the event loop, and the futures resolve on
                    # the separate _encode_pool — distinct pools, so
                    # the wait cannot self-deadlock
                    results[i] = fut.result()  # ompb-lint: disable=loop-block -- executor-thread wait on a different pool
                except Exception:
                    log.exception("encode failed for lane %d", i)
                    results[i] = None

    def _log_device_deflate(self) -> None:
        if not self._device_deflate_logged:
            self._device_deflate_logged = True
            log.info(
                "device deflate active (mode=%s, queue-depth=%d): PNG "
                "lanes compress on the accelerator through the "
                "streaming encode queue; backend.png.level/strategy "
                "apply only to host-encoded lanes",
                self.device_deflate_mode, self.queue_depth,
            )

    def _submit_bucket_groups(
        self, lanes, tiles, bh, bw, dtype, samples=1
    ):
        """Host-staged lanes -> double-buffered fused dispatch. Lanes
        group by real (w, h) — stream layout is static per payload
        length, one jit specialization per size — and each group
        becomes one dispatcher submission: H2D + the single fused
        byteswap+filter+deflate program + async readback. Returns
        [(lane_indices, future)] for handle_batch to drain."""
        self._log_device_deflate()
        disp = self._get_dispatcher()
        itemsize = dtype.itemsize
        bpp = samples * itemsize
        groups: Dict[Tuple[int, int], List[int]] = {}
        for i in lanes:
            t = tiles[i]
            groups.setdefault((t.shape[1], t.shape[0]), []).append(i)
        pending = []
        with TRACER.start_span("batch_device"):
            for (w, h), idxs in groups.items():
                shape = (
                    (len(idxs), bh, bw) if samples == 1
                    else (len(idxs), bh, bw, samples)
                )
                batch = np.zeros(shape, dtype=dtype)
                for j, i in enumerate(idxs):
                    t = tiles[i]
                    batch[j, : t.shape[0], : t.shape[1]] = t
                try:
                    fut = disp.submit(
                        batch, h, 1 + w * bpp, bpp, self.png_filter,
                        self.device_deflate_mode, idxs,
                        [(w, h)] * len(idxs),
                        itemsize * 8, 0 if samples == 1 else 2,
                    )
                except Exception as e:
                    # a raise here must not lose the futures of groups
                    # ALREADY submitted in this loop — degrade this
                    # group alone through the normal drain fallback
                    fut = concurrent.futures.Future()
                    fut.set_exception(e)
                pending.append((idxs, fut))
        return pending

    def _submit_plane_groups(
        self, plane, lanes, resolved, bh, bw, dtype
    ):
        """HBM-resident lanes -> fused dispatch: crop on device, then
        the same fused filter+deflate program per (w, h) group — the
        tiles never exist on the host at all."""
        self._log_device_deflate()
        disp = self._get_dispatcher()
        cache = self._get_plane_cache()
        # where the planes spread over chips the group names its
        # plane's: it runs there, through the queue's pipe of such groups
        device = device_of(plane) if cache.spread else None
        itemsize = dtype.itemsize
        groups: Dict[Tuple[int, int], List[int]] = {}
        for i in lanes:
            groups.setdefault((resolved[i].w, resolved[i].h), []).append(i)
        pending = []
        with TRACER.start_span("batch_device"):
            for (w, h), idxs in groups.items():
                coords = [(resolved[i].y, resolved[i].x) for i in idxs]
                try:
                    fut = disp.submit(
                        cache.crop_batch(plane, coords, bh, bw),
                        h, 1 + w * itemsize, itemsize,
                        self.png_filter, self.device_deflate_mode, idxs,
                        [(w, h)] * len(idxs), itemsize * 8, 0,
                        staged=True, device=device,
                    )
                    fut.plane = plane  # _count_device_lanes: its chip
                except Exception as e:
                    # same per-group degradation as the bucket path
                    fut = concurrent.futures.Future()
                    fut.set_exception(e)
                pending.append((idxs, fut))
        return pending

    def _host_png_lanes(self, lanes, tiles, ctxs, results) -> None:
        """Host engine: the whole batch in one fused native call
        (byteswap + filter + deflate + framing on the C++ pool). Falls
        back to per-lane python encode without the native engine."""
        engine = get_engine()
        encoded = None
        if engine is not None:
            with TRACER.start_span("batch_encode"), stage_all(
                [ctxs[i] for i in lanes], "encode"
            ):
                encoded = engine.png_encode_batch(
                    [tiles[i] for i in lanes],
                    filter_mode=self.png_filter,
                    level=self.png_level,
                    strategy=self.png_strategy,
                )
        if encoded is None:
            for i in lanes:
                results[i] = self.encode(ctxs[i], tiles[i])
            return
        for i, png in zip(lanes, encoded):
            results[i] = (
                png if png is not None else self.encode(ctxs[i], tiles[i])
            )

    def _device_png_lanes(
        self, lanes, tiles, ctxs, results, bh, bw, dtype, samples=1
    ):
        """Host-staged device path: tiles padded into one bucket batch,
        transferred, filtered on device, then the shared deflate tail.
        Grayscale and RGB ride the same math — the filter unit (bpp) is
        just samples*itemsize bytes. With a serving mesh the batch axis
        shards across chips (data parallel — the reference's worker
        pool over ICI)."""
        itemsize = dtype.itemsize
        bpp = samples * itemsize
        shape = (
            (len(lanes), bh, bw) if samples == 1
            else (len(lanes), bh, bw, samples)
        )
        batch = np.zeros(shape, dtype=dtype)
        for j, i in enumerate(lanes):
            t = tiles[i]
            batch[j, : t.shape[0], : t.shape[1]] = t
        mesh = self._get_mesh()
        with TRACER.start_span("batch_device"):
            if mesh is not None:
                from ..parallel.sharding import (
                    pad_batch,
                    shard_batch,
                    sharded_batch_filter,
                )

                n = mesh.shape["data"]
                padded, real = pad_batch(jnp.asarray(batch), n)
                sharded = shard_batch(mesh, padded)
                filtered = sharded_batch_filter(
                    mesh, sharded, bpp, self.png_filter
                )[:real]
            elif self.use_pallas and pallas_supports(
                (bh, bw), dtype, samples
            ):
                # fused Pallas kernel: byteswap + filter in one VMEM
                # pass (grayscale and interleaved RGB lanes alike)
                filtered = pallas_filter_tiles(
                    jnp.asarray(batch), self.png_filter
                )
            else:
                rows = to_big_endian_bytes(jnp.asarray(batch))
                if samples > 1:
                    # (B, bh, bw, S*itemsize) interleaved -> scanrows
                    rows = rows.reshape(len(lanes), bh, bw * bpp)
                filtered = filter_batch(
                    rows, bpp, self.png_filter
                )  # (B, bh, 1 + bw*bpp)
        sizes = [(tiles[i].shape[1], tiles[i].shape[0]) for i in lanes]
        self._finish_png_lanes(
            # ompb-lint: disable=jax-hotpath -- the ONE intended device->host pull of this path (filtered scanlines for the host deflate tail)
            np.asarray(filtered), lanes, sizes, results, itemsize,
            samples,
        )

    def _distributed_plane_lane(self, mesh, i, tile, results) -> None:
        """Space-parallel path for one plane-sized PNG lane: rows shard
        across the mesh, the Up filter's one-row dependency rides a
        ppermute halo exchange over ICI, and only filtered scanlines
        return to the host (SURVEY.md §5.7's long-context analog).
        Rows pad up to the mesh size; padding sits BELOW the real rows
        (Up only looks upward) and slices away before assembly."""
        from ..parallel.sharding import (
            distributed_filter_plane,
            shard_rows,
        )

        itemsize = tile.dtype.itemsize
        h, w = tile.shape
        n = mesh.shape["data"]
        pad = (-h) % n
        arr = np.pad(tile, ((0, pad), (0, 0))) if pad else tile
        with TRACER.start_span("batch_device"):
            rows_sharded = shard_rows(mesh, jnp.asarray(arr))
            # ompb-lint: disable=jax-hotpath -- the ONE intended device->host pull: filtered scanlines return once per plane
            filtered = np.asarray(
                distributed_filter_plane(mesh, rows_sharded, mode="up")
            )[:h]
        self._finish_png_lanes(
            filtered[None], [i], [(w, h)], results, itemsize
        )
