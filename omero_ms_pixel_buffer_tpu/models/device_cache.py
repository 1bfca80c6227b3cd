"""HBM-resident plane cache for the device engine.

The reference reads every tile from disk per request
(TileRequestHandler.java:104-112). The device engine's TPU-first
counterpart keeps whole decoded planes resident in HBM: the first tile
of a plane pays one host read + one host->HBM transfer; every later
tile on that plane is a `dynamic_slice` crop executed on the device,
so the per-tile host->device traffic drops from tile-bytes to zero.
This is the "double-buffered HBM staging of chunk-aligned reads"
design from SURVEY.md §5.7/§5.8.

Planes are evicted LRU by byte budget (OMPB_HBM_CACHE_MB, default
4096 — a v5e chip has 16 GB of HBM; the serving working set of a
viewer session is a handful of planes). Crops are jitted per
(bucket-shape, dtype): start indices are runtime values, so one
compilation serves every tile position.
"""

from __future__ import annotations

import logging
import os
import threading
from collections import OrderedDict
from functools import partial
from typing import Optional, Sequence, Tuple

import numpy as np

log = logging.getLogger("omero_ms_pixel_buffer_tpu.device_cache")


def default_hbm_cache_bytes() -> int:
    return int(os.environ.get("OMPB_HBM_CACHE_MB", "4096")) << 20


_crop_batch_jit = None


def _crop_batch(plane, ys, xs, bh: int, bw: int):
    """Gather N (bh, bw) crops from one resident plane. vmap over the
    per-lane start indices; slice sizes are static per bucket so XLA
    compiles one gather kernel per (bucket, dtype). The jitted callable
    is built on first use so importing this module never imports jax."""
    global _crop_batch_jit
    if _crop_batch_jit is None:
        import jax
        from jax import lax

        @partial(jax.jit, static_argnums=(3, 4))
        def crop(plane, ys, xs, bh, bw):
            def one(y0, x0):
                return lax.dynamic_slice(plane, (y0, x0), (bh, bw))

            return jax.vmap(one)(ys, xs)

        _crop_batch_jit = crop
    return _crop_batch_jit(plane, ys, xs, bh, bw)


class DevicePlaneCache:
    """LRU of device-resident (level, z, c, t) planes per buffer.

    Admission: a plane is staged only on its ``admit_after``-th touch
    (default 2) — one stray tile on a cold plane must not pay a
    multi-hundred-MB read/decode/transfer, and a working set larger
    than the budget degrades to the batched host-read path instead of
    thrashing full-plane restages."""

    def __init__(
        self, max_bytes: Optional[int] = None, admit_after: int = 2
    ):
        self.max_bytes = (
            default_hbm_cache_bytes() if max_bytes is None else max_bytes
        )
        self.admit_after = admit_after
        self._planes: "OrderedDict[tuple, object]" = OrderedDict()
        self._touches: OrderedDict = OrderedDict()  # key -> count
        self._staging: set = set()  # keys being read/transferred now
        self._bytes = 0
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def _key(self, buffer, level: int, z: int, c: int, t: int) -> tuple:
        return (buffer.cache_ns, level, z, c, t)

    def get_plane(self, buffer, level: int, z: int, c: int, t: int):
        """The device array for a whole plane, staging it once the
        admission threshold is met; None when not (yet) resident
        (caller falls back to host staging)."""
        import jax

        key = self._key(buffer, level, z, c, t)
        with self._lock:
            plane = self._planes.get(key)
            if plane is not None:
                self._planes.move_to_end(key)
                self.hits += 1
                return plane
            self.misses += 1
            touches = self._touches.pop(key, 0) + 1
            if touches < self.admit_after:
                # re-insert at the recent end so active warmers survive
                # the bounded trim; admitted keys leave the dict (their
                # count must restart after an eviction, or a working
                # set above the budget thrashes full-plane restages)
                self._touches[key] = touches
                while len(self._touches) > 4096:
                    self._touches.popitem(last=False)
                return None
            if key in self._staging:
                # single-flight: another thread is mid-read/transfer of
                # this multi-hundred-MB plane; duplicating the work
                # doubles host+HBM pressure for nothing. Followers take
                # the host path this once.
                return None
            self._staging.add(key)
        plane = None
        try:
            # budget check BEFORE materializing anything: a whole-slide
            # plane can be tens of GB, and rejecting it must cost nothing
            size_x, size_y = buffer.level_size(level)
            nbytes = size_x * size_y * buffer.meta.bytes_per_pixel
            if self.max_bytes <= 0 or nbytes > self.max_bytes:
                return None
            host = buffer.get_tile_at(level, z, c, t, 0, 0, size_x, size_y)
            if host.dtype.byteorder == ">":
                # device arrays are native-endian; byteswap at staging
                host = host.astype(host.dtype.newbyteorder("="))
            nbytes = host.nbytes
            plane = jax.device_put(np.ascontiguousarray(host))
        finally:
            # publish and release the staging claim under ONE lock
            # acquisition: a gap between them would let a concurrent
            # thread re-stage the plane this guard exists to dedupe
            with self._lock:
                self._staging.discard(key)
                if plane is not None and key not in self._planes:
                    self._planes[key] = plane
                    self._bytes += nbytes
                    while (
                        self._bytes > self.max_bytes
                        and len(self._planes) > 1
                    ):
                        _, evicted = self._planes.popitem(last=False)
                        self._bytes -= evicted.nbytes
        return plane

    def crop_batch(
        self, plane, coords: Sequence[Tuple[int, int]], bh: int, bw: int
    ):
        """(B, bh, bw) device batch of crops at the given (y, x)
        starts. Starts must be in-bounds for the static slice size
        (dynamic_slice clamps silently otherwise — callers pre-clamp
        and slice the valid region out after filtering)."""
        import jax.numpy as jnp

        ys = jnp.asarray([c[0] for c in coords], jnp.int32)
        xs = jnp.asarray([c[1] for c in coords], jnp.int32)
        return _crop_batch(plane, ys, xs, bh, bw)

    def invalidate_ns(self, cache_ns) -> int:
        """Drop every resident plane (and pending admission count) of
        one buffer namespace — the image-invalidation hook: a changed
        ``pixels`` row means the staged planes no longer match disk.
        Returns how many planes were dropped."""
        with self._lock:
            victims = [k for k in self._planes if k[0] == cache_ns]
            for k in victims:
                plane = self._planes.pop(k)
                self._bytes -= plane.nbytes
            for k in [t for t in self._touches if t[0] == cache_ns]:
                self._touches.pop(k, None)
        if victims:
            log.info(
                "invalidated %d device plane(s) for namespace %s",
                len(victims), cache_ns,
            )
        return len(victims)

    def snapshot(self) -> dict:
        """/healthz view: residency + effectiveness of the HBM tier."""
        with self._lock:
            return {
                "planes": len(self._planes),
                "bytes": self._bytes,
                "max_bytes": self.max_bytes,
                "hits": self.hits,
                "misses": self.misses,
                # where the resident planes live (device ids): on a
                # multi-chip host staging goes to the default device
                "devices": sorted({
                    d.id for p in self._planes.values()
                    for d in p.devices()
                }),
            }

    @property
    def nbytes(self) -> int:
        with self._lock:
            return self._bytes

    def __len__(self) -> int:
        with self._lock:
            return len(self._planes)
