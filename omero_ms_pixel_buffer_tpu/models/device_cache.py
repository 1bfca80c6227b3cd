"""HBM-resident plane cache for the device engine.

The reference reads every tile from disk per request
(TileRequestHandler.java:104-112). The device engine's TPU-first
counterpart keeps whole decoded planes resident in HBM: the first tile
of a plane pays one host read + one host->HBM transfer; every later
tile on that plane is a `dynamic_slice` crop executed on the device,
so the per-tile host->device traffic drops from tile-bytes to zero.
This is the "double-buffered HBM staging of chunk-aligned reads"
design from SURVEY.md §5.7/§5.8.

Planes are evicted LRU by byte budget (config ``backend.plane-cache-mb``,
default 4096 — a v5e chip has 16 GB of HBM; a deployment that means to
hold a whole Z stack resident sets it to the stack's size). Crops are
jitted per (bucket-shape, dtype): start indices are runtime values, so
one compilation serves every tile position.
"""

from __future__ import annotations

import concurrent.futures
import logging
import threading
import time
from collections import OrderedDict
from functools import partial
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..utils.metrics import REGISTRY

log = logging.getLogger("omero_ms_pixel_buffer_tpu.device_cache")

DEFAULT_MAX_BYTES = 4096 << 20

# planes of one batch that pass admission together are read and
# transferred side by side, this many at a time
_STAGERS = 4

# the first crop of a (plane shape, dtype, bucket) class compiles the
# crop at every power-of-two lane count up to this one (config's
# default ``max-batch``), so a lane count a deployment rarely meets
# does not compile in the middle of serving
_WARM_LANES = 32

PLANE_ADMISSIONS = REGISTRY.counter(
    "device_plane_admissions_total",
    "Planes staged into the HBM plane cache",
)
PLANE_EVICTIONS = REGISTRY.counter(
    "device_plane_evictions_total",
    "Planes evicted from the HBM plane cache by its byte budget",
)
PLANE_BYTES = REGISTRY.gauge(
    "device_plane_bytes", "Bytes of planes resident in the HBM plane cache"
)
PLANE_STAGE_SECONDS = REGISTRY.histogram(
    "device_plane_stage_seconds",
    "Staging one plane into HBM (stage=read: decode the whole plane on "
    "the host; stage=h2d: the transfer, to its end)",
)


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
            DEFAULT_MAX_BYTES if max_bytes is None else max_bytes
        )
        self.admit_after = admit_after
        self._planes: "OrderedDict[tuple, object]" = OrderedDict()
        self._touches: OrderedDict = OrderedDict()  # key -> count
        self._staging: set = set()  # keys being read/transferred now
        self._bytes = 0
        self._lock = threading.Lock()
        self._stagers: Optional[concurrent.futures.Executor] = None
        self._warm_crops: set = set()  # (plane shape, dtype, bh, bw)
        self.hits = 0
        self.misses = 0
        self.admissions = 0
        self.evictions = 0

    def close(self) -> None:
        with self._lock:
            stagers, self._stagers = self._stagers, None
        if stagers is not None:
            stagers.shutdown(wait=False)

    def _key(self, buffer, level: int, z: int, c: int, t: int) -> tuple:
        return (buffer.cache_ns, level, z, c, t)

    def get_plane(self, buffer, level: int, z: int, c: int, t: int):
        """The device array for a whole plane, staging it once the
        admission threshold is met; None when not (yet) resident
        (caller falls back to host staging)."""
        return self.get_planes([(buffer, level, z, c, t)])[0]

    def get_planes(
        self, wanted: Sequence[tuple], on_error=None
    ) -> List[Optional[object]]:
        """``get_plane`` for every (buffer, level, z, c, t) of one
        batch, one admission touch each. The planes that pass
        admission together are staged side by side (``_STAGERS`` at a
        time): a batch across the Z sections of a cold stack waits for
        its slowest plane, not for their sum. A staging that fails
        leaves its entry None and is handed to ``on_error``; without
        one the first failure is raised, after the others have
        finished (they stay resident)."""
        out: List[Optional[object]] = [None] * len(wanted)
        claimed: List[Tuple[int, tuple]] = []
        with self._lock:
            for n, (buffer, level, z, c, t) in enumerate(wanted):
                key = self._key(buffer, level, z, c, t)
                plane = self._planes.get(key)
                if plane is not None:
                    self._planes.move_to_end(key)
                    self.hits += 1
                    out[n] = plane
                    continue
                self.misses += 1
                touches = self._touches.pop(key, 0) + 1
                if touches < self.admit_after:
                    # re-insert at the recent end so active warmers
                    # survive the bounded trim; admitted keys leave the
                    # dict (their count must restart after an eviction,
                    # or a working set above the budget thrashes
                    # full-plane restages)
                    self._touches[key] = touches
                    while len(self._touches) > 4096:
                        self._touches.popitem(last=False)
                    continue
                if key in self._staging:
                    # single-flight: another thread is mid-read/transfer
                    # of this multi-hundred-MB plane; duplicating the
                    # work doubles host+HBM pressure for nothing.
                    # Followers take the host path this once.
                    continue
                self._staging.add(key)
                claimed.append((n, key))
            if len(claimed) > 1 and self._stagers is None:
                self._stagers = concurrent.futures.ThreadPoolExecutor(
                    _STAGERS, thread_name_prefix="plane-stage"
                )
            stagers = self._stagers

        def attempt(n, key):
            try:
                return self._stage(key, *wanted[n])
            except Exception as e:  # _stage has released the claim
                return e

        if len(claimed) == 1:
            staged = [attempt(*claimed[0])]
        else:
            futures = []
            try:
                for n, key in claimed:
                    futures.append(stagers.submit(attempt, n, key))
            except RuntimeError as e:
                # close() won the race: the planes not handed over
                # will never reach _stage, so their claims go here
                rest = claimed[len(futures):]
                with self._lock:
                    self._staging.difference_update(k for _, k in rest)
                staged = [f.result() for f in futures] + [e] * len(rest)
            else:
                staged = [f.result() for f in futures]
        failure = None
        for (n, _), got in zip(claimed, staged):
            if not isinstance(got, Exception):
                out[n] = got
            elif on_error is not None:
                on_error(got)
            elif failure is None:
                failure = got
        if failure is not None:
            raise failure
        return out

    def _stage(self, key, buffer, level: int, z: int, c: int, t: int):
        """Read one whole plane and put it on the device; the caller
        holds the staging claim on ``key``, released here."""
        import jax
        from jax.profiler import TraceAnnotation

        plane, nbytes = None, 0
        try:
            # budget check BEFORE materializing anything: a whole-slide
            # plane can be tens of GB, and rejecting it must cost nothing
            size_x, size_y = buffer.level_size(level)
            nbytes = size_x * size_y * buffer.meta.bytes_per_pixel
            if self.max_bytes <= 0 or nbytes > self.max_bytes:
                return None
            # named on the profiler's clock: an admission that falls
            # inside a traced slice shows as itself, not as the queue
            # stage that happened to be open
            with TraceAnnotation(
                "ompb.plane.stage", plane=str(key[1:]), bytes=nbytes
            ):
                t0 = time.perf_counter()
                host = buffer.get_tile_at(
                    level, z, c, t, 0, 0, size_x, size_y
                )
                if host.dtype.byteorder == ">":
                    # device arrays are native-endian; byteswap at staging
                    host = host.astype(host.dtype.newbyteorder("="))
                host = np.ascontiguousarray(host)
                nbytes = host.nbytes
                t1 = time.perf_counter()
                plane = jax.block_until_ready(jax.device_put(host))  # ompb-lint: disable=jax-hotpath -- staging a plane IS the transfer; it is timed to its end
                PLANE_STAGE_SECONDS.observe(t1 - t0, stage="read")
                PLANE_STAGE_SECONDS.observe(
                    time.perf_counter() - t1, stage="h2d"
                )
        finally:
            # publish and release the staging claim under ONE lock
            # acquisition: a gap between them would let a concurrent
            # thread re-stage the plane this guard exists to dedupe
            evicted = 0
            with self._lock:
                self._staging.discard(key)
                if plane is not None and key not in self._planes:
                    self._planes[key] = plane
                    self._bytes += nbytes
                    self.admissions += 1
                    while (
                        self._bytes > self.max_bytes
                        and len(self._planes) > 1
                    ):
                        _, victim = self._planes.popitem(last=False)
                        self._bytes -= victim.nbytes
                        evicted += 1
                    self.evictions += evicted
                    PLANE_BYTES.set(self._bytes)
                    PLANE_ADMISSIONS.inc()
                    if evicted:
                        PLANE_EVICTIONS.inc(evicted)
        return plane

    def crop_batch(
        self, plane, coords: Sequence[Tuple[int, int]], bh: int, bw: int
    ):
        """Device batch of crops at the given (y, x) starts, the lane
        axis padded to a power of two by repeating the last start: the
        encode programs pad to the same counts, so this path adds one
        small program a count and no pad. Lane j is ``coords[j]``; the
        caller ignores the lanes past them. Starts must be in-bounds
        for the static slice size (dynamic_slice clamps silently
        otherwise — callers pre-clamp and slice the valid region out
        after filtering)."""
        import jax.numpy as jnp

        key = (plane.shape, plane.dtype.str, bh, bw)
        with self._lock:
            cold = key not in self._warm_crops
            self._warm_crops.add(key)
        if cold:
            n = 1
            while n <= _WARM_LANES:
                zeros = jnp.asarray([0] * n, jnp.int32)
                _crop_batch(plane, zeros, zeros, bh, bw)
                n *= 2
        pad = (1 << max(len(coords) - 1, 0).bit_length()) - len(coords)
        coords = list(coords) + [coords[-1]] * pad
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
            PLANE_BYTES.set(self._bytes)
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
                "admissions": self.admissions,
                "evictions": self.evictions,
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
