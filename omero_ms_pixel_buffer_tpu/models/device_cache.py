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

On a host with several chips the cache is a cache a chip: a plane
belongs to one chip, chosen when it is admitted (the least loaded one,
ties broken by the plane's place in a Z sweep, so the chips' resident
bytes stay level and the consecutive requests of a sweep fall on
different chips); the budget is split evenly over the chips and a chip
evicts only its own planes. A program is compiled once a device, so
the crop is warmed once a device too, and a plane is published (counted
in ``snapshot()["planes"]``, handed to callers) only after the caller's
``warm`` hook has run on its chip: resident means servable.
"""

from __future__ import annotations

import concurrent.futures
import logging
import threading
import time
from collections import OrderedDict
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

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
    "device_plane_bytes",
    "Bytes of planes resident in the HBM plane cache (with a `chip` "
    "label, like the family's other series, where the cache spreads "
    "over several chips)",
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


def device_of(plane):
    """The one device that holds a resident plane (or a crop of it)."""
    return next(iter(plane.devices()))


class _Chip:
    """One chip's share of the cache: its planes in LRU order, the
    bytes it holds and has promised to stagings in flight, and its
    counters. ``device`` None is the process's default device (the
    cache of a one-chip host, which names no device anywhere)."""

    __slots__ = (
        "device", "planes", "bytes", "claimed", "hits", "misses",
        "lanes", "evictions",
    )

    def __init__(self, device=None):
        self.device = device
        self.planes: "OrderedDict[tuple, object]" = OrderedDict()
        self.bytes = 0
        self.claimed = 0
        self.hits = 0
        self.misses = 0
        self.lanes = 0
        self.evictions = 0

    @property
    def id(self) -> int:
        if self.device is not None:
            return self.device.id
        import jax

        return jax.devices()[0].id


class DevicePlaneCache:
    """LRU of device-resident (level, z, c, t) planes per buffer, a
    shelf a chip.

    Admission: a plane is staged only on its ``admit_after``-th touch
    (default 2) — one stray tile on a cold plane must not pay a
    multi-hundred-MB read/decode/transfer, and a working set larger
    than the budget degrades to the batched host-read path instead of
    thrashing full-plane restages.

    ``devices``: the chips the planes are spread over (the serving
    mesh's); None or a single one keeps every plane on the process's
    default device. ``max_bytes`` is the process's budget whatever the
    chip count: each chip gets an even share of it."""

    def __init__(
        self, max_bytes: Optional[int] = None, admit_after: int = 2,
        devices: Optional[Sequence] = None,
    ):
        self.max_bytes = (
            DEFAULT_MAX_BYTES if max_bytes is None else max_bytes
        )
        self.admit_after = admit_after
        spread = devices is not None and len(devices) > 1
        self._chips: List[_Chip] = (
            [_Chip(d) for d in devices] if spread else [_Chip()]
        )
        self._where: Dict[tuple, _Chip] = {}  # resident key -> its chip
        self._touches: OrderedDict = OrderedDict()  # key -> count
        self._staging: set = set()  # keys being read/transferred now
        self._lock = threading.Lock()
        self._stagers: Optional[concurrent.futures.Executor] = None
        # (device id, plane shape, dtype, bh, bw): a program is
        # compiled once a device
        self._warm_crops: set = set()
        self.hits = 0
        self.misses = 0
        self.admissions = 0
        self.evictions = 0
        # what check_budget() found at start-up, for /healthz
        self.budget_error: Optional[str] = None

    @property
    def spread(self) -> bool:
        """Whether the planes live on more than one chip."""
        return len(self._chips) > 1

    @property
    def chip_max_bytes(self) -> int:
        """A chip's even share of the process's budget."""
        return self.max_bytes // len(self._chips)

    def _labels(self, chip: _Chip) -> dict:
        """The `chip` label of the family's series. A one-chip cache
        names none: its series are the ones it always had."""
        return {"chip": str(chip.id)} if self.spread else {}

    def check_budget(self) -> Optional[str]:
        """Start-up check: a chip's share of the budget above what the
        chip's memory holds can never be met, and is said at once (log,
        /healthz ``cache.device_planes.error``) instead of at the first
        staging that does not fit. None where the share fits or the
        backend reports no memory (the CPU's)."""
        import jax

        share = self.chip_max_bytes
        error = None
        for chip in self._chips:
            device = chip.device or jax.devices()[0]
            try:
                limit = (device.memory_stats() or {}).get("bytes_limit")
            except Exception:
                limit = None
            if limit is not None and share > limit:
                error = (
                    f"backend.plane-cache-mb: {self.max_bytes >> 20} MiB "
                    f"over {len(self._chips)} chip(s) is {share >> 20} MiB "
                    f"a chip, above chip {device.id}'s memory of "
                    f"{limit >> 20} MiB"
                )
                log.error(error)
                break
        with self._lock:
            self.budget_error = error
        return error

    def close(self) -> None:
        with self._lock:
            stagers, self._stagers = self._stagers, None
        if stagers is not None:
            stagers.shutdown(wait=False)

    def _key(self, buffer, level: int, z: int, c: int, t: int) -> tuple:
        return (buffer.cache_ns, level, z, c, t)

    def _sweep_slot(self, buffer, z: int, c: int, t: int) -> int:
        """The plane's place in a Z sweep (t, then z, then c inside),
        folded onto the chips."""
        meta = getattr(buffer, "meta", None)
        size_c = max(int(getattr(meta, "size_c", 1) or 1), 1)
        size_z = max(int(getattr(meta, "size_z", 1) or 1), 1)
        return ((t * size_z + z) * size_c + c) % len(self._chips)

    def _place(self, slot: int) -> _Chip:
        """The chip a plane admitted now belongs to: the one holding
        (and promised) the fewest bytes, so the chips stay level; among
        equals the first from the plane's sweep slot on, so the
        consecutive planes of a sweep land on different chips. Caller
        holds the lock."""
        n = len(self._chips)
        order = [self._chips[(slot + k) % n] for k in range(n)]
        return min(order, key=lambda chip: chip.bytes + chip.claimed)

    def get_plane(self, buffer, level: int, z: int, c: int, t: int):
        """The device array for a whole plane, staging it once the
        admission threshold is met; None when not (yet) resident
        (caller falls back to host staging)."""
        return self.get_planes([(buffer, level, z, c, t)])[0]

    def get_planes(
        self, wanted: Sequence[tuple], on_error=None,
        warm: Optional[Callable[[int, object], None]] = None,
    ) -> List[Optional[object]]:
        """``get_plane`` for every (buffer, level, z, c, t) of one
        batch, one admission touch each. The planes that pass
        admission together are staged side by side (``_STAGERS`` at a
        time): a batch across the Z sections of a cold stack waits for
        its slowest plane, not for their sum. A staging that fails
        leaves its entry None and is handed to ``on_error``; without
        one the first failure is raised, after the others have
        finished (they stay resident). ``warm(n, plane)`` runs after
        plane ``wanted[n]`` has reached its chip and before it is
        published: the caller compiles there what its lanes will run,
        so a plane that counts as resident compiles nothing."""
        out: List[Optional[object]] = [None] * len(wanted)
        claimed: List[Tuple[int, tuple]] = []
        with self._lock:
            for n, (buffer, level, z, c, t) in enumerate(wanted):
                key = self._key(buffer, level, z, c, t)
                chip = self._where.get(key)
                if chip is not None:
                    chip.planes.move_to_end(key)
                    chip.hits += 1
                    self.hits += 1
                    out[n] = chip.planes[key]
                    continue
                self.misses += 1
                self._chips[self._sweep_slot(buffer, z, c, t)].misses += 1
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
                return self._stage(
                    key, *wanted[n],
                    warm=None if warm is None else partial(warm, n),
                )
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

    def _stage(
        self, key, buffer, level: int, z: int, c: int, t: int, warm=None
    ):
        """Read one whole plane, put it on its chip and run ``warm``
        there; the caller holds the staging claim on ``key``, released
        here. The chip is chosen, and the plane's bytes promised to it,
        before anything is read: planes staged side by side spread over
        the chips instead of all choosing the one that was emptiest."""
        import jax
        from jax.profiler import TraceAnnotation

        plane, nbytes, chip, promised = None, 0, None, 0
        try:
            # budget check BEFORE materializing anything: a whole-slide
            # plane can be tens of GB, and rejecting it must cost nothing
            size_x, size_y = buffer.level_size(level)
            nbytes = size_x * size_y * buffer.meta.bytes_per_pixel
            if self.max_bytes <= 0 or nbytes > self.chip_max_bytes:
                return None
            with self._lock:
                chip = self._place(self._sweep_slot(buffer, z, c, t))
                chip.claimed += nbytes
                promised = nbytes
            labels = self._labels(chip)
            # named on the profiler's clock: an admission that falls
            # inside a traced slice shows as itself, not as the queue
            # stage that happened to be open
            with TraceAnnotation(
                "ompb.plane.stage", plane=str(key[1:]), bytes=nbytes,
                **labels,
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
                plane = jax.block_until_ready(jax.device_put(host, chip.device))  # ompb-lint: disable=jax-hotpath -- staging a plane IS the transfer; it is timed to its end
                PLANE_STAGE_SECONDS.observe(t1 - t0, stage="read", **labels)
                PLANE_STAGE_SECONDS.observe(
                    time.perf_counter() - t1, stage="h2d", **labels
                )
            if warm is not None:
                try:
                    warm(plane)
                except Exception:
                    plane = None  # not servable: not resident
                    raise
        finally:
            # publish and release the staging claim under ONE lock
            # acquisition: a gap between them would let a concurrent
            # thread re-stage the plane this guard exists to dedupe
            evicted = 0
            with self._lock:
                self._staging.discard(key)
                if chip is not None:
                    chip.claimed -= promised
                if plane is not None and key not in self._where:
                    chip.planes[key] = plane
                    self._where[key] = chip
                    chip.bytes += nbytes
                    self.admissions += 1
                    while (
                        chip.bytes > self.chip_max_bytes
                        and len(chip.planes) > 1
                    ):
                        gone, victim = chip.planes.popitem(last=False)
                        del self._where[gone]
                        chip.bytes -= victim.nbytes
                        evicted += 1
                    chip.evictions += evicted
                    self.evictions += evicted
                    PLANE_BYTES.set(chip.bytes, **labels)
                    PLANE_ADMISSIONS.inc(**labels)
                    if evicted:
                        PLANE_EVICTIONS.inc(evicted, **labels)
        return plane

    def crop_batch(
        self, plane, coords: Sequence[Tuple[int, int]], bh: int, bw: int
    ):
        """Device batch of crops at the given (y, x) starts, on the
        plane's chip, the lane axis padded to a power of two by
        repeating the last start: the encode programs pad to the same
        counts, so this path adds one small program a count and no
        pad. Lane j is ``coords[j]``; the caller ignores the lanes past
        them. Starts must be in-bounds for the static slice size
        (dynamic_slice clamps silently otherwise — callers pre-clamp
        and slice the valid region out after filtering). The starts go
        in as host arrays: the program's own transfer takes them
        straight to the plane's chip."""
        key = (
            device_of(plane).id, plane.shape, plane.dtype.str, bh, bw
        )
        with self._lock:
            cold = key not in self._warm_crops
            self._warm_crops.add(key)
        if cold:
            n = 1
            while n <= _WARM_LANES:
                zeros = np.zeros(n, np.int32)
                _crop_batch(plane, zeros, zeros, bh, bw)
                n *= 2
        pad = (1 << max(len(coords) - 1, 0).bit_length()) - len(coords)
        coords = list(coords) + [coords[-1]] * pad
        ys = np.asarray([c[0] for c in coords], np.int32)
        xs = np.asarray([c[1] for c in coords], np.int32)
        return _crop_batch(plane, ys, xs, bh, bw)

    def note_lanes(self, plane, lanes: int) -> dict:
        """Count lanes served from ``plane``'s chip (``per_chip``);
        returns that chip's metric labels."""
        device = device_of(plane)
        with self._lock:
            for chip in self._chips:
                if chip.device is None or chip.device == device:
                    chip.lanes += lanes
                    return self._labels(chip)
        return {}

    def invalidate_ns(self, cache_ns) -> int:
        """Drop every resident plane (and pending admission count) of
        one buffer namespace — the image-invalidation hook: a changed
        ``pixels`` row means the staged planes no longer match disk.
        Returns how many planes were dropped."""
        with self._lock:
            victims = [k for k in self._where if k[0] == cache_ns]
            for k in victims:
                chip = self._where.pop(k)
                chip.bytes -= chip.planes.pop(k).nbytes
            for chip in self._chips:
                PLANE_BYTES.set(chip.bytes, **self._labels(chip))
            for k in [t for t in self._touches if t[0] == cache_ns]:
                self._touches.pop(k, None)
        if victims:
            log.info(
                "invalidated %d device plane(s) for namespace %s",
                len(victims), cache_ns,
            )
        return len(victims)

    def snapshot(self) -> dict:
        """/healthz view: residency + effectiveness of the HBM tier,
        the process's totals and a row a chip. A chip's ``misses`` are
        those of the planes whose sweep slot it is (a plane has no
        chip before it is admitted); its ``lanes`` are the device
        lanes cropped from its planes."""
        with self._lock:
            out = {
                "planes": len(self._where),
                "bytes": sum(chip.bytes for chip in self._chips),
                "max_bytes": self.max_bytes,
                "chip_max_bytes": self.chip_max_bytes,
                "hits": self.hits,
                "misses": self.misses,
                "admissions": self.admissions,
                "evictions": self.evictions,
                # where the resident planes live (device ids)
                "devices": sorted({
                    d.id for chip in self._chips
                    for p in chip.planes.values() for d in p.devices()
                }),
                "per_chip": [
                    {
                        "chip": chip.id, "planes": len(chip.planes),
                        "bytes": chip.bytes, "hits": chip.hits,
                        "misses": chip.misses, "lanes": chip.lanes,
                        "evictions": chip.evictions,
                    }
                    for chip in self._chips
                ],
            }
            if self.budget_error is not None:
                out["error"] = self.budget_error
            return out

    @property
    def nbytes(self) -> int:
        with self._lock:
            return sum(chip.bytes for chip in self._chips)

    def __len__(self) -> int:
        with self._lock:
            return len(self._where)
