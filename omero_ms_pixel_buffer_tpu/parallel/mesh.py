"""Device meshes for multi-chip tile serving.

The reference's only parallelism is a worker-thread pool
(PixelBufferMicroserviceVerticle.java:117-118,224-233; SURVEY.md §2.3).
The TPU equivalent is a ``jax.sharding.Mesh``:

- ``data`` axis — request parallelism: coalesced tile batches shard
  their batch dimension across chips (the worker-pool analog);
- the same axis doubles as the **space** axis for single huge reads
  (w/h=0 full-plane requests on whole-slide images): plane rows shard
  across chips and PNG filtering runs distributed with a one-row halo
  exchange over ICI (parallel/sharding.py).

Multi-host: jax.devices() spans hosts under jax.distributed; the mesh
builder just consumes it, so the same code scales DCN-wide.
"""

from __future__ import annotations

import logging
import threading
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger("omero_ms_pixel_buffer_tpu.mesh")


def make_mesh(
    axes: Tuple[str, ...] = ("data",),
    shape: Optional[Tuple[int, ...]] = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a mesh over the available devices. Default: 1-D ``data``
    mesh over every device."""
    devs = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (len(devs),) + (1,) * (len(axes) - 1)
    arr = np.array(devs).reshape(shape)
    return Mesh(arr, axes)


def batch_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Shard the leading (batch) dimension across the mesh axis."""
    return NamedSharding(mesh, P(axis))


def row_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Shard a (H, W)-like array's rows across the mesh axis — the
    'sequence/space parallel' layout for full-plane operations."""
    return NamedSharding(mesh, P(axis, None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def lane_counts(real: int, n_devices: int) -> List[int]:
    """How many REAL lanes each mesh device serves when ``real`` lanes
    pad to a multiple of ``n_devices`` and shard contiguously — the
    per-device accounting `last_dispatch` reports."""
    if n_devices <= 0:
        return []
    per = (max(real, 0) + n_devices - 1) // n_devices
    out = []
    for d in range(n_devices):
        lo, hi = d * per, (d + 1) * per
        out.append(max(0, min(real, hi) - lo))
    return out


class MeshHealthError(RuntimeError):
    """Every device in the serving mesh is breaker-open — the caller
    must fall back to the single-device or host path."""


class MeshManager:
    """Owns the serving mesh and its health — the multi-chip analog of
    the per-dependency circuit breakers on remote-I/O edges.

    A sick chip (wedged ICI link, ECC storm, runtime crash) surfaces
    as the WHOLE sharded dispatch raising, because shard_map runs one
    program over every device. Without isolation that converts each
    coalesced batch into a full failure for as long as the chip is
    down. This manager:

    - keeps a per-device circuit breaker (``device:<id>``, the shared
      BreakerBoard, so chip state shows in /healthz with everything
      else);
    - on dispatch failure, probes every chip individually (a tiny
      device_put + add, wrapped in the ``device.chip:<id>`` fault
      point so the chaos suite can fail exactly one chip
      deterministically), records outcomes on the breakers, rebuilds
      the mesh from the survivors, and retries the dispatch ONCE on
      the shrunken mesh;
    - heals automatically: an open breaker's half-open window readmits
      the chip at the next dispatch after ``open-duration-ms``.

    The ``device.mesh-dispatch`` fault point fires before each
    dispatch attempt so tests can fail the first attempt without
    touching jax internals."""

    def __init__(self, devices=None, axes: Tuple[str, ...] = ("data",)):
        self._devices = list(
            devices if devices is not None else jax.devices()
        )
        self._axes = axes
        self._lock = threading.Lock()
        self._breakers: dict = {}
        self._mesh_cache: Optional[Tuple[tuple, Mesh]] = None
        #: record of the most recent successful sharded dispatch —
        #: {"n_devices", "device_ids", "lanes_per_device", "executed"}
        self.last_dispatch: Optional[dict] = None
        # width-change listeners (r12): a probe-shrink or heal changes
        # the padded batch width every sharded group compiles against,
        # so the encode dispatcher subscribes here and pre-warms its
        # known group shapes on a background thread — the first
        # dispatch on a resized mesh must not pay the recompile inline
        self._width_listeners: list = []
        self._last_width: Optional[int] = None

    def add_width_listener(self, fn) -> None:
        """``fn(new_width)`` fires whenever the healthy-device count
        changes (shrink on a failed probe, growth on a heal). Called
        from probe paths — listeners must be quick and must not
        dispatch inline (spawn a thread for real work)."""
        with self._lock:
            self._width_listeners.append(fn)

    def _notify_width(self) -> None:
        # healthy_devices touches the breakers (which take _lock), so
        # compute the width OUTSIDE the lock; the read-modify-write of
        # _last_width is what must be atomic — concurrent probe paths
        # (MeshProber tick + a dispatch-failure probe_all) must not
        # interleave and swallow a real transition
        n = len(self.healthy_devices())
        fire = []
        with self._lock:
            prev = self._last_width
            if n:
                self._last_width = n
            if n and prev is not None and n != prev:
                fire = list(self._width_listeners)
        for fn in fire:
            try:
                fn(n)
            except Exception:
                log.exception("mesh width listener failed")

    def _breaker(self, dev):
        key = f"device:{getattr(dev, 'id', dev)}"
        with self._lock:
            br = self._breakers.get(key)
            if br is None:
                from ..resilience.breaker import for_dependency

                # one failed liveness probe is definitive (probes only
                # run after a dispatch already failed), so the breaker
                # opens immediately; the half-open window readmits the
                # chip after open-duration-ms as usual
                br = for_dependency(key, failure_threshold=1)
                self._breakers[key] = br
        return br

    def healthy_devices(self) -> list:
        out = []
        for dev in self._devices:
            try:
                self._breaker(dev).allow()
            except Exception:
                continue  # open: excluded until the half-open window
            out.append(dev)
        return out

    def mesh(self) -> Mesh:
        """A mesh over the currently-healthy devices (1-D over the
        first axis). Raises ``MeshHealthError`` when none remain."""
        devs = self.healthy_devices()
        if not devs:
            raise MeshHealthError(
                "all mesh devices are breaker-open"
            )
        key = tuple(getattr(d, "id", id(d)) for d in devs)
        with self._lock:
            if self._last_width is None:
                self._last_width = len(devs)  # change-detection baseline
            if self._mesh_cache is not None and self._mesh_cache[0] == key:
                return self._mesh_cache[1]
        mesh = make_mesh(self._axes, devices=devs)
        with self._lock:
            self._mesh_cache = (key, mesh)
        return mesh

    def probe_device(self, dev) -> bool:
        """One chip's liveness: a tiny transfer + add, blocked on.
        Records the outcome on the chip's breaker; a SUCCESSFUL probe
        also heals an open breaker outright — the probe genuinely
        exercised the chip, so there is nothing left for a half-open
        trial to learn."""
        from ..resilience.faultinject import INJECTOR

        br = self._breaker(dev)
        try:
            INJECTOR.fire(f"device.chip:{getattr(dev, 'id', dev)}")
            x = jax.device_put(np.arange(8, dtype=np.int32), dev)
            jax.block_until_ready(x + 1)
        except Exception:
            log.warning(
                "mesh device %s failed its probe; excluding it",
                getattr(dev, "id", dev),
            )
            br.record_failure()
            self._notify_width()
            return False
        br.record_success()
        if getattr(br, "heal", None) is not None:
            br.heal()  # readmit NOW, not after the open window
        self._notify_width()
        return True

    def probe_all(self) -> list:
        return [d for d in self._devices if self.probe_device(d)]

    def probe_open(self) -> int:
        """Background-health pass: probe ONLY the chips whose breaker
        is currently excluding them (open/half-open), so a recovered
        chip rejoins the mesh before the next dispatch has to fail.
        Healthy chips are never touched — the pass is free when the
        mesh is whole. Returns how many chips were readmitted."""
        healed = 0
        for dev in self._devices:
            if self._breaker(dev).state == "closed":
                continue
            if self.probe_device(dev):
                healed += 1
                log.info(
                    "mesh device %s recovered; rejoining the mesh",
                    getattr(dev, "id", dev),
                )
        return healed

    def dispatch(
        self,
        fn,
        real_lanes: Optional[int] = None,
        tag: Optional[str] = None,
    ):
        """Run ``fn(mesh)`` on the healthy mesh; on failure, probe the
        chips, shrink to the survivors, and retry once. Successful
        dispatches record per-device lane accounting in
        ``last_dispatch`` and a success on every participating
        breaker. ``tag`` names the program family ("tiles" / "render"
        / "dynamic" / "supertile") in ``last_dispatch`` so tests and
        the multichip dryrun can assert WHICH mesh chain actually
        executed, not just that one did."""
        from ..resilience.faultinject import INJECTOR

        mesh = self.mesh()
        try:
            INJECTOR.fire("device.mesh-dispatch")
            out = fn(mesh)
        except Exception:
            log.exception(
                "sharded dispatch failed on %d devices; probing chips",
                mesh.devices.size,
            )
            self.probe_all()
            mesh = self.mesh()  # survivors only (raises when empty)
            INJECTOR.fire("device.mesh-dispatch")
            out = fn(mesh)
        n = mesh.shape[self._axes[0]]
        for dev in mesh.devices.flat:
            self._breaker(dev).record_success()
        self.last_dispatch = {
            "executed": True,
            "n_devices": int(n),
            "device_ids": [
                getattr(d, "id", None) for d in mesh.devices.flat
            ],
            "lanes_per_device": (
                lane_counts(real_lanes, int(n))
                if real_lanes is not None else None
            ),
            "tag": tag,
        }
        return out

    def snapshot(self) -> dict:
        return {
            "devices": len(self._devices),
            "healthy": len(self.healthy_devices()),
            "last_dispatch": self.last_dispatch,
        }


class MeshProber:
    """Background mesh health (config ``mesh.probe-interval-ms``): a
    daemon thread that periodically runs ``MeshManager.probe_open``
    so a recovered chip rejoins the serving mesh without waiting for
    (a) the breaker's open window AND (b) the next dispatch — closing
    the KNOWN_GAPS reactive-only degradation item. The probe is
    blocking jax work, which is why this is a thread and not a loop
    task; ``manager_fn`` re-resolves per tick because the dispatcher
    (and its MeshManager) is built lazily on the first device batch."""

    def __init__(self, manager_fn, interval_s: float):
        self._manager_fn = manager_fn
        self.interval_s = interval_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="mesh-prober", daemon=True
            )
            self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                mgr = self._manager_fn()
                if mgr is not None:
                    mgr.probe_open()
            except Exception:
                log.exception("background mesh probe failed")
