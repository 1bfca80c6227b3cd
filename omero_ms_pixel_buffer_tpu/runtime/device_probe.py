"""In-process accelerator probe.

A chip belongs to one process at a time, so the process that serves is
the one that asks: ``probe()`` initialises the JAX backend in the
calling process, once, and reports what ``jax.devices()`` says plus a
measured host<->device round trip. The server calls it at start-up,
before the port opens (``TilePipeline.resolve_engine``); nothing here
starts a child, retries, or turns a backend error into a default — if
the backend cannot be asked, the caller fails.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

log = logging.getLogger("omero_ms_pixel_buffer_tpu.device_probe")

_cached: Optional[dict] = None
_lock = threading.Lock()


def reset() -> None:
    """Drop the cached result (tests only)."""
    global _cached
    with _lock:
        _cached = None


def probe() -> dict:
    """``{platform, kind, count, link_mbps}`` of this process's JAX
    backend — the same three device fields every chip record names
    (``jax.devices()[0].platform``, ``.device_kind``,
    ``len(jax.devices())``). Cached for the process lifetime: the
    backend a process initialised is the one it keeps."""
    global _cached
    with _lock:
        if _cached is None:
            import jax

            devices = jax.devices()
            _cached = {
                "platform": devices[0].platform,
                "kind": devices[0].device_kind,
                "count": len(devices),
                "link_mbps": _link_mbps(),
            }
            log.info("device probe: %s", _cached)
        return _cached


def _link_mbps() -> float:
    """One timed 4 MB host->device->host round trip, MB/s."""
    import jax
    import numpy as np

    sample = np.zeros((2 * 1024 * 1024,), np.uint16)
    jax.device_put(np.zeros(8, np.uint8)).block_until_ready()  # warm
    t0 = time.perf_counter()
    dev = jax.device_put(sample)
    dev.block_until_ready()
    np.asarray(dev)
    return round((2 * sample.nbytes) / (time.perf_counter() - t0) / 1e6, 1)
