"""Kernel-only device-compute microbenchmarks.

The device engine's marquee ops — the Pallas byteswap+filter kernel,
the on-device deflate, the HBM plane-cache crop chain (rebuilding the
reference's encode hot loop, TileRequestHandler.java:176-199) — are
hard to tell apart in end-to-end tiles/s, where host staging and
transfers share the clock. This module measures the COMPUTE side by
itself:

- inputs are device-resident before any timing (``jax.device_put``
  outside the timed region);
- every timed iteration ends in ``block_until_ready`` and outputs stay
  on device (no device→host fetch inside the loop);
- compiles are excluded (one warm call per shape first).

Emitted by ``bench.py --device-sub`` into BENCH's ``device`` section:
``filter_gbps`` (Pallas and XLA-fusion variants), ``deflate_gbps``,
``pack_gbps`` (the bit packer in isolation, plus the pinned
``pack_speedup_vs_gather`` comparison against the legacy gather
packer this round replaced), ``deflate_ratio_vs_host`` (device
RLE+fixed-Huffman stream bytes vs the host's dynamic-Huffman zlib
level 6 on identical payloads), ``batch_ms_steady`` for the full
resident-plane chain (crop → filter → deflate), and
``stage_breakdown`` — per-stage ``h2d_ms`` / ``compute_ms`` /
``d2h_ms`` of one host-staged fused encode batch, so the next round
can see WHICH stage moved.
"""

from __future__ import annotations

import time
import zlib

import numpy as np


def synth_tiles(
    b: int, h: int, w: int, dtype=np.uint16, seed: int = 5,
    noise: float = 120.0,
) -> np.ndarray:
    """Microscopy-like content (smooth field + sensor noise) — the same
    family as bench.py's fixture, so compressed sizes are realistic
    rather than white-noise worst case."""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    base = 2000 + 1500 * np.sin(xx / 97.0) + 1500 * np.cos(yy / 131.0)
    info = np.iinfo(dtype)
    tiles = (
        base[None] + rng.normal(0, noise, (b, h, w))
    ).clip(info.min, info.max)
    return tiles.astype(dtype)


def synth_rgb_tiles(
    b: int, h: int, w: int, seed: int = 5, noise: float = 6.0
) -> np.ndarray:
    """Rendered-RGB-like content (three smooth composited channels +
    light noise — what the /render surface emits after window/LUT
    compositing): the fixture for the dynamic-Huffman ratio pin.
    Rendered composites are far less run-heavy than raw greyscale
    planes, which is exactly where the fixed-Huffman device stream
    paid its 1.38x-of-host bytes."""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    chans = []
    for ph, (fx, fy) in enumerate(
        ((97.0, 131.0), (61.0, 89.0), (151.0, 47.0))
    ):
        chans.append(
            120 + 60 * np.sin(xx / fx + ph) + 50 * np.cos(yy / fy)
        )
    img = np.stack(chans, -1)[None] + rng.normal(0, noise, (b, h, w, 3))
    return img.clip(0, 255).astype(np.uint8)


def _time_steady(fn, iters: int) -> float:
    """Seconds per call at steady state (fn must block on its result).
    MEDIAN of per-call times, not the mean: a single stall inside the
    loop must not masquerade as kernel cost (observed: one spike
    inflated a 1.5 ms chain to a 2.7 s 'average')."""
    fn()  # warm: compile + first-touch allocations
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def _sig(value: float, digits: int = 3) -> float:
    """Round to significant figures, not fixed decimals: a GB/s
    metric over a KB-scale test payload can be legitimately tiny
    (loaded CI box, scheduler stall inside the median), and
    fixed-decimal rounding would flatten a real positive rate to
    exactly 0.0 — which reads as "kernel produced nothing" to every
    consumer asserting positivity."""
    if value == 0:
        return 0.0
    return float(f"{value:.{digits}g}")


def run_microbench(
    batch: int = 32,
    tile: int = 512,
    plane: int = 4096,
    iters_filter: int = 20,
    iters_deflate: int = 5,
    seed: int = 5,
) -> dict:
    """All kernel-only metrics as one dict; raises only if jax itself
    is unusable (callers run it inside the bounded device child)."""
    import jax

    from ..models.device_cache import DevicePlaneCache
    from ..ops.device_deflate import deflate_filtered_batch
    from ..ops.convert import to_big_endian_bytes
    from ..ops.pallas.filter import filter_tiles
    from ..ops.pallas.filter import supports as pallas_supports
    from ..ops.png import filter_batch

    out: dict = {
        "batch": batch,
        "tile": tile,
        "backend": jax.default_backend(),
    }
    tiles_np = synth_tiles(batch, tile, tile, seed=seed)
    itemsize = tiles_np.dtype.itemsize
    in_bytes = tiles_np.nbytes
    tiles = jax.device_put(tiles_np)
    jax.block_until_ready(tiles)

    # --- (a) fused byteswap + PNG filter ------------------------------
    use_pallas = pallas_supports((tile, tile), tiles_np.dtype)
    filtered = None
    if use_pallas:
        dt = _time_steady(
            lambda: jax.block_until_ready(filter_tiles(tiles, "up")),
            iters_filter,
        )
        out["filter_gbps"] = _sig(in_bytes / dt / 1e9)
        out["filter_ms_per_batch"] = round(dt * 1e3, 3)
        filtered = filter_tiles(tiles, "up")

    def xla_filter():
        rows = to_big_endian_bytes(tiles)
        return jax.block_until_ready(filter_batch(rows, itemsize, "up"))

    dt = _time_steady(xla_filter, iters_filter)
    out["filter_gbps_xla"] = _sig(in_bytes / dt / 1e9)
    if filtered is None:
        filtered = xla_filter()

    # --- (b) on-device deflate (RLE + fixed Huffman) ------------------
    row_bytes = 1 + tile * itemsize
    payload_bytes = batch * tile * row_bytes
    dt = _time_steady(
        lambda: jax.block_until_ready(
            deflate_filtered_batch(filtered, tile, row_bytes)
        ),
        iters_deflate,
    )
    out["deflate_gbps"] = _sig(payload_bytes / dt / 1e9)
    out["deflate_ms_per_batch"] = round(dt * 1e3, 2)

    # --- (b2) the bit packer in isolation: scan vs legacy gather ------
    # tokens precomputed outside the timing, so this is the PACKER's
    # throughput alone; the gather comparison pins the replacement's
    # speedup (BENCH_PACK_COMPARE=0 skips the slow legacy run).
    import os as _os

    from ..ops.device_deflate import (
        _lane_tokens,
        _pack_bits_gather,
        _pack_bits_scan,
        _packing_maxbits,
    )

    payloads = filtered[:, :tile, :row_bytes].reshape(batch, -1)
    tok_bits, tok_nbits = jax.jit(jax.vmap(_lane_tokens))(payloads)
    jax.block_until_ready((tok_bits, tok_nbits))
    maxbits = _packing_maxbits(payloads.shape[1])
    pack_scan = jax.jit(
        jax.vmap(lambda b, n: _pack_bits_scan(b, n, maxbits))
    )
    dt = _time_steady(
        lambda: jax.block_until_ready(pack_scan(tok_bits, tok_nbits)),
        iters_deflate,
    )
    out["pack_gbps"] = _sig(payload_bytes / dt / 1e9)
    if _os.environ.get("BENCH_PACK_COMPARE", "1") != "0":
        pack_gather = jax.jit(
            jax.vmap(lambda b, n: _pack_bits_gather(b, n, maxbits))
        )
        dt_g = _time_steady(
            lambda: jax.block_until_ready(
                pack_gather(tok_bits, tok_nbits)
            ),
            max(2, iters_deflate // 2),
        )
        out["pack_gbps_gather"] = _sig(payload_bytes / dt_g / 1e9)
        out["pack_speedup_vs_gather"] = _sig(dt_g / dt)

    # --- (b2b) the in-kernel emit formulations, pinned analytically ---
    # runtime constants, not a measurement: the scalar-prefetch
    # token-window kernel vs the r9 dense (SPAN x TB) compare-reduce
    from ..ops.pallas.bitpack import emit_ops_per_token

    dense_ops = emit_ops_per_token("dense")
    sp_ops = emit_ops_per_token("sp")
    out["emit_ops_per_token"] = {
        "dense": round(dense_ops, 1),
        "sp": round(sp_ops, 1),
        "reduction_x": _sig(dense_ops / sp_ops),
    }

    # --- (b3) stage breakdown of one host-staged fused batch ----------
    # what the double-buffered dispatcher overlaps: H2D of the native
    # tiles, the single fused byteswap+filter+deflate program, and the
    # compressed-stream pull (sliced to a serving-like pow2 cap).
    from ..ops.device_deflate import fused_filter_deflate_batch

    warm_s, warm_l = fused_filter_deflate_batch(
        jax.device_put(tiles_np), tile, row_bytes, itemsize
    )
    jax.block_until_ready((warm_s, warm_l))
    cap = min(
        warm_s.shape[1],
        1 << max(int(np.asarray(warm_l).max()) - 1, 63).bit_length(),
    )
    stages: dict = {"h2d": [], "compute": [], "d2h": []}
    for _ in range(iters_deflate):
        t0 = time.perf_counter()
        dev = jax.device_put(tiles_np)
        jax.block_until_ready(dev)
        t1 = time.perf_counter()
        s, length = fused_filter_deflate_batch(
            dev, tile, row_bytes, itemsize
        )
        jax.block_until_ready((s, length))
        t2 = time.perf_counter()
        jax.device_get((length, s[:, :cap]))
        t3 = time.perf_counter()
        stages["h2d"].append(t1 - t0)
        stages["compute"].append(t2 - t1)
        stages["d2h"].append(t3 - t2)
    out["stage_breakdown"] = {
        f"{k}_ms": round(sorted(v)[len(v) // 2] * 1e3, 3)
        for k, v in stages.items()
    }
    out["stage_breakdown"]["pack_gbps"] = out["pack_gbps"]

    # --- (c) full chain from an HBM-resident plane --------------------
    # crop (dynamic_slice gather) → filter → deflate, nothing crossing
    # the link inside the timed call: the steady-state cost of serving
    # one coalesced batch when the plane is already cached on device.
    # Coordinates are pre-staged device arrays, so no upload sits
    # inside the timed call.
    from ..models.device_cache import _crop_batch

    plane_np = synth_tiles(1, plane, plane, seed=seed + 1)[0]
    dplane = jax.device_put(plane_np)
    jax.block_until_ready(dplane)
    rng = np.random.default_rng(seed + 2)
    span = (plane - tile) // 64 + 1
    ys = jax.device_put(
        (rng.integers(0, span, batch) * 64).astype(np.int32)
    )
    xs = jax.device_put(
        (rng.integers(0, span, batch) * 64).astype(np.int32)
    )
    jax.block_until_ready((ys, xs))

    def chain():
        crops = _crop_batch(dplane, ys, xs, tile, tile)
        if use_pallas:
            f = filter_tiles(crops, "up")
        else:
            f = filter_batch(to_big_endian_bytes(crops), itemsize, "up")
        return jax.block_until_ready(
            deflate_filtered_batch(f, tile, row_bytes)
        )

    dt = _time_steady(chain, iters_deflate)
    out["batch_ms_steady"] = round(dt * 1e3, 2)
    out["chain_tiles_per_sec_compute"] = round(batch / dt, 1)

    # --- compressed-ratio vs the host encoder, identical payloads -----
    # Host reference: zlib level 6 (the serving default, dynamic
    # Huffman — what native/fast_deflate.cc and the Java Deflater
    # both produce trees for). Runs LAST: it downloads the filtered
    # batch, which must not sit between the kernel timings above.
    streams, lengths = deflate_filtered_batch(filtered, tile, row_bytes)
    dev_sizes = np.asarray(lengths, dtype=np.int64)
    filtered_np = np.asarray(filtered)
    host_sizes = np.array(
        [
            len(zlib.compress(
                filtered_np[i, :tile, :row_bytes].tobytes(), 6
            ))
            for i in range(batch)
        ],
        dtype=np.int64,
    )
    out["device_bytes_per_tile"] = round(float(dev_sizes.mean()), 1)
    out["host_bytes_per_tile"] = round(float(host_sizes.mean()), 1)
    out["deflate_ratio_vs_host"] = round(
        float(dev_sizes.mean() / host_sizes.mean()), 3
    )
    out["deflate_compression_x"] = round(
        float(tile * row_bytes / dev_sizes.mean()), 2
    )

    # --- dynamic-Huffman ratio on the rendered-RGB fixture ------------
    # The ratio pin the r12 two-pass path exists for: device bytes vs
    # host zlib level 6 on identical filtered payloads of LOW-RUN
    # rendered-RGB content (the fixed-Huffman stream measured 1.38x
    # here; the acceptance bound is <= 1.10x). Also measured on the
    # greyscale fixture above as deflate_dynamic_* for trend lines.
    from ..ops.device_deflate import fused_filter_deflate_dynamic

    rgb_np = synth_rgb_tiles(batch, tile, tile, seed=seed)
    rgb_rows = 1 + tile * 3
    rgb_dev = jax.device_put(rgb_np)
    jax.block_until_ready(rgb_dev)
    streams_d, lengths_d = fused_filter_deflate_dynamic(
        rgb_dev, tile, rgb_rows, 3
    )
    dyn_sizes = np.asarray(lengths_d, dtype=np.int64)
    rgb_filtered = np.asarray(
        filter_batch(
            to_big_endian_bytes(rgb_dev).reshape(batch, tile, tile * 3),
            3, "up",
        )
    )
    rgb_host = np.array(
        [
            len(zlib.compress(rgb_filtered[i].tobytes(), 6))
            for i in range(batch)
        ],
        dtype=np.int64,
    )
    out["deflate_ratio_vs_host_dynamic"] = round(
        float(dyn_sizes.mean() / rgb_host.mean()), 3
    )
    # fixed-Huffman on the SAME rgb payloads: what the dynamic path
    # improves on (this is where the 1.38x lived)
    from ..ops.device_deflate import fused_filter_deflate_batch as _ffd

    _, lengths_r = _ffd(rgb_dev, tile, rgb_rows, 3, mode="rle")
    out["deflate_ratio_vs_host_rle_rgb"] = round(
        float(np.asarray(lengths_r, dtype=np.int64).mean() / rgb_host.mean()),
        3,
    )
    dt = _time_steady(
        lambda: jax.block_until_ready(
            fused_filter_deflate_dynamic(rgb_dev, tile, rgb_rows, 3)[0]
        ),
        max(2, iters_deflate // 2),
    )
    out["deflate_dynamic_gbps"] = _sig(batch * tile * rgb_rows / dt / 1e9)
    return out
