"""North-star benchmark (BASELINE.json): tiles/sec for 512x512 uint16
PNG tiles served from a large pyramidal OME-TIFF under concurrent load.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

- value: tiles/sec of the batched TPU pipeline (coalesced batches,
  device byteswap+filter, threaded host deflate) over 1024 requests.
- vs_baseline: speedup over the reference-architecture path measured
  in-process — one request at a time, single-threaded, host-only
  (read -> numpy filter -> zlib), i.e. the shape of the reference's
  per-request Java worker (TileRequestHandler.java:80-139). The Java
  service itself is not runnable in this environment (BASELINE.md:
  baseline must be measured); this stand-in preserves its execution
  structure on identical inputs.
- extra keys: http_tiles_per_sec + p50_ms/p99_ms measured through the
  FULL stack (aiohttp client over a real socket -> session middleware
  -> event bus -> batcher -> pipeline).

This is NOT the chip entry point and measures nothing on the chip:
`python chip_smoke.py` is the proof that the served path runs there,
and `benchmarks/` (BENCHMARK.json) measures it.

All progress chatter goes to stderr; stdout carries only the JSON line.
"""

import asyncio
import json
import os
import subprocess
import sys
import tempfile
import time
import zlib

import numpy as np


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def jax_backend_info() -> dict:
    """This process's JAX backend (platform, kind, count, link_mbps).
    Initialises it here: from then on this process holds the chip."""
    from omero_ms_pixel_buffer_tpu.runtime.device_probe import probe

    return dict(probe())


def build_fixture(root: str, size: int = 8192):
    from omero_ms_pixel_buffer_tpu.io.ometiff import write_ome_tiff

    path = os.path.join(root, f"bench_{size}.ome.tiff")
    if os.path.exists(path):
        return path
    log(f"writing {size}x{size} uint16 fixture...")
    rng = np.random.default_rng(42)
    # smooth-ish synthetic microscopy-like data (compresses realistically,
    # unlike white noise)
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32)
    base = (
        2000
        + 1500 * np.sin(xx / 97.0)
        + 1500 * np.cos(yy / 131.0)
    )
    data = (base + rng.normal(0, 120, (size, size))).clip(0, 65535)
    data = data.astype(np.uint16)[None, None, None]
    write_ome_tiff(path, data, tile_size=(512, 512), compression="zlib")
    return path


def make_ctxs(n, size, tile=512, fmt="png", seed=7):
    from omero_ms_pixel_buffer_tpu.tile_ctx import RegionDef, TileCtx

    rng = np.random.default_rng(seed)
    ctxs = []
    for _ in range(n):
        x = int(rng.integers(0, (size - tile) // 64)) * 64
        y = int(rng.integers(0, (size - tile) // 64)) * 64
        ctxs.append(
            TileCtx(
                image_id=1, z=0, c=0, t=0,
                region=RegionDef(x, y, tile, tile),
                format=fmt, omero_session_key="bench",
            )
        )
    return ctxs


def run_batched(pipe, ctxs, batch):
    """Drive handle_batch over all ctxs; returns tiles/s."""
    t0 = time.perf_counter()
    done = 0
    for i in range(0, len(ctxs), batch):
        chunk = ctxs[i : i + batch]
        results = pipe.handle_batch(chunk)
        assert all(r is not None for r in results), "bench tile failed"
        done += len(chunk)
    return done / (time.perf_counter() - t0)


def bench_http(
    path: str, n_requests: int, concurrency: int, engine: str = "auto"
) -> dict:
    """Full-stack latency: a lean hand-rolled HTTP client over a real
    localhost socket -> tracing middleware -> session middleware ->
    bus.request -> BatchingTileWorker -> TilePipeline. The reference's
    hot path
    (TileRequestHandler.java:80-139) ran per-request on a worker
    thread behind Vert.x; this measures our complete analog.

    ``engine`` must be the probe-gated value computed in main(), NOT
    re-read from the environment: BENCH_ENGINE=device on a wedged TPU
    would otherwise hang this section at in-process PJRT init before
    the bounded device child ever runs.

    The client is hand-rolled over raw asyncio streams (keep-alive,
    minimal HTTP/1.1 parsing): the client shares the server's core(s)
    in this in-process measurement, so a heavyweight client library
    would bill its own parsing against the server's throughput."""
    from aiohttp import web

    from omero_ms_pixel_buffer_tpu.auth.stores import MemorySessionStore
    from omero_ms_pixel_buffer_tpu.http.server import PixelBufferApp
    from omero_ms_pixel_buffer_tpu.io.pixels_service import (
        ImageRegistry,
        PixelsService,
    )
    from omero_ms_pixel_buffer_tpu.utils.config import Config

    registry = ImageRegistry()
    registry.add(1, path)
    config = Config.from_dict(
        {
            "session-store": {"type": "memory"},
            "backend": {"engine": engine},
        }
    )
    service = PixelsService(registry)
    app_obj = PixelBufferApp(
        config,
        pixels_service=service,
        session_store=MemorySessionStore({"bench-cookie": "bench-key"}),
    )
    size = int(os.environ.get("BENCH_IMAGE_SIZE", "8192"))
    rng = np.random.default_rng(11)
    urls = []
    for _ in range(n_requests):
        x = int(rng.integers(0, (size - 512) // 64)) * 64
        y = int(rng.integers(0, (size - 512) // 64)) * 64
        urls.append(
            f"/tile/1/0/0/0?x={x}&y={y}&w=512&h=512&format=png"
        )
    # warmup covers every storage chunk once (chunk-aligned sweep):
    # the pipeline-direct headline amortizes first-touch decode over
    # 2x the requests, so a random warmup would bill the HTTP section
    # asymmetrically for cache misses instead of serving
    warm_urls = [
        f"/tile/1/0/0/0?x={x}&y={y}&w=512&h=512&format=png"
        for y in range(0, size - 511, 512)
        for x in range(0, size - 511, 512)
    ]

    async def run() -> dict:
        runner = web.AppRunner(app_obj.make_app(), access_log=None)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = runner.addresses[0][1]
        latencies = []

        async def drive(request_urls):
            """``concurrency`` keep-alive connections, each a worker
            draining the shared URL queue."""
            queue: asyncio.Queue = asyncio.Queue()
            for u in request_urls:
                queue.put_nowait(u)
            for _ in range(concurrency):
                queue.put_nowait(None)

            async def worker():
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", port
                )
                try:
                    while True:
                        url = await queue.get()
                        if url is None:
                            return
                        t0 = time.perf_counter()
                        writer.write(
                            f"GET {url} HTTP/1.1\r\n"
                            "Host: bench\r\n"
                            "Cookie: sessionid=bench-cookie\r\n"
                            "\r\n".encode()
                        )
                        await writer.drain()
                        status_line = await reader.readline()
                        status = int(status_line.split()[1])
                        clen = 0
                        while True:
                            line = await reader.readline()
                            if line in (b"\r\n", b""):
                                break
                            if line.lower().startswith(b"content-length:"):
                                clen = int(line.split(b":", 1)[1])
                        body = await reader.readexactly(clen)
                        assert status == 200, (status, body[:200])
                        latencies.append(time.perf_counter() - t0)
                finally:
                    writer.close()

            await asyncio.gather(*(worker() for _ in range(concurrency)))

        try:
            # warmup: engine resolution, jit, native build, and one
            # full chunk-coverage sweep so the timed phase measures
            # steady-state serving
            await drive(warm_urls)
            latencies.clear()
            t0 = time.perf_counter()
            await drive(urls)
            elapsed = time.perf_counter() - t0
        finally:
            await runner.cleanup()
            service.close()  # idempotent (app cleanup also closes it)
        lat_ms = np.array(latencies) * 1000.0
        return {
            "http_tiles_per_sec": round(len(urls) / elapsed, 2),
            "p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
            "p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
            "concurrency": concurrency,
            "engine": app_obj.pipeline.engine,
        }

    return asyncio.run(run())


def bench_cache(
    path: str, n_tiles: int = 192, concurrency: int = 1,
    engine: str = "host",
) -> dict:
    """Cache warm-pass mode: the same full HTTP stack as bench_http
    but with the tiered tile-result cache enabled. Pass 1 (cold)
    renders and memoizes a unique tile set; pass 2 (warm) replays the
    identical URLs. Records the hit ratio and the p50/p99 delta — the
    repeated-tile serving story — and verifies every warm body is
    byte-identical to its cold twin (the correctness bar: a cache that
    alters bytes is worse than no cache).

    Default concurrency is 1: this section is a LATENCY probe (what
    one viewer feels per tile, cold vs hit), so it must not run at
    saturation — at high concurrency both passes measure queueing on
    the shared loop, not the path under test. bench_http carries the
    throughput story; BENCH_CACHE_CONCURRENCY overrides."""
    import hashlib

    from aiohttp import web

    from omero_ms_pixel_buffer_tpu.auth.stores import MemorySessionStore
    from omero_ms_pixel_buffer_tpu.http.server import PixelBufferApp
    from omero_ms_pixel_buffer_tpu.io.pixels_service import (
        ImageRegistry,
        PixelsService,
    )
    from omero_ms_pixel_buffer_tpu.utils.config import Config

    registry = ImageRegistry()
    registry.add(1, path)
    config = Config.from_dict(
        {
            "session-store": {"type": "memory"},
            "backend": {"engine": engine},
            "cache": {"memory-mb": 512,
                      # the bench replays exact URLs; speculative
                      # neighbors would blur the hit-ratio reading
                      "prefetch": {"enabled": False}},
        }
    )
    service = PixelsService(registry)
    app_obj = PixelBufferApp(
        config,
        pixels_service=service,
        session_store=MemorySessionStore({"bench-cookie": "bench-key"}),
    )
    size = int(os.environ.get("BENCH_IMAGE_SIZE", "8192"))
    rng = np.random.default_rng(29)
    urls = []
    seen = set()
    while len(urls) < n_tiles:
        x = int(rng.integers(0, (size - 512) // 64)) * 64
        y = int(rng.integers(0, (size - 512) // 64)) * 64
        if (x, y) not in seen:  # unique tiles: pass 1 is all misses
            seen.add((x, y))
            urls.append(
                f"/tile/1/0/0/0?x={x}&y={y}&w=512&h=512&format=png"
            )

    async def run() -> dict:
        runner = web.AppRunner(app_obj.make_app(), access_log=None)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = runner.addresses[0][1]

        async def drive(request_urls):
            latencies, digests = [], {}
            queue: asyncio.Queue = asyncio.Queue()
            for u in request_urls:
                queue.put_nowait(u)
            for _ in range(concurrency):
                queue.put_nowait(None)

            async def worker():
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", port
                )
                try:
                    while True:
                        url = await queue.get()
                        if url is None:
                            return
                        t0 = time.perf_counter()
                        writer.write(
                            f"GET {url} HTTP/1.1\r\n"
                            "Host: bench\r\n"
                            "Cookie: sessionid=bench-cookie\r\n"
                            "\r\n".encode()
                        )
                        await writer.drain()
                        status_line = await reader.readline()
                        status = int(status_line.split()[1])
                        clen = 0
                        while True:
                            line = await reader.readline()
                            if line in (b"\r\n", b""):
                                break
                            if line.lower().startswith(
                                b"content-length:"
                            ):
                                clen = int(line.split(b":", 1)[1])
                        body = await reader.readexactly(clen)
                        assert status == 200, (status, body[:200])
                        latencies.append(time.perf_counter() - t0)
                        digests[url] = hashlib.sha1(body).hexdigest()
                finally:
                    writer.close()

            await asyncio.gather(
                *(worker() for _ in range(concurrency))
            )
            return latencies, digests

        try:
            # engine/jit/native warmup outside the timed passes
            await drive(urls[:concurrency])
            cold_lat, cold_digests = await drive(urls)
            # hit ratio reads the WARM pass only
            app_obj.result_cache.memory.hits = 0
            app_obj.result_cache.memory.misses = 0
            warm_lat, warm_digests = await drive(urls)
        finally:
            await runner.cleanup()
            service.close()
        mem = app_obj.result_cache.memory.snapshot()
        cold = np.array(cold_lat) * 1000.0
        warm = np.array(warm_lat) * 1000.0
        identical = cold_digests == warm_digests
        p50_cold = float(np.percentile(cold, 50))
        p50_warm = float(np.percentile(warm, 50))
        return {
            "tiles": len(urls),
            "hit_ratio": round(
                mem["hits"] / max(1, mem["hits"] + mem["misses"]), 4
            ),
            "p50_cold_ms": round(p50_cold, 3),
            "p99_cold_ms": round(float(np.percentile(cold, 99)), 3),
            "p50_warm_ms": round(p50_warm, 3),
            "p99_warm_ms": round(float(np.percentile(warm, 99)), 3),
            "p50_speedup": round(p50_cold / max(p50_warm, 1e-6), 2),
            "identical_bytes": identical,
        }

    return asyncio.run(run())


def bench_cache_plane(path: str, cache_dir: str) -> dict:
    """Cache plane (r11) section — three pins:

    - ``warm_restart``: fill a disk-spilling result cache, close it,
      reopen, and measure the hit rate of the first 100 requests with
      the manifest journal vs the legacy sweep (which is 0 by
      construction);
    - ``l2``: round-trip p50/p99 against the in-memory RESP stub
      (the protocol + framing cost floor — a real Redis adds wire
      latency on top);
    - ``two_replica``: TWO in-process app replicas with a shared ring
      + L2 serve a shared unique-tile workload; pins the render-once
      acceptance number (total renders across both processes ==
      unique tiles) and that both replicas answered with one ETag per
      tile.
    """
    import hashlib  # noqa: F401  (parity with bench_cache imports)
    import socket

    from aiohttp import ClientSession, web

    from omero_ms_pixel_buffer_tpu.auth.stores import MemorySessionStore
    from omero_ms_pixel_buffer_tpu.cache.plane.l2 import RedisL2Tier
    from omero_ms_pixel_buffer_tpu.cache.plane.resp_stub import (
        InMemoryRespServer,
    )
    from omero_ms_pixel_buffer_tpu.cache.result_cache import (
        CachedTile,
        TileResultCache,
    )
    from omero_ms_pixel_buffer_tpu.http.server import PixelBufferApp
    from omero_ms_pixel_buffer_tpu.io.pixels_service import (
        ImageRegistry,
        PixelsService,
    )
    from omero_ms_pixel_buffer_tpu.utils.config import Config

    out: dict = {}

    # -- warm restart (manifest on vs off) -----------------------------
    def restart_hit_rate(manifest: bool, tag: str) -> float:
        spill = os.path.join(cache_dir, f"plane_spill_{tag}")
        body = os.urandom(4096)
        cache = TileResultCache(
            memory_bytes=64 << 10, disk_dir=spill,
            disk_bytes=64 << 20, manifest=manifest,
        )

        async def fill():
            for i in range(150):
                await cache.put(
                    f"img=1|z=0|c=0|t=0|x={i}|q=bench",
                    CachedTile(body, filename="b.png"),
                )

        asyncio.run(fill())
        cache._io.submit(lambda: None).result()  # drain spills
        cache.close()
        reborn = TileResultCache(
            memory_bytes=64 << 10, disk_dir=spill,
            disk_bytes=64 << 20, manifest=manifest,
        )

        async def probe() -> int:
            hits = 0
            for i in range(100):
                key = f"img=1|z=0|c=0|t=0|x={i}|q=bench"
                if await reborn.get(key) is not None:
                    hits += 1
            return hits

        hits = asyncio.run(probe())
        reborn.close()
        return hits / 100.0

    out["warm_restart"] = {
        "first_100_hit_rate_manifest": restart_hit_rate(True, "on"),
        "first_100_hit_rate_sweep": restart_hit_rate(False, "off"),
    }

    # -- L2 round trip -------------------------------------------------
    async def l2_round_trip() -> dict:
        server = InMemoryRespServer()
        await server.start()
        tier = RedisL2Tier(server.uri)
        body = os.urandom(32 << 10)  # a typical encoded-tile size
        entry = CachedTile(body, filename="b.png")
        lat = []
        try:
            for i in range(50):
                await tier.put(f"img=9|x={i}|q=bench", entry)
            for _ in range(4):  # warm
                await tier.get("img=9|x=0|q=bench")
            for i in range(200):
                t0 = time.perf_counter()
                got = await tier.get(f"img=9|x={i % 50}|q=bench")
                lat.append(time.perf_counter() - t0)
                assert got is not None and got.body == body
        finally:
            await tier.close()
            await server.close()
        ms = np.array(lat) * 1000.0
        return {
            "round_trips": len(lat),
            "p50_ms": round(float(np.percentile(ms, 50)), 3),
            "p99_ms": round(float(np.percentile(ms, 99)), 3),
        }

    out["l2"] = asyncio.run(l2_round_trip())

    # -- two-replica render-once ---------------------------------------
    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    async def two_replica() -> dict:
        resp = InMemoryRespServer()
        await resp.start()
        ports = [free_port(), free_port()]
        members = [f"http://127.0.0.1:{p}" for p in ports]
        replicas, runners, renders = [], [], []
        for i, port in enumerate(ports):
            registry = ImageRegistry()
            registry.add(1, path)
            config = Config.from_dict({
                "session-store": {"type": "memory"},
                "backend": {"engine": "host",
                            "batching": {"coalesce-window-ms": 1.0}},
                "cache": {"prefetch": {"enabled": False}},
                "cluster": {
                    "members": members, "self": members[i],
                    "peer-timeout-ms": 5000,
                    "l2": {"uri": resp.uri},
                },
            })
            app_obj = PixelBufferApp(
                config,
                pixels_service=PixelsService(registry),
                session_store=MemorySessionStore(
                    {"bench-cookie": "bench-key"}
                ),
            )
            counter: list = []

            def wrap(app=app_obj, counter=counter):
                inner_h, inner_b = (
                    app.pipeline.handle, app.pipeline.handle_batch
                )
                app.pipeline.handle = lambda c: (
                    counter.append(1), inner_h(c)
                )[1]
                app.pipeline.handle_batch = lambda cs: (
                    counter.extend([1] * len(cs)), inner_b(cs)
                )[1]

            wrap()
            renders.append(counter)
            runner = web.AppRunner(app_obj.make_app(), access_log=None)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", port)
            await site.start()
            replicas.append(app_obj)
            runners.append(runner)
        size = int(os.environ.get("BENCH_IMAGE_SIZE", "8192"))
        n_tiles = 24
        urls = [
            f"/tile/1/0/0/0?x={(i % 8) * 512}&y={(i // 8) * 512}"
            "&w=512&h=512&format=png"
            for i in range(n_tiles)
        ]
        assert (max(8, n_tiles // 8) * 512) <= size
        etags: dict = {}
        identical = True
        headers = {"Cookie": "sessionid=bench-cookie"}
        try:
            async with ClientSession() as http:
                for i, url in enumerate(urls):
                    first = members[i % 2]
                    second = members[(i + 1) % 2]
                    async with http.get(
                        first + url, headers=headers
                    ) as r1:
                        assert r1.status == 200, await r1.text()
                        etag1 = r1.headers["ETag"]
                    async with http.get(
                        second + url, headers=headers
                    ) as r2:
                        assert r2.status == 200
                        etag2 = r2.headers["ETag"]
                    identical = identical and (etag1 == etag2)
                    etags[url] = etag1
        finally:
            for runner in runners:
                await runner.cleanup()
            await resp.close()
        total = sum(len(c) for c in renders)
        return {
            "unique_tiles": n_tiles,
            "total_renders": total,
            "render_once": total == n_tiles,
            "identical_etags": identical,
        }

    out["two_replica"] = asyncio.run(two_replica())
    return out


def bench_cluster(cache_dir: str) -> dict:
    """Cluster coordination plane (r17) section — three measurements,
    two hard pins:

    - ``failover``: a three-replica cluster (leases + replication
      factor 2) serves a hot set twice, the owner of part of it is
      KILLED and the shared L2 flushed (so only pushed replicas can
      answer); the ring rebuild maps each orphaned key to exactly the
      successor holding its replica. Pin ``cluster_ok_failover_hits``:
      >= 0.8 post-crash hit rate on the replicated hot set (the
      replication-factor-1 control records the ~0 baseline).
    - ``join``: a cold replica joins a warm cluster; seconds until its
      local cache holds >= 90% of the hot set via the one-round
      warm-up transfer (pinned <= 5 s — one transfer round, not an
      organic re-render).
    - ``hedge``: cold misses against a wedged owner, hedged vs
      unhedged p99. Pin ``cluster_ok_hedge_p99``: hedging must cut
      the wedged-owner p99 to < 70% of the unhedged tail.
    """
    import socket

    from aiohttp import ClientSession, web

    from omero_ms_pixel_buffer_tpu.auth.stores import MemorySessionStore
    from omero_ms_pixel_buffer_tpu.cache.plane.resp_stub import (
        InMemoryRespServer,
    )
    from omero_ms_pixel_buffer_tpu.http.server import PixelBufferApp
    from omero_ms_pixel_buffer_tpu.io.ometiff import write_ome_tiff
    from omero_ms_pixel_buffer_tpu.io.pixels_service import (
        ImageRegistry,
        PixelsService,
    )
    from omero_ms_pixel_buffer_tpu.tile_ctx import TileCtx
    from omero_ms_pixel_buffer_tpu.utils.config import Config

    out: dict = {}
    headers = {"Cookie": "sessionid=bench-cookie"}
    img_path = os.path.join(cache_dir, "cluster_fixture.ome.tiff")
    if not os.path.exists(img_path):
        rng_local = np.random.default_rng(23)
        img = rng_local.integers(
            0, 60000, (1, 1, 1, 512, 512), dtype=np.uint16
        )
        write_ome_tiff(
            img_path, img, tile_size=(64, 64), pyramid_levels=2
        )

    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def tile_paths(n):
        return [
            f"/tile/1/0/0/0?x={64 * (i % 8)}&y={64 * (i // 8)}"
            "&w=64&h=64&format=png"
            for i in range(n)
        ]

    async def boot(members, self_url, port, resp_uri, extra):
        registry = ImageRegistry()
        registry.add(1, img_path)
        cluster_block = {
            "members": members, "self": self_url,
            "peer-timeout-ms": 3000, **(extra or {}),
        }
        if resp_uri:
            cluster_block["l2"] = {"uri": resp_uri}
        config = Config.from_dict({
            "session-store": {"type": "memory"},
            "backend": {"batching": {"coalesce-window-ms": 1.0}},
            "cache": {"prefetch": {"enabled": False}},
            "cluster": cluster_block,
        })
        app_obj = PixelBufferApp(
            config,
            pixels_service=PixelsService(registry),
            session_store=MemorySessionStore(
                {"bench-cookie": "bench-key"}
            ),
        )
        runner = web.AppRunner(app_obj.make_app(), access_log=None)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", port)
        await site.start()
        return app_obj, runner

    def key_for(app_obj, path):
        query = dict(
            kv.split("=") for kv in path.split("?", 1)[1].split("&")
        )
        _, _, image_id, z, c, t = path.split("?", 1)[0].split("/")
        ctx = TileCtx.from_params(
            {"imageId": image_id, "z": z, "c": c, "t": t, **query},
            None,
        )
        return ctx.cache_key(app_obj.pipeline.encode_signature())

    n_hot = 24

    async def failover(replication_factor: int) -> dict:
        resp = InMemoryRespServer()
        await resp.start()
        ports = [free_port() for _ in range(3)]
        members = [f"http://127.0.0.1:{p}" for p in ports]
        nodes = []
        for i, port in enumerate(ports):
            nodes.append(await boot(
                members, members[i], port, resp.uri,
                {"lease-ttl-s": 0.5,
                 "replication-factor": replication_factor},
            ))
        try:
            await asyncio.sleep(0.4)  # leases discovered
            paths = tile_paths(n_hot)
            async with ClientSession() as http:
                for path in paths:
                    key = key_for(nodes[0][0], path)
                    owner_url = nodes[0][0].cache_plane.ring.owner(key)
                    owner = next(
                        a for a, _r in nodes
                        if a.cache_plane.self_url == owner_url
                    )
                    base = owner.cache_plane.self_url
                    for _ in range(2):  # second touch crosses hot bar
                        async with http.get(
                            base + path, headers=headers
                        ) as r:
                            assert r.status == 200, await r.text()
                await asyncio.sleep(0.6)  # pushes drain
                victim_app, victim_runner = nodes[0]
                victim_url = victim_app.cache_plane.self_url
                survivors = nodes[1:]
                victim_paths = [
                    p for p in paths
                    if survivors[0][0].cache_plane.ring.owner(
                        key_for(survivors[0][0], p)
                    ) == victim_url
                ]
                await victim_runner.cleanup()
                for key in [
                    k for k in resp.data
                    if k.startswith(b"ompb:tile:")
                ]:
                    del resp.data[key]  # L2 cold: replicas or nothing
                deadline = time.monotonic() + 5.0
                while time.monotonic() < deadline:
                    if all(
                        len(a.cache_plane.membership.members) == 2
                        for a, _r in survivors
                    ):
                        break
                    await asyncio.sleep(0.1)
                hits = 0
                for path in victim_paths:
                    key = key_for(survivors[0][0], path)
                    new_owner_url = (
                        survivors[0][0].cache_plane.ring.owner(key)
                    )
                    new_owner = next(
                        a for a, _r in survivors
                        if a.cache_plane.self_url == new_owner_url
                    )
                    async with http.get(
                        new_owner.cache_plane.self_url + path,
                        headers=headers,
                    ) as r:
                        assert r.status == 200
                        if r.headers.get("X-Cache") == "hit":
                            hits += 1
            return {
                "orphaned_keys": len(victim_paths),
                "post_crash_hits": hits,
                "hit_rate": round(
                    hits / max(1, len(victim_paths)), 3
                ),
            }
        finally:
            for _a, runner in nodes[1:]:
                await runner.cleanup()
            await resp.close()

    out["failover"] = {
        "replicated": asyncio.run(failover(2)),
        "unreplicated": asyncio.run(failover(1)),
    }

    async def join_warm() -> dict:
        resp = InMemoryRespServer()
        await resp.start()
        ports = [free_port() for _ in range(2)]
        members = [f"http://127.0.0.1:{p}" for p in ports]
        nodes = []
        for i, port in enumerate(ports):
            nodes.append(await boot(
                members, members[i], port, resp.uri,
                {"lease-ttl-s": 0.5, "replication-factor": 2},
            ))
        joiner = None
        try:
            await asyncio.sleep(0.4)
            paths = tile_paths(n_hot)
            async with ClientSession() as http:
                for i, path in enumerate(paths):
                    base = nodes[i % 2][0].cache_plane.self_url
                    async with http.get(
                        base + path, headers=headers
                    ) as r:
                        assert r.status == 200
            port = free_port()
            t0 = time.monotonic()
            joiner = await boot(
                [f"http://127.0.0.1:{port}"],
                f"http://127.0.0.1:{port}", port, resp.uri,
                {"lease-ttl-s": 0.5, "replication-factor": 2},
            )
            target = int(0.9 * n_hot)
            warm_s = None
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                if len(joiner[0].result_cache.memory) >= target:
                    warm_s = time.monotonic() - t0
                    break
                await asyncio.sleep(0.05)
            return {
                "hot_set": n_hot,
                "target_entries": target,
                "warm_entries": len(joiner[0].result_cache.memory),
                "join_to_90pct_warm_s": (
                    round(warm_s, 3) if warm_s is not None else None
                ),
            }
        finally:
            if joiner is not None:
                await joiner[1].cleanup()
            for _a, runner in nodes:
                await runner.cleanup()
            await resp.close()

    out["join"] = asyncio.run(join_warm())

    async def hedge_run(enabled: bool) -> dict:
        ports = [free_port() for _ in range(2)]
        members = [f"http://127.0.0.1:{p}" for p in ports]
        extra = {"hedge": {
            "enabled": enabled, "min-ms": 10, "max-ms": 40,
            "fallback-ms": 20,
        }}
        nodes = [
            await boot(members, members[i], ports[i], None, extra)
            for i in range(2)
        ]
        try:
            a_app = nodes[0][0]
            paths = [
                p for p in tile_paths(64)
                if a_app.cache_plane.ring.owner(key_for(a_app, p))
                == members[0]
            ][:24]
            # wedge the owner: every render pays 150 ms
            wedged = nodes[0][0]
            inner_h = wedged.pipeline.handle
            inner_b = wedged.pipeline.handle_batch
            wedged.pipeline.handle = lambda c: (
                time.sleep(0.15), inner_h(c)
            )[1]
            wedged.pipeline.handle_batch = lambda cs: (
                time.sleep(0.15), inner_b(cs)
            )[1]
            lat = []
            async with ClientSession() as http:
                for path in paths:
                    t0 = time.perf_counter()
                    async with http.get(
                        members[1] + path, headers=headers
                    ) as r:
                        assert r.status == 200
                    lat.append(time.perf_counter() - t0)
            ms = np.array(lat) * 1000.0
            return {
                "requests": len(lat),
                "p50_ms": round(float(np.percentile(ms, 50)), 1),
                "p99_ms": round(float(np.percentile(ms, 99)), 1),
            }
        finally:
            for _a, runner in nodes:
                await runner.cleanup()

    # unhedged FIRST: its peer-stage observations are what the hedge
    # policy's p99 then clamps against, mirroring production order
    unhedged = asyncio.run(hedge_run(False))
    hedged = asyncio.run(hedge_run(True))
    out["hedge"] = {"unhedged": unhedged, "hedged": hedged}

    rep_rate = out["failover"]["replicated"]["hit_rate"]
    out["cluster_ok_failover_hits"] = rep_rate >= 0.8
    join_s = out["join"]["join_to_90pct_warm_s"]
    out["cluster_ok_join_warm"] = (
        join_s is not None and join_s <= 5.0
    )
    out["cluster_ok_hedge_p99"] = (
        hedged["p99_ms"] < unhedged["p99_ms"] * 0.7
    )
    return out


def bench_lifecycle(cache_dir: str) -> dict:
    """Fleet lifecycle plane (r18) section — two drives, two pins:

    - ``rolling_restart``: a three-replica cluster (leases +
      replication + graceful drain) is restarted one replica at a
      time under live traffic: each replica drains (lease marker,
      full-RAM handoff, quiesce, lease release), is killed, the
      shared L2's tile keys are FLUSHED (so the handed-off RAM
      copies are the only warm source), and a replacement boots on
      the same identity and warms via the join transfer. Pin
      ``cluster_ok_drain_zero_errors``: ZERO serving 5xx across the
      whole drive AND warm-hit rate >= 0.95 — a planned leave rides
      the warm path, not the crash path (the crash-path bench above
      pins only >= 0.8).
    - ``repair``: a hot entry whose replica push is deliberately
      dropped is healed by the anti-entropy digest exchange. Pin
      ``cluster_ok_repair_convergence``: repaired within ONE
      rotation over the peers (<= 2 rounds in a 3-replica fleet).
    """
    import socket

    from aiohttp import ClientSession, web

    from omero_ms_pixel_buffer_tpu.auth.stores import MemorySessionStore
    from omero_ms_pixel_buffer_tpu.cache.plane.resp_stub import (
        InMemoryRespServer,
    )
    from omero_ms_pixel_buffer_tpu.http.server import PixelBufferApp
    from omero_ms_pixel_buffer_tpu.io.ometiff import write_ome_tiff
    from omero_ms_pixel_buffer_tpu.io.pixels_service import (
        ImageRegistry,
        PixelsService,
    )
    from omero_ms_pixel_buffer_tpu.tile_ctx import TileCtx
    from omero_ms_pixel_buffer_tpu.utils.config import Config

    out: dict = {}
    headers = {"Cookie": "sessionid=bench-cookie"}
    peer_headers = {**headers, "X-OMPB-Peer": "bench-ops"}
    img_path = os.path.join(cache_dir, "cluster_fixture.ome.tiff")
    if not os.path.exists(img_path):
        rng_local = np.random.default_rng(23)
        img = rng_local.integers(
            0, 60000, (1, 1, 1, 512, 512), dtype=np.uint16
        )
        write_ome_tiff(
            img_path, img, tile_size=(64, 64), pyramid_levels=2
        )

    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def tile_paths(n):
        return [
            f"/tile/1/0/0/0?x={64 * (i % 8)}&y={64 * (i // 8)}"
            "&w=64&h=64&format=png"
            for i in range(n)
        ]

    def key_for(app_obj, path):
        query = dict(
            kv.split("=") for kv in path.split("?", 1)[1].split("&")
        )
        _, _, image_id, z, c, t = path.split("?", 1)[0].split("/")
        ctx = TileCtx.from_params(
            {"imageId": image_id, "z": z, "c": c, "t": t, **query},
            None,
        )
        return ctx.cache_key(app_obj.pipeline.encode_signature())

    def lifecycle_block(extra=None):
        return {
            "lease-ttl-s": 0.5, "replication-factor": 2,
            "drain": {"deadline-s": 5, "signal": False},
            **(extra or {}),
        }

    async def boot(members, self_url, port, resp_uri, extra):
        registry = ImageRegistry()
        registry.add(1, img_path)
        cluster_block = {
            "members": members, "self": self_url,
            "peer-timeout-ms": 3000, **(extra or {}),
        }
        if resp_uri:
            cluster_block["l2"] = {"uri": resp_uri}
        config = Config.from_dict({
            "session-store": {"type": "memory"},
            "backend": {"batching": {"coalesce-window-ms": 1.0}},
            "cache": {"prefetch": {"enabled": False}},
            "cluster": cluster_block,
        })
        app_obj = PixelBufferApp(
            config,
            pixels_service=PixelsService(registry),
            session_store=MemorySessionStore(
                {"bench-cookie": "bench-key"}
            ),
        )
        runner = web.AppRunner(app_obj.make_app(), access_log=None)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", port)
        await site.start()
        return app_obj, runner

    n_hot = 16
    warm_sources = ("hit", "l2-hit", "peer-hit")

    async def rolling_restart() -> dict:
        resp = InMemoryRespServer()
        await resp.start()
        ports = [free_port() for _ in range(3)]
        members = [f"http://127.0.0.1:{p}" for p in ports]
        nodes = []
        for i, port in enumerate(ports):
            nodes.append(await boot(
                members, members[i], port, resp.uri,
                lifecycle_block(),
            ))
        statuses: list = []
        sources: list = []
        try:
            await asyncio.sleep(0.4)  # leases discovered
            paths = tile_paths(n_hot)
            async with ClientSession() as http:
                for path in paths:  # warm every replica
                    for app_obj, _r in nodes:
                        async with http.get(
                            app_obj.cache_plane.self_url + path,
                            headers=headers,
                        ) as r:
                            assert r.status == 200, await r.text()

                async def traffic_round(live):
                    for path in paths:
                        for app_obj, _r in live:
                            async with http.get(
                                app_obj.cache_plane.self_url + path,
                                headers=headers,
                            ) as r:
                                await r.read()
                                statuses.append(r.status)
                                sources.append(
                                    r.headers.get("X-Cache")
                                )

                handoff_pushed = 0
                for i in range(3):
                    victim_app, victim_runner = nodes[i]
                    victim_url = victim_app.cache_plane.self_url
                    survivors = [
                        n for j, n in enumerate(nodes) if j != i
                    ]

                    async def _drain():
                        async with http.post(
                            victim_url + "/internal/drain?wait=1",
                            headers=peer_headers,
                        ) as r:
                            return r.status, await r.json()

                    drain_task = asyncio.ensure_future(_drain())
                    while not drain_task.done():
                        await traffic_round(survivors)
                        await asyncio.sleep(0.02)
                    status, drained = await drain_task
                    assert status == 200, drained
                    handoff_pushed += drained["stats"]["handoff"][
                        "pushed"
                    ]
                    await victim_runner.cleanup()
                    for key in [
                        k for k in resp.data
                        if k.startswith(b"ompb:tile:")
                    ]:
                        del resp.data[key]
                    for _ in range(2):
                        await traffic_round(survivors)
                    nodes[i] = await boot(
                        members, victim_url, ports[i], resp.uri,
                        lifecycle_block(),
                    )
                    deadline = time.monotonic() + 6.0
                    while time.monotonic() < deadline:
                        if all(
                            len(a.cache_plane.membership.members) == 3
                            for a, _r in nodes
                        ):
                            break
                        await traffic_round(survivors)
                        await asyncio.sleep(0.1)
            errors = sum(1 for s in statuses if s >= 500)
            warm = sum(1 for s in sources if s in warm_sources)
            return {
                "requests": len(statuses),
                "serving_errors": errors,
                "warm_hits": warm,
                "warm_hit_rate": round(warm / max(1, len(sources)), 3),
                "handoff_pushed": handoff_pushed,
            }
        finally:
            for _a, runner in nodes:
                try:
                    await runner.cleanup()
                except Exception:
                    pass
            await resp.close()

    out["rolling_restart"] = asyncio.run(rolling_restart())

    async def repair_drive() -> dict:
        resp = InMemoryRespServer()
        await resp.start()
        ports = [free_port() for _ in range(3)]
        members = [f"http://127.0.0.1:{p}" for p in ports]
        nodes = []
        for i, port in enumerate(ports):
            nodes.append(await boot(
                members, members[i], port, resp.uri,
                lifecycle_block({"repair": {"interval-s": 60}}),
            ))
        try:
            await asyncio.sleep(0.4)
            apps = {
                a.cache_plane.self_url: a for a, _r in nodes
            }
            plane0 = nodes[0][0].cache_plane
            target = None
            for path in tile_paths(n_hot):
                key = key_for(nodes[0][0], path)
                owners = plane0.ring.owners(key, 2)
                if len(owners) == 2:
                    target = (path, key, owners[0], owners[1])
                    break
            path, key, owner_url, succ_url = target
            owner, succ = apps[owner_url], apps[succ_url]

            async def lost_push(*a, **k):
                return None

            owner.cache_plane._push_replicas = lost_push
            async with ClientSession() as http:
                for _ in range(2):  # second touch crosses the hot bar
                    async with http.get(
                        owner_url + path, headers=headers
                    ) as r:
                        assert r.status == 200
            rounds = 0
            repaired = False
            for _ in range(2):  # one rotation over the peers
                rounds += 1
                await succ.cache_plane.repair_round()
                if succ.result_cache.contains(key):
                    repaired = True
                    break
            return {
                "repaired": repaired,
                "rounds_to_converge": rounds if repaired else None,
                "round_bound": 2,
                "repairer": succ.cache_plane.repairer.snapshot(),
            }
        finally:
            for _a, runner in nodes:
                await runner.cleanup()
            await resp.close()

    out["repair"] = asyncio.run(repair_drive())

    rr = out["rolling_restart"]
    out["cluster_ok_drain_zero_errors"] = (
        rr["serving_errors"] == 0
        and rr["warm_hit_rate"] >= 0.95
        and rr["requests"] > 0
    )
    out["cluster_ok_repair_convergence"] = (
        out["repair"]["repaired"]
        and out["repair"]["rounds_to_converge"]
        <= out["repair"]["round_bound"]
    )
    return out


def bench_decentralized(cache_dir: str) -> dict:
    """Decentralized control plane (r20) section — two drives, two
    pins:

    - ``redisless``: a three-replica GOSSIP cluster (Redis demoted to
      L2 + join hint) is warmed, then the RESP stub is killed
      mid-traffic and the same hot set is driven again. Pin
      ``cluster_ok_redisless_convergence``: every replica's
      membership view stays fully converged through the outage, the
      post-outage warm-hit rate holds >= 0.8, and the whole drive
      serves ZERO 5xx — "Redis down" degrades the shared cache,
      never coordination.
    - ``integrity``: one replica of a gossip+suspicion fleet serves
      bit-flipped bodies under intact ETags (the wrong-but-200 bad-
      RAM failure). Every transfer is discarded at the content-hash
      gate and the strikes feed the suspicion quorum. Pin
      ``cluster_ok_integrity_demotion``: zero wrong bytes reach any
      client, and the corrupt replica is demoted within <= 2 brain
      rounds of the verdict landing (one round to publish the
      verdict over gossip, one for the peers to apply it).
    """
    import socket

    from aiohttp import ClientSession, web

    from omero_ms_pixel_buffer_tpu.auth.stores import MemorySessionStore
    from omero_ms_pixel_buffer_tpu.cache.plane.resp_stub import (
        InMemoryRespServer,
    )
    from omero_ms_pixel_buffer_tpu.cache.result_cache import CachedTile
    from omero_ms_pixel_buffer_tpu.http.server import PixelBufferApp
    from omero_ms_pixel_buffer_tpu.io.ometiff import write_ome_tiff
    from omero_ms_pixel_buffer_tpu.io.pixels_service import (
        ImageRegistry,
        PixelsService,
    )
    from omero_ms_pixel_buffer_tpu.tile_ctx import TileCtx
    from omero_ms_pixel_buffer_tpu.utils.config import Config

    out: dict = {}
    headers = {"Cookie": "sessionid=bench-cookie"}
    img_path = os.path.join(cache_dir, "cluster_fixture.ome.tiff")
    if not os.path.exists(img_path):
        rng_local = np.random.default_rng(23)
        img = rng_local.integers(
            0, 60000, (1, 1, 1, 512, 512), dtype=np.uint16
        )
        write_ome_tiff(
            img_path, img, tile_size=(64, 64), pyramid_levels=2
        )

    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def tile_paths(n):
        return [
            f"/tile/1/0/0/0?x={64 * (i % 8)}&y={64 * (i // 8)}"
            "&w=64&h=64&format=png"
            for i in range(n)
        ]

    def key_for(app_obj, path):
        query = dict(
            kv.split("=") for kv in path.split("?", 1)[1].split("&")
        )
        _, _, image_id, z, c, t = path.split("?", 1)[0].split("/")
        ctx = TileCtx.from_params(
            {"imageId": image_id, "z": z, "c": c, "t": t, **query},
            None,
        )
        return ctx.cache_key(app_obj.pipeline.encode_signature())

    gossip_block = {
        "gossip": {
            "enabled": True, "interval-s": 0.15, "fail-after-s": 1.2,
        },
    }

    async def boot(members, self_url, port, resp_uri, extra):
        registry = ImageRegistry()
        registry.add(1, img_path)
        cluster_block = {
            "members": members, "self": self_url,
            "peer-timeout-ms": 3000, **(extra or {}),
        }
        if resp_uri:
            cluster_block["l2"] = {"uri": resp_uri}
        config = Config.from_dict({
            "session-store": {"type": "memory"},
            "backend": {"batching": {"coalesce-window-ms": 1.0}},
            "cache": {"prefetch": {"enabled": False}},
            "cluster": cluster_block,
        })
        app_obj = PixelBufferApp(
            config,
            pixels_service=PixelsService(registry),
            session_store=MemorySessionStore(
                {"bench-cookie": "bench-key"}
            ),
        )
        runner = web.AppRunner(app_obj.make_app(), access_log=None)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", port)
        await site.start()
        return app_obj, runner

    n_hot = 16
    warm_sources = ("hit", "l2-hit", "peer-hit")

    async def redisless_drive() -> dict:
        resp = InMemoryRespServer()
        await resp.start()
        ports = [free_port() for _ in range(3)]
        members = [f"http://127.0.0.1:{p}" for p in ports]
        nodes = []
        for i, port in enumerate(ports):
            nodes.append(await boot(
                members, members[i], port, resp.uri, gossip_block,
            ))
        statuses: list = []
        post_sources: list = []
        try:
            await asyncio.sleep(0.6)  # gossip rounds seed the view
            paths = tile_paths(n_hot)
            async with ClientSession() as http:
                for path in paths:  # warm every replica
                    for app_obj, _r in nodes:
                        async with http.get(
                            app_obj.cache_plane.self_url + path,
                            headers=headers,
                        ) as r:
                            await r.read()
                            statuses.append(r.status)
                # the coordinator dies mid-traffic
                await resp.close()
                await asyncio.sleep(0.6)  # gossip keeps ticking
                for path in paths:
                    for app_obj, _r in nodes:
                        async with http.get(
                            app_obj.cache_plane.self_url + path,
                            headers=headers,
                        ) as r:
                            await r.read()
                            statuses.append(r.status)
                            post_sources.append(
                                r.headers.get("X-Cache")
                            )
            converged = all(
                len(a.cache_plane.membership.members) == 3
                for a, _r in nodes
            )
            errors = sum(1 for s in statuses if s >= 500)
            warm = sum(1 for s in post_sources if s in warm_sources)
            return {
                "requests": len(statuses),
                "serving_errors": errors,
                "ring_converged_after_outage": converged,
                "post_outage_warm_hit_rate": round(
                    warm / max(1, len(post_sources)), 3
                ),
            }
        finally:
            for _a, runner in nodes:
                try:
                    await runner.cleanup()
                except Exception:
                    pass
            await resp.close()

    out["redisless"] = asyncio.run(redisless_drive())

    async def integrity_drive() -> dict:
        ports = [free_port() for _ in range(3)]
        members = [f"http://127.0.0.1:{p}" for p in ports]
        nodes = []
        for i, port in enumerate(ports):
            nodes.append(await boot(
                members, members[i], port, None,
                {**gossip_block, "suspect": {"enabled": True}},
            ))
        victim_app = nodes[2][0]
        victim_url = victim_app.cache_plane.self_url
        healthy = [a for a, _r in nodes[:2]]
        try:
            await asyncio.sleep(0.6)
            paths = tile_paths(n_hot)
            baseline = {}
            wrong_bytes = 0
            async with ClientSession() as http:
                # baseline through the honest victim: it caches its
                # owned keys, the healthy replicas only their own
                for path in paths:
                    async with http.get(
                        victim_url + path, headers=headers
                    ) as r:
                        baseline[path] = await r.read()
                # bad-RAM lever: victim serves flipped bytes under
                # the ORIGINAL ETag from here on
                cache = victim_app.result_cache
                inner = cache.get

                async def bad_get(key):
                    entry = await inner(key)
                    if entry is None:
                        return None
                    flipped = (
                        bytes([entry.body[0] ^ 0xFF]) + entry.body[1:]
                    )
                    return CachedTile(
                        flipped, etag=entry.etag,
                        filename=entry.filename,
                        stored_at=entry.stored_at,
                    )

                cache.get = bad_get
                for a in healthy:
                    for path in paths:
                        async with http.get(
                            a.cache_plane.self_url + path,
                            headers=headers,
                        ) as r:
                            if await r.read() != baseline[path]:
                                wrong_bytes += 1

                async def _verdicts():
                    while not all(
                        victim_url in a.cache_plane.brains.my_verdicts
                        for a in healthy
                    ):
                        await asyncio.sleep(0.02)

                await asyncio.wait_for(_verdicts(), 10.0)
                base_rounds = {
                    a: a.cache_plane.membership.refreshes
                    for a in healthy
                }
                demote_rounds: dict = {}

                async def _demoted():
                    while len(demote_rounds) < len(healthy):
                        for a in healthy:
                            if a in demote_rounds:
                                continue
                            if victim_url in a.cache_plane.brains.demoted:
                                demote_rounds[a] = (
                                    a.cache_plane.membership.refreshes
                                    - base_rounds[a]
                                )
                        await asyncio.sleep(0.02)

                await asyncio.wait_for(_demoted(), 10.0)
                # the re-homed keys still serve correct bytes
                for a in healthy:
                    for path in paths[:4]:
                        async with http.get(
                            a.cache_plane.self_url + path,
                            headers=headers,
                        ) as r:
                            if await r.read() != baseline[path]:
                                wrong_bytes += 1
            strikes = {
                a.cache_plane.self_url:
                    a.cache_plane.corruption.counts().get(victim_url, 0)
                for a in healthy
            }
            return {
                "wrong_bytes_served": wrong_bytes,
                "demoted": True,
                "rounds_to_demote": max(demote_rounds.values()),
                "round_bound": 2,
                "integrity_strikes": strikes,
            }
        finally:
            for _a, runner in nodes:
                try:
                    await runner.cleanup()
                except Exception:
                    pass

    out["integrity"] = asyncio.run(integrity_drive())

    rl = out["redisless"]
    out["cluster_ok_redisless_convergence"] = (
        rl["serving_errors"] == 0
        and rl["ring_converged_after_outage"]
        and rl["post_outage_warm_hit_rate"] >= 0.8
        and rl["requests"] > 0
    )
    it = out["integrity"]
    out["cluster_ok_integrity_demotion"] = (
        it["wrong_bytes_served"] == 0
        and it["demoted"]
        and it["rounds_to_demote"] <= it["round_bound"]
    )
    return out


def bench_session(cache_dir: str) -> dict:
    """Interactive session plane (r22) section — two drives, two pins:

    - ``push``: a two-replica pair; a WebSocket channel subscribed on
      replica B while annotation writes land on replica A. Each
      write's invalidation rides the purge fan-out to B and is pushed
      down the channel — the measured write->frame latency is the
      delta path end to end, cross-replica. Pin
      ``session_ok_push_latency``: every delta arrives, p99 under
      1000 ms (a TTL-polling viewer would wait a cache TTL — tens of
      seconds — to learn the same fact).
    - ``drain``: replica A drains while holding 10 live channels and
      serving tile traffic. Every channel must receive an explicit
      ``{"reconnect": successor}`` frame before its close, the
      successor must absorb the subscription summary, and the tile
      traffic must see zero 5xx. Pin ``session_ok_drain_zero_drops``:
      reconnect frames == channels, absorbed == channels, zero 5xx.
    """
    import socket

    from aiohttp import ClientSession, WSMsgType, web

    from omero_ms_pixel_buffer_tpu.auth.stores import MemorySessionStore
    from omero_ms_pixel_buffer_tpu.cache.plane.resp_stub import (
        InMemoryRespServer,
    )
    from omero_ms_pixel_buffer_tpu.http.server import PixelBufferApp
    from omero_ms_pixel_buffer_tpu.io.ometiff import write_ome_tiff
    from omero_ms_pixel_buffer_tpu.io.pixels_service import (
        ImageRegistry,
        PixelsService,
    )
    from omero_ms_pixel_buffer_tpu.utils.config import Config

    out: dict = {}
    headers = {"Cookie": "sessionid=bench-cookie"}
    peer_headers = {**headers, "X-OMPB-Peer": "bench-ops"}
    img_path = os.path.join(cache_dir, "session_fixture.ome.tiff")
    if not os.path.exists(img_path):
        rng_local = np.random.default_rng(29)
        img = rng_local.integers(
            0, 60000, (1, 1, 1, 256, 256), dtype=np.uint16
        )
        write_ome_tiff(img_path, img, tile_size=(64, 64))

    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    async def boot(members, self_url, port, resp_uri=None, extra=None):
        registry = ImageRegistry()
        registry.add(1, img_path)
        cluster_block = {
            "members": members, "self": self_url,
            "peer-timeout-ms": 3000, **(extra or {}),
        }
        if resp_uri:
            cluster_block["l2"] = {"uri": resp_uri}
        config = Config.from_dict({
            "session-store": {"type": "memory"},
            "backend": {"batching": {"coalesce-window-ms": 1.0}},
            "cache": {"prefetch": {"enabled": False}},
            "cluster": cluster_block,
        })
        app_obj = PixelBufferApp(
            config,
            pixels_service=PixelsService(registry),
            session_store=MemorySessionStore(
                {"bench-cookie": "bench-key"}
            ),
        )
        runner = web.AppRunner(app_obj.make_app(), access_log=None)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", port)
        await site.start()
        return app_obj, runner

    async def recv_frame(ws, timeout=10.0):
        msg = await asyncio.wait_for(ws.receive(), timeout)
        if msg.type != WSMsgType.TEXT:
            return None
        return json.loads(msg.data)

    n_writes = 20

    async def push_drive() -> dict:
        ports = [free_port() for _ in range(2)]
        members = [f"http://127.0.0.1:{p}" for p in ports]
        nodes = []
        for i, port in enumerate(ports):
            nodes.append(await boot(members, members[i], port))
        url_a, url_b = members
        latencies: list = []
        delivered = 0
        try:
            async with ClientSession() as http:
                ws = await asyncio.wait_for(
                    http.ws_connect(
                        url_b + "/session/1/live", headers=headers
                    ), 10.0,
                )
                await recv_frame(ws)  # hello
                shape = {"type": "rect", "x": 4, "y": 4,
                         "w": 16, "h": 16}
                for i in range(n_writes):
                    t0 = time.perf_counter()
                    async with http.post(
                        url_a + "/annotations/1", headers=headers,
                        json={"shape": shape, "label": f"w{i}"},
                    ) as r:
                        assert r.status == 201, await r.text()
                    frame = await recv_frame(ws, timeout=5.0)
                    if frame is not None and frame.get("type") in (
                        "invalidate", "annotations"
                    ):
                        latencies.append(
                            (time.perf_counter() - t0) * 1000.0
                        )
                        delivered += 1
                    # drain any second frame from the same write (the
                    # local fan-out can produce both kinds) so the
                    # next measurement starts on an empty queue
                    while True:
                        try:
                            msg = await asyncio.wait_for(
                                ws.receive(), 0.05
                            )
                        except asyncio.TimeoutError:
                            break
                        if msg.type != WSMsgType.TEXT:
                            break
                await ws.close()
        finally:
            for _a, runner in nodes:
                await runner.cleanup()
        latencies.sort()
        return {
            "writes": n_writes,
            "delivered": delivered,
            "p50_ms": round(
                latencies[len(latencies) // 2], 2
            ) if latencies else None,
            "p99_ms": round(
                latencies[min(len(latencies) - 1,
                              int(len(latencies) * 0.99))], 2
            ) if latencies else None,
        }

    out["push"] = asyncio.run(push_drive())

    n_channels = 10

    async def drain_drive() -> dict:
        resp = InMemoryRespServer()
        await resp.start()
        ports = [free_port() for _ in range(2)]
        members = [f"http://127.0.0.1:{p}" for p in ports]
        extra = {
            "lease-ttl-s": 0.5,
            "drain": {"deadline-s": 5, "signal": False},
        }
        nodes = []
        for i, port in enumerate(ports):
            nodes.append(await boot(
                members, members[i], port, resp.uri, extra,
            ))
        url_a, url_b = members
        statuses: list = []
        reconnects = 0
        try:
            await asyncio.sleep(0.4)  # leases discovered
            async with ClientSession() as http:
                sockets = []
                for _ in range(n_channels):
                    ws = await asyncio.wait_for(
                        http.ws_connect(
                            url_a + "/session/1/live", headers=headers
                        ), 10.0,
                    )
                    await recv_frame(ws)  # hello
                    sockets.append(ws)

                async def tile_round():
                    for url in (url_a, url_b):
                        async with http.get(
                            url + "/tile/1/0/0/0?w=64&h=64&format=png",
                            headers=headers,
                        ) as r:
                            await r.read()
                            statuses.append(r.status)

                async def _drain():
                    async with http.post(
                        url_a + "/internal/drain?wait=1",
                        headers=peer_headers,
                    ) as r:
                        return r.status, await r.json()

                drain_task = asyncio.ensure_future(_drain())
                while not drain_task.done():
                    await tile_round()
                    await asyncio.sleep(0.02)
                status, drained = await drain_task
                assert status == 200, drained
                for ws in sockets:
                    frame = await recv_frame(ws, timeout=10.0)
                    if frame is not None and \
                            frame.get("type") == "reconnect" and \
                            frame.get("reconnect") == url_b:
                        reconnects += 1
                    await ws.close()
                absorbed = nodes[1][0].session_channels.snapshot()[
                    "handoff_in"
                ]
            return {
                "channels": n_channels,
                "reconnect_frames": reconnects,
                "absorbed_by_successor": absorbed,
                "requests": len(statuses),
                "serving_errors": sum(
                    1 for s in statuses if s >= 500
                ),
                "drain_sessions": drained["stats"]["sessions"],
            }
        finally:
            for _a, runner in nodes:
                try:
                    await runner.cleanup()
                except Exception:
                    pass
            await resp.close()

    out["drain"] = asyncio.run(drain_drive())

    push = out["push"]
    out["session_ok_push_latency"] = (
        push["delivered"] == push["writes"]
        and push["p99_ms"] is not None
        and push["p99_ms"] < 1000.0
    )
    dr = out["drain"]
    out["session_ok_drain_zero_drops"] = (
        dr["reconnect_frames"] == dr["channels"]
        and dr["absorbed_by_successor"] == dr["channels"]
        and dr["serving_errors"] == 0
        and dr["requests"] > 0
    )
    return out


def bench_ingest(cache_dir: str) -> dict:
    """Ingest plane (r24) section — write-while-serve, two pins:

    - ``read_p99``: one node serving a tile read loop, first alone
      (baseline), then with a writer PUTting tiles through
      ``/image/{id}/tile`` the whole time. Every read must succeed and
      the concurrent read p99 must stay within 1.5x of the read-only
      baseline (with a small absolute floor so a sub-millisecond
      warm-cache baseline doesn't turn the ratio into noise). Pin
      ``ingest_ok_read_p99``.
    - ``invalidation``: after each committed write, the FIRST read of
      the written region must return the new bytes — the epoch bump
      and purge ride the commit response, so staleness is bounded by
      one epoch round, not a cache TTL. Pin
      ``ingest_ok_invalidation``: zero stale first-reads.
    """
    from aiohttp import web
    from aiohttp.test_utils import TestClient, TestServer

    from omero_ms_pixel_buffer_tpu.auth.stores import MemorySessionStore
    from omero_ms_pixel_buffer_tpu.http.server import PixelBufferApp
    from omero_ms_pixel_buffer_tpu.io.pixels_service import (
        ImageRegistry,
        PixelsService,
    )
    from omero_ms_pixel_buffer_tpu.io.zarr import write_ngff
    from omero_ms_pixel_buffer_tpu.utils.config import Config

    headers = {"Cookie": "sessionid=bench-cookie"}
    img_path = os.path.join(cache_dir, "ingest_fixture.zarr")
    rng_local = np.random.default_rng(31)
    img = rng_local.integers(
        0, 4096, (1, 1, 1, 256, 256), dtype=np.uint16
    )
    if not os.path.exists(img_path):
        write_ngff(
            img_path, img, chunks=(64, 64), levels=1,
            zarr_format=3, shards=(128, 128),
        )

    n_reads = int(os.environ.get("BENCH_INGEST_READS", "300"))
    n_writes = int(os.environ.get("BENCH_INGEST_WRITES", "40"))

    async def drive() -> dict:
        registry = ImageRegistry()
        registry.add(1, img_path)
        config = Config.from_dict({
            "session-store": {"type": "memory"},
            "backend": {"batching": {"coalesce-window-ms": 1.0}},
            "ingest": {"enabled": True},
        })
        app_obj = PixelBufferApp(
            config,
            pixels_service=PixelsService(registry),
            session_store=MemorySessionStore(
                {"bench-cookie": "bench-key"}
            ),
        )
        client = TestClient(
            TestServer(app_obj.make_app()),
            loop=asyncio.get_running_loop(),
        )
        await client.start_server()
        tiles = [(x, y) for x in (0, 64, 128) for y in (0, 64, 128)]
        try:
            async def read_loop(n, latencies, statuses):
                for i in range(n):
                    x, y = tiles[i % len(tiles)]
                    t0 = time.perf_counter()
                    r = await client.get(
                        f"/tile/1/0/0/0?x={x}&y={y}&w=64&h=64",
                        headers=headers,
                    )
                    await r.read()
                    statuses.append(r.status)
                    latencies.append(
                        (time.perf_counter() - t0) * 1000.0
                    )

            # baseline: the read loop alone
            base_lat: list = []
            base_status: list = []
            await read_loop(n_reads, base_lat, base_status)

            # concurrent: same loop with a writer alongside
            write_status: list = []

            async def write_loop():
                tile = np.full((64, 64), 7, dtype=np.uint16)
                for i in range(n_writes):
                    tile[...] = i
                    r = await client.put(
                        f"/image/1/tile/0/0/0"
                        f"?x={(i % 3) * 64}&y=64&w=64&h=64",
                        data=tile.astype(">u2").tobytes(),
                        headers=headers,
                    )
                    await r.read()
                    write_status.append(r.status)
                    await asyncio.sleep(0)

            conc_lat: list = []
            conc_status: list = []
            writer = asyncio.ensure_future(write_loop())
            await read_loop(n_reads, conc_lat, conc_status)
            await writer

            # invalidation: first read after each commit must be fresh
            stale = 0
            for i in range(n_writes):
                tile = np.full((64, 64), 100 + i, dtype=np.uint16)
                wire = tile.astype(">u2").tobytes()
                r = await client.put(
                    "/image/1/tile/0/0/0?x=128&y=128&w=64&h=64",
                    data=wire, headers=headers,
                )
                await r.read()
                assert r.status == 200
                r = await client.get(
                    "/tile/1/0/0/0?x=128&y=128&w=64&h=64",
                    headers=headers,
                )
                if await r.read() != wire:
                    stale += 1

            def p99(lat):
                lat = sorted(lat)
                return round(
                    lat[min(len(lat) - 1, int(len(lat) * 0.99))], 2
                )

            return {
                "reads": n_reads,
                "writes": n_writes,
                "baseline_read_p99_ms": p99(base_lat),
                "concurrent_read_p99_ms": p99(conc_lat),
                "read_errors": sum(
                    1 for s in base_status + conc_status if s >= 500
                ),
                "write_errors": sum(
                    1 for s in write_status if s != 200
                ),
                "stale_first_reads": stale,
            }
        finally:
            await client.close()

    out = asyncio.run(drive())
    out["ingest_ok_read_p99"] = (
        out["read_errors"] == 0
        and out["write_errors"] == 0
        and out["concurrent_read_p99_ms"] <= max(
            1.5 * out["baseline_read_p99_ms"], 25.0
        )
    )
    out["ingest_ok_invalidation"] = out["stale_first_reads"] == 0
    return out


def bench_overload(
    cache_dir: str,
    duration_s: float = 4.0,
    capacity: int = 2,
    queue_size: int = 6,
    service_ms: float = 25.0,
    budget_ms: float = 300.0,
    degrade_factor: float = 6.0,
    interactive_p99_bound_ms: float = 0.0,
) -> dict:
    """Sustained-overload SLO scenario (r13): mixed-class closed-loop
    load at ~2x admission capacity against the deadline-ordered
    scheduler, asserting *SLO outcomes* — interactive p99 and
    degraded-fraction per class — instead of throughput alone.

    Shape: a pyramidal NGFF image behind the full app (cache OFF so
    every request exercises the scheduler + pipeline; the pipeline is
    slowed a deterministic ``service_ms`` per tile so capacity is a
    controlled constant). 10 closed-loop clients — 5 interactive,
    3 prefetch-labelled, 2 bulk-labelled — sustain well past 2x the
    admission capacity (5x the executing slots, 1.25x what slots +
    wait queue absorb), so the queue is genuinely full for the whole
    window and the shed policy is continuously exercised.
    ``queue_size`` deliberately exceeds the interactive client count:
    an interactive arrival can then always evict a lower-class waiter,
    so any interactive 503 is a scheduler bug, not a sizing artifact
    (and lower classes still shed, because slots + queue < total
    clients).

    The three pins (recorded as slo_ok_* booleans; the CI smoke fails
    on them):
    - zero interactive 503s while lower classes still had sheddable
      work (the scheduler's core promise);
    - interactive p99 within ``interactive_p99_bound_ms`` (default:
      the request budget — an interactive request either makes its
      deadline or degrades, it never blows through it);
    - degradation engaged (degraded fraction > 0 for interactive)
      and every degraded response is tagged.
    """
    from aiohttp import web

    from omero_ms_pixel_buffer_tpu.auth.stores import MemorySessionStore
    from omero_ms_pixel_buffer_tpu.http.server import PixelBufferApp
    from omero_ms_pixel_buffer_tpu.io.pixels_service import (
        ImageRegistry,
        PixelsService,
    )
    from omero_ms_pixel_buffer_tpu.io.zarr import write_ngff
    from omero_ms_pixel_buffer_tpu.utils.config import Config

    if not interactive_p99_bound_ms:
        interactive_p99_bound_ms = budget_ms
    size = 1024
    path = os.path.join(cache_dir, "overload_1024.zarr")
    if not os.path.exists(path):
        rng = np.random.default_rng(29)
        img = rng.integers(
            0, 60000, (1, 1, 1, size, size), dtype=np.uint16
        )
        write_ngff(path, img, chunks=(256, 256), levels=3)
    registry = ImageRegistry()
    registry.add(1, path, type="zarr")
    config = Config.from_dict(
        {
            "session-store": {"type": "memory"},
            "worker_pool_size": capacity,
            "backend": {"batching": {"max-batch": 1,
                                     "coalesce-window-ms": 0.0}},
            "cache": {"enabled": False},
            "resilience": {
                "admission": {"max-inflight": capacity},
                "request-budget-ms": budget_ms,
            },
            "slo": {
                "queue-size": queue_size,
                "degrade-factor": degrade_factor,
            },
        }
    )
    service = PixelsService(registry)
    app_obj = PixelBufferApp(
        config,
        pixels_service=service,
        session_store=MemorySessionStore({"bench-cookie": "bench-key"}),
    )
    inner = app_obj.pipeline.handle
    service_s = service_ms / 1000.0

    def slowed(ctx):
        time.sleep(service_s)
        return inner(ctx)

    app_obj.pipeline.handle = slowed

    classes = (
        [("interactive", {})] * 5
        + [("prefetch", {"Sec-Purpose": "prefetch"})] * 3
        + [("bulk", {"X-OMPB-Priority": "bulk"})] * 2
    )
    samples: list = []  # (class, status, latency_s, degraded)

    async def run() -> dict:
        runner = web.AppRunner(app_obj.make_app(), access_log=None)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = runner.addresses[0][1]

        import aiohttp

        async def worker(idx, cls, extra_headers, warm_only=False):
            # stable per-worker seed: hash() is PYTHONHASHSEED-
            # randomized (a CI flake here would be unreproducible) and
            # a per-class seed would run same-class workers in lockstep
            rng = np.random.default_rng(
                zlib.crc32(f"{cls}-{idx}".encode())
            )
            headers = {"Cookie": "sessionid=bench-cookie"}
            headers.update(extra_headers)
            deadline = time.perf_counter() + duration_s
            async with aiohttp.ClientSession() as sess:
                while time.perf_counter() < deadline:
                    x = int(rng.integers(0, size // 256)) * 256
                    y = int(rng.integers(0, size // 256)) * 256
                    url = (
                        f"http://127.0.0.1:{port}/tile/1/0/0/0"
                        f"?x={x}&y={y}&w=256&h=256&format=png"
                    )
                    t0 = time.perf_counter()
                    async with sess.get(url, headers=headers) as r:
                        await r.read()
                        samples.append((
                            cls, r.status,
                            time.perf_counter() - t0,
                            int(r.headers.get("X-OMPB-Degraded", 0)),
                        ))
                    if warm_only:
                        return

        try:
            # warm: one uncontended request trains the service EWMA
            await worker(0, "interactive", {}, warm_only=True)
            samples.clear()
            await asyncio.gather(*(
                worker(i, cls, hdrs)
                for i, (cls, hdrs) in enumerate(classes)
            ))
        finally:
            await runner.cleanup()
            service.close()

        out: dict = {
            "offered_classes": {"interactive": 5, "prefetch": 3,
                                "bulk": 2},
            "capacity": capacity,
            "queue_size": queue_size,
            "service_ms": service_ms,
            "budget_ms": budget_ms,
            "duration_s": duration_s,
        }
        for cls in ("interactive", "prefetch", "bulk"):
            rows = [s for s in samples if s[0] == cls]
            ok = [s for s in rows if s[1] == 200]
            lat = np.array([s[2] for s in ok]) * 1000.0
            degraded = sum(1 for s in ok if s[3])
            out[cls] = {
                "requests": len(rows),
                "status_200": len(ok),
                "status_503": sum(1 for s in rows if s[1] == 503),
                "status_504": sum(1 for s in rows if s[1] == 504),
                "degraded": degraded,
                "degraded_fraction": (
                    round(degraded / len(ok), 3) if ok else None
                ),
                "p50_ms": (
                    round(float(np.percentile(lat, 50)), 2)
                    if len(lat) else None
                ),
                "p99_ms": (
                    round(float(np.percentile(lat, 99)), 2)
                    if len(lat) else None
                ),
            }
        out["scheduler"] = app_obj.scheduler.snapshot()
        lower_shed = (
            out["prefetch"]["status_503"] + out["bulk"]["status_503"]
        )
        # the three SLO pins (explicit if/record — never bare asserts,
        # python -O would strip them)
        out["slo_ok_no_interactive_503"] = (
            out["interactive"]["status_503"] == 0 and lower_shed > 0
        )
        p99 = out["interactive"]["p99_ms"]
        out["interactive_p99_bound_ms"] = interactive_p99_bound_ms
        out["slo_ok_interactive_p99"] = (
            p99 is not None and p99 <= interactive_p99_bound_ms
        )
        out["slo_ok_degradation_engaged"] = (
            (out["interactive"]["degraded"] or 0) > 0
        )
        return out

    return asyncio.run(run())


def bench_io(cache_dir: str) -> dict:
    """Cold-remote read plane (r14): a loopback HTTP object store with
    per-request latency serving a multi-chunk NGFF image (16 chunks
    per 256px tile) both unsharded and Zarr-v3-sharded.

    Pins (io_ok_*): batch dedupe + range coalescing spend < 1.0 store
    requests per tile on the sharded fixture (sequential was >= 16);
    the parallel+coalesced plane is >= 2x the sequential path's
    tiles/s on identical inputs; and sharded tile bytes are identical
    to the unsharded ground truth."""
    import functools
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from omero_ms_pixel_buffer_tpu.io import fetch
    from omero_ms_pixel_buffer_tpu.io.zarr import (
        ZarrPixelBuffer,
        write_ngff,
    )

    rng = np.random.default_rng(23)
    img = rng.integers(0, 60000, (1, 1, 1, 1024, 1024), dtype=np.uint16)
    plain = os.path.join(cache_dir, "io_plain.zarr")
    sharded = os.path.join(cache_dir, "io_sharded.zarr")
    if not os.path.exists(plain):
        write_ngff(plain, img, chunks=(64, 64), levels=1,
                   zarr_format=3, compressor="zlib")
    if not os.path.exists(sharded):
        write_ngff(sharded, img, chunks=(64, 64), levels=1,
                   zarr_format=3, compressor="zlib", shards=(512, 512))

    class Handler(BaseHTTPRequestHandler):
        """Range-capable static handler with a 2 ms per-request floor
        — the round-trip a remote object store charges."""

        protocol_version = "HTTP/1.1"
        counts = {"n": 0}
        lock = threading.Lock()

        def __init__(self, root, *args, **kwargs):
            self.root = root
            super().__init__(*args, **kwargs)

        def log_message(self, *a):
            pass

        def _reply(self, code, body=b""):
            self.send_response(code)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            import urllib.parse

            with self.lock:
                self.counts["n"] += 1
            time.sleep(0.002)
            rel = urllib.parse.unquote(self.path.lstrip("/"))
            path = os.path.join(self.root, rel)
            if ".." in rel or not os.path.isfile(path):
                return self._reply(404)
            with open(path, "rb") as f:
                data = f.read()
            rng_h = self.headers.get("Range")
            if rng_h is None:
                return self._reply(200, data)
            spec = rng_h.split("=", 1)[1]
            if spec.startswith("-"):
                n = int(spec[1:])
                body = data[-n:] if n <= len(data) else data
                self.send_response(206)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            lo_s, _, hi_s = spec.partition("-")
            lo = int(lo_s)
            if lo >= len(data):
                return self._reply(416)
            hi = int(hi_s) + 1 if hi_s else len(data)
            body = data[lo:min(hi, len(data))]
            self.send_response(206)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    server = ThreadingHTTPServer(
        ("127.0.0.1", 0), functools.partial(Handler, cache_dir)
    )
    threading.Thread(target=server.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{server.server_address[1]}"

    tiles16 = [
        (0, 0, 0, x * 256, y * 256, 256, 256)
        for y in range(4) for x in range(4)
    ]
    ground = ZarrPixelBuffer(plain).read_tiles(tiles16, level=0)

    out: dict = {"fixture": {
        "plane": "1024x1024 uint16", "chunks": 64, "shards": 512,
        "tile": 256, "chunks_per_tile": 16,
    }}
    try:
        # -- sequential escape path, cold (the pre-r14 shape) ----------
        fetch.CONFIG.parallel = False
        Handler.counts["n"] = 0
        buf = ZarrPixelBuffer(f"{base}/io_sharded.zarr")
        meta_reqs = Handler.counts["n"]
        t0 = time.perf_counter()
        seq_tiles = []
        for i in range(0, 16, 8):
            seq_tiles += buf.read_tiles(tiles16[i:i + 8], level=0)
        seq_s = time.perf_counter() - t0
        seq_reqs = Handler.counts["n"] - meta_reqs
        out["sequential"] = {
            "tiles_per_sec": round(16 / seq_s, 2),
            "requests_per_tile": round(seq_reqs / 16, 2),
        }

        # -- parallel + coalesced, cold --------------------------------
        fetch.CONFIG.parallel = True
        stats0 = fetch.IO_STATS.snapshot()
        Handler.counts["n"] = 0
        buf = ZarrPixelBuffer(f"{base}/io_sharded.zarr")
        meta_reqs = Handler.counts["n"]
        t0 = time.perf_counter()
        par_tiles = []
        for i in range(0, 16, 8):
            par_tiles += buf.read_tiles(tiles16[i:i + 8], level=0)
        par_s = time.perf_counter() - t0
        par_reqs = Handler.counts["n"] - meta_reqs
        stats1 = fetch.IO_STATS.snapshot()
        planned = stats1["planned"] - stats0["planned"]
        saved = stats1["coalesced_saved"] - stats0["coalesced_saved"]

        # per-tile fetch latency distribution: 16 cold single-tile
        # reads on a fresh buffer (each is one planned batch)
        lat_ms = []
        buf = ZarrPixelBuffer(f"{base}/io_sharded.zarr")
        for co in tiles16:
            t0 = time.perf_counter()
            buf.read_tiles([co], level=0)
            lat_ms.append((time.perf_counter() - t0) * 1000.0)
        lat = np.array(sorted(lat_ms))

        out["parallel"] = {
            "tiles_per_sec": round(16 / par_s, 2),
            "requests_per_tile": round(par_reqs / 16, 3),
            "coalesced_ratio": (
                round(saved / planned, 3) if planned else 0.0
            ),
            "fetch_p50_ms": round(float(np.percentile(lat, 50)), 2),
            "fetch_p99_ms": round(float(np.percentile(lat, 99)), 2),
        }
        out["speedup_parallel_vs_sequential"] = round(seq_s / par_s, 2)
        identical = all(
            a.tobytes() == b.tobytes()
            for a, b in zip(ground, par_tiles)
        ) and all(
            a.tobytes() == b.tobytes()
            for a, b in zip(ground, seq_tiles)
        )
        # the three acceptance pins — explicit booleans in BENCH json
        out["io_ok_requests_per_tile"] = (
            out["parallel"]["requests_per_tile"] < 1.0
        )
        out["io_ok_parallel_speedup"] = (
            out["speedup_parallel_vs_sequential"] >= 2.0
        )
        out["io_ok_sharded_identical"] = identical
    finally:
        fetch.CONFIG.parallel = True
        server.shutdown()
    return out


def bench_obs(cache_dir: str, n: int = 240) -> dict:
    """Observability plane (r16) section — two pins:

    - ``obs_ok_overhead``: the flight recorder's warm-path cost. The
      same warm (cache-hit) URL set is replayed through two identical
      apps, obs on vs off, A/B interleaved over several rounds with
      the per-arm MIN p50 compared (min-of-rounds discards scheduler
      noise on a shared CI box). Pin: p50 penalty <= 3%, with a
      0.3 ms absolute floor — a sub-ms warm hit jitters by more than
      the recorder's ~30 us cost, and the floor keeps timer noise
      from failing a pin the recorder didn't earn.
    - ``obs_ok_tail_capture``: a forced-slow request (slow-threshold
      0 ms makes every cold render "slow") appears in the
      /debug/requests ring with full attribution — pipeline stages
      stamped and the stage sum within the observed total.
    """
    import hashlib  # noqa: F401 - parity with bench_cache imports

    from aiohttp import web

    from omero_ms_pixel_buffer_tpu.auth.stores import MemorySessionStore
    from omero_ms_pixel_buffer_tpu.http.server import PixelBufferApp
    from omero_ms_pixel_buffer_tpu.io.ometiff import write_ome_tiff
    from omero_ms_pixel_buffer_tpu.io.pixels_service import (
        ImageRegistry,
        PixelsService,
    )
    from omero_ms_pixel_buffer_tpu.utils.config import Config

    size = 2048
    path = os.path.join(cache_dir, "obs_fixture.ome.tiff")
    if not os.path.exists(path):
        rng = np.random.default_rng(61)
        img = rng.integers(
            0, 60000, (1, 1, 1, size, size), dtype=np.uint16
        )
        write_ome_tiff(path, img, tile_size=(256, 256))

    def make_app(obs_enabled: bool, slow_ms: float = 10_000.0):
        registry = ImageRegistry()
        registry.add(1, path)
        config = Config.from_dict({
            "session-store": {"type": "memory"},
            "backend": {"engine": "host"},
            "cache": {"prefetch": {"enabled": False}},
            "obs": {
                "enabled": obs_enabled,
                # overhead arms: nothing kept (pure recording cost);
                # the tail arm flips slow-threshold to 0 instead
                "head-sample-rate": 0.0,
                "slow-threshold-ms": slow_ms,
            },
        })
        service = PixelsService(registry)
        return PixelBufferApp(
            config,
            pixels_service=service,
            session_store=MemorySessionStore({"bench": "bench-key"}),
        ), service

    # 512-px tiles (the bench_cache latency-probe shape): the warm
    # baseline includes a realistic body transfer, so the pin reads
    # the recorder against what a viewer actually feels per hit
    urls = [
        f"/tile/1/0/0/0?x={512 * (i % 3)}&y={512 * (i // 3 % 3)}"
        "&w=512&h=512&format=png"
        for i in range(9)
    ]

    async def drive(port, request_urls, expect_status=200):
        latencies = []
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        try:
            for url in request_urls:
                t0 = time.perf_counter()
                writer.write(
                    f"GET {url} HTTP/1.1\r\n"
                    "Host: bench\r\n"
                    "Cookie: sessionid=bench\r\n"
                    "\r\n".encode()
                )
                await writer.drain()
                status = int((await reader.readline()).split()[1])
                clen = 0
                while True:
                    line = await reader.readline()
                    if line in (b"\r\n", b""):
                        break
                    if line.lower().startswith(b"content-length:"):
                        clen = int(line.split(b":", 1)[1])
                body = await reader.readexactly(clen)
                assert status == expect_status, (status, body[:200])
                latencies.append(time.perf_counter() - t0)
        finally:
            writer.close()
        return latencies, body

    async def warm_p50(app_obj, service, rounds: int = 3) -> float:
        runner = web.AppRunner(app_obj.make_app(), access_log=None)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = runner.addresses[0][1]
        try:
            await drive(port, urls)  # cold fill + warmup
            p50s = []
            for _ in range(rounds):
                lat, _ = await drive(
                    port, (urls * (n // len(urls) + 1))[:n]
                )
                p50s.append(
                    float(np.percentile(np.array(lat) * 1e3, 50))
                )
            return min(p50s)
        finally:
            await runner.cleanup()
            service.close()

    async def run() -> dict:
        out: dict = {"warm_requests_per_arm": n}
        app_on, svc_on = make_app(True)
        app_off, svc_off = make_app(False)
        out["warm_p50_on_ms"] = round(await warm_p50(app_on, svc_on), 3)
        out["warm_p50_off_ms"] = round(
            await warm_p50(app_off, svc_off), 3
        )
        penalty = (
            out["warm_p50_on_ms"] - out["warm_p50_off_ms"]
        ) / max(out["warm_p50_off_ms"], 1e-9)
        out["warm_p50_penalty"] = round(penalty, 4)
        out["obs_ok_overhead"] = bool(
            penalty <= 0.03
            or out["warm_p50_on_ms"] - out["warm_p50_off_ms"] <= 0.3
        )

        # forced-slow tail capture: slow-threshold 0 -> every serve is
        # "slow" and must be kept with full attribution
        app_slow, svc_slow = make_app(True, slow_ms=0.0)
        runner = web.AppRunner(app_slow.make_app(), access_log=None)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = runner.addresses[0][1]
        try:
            await drive(port, urls[:1])
            events = app_slow.recorder.events()
            captured = bool(events)
            event = events[0] if events else {}
            stages = event.get("stages_ms", {})
            attributed = sum(stages.values())
            out["tail_event_stages"] = sorted(stages)
            out["tail_event_total_ms"] = event.get("total_ms")
            out["obs_ok_tail_capture"] = bool(
                captured
                and event.get("kept_reason") == "slow"
                and {"resolve", "read", "encode"} <= set(stages)
                and attributed <= (event.get("total_ms") or 0) + 1.0
            )
        finally:
            await runner.cleanup()
            svc_slow.close()
        return out

    return asyncio.run(run())


def build_render_fixture(root: str, size: int = 2048, depth: int = 1):
    """3-channel uint16 fixture for the rendered-tile section;
    ``depth`` > 1 writes a z-stack (shifted copies of the base
    pattern) for projection-burst sections."""
    from omero_ms_pixel_buffer_tpu.io.ometiff import write_ome_tiff

    path = os.path.join(
        root,
        f"bench_render_{size}.ome.tiff" if depth == 1
        else f"bench_render_{size}_z{depth}.ome.tiff",
    )
    if os.path.exists(path):
        return path
    log(f"writing {size}x{size} 3-channel z={depth} render fixture...")
    rng = np.random.default_rng(31)
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32)
    chans = []
    for phase in (0.0, 1.1, 2.3):
        base = (
            1800
            + 1200 * np.sin(xx / 89.0 + phase)
            + 1200 * np.cos(yy / 127.0 + phase)
        )
        chans.append(
            (base + rng.normal(0, 90, (size, size))).clip(0, 4095)
        )
    data = np.stack(chans).astype(np.uint16)[None, :, None]
    if depth > 1:
        data = np.concatenate(
            [np.roll(data, 17 * z, axis=-1) for z in range(depth)],
            axis=2,
        )
    write_ome_tiff(path, data, tile_size=(512, 512), compression="zlib")
    return path


def bench_render(
    cache_dir: str, engine: str, size: int = 2048, n: int = 96
) -> dict:
    """Rendered-tile serving (render/): 3 channels window/leveled,
    colored, and composited per tile — p50/p99 per-tile latency plus
    coalesced tiles/s, host engine vs the headline engine (identical
    bytes by the engine contract, so only the clock differs)."""
    import time as _t

    from omero_ms_pixel_buffer_tpu.io.pixels_service import (
        ImageRegistry,
        PixelsService,
    )
    from omero_ms_pixel_buffer_tpu.models.tile_pipeline import TilePipeline
    from omero_ms_pixel_buffer_tpu.render.model import RenderSpec
    from omero_ms_pixel_buffer_tpu.tile_ctx import RegionDef, TileCtx

    path = build_render_fixture(cache_dir, size)
    registry = ImageRegistry()
    registry.add(1, path)
    spec = RenderSpec.from_params({
        "c": "1|0:4095$FF0000,2|0:4095$00FF00,3|0:4095$0000FF",
    })
    rng = np.random.default_rng(37)
    ctxs = []
    for _ in range(n):
        x = int(rng.integers(0, (size - 512) // 64)) * 64
        y = int(rng.integers(0, (size - 512) // 64)) * 64
        ctxs.append(TileCtx(
            image_id=1, z=0, c=0, t=0,
            region=RegionDef(x, y, 512, 512), format="png",
            omero_session_key="bench", render=spec,
        ))
    out = {}
    engines = ["host"] if engine == "host" else ["host", engine]
    for label in engines:
        service = PixelsService(registry)
        try:
            pipe = TilePipeline(service, engine=label, buckets=(512,))
            pipe.handle_batch(ctxs[:16])  # warm reads + tables + jit
            lat = []
            for ctx in ctxs[:32]:
                t0 = _t.perf_counter()
                assert pipe.handle(ctx) is not None
                lat.append(_t.perf_counter() - t0)
            tps = run_batched(pipe, ctxs, 16)
            lat_ms = np.array(lat) * 1000.0
            out[label] = {
                "tiles_per_sec": round(tps, 2),
                "p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
                "p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
            }
            log(f"[render] {label}: {out[label]}")
            pipe.close()
        except Exception as e:
            out[label] = {"error": f"{type(e).__name__}: {e}"}
            log(f"[render] {label} failed: {e!r}")
        finally:
            service.close()
    return out


def bench_supertile(
    cache_dir: str, engine: str, size: int = 1024, tile: int = 64,
    grid: int = 4, rounds: int = 3, depth: int = 4,
) -> dict:
    """Super-tile plane (r19) section — a 4x4 DZI-row burst (one
    spec, one resolution, grid-adjacent tiles; a 3-channel intmax
    z-projection over ``depth`` planes, the viewer burst shape where
    the shared plane gather is largest — every independent tile
    re-gathers and re-projects the whole z-range) rendered two ways:

    - ``independent``: every tile through its own ``handle()`` call —
      the literal "independently rendered tile" the byte-identity
      contract is pinned against (each pays its own gather,
      projection, composite, and dispatch);
    - ``fused``: the same tiles stamped by the batcher's adjacency
      bucketing and served through one ``handle_batch`` — ONE plane
      gather over the bounding rectangle, ONE projection + composite,
      carved per-tile encodes.

    Two pins (recorded per engine; the CI smoke fails on either):
    ``supertile_ok_speedup`` — the fused burst serves >= 2x the
    independent tiles/s on the headline engine; and
    ``supertile_ok_identical`` — fused bytes == independent bytes on
    EVERY engine that ran (the contract that lets fused tiles share
    ETags and cache entries).

    Default operating point: 64px tiles over a z=4 stack — the
    regime where the per-tile gather/projection/dispatch the fusion
    eliminates dominates. At 256px+ tiles on the CPU backend the
    per-tile deflate floor (untouched by fusion) dominates instead
    and the ratio compresses toward 1; KNOWN_GAPS records that
    honestly."""
    import time as _t

    from omero_ms_pixel_buffer_tpu.io.pixels_service import (
        ImageRegistry,
        PixelsService,
    )
    from omero_ms_pixel_buffer_tpu.models.tile_pipeline import TilePipeline
    from omero_ms_pixel_buffer_tpu.render.model import RenderSpec
    from omero_ms_pixel_buffer_tpu.render.supertile import (
        BurstHint,
        assign_supertiles,
    )
    from omero_ms_pixel_buffer_tpu.tile_ctx import RegionDef, TileCtx

    path = build_render_fixture(cache_dir, size, depth=depth)
    registry = ImageRegistry()
    registry.add(1, path)
    params = {
        "c": "1|0:4095$FF0000,2|0:4095$00FF00,3|0:4095$0000FF",
    }
    if depth > 1:
        params["p"] = f"intmax|0:{depth - 1}"
    spec = RenderSpec.from_params(params)
    hint = BurstHint(tile, tile)

    def burst_ctxs():
        return [
            TileCtx(
                image_id=1, z=0, c=0, t=0,
                region=RegionDef(col * tile, row * tile, tile, tile),
                format="png", omero_session_key="bench", render=spec,
                burst=hint,
            )
            for row in range(grid) for col in range(grid)
        ]

    out: dict = {}
    identical = True
    engines = ["host"] if engine == "host" else ["host", engine]
    for label in engines:
        service = PixelsService(registry)
        try:
            pipe = TilePipeline(
                service, engine=label, buckets=(tile,),
                device_deflate=(label != "host"),
            )
            pipe.mesh = None  # the fused composite is single-device
            # warm both shapes: per-tile jit/native paths AND the
            # fused super-tile program
            warm_ind = [pipe.handle(c) for c in burst_ctxs()]
            assert all(b is not None for b in warm_ind)
            warm_ctxs = burst_ctxs()
            assign_supertiles(warm_ctxs, max_pixels=(grid * tile) ** 2)
            warm_fused = pipe.handle_batch(warm_ctxs)
            if warm_fused != warm_ind:
                identical = False
                log(f"[supertile] {label}: FUSED BYTES DIVERGED")
            t0 = _t.perf_counter()
            for _ in range(rounds):
                for ctx in burst_ctxs():
                    assert pipe.handle(ctx) is not None
            ind_tps = rounds * grid * grid / (_t.perf_counter() - t0)
            t0 = _t.perf_counter()
            for _ in range(rounds):
                ctxs = burst_ctxs()
                assign_supertiles(
                    ctxs, max_pixels=(grid * tile) ** 2
                )
                res = pipe.handle_batch(ctxs)
                assert all(b is not None for b in res)
            fused_tps = rounds * grid * grid / (_t.perf_counter() - t0)
            out[label] = {
                "independent_tiles_per_sec": round(ind_tps, 2),
                "fused_tiles_per_sec": round(fused_tps, 2),
                "speedup": round(fused_tps / max(ind_tps, 1e-9), 3),
            }
            log(f"[supertile] {label}: {out[label]}")
            pipe.close()
        except Exception as e:
            out[label] = {"error": f"{type(e).__name__}: {e}"}
            identical = False
            log(f"[supertile] {label} failed: {e!r}")
        finally:
            service.close()
    headline = engines[-1]
    speedup = (out.get(headline) or {}).get("speedup")
    out["supertile_ok_speedup"] = bool(speedup and speedup >= 2.0)
    out["supertile_ok_identical"] = identical
    return out


def _mesh_fusion_child():
    """Subprocess body for the mesh half of ``bench_mesh_fusion`` —
    runs on a virtual n-device CPU platform (the parent sets XLA_FLAGS
    in the child's environment). Prints ONE marker line
    ``MESH_FUSION_CHILD {json}`` on stdout for the parent to parse."""
    import time as _t

    args = json.loads(os.environ["_OMPB_MESH_FUSION_ARGS"])
    cache_dir = args["cache_dir"]
    size, tile, grid = args["size"], args["tile"], args["grid"]
    rounds, depth, n_devices = args["rounds"], args["depth"], args["n"]

    import jax

    from omero_ms_pixel_buffer_tpu.io.pixels_service import (
        ImageRegistry,
        PixelsService,
    )
    from omero_ms_pixel_buffer_tpu.models.tile_pipeline import TilePipeline
    from omero_ms_pixel_buffer_tpu.parallel.mesh import make_mesh
    from omero_ms_pixel_buffer_tpu.render.model import RenderSpec
    from omero_ms_pixel_buffer_tpu.render.supertile import (
        BurstHint,
        assign_supertiles,
    )
    from omero_ms_pixel_buffer_tpu.tile_ctx import RegionDef, TileCtx

    assert len(jax.devices()) >= n_devices, (
        f"child got {len(jax.devices())} devices, wanted {n_devices}"
    )
    path = build_render_fixture(cache_dir, size, depth=depth)
    registry = ImageRegistry()
    registry.add(1, path)
    params = {
        "c": "1|0:4095$FF0000,2|0:4095$00FF00,3|0:4095$0000FF",
    }
    if depth > 1:
        params["p"] = f"intmax|0:{depth - 1}"
    spec = RenderSpec.from_params(params)
    hint = BurstHint(tile, tile)
    max_pixels = (grid * tile) ** 2

    def burst_ctxs():
        return [
            TileCtx(
                image_id=1, z=0, c=0, t=0,
                region=RegionDef(col * tile, row * tile, tile, tile),
                format="png", omero_session_key="bench", render=spec,
                burst=hint,
            )
            for row in range(grid) for col in range(grid)
        ]

    def stamped():
        ctxs = burst_ctxs()
        assign_supertiles(ctxs, max_pixels=max_pixels)
        return ctxs

    service = PixelsService(registry)
    result = {"n_devices": n_devices}
    try:
        def make_pipe(supertile_mesh, width):
            pipe = TilePipeline(
                service, engine="device", device_deflate=True,
                buckets=(tile,), supertile_mesh=supertile_mesh,
            )
            if width is None:
                pipe.mesh = None
            else:
                pipe.mesh = make_mesh(
                    ("data",), devices=jax.devices()[:width]
                )
            return pipe

        # single-device reference: independent tiles AND the fused
        # single-device program — the two identity anchors
        p_single = make_pipe(True, None)
        ref_ind = [p_single.handle(c) for c in burst_ctxs()]
        ref_fused = p_single.handle_batch(stamped())

        # fused over the mesh: ONE sharded gather+project+composite+
        # carve+deflate program per super-tile group
        p_fused = make_pipe(True, n_devices)
        fused_out = p_fused.handle_batch(stamped())
        st_dispatch = p_fused.last_mesh_dispatch or {}
        result["identical"] = bool(
            fused_out == ref_fused == ref_ind
            and st_dispatch.get("tag") == "supertile"
            and st_dispatch.get("executed")
        )

        # comparator: same mesh, fusion off — each tile rides the
        # per-lane sharded render path (the pre-fusion decision-table
        # row this PR deletes: "serving mesh active -> no fusion")
        p_lane = make_pipe(False, n_devices)
        lane_out = p_lane.handle_batch(stamped())
        if lane_out != ref_ind:
            result["identical"] = False

        n_tiles = grid * grid
        t0 = _t.perf_counter()
        for _ in range(rounds):
            assert all(
                b is not None for b in p_lane.handle_batch(stamped())
            )
        lane_tps = rounds * n_tiles / (_t.perf_counter() - t0)
        t0 = _t.perf_counter()
        for _ in range(rounds):
            assert all(
                b is not None for b in p_fused.handle_batch(stamped())
            )
        fused_tps = rounds * n_tiles / (_t.perf_counter() - t0)
        result.update({
            "fused_mesh_tiles_per_sec": round(fused_tps, 2),
            "per_lane_sharded_tiles_per_sec": round(lane_tps, 2),
            "speedup": round(fused_tps / max(lane_tps, 1e-9), 3),
        })
        for p in (p_single, p_fused, p_lane):
            p.close()
    finally:
        service.close()
    print("MESH_FUSION_CHILD " + json.dumps(result), flush=True)


def _bench_burst_programs(
    n_tiles: int = 100, stagger_ms: float = 3.0
) -> dict:
    """100-tile zoom burst through the REAL batcher (no jax): lanes
    arrive staggered past the 2ms coalesce window, so without
    continuation nearly every lane is its own device program; with the
    burst-continuation key the windows chain. handle_batch call count
    is the device-program proxy."""
    from omero_ms_pixel_buffer_tpu.auth.omero_session import (
        AllowListValidator,
    )
    from omero_ms_pixel_buffer_tpu.dispatch.batcher import (
        BatchingTileWorker,
    )
    from omero_ms_pixel_buffer_tpu.render.model import RenderSpec
    from omero_ms_pixel_buffer_tpu.render.supertile import BurstHint
    from omero_ms_pixel_buffer_tpu.tile_ctx import RegionDef, TileCtx
    from omero_ms_pixel_buffer_tpu.utils.config import (
        BurstContinuationConfig,
    )

    spec = RenderSpec.from_params({"c": "1|0:4095$FF0000"})
    hint = BurstHint(64, 64)

    class _Counting:
        def __init__(self):
            self.programs = 0

        def handle(self, ctx):
            return b"x"

        def handle_batch(self, ctxs):
            self.programs += 1
            return [b"x"] * len(ctxs)

    def run(bc) -> int:
        counting = _Counting()
        worker = BatchingTileWorker(
            counting, AllowListValidator(), max_batch=32,
            coalesce_window_ms=2.0, workers=1, burst_continuation=bc,
        )

        async def go():
            await worker.start()
            sends = []
            for i in range(n_tiles):
                sends.append(asyncio.ensure_future(worker.handle(
                    TileCtx(
                        image_id=1, z=0, c=0, t=0,
                        region=RegionDef(
                            64 * (i % 10), 64 * (i // 10), 64, 64
                        ),
                        format="png", omero_session_key="bench",
                        render=spec, burst=hint,
                    )
                )))
                await asyncio.sleep(stagger_ms / 1000.0)
            out = await asyncio.gather(*sends)
            await worker.close()
            assert all(t == b"x" for t, _ in out)

        loop = asyncio.new_event_loop()
        try:
            loop.run_until_complete(go())
        finally:
            loop.close()
        return counting.programs

    on = run(BurstContinuationConfig(enabled=True, window_ms=50.0))
    off = run(None)
    return {
        "tiles": n_tiles,
        "continuation_on_programs": on,
        "continuation_off_programs": off,
    }


def bench_mesh_fusion(
    cache_dir: str, engine: str, size: int = 1024, tile: int = 64,
    grid: int = 4, rounds: int = 3, depth: int = 4, n_devices: int = 8,
) -> dict:
    """Mesh-fusion plane (r23) section, two halves:

    - **mesh**: the bench_supertile burst (4x4 adjacent 64px tiles,
      3-channel intmax z-projection) over an 8-chip mesh, fused
      (``supertile_mesh=True`` — one sharded
      gather+project+composite+carve+deflate program) vs the per-lane
      sharded path the mesh used before this PR
      (``supertile_mesh=False`` — every tile its own gather/projection,
      only the encode sharded). The driver env pins exactly one real
      chip and tests alone force virtual devices, so this half re-execs
      a subprocess on a virtual 8-device CPU platform — ratios on
      virtual chips are work-count ratios, which is what the pin
      guards.
    - **burst**: programs-per-100-tile-zoom through the real batcher
      with burst continuation on vs off (in-process, no jax).

    Pins (CI smoke fails on any):
    ``mesh_ok_fusion_identity`` — fused-mesh bytes == single-device
    fused == independent tiles, with the dispatch tagged "supertile";
    ``mesh_ok_fusion_speedup`` — fused >= 2x per-lane-sharded tiles/s;
    ``mesh_ok_burst_programs`` — continuation serves the zoom in
    <= 1/4 the programs."""
    import re

    out: dict = {}
    try:
        out["burst"] = _bench_burst_programs()
        on = out["burst"]["continuation_on_programs"]
        off = out["burst"]["continuation_off_programs"]
        out["mesh_ok_burst_programs"] = bool(on * 4 <= off)
        log(f"[mesh_fusion] burst: {out['burst']}")
    except Exception as e:
        out["burst"] = {"error": f"{type(e).__name__}: {e}"}
        out["mesh_ok_burst_programs"] = False
        log(f"[mesh_fusion] burst failed: {e!r}")

    try:
        env = dict(os.environ)
        env["_OMPB_MESH_FUSION_ARGS"] = json.dumps({
            "cache_dir": cache_dir, "size": size, "tile": tile,
            "grid": grid, "rounds": rounds, "depth": depth,
            "n": n_devices,
        })
        # replace (not merely add) any ambient device-count flag; the
        # child is a virtual-device CPU run by construction
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+",
            "",
            env.get("XLA_FLAGS", ""),
        )
        env["XLA_FLAGS"] = (
            flags
            + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
        env["JAX_PLATFORMS"] = "cpu"
        repo = os.path.dirname(os.path.abspath(__file__))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        code = "import bench; bench._mesh_fusion_child()"
        proc = subprocess.run(
            [sys.executable, "-c", code], env=env, cwd=repo,
            capture_output=True, text=True, timeout=1200,
        )
        if proc.stderr:
            log(proc.stderr.rstrip())
        marker = next(
            (
                line[len("MESH_FUSION_CHILD "):]
                for line in proc.stdout.splitlines()
                if line.startswith("MESH_FUSION_CHILD ")
            ),
            None,
        )
        if proc.returncode != 0 or marker is None:
            raise RuntimeError(
                f"mesh child rc={proc.returncode}, no result marker"
            )
        out["mesh"] = json.loads(marker)
        out["mesh_ok_fusion_identity"] = bool(
            out["mesh"].get("identical")
        )
        out["mesh_ok_fusion_speedup"] = bool(
            (out["mesh"].get("speedup") or 0) >= 2.0
        )
        log(f"[mesh_fusion] mesh: {out['mesh']}")
    except Exception as e:
        out["mesh"] = {"error": f"{type(e).__name__}: {e}"}
        out["mesh_ok_fusion_identity"] = False
        out["mesh_ok_fusion_speedup"] = False
        log(f"[mesh_fusion] mesh failed: {e!r}")
    return out


def bench_analysis(
    cache_dir: str, engine: str, size: int = 2048, n: int = 64
) -> dict:
    """Analysis plane (render/analysis + render/masks): histogram
    tiles/s host vs the headline engine — with the integer-identity
    pin ``analysis_ok_hist_identical`` (same ctx, byte-identical JSON
    across engines) — and the masked-render overhead ratio
    (``analysis_ok_masked_overhead``: ROI compositing must stay a
    small multiple of the plain render, since rasters are cached per
    (shape-set, region))."""
    import time as _t

    from omero_ms_pixel_buffer_tpu.io.pixels_service import (
        ImageRegistry,
        PixelsService,
    )
    from omero_ms_pixel_buffer_tpu.models.tile_pipeline import TilePipeline
    from omero_ms_pixel_buffer_tpu.render.analysis import HistogramSpec
    from omero_ms_pixel_buffer_tpu.render.model import RenderSpec
    from omero_ms_pixel_buffer_tpu.tile_ctx import RegionDef, TileCtx

    path = build_render_fixture(cache_dir, size)
    registry = ImageRegistry()
    registry.add(1, path)
    hspec = HistogramSpec.from_params({"bins": "256", "c": "1,2,3"})
    rng = np.random.default_rng(41)
    ctxs = []
    for _ in range(n):
        x = int(rng.integers(0, (size - 512) // 64)) * 64
        y = int(rng.integers(0, (size - 512) // 64)) * 64
        ctxs.append(TileCtx(
            image_id=1, z=0, c=0, t=0,
            region=RegionDef(x, y, 512, 512), format="json",
            omero_session_key="bench", analysis=hspec,
        ))
    out: dict = {}
    bodies: dict = {}
    engines = ["host"] if engine == "host" else ["host", engine]
    for label in engines:
        service = PixelsService(registry)
        try:
            pipe = TilePipeline(service, engine=label, buckets=(512,))
            warm = pipe.handle_batch(ctxs[:8])
            assert all(w is not None for w in warm)
            bodies[label] = pipe.handle_batch([ctxs[0]])[0]
            tps = run_batched(pipe, ctxs, 16)
            out[label] = {"hist_tiles_per_sec": round(tps, 2)}
            log(f"[analysis] {label}: {out[label]}")
            pipe.close()
        except Exception as e:
            out[label] = {"error": f"{type(e).__name__}: {e}"}
            log(f"[analysis] {label} failed: {e!r}")
        finally:
            service.close()
    vals = [b for b in bodies.values() if b is not None]
    out["analysis_ok_hist_identical"] = (
        len(vals) == len(engines) and all(v == vals[0] for v in vals)
    )

    # masked-render overhead: the same tile set rendered plain vs
    # with a 3-shape ROI union (host engine — masked lanes serve
    # through the host mirror), warm raster cache
    roi = (
        '[{"type":"rect","x":64,"y":64,"w":320,"h":320},'
        '{"type":"ellipse","cx":256,"cy":256,"rx":200,"ry":140},'
        '{"type":"polygon","points":[[0,0],[500,40],[260,500]]}]'
    )
    plain = RenderSpec.from_params({"c": "1|0:4095$FF0000"})
    masked = RenderSpec.from_params(
        {"c": "1|0:4095$FF0000", "roi": roi}
    )
    service = PixelsService(registry)
    try:
        pipe = TilePipeline(service, engine="host", buckets=(512,))

        def render_ctxs(spec):
            return [TileCtx(
                image_id=1, z=0, c=0, t=0,
                region=RegionDef(c.region.x, c.region.y, 512, 512),
                format="png", omero_session_key="bench", render=spec,
            ) for c in ctxs[:24]]

        for spec in (plain, masked):  # warm reads + tables + rasters
            assert all(
                r is not None
                for r in pipe.handle_batch(render_ctxs(spec)[:8])
            )
        times = {}
        for key, spec in (("plain", plain), ("masked", masked)):
            rcs = render_ctxs(spec)
            t0 = _t.perf_counter()
            res = pipe.handle_batch(rcs)
            assert all(r is not None for r in res)
            times[key] = _t.perf_counter() - t0
        ratio = times["masked"] / max(times["plain"], 1e-9)
        out["masked_overhead_ratio"] = round(ratio, 3)
        out["analysis_ok_masked_overhead"] = ratio <= 3.0
        log(
            f"[analysis] masked overhead {ratio:.2f}x "
            f"(plain {times['plain']*1000:.0f}ms, "
            f"masked {times['masked']*1000:.0f}ms)"
        )
        pipe.close()
    except Exception as e:
        out["masked_error"] = f"{type(e).__name__}: {e}"
        out["analysis_ok_masked_overhead"] = False
        log(f"[analysis] masked bench failed: {e!r}")
    finally:
        service.close()
    return out


def main():
    t_setup = time.perf_counter()
    from omero_ms_pixel_buffer_tpu.io.pixels_service import (
        ImageRegistry,
        PixelsService,
    )
    from omero_ms_pixel_buffer_tpu.models.tile_pipeline import TilePipeline

    cache_dir = os.environ.get(
        "BENCH_CACHE", os.path.join(tempfile.gettempdir(), "ompb_bench")
    )
    os.makedirs(cache_dir, exist_ok=True)
    size = int(os.environ.get("BENCH_IMAGE_SIZE", "8192"))
    n_requests = int(os.environ.get("BENCH_REQUESTS", "1024"))
    batch = int(os.environ.get("BENCH_BATCH", "32"))
    path = build_fixture(cache_dir, size)

    registry = ImageRegistry()
    registry.add(1, path)

    # --- baseline: reference-architecture path (sequential, host) -----
    # Separate service with the decoded-block cache OFF: the reference
    # re-opens and re-decodes per request (TileRequestHandler.java:86),
    # so its stand-in must too. Python (not native) encode, one at a
    # time, single worker — the Java worker-thread shape.
    base_service = PixelsService(registry, block_cache_bytes=0)
    base_pipe = TilePipeline(
        base_service, use_device=False, encode_workers=1,
        png_level=6, png_strategy="default",  # Java Deflater defaults
    )
    base_ctxs = make_ctxs(64, size)
    for ctx in base_ctxs[:4]:  # warm page cache + code paths
        assert base_pipe.handle(ctx) is not None
    t0 = time.perf_counter()
    for ctx in base_ctxs:
        out = base_pipe.handle(ctx)
        assert out is not None
    host_tps = len(base_ctxs) / (time.perf_counter() - t0)
    log(f"baseline (sequential host path): {host_tps:.1f} tiles/s")

    # --- framework batched path (auto engine) -------------------------
    probe_info = jax_backend_info()
    log(f"jax: {probe_info}")
    service = PixelsService(registry)
    engine = os.environ.get("BENCH_ENGINE", "auto")
    if engine in ("device", "tpu") and probe_info.get("platform") != "tpu":
        log(
            f"engine '{engine}' requested but probe says "
            f"{probe_info}; falling back to host"
        )
        engine = "host"
    ctxs = make_ctxs(n_requests, size, seed=9)
    try:
        pipe = TilePipeline(service, engine=engine, buckets=(512,))
        # warmup: resolve auto engine, trigger jit/native build
        warm = pipe.handle_batch(ctxs[:batch])
        assert all(w is not None for w in warm)
    except Exception as e:
        # an explicitly-requested device engine on a wedged TPU must
        # still produce a headline number — re-run on the host engine
        log(f"engine '{engine}' failed ({e!r}); falling back to host")
        engine = "host"
        pipe = TilePipeline(service, engine="host", buckets=(512,))
        warm = pipe.handle_batch(ctxs[:batch])
        assert all(w is not None for w in warm)
    log(f"engine: {pipe.engine}")
    tpu_tps = run_batched(pipe, ctxs, batch)
    log(
        f"batched path ({pipe.engine}): {tpu_tps:.1f} tiles/s over "
        f"{len(ctxs)} tiles (setup+warmup "
        f"{time.perf_counter() - t_setup:.1f}s total elapsed)"
    )

    # --- full-stack HTTP latency --------------------------------------
    http_stats: dict = {}
    if os.environ.get("BENCH_HTTP", "1") != "0":
        try:
            http_stats = bench_http(
                path,
                int(os.environ.get("BENCH_HTTP_REQUESTS", "512")),
                int(os.environ.get("BENCH_HTTP_CONCURRENCY", "64")),
                engine=pipe.engine,  # probe-gated, never re-read from env
            )
            log(f"full-stack http: {http_stats}")
        except Exception as e:
            # namespaced: a top-level "error" key means total failure
            http_stats = {"http_error": f"{type(e).__name__}: {e}"}
            log(f"http bench failed: {e!r}")

    # --- cache warm-pass: repeated-tile serving (hit ratio + p50/p99
    # delta; identical bytes is the correctness bar) -------------------
    cache_stats: dict = {}
    if os.environ.get("BENCH_CACHE_PASS", "1") != "0":
        try:
            cache_stats = bench_cache(
                path,
                int(os.environ.get("BENCH_CACHE_TILES", "192")),
                int(os.environ.get("BENCH_CACHE_CONCURRENCY", "1")),
                engine=pipe.engine,  # probe-gated, never re-read
            )
            log(f"cache warm pass: {cache_stats}")
        except Exception as e:
            cache_stats = {"error": f"{type(e).__name__}: {e}"}
            log(f"cache bench failed: {e!r}")

    # --- cache plane (r11): warm-restart hit rate, L2 round trip,
    # two-replica render-once ------------------------------------------
    plane_stats: dict = {}
    if os.environ.get("BENCH_CACHE_PLANE", "1") != "0":
        try:
            plane_stats = bench_cache_plane(path, cache_dir)
            log(f"cache plane: {plane_stats}")
        except Exception as e:
            plane_stats = {"error": f"{type(e).__name__}: {e}"}
            log(f"cache plane bench failed: {e!r}")

    # --- sustained-overload SLO scenario (r13): mixed-class closed-
    # loop load at ~2x admission capacity against the deadline-ordered
    # scheduler; asserts SLO outcomes (slo_ok_* pins), not throughput
    overload_stats: dict = {}
    if os.environ.get("BENCH_OVERLOAD", "1") != "0":
        try:
            overload_stats = bench_overload(
                cache_dir,
                duration_s=float(
                    os.environ.get("BENCH_OVERLOAD_S", "4")
                ),
            )
            log(f"overload: {overload_stats}")
        except Exception as e:
            overload_stats = {"error": f"{type(e).__name__}: {e}"}
            log(f"overload bench failed: {e!r}")

    # --- cluster coordination plane (r17): owner-kill failover on the
    # replicated hot set, join-time warm-up, hedged vs unhedged peer
    # p99 (cluster_ok_* pins)
    cluster_stats: dict = {}
    if os.environ.get("BENCH_CLUSTER", "1") != "0":
        try:
            cluster_stats = bench_cluster(cache_dir)
            log(f"cluster: {cluster_stats}")
        except Exception as e:
            cluster_stats = {"error": f"{type(e).__name__}: {e}"}
            log(f"cluster bench failed: {e!r}")

    # --- fleet lifecycle plane (r18): rolling restart under traffic
    # (graceful drain + handoff + join warm-up) and anti-entropy
    # repair convergence (cluster_ok_drain_zero_errors /
    # cluster_ok_repair_convergence pins)
    lifecycle_stats: dict = {}
    if os.environ.get("BENCH_LIFECYCLE", "1") != "0":
        try:
            lifecycle_stats = bench_lifecycle(cache_dir)
            log(f"lifecycle: {lifecycle_stats}")
        except Exception as e:
            lifecycle_stats = {"error": f"{type(e).__name__}: {e}"}
            log(f"lifecycle bench failed: {e!r}")

    # --- decentralized control plane (r20): gossip membership through
    # a Redis outage + corrupt-replica demotion via integrity verdicts
    # (cluster_ok_redisless_convergence /
    # cluster_ok_integrity_demotion pins)
    decentralized_stats: dict = {}
    if os.environ.get("BENCH_DECENTRALIZED", "1") != "0":
        try:
            decentralized_stats = bench_decentralized(cache_dir)
            log(f"decentralized: {decentralized_stats}")
        except Exception as e:
            decentralized_stats = {"error": f"{type(e).__name__}: {e}"}
            log(f"decentralized bench failed: {e!r}")

    # --- interactive session plane (r22): cross-replica delta push
    # latency over a live channel + rolling drain with channel handoff
    # (session_ok_push_latency / session_ok_drain_zero_drops pins)
    session_stats: dict = {}
    if os.environ.get("BENCH_SESSION", "1") != "0":
        try:
            session_stats = bench_session(cache_dir)
            log(f"session: {session_stats}")
        except Exception as e:
            session_stats = {"error": f"{type(e).__name__}: {e}"}
            log(f"session bench failed: {e!r}")

    # --- ingest plane (r24): read p99 under concurrent writes +
    # write-to-fresh-read staleness (ingest_ok_* pins) -----------------
    ingest_stats: dict = {}
    if os.environ.get("BENCH_INGEST", "1") != "0":
        try:
            ingest_stats = bench_ingest(cache_dir)
            log(f"ingest: {ingest_stats}")
        except Exception as e:
            ingest_stats = {"error": f"{type(e).__name__}: {e}"}
            log(f"ingest bench failed: {e!r}")

    # --- batched read plane (r14): cold remote reads over a loopback
    # HTTP object store — sequential vs parallel+coalesced, sharded
    # byte identity, requests-per-tile (io_ok_* pins)
    io_stats: dict = {}
    if os.environ.get("BENCH_IO", "1") != "0":
        try:
            io_stats = bench_io(cache_dir)
            log(f"io read plane: {io_stats}")
        except Exception as e:
            io_stats = {"error": f"{type(e).__name__}: {e}"}
            log(f"io bench failed: {e!r}")

    # --- observability plane (r16): flight-recorder warm-path
    # overhead A/B + forced-slow tail capture (obs_ok_* pins) ----------
    obs_stats: dict = {}
    if os.environ.get("BENCH_OBS", "1") != "0":
        try:
            obs_stats = bench_obs(cache_dir)
            log(f"obs: {obs_stats}")
        except Exception as e:
            obs_stats = {"error": f"{type(e).__name__}: {e}"}
            log(f"obs bench failed: {e!r}")

    # --- rendered-tile serving (render/): host vs headline engine ----
    render_stats: dict = {}
    if os.environ.get("BENCH_RENDER", "1") != "0":
        try:
            render_stats = bench_render(cache_dir, pipe.engine)
        except Exception as e:
            render_stats = {"error": f"{type(e).__name__}: {e}"}
            log(f"render bench failed: {e!r}")

    # --- analysis plane (r15): histogram throughput host vs engine +
    # masked-render overhead (analysis_ok_* pins) ----------------------
    analysis_stats: dict = {}
    if os.environ.get("BENCH_ANALYSIS", "1") != "0":
        try:
            analysis_stats = bench_analysis(cache_dir, pipe.engine)
        except Exception as e:
            analysis_stats = {"error": f"{type(e).__name__}: {e}"}
            log(f"analysis bench failed: {e!r}")

    # --- super-tile plane (r19): 4x4 DZI-row projection burst fused
    # vs independent (supertile_ok_speedup >= 2x +
    # supertile_ok_identical pins) -------------------------------------
    supertile_stats: dict = {}
    if os.environ.get("BENCH_SUPERTILE", "1") != "0":
        try:
            supertile_stats = bench_supertile(cache_dir, pipe.engine)
            log(f"supertile: {supertile_stats}")
        except Exception as e:
            supertile_stats = {"error": f"{type(e).__name__}: {e}"}
            log(f"supertile bench failed: {e!r}")

    # --- mesh-fusion plane (r23): fused-mesh vs per-lane-sharded
    # super-tile burst + programs-per-zoom with burst continuation
    # (mesh_ok_* pins) -------------------------------------------------
    mesh_fusion_stats: dict = {}
    if os.environ.get("BENCH_MESH_FUSION", "1") != "0":
        try:
            mesh_fusion_stats = bench_mesh_fusion(cache_dir, pipe.engine)
            log(f"mesh_fusion: {mesh_fusion_stats}")
        except Exception as e:
            mesh_fusion_stats = {"error": f"{type(e).__name__}: {e}"}
            log(f"mesh_fusion bench failed: {e!r}")

    if os.environ.get("BENCH_SUBS", "1") != "0":
        try:
            sub_benches(pipe, service, size, cache_dir)
        except Exception as e:
            log(f"sub-benches failed: {e!r}")

    record = {
        "metric": "tiles_per_sec_512x512_uint16_png",
        "value": round(tpu_tps, 2),
        "unit": "tiles/s",
        "vs_baseline": round(tpu_tps / host_tps, 3),
        "engine": pipe.engine,
        "baseline_tiles_per_sec": round(host_tps, 2),
    }
    record.update(
        {k: v for k, v in http_stats.items() if k != "engine"}
    )
    if cache_stats:
        record["cache"] = cache_stats
    if plane_stats:
        record["cache_plane"] = plane_stats
    if cluster_stats:
        record["cluster"] = cluster_stats
    if lifecycle_stats:
        record["lifecycle"] = lifecycle_stats
    if decentralized_stats:
        record["decentralized"] = decentralized_stats
    if session_stats:
        record["session"] = session_stats
    if ingest_stats:
        record["ingest"] = ingest_stats
    if overload_stats:
        record["overload"] = overload_stats
    if io_stats:
        record["io"] = io_stats
    if obs_stats:
        record["obs"] = obs_stats
    if render_stats:
        record["render"] = render_stats
    if analysis_stats:
        record["analysis"] = analysis_stats
    if supertile_stats:
        record["supertile"] = supertile_stats
    if mesh_fusion_stats:
        record["mesh_fusion"] = mesh_fusion_stats
    # explicit host-vs-device table so the next round can read WHICH
    # engine/stage moved without diffing nested sections
    comparison = {
        "sequential_host": round(host_tps, 2),
        f"batched_{pipe.engine}": round(tpu_tps, 2),
    }
    for label, stats in render_stats.items():
        if isinstance(stats, dict) and "tiles_per_sec" in stats:
            comparison[f"render_{label}"] = stats["tiles_per_sec"]
    for label, stats in analysis_stats.items():
        if isinstance(stats, dict) and "hist_tiles_per_sec" in stats:
            comparison[f"hist_{label}"] = stats["hist_tiles_per_sec"]
    if "masked_overhead_ratio" in analysis_stats:
        comparison["masked_overhead_ratio"] = (
            analysis_stats["masked_overhead_ratio"]
        )
    for label, stats in supertile_stats.items():
        if isinstance(stats, dict) and "fused_tiles_per_sec" in stats:
            comparison[f"supertile_fused_{label}"] = (
                stats["fused_tiles_per_sec"]
            )
            comparison[f"supertile_independent_{label}"] = (
                stats["independent_tiles_per_sec"]
            )
    mesh_half = mesh_fusion_stats.get("mesh") or {}
    if "fused_mesh_tiles_per_sec" in mesh_half:
        comparison["mesh_fused_tiles_per_sec"] = (
            mesh_half["fused_mesh_tiles_per_sec"]
        )
        comparison["mesh_per_lane_sharded_tiles_per_sec"] = (
            mesh_half["per_lane_sharded_tiles_per_sec"]
        )
    burst_half = mesh_fusion_stats.get("burst") or {}
    if "continuation_on_programs" in burst_half:
        comparison["burst_programs_continuation_on"] = (
            burst_half["continuation_on_programs"]
        )
        comparison["burst_programs_continuation_off"] = (
            burst_half["continuation_off_programs"]
        )
    if io_stats and "parallel" in io_stats:
        comparison["io_cold_sequential_tiles_per_sec"] = (
            io_stats["sequential"]["tiles_per_sec"]
        )
        comparison["io_cold_parallel_tiles_per_sec"] = (
            io_stats["parallel"]["tiles_per_sec"]
        )
        comparison["io_requests_per_tile"] = (
            io_stats["parallel"]["requests_per_tile"]
        )
        comparison["io_coalesced_ratio"] = (
            io_stats["parallel"]["coalesced_ratio"]
        )
    if overload_stats and "interactive" in overload_stats:
        comparison["slo_interactive_p99_ms"] = (
            overload_stats["interactive"]["p99_ms"]
        )
        comparison["slo_interactive_degraded_fraction"] = (
            overload_stats["interactive"]["degraded_fraction"]
        )
    if obs_stats and "warm_p50_penalty" in obs_stats:
        comparison["obs_warm_p50_penalty"] = (
            obs_stats["warm_p50_penalty"]
        )
    if cluster_stats and "failover" in cluster_stats:
        comparison["cluster_failover_hit_rate"] = (
            cluster_stats["failover"]["replicated"]["hit_rate"]
        )
        comparison["cluster_failover_hit_rate_unreplicated"] = (
            cluster_stats["failover"]["unreplicated"]["hit_rate"]
        )
        comparison["cluster_join_warm_s"] = (
            cluster_stats["join"]["join_to_90pct_warm_s"]
        )
        comparison["cluster_hedged_peer_p99_ms"] = (
            cluster_stats["hedge"]["hedged"]["p99_ms"]
        )
        comparison["cluster_unhedged_peer_p99_ms"] = (
            cluster_stats["hedge"]["unhedged"]["p99_ms"]
        )
    if lifecycle_stats and "rolling_restart" in lifecycle_stats:
        comparison["cluster_drain_serving_errors"] = (
            lifecycle_stats["rolling_restart"]["serving_errors"]
        )
        comparison["cluster_drain_warm_hit_rate"] = (
            lifecycle_stats["rolling_restart"]["warm_hit_rate"]
        )
        comparison["cluster_repair_rounds_to_converge"] = (
            lifecycle_stats["repair"]["rounds_to_converge"]
        )
    if decentralized_stats and "redisless" in decentralized_stats:
        comparison["cluster_redisless_warm_hit_rate"] = (
            decentralized_stats["redisless"][
                "post_outage_warm_hit_rate"
            ]
        )
        comparison["cluster_integrity_rounds_to_demote"] = (
            decentralized_stats["integrity"]["rounds_to_demote"]
        )
    if session_stats and "push" in session_stats:
        comparison["session_push_p99_ms"] = (
            session_stats["push"]["p99_ms"]
        )
        comparison["session_drain_reconnects"] = (
            session_stats["drain"]["reconnect_frames"]
        )
        comparison["session_drain_serving_errors"] = (
            session_stats["drain"]["serving_errors"]
        )
    if ingest_stats and "concurrent_read_p99_ms" in ingest_stats:
        comparison["ingest_read_p99_ms"] = (
            ingest_stats["concurrent_read_p99_ms"]
        )
        comparison["ingest_baseline_read_p99_ms"] = (
            ingest_stats["baseline_read_p99_ms"]
        )
        comparison["ingest_stale_first_reads"] = (
            ingest_stats["stale_first_reads"]
        )
    record["engine_comparison"] = comparison
    print(json.dumps(record))


def sub_benches(pipe, service, size, cache_dir):
    """The remaining BASELINE.md measurement-matrix configs, scaled to
    bench-friendly sizes; stderr only (the driver consumes stdout)."""
    import time as _t

    from omero_ms_pixel_buffer_tpu.io.ometiff import write_ome_tiff
    from omero_ms_pixel_buffer_tpu.io.pixels_service import (
        ImageRegistry,
        PixelsService,
    )
    from omero_ms_pixel_buffer_tpu.models.tile_pipeline import TilePipeline
    from omero_ms_pixel_buffer_tpu.runtime.native import get_engine

    rng = np.random.default_rng(3)

    # -- config 2: random 256x256 replay, format=raw -------------------
    ctxs = make_ctxs(256, size, tile=256, fmt=None, seed=13)
    pipe.handle_batch(ctxs[:32])
    t0 = _t.perf_counter()
    for i in range(0, len(ctxs), 32):
        results = pipe.handle_batch(ctxs[i : i + 32])
        assert all(r is not None for r in results)
    log(f"[sub] raw 256x256 replay: "
        f"{len(ctxs) / (_t.perf_counter() - t0):.1f} tiles/s")

    # -- config 3: multi-Z stack, PNG coalesced across Z ---------------
    zpath = os.path.join(cache_dir, "bench_z8.ome.tiff")
    if not os.path.exists(zpath):
        zdata = rng.integers(
            0, 60000, (1, 1, 8, 1024, 1024), dtype=np.uint16
        )
        write_ome_tiff(zpath, zdata, tile_size=(512, 512),
                       compression="zlib")
    registry = ImageRegistry()
    registry.add(2, zpath)
    zservice = PixelsService(registry)
    zpipe = TilePipeline(zservice, engine=pipe.engine)
    from omero_ms_pixel_buffer_tpu.tile_ctx import RegionDef, TileCtx

    zctxs = [
        TileCtx(image_id=2, z=z, c=0, t=0,
                region=RegionDef(256, 256, 512, 512), format="png",
                omero_session_key="bench")
        for z in range(8)
    ] * 8  # 64 requests coalescing across the Z axis
    zpipe.handle_batch(zctxs[:16])
    t0 = _t.perf_counter()
    for i in range(0, len(zctxs), 32):
        results = zpipe.handle_batch(zctxs[i : i + 32])
        assert all(r is not None for r in results)
    log(f"[sub] multi-Z 512x512 png (coalesced): "
        f"{len(zctxs) / (_t.perf_counter() - t0):.1f} tiles/s")
    zservice.close()

    # -- config 4 (scaled): RGB8 256x256 encode sweep ------------------
    engine = get_engine()
    if engine is not None:
        rgb = [
            rng.integers(0, 255, (256, 256, 3), dtype=np.uint8)
            for _ in range(64)
        ]
        engine.png_encode_batch(rgb[:8], "up", 6, strategy="fast")
        t0 = _t.perf_counter()
        out = engine.png_encode_batch(rgb, "up", 6, strategy="fast")
        assert all(o is not None for o in out)
        log(f"[sub] rgb8 256x256 png encode: "
            f"{len(rgb) / (_t.perf_counter() - t0):.1f} tiles/s")

    # -- config 4b: JPEG whole-slide RGB pyramid, 256x256 png sweep ----
    # (the actual config-4 storage: JPEG-compressed tiled RGB TIFF,
    # read through the in-tree baseline decoder, served as PNG)
    jpath = os.path.join(cache_dir, "bench_rgb_jpeg.ome.tiff")
    if not os.path.exists(jpath):
        yy, xx = np.mgrid[0:2048, 0:2048].astype(np.float32)
        base = (
            128 + 60 * np.sin(xx / 37) + 50 * np.cos(yy / 53)
            + rng.normal(0, 8, (2048, 2048))
        ).clip(0, 255).astype(np.uint8)
        rgbdata = np.stack(
            [base, np.roll(base, 11, 0), np.roll(base, 7, 1)], -1
        )
        write_ome_tiff(
            jpath, rgbdata[None, None, None], tile_size=(256, 256),
            compression="jpeg", pyramid_levels=2,
        )
    jreg = ImageRegistry()
    jreg.add(3, jpath)
    jsvc = PixelsService(jreg)
    jpipe = TilePipeline(jsvc, engine=pipe.engine)
    from omero_ms_pixel_buffer_tpu.tile_ctx import RegionDef as _RD
    from omero_ms_pixel_buffer_tpu.tile_ctx import TileCtx as _TC

    jctxs = []
    for _ in range(128):
        x = int(rng.integers(0, (2048 - 256) // 64)) * 64
        y = int(rng.integers(0, (2048 - 256) // 64)) * 64
        jctxs.append(
            _TC(image_id=3, z=0, c=int(rng.integers(0, 3)), t=0,
                region=_RD(x, y, 256, 256), format="png",
                omero_session_key="bench")
        )
    jpipe.handle_batch(jctxs[:16])
    t0 = _t.perf_counter()
    for i in range(0, len(jctxs), 32):
        results = jpipe.handle_batch(jctxs[i : i + 32])
        assert all(r is not None for r in results)
    log(f"[sub] jpeg-rgb 256x256 png sweep: "
        f"{len(jctxs) / (_t.perf_counter() - t0):.1f} tiles/s")
    jsvc.close()

    # -- config 5 (scaled): concurrent format=tif fan-out --------------
    tctxs = make_ctxs(128, size, tile=512, fmt="tif", seed=17)
    pipe.handle_batch(tctxs[:16])
    t0 = _t.perf_counter()
    for i in range(0, len(tctxs), 32):
        results = pipe.handle_batch(tctxs[i : i + 32])
        assert all(r is not None for r in results)
    log(f"[sub] tif 512x512 fan-out: "
        f"{len(tctxs) / (_t.perf_counter() - t0):.1f} tiles/s")


if __name__ == "__main__":
    main()
