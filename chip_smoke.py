#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the tile path runs on the chip.

Drives the system's main path once, through the entry point a user
calls: builds one seeded pyramidal OME-TIFF of the BASELINE headline
shape (8192x8192 uint16, 512x512 zlib tiles), starts the REAL server
(`python -m omero_ms_pixel_buffer_tpu --dev --registry ... --config ...`
with `backend.engine: device`, device deflate, plane cache on), sends
tile/render requests over the socket, and checks every response by
decoded pixels against a plain numpy crop of the seeded array.

One process on the chip: the server is the only process that
initialises a JAX backend. This script never does (fixture writing and
PIL decoding need none); it takes the device description from the
server's /healthz.

    python chip_smoke.py            # one chip; what the driver runs
    python chip_smoke.py --mesh     # four chips: the auto mesh against
                                    # a one-chip server, byte for byte

The last stdout line is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`,
printed only when every phase passed AND the server ran on a TPU. With
no accelerator the script exits non-zero and prints no result line;
rehearse with `JAX_PLATFORMS=cpu python chip_smoke.py --size 2048`
(every check but the platform one passes on the earlier lines).
"""

import argparse
import concurrent.futures
import http.client
import io
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np
from PIL import Image

REPO = os.path.dirname(os.path.abspath(__file__))
TILE = 512
COOKIE = {"Cookie": "sessionid=chip-smoke"}
MAX_BATCH = 8  # lanes pad to powers of two: a handful of shapes cold
REQUEST_TIMEOUT_S = 1100.0  # a cold shape compiles for minutes
RENDER_WINDOW = (500, 6000)


def say(msg: str) -> None:
    print(msg, flush=True)


# -- the seeded fixture and its plain numpy reference -------------------


def seeded_image(seed: int, size: int) -> np.ndarray:
    """Smooth-ish synthetic microscopy-like uint16 plane (compresses
    realistically, unlike white noise) — bench.py's headline shape."""
    rng = np.random.default_rng(seed)
    xx = np.arange(size, dtype=np.float32)[None, :]
    yy = np.arange(size, dtype=np.float32)[:, None]
    base = 2000 + 1500 * np.sin(xx / 97.0) + 1500 * np.cos(yy / 131.0)
    noise = rng.normal(0, 120, (size, size)).astype(np.float32)
    return (base + noise).clip(0, 65535).astype(np.uint16)


def write_fixture(workdir: str, image: np.ndarray, port: int) -> tuple:
    """The OME-TIFF (2 pyramid levels), registry.json and config."""
    from omero_ms_pixel_buffer_tpu.io.ometiff import write_ome_tiff

    tiff = os.path.join(workdir, "smoke.ome.tiff")
    write_ome_tiff(
        tiff, image[None, None, None], tile_size=(TILE, TILE),
        compression="zlib", pyramid_levels=2,
    )
    registry = os.path.join(workdir, "registry.json")
    with open(registry, "w") as f:
        json.dump({"images": [{"id": 1, "path": tiff}]}, f)
    config = os.path.join(workdir, "config.yaml")
    with open(config, "w") as f:
        f.write(
            f"port: {port}\n"
            # a cold shape compiles for minutes; the request deadline
            # must outlast it or the warm-up answers 504
            f"event-bus-send-timeout: {int(REQUEST_TIMEOUT_S * 1000)}\n"
            "session-store:\n"
            "  type: memory\n"
            "backend:\n"
            "  engine: device\n"
            "  png:\n"
            "    device-deflate: true\n"
            "  batching:\n"
            f"    max-batch: {MAX_BATCH}\n"
            "    coalesce-window-ms: 50.0\n"
            "cache:\n"
            "  prefetch:\n"
            "    enabled: false\n"
        )
    return registry, config


def reference_render(crop: np.ndarray) -> np.ndarray:
    """c=1|lo:hi$FF0000 in straight numpy: linear window -> 8-bit
    index -> red ramp; green and blue stay 0."""
    lo, hi = RENDER_WINDOW
    x = np.clip((crop.astype(np.float64) - lo) / (hi - lo), 0.0, 1.0)
    rgb = np.zeros(crop.shape + (3,), np.uint8)
    rgb[..., 0] = np.floor(x * 255.0 + 0.5).astype(np.uint8)
    return rgb


# -- the server: the one process that touches the chip ------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    def __init__(self, workdir, registry, config, port, env, name):
        self.port = port
        self.log_path = os.path.join(workdir, f"server-{name}.log")
        self._log = open(self.log_path, "w")
        env = dict(env)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "omero_ms_pixel_buffer_tpu", "--dev",
             "--registry", registry, "--config", config,
             "--port", str(port)],
            cwd=REPO, env=env, stdout=self._log, stderr=subprocess.STDOUT,
        )

    def get(self, path: str, timeout: float = REQUEST_TIMEOUT_S):
        conn = http.client.HTTPConnection(
            "127.0.0.1", self.port, timeout=timeout
        )
        try:
            conn.request("GET", path, headers=COOKIE)
            resp = conn.getresponse()
            return resp.status, dict(resp.getheaders()), resp.read()
        finally:
            conn.close()

    def healthz(self) -> dict:
        status, _, body = self.get("/healthz", timeout=30.0)
        if status != 200:
            raise RuntimeError(f"/healthz answered {status}")
        return json.loads(body)

    def wait_healthy(self, limit_s: float = 600.0) -> float:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < limit_s:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"server exited rc={self.proc.returncode} before "
                    f"/healthz answered:\n{self.log_tail()}"
                )
            try:
                self.healthz()
                return time.perf_counter() - t0
            except (OSError, http.client.HTTPException):
                time.sleep(0.25)
        raise RuntimeError(
            f"server not healthy after {limit_s:.0f}s:\n{self.log_tail()}"
        )

    def stage_means_ms(self) -> dict:
        """Mean ms per device-queue stage from /metrics
        (`device_stage_seconds`: stage|h2d|hist|emit|compute|d2h|
        frame), host clock."""
        _, _, body = self.get("/metrics", timeout=30.0)
        sums, counts = {}, {}
        for line in body.decode().splitlines():
            for suffix, into in (("_sum", sums), ("_count", counts)):
                head = f'device_stage_seconds{suffix}{{stage="'
                if line.startswith(head):
                    stage, value = line[len(head):].split('"} ')
                    into[stage] = float(value)
        return {
            stage: round(sums[stage] / counts[stage] * 1e3, 1)
            for stage in sorted(sums) if counts.get(stage)
        }

    def log_text(self) -> str:
        self._log.flush()
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def log_tail(self, lines: int = 80) -> str:
        return "\n".join(self.log_text().splitlines()[-lines:])

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()


# -- requests and checks ------------------------------------------------


def tile_origins(seed: int, size: int, n: int) -> list:
    """n distinct (x, y) origins on a 64-px grid, whole tile inside."""
    rng = np.random.default_rng(seed + 1)
    span = (size - TILE) // 64 + 1
    picks = rng.choice(span * span, size=n, replace=False)
    return [(int(p % span) * 64, int(p // span) * 64) for p in picks]


def tile_url(x, y, fmt=None, resolution=None) -> str:
    url = f"/tile/1/0/0/0?x={x}&y={y}&w={TILE}&h={TILE}"
    if resolution is not None:
        url += f"&resolution={resolution}"
    if fmt is not None:
        url += f"&format={fmt}"
    return url


def check_response(kind, url, status, headers, body, expected) -> list:
    """Failures of one response (empty = correct): 200, not degraded,
    and decoded pixels (PNG/TIFF) or bytes (raw) equal the numpy
    reference."""
    if status != 200:
        return [f"{kind} {url}: HTTP {status}"]
    if any(k.lower() == "x-ompb-degraded" for k in headers):
        return [f"{kind} {url}: served degraded"]
    if kind == "raw":
        good = body == expected.astype(">u2").tobytes()
    else:
        decoded = np.array(Image.open(io.BytesIO(body)))
        good = decoded.shape == expected.shape and np.array_equal(
            decoded.astype(expected.dtype), expected
        )
    return [] if good else [f"{kind} {url}: pixels differ from numpy"]


def run_requests(server, jobs, concurrency) -> tuple:
    """jobs: [(kind, url, expected)] -> (failures, bodies, seconds),
    `concurrency` requests in flight (closed loop)."""
    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(concurrency) as pool:
        replies = list(pool.map(lambda job: server.get(job[1]), jobs))
    took = time.perf_counter() - t0
    failures, bodies = [], {}
    for (kind, url, expected), (status, headers, body) in zip(
        jobs, replies
    ):
        failures += check_response(
            kind, url, status, headers, body, expected
        )
        bodies[url] = body
    return failures, bodies, took


def crop(plane, x, y):
    return plane[y : y + TILE, x : x + TILE]


def png_jobs(image, origins) -> list:
    return [
        ("png", tile_url(x, y, "png"), crop(image, x, y))
        for x, y in origins
    ]


def cache_entries() -> tuple:
    """(dir, entry count) of the compile cache the server will use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, ".jax_cache"
    )
    return path, (len(os.listdir(path)) if os.path.isdir(path) else 0)


def server_checks(server, health, want_count=None) -> list:
    """What /healthz and the log must say after the run."""
    failures = []
    if health.get("engine") != "device":
        failures.append(f"engine is {health.get('engine')!r}, not device")
    queue = health.get("device_queue") or {}
    if not queue.get("groups"):
        failures.append("device queue dispatched no group")
    if queue.get("compute_ms_mean") is None:
        failures.append("device queue has no compute_ms_mean")
    if not health.get("tile_device_lanes_total"):
        failures.append("no PNG lane was encoded on the device")
    for key in ("tile_device_fallback_total", "render_fallback_total"):
        if health.get(key) != 0:
            failures.append(f"{key} = {health.get(key)}")
    if "host fallback" in server.log_text():
        failures.append("server log holds 'host fallback'")
    device = health.get("device") or {}
    if want_count is not None and device.get("count") != want_count:
        failures.append(
            f"server saw {device.get('count')} device(s), "
            f"wanted {want_count}"
        )
    return failures


# -- one chip: the default run ------------------------------------------


def run_one_chip(args, workdir, image) -> tuple:
    port = free_port()
    registry, config = write_fixture(workdir, image, port)
    cache_dir, before = cache_entries()
    server = Server(workdir, registry, config, port, os.environ, "one")
    try:
        say(f"seconds_to_healthy: {server.wait_healthy():.1f}")
        first = server.healthz()
        say(f"device: {json.dumps(first['device'])}")
        say(
            f"auto_verdict: {first['auto_verdict']} "
            f"(link_mbps {first['link_mbps']}; this run is engine: "
            f"{first['engine']}, {first['engine_reason']})"
        )
        failures = []
        origins = tile_origins(args.seed, args.size, 16 + 64 + 8)
        warm, main, rest = origins[:16], origins[16:80], origins[80:]

        fails, _, took = run_requests(server, png_jobs(image, warm), 16)
        failures += fails
        say(f"warmup_burst_seconds: {took:.1f} (16 png tiles, compile)")

        fails, _, took = run_requests(server, png_jobs(image, main), 16)
        failures += fails
        say(f"main_burst_seconds: {took:.1f} (64 png tiles, 16 in flight)")

        level1 = image[::2, ::2]
        lx, ly = tile_origins(args.seed + 7, args.size // 2, 1)[0]
        rx, ry = rest[0]
        lo, hi = RENDER_WINDOW
        others = [
            ("tif", tile_url(x, y, "tif"), crop(image, x, y))
            for x, y in rest[:4]
        ] + [
            ("raw", tile_url(x, y), crop(image, x, y))
            for x, y in rest[4:8]
        ] + [
            ("png", tile_url(lx, ly, "png", resolution=1),
             crop(level1, lx, ly)),
            ("render",
             f"/render/1/0/0/0?x={rx}&y={ry}&w={TILE}&h={TILE}"
             f"&c=1%7C{lo}:{hi}%24FF0000&format=png",
             reference_render(crop(image, rx, ry))),
        ]
        fails, _, took = run_requests(server, others, 4)
        failures += fails
        say(
            f"other_requests_seconds: {took:.1f} (4 tif, 4 raw, "
            "1 resolution=1 png, 1 /render)"
        )

        health = server.healthz()
        failures += server_checks(server, health)
        say(
            "tiles_by_path: "
            f"{16 + 64 + 1} png requested, "
            f"{int(health['tile_device_lanes_total'])} encoded on the "
            "device (a singleton batch takes the single-request host "
            "path); 1 render, "
            f"render_fallback_total {health['render_fallback_total']}, "
            f"tile_device_fallback_total "
            f"{health['tile_device_fallback_total']}"
        )
        say(f"device_queue: {json.dumps(health['device_queue'])}")
        say(f"device_stage_ms_mean: {json.dumps(server.stage_means_ms())}")
        planes = (health.get("cache") or {}).get("device_planes")
        say(f"plane_cache: {json.dumps(planes)}")
        if failures:
            say("server log tail:\n" + server.log_tail())
    finally:
        server.stop()
    after = cache_entries()[1]
    say(f"compile_cache: {cache_dir} entries before {before} after {after}")
    return failures, first["device"]


# -- four chips: the auto mesh against one chip, byte for byte ----------


def narrowed_env(platform_cpu: bool) -> dict:
    """The child's environment with ONE device visible, through the
    runtime's own visibility variables (no mesh on/off key exists)."""
    env = dict(os.environ)
    if platform_cpu:
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    else:
        env.update({
            "TPU_VISIBLE_CHIPS": "0",
            "TPU_VISIBLE_DEVICES": "0",
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
        })
    return env


def serve_waves(name, server, waves, healthy_s) -> tuple:
    """Every wave through one healthy server:
    (failures, bodies by url, final /healthz)."""
    say(f"[{name}] seconds_to_healthy: {healthy_s:.1f}")
    say(f"[{name}] device: {json.dumps(server.healthz()['device'])}")
    failures, bodies = [], {}
    for n, wave in enumerate(waves):
        fails, wave_bodies, took = run_requests(server, wave, MAX_BATCH)
        failures += fails
        bodies.update(wave_bodies)
        say(f"[{name}] wave {n}: {took:.1f}s")
    health = server.healthz()
    say(f"[{name}] device_stage_ms_mean: {json.dumps(server.stage_means_ms())}")
    planes = (health.get("cache") or {}).get("device_planes")
    say(f"[{name}] plane_cache: {json.dumps(planes)}")
    return failures, bodies, health


def run_mesh(args, workdir, image) -> tuple:
    cpu = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    wide = dict(os.environ)
    if cpu:  # rehearsal: four virtual devices stand in for the host
        wide["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    # waves of exactly max-batch concurrent requests: each wave is one
    # full batch in both servers, so no lane strays onto the
    # single-request host path and the byte comparison is like for like
    jobs = png_jobs(image, tile_origins(args.seed, args.size, 4 * MAX_BATCH))
    waves = [jobs[i : i + MAX_BATCH] for i in range(0, len(jobs), MAX_BATCH)]
    port = free_port()  # one after the other: the same port serves both
    registry, config = write_fixture(workdir, image, port)

    server = Server(workdir, registry, config, port, wide, "mesh")
    try:
        failures, mesh_bodies, health = serve_waves(
            "mesh", server, waves, server.wait_healthy()
        )
        device = health["device"]
        failures += server_checks(server, health, want_count=4)
        last = (health["render"].get("mesh") or {}).get("last_dispatch") or {}
        say(f"[mesh] last_mesh_dispatch: {json.dumps(last)}")
        lanes = last.get("lanes_per_device") or []
        if (
            len(set(last.get("device_ids") or [])) != 4
            or not lanes or min(lanes) < 1
        ):
            failures.append(
                "last_mesh_dispatch does not show 4 distinct device ids "
                f"with lanes on each: {last}"
            )
        if failures:
            say("[mesh] server log tail:\n" + server.log_tail())
    finally:
        server.stop()  # the chip is free before the next server

    pixels_only = (
        "mesh bodies were compared with the numpy reference by decoded "
        "pixels only, not byte for byte with one chip"
    )
    server = Server(workdir, registry, config, port, narrowed_env(cpu), "one")
    try:
        try:
            healthy_s = server.wait_healthy()
        except RuntimeError as e:
            # the runtime refused the narrowed environment
            say(
                "[one] device visibility could not be narrowed on this "
                f"host (the one-chip server did not start: {e}); "
                + pixels_only
            )
            return failures, device
        fails, one_bodies, health = serve_waves(
            "one", server, waves, healthy_s
        )
        if health["device"]["count"] != 1:
            say(
                "[one] device visibility could not be narrowed (server "
                f"saw {health['device']}); " + pixels_only
            )
            return failures, device
        failures += fails + server_checks(server, health)
        differ = [u for u in mesh_bodies if mesh_bodies[u] != one_bodies[u]]
        say(
            f"byte_identity: {len(mesh_bodies) - len(differ)}/"
            f"{len(mesh_bodies)} bodies identical, mesh vs one chip"
        )
        failures += [f"mesh and one-chip bodies differ: {u}" for u in differ]
        if failures:
            say("[one] server log tail:\n" + server.log_tail())
    finally:
        server.stop()
    return failures, device


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--size", type=int, default=8192,
        help="image edge in pixels (8192 = the BASELINE headline shape)",
    )
    parser.add_argument(
        "--mesh", action="store_true",
        help="four chips: the auto mesh vs a one-chip server, and "
        "nothing else",
    )
    args = parser.parse_args()
    if args.size < 4 * TILE or args.size % (2 * TILE):
        parser.error(f"--size must be a multiple of {2 * TILE}, >= {4 * TILE}")

    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        t0 = time.perf_counter()
        image = seeded_image(args.seed, args.size)
        say(
            f"fixture: {args.size}x{args.size} uint16, seed {args.seed}, "
            f"{TILE}x{TILE} zlib tiles, 2 levels"
        )
        run = run_mesh if args.mesh else run_one_chip
        failures, device = run(args, workdir, image)
        say(f"total_seconds: {time.perf_counter() - t0:.1f}")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        failures.append("chip_smoke's own process initialised a JAX backend")
    for failure in failures:
        say(f"FAILED: {failure}")
    if not device or device.get("platform") != "tpu":
        # no accelerator: no result line, whatever else passed
        print(
            f"no accelerator: the server ran on {device}; "
            f"{len(failures)} other failure(s)",
            file=sys.stderr,
        )
        return 2
    print(json.dumps({"ok": not failures, "device": device}), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
