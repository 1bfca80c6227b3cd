"""HBM-resident plane cache: device crops must encode identically to
the host path, planes stage once, and edge lanes fall back."""

import numpy as np
import pytest

from omero_ms_pixel_buffer_tpu.io.ometiff import write_ome_tiff
from omero_ms_pixel_buffer_tpu.io.pixels_service import (
    ImageRegistry,
    PixelsService,
)
from omero_ms_pixel_buffer_tpu.models.device_cache import DevicePlaneCache
from omero_ms_pixel_buffer_tpu.models.tile_pipeline import TilePipeline
from omero_ms_pixel_buffer_tpu.ops.png import decode_png
from omero_ms_pixel_buffer_tpu.tile_ctx import RegionDef, TileCtx


@pytest.fixture
def image(tmp_path):
    rng = np.random.default_rng(41)
    data = rng.integers(0, 60000, (1, 1, 2, 640, 640), dtype=np.uint16)
    path = str(tmp_path / "img.ome.tiff")
    write_ome_tiff(path, data, tile_size=(256, 256), compression="zlib")
    registry = ImageRegistry()
    registry.add(1, path)
    return PixelsService(registry), data[0, 0]


def _ctx(x, y, w, h, z=0):
    return TileCtx(
        image_id=1, z=z, c=0, t=0, region=RegionDef(x, y, w, h),
        format="png", omero_session_key="k",
    )


class TestPlaneCache:
    def test_device_plane_path_matches_host(self, image):
        service, truth = image
        dev = TilePipeline(
            service, engine="device", use_pallas=False, buckets=(256,),
        )
        dev.mesh = None  # plane cache is the single-device path
        host = TilePipeline(service, engine="host")
        ctxs = [
            _ctx(0, 0, 256, 256),
            _ctx(128, 64, 256, 256),
            _ctx(37, 51, 100, 200),     # sub-bucket
            _ctx(500, 500, 140, 140),   # edge: crop would clamp -> host
            _ctx(0, 0, 256, 256, z=1),  # second plane
        ]
        # batch 1: admission threshold not met -> host staging, but
        # outputs already correct; batch 2: planes resident
        for round_ in range(2):
            out_dev = dev.handle_batch(list(ctxs))
            out_host = host.handle_batch(list(ctxs))
            for ctx, d, h in zip(ctxs, out_dev, out_host):
                assert d is not None and h is not None
                r = ctx.region
                z = ctx.z
                np.testing.assert_array_equal(
                    decode_png(d), truth[z, r.y : r.y + r.height,
                                         r.x : r.x + r.width],
                )
                np.testing.assert_array_equal(decode_png(d), decode_png(h))
        # two planes staged (z=0, z=1) on the second touch
        cache = dev._plane_cache
        assert cache is not None and len(cache) == 2
        misses = cache.misses
        out2 = dev.handle_batch([_ctx(64, 64, 256, 256)])
        assert out2[0] is not None
        assert cache.misses == misses  # pure hit

    def test_budget_zero_falls_back(self, image):
        service, truth = image
        pipe = TilePipeline(
            service, engine="device", use_pallas=False, buckets=(256,),
        )
        pipe.mesh = None  # plane cache is the single-device path
        pipe._plane_cache = DevicePlaneCache(max_bytes=0)
        out = pipe.handle_batch([_ctx(0, 0, 256, 256)])
        np.testing.assert_array_equal(
            decode_png(out[0]), truth[0, :256, :256]
        )
        assert len(pipe._plane_cache) == 0

    def test_plane_cache_lru_evicts(self, image):
        service, _ = image
        plane_bytes = 640 * 640 * 2
        cache = DevicePlaneCache(
            max_bytes=plane_bytes + 16, admit_after=1
        )
        buf = service.get_pixel_buffer(1)
        p0 = cache.get_plane(buf, 0, 0, 0, 0)
        p1 = cache.get_plane(buf, 0, 1, 0, 0)
        assert p0 is not None and p1 is not None
        assert len(cache) == 1  # first plane evicted
        assert cache.nbytes <= plane_bytes + 16

    def test_admission_defers_first_touch(self, image):
        service, _ = image
        cache = DevicePlaneCache(max_bytes=1 << 30)  # admit_after=2
        buf = service.get_pixel_buffer(1)
        assert cache.get_plane(buf, 0, 0, 0, 0) is None  # touch 1
        assert cache.get_plane(buf, 0, 0, 0, 0) is not None  # touch 2

    def test_disabled_plane_cache(self, image):
        service, truth = image
        pipe = TilePipeline(
            service, engine="device", use_pallas=False, buckets=(256,),
            use_plane_cache=False,
        )
        out = pipe.handle_batch([_ctx(32, 32, 128, 128)])
        np.testing.assert_array_equal(
            decode_png(out[0]), truth[0, 32:160, 32:160]
        )
        assert pipe._plane_cache is None


def test_admission_single_touch_per_batch(image):
    """Multiple cold lanes on one plane in one batch count ONE
    admission touch (get_plane called once), so admit_after=2 really
    defers staging to the second batch."""
    service, truth = image
    pipe = TilePipeline(
        service, engine="device", use_pallas=False, buckets=(256,),
    )
    pipe.mesh = None  # plane cache is the single-device path
    batch = [_ctx(0, 0, 256, 256), _ctx(128, 128, 256, 256)]
    out1 = pipe.handle_batch(list(batch))
    assert all(o is not None for o in out1)
    assert len(pipe._plane_cache) == 0  # still cold after batch 1
    out2 = pipe.handle_batch(list(batch))
    assert all(o is not None for o in out2)
    assert len(pipe._plane_cache) == 1  # staged on batch 2


def test_admission_counter_resets_after_staging(image):
    service, _ = image
    cache = DevicePlaneCache(max_bytes=1 << 30)
    buf = service.get_pixel_buffer(1)
    assert cache.get_plane(buf, 0, 0, 0, 0) is None
    assert cache.get_plane(buf, 0, 0, 0, 0) is not None  # staged
    # evict by replacing the cache contents, then the counter must
    # restart (no immediate restage on the first post-eviction touch)
    for chip in cache._chips:
        chip.planes.clear()
        chip.bytes = 0
    cache._where.clear()
    assert cache.get_plane(buf, 0, 0, 0, 0) is None  # touch 1 again


def test_admission_one_touch_across_buckets(image):
    """Two buckets of one cold plane in one batch still count a single
    admission touch."""
    service, _ = image
    pipe = TilePipeline(
        service, engine="device", use_pallas=False, buckets=(256, 512),
    )
    pipe.mesh = None  # plane cache is the single-device path
    batch = [_ctx(0, 0, 256, 256), _ctx(0, 0, 400, 400)]  # two buckets
    out1 = pipe.handle_batch(list(batch))
    assert all(o is not None for o in out1)
    assert len(pipe._plane_cache) == 0  # one touch -> still cold


def test_staging_single_flight(image):
    """Two threads passing admission concurrently stage the plane ONCE;
    the follower falls back to the host path instead of duplicating a
    full-plane read + transfer (ADVICE r1)."""
    import threading

    from omero_ms_pixel_buffer_tpu.models.device_cache import DevicePlaneCache

    service, _ = image
    buf = service.get_pixel_buffer(1)

    started = threading.Event()
    release = threading.Event()
    reads = []
    real_get = buf.get_tile_at

    def slow_get(level, z, c, t, x, y, w, h):
        reads.append((level, z, c, t))
        started.set()
        release.wait(5)
        return real_get(level, z, c, t, x, y, w, h)

    buf.get_tile_at = slow_get
    try:
        cache = DevicePlaneCache(admit_after=1)
        results = {}

        def leader():
            results["leader"] = cache.get_plane(buf, 0, 0, 0, 0)

        t1 = threading.Thread(target=leader)
        t1.start()
        assert started.wait(5)
        # leader is mid-read; a follower must get None, not a 2nd read
        assert cache.get_plane(buf, 0, 0, 0, 0) is None
        release.set()
        t1.join(10)
        assert results["leader"] is not None
        assert len([r for r in reads]) == 1
        # once staged, followers hit the resident plane
        assert cache.get_plane(buf, 0, 0, 0, 0) is not None
    finally:
        buf.get_tile_at = real_get


# ---------------------------------------------------------------------------
# A cache a chip (hosts with several chips; conftest's virtual devices)
# ---------------------------------------------------------------------------

Z, C, SIDE, TILE = 8, 3, 96, 48  # 24 planes of 18 432 B
SWEEP = [(z, c) for z in range(Z) for c in range(C)]  # z outer, c inner


@pytest.fixture(scope="module")
def stack(tmp_path_factory):
    rng = np.random.default_rng(433)
    data = rng.integers(0, 4000, (1, C, Z, SIDE, SIDE), dtype=np.uint16)
    path = str(tmp_path_factory.mktemp("chips") / "stack.ome.tiff")
    write_ome_tiff(path, data, tile_size=(TILE, TILE))
    registry = ImageRegistry()
    registry.add(1, path)
    service = PixelsService(registry)
    yield service, data
    service.close()


def _chips(n=4, first=0):
    import jax

    return jax.devices()[first:first + n]


def _stage_sweep(cache, buf, sweep=SWEEP):
    return [cache.get_plane(buf, 0, z, c, 0) for z, c in sweep]


class TestPlacement:
    def test_a_sweep_lands_level_on_its_own_chips(self, stack):
        service, _ = stack
        buf = service.get_pixel_buffer(1)
        chips = _chips()
        cache = DevicePlaneCache(
            max_bytes=1 << 20, admit_after=1, devices=chips
        )
        planes = _stage_sweep(cache, buf)
        snap = cache.snapshot()
        assert [row["planes"] for row in snap["per_chip"]] == [6, 6, 6, 6]
        assert [row["chip"] for row in snap["per_chip"]] == [
            d.id for d in chips
        ]
        assert snap["planes"] == 24 and snap["admissions"] == 24
        assert snap["devices"] == [d.id for d in chips]
        where = [next(iter(p.devices())) for p in planes]
        for s, device in enumerate(where):
            assert device == chips[s % 4]  # the sweep slot's chip
        for before, after in zip(where, where[1:]):
            assert before != after  # consecutive requests of a sweep
        # the look-up finds each plane again, on its chip, as a hit
        again = _stage_sweep(cache, buf)
        assert all(a is p for a, p in zip(again, planes))
        assert [row["hits"] for row in cache.snapshot()["per_chip"]] == [
            6, 6, 6, 6]

    @pytest.mark.parametrize("order_seed", [1, 2, 3])
    def test_any_admission_order_stays_level(self, stack, order_seed):
        service, _ = stack
        buf = service.get_pixel_buffer(1)
        cache = DevicePlaneCache(
            max_bytes=1 << 20, admit_after=1, devices=_chips()
        )
        order = list(SWEEP)
        np.random.default_rng(order_seed).shuffle(order)
        for n, (z, c) in enumerate(order, 1):
            assert cache.get_plane(buf, 0, z, c, 0) is not None
            held = [row["planes"] for row in cache.snapshot()["per_chip"]]
            assert max(held) - min(held) <= 1 and sum(held) == n
        assert held == [6, 6, 6, 6]

    def test_the_budget_is_split_and_a_chip_evicts_only_its_own(
        self, stack
    ):
        service, _ = stack
        buf = service.get_pixel_buffer(1)
        plane_bytes = SIDE * SIDE * 2
        # the process's number: room for three planes a chip
        cache = DevicePlaneCache(
            max_bytes=4 * 3 * plane_bytes, admit_after=1, devices=_chips()
        )
        assert cache.chip_max_bytes == 3 * plane_bytes
        _stage_sweep(cache, buf, SWEEP[:12])  # full: 3 / 3 / 3 / 3
        assert cache.snapshot()["evictions"] == 0
        # the thirteenth plane goes to the sweep slot's chip (all are
        # level), which drops ITS oldest and nobody else's
        assert cache.get_plane(buf, 0, *SWEEP[12], 0) is not None
        rows = cache.snapshot()["per_chip"]
        assert [row["evictions"] for row in rows] == [1, 0, 0, 0]
        assert [row["planes"] for row in rows] == [3, 3, 3, 3]
        assert all(row["bytes"] <= cache.chip_max_bytes for row in rows)
        # chip 0 lost the first plane of the sweep: a miss again
        misses = cache.misses
        cache.get_plane(buf, 0, *SWEEP[0], 0)
        assert cache.misses == misses + 1
        # a plane above a chip's share is refused, whatever the total
        small = DevicePlaneCache(
            max_bytes=4 * plane_bytes - 4, admit_after=1, devices=_chips()
        )
        assert small.get_plane(buf, 0, 0, 0, 0) is None
        assert small.snapshot()["planes"] == 0

    def test_planes_staged_side_by_side_spread_over_the_chips(self, stack):
        service, _ = stack
        buf = service.get_pixel_buffer(1)
        cache = DevicePlaneCache(
            max_bytes=1 << 20, admit_after=1, devices=_chips()
        )
        try:
            planes = cache.get_planes(
                [(buf, 0, z, c, 0) for z, c in SWEEP[:8]]
            )
        finally:
            cache.close()
        assert all(p is not None for p in planes)
        assert [row["planes"] for row in cache.snapshot()["per_chip"]] == [
            2, 2, 2, 2]

    def test_one_device_is_the_cache_it_was(self, stack):
        import jax

        service, _ = stack
        buf = service.get_pixel_buffer(1)
        for devices in (None, _chips(1)):
            cache = DevicePlaneCache(admit_after=1, devices=devices)
            assert not cache.spread
            assert cache.chip_max_bytes == cache.max_bytes
            plane = cache.get_plane(buf, 0, 1, 2, 0)
            assert plane.devices() == {jax.devices()[0]}
            assert cache._labels(cache._chips[0]) == {}
            snap = cache.snapshot()
            assert snap["per_chip"] == [{
                "chip": jax.devices()[0].id, "planes": 1,
                "bytes": plane.nbytes, "hits": 0, "misses": 1, "lanes": 0,
                "evictions": 0,
            }]

    def test_a_warm_hook_that_fails_leaves_the_plane_out(self, stack):
        service, _ = stack
        buf = service.get_pixel_buffer(1)
        cache = DevicePlaneCache(
            max_bytes=1 << 20, admit_after=1, devices=_chips()
        )
        seen, errors = [], []

        def warm(n, plane):
            seen.append((n, next(iter(plane.devices()))))
            if n == 1:
                raise RuntimeError("the chip cannot serve it")

        planes = cache.get_planes(
            [(buf, 0, 0, 0, 0), (buf, 0, 0, 1, 0)],
            on_error=errors.append, warm=warm,
        )
        cache.close()
        assert planes[0] is not None and planes[1] is None
        assert sorted(n for n, _ in seen) == [0, 1]
        assert len(errors) == 1 and cache.snapshot()["planes"] == 1
        assert not cache._staging
        assert all(chip.claimed == 0 for chip in cache._chips)


class _Memory:
    """A device that reports its memory, as a TPU does."""

    def __init__(self, id_, limit):
        self.id, self._limit = id_, limit

    def memory_stats(self):
        return {"bytes_limit": self._limit}


class TestStartUpBudgetCheck:
    def test_a_share_above_the_chips_memory_is_an_error(self, caplog):
        chips = [_Memory(k, 16 << 30) for k in range(4)]
        cache = DevicePlaneCache(max_bytes=80 << 30, devices=chips)
        with caplog.at_level("ERROR"):
            error = cache.check_budget()
        assert "20480 MiB a chip" in error and "16384 MiB" in error
        assert cache.snapshot()["error"] == error
        assert any("plane-cache-mb" in r.message for r in caplog.records)

    def test_a_share_that_fits_says_nothing(self):
        chips = [_Memory(k, 16 << 30) for k in range(4)]
        # the Z stack at 32 sections: 20 GiB over four chips
        cache = DevicePlaneCache(max_bytes=20480 << 20, devices=chips)
        assert cache.check_budget() is None
        assert "error" not in cache.snapshot()

    def test_a_backend_without_memory_stats_is_not_judged(self):
        cache = DevicePlaneCache(max_bytes=1 << 50)  # the CPU backend
        assert cache.check_budget() is None


# -- resident means servable (the contract of a chip's first plane) ---------

_COMPILES = []


def _count_backend_compiles():
    """Every backend compile of this process from now on, by name: a
    compile is what adds an entry to the persistent cache (every one
    is persisted, runtime/jax_cache.py)."""
    if not _COMPILES:
        from jax import monitoring

        _COMPILES.append("listening")
        monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: _COMPILES.append(name)
            if name.endswith("backend_compile_duration") else None
        )
    return lambda: len(_COMPILES) - 1


def _chip_pipeline(service, devices, tile, max_batch=8):
    from omero_ms_pixel_buffer_tpu.parallel.mesh import make_mesh

    pipe = TilePipeline(
        service, engine="device", use_pallas=False, buckets=(tile,),
        device_deflate=True, device_deflate_mode="dynamic",
        max_batch=max_batch,
    )
    pipe.mesh = make_mesh(("data",), devices=devices)
    return pipe


def _zc_ctx(z, c, x, y, tile):
    return TileCtx(
        image_id=1, z=z, c=c, t=0, region=RegionDef(x, y, tile, tile),
        format="png", omero_session_key="k",
    )


class TestResidentMeansServable:
    TILE = 40  # a size of this class's own: its programs are cold

    def _admit(self, pipe, planes, compiles, warm=None):
        """Two touches: the planes are resident after the second, and
        the batch that admitted them goes on to serve its lanes from
        them. Returns the planes a chip and the process's compile count
        when the last chip's warm hook had returned: the moment from
        which nothing may compile."""
        warm = warm or pipe._warm_plane_chip
        warmed = []

        def hook(plane, classes):
            warm(plane, classes)
            warmed.append(compiles())

        pipe._warm_plane_chip = hook
        for _ in range(2):
            out = pipe.handle_batch(
                [_zc_ctx(z, c, 0, 0, self.TILE) for z, c in planes]
            )
            assert all(o is not None for o in out)
        assert len(warmed) == len(planes)
        rows = pipe.plane_cache_snapshot()["per_chip"]
        return {row["chip"]: row["planes"] for row in rows}, max(warmed)

    @pytest.mark.parametrize("lanes", [1, 2, 4, 8, 3, 7])
    def test_a_batch_on_a_resident_chip_compiles_nothing(self, stack, lanes):
        service, data = stack
        compiles = _count_backend_compiles()
        chips = _chips(2)
        pipe = _chip_pipeline(service, chips, self.TILE)
        try:
            # one plane a chip: (0, 0) on the first, (0, 1) on the next
            held, resident_at = self._admit(pipe, [(0, 0), (0, 1)], compiles)
            assert held == {chips[0].id: 1, chips[1].id: 1}
            for z, c in [(0, 0), (0, 1)]:  # the same lanes on each chip
                ctxs = [
                    _zc_ctx(z, c, 8 * j, 56 - 8 * j, self.TILE)
                    for j in range(lanes)
                ]
                out = pipe.handle_batch(ctxs)
                for ctx, png in zip(ctxs, out):
                    r = ctx.region
                    np.testing.assert_array_equal(
                        decode_png(png),
                        data[0, c, z, r.y:r.y + self.TILE,
                             r.x:r.x + self.TILE],
                    )
            assert compiles() == resident_at
            rows = pipe.plane_cache_snapshot()["per_chip"]
            assert [row["lanes"] for row in rows] == [1 + lanes, 1 + lanes]
        finally:
            pipe.close()

    def test_the_pull_of_a_chips_group_follows_no_size_guess(self, stack):
        """The pull's size guess (`_dd_cap`) moves with the data; a
        slice cut to it would be a new program a size, a lane count and
        a device, in the middle of serving (PR 33's first four-chip
        runs: ten of them in one window)."""
        service, data = stack
        compiles = _count_backend_compiles()
        chips = _chips(2)
        pipe = _chip_pipeline(service, chips, self.TILE)
        try:
            _, resident_at = self._admit(pipe, [(0, 0), (0, 1)], compiles)
            for guess in (64, 1 << 10, 1 << 30):
                pipe._dd_cap[(self.TILE, self.TILE)] = guess
                out = pipe.handle_batch([
                    _zc_ctx(0, c, 8 * j, 16, self.TILE)
                    for c in (0, 1) for j in range(3)
                ])
                np.testing.assert_array_equal(
                    decode_png(out[4]),
                    data[0, 1, 0, 16:16 + self.TILE, 8:8 + self.TILE],
                )
                assert pipe._dd_cap[(self.TILE, self.TILE)] == guess
            assert compiles() == resident_at
        finally:
            pipe.close()

    def test_the_check_fails_on_a_crop_warmed_on_one_device_only(
        self, stack
    ):
        """The parent's warm-up: once a (shape, dtype, bucket), on
        whichever device held the first plane. The other chip then
        compiles when serving first brings it a lane."""
        service, _ = stack
        compiles = _count_backend_compiles()
        chips = _chips(2, first=4)  # chips no other test has warmed
        pipe = _chip_pipeline(service, chips, self.TILE)
        warm = pipe._warm_plane_chip
        first_plane = []

        def on_the_first_chip_only(plane, classes):
            first_plane.append(plane)
            warm(first_plane[0], classes)

        try:
            held, resident_at = self._admit(
                pipe, [(0, 0), (0, 1)], compiles, on_the_first_chip_only
            )
            assert held == {chips[0].id: 1, chips[1].id: 1}
            assert compiles() > resident_at  # the lane on the cold chip
        finally:
            pipe.close()


class TestOneVisibleDevice:
    def test_the_plane_path_is_the_parents(self, stack, monkeypatch):
        import jax

        from omero_ms_pixel_buffer_tpu.models import device_cache
        from omero_ms_pixel_buffer_tpu.models import tile_pipeline

        service, data = stack
        pipe = TilePipeline(
            service, engine="device", use_pallas=False, buckets=(TILE,),
            device_deflate=True, device_deflate_mode="dynamic",
        )
        pipe.mesh = None
        asked = []
        real = DevicePlaneCache.get_planes

        def get_planes(self, wanted, on_error=None, warm=None):
            asked.append(warm)
            return real(self, wanted, on_error=on_error, warm=warm)

        monkeypatch.setattr(DevicePlaneCache, "get_planes", get_planes)
        lanes_before = tile_pipeline.TILE_DEVICE_LANES._values.get((), 0.0)
        try:
            for _ in range(2):
                out = pipe.handle_batch(
                    [_zc_ctx(1, 1, 0, 0, TILE), _zc_ctx(1, 1, 16, 32, TILE)]
                )
            np.testing.assert_array_equal(
                decode_png(out[1]), data[0, 1, 1, 32:32 + TILE, 16:16 + TILE]
            )
            cache = pipe._plane_cache
            assert not cache.spread and asked == [None, None]
            assert not pipe._warm_chips  # no warm-up at admission
            queue = pipe.device_queue_snapshot()
            assert "chips" not in queue  # the process's one pipe
            assert pipe._dispatcher._chip_pipe is None
            # the series keep the names the accepted readers match
            assert tile_pipeline.TILE_DEVICE_LANES._values[()] == (
                lanes_before + 4)
            assert () in device_cache.PLANE_ADMISSIONS._values
            # the crop's program does not know where its starts came
            # from: host arrays (now) lower as device arrays (before)
            plane = cache.get_plane(service.get_pixel_buffer(1), 0, 1, 1, 0)
            zeros = np.zeros(2, np.int32)
            lowered = [
                device_cache._crop_batch_jit.lower(
                    plane, ys, ys, TILE, TILE).as_text()
                for ys in (zeros, jax.numpy.asarray([0, 0], jax.numpy.int32))
            ]
            assert lowered[0] == lowered[1]
        finally:
            pipe.close()


class TestChipPipe:
    """The device queue on a host with several chips: a group that
    names its chip runs there, and waits in ONE queue with the groups
    of every other chip, as many of them in flight as the host has
    workers, not in a pipe of its chip's own."""

    def test_groups_of_one_chip_use_every_worker(self):
        import threading
        import time

        import jax

        from omero_ms_pixel_buffer_tpu.models import device_dispatch as dd

        chip = jax.devices()[1]
        disp = dd.DeviceEncodeDispatcher({}, queue_depth=1, chips=4)
        gate = threading.Event()
        real = disp._readback_group

        def held(*args, **kwargs):
            gate.wait(timeout=60)
            return real(*args, **kwargs)

        disp._readback_group = held
        rng = np.random.default_rng(9)
        tiles = rng.integers(0, 4000, (1, 16, 16), dtype=np.uint16)
        try:
            futures = [
                disp.submit(
                    jax.device_put(tiles, chip), 16, 1 + 16 * 2, 2, "up",
                    "rle", [0], [(16, 16)], 16, 0, staged=True, device=chip,
                )
                for _ in range(8)
            ]
            deadline = time.monotonic() + 60
            while (disp.snapshot()["chip_pipe"]["inflight"] < 4
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            # four of the one chip's groups are in flight at once (a
            # slot a worker), the other four wait their turn
            assert disp.snapshot()["chip_pipe"] == {
                "workers": 4, "inflight": 4}
            assert not any(f.done() for f in futures)
            gate.set()
            pngs = [f.result(timeout=120)[0] for f in futures]
            for png in pngs:
                np.testing.assert_array_equal(decode_png(png), tiles[0])
            snap = disp.snapshot()
            assert snap["chips"] == [{"chip": chip.id, "groups": 8}]
            assert snap["chip_pipe"] == {"workers": 4, "inflight": 0}
            # a group that names no chip keeps to the process's pipe
            fut = disp.submit(
                tiles, 16, 1 + 16 * 2, 2, "up", "rle", [0], [(16, 16)],
                16, 0,
            )
            assert fut.result(timeout=120)[0] == pngs[0]
            assert disp.snapshot()["chips"] == snap["chips"]
        finally:
            gate.set()
            disp.close()
