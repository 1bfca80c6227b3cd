"""A Z stack on the served /tile path: a batch whose lanes lie on many
HBM-resident planes is cropped on the device, the lanes of one plane
one device group, and every answer is the plain numpy crop of the
seeded array at (c, z). (Joining the planes of a batch into one group
was built and measured in PR 28 and lost in both cells, so per-plane
groups stayed: PERF.md section 6.)

The image is a small multi-Z BigTIFF (4 Z x 3 C of 1024^2 uint16,
zlib, two levels) written by the threaded writer; the pipeline runs the
device engine (fused dynamic deflate through the dispatcher) on the CPU
backend.
"""

import threading

import numpy as np
import pytest

from omero_ms_pixel_buffer_tpu.io.ometiff import write_ome_tiff
from omero_ms_pixel_buffer_tpu.io.pixels_service import (
    ImageRegistry,
    PixelsService,
)
from omero_ms_pixel_buffer_tpu.models import device_cache
from omero_ms_pixel_buffer_tpu.models.device_cache import DevicePlaneCache
from omero_ms_pixel_buffer_tpu.models.device_dispatch import (
    DEVICE_GROUP_LANES,
)
from omero_ms_pixel_buffer_tpu.models.tile_pipeline import TilePipeline
from omero_ms_pixel_buffer_tpu.ops.png import decode_png
from omero_ms_pixel_buffer_tpu.tile_ctx import RegionDef, TileCtx

SIZE, TILE, SIZE_Z, SIZE_C = 1024, 128, 4, 3
PLANE_BYTES = SIZE * SIZE * 2
# (z, c) of the twelve planes, in the order the tests touch them
PLANES = [(z, c) for z in range(SIZE_Z) for c in range(SIZE_C)]


@pytest.fixture(scope="module")
def stack(tmp_path_factory):
    rng = np.random.default_rng(2147485207)
    noise = rng.standard_normal((SIZE, SIZE)).astype(np.float32) * 120.0
    data = np.empty((1, SIZE_C, SIZE_Z, SIZE, SIZE), np.uint16)
    for k, (z, c) in enumerate(PLANES):
        data[0, c, z] = np.clip(
            np.roll(noise, (97 * k, 61 * k), axis=(0, 1))
            + 2000.0 + 300.0 * c + 40.0 * z, 0, 65535,
        )
    path = str(tmp_path_factory.mktemp("zstack") / "stack.ome.tiff")
    write_ome_tiff(
        path, data, tile_size=(256, 256), compression="zlib",
        pyramid_levels=2, bigtiff=True,
    )
    registry = ImageRegistry()
    registry.add(1, path)
    service = PixelsService(registry)
    yield service, data
    service.close()


def _pipeline(service, **kw):
    pipe = TilePipeline(
        service, engine="device", use_pallas=False, buckets=(TILE,),
        device_deflate=True, device_deflate_mode="dynamic", **kw,
    )
    pipe.mesh = None  # the plane cache is the single-device path
    return pipe


def _ctx(z, c, x, y, w=TILE, h=TILE):
    return TileCtx(
        image_id=1, z=z, c=c, t=0, region=RegionDef(x, y, w, h),
        format="png", omero_session_key="k",
    )


def _crop(data, ctx):
    r = ctx.region
    return data[0, ctx.c, ctx.z, r.y : r.y + r.height, r.x : r.x + r.width]


def _serve(pipe, data, ctxs):
    """handle_batch, every lane checked against the numpy crop."""
    out = pipe.handle_batch(list(ctxs))
    for ctx, png in zip(ctxs, out):
        assert isinstance(png, bytes)
        np.testing.assert_array_equal(decode_png(png), _crop(data, ctx))
    return out


def _make_resident(pipe, data, planes):
    """Two touches admit a plane (`admit_after` stays 2)."""
    for _ in range(2):
        _serve(pipe, data, [_ctx(z, c, 0, 0) for z, c in planes])
    assert len(pipe._plane_cache) == len(planes)


def _groups(pipe):
    return pipe.device_queue_snapshot()["groups"]


@pytest.fixture(scope="module")
def warm(stack):
    """One pipeline with eight of the twelve planes resident."""
    service, data = stack
    pipe = _pipeline(service)
    _make_resident(pipe, data, PLANES[:8])
    yield pipe, data
    pipe.close()


class TestGroupsAcrossPlanes:
    @pytest.mark.parametrize("k", [1, 2, 3, 8])
    def test_resident_lanes_on_k_planes_are_k_groups(self, warm, k):
        pipe, data = warm
        lanes = [
            _ctx(z, c, 64 * (j + 1), 128 * (j % 3))
            for j, (z, c) in enumerate(PLANES[:k])
        ]
        # the first plane holds three lanes: a group padded to four
        lanes.append(_ctx(*PLANES[0], 512, 640))
        lanes.append(_ctx(*PLANES[0], 320, 448))
        # mixed with a lane of a plane that has never been touched
        # (its first touch: host-staged)
        cold = [_ctx(*PLANES[8 + k % 4], 256, 256)]
        before, lanes_before = _groups(pipe), DEVICE_GROUP_LANES._sums[()]
        hits = pipe._plane_cache.hits
        _serve(pipe, data, lanes + cold)
        # every resident lane is cropped on the device, the lanes of a
        # plane leave as one group; the cold lane is the host-staged one
        assert _groups(pipe) - before == k + 1
        assert DEVICE_GROUP_LANES._sums[()] - lanes_before == len(lanes) + 1
        assert pipe._plane_cache.hits - hits == k  # one touch a plane

    def test_png_bytes_do_not_depend_on_the_batchs_planes(self, warm):
        pipe, data = warm
        lane = _ctx(*PLANES[2], 192, 320)
        alone = _serve(pipe, data, [lane])[0]
        beside = _serve(pipe, data, [
            _ctx(*PLANES[2], 0, 512), lane, _ctx(*PLANES[2], 512, 0),
        ])[1]  # a group of three on its own plane
        among = _serve(pipe, data, [
            _ctx(*PLANES[0], 64, 64), lane, _ctx(*PLANES[5], 640, 0),
            _ctx(*PLANES[7], 320, 192),
        ])[1]
        assert alone == beside == among

    def test_sizes_below_the_bucket_and_the_coarse_level(self, warm):
        pipe, data = warm
        before = _groups(pipe)
        _serve(pipe, data, [
            _ctx(*PLANES[3], 0, 0), _ctx(*PLANES[3], 128, 0, 100, 90),
            _ctx(*PLANES[4], 0, 128, 100, 90),
        ])
        assert _groups(pipe) - before == 3  # a plane's two sizes: two
        coarse = TileCtx(
            image_id=1, z=1, c=1, t=0, region=RegionDef(64, 192, TILE, TILE),
            format="png", omero_session_key="k", resolution=1,
        )
        for _ in range(3):  # host-staged twice, then from its own plane
            png = pipe.handle_batch([coarse, _ctx(*PLANES[0], 0, 0)])[0]
            np.testing.assert_array_equal(
                decode_png(png), data[0, 1, 1, ::2, ::2][192:320, 64:192])

    def test_a_lane_at_the_planes_edge_stays_on_the_host_path(self, warm):
        pipe, data = warm
        hits = pipe._plane_cache.hits
        _serve(pipe, data, [
            _ctx(*PLANES[0], SIZE - 100, SIZE - 100, 100, 100),
            _ctx(*PLANES[1], 0, 0),
        ])
        assert pipe._plane_cache.hits - hits == 1


class TestBudget:
    def test_five_planes_of_twelve_evict_and_stay_exact(self, stack):
        service, data = stack
        pipe = _pipeline(service, plane_cache_bytes=5 * PLANE_BYTES)
        try:
            for _ in range(2):  # every plane touched twice
                for at in range(0, len(PLANES), 4):
                    _serve(pipe, data, [
                        _ctx(z, c, 128, 256) for z, c in PLANES[at:at + 4]
                    ])
            cache = pipe._plane_cache
            assert cache.admissions == 12 and cache.evictions == 7
            assert len(cache) == 5 and cache.nbytes == 5 * PLANE_BYTES
            snap = cache.snapshot()
            assert snap["admissions"] == 12 and snap["evictions"] == 7
            # an evicted plane starts over: one touch does not bring it
            # back (a working set above the budget must not thrash)
            first = _ctx(*PLANES[0], 384, 384)
            _serve(pipe, data, [first])
            assert cache.admissions == 12
            _serve(pipe, data, [first])
            assert cache.admissions == 13 and cache.evictions == 8
        finally:
            pipe.close()

    def test_the_budget_is_a_key_of_the_configuration(self, tmp_path):
        from omero_ms_pixel_buffer_tpu.utils.config import (
            Config,
            ConfigError,
        )

        def load(backend):
            return Config.from_dict(
                {"session-store": {"type": "memory"}, "backend": backend}
            )

        assert load({}).backend.plane_cache_mb == 4096
        assert load({"plane-cache-mb": 10240}).backend.plane_cache_mb == 10240
        assert load({"plane-cache-mb": 0}).backend.plane_cache_mb == 0
        for bad in ("lots", -1, None, True, [4096]):
            with pytest.raises(ConfigError, match="plane-cache-mb"):
                load({"plane-cache-mb": bad})
        with pytest.raises(ConfigError, match="Unknown keys in 'backend'"):
            load({"plane-cache-mib": 10240})
        assert not hasattr(device_cache, "default_hbm_cache_bytes")
        assert DevicePlaneCache().max_bytes == 4096 << 20


class TestStaging:
    def test_two_threads_stage_one_cold_plane_once(self, stack):
        service, _ = stack
        buf = service.get_pixel_buffer(1)
        started, release = threading.Event(), threading.Event()
        reads, real_get = [], buf.get_tile_at

        def slow_get(level, z, c, t, x, y, w, h):
            reads.append((z, c))
            started.set()
            release.wait(10)
            return real_get(level, z, c, t, x, y, w, h)

        buf.get_tile_at = slow_get
        try:
            cache = DevicePlaneCache(admit_after=1)
            got = {}

            def leader():  # two cold planes, staged side by side
                got["leader"] = cache.get_planes(
                    [(buf, 0, 0, 0, 0), (buf, 0, 1, 0, 0)])

            t1 = threading.Thread(target=leader)
            t1.start()
            assert started.wait(10)
            # both are mid-read: a follower takes the host path
            assert cache.get_planes(
                [(buf, 0, 1, 0, 0), (buf, 0, 0, 0, 0)]) == [None, None]
            release.set()
            t1.join(30)
            assert all(p is not None for p in got["leader"])
            assert sorted(reads) == [(0, 0), (1, 0)]
            assert cache.admissions == 2 and len(cache) == 2
            again = cache.get_planes([(buf, 0, 0, 0, 0), (buf, 0, 1, 0, 0)])
            assert again[0] is got["leader"][0]
            assert again[1] is got["leader"][1]
        finally:
            buf.get_tile_at = real_get
            cache.close()

    def test_a_failed_staging_leaves_the_others_resident(self, stack):
        service, data = stack
        buf = service.get_pixel_buffer(1)
        real_get = buf.get_tile_at

        def flaky(level, z, c, t, x, y, w, h):
            if z == 2:
                raise OSError("read failed")
            return real_get(level, z, c, t, x, y, w, h)

        buf.get_tile_at = flaky
        try:
            cache = DevicePlaneCache(admit_after=1)
            with pytest.raises(OSError):
                cache.get_planes([(buf, 0, z, 0, 0) for z in range(4)])
            assert len(cache) == 3 and cache.admissions == 3
            plane = cache.get_plane(buf, 0, 3, 0, 0)
            np.testing.assert_array_equal(np.asarray(plane), data[0, 0, 3])
        finally:
            buf.get_tile_at = real_get
            cache.close()

    def test_a_failed_staging_costs_only_its_own_lanes(self, stack):
        from omero_ms_pixel_buffer_tpu.models.tile_pipeline import (
            TILE_DEVICE_FALLBACK,
        )

        service, data = stack
        buf = service.get_pixel_buffer(1)
        real_get = buf.get_tile_at

        def flaky(level, z, c, t, x, y, w, h):
            if (z, c) == PLANES[1] and w == SIZE:  # the whole plane
                raise OSError("read failed")
            return real_get(level, z, c, t, x, y, w, h)

        pipe = _pipeline(service)
        try:
            batch = [_ctx(z, c, 128, 256) for z, c in PLANES[:3]]
            _serve(pipe, data, batch)  # first touch: all host-staged
            buf.get_tile_at = flaky
            failed = TILE_DEVICE_FALLBACK.total()
            _serve(pipe, data, batch)  # second touch: two are admitted
            assert TILE_DEVICE_FALLBACK.total() - failed == 1
            assert len(pipe._plane_cache) == 2
            hits = pipe._plane_cache.hits
            _serve(pipe, data, batch)
            assert pipe._plane_cache.hits - hits == 2
        finally:
            buf.get_tile_at = real_get
            pipe.close()

    def test_a_closed_cache_keeps_no_claim_it_cannot_serve(self, stack):
        service, _ = stack
        buf = service.get_pixel_buffer(1)
        cache = DevicePlaneCache(admit_after=1)
        wanted = [(buf, 0, 0, 0, 0), (buf, 0, 1, 0, 0)]
        assert all(p is not None for p in cache.get_planes(wanted))
        stagers = cache._stagers
        cache.close()
        cache._stagers = stagers  # a batch that saw the pool before close()
        cold = [(buf, 0, 2, 0, 0), (buf, 0, 3, 0, 0)]
        errors = []
        assert cache.get_planes(cold, on_error=errors.append) == [None, None]
        assert len(errors) == 2 and not cache._staging
        with pytest.raises(RuntimeError):
            cache.get_planes(cold)
        assert not cache._staging

    def test_crops_come_in_the_encoders_lane_counts(self, stack):
        service, data = stack
        buf = service.get_pixel_buffer(1)
        cache = DevicePlaneCache(admit_after=1)
        try:
            plane = cache.get_plane(buf, 0, 2, 1, 0)
            compiled = device_cache._crop_batch_jit._cache_size
            coords = [(0, 0), (128, 64), (640, 512)]
            # the first crop of a class compiles every count up to the
            # largest batch; no later lane count compiles anything
            first = cache.crop_batch(plane, coords[:1], TILE, TILE)
            after = compiled()
            for k, padded in ((1, 1), (2, 2), (3, 4), (5, 8), (8, 8)):
                got = cache.crop_batch(
                    plane, (coords * 3)[:k], TILE, TILE)
                assert got.shape == (padded, TILE, TILE)
                for j, (y, x) in enumerate((coords * 3)[:k]):
                    np.testing.assert_array_equal(
                        np.asarray(got[j]),
                        data[0, 1, 2, y:y + TILE, x:x + TILE])
            assert first.shape == (1, TILE, TILE)
            assert compiled() == after
        finally:
            cache.close()

    def test_the_counters_reach_the_metrics_page(self, warm):
        from omero_ms_pixel_buffer_tpu.utils.metrics import REGISTRY

        text = REGISTRY.exposition()
        for series in (
            "device_plane_admissions_total ",
            "device_plane_evictions_total ",
            "device_plane_bytes ",
            'device_plane_stage_seconds_sum{stage="read"}',
            'device_plane_stage_seconds_sum{stage="h2d"}',
            "device_group_lanes_count ",
            'device_group_lanes_bucket{le="8"}',
        ):
            assert series in text, series

    def test_a_staging_is_named_in_a_profilers_trace(self, stack, tmp_path):
        import glob
        import gzip

        import jax

        service, _ = stack
        buf = service.get_pixel_buffer(1)
        cache = DevicePlaneCache(admit_after=1)
        jax.profiler.start_trace(str(tmp_path))
        try:
            assert cache.get_plane(buf, 0, 3, 2, 0) is not None
        finally:
            jax.profiler.stop_trace()
            cache.close()
        names = b""
        for path in glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True):
            with open(path, "rb") as f:
                names += f.read()
        for path in glob.glob(f"{tmp_path}/**/*.json.gz", recursive=True):
            names += gzip.open(path).read()
        assert b"ompb.plane.stage" in names
