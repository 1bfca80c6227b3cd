"""The serving mesh: TilePipeline.handle_batch end-to-end on the
8-virtual-device CPU mesh (conftest), byte-identical to single-device.

VERDICT r2 item 3: the mesh must actually serve tiles — device-PNG
bucket groups ride ``sharded_batch_filter`` (data parallel over the
mesh) and plane-sized PNG lanes ride ``distributed_filter_plane``
(rows sharded, one-row halo exchange), replacing the reference's
worker-pool parallelism (PixelBufferMicroserviceVerticle.java:224-233)
with ICI-resident parallelism."""

import io

import numpy as np
import pytest
from PIL import Image

from omero_ms_pixel_buffer_tpu.io.ometiff import write_ome_tiff
from omero_ms_pixel_buffer_tpu.io.pixels_service import (
    ImageRegistry,
    PixelsService,
)
from omero_ms_pixel_buffer_tpu.models.tile_pipeline import TilePipeline
from omero_ms_pixel_buffer_tpu.tile_ctx import RegionDef, TileCtx

rng = np.random.default_rng(29)

# 1200 wide: wider than the largest default bucket (1024), so a
# full-plane PNG request cannot take the bucket path and must go
# space-parallel when a mesh is present
IMG = rng.integers(0, 60000, (1, 1, 2, 160, 1200), dtype=np.uint16)


def _ctx(z=0, x=0, y=0, w=64, h=64, fmt="png"):
    return TileCtx(
        image_id=1, z=z, c=0, t=0, region=RegionDef(x, y, w, h),
        format=fmt, omero_session_key="k",
    )


@pytest.fixture(scope="module")
def service(tmp_path_factory):
    root = tmp_path_factory.mktemp("mesh-serving")
    path = str(root / "img.ome.tiff")
    write_ome_tiff(path, IMG, tile_size=(64, 64))
    registry = ImageRegistry()
    registry.add(1, path)
    svc = PixelsService(registry)
    yield svc
    svc.close()


@pytest.fixture
def pipes(service):
    import jax

    assert len(jax.devices()) == 8, "conftest should provide 8 devices"
    multi = TilePipeline(service, engine="device")
    single = TilePipeline(service, engine="device")
    single.mesh = None  # force the single-device stages
    return multi, single


BATCH = [
    _ctx(x=0, y=0, w=64, h=64),
    _ctx(x=128, y=32, w=100, h=80),   # non-bucket-aligned
    _ctx(z=1, x=1150, y=110, w=50, h=50),  # edge tile
    _ctx(x=0, y=0, w=256, h=128),     # larger bucket
    _ctx(x=64, y=0, w=64, h=64, fmt=None),   # raw lane
    _ctx(x=64, y=64, w=64, h=64, fmt="tif"),  # tif lane
    _ctx(w=0, h=0),                   # full plane -> space parallel
]


class TestMeshServing:
    def test_mesh_auto_builds(self, pipes):
        multi, single = pipes
        assert multi._get_mesh() is not None
        assert dict(multi._get_mesh().shape) == {"data": 8}
        assert single._get_mesh() is None

    def test_batch_byte_identical_to_single_device(self, pipes):
        multi, single = pipes
        out_multi = multi.handle_batch([_c for _c in BATCH])
        out_single = single.handle_batch([_c for _c in BATCH])
        assert all(o is not None for o in out_multi)
        # bucketed/raw/tif lanes: identical stages -> identical bytes
        for i in range(6):
            assert out_multi[i] == out_single[i], f"lane {i} differs"

    def test_full_plane_pixels_exact(self, pipes):
        multi, _ = pipes
        out = multi.handle_batch([_ctx(w=0, h=0)])
        png = np.array(Image.open(io.BytesIO(out[0])))
        np.testing.assert_array_equal(png, IMG[0, 0, 0])

    def test_bucketed_pixels_exact(self, pipes):
        multi, _ = pipes
        out = multi.handle_batch([_ctx(x=128, y=32, w=100, h=80)])
        png = np.array(Image.open(io.BytesIO(out[0])))
        np.testing.assert_array_equal(
            png, IMG[0, 0, 0, 32:112, 128:228]
        )

    def test_plane_cache_beside_the_mesh(self, service):
        """With a mesh the plane cache spreads over the mesh's chips;
        a plane below its admission threshold takes the mesh path as
        it did when the mesh superseded the cache."""
        multi = TilePipeline(service, engine="device", use_plane_cache=True)
        assert multi._get_mesh() is not None
        out = multi.handle_batch([_ctx(x=0, y=0, w=64, h=64)])
        png = np.array(Image.open(io.BytesIO(out[0])))
        np.testing.assert_array_equal(png, IMG[0, 0, 0, :64, :64])
        cache = multi._plane_cache
        assert cache.spread and len(cache._chips) == 8
        assert cache.chip_max_bytes == cache.max_bytes // 8
        assert cache.snapshot()["planes"] == 0  # first touch: not admitted

    def test_odd_batch_padding(self, pipes):
        """Lane counts not divisible by the mesh size pad and slice."""
        multi, single = pipes
        ctxs = [
            _ctx(x=64 * i, y=0, w=64, h=64) for i in range(13)
        ]
        out_multi = multi.handle_batch(list(ctxs))
        out_single = single.handle_batch(list(ctxs))
        assert out_multi == out_single


# ---------------------------------------------------------------------------
# background mesh health probe (config mesh.probe-interval-ms)
# ---------------------------------------------------------------------------

class TestBackgroundMeshProbe:
    """A recovered chip must rejoin the mesh BEFORE the next dispatch
    has to fail — probe_open/MeshProber close the reactive-only
    degradation gap."""

    def _run(self, mesh):
        import jax
        import jax.numpy as jnp

        from omero_ms_pixel_buffer_tpu.parallel.sharding import (
            shard_batch,
        )

        n = mesh.shape["data"]
        x = jnp.arange(n * 4, dtype=jnp.int32).reshape(n, 4)
        return jax.block_until_ready(shard_batch(mesh, x) + 1)

    @pytest.mark.resilience
    def test_recovered_chip_rejoins_without_a_failed_batch(self):
        import jax

        from omero_ms_pixel_buffer_tpu.parallel.mesh import MeshManager
        from omero_ms_pixel_buffer_tpu.resilience.breaker import BOARD
        from omero_ms_pixel_buffer_tpu.resilience.faultinject import (
            INJECTOR,
            first_n,
        )

        devices = jax.devices()
        assert len(devices) == 8
        sick = devices[3]
        INJECTOR.clear()
        try:
            # one dispatch failure triggers the reactive probe; the
            # sick chip fails exactly that one probe, then heals
            INJECTOR.install(
                "device.mesh-dispatch",
                first_n(1, RuntimeError("ICI wedged")),
            )
            INJECTOR.install(
                f"device.chip:{sick.id}",
                first_n(1, RuntimeError("chip down")),
            )
            mgr = MeshManager(devices=devices)
            mgr.dispatch(self._run)  # degrades to the 7 survivors
            assert mgr.last_dispatch["n_devices"] == 7
            assert mgr.mesh().devices.size == 7

            # the background pass probes ONLY the excluded chip,
            # which now answers -> breaker heals -> full width again,
            # and no serving batch ever saw the recovery
            healed = mgr.probe_open()
            assert healed == 1
            assert mgr.mesh().devices.size == 8
            mgr.dispatch(self._run)
            assert mgr.last_dispatch["n_devices"] == 8
        finally:
            INJECTOR.clear()
            BOARD.reset()

    @pytest.mark.resilience
    def test_probe_open_skips_healthy_chips(self):
        import jax

        from omero_ms_pixel_buffer_tpu.parallel.mesh import MeshManager
        from omero_ms_pixel_buffer_tpu.resilience.breaker import BOARD
        from omero_ms_pixel_buffer_tpu.resilience.faultinject import (
            INJECTOR,
        )

        INJECTOR.clear()
        try:
            mgr = MeshManager(devices=jax.devices())
            assert mgr.probe_open() == 0  # whole mesh: free no-op
            for dev in mgr._devices:
                assert INJECTOR.calls(
                    f"device.chip:{dev.id}"
                ) == 0  # no probe traffic touched healthy chips
        finally:
            INJECTOR.clear()
            BOARD.reset()

    @pytest.mark.resilience
    def test_prober_thread_restores_width(self):
        import time

        import jax

        from omero_ms_pixel_buffer_tpu.parallel.mesh import (
            MeshManager,
            MeshProber,
        )
        from omero_ms_pixel_buffer_tpu.resilience.breaker import BOARD
        from omero_ms_pixel_buffer_tpu.resilience.faultinject import (
            INJECTOR,
            first_n,
        )

        devices = jax.devices()
        sick = devices[5]
        INJECTOR.clear()
        try:
            INJECTOR.install(
                "device.mesh-dispatch", first_n(1, RuntimeError("down"))
            )
            INJECTOR.install(
                f"device.chip:{sick.id}",
                first_n(1, RuntimeError("down")),
            )
            mgr = MeshManager(devices=devices)
            mgr.dispatch(self._run)
            assert mgr.mesh().devices.size == 7
            prober = MeshProber(lambda: mgr, interval_s=0.02)
            prober.start()
            try:
                deadline = time.monotonic() + 10.0
                while time.monotonic() < deadline:
                    if len(mgr.healthy_devices()) == 8:
                        break
                    time.sleep(0.02)
                assert len(mgr.healthy_devices()) == 8
            finally:
                prober.stop()
            mgr.dispatch(self._run)
            assert mgr.last_dispatch["n_devices"] == 8
        finally:
            INJECTOR.clear()
            BOARD.reset()


# ---------------------------------------------------------------------------
# The plane cache a chip, beside the mesh (four of the virtual devices)
# ---------------------------------------------------------------------------

Z, C, SIDE, TILE = 8, 3, 96, 32
STACK = np.random.default_rng(3302).integers(
    0, 4000, (1, C, Z, SIDE, SIDE), dtype=np.uint16
)
SWEEP = [(z, c) for z in range(Z) for c in range(C)]  # z outer, c inner


def _zc(z, c, x, y, w=TILE, h=TILE):
    return TileCtx(
        image_id=1, z=z, c=c, t=0, region=RegionDef(x, y, w, h),
        format="png", omero_session_key="k",
    )


@pytest.fixture(scope="module")
def stack_service(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("mesh-stack") / "stack.ome.tiff")
    write_ome_tiff(path, STACK, tile_size=(TILE, TILE))
    registry = ImageRegistry()
    registry.add(1, path)
    svc = PixelsService(registry)
    yield svc
    svc.close()


@pytest.fixture(scope="module")
def stack_pipes(stack_service):
    """(four chips, one device), both with the device deflate the
    deployment runs; the four-chip one has served the whole sweep
    twice, so all 24 planes are resident, six a chip."""
    import jax

    from omero_ms_pixel_buffer_tpu.parallel.mesh import make_mesh

    def build():
        return TilePipeline(
            stack_service, engine="device", use_pallas=False,
            buckets=(TILE,), device_deflate=True,
            device_deflate_mode="dynamic", max_batch=8,
        )

    four, one = build(), build()
    four.mesh = make_mesh(("data",), devices=jax.devices()[:4])
    one.mesh = None
    for _ in range(2):
        for start in range(0, len(SWEEP), 8):
            four.handle_batch(
                [_zc(z, c, 32, 64) for z, c in SWEEP[start:start + 8]]
            )
    yield four, one
    four.close()
    one.close()


class TestPlaneCacheOnFourChips:
    def test_the_stack_is_resident_six_planes_a_chip(self, stack_pipes):
        four, _ = stack_pipes
        snap = four.plane_cache_snapshot()
        assert snap["planes"] == 24 and snap["evictions"] == 0
        assert [row["planes"] for row in snap["per_chip"]] == [6, 6, 6, 6]
        assert snap["devices"] == [0, 1, 2, 3]

    @pytest.mark.parametrize("x, y", [(0, 0), (32, 64), (17, 5), (64, 64)])
    def test_a_z_sweep_is_the_numpy_crop_and_the_one_device_bytes(
        self, stack_pipes, x, y
    ):
        four, one = stack_pipes
        before = four.plane_cache_snapshot()
        lanes_before = [row["lanes"] for row in before["per_chip"]]
        for start in range(0, len(SWEEP), 8):  # a batch: 8 lanes, 8 planes
            ctxs = [_zc(z, c, x, y) for z, c in SWEEP[start:start + 8]]
            out_four = four.handle_batch(list(ctxs))
            out_one = one.handle_batch(list(ctxs))
            for (z, c), got, ref in zip(SWEEP[start:], out_four, out_one):
                np.testing.assert_array_equal(
                    np.array(Image.open(io.BytesIO(got))),
                    STACK[0, c, z, y:y + TILE, x:x + TILE],
                )
                assert got == ref  # the stream's bytes, not only pixels
        after = four.plane_cache_snapshot()
        assert after["misses"] == before["misses"]  # every lane a hit
        assert after["hits"] - before["hits"] == 24
        # every lane was cropped on its plane's chip: six a chip
        assert [
            row["lanes"] - was
            for row, was in zip(after["per_chip"], lanes_before)
        ] == [6, 6, 6, 6]

    def test_groups_name_their_chip_and_share_one_pipe(
        self, stack_pipes
    ):
        four, _ = stack_pipes
        queue = four.device_queue_snapshot()
        assert [row["chip"] for row in queue["chips"]] == [0, 1, 2, 3]
        assert all(row["groups"] > 0 for row in queue["chips"])
        assert sum(r["groups"] for r in queue["chips"]) <= queue["groups"]
        assert queue["chip_pipe"] == {"workers": 4, "inflight": 0}

    def test_an_edge_lane_beside_resident_ones_takes_the_mesh_path(
        self, stack_pipes
    ):
        """A lane whose bucket would clamp at the plane's edge is not
        eligible for the crop; it shards over the mesh as before, in
        the same batch as lanes served from resident planes."""
        four, one = stack_pipes
        ctxs = [
            _zc(0, 0, 8, 8),
            _zc(0, 1, 8, 8),
            _zc(0, 0, 80, 72, w=16, h=24),  # 80 + 32 > 96: the edge
            _zc(3, 2, 40, 40),
        ]
        lanes = [r["lanes"] for r in four.plane_cache_snapshot()["per_chip"]]
        mesh_before = four.last_mesh_dispatch
        out_four = four.handle_batch(list(ctxs))
        out_one = one.handle_batch(list(ctxs))
        for ctx, got, ref in zip(ctxs, out_four, out_one):
            r = ctx.region
            np.testing.assert_array_equal(
                np.array(Image.open(io.BytesIO(got))),
                STACK[0, ctx.c, ctx.z, r.y:r.y + r.height,
                      r.x:r.x + r.width],
            )
            assert got == ref
        served = [
            row["lanes"] - was for row, was in zip(
                four.plane_cache_snapshot()["per_chip"], lanes)
        ]
        assert sum(served) == 3  # the three eligible lanes only
        mesh_after = four.last_mesh_dispatch
        assert mesh_after is not None and mesh_after is not mesh_before
        assert mesh_after["n_devices"] == 4

    def test_no_lane_fell_to_the_host(self, stack_pipes):
        from omero_ms_pixel_buffer_tpu.models import tile_pipeline

        fallen = dict(tile_pipeline.TILE_DEVICE_FALLBACK._values)
        four, _ = stack_pipes
        four.handle_batch([_zc(z, c, 48, 16) for z, c in SWEEP[:8]])
        assert dict(tile_pipeline.TILE_DEVICE_FALLBACK._values) == fallen

    def test_the_series_name_the_chip(self, stack_pipes):
        from omero_ms_pixel_buffer_tpu.models import (
            device_cache,
            tile_pipeline,
        )

        for chip in "0123":
            key = (("chip", chip),)
            assert device_cache.PLANE_ADMISSIONS._values[key] >= 6
            assert device_cache.PLANE_BYTES._values[key] == 6 * SIDE * SIDE * 2
            assert tile_pipeline.TILE_DEVICE_LANES._values[key] >= 6
            assert (("chip", chip), ("stage", "h2d")) in (
                device_cache.PLANE_STAGE_SECONDS._sums)
