"""End-to-end HTTP integration: routes, auth, error mapping, headers,
format matrix — the reference's manual-curl verification matrix
(README.md:129-144) as automated tests, against a fake session store +
synthetic fixtures (SURVEY.md §4)."""

import asyncio
import io
import json

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer
from PIL import Image

from omero_ms_pixel_buffer_tpu.auth.stores import MemorySessionStore
from omero_ms_pixel_buffer_tpu.http.server import PixelBufferApp
from omero_ms_pixel_buffer_tpu.io.ometiff import write_ome_tiff
from omero_ms_pixel_buffer_tpu.io.pixels_service import (
    ImageRegistry,
    PixelsService,
)
from omero_ms_pixel_buffer_tpu.io.zarr import write_ngff
from omero_ms_pixel_buffer_tpu.utils.config import Config

rng = np.random.default_rng(3)

IMG = rng.integers(0, 60000, (1, 2, 4, 96, 128), dtype=np.uint16)


@pytest.fixture
def client(tmp_path, loop):
    write_ome_tiff(
        str(tmp_path / "img.ome.tiff"), IMG, tile_size=(64, 64),
        pyramid_levels=2,
    )
    zarr_img = rng.integers(0, 255, (1, 1, 1, 64, 64), dtype=np.uint8)
    write_ngff(str(tmp_path / "img.zarr"), zarr_img)
    registry = ImageRegistry()
    registry.add(1, str(tmp_path / "img.ome.tiff"))
    registry.add(2, str(tmp_path / "img.zarr"), type="zarr")
    store = MemorySessionStore({"cookie-1": "omero-key-1"})
    config = Config.from_dict(
        {"session-store": {"type": "memory"},
         "backend": {"batching": {"coalesce-window-ms": 1.0}}}
    )
    app_obj = PixelBufferApp(
        config,
        pixels_service=PixelsService(registry),
        session_store=store,
    )
    client = TestClient(TestServer(app_obj.make_app()), loop=loop)
    loop.run_until_complete(client.start_server())
    yield client
    loop.run_until_complete(client.close())


AUTH = {"Cookie": "sessionid=cookie-1"}


class TestRoutes:
    async def test_options_discovery(self, client):
        resp = await client.request("OPTIONS", "/")
        assert resp.status == 200
        body = await resp.json()
        assert body["provider"] == "PixelBufferMicroservice"
        assert "version" in body and body["features"] == []

    async def test_metrics_unauthenticated(self, client):
        resp = await client.get("/metrics")
        assert resp.status == 200
        text = await resp.text()
        assert "# TYPE" in text

    async def test_raw_tile(self, client):
        resp = await client.get(
            "/tile/1/0/0/0?x=8&y=16&w=32&h=24", headers=AUTH
        )
        assert resp.status == 200
        assert resp.headers["Content-Type"] == "application/octet-stream"
        body = await resp.read()
        assert resp.headers["Content-Length"] == str(len(body))
        assert (
            resp.headers["Content-Disposition"]
            == 'attachment; filename="image1_z0_c0_t0_x8_y16_w32_h24.bin"'
        )
        # raw bytes are big-endian uint16
        tile = np.frombuffer(body, dtype=">u2").reshape(24, 32)
        np.testing.assert_array_equal(
            tile.astype(np.uint16), IMG[0, 0, 0, 16:40, 8:40]
        )

    async def test_png_tile(self, client):
        resp = await client.get(
            "/tile/1/1/1/0?x=0&y=0&w=64&h=64&format=png", headers=AUTH
        )
        assert resp.status == 200
        assert resp.headers["Content-Type"] == "image/png"
        body = await resp.read()
        decoded = np.array(Image.open(io.BytesIO(body)))
        np.testing.assert_array_equal(
            decoded.astype(np.uint16), IMG[0, 1, 1, :64, :64]
        )

    async def test_tif_tile(self, client):
        resp = await client.get(
            "/tile/1/0/0/0?w=48&h=32&format=tif", headers=AUTH
        )
        assert resp.status == 200
        assert resp.headers["Content-Type"] == "image/tiff"
        body = await resp.read()
        decoded = np.array(Image.open(io.BytesIO(body)))
        np.testing.assert_array_equal(
            decoded.astype(np.uint16), IMG[0, 0, 0, :32, :48]
        )
        assert resp.headers["Content-Disposition"].endswith('.tif"')

    async def test_wh_zero_defaults_full_plane(self, client):
        resp = await client.get("/tile/2/0/0/0", headers=AUTH)
        assert resp.status == 200
        body = await resp.read()
        assert len(body) == 64 * 64  # uint8 full plane
        assert "w64_h64" in resp.headers["Content-Disposition"]

    async def test_resolution_level(self, client):
        resp = await client.get(
            "/tile/1/0/0/0?resolution=1&w=64&h=48", headers=AUTH
        )
        assert resp.status == 200
        tile = np.frombuffer(await resp.read(), dtype=">u2").reshape(48, 64)
        np.testing.assert_array_equal(
            tile.astype(np.uint16), IMG[0, 0, 0, ::2, ::2][:48, :64]
        )


class TestErrors:
    async def test_no_cookie_403(self, client):
        resp = await client.get("/tile/1/0/0/0")
        assert resp.status == 403

    async def test_unknown_session_403(self, client):
        resp = await client.get(
            "/tile/1/0/0/0", headers={"Cookie": "sessionid=nope"}
        )
        assert resp.status == 403

    async def test_bad_param_400(self, client):
        resp = await client.get("/tile/abc/0/0/0", headers=AUTH)
        assert resp.status == 400
        assert "abc" in await resp.text()

    async def test_unknown_image_404(self, client):
        resp = await client.get("/tile/99/0/0/0", headers=AUTH)
        assert resp.status == 404

    async def test_unknown_format_404(self, client):
        resp = await client.get(
            "/tile/1/0/0/0?format=bmp&w=8&h=8", headers=AUTH
        )
        assert resp.status == 404

    async def test_out_of_bounds_404(self, client):
        resp = await client.get(
            "/tile/1/0/0/0?x=120&y=90&w=64&h=64", headers=AUTH
        )
        assert resp.status == 404

    async def test_bad_z_404(self, client):
        resp = await client.get("/tile/1/9/0/0?w=8&h=8", headers=AUTH)
        assert resp.status == 404

    async def test_bad_resolution_404(self, client):
        resp = await client.get(
            "/tile/1/0/0/0?resolution=7&w=8&h=8", headers=AUTH
        )
        assert resp.status == 404


class TestBatching:
    async def test_concurrent_requests_coalesce(self, client):
        import asyncio

        async def fetch(z, c):
            resp = await client.get(
                f"/tile/1/{z}/{c}/0?w=64&h=64&format=png", headers=AUTH
            )
            assert resp.status == 200
            return np.array(Image.open(io.BytesIO(await resp.read())))

        results = await asyncio.gather(
            *(fetch(z, c) for z in range(4) for c in range(2))
        )
        i = 0
        for z in range(4):
            for c in range(2):
                np.testing.assert_array_equal(
                    results[i].astype(np.uint16), IMG[0, c, z, :64, :64]
                )
                i += 1

    async def test_mixed_formats_in_one_burst(self, client):
        import asyncio

        async def fetch(fmt):
            url = f"/tile/1/0/0/0?w=32&h=32"
            if fmt:
                url += f"&format={fmt}"
            resp = await client.get(url, headers=AUTH)
            return resp.status, await resp.read()

        results = await asyncio.gather(
            *(fetch(f) for f in [None, "png", "tif", None, "png"])
        )
        for status, _ in results:
            assert status == 200


class TestRgbImage:
    """RGB (SamplesPerPixel=3) images through the full HTTP surface."""

    @pytest.fixture
    def rgb_client(self, tmp_path, loop):
        rgb = rng.integers(0, 255, (1, 1, 1, 48, 56, 3), dtype=np.uint8)
        write_ome_tiff(
            str(tmp_path / "rgb.ome.tiff"), rgb, tile_size=(32, 32)
        )
        registry = ImageRegistry()
        registry.add(1, str(tmp_path / "rgb.ome.tiff"))
        store = MemorySessionStore({"cookie-1": "omero-key-1"})
        config = Config.from_dict({"session-store": {"type": "memory"}})
        app_obj = PixelBufferApp(
            config, pixels_service=PixelsService(registry),
            session_store=store,
        )
        client = TestClient(TestServer(app_obj.make_app()), loop=loop)
        loop.run_until_complete(client.start_server())
        yield client, rgb[0, 0, 0]
        loop.run_until_complete(client.close())

    def test_rgb_channels_served_separately(self, rgb_client, loop):
        """OMERO semantics: an RGB image is SizeC=3; channel c serves
        that sample as a grayscale tile (viewers compose client-side)."""
        client, truth = rgb_client

        async def run():
            for c in range(3):
                r = await client.get(
                    f"/tile/1/0/{c}/0?x=8&y=4&w=32&h=24&format=png",
                    headers=AUTH,
                )
                assert r.status == 200
                png = np.array(Image.open(io.BytesIO(await r.read())))
                np.testing.assert_array_equal(png, truth[4:28, 8:40, c])
            r2 = await client.get(
                "/tile/1/0/2/0?x=0&y=0&w=56&h=48&format=tif",
                headers=AUTH,
            )
            assert r2.status == 200
            tif = np.array(Image.open(io.BytesIO(await r2.read())))
            np.testing.assert_array_equal(tif, truth[:, :, 2])
            # channel out of range -> 404, like any bad coordinate
            r3 = await client.get(
                "/tile/1/0/3/0?w=8&h=8", headers=AUTH
            )
            assert r3.status == 404

        loop.run_until_complete(run())


class TestFloatImage:
    """float32 pixels: raw and TIFF serve; PNG has no float -> 404
    (the reference's encode-failure -> null -> 404 path)."""

    @pytest.fixture
    def float_client(self, tmp_path, loop):
        data = rng.normal(0, 1, (1, 1, 1, 32, 40)).astype(np.float32)
        write_ome_tiff(str(tmp_path / "f.ome.tiff"), data)
        registry = ImageRegistry()
        registry.add(1, str(tmp_path / "f.ome.tiff"))
        store = MemorySessionStore({"cookie-1": "omero-key-1"})
        config = Config.from_dict({"session-store": {"type": "memory"}})
        app_obj = PixelBufferApp(
            config, pixels_service=PixelsService(registry),
            session_store=store,
        )
        client = TestClient(TestServer(app_obj.make_app()), loop=loop)
        loop.run_until_complete(client.start_server())
        yield client, data[0, 0, 0]
        loop.run_until_complete(client.close())

    def test_float_formats(self, float_client, loop):
        client, truth = float_client

        async def run():
            r = await client.get("/tile/1/0/0/0?w=0&h=0", headers=AUTH)
            assert r.status == 200
            raw = np.frombuffer(await r.read(), dtype=">f4").reshape(32, 40)
            np.testing.assert_array_equal(
                raw.astype(np.float32), truth
            )
            r2 = await client.get(
                "/tile/1/0/0/0?w=0&h=0&format=tif", headers=AUTH
            )
            assert r2.status == 200
            tif = np.array(Image.open(io.BytesIO(await r2.read())))
            np.testing.assert_array_equal(tif, truth)
            r3 = await client.get(
                "/tile/1/0/0/0?w=8&h=8&format=png", headers=AUTH
            )
            assert r3.status == 404  # no float PNG

        loop.run_until_complete(run())


class TestGuardsAndFuzz:
    def test_oversized_tile_404(self, tmp_path, loop):
        data = np.zeros((1, 1, 1, 64, 64), np.uint16)
        write_ome_tiff(str(tmp_path / "g.ome.tiff"), data)
        registry = ImageRegistry()
        registry.add(1, str(tmp_path / "g.ome.tiff"))
        store = MemorySessionStore({"cookie-1": "omero-key-1"})
        config = Config.from_dict(
            {"session-store": {"type": "memory"},
             "backend": {"max-tile-mb": 0}}  # disabled -> full plane OK
        )
        assert config.backend.max_tile_mb == 0
        from omero_ms_pixel_buffer_tpu.models.tile_pipeline import (
            TilePipeline,
        )

        pipe = TilePipeline(
            PixelsService(registry), engine="host", max_tile_bytes=1024
        )
        from omero_ms_pixel_buffer_tpu.tile_ctx import RegionDef, TileCtx

        big = TileCtx(
            image_id=1, z=0, c=0, t=0, region=RegionDef(0, 0, 0, 0),
            format=None, omero_session_key="k",
        )  # full plane = 8 KiB > 1 KiB guard
        assert pipe.handle(big) is None  # -> 404 via broad catch
        small = TileCtx(
            image_id=1, z=0, c=0, t=0, region=RegionDef(0, 0, 16, 16),
            format=None, omero_session_key="k",
        )
        assert pipe.handle(small) is not None

    def test_param_fuzz_never_500(self, client, loop):
        """Garbage params must map to 4xx/404, never 500."""
        cases = [
            "/tile/1/0/0/0?x=-5&y=0&w=8&h=8",
            "/tile/1/0/0/0?w=1e9&h=2",
            "/tile/1/0/0/0?resolution=-1&w=8&h=8",
            "/tile/1/0/0/0?resolution=99&w=8&h=8",
            "/tile/1/zz/0/0?w=8&h=8",
            "/tile/1/0/0/0?w=8&h=8&format=bmp",
            "/tile/99999999999999999999/0/0/0?w=8&h=8",
            "/tile/1/0/0/0?x=999999&y=999999&w=8&h=8",
        ]

        async def run():
            for path in cases:
                r = await client.get(path, headers=AUTH)
                assert 400 <= r.status < 500, (path, r.status)

        loop.run_until_complete(run())


class TestEngineVisibility:
    """One process on the chip, no quiet host: /healthz says which
    engine serves, why, and on what device; `engine: device` is strict
    at start-up; a device-path failure that degrades to the host is
    counted, not just logged."""

    @pytest.fixture
    def device_client(self, tmp_path, loop):
        write_ome_tiff(
            str(tmp_path / "img.ome.tiff"), IMG, tile_size=(64, 64)
        )
        registry = ImageRegistry()
        registry.add(1, str(tmp_path / "img.ome.tiff"))
        config = Config.from_dict(
            {"session-store": {"type": "memory"},
             "cache": {"enabled": False},
             "backend": {
                 "engine": "device",
                 "png": {"device-deflate": True},
                 # wide window: two concurrent requests coalesce into
                 # one batch (a singleton takes the single-request path)
                 "batching": {"coalesce-window-ms": 100.0,
                              "buckets": [64]},
             }}
        )
        app_obj = PixelBufferApp(
            config,
            pixels_service=PixelsService(registry),
            session_store=MemorySessionStore({"cookie-1": "omero-key-1"}),
        )
        client = TestClient(TestServer(app_obj.make_app()), loop=loop)
        loop.run_until_complete(client.start_server())
        yield client
        loop.run_until_complete(client.close())

    async def test_healthz_names_engine_reason_and_device(
        self, device_client
    ):
        import jax

        body = await (await device_client.get("/healthz")).json()
        assert body["engine"] == "device"
        assert body["engine_reason"] == "configured"
        assert body["device"] == {
            "platform": "cpu",
            "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
        }
        assert body["link_mbps"] > 0
        assert body["auto_verdict"] == "host"  # no chip here
        assert "tile_device_fallback_total" in body
        assert "render_fallback_total" in body

    async def test_host_engine_reports_no_device(self, client):
        body = await (await client.get("/healthz")).json()
        # conftest pins JAX_PLATFORMS=cpu, so the default `auto` is
        # host and says why
        assert body["engine"] == "host"
        assert "JAX_PLATFORMS=cpu" in body["engine_reason"]
        assert body["device"] is None

    def test_device_engine_without_a_chip_fails_startup(
        self, monkeypatch
    ):
        # nobody asked for the CPU (JAX_PLATFORMS unset), yet JAX
        # finds no tpu backend: an error, not a CPU run
        monkeypatch.delenv("JAX_PLATFORMS")
        config = Config.from_dict(
            {"session-store": {"type": "memory"},
             "backend": {"engine": "device"}}
        )
        with pytest.raises(RuntimeError, match="found no TPU"):
            PixelBufferApp(config)

    async def test_device_group_failure_is_counted_and_tile_still_200(
        self, device_client
    ):
        from omero_ms_pixel_buffer_tpu.resilience import INJECTOR
        from omero_ms_pixel_buffer_tpu.resilience.faultinject import (
            first_n,
        )

        async def fallbacks():
            body = await (await device_client.get("/healthz")).json()
            return body["tile_device_fallback_total"]

        before = await fallbacks()
        INJECTOR.install(
            "device.encode-group",
            first_n(1, RuntimeError("injected device group failure")),
        )
        try:
            resps = await asyncio.gather(*(
                device_client.get(
                    f"/tile/1/0/0/0?x={x}&y=0&w=64&h=64&format=png",
                    headers=AUTH,
                )
                for x in (0, 64)
            ))
            for x, resp in zip((0, 64), resps):
                assert resp.status == 200
                decoded = np.array(
                    Image.open(io.BytesIO(await resp.read()))
                )
                np.testing.assert_array_equal(
                    decoded.astype(np.uint16),
                    IMG[0, 0, 0, :64, x : x + 64],
                )
            assert INJECTOR.calls("device.encode-group") >= 1
        finally:
            INJECTOR.clear()
        assert await fallbacks() == before + 2  # both lanes of the group
        metrics = await (await device_client.get("/metrics")).text()
        assert 'tile_device_fallback_total{site="encode_group"}' in metrics
