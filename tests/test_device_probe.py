"""Device probe + engine resolution: one process on the chip, no quiet
host. The probe runs in the serving process, once; `engine: device` is
strict (no chip found, nobody asked for the CPU -> start-up fails);
`auto` decides before the port opens and says why.
"""

import logging
import subprocess

import pytest

from omero_ms_pixel_buffer_tpu.models.tile_pipeline import TilePipeline
from omero_ms_pixel_buffer_tpu.runtime import device_probe


@pytest.fixture(autouse=True)
def fresh_probe():
    device_probe.reset()
    yield
    device_probe.reset()


def _fake_probe(monkeypatch, platform="tpu", link_mbps=5000.0, count=1):
    found = {
        "platform": platform, "kind": f"fake {platform}",
        "count": count, "link_mbps": link_mbps,
    }
    monkeypatch.setattr(device_probe, "probe", lambda: found)
    return found


class TestProbe:
    def test_reports_this_process_backend_without_a_child(
        self, monkeypatch
    ):
        import jax

        def no_children(*a, **k):
            raise AssertionError("the probe must not start a process")

        monkeypatch.setattr(subprocess, "Popen", no_children)
        found = device_probe.probe()
        assert found["platform"] == jax.devices()[0].platform == "cpu"
        assert found["kind"] == jax.devices()[0].device_kind
        assert found["count"] == len(jax.devices())
        assert found["link_mbps"] > 0

    def test_runs_once_per_process(self, monkeypatch):
        first = device_probe.probe()
        monkeypatch.setattr(
            device_probe, "_link_mbps",
            lambda: pytest.fail("second probe measured the link again"),
        )
        assert device_probe.probe() is first

    def test_backend_error_is_not_swallowed(self, monkeypatch):
        import jax

        def broken():
            raise RuntimeError("TPU backend setup failed")

        monkeypatch.setattr(jax, "devices", broken)
        with pytest.raises(RuntimeError, match="backend setup failed"):
            device_probe.probe()


class TestAutoEngine:
    def test_platform_pinned_off_tpu_is_host_without_a_backend(
        self, monkeypatch
    ):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        monkeypatch.setattr(
            device_probe, "probe",
            lambda: pytest.fail("auto probed though JAX_PLATFORMS=cpu"),
        )
        pipe = TilePipeline(None, engine="auto")
        info = pipe.resolve_engine()
        assert pipe.engine == info["engine"] == "host"
        assert "JAX_PLATFORMS=cpu" in info["reason"]
        assert info["device"] is None

    def test_fast_link_on_a_chip_is_device(self, monkeypatch):
        monkeypatch.delenv("JAX_PLATFORMS")
        monkeypatch.setenv("OMPB_DEVICE_MIN_MBPS", "1000")
        found = _fake_probe(monkeypatch, "tpu", link_mbps=5000.0)
        pipe = TilePipeline(None, engine="auto")
        info = pipe.resolve_engine()
        assert pipe.engine == info["engine"] == "device"
        assert info["auto_verdict"] == "device"
        assert info["link_mbps"] == 5000.0
        assert info["device"] == {
            "platform": "tpu", "kind": found["kind"], "count": 1,
        }

    def test_slow_link_is_host_and_says_so_at_warning(
        self, monkeypatch, caplog
    ):
        monkeypatch.delenv("JAX_PLATFORMS")
        monkeypatch.setenv("OMPB_DEVICE_MIN_MBPS", "1000")
        _fake_probe(monkeypatch, "tpu", link_mbps=9.4)
        pipe = TilePipeline(None, engine="auto")
        with caplog.at_level(logging.WARNING):
            info = pipe.resolve_engine()
        assert info["engine"] == "host"
        assert "9.4" in info["reason"]
        assert any(
            r.levelno == logging.WARNING and "9.4" in r.getMessage()
            for r in caplog.records
        )
        assert pipe.resolve_engine() is info  # decided once

    def test_probe_error_fails_instead_of_serving_host(self, monkeypatch):
        monkeypatch.delenv("JAX_PLATFORMS")

        def broken():
            raise RuntimeError("chip held by another process")

        monkeypatch.setattr(device_probe, "probe", broken)
        pipe = TilePipeline(None, engine="auto")
        with pytest.raises(RuntimeError, match="another process"):
            pipe.resolve_engine()


class TestStrictDeviceEngine:
    @pytest.mark.parametrize("platforms", [None, "tpu", "tpu,cpu"])
    def test_no_chip_and_nobody_asked_for_the_cpu_fails(
        self, monkeypatch, platforms
    ):
        if platforms is None:
            monkeypatch.delenv("JAX_PLATFORMS")
        else:
            monkeypatch.setenv("JAX_PLATFORMS", platforms)
        _fake_probe(monkeypatch, "cpu")
        pipe = TilePipeline(None, engine="device")
        with pytest.raises(RuntimeError, match="found no TPU"):
            pipe.resolve_engine()

    def test_explicit_cpu_runs_the_device_programs_there(
        self, monkeypatch
    ):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        pipe = TilePipeline(None, engine="device")
        info = pipe.resolve_engine()
        assert info["engine"] == "device"  # never resolves to host
        assert info["device"]["platform"] == "cpu"
        assert info["auto_verdict"] == "host"

    def test_host_engine_never_touches_the_backend(self, monkeypatch):
        monkeypatch.setattr(
            device_probe, "probe",
            lambda: pytest.fail("engine: host initialised a backend"),
        )
        info = TilePipeline(None, engine="host").resolve_engine()
        assert info["engine"] == "host"
        assert info["device"] is None


class TestServerStartup:
    def test_engine_is_decided_before_the_port_opens(self, monkeypatch):
        from omero_ms_pixel_buffer_tpu.http.server import PixelBufferApp
        from omero_ms_pixel_buffer_tpu.utils.config import Config

        monkeypatch.delenv("JAX_PLATFORMS")
        monkeypatch.setenv("OMPB_DEVICE_MIN_MBPS", "1000")
        _fake_probe(monkeypatch, "tpu", link_mbps=12.0)
        app = PixelBufferApp(
            Config.from_dict({"session-store": {"type": "memory"}})
        )
        # construction alone (no app start, no request) resolved it
        assert app.pipeline._engine == "host"
        assert "12.0" in app.pipeline._engine_info["reason"]
