"""Loop watchdog (utils/loop_watchdog.py) — the runtime twin of the
``loop-block`` static rule: a deliberately blocked loop must raise the
lag metric, count a blocked event, and surface on /healthz."""

import asyncio
import logging
import time

import pytest

from omero_ms_pixel_buffer_tpu.utils.loop_watchdog import LoopWatchdog
from omero_ms_pixel_buffer_tpu.utils.metrics import REGISTRY


def test_blocked_loop_detected(caplog):
    async def scenario():
        wd = LoopWatchdog(interval_s=0.02, warn_after_s=0.1)
        wd.start()
        await asyncio.sleep(0.08)  # healthy beats first
        with caplog.at_level(
            logging.WARNING, "omero_ms_pixel_buffer_tpu.loop_watchdog"
        ):
            time.sleep(0.4)  # deliberately wedge the loop
            await asyncio.sleep(0.15)  # heartbeat observes + recovery
        snap = wd.snapshot()
        wd.stop()
        return snap

    snap = asyncio.run(scenario())
    # the 400 ms stall shows up as heartbeat lag...
    assert snap["max_lag_ms"] >= 200
    # ...and as an edge-triggered blocked event with a stack dump
    assert snap["blocked_events"] >= 1
    assert not snap["blocked"]  # recovered after the sleep
    blocked_logs = [
        r for r in caplog.records if "event loop blocked" in r.message
    ]
    assert blocked_logs
    # the dump names the offender: the time.sleep frame in this test
    assert "time.sleep(0.4)" in blocked_logs[0].getMessage()


def test_healthy_loop_stays_quiet():
    async def scenario():
        wd = LoopWatchdog(interval_s=0.02, warn_after_s=0.5)
        wd.start()
        await asyncio.sleep(0.2)
        snap = wd.snapshot()
        wd.stop()
        return snap

    snap = asyncio.run(scenario())
    assert snap["blocked_events"] == 0
    assert not snap["blocked"]


def test_stop_from_another_thread():
    """stop() may be called off the loop thread (management threads,
    signal handlers): the heartbeat cancel must hop through
    call_soon_threadsafe, not touch the Task directly."""
    import threading

    async def scenario():
        wd = LoopWatchdog(interval_s=0.02, warn_after_s=0.5)
        wd.start()
        await asyncio.sleep(0.05)
        t = threading.Thread(target=wd.stop)
        t.start()
        await asyncio.sleep(0.05)  # loop runs the threadsafe cancel
        t.join(timeout=2.0)
        assert not t.is_alive()
        assert wd._task is None and wd._thread is None

    asyncio.run(scenario())


def test_stop_is_idempotent_and_restartable():
    async def scenario():
        wd = LoopWatchdog(interval_s=0.02, warn_after_s=0.5)
        wd.start()
        wd.start()  # second arm is a no-op
        await asyncio.sleep(0.05)
        wd.stop()
        wd.stop()

    asyncio.run(scenario())


def test_metrics_exported():
    text = REGISTRY.exposition()
    assert "event_loop_lag_seconds" in text
    assert "event_loop_blocked_total" in text
    assert "event_loop_max_lag_seconds" in text
    assert "gil_wait_seconds" in text


async def test_healthz_reports_loop_health(tmp_path, loop):
    """End-to-end: the app arms the watchdog on startup and /healthz
    carries its snapshot (watchdog tuned hot so the test is fast)."""
    from test_resilience import _make_app

    app_obj, client = await _make_app(
        tmp_path,
        resilience={"watchdog": {"interval-ms": 10, "warn-ms": 50}},
    )
    try:
        body = await (await client.get("/healthz")).json()
        assert body["loop"]["enabled"] is True
        assert body["loop"]["blocked_events"] == 0
        assert "max_lag_ms" in body["loop"]
    finally:
        await client.close()
    assert app_obj.watchdog is not None
    assert app_obj.watchdog._thread is None  # stopped on cleanup


async def test_watchdog_disabled_by_config(tmp_path, loop):
    from test_resilience import _make_app

    app_obj, client = await _make_app(
        tmp_path, resilience={"watchdog": {"enabled": False}}
    )
    try:
        body = await (await client.get("/healthz")).json()
        assert body["loop"] == {"enabled": False}
        assert app_obj.watchdog is None
    finally:
        await client.close()


# -- the GIL probe on the watchdog's thread ----------------------------------

def _gil_waits(over=None):
    """Observations of `gil_wait_seconds` so far; with `over`, those
    above that bucket edge."""
    from omero_ms_pixel_buffer_tpu.utils.loop_watchdog import GIL_WAIT

    lines = {line.rpartition(" ")[0]: float(line.rpartition(" ")[2])
             for line in GIL_WAIT.collect() if not line.startswith("#")}
    count = lines.get("gil_wait_seconds_count", 0.0)
    if over is None:
        return count
    return count - lines.get(f'gil_wait_seconds_bucket{{le="{over}"}}', 0.0)


def test_the_probe_wakes_about_fifty_times_a_second():
    async def scenario():
        wd = LoopWatchdog(interval_s=0.02, warn_after_s=1.0)
        before = _gil_waits()
        wd.start()
        await asyncio.sleep(1.0)
        wd.stop()
        return _gil_waits() - before

    woke = asyncio.run(scenario())
    assert 25 <= woke <= 52  # one wake each 20 ms, never early


def _holds_the_gil_for(seconds: float) -> int:
    """A size for `sum(range(n))`, one C call that never gives the GIL
    up (a loop of Python statements would: the interpreter hands it
    over every 5 ms), taking about `seconds`."""
    n, took = 1_000_000, float("inf")
    for _ in range(3):
        t = time.perf_counter()
        sum(range(n))
        took = min(took, time.perf_counter() - t)
    return int(n * seconds / max(took, 1e-6))


def test_a_c_call_that_holds_the_gil_shows_as_the_probes_wait():
    import threading

    n = _holds_the_gil_for(0.2)

    async def scenario():
        wd = LoopWatchdog(interval_s=0.02, warn_after_s=1.0)
        wd.start()
        await asyncio.sleep(0.1)
        before = _gil_waits(over=0.05)
        hog = threading.Thread(target=lambda: sum(range(n)))
        t = time.perf_counter()
        hog.start()
        hog.join()
        held_ms = 1e3 * (time.perf_counter() - t)
        await asyncio.sleep(0.1)  # the probe wakes and observes
        wd.stop()
        return held_ms, _gil_waits(over=0.05) - before

    held_ms, late = asyncio.run(scenario())
    assert held_ms >= 100
    assert late >= 1  # a wake that waited more than 50 ms for the GIL


@pytest.mark.parametrize("stall_s, fires", [(0.35, True), (0.04, False)])
def test_the_stall_warning_fires_once_a_stall_at_its_threshold(
        caplog, stall_s, fires):
    async def scenario():
        wd = LoopWatchdog(interval_s=0.02, warn_after_s=0.1)
        wd.start()
        await asyncio.sleep(0.08)
        with caplog.at_level(
            logging.WARNING, "omero_ms_pixel_buffer_tpu.loop_watchdog"
        ):
            time.sleep(stall_s)  # the loop wedged, the GIL free
            await asyncio.sleep(0.2)
        snap = wd.snapshot()
        wd.stop()
        return snap

    snap = asyncio.run(scenario())
    blocked = [r for r in caplog.records
               if "event loop blocked" in r.getMessage()]
    assert len(blocked) == (1 if fires else 0)
    assert snap["blocked_events"] == (1 if fires else 0)
    assert not snap["blocked"]
