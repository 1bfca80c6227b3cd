"""Event-loop lag watchdog — the BlockedThreadChecker, asyncio-style.

The reference leans on Vert.x's BlockedThreadChecker to keep its event
loop honest: a watchdog thread that yells (with a stack trace) when an
event-loop thread stops turning over. This port has the same failure
mode with asyncio — one blocking call on the loop degrades EVERY
concurrent tile lane — plus a static twin (``tools/analyze``'s
``loop-block`` rule) that catches most offenders before they ship.
The watchdog is the runtime backstop for what static analysis can't
see: C extensions that hold the GIL, pathological GC pauses,
accidentally-synchronous third-party calls.

Two halves, mirroring the Vert.x design:

- a **heartbeat coroutine** on the watched loop: sleeps ``interval_s``
  and measures how much later than scheduled it actually ran — that
  overshoot IS the loop lag, exported as the
  ``event_loop_lag_seconds`` histogram;
- a **checker daemon thread** (the part that still works when the
  loop is wedged): if no beat lands within ``warn_after_s`` it
  declares the loop blocked, increments
  ``event_loop_blocked_total``, and logs the loop thread's CURRENT
  stack via ``sys._current_frames()`` — naming the exact frame
  sitting on the loop, which is the line an operator needs.

Blocked detection is edge-triggered (one log per stall, plus one on
recovery with the measured duration) so a long stall doesn't flood the
log at the check frequency. ``snapshot()`` feeds ``/healthz``.

The checker thread is also the process's GIL probe: it wakes every
``PROBE_S`` (20 ms) and observes how late it woke against its
schedule into ``gil_wait_seconds``. A sleeping thread has to take the
GIL back before it runs again, so its lateness is the GIL's queue (the
interpreter hands the GIL over every 5 ms; a C call that never
releases it holds every other thread for as long as it runs) plus the
OS's wake latency, microseconds. The stall check runs on the same
thread on its own period, as it always did.
"""

from __future__ import annotations

import asyncio
import logging
import sys
import threading
import time
import traceback
from typing import Optional

from .metrics import REGISTRY

log = logging.getLogger("omero_ms_pixel_buffer_tpu.loop_watchdog")

LOOP_LAG = REGISTRY.histogram(
    "event_loop_lag_seconds",
    "How much later than scheduled the event-loop heartbeat ran",
)
LOOP_BLOCKED = REGISTRY.counter(
    "event_loop_blocked_total",
    "Stalls where the event loop missed the blocked threshold",
)
LOOP_MAX_LAG = REGISTRY.gauge(
    "event_loop_max_lag_seconds",
    "Largest heartbeat lag observed since start",
)
GIL_WAIT = REGISTRY.histogram(
    "gil_wait_seconds",
    "How much later than scheduled the watchdog thread woke: the wait "
    "to take the GIL back, and the OS's wake latency",
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
             0.05, 0.1, 0.25, 0.5, 1.0, float("inf")),
)

# the GIL probe's period: the watchdog thread wakes this often
PROBE_S = 0.02


class LoopWatchdog:
    """Watch one asyncio loop. ``start()`` must run on the loop's own
    thread (it captures the thread id the stack dump needs); ``stop()``
    can run anywhere."""

    def __init__(
        self,
        interval_s: float = 0.1,
        warn_after_s: float = 1.0,
    ):
        self.interval_s = interval_s
        self.warn_after_s = warn_after_s
        self._task: Optional[asyncio.Task] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._loop_thread_id: Optional[int] = None
        # single-tuple swap (last_beat_monotonic, last_lag_s): written
        # by the loop thread, read by the checker — atomic under the
        # GIL, no lock on the beat path
        self._beat = (time.monotonic(), 0.0)
        self._max_lag_s = 0.0
        self._blocked_since: Optional[float] = None
        self._blocked_events = 0

    # -- loop side -----------------------------------------------------

    def start(self, loop: Optional[asyncio.AbstractEventLoop] = None) -> None:
        if self._task is not None:
            return
        loop = loop or asyncio.get_running_loop()
        self._loop = loop
        self._loop_thread_id = threading.get_ident()
        self._stop.clear()
        self._beat = (time.monotonic(), 0.0)
        self._task = loop.create_task(self._heartbeat())
        self._thread = threading.Thread(
            target=self._check, name="loop-watchdog", daemon=True
        )
        self._thread.start()
        log.info(
            "loop watchdog armed: interval=%.0fms blocked-threshold=%.0fms",
            self.interval_s * 1000, self.warn_after_s * 1000,
        )

    async def _heartbeat(self) -> None:
        while not self._stop.is_set():
            t0 = time.monotonic()
            await asyncio.sleep(self.interval_s)
            lag = max(0.0, time.monotonic() - t0 - self.interval_s)
            LOOP_LAG.observe(lag)
            if lag > self._max_lag_s:
                self._max_lag_s = lag
                LOOP_MAX_LAG.set(lag)
            self._beat = (time.monotonic(), lag)

    # -- checker-thread side -------------------------------------------

    def _check(self) -> None:
        # check twice per threshold: worst-case detection latency is
        # warn_after_s * 1.5 without busy-spinning
        period = max(self.warn_after_s / 2.0, 0.01)
        due = time.monotonic() + PROBE_S
        next_check = time.monotonic() + period
        while not self._stop.is_set():
            # a plain sleep: a quarter less CPU a wake than a timed
            # Event.wait, and stop() waits at most one period
            time.sleep(max(due - time.monotonic(), 0.0))
            woke = time.monotonic()
            GIL_WAIT.observe(max(woke - due, 0.0))
            due = woke + PROBE_S
            if woke >= next_check:
                next_check = woke + period
                self._check_stall()

    def _check_stall(self) -> None:
        last_beat, _lag = self._beat
        stalled_s = time.monotonic() - last_beat - self.interval_s
        if stalled_s >= self.warn_after_s:
            if self._blocked_since is None:
                self._blocked_since = last_beat
                self._blocked_events += 1
                LOOP_BLOCKED.inc()
                log.warning(
                    "event loop blocked for >= %.0f ms — current "
                    "loop-thread stack:\n%s",
                    stalled_s * 1000, self._loop_stack(),
                )
        elif self._blocked_since is not None:
            duration = time.monotonic() - self._blocked_since
            self._blocked_since = None
            log.warning(
                "event loop recovered after ~%.0f ms stall",
                duration * 1000,
            )

    def _loop_stack(self) -> str:
        frames = sys._current_frames()
        frame = frames.get(self._loop_thread_id)
        if frame is None:
            return "<loop thread not found>"
        return "".join(traceback.format_stack(frame))

    # -- shared --------------------------------------------------------

    def stop(self) -> None:
        self._stop.set()
        task, self._task = self._task, None
        if task is not None and self._loop is not None:
            if threading.get_ident() == self._loop_thread_id:
                task.cancel()
            elif not self._loop.is_closed():
                # Task.cancel is not thread-safe; from any other
                # thread it must hop through the loop (a closed loop
                # means the heartbeat died with it — nothing to do)
                self._loop.call_soon_threadsafe(task.cancel)
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None

    def snapshot(self) -> dict:
        """The /healthz view. ``stalled_ms`` is LIVE — while the loop
        is wedged the heartbeat can't report, so health computes the
        in-progress stall from the checker's side of the clock."""
        last_beat, last_lag = self._beat
        stalled_s = max(
            0.0, time.monotonic() - last_beat - self.interval_s
        )
        return {
            "enabled": True,
            "last_lag_ms": round(last_lag * 1000, 2),
            "max_lag_ms": round(self._max_lag_s * 1000, 2),
            "stalled_ms": round(stalled_s * 1000, 2),
            "blocked": self._blocked_since is not None,
            "blocked_events": self._blocked_events,
            "blocked_threshold_ms": self.warn_after_s * 1000,
        }
