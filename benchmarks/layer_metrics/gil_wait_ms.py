"""Host process: the GIL's queue as the watchdog thread meets it, mean
ms a wake: 1e3 × Δ `gil_wait_seconds_sum` ÷ Δ `gil_wait_seconds_count`
(/metrics). The thread sleeps 20 ms at a time and observes how late
it woke (`utils/loop_watchdog.py`): the wait to take the GIL back,
and the OS's wake latency. None on a program without the probe, or
with no wake in the window."""

from benchmarks.harness.counters import metric_delta


def read(ctx):
    if "gil_wait_seconds_count" not in ctx["after"]["metrics"]:
        return None
    wakes = metric_delta(ctx, "gil_wait_seconds_count")
    if wakes <= 0:
        return None
    return 1e3 * metric_delta(ctx, "gil_wait_seconds_sum") / wakes
