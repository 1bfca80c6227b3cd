"""HTTP front: how late the serving loop's heartbeat ran, mean ms a
beat over the window: 1e3 × Δ `event_loop_lag_seconds_sum` ÷ Δ
`event_loop_lag_seconds_count` (/metrics; the watchdog's heartbeat
coroutine, `utils/loop_watchdog.py`). A loop that is busy, or waits
for the GIL, runs its timers late. None without a beat in the
window."""

from benchmarks.harness.counters import metric_delta


def read(ctx):
    beats = metric_delta(ctx, "event_loop_lag_seconds_count")
    if beats <= 0:
        return None
    return 1e3 * metric_delta(ctx, "event_loop_lag_seconds_sum") / beats
