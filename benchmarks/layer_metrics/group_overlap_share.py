"""Device queue: the share of group launches that found the device
still busy with an earlier group (overlapped) rather than idle."""

from benchmarks.harness.counters import healthz_delta


def read(ctx):
    overlapped = healthz_delta(ctx, "device_queue", "overlapped")
    idle = healthz_delta(ctx, "device_queue", "idle_gaps")
    if overlapped + idle <= 0:
        return None
    return 100.0 * overlapped / (overlapped + idle)
