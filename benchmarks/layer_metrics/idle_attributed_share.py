"""Device queue: the share of the device's idle time in the traced
slice (every gap between two operations, not the five longest) during
which the host was inside a stage of the device queue, an
`ompb.queue.<stage>` annotation on the profiler's clock: idle time
the program can explain. The waits (`ompb.queue.wait_*`) do not count
(see _scopes.py)."""

from benchmarks.layer_metrics import _scopes


def read(ctx):
    found = _scopes.of(ctx)
    if not found or not found["idle"] or not found["idle"]["idle_s"]:
        return None
    return 100.0 * found["idle"]["attributed_s"] / found["idle"]["idle_s"]
