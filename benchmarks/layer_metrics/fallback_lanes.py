"""Pipeline: lanes whose device path failed and fell back to the host
during the window. 0 is the only healthy reading."""

from benchmarks.harness.counters import healthz_delta


def read(ctx):
    return (healthz_delta(ctx, "tile_device_fallback_total")
            + healthz_delta(ctx, "render_fallback_total"))
