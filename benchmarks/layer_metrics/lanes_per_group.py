"""Device queue: mean real lanes of the encode groups launched on the
device over the window: Δ `tile_device_lanes_total` ÷ Δ `groups` of the
dispatcher's snapshot, both on /healthz."""

from benchmarks.harness.counters import healthz_delta


def read(ctx):
    groups = healthz_delta(ctx, "device_queue", "groups")
    if groups <= 0:
        return None
    return healthz_delta(ctx, "tile_device_lanes_total") / groups
