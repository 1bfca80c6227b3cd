"""Plane cache: the share of the window's plane look-ups that found
the plane resident in HBM: Δ hits ÷ Δ (hits + misses) of
`cache.device_planes` on /healthz. 100 when the deployment is resident
and stays so."""

from benchmarks.harness.counters import healthz_delta


def read(ctx):
    hits = healthz_delta(ctx, "cache", "device_planes", "hits")
    looked = hits + healthz_delta(ctx, "cache", "device_planes", "misses")
    if looked <= 0:
        return None
    return 100.0 * hits / looked
