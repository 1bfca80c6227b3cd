"""Pipeline, tile cells: the share of the PNG tiles answered between
the two counter readings that were encoded on the device
(`tile_device_lanes_total`); the rest took the singleton-batch host
path. `correct` holds 100 minus this under the workload's
`limits.host_served_share`."""

from benchmarks.harness.counters import device_served_share


def read(ctx):
    return device_served_share(ctx)
