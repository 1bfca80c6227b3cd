"""Batcher: mean lanes per coalesced batch over the window
(`tile_batch_size` histogram on /metrics)."""

from benchmarks.harness.counters import metric_delta


def read(ctx):
    batches = metric_delta(ctx, "tile_batch_size_count")
    if batches <= 0:
        return None
    return metric_delta(ctx, "tile_batch_size_sum") / batches
