"""Batcher: milliseconds a request waits from its enqueue to the start
of the batch that carries it (the coalesce window, and the wait for an
executor slot): mean of the flight recorder's `batch_wait` stage."""

from benchmarks.harness.counters import metric_delta


def read(ctx):
    n = metric_delta(ctx, 'request_stage_seconds_count{stage="batch_wait"}')
    if n <= 0:
        return None
    return 1e3 * metric_delta(
        ctx, 'request_stage_seconds_sum{stage="batch_wait"}') / n
