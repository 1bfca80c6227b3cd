"""Device queue: milliseconds a group waits before the queue's first
stage: for the submit thread (`where="pool"`) and then for one of the
`queue-depth` in-flight slots (`where="slot"`), summed, per group
(`device_queue_wait_seconds`, observed once per group and place). A
program without the family reads nothing."""

import json

from benchmarks.harness.counters import metric_delta


def read(ctx):
    groups = metric_delta(
        ctx, 'device_queue_wait_seconds_count{where="pool"}')
    if groups <= 0:
        return None
    waits = {
        where: 1e3 * metric_delta(
            ctx, f'device_queue_wait_seconds_sum{{where="{where}"}}') / groups
        for where in ("pool", "slot")
    }
    print("device_wait_ms_by_place: " + json.dumps(waits), flush=True)
    return sum(waits.values())
