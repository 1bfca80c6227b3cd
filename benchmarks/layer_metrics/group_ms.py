"""Device queue: host-clock milliseconds a group spends in the queue's
stages (sum over stages of `device_stage_seconds`), per group."""

from benchmarks.harness.counters import healthz_delta, metric_family_delta


def stage_seconds(ctx) -> dict:
    """{stage: seconds} summed over the window."""
    head = 'device_stage_seconds_sum{stage="'
    return {
        key[len(head):].split('"')[0]: value
        for key, value in metric_family_delta(ctx, head).items()
    }


def read(ctx):
    groups = healthz_delta(ctx, "device_queue", "groups")
    if groups <= 0:
        return None
    return 1e3 * sum(stage_seconds(ctx).values()) / groups
