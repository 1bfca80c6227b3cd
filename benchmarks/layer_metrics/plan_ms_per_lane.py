"""Device queue: host milliseconds of the `plan` stage a planned lane:
1e3 × Δ `device_stage_seconds_sum{stage="plan"}` (/metrics) ÷ Δ
(`plan_lanes_native` + `plan_lanes_python`) (/healthz
`device_queue`). None on a program without the two counters, with no
lane planned in the window, or with no `plan` stage observed."""

from benchmarks.harness.counters import healthz_delta, metric_delta

PLAN = 'device_stage_seconds_sum{stage="plan"}'


def read(ctx):
    queue = ctx["after"]["healthz"].get("device_queue") or {}
    if "plan_lanes_native" not in queue or PLAN not in ctx["after"]["metrics"]:
        return None
    lanes = (healthz_delta(ctx, "device_queue", "plan_lanes_native")
             + healthz_delta(ctx, "device_queue", "plan_lanes_python"))
    if lanes <= 0:
        return None
    return 1e3 * metric_delta(ctx, PLAN) / lanes
