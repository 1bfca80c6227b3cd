"""Device queue: the share of the window's planned lanes (the real
lanes of served dynamic-Huffman groups) whose host plan ran as the one
native call rather than in Python: Δ `plan_lanes_native` ÷ Δ
(`plan_lanes_native` + `plan_lanes_python`) of /healthz
`device_queue`. None on a program without the two counters, or with
no lane planned in the window."""

from benchmarks.harness.counters import healthz_delta


def read(ctx):
    queue = ctx["after"]["healthz"].get("device_queue") or {}
    if "plan_lanes_native" not in queue:
        return None
    native = healthz_delta(ctx, "device_queue", "plan_lanes_native")
    python = healthz_delta(ctx, "device_queue", "plan_lanes_python")
    if native + python <= 0:
        return None
    return 100.0 * native / (native + python)
