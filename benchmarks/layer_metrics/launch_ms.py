"""Device queue: mean wall ms of one launch call, the call that hands
a device program to the runtime, on the thread that makes it: 1e3 × Δ
Σ `device_launch_seconds_sum` ÷ Δ Σ `device_launch_seconds_count`
over `program` (/metrics; the `ompb.queue.launch` span of
`models/device_dispatch.py`). Prints `launch:`, a program's mean wall
ms, the launching thread's mean CPU ms over the same call
(`device_launch_cpu_seconds`) and its count: wall less CPU is the
call's wait. None on a program without the span, or with no launch in
the window."""

import json

from benchmarks.harness.counters import metric_delta, metric_family_delta

WALL = "device_launch_seconds"
CPU = "device_launch_cpu_seconds"


def by_program(ctx) -> dict:
    """{program: {"wall_ms", "cpu_ms", "count"}} over the window."""
    head = f'{WALL}_count{{program="'
    out = {}
    for key, count in metric_family_delta(ctx, head).items():
        program = key[len(head):].split('"')[0]
        if count <= 0:
            continue
        label = f'{{program="{program}"}}'
        out[program] = {
            "wall_ms": 1e3 * metric_delta(ctx, f"{WALL}_sum{label}") / count,
            "cpu_ms": 1e3 * metric_delta(ctx, f"{CPU}_sum{label}") / count,
            "count": count,
        }
    return out


def read(ctx):
    programs = by_program(ctx)
    if not programs:
        return None
    print("launch: " + json.dumps(programs), flush=True)
    count = sum(p["count"] for p in programs.values())
    return sum(p["wall_ms"] * p["count"] for p in programs.values()) / count
