"""From a profiler trace (.xplane.pb) to busy time, idle gaps and the
operations that took most time. Reads with `jax.profiler.ProfileData`,
which parses the file and initialises no backend.

What the planes are (looked at by hand on the v5e, PR 25): a device is
a plane named `/device:TPU:<n>`; its line `XLA Ops` holds one event
per executed HLO operation (start, duration in ns), `XLA Modules` one
per executed program, `Steps` the profiler's own step grouping. Busy
time is the union of the `XLA Ops` intervals. Host threads are the
lines of `/host:CPU`.
"""

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


_OP = re.compile(r"^(%[^ ]+) = (\(.*?\)|[^ ({]+)[^ ]* ([a-z][\w\-]*)\(")


def short_op(text: str) -> str:
    """'%fusion.47 fusion s32[295232]' from the trace's full HLO line
    (no `jax.named_scope` exists in the program yet, so the HLO name,
    the opcode and the result's shape are all there is)."""
    found = _OP.match(text)
    if not found:
        return text[:96]
    name, shape, opcode = found.groups()
    if shape.startswith("("):
        shape = "tuple"
    return f"{name} {opcode} {shape}"[:96]


def find_xplane(trace_dir: str):
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return found[-1] if found else None


def union(intervals: list) -> list:
    """Merged, sorted [(start, end)] of possibly overlapping ones."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def load(path: str) -> dict:
    """{'devices': {plane: [(name, start_ns, end_ns)]},
        'host': [(name, start_ns, end_ns)], 'lines': [...]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, lines = {}, [], []
    for plane in data.planes:
        for line in plane.lines:
            events = [
                (e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                for e in line.events
            ]
            lines.append((plane.name, line.name, len(events),
                          sum(e[2] - e[1] for e in events) / 1e9))
            if plane.name.startswith(DEVICE_PREFIX):
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(events)
            elif plane.name == HOST_PLANE:
                host.extend(e for e in events if e[2] > e[1])
    return {"devices": devices, "host": host, "lines": lines}


def _busiest_host_event(host: list, start: float, end: float) -> str:
    """The host event that covers most of [start, end]."""
    cover = {}
    for name, s, e in host:
        overlap = min(e, end) - max(s, start)
        if overlap > 0:
            cover[name] = cover.get(name, 0.0) + overlap
    if not cover:
        return "unattributed"
    return max(cover.items(), key=lambda kv: kv[1])[0]


def reduce(loaded: dict, top: int = 10, gaps: int = 5) -> dict:
    """busy_s (mean over devices of the union of op intervals), the
    `top` operations by total time and the `gaps` longest idle gaps,
    labelled by the host."""
    devices = loaded["devices"]
    if not devices:
        return None
    busy, op_time, idle = [], {}, []
    for plane, events in sorted(devices.items()):
        if not events:
            continue
        merged = union([(s, e) for _, s, e in events])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, s, e in events:
            op_time[name] = op_time.get(name, 0.0) + (e - s) / 1e9
        for (_, prev_end), (next_start, _) in zip(merged, merged[1:]):
            idle.append((next_start - prev_end, prev_end, next_start))
    if not busy:
        return None
    n = len(busy)
    idle.sort(reverse=True)
    return {
        "devices": n,
        "busy_s": sum(busy) / n,
        "device_ops": [
            [short_op(name), seconds / n] for name, seconds in
            sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [
            [_busiest_host_event(loaded["host"], s, e), (e - s) / 1e9]
            for _, s, e in idle[:gaps]
        ],
    }


def describe(loaded: dict) -> str:
    """One line per (plane, line): what a human looks at first."""
    return "\n".join(
        f"{plane} | {line} | events {n} | seconds {secs:.6f}"
        for plane, line, n, secs in loaded["lines"]
    )
