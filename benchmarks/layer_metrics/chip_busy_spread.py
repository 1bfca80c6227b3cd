"""Device: how unevenly the chips were busy in the traced slice: the
largest less the smallest busy share (union of a chip's `XLA Ops`
intervals ÷ the slice, percent) over the `/device:TPU:<n>` planes of
the run's trace. `device_idle_share` is their mean; this is their
spread. It names no span or counter of the program, so it reads any
program that ran on several chips. None without a trace, or with
fewer than two device planes."""

import json
import os

from benchmarks.harness import fixture, trace_reduce
from benchmarks.harness.server import BENCH_DIR


def busy_shares(devices: dict, window_s: float) -> dict:
    """{plane: percent busy} from `trace_reduce.load(...)["devices"]`."""
    shares = {}
    for plane, events in sorted(devices.items()):
        merged = trace_reduce.union([(s, e) for _, s, e in events])
        shares[plane] = 100.0 * sum(e - s for s, e in merged) / 1e9 / window_s
    return shares


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    try:
        path = trace_reduce.find_xplane(os.path.join(
            fixture.cache_root(BENCH_DIR), "work",
            ctx["workload"]["name"], "trace"))
        if path is None:
            return None
        shares = busy_shares(
            trace_reduce.load(path)["devices"], trace["window_s"])
    except Exception as e:  # a reader reports nothing; it never raises
        print(f"chip_busy_spread: the trace could not be read "
              f"({type(e).__name__}: {e})", flush=True)
        return None
    print("chip_busy_share: " + json.dumps(shares), flush=True)
    if len(shares) < 2:
        return None
    return max(shares.values()) - min(shares.values())
