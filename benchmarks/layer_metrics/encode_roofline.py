"""Kernels: the encode work's share of the memory-bandwidth roofline.

The least time the chip could take for the lanes it encoded in the
traced slice is the bytes that work has to move over the published HBM
bandwidth; the share is that over the device's busy seconds in the
slice. The lanes are the device's own (`tile_device_lanes_total`, read
just inside the slice's two ends: tiles the program encodes on the
host are no work of the chip). A lane moves the pixel bytes read
(`raw_bytes_per_tile` of the workload file) plus the body bytes
written (the mean body of the answers completed in the slice).
Bandwidth-bound by construction: the chain has no matrix work, so the
FLOP roof is never the nearer one. It counts the work, not the
kernels, so a change of packer or a fused pass cannot make it stale.
"""

from benchmarks.harness.peaks import peak


def work_bytes(samples, raw_bytes_per_tile: int) -> int:
    return sum(raw_bytes_per_tile + len(s["body"]) for s in samples)


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s") or not trace.get("device_lanes"):
        return None
    lo, hi = trace["slice"]
    inside = [s for s in ctx["samples"]
              if s["status"] == 200 and lo <= s["t_done"] <= hi]
    if not inside:
        return None
    per_lane = work_bytes(
        inside, ctx["workload"]["raw_bytes_per_tile"]) / len(inside)
    needed = trace["device_lanes"] * per_lane
    least_s = needed / peak(ctx["device"]["kind"], "hbm_bytes_per_s")
    return 100.0 * least_s / trace["busy_s"]
