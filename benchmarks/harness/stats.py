"""The arithmetic of the end-to-end metrics, in one place."""

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in (0, 100]) of ALL the values: the
    smallest value with at least q% of the sample at or below it."""
    if not values:
        raise ValueError("no sample")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def window_metrics(samples, t0: float, seconds: float) -> dict:
    """samples: [{t_send, t_done, good}], times on one clock. A request
    belongs to the window when it COMPLETED inside [t0, t0+seconds]:
    latencies are of every such request, good or not; the rate counts
    the good ones over the whole window, stalls included."""
    t1 = t0 + seconds
    inside = [s for s in samples if t0 <= s["t_done"] <= t1]
    latencies_ms = [(s["t_done"] - s["t_send"]) * 1e3 for s in inside]
    out = {
        "completed": len(inside),
        "tiles_per_s": sum(1 for s in inside if s["good"]) / seconds,
    }
    if latencies_ms:
        out["tile_p50_ms"] = percentile(latencies_ms, 50)
        out["tile_p95_ms"] = percentile(latencies_ms, 95)
    return out


def latency_deciles_ms(samples, t0: float, seconds: float) -> list:
    """The 10th to 90th percentile of the window's latencies: where the
    median sits in the distribution (between two modes, it jumps)."""
    t1 = t0 + seconds
    latencies_ms = [(s["t_done"] - s["t_send"]) * 1e3 for s in samples
                    if t0 <= s["t_done"] <= t1]
    if not latencies_ms:
        return []
    return [round(percentile(latencies_ms, q), 1) for q in range(10, 100, 10)]


def completions_per_bucket(samples, t0: float, seconds: float,
                           width: float) -> list:
    """How many requests completed in each `width`-second part of the
    window: a transient or a stall shows here, not in the metrics."""
    counts = [0] * max(1, math.ceil(seconds / width))
    for s in samples:
        at = s["t_done"] - t0
        if 0 <= at <= seconds:
            counts[min(int(at // width), len(counts) - 1)] += 1
    return counts
