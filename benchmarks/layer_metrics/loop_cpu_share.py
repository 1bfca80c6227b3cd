"""HTTP front: the share of one core the serving loop's thread used in
the window: 100 × Δ `thread_cpu_seconds_total{role="loop"}` ÷ the
window's seconds (/metrics; each thread's CPU clock, read at scrape
time, `utils/process_metrics.py`). Prints `thread_cpu:`, every role's
share of a core. None on a program without the family."""

import json

from benchmarks.harness.counters import metric_delta, metric_family_delta

HEAD = 'thread_cpu_seconds_total{role="'


def shares(ctx) -> dict:
    """{role: percent of one core over the window}."""
    seconds = ctx["window"][1]
    return {
        key[len(HEAD):].split('"')[0]: 100.0 * cpu / seconds
        for key, cpu in metric_family_delta(ctx, HEAD).items()
    }


def read(ctx):
    loop = HEAD + 'loop"}'
    if loop not in ctx["after"]["metrics"] or not ctx["window"][1]:
        return None
    print("thread_cpu: " + json.dumps(shares(ctx)), flush=True)
    return 100.0 * metric_delta(ctx, loop) / ctx["window"][1]
