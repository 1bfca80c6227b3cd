"""HTTP front: the front's own milliseconds per request: the door, the
session lookup, the cache probe, the event loop and the response
framing. Mean request time at the door (`http_request_seconds`, every
`outcome`) less the mean time the flight recorder stamped on the
stages behind the front (`request_stage_seconds{stage=...}`).

On the tile path those stages do not overlap (read in the program, PR
26): a request is stamped `queue_wait` in `http/server.py` before it
enters the bus, `batch_wait` from its enqueue to the batch's start
(`dispatch/batcher.py`), then on the batch's executor thread, one
after the other, `resolve`, `read`, and `device` (the wait on the
group's future, `models/tile_pipeline.py`) or `encode` (a singleton
batch, on the host); `render` is the /render path's. A batched stage
stamps the batch's wall time on each of its lanes, which is what each
request waited. `l2` and `peer` belong to a cache plane this
deployment does not have."""

import json

from benchmarks.harness.counters import metric_delta, metric_family_delta

BEHIND = ("queue_wait", "batch_wait", "resolve", "read", "render", "device",
          "encode")


def stage_ms(ctx) -> dict:
    """{stage: mean ms over the requests that passed it}."""
    head = 'request_stage_seconds_sum{stage="'
    out = {}
    for key, seconds in metric_family_delta(ctx, head).items():
        stage = key[len(head):].split('"')[0]
        n = metric_delta(
            ctx, f'request_stage_seconds_count{{stage="{stage}"}}')
        if n > 0:
            out[stage] = 1e3 * seconds / n
    return out


def read(ctx):
    requests = sum(
        metric_family_delta(ctx, "http_request_seconds_count").values())
    if requests <= 0:
        return None
    total = sum(metric_family_delta(ctx, "http_request_seconds_sum").values())
    behind = sum(
        metric_delta(ctx, f'request_stage_seconds_sum{{stage="{stage}"}}')
        for stage in BEHIND)
    print("request_stage_ms: " + json.dumps(
        {"http_request": 1e3 * total / requests, **stage_ms(ctx)}),
        flush=True)
    return 1e3 * (total - behind) / requests
