"""The two readers of the host Huffman plan, on hand-made `ctx`
dictionaries: `plan_native_share` and `plan_ms_per_lane` take deltas of
/healthz `device_queue`'s plan counters (and of the `plan` stage's
seconds on /metrics) over the window, and read None, never 0 and never
a raise, where a program has no such counters (the parent's /healthz)
or planned no lane in the window."""

import pytest

from benchmarks.harness import cell

PLAN = 'device_stage_seconds_sum{stage="plan"}'


def _read(name, ctx):
    return cell.load_plugin("layer_metrics", name).read(ctx)


def _queue(native, python):
    return {"device_queue": {"groups": 10, "plan_lanes_native": native,
                             "plan_lanes_python": python}}


def _ctx(before, after, plan_s=(0.0, 0.0)):
    def side(health, seconds):
        metrics = {} if seconds is None else {PLAN: seconds}
        return {"healthz": health, "metrics": metrics, "cache_entries": 0}

    return {"before": side(before, plan_s[0]), "after": side(after, plan_s[1]),
            "samples": [], "workload": {"name": "tile_png512_c32"},
            "config": {}, "trace": None}


@pytest.mark.parametrize("before, after, share", [
    (_queue(40, 0), _queue(240, 0), 100.0),
    (_queue(40, 10), _queue(190, 60), 75.0),   # 150 native, 50 python
    (_queue(0, 7), _queue(0, 107), 0.0),       # no engine: all in Python
])
def test_plan_native_share_is_the_windows_native_lanes(before, after, share):
    assert _read("plan_native_share", _ctx(before, after)) == (
        pytest.approx(share))


def test_plan_ms_per_lane_is_the_windows_plan_seconds_a_lane():
    # 0.5 s of `plan` over 200 lanes: 2.5 ms a lane
    ctx = _ctx(_queue(40, 0), _queue(190, 50), plan_s=(1.25, 1.75))
    assert _read("plan_ms_per_lane", ctx) == pytest.approx(2.5)


# what the parent answers with: no plan counters on /healthz
PARENTS = [
    ({}, {}),
    ({"device_queue": None}, {"device_queue": None}),
    ({"device_queue": {"groups": 3}}, {"device_queue": {"groups": 90}}),
]


@pytest.mark.parametrize("name", ["plan_native_share", "plan_ms_per_lane"])
@pytest.mark.parametrize("before, after", PARENTS)
def test_without_the_plan_counters_both_read_none(name, before, after):
    assert _read(name, _ctx(before, after, plan_s=(1.0, 2.0))) is None


@pytest.mark.parametrize("name", ["plan_native_share", "plan_ms_per_lane"])
def test_a_window_that_planned_no_lane_reads_none(name):
    ctx = _ctx(_queue(64, 2), _queue(64, 2), plan_s=(1.0, 1.0))
    assert _read(name, ctx) is None


def test_plan_ms_per_lane_without_a_plan_stage_reads_none():
    ctx = _ctx(_queue(0, 0), _queue(30, 0), plan_s=(None, None))
    assert _read("plan_ms_per_lane", ctx) is None
