"""The three readers this deployment added, on hand-made `ctx`
dictionaries: what they divide, and that nothing to divide by reads
None (the line leaves the metric out), never 0."""

import pytest

from benchmarks.harness import cell


def _ctx(before, after, metrics_after=None):
    def side(health, metrics):
        return {"healthz": health, "metrics": metrics or {},
                "cache_entries": 0}

    return {"before": side(before, {}), "after": side(after, metrics_after),
            "samples": [], "workload": {}, "config": {}, "trace": None}


def _read(name, ctx):
    return cell.load_plugin("layer_metrics", name).read(ctx)


def _health(lanes, groups, hits=0, misses=0):
    return {"tile_device_lanes_total": lanes,
            "device_queue": {"groups": groups},
            "cache": {"device_planes": {"hits": hits, "misses": misses}}}


def test_lanes_per_group_is_device_lanes_over_groups():
    ctx = _ctx(_health(100, 90), _health(1300, 490))
    assert _read("lanes_per_group", ctx) == pytest.approx(3.0)


@pytest.mark.parametrize("before, after", [
    (_health(5, 7), _health(9, 7)),         # no group in the window
    ({}, {}),                               # a server with no counters
    ({"device_queue": None}, {"device_queue": None}),
])
def test_lanes_per_group_without_groups_reads_none(before, after):
    assert _read("lanes_per_group", _ctx(before, after)) is None


def test_plane_hit_share_is_hits_over_lookups():
    ctx = _ctx(_health(0, 0, hits=10, misses=96),
               _health(0, 0, hits=1210, misses=96))
    assert _read("plane_hit_share", ctx) == 100.0
    ctx = _ctx(_health(0, 0, hits=10, misses=96),
               _health(0, 0, hits=310, misses=196))
    assert _read("plane_hit_share", ctx) == pytest.approx(75.0)


@pytest.mark.parametrize("before, after", [
    (_health(0, 0, 4, 4), _health(0, 0, 4, 4)),  # no look-up
    ({"cache": {}}, {"cache": {"device_planes": None}}),
    ({}, {}),
])
def test_plane_hit_share_without_lookups_reads_none(before, after):
    assert _read("plane_hit_share", _ctx(before, after)) is None


def test_plane_stage_ms_is_seconds_over_admissions_since_the_start():
    metrics = {
        "device_plane_admissions_total": 48.0,
        'device_plane_stage_seconds_sum{stage="read"}': 14.4,
        'device_plane_stage_seconds_sum{stage="h2d"}': 4.8,
        'device_plane_stage_seconds_count{stage="read"}': 48.0,
        'device_stage_seconds_sum{stage="h2d"}': 99.0,  # another family
    }
    assert _read("plane_stage_ms", _ctx({}, {}, metrics)) == pytest.approx(
        400.0)


@pytest.mark.parametrize("metrics", [
    {},                                          # the parent: no counter
    {"device_plane_admissions_total": 0.0},      # nothing admitted yet
    {'device_plane_stage_seconds_sum{stage="read"}': 0.0},
])
def test_plane_stage_ms_without_admissions_reads_none(metrics):
    assert _read("plane_stage_ms", _ctx({}, {}, metrics)) is None


def test_the_benchmark_lists_them_for_both_cells():
    bench = cell.benchmark_json()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, layer, moves in (
        ("lanes_per_group", "device queue", "tiles_per_s"),
        ("plane_hit_share", "plane cache", "tiles_per_s"),
        ("plane_stage_ms", "plane cache", "setup_s"),
    ):
        metric = by_name[name]
        assert metric["layer"] == layer and metric["moves"] == moves
        assert metric["source"] == "program_counter"
        assert metric["workloads"] == ["tile_png512_c32", "zstack_png512"]
    entry = next(w for w in bench["workloads"] if w["name"] == "zstack_png512")
    assert entry == {**entry, "config": "fluor-zstack",
                     "traffic": "zsweep_v8x4", "chips": 1}
