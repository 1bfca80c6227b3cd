"""The three readers of the four-chip cell, on hand-made `ctx`
dictionaries: four `per_chip` rows of /healthz, a trace with four
device planes, and the shapes a program without them answers with
(the parent's /healthz has no `per_chip`): None, never 0, never a
raise."""

import pytest

from benchmarks.harness import cell, trace_reduce


def _read(name, ctx):
    return cell.load_plugin("layer_metrics", name).read(ctx)


def _health(rows):
    planes = {"planes": 96, "hits": 0, "misses": 0}
    if rows is not None:
        planes["per_chip"] = rows
    return {"cache": {"device_planes": planes}}


def _rows(planes, lanes):
    return [{"chip": chip, "planes": p, "bytes": p * 209715200,
             "hits": 0, "misses": 0, "lanes": n}
            for chip, (p, n) in enumerate(zip(planes, lanes))]


def _ctx(before, after, trace=None):
    def side(health):
        return {"healthz": health, "metrics": {}, "cache_entries": 0}

    return {"before": side(before), "after": side(after), "samples": [],
            "workload": {"name": "zstack32_png512_x4"}, "config": {},
            "trace": trace}


PARENTS = [
    ({}, {}),                                    # no cache on /healthz
    ({"cache": {}}, {"cache": {"device_planes": None}}),
    (_health(None), _health(None)),              # totals, no row a chip
    (_health([]), _health([])),
]


@pytest.mark.parametrize("planes, fewest", [
    ([24, 24, 24, 24], 24.0),
    ([24, 23, 24, 25], 23.0),
    ([96, 0, 0, 0], 0.0),       # everything on chip 0: the parent's fault
])
def test_chip_planes_min_is_the_emptiest_chip_at_the_windows_end(
        planes, fewest):
    before = _health(_rows([0, 0, 0, 0], [0, 0, 0, 0]))
    after = _health(_rows(planes, [9, 9, 9, 9]))
    assert _read("chip_planes_min", _ctx(before, after)) == fewest


@pytest.mark.parametrize("before, after", PARENTS)
def test_chip_planes_min_without_rows_reads_none(before, after):
    assert _read("chip_planes_min", _ctx(before, after)) is None


def test_chip_lane_imbalance_is_the_busiest_chip_over_the_mean():
    before = _health(_rows([24] * 4, [100, 100, 100, 100]))
    after = _health(_rows([24] * 4, [1100, 1100, 1100, 1100]))
    assert _read("chip_lane_imbalance", _ctx(before, after)) == 0.0
    # the window's lanes 1500 / 1000 / 1000 / 500: mean 1000
    after = _health(_rows([24] * 4, [1600, 1100, 1100, 600]))
    assert _read("chip_lane_imbalance", _ctx(before, after)) == (
        pytest.approx(50.0))
    # a chip that first shows in the second reading counts from 0
    after = _health(_rows([24] * 4, [1100, 1100, 1100, 1100]))
    fewer = _health(_rows([24] * 3, [100, 100, 100]))
    assert _read("chip_lane_imbalance", _ctx(fewer, after)) == (
        pytest.approx(100.0 * (1100 / 1025 - 1)))


@pytest.mark.parametrize("before, after", PARENTS + [
    # rows, and no lane in the window
    (_health(_rows([24] * 4, [7] * 4)), _health(_rows([24] * 4, [7] * 4))),
])
def test_chip_lane_imbalance_without_lanes_reads_none(before, after):
    assert _read("chip_lane_imbalance", _ctx(before, after)) is None


def _events(busy_ms):
    """One device plane's `XLA Ops`: two overlapping operations and a
    third, busy `busy_ms` in all."""
    half = busy_ms * 1e6 / 2
    return [("%a", 0.0, half), ("%b", half / 2, half),   # inside %a
            ("%c", 5e9, 5e9 + half)]


@pytest.fixture
def four_planes(monkeypatch):
    loaded = {"devices": {
        "/device:TPU:0": _events(1800), "/device:TPU:1": _events(1500),
        "/device:TPU:2": _events(2100), "/device:TPU:3": _events(1200),
    }, "host": [], "lines": []}
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: "a.xplane.pb")
    monkeypatch.setattr(trace_reduce, "load", lambda path: loaded)
    return loaded


def test_chip_busy_spread_is_largest_less_smallest_busy_share(four_planes):
    ctx = _ctx({}, {}, trace={"window_s": 6.0, "busy_s": 1.65})
    # 35 % less 20 % of a 6 s slice
    assert _read("chip_busy_spread", ctx) == pytest.approx(15.0)
    shares = cell.load_plugin("layer_metrics", "chip_busy_spread").busy_shares(
        four_planes["devices"], 6.0)
    assert shares["/device:TPU:2"] == pytest.approx(35.0)
    assert sum(shares.values()) / 4 == pytest.approx(100 * 1.65 / 6.0)


def test_chip_busy_spread_reads_one_chip_as_nothing(four_planes):
    for plane in ("/device:TPU:1", "/device:TPU:2", "/device:TPU:3"):
        del four_planes["devices"][plane]
    ctx = _ctx({}, {}, trace={"window_s": 6.0, "busy_s": 1.8})
    assert _read("chip_busy_spread", ctx) is None


@pytest.mark.parametrize("trace", [None, {}, {"window_s": 0.0}])
def test_chip_busy_spread_without_a_trace_reads_none(trace):
    assert _read("chip_busy_spread", _ctx({}, {}, trace=trace)) is None


def test_chip_busy_spread_never_raises(monkeypatch, capsys):
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: None)
    ctx = _ctx({}, {}, trace={"window_s": 6.0})
    assert _read("chip_busy_spread", ctx) is None  # no file under work/

    def broken(path):
        raise ValueError("not an xplane")

    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: "x")
    monkeypatch.setattr(trace_reduce, "load", broken)
    assert _read("chip_busy_spread", ctx) is None
    assert "could not be read" in capsys.readouterr().out
