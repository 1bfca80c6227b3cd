"""The trace reduction on one small trace recorded on the v5e (PR 25):
three rounds of two jitted programs with sleeps between, 33 device
operations in all (`small.xplane.pb`, 29 kB)."""

import os

import pytest

from benchmarks.harness import trace_reduce
from benchmarks.harness.cell import load_plugin

SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "small.xplane.pb")


@pytest.fixture(scope="module")
def loaded():
    return trace_reduce.load(SMALL)


def test_union_merges_overlaps_and_touching_intervals():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)]) == [
        (0, 4), (5, 7), (10, 11)]
    assert trace_reduce.union([]) == []
    assert trace_reduce.union([(0, 10), (2, 3)]) == [(0, 10)]


def test_the_device_plane_and_its_ops_line_are_found(loaded):
    assert list(loaded["devices"]) == ["/device:TPU:0"]
    assert len(loaded["devices"]["/device:TPU:0"]) == 33
    lines = {(plane, line): n for plane, line, n, _ in loaded["lines"]}
    assert lines[("/device:TPU:0", "XLA Modules")] == 6
    assert loaded["host"]  # host threads are there for the gaps


def test_busy_union_top_ops_and_gaps(loaded):
    reduced = trace_reduce.reduce(loaded)
    assert reduced["devices"] == 1
    # the six programs ran for 787 us in all, as `XLA Modules` says too
    assert reduced["busy_s"] == pytest.approx(787.301e-6, rel=1e-6)
    name, seconds = reduced["device_ops"][0]
    assert name == "%fusion fusion f32[1024,8,128]"  # the cumsum
    assert seconds == pytest.approx(706.651e-6, rel=1e-6)
    assert len(reduced["device_ops"]) == 10
    assert reduced["device_ops"] == sorted(
        reduced["device_ops"], key=lambda kv: -kv[1])
    # five gaps between six programs, longest first, each labelled
    gaps = reduced["idle_gaps"]
    assert len(gaps) == 5
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert 0.020 < gaps[0][1] < 0.023  # the 20 ms sleep and the dispatch
    assert all(isinstance(g[0], str) and g[0] for g in gaps)


def test_idle_share_reader_uses_the_slice_length(loaded):
    reduced = trace_reduce.reduce(loaded)
    reduced["window_s"] = 0.1
    idle = load_plugin("layer_metrics", "device_idle_share").read(
        {"trace": reduced})
    assert idle == pytest.approx(100.0 * (1 - 787.301e-6 / 0.1))
    assert load_plugin("layer_metrics", "device_idle_share").read(
        {"trace": None}) is None


def test_a_trace_with_no_device_plane_reduces_to_nothing():
    assert trace_reduce.reduce(
        {"devices": {}, "host": [], "lines": []}) is None


def test_short_op_names():
    assert trace_reduce.short_op(
        "%fusion.47 = s32[295232]{0:T(1024)S(1)} fusion(s32[2,525121]{1,0} "
        "%p), kind=kCustom") == "%fusion.47 fusion s32[295232]"
    assert trace_reduce.short_op(
        "%while.14 = (s32[]{:T(128)}, s32[2,147616]{1,0}) while((s32[]) "
        "%tuple.60), condition=%c") == "%while.14 while tuple"
    assert trace_reduce.short_op("no hlo here") == "no hlo here"
