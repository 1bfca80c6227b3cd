"""traffic/zsweep.py: a viewer's stream is decided by the seed and its
number, and is one field of view after another, each swept through
every (z, c) once, sections in order from a seeded start."""

import itertools

import pytest

from benchmarks.harness import cell

WORKLOAD = cell.load_json("workloads", "zstack_png512")
IMAGE = cell.load_json("configs", "fluor-zstack")["image"]
ZSWEEP = cell.load_plugin("traffic", "zsweep")
SWEEP = WORKLOAD["z_sections"] * len(WORKLOAD["c_choices"])
BIG_SEED = 2147485301  # the driver's seeds are a little over 2**31


def _take(seed, viewer, n):
    stream = ZSWEEP.viewer_stream(WORKLOAD, IMAGE, seed, viewer)
    return list(itertools.islice(stream, n))


def test_the_workload_is_the_issues():
    assert WORKLOAD["generator"] == "zsweep" and WORKLOAD["loop"] == "closed"
    assert WORKLOAD["viewers"] * WORKLOAD["connections_per_viewer"] == 32
    assert SWEEP == 48 == cell.load_json(
        "configs", "fluor-zstack")["device_planes"]
    assert WORKLOAD["z_sections"] == IMAGE["size_z"]
    streams = ZSWEEP.viewers(WORKLOAD, IMAGE, BIG_SEED)
    assert [n for _, n in streams] == [4] * 8


@pytest.mark.parametrize("viewer", [0, 3, 7])
def test_same_seed_and_viewer_same_stream(viewer):
    assert _take(BIG_SEED, viewer, 3 * SWEEP) == _take(
        BIG_SEED, viewer, 3 * SWEEP)
    assert _take(BIG_SEED, viewer, SWEEP) != _take(
        BIG_SEED, (viewer + 1) % 8, SWEEP)


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED, BIG_SEED * 1000003 + 911])
def test_a_field_is_48_requests_covering_every_plane_in_sweep_order(seed):
    requests = _take(seed, 2, 4 * SWEEP)
    sections, channels = WORKLOAD["z_sections"], WORKLOAD["c_choices"]
    for at in range(0, len(requests), SWEEP):
        field = requests[at : at + SWEEP]
        assert len({(r["x"], r["y"], r["w"], r["h"]) for r in field}) == 1
        z0 = field[0]["z"]
        assert [(r["z"], r["c"]) for r in field] == [
            ((z0 + step) % sections, c)
            for step in range(sections) for c in channels]
        first = field[0]
        assert first["w"] == first["h"] == WORKLOAD["tile"]
        assert first["x"] % WORKLOAD["grid"] == 0
        assert first["y"] % WORKLOAD["grid"] == 0
        assert 0 <= first["x"] <= IMAGE["size_x"] - first["w"]
        assert 0 <= first["y"] <= IMAGE["size_y"] - first["h"]
        for r in field:
            assert r["url"] == (
                f"/tile/1/{r['z']}/{r['c']}/0?x={r['x']}&y={r['y']}"
                "&w=512&h=512&format=png")


def test_another_seed_draws_other_fields():
    def fields(seed):
        return {(r["x"], r["y"]) for v in range(8)
                for r in _take(seed, v, 4 * SWEEP)}

    one, other = fields(BIG_SEED), fields(BIG_SEED + 1)
    assert len(one) == len(other) == 32
    assert len(one & other) <= 1
    starts = {_take(BIG_SEED, v, 1)[0]["z"] for v in range(8)}
    assert len(starts) > 1  # viewers do not start on one section


def test_the_reference_indexes_z():
    import numpy as np

    reference = cell.load_plugin("reference", WORKLOAD["reference"])
    data = np.arange(1 * 3 * 4 * 8 * 8, dtype=np.uint16).reshape(
        1, 3, 4, 8, 8)
    request = {"z": 3, "c": 2, "x": 2, "y": 1, "w": 4, "h": 5}
    np.testing.assert_array_equal(
        reference.expected(data, request), data[0, 2, 3, 1:6, 2:6])


def test_a_program_without_the_plane_budget_is_refused_at_once(
        monkeypatch, capsys):
    import dataclasses

    from omero_ms_pixel_buffer_tpu.utils import config

    assert ZSWEEP.program_has_plane_budget()

    @dataclasses.dataclass
    class ParentsBackend:  # the block as it was before PR 28
        engine: str = "jax"
        max_tile_mb: int = 256

    monkeypatch.setattr(config, "BackendConfig", ParentsBackend)
    with pytest.raises(SystemExit) as refused:
        cell.load_plugin("traffic", "zsweep")
    assert refused.value.code == 2
    assert "plane-cache-mb" in capsys.readouterr().err
