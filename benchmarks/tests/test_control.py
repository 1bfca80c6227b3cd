"""The control and the fault: the harness driven end to end (its look
for a chip skipped) with the plain reference in the server's place.
Sound, it comes out correct; with a guarantee broken, or an answer
altered where it is produced, `correct` is false."""

import json

import pytest

from benchmarks.harness import cell
from benchmarks.tests.control_run import control_command

CELLS = ["tile_png512_c32"]


def _run(capfd, monkeypatch, workload, mode, seed=3):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("BENCH_REHEARSE_SIZE", "2560")
    import time

    code = cell.run_cell(
        workload, seed, 1.0, False, time.perf_counter(), require_chip=False,
        server_command=control_command(workload, seed, mode),
    )
    out, err = capfd.readouterr()
    return code, json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("workload", CELLS)
def test_sound_reference_is_correct(capfd, monkeypatch, workload):
    code, line, _ = _run(capfd, monkeypatch, workload, "sound")
    assert code == 0 and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"
    assert line["checks"]["max_abs_pixel_diff"] == {"value": 0, "limit": 0}
    assert line["checks"]["host_served_share"]["value"] == 0.0


@pytest.mark.parametrize("workload", CELLS)
def test_lower_precision_control_is_not_correct(capfd, monkeypatch, workload):
    code, line, err = _run(capfd, monkeypatch, workload, "lowered")
    assert code == 1 and line["correct"] is False
    assert line["checks"]["max_abs_pixel_diff"]["value"] >= 1
    assert line["failed"] == line["attempted"]
    assert line["metrics"]["tiles_per_s"]["value"] == 0.0
    assert "check max_abs_pixel_diff" in err and "correct: False" in err


@pytest.mark.parametrize("workload", CELLS)
def test_an_answer_altered_where_it_is_produced(capfd, monkeypatch, workload):
    code, line, _ = _run(capfd, monkeypatch, workload, "one_pixel")
    assert code == 1 and line["correct"] is False
    assert 0 < line["failed"] < line["attempted"]
    assert line["checks"]["max_abs_pixel_diff"]["value"] == 1


def test_a_degraded_answer_is_a_failure(capfd, monkeypatch):
    code, line, _ = _run(capfd, monkeypatch, CELLS[0], "degraded")
    assert code == 1 and line["correct"] is False
    assert line["checks"]["degraded"]["value"] == line["attempted"]


def test_host_encoded_answers_are_a_failure(capfd, monkeypatch):
    code, line, err = _run(capfd, monkeypatch, CELLS[0], "host")
    assert code == 1 and line["correct"] is False
    assert line["checks"]["max_abs_pixel_diff"]["value"] == 0
    share = line["checks"]["host_served_share"]
    assert share["value"] == 100.0 and share["value"] > share["limit"]
    assert "check host_served_share" in err
