"""The rest of a run driven over the REAL server (device engine on the
CPU backend, small image) with the look for a chip skipped: sound, the
run is correct; with one sample altered where the plane cache hands
out its crops, `correct` comes out false."""

import json
import os
import sys
import time

import pytest

from benchmarks.harness import cell

HERE = os.path.dirname(os.path.abspath(__file__))


def _run(capfd, monkeypatch, command):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("BENCH_REHEARSE_SIZE", "2048")
    workdir = os.path.join(cell.BENCH_DIR, ".cache", "work", "tile_png512_c32")
    if command is not None:
        command = command + [workdir, "--"]
    code = cell.run_cell("tile_png512_c32", 17, 2.0, False,
                         time.perf_counter(), require_chip=False,
                         server_command=command)
    out, err = capfd.readouterr()
    return code, json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.slow
def test_real_server_sound_then_broken(capfd, monkeypatch):
    code, line, _ = _run(capfd, monkeypatch, None)
    assert code == 0 and line["correct"] is True
    assert line["device"]["platform"] == "cpu"  # never a measurement
    assert line["checks"]["compared"]["value"] > 0

    code, line, err = _run(
        capfd, monkeypatch,
        [sys.executable, os.path.join(HERE, "faulty_launcher.py")])
    assert code == 1 and line["correct"] is False
    assert line["checks"]["max_abs_pixel_diff"]["value"] == 1
    assert line["checks"]["wrong_pixels"]["value"] > 0
    assert "correct: False" in err
