"""A configuration, a workload (with a traffic generator and a
reference of its own) and a per-layer metric dropped in as NEW files,
plus their BENCHMARK.json entries, run without an edit to any file
that was there."""

import json
import os
import shutil
import time

from benchmarks.harness import cell, server
from benchmarks.tests.control_run import control_command


def _copy_benchmark(tmp_path):
    """A checkout in miniature: BENCHMARK.json and benchmarks/."""
    bench_dir = tmp_path / "benchmarks"
    shutil.copytree(
        cell.BENCH_DIR, bench_dir,
        ignore=shutil.ignore_patterns(".cache", "__pycache__"),
    )
    shutil.copy(os.path.join(cell.REPO, "BENCHMARK.json"), tmp_path)
    return bench_dir


def test_new_files_and_entries_are_enough(tmp_path, monkeypatch, capfd):
    bench_dir = _copy_benchmark(tmp_path)
    before = {p: os.path.getmtime(p) for p in bench_dir.rglob("*")
              if p.is_file()}

    # -- the new files: a deployment, a mix, a generator, a metric ----
    config = cell.load_json("configs", "fluor-u16-tile")
    config["name"] = "tiny-u16"
    config["image"].update(size_x=2048, size_y=2048, size_c=2)
    (bench_dir / "configs" / "tiny-u16.json").write_text(json.dumps(config))
    workload = cell.load_json("workloads", "tile_png512_c32")
    workload.update(name="tiny_rows", config="tiny-u16", generator="rows",
                    viewers=3, c_choices=[0, 1])
    (bench_dir / "workloads" / "tiny_rows.json").write_text(
        json.dumps(workload))
    (bench_dir / "traffic" / "rows.py").write_text(
        "import itertools\n"
        "def viewers(params, image, seed):\n"
        "    def stream(v):\n"
        "        for i in itertools.count(seed + v):\n"
        "            r = {'z': 0, 'c': i % 2, 'w': 512, 'h': 512,\n"
        "                 'x': 512 * (i % 4), 'y': 512 * (v % 4)}\n"
        "            r['url'] = params['path'].format(**r)\n"
        "            yield r\n"
        "    return [(stream(v), 1) for v in range(params['viewers'])]\n"
    )
    (bench_dir / "layer_metrics" / "answers.tiny.py").write_text(
        "def read(ctx):\n"
        "    return float(len(ctx['samples']))\n"
    )
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-u16", "source": "test", "reduced": [], "why": "test",
        "file": "benchmarks/configs/tiny-u16.json"})
    bench["workloads"].append({
        "name": "tiny_rows", "config": "tiny-u16", "traffic": "rows",
        "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "answers.tiny", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "batcher",
        "moves": "tiles_per_s", "workloads": ["tiny_rows"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    # -- the harness, pointed at that checkout -------------------------
    monkeypatch.setattr(cell, "BENCH_DIR", str(bench_dir))
    monkeypatch.setattr(cell, "REPO", str(tmp_path))
    monkeypatch.setattr(server, "REPO", str(tmp_path))
    lines = {}
    for trace in (False, True):
        code = cell.run_cell(
            "tiny_rows", 11, 1.0, trace, time.perf_counter(),
            require_chip=False,
            server_command=control_command("tiny_rows", 11, "sound"),
        )
        out, _ = capfd.readouterr()
        assert code == 0
        lines[trace] = json.loads(out.strip().splitlines()[-1])

    assert lines[False]["correct"] and lines[True]["correct"]
    assert set(lines[False]["metrics"]) == {
        "tiles_per_s", "tile_p50_ms", "tile_p95_ms", "setup_s"}
    traced = lines[True]["metrics"]
    # the new metric is read in its cell; the metrics that find nothing
    # to read against a stand-in server (no counters, no trace) are
    # left out of the line, never reported as 0
    assert traced["answers.tiny"]["value"] == lines[True]["attempted"]
    assert "device_served_share.tile" not in traced  # lists other cells
    assert "encode_roofline" not in traced
    assert "device_idle_share" not in traced
    # nothing that was there was edited
    assert all(os.path.getmtime(p) == t for p, t in before.items())

