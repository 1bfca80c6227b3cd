"""BENCHMARK.json against the static limits of the benchmark's
contract, and against the files it names."""

import json
import os
import re

from benchmarks.harness import cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = cell.benchmark_json()


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_run_seconds():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["benchmarks"]
    assert all(_line(word) for word in BENCH["command"])
    size = os.path.getsize(os.path.join(cell.REPO, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_configs_name_their_files():
    used = {w["config"] for w in BENCH["workloads"]}
    for config in BENCH["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(config["name"]) and config["name"] in used
        assert _line(config["source"]) and _line(config["why"])
        assert config["file"].startswith("benchmarks/")
        with open(os.path.join(cell.REPO, config["file"])) as f:
            body = json.load(f)
        assert body["name"] == config["name"]
        assert body["source"] == config["source"]
        assert body["reduced"] == config["reduced"]
        assert all(NAME.match(key) for key in config["reduced"])
        assert body["guarantees"]


def test_workloads_have_files_and_one_chip():
    pairs = set()
    configs = {c["name"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        body = cell.load_json("workloads", w["name"])
        assert body["config"] == w["config"]
        assert body["traffic"] == w["traffic"]
        for kind, key in (("traffic", "generator"), ("reference", "reference")):
            assert os.path.exists(os.path.join(
                cell.BENCH_DIR, kind, body[key] + ".py"))


def test_metrics():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
        assert _line(m["layer"])
        assert set(m.get("workloads", cells)) <= cells
        assert os.path.exists(os.path.join(
            cell.BENCH_DIR, "layer_metrics", m["name"] + ".py"))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
