"""The readers of PR 26: three from the program's counters, on
hand-made readings, and seven from the profiler's trace, on
`scoped.xplane.pb`: three groups of two 16x16 tiles through the
program's device queue, recorded on the v5e by `scoped_trace.py`
(the five kernel scopes in the operations' metadata, the programs'
HLO, the queue's stages on the host plane)."""

import os

import pytest

from benchmarks.harness import trace_reduce
from benchmarks.harness.cell import benchmark_json, load_plugin
from benchmarks.layer_metrics import _scopes

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPED = os.path.join(HERE, "scoped.xplane.pb")
UNNAMED = os.path.join(HERE, "small.xplane.pb")  # PR 25's: no scope in it
KERNELS = [f"kernel_ms_per_lane.{s}" for s in _scopes.SCOPES]
TRACE_READERS = KERNELS + ["kernel_unnamed_share", "idle_attributed_share"]


def reader(name):
    return load_plugin("layer_metrics", name).read


def counters(before: dict, after: dict) -> dict:
    return {"before": {"metrics": before}, "after": {"metrics": after}}


# -- the three counter readers --------------------------------------------

def stage(name, what="sum"):
    return f'request_stage_seconds_{what}{{stage="{name}"}}'


def test_front_self_ms_is_the_door_time_less_the_stages_behind_it():
    before = {
        'http_request_seconds_sum{outcome="ok"}': 10.0,
        'http_request_seconds_count{outcome="ok"}': 5.0,
        stage("device"): 6.0, stage("batch_wait"): 1.0,
    }
    after = {
        'http_request_seconds_sum{outcome="ok"}': 210.0,    # +200 s
        'http_request_seconds_count{outcome="ok"}': 100.0,  # +95
        'http_request_seconds_sum{outcome="error"}': 1.0,   # +1 s
        'http_request_seconds_count{outcome="error"}': 5.0,  # +5
        stage("device"): 156.0,      # +150
        stage("batch_wait"): 5.0,    # +4
        stage("encode"): 3.0,        # +3
        stage("read"): 2.0, stage("resolve"): 0.5, stage("queue_wait"): 0.5,
        stage("render"): 0.0,
        stage("auth"): 9.0, stage("door"): 9.0,  # the front's own: not taken off
        stage("device", "count"): 70.0, stage("encode", "count"): 30.0,
    }
    ctx = counters(before, after)
    # (201 - (150 + 4 + 3 + 2 + 0.5 + 0.5)) s over 100 requests
    assert reader("front_self_ms")(ctx) == pytest.approx(410.0)
    assert reader("front_self_ms")(counters({}, {})) is None
    table = load_plugin("layer_metrics", "front_self_ms").stage_ms(ctx)
    assert table["encode"] == pytest.approx(100.0)  # 3 s over 30 requests
    assert "render" not in table  # nothing passed it


def test_batch_wait_ms_is_the_recorders_stage_mean():
    ctx = counters(
        {stage("batch_wait"): 1.0, stage("batch_wait", "count"): 10.0},
        {stage("batch_wait"): 5.5, stage("batch_wait", "count"): 100.0},
    )
    assert reader("batch_wait_ms")(ctx) == pytest.approx(50.0)
    assert reader("batch_wait_ms")(counters({}, {})) is None


def test_device_wait_ms_sums_both_places_per_group():
    def wait(where, what):
        return f'device_queue_wait_seconds_{what}{{where="{where}"}}'

    ctx = counters(
        {wait("pool", "sum"): 10.0, wait("pool", "count"): 10.0,
         wait("slot", "sum"): 1.0, wait("slot", "count"): 10.0},
        {wait("pool", "sum"): 160.0, wait("pool", "count"): 110.0,
         wait("slot", "sum"): 11.0, wait("slot", "count"): 110.0},
    )
    assert reader("device_wait_ms")(ctx) == pytest.approx(1600.0)
    # the parent program has no such family: nothing, and no exception
    assert reader("device_wait_ms")(counters(
        {"device_stage_seconds_sum{stage=\"hist\"}": 1.0}, {})) is None


def test_the_new_entries_name_their_layers_and_their_files():
    entries = {m["name"]: m for m in benchmark_json()["per_layer"]}
    assert entries["front_self_ms"]["layer"] == "HTTP front"
    assert entries["batch_wait_ms"]["layer"] == "batcher"
    for name in ("device_wait_ms", "idle_attributed_share"):
        assert entries[name]["layer"] == "device queue"
    for name in KERNELS + ["kernel_unnamed_share"]:
        assert entries[name]["layer"] == "kernels"
        assert entries[name]["source"] == "device_trace"
    for name in KERNELS:
        assert entries[name]["workloads"] == ["tile_png512_c32"]
    for name in ("front_self_ms", "batch_wait_ms", "device_wait_ms"):
        assert entries[name]["moves"] == "tile_p50_ms"
        assert entries[name]["source"] == "program_counter"
    assert not os.path.exists(os.path.join(
        HERE, "..", "layer_metrics", "_scopes.json"))
    assert "_scopes" not in entries  # the helper is no metric


# -- the wire format and the graph ----------------------------------------

def test_the_wire_reader_reads_varints_bytes_and_packed_ints():
    # field 1 varint 300; field 2 bytes "ab"; field 36 packed [1, 200]
    message = bytes([0x08, 0xAC, 0x02, 0x12, 0x02]) + b"ab" + bytes(
        [0xA2, 0x02, 0x03, 0x01, 0xC8, 0x01])
    fields = list(_scopes._fields(message))
    assert fields[0] == (1, 300)
    assert (fields[1][0], bytes(fields[1][1])) == (2, b"ab")
    assert fields[2][0] == 36 and _scopes._ints(fields[2][1]) == [1, 200]
    assert _scopes._ints(7) == [7]


def test_scope_names_are_found_in_an_op_name_and_nowhere_else():
    assert _scopes.scope_of(
        "jit(f)/jit(ompb_pack)/ompb_pack/vmap(searchsorted)/while:") == "pack"
    assert _scopes.scope_of("jit(f)/vmap(jit(ompb_tokens))") == "tokens"
    assert _scopes.scope_of("jit(f)/ompb_frame/add:") == "frame"
    assert _scopes.scope_of("jit(f)/my_ompb_packer/add") is None
    assert _scopes.scope_of("jit(f)/ompb_packed/add") is None
    assert _scopes.scope_of("") is None and _scopes.scope_of(None) is None
    assert _scopes.second_level(
        "jit(f)/jit(ompb_pack)/ompb_pack/vmap(searchsorted)/jit(s)/while:"
    ) == "searchsorted"
    assert _scopes.second_level("jit(f)/ompb_pack/offsets/cumsum:") == "offsets"
    assert _scopes.second_level("jit(f)/ompb_pack/mul:") is None


def instruction(ident, name, opcode, op_name="", operands=(), called=()):
    return {"id": ident, "name": name, "opcode": opcode, "op_name": op_name,
            "operands": list(operands), "called": list(called)}


def test_what_the_compiler_made_takes_its_scope_from_the_graph():
    module = {"entry": 1, "computations": {
        1: [
            instruction(1, "p", "parameter"),
            # a layout copy, no metadata: made for the filter that uses it
            instruction(2, "copy.1", "copy", operands=[1]),
            instruction(3, "sub.1", "subtract", "jit(f)/ompb_filter/sub",
                        operands=[2]),
            # a fusion without metadata that nothing named uses: named
            # by what is fused into it
            instruction(4, "fusion.7", "fusion", operands=[3], called=[2]),
            # the pieces of a rewritten cumsum, feeding the loop: a
            # fusion among them is named by its user, not its contents
            instruction(9, "fusion.8", "fusion", operands=[3], called=[2]),
            instruction(5, "reduce-window.3", "reduce-window", operands=[9]),
            instruction(6, "while.2", "while",
                        "jit(f)/ompb_pack/searchsorted/while",
                        operands=[5], called=[3]),
            # nothing names it and nothing uses it; its operand is named
            instruction(7, "copy.9", "copy", operands=[6]),
            # an island: no named neighbour at all
            instruction(8, "iota.1", "iota"),
        ],
        2: [instruction(20, "a", "add", "jit(f)/ompb_tokens/add"),
            instruction(21, "b", "add", "jit(f)/ompb_tokens/mul"),
            instruction(22, "c", "add", "jit(f)/ompb_frame/mul")],
        3: [instruction(30, "body_copy", "copy")],
    }}
    scopes = _scopes.module_scopes(module)
    scopes = {name: found[:2] for name, found in scopes.items()}
    assert scopes["sub.1"] == ("filter", "own")
    assert scopes["copy.1"] == ("filter", "graph")
    assert scopes["fusion.7"] == ("tokens", "fused")
    assert scopes["fusion.8"] == ("pack", "graph")
    assert scopes["reduce-window.3"] == ("pack", "graph")
    assert scopes["while.2"] == ("pack", "own")
    assert scopes["copy.9"] == ("pack", "graph")
    assert scopes["body_copy"] == ("pack", "loop")
    assert scopes["iota.1"] == (None, None)
    assert "a" not in scopes  # fused operations run as no event of their own


def test_self_time_counts_a_loop_and_its_body_once():
    events = [
        (0, 100, "while"),    # the loop
        (10, 40, "body"),     # its body's fusion, twice
        (50, 90, "body"),
        (120, 130, "other"),
        (125, 140, "late"),   # overlapping, not nested: still one union
    ]
    times = _scopes.self_times(events)
    assert times == {"while": 30, "body": 70, "other": 5, "late": 15}
    assert sum(times.values()) == 120  # the union, not the 195 of a plain sum
    assert _scopes.self_times([]) == {}


# -- the trace from the chip ----------------------------------------------

@pytest.fixture(scope="module")
def parsed():
    return _scopes.parse(SCOPED)


@pytest.fixture(scope="module")
def kernels(parsed):
    return _scopes.kernel_seconds(parsed)


def test_the_scopes_are_in_the_event_metadata_of_the_device_plane(parsed):
    assert list(parsed["devices"]) == ["/device:TPU:0"]
    rows = parsed["devices"]["/device:TPU:0"]
    own = {r[4] for r in rows if r[5] == "own"}
    assert own == set(_scopes.SCOPES)  # all five reached the chip
    assert all(r[2].startswith("%") for r in rows)  # names are HLO text
    assert not any("ompb_" in r[2] for r in rows)   # ... and hold no scope
    # a `%while` has no `tf_op` of its own in the trace: the program's
    # HLO names it. Two loops a program: the packer's binary search
    # and the scatter the chip makes of the frame's update-slice
    loops = {(r[4], _scopes.second_level(r[3])) for r in rows
             if " while(" in r[2]}
    assert loops == {("pack", "searchsorted"), ("frame", None)}


def test_the_five_kernels_and_the_remainder_are_the_busy_time(
        parsed, kernels):
    busy = trace_reduce.reduce(trace_reduce.load(SCOPED))["busy_s"]
    assert kernels["busy_s"] == pytest.approx(busy, rel=1e-3)
    assert sum(kernels["scopes"].values()) == pytest.approx(
        kernels["busy_s"], rel=1e-12)
    assert set(kernels["scopes"]) - {None} == set(_scopes.SCOPES)
    assert sum(kernels["how"].values()) == pytest.approx(
        kernels["busy_s"], rel=1e-12)
    # a plain sum of the packer's events counts the loop's body twice
    rows = parsed["devices"]["/device:TPU:0"]
    plain = sum(r[1] - r[0] for r in rows if r[4] == "pack") / 1e12
    assert plain > kernels["scopes"]["pack"] * 1.2
    assert sum(kernels["second"].values()) == pytest.approx(
        kernels["scopes"]["pack"], rel=1e-12)
    assert kernels["second"]["searchsorted"] > 0


def test_most_of_the_busy_time_is_named(kernels):
    unnamed = kernels["scopes"].get(None, 0.0)
    assert unnamed / kernels["busy_s"] < 0.25
    own = kernels["how"]["own"] / kernels["busy_s"]
    assert 0.3 < own < 1.0  # the compiler's own operations carry no name


def test_the_queues_stages_are_on_the_host_plane_with_their_group(parsed):
    # (the trace was recorded while the wait for a slot was still
    # annotated; the program dropped that since, and the readers skip
    # any `ompb.queue.wait_*`: see idle_cover)
    names = {name for name, _, _, _ in parsed["queue"]}
    assert names == {
        _scopes.QUEUE + s for s in
        ("wait_slot", "h2d", "hist", "emit", "compute", "d2h", "frame")}
    groups = sorted({group for _, _, _, group in parsed["queue"]})
    assert len(groups) == 3 and groups == list(
        range(groups[0], groups[0] + 3))
    for group in groups[:2]:  # the two dynamic groups
        mine = sorted(name[len(_scopes.QUEUE):] for name, _, _, g
                      in parsed["queue"] if g == group)
        assert mine == sorted(
            ["wait_slot", "h2d", "hist", "emit", "d2h", "frame"])


def test_idle_time_goes_to_the_stage_that_started_last(parsed):
    idle = _scopes.idle_cover(parsed)
    assert 0 < idle["attributed_s"] <= idle["idle_s"]
    assert sum(idle["by"].values()) == pytest.approx(idle["attributed_s"])
    assert set(idle["by"]) <= {"h2d", "hist", "emit", "compute", "d2h",
                               "frame"}  # a wait is no stage
    seconds, stage, group, open_in_it = idle["longest"][0]
    assert seconds == max(g[0] for g in idle["longest"])
    assert f"{stage}:{group}" in open_in_it
    total = trace_reduce.reduce(trace_reduce.load(SCOPED))
    rows = parsed["devices"]["/device:TPU:0"]
    span = (max(r[1] for r in rows) - min(r[0] for r in rows)) / 1e12
    assert idle["idle_s"] == pytest.approx(span - total["busy_s"], rel=1e-3)


def ctx_for(monkeypatch, path, lanes=6):
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda _dir: path)
    return {"workload": {"name": "tile_png512_c32"},
            "trace": {"device_lanes": lanes, "busy_s": 1.0}}


def test_every_trace_reader_reads_a_number_and_parses_once(
        monkeypatch, capsys, kernels):
    ctx = ctx_for(monkeypatch, SCOPED)
    values = {name: reader(name)(ctx) for name in TRACE_READERS}
    assert all(isinstance(v, float) for v in values.values())
    per_lane = sum(values[name] for name in KERNELS)
    unnamed_ms = 1e3 * kernels["scopes"].get(None, 0.0) / 6
    assert per_lane + unnamed_ms == pytest.approx(
        1e3 * kernels["busy_s"] / 6)
    assert values["kernel_unnamed_share"] == pytest.approx(
        100.0 * kernels["scopes"].get(None, 0.0) / kernels["busy_s"])
    assert 0 < values["idle_attributed_share"] <= 100.0
    out = capsys.readouterr().out
    assert out.count("scopes: parse_seconds=") == 1  # ten readers, one parse
    assert out.count("kernel_scopes: ") == 1 and "idle_by_stage: " in out


def test_an_unnamed_trace_reads_nothing_and_says_why(monkeypatch, capsys):
    ctx = ctx_for(monkeypatch, UNNAMED)
    for name in TRACE_READERS:
        assert reader(name)(ctx) is None, name
    out = capsys.readouterr().out
    assert "no device operation of the trace names an ompb_* scope" in out
    assert "no ompb.queue.* stage" in out


@pytest.mark.parametrize("trace", [None, {}, {"device_lanes": 0}])
def test_no_trace_no_file_or_no_lanes_reads_nothing(
        monkeypatch, tmp_path, trace):
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda _dir: None)
    ctx = {"workload": {"name": "tile_png512_c32"}, "trace": trace}
    for name in TRACE_READERS:
        assert reader(name)(ctx) is None


def test_a_file_that_is_no_trace_reads_nothing_and_does_not_raise(
        monkeypatch, tmp_path, capsys):
    broken = tmp_path / "broken.xplane.pb"
    broken.write_bytes(b"\x0a\xff\xff\xff\xff\x0fnot a protobuf")
    ctx = ctx_for(monkeypatch, str(broken))
    for name in TRACE_READERS:
        assert reader(name)(ctx) is None
    assert "could not be read" in capsys.readouterr().out
