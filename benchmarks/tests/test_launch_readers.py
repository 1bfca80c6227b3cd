"""The readers of the host's time on the chip's clock: `loop_lag_ms`,
`loop_cpu_share`, `gil_wait_ms` and `launch_ms` take deltas of /metrics
over the window, on hand-made readings, and read None, never 0 and
never a raise, on what the parent answers with (`loop_lag_ms` reads
the parent too). `idle_in_launch_share` reads a small trace written
here in the profiler's wire format (`_scopes.py` reads it back): two
chips, their programs on the `XLA Modules` line, the queue's stages
and launches on the host's thread lines; and the chip's clock put on
the host's, on `scoped.xplane.pb` (recorded on the v5e) and on a
trace whose chip lags."""

import os

import pytest

from benchmarks.harness import cell, trace_reduce
from benchmarks.layer_metrics import _launches

HERE = os.path.dirname(os.path.abspath(__file__))
PARENT_TRACE = os.path.join(HERE, "scoped.xplane.pb")  # no launch span


def _read(name, ctx):
    return cell.load_plugin("layer_metrics", name).read(ctx)


def _ctx(before, after, seconds=50.0):
    return {"before": {"metrics": before, "healthz": {}},
            "after": {"metrics": after, "healthz": {}},
            "samples": [], "window": (0.0, seconds),
            "workload": {"name": "tile_png512_c32"}, "trace": None}


def _launch(program, count, wall, cpu):
    label = f'{{program="{program}"}}'
    return {f"device_launch_seconds_count{label}": count,
            f"device_launch_seconds_sum{label}": wall,
            f"device_launch_cpu_seconds_count{label}": count,
            f"device_launch_cpu_seconds_sum{label}": cpu}


def _roles(**cpu):
    return {f'thread_cpu_seconds_total{{role="{role}"}}': seconds
            for role, seconds in cpu.items()}


# -- the counters -----------------------------------------------------------

def test_loop_lag_ms_is_the_windows_mean_beat_lag():
    ctx = _ctx({"event_loop_lag_seconds_sum": 1.0,
                "event_loop_lag_seconds_count": 100},
               {"event_loop_lag_seconds_sum": 1.5,
                "event_loop_lag_seconds_count": 600})
    assert _read("loop_lag_ms", ctx) == pytest.approx(1.0)  # 0.5 s / 500


def test_gil_wait_ms_is_the_windows_mean_wake_lateness():
    ctx = _ctx({"gil_wait_seconds_sum": 0.2, "gil_wait_seconds_count": 50},
               {"gil_wait_seconds_sum": 2.7, "gil_wait_seconds_count": 2550})
    assert _read("gil_wait_ms", ctx) == pytest.approx(1.0)  # 2.5 s / 2500


def test_loop_cpu_share_is_the_loop_threads_share_of_a_core(capsys):
    ctx = _ctx(_roles(loop=10.0, plan=4.0, native=1.0),
               _roles(loop=40.0, plan=9.0, native=26.0), seconds=50.0)
    assert _read("loop_cpu_share", ctx) == pytest.approx(60.0)
    out = capsys.readouterr().out
    assert out.startswith("thread_cpu: ")
    assert '"native": 50.0' in out and '"plan": 10.0' in out


def test_launch_ms_weighs_each_program_by_its_launches(capsys):
    before = {**_launch("hist", 100, 0.1, 0.05), **_launch("emit", 100, 1.0,
                                                           0.1)}
    after = {**_launch("hist", 400, 0.4, 0.2), **_launch("emit", 200, 11.0,
                                                         0.3)}
    # 300 hist launches of 1 ms, 100 emit launches of 100 ms
    assert _read("launch_ms", _ctx(before, after)) == pytest.approx(
        (300 * 1.0 + 100 * 100.0) / 400)
    out = capsys.readouterr().out
    assert out.startswith("launch: ")
    programs = cell.load_plugin("layer_metrics", "launch_ms").by_program(
        _ctx(before, after))
    assert programs["emit"] == pytest.approx(
        {"wall_ms": 100.0, "cpu_ms": 2.0, "count": 100})
    assert programs["hist"]["cpu_ms"] == pytest.approx(0.5)


# what the parent answers with: none of the new families
PARENT = {"event_loop_lag_seconds_sum": 1.0,
          "event_loop_lag_seconds_count": 10,
          "process_cpu_seconds_total": 3.0}


@pytest.mark.parametrize("name", ["loop_cpu_share", "gil_wait_ms",
                                  "launch_ms"])
def test_on_the_parents_metrics_the_new_families_read_none(name):
    after = dict(PARENT, event_loop_lag_seconds_count=90,
                 process_cpu_seconds_total=9.0)
    assert _read(name, _ctx(PARENT, after)) is None


def test_loop_lag_ms_reads_the_parent_too():
    after = dict(PARENT, event_loop_lag_seconds_sum=1.8,
                 event_loop_lag_seconds_count=90)
    assert _read("loop_lag_ms", _ctx(PARENT, after)) == pytest.approx(10.0)


QUIET = {"event_loop_lag_seconds_count": 5, "gil_wait_seconds_count": 5,
         **_launch("emit", 7, 0.1, 0.01), **_roles(loop=2.0)}


@pytest.mark.parametrize("name", ["loop_lag_ms", "gil_wait_ms", "launch_ms"])
def test_a_window_with_nothing_counted_reads_none(name):
    assert _read(name, _ctx(QUIET, dict(QUIET))) is None


def test_a_loop_that_used_no_cpu_in_the_window_reads_zero():
    assert _read("loop_cpu_share", _ctx(QUIET, dict(QUIET))) == 0.0


# -- a trace in the profiler's wire format ---------------------------------

def _varint(n):
    out = bytearray()
    while True:
        byte = n & 0x7F
        n >>= 7
        if n:
            out.append(byte | 0x80)
        else:
            out.append(byte)
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _message(*fields):
    return b"".join(_field(n, v) for n, v in fields)


class Plane:
    """One XPlane: names interned as event and stat metadata."""

    def __init__(self, name):
        self.name, self.lines = name, []
        self.events, self.stats = {}, {}

    def _id(self, table, name):
        return table.setdefault(name, len(table) + 1)

    def line(self, name, events):
        """events: [(name, start_us, end_us, {stat: int or str})]."""
        body = []
        for event, start, end, stats in events:
            xstats = []
            for key, value in stats.items():
                if isinstance(value, str):  # a reference to a stat name
                    value = (7, self._id(self.stats, value))
                else:
                    value = (4, value)
                xstats.append(_message((1, self._id(self.stats, key)), value))
            body.append(_message(
                (1, self._id(self.events, event)),
                (2, int(start * 1e6)), (3, int((end - start) * 1e6)),
                *[(4, s) for s in xstats]))
        self.lines.append(_message((2, name), (3, 0),
                                   *[(4, e) for e in body]))

    def encode(self):
        events = [_message((1, i), (2, _message((1, i), (2, name))))
                  for name, i in self.events.items()]
        stats = [_message((1, i), (2, _message((1, i), (2, name))))
                 for name, i in self.stats.items()]
        return _message((2, self.name), *[(3, l) for l in self.lines],
                        *[(4, e) for e in events], *[(5, s) for s in stats])


def _ops(*spans):
    return [("%fusion = f32[] fusion()", s, e, {}) for s, e in spans]


def _module(function, start, end):
    return (f"jit_{function}(123)", start, end, {})


def _write(path, planes):
    with open(path, "wb") as f:
        f.write(b"".join(_field(1, p.encode()) for p in planes))
    return str(path)


WINDOW_S = 250e-6  # the slice: 250 us


@pytest.fixture
def two_chips(tmp_path):
    """Times in us. Chip 1 idles 10-100 and its pass 1 starts at 100:
    the submit thread for chip 1 was in `h2d` 30-58 and opened the
    launch at 60 (so 40 us in launch, 50 host late: 28 under `h2d`, 22
    in no stage) while chip 0's
    emit launch, 5-200, covers the whole gap; 120-150 ends in a crop,
    which no launch names. Chip 0 idles 50-180 and its emit, launched
    at 5, starts at 180: the whole gap is in launch."""
    chip0, chip1 = Plane("/device:TPU:0"), Plane("/device:TPU:1")
    chip0.line("XLA Ops", _ops((0, 50), (180, 240)))
    chip0.line("XLA Modules", [_module("_filter_histogram_core", 0, 50),
                               _module("dynamic_emit_local", 180, 240)])
    chip1.line("XLA Ops", _ops((0, 10), (100, 120), (150, 160)))
    chip1.line("XLA Modules", [_module("dynamic_emit_local", 0, 10),
                               _module("_filter_histogram_core", 100, 120),
                               _module("crop", 150, 160)])
    host = Plane("/host:CPU")
    host.line("python3", [  # the plan worker, for chip 0
        ("ompb.queue.hist", 0, 4, {"group": 7, "lanes": 2, "chip": 0}),
        ("ompb.queue.plan", 4, 5, {"group": 7, "lanes": 2, "chip": 0}),
        ("ompb.queue.launch", 5, 200, {"group": 7, "lanes": 2, "chip": 0,
                                       "program": "emit"})])
    host.line("python3", [  # the submit thread, for chip 1
        ("ompb.queue.h2d", 30, 58, {"group": 8, "lanes": 1, "chip": 1}),
        ("ompb.queue.launch", 60, 110, {"group": 8, "lanes": 1, "chip": 1,
                                        "program": "hist"})])
    return _write(tmp_path / "two.xplane.pb", [chip0, chip1, host])


def test_a_gap_goes_to_the_launch_for_its_own_chip(two_chips):
    chips = {c["chip"]: c for c in _launches.split(
        _launches.parse(two_chips), WINDOW_S)}
    one = chips[1]
    assert one["in_launch_s"] == pytest.approx(40e-6)
    assert one["host_late_s"] == pytest.approx(
        {"h2d": 28e-6, "none": 22e-6})
    assert one["unmatched_s"] == pytest.approx({"crop": 30e-6})
    assert one["after_return_s"] == 0
    # the longest gap names chip, group, program and the thread's stage
    assert one["longest"][0] == pytest.approx(
        [90e-6, 1, 8, "hist", "h2d", 40e-6])
    zero = chips[0]
    assert zero["in_launch_s"] == pytest.approx(130e-6)
    assert zero["host_late_s"] == {} and zero["unmatched_s"] == {}


def test_the_parts_add_up_to_each_chips_idle_time(two_chips):
    for chip in _launches.split(_launches.parse(two_chips), WINDOW_S):
        parts = (chip["in_launch_s"] + sum(chip["host_late_s"].values())
                 + sum(chip["unmatched_s"].values()) + chip["edges_s"])
        assert parts == pytest.approx(chip["idle_s"])
        assert chip["idle_share"] == pytest.approx(
            100.0 * chip["idle_s"] / WINDOW_S)
    chips = _launches.split(_launches.parse(two_chips), WINDOW_S)
    assert [c["edges_s"] for c in chips] == pytest.approx([10e-6, 90e-6])
    # chip 1: 210 us idle in the slice, 120 of them between its
    # operations (its span, 0-160, holds 40 busy)
    assert chips[1]["parts_share"] == pytest.approx(100.0 * 120 / 250)
    assert chips[1]["span_idle_share"] == pytest.approx(100.0 * 120 / 160)


def test_a_gap_inside_a_program_and_a_launch_that_returned(tmp_path):
    """One chip, launches that name none: the chip is that one. The
    plan worker pulled the counts until 12 and planned until 14; the
    emit's launch, 15-20, returned before its program started at 40
    (20 us of the gap after the call's return); 60-70 lies inside the
    emit."""
    chip = Plane("/device:TPU:0")
    chip.line("XLA Ops", _ops((0, 10), (40, 60), (70, 80)))
    chip.line("XLA Modules", [_module("_fused_filter_deflate", 0, 10),
                              _module("dynamic_emit_local", 39, 80)])
    host = Plane("/host:CPU")
    host.line("python3", [
        ("ompb.queue.hist", 2, 12, {"group": 3, "lanes": 1}),
        ("ompb.queue.plan", 12, 14, {"group": 3, "lanes": 1}),
        ("ompb.queue.launch", 15, 20, {"group": 3, "lanes": 1,
                                       "program": "emit"})])
    path = _write(tmp_path / "one.xplane.pb", [chip, host])
    (one,) = _launches.split(_launches.parse(path), 100e-6)
    assert one["in_launch_s"] == pytest.approx(25e-6)
    assert one["after_return_s"] == pytest.approx(20e-6)
    # the counts' pull (`hist`), the plan, then no stage
    assert one["host_late_s"] == pytest.approx(
        {"hist": 2e-6, "plan": 2e-6, "none": 1e-6})
    assert one["unmatched_s"] == pytest.approx({"in_program": 10e-6})


def test_a_program_whose_launch_is_missing_is_unmatched(tmp_path):
    chip = Plane("/device:TPU:0")
    chip.line("XLA Ops", _ops((5, 10), (30, 40)))
    chip.line("XLA Modules", [_module("dynamic_emit_local", 2, 10),
                              _module("dynamic_emit_local", 30, 40)])
    host = Plane("/host:CPU")
    host.line("python3", [  # the first emit's: taken by its program
        ("ompb.queue.launch", 0, 1, {"group": 1, "lanes": 1,
                                     "program": "emit"})])
    path = _write(tmp_path / "missing.xplane.pb", [chip, host])
    (one,) = _launches.split(_launches.parse(path), 40e-6)
    assert one["in_launch_s"] == 0
    assert one["unmatched_s"] == pytest.approx({"no_launch:emit": 20e-6})


def _trace_ctx(monkeypatch, path, window_s):
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda _dir: path)
    return {"workload": {"name": "zstack32_png512_x4"},
            "trace": {"window_s": window_s, "busy_s": 1.0}}


def test_the_metric_is_the_mean_over_the_chips_of_the_slice_in_launch(
        monkeypatch, capsys, two_chips):
    ctx = _trace_ctx(monkeypatch, two_chips, WINDOW_S)
    value = _read("idle_in_launch_share", ctx)
    assert value == pytest.approx((130 / 250 + 40 / 250) / 2 * 100)
    out = capsys.readouterr().out
    assert "idle_by_launch: " in out and "launch_chips=['0', '1']" in out
    assert _read("idle_in_launch_share", ctx) == value  # parsed once
    assert capsys.readouterr().out == ""


def test_a_trace_without_launch_spans_reads_none(monkeypatch, capsys):
    ctx = _trace_ctx(monkeypatch, PARENT_TRACE, 0.02)
    assert _read("idle_in_launch_share", ctx) is None
    assert "no ompb.queue.launch span" in capsys.readouterr().out


@pytest.mark.parametrize("trace", [None, {}, {"window_s": 0}])
def test_no_trace_reads_none(monkeypatch, trace):
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda _dir: None)
    ctx = {"workload": {"name": "tile_png512_c32"}, "trace": trace}
    assert _read("idle_in_launch_share", ctx) is None


def test_a_file_that_is_no_trace_reads_none_and_says_so(
        monkeypatch, tmp_path, capsys):
    broken = tmp_path / "broken.xplane.pb"
    broken.write_bytes(b"\x0a\xff\xff\xff\xff\x0fnot a protobuf")
    ctx = _trace_ctx(monkeypatch, str(broken), 1.0)
    assert _read("idle_in_launch_share", ctx) is None
    assert "could not be read" in capsys.readouterr().out


def test_the_chips_clock_is_put_on_the_hosts_by_the_enqueue_flows():
    """On the v5e a program starts 1.44-1.49 ms "before" the host's
    runtime enqueued it: the device's clock lags the host's."""
    parsed = _launches.parse(PARENT_TRACE)
    offset, joined = _launches.clock_offset(
        parsed["chips"][0], parsed["enqueues"])
    assert joined == 5
    assert offset == pytest.approx(1.4892e9, rel=1e-3)  # ps


def test_a_lagging_chip_clock_is_aligned_before_the_match(tmp_path):
    """The chip's clock lags the host's by 30 us. On the chip's clock
    its pass 1 starts at 70, before the launch (60 on the host's):
    aligned by the program's enqueue (host 100), the gap 50-100 (host)
    splits 40 in launch, 5 of them after the call returned, and 10
    host late."""
    chip = Plane("/device:TPU:0")
    chip.line("XLA Ops", _ops((10, 20), (70, 90)))
    chip.line("XLA Modules", [
        ("jit__filter_histogram_core(1)", 70, 90, {"_c": 77})])
    host = Plane("/host:CPU")
    host.line("python3", [
        ("ompb.queue.h2d", 10, 60, {"group": 2, "lanes": 1}),
        ("ompb.queue.launch", 60, 95, {"group": 2, "lanes": 1,
                                       "program": "hist"})])
    host.line("tfrt-non-blocking-queue", [
        ("DoEnqueueProgram", 100, 101, {"_p": 77})])
    path = _write(tmp_path / "lag.xplane.pb", [chip, host])
    (one,) = _launches.split(_launches.parse(path), 200e-6)
    assert one["clock_offset_ms"] == pytest.approx(30e-3)
    assert one["programs_joined"] == 1
    assert one["in_launch_s"] == pytest.approx(40e-6)
    assert one["after_return_s"] == pytest.approx(5e-6)
    assert one["host_late_s"] == pytest.approx({"h2d": 10e-6})
