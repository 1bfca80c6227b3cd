"""The device queue as a three-stage pipe (models/device_dispatch.py,
PR 34): a plan worker between the submit thread and the pull worker,
and a slot that follows the chip. Every ordering here is held by an
event or a gate, none by a sleep: with the pull of group k held, group
k+1's emit is launched and `overlapped` counts it; a slot comes back
when the group's last program is seen done, and exactly once whichever
stage raises; `close()` drains both workers and its deadline still
abandons a wedged group. Each program's launch call is a span of its
own on the thread that makes it (`ompb.queue.launch`, with its wall
and CPU time), closed before the queue notes the launch."""

import concurrent.futures
import threading
import time

import jax
import numpy as np
import pytest

from omero_ms_pixel_buffer_tpu.models import device_dispatch as dd
from omero_ms_pixel_buffer_tpu.ops import device_deflate, png
from omero_ms_pixel_buffer_tpu.ops.png import decode_png
from omero_ms_pixel_buffer_tpu.resilience import INJECTOR
from omero_ms_pixel_buffer_tpu.resilience.faultinject import always
from omero_ms_pixel_buffer_tpu.runtime import native
from test_queue_spans import Notes, counts, delta

WAIT = 120  # seconds: every wait of this file is bounded
N = 16


def tiles(seed, b=2):
    return np.random.default_rng(seed).integers(
        0, 60000, (b, N, N)).astype(np.uint16)


def submit(disp, batch, mode="dynamic", chip=None):
    b = batch.shape[0]
    if chip is not None:
        batch = jax.device_put(batch, chip)
    return disp.submit(
        batch, N, 1 + N * 2, 2, "up", mode, list(range(b)),
        [(N, N)] * b, 16, 0, staged=chip is not None, device=chip,
    )


def assert_pngs(out, batch):
    assert set(out) == set(range(batch.shape[0]))
    for lane, data in out.items():
        np.testing.assert_array_equal(decode_png(data), batch[lane])


def pipe_of(disp, width):
    return disp._pipe if width == 1 else disp._chip_pipe


def chip_of(width):
    return None if width == 1 else jax.devices()[1]


class Launches:
    """Wraps `_note_last_launch`: an event a group's last launch."""

    def __init__(self, disp, groups):
        self.events = [threading.Event() for _ in range(groups)]
        self._n = 0
        self._lock = threading.Lock()
        real = disp._note_last_launch

        def noted(t_launch, group=None):
            real(t_launch, group)
            with self._lock:
                event = self.events[self._n]
                self._n += 1
            event.set()

        disp._note_last_launch = noted


def gated(disp, name, gate, entered=None):
    """Hold the dispatcher's method `name` on `gate`, noting in
    `entered` the group id each call is for."""
    real = getattr(disp, name)

    def held(group, *args, **kwargs):
        if entered is not None:
            entered.append(group.gid)
        assert gate.wait(timeout=WAIT)
        return real(group, *args, **kwargs)

    setattr(disp, name, held)


@pytest.fixture
def disp():
    d = dd.DeviceEncodeDispatcher({}, queue_depth=2, chips=4)
    yield d
    d.close()


# -- the overlap exists, and the counter says so ---------------------------

def test_the_next_emit_is_launched_while_the_pull_of_this_one_is_held(disp):
    gate = threading.Event()
    launches = Launches(disp, 2)
    gated(disp, "_readback_group", gate)
    batches = [tiles(1), tiles(2)]
    try:
        futures = [submit(disp, b) for b in batches]
        # group 1 sits on the pull worker, its emit launched and not
        # seen done; group 2's plan ran beside it and launched its own
        assert all(e.wait(timeout=WAIT) for e in launches.events)
        snap = disp.snapshot()
        assert snap["groups"] == 2 and snap["inflight"] == 2
        assert snap["overlapped"] == 1 and snap["idle_gaps"] == 0
        assert disp._pipe.emitting == 2
        assert not any(f.done() for f in futures)
    finally:
        gate.set()
    for fut, batch in zip(futures, batches):
        assert_pngs(fut.result(timeout=WAIT), batch)
    snap = disp.snapshot()
    assert snap["inflight"] == 0 and disp._pipe.emitting == 0
    assert snap["overlapped_fraction"] == 1.0


@pytest.mark.parametrize("mode", ["dynamic", "rle"])
def test_groups_sent_one_after_the_other_count_an_idle_gap_each(disp, mode):
    """One count a group past the first (which has nothing to be
    compared with): a launch that finds the pipe's device empty is an
    idle gap, as long as the time since the last group was seen done."""
    for seed in range(4):
        assert_pngs(submit(disp, tiles(seed), mode).result(timeout=WAIT),
                    tiles(seed))
    snap = disp.snapshot()
    assert (snap["groups"], snap["overlapped"], snap["idle_gaps"]) == (4, 0, 3)
    assert snap["overlapped_fraction"] == 0.0
    assert 0.0 < snap["idle_gap_mean_ms"] <= snap["idle_gap_max_ms"]
    assert snap["compute_ms_mean"] > 0.0


# -- the slot follows the chip ---------------------------------------------

@pytest.mark.parametrize("mode", ["dynamic", "rle"])
def test_the_slot_comes_back_when_the_last_program_is_seen_done(mode):
    """Depth 1, group 1 held in its pull and frame: group 2 can only be
    staged and launched if the slot came back at the wait's end."""
    disp = dd.DeviceEncodeDispatcher({}, queue_depth=1)
    gate = threading.Event()
    launches = Launches(disp, 2)
    held = []
    real = disp._pull_and_frame

    def pull(*args, **kwargs):
        held.append(args[2])  # the group's id
        assert gate.wait(timeout=WAIT)
        return real(*args, **kwargs)

    disp._pull_and_frame = pull
    try:
        first, second = submit(disp, tiles(3), mode), submit(
            disp, tiles(4), mode)
        assert launches.events[1].wait(timeout=WAIT)
        assert len(held) == 1 and not first.done()  # group 1: not framed
        assert disp.snapshot()["inflight"] == 1  # group 2's, alone
        gate.set()
        assert_pngs(first.result(timeout=WAIT), tiles(3))
        assert_pngs(second.result(timeout=WAIT), tiles(4))
        assert disp._pipe.slots._value == 1
    finally:
        gate.set()
        disp.close()


def on_worker(kind, real, exc):
    """`real`, but raising `exc` on the queue's `kind` workers."""
    def maybe(*args, **kwargs):
        if threading.current_thread().name.startswith(f"devenc-{kind}"):
            raise exc
        return real(*args, **kwargs)
    return maybe


def raising(exc):
    def boom(*args, **kwargs):
        raise exc
    return boom


def break_stage(monkeypatch, site, exc):
    """Make one stage of the pipe raise `exc` for every group."""
    if site == "staging":
        INJECTOR.install("device.encode-group", always(exc))
    elif site == "hist":  # the plan worker's pull of the counts
        monkeypatch.setattr(jax, "device_get", on_worker(
            "plan", jax.device_get, exc))
    elif site == "plan":
        monkeypatch.setattr(
            device_deflate, "build_dynamic_tables", raising(exc))
    elif site == "native plan":  # inside the engine's one plan call
        monkeypatch.setattr(native.NativeEngine, "dynamic_plan_batch",
                            raising(exc))
    elif site == "emit-launch":
        monkeypatch.setattr(
            device_deflate, "dynamic_emit_planned", raising(exc))
    elif site == "wait":
        monkeypatch.setattr(jax, "block_until_ready", on_worker(
            "pull", jax.block_until_ready, exc))
    elif site == "pull":
        monkeypatch.setattr(jax, "device_get", on_worker(
            "pull", jax.device_get, exc))
    elif site == "frame":
        monkeypatch.setattr(png, "frame_png", raising(exc))
    else:
        raise AssertionError(site)


SITES = ["staging", "hist", "plan", "native plan", "emit-launch", "wait",
         "pull", "frame"]


@pytest.mark.parametrize("width", [1, 4])
@pytest.mark.parametrize("site", SITES)
def test_a_storm_of_failures_gives_every_slot_back_exactly_once(
        disp, monkeypatch, site, width):
    """Every group of the storm fails at `site` with its own future and
    the semaphore is back at `queue-depth × width`: no slot lost (the
    next groups would wait for ever) and none given back twice (the
    bound would widen)."""
    chip, exc = chip_of(width), RuntimeError(f"{site} failed")
    # a healthy group first: the programs are compiled and, on the wide
    # pipe, the pipe exists
    assert_pngs(submit(disp, tiles(0), chip=chip).result(timeout=WAIT),
                tiles(0))
    pipe = pipe_of(disp, width)
    try:
        break_stage(monkeypatch, site, exc)
        # a site that only a dynamic group passes leaves `rle` whole
        storm = [(submit(disp, tiles(seed), mode, chip), mode, seed)
                 for seed in range(5) for mode in ("dynamic", "rle")]
        for fut, mode, seed in storm:
            if mode == "rle" and site in (
                    "hist", "plan", "native plan", "emit-launch"):
                assert_pngs(fut.result(timeout=WAIT), tiles(seed))
            else:
                with pytest.raises(RuntimeError, match=f"{site} failed"):
                    fut.result(timeout=WAIT)
    finally:
        monkeypatch.undo()
        INJECTOR.clear()
    assert pipe.slots._value == 2 * width
    assert disp.snapshot()["inflight"] == 0 and pipe.inflight == 0
    assert pipe.emitting == 0
    # nothing stalled: the pipe serves the next group
    assert_pngs(submit(disp, tiles(9), chip=chip).result(timeout=WAIT),
                tiles(9))
    assert pipe.slots._value == 2 * width


def test_a_failing_group_neither_stalls_nor_reorders_the_others(
        disp, monkeypatch):
    """One group's plan raises, between two healthy ones: it resolves
    its own future, theirs carry their own tiles, and the pull worker
    took them up in the order they came."""
    real = device_deflate.build_dynamic_tables
    calls = []

    def second_fails(*args, **kwargs):
        calls.append(None)
        if len(calls) == 2:
            raise RuntimeError("plan failed")
        return real(*args, **kwargs)

    monkeypatch.setattr(device_deflate, "build_dynamic_tables", second_fails)
    pulled, gate = [], threading.Event()
    gate.set()  # open: only the order of entry is wanted
    gated(disp, "_readback_group", gate, pulled)
    first = next(disp._gids) + 1
    futures = [submit(disp, tiles(seed)) for seed in range(3)]
    assert_pngs(futures[0].result(timeout=WAIT), tiles(0))
    with pytest.raises(RuntimeError, match="plan failed"):
        futures[1].result(timeout=WAIT)
    assert_pngs(futures[2].result(timeout=WAIT), tiles(2))
    assert pulled == [first, first + 2]
    assert disp._pipe.slots._value == 2


# -- order -----------------------------------------------------------------

@pytest.mark.parametrize("mode", ["dynamic", "rle"])
def test_a_one_wide_pipe_pulls_in_submission_order(mode):
    d = dd.DeviceEncodeDispatcher({}, queue_depth=2)
    pulled = []
    gate = threading.Event()
    gate.set()
    gated(d, "_readback_group", gate, pulled)
    try:
        first = next(d._gids) + 1
        futures = [submit(d, tiles(seed), mode) for seed in range(6)]
        for seed, fut in enumerate(futures):  # each its own tiles
            assert_pngs(fut.result(timeout=WAIT), tiles(seed))
        assert pulled == list(range(first, first + 6))
    finally:
        d.close()


def test_a_wide_pipe_pulls_in_the_order_the_last_programs_were_handed_on(
        disp):
    """Four workers of each kind: three pull workers are held with a
    group each, so the fourth alone takes up what comes: in the order
    the groups were handed to the pull pool (four submit threads and
    four plan workers race for that order; the pool keeps it), and
    every future carries its own group's tiles."""
    chip = jax.devices()[1]
    assert_pngs(submit(disp, tiles(0), chip=chip).result(timeout=WAIT),
                tiles(0))  # the pipe exists
    pipe = disp._chip_pipe
    blockers_in, gate, all_in = [], threading.Event(), threading.Event()
    handed, pulled, lock = [], [], threading.Lock()
    real_hand, real_readback = disp._hand, disp._readback_group

    def hand(workers, group, fn, *args):
        with lock:  # the note and the hand-over are one step
            if workers is pipe.pull:
                handed.append(group.gid)
            real_hand(workers, group, fn, *args)

    def readback(group, *args, **kwargs):
        if len(blockers_in) < 3:
            blockers_in.append(group.gid)
            if len(blockers_in) == 3:
                all_in.set()
            assert gate.wait(timeout=WAIT)
        else:
            pulled.append(group.gid)
        return real_readback(group, *args, **kwargs)

    disp._hand, disp._readback_group = hand, readback
    try:
        blockers = [submit(disp, tiles(10 + i), chip=chip) for i in range(3)]
        # every blocker is on a pull worker of its own before the rest
        # is sent (three of the eight slots stay with them)
        assert all_in.wait(timeout=WAIT)
        rest = [(submit(disp, tiles(20 + i), chip=chip), 20 + i)
                for i in range(5)]
        for fut, seed in rest:
            assert_pngs(fut.result(timeout=WAIT), tiles(seed))
        assert pulled == [g for g in handed if g not in blockers_in]
        assert len(pulled) == 5
        gate.set()
        for i, fut in enumerate(blockers):
            assert_pngs(fut.result(timeout=WAIT), tiles(10 + i))
        assert pipe.slots._value == 8
    finally:
        gate.set()


# -- close -----------------------------------------------------------------

def test_close_drains_the_plan_and_the_pull_workers():
    d = dd.DeviceEncodeDispatcher({}, queue_depth=2)
    futures = [submit(d, tiles(seed), mode)
               for seed in range(3) for mode in ("dynamic", "rle")]
    d.close()  # must DRAIN, not abandon
    for fut in futures:
        assert set(fut.result(timeout=5)) == {0, 1}
    for pool in (d._pipe.submit_pool, d._pipe.plan, d._pipe.pull):
        assert pool._shutdown
        assert not any(t.is_alive() for t in pool._threads)
    assert d._pipe.slots._value == 2
    with pytest.raises(RuntimeError):
        submit(d, tiles(0))


@pytest.mark.parametrize("stage", ["_plan_group", "_readback_group"])
def test_closes_deadline_abandons_a_group_wedged_on_either_worker(stage):
    d = dd.DeviceEncodeDispatcher({}, queue_depth=2)
    gate = threading.Event()
    gated(d, stage, gate)
    try:
        futures = [submit(d, tiles(seed)) for seed in range(3)]
        done = concurrent.futures.ThreadPoolExecutor(1).submit(
            d.close, drain_timeout=0.5)
        done.result(timeout=30)  # close() came back past its deadline
        for fut in futures:
            with pytest.raises(TimeoutError):
                fut.result(timeout=5)
    finally:
        gate.set()
    # unwedged, the abandoned workers run out and give every slot back,
    # once: the late results lose the race for the futures benignly
    for pool in (d._pipe.submit_pool, d._pipe.plan, d._pipe.pull):
        for thread in list(pool._threads):
            thread.join(timeout=WAIT)
            assert not thread.is_alive()
    assert d._pipe.slots._value == 2
    assert d.snapshot()["inflight"] == 0 and d._pipe.emitting == 0


# -- the launch span -------------------------------------------------------

# the thread that launches each program
LAUNCHER = {"hist": "devenc-submit", "compute": "devenc-submit",
            "emit": "devenc-plan"}
PROGRAMS = {"dynamic": ["hist", "emit"], "rle": ["compute"]}


@pytest.fixture
def notes(monkeypatch):
    recorded = Notes()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", recorded)
    return recorded


@pytest.mark.parametrize("width", [1, 4])
@pytest.mark.parametrize("mode", sorted(PROGRAMS))
def test_each_program_is_launched_under_a_span_on_the_calling_thread(
        disp, notes, mode, width):
    chip = chip_of(width)
    first = next(disp._gids) + 1
    assert_pngs(submit(disp, tiles(1), mode, chip).result(timeout=WAIT),
                tiles(1))
    launches = [n for n in notes.of_group(first)
                if n["name"] == "ompb.queue.launch"]
    assert [n["tags"]["program"] for n in launches] == PROGRAMS[mode]
    for note in launches:
        program = note["tags"]["program"]
        assert note["closed"] == 1
        assert note["opened_on"] == note["closed_on"]
        assert note["opened_on"].startswith(LAUNCHER[program])
        want = {"group": first, "lanes": 2, "program": program}
        if chip is not None:
            want["chip"] = chip.id
        assert note["tags"] == want


@pytest.mark.parametrize("mode", sorted(PROGRAMS))
def test_each_launch_observes_its_wall_and_cpu_time_once(
        disp, monkeypatch, mode):
    seen = {"wall": [], "cpu": []}
    for kind, hist in (("wall", dd.DEVICE_LAUNCH_SECONDS),
                       ("cpu", dd.DEVICE_LAUNCH_CPU_SECONDS)):
        def noted(value, exemplar=None, _real=hist.observe, _kind=kind,
                  **labels):
            seen[_kind].append((threading.get_ident(), labels, value))
            _real(value, exemplar, **labels)
        monkeypatch.setattr(hist, "observe", noted)
    wall_before = counts(dd.DEVICE_LAUNCH_SECONDS, "program")
    cpu_before = counts(dd.DEVICE_LAUNCH_CPU_SECONDS, "program")
    for seed in range(3):
        assert_pngs(submit(disp, tiles(seed), mode).result(timeout=WAIT),
                    tiles(seed))
    want = {program: 3 for program in PROGRAMS[mode]}
    assert delta(wall_before, counts(dd.DEVICE_LAUNCH_SECONDS,
                                     "program")) == want
    assert delta(cpu_before, counts(dd.DEVICE_LAUNCH_CPU_SECONDS,
                                    "program")) == want
    # one launch's two observations, made back to back on its thread
    for thread in {t for t, _, _ in seen["wall"]}:
        walls = [(l, v) for t, l, v in seen["wall"] if t == thread]
        cpus = [(l, v) for t, l, v in seen["cpu"] if t == thread]
        assert [l for l, _ in walls] == [l for l, _ in cpus]
        for (_, wall), (_, cpu) in zip(walls, cpus):
            assert 0.0 <= cpu <= wall


def test_a_launch_that_raises_closes_its_span_and_observes_nothing(
        disp, notes, monkeypatch):
    wall_before = counts(dd.DEVICE_LAUNCH_SECONDS, "program")
    cpu_before = counts(dd.DEVICE_LAUNCH_CPU_SECONDS, "program")
    monkeypatch.setattr(device_deflate, "dynamic_emit_planned",
                        raising(RuntimeError("emit launch failed")))
    with pytest.raises(RuntimeError, match="emit launch failed"):
        submit(disp, tiles(2)).result(timeout=WAIT)
    # pass 1 was launched and observed; the emit's launch raised
    assert delta(wall_before, counts(dd.DEVICE_LAUNCH_SECONDS,
                                     "program")) == {"hist": 1}
    assert delta(cpu_before, counts(dd.DEVICE_LAUNCH_CPU_SECONDS,
                                    "program")) == {"hist": 1}
    emits = [n for n in notes.seen if n["name"] == "ompb.queue.launch"
             and n["tags"]["program"] == "emit"]
    assert len(emits) == 1 and emits[0]["closed"] == 1
    assert disp.snapshot()["inflight"] == 0


@pytest.mark.parametrize("mode", sorted(PROGRAMS))
def test_the_queue_notes_a_launch_after_its_span_has_closed(
        disp, monkeypatch, mode):
    """The launch span holds the call and nothing else: the stamps
    `_note_group` / `_note_last_launch` take (and the stage that opens
    at the launch) come after it, as they came after the call."""
    closed, noted = [], []
    real_exit = dd._Launch.__exit__

    def exit_(self, *exc):
        real_exit(self, *exc)
        closed.append((self.program, time.perf_counter()))

    monkeypatch.setattr(dd._Launch, "__exit__", exit_)
    for name in ("_note_group", "_note_last_launch"):
        real = getattr(disp, name)

        def note(t_launch, *args, _real=real, _name=name):
            noted.append((_name, t_launch))
            return _real(t_launch, *args)

        setattr(disp, name, note)
    assert_pngs(submit(disp, tiles(4), mode).result(timeout=WAIT), tiles(4))
    ends = dict(closed)
    first = ends[PROGRAMS[mode][0]]
    last = ends[PROGRAMS[mode][-1]]
    assert dict(noted)["_note_group"] >= first
    assert dict(noted)["_note_last_launch"] >= last
