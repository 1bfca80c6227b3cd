"""The device queue's stages and waits on two clocks
(models/device_dispatch.py): `device_stage_seconds` has seven labels
(`plan`, the host's Huffman plan of a dynamic group, since PR 34) and
one observation per stage per group; every stage is also a
`jax.profiler.TraceAnnotation` `ompb.queue.<stage>` that carries the
group's id; `device_queue_wait_seconds` gets one `pool` and one `slot`
observation per group, whatever the stage function does (a wait is a
histogram only: no annotation)."""

import glob
import threading
import time

import jax
import numpy as np
import pytest

from omero_ms_pixel_buffer_tpu.models import device_dispatch as dd
from omero_ms_pixel_buffer_tpu.obs.recorder import FlightRecord, record_scope

STAGES = {"h2d", "compute", "hist", "plan", "emit", "d2h", "frame"}
PER_GROUP = {
    "rle": {"h2d", "compute", "d2h", "frame"},
    "stored": {"h2d", "compute", "d2h", "frame"},
    "dynamic": {"h2d", "hist", "plan", "emit", "d2h", "frame"},
}
rng = np.random.default_rng(5)


def counts(hist, label) -> dict:
    """{label value: (observations, seconds)} of one histogram family."""
    out = {}
    for line in hist.collect():
        for suffix, at in (("_count{", 0), ("_sum{", 1)):
            head = hist.name + suffix + label + '="'
            if line.startswith(head):
                value = line[len(head):].split('"')[0]
                out.setdefault(value, [0.0, 0.0])[at] = float(
                    line.rpartition(" ")[2])
    return {k: tuple(v) for k, v in out.items()}


def delta(before, after) -> dict:
    return {
        k: after[k][0] - before.get(k, (0, 0))[0]
        for k in after if after[k][0] != before.get(k, (0, 0))[0]
    }


def tiles(b=2, n=16):
    return rng.integers(0, 60000, (b, n, n)).astype(np.uint16)


def submit(disp, batch, mode="rle"):
    b, n = batch.shape[0], batch.shape[1]
    return disp.submit(
        batch, n, 1 + n * 2, 2, "up", mode,
        list(range(b)), [(n, n)] * b, 16, 0,
    )


@pytest.fixture
def disp():
    d = dd.DeviceEncodeDispatcher({}, queue_depth=2)
    yield d
    d.close()


class Notes:
    """Stands in for `jax.profiler.TraceAnnotation`: records every
    annotation, its tags, and the threads that opened and closed it."""

    def __init__(self):
        self.seen = []
        self._lock = threading.Lock()

    def __call__(self, name, **tags):
        note = {"name": name, "tags": tags, "closed": 0,
                "opened_on": threading.current_thread().name,
                "closed_on": None}
        with self._lock:
            self.seen.append(note)
        notes = self

        class Open:
            def __enter__(self):
                return self

            def __exit__(self, *exc):
                with notes._lock:
                    note["closed"] += 1
                    note["closed_on"] = threading.current_thread().name

        return Open()

    def of_group(self, gid):
        return [n for n in self.seen if n["tags"].get("group") == gid]


@pytest.fixture
def notes(monkeypatch):
    recorded = Notes()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", recorded)
    return recorded


@pytest.mark.parametrize("mode", sorted(PER_GROUP))
def test_each_stage_is_observed_once_per_group(disp, mode):
    before = counts(dd.DEVICE_STAGE_SECONDS, "stage")
    groups = 3
    for _ in range(groups):
        assert set(submit(disp, tiles(), mode).result(timeout=120)) == {0, 1}
    moved = delta(before, counts(dd.DEVICE_STAGE_SECONDS, "stage"))
    assert moved == {stage: groups for stage in PER_GROUP[mode]}


def test_the_family_has_exactly_the_seven_stage_labels(disp):
    for mode in PER_GROUP:
        submit(disp, tiles(), mode).result(timeout=120)
    assert set(counts(dd.DEVICE_STAGE_SECONDS, "stage")) == STAGES
    assert "stage=stage" not in dd.DEVICE_STAGE_SECONDS.help
    for stage in STAGES:
        assert stage in dd.DEVICE_STAGE_SECONDS.help
    # the waits are a family of their own, so no sum over the stage
    # family (the benchmark's group_ms) can pick them up
    assert set(counts(dd.DEVICE_QUEUE_WAIT_SECONDS, "where")) == {
        "pool", "slot"}


@pytest.mark.parametrize("mode", sorted(PER_GROUP))
def test_one_annotation_per_stage_and_wait_with_the_groups_id(
        disp, notes, mode):
    first = next(disp._gids) + 1  # the ids are a per-dispatcher sequence
    futures = [submit(disp, tiles(b=2), mode) for _ in range(2)]
    for fut in futures:
        fut.result(timeout=120)
    for gid in (first, first + 1):
        mine = notes.of_group(gid)
        assert sorted(n["name"] for n in mine) == sorted(
            f"ompb.queue.{s}" for s in PER_GROUP[mode])
        assert all(n["closed"] == 1 for n in mine)
        assert all(n["tags"] == {"group": gid, "lanes": 2} for n in mine)
    assert len(notes.seen) == 2 * len(PER_GROUP[mode])  # no wait among them


def test_a_stage_that_spans_threads_is_opened_at_the_launch(disp, notes):
    """`hist` runs from the launch (submit thread) to the counts pull
    (plan worker), `emit` from its launch (plan worker) to the pull
    worker seeing it done: one annotation over each whole interval."""
    submit(disp, tiles(), "dynamic").result(timeout=120)
    by_name = {n["name"]: n for n in notes.seen}
    hist = by_name["ompb.queue.hist"]
    assert hist["opened_on"].startswith("devenc-submit")
    assert hist["closed_on"].startswith("devenc-plan")
    emit = by_name["ompb.queue.emit"]
    assert emit["opened_on"].startswith("devenc-plan")
    assert emit["closed_on"].startswith("devenc-pull")
    assert not any("wait" in name for name in by_name)  # histograms only
    plan = by_name["ompb.queue.plan"]
    assert plan["opened_on"] == plan["closed_on"] == hist["closed_on"]
    for stage in ("d2h", "frame"):
        note = by_name[f"ompb.queue.{stage}"]
        assert note["opened_on"] == note["closed_on"] == emit["closed_on"]


def test_a_single_pass_group_never_sees_the_plan_worker(disp, notes):
    """`rle` has no plan: submit thread to pull worker, no `plan`
    observed and no thread of the plan pool started."""
    before = counts(dd.DEVICE_STAGE_SECONDS, "stage")
    submit(disp, tiles(), "rle").result(timeout=120)
    moved = delta(before, counts(dd.DEVICE_STAGE_SECONDS, "stage"))
    assert "plan" not in moved and "emit" not in moved
    compute = {n["name"]: n for n in notes.seen}["ompb.queue.compute"]
    assert compute["opened_on"].startswith("devenc-submit")
    assert compute["closed_on"].startswith("devenc-pull")
    assert not disp._pipe.plan._threads


def test_waits_are_observed_once_per_group_also_when_staging_raises(
        disp, notes):
    stages_before = counts(dd.DEVICE_STAGE_SECONDS, "stage")
    waits_before = counts(dd.DEVICE_QUEUE_WAIT_SECONDS, "where")

    def broken(*args, **kwargs):
        raise RuntimeError("staging failed")

    disp._stage_group = broken
    fut = disp._enqueue(disp._stage_group)
    with pytest.raises(RuntimeError, match="staging failed"):
        fut.result(timeout=30)
    assert delta(waits_before, counts(
        dd.DEVICE_QUEUE_WAIT_SECONDS, "where")) == {"pool": 1, "slot": 1}
    assert delta(stages_before, counts(
        dd.DEVICE_STAGE_SECONDS, "stage")) == {}
    assert notes.seen == []  # no stage was reached, and a wait is no note
    assert disp.snapshot()["inflight"] == 0  # the slot came back


def test_a_failing_stage_ends_its_annotation_and_observes_nothing(
        disp, notes, monkeypatch):
    before = counts(dd.DEVICE_STAGE_SECONDS, "stage")

    def no_pull(*args, **kwargs):
        raise RuntimeError("pull failed")

    monkeypatch.setattr(jax, "device_get", no_pull)
    with pytest.raises(RuntimeError, match="pull failed"):
        submit(disp, tiles(), "rle").result(timeout=120)
    monkeypatch.undo()
    moved = delta(before, counts(dd.DEVICE_STAGE_SECONDS, "stage"))
    assert moved == {"h2d": 1, "compute": 1}  # d2h raised, frame never ran
    d2h = [n for n in notes.seen if n["name"] == "ompb.queue.d2h"]
    assert len(d2h) == 1 and d2h[0]["closed"] == 1


def test_the_slot_wait_is_the_time_blocked_on_the_semaphore():
    disp = dd.DeviceEncodeDispatcher({}, queue_depth=1)
    gate = threading.Event()
    real = disp._readback_group

    def held(*args, **kwargs):
        gate.wait(timeout=60)
        return real(*args, **kwargs)

    disp._readback_group = held
    try:
        before = counts(dd.DEVICE_QUEUE_WAIT_SECONDS, "where")
        futures = [submit(disp, tiles()) for _ in range(3)]
        time.sleep(0.3)  # group 2 waits for the slot, group 3 in the pool
        gate.set()
        for fut in futures:
            fut.result(timeout=120)
        after = counts(dd.DEVICE_QUEUE_WAIT_SECONDS, "where")
        assert delta(before, after) == {"pool": 3, "slot": 3}
        slot_s = after["slot"][1] - before.get("slot", (0, 0))[1]
        pool_s = after["pool"][1] - before.get("pool", (0, 0))[1]
        assert slot_s >= 0.25  # group 2 sat on acquire() behind the gate
        assert pool_s >= 0.25  # group 3 sat behind group 2 in the pool
    finally:
        gate.set()
        disp.close()


def test_the_submitting_requests_record_names_its_group(disp):
    rec = FlightRecord("/tile/1/0/0/0")
    with record_scope(rec):
        fut = submit(disp, tiles())
    fut.result(timeout=120)
    assert rec.tags["device_group"] == next(disp._gids) - 1
    assert submit(disp, tiles()).result(timeout=120)  # no record: no tag


def test_marks_keep_one_annotation_open_and_return_the_stamps(notes):
    marks = dd._Marks("h2d", 9, 4)
    t1 = marks.next("hist")
    t2 = marks.next("emit")
    marks.close()
    assert t1 <= t2 <= time.perf_counter()
    assert [n["name"] for n in notes.seen] == [
        "ompb.queue.h2d", "ompb.queue.hist", "ompb.queue.emit"]
    assert all(n["closed"] == 1 for n in notes.seen)
    assert all(n["tags"] == {"group": 9, "lanes": 4} for n in notes.seen)


def test_mesh_groups_annotate_by_hand_and_keep_the_histogram(notes):
    from omero_ms_pixel_buffer_tpu.parallel.mesh import MeshManager

    disp = dd.DeviceEncodeDispatcher(
        {}, mesh_manager=MeshManager(devices=jax.devices()))
    try:
        before = counts(dd.DEVICE_STAGE_SECONDS, "stage")
        first = next(disp._gids) + 1
        submit(disp, tiles(), "rle").result(timeout=300)
        submit(disp, tiles(), "dynamic").result(timeout=300)
        assert delta(before, counts(dd.DEVICE_STAGE_SECONDS, "stage")) == {
            "h2d": 2, "compute": 1, "hist": 1, "emit": 1, "d2h": 2,
            "frame": 2}
        assert sorted(n["name"] for n in notes.of_group(first)) == sorted(
            f"ompb.queue.{s}" for s in
            ("h2d", "compute", "d2h", "frame"))
        # a mesh group's plan runs inside its managed dispatch, under
        # `emit` as it always did: no stage of its own
        assert sorted(n["name"] for n in notes.of_group(first + 1)) == sorted(
            f"ompb.queue.{s}" for s in
            ("h2d", "hist", "emit", "d2h", "frame"))
        assert all(n["closed"] == 1 for n in notes.seen)
    finally:
        disp.close()


def test_the_annotations_land_in_the_profilers_trace(disp, tmp_path):
    """The real thing on the CPU backend: a traced dynamic group leaves
    `ompb.queue.*` events with the group's id on the host plane."""
    from jax.profiler import ProfileData

    submit(disp, tiles(), "dynamic").result(timeout=120)  # compiled
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        gid = next(disp._gids) + 1
        submit(disp, tiles(), "dynamic").result(timeout=120)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("ompb.queue."):
                    found[event.name] = (dict(event.stats),
                                         event.duration_ns)
    assert set(found) == {
        f"ompb.queue.{s}" for s in
        ("h2d", "hist", "plan", "emit", "d2h", "frame")}
    for stats, duration in found.values():
        assert stats["group"] == gid and stats["lanes"] == 2
        assert duration >= 0
