"""Process collector (the JMX/hotspot exports analog), and its
``thread_cpu_seconds_total{role}``: each live thread's CPU clock read
at scrape time, by the name prefix of its pool."""

import threading
import time

import pytest

from omero_ms_pixel_buffer_tpu.utils import process_metrics
from omero_ms_pixel_buffer_tpu.utils.metrics import Registry
from omero_ms_pixel_buffer_tpu.utils.process_metrics import (
    ProcessCollector,
    install,
)


def test_collect_exposes_process_metrics():
    text = "\n".join(ProcessCollector("1.2.3").collect())
    for metric in (
        "process_cpu_seconds_total",
        "process_resident_memory_bytes",
        "process_open_fds",
        "process_max_fds",
        "process_threads",
        "python_gc_collections_total",
        "thread_cpu_seconds_total",
    ):
        assert metric in text, metric
    assert 'build_info{version="1.2.3"} 1' in text
    # numbers are sane
    rss = float(
        [l for l in text.splitlines()
         if l.startswith("process_resident_memory_bytes")][0].split()[-1]
    )
    assert rss > 1e6  # a real python process uses > 1 MB


def test_install_idempotent_and_scraped_via_registry():
    registry = Registry()
    a = install(registry)
    b = install(registry)
    assert a is b
    text = registry.exposition()
    assert "process_cpu_seconds_total" in text


# -- each thread's CPU, by role --------------------------------------------

def _roles(collector) -> dict:
    head = 'thread_cpu_seconds_total{role="'
    return {
        line[len(head):].split('"')[0]: float(line.rpartition(" ")[2])
        for line in collector.collect() if line.startswith(head)
    }


def _spin(seconds):
    t = time.thread_time()
    while time.thread_time() - t < seconds:
        pass


@pytest.mark.parametrize("name, role", [
    ("pixel-buffer-pool_0", "batch"), ("encode_1", "encode"),
    ("devenc-submit-chips_2", "submit"), ("devenc-plan_0", "plan"),
    ("devenc-pull-chips_3", "pull"), ("plane-stage_0", "stager"),
    ("io-fetch_0", "other"),
])
def test_a_busy_named_threads_role_grows(name, role):
    collector = ProcessCollector("1")
    before = _roles(collector)
    assert set(before) == set(process_metrics.ROLES)  # every role
    thread = threading.Thread(target=_spin, args=(0.15,), name=name)
    thread.start()
    thread.join()
    after = _roles(collector)
    # the thread has exited: what the first scrape could not see of it
    # is the process's, `native`; a second busy thread is read live
    live = threading.Event()
    done = threading.Event()

    def busy():
        _spin(0.15)
        live.set()
        done.wait(timeout=30)

    second = threading.Thread(target=busy, name=name)
    second.start()
    assert live.wait(timeout=30)
    try:
        during = _roles(collector)
    finally:
        done.set()
        second.join()
    assert during[role] - after[role] >= 0.1
    assert all(during[r] >= after[r] - 1e-9 for r in during
               if r != "native")


def test_the_loop_is_the_thread_marked_as_the_loops():
    collector = ProcessCollector("1")
    ran = {}

    def loop_thread():
        process_metrics.mark_loop_thread()
        _spin(0.15)
        ran["roles"] = _roles(collector)

    thread = threading.Thread(target=loop_thread, name="not-a-pool-name")
    thread.start()
    thread.join()
    assert ran["roles"]["loop"] >= 0.1


def test_a_thread_that_has_exited_keeps_its_role_and_is_skipped(
        monkeypatch):
    collector = ProcessCollector("1")
    go, read = threading.Event(), threading.Event()

    def busy():
        _spin(0.15)
        go.set()
        read.wait(timeout=30)

    thread = threading.Thread(target=busy, name="encode_9")
    thread.start()
    assert go.wait(timeout=30)
    seen = _roles(collector)["encode"]
    read.set()
    thread.join()
    # gone between two scrapes: its role keeps what was read of it
    assert _roles(collector)["encode"] >= seen >= 0.1
    # gone between the enumeration and the read of its clock: skipped
    real = time.pthread_getcpuclockid

    def exited(ident):
        if ident == threading.get_ident():
            raise OSError(3, "No such process")
        return real(ident)

    monkeypatch.setattr(time, "pthread_getcpuclockid", exited)
    roles = _roles(collector)
    assert set(roles) == set(process_metrics.ROLES)


def test_native_is_the_process_less_its_python_threads():
    collector = ProcessCollector("1")
    roles = _roles(collector)
    assert roles["native"] >= 0.0
    assert sum(roles.values()) <= time.process_time()
