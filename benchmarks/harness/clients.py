"""Closed-loop clients: one thread and one keep-alive connection each.

A viewer's connections share the viewer's request stream; each sends
its next request when its last one has completed. Bodies are kept and
checked after the window, so decoding never shares the clients' time.
"""

import http.client
import threading
import time

from .server import COOKIE


class _Viewer:
    def __init__(self, stream):
        self._stream = stream
        self._lock = threading.Lock()

    def next(self):
        with self._lock:
            return next(self._stream)


def _connection_loop(port, viewer, stop_at, samples, lock, timeout,
                     budget):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        while time.perf_counter() < stop_at:
            if budget is not None:
                with lock:
                    if budget[0] <= 0:
                        return
                    budget[0] -= 1
            request = viewer.next()
            sample = {"request": request, "t_send": time.perf_counter(),
                      "status": None, "degraded": False, "body": b"",
                      "error": None}
            try:
                conn.request("GET", request["url"], headers=COOKIE)
                resp = conn.getresponse()
                sample["body"] = resp.read()
                sample["status"] = resp.status
                sample["degraded"] = any(
                    k.lower() == "x-ompb-degraded"
                    for k, _ in resp.getheaders()
                )
            except (OSError, http.client.HTTPException) as e:
                sample["error"] = f"{type(e).__name__}: {e}"
                conn.close()  # a fresh connection for the next request
            sample["t_done"] = time.perf_counter()
            with lock:
                samples.append(sample)
    finally:
        conn.close()


def drive(port: int, viewers: list, seconds: float, timeout: float = 90.0,
          connections=None, requests=None) -> tuple:
    """Run the viewers' connections for `seconds` (each finishes the
    request it has in flight: an answer that comes late is late, not
    lost) -> (t0, samples). `connections` caps how many connections are
    opened in all and `requests` how many requests are sent in all
    (warm-up bursts)."""
    samples, lock = [], threading.Lock()
    budget = None if requests is None else [requests]
    threads = []
    t0 = time.perf_counter()
    stop_at = t0 + seconds
    for stream, n in viewers:
        viewer = _Viewer(stream)
        for _ in range(n):
            if connections is not None and len(threads) >= connections:
                break
            threads.append(threading.Thread(
                target=_connection_loop, daemon=True,
                args=(port, viewer, stop_at, samples, lock, timeout, budget),
            ))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return t0, samples
