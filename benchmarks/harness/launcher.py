"""The benchmark's launcher for the system under test.

Runs `omero_ms_pixel_buffer_tpu.http.server.main(argv)`, the function
`python -m omero_ms_pixel_buffer_tpu` calls, in the main thread of this
process, and beside it one daemon thread that answers three requests
the parent makes by dropping files into the work directory:

  trace.start  -> jax.profiler.start_trace(<workdir>/trace); writes
                  trace.started {"t": time.time()}
  trace.stop   -> jax.profiler.stop_trace(); writes trace.stopped
  stats.ask    -> writes stats.json: memory_stats() of every local
                  device (peak bytes), platform, kind, count

Only the process that holds the chip can trace it or read its memory,
and the program has no route for either; nothing of the program is
changed. The thread sleeps 20 ms between looks and imports jax only
once it is asked for something, after the server has initialised it.

    python benchmarks/harness/launcher.py <workdir> -- <server argv>
"""

import json
import os
import sys
import threading
import time


def _write(path: str, payload: dict) -> None:
    tmp = path + ".part"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)


def _device_stats() -> dict:
    import jax

    devices = jax.local_devices()
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use"))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "peak_bytes_in_use": peaks,
    }


def _serve_files(workdir: str) -> None:
    def taken(name: str) -> bool:
        path = os.path.join(workdir, name)
        if not os.path.exists(path):
            return False
        os.remove(path)
        return True

    while True:
        time.sleep(0.02)
        try:
            if taken("trace.start"):
                import jax

                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0  # the python tracer
                # would make the trace many times larger and slow the
                # server's threads; host events stay at their default
                jax.profiler.start_trace(
                    os.path.join(workdir, "trace"),
                    profiler_options=options,
                )
                _write(os.path.join(workdir, "trace.started"),
                       {"t": time.time()})
            if taken("trace.stop"):
                import jax

                t = time.time()
                jax.profiler.stop_trace()
                _write(os.path.join(workdir, "trace.stopped"),
                       {"t": t, "t_written": time.time()})
            if taken("stats.ask"):
                _write(os.path.join(workdir, "stats.json"), _device_stats())
        except Exception as e:  # the parent reads the reason
            _write(os.path.join(workdir, "launcher.error"),
                   {"error": f"{type(e).__name__}: {e}"})


def main() -> None:
    workdir = sys.argv[1]
    argv = sys.argv[3:]  # after "--"
    threading.Thread(
        target=_serve_files, args=(workdir,), daemon=True,
        name="bench-launcher",
    ).start()
    from omero_ms_pixel_buffer_tpu.http.server import main as serve

    serve(argv)


if __name__ == "__main__":
    main()
