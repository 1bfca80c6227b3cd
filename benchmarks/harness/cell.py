"""One run of one cell: fixture, server, warm-up, window, check,
result line. Everything that belongs to one configuration, workload,
traffic generator, reference or per-layer metric is a file found by
its name in BENCHMARK.json or in the workload's file."""

import importlib.util
import json
import os
import shutil
import sys
import threading
import time

from . import check, counters, fixture, stats, trace_reduce
from .clients import drive
from .server import BENCH_DIR, REPO, Server, cache_entries, free_port

WARM_ROUND_S = 3.0
WARM_ROUNDS_MAX = 12
TRACE_SECONDS = 6.0


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH_DIR, kind, f"{name}.json")) as f:
        return json.load(f)


def load_plugin(kind: str, name: str):
    """benchmarks/<kind>/<name>.py as a module (names may hold dots)."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name.replace('.', '_').replace('-', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def benchmark_json() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def metrics_of(bench: dict, section: str, cell: str) -> list:
    """The metrics of `section` that this cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def _workdir(cell: str) -> str:
    path = os.path.join(fixture.cache_root(BENCH_DIR), "work", cell)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def _planes_resident(health: dict) -> int:
    planes = (health.get("cache") or {}).get("device_planes") or {}
    return int(planes.get("planes") or 0)


def warm_up(server, generator, workload, config, seed) -> list:
    """Bursts at the cell's lane counts, twice each (a plane is admitted
    to HBM on its second touch, so both the host-staged and the
    plane-cache programs compile), then rounds of the cell's own
    traffic until a round adds no compile-cache entry and the planes
    the deployment holds are resident. Returns every sample."""
    image, samples = config["image"], []
    long = fixture.REQUEST_TIMEOUT_S

    def viewers(salt):
        return generator.viewers(workload, image, seed * 1000003 + salt)

    for n in workload.get("warm_bursts", []):
        for again in (0, 1):
            t = time.perf_counter()
            _, got = drive(server.port, viewers(100 + 2 * n + again), long,
                           timeout=long, connections=n, requests=n)
            samples += got
            say(f"warmup_burst lanes={n} pass={again} "
                f"seconds={time.perf_counter() - t:.2f}")
    want_planes = int(config.get("device_planes") or 0)
    clean = 0  # rounds in a row that compiled nothing
    for round_no in range(WARM_ROUNDS_MAX):
        before = cache_entries()
        _, got = drive(server.port, viewers(900 + round_no), WARM_ROUND_S,
                       timeout=long)
        samples += got
        added = cache_entries() - before
        resident = _planes_resident(server.healthz())
        say(f"warmup_round {round_no}: requests={len(got)} "
            f"compile_entries_added={added} planes_resident={resident}")
        # a process that has compiled may meet one more rare shape: it
        # needs two clean rounds in a row, a warm one needs one
        clean = clean + 1 if added == 0 else -1
        if clean >= 1 and resident >= want_planes:
            break
    return samples


def _trace_slice(server, seconds, t0, out: dict) -> None:
    """Runs beside the window: brackets a few seconds in its middle
    with the launcher's start_trace/stop_trace."""
    span = min(TRACE_SECONDS, seconds / 2.0)
    delay = t0 + 0.3 * seconds - time.perf_counter()
    if delay > 0:
        time.sleep(delay)
    try:
        started = server.ask("trace.start", "trace.started")
        out["started"] = started["t"]
        lanes = server.healthz().get("tile_device_lanes_total")
        time.sleep(span)
        if lanes is not None:  # the device's lanes, just inside the slice
            out["device_lanes"] = (
                server.healthz()["tile_device_lanes_total"] - lanes)
        stopped = server.ask("trace.stop", "trace.stopped", limit_s=300.0)
        out["stopped"] = stopped["t"]
        out["stop_took_s"] = stopped["t_written"] - stopped["t"]
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"


def _reduced_trace(workdir: str, marks: dict, clock_offset: float):
    """The trace, reduced, with the slice on the parent's clock."""
    if "error" in marks or "stopped" not in marks:
        say(f"trace: not taken ({marks.get('error', 'no stop mark')})")
        return None
    path = trace_reduce.find_xplane(os.path.join(workdir, "trace"))
    if path is None:
        say("trace: no .xplane.pb was written")
        return None
    t = time.perf_counter()
    loaded = trace_reduce.load(path)
    say(f"trace: {path} bytes={os.path.getsize(path)} "
        f"parse_seconds={time.perf_counter() - t:.1f}")
    say("trace_lines:\n" + trace_reduce.describe(loaded))
    reduced = trace_reduce.reduce(loaded)
    if reduced is None:
        say("trace: no device plane with operations in it")
        return None
    reduced["window_s"] = marks["stopped"] - marks["started"]
    reduced["slice"] = (marks["started"] - clock_offset,
                        marks["stopped"] - clock_offset)
    reduced["stop_took_s"] = marks.get("stop_took_s")
    reduced["device_lanes"] = marks.get("device_lanes")
    return reduced


def run_cell(cell: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             server_command=None) -> int:
    """Returns the exit code; prints the result line when there is one.
    `server_command` puts another program in the server's place (the
    control and the fault tests; never a benchmark run)."""
    bench = benchmark_json()
    entry = next((w for w in bench["workloads"] if w["name"] == cell), None)
    if entry is None:
        print(f"no workload {cell!r} in BENCHMARK.json", file=sys.stderr)
        return 2
    workload = load_json("workloads", cell)
    config = load_json("configs", entry["config"])
    generator = load_plugin("traffic", workload["generator"])
    reference = load_plugin("reference", workload["reference"])
    image = config["image"]
    rehearse = os.environ.get("BENCH_REHEARSE_SIZE")
    if rehearse and os.environ.get("JAX_PLATFORMS") == "cpu":
        # a rehearsal on the CPU backend, which can print no result
        # line (no TPU): a smaller image, everything else as committed
        image["size_x"] = image["size_y"] = int(rehearse)
        say(f"REHEARSAL at {rehearse}x{rehearse}: no number of this run "
            "is a measurement")
    clock_offset = time.time() - time.perf_counter()

    t = time.perf_counter()
    data = fixture.seeded_planes(
        seed, image["size_x"], image["size_y"], image["size_z"],
        image["size_c"])
    say(f"fixture_array_seconds: {time.perf_counter() - t:.1f} "
        f"shape={data.shape}")
    workdir = _workdir(cell)
    tiff = os.path.join(workdir, "image.ome.tiff")
    written = {}

    def write_image():
        t = time.perf_counter()
        try:
            fixture.write_tiff(tiff, config, data)
            written["seconds"] = time.perf_counter() - t
        except Exception as e:
            written["error"] = f"{type(e).__name__}: {e}"

    # the server opens the image at its first request, so the file is
    # written while the server starts; warm-up waits for both
    writer = threading.Thread(target=write_image, daemon=True)
    writer.start()
    port = free_port()
    registry, config_path = fixture.write_server_files(
        workdir, config, tiff, port)
    entries_at_start = cache_entries()
    server = Server(
        workdir, ["--dev", "--registry", registry, "--config", config_path,
                  "--port", str(port)], port, command=server_command,
    )
    marks, stats_json = {}, None
    try:
        say(f"seconds_to_healthy: {server.wait_healthy():.1f}")
        writer.join()
        if "error" in written:
            raise RuntimeError(f"fixture: {written['error']}")
        say(f"fixture_tiff_seconds: {written['seconds']:.1f} "
            f"bytes={os.path.getsize(tiff)}")
        first = server.healthz()
        say(f"device: {json.dumps(first.get('device'))} engine: "
            f"{first.get('engine')} ({first.get('engine_reason')})")
        warm_samples = warm_up(server, generator, workload, config, seed)
        say(f"compile_cache_entries: at_start={entries_at_start} "
            f"after_warmup={cache_entries()}")

        before = server.counters()
        viewers = generator.viewers(workload, image, seed)
        setup_s = time.perf_counter() - t_start
        tracer = None
        if trace and server_command is not None:
            marks["error"] = "a stand-in server has no launcher to trace"
        elif trace:
            tracer = threading.Thread(
                target=_trace_slice, daemon=True,
                args=(server, seconds, time.perf_counter(), marks))
            tracer.start()
        t0, samples = drive(server.port, viewers, seconds)
        if tracer is not None:
            tracer.join()
        after = server.counters()
        health = after["healthz"]
        say("compile_entries_added_in_window: "
            f"{after['cache_entries'] - before['cache_entries']}")
        if server_command is None:
            stats_json = server.ask("stats.ask", "stats.json")
        say(f"device_queue: {json.dumps(health.get('device_queue'))}")
        say("plane_cache: "
            f"{json.dumps((health.get('cache') or {}).get('device_planes'))}")
        log_says_fallback = "host fallback" in server.log_text()
    except Exception as e:
        print(f"run failed: {type(e).__name__}: {e}\n{server.log_tail()}",
              file=sys.stderr)
        return 3
    finally:
        server.stop()  # the program's state is freed before the check
        writer.join()
        if os.path.exists(tiff):
            os.remove(tiff)  # nothing is kept from run to run

    # -- the comparison, after the window and after the server is gone
    t = time.perf_counter()
    check.judge_all(warm_samples + samples, data, reference.expected)
    say(f"check_seconds: {time.perf_counter() - t:.1f} "
        f"responses={len(warm_samples) + len(samples)}")
    device = dict(health.get("device") or {})
    engine_ok = (health.get("engine") == "device") and not log_says_fallback
    fallback = (
        (health.get("tile_device_fallback_total") or 0)
        + (health.get("render_fallback_total") or 0)
    ) if "tile_device_fallback_total" in health else None
    ctx = {
        "workload": workload, "config": config, "before": before,
        "after": after, "samples": samples, "window": (t0, seconds),
        "device": device, "trace": None,
    }
    numbers = check.checks(samples, fallback, engine_ok)
    numbers["warmup_bad"] = {
        "value": sum(1 for s in warm_samples if not s["good"]), "limit": 0}
    host_limit = (workload.get("limits") or {}).get("host_served_share")
    if host_limit is not None:
        # right pixels from the host are no answer of this deployment:
        # the share the program's singleton-batch path encodes there
        served = counters.device_served_share(ctx)
        numbers["host_served_share"] = {
            "value": 100.0 if served is None else 100.0 - served,
            "limit": host_limit}
    correct = check.verdict(numbers)
    failed = sum(1 for s in samples if not s["good"])
    result_metrics, breakdown = {}, None
    device_out = {
        "platform": device.get("platform"), "kind": device.get("kind"),
        "count": device.get("count"),
        "memory_peak_bytes": max(
            (p for p in (stats_json or {}).get("peak_bytes_in_use", [])
             if p is not None), default=None),
    }
    if trace:
        reduced = _reduced_trace(workdir, marks, clock_offset)
        ctx["trace"] = reduced
        if reduced is not None:
            device_out["busy_s"] = reduced["busy_s"]
            device_out["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
            say(f"trace_stop_took_s: {reduced['stop_took_s']}")
        stage = load_plugin("layer_metrics", "group_ms").stage_seconds(ctx)
        say(f"device_stage_seconds_in_window: {json.dumps(stage)}")
        for metric in metrics_of(bench, "per_layer", cell):
            value = load_plugin("layer_metrics", metric["name"]).read(ctx)
            if value is not None:
                result_metrics[metric["name"]] = {
                    "value": value, "unit": metric["unit"]}
    else:
        measured = stats.window_metrics(samples, t0, seconds)
        measured["setup_s"] = setup_s
        say(f"window: completed={measured['completed']} "
            f"attempted={len(samples)} completions_per_5s="
            f"{stats.completions_per_bucket(samples, t0, seconds, 5.0)}")
        say("latency_deciles_ms: "
            f"{stats.latency_deciles_ms(samples, t0, seconds)}")
        for metric in metrics_of(bench, "end_to_end", cell):
            if metric["name"] in measured:
                result_metrics[metric["name"]] = {
                    "value": measured[metric["name"]], "unit": metric["unit"]}
    shutil.rmtree(os.path.join(workdir, "trace"), ignore_errors=True)

    for name, number in numbers.items():
        print(f"check {name}: {json.dumps(number)}", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    if require_chip and (
        device_out["platform"] != "tpu"
        or (device_out["count"] or 0) < entry["chips"]
    ):
        print(f"no accelerator: the server ran on {device}; correct="
              f"{correct}; no result line", file=sys.stderr)
        return 2
    line = {
        "correct": correct, "attempted": len(samples), "failed": failed,
        "metrics": result_metrics, "device": device_out,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = numbers
    print(json.dumps(line), flush=True)
    return 0 if correct else 1
