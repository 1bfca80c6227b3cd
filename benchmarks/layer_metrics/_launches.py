"""What `idle_in_launch_share` reads: every idle gap of every chip of
the run's `.xplane.pb`, put down to the launch of the program that
ends it. No metric of its own (the harness loads a reader by a
`per_layer` entry's name, and none has this one).

Where the names are (looked at by hand on the v5e, `scoped.xplane.pb`):

- A chip is the plane `/device:TPU:<n>`. Its line `XLA Ops` holds the
  operations (busy time is their union, as in `trace_reduce`), its
  line `XLA Modules` one event a program run, named
  `jit_<function>(<fingerprint>)`. `PROGRAMS` maps the function of each
  program the device queue launches to the `program` stat of its
  launch.
- A launch is the annotation `ompb.queue.launch` (stats `group`,
  `lanes`, `chip`, `program`; `models/device_dispatch.py`), opened and
  closed on the launching thread, so it lies on that thread's line of
  `/host:CPU`. A string stat is stored as a reference to a stat name.
  The device's `XLA Modules` events carry a `run_id`, but no event of
  the launching thread does, so the match is by chip and program.
- The chip's clock is not the host's: in `scoped.xplane.pb` a program
  starts on the device 1.44-1.49 ms "before" the host's runtime
  enqueued it (`DoEnqueueProgram`, on a runtime thread of the host,
  joined to its `XLA Modules` event by a flow id: `_p` there, `_c`
  here, and the same `run_id`). `clock_offset` puts each chip's times
  on the host's clock before anything is matched.
- A queue stage `ompb.queue.<stage>` that one thread opens and another
  closes lies on the line of the thread that closed it: on the plan
  worker's line, `hist` (its counts pulled) and `plan` come before the
  emit's launch; on the submit thread's, `h2d` before pass 1's.

The split of one gap that ends where a program starts: the launch is
the latest of that chip and program that began before the gap's end
and no earlier program took (on a trace with one chip and launches
that name none, the chip is that one). From the launch's start to the
gap's end the chip waited **in launch**; before it, the launching
thread was not yet launching: **host late**, charged to the queue
stages that thread was in over that time (on its own line; where two
cover an instant, the one opened last), and to `none` where it was in
none (waiting for work: a wait is no annotation). A gap's `stage` in
`longest` is the stage the thread opened last before the launch.
Within "in launch", `after_return` is the part
after the launch call had already returned. **Unmatched** is a gap
inside one program's run (`in_program`), a gap that ends in a program
no launch span names (by the program's function: a plane crop, an
eager slice, a mesh group's programs), and a known program whose
launch is not in the trace (`no_launch:<program>`). `edges` is the
slice's time before the first and after the last operation of the
chip: the parts and the edges add up to the chip's idle time. It is
negative where the chip's operations in the trace span more than the
slice: the harness stamps the slice after `start_trace` returns and
before `stop_trace` is called, and the profiler records around both
(0.11-0.25 s on the v5e), so `parts_share` (the parts ÷ the slice)
reads above `idle_share` (1 - busy ÷ the slice, as
`device_idle_share`) by as much; `span_idle_share` is the parts ÷
the chip's own span.
"""

import bisect
import heapq
import json
import os
import time

from benchmarks.harness import fixture, trace_reduce
from benchmarks.harness.server import BENCH_DIR
from benchmarks.layer_metrics import _scopes

LAUNCH = "ompb.queue.launch"
QUEUE = "ompb.queue."
MODULES = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
PROGRAMS = {
    "_filter_histogram_core": "hist",
    "dynamic_emit_local": "emit",
    "_fused_filter_deflate": "compute",
    "_fused_filter_deflate_donated": "compute",
    "_fused_render_filter_deflate": "render",
}


def say(msg: str) -> None:
    print(msg, flush=True)


def function_of(module: str) -> str:
    """'dynamic_emit_local' from 'jit_dynamic_emit_local(1138...)'."""
    name = module.split("(")[0]
    return name[len("jit_"):] if name.startswith("jit_") else name


def _stats(stats: dict, names: dict) -> dict:
    out = dict(stats)
    program = out.get("program")
    if isinstance(program, int):  # a reference to a stat name
        out["program"] = names.get(program, program)
    elif isinstance(program, (bytes, memoryview)):
        out["program"] = bytes(program).decode(errors="replace")
    return out


def parse(path: str) -> dict:
    """{'chips': {n: {'ops': [(start, end)], 'modules': [(start,
    name)], 'flows': [(flow, start)]}}, 'launches': [{start, end, line,
    group, lanes, chip, program}], 'stages': {line: [(start, end,
    stage)]}, 'enqueues': {flow: start}}, times in ps, each plane on
    its own clock."""
    with open(path, "rb") as f:
        data = f.read()
    chips, launches, stages, enqueues = {}, [], {}, {}
    for field, value in _scopes._fields(memoryview(data)):
        if field != 1:
            continue
        plane = _scopes._plane(value)
        if plane["name"].startswith(trace_reduce.DEVICE_PREFIX):
            try:
                n = int(plane["name"][len(trace_reduce.DEVICE_PREFIX):])
            except ValueError:
                continue
            chip = chips.setdefault(
                n, {"ops": [], "modules": [], "flows": []})
            for raw in plane["raw_lines"]:
                name, stamp_ns, events = _scopes._line(raw, {}, False)
                if name == trace_reduce.OPS_LINE:
                    for _, offset, duration, _ in events:
                        start = stamp_ns * 1000 + offset
                        chip["ops"].append((start, start + duration))
                elif name == MODULES:
                    _, _, events = _scopes._line(
                        raw, plane["stat_names"], True)
                    for meta, offset, _, stats in events:
                        start = stamp_ns * 1000 + offset
                        chip["modules"].append((start, plane["meta"].get(
                            meta, {}).get("name", "")))
                        if isinstance(stats.get("_c"), int):
                            chip["flows"].append((stats["_c"], start))
        elif plane["name"] == trace_reduce.HOST_PLANE:
            wanted = {ident: item["name"]
                      for ident, item in plane["meta"].items()
                      if item.get("name", "").startswith(QUEUE)
                      and not item["name"].startswith(QUEUE + "wait_")
                      or item.get("name") == ENQUEUE}
            for line, raw in enumerate(plane["raw_lines"]):
                _, stamp_ns, events = _scopes._line(
                    raw, plane["stat_names"], True)
                for meta, offset, duration, stats in events:
                    if meta not in wanted:
                        continue
                    start = stamp_ns * 1000 + offset
                    if wanted[meta] == ENQUEUE:
                        if isinstance(stats.get("_p"), int):
                            enqueues[stats["_p"]] = start
                        continue
                    if wanted[meta] != LAUNCH:
                        stages.setdefault(line, []).append(
                            (start, start + duration,
                             wanted[meta][len(QUEUE):]))
                        continue
                    stats = _stats(stats, plane["stat_names"])
                    launches.append({
                        "start": start, "end": start + duration,
                        "line": line, "group": stats.get("group"),
                        "lanes": stats.get("lanes"),
                        "chip": stats.get("chip"),
                        "program": stats.get("program")})
    for chip in chips.values():
        chip["modules"].sort()
    for events in stages.values():
        events.sort()
    launches.sort(key=lambda l: l["start"])
    return {"chips": chips, "launches": launches, "stages": stages,
            "enqueues": enqueues}


def clock_offset(chip: dict, enqueues: dict):
    """(ps to add to the chip's times to put them on the host's clock,
    programs that told it). A program cannot start before the host's
    runtime enqueued it, and starts at once on an idle chip: the
    offset is the upper edge of (enqueue - program start) over the
    programs joined to their enqueue by flow (the 99th percentile, so
    one stray pair does not set it). 0 and 0 without such a pair."""
    ahead = sorted(enqueues[flow] - start for flow, start in chip["flows"]
                   if flow in enqueues)
    if not ahead:
        return 0, 0
    return ahead[min(int(0.99 * len(ahead)), len(ahead) - 1)], len(ahead)


def _stage_before(stages: dict, launch: dict) -> str:
    """The queue stage the launching thread opened last before the
    launch, or `none`."""
    events = stages.get(launch["line"], [])
    i = bisect.bisect_left(events, (launch["start"],)) - 1
    return events[i][2] if i >= 0 else "none"


def _late_by_stage(stages: dict, launch: dict, start: float, end: float,
                   into: dict) -> None:
    """Adds [start, end) to `into` by the queue stages of the launching
    thread's line that cover it (where two do, the one opened last),
    the rest to `none`: the thread was in no stage, waiting for work."""
    events = stages.get(launch["line"], [])
    upto = bisect.bisect_left(events, (end,))
    # a thread is in a stage or two at a time: the last few opened
    # before `end` are all that can cover [start, end)
    inside = [(max(s, start), min(e, end), s, stage)
              for s, e, stage in events[max(upto - 16, 0):upto]
              if e > start]
    cuts = sorted({start, end} | {p for lo, hi, _, _ in inside
                                  for p in (lo, hi)})
    for lo, hi in zip(cuts, cuts[1:]):
        over = [(s, stage) for a, b, s, stage in inside if a <= lo and b >= hi]
        stage = max(over)[1] if over else "none"
        into[stage] = into.get(stage, 0) + hi - lo


def _match(modules: list, ends: dict, launches: list) -> dict:
    """{module index: launch} for each run of a known program: the
    latest launch of its program, not yet taken, that began before the
    program's time (the gap's end where it ends one, else its start),
    the runs taken in the order they started."""
    free, out = {}, {}
    for launch in launches:  # in the order they began
        starts, pool = free.setdefault(launch["program"], ([], []))
        starts.append(launch["start"])
        pool.append(launch)
    for i, (start, name) in enumerate(modules):
        starts, pool = free.get(PROGRAMS.get(function_of(name)), ([], []))
        j = bisect.bisect_left(starts, ends.get(i, start)) - 1
        if j >= 0:
            del starts[j]
            out[i] = pool.pop(j)
    return out


def _chip(n: int, chip: dict, launches: list, stages: dict,
          window_ps: float, offset: float = 0, joined: int = 0) -> dict:
    merged = trace_reduce.union(
        [(s + offset, e + offset) for s, e in chip["ops"]])
    modules = [(start + offset, name) for start, name in chip["modules"]]
    starts = [m[0] for m in modules]
    gaps, ends = [], {}
    for (_, gap_start), (gap_end, _) in zip(merged, merged[1:]):
        # the program that starts at the gap's end: the last to start
        # by then, if it started after the gap began
        i = bisect.bisect_right(starts, gap_end) - 1
        if i >= 0 and starts[i] > gap_start:
            ends[i] = gap_end
        else:
            i = None
        gaps.append((gap_start, gap_end, i))
    matched = _match(modules, ends, launches)
    in_launch = after_return = 0
    late, unmatched, longest = {}, {}, []
    for gap_start, gap_end, i in gaps:
        size = gap_end - gap_start
        launch = matched.get(i)
        if launch is None:
            if i is None:
                why = "in_program"
            else:
                function = function_of(modules[i][1])
                program = PROGRAMS.get(function)
                why = f"no_launch:{program}" if program else function
            unmatched[why] = unmatched.get(why, 0) + size
            longest.append([size / 1e12, n, None, why, None, 0.0])
            continue
        begun = max(launch["start"], gap_start)
        inside = gap_end - begun
        in_launch += inside
        after_return += max(gap_end - max(launch["end"], begun), 0)
        stage = _stage_before(stages, launch)
        if begun > gap_start:
            _late_by_stage(stages, launch, gap_start, begun, late)
        longest.append([size / 1e12, n, launch["group"], launch["program"],
                        stage, inside / 1e12])
    busy = sum(e - s for s, e in merged)
    span = merged[-1][1] - merged[0][0] if merged else 0
    parts = span - busy  # the gaps: in launch, host late, unmatched
    return {
        "chip": n,
        # as device_idle_share reads a chip: 1 - busy ÷ the slice
        "idle_share": 100.0 * (window_ps - busy) / window_ps,
        "idle_s": (window_ps - busy) / 1e12,
        # the parts ÷ the slice, and ÷ the chip's own span in the trace
        "parts_share": 100.0 * parts / window_ps,
        "span_idle_share": 100.0 * parts / span if span else None,
        "span_s": span / 1e12,
        "in_launch_s": in_launch / 1e12,
        "after_return_s": after_return / 1e12,
        "host_late_s": {k: v / 1e12 for k, v in sorted(late.items())},
        "unmatched_s": {k: v / 1e12 for k, v in sorted(unmatched.items())},
        "edges_s": (window_ps - span) / 1e12,
        "gaps": len(gaps),
        "launches_matched": len(matched),
        # the chip's clock put on the host's, and by how many programs
        "clock_offset_ms": offset / 1e9,
        "programs_joined": joined,
        # [seconds, chip, group, program, launching thread's stage,
        #  seconds in launch]
        "longest": heapq.nlargest(5, longest, key=lambda g: g[0]),
    }


def split(parsed: dict, window_s: float):
    """[per chip, as `_chip`] or None when the trace holds no launch
    span or no chip."""
    launches, chips = parsed["launches"], parsed["chips"]
    if not launches or not chips:
        return None
    only = next(iter(chips)) if len(chips) == 1 else None
    out = []
    for n, chip in sorted(chips.items()):
        mine = [l for l in launches
                if l["chip"] == n or (l["chip"] is None and only == n)]
        out.append(_chip(n, chip, mine, parsed["stages"], window_s * 1e12,
                         *clock_offset(chip, parsed.get("enqueues", {}))))
    return out


def of(ctx: dict):
    """`split` of this run's trace, parsed at the first call and kept in
    `ctx`; None (and one line saying why) when there is nothing to
    read."""
    if "_launches" in ctx:
        return ctx["_launches"]
    ctx["_launches"] = None
    trace = ctx.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    try:
        path = trace_reduce.find_xplane(os.path.join(
            fixture.cache_root(BENCH_DIR), "work",
            ctx["workload"]["name"], "trace"))
        if path is None:
            return None
        t = time.perf_counter()
        parsed = parse(path)
        chips = split(parsed, trace["window_s"])
    except Exception as e:  # a reader reports nothing; it never raises
        say(f"launches: the trace could not be read ({type(e).__name__}: "
            f"{e})")
        return None
    if chips is None:
        say("launches: no ompb.queue.launch span on the trace's host plane")
        return None
    say(f"launches: parse_seconds={time.perf_counter() - t:.1f} "
        f"launch_spans={len(parsed['launches'])} launch_chips="
        f"{sorted({str(l['chip']) for l in parsed['launches']})} "
        f"planes={sorted(parsed['chips'])}")
    say("idle_by_launch: " + json.dumps(chips))
    ctx["_launches"] = chips
    return chips
