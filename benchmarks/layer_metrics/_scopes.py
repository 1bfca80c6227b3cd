"""What the trace readers share (`kernel_ms_per_lane.*`,
`kernel_unnamed_share`, `idle_attributed_share`): the run's
`.xplane.pb`, parsed once and kept in `ctx`. No metric of its own: the
harness loads a reader by a `per_layer` entry's name, and none has
this one.

Where the names are (looked at by hand on the v5e, PR 26). The program
puts every operation of its tile programs under one of five scopes
(`omero_ms_pixel_buffer_tpu/ops/kernel_scope.py`) and every stage of
its device queue under a `jax.profiler.TraceAnnotation`
`ompb.queue.<stage>`:

- A device operation is an event of the line `XLA Ops` of the plane
  `/device:TPU:<n>`. Its `name` is the HLO text (`%fusion.47 = ...`,
  what `trace_reduce.short_op` cuts up) and holds no scope. The scope
  is in the *event metadata's* stat `tf_op`: the HLO metadata's
  `op_name` and op type, `jit(f)/jit(ompb_pack)/ompb_pack/searchsorted/
  while:`. `jax.profiler.ProfileData` shows an event's own stats
  (`device_offset_ps`, `device_duration_ps`) and not its metadata's, so
  this file reads the protobuf's wire format itself (tsl's
  `xplane.proto`: a dozen fields).
- The chip's compiler gives no metadata to what it makes itself: the
  copies and slices of its layout assignment, and the tree of
  reduce-windows it rewrites a cumulative sum into. The optimized HLO
  of every executed program is in the same file (plane
  `/host:metadata`, stat `Hlo Proto`, keyed by `program_id`), so such
  an operation takes its scope from the program's graph: a fusion from
  the operations fused into it, any other from its users (it was made
  for them), else from its operands, else from the loop it runs in.
  `kernel_scopes:` in the run's output says how much time was named at
  each step; what is left is `kernel_unnamed_share`.
- A queue stage is an event `ompb.queue.<stage>` on a thread line of
  the plane `/host:CPU`, with stats `group` and `lanes`, on the clock
  of the device plane.

Time is self time: where a `%while` covers the fusions of its body,
each instant belongs to the innermost operation, so a loop and its
body are one stretch and the scopes and the remainder add up to the
busy time `trace_reduce` reports.
"""

import json
import os
import re
import time

from benchmarks.harness import fixture, trace_reduce
from benchmarks.harness.server import BENCH_DIR

SCOPES = ("filter", "hist", "tokens", "pack", "frame")
QUEUE = "ompb.queue."
_SCOPE = re.compile(
    r"(?:^|[/(])ompb_(filter|hist|tokens|pack|frame)(?=[/):]|$)")
_SECOND = re.compile(
    r"ompb_pack/(?:\w+\()*(offsets|searchsorted|gather)\)*(?=[/:]|$)")
# their computations run as events of their own: not "fused into" them
_CONTROL = {"while", "conditional", "call", "async-start"}


def say(msg: str) -> None:
    print(msg, flush=True)


# -- protobuf wire format -------------------------------------------------

def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, bytes
    for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = buf[i:i + 8]
            i += 8
        elif wire == 5:
            value = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, value


def _ints(value):
    """A repeated int64 field: packed (bytes) or one varint."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _map_entry(buf):
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


# -- xplane ---------------------------------------------------------------

def _stat(buf, stat_names):
    """(stat name, value) of one XStat."""
    name = value = None
    for f, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, v)
        elif f in (3, 4, 7):
            value = v
        elif f in (5, 6):
            value = bytes(v)
        elif f == 2:
            value = v  # a double: nothing here reads one
    return name, value


def _plane(buf):
    """{'name', 'raw_lines': [bytes, for `_line`], 'meta': {id: {'id',
    'name', <stat name>: value...}}, 'stat_names': {id: name}}"""
    name, lines, metas, stat_names = "", [], [], {}
    for f, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode(errors="replace")
        elif f == 3:
            lines.append(v)
        elif f == 4:
            metas.append(v)
        elif f == 5:
            _, meta = _map_entry(v)
            ident = label = None
            for g, w in _fields(meta):
                if g == 1:
                    ident = w
                elif g == 2:
                    label = bytes(w).decode(errors="replace")
            stat_names[ident] = label
    meta = {}
    for entry in metas:
        _, body = _map_entry(entry)
        item = {}
        for f, v in _fields(body):
            if f == 1:
                item["id"] = v
            elif f == 2:
                item["name"] = bytes(v).decode(errors="replace")
            elif f == 5:
                key, value = _stat(v, stat_names)
                item[key] = value
        meta[item.get("id")] = item
    return {"name": name, "raw_lines": lines, "meta": meta,
            "stat_names": stat_names}


def _line(buf, stat_names, want_stats: bool):
    name, stamp_ns, events = "", 0, []
    for f, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode(errors="replace")
        elif f == 3:
            stamp_ns = v
        elif f == 4:
            meta = offset = duration = 0
            stats = {}
            for g, w in _fields(v):
                if g == 1:
                    meta = w
                elif g == 2:
                    offset = w
                elif g == 3:
                    duration = w
                elif g == 4 and want_stats:
                    key, value = _stat(w, stat_names)
                    stats[key] = value
            events.append((meta, offset, duration, stats))
    return name, stamp_ns, events


def _hlo_module(buf):
    """{'entry': id, 'computations': {id: [instruction]}} of one
    HloProto (xla's hlo.proto: the fields of the graph and no other)."""
    module = None
    for f, v in _fields(buf):
        if f == 1:
            module = v
    if module is None:
        return None
    computations, entry = {}, None
    for f, v in _fields(module):
        if f == 6:
            entry = v
        elif f == 3:
            ident, instructions = None, []
            for g, w in _fields(v):
                if g == 5:
                    ident = w
                elif g == 2:
                    one = {"operands": [], "called": [], "op_name": ""}
                    for h, x in _fields(w):
                        if h == 1:
                            one["name"] = bytes(x).decode(errors="replace")
                        elif h == 2:
                            one["opcode"] = bytes(x).decode(errors="replace")
                        elif h == 35:
                            one["id"] = x
                        elif h == 36:
                            one["operands"] += _ints(x)
                        elif h == 38:
                            one["called"] += _ints(x)
                        elif h == 7:
                            for k, y in _fields(x):
                                if k == 2:
                                    one["op_name"] = bytes(y).decode(
                                        errors="replace")
                    instructions.append(one)
            computations[ident] = instructions
    return {"entry": entry, "computations": computations}


def scope_of(op_name: str):
    found = _SCOPE.search(op_name or "")
    return found.group(1) if found else None


def second_level(op_name: str):
    found = _SECOND.search(op_name or "")
    return found.group(1) if found else None


def module_scopes(module: dict) -> dict:
    """{instruction name: (scope, how, op_name)} for every instruction
    of the computations that run as events of their own; how is `own`,
    `fused`, `graph` or `loop`."""
    computations = module["computations"]
    fused_into = set()
    for instructions in computations.values():
        for one in instructions:
            if one.get("opcode") not in _CONTROL:
                fused_into.update(one["called"])

    def majority(ident, seen):
        votes = {}
        for one in computations.get(ident, ()):
            s = scope_of(one["op_name"])
            if s:
                votes[s] = votes.get(s, 0) + 1
            for callee in one["called"]:
                if callee not in seen:
                    seen.add(callee)
                    for k, n in majority(callee, seen).items():
                        votes[k] = votes.get(k, 0) + n
        return votes

    out, parent = {}, {}  # parent: computation -> the instruction calling it
    for ident, instructions in computations.items():
        for one in instructions:
            if one.get("opcode") in _CONTROL:
                for callee in one["called"]:
                    parent[callee] = (ident, one["name"])
    for ident, instructions in computations.items():
        if ident in fused_into:
            continue
        scope, users = {}, {}
        for one in instructions:
            own = scope_of(one["op_name"])
            if own:
                scope[one["id"]] = (own, "own")
            for operand in one["operands"]:
                users.setdefault(operand, []).append(one["id"])

        def spread(neighbours):
            """Unnamed operations take the scope most of their named
            neighbours have, until nothing changes."""
            changed = True
            while changed:
                changed = False
                for one in reversed(instructions):
                    if one["id"] in scope:
                        continue
                    votes = {}
                    for other in neighbours(one):
                        if other in scope:
                            s = scope[other][0]
                            votes[s] = votes.get(s, 0) + 1
                    if votes:
                        scope[one["id"]] = (
                            max(sorted(votes), key=votes.get), "graph")
                        changed = True

        # what the compiler made takes the scope of what it was made
        # for: its users first (a layout copy, the pieces of a rewritten
        # cumulative sum: all feed the operation that asked for them)
        spread(lambda one: users.get(one["id"], ()))
        # then a fusion the compiler rooted in its own operation: by the
        # operations fused into it (its producers, so weaker evidence)
        for one in instructions:
            if (one["id"] not in scope and one["called"]
                    and one.get("opcode") not in _CONTROL):
                votes = {}
                for callee in one["called"]:
                    for k, n in majority(callee, {callee}).items():
                        votes[k] = votes.get(k, 0) + n
                if votes:
                    scope[one["id"]] = (
                        max(sorted(votes), key=votes.get), "fused")
        spread(lambda one: users.get(one["id"], ()))
        spread(lambda one: one["operands"])
        for one in instructions:
            out[one["name"]] = scope.get(one["id"], (None, None)) + (
                one["op_name"],)
    # an operation of a loop's body that nothing names runs for the loop
    for ident, instructions in computations.items():
        if ident in fused_into or ident not in parent:
            continue
        at = ident
        inherited = None
        while at in parent and inherited is None:
            at, caller = parent[at]
            inherited = out.get(caller, (None, None))[0]
        if inherited:
            for one in instructions:
                if out[one["name"]][0] is None:
                    out[one["name"]] = (inherited, "loop", one["op_name"])
    return out


def parse(path: str) -> dict:
    """{'devices': {plane: [(start_ps, end_ps, name, tf_op, scope,
    how)]}, 'queue': [(name, start_ps, end_ps, group)]}"""
    with open(path, "rb") as f:
        data = f.read()
    planes = [_plane(v) for f, v in _fields(memoryview(data)) if f == 1]
    modules = {}
    for plane in planes:
        if plane["name"] != "/host:metadata":
            continue
        for ident, item in plane["meta"].items():
            proto = item.get("Hlo Proto")
            if isinstance(proto, (bytes, memoryview)) and len(proto):
                module = _hlo_module(proto)
                if module:
                    modules[ident] = module_scopes(module)
    devices, queue = {}, []
    for plane in planes:
        if plane["name"].startswith(trace_reduce.DEVICE_PREFIX):
            resolved = {}
            for ident, item in plane["meta"].items():
                tf_op = item.get("tf_op") or b""
                if isinstance(tf_op, (bytes, memoryview)):
                    tf_op = bytes(tf_op).decode(errors="replace")
                else:
                    tf_op = ""
                name = item.get("name", "")
                scope, how = scope_of(tf_op), "own"
                if scope is None:
                    graph = modules.get(item.get("program_id"))
                    instruction = name.split(" = ")[0].lstrip("%")
                    # (a `%while` has no `tf_op` in the trace at all:
                    # its name is in the program's HLO alone)
                    scope, how, op_name = (graph or {}).get(
                        instruction, (None, None, ""))
                    tf_op = tf_op or op_name
                resolved[ident] = (name, tf_op, scope, how)
            for raw in plane["raw_lines"]:
                name, stamp_ns, events = _line(raw, {}, False)
                if name != trace_reduce.OPS_LINE:
                    continue
                rows = devices.setdefault(plane["name"], [])
                for meta, offset, duration, _ in events:
                    start = stamp_ns * 1000 + offset
                    rows.append((start, start + duration)
                                + resolved.get(meta, ("", "", None, None)))
        elif plane["name"] == trace_reduce.HOST_PLANE:
            wanted = {ident for ident, item in plane["meta"].items()
                      if item.get("name", "").startswith(QUEUE)}
            if not wanted:
                continue
            for raw in plane["raw_lines"]:
                _, stamp_ns, events = _line(raw, plane["stat_names"], True)
                for meta, offset, duration, stats in events:
                    if meta in wanted and duration > 0:
                        start = stamp_ns * 1000 + offset
                        queue.append((plane["meta"][meta]["name"], start,
                                      start + duration, stats.get("group")))
    return {"devices": devices, "queue": queue}


# -- reductions -----------------------------------------------------------

def self_times(events: list) -> dict:
    """{key: time} where each instant of the union of [(start, end,
    key)] belongs to the innermost event that covers it."""
    out, stack, cursor = {}, [], 0

    def credit(upto):
        nonlocal cursor
        if upto > cursor:
            key = stack[-1][2]
            out[key] = out.get(key, 0) + (upto - cursor)
            cursor = upto

    for start, end, key in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][1] <= start:
            credit(stack[-1][1])
            stack.pop()
        if stack:
            credit(start)
        cursor = max(cursor, start)
        stack.append((start, end, key))
    while stack:
        credit(stack[-1][1])
        stack.pop()
    return out


def kernel_seconds(parsed: dict):
    """{'busy_s', 'scopes': {scope or None: s}, 'how': {how: s},
    'second': {...}, 'ops': [[op, scope, s]]} as means over the
    devices, or None when no operation of the trace names a scope
    itself (the program has none, or a compile cache gave back an
    executable from before the names)."""
    per_device = [rows for rows in parsed["devices"].values() if rows]
    if not per_device:
        return None
    if not any(row[5] == "own" and row[4] for rows in per_device
               for row in rows):
        return None
    n = len(per_device)
    scopes, how, second, ops = {}, {}, {}, {}
    for rows in per_device:
        keyed = [(s, e, (scope, way, name, tf_op))
                 for s, e, name, tf_op, scope, way in rows]
        for (scope, way, name, tf_op), ps in self_times(keyed).items():
            seconds = ps / 1e12 / n
            scopes[scope] = scopes.get(scope, 0.0) + seconds
            how[way] = how.get(way, 0.0) + seconds
            if scope == "pack":
                step = second_level(tf_op) or "other"
                second[step] = second.get(step, 0.0) + seconds
            op = (trace_reduce.short_op(name), scope)
            ops[op] = ops.get(op, 0.0) + seconds
    return {
        "busy_s": sum(scopes.values()),
        "scopes": scopes, "how": how, "second": second,
        "ops": [[op, scope, s] for (op, scope), s in
                sorted(ops.items(), key=lambda kv: -kv[1])[:12]],
    }


def idle_cover(parsed: dict):
    """{'idle_s', 'attributed_s', 'by': {stage: s}, 'longest': [[s,
    stage, group, [stages open in it]]]} over the devices' idle gaps
    (between the first and the last operation of the trace), or None
    when the trace holds no `ompb.queue.*` stage. A wait
    (`ompb.queue.wait_*`) is no stage: the submit thread is in one
    most of the time, whatever the device does. Where stages overlap
    (two groups are in flight, and `hist` runs from the launch), the
    gap goes to the one that started last, the most specific."""
    stages = sorted(
        (s, e, name[len(QUEUE):], group)
        for name, s, e, group in parsed["queue"]
        if not name.startswith(QUEUE + "wait_"))
    per_device = [rows for rows in parsed["devices"].values() if rows]
    if not stages or not per_device:
        return None
    idle = attributed = 0
    by, gaps = {}, []
    for rows in per_device:
        merged = trace_reduce.union([(r[0], r[1]) for r in rows])
        for (_, gap_start), (gap_end, _) in zip(merged, merged[1:]):
            idle += gap_end - gap_start
            inside = [(max(s, gap_start), min(e, gap_end), name, group)
                      for s, e, name, group in stages
                      if s < gap_end and e > gap_start]
            cuts = sorted({gap_start, gap_end}
                          | {p for s, e, _, _ in inside for p in (s, e)})
            mine = {}
            for lo, hi in zip(cuts, cuts[1:]):
                over = [(s, name, group) for s, e, name, group in inside
                        if s <= lo and e >= hi]
                if over:
                    attributed += hi - lo
                    _, name, group = max(over)
                    by[name] = by.get(name, 0) + (hi - lo)
                    mine[(name, group)] = mine.get((name, group), 0) + hi - lo
            top = max(mine, key=mine.get) if mine else (None, None)
            gaps.append([(gap_end - gap_start) / 1e12, top[0], top[1],
                         sorted({f"{name}:{group}"
                                 for _, _, name, group in inside})])
    n = len(per_device)
    return {"idle_s": idle / 1e12 / n, "attributed_s": attributed / 1e12 / n,
            "by": {k: v / 1e12 / n for k, v in sorted(by.items())},
            "longest": sorted(gaps, key=lambda g: -g[0])[:5]}


def slice_completions(ctx: dict):
    """What tracing costs while it is on: good answers per second
    inside the traced slice against the rest of the same window."""
    if "slice" not in ctx["trace"] or "window" not in ctx:
        return None
    lo, hi = ctx["trace"]["slice"]
    t0, seconds = ctx["window"]
    good = [s["t_done"] for s in ctx["samples"]
            if s.get("good") and t0 <= s["t_done"] <= t0 + seconds]
    inside = sum(1 for t in good if lo <= t <= hi)
    rest = seconds - (hi - lo)
    return {"inside": inside, "slice_s": hi - lo,
            "inside_per_s": inside / (hi - lo),
            "outside_per_s": (len(good) - inside) / rest if rest > 0 else None}


# -- the run's trace, once ------------------------------------------------

def of(ctx: dict):
    """{'kernels': kernel_seconds(...), 'idle': idle_cover(...)} of this
    run's trace, parsed at the first reader's call and kept in `ctx`;
    None (and one line saying why) when there is nothing to read."""
    if "_scopes" in ctx:
        return ctx["_scopes"]
    ctx["_scopes"] = None
    if not ctx.get("trace"):
        say("scopes: no trace was taken in this run")
        return None
    try:
        path = trace_reduce.find_xplane(os.path.join(
            fixture.cache_root(BENCH_DIR), "work",
            ctx["workload"]["name"], "trace"))
        if path is None:
            say("scopes: no .xplane.pb under the run's work directory")
            return None
        t = time.perf_counter()
        parsed = parse(path)
        kernels, idle = kernel_seconds(parsed), idle_cover(parsed)
        say(f"scopes: parse_seconds={time.perf_counter() - t:.1f} "
            f"queue_events={len(parsed['queue'])}")
        say("slice_completions: " + json.dumps(slice_completions(ctx)))
    except Exception as e:  # a reader reports nothing; it never raises
        say(f"scopes: the trace could not be read ({type(e).__name__}: {e})")
        return None
    if kernels is None:
        say("scopes: no device operation of the trace names an ompb_* "
            "scope: the program has none, or its compile cache gave back "
            "an executable compiled without them")
    else:
        say("kernel_scopes: " + json.dumps({
            "busy_s": kernels["busy_s"],
            "seconds": {str(k): v for k, v in kernels["scopes"].items()},
            "named_by": {str(k): v for k, v in kernels["how"].items()},
            "pack": kernels["second"], "ops": kernels["ops"]}))
    if idle is None:
        say("scopes: no ompb.queue.* stage on the trace's host plane")
    else:
        say("idle_by_stage: " + json.dumps(idle))
    ctx["_scopes"] = {"kernels": kernels, "idle": idle}
    return ctx["_scopes"]


def kernel_ms_per_lane(ctx: dict, scope: str):
    found = of(ctx)
    lanes = (ctx.get("trace") or {}).get("device_lanes")
    if not found or not found["kernels"] or not lanes:
        return None
    return 1e3 * found["kernels"]["scopes"].get(scope, 0.0) / lanes
