"""The core ompb-lint checkers (plus the r21 fleet rules registered
from ``checkers_fleet``).

Each checker is a function ``(project, indexes) -> [Finding]``; the
driver (``tools.analyze.run``) applies suppressions and the baseline
afterwards, so checkers just report what they see.

Rule ids:

- ``loop-block``           blocking call reachable from an async def
                           (strict INTERPROCEDURAL edges since r21 —
                           a sync helper imported from another module
                           propagates its may-block fact)
- ``lock-discipline``      lock-guarded attribute accessed without it
- ``resilience-coverage``  naked remote-I/O (no breaker/fault-point/
                           per-call timeout)
- ``jax-hotpath``          host sync / per-call jit in device modules
                           (device values now propagate through call
                           parameters and returns — the
                           ``_finish_png_lanes`` escape)
- ``error-taxonomy``       bare except, swallowed CancelledError,
                           unmapped exception on the request path
- ``task-hygiene`` / ``bounded-growth`` / ``trust-surface`` /
  ``config-drift``         see ``checkers_fleet.py``
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Set, Tuple

from .callgraph import (
    CallSite,
    FunctionInfo,
    ModuleIndex,
    ProjectGraph,
    _base_of,
    project_graph,
)
from .core import Finding, Project, SourceFile

# ---------------------------------------------------------------------------
# loop-block
# ---------------------------------------------------------------------------

# Primitives that park the calling thread. STRONG ones propagate
# through the (strict) call graph; DIRECT_ONLY ones are flagged only
# when they appear lexically inside an async def — `open()` and
# `.result()` are everywhere in legitimate sync code, and flagging a
# sync helper for them would drown the signal.
_STRONG_BLOCKING: List[Tuple[Optional[str], str, str]] = [
    ("time", "sleep", "time.sleep"),
    ("subprocess", "run", "subprocess.run"),
    ("subprocess", "call", "subprocess.call"),
    ("subprocess", "check_call", "subprocess.check_call"),
    ("subprocess", "check_output", "subprocess.check_output"),
    ("subprocess", "Popen", "subprocess.Popen"),
    (None, "urlopen", "urllib.request.urlopen"),
    ("socket", "create_connection", "socket.create_connection"),
    (None, "block_until_ready", "jax block_until_ready (host sync)"),
    (None, "encode_png", "host PNG encode"),
    (None, "encode_tiff", "host TIFF encode"),
    (None, "encode_jpeg", "host JPEG encode"),
    (None, "assemble_png", "host PNG assembly"),
    (None, "png_encode_batch", "native batch PNG encode"),
    (None, "png_assemble_batch", "native batch PNG assembly"),
]
_DIRECT_ONLY: List[Tuple[Optional[str], str, str]] = [
    (None, "open", "sync file open"),
    (None, "result", "Future.result() (blocks until the future resolves)"),
]


def _match_blocking(
    call: CallSite, table: List[Tuple[Optional[str], str, str]]
) -> Optional[str]:
    for base, name, desc in table:
        if call.name != name:
            continue
        if base is None or call.base == base:
            return desc
    return None


def may_block_lattice(graph: ProjectGraph) -> Dict[str, str]:
    """"May block the event loop" fact per function qualname: a
    human-readable reason chain, propagated over STRICT interprocedural
    edges (cross-module included) through SYNC callees — an async
    callee suspends instead of blocking its caller. Executor-tagged
    calls are exempt by construction."""
    direct_strong: Dict[str, str] = {}
    for fn in graph.functions():
        for call in fn.calls:
            if call.in_executor:
                continue
            desc = _match_blocking(call, _STRONG_BLOCKING)
            if desc is not None:
                direct_strong.setdefault(fn.qualname, desc)

    reaches: Dict[str, Optional[str]] = {}

    def blocking_reason(fn: FunctionInfo, stack: Set[str]) -> Optional[str]:
        if fn.qualname in reaches:
            return reaches[fn.qualname]
        if fn.qualname in stack:
            return None
        stack.add(fn.qualname)
        reason = direct_strong.get(fn.qualname)
        if reason is None:
            for call in fn.calls:
                if call.in_executor:
                    continue
                callee = graph.resolve(fn, call)
                if callee is None or callee.is_async:
                    continue
                sub = blocking_reason(callee, stack)
                if sub is not None:
                    reason = f"{callee.name}() -> {sub}"
                    break
        stack.discard(fn.qualname)
        reaches[fn.qualname] = reason
        return reason

    for fn in graph.functions():
        blocking_reason(fn, set())
    return {q: r for q, r in reaches.items() if r is not None}


def check_loop_block(
    project: Project, indexes: Dict[str, ModuleIndex]
) -> List[Finding]:
    findings: List[Finding] = []
    graph = project_graph(project, indexes)
    reaches = may_block_lattice(graph)

    # flag async functions: direct blocking primitives, then strict
    # (interprocedural — a sync helper imported from another module
    # counts) reachability into the may-block set
    for fn in graph.functions():
        if not fn.is_async:
            continue
        for call in fn.calls:
            if call.in_executor:
                continue
            desc = _match_blocking(
                call, _STRONG_BLOCKING
            ) or _match_blocking(call, _DIRECT_ONLY)
            if desc is not None:
                findings.append(Finding(
                    "loop-block", fn.module, call.line,
                    f"blocking call in async '{fn.name}': {desc} "
                    "— hop through run_in_executor (or use the "
                    "async variant)",
                ))
                continue
            callee = graph.resolve(fn, call)
            if callee is None or callee.is_async:
                continue
            reason = reaches.get(callee.qualname)
            if reason is not None:
                via = (
                    "" if callee.module == fn.module
                    else f" (via {callee.module})"
                )
                findings.append(Finding(
                    "loop-block", fn.module, call.line,
                    f"async '{fn.name}' reaches blocking code: "
                    f"{callee.name}() -> {reason}{via}",
                ))
    return findings


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------

_LOCK_CTORS = {"Lock", "RLock", "Condition"}
_MUTATORS = {
    "append", "appendleft", "extend", "insert", "add", "discard",
    "remove", "clear", "pop", "popitem", "update", "setdefault",
    "move_to_end",
}


class _ClassLockInfo:
    def __init__(self, name: str):
        self.name = name
        self.lock_attrs: Set[str] = set()
        # attr -> list of (method, line, under_lock, is_write)
        self.accesses: Dict[str, List[Tuple[str, int, bool, bool]]] = {}
        # method -> list of (callee_method, under_lock)
        self.method_calls: Dict[str, List[Tuple[str, bool]]] = {}
        self.method_names: Set[str] = set()


def _scan_class_locks(node: ast.ClassDef) -> Optional[_ClassLockInfo]:
    info = _ClassLockInfo(node.name)
    methods = [
        m for m in node.body
        if isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef))
    ]
    info.method_names = {m.name for m in methods}
    # find lock attributes: self.X = threading.Lock() / asyncio.Lock()
    for m in methods:
        for sub in ast.walk(m):
            if (
                isinstance(sub, ast.Assign)
                and isinstance(sub.value, ast.Call)
            ):
                _, ctor = _base_of(sub.value.func)
                if ctor in _LOCK_CTORS:
                    for t in sub.targets:
                        if (
                            isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"
                        ):
                            info.lock_attrs.add(t.attr)
    if not info.lock_attrs:
        return None

    def is_lock_expr(expr: ast.expr) -> bool:
        return (
            isinstance(expr, ast.Attribute)
            and isinstance(expr.value, ast.Name)
            and expr.value.id == "self"
            and expr.attr in info.lock_attrs
        )

    def visit(n: ast.AST, method: str, under: bool) -> None:
        if isinstance(n, (ast.With, ast.AsyncWith)):
            locked = under or any(
                is_lock_expr(item.context_expr) for item in n.items
            )
            for item in n.items:
                visit(item.context_expr, method, under)
            for stmt in n.body:
                visit(stmt, method, locked)
            return
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            body = n.body if isinstance(n.body, list) else [n.body]
            for stmt in body:
                visit(stmt, method, under)
            return
        if isinstance(n, ast.Call):
            base, name = _base_of(n.func)
            if base == "self" and name in info.method_names:
                info.method_calls.setdefault(method, []).append(
                    (name, under)
                )
        if (
            isinstance(n, ast.Attribute)
            and isinstance(n.value, ast.Name)
            and n.value.id == "self"
            and n.attr not in info.lock_attrs
        ):
            is_write = isinstance(n.ctx, (ast.Store, ast.Del))
            info.accesses.setdefault(n.attr, []).append(
                (method, n.lineno, under, is_write)
            )
        for child in ast.iter_child_nodes(n):
            visit(child, method, under)

    for m in methods:
        for stmt in m.body:
            visit(stmt, m.name, False)

    # mutating method calls on attrs count as writes:
    # self.items.append(x) parses as Call(Attribute(Attribute(self,
    # items), append)); mark via a second walk
    for m in methods:
        for sub in ast.walk(m):
            if not isinstance(sub, ast.Call):
                continue
            f = sub.func
            if (
                isinstance(f, ast.Attribute)
                and f.attr in _MUTATORS
                and isinstance(f.value, ast.Attribute)
                and isinstance(f.value.value, ast.Name)
                and f.value.value.id == "self"
            ):
                attr = f.value.attr
                for i, (meth, line, under, _w) in enumerate(
                    info.accesses.get(attr, [])
                ):
                    if line == sub.lineno and meth == m.name:
                        info.accesses[attr][i] = (meth, line, under, True)
    # augmented assigns (self.x += 1) — ctx is Store on the Attribute
    # already, so nothing extra to do
    return info


def check_lock_discipline(
    project: Project, indexes: Dict[str, ModuleIndex]
) -> List[Finding]:
    findings: List[Finding] = []
    for sf in project.files:
        if sf.tree is None:
            continue
        for node in sf.tree.body:  # type: ignore[attr-defined]
            if not isinstance(node, ast.ClassDef):
                continue
            info = _scan_class_locks(node)
            if info is None:
                continue
            # lock-held helpers: methods only ever called with the
            # lock held ("callers hold self._lock" pattern); iterate
            # to the fixpoint so helper chains of any depth converge
            # (each round can only ADD one call-graph level)
            held = set()
            for _ in range(len(info.method_names) + 1):
                new_held = set(held)
                calls_of: Dict[str, List[bool]] = {}
                for caller, calls in info.method_calls.items():
                    for callee, under in calls:
                        effective = under or caller in new_held
                        calls_of.setdefault(callee, []).append(effective)
                for meth, contexts in calls_of.items():
                    if contexts and all(contexts):
                        new_held.add(meth)
                if new_held == held:
                    break
                held = new_held

            def effective_under(meth: str, under: bool) -> bool:
                return under or meth in held

            # guarded = touched under the lock somewhere AND mutated
            # outside __init__ somewhere (immutable config attrs set
            # once in __init__ don't need the lock)
            for attr, accesses in sorted(info.accesses.items()):
                under_somewhere = any(
                    effective_under(m, u) for (m, _l, u, _w) in accesses
                    if m != "__init__"
                )
                mutated = any(
                    w for (m, _l, _u, w) in accesses if m != "__init__"
                )
                if not (under_somewhere and mutated):
                    continue
                # one finding per (attr, method), at the first
                # offending line — a method touching the attr five
                # times is one violation, not five
                first_bad: Dict[str, int] = {}
                for meth, line, under, _w in accesses:
                    if meth == "__init__":
                        continue
                    if not effective_under(meth, under):
                        first_bad[meth] = min(
                            first_bad.get(meth, line), line
                        )
                for meth, line in sorted(first_bad.items()):
                    findings.append(Finding(
                        "lock-discipline", sf.path, line,
                        f"'{info.name}.{attr}' is accessed under "
                        f"the class lock elsewhere but without it "
                        f"in '{meth}'",
                    ))
    return findings


# ---------------------------------------------------------------------------
# resilience-coverage
# ---------------------------------------------------------------------------

_RESILIENCE_SCOPE = (
    "omero_ms_pixel_buffer_tpu/io/stores.py",
    # the batched read plane (r14): the shared fetch pool + the
    # ranged/parallel fetch planner are THE remote chunk-read clients
    # now — breaker gate + fault point + per-call timeout required
    "omero_ms_pixel_buffer_tpu/io/fetch.py",
    "omero_ms_pixel_buffer_tpu/db/postgres.py",
    "omero_ms_pixel_buffer_tpu/auth/stores.py",
    "omero_ms_pixel_buffer_tpu/auth/ice.py",
    # the cache plane's network call sites (r11): the RESP L2 client
    # and the peer-fetch HTTP client must carry breaker gate + fault
    # point + per-call timeout like every other remote edge
    "omero_ms_pixel_buffer_tpu/cache/plane/",
    # the viewer-protocol adapters (r15): grammar-only today (every
    # network hop happens in the native serving path they delegate
    # to), but the scope pin means any future remote call added here
    # must arrive wrapped like every other edge
    "omero_ms_pixel_buffer_tpu/http/protocols/",
    # the Zipkin span reporter (r16): a network client that escaped
    # the rule for five rounds — its batch POST must carry the same
    # breaker gate + fault point + per-call timeout as every edge
    "omero_ms_pixel_buffer_tpu/utils/tracing.py",
    # the cluster coordination plane (r17): the coordination RESP
    # link is the one raw network primitive here (membership leases,
    # epoch bumps, and brain exchanges all ride it); every future
    # remote call added to this package must arrive wrapped too.
    # r20 explicitly includes cluster/gossip.py — its exchanges must
    # keep riding PeerClient's breaker/fault-point/timeout wrapper
    # rather than growing a raw network path of their own
    "omero_ms_pixel_buffer_tpu/cluster/",
    "omero_ms_pixel_buffer_tpu/cluster/gossip.py",
    # the interactive session plane (r22): channels and annotations
    # are loop-side fan-out today (their one network hop — the drain
    # handoff POST — rides PeerClient's wrapper), but a push plane is
    # exactly where someone adds a webhook or an upstream subscribe
    # next; the scope pin means it arrives wrapped
    "omero_ms_pixel_buffer_tpu/session/",
    # the ingest plane (r24): shard commits go through the store
    # layer (FileStore rename / S3 SigV4 PUT) with ingest.commit and
    # ingest.index fault points; any future direct network call added
    # to the write path must carry the same breaker/fault/timeout
    # wrapping as the read edges it races
    "omero_ms_pixel_buffer_tpu/ingest/",
)

_NET_PRIMITIVES: List[Tuple[Optional[str], str, str]] = [
    (None, "open_connection", "asyncio.open_connection"),
    (None, "create_connection", "socket.create_connection"),
    (None, "urlopen", "urllib.request.urlopen"),
    (None, "HTTPConnection", "http.client.HTTPConnection"),
    (None, "HTTPSConnection", "http.client.HTTPSConnection"),
]


def _has_breaker_marker(fn: FunctionInfo) -> bool:
    for call in fn.calls:
        if call.name in ("allow",) and call.base and "breaker" in call.base.lower():
            return True
        if call.name == "call" and call.base and "breaker" in call.base.lower():
            return True
        if call.name in ("_get_with_retry", "resilient_get"):
            return True
    return False


def _has_injection_marker(fn: FunctionInfo) -> bool:
    for call in fn.calls:
        if call.name in ("fire", "fire_async") and call.base and (
            "injector" in call.base.lower()
        ):
            return True
        if call.name in ("_get_with_retry", "resilient_get"):
            return True
    return False


def _has_timeout_marker(fn: FunctionInfo) -> bool:
    """Per-call timeout evidence: an ``asyncio.wait_for`` (the async
    edges) or any call passing a ``timeout``-named keyword (the
    http.client edges, where the timeout rides the constructor)."""
    for call in fn.calls:
        if call.name == "wait_for":
            return True
        if call.has_timeout_kw:
            return True
        if call.name == "_get_with_retry":
            return True
    return False


def _has_retry_marker(fn: FunctionInfo) -> bool:
    """Retry-policy evidence (the KNOWN_GAPS "does not require the
    retry wrapper" item): a call through ``resilient_get`` / the old
    ``_get_with_retry`` name / anything retry-named, or the
    reconnect-once shape — a ``try`` whose except handler re-issues a
    call the try body made (the wire clients' drop-and-redo recovery:
    one transient transport error heals in place instead of failing
    the request)."""
    for call in fn.calls:
        if call.name in ("_get_with_retry", "resilient_get"):
            return True
        if "retry" in call.name.lower():
            return True
    node = getattr(fn, "node", None)
    if node is None:
        return False
    for sub in ast.walk(node):
        if not isinstance(sub, ast.Try):
            continue
        tried: Set[Tuple[Optional[str], str]] = set()
        for stmt in sub.body:
            for c in ast.walk(stmt):
                if isinstance(c, ast.Call):
                    tried.add(_base_of(c.func))
        for handler in sub.handlers:
            for stmt in handler.body:
                for c in ast.walk(stmt):
                    if (
                        isinstance(c, ast.Call)
                        and _base_of(c.func) in tried
                    ):
                        return True
    return False


def check_resilience_coverage(
    project: Project, indexes: Dict[str, ModuleIndex]
) -> List[Finding]:
    findings: List[Finding] = []
    for sf in project.files:
        if sf.tree is None or not project.in_scope(
            sf, "resilience-coverage", _RESILIENCE_SCOPE
        ):
            continue
        idx = indexes[sf.path]
        # markers a function *transitively contains* (itself + loose
        # same-module callees): (breaker, injection, timeout, retry)
        contains: Dict[str, Tuple[bool, bool, bool, bool]] = {}

        def markers_of(
            fn: FunctionInfo, stack: Set[str]
        ) -> Tuple[bool, bool, bool, bool]:
            if fn.qualname in contains:
                return contains[fn.qualname]
            if fn.qualname in stack:
                return (False, False, False, False)
            stack.add(fn.qualname)
            marks = (
                _has_breaker_marker(fn),
                _has_injection_marker(fn),
                _has_timeout_marker(fn),
                _has_retry_marker(fn),
            )
            if not all(marks):
                for call in fn.calls:
                    for callee in idx.resolve_loose(call):
                        sub = markers_of(callee, stack)
                        marks = tuple(
                            a or b for a, b in zip(marks, sub)
                        )
                        if all(marks):
                            break
                    if all(marks):
                        break
            stack.discard(fn.qualname)
            contains[fn.qualname] = marks
            return marks

        # reverse edges (loose): callee bare name -> caller functions
        callers: Dict[str, Set[str]] = {}
        by_qual = {fn.qualname: fn for fn in idx.functions}
        for fn in idx.functions:
            for call in fn.calls:
                for callee in idx.resolve_loose(call):
                    callers.setdefault(callee.qualname, set()).add(
                        fn.qualname
                    )

        def coverage(fn: FunctionInfo) -> Tuple[bool, bool, bool, bool]:
            """OR of markers over the function and every caller path
            (the rule only *admits* guards, so over-connecting is
            safe)."""
            marks = (False, False, False, False)
            seen: Set[str] = set()
            frontier = [fn.qualname]
            while frontier:
                q = frontier.pop()
                if q in seen:
                    continue
                seen.add(q)
                sub = markers_of(by_qual[q], set())
                marks = tuple(a or b for a, b in zip(marks, sub))
                if all(marks):
                    return marks
                frontier.extend(callers.get(q, ()))
            return marks

        for fn in idx.functions:
            for call in fn.calls:
                desc = _match_blocking(call, _NET_PRIMITIVES)
                if desc is None:
                    continue
                brk, inj, tmo, rty = coverage(fn)
                if not (brk and inj):
                    findings.append(Finding(
                        "resilience-coverage", sf.path, call.line,
                        f"remote I/O ({desc}) in '{fn.name}' has no "
                        "circuit-breaker gate or fault-injection "
                        "point on any caller path — route it through "
                        "the resilience wrappers",
                    ))
                elif not tmo:
                    findings.append(Finding(
                        "resilience-coverage", sf.path, call.line,
                        f"remote I/O ({desc}) in '{fn.name}' has no "
                        "per-call timeout on any caller path — bound "
                        "the exchange with asyncio.wait_for (or a "
                        "timeout= argument) so a silent dependency "
                        "can't park the caller",
                    ))
                elif not rty:
                    findings.append(Finding(
                        "resilience-coverage", sf.path, call.line,
                        f"remote I/O ({desc}) in '{fn.name}' has no "
                        "retry policy on any caller path — route one "
                        "caller through resilient_get / a retry "
                        "wrapper (or a reconnect-once recovery) so a "
                        "single transient transport error doesn't "
                        "surface as a request failure; if single-"
                        "attempt is the design, suppress with the "
                        "justification",
                    ))
    return findings


# ---------------------------------------------------------------------------
# jax-hotpath
# ---------------------------------------------------------------------------

_JAX_SYNC_SCOPE = (
    "omero_ms_pixel_buffer_tpu/models/tile_pipeline.py",
    "omero_ms_pixel_buffer_tpu/models/device_dispatch.py",
    "omero_ms_pixel_buffer_tpu/ops/",
    # render/ covers the whole analysis plane too: engine.py,
    # analysis.py (device histograms), masks.py — and, since r19,
    # supertile.py (the fused composite+carve program: its carved
    # batches must stay device-resident into the encode queue) —
    # every device->host pull there needs the intended-sink
    # justification
    "omero_ms_pixel_buffer_tpu/render/",
)
_JAX_JIT_SCOPE = _JAX_SYNC_SCOPE + (
    "omero_ms_pixel_buffer_tpu/models/device_cache.py",
    "omero_ms_pixel_buffer_tpu/parallel/",
    "omero_ms_pixel_buffer_tpu/io/jpeg.py",
)
# calls whose results live on the device
_DEVICE_PRODUCER_BASES = {"jnp", "jax", "lax"}
_DEVICE_PRODUCER_NAMES = {
    "pallas_filter_tiles", "filter_tiles", "filter_batch",
    "deflate_filtered_batch", "shard_batch", "shard_rows",
    "sharded_batch_filter", "distributed_filter_plane",
    "to_big_endian_bytes", "device_put", "crop_batch", "pad_batch",
    "render_batch", "render_local", "fused_render_filter_deflate_batch",
    "sharded_render_filter_deflate", "render_filter_deflate_local",
}
# ...except these, which return host values
_HOST_RETURNING = {"device_get", "devices", "default_backend"}

_SYNC_SINKS = {
    "asarray", "array", "float", "int", "bytes", "tobytes", "item",
}


@dataclasses.dataclass
class _DeviceFlowResult:
    #: line -> sink descriptions (the findings feed)
    sinks: Dict[int, Set[str]]
    #: calls that received >= 1 device-valued argument:
    #: (base, name, line, positional device flags, keyword device flags)
    device_calls: List[
        Tuple[Optional[str], str, int, List[bool], Dict[str, bool]]
    ]
    #: whether some ``return`` expression carries a device value
    returns_device: bool


def _device_names_flow(
    fn: FunctionInfo,
    seed_params: frozenset = frozenset(),
    extra_producer=None,
) -> _DeviceFlowResult:
    """One forward pass over statements in source order — an SSA
    approximation good enough for a linter: names assigned from device
    producers join the device set, names reassigned from anything else
    (``jax.device_get`` included) leave it. Sinks are evaluated with
    the device set AS OF their statement, so a post-``device_get``
    ``int(lengths.max())`` is correctly host-side.

    The r21 interprocedural layer threads through three extensions:
    ``seed_params`` are parameter names device-valued at entry (the
    passed-device-param escape — a callee receiving ``filtered`` from
    a device producer at some call site); ``extra_producer`` lets the
    driver mark calls to functions whose RETURN carries a device value;
    the result records every call that received a device argument and
    whether the function returns one, which is what the fixpoint in
    ``check_jax_hotpath`` feeds back in.

    Sinks reached INSIDE a loop body (``for``/``while``) are tagged
    distinctly: a per-iteration ``np.asarray``/``.item()``/``float()``
    on a device value pays one full device round trip per lane, the
    exact pattern the double-buffered dispatcher exists to avoid —
    batch the pull through one ``jax.device_get`` outside the loop."""
    device: Set[str] = set(seed_params)
    sinks: Dict[int, Set[str]] = {}
    device_calls: List[
        Tuple[Optional[str], str, int, List[bool], Dict[str, bool]]
    ] = []
    returns_device = False
    loop_depth = 0

    def call_is_producer(call: ast.Call) -> Optional[bool]:
        base, name = _base_of(call.func)
        if name in _HOST_RETURNING:
            return False
        root = base.split(".")[0] if base else None
        if root in _DEVICE_PRODUCER_BASES or (base or "").endswith("_jax"):
            return True
        if name in _DEVICE_PRODUCER_NAMES:
            return True
        if extra_producer is not None:
            return extra_producer(call)
        return None

    def expr_device(expr: ast.expr) -> bool:
        if isinstance(expr, ast.Call):
            return bool(call_is_producer(expr))
        if isinstance(expr, ast.Name):
            return expr.id in device
        if isinstance(expr, ast.Subscript):
            return expr_device(expr.value)
        if isinstance(expr, (ast.Tuple, ast.List)):
            return any(expr_device(e) for e in expr.elts)
        if isinstance(expr, ast.IfExp):
            return expr_device(expr.body) or expr_device(expr.orelse)
        if isinstance(expr, ast.Attribute):
            return expr_device(expr.value)
        if isinstance(expr, ast.BinOp):
            return expr_device(expr.left) or expr_device(expr.right)
        return False

    def assign_names(target: ast.expr, is_device: bool) -> None:
        if isinstance(target, ast.Name):
            if is_device:
                device.add(target.id)
            else:
                device.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                assign_names(e, is_device)

    def scan_sinks(expr: Optional[ast.AST]) -> None:
        if expr is None:
            return
        in_loop = " inside a loop (per-iteration device round trip)" \
            if loop_depth else ""
        for node in ast.walk(expr):
            if not isinstance(node, ast.Call):
                continue
            base, name = _base_of(node.func)
            if name is not None:
                pos_flags = [expr_device(a) for a in node.args]
                kw_flags = {
                    kw.arg: expr_device(kw.value)
                    for kw in node.keywords if kw.arg is not None
                }
                if any(pos_flags) or any(kw_flags.values()):
                    device_calls.append(
                        (base, name, node.lineno, pos_flags, kw_flags)
                    )
            if name not in _SYNC_SINKS:
                continue
            if name in ("asarray", "array") and base not in ("np", "numpy"):
                continue
            if name in ("tobytes", "item"):
                target = node.func.value  # type: ignore[union-attr]
                if expr_device(target):
                    sinks.setdefault(node.lineno, set()).add(
                        f".{name}() on device value{in_loop}"
                    )
                continue
            if any(expr_device(a) for a in node.args):
                label = f"{base + '.' if base else ''}{name}(...)"
                sinks.setdefault(node.lineno, set()).add(
                    f"{label} on device value{in_loop}"
                )

    def process(node: ast.AST) -> None:
        nonlocal loop_depth, returns_device
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return  # nested defs analyzed as their own scope? no — skip
        if isinstance(node, ast.Return):
            scan_sinks(node.value)
            if node.value is not None and expr_device(node.value):
                returns_device = True
            return
        if isinstance(node, ast.Assign):
            scan_sinks(node.value)
            is_dev = expr_device(node.value)
            for t in node.targets:
                assign_names(t, is_dev)
            return
        if isinstance(node, ast.AugAssign):
            scan_sinks(node.value)
            return
        if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
            # a for's iterable evaluates ONCE (scan at the current
            # depth); a while's test re-evaluates per iteration
            scan_sinks(getattr(node, "iter", None))
            loop_depth += 1
            try:
                scan_sinks(getattr(node, "test", None))
                for part in (node.body, node.orelse):
                    for stmt in part:
                        process(stmt)
            finally:
                loop_depth -= 1
            return
        # evaluate the statement's own expressions with the current
        # set, then walk child statements in order (branch sets flow
        # linearly — an over-approximation that suits a linter)
        child_stmts: List[ast.stmt] = []
        for field in ("body", "orelse", "finalbody", "handlers"):
            part = getattr(node, field, None)
            if part:
                child_stmts.extend(
                    h for h in part if isinstance(h, (ast.stmt, ast.excepthandler))
                )
        own_exprs = [
            v for v in ast.iter_child_nodes(node)
            if isinstance(v, ast.expr)
        ]
        for e in own_exprs:
            scan_sinks(e)
        if child_stmts:
            for stmt in child_stmts:
                if isinstance(stmt, ast.excepthandler):
                    for s in stmt.body:
                        process(s)
                else:
                    process(stmt)

    for stmt in getattr(fn.node, "body", []):
        process(stmt)
    return _DeviceFlowResult(sinks, device_calls, returns_device)


def _device_param_lattice(
    graph: ProjectGraph,
    sync_fns: List[FunctionInfo],
) -> Tuple[Dict[str, frozenset], Set[str]]:
    """"Carries a device value" fact, propagated interprocedurally:
    a parameter is device-valued if ANY strict call site passes a
    device expression in its position (the ``_finish_png_lanes``
    ``filtered`` escape the module-local analyzer provably missed),
    and a function is device-returning if some ``return`` carries one.
    Fixpoint over the sync-scope functions — each round can only add
    facts, and call chains here are shallow, so it converges fast."""
    in_scope = {fn.qualname for fn in sync_fns}
    seeds: Dict[str, frozenset] = {}
    device_returns: Set[str] = set()

    def param_names(fn: FunctionInfo) -> List[str]:
        a = fn.node.args  # type: ignore[union-attr]
        return [p.arg for p in list(a.posonlyargs) + list(a.args)]

    for _ in range(len(sync_fns) + 1):
        changed = False
        for fn in sync_fns:

            def extra_producer(call_node, _fn=fn):
                base, name = _base_of(call_node.func)
                if name is None:
                    return None
                callee = graph.resolve(
                    _fn, CallSite(base, name, call_node.lineno, False)
                )
                if callee is not None and callee.qualname in device_returns:
                    return True
                return None

            res = _device_names_flow(
                fn, seeds.get(fn.qualname, frozenset()), extra_producer
            )
            if res.returns_device and fn.qualname not in device_returns:
                device_returns.add(fn.qualname)
                changed = True
            for base, name, line, pos_flags, kw_flags in res.device_calls:
                callee = graph.resolve(
                    fn, CallSite(base, name, line, False)
                )
                if callee is None or callee.qualname not in in_scope:
                    continue
                params = param_names(callee)
                offset = 1 if (
                    callee.class_name is not None
                    and params and params[0] == "self"
                ) else 0
                hit: Set[str] = set(seeds.get(callee.qualname, frozenset()))
                before = len(hit)
                for i, flag in enumerate(pos_flags):
                    j = i + offset
                    if flag and j < len(params):
                        hit.add(params[j])
                for kw, flag in kw_flags.items():
                    if flag and kw in params:
                        hit.add(kw)
                if len(hit) != before:
                    seeds[callee.qualname] = frozenset(hit)
                    changed = True
        if not changed:
            break
    return seeds, device_returns


def check_jax_hotpath(
    project: Project, indexes: Dict[str, ModuleIndex]
) -> List[Finding]:
    findings: List[Finding] = []
    graph = project_graph(project, indexes)

    sync_fns: List[FunctionInfo] = []
    for sf in project.files:
        if sf.tree is None:
            continue
        if project.in_scope(sf, "jax-hotpath", _JAX_SYNC_SCOPE):
            sync_fns.extend(indexes[sf.path].functions)
    seeds, device_returns = _device_param_lattice(graph, sync_fns)

    for sf in project.files:
        if sf.tree is None:
            continue
        in_sync_scope = project.in_scope(sf, "jax-hotpath", _JAX_SYNC_SCOPE)
        in_jit_scope = project.in_scope(sf, "jax-hotpath", _JAX_JIT_SCOPE)
        if not (in_sync_scope or in_jit_scope):
            continue
        idx = indexes[sf.path]
        if in_sync_scope:
            for fn in idx.functions:
                # explicit full sync
                for call in fn.calls:
                    if call.name == "block_until_ready":
                        findings.append(Finding(
                            "jax-hotpath", sf.path, call.line,
                            f"block_until_ready in '{fn.name}' "
                            "stalls the host on device completion — "
                            "serving code should stay async to the "
                            "device (measurements belong in "
                            "benchmarks/)",
                        ))

                def extra_producer(call_node, _fn=fn):
                    base, name = _base_of(call_node.func)
                    if name is None:
                        return None
                    callee = graph.resolve(
                        _fn,
                        CallSite(base, name, call_node.lineno, False),
                    )
                    if (
                        callee is not None
                        and callee.qualname in device_returns
                    ):
                        return True
                    return None

                seed = seeds.get(fn.qualname, frozenset())
                res = _device_names_flow(fn, seed, extra_producer)
                via = (
                    " (device value arrives via parameter "
                    + "/".join(sorted(seed)) + ")"
                ) if seed else ""
                for line, descs in sorted(res.sinks.items()):
                    for desc in sorted(descs):
                        findings.append(Finding(
                            "jax-hotpath", sf.path, line,
                            f"host sync in '{fn.name}': {desc} forces "
                            "a device->host transfer — batch pulls "
                            "through one jax.device_get, or justify "
                            f"with a suppression{via}",
                        ))
        if in_jit_scope:
            findings.extend(_check_jit_in_function(sf))
    return findings


def _check_jit_in_function(sf: SourceFile) -> List[Finding]:
    """``jax.jit`` applied inside a function body re-traces on every
    call unless the jitted callable is cached at module level (a
    ``global`` rebind or a module-level cache dict)."""
    findings: List[Finding] = []
    module_names = set()
    for node in sf.tree.body:  # type: ignore[attr-defined]
        if isinstance(node, ast.Assign):
            module_names.update(
                t.id for t in node.targets if isinstance(t, ast.Name)
            )
        elif isinstance(node, ast.AnnAssign) and isinstance(
            node.target, ast.Name
        ):
            module_names.add(node.target.id)

    def jit_sites(fn_node: ast.AST) -> List[int]:
        sites = []
        for node in ast.walk(fn_node):
            if isinstance(node, ast.Call):
                base, name = _base_of(node.func)
                if name == "jit" and base in ("jax", None):
                    sites.append(node.lineno)
            elif isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef)
            ):
                for dec in node.decorator_list:
                    d = dec.func if isinstance(dec, ast.Call) else dec
                    base, name = _base_of(d) if isinstance(
                        d, (ast.Name, ast.Attribute)
                    ) else (None, None)
                    if name == "jit" and base in ("jax", None):
                        sites.append(dec.lineno)
                    # partial(jax.jit, ...) decorator
                    if (
                        isinstance(dec, ast.Call)
                        and name == "partial"
                        and dec.args
                    ):
                        b2, n2 = _base_of(dec.args[0]) if isinstance(
                            dec.args[0], (ast.Name, ast.Attribute)
                        ) else (None, None)
                        if n2 == "jit" and b2 in ("jax", None):
                            sites.append(dec.lineno)
        return sites

    def caches_at_module_level(fn_node: ast.AST) -> bool:
        for node in ast.walk(fn_node):
            if isinstance(node, ast.Global):
                return True
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if (
                        isinstance(t, ast.Subscript)
                        and isinstance(t.value, ast.Name)
                        and t.value.id in module_names
                    ):
                        return True
        return False

    for node in sf.tree.body:  # type: ignore[attr-defined]
        tops: List[ast.AST] = []
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            tops = [node]
        elif isinstance(node, ast.ClassDef):
            tops = [
                m for m in node.body
                if isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef))
            ]
        for top in tops:
            # decorators on the top-level def itself run once at
            # definition time — only jits nested *inside* the body count
            body_sites: List[int] = []
            for stmt in top.body:  # type: ignore[attr-defined]
                body_sites.extend(jit_sites(stmt))
            if body_sites and not caches_at_module_level(top):
                for line in body_sites:
                    findings.append(Finding(
                        "jax-hotpath", sf.path, line,
                        f"jax.jit inside '{top.name}' without a "
                        "module-level cache — the program re-traces "
                        "(and may recompile) on every call",
                    ))
    return findings


# ---------------------------------------------------------------------------
# error-taxonomy
# ---------------------------------------------------------------------------

_TAXONOMY_SCOPE = (
    "omero_ms_pixel_buffer_tpu/dispatch/",
    "omero_ms_pixel_buffer_tpu/http/",
)
_ERRORS_MODULE = "omero_ms_pixel_buffer_tpu/errors.py"
# fallback when the errors module isn't in the analyzed file set
# (fixture corpora) — the taxonomy as of this writing
_KNOWN_TAXONOMY = {
    "TileError", "BadRequestError", "PermissionDeniedError",
    "NotFoundError", "InternalError", "ServiceUnavailableError",
    "GatewayTimeoutError", "DeadlineExceeded",
}


def _taxonomy_classes(project: Project) -> Set[str]:
    roots: Set[str] = set()
    errors_sf = project.by_path.get(_ERRORS_MODULE)
    if errors_sf is not None and errors_sf.tree is not None:
        for node in errors_sf.tree.body:  # type: ignore[attr-defined]
            if isinstance(node, ast.ClassDef):
                roots.add(node.name)
    if not roots:
        roots = set(_KNOWN_TAXONOMY)
    # package-wide subclasses (DeadlineExceeded(GatewayTimeoutError))
    changed = True
    while changed:
        changed = False
        for sf in project.files:
            if sf.tree is None:
                continue
            for node in ast.walk(sf.tree):
                if not isinstance(node, ast.ClassDef):
                    continue
                for b in node.bases:
                    _, bname = _base_of(b) if isinstance(
                        b, (ast.Name, ast.Attribute)
                    ) else (None, None)
                    if bname in roots and node.name not in roots:
                        roots.add(node.name)
                        changed = True
    return roots


def check_error_taxonomy(
    project: Project, indexes: Dict[str, ModuleIndex]
) -> List[Finding]:
    findings: List[Finding] = []
    taxonomy = _taxonomy_classes(project)

    for sf in project.files:
        if sf.tree is None:
            continue
        in_raise_scope = project.in_scope(
            sf, "error-taxonomy", _TAXONOMY_SCOPE
        )

        class _V(ast.NodeVisitor):
            def __init__(self):
                self.async_depth = 0

            def visit_AsyncFunctionDef(self, node):
                self.async_depth += 1
                self.generic_visit(node)
                self.async_depth -= 1

            def visit_FunctionDef(self, node):
                depth, self.async_depth = self.async_depth, 0
                self.generic_visit(node)
                self.async_depth = depth

            def visit_ExceptHandler(self, node):
                catches_base = False
                if node.type is None:
                    findings.append(Finding(
                        "error-taxonomy", sf.path, node.lineno,
                        "bare 'except:' catches SystemExit/"
                        "KeyboardInterrupt/CancelledError — name the "
                        "exceptions (Exception at the broadest)",
                    ))
                    catches_base = True
                else:
                    names = []
                    types = (
                        node.type.elts
                        if isinstance(node.type, ast.Tuple)
                        else [node.type]
                    )
                    for t in types:
                        if isinstance(t, (ast.Name, ast.Attribute)):
                            names.append(_base_of(t)[1] if isinstance(
                                t, ast.Attribute
                            ) else t.id)
                    if "BaseException" in names:
                        catches_base = True
                    if "CancelledError" in names and not _reraises(node):
                        findings.append(Finding(
                            "error-taxonomy", sf.path, node.lineno,
                            "CancelledError caught and swallowed — "
                            "cancellation must propagate (re-raise "
                            "it)",
                        ))
                if (
                    catches_base
                    and node.type is not None
                    and not _reraises(node)
                ):
                    findings.append(Finding(
                        "error-taxonomy", sf.path, node.lineno,
                        "except BaseException without re-raise "
                        "swallows CancelledError in coroutines",
                    ))
                self.generic_visit(node)

            def visit_Raise(self, node):
                if not in_raise_scope or node.exc is None:
                    self.generic_visit(node)
                    return
                exc = node.exc
                target = exc.func if isinstance(exc, ast.Call) else exc
                name = None
                if isinstance(target, (ast.Name, ast.Attribute)):
                    name = _base_of(target)[1] if isinstance(
                        target, ast.Attribute
                    ) else target.id
                if (
                    name is not None
                    and name not in taxonomy
                    and name[:1].isupper()
                ):
                    findings.append(Finding(
                        "error-taxonomy", sf.path, node.lineno,
                        f"'{name}' raised on the request path has no "
                        "HTTP status mapping in errors.py — raise a "
                        "TileError subclass (or map it)",
                    ))
                self.generic_visit(node)

        def _reraises(handler: ast.ExceptHandler) -> bool:
            for sub in ast.walk(handler):
                if isinstance(sub, ast.Raise):
                    return True
            return False

        _V().visit(sf.tree)
    return findings


from .checkers_fleet import FLEET_CHECKERS  # noqa: E402

ALL_CHECKERS = {
    "loop-block": check_loop_block,
    "lock-discipline": check_lock_discipline,
    "resilience-coverage": check_resilience_coverage,
    "jax-hotpath": check_jax_hotpath,
    "error-taxonomy": check_error_taxonomy,
    **FLEET_CHECKERS,
}
