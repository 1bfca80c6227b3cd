"""Streaming cross-batch device-encode queue.

The r9 dispatcher double-buffered groups WITHIN one ``handle_batch``
call: the batcher thread staged + launched each group and a readback
worker absorbed the device wait — but the batcher drained every future
before returning, so consecutive batches serialized at the batcher
boundary and the TPU sat idle between flushes. This module makes the
dispatcher a PERSISTENT queue (the PATCHEDSERVE keep-the-queue-fed
framing, applied to the encode pipe):

- callers (``TilePipeline.handle_batch``, any batch, any thread) get a
  Future back immediately; a long-lived SUBMIT thread stages each
  group's host batch, blocks only on its H2D transfer (which the
  transfer engine runs concurrently with earlier groups' compute),
  then launches the fused program — jax dispatch is async, so the
  submit thread moves straight on to the next group, INCLUDING groups
  of a batch that arrived while the previous batch was still in
  flight;
- a PULL worker blocks on each group's device completion in the order
  the groups' last programs were launched, pulls lengths + streams in
  one host sync, and frames the PNGs — overlapping group k's D2H +
  framing with group k+1's (and batch N+1's) compute;
- a semaphore bounds the groups the DEVICE holds to ``queue_depth``
  (config ``backend.png.queue-depth``, default 2 = the classic double
  buffer): a slot is taken before a group is staged and given back
  when the pull worker sees the group's last device program done, not
  after the frame, so the host's pull and framing of group k keep no
  later group off the device. Staging backpressures on the SUBMIT
  thread, never on callers.

Dynamic-Huffman groups (deflate mode "dynamic") run two programs with
a host hop between, and a third worker keeps that hop off the chip's
critical path: the submit thread launches pass 1 (filter + histogram,
one program); a PLAN worker pulls the (B, 286) counts — absorbing pass
1's wait — builds the canonical code tables on host, launches pass 2
(emit) and does NOT wait for it: it hands the group to the pull worker
and takes up group k+1, whose emit is then queued on the device behind
group k's. Nothing the chip would wait for needs the chip: the plan of
k+1 needs only k+1's counts, the pull of k only k's emit. (With the
plan, the wait and the frame on one worker the chip idled 44-50% of
the time on the v5e, PERF.md §6 PR 31 and PR 34.) Single-pass groups
(``rle``, ``stored``, render) have no plan and go from the submit
thread straight to the pull worker.

The queue counts, per group, whether its LAST program was launched
while the pipe's device still held an earlier group's last program
(launched, not yet seen done: ``overlapped``) or found none
(``idle_gaps``, with the time since the last one was seen done) —
``snapshot()`` reports steady-state occupancy, the idle-gap
distribution, and mean compute time so BENCH can assert the overlap
instead of describing it.

On a host with several chips a group whose input already lives on one
of them (a crop of an HBM-resident plane, ``submit(..., device=)``)
runs on that chip: its programs follow its data. What orders and
bounds such groups is one more pipe, as wide as there are chips: that
many submit threads, that many times ``queue_depth`` slots and that
many plan and pull workers behind ONE queue, so a group that blocks on
chip 0's pass 2 keeps no other chip from being launched. The width is
the host's and no worker is a chip's own: the groups are taken up in
the order they came, whatever their chip. (A pipe a chip was built
first and measured on the v5e host, PERF.md §6 PR 33: a plane's chip
is fixed, so a closed loop of viewers piles its requests up behind
whichever pipe the host happens to serve slowest, and the latencies
spread like a random-order queue's: p50 340 ms, p95 1236. The chips
idle 70% of the time; it is the host's work that has to be handed out
in order.)
The process keeps what is one whatever the chip count: the group ids,
the pull-size guesses, the counters, and the pipe of groups that name
no device (host-staged and mesh groups, and every group of a one-chip
host), which is the queue as it always was.

Failure contract (unchanged from r9, now chaos-pinned): any failure in
staging, plan, dispatch, wait, pull or frame resolves THAT group's
future with the exception — the pipeline degrades those lanes to the
host encoder — gives its slot back exactly once, and never stalls or
reorders other groups; the ``device.encode-group`` fault point injects
exactly that. With a serving mesh, groups run whole and
blocking on the pull worker through ``parallel.mesh.MeshManager``
(per-chip breakers, probe-shrink-retry), and the dispatcher pre-warms
jit specializations for recently-seen group shapes on a background
thread whenever the healthy mesh WIDTH changes, so the first dispatch
after a shrink or heal doesn't pay the recompile inline.
"""

from __future__ import annotations

import concurrent.futures
import itertools
import logging
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs.recorder import current_record, defer_exemplar, record_scope
from ..utils.metrics import REGISTRY

log = logging.getLogger("omero_ms_pixel_buffer_tpu.device_dispatch")

DEVICE_STAGE_SECONDS = REGISTRY.histogram(
    "device_stage_seconds",
    "Device encode pipeline stage durations "
    "(stage=h2d|compute|hist|plan|emit|d2h|frame)",
)
# a family of its own on purpose: a wait is no stage, and a new label
# on device_stage_seconds would add itself to every sum over the family
DEVICE_QUEUE_WAIT_SECONDS = REGISTRY.histogram(
    "device_queue_wait_seconds",
    "Per group, the waits before its first stage (where=pool: from "
    "submit to the submit thread taking the group up; where=slot: for "
    "one of the queue-depth in-flight slots)",
)


def _observe_stage(duration: float, stage: str) -> None:
    """Stage histogram + deferred trace exemplar: the submitting
    request's record is scoped onto the queue's worker threads per
    group (``record_scope`` in ``_run_stage`` and ``_on_worker``),
    and the exemplar only lands if the tail sampler keeps the trace —
    a device-stage spike in a dashboard pivots to a citable trace."""
    DEVICE_STAGE_SECONDS.observe(duration, stage=stage)
    defer_exemplar(DEVICE_STAGE_SECONDS, duration, stage=stage)


def _observe_wait(duration: float, where: str) -> None:
    DEVICE_QUEUE_WAIT_SECONDS.observe(duration, where=where)


def _annotation(what: str, gid: int, lanes: int, chip=None):
    """``jax.profiler.TraceAnnotation`` ``ompb.queue.<what>``, started
    here and ended by its ``__exit__`` (on any thread). It lands on the
    host plane of the profiler's trace, on the clock of the device's
    operations, so a device idle gap names the stage of the group the
    host was in; with no profiler running it is an atomic flag test.
    A group sent to a named chip carries the stat ``chip``."""
    from jax.profiler import TraceAnnotation

    stats = {"group": gid, "lanes": lanes}
    if chip is not None:
        stats["chip"] = chip
    return TraceAnnotation(f"ompb.queue.{what}", **stats)


class _Span:
    """One interval of one group on two clocks: a histogram (through
    ``observe``) on the host's ``perf_counter`` and a profiler
    annotation over the same interval. Both start at construction, so
    a stage that starts on one thread and ends on another (the launch
    on the submit or the plan worker, the wait on the plan or the pull
    worker) is built where it starts and entered, ``with span:``,
    where it ends. An
    exception ends the annotation and observes nothing, like the
    stamp pairs this replaced."""

    __slots__ = ("gid", "lanes", "t0", "t1", "_observe", "_note")

    def __init__(self, observe, what: Optional[str] = None,
                 gid: int = 0, lanes: int = 0, chip=None):
        self.gid, self.lanes = gid, lanes
        self._observe = observe
        # no `what`: the histogram alone (a wait)
        self._note = (
            None if what is None else _annotation(what, gid, lanes, chip)
        )
        self.t0 = time.perf_counter()
        self.t1: Optional[float] = None

    def __enter__(self) -> "_Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.t1 = time.perf_counter()
        if self._note is not None:
            self._note.__exit__(exc_type, exc, tb)
        if exc_type is None:
            self._observe(self.t1 - self.t0)

    def end(self) -> None:
        self.__exit__(None, None, None)


class _Marks:
    """The annotations of a group whose stage ends are stamped from
    inside a callback (the mesh methods: ``MeshManager.dispatch`` may
    run it twice), opened and closed by hand, one open at a time:
    ``next(stage)`` ends the open one at the stamp it returns and
    opens ``ompb.queue.<stage>``. The histogram keeps reading the
    stamps."""

    __slots__ = ("gid", "lanes", "_open")

    def __init__(self, stage: str, gid: int, lanes: int):
        self.gid, self.lanes = gid, lanes
        self._open = _annotation(stage, gid, lanes)

    def next(self, stage: str) -> float:
        t = time.perf_counter()
        self._open.__exit__(None, None, None)
        self._open = _annotation(stage, self.gid, self.lanes)
        return t

    def close(self) -> None:
        self._open.__exit__(None, None, None)


DEVICE_GROUP_LANES = REGISTRY.histogram(
    "device_group_lanes",
    "Real lanes of each encode group launched on the device, before "
    "the padding to its program's lane count",
    buckets=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 64, float("inf")),
)

DEVICE_QUEUE_IDLE_SECONDS = REGISTRY.histogram(
    "device_queue_idle_seconds",
    "Device idle gap between the pipe's last in-flight group being "
    "seen done and the next launch of a group's last program "
    "(0-bucketed when that launch found one still in flight)",
)

# how many distinct mesh group shapes the width-change warmup replays
_WARM_SHAPES = 16


def _pow2_lanes(b: int) -> int:
    """The pow2 lane bucket (the per-shape jit-specialization cap)."""
    return 1 << max(b - 1, 0).bit_length()


def _mesh_padded_lanes(b: int, width: int) -> int:
    """Mesh group lane padding: pow2 first (specialization cap), then
    up to a multiple of the healthy mesh width. ONE definition shared
    by the serving dispatch AND the width-change warmup — they must
    compile the same batch shape or the warmup is a lie."""
    return -(-_pow2_lanes(b) // width) * width


class _Pipe:
    """What orders and bounds a stream of groups: submit threads
    (groups stage + launch in the order they came, across batches),
    plan workers (dynamic groups only, taken up in submission order:
    the counts, the host's Huffman plan, the emit's launch, no wait),
    pull workers (taken up in the order the last programs were
    launched: the wait, the pull, the frame; with one worker group
    k's D2H never competes with group k+1's: the pipe stays a pipe)
    and ``queue_depth`` slots a worker for the groups the device
    holds. ``emitting`` counts the groups whose last program is
    launched and not yet seen done, ``idle_since`` stamps the moment
    it last fell to none. The process's pipe has one worker of each
    kind; the pipe of the groups that name their chip has one a
    chip."""

    __slots__ = (
        "workers", "submit_pool", "plan", "pull", "slots", "inflight",
        "emitting", "idle_since",
    )

    def __init__(self, tag: str, queue_depth: int, workers: int = 1):
        self.workers = workers
        self.submit_pool, self.plan, self.pull = (
            concurrent.futures.ThreadPoolExecutor(
                max_workers=workers,
                thread_name_prefix=f"devenc-{stage}{tag}",
            )
            for stage in ("submit", "plan", "pull")
        )
        self.slots = threading.Semaphore(queue_depth * workers)
        self.inflight = 0
        self.emitting = 0
        self.idle_since: Optional[float] = None

    def seen_done(self, t_done: float) -> None:
        """One emitting group less (under the dispatcher's stats
        lock)."""
        self.emitting -= 1
        if not self.emitting:
            self.idle_since = t_done


class _Group:
    """One group on its way through the queue: its id, the caller's
    future, the submitting request's flight record, the pipe that
    orders it and, where it names one, the id of the chip its arrays
    live on (the stat `chip` of its annotations). ``t_launch`` stamps
    its first program's launch. ``holds_slot`` and ``emitting`` are
    what the group has taken of its pipe and not yet given back (a
    slot; a place among the groups whose last program the device
    holds): `_device_done` gives both back, once, whichever way the
    group leaves."""

    __slots__ = (
        "gid", "fut", "rec", "pipe", "chip", "t_launch", "holds_slot",
        "emitting",
    )

    def __init__(self, gid: int, fut, rec, pipe: _Pipe, chip=None):
        self.gid, self.fut, self.rec = gid, fut, rec
        self.pipe, self.chip = pipe, chip
        self.t_launch = 0.0
        self.holds_slot = self.emitting = False


class DeviceEncodeDispatcher:
    """Submit encode groups into the persistent queue; collect
    per-group futures.

    One dispatcher per TilePipeline; ``dd_cap`` is the pipeline's
    shared adaptive compressed-size guess keyed (w, h) — the pull
    worker both consumes and trains it. ``mesh_manager`` (optional)
    switches group dispatch to the sharded multi-chip path.
    ``queue_depth`` bounds the groups the device holds at once.
    """

    def __init__(
        self,
        dd_cap: Dict[Tuple[int, int], int],
        mesh_manager=None,
        queue_depth: int = 2,
        chips: int = 1,
    ):
        self._dd_cap = dd_cap
        self.mesh_manager = mesh_manager
        self.queue_depth = max(1, int(queue_depth))
        # the process's pipe carries every group that names no chip;
        # those that do share one more, `chips` workers wide, built
        # when the first such group arrives
        self._pipes_lock = threading.Lock()
        self._pipe = _Pipe("", self.queue_depth)
        self._chip_pipe: Optional[_Pipe] = None
        self._chips = max(1, int(chips))
        self._chip_groups: Dict[int, int] = {}  # chip id -> groups
        # per-dispatcher group ids: every stage annotation, the waits
        # and the submitting request's flight record carry one
        self._gids = itertools.count(1)
        self._donate: Optional[bool] = None
        self._closed = False
        # outstanding caller futures: close() drains against these
        # with a deadline, so a wedged device program can't hold
        # server shutdown hostage
        self._pending_lock = threading.Lock()
        self._pending: set = set()
        # queue telemetry (all guarded by _stats_lock): in-flight count,
        # occupancy samples, idle-gap vs overlap accounting, compute time
        self._stats_lock = threading.Lock()
        self._inflight = 0
        self._groups = 0
        self._occupancy_sum = 0
        self._idle_gap_sum = 0.0
        self._idle_gap_max = 0.0
        self._idle_gaps = 0
        self._overlapped = 0
        self._compute_sum = 0.0
        self._computes = 0
        # real lanes of served dynamic groups, by the implementation
        # that planned them (device_deflate.plan_impl)
        self._plan_lanes = {"native": 0, "python": 0}
        # mesh warmup state: recently-seen raw-tile group shapes +
        # widths already warmed (tests read _warmed)
        self._seen_mesh: Dict[tuple, None] = {}
        self._warmed: set = set()
        self._warm_lock = threading.Lock()
        if mesh_manager is not None and hasattr(
            mesh_manager, "add_width_listener"
        ):
            mesh_manager.add_width_listener(self._on_mesh_width)

    def close(self, drain_timeout: float = 30.0) -> None:
        """Drain the queue: stop accepting groups, wait up to
        ``drain_timeout`` seconds for every staged group to finish
        (their futures resolve), then release the threads. The
        deadline matters: a wedged device program holds
        ``block_until_ready`` forever, and an unbounded drain would
        hang server shutdown — past the
        deadline the leftover futures resolve exceptionally (callers
        host-fall-back) and the stuck worker threads are abandoned.
        Idempotent; TilePipeline.close() calls it."""
        self._closed = True
        with self._pipes_lock:
            pipes = [self._pipe]
            if self._chip_pipe is not None:
                pipes.append(self._chip_pipe)
        for pipe in pipes:
            pipe.submit_pool.shutdown(wait=False)
        with self._pending_lock:
            pending = list(self._pending)
        _, not_done = concurrent.futures.wait(
            pending, timeout=drain_timeout
        )
        for fut in not_done:
            try:
                fut.set_exception(
                    TimeoutError("device encode queue drain timed out")
                )
            except concurrent.futures.InvalidStateError:
                pass  # resolved in the race window: nothing to do
        for pipe in pipes:
            # in the order a group passes them: a plan worker that is
            # still winding down may hand its group on
            pipe.plan.shutdown(wait=not not_done)
            pipe.pull.shutdown(wait=not not_done)
        if not_done:
            log.warning(
                "device encode queue: %d group(s) unresolved after "
                "%.0fs drain; abandoning the worker threads",
                len(not_done), drain_timeout,
            )

    def _donate_ok(self) -> bool:
        # donation frees the staged input for reuse mid-program on
        # TPU; CPU/GPU interpret paths warn and ignore it, so only
        # resolve (and pay the backend query) once
        if self._donate is None:
            import jax

            self._donate = jax.default_backend() == "tpu"
        return self._donate

    # -- queue telemetry ------------------------------------------------

    @staticmethod
    def _stage(stage: str, gid: int, lanes: int, chip=None) -> _Span:
        """``with self._stage("hist", gid, n):`` observes
        ``device_stage_seconds{stage=...}`` and holds the annotation
        ``ompb.queue.<stage>`` over the same interval."""
        return _Span(
            lambda dt: _observe_stage(dt, stage), stage, gid, lanes, chip
        )

    def _pipe_for(self, device) -> tuple:
        """(pipe, chip id): the process's pipe for a group that names
        no chip, else the pipe of those that do."""
        if device is None:
            return self._pipe, None
        with self._pipes_lock:
            if self._chip_pipe is None:
                self._chip_pipe = _Pipe(
                    "-chips", self.queue_depth, workers=self._chips
                )
            return self._chip_pipe, device.id

    @staticmethod
    def _wait(where: str) -> _Span:
        """``device_queue_wait_seconds{where=...}``, a histogram only.
        A wait is no annotation: a dozen groups stand in the pool at
        every instant of a closed loop and the submit thread sits on
        the semaphore three quarters of the time, so the trace's
        reduction, which labels a device idle gap by the host event
        name that covers most of it, called the gaps ``wait_pool`` and
        ``wait_slot`` (measured on the chip, PR 26) and hid the stage
        that was running."""
        return _Span(lambda dt: _observe_wait(dt, where))

    def _note_group(
        self, t_launch: float, lanes: int, group: Optional[_Group]
    ) -> None:
        """Called as a group's first device program is dispatched:
        counts the group and its real lanes and samples occupancy."""
        DEVICE_GROUP_LANES.observe(lanes)
        with self._stats_lock:
            self._groups += 1
            self._occupancy_sum += self._inflight
            if group is None:
                return
            group.t_launch = t_launch
            if group.chip is not None:
                self._chip_groups[group.chip] = (
                    self._chip_groups.get(group.chip, 0) + 1
                )

    def _note_last_launch(
        self, t_launch: float, group: Optional[_Group] = None
    ) -> None:
        """Called as a group's LAST device program is dispatched (the
        emit of a dynamic group, the one program of any other):
        classifies the launch as overlapped (its pipe's device still
        held an earlier group's last program: launched, not yet seen
        done) or post-idle-gap, and counts the group among those the
        device holds until `_device_done`. A mesh method, which notes
        its launch once its blocking dispatch is back, names no group:
        the process's pipe."""
        pipe = self._pipe if group is None else group.pipe
        with self._stats_lock:
            if pipe.emitting:
                self._overlapped += 1
                DEVICE_QUEUE_IDLE_SECONDS.observe(0.0)
            elif pipe.idle_since is not None:
                gap = max(t_launch - pipe.idle_since, 0.0)
                self._idle_gaps += 1
                self._idle_gap_sum += gap
                self._idle_gap_max = max(self._idle_gap_max, gap)
                DEVICE_QUEUE_IDLE_SECONDS.observe(gap)
            pipe.emitting += 1
            if group is not None:
                group.emitting = True

    def _note_launch(
        self, t_launch: float, lanes: int, group: Optional[_Group] = None
    ) -> None:
        """A group whose one program is its first and its last."""
        self._note_group(t_launch, lanes, group)
        self._note_last_launch(t_launch, group)

    def _note_plan(self, lanes: int) -> None:
        """A served dynamic group's ``lanes`` real lanes are planned."""
        from ..ops.device_deflate import plan_impl

        impl = plan_impl()
        with self._stats_lock:
            self._plan_lanes[impl] += lanes

    def _note_compute_done(self, t_done: float, dt: float) -> None:
        """A mesh method's blocking dispatch is back (the process's
        pipe; its slot stays until the method returns)."""
        with self._stats_lock:
            self._compute_sum += dt
            self._computes += 1
            self._pipe.seen_done(t_done)

    def _device_done(
        self, group: _Group, t_done: Optional[float] = None
    ) -> None:
        """The device is done with the group: its last program was
        seen done (at ``t_done``) or the group failed or resolved,
        wherever. Gives back what the group holds of its pipe, each
        exactly once: its place among the emitting and its slot — so
        the slot follows the chip, not the host's pull and frame.
        Idempotent."""
        pipe = group.pipe
        with self._stats_lock:
            if t_done is not None:
                self._compute_sum += t_done - group.t_launch
                self._computes += 1
            if group.emitting:
                group.emitting = False
                pipe.seen_done(
                    time.perf_counter() if t_done is None else t_done
                )
            if not group.holds_slot:
                return
            group.holds_slot = False
            self._inflight -= 1
            pipe.inflight -= 1
        pipe.slots.release()

    def snapshot(self) -> dict:
        """Steady-state queue health for /healthz and BENCH: occupancy,
        the inter-group idle-gap distribution, and mean compute time —
        cross-batch overlap holds when overlapped_fraction is high and
        idle_gap_mean_ms stays below compute_ms_mean."""
        with self._stats_lock:
            groups = self._groups
            out = {
                "queue_depth": self.queue_depth,
                "inflight": self._inflight,
                "groups": groups,
                "mean_occupancy": (
                    round(self._occupancy_sum / groups, 3) if groups else None
                ),
                "overlapped": self._overlapped,
                "idle_gaps": self._idle_gaps,
                "overlapped_fraction": (
                    round(
                        self._overlapped
                        / max(self._overlapped + self._idle_gaps, 1),
                        3,
                    )
                    if (self._overlapped + self._idle_gaps) else None
                ),
                "idle_gap_mean_ms": (
                    round(self._idle_gap_sum / self._idle_gaps * 1e3, 3)
                    if self._idle_gaps else 0.0
                ),
                "idle_gap_max_ms": round(self._idle_gap_max * 1e3, 3),
                "compute_ms_mean": (
                    round(self._compute_sum / self._computes * 1e3, 3)
                    if self._computes else None
                ),
                "plan_lanes_native": self._plan_lanes["native"],
                "plan_lanes_python": self._plan_lanes["python"],
            }
            if self._chip_pipe is not None:  # groups name their chip
                out["chips"] = [
                    {"chip": chip, "groups": n}
                    for chip, n in sorted(self._chip_groups.items())
                ]
                out["chip_pipe"] = {
                    "workers": self._chip_pipe.workers,
                    "inflight": self._chip_pipe.inflight,
                }
        return out

    # -- submission -----------------------------------------------------

    def submit(
        self,
        tiles,
        rows: int,
        row_bytes: int,
        bpp: int,
        filter_mode: str,
        deflate_mode: str,
        lanes: Sequence[int],
        sizes: Sequence[Tuple[int, int]],
        bit_depth: int,
        color_type: int,
        staged: bool = False,
        device=None,
    ) -> "concurrent.futures.Future":
        """Enqueue one encode group; returns a Future resolving to
        {lane_index: png_bytes}. ``tiles`` is either a host ndarray
        (bucket path — staged H2D on the submit thread) or an already
        device-resident batch the caller gives up (plane-cache crops,
        ``staged=True``: donated to the program like a host-staged one;
        its lane axis may be padded beyond ``lanes``). ``device`` names
        the chip a staged batch lives on where the host has several:
        the group runs through the pipe of such groups (None: the
        process's pipe).
        All lanes in a group share one real (w, h) — ``rows``/
        ``row_bytes`` describe it — but ``sizes`` still rides along
        for framing. Returns immediately: staging happens on the
        queue's submit thread, bounded by ``queue_depth``."""
        return self._enqueue(
            self._stage_group,
            tiles, rows, row_bytes, bpp, filter_mode, deflate_mode,
            lanes, sizes, bit_depth, color_type, staged,
            device=device,
        )

    def submit_render(
        self,
        planes,
        index_tables,
        color_luts,
        rows: int,
        row_bytes: int,
        filter_mode: str,
        deflate_mode: str,
        lanes: Sequence[int],
        sizes: Sequence[Tuple[int, int]],
        mask=None,
        staged: bool = False,
    ) -> "concurrent.futures.Future":
        """Enqueue one RENDER group (render/engine): ``planes`` is a
        host (B, C, H, W) unsigned channel batch — or an already
        device-resident one (plane-cache projection crops,
        ``staged=True``, which skips the H2D stage). ``mask`` is an
        optional (B, H, W) uint8 ROI batch multiplied into the
        composite on device (the r19 mask queue wiring — masked lanes
        no longer detour to the host mirror). The fused composite +
        filter + deflate program runs as ONE dispatch and the
        pull worker frames RGB8 PNGs. Same queue semantics as
        ``submit``; with a serving mesh the group shards across chips
        through ``sharded_render_filter_deflate`` instead — masks
        included, as a sharded operand (only staged device-resident
        groups stay single-device, their arrays already live on one
        chip)."""
        return self._enqueue(
            self._stage_render_group,
            planes, index_tables, color_luts, rows, row_bytes,
            filter_mode, deflate_mode, lanes, sizes, mask, staged,
        )

    def _enqueue(
        self, stage_fn, *args, device=None
    ) -> "concurrent.futures.Future":
        if self._closed:
            raise RuntimeError("device encode queue is closed")
        fut: "concurrent.futures.Future" = concurrent.futures.Future()
        with self._pending_lock:
            self._pending.add(fut)
        fut.add_done_callback(self._discard_pending)
        # capture the submitting request's flight record NOW (the
        # caller runs inside the batcher's record scope); the queue's
        # worker threads re-scope it per group for deferred exemplars
        rec = current_record()
        group = _Group(next(self._gids), fut, rec, *self._pipe_for(device))
        if rec is not None:
            rec.tag("device_group", group.gid)  # /debug/requests names the group
        # the wait for the submit thread starts here and ends there
        pool = self._wait("pool")
        try:
            group.pipe.submit_pool.submit(
                self._run_stage, stage_fn, group, args, pool
            )
        except RuntimeError as e:
            # close() raced the _closed check and shut the pool down:
            # resolve THIS group's future exceptionally (the pipeline
            # host-falls-back those lanes) instead of raising past
            # already-submitted groups' futures
            self._fail(group, e)
        return fut

    def _discard_pending(self, fut) -> None:
        with self._pending_lock:
            self._pending.discard(fut)

    def _fail(self, group: _Group, exc) -> None:
        """The group leaves with an exception, from whichever thread
        had it: its slot comes back, its future resolves (the pipeline
        host-falls-back the lanes), no other group is touched."""
        self._device_done(group)
        # close()'s drain deadline may have resolved the future first;
        # losing that race is fine — the caller already host-fell-back
        try:
            group.fut.set_exception(exc)
        except concurrent.futures.InvalidStateError:
            pass

    def _run_stage(self, stage_fn, group: _Group, args, pool: _Span) -> None:
        """Submit-thread trampoline: acquire a slot, stage + launch,
        hand the group to its next worker (the stage function does).
        Any failure resolves the caller future exceptionally (the
        pipeline host-falls-back that group) without touching other
        groups. The group's two waits are observed here, once each,
        whatever the stage function does: ``pool`` ends as this is
        entered, ``slot`` spans the acquire."""
        from ..resilience.faultinject import INJECTOR

        pool.end()
        pipe = group.pipe
        try:
            INJECTOR.fire("device.encode-group")
            # bounded groups on the device: backpressure lands HERE
            # (the submit thread), keeping callers non-blocking and
            # the device at most queue_depth groups ahead of the pull
            with self._wait("slot"):
                pipe.slots.acquire()
            with self._stats_lock:
                group.holds_slot = True
                self._inflight += 1
                pipe.inflight += 1
            with record_scope(group.rec):
                stage_fn(group, *args)
        except Exception as e:
            self._fail(group, e)

    def _hand(self, workers, group: _Group, fn, *args) -> None:
        """Give the group to a plan or a pull worker of its pipe."""
        workers.submit(self._on_worker, group, fn, args)

    def _on_worker(self, group: _Group, fn, args) -> None:
        """Plan- and pull-worker trampoline: the stage runs under the
        submitting request's flight record (its stage observes keep
        their deferred exemplar — the workers outlive any request
        context). A pull stage returns the group's PNGs, which resolve
        it; a plan stage hands the group on and returns None; whatever
        either raises fails the group, and only it."""
        try:
            with record_scope(group.rec):
                out = fn(*args)
        except Exception as e:
            self._fail(group, e)
            return
        if out is None:
            return
        self._device_done(group)  # a mesh method's slot: held to here
        try:
            group.fut.set_result(out)
        except concurrent.futures.InvalidStateError:
            pass  # close()'s drain deadline got there first

    # -- staging (submit thread) ---------------------------------------

    def _stage_group(
        self, group, tiles, rows, row_bytes, bpp, filter_mode,
        deflate_mode, lanes, sizes, bit_depth, color_type, staged,
    ):
        import jax

        n = len(lanes)
        gid, pipe, chip = group.gid, group.pipe, group.chip
        mesh_mgr = self.mesh_manager
        if mesh_mgr is not None and not staged:
            # sharded groups run ENTIRELY on the pull worker: the
            # dispatch must block on device completion inside
            # MeshManager.dispatch, or a chip that wedges mid-compute
            # would surface at a later block_until_ready outside the
            # breaker/probe/shrink machinery and record a phantom
            # success; chips supply the parallelism there, so losing
            # the submit-thread overlap costs nothing.
            self._register_mesh_shape(
                tiles, rows, row_bytes, bpp, filter_mode, deflate_mode
            )
            if deflate_mode == "dynamic":
                # two sharded programs with the host Huffman-plan hop
                # between: the plan runs per shard's pulled counts
                # inside the managed dispatch, so mesh lanes keep
                # content-adaptive codes instead of downgrading to rle
                return self._hand(
                    pipe.pull, group, self._mesh_dynamic_group,
                    gid, tiles, rows, row_bytes, bpp, filter_mode,
                    lanes, sizes, bit_depth, color_type,
                )
            return self._hand(
                pipe.pull, group, self._mesh_group,
                gid, tiles, rows, row_bytes, bpp, filter_mode, deflate_mode,
                lanes, sizes, bit_depth, color_type,
            )
        with self._stage("h2d", gid, n, chip):
            if staged:
                batch_dev = tiles
            else:
                batch_dev = jax.device_put(tiles)
                # blocking on the INPUT transfer only: earlier groups'
                # compute keeps the device busy meanwhile
                jax.block_until_ready(batch_dev)  # ompb-lint: disable=jax-hotpath -- H2D stage boundary: waits on the transfer engine, overlapped with earlier groups' compute
        if deflate_mode == "dynamic":
            from ..ops.device_deflate import fused_filter_histogram_batch

            flat, counts, extras, real_b = fused_filter_histogram_batch(
                batch_dev, rows, row_bytes, bpp, filter_mode=filter_mode,
                donate=self._donate_ok(),
            )
            # the stage starts at the launch, here; the plan worker
            # ends it when it has pulled the counts
            hist = self._stage("hist", gid, n, chip)
            self._note_group(hist.t0, n, group)
            # a plane-cache group arrives with its lane axis already
            # padded: the host plans only the real lanes, as it does
            # for a group it padded itself
            return self._hand(
                pipe.plan, group, self._plan_group,
                group, flat, counts, extras, min(real_b, n), hist, lanes,
                sizes, bit_depth, color_type,
            )
        from ..ops.device_deflate import fused_filter_deflate_batch

        streams, lengths = fused_filter_deflate_batch(
            batch_dev, rows, row_bytes, bpp,
            filter_mode=filter_mode, mode=deflate_mode,
            donate=self._donate_ok(),
        )
        compute = self._stage("compute", gid, n, chip)  # launch -> ready
        self._note_launch(compute.t0, n, group)
        return self._hand(
            pipe.pull, group, self._readback_group,
            group, streams, lengths, compute, lanes, sizes,
            bit_depth, color_type,
        )

    def _stage_render_group(
        self, group, planes, index_tables, color_luts, rows, row_bytes,
        filter_mode, deflate_mode, lanes, sizes, mask=None,
        staged=False,
    ):
        import jax

        gid, pull = group.gid, group.pipe.pull
        if self.mesh_manager is not None and not staged:
            # same rationale as the raw-tile mesh path: block inside
            # the managed dispatch so a sick chip degrades the mesh.
            # Masked groups ride along since the ROI mask became a
            # sharded operand of the render chain (the (B, H, W)
            # batch shards with its lanes); only staged
            # (device-resident) groups stay single-device — their
            # arrays already live on one chip.
            return self._hand(
                pull, group, self._mesh_render_group,
                gid, planes, index_tables, color_luts, rows, row_bytes,
                filter_mode, deflate_mode, lanes, sizes, mask,
            )
        from ..render.engine import fused_render_filter_deflate_batch

        n = len(lanes)
        with self._stage("h2d", gid, n):
            if staged:
                batch_dev, mask_dev = planes, mask
            else:
                batch_dev = jax.device_put(planes)
                mask_dev = None if mask is None else jax.device_put(mask)
                # blocking on the INPUT transfer only: earlier groups'
                # compute keeps the device busy meanwhile
                jax.block_until_ready(batch_dev)  # ompb-lint: disable=jax-hotpath -- H2D stage boundary: waits on the transfer engine, overlapped with earlier groups' compute
        streams, lengths = fused_render_filter_deflate_batch(
            batch_dev, index_tables, color_luts, rows, row_bytes,
            filter_mode=filter_mode, mode=deflate_mode,
            mask=mask_dev,
        )
        compute = self._stage("compute", gid, n)  # launch -> ready
        self._note_launch(compute.t0, n, group)
        return self._hand(
            pull, group, self._readback_group,
            group, streams, lengths, compute, lanes, sizes, 8, 2,
        )

    # -- mesh groups (pull worker) -------------------------------------

    def _mesh_render_group(
        self, gid, planes, index_tables, color_luts, rows, row_bytes,
        filter_mode, deflate_mode, lanes, sizes, mask=None,
    ):
        """One sharded render group on the pull worker (same
        pow2-then-mesh-width lane padding and blocking-dispatch
        semantics as ``_mesh_group``). ``mask`` (optional) is the
        (B, H, W) uint8 ROI batch — padded and sharded exactly like
        its lanes, so masked groups keep the full mesh width."""
        import jax
        import jax.numpy as jnp

        from ..parallel.sharding import (
            shard_batch,
            sharded_render_filter_deflate,
        )

        t0 = time.perf_counter()
        stamps = {}
        marks = _Marks("h2d", gid, len(lanes))

        def _pad_lanes(arr, padded_b):
            b = arr.shape[0]
            if padded_b == b:
                return arr
            return jnp.pad(
                arr, ((0, padded_b - b),) + ((0, 0),) * (arr.ndim - 1)
            )

        def run(mesh):
            n = mesh.shape["data"]
            b = planes.shape[0]
            padded_b = _mesh_padded_lanes(b, n)
            batch = _pad_lanes(jnp.asarray(planes), padded_b)
            sharded = shard_batch(mesh, batch)
            mask_sh = None
            if mask is not None:
                mask_sh = shard_batch(
                    mesh, _pad_lanes(jnp.asarray(mask), padded_b)
                )
            jax.block_until_ready(sharded)  # ompb-lint: disable=jax-hotpath -- H2D stage boundary on the pull worker
            stamps["h2d"] = marks.next("compute")
            out = sharded_render_filter_deflate(
                mesh, sharded, index_tables, color_luts, rows,
                row_bytes, filter_mode=filter_mode,
                deflate_mode=deflate_mode, mask=mask_sh,
            )
            return jax.block_until_ready(out)  # ompb-lint: disable=jax-hotpath -- pull worker: the one thread that waits on device completion

        try:
            streams, lengths = self.mesh_manager.dispatch(
                run, real_lanes=len(lanes), tag="render"
            )
        finally:
            marks.close()
        t_ready = time.perf_counter()
        t_h2d = stamps.get("h2d", t0)
        # noted AFTER the managed dispatch returns: dispatch() may
        # re-invoke run() once on a probe-shrink retry, and the queue
        # telemetry must count each submitted group exactly once
        self._note_launch(t_h2d, len(lanes))
        _observe_stage(t_h2d - t0, "h2d")
        _observe_stage(t_ready - t_h2d, "compute")
        self._note_compute_done(t_ready, t_ready - t_h2d)
        return self._pull_and_frame(
            streams, lengths, gid, lanes, sizes, 8, 2
        )

    def _mesh_group(
        self, gid, tiles, rows, row_bytes, bpp, filter_mode, deflate_mode,
        lanes, sizes, bit_depth, color_type,
    ):
        """One sharded group on the pull worker: pad pow2 (the
        same per-shape jit-specialization cap the single-device path
        has, then up to the healthy mesh width), shard, run the fused
        chain, and BLOCK inside the managed dispatch so a sick chip's
        failure is attributed to the mesh and degrades it."""
        import jax
        import jax.numpy as jnp

        from ..parallel.sharding import (
            shard_batch,
            sharded_filter_deflate,
        )

        t0 = time.perf_counter()
        stamps = {}
        marks = _Marks("h2d", gid, len(lanes))

        def run(mesh):
            n = mesh.shape["data"]
            b = tiles.shape[0]
            padded_b = _mesh_padded_lanes(b, n)
            batch = jnp.asarray(tiles)
            if padded_b != b:
                batch = jnp.pad(
                    batch,
                    ((0, padded_b - b),) + ((0, 0),) * (batch.ndim - 1),
                )
            sharded = shard_batch(mesh, batch)
            jax.block_until_ready(sharded)  # ompb-lint: disable=jax-hotpath -- H2D stage boundary on the pull worker
            stamps["h2d"] = marks.next("compute")
            out = sharded_filter_deflate(
                mesh, sharded, rows, row_bytes, bpp,
                filter_mode=filter_mode, deflate_mode=deflate_mode,
            )
            # block INSIDE the managed dispatch: a mid-compute chip
            # failure must raise here, where MeshManager probes and
            # shrinks, not at a later pull
            return jax.block_until_ready(out)  # ompb-lint: disable=jax-hotpath -- pull worker: the one thread that waits on device completion

        try:
            streams, lengths = self.mesh_manager.dispatch(
                run, real_lanes=len(lanes), tag="tiles"
            )
        finally:
            marks.close()
        t_ready = time.perf_counter()
        t_h2d = stamps.get("h2d", t0)
        # noted AFTER the managed dispatch returns: dispatch() may
        # re-invoke run() once on a probe-shrink retry, and the queue
        # telemetry must count each submitted group exactly once
        self._note_launch(t_h2d, len(lanes))
        _observe_stage(t_h2d - t0, "h2d")
        _observe_stage(t_ready - t_h2d, "compute")
        self._note_compute_done(t_ready, t_ready - t_h2d)
        return self._pull_and_frame(
            streams, lengths, gid, lanes, sizes, bit_depth,
            color_type,
        )

    def _mesh_dynamic_group(
        self, gid, tiles, rows, row_bytes, bpp, filter_mode,
        lanes, sizes, bit_depth, color_type,
    ):
        """Dynamic-Huffman on the mesh: the two-pass chain with the
        host Huffman-plan hop threaded BETWEEN two sharded programs —
        pass 1 (filter + histogram) sharded, the (B, 286) counts
        pulled (a few KB), the per-lane code tables built on host, and
        pass 2 (emit) sharded with every table array sharded alongside
        its lanes. Both passes run inside ONE managed dispatch: a chip
        failing in either pass (or the hop's pull) degrades the mesh
        through the same probe-shrink-retry, and the retry re-runs the
        whole two-pass chain on the survivors. Pad lanes keep the
        prefilled fixed tables, exactly like the single-device path,
        so mesh dynamic bytes == single-device dynamic bytes."""
        import jax
        import jax.numpy as jnp

        from ..ops.device_deflate import build_dynamic_tables
        from ..parallel.sharding import (
            shard_batch,
            sharded_dynamic_emit,
            sharded_filter_histogram,
        )

        t0 = time.perf_counter()
        stamps = {}
        marks = _Marks("h2d", gid, len(lanes))

        def run(mesh):
            n = mesh.shape["data"]
            b = tiles.shape[0]
            padded_b = _mesh_padded_lanes(b, n)
            batch = jnp.asarray(tiles)
            if padded_b != b:
                batch = jnp.pad(
                    batch,
                    ((0, padded_b - b),) + ((0, 0),) * (batch.ndim - 1),
                )
            sharded = shard_batch(mesh, batch)
            jax.block_until_ready(sharded)  # ompb-lint: disable=jax-hotpath -- H2D stage boundary on the pull worker
            stamps["h2d"] = marks.next("hist")
            flat, counts, extras = sharded_filter_histogram(
                mesh, sharded, rows, row_bytes, bpp,
                filter_mode=filter_mode,
            )
            counts_np, extras_np = jax.device_get((counts, extras))  # ompb-lint: disable=jax-hotpath -- pull worker: the dynamic host hop (pass-1 counts, a few KB)
            stamps["hist"] = marks.next("emit")
            tables = build_dynamic_tables(counts_np, extras_np, real=b)
            out = sharded_dynamic_emit(mesh, flat, tables)
            return jax.block_until_ready(out)  # ompb-lint: disable=jax-hotpath -- pull worker: the one thread that waits on device completion

        try:
            streams, lengths = self.mesh_manager.dispatch(
                run, real_lanes=len(lanes), tag="dynamic"
            )
        finally:
            marks.close()
        t_ready = time.perf_counter()
        t_h2d = stamps.get("h2d", t0)
        t_hist = stamps.get("hist", t_h2d)
        self._note_launch(t_h2d, len(lanes))
        self._note_plan(len(lanes))  # once, however often a retry planned
        _observe_stage(t_h2d - t0, "h2d")
        _observe_stage(t_hist - t_h2d, "hist")
        _observe_stage(t_ready - t_hist, "emit")
        self._note_compute_done(t_ready, t_ready - t_h2d)
        return self._pull_and_frame(
            streams, lengths, gid, lanes, sizes, bit_depth,
            color_type,
        )

    # -- mesh-fused super-tile (pull worker) ---------------------------

    def submit_supertile(
        self,
        stack,
        index_tables,
        color_luts,
        rel_rects: Sequence[Tuple[int, int, int, int]],
        tile_w: int,
        tile_h: int,
        filter_mode: str,
        deflate_mode: str,
        lanes: Sequence[int],
    ) -> "concurrent.futures.Future":
        """Enqueue one mesh-fused SUPER-TILE group: ``stack`` is the
        staged (C, H, W) unsigned bounding-rect stack (host ndarray),
        ``rel_rects`` the lanes' (x, y, w, h) rectangles relative to
        it — one homogeneous (tile_w, tile_h) size class. The whole
        composite + carve + filter + deflate chain runs as ONE sharded
        program over per-chip overlapped sub-rect windows
        (render/supertile.plan_mesh_partition carves them INSIDE the
        managed dispatch, so a probe-shrink retry re-plans for the
        surviving width). Resolves to {lane_index: png_bytes}."""
        return self._enqueue(
            self._stage_supertile_group,
            stack, index_tables, color_luts, list(rel_rects),
            tile_w, tile_h, filter_mode, deflate_mode, list(lanes),
        )

    def _stage_supertile_group(
        self, group, stack, index_tables, color_luts, rel_rects,
        tile_w, tile_h, filter_mode, deflate_mode, lanes,
    ):
        # mesh-only entry point (the pipeline routes single-device
        # groups through composite_carve_batch + submit instead);
        # like every sharded group it runs wholly on the pull
        # worker so the blocking dispatch stays inside MeshManager
        return self._hand(
            group.pipe.pull, group, self._mesh_supertile_group,
            group.gid, stack, index_tables, color_luts, rel_rects,
            tile_w, tile_h, filter_mode, deflate_mode, lanes,
        )

    def _mesh_supertile_group(
        self, gid, stack, index_tables, color_luts, rel_rects,
        tile_w, tile_h, filter_mode, deflate_mode, lanes,
    ):
        """One mesh-fused super-tile on the pull worker: plan the
        per-chip overlapped windows, slice them out of the staged
        stack, and run composite + carve + filter + deflate as one
        sharded program. The result rows come back chip-major with
        pow2 slot padding interleaved, so the pull selects the real
        rows through the partition's row map instead of the leading-
        rows convention ``_pull_and_frame`` assumes."""
        import jax
        import jax.numpy as jnp

        from ..ops.png import frame_png
        from ..parallel.sharding import sharded_supertile_carve_deflate
        from ..render.supertile import plan_mesh_partition

        t0 = time.perf_counter()
        stamps = {}
        n_lanes = len(lanes)
        marks = _Marks("h2d", gid, n_lanes)
        c, stack_h, stack_w = stack.shape

        def run(mesh):
            # plan INSIDE the managed dispatch: a probe-shrink retry
            # re-invokes run() with the survivors' mesh, and the
            # partition must match the actual width
            n = mesh.shape["data"]
            origins, (sub_h, sub_w), coords, rows_map = (
                plan_mesh_partition(rel_rects, stack_h, stack_w, n)
            )
            sub = np.stack([
                stack[:, sy : sy + sub_h, sx : sx + sub_w]
                for (sy, sx) in origins
            ])
            sub_dev = jnp.asarray(sub)
            coords_dev = jnp.asarray(coords)
            jax.block_until_ready(sub_dev)  # ompb-lint: disable=jax-hotpath -- H2D stage boundary on the pull worker
            stamps["h2d"] = marks.next("compute")
            out = sharded_supertile_carve_deflate(
                mesh, sub_dev, index_tables, color_luts, coords_dev,
                tile_h, tile_w, filter_mode=filter_mode,
                deflate_mode=deflate_mode,
            )
            out = jax.block_until_ready(out)  # ompb-lint: disable=jax-hotpath -- pull worker: the one thread that waits on device completion
            return out, rows_map

        try:
            (streams, lengths), rows_map = self.mesh_manager.dispatch(
                run, real_lanes=len(lanes), tag="supertile"
            )
        finally:
            marks.close()
        t_ready = time.perf_counter()
        t_h2d = stamps.get("h2d", t0)
        self._note_launch(t_h2d, len(lanes))
        _observe_stage(t_h2d - t0, "h2d")
        _observe_stage(t_ready - t_h2d, "compute")
        self._note_compute_done(t_ready, t_ready - t_h2d)
        # custom pull: the real rows are scattered chip-major through
        # the slot padding, so pull the (tiny) lengths first, then the
        # kept rows' streams bounded by their true max
        with self._stage("d2h", gid, n_lanes):
            sel = np.asarray(rows_map, dtype=np.int64)
            lengths_np = np.asarray(jax.device_get(lengths))[sel]  # ompb-lint: disable=jax-hotpath -- pull worker: lengths pull, a few bytes per lane
            full_cap = streams.shape[1]
            max_len = int(lengths_np.max()) if len(lanes) else 0
            cap = min(full_cap, 1 << max(max_len - 1, 0).bit_length())
            streams_np = np.asarray(
                jax.device_get(streams[:, :cap])  # ompb-lint: disable=jax-hotpath -- pull worker: the one bounded streams pull for the group
            )[sel]
            with self._stats_lock:
                self._dd_cap[(tile_w, tile_h)] = min(
                    full_cap, 1 << max(2 * max_len - 1, 0).bit_length()
                )
        out: Dict[int, bytes] = {}
        with self._stage("frame", gid, n_lanes):
            for j, lane in enumerate(lanes):
                out[lane] = frame_png(
                    streams_np[j, : int(lengths_np[j])].tobytes(),
                    tile_w, tile_h, 8, 2,
                )
        return out

    # -- mesh-resize jit warmup ----------------------------------------

    def _register_mesh_shape(
        self, tiles, rows, row_bytes, bpp, filter_mode, deflate_mode
    ) -> None:
        """Remember a raw-tile mesh group's jit-relevant shape so a
        later mesh WIDTH change can pre-warm its specialization."""
        key = (
            tuple(tiles.shape[1:]), np.dtype(tiles.dtype).str,
            _pow2_lanes(tiles.shape[0]),
            rows, row_bytes, bpp, filter_mode, deflate_mode,
        )
        with self._warm_lock:
            self._seen_mesh[key] = None
            while len(self._seen_mesh) > _WARM_SHAPES:
                self._seen_mesh.pop(next(iter(self._seen_mesh)))

    def _on_mesh_width(self, width: int) -> None:
        """MeshManager width listener: a probe-shrink or heal changed
        the healthy chip count, so every known group shape's padded
        batch width — and therefore its jit specialization — changed.
        Compile them NOW on a background thread instead of inside the
        first serving dispatch on the resized mesh."""
        with self._warm_lock:
            shapes = [
                k for k in self._seen_mesh
                if (width, k) not in self._warmed
            ]
        if not shapes or self._closed:
            return
        t = threading.Thread(
            target=self._warm_width,
            args=(width, shapes),
            name="devenc-mesh-warm",
            daemon=True,
        )
        t.start()
        self._warm_thread = t  # tests join this

    def _warm_width(self, width: int, shapes: List[tuple]) -> None:
        import jax
        import jax.numpy as jnp

        from ..ops.device_deflate import build_dynamic_tables
        from ..parallel.sharding import (
            shard_batch,
            sharded_dynamic_emit,
            sharded_filter_deflate,
            sharded_filter_histogram,
        )

        for key in shapes:
            (lane_shape, dtype_str, pow2_b, rows, row_bytes, bpp,
             filter_mode, deflate_mode) = key
            try:
                mesh = self.mesh_manager.mesh()
                n = mesh.shape["data"]
                if n != width:
                    return  # the mesh moved again; a fresh warmup owns it
                padded_b = _mesh_padded_lanes(pow2_b, n)
                batch = jnp.zeros(
                    (padded_b,) + lane_shape, dtype=np.dtype(dtype_str)
                )
                sharded = shard_batch(mesh, batch)
                if deflate_mode == "dynamic":
                    # the serving path is TWO sharded programs; warm
                    # both (sharded_filter_deflate would compile a
                    # program dynamic groups never run)
                    flat, counts, extras = sharded_filter_histogram(
                        mesh, sharded, rows, row_bytes, bpp,
                        filter_mode=filter_mode,
                    )
                    counts_np, extras_np = jax.device_get((counts, extras))  # ompb-lint: disable=jax-hotpath -- background warmup thread: compiles ahead of the serving path
                    tables = build_dynamic_tables(
                        counts_np, extras_np, real=0
                    )
                    out = sharded_dynamic_emit(mesh, flat, tables)
                else:
                    out = sharded_filter_deflate(
                        mesh, sharded, rows, row_bytes, bpp,
                        filter_mode=filter_mode,
                        deflate_mode=deflate_mode,
                    )
                jax.block_until_ready(out)  # ompb-lint: disable=jax-hotpath -- background warmup thread: compiles ahead of the serving path
                with self._warm_lock:
                    self._warmed.add((width, key))
                log.info(
                    "pre-warmed mesh width %d for group shape %s",
                    width, lane_shape,
                )
            except Exception:
                log.exception("mesh warmup failed for %s", key)

    # -- plan (plan worker) --------------------------------------------

    def _plan_group(
        self, group: _Group, flat, counts, extras, real_b, hist: _Span,
        lanes, sizes, bit_depth, color_type,
    ) -> None:
        """Dynamic mode between its passes, on the plan worker: pull
        the pass-1 counts (absorbing the histogram program's wait),
        build the canonical code tables on host (real lanes only — pad
        lanes keep the fixed defaults), launch the emit program and
        hand the group to the pull worker WITHOUT waiting for it: the
        next group's plan runs while the chip emits this one."""
        import jax

        from ..ops.device_deflate import (
            build_dynamic_tables,
            dynamic_emit_planned,
        )

        gid, n, chip = group.gid, hist.lanes, group.chip
        with hist:  # started at the launch, on the submit thread
            counts_np, extras_np = jax.device_get((counts, extras))  # ompb-lint: disable=jax-hotpath -- plan worker: the dynamic host hop (pass-1 counts, a few KB); the one wait of this thread
        with self._stage("plan", gid, n, chip):
            tables = build_dynamic_tables(counts_np, extras_np, real=real_b)
        self._note_plan(real_b)
        streams, lengths = dynamic_emit_planned(flat, tables, real=real_b)
        # launch -> seen done; the pull worker ends it
        emit = self._stage("emit", gid, n, chip)
        self._note_last_launch(emit.t0, group)
        self._hand(
            group.pipe.pull, group, self._readback_group,
            group, streams, lengths, emit, lanes, sizes, bit_depth,
            color_type,
        )

    # -- readback (pull worker) ----------------------------------------

    def _readback_group(
        self, group: _Group, streams, lengths, last: _Span, lanes, sizes,
        bit_depth, color_type,
    ) -> Dict[int, bytes]:
        """Runs on the pull worker: wait for the group's last program
        (``last``: a single-pass group's ``compute``, a dynamic
        group's ``emit``), give the slot back — the device is done
        with the group, what is left is the host's — pull the
        compressed bytes in ONE sync, frame the PNGs."""
        import jax

        # intended stage boundary: this thread EXISTS to absorb the
        # device wait so submitters and planners never do
        with last:  # started at the launch, on the submit or plan worker
            jax.block_until_ready((streams, lengths))  # ompb-lint: disable=jax-hotpath -- pull worker: the one thread that waits on device completion
        self._device_done(group, last.t1)
        return self._pull_and_frame(
            streams, lengths, last.gid, lanes, sizes, bit_depth,
            color_type, group.chip,
        )

    def _pull_and_frame(
        self, streams, lengths, gid, lanes, sizes, bit_depth,
        color_type, chip=None,
    ) -> Dict[int, bytes]:
        """Shared tail: pull the compressed bytes in ONE sync, frame
        the PNGs on the host. A group that names its chip pulls its
        lanes' whole rows; the others cut to the adaptive size guess
        first (`_pull_to_guess`). The guess follows the data, so the
        slice that cuts to it is a program of its own a size (and a
        lane count, and a device), which no warm-up can have compiled
        on every chip beforehand; a row is 0.6 MB, and on a named chip
        nothing compiles while serving."""
        import jax

        from ..ops.png import frame_png

        real = len(lanes)
        with self._stage("d2h", gid, real, chip):
            if chip is not None:
                lengths_np, streams_np = jax.device_get((lengths, streams))  # ompb-lint: disable=jax-hotpath -- pull worker: the one pull of the group
            else:
                lengths_np, streams_np = self._pull_to_guess(
                    streams, lengths, real, sizes[0]
                )
        out: Dict[int, bytes] = {}
        with self._stage("frame", gid, real, chip):
            for j, lane in enumerate(lanes):
                out[lane] = frame_png(
                    streams_np[j, : int(lengths_np[j])].tobytes(),
                    sizes[j][0], sizes[j][1], bit_depth, color_type,
                )
        return out

    def _pull_to_guess(self, streams, lengths, real: int, size):
        """(lengths, streams) of the real lanes on the host, the
        streams cut on the device to the adaptive pow2 cap of their
        (w, h) first: one pull, a second only when a stream outgrew
        the guess."""
        import jax

        w, h = size
        full_cap = streams.shape[1]
        # _dd_cap is shared with host-fallback paths on other
        # threads; the stats lock makes the read-update pair
        # coherent (r14 lock-discipline burndown — was a documented
        # KNOWN_GAPS item)
        with self._stats_lock:
            cap_hint = self._dd_cap.get(
                (w, h), 1 << max(full_cap // 4, 64).bit_length()
            )
        guess = min(cap_hint, full_cap)
        lengths_np, streams_np = jax.device_get(
            (lengths[:real], streams[:real, :guess])
        )
        max_len = int(lengths_np.max()) if real else 0
        if max_len > guess:
            cap = min(full_cap, 1 << max(max_len - 1, 0).bit_length())
            # guess overflow: one extra pull, rare by construction
            # (the cap tracks the running max)
            streams_np = np.asarray(streams[:real, :cap])  # ompb-lint: disable=jax-hotpath -- guess-overflow path: a second bounded pull, not a per-lane sync
        with self._stats_lock:
            self._dd_cap[(w, h)] = min(
                full_cap, 1 << max(2 * max_len - 1, 0).bit_length()
            )
        return lengths_np, streams_np
