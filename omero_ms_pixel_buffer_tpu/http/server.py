"""HTTP front — routes, session adoption, error mapping, headers.

Replaces the reference's front verticle
(PixelBufferMicroserviceVerticle.java):

- ``GET /metrics`` — Prometheus text, registered before auth (order -2,
  :238-240), unauthenticated;
- ``OPTIONS *`` — microservice discovery JSON
  {provider, version, features} (:315-327);
- router-wide tracing span tagged ``omero.session_key`` (:242-251);
- router-wide OMERO.web session adoption: ``sessionid`` cookie ->
  session store -> ``omero.session_key`` or 403 (:275-276);
- ``GET /tile/:imageId/:z/:c/:t`` -> TileCtx parse (400 with message on
  failure, :340-348) -> event-bus request with send timeout (:352-354)
  -> response assembly: Content-Type by format, Content-Length,
  Content-Disposition attachment with the reply's filename header
  (:372-392); failures map via failureCode (404 default, <1 -> 500,
  :356-370).
"""

from __future__ import annotations

import asyncio
import contextvars
import logging
import threading
import time
from typing import Optional

from aiohttp import web

from .. import __version__
from ..auth.omero_session import (
    AllowListValidator,
    IceSessionValidator,
    SessionValidator,
)
from ..auth.stores import OmeroWebSessionStore, make_session_store
from ..cache.plane.peer import (
    EPOCH_HEADER,
    KEY_HEADER,
    PEER_HEADER,
    TRACE_HEADER,
    TRACE_PARENT_HEADER,
)
from ..cluster.security import SIG_HEADER, NonceCache
from ..cluster.security import verify as verify_cluster_sig
from ..cache.prefetch import ViewportPrefetcher
from ..cache.result_cache import (
    CachedTile,
    TileResultCache,
    etag_matches,
)
from ..dispatch.batcher import BatchingTileWorker
from ..dispatch.bus import GET_TILE_EVENT, EventBus, Message
from ..errors import (
    ServiceUnavailableError,
    TileError,
    http_status_for_failure,
)
from ..io.pixels_service import ImageRegistry, PixelsService
from ..models.tile_pipeline import TilePipeline
from ..obs import FlightRecorder, SliLayer
from ..obs import recorder as obs_recorder
from ..io.fetch import configure as configure_fetch
from ..io.fetch import io_snapshot
from ..resilience import AdmissionController, Deadline
from ..resilience import configure as configure_resilience
from ..resilience.breaker import BOARD
from ..resilience.scheduler import (
    PRIORITY_INTERACTIVE,
    PRIORITY_NAMES,
    SloScheduler,
    SweepDetector,
    classify,
    header_priority,
)
from ..tile_ctx import TileCtx
from ..utils.config import Config
from ..utils.loop_watchdog import LoopWatchdog
from ..utils.metrics import REGISTRY
from ..utils.process_metrics import mark_loop_thread
from ..utils.tracing import TRACER, configure as configure_tracing

log = logging.getLogger("omero_ms_pixel_buffer_tpu.http")

# /healthz?probe=1 rate floor: the endpoint is unauthenticated, so
# active dependency probes are throttled to one round per interval no
# matter the request rate (amplification / breaker-poisoning guard)
_PROBE_MIN_INTERVAL_S = 5.0

CONTENT_TYPES = {
    None: "application/octet-stream",
    "png": "image/png",
    "tif": "image/tiff",
    "jpeg": "image/jpeg",
    "json": "application/json",  # histogram bodies (render/analysis)
}

# The serving lanes the admission machinery gates (binary gate, SLO
# door gate, scheduler classification): the native endpoints AND every
# protocol-adapter surface — an adapter request is the same pipeline
# work in a different grammar, so it must shed/degrade/504 exactly
# like a native one. Discovery, metrics, and health stay ungated.
SERVING_PREFIXES = (
    "/tile/", "/render/", "/histogram/", "/dzi/", "/iiif/", "/iris/",
)


async def handle_metrics(request: web.Request) -> web.Response:
    # content negotiation: scrapers asking for OpenMetrics get the
    # exemplar-carrying dialect (metric -> trace pivots); everything
    # else gets the byte-stable classic Prometheus text
    accept = request.headers.get("Accept", "")
    if "application/openmetrics-text" in accept:
        return web.Response(
            body=REGISTRY.exposition(openmetrics=True).encode(),
            content_type="application/openmetrics-text",
            charset="utf-8",
        )
    return web.Response(
        text=REGISTRY.exposition(),
        content_type="text/plain",
        charset="utf-8",
    )


async def handle_options(request: web.Request) -> web.Response:
    # getMicroserviceDetails (:315-327)
    return web.json_response(
        {
            "provider": "PixelBufferMicroservice",
            "version": __version__,
            "features": [],
        }
    )


def obs_middleware(app_obj: "PixelBufferApp"):
    """The flight recorder's door (outermost middleware, before the
    overload gate and session auth, so door sheds and 403s record
    too): mint one ``FlightRecord`` per serving request, make it the
    ambient record for the request's task, and complete it — total,
    stage histograms, SLI accounting, the tail-sampling decision —
    when the response (or the exception) comes back.

    Peer-hop continuity: a request carrying the cache plane's
    ``X-OMPB-Peer`` marker may also carry ``X-OMPB-Trace-Id`` — the
    requester's trace — and the owner's record JOINS it instead of
    minting its own, so one trace spans both replicas. Adoption is
    gated on the peer marker: the trace headers ride the same
    network-trust internal surface as ``/internal/*`` (deploy-time
    network policy, documented in ARCHITECTURE)."""

    @web.middleware
    async def middleware(request: web.Request, handler):
        recorder = app_obj.recorder
        if (
            recorder is None
            or not recorder.enabled
            or not request.path.startswith(SERVING_PREFIXES)
            or request.method == "OPTIONS"
        ):
            return await handler(request)
        trace_id = parent = None
        if PEER_HEADER in request.headers and _peer_claim_verified(
            app_obj, request
        ):
            # adopt the forwarded trace only when it LOOKS like one of
            # ours (lowercase hex): a malformed id would poison the
            # deterministic keep-hash and every downstream exposition.
            # With cluster.secret configured the peer claim must ALSO
            # carry a valid signature — this middleware runs OUTSIDE
            # the cluster guard (so the guard's 403s complete records)
            # and must not adopt attacker-chosen trace ids from a
            # request the guard is about to reject
            trace_id = _valid_trace_id(
                request.headers.get(TRACE_HEADER)
            )
            parent = _valid_trace_id(
                request.headers.get(TRACE_PARENT_HEADER), 16
            )
        rec = recorder.start(
            request.path, request.method,
            trace_id=trace_id, parent_span_id=parent,
        )
        if rec is None:
            return await handler(request)
        if trace_id is not None:
            rec.peer_origin = request.headers.get(PEER_HEADER)
        request["obs.rec"] = rec
        status = 500
        try:
            with obs_recorder.record_scope(rec):
                response = await handler(request)
            status = response.status
            degraded = response.headers.get("X-OMPB-Degraded")
            if degraded:
                rec.tag("degraded", int(degraded))
            x_cache = response.headers.get("X-Cache")
            if x_cache:
                rec.tag("cache", x_cache)
            return response
        except web.HTTPException as e:
            # router-raised responses (404 on an unroutable /tile/...
            # path, 405 on a bad method) are CLIENT outcomes — without
            # this they'd complete as 500s, force-keep into the ring,
            # and burn the SLI error budget on scanner noise
            status = e.status
            raise
        finally:
            recorder.complete(rec, status)

    return middleware


def _peer_claim_verified(app_obj, request: web.Request) -> bool:
    """Whether a peer-marked request's cluster identity checks out
    for trust decisions made OUTSIDE the guard middleware (trace
    adoption). Serving-path peer hops are bodiless GETs, so the
    signature verifies over an empty body. Without a secret the r11
    posture holds: network policy is the boundary."""
    secret = app_obj.config.cluster.secret
    if not secret:
        return True
    return app_obj.verify_cluster_request(request, b"")


def _parse_epoch(value):
    """The forwarded image epoch, or None when absent/malformed."""
    try:
        return int(value) if value is not None else None
    except (TypeError, ValueError):
        return None


def _valid_trace_id(value, length: int = 32):
    """The forwarded trace/span id, or None when absent/malformed
    (ids this service mints are fixed-width lowercase hex)."""
    if (
        isinstance(value, str)
        and len(value) == length
        and all(c in "0123456789abcdef" for c in value)
    ):
        return value
    return None


@web.middleware
async def tracing_middleware(request: web.Request, handler):
    rec = request.get("obs.rec")
    if rec is not None and TRACER.enabled:
        # live tracing joins the flight record's trace, so a span in
        # Zipkin and a wide event in the ring share one trace id (and
        # a peer-forwarded trace id reaches the spans too)
        span = TRACER.start_span_with_context(
            f"http:{request.path}",
            {"traceId": rec.trace_id, "spanId": rec.parent_span_id},
        )
        if span.span_id is not None:
            # the record's span id is what the peer hop propagates as
            # the owner's parent (coordinator.fetch) — the LIVE root
            # span must carry the same id or the owner's spans parent
            # to an id no exported span ever has
            span.span_id = rec.span_id
    else:
        span = TRACER.start_span(f"http:{request.path}")
    request["span"] = span
    with span:
        try:
            return await handler(request)
        finally:
            # session middleware runs after us (the reference's order
            # -1 tracing handler also precedes auth); tag at finish
            key = request.get("omero.session_key")
            if key:
                span.tag("omero.session_key", key)


def session_middleware(store: OmeroWebSessionStore, synchronicity: str = "async"):
    """OmeroWebSessionRequestHandler analog: resolve the ``sessionid``
    cookie to an OMERO session key; 403 when absent/unknown. /metrics
    and OPTIONS are registered before auth in the reference and stay
    open here.

    ``synchronicity: sync`` is accepted for config compatibility with
    the reference (config.yaml:25-26) but no longer serializes: the
    store implementations here are genuinely async (their own
    per-connection locking is the correctness boundary), and the old
    one-lookup-at-a-time lock meant ONE slow session check queued
    every other request's auth behind it — the KNOWN_GAPS
    "Operational" item. Lookups now always run concurrently; the key
    logs a deprecation warning once at startup.

    Failure split (resilience layer): an unknown session is 403; a
    session store that cannot ANSWER — open breaker, connection
    refused — is 503 + Retry-After. Auth unavailable must never read
    as auth denied, or a Redis blip logs every user out."""
    if synchronicity == "sync":
        log.warning(
            "session-store.synchronicity: sync no longer serializes "
            "lookups (the async stores handle their own connection "
            "locking); the key is accepted for compatibility only"
        )

    @web.middleware
    async def middleware(request: web.Request, handler):
        if request.path in ("/metrics", "/healthz") or (
            request.path.startswith(("/internal/", "/debug/"))
            or request.method == "OPTIONS"
        ):
            # /internal/* is the peer-to-peer surface (cache plane
            # purge fan-out): peers carry no browser session, and the
            # handlers only drop caches (re-renders produce identical
            # bytes) — deploy-time network policy, not session auth,
            # is the trust boundary there (deploy/nginx.conf.sample).
            # /debug/* (the flight-recorder ring) is the same class of
            # internal surface: operators reach it from inside the
            # perimeter exactly when the session stack may be the
            # thing that's broken.
            return await handler(request)
        session_id = request.cookies.get("sessionid")
        if not session_id:
            return web.Response(status=403, text="Permission denied")
        try:
            # ambient_stage: no-op without a flight record, one
            # lookup call either way
            with obs_recorder.ambient_stage("auth"):
                key = await store.get_omero_session_key(session_id)
        except ServiceUnavailableError as e:
            return web.Response(
                status=503, text="Session store unavailable",
                headers={"Retry-After": _retry_after(e.retry_after_s)},
            )
        except (ConnectionError, OSError, asyncio.TimeoutError) as e:
            log.warning("session store lookup failed: %s", e)
            return web.Response(
                status=503, text="Session store unavailable",
                headers={"Retry-After": "1"},
            )
        if not key:
            return web.Response(status=403, text="Permission denied")
        request["omero.session_key"] = key
        return await handler(request)

    return middleware


def _retry_after(seconds: float) -> str:
    """Retry-After is an integer number of seconds; round up so the
    client never probes before the window opens."""
    return str(max(1, int(seconds + 0.999)))


def admission_middleware(admission: AdmissionController):
    """The LEGACY binary gate (resilience/admission): beyond the
    in-flight bound, tile/render requests answer 503 + Retry-After
    immediately instead of queueing toward a bus timeout. Installed
    only with ``slo.enabled: false`` — the default serving path
    replaced it with the SLO scheduler (resilience/scheduler), which
    gates the *miss* path per priority class and queues deadline-
    ordered instead of shedding at the door. Only the serving lanes
    are gated — discovery, metrics, and health must stay reachable
    precisely when the service is saturated."""

    @web.middleware
    async def middleware(request: web.Request, handler):
        if (
            not request.path.startswith(SERVING_PREFIXES)
            or request.method == "OPTIONS"  # discovery/CORS preflight
        ):
            return await handler(request)
        if not admission.try_acquire():
            return web.Response(
                status=503, text="Service overloaded",
                headers={
                    "Retry-After": _retry_after(admission.retry_after_s)
                },
            )
        try:
            return await handler(request)
        finally:
            admission.release()

    return middleware


def overload_gate_middleware(app_obj: "PixelBufferApp"):
    """The scheduler-era door gate (outermost, BEFORE the session
    middleware): when the SLO wait queue is genuinely full and the
    arrival's class would shed at ``acquire`` anyway, answer 503 now —
    true overload must not convert into a session-store lookup plus a
    cluster-cache (L2/peer) consult per excess request, or sustained
    overload saturates the dependencies and takes down the cache-hit
    traffic the scheduler is designed to keep serving (the r6
    admission middleware's dependency-protection property).

    Exemptions: local result-cache HITS pass through — serving a hit
    costs no execution slot (the scheduler only gates misses), so
    shedding it at the door would be a pure loss. The probe is the
    pre-auth content key against the RAM/disk index; /render requests
    skip the probe (their key needs the spec parse the handler owns)
    and door-shed like any other would-shed arrival. Classification
    here is header-only (the sweep detector keys on the authenticated
    session, which does not exist yet): an unlabeled robot sweep
    passes the door and sheds at ``acquire`` after auth instead."""

    @web.middleware
    async def middleware(request: web.Request, handler):
        sched = app_obj.scheduler
        if (
            sched is None
            or not request.path.startswith(SERVING_PREFIXES)
            or request.method == "OPTIONS"  # discovery/CORS preflight
        ):
            return await handler(request)
        rec = request.get("obs.rec")
        t_door = time.perf_counter()
        priority = classify(
            request.headers, None, None, app_obj._priority_header
        )
        if not sched.would_overflow_shed(priority):
            if rec is not None:
                rec.stamp("door", time.perf_counter() - t_door)
            return await handler(request)
        cache = app_obj.result_cache
        if cache is not None and request.path.startswith(
            ("/tile/", "/render/")
        ):
            probe_key = app_obj._door_probe_key(request)
            if probe_key is not None and cache.contains_any_tier(
                probe_key
            ):
                if rec is not None:
                    rec.stamp("door", time.perf_counter() - t_door)
                return await handler(request)
        sched.shed_at_door(priority)
        if rec is not None:
            rec.stamp("door", time.perf_counter() - t_door)
            rec.tag("priority", PRIORITY_NAMES[priority])
            rec.tag("shed_at", "door")
        return web.Response(
            status=503, text="Service overloaded",
            headers={
                "Retry-After": _retry_after(
                    app_obj.admission.retry_after_s
                )
            },
        )

    return middleware


def cluster_guard_middleware(app_obj: "PixelBufferApp"):
    """The peer-surface authentication gate (cluster/security). Two
    request classes claim cluster identity: ``/internal/*`` (purge
    fan-out, replica push, warm-up transfer) and anything carrying the
    ``X-OMPB-Peer`` marker (the owner hop, whose marker short-circuits
    L2 re-checks and is what the trace-adoption trust rides on).

    With ``cluster.secret`` configured, BOTH must present a valid
    ``X-OMPB-Sig`` — HMAC over (method, path?query, timestamp,
    body-digest), constant-time compared, clock-skew bounded — or they
    answer 403 before any handler runs. Without a secret the previous
    posture holds: ``/internal/*`` requires the peer marker and
    deploy-time network policy is the boundary (KNOWN_GAPS documents
    the residual trust). Normal browser traffic never carries either
    marker and never pays this check."""

    @web.middleware
    async def middleware(request: web.Request, handler):
        secret = app_obj.config.cluster.secret
        is_internal = request.path.startswith("/internal/")
        claims_peer = PEER_HEADER in request.headers
        if not (is_internal or claims_peer):
            return await handler(request)
        if secret:
            body = b""
            if request.can_read_body:
                # aiohttp memoizes the payload: the handler's own
                # read() gets the same bytes back
                body = await request.read()
            if not app_obj.verify_cluster_request(request, body):
                return web.Response(
                    status=403, text="invalid cluster signature"
                )
            if claims_peer and app_obj.cache_plane is not None:
                # gossip-native join hint (r22): the peer marker
                # carries the sender's serving URL INSIDE the HMAC,
                # so a verified contact in either direction teaches
                # this replica a member address — an out-of-seed
                # joiner bootstraps from its first signed exchange,
                # no Redis required. Unverified requests never reach
                # here; non-URL markers are ignored downstream.
                app_obj.cache_plane.note_peer_contact(
                    request.headers.get(PEER_HEADER, "")
                )
        elif is_internal and not claims_peer:
            return web.Response(status=403, text="peer requests only")
        return await handler(request)

    return middleware


def quality_middleware(app_obj: "PixelBufferApp"):
    """Serve-quality accounting for the suspicion signal
    (cluster/suspect.QualityTracker): every serving-path completion —
    hits, misses, sheds, guard 403s, router 404s — notes its status
    and wall latency. Installed OUTERMOST (outside even the flight
    recorder) only when the cluster plane is on; a replica whose
    front is melting down must not be able to hide it from the
    fleet by failing before the bookkeeping."""

    @web.middleware
    async def middleware(request: web.Request, handler):
        quality = app_obj.quality
        if (
            quality is None
            or not request.path.startswith(SERVING_PREFIXES)
            or request.method == "OPTIONS"
        ):
            return await handler(request)
        t0 = time.perf_counter()
        status = 500
        try:
            response = await handler(request)
            status = response.status
            return response
        except web.HTTPException as e:
            status = e.status
            raise
        except asyncio.CancelledError:
            # a client hanging up mid-request (viewport pan aborting
            # its tile fetches) says nothing about THIS replica's
            # health — counting it as a 500 would let an aggressive
            # viewer's aborts quorum-demote a healthy replica
            status = None
            raise
        finally:
            if status is not None:
                quality.note(status, time.perf_counter() - t0)

    return middleware


class PixelBufferApp:
    """Wires config -> session store -> pixels service -> pipeline ->
    batching worker -> bus -> routes (the deploy() analog,
    PixelBufferMicroserviceVerticle.java:145-292)."""

    def __init__(
        self,
        config: Config,
        pixels_service: Optional[PixelsService] = None,
        session_store: Optional[OmeroWebSessionStore] = None,
        session_validator: Optional[SessionValidator] = None,
    ):
        self.config = config
        # resilience policy FIRST: breakers minted by the stores /
        # clients below pick up the configured thresholds
        configure_resilience(config.resilience)
        # the batched read plane (io/fetch): pool bounds, coalescing
        # gap, decode pool, negative-chunk TTL — before any store is
        # constructed so the first cold read already runs configured
        configure_fetch(config.io)
        self.admission = AdmissionController(
            max_inflight=config.resilience.admission.max_inflight,
            retry_after_s=config.resilience.admission.retry_after_s,
        )
        # SLO-aware scheduling (resilience/scheduler): priority
        # classes + the deadline-ordered queue replace the binary
        # admission gate on the serving (miss) path; the
        # AdmissionController above stays the executing-slot counter
        # (and the prefetcher's headroom gate), so /healthz and the
        # inflight metrics keep their meaning
        slo = config.slo
        self.sweep_detector: Optional[SweepDetector] = None
        self.scheduler: Optional[SloScheduler] = None
        self._priority_header = slo.priority_header
        if slo.enabled:
            self.sweep_detector = SweepDetector(
                threshold=slo.sweep_window, ttl_s=slo.sweep_ttl_s,
            )
            self.scheduler = SloScheduler(
                self.admission,
                queue_size=slo.queue_size,
                class_weights=slo.class_weights,
                degrade=slo.degrade,
                degrade_factor=slo.degrade_factor,
            )
        # per-request budget minted in handle_get_tile; defaults to
        # the bus send timeout so the deadline and the reply timeout
        # are the same clock
        self.request_budget_s = (
            config.resilience.request_budget_ms
            if config.resilience.request_budget_ms is not None
            else config.event_bus_send_timeout_ms
        ) / 1000.0
        self._started_at = time.time()
        # /healthz?probe=1 throttle state (one shared round per window)
        self._probe_cache: Optional[tuple] = None
        self._probe_task: Optional[asyncio.Task] = None
        # the runtime twin of tools/analyze's loop-block rule: a lag
        # monitor + blocked-loop stack dumper (the Vert.x
        # BlockedThreadChecker analog, utils/loop_watchdog.py) — armed
        # on the serving loop at startup
        wd = config.resilience.watchdog
        self.watchdog = (
            LoopWatchdog(
                interval_s=wd.interval_ms / 1000.0,
                warn_after_s=wd.warn_ms / 1000.0,
            )
            if wd.enabled else None
        )
        # The flight recorder (obs/): one fixed-slot stamp record per
        # serving request, always on by default — stage histograms and
        # slow-request forensics no longer depend on the tracing flag
        oc = config.obs
        self.recorder: Optional[FlightRecorder] = None
        if oc.enabled:
            self.recorder = FlightRecorder(
                enabled=True,
                slow_threshold_s=oc.slow_threshold_ms / 1000.0,
                head_sample_rate=oc.head_sample_rate,
                ring_size=oc.ring_size,
                sli=SliLayer(budget_s=oc.slow_threshold_ms / 1000.0),
            )
        # Reporter selection mirrors the reference
        # (PixelBufferMicroserviceVerticle.java:169-200): zipkin-url ->
        # batched HTTP sender; enabled without URL -> log reporter;
        # DISABLED -> noop live spans (the reference's :196-198 — span
        # objects cost uuid4 + contextvar churn per request, so off
        # means off). With the flight recorder on, a configured
        # zipkin-url builds the reporter even with live tracing off:
        # kept (tail-sampled) records materialize into retroactive
        # spans through it.
        configure_tracing(
            enabled=config.http_tracing_enabled,
            log_spans=config.http_tracing_enabled,
            zipkin_url=(
                config.zipkin_url
                if (config.http_tracing_enabled or oc.enabled)
                else None
            ),
            tail=oc.enabled,
        )
        self.session_store = session_store or make_session_store(
            config.session_store.type, config.session_store.uri
        )
        if pixels_service is None:
            resolver = None
            db_uri = config.omero_server.get("omero.db.uri")
            data_dir = config.omero_server.get("omero.data.dir")
            if db_uri:
                # authoritative metadata from the OMERO database (the
                # HQL plane), permission-scoped by default: the
                # reference's HQL runs inside the caller's session so
                # ACLs filter what resolves — opt out only for
                # deployments fronted by their own authorization
                from ..db.metadata import OmeroPostgresMetadataResolver

                # omero.server values are Java-style properties and may
                # arrive as strings — "false"/"0"/"no"/"off" must
                # actually disable (bool("false") would not)
                flag = config.omero_server.get(
                    "omero.db.enforce-permissions", True
                )
                resolver = OmeroPostgresMetadataResolver(
                    db_uri,
                    enforce_permissions=str(flag).strip().lower()
                    not in ("false", "0", "no", "off"),
                )
            if db_uri and data_dir and not config.image_registry:
                # full OMERO deployment: imageId -> storage path from
                # the database + data dir (the OmeroFilePathResolver
                # analog, db/resolver.py) — no JSON registry needed
                from ..db.resolver import OmeroImageSource

                registry = OmeroImageSource(
                    db_uri, data_dir, metadata=resolver
                )
            else:
                registry = ImageRegistry(config.image_registry)
            pixels_service = PixelsService(
                registry,
                metadata_resolver=resolver,
                # the Memoizer-dir analog (the reference's data layer
                # memoizes Bio-Formats metadata under the data dir)
                memo_dir=config.omero_server.get(
                    "omero.pixeldata.memoizer.dir"
                ),
            )
        self.pixels_service = pixels_service
        if session_validator is None:
            if config.omero_validate_sessions:
                # per-request Glacier2 join, the OmeroRequest analog
                # (PixelBufferVerticle.java:106-110)
                session_validator = IceSessionValidator(
                    config.omero_host, config.omero_port,
                    secure=config.omero_secure,
                    verify_tls=config.omero_verify_tls,
                    cache_ttl_s=config.omero_session_validation_ttl_s,
                )
            else:
                session_validator = AllowListValidator()
        self.session_validator = session_validator
        batching = config.backend.batching
        # config `backend.engine`: jax/auto -> measure the device link
        # and pick; device/tpu -> the accelerator path, strictly (no
        # chip found is a start-up error); host -> the native host
        # engine. `device-encode: false` forces host.
        engine = {
            "jax": "auto", "auto": "auto",
            "device": "device", "tpu": "device",
            "host": "host",
        }.get(config.backend.engine, "auto")
        if not batching.device_encode:
            engine = "host"
        self.pipeline = TilePipeline(
            pixels_service,
            engine=engine,
            buckets=batching.buckets,
            png_filter=config.backend.png.filter,
            png_level=config.backend.png.level,
            png_strategy=config.backend.png.strategy,
            max_tile_bytes=config.backend.max_tile_mb << 20,
            plane_cache_bytes=config.backend.plane_cache_mb << 20,
            device_deflate=config.backend.png.device_deflate,
            device_deflate_mode=config.backend.png.device_deflate_mode,
            queue_depth=config.backend.png.queue_depth,
            compilation_cache_dir=config.jax.compilation_cache_dir,
            lut_dir=config.render.lut_dir,
            # mesh-fused super-tiles (r19 fusion plane): shard the
            # fused gather+composite+carve+deflate across the serving
            # mesh; `supertile.mesh: false` is the escape hatch back
            # to the per-lane sharded preference
            supertile_mesh=config.supertile.mesh,
            # the lane counts a chip compiles before its first HBM
            # plane counts as resident (hosts with several chips)
            max_batch=batching.max_batch,
        )
        if config.render.enabled:
            # build the LUT registry NOW (directory scan + file reads,
            # render.lut-dir may sit on slow storage) — never lazily
            # on the serving loop inside the first /render request
            self.pipeline.lut_registry
        # background mesh health probe (config mesh.probe-interval-ms):
        # re-probes breaker-open chips on a cadence so a recovered chip
        # rejoins the serving mesh BEFORE the next dispatch failure
        # (reactive probing alone only runs after a batch already
        # failed). Built here, started at app startup.
        self.mesh_prober = None
        if config.mesh.probe_interval_ms > 0:
            from ..parallel.mesh import MeshProber

            self.mesh_prober = MeshProber(
                self._mesh_manager,
                interval_s=config.mesh.probe_interval_ms / 1000.0,
            )
        self.worker = BatchingTileWorker(
            self.pipeline,
            self.session_validator,
            max_batch=batching.max_batch,
            coalesce_window_ms=batching.coalesce_window_ms,
            workers=config.effective_worker_pool_size,
            # super-tile fusion (r19): the batcher stamps spatially
            # adjacent render lanes; the pipeline fuses their gather +
            # composite and carves byte-identical per-tile results
            supertile=config.supertile,
            # burst continuation (r19): zoom bursts chain coalesce
            # windows so a 100-tile zoom executes as a handful of
            # device programs instead of one per window
            burst_continuation=batching.burst_continuation,
        )
        self.bus = EventBus()
        self.bus.consumer(GET_TILE_EVENT, self.worker.handle)
        # -- tiered tile-result cache + viewport prefetch (cache/) ----
        cc = config.cache
        self.result_cache: Optional[TileResultCache] = None
        self.prefetcher: Optional[ViewportPrefetcher] = None
        self.cache_plane = None
        self.quality = None
        self.drainer = None
        self._sigterm_installed = False
        self._drain_task: Optional[asyncio.Task] = None
        # replay guard for the HMAC peer surface (cluster/security):
        # nonces accepted inside the skew window, bounded per peer
        self.cluster_nonces = NonceCache()
        # interactive session plane (session/, r22): the live-channel
        # registry and the annotation store. Built BEFORE the cluster
        # plane so the drain coordinator can hand channels off, and
        # independent of it — single-node deployments get local delta
        # push and annotations too.
        self.session_channels = None
        self.annotations = None
        sp = config.session
        if sp.enabled:
            from ..session import AnnotationStore, ChannelRegistry

            self.session_channels = ChannelRegistry(
                max_channels=sp.max_channels,
                max_per_image=sp.max_per_image,
                queue_size=sp.queue_size,
                recorder=self.recorder,
            )
            self.annotations = AnnotationStore(
                max_images=sp.max_annotation_images,
                max_per_image=sp.max_annotations_per_image,
            )
        # ingest plane (ingest/, r24): the authenticated write path.
        # Off by default — the service stays a read-only viewer
        # backend unless the operator opens the surface. Writes go
        # through the SAME PixelsService the readers use, so the ACL
        # resolver, buffer cache, and invalidation machinery all see
        # one image identity.
        self.ingest = None
        ig = config.ingest
        if ig.enabled:
            from ..ingest import IngestPlane

            self.ingest = IngestPlane(
                self.pixels_service,
                max_inflight_shards=ig.max_inflight_shards,
                staging_bytes=ig.staging_bytes,
            )
        # local epoch fallback when no cluster epoch registry exists:
        # a post-commit token so open buffers' shard-index memos still
        # invalidate (io/zarr.py note_epoch keys on change, not order)
        self._ingest_epoch_seq = 0
        if cc.enabled:
            admission = None
            if cc.tinylfu.enabled:
                from ..cache.plane.tinylfu import TinyLFU

                admission = TinyLFU(
                    counters=cc.tinylfu.counters,
                    sample_size=cc.tinylfu.sample_size,
                )
            self.result_cache = TileResultCache(
                memory_bytes=cc.memory_mb << 20,
                protected_fraction=cc.protected_fraction,
                disk_dir=cc.disk_dir,
                disk_bytes=cc.disk_mb << 20,
                ttl_s=cc.ttl_s,
                max_entry_bytes=cc.max_entry_kb << 10,
                manifest=cc.manifest,
                admission=admission,
            )
            # distributed cache plane (cache/plane/): the shared L2
            # tier and/or the consistent-hash peer ring — the cluster
            # layers only make sense over a live local cache (they
            # fill and are filled by it)
            cl = config.cluster
            if cl.plane_enabled:
                from ..cache.plane import CachePlane
                from ..cluster import (
                    DrainCoordinator,
                    HedgePolicy,
                    QualityTracker,
                    SuspicionPolicy,
                )

                hedge = None
                if cl.hedge.enabled:
                    peer_timeout_s = cl.peer_timeout_ms / 1000.0
                    hedge = HedgePolicy(
                        enabled=True,
                        quantile=cl.hedge.quantile,
                        min_s=cl.hedge.min_ms / 1000.0,
                        max_s=cl.hedge.max_ms / 1000.0,
                        fallback_s=(
                            cl.hedge.fallback_ms / 1000.0
                            or peer_timeout_s / 2.0
                        ),
                    )
                self.quality = QualityTracker()
                suspicion = SuspicionPolicy(
                    enabled=cl.suspect.enabled,
                    error_rate=cl.suspect.error_rate,
                    p99_factor=cl.suspect.p99_factor,
                    min_requests=cl.suspect.min_requests,
                    peer_failures=cl.suspect.peer_failures,
                    corruption_after=cl.integrity.verdict_after,
                )
                self.cache_plane = CachePlane(
                    members=cl.members,
                    self_url=cl.self_url,
                    virtual_nodes=cl.virtual_nodes,
                    peer_timeout_s=cl.peer_timeout_ms / 1000.0,
                    l2_uri=cl.l2.uri,
                    l2_ttl_s=cl.l2.ttl_s,
                    lease_ttl_s=cl.lease_ttl_s,
                    replication_factor=cl.replication_factor,
                    transfer_max_entries=cl.transfer_max_entries,
                    hedge=hedge,
                    secret=cl.secret,
                    result_cache=self.result_cache,
                    scheduler=self.scheduler,
                    admission=self.admission,
                    repair_interval_s=cl.repair.interval_s,
                    repair_max_keys=cl.repair.max_keys,
                    quality=self.quality,
                    suspicion=suspicion,
                    gossip_interval_s=(
                        cl.gossip.interval_s if cl.gossip.enabled else 0.0
                    ),
                    gossip_fanout=cl.gossip.fanout,
                    gossip_fail_after_s=cl.gossip.fail_after_s,
                    integrity_verify=cl.integrity.verify_bodies,
                )
                # the planned-leave protocol (cluster/lifecycle.py):
                # SIGTERM or POST /internal/drain runs it; the
                # coordinator owns the timeline, the plane the
                # mechanics
                self.drainer = DrainCoordinator(
                    self.cache_plane,
                    deadline_s=cl.drain.deadline_s,
                    admission=self.admission,
                    scheduler=self.scheduler,
                    # live channels ride the drain: reconnect frames
                    # out, subscription summary to the successor
                    session_registry=self.session_channels,
                )
            if cc.prefetch.enabled:
                self.prefetcher = ViewportPrefetcher(
                    self._prefetch_fetch,
                    self.result_cache,
                    self.admission,
                    quality=self.pipeline.encode_signature(),
                    queue_size=cc.prefetch.queue_size,
                    headroom_fraction=cc.prefetch.headroom,
                    # 0 = the full request budget: real requests JOIN
                    # prefetch flights, so a shorter leader deadline
                    # would 504 them on stores a direct request rides out
                    budget_s=(
                        cc.prefetch.budget_ms / 1000.0
                        or self.request_budget_s
                    ),
                    lookahead=cc.prefetch.lookahead,
                    # r19: whole-viewport speculation — the predicted
                    # band feeds the super-tile path at prefetch class
                    viewport_span=cc.prefetch.viewport_span,
                    # bounds math at prediction time: the motion
                    # stream's first tile already opened the image's
                    # buffer, so its level extent answers from cache —
                    # off-image predictions die here instead of
                    # wasting a pipeline resolve each
                    extent_fn=self.pixels_service.peek_extent
                    if hasattr(self.pixels_service, "peek_extent")
                    else None,
                    sweep_detector=self.sweep_detector,
                )
        # authorization-verdict TTL cache for the hit path: a session
        # that just took the FULL path for an image (session join +
        # ACL inside the worker/resolver) stays authorized for that
        # image for a short window, so serving a RAM hit costs a dict
        # probe instead of an executor hop per tile. The 10 s bound
        # matches the resolver's session-context TTL (db/metadata):
        # a revoked session or ACL stops reading within it.
        self._authz_ttl_s = 10.0
        self._authz_cache: dict = {}  # (session, image) -> expiry
        self._authz_lock = threading.Lock()  # invalidation is x-thread
        # invalidation: when the metadata resolver observes a changed
        # pixels row, purge every cached artifact of the image —
        # rendered tiles (both tiers), the open pixel buffer, and any
        # device-resident planes
        resolver = getattr(self.pixels_service, "metadata_resolver", None)
        if resolver is not None and hasattr(
            resolver, "add_invalidation_listener"
        ):
            resolver.add_invalidation_listener(self._invalidate_image)
        if config.jmx_metrics_enabled:
            # JMX/hotspot collectors analog (:202-218), config-gated
            from ..utils.process_metrics import install as install_process

            install_process()
        # warm the native engine at startup so a cold deploy never pays
        # the build/load (up to ~2 min of g++) inside the first request
        from ..runtime.native import get_engine

        get_engine()
        # decide the engine NOW, in this process, before the port
        # opens: `device` fails start-up when it finds no chip, `auto`
        # measures the link once and says what it chose (/healthz)
        self._engine_info = self.pipeline.resolve_engine()
        # a chip's share of backend.plane-cache-mb above the chip's
        # memory is said now (log, /healthz cache.device_planes.error),
        # not by the first staging that does not fit
        self.pipeline.check_plane_budget()

    def make_app(self) -> web.Application:
        middlewares = [
            tracing_middleware,
            session_middleware(
                self.session_store,
                self.config.session_store.synchronicity,
            ),
        ]
        if self.scheduler is None:
            # slo.enabled: false restores the r6 binary gate at the
            # door; with the scheduler on, admission happens at the
            # miss path (_serve) per priority class instead
            middlewares.insert(0, admission_middleware(self.admission))
        else:
            # scheduler on: the door still needs a gate for GENUINE
            # overflow (queue full + class would shed anyway), or
            # every excess request costs a session lookup + cluster
            # cache consult before the scheduler can refuse it
            middlewares.insert(0, overload_gate_middleware(self))
        if self.cache_plane is not None:
            # authenticate the peer surface BEFORE the door gate (a
            # forged /internal/* or peer-marked request must not pay
            # the probe machinery) but INSIDE the obs middleware, so
            # the 403 still completes a flight record — obs gates its
            # own trace adoption on the same signature check
            middlewares.insert(0, cluster_guard_middleware(self))
        if self.recorder is not None:
            # outermost: door sheds, auth 503s, and 403s all complete
            # a record — "every outcome leaves a trace" is the
            # completeness contract the obs tests pin
            middlewares.insert(0, obs_middleware(self))
        if self.quality is not None:
            # outside even the recorder: the suspicion signal must
            # see every serving outcome, whatever layer produced it
            middlewares.insert(0, quality_middleware(self))
        # request-body bound: inbound bodies are replica pushes
        # (/internal/replica — one L2-framed cache entry) and, with
        # the lifecycle plane, drain-handoff / repair-pull batches
        # (transfer-framed, hard-capped at the transfer byte bound) —
        # size the cap accordingly instead of aiohttp's 1 MiB default
        # silently 413ing them
        max_body = (self.config.cache.max_entry_kb << 10) + 65536
        if self.cache_plane is not None:
            from ..cluster.replicate import MAX_TRANSFER_BYTES

            max_body = max(max_body, MAX_TRANSFER_BYTES + 65536)
        if self.ingest is not None:
            # ingest bodies carry raw pixels; anything larger than the
            # staging bound would be refused by the assembler anyway,
            # so cap the transport at the same number
            max_body = max(
                max_body, self.config.ingest.staging_bytes + 65536
            )
        app = web.Application(
            middlewares=middlewares, client_max_size=max_body
        )
        app.router.add_get("/metrics", handle_metrics)
        app.router.add_get("/healthz", self.handle_healthz)
        if self.recorder is not None:
            app.router.add_get(
                "/debug/requests", self.handle_debug_requests
            )
            app.router.add_get(
                "/debug/requests/{traceId}",
                self.handle_debug_request_detail,
            )
        app.router.add_route("OPTIONS", "/{tail:.*}", handle_options)
        app.router.add_get(
            "/tile/{imageId}/{z}/{c}/{t}", self.handle_get_tile
        )
        if self.ingest is not None:
            # ingest plane (r24): the write surface. Behind the session
            # middleware (cookie auth) like every /image-scoped route;
            # deliberately NOT a SERVING_PREFIXES lane — the scheduler
            # pin lives in-handler (acquire(degradable=False), no sweep
            # or prefetch training), same posture as the session plane
            app.router.add_put(
                "/image/{imageId}/tile/{z}/{c}/{t}",
                self.handle_ingest_tile,
            )
            app.router.add_post(
                "/image/{imageId}/planes", self.handle_ingest_planes
            )
        if self.cache_plane is not None:
            app.router.add_post(
                "/internal/purge/{imageId}", self.handle_internal_purge
            )
            app.router.add_post(
                "/internal/replica", self.handle_internal_replica
            )
            app.router.add_get(
                "/internal/transfer", self.handle_internal_transfer
            )
            app.router.add_post(
                "/internal/handoff", self.handle_internal_handoff
            )
            app.router.add_get(
                "/internal/digest", self.handle_internal_digest
            )
            app.router.add_post(
                "/internal/pull", self.handle_internal_pull
            )
            app.router.add_post(
                "/internal/drain", self.handle_internal_drain
            )
            app.router.add_post(
                "/internal/gossip", self.handle_internal_gossip
            )
        if self.config.render.enabled:
            app.router.add_get(
                "/render/{imageId}/{z}/{c}/{t}", self.handle_get_render
            )
        if self.session_channels is not None:
            # the interactive session plane (session/, r22): the live
            # channel, its SSE-side viewport report, and annotation
            # CRUD. All behind the session middleware (cookie auth) —
            # none are SERVING_PREFIXES lanes (a held-open channel
            # must not occupy an admission slot or door budget)
            app.router.add_get(
                "/session/{imageId}/live", self.handle_session_live
            )
            app.router.add_post(
                "/session/{imageId}/viewport",
                self.handle_session_viewport,
            )
            app.router.add_post(
                "/annotations/{imageId}", self.handle_annotations_create
            )
            app.router.add_get(
                "/annotations/{imageId}", self.handle_annotations_list
            )
            app.router.add_get(
                "/annotations/{imageId}/{annId}",
                self.handle_annotation_get,
            )
            app.router.add_put(
                "/annotations/{imageId}/{annId}",
                self.handle_annotation_update,
            )
            app.router.add_delete(
                "/annotations/{imageId}/{annId}",
                self.handle_annotation_delete,
            )
        self._protocols_enabled: dict = {}
        if self.config.analysis.enabled:
            app.router.add_get(
                "/histogram/{imageId}/{z}/{c}/{t}",
                self.handle_get_histogram,
            )
        if self.config.render.enabled:
            # the protocol adapters serve RENDERED tiles, so they only
            # mount when the render surface itself is on
            from .protocols import register as register_protocols

            self._protocols_enabled = register_protocols(
                app.router, self
            )
        app.on_startup.append(self._on_startup)
        app.on_cleanup.append(self._on_cleanup)
        return app

    def _door_probe_key(self, request: web.Request) -> Optional[str]:
        """The cache key the overload door gate probes for its
        hit exemption, or None when the request can't be keyed
        cheaply (malformed params — the handler owns the 400, so the
        arrival sheds like any other would-shed request).

        Two fixes over the original pre-auth probe (KNOWN_GAPS
        "Operational"): w/h=0 full-plane spellings NORMALIZE first —
        via ``peek_extent``, the open-buffer cache peek, so the probe
        never blocks or does I/O — and ``/render/`` requests parse
        their spec (pure grammar + LUT-registry lookup, no I/O
        either) instead of being categorically unprobeable. A tile
        cached under its explicit spelling therefore passes the door
        under genuine overflow whichever spelling (or dialect
        grammar) asks for it. A failed extent peek leaves the region
        unnormalized — exactly the old probe, which still matches
        explicitly-spelled entries."""
        try:
            if request.path.startswith("/render/"):
                # match_info only — the ``c`` QUERY param is the
                # render channel grammar, not the path's channel
                # index (mirrors handle_get_render exactly)
                probe_ctx = TileCtx.from_params(
                    dict(request.match_info), None
                )
                spec, err = self.build_render_spec(
                    request.query, probe_ctx.c
                )
                if err is not None:
                    return None
                probe_ctx.render = spec
                probe_ctx.format = spec.format
                if self._apply_region_params(
                    probe_ctx, request.query
                ) is not None:
                    return None
            else:
                params = dict(request.match_info)
                params.update(request.query)
                probe_ctx = TileCtx.from_params(params, None)
            region = probe_ctx.region
            if region.width == 0 or region.height == 0:
                extent = None
                svc = self.pixels_service
                if hasattr(svc, "peek_extent"):
                    extent = svc.peek_extent(
                        probe_ctx.image_id, probe_ctx.resolution
                    )
                if extent is not None:
                    # the resolve_region contract verbatim (w==0 ->
                    # sizeX regardless of x), mirroring
                    # _normalize_region so both spellings probe the
                    # one shared entry
                    if region.width == 0:
                        region.width = extent[0]
                    if region.height == 0:
                        region.height = extent[1]
            return probe_ctx.cache_key(
                self.pipeline.encode_signature()
            )
        except TileError:
            return None

    def verify_cluster_request(
        self, request: web.Request, body: bytes
    ) -> bool:
        """One signature verdict per request, memoized on the request
        object: the obs middleware (trace adoption) and the cluster
        guard both need it, and the nonce cache consumes a nonce on
        first acceptance — verifying the same header twice would read
        the second check as a replay and 403 every legitimately
        signed peer hop."""
        cached = request.get("cluster.sig_ok")
        if cached is not None:
            return cached
        ok = verify_cluster_sig(
            self.config.cluster.secret,
            request.headers.get(SIG_HEADER),
            request.method,
            request.path_qs,
            body,
            nonce_cache=self.cluster_nonces,
            peer=request.headers.get(PEER_HEADER, "-"),
        )
        request["cluster.sig_ok"] = ok
        return ok

    def _mesh_manager(self):
        """The live MeshManager, when the device path has built one
        (the prober's lookup hook — the dispatcher is lazy, so this
        resolves per probe tick, never caches None)."""
        disp = self.pipeline._dispatcher
        return None if disp is None else disp.mesh_manager

    async def _on_startup(self, app) -> None:
        mark_loop_thread()  # thread_cpu_seconds_total{role="loop"}
        if self.watchdog is not None:
            self.watchdog.start()  # on the serving loop's thread
        await self.worker.start()
        if self.prefetcher is not None:
            self.prefetcher.start()
        if self.mesh_prober is not None:
            self.mesh_prober.start()
        if self.session_channels is not None:
            # like the cache plane: delta pushes originate on resolver
            # threads and must marshal onto the serving loop
            self.session_channels.start(asyncio.get_running_loop())
        if self.cache_plane is not None:
            # the plane needs the serving loop: invalidation listeners
            # fire from resolver threads and schedule their fan-out here
            self.cache_plane.start(asyncio.get_running_loop())
        if (
            self.drainer is not None
            and self.config.cluster.drain.signal
        ):
            # SIGTERM = planned leave: run the drain protocol, THEN
            # the normal graceful exit (aiohttp's own handler would
            # stop serving immediately — the crash path)
            import signal as _signal

            try:
                asyncio.get_running_loop().add_signal_handler(
                    _signal.SIGTERM, self._on_sigterm
                )
                self._sigterm_installed = True
            except (NotImplementedError, RuntimeError, ValueError):
                pass  # non-unix / nested loop: endpoint-only drains

    def _on_sigterm(self) -> None:
        # keep a reference and consume the outcome: an untracked
        # ensure_future can be GC'd mid-drain and silently loses its
        # exception (the PR-14 hang class). A repeat SIGTERM while the
        # drain is in flight reuses it instead of racing a second one.
        if self._drain_task is not None and not self._drain_task.done():
            return
        task = asyncio.ensure_future(self._drain_then_exit())
        task.add_done_callback(self._drain_task_done)
        self._drain_task = task

    @staticmethod
    def _drain_task_done(task: "asyncio.Task") -> None:
        if task.cancelled():
            log.warning("SIGTERM drain task cancelled before completion")
            return
        exc = task.exception()
        if exc is not None:
            log.error("SIGTERM drain task died: %r", exc)

    async def _drain_then_exit(self) -> None:
        try:
            await self.drainer.drain()
        except Exception:
            log.exception("drain on SIGTERM failed; exiting anyway")
        finally:
            from aiohttp.web_runner import GracefulExit

            def _raise() -> None:
                raise GracefulExit()  # ompb-lint: disable=error-taxonomy -- not a request path: a bare loop callback raising GracefulExit is exactly how aiohttp's own signal handler stops web.run_app

            # raising from a bare callback propagates out of
            # run_forever — exactly how aiohttp's own signal handler
            # stops web.run_app, now one drain later
            asyncio.get_running_loop().call_soon(_raise)

    async def _on_cleanup(self, app) -> None:
        # stop() analog (:298-308): worker, session store, pixel
        # buffers, then the span reporter/sender
        if self.watchdog is not None:
            self.watchdog.stop()
        if self._sigterm_installed:
            import signal as _signal

            try:
                asyncio.get_running_loop().remove_signal_handler(
                    _signal.SIGTERM
                )
            except (NotImplementedError, RuntimeError, ValueError):
                pass
            self._sigterm_installed = False
        if self.mesh_prober is not None:
            self.mesh_prober.stop()
        if self.prefetcher is not None:
            await self.prefetcher.close()
        if self.session_channels is not None:
            # close every live channel (sentinel frames) so their
            # writer tasks unwind before the loop does
            await self.session_channels.close()
        if self.cache_plane is not None:
            await self.cache_plane.close()
        if self.result_cache is not None:
            self.result_cache.close()
        await self.worker.close()
        self.pipeline.close()
        await self.session_store.close()
        self.pixels_service.close()
        resolver = getattr(self.pixels_service, "metadata_resolver", None)
        if resolver is not None and hasattr(resolver, "close_sync"):
            resolver.close_sync()
        if TRACER.reporter is not None:
            TRACER.reporter.close()
            TRACER.reporter = None

    async def handle_healthz(self, request: web.Request) -> web.Response:
        """Operational health, unauthenticated (like /metrics): live
        breaker states, admission/queue pressure, and uptime. Status
        is "degraded" (still 200 — the service IS serving; shedding
        and breakers are it working as designed) whenever any breaker
        is open or requests are being shed."""
        breakers = BOARD.snapshot()
        admission = self.admission.snapshot()
        queue_depth = self.worker._queue.qsize()
        loop_health = (
            self.watchdog.snapshot()
            if self.watchdog is not None
            else {"enabled": False}
        )
        cache_health = (
            self.result_cache.snapshot()
            if self.result_cache is not None
            else {"enabled": False}
        )
        planes = self.pipeline.plane_cache_snapshot()
        if planes is not None:
            cache_health["device_planes"] = planes
        if self.cache_plane is not None:
            cache_health["plane"] = self.cache_plane.snapshot()
        prefetch_health = (
            self.prefetcher.snapshot()
            if self.prefetcher is not None
            else {"enabled": False}
        )
        render_health = {"enabled": self.config.render.enabled}
        if self.config.render.enabled:
            render_health.update(self.pipeline.render_snapshot())
        analysis_health = {"enabled": self.config.analysis.enabled}
        if self.config.analysis.enabled:
            analysis_health.update(self.pipeline.analysis_snapshot())
        mesh_mgr = self._mesh_manager()
        if mesh_mgr is not None:
            render_health["mesh"] = mesh_mgr.snapshot()
        device_queue = self.pipeline.device_queue_snapshot()
        if self.scheduler is not None:
            slo_health = self.scheduler.snapshot()
            slo_health["sweep"] = self.sweep_detector.snapshot()
        else:
            slo_health = {"enabled": False}
        degraded = (
            any(b["state"] == "open" for b in breakers.values())
            or admission["inflight"] >= admission["max_inflight"]
            or loop_health.get("blocked", False)
            # a plane budget the chips cannot hold (start-up check)
            or bool((planes or {}).get("error"))
        )
        obs_health = (
            self.recorder.snapshot()
            if self.recorder is not None
            else {"enabled": False}
        )
        cluster_health = (
            self.cache_plane.cluster_snapshot()
            if self.cache_plane is not None
            else {"enabled": False}
        )
        if self.drainer is not None:
            cluster_health["drain"] = self.drainer.snapshot()
        if self.config.cluster.secret:
            cluster_health["nonces"] = self.cluster_nonces.snapshot()
        body = {
            "status": "degraded" if degraded else "ok",
            "uptime_s": round(time.time() - self._started_at, 1),
            "obs": obs_health,
            "cluster": cluster_health,
            "breakers": breakers,
            "admission": admission,
            "slo": slo_health,
            "queue_depth": queue_depth,
            "loop": loop_health,
            "cache": cache_health,
            "prefetch": prefetch_health,
            "render": render_health,
            "analysis": analysis_health,
            "protocols": getattr(self, "_protocols_enabled", {}),
            "session": self._session_snapshot(),
            "ingest": self._ingest_snapshot(),
            **self._engine_snapshot(),
            "device_queue": device_queue,
            "io": io_snapshot(),
            "request_budget_ms": self.request_budget_s * 1000.0,
        }
        if request.query.get("probe", "").strip().lower() in (
            "1", "true", "yes"
        ):
            # opt-in active dependency pings (?probe=1): exercise each
            # configured remote dependency once so a never-used one
            # materializes its breaker/state before first traffic.
            # ``probe=0``/``probe=false`` means OFF — an orchestrator
            # templating the flag must not trigger dependency traffic
            body["probes"] = await self._probe_dependencies_throttled()
            body["breakers"] = BOARD.snapshot()  # probes mint breakers
        return web.json_response(body)

    def _engine_snapshot(self) -> dict:
        """/healthz engine block: which engine serves and why, the
        device as ``jax.devices()`` reports it in THIS process (None
        when the host engine never initialised a backend), the link
        `auto` measured, and every device-path failure that degraded
        to the host since start."""
        from ..models.tile_pipeline import (
            TILE_DEVICE_FALLBACK,
            TILE_DEVICE_LANES,
        )
        from ..render.engine import RENDER_FALLBACK

        info = self._engine_info
        return {
            "engine": info["engine"],
            "engine_reason": info["reason"],
            "device": info["device"],
            "link_mbps": info["link_mbps"],
            "auto_verdict": info["auto_verdict"],
            "tile_device_lanes_total": TILE_DEVICE_LANES.total(),
            "tile_device_fallback_total": TILE_DEVICE_FALLBACK.total(),
            "render_fallback_total": RENDER_FALLBACK.total(),
        }

    async def _probe_dependencies_throttled(self) -> dict:
        """/healthz is unauthenticated, so ``?probe=1`` must not be an
        amplification lever against the backing stores (or a way to
        pump failures into their breakers): at most one probe round
        per ``_PROBE_MIN_INTERVAL_S`` — concurrent callers share the
        in-flight round, later callers inside the window get the
        cached result."""
        now = time.monotonic()
        cached = self._probe_cache
        if cached is not None and now - cached[0] < _PROBE_MIN_INTERVAL_S:
            return cached[1]
        task = self._probe_task
        if task is None or task.done():

            async def _round() -> dict:
                probes = await self._probe_dependencies()
                self._probe_cache = (time.monotonic(), probes)
                return probes

            task = asyncio.get_running_loop().create_task(_round())
            self._probe_task = task
        # shield: a disconnecting healthz client must not cancel the
        # round other callers are sharing
        return await asyncio.shield(task)

    async def _probe_dependencies(self) -> dict:
        """One lightweight, bounded exchange against each configured
        remote dependency, concurrently. Each ping rides the
        dependency's normal client path, so outcomes feed the same
        breakers real traffic uses — after one probe, /healthz shows a
        state for a dependency no request has touched yet. Failures
        report as strings and never fail the endpoint."""
        from ..resilience.timeouts import io_timeout_s

        bound = io_timeout_s()
        bound = min(bound, 2.0) if bound > 0 else 2.0
        probes: dict = {}

        async def run(name: str, awaitable_factory) -> None:
            try:
                await asyncio.wait_for(awaitable_factory(), bound)
                probes[name] = "ok"
            except Exception as e:
                probes[name] = f"{type(e).__name__}: {e}"

        tasks = [
            run(
                "session-store",
                lambda: self.session_store.get_omero_session_key(
                    "__ompb_healthz_probe__"
                ),
            )
        ]
        plane = self.cache_plane
        if plane is not None and getattr(plane, "l2", None) is not None:
            tasks.append(
                run(
                    "cache-l2",
                    lambda: plane.l2.get("__ompb_healthz_probe__"),
                )
            )
        resolver = getattr(self.pixels_service, "metadata_resolver", None)
        if resolver is not None and hasattr(resolver, "query"):
            loop = asyncio.get_running_loop()
            tasks.append(
                run(
                    "postgres",
                    lambda: loop.run_in_executor(
                        None, lambda: resolver.query("SELECT 1", [])
                    ),
                )
            )
        await asyncio.gather(*tasks)
        return probes

    # -- tile serving: cache hit / conditional GET / coalesced miss ----

    def _cache_headers(self, etag: Optional[str]) -> dict:
        """Validator + freshness headers on every tile answer the
        cache layer saw. ``private``: tile responses are authorized
        per browser session, so shared proxies must not store them."""
        headers = {}
        if etag:
            headers["ETag"] = etag
            headers["Cache-Control"] = (
                f"private, max-age={int(self.config.cache.max_age_s)}"
            )
        return headers

    def _tile_response(
        self, ctx: TileCtx, body: bytes, filename: str,
        etag: Optional[str], x_cache: Optional[str] = None,
        degraded: int = 0,
    ) -> web.Response:
        t_frame = time.perf_counter()
        headers = {
            "Content-Type": CONTENT_TYPES.get(
                ctx.format, "application/octet-stream"
            ),
            "Content-Length": str(len(body)),
            "Content-Disposition": (
                f'attachment; filename="{filename}"'
            ),
            **self._cache_headers(etag),
        }
        if x_cache:
            headers["X-Cache"] = x_cache
        if degraded:
            # hybrid-resolution fallback body: the next-lower pyramid
            # level upscaled (resilience/scheduler). The value is how
            # many levels down the pixels came from; clients may
            # re-request once pressure clears (the degraded entry has
            # its own cache key + ETag, so full-resolution state is
            # untouched)
            headers["X-OMPB-Degraded"] = str(degraded)
        rec = getattr(ctx, "obs", None)
        if rec is not None:
            rec.stamp("frame", time.perf_counter() - t_frame)
        return web.Response(body=body, headers=headers)

    def _failure_response(
        self, request: web.Request, e: BaseException
    ) -> web.Response:
        """One failure-shaping path for every serving error: TileError
        codes pass through (404 default, <1 -> 500), 503s carry
        Retry-After — which, with the scheduler on, is only ever
        emitted when the wait queue is genuinely full."""
        status = http_status_for_failure(e)
        if status < 1:
            status = 500
        headers = {}
        if status == 503:
            retry_s = getattr(e, "retry_after_s", None)
            headers["Retry-After"] = _retry_after(
                retry_s if retry_s else
                self.config.resilience.admission.retry_after_s
            )
        span = request.get("span")
        if span is not None:
            span.tag("http.status", status)
        return web.Response(status=status, headers=headers)

    def _degradable(self, ctx: TileCtx) -> bool:
        """Whether the hybrid-resolution fallback may serve this
        request: viewport media only — PNG tiles and rendered tiles.
        Raw binary and TIFF consumers are analysis tools; silently
        interpolated pixels would corrupt measurements, so those
        formats ride out the queue at full resolution."""
        return ctx.degraded == 0 and (
            ctx.format == "png" or ctx.render is not None
        )

    def _authz_fresh(self, ctx: TileCtx) -> bool:
        with self._authz_lock:
            expiry = self._authz_cache.get(
                (ctx.omero_session_key, ctx.image_id)
            )
        return expiry is not None and expiry > time.monotonic()

    def _authz_record(self, ctx: TileCtx) -> None:
        with self._authz_lock:
            if len(self._authz_cache) >= 65536:
                self._authz_cache.clear()  # coarse but bounded
            self._authz_cache[(ctx.omero_session_key, ctx.image_id)] = (
                time.monotonic() + self._authz_ttl_s
            )

    def _authz_purge(self, image_id: int) -> None:
        with self._authz_lock:
            for key in [
                k for k in self._authz_cache if k[1] == image_id
            ]:
                del self._authz_cache[key]

    async def _authorize_cached(self, ctx: TileCtx) -> bool:
        """A cache hit skips the *pipeline*, never the auth: the
        caller's session must still validate (Glacier2/allow-list,
        TTL-cached) and — under a permission-scoped resolver — the
        image must still resolve for this caller (the ACL contract:
        unauthorized reads exactly like nonexistent). Any failure
        answers False and the request takes the full miss path, which
        maps auth/store failures to proper statuses."""
        if self._authz_fresh(ctx):
            return True
        with obs_recorder.ambient_stage("cache_probe"):
            return await self._authorize_cached_slow(ctx)

    async def _authorize_cached_slow(self, ctx: TileCtx) -> bool:
        try:
            ok = await self.session_validator.validate(
                ctx.omero_session_key
            )
            if not ok:
                return False
            svc = self.pixels_service
            loop = asyncio.get_running_loop()
            meta = await loop.run_in_executor(
                None,
                lambda: svc.get_pixels(
                    ctx.image_id, session_key=ctx.omero_session_key
                ),
            )
            if meta is None:
                return False
            self._authz_record(ctx)
            return True
        except Exception:
            log.debug("cache-hit authorization failed; full path",
                      exc_info=True)
            return False

    def _cache_filler(
        self, key: str, full_res_key: Optional[str] = None,
        epoch: Optional[int] = None,
    ):
        """The request_coalesced on_result hook: memoize exactly once
        per flight (no matter how many requests coalesced) and stamp
        the ETag onto the shared reply so every waiter's response
        carries the validator. The invalidation generation is captured
        NOW — before the render — so a purge landing mid-flight
        discards this fill instead of racing it into the cache.

        ``full_res_key`` is set when ``key`` is a degraded (|deg=N)
        key: the pipeline clears ``ctx.degraded`` when no coarser
        pyramid level exists, so the flight may come back with FULL-
        resolution bytes — those must land under the full-resolution
        key, or every later degraded-permit request would hit the
        |deg=N entry and tag an undegraded body ``X-OMPB-Degraded``.

        ``epoch`` is the image epoch observed BEFORE this flight's
        render began (the plane fetch's L2 round trip, or the peer
        hop's forwarded header): the L2 write-through stamps it, so a
        cluster purge that lands mid-flight makes this fill
        stale-on-arrival (cluster/epochs.py)."""
        cache = self.result_cache
        generation = cache.generation()

        async def fill(msg: Message) -> None:
            entry = CachedTile(
                bytes(msg.body),
                filename=msg.headers.get("filename", ""),
            )
            msg.headers["etag"] = entry.etag
            target = key
            if full_res_key is not None and not int(
                msg.headers.get("degraded", 0) or 0
            ):
                target = full_res_key
            await cache.put(target, entry, generation=generation)
            if self.cache_plane is not None:
                # write-through to the shared L2 tier, once per flight
                # (fire-and-forget: Redis must never cost the reply),
                # epoch-stamped with the pre-render snapshot
                self.cache_plane.publish(target, entry, epoch=epoch)

        return fill

    async def _fetch_tile(
        self, ctx: TileCtx, key: str,
        full_res_key: Optional[str] = None,
        epoch: Optional[int] = None,
    ) -> Message:
        """The shared miss path: coalesced bus request, memoized on
        completion. ``key`` is the content key; the flight dedupes on
        the session-scoped key so one caller never rides past another
        caller's ACL check."""
        quality = self.pipeline.encode_signature()
        on_result = (
            self._cache_filler(key, full_res_key, epoch)
            if self.result_cache is not None else None
        )
        return await self.bus.request_coalesced(
            GET_TILE_EVENT,
            ctx,
            ctx.dedupe_key(quality),
            timeout_ms=self.config.event_bus_send_timeout_ms,
            on_result=on_result,
        )

    async def _hedged_fetch(
        self, request: web.Request, ctx: TileCtx, key: str,
        full_res_key: Optional[str], epoch: Optional[int],
        pending: asyncio.Task, generation: Optional[int], inm: str,
    ):
        """The hedge race (cluster/hedge.py): the peer fetch ran past
        the observed p99, so the local render starts NOW and whichever
        finishes first serves. Returns ``(reply, None)`` when the
        local render wins (the normal miss path continues) or
        ``(None, response)`` when the peer's bytes arrive first.

        A peer win cancels only OUR wait on the coalesced flight (a
        waiter's cancellation never kills the flight — followers and
        the cache fill are unaffected) and admits the peer entry under
        the pre-fetch generation snapshot so a racing purge still
        wins. Either way the loser's work lands in the caches it was
        already headed for: the bounded one-extra-render cost the
        membership layer documents, spent deliberately."""
        plane = self.cache_plane
        hedge = plane.hedge
        rec = request.get("obs.rec")
        fetch_task = asyncio.ensure_future(
            self._fetch_tile(ctx, key, full_res_key, epoch)
        )
        try:
            done, _ = await asyncio.wait(
                {fetch_task, pending},
                return_when=asyncio.FIRST_COMPLETED,
            )
            if pending in done and fetch_task not in done:
                result = pending.result()  # ompb-lint: disable=loop-block -- asyncio.Task already in asyncio.wait's done set: result() returns immediately, never blocks
                if result is not None and result[1].get(
                    "x-ompb-degraded"
                ):
                    # the owner was under enough pressure to serve its
                    # OWN hybrid-resolution fallback: those bytes
                    # belong under a |deg key we can't reconstruct
                    # here — discard and let the local render decide
                    result = None
                entry = plane.entry_from_peer(
                    result, getattr(pending, "ompb_owner", None)
                )
                if entry is not None and (
                    await self._authorize_cached(ctx)
                ):
                    hedge.note("peer_win")
                    if rec is not None:
                        rec.tag("hedge", "peer_win")
                    fetch_task.cancel()
                    if self.result_cache is not None:
                        # the peer fetch rode the ORIGINAL path, so
                        # these are full-resolution bytes — they must
                        # land under the full-res key even when this
                        # request's permit switched `key` to |deg=1
                        # (the _cache_filler target invariant)
                        await self.result_cache.put(
                            full_res_key if full_res_key is not None
                            else key,
                            entry, generation=generation,
                        )
                    if inm and etag_matches(inm, entry.etag):
                        return None, web.Response(
                            status=304,
                            headers=self._cache_headers(entry.etag),
                        )
                    return None, self._tile_response(
                        ctx, entry.body, entry.filename, entry.etag,
                        x_cache="peer-hit",
                    )
                hedge.note("peer_failed")
            reply = await fetch_task
            hedge.note("local_win")
            if rec is not None:
                rec.tag("hedge", "local_win")
            return reply, None
        finally:
            if not pending.done():
                pending.cancel()

    async def _prefetch_fetch(self, ctx: TileCtx, key: str) -> None:
        """The prefetcher's fetch hook: identical machinery to a real
        miss, so warmed tiles land in the cache with their ETags and
        dedupe against concurrent real requests."""
        await self._fetch_tile(ctx, key)

    def _invalidate_local(self, image_id: int) -> None:
        """Purge every PROCESS-LOCAL cached artifact of one image —
        tiles, authorization verdicts (the row change may BE an ACL
        change), the open buffer, and device planes. Callable from any
        thread; also the inbound target of a peer purge (which must
        NOT re-fan-out, or two replicas would purge-ping-pong)."""
        epoch = None
        plane = self.cache_plane
        if plane is not None and plane.epochs is not None:
            epoch = plane.epochs.known(image_id)
        if epoch is not None:
            # r24: stamp the epoch onto the OPEN buffer BEFORE the
            # pipeline purge pops it from the service cache —
            # concurrent requests still holding the buffer object get
            # shard-index-memo misses on their next footer lookup
            # instead of serving pre-commit offsets (io/zarr.py)
            note = getattr(self.pixels_service, "note_epoch", None)
            if note is not None:
                note(image_id, epoch)
        if self.result_cache is not None:
            self.result_cache.invalidate_image(image_id)
        if self.prefetcher is not None:
            self.prefetcher.invalidate_image(image_id)
        self._authz_purge(image_id)
        self.pipeline.invalidate_image(image_id)
        if self.session_channels is not None:
            # session plane (r22): every local purge — originated here
            # OR inbound from a peer's fan-out — becomes a delta frame
            # to the image's subscribed channels. That inbound leg is
            # what makes a purge on replica A reach a viewer whose
            # channel lives on replica B without any new fan-out
            # machinery. Thread-safe (resolver refresh thread included).
            self.session_channels.push_delta(image_id, epoch=epoch)

    def _invalidate_image(self, image_id: int) -> None:
        """Metadata-change listener (the resolver's refresh thread):
        local purge first — synchronous, unconditional — then the
        best-effort cluster fan-out (L2 DELs + peer purges), which is
        scheduled on the serving loop and can never block or fail the
        local purge."""
        self._invalidate_local(image_id)
        if self.cache_plane is not None:
            self.cache_plane.invalidate_image(image_id)

    async def handle_debug_requests(self, request: web.Request) -> web.Response:
        """The flight-recorder ring: most-recent-first kept wide
        events. Session-exempt like /internal/* (an internal,
        network-trust surface — it must answer precisely when auth or
        the serving path is the thing being debugged); bounded by the
        ring, with an optional ``?limit=`` narrowing."""
        limit = None
        raw = request.query.get("limit")
        if raw is not None:
            try:
                limit = max(0, int(raw))
            except (TypeError, ValueError):
                return web.Response(status=400, text="bad limit")
        events = self.recorder.events(limit=limit)
        local = {
            "kept": self.recorder.kept_count(),
            "ring_size": self.recorder.ring_size,
            "count": len(events),
            "events": events,
        }
        fleet = request.query.get("fleet", "").strip().lower() in (
            "1", "true", "yes"
        )
        plane = self.cache_plane
        if (
            fleet
            and plane is not None
            and plane.self_url
            # a peer-originated scatter is terminal here — the fleet
            # fan-out must never recurse peer-to-peer
            and PEER_HEADER not in request.headers
        ):
            others = [
                m for m in plane.members_view() if m != plane.self_url
            ]
            path = "/debug/requests" + (
                f"?limit={limit}" if limit is not None else ""
            )
            replies = await asyncio.gather(
                *(plane.peers.get_json(m, path) for m in others)
            )
            members = {plane.self_url: local}
            for member, reply in zip(others, replies):
                members[member] = reply  # None = unreachable, kept honest
            return web.json_response({
                "fleet": True, "members": members,
            })
        return web.json_response(local)

    async def handle_debug_request_detail(
        self, request: web.Request
    ) -> web.Response:
        """One trace's kept wide events (a trace id can appear once
        per completed request it spanned — e.g. requester + owner on
        a peer hop hold separate rings; each replica serves its own
        half)."""
        trace_id = request.match_info["traceId"]
        events = self.recorder.events(trace_id=trace_id)
        if not events:
            return web.Response(status=404, text="unknown trace id")
        return web.json_response({
            "trace_id": trace_id, "events": events,
        })

    async def handle_internal_gossip(self, request: web.Request) -> web.Response:
        """One push-pull gossip exchange (cluster/gossip.py): the
        sender's full-state digest (membership + epochs + brains)
        arrives as JSON; this replica merges it, marks the sender
        alive (a POST that reached us IS liveness evidence), and
        answers with its own digest — one round trip disseminates in
        both directions. Peer-marked and HMAC-guarded like the rest
        of /internal/*."""
        if PEER_HEADER not in request.headers:
            return web.Response(status=403, text="peer requests only")
        import json as _json

        try:
            remote = _json.loads(await request.read())
        except Exception:
            return web.Response(status=400, text="bad digest")
        if not isinstance(remote, dict):
            return web.Response(status=400, text="bad digest")
        reply = self.cache_plane.gossip_receive(remote)
        if reply is None:
            return web.Response(status=503, text="gossip disabled")
        return web.json_response(reply)

    # -- interactive session plane (session/, r22) ---------------------

    def _session_snapshot(self) -> dict:
        if self.session_channels is None:
            return {"enabled": False}
        out = self.session_channels.snapshot()
        if self.annotations is not None:
            out["annotations"] = self.annotations.snapshot()
        return out

    def _session_epoch(self, image_id: int) -> Optional[int]:
        plane = self.cache_plane
        if plane is not None and plane.epochs is not None:
            return plane.epochs.known(image_id)
        return None

    def _note_viewport(
        self, session_key: str, image_id: int, rect
    ) -> bool:
        if self.prefetcher is None or not isinstance(rect, dict):
            return False
        return self.prefetcher.note_viewport(
            session_key, image_id, rect
        )

    async def _session_still_valid(self, session_id: str) -> bool:
        """Ping-interval revalidation: a browser session revoked in
        the session store loses its live channel within one interval.
        Store UNAVAILABLE reads as still-valid — the same 'auth
        unavailable must never read as auth denied' posture the
        session middleware takes."""
        try:
            key = await self.session_store.get_omero_session_key(
                session_id
            )
        except Exception:
            return True
        return bool(key)

    def _session_hello(self, channel) -> dict:
        return {
            "type": "hello",
            "image": channel.image_id,
            "transport": channel.transport,
            "epoch": self._session_epoch(channel.image_id),
            "annotations": (
                self.annotations.sub_epoch(channel.image_id)
                if self.annotations is not None else 0
            ),
        }

    def _session_inbound(self, channel, frame) -> None:
        """One client->server frame off the live channel. Only the
        viewport report is meaningful today; unknown types are
        ignored (forward compatibility, never an error loop)."""
        if not isinstance(frame, dict):
            return
        if frame.get("type") == "viewport":
            self._note_viewport(
                channel.omero_session_key, channel.image_id, frame
            )

    async def _session_pump(self, channel, send) -> None:
        """Drain the channel's frame queue into one transport until
        the close sentinel. Quiet intervals ping (liveness for
        proxies) and REVALIDATE the session — revocation closes the
        channel from inside the pump via the registry's revoke
        frames."""
        interval = self.config.session.ping_interval_s
        while True:
            try:
                frame = await asyncio.wait_for(
                    channel.queue.get(), interval
                )
            except asyncio.TimeoutError:
                if not await self._session_still_valid(
                    channel.session_id
                ):
                    self.session_channels.revoke(channel)
                    continue  # the revoke frames drain next loop
                await send({
                    "type": "ping",
                    "epoch": self._session_epoch(channel.image_id),
                })
                continue
            if frame is None:
                return
            await send(frame)

    async def handle_session_live(self, request: web.Request) -> web.StreamResponse:
        """The live channel: WebSocket when the client asks to
        upgrade, SSE (text/event-stream) otherwise. Authenticated by
        the session middleware like every serving route; registration
        beyond the channel bounds answers 503 + Retry-After (explicit
        backpressure, never an eviction of someone else's channel).
        Deliberately NOT a SERVING_PREFIXES lane: a held-open channel
        must not occupy an admission slot or door budget for hours."""
        try:
            image_id = int(request.match_info["imageId"])
        except (TypeError, ValueError):
            return web.Response(status=400, text="bad image id")
        session_id = request.cookies.get("sessionid", "")
        omero_key = request.get("omero.session_key", "")
        want_ws = (
            request.headers.get("Upgrade", "").strip().lower()
            == "websocket"
        )
        channel = self.session_channels.register(
            image_id, session_id, omero_key,
            "ws" if want_ws else "sse",
        )
        if channel is None:
            return web.Response(
                status=503, text="Session plane at capacity",
                headers={"Retry-After": "1"},
            )
        try:
            if want_ws:
                return await self._session_ws(request, channel)
            return await self._session_sse(request, channel)
        finally:
            self.session_channels.unregister(channel)

    async def _session_ws(self, request: web.Request, channel) -> web.StreamResponse:
        import json as _json

        ws = web.WebSocketResponse()
        await ws.prepare(request)
        await ws.send_json(self._session_hello(channel))

        async def _pump_then_close() -> None:
            # when the pump sees the close sentinel (drain handoff,
            # revocation, shutdown) it returns — closing the socket
            # here unblocks the reader loop below, so the handler
            # unwinds without waiting on a silent client
            try:
                await self._session_pump(channel, ws.send_json)
            finally:
                if not ws.closed:
                    await ws.close()

        # the pump is a TRACKED per-channel task: cancelled (and
        # awaited) in the finally below, so a dropped socket can
        # never leak a pump into the loop
        pump = asyncio.get_running_loop().create_task(
            _pump_then_close()
        )
        try:
            async for msg in ws:
                if msg.type == web.WSMsgType.TEXT:
                    try:
                        frame = _json.loads(msg.data)
                    except ValueError:
                        continue  # a garbled frame is a no-op
                    self._session_inbound(channel, frame)
                elif msg.type in (
                    web.WSMsgType.ERROR, web.WSMsgType.CLOSE,
                ):
                    break
        finally:
            pump.cancel()
            try:
                await pump
            except asyncio.CancelledError:
                if not pump.cancelled():
                    raise  # the HANDLER was cancelled: propagate
            except (ConnectionResetError, ConnectionError, OSError):
                pass  # a send racing a gone socket IS the close
        return ws

    async def _session_sse(self, request: web.Request, channel) -> web.StreamResponse:
        """The SSE fallback: same frames, one per ``data:`` event.
        Inbound geometry rides POST /session/{imageId}/viewport
        instead (SSE is one-directional)."""
        import json as _json

        resp = web.StreamResponse(
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "X-Accel-Buffering": "no",
            }
        )
        await resp.prepare(request)

        async def send(frame: dict) -> None:
            data = _json.dumps(frame, separators=(",", ":"))
            await resp.write(b"data: " + data.encode() + b"\n\n")

        try:
            await send(self._session_hello(channel))
            await self._session_pump(channel, send)
            await resp.write_eof()
        except (ConnectionResetError, ConnectionError, OSError):
            pass  # the viewer went away: close is the outcome
        return resp

    async def handle_session_viewport(self, request: web.Request) -> web.Response:
        """Viewport-geometry report for SSE clients (WS clients send
        the same frame inline). The rect supersedes the prefetcher's
        fixed span band for this (session, image) stream."""
        import json as _json

        try:
            image_id = int(request.match_info["imageId"])
        except (TypeError, ValueError):
            return web.Response(status=400, text="bad image id")
        try:
            body = _json.loads(await request.read())
        except Exception:
            return web.Response(status=400, text="bad viewport body")
        if not isinstance(body, dict):
            return web.Response(status=400, text="bad viewport body")
        noted = self._note_viewport(
            request.get("omero.session_key", ""), image_id, body
        )
        if not noted and self.prefetcher is not None:
            return web.Response(status=400, text="bad viewport rect")
        return web.json_response({"noted": noted})

    def _annotation_changed(self, image_id: int, sub_epoch: int) -> None:
        """Every annotation write: bump-and-tell. The image purge
        fans out cluster-wide through the existing epoch machinery
        (remote replicas' inbound purge becomes THEIR channels' delta
        push), and local subscribers additionally get the annotation
        sub-epoch frame."""
        self._invalidate_image(image_id)
        if self.session_channels is not None:
            self.session_channels.push_delta(
                image_id,
                epoch=self._session_epoch(image_id),
                kind="annotations",
                annotation_epoch=sub_epoch,
            )

    async def _annotation_body(self, request: web.Request):
        import json as _json

        try:
            body = _json.loads(await request.read())
        except Exception:
            return None
        return body if isinstance(body, dict) else None

    async def handle_annotations_create(self, request: web.Request) -> web.Response:
        try:
            image_id = int(request.match_info["imageId"])
        except (TypeError, ValueError):
            return web.Response(status=400, text="bad image id")
        body = await self._annotation_body(request)
        if body is None:
            return web.Response(status=400, text="bad annotation body")
        try:
            record, sub_epoch = self.annotations.create(image_id, body)
        except TileError as e:
            return web.Response(status=e.code, text=e.message)
        self._annotation_changed(image_id, sub_epoch)
        return web.json_response(
            {"annotation": record, "epoch": sub_epoch}, status=201
        )

    async def handle_annotations_list(self, request: web.Request) -> web.Response:
        try:
            image_id = int(request.match_info["imageId"])
        except (TypeError, ValueError):
            return web.Response(status=400, text="bad image id")
        return web.json_response(self.annotations.list(image_id))

    async def handle_annotation_get(self, request: web.Request) -> web.Response:
        try:
            image_id = int(request.match_info["imageId"])
        except (TypeError, ValueError):
            return web.Response(status=400, text="bad image id")
        record = self.annotations.get(
            image_id, request.match_info["annId"]
        )
        if record is None:
            return web.Response(status=404, text="no such annotation")
        return web.json_response({"annotation": record})

    async def handle_annotation_update(self, request: web.Request) -> web.Response:
        try:
            image_id = int(request.match_info["imageId"])
        except (TypeError, ValueError):
            return web.Response(status=400, text="bad image id")
        body = await self._annotation_body(request)
        if body is None:
            return web.Response(status=400, text="bad annotation body")
        try:
            result = self.annotations.update(
                image_id, request.match_info["annId"], body
            )
        except TileError as e:
            return web.Response(status=e.code, text=e.message)
        if result is None:
            return web.Response(status=404, text="no such annotation")
        record, sub_epoch = result
        self._annotation_changed(image_id, sub_epoch)
        return web.json_response(
            {"annotation": record, "epoch": sub_epoch}
        )

    async def handle_annotation_delete(self, request: web.Request) -> web.Response:
        try:
            image_id = int(request.match_info["imageId"])
        except (TypeError, ValueError):
            return web.Response(status=400, text="bad image id")
        sub_epoch = self.annotations.delete(
            image_id, request.match_info["annId"]
        )
        if sub_epoch is None:
            return web.Response(status=404, text="no such annotation")
        self._annotation_changed(image_id, sub_epoch)
        return web.json_response({"deleted": True, "epoch": sub_epoch})

    # -- ingest plane (ingest/, r24) ------------------------------------

    def _ingest_snapshot(self) -> dict:
        if self.ingest is None:
            return {"enabled": False}
        out = self.ingest.snapshot()
        out["enabled"] = True
        return out

    async def _ingest_allowed(
        self, image_id: int, session_key: str
    ) -> bool:
        """Write-permission check against the metadata resolver. A
        resolver without a write surface (the plain filesystem
        registry — no ACL model at all) allows writes, matching the
        read posture; a permission-scoped resolver (db/metadata)
        answers from the OMERO permissions long (can_write)."""
        resolver = getattr(
            self.pixels_service, "metadata_resolver", None
        )
        can_write = getattr(resolver, "can_write_image", None)
        if can_write is None:
            return True
        loop = asyncio.get_running_loop()
        return bool(
            await loop.run_in_executor(
                None, can_write, image_id, session_key
            )
        )

    async def _ingest_commit(
        self,
        request: web.Request,
        image_id: int,
        tiles: list,
    ) -> web.Response:
        """The shared write path: ACL -> scheduler (pinned
        non-degradable, never trains sweep/prefetch) -> stage+commit
        on a worker thread -> epoch bump FIRST, then every purge and
        the session delta frames (the r17 write-side contract)."""
        from ..ingest import IngestError

        session_key = request.get("omero.session_key", "")
        if not await self._ingest_allowed(image_id, session_key):
            return web.Response(
                status=403, text=f"Cannot write Image:{image_id}"
            )
        sched = self.scheduler
        permit = None
        deadline = Deadline.after(self.request_budget_s)
        if sched is not None:
            # the ingest scheduler pin: writes are interactive-class
            # but NEVER degradable (a "degraded" write makes no
            # sense), and they must not train the viewer-facing
            # models — a linear acquisition scan IS the canonical
            # sweep shape, and feeding it to the sweep detector or
            # prefetcher would demote/chase the writer's own session
            try:
                permit = await sched.acquire(
                    PRIORITY_INTERACTIVE, deadline, degradable=False
                )
            except TileError as e:
                return self._failure_response(request, e)
        try:
            plane = self.ingest

            def _commit() -> dict:
                with obs_recorder.ambient_stage("ingest"):
                    return plane.write_tiles(
                        image_id, tiles, session_key=session_key
                    )

            loop = asyncio.get_running_loop()
            # copy_context: the obs ambient record is a contextvar and
            # run_in_executor does not propagate it on its own — the
            # "ingest" stage stamp must land on THIS request's record
            cvctx = contextvars.copy_context()
            try:
                stats = await loop.run_in_executor(
                    None, lambda: cvctx.run(_commit)
                )
            except IngestError as e:
                return web.Response(status=e.code, text=e.message)
            except TileError as e:
                return self._failure_response(request, e)
            except Exception as e:
                # a store/codec failure mid-commit is a dependency
                # problem, not a missing image — never the generic
                # 404 mapping. Nothing partial became visible: each
                # object publishes atomically and the fault points
                # fire BEFORE the publish.
                log.warning("ingest commit failed: %s", e)
                return web.Response(
                    status=503, text=f"ingest commit failed: {e}"
                )
        finally:
            if permit is not None:
                # writes never train the read service-time EWMA: a
                # multi-second shard rebuild would inflate the
                # estimate and engage read degradation spuriously
                sched.release(permit, train=False)
        # commit is durable: bump the image epoch FIRST (r17 — every
        # consistency decision downstream keys on it), then purge
        # every local tier, then the best-effort cluster fan-out
        epoch = None
        cache_plane = self.cache_plane
        if cache_plane is not None and cache_plane.epochs is not None:
            await cache_plane.epochs.bump(image_id)
            epoch = cache_plane.epochs.known(image_id)
        else:
            # no epoch registry: synthesize a local token so open
            # buffers' shard-index memos still invalidate
            self._ingest_epoch_seq += 1
            note = getattr(self.pixels_service, "note_epoch", None)
            if note is not None:
                note(image_id, self._ingest_epoch_seq)
        self._invalidate_image(image_id)
        if self.session_channels is not None:
            # tile-granular delta on top of _invalidate_local's
            # whole-image frame: subscribed viewers re-fetch just the
            # written tiles instead of their whole viewport
            self.session_channels.push_delta(
                image_id,
                epoch=self._session_epoch(image_id),
                tiles=[t[:7] for t in tiles],
            )
        body = {"image": image_id, "epoch": epoch}
        body.update(stats)
        return web.json_response(body)

    async def handle_ingest_tile(self, request: web.Request) -> web.Response:
        """PUT /image/{imageId}/tile/{z}/{c}/{t}?x&y&w&h — one raw
        tile write: body is w*h big-endian pixels of the image's
        dtype (the byte order the raw /tile read surface serves, so
        PUT bytes round-trip to GET bytes exactly). Readable back
        byte-identical through every read surface the moment the
        response returns."""
        try:
            image_id = int(request.match_info["imageId"])
            z = int(request.match_info["z"])
            c = int(request.match_info["c"])
            t = int(request.match_info["t"])
            x = int(request.query["x"])
            y = int(request.query["y"])
            w = int(request.query["w"])
            h = int(request.query["h"])
        except (KeyError, TypeError, ValueError):
            return web.Response(
                status=400,
                text="expected /image/{id}/tile/{z}/{c}/{t}?x&y&w&h "
                "with integer values",
            )
        raw = await request.read()
        return await self._ingest_commit(
            request, image_id, [(z, c, t, x, y, w, h, raw)]
        )

    async def handle_ingest_planes(self, request: web.Request) -> web.Response:
        """POST /image/{imageId}/planes?planes=z:c:t,z:c:t,... —
        batched whole-plane append: the body is the listed planes'
        raw big-endian pixels concatenated in order, each a full
        size_x * size_y plane. One commit, one epoch bump — the
        batch's natural unit for an acquisition loop appending a
        z-stack or timepoint."""
        try:
            image_id = int(request.match_info["imageId"])
        except (TypeError, ValueError):
            return web.Response(status=400, text="bad image id")
        spec = request.query.get("planes", "")
        coords = []
        try:
            for part in spec.split(","):
                z, c, t = (int(v) for v in part.split(":"))
                coords.append((z, c, t))
        except (TypeError, ValueError):
            return web.Response(
                status=400,
                text="expected ?planes=z:c:t[,z:c:t...] "
                "with integer coordinates",
            )
        session_key = request.get("omero.session_key", "")
        loop = asyncio.get_running_loop()
        meta = await loop.run_in_executor(
            None, self.pixels_service.get_pixels, image_id, session_key
        )
        if meta is None:
            return web.Response(
                status=404, text=f"Cannot find Image:{image_id}"
            )
        raw = await request.read()
        if not raw or len(raw) % len(coords):
            return web.Response(
                status=400,
                text=f"body ({len(raw)} bytes) is not {len(coords)} "
                "equal whole planes",
            )
        step = len(raw) // len(coords)
        tiles = [
            (z, c, t, 0, 0, meta.size_x, meta.size_y,
             raw[i * step:(i + 1) * step])
            for i, (z, c, t) in enumerate(coords)
        ]
        return await self._ingest_commit(request, image_id, tiles)

    async def handle_internal_purge(self, request: web.Request) -> web.Response:
        """Inbound half of the purge fan-out. Requires the peer
        header (the same loop guard as tile forwarding: a peer-
        originated purge is terminal here; the cluster guard
        middleware has already authenticated it when a secret is
        configured). The forwarded epoch advances this replica's
        local high-water mark so an in-flight replica push against
        the purged image is rejected without a Redis round trip."""
        if PEER_HEADER not in request.headers:
            return web.Response(status=403, text="peer requests only")
        try:
            image_id = int(request.match_info["imageId"])
        except (TypeError, ValueError):
            return web.Response(status=400, text="bad image id")
        epoch_raw = request.headers.get(EPOCH_HEADER)
        if epoch_raw is not None:
            try:
                self.cache_plane.note_epoch(image_id, int(epoch_raw))
            except (TypeError, ValueError):
                pass  # a malformed epoch is an absent epoch
        self._invalidate_local(image_id)
        return web.json_response({"purged": image_id})

    async def handle_internal_replica(self, request: web.Request) -> web.Response:
        """Inbound next-owner replication (cluster/replicate.py): one
        hot entry, framed exactly like an L2 value (epoch stamp
        included), admitted into the LOCAL result cache so an owner
        crash finds the hot set already resident here. A push whose
        epoch predates a purge this replica has seen is dropped —
        replication must never resurrect invalidated bytes."""
        if PEER_HEADER not in request.headers:
            return web.Response(status=403, text="peer requests only")
        if self.result_cache is None:
            return web.Response(status=503, text="cache disabled")
        key = request.headers.get(KEY_HEADER)
        if not key:
            return web.Response(status=400, text="missing key header")
        from ..cache.plane.l2 import decode_entry_epoch

        body = await request.read()
        entry, epoch = decode_entry_epoch(body)
        if entry is None:
            return web.Response(status=400, text="malformed frame")
        plane = self.cache_plane
        if not plane.verify_entry_bytes(
            entry, "replica", member=request.headers.get(PEER_HEADER)
        ):
            # corrupt push: refuse the bytes AND let the ledger feed
            # the suspicion quorum — replication must never implant
            # wrong-but-200 bytes into this replica's caches
            return web.Response(status=400, text="integrity check failed")
        if plane.replica_push_stale(key, epoch):
            if plane.replicator is not None:
                plane.replicator.rejected_stale += 1
            return web.json_response({"stored": False, "stale": True})
        await self.result_cache.put(
            key, entry, generation=self.result_cache.generation()
        )
        if plane.replicator is not None:
            plane.replicator.received += 1
        return web.json_response({"stored": True})

    async def handle_internal_transfer(self, request: web.Request) -> web.Response:
        """Outbound half of join-time warm-up: this replica's hottest
        RAM entries as one bounded, length-prefixed payload. The
        joiner pulls each live peer once and serves warm within one
        transfer round."""
        if PEER_HEADER not in request.headers:
            return web.Response(status=403, text="peer requests only")
        limit = self.config.cluster.transfer_max_entries
        raw = request.query.get("limit")
        if raw is not None:
            try:
                limit = min(limit, max(0, int(raw)))
            except (TypeError, ValueError):
                return web.Response(status=400, text="bad limit")
        payload = self.cache_plane.hot_transfer_payload(limit)
        return web.Response(
            body=payload, content_type="application/octet-stream"
        )

    async def handle_internal_handoff(self, request: web.Request) -> web.Response:
        """Inbound half of the graceful-drain handoff: a draining
        peer's RAM hot set (transfer framing), absorbed through the
        same epoch-checked path as a join warm-up — so a rolling
        restart keeps the fleet's warm-hit rate instead of paying a
        re-render per key."""
        if PEER_HEADER not in request.headers:
            return web.Response(status=403, text="peer requests only")
        body = await request.read()
        if request.content_type == "application/json":
            # session-plane handoff (r22): the draining peer's live-
            # channel subscription summary rides the same route as
            # JSON; cache batches stay octet-stream. Routed on
            # content type so the two handoffs share one signed
            # surface without ambiguity.
            import json as _json

            if self.session_channels is None:
                return web.Response(
                    status=503, text="session plane disabled"
                )
            try:
                payload = _json.loads(body)
            except Exception:
                return web.Response(status=400, text="bad handoff body")
            if not isinstance(payload, dict) or (
                payload.get("kind") != "session_handoff"
            ):
                return web.Response(status=400, text="bad handoff kind")
            absorbed = self.session_channels.absorb_handoff(payload)
            return web.json_response({"absorbed": absorbed})
        if self.cache_plane is None or self.result_cache is None:
            return web.Response(status=503, text="cache disabled")
        stored = await self.cache_plane.absorb_handoff(
            body, member=request.headers.get(PEER_HEADER)
        )
        return web.json_response({"stored": stored})

    async def handle_internal_digest(self, request: web.Request) -> web.Response:
        """Anti-entropy digest (cluster/repair.py): a compact
        (key, epoch) summary of this replica's hottest RAM entries,
        checksummed so an unchanged peer costs one comparison."""
        if PEER_HEADER not in request.headers:
            return web.Response(status=403, text="peer requests only")
        limit = self.cache_plane.digest_limit()
        raw = request.query.get("limit")
        if raw is not None:
            try:
                limit = min(limit, max(0, int(raw)))
            except (TypeError, ValueError):
                return web.Response(status=400, text="bad limit")
        return web.Response(
            body=self.cache_plane.digest_payload(limit),
            content_type="application/json",
        )

    async def handle_internal_pull(self, request: web.Request) -> web.Response:
        """Anti-entropy pull: the requested entries (those present
        locally), transfer-framed. Key count and payload bytes are
        both bounded — a repair round can never be made expensive by
        its peer."""
        if PEER_HEADER not in request.headers:
            return web.Response(status=403, text="peer requests only")
        if self.cache_plane is None:
            return web.Response(status=503, text="cache disabled")
        import json as _json

        try:
            parsed = _json.loads(await request.read())
            keys = parsed.get("keys")
        except Exception:
            keys = None
        if not isinstance(keys, list):
            return web.Response(status=400, text="bad key list")
        payload = await self.cache_plane.pull_payload(keys)
        return web.Response(
            body=payload, content_type="application/octet-stream"
        )

    async def handle_internal_drain(self, request: web.Request) -> web.Response:
        """Operator-side drain trigger: run (or join) the planned-
        leave protocol. ``?wait=1`` answers when the drain completes
        (the rolling-restart driver's lever — the caller then knows
        the hot set is handed off and the lease released before it
        stops the process); without it the drain runs in the
        background and the current state comes back immediately.
        Idempotent — a second POST joins the first run."""
        if PEER_HEADER not in request.headers:
            return web.Response(status=403, text="peer requests only")
        if self.drainer is None:
            return web.Response(status=503, text="no cluster plane")
        wait = request.query.get("wait", "").strip().lower() in (
            "1", "true", "yes"
        )
        if wait:
            stats = await self.drainer.drain()
            return web.json_response(
                {"state": self.drainer.state, "stats": stats}
            )
        task = asyncio.ensure_future(self.drainer.drain())
        # consume the result if nobody ever polls ("Task exception
        # was never retrieved" guard; the protocol itself degrades)
        task.add_done_callback(lambda t: t.cancelled() or t.exception())
        return web.json_response(self.drainer.snapshot())

    def _full_plane_extent(self, ctx: TileCtx):
        """(size_x, size_y) of the ctx's plane at its resolution
        level, or None — the w/h=0 normalization lookup. Answers from
        the pixels service's caches (metadata + open-buffer LRU), so
        repeated full-plane requests cost dict probes."""
        svc = self.pixels_service
        try:
            if ctx.resolution in (None, 0):
                meta = svc.get_pixels(
                    ctx.image_id, session_key=ctx.omero_session_key
                )
                return (
                    None if meta is None
                    else (meta.size_x, meta.size_y)
                )
            buf = svc.get_pixel_buffer(
                ctx.image_id, session_key=ctx.omero_session_key
            )
            if buf is None or not (
                0 <= ctx.resolution < buf.resolution_levels
            ):
                return None
            return buf.level_size(ctx.resolution)
        except Exception:
            log.debug("full-plane extent lookup failed", exc_info=True)
            return None

    async def _normalize_region(self, ctx: TileCtx) -> None:
        """Rewrite w/h=0 full-plane defaulting to the explicit
        spelling BEFORE any key derives from the region, so both
        spellings of the same tile share one cache entry, one
        single-flight, and one batch lane (the KNOWN_GAPS
        duplicate-bytes item). The rewrite is EXACTLY the pipeline's
        ``resolve_region`` defaulting (w==0 -> sizeX, h==0 -> sizeY,
        regardless of x/y) — so an out-of-bounds spelling like
        ``x=100&w=0`` normalizes to the same region the pipeline
        rejects with 404, and cache on/off cannot change a status. A
        failed lookup leaves the region untouched — the pipeline
        resolves it as before, and the two spellings merely cache
        separately like they always did."""
        if ctx.region.width > 0 and ctx.region.height > 0:
            return
        extent = await asyncio.get_running_loop().run_in_executor(
            None, lambda: self._full_plane_extent(ctx)
        )
        if extent is None:
            return
        if ctx.region.width == 0:
            ctx.region.width = extent[0]
        if ctx.region.height == 0:
            ctx.region.height = extent[1]

    async def handle_get_tile(self, request: web.Request) -> web.Response:
        log.info("Get tile")
        params = dict(request.match_info)
        params.update(request.query)
        try:
            ctx = TileCtx.from_params(
                params, request.get("omero.session_key")
            )
        except TileError as e:
            return web.Response(status=400, text=e.message)
        return await self._serve(request, ctx)

    async def handle_get_render(self, request: web.Request) -> web.Response:
        """The rendered-tile surface: same path shape, auth, deadline,
        admission, cache, and conditional-GET semantics as /tile —
        plus a RenderSpec parsed from the query (render/model.py).
        Spec grammar errors are 400s; the ``c`` QUERY param (channel
        selection) never collides with the ``c`` PATH segment, which
        stays the default channel when no selection narrows it."""
        log.info("Get render")
        try:
            ctx = TileCtx.from_params(
                dict(request.match_info), request.get("omero.session_key")
            )
        except TileError as e:
            return web.Response(status=400, text=e.message)
        spec, err = self.build_render_spec(request.query, ctx.c)
        if err is not None:
            return err
        if self.annotations is not None and request.query.get(
            "annotations", ""
        ).strip().lower() in ("1", "true", "yes"):
            # annotation overlays (session/, r22): stored shapes ARE
            # ShapeSpecs from the roi= grammar, so compositing is just
            # appending them to the mask tuple — the joined spec's
            # signature (hence cache key and ETag) is identical to an
            # explicit roi= request carrying the same shapes, and the
            # raster path is the engine-independent masks.py math, so
            # overlays are byte-identical host vs device by the same
            # argument roi= already is
            stored = self.annotations.shapes(ctx.image_id)
            if stored:
                import dataclasses as _dc

                from ..render.masks import MAX_SHAPES

                # same bound the roi= grammar enforces, applied to
                # the JOINED set — explicit roi shapes win the budget
                merged = (spec.masks + stored)[:MAX_SHAPES]
                spec = _dc.replace(spec, masks=merged)
        ctx.render = spec
        ctx.format = spec.format  # drives Content-Type + filename
        # query x/y/w/h/resolution ride along exactly like /tile's
        err = self._apply_region_params(ctx, request.query)
        if err is not None:
            return err
        return await self._serve(request, ctx)

    @staticmethod
    def _apply_region_params(ctx: TileCtx, query) -> Optional[web.Response]:
        """Apply the x/y/w/h/resolution query params — the ONE parse
        for every query-region surface (/render, /histogram), so a
        bounds or message change can never drift between them.
        Returns a 400 response on a malformed value, else None."""
        try:
            ctx.region.x = int(query.get("x", 0))
            ctx.region.y = int(query.get("y", 0))
            ctx.region.width = int(query.get("w", 0))
            ctx.region.height = int(query.get("h", 0))
            res = query.get("resolution")
            ctx.resolution = None if res is None else int(res)
        except (TypeError, ValueError) as e:
            return web.Response(status=400, text=str(e))
        return None

    def build_render_spec(self, query, default_channel: int):
        """Parse + validate a RenderSpec the ONE way — the native
        /render handler and every protocol adapter call this, so
        grammar 400s, default quality, and the LUT-registry check can
        never drift between dialects. Returns (spec, None) or
        (None, 400 response)."""
        from ..render.model import RenderSpec

        try:
            spec = RenderSpec.from_params(
                query,
                default_channel=default_channel,
                default_quality=self.config.render.jpeg_quality,
            )
        except TileError as e:
            return None, web.Response(status=400, text=e.message)
        for ch in spec.channels:
            if ch.lut is not None and (
                ch.lut not in self.pipeline.lut_registry
            ):
                return None, web.Response(
                    status=400, text=f"Unknown LUT: {ch.lut}"
                )
        return spec, None

    async def handle_get_histogram(self, request: web.Request) -> web.Response:
        """The analysis surface: per-channel pixel-intensity
        histograms (render/analysis.py) in the omero-ms-image-region
        dialect (``bins``, ``usePixelsTypeRange``, region/resolution
        params, the render channel grammar for multi-channel +
        windows). The JSON body is keyed, cached, ETagged, admitted,
        and deadline-bounded EXACTLY like a tile — ``_serve`` is the
        one serving path."""
        log.info("Get histogram")
        from ..render.analysis import HistogramSpec

        try:
            ctx = TileCtx.from_params(
                dict(request.match_info), request.get("omero.session_key")
            )
            spec = HistogramSpec.from_params(
                request.query,
                default_channel=ctx.c,
                max_bins=self.config.analysis.max_bins,
            )
        except TileError as e:
            return web.Response(status=400, text=e.message)
        ctx.analysis = spec
        ctx.format = "json"  # drives Content-Type
        err = self._apply_region_params(ctx, request.query)
        if err is not None:
            return err
        return await self._serve(request, ctx)

    async def _serve(self, request: web.Request, ctx: TileCtx) -> web.Response:
        cache = self.result_cache
        rec = request.get("obs.rec")
        ctx.obs = rec  # the pipeline stamps per-lane through the ctx
        if self.scheduler is not None:
            # classify BEFORE serving (header override > prefetch
            # purpose markers > sweep detection), then feed this
            # access to the sweep detector — a sweep demotes the
            # session's NEXT request, not this one
            ctx.priority = classify(
                request.headers, ctx.omero_session_key,
                self.sweep_detector, self._priority_header,
            )
            if header_priority(
                request.headers, self._priority_header
            ) is None:
                # only UNLABELED traffic trains the sweep detector: a
                # client honestly labeling its lookahead as prefetch
                # produces the canonical constant-stride sweep shape,
                # and learning from it would demote the whole session
                # — shedding the same user's interactive pans.
                # Detector-demoted (bulk) requests still observe, so a
                # continuing robot walk keeps refreshing its TTL.
                self.sweep_detector.observe(
                    ctx.omero_session_key, ctx.image_id, ctx.z, ctx.c,
                    ctx.t, ctx.resolution, ctx.region.x, ctx.region.y,
                    ctx.region.width, ctx.region.height,
                )
        if rec is not None:
            rec.tag("priority", PRIORITY_NAMES.get(
                ctx.priority, "interactive"
            ))
            rec.tag("engine", getattr(self.pipeline, "_engine", None))
        if cache is not None:
            with obs_recorder.ambient_stage("cache_probe"):
                await self._normalize_region(ctx)
        inm = request.headers.get("If-None-Match", "")
        key = None
        plane_entry = plane_source = None
        plane_epoch = None
        plane_pending = None
        plane_generation = None
        if cache is not None:
            key = ctx.cache_key(self.pipeline.encode_signature())
            with obs_recorder.ambient_stage("cache_probe"):
                entry = await cache.get(key)
            if entry is not None and self.cache_plane is not None:
                # hot-set replication qualifies on frequency, and most
                # keys cross the bar on a HIT, not a fill (O(1) when
                # it declines)
                self.cache_plane.note_hit(key, entry)
            if entry is None and self.cache_plane is not None:
                # the cluster consult, between local miss and render:
                # shared L2 first, then one bounded GET to the key's
                # owner. Generation snapshot BEFORE the network hop —
                # an invalidation racing the fetch must block the
                # local re-admission (the disk-tier precedent).
                peer_originated = PEER_HEADER in request.headers
                generation = plane_generation = cache.generation()
                (
                    plane_entry, plane_source, plane_epoch,
                    plane_pending,
                ) = await self.cache_plane.fetch(
                    key,
                    request.path_qs,
                    request.cookies.get("sessionid"),
                    peer_originated=peer_originated,
                )
                if peer_originated and plane_epoch is None:
                    # owner side of a peer hop: the requester forwards
                    # the epoch IT observed before the hop, so this
                    # replica's fill stamps the requester's pre-render
                    # snapshot without an extra Redis round trip
                    plane_epoch = _parse_epoch(
                        request.headers.get(EPOCH_HEADER)
                    )
                if plane_entry is not None:
                    if await self._authorize_cached(ctx):
                        await cache.put(
                            key, plane_entry, generation=generation
                        )
                        if self.prefetcher is not None and (
                            ctx.analysis is None
                        ):
                            # histogram streams never train the tile
                            # prefetcher: its predictions carry no
                            # analysis spec and would warm RAW tiles
                            self.prefetcher.observe(ctx)
                        if inm and etag_matches(inm, plane_entry.etag):
                            return web.Response(
                                status=304,
                                headers=self._cache_headers(
                                    plane_entry.etag
                                ),
                            )
                        return self._tile_response(
                            ctx, plane_entry.body, plane_entry.filename,
                            plane_entry.etag, x_cache=plane_source,
                        )
                    # authorization didn't confirm: full path below
                    # maps 403/404/503 properly (and never admits the
                    # fetched bytes under an unverified session)
                    plane_entry = None
            if entry is not None:
                if inm and etag_matches(inm, entry.etag) and (
                    self.config.cache.etag_precheck
                ):
                    # conditional-GET short circuit BEFORE the
                    # session join / ACL re-check: a matching strong
                    # content ETag proves the client already holds
                    # these exact bytes — revalidation discloses
                    # nothing new (config `cache.etag-precheck: false`
                    # moves this below the authorization step)
                    return web.Response(
                        status=304, headers=self._cache_headers(entry.etag)
                    )
                if await self._authorize_cached(ctx):
                    if self.prefetcher is not None and (
                        ctx.analysis is None
                    ):
                        self.prefetcher.observe(ctx)
                    if inm and etag_matches(inm, entry.etag):
                        return web.Response(
                            status=304,
                            headers=self._cache_headers(entry.etag),
                        )
                    return self._tile_response(
                        ctx, entry.body, entry.filename, entry.etag,
                        x_cache="hit",
                    )
                # authorization didn't confirm: fall through to the
                # full pipeline path, which maps 403/404/503 properly

        ctx.trace_context = TRACER.inject(request.get("span"))
        # the end-to-end budget: minted once here, decremented by
        # every layer below (scheduler wait, bus wait, batching,
        # store retries) — resilience/deadline.py
        ctx.deadline = Deadline.after(self.request_budget_s)

        sched = self.scheduler
        permit = None
        full_res_key = None
        served = False
        try:
            if sched is not None:
                # the SLO gate sits HERE — between the cache and the
                # pipeline — so hits never wait in the queue. A shed
                # (queue genuinely full, this request the least
                # valuable work in sight) or an in-queue expiry
                # surfaces as 503/504 through the one failure shaper.
                try:
                    permit = await sched.acquire(
                        ctx.priority, ctx.deadline,
                        degradable=self._degradable(ctx),
                    )
                except TileError as e:
                    if rec is not None and isinstance(
                        e, ServiceUnavailableError
                    ):
                        # acquire's only 503 is a shed decision —
                        # tagged so the record's outcome reads "shed",
                        # not "unavailable" (dependency-down 503s
                        # carry no shed_at)
                        rec.tag("shed_at", "queue")
                    return self._failure_response(request, e)
                if rec is not None and permit.queued_s > 0.0:
                    rec.stamp("queue_wait", permit.queued_s)
                if permit.degraded:
                    # deadline at risk: serve the next-lower pyramid
                    # level upscaled instead of risking a 504. The
                    # degraded resource has its OWN cache key + ETag
                    # (|deg=1): it never overwrites, nor serves as,
                    # the full-resolution entry.
                    ctx.degraded = 1
                    if cache is not None:
                        # keep the full-resolution key: if the image
                        # turns out to have no coarser level, the
                        # flight returns full-res bytes and the fill
                        # must land under THIS key, not |deg=1
                        full_res_key = key
                        key = ctx.cache_key(
                            self.pipeline.encode_signature()
                        )
                        dentry = await cache.get(key)
                        if dentry is not None and (
                            await self._authorize_cached(ctx)
                        ):
                            if inm and etag_matches(inm, dentry.etag):
                                return web.Response(
                                    status=304,
                                    headers={
                                        **self._cache_headers(
                                            dentry.etag
                                        ),
                                        "X-OMPB-Degraded": "1",
                                    },
                                )
                            return self._tile_response(
                                ctx, dentry.body, dentry.filename,
                                dentry.etag, x_cache="hit",
                                degraded=1,
                            )
            try:
                if key is not None:
                    if plane_pending is not None:
                        # the hedge fired: race the local render
                        # against the still-in-flight peer fetch and
                        # serve whichever finishes first
                        reply, early = await self._hedged_fetch(
                            request, ctx, key, full_res_key,
                            plane_epoch, plane_pending,
                            plane_generation, inm,
                        )
                        if early is not None:
                            return early
                    else:
                        reply = await self._fetch_tile(
                            ctx, key, full_res_key, plane_epoch
                        )
                else:
                    # cache.enabled: false disables the WHOLE
                    # subsystem, single-flight included — operators
                    # who turn it off (e.g. the chaos suite) get true
                    # per-request execution back
                    reply = await self.bus.request(
                        GET_TILE_EVENT,
                        ctx,
                        timeout_ms=self.config.event_bus_send_timeout_ms,
                    )
            except Exception as e:
                return self._failure_response(request, e)
            served = True
        finally:
            if plane_pending is not None and not plane_pending.done():
                # every exit cancels an unconsumed hedge task (the
                # degraded-hit early returns, acquire sheds, failures)
                plane_pending.cancel()
            if permit is not None:
                # failed requests don't train the service-time EWMA: a
                # fast-failing burst (404 loop, open breaker) would
                # collapse the estimate and disarm degradation
                sched.release(permit, train=served)

        # the full path just validated the session AND resolved the
        # image under its ACL: remember the verdict for the hit path
        # (only the hit path reads it — no bookkeeping when the cache
        # is off)
        if cache is not None:
            self._authz_record(ctx)
        if self.prefetcher is not None and ctx.analysis is None:
            self.prefetcher.observe(ctx)
        etag = reply.headers.get("etag")
        # the pipeline clears ctx.degraded when no coarser level
        # exists; the reply header carries the LEADER lane's final
        # state, so coalesced followers tag consistently
        served_degraded = int(reply.headers.get("degraded", 0) or 0)
        if inm and etag and etag_matches(inm, etag):
            # freshly rendered, but it matches what the client holds
            # (e.g. the cache was cold after a restart): spare the body
            headers = self._cache_headers(etag)
            if served_degraded:
                headers["X-OMPB-Degraded"] = str(served_degraded)
            return web.Response(status=304, headers=headers)
        return self._tile_response(
            ctx, reply.body, reply.headers.get("filename", ""), etag,
            x_cache="miss" if cache is not None else None,
            degraded=served_degraded,
        )


def create_app(
    config: Config,
    pixels_service: Optional[PixelsService] = None,
    session_store: Optional[OmeroWebSessionStore] = None,
    session_validator: Optional[SessionValidator] = None,
) -> web.Application:
    return PixelBufferApp(
        config, pixels_service, session_store, session_validator
    ).make_app()


def main(argv: Optional[list] = None) -> None:
    import argparse

    parser = argparse.ArgumentParser(description="TPU pixel-buffer service")
    parser.add_argument("--config", default="conf/config.yaml")
    parser.add_argument("--port", type=int, default=None)
    parser.add_argument(
        "--dev", action="store_true",
        help="accept any sessionid cookie (echo session store); "
        "implies an in-memory store — never use in production",
    )
    parser.add_argument("--registry", default=None,
                        help="image registry JSON (overrides config)")
    args = parser.parse_args(argv)
    config = Config.load(args.config, default_memory_store=args.dev)
    if args.port is not None:
        config.port = args.port
    if args.registry is not None:
        config.image_registry = args.registry
    from ..utils.logging_setup import configure_logging

    configure_logging(config.logging)
    session_store = None
    if args.dev:
        from ..auth.stores import EchoSessionStore

        session_store = EchoSessionStore()
    app = create_app(config, session_store=session_store)
    log.info("Starting HTTP server *:%d", config.port)
    web.run_app(app, port=config.port, access_log=None)


if __name__ == "__main__":
    main()
