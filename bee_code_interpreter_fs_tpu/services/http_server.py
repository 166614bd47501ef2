"""HTTP API (aiohttp): Execute, custom tools, and the files CRUD.

Endpoint parity with the reference's FastAPI app
(src/code_interpreter/services/http_server.py:75-215): POST /v1/execute,
POST /v1/parse-custom-tool, POST /v1/execute-custom-tool, PUT /v1/files,
GET/DELETE /v1/files/{hash}. Differences by design:

- /v1/execute accepts BOTH inline `source_code` and `source_file` (the
  reference required source_file while its own tests posted source_code —
  SURVEY.md §0.1); plus TPU fields `chip_count` and `env`.
- Responses include per-phase timings; GET /healthz is a cheap liveness probe.
- FastAPI/uvicorn are not available in this environment; aiohttp serves the
  same surface.
"""

from __future__ import annotations

import json
import logging
import math
import os

from aiohttp import web
from pydantic import BaseModel, Field, ValidationError

from ..utils import tracing
from ..utils.logs import new_request_id, request_id_var
from ..utils.metrics import PROMETHEUS_CONTENT_TYPE
from ..utils.tracing import TRACE_ID_RE, Tracer
from ..utils.validation import OBJECT_ID_RE
from .backends.base import SandboxSpawnError
from .code_executor import (
    CircuitOpenError,
    CodeExecutor,
    ExecutorError,
    LimitExceededError,
    QuotaExceededError,
    SessionLimitError,
    SessionRestoringError,
    StaleLeaseError,
    StateStoreDegradedError,
)
from .custom_tool_executor import (
    CustomToolExecuteError,
    CustomToolExecutor,
    CustomToolParseError,
)
from .perf_observer import summarize_profile
from .storage import Storage, StorageObjectNotFound

logger = logging.getLogger(__name__)


class ExecuteRequest(BaseModel):
    source_code: str | None = None
    source_file: str | None = None
    files: dict[str, str] = Field(default_factory=dict)
    timeout: float | None = Field(default=None, gt=0)
    env: dict[str, str] | None = None
    chip_count: int | None = Field(default=None, ge=0)
    profile: bool = False
    # Session affinity: requests sharing an executor_id run in one live
    # sandbox whose workspace persists across them. Empty/absent = stateless.
    executor_id: str | None = None
    # Admission control (fair-share scheduler). Body fields win; the
    # X-Tenant / X-Priority / X-Deadline-Seconds headers are the fallback
    # (gateways that can't rewrite bodies set headers). Absent = shared
    # tenant, interactive class, no deadline.
    tenant: str | None = None
    priority: str | None = None  # "interactive" | "batch"
    # Start within N seconds; 0 = "only if a slot is free right now".
    # ge (not gt) to match the header/metadata paths, which the scheduler
    # validates with the same >= 0 rule — one value, one verdict.
    deadline: float | None = Field(default=None, ge=0)
    # Per-request resource budget override (keys from services.limits:
    # memory_bytes/cpu_seconds/nproc/nofile/fsize_bytes/disk_bytes/
    # output_bytes). Layers over the configured default + lane budgets and
    # is min-clamped by the server caps — only ever tightens. Header
    # fallback: X-Sandbox-Limits (a JSON object). Breaches return 422 with
    # the typed violation kind.
    limits: dict[str, float] | None = None
    # Purity declaration (result memoization): this run reads no network,
    # no randomness, no wall clock — its output is a function of its
    # inputs. Declared-pure runs ride the content-addressed result memo:
    # an identical earlier run answers from its record (X-Memo: hit) with
    # zero chip-seconds billed. A promise, not a sandbox restriction.
    pure: bool = False


class ParseCustomToolRequest(BaseModel):
    tool_source_code: str


class ExecuteCustomToolRequest(BaseModel):
    tool_source_code: str
    tool_input_json: str
    # Same session semantics as ExecuteRequest.executor_id: tool calls
    # sharing an id see each other's workspace files.
    executor_id: str | None = None
    timeout: float | None = Field(default=None, gt=0)
    # The session-affinity key's tenant half (body-then-X-Tenant-header,
    # the same resolution as /v1/execute): a session created with a body
    # tenant must hash to the SAME replica from every route that can
    # touch it. Routing-only on this surface — custom-tool admission
    # itself runs under the shared tenant, as before.
    tenant: str | None = None


def _usage_row_text(tenant: str, row: dict) -> str:
    """One tenant's ledger line for the text renderers (shared by
    /statusz?format=text and /usage?format=text)."""
    violations = row.get("violations") or {}
    violation_text = (
        " violations["
        + " ".join(f"{k}={int(v)}" for k, v in sorted(violations.items()))
        + "]"
        if violations
        else ""
    )
    return (
        f"  {tenant}: chip_s={row.get('chip_seconds', 0.0)} "
        f"queue_s={row.get('queue_wait_seconds', 0.0)} "
        f"requests={int(row.get('requests', 0))} "
        f"batch_jobs={int(row.get('batch_jobs', 0))} "
        f"up_bytes={int(row.get('upload_bytes', 0))} "
        f"down_bytes={int(row.get('download_bytes', 0))} "
        f"recompiles={int(row.get('compile_cache_recompiles', 0))}"
        + violation_text
    )


def usage_text(body: dict) -> str:
    """Human-readable GET /usage (`?format=text`)."""
    if not body.get("enabled", False):
        return "usage metering: disabled\n"
    lines = [
        f"usage metering: tenants={body.get('tenant_count', 0)}"
        f"/{body.get('max_tenants', 0)} "
        f"flushes={body.get('flushes', 0)} "
        f"journal_lines={body.get('journal_lines', 0)}",
    ]
    tenants = body.get("tenants", {})
    if tenants:
        for tenant, row in sorted(tenants.items()):
            lines.append(_usage_row_text(tenant, row))
    else:
        lines.append("  (no usage recorded)")
    return "\n".join(lines) + "\n"


def _quota_row_text(tenant: str, row: dict) -> str:
    """One tenant's quota line for the text renderers (shared by
    /statusz?format=text and /quotas?format=text)."""
    policy = row.get("policy", {})
    budget = policy.get("chip_seconds_per_window", 0)
    parts = [f"  {tenant}:"]
    if budget:
        parts.append(
            f"chip_s={row.get('used_chip_seconds_window', 0.0)}/{budget}"
        )
    else:
        parts.append(
            f"chip_s={row.get('used_chip_seconds_window', 0.0)} (no budget)"
        )
    parts.append(f"in_flight={row.get('in_flight', 0)}")
    parts.append(f"denials={row.get('denials', 0)}")
    quarantined = row.get("quarantined_for_s", 0.0)
    if quarantined:
        parts.append(
            f"QUARANTINED {quarantined}s"
            f" (level {row.get('offender_level', 0)})"
        )
    elif row.get("offender_level", 0):
        parts.append(f"offender_level={row.get('offender_level', 0)}")
    return " ".join(parts)


def quotas_text(body: dict) -> str:
    """Human-readable GET /quotas (`?format=text`)."""
    if not body.get("enabled", False):
        return "quota enforcement: disabled\n"
    default = body.get("default_policy", {})
    lines = [
        "quota enforcement: "
        f"denials={body.get('denials_total', 0)} "
        f"policy_file={body.get('policy_file') or '(none)'} "
        f"overrides={len(body.get('tenant_overrides', ()))}",
        "  default: "
        f"chip_s/window={default.get('chip_seconds_per_window', 0)} "
        f"window={default.get('window_seconds', 0)}s "
        f"req/window={default.get('requests_per_window', 0)} "
        f"concurrent={default.get('max_concurrent', 0)} "
        f"violations/window={default.get('violations_per_window', 0)}",
    ]
    tenants = body.get("tenants", {})
    if tenants:
        for tenant, row in sorted(tenants.items()):
            lines.append(_quota_row_text(tenant, row))
    else:
        lines.append("  (no tenants observed)")
    return "\n".join(lines) + "\n"


def perf_text(body: dict) -> str:
    """Human-readable GET /perf (`?format=text`)."""
    if not body.get("enabled", False):
        return "perf observer: disabled\n"
    lines = [_perf_header_text(body)]
    series = body.get("series", {})
    if series:
        for key, row in sorted(series.items()):
            lines.append(_perf_series_text(key, row))
    else:
        lines.append("  (no latency series yet)")
    tenants = body.get("tenants", {})
    for tenant, row in sorted(tenants.items()):
        lines.append(_perf_series_text(f"tenant {tenant}", row))
    store = body.get("profile_store")
    if store is not None:
        lines.append(
            f"profiles: {store.get('entries', 0)} entries "
            f"{store.get('bytes', 0)} bytes "
            f"(captured {body.get('auto_profile', {}).get('captured', 0)}, "
            f"evictions {store.get('evictions', 0)})"
        )
    return "\n".join(lines) + "\n"


def _perf_header_text(body: dict) -> str:
    bands = body.get("bands", {})
    return (
        f"perf observer: status={body.get('status', 'normal')} "
        f"window={body.get('window_seconds', 0)}s "
        f"drift_q=p{int(float(body.get('drift_quantile', 0.95)) * 100)} "
        f"bands=x{bands.get('degraded_factor', 0)}"
        f"/x{bands.get('regressed_factor', 0)}"
    )


def _perf_series_text(key: str, row: dict) -> str:
    """One latency series' line for the text renderers (shared by
    /statusz?format=text and /perf?format=text)."""
    marker = "!!" if row.get("state") == "regressed" else "  "
    baseline = row.get("baseline_s")
    return (
        f"{marker}{key}: [{row.get('state', 'normal')}] "
        f"p50={row.get('p50_s', 0.0)}s p95={row.get('p95_s', 0.0)}s "
        f"p99={row.get('p99_s', 0.0)}s "
        f"baseline={baseline if baseline is not None else '-'}s "
        f"n={row.get('count', 0)} windows={row.get('windows', 0)}"
        + (
            f" regressions={row.get('regressions', 0)}"
            if row.get("regressions")
            else ""
        )
    )


def statusz_text(body: dict) -> str:
    """Human-readable /statusz (`?format=text`): the at-a-glance view
    that replaces an operator's ssh-and-grep loop.
    Module-level (not a handler closure) so the renderer is directly
    testable against edge-case bodies — empty fleet, overflow rows,
    wedged hosts with evidence."""
    lines = [
        f"status: {body.get('status', 'unknown')}   "
        f"inflight: {body.get('inflight', 0)}",
        "",
        "lanes:",
    ]
    for lane, entry in sorted(body.get("lanes", {}).items()):
        lines.append(
            f"  lane {lane}: pool={entry.get('pool_depth', 0)}"
            f"/{entry.get('pool_target', 0)} "
            f"in_use={entry.get('in_use', 0)} "
            f"sessions={entry.get('session_held', 0)} "
            f"spawning={entry.get('spawning', 0)} "
            f"queued={entry.get('queued', 0)} "
            f"wait_ewma={entry.get('queue_wait_ewma_s', 0.0)}s "
            f"batch_occ={entry.get('batch_occupancy', 0.0)} "
            f"breaker={entry.get('breaker', 'closed')}"
        )
    if not body.get("lanes"):
        lines.append("  (no lanes)")
    autoscaler = body.get("autoscaler", {})
    lines.append("")
    if autoscaler.get("enabled"):
        lines.append(
            f"autoscaler: bounds=[{autoscaler.get('min_target')}"
            f"..{autoscaler.get('max_target')}] "
            f"static={autoscaler.get('static_target')}"
        )
        for lane, row in sorted(autoscaler.get("lanes", {}).items()):
            lines.append(
                f"  lane {lane}: target={row.get('target')} "
                f"demand={row.get('raw_demand')} "
                f"rate={row.get('arrival_rate_per_s')}/s "
                f"ups={row.get('scale_ups')} downs={row.get('scale_downs')} "
                f"reaped={row.get('reaped')}"
            )
    else:
        lines.append(
            "autoscaler: disabled "
            f"(static target {autoscaler.get('static_target', '?')})"
        )
    health = body.get("device_health", {})
    lines.append("")
    if health.get("enabled"):
        states = health.get("states", {})
        lines.append(
            "device health: "
            + " ".join(f"{k}={v}" for k, v in states.items())
            + f"   last_poll_age={health.get('last_poll_age_s')}s"
        )
        for host in health.get("hosts", ()):
            marker = "!!" if host.get("state") == "wedged" else "  "
            lines.append(
                f"{marker}lane {host.get('lane')} {host.get('host')} "
                f"[{host.get('state')}]"
                + (f" {host['reason']}" if host.get("reason") else "")
                + (
                    f" stall={host['stall_s']}s"
                    if host.get("stall_s")
                    else ""
                )
            )
    else:
        lines.append("device health: probe disabled")
    recovery = body.get("recovery", {})
    if recovery.get("fencing_enabled"):
        budget = recovery.get("fence_budget", {})
        lines.append(
            f"recovery: fences={recovery.get('fences_total', 0)} "
            f"readmissions={recovery.get('readmissions_total', 0)} "
            f"budget={budget.get('max_per_window', 0)}"
            f"/{budget.get('window_seconds', 0)}s "
            f"streak={recovery.get('readmit_streak', 0)}"
        )
        for scope, row in sorted(recovery.get("recovering", {}).items()):
            lines.append(
                f"  recovering {scope}: {row.get('streak')}/"
                f"{row.get('need')} clean ({row.get('reason', '')}, "
                f"{row.get('for_s')}s, {row.get('relapses')} relapse(s))"
            )
    elif recovery:
        lines.append("recovery: fencing disabled")
    cc = body.get("compile_cache", {})
    lines.append(
        f"compile cache: enabled={cc.get('enabled')} "
        f"entries={cc.get('entries')} bytes={cc.get('bytes')}"
    )
    otlp = body.get("otlp", {})
    if otlp.get("enabled"):
        lines.append(
            f"otlp: {otlp.get('endpoint')} queued={otlp.get('queued_spans')} "
            f"exported={otlp.get('exported_spans')} "
            f"dropped={otlp.get('dropped_spans')} "
            f"failures={otlp.get('export_failures')}"
        )
    else:
        lines.append("otlp: disabled")
    replicas = body.get("replicas", {})
    if replicas.get("enabled"):
        live = replicas.get("live")
        lines.append(
            f"replicas: self={replicas.get('self')} "
            + (
                f"live={'/'.join(live)} "
                f"proxied={replicas.get('proxied_total', 0)} "
                f"redirected={replicas.get('redirected_total', 0)}"
                if live is not None
                else f"store={replicas.get('store', '?')} (no peer ring)"
            )
        )
    usage = body.get("usage", {})
    if usage.get("enabled"):
        lines.append(
            f"usage: tenants={usage.get('tenant_count', 0)}"
            f"/{usage.get('max_tenants', 0)} "
            f"flushes={usage.get('flushes', 0)}"
        )
        for tenant, row in sorted(usage.get("tenants", {}).items()):
            lines.append(_usage_row_text(tenant, row))
    else:
        lines.append("usage: metering disabled")
    quotas = body.get("quotas", {})
    if quotas.get("enabled"):
        lines.append(
            f"quotas: denials={quotas.get('denials_total', 0)} "
            f"overrides={len(quotas.get('tenant_overrides', ()))}"
        )
        for tenant, row in sorted(quotas.get("tenants", {}).items()):
            lines.append(_quota_row_text(tenant, row))
    else:
        lines.append("quotas: enforcement disabled")
    perf = body.get("perf", {})
    if perf.get("enabled"):
        lines.append(_perf_header_text(perf))
        for key, row in sorted(perf.get("series", {}).items()):
            lines.append(_perf_series_text(key, row))
        store = perf.get("profile_store")
        if store is not None and (
            store.get("entries") or perf.get("auto_profile", {}).get("captured")
        ):
            lines.append(
                f"profiles: {store.get('entries', 0)} entries "
                f"{store.get('bytes', 0)} bytes"
            )
    else:
        lines.append("perf observer: disabled")
    sessions = body.get("sessions", ())
    durability = body.get("session_durability", {})
    if durability.get("enabled"):
        lines.append(
            f"sessions: {len(sessions)} live, "
            f"{durability.get('hibernated', 0)} hibernated "
            f"(saves={durability.get('saves', 0)} "
            f"restores={durability.get('restores', 0)} "
            f"conflicts={durability.get('conflicts', 0)} "
            f"idle_chip_s={durability.get('idle_chip_seconds_total', 0.0)})"
        )
    else:
        lines.append(f"sessions: {len(sessions)}")
    for row in sessions:
        lines.append(
            f"  {row.get('executor_id')}: lane={row.get('chip_count')} "
            f"idle={row.get('idle_s')}s busy={row.get('busy')} "
            f"requests={row.get('requests')} [{row.get('status')}]"
        )
    return "\n".join(lines) + "\n"


def create_http_app(
    code_executor: CodeExecutor,
    custom_tool_executor: CustomToolExecutor,
    storage: Storage,
    tracer: Tracer | None = None,
    router=None,
) -> web.Application:
    tracer = tracer or code_executor.tracer
    # Session→replica affinity (services/replicas.py): with a replica set
    # configured, session requests this replica does not own are proxied
    # (or 307-redirected) to the owner. None = single-replica mode: zero
    # routing code on any path.
    router = router if router is not None else code_executor.session_router

    async def route_session(
        request: web.Request, tenant: str | None, executor_id: str | None
    ):
        """Affinity gate for session-carrying routes: None = serve locally
        (stateless request, we own the key, or single-replica mode); a
        Response = the owner's answer (transparent proxy) or the 307
        redirect contract. A dead owner drops off the ring inside
        `forward`, so the loop re-evaluates against the survivors — the
        failover path: the key rehashes (usually to us) and serving
        continues after lease-fenced turnover of the dead owner's hosts.
        NOTE: the proxied NDJSON stream is relayed buffered — incremental
        events coalesce; the final body is identical."""
        if router is None or not executor_id:
            return None
        if router.peer_forwarded(request.headers.get("X-Replica-Forwarded-By")):
            # Forwarded by a PEER (the header carries the fleet's
            # shared-store secret — a client-spoofed value fails the
            # check and routes normally): serve HERE regardless of what
            # this replica's ring says. Ring views can diverge for up to
            # one TTL (per-replica proxy suspicions), and without this
            # guard a disagreement becomes an unbounded A→B→C→A proxy
            # cycle — one hop of disagreement costs at most one misplaced
            # session, never a loop.
            return None
        for _ in range(1 + len(router.ring.peers)):
            owner = router.owner_of(tenant, executor_id)
            if owner == router.ring.self_id:
                return None
            response = await router.forward(request, owner)
            if response is not None:
                return response
        return None

    def session_tenant(request: web.Request, req=None) -> str | None:
        """The tenant half of the affinity key — the SAME body-then-header
        resolution the scheduler sees, so routing and admission can never
        hash a session to different tenants."""
        body_tenant = getattr(req, "tenant", None) if req is not None else None
        return body_tenant or request.headers.get("X-Tenant")

    @web.middleware
    async def request_context_middleware(request: web.Request, handler):
        """Per-request correlation: a fresh request id (logging ContextVar,
        echoed as X-Request-Id — before this PR the id existed only in
        logs), and for the business API a root trace span joined from the
        client's `traceparent` header. Probes/scrapes (/healthz, /metrics)
        and the trace-debug surface itself stay untraced."""
        rid = new_request_id()
        trace_ctx = None
        if request.path.startswith("/v1/"):
            # Span names must be a BOUNDED set (they label the span_seconds
            # histogram): use the route template ("/v1/files/{hash}"), never
            # the raw path — file hashes / executor ids / 404 garbage would
            # mint a metric series each. The raw path rides as a span
            # attribute instead (attributes never become metric labels).
            resource = request.match_info.route.resource
            canonical = resource.canonical if resource is not None else "unmatched"
            trace_ctx = tracer.start_trace(
                f"http {request.method} {canonical}",
                traceparent=request.headers.get("traceparent"),
                attributes={
                    "http.method": request.method,
                    "http.path": request.path,
                    "request_id": rid,
                },
            )

        def stamp(response) -> None:
            # A prepared response (the NDJSON stream) already sent its
            # headers; mutating them now would be a silent no-op at best.
            if getattr(response, "prepared", False):
                return
            response.headers["X-Request-Id"] = rid
            if trace_ctx is not None and trace_ctx.trace_id:
                response.headers["X-Trace-Id"] = trace_ctx.trace_id
                # Emit the context too (accept/emit symmetry): lets a
                # caller that did NOT send a traceparent adopt the trace
                # this service started for it.
                header = trace_ctx.traceparent()
                if header:
                    response.headers["traceparent"] = header

        if trace_ctx is None:
            response = await handler(request)
            stamp(response)
            return response
        with trace_ctx as span:
            try:
                response = await handler(request)
            except web.HTTPException as e:
                stamp(e)
                raise
            if span.recording:
                span.set_attribute("http.status", response.status)
                if response.status >= 500:
                    span.status = "error"
            stamp(response)
            return response

    app = web.Application(
        middlewares=[request_context_middleware], client_max_size=256 * 2**20
    )
    routes = web.RouteTableDef()

    def bad_request(message, **extra) -> web.Response:
        return web.json_response({"error": message, **extra}, status=400)

    def with_trace_id(body: dict) -> dict:
        """Error bodies carry the trace id too: a shed/degraded response is
        exactly the request an operator wants to pull the trace for."""
        trace_id = tracing.current_trace_id()
        if trace_id is not None:
            body["trace_id"] = trace_id
        return body

    def shed(e: CircuitOpenError) -> web.Response:
        """Load-shedding response while a lane's breaker is open: 503 +
        Retry-After (degraded SERVICE — distinct from 429, which means the
        service is healthy but THIS caller hit a capacity cap)."""
        retry_after = max(1, math.ceil(e.retry_after or 1.0))
        return web.json_response(
            with_trace_id({"error": str(e), "degraded": True}),
            status=503,
            headers={"Retry-After": str(retry_after)},
        )

    async def parse_model(request: web.Request, model):
        try:
            return model.model_validate(await request.json())
        except json.JSONDecodeError:
            raise web.HTTPBadRequest(
                text=json.dumps({"error": "invalid JSON body"}),
                content_type="application/json",
            )
        except ValidationError as e:
            raise web.HTTPBadRequest(
                text=json.dumps({"error": "validation failed", "detail": e.errors(include_url=False)}),
                content_type="application/json",
            )

    @routes.get("/healthz")
    async def healthz(request: web.Request) -> web.Response:
        if code_executor.draining:
            # Graceful shutdown in progress: load balancers must stop
            # sending work here while in-flight executes finish.
            return web.json_response(
                {"status": "draining", "reason": "service is shutting down"},
                status=503,
            )
        if code_executor.degraded():
            retry_after = max(1, math.ceil(code_executor.degraded_retry_after() or 1.0))
            return web.json_response(
                {
                    "status": "degraded",
                    "reason": "default-lane spawn circuit open",
                },
                status=503,
                headers={"Retry-After": str(retry_after)},
            )
        # Operator detail: per-lane queue pressure (the scheduler's own
        # queue-wait EWMA — no longer just a hint: the warm-pool
        # autoscaler closes the loop on it) and batch occupancy ("are
        # batches running under-filled?"), joined with SUPPLY (the dynamic
        # pool target and the pooled/in-use/spawning counts backing it) so
        # demand and supply read side by side.
        body: dict = {"status": "ok"}
        lanes = code_executor.scheduler.lane_detail()
        for lane, entry in code_executor.lane_supply().items():
            lanes.setdefault(lane, {}).update(entry)
        if lanes:
            body["lanes"] = lanes
        body["batching"] = {
            "enabled": code_executor.batcher is not None,
            "window_ms": code_executor.config.batch_window_ms,
            "max_jobs": code_executor.config.batch_max_jobs,
        }
        return web.json_response(body)

    @routes.get("/metrics")
    async def metrics(request: web.Request) -> web.Response:
        # The versioned Content-Type is part of the exposition contract
        # (Prometheus text format 0.0.4); a bare text/plain reads as an
        # unversioned payload to strict scrapers.
        return web.Response(
            body=code_executor.metrics.registry.render().encode("utf-8"),
            headers={"Content-Type": PROMETHEUS_CONTENT_TYPE},
        )

    def paging_params(
        request: web.Request, *, default_limit: int, max_limit: int
    ) -> tuple[int, int]:
        """Shared `?limit=`/`?offset=` parsing with hard caps: the trace
        debug surfaces page through bounded responses — a full TraceRing
        must never become one multi-megabyte reply."""
        try:
            limit = int(request.query.get("limit", str(default_limit)))
            offset = int(request.query.get("offset", "0"))
        except ValueError:
            raise web.HTTPBadRequest(
                text=json.dumps({"error": "limit/offset must be integers"}),
                content_type="application/json",
            )
        return max(0, min(limit, max_limit)), max(0, offset)

    @routes.get("/traces")
    async def recent_traces(request: web.Request) -> web.Response:
        """Debug surface: newest traces still in the in-memory ring
        (trace id, root span, span count, errors). `?limit=`/`?offset=`
        page the list (hard cap per response)."""
        limit, offset = paging_params(request, default_limit=20, max_limit=200)
        return web.json_response(
            {
                "enabled": tracer.enabled,
                "sample_ratio": tracer.sample_ratio,
                "limit": limit,
                "offset": offset,
                "traces": tracer.ring.recent(limit=limit, offset=offset),
            }
        )

    @routes.get("/traces/{trace_id}")
    async def get_trace(request: web.Request) -> web.Response:
        """One trace's retained spans in start order. `?format=jsonl` gets
        the export format (one span per line) instead of the JSON tree;
        `?limit=`/`?offset=` page the span list (a 100%-sampled trace can
        hold thousands of spans — `total_spans` says when to page)."""
        trace_id = request.match_info["trace_id"].lower()
        if not TRACE_ID_RE.match(trace_id):
            return bad_request("invalid trace id (want 32 hex chars)")
        limit, offset = paging_params(
            request, default_limit=500, max_limit=2000
        )
        spans = tracer.ring.trace(trace_id)
        if not spans:
            return web.json_response(
                {"error": "trace not found (expired from the ring, "
                          "unsampled, or never existed)"},
                status=404,
            )
        total = len(spans)
        page = spans[offset : offset + limit]
        if request.query.get("format") == "jsonl":
            # NDJSON has no envelope for paging state, so truncation rides
            # the headers: a consumer seeing X-Total-Spans > its line count
            # knows to page with ?offset= — the export must never LOOK
            # complete when it isn't.
            text = "".join(
                json.dumps(span, sort_keys=True) + "\n" for span in page
            )
            return web.Response(
                text=text,
                content_type="application/x-ndjson",
                headers={
                    "X-Total-Spans": str(total),
                    "X-Limit": str(limit),
                    "X-Offset": str(offset),
                },
            )
        return web.json_response(
            {
                "trace_id": trace_id,
                "total_spans": total,
                "limit": limit,
                "offset": offset,
                "spans": page,
            }
        )

    @routes.get("/statusz")
    async def statusz(request: web.Request) -> web.Response:
        """Consolidated operator status: lanes (queue pressure, pool depth,
        batch occupancy, breaker state), every live host with its
        device-health verdict, sessions, compile-cache store stats, and
        the telemetry plane's own health — one endpoint for the question
        "is this fleet OK, and if not, which host is the problem?"."""
        body = code_executor.statusz()
        if request.query.get("format") == "text":
            return web.Response(text=statusz_text(body))
        return web.json_response(body)

    @routes.get("/usage")
    async def usage(request: web.Request) -> web.Response:
        """Per-tenant usage accounting: every tenant's cumulative
        chip-seconds, queue wait, transfer bytes, recompiles, violations,
        and request/batch-job counts, straight from the durable ledger
        (services/usage.py). `?format=text` renders the operator view.
        With the metering kill switch off this surface answers 404 —
        pre-metering behavior, byte-for-byte."""
        if not code_executor.usage.enabled:
            return web.json_response(
                {"error": "usage metering is disabled "
                          "(APP_USAGE_METERING_ENABLED=0)"},
                status=404,
            )
        body = code_executor.usage.snapshot()
        if request.query.get("format") == "text":
            return web.Response(text=usage_text(body))
        return web.json_response(body)

    @routes.get("/usage/{tenant}")
    async def usage_tenant(request: web.Request) -> web.Response:
        """One tenant's ledger row. A tenant past the cardinality cap
        accrues under `_overflow` — query that row for the aggregate."""
        if not code_executor.usage.enabled:
            return web.json_response(
                {"error": "usage metering is disabled "
                          "(APP_USAGE_METERING_ENABLED=0)"},
                status=404,
            )
        tenant = request.match_info["tenant"]
        row = code_executor.usage.tenant_snapshot(tenant)
        if row is None:
            return web.json_response(
                {"error": f"no usage recorded for tenant {tenant!r}"},
                status=404,
            )
        body = {"tenant": tenant, "usage": row}
        if request.query.get("format") == "text":
            return web.Response(text=_usage_row_text(tenant, row) + "\n")
        return web.json_response(body)

    @routes.get("/quotas")
    async def quotas(request: web.Request) -> web.Response:
        """The quota layer's verdict state: default policy, per-tenant
        window consumption vs budget, in-flight counts, quarantine
        sentences, and denial totals (services/quotas.py). `?format=text`
        renders the operator view. 404 with the kill switch off —
        pre-quota behavior, byte-for-byte."""
        if not code_executor.quotas.enabled:
            return web.json_response(
                {"error": "quota enforcement is disabled "
                          "(APP_QUOTAS_ENABLED=0, or usage metering is off)"},
                status=404,
            )
        body = code_executor.quotas.snapshot()
        if request.query.get("format") == "text":
            return web.Response(text=quotas_text(body))
        return web.json_response(body)

    @routes.get("/quotas/{tenant}")
    async def quotas_tenant(request: web.Request) -> web.Response:
        """One tenant's quota view. A tenant past the ledger's cardinality
        cap shares the `_overflow` row's budget — query that row for the
        aggregate, exactly like /usage/{tenant}."""
        if not code_executor.quotas.enabled:
            return web.json_response(
                {"error": "quota enforcement is disabled "
                          "(APP_QUOTAS_ENABLED=0, or usage metering is off)"},
                status=404,
            )
        tenant = request.match_info["tenant"]
        row = code_executor.quotas.tenant_snapshot(tenant)
        if row is None:
            return web.json_response(
                {"error": f"no quota state for tenant {tenant!r}"},
                status=404,
            )
        body = {"tenant": tenant, "quota": row}
        if request.query.get("format") == "text":
            return web.Response(text=_quota_row_text(tenant, row) + "\n")
        return web.json_response(body)

    @routes.get("/perf")
    async def perf(request: web.Request) -> web.Response:
        """The performance anomaly plane's verdicts: per-(lane, phase)
        latency quantiles with their EWMA baselines and drift states
        (normal/degraded/regressed), per-tenant latency series, and the
        auto-profiling state (services/perf_observer.py). `?format=text`
        renders the operator view. 404 with the kill switch off —
        today's surface set, byte-for-byte."""
        if not code_executor.perf.enabled:
            return web.json_response(
                {"error": "perf observer is disabled "
                          "(APP_PERF_OBSERVER_ENABLED=0)"},
                status=404,
            )
        body = code_executor.perf.snapshot()
        if request.query.get("format") == "text":
            return web.Response(text=perf_text(body))
        return web.json_response(body)

    @routes.get("/profiles")
    async def profiles(request: web.Request) -> web.Response:
        """Auto-captured profile artifacts: id, trigger reason, lane,
        tenant, trace-id cross-link, size, capture time — newest first.
        `?limit=`/`?offset=` page the list, and the X-Total-* headers
        signal truncation (the /traces jsonl discipline: a paged listing
        must never LOOK complete when it isn't)."""
        store = code_executor.perf.store
        if not code_executor.perf.enabled or store is None:
            return web.json_response(
                {"error": "perf observer is disabled "
                          "(APP_PERF_OBSERVER_ENABLED=0)"},
                status=404,
            )
        limit, offset = paging_params(request, default_limit=50, max_limit=500)
        rows = store.list()
        total = len(rows)
        return web.json_response(
            {
                "total": total,
                "limit": limit,
                "offset": offset,
                "profiles": rows[offset : offset + limit],
            },
            headers={
                "X-Total-Profiles": str(total),
                "X-Limit": str(limit),
                "X-Offset": str(offset),
            },
        )

    @routes.get("/profiles/{profile_id}")
    async def get_profile(request: web.Request) -> web.Response:
        """One harvested profile's zip bytes (the JAX profiler trace an
        operator feeds to TensorBoard/xprof), with its capture meta in
        headers — X-Trace-Id links back to the triggering request's
        /traces entry."""
        store = code_executor.perf.store
        if not code_executor.perf.enabled or store is None:
            return web.json_response(
                {"error": "perf observer is disabled "
                          "(APP_PERF_OBSERVER_ENABLED=0)"},
                status=404,
            )
        profile_id = request.match_info["profile_id"]
        if not OBJECT_ID_RE.match(profile_id):
            return bad_request("invalid profile id")
        found = store.get(profile_id)
        if found is None:
            return web.json_response(
                {"error": f"no profile {profile_id!r} (evicted or never "
                          "captured)"},
                status=404,
            )
        data, meta = found
        headers = {
            "Content-Disposition": (
                f'attachment; filename="profile-{profile_id}.zip"'
            ),
        }
        if meta.get("trace_id"):
            headers["X-Trace-Id"] = str(meta["trace_id"])
        if meta.get("reason"):
            headers["X-Profile-Trigger"] = str(meta["reason"])
        return web.Response(
            body=data, content_type="application/zip", headers=headers
        )

    @routes.get("/profiles/{profile_id}/summary")
    async def get_profile_summary(request: web.Request) -> web.Response:
        """An xprof verdict instead of a raw zip: top device ops, device-op
        wall share, and the largest idle gaps, parsed from the profile's
        trace-event JSON (services/perf_observer.py:summarize_profile).
        Artifacts without a parseable trace degrade to a member listing."""
        store = code_executor.perf.store
        if not code_executor.perf.enabled or store is None:
            return web.json_response(
                {"error": "perf observer is disabled "
                          "(APP_PERF_OBSERVER_ENABLED=0)"},
                status=404,
            )
        profile_id = request.match_info["profile_id"]
        if not OBJECT_ID_RE.match(profile_id):
            return bad_request("invalid profile id")
        found = store.get(profile_id)
        if found is None:
            return web.json_response(
                {"error": f"no profile {profile_id!r} (evicted or never "
                          "captured)"},
                status=404,
            )
        data, meta = found
        summary = summarize_profile(data)
        body = {"id": profile_id, "meta": meta, **summary}
        headers = {}
        if meta.get("trace_id"):
            headers["X-Trace-Id"] = str(meta["trace_id"])
        return web.json_response(body, headers=headers)

    def validate_execute(req: ExecuteRequest) -> web.Response | None:
        """Shared /v1/execute + /v1/execute/stream pre-flight checks."""
        if (req.source_code is None) == (req.source_file is None):
            return bad_request("exactly one of source_code/source_file is required")
        for path, object_id in req.files.items():
            if not OBJECT_ID_RE.match(object_id):
                return bad_request(f"invalid file object id for {path}")
        return None

    def admission_params(request: web.Request, req: ExecuteRequest) -> dict:
        """Tenant/priority/deadline for the scheduler: body fields first,
        headers as fallback. Value validation (tenant charset, priority
        names) lives in the scheduler — its ValueError maps to 400 on the
        same path as every other client error."""
        tenant = req.tenant or request.headers.get("X-Tenant")
        priority = req.priority or request.headers.get("X-Priority")
        deadline = req.deadline
        if deadline is None:
            raw = request.headers.get("X-Deadline-Seconds")
            if raw is not None:
                try:
                    deadline = float(raw)
                except ValueError:
                    raise web.HTTPBadRequest(
                        text=json.dumps(
                            {"error": "X-Deadline-Seconds must be a number"}
                        ),
                        content_type="application/json",
                    )
        return {"tenant": tenant, "priority": priority, "deadline": deadline}

    def limits_param(request: web.Request, req: ExecuteRequest) -> dict | None:
        """Per-request resource-budget override: body field first, the
        X-Sandbox-Limits header (JSON object) as the gateway fallback.
        Value/key validation lives in services.limits — its ValueError maps
        to 400 on the same path as every other client error."""
        if req.limits is not None:
            return req.limits
        raw = request.headers.get("X-Sandbox-Limits")
        if raw is None:
            return None
        try:
            parsed = json.loads(raw)
        except json.JSONDecodeError:
            raise web.HTTPBadRequest(
                text=json.dumps(
                    {"error": "X-Sandbox-Limits must be a JSON object"}
                ),
                content_type="application/json",
            )
        return parsed

    def violation_response(e: LimitExceededError) -> web.Response:
        """422 for typed limit violations: the request was well-formed but
        unprocessable within its resource budget. Deterministic — clients
        must not blind-retry (no Retry-After on purpose); the body names
        the violated limit so they can raise their budget or fix the
        snippet."""
        return web.json_response(
            with_trace_id({"error": str(e), "violation": e.kind}),
            status=422,
        )

    def capacity_response(e: SessionLimitError) -> web.Response:
        """429 for capacity rejections. Admission sheds carry a computed
        Retry-After (queue-depth/EWMA-derived) — surface it as the header so
        clients back off proportionally to the actual backlog."""
        headers = {}
        retry_after = getattr(e, "retry_after", 0.0)
        if retry_after:
            headers["Retry-After"] = str(max(1, math.ceil(retry_after)))
        return web.json_response(
            with_trace_id({"error": str(e)}), status=429, headers=headers
        )

    def quota_response(e: QuotaExceededError) -> web.Response:
        """429 for quota denials — the same retryable family as every
        capacity shed (client retry loops need no new branch), but typed:
        the Retry-After is computed from the WINDOW's refill point (or the
        quarantine sentence), and the X-Quota-* headers carry the reason
        and the remaining budget so a pacing client can distinguish "slow
        down" (chip_seconds/request_rate), "narrow down" (concurrency),
        and "stop violating limits" (quarantined)."""
        headers = {
            "Retry-After": str(max(1, math.ceil(e.retry_after or 1.0))),
            "X-Quota-Reason": e.reason,
        }
        body: dict = {
            "error": str(e),
            "quota": {"tenant": e.tenant, "reason": e.reason,
                      "retry_after_s": round(e.retry_after, 3)},
        }
        if e.remaining_chip_seconds is not None:
            headers["X-Quota-Remaining-Chip-Seconds"] = (
                f"{e.remaining_chip_seconds:.6f}"
            )
            body["quota"]["remaining_chip_seconds"] = round(
                e.remaining_chip_seconds, 6
            )
        if e.limit_chip_seconds is not None:
            headers["X-Quota-Limit-Chip-Seconds"] = (
                f"{e.limit_chip_seconds:.6f}"
            )
            body["quota"]["limit_chip_seconds"] = round(
                e.limit_chip_seconds, 6
            )
        if e.window_seconds is not None:
            headers["X-Quota-Window-Seconds"] = f"{e.window_seconds:.3f}"
            body["quota"]["window_seconds"] = round(e.window_seconds, 3)
        if getattr(e, "remaining_hbm_byte_seconds", None) is not None:
            headers["X-Quota-Remaining-Hbm-Byte-Seconds"] = (
                f"{e.remaining_hbm_byte_seconds:.3f}"
            )
            body["quota"]["remaining_hbm_byte_seconds"] = round(
                e.remaining_hbm_byte_seconds, 3
            )
        if getattr(e, "limit_hbm_byte_seconds", None) is not None:
            headers["X-Quota-Limit-Hbm-Byte-Seconds"] = (
                f"{e.limit_hbm_byte_seconds:.3f}"
            )
            body["quota"]["limit_hbm_byte_seconds"] = round(
                e.limit_hbm_byte_seconds, 3
            )
        if getattr(e, "burst_credits_remaining", None) is not None:
            headers["X-Quota-Burst-Credits"] = (
                f"{e.burst_credits_remaining:.6f}"
            )
            body["quota"]["burst_credits_remaining"] = round(
                e.burst_credits_remaining, 6
            )
        return web.json_response(
            with_trace_id(body), status=429, headers=headers
        )

    def stale_lease_response(e: StaleLeaseError) -> web.Response:
        """409 for a stale-lease refusal that made it all the way to the
        client (sessions, which never retry; the stateless path replays on
        a fresh sandbox first): the request's host was fenced mid-flight.
        Retryable — a fresh request lands on a healthy host — so the 409
        carries a Retry-After, and the typed reason lets a session client
        distinguish "reconnect" from a genuine conflict."""
        return web.json_response(
            with_trace_id({"error": str(e), "reason": "stale_lease"}),
            status=409,
            headers={
                "Retry-After": str(
                    max(1, math.ceil(getattr(e, "retry_after", 1.0) or 1.0))
                )
            },
        )

    def session_restoring_response(e: SessionRestoringError) -> web.Response:
        """409 for a turn that raced a restore-in-flight: another turn is
        rehydrating this session from its durable checkpoint right now.
        The stale-lease 409 family on purpose — typed reason + Retry-After,
        so a session client's existing 409 retry loop needs no new branch
        and the retry lands after the restore completes."""
        return web.json_response(
            with_trace_id({"error": str(e), "reason": "session_restoring"}),
            status=409,
            headers={
                "Retry-After": str(
                    max(1, math.ceil(getattr(e, "retry_after", 1.0) or 1.0))
                )
            },
        )

    def store_degraded_response(e: StateStoreDegradedError) -> web.Response:
        """503 for a request refused because the shared control-plane store
        is unreachable and the touched subsystem fails CLOSED (lease mints,
        session hibernate/restore). Deliberately NOT a 502: nothing is
        wrong with the request or the sandbox fleet — the store outage is
        transient, so the typed reason + Retry-After tells clients to back
        off and retry rather than fail over or alert."""
        return web.json_response(
            with_trace_id(
                {
                    "error": str(e),
                    "reason": "store_degraded",
                    "subsystem": getattr(e, "subsystem", "") or "",
                    "retry_after_s": round(
                        float(getattr(e, "retry_after", 5.0) or 5.0), 3
                    ),
                }
            ),
            status=503,
            headers={
                "Retry-After": str(
                    max(1, math.ceil(getattr(e, "retry_after", 5.0) or 5.0))
                )
            },
        )

    def add_session_fields(body: dict, result, executor_id: str | None) -> dict:
        """Session continuity, one rule for every surface: seq==1 on a
        request the client expected to land in an existing session means
        prior state was lost (idle expiry); session_ended means THIS request
        killed the session."""
        if executor_id and result is not None:
            body["session_seq"] = result.session_seq
            body["session_ended"] = result.session_ended
        return body

    def result_body(result, req: ExecuteRequest) -> dict:
        """Execute response body, identical for both surfaces (the stream's
        final event must never diverge from the non-streaming body). The
        last point before the body is serialised: the request's trailing
        edge ends here (`phases.edge_after`, `edge.after_download`)."""
        code_executor.close_edge(result)
        body = {
            "stdout": result.stdout,
            "stderr": result.stderr,
            "exit_code": result.exit_code,
            "files": result.files,
            "phases": result.phases,
            "warm": result.warm,
            "stdout_truncated": result.stdout_truncated,
            "stderr_truncated": result.stderr_truncated,
        }
        return add_session_fields(body, result, req.executor_id)

    def memo_header(result) -> dict[str, str]:
        """The X-Memo response header: the memo verdict for declared-pure
        requests (hit|miss|bypass, from the phases block the executor
        stamped). No header when the run didn't declare purity or the memo
        kill switch is off — pre-memo responses byte-for-byte."""
        memo = result.phases.get("memo")
        if isinstance(memo, dict) and isinstance(memo.get("state"), str):
            return {"X-Memo": memo["state"]}
        return {}

    @routes.post("/v1/execute")
    async def execute(request: web.Request) -> web.Response:
        req = await parse_model(request, ExecuteRequest)
        if (error := validate_execute(req)) is not None:
            return error
        routed = await route_session(
            request, session_tenant(request, req), req.executor_id
        )
        if routed is not None:
            return routed
        try:
            result = await code_executor.execute(
                req.source_code,
                source_file=req.source_file,
                files=req.files,
                timeout=req.timeout,
                env=req.env,
                chip_count=req.chip_count,
                profile=req.profile,
                executor_id=req.executor_id,
                limits=limits_param(request, req),
                pure=req.pure,
                **admission_params(request, req),
            )
        except ValueError as e:
            return bad_request(str(e))
        except CircuitOpenError as e:
            return shed(e)
        except LimitExceededError as e:
            return violation_response(e)
        except QuotaExceededError as e:
            # Quota denial (before SessionLimitError: it subclasses it) —
            # 429 with the window-derived Retry-After and X-Quota-* headers.
            return quota_response(e)
        except SessionLimitError as e:
            # Resource exhaustion, not a request defect: retryable.
            return capacity_response(e)
        except SessionRestoringError as e:
            # Before ExecutorError (its parent): a concurrent turn owns the
            # session's restore — typed 409 + Retry-After, retry lands
            # after the restore completes.
            return session_restoring_response(e)
        except StaleLeaseError as e:
            # Before ExecutorError (its parent): the host was fenced —
            # typed 409 + Retry-After, the client reconnects to a healthy
            # host.
            return stale_lease_response(e)
        except StateStoreDegradedError as e:
            # The shared store is down and this request needed a
            # fail-closed subsystem (lease mint, session restore) —
            # typed 503 + Retry-After, retry lands after the store heals.
            return store_degraded_response(e)
        except (ExecutorError, SandboxSpawnError) as e:
            logger.exception("execute failed")
            return web.json_response({"error": str(e)}, status=502)
        return web.json_response(
            result_body(result, req), headers=memo_header(result)
        )

    @routes.post("/v1/execute/stream")
    async def execute_stream(request: web.Request) -> web.StreamResponse:
        """Streaming Execute: chunked NDJSON — {"stream","data"} events while
        the code runs, then a final object with the full execute response
        body. Pre-flight errors use plain JSON statuses; a mid-stream
        failure emits a final {"error": ...} line (headers are already
        gone)."""
        req = await parse_model(request, ExecuteRequest)
        if (error := validate_execute(req)) is not None:
            return error
        routed = await route_session(
            request, session_tenant(request, req), req.executor_id
        )
        if routed is not None:
            return routed
        events = code_executor.execute_stream(
            req.source_code,
            source_file=req.source_file,
            files=req.files,
            timeout=req.timeout,
            env=req.env,
            chip_count=req.chip_count,
            profile=req.profile,
            executor_id=req.executor_id,
            limits=limits_param(request, req),
            pure=req.pure,
            **admission_params(request, req),
        )
        # Correlation headers must land BEFORE prepare() on a stream (the
        # middleware can only stamp unprepared responses).
        stream_headers = {"Content-Type": "application/x-ndjson"}
        stream_headers["X-Request-Id"] = request_id_var.get()
        trace_id = tracing.current_trace_id()
        if trace_id is not None:
            stream_headers["X-Trace-Id"] = trace_id
        response = web.StreamResponse(status=200, headers=stream_headers)
        # Chunked implicitly (no Content-Length); flush per event so clients
        # see output with the code's own cadence.
        started = False
        try:
            async for event in events:
                if "result" in event:
                    payload = result_body(event["result"], req)
                else:
                    payload = event
                if not started:
                    await response.prepare(request)
                    started = True
                await response.write(
                    (json.dumps(payload) + "\n").encode("utf-8")
                )
        except ValueError as e:
            if not started:
                return bad_request(str(e))
            await response.write(
                (json.dumps({"error": str(e)}) + "\n").encode("utf-8")
            )
        except CircuitOpenError as e:
            if not started:
                return shed(e)
            await response.write(
                (json.dumps({"error": str(e)}) + "\n").encode("utf-8")
            )
        except LimitExceededError as e:
            # Mid-stream the violation rides the final NDJSON event (the
            # output already streamed is exactly what ran before the kill).
            if not started:
                return violation_response(e)
            await response.write(
                (
                    json.dumps({"error": str(e), "violation": e.kind}) + "\n"
                ).encode("utf-8")
            )
        except QuotaExceededError as e:
            if not started:
                return quota_response(e)
            await response.write(
                (
                    json.dumps({"error": str(e), "quota_reason": e.reason})
                    + "\n"
                ).encode("utf-8")
            )
        except SessionLimitError as e:
            if not started:
                return capacity_response(e)
            await response.write(
                (json.dumps({"error": str(e)}) + "\n").encode("utf-8")
            )
        except SessionRestoringError as e:
            # Before ExecutorError (its parent): restore-in-flight refusal.
            if not started:
                return session_restoring_response(e)
            await response.write(
                (
                    json.dumps({"error": str(e), "reason": "session_restoring"})
                    + "\n"
                ).encode("utf-8")
            )
        except StaleLeaseError as e:
            # Before ExecutorError (its parent): typed fence refusal.
            if not started:
                return stale_lease_response(e)
            await response.write(
                (
                    json.dumps({"error": str(e), "reason": "stale_lease"})
                    + "\n"
                ).encode("utf-8")
            )
        except StateStoreDegradedError as e:
            # Fail-closed store refusal: typed 503 pre-stream, final
            # typed event once headers are gone.
            if not started:
                return store_degraded_response(e)
            await response.write(
                (
                    json.dumps({"error": str(e), "reason": "store_degraded"})
                    + "\n"
                ).encode("utf-8")
            )
        except (ExecutorError, SandboxSpawnError) as e:
            logger.exception("execute stream failed")
            if not started:
                return web.json_response({"error": str(e)}, status=502)
            await response.write(
                (json.dumps({"error": str(e)}) + "\n").encode("utf-8")
            )
        await response.write_eof()
        return response

    @routes.get("/v1/executors")
    async def list_executor_sessions(request: web.Request) -> web.Response:
        """Live executor_id sessions: id, chip lane, idle seconds, busy flag,
        requests served — the operator's view of what is parking sandboxes."""
        return web.json_response({"sessions": code_executor.list_sessions()})

    @routes.delete("/v1/executors/{executor_id}")
    async def close_executor_session(request: web.Request) -> web.Response:
        """End an executor_id session: waits out an in-flight request, then
        releases the sandbox (its workspace is discarded; files already
        round-tripped through /v1/files or Execute responses survive).

        Replicated deployments: DELETE has no body, so the affinity key's
        tenant half comes from X-Tenant ALONE — a session created with a
        body tenant must pass the same tenant as X-Tenant here, or the
        key hashes to the wrong replica (the 404 body reminds; the idle
        sweeper bounds the cost of a missed close either way)."""
        executor_id = request.match_info["executor_id"]
        if not OBJECT_ID_RE.match(executor_id):
            return bad_request("invalid executor_id")
        routed = await route_session(
            request, session_tenant(request), executor_id
        )
        if routed is not None:
            return routed
        try:
            closed = await code_executor.close_session(
                executor_id, tenant=session_tenant(request)
            )
        except StateStoreDegradedError as e:
            # A hibernated session's record lives in the shared store; with
            # the store down the close cannot prove (or destroy) it — the
            # typed 503 beats silently reporting "no such session".
            return store_degraded_response(e)
        if closed:
            return web.json_response({"closed": executor_id})
        body = {"error": "no such session"}
        if router is not None and len(router.ring.peers) > 1:
            body["hint"] = (
                "replicated deployment: a session created with a body "
                "tenant routes by that tenant — pass it as X-Tenant on "
                "DELETE (idle sweep reclaims missed closes)"
            )
        return web.json_response(body, status=404)

    @routes.post("/v1/parse-custom-tool")
    async def parse_custom_tool(request: web.Request) -> web.Response:
        req = await parse_model(request, ParseCustomToolRequest)
        try:
            tool = custom_tool_executor.parse(req.tool_source_code)
        except CustomToolParseError as e:
            return web.json_response({"error_messages": e.errors}, status=400)
        return web.json_response(
            {
                "tool_name": tool.name,
                "tool_description": tool.description,
                "tool_input_schema_json": json.dumps(tool.input_schema),
            }
        )

    @routes.post("/v1/execute-custom-tool")
    async def execute_custom_tool(request: web.Request) -> web.Response:
        req = await parse_model(request, ExecuteCustomToolRequest)
        routed = await route_session(
            request, session_tenant(request, req), req.executor_id
        )
        if routed is not None:
            return routed
        try:
            tool_input = json.loads(req.tool_input_json)
        except json.JSONDecodeError:
            return bad_request("tool_input_json is not valid JSON")
        try:
            output, exec_result = await custom_tool_executor.execute_with_result(
                req.tool_source_code,
                tool_input,
                executor_id=req.executor_id,
                timeout=req.timeout,
            )
        except CustomToolParseError as e:
            return web.json_response({"error_messages": e.errors}, status=400)
        except CustomToolExecuteError as e:
            # Continuity on failure too: a timeout that killed the session
            # must be visible even though the tool call itself failed.
            return web.json_response(
                add_session_fields({"stderr": e.stderr}, e.result, req.executor_id),
                status=400,
            )
        except ValueError as e:
            return bad_request(str(e))
        except CircuitOpenError as e:
            return shed(e)
        except LimitExceededError as e:
            return violation_response(e)
        except QuotaExceededError as e:
            return quota_response(e)
        except SessionLimitError as e:
            return capacity_response(e)
        except StateStoreDegradedError as e:
            return store_degraded_response(e)
        except (ExecutorError, SandboxSpawnError) as e:
            logger.exception("custom tool execute failed")
            return web.json_response({"error": str(e)}, status=502)
        return web.json_response(
            add_session_fields(
                {"tool_output_json": json.dumps(output)}, exec_result, req.executor_id
            )
        )

    @routes.put("/v1/files")
    async def upload_file(request: web.Request) -> web.Response:
        # multipart/form-data with a `file` part, or a raw body
        object_id: str | None = None
        if request.content_type.startswith("multipart/"):
            reader = await request.multipart()
            part = await reader.next()
            while part is not None and part.name != "file":
                part = await reader.next()
            if part is None:
                return bad_request("multipart body must contain a 'file' part")
            async with storage.writer() as writer:
                while chunk := await part.read_chunk(1 << 20):
                    await writer.write(chunk)
            object_id = writer.hash
        else:
            async with storage.writer() as writer:
                async for chunk in request.content.iter_chunked(1 << 20):
                    await writer.write(chunk)
            object_id = writer.hash
        return web.json_response({"hash": object_id})

    @routes.get("/v1/files/{hash}")
    async def download_file(request: web.Request) -> web.StreamResponse:
        object_id = request.match_info["hash"]
        if not OBJECT_ID_RE.match(object_id):
            return bad_request("invalid object id")
        delete_after = request.query.get("delete", "").lower() in ("1", "true", "yes")
        # Open the reader BEFORE preparing the response: once headers are sent
        # a late StorageObjectNotFound could no longer become a clean 404 (and
        # an open fd keeps the content alive even if a concurrent delete wins).
        reader_cm = storage.reader(object_id)
        try:
            reader = await reader_cm.__aenter__()
        except StorageObjectNotFound:
            return web.json_response({"error": "file not found"}, status=404)
        try:
            size = os.fstat(reader.wrapped.fileno()).st_size
            response = web.StreamResponse(
                status=200,
                headers={
                    "Content-Type": "application/octet-stream",
                    "Content-Length": str(size),
                },
            )
            await response.prepare(request)
            while chunk := await reader.read(1 << 20):
                await response.write(chunk)
            await response.write_eof()
        finally:
            await reader_cm.__aexit__(None, None, None)
        if delete_after:
            await storage.delete(object_id)
        return response

    @routes.delete("/v1/files/{hash}")
    async def delete_file(request: web.Request) -> web.Response:
        object_id = request.match_info["hash"]
        if not OBJECT_ID_RE.match(object_id):
            return bad_request("invalid object id")
        await storage.delete(object_id)
        return web.json_response({"deleted": object_id})

    app.add_routes(routes)
    return app
