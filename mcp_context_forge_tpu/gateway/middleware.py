"""aiohttp middleware chain.

Reference stack (`/root/reference/mcpgateway/main.py:3259-3330`): CORS,
security headers, header-size guard, correlation id, compression, rate limit,
auth, RBAC, token scoping, request logging, OTel. Same capabilities here as
aiohttp middlewares, ordered outermost-first in ``MIDDLEWARES``.
"""

from __future__ import annotations

import asyncio
import base64
import time
import uuid
from typing import Awaitable, Callable

from aiohttp import web

from ..observability import phases as request_phases
from ..observability import tenant as tenant_ctx
from ..observability.tracing import current_span
from ..services.auth_service import AuthContext, AuthError, PermissionDenied
from ..services.base import ConflictError, NotFoundError, ValidationFailure
from .flight_recorder import backpressure_headers, queue_state

Handler = Callable[[web.Request], Awaitable[web.StreamResponse]]

PUBLIC_PATHS = {"/health", "/ready", "/version", "/auth/login", "/robots.txt",
                # reset flow is pre-auth by nature; both endpoints are
                # rate-limited + enumeration-hardened in the handlers
                "/auth/password/reset-request", "/auth/password/reset"}


@web.middleware
async def forwarded_middleware(request: web.Request, handler: Handler) -> web.StreamResponse:
    """Honor X-Forwarded-For/Proto from a trusted edge (reference
    ProxyHeaders + ForwardedHostMiddleware). Off unless trust_proxy_headers
    — honoring client-supplied headers otherwise lets callers spoof their
    rate-limit identity."""
    settings = request.app["ctx"].settings
    client_ip = request.remote or "unknown"
    if settings.trust_proxy_headers:
        forwarded = request.headers.get("x-forwarded-for", "")
        if forwarded:
            # RIGHTMOST entry: the one the trusted edge appended — the
            # leftmost is client-supplied and would let callers mint a fresh
            # rate-limit identity per request
            client_ip = forwarded.split(",")[-1].strip() or client_ip
    request["client_ip"] = client_ip
    return await handler(request)


@web.middleware
async def header_size_middleware(request: web.Request, handler: Handler) -> web.StreamResponse:
    """Reject oversized header blocks (reference HeaderSizeMiddleware) —
    431 before any downstream work."""
    settings = request.app["ctx"].settings
    limit = settings.max_header_bytes
    if limit:
        total = sum(len(k) + len(v) for k, v in request.raw_headers)
        if total > limit:
            return web.json_response(
                {"detail": f"Request headers exceed {limit} bytes"},
                status=431)
    if settings.max_header_count and \
            len(request.raw_headers) > settings.max_header_count:
        return web.json_response(
            {"detail": f"More than {settings.max_header_count} header fields"},
            status=431)
    if settings.max_header_field_bytes:
        for key, value in request.raw_headers:
            if len(key) + len(value) > settings.max_header_field_bytes:
                return web.json_response(
                    {"detail": "Header field exceeds "
                               f"{settings.max_header_field_bytes} bytes"},
                    status=431)
    return await handler(request)


@web.middleware
async def protocol_version_middleware(request: web.Request, handler: Handler) -> web.StreamResponse:
    """Validate MCP-Protocol-Version when a client sends one (reference
    MCPProtocolVersionMiddleware): unsupported versions get a clear 400
    instead of undefined behavior deeper in the stack."""
    version = request.headers.get("mcp-protocol-version")
    if version and request.path.startswith(("/mcp", "/servers", "/rpc")):
        supported = request.app["ctx"].settings.supported_protocol_versions
        if version not in supported:
            return web.json_response(
                {"detail": f"Unsupported MCP protocol version {version!r};"
                           f" supported: {sorted(supported)}"}, status=400)
    return await handler(request)


@web.middleware
async def cors_middleware(request: web.Request, handler: Handler) -> web.StreamResponse:
    """CORS for browser-based MCP clients (reference CORSMiddleware).
    Enabled by setting cors_allowed_origins; '*' allows any origin."""
    settings = request.app["ctx"].settings
    allowed = settings.cors_origins
    origin = request.headers.get("origin", "")
    grant = origin if (allowed and origin and
                       ("*" in allowed or origin in allowed)) else ""
    if request.method == "OPTIONS" and grant:
        headers = {
            "access-control-allow-origin": grant,
            "access-control-allow-methods": settings.cors_allowed_methods,
            "access-control-allow-headers": settings.cors_allowed_headers,
            "access-control-max-age": str(settings.cors_max_age_s),
            "vary": "origin",
        }
        if settings.cors_allow_credentials:
            headers["access-control-allow-credentials"] = "true"
        return web.Response(status=204, headers=headers)
    response = await handler(request)
    if grant:
        response.headers["access-control-allow-origin"] = grant
        response.headers.setdefault("vary", "origin")
        response.headers["access-control-expose-headers"] = \
            "mcp-session-id, x-correlation-id"
        if settings.cors_allow_credentials:
            response.headers["access-control-allow-credentials"] = "true"
    return response


@web.middleware
async def error_middleware(request: web.Request, handler: Handler) -> web.StreamResponse:
    """Map domain errors to HTTP codes; never leak stack traces."""
    try:
        return await handler(request)
    except web.HTTPException:
        raise
    except NotFoundError as exc:
        return web.json_response({"detail": str(exc)}, status=404)
    except ConflictError as exc:
        return web.json_response({"detail": str(exc)}, status=409)
    except (ValidationFailure, ValueError) as exc:
        return web.json_response({"detail": str(exc)}, status=422)
    except AuthError as exc:
        return web.json_response({"detail": str(exc)}, status=401,
                                 headers={"www-authenticate": "Bearer"})
    except PermissionDenied as exc:
        return web.json_response({"detail": str(exc)}, status=403)
    except Exception as exc:  # pragma: no cover - last resort
        request.app.logger.exception("Unhandled error on %s", request.path)
        return web.json_response({"detail": f"Internal error: {type(exc).__name__}"},
                                 status=500)


def _extract_baggage(request: web.Request, settings) -> dict[str, str]:
    """W3C baggage from the inbound header plus configured header→key
    mappings (reference middleware/baggage_middleware.py +
    otel_baggage_* family). Values are percent-decoded per the W3C
    syntax, item count and TOTAL utf-8 size are bounded, and operator
    mappings are admitted BEFORE the untrusted inbound header so a
    padded baggage header cannot starve tenant attribution."""
    from urllib.parse import unquote

    entries: dict[str, str] = {}
    max_items = settings.otel_baggage_max_items
    budget = settings.otel_baggage_max_size_bytes

    def _add(key: str, value: str) -> None:
        nonlocal budget
        key = key.strip()
        value = unquote(value.strip()).replace(",", "").replace(";", "")[:256]
        cost = len(key.encode()) + len(value.encode())
        if key and value and len(entries) < max_items and cost <= budget:
            entries[key] = value
            budget -= cost

    for header, key in settings.otel_baggage_header_mappings:
        value = request.headers.get(header)
        if value:
            _add(key, value)
    raw = request.headers.get("baggage", "")
    for member in raw.split(","):
        if "=" in member:
            key, value = member.split("=", 1)
            _add(key, value.split(";", 1)[0])  # properties are dropped
    return entries


@web.middleware
async def observability_middleware(request: web.Request, handler: Handler) -> web.StreamResponse:
    """Correlation id + span + Prometheus metrics per request."""
    ctx = request.app["ctx"]
    settings = ctx.settings
    inbound = (request.headers.get(settings.correlation_id_header, "")
               if settings.correlation_id_preserve else "")
    correlation_id = inbound or uuid.uuid4().hex[:16]
    request["correlation_id"] = correlation_id
    started = time.monotonic()
    route = request.match_info.route.resource
    path_label = route.canonical if route is not None else request.path
    attrs = {
        "http.method": request.method, "http.path": request.path,
        "correlation_id": correlation_id,
    }
    if settings.otel_baggage_enabled:
        baggage = _extract_baggage(request, settings)
        request["baggage"] = baggage
        attrs.update({f"baggage.{k}": v for k, v in baggage.items()})
    with ctx.tracer.span("http.request", attrs,
                         traceparent=request.headers.get("traceparent")) as span:
        # route TEMPLATE for bounded-cardinality consumers (the trace
        # store's slowest-per-route tables); unmatched paths are
        # client-controlled and collapse to one key
        span.set_attribute("http.route",
                           path_label if route is not None else "unmatched")
        response = await handler(request)
        span.set_attribute("http.status_code", response.status)
        elapsed = time.monotonic() - started
        ctx.metrics.http_requests.labels(request.method, path_label, str(response.status)).inc()
        # tenant resolved by the auth middleware (deeper in the chain —
        # set by the time the handler returns); requests rejected before
        # auth (rate limit, header size) read as anonymous. Clamped: the
        # label child set stays bounded at tenant_label_clamp + 1. The
        # span carries the EXACT tenant (bounded store, no cardinality
        # concern) so the trace store can slice slowest-N per tenant,
        # and the observe rides a trace-id exemplar: a p99 spike on the
        # http histogram clicks through to a retained trace
        span.set_attribute("gw.tenant",
                           request.get("tenant") or tenant_ctx.ANONYMOUS)
        tenant_label = ctx.metrics.tenant_clamp.label(
            request.get("tenant") or tenant_ctx.ANONYMOUS)
        ctx.metrics.http_duration.labels(
            request.method, path_label, tenant_label,
        ).observe(elapsed, exemplar=ctx.metrics.exemplar(
            "http_duration", elapsed, span.trace_id,
            (request.method, path_label, tenant_label)))
        perf = ctx.extras.get("perf_tracker")
        if perf is not None:
            # the flight recorder (one layer in) already attributed this
            # request; ride its phase vector on the tracker's slow-op
            # warning so "http.request: 3786 ms" is never a bare
            # duration again (r05 bench-tail satellite). Formatted only
            # when the record will actually WARN — record() reads
            # component on the slow branch alone, and stringifying a
            # dict per request is hot-path waste
            entry = request.get("flight_entry")
            slow = entry is not None and perf.will_warn("http.request",
                                                        elapsed)
            perf.record("http.request", elapsed,
                        component=(f"phases={entry['phases_ms']}"
                                   if slow else None))
        response.headers[settings.correlation_id_response_header] = \
            correlation_id
        return response


@web.middleware
async def flight_recorder_middleware(request: web.Request,
                                     handler: Handler) -> web.StreamResponse:
    """Gateway data-plane flight recorder (flight_recorder.py +
    observability/phases.py): open a PhaseClock for the request, let the
    instrumented layers (auth resolution, plugin hooks, DB statements,
    the engine handoff, serialization) charge their wall into named
    buckets, then record the completed request — phase vector, status,
    trace ids — into the bounded rings behind
    ``GET /admin/gateway/requests``, the per-route phase histograms, and
    a ``gw.phases`` event on the ``http.request`` span. The residue
    (wall minus every attributed phase) reports as ``handler`` — or
    ``error`` when an exception passed through — so the vector always
    sums to the measured wall (tolerance-gated in tests).

    Sits just inside observability_middleware: current_span() is the
    http.request span here, and client-disconnect CancelledErrors still
    propagate through (rows for aborted requests carry
    ``client_disconnected``). Also surfaces engine-pool admission depth
    as X-Queue-Depth / Retry-After backpressure headers on the LLM
    serving surface."""
    settings = request.app["ctx"].settings
    recorder = request.app.get("flight_recorder")
    if recorder is None:
        # recorder off is NOT backpressure off: the two are independent
        # knobs, and clients must keep their queue-depth signal
        response = await handler(request)
        _apply_backpressure(request, response, settings)
        return response
    clock = request_phases.PhaseClock()
    token = request_phases.set_phase_clock(clock)
    span = current_span()
    trace = span.context() if span is not None else None
    rid = recorder.start_request(request.path, trace)
    # the row's start and the request's first mark are one reading: what
    # lies before it (socket, aiohttp's parse, the middleware outside this
    # one) is the client's ``sent -> recv``
    started = clock.mark("recv")
    response: web.StreamResponse | None = None
    error: str | None = None
    disconnected = False
    try:
        response = await handler(request)
        return response
    except web.HTTPException as exc:
        response = exc  # an HTTPException IS its response
        raise
    except asyncio.CancelledError:
        error = "CancelledError"
        disconnected = True
        raise
    except Exception as exc:  # recorded, then translated upstream
        error = type(exc).__name__
        raise
    finally:
        recorder.finish_request(rid)
        request_phases.reset_phase_clock(token)
        wall = time.perf_counter() - started
        clock.add("error" if error else "handler",
                  max(0.0, wall - clock.total()))
        if response is not None:
            status = response.status
        elif disconnected:
            status = 499  # client closed request (nginx convention)
        else:
            status = 500
        route = request.match_info.route.resource
        # unmatched paths are client-controlled: one fixed label child,
        # never a per-path Prometheus series (the row keeps the raw path)
        route_label = route.canonical if route is not None else "unmatched"
        phases_ms = clock.vector_ms()
        if error is None and status >= 500:
            # the handler's exception was already translated to a 5xx
            # below us — the row must still say this request failed
            error = f"http_{status}"
        entry = recorder.record(
            method=request.method, path=request.path, route=route_label,
            status=status, duration_s=wall, phases_ms=phases_ms,
            trace_id=trace[0] if trace else None,
            span_id=trace[1] if trace else None,
            correlation_id=request.get("correlation_id"),
            tenant=request.get("tenant"),
            error=error,
            client_disconnected=(disconnected
                                 or bool(request.get("client_disconnected"))))
        request["flight_entry"] = entry
        if span is not None:
            span.add_event("gw.phases", {
                "duration_ms": entry["duration_ms"], **phases_ms})
        if response is not None:
            _apply_backpressure(request, response, settings)


def _apply_backpressure(request: web.Request,
                        response: web.StreamResponse, settings) -> None:
    """X-Queue-Depth / Retry-After on the LLM serving surface (unary
    responses; the SSE path sets them pre-prepare in tpu_local/server).
    queue_state() feeds the saturation gauge as a side effect."""
    if (not settings.gw_backpressure_headers or response.prepared
            or not request.path.startswith(
                (settings.llm_api_prefix + "/", "/llmchat"))):
        return
    response.headers.update(
        backpressure_headers(queue_state(request.app), settings))


@web.middleware
async def deprecation_middleware(request: web.Request, handler: Handler) -> web.StreamResponse:
    """Sunset/Deprecation headers on configured legacy path prefixes
    (reference middleware/deprecation.py + legacy_api_* settings): lets
    an operator announce an endpoint's retirement machine-readably
    (RFC 8594) without touching handlers."""
    response = await handler(request)
    settings = request.app["ctx"].settings
    prefixes = settings.deprecated_path_prefixes
    if prefixes and any(request.path.startswith(p) for p in prefixes):
        response.headers["Deprecation"] = "true"
        response.headers["X-Deprecated-Endpoint"] = request.path
        if settings.legacy_api_sunset_date:
            response.headers["Sunset"] = settings.legacy_api_sunset_date
    return response


@web.middleware
async def security_headers_middleware(request: web.Request, handler: Handler) -> web.StreamResponse:
    response = await handler(request)
    response.headers.setdefault("x-content-type-options", "nosniff")
    response.headers.setdefault("x-frame-options", "DENY")
    response.headers.setdefault("referrer-policy", "no-referrer")
    response.headers.setdefault("cache-control", "no-store")
    return response


class RateLimiter:
    """Per-client token bucket (reference RateLimitMiddleware).

    The bucket dict is kept in RECENCY order (allow() re-inserts the key,
    so dict iteration order == least-recently-seen first): overflow
    eviction pops from the front in O(evictions) instead of sorting the
    whole dict mid-flood (round-2 VERDICT weak #10 residual)."""

    # a bucket that would refill to full is state-free (recreating it at
    # full burst is identical), so it can be pruned losslessly; prune so IP
    # churn cannot grow the dict without bound
    _SWEEP_INTERVAL = 60.0

    def __init__(self, rps: int, burst: int, max_buckets: int = 100_000) -> None:
        self.rps = rps
        self.burst = burst
        self.max_buckets = max_buckets
        self._buckets: dict[str, tuple[float, float]] = {}  # key -> (tokens, last)
        self._next_sweep = time.monotonic() + self._SWEEP_INTERVAL

    def _sweep(self, now: float) -> None:
        self._buckets = {
            k: (tokens, last) for k, (tokens, last) in self._buckets.items()
            if tokens + (now - last) * self.rps < self.burst}
        self._next_sweep = now + self._SWEEP_INTERVAL

    def allow(self, key: str) -> bool:
        if self.rps <= 0:
            return True
        now = time.monotonic()
        if now >= self._next_sweep:
            self._sweep(now)
        entry = self._buckets.pop(key, None)  # re-insert -> recency order
        tokens, last = entry if entry is not None else (float(self.burst), now)
        tokens = min(self.burst, tokens + (now - last) * self.rps)
        allowed = tokens >= 1.0
        self._buckets[key] = (tokens - 1.0 if allowed else tokens, now)
        while len(self._buckets) > self.max_buckets:
            # oldest-first eviction, O(1) per surplus entry (dict iteration
            # order == insertion order == recency here; no key-list copy)
            del self._buckets[next(iter(self._buckets))]
        return allowed


@web.middleware
async def host_validation_middleware(request: web.Request,
                                     handler: Handler) -> web.StreamResponse:
    """Reject requests whose Host header isn't allowlisted (reference
    forwarded-host validation tier). '' (default) allows any host —
    deployments behind a proxy pin MCPFORGE_ALLOWED_HOSTS."""
    allowed = request.app["ctx"].settings.allowed_host_set
    if allowed:
        host = (request.host or "").split(":", 1)[0].lower()
        if host not in allowed:
            return web.json_response({"detail": f"Host {host!r} not allowed"},
                                     status=421)
    return await handler(request)


@web.middleware
async def compression_middleware(request: web.Request,
                                 handler: Handler) -> web.StreamResponse:
    """Negotiated response compression with SSE special-casing (reference
    SSEAwareCompressMiddleware): event streams and small bodies are never
    compressed — compressing an SSE response would buffer/break it."""
    response = await handler(request)
    settings = request.app["ctx"].settings
    if not settings.compression_enabled:
        return response
    if not isinstance(response, web.Response) or response.body is None:
        return response  # streaming (SSE/WS upgrade): leave untouched
    if response.content_type == "text/event-stream":
        return response
    if "content-encoding" in response.headers:
        return response
    if len(response.body) < settings.compression_min_bytes:
        return response
    response.enable_compression()  # negotiates via Accept-Encoding
    return response


@web.middleware
async def client_disconnect_middleware(request: web.Request,
                                       handler: Handler) -> web.StreamResponse:
    """Observe client disconnects (reference client-disconnect middleware):
    aiohttp cancels the handler task when the peer goes away mid-request;
    count it and mark the trace instead of logging a naked
    CancelledError."""
    try:
        return await handler(request)
    except asyncio.CancelledError:
        metrics = request.app["ctx"].metrics
        if metrics is not None:
            metrics.client_disconnects.inc()
        request["client_disconnected"] = True
        raise


@web.middleware
async def rate_limit_middleware(request: web.Request, handler: Handler) -> web.StreamResponse:
    limiter: RateLimiter = request.app["rate_limiter"]
    key = request.get("client_ip") or request.remote or "unknown"
    if not limiter.allow(key):
        return web.json_response({"detail": "Rate limit exceeded"}, status=429,
                                 headers={"retry-after": "1"})
    return await handler(request)


async def _handle_as_tenant(request: web.Request,
                            handler: Handler) -> web.StreamResponse:
    """Run the rest of the chain under the principal's resolved tenant:
    ``request['tenant']`` for the observability/flight-recorder layers
    above, and the contextvar the LLM provider stamps onto GenRequests
    (team → API key → user resolution; docs/multitenancy.md)."""
    tenant = tenant_ctx.resolve_tenant(request.get("auth"))
    request["tenant"] = tenant
    token = tenant_ctx.set_current_tenant(tenant)
    try:
        return await handler(request)
    finally:
        tenant_ctx.reset_current_tenant(token)


@web.middleware
async def auth_middleware(request: web.Request, handler: Handler) -> web.StreamResponse:
    """Resolve identity (Bearer JWT / Basic) into request['auth'].

    Plugin http_auth_resolve_user hooks may override resolution; the
    http_pre_request hook runs after auth (reference HttpAuthMiddleware +
    run_pre_request_hooks).
    """
    ctx = request.app["ctx"]
    auth_service = request.app["auth_service"]
    settings = ctx.settings

    if (request.method == "OPTIONS" or request.path in PUBLIC_PATHS
            or request.path.startswith("/auth/sso/")
            # well-known files are public discovery surface by definition
            # (gateway-level AND per-server; reference well_known +
            # server_well_known routers serve them unauthenticated)
            or request.path.startswith("/.well-known/")
            or (request.path.startswith("/servers/")
                and request.path.endswith("/.well-known/mcp"))):
        request["auth"] = AuthContext(user="anonymous", via="anonymous")
        return await _handle_as_tenant(request, handler)

    # flight-recorder attribution: identity resolution (header parse,
    # plugin resolve, DB-backed bearer/basic lookups) charges the "auth"
    # phase; the plugin hooks inside charge "plugins" via PluginManager
    # and self-time accounting keeps the two from double-counting
    with request_phases.phase("auth", mark="authed"):
        header = request.headers.get(settings.auth_header_name, "")
        auth_ctx: AuthContext | None = None
        pm = ctx.plugin_manager
        if pm is not None:
            auth_ctx = await pm.http_auth_resolve_user(dict(request.headers))
        if auth_ctx is None:
            if header.lower().startswith("bearer "):
                auth_ctx = await auth_service.resolve_bearer(header[7:].strip())
            elif header.lower().startswith("basic "):
                try:
                    decoded = base64.b64decode(header[6:].strip()).decode()
                    username, _, password = decoded.partition(":")
                except Exception as exc:
                    raise AuthError("Malformed basic credentials") from exc
                auth_ctx = await auth_service.resolve_basic(username, password)
            elif not settings.auth_required:
                auth_ctx = AuthContext(user="anonymous", is_admin=True, via="anonymous")
            else:
                raise AuthError("Authentication required")
        request["auth"] = auth_ctx
    if pm is not None:
        await pm.http_pre_request(request.method, request.path, dict(request.headers),
                                  user=auth_ctx.user)
    return await _handle_as_tenant(request, handler)


@web.middleware
async def csrf_middleware(request: web.Request, handler: Handler
                          ) -> web.StreamResponse:
    """CSRF protection for the ambient-credential surface (reference
    middleware/csrf_middleware.py + services/csrf_service.py).

    Runs AFTER auth (needs the resolved identity). Bearer-token requests
    are exempt — a cross-site page cannot set an Authorization header
    with a token it doesn't hold. Basic-auth and cookie-session requests
    ride credentials the BROWSER attaches automatically, so unsafe
    methods must prove same-origin provenance:

    - browser-declared cross-site (``Sec-Fetch-Site``/mismatched
      ``Origin``) → 403 (non-browser clients send neither header and are
      not CSRF-able);
    - when the admin page's ``csrf_token`` cookie is present, the
      ``X-CSRF-Token`` header must echo it and verify (double-submit:
      cross-site JS can make the browser SEND the cookie, not READ it).
    """
    from ..services import csrf_service

    settings = request.app["ctx"].settings
    if (not settings.csrf_enabled
            or request.method in csrf_service.SAFE_METHODS
            or request.path in PUBLIC_PATHS):
        return await handler(request)
    for exempt in settings.csrf_exempt_paths:
        if request.path == exempt or \
                request.path.startswith(exempt.rstrip("/") + "/"):
            return await handler(request)
    auth = request.get("auth")
    header = request.headers.get(settings.auth_header_name, "")
    if header.lower().startswith("bearer ") or auth is None \
            or auth.via == "anonymous":
        return await handler(request)
    host = request.headers.get("host", "")
    if csrf_service.browser_cross_site(request.headers, host,
                                       settings.csrf_trusted_origins):
        return web.json_response(
            {"detail": "CSRF validation failed", "code": "CSRF_CROSS_SITE"},
            status=403)
    if settings.csrf_check_referer and not (
            request.headers.get("origin")
            or request.headers.get("referer")
            or request.headers.get("sec-fetch-site")):
        # fail-closed posture: ambient-credential mutations must declare
        # provenance (rejects legacy browsers AND non-browser basic-auth
        # clients — that is the documented trade of enabling this knob)
        return web.json_response(
            {"detail": "CSRF validation failed",
             "code": "CSRF_NO_PROVENANCE"}, status=403)
    cookie = request.cookies.get(settings.csrf_cookie_name)
    if cookie:
        echoed = request.headers.get(settings.csrf_header_name, "")
        import hmac as _hmac
        if not echoed or not _hmac.compare_digest(echoed, cookie) \
                or not csrf_service.validate(echoed, auth.user,
                                             settings.jwt_secret_key):
            return web.json_response(
                {"detail": "CSRF validation failed",
                 "code": "CSRF_TOKEN_INVALID"}, status=403)
    return await handler(request)


@web.middleware
async def password_change_middleware(request: web.Request, handler: Handler
                                     ) -> web.StreamResponse:
    """Mandatory password-change enforcement (reference
    middleware/password_change_enforcement.py): an interactive identity
    whose ``password_change_required`` flag is set may only reach the
    password-change surface until it rotates. API tokens (programmatic)
    are exempt, as are the endpoints needed to perform the change; the
    REST shape is a 403 with a machine-readable code (the reference's
    browser tier 303-redirects to its change-password page)."""
    settings = request.app["ctx"].settings
    if not settings.password_change_enforcement_enabled:
        return await handler(request)
    auth = request.get("auth")
    if (auth is None or auth.via == "anonymous" or auth.token_jti
            or auth.scoped or request.path in PUBLIC_PATHS
            or request.path == "/auth/password"):
        return await handler(request)
    # the flag rides AuthContext (read in resolve_*'s existing users-row
    # fetch) — no extra hot-path query here
    if auth.password_change_required:
        return web.json_response(
            {"detail": "Password change required before further access",
             "code": "PASSWORD_CHANGE_REQUIRED",
             "change_url": "/auth/password"}, status=403)
    return await handler(request)


@web.middleware
async def token_usage_middleware(request: web.Request, handler: Handler
                                 ) -> web.StreamResponse:
    """API-token usage accounting (reference
    middleware/token_usage_middleware.py + TokenUsageLog, db.py:5565):
    every request that authenticates with an API token (jti-bearing JWT)
    is recorded — endpoint, status, latency, client — including 4xx
    outcomes (marked blocked) and 401 rejections of revoked/expired
    tokens, where the jti is recovered from the unverified payload and
    checked against the token catalog so forged tokens can't spam the
    log. Sits OUTSIDE error translation to see final statuses."""
    settings = request.app["ctx"].settings
    if not settings.token_usage_logging_enabled:
        return await handler(request)
    started = time.monotonic()
    response = await handler(request)
    auth = request.get("auth")
    jti = auth.token_jti if auth is not None else None
    user_email = auth.user if auth is not None else None
    if jti is None and response.status in (401, 403):
        # auth rejected before an identity existed: identify (not trust)
        # the token, then confirm the jti is a real catalog row
        header = request.headers.get(settings.auth_header_name, "")
        if header.lower().startswith("bearer "):
            from ..utils import jwt as jwt_utils
            payload = jwt_utils.decode_unverified(header[7:].strip())
            candidate = (payload or {}).get("jti")
            if candidate:
                row = await request.app["ctx"].db.fetchone(
                    "SELECT jti, user_email FROM api_tokens WHERE jti=?",
                    (candidate,))
                if row:
                    jti = row["jti"]
                    # catalog attribution ONLY: the unverified sub is
                    # attacker-chosen and must not spoof the trail
                    user_email = row["user_email"]
    if jti is not None:
        # "blocked" means a security denial (authn/authz/rate limit) —
        # routine 404s/validation 400s are normal traffic, and counting
        # them would poison the compliance evidence built on this table
        blocked = response.status in (401, 403, 429)
        row_values = (
            jti, user_email, time.time(), request.method, request.path,
            response.status,
            round((time.monotonic() - started) * 1000, 2),
            request.get("client_ip", request.remote),
            request.headers.get("user-agent", "")[:256],
            1 if blocked else 0,
            f"http_{response.status}" if blocked else None)

        async def _record() -> None:
            try:
                await request.app["ctx"].db.execute(
                    "INSERT INTO token_usage_logs (token_jti, user_email,"
                    " ts, method, path, status, response_ms, client_ip,"
                    " user_agent, blocked, block_reason)"
                    " VALUES (?,?,?,?,?,?,?,?,?,?,?)", row_values)
            except Exception:  # accounting must never break serving
                request.app.logger.debug("token usage write failed",
                                         exc_info=True)

        # off the critical path: the response must not wait on the
        # serialized DB executor for an accounting write. The task set
        # (created in build_app — a frozen aiohttp app rejects new keys)
        # holds strong references (the loop keeps only weak ones) and is
        # drained at shutdown so final-request rows aren't lost.
        tasks: set = request.app["_token_usage_tasks"]
        task = asyncio.ensure_future(_record())
        tasks.add(task)
        task.add_done_callback(tasks.discard)
    return response


@web.middleware
async def db_query_logging_middleware(request: web.Request, handler: Handler
                                      ) -> web.StreamResponse:
    """Per-request DB query telemetry (reference
    middleware/db_query_logging.py): when enabled, every query the
    handler runs is collected (innermost middleware — auth-layer queries
    are excluded by position), slow statements WARN, and N+1 patterns
    (the same normalized statement repeated >= threshold times) are
    called out. Response gains X-DB-Query-Count/-Time-MS headers so the
    signal is scriptable without log scraping."""
    settings = request.app["ctx"].settings
    if not settings.db_query_logging:
        return await handler(request)
    from ..db.core import query_log_capture
    with query_log_capture() as queries:
        response = await handler(request)
    if not queries:
        return response
    logger = request.app.logger
    total_ms = sum(ms for _, ms in queries)
    response.headers["X-DB-Query-Count"] = str(len(queries))
    response.headers["X-DB-Query-Time-MS"] = f"{total_ms:.2f}"
    for sql, ms in queries:
        if ms >= settings.db_query_logging_slow_ms:
            logger.warning("slow query (%.1f ms) on %s %s: %s",
                           ms, request.method, request.path, sql[:300])
    shapes: dict[str, int] = {}
    for sql, _ in queries:
        shapes[sql] = shapes.get(sql, 0) + 1
    suspects = {sql: n for sql, n in shapes.items()
                if n >= settings.db_query_n1_threshold}
    if suspects:
        logger.warning(
            "possible N+1 on %s %s: %s", request.method, request.path,
            "; ".join(f"{n}x {sql[:160]}" for sql, n in suspects.items()))
    else:
        logger.debug("%s %s ran %d queries in %.2f ms", request.method,
                     request.path, len(queries), total_ms)
    return response


@web.middleware
async def request_logging_middleware(request: web.Request, handler: Handler
                                     ) -> web.StreamResponse:
    """DEBUG-level request/response logging with sensitive-value masking via
    the native extension (reference: RequestLoggingMiddleware + the Rust
    masking crate)."""
    logger = request.app.logger
    if logger.isEnabledFor(10):  # DEBUG
        from ..utils.masking import mask_text
        body = await request.text() if request.can_read_body else ""
        logger.debug("req %s %s %s", request.method, request.path,
                     mask_text(body[:4096]) if body else "")
    response = await handler(request)
    if logger.isEnabledFor(10):
        logger.debug("resp %s %s -> %s", request.method, request.path,
                     response.status)
    # audit trail: record successful mutations (reference AuditTrail)
    audit = request.app.get("audit_service")
    if (audit is not None and request.method in ("POST", "PUT", "DELETE")
            and 200 <= response.status < 300
            and not request.path.startswith(("/rpc", "/mcp", "/messages",
                                             "/v1/", "/llmchat"))):
        auth = request.get("auth")
        await audit.record(auth.user if auth else None,
                           f"{request.method} {request.path}",
                           details={"status": response.status})
    return response


# Order matters: observability outermost so error responses still get
# metrics + correlation ids; error_middleware outside rate-limit/auth so
# AuthError and friends map to status codes.
MIDDLEWARES = [
    observability_middleware,
    # flight recorder just inside observability: current_span() is the
    # http.request span, and disconnect CancelledErrors (re-raised one
    # layer down) still pass through so aborted requests get rows too
    flight_recorder_middleware,
    client_disconnect_middleware,
    forwarded_middleware,
    host_validation_middleware,
    cors_middleware,
    compression_middleware,
    security_headers_middleware,
    deprecation_middleware,
    header_size_middleware,
    # token usage sits OUTSIDE error translation so 401/403 rejections of
    # revoked tokens surface here as statuses, not exceptions
    token_usage_middleware,
    error_middleware,
    protocol_version_middleware,
    rate_limit_middleware,
    auth_middleware,
    # csrf + password-change need the resolved identity (inside auth)
    csrf_middleware,
    password_change_middleware,
    request_logging_middleware,
    # innermost: captures only the HANDLER's queries (auth/limit-layer
    # queries run above and are excluded by position)
    db_query_logging_middleware,
]
