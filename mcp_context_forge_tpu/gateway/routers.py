"""REST API routers (reference: mcpgateway/main.py protocol routers +
mcpgateway/routers/ — 28 routers). Table-driven CRUD over the services plus
auth, metrics, admin observability endpoints."""

from __future__ import annotations

import json
from typing import Any

from aiohttp import web
from pydantic import ValidationError

from ..observability.logging import ring_buffer
from ..schemas import (
    A2AAgentCreate,
    GatewayCreate,
    GatewayUpdate,
    PromptCreate,
    PromptUpdate,
    ResourceCreate,
    ResourceUpdate,
    ServerCreate,
    ServerUpdate,
    ToolCreate,
    ToolUpdate,
)
from ..services.auth_service import AuthError, PermissionDenied
from ..services.base import NotFoundError, ValidationFailure
from .pagination import paginate


def _dump(model) -> Any:
    if isinstance(model, list):
        return [_dump(m) for m in model]
    return json.loads(model.model_dump_json())


async def _cached_list(request: web.Request, entity: str, key: str, loader):
    """List-endpoint TTL cache (reference registry_cache_* family); the
    loader runs on miss and the result is bus-invalidated on change."""
    cache = request.app.get("registry_cache")
    if cache is None:
        return await loader()
    items = cache.get(entity, key)
    if items is None:
        # capture the generation BEFORE loading: an invalidation that
        # fires while the db read runs makes this snapshot stale, and
        # put() must then drop it instead of caching pre-write state
        generation = cache.generation(entity)
        items = await loader()
        cache.put(entity, key, items, generation)
    return items


async def _body(request: web.Request, schema):
    try:
        model = schema.model_validate(await request.json())
    except json.JSONDecodeError as exc:
        raise ValidationFailure(f"Invalid JSON body: {exc}") from exc
    except ValidationError as exc:
        raise ValidationFailure(str(exc)) from exc
    _check_field_limits(model, request.app["ctx"].settings)
    return model


def _check_field_limits(model, settings) -> None:
    """Central create/update field limits (reference validation_* family,
    `/root/reference/mcpgateway/config.py` validation_max_name_length ..
    validation_max_tag_length): one enforcement point for every entity
    schema instead of per-model validators that can drift."""
    checks = (("name", settings.validation_max_name_length),
              ("description", settings.validation_max_description_length),
              ("url", settings.validation_max_url_length))
    for field_name, limit in checks:
        value = getattr(model, field_name, None)
        if isinstance(value, str) and limit and len(value) > limit:
            raise ValidationFailure(
                f"{field_name} exceeds {limit} characters")
    tags = getattr(model, "tags", None)
    if tags:
        if settings.validation_max_tags and \
                len(tags) > settings.validation_max_tags:
            raise ValidationFailure(
                f"More than {settings.validation_max_tags} tags")
        for tag in tags:
            if settings.validation_max_tag_length and \
                    len(tag) > settings.validation_max_tag_length:
                raise ValidationFailure(
                    f"Tag exceeds {settings.validation_max_tag_length}"
                    " characters")


def setup_routes(app: web.Application) -> None:
    routes = web.RouteTableDef()

    # ----------------------------------------------------------- health/meta
    @routes.get("/health")
    async def health(request: web.Request) -> web.Response:
        return web.json_response({"status": "healthy"})

    @routes.get("/ready")
    async def ready(request: web.Request) -> web.Response:
        try:
            ctx = request.app["ctx"]
            await ctx.db.execute("SELECT 1")
            elector = ctx.extras.get("leader_elector")
            return web.json_response({
                "status": "ready", "worker_id": ctx.worker_id,
                "leader": bool(elector and elector.is_leader)})
        except Exception as exc:
            return web.json_response({"status": "not ready", "detail": str(exc)}, status=503)

    @routes.get("/.well-known/mcp")
    async def well_known(request: web.Request) -> web.Response:
        settings = request.app["ctx"].settings
        return web.json_response({
            "name": settings.app_name,
            "protocolVersion": settings.protocol_version,
            "endpoints": {"mcp": "/mcp", "rpc": "/rpc"},
        })

    @routes.get("/version")
    async def version(request: web.Request) -> web.Response:
        from .. import __version__
        return web.json_response({"version": __version__})

    # ----------------------------------------------------------------- auth
    @routes.post("/auth/login")
    async def login(request: web.Request) -> web.Response:
        body = await request.json()
        auth_service = request.app["auth_service"]
        email = body.get("email") or body.get("username") or ""
        password = body.get("password") or ""
        if not await auth_service.verify_password(email, password):
            raise AuthError("Invalid credentials")
        token = auth_service.issue_jwt(email)
        return web.json_response({"access_token": token, "token_type": "bearer"})

    @routes.post("/auth/tokens")
    async def create_token(request: web.Request) -> web.Response:
        auth = request["auth"]
        auth.require("tokens.manage")
        body = await request.json()
        token, token_id = await request.app["auth_service"].create_api_token(
            auth.user, body.get("name", "api-token"),
            server_id=body.get("server_id"),
            permissions=body.get("permissions"),
            expires_minutes=body.get("expires_minutes"), grantor=auth)
        return web.json_response({"token": token, "id": token_id}, status=201)

    @routes.get("/auth/tokens")
    async def list_tokens(request: web.Request) -> web.Response:
        auth = request["auth"]
        auth.require("tokens.manage")
        return web.json_response(await request.app["auth_service"].list_api_tokens(auth.user))

    @routes.delete("/auth/tokens/{token_id}")
    async def revoke_token(request: web.Request) -> web.Response:
        auth = request["auth"]
        auth.require("tokens.manage")
        await request.app["auth_service"].revoke_token(request.match_info["token_id"])
        return web.Response(status=204)

    @routes.get("/auth/tokens/{token_id}/usage")
    async def token_usage(request: web.Request) -> web.Response:
        """Usage trail of one API token (reference TokenUsageLog +
        token_usage_middleware): endpoint, status, latency, client,
        blocked attempts — owner or admin only."""
        auth = request["auth"]
        auth.require("tokens.manage")
        row = await request.app["ctx"].db.fetchone(
            "SELECT jti, user_email FROM api_tokens WHERE id=?",
            (request.match_info["token_id"],))
        if row is None:
            raise NotFoundError("Token not found")
        if row["user_email"] != auth.user and not auth.can("admin.all"):
            raise PermissionDenied("Not your token")
        logs = await request.app["ctx"].db.fetchall(
            "SELECT ts, method, path, status, response_ms, client_ip,"
            " user_agent, blocked, block_reason FROM token_usage_logs"
            " WHERE token_jti=? ORDER BY ts DESC LIMIT 500", (row["jti"],))
        return web.json_response({"token_id": request.match_info["token_id"],
                                  "entries": logs})

    @routes.post("/auth/password")
    async def change_password(request: web.Request) -> web.Response:
        auth = request["auth"]
        body = await request.json()
        await request.app["auth_service"].change_password(
            auth.user, body.get("old_password", ""),
            body.get("new_password", ""))
        return web.json_response({"status": "changed"})

    @routes.post("/auth/password/reset-request")
    async def password_reset_request(request: web.Request) -> web.Response:
        """Start a reset: always 202 with the same body and a minimum
        response time, whether or not the account exists (reference
        password_reset_min_response_ms user-enumeration guard)."""
        import asyncio as _asyncio
        import time as _time
        settings = request.app["ctx"].settings
        if not settings.password_reset_enabled:
            raise NotFoundError("password reset is disabled")
        started = _time.monotonic()

        async def _floor() -> None:
            # the enumeration guard must hold on EVERY exit path — a
            # malformed-body fast 400 vs a padded 202 would itself be a
            # timing side channel on the parse branch
            remaining = (settings.password_reset_min_response_ms / 1e3
                         - (_time.monotonic() - started))
            if remaining > 0:
                await _asyncio.sleep(remaining)

        try:
            body = await request.json()
        except Exception:
            # malformed JSON is a client error (400), not a 500
            await _floor()
            return web.json_response({"detail": "Invalid JSON body"},
                                     status=400)
        if not isinstance(body, dict):
            await _floor()
            return web.json_response({"detail": "body must be a JSON object"},
                                     status=400)
        email = str(body.get("email", "")).strip().lower()
        if email:
            token = await request.app["auth_service"].request_password_reset(
                email)
            if token:
                email_service = request.app.get("email_service")
                if email_service is not None:
                    # background send: awaiting SMTP inline would make
                    # existing accounts answer SLOWER than unknown ones
                    # (up to smtp_timeout_seconds) — the floor below only
                    # pads short responses, it cannot cap long ones
                    tasks = request.app["_token_usage_tasks"]
                    task = _asyncio.get_running_loop().create_task(
                        email_service.send_password_reset(
                            email, token,
                            settings.password_reset_token_expiry_minutes))
                    tasks.add(task)
                    task.add_done_callback(tasks.discard)
        await _floor()
        return web.json_response(
            {"status": "accepted",
             "detail": "If the account exists, a reset link was sent."},
            status=202)

    @routes.get("/auth/password/reset")
    async def password_reset_page(request: web.Request) -> web.Response:
        """The page the emailed reset link lands on: a minimal form that
        POSTs the token + new password back to this path. Without it the
        link in the mail would hit a POST-only JSON endpoint (405)."""
        if not request.app["ctx"].settings.password_reset_enabled:
            raise NotFoundError("password reset is disabled")
        # the token is NEVER interpolated into the page (reflected-XSS
        # surface); the script reads it from location.search client-side
        return web.Response(content_type="text/html", text="""<!doctype html>
<title>Password reset</title>
<h3>Choose a new password</h3>
<form id="f"><input type="password" id="p" placeholder="new password"
  autocomplete="new-password" minlength="8" required>
<button>Reset</button></form><p id="out"></p>
<script>
document.getElementById("f").onsubmit = async (e) => {
  e.preventDefault();
  const token = new URLSearchParams(location.search).get("token") || "";
  const r = await fetch("/auth/password/reset", {method: "POST",
    headers: {"content-type": "application/json"},
    body: JSON.stringify({token, new_password:
      document.getElementById("p").value})});
  document.getElementById("out").textContent = r.ok
    ? "Password reset. You can sign in now."
    : "Reset failed: " + (await r.json()).detail;
};
</script>""")

    @routes.post("/auth/password/reset")
    async def password_reset(request: web.Request) -> web.Response:
        settings = request.app["ctx"].settings
        if not settings.password_reset_enabled:
            raise NotFoundError("password reset is disabled")
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"detail": "Invalid JSON body"},
                                     status=400)
        if not isinstance(body, dict):
            return web.json_response({"detail": "body must be a JSON object"},
                                     status=400)
        email = await request.app["auth_service"].reset_password(
            str(body.get("token", "")), str(body.get("new_password", "")))
        email_service = request.app.get("email_service")
        if email_service is not None:
            # background: the just-reset user must not wait out a slow MX
            import asyncio as _asyncio
            tasks = request.app["_token_usage_tasks"]
            task = _asyncio.get_running_loop().create_task(
                email_service.send_password_reset_confirmation(email))
            tasks.add(task)
            task.add_done_callback(tasks.discard)
        audit = request.app.get("audit_service")
        if audit is not None:
            await audit.record(email, "auth.password_reset")
        return web.json_response({"status": "reset"})

    # ----------------------------------------------------- admin user CRUD
    @routes.post("/admin/users")
    async def create_user(request: web.Request) -> web.Response:
        auth = request["auth"]
        auth.require("admin.all")
        body = await request.json()
        await request.app["auth_service"].create_user(
            body.get("email", ""), body.get("password", ""),
            full_name=body.get("full_name", ""),
            is_admin=bool(body.get("is_admin")), enforce_policy=True,
            require_password_change=bool(body.get("require_password_change")))
        return web.json_response({"email": body.get("email")}, status=201)

    @routes.get("/admin/config")
    async def effective_config(request: web.Request) -> web.Response:
        """The EFFECTIVE settings the worker is running with, secrets
        redacted (reference admin exposes its configuration view the
        same way) — the operator's 'what is this gateway actually
        configured to do' answer without shell access."""
        request["auth"].require("admin.all")
        from ..utils.redact import redact_settings
        return web.json_response(
            redact_settings(request.app["ctx"].settings))

    @routes.post("/admin/users/{email}/require-password-change")
    async def require_password_change(request: web.Request) -> web.Response:
        """Flag a user for mandatory rotation (reference
        password_change_enforcement.py); cleared by /auth/password."""
        request["auth"].require("admin.all")
        await request.app["auth_service"].set_password_change_required(
            request.match_info["email"], True)
        return web.json_response({"email": request.match_info["email"],
                                  "password_change_required": True})

    @routes.get("/admin/users")
    async def list_users(request: web.Request) -> web.Response:
        request["auth"].require("admin.all")
        rows = await request.app["ctx"].db.fetchall(
            "SELECT email, full_name, is_admin, is_active, auth_provider,"
            " last_login, created_at FROM users ORDER BY email")
        return paginate(request, rows, lambda page: list(page),
                        key=lambda row: row["email"])

    @routes.post("/admin/users/{email}/toggle")
    async def toggle_user(request: web.Request) -> web.Response:
        request["auth"].require("admin.all")
        email = request.match_info["email"]
        from ..services.base import now
        await request.app["ctx"].db.execute(
            "UPDATE users SET is_active=1-is_active, updated_at=? WHERE email=?",
            (now(), email))
        request.app["auth_service"].invalidate_user(email)
        row = await request.app["ctx"].db.fetchone(
            "SELECT email, is_active FROM users WHERE email=?", (email,))
        if row is None:
            raise NotFoundError(f"User {email} not found")
        return web.json_response(row)

    # ---------------------------------------------------------------- tools
    @routes.get("/tools")
    async def list_tools(request: web.Request) -> web.Response:
        request["auth"].require("tools.read")
        include_inactive = request.query.get("include_inactive") == "true"
        # the tool list is TEAM-scoped: the cache key must carry the
        # viewer's team set or private entries would leak across users
        teams = ",".join(sorted(request["auth"].teams or []))
        tools = await _cached_list(
            request, "tools", f"{include_inactive}:{teams}",
            lambda: request.app["tool_service"].list_tools(
                include_inactive=include_inactive,
                team_ids=request["auth"].teams))
        return paginate(request, tools, _dump)

    @routes.post("/tools")
    async def create_tool(request: web.Request) -> web.Response:
        request["auth"].require("tools.create")
        tool = await _body(request, ToolCreate)
        if not tool.owner_email:
            tool.owner_email = request["auth"].user
        created = await request.app["tool_service"].register_tool(tool)
        return web.json_response(_dump(created), status=201)

    @routes.get("/tools/{tool_id}")
    async def get_tool(request: web.Request) -> web.Response:
        request["auth"].require("tools.read")
        tool = await request.app["tool_service"].get_tool(request.match_info["tool_id"])
        return web.json_response(_dump(tool))

    @routes.put("/tools/{tool_id}")
    async def update_tool(request: web.Request) -> web.Response:
        request["auth"].require("tools.update")
        update = await _body(request, ToolUpdate)
        tool = await request.app["tool_service"].update_tool(
            request.match_info["tool_id"], update)
        return web.json_response(_dump(tool))

    @routes.delete("/tools/{tool_id}")
    async def delete_tool(request: web.Request) -> web.Response:
        request["auth"].require("tools.delete")
        await request.app["tool_service"].delete_tool(request.match_info["tool_id"])
        return web.Response(status=204)

    @routes.post("/tools/{tool_id}/toggle")
    async def toggle_tool(request: web.Request) -> web.Response:
        request["auth"].require("tools.update")
        body = {}
        if request.can_read_body and (await request.read()):
            # malformed JSON must 422, not silently select flip mode — a
            # client that MEANT {"enabled": false} must not re-enable
            body = json.loads(await request.text())
        tool_id = request.match_info["tool_id"]
        if "enabled" in body:
            enabled = bool(body["enabled"])
        else:  # bare POST (admin UI): flip the current state
            current = await request.app["tool_service"].get_tool(tool_id)
            enabled = not current.enabled
        tool = await request.app["tool_service"].toggle_tool(tool_id, enabled)
        return web.json_response(_dump(tool))

    # -------------------------------------------------------------- gateways
    @routes.get("/gateways")
    async def list_gateways(request: web.Request) -> web.Response:
        request["auth"].require("gateways.read")
        include_inactive = request.query.get("include_inactive") == "true"
        gws = await _cached_list(
            request, "gateways", str(include_inactive),
            lambda: request.app["gateway_service"].list_gateways(
                include_inactive))
        return paginate(request, gws, _dump)

    @routes.post("/gateways")
    async def register_gateway(request: web.Request) -> web.Response:
        request["auth"].require("gateways.create")
        gw = await _body(request, GatewayCreate)
        created = await request.app["gateway_service"].register_gateway(gw)
        return web.json_response(_dump(created), status=201)

    @routes.post("/gateways/test")
    async def test_gateway(request: web.Request) -> web.Response:
        """Registration-wizard dry run: probe a peer before persisting
        it (reference admin gateway connectivity test)."""
        request["auth"].require("gateways.create")
        body = await request.json()
        result = await request.app["gateway_service"].test_gateway(
            str(body.get("url", "")),
            transport=str(body.get("transport") or "streamablehttp"),
            auth_type=body.get("auth_type"),
            auth_value=body.get("auth_value"))
        return web.json_response(result)

    @routes.get("/gateways/{gateway_id}")
    async def get_gateway(request: web.Request) -> web.Response:
        request["auth"].require("gateways.read")
        gw = await request.app["gateway_service"].get_gateway(request.match_info["gateway_id"])
        return web.json_response(_dump(gw))

    @routes.put("/gateways/{gateway_id}")
    async def update_gateway(request: web.Request) -> web.Response:
        request["auth"].require("gateways.update")
        update = await _body(request, GatewayUpdate)
        gw = await request.app["gateway_service"].update_gateway(
            request.match_info["gateway_id"], update)
        return web.json_response(_dump(gw))

    @routes.delete("/gateways/{gateway_id}")
    async def delete_gateway(request: web.Request) -> web.Response:
        request["auth"].require("gateways.delete")
        await request.app["gateway_service"].delete_gateway(request.match_info["gateway_id"])
        return web.Response(status=204)

    @routes.post("/gateways/{gateway_id}/refresh")
    async def refresh_gateway(request: web.Request) -> web.Response:
        request["auth"].require("gateways.update")
        gw = await request.app["gateway_service"].refresh_gateway(
            request.match_info["gateway_id"])
        return web.json_response(_dump(gw))

    # ------------------------------------------------------------- resources
    @routes.get("/resources")
    async def list_resources(request: web.Request) -> web.Response:
        request["auth"].require("resources.read")
        include_inactive = request.query.get("include_inactive") == "true"
        res = await _cached_list(
            request, "resources", str(include_inactive),
            lambda: request.app["resource_service"].list_resources(
                include_inactive))
        return paginate(request, res, _dump)

    @routes.post("/resources")
    async def create_resource(request: web.Request) -> web.Response:
        request["auth"].require("resources.create")
        res = await _body(request, ResourceCreate)
        created = await request.app["resource_service"].register_resource(res)
        return web.json_response(_dump(created), status=201)

    @routes.put("/resources/{resource_id}")
    async def update_resource(request: web.Request) -> web.Response:
        request["auth"].require("resources.update")
        update = await _body(request, ResourceUpdate)
        res = await request.app["resource_service"].update_resource(
            request.match_info["resource_id"], update)
        return web.json_response(_dump(res))

    @routes.delete("/resources/{resource_id}")
    async def delete_resource(request: web.Request) -> web.Response:
        request["auth"].require("resources.delete")
        await request.app["resource_service"].delete_resource(
            request.match_info["resource_id"])
        return web.Response(status=204)

    @routes.post("/resources/read")
    async def read_resource(request: web.Request) -> web.Response:
        request["auth"].require("resources.read")
        body = await request.json()
        result = await request.app["resource_service"].read_resource(body.get("uri", ""))
        return web.json_response(result)

    # --------------------------------------------------------------- prompts
    @routes.get("/prompts")
    async def list_prompts(request: web.Request) -> web.Response:
        request["auth"].require("prompts.read")
        include_inactive = request.query.get("include_inactive") == "true"
        prompts = await _cached_list(
            request, "prompts", str(include_inactive),
            lambda: request.app["prompt_service"].list_prompts(
                include_inactive))
        return paginate(request, prompts, _dump)

    @routes.post("/prompts")
    async def create_prompt(request: web.Request) -> web.Response:
        request["auth"].require("prompts.create")
        prompt = await _body(request, PromptCreate)
        created = await request.app["prompt_service"].register_prompt(prompt)
        return web.json_response(_dump(created), status=201)

    @routes.put("/prompts/{prompt_id}")
    async def update_prompt(request: web.Request) -> web.Response:
        request["auth"].require("prompts.update")
        update = await _body(request, PromptUpdate)
        prompt = await request.app["prompt_service"].update_prompt(
            request.match_info["prompt_id"], update)
        return web.json_response(_dump(prompt))

    @routes.delete("/prompts/{prompt_id}")
    async def delete_prompt(request: web.Request) -> web.Response:
        request["auth"].require("prompts.delete")
        await request.app["prompt_service"].delete_prompt(request.match_info["prompt_id"])
        return web.Response(status=204)

    @routes.post("/prompts/{name}/render")
    async def render_prompt(request: web.Request) -> web.Response:
        request["auth"].require("prompts.read")
        try:
            args = await request.json()
        except Exception:
            args = {}
        result = await request.app["prompt_service"].render_prompt(
            request.match_info["name"], args)
        return web.json_response(result)

    # --------------------------------------------------------------- servers
    @routes.get("/servers")
    async def list_servers(request: web.Request) -> web.Response:
        request["auth"].require("servers.read")
        include_inactive = request.query.get("include_inactive") == "true"
        servers = await _cached_list(
            request, "servers", str(include_inactive),
            lambda: request.app["server_service"].list_servers(
                include_inactive))
        return paginate(request, servers, _dump)

    @routes.post("/servers")
    async def create_server(request: web.Request) -> web.Response:
        request["auth"].require("servers.create")
        server = await _body(request, ServerCreate)
        created = await request.app["server_service"].register_server(server)
        return web.json_response(_dump(created), status=201)

    @routes.get("/servers/{server_id}")
    async def get_server(request: web.Request) -> web.Response:
        request["auth"].require("servers.read")
        server = await request.app["server_service"].get_server(
            request.match_info["server_id"])
        return web.json_response(_dump(server))

    @routes.put("/servers/{server_id}")
    async def update_server(request: web.Request) -> web.Response:
        request["auth"].require("servers.update")
        update = await _body(request, ServerUpdate)
        server = await request.app["server_service"].update_server(
            request.match_info["server_id"], update)
        return web.json_response(_dump(server))

    @routes.delete("/servers/{server_id}")
    async def delete_server(request: web.Request) -> web.Response:
        request["auth"].require("servers.delete")
        await request.app["server_service"].delete_server(request.match_info["server_id"])
        return web.Response(status=204)

    # --------------------------------------------------------------- metrics
    @routes.get("/metrics/prometheus")
    async def prometheus(request: web.Request) -> web.Response:
        # content negotiation: a scraper that accepts OpenMetrics gets
        # the exemplar-bearing exposition (per-bucket trace ids on the
        # TTFT/TPOT/queue-wait/http histograms — the dashboard's
        # click-through into /admin/trace/{id}); classic text otherwise.
        # ?scope=fleet (multi-worker, docs/scaleout.md): the merged
        # cross-worker exposition — counters/histograms summed, gauges
        # per-worker under a `worker` label — from ANY worker
        if request.query.get("scope") == "fleet":
            fleet = request.app.get("fleet_metrics")
            if fleet is None:
                raise NotFoundError(
                    "fleet metrics aggregation is not enabled "
                    "(set MCPFORGE_GW_FLEET_METRICS=true)")
            body, content_type = fleet.render_fleet()
        else:
            body, content_type = request.app["ctx"].metrics.render(
                accept=request.headers.get("accept", ""))
        return web.Response(body=body,
                            headers={"Content-Type": content_type})

    @routes.get("/metrics")
    async def metrics_summary(request: web.Request) -> web.Response:
        request["auth"].require("observability.read")
        settings = request.app["ctx"].settings
        if settings.admin_stats_cache_enabled:
            # dashboard polling (auto-refresh tabs) must not re-aggregate
            # per request (reference admin_stats_cache_* family)
            import time as _time
            cached = request.app["_stats_cache"].get("v")
            if cached and cached[1] > _time.monotonic():
                return web.json_response(cached[0])
        db = request.app["ctx"].db
        buffer = request.app["ctx"].extras.get("metrics_buffer")
        if buffer is not None:
            await buffer.flush()  # read-after-write for the dashboard
        rows = await db.fetchall(
            "SELECT t.original_name AS name, COUNT(*) AS calls,"
            " SUM(1 - m.success) AS errors, AVG(m.duration_ms) AS avg_ms,"
            " MIN(m.duration_ms) AS min_ms, MAX(m.duration_ms) AS max_ms"
            " FROM tool_metrics m JOIN tools t ON t.id = m.tool_id"
            " WHERE m.entity_type='tool'"
            " GROUP BY t.original_name ORDER BY calls DESC LIMIT 100")
        out = {"tools": rows}
        # per-entity families (reference keeps separate metric models per
        # entity, db.py:2556-2848; here one discriminated table)
        for etype, key in (("resource", "resources"), ("prompt", "prompts"),
                           ("a2a", "a2a_agents")):
            out[key] = await db.fetchall(
                "SELECT tool_id AS name, COUNT(*) AS calls,"
                " SUM(1 - success) AS errors, AVG(duration_ms) AS avg_ms,"
                " MIN(duration_ms) AS min_ms, MAX(duration_ms) AS max_ms"
                " FROM tool_metrics WHERE entity_type=?"
                " GROUP BY tool_id ORDER BY calls DESC LIMIT 100", (etype,))
        if settings.admin_stats_cache_enabled:
            import time as _time
            request.app["_stats_cache"]["v"] = (
                out, _time.monotonic() + settings.admin_stats_cache_ttl_s)
        return web.json_response(out)

    # ----------------------------------------------------- admin observability
    @routes.get("/admin/logs")
    async def admin_logs(request: web.Request) -> web.Response:
        request["auth"].require("observability.read")
        return web.json_response(ring_buffer.search(
            query=request.query.get("q", ""),
            level=request.query.get("level"),
            limit=int(request.query.get("limit", "200"))))

    @routes.get("/admin/traces")
    async def admin_traces(request: web.Request) -> web.Response:
        """Span search: ?q= (name substring), ?status=ERROR, ?trace_id=,
        ?min_ms= (duration floor), ?store=db|memory (reference
        routers/observability + log_search)."""
        request["auth"].require("observability.read")
        tracer = request.app["ctx"].tracer
        limit = max(1, min(int(request.query.get("limit", "100")), 1000))
        q = request.query.get("q", "")
        status = request.query.get("status")
        trace_id = request.query.get("trace_id")
        min_ms = float(request.query.get("min_ms", "0") or 0)
        if request.query.get("store") == "db":
            clauses, params = [], []
            if q:
                clauses.append("name LIKE ?")
                params.append(f"%{q}%")
            if status:
                clauses.append("status=?")
                params.append(status)
            if trace_id:
                clauses.append("trace_id=?")
                params.append(trace_id)
            if min_ms:
                clauses.append("(end_ts - start_ts) * 1000 >= ?")
                params.append(min_ms)
            where = (" WHERE " + " AND ".join(clauses)) if clauses else ""
            rows = await request.app["ctx"].db.fetchall(
                f"SELECT * FROM observability_spans{where}"
                f" ORDER BY start_ts DESC LIMIT ?", [*params, limit])
            return web.json_response(rows)
        spans = [s for s in tracer.finished
                 if (not q or q in s.name)
                 and (not status or s.status == status)
                 and (not trace_id or s.trace_id == trace_id)
                 and (s.duration_ms or 0) >= min_ms][-limit:]
        return web.json_response([{
            "name": s.name, "trace_id": s.trace_id, "span_id": s.span_id,
            "parent_span_id": s.parent_span_id, "start_ts": s.start_ts,
            "duration_ms": s.duration_ms, "status": s.status,
            "attributes": {k: str(v) for k, v in s.attributes.items()},
        } for s in reversed(spans)])

    @routes.get("/admin/system/stats")
    async def system_stats(request: web.Request) -> web.Response:
        """Deployment-scale counters across every entity family
        (reference services/system_stats_service.py, admin.py:18142)."""
        request["auth"].require("observability.read")
        return web.json_response(
            await request.app["system_stats_service"].stats())

    @routes.get("/admin/performance")
    async def performance_summary(request: web.Request) -> web.Response:
        """Operation timing percentiles + slow-op counts (reference
        services/performance_tracker.py:178)."""
        request["auth"].require("observability.read")
        perf = request.app["ctx"].extras.get("perf_tracker")
        if perf is None:
            raise NotFoundError("performance tracking is disabled")
        op = request.query.get("operation")
        out = perf.summary(op)
        if op and request.query.get("degradation") == "true":
            settings = request.app["ctx"].settings
            out["degradation"] = perf.degradation(
                op, settings.performance_degradation_multiplier)
        return web.json_response(out)

    @routes.delete("/admin/performance")
    async def performance_clear(request: web.Request) -> web.Response:
        request["auth"].require("admin.all")
        perf = request.app["ctx"].extras.get("perf_tracker")
        if perf is None:
            raise NotFoundError("performance tracking is disabled")
        perf.clear(request.query.get("operation"))
        return web.Response(status=204)

    @routes.get("/admin/classification")
    async def classification_state(request: web.Request) -> web.Response:
        """Hot/cold polling state (reference
        server_classification_service.py; restored, not stubbed)."""
        request["auth"].require("observability.read")
        classifier = request.app["ctx"].extras.get("server_classifier")
        if classifier is None:
            raise NotFoundError("hot/cold classification is disabled")
        # recompute on read: the health loop refreshes only once per
        # interval, and the operator wants the CURRENT hot/cold split
        return web.json_response(await classifier.classify())

    @routes.get("/admin/support-bundle")
    async def support_bundle(request: web.Request) -> web.Response:
        """Sanitized diagnostics zip download (reference
        services/support_bundle_service.py, admin.py:18212)."""
        request["auth"].require("admin.all")
        settings = request.app["ctx"].settings
        if not settings.support_bundle_enabled:
            raise NotFoundError("support bundle generation is disabled")
        try:
            tail = int(request.query.get("tail",
                                         settings.support_bundle_log_tail))
        except ValueError as exc:
            raise ValidationFailure("tail must be an integer") from exc
        name, payload = await request.app["support_bundle_service"].generate(
            include_logs=request.query.get("logs") != "false",
            include_env=request.query.get("env") != "false",
            log_tail=tail)
        return web.Response(
            body=payload, content_type="application/zip",
            headers={"content-disposition":
                     f'attachment; filename="{name}"'})

    @routes.get("/admin/engine/stats")
    async def engine_stats(request: web.Request) -> web.Response:
        """Scheduler/cache counters of the in-process tpu_local engine
        (reference analog: runtime_admin/observability admin surfaces)."""
        request["auth"].require("observability.read")
        from ..services.diagnostics_service import live_tpu_engine
        engine = live_tpu_engine(request.app)
        if engine is None:
            raise NotFoundError("tpu_local engine is not enabled")
        stats = engine.stats
        alloc = engine.allocator
        return web.json_response({
            "model": engine.config.model,
            "mesh": dict(engine.mesh.shape),
            "requests": stats.requests,
            "prompt_tokens": stats.prompt_tokens,
            "completion_tokens": stats.completion_tokens,
            "decode_steps": stats.decode_steps,
            # host syncs: one retire per dispatch; steps/dispatches ≈ the
            # effective superstep K (token-loop fusion, perf_decode.md)
            "decode_dispatches": stats.decode_dispatches,
            "superstep": engine.config.superstep,
            "prefill_batches": stats.prefill_batches,
            "prefill_requests": stats.prefill_requests,
            # host-to-device transfers made for dispatches: over
            # decode_dispatches + prefill_batches, ~1 a dispatch
            "host_uploads": stats.host_uploads,
            # dense prefills: the prompt tokens they carried, the positions
            # they dispatched (padded rows x length: 1 - tokens / positions
            # is the padding share) and those that took the half-length
            # program of their bucket (half_lengths: bucket -> its half)
            "dense_prefill": {
                "tokens": stats.dense_prefill_tokens,
                "positions": stats.dense_prefill_positions,
                "half_batches": stats.half_prefill_batches,
                "half_lengths": {str(b): h
                                 for b, h in engine.half_lengths.items()},
            },
            "queue_depth": stats.queue_depth,
            "kv_pages_in_use": alloc.pages_in_use,
            "kv_pages_free": alloc.free_pages,
            "kv_quant": engine.config.kv_quant or "off",
            "kv_bytes_in_use": engine.kv_bytes_in_use(),
            # the whole pool: by the elements its family declares a token,
            # and as the arrays are stored (a vector padded to whole lanes)
            "kv_bytes_capacity": engine.kv_bytes_capacity(),
            "kv_bytes_resident": engine.kv_bytes_resident(),
            # per-sequence recurrent state beside the pages (0 for a family
            # whose cache grows a token only)
            "state_rows_in_use": alloc.rows_in_use,
            "state_rows_total": stats.state_rows_total,
            "state_bytes_in_use": engine.state_bytes_in_use(),
            # a model whose window layers keep a ring a sequence beside full
            # layers that page (zeros but the first for any other): the
            # paged pool, every row's rings, rows that hold a sequence, and
            # what the step programs counted of context seen and kept
            "kv": {
                "full_pool_bytes": engine.kv_bytes_capacity(),
                "window_pool_bytes": engine.window_pool_bytes(),
                "window_rows_in_use": (alloc.rows_in_use
                                       if engine.window_pool_bytes() else 0),
                "context_keys": stats.context_keys,
                "window_keys": stats.window_keys,
            },
            "prefill_ms_total": round(stats.prefill_ms_total, 1),
            "decode_ms_total": round(stats.decode_ms_total, 1),
            "engine_restarts": stats.engine_restarts,
            "chunking": stats.chunking,  # long prompts mid-chunk-prefill
            "prefix_cache": {
                "enabled": engine.config.prefix_cache,
                "cached_pages": alloc.cached_pages,
                "hits": alloc.prefix_hits,
                "hit_tokens": alloc.prefix_hit_tokens,
                # tiered spill store (docs/kv_tiering.md): per-tier hit
                # split, spill/restore counters, store footprint
                "tiers": engine.tier_stats(),
            },
            # flat twins for the admin-UI engine cards (cell() renders
            # scalars; the nested block above is the API-facing detail)
            "tier_hits_host": alloc.tier_hits["host"],
            "tier_hits_disk": alloc.tier_hits["disk"],
            "tier_hits_object": alloc.tier_hits.get("object", 0),
            "tier_hit_tokens_spilled": (alloc.tier_hit_tokens["host"]
                                        + alloc.tier_hit_tokens["disk"]
                                        + alloc.tier_hit_tokens.get(
                                            "object", 0)),
            "spec_decode": {
                "enabled": engine.config.spec_decode,
                "steps": stats.spec_steps,
                "extra_tokens": stats.spec_tokens,
                # counted on the device by a family that drafts there
                "drafted_rows": stats.spec_drafted,
                "accepted_drafts": stats.spec_accepted,
            },
            # routed experts: tokens through expert layers and pairs on
            # held experts (counted on the device by families that do),
            # and steps by the formulation their expert FFN took
            "moe": {
                "tokens": stats.moe_tokens,
                "local_pairs": stats.moe_local_pairs,
                "grouped_steps": stats.moe_grouped_steps,
                "scan_steps": stats.moe_scan_steps,
            },
            # a family with per-sequence state: prefill dispatches and chunk
            # rounds by the body their delta-rule kernel took (zeros for any
            # other family, and where the jax.numpy twin runs)
            "delta_rule": {
                "chunkwise_steps": stats.delta_chunkwise_steps,
                "walk_steps": stats.delta_walk_steps,
            },
            # a family whose decode dispatch is a block step (generation by
            # diffusion over blocks): dispatches, the passes inside them that
            # sampled, tokens emitted, positions the threshold filled; and
            # tokens a block committed per forward pass it went through is
            # the benchmark's diffusion.tokens_per_pass. Zeros elsewhere
            "diffusion": {
                "block_length": getattr(engine.model_config, "block_length", 0),
                "block_steps": stats.block_steps,
                "denoise_passes": stats.denoise_passes,
                "block_tokens": stats.block_tokens,
                "positions_filled_by_threshold":
                    stats.block_positions_filled_by_threshold,
            },
            # sampled steps by the work their rows' parameters asked for
            "sampling": {
                "argmax_steps": stats.sample_argmax_steps,
                "plain_steps": stats.sample_plain_steps,
                "filtered_steps": stats.sample_filtered_steps,
            },
            # host-fed dispatches that held the drained device past
            # timeline.STALL_S (each logged with the part that held it), and
            # the collector's pauses of the whole process by generation
            "dispatch_stalls": stats.dispatch_stalls,
            "gc": engine.timeline.gc_stats(),
            # the way out: barriers that retired the step in flight, and
            # flushes made early for first tokens alone (one a prefill or
            # chunk round that emitted, ahead of the iteration's decode)
            "pipeline_drains": stats.pipeline_drains,
            "first_flushes": stats.first_flushes,
        })

    @routes.get("/admin/slo")
    async def slo_status(request: web.Request) -> web.Response:
        """Serving-SLO verdicts over the TTFT/TPOT/queue-wait histograms
        (observability/slo.py): per-objective percentile estimates
        (cumulative + window since the previous call), fraction of window
        samples over target, and burn rate against the error budget.
        ``?window=<name>`` names the caller's delta window (default
        "default") — the admin UI polls its own so it cannot shred a
        load harness's phase-length windows. ``?tenant=<id>`` evaluates
        that tenant's assigned SLO CLASS (slo_classes /
        slo_tenant_classes) against the tenant's metric label slice,
        with its own per-(window, tenant) delta isolation."""
        request["auth"].require("observability.read")
        evaluator = request.app.get("slo_evaluator")
        if request.query.get("scope") == "fleet":
            # fleet-wide verdicts (docs/scaleout.md): objectives over
            # the SUMMED cross-worker histogram state — fleet p95, with
            # its own per-consumer delta windows
            evaluator = request.app.get("slo_evaluator_fleet")
            if evaluator is None:
                raise NotFoundError(
                    "fleet SLO evaluation needs MCPFORGE_GW_FLEET_METRICS")
        if evaluator is None:  # pragma: no cover - evaluator is unconditional
            raise NotFoundError("SLO evaluation is not enabled")
        consumer = request.query.get("window", "default")[:64] or "default"
        tenant = request.query.get("tenant") or None
        report = evaluator.evaluate(
            consumer=consumer, tenant=tenant[:128] if tenant else None)
        if request.query.get("scope") == "fleet":
            report["scope"] = "fleet"
        return web.json_response(report)

    @routes.get("/admin/engine/pool")
    async def engine_pool_status(request: web.Request) -> web.Response:
        """Replica-pool topology card: per-replica health, occupancy, and
        routing/failover counters (tpu_local/pool/, docs/serving_pool.md)."""
        request["auth"].require("observability.read")
        pool = request.app.get("tpu_engine_pool")
        if pool is None:
            raise NotFoundError(
                "engine replica pool is not enabled "
                "(set MCPFORGE_TPU_LOCAL_REPLICAS > 1)")
        return web.json_response(pool.status())

    @routes.post("/admin/engine/pool/{replica}/{action}")
    async def engine_pool_action(request: web.Request) -> web.Response:
        """drain | undrain | reload | role for one replica. Drain stops
        routing and waits for in-flight work; reload is the rolling
        weight hot-swap (drain -> rebuild engine from config.checkpoint
        -> readmit); role retargets the replica's prefill/decode/any
        assignment live (body {"role": "..."}, docs/disaggregation.md —
        routing-only state, nothing drains)."""
        request["auth"].require("admin.all")  # reload swaps weights
        pool = request.app.get("tpu_engine_pool")
        if pool is None:
            raise NotFoundError(
                "engine replica pool is not enabled "
                "(set MCPFORGE_TPU_LOCAL_REPLICAS > 1)")
        action = request.match_info["action"]
        rid = request.match_info["replica"]
        body = {}
        if request.can_read_body:
            try:
                body = await request.json()
            except json.JSONDecodeError:
                raise ValidationFailure("body must be JSON")
        if not isinstance(body, dict):  # valid JSON but e.g. [30] or "60"
            raise ValidationFailure("body must be a JSON object")
        try:
            timeout_s = float(body.get("timeout_s", 60.0))
        except (TypeError, ValueError):
            raise ValidationFailure("timeout_s must be a number")
        try:
            if action == "drain":
                result = await pool.drain(rid, timeout_s=timeout_s)
            elif action == "undrain":
                result = await pool.undrain(rid)
            elif action == "reload":
                result = await pool.reload(rid, timeout_s=timeout_s)
            elif action == "role":
                role = body.get("role")
                if not isinstance(role, str) or not role:
                    raise ValidationFailure(
                        'role action needs a body {"role": '
                        '"prefill|decode|any"}')
                result = pool.set_role(rid, role)
            else:
                raise ValidationFailure(
                    f"action must be drain|undrain|reload|role, "
                    f"got {action!r}")
        except KeyError as exc:
            raise NotFoundError(str(exc)) from exc
        except ValueError as exc:
            raise ValidationFailure(str(exc)) from exc
        return web.json_response(result)

    @routes.post("/admin/engine/profile")
    async def engine_profile(request: web.Request) -> web.Response:
        """Capture a jax.profiler trace of the running engine (SURVEY §5.1
        TPU mapping: jax.profiler integration alongside the OTel layer).
        Body: {"duration_ms": 1000, "dir": "/tmp/mcpforge-jaxprof"}."""
        # writes to disk: an admin capability, not a read one — and opt-in
        # via config (profiling stalls the runtime and writes traces)
        request["auth"].require("admin.all")
        from .routers_extra import profiler_or_404

        # the shared JaxProfilerCapture serializes EVERY profiling surface
        # (the jax profiler is process-global): a timed capture and the
        # start/stop endpoints must see each other's state. A concurrent
        # capture raises ConflictError -> 409 via the error middleware.
        profiler = profiler_or_404(request)
        from ..services.diagnostics_service import live_tpu_engine
        engine = live_tpu_engine(request.app)
        if engine is None:
            raise NotFoundError("tpu_local engine is not enabled")
        body = await request.json() if request.can_read_body else {}
        duration_ms = min(float(body.get("duration_ms", 1000.0)), 30_000.0)

        import asyncio as _aio

        # profiler start/stop write trace files — run them off the loop
        # (async-blocking-call discipline; the capture's mutex serializes)
        started = (await _aio.to_thread(profiler.start))["started_at"]
        try:
            await _aio.sleep(duration_ms / 1000.0)
        finally:
            from ..services.base import ConflictError as _Conflict
            try:
                # stop OUR capture only: an operator who stopped it and
                # started their own mid-window must not lose theirs
                result = await _aio.to_thread(profiler.stop,
                                              expect_started_at=started)
            except _Conflict:
                result = {"active": profiler.active,
                          "trace_dir": profiler.trace_dir,
                          "detail": "capture was stopped externally"}
        result.update({
            "duration_ms": duration_ms,
            "decode_steps": engine.stats.decode_steps,
            "prefill_batches": engine.stats.prefill_batches,
        })
        return web.json_response(result)

    @routes.get("/admin/traces/{trace_id}")
    async def admin_trace_tree(request: web.Request) -> web.Response:
        """Full span tree for one trace (memory + db union, deduped)."""
        request["auth"].require("observability.read")
        trace_id = request.match_info["trace_id"]
        tracer = request.app["ctx"].tracer
        spans = {s.span_id: {
            "name": s.name, "span_id": s.span_id,
            "parent_span_id": s.parent_span_id, "start_ts": s.start_ts,
            "duration_ms": s.duration_ms, "status": s.status,
            "attributes": {k: str(v) for k, v in s.attributes.items()},
        } for s in tracer.finished if s.trace_id == trace_id}
        for row in await request.app["ctx"].db.fetchall(
                "SELECT * FROM observability_spans WHERE trace_id=?",
                (trace_id,)):
            # normalize db rows to the memory-span response shape
            try:
                attrs = json.loads(row["attributes"] or "{}")
            except (TypeError, json.JSONDecodeError):
                attrs = {}
            duration = (None if row["end_ts"] is None
                        else (row["end_ts"] - row["start_ts"]) * 1000)
            spans.setdefault(row["span_id"], {
                "name": row["name"], "span_id": row["span_id"],
                "parent_span_id": row["parent_span_id"],
                "start_ts": row["start_ts"], "duration_ms": duration,
                "status": row["status"], "attributes": attrs})
        if not spans:
            raise NotFoundError(f"Trace {trace_id} not found")
        ordered = sorted(spans.values(), key=lambda s: s["start_ts"])
        return web.json_response({"trace_id": trace_id, "spans": ordered})

    app.add_routes(routes)
