"""Multi-worker scale-out, end to end (docs/scaleout.md): two full
gateway workers over one coordination hub. Pins the cross-worker session
handoff — an SSE stream or elicit request landing on the NON-owning
worker is served with byte-identical output over the bus RPC seam, and
the pre-scale-out 409 survives only as the explicit fallback — plus the
fleet metrics surface."""

import asyncio

import aiohttp
from aiohttp.test_utils import TestClient, TestServer

from mcp_context_forge_tpu.config import load_settings
from mcp_context_forge_tpu.gateway.app import build_app
from mcp_context_forge_tpu.gateway.transports.streamable_http import \
    _sse_frame

AUTH = aiohttp.BasicAuth("admin", "changeme")

BASE_ENV = {
    "MCPFORGE_DATABASE_URL": "sqlite:///:memory:",
    "MCPFORGE_PLUGINS_ENABLED": "false",
    "MCPFORGE_TPU_LOCAL_ENABLED": "false",
    "MCPFORGE_GATEWAY_HEALTH_INTERVAL": "3600",
    "MCPFORGE_STREAMABLE_HTTP_STATEFUL": "true",
    "MCPFORGE_SSE_KEEPALIVE_INTERVAL": "0.5",
    "MCPFORGE_GW_STREAM_IDLE_TIMEOUT_S": "1.0",
    "MCPFORGE_GW_FLEET_METRICS": "true",
    "MCPFORGE_GW_FLEET_METRICS_INTERVAL_S": "0.2",
}


async def _worker(hub_port=None, **extra_env) -> TestClient:
    env = dict(BASE_ENV)
    env["MCPFORGE_BUS_BACKEND"] = "tcp"
    if hub_port is None:
        env["MCPFORGE_BUS_TCP_SERVE"] = "true"
        env["MCPFORGE_BUS_TCP_PORT"] = "0"
    else:
        env["MCPFORGE_BUS_TCP_PORT"] = str(hub_port)
    env.update(extra_env)
    app = await build_app(load_settings(env=env, env_file=None))
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


async def _two_workers(**extra_env):
    a = await _worker(**extra_env)
    b = await _worker(hub_port=a.app["coordination_hub"].bound_port,
                      **extra_env)
    return a, b


async def _initialize_session(client) -> str:
    resp = await client.post("/mcp", auth=AUTH, json={
        "jsonrpc": "2.0", "id": 1, "method": "initialize",
        "params": {"protocolVersion": "2025-06-18", "capabilities": {},
                   "clientInfo": {"name": "scaleout-test"}}})
    assert resp.status == 200, await resp.text()
    return resp.headers["mcp-session-id"]


async def _read_exactly(content, n: int, timeout: float = 10.0) -> bytes:
    got = b""
    while len(got) < n:
        chunk = await asyncio.wait_for(content.read(n - len(got)), timeout)
        if not chunk:
            break
        got += chunk
    return got


async def test_sse_stream_handoff_is_byte_identical():
    """A GET /mcp stream for a session owned by worker A, opened against
    worker B, serves the SAME bytes A's own SSE writer would produce —
    the relay rides session.stream RPC chunks rendered through the one
    _sse_frame implementation."""
    a, b = await _two_workers()
    try:
        sid = await _initialize_session(a)
        transport_a = a.app["streamable_transport"]
        events = [{"jsonrpc": "2.0", "method": "notifications/ping",
                   "params": {"n": i, "payload": "x" * i}}
                  for i in range(4)]
        for event in events:
            assert await transport_a.sessions.send_to_session(sid, event)
        # the owner's own rendering of those exact store entries is the
        # byte-identity bar the forwarded stream must meet
        expected = b"".join(
            _sse_frame(entry.event_id, entry.message)
            for entry in transport_a.sessions.events._events[sid])
        resp = await b.get("/mcp", auth=AUTH,
                           headers={"mcp-session-id": sid})
        assert resp.status == 200
        assert resp.headers["content-type"].startswith("text/event-stream")
        got = await _read_exactly(resp.content, len(expected))
        assert got == expected
        resp.close()
        handoffs = b.app["ctx"].metrics.render()[0].decode()
        assert 'mcpforge_gw_session_handoffs_total{kind="stream"}' \
            in handoffs
    finally:
        await b.close()
        await a.close()


async def test_sse_handoff_replays_from_last_event_id():
    a, b = await _two_workers()
    try:
        sid = await _initialize_session(a)
        transport_a = a.app["streamable_transport"]
        for i in range(3):
            await transport_a.sessions.send_to_session(
                sid, {"jsonrpc": "2.0", "method": "notifications/ping",
                      "params": {"n": i}})
        entries = transport_a.sessions.events._events[sid]
        # drain the live queue so only the REPLAY path serves the bytes
        session = transport_a.sessions.sessions[sid]
        while not session.queue.empty():
            session.queue.get_nowait()
        expected = b"".join(_sse_frame(e.event_id, e.message)
                            for e in entries[1:])
        resp = await b.get("/mcp", auth=AUTH, headers={
            "mcp-session-id": sid, "last-event-id": entries[0].event_id})
        got = await _read_exactly(resp.content, len(expected))
        assert got == expected
        resp.close()
    finally:
        await b.close()
        await a.close()


async def test_elicit_lands_on_wrong_worker_and_is_served():
    """POST /sessions/{sid}/elicit on the non-owning worker forwards to
    the owner, whose SSE stream carries the elicitation request; the
    client's reply POSTed to the WRONG worker still resolves it (the
    affinity response-forwarding path) — no 409 anywhere."""
    a, b = await _two_workers()
    try:
        sid = await _initialize_session(a)
        session = a.app["streamable_transport"].sessions.sessions[sid]

        async def client_side():
            # the connected MCP client: sees elicitation/create on its
            # stream queue, answers through worker B (wrong worker!)
            _event_id, message = await asyncio.wait_for(
                session.queue.get(), timeout=10)
            assert message["method"] == "elicitation/create"
            resp = await b.post("/mcp", auth=AUTH,
                                headers={"mcp-session-id": sid},
                                json={"jsonrpc": "2.0",
                                      "id": message["id"],
                                      "result": {"action": "accept",
                                                 "content": {"ok": 1}}})
            assert resp.status in (200, 202), await resp.text()

        client_task = asyncio.ensure_future(client_side())
        resp = await b.post(f"/sessions/{sid}/elicit", auth=AUTH,
                            json={"message": "pick one", "timeout": 10})
        await client_task
        assert resp.status == 200, await resp.text()
        body = await resp.json()
        assert body == {"action": "accept", "content": {"ok": 1}}
        handoffs = b.app["ctx"].metrics.render()[0].decode()
        assert 'mcpforge_gw_session_handoffs_total{kind="elicit"}' \
            in handoffs
    finally:
        await b.close()
        await a.close()


async def test_handoff_disabled_keeps_the_409_fallback():
    a, b = await _two_workers(MCPFORGE_GW_SESSION_HANDOFF="false")
    try:
        sid = await _initialize_session(a)
        resp = await b.post(f"/sessions/{sid}/elicit", auth=AUTH,
                            json={"message": "pick one", "timeout": 2})
        assert resp.status == 409
        assert "owning worker" in (await resp.json())["detail"]
    finally:
        await b.close()
        await a.close()


async def test_fleet_metrics_and_slo_aggregate_both_workers():
    a, b = await _two_workers()
    try:
        for client in (a, b):
            resp = await client.get("/health")
            assert resp.status == 200
        # both workers publish at 0.2 s cadence; wait for frames to cross
        fleet_a = a.app["fleet_metrics"]
        for _ in range(50):
            await fleet_a.publish_once()
            await b.app["fleet_metrics"].publish_once()
            if fleet_a.live_peers():
                break
            await asyncio.sleep(0.05)
        assert fleet_a.live_peers(), "worker A never saw B's frames"
        # counters sum across workers: both workers served /health. A frame
        # B published before its /health request may be the one A holds, so
        # wait for the sum and not for a peer alone
        for _ in range(50):
            resp = await a.get("/metrics/prometheus?scope=fleet", auth=AUTH)
            assert resp.status == 200
            text = await resp.text()
            line = next(l for l in text.splitlines()
                        if l.startswith("mcpforge_http_requests_total")
                        and 'path="/health"' in l)
            if float(line.rsplit(" ", 1)[1]) >= 2.0:
                break
            await b.app["fleet_metrics"].publish_once()
            await asyncio.sleep(0.05)
        assert float(line.rsplit(" ", 1)[1]) >= 2.0
        # gauges keep per-worker truth under an added worker label
        assert 'worker="' in text
        resp = await a.get("/admin/slo?scope=fleet", auth=AUTH)
        assert resp.status == 200
        assert (await resp.json())["scope"] == "fleet"
    finally:
        await b.close()
        await a.close()
