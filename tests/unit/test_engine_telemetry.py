"""Engine telemetry: prefill/decode spans, token-level SLO metrics, the
step-introspection ring buffer, and gateway -> engine trace propagation."""

import asyncio

import pytest

from mcp_context_forge_tpu.observability.metrics import PrometheusRegistry
from mcp_context_forge_tpu.observability.tracing import Tracer
from mcp_context_forge_tpu.tpu_local.engine import (EngineConfig, GenRequest,
                                                    TPUEngine)


@pytest.fixture(scope="module")
def telemetry_engine():
    tracer = Tracer(exporter="memory")
    metrics = PrometheusRegistry()
    config = EngineConfig(model="llama3-test", max_batch=4, max_seq_len=128,
                          page_size=16, num_pages=64, prefill_buckets=(16, 64),
                          dtype="float32", attn_impl="reference",
                          step_log_size=8)
    engine = TPUEngine(config, tracer=tracer, metrics=metrics)
    return engine, tracer, metrics


def _run(engine, coro):
    async def wrapper():
        await engine.start()
        try:
            return await asyncio.wait_for(coro, timeout=300)
        finally:
            await engine.stop()
    return asyncio.run(wrapper())


def _generate(engine, prompt="hello telemetry", max_tokens=6,
              trace_ctx=None):
    async def main():
        request = GenRequest(request_id="tel-req",
                             prompt_ids=engine.tokenizer.encode(prompt),
                             max_tokens=max_tokens, trace_ctx=trace_ctx)
        await engine.submit(request)
        tokens = []
        while True:
            token = await request.stream.get()
            if token is None:
                break
            tokens.append(token)
        return request, tokens
    return _run(engine, main())


def test_engine_emits_queue_prefill_decode_spans(telemetry_engine):
    engine, tracer, _ = telemetry_engine
    trace_ctx = ("ab" * 16, "cd" * 8)  # the submitter's llm.request span
    _, tokens = _generate(engine, trace_ctx=trace_ctx)
    assert tokens
    spans = {s.name: s for s in tracer.finished
             if s.trace_id == trace_ctx[0]}
    assert {"llm.queue", "llm.prefill", "llm.decode"} <= set(spans)
    # every engine span parents to the submitted llm.request context
    for span in spans.values():
        assert span.parent_span_id == trace_ctx[1]
    prefill = spans["llm.prefill"]
    assert prefill.attributes["gen_ai.request.model"] == "llama3-test"
    assert prefill.attributes["gen_ai.usage.prompt_tokens"] >= 1
    assert prefill.attributes["llm.slot"] >= 0
    decode = spans["llm.decode"]
    assert decode.attributes["gen_ai.usage.completion_tokens"] == len(tokens)
    assert decode.attributes["llm.finish_reason"] in ("stop", "length")
    # replica identity rides every engine span (pool-separable traces)
    assert prefill.attributes["llm.replica_id"] == "0"
    assert decode.attributes["llm.replica_id"] == "0"


def test_engine_without_telemetry_handles_is_silent(telemetry_engine):
    """trace_ctx=None must not emit spans (and a bare engine has no
    tracer at all — the default construction path)."""
    engine, tracer, _ = telemetry_engine
    before = len(tracer.finished)
    _, tokens = _generate(engine, prompt="no spans please")
    assert tokens
    assert all(s.name not in ("llm.queue", "llm.prefill", "llm.decode")
               or s.trace_id != ""  # no orphan engine spans appeared
               for s in tracer.finished[before:])
    assert not [s for s in tracer.finished[before:]
                if s.name in ("llm.queue", "llm.prefill", "llm.decode")]


def test_slo_metrics_and_stable_labels(telemetry_engine):
    engine, _, metrics = telemetry_engine
    _generate(engine, prompt="measure me", max_tokens=8)
    body, _ = metrics.render()
    text = body.decode()
    # histograms carry samples with the model + replica + (clamped)
    # tenant labels; direct engine submissions have no resolved tenant
    # and account as "unattributed"
    assert ('mcpforge_llm_ttft_seconds_count'
            '{model="llama3-test",replica="0",tenant="unattributed"}') in text
    assert ('mcpforge_llm_tpot_seconds_count'
            '{model="llama3-test",replica="0",tenant="unattributed"}') in text
    assert 'mcpforge_llm_dispatch_gap_seconds_count{replica="0"}' in text
    assert 'mcpforge_llm_kv_bytes_in_use{replica="0"}' in text
    assert "mcpforge_llm_queue_wait_seconds_count" in text
    # engine-fed gauges are replica-labeled (gauges are last-writer-wins,
    # so a pool's replicas must not share one series) and KV utilization
    # stays in [0, 1]
    util = [line for line in text.splitlines()
            if line.startswith(
                'mcpforge_llm_kv_page_utilization{replica="0"} ')]
    assert util and 0.0 <= float(util[0].split()[-1]) <= 1.0
    assert 'mcpforge_llm_batch_occupancy{replica="0"}' in text
    assert 'mcpforge_llm_step_tokens_per_sec{replica="0"}' in text
    assert 'mcpforge_llm_queue_depth{replica="0"}' in text

    def count_of(metric: str) -> float:
        for line in text.splitlines():
            if line.startswith(metric):
                return float(line.split()[-1])
        return 0.0

    assert count_of('mcpforge_llm_ttft_seconds_count'
                    '{model="llama3-test",replica="0",'
                    'tenant="unattributed"}') >= 1
    assert count_of('mcpforge_llm_tpot_seconds_count'
                    '{model="llama3-test",replica="0",'
                    'tenant="unattributed"}') >= 1


def test_step_ring_buffer_bounded_and_shaped(telemetry_engine):
    engine, _, _ = telemetry_engine
    # enough decode steps to overflow the size-8 ring
    _generate(engine, prompt="fill the ring", max_tokens=24)
    steps = engine.recent_steps()
    assert 0 < len(steps) <= engine.config.step_log_size
    assert len(engine.step_log) <= engine.config.step_log_size
    kinds = {s["kind"] for s in steps}
    assert kinds <= {"prefill", "chunk_prefill", "decode", "spec_decode"}
    assert "decode" in kinds
    for step in steps:
        assert step["duration_ms"] >= 0
        assert step["width"] >= step["batch"] >= 0
        assert step["kv_pages_in_use"] >= 0
    # sequence numbers strictly increase (ring drops the oldest)
    seqs = [s["seq"] for s in steps]
    assert seqs == sorted(seqs)
    assert engine.recent_steps(limit=2) == steps[-2:]


# --------------------------------------------------------------- gateway path

async def _make_llm_gateway(**extra_env):
    from aiohttp.test_utils import TestClient, TestServer

    from mcp_context_forge_tpu.config import load_settings
    from mcp_context_forge_tpu.gateway.app import build_app

    settings = load_settings(env={
        **extra_env,
        "MCPFORGE_DATABASE_URL": "sqlite:///:memory:",
        "MCPFORGE_PLUGINS_ENABLED": "false",
        "MCPFORGE_TPU_LOCAL_ENABLED": "true",
        "MCPFORGE_TPU_LOCAL_MODEL": "llama3-test",
        "MCPFORGE_TPU_LOCAL_MAX_BATCH": "4",
        "MCPFORGE_TPU_LOCAL_MAX_SEQ_LEN": "128",
        "MCPFORGE_TPU_LOCAL_PAGE_SIZE": "16",
        "MCPFORGE_TPU_LOCAL_NUM_PAGES": "64",
        "MCPFORGE_TPU_LOCAL_PREFILL_BUCKETS": "64",
        "MCPFORGE_TPU_LOCAL_DTYPE": "float32",
        "MCPFORGE_GATEWAY_HEALTH_INTERVAL": "3600",
    }, env_file=None)
    app = await build_app(settings)
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


async def test_gateway_http_span_is_ancestor_of_llm_request():
    import aiohttp
    auth = aiohttp.BasicAuth("admin", "changeme")
    gateway = await _make_llm_gateway()
    try:
        resp = await gateway.post("/v1/chat/completions", json={
            "model": "llama3-test",
            "messages": [{"role": "user", "content": "trace me"}],
            "max_tokens": 4,
        }, auth=auth)
        assert resp.status == 200, await resp.text()

        tracer = gateway.app["ctx"].tracer
        by_id = {s.span_id: s for s in tracer.finished}
        llm_requests = [s for s in tracer.finished if s.name == "llm.request"]
        assert llm_requests, [s.name for s in tracer.finished]
        span = llm_requests[-1]
        # walk up the parent chain: the gateway HTTP span is an ancestor
        names_up = []
        parent = span.parent_span_id
        while parent is not None and parent in by_id:
            names_up.append(by_id[parent].name)
            parent = by_id[parent].parent_span_id
        assert "http.request" in names_up
        # engine phase spans are DESCENDANTS of llm.request in one trace
        children = {s.name for s in tracer.finished
                    if s.parent_span_id == span.span_id
                    and s.trace_id == span.trace_id}
        assert {"llm.prefill", "llm.decode"} <= children

        # /metrics exposition carries non-zero SLO histograms + gauges;
        # the HTTP-resolved principal rides the tenant label end to end
        # (the env-credential superuser has no team rows, so resolution
        # falls through team -> API key -> USER)
        resp = await gateway.get("/metrics/prometheus", auth=auth)
        text = await resp.text()
        assert ('mcpforge_llm_ttft_seconds_count'
                '{model="llama3-test",replica="0",'
                'tenant="user:admin@example.com"}') in text
        assert ('mcpforge_llm_tpot_seconds_count'
                '{model="llama3-test",replica="0"') in text
        # the ledger's exported twin carries the same tenant
        assert ('mcpforge_llm_tenant_tokens_total{kind="prompt",'
                'tenant="user:admin@example.com"}') in text
        assert "mcpforge_llm_kv_page_utilization" in text

        # step-introspection endpoint returns the last N step summaries
        resp = await gateway.get("/admin/engine/steps?limit=16", auth=auth)
        assert resp.status == 200
        body = await resp.json()
        assert body["model"] == "llama3-test"
        assert body["steps"] and body["steps"][-1]["kind"] in (
            "prefill", "decode", "spec_decode", "chunk_prefill")
        assert {"kv", "queue_depth"} <= set(body)
        # the counters' route: a model without routed experts counts no
        # expert step on either formulation
        resp = await gateway.get("/admin/engine/stats", auth=auth)
        assert resp.status == 200
        body = await resp.json()
        assert body["moe"] == {
            "tokens": 0, "local_pairs": 0, "grouped_steps": 0,
            "scan_steps": 0}
        # nor a delta-rule body: it keeps no state a sequence
        assert body["delta_rule"] == {"chunkwise_steps": 0, "walk_steps": 0}

        # profiler capture is opt-in: default-off config gates it
        resp = await gateway.post("/admin/engine/profile/start", auth=auth)
        assert resp.status == 404
        resp = await gateway.post("/admin/engine/profile", json={}, auth=auth)
        assert resp.status == 404
    finally:
        await gateway.close()


async def test_gateway_slo_and_step_attribution_surfaces():
    """GET /admin/slo serves objective verdicts over the engine's real
    histograms, and /admin/engine/steps carries the step-attribution /
    roofline / compile-tracking blocks (with a phase row on every host-fed
    step, read off the timeline's spans: no setting turns it on)."""
    import aiohttp
    auth = aiohttp.BasicAuth("admin", "changeme")
    gateway = await _make_llm_gateway(
        MCPFORGE_SLO_TPOT_P95_MS="60000",  # CPU decode must not flake it
        MCPFORGE_SLO_TTFT_P95_MS="60000",
        MCPFORGE_SLO_QUEUE_WAIT_P95_MS="60000",
        MCPFORGE_SLO_HTTP_P95_MS="60000",
    )
    try:
        # SLO endpoint is live before any traffic (empty histograms)
        resp = await gateway.get("/admin/slo", auth=auth)
        assert resp.status == 200
        body = await resp.json()
        assert body["ok"] is True
        assert {o["name"] for o in body["objectives"]} == {
            "ttft_p95", "tpot_p95", "queue_wait_p95", "http_p95"}

        resp = await gateway.post("/v1/chat/completions", json={
            "model": "llama3-test",
            "messages": [{"role": "user", "content": "measure my steps"}],
            "max_tokens": 8,
        }, auth=auth)
        assert resp.status == 200, await resp.text()

        # traffic landed: objectives now carry samples, generous targets
        # keep the verdict green
        resp = await gateway.get("/admin/slo", auth=auth)
        body = await resp.json()
        assert body["ok"] is True, body
        ttft = next(o for o in body["objectives"] if o["name"] == "ttft_p95")
        assert ttft["total_samples"] >= 1
        assert ttft["cumulative_p_ms"] is not None

        # step introspection: attribution + roofline + compile blocks,
        # and host-fed rows carry complete phase dicts
        resp = await gateway.get("/admin/engine/steps?limit=32", auth=auth)
        assert resp.status == 200
        intro = await resp.json()
        assert "phase_sampling" not in intro
        assert intro["dispatch_stalls"] >= 0
        assert "cost_entries" in intro["roofline"]
        assert intro["xla_compiles"]["serving"]["count"] >= 0
        phase_rows = [s for s in intro["steps"] if s.get("phases")]
        assert phase_rows, "no host-fed step served its phase row"
        for row in phase_rows:
            assert {"rows_ms", "sampling_ms", "rng_ms", "upload_ms",
                    "launch_ms", "readback_ms", "total_ms"} \
                == set(row["phases"]) - {"table_sync_ms"}
        assert any("table_sync_ms" in row["phases"] for row in phase_rows
                   if row["kind"] == "decode")

        # operator's view of stalls and of the collector's pauses
        resp = await gateway.get("/admin/engine/stats", auth=auth)
        stats = await resp.json()
        assert stats["dispatch_stalls"] == intro["dispatch_stalls"]
        # the way out's counts, in both views: one chat served, whose first
        # token left by a flush of its own
        assert stats["first_flushes"] == intro["first_flushes"] == 1
        assert stats["pipeline_drains"] == intro["pipeline_drains"]
        assert set(stats["gc"]) == {"gen0", "gen1", "gen2"}
        assert stats["gc"]["gen0"]["collections"] >= 1
        assert set(stats["gc"]["gen2"]) == {"collections", "total_ms",
                                            "longest_ms"}

        # phase and pause histograms reached the exposition
        resp = await gateway.get("/metrics/prometheus", auth=auth)
        text = await resp.text()
        assert 'mcpforge_llm_step_phase_seconds_count' in text
        assert 'mcpforge_gc_pause_seconds_bucket{generation="0",le="0.001"}' in text
        assert 'mcpforge_gc_pause_seconds_count{generation="2"}' in text
        assert 'mcpforge_llm_xla_compiles_total' in text
    finally:
        await gateway.close()
