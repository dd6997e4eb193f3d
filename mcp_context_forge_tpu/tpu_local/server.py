"""OpenAI-compatible /v1 surface bound to the gateway app.

Reference: `routers/llm_proxy_router.py:44` (`POST /v1/chat/completions`,
`/v1/models`) — same wire shapes, served by the in-tree engine instead of
proxying outbound (chat may still route to an external provider when a
model alias maps to an ``openai_compatible`` provider in the registry).
"""

from __future__ import annotations

import asyncio
import json
from typing import Any

from aiohttp import web

from ..gateway.serialize import SSE_DONE, sse_event
from ..observability import phases as request_phases
from ..observability.tracing import current_span
from .provider import LLMError, LLMProviderRegistry, LLMUnavailable


def _queue_state(request: web.Request) -> dict[str, Any] | None:
    """Engine/pool admission state for the backpressure headers, when
    the gateway has them enabled (gateway/flight_recorder.queue_state)."""
    if not request.app["ctx"].settings.gw_backpressure_headers:
        return None
    from ..gateway.flight_recorder import queue_state
    return queue_state(request.app)


def setup_llm_routes(app: web.Application, registry: LLMProviderRegistry,
                     prefix: str = "/v1") -> None:
    routes = web.RouteTableDef()

    def _count_error(request: web.Request) -> None:
        """Resolution/validation failures never reach the provider's own
        counters — record them here. The model label is FIXED: on this
        path the name is client-supplied and unresolvable, so labeling
        with it would mint unbounded Prometheus label children."""
        metrics = request.app["ctx"].metrics
        if metrics is not None:
            metrics.llm_requests.labels(model="unresolved",
                                        status="error").inc()

    def _unavailable_response(request: web.Request,
                              exc: LLMUnavailable) -> web.Response:
        """503 + Retry-After: the backpressure-header contract for a
        request the pool could not serve (requeue budget spent, no
        routable replica). Retry-After scales with live saturation when
        the queue state is readable, floored at the exception's own
        advisory."""
        from ..gateway.flight_recorder import queue_state, retry_after_s
        state = queue_state(request.app)
        retry_in = exc.retry_after_s
        headers = {}
        if state is not None:
            headers["X-Queue-Depth"] = str(state["depth"])
            retry_in = max(retry_in, retry_after_s(state["saturation"]))
        headers["Retry-After"] = str(retry_in)
        _count_error(request)
        return web.json_response(
            {"error": {"message": str(exc), "type": "overloaded_error",
                       "code": 503, "retry_after_s": retry_in}},
            status=503, headers=headers)

    def _estimate_tokens(body: dict) -> float:
        """Admission-time token estimate for the distributed limiter's
        grant debit (~4 chars/token prompt heuristic + per-message chat
        template overhead + the completion budget); the ledger
        reconciliation squares it against actuals. Systematic
        UNDER-estimation is the one direction that loosens the limiter's
        bound (grants deplete slower than real consumption until the
        next reconcile), so the template constant errs high."""
        try:
            messages = [m for m in body.get("messages", [])
                        if isinstance(m, dict)]
            prompt_chars = sum(len(str(m.get("content", "")))
                               for m in messages)
            # chat-template wrapping (role headers, BOS/EOT) costs real
            # prompt tokens the content length cannot see
            overhead = 8.0 + 6.0 * len(messages)
            return (prompt_chars / 4.0 + overhead
                    + float(body.get("max_tokens") or 16))
        except Exception:
            return 1.0

    async def _shed_response(request: web.Request,
                             body: dict | None = None
                             ) -> web.Response | None:
        """Overload-shedding admission gate (observability/degradation.py,
        docs/resilience.md): consult the shedder with the live engine
        saturation + the request's tenant; a shed verdict becomes a 429
        with Retry-After, lowest SLO class first. With the distributed
        limiter wired (docs/scaleout.md), the quota half of the verdict
        comes from the SHARED cross-worker window."""
        shedder = request.app.get("overload_shedder")
        if shedder is None:
            return None
        from ..gateway.flight_recorder import queue_state
        state = queue_state(request.app)
        verdict = await shedder.decide_admission(
            (state or {}).get("saturation", 0.0),
            request.get("tenant") or "",
            est_tokens=_estimate_tokens(body or {}))
        if verdict is None:
            return None
        headers = {"Retry-After": str(verdict["retry_after_s"])}
        if state is not None:
            headers["X-Queue-Depth"] = str(state["depth"])
        _count_error(request)
        return web.json_response(
            {"error": {"message": "request shed under overload "
                       f"({verdict['reason']}); retry after "
                       f"{verdict['retry_after_s']}s",
                       "type": "overloaded_error", "code": 429,
                       "reason": verdict["reason"],
                       "slo_class": verdict["slo_class"],
                       "retry_after_s": verdict["retry_after_s"]}},
            status=429, headers=headers)

    @routes.post(f"{prefix}/chat/completions")
    async def chat_completions(request: web.Request) -> web.StreamResponse:
        request["auth"].require("llm.chat")
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": {"message": "invalid JSON"}}, status=400)
        if not isinstance(body.get("messages"), list) or not body["messages"]:
            return web.json_response(
                {"error": {"message": "messages must be a non-empty list"}}, status=422)
        shed = await _shed_response(request, body)
        if shed is not None:
            return shed
        span = current_span()  # the gateway's http.request span
        if span is not None:
            span.set_attribute("gen_ai.operation.name", "chat")
            span.set_attribute("gen_ai.request.model", body.get("model") or "")
            span.set_attribute("llm.stream", bool(body.get("stream")))
        try:
            if body.get("stream"):
                # ``parsed``: the body read, the shed check passed and the
                # model resolved: what is left is the provider's
                with request_phases.phase("routing", mark="parsed"):
                    registry.resolve(body.get("model"))  # fail before the stream starts
                # the FIRST chunk is awaited BEFORE prepare() — but only
                # for a BOUNDED window: a request the pool refuses
                # outright (LLMUnavailable — requeue budget spent,
                # nothing routable) gets a clean 503 + Retry-After
                # instead of a 200 stream that dies, while a long-TTFT
                # request (deep queue, cold compile) must not have its
                # response HEADERS serialized behind the whole TTFT —
                # past the window headers go out and the first chunk is
                # awaited mid-stream like before
                chunks = registry.chat_stream(body).__aiter__()
                first_task = asyncio.ensure_future(chunks.__anext__())
                try:
                    first = None
                    first_pending = True
                    wait_s = request.app["ctx"].settings \
                        .gw_stream_first_chunk_wait_s
                    if wait_s > 0:
                        with request_phases.phase("engine"):
                            done, _ = await asyncio.wait({first_task},
                                                         timeout=wait_s)
                        if done:
                            first_pending = False
                            try:
                                # raises LLMUnavailable -> pre-prepare 503
                                first = first_task.result()
                            except StopAsyncIteration:
                                first = None
                    headers = {"content-type": "text/event-stream",
                               "cache-control": "no-store"}
                    # backpressure surfaces BEFORE prepare(): a streamed
                    # response's headers are immutable once sent, so the
                    # flight-recorder middleware cannot add them afterwards
                    state = _queue_state(request)
                    if state is not None:
                        from ..gateway.flight_recorder import \
                            backpressure_headers
                        headers.update(backpressure_headers(
                            state, request.app["ctx"].settings))
                    resp = web.StreamResponse(headers=headers)
                    await resp.prepare(request)
                    try:
                        # phase attribution splits the stream loop:
                        # waiting on the engine's next chunk is
                        # "engine", pushing it to the socket is
                        # "serialize"
                        chunk = first
                        if first_pending:
                            # headers already out: finish waiting for
                            # the first chunk on the open stream (a
                            # refusal now lands as a structured error
                            # event below)
                            with request_phases.phase("engine"):
                                try:
                                    chunk = await first_task
                                except StopAsyncIteration:
                                    chunk = None
                        while chunk is not None:
                            # ``written`` keeps the FIRST chunk's write (for
                            # one awaited before prepare(), after that too)
                            with request_phases.phase("serialize",
                                                      mark="written"):
                                await resp.write(sse_event(chunk))
                            with request_phases.phase("engine"):
                                try:
                                    chunk = await chunks.__anext__()
                                except StopAsyncIteration:
                                    chunk = None
                        await resp.write(SSE_DONE)
                    except Exception as exc:
                        # mid-stream failure: error event on the stream —
                        # a second response cannot be started once
                        # prepare() has run
                        await resp.write(sse_event(
                            {"error": {"message":
                                       f"{type(exc).__name__}: {exc}"}}))
                    await resp.write_eof()
                    return resp
                finally:
                    # the prefetch must never leak a generation: if
                    # anything failed (client disconnect during the
                    # bounded wait, prepare() error, mid-stream cancel
                    # while the first chunk was still pending) cancel
                    # the task, retrieve any unobserved exception, and
                    # close the provider stream so the engine side
                    # winds down instead of generating for a dead client
                    if not first_task.done():
                        first_task.cancel()
                    elif not first_task.cancelled():
                        first_task.exception()  # mark retrieved
                    try:
                        await chunks.aclose()
                    except Exception:
                        pass
            with request_phases.phase("engine"):
                result = await registry.chat(body)
            with request_phases.phase("serialize"):
                return web.json_response(result)
        except LLMUnavailable as exc:
            return _unavailable_response(request, exc)
        except LLMError as exc:
            _count_error(request)
            return web.json_response({"error": {"message": str(exc),
                                                "type": "invalid_request_error"}},
                                     status=404)

    @routes.post(f"{prefix}/embeddings")
    async def embeddings(request: web.Request) -> web.Response:
        request["auth"].require("llm.chat")
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": {"message": "invalid JSON"}}, status=400)
        texts = body.get("input", [])
        if isinstance(texts, str):
            texts = [texts]
        if not texts or not all(isinstance(t, str) for t in texts):
            return web.json_response(
                {"error": {"message": "input must be a string or list of strings"}},
                status=422)
        try:
            vectors = await registry.embed(texts, model=body.get("model"))
        except LLMError as exc:
            return web.json_response({"error": {"message": str(exc)}}, status=404)
        return web.json_response({
            "object": "list",
            "data": [{"object": "embedding", "index": i, "embedding": vec}
                     for i, vec in enumerate(vectors)],
            "model": body.get("model") or "tpu_local-encoder",
            "usage": {"prompt_tokens": sum(len(t.split()) for t in texts),
                      "total_tokens": sum(len(t.split()) for t in texts)},
        })

    @routes.get(f"{prefix}/models")
    async def models(request: web.Request) -> web.Response:
        return web.json_response({"object": "list", "data": registry.list_models()})

    @routes.post(f"{prefix}/moderations")
    async def moderations(request: web.Request) -> web.Response:
        """OpenAI-compatible moderation endpoint backed by the classifier head."""
        request["auth"].require("llm.chat")
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": {"message": "invalid JSON"}}, status=400)
        texts = body.get("input", [])
        if isinstance(texts, str):
            texts = [texts]
        try:
            scores = await registry.classify(texts)
        except LLMError as exc:
            return web.json_response({"error": {"message": str(exc)}}, status=404)
        return web.json_response({
            "id": "modr-tpu",
            "model": "tpu_local-moderation",
            "results": [{
                "flagged": score >= 0.5,
                "category_scores": {"harmful": score},
                "categories": {"harmful": score >= 0.5},
            } for score in scores],
        })

    app.add_routes(routes)
