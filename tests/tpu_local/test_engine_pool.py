"""EnginePool: affinity routing, failover requeue, drain/reload.

The pool's contract, in falsifiable form:

- a pool of 2 CPU replicas emits exactly the tokens a single engine
  would (greedy determinism survives the routing layer);
- prefix-cache affinity steers repeat prompts to the replica whose KV
  already holds the prefix;
- killing one replica mid-decode loses ZERO requests and duplicates
  ZERO tokens: in-flight requests requeue onto survivors as
  continuations and the merged streams stay byte-identical to an
  uninterrupted run;
- a wedged (blocked, not crashed) replica is detected by heartbeat +
  step-ring staleness and failed over the same way;
- drain stops routing, reload hot-swaps the engine, undrain readmits;
- the gateway serves GET /admin/engine/pool + per-replica actions.
"""

import asyncio
import threading

import pytest

from mcp_context_forge_tpu.tpu_local.engine import (EngineConfig, GenRequest,
                                                    TPUEngine)
from mcp_context_forge_tpu.tpu_local.pool import (EnginePool,
                                                  partition_devices)


def _config(**overrides):
    kwargs = dict(model="llama3-test", max_batch=4, max_seq_len=128,
                  page_size=16, num_pages=64, prefill_buckets=(16, 64),
                  dtype="float32", attn_impl="reference")
    kwargs.update(overrides)
    return EngineConfig(**kwargs)


def _pool(replicas=2, **overrides):
    health = overrides.pop("health_interval_s", 0.05)
    beat = overrides.pop("heartbeat_timeout_s", 10.0)
    return EnginePool(_config(**overrides), replicas=replicas,
                      health_interval_s=health, heartbeat_timeout_s=beat)


async def _reference_streams(prompts, max_tokens=24, **overrides):
    """What a single uninterrupted engine produces for ``prompts``."""
    engine = TPUEngine(_config(**overrides))
    await engine.start()
    outs = []
    try:
        for prompt in prompts:
            ids = engine.tokenizer.encode(prompt)
            outs.append([t async for t in engine.generate(
                ids, max_tokens=max_tokens)])
    finally:
        await engine.stop()
    return outs


def _poison_decode(engine, explode_after=3):
    """Wrap both decode-dispatch compilers so the Nth dispatch raises —
    the same injected-device-fault seam test_engine_overlap uses."""
    calls = {"n": 0}
    for name in ("_decode_fn", "_decode_fb_fn"):
        real = getattr(engine, name)

        def make(real):
            def exploding(ctx_pages, batch=None):
                fn = real(ctx_pages, batch)

                def wrapper(*args, **kwargs):
                    calls["n"] += 1
                    if calls["n"] >= explode_after:
                        raise RuntimeError("injected device fault")
                    return fn(*args, **kwargs)
                return wrapper
            return exploding
        setattr(engine, name, make(real))
    return calls


# ----------------------------------------------------------------- routing

def test_partition_devices_shapes():
    devs = list(range(8))
    assert partition_devices(devs, 1) == [devs]
    assert partition_devices(devs, 2) == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert partition_devices(devs, 4) == [[0, 1], [2, 3], [4, 5], [6, 7]]
    # non-divisor: equal slices, remainder idles (logged)
    assert partition_devices(devs, 3) == [[0, 1], [2, 3], [4, 5]]
    # fewer devices than replicas (CPU tests): full-overlap sharing
    assert partition_devices([0], 3) == [[0], [0], [0]]


def test_full_machine_mesh_shape_falls_back_per_replica():
    """An explicit tpu_local_mesh_shape is sized for the FULL machine:
    when it cannot fit a replica's device subset the pool must fall back
    to the auto mesh instead of failing every per-replica make_mesh at
    boot (the '1x8 spec + 2 replicas on a v5e-8' config)."""
    pool = _pool(replicas=2, mesh_shape="1x8")
    for replica in pool.replicas:
        assert replica.engine.config.mesh_shape == ""
        assert replica.engine.mesh.size >= 1


@pytest.fixture(scope="module")
def healthy_pool_run():
    """Six greedy prompts gathered through a healthy pool of 2, beside
    what a single uninterrupted engine emits for them; the stopped pool
    rides along for its counters."""
    prompts = [f"parity prompt {i} with a few extra words" for i in range(6)]

    async def main():
        refs = await _reference_streams(prompts, max_tokens=12)
        pool = _pool(replicas=2)
        await pool.start()
        try:
            async def gen(p):
                ids = pool.tokenizer.encode(p)
                return [t async for t in pool.generate(ids, max_tokens=12)]

            outs = await asyncio.gather(*[gen(p) for p in prompts])
        finally:
            await pool.stop()
        return refs, [list(o) for o in outs], pool

    return asyncio.run(main())


def test_pool_greedy_parity_with_single_engine(healthy_pool_run):
    """Seeded greedy token parity: routing across 2 replicas must be
    invisible in the token streams."""
    refs, outs, pool = healthy_pool_run
    assert outs == refs
    # both replicas actually served (least-outstanding spreads load)
    assert all(r.routed > 0 for r in pool.replicas), \
        [r.routed for r in pool.replicas]
    assert pool.requeues == 0


def test_pool_accounts_every_token_to_the_replica_that_served_it(
        healthy_pool_run):
    """The pool's balance report adds up: every request was routed once,
    every emitted token is counted on exactly one replica's stats, and a
    uniform pool (no roles) never migrates."""
    _, outs, pool = healthy_pool_run
    assert pool.router.counters()["routed"] == len(outs)
    assert sum(r.routed for r in pool.replicas) == len(outs)
    assert [r.id for r in pool.replicas] == ["0", "1"]
    assert sum(r.engine.stats.completion_tokens
               for r in pool.replicas) == sum(len(o) for o in outs)
    assert pool.migrations == {"ok": 0, "degraded": 0}


def test_prefix_affinity_routes_to_cached_replica():
    """A prompt whose full-page prefix is resident on replica R routes
    back to R (suffix-only prefill there); the router counts the hit."""
    async def main():
        pool = _pool(replicas=2)
        await pool.start()
        try:
            prompt = "the quick brown fox jumps over the lazy dog " * 2
            ids = pool.tokenizer.encode(prompt)
            out1 = [t async for t in pool.generate(ids, max_tokens=4)]
            assert out1
            first = next(r for r in pool.replicas if r.routed)
            # the serving replica's cache now holds the prompt's pages
            assert first.engine.allocator.probe_prefix(ids) >= \
                pool.config.page_size
            out2 = [t async for t in pool.generate(ids, max_tokens=4)]
            assert out2 == out1  # same weights, same greedy continuation
            assert pool.router.affinity_hits >= 1
            assert first.routed == 2  # the twin followed the cache
        finally:
            await pool.stop()

    asyncio.run(main())


def test_priority_rides_through_to_the_shadow():
    """Per-priority admission is carried through routing: the engine-facing
    shadow keeps the request's class (the replica's own scheduler applies
    it), and a requeued shadow rides the once-only queue-observation
    guard."""
    pool = _pool(replicas=2)
    request = GenRequest(request_id="prio", prompt_ids=[1, 2, 3],
                         max_tokens=8, priority=1)
    shadow = pool._make_shadow(request, attempts=1)
    assert shadow.priority == 1
    assert shadow.queue_observed is False
    assert shadow.ttft_observed is False
    request.generated.extend([5, 6])
    requeued = pool._make_shadow(request, attempts=2)
    assert requeued.priority == 1
    assert requeued.queue_observed is True  # once-only guard composition
    assert requeued.prompt_ids == [1, 2, 3, 5, 6]  # continuation prompt
    assert requeued.max_tokens == 6
    # the failed attempt already delivered a first token, so the logical
    # request's TTFT was observed: the continuation must not observe a
    # second sample (or re-emit llm.prefill)
    assert requeued.ttft_observed is True
    # ...but a requeue BEFORE any token keeps the TTFT observation live
    fresh = GenRequest(request_id="fresh", prompt_ids=[1, 2], max_tokens=4)
    assert pool._make_shadow(fresh, attempts=2).ttft_observed is False


def test_shadow_carries_trace_context_across_requeues():
    """llm.* spans must stay parented to the gateway request after a
    replica kill: first-attempt AND requeued continuation shadows carry
    the original request's trace_ctx (the engine's _span parents off it,
    so losing it on failover would orphan every post-failover span)."""
    pool = _pool(replicas=2)
    trace_ctx = ("ab" * 16, "cd" * 8)
    request = GenRequest(request_id="traced", prompt_ids=[1, 2, 3],
                         max_tokens=8, trace_ctx=trace_ctx)
    assert pool._make_shadow(request, attempts=1).trace_ctx == trace_ctx
    request.generated.extend([4, 5])
    requeued = pool._make_shadow(request, attempts=2)
    assert requeued.trace_ctx == trace_ctx
    assert requeued.request_id == "traced~r1"


# ---------------------------------------------------------------- failover

def test_kill_one_replica_mid_decode_loses_nothing():
    """Chaos: replica 1's dispatch crashes mid-decode. Every in-flight
    request completes on the survivor, every stream is byte-identical to
    an uninterrupted single-engine run (zero loss, zero duplicates), and
    the pool records the requeues."""
    prompts = [f"chaos prompt number {i} with some extra words"
               for i in range(6)]

    async def main():
        refs = await _reference_streams(prompts, max_tokens=24)
        pool = _pool(replicas=2)
        _poison_decode(pool.replicas[1].engine, explode_after=3)
        await pool.start()
        try:
            async def gen(p):
                ids = pool.tokenizer.encode(p)
                return [t async for t in pool.generate(ids, max_tokens=24)]

            outs = await asyncio.gather(*[gen(p) for p in prompts])
        finally:
            await pool.stop()
        assert [list(o) for o in outs] == refs  # no loss, no duplicates
        assert pool.requeues >= 1
        assert pool.replicas[1].state == "dead"
        assert pool.replicas[1].requeued_off >= 1
        assert pool.replicas[0].state == "ready"
        # the status card's requeued_off and the pool's requeues counter
        # (which feeds mcpforge_llm_pool_requeues_total) count the same
        # events, whichever path (health sweep / pump terminal) fired
        assert sum(r.requeued_off for r in pool.replicas) == pool.requeues
        status = pool.status()
        assert status["replicas"][1]["last_failure"]
        # the status card carries the compile-tracking + live-roofline
        # blocks per replica (what /admin/engine/pool and the support
        # bundle serve)
        for card in status["replicas"]:
            assert {"warmup", "serving"} <= set(card["xla_compiles"])
            assert "cost_entries" in card["roofline"]
            # and, beside the engine's other counts, the early flushes of
            # first tokens (every request here had its first from a prefill)
            assert card["first_flushes"] == \
                pool.replicas[int(card["id"])].engine.stats.first_flushes
        assert sum(card["first_flushes"] for card in status["replicas"]) >= 1

    asyncio.run(main())


def test_wedged_replica_detected_and_failed_over():
    """A replica whose dispatch thread BLOCKS (alive but stuck in a
    device call) is detected by heartbeat + step-ring staleness and its
    in-flight requests finish on the survivor."""
    async def main():
        # warmed engines: with the shape grid precompiled, a stale
        # heartbeat means a genuine stall, never a mid-traffic compile —
        # the same posture docs/serving_pool.md prescribes for running
        # the monitor with a tight timeout in production
        pool = _pool(replicas=2, health_interval_s=0.05,
                     heartbeat_timeout_s=0.5, warmup=True)
        await pool.start()
        release = threading.Event()
        try:
            # both replicas retire steps first: the wedge verdict
            # deliberately ignores cold replicas (first-dispatch compiles)
            for _ in range(2):
                for replica in pool.replicas:
                    req = GenRequest(
                        request_id=f"warm-{replica.id}",
                        prompt_ids=pool.tokenizer.encode("warm up"),
                        max_tokens=2)
                    await replica.engine.submit(req)
                    while await req.stream.get() is not None:
                        pass
            victim = pool.replicas[1].engine

            def make_blocking(real):
                def blocking(ctx_pages, batch=None):
                    fn = real(ctx_pages, batch)

                    def wrapper(*args, **kwargs):
                        release.wait(30)  # simulated wedged device call
                        return fn(*args, **kwargs)
                    return wrapper
                return blocking
            victim._decode_fn = make_blocking(victim._decode_fn)
            victim._decode_fb_fn = make_blocking(victim._decode_fb_fn)

            refs = await _reference_streams(["wedge survivor prompt"],
                                            max_tokens=16)
            # route a request directly onto the wedged replica's path by
            # submitting through the pool until it lands there
            async def gen():
                ids = pool.tokenizer.encode("wedge survivor prompt")
                return [t async for t in pool.generate(ids, max_tokens=16)]

            outs = await asyncio.gather(*[gen() for _ in range(4)])
            assert all(list(o) == refs[0] for o in outs)
            assert pool.replicas[1].state == "dead"
            assert pool.requeues >= 1
            assert pool.health.failures >= 1
        finally:
            release.set()  # let the blocked thread exit before joining
            await pool.stop()

    asyncio.run(main())


def test_wedge_verdict_matrix():
    """The health verdict's exemption logic, directly: wedge detection is
    armed ONLY on warmed engines — on an unwarmed one any dispatch,
    first or mid-traffic (new batch width, bigger ctx bucket), may sit
    in an XLA compile longer than the heartbeat bar, and killing a
    compiling replica cascades onto an equally unwarmed survivor. A
    WARMED replica with a stale heartbeat and in-flight work is a wedge
    even before its first step — without that arm a device that dies
    between warmup and the first request hangs its requests forever
    (step_age never becomes non-None on a replica that cannot retire a
    step)."""
    from types import SimpleNamespace

    from mcp_context_forge_tpu.tpu_local.pool.health import HealthMonitor

    def replica(warmed, hb_age, step_age, outstanding=1, alive=True):
        engine = SimpleNamespace(
            dispatch_alive=lambda: alive,
            heartbeat_age=lambda: hb_age,
            last_step_age=lambda: step_age,
            warmed=warmed)
        return SimpleNamespace(engine=engine,
                               outstanding={"r": None} if outstanding else {})

    monitor = HealthMonitor(pool=None, heartbeat_timeout_s=1.0)
    assert monitor.verdict(replica(False, 99.0, None)) is None     # cold compile
    assert monitor.verdict(replica(False, 99.0, 99.0)) is None     # mid-traffic compile
    assert monitor.verdict(replica(True, 99.0, None)) is not None  # warmed wedge
    assert monitor.verdict(replica(True, 0.1, None)) is None       # beating
    assert monitor.verdict(replica(True, 99.0, 99.0)) is not None  # classic wedge
    assert monitor.verdict(replica(True, 99.0, 0.1)) is None       # retiring
    assert monitor.verdict(replica(True, 99.0, None,
                                   outstanding=0)) is None         # idle
    # crash detection stays armed on UNWARMED engines (warmup gates only
    # the wedge heuristics, which compiles can fool)
    assert monitor.verdict(replica(False, 0.0, None,
                                   alive=False)) == "dispatch thread dead"
    assert monitor.verdict(replica(True, 0.0, None,
                                   alive=False)) == "dispatch thread dead"


def test_killed_engine_refuses_submissions():
    """kill() must make submit() raise: the health sweep can kill a
    replica WHILE a pool submit awaits queue backpressure, and a silent
    enqueue into the dead engine would strand that request forever
    (kill clears _started, which alone would disarm the thread-liveness
    check)."""
    async def main():
        engine = TPUEngine(_config())
        await engine.start()
        try:
            engine.kill()
            request = GenRequest(request_id="late", prompt_ids=[1, 2, 3],
                                 max_tokens=4)
            with pytest.raises(RuntimeError):
                await engine.submit(request)
        finally:
            await engine.stop()

    asyncio.run(main())


def test_engine_request_cancel_mid_decode():
    """request_cancel terminates a running generation through the normal
    stream path: the dispatch thread consumes the mark at its next
    iteration and posts the terminal with finish_reason='cancelled'."""
    async def main():
        engine = TPUEngine(_config())
        await engine.start()
        try:
            ids = engine.tokenizer.encode("cancel me mid decode")
            request = GenRequest(request_id="to-cancel", prompt_ids=ids,
                                 max_tokens=96)
            await engine.submit(request)
            tokens = []
            cancelled = False
            while True:
                token = await asyncio.wait_for(request.stream.get(),
                                               timeout=60)
                if token is None:
                    break
                tokens.append(token)
                if len(tokens) == 2 and not cancelled:
                    cancelled = engine.request_cancel("to-cancel")
            assert cancelled
            assert request.finish_reason == "cancelled"
            assert len(tokens) < 96  # terminated early, stream clean
            # unknown ids report False instead of parking a dead mark
            assert engine.request_cancel("never-existed") is False
        finally:
            await engine.stop()

    asyncio.run(main())


def test_pool_cancel_routes_to_serving_replica():
    """pool.cancel finds the record by the CLIENT-facing id on whichever
    replica the router chose and cancels the engine-side shadow; the
    pump forwards the cancelled terminal to the client stream."""
    async def main():
        pool = _pool(replicas=2)
        await pool.start()
        try:
            ids = pool.tokenizer.encode("pool cancel target")
            request = GenRequest(request_id="logical-1", prompt_ids=ids,
                                 max_tokens=96)
            await pool.submit(request)
            tokens = []
            cancelled = False
            while True:
                token = await asyncio.wait_for(request.stream.get(),
                                               timeout=60)
                if token is None:
                    break
                tokens.append(token)
                if len(tokens) == 2 and not cancelled:
                    cancelled = pool.cancel("logical-1")
            assert cancelled
            assert request.finish_reason == "cancelled"
            assert len(tokens) < 96
            assert pool.cancel("logical-1") is False  # already finished
            # the CancellationService speaks the same surface (the MCP
            # notifications/cancelled path under a pool)
            from types import SimpleNamespace

            from mcp_context_forge_tpu.services.cancellation_service import \
                CancellationService
            service = CancellationService(
                SimpleNamespace(extras={"tpu_engine_pool": pool}))
            victim = GenRequest(request_id="logical-2", prompt_ids=ids,
                                max_tokens=96)
            await pool.submit(victim)
            got = await asyncio.wait_for(victim.stream.get(), timeout=60)
            assert got is not None
            assert await service.cancel("logical-2") is True
            while await asyncio.wait_for(victim.stream.get(),
                                         timeout=60) is not None:
                pass
            assert victim.finish_reason == "cancelled"
        finally:
            await pool.stop()

    asyncio.run(main())


def test_requeue_budget_exhaustion_terminates_as_unavailable():
    """ISSUE-14 satellite: a spent requeue budget (every replica gone)
    terminates the stream with finish_reason='unavailable' — the clean
    capacity-loss terminal the HTTP surface maps to 503 + Retry-After
    (backpressure-header contract) — never a bare mid-stream 'error'."""
    async def main():
        pool = _pool(replicas=2)
        _poison_decode(pool.replicas[0].engine, explode_after=1)
        _poison_decode(pool.replicas[1].engine, explode_after=1)
        await pool.start()
        try:
            ids = pool.tokenizer.encode("doomed request")
            request = GenRequest(request_id="doomed", prompt_ids=ids,
                                 max_tokens=16)
            await pool.submit(request)
            tokens = []
            while True:
                token = await asyncio.wait_for(request.stream.get(),
                                               timeout=60)
                if token is None:
                    break
                tokens.append(token)
            assert request.finish_reason == "unavailable"
            assert all(r.state == "dead" for r in pool.replicas)
        finally:
            await pool.stop()

    asyncio.run(main())


def test_unavailable_terminal_maps_to_llm_unavailable():
    """The provider half of the contract: a stream that ends
    'unavailable' with nothing delivered raises LLMUnavailable (the
    server answers 503 + Retry-After), both unary and streaming."""
    from mcp_context_forge_tpu.tpu_local.provider import LLMUnavailable
    from mcp_context_forge_tpu.tpu_local.tpu_provider import \
        TPULocalProvider

    class _UnavailableEngine:
        """Duck-typed engine surface whose every request is refused the
        way a requeue-exhausted pool refuses it."""

        def __init__(self, engine):
            self.tokenizer = engine.tokenizer
            self.config = engine.config

        async def submit(self, gen):
            gen.finish_reason = "unavailable"
            gen.stream.put_nowait(None)
            return gen

    async def main():
        engine = TPUEngine(_config())
        provider = TPULocalProvider("tpu_local",
                                    _UnavailableEngine(engine))
        request = {"model": "llama3-test",
                   "messages": [{"role": "user", "content": "hi"}],
                   "max_tokens": 4}
        with pytest.raises(LLMUnavailable) as err:
            await provider.chat(request)
        assert err.value.retry_after_s >= 1
        with pytest.raises(LLMUnavailable):
            async for _chunk in provider.chat_stream(dict(request)):
                pass

    asyncio.run(main())


def test_unavailable_mid_stream_yields_structured_terminal():
    """Tokens already delivered: the stream must END with a structured
    chunk (finish_reason='unavailable' + error object carrying the 503
    retry advisory), never a bare exception into the SSE writer."""
    from mcp_context_forge_tpu.tpu_local.tpu_provider import \
        TPULocalProvider

    class _DieMidStreamEngine:
        def __init__(self, engine):
            self.tokenizer = engine.tokenizer
            self.config = engine.config

        async def submit(self, gen):
            for token in self.tokenizer.encode("partial answer")[:3]:
                gen.generated.append(token)
                gen.stream.put_nowait(token)
            gen.finish_reason = "unavailable"
            gen.stream.put_nowait(None)
            return gen

    async def main():
        engine = TPUEngine(_config())
        provider = TPULocalProvider("tpu_local",
                                    _DieMidStreamEngine(engine))
        chunks = [c async for c in provider.chat_stream(
            {"model": "llama3-test",
             "messages": [{"role": "user", "content": "hi"}],
             "max_tokens": 8})]
        assert chunks, "partial content must still reach the client"
        terminal = chunks[-1]
        assert terminal["choices"][0]["finish_reason"] == "unavailable"
        assert terminal["error"]["code"] == 503
        assert terminal["error"]["retry_after_s"] >= 1

    asyncio.run(main())


# ------------------------------------------------------------ drain/reload

def test_drain_reload_roundtrip():
    """drain -> no new routing; reload -> fresh engine object serving
    identical weights; undrain symmetric."""
    async def main():
        pool = _pool(replicas=2)
        await pool.start()
        try:
            ids = pool.tokenizer.encode("drain reload prompt")
            out1 = [t async for t in pool.generate(ids, max_tokens=6)]

            status = await pool.drain("0")
            assert status["drained"]
            assert pool.replicas[0].state == "draining"
            routed_before = pool.replicas[1].routed
            for _ in range(3):
                out = [t async for t in pool.generate(ids, max_tokens=4)]
                assert out
            assert pool.replicas[1].routed == routed_before + 3
            assert pool.replicas[0].state == "draining"

            await pool.undrain("0")
            assert pool.replicas[0].state == "ready"

            old_engine = pool.replicas[0].engine
            status = await pool.reload("0")
            assert status["state"] == "ready"
            assert pool.replicas[0].engine is not old_engine
            assert pool.replicas[0].reloads == 1
            # the single-engine admin surfaces resolve the CURRENT
            # engine through the pool — a "tpu_engine" reference
            # captured at app build time is stale after the swap
            from mcp_context_forge_tpu.services.diagnostics_service import \
                live_tpu_engine
            container = {"tpu_engine_pool": pool, "tpu_engine": old_engine}
            assert live_tpu_engine(container) is pool.replicas[0].engine
            assert live_tpu_engine(
                {"tpu_engine": old_engine}) is old_engine  # pool-less path
            # the reloaded engine serves the same (seeded) weights
            out2 = [t async for t in pool.generate(ids, max_tokens=6)]
            assert out2 == out1
        finally:
            await pool.stop()

    asyncio.run(main())


def test_reload_recovers_a_dead_replica():
    """reload is the recovery path for a crashed replica: rebuild, then
    the router uses it again."""
    async def main():
        pool = _pool(replicas=2)
        _poison_decode(pool.replicas[1].engine, explode_after=1)
        await pool.start()
        try:
            ids = pool.tokenizer.encode("kill then heal")
            # drive traffic until the poisoned replica dies
            for _ in range(4):
                out = [t async for t in pool.generate(ids, max_tokens=6)]
                assert out
                if pool.replicas[1].state == "dead":
                    break
            assert pool.replicas[1].state == "dead"
            await pool.reload("1")
            assert pool.replicas[1].state == "ready"
            # drain the healthy one: traffic must now flow through the
            # recovered replica
            await pool.drain("0")
            out = [t async for t in pool.generate(ids, max_tokens=6)]
            assert out
            assert pool.replicas[1].routed >= 1
        finally:
            await pool.stop()

    asyncio.run(main())


def test_reload_requeues_stragglers_onto_survivor():
    """A reload whose drain window closes with a generation still running
    must hand it to the surviving replicas as a continuation (the same
    path failover uses), NOT let engine.stop() truncate the client
    stream with finish_reason='cancelled'."""
    async def main():
        refs = await _reference_streams(["reload straggler prompt"],
                                        max_tokens=64)
        assert len(refs[0]) == 64  # long enough to outlive a 0s drain
        pool = _pool(replicas=2)
        await pool.start()
        try:
            # pin the request onto replica 0 by draining 1 first
            await pool.drain("1")
            ids = pool.tokenizer.encode("reload straggler prompt")
            request = GenRequest(request_id="straggler", prompt_ids=ids,
                                 max_tokens=64)
            await pool.submit(request)
            assert "straggler" in pool.replicas[0].outstanding
            first = await asyncio.wait_for(request.stream.get(), timeout=60)
            assert first is not None
            await pool.undrain("1")

            # zero drain window: the generation cannot finish in time
            await pool.reload("0", timeout_s=0)

            tokens = [first]
            while True:
                token = await asyncio.wait_for(request.stream.get(),
                                               timeout=60)
                if token is None:
                    break
                tokens.append(token)
            assert request.finish_reason != "cancelled"
            assert tokens == refs[0]  # continuation parity on the survivor
            assert pool.requeues >= 1
            assert pool.replicas[1].routed >= 1
            assert pool.replicas[0].state == "ready"  # reload completed
        finally:
            await pool.stop()

    asyncio.run(main())


# ------------------------------------------------------------ gateway HTTP

async def _make_pool_gateway():
    from aiohttp.test_utils import TestClient, TestServer

    from mcp_context_forge_tpu.config import load_settings
    from mcp_context_forge_tpu.gateway.app import build_app

    settings = load_settings(env={
        "MCPFORGE_DATABASE_URL": "sqlite:///:memory:",
        "MCPFORGE_PLUGINS_ENABLED": "false",
        "MCPFORGE_TPU_LOCAL_ENABLED": "true",
        "MCPFORGE_TPU_LOCAL_MODEL": "llama3-test",
        "MCPFORGE_TPU_LOCAL_REPLICAS": "2",
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


async def test_gateway_pool_endpoints():
    import aiohttp
    auth = aiohttp.BasicAuth("admin", "changeme")
    gateway = await _make_pool_gateway()
    try:
        # chat flows through the pool-backed provider
        resp = await gateway.post("/v1/chat/completions", json={
            "model": "llama3-test",
            "messages": [{"role": "user", "content": "pool me"}],
            "max_tokens": 4,
        }, auth=auth)
        assert resp.status == 200, await resp.text()

        # acceptance: per-replica health, occupancy, routing counters
        resp = await gateway.get("/admin/engine/pool", auth=auth)
        assert resp.status == 200
        body = await resp.json()
        assert [r["id"] for r in body["replicas"]] == ["0", "1"]
        for replica in body["replicas"]:
            assert replica["state"] == "ready"
            assert "occupancy" in replica and "outstanding" in replica
            assert "heartbeat_age_s" in replica
        assert body["router"]["routed"] >= 1
        assert "requeues" in body and "health" in body

        # drain/undrain round-trip over HTTP
        resp = await gateway.post("/admin/engine/pool/0/drain", auth=auth)
        assert resp.status == 200
        assert (await resp.json())["state"] == "draining"
        resp = await gateway.post("/admin/engine/pool/0/undrain", auth=auth)
        assert resp.status == 200
        assert (await resp.json())["state"] == "ready"

        # unknown replica / action -> clean 4xx, not a 500
        resp = await gateway.post("/admin/engine/pool/9/drain", auth=auth)
        assert resp.status == 404
        resp = await gateway.post("/admin/engine/pool/0/explode", auth=auth)
        assert resp.status in (400, 422)
        # valid-JSON non-object body -> clean 4xx too (body.get would 500)
        resp = await gateway.post("/admin/engine/pool/0/drain", json=[30],
                                  auth=auth)
        assert resp.status in (400, 422)

        # replica-labeled SLO metrics reach the exposition
        resp = await gateway.get("/metrics/prometheus", auth=auth)
        text = await resp.text()
        assert 'mcpforge_llm_pool_replica_up{replica="0"}' in text
        assert 'mcpforge_llm_pool_replica_up{replica="1"}' in text
        assert 'replica="' in [line for line in text.splitlines()
                               if "mcpforge_llm_ttft_seconds_count" in line][0]
    finally:
        await gateway.close()


async def test_gateway_pool_404_when_single_replica():
    """With replicas=1 the pool layer does not exist; the endpoint says
    so instead of pretending a pool of one."""
    from aiohttp.test_utils import TestClient, TestServer

    import aiohttp

    from mcp_context_forge_tpu.config import load_settings
    from mcp_context_forge_tpu.gateway.app import build_app

    settings = load_settings(env={
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
    try:
        auth = aiohttp.BasicAuth("admin", "changeme")
        resp = await client.get("/admin/engine/pool", auth=auth)
        assert resp.status == 404
    finally:
        await client.close()
