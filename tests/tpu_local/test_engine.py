"""Engine: continuous batching scheduler over the paged cache (CPU)."""

import asyncio
import dataclasses

import pytest

from mcp_context_forge_tpu.tpu_local.engine import (_SETTING_OF, EngineConfig,
                                                    GenRequest, TPUEngine)


@pytest.fixture(scope="module")
def engine():
    config = EngineConfig(model="llama3-test", max_batch=4, max_seq_len=128,
                          page_size=16, num_pages=64, prefill_buckets=(16, 64),
                          dtype="float32", attn_impl="reference")
    return TPUEngine(config)


async def _run(engine: TPUEngine, coro):
    await engine.start()
    try:
        return await asyncio.wait_for(coro, timeout=300)
    finally:
        await engine.stop()


def test_greedy_generation_deterministic(engine):
    async def main():
        ids = engine.tokenizer.encode("hello world")
        out1 = [t async for t in engine.generate(ids, max_tokens=8)]
        out2 = [t async for t in engine.generate(ids, max_tokens=8)]
        assert len(out1) == 8 or engine.tokenizer.eos_id in out1
        assert out1 == out2  # greedy => deterministic
        return out1

    asyncio.run(_run_with(engine, main()))


def _run_with(engine, coro):
    async def wrapper():
        await engine.start()
        try:
            return await asyncio.wait_for(coro, timeout=300)
        finally:
            await engine.stop()
    return wrapper()


def test_concurrent_requests_share_batch(engine):
    async def main():
        ids1 = engine.tokenizer.encode("alpha")
        ids2 = engine.tokenizer.encode("bravo charlie")
        ids3 = engine.tokenizer.encode("delta echo foxtrot golf")
        steps_before = engine.stats.decode_steps

        async def gen(ids, n):
            return [t async for t in engine.generate(ids, max_tokens=n)]

        outs = await asyncio.gather(gen(ids1, 6), gen(ids2, 6), gen(ids3, 6))
        for out in outs:
            assert 1 <= len(out) <= 6
        # all pages freed after completion
        assert engine.allocator.pages_in_use == 0
        # continuous batching actually batched: strictly fewer decode steps
        # than a serial run (3 requests × 5 post-prefill tokens = 15)
        assert engine.stats.decode_steps - steps_before < 15

    asyncio.run(_run_with(engine, main()))


def test_oversized_prompt_rejected(engine):
    async def main():
        ids = list(range(300))  # > max bucket 64
        request = GenRequest(request_id="big", prompt_ids=ids, max_tokens=4)
        await engine.submit(request)
        token = await asyncio.wait_for(request.stream.get(), timeout=60)
        assert token is None
        assert request.finish_reason == "length"

    asyncio.run(_run_with(engine, main()))


def test_more_requests_than_slots(engine):
    async def main():
        ids = engine.tokenizer.encode("queue pressure")

        async def gen():
            return [t async for t in engine.generate(ids, max_tokens=4)]

        outs = await asyncio.gather(*[gen() for _ in range(10)])  # > max_batch=4
        assert all(len(o) >= 1 for o in outs)
        assert engine.allocator.pages_in_use == 0

    asyncio.run(_run_with(engine, main()))


def test_burst_admissions_share_prefill_batch(engine):
    """4 same-bucket requests submitted together must fuse into few prefill
    calls (batched admission), not 4 serial batch=1 prefills."""
    async def main():
        ids = engine.tokenizer.encode("burst")
        batches_before = engine.stats.prefill_batches
        reqs_before = engine.stats.prefill_requests

        async def gen():
            return [t async for t in engine.generate(ids, max_tokens=3)]

        outs = await asyncio.gather(*[gen() for _ in range(4)])
        assert all(len(o) >= 1 for o in outs)
        new_batches = engine.stats.prefill_batches - batches_before
        new_reqs = engine.stats.prefill_requests - reqs_before
        assert new_reqs == 4
        assert new_batches < 4  # at least one fused admission

    asyncio.run(_run_with(engine, main()))


def test_sampled_generation_on_device(engine):
    """temperature>0 path: first token comes from the device sampler too."""
    async def main():
        ids = engine.tokenizer.encode("sample me")
        out = [t async for t in engine.generate(ids, max_tokens=6,
                                                temperature=0.9, top_k=40,
                                                top_p=0.95)]
        assert 1 <= len(out) <= 6
        assert all(0 <= t < engine.model_config.vocab_size for t in out)
        assert engine.allocator.pages_in_use == 0

    asyncio.run(_run_with(engine, main()))


# what the tree before the tiers (the vocabulary sort on every step) emitted
# for this prompt, this model and this engine configuration
_GREEDY_HELLO_WORLD = [469, 0, 77, 28, 408, 224, 471, 490, 381, 218, 186, 290]
_SAMPLE_TIERS = ("sample_argmax_steps", "sample_plain_steps",
                 "sample_filtered_steps")


@pytest.mark.parametrize("asked,tier", [
    ({}, "sample_argmax_steps"),
    ({"temperature": 0.7}, "sample_plain_steps"),
    ({"temperature": 0.7, "top_p": 0.9}, "sample_filtered_steps"),
], ids=["greedy", "temperature", "top_p"])
def test_steps_counted_by_the_sampling_their_rows_ask_for(engine, asked, tier):
    """A request's steps count in its tier while it lives and in no other;
    once it is gone a greedy request takes the argmax alone, and emits the
    tokens it always did."""
    def counts():
        return [getattr(engine.stats, name) for name in _SAMPLE_TIERS]

    def moved(before, after):
        return [name for name, b, a in zip(_SAMPLE_TIERS, before, after)
                if a > b]

    async def main():
        ids = engine.tokenizer.encode("hello world")
        start = counts()
        out = [t async for t in engine.generate(ids, max_tokens=12, **asked)]
        assert 1 <= len(out) <= 12
        lived = counts()
        assert moved(start, lived) == [tier]
        greedy = [t async for t in engine.generate(ids, max_tokens=12)]
        assert moved(lived, counts()) == ["sample_argmax_steps"]
        assert greedy == _GREEDY_HELLO_WORLD

    asyncio.run(_run_with(engine, main()))


def test_event_loop_stays_responsive(engine):
    """Device syncs live on the dispatch thread: the asyncio loop must keep
    scheduling while a generation runs (VERDICT round 1 weak #3)."""
    async def main():
        ids = engine.tokenizer.encode("long generation " * 3)
        gaps = []

        async def ticker():
            last = asyncio.get_running_loop().time()
            while True:
                await asyncio.sleep(0.005)
                now = asyncio.get_running_loop().time()
                gaps.append(now - last)
                last = now

        task = asyncio.create_task(ticker())
        out = [t async for t in engine.generate(ids, max_tokens=24)]
        task.cancel()
        assert len(out) >= 1
        # loop iterations kept flowing; a blocked loop would show one giant gap
        assert gaps, "ticker never ran"
        assert max(gaps) < 1.0, f"event loop starved: max gap {max(gaps):.3f}s"

    asyncio.run(_run_with(engine, main()))


def test_encoder_batcher_coalesces():
    """Concurrent classify calls fuse into shared encoder forwards."""
    from mcp_context_forge_tpu.tpu_local.engine import EngineConfig, TPUEngine
    from mcp_context_forge_tpu.tpu_local.tpu_provider import TPULocalProvider

    config = EngineConfig(model="llama3-test", max_batch=2, max_seq_len=64,
                          page_size=16, num_pages=16, prefill_buckets=(16,),
                          dtype="float32", attn_impl="reference")
    provider = TPULocalProvider("tpu_local", TPUEngine(config))
    calls = []
    original = provider._encode_batch

    def counting(texts):
        calls.append(len(texts))
        return original(texts)

    provider._batcher._encode_batch = counting

    async def main():
        scores = await asyncio.gather(
            *[provider.classify([f"text {i}"]) for i in range(12)])
        assert all(0.0 <= s[0] <= 1.0 for s in scores)
        assert sum(calls) == 12
        assert len(calls) < 12  # at least one fused batch
        # embeddings ride the same batcher
        vecs = await provider.embed(["a", "b", "c"])
        assert len(vecs) == 3 and len(vecs[0]) > 0

    asyncio.run(main())


def test_superstep_matches_single_step():
    """Multi-step decode dispatch (superstep=4) produces the same greedy
    tokens as step-by-step, with ~1/4 the device dispatches."""
    def build(block):
        config = EngineConfig(model="llama3-test", max_batch=2, max_seq_len=128,
                              page_size=16, num_pages=64, prefill_buckets=(16,),
                              dtype="float32", attn_impl="reference",
                              superstep=block)
        return TPUEngine(config)

    async def run(engine, n):
        await engine.start()
        try:
            ids = engine.tokenizer.encode("block decode")
            return [t async for t in engine.generate(ids, max_tokens=n)]
        finally:
            await engine.stop()

    single = build(1)
    out1 = asyncio.run(run(single, 12))
    blocked = build(4)
    out4 = asyncio.run(run(blocked, 12))
    assert out1 == out4, (out1, out4)
    # 12 tokens: 1 prefill + 11 decode in blocks of 4 -> 3 dispatches = 12
    # counted steps; the single-step engine counts 11
    assert blocked.stats.decode_steps <= single.stats.decode_steps + 4
    assert blocked.allocator.pages_in_use == 0


def test_superstep_respects_max_tokens_and_capacity():
    config = EngineConfig(model="llama3-test", max_batch=2, max_seq_len=32,
                          page_size=16, num_pages=8, prefill_buckets=(16,),
                          dtype="float32", attn_impl="reference",
                          superstep=8)
    engine = TPUEngine(config)

    async def main():
        await engine.start()
        try:
            ids = engine.tokenizer.encode("cap")
            out = [t async for t in engine.generate(ids, max_tokens=5)]
            assert 1 <= len(out) <= 5
            # page-capacity-bound request terminates with finish
            long_out = [t async for t in engine.generate(ids, max_tokens=64)]
            assert len(long_out) >= 1
            assert engine.allocator.pages_in_use == 0
        finally:
            await engine.stop()

    asyncio.run(main())


def test_init_watchdog_times_out_on_wedged_backend(monkeypatch):
    """A dead TPU runtime blocks jax.devices() forever; the watchdog must
    convert that into a prompt EngineInitTimeout (gateway fails fast
    instead of never binding its port)."""
    import threading

    from mcp_context_forge_tpu.tpu_local import engine as eng

    release = threading.Event()

    def wedged_devices():
        release.wait(10)  # simulated wedged runtime; released in teardown
        return []

    monkeypatch.setattr(eng.jax, "devices", wedged_devices)
    try:
        with pytest.raises(eng.EngineInitTimeout, match="backend init"):
            eng.probe_devices(0.2)
    finally:
        release.set()


def test_init_watchdog_propagates_backend_errors(monkeypatch):
    from mcp_context_forge_tpu.tpu_local import engine as eng

    def broken_devices():
        raise RuntimeError("no backend")

    monkeypatch.setattr(eng.jax, "devices", broken_devices)
    with pytest.raises(RuntimeError, match="no backend"):
        eng.probe_devices(5.0)


def test_init_watchdog_disabled_and_passthrough():
    from mcp_context_forge_tpu.tpu_local import engine as eng

    assert eng.probe_devices(0) == eng.jax.devices()
    assert eng.probe_devices(30.0) == eng.jax.devices()


def test_engine_config_carries_init_timeout():
    from mcp_context_forge_tpu.config import load_settings

    settings = load_settings(env_file=None)
    cfg = EngineConfig.from_settings(settings)
    assert cfg.init_timeout_s == settings.tpu_local_init_timeout_s > 0


# where the gateway's default and a hand-built engine's differ, and why
_DEFAULTS_APART = {
    "max_batch": "a gateway serves 64 slots; an engine built by a test or a "
                 "script with no max_batch stays 8 rows small",
}


@pytest.mark.parametrize("spec", [
    spec for spec in dataclasses.fields(EngineConfig)
    if _SETTING_OF.get(spec.name, "") is not None], ids=lambda spec: spec.name)
def test_from_settings_reads_every_field(spec):
    """Every ``EngineConfig`` field that a setting reaches: a value set on
    ``Settings`` arrives in the config (a setting that is missing is an
    ``AttributeError`` here), and the default is the same in both places."""
    from mcp_context_forge_tpu.config import Settings, load_settings

    name = spec.name
    source = _SETTING_OF.get(name, "tpu_local_" + name)
    setting = "controller_k_ladder" if callable(source) else source
    if name not in _DEFAULTS_APART:
        assert Settings.model_fields[setting].default == spec.default
    kind = type(spec.default)
    value = {bool: lambda d: not d, int: lambda d: d + 1,
             float: lambda d: d + 1.5, str: lambda d: d + "-changed",
             tuple: lambda d: d + (7,)}[kind](spec.default)
    changed = load_settings(env_file=None).model_copy(
        update={setting: value, "controller_enabled": True})
    assert getattr(EngineConfig.from_settings(changed), name) == value
    if name == "k_ladder":      # read only where the controller is on
        off = changed.model_copy(update={"controller_enabled": False})
        assert EngineConfig.from_settings(off).k_ladder == ()


def test_engine_serves_qwen2_family():
    """End-to-end serving on the Qwen2-style config (attention biases +
    tied embeddings) — the family knobs work through the whole engine."""
    async def run():
        engine = TPUEngine(EngineConfig(
            model="qwen2-tiny", max_batch=2, max_seq_len=128, page_size=16,
            num_pages=64, prefill_buckets=(32,), dtype="float32",
            attn_impl="reference"))
        await engine.start()
        try:
            ids = engine.tokenizer.encode("hello qwen family")
            out = [t async for t in engine.generate(ids, max_tokens=8)]
            out2 = [t async for t in engine.generate(ids, max_tokens=8)]
            assert 1 <= len(out) <= 8 and out == out2  # greedy determinism
            assert engine.stats.prefill_batches >= 1
        finally:
            await engine.stop()

    asyncio.run(run())


def test_warmup_precompiles_without_corrupting_state():
    """warmup() compiles the full shape grid pre-traffic; generation after
    warmup is identical to a cold engine's (trash-page writes only, the
    allocator untouched)."""
    async def run():
        kwargs = dict(model="llama3-test", max_batch=2, max_seq_len=128,
                      page_size=16, num_pages=64, prefill_buckets=(16, 32),
                      prefill_max_batch=2, dtype="float32",
                      attn_impl="reference", superstep=2)
        warm = TPUEngine(EngineConfig(**kwargs, warmup=True))
        assert warm.allocator.pages_in_use == 0
        cold = TPUEngine(EngineConfig(**kwargs))
        ids = warm.tokenizer.encode("warmup parity prompt")

        async def gen(engine):
            await engine.start()
            try:
                return [t async for t in engine.generate(ids, max_tokens=6)]
            finally:
                await engine.stop()

        assert await gen(warm) == await gen(cold)

    asyncio.run(run())


def test_prepare_reserves_completion_room():
    """A near-full-context prompt must not clamp max_tokens to 1: the
    truncation reserves up to a quarter of the context for generation
    (the summarizer-over-long-tool-output shape)."""
    from mcp_context_forge_tpu.tpu_local.engine import EngineConfig, TPUEngine
    from mcp_context_forge_tpu.tpu_local.tpu_provider import TPULocalProvider

    config = EngineConfig(model="llama3-test", max_batch=2, max_seq_len=128,
                          page_size=16, num_pages=32, prefill_buckets=(32,),
                          dtype="float32", attn_impl="reference")
    provider = TPULocalProvider("tpu_local", TPUEngine(config))
    gen = provider._prepare({
        "messages": [{"role": "user", "content": "x" * 4000}],
        "max_tokens": 32})
    assert gen.max_tokens == 32
    assert len(gen.prompt_ids) == 128 - 32
    # small prompts are untouched and keep their full budget
    gen = provider._prepare({
        "messages": [{"role": "user", "content": "hi"}], "max_tokens": 16})
    assert gen.max_tokens == 16
    assert len(gen.prompt_ids) < 64
    # a request asking for more than the whole context still fits
    gen = provider._prepare({
        "messages": [{"role": "user", "content": "x" * 4000}],
        "max_tokens": 9999})
    assert len(gen.prompt_ids) + gen.max_tokens <= 128
    assert gen.max_tokens == 32  # reserve cap = ctx // 4


@pytest.mark.parametrize("case", ["env_set", "env_unset", "second_engine",
                                  "cache_off"])
def test_compile_cache_placed_from_outside(monkeypatch, case):
    """engine.apply_compile_cache's one rule: JAX_COMPILATION_CACHE_DIR set
    -> the code sets NO directory; unset -> <checkout>/.jax_cache (fixed:
    no per-host, per-pid or per-time part); a second engine does not move
    it; with the cache switched off (this suite) nothing is set at all."""
    import os
    from mcp_context_forge_tpu.tpu_local import engine as eng

    state = {"jax_compilation_cache_dir": None,
             "jax_enable_compilation_cache": case != "cache_off"}
    updates = []

    class FakeConfig:
        def __getattr__(self, name):
            return state[name]

        def update(self, key, value):
            updates.append((key, value))
            state[key] = value

    monkeypatch.setattr(eng.jax, "config", FakeConfig())
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if case == "env_set":
        monkeypatch.setenv(eng.COMPILE_CACHE_ENV, "/somewhere/else")
        assert eng.apply_compile_cache() == "/somewhere/else"
        assert updates == []
        return
    monkeypatch.delenv(eng.COMPILE_CACHE_ENV, raising=False)
    if case == "cache_off":
        assert eng.apply_compile_cache() is None
        assert updates == []
        return
    expected = os.path.join(checkout, ".jax_cache")
    assert eng.apply_compile_cache() == expected
    assert updates == [("jax_compilation_cache_dir", expected)]
    if case == "second_engine":
        assert eng.apply_compile_cache() == expected
        assert len(updates) == 1


def test_priority_admission_interactive_before_batch(engine):
    """Admission classes (SURVEY §7.2 #2): when slots are contended, an
    interactive request queued BEHIND background summaries admits first;
    FIFO holds within each class. Drives _admit_batch directly (no
    dispatch thread) so the pending order is deterministic."""
    ids = engine.tokenizer.encode("hello world")
    batch = [GenRequest(request_id=f"bg{i}", prompt_ids=ids, max_tokens=4,
                        priority=1) for i in range(3)]
    chat = GenRequest(request_id="chat", prompt_ids=ids, max_tokens=4,
                      priority=0)
    for request in batch:
        engine._pending.append(request)
    engine._pending.append(chat)  # arrives LAST
    try:
        engine._admit_batch()
        running = {r.request_id for r in engine._running.values()}
        assert "chat" in running
        # 4 slots, 4 requests, prefill_max_batch=4: all admitted, but the
        # interactive one leads the group (slot order follows group order)
        assert chat.slot == 0
        # FIFO preserved within the background class
        bg_slots = [r.slot for r in batch]
        assert bg_slots == sorted(bg_slots)
    finally:
        for slot in list(engine._running):
            engine._running.pop(slot)
            engine.allocator.free_slot(slot)
        engine._pending.clear()
        engine._sync_tables()
