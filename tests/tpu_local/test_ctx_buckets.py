"""Context-width bucketing correctness (ADR 010).

The engine compiles decode/history-prefill per power-of-two context-width
bucket and slices the block table to it. These tests pin the invariants
that make that safe: bucket selection always covers the longest active
row (including mid-block growth), and generations that CROSS bucket
boundaries are bit-identical to a full-width engine.
"""

import asyncio

import jax
import pytest

from mcp_context_forge_tpu.tpu_local.engine import EngineConfig, TPUEngine


def _engine(devices: int | None = None, **overrides) -> TPUEngine:
    base = dict(model="llama3-test", max_batch=2, max_seq_len=256,
                page_size=16, num_pages=64, prefill_buckets=(32,),
                dtype="float32", attn_impl="reference", prefix_cache=False)
    base.update(overrides)
    return TPUEngine(EngineConfig(**base), devices=jax.devices()[:devices])


def _greedy(engine: TPUEngine, prompt: list[int], max_tokens: int) -> list[int]:
    async def run():
        await engine.start()
        try:
            out = []
            async for tok in engine.generate(prompt, max_tokens=max_tokens):
                out.append(tok)
            return out
        finally:
            await engine.stop()

    return asyncio.run(run())


def test_bucket_selection_covers_need():
    engine = _engine()
    # max_seq_len 256 / page 16 = 16 pages; buckets 4, 8, 16
    assert engine._ctx_buckets() == [4, 8, 16]
    assert engine._ctx_bucket_for(1) == 4
    assert engine._ctx_bucket_for(64) == 4      # exactly 4 pages
    assert engine._ctx_bucket_for(65) == 8      # crosses into page 5
    assert engine._ctx_bucket_for(128) == 8
    assert engine._ctx_bucket_for(129) == 16
    assert engine._ctx_bucket_for(10_000) == 16  # clamped to table width


def test_generation_across_bucket_boundary_matches_full_width():
    """A greedy generation that grows from inside the smallest bucket
    (prompt 30 tokens) THROUGH the 64- and 128-token boundaries must
    emit exactly what an engine pinned to full width emits — bucketing
    may never change logits, only traffic."""
    bucketed = _engine()
    prompt = bucketed.tokenizer.encode("x" * 29)  # bos + 29 -> 30 tokens
    out_bucketed = _greedy(bucketed, prompt, max_tokens=120)

    full = _engine()
    # pin every dispatch to the full table width
    table_pages = full.config.max_seq_len // full.config.page_size
    full._ctx_bucket_for = lambda needed: table_pages
    full._hist_ctx_for = lambda needed: table_pages
    out_full = _greedy(full, prompt, max_tokens=120)

    assert out_bucketed == out_full
    assert len(out_bucketed) == 120  # crossed 64 and 128 token boundaries


def test_superstep_respects_bucket_growth():
    """superstep > 1 extends positions INSIDE one dispatch: the bucket
    chosen for the block must already cover seq_len + k, or late
    sub-steps would write/read past the sliced table."""
    engine = _engine(superstep=4)
    prompt = engine.tokenizer.encode("y" * 29)
    out = _greedy(engine, prompt, max_tokens=40)
    assert len(out) == 40

    reference = _engine(superstep=1)
    assert out == _greedy(reference, prompt, max_tokens=40)


@pytest.mark.parametrize("family", [
    dict(superstep=2, k_ladder=(1, 2)),
    dict(model="sdar-test", decode_overlap=False),
], ids=["token", "block"])
def test_decode_programs_are_keyed_by_k_and_context_only(family):
    """A decode dispatch is ``max_batch`` rows wide, always: a warm-up
    leaves one program a (superstep rung, context bucket) pair in the token
    caches, or one a context bucket in the block cache, each at one shape,
    and traffic whose occupancy shrinks and regrows (short requests that
    finish and admit beside a long one) compiles nothing, adds no entry and
    leaves the long stream what it is alone."""
    engine = _engine(max_batch=4, warmup=True, devices=1, **family)
    buckets = set(engine._ctx_buckets())
    grid = {(k, pages) for k in engine.config.k_rungs() for pages in buckets}

    def programs():
        return [set(engine._decode_fns), set(engine._decode_fb_fns),
                set(engine._block_fns)]

    want = [set(), set(), buckets] if engine._block else [grid, grid, set()]
    assert programs() == want
    every = [*engine._decode_fns.values(), *engine._decode_fb_fns.values(),
             *engine._block_fns.values()]
    assert every and all(fn._cache_size() == 1 for fn in every)

    async def run():
        await engine.start()
        try:
            short = engine.tokenizer.encode("a" * 20)
            long = engine.tokenizer.encode("b" * 20)

            async def consume(prompt, n):
                return [t async for t in engine.generate(prompt, max_tokens=n)]

            alone = await consume(long, 60)
            beside = await asyncio.gather(
                consume(short, 3), consume(short, 3), consume(long, 60),
                consume(short, 3))
            late = await asyncio.gather(consume(short, 5), consume(short, 9))
            return alone, beside, late
        finally:
            await engine.stop()

    alone, beside, late = asyncio.run(run())
    assert len(alone) == 60 and beside[2] == alone and all(late)
    assert engine.compile_tracker.serving_compiles() == 0
    assert programs() == want
    assert all(fn._cache_size() == 1 for fn in every)


def test_chunk_rounds_batch_concurrent_long_prompts():
    """Long prompts (beyond every bucket) used to chunk-prefill alone at
    B=1; chunk ROUNDS batch rows of different requests at their own
    absolute offsets. Proof: 4 concurrent long prompts consume ~1 round
    per chunk, not 4, and outputs stay identical to solo runs."""
    def build():
        return _engine(max_batch=4, max_seq_len=256, num_pages=96,
                       prefill_buckets=(32,), prefill_max_batch=4)

    engine = build()
    # 80 tokens > largest bucket 32 -> chunked (3 chunks of <=32)
    prompt = engine.tokenizer.encode("z" * 79)
    assert len(prompt) == 80

    solo = _greedy(engine, prompt, max_tokens=5)

    engine2 = build()

    async def run_concurrent():
        await engine2.start()
        try:
            async def one():
                out = []
                async for tok in engine2.generate(prompt, max_tokens=5):
                    out.append(tok)
                return out
            return await asyncio.gather(*[one() for _ in range(4)])
        finally:
            await engine2.stop()

    results = asyncio.run(run_concurrent())
    assert all(r == solo for r in results), (solo, results)
    # 4 requests x 3 chunks: batched rounds need ~3-6 prefill dispatches
    # (arrival stagger can split the first round), never the serial 12
    assert engine2.stats.prefill_batches <= 8, engine2.stats.prefill_batches


def test_decode_overlap_does_not_corrupt_mid_chunk_kv():
    """THE interleaving hazard: a request decoding while another is
    mid-chunk-prefill. Decode dispatches cover every slot; mid-chunk
    slots have REAL pages mapped, so an unmasked inactive-row write
    (position 0) would silently overwrite the chunker's first prompt
    page. The chunker's output must equal its solo output even when
    decode steps run between its chunk rounds."""
    def build():
        return _engine(max_batch=2, max_seq_len=256, num_pages=96,
                       prefill_buckets=(16,), prefill_max_batch=1)

    solo_engine = build()
    long_prompt = solo_engine.tokenizer.encode("w" * 99)  # 100 tok, 7 chunks
    solo = _greedy(solo_engine, long_prompt, max_tokens=5)

    engine = build()

    async def run():
        await engine.start()
        try:
            short_prompt = engine.tokenizer.encode("s" * 10)

            async def consume(prompt, n):
                out = []
                async for tok in engine.generate(prompt, max_tokens=n):
                    out.append(tok)
                return out

            # the short request decodes first (stream until done) WHILE the
            # long prompt advances through its 7 chunk rounds
            short_task = asyncio.ensure_future(consume(short_prompt, 40))
            # let the short request get admitted and decoding
            await asyncio.sleep(0.15)
            long_out = await consume(long_prompt, 5)
            await short_task
            return long_out
        finally:
            await engine.stop()

    assert asyncio.run(run()) == solo


def test_oversized_prompt_behind_blocked_chunker_rejects_cleanly():
    """An over-long prompt queued behind a capacity-blocked chunker must
    reject with finish_reason=length — never become the admission head
    with bucket 0 (which would crash the dispatch thread)."""
    engine = _engine(max_batch=2, max_seq_len=64, num_pages=96,
                     prefill_buckets=(16,), prefill_max_batch=1)

    async def run():
        await engine.start()
        try:
            async def consume(prompt, n):
                out = []
                async for tok in engine.generate(prompt, max_tokens=n):
                    out.append(tok)
                return out

            # two chunked prompts: the second defers behind chunking capacity
            long_prompt = engine.tokenizer.encode("c" * 50)   # 51 tok, chunked
            oversized = list(range(70))                       # > max_seq_len-1
            t1 = asyncio.ensure_future(consume(long_prompt, 3))
            t2 = asyncio.ensure_future(consume(long_prompt, 3))
            await asyncio.sleep(0.05)
            # generous bound: this box is 1 vCPU and the suite may share it
            # with the TPU capture loop — 10 s flaked under that contention
            bad = await asyncio.wait_for(consume(oversized, 3), 30.0)
            assert bad == []                                  # length-rejected
            out1, out2 = await asyncio.gather(t1, t2)
            assert len(out1) == 3 and out1 == out2            # engine healthy
            return True
        finally:
            await engine.stop()

    assert asyncio.run(run())


# (the spec-decode x chunked-prefill losslessness test lives in
# test_real_checkpoint.py — random weights never ACCEPT a draft, so only
# a trained, repetitive model exercises the accepted-draft path)
