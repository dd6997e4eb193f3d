"""Speculative decoding (prompt-lookup drafting + chunk verify).

Losslessness is the whole contract: greedy output through the [B,K] verify
step must be TOKEN-IDENTICAL to the plain decode loop — drafts only change
how many dispatches it takes, never what comes out."""

import asyncio

import pytest

from mcp_context_forge_tpu.tpu_local.engine import (EngineConfig, GenRequest,
                                                    TPUEngine)


def _engine(**over) -> TPUEngine:
    kwargs = dict(model="llama3-test", max_batch=2, max_seq_len=128,
                  page_size=16, num_pages=64, prefill_buckets=(16, 64),
                  dtype="float32", attn_impl="reference")
    kwargs.update(over)
    return TPUEngine(EngineConfig(**kwargs))


async def _gen(engine, ids, n=16, **kw):
    return [t async for t in engine.generate(ids, max_tokens=n, **kw)]


def test_spec_decode_matches_plain_greedy_exactly():
    async def run():
        spec = _engine(spec_decode=True, spec_k=4)
        plain = _engine()
        prompts = [
            spec.tokenizer.encode("abc abc abc abc abc abc"),  # repetitive
            spec.tokenizer.encode("the quick brown fox"),      # not
            list(range(5, 45)),                                # 40 tokens
        ]
        for engine in (spec, plain):
            await engine.start()
        try:
            for ids in prompts:
                out_spec = await _gen(spec, ids, n=16)
                out_plain = await _gen(plain, ids, n=16)
                assert out_spec == out_plain, (ids, out_spec, out_plain)
            assert spec.stats.spec_steps >= 1  # the verify path actually ran
        finally:
            for engine in (spec, plain):
                await engine.stop()

    asyncio.run(run())


def test_spec_decode_accepts_drafts_on_cyclic_output():
    """Force a repetitive context: accepted drafts emit >1 token/step."""
    async def run():
        engine = _engine(spec_decode=True, spec_k=4)
        # context whose trailing 2-gram repeats -> drafts always available
        ids = [7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8]
        await engine.start()
        try:
            out = await _gen(engine, ids, n=12)
            assert len(out) >= 4
            steps = engine.stats.spec_steps
            # lossless spec may or may not accept with random weights, but
            # dispatches never exceed tokens emitted
            assert steps <= len(out) + 1
            if engine.stats.spec_tokens:
                assert steps < len(out)
        finally:
            await engine.stop()

    asyncio.run(run())


def test_spec_decode_sampled_rows_ride_at_width_one():
    """temperature>0 rows must get exactly one true-distribution token per
    step (no drafts) and still finish correctly alongside greedy rows."""
    async def run():
        engine = _engine(spec_decode=True, spec_k=4)
        await engine.start()
        try:
            g, s = await asyncio.gather(
                _gen(engine, [3, 4, 5, 3, 4, 5, 3, 4], n=8),
                _gen(engine, [10, 11, 12, 13], n=8, temperature=0.8,
                     top_k=20),
            )
            assert 1 <= len(g) <= 8 and 1 <= len(s) <= 8
        finally:
            await engine.stop()

    asyncio.run(run())


def test_spec_decode_respects_max_tokens_and_capacity():
    async def run():
        engine = _engine(spec_decode=True, spec_k=4, max_seq_len=32,
                         prefill_buckets=(16,), num_pages=8, page_size=16)
        await engine.start()
        try:
            out = await _gen(engine, [5, 5, 5, 5, 5, 5], n=30)
            # capacity: 32-position table minus 6 prompt, +1 because the
            # final emitted token is never written to KV
            assert 1 <= len(out) <= 27
        finally:
            await engine.stop()

    asyncio.run(run())


def test_spec_config_validation():
    with pytest.raises(ValueError):
        _engine(spec_decode=True, superstep=2)
    with pytest.raises(ValueError):
        _engine(spec_decode=True, spec_k=1)


def test_draft_lookup_finds_recent_ngram():
    engine = _engine(spec_decode=True, spec_k=4, spec_ngram=2)
    request = GenRequest(request_id="r",
                         prompt_ids=[1, 2, 3, 9, 9, 1, 2])
    # trailing (1,2) matched at start -> continuation [3, 9, 9]
    assert engine._draft_tokens(request, 3) == [3, 9, 9]
    request2 = GenRequest(request_id="r2", prompt_ids=[4, 5, 6, 7])
    assert engine._draft_tokens(request2, 3) == []


def test_accept_loop_emits_confirmed_drafts_deterministically():
    """Unit-test the accept/emit logic with a stubbed verify step: the
    model's 'sample' at position j is defined as chunk[j]+1, so exactly
    the drafts matching that rule are accepted — independent of weights."""
    import jax.numpy as jnp
    import numpy as np

    engine = _engine(spec_decode=True, spec_k=4, spec_ngram=2)
    # context [5,6,7,5,6]: trailing (5,6) matches at 0 -> draft [7,5,6]
    request = GenRequest(request_id="r", prompt_ids=[5, 6, 7, 5],
                         max_tokens=8, generated=[6])
    assert engine.allocator.allocate_slot(0, 12)
    request.slot = 0
    engine._running[0] = request

    captured = {}

    def fake_verify(params, kv, packed, base_key):
        # the verify program's one packed call, sliced by its layout
        tokens = engine._verify_call.unpack(packed)["tokens"]
        captured["tokens"] = np.asarray(tokens)
        return jnp.asarray(np.asarray(tokens) + 1), kv

    engine._verify_fn = lambda ctx_pages: fake_verify
    engine._spec_step_all()

    # chunk = [t0=6, d1=7, d2=5, d3=6]; s = [7, 8, 6, 7]
    assert captured["tokens"][0].tolist() == [6, 7, 5, 6]
    # d1=7 == s0=7 -> accept, emit s1=8; d2=5 != s1=8 -> stop
    assert request.generated == [6, 7, 8]
    assert engine.stats.spec_tokens == 1
    engine._running.clear()
    engine.allocator.free_slot(0)
