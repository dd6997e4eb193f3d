"""Token-loop fusion: K-step decode super-steps (ROADMAP item 1).

The fused scan's contract, in falsifiable form:

- exact greedy token parity serial (K=1) vs fused (K in {2, 8}),
  including max_tokens boundaries not divisible by K;
- sampled-mode parity between the serial-dispatch and overlapped
  pipelines at the SAME K (identical per-dispatch RNG consumption);
- a stop token sampled mid-super-step ends the stream exactly where the
  serial engine does — nothing past it emits, and the device's own
  valid/done masks froze the row (no post-EOS KV writes);
- host syncs per emitted token drop ~K-fold (stats.decode_dispatches);
- a pool replica killed mid-super-step requeues its in-flight requests
  as continuations with zero loss/duplication: only RETIRED tokens ride
  the continuation prompt, the unretired speculative tail is discarded;
- PageAllocator.pregrant_block grants a K-token super-step's pages in
  ONE call and keeps the block-table reconcile once-per-super-step.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcp_context_forge_tpu.tpu_local.engine import (EngineConfig, GenRequest,
                                                    TPUEngine)
from mcp_context_forge_tpu.tpu_local.kv import PageAllocator
from mcp_context_forge_tpu.tpu_local.pool import EnginePool
from mcp_context_forge_tpu.tpu_local.sampling import SamplingParams


def _config(**overrides):
    kwargs = dict(model="llama3-test", max_batch=4, max_seq_len=128,
                  page_size=16, num_pages=64, prefill_buckets=(16, 64),
                  dtype="float32", attn_impl="reference")
    kwargs.update(overrides)
    return EngineConfig(**kwargs)


def _gen_preloaded(engine, prompts, max_tokens, **kwargs):
    """Queue every request BEFORE the dispatch thread starts so admission
    grouping — and thus every dispatched shape — is deterministic across
    the engines being compared."""
    requests = [GenRequest(request_id=f"r{i}", prompt_ids=ids,
                           max_tokens=max_tokens, **kwargs)
                for i, ids in enumerate(prompts)]
    engine._pending.extend(requests)

    async def main():
        await engine.start()
        try:
            outs = []
            for request in requests:
                tokens = []
                while True:
                    token = await asyncio.wait_for(request.stream.get(),
                                                   timeout=120)
                    if token is None:
                        break
                    tokens.append(token)
                outs.append(tokens)
            return outs
        finally:
            await engine.stop()

    return asyncio.run(main())


def _gen_all(engine, prompts, max_tokens=12, **kwargs):
    async def main():
        await engine.start()
        try:
            async def one(ids):
                return [t async for t in engine.generate(
                    ids, max_tokens=max_tokens, **kwargs)]
            return await asyncio.gather(*[one(ids) for ids in prompts])
        finally:
            await engine.stop()
    return asyncio.run(main())


# ------------------------------------------------------------------- parity

def test_superstep_greedy_parity_and_sync_drop():
    """The acceptance gate: seeded greedy engines at K in {1, 2, 8} emit
    byte-identical streams on a max_tokens boundary (13) no K divides,
    while host syncs per token fall ~K-fold."""
    prompts_text = ["alpha bravo", "charlie", "delta echo foxtrot golf",
                    "hotel india juliet"]
    outs, dispatches = {}, {}
    for k in (1, 2, 8):
        # buckets (16, 32): the upper one has no half-length program (its
        # half is the bucket below), so the two longer prompts are admitted
        # in ONE dispatch and every row starts decoding within two
        # admissions; a lone short prompt's own dispatch (the file's (16,
        # 64)) would stagger the rows and the count of dispatches with them
        engine = TPUEngine(_config(superstep=k, prefill_buckets=(16, 32)))
        assert engine.half_lengths == {}
        engine._rng = jax.random.PRNGKey(1234)
        prompts = [engine.tokenizer.encode(t) for t in prompts_text]
        outs[k] = _gen_preloaded(engine, prompts, max_tokens=13)
        dispatches[k] = engine.stats.decode_dispatches
        assert engine.allocator.pages_in_use == 0
        assert all(len(stream) == 13 for stream in outs[k])
    assert outs[2] == outs[1]
    assert outs[8] == outs[1]
    # 12 post-prefill tokens per stream: K=8 retires them in 2 dispatches
    assert dispatches[8] * 4 <= dispatches[1], dispatches


def test_superstep_composes_with_overlap_sampled_parity():
    """At the same K the serial-dispatch and depth-2 overlapped pipelines
    consume RNG identically per dispatch, so even SAMPLED streams must
    match exactly — the fused block feeds the next dispatch on device."""
    outs = {}
    for overlap in (False, True):
        engine = TPUEngine(_config(superstep=8, decode_overlap=overlap,
                                   max_batch=2))
        engine._rng = jax.random.PRNGKey(7)
        ids = engine.tokenizer.encode("sampled superstep parity")
        outs[overlap] = _gen_all(engine, [ids], max_tokens=18,
                                 temperature=0.8, top_k=20)
        assert engine.allocator.pages_in_use == 0
        if overlap:
            assert engine.stats.overlap_steps > 0, \
                "pipeline never engaged at superstep granularity"
    assert outs[True] == outs[False]


def test_eos_mid_superstep_emits_nothing_past_stop():
    """A stop token sampled mid-block must end the stream at ITS first
    occurrence — the fused lookahead past it is discarded, pages free,
    and the serial engine's stream is reproduced exactly."""
    serial = TPUEngine(_config(superstep=1))
    ids = serial.tokenizer.encode("stop mid superstep")
    ref = _gen_all(serial, [ids], max_tokens=12)[0]
    assert len(ref) >= 4, "need a few tokens to pick a stop id from"
    idx = next(i for i in range(1, len(ref)) if ref[i] not in ref[:i])
    stop = ref[idx]

    for k in (1, 8):
        engine = TPUEngine(_config(superstep=k))
        out = _gen_all(engine,
                       [engine.tokenizer.encode("stop mid superstep")],
                       max_tokens=50, stop_ids=(stop,))[0]
        assert out == ref[:idx + 1], (k, out, ref[:idx + 1])
        assert engine.allocator.pages_in_use == 0
        assert engine._inflight is None


# ------------------------------------------------------- device-side masks

def test_device_masks_budget_and_stop_freeze():
    """The fused scan's own verdicts, unjitted (no kv donation): a row's
    valid mask cuts at its budget, an inactive row never validates, and
    a stop id in the device table freezes the row mid-block with done
    set — the no-host-round-trip stop condition the tentpole adds."""
    engine = TPUEngine(_config(superstep=4, max_batch=2))
    assert engine.allocator.allocate_slot(0, 8)
    engine._sync_tables()
    B = 2
    args = dict(
        tokens=jnp.array([3, 0], jnp.int32),
        positions=jnp.array([4, 0], jnp.int32),
        slot_ids=jnp.arange(B, dtype=jnp.int32),
        seq_lens=jnp.array([5, 0], jnp.int32),   # row 1 inactive
        sampling=SamplingParams(jnp.zeros((B,), jnp.float32),
                                jnp.zeros((B,), jnp.int32),
                                jnp.ones((B,), jnp.float32)),
        key=jax.random.PRNGKey(0),
        ctx_pages=4,
    )
    no_stops = jnp.full((B, TPUEngine._STOP_TBL_WIDTH), -1, jnp.int32)

    # budget freeze: row 0 may emit 2 of the 4 fused tokens
    (toks, valid, done), _ = engine._decode_and_sample(
        engine.params, engine.kv, budgets=jnp.array([2, 0], jnp.int32),
        stop_tbl=no_stops, **args)
    assert toks.shape == (4, B) and valid.shape == (4, B)
    assert list(np.asarray(valid)[:, 0]) == [True, True, False, False]
    assert not np.asarray(valid)[:, 1].any()     # inactive row: no tokens
    assert not np.asarray(done).any()            # budget is not done

    # stop freeze: greedy is deterministic, so rerunning with the first
    # sampled token in the stop table must freeze the row after it
    first = int(np.asarray(toks)[0, 0])
    stop_tbl = no_stops.at[0, 0].set(first)
    (toks2, valid2, done2), _ = engine._decode_and_sample(
        engine.params, engine.kv, budgets=jnp.array([4, 0], jnp.int32),
        stop_tbl=stop_tbl, **args)
    assert int(np.asarray(toks2)[0, 0]) == first
    assert list(np.asarray(valid2)[:, 0]) == [True, False, False, False]
    assert bool(np.asarray(done2)[0])
    engine.allocator.free_slot(0)


def test_step_ring_rows_carry_superstep_accounting():
    """/admin/engine/steps truthfulness at K>1: decode rows report the
    fused K, the device-frozen row count, and a tokens count that can
    exceed one per dispatch."""
    engine = TPUEngine(_config(superstep=8, max_batch=2))
    ids = engine.tokenizer.encode("ring accounting")
    _gen_all(engine, [ids], max_tokens=16)
    rows = [s for s in engine.recent_steps() if s["kind"] == "decode"]
    assert rows
    assert all(r["superstep"] == 8 for r in rows)
    assert all(r["frozen"] is not None for r in rows)
    assert any(r["tokens"] > 1 for r in rows), \
        "no dispatch retired more than one token"
    prefills = [s for s in engine.recent_steps() if s["kind"] == "prefill"]
    assert all(p["superstep"] is None for p in prefills)


# ------------------------------------------------------------ pool requeue

def test_pool_kill_mid_superstep_requeues_as_continuation():
    """Chaos at K=8: a replica dies mid-super-step. In-flight requests
    requeue onto the survivor as continuations built from RETIRED tokens
    only — the dead dispatch's unretired tail is discarded — and merged
    streams stay byte-identical to an uninterrupted run."""
    prompts = [f"superstep chaos prompt {i} extra words" for i in range(4)]

    async def main():
        ref_engine = TPUEngine(_config(superstep=8))
        await ref_engine.start()
        refs = []
        try:
            for p in prompts:
                ids = ref_engine.tokenizer.encode(p)
                refs.append([t async for t in ref_engine.generate(
                    ids, max_tokens=24)])
        finally:
            await ref_engine.stop()

        pool = EnginePool(_config(superstep=8), replicas=2,
                          health_interval_s=0.05, heartbeat_timeout_s=10.0)
        victim = pool.replicas[1].engine
        calls = {"n": 0}
        for name in ("_decode_fn", "_decode_fb_fn"):
            real = getattr(victim, name)

            def make(real):
                def exploding(ctx_pages, batch=None):
                    fn = real(ctx_pages, batch)

                    def wrapper(*args, **kwargs):
                        calls["n"] += 1
                        if calls["n"] >= 2:
                            raise RuntimeError("injected device fault")
                        return fn(*args, **kwargs)
                    return wrapper
                return exploding
            setattr(victim, name, make(real))
        await pool.start()
        try:
            async def gen(p):
                ids = pool.tokenizer.encode(p)
                return [t async for t in pool.generate(ids, max_tokens=24)]

            outs = await asyncio.gather(*[gen(p) for p in prompts])
        finally:
            await pool.stop()
        assert [list(o) for o in outs] == refs  # zero loss, zero dupes
        assert pool.requeues >= 1
        assert pool.replicas[1].state == "dead"

    asyncio.run(main())


# -------------------------------------------------- allocator pre-granting

def test_pregrant_block_grants_a_superstep_in_one_call():
    alloc = PageAllocator(num_pages=32, page_size=16, max_slots=4,
                          max_pages_per_slot=8)
    assert alloc.allocate_slot(0, 16)      # 1 page, capacity 16
    alloc.tables()
    # n_ctx=17 (input token at position 16), K=8: tokens land at
    # positions 16..23, the last one's KV defers to the next dispatch —
    # capacity must cover 24 tokens = 2 pages
    assert alloc.pregrant_block(0, 17, 8) == 8
    assert alloc.slot_pages(0) == 2
    assert alloc.dirty                      # new page -> one reconcile
    alloc.tables()
    # the next super-step fits the already-granted pages: full budget,
    # NO dirt — steady-state decode uploads nothing
    assert alloc.pregrant_block(0, 25, 8) == 8
    assert not alloc.dirty
    assert alloc.pregrant_block(0, 33, 0) == 0   # k=0: nothing to grant


def test_pregrant_block_partial_budget_on_dry_pool():
    alloc = PageAllocator(num_pages=4, page_size=16, max_slots=2,
                          max_pages_per_slot=8)   # 3 usable pages
    assert alloc.allocate_slot(0, 16)
    assert alloc.allocate_slot(1, 16)
    # slot 0 wants 8 tokens past position 31 -> pages for 39 tokens
    # (3 pages), but only ONE page is free: partial growth sticks and
    # the budget truncates to the 1 token the granted capacity (32)
    # covers past the input position — the serial engine's mid-stream
    # truncation point, reproduced per super-step
    assert alloc.pregrant_block(0, 32, 8) == 1
    assert alloc.slot_pages(0) == 2
    # pool is now dry: the same ask grants nothing more
    assert alloc.pregrant_block(0, 33, 8) == 0
    assert alloc.pregrant_block(1, 32, 8) == 0


def test_pregrant_block_respects_per_slot_cap():
    alloc = PageAllocator(num_pages=32, page_size=16, max_slots=2,
                          max_pages_per_slot=2)
    assert alloc.allocate_slot(0, 16)
    # per-slot cap 2 pages = 32 tokens: an 8-token block at the edge
    # gets only what the cap leaves
    assert alloc.pregrant_block(0, 28, 8) == 5
    assert alloc.pregrant_block(0, 33, 8) == 0


# ---------------------------------------------------------------- config

def test_superstep_config_wiring_and_validation():
    from mcp_context_forge_tpu.config import load_settings

    settings = load_settings(
        env={"MCPFORGE_TPU_LOCAL_SUPERSTEP": "8"}, env_file=None)
    cfg = EngineConfig.from_settings(settings)
    assert cfg.superstep == 8 and cfg.k_rungs() == (8,)
    with pytest.raises(ValueError, match="superstep must be"):
        TPUEngine(_config(superstep=0))
    with pytest.raises(ValueError, match="mutually"):
        TPUEngine(_config(superstep=8, spec_decode=True))
