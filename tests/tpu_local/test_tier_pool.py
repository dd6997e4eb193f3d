"""Pool-global tiered prefix cache: fetch-on-miss across replicas,
index-driven router affinity, and failover through the tier path.

The pool contract the tiers add (ISSUE 12 / docs/kv_tiering.md):

- ONE spill store + ONE prefix index serve every replica: a prefix
  prefilled (then evicted) on replica A restores into replica B's HBM
  inside B's own admission — byte-identical continuations;
- the router treats a pool-index hit as affinity even when the probed
  replica's local cache is empty: a prefix resident only on replica 1's
  HBM steers the request to replica 1 (the pre-tier router scored it
  zero and round-robined);
- killing the serving replica mid-generation requeues the continuation
  onto the survivor, which restores the shared prefix from the tier
  store — stream parity vs an uninterrupted engine holds.
"""

import asyncio

from mcp_context_forge_tpu.tpu_local.engine import (EngineConfig, GenRequest,
                                                    TPUEngine)
from mcp_context_forge_tpu.tpu_local.kv.prefix_index import chain_hashes
from mcp_context_forge_tpu.tpu_local.pool import EnginePool

PS = 16


def _config(**overrides):
    kwargs = dict(model="llama3-test", max_batch=4, max_seq_len=128,
                  page_size=PS, num_pages=64, prefill_buckets=(16, 64),
                  dtype="float32", attn_impl="reference",
                  prefix_cache=True, prefix_tiers=True,
                  tier_host_bytes=1 << 20, tier_disk_bytes=1 << 20)
    kwargs.update(overrides)
    return EngineConfig(**kwargs)


def _pool(replicas=2, **overrides):
    health = overrides.pop("health_interval_s", 0.05)
    return EnginePool(_config(**overrides), replicas=replicas,
                      health_interval_s=health)


async def _engine_gen(engine, ids, n=6):
    return [t async for t in engine.generate(ids, max_tokens=n)]


def test_pool_shares_one_store_and_index():
    pool = _pool(replicas=2)
    assert pool.tier_store is not None and pool.prefix_index is not None
    clients = [r.engine._tier_client for r in pool.replicas]
    assert all(c is not None for c in clients)
    assert clients[0].store is clients[1].store is pool.tier_store
    assert clients[0].index is clients[1].index is pool.prefix_index
    status = pool.status()
    assert status["prefix_tiers"]["enabled"] is True
    assert "host_pages" in status["prefix_tiers"]["store"]
    assert "keys_hbm" in status["prefix_tiers"]["index"]


def test_fetch_on_miss_restores_from_any_replica():
    """Replica 0 prefills + spills a template (pressure); replica 1 — a
    cold replica — serves the same template by restoring from the SHARED
    store into its own HBM, with exact greedy parity vs a single
    uninterrupted engine."""
    tmpl_a = list(range(3, 36))      # 2 full pages + tail
    tmpl_b = list(range(200, 233))
    tmpl_c = list(range(400, 433))

    async def main():
        # replica pools small enough that three templates cannot stay
        # resident: serving C evicts (spills) A on replica 0
        pool = _pool(replicas=2, num_pages=5)
        ref = TPUEngine(_config(num_pages=5, prefix_tiers=False))
        await pool.start()
        await ref.start()
        try:
            r0, r1 = pool.replicas[0].engine, pool.replicas[1].engine
            for tmpl in (tmpl_a, tmpl_b, tmpl_c):
                await _engine_gen(r0, tmpl + [40])
            assert pool.tier_store.stats()["spilled"] >= 1
            # replica 1 never saw template A — its only copy reachable
            # from r1 is the spilled one
            out_pool = await _engine_gen(r1, tmpl_a + [41])
            out_ref = [await _engine_gen(ref, t + [40])
                       for t in (tmpl_a, tmpl_b, tmpl_c)]
            out_ref_a = await _engine_gen(ref, tmpl_a + [41])
            assert out_pool == out_ref_a
            stats = r1.tier_stats()
            assert stats["restores"] >= 1
            assert (r1.allocator.tier_hit_tokens["host"]
                    + r1.allocator.tier_hit_tokens["disk"]) >= 2 * PS
        finally:
            await pool.stop()
            await ref.stop()

    asyncio.run(main())


def test_router_scores_pool_index_hit_as_affinity():
    """Satellite fix: a prefix resident ONLY on replica 1's HBM must
    steer routing to replica 1 — both when the residency is visible to
    replica 1's own probe (real seeded traffic) and when ONLY the pool
    index knows it (the index-beats-local fold, counted by
    ``index_hits``)."""
    template = list(range(3, 36))

    async def main():
        pool = _pool(replicas=2)
        await pool.start()
        try:
            r1 = pool.replicas[1].engine
            # seed the template on replica 1 ONLY (direct engine call:
            # registers the prefix + publishes HBM residency)
            await _engine_gen(r1, template + [40])
            assert pool.prefix_index.stats()["keys_hbm"] >= 2
            routed = pool.router.routed
            # route() itself (not submit) so occupancy can't mask the
            # affinity signal
            choice, hit = pool.router.route(
                [r for r in pool.replicas], template + [41])
            assert hit is True
            assert choice is pool.replicas[1]
            assert pool.router.routed == routed + 1
            assert pool.router.affinity_hits >= 1

            # index-beats-local: a chain NO allocator can see locally
            # (published straight into the index for replica 1 — the
            # shape a capacity-capped probe leaves behind) still steers
            # to replica 1 and counts as an index-driven hit
            ghost = [9000 + i for i in range(33)] + [41]
            for key_hash in chain_hashes(ghost, PS):
                pool.prefix_index.publish_hbm(key_hash, "1")
            choice, hit = pool.router.route(
                [r for r in pool.replicas], ghost)
            assert hit is True
            assert choice is pool.replicas[1]
            assert pool.router.index_hits >= 1
            assert "index_hits" in pool.router.counters()
        finally:
            await pool.stop()

    asyncio.run(main())


def test_shared_tier_hit_is_affinity_neutral_but_counts():
    """A chain resident only in the SHARED tiers scores as affinity for
    every replica equally: any replica can restore it, so placement
    falls through to least-outstanding — but the hit is real."""
    tmpl_a = list(range(3, 36))
    tmpl_b = list(range(200, 233))
    tmpl_c = list(range(400, 433))

    async def main():
        pool = _pool(replicas=2, num_pages=5)
        await pool.start()
        try:
            r0 = pool.replicas[0].engine
            for tmpl in (tmpl_a, tmpl_b, tmpl_c):
                await _engine_gen(r0, tmpl + [40])
            assert pool.tier_store.stats()["spilled"] >= 1
            # template A's chain now lives (at least partly) in the
            # shared store; both replicas must see an affinity-positive
            # score and the router must not crash on the tier-only chain
            choice, hit = pool.router.route(
                [r for r in pool.replicas], tmpl_a + [60])
            assert hit is True
            assert choice is not None
        finally:
            await pool.stop()

    asyncio.run(main())


def test_kill_mid_generation_requeues_through_tier_restore():
    """Chaos x tiers: the replica serving a tier-restored prefix is
    killed mid-decode; the survivor finishes the continuation —
    restoring the shared prefix itself at re-admission — and the merged
    stream is byte-identical to an uninterrupted engine's (zero loss,
    zero duplicates)."""
    tmpl_a = list(range(3, 36))
    tmpl_b = list(range(200, 233))
    tmpl_c = list(range(400, 433))
    warm = [t + [40] for t in (tmpl_a, tmpl_b, tmpl_c)]
    victim_prompt = tmpl_a + [41]

    async def main():
        ref = TPUEngine(_config(num_pages=5, prefix_tiers=False))
        await ref.start()
        try:
            for p in warm:
                await _engine_gen(ref, p)
            ref_out = await _engine_gen(ref, victim_prompt, n=16)
        finally:
            await ref.stop()

        # resident-precision spill: the float32 pool's default
        # (tier_spill_quant="int8") quantizes pages on spill, so a
        # restored prefix is close but not bit-equal and 16 greedy tokens
        # may drift (test_kv_tiering pins that drift); byte identity
        # through the requeue is promised for the lossless mode only
        pool = _pool(replicas=2, num_pages=5, tier_spill_quant="")
        await pool.start()
        try:
            r0 = pool.replicas[0].engine
            for p in warm:
                await _engine_gen(r0, p)
            assert pool.tier_store.stats()["spilled"] >= 1
            request = GenRequest(request_id="victim",
                                 prompt_ids=list(victim_prompt),
                                 max_tokens=16)
            await pool.submit(request)
            out = []
            for _ in range(2):   # let the serving replica emit a little
                token = await asyncio.wait_for(request.stream.get(), 120)
                assert token is not None
                out.append(token)
            serving = next(r for r in pool.replicas
                           if request.request_id in r.outstanding)
            pool.fail_replica(serving, reason="chaos: kill mid tier serve")
            while True:
                token = await asyncio.wait_for(request.stream.get(), 120)
                if token is None:
                    break
                out.append(token)
            assert out == ref_out            # zero loss, zero duplicates
            assert pool.requeues >= 1
            survivor = [r for r in pool.replicas if r is not serving][0]
            assert survivor.state == "ready"
            assert serving.state == "dead"
        finally:
            await pool.stop()

    asyncio.run(main())


def test_spill_on_drain_preserves_prefix_corpus_across_reload():
    """ISSUE-14 satellite (ROADMAP item 3's remaining half): drain →
    reload SPILLS the replica's ref==0 resident prefix pages through
    the TierClient path before the HBM pool is torn down, so the
    rebuilt replica serves the template by fetch-on-miss — pinned by a
    byte-identical continuation vs an uninterrupted engine (lossless
    resident-precision spills) instead of losing the prefix corpus."""
    template = list(range(3, 36))   # 2 full pages + tail

    async def main():
        ref = TPUEngine(_config(prefix_tiers=False))
        await ref.start()
        try:
            await _engine_gen(ref, template + [40])
            ref_out = await _engine_gen(ref, template + [41], n=12)
        finally:
            await ref.stop()

        # tier_spill_quant="" = lossless spill container: the restored
        # pages are bit-identical, so the continuation must be too
        pool = _pool(replicas=1, tier_spill_quant="")
        await pool.start()
        try:
            r0 = pool.replicas[0].engine
            await _engine_gen(r0, template + [40])
            # no allocation pressure: nothing spilled yet — the corpus
            # is exactly what a naive reload would LOSE
            spilled0 = pool.tier_store.stats()["spilled"]
            assert r0.allocator.cached_pages >= 2
            await pool.reload("0")
            assert pool.tier_store.stats()["spilled"] > spilled0, \
                "reload must spill resident prefix pages before teardown"
            engine = pool.replicas[0].engine
            assert engine is not r0                  # rebuilt object
            out = await _engine_gen(engine, template + [41], n=12)
            assert out == ref_out                    # byte-identical
            # and the hit really came through the tier restore path
            assert (engine.allocator.tier_hit_tokens["host"]
                    + engine.allocator.tier_hit_tokens["disk"]) >= 2 * PS
        finally:
            await pool.stop()

    asyncio.run(main())


def test_reload_drops_stale_hbm_index_entries():
    """A reloaded (rebuilt) replica's HBM pages are gone: the index must
    forget its entries at rebuild so the router can't chase ghosts; the
    spilled copies (content-addressed) survive and still serve."""
    template = list(range(3, 36))

    async def main():
        pool = _pool(replicas=2)
        await pool.start()
        try:
            r1 = pool.replicas[1].engine
            await _engine_gen(r1, template + [40])
            assert pool.prefix_index.stats()["keys_hbm"] >= 2
            await pool.reload("1")
            # replica 1's rebuilt engine re-wired onto the shared plane
            c = pool.replicas[1].engine._tier_client
            assert c is not None and c.store is pool.tier_store
            chain = pool.prefix_index.chain_locations(template + [41], PS)
            assert pool.prefix_index.reachable_tokens(chain, "1", PS) == 0 \
                or all("1" not in hbm for hbm, _ in chain)
        finally:
            await pool.stop()

    asyncio.run(main())
