"""Tiered prefix/KV cache: spill-on-evict, fetch-on-miss, tier parity.

The contract (ISSUE 12 / docs/kv_tiering.md), in falsifiable form:

- an evicted prefix page SPILLS (int8 bytes + scales) instead of
  dropping, and a later match RESTORES it into HBM with the greedy
  continuation byte-identical to a tier-less run — for bf16/f32
  resident pools (quantize-on-spill) AND int8 resident pools (verbatim
  bytes, bit-exact round trip);
- the disk tier (async write-behind) round-trips the same way and
  re-onlines on match;
- eviction NEVER touches a pinned in-flight span (refcount > 0);
- a chain-hash collision degrades to a miss — wrong pages are never
  served — and the poisoned entry is dropped so admission cannot
  livelock re-probing it;
- the hit accounting conserves: tier_hit_tokens sums to
  prefix_hit_tokens at the same consume site the tenant ledger meters.
"""

import asyncio
import time

import numpy as np
import pytest

from mcp_context_forge_tpu.tpu_local.engine import EngineConfig, TPUEngine
from mcp_context_forge_tpu.tpu_local.kv.paged_cache import PageAllocator
from mcp_context_forge_tpu.tpu_local.kv.prefix_index import (
    ROOT_HASH, PrefixIndex, chain_hashes)
from mcp_context_forge_tpu.tpu_local.kv.tiers import (SpilledPage,
                                                      TieredPageStore)

PS = 16


def _payload(chunk, parent=ROOT_HASH, fill=1):
    shape = (2, 4, 2, 8)  # [L, page, KV, hd]
    return SpilledPage(chunk=tuple(chunk), parent=parent,
                       k=np.full(shape, fill, dtype=np.int8),
                       v=np.full(shape, fill, dtype=np.int8),
                       k_scales=np.ones((2, 2), dtype=np.float32),
                       v_scales=np.ones((2, 2), dtype=np.float32))


# ------------------------------------------------------------------- store

def test_store_put_get_verifies_identity_and_counts():
    store = TieredPageStore(host_bytes=1 << 20, disk_bytes=0, pin=False)
    try:
        chunk = tuple(range(4))
        h = chain_hashes(list(chunk) + [99], 4)[0]
        store.put(h, _payload(chunk))
        assert store.probe(h)
        hit = store.get(h, ROOT_HASH, chunk)
        assert hit is not None and hit[1] == "host"
        # wrong chunk under the same key = collision -> miss, entry DROPPED
        # (a surviving poisoned entry would livelock admission: probe
        # promises a hist match_prefix can never restore)
        store.put(h, _payload(chunk))  # refresh after the get above
        assert store.get(h, ROOT_HASH, (9, 9, 9, 9)) is None
        assert store.collisions == 1
        assert not store.probe(h)
    finally:
        store.close()


def test_store_disk_writeback_and_reonline():
    """T1 overflow hands off to the write-behind worker; a disk hit
    re-onlines into T1 and the payload round-trips exactly."""
    one = _payload((0,) * 4).nbytes
    store = TieredPageStore(host_bytes=one + 1, disk_bytes=1 << 20,
                            pin=False)
    try:
        chunks = [tuple(range(i, i + 4)) for i in range(0, 12, 4)]
        hashes = [chain_hashes(list(c) + [99], 4)[0] for c in chunks]
        for h, c in zip(hashes, chunks):
            store.put(h, _payload(c, fill=c[0] + 1))
        deadline = time.monotonic() + 10
        while store.stats()["disk_pages"] < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        stats = store.stats()
        assert stats["disk_pages"] >= 2, stats
        assert stats["disk_writes"] >= 2
        # the displaced (oldest) entries serve from disk, verified
        hit = store.get(hashes[0], ROOT_HASH, chunks[0])
        assert hit is not None and hit[1] == "disk"
        payload = hit[0]
        assert payload.chunk == chunks[0]
        assert int(payload.k[0, 0, 0, 0]) == chunks[0][0] + 1
        assert store.stats()["host_pages"] >= 2  # re-onlined into T1
    finally:
        store.close()


def test_store_disk_budget_drops_oldest():
    one = _payload((0,) * 4).nbytes
    store = TieredPageStore(host_bytes=one + 1, disk_bytes=2 * one + 1,
                            pin=False)
    try:
        chunks = [tuple(range(i, i + 4)) for i in range(0, 24, 4)]
        hashes = [chain_hashes(list(c) + [99], 4)[0] for c in chunks]
        for h, c in zip(hashes, chunks):
            store.put(h, _payload(c))
        deadline = time.monotonic() + 10
        while (store.stats()["host_pages"] + store.stats()["disk_pages"]
               > 4 and time.monotonic() < deadline):
            time.sleep(0.01)
        stats = store.stats()
        assert stats["disk_bytes"] <= 2 * one + 1
        assert stats["dropped"] >= 1  # past the last tier: truly gone
    finally:
        store.close()


# --------------------------------------------------------------- allocator

class _FakeTiers:
    """TierClient stand-in recording spills; probe/restore are misses."""

    active = True

    def __init__(self):
        self.spilled: list[int] = []

    def probe(self, key_hash):
        return False

    def spill(self, key_hash, parent, chunk, page):
        self.spilled.append(page)
        return True

    def restore(self, key_hash, parent, chunk, page):
        return None

    def publish_hbm(self, key_hash):
        pass

    def unpublish_hbm(self, key_hash):
        pass


def test_eviction_under_pressure_never_drops_pinned_inflight_span():
    """Pages referenced by in-flight spans (pin counts) are never
    eviction candidates: pressure fails the allocation instead, and the
    only pages that spill are ref==0 residents."""
    tiers = _FakeTiers()
    alloc = PageAllocator(num_pages=8, page_size=4, max_slots=4,
                          max_pages_per_slot=8, tiers=tiers)  # 7 usable
    prompt = list(range(12))
    assert alloc.allocate_slot(0, 13)                  # 4 pages, pinned
    alloc.register_prefix(0, prompt)                   # 3 registered
    hist, shared = alloc.match_prefix(prompt + [50])
    assert hist == 12
    assert alloc.allocate_slot(1, 13, prefix_pages=shared)  # shares 3 +1
    pinned = set(alloc._slots[0]) | set(alloc._slots[1])
    # pool: 7 usable, 5 distinct pages held, 2 free, nothing evictable
    assert not alloc.allocate_slot(2, 3 * 4)           # needs 3 > 2 free
    assert tiers.spilled == []                         # nothing stolen
    assert set(alloc._slots[0]) | set(alloc._slots[1]) == pinned
    # free slot 1: its private page frees, shared pages stay pinned by 0
    alloc.free_slot(1)
    assert not alloc.allocate_slot(2, 4 * 4)           # 4 > 3 free
    assert tiers.spilled == []
    # free slot 0 too: registered pages become ref==0 residents — ONLY
    # NOW may pressure reclaim them, and each reclaim spills
    alloc.free_slot(0)
    assert alloc.allocate_slot(2, 6 * 4)
    assert len(tiers.spilled) >= 2


def test_tier_hits_conserve_against_prefix_hit_tokens():
    """The per-tier split counts at the same consume site as
    prefix_hit_tokens: their sums must always agree (the tenant ledger's
    cache_hit conservation rides this)."""
    alloc = PageAllocator(num_pages=16, page_size=4, max_slots=4,
                          max_pages_per_slot=8)
    prompt = list(range(9))
    assert alloc.allocate_slot(0, 9)
    alloc.register_prefix(0, prompt)
    hist, pages = alloc.match_prefix(prompt)
    assert alloc.allocate_slot(1, 9, prefix_pages=pages)
    assert sum(alloc.tier_hit_tokens.values()) == alloc.prefix_hit_tokens
    assert alloc.tier_hit_tokens["hbm"] == alloc.prefix_hit_tokens


# ------------------------------------------------------------------ engine

def _engine(tiers: bool, *, num_pages=5, kv_quant="", prefix_cache=True,
            host_bytes=1 << 20, disk_bytes=1 << 20, disk_dir="",
            spill_quant="", dtype="float32"):
    # spill_quant="" (resident-precision spill) is the LOSSLESS mode the
    # byte-identical gates run under; the "int8" default's bounded drift
    # has its own test below
    return TPUEngine(EngineConfig(
        model="llama3-test", max_batch=2, max_seq_len=128, page_size=PS,
        num_pages=num_pages, prefill_buckets=(16, 64), dtype=dtype,
        attn_impl="reference", prefix_cache=prefix_cache,
        prefix_tiers=tiers, tier_host_bytes=host_bytes,
        tier_disk_bytes=disk_bytes, tier_disk_dir=disk_dir,
        kv_quant=kv_quant, tier_spill_quant=spill_quant))


@pytest.mark.parametrize("kv_quant,dtype", [("int8", "float32"),
                                            ("", "bfloat16")])
def test_tier_page_payload_is_token_major_and_round_trips(kv_quant, dtype):
    """The page payload that leaves the device is ``[L, page, KV, hd]``
    (what kv/tiers.py, the fabric and anything already on disk hold), and
    read -> write -> read returns the same bytes (int8 pool: verbatim
    values and scales) or values (bf16 pool, resident-precision spill)."""
    import jax.numpy as jnp

    engine = _engine(True, kv_quant=kv_quant, dtype=dtype)
    cfg = engine.model_config
    shape = (cfg.n_layers, PS, cfg.n_kv_heads, cfg.head_dim)
    rng = np.random.default_rng(7)
    pool_dtype = engine.kv.k_pages.dtype
    if kv_quant == "int8":
        pages = [rng.integers(-127, 128, shape).astype(np.int8)
                 for _ in range(2)]
        scales = [rng.uniform(0.01, 0.02, (cfg.n_layers, cfg.n_kv_heads))
                  .astype(np.float32) for _ in range(2)]
        engine.kv = engine.kv._replace(
            k_scales=engine.kv.k_scales.at[:, 1].set(scales[0]),
            v_scales=engine.kv.v_scales.at[:, 1].set(scales[1]))
    else:
        pages = [np.asarray(jnp.asarray(rng.standard_normal(shape),
                                        pool_dtype).astype(jnp.float32))
                 for _ in range(2)]
    # page 1 of the pool holds token t, head h of layer l at [l, 1, t, h]
    engine.kv = engine.kv._replace(
        k_pages=engine.kv.k_pages.at[:, 1].set(jnp.asarray(pages[0], pool_dtype)),
        v_pages=engine.kv.v_pages.at[:, 1].set(jnp.asarray(pages[1], pool_dtype)))
    first = engine._read_page_payload(1)
    assert first.k.shape == first.v.shape == shape
    np.testing.assert_array_equal(first.k, pages[0])
    np.testing.assert_array_equal(first.v, pages[1])
    if kv_quant == "int8":
        assert first.k.dtype == np.int8
        np.testing.assert_array_equal(first.k_scales, scales[0])
        np.testing.assert_array_equal(first.v_scales, scales[1])
    engine._upload_page(3, first)
    again = engine._read_page_payload(3)
    for name in ("k", "v", "k_scales", "v_scales"):
        a, b = getattr(first, name), getattr(again, name)
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


async def _gen(engine, ids, n=6):
    return [t async for t in engine.generate(ids, max_tokens=n)]


def _pressure_prompts(n_templates: int = 2):
    """>1-page templates over a pool too small to keep them all cached:
    round-robin reuse finds each template evicted (spilled) in turn."""
    templates = [list(range(3 + 97 * g, 36 + 97 * g))
                 for g in range(n_templates)]   # 2 full pages + tail each
    prompts = []
    for r in range(2):
        for g, tmpl in enumerate(templates):
            prompts.append(tmpl + [40 + 10 * r + g])
    return prompts + [templates[0] + [77]]


# kv_quant="" at a 5-page budget and "int8" at a 2-f32-page budget (the
# byte budget converts to ~7 int8 pages) both leave the pool too small
# for the template working set, so eviction pressure is real in both.
# Both arms are LOSSLESS round trips: the full-precision pool spills in
# resident precision (tier_spill_quant=""), the int8 pool spills its
# resident bytes + scales verbatim — so byte-identical is a hard gate.
@pytest.mark.parametrize("kv_quant,num_pages,n_templates",
                         [("", 5, 2), ("int8", 2, 3)])
def test_tier_roundtrip_byte_identical_continuation(kv_quant, num_pages,
                                                    n_templates):
    """T1 round trip under eviction pressure: greedy streams with tiers
    on must equal a tier-less engine's exactly, while actually spilling
    and restoring."""
    async def main():
        tiered = _engine(True, kv_quant=kv_quant, num_pages=num_pages)
        plain = _engine(False, kv_quant=kv_quant, num_pages=num_pages)
        outs = {}
        for name, engine in (("tiered", tiered), ("plain", plain)):
            await engine.start()
            try:
                outs[name] = [await _gen(engine, ids)
                              for ids in _pressure_prompts(n_templates)]
            finally:
                await engine.stop()
        assert outs["tiered"] == outs["plain"]
        stats = tiered.tier_stats()
        assert stats["spills"] >= 1 and stats["restores"] >= 1
        alloc = tiered.allocator
        assert alloc.tier_hit_tokens["host"] >= 2 * PS
        # tiers held hits the page budget alone could not: at the same
        # fixed page budget, at least twice the tier-less engine's
        assert tiered.num_kv_pages == plain.num_kv_pages
        assert alloc.prefix_hit_tokens \
            >= 2 * max(1, plain.allocator.prefix_hit_tokens)
        # conservation: the tier split sums to the headline counter the
        # tenant ledger's cache_hit accounting mirrors
        assert sum(alloc.tier_hit_tokens.values()) == alloc.prefix_hit_tokens

    asyncio.run(main())


def test_quantize_on_spill_default_is_safe_and_counted():
    """tier_spill_quant="int8" (the default) on a full-precision pool:
    restored pages carry resident-int8-grade quantization — greedy
    streams may drift within the same bounded trade resident int8 KV
    makes (test_kv_quant pins that drift), but the machinery must stay
    sound: spills/restores fire, hits count, lengths and terminations
    match the tier-less run token-for-position >= 90%."""
    async def main():
        tiered = _engine(True, spill_quant="int8")
        plain = _engine(False)
        outs = {}
        for name, engine in (("tiered", tiered), ("plain", plain)):
            await engine.start()
            try:
                outs[name] = [await _gen(engine, ids)
                              for ids in _pressure_prompts()]
            finally:
                await engine.stop()
        assert all(len(o) >= 1 for o in outs["tiered"])
        matched = sum(1 for a, b in zip(outs["tiered"], outs["plain"])
                      for x, y in zip(a, b) if x == y)
        total = sum(min(len(a), len(b)) for a, b
                    in zip(outs["tiered"], outs["plain"]))
        # bounded drift, not byte-parity: the tiny random-init test model
        # amplifies int8 noise far beyond real checkpoints — the
        # byte-identical gates are the LOSSLESS arms above
        assert matched / total >= 0.75, (matched, total)
        stats = tiered.tier_stats()
        assert stats["spills"] >= 1 and stats["restores"] >= 1
        alloc = tiered.allocator
        assert sum(alloc.tier_hit_tokens.values()) == alloc.prefix_hit_tokens

    asyncio.run(main())


def test_disk_tier_roundtrip_byte_identical(tmp_path):
    """T2 round trip: a host budget of ~one page pushes spills through
    the write-behind worker to disk; with T1 emptied, a later match is
    served FROM DISK (re-onlining) with exact continuation parity."""
    async def main():
        tiered = _engine(True, host_bytes=3000,
                         disk_dir=str(tmp_path / "tier"))
        plain = _engine(False)
        await tiered.start()
        await plain.start()
        try:
            prompts = _pressure_prompts()
            outs_t = [await _gen(tiered, ids) for ids in prompts]
            outs_p = [await _gen(plain, ids) for ids in prompts]
            assert outs_t == outs_p
            store = tiered._tier_client.store
            # force template A's chain fully out of HBM the way real
            # pressure would: evict (= spill) cached pages until no
            # local chain remains. The engine is idle, so driving the
            # allocator's eviction path directly is safe.
            probe_prompt = list(prompts[0][:33]) + [88]
            local = tiered.allocator
            saved, local._free = local._free, []   # evictions, not frees
            while local._walk_prefix(probe_prompt):
                saved.append(local._take_page())
            local._free = saved
            assert all(store.probe(h)
                       for h in chain_hashes(probe_prompt, PS))
            # push EVERY T1 entry through the real write-behind path and
            # wait for the worker to land them: afterwards the chain is
            # disk-only, so the next match can only be served by T2
            with store._lock:
                for key_hash in list(store._host):
                    payload = store._host.pop(key_hash)
                    store._host_nbytes -= payload.nbytes
                    store._pending[key_hash] = payload
                    store._writeq.put(key_hash)
            store._ensure_writer()
            deadline = time.monotonic() + 20
            while ((store._pending or store.stats()["disk_pages"] < 1)
                   and time.monotonic() < deadline):
                await asyncio.sleep(0.02)
            stats = store.stats()
            assert stats["disk_pages"] >= 1 and stats["host_pages"] == 0, \
                stats
            reads0 = store.disk_reads
            out_t = await _gen(tiered, probe_prompt)
            out_p = await _gen(plain, probe_prompt)
            assert out_t == out_p                  # byte-identical via T2
            assert store.disk_reads > reads0       # the disk really served
            assert tiered.allocator.tier_hit_tokens["disk"] >= PS
        finally:
            await tiered.stop()
            await plain.stop()

    asyncio.run(main())


def test_fetch_on_miss_greedy_parity_vs_cold_admission():
    """A restore-served request must emit exactly what a cold admission
    (no cache at all) emits — restored KV is the prompt's KV."""
    async def main():
        tiered = _engine(True)
        cold = _engine(False, prefix_cache=False)
        await tiered.start()
        await cold.start()
        try:
            prompts = _pressure_prompts()
            outs_t = [await _gen(tiered, ids) for ids in prompts]
            outs_c = [await _gen(cold, ids) for ids in prompts]
            assert outs_t == outs_c
            assert tiered.tier_stats()["restores"] >= 1
        finally:
            await tiered.stop()
            await cold.stop()

    asyncio.run(main())


def test_hash_collision_falls_back_to_miss_never_wrong_pages():
    """A poisoned store entry under a prompt's exact chain hash must
    verify-fail (collision), serve a MISS, and leave the continuation
    identical to a cold run."""
    async def main():
        # ample pages (the poison is injected directly, no pressure
        # needed — and the 72-token chunked footprint must fit the pool)
        tiered = _engine(True, num_pages=16)
        cold = _engine(False, prefix_cache=False, num_pages=16)
        # 66-token prompt: a 1-page "hit" changes its admission path
        # (chunked-from-hist), so the probe keeps the poisoned hist and
        # admission actually attempts the restore
        template = list(range(3, 68))
        prompt = template + [99]
        store = tiered._tier_client.store
        # poison: correct chain hash, WRONG payload identity
        h0 = chain_hashes(prompt, PS)[0]
        store.put(h0, _payload(tuple(range(900, 916))))
        await tiered.start()
        await cold.start()
        try:
            out_t = await _gen(tiered, prompt)
            out_c = await _gen(cold, prompt)
            assert out_t == out_c
            assert store.collisions >= 1
            assert not store.probe(h0)  # dropped: no admission livelock
            # the engine made progress WITHOUT counting a tier hit
            assert tiered.allocator.tier_hit_tokens["host"] == 0
            assert tiered.allocator.tier_hit_tokens["disk"] == 0
        finally:
            await tiered.stop()
            await cold.stop()

    asyncio.run(main())


def test_tier_stats_surface_shapes():
    """tier_stats() (the /admin/engine/stats + pool card payload) carries
    the per-tier split, store footprint, and restore latency fields."""
    async def main():
        engine = _engine(True)
        await engine.start()
        try:
            for ids in _pressure_prompts():
                await _gen(engine, ids, n=2)
            stats = engine.tier_stats()
            assert stats["enabled"] is True
            assert set(stats["hits"]) == {"hbm", "host", "disk", "object"}
            assert set(stats["hit_tokens"]) == {"hbm", "host", "disk",
                                                "object"}
            assert stats["store"]["host_budget_bytes"] > 0
            assert stats["restores"] >= 1
            assert stats["restore_p95_ms"] is not None
        finally:
            await engine.stop()

    asyncio.run(main())


def test_prefix_tiers_requires_prefix_cache():
    with pytest.raises(ValueError, match="prefix_tiers requires"):
        _engine(True, prefix_cache=False)


def test_prefix_index_chain_locations_and_reachability():
    index = PrefixIndex()
    prompt = list(range(33))           # 2 matchable full pages at PS=16
    hashes = chain_hashes(prompt, PS)
    assert len(hashes) == 2
    index.publish_hbm(hashes[0], "1")
    index.publish_hbm(hashes[1], "1")
    chain = index.chain_locations(prompt, PS)
    # replica 1 reaches both pages; replica 0 none (cross-replica HBM
    # reads don't exist — the router routes TO replica 1 instead)
    assert index.reachable_tokens(chain, "1", PS) == 32
    assert index.reachable_tokens(chain, "0", PS) == 0
    # a spill moves page 0 to a shared tier: now ANY replica reaches it,
    # and replica 1 still reaches both
    index.unpublish_hbm(hashes[0], "1")
    index.publish_tier(hashes[0], "host")
    chain = index.chain_locations(prompt, PS)
    assert index.reachable_tokens(chain, "0", PS) == 16
    assert index.reachable_tokens(chain, "1", PS) == 32
    # replica rebuild forgets its HBM entries
    index.drop_replica("1")
    chain = index.chain_locations(prompt, PS)
    assert index.reachable_tokens(chain, "1", PS) == 16  # tier only
    assert index.stats() == {"keys_hbm": 0, "keys_tiered": 1,
                             "keys_object": 0}


# ---------------------------------------------- disk IO hardening (ISSUE 14)

def _arm(rule_kwargs):
    from mcp_context_forge_tpu.observability.faults import (FaultRule,
                                                            configure_fault_plane)
    plane = configure_fault_plane(True)
    plane.arm(FaultRule(**rule_kwargs))
    return plane


@pytest.fixture()
def fault_env():
    """Armed fault plane + fast degradation thresholds, reset after."""
    from mcp_context_forge_tpu.observability.degradation import \
        configure_degradation
    from mcp_context_forge_tpu.observability.faults import \
        configure_fault_plane
    configure_degradation(failure_threshold=2, cooldown_s=0.05)
    yield
    configure_fault_plane(False)
    configure_degradation()


def _spill_three(store):
    """Three one-page spills into a T1 sized for one page: two overflow
    to the write-behind worker."""
    chunks = [tuple(range(i, i + 4)) for i in range(0, 12, 4)]
    hashes = [chain_hashes(list(c) + [99], 4)[0] for c in chunks]
    for h, c in zip(hashes, chunks):
        store.put(h, _payload(c, fill=c[0] + 1))
    return hashes, chunks


def _drain_writer(store, deadline_s=10):
    deadline = time.monotonic() + deadline_s
    while (not store._writeq.empty() or store._pending) \
            and time.monotonic() < deadline:
        time.sleep(0.01)


def test_disk_write_fault_retries_then_quarantines_entry(fault_env):
    """A persistent write error exhausts the bounded retries, drops the
    entry CLEANLY (no hang, no poisoned serve), counts it in
    io_errors{disk,write}, and opens the tier.disk breaker after the
    threshold — T1 keeps serving throughout."""
    from mcp_context_forge_tpu.observability.degradation import \
        get_degradation
    _arm({"point": "tier.disk.write", "kind": "error", "mode": "always"})
    one = _payload((0,) * 4).nbytes
    store = TieredPageStore(host_bytes=one + 1, disk_bytes=1 << 20,
                            pin=False, io_retry_max=1,
                            io_retry_backoff_ms=1.0)
    try:
        hashes, chunks = _spill_three(store)
        _drain_writer(store)
        stats = store.stats()
        assert stats["disk_pages"] == 0
        assert stats["io_errors"]["disk.write"] >= 2
        assert stats["dropped"] >= 2                  # clean quarantine
        assert stats["disk_breaker"]["state"] == "open"
        assert get_degradation().component_state("tier.disk") == "open"
        # T1 keeps serving: the newest entry is still a HIT
        assert store.get(hashes[-1], ROOT_HASH, chunks[-1]) is not None
        # the quarantined entries are clean MISSes, not hangs/errors
        assert store.get(hashes[0], ROOT_HASH, chunks[0]) is None
    finally:
        store.close()


def test_disk_write_transient_fault_recovers_via_retry(fault_env):
    """A 1-in-2 write fault is absorbed by the retry (backoff then
    success): nothing is lost, the breaker stays closed."""
    _arm({"point": "tier.disk.write", "kind": "error",
          "mode": "one_in_n", "n": 2})
    one = _payload((0,) * 4).nbytes
    store = TieredPageStore(host_bytes=one + 1, disk_bytes=1 << 20,
                            pin=False, io_retry_max=2,
                            io_retry_backoff_ms=1.0)
    try:
        _spill_three(store)
        _drain_writer(store)
        stats = store.stats()
        assert stats["disk_pages"] == 2
        assert stats["io_errors"]["disk.write"] == 0
        assert stats["disk_breaker"]["state"] == "closed"
    finally:
        store.close()


def test_disk_breaker_half_open_probe_recovers(fault_env):
    """After the injected outage clears, the cooldown admits ONE probe
    writeback; its success closes the breaker and the disk tier serves
    again — the open -> half_open -> closed ladder in order."""
    from mcp_context_forge_tpu.observability.degradation import \
        get_degradation
    from mcp_context_forge_tpu.observability.faults import \
        get_fault_plane
    _arm({"point": "tier.disk.write", "kind": "error", "mode": "always"})
    one = _payload((0,) * 4).nbytes
    store = TieredPageStore(host_bytes=one + 1, disk_bytes=1 << 20,
                            pin=False, io_retry_max=0,
                            io_retry_backoff_ms=1.0)
    try:
        _spill_three(store)
        _drain_writer(store)
        assert store.stats()["disk_breaker"]["state"] == "open"
        get_fault_plane().disarm("tier.disk.write")
        time.sleep(0.06)                     # cooldown elapses
        chunks = [tuple(range(i, i + 4)) for i in range(100, 112, 4)]
        hashes = [chain_hashes(list(c) + [99], 4)[0] for c in chunks]
        for h, c in zip(hashes, chunks):
            store.put(h, _payload(c))
        _drain_writer(store)
        assert store.stats()["disk_breaker"]["state"] == "closed"
        assert store.stats()["disk_pages"] >= 1
        transitions = [t["to"] for t in
                       get_degradation().transitions("tier.disk")]
        assert transitions[:3] == ["open", "half_open", "closed"]
    finally:
        store.close()


def test_disk_read_fault_is_a_clean_miss_and_quarantines(fault_env):
    """A persistent read error (after retries) drops the disk entry to
    a clean MISS — never a hang, never garbage pages."""
    _arm({"point": "tier.disk.read", "kind": "error", "mode": "always"})
    one = _payload((0,) * 4).nbytes
    store = TieredPageStore(host_bytes=one + 1, disk_bytes=1 << 20,
                            pin=False, io_retry_max=1,
                            io_retry_backoff_ms=1.0)
    try:
        hashes, chunks = _spill_three(store)
        _drain_writer(store)
        assert store.stats()["disk_pages"] == 2
        assert store.get(hashes[0], ROOT_HASH, chunks[0]) is None
        stats = store.stats()
        assert stats["io_errors"]["disk.read"] == 1
        assert stats["disk_pages"] == 1               # entry quarantined
    finally:
        store.close()


def test_disk_read_corruption_quarantines_immediately(fault_env):
    """Injected payload corruption (mangled file bytes) must surface as
    a clean MISS via the unreadable-content path — wrong pages are
    never served, and no retry storm (corruption is not transient)."""
    _arm({"point": "tier.disk.read", "kind": "corrupt", "mode": "once"})
    one = _payload((0,) * 4).nbytes
    store = TieredPageStore(host_bytes=one + 1, disk_bytes=1 << 20,
                            pin=False, io_retry_max=3,
                            io_retry_backoff_ms=1.0)
    try:
        hashes, chunks = _spill_three(store)
        _drain_writer(store)
        assert store.get(hashes[0], ROOT_HASH, chunks[0]) is None
        assert store.stats()["io_errors"]["disk.read"] == 1
        # the OTHER disk entry (fault fired once) still round-trips
        assert store.get(hashes[1], ROOT_HASH, chunks[1]) is not None
    finally:
        store.close()


def test_host_get_fault_degrades_to_miss(fault_env):
    """tier.host.get error = MISS (admission continues with the pages
    already secured); corrupt = identity-verify failure, the entry
    quarantines exactly like a hash collision."""
    from mcp_context_forge_tpu.observability.faults import (
        FaultRule, get_fault_plane)
    store = TieredPageStore(host_bytes=1 << 20, disk_bytes=0, pin=False)
    try:
        chunk = tuple(range(4))
        h = chain_hashes(list(chunk) + [99], 4)[0]
        store.put(h, _payload(chunk))
        plane = _arm({"point": "tier.host.get", "kind": "error",
                      "mode": "once"})
        assert store.get(h, ROOT_HASH, chunk) is None      # injected MISS
        assert store.stats()["io_errors"]["host.get"] == 1
        assert store.get(h, ROOT_HASH, chunk) is not None  # entry intact
        plane.arm(FaultRule(point="tier.host.get", kind="corrupt",
                            mode="once"))
        assert store.get(h, ROOT_HASH, chunk) is None      # quarantined
        assert not store.probe(h)
        get_fault_plane().clear()
    finally:
        store.close()
