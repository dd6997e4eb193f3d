"""Int8 quantized paged KV cache (kv/paged_cache.py quant mode).

Covers the numeric contract (running-max per-page scales: roundtrip
bounds, append-time requantization, tenancy reset on page reuse), the
dtype-aware capacity math (a fixed byte budget holds ~2x the pages), and
the serving guarantees the mode ships with: pinned decode-logit drift vs
full-precision pages, exact greedy-token parity on short contexts, and
composition with spec-decode, chunked prefill, and the overlap pipeline.
"""

import asyncio

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mcp_context_forge_tpu.tpu_local.engine import EngineConfig, TPUEngine
from mcp_context_forge_tpu.tpu_local.kv import (PageAllocator, gather_kv,
                                                init_kv_state, kv_page_bytes,
                                                num_pages_for_budget,
                                                write_decode_kv,
                                                write_prefill_kv)
from mcp_context_forge_tpu.tpu_local.models import MODEL_CONFIGS
from mcp_context_forge_tpu.tpu_local.models.llama import (decode_step,
                                                          init_params,
                                                          prefill)

CFG = MODEL_CONFIGS["llama3-test"]  # KV=2, H=4, hd=16, L=2

# decode-logit drift bar for int8 KV on the llama3-test geometry: measured
# ~4.2e-2 peak on the seeded run below; pinned at ~2.5x that so a numeric
# regression (a scale applied twice, a missing requantization) trips
# loudly while rounding-order noise does not
LOGIT_DRIFT_TOL = 0.1


def _filled_pair(seq_lens, page_size=8, num_pages=32, per_slot=8, seed=0):
    """Full-precision and int8 KV states holding the SAME sequentially
    written values; returns (kv_full, kv_q, originals[slot][pos])."""
    slots = len(seq_lens)
    kv_f = init_kv_state(CFG, num_pages, page_size, slots, per_slot,
                         dtype=jnp.float32)
    kv_q = init_kv_state(CFG, num_pages, page_size, slots, per_slot,
                         dtype=jnp.float32, quant="int8")
    alloc = PageAllocator(num_pages, page_size, slots, per_slot)
    for slot, n in enumerate(seq_lens):
        assert alloc.allocate_slot(slot, n)
    tables = alloc.tables()
    kv_f = kv_f._replace(block_tables=tables)
    kv_q = kv_q._replace(block_tables=tables)
    key = jax.random.PRNGKey(seed)
    originals = {}
    for slot, n in enumerate(seq_lens):
        for pos in range(n):
            key, k1, k2 = jax.random.split(key, 3)
            kt = jax.random.normal(k1, (1, CFG.n_kv_heads, CFG.head_dim),
                                   dtype=jnp.float32)
            vt = jax.random.normal(k2, (1, CFG.n_kv_heads, CFG.head_dim),
                                   dtype=jnp.float32)
            originals[(slot, pos)] = (np.asarray(kt[0]), np.asarray(vt[0]))
            kv_f = write_decode_kv(kv_f, 0, kt, vt, jnp.array([slot]),
                                   jnp.array([pos]))
            kv_q = write_decode_kv(kv_q, 0, kt, vt, jnp.array([slot]),
                                   jnp.array([pos]))
    return kv_f, kv_q, originals


# ------------------------------------------------------------------ numerics

def test_int8_state_shapes_and_dtypes():
    kv = init_kv_state(CFG, 16, 8, 2, 4, dtype=jnp.float32, quant="int8")
    assert kv.quantized
    assert kv.k_pages.dtype == jnp.int8 and kv.v_pages.dtype == jnp.int8
    assert kv.k_scales.shape == (CFG.n_layers, 16, CFG.n_kv_heads)
    assert kv.k_scales.dtype == jnp.float32  # the compute-dtype marker
    full = init_kv_state(CFG, 16, 8, 2, 4, dtype=jnp.float32)
    assert not full.quantized and full.k_scales is None


def test_roundtrip_error_bounded_per_page():
    """Every stored token dequantizes within (1 + raises) * s/2 of its
    original, per kv-head, s = page_amax/127 the page's final scale:
    half a step for its own write — the symmetric-int8 worst case — and
    half a step more for every later append that RAISED the page's
    running-max scale, because each raise requantizes what the page
    already holds (from its int8 values, under a scale <= s)."""
    seq_lens = [13, 5, 20]
    _, kv_q, originals = _filled_pair(seq_lens)
    ks, vs = gather_kv(kv_q, 0, jnp.arange(len(seq_lens)))
    scales = np.asarray(kv_q.k_scales[0])     # [P, KV]
    tables = np.asarray(kv_q.block_tables)
    size = kv_q.page_size
    for slot, n in enumerate(seq_lens):
        for pos in range(n):
            page = tables[slot, pos // size]
            ref_k, _ = originals[(slot, pos)]
            got = np.asarray(ks[slot, pos])
            first = pos - pos % size
            amax = np.stack([np.abs(originals[(slot, q)][0]).max(axis=-1)
                             for q in range(first, min(first + size, n))])
            running = np.maximum.accumulate(amax, axis=0)     # [T, KV]
            raises = (running[1:] > running[:-1])[pos - first:].sum(axis=0)
            bound = (1 + raises)[:, None] * scales[page][:, None] / 2 * 1.01 \
                + 1e-6
            assert (np.abs(got - ref_k) <= bound).all(), (slot, pos, raises)


def test_prefill_writer_matches_decode_writer_storage():
    """A [B,S] prefill scatter and S sequential decode scatters of the
    same values land the same page SCALES (the running max is order-free)
    and dequantize within one quantization step of each other (sequential
    appends pay requantization hops the one-shot scatter does not)."""
    S, page_size = 11, 4
    kv_a = init_kv_state(CFG, 16, page_size, 1, 4, dtype=jnp.float32,
                         quant="int8")
    kv_b = init_kv_state(CFG, 16, page_size, 1, 4, dtype=jnp.float32,
                         quant="int8")
    alloc = PageAllocator(16, page_size, 1, 4)
    assert alloc.allocate_slot(0, S)
    tables = alloc.tables()
    kv_a = kv_a._replace(block_tables=tables)
    kv_b = kv_b._replace(block_tables=tables)
    key = jax.random.PRNGKey(7)
    k = jax.random.normal(key, (1, S, CFG.n_kv_heads, CFG.head_dim),
                          dtype=jnp.float32)
    v = -k
    positions = jnp.arange(S)[None, :]
    kv_a = write_prefill_kv(kv_a, 0, k, v, jnp.array([0]), positions,
                            jnp.ones((1, S), bool))
    for pos in range(S):
        kv_b = write_decode_kv(kv_b, 0, k[:, pos], v[:, pos],
                               jnp.array([0]), jnp.array([pos]))
    np.testing.assert_allclose(np.asarray(kv_a.k_scales),
                               np.asarray(kv_b.k_scales), rtol=1e-6)
    ka, _ = gather_kv(kv_a, 0, jnp.arange(1))
    kb, _ = gather_kv(kv_b, 0, jnp.arange(1))
    step = float(np.asarray(kv_a.k_scales[0]).max())
    assert np.abs(np.asarray(ka[0, :S]) - np.asarray(kb[0, :S])).max() \
        <= 2 * step


def test_decode_append_requantizes_growing_page():
    """A decode append whose magnitude exceeds the page's running max must
    grow the scale AND requantize the resident tokens — earlier values
    still dequantize within the NEW scale's step."""
    page_size = 8
    kv = init_kv_state(CFG, 8, page_size, 1, 2, dtype=jnp.float32,
                       quant="int8")
    alloc = PageAllocator(8, page_size, 1, 2)
    assert alloc.allocate_slot(0, page_size)
    kv = kv._replace(block_tables=alloc.tables())
    vals = []
    for pos in range(page_size):       # magnitudes grow 1, 2, ..., 8
        mag = float(pos + 1)
        kt = jnp.full((1, CFG.n_kv_heads, CFG.head_dim), mag,
                      dtype=jnp.float32)
        vals.append(mag)
        kv = write_decode_kv(kv, 0, kt, kt, jnp.array([0]),
                             jnp.array([pos]))
    page = int(np.asarray(kv.block_tables)[0, 0])
    s = np.asarray(kv.k_scales[0, page])
    np.testing.assert_allclose(s, 8.0 / 127.0, rtol=1e-5)  # running max
    ks, _ = gather_kv(kv, 0, jnp.arange(1))
    got = np.asarray(ks[0, :page_size])
    for pos, mag in enumerate(vals):
        # requantized early tokens: one extra rounding hop per rescale,
        # bounded by (#rescales + 1) half-steps of the final scale
        assert np.abs(got[pos] - mag).max() <= s.max() * (page_size / 2 + 1)
    # the most recent token is a single quantization away
    assert np.abs(got[-1] - 8.0).max() <= s.max()


def test_page_reuse_resets_scale():
    """A freed page re-entering service at offset 0 must NOT inherit the
    old tenant's (huge) scale: the small new tenant keeps small-value
    precision."""
    page_size = 8
    kv = init_kv_state(CFG, 4, page_size, 1, 2, dtype=jnp.float32,
                       quant="int8")
    alloc = PageAllocator(4, page_size, 1, 2)
    assert alloc.allocate_slot(0, page_size)
    kv = kv._replace(block_tables=alloc.tables())
    big = jnp.full((1, CFG.n_kv_heads, CFG.head_dim), 1000.0, jnp.float32)
    kv = write_decode_kv(kv, 0, big, big, jnp.array([0]), jnp.array([0]))
    page = int(np.asarray(kv.block_tables)[0, 0])
    assert float(np.asarray(kv.k_scales[0, page]).max()) > 1.0
    # same physical page, new tenancy (offset-0 write), tiny values
    small = jnp.full((1, CFG.n_kv_heads, CFG.head_dim), 0.01, jnp.float32)
    kv = write_decode_kv(kv, 0, small, small, jnp.array([0]),
                         jnp.array([0]))
    s = float(np.asarray(kv.k_scales[0, page]).max())
    assert s <= 0.01 / 127.0 * 1.001  # reset, not creeping on the stale max
    ks, _ = gather_kv(kv, 0, jnp.arange(1))
    assert abs(float(np.asarray(ks[0, 0]).max()) - 0.01) < 1e-3


def test_masked_rows_only_touch_trash_page():
    """Invalid decode rows must leave real pages AND scales untouched (the
    same trash-page discipline the full-precision writer has)."""
    page_size = 8
    kv = init_kv_state(CFG, 8, page_size, 2, 2, dtype=jnp.float32,
                       quant="int8")
    alloc = PageAllocator(8, page_size, 2, 2)
    assert alloc.allocate_slot(0, page_size)
    kv = kv._replace(block_tables=alloc.tables())
    one = jnp.ones((2, CFG.n_kv_heads, CFG.head_dim), jnp.float32)
    kv = write_decode_kv(kv, 0, one, one, jnp.array([0, 0]),
                         jnp.array([3, 3]),
                         valid=jnp.array([True, False]))
    # a second call, all-masked: nothing may change outside page 0
    before_pages = np.asarray(kv.k_pages[0, 1:])
    before_scales = np.asarray(kv.k_scales[0, 1:])
    kv = write_decode_kv(kv, 0, 100 * one, 100 * one, jnp.array([0, 0]),
                         jnp.array([5, 5]),
                         valid=jnp.array([False, False]))
    np.testing.assert_array_equal(np.asarray(kv.k_pages[0, 1:]), before_pages)
    np.testing.assert_array_equal(np.asarray(kv.k_scales[0, 1:]),
                                  before_scales)


# ------------------------------------------------------------ capacity math

def test_fixed_byte_budget_holds_2x_pages_bf16_to_int8():
    """The acceptance bar: at a fixed HBM byte budget, int8 storage holds
    >= 1.9x the bf16 page count — on the CI geometry AND the 8B serving
    geometry."""
    for config, page_size in ((CFG, 16), (MODEL_CONFIGS["llama3-8b"], 128)):
        budget = 512 * kv_page_bytes(config, page_size, jnp.bfloat16)
        bf16_pages = num_pages_for_budget(config, page_size, budget,
                                          jnp.bfloat16)
        int8_pages = num_pages_for_budget(config, page_size, budget,
                                          jnp.bfloat16, "int8")
        assert bf16_pages == 512
        assert int8_pages >= 1.9 * bf16_pages, (config.name, int8_pages)


def test_engine_allocator_sized_by_dtype_aware_budget():
    base = dict(model="llama3-test", max_batch=2, max_seq_len=64,
                page_size=16, num_pages=32, prefill_buckets=(16,),
                dtype="float32", attn_impl="reference")
    full = TPUEngine(EngineConfig(**base))
    quant = TPUEngine(EngineConfig(**base, kv_quant="int8"))
    assert full.num_kv_pages == 32
    assert full.allocator.num_pages == 32
    assert quant.num_kv_pages >= 1.9 * full.num_kv_pages
    assert quant.allocator.num_pages == quant.num_kv_pages
    assert quant.kv.k_pages.shape[1] == quant.num_kv_pages
    # byte view: the quantized pool's capacity stays within the budget
    assert quant.kv_bytes_capacity() <= full.kv_bytes_capacity()
    assert quant.kv_bytes_in_use() == 0


def test_engine_rejects_unknown_kv_quant():
    with pytest.raises(ValueError, match="kv_quant"):
        TPUEngine(EngineConfig(model="llama3-test", max_batch=2,
                               max_seq_len=64, page_size=16, num_pages=32,
                               prefill_buckets=(16,), dtype="float32",
                               kv_quant="int4"))


# ----------------------------------------------------- drift + greedy parity

def test_decode_logit_drift_pinned_and_greedy_parity():
    """Seeded A/B on one decode step: int8 pages vs full-precision pages,
    max-abs logit drift under the pinned tolerance and identical argmax."""
    params = init_params(CFG, jax.random.PRNGKey(3), dtype=jnp.float32)
    page_size, num_pages, per_slot = 16, 32, 16
    n_prompt = 24
    kv_f = init_kv_state(CFG, num_pages, page_size, 1, per_slot,
                         dtype=jnp.float32)
    kv_q = init_kv_state(CFG, num_pages, page_size, 1, per_slot,
                         dtype=jnp.float32, quant="int8")
    alloc = PageAllocator(num_pages, page_size, 1, per_slot)
    assert alloc.allocate_slot(0, n_prompt + 8)
    tables = alloc.tables()
    kv_f = kv_f._replace(block_tables=tables)
    kv_q = kv_q._replace(block_tables=tables)
    tokens = (jnp.arange(n_prompt) * 7 % CFG.vocab_size)[None, :]
    positions = jnp.arange(n_prompt)[None, :]
    logits_f, kv_f = prefill(params, CFG, tokens, positions, kv_f,
                             jnp.array([0]), attn_impl="reference")
    logits_q, kv_q = prefill(params, CFG, tokens, positions, kv_q,
                             jnp.array([0]), attn_impl="reference")
    # prefill attends over its OWN in-call k/v — storage mode can't move it
    np.testing.assert_allclose(np.asarray(logits_f), np.asarray(logits_q),
                               rtol=1e-5, atol=1e-5)
    nxt = jnp.argmax(logits_f[0, -1]).astype(jnp.int32)
    drift = 0.0
    for step in range(4):   # decode READS the cache: drift shows up here
        pos = jnp.array([n_prompt + step])
        lens = pos + 1
        lf, kv_f = decode_step(params, CFG, nxt[None], pos, kv_f,
                               jnp.array([0]), lens)
        lq, kv_q = decode_step(params, CFG, nxt[None], pos, kv_q,
                               jnp.array([0]), lens)
        drift = max(drift, float(jnp.max(jnp.abs(lf - lq))))
        assert int(jnp.argmax(lf[0])) == int(jnp.argmax(lq[0]))
        nxt = jnp.argmax(lf[0]).astype(jnp.int32)
    assert drift <= LOGIT_DRIFT_TOL, drift


def _engine(**overrides) -> TPUEngine:
    base = dict(model="llama3-test", max_batch=2, max_seq_len=512,
                page_size=16, num_pages=128, prefill_buckets=(64, 256),
                dtype="float32", attn_impl="reference")
    base.update(overrides)
    return TPUEngine(EngineConfig(**base))


async def _gen(engine: TPUEngine, ids, n=8, **kwargs):
    return [t async for t in engine.generate(ids, max_tokens=n, **kwargs)]


def test_engine_greedy_parity_256_token_context():
    """The serving acceptance bar: exact greedy-token parity between the
    full-precision and int8 engines on a <=256-token context."""
    async def run():
        full = _engine()
        quant = _engine(kv_quant="int8")
        prompt = [(3 + 11 * i) % 512 for i in range(200)]  # 200 tokens
        for e in (full, quant):
            await e.start()
        try:
            out_f = await _gen(full, prompt, n=32)
            out_q = await _gen(quant, prompt, n=32)
            assert len(out_f) == 32
            assert out_f == out_q
        finally:
            for e in (full, quant):
                await e.stop()

    asyncio.run(run())


# ------------------------------------------------------------- composition

def test_spec_decode_composes_with_kv_quant():
    """Prompt-lookup speculative verify reads (and rewrites) quantized
    pages through the chunk path — greedy output must equal the plain
    int8 decode path's."""
    async def run():
        plain = _engine(kv_quant="int8")
        spec = _engine(kv_quant="int8", spec_decode=True, spec_k=3)
        # repetitive prompt so the n-gram drafter actually engages
        prompt = ([5, 6, 7, 8] * 10) + [9]
        for e in (plain, spec):
            await e.start()
        try:
            out_p = await _gen(plain, prompt, n=16)
            out_s = await _gen(spec, prompt, n=16)
            assert out_p == out_s
        finally:
            for e in (plain, spec):
                await e.stop()

    asyncio.run(run())


def test_overlap_pipeline_composes_with_kv_quant():
    """The depth-2 overlapped decode pipeline on int8 pages stays
    token-identical to the serial path."""
    async def run():
        serial = _engine(kv_quant="int8", decode_overlap=False)
        overlap = _engine(kv_quant="int8", decode_overlap=True)
        prompt = [(2 + 5 * i) % 512 for i in range(40)]
        for e in (serial, overlap):
            await e.start()
        try:
            outs_s = await asyncio.gather(_gen(serial, prompt, n=12),
                                          _gen(serial, prompt[:30], n=12))
            outs_o = await asyncio.gather(_gen(overlap, prompt, n=12),
                                          _gen(overlap, prompt[:30], n=12))
            assert outs_s == outs_o
        finally:
            for e in (serial, overlap):
                await e.stop()

    asyncio.run(run())


def test_chunked_prefill_composes_with_kv_quant():
    """A prompt longer than every bucket chunk-prefills through the
    history path on quantized pages; output equals a wide-bucket int8
    engine's."""
    async def run():
        chunked = _engine(kv_quant="int8", prefill_buckets=(16,),
                          max_seq_len=128, num_pages=64, prefix_cache=False)
        wide = _engine(kv_quant="int8", prefill_buckets=(64,),
                       max_seq_len=128, num_pages=64, prefix_cache=False)
        ids = [(3 + i) % 512 for i in range(50)]
        for e in (chunked, wide):
            await e.start()
        try:
            out_c = await _gen(chunked, ids, n=8)
            out_w = await _gen(wide, ids, n=8)
            assert len(out_w) >= 1 and out_c == out_w
            assert chunked.stats.prefill_batches >= 4
        finally:
            for e in (chunked, wide):
                await e.stop()

    asyncio.run(run())
