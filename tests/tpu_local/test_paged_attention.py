"""Pallas paged decode attention vs the gather-based reference."""

import numpy as np
import jax
import jax.numpy as jnp

from mcp_context_forge_tpu.tpu_local.kv import PageAllocator, init_kv_state
from mcp_context_forge_tpu.tpu_local.models import MODEL_CONFIGS
from mcp_context_forge_tpu.tpu_local.ops.paged_attention import (
    paged_decode_attention_pallas,
)


def _check_against_gather(CFG, page_size, num_pages, slots, per_slot, seq_lens,
                          quant="", dtype=jnp.float32):
    kv = init_kv_state(CFG, num_pages, page_size, slots, per_slot,
                       dtype=dtype, quant=quant)
    alloc = PageAllocator(num_pages, page_size, slots, per_slot)
    for slot, n in enumerate(seq_lens):
        assert alloc.allocate_slot(slot, n)
    kv = kv._replace(block_tables=alloc.tables())

    key = jax.random.PRNGKey(0)
    KV, hd = CFG.n_kv_heads, CFG.head_dim
    G = CFG.n_heads // KV
    # fill the used cache positions with random K/V via the writer path
    from mcp_context_forge_tpu.tpu_local.kv import write_decode_kv, gather_kv
    for slot, n in enumerate(seq_lens):
        for pos in range(n):
            key, k1, k2 = jax.random.split(key, 3)
            k_tok = jax.random.normal(k1, (1, KV, hd)).astype(dtype)
            v_tok = jax.random.normal(k2, (1, KV, hd)).astype(dtype)
            kv = write_decode_kv(kv, 0, k_tok, v_tok,
                                 jnp.array([slot]), jnp.array([pos]))

    key, kq = jax.random.split(key)
    q = jax.random.normal(kq, (slots, KV, G, hd), dtype=jnp.float32)

    # reference: gather + masked softmax (same math as llama's
    # _paged_decode_attention; gather_kv dequantizes int8 pages, so the
    # kernel's FUSED dequant is held to the same stored values)
    import math
    keys_g, values_g = gather_kv(kv, 0, jnp.arange(slots))
    keys_g, values_g = (a.astype(jnp.float32) for a in (keys_g, values_g))
    scores = jnp.einsum("bkgh,bckh->bkgc", q, keys_g) / math.sqrt(hd)
    valid = jnp.arange(keys_g.shape[1])[None, :] < jnp.asarray(seq_lens)[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ref = jnp.einsum("bkgc,bckh->bkgh", probs, values_g)

    out = paged_decode_attention_pallas(
        q, kv.k_pages, kv.v_pages, kv.block_tables,
        jnp.asarray(seq_lens, dtype=jnp.int32), layer=0, interpret=True,
        k_scales=kv.k_scales, v_scales=kv.v_scales)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_matches_gather_reference():
    CFG = MODEL_CONFIGS["llama3-test"]  # KV=2, H=4, hd=16
    _check_against_gather(CFG, page_size=8, num_pages=16, slots=3, per_slot=4,
                          seq_lens=[13, 5, 20])


def test_paged_decode_int8_fused_dequant_matches_gather():
    """Tier-1 interpret-mode pin for the fused-dequant decode kernel: the
    in-VMEM q*scale path must equal the dequant-gather epilogue exactly
    (same int8 values, same scales — only WHERE the multiply happens
    differs), so the kernel cannot rot between TPU hardware windows."""
    CFG = MODEL_CONFIGS["llama3-test"]
    _check_against_gather(CFG, page_size=8, num_pages=16, slots=3, per_slot=4,
                          seq_lens=[13, 5, 20], quant="int8")


def test_paged_decode_int8_llama1b_geometry():
    class Geo:
        n_kv_heads, n_heads, head_dim, n_layers = 8, 32, 64, 1
    _check_against_gather(Geo, page_size=16, num_pages=24, slots=2, per_slot=8,
                          seq_lens=[19, 33], quant="int8")


def test_paged_decode_llama1b_geometry():
    """Exact llama3-1b attention geometry (KV=8, G=4, head_dim=64) — the
    shape the TPU gate must admit for the 1B serving path."""
    class Geo:
        n_kv_heads, n_heads, head_dim, n_layers = 8, 32, 64, 1
    _check_against_gather(Geo, page_size=16, num_pages=24, slots=2, per_slot=8,
                          seq_lens=[19, 33])


import pytest


class _Heads:
    """Head geometry alone (what init_kv_state and the kernel read)."""

    def __init__(self, n_kv_heads, group=2, head_dim=16):
        self.n_kv_heads, self.n_heads = n_kv_heads, n_kv_heads * group
        self.head_dim, self.n_layers = head_dim, 1


# how the kernel reads ONE kv head out of a page block depends on the
# pool's dtype and head count (ops/paged_attention._word_heads): strided
# loads of 32-bit words where the heads fill whole words, the per-token
# sublane gather where they do not. The stored values are the same in the
# kernel and in the gather oracle, so the comparison is exact-tolerance.
@pytest.mark.parametrize("n_kv,dtype,quant", [
    (8, jnp.bfloat16, ""),      # two heads a word, four words a token
    (2, jnp.bfloat16, ""),      # one word a token: a TP shard of 8 heads
    (3, jnp.bfloat16, ""),      # heads do not fill words: the gather
    (1, jnp.bfloat16, ""),      # one head a shard: the gather
    (8, jnp.float32, ""),       # a head is a word
    # (int8 pools: f32 scales, so the oracle's dequant does not round)
    (4, jnp.float32, "int8"),   # four heads a word, one word a token
    (6, jnp.float32, "int8"),   # int8 heads that do not fill words
], ids=["bf16x8", "bf16x2", "bf16x3", "bf16x1", "f32x8", "int8x4", "int8x6"])
def test_paged_decode_reads_every_head_of_packed_pools(n_kv, dtype, quant):
    _check_against_gather(_Heads(n_kv), page_size=16, num_pages=12, slots=2,
                          per_slot=4, seq_lens=[37, 9], quant=quant,
                          dtype=dtype)


@pytest.mark.parametrize("quant,dtype", [("", jnp.float32), ("int8", jnp.float32),
                                         ("", jnp.bfloat16)],
                         ids=["f32", "int8", "bf16"])
def test_paged_chunk_matches_history_reference(quant, dtype):
    """Chunk kernel (S queries over the page list) vs _history_attention:
    per-row history offsets, padding rows, multi-page contexts. The int8
    variant pins the kernel's fused dequant against the gather epilogue
    (identical stored values, so the comparison is exact-tolerance)."""
    from mcp_context_forge_tpu.tpu_local.kv import write_decode_kv, gather_kv
    from mcp_context_forge_tpu.tpu_local.models.llama import _history_attention
    from mcp_context_forge_tpu.tpu_local.ops.paged_attention import (
        paged_chunk_attention_pallas,
    )

    CFG = MODEL_CONFIGS["llama3-test"]  # KV=2, H=4, hd=16
    page_size, num_pages, slots, per_slot = 8, 16, 3, 4
    KV, hd = CFG.n_kv_heads, CFG.head_dim
    G = CFG.n_heads // KV
    S = 6
    # per-slot (history, chunk) splits; slot 2's row is partly padding
    hists = [8, 0, 13]
    chunk_lens = [6, 6, 3]

    kv = init_kv_state(CFG, num_pages, page_size, slots, per_slot,
                       dtype=dtype, quant=quant)
    alloc = PageAllocator(num_pages, page_size, slots, per_slot)
    for slot in range(slots):
        assert alloc.allocate_slot(slot, hists[slot] + chunk_lens[slot])
    kv = kv._replace(block_tables=alloc.tables())

    key = jax.random.PRNGKey(1)
    for slot in range(slots):
        for pos in range(hists[slot] + chunk_lens[slot]):
            key, k1, k2 = jax.random.split(key, 3)
            kv = write_decode_kv(
                kv, 0, jax.random.normal(k1, (1, KV, hd)).astype(dtype),
                jax.random.normal(k2, (1, KV, hd)).astype(dtype),
                jnp.array([slot]), jnp.array([pos]))

    key, kq = jax.random.split(key)
    q = jax.random.normal(kq, (slots, S, KV * G, hd), dtype=jnp.float32)
    positions = np.full((slots, S), -1, dtype=np.int32)
    for slot in range(slots):
        positions[slot, :chunk_lens[slot]] = np.arange(
            hists[slot], hists[slot] + chunk_lens[slot])
    positions = jnp.asarray(positions)
    valid = positions >= 0
    safe = jnp.maximum(positions, 0)

    keys_g, values_g = gather_kv(kv, 0, jnp.arange(slots))
    ref = _history_attention(q, keys_g.astype(jnp.float32),
                             values_g.astype(jnp.float32), safe, valid, CFG)

    qg = q.reshape(slots, S, KV, G, hd)
    out = paged_chunk_attention_pallas(
        qg, kv.k_pages, kv.v_pages, kv.block_tables, positions,
        layer=0, interpret=True,
        k_scales=kv.k_scales, v_scales=kv.v_scales)
    out = out.reshape(slots, S, KV * G, hd)
    # compare only valid rows (padding rows are garbage in both paths)
    for slot in range(slots):
        n = chunk_lens[slot]
        np.testing.assert_allclose(np.asarray(out[slot, :n]),
                                   np.asarray(ref[slot, :n]),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("KV,pool_dtype,per_slot", [
    (4, jnp.float32, 4), (8, jnp.bfloat16, 4),
    (8, jnp.bfloat16, 8),   # two KV blocks a row, the second partly dead
], ids=["f32-1head", "bf16-2heads", "bf16-2heads-2blocks"])
def test_kernels_per_model_shard_match_unsharded(KV, pool_dtype, per_slot):
    """On a TP mesh the kernels run under shard_map over ``model`` — each
    shard on the kv heads it holds (ops/attention.on_model_axis) — and must
    give what one unsharded call gives. Layer 1 of 2, so the layer index
    that rides the BlockSpec index map is exercised too. A bf16 shard of
    two heads reads them as one 32-bit word a token."""
    from functools import partial

    from jax.sharding import NamedSharding, PartitionSpec as P

    from mcp_context_forge_tpu.tpu_local.ops.attention import (
        flash_attention_pallas, on_model_axis)
    from mcp_context_forge_tpu.tpu_local.ops.paged_attention import (
        paged_chunk_attention_pallas)
    from mcp_context_forge_tpu.tpu_local.parallel import make_mesh

    mesh = make_mesh("1x4", devices=jax.devices()[:4])
    L, page, G, hd, B, S = 2, 8, 2, 16, 2, 8
    N = 1 + B * per_slot
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 8))
    pool_sharding = NamedSharding(mesh, P(None, None, None, "model", None))
    k_pages, v_pages = (jax.device_put(
        jax.random.normal(next(keys), (L, N, page, KV, hd)).astype(pool_dtype),
        pool_sharding) for _ in range(2))
    tables = 1 + jnp.arange(B * per_slot, dtype=jnp.int32).reshape(B, per_slot)
    seq_lens = jnp.asarray([per_slot * page, 11], jnp.int32)
    q = jax.random.normal(next(keys), (B, KV, G, hd))
    decode = partial(paged_decode_attention_pallas, q, k_pages, v_pages,
                     tables, seq_lens, layer=1, interpret=True)
    np.testing.assert_allclose(np.asarray(decode(mesh=mesh)),
                               np.asarray(decode()), rtol=1e-6, atol=1e-6)

    positions = jnp.stack([per_slot * page - S + jnp.arange(S),
                           jnp.arange(S)]).astype(jnp.int32)
    qc = jax.random.normal(next(keys), (B, S, KV, G, hd))
    chunk = partial(paged_chunk_attention_pallas, qc, k_pages, v_pages,
                    tables, positions, layer=1, interpret=True)
    np.testing.assert_allclose(np.asarray(chunk(mesh=mesh)),
                               np.asarray(chunk()), rtol=1e-6, atol=1e-6)

    heads = P(None, None, "model", None)
    qf = jax.random.normal(next(keys), (B, 16, KV * G, hd))
    kf, vf = (jax.random.normal(next(keys), (B, 16, KV, hd)) for _ in range(2))
    valid = jnp.ones((B, 16), bool).at[1, 12:].set(False)
    flash = partial(flash_attention_pallas, block_q=8, block_k=8,
                    interpret=True)
    sharded = on_model_axis(flash, mesh, (heads, heads, heads, P()), heads)
    np.testing.assert_allclose(np.asarray(sharded(qf, kf, vf, valid)),
                               np.asarray(flash(qf, kf, vf, valid)),
                               rtol=1e-6, atol=1e-6)


# ------------------------------------------------- the walk by KV blocks
#
# A grid step holds N block-table pages (ops/paged_attention._kv_block_pages:
# 4 here, or the largest divisor of the table width under it). What has to
# hold whatever the width and the rows' lengths: a block whose tail is dead
# (its slots fall back to pages they held before), blocks that are wholly
# dead, idle rows, widths N does not divide, and a scale row a page for int8
# pools.

def _random_state(n_kv, hd, page_size, slots, per_slot, dtype, quant, key):
    """Pools filled at random (the kernel and the gather oracle read the
    same stored values), row ``b`` holding pages 1 + b * per_slot ..., in a
    shuffled order so that a block's pages are not neighbours."""
    from mcp_context_forge_tpu.tpu_local.kv import PagedKVState

    n_pages = 1 + slots * per_slot
    shape = (2, n_pages, page_size, n_kv, hd)
    ks = jax.random.split(key, 5)
    order = np.asarray(jax.random.permutation(ks[4], slots * per_slot))
    tables = jnp.asarray(1 + order.reshape(slots, per_slot), jnp.int32)
    if quant:
        pools = [jax.random.randint(k, shape, -127, 128, jnp.int8)
                 for k in ks[:2]]
        scales = [(0.002 + 0.01 * jax.random.uniform(
            k, (2, n_pages, n_kv))).astype(dtype) for k in ks[2:4]]
        return PagedKVState(*pools, tables, *scales)
    pools = [jax.random.normal(k, shape).astype(dtype) for k in ks[:2]]
    return PagedKVState(*pools, tables)


def _masked_softmax_reference(q, kv, layer, positions):
    """q [B, S, KV, G, hd]; positions [B, S] (-1: none) -> same shape."""
    import math

    from mcp_context_forge_tpu.tpu_local.kv import gather_kv

    keys_g, values_g = (a.astype(jnp.float32) for a in gather_kv(
        kv, layer, jnp.arange(q.shape[0])))
    scores = jnp.einsum("bskgh,bckh->bskgc", q, keys_g) / math.sqrt(q.shape[-1])
    seen = jnp.arange(keys_g.shape[1])[None, None, :] <= positions[:, :, None]
    scores = jnp.where(seen[:, :, None, None, :], scores, -1e30)
    return jnp.einsum("bskgc,bckh->bskgh", jax.nn.softmax(scores, axis=-1),
                      values_g)


BLOCK_WALKS = {
    # id: (table width, page, rows' live lengths (0: an idle row), kv heads,
    #      pool dtype, quant, queries a row or None for decode)
    "w4-partly-dead-block": (4, 8, [13, 5, 32], 2, jnp.float32, "", None),
    "w8-dead-trailing-block": (8, 8, [19, 33, 64, 0], 2, jnp.float32, "", None),
    "w8-int8": (8, 8, [19, 33, 64, 0], 4, jnp.float32, "int8", None),
    "w8-bf16x8": (8, 8, [19, 33, 64, 0], 8, jnp.bfloat16, "", None),
    "w8-bf16x3-gather": (8, 8, [19, 33, 64, 0], 3, jnp.bfloat16, "", None),
    "w128": (128, 8, [1024, 8 * 37 + 3, 1], 2, jnp.float32, "", None),
    "w128-int8": (128, 8, [1024, 8 * 37 + 3, 1], 4, jnp.float32, "int8", None),
    "w6-blocks-of-3": (6, 8, [48, 25, 7], 2, jnp.float32, "", None),
    "w7-a-page-a-step": (7, 8, [56, 25, 7], 2, jnp.float32, "", None),
    "w2": (2, 8, [16, 3], 2, jnp.float32, "", None),
    "chunk-w4": (4, 8, [32, 13, 6], 2, jnp.float32, "", 6),
    "chunk-w8-dead-trailing-block": (8, 8, [64, 30, 17], 2, jnp.float32, "", 16),
    "chunk-w8-int8": (8, 8, [64, 30, 17], 4, jnp.float32, "int8", 16),
    "chunk-w8-bf16x8": (8, 8, [64, 30, 17], 8, jnp.bfloat16, "", 16),
    "chunk-w128-two-row-blocks": (128, 8, [1024, 500, 300], 2, jnp.float32,
                                  "", 256),
    "chunk-w6-blocks-of-3": (6, 8, [48, 25, 9], 2, jnp.float32, "", 8),
}


@pytest.mark.parametrize("case", sorted(BLOCK_WALKS))
def test_block_walk_matches_gather_reference(case):
    from mcp_context_forge_tpu.tpu_local.ops import paged_attention as paged

    width, page, lens, n_kv, dtype, quant, chunk = BLOCK_WALKS[case]
    G, hd, B = 2, 16, len(lens)
    kv = _random_state(n_kv, hd, page, B, width, dtype, quant,
                       jax.random.PRNGKey(len(case)))
    S = chunk or 1
    lens = np.asarray(lens)
    # the last S positions of each row's live context (fewer: padding)
    positions = lens[:, None] - S + np.arange(S)[None, :]
    positions = jnp.asarray(np.where(positions >= 0, positions, -1), jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(5), (B, S, n_kv, G, hd))
    ref = _masked_softmax_reference(q, kv, 1, positions)
    if chunk is None:
        out = paged.paged_decode_attention_pallas(
            q[:, 0], kv.k_pages, kv.v_pages, kv.block_tables,
            jnp.asarray(lens, jnp.int32), layer=1, interpret=True,
            k_scales=kv.k_scales, v_scales=kv.v_scales)[:, None]
        idle = np.asarray(out)[lens == 0]
        assert not idle.any()                       # an idle row: zeros
    else:
        out = paged.paged_chunk_attention_pallas(
            q, kv.k_pages, kv.v_pages, kv.block_tables, positions, layer=1,
            interpret=True, k_scales=kv.k_scales, v_scales=kv.v_scales)
    assert np.isfinite(np.asarray(out)).all()
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    valid = np.asarray(positions >= 0)
    np.testing.assert_allclose(np.asarray(out)[valid], np.asarray(ref)[valid],
                               rtol=tol, atol=tol)


def test_kv_block_is_a_divisor_of_any_table_width():
    from mcp_context_forge_tpu.tpu_local.ops import paged_attention as paged

    for width in range(1, 130):
        for rows in (4, 32, 256):
            n = paged._kv_block_pages(width, rows, 128)
            assert 1 <= n <= paged._BLOCK_PAGES and width % n == 0
    assert [paged._kv_block_pages(w, 256, 128)
            for w in (4, 8, 16, 32, 64, 128, 2, 3, 6)] == [4] * 6 + [2, 3, 3]


@pytest.mark.parametrize("n", [1, 3, 4, 8])
def test_a_slot_the_row_does_not_reach_keeps_what_it_fetched(n):
    """Slot ``i`` of block ``j`` names table entry j * n + i while the row
    block reaches that page; where it does not (a dead page inside a live
    block, a dead block, an idle row) it names what the slot named a grid
    step before, so the pipeline fetches nothing. Both kernels keep their own
    copy of the rule."""
    from mcp_context_forge_tpu.tpu_local.ops import mla_attention as mla
    from mcp_context_forge_tpu.tpu_local.ops import paged_attention as paged

    width, page_size = 2 * n if n > 1 else 3, 8
    # rows x row blocks: full, ends inside its first block, idle, one token
    max_pos = np.asarray([[width * page_size - 1, 5 * page_size // 2],
                          [-1, -1], [0, page_size * (width - 1)]])
    tables = 100 + np.arange(3 * width).reshape(3, width)
    want, held = [], list(tables[0, :n])
    for b in range(3):
        for r in range(2):
            for page in range(width):
                if page <= max_pos[b, r] // page_size:
                    held[page % n] = tables[b, page]
                want.append(held[page % n])
    for module in (paged, mla):
        got = module._block_entries(jnp.asarray(tables, jnp.int32),
                                    jnp.asarray(max_pos, jnp.int32), n,
                                    page_size)
        assert got.shape == (6, width)
        assert np.asarray(got).reshape(-1).tolist() == want
