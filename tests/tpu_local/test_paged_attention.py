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
# pool's dtype and head count (ops/paged_attention._heads): strided
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


@pytest.mark.parametrize("KV,pool_dtype", [(4, jnp.float32),
                                           (8, jnp.bfloat16)],
                         ids=["f32-1head", "bf16-2heads"])
def test_kernels_per_model_shard_match_unsharded(KV, pool_dtype):
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
    L, N, page, G, hd, B, per_slot, S = 2, 9, 8, 2, 16, 2, 4, 8
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

    positions = jnp.stack([16 + jnp.arange(S), jnp.arange(S)]).astype(jnp.int32)
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
