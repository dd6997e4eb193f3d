"""Paged attention over the KV pool (Pallas).

Replaces the gather-based attention (`models/llama.py:
_paged_decode_attention` / `_history_attention` + `kv/paged_cache.py:
gather_kv`) on TPU: instead of materializing each slot's whole context
([B, C, KV, hd] per layer) in HBM, the kernel walks the block table
page-by-page — the page index is scalar-prefetched so Pallas can DMA
exactly the pages a sequence uses from HBM into VMEM — maintaining
online-softmax stats in VMEM scratch. HBM traffic drops from
O(B·C_max·hd) copies to the pages actually referenced.

One kernel body serves both entry points: decode is the chunk kernel with
one query position per sequence (``seq_len - 1``).

The kernel takes the WHOLE pool ``[L, num_pages, page, KV, hd]`` plus a
static layer index that rides the BlockSpec index map: a per-layer slice
outside the kernel would make XLA copy that layer's pool before every call.
A K/V block is one page with ALL its kv heads (the TPU lowering wants the
last two block dims to equal the array's), and the head loop runs inside
the kernel.

Reading ONE head out of that block is the kernel's inner cost. In the
pool's layout the kv head is the sublane dim (a token's heads are one
tile), so ``k_ref[:, h, :]`` is one sublane out of each of ``page`` tiles:
Mosaic realises it as ~18,000 load / unpack / rotate / select / pack
instructions a page (bf16, 8 heads). ``_heads`` reads the same bytes
as sublane-STRIDED loads of 32-bit words instead — the page viewed as
``[page * KV / packing, hd]`` words, every ``KV / packing``-th row — and
splits a word into its 2 (bf16) or 4 (int8) heads with shifts: a tenth of
the instructions, and the page step is bound by its DMA (PERF.md, PR 25).
The pool's layout, its writers, its sharding and the page payload that
leaves the device are what they were.

Int8 pages (kv/paged_cache.py quant mode) dequantize IN VMEM: the
per-(page, kv-head) scales arrive in blocks of ``_SCALE_ROWS`` pages
indexed by the same block-table entry, and apply to the f32 scores and
the f32 P·V product (one scale per (page, head), so it factors out of
both matmuls) — the HBM side of attention moves 1 byte/element.

Grid: (batch, query-row block, page). Scalar prefetch: block tables
[B, P] and the highest query position per (batch, row block).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from .attention import on_model_axis

NEG_INF = -1e30
# pages per scale block: a multiple of every dtype's sublane tile (f32 8,
# bf16 16), so the [rows, KV] block is legal whatever the scale dtype
_SCALE_ROWS = 32
# query rows (positions x group) one grid step holds: bounds the f32
# accumulator scratch at KV * _ROW_BLOCK * hd * 4 bytes
_ROW_BLOCK = 256


def _heads(ref, out_dtype):
    """Yield ``[page, hd]`` of each kv head in turn, as ``out_dtype``, from
    a page block ``ref`` [1, 1, page, KV, hd].

    Heads that fill whole 32-bit words (f32; bf16 with an even head count;
    int8 with a multiple of 4) are read as strided word loads, see the
    module docstring. Mosaic has no strided load of narrower types, so any
    other geometry takes the per-token sublane gather."""
    _, _, page, n_kv, hd = ref.shape
    packing = 4 // ref.dtype.itemsize
    if ref.dtype not in (jnp.float32, jnp.bfloat16, jnp.int8) \
            or n_kv % packing:
        for h in range(n_kv):
            yield ref[0, 0, :, h, :].astype(out_dtype)
        return
    words = ref if packing == 1 else ref.bitcast(jnp.int32)
    stride = n_kv // packing            # words a token
    words = words.reshape(page * stride, hd)
    bits = 32 // packing
    for word in range(stride):
        x = words[pl.ds(word, page, stride=stride), :]
        for sub in range(packing):
            if packing == 1:
                head = x
            elif ref.dtype == jnp.bfloat16:  # the high half of an f32
                head = pltpu.bitcast((x >> (bits * sub)) << bits, jnp.float32)
            else:                            # int8: sign-extend byte ``sub``
                head = (x << (32 - bits * (sub + 1))) >> (32 - bits)
            yield head.astype(out_dtype)


def _kernel(tables_ref, max_pos_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
            page_size: int, quantized: bool):
    """Refs: pos [R, 1] int32 (absolute position of each query row, -1 =
    padding); q/o [KV, R, hd]; k/v [1, 1, page, KV, hd]; scales
    [_SCALE_ROWS, KV]; scratch acc [KV, R, hd], m/l [KV, R, 1] f32."""
    if quantized:
        k_scale_ref, v_scale_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b, r, page_idx = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_kv, n_rows, hd = q_ref.shape

    @pl.when(page_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    page_start = page_idx * page_size

    # the page holds live context iff some query position reaches it
    @pl.when(max_pos_ref[b, r] >= page_start)
    def _process():
        col = page_start + jax.lax.broadcasted_iota(
            jnp.int32, (n_rows, page_size), 1)
        live = col <= pos_ref[...]                    # causal, on position
        if quantized:
            # this page's row of the scale block, as a masked reduce: a
            # dynamic sublane slice of a packed 16-bit tile does not lower
            row = tables_ref[b, page_idx] % _SCALE_ROWS
            pick = jax.lax.broadcasted_iota(
                jnp.int32, k_scale_ref.shape, 0) == row

            def page_scale(ref):
                return jnp.sum(jnp.where(pick, ref[...].astype(jnp.float32),
                                         0.0), axis=0, keepdims=True)
            k_scale, v_scale = page_scale(k_scale_ref), page_scale(v_scale_ref)
        for h, (k, v) in enumerate(zip(_heads(k_ref, q_ref.dtype),
                                       _heads(v_ref, q_ref.dtype))):
            q = q_ref[h]                              # [R, hd]; k, v [page, hd]
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) / math.sqrt(hd)
            if quantized:
                scores = scores * k_scale[:, h:h + 1]
            scores = jnp.where(live, scores, NEG_INF)     # [R, page]
            m_prev = m_ref[h]                             # [R, 1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(scores, axis=1, keepdims=True))
            correction = jnp.exp(m_prev - m_new)
            probs = jnp.exp(scores - m_new)
            l_ref[h] = (l_ref[h] * correction
                        + jnp.sum(probs, axis=1, keepdims=True))
            pv = jnp.dot(probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
            if quantized:
                pv = pv * v_scale[:, h:h + 1]
            acc_ref[h] = acc_ref[h] * correction + pv
            m_ref[h] = m_new

    @pl.when(page_idx == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_attention(q, row_pos, k_pages, v_pages, block_tables, layer,
                     k_scales, v_scales, interpret):
    """q: [B, KV, R, hd] (R query rows per kv head); row_pos: [B, R] int32
    absolute position of each row (-1 = padding) -> [B, KV, R, hd]."""
    B, KV, R, hd = q.shape
    n_pages = block_tables.shape[1]
    page_size = k_pages.shape[2]
    quantized = k_scales is not None
    rows = min(R, _ROW_BLOCK)
    if R % rows:
        raise ValueError(f"query rows {R} must divide into blocks of {rows}")
    n_blocks = R // rows

    def page_map(b, r, j, tables, max_pos):
        return (layer, tables[b, j], 0, 0, 0)

    def row_map(b, r, j, tables, max_pos):
        return (b, 0, r, 0)

    in_specs = [
        pl.BlockSpec((None, rows, 1), lambda b, r, j, t, m: (b, r, 0)),
        pl.BlockSpec((None, KV, rows, hd), row_map),
        # not squeezed: a ref with squeezed dims cannot be bitcast
        pl.BlockSpec((1, 1, page_size, KV, hd), page_map),
        pl.BlockSpec((1, 1, page_size, KV, hd), page_map),
    ]
    inputs = [row_pos[:, :, None], q, k_pages, v_pages]
    if quantized:
        scale_spec = pl.BlockSpec(
            (None, _SCALE_ROWS, KV),
            lambda b, r, j, t, m: (layer, t[b, j] // _SCALE_ROWS, 0))
        in_specs += [scale_spec, scale_spec]
        inputs += [k_scales, v_scales]
    max_pos = jnp.max(row_pos.reshape(B, n_blocks, rows), axis=2)
    return pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_blocks, n_pages),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, KV, rows, hd), row_map),
            scratch_shapes=[
                pltpu.VMEM((KV, rows, hd), jnp.float32),
                pltpu.VMEM((KV, rows, 1), jnp.float32),
                pltpu.VMEM((KV, rows, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, R, hd), q.dtype),
        name="paged_attention",
        interpret=interpret,
    )(block_tables, max_pos, *inputs)


_POOL_SPEC = P(None, None, None, "model", None)   # kv_pages, per shard


def _shard_specs(q_spec: P, quantized: bool) -> tuple:
    """shard_map in_specs for (q, k_pages, v_pages, block_tables,
    positions-or-lens, k_scales, v_scales): everything with a kv-head dim
    splits over ``model`` like the pool (parallel/sharding.py)."""
    scales = P(None, None, "model") if quantized else None
    return (q_spec, _POOL_SPEC, _POOL_SPEC, P(), P(), scales, scales)


@functools.partial(jax.jit, static_argnames=("layer", "interpret", "mesh"))
def paged_chunk_attention_pallas(q, k_pages, v_pages, block_tables,
                                 q_positions, layer: int = 0,
                                 interpret: bool = False,
                                 k_scales=None, v_scales=None, mesh=None):
    """Chunk (multi-query) attention: S queries per sequence walk the page
    list; causality rides the absolute query positions (cache position c
    attends iff c <= q_pos). Serves the prefix-cache suffix prefill, chunked
    prefill and the spec-decode verify step.

    q: [B, S, KV, G, hd]; k_pages/v_pages: [L, num_pages, page, KV, hd];
    block_tables: [B, P] int32; q_positions: [B, S] int32 absolute
    positions (-1 = padding); k_scales/v_scales: [L, num_pages, KV] dequant
    scales for int8 pages (None = full-precision pages); ``mesh``: the
    engine's mesh when the pool is sharded over its ``model`` axis
    -> [B, S, KV, G, hd]."""
    def per_shard(q, k_pages, v_pages, block_tables, q_positions,
                  k_scales, v_scales):
        B, S, KV, G, hd = q.shape
        # head-major rows: the kernel reads one [S*G, hd] matrix per kv head
        rows = q.transpose(0, 2, 1, 3, 4).reshape(B, KV, S * G, hd)
        out = _paged_attention(rows, jnp.repeat(q_positions, G, axis=1),
                               k_pages, v_pages, block_tables, layer,
                               k_scales, v_scales, interpret)
        return out.reshape(B, KV, S, G, hd).transpose(0, 2, 1, 3, 4)

    q_spec = P(None, None, "model", None, None)
    return on_model_axis(
        per_shard, mesh, _shard_specs(q_spec, k_scales is not None), q_spec)(
            q, k_pages, v_pages, block_tables, q_positions, k_scales, v_scales)


@functools.partial(jax.jit, static_argnames=("layer", "interpret", "mesh"))
def paged_decode_attention_pallas(q, k_pages, v_pages, block_tables, seq_lens,
                                  layer: int = 0, interpret: bool = False,
                                  k_scales=None, v_scales=None, mesh=None):
    """One query token per sequence, attending its first ``seq_len`` cache
    positions (0 = inactive row, output zeros).

    q: [B, KV, G, hd]; k_pages/v_pages: [L, num_pages, page, KV, hd];
    block_tables: [B, P] int32; seq_lens: [B] int32; k_scales/v_scales:
    [L, num_pages, KV] (None = full precision); ``mesh`` as in
    :func:`paged_chunk_attention_pallas` -> [B, KV, G, hd]."""
    def per_shard(q, k_pages, v_pages, block_tables, seq_lens,
                  k_scales, v_scales):
        row_pos = jnp.broadcast_to(seq_lens[:, None] - 1,
                                   (q.shape[0], q.shape[2]))
        return _paged_attention(q, row_pos, k_pages, v_pages, block_tables,
                                layer, k_scales, v_scales, interpret)

    q_spec = P(None, "model", None, None)
    return on_model_axis(
        per_shard, mesh, _shard_specs(q_spec, k_scales is not None), q_spec)(
            q, k_pages, v_pages, block_tables, seq_lens, k_scales, v_scales)
