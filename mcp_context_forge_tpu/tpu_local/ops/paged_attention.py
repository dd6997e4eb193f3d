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
A pool block is one page with ALL its kv heads (the TPU lowering wants the
last two block dims to equal the array's), and the head loop runs inside
the kernel.

A grid step holds a KV BLOCK of N consecutive block-table pages (N * page
tokens): each pool is passed N times, slot ``i`` fetching the row's table
entry ``j * N + i``, so Pallas keeps its one-block-ahead pipeline, 2 N DMAs
deep. The step scores the whole block a kv head and makes ONE online-softmax
update: the lane-sparse ``[R, 1]`` ``m`` / ``l`` stats and the accumulator's
correction are paid once a block, not once a page. A slot the row block
does not reach (a page past its last position inside a live block, a dead
block, an idle row) costs no DMA: it keeps the page it last fetched
(``_block_entries``; an unchanged block index fetches nothing), and what it
then holds is masked by position. N comes from what the call can see
(``_kv_block_pages``): as many pages as keep a head's f32 score tile
``[rows, N * page]`` within ``_SCORE_TILE`` entries, at most
``_BLOCK_PAGES``, and the largest such number that divides the block-table
width (any width is served; a prime one a page a step). More DMAs in flight
do not make a page's DMA faster (470 of 819 GB/s either way: PERF.md, PR
30); what a block saves is the bookkeeping and the dead grid steps.

Reading ONE head out of that block is the kernel's inner cost. In the
pool's layout the kv head is the sublane dim (a token's heads are one
tile), so ``k_ref[:, h, :]`` is one sublane out of each of ``page`` tiles:
Mosaic realises it as ~18,000 load / unpack / rotate / select / pack
instructions a page (bf16, 8 heads). ``_word_heads`` reads the same bytes
as sublane-STRIDED loads of 32-bit words instead — the page viewed as
``[page * KV / packing, hd]`` words, every ``KV / packing``-th row — and
splits a word into its 2 (bf16) or 4 (int8) heads with shifts: a tenth of
the instructions, and the page step is bound by its DMA (PERF.md, PR 25).
The loop over a token's words is a ``fori_loop`` that the lowering unrolls:
its body is traced once, not once a head, and a step program traces 32 of
these kernels on every start (PERF.md, PR 30).
The pool's layout, its writers, its sharding and the page payload that
leaves the device are what they were.

Int8 pages (kv/paged_cache.py quant mode) dequantize IN VMEM: the
per-(page, kv-head) scales arrive in blocks of ``_SCALE_ROWS`` pages
indexed by the same block-table entry (a scale block a slot), and apply to
the f32 scores and the f32 probabilities of the page's columns (one scale
per (page, head), so it factors out of both matmuls) — the HBM side of
attention moves 1 byte/element.

A WINDOW (both entry points' static ``window``; absent: the body and the
program above, unchanged) bounds what a query sees from below as well: key
``c`` is visible iff ``q_pos - window < c <= q_pos``. The mask gains that
comparison, and a KV block wholly behind ``lowest query position - window``
of its row block is as dead as one past the highest: it is not processed and
its slots fetch nothing (``_block_entries``: the first live page a row block
from the scalar-prefetched positions). A decode step at context 16k with a
window of 2048 touches 17 pages a row, not 125.

Grid: (batch, query-row block, KV block). Scalar prefetch: the pool page of
every slot of every block per (batch, row block), the highest query position
per (batch, row block) and, under a window, the lowest.
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
# pages a grid step holds (a KV block): as many as keep one head's f32 score
# tile [rows, pages * page] at this many entries, never more than
# _BLOCK_PAGES
_SCORE_TILE = 256 * 512
_BLOCK_PAGES = 4
# kv heads x pages a grid step holds: the head loop is unrolled, and every
# head's [pages * page, hd] operands of a block are live on the kernel's
# VMEM stack, so a pool of many kv heads takes fewer pages a step (8 heads:
# all 4; the hybrid family's 32: 1, where 4 overran the scoped VMEM at compile)
_BLOCK_HEAD_PAGES = 32


def _word_packed(ref) -> bool:
    """Whether the kv heads of a page block fill whole 32-bit words (f32;
    bf16 with an even head count; int8 with a multiple of 4): they are then
    read as strided word loads, see the module docstring. Mosaic has no
    strided load of narrower types, so any other geometry takes the
    per-token sublane gather."""
    packing = 4 // ref.dtype.itemsize
    return (ref.dtype in (jnp.float32, jnp.bfloat16, jnp.int8)
            and ref.shape[3] % packing == 0)


def _word_heads(refs, word, out_dtype):
    """The ``4 // itemsize`` kv heads that share 32-bit word ``word`` of a
    token (a traced index), each ``[N * page, hd]`` as ``out_dtype``, from
    the N page blocks ``refs`` [1, 1, page, KV, hd] of a KV block, in table
    order."""
    _, _, page, n_kv, hd = refs[0].shape
    dtype = refs[0].dtype
    packing = 4 // dtype.itemsize
    stride = n_kv // packing            # words a token
    x = jnp.concatenate([
        (ref if packing == 1 else ref.bitcast(jnp.int32))
        .reshape(page * stride, hd)[pl.ds(word, page, stride=stride), :]
        for ref in refs])
    bits = 32 // packing
    for sub in range(packing):
        if packing == 1:
            head = x
        elif dtype == jnp.bfloat16:      # the high half of an f32
            head = pltpu.bitcast((x >> (bits * sub)) << bits, jnp.float32)
        else:                            # int8: sign-extend byte ``sub``
            head = (x << (32 - bits * (sub + 1))) >> (32 - bits)
        yield head.astype(out_dtype)


def _kv_block_pages(n_pages: int, rows: int, page_size: int,
                    n_kv: int = 1) -> int:
    """Pages a grid step holds: see the module docstring."""
    want = max(1, min(_BLOCK_PAGES, _SCORE_TILE // (rows * page_size),
                      _BLOCK_HEAD_PAGES // n_kv))
    return max(n for n in range(1, want + 1) if n_pages % n == 0)


def _first_visible(min_pos, window: int):
    """The lowest key position a row block's queries see: its lowest query
    position's window (0 until a window has filled)."""
    return jnp.maximum(min_pos - (window - 1), 0)


def _block_entries(block_tables, max_pos, n: int, page_size: int,
                   min_pos=None, window: int | None = None):
    """The pool page every slot of every KV block fetches: block_tables
    [B, P], max_pos [B, row blocks] -> [B * row blocks, P] int32. Entry
    ``j * n + i`` (slot ``i`` of block ``j``) is the row's table entry while
    the row block reaches that page; a slot it does not reach (a dead page
    inside a live block, a dead block, an idle row) keeps the entry the slot
    last fetched, in the order the grid walks, so it fetches nothing. Worked
    out here and not in the index maps: a map is traced and lowered once a
    pool operand a layer, and cannot see what its slot held a step before.
    Under a ``window`` the pages behind the first one the row block's lowest
    query position (``min_pos``) still sees are not reached either."""
    B, P = block_tables.shape
    row_blocks = max_pos.shape[1]
    live = (jnp.arange(P, dtype=jnp.int32)
            <= (max_pos // page_size)[..., None])
    if window is not None:
        live &= (jnp.arange(P, dtype=jnp.int32) >= (
            _first_visible(min_pos, window) // page_size)[..., None])
    live = live.reshape(-1, n)
    entries = jnp.broadcast_to(
        block_tables[:, None, :], (B, row_blocks, P)).reshape(-1, n)
    step = jnp.arange(live.shape[0], dtype=jnp.int32)[:, None]
    fetched = jax.lax.cummax(jnp.where(live, step, 0), axis=0)
    return jnp.take_along_axis(entries, fetched, axis=0).reshape(-1, P)


def _kernel(entries_ref, max_pos_ref, *rest,
            page_size: int, n: int, quantized: bool,
            window: int | None = None):
    """Refs (under a ``window`` the lowest query positions ``min_pos`` come
    third, as scalar prefetch): pos [R, 1] int32 (absolute position of each
    query row, -1 = padding); q/o [KV, R, hd]; N k then N v refs [1, 1, page,
    KV, hd], the block's pages in table order; N k then N v scale refs [_SCALE_ROWS, KV];
    scratch acc [KV, R, hd], m/l [KV, R, 1] f32."""
    if window is not None:
        min_pos_ref, *rest = rest
    pos_ref, q_ref, *rest = rest
    k_refs, v_refs, rest = rest[:n], rest[n:2 * n], rest[2 * n:]
    if quantized:
        k_scale_refs, v_scale_refs, rest = rest[:n], rest[n:2 * n], rest[2 * n:]
    o_ref, acc_ref, m_ref, l_ref = rest
    b, r, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_kv, n_rows, hd = q_ref.shape
    block = n * page_size

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # the block holds live context iff some query position reaches it (and,
    # under a window, the lowest one's window still does)
    reached = max_pos_ref[b, r] >= j * block
    if window is not None:
        reached &= (j + 1) * block > _first_visible(min_pos_ref[b, r], window)

    @pl.when(reached)
    def _process():
        col = j * block + jax.lax.broadcasted_iota(
            jnp.int32, (n_rows, block), 1)
        live = col <= pos_ref[...]                    # causal, on position
        if window is not None:
            live &= col > pos_ref[...] - window
        if quantized:
            slot_of = jax.lax.broadcasted_iota(
                jnp.int32, (1, block), 1) // page_size
            kv_lane = jax.lax.broadcasted_iota(jnp.int32, (1, n_kv), 1)

            def page_scales(refs):
                """A ``[1, KV]`` row a slot: its page's scales."""
                rows = []
                for slot, ref in enumerate(refs):
                    # the slot's row of its scale block, as a masked reduce:
                    # a dynamic sublane slice of a packed 16-bit tile does
                    # not lower
                    row = entries_ref[b * pl.num_programs(1) + r,
                                      j * n + slot] % _SCALE_ROWS
                    pick = jax.lax.broadcasted_iota(
                        jnp.int32, ref.shape, 0) == row
                    rows.append(jnp.sum(jnp.where(
                        pick, ref[...].astype(jnp.float32), 0.0),
                        axis=0, keepdims=True))
                return rows

            def column_scale(rows, h):
                """Head ``h``'s scale of each column's page, [1, block]."""
                at = [jnp.sum(jnp.where(kv_lane == h, row, 0.0), axis=1,
                              keepdims=True) for row in rows]
                out = at[0]
                for slot in range(1, n):
                    out = jnp.where(slot_of >= slot, at[slot], out)
                return out
            k_scales, v_scales = (page_scales(k_scale_refs),
                                  page_scales(v_scale_refs))

        def attend(h, k, v):
            """One online-softmax update of kv head ``h`` (may be traced)
            over the block: k, v [block, hd]."""
            scores = jax.lax.dot_general(
                q_ref[h], k, (((1,), (1,)), ((), ())),    # q_ref[h]: [R, hd]
                preferred_element_type=jnp.float32) * (1.0 / math.sqrt(hd))
            if quantized:
                scores = scores * column_scale(k_scales, h)
            scores = jnp.where(live, scores, NEG_INF)     # [R, block]
            m_prev = m_ref[h]                             # [R, 1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(scores, axis=1, keepdims=True))
            correction = jnp.exp(m_prev - m_new)
            probs = jnp.exp(scores - m_new)
            l_ref[h] = (l_ref[h] * correction
                        + jnp.sum(probs, axis=1, keepdims=True))
            if quantized:
                probs = probs * column_scale(v_scales, h)
            acc_ref[h] = acc_ref[h] * correction + jnp.dot(
                probs.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

        if _word_packed(k_refs[0]):
            # a loop over a token's words that the lowering unrolls (static
            # word indices, as scheduled as a Python loop over heads) but
            # that is TRACED once: 32 kernels a step program are set-up time
            packing = 4 // k_refs[0].dtype.itemsize

            def word_step(word, carry):
                for sub, (k, v) in enumerate(zip(
                        _word_heads(k_refs, word, q_ref.dtype),
                        _word_heads(v_refs, word, q_ref.dtype))):
                    attend(word * packing + sub, k, v)
                return carry
            jax.lax.fori_loop(0, n_kv // packing, word_step, 0, unroll=True)
        else:
            for h in range(n_kv):
                attend(h, *(jnp.concatenate(
                    [ref[0, 0, :, h, :] for ref in refs]).astype(q_ref.dtype)
                    for refs in (k_refs, v_refs)))

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_attention(q, row_pos, k_pages, v_pages, block_tables, layer,
                     k_scales, v_scales, interpret, window=None):
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
    n = _kv_block_pages(n_pages, rows, page_size, KV)

    def slot_entry(slot, b, r, j, entries, *_):
        return entries[b * n_blocks + r, j * n + slot]

    def page_map(slot):
        return lambda *at: (layer, slot_entry(slot, *at), 0, 0, 0)

    def scale_map(slot):
        return lambda *at: (layer, slot_entry(slot, *at) // _SCALE_ROWS, 0)

    def row_map(b, r, j, *_):
        return (b, 0, r, 0)

    # not squeezed: a ref with squeezed dims cannot be bitcast
    page_specs = [pl.BlockSpec((1, 1, page_size, KV, hd), page_map(slot))
                  for slot in range(n)]
    in_specs = [
        pl.BlockSpec((None, rows, 1), lambda b, r, j, *_: (b, r, 0)),
        pl.BlockSpec((None, KV, rows, hd), row_map),
        *page_specs, *page_specs,
    ]
    inputs = [row_pos[:, :, None], q, *[k_pages] * n, *[v_pages] * n]
    if quantized:
        in_specs += 2 * [pl.BlockSpec((None, _SCALE_ROWS, KV), scale_map(slot))
                         for slot in range(n)]
        inputs += [*[k_scales] * n, *[v_scales] * n]
    max_pos = jnp.max(row_pos.reshape(B, n_blocks, rows), axis=2)
    bounds, kernel = (max_pos,), functools.partial(
        _kernel, page_size=page_size, n=n, quantized=quantized)
    if window is not None:
        # the lowest REAL query position a row block (padding is -1; a block
        # of padding alone is dead by max_pos)
        real = jnp.where(row_pos >= 0, row_pos, jnp.iinfo(jnp.int32).max)
        bounds += (jnp.min(real.reshape(B, n_blocks, rows), axis=2),)
        kernel = functools.partial(kernel, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 + len(bounds),
            grid=(B, n_blocks, n_pages // n),
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
    )(_block_entries(block_tables, max_pos, n, page_size, *bounds[1:],
                     window=window), *bounds, *inputs)


_POOL_SPEC = P(None, None, None, "model", None)   # kv_pages, per shard


def _shard_specs(q_spec: P, quantized: bool) -> tuple:
    """shard_map in_specs for (q, k_pages, v_pages, block_tables,
    positions-or-lens, k_scales, v_scales): everything with a kv-head dim
    splits over ``model`` like the pool (parallel/sharding.py)."""
    scales = P(None, None, "model") if quantized else None
    return (q_spec, _POOL_SPEC, _POOL_SPEC, P(), P(), scales, scales)


@functools.partial(jax.jit, static_argnames=("layer", "interpret", "mesh",
                                             "window"))
def paged_chunk_attention_pallas(q, k_pages, v_pages, block_tables,
                                 q_positions, layer: int = 0,
                                 interpret: bool = False,
                                 k_scales=None, v_scales=None, mesh=None,
                                 window: int | None = None):
    """Chunk (multi-query) attention: S queries per sequence walk the page
    list; causality rides the absolute query positions (cache position c
    attends iff c <= q_pos). Serves the prefix-cache suffix prefill, chunked
    prefill and the spec-decode verify step.

    q: [B, S, KV, G, hd]; k_pages/v_pages: [L, num_pages, page, KV, hd];
    block_tables: [B, P] int32; q_positions: [B, S] int32 absolute
    positions (-1 = padding); k_scales/v_scales: [L, num_pages, KV] dequant
    scales for int8 pages (None = full-precision pages); ``mesh``: the
    engine's mesh when the pool is sharded over its ``model`` axis;
    ``window``: a query also sees no key at or below ``q_pos - window``
    (module docstring) -> [B, S, KV, G, hd]."""
    def per_shard(q, k_pages, v_pages, block_tables, q_positions,
                  k_scales, v_scales):
        B, S, KV, G, hd = q.shape
        # head-major rows: the kernel reads one [S*G, hd] matrix per kv head
        rows = q.transpose(0, 2, 1, 3, 4).reshape(B, KV, S * G, hd)
        out = _paged_attention(rows, jnp.repeat(q_positions, G, axis=1),
                               k_pages, v_pages, block_tables, layer,
                               k_scales, v_scales, interpret, window)
        return out.reshape(B, KV, S, G, hd).transpose(0, 2, 1, 3, 4)

    q_spec = P(None, None, "model", None, None)
    return on_model_axis(
        per_shard, mesh, _shard_specs(q_spec, k_scales is not None), q_spec)(
            q, k_pages, v_pages, block_tables, q_positions, k_scales, v_scales)


@functools.partial(jax.jit, static_argnames=("layer", "interpret", "mesh",
                                             "window"))
def paged_decode_attention_pallas(q, k_pages, v_pages, block_tables, seq_lens,
                                  layer: int = 0, interpret: bool = False,
                                  k_scales=None, v_scales=None, mesh=None,
                                  window: int | None = None):
    """One query token per sequence, attending its first ``seq_len`` cache
    positions (0 = inactive row, output zeros).

    q: [B, KV, G, hd]; k_pages/v_pages: [L, num_pages, page, KV, hd];
    block_tables: [B, P] int32; seq_lens: [B] int32; k_scales/v_scales:
    [L, num_pages, KV] (None = full precision); ``mesh`` and ``window`` as
    in :func:`paged_chunk_attention_pallas` -> [B, KV, G, hd]."""
    def per_shard(q, k_pages, v_pages, block_tables, seq_lens,
                  k_scales, v_scales):
        row_pos = jnp.broadcast_to(seq_lens[:, None] - 1,
                                   (q.shape[0], q.shape[2]))
        return _paged_attention(q, row_pos, k_pages, v_pages, block_tables,
                                layer, k_scales, v_scales, interpret, window)

    q_spec = P(None, "model", None, None)
    return on_model_axis(
        per_shard, mesh, _shard_specs(q_spec, k_scales is not None), q_spec)(
            q, k_pages, v_pages, block_tables, seq_lens, k_scales, v_scales)
