"""Latent attention over a paged latent cache, and its learned sparse
selector (Pallas kernels + the jnp references they are held to).

The latent family (``models/deepseek.py``) caches ONE vector a token a layer,
``c || k_r`` (``kv_lora_rank + qk_rope_head_dim``), shared by all heads, and a
second per-token key for the selector. Three kernels serve a step:

``sparse_index_scores``
    ``I[t, s] = sum_j w[t, j] * relu(q_j[t] . k[s])`` for a chunk of queries
    against the row's index-key pages (block table scalar-prefetched, one page
    a grid step, pages past the chunk's last position skipped). The head loop
    runs inside the kernel on the transposed tile ``[page, queries]`` so that
    a head's weights are a sublane-broadcast row; the finished tile is
    transposed once on its way out. Entries a query may not see (a later
    position, padding) are ``NEG_INF``.
``sparse_select``
    The EXACT ``k``-th largest score of every row, by a 32-step bitwise search
    over the order-preserving integer image of the float32 scores (count the
    entries at or above a candidate, keep the bit if at least ``k`` are:
    ``ops/threshold_search.py``, which sampling's cuts share). The
    selected set of a row is then ``score >= threshold``: the top ``k``, ties
    at the threshold all kept (continuous scores tie with probability zero).
    No sort, no indices: what attention needs is the mask.
``mla_paged_attention``
    Softmax attention in the ABSORBED form over the latent pages themselves:
    queries are ``q_nope . W_uk^T || q_rope`` (so a score is one dot product
    with the cached vector), values are the first ``kv_lora_rank`` lanes of
    the same page, and a bias tile carries causality, validity and the
    selection (0 or ``NEG_INF``). It reads every page up to the row's last
    position and masks what was not selected: the same function as a gather of
    the selected tokens, at these contexts (<= 16k) on whole-page DMAs. What
    that spends against the selected set alone is the kernel's roofline share
    in the benchmark (``mla_*_attention_roofline``).

    A grid step holds a KV BLOCK of N consecutive block-table pages (N * page
    tokens): the pool is passed N times, slot ``i`` fetching the row's table
    entry ``j * N + i``, so Pallas keeps its one-block-ahead pipeline, N DMAs
    deep. The step scores the whole block, the block's heads as rows of one
    matmul against the vectors they share, and makes ONE online-softmax
    update: the ``[R, 1]`` ``m`` / ``l`` stats and the correction of the f32
    accumulator (four times a page's score tile) are paid once a block, not
    once a page. A slot the row block does not reach (a page past its last
    position inside a live block, a dead block, an idle row) costs no DMA: it
    keeps the page it last fetched (``_block_entries``; an unchanged block
    index fetches nothing), and what it then holds is masked by the bias,
    which is ``NEG_INF`` past every row's position. N comes from what the
    call can see (``_kv_block_pages``): as many pages as keep a head's f32
    score tile ``[rows, N * page]`` within ``_ATTN_SCORE_TILE`` entries, at
    most ``_ATTN_BLOCK_PAGES``, and the largest such number that divides the
    block-table width (any width is served; a prime one a page a step).

One body serves chunk rounds (a block is some heads x a tile of queries, the
bias a row a query), decode (a block is one query's heads, the bias one row
for all of them) and verify steps (``group_bias``: a block is the heads of
each of a row's K query positions, K groups of head rows, the bias one row a
POSITION: each draft position has its own visibility, its heads share it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .threshold_search import from_ordered_bits, kth_largest_key, ordered_bits

NEG_INF = -1e30
_VMEM_LIMIT_BYTES = 96 * 1024 * 1024
# queries a grid step of the index kernel scores against one page, and of the
# attention kernel attends with; heads an attention step holds beside them
_INDEX_QUERY_TILE = 256
_ATTN_QUERY_TILE = 256
_ATTN_HEAD_BLOCK = 8
# pages a grid step of the attention kernel holds (a KV block): as many as
# keep one head's f32 score tile [rows, pages * page] at this many entries, and
# never more than _ATTN_BLOCK_PAGES
_ATTN_SCORE_TILE = 256 * 512
_ATTN_BLOCK_PAGES = 8
# rows of scores one grid step of the selection holds whole ([rows, C] f32)
_SELECT_ROWS = 128


# ------------------------------------------------------------- jnp references

def index_scores_reference(q: jax.Array, w: jax.Array, keys: jax.Array,
                           positions: jax.Array) -> jax.Array:
    """q: [B, S, Hi, Di]; w: [B, S, Hi] (float32, scaled); keys: [B, C, Di]
    (cache position c of the row); positions: [B, S] absolute, -1 = padding
    -> [B, S, C] float32, NEG_INF where c > position."""
    dots = jnp.einsum("bshd,bcd->bshc", q, keys,
                      preferred_element_type=jnp.float32)
    scores = jnp.einsum("bshc,bsh->bsc", jax.nn.relu(dots),
                        w.astype(jnp.float32))
    cache_pos = jnp.arange(keys.shape[1])[None, None, :]
    return jnp.where(cache_pos <= positions[:, :, None], scores, NEG_INF)


def _kth_largest(scores: jax.Array, k: int) -> jax.Array:
    """scores: [R, C] float32 -> [R, 1]: the k-th largest of each row (the
    smallest when the row has fewer than k entries)."""
    keys = ordered_bits(scores)
    return from_ordered_bits(kth_largest_key(keys, min(k, keys.shape[-1])))


def topk_threshold_reference(scores: jax.Array, k: int) -> jax.Array:
    """scores: [..., C] float32 -> [..., 1]: the k-th largest of each row."""
    flat = scores.reshape(-1, scores.shape[-1])
    return _kth_largest(flat, k).reshape(*scores.shape[:-1], 1)


def mla_attention_reference(q: jax.Array, bias: jax.Array, latent: jax.Array,
                            value_dim: int) -> jax.Array:
    """q: [B, G, R, Dk]; bias: [B, Rb, C] float32 with Rb in (1, R);
    latent: [B, C, Dk] -> [B, G, R, value_dim] float32-accumulated."""
    scores = jnp.einsum("bgrd,bcd->bgrc", q, latent,
                        preferred_element_type=jnp.float32)
    scores = scores + bias[:, None]
    live = (bias > 0.5 * NEG_INF)[:, None]
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.where(live, jnp.exp(scores - m), 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bgrc,bcd->bgrd", (p / l).astype(latent.dtype),
                     latent[..., :value_dim],
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# ------------------------------------------------------------ index scores

def _index_kernel(tables_ref, max_pos_ref, pos_ref, q_ref, w_ref, k_ref,
                  o_ref, *, page_size: int):
    """Refs: pos [1, T] int32; q [Hi, T, Di]; w [Hi, T] f32; k [1, 1, page,
    Di]; o [T, page] f32."""
    b, t, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_heads, tile, _ = q_ref.shape
    page_start = j * page_size

    @pl.when(max_pos_ref[b, t] < page_start)
    def _dead():
        o_ref[...] = jnp.full_like(o_ref, NEG_INF)

    @pl.when(max_pos_ref[b, t] >= page_start)
    def _live():
        keys = k_ref[0, 0]                                 # [page, Di]

        def head(h, acc):
            dots = jax.lax.dot_general(                    # [page, T]
                keys, q_ref[h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            return acc + jnp.maximum(dots, 0.0) * w_ref[pl.ds(h, 1), :]

        acc = jax.lax.fori_loop(
            0, n_heads, head, jnp.zeros((page_size, tile), jnp.float32))
        col = page_start + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, tile), 0)
        acc = jnp.where(col <= pos_ref[...], acc, NEG_INF)
        o_ref[...] = acc.T


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def sparse_index_scores_pallas(q: jax.Array, w: jax.Array,
                               index_pages: jax.Array,
                               block_tables: jax.Array, positions: jax.Array,
                               layer: int = 0,
                               interpret: bool = False) -> jax.Array:
    """q: [B, S, Hi, Di]; w: [B, S, Hi] float32; index_pages: [L, N, page,
    Di]; block_tables: [B, P]; positions: [B, S] (-1 = padding)
    -> [B, S, P * page] float32 (NEG_INF where not visible)."""
    B, S, Hi, Di = q.shape
    n_pages = block_tables.shape[1]
    page_size = index_pages.shape[2]
    tile = min(S, _INDEX_QUERY_TILE)
    if S % tile:
        raise ValueError(f"{S} queries must divide into tiles of {tile}")
    n_tiles = S // tile
    qh = q.transpose(0, 2, 1, 3)                           # [B, Hi, S, Di]
    wh = w.astype(jnp.float32).transpose(0, 2, 1)          # [B, Hi, S]
    max_pos = jnp.max(positions.reshape(B, n_tiles, tile), axis=2)
    return pl.pallas_call(
        functools.partial(_index_kernel, page_size=page_size),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_tiles, n_pages),
            in_specs=[
                pl.BlockSpec((None, 1, tile), lambda b, t, j, tb, mp: (b, 0, t)),
                pl.BlockSpec((None, Hi, tile, Di),
                             lambda b, t, j, tb, mp: (b, 0, t, 0)),
                pl.BlockSpec((None, Hi, tile),
                             lambda b, t, j, tb, mp: (b, 0, t)),
                pl.BlockSpec((1, 1, page_size, Di),
                             lambda b, t, j, tb, mp: (layer, tb[b, j], 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, tile, page_size),
                                   lambda b, t, j, tb, mp: (b, t, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, S, n_pages * page_size),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="sparse_index_scores",
        interpret=interpret,
    )(block_tables, max_pos, positions[:, None, :], qh, wh, index_pages)


# ---------------------------------------------------------------- selection

def _select_kernel(s_ref, o_ref, *, k: int):
    """s [R, C] f32 -> o [R, 1] f32: each row's k-th largest."""
    o_ref[...] = _kth_largest(s_ref[...], k)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def sparse_select_pallas(scores: jax.Array, k: int,
                         interpret: bool = False) -> jax.Array:
    """scores: [R, C] float32 -> [R, 1]: the k-th largest of each row."""
    R, C = scores.shape
    rows = min(R, _SELECT_ROWS)
    if R % rows:
        raise ValueError(f"{R} rows must divide into blocks of {rows}")
    return pl.pallas_call(
        functools.partial(_select_kernel, k=k),
        grid=(R // rows,),
        in_specs=[pl.BlockSpec((rows, C), lambda r: (r, 0))],
        out_specs=pl.BlockSpec((rows, 1), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((R, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="sparse_select",
        interpret=interpret,
    )(scores)


# ---------------------------------------------------------------- attention

def _kv_block_pages(n_pages: int, rows: int, page_size: int) -> int:
    """Pages a grid step holds: see the module docstring."""
    want = max(1, min(_ATTN_BLOCK_PAGES,
                      _ATTN_SCORE_TILE // (rows * page_size)))
    return max(n for n in range(1, want + 1) if n_pages % n == 0)


def _block_entries(block_tables, max_pos, n: int, page_size: int):
    """The pool page every slot of every KV block fetches: block_tables
    [B, P], max_pos [B, row blocks] -> [B * row blocks, P] int32. Entry
    ``j * n + i`` (slot ``i`` of block ``j``) is the row's table entry while
    the row block reaches that page; a slot it does not reach (a dead page
    inside a live block, a dead block, an idle row) keeps the entry the slot
    last fetched, in the order the grid walks, so it fetches nothing."""
    B, P = block_tables.shape
    row_blocks = max_pos.shape[1]
    live = (jnp.arange(P, dtype=jnp.int32)
            <= (max_pos // page_size)[..., None]).reshape(-1, n)
    entries = jnp.broadcast_to(
        block_tables[:, None, :], (B, row_blocks, P)).reshape(-1, n)
    step = jnp.arange(live.shape[0], dtype=jnp.int32)[:, None]
    fetched = jax.lax.cummax(jnp.where(live, step, 0), axis=0)
    return jnp.take_along_axis(entries, fetched, axis=0).reshape(-1, P)


def _mla_kernel(entries_ref, max_pos_ref, q_ref, bias_ref, *rest,
                page_size: int, value_dim: int, group_bias: bool):
    """Refs: q/o [G, R, Dk] / [G, R, value_dim]; bias [Rb, N * page] f32 (Rb
    in (1, R), a row a query row, or with ``group_bias`` [G, N * page], a row
    a group); N kv refs [1, 1, page, Dk], the block's pages in table order;
    scratch acc [G, R, value_dim], m/l [G, R, 1] f32."""
    *kv_refs, o_ref, acc_ref, m_ref, l_ref = rest
    b, r, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    n_groups, n_rows, _ = q_ref.shape

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(max_pos_ref[b, r] >= j * len(kv_refs) * page_size)
    def _process():
        kv = jnp.concatenate([ref[0, 0] for ref in kv_refs])  # [N * page, Dk]
        block = kv.shape[0]
        # every head attends the same latent vectors: the block's heads are
        # rows of ONE matmul against them, and of one against the values
        scores = jax.lax.dot_general(                  # [G * R, N * page]
            q_ref[...].reshape(n_groups * n_rows, -1), kv,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        scores = scores.reshape(n_groups, n_rows, block) + (
            bias_ref[...][:, None, :] if group_bias else bias_ref[...][None])
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=2, keepdims=True))
        correction = jnp.exp(m_prev - m_new)
        # a block none of whose tokens a row selected leaves m at NEG_INF:
        # exp(0) there must not count, so the exponent's base stays above
        # every masked score (NEG_INF + a dot product) and they read 0
        probs = jnp.exp(scores - jnp.maximum(m_new, 0.5 * NEG_INF))
        l_ref[...] = (l_ref[...] * correction
                      + jnp.sum(probs, axis=2, keepdims=True))
        pv = jnp.dot(
            probs.astype(kv.dtype).reshape(n_groups * n_rows, block),
            kv[:, :value_dim], preferred_element_type=jnp.float32)
        acc_ref[...] = (acc_ref[...] * correction
                        + pv.reshape(n_groups, n_rows, value_dim))
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("layer", "value_dim",
                                             "interpret", "group_bias"))
def mla_paged_attention_pallas(q: jax.Array, bias: jax.Array,
                               latent_pages: jax.Array,
                               block_tables: jax.Array, max_pos: jax.Array,
                               layer: int = 0, value_dim: int = 512,
                               interpret: bool = False,
                               group_bias: bool = False) -> jax.Array:
    """q: [B, G, R, Dk] absorbed queries, scale folded in; bias: [B, Rb, P *
    page] float32, 0 where a row attends and NEG_INF elsewhere (so NEG_INF
    past the row's last position), Rb = R (a row each) or 1 (one row for all),
    or with ``group_bias`` [B, G, P * page]: one row a GROUP, shared by the
    group's R rows (a verify step: G query positions of R heads; G must fit
    one block, R one tile);
    latent_pages: [L, N, page, Dk]; block_tables: [B, P]; max_pos: [B, R //
    row tile] int32, the last position any row of the tile attends to (blocks
    past it are skipped, pages past it inside its block not fetched)
    -> [B, G, R, value_dim]."""
    B, G, R, Dk = q.shape
    n_pages = block_tables.shape[1]
    page_size = latent_pages.shape[2]
    rows = min(R, _ATTN_QUERY_TILE)
    groups = min(G, _ATTN_HEAD_BLOCK)
    if R % rows or G % groups:
        raise ValueError(f"[{G}, {R}] queries must divide into blocks of "
                         f"[{groups}, {rows}]")
    shared = bias.shape[1] == 1 and R > 1
    bias_rows = 1 if shared else rows
    if group_bias:
        if G != groups or R != rows or bias.shape[1] != G:
            raise ValueError(f"a bias row a group needs [{G}, {R}] queries in "
                             f"one block and {G} bias rows, got "
                             f"{bias.shape[1]}")
        shared, bias_rows = True, G
    n = _kv_block_pages(n_pages, rows, page_size)
    n_tiles = R // rows

    def page_map(slot):
        return lambda b, g, r, j, en, mp: (
            layer, en[b * n_tiles + r, j * n + slot], 0, 0)

    return pl.pallas_call(
        functools.partial(_mla_kernel, page_size=page_size,
                          value_dim=value_dim, group_bias=group_bias),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, G // groups, n_tiles, n_pages // n),
            in_specs=[
                pl.BlockSpec((None, groups, rows, Dk),
                             lambda b, g, r, j, tb, mp: (b, g, r, 0)),
                pl.BlockSpec((None, bias_rows, n * page_size),
                             (lambda b, g, r, j, tb, mp: (b, 0, j)) if shared
                             else (lambda b, g, r, j, tb, mp: (b, r, j))),
                *(pl.BlockSpec((1, 1, page_size, Dk), page_map(slot))
                  for slot in range(n)),
            ],
            out_specs=pl.BlockSpec((None, groups, rows, value_dim),
                                   lambda b, g, r, j, tb, mp: (b, g, r, 0)),
            scratch_shapes=[
                pltpu.VMEM((groups, rows, value_dim), jnp.float32),
                pltpu.VMEM((groups, rows, 1), jnp.float32),
                pltpu.VMEM((groups, rows, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, G, R, value_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="mla_paged_attention",
        interpret=interpret,
    )(_block_entries(block_tables, max_pos, n, page_size), max_pos, q, bias,
      *([latent_pages] * n))
