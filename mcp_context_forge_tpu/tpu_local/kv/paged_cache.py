"""Paged KV cache in HBM.

vLLM-style paging adapted to XLA's static-shape discipline (SURVEY.md §7.2
hard part #1): a fixed pool of pages [L, num_pages, page_size, KV, hd] lives
in HBM sharded over the ``model`` axis on the kv-head dim; a block table
[slots, max_pages_per_slot] maps decode slots to pages. Decode memory scales
with tokens-in-use, not slots × max-context. All writes are scatters and all
reads are gathers with static shapes, so one compiled decode program serves
every step.

Page 0 is reserved as the trash page: masked/padding writes land there.

What a page holds a token is the model family's to declare (``kv_pools``):
the GQA trunk keeps ``k`` and ``v`` of ``[KV, hd]`` (``PagedKVState``, below,
with every rule of this docstring); the latent family keeps ``latent``
(``c || k_r``) and, where the model has a selector, its ``index_key``, one
vector each (``LatentKVState``). Both ride the ONE block table and the ONE
``PageAllocator``, which deals in page ids and knows nothing of the pools.

A family DECLARES what a token's vector holds; the latent family's pools
STORE it padded with zeros to whole 128-lane tiles (``stored_width``: 576 ->
640, 128 -> 128), because that is what the chip's tiled layout occupies
anyway, and with the padding explicit the pool's parameter layout, the
scatter that writes it and the kernel that reads it agree: at 576 every
step program relayouted the WHOLE pool on its way in and again on its way
out. A new pool gets this by declaring its vector, not by asking: its writer
pads with ``lane_padded`` and its queries carry the same zero tail, so every
dot product is the same sum plus zeros.

A pool also declares WHICH layers hold it and whether it grows a token or is
a fixed size a sequence (``PoolSpec.layers`` / ``.per``). The hybrid family
(``HybridKVState``) keeps ``k`` and ``v`` pages in its full-attention layers
only, and in its linear-attention layers a ``"sequence"`` pool: the recurrent
``state`` ``[L_lin, rows, d_k, H * d_v]`` float32 and the convolution's
``conv_tail`` ``[L_lin, rows, taps, channels]``. A decode slot then owns a
state ROW as well as pages: the allocator deals row ids with the slot
(``state_rows = slots + 1``; row 0 is the trash row as page 0 is the trash
page), a request reaches its row by id
(``HybridKVState.state_rows``, uploaded with the block table), and nothing
copies a state. ``kv_page_bytes`` counts the per-token pools by their own
layer counts; ``kv_state_bytes`` the per-sequence ones.

The same tuple serves a family whose layers are WINDOW or FULL attention
(``models/afmoe.py``). A full layer holds every token of its sequence in
``k``/``v`` pages under the block table, as above. A window layer's query sees
its ``W`` newest keys and nothing older, so its K and V are per-sequence pools
too: a RING of ``ring_pages = ring_tokens / page`` pages a state row (the
tuple's two per-sequence fields, stored ``[L_window, rows * ring_pages + 1,
page, KV, hd]`` so that the writers and the paged kernel read them as pages;
page 0 the trash page). Position ``p`` lives in the row's ring page ``(p //
page) mod ring_pages``. The ring's block table is never uploaded: the step
program computes it from ``state_rows`` (``ring_tables``), and ``ring_view``
hands the writers and the kernels a ``PagedKVState`` over the rings. Positions
stay what they are and every mask stays a comparison of positions: an entry
older than the window that its page still holds, an entry of an earlier lap of
the ring or of the row's last tenant is dead by position (docs/adr/019). With
``ring_tokens >= W +`` the widest step that writes before it attends ``+`` a
page, no step overwrites a key one of its own queries still sees. The
``PageAllocator`` deals page ids for the full layers alone and learns nothing:
no pool has a retire policy and no second free list exists.

The layout is token-major on purpose: ``(KV, hd)`` are the two minor dims,
so one token's kv heads are one contiguous tile and a token write (decode,
prefill scatter) is one whole-tile update. Head-major pages
(``[.., KV, page, hd]``) would make a token 8 half-word row updates: on a
v5e a decode write of 32 tokens took 197 us a layer against 32 us (my chip
run, PR 25, PERF.md). The paged kernel pays nothing for this order: it
reads one head of a page as sublane-strided word loads
(``ops/paged_attention._heads``).

Int8 storage mode (``quant="int8"``): pages hold int8 values plus a
per-page, per-kv-head scale array [L, num_pages, KV], halving the pool's
HBM footprint and the per-step KV traffic. Scales are RUNNING MAXIMA over
a page's tenancy: a write at offset 0 begins a new tenancy and resets the
page scale (a freed/reallocated page must not inherit the old tenant's
range), later appends grow the scale monotonically and requantize the
page's resident values when it grows — so every live value always
dequantizes with the scale it was quantized under. The writers assume
each row's valid positions within one call form a CONTIGUOUS ascending
span (true for every engine path: prefill, chunked/suffix prefill,
decode, spec-verify), which is what makes the prior-content requantize
cheap: only the page under each row's first written token can hold
earlier tokens of that row.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..models.configs import (AfmoeConfig, DeepseekConfig,
                              GraniteHybridConfig, LlamaConfig,
                              OlmoHybridConfig, SolarOpen2Config)
from ..quantize import KV_SCALE_EPS, kv_dequantize, kv_int8_scale, kv_quantize


class PagedKVState(NamedTuple):
    """Device state (a pytree — every field is a jax array).

    ``k_scales``/``v_scales`` are None for full-precision pools; under
    int8 they hold the per-(layer, page, kv-head) dequant scales in the
    engine's COMPUTE dtype (the scale dtype doubles as the compute-dtype
    marker, mirroring quantize.py's weight-scale convention)."""

    k_pages: jax.Array      # [L, num_pages, page_size, KV, hd]
    v_pages: jax.Array      # [L, num_pages, page_size, KV, hd]
    block_tables: jax.Array  # [slots, max_pages_per_slot] int32 (0 = unassigned)
    k_scales: jax.Array | None = None   # [L, num_pages, KV] (int8 mode only)
    v_scales: jax.Array | None = None   # [L, num_pages, KV]

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[2]

    @property
    def max_context(self) -> int:
        return self.block_tables.shape[1] * self.page_size

    @property
    def quantized(self) -> bool:
        return self.k_scales is not None


class PoolSpec(NamedTuple):
    """One pool of the cache. ``per="token"``: ``[layers, num_pages, page,
    *shape]``, reached through the block table. ``per="sequence"``: ``[layers,
    rows, *shape]``, a fixed size a sequence, reached through the slot's row
    id. ``layers``: how many layers hold it (None: every layer of the model).
    ``dtype``: None for the engine's cache dtype."""
    name: str
    shape: tuple[int, ...]
    layers: int | None = None
    per: str = "token"
    dtype: Any = None


AnyConfig = (LlamaConfig | DeepseekConfig | OlmoHybridConfig | AfmoeConfig
             | SolarOpen2Config | GraniteHybridConfig)
# families some of whose layers keep a recurrent state and a convolution tail
# a SEQUENCE (the delta rule's two, and the state-space family) beside the
# K/V pages of their attending layers
_STATE_AND_TAIL = (OlmoHybridConfig, SolarOpen2Config, GraniteHybridConfig)
LANES = 128     # the minor dimension of the chip's tiles


def stored_width(width: int) -> int:
    """Elements a per-token vector of ``width`` declared elements is STORED
    in: whole lane tiles (module docstring)."""
    return -(-width // LANES) * LANES


def lane_padded(x: jax.Array) -> jax.Array:
    """x [..., d] -> [..., stored_width(d)]: the zero tail appended (``x``
    itself where d is whole tiles)."""
    tail = stored_width(x.shape[-1]) - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, tail)]) if tail else x


def kv_pools(config: AnyConfig) -> tuple[PoolSpec, ...]:
    """The pools a family's cache holds, in the order of its state's fields."""
    if isinstance(config, DeepseekConfig):
        latent = PoolSpec("latent", (config.latent_dim,),
                          config.n_cache_layers)
        if not config.has_selector:
            return (latent,)
        return (latent, PoolSpec("index_key", (config.index_head_dim,),
                                 config.n_cache_layers))
    heads = (config.n_kv_heads, config.head_dim)
    if isinstance(config, _STATE_AND_TAIL):
        # a family may store a head wider than it is (``kv_head_dim``)
        heads = (config.kv_pool_heads,
                 getattr(config, "kv_head_dim", config.head_dim))
        full = len(config.layers_of("full_attention"))
        linear = config.n_layers - full
        return (PoolSpec("k", heads, full), PoolSpec("v", heads, full),
                PoolSpec("state", (config.linear_key_dim, config.linear_n_heads
                                   * config.linear_value_dim),
                         linear, "sequence", jnp.float32),
                PoolSpec("conv_tail", (config.conv_kernel - 1, config.conv_dim),
                         linear, "sequence"))
    if isinstance(config, AfmoeConfig):
        full = len(config.layers_of("full"))
        ring = (config.ring_tokens, *heads)
        return (PoolSpec("k", heads, full), PoolSpec("v", heads, full),
                PoolSpec("window_k", ring, config.n_layers - full, "sequence"),
                PoolSpec("window_v", ring, config.n_layers - full, "sequence"))
    return (PoolSpec("k", heads), PoolSpec("v", heads))


def state_rows_for(config: AnyConfig, max_slots: int) -> int:
    """Rows of the per-sequence pools: one a slot and the trash row, or 0 for
    a family that keeps nothing a sequence."""
    per_sequence = any(pool.per == "sequence" for pool in kv_pools(config))
    return max_slots + 1 if per_sequence else 0


def kv_state_bytes(config: AnyConfig, rows: int,
                   dtype: jnp.dtype = jnp.bfloat16) -> int:
    """HBM bytes ``rows`` rows of the per-sequence pools cost (all layers)."""
    return rows * sum(
        (pool.layers or config.n_layers) * math.prod(pool.shape)
        * jnp.dtype(pool.dtype or dtype).itemsize
        for pool in kv_pools(config) if pool.per == "sequence")


class LatentKVState(NamedTuple):
    """Device state of the latent family: one attention vector and, where the
    model has a selector, one selector key a token a layer, shared by all
    heads (nothing to shard over ``model``; full precision only). L counts
    the model's layers and its multi-token-prediction block's. The minor
    dimension is the STORED width of the declared vector (``stored_width``):
    zeros past ``kv_lora_rank + rope`` / ``index_head_dim``."""

    latent_pages: jax.Array   # [L, num_pages, page_size, stored latent_dim]
    index_pages: jax.Array | None   # [L, num_pages, page_size, stored index dim]
    block_tables: jax.Array   # [slots, max_pages_per_slot] int32

    @property
    def page_size(self) -> int:
        return self.latent_pages.shape[2]

    @property
    def max_context(self) -> int:
        return self.block_tables.shape[1] * self.page_size

    @property
    def quantized(self) -> bool:
        return False


class HybridKVState(NamedTuple):
    """Device state of a family in which only SOME layers page their K/V: K/V
    pages of the full-attention layers (indexed by the layer's ordinal AMONG
    them) under the block table, and the other layers' two per-sequence pools
    under ``state_rows`` (slot -> row id, 0 = none: the trash row). The hybrid
    family's linear-attention layers keep their recurrent ``state`` and
    ``conv_tail`` there; a window / full family's window layers keep their K
    ring in the first field and their V ring in the second, read through
    :func:`ring_view`. Full precision only; the ``*_scales`` fields are the
    GQA trunk's attention functions' (always None here)."""

    k_pages: jax.Array       # [L_full, num_pages, page_size, KV, hd]
    v_pages: jax.Array
    block_tables: jax.Array  # [slots, max_pages_per_slot] int32
    state: jax.Array         # [L_lin, rows, d_k, H * d_v] float32; or the K ring
    conv_tail: jax.Array     # [L_lin, rows, taps, channels]; or the V ring
    state_rows: jax.Array    # [slots] int32
    k_scales: None = None
    v_scales: None = None

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[2]

    @property
    def max_context(self) -> int:
        return self.block_tables.shape[1] * self.page_size

    @property
    def quantized(self) -> bool:
        return False


def _full_precision_only(config, quant: str) -> bool:
    """True for the families of :class:`HybridKVState` (which then refuse
    ``quant``)."""
    hybrid = isinstance(config, (*_STATE_AND_TAIL, AfmoeConfig))
    if hybrid and quant:
        raise NotImplementedError(
            f"kv_quant={quant!r}: the hybrid family's pools are full "
            f"precision only")
    return hybrid


def _stored_shape(pool: PoolSpec, num_pages: int, page_size: int,
                  rows: int) -> tuple[int, ...]:
    """The array a pool of :class:`HybridKVState` is stored in. A window ring
    (a per-sequence pool of ``[ring_tokens, KV, hd]``) is stored as pages, a
    trash page and ``ring_tokens / page_size`` a row."""
    if pool.per == "token":
        return (pool.layers, num_pages, page_size, *pool.shape)
    if pool.name.startswith("window_"):
        tokens, *heads = pool.shape
        return (pool.layers, rows * (tokens // page_size) + 1, page_size,
                *heads)
    return (pool.layers, rows, *pool.shape)


def _latent_only(config, quant: str) -> bool:
    latent = isinstance(config, DeepseekConfig)
    if latent and quant:
        raise NotImplementedError(
            f"kv_quant={quant!r}: the latent pools are full precision only "
            f"(no per-page scale rule for a vector all heads share yet)")
    return latent


def kv_logical(quant: str = "", config: AnyConfig | None = None
               ) -> PagedKVState | LatentKVState | HybridKVState:
    """Logical sharding names for the state tree."""
    if _full_precision_only(config, quant):
        return HybridKVState(k_pages="kv_pages", v_pages="kv_pages",
                             block_tables="replicated", state="state_pool",
                             conv_tail="state_pool", state_rows="replicated")
    if _latent_only(config, quant):
        return LatentKVState(
            latent_pages="latent_pages",
            index_pages="latent_pages" if config.has_selector else None,
            block_tables="replicated")
    scales = "kv_scales" if quant == "int8" else None
    return PagedKVState(k_pages="kv_pages", v_pages="kv_pages",
                        block_tables="replicated",
                        k_scales=scales, v_scales=scales)


def init_kv_state(config: AnyConfig, num_pages: int, page_size: int,
                  max_slots: int, max_pages_per_slot: int,
                  dtype: jnp.dtype = jnp.bfloat16,
                  quant: str = ""
                  ) -> PagedKVState | LatentKVState | HybridKVState:
    tables = jnp.zeros((max_slots, max_pages_per_slot), dtype=jnp.int32)
    if _full_precision_only(config, quant):
        rows = state_rows_for(config, max_slots)
        k, v, state, conv_tail = (
            jnp.zeros(_stored_shape(pool, num_pages, page_size, rows),
                      dtype=pool.dtype or dtype)
            for pool in kv_pools(config))
        return HybridKVState(k, v, tables, state, conv_tail,
                             jnp.zeros((max_slots,), dtype=jnp.int32))
    if _latent_only(config, quant):
        latent, *index_key = (
            jnp.zeros((pool.layers, num_pages, page_size,
                       stored_width(*pool.shape)), dtype=dtype)
            for pool in kv_pools(config))
        return LatentKVState(latent, index_key[0] if index_key else None,
                             tables)
    shape = (config.n_layers, num_pages, page_size, config.n_kv_heads,
             config.head_dim)
    if quant == "int8":
        scale_shape = (config.n_layers, num_pages, config.n_kv_heads)
        return PagedKVState(
            k_pages=jnp.zeros(shape, dtype=jnp.int8),
            v_pages=jnp.zeros(shape, dtype=jnp.int8),
            block_tables=tables,
            k_scales=jnp.zeros(scale_shape, dtype=dtype),
            v_scales=jnp.zeros(scale_shape, dtype=dtype),
        )
    return PagedKVState(
        k_pages=jnp.zeros(shape, dtype=dtype),
        v_pages=jnp.zeros(shape, dtype=dtype),
        block_tables=tables,
    )


def kv_page_bytes(config: AnyConfig, page_size: int,
                  dtype: jnp.dtype = jnp.bfloat16, quant: str = "") -> int:
    """HBM bytes ONE page (every per-token pool the family declares, each
    over the layers that hold it) costs under a storage mode — the unit
    _init_kv's byte-denominated budget divides by. It counts DECLARED
    elements. The chip's tiled layout pads a pool's minor dimension to whole
    lanes whether the stored shape says so (the latent family's:
    ``stored_width``) or not, so what a pool occupies is ``kv_resident_bytes``
    of the built state, 11 % more for a 576-wide vector."""
    _full_precision_only(config, quant)
    _latent_only(config, quant)
    elems = page_size * sum(
        (pool.layers or config.n_layers) * math.prod(pool.shape)
        for pool in kv_pools(config) if pool.per == "token")
    if quant == "int8":
        scale_bytes = (2 * config.n_layers * config.n_kv_heads
                       * jnp.dtype(dtype).itemsize)
        return elems + scale_bytes  # int8 values + per-(page, head) scales
    return elems * jnp.dtype(dtype).itemsize


def kv_resident_bytes(kv) -> int:
    """HBM bytes the per-token arrays of a built state hold as STORED (the
    arrays' own sizes: pages and their scales, whatever the family)."""
    return sum(a.size * a.dtype.itemsize for name, a in kv._asdict().items()
               if a is not None and name.endswith(("_pages", "_scales")))


def num_pages_for_budget(config: AnyConfig, page_size: int,
                         budget_bytes: int, dtype: jnp.dtype = jnp.bfloat16,
                         quant: str = "") -> int:
    """Pages a fixed HBM byte budget holds under a storage mode (~2x under
    int8: 1 byte/elem + a per-page scale sliver vs 2 bytes/elem bf16)."""
    return max(2, int(budget_bytes
                      // kv_page_bytes(config, page_size, dtype, quant)))


# --------------------------------------------------------- int8 write helpers

def _quant_store(pages: jax.Array, scales: jax.Array, layer: int,
                 values: jax.Array, flat_pages: jax.Array,
                 flat_offset: jax.Array, first_pages: jax.Array,
                 first_mask: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Quantize ``values`` [N, KV, hd] into int8 ``pages`` with running-max
    per-(page, kv-head) scales; returns (pages, scales) for one layer's
    K or V side.

    ``first_pages``/``first_mask`` [R]: the page under each row's FIRST
    written token, masked to rows whose span starts mid-page — the only
    pages that can hold prior tokens of the spans being written (spans
    are contiguous), so only they are requantized when their scale grows.
    """
    old_scales = scales[layer]                               # [P, KV]
    # offset-0 writes begin a page tenancy: drop the stale scale so a
    # reallocated page can't inherit (and forever creep on) the previous
    # tenant's range. Non-fresh tokens alias the trash page here.
    fresh_pages = jnp.where(flat_offset == 0, flat_pages, 0)
    layer_scales = old_scales.at[fresh_pages].set(0.0, mode="drop")
    # running-max update from this call's tokens
    amax = jnp.max(jnp.abs(values.astype(jnp.float32)), axis=-1)  # [N, KV]
    tok_scale = kv_int8_scale(amax).astype(layer_scales.dtype)
    layer_scales = layer_scales.at[flat_pages].max(tok_scale, mode="drop")
    # requantize prior resident content of first-touched pages whose scale
    # grew: q_old was written under s_old; under the new page scale s_new
    # the same value is q_old * s_old / s_new (ratio <= 1, so no clipping
    # of live values — stale masked-dead positions may saturate, but they
    # are never read before being rewritten)
    safe_first = jnp.where(first_mask, first_pages, 0)
    resident = pages[layer, safe_first]                      # [R, page, KV, hd]
    s_old = old_scales[safe_first].astype(jnp.float32)       # [R, KV]
    s_new = layer_scales[safe_first].astype(jnp.float32)
    ratio = s_old / jnp.maximum(s_new, KV_SCALE_EPS)
    requant = jnp.round(resident.astype(jnp.float32) * ratio[:, None, :, None])
    requant = jnp.clip(requant, -127.0, 127.0).astype(jnp.int8)
    requant = jnp.where(first_mask[:, None, None, None], requant, resident)
    pages = pages.at[layer, safe_first].set(requant, mode="drop")
    # finally the new tokens, quantized under the settled page scales
    s_final = layer_scales[flat_pages][..., None]            # [N, KV, 1]
    q = kv_quantize(values, s_final.astype(jnp.float32))
    pages = pages.at[layer, flat_pages, flat_offset].set(q, mode="drop")
    return pages, scales.at[layer].set(layer_scales)


def write_prefill_kv(kv: PagedKVState, layer: int, k: jax.Array, v: jax.Array,
                     slot_ids: jax.Array, positions: jax.Array,
                     valid: jax.Array) -> PagedKVState:
    """Scatter a [B,S] block of K/V into pages (quantizing on store under
    int8 mode — each row's span must be contiguous, see module docstring).

    k/v: [B,S,KV,hd]; slot_ids: [B]; positions: [B,S]; valid: [B,S] bool."""
    B, S = positions.shape
    page_size = kv.page_size
    page_slot = positions // page_size                      # [B,S] index into table row
    offset = positions % page_size                          # [B,S]
    rows = kv.block_tables[slot_ids]                        # [B, P]
    pages = jnp.take_along_axis(rows, page_slot, axis=1)    # [B,S]
    pages = jnp.where(valid, pages, 0)                      # trash page for padding
    offset = jnp.where(valid, offset, 0)
    flat_pages = pages.reshape(-1)
    flat_offset = offset.reshape(-1)
    k_flat = k.reshape(B * S, *k.shape[2:])
    v_flat = v.reshape(B * S, *v.shape[2:])
    if kv.quantized:
        # the page under each row's first written token is the only one
        # that can hold PRIOR tokens of the span; rows are robust to
        # leading padding (argmax finds the first valid column)
        first_idx = jnp.argmax(valid, axis=1)               # [B]
        take = lambda a: jnp.take_along_axis(a, first_idx[:, None],
                                             axis=1)[:, 0]
        first_pages = take(pages)
        first_mask = take(valid) & (take(offset) > 0)
        k_pages, k_scales = _quant_store(kv.k_pages, kv.k_scales, layer,
                                         k_flat, flat_pages, flat_offset,
                                         first_pages, first_mask)
        v_pages, v_scales = _quant_store(kv.v_pages, kv.v_scales, layer,
                                         v_flat, flat_pages, flat_offset,
                                         first_pages, first_mask)
        return kv._replace(k_pages=k_pages, v_pages=v_pages,
                           k_scales=k_scales, v_scales=v_scales)
    k_pages = kv.k_pages.at[layer, flat_pages, flat_offset].set(
        k_flat, mode="drop")
    v_pages = kv.v_pages.at[layer, flat_pages, flat_offset].set(
        v_flat, mode="drop")
    return kv._replace(k_pages=k_pages, v_pages=v_pages)


def write_decode_kv(kv: PagedKVState, layer: int, k: jax.Array, v: jax.Array,
                    slot_ids: jax.Array, positions: jax.Array,
                    valid: jax.Array | None = None) -> PagedKVState:
    """Scatter one token per slot. k/v: [B,KV,hd]; positions: [B];
    valid: [B] bool — False rows write to the trash page. Inactive decode
    rows MUST be masked explicitly: a slot can be allocated but not
    decoding (mid-chunk-prefill), in which case its block-table row maps
    REAL pages and an unmasked position-0 write would corrupt the
    prompt's first page."""
    page_size = kv.page_size
    rows = kv.block_tables[slot_ids]                        # [B,P]
    pages = jnp.take_along_axis(rows, (positions // page_size)[:, None],
                                axis=1)[:, 0]               # [B]
    offset = positions % page_size
    if valid is not None:
        pages = jnp.where(valid, pages, 0)                  # trash page
        offset = jnp.where(valid, offset, 0)
    if kv.quantized:
        # a one-token span: the written page itself may hold the row's
        # earlier tokens (offset > 0), so it is its own "first page"
        first_mask = offset > 0
        if valid is not None:
            first_mask = first_mask & valid
        k_pages, k_scales = _quant_store(kv.k_pages, kv.k_scales, layer,
                                         k, pages, offset, pages, first_mask)
        v_pages, v_scales = _quant_store(kv.v_pages, kv.v_scales, layer,
                                         v, pages, offset, pages, first_mask)
        return kv._replace(k_pages=k_pages, v_pages=v_pages,
                           k_scales=k_scales, v_scales=v_scales)
    k_pages = kv.k_pages.at[layer, pages, offset].set(k, mode="drop")
    v_pages = kv.v_pages.at[layer, pages, offset].set(v, mode="drop")
    return kv._replace(k_pages=k_pages, v_pages=v_pages)


def gather_kv(kv: PagedKVState, layer: int, slot_ids: jax.Array,
              ctx_pages: int | None = None
              ) -> tuple[jax.Array, jax.Array]:
    """Materialize each slot's context: -> ([B, C, KV, hd], [B, C, KV, hd])
    where C = ctx_pages * page_size (default: the full block-table width).
    ``ctx_pages`` is STATIC (a compile-time context-width bucket): decode
    cost is dominated by this gather's HBM traffic, and pulling the full
    max-context width for 40-token conversations wastes ~24x the
    bandwidth — the engine picks a power-of-two bucket covering the
    longest active row each step. (The Pallas paged-attention kernel
    replaces this gather on TPU for large configs.)

    Int8 pools dequantize in a per-page epilogue (q * scale), returning
    the scales' dtype — the compute dtype — so the CPU/interpret
    fallback, the history/chunk prefill path, and the spec-decode verify
    path all serve quantized pages unchanged."""
    rows = kv.block_tables[slot_ids]                        # [B,P]
    if ctx_pages is not None:
        rows = rows[:, :ctx_pages]
    k = kv.k_pages[layer][rows]                             # [B,P,page,KV,hd]
    v = kv.v_pages[layer][rows]
    if kv.quantized:
        dt = kv.k_scales.dtype
        ks = kv.k_scales[layer][rows][:, :, None, :, None]  # [B,P,1,KV,1]
        vs = kv.v_scales[layer][rows][:, :, None, :, None]
        k = kv_dequantize(k, ks, dt)
        v = kv_dequantize(v, vs, dt)
    B, P, page, KV, hd = k.shape
    return k.reshape(B, P * page, KV, hd), v.reshape(B, P * page, KV, hd)


def ring_tables(ring_pages: int, rows: jax.Array, first_page: jax.Array,
                n_pages: int) -> jax.Array:
    """The ring pages that hold logical pages ``first_page .. first_page +
    n_pages - 1`` of state rows ``rows``: rows, first_page [B] -> [B, n_pages]
    int32 (``1 + row * ring_pages + n mod ring_pages``; module docstring)."""
    logical = first_page[:, None] + jnp.arange(n_pages, dtype=jnp.int32)[None]
    return 1 + rows[:, None] * ring_pages + logical % ring_pages


def ring_view(kv: HybridKVState, ring_pages: int) -> PagedKVState:
    """The window layers' rings as the trunk's writers and kernels take a
    cache: K and V pages indexed by the layer's ordinal among the window
    layers, and a block table ``[slots, max_pages_per_slot]`` that sends
    every logical page of a slot to its ring page. Write through it with
    ``write_prefill_kv`` / ``write_decode_kv`` and put the pages back with
    :func:`with_rings`."""
    slots, width = kv.block_tables.shape
    tables = ring_tables(ring_pages, kv.state_rows,
                         jnp.zeros((slots,), jnp.int32), width)
    return PagedKVState(kv.state, kv.conv_tail, tables)


def with_rings(kv: HybridKVState, view: PagedKVState) -> HybridKVState:
    return kv._replace(state=view.k_pages, conv_tail=view.v_pages)


def _token_pages(kv, slot_ids: jax.Array, positions: jax.Array,
                 valid: jax.Array | None) -> tuple[jax.Array, jax.Array]:
    """(page id, offset in page) of each position of each row; positions
    [B] or [B, S]. Rows or tokens that are not ``valid`` get the trash page."""
    page_size = kv.page_size
    rows = kv.block_tables[slot_ids]                        # [B, P]
    slot = positions // page_size
    pages = jnp.take_along_axis(
        rows, slot if slot.ndim == 2 else slot[:, None], axis=1)
    pages = pages.reshape(positions.shape)
    offset = positions % page_size
    if valid is not None:
        pages = jnp.where(valid, pages, 0)
        offset = jnp.where(valid, offset, 0)
    return pages, offset


def write_latent_kv(kv: LatentKVState, layer: int, latent: jax.Array,
                    index_key: jax.Array | None, slot_ids: jax.Array,
                    positions: jax.Array,
                    valid: jax.Array | None = None) -> LatentKVState:
    """Scatter tokens' latent vectors and selector keys into their pages: a
    [B, S] block (prefill, chunk rounds; ``valid`` [B, S]) or one token a slot
    (decode; positions and ``valid`` [B], False rows MUST be masked for the
    reason ``write_decode_kv`` gives). latent, index_key: [..., the pool's
    stored width] (``lane_padded``); index_key None for a model without a
    selector (no pool)."""
    pages, offset = _token_pages(kv, slot_ids, positions, valid)
    pages, offset = pages.reshape(-1), offset.reshape(-1)
    flat = lambda a, pool: a.reshape(-1, a.shape[-1]).astype(pool.dtype)
    kv = kv._replace(
        latent_pages=kv.latent_pages.at[layer, pages, offset].set(
            flat(latent, kv.latent_pages), mode="drop"))
    if index_key is None:
        return kv
    return kv._replace(
        index_pages=kv.index_pages.at[layer, pages, offset].set(
            flat(index_key, kv.index_pages), mode="drop"))


def gather_pool(pages: jax.Array, layer: int, tables: jax.Array) -> jax.Array:
    """One pool's context of each row: pages [L, N, page, d], tables [B, P]
    -> [B, P * page, d] (the jnp reference path; the kernels walk the table)."""
    ctx = pages[layer][tables]                              # [B, P, page, d]
    return ctx.reshape(ctx.shape[0], -1, ctx.shape[-1])


class PrefixEvictionPolicy:
    """Eviction order over the ref==0 resident prefix pages: LRU by LAST
    MATCH. A page leaves the policy when a match re-references it (pin
    counts — the refcounts — protect every in-flight span by
    construction: referenced pages are simply never candidates) and
    re-enters at the MRU end when the last reference drops, so the
    victim is always the resident page whose prefix went unmatched the
    longest. Dict-shaped on purpose: the allocator (and tests) treat it
    as the old ``_lru`` ordered-dict."""

    def __init__(self) -> None:
        self._order: dict[int, None] = {}

    def add(self, page: int) -> None:
        """(Re-)admit a ref==0 resident page at the MRU end."""
        self._order.pop(page, None)
        self._order[page] = None

    def discard(self, page: int) -> None:
        self._order.pop(page, None)

    def pop(self, page: int, default=None):
        return self._order.pop(page, default)

    def victim(self) -> int | None:
        """The LRU-by-last-match page, or None when nothing is evictable."""
        return next(iter(self._order)) if self._order else None

    def __contains__(self, page: int) -> bool:
        return page in self._order

    def __len__(self) -> int:
        return len(self._order)

    def __iter__(self):
        return iter(self._order)


class PageAllocator:
    """Host-side page bookkeeping: refcounted free list + per-slot
    assignment + prefix cache.

    Page 0 is reserved (trash). The device block table is refreshed from
    ``tables()`` whenever assignments change.

    Prefix cache (vLLM automatic-prefix-caching analog, TPU-static
    shapes): FULL pages of prompt tokens are registered under a chained
    key (parent_key, page_tokens), so a later prompt sharing the prefix
    reuses the resident pages and only its suffix is prefilled. Pages are
    refcounted across slots; cached pages whose refcount drops to 0 stay
    resident under the eviction policy (LRU-by-last-match) until
    allocation pressure reclaims them. A matched page is immutable by
    construction — matches cover only positions strictly before the new
    prompt's last token, and decode writes start at the prompt's end.

    Tiers (``tiers.py`` + ``prefix_index.py``, attach via ``self.tiers``):
    with a :class:`~.tiers.TierClient` wired, eviction SPILLS the page's
    bytes to the pool-shared host/disk store instead of dropping them,
    and ``probe_prefix``/``match_prefix`` extend past the local HBM walk
    by RESTORING tier-resident chain pages into freshly taken pages
    (fetch-on-miss) — so a prefix prefilled on any replica, then evicted
    anywhere, still serves a hit here. Restored pages register into the
    local cache and count toward ``prefix_hit_tokens`` at the same
    consume site as resident hits (the tenant-ledger ``cache_hit``
    conservation contract is unchanged)."""

    def __init__(self, num_pages: int, page_size: int, max_slots: int,
                 max_pages_per_slot: int, tiers=None, state_rows: int = 0):
        import numpy as np
        self.num_pages = num_pages
        # per-sequence state rows (a family with "sequence" pools): a row id
        # is dealt with the slot's pages and freed with them; row 0 is the
        # trash row. state_rows > max_slots, so a slot never waits for one.
        self.state_rows = state_rows
        self._free_rows = list(range(state_rows - 1, 0, -1))
        self._row: dict[int, int] = {}                  # slot -> state row
        self.page_size = page_size
        self.max_slots = max_slots
        self.max_pages_per_slot = max_pages_per_slot
        self.tiers = tiers                              # TierClient | None
        self._free = list(range(num_pages - 1, 0, -1))  # page 0 reserved
        self._slots: dict[int, list[int]] = {}
        self._ref: dict[int, int] = {}                  # page -> live refs
        self._cached: dict[tuple, int] = {}             # chain key -> page
        self._page_key: dict[int, tuple] = {}           # page -> chain key
        self._page_hash: dict[int, tuple] = {}          # page -> (hash, parent)
        self._lru = PrefixEvictionPolicy()              # ref==0 resident pages
        # provenance of pages restored from a spill tier, consumed (and
        # cleared) when a successful allocate takes the hit — the per-tier
        # split of prefix_hit_tokens
        self._restored_tier: dict[int, str] = {}
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        self.tier_hits = {"hbm": 0, "host": 0, "disk": 0, "object": 0}
        self.tier_hit_tokens = {"hbm": 0, "host": 0, "disk": 0, "object": 0}
        # monotonic high-water mark of pages_in_use (benches/telemetry):
        # a rolling step ring under-reports peaks on long runs
        self.peak_pages_in_use = 0
        # dirty-row tracking: rows whose page list changed since tables()
        # was last read. Steady-state decode (no page growth, no finishes)
        # leaves this empty, so the engine skips the host->device table
        # upload entirely between such steps.
        self._dirty: set[int] = set()
        self._table = np.zeros((max_slots, max_pages_per_slot), dtype=np.int32)

    @property
    def dirty(self) -> bool:
        """True iff some block-table row changed since the last tables()."""
        return bool(self._dirty)

    @property
    def free_pages(self) -> int:
        return len(self._free) + len(self._lru)

    def avg_slot_pages(self) -> int:
        """Average page footprint of currently active slots (the typical
        admission cost); max_pages_per_slot when nothing is active —
        conservative for capacity estimates."""
        if not self._slots:
            return self.max_pages_per_slot
        total = sum(len(pages) for pages in self._slots.values())
        return max(1, total // len(self._slots))

    @property
    def pages_in_use(self) -> int:
        return (self.num_pages - 1) - self.free_pages

    def _track_peak(self) -> None:
        self.peak_pages_in_use = max(self.peak_pages_in_use,
                                     self.pages_in_use)

    @property
    def cached_pages(self) -> int:
        return len(self._cached)

    @property
    def rows_in_use(self) -> int:
        return len(self._row)

    def slot_row(self, slot: int) -> int:
        """The slot's state row (0: none)."""
        return self._row.get(slot, 0)

    def state_row_table(self) -> "np.ndarray":
        """slot -> state row id, [max_slots] int32 (0 = the trash row): the
        device's ``state_rows``, uploaded whenever the block table is."""
        import numpy as np
        table = np.zeros((self.max_slots,), dtype=np.int32)
        for slot, row in self._row.items():
            table[slot] = row
        return table

    def pages_needed(self, n_tokens: int) -> int:
        return (n_tokens + self.page_size - 1) // self.page_size

    def slot_pages(self, slot: int) -> int:
        """Pages currently held by one slot (telemetry surface)."""
        return len(self._slots.get(slot, ()))

    def can_allocate(self, n_tokens: int) -> bool:
        return self.pages_needed(n_tokens) <= self.free_pages

    def _take_page(self) -> int:
        """A writable page: prefer truly-free, else reclaim the eviction
        policy's victim (LRU-by-last-match). With a tier client wired,
        a reclaimed prefix page SPILLS its bytes to the shared host/disk
        store on the way out instead of dropping them."""
        if self._free:
            return self._free.pop()
        page = self._lru.victim()
        if page is None:  # callers gate on free_pages; this is a bug trap
            raise RuntimeError("page pool exhausted with nothing evictable")
        self._lru.discard(page)
        key = self._page_key.pop(page, None)
        if key is not None and self._cached.get(key) == page:
            del self._cached[key]
            self._evict_page(page, key)
        self._page_hash.pop(page, None)
        self._restored_tier.pop(page, None)
        return page

    def _evict_page(self, page: int, key: tuple) -> None:
        """Spill-instead-of-drop: hand the evicted page's bytes to the
        tier store (device read runs on the calling dispatch thread) and
        move its index residency HBM -> tier."""
        tiers = self.tiers
        if tiers is None:
            return
        hashed = self._page_hash.get(page)
        if hashed is not None:
            key_hash, parent = hashed
            tiers.spill(key_hash, parent, key[1], page)
            tiers.unpublish_hbm(key_hash)

    def _release_page(self, page: int) -> None:
        # defensive default: the allocate/extend/match paths always set a
        # ref before a page can be released
        current = self._ref.get(page, 1)
        self._ref[page] = current - 1
        if self._ref[page] > 0:
            return
        del self._ref[page]
        if page in self._page_key:       # registered prefix page: keep warm
            self._lru.add(page)          # MRU end: LRU-by-last-match order
        else:
            self._free.append(page)

    # ------------------------------------------------------------ prefix cache

    def _walk_prefix(self, prompt_ids: list[int]) -> list[int]:
        """Pages of the longest cached full-page prefix. Matches never
        cover the prompt's last token — at least one token must prefill to
        produce logits."""
        max_pages = max(0, (len(prompt_ids) - 1) // self.page_size)
        key: tuple = ()
        pages: list[int] = []
        for i in range(max_pages):
            chunk = tuple(prompt_ids[i * self.page_size:(i + 1) * self.page_size])
            key = (key, chunk)
            page = self._cached.get(key)
            if page is None:
                break
            pages.append(page)
        return pages

    def _chain_steps(self, prompt_ids: list[int], full: bool = False):
        """Yield ``(key, key_hash, parent_hash, chunk)`` per full page of
        the prompt (depth order) — the MATCHABLE pages by default (a
        match never covers the last token), or every full page with
        ``full=True`` (the registration walk: a prompt ending exactly on
        a page boundary registers its final page too, for longer prompts
        to share). Hashes come from prefix_index.chain_hash so the
        allocator, the tier store, and the pool index all speak one
        chain identity."""
        from .prefix_index import ROOT_HASH, chain_hash
        if full:
            max_pages = len(prompt_ids) // self.page_size
        else:
            max_pages = max(0, (len(prompt_ids) - 1) // self.page_size)
        key: tuple = ()
        parent = ROOT_HASH
        for i in range(max_pages):
            chunk = tuple(prompt_ids[i * self.page_size:(i + 1) * self.page_size])
            key = (key, chunk)
            key_hash = chain_hash(parent, chunk)
            yield key, key_hash, parent, chunk
            parent = key_hash

    def probe_prefix(self, prompt_ids: list[int]) -> int:
        """Read-only: tokens a match WOULD cover (used for bucket sizing
        and router affinity). Takes no references, so probing can never
        pin pages — the real match happens at admission via
        match_prefix. With tiers wired the walk continues past the local
        HBM chain through tier-resident pages, capped at the restore
        capacity currently available (free + evictable pages): the probe
        must never promise a hist the match cannot restore, or admission
        would livelock re-probing the same prompt."""
        if self.tiers is None or not self.tiers.active:
            return len(self._walk_prefix(prompt_ids)) * self.page_size
        n = 0
        restorable = self.free_pages
        for key, key_hash, _parent, _chunk in self._chain_steps(prompt_ids):
            page = self._cached.get(key)
            if page is not None:
                if page in self._lru:
                    # matching PINS a ref==0 resident page (it leaves the
                    # eviction policy), consuming one unit of the same
                    # capacity later restores draw from — not modeling
                    # that promises a hist the match cannot deliver and
                    # admission livelocks re-probing it
                    restorable -= 1
                n += 1
            elif restorable > 0 and self.tiers.probe(key_hash):
                n += 1
                restorable -= 1
            else:
                break
        return n * self.page_size

    def match_prefix(self, prompt_ids: list[int]) -> tuple[int, list[int]]:
        """Longest cached full-page prefix of ``prompt_ids``.

        Returns (n_tokens_matched, pages) and takes a REFERENCE on every
        matched page (caller must either assign them to a slot or call
        release_prefix). With tiers wired, chain pages missing from HBM
        but present in the shared spill store are RESTORED here
        (fetch-on-miss): a fresh page is taken (evicting — and spilling —
        colder pages if needed), the verified payload uploads into this
        replica's HBM, and the page registers into the local cache so
        later matches treat it as resident. A failed restore (payload
        gone, hash collision, pool dry) ends the match at the pages
        already secured."""
        if self.tiers is None:  # hash-free fast path (tier-less default)
            # behaviorally identical to the chain walk below minus the
            # per-chunk sha256 the tier identity needs — the default
            # config must not pay hashing on the admission hot path
            pages = self._walk_prefix(prompt_ids)
            for page in pages:
                self._ref[page] = self._ref.get(page, 0) + 1
                self._lru.pop(page, None)
            self._track_peak()
            return len(pages) * self.page_size, pages
        tiered = self.tiers.active
        pages: list[int] = []
        for key, key_hash, parent, chunk in self._chain_steps(prompt_ids):
            page = self._cached.get(key)
            if page is not None:
                self._ref[page] = self._ref.get(page, 0) + 1
                self._lru.pop(page, None)
                pages.append(page)
                continue
            if not tiered or not (self._free or len(self._lru)):
                break
            if not self.tiers.probe(key_hash):
                break
            page = self._take_page()
            tier = self.tiers.restore(key_hash, parent, chunk, page)
            if tier is None:
                self._free.append(page)   # miss/collision: hand it back
                break
            self._ref[page] = 1
            self._cached[key] = page
            self._page_key[page] = key
            self._page_hash[page] = (key_hash, parent)
            self._restored_tier[page] = tier
            self.tiers.publish_hbm(key_hash)
            pages.append(page)
        self._track_peak()  # re-referencing LRU pages raises pages_in_use
        return len(pages) * self.page_size, pages

    def release_prefix(self, pages: list[int]) -> None:
        """Drop the references taken by match_prefix (request not admitted)."""
        for page in reversed(pages):
            self._release_page(page)

    def spill_resident_prefix(self) -> int:
        """Spill-on-drain (ROADMAP item 3, docs/resilience.md): push
        every ref==0 REGISTERED prefix page through the tier spill path
        before this pool's HBM is torn down (drain → reload), so the
        rebuilt replica — or any pool sibling — restores the prefix
        corpus by fetch-on-miss instead of re-prefilling it from
        scratch. In-flight spans (ref > 0) are untouched: their pages
        die with the teardown like any active allocation. Pages stay
        resident afterwards (the spill is a copy, not an eviction); the
        caller is about to drop the whole pool. Returns pages spilled
        (``TieredPageStore.put`` dedupes chains other replicas already
        spilled — those still count as preserved here)."""
        tiers = self.tiers
        if tiers is None or not tiers.active:
            return 0
        spilled = 0
        for page in list(self._lru):
            key = self._page_key.get(page)
            hashed = self._page_hash.get(page)
            if key is None or hashed is None:
                continue
            key_hash, parent = hashed
            if tiers.spill(key_hash, parent, key[1], page):
                spilled += 1
        return spilled

    def spill_chain(self, prompt_ids: list[int]) -> int:
        """Export one prompt's registered chain pages into the shared
        tier store (docs/disaggregation.md): the prefill->decode
        migration seam. Walks every FULL page of ``prompt_ids`` (the
        registration depth — exactly the pages a continuation prompt of
        ``prompt_ids`` plus one generated token can match) and pushes
        each through the tier spill path. Unlike eviction this is a
        COPY: pages stay resident and referenced here, so a degraded
        migration decodes in place with zero re-prefill. Runs on the
        dispatch thread (device reads). Returns pages now present in the
        store (``TieredPageStore.put`` dedupes — chains another replica
        already spilled count as exported)."""
        tiers = self.tiers
        if tiers is None or not tiers.active:
            return 0
        spilled = 0
        for key, key_hash, parent, chunk in self._chain_steps(
                prompt_ids, full=True):
            page = self._cached.get(key)
            if page is None:
                break  # unregistered depth: nothing deeper can verify
            if tiers.spill(key_hash, parent, chunk, page):
                spilled += 1
        return spilled

    def register_prefix(self, slot: int, prompt_ids: list[int]) -> None:
        """Register the slot's full prompt pages for future reuse (and
        publish their HBM residency to the pool index when one is
        wired). First registration of a chain key wins; later identical
        pages stay private and simply free when their slot does."""
        pages = self._slots.get(slot, [])
        for i, (key, key_hash, parent, _chunk) in enumerate(
                self._chain_steps(prompt_ids, full=True)):
            if i >= len(pages):
                break
            page = pages[i]
            if key not in self._cached and page not in self._page_key:
                # (a page already registered under another key stays
                # private and simply frees with its slot)
                self._cached[key] = page
                self._page_key[page] = key
                self._page_hash[page] = (key_hash, parent)
                if self.tiers is not None:
                    self.tiers.publish_hbm(key_hash)

    # -------------------------------------------------------------- slot pages

    def allocate_slot(self, slot: int, n_tokens: int,
                      prefix_pages: list[int] | None = None) -> bool:
        """Assign pages for a sequence of n_tokens to ``slot``; the first
        ``prefix_pages`` (already referenced via match_prefix) are shared."""
        shared = prefix_pages or []
        needed = self.pages_needed(n_tokens)
        fresh = needed - len(shared)
        if (fresh > len(self._free) + len(self._lru)
                or needed > self.max_pages_per_slot or fresh < 0):
            return False
        if shared:  # hits are counted when the match is CONSUMED, not probed
            self.prefix_hits += 1
            self.prefix_hit_tokens += len(shared) * self.page_size
            for page in shared:
                # per-tier split of the SAME consume event: pages restored
                # from a spill tier carry their provenance until first
                # consumed, resident pages count as hbm
                tier = self._restored_tier.pop(page, "hbm")
                self.tier_hits[tier] += 1
                self.tier_hit_tokens[tier] += self.page_size
        pages = list(shared)
        for _ in range(fresh):
            page = self._take_page()
            self._ref[page] = self._ref.get(page, 0) + 1
            pages.append(page)
        self._slots[slot] = pages
        if self.state_rows and slot not in self._row:
            self._row[slot] = self._free_rows.pop()
        self._dirty.add(slot)
        self._track_peak()
        return True

    def grow_slot(self, slot: int, n_tokens: int) -> int:
        """Best-effort growth toward ``n_tokens`` total capacity; returns
        the slot's token capacity (pages * page_size) after growth. ONE
        call replaces the per-lookahead-token extend_slot probe loop the
        engine used to run per slot per step: the caller derives its
        usable-token budget from the returned capacity. Partial growth
        persists (pages already taken stay with the slot), matching the
        old loop's behavior when the pool ran dry mid-extension."""
        pages = self._slots.get(slot)
        missing = pages is None
        if missing:
            pages = []
        needed = self.pages_needed(n_tokens)
        grew = False
        while len(pages) < needed:
            if not (self._free or self._lru) \
                    or len(pages) >= self.max_pages_per_slot:
                break
            page = self._take_page()
            self._ref[page] = self._ref.get(page, 0) + 1
            pages.append(page)
            grew = True
        if grew:
            if missing:
                self._slots[slot] = pages
            self._dirty.add(slot)
            self._track_peak()
        return len(pages) * self.page_size

    def pregrant_block(self, slot: int, n_ctx: int, k: int) -> int:
        """Pre-grant pages for a K-token decode super-step in ONE call;
        returns the usable token budget (0..k).

        ``n_ctx`` counts every token that exists for the row INCLUDING
        the incoming input token (0-based position n_ctx-1, whose KV is
        written this dispatch). The k sampled tokens land at positions
        n_ctx-1+1.., but the LAST one's KV is written only when it
        becomes the next dispatch's input — so capacity must cover
        n_ctx + k - 1 tokens, and the budget is how many sampled tokens
        fit the granted capacity. Growth dirties the slot's block-table
        row exactly when new pages were taken, so the host->device table
        sync stays a once-per-super-step reconcile (tables() clears the
        dirty set at upload)."""
        if k <= 0:
            return 0
        capacity = self.grow_slot(slot, n_ctx + k - 1)
        return max(0, min(k, capacity - (n_ctx - 1)))

    def free_slot(self, slot: int) -> None:
        pages = self._slots.pop(slot, [])
        if pages:
            self._dirty.add(slot)
        row = self._row.pop(slot, None)
        if row is not None:
            self._free_rows.append(row)
        for page in reversed(pages):
            self._release_page(page)

    def tables_host(self) -> "np.ndarray":
        """The block table to upload, on the host. Only dirty rows are
        rebuilt in the cached host table; the returned array is a fresh
        copy, so later in-place row updates can never alias a device
        buffer. Reading clears the dirty set — callers that gate on
        ``dirty`` skip the upload entirely when nothing changed."""
        for slot in self._dirty:
            row = self._table[slot]
            row[:] = 0
            pages = self._slots.get(slot)
            if pages:
                row[:len(pages)] = pages
        self._dirty.clear()
        return self._table.copy()

    def tables(self) -> "jnp.ndarray":
        """:meth:`tables_host` as a device array (the default device's): for
        callers that hand it straight to a model function. The engine
        uploads the host table itself, in one transfer and no program."""
        return jnp.array(self.tables_host())
