"""Paged KV cache (+ spill tiers and the pool-global prefix index)."""

from .paged_cache import (
    PagedKVState,
    LatentKVState,
    HybridKVState,
    state_rows_for,
    kv_state_bytes,
    PoolSpec,
    kv_pools,
    stored_width,
    lane_padded,
    kv_resident_bytes,
    write_latent_kv,
    gather_pool,
    PageAllocator,
    PrefixEvictionPolicy,
    init_kv_state,
    kv_page_bytes,
    num_pages_for_budget,
    write_prefill_kv,
    write_decode_kv,
    gather_kv,
    kv_logical,
)
from .prefix_index import PrefixIndex, chain_hash, chain_hashes
from .tiers import SpilledPage, TierClient, TieredPageStore

__all__ = ["PagedKVState", "LatentKVState", "HybridKVState", "PoolSpec",
           "kv_pools", "stored_width", "lane_padded", "kv_resident_bytes",
           "state_rows_for", "kv_state_bytes",
           "write_latent_kv", "gather_pool", "PageAllocator", "PrefixEvictionPolicy",
           "init_kv_state", "kv_page_bytes",
           "num_pages_for_budget", "write_prefill_kv", "write_decode_kv",
           "gather_kv", "kv_logical",
           "PrefixIndex", "chain_hash", "chain_hashes",
           "SpilledPage", "TierClient", "TieredPageStore"]
