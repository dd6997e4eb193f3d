"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

Long-context prefill beyond one chip's memory (SURVEY.md §5.7): the sequence
dim is sharded over a mesh axis and attention runs either as

- **ring attention**: K/V blocks rotate around the ICI ring via
  ``jax.lax.ppermute`` while each device keeps its Q shard; online-softmax
  stats (running max / denominator / accumulator) merge per hop, so the full
  S×S score matrix never materializes and peak memory is O(S/n per device).
- **Ulysses**: ``jax.lax.all_to_all`` reshards sequence→heads so every device
  computes full-sequence attention for its head slice, then reshards back.
  Fewer, larger collectives — the better first choice on ICI (SURVEY.md
  §7.2 #6).

Both are pure functions compiled under ``shard_map`` over the given axis and
validated against single-device attention in tests (8-device virtual mesh).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     q_offset: jax.Array, k_offset: jax.Array,
                     causal: bool, k_valid: jax.Array | None = None
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One (q-shard × k-block) partial attention with un-normalized stats.

    q: [B,Sq,H,hd]; k/v: [B,Sk,KV,hd] (GQA: expanded locally, so rotated
    blocks stay KV-width on the wire); k_valid: [B,Sk] bool (padding mask).
    Returns (acc [B,Sq,H,hd], row_max [B,Sq,H,1], row_sum [B,Sq,H,1]) for
    online-softmax merging."""
    hd = q.shape[-1]
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    if causal:
        Sq, Sk = q.shape[1], k.shape[1]
        q_pos = q_offset + jnp.arange(Sq)[:, None]
        k_pos = k_offset + jnp.arange(Sk)[None, :]
        mask = (k_pos <= q_pos)[None, None]
        scores = jnp.where(mask, scores, NEG_INF)
    if k_valid is not None:
        scores = jnp.where(k_valid[:, None, None, :], scores, NEG_INF)
    row_max = jnp.max(scores, axis=-1, keepdims=True)             # [B,H,Sq,1]
    probs = jnp.exp(scores - row_max)
    # fully-masked rows: row_max == NEG_INF → make them contribute nothing
    probs = jnp.where(row_max > NEG_INF / 2, probs, 0.0)
    row_sum = jnp.sum(probs, axis=-1, keepdims=True)
    acc = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return acc, row_max.transpose(0, 2, 1, 3), row_sum.transpose(0, 2, 1, 3)


def _merge(acc_a, max_a, sum_a, acc_b, max_b, sum_b):
    """Merge two un-normalized online-softmax partials."""
    new_max = jnp.maximum(max_a, max_b)
    scale_a = jnp.exp(max_a - new_max)
    scale_b = jnp.exp(max_b - new_max)
    acc = acc_a * scale_a + acc_b * scale_b
    total = sum_a * scale_a + sum_b * scale_b
    return acc, new_max, total


def ring_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                           axis_name: str, causal: bool = True,
                           k_valid: jax.Array | None = None) -> jax.Array:
    """Per-device body (call under shard_map with sequence sharded on
    ``axis_name``). q/k/v: local shards [B, S_local, H, hd];
    k_valid: [B, S_local] padding mask rotating with k/v."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    S_local = q.shape[1]
    q_offset = idx * S_local
    if k_valid is None:
        k_valid = jnp.ones(k.shape[:2], dtype=bool)

    # step 0: the local block needs no communication
    acc, row_max, row_sum = _block_attention(q, k, v, q_offset,
                                             idx * S_local, causal, k_valid)

    def body(step, carry):
        acc, row_max, row_sum, k_blk, v_blk, valid_blk = carry
        # rotate first, then consume: exactly n-1 hops total (the block
        # produced by a final rotation would be discarded)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        valid_blk = jax.lax.ppermute(valid_blk, axis_name, perm)
        src = (idx - (step + 1)) % n
        blk_acc, blk_max, blk_sum = _block_attention(
            q, k_blk, v_blk, q_offset, src * S_local, causal, valid_blk)
        acc, row_max, row_sum = _merge(acc, row_max, row_sum,
                                       blk_acc, blk_max, blk_sum)
        return acc, row_max, row_sum, k_blk, v_blk, valid_blk

    acc, row_max, row_sum, _, _, _ = jax.lax.fori_loop(
        0, n - 1, body, (acc, row_max, row_sum, k, v, k_valid))
    out = acc / jnp.maximum(row_sum, 1e-30)
    return out.astype(q.dtype)


# built fns cached per (mesh, axis, causal): eager callers would otherwise
# re-jit the shard_map wrapper (and recompile) on every invocation
_MAKER_CACHE: dict[tuple, Any] = {}


def make_ring_attention(mesh: Mesh, axis_name: str = "model", causal: bool = True):
    """Build a jitted ring-attention fn: full arrays in, sequence-sharded
    compute via shard_map, full array out. Signature: (q, k, v, valid);
    k/v may be GQA (KV < H) — expansion happens per device, not on the wire."""
    key = ("ring", mesh, axis_name, causal)
    if key in _MAKER_CACHE:
        return _MAKER_CACHE[key]

    spec = P(None, axis_name, None, None)  # [B, S, H, hd] sharded on S
    valid_spec = P(None, axis_name)

    def body(q, k, v, valid):
        return ring_attention_sharded(q, k, v, axis_name=axis_name,
                                      causal=causal, k_valid=valid)

    sharded = jax.shard_map(body, mesh=mesh,
                            in_specs=(spec, spec, spec, valid_spec),
                            out_specs=spec, check_vma=False)
    _MAKER_CACHE[key] = jax.jit(sharded)
    return _MAKER_CACHE[key]


def ulysses_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                              axis_name: str, causal: bool = True,
                              k_valid: jax.Array | None = None) -> jax.Array:
    """Ulysses SP body (under shard_map, sequence sharded on ``axis_name``):
    all-to-all seq→heads, full-sequence attention per head slice, all-to-all
    back. Requires H % axis_size == 0 and KV % axis_size == 0 (GQA k/v are
    resharded at KV width, then expanded per device)."""
    n = jax.lax.psum(1, axis_name)
    # [B, S/n, H, hd] -> [B, S, H/n, hd]
    def scatter_heads(x):
        # split heads into n groups along axis 2, concat seq along axis 1
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def gather_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    q_full = scatter_heads(q)
    k_full = scatter_heads(k)
    v_full = scatter_heads(v)
    group = q_full.shape[2] // k_full.shape[2]
    if group > 1:  # expand GQA heads locally, after the wire transfer
        k_full = jnp.repeat(k_full, group, axis=2)
        v_full = jnp.repeat(v_full, group, axis=2)
    hd = q_full.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_full.astype(jnp.float32),
                        k_full.astype(jnp.float32)) / math.sqrt(hd)
    if causal:
        S = q_full.shape[1]
        mask = jnp.tril(jnp.ones((S, S), dtype=bool))
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    if k_valid is not None:
        # every device needs the full-sequence padding mask
        valid_full = jax.lax.all_gather(k_valid, axis_name, axis=1, tiled=True)
        scores = jnp.where(valid_full[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v_full.astype(jnp.float32))
    return gather_heads(out.astype(q.dtype))


def make_ulysses_attention(mesh: Mesh, axis_name: str = "model",
                           causal: bool = True):
    """Signature: (q, k, v, valid) like make_ring_attention."""
    key = ("ulysses", mesh, axis_name, causal)
    if key in _MAKER_CACHE:
        return _MAKER_CACHE[key]

    spec = P(None, axis_name, None, None)
    valid_spec = P(None, axis_name)

    def body(q, k, v, valid):
        return ulysses_attention_sharded(q, k, v, axis_name=axis_name,
                                         causal=causal, k_valid=valid)

    sharded = jax.shard_map(body, mesh=mesh,
                            in_specs=(spec, spec, spec, valid_spec),
                            out_specs=spec, check_vma=False)
    _MAKER_CACHE[key] = jax.jit(sharded)
    return _MAKER_CACHE[key]
