"""The largest float32 threshold a row still passes, without a sort.

A predicate that falls monotonically in its threshold (true up to some cut,
false above it) has its cut found by a 32-step bitwise search over the
order-preserving integer image of float32: the sign first, then one bit at a
time from the top, keeping a bit while the candidate still passes. Each step
is one pass over the row (a compare and a reduction), so the search is exact,
needs no indices and no scratch beyond the row, and traces the same inside a
Pallas kernel body (``ops/mla_attention.py``'s selector: the k-th largest
score) as in a step program (``sampling.py``: the top-k and the nucleus cut of
the vocabulary).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def ordered_bits(x: jax.Array) -> jax.Array:
    """float32 -> int32 whose signed order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def from_ordered_bits(key: jax.Array) -> jax.Array:
    bits = key ^ ((key >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def largest_passing_key(passes: Callable[[jax.Array], jax.Array],
                        shape: tuple[int, ...]) -> jax.Array:
    """``passes``: candidate keys (int32, ``shape``) -> bool of that shape,
    falling monotonically in the candidate. Returns the largest key that
    passes; the smallest int32 where none does."""
    prefix = jnp.where(passes(jnp.zeros(shape, jnp.int32)),
                       jnp.int32(0), jnp.int32(-2 ** 31))

    def body(i, prefix):
        cand = prefix | (jnp.int32(1) << (30 - i))
        return jnp.where(passes(cand), cand, prefix)

    return jax.lax.fori_loop(0, 31, body, prefix)


def kth_largest_key(keys: jax.Array, k) -> jax.Array:
    """keys: [R, C] int32; k: an int or [R, 1] int32, 1 <= k <= C
    -> [R, 1] int32, the k-th largest of each row."""

    def enough(cand):
        count = jnp.sum((keys >= cand).astype(jnp.int32), axis=-1,
                        keepdims=True)
        return count >= k

    return largest_passing_key(enough, (keys.shape[0], 1))
