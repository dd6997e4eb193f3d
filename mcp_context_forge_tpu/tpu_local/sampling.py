"""On-device token sampling: greedy / temperature / top-k / top-p.

Fully vectorized over the decode batch with per-slot parameters so one
compiled function serves heterogeneous requests (SURVEY.md §7.1 phase 3.4).
A step does the work its batch's parameters ask for, chosen inside the step
program from two scalars of them (``SamplingParams.tiers``): the argmax and
nothing else while no row samples, one categorical draw while no sampled row
filters, and otherwise the top-k / top-p masks found by a threshold search
(``ops/threshold_search.py``). No tier sorts the vocabulary.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .ops.threshold_search import (kth_largest_key, largest_passing_key,
                                   ordered_bits)


class SamplingParams(NamedTuple):
    """Per-slot arrays, all [B]."""

    temperature: jax.Array  # 0 => greedy
    top_k: jax.Array        # 0 => disabled
    top_p: jax.Array        # 1.0 => disabled

    def tiers(self):
        """(some row samples, some sampled row filters): what decides a
        step's work, as scalars of the arrays' own kind, so the host counts
        from its numpy rows what the step program branches on."""
        samples = self.temperature > 0.0
        filters = samples & ((self.top_k > 0) | (self.top_p < 1.0))
        return samples.any(), filters.any()


def _filtered(scaled: jax.Array, params: SamplingParams) -> jax.Array:
    """scaled: [B, V] -> the same with everything outside a row's top-k and
    nucleus at -inf. Both cuts are the largest threshold whose kept set is
    still large enough (k entries; ``top_p`` of the softmax mass), ties at
    the threshold kept: what a descending sort and a running sum find."""
    V = scaled.shape[-1]
    # -0.0 ties +0.0, as in a sort
    keys = ordered_bits(jnp.where(scaled == 0.0, 0.0, scaled))
    k = jnp.clip(params.top_k, 0, V)
    kth = kth_largest_key(keys, jnp.where(k > 0, k, V)[:, None])

    probs = jax.nn.softmax(scaled, axis=-1)

    def heavy_enough(cand):
        mass = jnp.sum(jnp.where(keys >= cand, probs, 0.0), axis=-1,
                       keepdims=True)
        return mass >= params.top_p[:, None]

    # no threshold passes where rounding keeps the whole mass under top_p:
    # the smallest key, which keeps the row whole
    cut = largest_passing_key(heavy_enough, kth.shape)
    return jnp.where(keys >= jnp.maximum(kth, cut), scaled, -jnp.inf)


def sample_tokens(logits: jax.Array, params: SamplingParams,
                  key: jax.Array) -> jax.Array:
    """logits: [B, V] fp32 -> token ids [B]."""
    greedy = jnp.argmax(logits, axis=-1)
    samples, filters = params.tiers()

    def draw():
        temp = jnp.maximum(params.temperature, 1e-6)[:, None]
        scaled = logits / temp
        masked = jax.lax.cond(filters, _filtered, lambda s, _: s,
                              scaled, params)
        sampled = jax.random.categorical(key, masked, axis=-1)
        return jnp.where(params.temperature <= 0.0, greedy, sampled)

    return jax.lax.cond(samples, draw, lambda: greedy)
