"""On-device token sampling: greedy / temperature / top-k / top-p.

Fully vectorized over the decode batch with per-slot parameters so one
compiled function serves heterogeneous requests (SURVEY.md §7.1 phase 3.4).
A step does the work its batch's parameters ask for, chosen inside the step
program from two scalars of them (``SamplingParams.tiers``): the argmax and
nothing else while no row samples, one categorical draw while no sampled row
filters, and otherwise the top-k / top-p masks found by a threshold search
(``ops/threshold_search.py``). No tier sorts the vocabulary.

A family that generates by diffusion over blocks (``models/sdar.py``) also
needs each token's CONFIDENCE, the probability of the chosen token under the
distribution it was drawn from (:func:`sample_with_confidence`), and the rule
that says which of a block's masked positions a pass fills
(:func:`fill_positions`); both run inside the step program.
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


def _drawn(logits: jax.Array, params: SamplingParams, key: jax.Array,
           greedy: jax.Array, filters: jax.Array
           ) -> tuple[jax.Array, jax.Array]:
    """The draw of the tiers that sample: (token ids [B], the scaled and
    filtered logits [B, V] the sampled rows were drawn from)."""
    temp = jnp.maximum(params.temperature, 1e-6)[:, None]
    scaled = logits / temp
    masked = jax.lax.cond(filters, _filtered, lambda s, _: s,
                          scaled, params)
    sampled = jax.random.categorical(key, masked, axis=-1)
    return jnp.where(params.temperature <= 0.0, greedy, sampled), masked


def sample_tokens(logits: jax.Array, params: SamplingParams,
                  key: jax.Array) -> jax.Array:
    """logits: [B, V] fp32 -> token ids [B]."""
    greedy = jnp.argmax(logits, axis=-1)
    samples, filters = params.tiers()
    return jax.lax.cond(
        samples, lambda: _drawn(logits, params, key, greedy, filters)[0],
        lambda: greedy)


def _probability(dist: jax.Array, tokens: jax.Array) -> jax.Array:
    """softmax(dist)[tokens] a row, float32."""
    picked = jnp.take_along_axis(dist, tokens[:, None], axis=-1)[:, 0]
    return jnp.exp(picked - jax.nn.logsumexp(dist, axis=-1))


def sample_with_confidence(logits: jax.Array, params: SamplingParams,
                           key: jax.Array) -> tuple[jax.Array, jax.Array]:
    """logits: [B, V] fp32 -> (token ids [B] as :func:`sample_tokens` draws
    them, confidence [B] float32): the probability of the chosen token under
    the distribution it was drawn from, the plain softmax of the logits for
    a greedy row, the softmax of the scaled and filtered logits for a
    sampled one. The tiers are :func:`sample_tokens`'s."""
    greedy = jnp.argmax(logits, axis=-1)
    samples, filters = params.tiers()

    def draw():
        tokens, masked = _drawn(logits, params, key, greedy, filters)
        dist = jnp.where((params.temperature <= 0.0)[:, None], logits, masked)
        return tokens, _probability(dist, tokens)

    return jax.lax.cond(samples, draw,
                        lambda: (greedy, _probability(logits, greedy)))


def fill_counts(block_length: int, denoising_steps: int) -> tuple[int, ...]:
    """How many positions pass s of a block fills at least: ``block_length //
    denoising_steps``, the remainder one each to the earliest passes."""
    base, extra = divmod(block_length, denoising_steps)
    return tuple(base + (s < extra) for s in range(denoising_steps))


def fill_positions(confidence: jax.Array, masked: jax.Array,
                   count: jax.Array, threshold: float
                   ) -> tuple[jax.Array, jax.Array]:
    """Which masked positions of each block a denoise pass fills
    (``low_confidence_dynamic``): confidence, masked [B, L]; ``count``: this
    pass's least number (a scalar). Every masked position whose confidence
    is above ``threshold`` where those are at least ``count``, else the
    ``count`` masked positions of highest confidence, ties to the lower
    position (all of them where fewer are masked). -> (fill [B, L] bool,
    by_threshold [B] bool: the row took the first branch and filled)."""
    L = masked.shape[-1]
    over = masked & (confidence > threshold)
    enough = (jnp.sum(over, axis=-1) >= count) & jnp.any(over, axis=-1)
    c = jnp.where(masked, confidence, -1.0)
    at = jnp.arange(L)
    ahead = (c[:, None, :] > c[:, :, None]) | (
        (c[:, None, :] == c[:, :, None]) & (at[None, None, :] < at[None, :, None]))
    rank = jnp.sum(ahead, axis=-1)                      # [B, L], 0 = first
    top = masked & (rank < count)
    return jnp.where(enough[:, None], over, top), enough
