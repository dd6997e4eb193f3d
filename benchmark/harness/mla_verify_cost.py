"""Operations and bytes the latent kernel NEEDS in a verify step (beside
``mla_cost.py``, whose ``causal_pairs`` it uses, and ``kernel_cost.py``, whose
``least_seconds`` and ``peaks`` it is used with): a row's few query positions,
each against every cached vector it may see (a model without a selector), the
row's visible vectors read ONCE a layer however many positions look at them.
How the kernel walks pages does not enter; idle slots, padding positions and a
page read once a position count against the kernel, not for it."""

from __future__ import annotations

from .mla_cost import causal_pairs

# tokens of one request that reach the client closer together than this came
# out of one verify step (an accepted draft); a step takes several times as long
SAME_STEP_S = 0.002


def verify_attention(queries: int, context: int, n_heads: int, latent_dim: int,
                     value_dim: int, itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of ONE layer's absorbed-form attention of ONE row's
    verify step: ``queries`` consecutive positions of which the LAST sees
    ``context`` cached vectors (its own included; each earlier one one fewer).
    A (query, vector) pair is one dot product over ``latent_dim`` and one
    weighted sum of ``value_dim`` lanes, a head; the ``context`` vectors are
    read once, the absorbed queries read and the latent-space outputs written
    once."""
    pairs = causal_pairs(queries, context - queries)
    ops = 2.0 * pairs * n_heads * (latent_dim + value_dim)
    nbytes = (context * latent_dim * itemsize
              + queries * n_heads * (latent_dim + value_dim) * itemsize)
    return ops, nbytes


def verify_steps(record, span: tuple[float, float]):
    """``(queries, context)`` of each verify step that emitted tokens of
    ``record`` inside ``span``: tokens that arrived together are one step
    (``SAME_STEP_S``); a step whose first token is the request's ``j``-th
    (from 0; the 0-th is the prefill's) ran on the last token at position
    ``prompt + j - 1`` and on one draft after it, so its last query sees
    ``prompt + j + 1`` vectors; the step that can only emit the request's
    last token carries no draft."""
    lo, hi = span
    times, previous = record.token_times, None
    for j in range(1, len(times)):
        first_of_step = previous is None or times[j] - previous > SAME_STEP_S
        previous = times[j]
        if first_of_step and lo <= times[j] < hi:
            queries = 1 if j >= record.max_tokens - 1 else 2
            yield queries, record.prompt_tokens + j - 1 + queries
