"""Operations and bytes the latent family's kernels NEED (beside
``kernel_cost.py``, whose ``least_seconds`` and ``peaks`` they are used with):
each query against the tokens it SELECTS, not against everything a
masked-dense kernel happens to read. Padding rows, idle slots, pages re-read a
head block and masked-out tokens count against a kernel, not for it."""

from __future__ import annotations


def selected_pairs(new_tokens: int, history: int, topk: int) -> float:
    """(query, token) pairs of ``new_tokens`` queries after ``history`` cached
    tokens when query i attends to the ``min(history + i + 1, topk)`` tokens it
    selected."""
    first, last = history + 1, history + new_tokens        # contexts, inclusive
    full = max(0, min(last, topk) - first + 1)             # contexts <= topk
    below = (first + min(last, topk)) * full / 2.0 if full else 0.0
    return below + (new_tokens - full) * float(topk)


def causal_pairs(new_tokens: int, history: int) -> float:
    return new_tokens * history + new_tokens * (new_tokens + 1) / 2.0


def mla_attention(new_tokens: int, history: int, n_heads: int, latent_dim: int,
                  value_dim: int, topk: int, itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of ONE layer's absorbed-form attention of
    ``new_tokens`` queries after ``history`` cached tokens: a selected pair is
    one dot product over the cached vector (``latent_dim``) and one weighted
    sum of its first ``value_dim`` lanes, a head; every cached vector the
    queries can see is read once, the absorbed queries read and the
    latent-space outputs written once."""
    ops = 2.0 * selected_pairs(new_tokens, history, topk) * n_heads * (
        latent_dim + value_dim)
    nbytes = ((history + new_tokens) * latent_dim * itemsize
              + new_tokens * n_heads * (latent_dim + value_dim) * itemsize)
    if new_tokens == 1:                 # a decode token reads only what it selected
        nbytes = (min(history + 1, topk) * latent_dim * itemsize
                  + n_heads * (latent_dim + value_dim) * itemsize)
    return ops, nbytes


def index_scores(new_tokens: int, history: int, n_heads: int, head_dim: int,
                 query_block: int = 256, itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of ONE layer's index scores of ``new_tokens``
    queries after ``history`` cached tokens: ``n_heads * head_dim`` multiply-adds
    a causal (query, token) pair; the index keys the queries can see are read
    once a block of ``query_block`` queries, the selector's queries once, and a
    float32 score is written a causal pair."""
    pairs = causal_pairs(new_tokens, history)
    ops = 2.0 * pairs * n_heads * head_dim
    blocks = -(-new_tokens // query_block)
    nbytes = (blocks * (history + new_tokens) * head_dim * itemsize
              + new_tokens * n_heads * head_dim * itemsize + 4.0 * pairs)
    return ops, nbytes


def prompts_in_span(records, trace_span):
    """(record, share) of every prompt whose prefill (send to first token)
    overlaps the traced span; ``share`` is the part of that prefill inside it."""
    lo, hi = trace_span
    for r in records:
        if r.token_times:
            start, end = r.sent, r.token_times[0]
            inside = max(0.0, min(end, hi) - max(start, lo))
            if inside > 0:
                yield r, inside / max(end - start, 1e-9)
