"""Operations and bytes the attention of a BLOCK step NEEDS (beside
``kernel_cost.py``, whose ``least_seconds`` and ``peaks`` they are used with),
for a model that generates by diffusion over blocks: a block of ``b``
positions after ``c`` cached tokens is, a layer and a denoise pass, ``b``
queries that each see all ``c + b`` keys (the block is bidirectional inside
itself), and one read of those ``c + b`` tokens of K and V. What the fill rule
needs, not what an implementation runs: the commit pass, rows that ride along
a lockstep dispatch after their block is full, idle rows and re-read pages
count against the kernel, not for it."""

from __future__ import annotations

import bisect
from typing import Any, Iterable


def block_attention(block: int, cached: int, n_heads: int, n_kv_heads: int,
                    head_dim: int, kv_itemsize: int = 2,
                    act_itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of ONE denoise pass of ONE layer over a block of
    ``block`` positions after ``cached`` tokens: Q.K^T and P.V are 2 * 2 *
    block * (cached + block) * heads * head_dim operations; K and V of the
    cached tokens and the block are read once, q read, out written."""
    seen = cached + block
    ops = 4.0 * block * seen * n_heads * head_dim
    nbytes = (2.0 * seen * n_kv_heads * head_dim * kv_itemsize
              + 2.0 * block * n_heads * head_dim * act_itemsize)
    return ops, nbytes


def blocks_of(prompt_tokens: int, token_times: list[float], block: int
              ) -> Iterable[tuple[int, float]]:
    """``(cached tokens before the block, when the client saw it)`` for each
    block of one request: the first block starts at the last block boundary at
    or below the prompt's end (the prompt's remainder leads it), every later
    one ``block`` positions on; a block is seen when its first token is."""
    start = prompt_tokens - prompt_tokens % block
    at = 0
    while at < len(token_times):
        yield start, token_times[at]
        at += block - (prompt_tokens % block if start < prompt_tokens else 0)
        start += block


def block_length(model: Any) -> int | None:
    """The model's block length; None for a configuration of another family."""
    return getattr(model, "block_length", None)


def passes_at(steps: list, default: float = 0.0):
    """``at -> denoise passes`` of the block step that produced what the
    client saw at ``at``: the last of ``steps`` (by ``t_retired``) retired at
    or before it."""
    steps = sorted(steps, key=lambda s: s.t_retired)
    retired = [s.t_retired for s in steps]

    def lookup(at: float) -> float:
        i = bisect.bisect_right(retired, at) - 1
        return steps[i].counts.denoise_passes if i >= 0 else default
    return lookup


def block_steps(view: Any, window: tuple[float, float]) -> list:
    """The window's decode step records that are block steps (their counts
    carry ``denoise_passes``, and a block step makes at least one); none from
    a program, or a model family, without them."""
    return [s for s in view.decode_steps(window)
            if getattr(getattr(s, "counts", None), "denoise_passes", 0) > 0]
