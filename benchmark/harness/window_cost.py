"""Operations and bytes the attention of a model NEEDS in which ``n_window``
layers see a query's ``window`` newest keys (its own included) and ``n_full``
layers see its whole context: a decode token and a chunk of new tokens, summed
over the layers. As ``kernel_cost.py`` (whose per-layer functions it calls
for the full layers): what the algorithm needs, not what an implementation
moves. A window layer reads no key older than its lowest query's window."""

from __future__ import annotations

from . import kernel_cost


def window_pairs(new_tokens: int, history: int, window: int) -> float:
    """Query-key pairs of ``new_tokens`` queries after ``history`` cached
    tokens where query i (1-based) sees ``min(history + i, window)`` keys."""
    growing = max(0, min(new_tokens, window - history))   # windows not yet full
    return (growing * history + growing * (growing + 1) / 2.0
            + (new_tokens - growing) * float(window))


def window_keys(new_tokens: int, history: int, window: int) -> int:
    """Keys the ``new_tokens`` queries' windows touch between them."""
    return min(history + new_tokens, window + new_tokens - 1)


def decode_token(context: int, n_window: int, n_full: int, window: int,
                 n_heads: int, n_kv_heads: int, head_dim: int,
                 kv_itemsize: int = 2, act_itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of ONE token attending to ``context`` cached tokens
    (its own included) in every layer: ``min(context, window)`` of them in a
    window layer."""
    geometry = (n_heads, n_kv_heads, head_dim, kv_itemsize, act_itemsize)
    full_ops, full_bytes = kernel_cost.decode_attention(context, *geometry)
    win_ops, win_bytes = kernel_cost.decode_attention(min(context, window), *geometry)
    return (n_full * full_ops + n_window * win_ops,
            n_full * full_bytes + n_window * win_bytes)


def chunk(new_tokens: int, history: int, n_window: int, n_full: int, window: int,
          n_heads: int, n_kv_heads: int, head_dim: int, kv_itemsize: int = 2,
          act_itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of the causal attention of ``new_tokens`` queries
    after ``history`` cached tokens in every layer."""
    full_ops, full_bytes = kernel_cost.prefill_attention(
        new_tokens, history, n_heads, n_kv_heads, head_dim, kv_itemsize, act_itemsize)
    win_ops = 4.0 * window_pairs(new_tokens, history, window) * n_heads * head_dim
    win_bytes = (2.0 * window_keys(new_tokens, history, window) * n_kv_heads
                 * head_dim * kv_itemsize
                 + 2.0 * new_tokens * n_heads * head_dim * act_itemsize)
    return (n_full * full_ops + n_window * win_ops,
            n_full * full_bytes + n_window * win_bytes)


def layers_of(model) -> tuple[int, int, int] | None:
    """(window layers, full layers, window) of a model configuration that has
    them (``layers_of`` and ``sliding_window``), else None."""
    window = getattr(model, "sliding_window", None)
    if not window or not hasattr(model, "layers_of"):
        return None
    return len(model.layers_of("window")), len(model.layers_of("full")), int(window)
