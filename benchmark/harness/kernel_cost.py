"""Operations and bytes a kernel call NEEDS, from its shapes, and the least
time the chip could take for them. What the algorithm needs, not what an
implementation moves: padding rows, idle slots and re-read pages count
against the kernel, not for it."""

from __future__ import annotations

import json
import os
from typing import Any

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict[str, Any]:
    """The table's row for ``device_kind``; an unknown device is an error,
    never a default."""
    with open(PEAKS_FILE, encoding="utf-8") as handle:
        table = json.load(handle)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE}; "
                       f"it lists {sorted(table)}")
    return table[device_kind]


def decode_attention(context: int, n_heads: int, n_kv_heads: int, head_dim: int,
                     kv_itemsize: int = 2, act_itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of ONE token of ONE layer attending to ``context``
    cached tokens (its own included): Q.K^T and P.V are 2 * 2 * context *
    heads * head_dim operations; K and V are read once, q read, out written."""
    ops = 4.0 * context * n_heads * head_dim
    nbytes = (2.0 * context * n_kv_heads * head_dim * kv_itemsize
              + 2.0 * n_heads * head_dim * act_itemsize)
    return ops, nbytes


def prefill_attention(new_tokens: int, history: int, n_heads: int, n_kv_heads: int,
                      head_dim: int, kv_itemsize: int = 2,
                      act_itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of ONE layer's causal attention of ``new_tokens``
    queries after ``history`` cached tokens: query i sees history + i keys."""
    pairs = new_tokens * history + new_tokens * (new_tokens + 1) / 2.0
    ops = 4.0 * pairs * n_heads * head_dim
    nbytes = (2.0 * (history + new_tokens) * n_kv_heads * head_dim * kv_itemsize
              + 2.0 * new_tokens * n_heads * head_dim * act_itemsize)
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peak: dict[str, Any]) -> tuple[float, str]:
    """The roofline: the larger of operations over peak bf16 rate and bytes
    over peak HBM bandwidth, and which of the two it is."""
    by_ops = ops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (by_ops, "compute") if by_ops >= by_bytes else (by_bytes, "memory")
