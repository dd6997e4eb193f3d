"""Operations and bytes the gated delta-rule layers' kernels NEED (beside
``kernel_cost.py``, whose ``least_seconds`` and ``peaks`` they are used with):
the recurrence itself on the tokens that are real, whatever implements it. The
bucket's padding, idle decode rows, a padded state layout and a chunked
algorithm's own extra arithmetic count against a kernel, not for it."""

from __future__ import annotations

# S <- alpha S; S^T k; v - .; beta .; S += k (.)^T; S^T q: seven operations an
# entry of the state (two multiply-adds count as two each)
OPS_PER_STATE_ENTRY = 7.0


def state_bytes(n_heads: int, key_dim: int, value_dim: int,
                state_itemsize: int = 4) -> float:
    """One sequence's state of ONE linear layer, unpadded."""
    return float(n_heads * key_dim * value_dim * state_itemsize)


def token_bytes(n_heads: int, key_dim: int, value_dim: int,
                act_itemsize: int = 2, gate_itemsize: int = 4) -> float:
    """What ONE token of ONE linear layer brings and takes: q, k, v read and o
    written in the activations' precision, g and beta in float32."""
    return float(n_heads * ((2 * key_dim + 2 * value_dim) * act_itemsize
                            + 2 * gate_itemsize))


def delta_step(n_heads: int, key_dim: int, value_dim: int) -> tuple[float, float]:
    """(operations, bytes) of ONE decode token of ONE linear layer: its row's
    state is read and written once, and the recurrence runs once over it."""
    ops = OPS_PER_STATE_ENTRY * n_heads * key_dim * value_dim
    nbytes = (2.0 * state_bytes(n_heads, key_dim, value_dim)
              + token_bytes(n_heads, key_dim, value_dim))
    return ops, nbytes


def delta_chunk(tokens: float, n_heads: int, key_dim: int,
                value_dim: int) -> tuple[float, float]:
    """(operations, bytes) of ``tokens`` real prompt tokens of one row of ONE
    linear layer: the recurrence a token, each token's vectors once, and the
    row's state read and written once."""
    ops = OPS_PER_STATE_ENTRY * tokens * n_heads * key_dim * value_dim
    nbytes = (tokens * token_bytes(n_heads, key_dim, value_dim)
              + 2.0 * state_bytes(n_heads, key_dim, value_dim))
    return ops, nbytes


def linear_layers(model) -> int | None:
    """How many of the model's layers are gated delta-rule layers; None for a
    model configuration of another family."""
    if not hasattr(model, "linear_key_dim"):
        return None
    return len(model.layers_of("linear_attention"))
