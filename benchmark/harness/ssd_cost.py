"""Operations and bytes the Mamba-2 state-space layers' kernels NEED (beside
``gdn_cost.py``, same rule, used with ``kernel_cost.least_seconds``): the
recurrence itself on the tokens that are real and the rows that are live,
whatever implements it. The bucket's padding, idle decode rows and a chunked
algorithm's own extra arithmetic count against a kernel, not for it."""

from __future__ import annotations

# S <- a S (one), S += B (dt x)^T (a multiply-add: two), y = S^T C (a
# multiply-add: two): five operations an entry of the state a token
OPS_PER_STATE_ENTRY = 5.0


def state_bytes(n_heads: int, head_dim: int, d_state: int,
                state_itemsize: int = 4) -> float:
    """One sequence's state of ONE Mamba layer."""
    return float(d_state * n_heads * head_dim * state_itemsize)


def token_bytes(n_heads: int, head_dim: int, d_state: int,
                act_itemsize: int = 2, step_itemsize: int = 4) -> float:
    """What ONE token of ONE Mamba layer brings and takes: x read and y
    written, the one group's B and C read, in the activations' precision; dt
    in float32."""
    return float((2 * n_heads * head_dim + 2 * d_state) * act_itemsize
                 + n_heads * step_itemsize)


def ssd_step(n_heads: int, head_dim: int, d_state: int) -> tuple[float, float]:
    """(operations, bytes) of ONE decode token of ONE Mamba layer: its row's
    state is read and written once, and the recurrence runs once over it."""
    ops = OPS_PER_STATE_ENTRY * d_state * n_heads * head_dim
    nbytes = (2.0 * state_bytes(n_heads, head_dim, d_state)
              + token_bytes(n_heads, head_dim, d_state))
    return ops, nbytes


def ssd_chunk(tokens: float, n_heads: int, head_dim: int,
              d_state: int) -> tuple[float, float]:
    """(operations, bytes) of ``tokens`` real prompt tokens of one row of ONE
    Mamba layer: the recurrence a token, each token's vectors once, and the
    row's state read and written once."""
    ops = OPS_PER_STATE_ENTRY * tokens * d_state * n_heads * head_dim
    nbytes = (tokens * token_bytes(n_heads, head_dim, d_state)
              + 2.0 * state_bytes(n_heads, head_dim, d_state))
    return ops, nbytes


def mamba_layers(model) -> int | None:
    """How many of the model's layers are Mamba-2 layers; None for a model
    configuration of another family."""
    if not hasattr(model, "mamba_d_state"):
        return None
    return len(model.layers_of("linear_attention"))
