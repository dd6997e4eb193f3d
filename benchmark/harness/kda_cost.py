"""Operations and bytes the Kimi-Delta-Attention layers' kernels NEED (beside
``gdn_cost.py``, whose rule it keeps: the recurrence itself on the tokens that
are real, whatever implements it). The rule with a decay a key CHANNEL is the
scalar form's an entry of the state (``Diag(alpha) S`` is one multiply an
entry, as ``alpha S`` is); what differs is what a token brings: ``d_k`` decay
floats a head where the scalar form brings one."""

from __future__ import annotations

from .gdn_cost import OPS_PER_STATE_ENTRY, state_bytes


def token_bytes(n_heads: int, key_dim: int, value_dim: int,
                act_itemsize: int = 2, gate_itemsize: int = 4) -> float:
    """What ONE token of ONE KDA layer brings and takes: q, k, v read and o
    written in the activations' precision, a head's ``d_k`` decays and its
    beta in float32."""
    return float(n_heads * ((2 * key_dim + 2 * value_dim) * act_itemsize
                            + (key_dim + 1) * gate_itemsize))


def kda_step(n_heads: int, key_dim: int, value_dim: int) -> tuple[float, float]:
    """(operations, bytes) of ONE decode token of ONE KDA layer: its row's
    state is read and written once, and the recurrence runs once over it."""
    ops = OPS_PER_STATE_ENTRY * n_heads * key_dim * value_dim
    nbytes = (2.0 * state_bytes(n_heads, key_dim, value_dim)
              + token_bytes(n_heads, key_dim, value_dim))
    return ops, nbytes


def kda_chunk(tokens: float, n_heads: int, key_dim: int,
              value_dim: int) -> tuple[float, float]:
    """(operations, bytes) of ``tokens`` real prompt tokens of one row of ONE
    KDA layer: the recurrence a token, each token's vectors once, and the
    row's state read and written ONCE (a prompt of 16 chunk rounds moves it
    16 times; the algorithm needs it once)."""
    ops = OPS_PER_STATE_ENTRY * tokens * n_heads * key_dim * value_dim
    nbytes = (tokens * token_bytes(n_heads, key_dim, value_dim)
              + 2.0 * state_bytes(n_heads, key_dim, value_dim))
    return ops, nbytes


def kda_layers(model) -> int | None:
    """How many of the model's layers are KDA layers (a model configuration
    that names a ``gate_rank``: the low-rank decay is Kimi's); None for a
    model configuration of another family."""
    if not hasattr(model, "gate_rank"):
        return None
    return len(model.layers_of("linear_attention"))
