"""Expert parallelism: MoE FFN sharded over an ``expert`` mesh axis.

SURVEY.md §2.7 EP: expert-parallel FFN for MoE checkpoints. Idiomatic
pjit formulation (the repo's stated design philosophy — annotate
shardings, let XLA insert the collectives): top-k routing builds
dispatch/combine tensors, the dispatched token buffer and the stacked
expert weights carry ``expert``-axis sharding constraints, and XLA lowers
the dispatch einsum to the all_to_all over ICI (the hand-written NCCL
alltoall of GPU MoE stacks).

Capacity discipline keeps shapes static (XLA requirement): each expert
processes at most ``capacity = ceil(tokens/experts * capacity_factor)``
tokens; overflow tokens fall back to the residual stream (standard
Switch-Transformer drop policy).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class MoEConfig:
    dim: int
    n_experts: int
    expert_hidden: int
    top_k: int = 2
    capacity_factor: float = 1.25


def init_moe_params(config: MoEConfig, key: jax.Array,
                    dtype=jnp.float32) -> dict[str, Any]:
    keys = jax.random.split(key, 4)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, dtype=jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(dtype)

    E, D, F = config.n_experts, config.dim, config.expert_hidden
    return {
        "router": dense(keys[0], (D, E), D),
        "w1": dense(keys[1], (E, D, F), D),   # stacked per expert
        "w3": dense(keys[2], (E, D, F), D),
        "w2": dense(keys[3], (E, F, D), F),
    }


def moe_logical() -> dict[str, str]:
    return {"router": "replicated", "w1": "expert_stack",
            "w3": "expert_stack", "w2": "expert_stack"}


def shard_moe_params(params: dict[str, Any], mesh: Mesh,
                     axis_name: str = "expert") -> dict[str, Any]:
    """Experts sharded across the axis; the router replicates."""
    expert_sharding = NamedSharding(mesh, P(axis_name, None, None))
    replicated = NamedSharding(mesh, P())
    return {
        "router": jax.device_put(params["router"], replicated),
        "w1": jax.device_put(params["w1"], expert_sharding),
        "w3": jax.device_put(params["w3"], expert_sharding),
        "w2": jax.device_put(params["w2"], expert_sharding),
    }


def _top_k_routing(logits: jax.Array, k: int, capacity: int
                   ) -> tuple[jax.Array, jax.Array]:
    """Returns (dispatch [T, E, C] bool-ish, combine [T, E, C] float).

    Position within each expert's capacity buffer is the token's rank among
    tokens routed to that expert (cumsum over the token axis — deterministic,
    order-dependent like Switch)."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    _, top_idx = jax.lax.top_k(logits, k)                     # [T, k]
    one_hot = jax.nn.one_hot(top_idx, E, dtype=logits.dtype)  # [T, k, E]
    gates = probs[:, None, :] * one_hot                       # [T, k, E]
    # renormalize the selected gates so they sum to 1 per token
    denom = jnp.sum(gates, axis=(1, 2), keepdims=True)
    gates = gates / jnp.maximum(denom, 1e-9)

    # rank of each (token, slot) within its expert
    flat_assign = one_hot                                     # [T, k, E]
    positions = (jnp.cumsum(flat_assign.reshape(T * k, E), axis=0)
                 - flat_assign.reshape(T * k, E)).reshape(T, k, E)
    in_capacity = positions < capacity
    pos_one_hot = jax.nn.one_hot(
        jnp.sum(positions * flat_assign, axis=-1).astype(jnp.int32),
        capacity, dtype=logits.dtype)                          # [T, k, C]
    keep = flat_assign * in_capacity                           # [T, k, E]
    dispatch = jnp.einsum("tke,tkc->tec", keep, pos_one_hot)
    combine = jnp.einsum("tke,tkc->tec",
                         gates * in_capacity, pos_one_hot)
    return dispatch, combine


def moe_ffn(params: dict[str, Any], x: jax.Array, config: MoEConfig,
            axis_name: str = "expert") -> jax.Array:
    """MoE SwiGLU FFN. x: [B, S, D] -> [B, S, D].

    With params placed by ``shard_moe_params`` and this running under jit
    on the mesh, the dispatched [E, C, D] buffer is constrained to the
    expert axis, so the dispatch/return einsums lower to all_to_all."""
    B, S, D = x.shape
    T = B * S
    flat = x.reshape(T, D)
    capacity = max(1, int(math.ceil(T / config.n_experts
                                    * config.capacity_factor)))
    logits = (flat @ params["router"]).astype(jnp.float32)
    dispatch, combine = _top_k_routing(logits, config.top_k, capacity)
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(x.dtype)

    dispatched = jnp.einsum("td,tec->ecd", flat, dispatch)  # [E, C, D]
    try:  # constrain to the expert axis when running inside that mesh
        dispatched = jax.lax.with_sharding_constraint(
            dispatched, P(axis_name, None, None))
    except (ValueError, RuntimeError, NameError):
        pass  # no mesh context: single-device execution

    def expert_ffn(w1, w3, w2, tokens):                     # [C, D] per expert
        return (jax.nn.silu(tokens @ w1) * (tokens @ w3)) @ w2

    expert_out = jax.vmap(expert_ffn)(params["w1"], params["w3"],
                                      params["w2"], dispatched)  # [E, C, D]
    out = jnp.einsum("ecd,tec->td", expert_out, combine)
    return out.reshape(B, S, D)


def router_probs(router: Any, flat: jax.Array) -> jax.Array:
    """Router softmax probabilities [T, E]; handles a quantized router
    (the ONE place routing math lives — the serving FFN and the training
    aux loss must never drift)."""
    from ..quantize import qmm

    logits = (qmm(flat, router) if isinstance(router, dict)
              else flat @ router)
    return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)


def moe_ffn_dense_mask(params: dict[str, Any], x: jax.Array,
                       config: MoEConfig, act: str = "silu") -> jax.Array:
    """Drop-free routed FFN as a scan over EXPERTS with gate masks.

    The serving formulation: every expert runs over all T tokens and the
    top-k gate mask zeroes the rest. Per-token output is EXACTLY the
    reference function (no capacity drops), so it is invariant to batch
    shape — the property continuous batching needs (prefill + decode must
    equal one long prefill; capacity dispatch violates it whenever a
    batch-dependent drop occurs). Costs E/k x the ideal FFN FLOPs and
    O(T*F) transient memory per expert step (vs the [T,E,C] dispatch
    tensors of ``moe_ffn``, quadratic in T when run drop-free).
    Quantized expert stacks work unchanged: the scan slices the [E,...]
    int8/scale leaves into the 2D shapes ``qmm`` handles.
    """
    B, S, D = x.shape
    flat = x.reshape(-1, D)
    probs = router_probs(params["router"], flat)              # [T, E]
    _, top_idx = jax.lax.top_k(probs, config.top_k)
    one_hot = jax.nn.one_hot(top_idx, config.n_experts,
                             dtype=jnp.float32)               # [T, k, E]
    keep = jnp.sum(one_hot, axis=1)                           # [T, E]
    gates = probs * keep
    gates = gates / jnp.maximum(
        jnp.sum(gates, axis=-1, keepdims=True), 1e-9)         # renormalized
    gates = gates.astype(x.dtype)
    return expert_scan(params, flat, gates, act).reshape(B, S, D)


def expert_scan(params: dict[str, Any], flat: jax.Array, gates: jax.Array,
                act: str = "silu") -> jax.Array:
    """Every held expert over every token, weighted by its gate column: flat
    [T, D], gates [T, E] (zero where a token did not choose the expert) ->
    [T, D]. ``params`` holds the stacks ``w1``/``w3``/``w2`` of the E experts
    the gate columns name — all of a model's, or the share held here."""
    from ..quantize import qmm

    def one_expert(acc, weights):
        w1, w3, w2, gate_col = weights                        # gate_col [T]
        h = qmm(flat, w1)
        h = (jax.nn.gelu(h, approximate=True) if act == "gelu"
             else jax.nn.silu(h))
        h = qmm(h * qmm(flat, w3), w2)                        # [T, D]
        return acc + gate_col[:, None] * h, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(flat),
        (params["w1"], params["w3"], params["w2"], gates.T))
    return out


def moe_ffn_reference(params: dict[str, Any], x: jax.Array,
                      config: MoEConfig) -> jax.Array:
    """Dense per-token loop over selected experts (no capacity drops) —
    the numerics oracle for tests (matches moe_ffn when nothing drops)."""
    B, S, D = x.shape
    flat = x.reshape(-1, D)
    logits = (flat @ params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    _, top_idx = jax.lax.top_k(logits, config.top_k)
    out = jnp.zeros_like(flat)
    for slot in range(config.top_k):
        idx = top_idx[:, slot]                                # [T]
        gate = jnp.take_along_axis(probs, idx[:, None], axis=1)[:, 0]
        w1 = params["w1"][idx]                                # [T, D, F]
        w3 = params["w3"][idx]
        w2 = params["w2"][idx]
        hidden = jax.nn.silu(jnp.einsum("td,tdf->tf", flat, w1)) * \
            jnp.einsum("td,tdf->tf", flat, w3)
        out = out + gate[:, None] * jnp.einsum("tf,tfd->td", hidden, w2)
    denom = jnp.take_along_axis(probs, top_idx, axis=1).sum(axis=1)
    out = out / jnp.maximum(denom, 1e-9)[:, None]
    return out.reshape(B, S, D)
