"""Sharding rules: logical axis names -> PartitionSpec -> NamedSharding.

1D megatron TP over the ``model`` axis (SURVEY.md §2.7): attention QKV and
FFN up-projections shard their output dim; attention output and FFN
down-projections shard their input dim, so each block needs exactly one
psum (inserted automatically by XLA under pjit). Embedding + LM head shard
the vocab dim. Norms replicate.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical name -> PartitionSpec factory
LOGICAL_RULES: dict[str, P] = {
    "replicated": P(),
    "vocab_in": P("model", None),         # embedding table (vocab, dim)
    "vocab_out": P(None, "model"),        # lm head (dim, vocab)
    "attn_qkv": P(None, "model"),         # (dim, heads*hd) column-parallel
    "attn_out": P("model", None),         # (heads*hd, dim) row-parallel
    "ffn_up": P(None, "model"),           # (dim, hidden) column-parallel
    "ffn_down": P("model", None),         # (hidden, dim) row-parallel
    # MoE stacked experts (E, dim, hidden)/(E, hidden, dim): megatron
    # WITHIN each expert under plain TP (same comms as dense); an
    # 'expert'-axis mesh shards the stack instead (shard_moe_params)
    "moe_up": P(None, None, "model"),
    "moe_down": P(None, "model", None),
    "scale_moe_model": P(None, "model"),  # [E, hidden] expert-stack scales
    "scale_moe": P(None, None),           # [E, dim]
    # int8 per-channel scale vectors indexed by a model-sharded axis
    # (quantize.py): shard with the channels they scale
    "scale_model": P("model"),
    "kv_pages": P(None, None, None, "model", None),  # (L, pages, page, kv_heads, hd)
    # int8 KV-page dequant scales (L, pages, kv_heads): shard the kv-head
    # dim with the pages they scale
    "kv_scales": P(None, None, "model"),
    # the latent family's pools (L, pages, page, d): one vector a token
    # shared by all heads, so nothing to split over ``model``
    "latent_pages": P(),
    # the hybrid family's per-sequence pools (L, rows, ...): the recurrent
    # state and the convolution tail, whole on every chip (the family
    # refuses a model axis wider than one device)
    "state_pool": P(),
    "activations": P("data", None, None),  # (batch, seq, dim)
    "decode_heads": P("data", None, "model", None),  # (batch, seq, heads, hd)
}


def logical_to_sharding(logical: str, mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, LOGICAL_RULES[logical])


def param_specs(params_logical: dict[str, Any], mesh: Mesh):
    """Map a pytree of logical names to a pytree of NamedShardings."""
    return jax.tree.map(lambda name: logical_to_sharding(name, mesh), params_logical)


def kv_pages_sharding(mesh: Mesh, n_kv_heads: int) -> NamedSharding:
    """Paged-KV sharding: kv-head dim over ``model`` when divisible (the
    v5e-8 × Llama-3-8B case: 8 kv heads / TP=8), else replicated (GQA models
    whose kv heads don't divide the TP degree — XLA all-gathers the sharded
    k/v projections into the replicated cache)."""
    model_size = mesh.shape.get("model", 1)
    if n_kv_heads % model_size == 0:
        return NamedSharding(mesh, LOGICAL_RULES["kv_pages"])
    return NamedSharding(mesh, P())


def kv_scales_sharding(mesh: Mesh, n_kv_heads: int) -> NamedSharding:
    """Int8 KV scale sharding: tracks kv_pages_sharding — the scale of a
    model-sharded page shard lives on the same chip as its values."""
    model_size = mesh.shape.get("model", 1)
    if n_kv_heads % model_size == 0:
        return NamedSharding(mesh, LOGICAL_RULES["kv_scales"])
    return NamedSharding(mesh, P())


def shard_params(params: dict[str, Any], params_logical: dict[str, Any], mesh: Mesh):
    """Place a (host or single-device) param pytree onto the mesh."""
    shardings = param_specs(params_logical, mesh)
    return jax.tree.map(jax.device_put, params, shardings)
