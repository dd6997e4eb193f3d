"""Llama-3-class decoder, TPU-first functional implementation.

Pure pytree params (dict-of-arrays) + jit-compiled prefill/decode functions —
no module framework on the hot path so pjit sees plain matmuls the MXU can
tile. GQA attention, RoPE, RMSNorm, SwiGLU. Sharding is 1D megatron TP over
the ``model`` mesh axis (parallel/sharding.py); the paged KV cache shards the
kv-head dim so decode attention never crosses chips.

Design notes (BASELINE.json north star):
- prefill: [B, S] bucketed static shapes; causal attention via the Pallas
  flash kernel (ops/attention.py) on TPU, jnp reference elsewhere.
- decode: fixed-capacity [B, 1] step over the paged cache; pages walked by
  the Pallas paged kernel on TPU, gathered by block table elsewhere — fixed
  shapes, no recompilation per step.
- which implementation runs is the CALLER's choice (``attn_impl`` /
  ``paged_impl``): the engine resolves it from its mesh's devices through
  ops/attention.py's ``select_*`` functions. The defaults here are the
  references, so nothing in this module looks at ``jax.devices()``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from .configs import LlamaConfig
from ..ops.attention import (FLASH_BLOCK, causal_attention, on_tpu,
                             select_paged_attention, select_prefill_attention)
from ..kv.paged_cache import (PagedKVState, write_prefill_kv, write_decode_kv,  # noqa: F401 (family names)
                              gather_kv, init_kv_state, kv_logical,
                              kv_page_bytes)
from ..quantize import embed_rows, qmm, qmm_t


# ------------------------------------------------------------------ building blocks

def rms_norm(x: jax.Array, weight: jax.Array, eps: float,
             plus_one: bool = False) -> jax.Array:
    """``plus_one``: Gemma checkpoints store zero-centered norm weights
    and scale by (1 + w) — static at trace time."""
    orig_dtype = x.dtype
    x = x.astype(jnp.float32)
    normed = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    scale = weight + 1.0 if plus_one else weight
    return (normed * scale).astype(orig_dtype)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., seq, heads, head_dim]; positions: [..., seq]."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)                     # [hd/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]                  # [..., seq, 1, hd/2]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------------ params

def _dense(key: jax.Array, shape: tuple[int, ...], fan_in: int,
           dtype: jnp.dtype) -> jax.Array:
    return (jax.random.normal(key, shape, dtype=jnp.float32)
            * (1.0 / math.sqrt(fan_in))).astype(dtype)


# ------------------------------------------------ what the engine looks up
# (models/__init__.py: the names every decoder family gives)

STEP_AUX = False    # step functions return (logits, kv), no counts beside them
STEP_KIND = "token"  # a decode step yields one token a row (models/__init__.py)


def layer_kind(config: LlamaConfig, layer: int) -> str:
    """Every layer of this family has the same tree."""
    return "block"


def prefill_impl(impl: str, mesh, seq: int, config: LlamaConfig,
                 itemsize: int = 2) -> str:
    return select_prefill_attention(impl, mesh, seq, config.head_dim,
                                    config.n_kv_heads, itemsize)


def prefill_unit(mesh, config: LlamaConfig) -> int:
    """Tokens a dense prefill's length must be a whole number of to run the
    kernels its mesh gives it: the flash kernel's block on a TPU (any other
    length falls to the S x S reference there), any length off one."""
    return FLASH_BLOCK if on_tpu(mesh) else 1


def paged_impl(mesh, config: LlamaConfig, kv: PagedKVState) -> str:
    return select_paged_attention(mesh, config.head_dim, kv.page_size,
                                  config.n_kv_heads, kv.quantized)


def refusals(config: LlamaConfig, engine_config, mesh,
             tiers: bool) -> list[str]:
    """Engine settings the family cannot serve (none beyond the engine's own
    checks)."""
    return []


def init_layer(config: LlamaConfig, key: jax.Array,
               dtype: jnp.dtype = jnp.bfloat16,
               kind: str = "block") -> dict[str, Any]:
    """One decoder layer's random weights. Every layer has the same shapes,
    so a caller that jits this compiles it once (engine._init_params)."""
    hd = config.head_dim
    k = jax.random.split(key, 7)
    layer = {
        "attn_norm": jnp.ones((config.dim,), dtype=jnp.float32),
        "wq": _dense(k[0], (config.dim, config.n_heads * hd), config.dim, dtype),
        "wk": _dense(k[1], (config.dim, config.n_kv_heads * hd), config.dim, dtype),
        "wv": _dense(k[2], (config.dim, config.n_kv_heads * hd), config.dim, dtype),
        "wo": _dense(k[3], (config.n_heads * hd, config.dim),
                     config.n_heads * hd, dtype),
        "ffn_norm": jnp.ones((config.dim,), dtype=jnp.float32),
    }
    if config.n_experts:  # Mixtral: stacked expert FFN + router
        ek = jax.random.split(k[4], 3)
        E = config.n_experts
        layer["router"] = _dense(k[5], (config.dim, E), config.dim, dtype)
        layer["w1"] = _dense(ek[0], (E, config.dim, config.ffn_hidden),
                             config.dim, dtype)
        layer["w3"] = _dense(ek[1], (E, config.dim, config.ffn_hidden),
                             config.dim, dtype)
        layer["w2"] = _dense(ek[2], (E, config.ffn_hidden, config.dim),
                             config.ffn_hidden, dtype)
    else:
        layer["w1"] = _dense(k[4], (config.dim, config.ffn_hidden),
                             config.dim, dtype)
        layer["w3"] = _dense(k[5], (config.dim, config.ffn_hidden),
                             config.dim, dtype)
        layer["w2"] = _dense(k[6], (config.ffn_hidden, config.dim),
                             config.ffn_hidden, dtype)
    if config.attn_bias:  # Qwen2-style q/k/v projection biases
        layer["bq"] = jnp.zeros((config.n_heads * hd,), dtype=dtype)
        layer["bk"] = jnp.zeros((config.n_kv_heads * hd,), dtype=dtype)
        layer["bv"] = jnp.zeros((config.n_kv_heads * hd,), dtype=dtype)
    return layer


def init_trunk(config: LlamaConfig, embed_key: jax.Array,
               head_key: jax.Array,
               dtype: jnp.dtype = jnp.bfloat16) -> dict[str, Any]:
    """Everything outside the layer stack: embedding, final norm, lm head."""
    trunk = {
        "embed": _dense(embed_key, (config.vocab_size, config.dim),
                        config.dim, dtype),
        "final_norm": jnp.ones((config.dim,), dtype=jnp.float32),
    }
    if not config.tie_embeddings:
        trunk["lm_head"] = _dense(head_key, (config.dim, config.vocab_size),
                                  config.dim, dtype)
    return trunk


def init_keys(config: LlamaConfig, key: jax.Array) -> jax.Array:
    """[n_layers + 2] keys: one per layer, then the embedding's and the lm
    head's — the one derivation every init path shares, so a model built
    layer by layer has the weights of one built whole."""
    return jax.random.split(key, config.n_layers + 2)


def init_params(config: LlamaConfig, key: jax.Array,
                dtype: jnp.dtype = jnp.bfloat16) -> dict[str, Any]:
    keys = init_keys(config, key)
    params = init_trunk(config, keys[-2], keys[-1], dtype)
    params["layers"] = [init_layer(config, keys[i], dtype)
                        for i in range(config.n_layers)]
    return params


def params_logical(config: LlamaConfig) -> dict[str, Any]:
    """Logical sharding names matching init_params' tree."""
    layer = {
        "attn_norm": "replicated",
        "wq": "attn_qkv", "wk": "attn_qkv", "wv": "attn_qkv",
        "wo": "attn_out",
        "ffn_norm": "replicated",
    }
    if config.n_experts:
        layer.update({"router": "replicated", "w1": "moe_up",
                      "w3": "moe_up", "w2": "moe_down"})
    else:
        layer.update({"w1": "ffn_up", "w3": "ffn_up", "w2": "ffn_down"})
    if config.attn_bias:
        layer.update({"bq": "replicated", "bk": "replicated",
                      "bv": "replicated"})
    tree = {
        "embed": "vocab_in",
        "layers": [dict(layer) for _ in range(config.n_layers)],
        "final_norm": "replicated",
    }
    if not config.tie_embeddings:
        tree["lm_head"] = "vocab_out"
    return tree


def param_count(config: LlamaConfig) -> int:
    hd = config.head_dim
    if config.n_experts:
        ffn = (config.n_experts * 3 * config.dim * config.ffn_hidden
               + config.dim * config.n_experts)   # experts + router
    else:
        ffn = 3 * config.dim * config.ffn_hidden
    per_layer = (config.dim * (config.n_heads + 2 * config.n_kv_heads) * hd
                 + config.n_heads * hd * config.dim
                 + ffn + 2 * config.dim)
    if config.attn_bias:
        per_layer += (config.n_heads + 2 * config.n_kv_heads) * hd
    embeddings = config.vocab_size * config.dim * (
        1 if config.tie_embeddings else 2)
    return embeddings + config.dim + config.n_layers * per_layer


def lm_logits(params: dict[str, Any], x: jax.Array) -> jax.Array:
    """Project hidden states to vocab logits; tied models reuse embed.T
    (sharded vocab-out either way — embed is vocab-in, so the transpose
    keeps the vocab dim on the ``model`` axis). Quantized heads apply
    their per-vocab-channel scales to the OUTPUT, never materializing a
    dequantized table (quantize.py)."""
    head = params.get("lm_head")
    if head is None:
        return qmm_t(x, params["embed"]).astype(jnp.float32)
    return qmm(x, head).astype(jnp.float32)


# ----------------------------------------------------------------------- forward

def _attention_block(layer: dict[str, Any], config: LlamaConfig, x: jax.Array,
                     positions: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Project to q,k,v with RoPE. x: [B,S,D] -> q [B,S,H,hd], k/v [B,S,KV,hd]."""
    B, S, _ = x.shape
    hd = config.head_dim
    q = qmm(x, layer["wq"])
    k = qmm(x, layer["wk"])
    v = qmm(x, layer["wv"])
    if "bq" in layer:  # static at trace time (pytree structure)
        q, k, v = q + layer["bq"], k + layer["bk"], v + layer["bv"]
    q = q.reshape(B, S, config.n_heads, hd)
    k = k.reshape(B, S, config.n_kv_heads, hd)
    v = v.reshape(B, S, config.n_kv_heads, hd)
    q = apply_rope(q, positions, config.rope_theta)
    k = apply_rope(k, positions, config.rope_theta)
    return q, k, v


def _ffn(layer: dict[str, Any], x: jax.Array,
         act: str = "silu") -> jax.Array:
    gate = qmm(x, layer["w1"])
    gate = (jax.nn.gelu(gate, approximate=True) if act == "gelu"
            else jax.nn.silu(gate))  # GeGLU (Gemma) vs SwiGLU
    return qmm(gate * qmm(x, layer["w3"]), layer["w2"])


def expert_block(config: LlamaConfig, tokens: int,
                 dtype: Any = jnp.bfloat16) -> int:
    """The grouped kernel's row-block for a step of ``tokens`` tokens: the
    power of two that holds an expert's mean share of the step's pairs
    (T·k / E), no smaller than the sublane tile of the activations (16 rows
    of bfloat16, 8 of float32) and no larger than ``config.moe_block``, which
    stands where it is under the tile (the tiny test configurations). A step
    of T·k >= E·moe_block pairs gets ``moe_block`` itself."""
    tile = 32 // jnp.dtype(dtype).itemsize
    share = -(-tokens * config.moe_top_k // config.n_experts)
    return min(config.moe_block, max(tile, 1 << (share - 1).bit_length()))


def expert_path(config: LlamaConfig, mesh, tokens: int,
                dtype: Any = jnp.bfloat16) -> str | None:
    """Which formulation the expert FFN of a step of ``tokens`` tokens
    traces, from what is visible before tracing — ``"grouped"`` (each
    token's chosen experts only, ops/grouped_moe.py, at the row-block
    :func:`expert_block` gives the step), ``"scan"`` (every expert over
    every token, gate-masked: parallel/moe.py) — or None for a model without
    a router. The engine counts its steps by the same call.

    The rule is one of ROWS, a function of the step's shape alone (T, k, E,
    ``moe_block``, the activations' dtype): grouped row-blocks run at most
    T·k + E·b rows, the scan E·T.
    - A wide step, T·k >= E·moe_block (prefills, chunk rounds, wide history
      suffixes), is grouped at b = ``moe_block``.
    - A narrower step is grouped at b = b(T) when its padded rows are at
      most a quarter of the scan's: T·k + E·b(T) <= E·T / 4. That needs many
      small experts: a block step of 128 x top-8 (128 tokens: 1024 + 128·16
      rows against 16384) clears it, and there an expert iteration of the
      scan is NOT at its floor, because an int8 expert's matmuls (bound by
      pushing its 4.7 MB of weights through the MXU, whatever the rows) take
      as long as its weight read and the two serialise, while the kernel's
      pipeline fetches the next expert's tiles under this one's matmuls and
      never reads an expert no live token chose (on a v5e 6.6 us a live
      expert against the scan's 16 an expert: PERF.md §6, PR 38). A Mixtral
      decode or verify step (8 x top-2: T·k alone is E·T / 4) never clears
      it, and for it the scan is at its floor anyway — a step reads every
      expert's weights once, far longer than its matmuls.
    The row-block KERNEL runs where the caller's mesh says one device holds
    the whole stacks: it is not wrapped in shard_map, so on a ``model`` axis
    wider than one device XLA would gather the sharded stacks to every chip,
    and it has no gradient rule, so a caller that names no mesh (training,
    the pipeline stages) keeps the scan too."""
    if not config.n_experts:
        return None
    pairs, experts = tokens * config.moe_top_k, config.n_experts
    rows = pairs + experts * expert_block(config, tokens, dtype)
    pays = (pairs >= experts * config.moe_block
            or 4 * rows <= experts * tokens)
    whole = mesh is not None and mesh.shape.get("model", 1) == 1
    if (not config.moe_impl.startswith("grouped") or not pays
            or (config.moe_impl == "grouped_pallas" and not whole)):
        return "scan"
    return "grouped"


def routed_experts(stacks: dict[str, Any], config, flat: jax.Array,
                   ids: jax.Array, weights: jax.Array, mesh=None,
                   valid: jax.Array | None = None,
                   rule=expert_path,
                   held: tuple[int, int] | None = None) -> jax.Array:
    """The routed experts' weighted sum for routing CHOICES, however a router
    made them: flat [T, D], ids [T, k] int32 into the stacks ``w1``/``w3``
    [E, D, F] and ``w2`` [E, F, D], weights [T, k] float32 (final: whatever
    normalising and scaling the router does is done) -> [T, D]. The softmax
    router of :func:`_ffn_block` and a family's own router (sigmoid scores, a
    correction bias) feed the same two formulations through it, picked by
    ``rule`` from the step's shape (the trunk's :func:`expert_path`, or the
    calling family's own: the one the engine counts that family's steps by)
    and run at :func:`expert_block`'s row-block. ``valid`` (T entries): False
    marks padding and idle rows; the grouped path gives their pairs no row and
    zero output, the scan computes them like any token, and nothing reads
    either. ``held`` (lo, hi): the stacks are experts ``[lo, hi)`` of the
    ``config.n_experts`` the router chose among (one chip's share of an
    expert-parallel layer). A pair on an expert held elsewhere gets no row of
    the plan and a zero gate in the scan, and adds nothing here; the
    row-block still follows an expert's share of ALL the pairs
    (:func:`expert_block` reads the published count), and the weighted rows
    are scatter-added to their tokens, since most pairs have none."""
    T = flat.shape[0]
    E = config.n_experts if held is None else held[1] - held[0]
    if held is not None:
        ids = ids - held[0]                # outside [0, E): held elsewhere
    if rule(config, mesh, T, flat.dtype) == "grouped":
        # the kernel interprets off-TPU (the caller's mesh says which) so
        # the code path exists everywhere
        from ..ops.grouped_moe import experts_grouped, plan_sorted_blocks
        if valid is not None:
            ids = jnp.where(valid.reshape(-1, 1), ids, E)
        block = expert_block(config, T, flat.dtype)
        use_pallas = config.moe_impl == "grouped_pallas"
        return experts_grouped(
            stacks, flat, plan_sorted_blocks(ids, weights, E, block),
            act=config.hidden_act, impl="pallas" if use_pallas else "xla",
            block=block, interpret=use_pallas and not on_tpu(mesh),
            gather_back=held is None)
    from ..parallel.moe import expert_scan
    gates = jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32)
                    * weights[:, :, None], axis=1)               # [T, E]
    return expert_scan(stacks, flat, gates.astype(flat.dtype),
                       config.hidden_act)


def _ffn_block(layer: dict[str, Any], config: LlamaConfig,
               x: jax.Array, mesh=None,
               valid: jax.Array | None = None) -> jax.Array:
    """Dense SwiGLU/GeGLU, or top-k routed MoE when the layer carries a
    router (Mixtral family, and the block-diffusion family that imports this
    block as its own).

    Both serving formulations are DROP-FREE and compute the same per-token
    function (:func:`expert_path` says which a step takes, and
    :func:`expert_block` the grouped one's row-block: both follow the step's
    shape): capacity drops make a layer's output a function of the BATCH
    SHAPE — a token dropped in an 11-token prefill but kept in a 1-token
    decode would break the incremental-decode invariant (prefill + decode
    must equal one long prefill). EP fleets with an 'expert' mesh axis use
    moe_ffn's capacity dispatch instead (all_to_all lowering, Switch drop
    policy).

    ``valid`` [B, S]: False marks the bucket's padding tokens and the idle
    rows of a decode-width dispatch. The grouped path gives their pairs no
    row and their output is zero; the scan computes them like any token.
    Nothing reads either (no KV write, no sample)."""
    if "router" not in layer:
        return _ffn(layer, x, config.hidden_act)
    from ..parallel.moe import MoEConfig, moe_ffn_dense_mask

    moe_cfg = MoEConfig(dim=config.dim, n_experts=config.n_experts,
                        expert_hidden=config.ffn_hidden,
                        top_k=config.moe_top_k)
    moe_params = {k: layer[k] for k in ("router", "w1", "w3", "w2")}
    tokens = x.shape[0] * x.shape[1]
    if expert_path(config, mesh, tokens, x.dtype) == "grouped":
        # the softmax router's choices into the formulation every router
        # feeds (:func:`routed_experts`)
        from ..ops.grouped_moe import top_k_gates
        from ..parallel.moe import router_probs
        flat = x.reshape(-1, x.shape[-1])
        ids, gates = top_k_gates(router_probs(layer["router"], flat),
                                 config.moe_top_k)
        return routed_experts(moe_params, config, flat, ids, gates, mesh,
                              valid).reshape(x.shape)
    # the scan's gates straight from the probabilities (the program this
    # family's decode steps have always been); routed_experts' scan branch
    # builds the same [T, E] from ids and weights for any other router
    return moe_ffn_dense_mask(moe_params, x, moe_cfg, act=config.hidden_act)


def prefill(params: dict[str, Any], config: LlamaConfig, tokens: jax.Array,
            positions: jax.Array, kv: PagedKVState, slot_ids: jax.Array,
            attn_impl: str = "reference", mesh=None,
            last_idx: jax.Array | None = None) -> tuple[jax.Array, PagedKVState]:
    """Full-sequence forward writing KV into the paged cache.

    tokens/positions: [B, S]; slot_ids: [B] row into the block table.
    ``attn_impl``: reference | pallas, or the sequence-parallel paths
    (ring/ulysses) for long-context prefill — those and a TP-sharded
    pallas call need ``mesh`` (SURVEY.md §5.7).
    ``last_idx`` ([B], optional): project ONLY those positions through the
    lm head, returning [B, vocab] — serving needs one next-token
    distribution per row, and materializing [B, S, vocab] f32 is S x the
    FLOPs and memory (a 2048-bucket Llama-3 prefill would allocate >4 GB
    of logits on a 16 GB chip). Training/tests omit it for full logits.
    Returns (logits [B, S, vocab] or [B, vocab] fp32, updated kv state).
    """
    x = embed_rows(params["embed"], tokens, config.embed_multiplier)  # [B,S,D]
    mask_valid = positions >= 0  # padding has position -1
    safe_positions = jnp.maximum(positions, 0)
    for idx, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"], config.norm_eps, config.norm_plus_one)
        q, k, v = _attention_block(layer, config, h, safe_positions)
        kv = write_prefill_kv(kv, idx, k, v, slot_ids, safe_positions, mask_valid)
        attn = causal_attention(q, k, v, mask_valid, impl=attn_impl,
                                mesh=mesh)  # [B,S,H,hd]
        x = x + qmm(attn.reshape(*attn.shape[:2], -1), layer["wo"])
        h = rms_norm(x, layer["ffn_norm"], config.norm_eps, config.norm_plus_one)
        x = x + _ffn_block(layer, config, h, mesh, mask_valid)
    x = rms_norm(x, params["final_norm"], config.norm_eps, config.norm_plus_one)
    if last_idx is not None:
        x = x[jnp.arange(x.shape[0]), last_idx]  # [B, D] before the lm head
    logits = lm_logits(params, x)
    return logits, kv


def prefill_with_history(params: dict[str, Any], config: LlamaConfig,
                         tokens: jax.Array, positions: jax.Array,
                         kv: PagedKVState, slot_ids: jax.Array,
                         ctx_pages: int | None = None,
                         last_idx: jax.Array | None = None,
                         paged_impl: str = "gather", mesh=None
                         ) -> tuple[jax.Array, PagedKVState]:
    """Suffix/chunk prefill attending over cached history (prefix-cache
    path — reference analog: the response_cache_by_prompt plugin caches
    whole responses; this caches the KV of shared prompt PREFIXES so only
    each request's suffix pays prefill FLOPs).

    tokens/positions: [B, S] where positions carry ABSOLUTE positions (a
    row whose prompt shares ``hist`` cached tokens starts at position
    ``hist``); padding has position -1. The row's block table must already
    map its history pages. Per-row history lengths may differ freely —
    attention masks on absolute position (cache_pos <= q_pos), so one
    compiled shape serves any mix. ``ctx_pages`` is the STATIC
    context-width bucket (see gather_kv) — without it a prefix-cache hit
    with 40 resident tokens pays attention over the full table width,
    costing MORE than the dense prefill it was meant to save.
    ``paged_impl``: "gather" (jnp reference) or "pallas" (the paged chunk
    kernel, under shard_map over ``mesh``'s model axis when it is wider
    than one device).
    Returns (logits [B,S,V] fp32, kv)."""
    B, S = tokens.shape
    x = embed_rows(params["embed"], tokens, config.embed_multiplier)
    mask_valid = positions >= 0
    safe_positions = jnp.maximum(positions, 0)
    G = config.n_heads // config.n_kv_heads
    # Attention is tiled over S (queries only — the chunk's KV is written
    # first, causality rides absolute positions): the gather reference
    # materializes a [B,KV,G,T,C] f32 score tensor; untiled, a 2048-token
    # chunk against a long resident context is multi-GB per layer (round-2
    # ADVICE medium). The Pallas chunk kernel blocks its own rows in VMEM
    # and takes the same tiles. T divides S because both are powers of two.
    tile = _history_tile(S, G)
    use_pallas = paged_impl == "pallas"
    for idx, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"], config.norm_eps, config.norm_plus_one)
        q, k, v = _attention_block(layer, config, h, safe_positions)
        kv = write_prefill_kv(kv, idx, k, v, slot_ids, safe_positions,
                              mask_valid)
        if not use_pallas:
            keys, values = gather_kv(kv, idx, slot_ids, ctx_pages)
        else:
            tables = kv.block_tables[slot_ids]
            if ctx_pages is not None:
                tables = tables[:, :ctx_pages]
        tiles = []
        for t0 in range(0, S, tile):
            qs = q[:, t0:t0 + tile]
            ps = positions[:, t0:t0 + tile]
            if use_pallas:
                from ..ops.paged_attention import paged_chunk_attention_pallas
                qg = qs.reshape(B, -1, config.n_kv_heads, G, config.head_dim)
                at = paged_chunk_attention_pallas(
                    qg, kv.k_pages, kv.v_pages, tables, ps, layer=idx,
                    k_scales=kv.k_scales, v_scales=kv.v_scales, mesh=mesh)
                at = at.reshape(B, -1, config.n_heads, config.head_dim)
            else:
                at = _history_attention(
                    qs, keys, values, safe_positions[:, t0:t0 + tile],
                    mask_valid[:, t0:t0 + tile], config)
            tiles.append(at)
        attn = tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)
        x = x + qmm(attn.reshape(B, S, -1), layer["wo"])
        h = rms_norm(x, layer["ffn_norm"], config.norm_eps, config.norm_plus_one)
        x = x + _ffn_block(layer, config, h, mesh, mask_valid)
    x = rms_norm(x, params["final_norm"], config.norm_eps, config.norm_plus_one)
    if last_idx is not None:  # serving: one next-token row per request
        x = x[jnp.arange(B), last_idx]
    logits = lm_logits(params, x)
    return logits, kv


def _history_tile(S: int, G: int) -> int:
    """Query-tile width for chunk/history attention: large enough to keep
    the MXU busy, small enough that the gather reference's [B,KV,G,T,C]
    f32 scores stay bounded. S and the returned tile are powers of two,
    so the tile always divides S."""
    tile = max(128, 2048 // max(1, G))
    t = 128
    while t * 2 <= min(tile, S):
        t *= 2
    return min(t, S)


def _history_attention(q: jax.Array, keys: jax.Array, values: jax.Array,
                       positions: jax.Array, valid: jax.Array,
                       config: LlamaConfig,
                       window: int | None = None) -> jax.Array:
    """Chunk queries over the full gathered context (history + chunk).

    q: [B,S,H,hd]; keys/values: [B,C,KV,hd]; positions/valid: [B,S].
    Causality rides absolute position: cache index c (its position in the
    slot's context) attends iff c <= q_position, and under a ``window``
    (static) iff also q_position - c < window. -> [B,S,H,hd]."""
    B, S, H, hd = q.shape
    C = keys.shape[1]
    G = H // config.n_kv_heads
    qg = q.reshape(B, S, config.n_kv_heads, G, hd).astype(jnp.float32)
    kf = keys.astype(jnp.float32)
    scores = jnp.einsum("bskgh,bckh->bkgsc", qg, kf) / math.sqrt(hd)
    cache_pos = jnp.arange(C)[None, None, :]                 # [1,1,C]
    ok = (cache_pos <= positions[:, :, None]) & valid[:, :, None]  # [B,S,C]
    if window is not None:
        ok &= cache_pos > positions[:, :, None] - window
    scores = jnp.where(ok[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgsc,bckh->bskgh", probs, values.astype(jnp.float32))
    return out.reshape(B, S, H, hd).astype(values.dtype)


def decode_step(params: dict[str, Any], config: LlamaConfig, tokens: jax.Array,
                positions: jax.Array, kv: PagedKVState, slot_ids: jax.Array,
                seq_lens: jax.Array, ctx_pages: int | None = None,
                write_mask: jax.Array | None = None,
                paged_impl: str = "gather", mesh=None
                ) -> tuple[jax.Array, PagedKVState]:
    """One decode step over the paged cache.

    tokens: [B] this step's input token per slot; positions: [B];
    slot_ids: [B] block-table rows; seq_lens: [B] tokens already in cache
    (including this one after write); ctx_pages: STATIC context-width
    bucket — attention reads only the first ctx_pages table columns (the
    engine guarantees every active row fits); write_mask: [B] bool —
    False rows write to the trash page (a slot can be allocated but NOT
    decoding, e.g. mid-chunk-prefill, and must never be written by
    decode); ``paged_impl``/``mesh`` as in :func:`prefill_with_history`.
    Returns (logits [B,V], kv).
    """
    B = tokens.shape[0]
    x = embed_rows(params["embed"], tokens, config.embed_multiplier)[:, None, :]  # [B,1,D]
    pos = positions[:, None]                 # [B,1]
    use_pallas = paged_impl == "pallas"
    for idx, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"], config.norm_eps, config.norm_plus_one)
        q, k, v = _attention_block(layer, config, h, pos)
        kv = write_decode_kv(kv, idx, k[:, 0], v[:, 0], slot_ids, positions,
                             valid=write_mask)
        if use_pallas:
            from ..ops.paged_attention import paged_decode_attention_pallas
            G = config.n_heads // config.n_kv_heads
            qg = q[:, 0].reshape(B, config.n_kv_heads, G, config.head_dim)
            tables = kv.block_tables[slot_ids]
            if ctx_pages is not None:
                tables = tables[:, :ctx_pages]
            attn = paged_decode_attention_pallas(
                qg, kv.k_pages, kv.v_pages, tables, seq_lens, layer=idx,
                k_scales=kv.k_scales, v_scales=kv.v_scales, mesh=mesh)
            attn = attn.reshape(B, 1, config.n_heads, config.head_dim)
        else:
            keys, values = gather_kv(kv, idx, slot_ids, ctx_pages)
            attn = _paged_decode_attention(q[:, 0], keys, values, seq_lens, config)
        x = x + qmm(attn.reshape(B, 1, -1), layer["wo"])
        h = rms_norm(x, layer["ffn_norm"], config.norm_eps, config.norm_plus_one)
        x = x + _ffn_block(layer, config, h, mesh)
    x = rms_norm(x, params["final_norm"], config.norm_eps, config.norm_plus_one)
    logits = lm_logits(params, x[:, 0])
    return logits, kv


def _paged_decode_attention(q: jax.Array, keys: jax.Array, values: jax.Array,
                            seq_lens: jax.Array, config: LlamaConfig,
                            window: int | None = None) -> jax.Array:
    """q: [B,H,hd]; keys/values: [B,C,KV,hd]; seq_lens: [B] -> [B,1,H,hd].
    Under a ``window`` (static) the query, at position seq_len - 1, sees its
    ``window`` newest keys alone."""
    B, H, hd = q.shape
    C = keys.shape[1]
    group = H // config.n_kv_heads
    qg = q.reshape(B, config.n_kv_heads, group, hd).astype(jnp.float32)
    kf = keys.astype(jnp.float32)
    vf = values.astype(jnp.float32)
    scores = jnp.einsum("bkgh,bckh->bkgc", qg, kf) / math.sqrt(hd)
    valid = jnp.arange(C)[None, :] < seq_lens[:, None]        # [B,C]
    if window is not None:
        valid &= jnp.arange(C)[None, :] >= seq_lens[:, None] - window
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgc,bckh->bkgh", probs, vf)
    return out.reshape(B, 1, H, hd).astype(values.dtype)
